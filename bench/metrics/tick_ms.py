"""Mean host time of a serving tick in the window: the engine's own
``SLOTracker.tick_latencies`` (the host clock around one device step,
which ends in a copy to the host) summed over the window's ticks, over
their number, in milliseconds."""


def read(ctx, metric):
    ticks = ctx.observed.get("ticks")
    return 1e3 * ctx.observed["tick_s"] / ticks if ticks else None
