"""Mean time a serving tick waited for its result in the traced window:
the summed ``engine.fetch`` spans (the host blocked on the device, then
the copy to the host) over their number, in milliseconds.  Nothing is
returned where the trace holds no such span."""

import spans


def read(ctx, metric):
    t = spans.ticks(spans.of_cell(ctx))
    return None if t is None else 1e3 * t.wait_s / t.n
