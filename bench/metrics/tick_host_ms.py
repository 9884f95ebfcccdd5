"""Mean host work of a serving tick in the traced window: the union of
the engine's phase spans other than ``engine.fetch`` over the ticks that
fetched a result, in milliseconds.  It is wall time on the dispatch
thread, waits for the interpreter lock included.  Nothing is returned
where the trace holds no ``engine.fetch`` span."""

import spans


def read(ctx, metric):
    t = spans.ticks(spans.of_cell(ctx))
    return None if t is None else 1e3 * t.host_s / t.n
