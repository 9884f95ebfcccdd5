"""Mean time a request waited in the queue before it took a slot: the
``wait_s`` stats of the traced window's ``engine.admit`` spans summed,
over their summed ``n``, in milliseconds.  Nothing is returned where no
span of the window admitted a request."""

import spans


def read(ctx, metric):
    admits = [s.stats for s in spans.of_cell(ctx)
              if s.name == "engine.admit"]
    n = sum(int(a.get("n", 0)) for a in admits)
    return 1e3 * sum(a.get("wait_s", 0.0) for a in admits) / n if n else None
