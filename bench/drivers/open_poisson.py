"""Open-loop serving: independent requests at a fixed rate.

Arrivals are set at set-up: ``rate_per_s x seconds`` exponential gaps,
drawn once from a fixed stream and scaled to fill the window exactly,
put in an order drawn from the seed.  Every seed therefore offers the
same number of requests with the same gaps, and only their order moves.
Each is submitted from this thread when it falls due, whatever the
engine is doing.  A request's latency runs from when it was due to when
it completed; one never served counts as waiting until the wait for it
ends.  ``serve_p95_ms`` is the 95th percentile over every
request due in the window, ``served_per_s`` the requests served in the
window over the window.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import serving


def run(ctx) -> dict:
    server = ctx.family.Server(ctx.cfg, ctx.traffic, ctx.seed)
    eng = serving.engine(ctx, server)
    n = int(round(float(ctx.traffic["rate_per_s"]) * ctx.seconds))
    gaps = np.random.default_rng(0).exponential(size=n)
    gaps *= ctx.seconds / gaps.sum()
    due = np.cumsum(np.random.default_rng([ctx.seed, 4]).permutation(gaps))
    due[-1] = min(due[-1], ctx.seconds)
    reqs = [serving.TimedRequest(i, features=server.features(i))
            for i in range(len(due))]
    lag = np.zeros(len(due))
    before = len(eng.slo.tick_latencies)
    eng.start()
    try:
        ctx.start_window()
        t0 = time.perf_counter()
        i = 0
        while i < len(due):
            now = time.perf_counter() - t0
            if due[i] <= now:
                eng.submit(reqs[i])
                lag[i] = time.perf_counter() - t0 - due[i]
                i += 1
            else:
                time.sleep(due[i] - now)
        rest = t0 + ctx.seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        close = t0 + ctx.seconds
        ticks = serving.tick_window(eng, before)
        ctx.end_window()
        serving.settle(reqs, close)
    finally:
        eng.stop(drain=False)
    done = np.array([r.done and not r.failed for r in reqs])
    end = np.array([r.completed_at if d else close + serving.GRACE_S
                    for r, d in zip(reqs, done)])
    latency = end - t0 - due
    in_window = int(np.sum(done & (end <= close)))
    ctx.observe(window_s=ctx.seconds, served_per_s=in_window / ctx.seconds,
                lag_p95_s=float(np.percentile(lag, 95)), **ticks,
                build_s=server.build_s, work=server.work, model=server.model)
    return {"end_to_end": {
                "serve_p95_ms": float(np.percentile(latency, 95)) * 1e3,
                "served_per_s": in_window / ctx.seconds},
            "readings": serving.readings(ctx, server, reqs),
            "attempted": len(reqs), "failed": int(np.sum(~done))}
