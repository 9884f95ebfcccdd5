"""Closed backlog: the queue is kept deeper than the engine's slots.

Requests are submitted a panel at a time whenever fewer than the
traffic's ``queue_depth`` wait, so every tick finds a full panel.
``served_per_s`` is the requests served in the window over the window:
the engine's capacity.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import serving


def run(ctx) -> dict:
    server = ctx.family.Server(ctx.cfg, ctx.traffic, ctx.seed)
    eng = serving.engine(ctx, server)
    slots = int(ctx.traffic["slots"])
    depth = int(ctx.traffic["queue_depth"])
    reqs = []
    before = len(eng.slo.tick_latencies)
    eng.start()
    try:
        ctx.start_window()
        t0 = time.perf_counter()
        close = t0 + ctx.seconds
        while time.perf_counter() < close:
            if eng.queue_depth < depth:
                for _ in range(slots):
                    r = serving.TimedRequest(
                        len(reqs), features=server.features(len(reqs)))
                    eng.submit(r)
                    reqs.append(r)
            else:
                time.sleep(2e-4)
        ticks = serving.tick_window(eng, before)
        ctx.end_window()
        serving.settle(reqs, close)
    finally:
        eng.stop(drain=False)
    done = np.array([r.done and not r.failed for r in reqs])
    in_window = sum(1 for r, d in zip(reqs, done)
                    if d and r.completed_at <= close)
    ctx.observe(window_s=ctx.seconds, served_per_s=in_window / ctx.seconds,
                **ticks, build_s=server.build_s, work=server.work,
                model=server.model)
    return {"end_to_end": {"served_per_s": in_window / ctx.seconds},
            "readings": serving.readings(ctx, server, reqs),
            "attempted": len(reqs), "failed": int(np.sum(~done))}
