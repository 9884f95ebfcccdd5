"""What the serving drivers share: the timed request, the engine with
every shape warmed, and the check of what was served."""

from __future__ import annotations

import time

import numpy as np

from repro.serving import Request, ServingEngine

#: how long past the window's close a due request is waited for
GRACE_S = 60.0


class TimedRequest(Request):
    """A request that stamps its completion on the host clock."""

    completed_at: float | None = None

    def _finish(self, failed: bool = False) -> None:
        self.completed_at = time.perf_counter()
        super()._finish(failed)


def engine(ctx, server):
    """The cell's engine, its one panel shape compiled by a full tick."""
    slots = int(ctx.traffic["slots"])
    eng = ServingEngine(server.program, slots=slots, admission="block")
    for i in range(slots):
        eng.submit(TimedRequest(-1 - i, features=server.features(i)))
    eng.run()
    return eng


def settle(reqs, close: float) -> None:
    """Wait for every request, at most ``GRACE_S`` past ``close``."""
    for r in reqs:
        r.wait(timeout=max(0.0, close + GRACE_S - time.perf_counter()))


def readings(ctx, server, reqs) -> dict:
    """Requests never served, and the served rows of a seeded sample
    against the reference."""
    served = [r for r in reqs if r.done and not r.failed]
    rng = np.random.default_rng([ctx.seed, 5])
    k = min(len(served), int(ctx.traffic["check_rows"]))
    pick = sorted(rng.choice(len(served), size=k, replace=False))
    from check import row_gap

    gap = (row_gap(np.stack([served[i].result for i in pick]),
                   server.reference([served[i].rid for i in pick]))
           if k else float("inf"))
    return {"out_gap": gap, "unserved": len(reqs) - len(served)}


def tick_window(eng, before: int) -> dict:
    ticks = eng.slo.tick_latencies[before:]
    return {"tick_s": float(np.sum(ticks)), "ticks": len(ticks)}
