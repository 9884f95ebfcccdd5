"""Training traffic: the family's trainer dispatches back to back.

Set-up builds the trainer (weights, data, the compiled step and its first
steps).  The window dispatches until ``--seconds`` have passed, keeping at
most two dispatches in flight, and ends when the last one's output is
ready; ``train_samples_per_s`` is every sample of every dispatch in the
window over the window.
"""

from __future__ import annotations

import collections
import math
import time

import jax
import numpy as np


def run(ctx) -> dict:
    trainer = ctx.family.Trainer(ctx.cfg, ctx.traffic, ctx.seed)
    ctx.start_window()
    t0 = time.perf_counter()
    inflight = collections.deque()
    n = 0
    while True:
        inflight.append(trainer.dispatch())
        n += 1
        if len(inflight) > 2:
            jax.block_until_ready(inflight.popleft())
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    last = np.asarray(jax.block_until_ready(inflight[-1]))
    window = time.perf_counter() - t0
    ctx.end_window()
    samples = n * trainer.samples_per_dispatch
    ctx.observe(window_s=window, samples_per_s=samples / window,
                dispatches=n, work=trainer.work, model=trainer.model)
    readings = trainer.readings()
    bad = int(not all(math.isfinite(v) for v in last.ravel()))
    return {"end_to_end": {"train_samples_per_s": samples / window},
            "readings": readings, "attempted": n, "failed": bad}
