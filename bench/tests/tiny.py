"""Tiny versions of the benchmark's cells, for the CPU tests."""

import run

DEEP = {"dim": 16, "tile": 8, "depth": 2}
DEEP_TRAFFIC = {"batch": 16, "feed_batches": 4, "feature_rows": 256,
                "check_rows": 256, "rate_per_s": 200}
MNIST = {"train_pool": 40, "epoch_samples": 60}


def cell(name: str) -> run.Cell:
    c = run.Cell(name)
    if c.cfg["family"] == "deepgrid":
        c.cfg = dict(c.cfg, **DEEP)
        c.traffic = dict(c.traffic, **DEEP_TRAFFIC)
    else:
        c.cfg = dict(c.cfg, **MNIST)
    return c


def run_tiny(name: str, seed: int = 2**31 + 5, seconds: float = 0.5,
             trace: bool = False) -> dict:
    return run.run_cell(name, seed, seconds, trace, require_chip=False,
                        cell=cell(name))
