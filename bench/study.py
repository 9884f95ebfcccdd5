#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program on many seeds,
and the lower-precision control on a few, at a cell's own sizes.

    python bench/study.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--seconds 3] [--out <file.jsonl>]

Training cells: each seed's set-up (weights, compiled step, its first
steps) and the comparison of those steps with the reference; the control
puts the reference computed in bfloat16 in the program's place.  Serving
cells: each seed is a whole run of the cell with a window of
``--seconds`` at the cell's own load; the control compares the
reference in bfloat16 with the reference on the rows of a seeded sample.
One process; prints one JSON line per reading.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def program(cell, seed: int, seconds: float) -> dict:
    """The program's readings: a training cell's first steps against the
    reference, or a whole serving run with a short window."""
    if cell.traffic["driver"] == "train_steps":
        return cell.family.Trainer(cell.cfg, cell.traffic, seed).readings()
    result = run.run_cell(cell.name, seed, seconds, False, cell=cell)
    return {k: v["value"] for k, v in result["checks"].items()}


def control(cell, seed: int, seconds: float) -> dict:
    """The reference computed in bfloat16, in the program's place."""
    if cell.traffic["driver"] == "train_steps":
        trainer = cell.family.Trainer(cell.cfg, cell.traffic, seed)
        return trainer.control_readings()
    import numpy as np

    from check import row_gap
    from reference import physics

    server = cell.family.Server(cell.cfg, cell.traffic, seed)
    rng = np.random.default_rng([seed, 5])
    rids = rng.choice(len(server.rows), size=int(cell.traffic["check_rows"]),
                      replace=False)
    return {"out_gap": row_gap(server.reference(rids, physics.bf16),
                               server.reference(rids))}


def half_batch(cell, seed: int, seconds: float) -> dict:
    """A training cell's half-batch fault, planted in the reference put
    in the program's place."""
    trainer = cell.family.Trainer(cell.cfg, cell.traffic, seed)
    return trainer.half_batch_readings()


STUDIES = {"program": program, "control": control, "half_batch": half_batch}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--half-batch-seeds", default="",
                    help="training cells: the half-batch fault, planted "
                         "in the reference put in the program's place")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("study: needs a TPU", file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    cell = run.Cell(args.workload)
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    out = open(args.out, "a") if args.out else None
    jobs = [(kind, int(s)) for kind, seeds in
            (("program", args.seeds), ("control", args.control_seeds),
             ("half_batch", args.half_batch_seeds))
            for s in seeds.split(",") if s]
    for kind, seed in jobs:
        t0 = time.perf_counter()
        values = STUDIES[kind](cell, seed, args.seconds)
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           "seconds": time.perf_counter() - t0, **values})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
