#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name.  ``BENCHMARK.json`` (at
the root of the checkout) names the cell's configuration, traffic mix and
metrics; the configuration's file names its family
(``bench/families/<family>.py``), the traffic's file
(``bench/traffic/<mix>.json``) its driver (``bench/drivers/<driver>.py``),
and each per-layer metric is read by ``bench/metrics/<name>.py`` (or, for
a name with a suffix such as ``tick_ms.serve``, by the file of its first
part).  The limits of the comparison that decides ``correct`` are in
``bench/limits/<cell>.json``.

One process: it exits non-zero, printing no result, when JAX finds no TPU
or fewer chips than the cell asks for.  Set-up (weights, data, compiling
every shape the cell uses) is timed as ``setup_s``; then the driver
measures for ``--seconds``.  With ``--trace 1`` the window is traced by
the profiler (for at most the traffic's ``trace_seconds``) and the result
carries the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time
import traceback

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's clock."""
    try:
        import psutil

        return time.time() - psutil.Process().create_time()
    except Exception:  # noqa: BLE001 - no psutil: count from this import
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, under a name of its own
    (``bench/trace.py`` must not stand in for the standard library's)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind.strip('.')}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, else the
    file of the name's first dotted part."""
    for stem in (name, name.split(".")[0]):
        if (BENCH / "metrics" / f"{stem}.py").is_file():
            return load_module("metrics", stem)
    raise FileNotFoundError(f"no reader for metric {name!r}")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, spec: dict | None = None):
        spec = spec or load_json(ROOT / "BENCHMARK.json")
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = by_name[name]
        conf = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.cfg = load_json(ROOT / conf["file"])
        self.traffic = load_json(BENCH / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.limits = load_json(BENCH / "limits" / f"{name}.json")
        self.family = importlib.import_module(
            f"families.{self.cfg['family']}")
        self.driver = load_module("drivers", self.traffic["driver"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]


class Context:
    """What a driver is handed, and what it leaves for the readers."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.cfg, self.traffic, self.family = (cell.cfg, cell.traffic,
                                               cell.family)
        self.seconds = (min(seconds, float(cell.traffic["trace_seconds"]))
                        if trace else seconds)
        self.trace_dir = ROOT / ".bench_trace" / cell.name
        self.setup_s = None
        self.observed: dict = {}
        self.memory_peak_bytes = None

    def start_window(self) -> None:
        """Set-up ends here; with tracing, the profiler starts.  What set-up
        left alive (weights, pre-drawn requests) is moved out of the cyclic
        collector's reach, so that its passes over the benchmark's own
        objects do not stall the window."""
        import jax

        gc.collect()
        gc.freeze()
        self.setup_s = process_age_s()
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)

    def end_window(self) -> None:
        """The window has closed: stop the profiler, read peak memory."""
        import jax

        if self.trace:
            jax.profiler.stop_trace()
        stats = [d.memory_stats() or {} for d in self.devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        self.memory_peak_bytes = max((p for p in peaks if p is not None),
                                     default=None)

    def observe(self, **kw) -> None:
        self.observed.update(kw)

    def devices(self):
        import jax

        return jax.devices()[: int(self.cell.entry["chips"])]


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Every number compared, beside its limit; correct when each is a
    finite number within it."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = readings.get(name)
        good = _finite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, spec: dict | None = None,
             cell: Cell | None = None) -> dict:
    """Run one cell; returns the result object (without printing)."""
    import jax

    cell = cell or Cell(name, spec)
    devices = jax.devices()
    want = int(cell.entry["chips"])
    if require_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"bench: needs a TPU, JAX found "
                             f"{devices[0].platform!r}")
        if len(devices) < want:
            raise SystemExit(f"bench: cell {name} asks for {want} chips, "
                             f"JAX found {len(devices)}")
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    ctx = Context(cell, seed, seconds, trace)
    out = cell.driver.run(ctx)
    correct, checks = judge(out["readings"], cell.limits)

    used = devices[:want]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        red = load_module(".", "trace").reduce_trace(
            ctx.trace_dir, window_s=ctx.observed["window_s"],
            n_devices=len(used))
        ctx.observe(trace=red)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(ctx, m)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = red.breakdown()
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
