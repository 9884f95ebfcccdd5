"""Benchmark harness entry point: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the same rows as
a JSON document for trajectory tracking: by default (kernel suites) to
``BENCH_kernels.json`` at the repo root — the committed copy is the
previous run the CI smoke job diffs fresh numbers against
(``benchmarks.check_gate --prev``) before uploading the new document.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --only fig15
    BENCH_SMOKE=1 PYTHONPATH=src python -m benchmarks.run \
        --only kernel --json BENCH_new.json            # CI tiny config
    PYTHONPATH=src python -m benchmarks.run --suite kernels --json -  # no file

The platform comes from the environment (``JAX_PLATFORMS``): on a TPU
the kernels compile, on the CPU they run in the Pallas interpreter.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

# BENCH_HOST_DEVICES=8 forces a multi-device host platform so the sharded
# benchmark rows (tiled_apply_sharded_n64) get a real mesh; must be set
# before jax initializes its backends
if os.environ.get("BENCH_HOST_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
        f"device_count={int(os.environ['BENCH_HOST_DEVICES'])}").strip()

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_JSON = os.path.join(_REPO_ROOT, "BENCH_kernels.json")


def _git_sha() -> str | None:
    """Commit the benchmarked tree came from, so uploaded ``BENCH_*.json``
    artifacts are traceable in the trajectory diff.  Prefers the CI-pinned
    ``GITHUB_SHA`` (checkouts can be detached/shallow), falls back to
    ``git rev-parse``; ``None`` when neither is available (tarball)."""
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _run_context() -> dict:
    """CI / pytest provenance for the JSON header (empty values dropped)."""
    ctx = {
        "ci": os.environ.get("CI"),
        "github_run_id": os.environ.get("GITHUB_RUN_ID"),
        "github_run_attempt": os.environ.get("GITHUB_RUN_ATTEMPT"),
        "github_workflow": os.environ.get("GITHUB_WORKFLOW"),
        "github_job": os.environ.get("GITHUB_JOB"),
        "github_ref": os.environ.get("GITHUB_REF"),
        "pytest": os.environ.get("PYTEST_CURRENT_TEST"),
    }
    return {k: v for k, v in ctx.items() if v}


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    entry: dict = {"name": name, "derived": derived}
    entry["us_per_call"] = float(us) if us else None
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark function names")
    ap.add_argument("--suite", default="all",
                    choices=("all", "paper", "kernels"),
                    help="benchmark module to run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the rows as a JSON document; defaults to "
                         "BENCH_kernels.json at the repo root when the "
                         "kernels suite runs; '-' disables the file")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    if args.json is None and args.suite in ("all", "kernels"):
        args.json = DEFAULT_JSON
    if args.json == "-":
        args.json = None

    from benchmarks import bench_kernels, bench_paper
    from benchmarks.common import SMOKE

    benches = []
    if args.suite in ("all", "paper"):
        benches += list(bench_paper.ALL)
    if args.suite in ("all", "kernels"):
        benches += list(bench_kernels.ALL)
    if args.only:
        benches = [b for b in benches if args.only in b.__name__]
        if not benches:
            print(f"no benchmark matches {args.only!r}", file=sys.stderr)
            return 1

    print("name,us_per_call,derived")
    failures = 0
    entries: list[dict] = []
    for bench in benches:
        t0 = time.time()
        try:
            for line in bench():
                print(line, flush=True)
                entries.append(_parse_row(line))
        except AssertionError as e:
            failures += 1
            print(f"{bench.__name__},,FAILED_ASSERT:{e}", flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"{bench.__name__},,ERROR:{type(e).__name__}:{e}",
                  flush=True)
        dt = time.time() - t0
        print(f"# {bench.__name__} done in {dt:.1f}s", file=sys.stderr)

    if args.json:
        doc = {
            "schema": 1,
            "smoke": SMOKE,
            "git_sha": _git_sha(),
            "context": _run_context(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "platform": {
                "python": platform.python_version(),
                "machine": platform.machine(),
                "jax": jax.__version__,
                "backend": jax.default_backend(),
            },
            "failures": failures,
            "rows": entries,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {len(entries)} rows to {args.json}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
