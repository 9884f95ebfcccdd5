"""The benchmark's own tests: on the CPU, at tiny sizes, kernels in
interpret mode.  Nothing here touches a TPU."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")
