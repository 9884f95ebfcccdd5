"""The four-chip deep grid on four forced host devices: the sharded
chain of row-blocked kernels against the plain reference, and a tiny
``deep1024_l4_t16_train_sharded`` cell end to end.  Each runs in a
subprocess, since the device count is fixed when JAX starts."""

import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.kernels import givens_mesh, ops
from families import deepgrid as base
import run

TINY = {"dim": 32, "tile": 8, "depth": 2}
"""

# L = 2 layers of a 4 x 4 grid of 8x8 Reck tiles on a 2 x 2 mesh: each
# chip's layer call holds 2 tile rows, run one row block at a time.
_PARITY = _PRELUDE + r"""
givens_mesh.COEF_VMEM_BUDGET = 0          # one tile row a block
cell = run.Cell("deep1024_l4_t16_train_sharded")
cfg = dict(cell.cfg, **TINY)
params, keys = base.weights(cfg, 2**31 + 11)
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("rows", "data"))
hw, plans = base.program_hardware(cfg), base.plans(cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg["dim"]))   # ragged
t = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), x.shape))
ref_fwd = base.reference_forward(cfg)


def prog_loss(p):
    y = ops.deep_apply({**p, **keys}, x, n=cfg["tile"], plans=plans,
                       hardware=hw, mesh=mesh)
    return jnp.mean((y - t) ** 2), y


def ref_loss(p):
    y = ref_fwd(p, keys, x)
    return jnp.mean((y - t) ** 2), y


(lp, yp), gp = jax.value_and_grad(prog_loss, has_aux=True)(params)
(lr, yr), gr = jax.value_and_grad(ref_loss, has_aux=True)(params)
out = {"rows": ops.DEEP_BLOCKS["deep_apply_sharded"],
       "fwd": float(jnp.max(jnp.abs(yp - yr)) / jnp.max(jnp.abs(yr))),
       "loss": float(abs(lp - lr) / lr),
       "grad": {k: float(jnp.linalg.norm(gp[k] - gr[k])
                         / jnp.linalg.norm(gr[k])) for k in gr}}
print("PARITY " + json.dumps(out))
"""

_TINY_CELL = _PRELUDE + r"""
import sys
givens_mesh.COEF_VMEM_BUDGET = 0          # every layer call row-blocked
if "drop_data_psum" in sys.argv:
    # the fault: the coefficient gradients' psum over the batch axis
    # left out, so each chip keeps its own batch shard's share
    psum = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: (
        x if axis_name == "data" else psum(x, axis_name, **kw))
cell = run.Cell("deep1024_l4_t16_train_sharded")
cell.cfg = dict(cell.cfg, **TINY)
cell.traffic = dict(cell.traffic, batch=16, feed_batches=4)
result = run.run_cell(cell.name, 2**31 + 5, 0.5, False, require_chip=False,
                      cell=cell)
result["blocks"] = ops.DEEP_BLOCKS["deep_apply_sharded"]
print("CELL " + json.dumps(result))
"""


def _run(program: str, tag: str, *args: str):
    r = subprocess.run([sys.executable, "-c", program, *args],
                       capture_output=True,
                       text=True, timeout=900, cwd=BENCH.parent,
                       env={"PYTHONPATH": f"{BENCH}:{BENCH.parent / 'src'}",
                            "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    lines = [l for l in r.stdout.splitlines() if l.startswith(tag + " ")]
    assert lines, r.stdout[-4000:] + r.stderr[-4000:]
    import json
    return json.loads(lines[-1][len(tag) + 1:])


def test_sharded_row_blocked_chain_matches_reference():
    """Forward, loss and every parameter's gradient of the sharded chain
    (stacked entry, tile rows over 2 devices and the batch over 2, two
    row blocks of one tile row each) against the plain reference and
    ``jax.grad`` on seeded Table-I weights with the prototype's noise.
    Tolerances: the forward and loss agree to float32 rounding over ~60
    cell rotations a path (the 64-wide serving cells read up to 1.7e-6,
    this chain 1.6e-7); each parameter array's gradient, a sum over 12
    rows of products along every path through it, to the same 1e-5 in
    relative L2 norm (read: up to 4.2e-7)."""
    out = _run(_PARITY, "PARITY")
    assert out["rows"] == [8, 1, 1, 2]        # 6 rows a chip, whole
    assert out["fwd"] <= 1e-5, out
    assert out["loss"] <= 1e-5, out
    assert max(out["grad"].values()) <= 1e-5, out


def test_tiny_sharded_cell_is_correct():
    """The cell at dim 32, tile 8, depth 2, B = 16 through ``run_cell``
    on four host devices, each chip's layer call row-blocked: it trains
    and comes out correct against the reference."""
    result = _run(_TINY_CELL, "CELL")
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4
    assert result["blocks"][2:] == [1, 2]
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def test_tiny_sharded_cell_catches_a_dropped_batch_psum():
    """The same tiny cell with the coefficient gradients' psum over
    ``data`` left out: the forward is untouched, the gradient each chip
    keeps is its own half batch's, and the gradient comparison (of the
    steps taken on offsets) fails it."""
    result = _run(_TINY_CELL, "CELL", "drop_data_psum")
    assert not result["correct"]
    grad = result["checks"]["grad_gap"]
    assert grad["value"] > grad["limit"], result["checks"]
