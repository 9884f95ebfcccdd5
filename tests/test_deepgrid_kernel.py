"""Deep tiled-network megakernel validation: the fused L x To x Ti cascade
vs the per-layer ``tiled_apply`` composition (differential,
property-based), mixed Reck/Clements identity-column padding, ragged
batches, degenerate-wrapper parity, schedule/pack memoization, the
``lower_deep`` compile path (placements, parked blank tiles, serving)
and the shard_map scale-out of the deep kernel."""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import decompose, mesh as mesh_lib
from repro.kernels import ops
from repro.kernels.schedule import deep_grid_schedule

jax.config.update("jax_platform_name", "cpu")

REL_TOL = 1e-5


def _make_tiles(n, to, ti, *, seed=0, screens=False, plans=None):
    """A (to x ti) grid of per-tile kernel argument dicts."""
    rows = []
    for o in range(to):
        row = []
        for i in range(ti):
            pair = plans[o][i] if plans is not None else None
            v_plan = (pair[0] if pair is not None and pair[0] is not None
                      else mesh_lib.clements_plan(n))
            u_plan = (pair[1] if pair is not None and pair[1] is not None
                      else mesh_lib.clements_plan(n))
            k = jax.random.fold_in(jax.random.PRNGKey(seed), o * ti + i)
            kv, ku, ka, ks = jax.random.split(k, 4)
            vp = mesh_lib.init_mesh_params(kv, v_plan)
            up = mesh_lib.init_mesh_params(ku, u_plan)
            if screens:
                vp["alpha_in"] = jax.random.uniform(ks, (n,)) * 2 * np.pi
                up["alpha_in"] = jax.random.uniform(
                    jax.random.fold_in(ks, 1), (n,)) * 2 * np.pi
            row.append({
                "v": vp, "u": up,
                "atten": jax.random.uniform(ka, (n,), minval=0.2,
                                            maxval=0.9),
                "scale": 1.0 + 0.1 * (o + i),
            })
        rows.append(tuple(row))
    return tuple(rows)


def _make_deep(n, depth, to, ti, *, seed=0, screens=False, plans=None):
    """An L-deep stack of (to x ti) tile-argument grids."""
    return tuple(
        _make_tiles(n, to, ti, seed=seed + 101 * l, screens=screens,
                    plans=plans[l] if plans is not None else None)
        for l in range(depth))


def _per_layer(layers, x, n, *, plans=None, readout="magnitude"):
    """The unfused oracle: L separate tile-grid megakernel calls with
    power detection between layers in plain JAX."""
    y = x
    last = len(layers) - 1
    for l, tiles in enumerate(layers):
        pl = plans[l] if plans is not None else None
        y = ops.tiled_apply(tiles, y, n=n, plans=pl)
        if l < last or readout == "magnitude":
            y = jnp.abs(y)
    return y


def _rand_x(n, batch, seed=0):
    k = jax.random.PRNGKey(seed)
    xr = jax.random.normal(k, (batch, n))
    xi = jax.random.normal(jax.random.fold_in(k, 1), (batch, n))
    return (xr + 1j * xi).astype(jnp.complex64)


def _max_rel_err(got, want):
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(want))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    return err / (scale + 1e-30)


# ---------------------------------------------------------------------------
# property-based differential: deep megakernel vs per-layer composition
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(depth=st.integers(1, 3), g=st.integers(1, 2),
       tile=st.sampled_from([2, 4]), seed=st.integers(0, 10_000),
       screens=st.booleans())
def test_deepgrid_matches_per_layer_fwd_and_vjp(depth, g, tile, seed,
                                                screens):
    """Random depth / grid shapes / tile sizes / screens: the single-launch
    deep kernel must match the per-layer tiled_apply composition (detect
    between layers) to <= 1e-5 relative, forward and full VJP.

    Sizes are deliberately small: every example compiles a fresh fused
    L-layer backward, and this property runs on the CI fast leg."""
    if depth == 3 and g == 2:
        g = 1  # cap the deepest example's grid (runtime, CI fast leg)
    layers = _make_deep(tile, depth, g, g, seed=seed, screens=screens)
    x = _rand_x(g * tile, 5, seed=seed + 1)
    y_pl = _per_layer(layers, x, tile)
    y_k = ops.deep_apply(layers, x, n=tile)
    assert y_k.shape == (5, g * tile)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_pl),
                               atol=REL_TOL * 10 * max(1.0, g))

    w = 1.0 + jnp.arange(g * tile, dtype=jnp.float32)  # break degeneracies

    def loss_k(ls, xx):
        return jnp.sum(ops.deep_apply(ls, xx, n=tile) * w)

    def loss_pl(ls, xx):
        return jnp.sum(_per_layer(ls, xx, tile) * w)

    g_k = jax.jit(jax.grad(loss_k, argnums=(0, 1)))(layers, x)
    g_pl = jax.jit(jax.grad(loss_pl, argnums=(0, 1)))(layers, x)
    assert _max_rel_err(g_k, g_pl) <= REL_TOL


def test_deepgrid_mixed_reck_plans_identity_padding():
    """Reck tiles are deeper than Clements ones: a mixed deep stack
    exercises the network-wide identity-column padding, which must be an
    exact no-op in forward AND contribute exactly zero parameter grad."""
    n, depth, g = 4, 2, 2
    rplan, rparams = decompose.reck_program(
        decompose.random_unitary(n, seed=3))
    plans = (((None, (rplan, None)), (None, None)),
             (((None, rplan), None), (None, None)))
    layers = [[
        list(r) for r in _make_tiles(n, g, g, seed=5 + l, plans=plans[l])]
        for l in range(depth)]
    layers[0][0][1] = dict(layers[0][0][1], v=dict(rparams))
    layers[1][0][0] = dict(layers[1][0][0], u=dict(rparams))
    layers = tuple(tuple(tuple(r) for r in la) for la in layers)
    x = _rand_x(g * n, 6)
    y_pl = _per_layer(layers, x, n, plans=plans)
    y_k = ops.deep_apply(layers, x, n=n, plans=plans)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_pl), atol=1e-4)
    deep = deep_grid_schedule(n, depth, g, g, plans)
    assert deep.n_columns > deep.layers[0][1][1][0].n_columns  # padding used

    w = 1.0 + jnp.arange(g * n, dtype=jnp.float32)
    g_k = jax.grad(lambda ls: jnp.sum(
        ops.deep_apply(ls, x, n=n, plans=plans) * w))(layers)
    g_pl = jax.grad(lambda ls: jnp.sum(
        _per_layer(ls, x, n, plans=plans) * w))(layers)
    assert _max_rel_err(g_k, g_pl) <= REL_TOL


# ---------------------------------------------------------------------------
# ragged batches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 7, 130])
def test_deepgrid_ragged_batches(batch):
    """B need not divide the batch block: the tail block's zero-padded
    rows must stay exactly zero through every in-kernel detection (the
    zero-guarded |z| pullback) in forward and VJP."""
    n, depth, g = 4, 2, 2
    layers = _make_deep(n, depth, g, g, seed=2)
    x = _rand_x(g * n, batch)
    y_pl = _per_layer(layers, x, n)
    y_k = ops.deep_apply(layers, x, n=n, block_b=64)
    assert y_k.shape == (batch, g * n)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_pl), atol=1e-5)

    w = 1.0 + jnp.arange(g * n, dtype=jnp.float32)
    g_k = jax.grad(lambda ls: jnp.sum(
        ops.deep_apply(ls, x, n=n, block_b=64) * w))(layers)
    g_pl = jax.grad(lambda ls: jnp.sum(
        jnp.abs(_per_layer(ls, x, n)) * w))(layers)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g_k))
    assert _max_rel_err(g_k, g_pl) <= REL_TOL


# ---------------------------------------------------------------------------
# degenerate wrappers: tiled_apply (L=1) and rfnn_network (To=Ti=1)
# ---------------------------------------------------------------------------

def test_deepgrid_degenerate_single_layer_is_tiled_apply():
    """L=1 with complex readout must be BIT-identical to the tiled_apply
    wrapper — same kernel, same op order."""
    n, to, ti = 4, 2, 3
    tiles = _make_tiles(n, to, ti, seed=4)
    x = _rand_x(ti * n, 5)
    y_t = ops.tiled_apply(tiles, x, n=n)
    y_d = ops.deep_apply((tiles,), x, n=n, readout="complex")
    np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_d))


def test_deepgrid_degenerate_network_is_rfnn_network():
    """To=Ti=1 deep stack with magnitude readout must be BIT-identical to
    the rfnn_network wrapper."""
    n, depth = 6, 3
    layers1d = tuple(_make_tiles(n, 1, 1, seed=20 + l)[0][0]
                     for l in range(depth))
    nested = tuple(((la,),) for la in layers1d)
    x = _rand_x(n, 5)
    y_net = ops.rfnn_network(layers1d, x, n=n)
    y_deep = ops.deep_apply(nested, x, n=n, readout="magnitude")
    np.testing.assert_array_equal(np.asarray(y_net), np.asarray(y_deep))


# ---------------------------------------------------------------------------
# memoization: schedule lowering + trace cache + pack cache + kernel path
# ---------------------------------------------------------------------------

def test_deepgrid_schedule_memoized_no_retrace():
    """Structurally equal deep stacks (fresh objects every call) must not
    re-trigger a jit trace of the kernel impl."""
    n, depth, g = 4, 2, 2
    layers = _make_deep(n, depth, g, g)
    x = _rand_x(g * n, 4)
    ops.deep_apply(layers, x, n=n)
    before = ops.TRACE_COUNTS["deep_apply"]
    ops.deep_apply(layers, x, n=n)  # fresh schedule build, equal content
    assert ops.TRACE_COUNTS["deep_apply"] == before  # no retrace


def test_deepgrid_pack_cache_single_pack_event():
    """Same (immutable) tile arrays -> exactly one PACK_EVENT ever; new
    arrays -> exactly one more.  The kernel path is actually taken."""
    n, depth, g = 4, 2, 2
    layers = _make_deep(n, depth, g, g, seed=9)
    x = _rand_x(g * n, 4)
    calls = ops.KERNEL_PATH_CALLS["deep_apply"]
    packs = ops.PACK_EVENTS["deep_apply"]
    ops.deep_apply(layers, x, n=n)  # populate (exactly one pack)
    assert ops.KERNEL_PATH_CALLS["deep_apply"] == calls + 1
    assert ops.PACK_EVENTS["deep_apply"] == packs + 1
    for _ in range(5):
        ops.deep_apply(layers, x, n=n)
    assert ops.PACK_EVENTS["deep_apply"] == packs + 1  # steady state

    bumped = ((((dict(layers[0][0][0], atten=layers[0][0][0]["atten"] + .01),)
                + layers[0][0][1:],) + layers[0][1:]),) + layers[1:]
    ops.deep_apply(bumped, x, n=n)
    assert ops.PACK_EVENTS["deep_apply"] == packs + 2


# ---------------------------------------------------------------------------
# lower_deep: the compile path — placements, parked tiles, serving
# ---------------------------------------------------------------------------

def _deep_progs(ws, tile, *, method="reck"):
    from repro import compile as comp
    return [comp.program_tiled(comp.synthesize_tiled(w, tile), method=method)
            for w in ws]


def test_lower_deep_matches_per_layer_compiled_apply():
    """lower_deep(...).apply == the composition of per-layer lower_tiled
    programs, placements and calibration draws included (the interior
    boundary resolves by pack-time column re-ordering)."""
    from repro import compile as comp
    from repro.paper.prototype import PROTOTYPE

    rng = np.random.default_rng(1)
    tile, depth, d = 4, 3, 8
    ws = [rng.normal(size=(d, d)).astype(np.float32) * 0.4
          for _ in range(depth)]
    key = jax.random.PRNGKey(3)
    perms = [((1, 0), (0, 1)), ((0, 1), (1, 0)), ((1, 0), (1, 0))]
    tps = []
    for l, w in enumerate(ws):
        tp = _deep_progs([w], tile)[0]
        tp = comp.quantize_tiled(tp, "table1")
        tp = comp.apply_placement(tp, comp.TilePlacement(*perms[l]))
        tp = comp.calibrate_tiled(tp, PROTOTYPE,
                                  key=jax.random.fold_in(key, l))
        tps.append(tp)
    cd = comp.lower_deep(tps)
    x = jnp.asarray(rng.normal(size=(5, d)).astype(np.float32))
    y_deep = cd.apply(x)
    y = x
    for tp in tps:
        y = comp.lower_tiled(tp).apply(y)
    np.testing.assert_allclose(np.asarray(y_deep), np.asarray(y),
                               atol=1e-5 * float(jnp.max(jnp.abs(y))))


def test_deep_apply_ref_matches_compiled_deep_program():
    """The pure-jnp deep oracle (the chip smoke test's reference) agrees
    with the kernel on a calibrated Reck deep program — hardware model,
    per-tile noise keys and mixed-parity column alignment included — and
    its autodiff gradients match the kernel VJP on trainable tiles."""
    from repro import compile as comp
    from repro.kernels import ref
    from repro.paper.prototype import PROTOTYPE

    rng = np.random.default_rng(4)
    tile, depth, d = 4, 2, 8
    tps = []
    for l in range(depth):
        tp = _deep_progs([rng.normal(size=(d, d)).astype(np.float32)],
                         tile)[0]
        tp = comp.quantize_tiled(tp, "table1")
        tps.append(comp.calibrate_tiled(
            tp, PROTOTYPE, key=jax.random.fold_in(jax.random.PRNGKey(5), l)))
    cd = comp.lower_deep(tps)
    x = jnp.asarray(rng.normal(size=(6, d)).astype(np.float32))
    y_ref = ref.deep_apply_ref(cd.layer_args, x, n=tile, plans=cd.plans,
                               hardware=cd.hardware)
    assert _max_rel_err(cd.apply(x), y_ref) <= 1e-5

    layers = _make_deep(tile, depth, 2, 2, seed=6, screens=True)
    xc = _rand_x(2 * tile, 5, seed=7)
    w = 1.0 + jnp.arange(2 * tile, dtype=jnp.float32)
    g_k = jax.grad(lambda ls: jnp.sum(
        ops.deep_apply(ls, xc, n=tile) * w))(layers)
    g_r = jax.grad(lambda ls: jnp.sum(
        ref.deep_apply_ref(ls, xc, n=tile) * w))(layers)
    assert _max_rel_err(g_k, g_r) <= REL_TOL


def test_lower_deep_rejects_non_chaining_layers():
    from repro import compile as comp
    rng = np.random.default_rng(2)
    a = _deep_progs([rng.normal(size=(8, 8)).astype(np.float32)], 4)[0]
    b = _deep_progs([rng.normal(size=(12, 12)).astype(np.float32)], 4)[0]
    with pytest.raises(ValueError, match="does not chain"):
        comp.lower_deep([a, b])


def test_deepgrid_blank_tile_parked_zero_grad():
    """A parked (blank) tile inside a deep program: finite everywhere and
    EXACTLY zero gradient into the parked tile's mesh/attenuation
    parameters — scale==0 kills its contribution and the zero-guarded
    detection pullback keeps the zero exact instead of NaN."""
    from repro import compile as comp

    rng = np.random.default_rng(7)
    tile, depth, d = 4, 2, 8
    ws = [rng.normal(size=(d, d)).astype(np.float32) * 0.5
          for _ in range(depth)]
    tps = _deep_progs(ws, tile)
    grid = [list(r) for r in tps[1].grid]
    grid[0][1] = comp.blank_tile(grid[0][1])  # park one interior tile
    tps[1] = dataclasses.replace(tps[1], grid=tuple(tuple(r) for r in grid))
    cd = comp.lower_deep(tps)
    # ragged batch + a zero input row: padding and parked paths together
    x = jnp.asarray(rng.normal(size=(3, d)).astype(np.float32))
    x = x.at[1].set(0.0)
    assert bool(jnp.all(jnp.isfinite(cd.apply(x))))

    w = 1.0 + jnp.arange(d, dtype=jnp.float32)

    def loss(layer_args, xx):
        return jnp.sum(ops.deep_apply(layer_args, xx, n=tile,
                                      plans=cd.plans, block_b=8) * w)

    g_args, g_x = jax.jit(jax.grad(loss, argnums=(0, 1)))(cd.layer_args, x)
    assert all(bool(jnp.all(jnp.isfinite(le)))
               for le in jax.tree.leaves((g_args, g_x)))
    parked = g_args[1][0][1]
    for name in ("v", "u", "atten"):
        for leaf in jax.tree.leaves(parked[name]):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_engine_serves_compiled_deep_program():
    """params=None serving of a CompiledDeepProgram: tensors were emitted
    at lower_deep time, so NO tick — the first included — packs."""
    from repro import compile as comp
    from repro.serving import Request, ServingEngine

    rng = np.random.default_rng(11)
    tile, d = 4, 8
    ws = [rng.normal(size=(d, d)) / np.sqrt(d) for _ in range(2)]
    cd = comp.lower_deep(_deep_progs(ws, tile))
    engine = ServingEngine(cd, slots=3)
    packs = ops.PACK_EVENTS["deep_apply"]
    feats = rng.normal(size=(5, d)).astype(np.float32)
    reqs = [Request(rid=i, features=feats[i]) for i in range(5)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    want = np.abs(np.abs(feats @ ws[0].T) @ ws[1].T)
    for r in reqs:
        np.testing.assert_allclose(r.result, want[r.rid], atol=1e-4)
    assert ops.PACK_EVENTS["deep_apply"] == packs  # zero, first tick incl.


# ---------------------------------------------------------------------------
# shard_map scale-out of the deep kernel (subprocess: forced 8-device host)
# ---------------------------------------------------------------------------

_SHARDED_PROGRAM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import mesh as mesh_lib
from repro.kernels import ops

rng = np.random.default_rng(0)
n, g, depth, b = 4, 2, 2, 10        # ragged batch
plan = mesh_lib.clements_plan(n)
layers = []
for l in range(depth):
    rows = []
    for o in range(g):
        trow = []
        for i in range(g):
            kv, ku, ka = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(7), (l * g + o) * g + i), 3)
            trow.append({
                "v": mesh_lib.init_mesh_params(kv, plan),
                "u": mesh_lib.init_mesh_params(ku, plan),
                "atten": jax.random.uniform(ka, (n,), minval=0.2,
                                            maxval=0.9),
                "scale": 1.0 + 0.05 * (o + i + l),
            })
        rows.append(tuple(trow))
    layers.append(tuple(rows))
layers = tuple(layers)
x = jnp.asarray(rng.normal(size=(b, g * n)).astype(np.float32))
w = jnp.asarray(rng.normal(size=(b, g * n)).astype(np.float32))


def loss(layers, x, mesh=None):
    return jnp.sum(ops.deep_apply(layers, x, n=n, mesh=mesh) * w)


y_ref = np.asarray(ops.deep_apply(layers, x, n=n))
g_ref = jax.grad(loss, argnums=(0, 1))(layers, x)

for shape in [(2, 4), (1, 8)]:
    nr, nd = shape
    mesh = Mesh(np.array(jax.devices()[: nr * nd]).reshape(nr, nd),
                ("rows", "data"))
    y_sh = np.asarray(ops.deep_apply(layers, x, n=n, mesh=mesh))
    rel = np.abs(y_sh - y_ref).max() / np.abs(y_ref).max()
    assert rel <= 1e-5, f"fwd {shape}: rel={rel}"
    g_sh = jax.grad(loss, argnums=(0, 1))(layers, x, mesh=mesh)
    for a, bb in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sh)):
        a, bb = np.asarray(a), np.asarray(bb)
        rel = np.abs(a - bb).max() / max(np.abs(a).max(), 1e-12)
        assert rel <= 1e-5, f"grad {shape}: rel={rel}"

# the training-step shape: enclosing jit over raw tiles (packing traced)
mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("rows", "data"))
g_jit = jax.jit(jax.grad(lambda ls, xx: loss(ls, xx, mesh=mesh),
                         argnums=(0, 1)))(layers, x)
for a, bb in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_jit)):
    a, bb = np.asarray(a), np.asarray(bb)
    rel = np.abs(a - bb).max() / max(np.abs(a).max(), 1e-12)
    assert rel <= 1e-5, f"jit(grad) rel={rel}"

assert ops.KERNEL_PATH_CALLS["deep_apply_sharded"] > 0
print("DEEP_SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_deep_apply_matches_single_device():
    r = subprocess.run([sys.executable, "-c", _SHARDED_PROGRAM],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "DEEP_SHARDED_OK" in r.stdout, r.stdout + r.stderr
