"""Row-blocked deep-grid kernels and the stacked-tile entry.

A single-layer deep-grid call whose coefficient stacks outgrow VMEM
runs on a (row blocks, batch blocks) grid, deriving each mesh's inverse
coefficients in the kernel; it must agree with the resident call.  The
stacked ``[L, To, Ti, ...]`` entry of ``pack_deep_grid``/``deep_apply``
must pack exactly what the nested per-tile entry packs.  The block
arithmetic of the sharded chain sizes each chip's single-layer call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mesh as mesh_lib
from repro.kernels import givens_mesh, ops
from repro.kernels.schedule import deep_grid_schedule
from repro.paper.prototype import PROTOTYPE

from test_deepgrid_kernel import _make_deep, _max_rel_err, _rand_x

#: the VJP of a row-blocked call sums its row blocks' input-cotangent
#: partials outside the kernel, in another order than the resident
#: call's in-kernel sum, and derives the inverse coefficients in the
#: kernel: float32 rounding apart (read: up to 7e-8)
ROWS_TOL = 1e-6


@pytest.fixture
def coef_budget(monkeypatch):
    """Sets ``givens_mesh.COEF_VMEM_BUDGET`` (the VMEM a deep-grid call
    may give its coefficient stacks before it is row-blocked) and drops
    the cached traces, which the budget does not key."""
    def set_budget(value):
        monkeypatch.setattr(givens_mesh, "COEF_VMEM_BUDGET", value)
        jax.clear_caches()
    jax.clear_caches()
    yield set_budget
    jax.clear_caches()


# ---------------------------------------------------------------------------
# (a) row-blocked kernels against the resident kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("readout", ["magnitude", "complex"])
@pytest.mark.parametrize("row_block", [1, 2])
def test_row_blocked_matches_resident_fwd_and_vjp(coef_budget, readout,
                                                  row_block):
    """One layer of a 4 x 3 grid of 4x4 tiles, 200 rows (two 128-row
    lane blocks, the second padded): blocks of 1 or 2 tile rows (the
    VMEM budget cut to what that many rows take) against the one
    resident block, forward bit for bit and the VJP (tile parameters and
    input) to float32 rounding."""
    n, to, ti, batch = 4, 4, 3, 200
    layers = _make_deep(n, 1, to, ti, seed=5, screens=True)
    x = _rand_x(ti * n, batch, seed=3)
    w = 1.0 + jnp.arange(to * n, dtype=jnp.float32)

    def apply(ls, xx, **kw):
        return ops.deep_apply(ls, xx, n=n, block_b=128, readout=readout,
                              **kw)

    # the blocks as arithmetic, and the row-blocked kernel by its name in
    # the traced program (``DEEP_BLOCKS`` is written only when a call is
    # traced, which a cached call is not)
    deep = deep_grid_schedule(n, 1, to, ti, None)

    def loss(ls, xx):
        return jnp.sum(jnp.abs(apply(ls, xx)) * w)

    assert ops._deep_blocks(deep, 1, to, batch, 128, True) == (128, to)
    assert "deepgrid_rows" not in str(jax.make_jaxpr(apply)(layers, x))
    y_res = apply(layers, x)
    g_res = jax.grad(loss, argnums=(0, 1))(layers, x)

    coef_budget(givens_mesh.deep_coef_vmem(n, 1, row_block, ti,
                                           deep.n_columns, True))
    assert ops._deep_blocks(deep, 1, to, batch, 128,
                            True) == (128, row_block)
    assert "deepgrid_rows_kernel" in str(jax.make_jaxpr(apply)(layers, x))
    y_rows = apply(layers, x)
    np.testing.assert_array_equal(np.asarray(y_rows), np.asarray(y_res))
    g_rows = jax.grad(loss, argnums=(0, 1))(layers, x)
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(g_rows))
    assert _max_rel_err(g_rows, g_res) <= ROWS_TOL


def test_multi_layer_call_is_never_row_blocked(coef_budget):
    """Inter-layer re-detection needs every row of a layer, so an L > 1
    call keeps all its tile rows in one block however little VMEM its
    stacks may take."""
    n, g = 4, 2
    coef_budget(0)
    deep = deep_grid_schedule(n, 2, g, g, None)
    assert ops._deep_blocks(deep, 2, g, 9, None, True)[1] == g
    assert ops._deep_blocks(deep, 1, g, 9, None, True)[1] == 1
    layers = _make_deep(n, 2, g, g, seed=1)
    assert "deepgrid_rows" not in str(jax.make_jaxpr(
        lambda ls, xx: ops.deep_apply(ls, xx, n=n))(
            layers, _rand_x(g * n, 9)))


def test_row_block_budget_arithmetic(monkeypatch):
    """Where the row blocks come from, for 16x16 Reck tiles (29 columns,
    P = 8 pair slots; a (P, 8) slab is one 4-KiB VMEM tile): the 64-wide
    L = 4 grid's stacks fit and stay resident; one layer of the 1024-wide
    grid's 64 x 64 tiles cannot, and neither can one chip's 32 tile rows
    of it, which run one tile row a block (single-buffered inputs and
    inverse scratch 4 x 64 x 29 slabs = 29 MiB, double-buffered gradients
    another 29 MiB, under the 64-MiB budget; two rows would take 116).
    A budget of four rows' blocks gives four rows, or the most rows under
    four that divide the tile rows."""
    n, cols = 16, 29
    slab = 4 * 2**10
    assert givens_mesh.deep_row_block(n, 4, 4, 4, cols) == 4
    assert givens_mesh.deep_row_block(n, 1, 64, 64, cols) == 1
    assert givens_mesh.deep_row_block(n, 1, 32, 64, cols) == 1
    gains = 64 * slab
    assert givens_mesh.deep_coef_vmem(n, 1, 1, 64, cols, True) == \
        4 * 64 * cols * slab + gains + 2 * (2 * 64 * cols * slab + gains)
    monkeypatch.setattr(givens_mesh, "COEF_VMEM_BUDGET",
                        givens_mesh.deep_coef_vmem(n, 1, 4, 64, cols, True))
    assert givens_mesh.deep_row_block(n, 1, 32, 64, cols) == 4
    assert givens_mesh.deep_row_block(n, 1, 6, 64, cols) == 3


# ---------------------------------------------------------------------------
# the sharded chain's block arithmetic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "to, ti, b, to_local, whole, per_call",
    [(4, 4, 512, 2, 128, (512, 2)),      # 64 wide on a 2 x 2 mesh
     (64, 64, 2048, 32, 128, (128, 1))])  # 1024 wide: one tile row a block
def test_sharded_block_sized_from_the_chips_call(to, ti, b, to_local, whole,
                                                 per_call):
    """Each chip of the sharded chain runs one layer of its ``to_local``
    tile rows on its ``b`` batch rows, so its batch block is sized from
    that call's planes: 8 stage planes per tile of one layer of its row
    block, 4 input planes per input tile, 4 output planes per row, 4
    working planes.  At 64 wide (16x16 tiles, Clements: 16 columns) one
    chip's 2 x 4 tiles take 92 planes a row, so its 512 rows run whole;
    the whole L = 4 grid's 548 planes would cap the block at 128.  At
    1024 wide a chip's row block of 1 x 64 tiles takes 776 planes (24.8
    KB a row), a 128-row lane block."""
    n = 16
    deep = deep_grid_schedule(n, 4, to, ti, None)
    assert ops._vmem_auto_block(b, None, n, ops._deep_planes_per_row(deep),
                                interpret=False) == whole
    assert ops._deep_blocks(deep, 1, to_local, b, None,
                            interpret=False) == per_call
    rows = per_call[1]
    assert ops._deep_planes_per_row(deep, n_layers=1, rows=rows) == \
        8 * rows * ti + 4 * ti + 4 * rows + 4


# ---------------------------------------------------------------------------
# (b) the stacked entry packs what the nested entry packs
# ---------------------------------------------------------------------------

def _stacked(nested, keys=None):
    """Nested ``[L][To][Ti]`` tile dicts -> the stacked entry's arrays
    (``v_theta``, ``u_alpha_in``, ..., ``atten``, ``scale``)."""
    first = nested[0][0][0]
    paths = {f"{m}_{k}": (m, k) for m in ("v", "u") for k in first[m]}
    paths.update({k: (k,) for k in ("atten", "scale") if k in first})

    def leaf(ta, path):
        for k in path:
            ta = ta[k]
        return jnp.asarray(ta, jnp.float32)

    out = {name: jnp.stack([jnp.stack([jnp.stack(
        [leaf(ta, path) for ta in row]) for row in layer])
        for layer in nested]) for name, path in paths.items()}
    if keys is not None:
        out.update(keys)
    return out


def _with_keys(nested, seed):
    lead = (len(nested), len(nested[0]), len(nested[0][0]))
    count = int(np.prod(lead))
    kv, ku = jax.random.split(jax.random.PRNGKey(seed))
    keys = {"key_v": jax.random.split(kv, count).reshape(lead + (2,)),
            "key_u": jax.random.split(ku, count).reshape(lead + (2,))}
    merged = tuple(tuple(tuple(
        {**ta, "key_v": keys["key_v"][l, o, i],
         "key_u": keys["key_u"][l, o, i]}
        for i, ta in enumerate(row)) for o, row in enumerate(layer))
        for l, layer in enumerate(nested))
    return merged, keys


@pytest.mark.parametrize("hardware", [None, PROTOTYPE])
def test_stacked_entry_packs_bit_for_bit(hardware):
    """L = 2 layers of 3 x 3 tiles of n = 4 with screens and scales (and,
    with the prototype's hardware, per-tile noise keys): the stacked
    entry's coefficients and gains equal the nested entry's exactly, and
    each entry that packs ticks ``PACK_EVENTS`` once."""
    n = 4
    nested = _make_deep(n, 2, 3, 3, seed=9, screens=True)
    keys = None
    if hardware is not None:
        nested, keys = _with_keys(nested, 4)
    stacked = _stacked(nested, keys)
    before = ops.PACK_EVENTS["deep_apply"]
    deep_n, packed_n = ops.pack_deep_grid(nested, n=n, hardware=hardware)
    deep_s, packed_s = ops.pack_deep_grid(stacked, n=n, hardware=hardware)
    assert ops.PACK_EVENTS["deep_apply"] == before + 2
    assert deep_s == deep_n
    for a, b in zip(packed_s, packed_n):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_entry_applies_and_trains_like_nested():
    """``deep_apply`` on the stacked arrays gives the nested entry's
    output bit for bit, and its gradients are the nested gradients
    stacked, under a jitted step (packing traced) as the benchmark's
    trainer runs it."""
    n = 4
    nested = _make_deep(n, 2, 2, 2, seed=3, screens=True)
    stacked = _stacked(nested)
    x = jnp.real(_rand_x(2 * n, 10, seed=2))
    y_n = ops.deep_apply(nested, x, n=n)
    y_s = ops.deep_apply(stacked, x, n=n)
    np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_n))

    def loss(ls):
        return jnp.sum(ops.deep_apply(ls, x, n=n) ** 2)

    g_n = _stacked(jax.jit(jax.grad(loss))(nested))
    g_s = jax.jit(jax.grad(loss))(stacked)
    assert set(g_s) == set(g_n)
    assert _max_rel_err(g_s, g_n) <= 1e-6


def test_only_a_row_blocked_grid_packs_a_layer_at_a_time(coef_budget):
    """A grid whose layers fit VMEM packs as one vmapped computation, as
    the 64-wide cells' grids do; one whose layers run in row blocks
    packs a layer at a time under remat (``lax.map`` of a checkpoint),
    to the same values."""
    n = 4
    stacked = _stacked(_make_deep(n, 2, 2, 2, seed=9, screens=True))

    def pack(st):
        return ops.pack_deep_grid(st, n=n)[1]

    whole = str(jax.make_jaxpr(pack)(stacked))
    assert "scan" not in whole and "remat" not in whole
    want = jax.jit(pack)(stacked)
    coef_budget(0)
    layered = str(jax.make_jaxpr(pack)(stacked))
    assert "scan" in layered and "remat" in layered
    for a, b in zip(jax.jit(pack)(stacked), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_entry_rejects_unknown_names():
    n = 4
    stacked = _stacked(_make_deep(n, 1, 1, 1))
    with pytest.raises(ValueError, match="unknown stacked tile"):
        ops.pack_deep_grid({**stacked, "v_thetas": stacked["v_theta"]}, n=n)


def test_stacked_entry_with_reck_plans_matches_nested():
    """A Reck-planned grid (cells packed into fewer, aligned columns with
    identity padding) through both entries: equal outputs."""
    n = 4
    plan, _, _ = mesh_lib.pack_cells_to_columns(
        n, [(0, 0.1, 0.2), (1, 0.3, 0.4), (0, 0.5, 0.6), (2, 0.7, 0.8),
            (1, 0.9, 1.0), (0, 1.1, 1.2)])
    plans = tuple(tuple(tuple((plan, plan) for _ in range(2))
                        for _ in range(2)) for _ in range(2))
    nested = tuple(tuple(tuple(
        {"v": mesh_lib.init_mesh_params(jax.random.PRNGKey(7 * l + 2 * o + i),
                                        plan),
         "u": mesh_lib.init_mesh_params(jax.random.PRNGKey(99 + l + o + i),
                                        plan),
         "atten": jnp.full((n,), 0.6), "scale": jnp.float32(1.5)}
        for i in range(2)) for o in range(2)) for l in range(2))
    stacked = _stacked(nested)
    x = jnp.real(_rand_x(2 * n, 5, seed=1))
    np.testing.assert_array_equal(
        np.asarray(ops.deep_apply(stacked, x, n=n, plans=plans)),
        np.asarray(ops.deep_apply(nested, x, n=n, plans=plans)))
