"""Jitted, differentiable public wrappers around the Pallas mesh kernels.

``interpret`` defaults to :func:`default_interpret`: the kernels compile
to Mosaic on a TPU and run in the Pallas interpreter on the CPU (tests,
local runs), from the same call sites.

Both ``mesh_apply`` and ``rfnn_linear`` carry custom VJPs: the backward
pass is itself a fused Pallas kernel that re-runs the mesh columns in
reverse, rebuilding states with the per-cell analytic 2x2 **inverse** and
propagating the cotangent with the **adjoint** (see DESIGN.md) — so
training keeps the same VMEM-resident hot loop as inference for ideal
*and* hardware-imperfect cells, on Clements *and* Reck layouts.  There is
no reference fallback: ``backend="pallas"`` means the kernel path, always.

Everything outside the pallas_call boundary — coefficient packing from
theta/phi (ideal or via the hardware model, including ``key``-driven
phase-noise sampling), channel split/merge, phase screens, gains — is
ordinary JAX and differentiates natively, which is how gradients reach
the mesh phases, attenuations and the digital scale.  Detector noise and
the sensitivity floor also stay outside (``hardware.detect_magnitude``
composes on the returned magnitudes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hardware as hw_lib
from repro.core import mesh as mesh_lib
from repro.core.cell import cell_matrix
from repro.kernels import givens_mesh, ref
from repro.kernels.schedule import (
    MeshSchedule,
    align_columns,
    clements_schedule,
    deep_grid_column_sources,
    deep_grid_parity_arrays,
    deep_grid_schedule,
    pack_cells,
    parity_array,
    schedule_from_plan,
)

Array = jax.Array

#: Instrumentation: per-entry-point invocation counts of the kernel path.
#: Tests use this to assert the Pallas path is actually taken (there is no
#: silent reference fallback left to fall into).  Counts tick on every
#: public-wrapper call (trace time under an outer jit).
KERNEL_PATH_CALLS = {"mesh_apply": 0, "rfnn_linear": 0, "mesh_apply_cells": 0,
                     "rfnn_network": 0, "tiled_apply": 0,
                     "tiled_apply_sharded": 0, "deep_apply": 0,
                     "deep_apply_sharded": 0}

#: Instrumentation: number of times each jitted impl was actually *traced*.
#: Regression tests use this to pin the schedule/trace-cache memoization —
#: structurally equal plans must not re-trigger traces.
TRACE_COUNTS = {"mesh_apply": 0, "rfnn_linear": 0, "rfnn_network": 0,
                "tiled_apply": 0, "tiled_apply_sharded": 0, "deep_apply": 0,
                "deep_apply_sharded": 0}

#: Instrumentation: number of coefficient-pack builds actually executed by
#: :func:`pack_deep_grid` (cache misses / tracer bypasses), keyed by the
#: entry point that requested the pack.  Steady-state serving must not
#: tick this.
PACK_EVENTS = {"rfnn_network": 0, "tiled_apply": 0, "deep_apply": 0}


def default_interpret() -> bool:
    """Compiled kernels on the TPU, the Pallas interpreter on the CPU
    (tests and local runs); any other backend is an error rather than a
    silent interpreted fallback."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU and interpret on CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def _auto_block(b: int, block_b: int) -> int:
    """Shrink ``block_b`` to divide the batch evenly (never grow past it).

    ``block_b`` is a working-set ceiling, not a quantum: padding the
    batch up to a multiple of the raw ceiling can waste most of the last
    block (e.g. 256 rows in 232-row blocks -> 464 padded rows).
    Spreading the same rows over ``ceil(b / block_b)`` equal blocks keeps
    every block under the ceiling with at most 7 pad rows per block."""
    if b <= 0:
        return 1
    block_b = max(1, block_b)
    if b <= block_b + block_b // 8:
        # anti-fragmentation: a single block may overshoot the ceiling by
        # <= 1/8 (the target itself sits well under the physical budget)
        return max(1, -(-b // 8) * 8)
    n_blocks = -(-b // block_b)
    even = -(-b // n_blocks)                       # ceil(b / n_blocks)
    return max(1, min(block_b, -(-even // 8) * 8))


def _pad_batch(x2d: Array, block: int) -> tuple[Array, int]:
    b = x2d.shape[0]
    pad = (-b) % block
    if pad:
        # jnp.pad, not concatenate-with-zeros: GSPMD mis-partitions a
        # concatenate feeding shard_map on a multi-axis mesh (the row-axis
        # shards get summed instead of replicated); the pad HLO shards
        # correctly and is semantically identical here
        x2d = jnp.pad(x2d, ((0, pad),) + ((0, 0),) * (x2d.ndim - 1))
    return x2d, b


# ---------------------------------------------------------------------------
# custom-VJP boundary: de-interleaved planes in, planes out
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _mesh_planes(sched, block_b, nb, interpret, coef, xer, xei, xor, xoi):
    call = givens_mesh.mesh_pallas_call(
        sched.n, sched.n_columns, block_b, nb, interpret)
    return tuple(call(coef, parity_array(sched), xer, xei, xor, xoi))


def _mesh_planes_fwd(sched, block_b, nb, interpret, coef, xer, xei, xor, xoi):
    outs = _mesh_planes(sched, block_b, nb, interpret, coef,
                        xer, xei, xor, xoi)
    # the output planes are the only state residual needed: the backward
    # sweep rebuilds every intermediate via the per-cell inverse
    return outs, (coef, outs)


def _mesh_planes_bwd(sched, block_b, nb, interpret, res, cot):
    coef, outs = res
    coef_inv = givens_mesh.inverse_coefficients(coef)
    coef_adj = givens_mesh.adjoint_coefficients(coef)
    call = givens_mesh.mesh_bwd_pallas_call(
        sched.n, sched.n_columns, block_b, nb, interpret)
    dcoef, dxer, dxei, dxor, dxoi = call(
        coef_inv, coef_adj, parity_array(sched), *outs, *cot)
    return dcoef, dxer, dxei, dxor, dxoi


_mesh_planes.defvjp(_mesh_planes_fwd, _mesh_planes_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _rfnn_planes(sched_v, sched_u, block_b, nb, interpret, coef_v, coef_u,
                 gains, xer, xei, xor, xoi):
    call = givens_mesh.rfnn_linear_pallas_call(
        sched_v.n, sched_v.n_columns, sched_u.n_columns, block_b, nb,
        interpret)
    return tuple(call(coef_v, parity_array(sched_v),
                      coef_u, parity_array(sched_u), gains,
                      xer, xei, xor, xoi))


def _rfnn_planes_fwd(sched_v, sched_u, block_b, nb, interpret, coef_v,
                     coef_u, gains, xer, xei, xor, xoi):
    call = givens_mesh.rfnn_linear_fwd_pallas_call(
        sched_v.n, sched_v.n_columns, sched_u.n_columns, block_b, nb,
        interpret)
    oe, oo, *stage = call(coef_v, parity_array(sched_v),
                          coef_u, parity_array(sched_u), gains,
                          xer, xei, xor, xoi)
    return (oe, oo), (coef_v, coef_u, gains, tuple(stage))


def _rfnn_planes_bwd(sched_v, sched_u, block_b, nb, interpret, res, cot):
    coef_v, coef_u, gains, stage = res
    call = givens_mesh.rfnn_linear_bwd_pallas_call(
        sched_v.n, sched_v.n_columns, sched_u.n_columns, block_b, nb,
        interpret)
    dcv, dcu, dgains, dxer, dxei, dxor, dxoi = call(
        givens_mesh.inverse_coefficients(coef_v),
        givens_mesh.adjoint_coefficients(coef_v), parity_array(sched_v),
        givens_mesh.inverse_coefficients(coef_u),
        givens_mesh.adjoint_coefficients(coef_u), parity_array(sched_u),
        gains, *stage, *cot)
    return dcv, dcu, dgains, dxer, dxei, dxor, dxoi


_rfnn_planes.defvjp(_rfnn_planes_fwd, _rfnn_planes_bwd)


# ---------------------------------------------------------------------------
# coefficient construction (ideal cells or the hardware model)
# ---------------------------------------------------------------------------

def _mesh_coefficients(sched: MeshSchedule, params: dict,
                       hardware: hw_lib.HardwareModel | None,
                       key: Array | None) -> Array:
    """Packed [C', 8, P] coefficients from mesh params.

    With a hardware model, cells come from ``imperfect_cell_matrix`` —
    the same function (and the same ``key`` consumption) as the reference
    ``apply_mesh_hw`` path, so the two backends see identical draws.
    """
    theta, phi = params["theta"], params["phi"]
    if hardware is None:
        t_all = cell_matrix(theta, phi)
    else:
        t_all = hw_lib.imperfect_cell_matrix(theta, phi, hardware, key)
    return pack_cells(sched, t_all)


def _run_mesh_planes(sched, x2, coef, block_b, interpret):
    bb = _auto_block(x2.shape[0], block_b)
    x2, b_orig = _pad_batch(x2, bb)
    nb = x2.shape[0] // bb
    planes = ref.split_channels(x2)
    planes = _mesh_planes(sched, bb, nb, interpret, coef, *planes)
    return ref.merge_channels(*planes)[:b_orig]


# ---------------------------------------------------------------------------
# Public wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnums=(0, 1, 2, 3))
def _mesh_apply_impl(sched, hardware, block_b, interpret, params, x, key):
    TRACE_COUNTS["mesh_apply"] += 1  # python side effect: runs at trace only
    batch_shape = x.shape[:-1]
    x2 = x.reshape((-1, sched.n)).astype(jnp.complex64)
    alpha_in = params.get("alpha_in")
    if alpha_in is not None:
        x2 = x2 * jnp.exp(-1j * alpha_in.astype(jnp.complex64))
    coef = _mesh_coefficients(sched, params, hardware, key)
    y = _run_mesh_planes(sched, x2, coef, block_b, interpret)
    alpha = params.get("alpha")
    if alpha is not None:
        y = y * jnp.exp(-1j * alpha.astype(jnp.complex64))
    return y.reshape(batch_shape + (sched.n,))


def mesh_apply(params: dict, x: Array, *, n: int,
               plan: mesh_lib.MeshPlan | None = None,
               hardware: hw_lib.HardwareModel | None = None,
               key: Array | None = None, block_b: int = 128,
               interpret: bool | None = None) -> Array:
    """Apply a mesh to ``x[..., n]`` via the Pallas kernel.

    Semantics match ``repro.core.mesh.apply_mesh`` on the given plan
    (``None`` = the Clements rectangle), including the optional phase
    screens ``alpha_in`` / ``alpha``; with ``hardware`` they match
    ``repro.core.hardware.apply_mesh_hw`` (imperfect hybrids, per-cell
    insertion loss, and ``key``-sampled phase-shifter noise).
    Differentiable w.r.t. ``params`` and ``x`` through the kernel VJP.
    """
    if interpret is None:
        interpret = default_interpret()
    sched = clements_schedule(n) if plan is None else schedule_from_plan(plan)
    KERNEL_PATH_CALLS["mesh_apply"] += 1
    return _mesh_apply_impl(sched, hardware, block_b, interpret,
                            params, x, key)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _mesh_apply_cells_impl(sched, block_b, interpret, t_all, x, alpha_in,
                           alpha):
    batch_shape = x.shape[:-1]
    x2 = x.reshape((-1, sched.n)).astype(jnp.complex64)
    if alpha_in is not None:
        x2 = x2 * jnp.exp(-1j * alpha_in.astype(jnp.complex64))
    coef = pack_cells(sched, t_all)
    y = _run_mesh_planes(sched, x2, coef, block_b, interpret)
    if alpha is not None:
        y = y * jnp.exp(-1j * alpha.astype(jnp.complex64))
    return y.reshape(batch_shape + (sched.n,))


def mesh_apply_cells(t_all: Array, x: Array, *, plan: mesh_lib.MeshPlan,
                     alpha_in: Array | None = None,
                     alpha: Array | None = None, block_b: int = 128,
                     interpret: bool | None = None) -> Array:
    """Kernel mesh apply from explicit per-cell 2x2 matrices ``[C, P, 2, 2]``.

    The cells-level entry point: callers that build transfer matrices
    directly — e.g. Monte-Carlo yield sweeps vmapping over sampled
    ``HardwareModel`` draws — hit the same fused sweep without going
    through (theta, phi).  ``vmap``-compatible over ``t_all`` and ``x``.
    """
    if interpret is None:
        interpret = default_interpret()
    sched = schedule_from_plan(plan)
    KERNEL_PATH_CALLS["mesh_apply_cells"] += 1
    return _mesh_apply_cells_impl(sched, block_b, interpret, t_all, x,
                                  alpha_in, alpha)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _rfnn_linear_impl(sched_v, sched_u, hardware, block_b, interpret,
                      v_params, atten, u_params, x, scale, key_v, key_u):
    TRACE_COUNTS["rfnn_linear"] += 1  # python side effect: trace time only
    n = sched_v.n
    batch_shape = x.shape[:-1]
    x2 = x.reshape((-1, n)).astype(jnp.complex64)
    if v_params.get("alpha_in") is not None:
        x2 = x2 * jnp.exp(-1j * v_params["alpha_in"].astype(jnp.complex64))

    coef_v = _mesh_coefficients(sched_v, v_params, hardware, key_v)
    coef_u = _mesh_coefficients(sched_u, u_params, hardware, key_u)

    # fold V's output screen (and U's input screen) into the mid-gain and
    # U's output screen into the post-gain — all diagonal, so they commute
    g1 = atten.astype(jnp.complex64)
    if v_params.get("alpha") is not None:
        g1 = g1 * jnp.exp(-1j * v_params["alpha"].astype(jnp.complex64))
    if u_params.get("alpha_in") is not None:
        g1 = g1 * jnp.exp(-1j * u_params["alpha_in"].astype(jnp.complex64))
    g2 = jnp.full((n,), jnp.asarray(scale, jnp.complex64))
    if u_params.get("alpha") is not None:
        g2 = g2 * jnp.exp(-1j * u_params["alpha"].astype(jnp.complex64))
    gains = jnp.stack([
        jnp.real(g1[0::2]), jnp.imag(g1[0::2]),
        jnp.real(g1[1::2]), jnp.imag(g1[1::2]),
        jnp.real(g2[0::2]), jnp.imag(g2[0::2]),
        jnp.real(g2[1::2]), jnp.imag(g2[1::2]),
    ]).astype(jnp.float32)

    bb = _auto_block(x2.shape[0], block_b)
    x2, b_orig = _pad_batch(x2, bb)
    nb = x2.shape[0] // bb
    planes = ref.split_channels(x2)
    oe, oo = _rfnn_planes(sched_v, sched_u, bb, nb, interpret,
                          coef_v, coef_u, gains, *planes)
    out = jnp.stack([oe, oo], axis=-1).reshape((-1, n))[:b_orig]
    return out.reshape(batch_shape + (n,))


def rfnn_linear(v_params: dict, atten: Array, u_params: dict, x: Array, *,
                n: int, scale: Array | float = 1.0,
                v_plan: mesh_lib.MeshPlan | None = None,
                u_plan: mesh_lib.MeshPlan | None = None,
                hardware: hw_lib.HardwareModel | None = None,
                key_v: Array | None = None, key_u: Array | None = None,
                block_b: int = 128,
                interpret: bool | None = None) -> Array:
    """Fused analog linear layer |scale * U(D(V x))| via the Pallas kernel.

    ``atten``: [n] attenuation (paper's diagonal D / sigma_max);
    ``scale``: the digital gamma.  Output is the detected magnitude [.., n]
    (apply ``hardware.detect_magnitude`` on top for the detector's noise
    and sensitivity floor).  ``v_plan``/``u_plan`` default to the Clements
    rectangle; analytic Reck programs run in the same fused sweep.  With
    ``hardware``, cell coefficients come from the imperfection model, with
    phase noise drawn from ``key_v``/``key_u`` exactly like the reference
    path.  Differentiable w.r.t. both mesh params, ``atten``, ``scale``
    and ``x`` through the fused kernel VJP.
    """
    if interpret is None:
        interpret = default_interpret()
    sched_v = (clements_schedule(n) if v_plan is None
               else schedule_from_plan(v_plan))
    sched_u = (clements_schedule(n) if u_plan is None
               else schedule_from_plan(u_plan))
    KERNEL_PATH_CALLS["rfnn_linear"] += 1
    return _rfnn_linear_impl(sched_v, sched_u, hardware, block_b, interpret,
                             v_params, atten, u_params, x,
                             jnp.asarray(scale, jnp.float32), key_v, key_u)


# ---------------------------------------------------------------------------
# Deep tiled-network megakernel: L layers x (To x Ti) tiles, one pallas_call
# per direction
# ---------------------------------------------------------------------------
#
# Everything deeper than a single mesh pair routes through here.  An
# L-layer single-mesh RFNN is the To=Ti=1 degenerate case
# (``rfnn_network``); a one-layer (To x Ti) tile grid is the L=1 case
# (``tiled_apply``); the general case is a whole deep tiled network —
# e.g. the paper's 4-layer 64x64 MNIST scale-up — in ONE kernel launch
# per direction, with the inter-layer re-detection done in VMEM (zero
# inter-layer HBM traffic).


def _layer_gains(n: int, la: dict) -> Array:
    """One layer's 12-row gain stack: g0 (input screens), g1 (attenuation +
    folded mid screens), g2 (digital scale + output screen)."""
    v_params, u_params = la["v"], la["u"]
    g0 = jnp.ones((n,), jnp.complex64)
    if v_params.get("alpha_in") is not None:
        g0 = g0 * jnp.exp(-1j * v_params["alpha_in"].astype(jnp.complex64))
    g1 = la["atten"].astype(jnp.complex64)
    if v_params.get("alpha") is not None:
        g1 = g1 * jnp.exp(-1j * v_params["alpha"].astype(jnp.complex64))
    if u_params.get("alpha_in") is not None:
        g1 = g1 * jnp.exp(-1j * u_params["alpha_in"].astype(jnp.complex64))
    g2 = jnp.full((n,), jnp.asarray(la.get("scale", 1.0), jnp.complex64))
    if u_params.get("alpha") is not None:
        g2 = g2 * jnp.exp(-1j * u_params["alpha"].astype(jnp.complex64))
    rows = []
    for g in (g0, g1, g2):
        rows += [jnp.real(g[0::2]), jnp.imag(g[0::2]),
                 jnp.real(g[1::2]), jnp.imag(g[1::2])]
    return jnp.stack(rows).astype(jnp.float32)  # [12, P]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _deepgrid_planes(deep, block_b, nb, interpret, detect_last,
                     coef_v, coef_u, gains, xer, xei, xor, xoi):
    call = givens_mesh.deepgrid_pallas_call(
        deep.n, deep.n_layers, deep.to, deep.ti, deep.n_columns,
        block_b, nb, detect_last, interpret)
    pv, pu = deep_grid_parity_arrays(deep)
    return tuple(call(coef_v, pv, coef_u, pu, gains, xer, xei, xor, xoi))


def _deepgrid_planes_fwd(deep, block_b, nb, interpret, detect_last,
                         coef_v, coef_u, gains, xer, xei, xor, xoi):
    call = givens_mesh.deepgrid_fwd_pallas_call(
        deep.n, deep.n_layers, deep.to, deep.ti, deep.n_columns,
        block_b, nb, detect_last, interpret)
    pv, pu = deep_grid_parity_arrays(deep)
    n_out = 2 if detect_last else 4
    outs = call(coef_v, pv, coef_u, pu, gains, xer, xei, xor, xoi)
    # residuals: coefficients/gains + the input planes + every tile's two
    # pre-gain stage boundaries — everything inside a mesh is recomputed
    # by the reversed inverse sweep, and every layer-boundary state is an
    # elementwise function of the saved post-U stages
    return tuple(outs[:n_out]), (coef_v, coef_u, gains,
                                 (xer, xei, xor, xoi), tuple(outs[n_out:]))


def _deepgrid_planes_bwd(deep, block_b, nb, interpret, detect_last, res,
                         cot):
    coef_v, coef_u, gains, xplanes, stages = res
    call = givens_mesh.deepgrid_bwd_pallas_call(
        deep.n, deep.n_layers, deep.to, deep.ti, deep.n_columns,
        block_b, nb, detect_last, interpret)
    pv, pu = deep_grid_parity_arrays(deep)
    dcv, dcu, dg, dxer, dxei, dxor, dxoi = call(
        givens_mesh.inverse_coefficients(coef_v),
        givens_mesh.adjoint_coefficients(coef_v), pv,
        givens_mesh.inverse_coefficients(coef_u),
        givens_mesh.adjoint_coefficients(coef_u), pu,
        gains, *xplanes, *stages, *cot)
    # the combine's transpose (sum of each input tile's cotangent over the
    # To rows) already ran inside the kernel — dx comes back as [B, Ti, P]
    return dcv, dcu, dg, dxer, dxei, dxor, dxoi


_deepgrid_planes.defvjp(_deepgrid_planes_fwd, _deepgrid_planes_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pack_deep_grid_impl(deep, hardware, layers):
    """Stacked [L, To, Ti, C, 8, P] coefficients + [L, To, Ti, 12, P]
    gains for the deep megakernel, each tile's columns gathered onto the
    aligned kernel columns (identity cells where it sits a column out
    and in the padding).  Per-tile gains use the layer layout (g0 input
    screens, g1 attenuation + folded mid screens, g2 digital scale +
    output screen)."""
    src = deep_grid_column_sources(deep)               # [2, L, To, Ti, C]
    # Tiles that share their schedules, column alignment and argument
    # structure pack as ONE vmapped computation, so the traced program
    # (and the chip compiler's time on it) grows with the number of
    # distinct tile kinds rather than with L * To * Ti.
    groups: dict = {}
    for l, o, i in np.ndindex(deep.n_layers, deep.to, deep.ti):
        ta = layers[l][o][i]
        kind = (deep.layers[l][o][i], src[0, l, o, i].tobytes(),
                src[1, l, o, i].tobytes(), jax.tree.structure(ta))
        groups.setdefault(kind, []).append((l, o, i))
    order, outs = [], []
    for positions in groups.values():
        l0, o0, i0 = positions[0]
        sv, su = deep.layers[l0][o0][i0]

        def one(ta, sv=sv, su=su, sv_src=src[0, l0, o0, i0],
                su_src=src[1, l0, o0, i0]):
            return (align_columns(_mesh_coefficients(
                        sv, ta["v"], hardware, ta.get("key_v")), sv_src),
                    align_columns(_mesh_coefficients(
                        su, ta["u"], hardware, ta.get("key_u")), su_src),
                    _layer_gains(deep.n, ta))

        stacked = jax.tree.map(lambda *a: jnp.stack(a),
                               *(layers[l][o][i] for l, o, i in positions))
        outs.append(jax.vmap(one)(stacked))
        order += [np.ravel_multi_index(p, (deep.n_layers, deep.to, deep.ti))
                  for p in positions]
    lead = (deep.n_layers, deep.to, deep.ti)
    inverse = np.argsort(order)

    def place(parts):
        flat = jnp.concatenate(parts)
        if order != sorted(order):
            flat = jnp.take(flat, jnp.asarray(inverse), axis=0)
        return flat.reshape(lead + flat.shape[1:])

    return tuple(place(parts) for parts in zip(*outs))


def _contains_tracer(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(tree))


class _LeafIdCache:
    """Small LRU keyed on (static key, id of every pytree leaf).

    Holding strong references to the keyed leaves keeps their ids from
    being recycled, so a hit is exact: same schedule, same (immutable)
    parameter arrays -> same packed coefficients, with zero packing work.
    Tracer leaves must bypass this cache (they are trace-local).
    """

    def __init__(self, maxsize: int = 8):
        self._maxsize = maxsize
        self._entries: dict[tuple, tuple] = {}  # key -> (leaves, value)

    def get_or_build(self, static_key, tree, builder):
        key = (static_key,) + tuple(id(l) for l in jax.tree.leaves(tree))
        hit = self._entries.get(key)
        if hit is not None:
            return hit[1]
        value = builder()
        while len(self._entries) >= self._maxsize:
            self._entries.pop(next(iter(self._entries)))
        self._entries[key] = (jax.tree.leaves(tree), value)
        return value

    def clear(self):
        self._entries.clear()


_DEEPGRID_PACK_CACHE = _LeafIdCache(maxsize=8)

_SHARED_LEAF_CACHES: dict = {}


def memoize_by_leaf_ids(static_key, tree, builder):
    """Leaf-identity memoization for derived-parameter pipelines.

    Callers (e.g. ``AnalogSequence``) use this to keep *derived* arrays
    (sigmoid'd attenuations, quantized phases, packed coefficients) stable
    across eager calls with the same underlying parameters, which is what
    lets the downstream pack cache hit.  Tracer leaves bypass (trace-local
    values must never be cached); the per-static-key LRU is small and
    holds strong leaf references so ids cannot be recycled.
    """
    if _contains_tracer(tree):
        return builder()
    cache = _SHARED_LEAF_CACHES.setdefault(static_key, _LeafIdCache())
    return cache.get_or_build(static_key, tree, builder)


def pack_deep_grid(layers, *, n: int, plans=None,
                   hardware: hw_lib.HardwareModel | None = None,
                   _event: str = "deep_apply"):
    """Emit the deep megakernel inputs for L layers of (To x Ti) tiles.

    ``layers``: nested ``[L][To][Ti]`` sequence of per-tile dicts with
    keys ``v``/``u`` (mesh params, optional ``alpha_in``/``alpha``
    screens), ``atten`` ([n] diagonal), optional ``scale`` (digital
    gamma) and, with ``hardware``, optional ``key_v``/``key_u``
    phase-noise keys.  ``plans``: matching ``[L][To][Ti]`` nesting of
    ``(v_plan, u_plan)`` pairs (or ``None`` entries for Clements).

    Returns ``(deep, (coef_v, coef_u, gains))``: the static
    :class:`~repro.kernels.schedule.DeepGridSchedule` plus the stacked
    ``[L, To, Ti, C, 8, P]`` coefficient tensors and
    ``[L, To, Ti, 12, P]`` gain rows, identity-padded to the
    network-wide column count — ready for :func:`deep_apply`'s
    ``packed=``.  Results go through the leaf-identity pack cache
    (``PACK_EVENTS``): repeat calls with the same (immutable) tile
    arrays do zero packing work; tracer leaves bypass so gradients flow
    through packing.
    """
    layers = tuple(tuple(tuple(row) for row in layer) for layer in layers)
    deep = deep_grid_schedule(n, len(layers), len(layers[0]),
                              len(layers[0][0]), plans)

    def build():
        PACK_EVENTS[_event] += 1
        return _pack_deep_grid_impl(deep, hardware, layers)

    if _contains_tracer(layers):
        return deep, build()
    return deep, _DEEPGRID_PACK_CACHE.get_or_build(
        (deep, hardware), layers, build)


#: Working-set target for one buffer of a call's batch-blocked planes
#: (the pipeline double-buffers them; the resident coefficient tensors
#: and the limit they need are counted by the kernel builders).
_VMEM_TARGET = 4 * 1024 * 1024


def _vmem_auto_block(b: int, block_b: int | None, n: int,
                     planes_per_row: int, interpret: bool) -> int:
    """The one VMEM-budget batch-block helper (every kernel's auto-block
    is this function with its own plane count).

    ``None`` sizes the block so ``planes_per_row`` [block, P] f32 planes
    fit the target, then shrinks for small batches.  On the chip a
    plane's P lanes occupy whole 128-lane tiles (see
    ``givens_mesh.vmem_bytes``), so a 16-channel tile's 8-lane plane
    costs 16x its data and deep grids get blocks of a few sublane rows.
    The interpreter has no tiled VMEM: there the data bytes set the
    block, and a block that fits a host cache rides through all L
    layers while hot.
    """
    if block_b is None:
        p = n // 2
        lanes = p if interpret else -(-p // 128) * 128
        per_row = planes_per_row * lanes * 4
        block_b = max(8, min(1024, _VMEM_TARGET // per_row // 8 * 8))
    return _auto_block(b, block_b)


def _deep_planes_per_row(deep) -> int:
    """Resident [block, P] planes per batch row for the deep kernel: 8
    stage-residual planes per tile per layer, 4 input and 4 output planes
    per tile column / row slot, ~4 working planes.  Reduces to the
    network kernel's ``8 L + 12`` at To = Ti = 1."""
    return 8 * deep.n_layers * deep.to * deep.ti + 4 * deep.ti \
        + 4 * deep.to + 4


def _split_tile_planes(xt):
    """[B, Ti, n] complex -> 4 de-interleaved [Ti, B, P] f32 planes (the
    kernel's tile-major layout)."""
    xt = jnp.swapaxes(xt, 0, 1)
    xe, xo = xt[..., 0::2], xt[..., 1::2]
    return (jnp.real(xe).astype(jnp.float32),
            jnp.imag(xe).astype(jnp.float32),
            jnp.real(xo).astype(jnp.float32),
            jnp.imag(xo).astype(jnp.float32))


def _merge_deep_out(outs, detect_last, to, n, b_orig, batch_shape):
    """Kernel output planes [To, B, P] -> [..., To*n] (real magnitudes or
    complex)."""
    outs = [jnp.swapaxes(t, 0, 1) for t in outs]   # [B, To, P]
    if detect_last:
        oe, oo = outs
        y = jnp.stack([oe, oo], axis=-1).reshape((-1, to * n))[:b_orig]
        return y.reshape(batch_shape + (to * n,))
    oer, oei, oor, ooi = outs
    ye = oer + 1j * oei
    yo = oor + 1j * ooi
    y = jnp.stack([ye, yo], axis=-1).reshape((-1, to * n))[:b_orig]
    return y.astype(jnp.complex64).reshape(batch_shape + (to * n,))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _deep_apply_impl(deep, block_b, interpret, detect_last, trace_key,
                     coef_v, coef_u, gains, x):
    TRACE_COUNTS[trace_key] += 1  # python side effect: trace time only
    n, to, ti = deep.n, deep.to, deep.ti
    batch_shape = x.shape[:-1]
    xt = x.reshape((-1, ti, n)).astype(jnp.complex64)
    bb = _vmem_auto_block(xt.shape[0], block_b, n,
                          _deep_planes_per_row(deep), interpret)
    xt, b_orig = _pad_batch(xt, bb)
    nb = xt.shape[0] // bb
    outs = _deepgrid_planes(deep, bb, nb, interpret, detect_last,
                            coef_v, coef_u, gains, *_split_tile_planes(xt))
    return _merge_deep_out(outs, detect_last, to, n, b_orig, batch_shape)


# ---------------------------------------------------------------------------
# Sharded deep megakernel: (tile-row x batch) grid over a jax.Mesh
# ---------------------------------------------------------------------------
#
# Past one device's VMEM, each layer's (To x Ti) grid shards over a
# 2-axis ``jax.Mesh`` via shard_map: every device runs the *identical*
# single-layer pallas call on its (To/rows)-row slab with its batch
# shard.  The forward needs no collective — every row's combine is local
# to the device holding that row.  The backward's input cotangent is the
# transpose of the row combine: the kernel sums its local rows' partials
# in VMEM, and a ``psum`` over the row axis finishes the reduction — the
# matched-line power combiner's exact distributed analog.  Depth does NOT
# fuse across devices: a layer's re-detected outputs are each next
# layer's *full* input, so L > 1 runs as a python chain of single-layer
# sharded calls (one resharding row->replicated per boundary, inserted by
# GSPMD), with the boundary |detect| taken inside the kernel
# (``detect_last=True``) so its zero-guarded backward keeps padded batch
# rows grad-exact.
#
# Coefficient operands enter the shard_map REPLICATED (in_spec P()) and
# each device slices its own row slab (axis 1 of [L, To, Ti, ...])
# in-body by ``axis_index``; the backward all-gathers the coefficient
# grads back to replicated.  They are small (L*To*Ti*C*8*P floats), and
# splitting them on the row axis instead trips a GSPMD bug on this jax
# version: under an enclosing jit on a multi-axis mesh, concatenate/
# stack-built values (exactly what ``pack_deep_grid`` emits when traced,
# e.g. under ``jit(grad(...))``) feeding a shard_map along a partitioned
# axis get mis-partitioned — row shards arrive summed, corrupting forward
# and backward alike.  Replicated operands take the all-gather path,
# which is sound (the batch planes are safe either way: they are built
# with ``jnp.pad`` + strided slices — see ``_pad_batch``).


def _shard_specs(row_axis: str, data_axis: str):
    from repro.parallel.sharding import tile_grid_shard_specs

    return tile_grid_shard_specs(row_axis, data_axis)


def _shard_map(body, mesh, in_specs, out_specs):
    from repro.parallel.sharding import shard_map_compat

    return shard_map_compat(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)


def _row_slab(row_axis, to_local, axis):
    """In-body slice of a device's tile-row slab from a replicated
    operand whose ``axis`` is the To axis."""
    def sl(a):
        r = jax.lax.axis_index(row_axis)
        return jax.lax.dynamic_slice_in_dim(a, r * to_local, to_local, axis)
    return sl


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
def _deepgrid_planes_sharded(deep, layer, mesh, row_axis, data_axis,
                             block_b, nb, interpret, detect_last,
                             coef_v, coef_u, gains, xer, xei, xor, xoi):
    specs = _shard_specs(row_axis, data_axis)
    to_local = deep.to // mesh.shape[row_axis]
    pv, pu = deep_grid_parity_arrays(deep)
    pv, pu = pv[layer:layer + 1], pu[layer:layer + 1]

    def body(cv, pv, cu, pu, g, xer, xei, xor, xoi):
        sl = _row_slab(row_axis, to_local, 1)
        call = givens_mesh.deepgrid_pallas_call(
            deep.n, 1, to_local, deep.ti, deep.n_columns, block_b, nb,
            detect_last, interpret)
        return tuple(call(sl(cv), pv, sl(cu), pu, sl(g),
                          xer, xei, xor, xoi))

    n_out = 2 if detect_last else 4
    fn = _shard_map(body, mesh,
                    (specs.coef,) * 5 + (specs.x_plane,) * 4,
                    (specs.o_plane,) * n_out)
    return fn(coef_v, pv, coef_u, pu, gains, xer, xei, xor, xoi)


def _deepgrid_planes_sharded_fwd(deep, layer, mesh, row_axis, data_axis,
                                 block_b, nb, interpret, detect_last,
                                 coef_v, coef_u, gains, xer, xei, xor, xoi):
    specs = _shard_specs(row_axis, data_axis)
    to_local = deep.to // mesh.shape[row_axis]
    pv, pu = deep_grid_parity_arrays(deep)
    pv, pu = pv[layer:layer + 1], pu[layer:layer + 1]

    def body(cv, pv, cu, pu, g, xer, xei, xor, xoi):
        sl = _row_slab(row_axis, to_local, 1)
        call = givens_mesh.deepgrid_fwd_pallas_call(
            deep.n, 1, to_local, deep.ti, deep.n_columns, block_b, nb,
            detect_last, interpret)
        return tuple(call(sl(cv), pv, sl(cu), pu, sl(g),
                          xer, xei, xor, xoi))

    n_out = 2 if detect_last else 4
    fn = _shard_map(body, mesh,
                    (specs.coef,) * 5 + (specs.x_plane,) * 4,
                    (specs.o_plane,) * n_out + (specs.stage,) * 8)
    outs = fn(coef_v, pv, coef_u, pu, gains, xer, xei, xor, xoi)
    # residuals keep their shardings inside the enclosing jit: coefficient
    # stacks stay replicated, stage planes stay (row x batch)-split, so
    # the backward's shard_map consumes them without any resharding
    return tuple(outs[:n_out]), (coef_v, coef_u, gains,
                                 (xer, xei, xor, xoi), tuple(outs[n_out:]))


def _deepgrid_planes_sharded_bwd(deep, layer, mesh, row_axis, data_axis,
                                 block_b, nb, interpret, detect_last, res,
                                 cot):
    coef_v, coef_u, gains, xplanes, stages = res
    specs = _shard_specs(row_axis, data_axis)
    to_local = deep.to // mesh.shape[row_axis]
    pv, pu = deep_grid_parity_arrays(deep)
    pv, pu = pv[layer:layer + 1], pu[layer:layer + 1]

    def body(cv, pv, cu, pu, g, xer, xei, xor, xoi, *rest):
        sl = _row_slab(row_axis, to_local, 1)
        cv, cu, g = sl(cv), sl(cu), sl(g)
        call = givens_mesh.deepgrid_bwd_pallas_call(
            deep.n, 1, to_local, deep.ti, deep.n_columns, block_b, nb,
            detect_last, interpret)
        dcv, dcu, dg, dxer, dxei, dxor, dxoi = call(
            givens_mesh.inverse_coefficients(cv),
            givens_mesh.adjoint_coefficients(cv), pv,
            givens_mesh.inverse_coefficients(cu),
            givens_mesh.adjoint_coefficients(cu), pu,
            g, xer, xei, xor, xoi, *rest)
        # the kernel already summed its local rows' input-cotangent
        # partials; the psum over the row axis completes the transpose of
        # the (now distributed) row combine
        dx = tuple(jax.lax.psum(d, row_axis)
                   for d in (dxer, dxei, dxor, dxoi))
        # coefficient grads: psum over the batch axis (the usual DP
        # gradient reduction of per-shard partials), then an all-gather
        # over the row axis (axis 1 = To of the [L, To, Ti, ...] stacks)
        # hands every device the full replicated grad — matching the
        # replicated primal operands, so the packing transpose outside
        # never consumes a row-partitioned value
        dcv, dcu, dg = (
            jax.lax.all_gather(jax.lax.psum(d, data_axis), row_axis,
                               axis=1, tiled=True)
            for d in (dcv, dcu, dg))
        return (dcv, dcu, dg) + dx

    n_cot = 2 if detect_last else 4
    fn = _shard_map(
        body, mesh,
        (specs.coef,) * 5 + (specs.x_plane,) * 4 + (specs.stage,) * 8
        + (specs.o_plane,) * n_cot,
        (specs.coef,) * 3 + (specs.dx_plane,) * 4)
    return tuple(fn(coef_v, pv, coef_u, pu, gains,
                    *xplanes, *stages, *cot))


_deepgrid_planes_sharded.defvjp(_deepgrid_planes_sharded_fwd,
                                _deepgrid_planes_sharded_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _deep_apply_sharded_impl(deep, mesh, row_axis, data_axis, block_b,
                             interpret, detect_last, trace_key,
                             coef_v, coef_u, gains, x):
    TRACE_COUNTS[trace_key] += 1  # python side effect: trace time only
    n, to, ti = deep.n, deep.to, deep.ti
    batch_shape = x.shape[:-1]
    xt = x.reshape((-1, ti, n)).astype(jnp.complex64)
    n_data = mesh.shape[data_axis]
    bb = _vmem_auto_block(max(1, -(-xt.shape[0] // n_data)), block_b, n,
                          _deep_planes_per_row(deep), interpret)
    # every device's batch shard must tile into whole blocks
    xt, b_orig = _pad_batch(xt, bb * n_data)
    nb = xt.shape[0] // n_data // bb
    planes = _split_tile_planes(xt)
    outs = None
    for l in range(deep.n_layers):
        last = l == deep.n_layers - 1
        outs = _deepgrid_planes_sharded(
            deep, l, mesh, row_axis, data_axis, bb, nb, interpret,
            detect_last if last else True,
            coef_v[l:l + 1], coef_u[l:l + 1], gains[l:l + 1], *planes)
        if not last:
            # layer boundary: the re-detected To rows are the next
            # layer's Ti real inputs (To == Ti whenever L > 1); the
            # boundary |detect| ran inside the kernel, so its backward is
            # the zero-guarded z/|z| — exact zeros on padded batch rows
            oe, oo = outs
            zero = jnp.zeros_like(oe)
            planes = (oe, zero, oo, zero)
    return _merge_deep_out(outs, detect_last, to, n, b_orig, batch_shape)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def deep_apply(layers, x: Array, *, n: int, plans=None,
               hardware: hw_lib.HardwareModel | None = None,
               block_b: int | None = None,
               interpret: bool | None = None, packed=None,
               readout: str = "magnitude",
               mesh=None, row_axis: str = "rows",
               data_axis: str = "data",
               _trace_key: str = "deep_apply") -> Array:
    """A whole deep tiled network — L layers of a (To x Ti) analog tile
    grid — in ONE ``pallas_call`` per direction.

    ``layers``/``plans``/``hardware``: see :func:`pack_deep_grid`.  ``x``
    is ``[..., Ti*n]``; each layer's rows combine their Ti tile outputs
    coherently in VMEM (the matched-line power combiner) and the
    re-detected row magnitudes feed the next layer *inside the kernel* —
    inter-layer activations never touch HBM.  ``readout`` picks the last
    layer's output: ``"magnitude"`` (default) applies the |detect| in
    kernel and returns the real ``[..., To*n]`` magnitudes;
    ``"complex"`` returns the combined complex row states so digital
    readout modes (real part, detector noise) compose on top, outside
    the kernel.  The custom VJP unwinds all layers in reverse inside one
    backward kernel from the saved per-tile stage boundaries, with the
    zero-guarded |detect| backward at every layer boundary.

    ``packed``: an explicit :func:`pack_deep_grid` result — offline
    compilation (``repro.compile.lower_deep``) hands it back here and
    skips the pack/cache lookup entirely, so its zero-packing guarantee
    cannot be evicted out from under it by other users of the shared
    cache.  ``block_b=None`` sizes the batch block to the kernel's VMEM
    target (large blocks for small grids, shrinking with n, L, To, Ti).

    ``mesh``: an optional 2-axis ``jax.sharding.Mesh`` — each layer's
    grid then shards over ``(row_axis, data_axis)`` via shard_map: tile
    rows split over ``row_axis`` (To no longer has to fit one device),
    batch over ``data_axis``, each device running the identical
    row-local pallas call; depth runs as a chain of single-layer sharded
    launches (a layer's outputs are the next layer's full input, so
    depth cannot fuse across devices).  Semantics (fwd and VJP) match
    the single-device call to float tolerance; requires
    ``To % mesh.shape[row_axis] == 0``.
    """
    if interpret is None:
        interpret = default_interpret()
    if readout not in ("magnitude", "complex"):
        raise ValueError(f"readout must be 'magnitude' or 'complex', "
                         f"got {readout!r}")
    KERNEL_PATH_CALLS["deep_apply"] += 1
    if packed is None:
        packed = pack_deep_grid(layers, n=n, plans=plans, hardware=hardware)
    deep, tensors = packed
    detect_last = readout == "magnitude"
    if x.shape[-1] != deep.ti * deep.n:
        raise ValueError(
            f"expected trailing dim {deep.ti * deep.n} "
            f"(Ti={deep.ti} tiles of n={deep.n}), got {x.shape}")
    if mesh is None:
        return _deep_apply_impl(deep, block_b, interpret, detect_last,
                                _trace_key, *tensors, x)
    KERNEL_PATH_CALLS["deep_apply_sharded"] += 1
    for ax in (row_axis, data_axis):
        if ax not in mesh.shape:
            raise ValueError(f"mesh has no axis {ax!r}: {dict(mesh.shape)}")
    if deep.to % mesh.shape[row_axis]:
        raise ValueError(
            f"To={deep.to} tile rows do not shard over "
            f"{mesh.shape[row_axis]} devices on axis {row_axis!r}")
    return _deep_apply_sharded_impl(deep, mesh, row_axis, data_axis,
                                    block_b, interpret, detect_last,
                                    _trace_key + "_sharded", *tensors, x)


# ---------------------------------------------------------------------------
# Degenerate-case wrappers: the network (To=Ti=1) and one-layer tile grid
# ---------------------------------------------------------------------------

def pack_network(layers, *, n: int, plans=None,
                 hardware: hw_lib.HardwareModel | None = None):
    """Emit the megakernel inputs for an L-layer RFNN program.

    The To=Ti=1 degenerate case of :func:`pack_deep_grid`: ``layers`` is
    a flat per-layer sequence of dicts (keys ``v``/``u``, ``atten``,
    optional ``scale``/``key_v``/``key_u``) and ``plans`` a flat
    per-layer sequence of ``(v_plan, u_plan)`` pairs.  Returns
    ``(deep, (coef_v, coef_u, gains))`` in the deep-grid layout
    (``[L, 1, 1, C, 8, P]`` coefficients), ready for
    :func:`rfnn_network`'s ``packed=``.  Pack-cache semantics are
    :func:`pack_deep_grid`'s, ticking ``PACK_EVENTS["rfnn_network"]``.
    """
    deep_layers = tuple(((la,),) for la in layers)
    deep_plans = (None if plans is None
                  else tuple(((p,),) for p in plans))
    return pack_deep_grid(deep_layers, n=n, plans=deep_plans,
                          hardware=hardware, _event="rfnn_network")


def rfnn_network(layers, x: Array, *, n: int,
                 plans=None,
                 hardware: hw_lib.HardwareModel | None = None,
                 block_b: int | None = None,
                 interpret: bool | None = None,
                 packed=None) -> Array:
    """The fused L-layer RFNN |.. |scale_l * U_l(D_l(V_l ..))| .. | sweep.

    A thin To=Ti=1 wrapper over :func:`deep_apply` with the in-kernel
    |detect| readout — numerics, argument shapes (see
    :func:`pack_network`) and pack-cache behavior are unchanged from the
    dedicated network megakernel this path replaced: one ``pallas_call``
    forward and one backward for the whole network, inter-layer
    activations never leaving VMEM, packing cached per (schedule, param
    identity) so serving steady state does zero packing work.
    """
    KERNEL_PATH_CALLS["rfnn_network"] += 1
    if packed is None:
        packed = pack_network(layers, n=n, plans=plans, hardware=hardware)
    return deep_apply(None, x, n=n, block_b=block_b, interpret=interpret,
                      packed=packed, readout="magnitude",
                      _trace_key="rfnn_network")


def pack_tile_grid(tiles, *, n: int, plans=None,
                   hardware: hw_lib.HardwareModel | None = None):
    """Emit the kernel inputs for a one-layer (To x Ti) grid of tiles.

    The L=1 degenerate case of :func:`pack_deep_grid`: ``tiles`` is a
    nested ``[To][Ti]`` sequence of per-tile dicts and ``plans`` a
    matching nesting of ``(v_plan, u_plan)`` pairs.  Returns
    ``(deep, (coef_v, coef_u, gains))`` in the deep-grid layout
    (``[1, To, Ti, C, 8, P]`` coefficients), ready for
    :func:`tiled_apply`'s ``packed=``.  Pack-cache semantics are
    :func:`pack_deep_grid`'s, ticking ``PACK_EVENTS["tiled_apply"]``.
    """
    deep_layers = (tuple(tuple(row) for row in tiles),)
    deep_plans = (None if plans is None
                  else (tuple(tuple(row) for row in plans),))
    return pack_deep_grid(deep_layers, n=n, plans=deep_plans,
                          hardware=hardware, _event="tiled_apply")


def tiled_apply(tiles, x: Array, *, n: int, plans=None,
                hardware: hw_lib.HardwareModel | None = None,
                block_b: int | None = None,
                interpret: bool | None = None, packed=None,
                mesh=None, row_axis: str = "rows",
                data_axis: str = "data") -> Array:
    """A (To x Ti) tile-grid matmul ``sum_i gamma U(D(V x_i))`` per row,
    in ONE ``pallas_call`` per direction.

    A thin L=1 wrapper over :func:`deep_apply` with the ``"complex"``
    readout — numerics, argument shapes (see :func:`pack_tile_grid`),
    pack-cache behavior and the ``mesh=`` sharded path are unchanged
    from the dedicated tile-grid megakernel this path replaced.  ``x``
    is ``[..., Ti*n]`` and the result is the **complex** combined row
    output ``[..., To*n]`` — the matched-line power combiner sums the Ti
    tile outputs of each row coherently in VMEM, and the readout mode
    (|.| detection, real part) plus detector noise compose on top,
    outside the kernel.
    """
    KERNEL_PATH_CALLS["tiled_apply"] += 1
    if packed is None:
        packed = pack_tile_grid(tiles, n=n, plans=plans, hardware=hardware)
    if mesh is not None:
        KERNEL_PATH_CALLS["tiled_apply_sharded"] += 1
    return deep_apply(None, x, n=n, block_b=block_b, interpret=interpret,
                      packed=packed, readout="complex", mesh=mesh,
                      row_axis=row_axis, data_axis=data_axis,
                      _trace_key="tiled_apply")


