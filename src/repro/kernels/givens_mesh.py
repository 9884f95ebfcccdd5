"""Pallas TPU kernels for the RF mesh apply — the paper's MVM hot spot.

TPU adaptation of the analog propagation: one mesh column is a set of
independent 2x2 complex rotations on channel pairs — pure VPU elementwise
work once channels are de-interleaved into even/odd (re, im) planes of shape
[batch, N/2].  The kernels keep a batch panel **resident in VMEM** and run
all C columns in-register/VMEM, the TPU analogue of the RF signal passing
through all S = N(N-1)/2 cells without intermediate storage (HBM traffic is
2 reads + 2 writes of the panel total, instead of per-column round trips).

Layout choices (see DESIGN.md):
  * planes [B, P] with P = N/2 on the lane dimension (128-aligned for N>=256)
    in the single-mesh and fused-layer kernels; the deep-grid kernels turn
    them to [P, B], the batch on lanes (see the deep-grid notes below);
  * coefficients [C, 8, P]: 8 rows = (t00, t01, t10, t11) x (re, im) per pair
    slot, broadcast over the batch sublanes.  The 2x2 cells are **arbitrary
    complex matrices** — ideal unitary rotations and the hardware model's
    lossy/imbalanced cells share the same layout and the same sweep;
  * a [C, 1] int32 parity input selects each column's pairing: parity 0
    rotates (even_i, odd_i), parity 1 rotates (odd_i, even_{i+1}) via
    shifted slices — static slicing only, no gathers.  Any adjacent-pair
    layout (Clements rectangle, triangular Reck programs, greedy-packed
    schedules) lowers to a parity sequence (see ``repro.kernels.schedule``).

Kernels:
  * ``mesh_kernel`` — one mesh (the paper's T(N), Eq. 28, ideal or not).
  * ``rfnn_linear_kernel`` — fused analog linear layer
    V-mesh -> diag gain -> U-mesh -> |detect| (paper Eq. 31 + Fig. 14),
    one VMEM residency for the whole layer.
  * ``deepgrid_kernel`` — the general deep tiled network: L layers, each
    a (To x Ti) grid of analog tile processors, in ONE VMEM residency.
    Every layer sweeps all input tiles through their meshes, coherently
    combines each tile row's outputs (matched-line power combiner) and
    re-detects the combined rows in VMEM to feed the next layer — zero
    inter-layer HBM traffic, the TPU analogue of the paper's end-to-end
    analog signal path (Sec. V, incl. the 4-layer MNIST scale-up).  The
    L-layer single-mesh RFNN (L x 1 x 1) and the one-layer tile grid
    (1 x To x Ti) are its degenerate cases — there are no separate
    network/tile-grid kernels.
  * ``mesh_bwd_kernel`` / ``rfnn_linear_bwd_kernel`` — the custom VJPs.
    The backward pass re-runs the column sequence *in reverse*, carrying
    two coefficient tensors: the per-cell analytic **2x2 inverse** rebuilds
    each column's input state from the saved forward output (for unitary
    cells this degenerates to the PR-1 conjugate-transpose trick), while
    the **adjoint** (conjugate transpose) propagates the cotangent — the
    transpose of the real-representation Jacobian of ``y = T x`` is ``T^H``
    for *any* complex ``T``, unitary or not.  Per-column coefficient
    gradients are accumulated into a [C, 8, P] output revisited across
    batch-grid steps.  See DESIGN.md ("Backward pass").

Validated against ``ref.py`` and the hardware-model reference in interpret
mode on the CPU (the test suite), compiled for a described TPU v5e in
``tests/test_tpu_compile.py``, and run on the chip by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _rotate(cc, ar, ai, br, bi):
    """Apply the 2x2 complex rotations in an 8-row coefficient slice."""
    xr, xi = _cmul(cc[0], cc[1], ar, ai)
    yr, yi = _cmul(cc[2], cc[3], br, bi)
    a2r, a2i = xr + yr, xi + yi
    xr, xi = _cmul(cc[4], cc[5], ar, ai)
    yr, yi = _cmul(cc[6], cc[7], br, bi)
    return a2r, a2i, xr + yr, xi + yi


def _column_body(coef_ref, parity_ref, c, state):
    """One mesh column on the de-interleaved planes."""
    er, ei, orr, oi = state
    cc = coef_ref[c]  # [8, P] dynamic-sliced from VMEM

    def even(_):
        a2r, a2i, b2r, b2i = _rotate(cc, er, ei, orr, oi)
        return a2r, a2i, b2r, b2i

    def odd(_):
        ar, ai = orr[:, :-1], oi[:, :-1]
        br, bi = er[:, 1:], ei[:, 1:]
        a2r, a2i, b2r, b2i = _rotate(cc[:, :-1], ar, ai, br, bi)
        ner = jnp.concatenate([er[:, :1], b2r], axis=1)
        nei = jnp.concatenate([ei[:, :1], b2i], axis=1)
        nor = jnp.concatenate([a2r, orr[:, -1:]], axis=1)
        noi = jnp.concatenate([a2i, oi[:, -1:]], axis=1)
        return ner, nei, nor, noi

    return jax.lax.cond(parity_ref[c, 0] == 0, even, odd, None)


def _run_columns(coef_ref, parity_ref, state):
    n_cols = coef_ref.shape[0]
    return jax.lax.fori_loop(
        0, n_cols,
        functools.partial(_column_body, coef_ref, parity_ref), state)


# ---------------------------------------------------------------------------
# Kernel 1: single mesh
# ---------------------------------------------------------------------------

def mesh_kernel(coef_ref, parity_ref, xer_ref, xei_ref, xor_ref, xoi_ref,
                oer_ref, oei_ref, oor_ref, ooi_ref):
    state = (xer_ref[...], xei_ref[...], xor_ref[...], xoi_ref[...])
    er, ei, orr, oi = _run_columns(coef_ref, parity_ref, state)
    oer_ref[...] = er
    oei_ref[...] = ei
    oor_ref[...] = orr
    ooi_ref[...] = oi


def _coef_spec(n_cols: int, p: int):
    return pl.BlockSpec((n_cols, 8, p), lambda i: (0, 0, 0))


def _parity_spec(n_cols: int):
    return pl.BlockSpec((n_cols, 1), lambda i: (0, 0))


def mesh_pallas_call(n: int, n_cols: int, batch_block: int,
                     n_batch_blocks: int, interpret: bool):
    p = n // 2
    plane = pl.BlockSpec((batch_block, p), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((n_batch_blocks * batch_block, p),
                                      jnp.float32)] * 4
    flops_per_block = 2 * n_cols * p * batch_block * 16
    return pl.pallas_call(
        mesh_kernel,
        grid=(n_batch_blocks,),
        in_specs=[_coef_spec(n_cols, p), _parity_spec(n_cols),
                  plane, plane, plane, plane],
        out_specs=[plane] * 4,
        out_shape=out_shape,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_block * n_batch_blocks,
            bytes_accessed=(8 * batch_block * p * 4 + n_cols * 8 * p * 4)
            * n_batch_blocks,
            transcendentals=0,
        ),
    )


# ---------------------------------------------------------------------------
# Kernel 2: fused analog linear  (V-mesh -> diag -> U-mesh -> |detect|)
# ---------------------------------------------------------------------------

def _rfnn_forward(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref, gains_ref,
                  state):
    """The fused layer body: V -> g1 -> U -> g2 -> |detect|.

    Returns detected magnitudes plus the two pre-gain stage boundaries
    (the VJP forward's residuals); the inference kernel discards them.
    """
    v = _run_columns(coef_v_ref, par_v_ref, state)
    g = gains_ref[...]  # [8, P]: g1 (even re/im, odd re/im), g2 (...)
    er, ei = _cmul(v[0], v[1], g[0], g[1])
    orr, oi = _cmul(v[2], v[3], g[2], g[3])
    u = _run_columns(coef_u_ref, par_u_ref, (er, ei, orr, oi))
    zer, zei = _cmul(u[0], u[1], g[4], g[5])
    zor, zoi = _cmul(u[2], u[3], g[6], g[7])
    oe = jnp.sqrt(zer * zer + zei * zei)   # |detect| on even channels
    oo = jnp.sqrt(zor * zor + zoi * zoi)
    return oe, oo, v, u


def rfnn_linear_kernel(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref,
                       gains_ref, xer_ref, xei_ref, xor_ref, xoi_ref,
                       oe_ref, oo_ref):
    state = (xer_ref[...], xei_ref[...], xor_ref[...], xoi_ref[...])
    oe, oo, _, _ = _rfnn_forward(coef_v_ref, par_v_ref, coef_u_ref,
                                 par_u_ref, gains_ref, state)
    oe_ref[...] = oe
    oo_ref[...] = oo


def rfnn_linear_pallas_call(n: int, n_cols_v: int, n_cols_u: int,
                            batch_block: int, n_batch_blocks: int,
                            interpret: bool):
    p = n // 2
    plane = pl.BlockSpec((batch_block, p), lambda i: (i, 0))
    gains = pl.BlockSpec((8, p), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((n_batch_blocks * batch_block, p),
                                      jnp.float32)] * 2
    flops_per_block = 2 * ((n_cols_v + n_cols_u) * p * 16 + 3 * n) \
        * batch_block
    return pl.pallas_call(
        rfnn_linear_kernel,
        grid=(n_batch_blocks,),
        in_specs=[_coef_spec(n_cols_v, p), _parity_spec(n_cols_v),
                  _coef_spec(n_cols_u, p), _parity_spec(n_cols_u),
                  gains, plane, plane, plane, plane],
        out_specs=[plane] * 2,
        out_shape=out_shape,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_block * n_batch_blocks,
            bytes_accessed=(6 * batch_block * p * 4
                            + (n_cols_v + n_cols_u) * 8 * p * 4
                            + 8 * p * 4) * n_batch_blocks,
            transcendentals=batch_block * p * 2 * n_batch_blocks,
        ),
    )


# ---------------------------------------------------------------------------
# Backward pass building blocks (the custom VJPs)
# ---------------------------------------------------------------------------

def adjoint_coefficients(coef: jax.Array, axis: int = -2) -> jax.Array:
    """Conjugate-transpose each packed 2x2 cell, column layout preserved.

    Rows (t00, t01, t10, t11) x (re, im) -> (t00*, t10*, t01*, t11*).  The
    adjoint propagates the cotangent in the reversed sweep: the transpose
    of the real-representation Jacobian of ``y = T x`` is ``T^H`` for any
    complex ``T``.  For unitary columns it is also the exact inverse, which
    is the PR-1 state-recompute trick as a special case.  The 8 rows live
    on ``axis``: -2 for the per-mesh ``[C, 8, P]`` and stacked network
    ``[L, C, 8, P]`` layouts, -1 for the deep grid's ``[.., C, P, 8]``.
    """
    idx = jnp.asarray([0, 1, 4, 5, 2, 3, 6, 7])
    sign = jnp.asarray([1.0, -1.0] * 4, coef.dtype)
    sign = sign.reshape((8,) + (1,) * (-1 - axis))
    return jnp.take(coef, idx, axis=axis) * sign


def inverse_coefficients(coef: jax.Array, eps: float = 1e-12,
                         axis: int = -2) -> jax.Array:
    """Analytic per-cell 2x2 inverse in the packed coefficient layout.

    ``inv(t) = adj(t) / det(t)`` with ``det = t00 t11 - t01 t10``.  This is
    what lets the backward sweep rebuild intermediate states for
    **non-unitary** cells (hybrid imbalance, per-cell insertion loss) with
    no per-column residuals: ``s_c = T_c^{-1} s_{c+1}``.  Hardware cells
    are well-conditioned (|det| ~ cell_gain^2); ``eps`` guards the
    identity-padded slots' neighbourhood against exact zeros.  Like
    :func:`adjoint_coefficients`, the rows live on ``axis``.
    """
    row = [jax.lax.index_in_dim(coef, k, axis % coef.ndim, keepdims=False)
           for k in range(8)]
    t00 = row[0] + 1j * row[1]
    t01 = row[2] + 1j * row[3]
    t10 = row[4] + 1j * row[5]
    t11 = row[6] + 1j * row[7]
    det = t00 * t11 - t01 * t10
    inv_det = jnp.conj(det) / jnp.maximum(jnp.abs(det) ** 2, eps)
    i00, i01 = t11 * inv_det, -t01 * inv_det
    i10, i11 = -t10 * inv_det, t00 * inv_det
    out = jnp.stack(
        [jnp.real(i00), jnp.imag(i00), jnp.real(i01), jnp.imag(i01),
         jnp.real(i10), jnp.imag(i10), jnp.real(i11), jnp.imag(i11)],
        axis=axis,
    )
    return out.astype(coef.dtype)


def _conj_dot(xr, xi, gr, gi):
    """Batch-summed conj(x) * g — one complex coefficient gradient entry."""
    return (jnp.sum(xr * gr + xi * gi, axis=0, keepdims=True),
            jnp.sum(xr * gi - xi * gr, axis=0, keepdims=True))


def _pair_grad_rows(ar, ai, br, bi, gar, gai, gbr, gbi):
    """d loss / d t for (a2, b2) = t (a, b): rows (00, 01, 10, 11)(re, im)."""
    r0, r1 = _conj_dot(ar, ai, gar, gai)
    r2, r3 = _conj_dot(br, bi, gar, gai)
    r4, r5 = _conj_dot(ar, ai, gbr, gbi)
    r6, r7 = _conj_dot(br, bi, gbr, gbi)
    return jnp.concatenate([r0, r1, r2, r3, r4, r5, r6, r7], axis=0)  # [8, P]


def _coef_grad(parity_ref, c, s_in, g_out):
    """Coefficient gradient of column ``c`` from its input state and the
    cotangent at its output, in the column's own pairing."""
    er, ei, orr, oi = s_in
    ger, gei, gor, goi = g_out

    def even(_):
        return _pair_grad_rows(er, ei, orr, oi, ger, gei, gor, goi)

    def odd(_):
        rows = _pair_grad_rows(
            orr[:, :-1], oi[:, :-1], er[:, 1:], ei[:, 1:],
            gor[:, :-1], goi[:, :-1], ger[:, 1:], gei[:, 1:])
        # wrap slot of odd columns holds no cell
        return jnp.concatenate([rows, jnp.zeros((8, 1), rows.dtype)], axis=1)

    return jax.lax.cond(parity_ref[c, 0] == 0, even, odd, None)


def _run_columns_bwd(coef_inv_ref, coef_adj_ref, parity_ref, dcoef_ref,
                     state, cot, layer=None):
    """Reversed column sweep: recompute states via the per-cell inverse,
    accumulate coefficient gradients, propagate the cotangent via the
    adjoint.  ``state`` starts at the mesh *output*.  ``layer`` (a static
    int, or a static tuple for grid layouts) selects the leading indices
    of a stacked ``[L, C, 8, P]`` / ``[To, Ti, C, 8, P]`` gradient
    accumulator — the network kernel's per-layer slot and the tile-grid
    kernel's per-tile slot."""
    n_cols = coef_inv_ref.shape[0]
    lead = (() if layer is None
            else layer if isinstance(layer, tuple) else (layer,))

    def body(k, carry):
        c = n_cols - 1 - k
        s, g = carry[0:4], carry[4:8]
        s_in = _column_body(coef_inv_ref, parity_ref, c, s)   # T_c^{-1} s_{c+1}
        grad = _coef_grad(parity_ref, c, s_in, g)
        dcoef_ref[lead + (c,)] = dcoef_ref[lead + (c,)] + grad
        g_in = _column_body(coef_adj_ref, parity_ref, c, g)   # T_c^H g_{c+1}
        return (*s_in, *g_in)

    out = jax.lax.fori_loop(0, n_cols, body, (*state, *cot))
    return out[0:4], out[4:8]


def mesh_bwd_kernel(coef_inv_ref, coef_adj_ref, parity_ref,
                    yer_ref, yei_ref, yor_ref, yoi_ref,
                    ger_ref, gei_ref, gor_ref, goi_ref,
                    dcoef_ref, dxer_ref, dxei_ref, dxor_ref, dxoi_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dcoef_ref[...] = jnp.zeros(dcoef_ref.shape, dcoef_ref.dtype)

    y = (yer_ref[...], yei_ref[...], yor_ref[...], yoi_ref[...])
    g = (ger_ref[...], gei_ref[...], gor_ref[...], goi_ref[...])
    _, gx = _run_columns_bwd(coef_inv_ref, coef_adj_ref, parity_ref,
                             dcoef_ref, y, g)
    dxer_ref[...] = gx[0]
    dxei_ref[...] = gx[1]
    dxor_ref[...] = gx[2]
    dxoi_ref[...] = gx[3]


def mesh_bwd_pallas_call(n: int, n_cols: int, batch_block: int,
                         n_batch_blocks: int, interpret: bool):
    p = n // 2
    plane = pl.BlockSpec((batch_block, p), lambda i: (i, 0))
    out_shape = (
        [jax.ShapeDtypeStruct((n_cols, 8, p), jnp.float32)]
        + [jax.ShapeDtypeStruct((n_batch_blocks * batch_block, p),
                                jnp.float32)] * 4)
    # state recompute + cotangent propagation + coefficient grads ~ 3x fwd
    flops_per_block = 3 * 2 * n_cols * p * batch_block * 16
    return pl.pallas_call(
        mesh_bwd_kernel,
        grid=(n_batch_blocks,),
        in_specs=[_coef_spec(n_cols, p), _coef_spec(n_cols, p),
                  _parity_spec(n_cols)] + [plane] * 8,
        out_specs=[_coef_spec(n_cols, p)] + [plane] * 4,
        out_shape=out_shape,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_block * n_batch_blocks,
            bytes_accessed=(12 * batch_block * p * 4 + 3 * n_cols * 8 * p * 4)
            * n_batch_blocks,
            transcendentals=0,
        ),
    )


# ---------------------------------------------------------------------------
# Fused analog linear: forward-with-residuals and backward
# ---------------------------------------------------------------------------

def rfnn_linear_fwd_kernel(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref,
                           gains_ref, xer_ref, xei_ref, xor_ref, xoi_ref,
                           oe_ref, oo_ref,
                           ver_ref, vei_ref, vor_ref, voi_ref,
                           uer_ref, uei_ref, uor_ref, uoi_ref):
    """Forward identical to ``rfnn_linear_kernel`` (same ``_rfnn_forward``
    body) but additionally writes the two stage boundaries (post-V and
    post-U, both pre-gain) — the only residuals the backward pass needs."""
    state = (xer_ref[...], xei_ref[...], xor_ref[...], xoi_ref[...])
    oe, oo, v, u = _rfnn_forward(coef_v_ref, par_v_ref, coef_u_ref,
                                 par_u_ref, gains_ref, state)
    oe_ref[...] = oe
    oo_ref[...] = oo
    ver_ref[...], vei_ref[...], vor_ref[...], voi_ref[...] = v
    uer_ref[...], uei_ref[...], uor_ref[...], uoi_ref[...] = u


def rfnn_linear_fwd_pallas_call(n: int, n_cols_v: int, n_cols_u: int,
                                batch_block: int, n_batch_blocks: int,
                                interpret: bool):
    p = n // 2
    plane = pl.BlockSpec((batch_block, p), lambda i: (i, 0))
    gains = pl.BlockSpec((8, p), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((n_batch_blocks * batch_block, p),
                                      jnp.float32)] * 10
    flops_per_block = 2 * ((n_cols_v + n_cols_u) * p * 16 + 3 * n) \
        * batch_block
    return pl.pallas_call(
        rfnn_linear_fwd_kernel,
        grid=(n_batch_blocks,),
        in_specs=[_coef_spec(n_cols_v, p), _parity_spec(n_cols_v),
                  _coef_spec(n_cols_u, p), _parity_spec(n_cols_u),
                  gains, plane, plane, plane, plane],
        out_specs=[plane] * 10,
        out_shape=out_shape,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_block * n_batch_blocks,
            bytes_accessed=(14 * batch_block * p * 4
                            + (n_cols_v + n_cols_u) * 8 * p * 4
                            + 8 * p * 4) * n_batch_blocks,
            transcendentals=batch_block * p * 2 * n_batch_blocks,
        ),
    )


def rfnn_linear_bwd_kernel(cv_inv_ref, cv_adj_ref, par_v_ref,
                           cu_inv_ref, cu_adj_ref, par_u_ref, gains_ref,
                           ver_ref, vei_ref, vor_ref, voi_ref,
                           uer_ref, uei_ref, uor_ref, uoi_ref,
                           goe_ref, goo_ref,
                           dcv_ref, dcu_ref, dg_ref,
                           dxer_ref, dxei_ref, dxor_ref, dxoi_ref):
    """Unwind |detect| -> g2 -> U-mesh -> g1 -> V-mesh in one VMEM residency.

    Saved residuals are only the two stage boundaries; everything inside a
    mesh is recomputed by the reversed inverse/adjoint column sweep.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dcv_ref[...] = jnp.zeros(dcv_ref.shape, dcv_ref.dtype)
        dcu_ref[...] = jnp.zeros(dcu_ref.shape, dcu_ref.dtype)
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)

    g = gains_ref[...]
    v = (ver_ref[...], vei_ref[...], vor_ref[...], voi_ref[...])
    u = (uer_ref[...], uei_ref[...], uor_ref[...], uoi_ref[...])
    goe, goo = goe_ref[...], goo_ref[...]

    # |detect| backward: d|z|/dz = z / |z| (0 at the non-smooth origin,
    # which also kills the padded batch rows).
    zer, zei = _cmul(u[0], u[1], g[4], g[5])
    zor, zoi = _cmul(u[2], u[3], g[6], g[7])
    me = jnp.sqrt(zer * zer + zei * zei)
    mo = jnp.sqrt(zor * zor + zoi * zoi)
    inv_e = jnp.where(me > 0, goe / jnp.where(me > 0, me, 1.0), 0.0)
    inv_o = jnp.where(mo > 0, goo / jnp.where(mo > 0, mo, 1.0), 0.0)
    gzer, gzei = inv_e * zer, inv_e * zei
    gzor, gzoi = inv_o * zor, inv_o * zoi

    # post-gain g2: gradient rows 4..7 and cotangent of the U output
    dg2 = (_conj_dot(u[0], u[1], gzer, gzei)
           + _conj_dot(u[2], u[3], gzor, gzoi))
    guer, guei = _cmul(g[4], -g[5], gzer, gzei)
    guor, guoi = _cmul(g[6], -g[7], gzor, gzoi)

    # U mesh: reversed inverse/adjoint sweep from the saved post-U boundary
    _, gh = _run_columns_bwd(cu_inv_ref, cu_adj_ref, par_u_ref, dcu_ref, u,
                             (guer, guei, guor, guoi))

    # mid gain g1: gradient rows 0..3 and cotangent of the V output
    dg1 = (_conj_dot(v[0], v[1], gh[0], gh[1])
           + _conj_dot(v[2], v[3], gh[2], gh[3]))
    gver, gvei = _cmul(g[0], -g[1], gh[0], gh[1])
    gvor, gvoi = _cmul(g[2], -g[3], gh[2], gh[3])

    dg_ref[...] = dg_ref[...] + jnp.concatenate(list(dg1) + list(dg2), axis=0)

    # V mesh: reversed inverse/adjoint sweep from the saved post-V boundary
    _, gx = _run_columns_bwd(cv_inv_ref, cv_adj_ref, par_v_ref, dcv_ref, v,
                             (gver, gvei, gvor, gvoi))
    dxer_ref[...] = gx[0]
    dxei_ref[...] = gx[1]
    dxor_ref[...] = gx[2]
    dxoi_ref[...] = gx[3]


def rfnn_linear_bwd_pallas_call(n: int, n_cols_v: int, n_cols_u: int,
                                batch_block: int, n_batch_blocks: int,
                                interpret: bool):
    p = n // 2
    plane = pl.BlockSpec((batch_block, p), lambda i: (i, 0))
    gains = pl.BlockSpec((8, p), lambda i: (0, 0))
    out_shape = (
        [jax.ShapeDtypeStruct((n_cols_v, 8, p), jnp.float32),
         jax.ShapeDtypeStruct((n_cols_u, 8, p), jnp.float32),
         jax.ShapeDtypeStruct((8, p), jnp.float32)]
        + [jax.ShapeDtypeStruct((n_batch_blocks * batch_block, p),
                                jnp.float32)] * 4)
    flops_per_block = 3 * 2 * ((n_cols_v + n_cols_u) * p * 16 + 6 * n) \
        * batch_block
    return pl.pallas_call(
        rfnn_linear_bwd_kernel,
        grid=(n_batch_blocks,),
        in_specs=[_coef_spec(n_cols_v, p), _coef_spec(n_cols_v, p),
                  _parity_spec(n_cols_v),
                  _coef_spec(n_cols_u, p), _coef_spec(n_cols_u, p),
                  _parity_spec(n_cols_u), gains] + [plane] * 10,
        out_specs=[_coef_spec(n_cols_v, p), _coef_spec(n_cols_u, p), gains]
        + [plane] * 4,
        out_shape=out_shape,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_block * n_batch_blocks,
            bytes_accessed=(14 * batch_block * p * 4
                            + 3 * (n_cols_v + n_cols_u) * 8 * p * 4
                            + 2 * 8 * p * 4) * n_batch_blocks,
            transcendentals=batch_block * p * 2 * n_batch_blocks,
        ),
    )


# ---------------------------------------------------------------------------
# Deep tiled-network megakernel: L layers of a (To x Ti) tile grid in one
# VMEM residency
# ---------------------------------------------------------------------------
#
# The general form of the paper's Sec. V scale-up: a deep network whose
# every layer is a (To x Ti) grid of analog tile processors realizing a
# large blocked matmul.  Per tile: pre-gain g0 (input phase screens) ->
# V-mesh -> mid gain g1 (attenuation + folded screens) -> U-mesh -> post
# gain g2 (digital scale + output screen); the Ti complex outputs of each
# tile row are summed in VMEM (matched-line power combiner) and the
# combined row magnitudes are re-detected *inside the kernel* to feed the
# next layer as a real signal (zero imaginary planes) — inter-layer
# activations never touch HBM.  Gains are [L, To, Ti, P, 12]: rows 0-3
# g0, 4-7 g1, 8-11 g2, each as (even re, even im, odd re, odd im).
# Coefficients stack to [L, To, Ti, C, P, 8] over the aligned column
# schedule of ``repro.kernels.schedule.DeepGridSchedule``: every column
# has ONE parity across a layer's tiles (an SMEM ``[L, C]`` table per
# mesh), tiles whose own schedules disagree sit the column out as an
# identity cell.  The pallas grid is the batch alone — inter-layer
# re-detection needs every row of a layer, so one grid step carries one
# batch block through the entire network.
#
# Layout: planes are [To, Ti, P, B] — tile axes leading, the P = n/2 pair
# slots on sublanes and the batch block on lanes (the single-mesh and
# fused-layer kernels above keep [B, P], with the batch on sublanes).  A
# 16-channel tile's P = 8 fills one sublane tile, so every vreg of a
# plane holds 128 batch rows instead of 8 pair slots.  Every
# tile-structure step stays a leading-axis operation the chip does per
# vreg with no relayout: broadcasting an input over the To rows, the row
# combine (a sum over Ti), its transpose (a sum over To), and handing a
# layer's To rows to the next layer's Ti input tiles.  The odd columns'
# pair shift moves along the sublanes.  Coefficient and gain rows load as
# [To, Ti, P, 1] columns (the row index is the last, lane axis of the
# packed tensors) and broadcast along the batch lanes, once per column
# per grid step; coefficient gradients reduce over the lanes back to that
# shape.
#
# The grid's To*Ti tiles are NOT unrolled: a layer's tiles are
# independent sweeps over the same aligned column count, so the body
# runs ONE column sweep per mesh stage over the stacked planes, each
# column taking its even/odd body from the parity table.  Only the L
# layer steps unroll (each depends on the previous layer's detected
# rows), keeping the emitted program O(L) column loops instead of
# O(L * To * Ti).
#
# In-kernel re-detection between layers is *exact*, not an approximation:
# the combined row output z is held coherently in VMEM, so |z| computed
# in-kernel is the same value the per-layer composition computes after
# its HBM round trip — same op order (multiply, add, sqrt), same floats.
#
# The last layer's readout is a static kernel variant (``detect_last``):
# True emits the detected magnitudes (the network/MNIST readout), False
# emits the combined complex planes (the tile-grid readout, where |.|,
# Re, and detector noise compose outside).  Both share the same sweep.
#
# Residuals follow the single-layer kernel's rule: everything inside a
# mesh is recomputed by the reversed inverse/adjoint sweep (no per-column
# state), but |z| is not invertible, so each tile saves its two pre-gain
# stage boundaries (post-V, post-U) — 8 stacked [L, To, Ti, P, B] planes
# total, identical to what the per-layer / per-tile composition would
# have stored, minus all the inter-layer HBM round trips and per-layer
# kernel launches.  The layer-boundary activations themselves are NOT
# stored: a layer's input is re-detected from the *previous* layer's
# saved post-U states (one cheap elementwise |sum_i g2 u_i| per row — no
# sweep), and the backward unwinds layers in reverse, converting the
# row-combine's transpose (every tile of a row sees the row's cotangent;
# each input tile sums its cotangent over rows) entirely in VMEM.
#
# VMEM: coefficient, gain and gradient-accumulator tensors are resident
# for the whole call (one buffer each); batch-blocked planes are double
# buffered.  On the chip every buffer's last two axes are tiled (8, 128):
# a plane row costs P sublanes (rounded up to 8) x 4 B, and a (P, 8)
# coefficient slab occupies a whole (8, 128) tile.  :func:`vmem_bytes`
# counts that, ``ops._vmem_auto_block`` sizes the batch block from it (a
# multiple of 128 lanes, or the whole padded batch when that fits), and a
# call whose count exceeds the default scoped limit raises
# ``vmem_limit_bytes`` to fit.

#: Mosaic's default scoped-VMEM limit on v5e.
_SCOPED_VMEM_DEFAULT = 16 * 2**20

#: Room left above the counted buffers for Mosaic's internal scratch
#: (the sweep state that does not fit the vector registers).
_VMEM_HEADROOM = 8 * 2**20


def vmem_bytes(shape) -> int:
    """Bytes a 4-byte-element buffer of ``shape`` occupies in TPU VMEM,
    where the last two axes are tiled (8 sublanes, 128 lanes)."""
    *lead, sub, lane = (1,) + tuple(shape)
    return math.prod(lead) * (-(-sub // 8) * 8) * (-(-lane // 128) * 128) * 4


def _deep_compiler_params(resident, blocked):
    """``vmem_limit_bytes`` for a deep-grid call whose resident buffers
    (one copy) and batch-blocked buffers (double-buffered) need more than
    the default scoped VMEM; ``None`` when the default suffices."""
    need = (sum(vmem_bytes(s) for s in resident)
            + 2 * sum(vmem_bytes(s) for s in blocked) + _VMEM_HEADROOM)
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _vshift_down(x):
    """x[..., p, :] <- x[..., p+1, :] (zero into the last sublane row)."""
    return jnp.concatenate([x[..., 1:, :], jnp.zeros_like(x[..., :1, :])],
                           axis=-2)


def _vshift_up(x, first):
    """x[..., p, :] <- x[..., p-1, :], row 0 taken from ``first``."""
    return jnp.concatenate([first[..., :1, :], x[..., :-1, :]], axis=-2)


def _vlast(x, last):
    """x with its last pair-slot row replaced from ``last``."""
    return jnp.concatenate([x[..., :-1, :], last[..., -1:, :]], axis=-2)


def _vcolumn_even(cc, state):
    """Even column over stacked tile planes: rotate (e_p, o_p) in place."""
    er, ei, orr, oi = state
    return _rotate(cc, er, ei, orr, oi)


def _vcolumn_odd(cc, state):
    """Odd column: rotate (o_p, e_{p+1}); the two wrap rows pass
    through (odd columns hold no cell in the wrap-around pair)."""
    er, ei, orr, oi = state
    a2r, a2i, b2r, b2i = _rotate(cc, orr, oi,
                                 _vshift_down(er), _vshift_down(ei))
    return (_vshift_up(b2r, er), _vshift_up(b2i, ei),
            _vlast(a2r, orr), _vlast(a2i, oi))


def _coef_rows(coef_ref, l, c):
    """Column ``c`` of layer ``l``: its 8 coefficient rows, [To, Ti, P, 1]
    each (broadcast along the batch lanes)."""
    return [coef_ref[l, :, :, c, :, k:k + 1] for k in range(8)]


def _vrun_columns(coef_ref, par_ref, l, state):
    """Stacked-tile column sweep of layer ``l``'s mesh: ``coef_ref``
    [L, To, Ti, C, P, 8], ``par_ref`` its SMEM [L, C] parity table, state
    planes [To, Ti, P, B] (batch-materialized — fori_loop carries must be
    full-shape)."""
    def body(c, s):
        cc = _coef_rows(coef_ref, l, c)
        return jax.lax.cond(par_ref[l, c] != 0,
                            functools.partial(_vcolumn_odd, cc),
                            functools.partial(_vcolumn_even, cc), s)

    return jax.lax.fori_loop(0, coef_ref.shape[3], body, state)


def _vconj_dot(xr, xi, gr, gi):
    """Batch-summed conj(x) * g over stacked planes -> [To, Ti, P, 1]
    pair (a lane reduction)."""
    return (jnp.sum(xr * gr + xi * gi, axis=-1, keepdims=True),
            jnp.sum(xr * gi - xi * gr, axis=-1, keepdims=True))


def _vrows_from_pairs(a, ga, b, gb):
    """The 8 per-coefficient conj-dot gradient rows, [To, Ti, P, 1]
    each."""
    return (_vconj_dot(a[0], a[1], ga[0], ga[1])
            + _vconj_dot(b[0], b[1], ga[0], ga[1])
            + _vconj_dot(a[0], a[1], gb[0], gb[1])
            + _vconj_dot(b[0], b[1], gb[0], gb[1]))


def _vcoef_grad_even(s_in, g_out):
    er, ei, orr, oi = s_in
    ger, gei, gor, goi = g_out
    return _vrows_from_pairs((er, ei), (ger, gei), (orr, oi), (gor, goi))


def _vcoef_grad_odd(s_in, g_out):
    """Odd pairing: (a, b) = (o_p, e_{p+1}); the wrap row holds no cell
    so its gradient rows are zeroed."""
    er, ei, orr, oi = s_in
    ger, gei, gor, goi = g_out
    rows = _vrows_from_pairs(
        (orr, oi), (gor, goi),
        (_vshift_down(er), _vshift_down(ei)),
        (_vshift_down(ger), _vshift_down(gei)))
    slot = jax.lax.broadcasted_iota(jnp.int32, rows[0].shape, 2)
    wrap = slot == rows[0].shape[2] - 1
    return tuple(jnp.where(wrap, 0.0, r) for r in rows)


def _vrun_columns_bwd(ci_ref, ca_ref, par_ref, dco_ref, l, state, cot):
    """Reversed stacked-tile sweep of layer ``l``'s mesh: recompute
    states via the per-cell inverse slab, accumulate each column's
    coefficient gradient (its 8 lane-reduced rows joined along the lanes
    into one [To, Ti, P, 8] slab) into its slot of the revisited
    ``dco_ref``, propagate the cotangent via the adjoint slab."""
    n_cols = ci_ref.shape[3]

    def branch(step, coef_grad, ci, ca):
        def run(sg):
            s_in = step(ci, sg[0:4])              # T_c^{-1} s_{c+1}
            rows = coef_grad(s_in, sg[4:8])
            g_in = step(ca, sg[4:8])              # T_c^H g_{c+1}
            return (*s_in, *rows, *g_in)
        return run

    def body(k, carry):
        c = n_cols - 1 - k
        ci, ca = _coef_rows(ci_ref, l, c), _coef_rows(ca_ref, l, c)
        out = jax.lax.cond(par_ref[l, c] != 0,
                           branch(_vcolumn_odd, _vcoef_grad_odd, ci, ca),
                           branch(_vcolumn_even, _vcoef_grad_even, ci, ca),
                           carry)
        dco_ref[l, :, :, c] += jnp.concatenate(out[4:12], axis=-1)
        return (*out[0:4], *out[12:16])

    out = jax.lax.fori_loop(0, n_cols, body, (*state, *cot))
    return out[0:4], out[4:8]


def _layer_gain_rows(gains_ref, l):
    """Layer ``l``'s 12 gain rows, [To, Ti, P, 1] each."""
    return [gains_ref[l, :, :, :, k:k + 1] for k in range(12)]


def _net_layer_stages(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref, l, g,
                      state):
    """g0 -> V -> g1 -> U for stacked layer ``l``; returns (v, u) states.

    ``g`` is the layer's 12 gain rows, ``state`` the stacked
    [To, Ti, P, B] input planes."""
    er, ei = _cmul(state[0], state[1], g[0], g[1])
    orr, oi = _cmul(state[2], state[3], g[2], g[3])
    v = _vrun_columns(coef_v_ref, par_v_ref, l, (er, ei, orr, oi))
    er, ei = _cmul(v[0], v[1], g[4], g[5])
    orr, oi = _cmul(v[2], v[3], g[6], g[7])
    u = _vrun_columns(coef_u_ref, par_u_ref, l, (er, ei, orr, oi))
    return v, u


def _tile_z(u, g):
    """g2 on a tile's U-stage output: the post-g2 complex planes the row
    combiner sums."""
    zer, zei = _cmul(u[0], u[1], g[8], g[9])
    zor, zoi = _cmul(u[2], u[3], g[10], g[11])
    return zer, zei, zor, zoi


def _detect_z(z):
    """|detect| on a combined post-g2 state (4 planes -> 2 magnitudes)."""
    oe = jnp.sqrt(z[0] * z[0] + z[1] * z[1])
    oo = jnp.sqrt(z[2] * z[2] + z[3] * z[3])
    return oe, oo


def _detect_bwd_z(z, goe, goo):
    """|detect| backward: d|z|/dz = z/|z| (0 at the origin, which also
    kills zero-padded batch rows).  ``z`` is the combined post-g2 complex
    state of a tile row; returns its cotangent."""
    zer, zei, zor, zoi = z
    me = jnp.sqrt(zer * zer + zei * zei)
    mo = jnp.sqrt(zor * zor + zoi * zoi)
    inv_e = jnp.where(me > 0, goe / jnp.where(me > 0, me, 1.0), 0.0)
    inv_o = jnp.where(mo > 0, goo / jnp.where(mo > 0, mo, 1.0), 0.0)
    return inv_e * zer, inv_e * zei, inv_o * zor, inv_o * zoi


def _layer_linear_bwd(cv_inv_ref, cv_adj_ref, par_v_ref, cu_inv_ref,
                      cu_adj_ref, par_u_ref, dcv_ref, dcu_ref, dg_ref, l, g,
                      x_in, v, u, gz):
    """Unwind the linear stages g2 -> U -> g1 -> V -> g0 of stacked layer
    ``l`` — every (To, Ti) tile at once.

    ``gz`` is the cotangent of the post-g2 complex state as [To, 1, P, B]
    row planes broadcast to every tile (the row combine is a sum, so each
    tile of a row sees its row's cotangent).  ``x_in``/``v``/``u`` are
    the stacked layer input and stage states.  Coefficient and gain
    gradients accumulate into layer ``l``'s slots of the revisited
    ``dcv_ref``/``dcu_ref``/``dg_ref``; returns the per-tile input
    cotangent planes [To, Ti, P, B] (NOT yet summed over rows — the
    caller applies the combine's transpose).
    """
    gzer, gzei, gzor, gzoi = gz
    dg2 = (_vconj_dot(u[0], u[1], gzer, gzei)
           + _vconj_dot(u[2], u[3], gzor, gzoi))
    guer, guei = _cmul(g[8], -g[9], gzer, gzei)
    guor, guoi = _cmul(g[10], -g[11], gzor, gzoi)

    _, gh = _vrun_columns_bwd(cu_inv_ref, cu_adj_ref, par_u_ref, dcu_ref, l,
                              u, (guer, guei, guor, guoi))

    dg1 = (_vconj_dot(v[0], v[1], gh[0], gh[1])
           + _vconj_dot(v[2], v[3], gh[2], gh[3]))
    gver, gvei = _cmul(g[4], -g[5], gh[0], gh[1])
    gvor, gvoi = _cmul(g[6], -g[7], gh[2], gh[3])

    _, gs0 = _vrun_columns_bwd(cv_inv_ref, cv_adj_ref, par_v_ref, dcv_ref, l,
                               v, (gver, gvei, gvor, gvoi))

    # pre-gain g0: s0 = g0 * x_in
    dg0 = (_vconj_dot(x_in[0], x_in[1], gs0[0], gs0[1])
           + _vconj_dot(x_in[2], x_in[3], gs0[2], gs0[3]))
    gxer, gxei = _cmul(g[0], -g[1], gs0[0], gs0[1])
    gxor, gxoi = _cmul(g[2], -g[3], gs0[2], gs0[3])

    dg_ref[l] += jnp.concatenate(dg0 + dg1 + dg2, axis=-1)
    return gxer, gxei, gxor, gxoi


def _broadcast_tiles(planes, to):
    """[Ti, P, B] input planes -> stacked [To, Ti, P, B] (every tile row
    sweeps the whole input), batch-materialized for the fori carries."""
    return tuple(jnp.broadcast_to(t[None], (to,) + t.shape) for t in planes)


def _row_combine(z):
    """Matched-line row combine: each row sums its Ti tile outputs,
    [To, Ti, P, B] -> [To, P, B]."""
    return tuple(t.sum(axis=1) for t in z)


def _deep_forward(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref, gains_ref,
                  x_refs, stage_refs=None):
    """All L layers of the (To x Ti) grid on one batch block, every
    layer's To*Ti tiles swept together as stacked [To, Ti, P, B] planes.

    Input planes are [Ti, P, B]; returns the *last* layer's combined
    post-g2 row planes ([To, P, B] x 4 — the caller applies the
    readout).  With ``stage_refs`` (the 8 ``[L, To, Ti, P, B]`` residual
    refs of the VJP forward) every tile's two pre-gain stage boundaries
    are saved as whole slabs; inference passes ``None``.
    """
    n_layers, to = coef_v_ref.shape[0], coef_v_ref.shape[1]
    state_in = _broadcast_tiles(tuple(r[...] for r in x_refs), to)
    z_row = None
    for l in range(n_layers):
        if l > 0:
            # in-VMEM re-detection: the previous layer's To combined rows
            # become this layer's Ti real input tiles (To == Ti for L > 1)
            oe, oo = _detect_z(z_row)
            zero = jnp.zeros_like(oe)
            state_in = _broadcast_tiles((oe, zero, oo, zero), to)
        g = _layer_gain_rows(gains_ref, l)
        v, u = _net_layer_stages(coef_v_ref, par_v_ref, coef_u_ref,
                                 par_u_ref, l, g, state_in)
        if stage_refs is not None:
            for ref, plane in zip(stage_refs, (*v, *u)):
                ref[l] = plane
        z_row = _row_combine(_tile_z(u, g))
    return z_row


def _write_readout(z, out_refs, detect_last):
    if detect_last:
        oe_ref, oo_ref = out_refs
        oe_ref[...], oo_ref[...] = _detect_z(z)
    else:
        for ref, plane in zip(out_refs, z):
            ref[...] = plane


def deepgrid_kernel(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref, gains_ref,
                    xer_ref, xei_ref, xor_ref, xoi_ref, *out_refs,
                    detect_last: bool):
    """Inference megakernel: the whole deep tiled network, one residency.

    ``detect_last`` (static) picks the readout: True writes the detected
    row magnitudes (2 output planes), False the combined complex row
    states (4 planes).
    """
    z = _deep_forward(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref,
                      gains_ref, (xer_ref, xei_ref, xor_ref, xoi_ref))
    _write_readout(z, out_refs, detect_last)


def deepgrid_fwd_kernel(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref,
                        gains_ref, xer_ref, xei_ref, xor_ref, xoi_ref,
                        *out_refs, detect_last: bool):
    """VJP forward: identical sweep, plus every tile's two pre-gain stage
    boundaries (post-V, post-U) into [L, To, Ti, P, B] residual planes."""
    n_out = 2 if detect_last else 4
    z = _deep_forward(coef_v_ref, par_v_ref, coef_u_ref, par_u_ref,
                      gains_ref, (xer_ref, xei_ref, xor_ref, xoi_ref),
                      stage_refs=out_refs[n_out:])
    _write_readout(z, out_refs[:n_out], detect_last)


def deepgrid_bwd_kernel(cv_inv_ref, cv_adj_ref, par_v_ref,
                        cu_inv_ref, cu_adj_ref, par_u_ref, gains_ref,
                        xer_ref, xei_ref, xor_ref, xoi_ref,
                        sver_ref, svei_ref, svor_ref, svoi_ref,
                        suer_ref, suei_ref, suor_ref, suoi_ref,
                        *cot_and_out_refs, detect_last: bool):
    """Unwind the whole deep grid in one residency, layers in reverse.

    Every tile unwinds g2 -> U -> g1 -> V -> g0 from its saved stage
    boundaries with the inverse/adjoint sweeps, accumulating into its
    (layer, row, tile) slot of the stacked coefficient/gain accumulators
    (revisited across the batch grid).  The row combine is a sum, so all
    Ti tiles of a row see the row's cotangent; the combine's transpose —
    each input tile's cotangent summed over the To rows — runs in VMEM,
    and crossing a layer boundary re-detects the previous layer's rows
    from their saved post-U states and converts the (real) cotangent
    through the |detect| backward.  Layer 0 writes the input cotangent
    planes [Ti, P, B].
    """
    n_cot = 2 if detect_last else 4
    cot_refs = cot_and_out_refs[:n_cot]
    (dcv_ref, dcu_ref, dg_ref,
     dxer_ref, dxei_ref, dxor_ref, dxoi_ref) = cot_and_out_refs[n_cot:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dcv_ref[...] = jnp.zeros(dcv_ref.shape, dcv_ref.dtype)
        dcu_ref[...] = jnp.zeros(dcu_ref.shape, dcu_ref.dtype)
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)

    n_layers, to = cv_inv_ref.shape[0], cv_inv_ref.shape[1]

    def saved_v(l):
        return (sver_ref[l], svei_ref[l], svor_ref[l], svoi_ref[l])

    def saved_u(l):
        return (suer_ref[l], suei_ref[l], suor_ref[l], suoi_ref[l])

    def row_z(l):
        """Recompute layer l's combined post-g2 row planes [To, P, B]
        from the saved post-U stages (elementwise — no sweep)."""
        return _row_combine(_tile_z(saved_u(l), _layer_gain_rows(gains_ref,
                                                                 l)))

    if detect_last:
        goe_ref, goo_ref = cot_refs
        gz = _detect_bwd_z(row_z(n_layers - 1), goe_ref[...], goo_ref[...])
    else:
        gz = tuple(r[...] for r in cot_refs)              # [To, P, B]

    for l in range(n_layers - 1, -1, -1):
        if l == 0:
            z_prev = None
            state_in = _broadcast_tiles(
                (xer_ref[...], xei_ref[...], xor_ref[...], xoi_ref[...]),
                to)
        else:
            # layer l's input tiles: re-detected previous-layer rows
            # (To == Ti whenever L > 1, so indices line up)
            z_prev = row_z(l - 1)
            be, bo = _detect_z(z_prev)
            zero = jnp.zeros_like(be)
            state_in = _broadcast_tiles((be, zero, bo, zero), to)
        # the row combine is a sum: every tile of a row sees the row's
        # cotangent ([To, 1, P, B] broadcast across the stacked sweep)
        gx = _layer_linear_bwd(
            cv_inv_ref, cv_adj_ref, par_v_ref, cu_inv_ref, cu_adj_ref,
            par_u_ref, dcv_ref, dcu_ref, dg_ref, l,
            _layer_gain_rows(gains_ref, l), state_in, saved_v(l),
            saved_u(l), tuple(t[:, None] for t in gz))
        # combine's transpose: each input tile sums its cotangent over
        # the To rows
        dx = tuple(t.sum(axis=0) for t in gx)             # [Ti, P, B]
        if l > 0:
            # boundary crossing keeps only the real cotangent planes (the
            # imaginary planes of an inter-layer input are structurally
            # zero) and converts through the |detect| backward
            gz = _detect_bwd_z(z_prev, dx[0], dx[2])
        else:
            dxer_ref[...], dxei_ref[...] = dx[0], dx[1]
            dxor_ref[...], dxoi_ref[...] = dx[2], dx[3]


def _resident():
    """A whole operand held in VMEM for the entire call (one copy)."""
    return pl.BlockSpec(memory_space=pltpu.VMEM)


def _smem():
    """A whole (parity table) operand in scalar memory."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _deep_flops_per_block(n: int, n_layers: int, to: int, ti: int,
                          n_cols: int, batch_block: int) -> int:
    p = n // 2
    return 2 * n_layers * to * ti * (2 * n_cols * p * 16 + 9 * n) \
        * batch_block


def _deep_coef_bytes(n_layers: int, to: int, ti: int, n_cols: int,
                     p: int) -> int:
    return n_layers * to * ti * (n_cols * 8 + 12) * p * 4


def _deep_shapes(n, n_layers, to, ti, n_cols, batch_block):
    """Shapes of the deep call's operand classes: coefficient stack, gain
    stack, and the per-block x / output / stage planes (batch last)."""
    p = n // 2
    return ((n_layers, to, ti, n_cols, p, 8), (n_layers, to, ti, p, 12),
            (ti, p, batch_block), (to, p, batch_block),
            (n_layers, to, ti, p, batch_block))


def _deep_plane_specs(n, n_layers, to, ti, batch_block):
    p = n // 2
    x_plane = pl.BlockSpec((ti, p, batch_block), lambda b: (0, 0, b))
    o_plane = pl.BlockSpec((to, p, batch_block), lambda b: (0, 0, b))
    stage = pl.BlockSpec((n_layers, to, ti, p, batch_block),
                         lambda b: (0, 0, 0, 0, b))
    return x_plane, o_plane, stage


def deepgrid_pallas_call(n: int, n_layers: int, to: int, ti: int,
                         n_cols: int, batch_block: int, n_batch_blocks: int,
                         detect_last: bool, interpret: bool):
    p = n // 2
    b_total = n_batch_blocks * batch_block
    coef, gains, xb, ob, _ = _deep_shapes(n, n_layers, to, ti, n_cols,
                                          batch_block)
    x_plane, o_plane, _ = _deep_plane_specs(n, n_layers, to, ti, batch_block)
    n_out = 2 if detect_last else 4
    out_shape = [jax.ShapeDtypeStruct((to, p, b_total), jnp.float32)] * n_out
    flops = _deep_flops_per_block(n, n_layers, to, ti, n_cols, batch_block)
    return pl.pallas_call(
        functools.partial(deepgrid_kernel, detect_last=detect_last),
        grid=(n_batch_blocks,),
        in_specs=[_resident(), _smem(), _resident(), _smem(), _resident(),
                  x_plane, x_plane, x_plane, x_plane],
        out_specs=[o_plane] * n_out,
        out_shape=out_shape,
        compiler_params=_deep_compiler_params(
            [coef, coef, gains], [xb] * 4 + [ob] * n_out),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops * n_batch_blocks,
            bytes_accessed=((4 * ti + n_out * to) * batch_block * p * 4
                            + _deep_coef_bytes(n_layers, to, ti, n_cols, p))
            * n_batch_blocks,
            transcendentals=n_layers * to * batch_block * p * 2
            * n_batch_blocks,
        ),
    )


def deepgrid_fwd_pallas_call(n: int, n_layers: int, to: int, ti: int,
                             n_cols: int, batch_block: int,
                             n_batch_blocks: int, detect_last: bool,
                             interpret: bool):
    p = n // 2
    b_total = n_batch_blocks * batch_block
    coef, gains, xb, ob, sb = _deep_shapes(n, n_layers, to, ti, n_cols,
                                           batch_block)
    x_plane, o_plane, stage = _deep_plane_specs(n, n_layers, to, ti,
                                                batch_block)
    n_out = 2 if detect_last else 4
    out_shape = (
        [jax.ShapeDtypeStruct((to, p, b_total), jnp.float32)] * n_out
        + [jax.ShapeDtypeStruct((n_layers, to, ti, p, b_total),
                                jnp.float32)] * 8)
    flops = _deep_flops_per_block(n, n_layers, to, ti, n_cols, batch_block)
    return pl.pallas_call(
        functools.partial(deepgrid_fwd_kernel, detect_last=detect_last),
        grid=(n_batch_blocks,),
        in_specs=[_resident(), _smem(), _resident(), _smem(), _resident(),
                  x_plane, x_plane, x_plane, x_plane],
        out_specs=[o_plane] * n_out + [stage] * 8,
        out_shape=out_shape,
        compiler_params=_deep_compiler_params(
            [coef, coef, gains], [xb] * 4 + [ob] * n_out + [sb] * 8),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops * n_batch_blocks,
            bytes_accessed=(((4 + 8 * n_layers * to) * ti + n_out * to)
                            * batch_block * p * 4
                            + _deep_coef_bytes(n_layers, to, ti, n_cols, p))
            * n_batch_blocks,
            transcendentals=n_layers * to * batch_block * p * 2
            * n_batch_blocks,
        ),
    )


def deepgrid_bwd_pallas_call(n: int, n_layers: int, to: int, ti: int,
                             n_cols: int, batch_block: int,
                             n_batch_blocks: int, detect_last: bool,
                             interpret: bool):
    p = n // 2
    b_total = n_batch_blocks * batch_block
    coef, gains, xb, ob, sb = _deep_shapes(n, n_layers, to, ti, n_cols,
                                           batch_block)
    x_plane, o_plane, stage = _deep_plane_specs(n, n_layers, to, ti,
                                                batch_block)
    n_cot = 2 if detect_last else 4
    out_shape = (
        [jax.ShapeDtypeStruct(coef, jnp.float32)] * 2
        + [jax.ShapeDtypeStruct(gains, jnp.float32)]
        + [jax.ShapeDtypeStruct((ti, p, b_total), jnp.float32)] * 4)
    # inverse state recompute + adjoint cotangent + coefficient grads
    flops = 3 * _deep_flops_per_block(n, n_layers, to, ti, n_cols,
                                      batch_block)
    return pl.pallas_call(
        functools.partial(deepgrid_bwd_kernel, detect_last=detect_last),
        grid=(n_batch_blocks,),
        in_specs=[_resident(), _resident(), _smem(),
                  _resident(), _resident(), _smem(), _resident()]
        + [x_plane] * 4 + [stage] * 8 + [o_plane] * n_cot,
        out_specs=[_resident()] * 3 + [x_plane] * 4,
        out_shape=out_shape,
        compiler_params=_deep_compiler_params(
            [coef] * 6 + [gains] * 2,
            [xb] * 8 + [sb] * 8 + [ob] * n_cot),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops * n_batch_blocks,
            bytes_accessed=(((8 + 8 * n_layers * to) * ti + n_cot * to)
                            * batch_block * p * 4
                            + 3 * _deep_coef_bytes(n_layers, to, ti, n_cols,
                                                   p)) * n_batch_blocks,
            transcendentals=3 * n_layers * to * batch_block * p * 2
            * n_batch_blocks,
        ),
    )
