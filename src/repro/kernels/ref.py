"""Pure-jnp oracles for the Pallas kernels.

The oracles re-express the mesh semantics in the kernels' de-interleaved
(even/odd channel) layout so the kernels can be validated value-for-value,
and are themselves validated against :func:`repro.core.mesh.apply_mesh` in
the test suite (two independent implementations of the same physics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hardware as hw_lib
from repro.core import mesh as mesh_lib
from repro.core.cell import cell_matrix

Array = jax.Array


def clements_coefficients(theta: Array, phi: Array, n: int) -> Array:
    """Pack cell matrices of a Clements-layout mesh into kernel coefficients.

    theta/phi: [C, P] with C == n columns, P == n//2 pair slots.
    Returns coef [C, 8, P] float32 with rows
    (t00r, t00i, t01r, t01i, t10r, t10i, t11r, t11i) per pair slot.
    Inactive slots (the wrap slot of odd columns) are forced to identity.
    """
    c, p = theta.shape
    if c != n or p != n // 2:
        raise ValueError(f"expected params [{n},{n//2}], got [{c},{p}]")
    t = cell_matrix(theta, phi)  # [C, P, 2, 2] complex
    plan = mesh_lib.clements_plan(n)
    active = jnp.asarray(plan.active)[..., None, None]
    t = jnp.where(active, t, jnp.eye(2, dtype=t.dtype))
    coef = jnp.stack(
        [jnp.real(t[..., 0, 0]), jnp.imag(t[..., 0, 0]),
         jnp.real(t[..., 0, 1]), jnp.imag(t[..., 0, 1]),
         jnp.real(t[..., 1, 0]), jnp.imag(t[..., 1, 0]),
         jnp.real(t[..., 1, 1]), jnp.imag(t[..., 1, 1])],
        axis=1,
    )  # [C, 8, P]
    return coef.astype(jnp.float32)


def split_channels(x: Array) -> tuple[Array, Array, Array, Array]:
    """Complex [B, N] -> (xer, xei, xor, xoi) float32 [B, N//2] planes."""
    xe, xo = x[..., 0::2], x[..., 1::2]
    return (jnp.real(xe).astype(jnp.float32), jnp.imag(xe).astype(jnp.float32),
            jnp.real(xo).astype(jnp.float32), jnp.imag(xo).astype(jnp.float32))


def merge_channels(xer: Array, xei: Array, xor: Array, xoi: Array) -> Array:
    """Inverse of :func:`split_channels`."""
    xe = xer + 1j * xei
    xo = xor + 1j * xoi
    b, p = xe.shape[:-1], xe.shape[-1]
    out = jnp.stack([xe, xo], axis=-1).reshape(b + (2 * p,))
    return out.astype(jnp.complex64)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _rotate_pair(coef_slice, ar, ai, br, bi):
    """(a', b') = t @ (a, b) with t given by an 8-row coefficient slice."""
    t00r, t00i, t01r, t01i, t10r, t10i, t11r, t11i = [coef_slice[k] for k in range(8)]
    xr, xi = _cmul(t00r, t00i, ar, ai)
    yr, yi = _cmul(t01r, t01i, br, bi)
    a2r, a2i = xr + yr, xi + yi
    xr, xi = _cmul(t10r, t10i, ar, ai)
    yr, yi = _cmul(t11r, t11i, br, bi)
    b2r, b2i = xr + yr, xi + yi
    return a2r, a2i, b2r, b2i


def mesh_apply_planes(coef: Array, xer: Array, xei: Array, xor: Array,
                      xoi: Array) -> tuple[Array, Array, Array, Array]:
    """Oracle for the kernel inner loop, in the de-interleaved layout.

    coef: [C, 8, P]; planes: [..., P].  Even columns rotate (even_i, odd_i);
    odd columns rotate (odd_i, even_{i+1}) with the wrap slot inactive.
    """
    n_cols = coef.shape[0]
    state = (xer, xei, xor, xoi)
    for c in range(n_cols):  # oracle: plain python loop, clarity first
        er, ei, orr, oi = state
        cc = coef[c]
        if c % 2 == 0:
            a2r, a2i, b2r, b2i = _rotate_pair(cc, er, ei, orr, oi)
            state = (a2r, a2i, b2r, b2i)
        else:
            ar, ai = orr[..., :-1], oi[..., :-1]
            br, bi = er[..., 1:], ei[..., 1:]
            cs = cc[:, :-1]
            a2r, a2i, b2r, b2i = _rotate_pair(cs, ar, ai, br, bi)
            orr = jnp.concatenate([a2r, orr[..., -1:]], axis=-1)
            oi = jnp.concatenate([a2i, oi[..., -1:]], axis=-1)
            er = jnp.concatenate([er[..., :1], b2r], axis=-1)
            ei = jnp.concatenate([ei[..., :1], b2i], axis=-1)
            state = (er, ei, orr, oi)
    return state


def mesh_apply_ref(params: dict, x: Array, n: int) -> Array:
    """Reference mesh apply in the kernel layout (complex in/out)."""
    coef = clements_coefficients(params["theta"], params["phi"], n)
    planes = split_channels(x.astype(jnp.complex64))
    planes = mesh_apply_planes(coef, *planes)
    y = merge_channels(*planes)
    alpha = params.get("alpha")
    if alpha is not None:
        y = y * jnp.exp(-1j * alpha.astype(jnp.complex64))
    return y


def rfnn_linear_ref(v_params: dict, atten: Array, u_params: dict, x: Array,
                    n: int, scale: Array | float = 1.0) -> Array:
    """Oracle for the fused analog linear kernel: |scale * U (D (V x))|."""
    h = mesh_apply_ref(v_params, x, n)
    h = h * atten.astype(jnp.complex64)
    y = mesh_apply_ref(u_params, h, n)
    return jnp.abs(scale * y)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mesh_ref(plan, hardware, params, x, key):
    if hardware is None:
        return mesh_lib.apply_mesh(plan, params, x)
    return hw_lib.apply_mesh_hw(plan, params, x, hardware, key)


def deep_apply_ref(layers, x: Array, *, n: int, plans=None,
                   hardware: hw_lib.HardwareModel | None = None,
                   readout: str = "magnitude") -> Array:
    """Pure-jnp oracle of :func:`repro.kernels.ops.deep_apply`.

    Every tile of every layer propagates through the reference mesh
    (:func:`repro.core.mesh.apply_mesh`, or with ``hardware``
    :func:`repro.core.hardware.apply_mesh_hw` with the tile's own
    ``key_v``/``key_u``): ``z = scale * U(atten * V(x_i))``.  Each row
    sums its tiles' outputs, and ``|.|`` detects between layers and,
    with the ``"magnitude"`` readout, after the last.  ``layers`` and
    ``plans`` are nested ``[L][To][Ti]`` as for ``deep_apply``.
    Each mesh is one jitted call keyed on its plan, so an eager
    evaluation compiles one small program per distinct plan and reuses it
    across every tile.
    """
    def mesh(plan, params, v, key):
        return _mesh_ref(plan, hardware, params, v, key)

    h = jnp.asarray(x).astype(jnp.complex64)
    for l, grid in enumerate(layers):
        rows = []
        for o, row in enumerate(grid):
            z = 0.0
            for i, ta in enumerate(row):
                pair = None if plans is None else plans[l][o][i]
                v_plan, u_plan = [
                    mesh_lib.clements_plan(n) if p is None else p
                    for p in ((None, None) if pair is None else pair)]
                v = mesh(v_plan, ta["v"], h[..., i * n:(i + 1) * n],
                         ta.get("key_v"))
                u = mesh(u_plan, ta["u"], ta["atten"] * v, ta.get("key_u"))
                z = z + jnp.asarray(ta.get("scale", 1.0), jnp.complex64) * u
            rows.append(z)
        h = jnp.concatenate(rows, axis=-1)
        if l < len(layers) - 1 or readout == "magnitude":
            h = jnp.abs(h)
    return h


def flash_attention_ref(q: Array, k: Array, v: Array,
                        causal: bool = True) -> Array:
    """Dense-softmax oracle for the flash attention kernel.

    q, k, v: [B, H, S, hd] -> [B, H, S, hd], f32 math.
    """
    import numpy as np
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None]
        s = jnp.where(mask, -1e30, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
