"""Plain physics of the RF analog processor, for the benchmark's references.

Straightforward ``jax.numpy`` that imports nothing of the package under
test.  A cell is the paper's 2x2 block (Eq. 5) built structurally,
phase shifter . hybrid . phase shifter . hybrid, under the
measured-prototype hardware model (imbalanced hybrids, insertion loss per
cell, Gaussian phase-shifter deviation drawn from a key); a mesh is a
sequence of columns of cells on adjacent channel pairs; detection reads
``|v|``.  Every 2x2 product is written out elementwise, so no matrix unit
and no matmul precision setting is involved.

Each function takes ``rnd``, applied to parameters, cell matrices and
channel states: :func:`f32` (the identity) for the reference, and
:func:`bf16` (rounding to bfloat16) for the lower-precision control.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def f32(a):
    """The reference's own precision: leave ``a`` as it is."""
    return a


def bf16(a):
    """Round ``a`` (real or complex) to bfloat16 and back."""
    a = jnp.asarray(a)
    if jnp.iscomplexobj(a):
        re = jnp.real(a).astype(jnp.bfloat16).astype(jnp.float32)
        im = jnp.imag(a).astype(jnp.bfloat16).astype(jnp.float32)
        return jax.lax.complex(re, im)
    if jnp.issubdtype(a.dtype, jnp.floating):
        return a.astype(jnp.bfloat16).astype(a.dtype)
    return a


ROUNDING = {"float32": f32, "bfloat16": bf16}


# ---------------------------------------------------------------------------
# hardware model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Hardware:
    """The imperfections of one cell and its detector, as a configuration
    states them (angles in degrees)."""

    hybrid_imbalance: float
    hybrid_phase_err_deg: float
    cell_loss_db: float
    phase_sigma_deg: float
    detector_floor_dbm: float
    detector_sigma: float
    z0_ohm: float = 50.0

    @classmethod
    def from_config(cls, d: dict) -> "Hardware":
        return cls(**{f.name: float(d[f.name]) for f in dataclasses.fields(cls)
                      if f.name in d})

    @property
    def cell_gain(self) -> float:
        return 10.0 ** (-self.cell_loss_db / 20.0)

    @property
    def phase_sigma(self) -> float:
        return float(np.deg2rad(self.phase_sigma_deg))

    @property
    def v_floor(self) -> float:
        floor_w = 10.0 ** (self.detector_floor_dbm / 10.0) * 1e-3
        return float(np.sqrt(2.0 * self.z0_ohm * floor_w))


def _mm2(a, b):
    """Product of two stacks of 2x2 complex matrices, written out."""
    def e(i, j):
        return a[..., i, 0] * b[..., 0, j] + a[..., i, 1] * b[..., 1, j]
    return jnp.stack([jnp.stack([e(0, 0), e(0, 1)], -1),
                      jnp.stack([e(1, 0), e(1, 1)], -1)], -2)


def hybrid(hw: Hardware):
    """Forward block of the lossy, imbalanced quadrature hybrid,
    renormalised so its worst row passes at most unit power."""
    eps = np.float32(hw.hybrid_imbalance)
    thru = ((1.0 + eps) * np.exp(1j * np.float32(np.deg2rad(
        hw.hybrid_phase_err_deg))) * 1j).astype(np.complex64)
    coup = np.complex64(1.0 - eps)
    m = np.array([[thru, coup], [coup, thru]], np.complex64)
    scale = np.sqrt(np.max(np.sum(np.abs(m) ** 2, axis=1)))
    return jnp.asarray(-m / scale, jnp.complex64)


def _shifter(p):
    """diag(e^{-jp}, 1) as a stack of 2x2 matrices."""
    e = jnp.exp(-1j * p.astype(jnp.complex64))
    z = jnp.zeros_like(e)
    return jnp.stack([jnp.stack([e, z], -1),
                      jnp.stack([z, jnp.ones_like(e)], -1)], -2)


def cell(theta, phi, hw: Hardware, key=None, rnd=f32):
    """t(theta, phi) of every cell under the hardware model.

    With ``key``, each shifter deviates by ``sigma * N(0, 1)``: the first
    half of ``split(key)`` draws theta's deviations and the second phi's,
    each in the shape of the phase array.
    """
    theta = rnd(jnp.asarray(theta, jnp.float32))
    phi = rnd(jnp.asarray(phi, jnp.float32))
    if key is not None:
        sigma = jnp.float32(hw.phase_sigma)
        k1, k2 = jax.random.split(key)
        theta = theta + sigma * jax.random.normal(k1, theta.shape)
        phi = phi + sigma * jax.random.normal(k2, phi.shape)
    h = jnp.broadcast_to(hybrid(hw), theta.shape + (2, 2))
    t = _mm2(_shifter(phi), _mm2(h, _mm2(_shifter(theta), h)))
    return rnd(jnp.complex64(hw.cell_gain) * t)


# ---------------------------------------------------------------------------
# layouts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Layout:
    """Where the cells of a mesh sit: ``top[c, s]`` is the upper channel of
    slot ``s`` in column ``c``, ``active[c, s]`` whether a cell is there;
    ``slot``/``role`` map each channel of a column to its slot and to
    0 (untouched), 1 (upper) or 2 (lower)."""

    n: int
    top: np.ndarray
    active: np.ndarray
    slot: np.ndarray
    role: np.ndarray

    @property
    def n_cells(self) -> int:
        return int(self.active.sum())


def _layout(n: int, top: np.ndarray, active: np.ndarray) -> Layout:
    c = top.shape[0]
    slot = np.zeros((c, n), np.int32)
    role = np.zeros((c, n), np.int8)
    for ci, si in zip(*np.nonzero(active)):
        p = int(top[ci, si])
        slot[ci, p] = slot[ci, p + 1] = si
        role[ci, p], role[ci, p + 1] = 1, 2
    return Layout(n, top, active, slot, role)


def clements(n: int) -> Layout:
    """The rectangle: n columns, pairs starting at 0 and 1 in turn."""
    top = np.zeros((n, n // 2), np.int32)
    active = np.zeros((n, n // 2), bool)
    for c in range(n):
        starts = np.arange(c % 2, n - 1, 2)
        top[c, : len(starts)] = starts
        active[c, : len(starts)] = True
    return _layout(n, top, active)


def reck(n: int) -> tuple[Layout, np.ndarray]:
    """The triangle of n(n-1)/2 cells, in the order in which the analytic
    programmer of a unitary places them, list-scheduled into 2n-3 columns.

    Returns the layout and, for each cell in that order, its
    ``(column, slot)``.
    """
    nulled = [q - 1 for col in range(n - 1) for q in range(n - 1, col, -1)]
    free = np.zeros(n, np.int64)
    placed: list[list[tuple[int, int]]] = []
    for k, p in enumerate(reversed(nulled)):
        c = int(max(free[p], free[p + 1]))
        while len(placed) <= c:
            placed.append([])
        placed[c].append((p, k))
        free[p] = free[p + 1] = c + 1
    n_cols = max(len(placed), 2 * n - 3)
    top = np.zeros((n_cols, n // 2), np.int32)
    active = np.zeros((n_cols, n // 2), bool)
    where = np.zeros((len(nulled), 2), np.int32)
    for c, cells in enumerate(placed):
        for s, (p, k) in enumerate(sorted(cells)):
            top[c, s], active[c, s] = p, True
            where[k] = (c, s)
    return _layout(n, top, active), where


# ---------------------------------------------------------------------------
# propagation
# ---------------------------------------------------------------------------

def _column(x, t2, top, slot, role):
    a = jnp.take(x, top, axis=-1)
    b = jnp.take(x, top + 1, axis=-1)
    a2 = t2[..., 0, 0] * a + t2[..., 0, 1] * b
    b2 = t2[..., 1, 0] * a + t2[..., 1, 1] * b
    from_top = jnp.take(a2, slot, axis=-1)
    from_bot = jnp.take(b2, slot, axis=-1)
    return jnp.where(role == 1, from_top, jnp.where(role == 2, from_bot, x))


def mesh(layout: Layout, params: dict, x, hw: Hardware, key=None, rnd=f32):
    """``x[..., n]`` through the input screen ``alpha_in`` (if given), every
    column of cells, and the output screen ``alpha`` (if given)."""
    x = rnd(x.astype(jnp.complex64))
    if params.get("alpha_in") is not None:
        x = rnd(x * jnp.exp(-1j * rnd(params["alpha_in"]).astype(
            jnp.complex64)))
    t = cell(params["theta"], params["phi"], hw, key, rnd)
    t = jnp.where(jnp.asarray(layout.active)[..., None, None], t,
                  jnp.eye(2, dtype=jnp.complex64))

    def step(h, col):
        return rnd(_column(h, *col)), None

    x, _ = jax.lax.scan(step, x, (t, jnp.asarray(layout.top),
                                  jnp.asarray(layout.slot),
                                  jnp.asarray(layout.role)))
    if params.get("alpha") is not None:
        x = rnd(x * jnp.exp(-1j * rnd(params["alpha"]).astype(
            jnp.complex64)))
    return x


def detect(v, hw: Hardware, key=None, rnd=f32):
    """The power detector's reading of ``|v|``: relative Gaussian noise
    from ``key``, and the sensitivity floor."""
    mag = jnp.abs(v)
    if key is not None and hw.detector_sigma > 0:
        mag = mag * (1.0 + hw.detector_sigma * jax.random.normal(key,
                                                                 mag.shape))
    return rnd(jnp.maximum(mag, hw.v_floor))


def nearest_phase(phase, codebook):
    """The codebook phase nearest to ``phase`` on the circle."""
    d = phase[..., None] - codebook
    d = jnp.abs(jnp.mod(d + np.pi, 2 * np.pi) - np.pi)
    return jnp.take(codebook, jnp.argmin(d, axis=-1), axis=0)


@jax.custom_vjp
def ste(phase, codebook):
    """Nearest-codebook phase; the gradient passes straight through."""
    return nearest_phase(phase, codebook)


def _ste_fwd(phase, codebook):
    return nearest_phase(phase, codebook), None


def _ste_bwd(_, g):
    return g, None


ste.defvjp(_ste_fwd, _ste_bwd)
