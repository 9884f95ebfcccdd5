"""Static kernel schedules for arbitrary adjacent-pair mesh layouts.

The Pallas mesh kernels operate on de-interleaved even/odd channel planes,
so a kernel column can only pair channels in one of two ways:

  * parity 0 — ``(2i, 2i+1)``: pair slot ``i`` rotates ``(even_i, odd_i)``;
  * parity 1 — ``(2i+1, 2i+2)``: slot ``i`` rotates ``(odd_i, even_{i+1})``
    (the wrap slot ``P-1`` never holds a cell).

A :class:`repro.core.mesh.MeshPlan` column, however, may mix both parities
(``pack_cells_to_columns`` packs greedily — e.g. Reck programs from the
analytic synthesizer).  :func:`schedule_from_plan` re-schedules any plan
into parity-homogeneous kernel columns: each plan column splits into at
most one parity-0 and one parity-1 sub-column (exact, because cells within
a plan column never overlap and cells of different parity in the same
column therefore commute).  The rectangular Clements layout maps 1:1 —
its columns are already parity-pure and alternate 0/1 — so the ideal path
is the degenerate case and pays nothing for the generality.

The resulting :class:`MeshSchedule` is a hashable, purely static object
(tuples of ints), usable as a jit/static and ``custom_vjp`` nondiff
argument; :func:`pack_cells` is the differentiable bridge that gathers
per-cell 2x2 transfer matrices (ideal *or* hardware-imperfect) into the
kernels' ``[C', 8, P]`` coefficient layout.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mesh as mesh_lib

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MeshSchedule:
    """Parity-homogeneous column schedule of an adjacent-pair mesh.

    Attributes:
      n: number of channels (even).
      parity: per kernel column, 0 (pairs ``(2i, 2i+1)``) or 1
        (pairs ``(2i+1, 2i+2)``).
      source: per kernel column, ``n//2`` entries mapping each kernel pair
        slot to a flat plan-cell index ``col * P + slot`` (or -1 for an
        identity slot).
    """

    n: int
    parity: tuple[int, ...]
    source: tuple[tuple[int, ...], ...]

    @property
    def n_columns(self) -> int:
        return len(self.parity)

    @property
    def pairs(self) -> int:
        return self.n // 2


# Bounded memoization (plans hash by content, see MeshPlan.__hash__):
# dynamically synthesized Reck programs mint a fresh plan object per
# reprogramming, and each distinct schedule is also a distinct jit static —
# returning the *same* MeshSchedule for equal plans keeps repeated
# ``mesh_apply(plan=...)`` calls from rebuilding parity tensors or
# re-triggering jit trace-cache misses, while the LRU bound keeps a
# long-lived sweep over many target matrices from accumulating schedules.
@functools.lru_cache(maxsize=128)
def schedule_from_plan(plan: mesh_lib.MeshPlan) -> MeshSchedule:
    """Re-schedule an arbitrary MeshPlan into kernel parity columns."""
    pk = plan.n // 2
    parity: list[int] = []
    source: list[tuple[int, ...]] = []
    for c in range(plan.n_columns):
        for par in (0, 1):
            row = [-1] * pk
            found = False
            for s in range(plan.pairs_per_column):
                if not plan.active[c, s]:
                    continue
                p = int(plan.top[c, s])
                if p % 2 != par:
                    continue
                row[p // 2] = c * plan.pairs_per_column + s
                found = True
            if found:
                parity.append(par)
                source.append(tuple(row))
    if not parity:  # cell-free mesh: one identity column keeps shapes valid
        parity = [0]
        source = [tuple([-1] * pk)]
    return MeshSchedule(n=plan.n, parity=tuple(parity), source=tuple(source))


def clements_schedule(n: int) -> MeshSchedule:
    """The rectangular Clements schedule (1:1 with its plan columns)."""
    return schedule_from_plan(mesh_lib.clements_plan(n))


@functools.lru_cache(maxsize=256)
def _parity_np(sched: MeshSchedule) -> np.ndarray:
    # cache the *numpy* array: jnp conversion must happen per trace (a
    # jnp constant built inside a jit trace is a trace-local tracer)
    return np.asarray(sched.parity, np.int32).reshape(-1, 1)


def parity_array(sched: MeshSchedule) -> Array:
    """The per-column parity as the kernels' ``[C', 1]`` int32 input."""
    return jnp.asarray(_parity_np(sched))


@functools.lru_cache(maxsize=256)
def _pack_indices(sched: MeshSchedule, c: int, p: int) -> np.ndarray:
    """Memoized gather map for :func:`pack_cells` (host work per schedule,
    not per call/trace): flat plan-cell index per kernel slot, with -1
    redirected to the appended identity cell at ``c * p``."""
    idx = np.asarray(sched.source, np.int64)
    return np.where(idx < 0, c * p, idx)


def pack_cells(sched: MeshSchedule, t_all: Array) -> Array:
    """Gather per-cell 2x2 matrices into kernel coefficients ``[C', 8, P]``.

    ``t_all``: complex ``[..., C, P, 2, 2]`` cell transfer matrices in plan
    layout (ideal :func:`repro.core.cell.cell_matrix` or the hardware
    model's :func:`repro.core.hardware.imperfect_cell_matrix`).  Inactive
    plan slots are never referenced by the schedule, so parked parameters
    cannot leak in; identity fills the unused kernel slots.  Differentiable
    (a gather), and batch dims vmap through.
    """
    c, p = t_all.shape[-4], t_all.shape[-3]
    if p != sched.pairs:
        raise ValueError(
            f"cell tensor has {p} pair slots per column, schedule expects "
            f"{sched.pairs} (n={sched.n})")
    max_src = max((s for row in sched.source for s in row), default=-1)
    if max_src >= c * p:
        raise ValueError(
            f"schedule references cell {max_src} but tensor holds only "
            f"{c * p} — t_all built from a different plan?")
    lead = t_all.shape[:-4]
    flat = t_all.reshape(lead + (c * p, 2, 2)).astype(jnp.complex64)
    eye = jnp.broadcast_to(jnp.eye(2, dtype=jnp.complex64),
                           lead + (1, 2, 2))
    flat = jnp.concatenate([flat, eye], axis=-3)
    idx = _pack_indices(sched, c, p)  # -1 -> the appended identity
    cells = jnp.take(flat, jnp.asarray(idx), axis=-3)  # [..., C', P, 2, 2]
    coef = jnp.stack(
        [jnp.real(cells[..., 0, 0]), jnp.imag(cells[..., 0, 0]),
         jnp.real(cells[..., 0, 1]), jnp.imag(cells[..., 0, 1]),
         jnp.real(cells[..., 1, 0]), jnp.imag(cells[..., 1, 0]),
         jnp.real(cells[..., 1, 1]), jnp.imag(cells[..., 1, 1])],
        axis=-2,
    )  # [..., C', 8, P]
    return coef.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Deep-grid schedules: L layers of (To x Ti) grids of (V, U) schedules for
# the deep tiled-network megakernel — the general form; network (L x 1 x 1)
# and tile-grid (1 x To x Ti) schedules are its degenerate cases.
# ---------------------------------------------------------------------------

#: Coefficient rows of an identity 2x2 cell (t00 = t11 = 1): the cell a
#: tile holds in a kernel column it sits out, and the padding column of
#: short meshes, so every mesh shares one column count.
_IDENTITY_ROWS = (1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.0, 0.0)


@dataclasses.dataclass(frozen=True)
class DeepGridSchedule:
    """Static schedule of an L-layer network of (To x Ti) tile grids.

    ``layers[l][o][i]`` is the ``(V, U)`` pair of :class:`MeshSchedule`\\ s
    of tile ``(o, i)`` in layer ``l``.  The deep megakernel runs the whole
    network in one VMEM residency with coefficient/gain tensors stacked
    to ``[L, To, Ti, C, 8, P]`` / ``[L, To, Ti, 12, P]`` and one ``[L, C]``
    parity table per mesh, where ``C = n_columns`` counts the columns of
    every layer's tile schedules aligned to shared parities (identity
    cells fill a tile's sat-out and padding columns — exact no-ops in
    the sweep).  Between layers the kernel re-detects the combined row
    outputs in VMEM, so layer ``l``'s ``To`` rows feed layer ``l+1``'s
    ``Ti`` input tiles without touching HBM; chaining under one uniform
    stacked tensor therefore requires ``To == Ti`` whenever ``L > 1``.  Hashable and purely static — a
    jit/static and ``custom_vjp`` nondiff argument like
    :class:`MeshSchedule`.
    """

    layers: tuple[tuple[tuple[tuple[MeshSchedule, MeshSchedule], ...],
                        ...], ...]

    def __post_init__(self):
        if not self.layers:
            raise ValueError("deep grid schedule needs at least one layer")
        to = len(self.layers[0])
        if not to or not self.layers[0][0]:
            raise ValueError("deep grid needs at least one tile")
        ti = len(self.layers[0][0])
        n = self.layers[0][0][0][0].n
        for grid in self.layers:
            if len(grid) != to or any(len(row) != ti for row in grid):
                raise ValueError(
                    "every layer's tile grid must be the same rectangular "
                    f"{to}x{ti} shape")
            for row in grid:
                for sv, su in row:
                    if sv.n != n or su.n != n:
                        raise ValueError(
                            f"all tile meshes must share n={n}, got "
                            f"({sv.n}, {su.n})")
        if len(self.layers) > 1 and to != ti:
            raise ValueError(
                f"a deep ({len(self.layers)}-layer) grid chains each "
                f"layer's To={to} row outputs into the next layer's "
                f"Ti={ti} input tiles, so To must equal Ti")

    @property
    def n(self) -> int:
        return self.layers[0][0][0][0].n

    @property
    def pairs(self) -> int:
        return self.n // 2

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def to(self) -> int:
        return len(self.layers[0])

    @property
    def ti(self) -> int:
        return len(self.layers[0][0])

    @property
    def n_columns(self) -> int:
        """Kernel column count: every layer's tile schedules aligned to
        shared per-column parities (see :func:`deep_grid_parity_arrays`),
        padded to the longest mesh in the network."""
        return _deep_grid_columns_np(self)[0].shape[-1]


def deep_grid_schedule(n: int, depth: int, to: int, ti: int,
                       plans=None) -> DeepGridSchedule:
    """Build a DeepGridSchedule: ``depth`` layers of (to x ti) tile grids.

    ``plans``: optional ``[depth][to][ti]`` nested sequence of per-tile
    ``(v_plan, u_plan)`` pairs (``None`` entries fall back to the Clements
    rectangle); ``None`` uses Clements everywhere — the trainable default.
    """
    if plans is None:
        plans = (((None,) * ti,) * to,) * depth
    if len(plans) != depth:
        raise ValueError(f"{len(plans)} plan grids for depth {depth}")
    layers = []
    for lgrid in plans:
        if lgrid is None:
            lgrid = ((None,) * ti,) * to
        if len(lgrid) != to or any(len(row) != ti for row in lgrid):
            raise ValueError(f"each layer's plans grid must be {to}x{ti}")
        rows = []
        for prow in lgrid:
            row = []
            for pair in prow:
                v_plan, u_plan = (None, None) if pair is None else pair
                sv = (clements_schedule(n) if v_plan is None
                      else schedule_from_plan(v_plan))
                su = (clements_schedule(n) if u_plan is None
                      else schedule_from_plan(u_plan))
                row.append((sv, su))
            rows.append(tuple(row))
        layers.append(tuple(rows))
    return DeepGridSchedule(layers=tuple(layers))


def _align_parities(seqs: list[tuple[int, ...]]) -> tuple[list[int],
                                                            list[list[int]]]:
    """Merge per-tile column parity sequences into one shared sequence.

    Tiles are independent meshes, so a tile may sit out any column as an
    identity cell.  Walking every tile's pending column, a column on
    which all pending tiles agree is taken by all of them; where they
    disagree, the parity of the tile with the most columns left goes
    first (it bounds the merged length) and the others wait.  Returns
    the merged parities and, per tile, the source column of each merged
    column (-1 where the tile sits out).
    """
    ptr = [0] * len(seqs)
    parity: list[int] = []
    sources: list[list[int]] = [[] for _ in seqs]
    while True:
        pending = [(len(s) - p, s[p]) for s, p in zip(seqs, ptr) if p < len(s)]
        if not pending:
            return parity, sources
        q = max(pending)[1] if len({par for _, par in pending}) > 1 \
            else pending[0][1]
        parity.append(q)
        for t, s in enumerate(seqs):
            if ptr[t] < len(s) and s[ptr[t]] == q:
                sources[t].append(ptr[t])
                ptr[t] += 1
            else:
                sources[t].append(-1)


@functools.lru_cache(maxsize=64)
def _deep_grid_columns_np(deep: DeepGridSchedule) -> tuple[np.ndarray,
                                                           np.ndarray]:
    """Per-column parity ``[2, L, C]`` and per-tile source column
    ``[2, L, To, Ti, C]`` (mesh axis 0 = V, 1 = U) of the aligned deep
    schedule, identity-padded to the network-wide column count."""
    merged = []
    for grid in deep.layers:
        for m in (0, 1):
            merged.append(_align_parities(
                [row[i][m].parity for row in grid for i in range(deep.ti)]))
    c = max(len(par) for par, _ in merged)
    parity = np.zeros((2, deep.n_layers, c), np.int32)
    source = np.full((2, deep.n_layers, deep.to * deep.ti, c), -1, np.int64)
    for k, (par, src) in enumerate(merged):
        l, m = divmod(k, 2)
        parity[m, l, : len(par)] = par
        source[m, l, :, : len(par)] = src
    return parity, source.reshape(2, deep.n_layers, deep.to, deep.ti, c)


def deep_grid_parity_arrays(deep: DeepGridSchedule) -> tuple[Array, Array]:
    """``[L, C]`` int32 column parities of the V and U meshes.

    Every tile of a layer shares each column's parity: tiles whose own
    schedules disagree on a column take it as two columns, each tile
    passing the other's as an identity cell (see
    :func:`deep_grid_column_sources`).  Identity-padded columns get
    parity 0.  Host-side build is memoized per schedule (numpy, nothing
    trace-local cached), keyed by content — structurally equal deep grids
    share it.
    """
    parity, _ = _deep_grid_columns_np(deep)
    return jnp.asarray(parity[0]), jnp.asarray(parity[1])


def deep_grid_column_sources(deep: DeepGridSchedule) -> np.ndarray:
    """``[2, L, To, Ti, C]`` source column of each tile's mesh (V, U) for
    every aligned kernel column; -1 marks an identity cell."""
    return _deep_grid_columns_np(deep)[1]


@dataclasses.dataclass(frozen=True)
class NetworkSchedule:
    """Static schedule of an L-layer RFNN for the fused network kernel.

    Each layer is a ``(V, U)`` pair of :class:`MeshSchedule`\\ s over the
    same channel count; the kernel runs all layers in one VMEM residency
    as the L x 1 x 1 :attr:`deep` schedule, where ``C = n_columns`` is the
    max column count over every mesh (shorter meshes are padded with
    identity columns, which the sweep applies as exact no-ops).  Hashable
    and purely static, so it is a jit/static and ``custom_vjp`` nondiff
    argument like :class:`MeshSchedule`.
    """

    layers: tuple[tuple[MeshSchedule, MeshSchedule], ...]

    def __post_init__(self):
        if not self.layers:
            raise ValueError("network schedule needs at least one layer")
        n = self.layers[0][0].n
        for sv, su in self.layers:
            if sv.n != n or su.n != n:
                raise ValueError(
                    f"all layer meshes must share n={n}, got "
                    f"{[(sv.n, su.n) for sv, su in self.layers]}")

    @property
    def n(self) -> int:
        return self.layers[0][0].n

    @property
    def pairs(self) -> int:
        return self.n // 2

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def n_columns(self) -> int:
        return max(max(sv.n_columns, su.n_columns) for sv, su in self.layers)

    @property
    def deep(self) -> DeepGridSchedule:
        """The equivalent L x 1 x 1 :class:`DeepGridSchedule` — the form
        the deep megakernel actually consumes."""
        return DeepGridSchedule(
            layers=tuple((((sv, su),),) for sv, su in self.layers))


def network_schedule(n: int, depth: int,
                     plans=None) -> NetworkSchedule:
    """Build a NetworkSchedule for ``depth`` layers of n-channel meshes.

    ``plans``: optional per-layer ``(v_plan, u_plan)`` pairs (``None``
    entries fall back to the Clements rectangle); ``None`` uses Clements
    everywhere — the trainable default.
    """
    if plans is None:
        plans = ((None, None),) * depth
    if len(plans) != depth:
        raise ValueError(f"{len(plans)} plan pairs for depth {depth}")
    layers = []
    for v_plan, u_plan in plans:
        sv = (clements_schedule(n) if v_plan is None
              else schedule_from_plan(v_plan))
        su = (clements_schedule(n) if u_plan is None
              else schedule_from_plan(u_plan))
        layers.append((sv, su))
    return NetworkSchedule(layers=tuple(layers))


# ---------------------------------------------------------------------------
# Tile-grid schedules: a (To x Ti) grid of per-tile (V, U) schedules for the
# tile-grid megakernel (one pallas_call for a large blocked matmul)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileGridSchedule:
    """Static schedule of a (To x Ti) grid of analog tile processors.

    Each grid entry is a ``(V, U)`` pair of :class:`MeshSchedule`\\ s over
    the tile channel count ``n`` — one tile realizes one ``n x n`` block
    of a large matrix in SVD mesh form (V-mesh -> diag -> U-mesh -> digital
    scale).  The tile-grid kernel runs an entire tile *row* per grid step:
    every input tile is swept through its meshes and the row's outputs are
    coherently summed in VMEM (the matched-line power-combiner), so a
    ``(To*n) x (Ti*n)`` matmul is one ``pallas_call`` instead of ``To*Ti``
    separate mesh applications.  ``C = n_columns`` is the max column count
    over every mesh in the grid; the kernel consumes the grid as its
    1 x To x Ti :attr:`deep` schedule.  Hashable and purely static — a
    jit/static and ``custom_vjp`` nondiff argument like
    :class:`NetworkSchedule`.
    """

    tiles: tuple[tuple[tuple[MeshSchedule, MeshSchedule], ...], ...]

    def __post_init__(self):
        if not self.tiles or not self.tiles[0]:
            raise ValueError("tile grid needs at least one tile")
        ti = len(self.tiles[0])
        if any(len(row) != ti for row in self.tiles):
            raise ValueError("tile grid must be rectangular")
        n = self.tiles[0][0][0].n
        for row in self.tiles:
            for sv, su in row:
                if sv.n != n or su.n != n:
                    raise ValueError(
                        f"all tile meshes must share n={n}, got "
                        f"({sv.n}, {su.n})")

    @property
    def n(self) -> int:
        return self.tiles[0][0][0].n

    @property
    def pairs(self) -> int:
        return self.n // 2

    @property
    def to(self) -> int:
        return len(self.tiles)

    @property
    def ti(self) -> int:
        return len(self.tiles[0])

    @property
    def n_columns(self) -> int:
        return max(max(sv.n_columns, su.n_columns)
                   for row in self.tiles for sv, su in row)

    @property
    def deep(self) -> DeepGridSchedule:
        """The equivalent 1 x To x Ti :class:`DeepGridSchedule` — the form
        the deep megakernel actually consumes."""
        return DeepGridSchedule(layers=(self.tiles,))


def tile_grid_schedule(n: int, to: int, ti: int,
                       plans=None) -> TileGridSchedule:
    """Build a TileGridSchedule for a (to x ti) grid of n-channel tiles.

    ``plans``: optional ``[to][ti]`` nested sequence of per-tile
    ``(v_plan, u_plan)`` pairs (``None`` entries fall back to the Clements
    rectangle); ``None`` uses Clements everywhere — the trainable default.
    Per-tile Reck programs (the compiled per-tile-SVD path) mix freely with
    Clements tiles; shorter meshes pad with identity columns.
    """
    if plans is None:
        plans = ((None,) * ti,) * to
    if len(plans) != to or any(len(row) != ti for row in plans):
        raise ValueError(f"plans grid must be {to}x{ti}")
    rows = []
    for prow in plans:
        row = []
        for pair in prow:
            v_plan, u_plan = (None, None) if pair is None else pair
            sv = (clements_schedule(n) if v_plan is None
                  else schedule_from_plan(v_plan))
            su = (clements_schedule(n) if u_plan is None
                  else schedule_from_plan(u_plan))
            row.append((sv, su))
        rows.append(tuple(row))
    return TileGridSchedule(tiles=tuple(rows))


def align_columns(coef: Array, source: np.ndarray) -> Array:
    """Gather one tile's ``[C_t, 8, P]`` coefficients onto the aligned
    kernel columns: column ``j`` takes the tile's column ``source[j]``,
    or an identity cell (an exact no-op in the sweep) where it is -1."""
    c, p = coef.shape[-3], coef.shape[-1]
    rows = jnp.asarray(_IDENTITY_ROWS, coef.dtype)[:, None]  # [8, 1]
    full = jnp.concatenate([coef, jnp.broadcast_to(rows, (1, 8, p))], axis=-3)
    return jnp.take(full, jnp.asarray(np.where(source < 0, c, source)),
                    axis=-3)
