"""The ``AnalogProgram`` IR: digital weights compiled onto the RF processor.

The paper's digital->analog transfer (Sec. IV-B, Fig. 11) is a compiler
pipeline: factor trained weight matrices (SVD, Eq. 31), program the two
unitary factors onto cell meshes, snap phases to the device codebook
(Table I), trim against the measured hardware, and hand the result to the
serving kernels.  This module holds the IR those passes transform:

* :class:`ProgramLayer` — one analog layer ``y = |gamma . U (D (V x))|``:
  the SVD targets, the diagonal attenuation + digital gamma, the mesh
  plans/params filled in by the ``program`` pass, the quantization state
  (codebook + integer device codes) and the hardware binding (model +
  frozen phase-noise draw keys) from ``calibrate``.
* :class:`AnalogProgram` — an L-layer stack of those (one entry for a
  single matrix).
* :class:`CompiledProgram` — the ``lower`` pass output: a static
  L x 1 x 1 :class:`~repro.kernels.schedule.DeepGridSchedule` plus the
  stacked ``[L, 1, 1, C, P, 8]`` megakernel coefficients, pre-emitted
  through the pack cache so ``apply`` is pure kernel execution with zero
  packing work.
* :class:`TiledAnalogProgram` — a (To x Ti) grid of per-tile-SVD
  :class:`ProgramLayer`\\ s realizing one large matrix as block sums (the
  paper's Sec. V scale-up story); the per-tile passes
  (``program_tiled``/``quantize_tiled``/``calibrate_tiled``) map the
  single-layer pipeline over every tile independently.
* :class:`CompiledTiledProgram` — the ``lower_tiled`` output: a static
  1 x To x Ti :class:`~repro.kernels.schedule.DeepGridSchedule` plus the
  stacked ``[1, To, Ti, C, P, 8]`` tile-grid tensors; ``apply`` is one
  tile-grid megakernel call (all To*Ti meshes swept and row-combined in
  VMEM).
* :class:`CompiledDeepProgram` — the ``lower_deep`` output: an L-layer
  *cascade* of tile grids on one ``[L, To, Ti, C, P, 8]`` deep
  megakernel — ``apply`` is a single launch for the whole network,
  inter-layer detection in VMEM, placements folded into the launch.

The IR is deliberately host-side (frozen dataclasses, not pytrees): passes
return new programs, and only ``lower`` touches the device.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hardware as hw_lib
from repro.core import mesh as mesh_lib
from repro.core import quantize as q_lib
from repro.kernels import ops as kernel_ops
from repro.kernels.schedule import DeepGridSchedule

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ProgramLayer:
    """One analog layer of the IR; passes fill in the optional fields."""

    n: int                      # padded square mesh size (even)
    out_dim: int
    in_dim: int
    target: np.ndarray          # [out_dim, in_dim] digital weight matrix
    target_u: np.ndarray        # [n, n] unitary (SVD left factor)
    target_vh: np.ndarray       # [n, n] unitary (SVD right factor, V^H)
    attenuation: Array          # [n] diagonal D / sigma_max, in [0, 1]
    scale: Array                # digital gamma (sigma_max), scalar f32
    # filled by the ``program`` pass
    v_plan: mesh_lib.MeshPlan | None = None
    v_params: dict | None = None
    u_plan: mesh_lib.MeshPlan | None = None
    u_params: dict | None = None
    # filled by the ``quantize`` pass
    codebook: Array | None = None
    quant_mode: str | None = None        # "nearest" | "ste"
    v_codes: dict | None = None          # integer device state codes
    u_codes: dict | None = None
    # filled by the ``calibrate`` pass
    hardware: hw_lib.HardwareModel | None = None
    key_v: Array | None = None           # frozen per-device noise draws
    key_u: Array | None = None

    @property
    def programmed(self) -> bool:
        return self.v_params is not None and self.u_params is not None

    def replace(self, **kw) -> "ProgramLayer":
        return dataclasses.replace(self, **kw)

    def device_params(self, which: str) -> dict:
        """The phases the device realizes: codebook-snapped when quantized.

        ``quant_mode="nearest"`` layers store snapped params already (the
        snap is then idempotent); ``"ste"`` layers keep continuous masters
        and snap here, at the device boundary.
        """
        params = self.v_params if which == "v" else self.u_params
        if params is None:
            raise ValueError(f"layer has no programmed {which!r} mesh — "
                             "run the `program` pass first")
        if self.codebook is None:
            return params
        return q_lib.quantize_mesh_params(params, self.codebook, ste=False)

    def padded_target(self) -> np.ndarray:
        """The [n, n] zero-padded complex target matrix."""
        t = np.zeros((self.n, self.n), np.complex128)
        t[: self.out_dim, : self.in_dim] = self.target
        return t


@dataclasses.dataclass(frozen=True)
class AnalogProgram:
    """An L-layer analog program (L == 1 for a single matrix)."""

    layers: tuple[ProgramLayer, ...]

    def __post_init__(self):
        if not self.layers:
            raise ValueError("an AnalogProgram needs at least one layer")
        n = self.layers[0].n
        if any(la.n != n for la in self.layers):
            raise ValueError(
                f"all layers must share the padded mesh size, got "
                f"{[la.n for la in self.layers]}")

    @property
    def n(self) -> int:
        return self.layers[0].n

    @property
    def depth(self) -> int:
        return len(self.layers)

    @property
    def in_dim(self) -> int:
        return self.layers[0].in_dim

    @property
    def out_dim(self) -> int:
        return self.layers[-1].out_dim

    @property
    def programmed(self) -> bool:
        return all(la.programmed for la in self.layers)

    def map_layers(self, fn) -> "AnalogProgram":
        return AnalogProgram(layers=tuple(fn(la) for la in self.layers))

    def n_cells(self) -> int:
        return sum(la.v_plan.n_cells + la.u_plan.n_cells
                   for la in self.layers if la.programmed)


def layer_matrix(layer: ProgramLayer, *, device: bool = True,
                 with_hardware: bool = True) -> np.ndarray:
    """The complex [out_dim, in_dim] matrix a programmed layer realizes.

    Runs the kernel path (two ``ops.mesh_apply`` probes over the identity
    batch).  ``device=True`` uses the codebook-snapped phases (what the
    hardware actually holds); ``with_hardware=True`` includes the layer's
    hardware binding and its frozen noise-draw keys, so the result is the
    as-fabricated matrix the ``calibrate`` pass fitted against.
    """
    if not layer.programmed:
        raise ValueError("layer is not programmed")
    vp = layer.device_params("v") if device else layer.v_params
    up = layer.device_params("u") if device else layer.u_params
    hw = layer.hardware if with_hardware else None
    kv = layer.key_v if with_hardware else None
    ku = layer.key_u if with_hardware else None
    probes = jnp.eye(layer.n, dtype=jnp.complex64)
    h = kernel_ops.mesh_apply(vp, probes, n=layer.n, plan=layer.v_plan,
                              hardware=hw, key=kv)
    h = h * layer.attenuation.astype(jnp.complex64)
    h = kernel_ops.mesh_apply(up, h, n=layer.n, plan=layer.u_plan,
                              hardware=hw, key=ku)
    rec = jnp.asarray(layer.scale, jnp.complex64) * h
    return np.asarray(rec).T[: layer.out_dim, : layer.in_dim]


def program_error(prog: AnalogProgram, *, device: bool = True,
                  with_hardware: bool = True) -> float:
    """Worst-case elementwise synthesis error across the program's layers."""
    return max(
        float(np.abs(layer_matrix(la, device=device,
                                  with_hardware=with_hardware)
                     - la.target).max())
        for la in prog.layers)


@dataclasses.dataclass(frozen=True)
class TiledAnalogProgram:
    """A (To x Ti) grid of single-layer analog programs for one matrix.

    Each grid entry is a :class:`ProgramLayer` (n = tile, depth 1) whose
    target is the corresponding tile-sized block of the (zero-padded)
    ``[out_dim, in_dim]`` matrix; row sums of the realized tile matrices
    reconstruct the full matmul.  The tiled passes map the per-layer
    pipeline over the grid, so quantization and hardware calibration run
    per tile — exactly how a physical grid of 8x8 processors would be
    trimmed device by device.
    """

    out_dim: int
    in_dim: int
    tile: int
    grid: tuple[tuple[ProgramLayer, ...], ...]
    # logical -> physical grid permutation from the yield-aware placement
    # pass (compile/placement.py); None = grid is in logical order
    placement: "object | None" = None

    def __post_init__(self):
        if not self.grid or not self.grid[0]:
            raise ValueError("a TiledAnalogProgram needs at least one tile")
        ti = len(self.grid[0])
        if any(len(row) != ti for row in self.grid):
            raise ValueError("tile grid must be rectangular")
        if any(la.n != self.tile for row in self.grid for la in row):
            raise ValueError("every tile must have n == tile "
                             f"({self.tile}), got "
                             f"{sorted({la.n for row in self.grid for la in row})}")

    @property
    def to(self) -> int:
        return len(self.grid)

    @property
    def ti(self) -> int:
        return len(self.grid[0])

    @property
    def programmed(self) -> bool:
        return all(la.programmed for row in self.grid for la in row)

    def map_tiles(self, fn) -> "TiledAnalogProgram":
        """New program with ``fn(o, i, layer)`` applied to every tile."""
        return dataclasses.replace(self, grid=tuple(
            tuple(fn(o, i, la) for i, la in enumerate(row))
            for o, row in enumerate(self.grid)))

    def realized_matrix(self, *, device: bool = True,
                        with_hardware: bool = True) -> np.ndarray:
        """The full complex matrix the programmed grid realizes (block
        sums of :func:`layer_matrix` per tile), truncated to
        ``[out_dim, in_dim]``.  A placed grid reports the *logical*
        matrix: physical position ``(po, pi)`` holds logical block
        ``(row_perm[po], col_perm[pi])``."""
        t = self.tile
        m = np.zeros((self.to * t, self.ti * t), np.complex128)
        pl = self.placement
        for po, row in enumerate(self.grid):
            for pi, la in enumerate(row):
                o = pl.row_perm[po] if pl is not None else po
                i = pl.col_perm[pi] if pl is not None else pi
                m[o * t:(o + 1) * t, i * t:(i + 1) * t] = layer_matrix(
                    la, device=device, with_hardware=with_hardware)
        return m[: self.out_dim, : self.in_dim]

    def n_cells(self) -> int:
        return sum(la.v_plan.n_cells + la.u_plan.n_cells
                   for row in self.grid for la in row if la.programmed)


def _prep_input(x: Array, in_dim: int, padded_dim: int) -> Array:
    """Shared compiled-apply preamble: trailing-dim check, complex64 cast,
    zero-pad up to the mesh/grid width."""
    if x.shape[-1] != in_dim:
        raise ValueError(f"expected trailing dim {in_dim}, got {x.shape}")
    if jnp.iscomplexobj(x):
        xc = x.astype(jnp.complex64)
    else:
        xc = jnp.asarray(x, jnp.float32).astype(jnp.complex64)
    pad = padded_dim - in_dim
    if pad:
        xc = jnp.concatenate(
            [xc, jnp.zeros(xc.shape[:-1] + (pad,), xc.dtype)], axis=-1)
    return xc


@dataclasses.dataclass(frozen=True)
class CompiledProgram:
    """The ``lower`` pass output: megakernel inputs, ready to serve.

    ``net``/``packed`` are the ``ops.pack_network`` result emitted at
    lower time; every ``apply`` hands them straight back to
    :func:`repro.kernels.ops.rfnn_network` (``packed=``), so serving does
    **zero** packing work — first tick included, and independent of the
    shared pack cache's eviction policy.  ``layer_args`` (with its stable
    parameter leaf identities, which also keep the cache entry exact) is
    retained as the program's kernel-level parameter view.
    """

    n: int
    in_dim: int
    out_dim: int
    depth: int
    plans: tuple
    layer_args: tuple
    hardware: hw_lib.HardwareModel | None
    net: DeepGridSchedule        # L x 1 x 1 deep-grid schedule
    packed: tuple                # (coef_v [L,1,1,C,8,P], coef_u, gains)
    block_b: int | None = None
    interpret: bool | None = None
    # the AnalogProgram this was lowered from (recovery/introspection);
    # not part of the kernel contract
    source: "AnalogProgram | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- ServableProgram surface (repro.serving.servable) ---------------
    @property
    def n_in(self) -> int:
        return self.in_dim

    @property
    def n_out(self) -> int:
        return self.out_dim

    @property
    def placement(self):
        return None              # a single mesh has no tile placement

    def recover(self, dead_tiles, **kw) -> "CompiledProgram":
        raise ValueError(
            "CompiledProgram has no tile grid to remap around dead tiles; "
            "tile_down recovery needs a CompiledTiledProgram or "
            "CompiledDeepProgram")

    def apply(self, x: Array) -> Array:
        """``x[..., in_dim]`` -> detected magnitudes ``[..., out_dim]``.

        One fused network-megakernel ``pallas_call``: per layer
        ``|gamma_l . U_l (D_l (V_l .))|`` with the detected magnitude
        feeding the next layer, exactly the multi-layer microwave ANN.
        """
        xc = _prep_input(x, self.in_dim, self.n)
        y = kernel_ops.rfnn_network(
            self.layer_args, xc, n=self.n, plans=self.plans,
            hardware=self.hardware, block_b=self.block_b,
            interpret=self.interpret, packed=(self.net, self.packed))
        return y[..., : self.out_dim]

    def n_cells(self) -> int:
        return sum(vp.n_cells + up.n_cells for vp, up in self.plans)


@dataclasses.dataclass(frozen=True)
class CompiledTiledProgram:
    """The ``lower_tiled`` pass output: tile-grid kernel inputs, servable.

    ``grid``/``packed`` are the ``ops.pack_tile_grid`` result emitted at
    lower time — every ``apply`` hands them straight back to
    :func:`repro.kernels.ops.tiled_apply` (``packed=``), so serving does
    **zero** packing work, first tick included, independent of the shared
    pack cache's eviction policy.  ``tile_args`` (stable parameter leaf
    identities) is retained as the program's kernel-level parameter view.
    """

    out_dim: int
    in_dim: int
    tile: int
    to: int
    ti: int
    plans: tuple                 # [To][Ti] of (v_plan, u_plan)
    tile_args: tuple             # [To][Ti] of kernel argument dicts
    hardware: hw_lib.HardwareModel | None
    grid: "object"               # 1 x To x Ti DeepGridSchedule (static)
    packed: tuple                # (coef_v [1,To,Ti,C,P,8], coef_u, gains)
    block_b: int | None = None
    interpret: bool | None = None
    # yield-aware placement (compile/placement.py): the kernel runs the
    # physically-permuted grid; apply() permutes the digital tile streams
    placement: "object | None" = None
    # optional (tile-row x batch) scale-out: with a 2-axis mesh every
    # apply shards through kernels/ops.tiled_apply's shard_map path
    mesh: "object | None" = None
    row_axis: str = "rows"
    data_axis: str = "data"
    # the TiledAnalogProgram this was lowered from — the recovery path
    # re-places/re-lowers it around dead tiles; not part of the kernel
    # contract
    source: "TiledAnalogProgram | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- ServableProgram surface (repro.serving.servable) ---------------
    @property
    def n_in(self) -> int:
        return self.in_dim

    @property
    def n_out(self) -> int:
        return self.out_dim

    def recover(self, dead_tiles, hardware: "hw_lib.HardwareModel | None"
                = None, *, key: Array | None = None, steps: int = 0,
                max_dropped_mass: float = 0.05,
                **calibrate_kw) -> "CompiledTiledProgram":
        """Recompile this program around dead physical tile positions.

        The full PR-6 recovery pipeline in one call: plan a remap that
        parks the least-sensitive logical tiles on the dead positions
        (:func:`repro.runtime.elastic.plan_tile_recovery`), re-place /
        blank / re-trim / re-lower (:func:`repro.compile.recover_tiled`),
        and carry this program's mesh scale-out settings onto the result.
        ``steps`` is the re-calibration budget for moved tiles (0 =
        re-bind draws only — the serving engine's mid-stream default;
        raise it for a full offline re-trim).  Raises when the remap
        would drop more than ``max_dropped_mass`` of the sensitivity
        mass, or when the program was built without its ``source``.
        """
        if self.source is None:
            raise ValueError(
                "this CompiledTiledProgram carries no source "
                "TiledAnalogProgram to re-place; re-lower it with "
                "repro.compile.lower_tiled or pass recovery= to the "
                "serving engine")
        from repro.compile import placement as place_lib
        from repro.runtime.elastic import plan_tile_recovery

        tp = self.source
        pl = tp.placement
        plan = plan_tile_recovery(
            place_lib.tile_sensitivities(place_lib.undo_placement(tp)),
            sorted({(int(o), int(i)) for o, i in dead_tiles}),
            row_perm=pl.row_perm if pl is not None else None,
            col_perm=pl.col_perm if pl is not None else None,
            max_dropped_mass=max_dropped_mass)
        if not plan.viable:
            raise ValueError(f"tile recovery is not viable: {plan.reason}")
        out = place_lib.recover_tiled(
            tp, plan, self.hardware if hardware is None else hardware,
            key=key, lower=True, block_b=self.block_b,
            interpret=self.interpret, steps=steps, **calibrate_kw)
        return dataclasses.replace(out, mesh=self.mesh,
                                   row_axis=self.row_axis,
                                   data_axis=self.data_axis)

    def apply(self, x: Array) -> Array:
        """``x[..., in_dim]`` -> detected magnitudes ``[..., out_dim]``.

        One fused tile-grid ``pallas_call``: every input tile sweeps
        through its row's meshes, rows combine coherently in VMEM, and
        the detector reads the combined magnitude — the paper's blocked
        scale-up of the 8x8 processor with zero per-tile launches.

        A placed program feeds physical column ``pi`` logical input tile
        ``col_perm[pi]`` and reads logical output row ``r`` from physical
        row ``inv_row_perm[r]`` — two index gathers on the digital tile
        streams, zero kernel changes.
        """
        xc = _prep_input(x, self.in_dim, self.ti * self.tile)
        pl = self.placement
        permuted = pl is not None and not pl.is_identity
        if permuted:
            xt = xc.reshape(xc.shape[:-1] + (self.ti, self.tile))
            xc = jnp.take(xt, jnp.asarray(pl.col_perm), axis=-2).reshape(
                xc.shape)
        y = kernel_ops.tiled_apply(
            self.tile_args, xc, n=self.tile, plans=self.plans,
            hardware=self.hardware, block_b=self.block_b,
            interpret=self.interpret, packed=(self.grid, self.packed),
            mesh=self.mesh, row_axis=self.row_axis,
            data_axis=self.data_axis)
        if permuted:
            yt = y.reshape(y.shape[:-1] + (self.to, self.tile))
            y = jnp.take(yt, jnp.asarray(pl.inv_row_perm),
                         axis=-2).reshape(y.shape)
        return jnp.abs(y)[..., : self.out_dim]

    def n_cells(self) -> int:
        return sum(vp.n_cells + up.n_cells
                   for row in self.plans for vp, up in row)


@dataclasses.dataclass(frozen=True)
class CompiledDeepProgram:
    """The ``lower_deep`` pass output: a whole multi-layer tiled network,
    one megakernel launch per direction.

    ``deep``/``packed`` are the ``ops.pack_deep_grid`` result emitted at
    lower time — every ``apply`` hands them straight back to
    :func:`repro.kernels.ops.deep_apply` (``packed=``), so serving does
    **zero** packing work, first tick included.  Inter-layer activations
    never leave VMEM: the kernel re-detects each layer's combined row
    magnitudes in place and feeds them to the next layer's tiles — the
    fully-analog cascade, with no digital stop between layers.

    Placements fold into the packed tensors: layer 0's column placement
    is undone by a digital input gather and the last layer's row
    placement by a digital output gather (exactly like
    :class:`CompiledTiledProgram`), while every *interior* boundary was
    resolved at pack time — each layer ``l >= 1`` packs its tile columns
    in the physical row order of layer ``l - 1``'s outputs, so the
    in-kernel handoff needs no permutation at all.  Per-tile calibration
    keys ride inside ``layer_args`` untouched.
    """

    out_dim: int
    in_dim: int
    tile: int
    depth: int
    to: int
    ti: int
    plans: tuple                 # [L][To][Ti] of (v_plan, u_plan)
    layer_args: tuple            # [L][To][Ti] of kernel argument dicts
    hardware: hw_lib.HardwareModel | None
    deep: "object"               # DeepGridSchedule (static)
    packed: tuple                # (coef_v [L,To,Ti,C,8,P], coef_u, gains)
    block_b: int | None = None
    interpret: bool | None = None
    # layer 0's placement (input gather) and the last layer's placement
    # (output gather); interior placements are already folded into packed
    in_placement: "object | None" = None
    out_placement: "object | None" = None
    # optional (tile-row x batch) scale-out through deep_apply's
    # shard_map path (depth runs as a chain of single-layer launches)
    mesh: "object | None" = None
    row_axis: str = "rows"
    data_axis: str = "data"
    # the per-layer TiledAnalogPrograms this was lowered from (logical
    # column order, placements still attached) — the recovery path
    # re-places one layer and re-lowers the cascade; not part of the
    # kernel contract
    sources: "tuple[TiledAnalogProgram, ...] | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- ServableProgram surface (repro.serving.servable) ---------------
    @property
    def n_in(self) -> int:
        return self.in_dim

    @property
    def n_out(self) -> int:
        return self.out_dim

    @property
    def placement(self):
        return self.out_placement

    def recover(self, dead_tiles, hardware: "hw_lib.HardwareModel | None"
                = None, *, layer: int = 0, key: Array | None = None,
                steps: int = 0, max_dropped_mass: float = 0.05,
                **calibrate_kw) -> "CompiledDeepProgram":
        """Recompile the cascade around dead tiles in one layer's grid.

        ``dead_tiles`` are physical ``(o, i)`` positions in layer
        ``layer``'s grid.  The damaged layer is re-placed/blanked/
        re-trimmed exactly like :meth:`CompiledTiledProgram.recover`
        (``lower=False``), then the whole cascade is re-lowered through
        ``lower_deep`` so the interior placement folding stays
        consistent.  Needs the program's ``sources``.
        """
        if self.sources is None:
            raise ValueError(
                "this CompiledDeepProgram carries no source layer programs "
                "to re-place; re-lower it with repro.compile.lower_deep or "
                "pass recovery= to the serving engine")
        if not 0 <= layer < len(self.sources):
            raise ValueError(f"layer {layer} outside depth "
                             f"{len(self.sources)} cascade")
        from repro.compile import passes as passes_lib
        from repro.compile import placement as place_lib
        from repro.runtime.elastic import plan_tile_recovery

        tp = self.sources[layer]
        pl = tp.placement
        plan = plan_tile_recovery(
            place_lib.tile_sensitivities(place_lib.undo_placement(tp)),
            sorted({(int(o), int(i)) for o, i in dead_tiles}),
            row_perm=pl.row_perm if pl is not None else None,
            col_perm=pl.col_perm if pl is not None else None,
            max_dropped_mass=max_dropped_mass)
        if not plan.viable:
            raise ValueError(f"tile recovery is not viable: {plan.reason}")
        recovered = place_lib.recover_tiled(
            tp, plan, self.hardware if hardware is None else hardware,
            key=key, lower=False, interpret=self.interpret, steps=steps,
            **calibrate_kw)
        srcs = (self.sources[:layer] + (recovered,)
                + self.sources[layer + 1:])
        return passes_lib.lower_deep(
            srcs, block_b=self.block_b, interpret=self.interpret,
            mesh=self.mesh, row_axis=self.row_axis,
            data_axis=self.data_axis)

    def apply(self, x: Array) -> Array:
        """``x[..., in_dim]`` -> detected magnitudes ``[..., out_dim]``.

        One fused deep-grid ``pallas_call``: every layer's tiles sweep,
        rows combine coherently, the detector reads each layer's rows in
        VMEM and feeds the next — the paper's multi-layer microwave ANN
        scale-up as a single forward (and a single backward) launch.
        """
        xc = _prep_input(x, self.in_dim, self.ti * self.tile)
        pin = self.in_placement
        if pin is not None and not pin.is_identity:
            xt = xc.reshape(xc.shape[:-1] + (self.ti, self.tile))
            xc = jnp.take(xt, jnp.asarray(pin.col_perm), axis=-2).reshape(
                xc.shape)
        y = kernel_ops.deep_apply(
            self.layer_args, xc, n=self.tile, plans=self.plans,
            hardware=self.hardware, block_b=self.block_b,
            interpret=self.interpret, packed=(self.deep, self.packed),
            readout="magnitude", mesh=self.mesh, row_axis=self.row_axis,
            data_axis=self.data_axis)
        pout = self.out_placement
        if pout is not None and not pout.is_identity:
            yt = y.reshape(y.shape[:-1] + (self.to, self.tile))
            y = jnp.take(yt, jnp.asarray(pout.inv_row_perm),
                         axis=-2).reshape(y.shape)
        return y[..., : self.out_dim]

    def n_cells(self) -> int:
        return sum(vp.n_cells + up.n_cells
                   for grid in self.plans for row in grid
                   for vp, up in row)
