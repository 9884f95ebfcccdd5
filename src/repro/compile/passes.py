"""Composable compiler passes over the :class:`AnalogProgram` IR.

The pipeline mirrors the paper's digital->analog transfer (Fig. 11):

    synthesize -> program -> [quantize] -> [calibrate] -> lower

* :func:`synthesize` — SVD-factor each digital weight matrix into
  ``U . D . V^H`` with the overall scale recovered digitally (Eq. 31);
  owns the factorization that used to live in ``core/svd_synthesis``.
* :func:`program` — fill in mesh plans/params for both unitary factors:
  analytically (:func:`repro.core.decompose.reck_program`) or by the
  kernel-backed gradient fit (the paper's "stochastic optimization"
  programming, Sec. IV-B) — identity probes swept through
  ``ops.mesh_apply`` columns under :class:`repro.optim.AdamW`, fully
  jitted, never touching the pure-jnp reference.
* :func:`quantize` — snap phases onto a discrete codebook (Table I or
  ``uniform<bits>``), either immediately (``nearest``) or keeping
  continuous masters for later quantization-aware fits (``ste``); records
  the integer device state codes either way.
* :func:`calibrate` — hardware-in-the-loop residual fit: re-fit phases
  (through the codebook's straight-through estimator when quantized) and
  the digital gains against the *imperfect* device, probing it through
  ``ops.mesh_apply(hardware=...)`` with frozen per-device noise-draw keys
  — the same ``imperfect_cell_matrix`` + key consumption as the reference
  path, so calibration and serving see the device draw-for-draw.
* :func:`lower` — emit the megakernel inputs (an L x 1 x 1
  ``DeepGridSchedule`` + stacked ``[L, 1, 1, C, P, 8]`` coefficients)
  through the existing ``ops.pack_network`` leaf-identity cache and
  return a :class:`CompiledProgram` whose ``apply`` is pure kernel
  execution.  :func:`lower_deep` lowers a *chain* of tiled programs onto
  one ``L x To x Ti`` deep megakernel (:class:`CompiledDeepProgram`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile.program import (
    AnalogProgram,
    CompiledDeepProgram,
    CompiledProgram,
    CompiledTiledProgram,
    ProgramLayer,
    TiledAnalogProgram,
)
from repro.core import decompose
from repro.core import hardware as hw_lib
from repro.core import mesh as mesh_lib
from repro.core import quantize as q_lib
from repro.kernels import ops as kernel_ops
from repro.optim.adamw import AdamW

Array = jax.Array


def _pad_even(k: int) -> int:
    return k + (k % 2)


# ---------------------------------------------------------------------------
# synthesize
# ---------------------------------------------------------------------------

def synthesize(matrices, *, n: int | None = None) -> AnalogProgram:
    """SVD-factor digital weight matrices into analog layer specs.

    ``matrices``: one ``[out, in]`` array or a sequence of them (a layer
    stack).  Every layer is zero-padded to a common even mesh size ``n``
    (default: the enclosing square of the largest layer) so the stack can
    later lower onto one network megakernel.  The diagonal is normalized
    by the largest singular value — a passive network only attenuates —
    and the scale is recovered digitally (the paper's gamma, Fig. 11).
    """
    if not isinstance(matrices, (list, tuple)):
        matrices = [np.asarray(matrices)]
    elif matrices and np.ndim(matrices[0]) <= 1:
        matrices = [np.asarray(matrices)]   # one matrix as nested lists
    else:
        matrices = [np.asarray(m) for m in matrices]
    if not matrices:
        raise ValueError("need at least one matrix")
    if n is None:
        n = max(_pad_even(max(m.shape)) for m in matrices)
    if n < 2 or n % 2:
        raise ValueError(f"mesh size must be even and >= 2, got n={n}")
    layers = []
    for m in matrices:
        out_dim, in_dim = m.shape
        if max(out_dim, in_dim) > n:
            raise ValueError(f"matrix {m.shape} exceeds mesh size n={n}")
        mp = np.zeros((n, n), np.complex128)
        mp[:out_dim, :in_dim] = m
        u, s, vh = np.linalg.svd(mp)
        smax = float(s.max()) if s.max() > 0 else 1.0
        layers.append(ProgramLayer(
            n=n, out_dim=out_dim, in_dim=in_dim, target=m.copy(),
            target_u=u, target_vh=vh,
            attenuation=jnp.asarray(s / smax, jnp.float32),
            scale=jnp.asarray(smax, jnp.float32)))
    for prev, nxt in zip(layers, layers[1:]):
        if prev.out_dim != nxt.in_dim:
            raise ValueError(
                f"layer stack does not chain: out_dim {prev.out_dim} feeds "
                f"in_dim {nxt.in_dim} (extra channels would be dropped "
                "silently)")
    return AnalogProgram(layers=tuple(layers))


# ---------------------------------------------------------------------------
# program
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("plan", "opt", "steps", "interpret"))
def _fit_run(params, state, target, probes, *, plan, opt, steps, interpret):
    """The fit-programming step loop, jitted once per (plan, opt, steps).

    Module-level so the trace cache is shared across layers and pass
    invocations — every layer of a stack reuses one compilation (targets
    and initializations are ordinary arguments).
    """
    def loss_fn(p):
        cols = kernel_ops.mesh_apply(p, probes, n=plan.n, plan=plan,
                                     interpret=interpret)
        return jnp.sum(jnp.abs(cols.T - target) ** 2)

    def step(carry, _):
        p, s = carry
        _, g = jax.value_and_grad(loss_fn)(p)
        p, s, _ = opt.update(p, g, s)
        return (p, s), None

    (params, _), _ = jax.lax.scan(step, (params, state), None, length=steps)
    return params


def _fit_unitary(target: np.ndarray, plan: mesh_lib.MeshPlan, *,
                 steps: int, lr: float, seed: int,
                 interpret: bool | None) -> dict:
    """Kernel-backed gradient programming of one unitary onto ``plan``.

    Identity probes swept through the fused ``ops.mesh_apply`` kernel
    reconstruct the realized matrix column-by-column; AdamW minimizes the
    Frobenius error in one jitted ``lax.scan`` (input phase screen on —
    required for universality of the single-phase cell, see DESIGN.md).
    """
    target = jnp.asarray(target, jnp.complex64)
    n = plan.n
    params = mesh_lib.init_mesh_params(jax.random.PRNGKey(seed), plan,
                                       with_sigma=True)
    params["alpha_in"] = jnp.zeros((n,), jnp.float32)
    probes = jnp.eye(n, dtype=jnp.complex64)
    opt = AdamW(lr=lr, b1=0.9, b2=0.999, weight_decay=0.0, clip_norm=0.0)
    if steps <= 0:
        return params
    return dict(_fit_run(params, opt.init(params), target, probes,
                         plan=plan, opt=opt, steps=steps,
                         interpret=interpret))


def program(prog: AnalogProgram, method: str = "reck", *,
            steps: int = 1500, lr: float = 0.05, seed: int = 0,
            interpret: bool | None = None) -> AnalogProgram:
    """Fill in mesh plans/params realizing each layer's unitary factors.

    ``method="reck"``: exact analytic factorization (triangular layout).
    ``method="fit"``: the paper's stochastic-optimization programming on
    the rectangular Clements layout, via the kernel-backed AdamW fit.
    """
    if method not in ("reck", "fit"):
        raise ValueError(f"unknown programming method {method!r}")

    def one(i, la):
        if method == "reck":
            u_plan, u_params = decompose.reck_program(la.target_u)
            v_plan, v_params = decompose.reck_program(la.target_vh)
        else:
            plan = mesh_lib.clements_plan(la.n)
            u_params = _fit_unitary(la.target_u, plan, steps=steps, lr=lr,
                                    seed=seed + 2 * i, interpret=interpret)
            v_params = _fit_unitary(la.target_vh, plan, steps=steps, lr=lr,
                                    seed=seed + 2 * i + 1,
                                    interpret=interpret)
            u_plan = v_plan = plan
        return la.replace(v_plan=v_plan, v_params=v_params,
                          u_plan=u_plan, u_params=u_params)

    return AnalogProgram(layers=tuple(
        one(i, la) for i, la in enumerate(prog.layers)))


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

def resolve_codebook(codebook) -> Array:
    """``"table1"`` | ``"uniform<bits>"`` | explicit phase array."""
    if isinstance(codebook, str):
        if codebook == "table1":
            return q_lib.table_i_codebook()
        if codebook.startswith("uniform"):
            return q_lib.uniform_codebook(int(codebook[len("uniform"):]))
        raise ValueError(f"unknown codebook {codebook!r}")
    return jnp.asarray(codebook, jnp.float32)


def quantize(prog: AnalogProgram, codebook="table1", *,
             mode: str = "nearest") -> AnalogProgram:
    """Snap mesh phases onto the discrete device codebook (Table I).

    ``mode="nearest"`` stores the snapped phases directly; ``mode="ste"``
    keeps the continuous masters (snapping happens at the device boundary
    — ``lower`` and ``layer_matrix`` — and later gradient fits see the
    codebook through the straight-through estimator).  Both record the
    integer device state codes.
    """
    if mode not in ("nearest", "ste"):
        raise ValueError(f"unknown quantize mode {mode!r}")
    cb = resolve_codebook(codebook)

    def one(la: ProgramLayer) -> ProgramLayer:
        if not la.programmed:
            raise ValueError("quantize needs a programmed layer — run the "
                             "`program` pass first")
        vp, up = la.v_params, la.u_params
        if mode == "nearest":
            vp = q_lib.quantize_mesh_params(vp, cb, ste=False)
            up = q_lib.quantize_mesh_params(up, cb, ste=False)
        return la.replace(
            v_params=vp, u_params=up, codebook=cb, quant_mode=mode,
            v_codes=q_lib.mesh_params_to_codes(vp, cb),
            u_codes=q_lib.mesh_params_to_codes(up, cb))

    return prog.map_layers(one)


# ---------------------------------------------------------------------------
# calibrate
# ---------------------------------------------------------------------------

def logit(p: Array) -> Array:
    """Inverse sigmoid, clipped to (1e-6, 1 - 1e-6) — the link function for
    attenuation logits (shared with ``AnalogLinear.init_from_matrix``)."""
    p = jnp.clip(p, 1e-6, 1.0 - 1e-6)
    return jnp.log(p / (1.0 - p))


def inv_softplus(s: Array) -> Array:
    """Inverse softplus, guarded at 1e-6 — the link function for the
    digital-gamma log-scale (shared with ``AnalogLinear.init_from_matrix``)."""
    return jnp.log(jnp.expm1(jnp.maximum(s, 1e-6)))


@functools.partial(jax.jit,
                   static_argnames=("v_plan", "u_plan", "hardware", "opt",
                                    "steps", "fit_gains", "interpret"))
def _calibration_run(train, state, base_v, base_u, atten0, scale0, probes,
                     target, codebook, kv, ku, *, v_plan, u_plan, hardware,
                     opt, steps, fit_gains, interpret):
    """The calibration step loop, jitted once per (plans, opt, steps).

    Module-level so homogeneous layer stacks (equal-content plans hash to
    the same statics) share one compilation across layers and calls.
    Keeps the best-seen iterate: STE steps can hop phases across code
    boundaries non-monotonically, and the start point (the uncalibrated
    program) is evaluated first — so calibration never returns something
    worse than its input.
    """
    n = v_plan.n

    def realize(tr):
        vp = tr.get("v", base_v)
        up = tr.get("u", base_u)
        if codebook is not None:
            vp = q_lib.quantize_mesh_params(vp, codebook, ste=True)
            up = q_lib.quantize_mesh_params(up, codebook, ste=True)
        atten = jax.nn.sigmoid(tr["atten_logit"]) if fit_gains else atten0
        scale = jax.nn.softplus(tr["log_scale"]) if fit_gains else scale0
        h = kernel_ops.mesh_apply(vp, probes, n=n, plan=v_plan,
                                  hardware=hardware, key=kv,
                                  interpret=interpret)
        h = h * atten.astype(jnp.complex64)
        h = kernel_ops.mesh_apply(up, h, n=n, plan=u_plan,
                                  hardware=hardware, key=ku,
                                  interpret=interpret)
        return (scale.astype(jnp.complex64) * h).T

    def loss_fn(tr):
        return jnp.sum(jnp.abs(realize(tr) - target) ** 2)

    def step(carry, _):
        tr, st, best_tr, best_loss = carry
        loss, g = jax.value_and_grad(loss_fn)(tr)
        better = loss < best_loss
        best_tr = jax.tree.map(
            lambda b, c: jnp.where(better, c, b), best_tr, tr)
        best_loss = jnp.minimum(loss, best_loss)
        tr, st, _ = opt.update(tr, g, st)
        return (tr, st, best_tr, best_loss), None

    carry = (train, state, train, jnp.asarray(jnp.inf, jnp.float32))
    (tr, _, best_tr, best_loss), _ = jax.lax.scan(step, carry, None,
                                                  length=steps)
    final_loss = loss_fn(tr)
    take_final = final_loss < best_loss
    return jax.tree.map(lambda b, c: jnp.where(take_final, c, b),
                        best_tr, tr)


def calibrate(prog: AnalogProgram,
              hardware: hw_lib.HardwareModel | None = None, *,
              key: Array | None = None, steps: int = 200, lr: float = 0.02,
              fit_phases: bool = True, fit_gains: bool = True,
              interpret: bool | None = None) -> AnalogProgram:
    """Hardware-in-the-loop residual fit of each layer against its target.

    Probes the *imperfect* device (``ops.mesh_apply`` with ``hardware``,
    phase noise frozen per layer by keys folded from ``key`` — consumed
    exactly like the reference ``apply_mesh_hw`` path, so the calibrated
    program later serves against the identical draw) and re-fits the mesh
    phases and the digital gains (attenuation + gamma) to minimize the
    Frobenius error of the realized matrix.  Quantized layers fit their
    continuous masters through the codebook's straight-through estimator
    and keep updated device codes (``quant_mode`` becomes ``"ste"``).

    ``hardware=None`` calibrates against ideal cells — useful to trim
    pure quantization error.  Returns a program with the hardware model
    and draw keys *bound*, so ``lower`` serves the calibrated device.
    """
    def one(i, la: ProgramLayer) -> ProgramLayer:
        if not la.programmed:
            raise ValueError("calibrate needs a programmed layer — run the "
                             "`program` pass first")
        kv = ku = None
        if hardware is not None and key is not None:
            kv, ku = jax.random.split(jax.random.fold_in(key, i))
        target = jnp.asarray(la.padded_target(), jnp.complex64)
        probes = jnp.eye(la.n, dtype=jnp.complex64)

        train = {}
        if fit_phases:
            train["v"] = dict(la.v_params)
            train["u"] = dict(la.u_params)
        if fit_gains:
            train["atten_logit"] = logit(la.attenuation)
            train["log_scale"] = inv_softplus(
                jnp.asarray(la.scale, jnp.float32))

        opt = AdamW(lr=lr, b1=0.9, b2=0.999, weight_decay=0.0,
                    clip_norm=0.0)
        ran = bool(train) and steps > 0
        if ran:
            train = _calibration_run(
                train, opt.init(train), la.v_params, la.u_params,
                jnp.asarray(la.attenuation, jnp.float32),
                jnp.asarray(la.scale, jnp.float32), probes, target,
                la.codebook, kv, ku, v_plan=la.v_plan, u_plan=la.u_plan,
                hardware=hardware, opt=opt, steps=steps,
                fit_gains=fit_gains, interpret=interpret)
        # steps=0 binds the device without trimming: parameters (and the
        # gains' logit/softplus round trip) stay bit-identical
        vp = dict(train["v"]) if fit_phases and ran else la.v_params
        up = dict(train["u"]) if fit_phases and ran else la.u_params
        new = dict(
            v_params=vp, u_params=up,
            hardware=hardware, key_v=kv, key_u=ku)
        if fit_gains and ran:
            new["attenuation"] = jax.nn.sigmoid(train["atten_logit"])
            new["scale"] = jax.nn.softplus(train["log_scale"])
        if la.codebook is not None and ran:
            new["quant_mode"] = "ste"
            new["v_codes"] = q_lib.mesh_params_to_codes(vp, la.codebook)
            new["u_codes"] = q_lib.mesh_params_to_codes(up, la.codebook)
        return la.replace(**new)

    return AnalogProgram(layers=tuple(
        one(i, la) for i, la in enumerate(prog.layers)))


# ---------------------------------------------------------------------------
# lower
# ---------------------------------------------------------------------------

def lower(prog: AnalogProgram, *, block_b: int | None = None,
          interpret: bool | None = None) -> CompiledProgram:
    """Emit megakernel inputs and return a servable :class:`CompiledProgram`.

    Builds the per-layer kernel argument dicts (device-snapped phases,
    attenuation, digital gamma, bound noise keys), then emits the
    L x 1 x 1 :class:`DeepGridSchedule` and the stacked
    ``[L, 1, 1, C, P, 8]`` coefficient tensors through ``ops.pack_network`` — the same leaf-identity pack
    cache the serving path reads, so the tensors are packed exactly once,
    here, and every subsequent ``apply`` (and every serving tick) finds
    them already resident.
    """
    if not prog.programmed:
        raise ValueError("lower needs a fully programmed AnalogProgram — "
                         "run the `program` pass first")
    hardwares = {la.hardware for la in prog.layers}
    if len(hardwares) > 1:
        raise ValueError("all layers must share one hardware binding, got "
                         f"{hardwares}")
    hardware = next(iter(hardwares))
    layer_args = []
    plans = []
    for la in prog.layers:
        layer_args.append(_tile_kernel_args(la, hardware))
        plans.append((la.v_plan, la.u_plan))
    layer_args = tuple(layer_args)
    plans = tuple(plans)
    net, packed = kernel_ops.pack_network(layer_args, n=prog.n, plans=plans,
                                          hardware=hardware)
    return CompiledProgram(
        n=prog.n, in_dim=prog.in_dim, out_dim=prog.out_dim,
        depth=prog.depth, plans=plans, layer_args=layer_args,
        hardware=hardware, net=net, packed=packed,
        block_b=block_b, interpret=interpret, source=prog)


# ---------------------------------------------------------------------------
# Tiled pipeline: per-tile-SVD programs for matrices larger than one mesh
# ---------------------------------------------------------------------------
#
# The single-matrix pipeline above tops out at one mesh (the prototype's
# 8x8); the tiled pipeline scales past it the way the paper's Sec. V
# sketches: split the matrix into a (To x Ti) grid of tile-sized blocks,
# run the whole per-layer pipeline on every block *independently*
# (synthesize -> program -> quantize -> calibrate, each tile is its own
# physical processor with its own codebook snap and hardware trim) and
# lower the grid onto ONE tile-grid megakernel call — row outputs combine
# coherently in VMEM, the readout detects after combination.


def synthesize_tiled(matrix, tile: int) -> TiledAnalogProgram:
    """SVD-factor a large matrix into a (To x Ti) grid of tile programs.

    The ``[out, in]`` matrix is zero-padded up to multiples of ``tile``
    (even, >= 2) and each ``tile x tile`` block becomes a single-layer
    :class:`ProgramLayer` spec via :func:`synthesize`.  Row sums of the
    realized tiles reconstruct the full matmul; ``apply`` later truncates
    the padding back to ``out``.
    """
    m = np.asarray(matrix)
    if m.ndim != 2:
        raise ValueError(f"need one [out, in] matrix, got shape {m.shape}")
    if tile < 2 or tile % 2:
        raise ValueError(f"tile size must be even and >= 2, got {tile}")
    out_dim, in_dim = m.shape
    to = -(-out_dim // tile)
    ti = -(-in_dim // tile)
    mp = np.zeros((to * tile, ti * tile), m.dtype)
    mp[:out_dim, :in_dim] = m
    grid = []
    for o in range(to):
        row = []
        for i in range(ti):
            block = mp[o * tile:(o + 1) * tile, i * tile:(i + 1) * tile]
            row.append(synthesize(block, n=tile).layers[0])
        grid.append(tuple(row))
    return TiledAnalogProgram(out_dim=out_dim, in_dim=in_dim, tile=tile,
                              grid=tuple(grid))


def program_tiled(tp: TiledAnalogProgram, method: str = "reck",
                  **kw) -> TiledAnalogProgram:
    """:func:`program` mapped over every tile (independent meshes)."""
    return tp.map_tiles(lambda o, i, la: program(
        AnalogProgram((la,)), method, **kw).layers[0])


def quantize_tiled(tp: TiledAnalogProgram, codebook="table1", *,
                   mode: str = "nearest") -> TiledAnalogProgram:
    """:func:`quantize` mapped over every tile (per-device codebooks)."""
    return tp.map_tiles(lambda o, i, la: quantize(
        AnalogProgram((la,)), codebook, mode=mode).layers[0])


def calibrate_tiled(tp: TiledAnalogProgram,
                    hardware: hw_lib.HardwareModel | None = None, *,
                    key: Array | None = None, only=None,
                    **kw) -> TiledAnalogProgram:
    """:func:`calibrate` mapped over every tile.

    Each tile is its own physical device: the noise-draw key is folded
    per *physical* grid position (``o * Ti + i``) so every tile freezes
    an independent draw, and the residual fit trims each tile against
    its own block target through the imperfect kernel path.  On a placed
    grid (``compile/placement.py``) the folding therefore binds each
    tile to the draw of the position it actually occupies.

    ``only``: optional iterable of ``(o, i)`` physical positions — every
    other tile passes through untouched, keeping its existing binding
    bit-identical.  The degraded-grid recovery path uses this to re-trim
    exactly the tiles the remap moved.
    """
    only_set = None if only is None else {tuple(p) for p in only}

    def one(o, i, la):
        if only_set is not None and (o, i) not in only_set:
            return la
        kt = (jax.random.fold_in(key, o * tp.ti + i)
              if key is not None else None)
        return calibrate(AnalogProgram((la,)), hardware, key=kt,
                         **kw).layers[0]

    return tp.map_tiles(one)


def lower_tiled(tp: TiledAnalogProgram, *, block_b: int | None = None,
                interpret: bool | None = None, mesh=None,
                row_axis: str = "rows",
                data_axis: str = "data") -> CompiledTiledProgram:
    """Emit tile-grid kernel inputs; returns a servable
    :class:`CompiledTiledProgram` whose ``apply`` is ONE ``pallas_call``
    per direction over the whole (To x Ti) grid.

    Tensors are emitted through ``ops.pack_tile_grid``'s leaf-identity
    cache — packed exactly once, here — and handed back verbatim on every
    ``apply``, so serving (every tick, the first included) does zero
    packing work.  A placement on ``tp`` is carried onto the compiled
    program (its ``apply`` undoes it digitally); ``mesh`` (a 2-axis
    ``jax.sharding.Mesh``) makes every ``apply`` shard over
    ``(row_axis, data_axis)`` through the kernel's shard_map path.
    """
    if not tp.programmed:
        raise ValueError("lower_tiled needs a fully programmed tile grid — "
                         "run the `program_tiled` pass first")
    hardwares = {la.hardware for row in tp.grid for la in row}
    if len(hardwares) > 1:
        raise ValueError("all tiles must share one hardware binding, got "
                         f"{hardwares}")
    hardware = next(iter(hardwares))
    tile_args, plans = [], []
    for row in tp.grid:
        tile_args.append(tuple(_tile_kernel_args(la, hardware) for la in row))
        plans.append(tuple((la.v_plan, la.u_plan) for la in row))
    tile_args, plans = tuple(tile_args), tuple(plans)
    grid, packed = kernel_ops.pack_tile_grid(tile_args, n=tp.tile,
                                             plans=plans, hardware=hardware)
    return CompiledTiledProgram(
        out_dim=tp.out_dim, in_dim=tp.in_dim, tile=tp.tile,
        to=tp.to, ti=tp.ti, plans=plans, tile_args=tile_args,
        hardware=hardware, grid=grid, packed=packed,
        block_b=block_b, interpret=interpret, placement=tp.placement,
        mesh=mesh, row_axis=row_axis, data_axis=data_axis, source=tp)


# ---------------------------------------------------------------------------
# Deep pipeline: a multi-layer cascade of tile grids on ONE megakernel
# ---------------------------------------------------------------------------

def _tile_kernel_args(la: ProgramLayer, hardware) -> dict:
    """The kernel argument dict of one programmed tile (shared by the
    network / tile-grid / deep-grid lowerings)."""
    args = {
        "v": la.device_params("v"),
        "u": la.device_params("u"),
        "atten": jnp.asarray(la.attenuation, jnp.float32),
        "scale": jnp.asarray(la.scale, jnp.float32),
    }
    if hardware is not None and la.key_v is not None:
        args["key_v"], args["key_u"] = la.key_v, la.key_u
    return args


def lower_deep(progs, *, block_b: int | None = None,
               interpret: bool | None = None, mesh=None,
               row_axis: str = "rows",
               data_axis: str = "data") -> CompiledDeepProgram:
    """Lower a cascade of programmed tile grids onto ONE deep megakernel.

    ``progs`` is a sequence of :class:`TiledAnalogProgram` — layer ``l``'s
    ``To`` tile rows feed layer ``l+1``'s ``Ti`` input tiles, so adjacent
    layers must chain (``prev.to == next.ti``, ``prev.out_dim ==
    next.in_dim``) and every tile shares one tile size and one hardware
    binding.  The result's ``apply`` is a single ``pallas_call`` per
    direction over the whole ``L x To x Ti`` cascade: combined row
    outputs are power-detected and re-injected into the next layer's
    tiles inside VMEM, which is exactly the physical cascade — the
    intermediate channels ride analog, with no digital truncation or
    masking between layers (compose per-layer ``lower_tiled`` programs
    if you need that).

    Placements fold into the single launch instead of costing per-layer
    digital gathers: the first layer's column permutation becomes the
    input gather, the last layer's row permutation the output gather,
    and every *interior* boundary is resolved at pack time by re-ordering
    the next layer's packed tile columns into the previous layer's
    physical row order (each tile keeps its own calibration draw — the
    re-order is a compile-time re-placement of interior columns, not a
    re-trim).
    """
    progs = tuple(progs)
    if not progs:
        raise ValueError("lower_deep needs at least one tiled layer program")
    tile = progs[0].tile
    for l, tp in enumerate(progs):
        if not tp.programmed:
            raise ValueError(f"lower_deep: layer {l} is not fully programmed "
                             "— run the `program_tiled` pass first")
        if tp.tile != tile:
            raise ValueError("all layers must share one tile size, got "
                             f"{[t.tile for t in progs]}")
    for l in range(len(progs) - 1):
        prev, nxt = progs[l], progs[l + 1]
        if prev.to != nxt.ti:
            raise ValueError(
                f"deep program does not chain: layer {l} emits To={prev.to} "
                f"tile rows but layer {l + 1} expects Ti={nxt.ti} input tiles")
        if prev.out_dim != nxt.in_dim:
            raise ValueError(
                f"deep program does not chain: layer {l} out_dim "
                f"{prev.out_dim} feeds layer {l + 1} in_dim {nxt.in_dim}")
    hardwares = {la.hardware for tp in progs for row in tp.grid for la in row}
    if len(hardwares) > 1:
        raise ValueError("all tiles must share one hardware binding, got "
                         f"{hardwares}")
    hardware = next(iter(hardwares))

    layer_args, layer_plans = [], []
    prev_rows = None  # logical tile row carried by incoming physical block j
    for l, tp in enumerate(progs):
        pl = tp.placement
        if l == 0:
            order = list(range(tp.ti))
        else:
            # incoming physical block j carries the previous layer's logical
            # row prev_rows[j]; the tile consuming that logical column sits
            # at this layer's physical column inv_col_perm[prev_rows[j]]
            src = prev_rows if prev_rows is not None else list(range(tp.ti))
            inv_col = (list(pl.inv_col_perm) if pl is not None
                       else list(range(tp.ti)))
            order = [inv_col[c] for c in src]
        grid_args, grid_plans = [], []
        for row in tp.grid:
            grid_args.append(tuple(
                _tile_kernel_args(row[j], hardware) for j in order))
            grid_plans.append(tuple(
                (row[j].v_plan, row[j].u_plan) for j in order))
        layer_args.append(tuple(grid_args))
        layer_plans.append(tuple(grid_plans))
        prev_rows = list(pl.row_perm) if pl is not None else None
    layer_args = tuple(layer_args)
    layer_plans = tuple(layer_plans)
    deep, packed = kernel_ops.pack_deep_grid(layer_args, n=tile,
                                             plans=layer_plans,
                                             hardware=hardware)
    return CompiledDeepProgram(
        out_dim=progs[-1].out_dim, in_dim=progs[0].in_dim, tile=tile,
        depth=len(progs), to=progs[-1].to, ti=progs[0].ti,
        plans=layer_plans, layer_args=layer_args, hardware=hardware,
        deep=deep, packed=packed, block_b=block_b, interpret=interpret,
        in_placement=progs[0].placement, out_placement=progs[-1].placement,
        mesh=mesh, row_axis=row_axis, data_axis=data_axis, sources=progs)
