"""The deep tiled network as the benchmark runs it.

Weights come from the seed, in one jitted call on the device: every cell
phase, input screen and attenuation of every tile, the tiles' digital
scale, and each tile's two phase-noise keys.  Phases are Table-I values,
as the quantize pass leaves them.  The program under test gets them
through its own entry points: ``ops.deep_apply`` under ``make_sgd_step``
for training, and ``lower_deep`` of per-tile ``ProgramLayer``\\ s for
serving.  The reference (``reference/deepgrid.py``) gets the same
stacked arrays and the benchmark's own layout.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import deepgrid as ref
from reference import physics

PHASES = ("v_theta", "v_phi", "u_theta", "u_phi")
SCREENS = ("v_alpha_in", "u_alpha_in")


def layout(cfg: dict) -> physics.Layout:
    if cfg["layout"] == "reck":
        return physics.reck(cfg["tile"])[0]
    if cfg["layout"] == "clements":
        return physics.clements(cfg["tile"])
    raise ValueError(f"unknown mesh layout {cfg['layout']!r}")


def grid(cfg: dict) -> tuple[int, int, int]:
    """(L, To, Ti) of the square ``dim x dim`` layers."""
    t = cfg["dim"] // cfg["tile"]
    if t * cfg["tile"] != cfg["dim"]:
        raise ValueError("dim must be a multiple of tile")
    return cfg["depth"], t, t


def codebook(cfg: dict) -> np.ndarray:
    return np.deg2rad(np.asarray(cfg["codebook_deg"])).astype(np.float32)


def hardware(cfg: dict) -> physics.Hardware:
    return physics.Hardware.from_config(cfg["hardware"])


def jax_key(seed: int, salt: int):
    """A PRNG key for ``(seed, salt)``; any whole seed, however large."""
    rng = np.random.default_rng([seed, salt])
    return jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))


@functools.partial(jax.jit, static_argnames=("lead", "shape", "n", "scale",
                                             "atten"))
def _draw(key, active, book, *, lead, shape, n, scale, atten):
    ks = jax.random.split(key, 9)
    params = {}
    for k, name in zip(ks[:4], PHASES):
        codes = jax.random.randint(k, lead + shape, 0, book.shape[0])
        params[name] = jnp.where(active, book[codes], 0.0)
    for k, name in zip(ks[4:6], SCREENS):
        params[name] = book[jax.random.randint(k, lead + (n,), 0,
                                               book.shape[0])]
    params["atten"] = jax.random.uniform(ks[6], lead + (n,), jnp.float32,
                                         atten[0], atten[1])
    params["scale"] = jnp.full(lead, scale, jnp.float32)
    count = int(np.prod(lead))
    keys = {"key_v": jax.random.split(ks[7], count).reshape(lead + (2,)),
            "key_u": jax.random.split(ks[8], count).reshape(lead + (2,))}
    return params, keys


def weights(cfg: dict, seed: int):
    """Stacked ``[L, To, Ti, ...]`` trainable arrays and noise keys."""
    lay = layout(cfg)
    w = cfg["weights"]
    return _draw(jax_key(seed, 0), jnp.asarray(lay.active),
                 jnp.asarray(codebook(cfg)), lead=grid(cfg),
                 shape=lay.top.shape, n=cfg["tile"], scale=float(w["scale"]),
                 atten=(float(w["atten_low"]), float(w["atten_high"])))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_hardware(cfg: dict):
    from repro.core.hardware import HardwareModel

    h = cfg["hardware"]
    return HardwareModel(
        hybrid_imbalance=h["hybrid_imbalance"],
        hybrid_phase_err=np.deg2rad(h["hybrid_phase_err_deg"]),
        cell_loss_db=h["cell_loss_db"],
        phase_sigma=np.deg2rad(h["phase_sigma_deg"]),
        detector_floor_dbm=h["detector_floor_dbm"],
        detector_sigma=h["detector_sigma"])


def program_plan(cfg: dict):
    """The program's ``MeshPlan`` of the benchmark's layout."""
    from repro.core import mesh as mesh_lib

    lay = layout(cfg)
    if cfg["layout"] == "clements":
        plan = mesh_lib.clements_plan(cfg["tile"])
    else:
        _, where = physics.reck(cfg["tile"])
        cells = [(int(lay.top[c, s]), 0.0, 0.0) for c, s in where]
        plan, _, _ = mesh_lib.pack_cells_to_columns(
            cfg["tile"], cells, pad_to_columns=lay.top.shape[0])
    if not (np.array_equal(plan.top * plan.active, lay.top * lay.active)
            and np.array_equal(plan.active, lay.active)):
        raise AssertionError("the program's plan differs from the layout")
    return plan


def tile_args(params: dict, keys: dict | None = None):
    """Stacked arrays -> the program's nested ``[L][To][Ti]`` tile dicts."""
    lead = params["atten"].shape[:3]
    host = {k: np.asarray(v) for k, v in params.items()}
    kh = {k: np.asarray(v) for k, v in (keys or {}).items()}

    def one(l, o, i):
        idx = (l, o, i)
        d = {"v": {"theta": host["v_theta"][idx], "phi": host["v_phi"][idx],
                   "alpha_in": host["v_alpha_in"][idx]},
             "u": {"theta": host["u_theta"][idx], "phi": host["u_phi"][idx],
                   "alpha_in": host["u_alpha_in"][idx]},
             "atten": host["atten"][idx], "scale": host["scale"][idx]}
        d.update({k: v[idx] for k, v in kh.items()})
        return d

    nested = tuple(tuple(tuple(one(l, o, i) for i in range(lead[2]))
                         for o in range(lead[1])) for l in range(lead[0]))
    return jax.device_put(nested)


def stack_args(nested) -> dict:
    """The program's nested tile dicts -> stacked host arrays."""
    def get(ta, name):
        if name in ("atten", "scale"):
            return ta[name]
        return ta[name[0]][name[2:]]

    names = PHASES + SCREENS + ("atten", "scale")
    return {name: np.stack([np.stack([np.stack(
        [np.asarray(get(ta, name)) for ta in row]) for row in layer])
        for layer in nested]) for name in names}


def plans(cfg: dict):
    plan = program_plan(cfg)
    n_layers, to, ti = grid(cfg)
    return tuple(tuple(tuple((plan, plan) for _ in range(ti))
                       for _ in range(to)) for _ in range(n_layers))


def serving_program(cfg: dict, params: dict, keys: dict):
    """``lower_deep`` of the seeded weights, as the compile pipeline
    would hand a quantized, hardware-bound grid to it."""
    from repro import compile as comp
    from repro.compile.program import ProgramLayer, TiledAnalogProgram

    hw = program_hardware(cfg)
    plan = program_plan(cfg)
    book = jnp.asarray(codebook(cfg))
    nested = tile_args(params, keys)
    n = cfg["tile"]
    eye = np.eye(n)
    progs = []
    for layer in nested:
        rows = tuple(tuple(ProgramLayer(
            n=n, out_dim=n, in_dim=n, target=np.zeros((n, n)),
            target_u=eye, target_vh=eye, attenuation=ta["atten"],
            scale=ta["scale"], v_plan=plan, v_params=ta["v"], u_plan=plan,
            u_params=ta["u"], codebook=book, quant_mode="nearest",
            hardware=hw, key_v=ta["key_v"], key_u=ta["key_u"])
            for ta in row) for row in layer)
        progs.append(TiledAnalogProgram(out_dim=n * len(rows),
                                        in_dim=n * len(rows[0]), tile=n,
                                        grid=rows))
    return comp.lower_deep(progs)


def sgd_step(cfg: dict, lr: float):
    """The jitted training step ``(params, x, t, keys) -> (params, (loss,
    _))`` on the program's nested tile dicts, through ``ops.deep_apply``.
    The noise keys are an argument, so that one compiled step serves
    every seed."""
    from repro.kernels import ops
    from repro.train.step import make_sgd_step

    hw = program_hardware(cfg)
    pl = plans(cfg)

    def loss(p, x, t, keys):
        merged = tuple(tuple(tuple({**ta, **kt} for ta, kt in zip(pr, kr))
                             for pr, kr in zip(pg, kg))
                       for pg, kg in zip(p, keys))
        y = ops.deep_apply(merged, x, n=cfg["tile"], plans=pl, hardware=hw)
        value = jnp.mean((y - t) ** 2)
        return value, value

    return jax.jit(make_sgd_step(loss, lr=lr))


def _key_tree(keys: dict):
    kv, ku = np.asarray(keys["key_v"]), np.asarray(keys["key_u"])
    lead = kv.shape[:3]
    return jax.device_put(tuple(tuple(tuple(
        {"key_v": kv[l, o, i], "key_u": ku[l, o, i]} for i in range(lead[2]))
        for o in range(lead[1])) for l in range(lead[0])))


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------

def reference_forward(cfg: dict, rnd=physics.f32):
    """Jitted ``(params, keys, x) -> y`` of the plain reference."""
    kw = {"layout": layout(cfg), "hw": hardware(cfg), "rnd": rnd}
    return jax.jit(functools.partial(ref.forward, **kw))


def reference_step(cfg: dict, lr: float, rnd=physics.f32):
    """Jitted ``(params, keys, x, t) -> (params, loss)``: one SGD step of
    the reference's autodiff."""
    kw = {"layout": layout(cfg), "hw": hardware(cfg), "rnd": rnd}

    def step(params, keys, x, t):
        value, grads = jax.value_and_grad(ref.mse)(params, keys, x, t, **kw)
        return jax.tree.map(lambda w, g: w - lr * g, params, grads), value

    return jax.jit(step)


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

def dims(cfg: dict, batch: int) -> dict:
    """What the benchmark's counts need of one call at ``batch`` rows."""
    n_layers, to, ti = grid(cfg)
    cells = layout(cfg).n_cells
    return {"layers": n_layers, "to": to, "ti": ti, "n": cfg["tile"],
            "cells_v": cells, "cells_u": cells, "batch": batch}


@functools.partial(jax.jit, static_argnames=("shape", "rms"))
def _feed(key, *, shape, rms):
    kx, kt = jax.random.split(key)
    return (jax.random.normal(kx, shape, jnp.float32),
            rms * jnp.abs(jax.random.normal(kt, shape, jnp.float32)))


class Trainer:
    """Set-up builds the jitted SGD step and its state, drives it through
    the first ``EARLY`` steps on distinct batches, and keeps the states
    the check needs; ``dispatch`` runs one more step of the same object."""

    EARLY = 3

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.lr = float(traffic["lr"])
        self.batch = int(traffic["batch"])
        self.samples_per_dispatch = self.batch
        params, self.keys = weights(cfg, seed)
        self.p0 = jax.tree.map(np.asarray, params)
        pool = int(traffic["feed_batches"])
        xs, ts = _feed(jax_key(seed, 1),
                       shape=(pool, self.batch, cfg["dim"]),
                       rms=float(traffic["target_rms"]))
        self.xs, self.ts = list(xs), list(ts)
        self.step = sgd_step(cfg, self.lr)
        self.key_tree = _key_tree(self.keys)
        self.params = tile_args(params)
        self.i = 0
        self.early = []
        for _ in range(self.EARLY):
            loss = self.dispatch()
            self.early.append((float(loss), stack_args(self.params)))
        self.work = {"deepgrid_fwd_kernel": dims(cfg, self.batch),
                     "deepgrid_bwd_kernel": dims(cfg, self.batch)}
        self.model = ("deepgrid_train", dims(cfg, 1))

    def dispatch(self):
        k = self.i % len(self.xs)
        self.params, (loss, _) = self.step(self.params, self.xs[k],
                                           self.ts[k], self.key_tree)
        self.i += 1
        return loss

    def _leaves(self, stacked: dict) -> list:
        names = PHASES + SCREENS + ("atten", "scale")
        return [stacked[k][idx] for k in names
                for idx in np.ndindex(stacked["atten"].shape[:3])]

    def _reference(self, rnd, rows=None):
        step = reference_step(self.cfg, self.lr, rnd)
        p = jax.tree.map(jnp.asarray, self.p0)
        out = []
        for k in range(self.EARLY):
            p, loss = step(p, self.keys, self.xs[k][:rows],
                           self.ts[k][:rows])
            out.append((float(loss), jax.tree.map(np.asarray, p)))
        return out

    def _gaps(self, other) -> dict:
        from check import train_gaps

        def side(run):
            return (self._leaves(run[0][1]), self._leaves(run[-1][1]),
                    [loss for loss, _ in run])

        return train_gaps(self._leaves(self.p0), side(other),
                          side(self._reference(physics.f32)), self.lr)

    def readings(self) -> dict:
        """The program's first steps against the reference's."""
        return self._gaps(self.early)

    def control_readings(self) -> dict:
        """The reference in bfloat16, put in the program's place."""
        return self._gaps(self._reference(physics.bf16))

    def half_batch_readings(self) -> dict:
        """The reference with half of each batch left out, the mean taken
        over the rest, put in the program's place."""
        return self._gaps(self._reference(physics.f32, self.batch // 2))


class Server:
    """The served program and its inputs: ``lower_deep`` of the seeded
    weights, feature rows drawn from the seed, and the reference's
    outputs for any of them."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.params, self.keys = weights(cfg, seed)
        jax.block_until_ready(self.params)
        t0 = time.perf_counter()
        self.program = serving_program(cfg, self.params, self.keys)
        jax.block_until_ready(self.program.packed)
        self.build_s = time.perf_counter() - t0
        rng = np.random.default_rng([seed, 3])
        self.rows = rng.standard_normal(
            (int(traffic["feature_rows"]), cfg["dim"])).astype(np.float32)
        self.work = {"deepgrid_kernel": dims(cfg, int(traffic["slots"]))}
        self.model = ("deepgrid_serve", dims(cfg, 1))

    def features(self, rid: int) -> np.ndarray:
        return self.rows[rid % len(self.rows)]

    def reference(self, rids, rnd=physics.f32, block: int = 4096):
        fwd = reference_forward(self.cfg, rnd)
        out = []
        for s in range(0, len(rids), block):
            x = self.rows[np.asarray(rids[s:s + block]) % len(self.rows)]
            out.append(np.asarray(fwd(self.params, self.keys, x)))
        return np.concatenate(out)
