"""The paper's MNIST RFNN (Sec. IV-B, Fig. 14) as the benchmark trains it.

Weights come from the seed in one jitted call, in the shapes and
distributions of the model's own initialisation.  The program under test
is ``MnistRFNN(backend="pallas")``: its ``loss`` under ``make_sgd_step``,
scanned over one epoch of minibatches per dispatch, with every step's
noise key split from the epoch's key, as the repository's training loop
does.  The reference (``reference/rfnn.py``) gets the same weights,
batches and keys.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from families import digits
from families.deepgrid import jax_key
from reference import physics
from reference import rfnn as ref


def hardware(cfg: dict) -> physics.Hardware:
    return physics.Hardware.from_config(cfg["hardware"])


def codebook(cfg: dict) -> np.ndarray:
    return np.deg2rad(np.asarray(cfg["codebook_deg"])).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("d_in", "d", "classes", "c"))
def _draw(key, *, d_in, d, classes, c):
    k1, k2, k3 = jax.random.split(key, 3)
    ka, kb, kc = jax.random.split(k2, 3)
    return {
        "w1": jax.random.normal(k1, (d_in, d)) * 0.05,
        "b1": jnp.zeros((d,)),
        "w3": jax.random.normal(k3, (d, classes)) * 0.3,
        "b3": jnp.zeros((classes,)),
        "mesh": {
            "theta": jax.random.uniform(ka, (c, d // 2), jnp.float32, 0.0,
                                        np.pi),
            "phi": jax.random.uniform(kb, (c, d // 2), jnp.float32, 0.0,
                                      2 * np.pi),
            "alpha": jax.random.uniform(kc, (d,), jnp.float32, 0.0,
                                        2 * np.pi),
        },
    }


def weights(cfg: dict, seed: int) -> dict:
    return _draw(jax_key(seed, 0), d_in=cfg["d_in"], d=cfg["d_hidden"],
                 classes=cfg["n_classes"], c=cfg["d_hidden"])


def data_pool(cfg: dict, seed: int):
    return digits.pool(cfg["train_pool"], seed)


def epoch_batches(pool_x, pool_y, cfg: dict, b: int, seed: int, epoch: int):
    """One epoch's minibatches of ``b``: ``epoch_samples`` drawn as whole
    seeded permutations of the pool, on the host, as ``[steps, b, ...]``."""
    n = len(pool_y)
    rng = np.random.default_rng([seed, 1, epoch])
    reps = -(-cfg["epoch_samples"] // n)
    idx = np.concatenate([rng.permutation(n) for _ in range(reps)])
    idx = idx[: cfg["epoch_samples"] // b * b]
    return (pool_x[idx].reshape(-1, b, pool_x.shape[1]),
            pool_y[idx].reshape(-1, b))


def epoch_key(seed: int, epoch: int):
    return jax.random.fold_in(jax_key(seed, 2), epoch)


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_model(cfg: dict):
    from repro.core.hardware import HardwareModel
    from repro.paper.mnist_rfnn import MnistRFNN

    h = cfg["hardware"]
    hw = HardwareModel(
        hybrid_imbalance=h["hybrid_imbalance"],
        hybrid_phase_err=np.deg2rad(h["hybrid_phase_err_deg"]),
        cell_loss_db=h["cell_loss_db"],
        phase_sigma=np.deg2rad(h["phase_sigma_deg"]),
        detector_floor_dbm=h["detector_floor_dbm"],
        detector_sigma=h["detector_sigma"])
    return MnistRFNN(analog=True, hardware=hw, quantize="table1",
                     d_hidden=cfg["d_hidden"], n_classes=cfg["n_classes"],
                     backend="pallas")


def epoch_fn(cfg: dict, lr: float, early: int = 3):
    """The jitted epoch ``(params, xb, yb, key) -> (params, losses[:early],
    params after step 1, params after step ``early``)``: a ``lax.scan``
    of the program's SGD step, one noise key per step."""
    from repro.train.step import make_sgd_step

    model = program_model(cfg)
    sgd = make_sgd_step(lambda p, x, y, k: model.loss(p, x, y, k), lr=lr)

    def run(params, xb, yb, key):
        keys = jax.random.split(key, xb.shape[0])

        def body(carry, inp):
            p, s1, sk, i = carry
            p, (loss, _) = sgd(p, *inp)
            s1 = jax.tree.map(lambda a, b: jnp.where(i == 0, a, b), p, s1)
            sk = jax.tree.map(lambda a, b: jnp.where(i == early - 1, a, b),
                              p, sk)
            return (p, s1, sk, i + 1), loss

        (params, s1, sk, _), losses = jax.lax.scan(
            body, (params, params, params, 0), (xb, yb, keys))
        return params, losses[:early], s1, sk

    return jax.jit(run)


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------

def reference_step(cfg: dict, lr: float, rnd=physics.f32):
    """Jitted ``(params, x, y, key) -> (params, loss)`` of the reference."""
    kw = {"layout": physics.clements(cfg["d_hidden"]), "hw": hardware(cfg),
          "codebook": jnp.asarray(codebook(cfg)),
          "slope": float(cfg["leaky_slope"]), "rnd": rnd}

    def step(params, x, y, key):
        value, grads = jax.value_and_grad(ref.nll)(params, x, y, key, **kw)
        return jax.tree.map(lambda w, g: w - lr * g, params, grads), value

    return jax.jit(step)


def step_keys(seed: int, steps: int, n: int):
    """The first ``n`` step keys of epoch 0, split as the epoch splits."""
    return jax.random.split(epoch_key(seed, 0), steps)[:n]


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

def dims(cfg: dict, batch: int) -> dict:
    """What the benchmark's counts need of one step at ``batch`` rows."""
    lay = physics.clements(cfg["d_hidden"])
    return {"d_in": cfg["d_in"], "d": cfg["d_hidden"],
            "classes": cfg["n_classes"], "n": cfg["d_hidden"],
            "columns": lay.top.shape[0], "cells": lay.n_cells,
            "batch": batch}


class Trainer:
    """Set-up renders the digit pool, draws the weights, and runs the
    first epoch through the jitted epoch (compiling it); that epoch's
    first ``EARLY`` steps are the ones the check compares.  Each
    ``dispatch`` shuffles and transfers one more epoch on the host and
    runs it."""

    EARLY = 3

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.lr = float(traffic["lr"])
        self.batch = int(traffic["batch"])
        self.pool = data_pool(cfg, seed)
        params = weights(cfg, seed)
        self.p0 = jax.tree.map(np.asarray, params)
        self.fn = epoch_fn(cfg, self.lr, self.EARLY)
        self.epoch = 0
        xb, yb = epoch_batches(*self.pool, cfg, self.batch, seed, 0)
        self.first = (xb[: self.EARLY], yb[: self.EARLY])
        self.samples_per_dispatch = xb.shape[0] * xb.shape[1]
        self.params = params
        losses, s1, sk = self._run(xb, yb)
        self.early = ([float(v) for v in np.asarray(losses)],
                      jax.tree.map(np.asarray, s1),
                      jax.tree.map(np.asarray, sk))
        self.work = {"mesh_kernel": dims(cfg, self.batch),
                     "mesh_bwd_kernel": dims(cfg, self.batch)}
        self.model = ("rfnn_train", dims(cfg, 1))

    def _run(self, xb, yb):
        self.params, losses, s1, sk = self.fn(
            self.params, jnp.asarray(xb), jnp.asarray(yb),
            epoch_key(self.seed, self.epoch))
        self.epoch += 1
        return losses, s1, sk

    def dispatch(self):
        xb, yb = epoch_batches(*self.pool, self.cfg, self.batch, self.seed,
                               self.epoch)
        return self._run(xb, yb)[0]

    def _reference(self, rnd, rows=None):
        step = reference_step(self.cfg, self.lr, rnd)
        keys = step_keys(self.seed, self.samples_per_dispatch // self.batch,
                         self.EARLY)
        p = jax.tree.map(jnp.asarray, self.p0)
        losses, states = [], []
        for k in range(self.EARLY):
            p, loss = step(p, jnp.asarray(self.first[0][k][:rows]),
                           jnp.asarray(self.first[1][k][:rows]), keys[k])
            losses.append(float(loss))
            states.append(jax.tree.map(np.asarray, p))
        return losses, states[0], states[-1]

    def _gaps(self, other) -> dict:
        from check import train_gaps

        def side(run):
            losses, s1, sk = run
            return jax.tree.leaves(s1), jax.tree.leaves(sk), losses

        return train_gaps(jax.tree.leaves(self.p0), side(other),
                          side(self._reference(physics.f32)), self.lr)

    def readings(self) -> dict:
        return self._gaps(self.early)

    def control_readings(self) -> dict:
        return self._gaps(self._reference(physics.bf16))

    def half_batch_readings(self) -> dict:
        return self._gaps(self._reference(physics.f32, self.batch // 2))
