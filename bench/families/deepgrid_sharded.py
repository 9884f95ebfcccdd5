"""The deep tiled network trained over one four-chip host.

The same network, weights, feed and reference as ``families/deepgrid.py``
(whose functions this reuses), at a width whose grid no single chip
holds: each layer's tile rows split over the configuration's ``rows``
chips and the batch over its ``data`` chips.  The program trains through
its normal path, ``make_sgd_step`` around ``ops.deep_apply(mesh=...)``,
with the tiles' parameters entering and leaving the step as the stacked
``[L, To, Ti, ...]`` arrays, split on tile rows.

The reference (``reference/deepgrid.py``, unchanged) cannot take the
whole batch at this width at once: each of its steps runs the batch in
blocks, each block's gradient with the layers rematerialised one at a
time, the blocks' losses and gradients summed, and the batch split over
the host's chips.

The step trains offsets ``d`` from the starting parameters ``p0`` (the
loss is taken at ``p0 + d``, from ``d = 0``).  At this width a step of
lr 0.5 moves a phase by a few float32 spacings, which a float32
parameter near 1 rounds away; an offset from zero keeps each update to
float32's relative precision.  So the check sees the timed step's own
gradient: its first offset is ``-lr`` times the gradient, and its third
the change of three steps.  The reference takes its steps the same way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from families import deepgrid as base
from reference import deepgrid as ref
from reference import physics

#: batch rows of one reference block on one chip: its states, kept
#: across one layer's columns for the gradient, stay within a few GB
REFERENCE_BLOCK = 32


def device_mesh(cfg: dict) -> Mesh:
    """The ``(rows, data)`` mesh the configuration states, over the first
    chips of the host."""
    shape = (int(cfg["mesh"]["rows"]), int(cfg["mesh"]["data"]))
    devices = jax.devices()[: shape[0] * shape[1]]
    if len(devices) < shape[0] * shape[1]:
        raise RuntimeError(f"the {shape[0]}x{shape[1]} mesh needs "
                           f"{shape[0] * shape[1]} devices, found "
                           f"{len(devices)}")
    return Mesh(np.array(devices).reshape(shape), ("rows", "data"))


def sgd_step(cfg: dict, lr: float, mesh: Mesh):
    """The jitted training step ``(d, p0, x, t, keys) -> (d - lr * g(p0 +
    d), (loss, _))`` on offsets ``d`` from the stacked arrays ``p0``,
    through ``ops.deep_apply(mesh=...)``.  Parameters, offsets and keys
    are split on tile rows, the batch on ``data``, and the step hands
    the offsets back split as they came."""
    from repro.kernels import ops
    from repro.train.step import make_sgd_step

    hw = base.program_hardware(cfg)
    plans = base.plans(cfg)

    def loss(d, p0, x, t, keys):
        p = jax.tree.map(jnp.add, p0, d)
        y = ops.deep_apply({**p, **keys}, x, n=cfg["tile"], plans=plans,
                           hardware=hw, mesh=mesh)
        value = jnp.mean((y - t) ** 2)
        return value, value

    tiles, batch = shardings(mesh)
    return jax.jit(make_sgd_step(loss, lr=lr),
                   in_shardings=(tiles, tiles, batch, batch, tiles),
                   out_shardings=(tiles, NamedSharding(mesh, P())))


def shardings(mesh: Mesh):
    """(stacked tile arrays split on tile rows, batch rows split on
    ``data``)."""
    return (NamedSharding(mesh, P(None, "rows")),
            NamedSharding(mesh, P("data")))


def reference_step(cfg: dict, lr: float, rnd=physics.f32,
                   block: int = REFERENCE_BLOCK):
    """Jitted ``(d, p0, keys, x, t) -> (d, loss)``: one SGD step of the
    reference's autodiff on the mean squared error over the whole batch,
    taken on offsets ``d`` from the parameters ``p0``.  The batch splits
    over every chip of the host; each chip runs its rows in blocks of at
    most ``block``, each block's gradient rematerialising one layer at a
    time, and the sums of the blocks' squared errors and gradients are
    added over blocks and chips before the mean."""
    kw = {"layout": base.layout(cfg), "hw": base.hardware(cfg), "rnd": rnd}
    n_layers = int(cfg["depth"])
    layer = jax.checkpoint(functools.partial(ref.forward, **kw))
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("chips",))

    def forward(params, keys, x):
        # ref.forward one layer at a time: its first step rounds the
        # input, which every later layer's input already is
        h = x
        for l in range(n_layers):
            h = layer({k: v[l:l + 1] for k, v in params.items()},
                      {k: v[l:l + 1] for k, v in keys.items()}, h)
        return h

    def block_sse(params, keys, x, t):
        return jnp.sum((forward(params, keys, x) - t) ** 2)

    def local(params, keys, x, t):
        rows = max(r for r in range(1, min(block, x.shape[0]) + 1)
                   if not x.shape[0] % r)
        xs = x.reshape((-1, rows) + x.shape[1:])
        ts = t.reshape((-1, rows) + t.shape[1:])

        def one(acc, xt):
            sse, grads = jax.value_and_grad(block_sse)(params, keys, *xt)
            return jax.tree.map(jnp.add, acc, (sse, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        acc, _ = jax.lax.scan(one, zero, (xs, ts))
        return jax.lax.psum(acc, "chips")

    summed = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(), P(), P("chips"), P("chips")),
                           out_specs=P(), check_vma=False)

    def step(d, p0, keys, x, t):
        sse, grads = summed(jax.tree.map(jnp.add, p0, d), keys, x, t)
        count = x.shape[0] * t.shape[-1]
        new = jax.tree.map(lambda w, g: w - lr * g / count, d, grads)
        return new, sse / count

    return jax.jit(step)


def dims(cfg: dict, batch: int, mesh: Mesh) -> dict:
    """The counts of one chip's single-layer kernel call: one layer, the
    chip's tile rows and its batch rows."""
    d = base.dims(cfg, batch // mesh.shape["data"])
    return dict(d, layers=1, to=d["to"] // mesh.shape["rows"])


class Trainer(base.Trainer):
    """Set-up builds the mesh, the jitted SGD step over the stacked
    arrays and its state (offsets from the seeded parameters, at zero),
    and drives it through the first ``EARLY`` steps on distinct batches;
    ``dispatch`` runs one more step."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.lr = float(traffic["lr"])
        self.batch = int(traffic["batch"])
        self.samples_per_dispatch = self.batch
        self.mesh = device_mesh(cfg)
        tiles, batch = shardings(self.mesh)
        params, keys = base.weights(cfg, seed)
        self.keys = jax.tree.map(np.asarray, keys)
        self.p0 = jax.tree.map(np.asarray, params)
        pool = int(traffic["feed_batches"])
        xs, ts = base._feed(base.jax_key(seed, 1),
                            shape=(pool, self.batch, cfg["dim"]),
                            rms=float(traffic["target_rms"]))
        self.xs = [jax.device_put(x, batch) for x in xs]
        self.ts = [jax.device_put(t, batch) for t in ts]
        del xs, ts
        self.step = sgd_step(cfg, self.lr, self.mesh)
        self.key_tree = jax.device_put(keys, tiles)
        self.base = jax.device_put(params, tiles)
        self.params = jax.device_put(jax.tree.map(np.zeros_like, self.p0),
                                     tiles)
        del params, keys
        self.i = 0
        self.early = []
        for _ in range(self.EARLY):
            loss = self.dispatch()
            self.early.append((float(loss),
                               jax.tree.map(np.asarray, self.params)))
        self._refs: dict = {}
        call = dims(cfg, self.batch, self.mesh)
        self.work = {"deepgrid_rows_fwd_kernel": call,
                     "deepgrid_rows_bwd_kernel": call}
        self.model = ("deepgrid_train", base.dims(cfg, 1))

    def dispatch(self):
        k = self.i % len(self.xs)
        self.params, (loss, _) = self.step(self.params, self.base,
                                           self.xs[k], self.ts[k],
                                           self.key_tree)
        self.i += 1
        return loss

    def _reference(self, rnd, rows=None):
        """The reference's first steps on offsets, ``[(loss, offsets)]``;
        the float32 run on the whole batch is computed once and kept."""
        memo = rnd is physics.f32 and rows is None
        if memo and "f32" in self._refs:
            return self._refs["f32"]
        step = reference_step(self.cfg, self.lr, rnd)
        p0 = jax.tree.map(jnp.asarray, self.p0)
        keys = jax.tree.map(jnp.asarray, self.keys)
        d = jax.tree.map(jnp.zeros_like, p0)
        out = []
        for k in range(self.EARLY):
            d, loss = step(d, p0, keys, self.xs[k][:rows], self.ts[k][:rows])
            out.append((float(loss), jax.tree.map(np.asarray, d)))
        if memo:
            self._refs["f32"] = out
        return out

    def _gaps(self, other) -> dict:
        """``check.train_gaps`` on offsets, which start at zero: the first
        is ``-lr`` times the gradient, the third the three steps'
        change."""
        from check import train_gaps

        def side(run):
            return (self._leaves(run[0][1]), self._leaves(run[-1][1]),
                    [loss for loss, _ in run])

        zero = [np.zeros_like(a) for a in self._leaves(self.p0)]
        return train_gaps(zero, side(other),
                          side(self._reference(physics.f32)), self.lr)
