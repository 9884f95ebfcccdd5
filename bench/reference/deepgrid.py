"""Plain reference of the deep tiled network (the paper's Sec.-V scale-up).

Layer ``l`` holds a ``To x Ti`` grid of ``n x n`` tiles.  Tile ``(o, i)``
takes input block ``i``, passes it through its V mesh, the attenuation
diagonal, its U mesh and the digital scale; row ``o`` sums its tiles'
outputs, and ``|.|`` detects every row before the next layer and after
the last.  Every tile shares one mesh layout; the weights are stacked
``[L, To, Ti, ...]`` arrays (see ``bench/families/deepgrid.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import physics


def forward(params: dict, keys: dict, x, *, layout: physics.Layout,
            hw: physics.Hardware, rnd=physics.f32):
    """``x[B, Ti*n]`` (real) -> detected magnitudes ``[B, To*n]``."""
    n = layout.n
    n_layers = params["atten"].shape[0]
    h = rnd(jnp.asarray(x, jnp.float32))

    def tile(p, kv, ku, xi):
        v = physics.mesh(layout, {"theta": p["v_theta"], "phi": p["v_phi"],
                                  "alpha_in": p["v_alpha_in"]},
                         xi, hw, kv, rnd)
        v = rnd(rnd(p["atten"]).astype(jnp.complex64) * v)
        u = physics.mesh(layout, {"theta": p["u_theta"], "phi": p["u_phi"],
                                  "alpha_in": p["u_alpha_in"]},
                         v, hw, ku, rnd)
        return rnd(rnd(p["scale"]).astype(jnp.complex64) * u)

    over_inputs = jax.vmap(tile, in_axes=(0, 0, 0, 0))
    over_grid = jax.vmap(over_inputs, in_axes=(0, 0, 0, None))
    for l in range(n_layers):
        p = {k: v[l] for k, v in params.items()}
        ti = p["atten"].shape[1]
        xin = jnp.moveaxis(h.reshape(h.shape[0], ti, n), 1, 0)  # [Ti, B, n]
        z = over_grid(p, keys["key_v"][l], keys["key_u"][l], xin)
        rows = rnd(jnp.sum(z, axis=1))                          # [To, B, n]
        h = rnd(jnp.abs(jnp.moveaxis(rows, 0, 1).reshape(h.shape[0], -1)))
    return h


def mse(params, keys, x, target, **kw):
    """The training cell's loss: mean squared error against ``target``."""
    return jnp.mean((forward(params, keys, x, **kw) - target) ** 2)
