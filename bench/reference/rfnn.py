"""Plain reference of the paper's MNIST RFNN (Sec. IV-B, Fig. 14).

    784 -> d    digital, leaky-ReLU (slope 0.01)
    d x d mesh  Clements layout, phases snapped to Table I with a
                straight-through gradient, hardware model with noise drawn
                from the step's key, output screen, |.| detection
    d -> 10     digital; softmax cross-entropy

The step's key splits into the mesh's draw and the detector's draw.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import physics

HIGHEST = jax.lax.Precision.HIGHEST


def logits(params, x, key, *, layout: physics.Layout, hw: physics.Hardware,
           codebook, slope: float, rnd=physics.f32):
    """``x[B, 784]`` -> class logits ``[B, 10]``."""
    p = {k: rnd(v) for k, v in params.items() if k != "mesh"}
    h = jnp.dot(rnd(x), p["w1"], precision=HIGHEST) + p["b1"]
    h = rnd(jnp.where(h >= 0, h, slope * h))
    m = {k: physics.ste(rnd(v), codebook) for k, v in params["mesh"].items()}
    kmesh, kdet = jax.random.split(key)
    v = physics.mesh(layout, m, h, hw, kmesh, rnd)
    h = physics.detect(v, hw, kdet, rnd)
    return rnd(jnp.dot(h, p["w3"], precision=HIGHEST) + p["b3"])


def nll(params, x, y, key, **kw):
    """Mean negative log-likelihood of the labels ``y``."""
    logp = jax.nn.log_softmax(logits(params, x, key, **kw))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
