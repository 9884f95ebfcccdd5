"""The benchmark's copied references agree with the program at a tiny
size (kernels in interpret mode) and with the program's own reference."""

import json

import jax
import jax.numpy as jnp
import numpy as np

import run
import tiny
from families import deepgrid, mnist_rfnn


def _cfg(name):
    return json.loads((run.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())


def test_deep_grid_reference_agrees_with_the_program():
    from repro.kernels import ref as program_ref

    cfg = dict(_cfg("deep64_l4_t16"), **tiny.DEEP)
    params, keys = deepgrid.weights(cfg, 2**33 + 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, cfg["dim"]))
    want = deepgrid.reference_forward(cfg)(params, keys, x)
    prog = deepgrid.serving_program(cfg, params, keys)
    np.testing.assert_allclose(prog.apply(x), want, rtol=1e-5, atol=1e-6)
    theirs = program_ref.deep_apply_ref(
        prog.layer_args, x, n=cfg["tile"], plans=prog.plans,
        hardware=prog.hardware)
    np.testing.assert_allclose(theirs, want, rtol=1e-5, atol=1e-6)


def test_rfnn_reference_agrees_with_the_program():
    cfg = _cfg("mnist_fig14_8x8")
    params = mnist_rfnn.weights(cfg, 7)
    x = jax.random.uniform(jax.random.PRNGKey(1), (10, cfg["d_in"]))
    y = jnp.arange(10) % cfg["n_classes"]
    key = jax.random.PRNGKey(2)
    model = mnist_rfnn.program_model(cfg)
    got = jax.value_and_grad(lambda p: model.loss(p, x, y, key)[0])(params)
    from reference import physics
    from reference import rfnn

    kw = {"layout": physics.clements(8), "hw": mnist_rfnn.hardware(cfg),
          "codebook": jnp.asarray(mnist_rfnn.codebook(cfg)),
          "slope": cfg["leaky_slope"]}
    want = jax.value_and_grad(
        lambda p: rfnn.nll(p, x, y, key, **kw))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)
