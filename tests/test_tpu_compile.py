"""The main path's kernels compile for a TPU v5e (no chip needed).

Interpret mode never checks what the chip's compiler refuses: the (8, 128)
VMEM tiling, scoped-VMEM limits, primitives Mosaic cannot lower.  These
tests compile the jitted training and inference programs of the main
path for a v5e described by ``jax.experimental.topologies`` with
``interpret=False`` at real widths — the README's 4-layer 64x64 deep
grid of 16x16 tiles, and the paper's 8x8 MNIST mesh — and check that the
Pallas kernels (``tpu_custom_call``) are in the compiled program.  Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a module fixture (only one process may
hold the TPU library, so never at import time), and the kernels are
steered off the CPU's interpreter by patching ``ops.default_interpret``
in the test.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mesh as mesh_lib
from repro.kernels import ops
from repro.paper.mnist_rfnn import MnistRFNN
from repro.paper.prototype import PROTOTYPE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Route every ``interpret=None`` kernel call to the chip compiler."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _deep_layers(n=16, depth=4, g=4):
    plan = mesh_lib.clements_plan(n)
    return tuple(tuple(tuple(
        {"v": mesh_lib.init_mesh_params(jax.random.PRNGKey(0), plan),
         "u": mesh_lib.init_mesh_params(jax.random.PRNGKey(1), plan),
         "atten": jnp.full((n,), 0.5), "scale": jnp.float32(1.0)}
        for _ in range(g)) for _ in range(g)) for _ in range(depth))


@pytest.mark.parametrize("batch", [64, 256, 1024])
@pytest.mark.parametrize("kind", ["inference", "vjp"])
def test_deep_grid_kernels_compile_for_v5e(one_chip, compiled_kernels,
                                           batch, kind):
    """64x64, L=4, 16x16 tiles at the block ``deep_apply`` picks for the
    batch: the inference kernel, and the VJP's forward and backward.  64
    rows is the serving panel, run whole in one grid step."""
    layers = _shapes(_deep_layers(), one_chip)
    x = jax.ShapeDtypeStruct((batch, 64), jnp.float32, sharding=one_chip)

    def apply(ls, xx):
        return ops.deep_apply(ls, xx, n=16)

    if kind == "inference":
        assert _n_kernels(_compile(apply, layers, x)) >= 1
    else:
        grad = jax.grad(lambda ls, xx: jnp.sum(apply(ls, xx) ** 2))
        assert _n_kernels(_compile(grad, layers, x)) >= 2


@pytest.mark.parametrize("hardware", [None, PROTOTYPE],
                         ids=["ideal", "prototype"])
def test_mnist_train_step_compiles_for_v5e(one_chip, compiled_kernels,
                                           hardware):
    """The paper's 784-8-(8x8 mesh)-10 RFNN: one SGD step of batch 10
    runs ``mesh_apply``'s forward and backward kernels at n=8."""
    from repro.train.step import make_sgd_step

    model = MnistRFNN(analog=True, hardware=hardware, backend="pallas")
    params = _shapes(model.init(jax.random.PRNGKey(0)), one_chip)
    x = jax.ShapeDtypeStruct((10, 784), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((10,), jnp.int32, sharding=one_chip)
    step = make_sgd_step(lambda p, xi, yi: model.loss(p, xi, yi), lr=0.005)
    assert _n_kernels(_compile(step, params, x, y)) >= 2


def test_rfnn_linear_kernels_compile_for_v5e(one_chip, compiled_kernels):
    """The fused analog linear layer at n=8: forward and backward."""
    plan = mesh_lib.clements_plan(8)
    v = mesh_lib.init_mesh_params(jax.random.PRNGKey(0), plan)
    u = mesh_lib.init_mesh_params(jax.random.PRNGKey(1), plan)
    args = _shapes((v, jnp.full((8,), 0.5), u), one_chip)
    x = jax.ShapeDtypeStruct((128, 8), jnp.float32, sharding=one_chip)

    def apply(vp, atten, up, xx):
        return ops.rfnn_linear(vp, atten, up, xx, n=8)

    def loss(*a):
        return jnp.sum(apply(*a) ** 2)

    assert _n_kernels(_compile(apply, *args, x)) >= 1
    assert _n_kernels(_compile(jax.grad(loss, argnums=(0, 1, 2)),
                               *args, x)) >= 2
