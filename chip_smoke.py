#!/usr/bin/env python3
"""Drive the RFNN main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]           # one chip: deep + mnist phases
    python chip_smoke.py --chips 4 [--seed N] # the four-chip paths only

One process; nothing here starts a child that touches JAX.

* ``deep`` -- the README's 4-layer 64x64 deep tiled network (a 4x4 grid of
  16x16 tiles per layer), compiled from weights drawn from ``--seed``
  through ``synthesize_tiled -> program_tiled -> quantize_tiled("table1")
  -> calibrate_tiled(PROTOTYPE) -> lower_deep``.  256 requests are served
  through ``ServingEngine(compiled, slots=64)`` and its dispatch thread and
  checked against the plain-jnp evaluation of the same program
  (``repro.kernels.ref.deep_apply_ref``).  Then 3 SGD steps of
  ``jax.grad`` through ``ops.deep_apply`` at B=1024: the loss must fall and
  the first step's gradients must match the reference's autodiff.
* ``mnist`` -- one epoch of the paper's 784-8-(8x8 mesh)-10 RFNN on the
  kernel path (``backend="pallas"``, PROTOTYPE hardware, STE phases) over
  digits rendered from the seed; one batch's logits are checked against
  ``backend="reference"`` with the same params and noise key.
* ``--chips 4`` -- the deep grid sharded over a 2x2 ("rows", "data") mesh,
  forward and VJP against the same call on one chip, and one data-parallel
  SGD step against the serial step.

Every phase prints one JSON line.  The last line,
``{"ok": true, "device": {...}}``, is printed only if every check held;
the script exits non-zero without it when JAX finds no TPU, when a check
fails, or when a phase raises.  The reference runs eagerly, one cached
program per mesh plan (a jit over all 128 meshes of the network takes the
chip's compiler minutes), at float32 matmul precision ("highest"); the
kernels themselves use no matmul.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

REL_TOL = 1e-4          # every kernel-vs-reference comparison
DIM, TILE, DEPTH = 64, 16, 4
N_REQUESTS, SLOTS = 256, 64
TRAIN_BATCH, SGD_STEPS, SGD_LR = 1024, 3, 0.5
WAIT_S = 600.0          # per-request wait bound (first tick compiles)


def _report(**fields) -> dict:
    print(json.dumps(fields), flush=True)
    return fields


def rel_err(got, want) -> float:
    """max |got - want| over every leaf, relative to max |want|."""
    gs = [np.asarray(g) for g in jax.tree.leaves(got)]
    ws = [np.asarray(w) for w in jax.tree.leaves(want)]
    scale = max(float(np.max(np.abs(w))) for w in ws)
    err = max(float(np.max(np.abs(g - w))) for g, w in zip(gs, ws))
    return err / (scale + 1e-30)


def _has_kernel(compiled) -> bool:
    """The Pallas kernels compiled for the chip (not the interpreter)."""
    return "tpu_custom_call" in compiled.as_text()


def _compile(fn, *args):
    """jit + lower + compile; returns (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# deep phase
# ---------------------------------------------------------------------------

def build_deep(seed: int, dim: int = DIM, tile: int = TILE,
               depth: int = DEPTH, calibrate_steps: int = 200):
    """The README's deep pipeline on seeded random weights."""
    from repro import compile as comp
    from repro.paper.prototype import PROTOTYPE

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    tps = []
    for l in range(depth):
        w = rng.normal(size=(dim, dim)) / np.sqrt(dim)
        tp = comp.synthesize_tiled(w, tile=tile)
        tp = comp.program_tiled(tp, method="reck")
        tp = comp.quantize_tiled(tp, "table1")
        tps.append(comp.calibrate_tiled(tp, PROTOTYPE,
                                        key=jax.random.fold_in(key, l),
                                        steps=calibrate_steps))
    compiled = comp.lower_deep(tps)
    if compiled.in_placement is not None or compiled.out_placement is not None:
        raise ValueError("the smoke program is built without placements")
    return compiled


def _reference_fn(compiled):
    """``layer_args, x -> y``: the plain-jnp evaluation of ``compiled``."""
    from repro.kernels import ref

    def fn(layer_args, x):
        y = ref.deep_apply_ref(layer_args, x, n=compiled.tile,
                               plans=compiled.plans,
                               hardware=compiled.hardware)
        return y[..., : compiled.out_dim]
    return fn


def deep_serve(compiled, seed: int, n_requests: int = N_REQUESTS,
               slots: int = SLOTS) -> dict:
    """Serve ``n_requests`` through the engine's dispatch thread and check
    every served row against the reference."""
    from repro.serving import Request, ServingEngine

    rng = np.random.default_rng(seed + 1)
    xs = rng.normal(size=(n_requests, compiled.in_dim)).astype(np.float32)
    kernel, compile_s = _compile(compiled.apply,
                                 jnp.zeros((slots, compiled.in_dim)))

    reqs = [Request(rid=i, features=x) for i, x in enumerate(xs)]
    engine = ServingEngine(compiled, slots=slots)
    finished = False
    t0 = time.perf_counter()
    engine.start()
    try:
        for r in reqs:
            engine.submit(r)
        finished = all(r.wait(timeout=WAIT_S) for r in reqs)
    finally:
        engine.stop(drain=finished)
    serve_s = time.perf_counter() - t0
    served = [r for r in reqs if r.done and not r.failed]

    y_ref = _reference_fn(compiled)(compiled.layer_args, jnp.asarray(xs))
    err = (rel_err(np.stack([r.result for r in reqs]), y_ref)
           if len(served) == n_requests else float("inf"))
    return _report(
        phase="deep_serve", compile_s=compile_s, serve_s=serve_s,
        requests=n_requests, served=len(served),
        failed=n_requests - len(served), ticks=engine.ticks,
        max_rel_err=err, tpu_custom_call=_has_kernel(kernel),
        checks={"all_served": len(served) == n_requests,
                "within_tol": err <= REL_TOL})


def _split_keys(layer_args):
    """Trainable float leaves and the frozen noise-draw keys of every tile."""
    def part(keep):
        return tuple(tuple(tuple(
            {k: v for k, v in ta.items() if k.startswith("key_") == keep}
            for ta in row) for row in grid) for grid in layer_args)
    return part(False), part(True)


def _merge(params, keys):
    return tuple(tuple(tuple(
        {**p, **k} for p, k in zip(prow, krow))
        for prow, krow in zip(pgrid, kgrid))
        for pgrid, kgrid in zip(params, keys))


def deep_sgd(compiled, seed: int, batch: int = TRAIN_BATCH,
             steps: int = SGD_STEPS, lr: float = SGD_LR) -> dict:
    """SGD through ``ops.deep_apply``'s kernel VJP on the compiled
    program's tiles; the first step's gradients against the reference."""
    from repro.kernels import ops

    params, keys = _split_keys(compiled.layer_args)
    ref_fn = _reference_fn(compiled)

    def kernel_fn(layer_args, x):
        return ops.deep_apply(layer_args, x, n=compiled.tile,
                              plans=compiled.plans,
                              hardware=compiled.hardware)

    def loss(apply_fn, p, x, t):
        return jnp.mean((apply_fn(_merge(p, keys), x) - t) ** 2)

    def step(p, x, t):
        value, grads = jax.value_and_grad(
            lambda q: loss(kernel_fn, q, x, t))(p)
        return jax.tree.map(lambda w, g: w - lr * g, p, grads), value, grads

    x = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (batch, compiled.in_dim))
    target = 0.5 * compiled.apply(x)       # pull every output halfway in
    sgd, compile_s = _compile(step, params, x, target)

    losses, grads0 = [], None
    t0 = time.perf_counter()
    for i in range(steps):
        params, value, grads = sgd(params, x, target)
        losses.append(float(value))
        if i == 0:
            grads0 = grads
    losses.append(float(loss(kernel_fn, params, x, target)))
    train_s = time.perf_counter() - t0

    p0, _ = _split_keys(compiled.layer_args)
    g_ref = jax.grad(lambda p: loss(ref_fn, p, x, target))(p0)
    err = rel_err(grads0, g_ref)
    falling = all(b < a for a, b in zip(losses, losses[1:]))
    return _report(
        phase="deep_sgd", batch=batch, compile_s=compile_s, train_s=train_s,
        loss=losses, grad_max_rel_err=err, tpu_custom_call=_has_kernel(sgd),
        checks={"loss_falls": falling, "grads_within_tol": err <= REL_TOL})


# ---------------------------------------------------------------------------
# mnist phase
# ---------------------------------------------------------------------------

def mnist(seed: int, n_train: int = 1000, n_test: int = 200,
          batch: int = 10) -> dict:
    """One epoch of the paper's MNIST RFNN on the kernel path."""
    from repro.data.digits import load_digits
    from repro.kernels import ops
    from repro.paper.mnist_rfnn import train_mnist
    from repro.paper.prototype import PROTOTYPE
    from repro.train.step import make_sgd_step

    x_tr, y_tr, x_te, y_te = load_digits(n_train=n_train, n_test=n_test,
                                         seed=seed)
    calls = ops.KERNEL_PATH_CALLS["mesh_apply"]
    t0 = time.perf_counter()
    res = train_mnist(x_tr, y_tr, x_te, y_te, analog=True,
                      hardware=PROTOTYPE, schedule="ste", backend="pallas",
                      epochs=1, batch=batch, seed=seed)
    train_s = time.perf_counter() - t0
    calls = ops.KERNEL_PATH_CALLS["mesh_apply"] - calls

    model, params = res["model"], res["params"]
    reference = dataclasses.replace(model, backend="reference")
    xb, yb = jnp.asarray(x_te[:batch]), jnp.asarray(y_te[:batch])
    key = jax.random.PRNGKey(seed + 3)
    apply_k, _ = _compile(model.apply, params, xb, key)
    err = rel_err(apply_k(params, xb, key),
                  jax.jit(reference.apply)(params, xb, key))
    step, compile_s = _compile(
        make_sgd_step(lambda p, xi, yi: model.loss(p, xi, yi), lr=0.005),
        params, xb, yb)
    return _report(
        phase="mnist", train_s=train_s, step_compile_s=compile_s,
        mesh_apply_calls=calls, loss=res["history"][0]["loss"],
        test_acc=res["test_acc"], logits_max_rel_err=err,
        tpu_custom_call=_has_kernel(step) and _has_kernel(apply_k),
        checks={"kernel_path_taken": calls > 0,
                "within_tol": err <= REL_TOL})


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def _custom_call_shapes(compiled) -> list[str]:
    """Result shapes of every Pallas kernel in a compiled (partitioned,
    per-device) program."""
    return [m for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line
            for m in re.findall(r"f32\[[\d,]+\]", line)]


def sharded(seed: int, batch: int = 256, n_rows: int = 2,
            n_data: int = 2, **deep_size) -> dict:
    """The deep grid over a (rows x data) mesh against one chip, and one
    data-parallel SGD step against the serial step."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.analog_linear import AnalogSequence
    from repro.kernels import ops
    from repro.train.step import make_sgd_step

    devices = jax.devices()[: n_rows * n_data]
    mesh = Mesh(np.array(devices).reshape(n_rows, n_data), ("rows", "data"))
    # the sharded path needs a bound device, not a trimmed one
    compiled = build_deep(seed, calibrate_steps=0, **deep_size)
    deep, tensors = compiled.deep, compiled.packed
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (batch, compiled.in_dim))

    def fwd(tt, xx, m):
        return ops.deep_apply(None, xx, n=compiled.tile, packed=(deep, tt),
                              mesh=m)

    def loss(tt, xx, m):
        return jnp.sum(fwd(tt, xx, m) ** 2)

    on_mesh = NamedSharding(mesh, P("data", "rows"))
    fwd_n = jax.jit(lambda tt, xx: fwd(tt, xx, mesh),
                    out_shardings=on_mesh).lower(tensors, x).compile()
    y_n = fwd_n(tensors, x)
    y_1 = jax.jit(lambda tt, xx: fwd(tt, xx, None))(tensors, x)
    grad = jax.grad(loss, argnums=(0, 1))
    g_n = jax.jit(lambda tt, xx: grad(tt, xx, mesh))(tensors, x)
    g_1 = jax.jit(lambda tt, xx: grad(tt, xx, None))(tensors, x)

    # each device computed its own (row slab x batch shard) of every layer
    # and holds a distinct block of the output
    shards = {(s.device.id, str(s.index)) for s in y_n.addressable_shards}
    local = f"f32[{deep.to // n_rows},{deep.n // 2},{batch // n_data}]"
    row_slabs = local in _custom_call_shapes(fwd_n)

    seq = AnalogSequence(n=8, depth=2, backend="pallas")
    params = seq.init(jax.random.PRNGKey(seed))
    dp_mesh = Mesh(np.array(jax.devices()[: n_rows * n_data]), ("data",))
    xb = jax.random.normal(jax.random.PRNGKey(seed + 2), (16, 8))
    yb = jax.random.normal(jax.random.PRNGKey(seed + 3), (16, 8)) ** 2

    def dp_loss(p, xx, yy):
        value = jnp.mean((seq.apply(p, xx) - yy) ** 2)
        return value, value

    p_1, (l_1, _) = jax.jit(make_sgd_step(dp_loss, lr=0.05))(params, xb, yb)
    p_n, (l_n, _) = jax.jit(make_sgd_step(dp_loss, lr=0.05, mesh=dp_mesh))(
        params, xb, yb)

    err_fwd, err_vjp = rel_err(y_n, y_1), rel_err(g_n, g_1)
    err_dp = max(rel_err(p_n, p_1), rel_err(l_n, l_1))
    n_dev = n_rows * n_data
    return _report(
        phase="sharded", mesh={"rows": n_rows, "data": n_data},
        devices=[str(d) for d in devices], output_shards=len(shards),
        kernel_row_slab=local, row_slab_in_kernel=row_slabs,
        fwd_max_rel_err=err_fwd, vjp_max_rel_err=err_vjp,
        dp_sgd_max_rel_err=err_dp,
        tpu_custom_call=_has_kernel(fwd_n),
        checks={"four_devices": len({d.id for d in devices}) == n_dev,
                "shards_on_every_device": len(shards) == n_dev,
                "row_slab_in_kernel": row_slabs,
                "fwd_within_tol": err_fwd <= REL_TOL,
                "vjp_within_tol": err_vjp <= REL_TOL,
                "dp_sgd_within_tol": err_dp <= REL_TOL})


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _failed(result: dict) -> list[str]:
    bad = [name for name, ok in result["checks"].items() if not ok]
    if not result["tpu_custom_call"]:
        bad.append("tpu_custom_call")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the multi-chip paths and nothing else")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import use_compile_cache

    cache_dir = pathlib.Path(use_compile_cache())
    jax.config.update("jax_default_matmul_precision", "highest")

    failures = []
    try:
        if args.chips == 4:
            results = [sharded(args.seed)]
        else:
            t0 = time.perf_counter()
            compiled = build_deep(args.seed)
            _report(phase="deep_build", seconds=time.perf_counter() - t0,
                    layers=compiled.depth, grid=[compiled.to, compiled.ti],
                    tile=compiled.tile, columns=compiled.deep.n_columns)
            results = [deep_serve(compiled, args.seed),
                       deep_sgd(compiled, args.seed),
                       mnist(args.seed)]
        for r in results:
            failures += [f"{r['phase']}:{name}" for name in _failed(r)]
        _report(phase="compile_cache", dir=str(cache_dir),
                entries=len(list(cache_dir.glob("*")))
                if cache_dir.is_dir() else 0)
    except Exception:  # noqa: BLE001 - any phase error fails the run
        traceback.print_exc()
        return 1
    if failures:
        print(f"chip_smoke: failed checks {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
