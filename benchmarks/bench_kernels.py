"""Mesh-kernel benchmarks: Pallas (interpret on CPU) vs pure-jnp reference.

On the CPU the timing is indicative only (interpret mode runs the kernel
body op-by-op) and is never a device number; the derived column also
reports the kernel's analytic VMEM working set and FLOPs — the numbers
that matter for the TPU target.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import SMOKE, row, time_call, time_pair
from repro.core import mesh as mesh_lib
from repro.kernels import ops, ref


def mesh_kernel_sweep(sizes=None, batch=None) -> list[str]:
    sizes = sizes or ((16,) if SMOKE else (16, 64, 256))
    batch = batch or (32 if SMOKE else 256)
    rows = []
    for n in sizes:
        plan = mesh_lib.clements_plan(n)
        params = mesh_lib.init_mesh_params(jax.random.PRNGKey(n), plan)
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, n),
                              jnp.float32).astype(jnp.complex64)
        k_fn = jax.jit(lambda p, xx: ops.mesh_apply(p, xx, n=n, block_b=64))
        r_fn = jax.jit(lambda p, xx: ref.mesh_apply_ref(p, xx, n))
        us_k = time_call(k_fn, params, x, iters=5)
        us_r = time_call(r_fn, params, x, iters=5)
        flops = 2 * plan.n_cells * batch * 16
        vmem_kb = (8 * 64 * (n // 2) * 4 + n * 8 * (n // 2) * 4) / 1024
        rows.append(row(f"mesh_kernel_n{n}", us_k,
                        f"ref_us={us_r:.1f};cells={plan.n_cells};"
                        f"flops={flops};vmem_kb={vmem_kb:.0f}"))
    return rows


def fused_rfnn_linear(n=None, batch=None) -> list[str]:
    n = n or (16 if SMOKE else 64)
    batch = batch or (32 if SMOKE else 256)
    plan = mesh_lib.clements_plan(n)
    vp = mesh_lib.init_mesh_params(jax.random.PRNGKey(0), plan)
    up = mesh_lib.init_mesh_params(jax.random.PRNGKey(1), plan)
    atten = jax.random.uniform(jax.random.PRNGKey(2), (n,))
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, n))
    fused = jax.jit(lambda v, a, u, xx: ops.rfnn_linear(
        v, a, u, xx, n=n, block_b=64))
    unfused = jax.jit(lambda v, a, u, xx: ref.rfnn_linear_ref(
        v, a, u, xx.astype(jnp.complex64), n))
    us_f = time_call(fused, vp, atten, up, x, iters=5)
    us_u = time_call(unfused, vp, atten, up, x, iters=5)
    # fused kernel does 1 HBM round-trip instead of 3 (V out, D out, U out)
    hbm_unfused = 3 * 2 * batch * n * 8
    hbm_fused = 2 * batch * n * 8
    return [row("rfnn_linear_fused", us_f,
                f"unfused_us={us_u:.1f};"
                f"hbm_bytes {hbm_fused} vs {hbm_unfused} (3x saved)")]


def mesh_kernel_fwd_bwd(sizes=None, batch=None) -> list[str]:
    """fwd+bwd through the mesh: kernel custom-VJP vs reference autodiff.

    The kernel backward is one reversed-column Pallas sweep
    (inverse/adjoint, DESIGN.md) instead of lax.scan's stored-intermediate
    transpose; the derived column reports the residual HBM bytes autodiff
    would have stored per column and the max grad deviation between the
    two paths.
    """
    sizes = sizes or ((16,) if SMOKE else (16, 64))
    batch = batch or (64 if SMOKE else 128)
    rows = []
    for n in sizes:
        plan = mesh_lib.clements_plan(n)
        params = mesh_lib.init_mesh_params(jax.random.PRNGKey(n), plan)
        k = jax.random.PRNGKey(0)
        x = (jax.random.normal(k, (batch, n))
             + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                      (batch, n))).astype(jnp.complex64)

        def loss_k(p, xx, n=n):
            return jnp.sum(jnp.abs(ops.mesh_apply(p, xx, n=n, block_b=64)))

        def loss_r(p, xx, n=n):
            return jnp.sum(jnp.abs(ref.mesh_apply_ref(p, xx, n)))

        k_fn = jax.jit(jax.grad(loss_k))
        r_fn = jax.jit(jax.grad(loss_r))
        us_k = time_call(k_fn, params, x, iters=3)
        us_r = time_call(r_fn, params, x, iters=3)
        gk, gr = k_fn(params, x), r_fn(params, x)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)))
        saved_bytes = n * batch * n * 8  # autodiff: one complex panel/column
        rows.append(row(f"mesh_fwd_bwd_n{n}", us_k,
                        f"ref_autodiff_us={us_r:.1f};max_grad_err={err:.1e};"
                        f"residual_hbm_bytes_saved={saved_bytes}"))
    return rows


def mesh_fwd_bwd_nonideal(sizes=None, batch=None) -> list[str]:
    """fwd+bwd with the hardware model and a Reck layout, both paths.

    The paper-faithful configurations (imperfect hybrids, per-cell
    insertion loss, triangular analytic programs) used to fall back to the
    reference path; these rows benchmark them *through the generalized
    kernel* (inverse/adjoint backward) against reference autodiff of
    ``apply_mesh_hw`` / ``apply_mesh``.
    """
    from repro.core import decompose
    from repro.core import hardware as hw_lib

    sizes = sizes or ((8,) if SMOKE else (8, 16))
    batch = batch or (64 if SMOKE else 128)
    hw = hw_lib.HardwareModel(phase_sigma=0.0, detector_sigma=0.0)
    rows = []
    for n in sizes:
        k = jax.random.PRNGKey(0)
        x = (jax.random.normal(k, (batch, n))
             + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                      (batch, n))).astype(jnp.complex64)
        cplan = mesh_lib.clements_plan(n)
        cparams = mesh_lib.init_mesh_params(jax.random.PRNGKey(n), cplan)
        rplan, rparams = decompose.reck_program(
            decompose.random_unitary(n, seed=n))
        for tag, plan, params, hmodel in [
                ("hw", cplan, cparams, hw),
                ("reck", rplan, rparams, None)]:
            def loss_k(p, xx, plan=plan, hmodel=hmodel, n=n):
                return jnp.sum(jnp.abs(ops.mesh_apply(
                    p, xx, n=n, plan=plan, hardware=hmodel, block_b=64)))

            def loss_r(p, xx, plan=plan, hmodel=hmodel):
                if hmodel is not None:
                    y = hw_lib.apply_mesh_hw(plan, p, xx, hmodel)
                else:
                    y = mesh_lib.apply_mesh(plan, p, xx)
                return jnp.sum(jnp.abs(y))

            k_fn = jax.jit(jax.grad(loss_k))
            r_fn = jax.jit(jax.grad(loss_r))
            us_k = time_call(k_fn, params, x, iters=3)
            us_r = time_call(r_fn, params, x, iters=3)
            gk, gr = k_fn(params, x), r_fn(params, x)
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(jax.tree.leaves(gk),
                                      jax.tree.leaves(gr)))
            rows.append(row(f"mesh_fwd_bwd_{tag}_n{n}", us_k,
                            f"ref_autodiff_us={us_r:.1f};"
                            f"max_grad_err={err:.1e}"))
    return rows


def mc_yield_sweep() -> list[str]:
    """Monte-Carlo hardware-yield sweep, vmapped over the Pallas kernel."""
    from repro.paper.efficiency import monte_carlo_yield

    n_draws = 8 if SMOKE else 32
    import time as _time
    monte_carlo_yield(n=8, n_draws=n_draws, backend="pallas")  # warm caches
    t0 = _time.perf_counter()
    res = monte_carlo_yield(n=8, n_draws=n_draws, backend="pallas")
    us = (_time.perf_counter() - t0) * 1e6
    return [row("mc_yield_n8", us,
                f"yield={res['yield']:.2f};draws={n_draws};"
                f"mean_err={res['mean_error']:.3f};"
                f"worst_err={res['worst_error']:.3f}")]


def rfnn_linear_fwd_bwd(n=16, batch=None) -> list[str]:
    """fwd+bwd through the fused analog linear layer, both paths."""
    batch = batch or (64 if SMOKE else 128)
    plan = mesh_lib.clements_plan(n)
    vp = mesh_lib.init_mesh_params(jax.random.PRNGKey(0), plan)
    up = mesh_lib.init_mesh_params(jax.random.PRNGKey(1), plan)
    atten = jax.random.uniform(jax.random.PRNGKey(2), (n,), minval=0.1,
                               maxval=0.9)
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, n))

    def loss_k(v, a, u, xx):
        return jnp.sum(ops.rfnn_linear(v, a, u, xx, n=n, block_b=64))

    def loss_r(v, a, u, xx):
        return jnp.sum(ref.rfnn_linear_ref(v, a, u,
                                           xx.astype(jnp.complex64), n))

    k_fn = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))
    r_fn = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))
    us_k = time_call(k_fn, vp, atten, up, x, iters=3)
    us_r = time_call(r_fn, vp, atten, up, x, iters=3)
    gk, gr = k_fn(vp, atten, up, x), r_fn(vp, atten, up, x)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)))
    # bwd residuals: 2 stage boundaries vs one complex panel per column
    hbm_kernel = 2 * 4 * batch * (n // 2) * 4
    hbm_autodiff = 2 * n * batch * n * 8
    return [row("rfnn_linear_fwd_bwd", us_k,
                f"ref_autodiff_us={us_r:.1f};max_grad_err={err:.1e};"
                f"residual_hbm_bytes {hbm_kernel} vs {hbm_autodiff}")]


def net_fwd_bwd(configs=None, n_layers=4) -> list[str]:
    """fwd+bwd through the whole L-layer RFNN: megakernel vs per-layer.

    The per-layer baseline composes L fused ``rfnn_linear`` kernels (each
    already one pallas_call per direction); the megakernel runs the entire
    network in ONE pallas_call per direction, keeping inter-layer
    activations VMEM-resident and saving only the L-1 boundary magnitudes
    as residuals.  The derived column reports the per-layer composition's
    timing, the residual-plane count each path stores, and the max grad
    deviation.  ``net_fwd_bwd_n16_b1024`` is the CI fusion gate row.
    """
    from repro.kernels.ops import rfnn_network

    configs = configs or (((16, 1024),) if SMOKE
                          else ((8, 256), (16, 256), (16, 1024), (16, 2048)))
    rows = []
    for n, batch in configs:
        plan = mesh_lib.clements_plan(n)
        layers = []
        for l in range(n_layers):
            kv, ku, ka = jax.random.split(jax.random.PRNGKey(100 + l), 3)
            layers.append({
                "v": mesh_lib.init_mesh_params(kv, plan),
                "u": mesh_lib.init_mesh_params(ku, plan),
                "atten": jax.random.uniform(ka, (n,), minval=0.2,
                                            maxval=0.9),
                "scale": 1.0,
            })
        layers = tuple(layers)
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, n))
        w = 1.0 + jnp.arange(n, dtype=jnp.float32)  # break |.|-degeneracy

        def per_layer(ls, xx):
            h = xx
            for la in ls:
                h = ops.rfnn_linear(la["v"], la["atten"], la["u"], h, n=n,
                                    scale=la["scale"])
            return h

        def loss_net(ls, xx):
            return jnp.sum(rfnn_network(ls, xx, n=n) * w)

        def loss_pl(ls, xx):
            return jnp.sum(per_layer(ls, xx) * w)

        net_fn = jax.jit(jax.grad(loss_net))
        pl_fn = jax.jit(jax.grad(loss_pl))
        # min-of-N: this row is a differential CI gate on a shared runner,
        # so use the noise-robust estimator for both sides
        us_net = time_call(net_fn, layers, x, iters=5, reduce="min")
        us_pl = time_call(pl_fn, layers, x, iters=5, reduce="min")
        gn, gp = net_fn(layers, x), pl_fn(layers, x)
        scale_ref = max(float(jnp.max(jnp.abs(g)))
                        for g in jax.tree.leaves(gp))
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(gp)))
        rel = err / (scale_ref + 1e-30)
        # both paths save 8 stage-residual planes per layer; the fusion win
        # is the inter-layer activation round trips (write + fwd read +
        # bwd read per boundary) and 2L-2 fewer kernel launches/direction
        interlayer = 3 * (n_layers - 1) * batch * n * 4
        rows.append(row(f"net_fwd_bwd_n{n}_b{batch}", us_net,
                        f"per_layer_us={us_pl:.1f};layers={n_layers};"
                        f"max_grad_rel_err={rel:.1e};"
                        f"interlayer_hbm_bytes 0 vs {interlayer};"
                        f"pallas_calls 2 vs {2 * n_layers}"))
    return rows


def tiled_apply_grid(n=64, tile=16, batch=256) -> list[str]:
    """Tile-grid megakernel vs the double-vmapped per-tile composition.

    The baseline is what ``TiledAnalogLinear(backend="pallas")`` used to
    run before the tile-grid kernel: vmap over the input-tile axis, then
    the output-tile axis, of a single-tile V -> diag -> U -> scale chain —
    To*Ti separate mesh applications with per-tile packing and HBM
    round-trips between tile rows.  The megakernel runs the whole grid in
    ONE pallas_call per direction.  ``tiled_apply_n64`` is a CI gate row
    (64x64, tile=16, B=256 — the first genuinely >8x8 analog workload),
    so the configuration does NOT shrink under BENCH_SMOKE.
    """
    import numpy as np

    from repro.kernels.ops import tiled_apply

    to, ti = n // tile, n // tile
    plan = mesh_lib.clements_plan(tile)
    tiles = []
    for o in range(to):
        trow = []
        for i in range(ti):
            kv, ku, ka = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(7), o * ti + i), 3)
            trow.append({
                "v": mesh_lib.init_mesh_params(kv, plan),
                "u": mesh_lib.init_mesh_params(ku, plan),
                "atten": jax.random.uniform(ka, (tile,), minval=0.2,
                                            maxval=0.9),
                "scale": 1.0 + 0.05 * (o + i),
            })
        tiles.append(tuple(trow))
    tiles = tuple(tiles)
    # the vmapped baseline consumes the same parameters stacked [To, Ti, .]
    stacked = jax.tree.map(lambda *rows: jnp.stack(rows), *[
        jax.tree.map(lambda *ts: jnp.stack(ts), *row) for row in tiles])
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, n))
    w = 1.0 + jnp.arange(n, dtype=jnp.float32)  # break |.|-degeneracy

    def vmapped(ps, xx):
        xt = xx.astype(jnp.complex64).reshape(xx.shape[:-1] + (ti, tile))

        def one_tile(p, xin):
            h = ops.mesh_apply(p["v"], xin, n=tile)
            h = h * p["atten"].astype(jnp.complex64)
            y = ops.mesh_apply(p["u"], h, n=tile)
            return p["scale"].astype(jnp.complex64) * y

        def row_f(prow):
            ys = jax.vmap(one_tile, in_axes=(0, -2), out_axes=-2)(prow, xt)
            return jnp.sum(ys, axis=-2)

        y = jax.vmap(row_f, in_axes=0, out_axes=-2)(ps)
        return y.reshape(y.shape[:-2] + (n,))

    def loss_k(ts, xx):
        return jnp.sum(jnp.abs(tiled_apply(ts, xx, n=tile)) * w)

    def loss_v(ps, xx):
        return jnp.sum(jnp.abs(vmapped(ps, xx)) * w)

    k_fn = jax.jit(jax.grad(loss_k))
    v_fn = jax.jit(jax.grad(loss_v))
    # min-of-N: this row is a differential CI gate on a shared runner
    us_k = time_call(k_fn, tiles, x, iters=3, reduce="min")
    us_v = time_call(v_fn, stacked, x, iters=3, reduce="min")
    g_tiles = k_fn(tiles, x)
    g_stack = v_fn(stacked, x)
    # kernel grads come back per-tile; compare tile-for-tile with the
    # vmapped baseline's stacked gradient (same dict structure per tile)
    scale_ref = max(float(jnp.max(jnp.abs(g)))
                    for g in jax.tree.leaves(g_stack))
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for o in range(to) for i in range(ti)
        for a, b in zip(
            jax.tree.leaves(g_tiles[o][i]),
            jax.tree.leaves(jax.tree.map(
                lambda g, o=o, i=i: g[o, i], g_stack))))
    rel = err / (scale_ref + 1e-30)
    # the fusion win: 2 pallas_calls/direction vs 2*To*Ti, and no HBM
    # round trip of the [B, tile] panel between V and U of every tile
    intertile = 2 * to * ti * batch * tile * 8
    return [row(f"tiled_apply_n{n}", us_k,
                f"per_tile_us={us_v:.1f};grid={to}x{ti};tile={tile};"
                f"max_grad_rel_err={rel:.1e};"
                f"intertile_hbm_bytes 0 vs {intertile};"
                f"pallas_calls 2 vs {2 * to * ti}")]


def deepgrid_fwd_bwd(n=64, tile=16, n_layers=4, batches=None) -> list[str]:
    """Deep tiled-network megakernel vs the per-layer tile-grid composition.

    The baseline composes L tile-grid megakernels (each already ONE
    pallas_call per direction) with the inter-layer power detection in
    plain JAX — L-1 activation round trips through HBM plus 2L kernel
    launches per direction.  The deep kernel runs the whole L x To x Ti
    cascade in ONE pallas_call per direction, detecting and re-injecting
    between layers inside VMEM; only the per-layer stage planes leave the
    kernel (as VJP residuals — the same count the composition stores).
    ``deepgrid_fwd_bwd_n64_l4`` (B=1024) is a CI gate row, so that
    configuration does NOT shrink under BENCH_SMOKE.
    """
    from repro.kernels.ops import deep_apply, tiled_apply

    batches = batches or ((1024,) if SMOKE else (256, 1024))
    g = n // tile
    plan = mesh_lib.clements_plan(tile)
    layers = []
    for l in range(n_layers):
        lrows = []
        for o in range(g):
            trow = []
            for i in range(g):
                kv, ku, ka = jax.random.split(jax.random.fold_in(
                    jax.random.PRNGKey(7), (l * g + o) * g + i), 3)
                trow.append({
                    "v": mesh_lib.init_mesh_params(kv, plan),
                    "u": mesh_lib.init_mesh_params(ku, plan),
                    "atten": jax.random.uniform(ka, (tile,), minval=0.2,
                                                maxval=0.9),
                    "scale": 1.0 + 0.05 * (o + i + l),
                })
            lrows.append(tuple(trow))
        layers.append(tuple(lrows))
    layers = tuple(layers)
    w = 1.0 + jnp.arange(n, dtype=jnp.float32)  # break |.|-degeneracy

    def per_layer(ls, xx):
        h = xx
        for tiles in ls:
            h = jnp.abs(tiled_apply(tiles, h, n=tile))
        return h

    def loss_deep(ls, xx):
        return jnp.sum(deep_apply(ls, xx, n=tile) * w)

    def loss_pl(ls, xx):
        return jnp.sum(per_layer(ls, xx) * w)

    deep_fn = jax.jit(jax.grad(loss_deep))
    pl_fn = jax.jit(jax.grad(loss_pl))
    rows = []
    for batch in batches:
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, n))
        # interleaved min-of-7: the B=1024 row is a differential CI gate
        # on a shared runner, so both sides must sample the same load
        us_d, us_p = time_pair(deep_fn, pl_fn, layers, x)
        gd, gp = deep_fn(layers, x), pl_fn(layers, x)
        scale_ref = max(float(jnp.max(jnp.abs(gr)))
                        for gr in jax.tree.leaves(gp))
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gp)))
        rel = err / (scale_ref + 1e-30)
        # fusion win: boundary activations (write + fwd read + bwd read
        # per boundary) never touch HBM, and 2L-2 fewer launches/direction
        boundary = 3 * (n_layers - 1) * batch * n * 4
        name = (f"deepgrid_fwd_bwd_n{n}_l{n_layers}" if batch == 1024
                else f"deepgrid_fwd_bwd_n{n}_l{n_layers}_b{batch}")
        rows.append(row(name, us_d,
                        f"per_layer_us={us_p:.1f};layers={n_layers};"
                        f"grid={g}x{g};tile={tile};batch={batch};"
                        f"max_grad_rel_err={rel:.1e};"
                        f"interlayer_hbm_bytes 0 vs {boundary};"
                        f"pallas_calls 2 vs {2 * n_layers}"))
    return rows


def tiled_apply_sharded(n=64, tile=16, batch=256) -> list[str]:
    """shard_map scale-out of the tile-grid megakernel vs single-device.

    Runs the same 64x64 fwd+bwd workload as ``tiled_apply_n64`` through
    ``tiled_apply(mesh=...)`` — tile rows sharded over ``rows``, batch
    over ``data`` — and reports the single-device megakernel as the
    baseline.  Skipped (returns no rows) on a 1-device host: launch with
    ``BENCH_HOST_DEVICES=8`` to force a host-device mesh.  On forced CPU
    host devices the collectives go through shared memory, so the timing
    only sanity-checks overhead; the row is allowlisted as noisy in the
    gate (``check_gate.NOISY_ROWS``).
    """
    import numpy as np

    from jax.sharding import Mesh
    from repro.kernels.ops import tiled_apply

    to, ti = n // tile, n // tile
    n_dev = len(jax.devices())
    nr = max(d for d in range(1, to + 1) if to % d == 0 and d <= n_dev)
    nd = max(d for d in (1, 2, 4) if nr * d <= n_dev)
    if nr * nd < 2:
        return []
    mesh = Mesh(np.array(jax.devices()[: nr * nd]).reshape(nr, nd),
                ("rows", "data"))
    plan = mesh_lib.clements_plan(tile)
    tiles = []
    for o in range(to):
        trow = []
        for i in range(ti):
            kv, ku, ka = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(7), o * ti + i), 3)
            trow.append({
                "v": mesh_lib.init_mesh_params(kv, plan),
                "u": mesh_lib.init_mesh_params(ku, plan),
                "atten": jax.random.uniform(ka, (tile,), minval=0.2,
                                            maxval=0.9),
                "scale": 1.0 + 0.05 * (o + i),
            })
        tiles.append(tuple(trow))
    tiles = tuple(tiles)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, n))
    w = 1.0 + jnp.arange(n, dtype=jnp.float32)

    def loss(ts, xx, mesh=None):
        return jnp.sum(jnp.abs(tiled_apply(ts, xx, n=tile, mesh=mesh)) * w)

    sh_fn = jax.jit(jax.grad(lambda ts, xx: loss(ts, xx, mesh=mesh)))
    sd_fn = jax.jit(jax.grad(loss))
    us_sh = time_call(sh_fn, tiles, x, iters=3, reduce="min")
    us_sd = time_call(sd_fn, tiles, x, iters=3, reduce="min")
    g_sh, g_sd = sh_fn(tiles, x), sd_fn(tiles, x)
    scale_ref = max(float(jnp.max(jnp.abs(g)))
                    for g in jax.tree.leaves(g_sd))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(g_sh), jax.tree.leaves(g_sd)))
    return [row(f"tiled_apply_sharded_n{n}", us_sh,
                f"single_device_us={us_sd:.1f};mesh={nr}x{nd};"
                f"grid={to}x{ti};tile={tile};"
                f"max_grad_rel_err={err / (scale_ref + 1e-30):.1e}")]


def compile_apply(n=16, batch=None) -> list[str]:
    """Compiled-program apply vs the retired reference synthesis chain.

    The compiler's ``lower`` pass emits megakernel tensors once; ``apply``
    is then a single fused ``pallas_call``.  The baseline is what
    ``SynthesizedMatrix.apply`` used to run before the repoint: two
    pure-jnp ``apply_mesh`` column scans (V, U) with the diagonal and
    digital scale between them.  ``compile_apply_n16`` is a CI gate row.
    """
    import numpy as np

    from repro import compile as compile_mod

    batch = batch or (64 if SMOKE else 256)
    m = np.random.default_rng(0).normal(size=(n, n))
    prog = compile_mod.program(compile_mod.synthesize(m), method="reck")
    compiled = compile_mod.lower(prog, block_b=64)
    la = prog.layers[0]
    atten = la.attenuation.astype(jnp.complex64)
    scale = jnp.asarray(la.scale, jnp.complex64)

    def ref_apply(xx):
        h = mesh_lib.apply_mesh(la.v_plan, la.v_params,
                                xx.astype(jnp.complex64))
        h = h * atten
        h = mesh_lib.apply_mesh(la.u_plan, la.u_params, h)
        return jnp.abs(scale * h)

    x = jax.random.normal(jax.random.PRNGKey(0), (batch, n), jnp.float32)
    k_fn = compiled.apply
    r_fn = jax.jit(ref_apply)
    err = float(jnp.max(jnp.abs(k_fn(x) - r_fn(x))))
    # min-of-N: this row is a differential CI gate on a shared runner
    us_k = time_call(k_fn, x, iters=5, reduce="min")
    us_r = time_call(r_fn, x, iters=5, reduce="min")
    # reference: one HBM round-trip per mesh column (2 x (2n-3) columns)
    hbm_ref = 2 * (2 * n - 3) * batch * n * 8
    hbm_kernel = 2 * batch * n * 8
    return [row(f"compile_apply_n{n}", us_k,
                f"ref_apply_us={us_r:.1f};max_err={err:.1e};"
                f"hbm_bytes {hbm_kernel} vs {hbm_ref}")]


def flash_attention_kernel(s=None, hd=64, h=4, b=2) -> list[str]:
    """Flash attention kernel vs dense-softmax reference (interpret mode)."""
    s = s or (256 if SMOKE else 512)
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, h, s, hd), jnp.float32)
    k = jax.random.normal(k2, (b, h, s, hd), jnp.float32)
    v = jax.random.normal(k3, (b, h, s, hd), jnp.float32)
    f_fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, bq=128, bk=128))
    r_fn = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v))
    us_f = time_call(f_fn, q, k, v, iters=3)
    us_r = time_call(r_fn, q, k, v, iters=3)
    err = float(jnp.abs(f_fn(q, k, v) - r_fn(q, k, v)).max())
    assert err < 2e-5
    # HBM score traffic eliminated by the kernel (the §Perf memory term)
    score_bytes = b * h * s * s * 4
    return [row("flash_attention", us_f,
                f"dense_us={us_r:.1f};err={err:.1e};"
                f"score_hbm_bytes_saved={score_bytes}")]


def serving_qps(n=64, tile=16, batches=(256, 1024, 4096)) -> list[str]:
    """Sustained serving QPS of the unified engine under a request stream.

    Drives :class:`repro.serving.ServingEngine` over a compiled 64x64
    tile-grid program with a dynamic Poisson-ish arrival stream (mean
    ~1.3 x slots new requests per tick) and reports requests/sec plus
    p50/p99 tick latency at B (= slots) in {256, 1024, 4096}.  The
    baseline is serial per-request serving — one megakernel call per
    request on a [1, n] panel — which is what the slot-batched engine
    exists to beat; ``serving_qps_n64`` gates that win in CI
    (``check_gate.GATED_ROWS``), allowlisted as noisy for absolute
    timings (the python tick loop and thread scheduling dominate the
    microseconds, not the kernels).  The gate configuration does NOT
    shrink under BENCH_SMOKE (only the stream length does).
    """
    import time as time_lib

    import numpy as np

    from repro import compile as compile_mod
    from repro.serving import Request, ServingEngine

    m = np.random.default_rng(0).normal(size=(n, n)) / np.sqrt(n)
    comp = compile_mod.lower_tiled(compile_mod.program_tiled(
        compile_mod.synthesize_tiled(m, tile=tile), method="reck"),
        block_b=64)

    feats = np.random.default_rng(1).normal(
        size=(256, n)).astype(np.float32)
    # serial baseline: one request per megakernel call, no batching win
    one = jnp.asarray(feats[:1])
    serial_us = time_call(comp.apply, one, warmup=2, iters=5, reduce="min")

    rounds = 2 if SMOKE else 3
    us_gate = None
    parts = [f"serial_us={serial_us:.1f}"]
    for b in batches:
        engine = ServingEngine(comp, slots=b)
        jax.block_until_ready(
            comp.apply(jnp.zeros((b, n), jnp.float32)))  # warm panel shape
        rng = np.random.default_rng(b)
        total = rounds * b
        rid = 0
        t0 = time_lib.perf_counter()
        while rid < total:
            burst = min(int(rng.poisson(1.3 * b)), total - rid)
            for _ in range(burst):
                engine.submit(Request(rid=rid, features=feats[rid % 256]))
                rid += 1
            engine.tick()
        engine.run()            # drain the tail of the stream
        elapsed = time_lib.perf_counter() - t0
        assert engine.stats["served"] == total
        if b == batches[0]:
            us_gate = elapsed / total * 1e6
        parts.append(
            f"b{b}_qps={total / elapsed:.0f};"
            f"b{b}_p50_tick_us={engine.slo.percentile_us(50):.0f};"
            f"b{b}_p99_tick_us={engine.slo.percentile_us(99):.0f}")
    return [row(f"serving_qps_n{n}", us_gate, ";".join(parts))]


ALL = [mesh_kernel_sweep, fused_rfnn_linear, mesh_kernel_fwd_bwd,
       mesh_fwd_bwd_nonideal, mc_yield_sweep, rfnn_linear_fwd_bwd,
       net_fwd_bwd, tiled_apply_grid, deepgrid_fwd_bwd,
       tiled_apply_sharded, compile_apply, flash_attention_kernel,
       serving_qps]
