"""End-to-end training driver.

Runs a real training loop with the full substrate: deterministic resumable
data stream, jitted train step, async checkpointing, straggler monitoring
and (simulated) failure recovery via the elastic planner.  On CPU it runs
reduced configs; on a TPU fleet the same driver runs the full configs with
the production mesh (--multi-pod).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 200 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
        --reduced --steps 50 --resume --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import TokenStream
from repro.models import Model
from repro.optim import AdamW, cosine_schedule
from repro.runtime import FailureInjector, StragglerMonitor, plan_recovery
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.failures import Failure
from repro.train import step as step_lib


def make_batch_arrays(cfg, raw, key):
    batch = {"tokens": jnp.asarray(raw["tokens"]),
             "labels": jnp.asarray(raw["labels"])}
    if cfg.family == "vlm":
        batch["vis_embed"] = 0.02 * jax.random.normal(
            key, (batch["tokens"].shape[0], cfg.n_vis_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            key, (batch["tokens"].shape[0], cfg.enc_seq, cfg.d_model))
    return batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-straggler", type=int, default=-1,
                    help="simulate a straggler host from this step")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    model = Model(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, 20, args.steps), weight_decay=0.01)
    train_step = jax.jit(step_lib.make_train_step(model, opt,
                                                  accum_steps=args.accum))

    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch, seed=0)
    state = step_lib.init_state(model, opt, jax.random.PRNGKey(0))
    start_step = 0

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and args.resume and mgr.latest_step() is not None:
        restored, meta = mgr.restore(None, like=state)
        state, start_step = restored, meta["data_step"]
        print(f"resumed from step {meta['step']} (data step {start_step})")

    monitor = StragglerMonitor(num_hosts=4)
    injector = FailureInjector(
        [Failure(step=args.inject_straggler, kind="straggler", host=1)]
        if args.inject_straggler >= 0 else [])

    print(f"training {cfg.name}: {model.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps")
    t_last = time.time()
    for step_i in range(start_step, args.steps):
        injector.at_step(step_i)
        raw = stream.batch(step_i)
        batch = make_batch_arrays(cfg, raw, jax.random.PRNGKey(step_i))
        state, metrics = train_step(state, batch)

        t_now = time.time()
        host_times = np.asarray([injector.step_time(h, t_now - t_last)
                                 for h in range(4)])
        monitor.observe(host_times)
        t_last = t_now
        if monitor.persistent():
            bad = monitor.persistent()
            plan = plan_recovery(512 - 4 * len(bad))
            print(f"[ft] persistent stragglers {bad}; recovery plan: "
                  f"mesh={plan.mesh_shape} accum x{plan.accum_multiplier}")
            if mgr:
                mgr.save_async(step_i + 1, state, data_step=step_i + 1)
            monitor = StragglerMonitor(num_hosts=4)  # fresh after re-mesh

        if step_i % args.log_every == 0 or step_i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {step_i:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
            if not np.isfinite(loss):
                raise RuntimeError("loss diverged")
        if mgr and (step_i + 1) % args.ckpt_every == 0:
            mgr.save_async(step_i + 1, state, data_step=step_i + 1)

    if mgr:
        mgr.save(args.steps, state, data_step=args.steps)
        mgr.wait()
    print("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
