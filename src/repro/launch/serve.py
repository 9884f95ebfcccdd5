"""Serving driver: prefill + batched decode with a KV cache.

Implements the serve path end to end: request batching, prefill to build
caches, greedy/temperature decode loop, and per-step latency stats.  On CPU
it serves reduced configs; the same step functions are what the dry-run
lowers for the production meshes.

Example:
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --reduced --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import Model
from repro.runtime.compile_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    b, s = args.batch, args.prompt_len
    key = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["vis_embed"] = 0.02 * jax.random.normal(
            key, (b, cfg.n_vis_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            key, (b, cfg.enc_seq, cfg.d_model))
    vis = cfg.n_vis_tokens if cfg.family == "vlm" else 0
    max_len = s + vis + args.gen + 1

    prefill = jax.jit(lambda p, bt: model.prefill(p, bt, max_len=max_len))
    decode = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos))

    t0 = time.time()
    logits, cache = jax.block_until_ready(prefill(params, batch))
    t_prefill = time.time() - t0
    print(f"prefill[{b}x{s}] {t_prefill*1e3:.1f} ms")

    def sample(logits, key):
        if args.temperature <= 0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(key, logits / args.temperature, -1)

    tok = sample(logits, key)
    out_tokens = [np.asarray(tok)]
    lat = []
    for i in range(args.gen):
        t0 = time.time()
        logits, cache = jax.block_until_ready(
            decode(params, tok, cache, jnp.asarray(s + vis + i, jnp.int32)))
        lat.append(time.time() - t0)
        tok = sample(logits, jax.random.fold_in(key, i))
        out_tokens.append(np.asarray(tok))

    lat_ms = np.asarray(lat[1:]) * 1e3  # skip compile step
    print(f"decode: {len(lat)} steps, median {np.median(lat_ms):.2f} ms/tok, "
          f"p99 {np.percentile(lat_ms, 99):.2f} ms")
    gen = np.stack(out_tokens, axis=1)
    print(f"generated[{gen.shape[0]}x{gen.shape[1]}]: row0 = {gen[0][:16]}...")
    assert np.isfinite(lat_ms).all() and (gen >= 0).all()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
