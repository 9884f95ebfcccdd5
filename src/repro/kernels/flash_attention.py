"""Pallas TPU flash attention — the designed fix for the score-traffic
memory term (§Perf cells B/tinyllama and the prefill cells).

The pure-XLA flash path (models/attention.py) materializes each score block
[Sq, chunk] to HBM between the QK and PV dots; this kernel keeps the block
in VMEM with the canonical TPU pattern:

  grid = (batch*heads, q_blocks, kv_blocks)   # kv fastest, sequential
  scratch (VMEM, carried across kv iterations): acc [BQ,hd] f32, m/l [BQ]

Causality is handled at two levels: whole kv-blocks strictly above the
diagonal are skipped with ``pl.when`` (no FLOPs, no traffic — the kernel
analogue of the causal q-block skipping in the XLA path), and the diagonal
block applies the element mask.

HBM traffic: q, k, v read once per (q-block, kv-block) pair in the causal
prefix, o written once — no score bytes, vs O(S^2 H) f32 score bytes in the
XLA lowering.  Validated against ``ref.flash_attention_ref`` in interpret
mode (CPU container; TPU is the target).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, causal: bool, scale: float, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip kv blocks strictly above the diagonal
    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)          # [BQ, hd]
        k = k_ref[0].astype(jnp.float32)          # [BK, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale   # [BQ, BK]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q, k, v: [B, H, S, hd] (KV heads pre-expanded to H).  -> [B, H, S, hd].

    Blocks default to 128x128 (MXU-aligned); the whole working set per grid
    step is q/k/v/o blocks + f32 accumulators ~ (3*bk + 2*bq)*hd*4 bytes +
    bq*bk*4 — well inside VMEM for hd <= 256.
    """
    if interpret is None:
        interpret = default_interpret()
    b, h, sq, hd = q.shape
    skv = k.shape[2]
    bq_ = min(bq, sq)
    bk_ = min(bk, skv)
    if sq % bq_ or skv % bk_:
        raise ValueError(f"seq lens ({sq},{skv}) must divide blocks "
                         f"({bq_},{bk_})")
    n_q, n_kv = sq // bq_, skv // bk_
    qf = q.reshape(b * h, sq, hd)
    kf = k.reshape(b * h, skv, hd)
    vf = v.reshape(b * h, skv, hd)

    kernel = functools.partial(_kernel, bq=bq_, bk=bk_, causal=causal,
                               scale=1.0 / np.sqrt(hd), n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq_, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk_, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk_, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, hd), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * skv * hd * (0.5 if causal else 1.0)),
            bytes_accessed=int(qf.size + kf.size + vf.size + qf.size) * 2,
            transcendentals=int(b * h * sq * skv),
        ),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd)
