"""Blockwise (flash-style) packed-segment attention in pure XLA.

Online-softmax attention computed chunk-by-chunk over the KV axis inside a
``lax.scan``: live memory is O(T · kv_chunk) instead of the O(T²) logits
tensor ``ops.basic.segment_attention`` materializes. Numerics are identical
(same fp32 accumulation; the online rescaling is exact).

Two roles:
- the memory-faithful proxy for the TPU splash kernel in AOT feasibility
  analysis (parallel/feasibility.py) — splash is Pallas/TPU-only, so
  lowering with the naive kernel would report a 16x-too-large activation
  footprint for long contexts;
- a portable long-context fallback on backends without Pallas (CPU mesh
  tests, interpret runs) and the building block for the ring-attention
  inner loop.

Compute is still O(T²) (every block pair is evaluated under mask — XLA has
no data-dependent block skipping); on real TPU the splash kernel is the
fast path. Reference role: flash-attn varlen (realhf/impl/model/modules/
attn.py) memory behavior.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


@functools.partial(
    jax.jit, static_argnames=("causal", "q_chunk", "kv_chunk")
)
def blockwise_segment_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    segment_ids: jnp.ndarray,  # [B, T]; 0 = padding
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    scale = d**-0.5
    cq = min(q_chunk, t)
    ck = min(kv_chunk, t)
    # chunk sizes must divide T (engine buckets are multiples of 256)
    while t % cq:
        cq //= 2
    while t % ck:
        ck //= 2
    nq, nk = t // cq, t // ck

    qg = (q.astype(jnp.float32) * scale).reshape(b, nq, cq, hkv, rep, d)
    kr = k.astype(jnp.float32).reshape(b, nk, ck, hkv, d)
    dv = v.shape[-1]  # latent attention's values are narrower
    vr = v.astype(jnp.float32).reshape(b, nk, ck, hkv, dv)
    seg_q = segment_ids.reshape(b, nq, cq)
    seg_k = segment_ids.reshape(b, nk, ck)
    qpos = jnp.arange(t).reshape(nq, cq)
    kpos = jnp.arange(t).reshape(nk, ck)

    def q_block(qi, args):
        qc, sq, qp = args  # [B, cq, Hkv, rep, D], [B, cq], [cq]

        def kv_step(carry, inp):
            acc, m, l = carry
            kc, vc, sk, kp = inp  # [B, ck, Hkv, D], ..., [B, ck], [ck]
            logits = jnp.einsum(
                "bqgrd,bkgd->bgrqk", qc, kc,
                preferred_element_type=jnp.float32,
            )  # [B, Hkv, rep, cq, ck]
            mask = (sq[:, :, None] == sk[:, None, :]) & (
                sq[:, :, None] > 0
            )
            if causal:
                mask = mask & (kp[None, None, :] <= qp[None, :, None])
            logits = jnp.where(
                mask[:, None, None, :, :], logits, NEG_INF
            )
            m_new = jnp.maximum(m, logits.max(-1))
            # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
            m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(logits - m_safe[..., None])
            p = jnp.where(mask[:, None, None, :, :], p, 0.0)
            corr = jnp.where(
                m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe)
            )
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p, vc,
                preferred_element_type=jnp.float32,
            )
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, hkv, rep, cq, dv), jnp.float32)
        m0 = jnp.full((b, hkv, rep, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, rep, cq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step, prevent_cse=False),
            (acc0, m0, l0),
            (
                kr.swapaxes(0, 1),
                vr.swapaxes(0, 1),
                seg_k.swapaxes(0, 1),
                kpos,
            ),
        )
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return qi, out  # [B, Hkv, rep, cq, D]

    _, outs = jax.lax.scan(
        jax.checkpoint(q_block, prevent_cse=False),
        0,
        (
            qg.swapaxes(0, 1),  # [nq, B, cq, Hkv, rep, D]
            seg_q.swapaxes(0, 1),
            qpos,
        ),
    )
    # outs: [nq, B, Hkv, rep, cq, D] -> [B, T, Hq, D]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, hq, dv)
    valid = (segment_ids > 0)[:, :, None, None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)
