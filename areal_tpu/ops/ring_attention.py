"""Ring attention + Ulysses all-to-all attention over the "seq" mesh axis.

Long-context sequence parallelism, first-class (the reference reaches long
context via Ulysses all-to-all — areal/utils/ulysses.py,
models/transformers/ulyssess_patch.py — and has NO ring attention;
SURVEY.md §2.5 marks it absent. Here both are native):

- **Ulysses** (`ulysses_segment_attention`): all-to-all converts the local
  [B, T/sp, H, D] layout to [B, T, H/sp, D], runs full-sequence attention on
  a head shard, and converts back. Communication: 2 all-to-alls per
  attention; heads must divide by sp.
- **Ring** (`ring_segment_attention`): K/V blocks rotate around the seq axis
  via `ppermute` while queries stay put; a streaming (online-softmax)
  accumulator merges each block's contribution. Communication overlaps with
  compute; no head-divisibility constraint and activation memory stays
  O(T/sp) — the long-context scaling path.

Both operate on PACKED streams (segment_ids carry sequence boundaries) and
are written as per-shard functions to be wrapped in `shard_map` (see
`make_sharded_attention`), composing with the (data, fsdp, seq, tensor)
mesh: XLA still shards heads over "tensor" inside the shard_map body.

`attention_fn` is the one place that turns a resolved attention name
("flash" | "ring" | "ulysses" | "xla"), a mesh and the tiles' cap into the
callable `transformer.apply` takes: each kernel's mesh form lives here,
beside the kernels, and a train engine only asks for it.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.ops.basic import segment_attention

NEG_INF = -2.3819763e38


def _block_attend(
    q, k, v, seg_q, seg_k, q_pos, kv_pos, causal, kv_chunk=1024
):
    """Unnormalized block attention for online-softmax merging: returns
    (scores_max [B,H,tq], exp-sum [B,H,tq], weighted values [B,tq,H,D]).

    Memory-bounded: the KV block is scanned in ``kv_chunk`` slices with a
    running (m, l, o) — the [t_local, t_shard] logits tensor the round-3
    version materialized never exists, and GQA uses the grouped einsum
    instead of repeating KV heads (the flash-kernel memory profile, in
    XLA, inside the ring step).

    NOTE: the inner scan mirrors ops/blockwise_attention.kv_step but
    returns UNNORMALIZED (m, l, o) with -1e30 max-clamping so blocks can
    merge across ring steps (blockwise normalizes + zero-masks at the
    end, which would lose the merge state). A numerics change in either
    must be mirrored; tests/test_ring_attention.py::
    test_block_attend_matches_blockwise pins them together."""
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = d**-0.5
    ck = min(kv_chunk, tk)
    while tk % ck:
        ck //= 2
    nk = tk // ck
    qg = (q.astype(jnp.float32) * scale).reshape(b, tq, hkv, rep, d)
    kr = k.astype(jnp.float32).reshape(b, nk, ck, hkv, d)
    vr = v.astype(jnp.float32).reshape(b, nk, ck, hkv, d)
    skr = seg_k.reshape(b, nk, ck)
    kpr = kv_pos.reshape(nk, ck)

    def step(carry, inp):
        m, l, o = carry
        kc, vc, sk, kp = inp
        s = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qg, kc,
            preferred_element_type=jnp.float32,
        )  # [B, Hkv, rep, tq, ck]
        mask = (seg_q[:, :, None] == sk[:, None, :]) & (
            seg_q[:, :, None] > 0
        )
        if causal:
            mask = mask & (kp[None, None, :] <= q_pos[None, :, None])
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        m_safe = jnp.maximum(m_new, -1e30)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[:, None, None, :, :], p, 0.0)
        corr = jnp.exp(jnp.maximum(m, -1e30) - m_safe)
        l = l * corr + p.sum(-1)
        o = o * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vc,
            preferred_element_type=jnp.float32,
        )
        return (jnp.maximum(m_new, -1e30), l, o), None

    m0 = jnp.full((b, hkv, rep, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, tq), jnp.float32)
    o0 = jnp.zeros((b, hkv, rep, tq, d), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False),
        (m0, l0, o0),
        (
            kr.swapaxes(0, 1),
            vr.swapaxes(0, 1),
            skr.swapaxes(0, 1),
            kpr,
        ),
    )
    # head h = g * rep + r, matching the [B,tq,Hq,D] reshape convention
    m_flat = m.reshape(b, hq, tq)
    l_flat = l.reshape(b, hq, tq)
    o_flat = o.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, d)
    return m_flat, l_flat, o_flat


def ring_segment_attention(
    q: jnp.ndarray,  # [B, t_local, Hq, D]
    k: jnp.ndarray,  # [B, t_local, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, t_local]
    axis_name: str = "seq",
    causal: bool = True,
) -> jnp.ndarray:
    """Per-shard ring attention body (call inside shard_map)."""
    sp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t, hq, d = q.shape
    q_pos = idx * t + jnp.arange(t)  # global packed positions

    # accumulators (online softmax over ring steps)
    m_acc = jnp.full((b, hq, t), -1e30, jnp.float32)
    l_acc = jnp.zeros((b, hq, t), jnp.float32)
    o_acc = jnp.zeros((b, t, hq, d), jnp.float32)

    perm = [(i, (i - 1) % sp) for i in range(sp)]  # rotate blocks leftward

    def merge(carry, block):
        m_acc, l_acc, o_acc = carry
        m_blk, l_blk, o_blk = block
        m_new = jnp.maximum(m_acc, m_blk)
        a = jnp.exp(m_acc - m_new)
        bfac = jnp.exp(m_blk - m_new)
        l_new = l_acc * a + l_blk * bfac
        o_new = (
            o_acc * a.transpose(0, 2, 1)[..., None]
            + o_blk * bfac.transpose(0, 2, 1)[..., None]
        )
        return m_new, l_new, o_new

    k_cur, v_cur, seg_cur = k, v, segment_ids
    src = idx
    for step in range(sp):
        kv_pos = src * t + jnp.arange(t)
        blk = _block_attend(
            q, k_cur, v_cur, segment_ids, seg_cur, q_pos, kv_pos, causal
        )
        m_acc, l_acc, o_acc = merge((m_acc, l_acc, o_acc), blk)
        if step + 1 < sp:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            seg_cur = jax.lax.ppermute(seg_cur, axis_name, perm)
            src = (src + 1) % sp
    out = o_acc / jnp.maximum(l_acc, 1e-30).transpose(0, 2, 1)[..., None]
    valid_q = (segment_ids > 0)[:, :, None, None]
    return jnp.where(valid_q, out, 0.0).astype(q.dtype)


def ulysses_segment_attention(
    q: jnp.ndarray,  # [B, t_local, Hq, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, t_local]
    axis_name: str = "seq",
    causal: bool = True,
) -> jnp.ndarray:
    """Per-shard Ulysses body: all-to-all seq→heads, attend, all-to-all back
    (reference areal/utils/ulysses.py:45-214 `SeqAllToAll`/gather-scatter,
    expressed as native lax.all_to_all instead of torch autograd functions)."""
    sp = jax.lax.psum(1, axis_name)
    hq, hkv = q.shape[2], k.shape[2]
    if hkv < sp:  # repeat KV heads so each shard owns >= 1 (reference
        rep = sp // hkv  # ulyssess_patch.py:43-45)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, t, H, D] → gather seq, scatter heads → [B, T, H/sp, D]
    def a2a_fwd(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def a2a_bwd(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    seg_full = jax.lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
    if qg.shape[1] >= 4096:
        # long context: bound attention memory to O(T·chunk) — the naive
        # kernel's [T, T] logits would dominate the shard's HBM
        from areal_tpu.ops.blockwise_attention import (
            blockwise_segment_attention,
        )

        out = blockwise_segment_attention(qg, kg, vg, seg_full, causal=causal)
    else:
        out = segment_attention(qg, kg, vg, seg_full, causal=causal)
    return a2a_bwd(out)


def make_sharded_attention(
    mesh: Mesh,
    impl: str = "ring",
    causal: bool = True,
):
    """Wrap a per-shard attention body in shard_map for the training stack.

    Returns ``attend(q, k, v, segment_ids) -> out`` taking GLOBAL arrays
    laid out [B, T, H, D] with B over (data, fsdp), T over seq, H over
    tensor — the transformer's activation sharding.
    """
    body = (
        ring_segment_attention if impl == "ring" else ulysses_segment_attention
    )
    fn = functools.partial(body, axis_name="seq", causal=causal)
    qkv_spec = P(("data", "fsdp"), "seq", "tensor", None)
    seg_spec = P(("data", "fsdp"), "seq")

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    def attend(q, k, v, segment_ids):
        return fn(q, k, v, segment_ids)

    return attend


def _bd_attention_fn(mesh: Mesh, block_cap: int):
    """``attend(q, k, v, segment_ids, blocks, streams)`` of a block-diffusion
    policy's rows (``ops.flash.flash_bd_attention``): each device runs its
    own rows (and heads), as the splash kernel's mesh form does."""
    from areal_tpu.ops.flash import flash_bd_attention

    attend = functools.partial(flash_bd_attention, block_cap=block_cap)
    if mesh.size == 1:
        return attend
    qkv_spec = P(("data", "fsdp"), None, "tensor", None)
    row_spec = P(("data", "fsdp"), None)
    return jax.shard_map(
        attend, mesh=mesh,
        in_specs=(qkv_spec,) * 3 + (row_spec,) * 3, out_specs=qkv_spec,
        check_vma=False,
    )


def attention_fn(impl: str, mesh: Mesh, block_cap: int = 0, topk: int = 0):
    """The attention callable ``attend(q, k, v, segment_ids)`` for a
    resolved name (``ops.flash.resolve_attn_impl``) on ``mesh``: "flash"
    (the Pallas splash kernel, TPU-only, its tiles no larger than
    ``block_cap``, what ``ops.flash.probe_block_size`` found to run, 0 =
    uncapped; it also takes ``layer_window=`` from a window-attention layer),
    "ring"/"ulysses" (the explicit sequence-parallel ``shard_map``),
    "sparse_topk" (a model with an indexer: ``topk`` selected keys a query,
    ``ops.sparse_attention.make_attend``, which also takes the indexer's
    arrays), "block_diffusion" (a model that generates by diffusion over
    blocks: ``attend(q, k, v, segment_ids, blocks, streams)``), or None for
    "xla", the XLA kernel with GSPMD auto-sharding."""
    if impl == "xla":
        return None
    if impl == "sparse_topk":
        from areal_tpu.ops.sparse_attention import make_attend

        return make_attend(mesh, topk)
    if impl == "block_diffusion":
        return _bd_attention_fn(mesh, block_cap)
    if impl != "flash":
        return make_sharded_attention(mesh, impl=impl)
    from areal_tpu.ops.flash import flash_segment_attention

    attend = functools.partial(flash_segment_attention, block_cap=block_cap)
    if mesh.size == 1:
        # (a window-attention layer calls it with ``layer_window=``)
        return attend
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    # shard_map"): each device runs it on its own rows (and heads).
    # Attention never crosses a row, so this is exact; only a sequence
    # split would need a cross-device kernel.
    if mesh.shape["seq"] > 1:
        raise ValueError(
            "attn_impl='flash' has no sequence-parallel form: use "
            "'ring' or 'ulysses' with seq_parallel_size > 1"
        )
    qkv_spec = P(("data", "fsdp"), None, "tensor", None)

    def sharded(q, k, v, segment_ids, layer_window: int = 0):
        return jax.shard_map(
            functools.partial(attend, layer_window=layer_window),
            mesh=mesh,
            in_specs=(
                qkv_spec, qkv_spec, qkv_spec, P(("data", "fsdp"), None)
            ),
            out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v, segment_ids)

    return sharded
