"""Jitted prefill / decode programs over the paged KV-block pool.

The TPU-native core of the generation engine (role of SGLang's model runner
behind the reference's HTTP API, driven at areal/engine/sglang_remote.py).
Compiled programs over the page pool (inference/cache.py layout):

- ``prefill_batch``: N prompt suffixes as ONE batched [N, Tp] forward.
  Each row resumes from ``offset`` tokens already cached in its pages
  (prefix reuse — the radix-cache analog): attention = gathered page
  window [0, offset) ++ in-flight suffix (causal), and the suffix K/V for
  all layers lands in the pool with one donated scatter after the layer
  scan — the pool itself never rides the scan (a mutated multi-GB scan
  carry costs a full copy per step on TPU; measured, not folklore).
- ``decode_multi``: `steps` fused decode+sample iterations in ONE dispatch
  with device-side stop handling. The pool is READ-ONLY inside the step
  loop; new tokens' K/V accumulate in a small [L, S, T] chunk buffer that
  the paged-attention kernel folds into the same online softmax, and one
  bulk scatter merges the chunk into the pool at the end.
- ``decode_step``: single step without sampling (tests / TP fallback).
- ``copy_pages``: page-granular pool copy (GRPO sibling partial-tail pages;
  full prompt pages are *shared* host-side, no copy).

Attention backend is static per call: "kernel" (Pallas manual-DMA flash,
TPU) or "jnp" (gather fallback — CPU tests and tensor-parallel serving).
Sampling (temperature / top-k / top-p / greedy, per slot) runs on device
with a static ``topk_bound``; fp32 softmax/logits throughout.
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig
from areal_tpu.models.transformer import Params
from areal_tpu.ops.basic import (
    apply_rope,
    hidden_act_fn,
    rms_norm,
    rope_frequencies,
)
from areal_tpu.ops.paged_attention import (
    layout_from_pool,
    paged_decode_attention,
    paged_decode_attention_jnp,
    unpacked_view,
)
from areal_tpu.utils.tracing import device_scope

NEG_INF = -2.3819763e38


def _project_qkv(cfg: ModelConfig, lp: Params, h: jnp.ndarray):
    """h [..., D] → q [..., Hq, Dh], k/v [..., Hkv, Dh] (pre-rope)."""
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm == "projection":  # olmoe: before the split into heads
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    q = q.reshape(h.shape[:-1] + (cfg.num_heads, cfg.head_dim))
    k = k.reshape(h.shape[:-1] + (cfg.num_kv_heads, cfg.head_dim))
    v = v.reshape(h.shape[:-1] + (cfg.num_kv_heads, cfg.head_dim))
    if cfg.qk_norm == "head":  # qwen3: each head by itself
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


@device_scope("mlp")
def _mlp(
    cfg: ModelConfig,
    lp: Params,
    h: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,  # [...] matching h[..., 0]
) -> jnp.ndarray:
    if cfg.is_moe:
        from areal_tpu.ops.moe import moe_ffn_from_params

        flat = h.reshape(1, -1, h.shape[-1])
        # padding / inactive-slot tokens must not consume expert capacity
        vflat = None if valid is None else valid.reshape(1, -1)
        out, _ = moe_ffn_from_params(cfg, lp, flat, valid=vflat)
        return out.reshape(h.shape)
    act = hidden_act_fn(cfg.hidden_act)
    return (act(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _layer_pre(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """Input norm + QKV projection — the front half of the per-layer
    sandwich every forward path (prefill, decode, spec-verify) shares.
    One copy with `_layer_post` so an architecture change (a new norm
    variant, QK-norm tweak, ...) cannot silently drift between the
    three forwards and break their bit-exactness contract; everything
    between the halves (rope positions, KV staging, the attention core)
    is genuinely path-specific."""
    h = rms_norm(
        x, lp["input_norm"], cfg.rms_norm_eps,
        add_unit_offset=cfg.norm_add_unit_offset,
    )
    return _project_qkv(cfg, lp, h)


def _layer_post(
    cfg: ModelConfig,
    lp: Params,
    x: jnp.ndarray,
    attn: jnp.ndarray,  # [..., q_dim] already in x.dtype
    valid: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """Output-projection residual + post-attn norm + MLP residual — the
    back half of the shared per-layer sandwich (see `_layer_pre`)."""
    x = x + attn @ lp["wo"]
    h2 = rms_norm(
        x, lp["post_attn_norm"], cfg.rms_norm_eps,
        add_unit_offset=cfg.norm_add_unit_offset,
    )
    return x + _mlp(cfg, lp, h2, valid=valid)


@device_scope("lm_head")
def _final_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray):
    x = rms_norm(
        x, params["final_norm"], cfg.rms_norm_eps,
        add_unit_offset=cfg.norm_add_unit_offset,
    )
    head = (
        params["embedding"].T if cfg.tie_word_embeddings else params["lm_head"]
    )
    return x.astype(jnp.float32) @ head.astype(jnp.float32)


def _row_flat(
    tables: jnp.ndarray,  # [N, PPS] int32 logical page ids
    row_positions: jnp.ndarray,  # [N, R] int32 TOKEN position of row start
    page_size: int,
    pack: int,
    num_pages: int,
    valid: jnp.ndarray,  # [N, R] bool
) -> jnp.ndarray:
    """Pool-row index for row-granular access. The pool's unit of access
    is one 128-lane row = ``pack`` consecutive tokens (any view with a
    trailing dim < 128 forces a full relaid copy of the pool on TPU —
    measured as a 2x HBM blowup — so every jnp read/write goes through
    [*, pack*D] rows). Invalid rows map past the pool (scatter drop)."""
    prow = page_size // pack
    page = jnp.take_along_axis(
        tables,
        jnp.clip(row_positions // page_size, 0, tables.shape[1] - 1),
        axis=1,
    )
    flat = page * prow + (row_positions % page_size) // pack
    return jnp.where(valid, flat, num_pages * prow)


def _rows_view(pool: jnp.ndarray) -> jnp.ndarray:
    """[L, Hkv, NP, BS//f, f*D] → [L, Hkv, NP*(BS//f), f*D] (free)."""
    nl, hkv, np_, prow, fd = pool.shape
    return pool.reshape(nl, hkv, np_ * prow, fd)


def init_last_rows(
    num_layers: int, num_slots: int, num_kv_heads: int, fd: int, dtype
) -> Dict[str, jnp.ndarray]:
    """Per-slot copy of the last (possibly partial) pool row each sequence
    wrote. Merges consult it instead of READING the pool: on this backend
    any computation that both reads and writes a buffer pays a full copy
    of it, and gathers/scatters with index arrays serialize per index —
    write-only DUS chains are the only fast pool mutation."""
    shape = (num_layers, num_slots, num_kv_heads, fd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@functools.partial(
    jax.jit, static_argnames=("num_pages", "prow", "pack", "merge")
)
def assemble_rows(
    tables: jnp.ndarray,  # [N, PPS]
    pos0: jnp.ndarray,  # [N] absolute start position of kv[…, 0]
    counts: jnp.ndarray,  # [N] valid tokens per row
    kbuf: jnp.ndarray,  # [L, N, T, Hkv, D] token-order new K
    vbuf: jnp.ndarray,
    last_rows: Dict[str, jnp.ndarray],  # [L, S?, Hkv, FD] (rows N used)
    slot_ids: jnp.ndarray,  # [N] engine slot of each row (last_rows index)
    num_pages: int,
    prow: int,
    pack: int,
    merge: bool = False,
):
    """Pack token-order K/V into full 128-lane pool rows.

    ``merge``: the head-merged pool view — K/V arrive [L, N, T, Hkv, D]
    and are reshaped to [L, N, T, 1, Hkv*D] INSIDE this jit (token-major,
    a free view; doing it eagerly in merge_tokens cost one stray
    eager-op compile per shape that dodged the dispatch-scope compile
    attribution).

    Returns (dest [N*NR] flat row ids with row 0 of the pool as the drop
    target for invalid rows, kvals/vvals [N*NR, L, Hkv, FD], new
    last_rows {k,v} [L, N, Hkv, FD]). Pure compute — the pool itself is
    neither read nor written here (see init_last_rows)."""
    if merge:
        nl_, n_, t_, hkv_, d_ = kbuf.shape
        kbuf = kbuf.reshape(nl_, n_, t_, 1, hkv_ * d_)
        vbuf = vbuf.reshape(nl_, n_, t_, 1, hkv_ * d_)
    nl, n, t, hkv, d = kbuf.shape
    f = pack
    fd = f * d
    bs = prow * f
    kv_dtype = kbuf.dtype
    nr = t // f + 2  # worst-case rows touched (alignment + remainder)
    a = pos0 % f  # [N] first-row misalignment
    j = jnp.arange(nr, dtype=jnp.int32)[None, :]  # [1, NR]

    def shifted_stride(buf, start: int):
        """buf[:, :, start::f] padded/truncated to NR rows along axis 2.
        ``start`` may be negative (leading zero row). Pure strided slices
        + pads — a generic gather here was measured ~150x slower."""
        if start < 0:
            sl = buf[:, :, f + start :: f]
            sl = jnp.pad(sl, ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
        else:
            sl = buf[:, :, start :: f]
        rows = sl.shape[2]
        if rows < nr:
            sl = jnp.pad(
                sl, ((0, 0), (0, 0), (0, nr - rows), (0, 0), (0, 0))
            )
        return sl[:, :, :nr]

    def assemble(buf, last):  # buf [L,N,T,Hkv,D], last [L,N,Hkv,FD]
        halves = []
        for g in range(f):
            tg = j * f + g - a[:, None]  # [N, NR]
            valid = (tg >= 0) & (tg < counts[:, None])
            gathered = shifted_stride(buf, g)  # a == 0
            for aa in range(1, f):
                cand = shifted_stride(buf, g - aa)
                pick = (a == aa)[None, :, None, None, None]
                gathered = jnp.where(pick, cand, gathered)
            # first-row halves before pos0 come from the slot's remembered
            # last partial row (NOT a pool read)
            keep_old = (j == 0) & (g < a[:, None]) & (counts[:, None] > 0)
            old = last[:, :, None, :, g * d : (g + 1) * d]  # [L,N,1,Hkv,D]
            val = jnp.where(
                valid[None, :, :, None, None],
                gathered,
                jnp.where(
                    keep_old[None, :, :, None, None],
                    jnp.broadcast_to(old, gathered.shape),
                    jnp.zeros((), kv_dtype),
                ),
            )
            halves.append(val.astype(kv_dtype))
        # [L, N, NR, Hkv, f*D] — lane order g*D:(g+1)*D = token row*f+g
        return jnp.concatenate(halves, axis=-1)

    last_k = jnp.take(last_rows["k"], slot_ids, axis=1)  # [L, N, Hkv, FD]
    last_v = jnp.take(last_rows["v"], slot_ids, axis=1)
    kvals = assemble(kbuf, last_k)
    vvals = assemble(vbuf, last_v)
    row_pos = (pos0 - a)[:, None] + j * f  # [N, NR]
    any_valid = (
        ((j + 1) * f - a[:, None] > 0)
        & (j * f - a[:, None] < counts[:, None])
        & (counts[:, None] > 0)
    )
    dest = _row_flat(tables, row_pos, bs, f, num_pages, any_valid)
    # invalid rows are redirected to row 0 — the engine RESERVES page 0 as
    # a trash page (DUS clamps out-of-range starts, which would corrupt a
    # real page)
    dest = jnp.where(any_valid, dest, 0).reshape(-1)
    kw = kvals.transpose(1, 2, 0, 3, 4).reshape(n * nr, nl, hkv, fd)
    vw = vvals.transpose(1, 2, 0, 3, 4).reshape(n * nr, nl, hkv, fd)
    # new last-row per sequence: the row containing token pos0+counts-1
    # (selected by one-hot reduce — index gathers serialize on TPU)
    last_j = jnp.clip((a + counts - 1) // f, 0, nr - 1)  # [N]
    onehot = (j == last_j[:, None]).astype(kvals.dtype)  # [N, NR]
    sel_k = jnp.einsum("lnrhf,nr->lnhf", kvals, onehot)
    sel_v = jnp.einsum("lnrhf,nr->lnhf", vvals, onehot)
    wrote = (counts > 0)[None, :, None, None]
    new_last = {
        "k": jnp.where(wrote, sel_k, last_k).astype(kv_dtype),
        "v": jnp.where(wrote, sel_v, last_v).astype(kv_dtype),
    }
    return dest, kw, vw, new_last


@functools.partial(jax.jit, donate_argnames=("cache",))
@device_scope("kv_write")
def write_rows(
    cache: Dict[str, jnp.ndarray],
    dest: jnp.ndarray,  # [M] flat row ids (0 = engine trash page)
    kvals: jnp.ndarray,  # [M, L, Hkv, FD]
    vvals: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """WRITE-ONLY pool update: a scan of per-row dynamic_update_slice ops
    on the donated pool — the only pool-mutation shape this backend runs
    in place (index-array scatters serialize per index; any read of the
    pool in the same dispatch forces a full copy)."""
    krows = _rows_view(cache["k"])
    vrows = _rows_view(cache["v"])

    def body(carry, xs):
        kr, vr = carry
        d_, kv_, vv_ = xs  # kv_ [L, Hkv, FD]
        kr = jax.lax.dynamic_update_slice(
            kr, kv_[:, :, None, :], (0, 0, d_, 0)
        )
        vr = jax.lax.dynamic_update_slice(
            vr, vv_[:, :, None, :], (0, 0, d_, 0)
        )
        return (kr, vr), None

    (krows, vrows), _ = jax.lax.scan(body, (krows, vrows), (dest, kvals, vvals))
    return {
        "k": krows.reshape(cache["k"].shape),
        "v": vrows.reshape(cache["v"].shape),
    }


def merge_tokens(
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,
    pos0: jnp.ndarray,
    counts: jnp.ndarray,
    kbuf: jnp.ndarray,  # [L, N, T, Hkv, D]
    vbuf: jnp.ndarray,
    last_rows: Optional[Dict[str, jnp.ndarray]] = None,
    slot_ids: Optional[jnp.ndarray] = None,
):
    """Two-dispatch merge: assemble rows (pure), then write-only DUS scan.
    Returns (cache, new_last_rows [L, N, Hkv_pool, LANE]).

    A head-merged pool (hkv dim 1, all heads per 128-lane row) reuses the
    same assembly machinery on kbuf viewed as [L, N, T, 1, Hkv*D] — a
    free reshape, since [T, Hkv, D] is token-major — with the pack factor
    counted in tokens-per-row."""
    nl, n, t, hkv, d = kbuf.shape
    _, hkv_pool, num_pages, prow, fd = cache["k"].shape
    merged, f = layout_from_pool(cache["k"].shape, hkv, d)
    if merged:
        hkv = 1
    if last_rows is None:
        last_rows = init_last_rows(nl, n, hkv, fd, kbuf.dtype)
    if slot_ids is None:
        slot_ids = jnp.arange(n, dtype=jnp.int32)
    # the merged-layout buffer reshape happens INSIDE assemble_rows
    # (static `merge`): an eager reshape here would compile one stray
    # program per buffer shape outside the dispatch-scope attribution
    dest, kw, vw, new_last = assemble_rows(
        tables, pos0, counts, kbuf, vbuf, last_rows, slot_ids,
        num_pages=num_pages, prow=prow, pack=f, merge=merged,
    )
    cache = write_rows(cache, dest, kw, vw)
    return cache, new_last


# ---------------------------------------------------------------------------
# Prefill (batched, prefix-aware)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "prefix_bound"),
)
def prefill_forward(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,  # [N, Tp] int32 suffix tokens, padded to bucket
    offsets: jnp.ndarray,  # [N] int32 tokens already cached (prefix reuse)
    true_lens: jnp.ndarray,  # [N] int32 suffix lengths (0 = padding row)
    tables: jnp.ndarray,  # [N, PPS] logical pages covering offset+Tp
    prefix_bound: int = 0,  # static: gathered window >= max(offsets), 0 = none
    embeds: Optional[jnp.ndarray] = None,  # [N, Tp, D] input embeddings
    pos3: Optional[jnp.ndarray] = None,  # [N, Tp, 3] mrope positions
):
    """One batched READ-ONLY forward over N prompt suffixes; returns
    (logits [N, V] fp32, k_sfx, v_sfx [L, N, Tp, Hkv, D]) — the caller
    merges the suffix K/V with the separate write-only dispatch
    (merge_tokens), keeping this dispatch free of pool writes (a
    read+write dispatch pays a full pool copy on this backend).

    Host contract: tables cover ceil((offset+Tp)/BS) pages per real row;
    ``prefix_bound`` >= every row's offset; offsets are POOL-ROW-aligned
    (page-aligned for full-page prefix claims; mid-page for the radix
    cache's COW claims — the per-token window masks below are exact for
    any offset, and row alignment is what the MERGE needs, since
    assemble_rows consults last_rows only for mid-row starts).

    This one entry point is ALSO the chunk-resume prefill (r15 chunked
    prefill): a continuation chunk is dispatched with ``offsets`` = the
    committed page-aligned prefix the engine re-claimed from the cache
    and ``true_lens`` = the chunk width — identical in shape and
    numerics to a radix-claim resume, which is what makes chunked
    greedy streams bit-identical to unchunked ones. Chunk-capped rows
    ride a wave slotless (slot id = max_num_seqs): their last_rows
    gather clips harmlessly because page-aligned ends mean the first
    row of the NEXT chunk is never mid-row.
    """
    n, tp = tokens.shape
    d = cfg.head_dim
    nl, hkv_pool, num_pages, prow, fd = cache["k"].shape
    hkv = cfg.num_kv_heads
    merged, f = layout_from_pool(cache["k"].shape, hkv, d)
    page_size = prow * f
    mb0 = prefix_bound
    sidx = jnp.arange(tp, dtype=jnp.int32)[None, :]
    pos = offsets[:, None] + sidx  # [N, Tp] absolute positions
    valid_q = sidx < true_lens[:, None]
    cos, sin = rope_frequencies(
        cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta
    )
    if embeds is not None:
        # VLM path: image-token embeddings were spliced at admission
        # (mm_prompt_embeds applies any embedding scaling itself; scaling
        # here would double-scale text rows and wrongly scale vision rows)
        x = embeds.astype(params["embedding"].dtype)
    else:
        x = params["embedding"][tokens]  # [N, Tp, D]
        if cfg.scale_embeddings:  # gemma: sqrt(d)-scaled embeddings
            x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)

    def _rope(t):  # [N, Tp, H, D]
        if pos3 is not None and cfg.mrope_sections:
            from areal_tpu.ops.basic import apply_mrope

            return apply_mrope(t, pos3, cos, sin, cfg.mrope_sections)
        return apply_rope(t, pos, cos, sin)

    scale = cfg.head_dim**-0.5
    g, rep = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads

    krows_all = _rows_view(cache["k"])  # [L, Hkv_pool, NP*prow, FD]
    vrows_all = _rows_view(cache["v"])

    if mb0 > 0:
        npg = -(-mb0 // page_size)  # window pages (covers every offset)
        wr = npg * prow  # window rows
        # page-run gather: one dynamic_slice per (row, page) — index-array
        # gathers serialize per index on TPU, DS runs at copy speed
        page_starts = (
            jnp.clip(tables[:, :npg], 0, num_pages - 1) * prow
        ).reshape(-1)  # [N*npg]

        def fetch(carry, st):
            win_k = jax.lax.dynamic_slice(
                krows_all, (0, 0, st, 0), (nl, hkv_pool, prow, fd)
            )
            win_v = jax.lax.dynamic_slice(
                vrows_all, (0, 0, st, 0), (nl, hkv_pool, prow, fd)
            )
            return carry, (win_k, win_v)

        _, (wk_pages, wv_pages) = jax.lax.scan(fetch, 0, page_starts)
        # [N*npg, L, Hkv_pool, prow, FD] → [L, Hkv_pool, N, WR, FD]
        def arrange(w):
            w = w.reshape(n, npg, nl, hkv_pool, prow, fd)
            return w.transpose(2, 3, 0, 1, 4, 5).reshape(
                nl, hkv_pool, n, wr, fd
            )

        win_k_all = arrange(wk_pages)
        win_v_all = arrange(wv_pages)
        fw = f  # lane halves per window row (token stride)
        if merged:
            # unpack the merged rows into per-head single-token rows ONCE
            # (prefix windows are an admission-time path, not decode-hot):
            # [L, 1, N, WR, tpr*Hkv*D] -> [L, Hkv, N, WR*tpr, D]
            def unmerge(w):
                y = w.reshape(nl, n, wr, f, hkv, d)
                return y.transpose(0, 4, 1, 2, 3, 5).reshape(
                    nl, hkv, n, wr * f, d
                )

            win_k_all = unmerge(win_k_all)
            win_v_all = unmerge(win_v_all)
            wr = wr * f
            fw = 1
        rpos = jnp.arange(wr, dtype=jnp.int32)[None, :] * fw  # [1, WR]
        # per-half key masks: token at (row r, half h) has position r*fw+h
        half_masks = [
            (rpos + h < offsets[:, None])[:, None, None, None]  # [N,1,1,1,WR]
            for h in range(fw)
        ]

    # causal within the in-flight suffix
    suffix_mask = (sidx[:, :, None] >= sidx[:, None, :]) & valid_q[:, None, :]

    def layer(carry, xs):
        x = carry
        lp, li = xs
        q, k, v = _layer_pre(cfg, lp, x)  # [N, Tp, H*, Dh]
        q = _rope(q)
        k = _rope(k)
        kz = jnp.where(valid_q[..., None, None], k, 0)
        vz = jnp.where(valid_q[..., None, None], v, 0)
        qg = q.reshape(n, tp, g, rep, cfg.head_dim)
        # suffix-vs-suffix scores (causal)
        sc_sfx = (
            jnp.einsum(
                "nqgrd,nkgd->ngrqk", qg, kz,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        sc_sfx = jnp.where(suffix_mask[:, None, None], sc_sfx, NEG_INF)
        if mb0 > 0:
            # pre-gathered page windows (full 128-lane rows), lane-half
            # slices — key order is [half0 rows..., half1 rows...,
            # suffix], which softmax doesn't care about
            win_k = jax.lax.dynamic_index_in_dim(
                win_k_all, li, 0, keepdims=False
            )  # [Hkv, N, WR, FD]
            win_v = jax.lax.dynamic_index_in_dim(
                win_v_all, li, 0, keepdims=False
            )
            scs = []
            vhs = []
            for hh in range(fw):
                wk = win_k[..., hh * d : (hh + 1) * d]  # [Hkv, N, WR, D]
                vhs.append(win_v[..., hh * d : (hh + 1) * d])
                sc_h = (
                    jnp.einsum(
                        "nqgrd,gnkd->ngrqk", qg, wk,
                        preferred_element_type=jnp.float32,
                    )
                    * scale
                )
                scs.append(jnp.where(half_masks[hh], sc_h, NEG_INF))
            # segment layout [half0 .. halfN, suffix] — the probs slicing
            # below depends on this order
            sc = jnp.concatenate(scs + [sc_sfx], axis=-1)
        else:
            sc = sc_sfx
        probs = jax.nn.softmax(sc, axis=-1)
        if mb0 > 0:
            wr_n = vhs[0].shape[2]
            attn = jnp.einsum(
                "ngrqk,nkgd->nqgrd",
                probs[..., fw * wr_n :].astype(vz.dtype), vz,
                preferred_element_type=jnp.float32,
            )
            for hh in range(fw):
                attn = attn + jnp.einsum(
                    "ngrqk,gnkd->nqgrd",
                    probs[..., hh * wr_n : (hh + 1) * wr_n].astype(
                        vhs[hh].dtype
                    ),
                    vhs[hh],
                    preferred_element_type=jnp.float32,
                )
        else:
            attn = jnp.einsum(
                "ngrqk,nkgd->nqgrd", probs.astype(vz.dtype), vz,
                preferred_element_type=jnp.float32,
            )
        attn = attn.astype(x.dtype).reshape(n, tp, cfg.q_dim)
        x = _layer_post(cfg, lp, x, attn, valid_q)
        kv_dtype = cache["k"].dtype
        return x, (kz.astype(kv_dtype), vz.astype(kv_dtype))

    x, (k_sfx, v_sfx) = jax.lax.scan(
        layer, x, (params["layers"], jnp.arange(nl, dtype=jnp.int32))
    )
    last = x[jnp.arange(n), jnp.maximum(true_lens - 1, 0)]  # [N, D]
    logits = _final_logits(params, cfg, last)  # [N, V] fp32
    return logits, k_sfx, v_sfx


def prefill_batch(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,
    offsets: jnp.ndarray,
    true_lens: jnp.ndarray,
    tables: jnp.ndarray,
    prefix_bound: int = 0,
    last_rows: Optional[Dict[str, jnp.ndarray]] = None,
    slot_ids: Optional[jnp.ndarray] = None,
    embeds: Optional[jnp.ndarray] = None,
    pos3: Optional[jnp.ndarray] = None,
):
    """Read-only forward + write-only merge (two dispatches).
    Returns (cache, logits, new_last_rows [L, N, Hkv, FD])."""
    logits, k_sfx, v_sfx = prefill_forward(
        params, cfg, cache, tokens, offsets, true_lens, tables,
        prefix_bound=prefix_bound, embeds=embeds, pos3=pos3,
    )
    cache, new_last = merge_tokens(
        cache, tables, offsets, true_lens, k_sfx, v_sfx,
        last_rows=last_rows, slot_ids=slot_ids,
    )
    return cache, logits, new_last


@functools.partial(jax.jit, static_argnames=("cfg",))
def mm_prompt_embeds(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [N, Tp] int32 prompt tokens (padded)
    pixels: jnp.ndarray,  # [N, P, patch_dim]
    vis_seg: jnp.ndarray,  # [N, P]
    vis_pos_h: jnp.ndarray,  # [N, P]
    vis_pos_w: jnp.ndarray,  # [N, P]
    ordinals: jnp.ndarray,  # [N, Tp] merged-patch ordinal; -1 = text
) -> jnp.ndarray:
    """Prompt embeddings with vision embeds spliced at image-pad tokens —
    computed ONCE at admission; prefill consumes the result instead of a
    token lookup (the serving analog of models/forward.packed_forward's
    training-side splice)."""
    from areal_tpu.models import vision as vision_lib

    x = params["embedding"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    emb = vision_lib.vision_apply(
        params["vision"], cfg.vision, pixels, vis_seg, vis_pos_h,
        vis_pos_w, remat=False,
    )  # [N, Pm, D]
    gathered = jnp.take_along_axis(
        emb, jnp.clip(ordinals, 0)[..., None].astype(jnp.int32), axis=1
    ).astype(x.dtype)
    return jnp.where(ordinals[..., None] >= 0, gathered, x)


@functools.partial(jax.jit, donate_argnames=("cache",))
def copy_pages(
    cache: Dict[str, jnp.ndarray],
    src: jnp.ndarray,  # [P] int32 source page per copy
    dst: jnp.ndarray,  # [P] int32 destination (>= num_pages rows dropped)
) -> Dict[str, jnp.ndarray]:
    """Duplicate pool pages src→dst (GRPO sibling partial-tail pages after
    one shared prompt prefill; full pages are shared host-side instead).
    Padding rows use dst >= num_pages."""
    k = cache["k"].at[:, :, dst].set(cache["k"][:, :, src], mode="drop")
    v = cache["v"].at[:, :, dst].set(cache["v"][:, :, src], mode="drop")
    return {"k": k, "v": v}


@jax.jit
def gather_pages(
    cache: Dict[str, jnp.ndarray],
    pages: jnp.ndarray,  # [P] int32 (padding rows use page 0: trash)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Read pool pages for a host-side demotion snapshot (r16 KV spill
    tier) or a kv-shipping export: [L, Hp, P, rows, lane] per tensor.
    Non-donating — the pool stays live; the caller's device_get blocks
    until every in-flight write to those pages has landed."""
    return cache["k"][:, :, pages], cache["v"][:, :, pages]


@functools.partial(jax.jit, donate_argnames=("cache",))
def scatter_pages(
    cache: Dict[str, jnp.ndarray],
    dst: jnp.ndarray,  # [P] int32 (>= num_pages rows dropped)
    k_new: jnp.ndarray,  # [L, Hp, P, rows, lane]
    v_new: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Write host-restored pages back into the pool (spill-tier
    promotion flush / kv-shipping import). Padding rows use
    dst >= num_pages, same drop contract as copy_pages."""
    k = cache["k"].at[:, :, dst].set(k_new, mode="drop")
    v = cache["v"].at[:, :, dst].set(v_new, mode="drop")
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def _gather_recent_kv(
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,  # [S, PPS]
    pos0: jnp.ndarray,  # [S] cached tokens per slot
    rl: jnp.ndarray,  # [S] replay length (tokens since the boundary)
    replay: int,  # static buffer width (max replay = chunk quantum - 1)
    num_kv_heads: int,
    head_dim: int,
):
    """K/V of positions [pos0-rl, pos0) per slot, gathered from the POOL
    into chunk-buffer form ([L, S, replay, Hkv, D] ×2; entry j holds
    position pos0-rl+j, valid for j < rl, zeros elsewhere).

    This is the canonical-alignment replay prefix for speculative
    serving: a dispatch that starts mid-chunk (a partial draft accept
    left the slot between ``decode_chunk`` boundaries) re-presents the
    boundary-to-now K/V as in-window chunk entries, so every position's
    attention sees EXACTLY the pool-window/chunk-buffer split a
    non-speculative run would give it — the split changes softmax
    summation order, so matching it is what keeps greedy streams
    bit-identical. Pool bytes are the very bytes the sequential path
    had in its buffer (merges are exact copies), so no recompute and no
    numerics bet. Spec-off engines never call this."""
    nl = cache["k"].shape[0]
    num_pages = cache["k"].shape[2]
    merged, tpr = layout_from_pool(
        cache["k"].shape, num_kv_heads, head_dim
    )
    bs = cache["k"].shape[3] * tpr  # page size in tokens
    j = jnp.arange(replay, dtype=jnp.int32)[None, :]
    valid = j < rl[:, None]  # [S, R]
    positions = jnp.where(valid, (pos0 - rl)[:, None] + j, 0)
    page = jnp.take_along_axis(
        tables, jnp.clip(positions // bs, 0, tables.shape[1] - 1), axis=1
    )
    flat = jnp.clip(page, 0, num_pages - 1) * bs + positions % bs  # [S, R]

    def gather(pool):
        view = unpacked_view(pool, head_dim, num_kv_heads)
        view = view.reshape(nl, view.shape[1], -1, head_dim)
        g = view[:, :, flat]  # [L, Hkv, S, R, D]
        g = g.transpose(0, 2, 3, 1, 4)  # [L, S, R, Hkv, D]
        return jnp.where(valid[None, :, :, None, None], g, 0)

    return gather(cache["k"]), gather(cache["v"])


@device_scope("attention")
def _attend(
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    li: jnp.ndarray,
    q: jnp.ndarray,  # [S, Hq, D]
    pos0: jnp.ndarray,  # [S] cached lengths
    tables: jnp.ndarray,  # [S, PPS]
    ck: jnp.ndarray,  # [S, Hkv, T, D]
    cv: jnp.ndarray,
    counts: jnp.ndarray,  # [S]
    attn_impl: str,
    ppcb: int,
    spb: int,
):
    if attn_impl == "kernel":
        return paged_decode_attention(
            q, cache["k"], cache["v"], li, pos0, tables, ck, cv, counts,
            pages_per_compute_block=ppcb, slots_per_block=spb,
            num_kv_heads=cfg.num_kv_heads,
        )
    return paged_decode_attention_jnp(
        q, cache["k"], cache["v"], li, pos0, tables, ck, cv, counts,
        num_kv_heads=cfg.num_kv_heads,
    )


def _decode_core(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,  # [S, PPS]
    pos0: jnp.ndarray,  # [S] cached tokens per slot (fixed this chunk)
    tokens0: jnp.ndarray,  # [S] current input token per slot
    active0: jnp.ndarray,  # [S] bool
    key: Optional[jax.Array],
    sample_args: Optional[tuple],
    stop_args: Optional[tuple],
    steps: int,
    attn_impl: str,
    ppcb: int,
    spb: int,
    topk_bound: int,
    rope_delta: Optional[jnp.ndarray] = None,  # [S] mrope text-position shift
    slot_ids: Optional[jnp.ndarray] = None,  # [S] engine slot per row
    align_base: Optional[jnp.ndarray] = None,  # [S] admission cache length
    replay: int = 0,  # static: canonical chunk quantum - 1 (0 = off)
):
    """Shared body of decode_multi / decode_step. When sample_args is None,
    runs exactly one step and returns the logits instead of sampling.

    ``rope_delta`` shifts ROPE positions only (VLM mrope compresses image
    blocks, so a text token's rotary position lags its cache index by a
    per-request constant); attention windows still use cache lengths.

    ``slot_ids`` keys each row's sampling RNG by its engine slot — under
    decode tail compaction rows are a gathered subset of slots, and the
    stream a slot produces must not depend on its row position.

    ``align_base``/``replay`` (replay MUST equal steps - 1 when used)
    enable canonical-alignment replay for speculative serving (see
    _gather_recent_kv): a slot sitting ``rl = (pos0 - align_base) %
    steps`` tokens past its last canonical chunk boundary gets the
    boundary-to-now K/V gathered from the pool into the leading chunk
    buffer entries, starts the scan at within-chunk count rl, and stops
    emitting at the boundary (dormant rows stay alive and resume next
    dispatch realigned). The buffer stays EXACTLY ``steps`` wide and
    every position lands at within-chunk column (p - base) with the
    pool window ending at its boundary — the same SHAPES and the same
    inputs as the non-speculative run, which is what bit-exactness
    actually requires (merely masking extra buffer columns changes
    reduce codegen and drifts ulps; measured on the head-merged
    layout). Spec-off engines pass replay = 0 and run the unchanged
    program; with replay the sample path returns a trailing
    ``next_tokens`` [S] (a dormant row's next input is its LAST emitted
    token, not step steps-1's sample)."""
    s = tables.shape[0]
    d = cfg.head_dim
    nl, hkv_pool, num_pages, prow, fd = cache["k"].shape
    hkv = cfg.num_kv_heads
    # tokens per pool row differ by layout (head-merged packs every head
    # into the lane dim); page_size = rows * tokens-per-row either way
    _, tpr = layout_from_pool(cache["k"].shape, hkv, d)
    page_size = prow * tpr
    cos, sin = rope_frequencies(
        cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta
    )
    srange = jnp.arange(s)
    kv_dtype = cache["k"].dtype
    use_replay = replay > 0 and align_base is not None
    if use_replay and replay != steps - 1:
        raise ValueError(
            f"replay ({replay}) must be steps - 1 ({steps - 1}): the "
            "canonical chunk quantum IS the dispatch step count"
        )
    if use_replay:
        rl = jnp.where(active0, jnp.mod(pos0 - align_base, steps), 0)
    else:
        rl = jnp.zeros(s, jnp.int32)
    base = pos0 - rl  # pool window ends at the canonical boundary

    def model_step(kbuf, vbuf, tokens, clen, active):
        """One forward pass for all slots; new K/V appended to the chunk
        buffers (inactive slots drop). Returns (kbuf, vbuf, logits).

        The 50MB-class chunk buffers are READ-ONLY inside the layer scan
        (a scatter on a nested scan carry costs a full buffer copy per
        layer — measured at ~25ms/step): each layer overlays only its own
        small [S, T] slice for the self-token, the per-layer K/V stack
        out as scan ys, and ONE bulk scatter per step appends them."""
        x = params["embedding"][tokens]  # [S, D]
        if cfg.scale_embeddings:  # gemma
            x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
        # clen is the ABSOLUTE within-chunk count (starts at rl under
        # replay — the replayed entries occupy buffer cols [0, rl)); the
        # just-written self token is visible
        pos = base + clen
        if rope_delta is not None:
            pos = jnp.maximum(pos + rope_delta, 0)
        counts = clen + 1
        ci = jnp.where(active, clen, steps)

        def layer(x, xs):
            lp, li = xs
            q, k, v = _layer_pre(cfg, lp, x)  # q [S,Hq,D] k/v [S,Hkv,D]
            q = apply_rope(q[:, None], pos[:, None], cos, sin)[:, 0]
            k = apply_rope(k[:, None], pos[:, None], cos, sin)[:, 0]
            kb = jax.lax.dynamic_index_in_dim(kbuf, li, 0, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vbuf, li, 0, keepdims=False)
            kb = kb.at[srange, ci].set(k.astype(kv_dtype), mode="drop")
            vb = vb.at[srange, ci].set(v.astype(kv_dtype), mode="drop")
            attn = _attend(
                cfg, cache, li, q, base, tables,
                kb.transpose(0, 2, 1, 3), vb.transpose(0, 2, 1, 3),
                counts, attn_impl, ppcb, spb,
            )
            x = _layer_post(
                cfg, lp, x, attn.reshape(s, cfg.q_dim).astype(x.dtype),
                active,
            )
            return x, (k.astype(kv_dtype), v.astype(kv_dtype))

        x, (knew, vnew) = jax.lax.scan(
            layer, x, (params["layers"], jnp.arange(nl, dtype=jnp.int32))
        )
        # ONE bulk append per step: [L, S, Hkv, D] at (slot, ci)
        kbuf = kbuf.at[:, srange, ci].set(knew, mode="drop")
        vbuf = vbuf.at[:, srange, ci].set(vnew, mode="drop")
        return kbuf, vbuf, _final_logits(params, cfg, x)

    # inactive slots scatter at index `steps` (out of range → dropped)
    kbuf0 = jnp.zeros((nl, s, steps, hkv, d), kv_dtype)
    vbuf0 = jnp.zeros_like(kbuf0)
    if use_replay:
        seed_k, seed_v = _gather_recent_kv(
            cache, tables, pos0, rl, replay, hkv, d
        )
        kbuf0 = kbuf0.at[:, :, :replay].set(seed_k)
        vbuf0 = vbuf0.at[:, :, :replay].set(seed_v)

    def merge_view(buf):
        """This chunk's OWN entries (cols [rl, rl+emitted) per row) —
        the replay prefix is already in the pool and must not re-merge
        (its first row may predate last_rows' remembered partial row).
        Clipped out-of-range cols land beyond the merge counts and
        drop."""
        if not use_replay:
            return buf
        idx = jnp.clip(
            rl[:, None] + jnp.arange(steps, dtype=jnp.int32)[None, :],
            0, steps - 1,
        )
        return jnp.take_along_axis(
            buf, idx[None, :, :, None, None], axis=2
        )

    if sample_args is None:
        kbuf, vbuf, logits = model_step(kbuf0, vbuf0, tokens0, rl, active0)
        clen_final = active0.astype(jnp.int32)
        return logits, merge_view(kbuf), merge_view(vbuf), clen_final

    temperature, top_p, top_k, greedy = sample_args
    remaining0, no_stop0, stop_tokens = stop_args

    def step(carry, step_key):
        kbuf, vbuf, tokens, clen, active, remaining, no_stop = carry
        # boundary cap: a row whose within-chunk count reached `steps`
        # goes DORMANT for the rest of this dispatch (still alive — it
        # resumes realigned next dispatch). Only ever binds under
        # replay, where clen starts at rl > 0
        on = active & (clen < steps)
        kbuf, vbuf, logits = model_step(kbuf, vbuf, tokens, clen, on)
        toks, logps = _sample_impl(
            logits, step_key, temperature, top_p, top_k, greedy,
            topk_bound, slot_ids=slot_ids,
        )
        emitted = on
        hit_stop = jnp.any(
            toks[:, None] == stop_tokens, axis=1
        ) & (no_stop <= 1)
        clen = clen + on
        remaining = jnp.where(on, remaining - 1, remaining)
        no_stop = jnp.where(on, no_stop - 1, no_stop)
        active = jnp.where(on, active & ~hit_stop & (remaining > 0), active)
        # dormant rows keep their last emitted token — it is the next
        # dispatch's input
        tokens = jnp.where(on, toks, tokens)
        return (kbuf, vbuf, tokens, clen, active, remaining, no_stop), (
            toks, logps, emitted,
        )

    keys = jax.random.split(key, steps)
    (kbuf, vbuf, next_tokens, clen, active, remaining, no_stop), (
        toks, logps, emitted,
    ) = jax.lax.scan(
        step,
        (kbuf0, vbuf0, tokens0, rl, active0, remaining0, no_stop0),
        keys,
    )
    return (
        toks, logps, emitted, active, remaining, no_stop, base + clen,
        merge_view(kbuf), merge_view(vbuf), clen - rl, next_tokens,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "steps", "topk_bound", "attn_impl", "ppcb", "spb", "replay",
    ),
)
def _decode_multi_forward(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,  # [S, PPS] int32 (bucketed page window)
    pos0: jnp.ndarray,  # [S] int32 cached tokens per slot
    tokens: jnp.ndarray,  # [S] current input token per slot
    active: jnp.ndarray,  # [S] bool
    remaining: jnp.ndarray,  # [S] int32 tokens still allowed per slot
    no_stop_before: jnp.ndarray,  # [S] int32 (min_new_tokens countdown)
    stop_tokens: jnp.ndarray,  # [S, K] int32, -1 padded
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    greedy: jnp.ndarray,
    steps: int,
    topk_bound: int = 0,
    attn_impl: str = "jnp",
    ppcb: int = 4,
    spb: int = 8,
    rope_delta: Optional[jnp.ndarray] = None,
    slot_ids: Optional[jnp.ndarray] = None,
    align_base: Optional[jnp.ndarray] = None,
    replay: int = 0,
):
    """`steps` fused decode+sample iterations in ONE dispatch with stop
    handling on device (see module doc). Host contract: tables cover
    ceil((pos0[s]+steps)/page_size) pages for every active slot.

    READ-ONLY forward chunk (the merge is a separate dispatch in
    decode_multi)."""
    return _decode_core(
        params, cfg, cache, tables, pos0, tokens, active, key,
        (temperature, top_p, top_k, greedy),
        (remaining, no_stop_before, stop_tokens),
        steps, attn_impl, ppcb, spb, topk_bound, rope_delta=rope_delta,
        slot_ids=slot_ids, align_base=align_base, replay=replay,
    )


def decode_multi(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,
    pos0: jnp.ndarray,
    tokens: jnp.ndarray,
    active: jnp.ndarray,
    remaining: jnp.ndarray,
    no_stop_before: jnp.ndarray,
    stop_tokens: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    greedy: jnp.ndarray,
    steps: int,
    topk_bound: int = 0,
    attn_impl: str = "jnp",
    ppcb: int = 1,
    spb: int = 16,
    last_rows: Optional[Dict[str, jnp.ndarray]] = None,
    rope_delta: Optional[jnp.ndarray] = None,
    slot_ids: Optional[jnp.ndarray] = None,
    align_base: Optional[jnp.ndarray] = None,
    replay: int = 0,
):
    """`steps` fused decode+sample iterations: one READ-ONLY forward
    dispatch + one WRITE-ONLY merge dispatch (reading and writing the
    pool in one computation costs a full pool copy on this backend).
    Host contract: tables cover ceil((pos0[s]+steps)/page_size) pages for
    every active slot.

    ``slot_ids`` maps each ROW to its engine slot (default: identity).
    Under decode tail compaction the engine dispatches a gathered subset
    of slots; slot_ids keys the per-row sampling RNG and indexes
    ``last_rows`` (which may then keep its full [L, max_num_seqs, ...]
    shape), and the returned ``new_last_rows`` is in ROW space for the
    caller to scatter back. Padding rows may carry an out-of-range slot
    id — gathers clip, and the caller drops their scatter.

    Returns (cache, toks [steps,S], logps [steps,S], emitted [steps,S],
    active_after [S], remaining_after, no_stop_after, lens_after [S],
    new_last_rows, next_tokens [S]). ``lens_after`` keeps the per-slot
    cached length device-resident so the host can dispatch chunk N+1
    before fetching chunk N's results (the serving loop pipelines
    dispatch against result processing).

    ``next_tokens`` is each row's next decode input: under
    canonical-alignment replay (``align_base`` given, ``replay`` =
    steps - 1 — speculative engines, see _decode_core) a row that hit
    its chunk boundary mid-dispatch goes dormant and resumes from its
    LAST emitted token; without replay it equals toks[-1] for every row
    still active at chunk end."""
    if slot_ids is None:
        slot_ids = jnp.arange(tables.shape[0], dtype=jnp.int32)
    (
        toks, logps, emitted, active_a, remaining_a, no_stop_a, lens_a,
        kbuf, vbuf, clen, next_tokens,
    ) = _decode_multi_forward(
        params, cfg, cache, tables, pos0, tokens, active, remaining,
        no_stop_before, stop_tokens, key, temperature, top_p, top_k,
        greedy, steps, topk_bound, attn_impl, ppcb, spb,
        rope_delta=rope_delta, slot_ids=slot_ids, align_base=align_base,
        replay=replay,
    )
    cache, new_last = merge_tokens(
        cache, tables, pos0, clen, kbuf, vbuf, last_rows=last_rows,
        slot_ids=slot_ids,
    )
    # next_tokens always rides along (r14): for replay == 0 it equals
    # toks[-1] for every row still active at chunk end (the scan carry
    # updates while `on`), and inactive rows' inputs are masked and
    # row-independent — returning it saves the caller an eager [-1]
    # slice per chunk that would dodge dispatch-scope attribution
    return (
        cache, toks, logps, emitted, active_a, remaining_a, no_stop_a,
        lens_a, new_last, next_tokens,
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "ppcb", "spb"),
)
def _decode_step_forward(
    params, cfg, cache, tables, pos0, tokens, active,
    attn_impl="jnp", ppcb=1, spb=16, rope_delta=None,
):
    return _decode_core(
        params, cfg, cache, tables, pos0, tokens, active, None, None, None,
        1, attn_impl, ppcb, spb, 0, rope_delta=rope_delta,
    )


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,  # [S, PPS]
    pos0: jnp.ndarray,  # [S]
    tokens: jnp.ndarray,  # [S]
    active: jnp.ndarray,  # [S] bool
    attn_impl: str = "jnp",
    ppcb: int = 1,
    spb: int = 16,
    last_rows: Optional[Dict[str, jnp.ndarray]] = None,
    rope_delta: Optional[jnp.ndarray] = None,
):
    """Single decode step for all slots (read-only forward + write-only
    merge); returns (cache, logits [S, V], new_last_rows). Callers MUST
    thread last_rows between sequential calls (it preserves the partial
    first row when pos0 isn't row-aligned)."""
    logits, kbuf, vbuf, clen = _decode_step_forward(
        params, cfg, cache, tables, pos0, tokens, active, attn_impl,
        ppcb, spb, rope_delta=rope_delta,
    )
    cache, new_last = merge_tokens(
        cache, tables, pos0, clen, kbuf, vbuf, last_rows=last_rows
    )
    return cache, logits, new_last


# ---------------------------------------------------------------------------
# Speculative verify (draft-free multi-token decode)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "topk_bound", "attn_impl", "ppcb", "spb", "replay",
    ),
)
def _spec_verify_forward(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,  # [S, PPS]
    pos0: jnp.ndarray,  # [S] cached tokens per slot
    tokens: jnp.ndarray,  # [S] current input token per slot
    draft: jnp.ndarray,  # [S, K-1] proposed continuation tokens
    draft_len: jnp.ndarray,  # [S] valid drafts per slot (0..K-1)
    active: jnp.ndarray,  # [S] bool
    remaining: jnp.ndarray,  # [S]
    no_stop_before: jnp.ndarray,  # [S]
    stop_tokens: jnp.ndarray,  # [S, 8]
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    greedy: jnp.ndarray,
    k: int,  # static verify window: 1 current token + K-1 draft positions
    topk_bound: int = 0,
    attn_impl: str = "jnp",
    ppcb: int = 4,
    spb: int = 8,
    rope_delta: Optional[jnp.ndarray] = None,
    slot_ids: Optional[jnp.ndarray] = None,
    align_base: Optional[jnp.ndarray] = None,
    replay: int = 0,
):
    """Score ``k`` positions per slot in ONE forward and accept the
    longest prefix the model itself would have produced.

    Position i's input is ``[tokens, draft[0], ..., draft[i-1]][i]``; its
    logits predict the NEXT token, sampled through the exact
    ``_sample_impl`` the sequential decode scan uses (greedy slots:
    argmax; sampled slots: an independent key per position — every kept
    token is drawn from the true conditional, so the output distribution
    is exactly the non-speculative one). Acceptance is EXACT MATCH: the
    sampled token at position i must equal the draft token that was fed
    as position i+1's input, otherwise positions > i were computed on a
    wrong prefix and emission stops. Greedy streams are therefore
    bit-identical with speculation on or off.

    Numerics contract (what makes that bit-exactness hold): every op is
    row/position-independent against the sequential ``_decode_core``
    path — batched matmuls ([S, K, D] vs [S, D]) are row-stable, rope /
    norms are elementwise, and each position's attention is the SAME
    ``_attend`` call the scan makes (q [S, Hq, D], chunk counts i+1;
    masked chunk/window tails contribute exact zeros regardless of
    buffer size — the same shape-invariance the kv_bucket ladder and
    decode compaction already rely on).

    Stop/budget semantics mirror the scan step-for-step: a stop-token
    hit or exhausted budget ends emission exactly where the sequential
    path would.

    Returns the ``_decode_multi_forward`` tuple plus nothing new: (toks
    [K, S], logps, emitted, active_after, remaining_after, no_stop_after,
    lens_after, kbuf, vbuf, clen) where ``clen`` is the per-slot count of
    chunk-buffer positions whose K/V is VALID (inputs on the accepted
    path) — the merge writes only those, which IS the KV rollback:
    rejected positions never reach the pool, and cache-length accounting
    (``lens_after = pos0 + clen``) matches a non-speculative run that
    emitted the same tokens.
    """
    s = tables.shape[0]
    d = cfg.head_dim
    nl = cache["k"].shape[0]
    hkv = cfg.num_kv_heads
    cos, sin = rope_frequencies(
        cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta
    )
    kv_dtype = cache["k"].dtype
    if slot_ids is None:
        slot_ids = jnp.arange(s, dtype=jnp.int32)
    # canonical-alignment replay (see _gather_recent_kv / _decode_core):
    # window position i is scored with the EXACT shapes the sequential
    # engine gives that position — a width-cq chunk buffer holding its
    # canonical chunk's entries at within-chunk columns, pool window
    # ending at that chunk's boundary. cq = replay + 1 is the engine's
    # decode_chunk; windows may cross boundaries (every position gets
    # its own buffer/window). Without align_base (standalone use) the
    # window itself plays the chunk-buffer role at width k.
    use_replay = replay > 0 and align_base is not None
    cq = replay + 1
    if use_replay:
        rl = jnp.where(active, jnp.mod(pos0 - align_base, cq), 0)
        seed_k, seed_v = _gather_recent_kv(
            cache, tables, pos0, rl, replay, hkv, d
        )
    else:
        rl = jnp.zeros(s, jnp.int32)
    base = pos0 - rl

    # [S, K] input token matrix: current token then the draft guesses
    tokens_mat = jnp.concatenate([tokens[:, None], draft], axis=1)
    x = params["embedding"][tokens_mat]  # [S, K, D]
    if cfg.scale_embeddings:  # gemma
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    pos = pos0[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]  # [S, K]
    if rope_delta is not None:
        pos = jnp.maximum(pos + rope_delta[:, None], 0)
    valid_q = jnp.broadcast_to(active[:, None], (s, k))

    def layer(x, xs):
        lp, li = xs
        q, kk, vv = _layer_pre(cfg, lp, x)  # [S, K, H*, D]
        q = apply_rope(q, pos, cos, sin)
        kk = apply_rope(kk, pos, cos, sin)
        kwin = kk.astype(kv_dtype)  # [S, K, Hkv, D]
        vwin = vv.astype(kv_dtype)
        attns = []
        if use_replay:
            # canonical chunk buffer: col c ↔ cache position base + c —
            # replayed boundary-to-now prefix at cols [0, rl), this
            # window scattered at per-row cols [rl, rl+K). Width is
            # EXACTLY cq (the sequential engine's chunk shape); window
            # positions at or past the next boundary are never emitted
            # (the acceptance loop caps there — their canonical chunk
            # would need [pos0, boundary) as POOL entries, which are not
            # merged yet), so slicing to cq loses nothing emittable.
            sk = jax.lax.dynamic_index_in_dim(seed_k, li, 0, keepdims=False)
            sv = jax.lax.dynamic_index_in_dim(seed_v, li, 0, keepdims=False)
            widx = jnp.clip(
                rl[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :],
                0, replay + k - 1,
            )
            srows = jnp.arange(s)[:, None]
            kb = jnp.concatenate(
                [sk, jnp.zeros((s, k, hkv, d), kv_dtype)], axis=1
            ).at[srows, widx].set(kwin)[:, :cq]
            vb = jnp.concatenate(
                [sv, jnp.zeros((s, k, hkv, d), kv_dtype)], axis=1
            ).at[srows, widx].set(vwin)[:, :cq]
            ck = kb.transpose(0, 2, 1, 3)  # [S, Hkv, cq, D]
            cv = vb.transpose(0, 2, 1, 3)
            for i in range(k):  # static unroll; K is small
                attns.append(
                    _attend(
                        cfg, cache, li, q[:, i], base, tables, ck, cv,
                        rl + i + 1, attn_impl, ppcb, spb,
                    )
                )
        else:
            # standalone (no alignment contract): the K in-window
            # positions play the chunk buffer's role; position i sees
            # entries [0, i] via counts
            ck = kwin.transpose(0, 2, 1, 3)  # [S, Hkv, K, D]
            cv = vwin.transpose(0, 2, 1, 3)
            for i in range(k):
                counts_i = jnp.full((s,), i + 1, jnp.int32)
                attns.append(
                    _attend(
                        cfg, cache, li, q[:, i], pos0, tables, ck, cv,
                        counts_i, attn_impl, ppcb, spb,
                    )
                )
        attn = jnp.stack(attns, axis=1)  # [S, K, Hq, D]
        x = _layer_post(
            cfg, lp, x, attn.reshape(s, k, cfg.q_dim).astype(x.dtype),
            valid_q,
        )
        return x, (kk.astype(kv_dtype), vv.astype(kv_dtype))

    x, (knew, vnew) = jax.lax.scan(
        layer, x, (params["layers"], jnp.arange(nl, dtype=jnp.int32))
    )
    # knew/vnew [L, S, K, Hkv, D] — already the decode chunk-buffer layout
    logits = _final_logits(params, cfg, x)  # [S, K, V] fp32

    keys = jax.random.split(key, k)
    # ``on`` gates EMISSION (dies on stop/budget like the scan's active,
    # and ALSO on a draft mismatch — later positions were computed on a
    # wrong prefix); ``alive`` is the request's continued-existence flag
    # the engine gets back: a rejected draft ends emission but NOT the
    # request (it simply continues un-speculated next chunk)
    on = active
    alive = active
    rem = remaining
    nsb = no_stop_before
    clen = jnp.zeros(s, jnp.int32)
    toks_list, logps_list, emitted_list = [], [], []
    for i in range(k):
        toks_i, logps_i = _sample_impl(
            logits[:, i], keys[i], temperature, top_p, top_k, greedy,
            topk_bound, slot_ids=slot_ids,
        )
        emitted_i = on
        emitted_list.append(emitted_i)
        hit_stop = jnp.any(
            toks_i[:, None] == stop_tokens, axis=1
        ) & (nsb <= 1)
        clen = clen + on
        rem = jnp.where(on, rem - 1, rem)
        nsb = jnp.where(on, nsb - 1, nsb)
        # exactly the scan's continue condition for this emitted token
        cont = ~hit_stop & (rem > 0)
        alive = jnp.where(emitted_i, cont, alive)
        on = emitted_i & cont
        if i + 1 < k:
            # continue into position i+1 only if the draft supplied it
            # AND the model just produced exactly that token (the
            # verified-prefix rule)
            on = on & (draft_len >= i + 1) & (toks_i == tokens_mat[:, i + 1])
            if use_replay:
                # canonical-boundary cap: a position in the NEXT chunk
                # would need this window's pre-boundary tokens as pool
                # entries (not merged yet) — the row stops here and
                # resumes realigned next dispatch
                on = on & (rl + (i + 1) < cq)
        toks_list.append(toks_i)
        logps_list.append(logps_i)
    toks = jnp.stack(toks_list)  # [K, S]
    logps = jnp.stack(logps_list)
    emitted = jnp.stack(emitted_list)
    # next decode input per row = its LAST EMITTED token (a row that
    # rejected its draft at position j resumes from token j, not from
    # position k-1's wrong-prefix sample — unlike the sequential scan,
    # toks[-1] is NOT the next input for every live row here)
    last_idx = jnp.clip(clen - 1, 0, k - 1)[None, :]
    next_tokens = jnp.take_along_axis(toks, last_idx, axis=0)[0]
    return (
        toks, logps, emitted, alive, rem, nsb, pos0 + clen, knew, vnew,
        clen, next_tokens,
    )


def spec_verify(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, jnp.ndarray],
    tables: jnp.ndarray,
    pos0: jnp.ndarray,
    tokens: jnp.ndarray,
    draft: jnp.ndarray,  # [S, K-1]
    draft_len: jnp.ndarray,  # [S]
    active: jnp.ndarray,
    remaining: jnp.ndarray,
    no_stop_before: jnp.ndarray,
    stop_tokens: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    greedy: jnp.ndarray,
    k: int,
    topk_bound: int = 0,
    attn_impl: str = "jnp",
    ppcb: int = 1,
    spb: int = 16,
    last_rows: Optional[Dict[str, jnp.ndarray]] = None,
    rope_delta: Optional[jnp.ndarray] = None,
    slot_ids: Optional[jnp.ndarray] = None,
    align_base: Optional[jnp.ndarray] = None,
    replay: int = 0,
):
    """Multi-token verify with KV rollback: one READ-ONLY k-position
    forward + the standard WRITE-ONLY merge, where the merge count is the
    ACCEPTED prefix length — rejected positions' K/V never reach the
    pool, so pool state after a verify equals a non-speculative run that
    emitted the same tokens (pinned by tests/test_spec_decode.py).

    Same return contract as ``decode_multi`` (the engine's dispatch /
    fetch / process machinery treats both identically, with steps = k),
    plus a trailing ``next_tokens`` [S]: each row's last EMITTED token —
    the next decode input (``toks[-1]`` would be a wrong-prefix sample
    for rows that rejected their draft early).
    """
    if slot_ids is None:
        slot_ids = jnp.arange(tables.shape[0], dtype=jnp.int32)
    (
        toks, logps, emitted, active_a, remaining_a, no_stop_a, lens_a,
        kbuf, vbuf, clen, next_tokens,
    ) = _spec_verify_forward(
        params, cfg, cache, tables, pos0, tokens, draft, draft_len,
        active, remaining, no_stop_before, stop_tokens, key, temperature,
        top_p, top_k, greedy, k, topk_bound, attn_impl, ppcb, spb,
        rope_delta=rope_delta, slot_ids=slot_ids, align_base=align_base,
        replay=replay,
    )
    cache, new_last = merge_tokens(
        cache, tables, pos0, clen, kbuf, vbuf, last_rows=last_rows,
        slot_ids=slot_ids,
    )
    return (
        cache, toks, logps, emitted, active_a, remaining_a, no_stop_a,
        lens_a, new_last, next_tokens,
    )


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------
@device_scope("sample")
def _sample_impl(
    logits: jnp.ndarray,  # [S, V] fp32
    key: jax.Array,
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S] int32 (0 = disabled)
    greedy: jnp.ndarray,  # [S] bool
    topk_bound: int,
    slot_ids: Optional[jnp.ndarray] = None,  # [S] engine slot per row
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-slot sampling; returns (tokens [S], logprobs [S]).

    Each row draws under a key folded from ``slot_ids[row]`` (defaulting
    to the row index), NOT from its position in the batch — so a
    request's stream is invariant to which row it occupies. This is what
    makes decode tail compaction (engine rows = active-slot bucket)
    token-exact against the full-slot dispatch.

    ``topk_bound`` picks the truncation strategy (static):
      -1  no truncation anywhere (all slots top_p>=1, top_k=0) — a single
          ``categorical`` over the scaled logits; no sort at all.
       0  exact full-vocab sort (argsort) — the always-correct fallback.
      K>0 ``lax.top_k(K)`` candidates, top-k/top-p masks applied within
          them — the fast serving path. Slots that request NO truncation
          (top_k=0, top_p>=1) sample full-vocab categorical instead, so
          their behavior logprob matches their true sampling distribution
          (advisor round-2 finding).

    The returned logprob is under the temperature-scaled (untruncated)
    distribution — the behavior-policy logprob the trainer consumes
    (reference ModelResponse.output_logprobs semantics). Greedy slots
    report the logprob under the *unscaled* distribution (temperature
    never enters their behavior policy).
    """
    s, v = logits.shape
    temp = jnp.maximum(temperature, 1e-5)[:, None]
    scaled = logits / temp
    logp_full = jax.nn.log_softmax(scaled, axis=-1)
    if slot_ids is None:
        slot_ids = jnp.arange(s, dtype=jnp.int32)
    row_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, slot_ids
    )
    _categorical = jax.vmap(lambda k_, l_: jax.random.categorical(k_, l_))

    if topk_bound < 0:
        sampled = _categorical(row_keys, scaled)
    elif topk_bound > 0:
        kb = min(topk_bound, v)
        vals, idx = jax.lax.top_k(scaled, kb)  # [S, kb]
        # top_p cutoffs are defined against the FULL-vocab distribution,
        # not renormalized over the kb candidates (matching the exact path)
        cand_probs = jnp.exp(jnp.take_along_axis(logp_full, idx, axis=-1))
        cumprev = jnp.cumsum(cand_probs, axis=-1) - cand_probs
        rank = jnp.arange(kb)[None, :]
        keep = jnp.where(top_k[:, None] > 0, rank < top_k[:, None], True)
        keep &= cumprev < top_p[:, None]
        keep = keep.at[:, 0].set(True)  # always keep the argmax token
        trunc = jnp.where(keep, vals, NEG_INF)
        choice = _categorical(row_keys, trunc)
        truncated_pick = jnp.take_along_axis(
            idx, choice[:, None], axis=-1
        )[:, 0]
        # untruncated slots keep the exact full-vocab distribution
        untruncated = (top_k <= 0) & (top_p >= 1.0)
        full_pick = _categorical(row_keys, scaled)
        sampled = jnp.where(untruncated, full_pick, truncated_pick)
    else:
        # exact path: full sort (slow; tests / host-side calls)
        sort_idx = jnp.argsort(-scaled, axis=-1)
        sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumprobs = jnp.cumsum(sorted_probs, axis=-1)
        rank = jnp.arange(v)[None, :]
        keep = jnp.ones((s, v), bool)
        keep &= jnp.where(top_k[:, None] > 0, rank < top_k[:, None], True)
        keep &= (cumprobs - sorted_probs) < top_p[:, None]
        keep = keep.at[:, 0].set(True)
        trunc_sorted = jnp.where(keep, sorted_logits, NEG_INF)
        trunc = jnp.full_like(scaled, NEG_INF).at[
            jnp.arange(s)[:, None], sort_idx
        ].set(trunc_sorted)
        sampled = _categorical(row_keys, trunc)

    argmax = jnp.argmax(logits, axis=-1)
    tokens = jnp.where(greedy, argmax, sampled).astype(jnp.int32)
    # Greedy slots ignore temperature when picking the token, so report the
    # logprob under the *unscaled* distribution.
    lp_sampled = jnp.take_along_axis(
        logp_full, tokens[:, None], axis=-1
    ).squeeze(-1)
    lp_greedy = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), tokens[:, None], axis=-1
    ).squeeze(-1)
    logprobs = jnp.where(greedy, lp_greedy, lp_sampled)
    return tokens, logprobs


@jax.jit
def pack_host(*arrays) -> jnp.ndarray:
    """Flatten+concat device arrays into ONE float32 blob so the host pays
    a single fetch round-trip instead of one per array (int32 token ids
    are exact in f32 below 2^24)."""
    return jnp.concatenate(
        [a.reshape(-1).astype(jnp.float32) for a in arrays]
    )


@functools.partial(jax.jit, static_argnames=("topk_bound",))
def sample_tokens(
    logits: jnp.ndarray,  # [S, V] fp32
    key: jax.Array,
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S] int32 (0 = disabled)
    greedy: jnp.ndarray,  # [S] bool
    topk_bound: int = 0,
    slot_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if slot_ids is None:
        slot_ids = jnp.arange(logits.shape[0], dtype=jnp.int32)
    return _sample_impl(
        logits, key, temperature, top_p, top_k, greedy, topk_bound,
        slot_ids=slot_ids,
    )
