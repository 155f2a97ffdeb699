"""Paged decode attention for the generation engine's block KV pool.

TPU-native answer to the paged/radix KV cache the reference leans on via
SGLang (areal/api/cli_args.py:408 ``disable_radix_cache``; the 27k-token
generation recipe blog/AReaL_v0_3.md:263-284 requires it): the KV cache is a
pool of fixed-size pages shared by all sequences, and decode attention reads
each slot's pages through a page table instead of a contiguous line.

Two implementations with identical semantics:

- ``paged_decode_attention`` — a Pallas TPU kernel (manual-DMA flash
  attention). Pages stay in HBM (``pl.ANY``); each (slot, kv-head) grid step
  streams only the pages that slot actually uses, double-buffered, and
  *skips* page blocks past the slot's length — ragged continuous batches
  don't pay max-length HBM traffic, unlike a dense gather. The in-flight
  chunk buffer of a fused multi-step decode (model_runner.decode_multi) is
  folded into the same online softmax, so multi-step decode needs no
  separate merge.
- ``paged_decode_attention_jnp`` — a pure-jnp gather fallback with the same
  signature, used on CPU (tests) and under tensor-parallel serving (the
  kernel is single-device; XLA shards the gather path automatically).

Layout contract (shared with inference/cache.py):
  k_pages / v_pages: [L, Hkv, NP, BS//f, f*D] with f = 128 // D (the "lane
  pack factor") — mosaic tiles the last dim to 128 lanes, so a page stores
  f consecutive tokens per 128-lane row to keep HBM page slices DMA-able
  for head_dim < 128 without padding the pool. A free reshape recovers the
  logical [L, Hkv, NP, BS, D] token view for everything outside the kernel
  (``unpacked_view``). Logical page ``p`` of a sequence holds tokens
  [p*BS, (p+1)*BS) for EVERY layer (one page-table entry serves all
  layers), so the host allocates pages once per sequence, not per layer.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def pack_factor(head_dim: int) -> int:
    """Tokens per 128-lane pool row (1 for D>=128; D must divide 128)."""
    if head_dim >= 128:
        if head_dim % 128:
            raise ValueError(f"head_dim {head_dim} not a multiple of 128")
        return 1
    if 128 % head_dim:
        raise ValueError(f"head_dim {head_dim} does not divide 128")
    return 128 // head_dim


def can_head_merge(num_kv_heads: int, head_dim: int) -> bool:
    """Head-merged rows need every kv head of a token inside one 128-lane
    row: Hkv*D must divide 128 (Hkv=2, D=64 — the qwen2-small family —
    fills it exactly)."""
    return (
        head_dim < 128
        and num_kv_heads * head_dim <= 128
        and 128 % (num_kv_heads * head_dim) == 0
    )


def resolve_pool_layout(
    layout: str, num_kv_heads: int, head_dim: int,
    single_device: bool = True,
) -> str:
    """Resolve a ``pool_layout`` config value ("auto" | "token_packed" |
    "head_merged") to a concrete layout — the ONE place the default
    lives. Since r6 "auto" means head_merged whenever the geometry
    allows it (Hkv*D | 128) on a single-device engine: one DMA per page
    moves every kv head, halving the decode kernel's per-page copy count
    for Hkv=2 at identical bytes. Tensor-parallel serving stays
    token_packed (TP shards the pool's kv-head dim, which merging
    collapses). Explicit layouts pass through unchanged — validation of
    an impossible explicit choice is the caller's job."""
    if layout != "auto":
        return layout
    if single_device and can_head_merge(num_kv_heads, head_dim):
        return "head_merged"
    return "token_packed"


def pool_layout(
    num_kv_heads: int, head_dim: int, head_merge: bool
):
    """(hkv_pool, tokens_per_row, lane_width, merged) for a pool layout.

    token_packed: row = ``128//D`` consecutive tokens of ONE
    head — pool [L, Hkv, NP, BS//f, f*D].
    head_merged (default since r6 where geometry allows, see
    resolve_pool_layout): row = ``128//(Hkv*D)`` consecutive tokens ×
    ALL kv heads — pool [L, 1, NP, BS//f', 128]. One DMA per page moves
    every head (the decode kernel's per-(page, head) copy count halves
    for Hkv=2), at identical bytes. For true MQA (Hkv=1) the merged and
    token-packed layouts coincide — ``layout_from_pool`` reports such a
    pool as token_packed, and external kernel callers must still pass
    ``num_kv_heads=1`` explicitly (see paged_decode_attention note).
    """
    if head_merge:
        if not can_head_merge(num_kv_heads, head_dim):
            raise ValueError(
                f"head_merge needs Hkv*D | 128, got {num_kv_heads}x{head_dim}"
            )
        group = num_kv_heads * head_dim
        return 1, 128 // group, 128, True
    f = pack_factor(head_dim)
    return num_kv_heads, f, f * head_dim, False


def packed_pool_shape(
    num_layers: int, num_kv_heads: int, num_pages: int, page_size: int,
    head_dim: int, head_merge: bool = False,
) -> Tuple[int, int, int, int, int]:
    hkv_pool, tpr, lane, _ = pool_layout(num_kv_heads, head_dim, head_merge)
    assert page_size % tpr == 0
    return (num_layers, hkv_pool, num_pages, page_size // tpr, lane)


def is_head_merged(pool: jnp.ndarray, num_kv_heads: int) -> bool:
    """Layout detection from the pool's shape: the merged pool collapses
    the kv-head dim to 1 while the model has >1 kv head."""
    return pool.shape[1] == 1 and num_kv_heads > 1


def layout_from_pool(
    pool_shape, num_kv_heads: int, head_dim: int
) -> Tuple[bool, int]:
    """(merged, tokens_per_row) derived from a pool's shape — the ONE
    place the merged-layout rule lives for consumers (merge, prefill,
    decode, fallbacks)."""
    _, hkv_pool, _, _, lane = pool_shape
    merged = hkv_pool == 1 and num_kv_heads > 1
    if merged:
        return True, lane // (num_kv_heads * head_dim)
    return False, lane // head_dim


def unpacked_view(
    pool: jnp.ndarray, head_dim: int, num_kv_heads: Optional[int] = None
) -> jnp.ndarray:
    """Logical [L, Hkv, NP, BS, D] token view of either pool layout
    (free reshape for token_packed; one transpose for head_merged)."""
    nl, hkv_pool, np_, rows, lane = pool.shape
    if num_kv_heads is not None and is_head_merged(pool, num_kv_heads):
        tpr = lane // (num_kv_heads * head_dim)
        v = pool.reshape(nl, np_, rows * tpr, num_kv_heads, head_dim)
        return v.transpose(0, 3, 1, 2, 4)
    f = lane // head_dim
    return pool.reshape(nl, hkv_pool, np_, rows * f, head_dim)


def _group_q(q: jnp.ndarray, num_kv_heads: int) -> Tuple[jnp.ndarray, int]:
    """[S, Hq, D] → [S, Hkv, GP, D] with the group dim padded to >=8 rows
    (mosaic sublane tiling); head h belongs to group h // rep (HF layout)."""
    s, hq, d = q.shape
    rep = hq // num_kv_heads
    qg = q.reshape(s, num_kv_heads, rep, d)
    gp = max(8, -(-rep // 8) * 8)
    if gp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rep), (0, 0)))
    return qg, rep


def _kernel(
    # --- scalar prefetch (SMEM) ---
    layer_ref,  # [1] layer index into the pool
    lengths_ref,  # [S] cached tokens per slot
    tables_flat_ref,  # [S*PPS] logical page ids
    chunk_counts_ref,  # [S] visible chunk positions (0 = no chunk part)
    # --- inputs ---
    q_ref,  # VMEM [SB, Hkv, GP, D] (pre-scaled)
    ck_ref,  # VMEM [SB, Hkv, T, D] chunk keys
    cv_ref,  # VMEM [SB, Hkv, T, D]
    k_hbm_ref,  # ANY [L, Hkv, NP, BS//f, f*D] (lane-packed pages)
    v_hbm_ref,  # ANY
    # --- outputs ---
    o_ref,  # VMEM [SB, Hkv, GP, D]
    # --- scratch ---
    k_vmem,  # [2, SB, Hkv, PPCB, BS//f, f*D] pool dtype
    v_vmem,
    sem_k,  # DMA (2,)
    sem_v,
    acc_ref,  # VMEM f32 [SB, Hkv, GP, D]
    m_ref,  # VMEM f32 [SB, Hkv, GP, 1]
    l_ref,  # VMEM f32 [SB, Hkv, GP, 1]
    *,
    pps: int,
    ppcb: int,
    sb: int,  # slots per grid step (grid-step overhead amortizer)
    num_kv_heads: int,
    page_size: int,
    pack: int,  # tokens per 128-lane pool row (f)
    head_dim: int,
    has_chunk: bool,
    merged: bool,  # head-merged rows: pool hkv dim is 1, heads in lanes
):
    grp = pl.program_id(0)
    li = layer_ref[0]
    bk = ppcb * page_size
    rows = bk // pack  # packed rows per compute block
    hkv = num_kv_heads
    # DMA loops iterate the POOL's head dim (1 when merged: one copy per
    # page moves every head); compute still maintains per-real-head state
    hkv_dma = 1 if merged else hkv

    def slot_meta(s):
        b = grp * sb + s
        length = lengths_ref[b]
        return b, length, (length + bk - 1) // bk, (
            length + page_size - 1
        ) // page_size

    def issue(s, i, buf):
        """Start page copies for slot-in-group s, page-block i. Per-page
        predicates skip fetches past the slot's length — ragged batches
        only move the bytes they use."""
        b, _, _, pcnt = slot_meta(s)
        for j in range(ppcb):
            pidx = i * ppcb + j

            @pl.when(pidx < pcnt)
            def _go(pidx=pidx, s=s, b=b, j=j):
                # defensive clamp: a stale/fill-value table entry must not
                # DMA past the pool
                page = jnp.minimum(
                    tables_flat_ref[b * pps + pidx],
                    k_hbm_ref.shape[2] - 1,
                )
                for h in range(hkv_dma):
                    pltpu.make_async_copy(
                        k_hbm_ref.at[li, h, page],
                        k_vmem.at[buf, s, h, j],
                        sem_k.at[buf],
                    ).start()
                    pltpu.make_async_copy(
                        v_hbm_ref.at[li, h, page],
                        v_vmem.at[buf, s, h, j],
                        sem_v.at[buf],
                    ).start()

    def drain(s, i, buf):
        b, _, _, pcnt = slot_meta(s)
        for j in range(ppcb):
            pidx = i * ppcb + j

            @pl.when(pidx < pcnt)
            def _wait(pidx=pidx, s=s, b=b, j=j):
                page = jnp.minimum(
                    tables_flat_ref[b * pps + pidx],
                    k_hbm_ref.shape[2] - 1,
                )
                for h in range(hkv_dma):
                    pltpu.make_async_copy(
                        k_hbm_ref.at[li, h, page],
                        k_vmem.at[buf, s, h, j],
                        sem_k.at[buf],
                    ).wait()
                    pltpu.make_async_copy(
                        v_hbm_ref.at[li, h, page],
                        v_vmem.at[buf, s, h, j],
                        sem_v.at[buf],
                    ).wait()

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    nb_group = 0
    for s in range(sb):
        nb_group = jnp.maximum(nb_group, slot_meta(s)[2])

    for s in range(sb):
        issue(s, 0, 0)

    def online_update(s, h, qk, v_list):
        """qk [GP, C] f32 (masked); v_list: per lane-group [C/len, D] whose
        rows match qk's column segments (kept separate — mosaic can't
        concat vectors with different lane offsets)."""
        m_prev, l_prev = m_ref[s, h], l_ref[s, h]
        m_curr = jnp.max(qk, axis=-1, keepdims=True)  # [GP, 1]
        m_next = jnp.maximum(m_prev, m_curr)
        p = jnp.exp(qk - m_next)  # [GP, C]
        alpha = jnp.exp(m_prev - m_next)  # [GP, 1]
        l_ref[s, h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[s, h] = m_next
        acc = acc_ref[s, h] * alpha
        seg = qk.shape[1] // len(v_list)
        for g, vg in enumerate(v_list):
            acc = acc + jax.lax.dot_general(
                p[:, g * seg : (g + 1) * seg], vg,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_ref[s, h] = acc

    def page_block(i, _):
        buf = jax.lax.rem(i, 2)
        for s in range(sb):
            _, _, nb_s, _ = slot_meta(s)

            @pl.when(i + 1 < nb_s)
            def _prefetch(s=s, i=i, buf=buf):
                issue(s, i + 1, 1 - buf)
        # drain EVERY slot's copies before any compute touches the buffer:
        # the per-buffer semaphore is a counter shared by the whole group,
        # so per-slot waits only prove "as many completions as waits", not
        # "this slot's pages arrived" — all-waits-then-read does.
        for s in range(sb):
            _, _, nb_s, _ = slot_meta(s)

            @pl.when(i < nb_s)
            def _drain(s=s, i=i, buf=buf):
                drain(s, i, buf)
        for s in range(sb):
            _, length, nb_s, _ = slot_meta(s)

            @pl.when(i < nb_s)
            def _compute(s=s, i=i, buf=buf, length=length):
                if merged:
                    # one 128-lane buffer holds every head: lane group
                    # l = fi*Hkv + h is (token i*bk + row*pack + fi,
                    # head h). Per-head score/value segments accumulate
                    # into that head's online-softmax state.
                    lanes = pack * hkv * head_dim
                    k = k_vmem[buf, s, 0].astype(jnp.float32).reshape(
                        rows, lanes
                    )
                    v = v_vmem[buf, s, 0].astype(jnp.float32).reshape(
                        rows, lanes
                    )
                    riota = None
                    vrow = None
                    qks = [[] for _ in range(hkv)]
                    vs = [[] for _ in range(hkv)]
                    for l in range(pack * hkv):
                        fi, h = divmod(l, hkv)
                        kg = k[:, l * head_dim : (l + 1) * head_dim]
                        q = q_ref[s, h].astype(jnp.float32)  # [GP, D]
                        qk_g = jax.lax.dot_general(
                            q, kg, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )  # [GP, rows]
                        if riota is None:
                            riota = jax.lax.broadcasted_iota(
                                jnp.int32, qk_g.shape, 1
                            )
                            vrow = jax.lax.broadcasted_iota(
                                jnp.int32, (rows, 1), 0
                            )
                        col = i * bk + riota * pack + fi
                        qks[h].append(
                            jnp.where(col < length, qk_g, NEG_INF)
                        )
                        vg = v[:, l * head_dim : (l + 1) * head_dim]
                        vcol = i * bk + vrow * pack + fi
                        vs[h].append(jnp.where(vcol < length, vg, 0.0))
                    for h in range(hkv):
                        qk = (
                            jnp.concatenate(qks[h], axis=-1)
                            if len(qks[h]) > 1
                            else qks[h][0]
                        )
                        online_update(s, h, qk, vs[h])
                    return
                for h in range(hkv):
                    q = q_ref[s, h].astype(jnp.float32)  # [GP, D]
                    k = k_vmem[buf, s, h].astype(jnp.float32).reshape(
                        rows, pack * head_dim
                    )
                    v = v_vmem[buf, s, h].astype(jnp.float32).reshape(
                        rows, pack * head_dim
                    )
                    qks, vs = [], []
                    riota = None
                    vrow = None
                    for g in range(pack):
                        kg = k[:, g * head_dim : (g + 1) * head_dim]
                        qk_g = jax.lax.dot_general(
                            q, kg, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )  # [GP, rows]
                        if riota is None:
                            riota = jax.lax.broadcasted_iota(
                                jnp.int32, qk_g.shape, 1
                            )
                            # same iota viewed column-wise for v rows
                            vrow = jax.lax.broadcasted_iota(
                                jnp.int32, (k.shape[0], 1), 0
                            )
                        col = i * bk + riota * pack + g
                        qks.append(jnp.where(col < length, qk_g, NEG_INF))
                        vg = v[:, g * head_dim : (g + 1) * head_dim]
                        # skipped/partial pages hold garbage (possibly NaN)
                        # — a 0-weight NaN still poisons the dot, so zero
                        # the out-of-length V rows explicitly
                        vcol = i * bk + vrow * pack + g
                        vs.append(jnp.where(vcol < length, vg, 0.0))
                    qk = (
                        jnp.concatenate(qks, axis=-1) if pack > 1 else qks[0]
                    )
                    online_update(s, h, qk, vs)
        return 0

    jax.lax.fori_loop(0, nb_group, page_block, 0)

    if has_chunk:
        for s in range(sb):
            b = grp * sb + s
            cnt = chunk_counts_ref[b]

            @pl.when(cnt > 0)
            def _chunk_tail(s=s, cnt=cnt):
                for h in range(hkv):
                    q = q_ref[s, h].astype(jnp.float32)
                    ck = ck_ref[s, h].astype(jnp.float32)  # [T, D]
                    cv = cv_ref[s, h].astype(jnp.float32)
                    qk = jax.lax.dot_general(
                        q, ck, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [GP, T]
                    t = jax.lax.broadcasted_iota(jnp.int32, qk.shape, 1)
                    qk = jnp.where(t < cnt, qk, NEG_INF)
                    online_update(s, h, qk, [cv])

    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "pages_per_compute_block", "slots_per_block", "interpret",
        "num_kv_heads",
    ),
)
def paged_decode_attention(
    q: jnp.ndarray,  # [S, Hq, D]
    k_pages: jnp.ndarray,  # [L, Hkv, NP, BS//f, f*D] (packed_pool_shape)
    v_pages: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32 layer index
    lengths: jnp.ndarray,  # [S] int32 cached tokens per slot
    tables: jnp.ndarray,  # [S, PPS] int32 logical page ids
    chunk_k: Optional[jnp.ndarray] = None,  # [S, Hkv, T, D]
    chunk_v: Optional[jnp.ndarray] = None,
    chunk_counts: Optional[jnp.ndarray] = None,  # [S] int32
    *,
    pages_per_compute_block: int = 8,
    slots_per_block: int = 8,
    interpret: bool = False,
    num_kv_heads: Optional[int] = None,  # required for head-merged pools
) -> jnp.ndarray:
    """out[s] = softmax-attention of q[s] over the slot's cached pages
    [0, lengths[s]) plus, when a chunk buffer is given, the in-flight chunk
    positions [0, chunk_counts[s]) (which sit after the cached window).
    Returns [S, Hq, D] in q.dtype.

    ``slots_per_block`` slots share one grid step (per-step overhead is the
    dominant cost at serving shapes; DMA skip predicates keep ragged
    batches cheap). A head-merged pool (pool head dim 1 < num_kv_heads,
    ops.paged_attention.pool_layout) halves the per-page DMA count.

    Row-compact batches (r6 decode tail compaction): S is the engine's
    ACTIVE row bucket, not max_num_seqs — q/lengths/tables/chunk buffers
    are gathered per active slot before the call. Any S >= 1 works: the
    slot grouping degrades to ``sb = gcd-style largest divisor <=
    slots_per_block`` and the grid shrinks with the batch, so a
    2-straggler tail dispatches a 2-row grid instead of streaming pages
    for 64 rows. Padding rows carry length 0 (+ chunk_count 0) and are
    skipped by the per-page DMA predicates.

    .. note:: **True-MQA callers must pass** ``num_kv_heads=1``. Since the
       head-merged layout landed, a pool with kv-head dim 1 under a
       multi-head ``q`` is ambiguous (true MQA vs merged GQA heads) and
       guessing wrong returns finite garbage — so the kernel raises
       instead of defaulting. This is a breaking change relative to pre-r5
       behavior for external tooling that called the kernel on MQA pools
       without the kwarg; all in-repo callers pass it."""
    s, hq, d = q.shape
    nl, hkv_pool, np_, prow, fd = k_pages.shape
    if hkv_pool == 1 and hq > 1 and num_kv_heads is None:
        # a [*, 1, ...] pool is ambiguous (true MQA vs head-merged) and
        # guessing MQA on a merged pool returns finite GARBAGE — demand
        # the caller say which
        raise ValueError(
            "pool has kv-head dim 1 with multi-head q: pass num_kv_heads "
            "explicitly (1 for true MQA; the model's Hkv for a "
            "head-merged pool)"
        )
    hkv = num_kv_heads or hkv_pool
    merged, f = layout_from_pool(k_pages.shape, hkv, d)
    if not merged and hkv != hkv_pool:
        # a mismatched head count on a token-packed pool would DMA past
        # the pool's head dim — finite garbage, not a shape error
        raise ValueError(
            f"num_kv_heads={hkv} contradicts token-packed pool head dim "
            f"{hkv_pool}"
        )
    bs = prow * f
    sb = min(slots_per_block, s)
    while s % sb:
        sb -= 1
    qg, rep = _group_q(q * (d**-0.5), hkv)
    gp = qg.shape[2]
    ppcb = pages_per_compute_block
    pps = tables.shape[1]
    if pps % ppcb:
        pad = ppcb - pps % ppcb
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
        pps += pad
    has_chunk = chunk_k is not None
    if not has_chunk:
        t = 8
        chunk_k = jnp.zeros((s, hkv, t, d), k_pages.dtype)
        chunk_v = jnp.zeros((s, hkv, t, d), k_pages.dtype)
        chunk_counts = jnp.zeros((s,), jnp.int32)
    t = chunk_k.shape[2]

    grid = (s // sb,)
    kernel = functools.partial(
        _kernel,
        pps=pps,
        ppcb=ppcb,
        sb=sb,
        num_kv_heads=hkv,
        page_size=bs,
        pack=f,
        head_dim=d,
        has_chunk=has_chunk,
        merged=merged,
    )
    hkv_vmem = 1 if merged else hkv
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (sb, hkv, gp, d), lambda b, *_: (b, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (sb, hkv, t, d), lambda b, *_: (b, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (sb, hkv, t, d), lambda b, *_: (b, 0, 0, 0)
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (sb, hkv, gp, d), lambda b, *_: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, sb, hkv_vmem, ppcb, prow, fd), k_pages.dtype),
                pltpu.VMEM((2, sb, hkv_vmem, ppcb, prow, fd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((sb, hkv, gp, d), jnp.float32),
                pltpu.VMEM((sb, hkv, gp, 1), jnp.float32),
                pltpu.VMEM((sb, hkv, gp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, hkv, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32).reshape(-1),
        chunk_counts.astype(jnp.int32),
        qg,
        chunk_k,
        chunk_v,
        k_pages,
        v_pages,
    )
    return out[:, :, :rep].reshape(s, hq, d)


def paged_decode_attention_jnp(
    q: jnp.ndarray,  # [S, Hq, D]
    k_pages: jnp.ndarray,  # [L, Hkv, NP, BS, D]
    v_pages: jnp.ndarray,
    layer: jnp.ndarray,
    lengths: jnp.ndarray,  # [S]
    tables: jnp.ndarray,  # [S, PPS]
    chunk_k: Optional[jnp.ndarray] = None,  # [S, Hkv, T, D]
    chunk_v: Optional[jnp.ndarray] = None,
    chunk_counts: Optional[jnp.ndarray] = None,
    num_kv_heads: Optional[int] = None,
    **_: object,
) -> jnp.ndarray:
    """Gather-based fallback with identical semantics (CPU / TP serving).

    Gathers each slot's page window at full-row granularity (a pool view
    with trailing dim < 128 lanes would force a relaid full-pool copy on
    TPU), then splits lane-halves — key order is [half0 rows..., half1
    rows..., chunk], which softmax doesn't care about. ~3x the HBM
    traffic of the kernel; correctness-first path. Head-merged pools are
    unpacked to the per-head view first (one extra relayout — fine for
    the CPU/TP correctness path). Like the kernel, accepts row-compact
    batches: S may be the engine's active row bucket with per-row
    gathered tables; length-0 padding rows hit the all-masked softmax
    guard and return zeros.
    """
    s, hq, d = q.shape
    nl, hkv_pool, np_, prow, fd = k_pages.shape
    if hkv_pool == 1 and hq > 1 and num_kv_heads is None:
        raise ValueError(
            "pool has kv-head dim 1 with multi-head q: pass num_kv_heads "
            "explicitly (1 for true MQA; the model's Hkv for a "
            "head-merged pool)"
        )
    hkv = num_kv_heads or hkv_pool
    pps = tables.shape[1]
    merged_, tpr = layout_from_pool(k_pages.shape, hkv, d)
    if not merged_ and hkv != hkv_pool:
        raise ValueError(
            f"num_kv_heads={hkv} contradicts token-packed pool head dim "
            f"{hkv_pool}"
        )
    if merged_:  # head-merged rows -> per-head token rows
        kl = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False)

        def unmerge(x):  # [1, NP, BS//tpr, 128] -> [Hkv, NP*BS, D]
            y = x.reshape(np_, prow * tpr, hkv, d)
            return y.transpose(2, 0, 1, 3).reshape(hkv, np_ * prow * tpr, d)

        klh, vlh = unmerge(kl), unmerge(vl)
        bs = prow * tpr
        wr = pps * bs  # window rows are single tokens here
        rflat = (
            tables[:, :, None] * bs + jnp.arange(bs)[None, None, :]
        )
        rflat = jnp.clip(rflat.reshape(s, wr), 0, np_ * bs - 1)
        win_k = klh[:, rflat]  # [Hkv, S, WR, D]
        win_v = vlh[:, rflat]
        f = 1
    else:
        f = fd // d
        bs = prow * f
        wr = pps * prow  # window rows
        kl = jax.lax.dynamic_index_in_dim(
            k_pages.reshape(nl, hkv, np_ * prow, fd), layer, 0,
            keepdims=False,
        )
        vl = jax.lax.dynamic_index_in_dim(
            v_pages.reshape(nl, hkv, np_ * prow, fd), layer, 0,
            keepdims=False,
        )
        # flat row ids per slot: page-major row order
        rflat = (
            tables[:, :, None] * prow + jnp.arange(prow)[None, None, :]
        )
        rflat = jnp.clip(rflat.reshape(s, wr), 0, np_ * prow - 1)
        win_k = kl[:, rflat]  # [Hkv, S, WR, FD]
        win_v = vl[:, rflat]
    rep = hq // hkv
    qg = q.reshape(s, hkv, rep, d)
    scale = d**-0.5
    rpos = jnp.arange(wr)[None, None, None, :] * f  # token pos of row start
    qks, vhs = [], []
    for g in range(f):
        wk = win_k[..., g * d : (g + 1) * d]  # [Hkv, S, WR, D]
        vhs.append(win_v[..., g * d : (g + 1) * d])
        qk_g = (
            jnp.einsum(
                "sgrd,gskd->sgrk", qg, wk,
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [S, Hkv, rep, WR]
        mask = rpos + g < lengths[:, None, None, None]
        qks.append(jnp.where(mask, qk_g, NEG_INF))
    qk = jnp.concatenate(qks, axis=-1)  # [S, Hkv, rep, f*WR]
    if chunk_k is not None:
        tl = chunk_k.shape[2]
        qc = (
            jnp.einsum(
                "sgrd,sgtd->sgrt", qg, chunk_k,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        tcol = jnp.arange(tl)[None, None, None, :]
        qc = jnp.where(tcol < chunk_counts[:, None, None, None], qc, NEG_INF)
        qk = jnp.concatenate([qk, qc], axis=-1)
    # guard fully-masked rows (length 0, no chunk): softmax of all -inf
    all_masked = jnp.all(qk <= NEG_INF / 2, axis=-1, keepdims=True)
    p = jax.nn.softmax(jnp.where(all_masked, 0.0, qk), axis=-1)
    p = jnp.where(all_masked, 0.0, p)
    out = jnp.zeros((s, hkv, rep, d), jnp.float32)
    for g in range(f):
        out = out + jnp.einsum(
            "sgrk,gskd->sgrd",
            p[..., g * wr : (g + 1) * wr].astype(vhs[g].dtype), vhs[g],
            preferred_element_type=jnp.float32,
        )
    if chunk_k is not None:
        out = out + jnp.einsum(
            "sgrt,sgtd->sgrd",
            p[..., f * wr :].astype(chunk_v.dtype), chunk_v,
            preferred_element_type=jnp.float32,
        )
    return out.reshape(s, hq, d).astype(q.dtype)
