"""A window-attention layer's band through kernels of its own: a forward and
ONE backward Pallas kernel over key strips (``ops/flash.py`` sends a band
here; PERF.md, PR 47).

A query of a layer whose window is ``W`` keys (its own among them) sees the
``W - 1`` keys before it and itself, whatever the row's length. The installed
splash kernel reads that band as square tiles, a head a call, and its split
backward forms the scores in two kernels. These are built for the band:

- **strips**: a block of ``bq`` queries meets the ``n_back + 1`` key blocks of
  ``bq`` keys that end with its own (``n_back = ceil((W - 1) / bq)``), no
  others: ``(W + bq) / W`` of the pairs it needs where a square tile of 512
  holds 2 (W 512) or 1.5 (W 1024). The band, the causal border and the row's
  segment ids (padding is id 0) mask inside the strip, in float32;
- **the query heads of a key head are one block**: ``[rep * bq, D]`` rows
  against a strip of K and V that is loaded once, so a grid step's fixed cost
  is paid once a group, and dk, dv sum over the group inside ``dS^T Q``;
- **the scores are formed TRANSPOSED**, keys on sublanes and the stacked
  queries on lanes (``S^T = K Q^T``): the running maximum and sum, the
  logsumexp and ``di`` are rows, stored and broadcast as rows, and every
  reduction over keys runs down sublanes;
- **the backward is one pass**: S and P once, five products a key block
  (``S^T``, ``dP^T = V dO^T``, ``dV += P^T dO``, ``dK += dS^T Q``,
  ``dQ^T += K^T dS^T``), from the forward's output and logsumexp
  (``di = dO . O`` is formed in the kernel). dq of a query block is complete
  inside its own grid step and is written once, in the compute dtype: no
  partial dq in HBM. dk and dv of a key block take contributions from
  ``n_back + 1`` neighbouring query blocks and live in a float32 VMEM ring
  that rolls along the sequential grid axis over the row: a block is written
  out when the last query block that sees it has passed (the grid has
  ``n_back`` steps past the row's end that only write);
- K (and K^T, V^T: transposed once a block), V and the keys' segment ids ride
  a ring of the same length in both kernels, so each is read from HBM once;
- they read and write ``[T, heads * D]``, the projections' own layout: a
  block is ``bq`` tokens of one key head's group, ``rep * D`` lanes wide;
- a query block of padding alone, and a key block whose sequences have all
  ended before the query block's first begins, computes nothing (two block
  summaries of the segment ids, scalar-prefetched: ``flash.tiles_needed``'s
  rule on strips).

bf16 (or float32) operands as stored, float32 scores, softmax and
accumulation; every pair of the window is computed.
"""

import functools

import jax
import jax.numpy as jnp
from jax._src import ad_checkpoint as _ad_checkpoint
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # as the splash kernel

# Queries a strip (and keys a block of one). A smaller block holds fewer
# pairs outside the band, (W + bq) / W of those inside, and pays more grid
# steps of smaller products. Device ms a layer on the v5e, the two kernels
# and in brackets the whole program with the relayouts of its [T, heads, 128]
# arguments, forward | forward + backward (``tools/microbench_attn_v2.py
# --backward strip --bq 128 256 512`` on the cells' packed rows; PERF.md,
# PR 47), against the splash kernel at ``flash.BAND_EDGE`` and its split
# backward:
#
# ====================== ============== ============== ============== =======
# rows                   bq 128         bq 256         bq 512         splash
# ====================== ============== ============== ============== =======
# T 16384, 64/8, W 512   3.95 | 10.19   3.44 | 9.88    3.95 | 11.86
#                        (5.89 | 12.74) (5.38 | 12.43) (      | 14.42) 8.00 | 25.49
# T 32768, 32/4, W 1024  6.39 | 16.40   5.46 | 15.27   5.99 | 17.36
#                        (8.35 | 18.97) (7.43 | 17.85) (      | 19.94) 10.17 | 34.41
# T 2048, 64/8, W 512                   (0.42 | 1.06)  (0.47 | 1.27)  0.93 | 2.74
# ====================== ============== ============== ============== =======
#
# so 256: the forward, whose two products a block leave the VPU the
# bottleneck, gains 13-15% over 128 and the backward is within 3% either way.
STRIP_BQ = 256


def strip_block(t: int, back: int, d: int) -> int:
    """The query block of the strip kernel on a row of ``t`` tokens whose band
    reaches ``back`` keys behind a query (``flash.instance``), heads ``d``
    wide; 0 where the kernel does not take the band: a head that is not whole
    lane rows, or a row that is not a whole number of blocks."""
    if d % _LANES or not 0 < back < t or t % STRIP_BQ:
        return 0
    return STRIP_BQ


def _tile_lanes(x, reps: int):
    return x if reps == 1 else jnp.concatenate([x] * reps, axis=1)


def _stacked(ref, rep: int, d: int):
    """A block ``[bq, rep * d]`` of a key head's group as ``[rep * bq, d]``:
    the group's heads as rows, one after another."""
    return jnp.concatenate(
        [ref[:, r * d:(r + 1) * d] for r in range(rep)], axis=0
    )


def _scaled_queries(q_ref, rep: int, d: int):
    """The group's queries stacked and scaled, rounded to the compute dtype
    once: what the forward's scores and the backward's are both formed from."""
    q = _stacked(q_ref, rep, d)
    return (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)


def _in_a_row(ref, rep: int):
    """A block ``[rep, bq]`` of per-query float32 as one row ``[1, rep * bq]``,
    in ``_stacked``'s order."""
    return jnp.concatenate([ref[r:r + 1, :] for r in range(rep)], axis=1)


def _row_dots(a_ref, b_ref, rep: int, d: int):
    """The dot product of two blocks ``[bq, rep * d]`` a query and head, in
    float32, as one row ``[1, rep * bq]`` in ``_stacked``'s order (the
    backward's ``di = dO . O``): the sum runs over sublanes of the transposed
    product, so that it lands on lanes as the scores' queries do."""
    return jnp.concatenate([
        (a_ref[:, r * d:(r + 1) * d].astype(jnp.float32)
         * b_ref[:, r * d:(r + 1) * d].astype(jnp.float32)
         ).T.sum(axis=0, keepdims=True)
        for r in range(rep)
    ], axis=1)


def _ahead(bq: int, rep: int):
    """Query offset less key offset inside two blocks of one position,
    ``[bq keys, rep * bq queries]``."""
    return _tile_lanes(
        jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
        - jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0),
        rep,
    )


def _kept(s: int, kseg, qseg, ahead, bq: int, back: int):
    """Which pairs of the strip's ``s``-th key block (0 = the query block's
    own) are attended, ``[bq keys, rows]``: one sequence, no padding, inside
    the causal border and the band's far one."""
    keep = jnp.logical_and(
        _tile_lanes(kseg, qseg.shape[1] // _LANES) == qseg, qseg > 0
    )
    if s == 0:
        keep = jnp.logical_and(keep, ahead >= 0)
    if (s + 1) * bq - 1 > back:
        keep = jnp.logical_and(keep, ahead <= back - s * bq)
    return keep


def _strip_blocks(lo_ref, hi_ref, b, i, ring: int, compute):
    """``compute(s, slot)`` for each key block of query block ``i``'s strip
    that holds a key of a sequence in it, the nearest first."""
    for s in range(ring):
        j = jnp.maximum(i - s, 0)

        @pl.when(jnp.logical_and(i >= s, hi_ref[b, j] >= lo_ref[b, i]))
        def _(s=s, j=j):
            compute(s, jax.lax.rem(j, ring))


def _fwd_kernel(
    lo_ref, hi_ref,  # scalar-prefetched [B, n_q]: a block's least / largest id
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    out_ref, lse_ref,
    k_ring, vt_ring, seg_ring, m_acc, l_acc, out_acc,
    *, rep: int, bq: int, d: int, n_back: int, back: int,
):
    b, i = pl.program_id(0), pl.program_id(2)
    ring = n_back + 1
    slot = jax.lax.rem(i, ring)
    k_ring[slot] = k_ref[...]
    vt_ring[slot] = v_ref[...].T
    seg_ring[slot] = kseg_ref[...]
    live = hi_ref[b, i] > 0

    @pl.when(live)
    def _strip():
        q = _scaled_queries(q_ref, rep, d)
        qseg = _tile_lanes(qseg_ref[...], rep)  # [1, rows]
        ahead = _ahead(bq, rep)
        m_acc[...] = jnp.full_like(m_acc, _MASKED)
        l_acc[...] = jnp.zeros_like(l_acc)
        out_acc[...] = jnp.zeros_like(out_acc)

        def block(s, slot):
            keep = _kept(s, seg_ring[slot], qseg, ahead, bq, back)
            st = jax.lax.dot_general(
                k_ring[slot], q, _NT, preferred_element_type=jnp.float32
            )
            st = jnp.where(keep, st, _MASKED)
            m_prev = m_acc[...]
            m_next = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
            # a real query's own key is in its first block, so its
            # ``m_next`` is a score from there on and a masked pair's
            # exponential is 0; a padding query's row is zeroed at the end
            p = jnp.exp(st - m_next)
            alpha = jnp.exp(m_prev - m_next)
            l_acc[...] = alpha * l_acc[...] + p.sum(axis=0, keepdims=True)
            m_acc[...] = m_next
            vt = vt_ring[slot]
            out_acc[...] = alpha * out_acc[...] + jnp.dot(
                vt, p.astype(vt.dtype), preferred_element_type=jnp.float32
            )

        _strip_blocks(lo_ref, hi_ref, b, i, ring, block)
        real = qseg > 0
        l = l_acc[...]
        out = out_acc[...] * jnp.where(real, 1.0 / l, 0.0)
        lse = jnp.where(real, m_acc[...] + jnp.log(l), 0.0)
        for r in range(rep):
            mine = slice(r * bq, (r + 1) * bq)
            out_ref[:, r * d:(r + 1) * d] = out[:, mine].T.astype(out_ref.dtype)
            lse_ref[r:r + 1, :] = lse[:, mine]

    @pl.when(jnp.logical_not(live))
    def _padding():
        out_ref[...] = jnp.zeros_like(out_ref)
        lse_ref[...] = jnp.zeros_like(lse_ref)


def _bwd_kernel(
    lo_ref, hi_ref,
    q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qseg_ref, kseg_ref,
    dq_ref, dk_ref, dv_ref,
    k_ring, kt_ring, v_ring, seg_ring, dk_ring, dv_ring, dq_acc,
    *, rep: int, bq: int, d: int, n_q: int, n_back: int, back: int,
):
    b, i = pl.program_id(0), pl.program_id(2)
    ring = n_back + 1
    in_row = i < n_q  # the steps past the row's end only write dk and dv

    @pl.when(in_row)
    def _take_keys():
        slot = jax.lax.rem(i, ring)
        k = k_ref[...]
        k_ring[slot] = k
        kt_ring[slot] = k.T
        v_ring[slot] = v_ref[...]
        seg_ring[slot] = kseg_ref[...]
        dk_ring[slot] = jnp.zeros((bq, d), jnp.float32)
        dv_ring[slot] = jnp.zeros((bq, d), jnp.float32)

    i_q = jnp.minimum(i, n_q - 1)
    live = jnp.logical_and(in_row, hi_ref[b, i_q] > 0)

    @pl.when(live)
    def _strip():
        q = _scaled_queries(q_ref, rep, d)
        do = _stacked(do_ref, rep, d)
        lse = _in_a_row(lse_ref, rep)
        di = _row_dots(do_ref, out_ref, rep, d)
        qseg = _tile_lanes(qseg_ref[...], rep)  # [1, rows]
        ahead = _ahead(bq, rep)
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def block(s, slot):
            k, v = k_ring[slot], v_ring[slot]
            keep = _kept(s, seg_ring[slot], qseg, ahead, bq, back)
            st = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32
            )
            p = jnp.where(keep, jnp.exp(st - lse), 0.0)
            dv_ring[slot] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=jnp.float32
            )
            dp = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32
            )
            ds = (p * (dp - di)).astype(k.dtype)
            dk_ring[slot] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq_acc[...] += jnp.dot(
                kt_ring[slot], ds, preferred_element_type=jnp.float32
            )

        _strip_blocks(lo_ref, hi_ref, b, i_q, ring, block)
        for r in range(rep):
            dq = dq_acc[:, r * bq:(r + 1) * bq].T * d ** -0.5
            dq_ref[:, r * d:(r + 1) * d] = dq.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(in_row, jnp.logical_not(live)))
    def _padding():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(i >= n_back)  # the last query block that sees it has passed
    def _write_keys():
        slot = jax.lax.rem(i - n_back, ring)
        dk_ref[...] = dk_ring[slot].astype(dk_ref.dtype)
        dv_ref[...] = dv_ring[slot].astype(dv_ref.dtype)


def _call(kernel, name, bsz, hkv, steps, in_specs, out_specs, out_shape,
          scratch, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, hkv, steps), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        out_shape=out_shape, name=name, interpret=interpret,
    )


def _segments(segment_ids, bq: int):
    """The row's segment ids as the kernels read them: a block's least
    non-zero id and its largest ``[B, n_q]`` (scalar-prefetched), the ids as
    a row ``[B, 1, T]`` (the queries') and across lanes ``[B, T, 128]`` (the
    keys', whose block is then a column of the transposed scores)."""
    seg = segment_ids.astype(jnp.int32)
    bsz, t = seg.shape
    blocks = seg.reshape(bsz, t // bq, bq)
    lo = jnp.where(blocks > 0, blocks, jnp.iinfo(jnp.int32).max).min(-1)
    across = jnp.broadcast_to(seg[:, :, None], (bsz, t, _LANES))
    return lo, blocks.max(-1), seg[:, None, :], across


def _specs(rep: int, bq: int, d: int, n_q: int):
    """Block specs over a (row, key head, query block) grid whose last axis
    may run past the row's end (the block index then stays the last): a
    group's ``[bq, rep * d]``, a key head's ``[bq, d]``, per-query rows
    ``[rep, bq]``, the queries' ids and the keys'."""
    def at(i):
        return jnp.minimum(i, n_q - 1)

    return (
        pl.BlockSpec((None, bq, rep * d), lambda b, h, i, *_: (b, at(i), h)),
        pl.BlockSpec((None, bq, d), lambda b, h, i, *_: (b, at(i), h)),
        pl.BlockSpec(
            (None, None, rep, bq), lambda b, h, i, *_: (b, h, 0, at(i))
        ),
        pl.BlockSpec((None, 1, bq), lambda b, h, i, *_: (b, 0, at(i))),
        pl.BlockSpec((None, bq, _LANES), lambda b, h, i, *_: (b, at(i), 0)),
    )


@functools.partial(
    jax.jit, static_argnames=("heads", "back", "bq", "interpret")
)
def band_forward(q, k, v, segment_ids, *, heads, back: int, bq: int,
                 interpret: bool = False):
    """Causal attention under a band ``back`` keys behind a query over packed
    rows: (out [B, T, Hq * D], logsumexp [B, Hkv, rep, T] float32). ``q`` [B,
    T, Hq * D] unscaled and ``k``, ``v`` [B, T, Hkv * D], ``heads`` = (Hq,
    Hkv), ``segment_ids`` [B, T]; a padding query's output is 0. Jitted, so
    that a program which meets the call again (each row length, forward and
    recomputed) lowers the kernel's body once a shape."""
    bsz, t, _ = q.shape
    hq, hkv = heads
    d = q.shape[2] // hq
    rep, n_q, ring = hq // hkv, t // bq, -(-back // bq) + 1
    lo, hi, seg_row, seg_across = _segments(segment_ids, bq)
    q_spec, k_spec, row_spec, qseg_spec, kseg_spec = _specs(rep, bq, d, n_q)
    return _call(
        functools.partial(
            _fwd_kernel, rep=rep, bq=bq, d=d, n_back=ring - 1, back=back
        ),
        "band_strip_fwd", bsz, hkv, n_q,
        [q_spec, k_spec, k_spec, qseg_spec, kseg_spec], [q_spec, row_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bsz, hkv, rep, t), jnp.float32)],
        [pltpu.VMEM((ring, bq, d), k.dtype),
         pltpu.VMEM((ring, d, bq), v.dtype),
         pltpu.VMEM((ring, bq, _LANES), jnp.int32),
         pltpu.VMEM((1, rep * bq), jnp.float32),
         pltpu.VMEM((1, rep * bq), jnp.float32),
         pltpu.VMEM((d, rep * bq), jnp.float32)],
        interpret,
    )(lo, hi, q, k, v, seg_row, seg_across)


@functools.partial(
    jax.jit, static_argnames=("heads", "back", "bq", "interpret")
)
def band_backward(q, k, v, segment_ids, out, lse, do, *, heads, back: int,
                  bq: int, interpret: bool = False):
    """(dq, dk, dv) of ``band_forward`` from its ``out`` and ``lse`` and the
    cotangent ``do`` [B, T, Hq * D]. A padding query (id 0) gives and takes
    no gradient. Jitted as the forward is."""
    bsz, t, _ = q.shape
    hq, hkv = heads
    d = q.shape[2] // hq
    rep, n_q, n_back = hq // hkv, t // bq, -(-back // bq)
    ring = n_back + 1
    lo, hi, seg_row, seg_across = _segments(segment_ids, bq)
    q_spec, k_spec, row_spec, qseg_spec, kseg_spec = _specs(rep, bq, d, n_q)
    written = pl.BlockSpec(
        (None, bq, d),
        lambda b, h, i, *_: (b, jnp.clip(i - n_back, 0, n_q - 1), h),
    )
    return _call(
        functools.partial(
            _bwd_kernel, rep=rep, bq=bq, d=d, n_q=n_q, n_back=n_back,
            back=back,
        ),
        "band_strip_bwd", bsz, hkv, n_q + n_back,
        [q_spec, k_spec, k_spec, q_spec, q_spec, row_spec, qseg_spec,
         kseg_spec],
        [q_spec, written, written],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((ring, bq, d), k.dtype),
         pltpu.VMEM((ring, d, bq), k.dtype),
         pltpu.VMEM((ring, bq, d), v.dtype),
         pltpu.VMEM((ring, bq, _LANES), jnp.int32),
         pltpu.VMEM((ring, bq, d), jnp.float32),
         pltpu.VMEM((ring, bq, d), jnp.float32),
         pltpu.VMEM((d, rep * bq), jnp.float32)],
        interpret,
    )(lo, hi, q, k, v, do, out, lse, seg_row, seg_across)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def band_attention(q, k, v, segment_ids, heads, back, bq, interpret):
    """Causal attention of packed rows under a layer's band ``back`` keys
    behind a query, over strips of ``bq`` queries: ``q`` [B, T, Hq * D],
    ``k``, ``v`` [B, T, Hkv * D] (the projections' own layout: a head is 128
    lanes of a token's row), ``heads`` = (Hq, Hkv), ``segment_ids`` [B, T]
    -> [B, T, Hq * D], padding's rows 0. Differentiable in q, k and v."""
    return _band_fwd(q, k, v, segment_ids, heads, back, bq, interpret)[0]


def _band_fwd(q, k, v, segment_ids, heads, back, bq, interpret):
    out, lse = band_forward(
        q, k, v, segment_ids, heads=heads, back=back, bq=bq,
        interpret=interpret,
    )
    # what a remat policy saving "attn_out" keeps, so that the backward does
    # not run the forward kernel again (as the splash kernel names its own)
    out = _ad_checkpoint.checkpoint_name(out, "attn_out")
    lse = _ad_checkpoint.checkpoint_name(lse, "attn_out")
    return out, (q, k, v, segment_ids, out, lse)


def _band_bwd(heads, back, bq, interpret, res, do):
    q, k, v, segment_ids, out, lse = res
    dq, dk, dv = band_backward(
        q, k, v, segment_ids, out, lse, do.astype(q.dtype), heads=heads,
        back=back, bq=bq, interpret=interpret,
    )
    return dq, dk, dv, None


band_attention.defvjp(_band_fwd, _band_bwd)
