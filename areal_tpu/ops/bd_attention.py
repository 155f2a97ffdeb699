"""The attention core of a block-diffusion policy's training rows: a mask
that is causal between blocks and two-way inside one, over rows that hold a
clean stream and its masked copies (``ops/flash.py`` sends such rows here).

A slot of a packed row is (sequence, block, stream): ``segment_ids`` > 0 (0 =
padding), the token's position in its sequence over the block's length, and
0 for the clean stream or 1..K for the noisy stream of a denoising state
(``utils/data.pack_bd_rows`` lays a sequence out as its clean stream, then
each noisy stream, blocks ascending; BD3-LM's training layout,
arXiv:2503.09573). The mask M keeps a (query, key) pair iff both are of one
sequence and

- the key is CLEAN and its block is before the query's (any query), or
- the key is of the query's own stream AND block (the later tokens of the
  block too: two-way inside a block; a clean query's own block with it).

So a clean query at block b sees the clean keys in blocks <= b, and a
stream-s query at block b the clean keys in blocks < b and the stream-s keys
of block b; no query sees another noisy stream or a later block.

Every other core of this repo is causal in row order and drops the tiles
above the diagonal unseen. Here a noisy query's clean keys lie a stream away
and keys after the query are kept, so the mask is the kernels' own:

- **three integer rows** carry it: a key's ``code`` (sequence, then block for
  a clean key, or a range of its own a noisy stream and block) and a query's
  half-open range of clean codes ``[lo, hi)`` and its ``own`` code; a pair is
  kept iff ``lo <= code < hi`` or ``code == own``: three compares a pair, no
  ``[T, T]`` mask anywhere (``slot_codes``);
- **the tiles the layout makes empty are dropped**: block summaries of the
  same rows classify every (query block, key block) tile as empty, partial
  or full (``tile_classes``: a comparison of summaries, a superset of the
  tiles that hold a pair). The table is scalar-prefetched: an empty tile's
  grid step computes nothing and fetches nothing (its block index is the
  next kept tile's, as the splash kernel's ``data_next``), a full tile skips
  the compares;
- the query heads of a key head are one block of rows and the scores are
  formed transposed, keys on sublanes and the stacked queries on lanes, as
  ``ops/band_attention.py`` forms them and for its reasons; they read and
  write ``[T, heads * D]``, the projections' own layout;
- the backward is ONE kernel over the same table and the forward's walk,
  ``bd_attn_bwd``: a kept tile's S, P, dP and dS are formed once, from the
  forward's output and logsumexp, and dQ, dK and dV made of them (five
  products a tile). dQ is a query block's scratch, written at its last key
  block; dK and dV of a key head's WHOLE ``[T, D]`` stay in float32 VMEM
  scratch across the (row, key head)'s walk and are written once at its end
  (``bwd_resident_bytes``; the call states its VMEM limit from it). A row
  whose dK and dV pass what ``flash.instance`` gives them takes the pair
  instead: ``bd_attn_dq`` walks a query block's kept key blocks (S, dP, dQ:
  three products a tile), ``bd_attn_dkv`` a key block's kept query blocks
  (S, dP, dV, dK: four). The two forms add the same float32 terms in the
  same order.

bf16 (or float32) operands as stored, float32 scores, softmax and
accumulation. ``bd_segment_attention`` is the plain XLA form they are held
equal to: the CPU's path and what a row that is not whole tiles takes.
"""

import functools

import jax
import jax.numpy as jnp
from jax._src import ad_checkpoint as _ad_checkpoint
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.utils.tracing import device_scope

_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # as the splash kernel
_INT_MAX = int(jnp.iinfo(jnp.int32).max)

# a key's code: sequence * _SEQ + block (clean), or + _NOISY + stream *
# _STREAM + block (a noisy stream): blocks < 2^14, streams < 32, sequences a
# row < 2048 (``utils/data.pack_bd_rows`` refuses a row beyond them)
_SEQ, _NOISY, _STREAM = 1 << 20, 1 << 19, 1 << 14
MAX_BLOCKS, MAX_STREAMS, MAX_SEQS = _STREAM, _NOISY // _STREAM, 2048

# Query slots and key slots a tile (the tile table's granularity): the sweep
# on the v5e on the cell's packed rows is in ``flash.instance``'s docstring.
BQ, BK = 256, 512

# What a tile's blocks, their pipeline buffers and the compiler's own
# temporaries (a tile's float32 scores and what is made of them) may take of
# VMEM beside what the fused backward keeps resident: at 256 x 512 and 8
# query heads a key head the described v5e's compiler takes the kernel at 16
# MiB and refuses it at 12, whatever the row's length.
_WORKING = 32 << 20


def bwd_resident_bytes(t: int, d: int, itemsize: int) -> int:
    """The bytes the fused backward (``bd_attn_bwd``) keeps in VMEM across a
    (row, key head)'s walk, on the arrays it creates: dK and dV of the key
    head's ``t`` slots in float32 scratch, and their two output blocks ``[t,
    d]`` of ``itemsize`` bytes an element, one buffer each."""
    return 2 * t * d * 4 + 2 * t * d * itemsize


def kept_mask(segment_ids, blocks, streams):
    """M as a dense bool ``[..., T queries, T keys]`` from the three rows
    ``[..., T]``: the plain statement of the rule, for rows short enough to
    hold it (``bd_segment_attention``, the tests)."""
    qs, ks = segment_ids[..., :, None], segment_ids[..., None, :]
    qb, kb = blocks[..., :, None], blocks[..., None, :]
    qu, ku = streams[..., :, None], streams[..., None, :]
    return (qs == ks) & (qs > 0) & (
        ((ku == 0) & (kb < qb)) | ((ku == qu) & (kb == qb))
    )


def bd_segment_attention(q, k, v, segment_ids, blocks, streams):
    """Softmax attention under M in plain XLA: ``q`` [B, T, Hq, D], ``k``,
    ``v`` [B, T, Hkv, D], the three rows [B, T] -> [B, T, Hq, D], padding's
    rows 0. Float32 scores and softmax, as ``ops.basic.segment_attention``."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    keep = kept_mask(segment_ids, blocks, streams)  # [B, T, T]
    qg = q.reshape(b, t, hkv, rep, d)
    s = jnp.einsum(
        "bqhrd,bkhd->bhrqk", qg, k, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    s = jnp.where(keep[:, None, None], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(keep[:, None, None], p, 0.0)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(v.dtype), v)
    out = out.reshape(b, t, hq, d)
    return jnp.where((segment_ids > 0)[:, :, None, None], out, 0).astype(
        q.dtype
    )


# ------------------------------------------------------------ the three rows --
def slot_codes(segment_ids, blocks, streams, xp=jnp):
    """(key code, query lo, query hi, query own), int32 ``[..., T]`` each,
    from the three rows: a pair is kept iff ``lo <= code < hi`` or ``code ==
    own``. Padding's key code is -2 and its query's range empty and own -1,
    so padding keeps and is kept by nothing. A real slot's code rises along
    a packed row (sequences ascending; in a sequence the clean stream, then
    each noisy one, blocks ascending)."""
    i32 = xp.int32
    seg = segment_ids.astype(i32)
    blk, u = blocks.astype(i32), streams.astype(i32)
    real, clean = seg > 0, u == 0
    base = seg * _SEQ
    code = base + xp.where(clean, blk, _NOISY + u * _STREAM + blk)
    return (
        xp.where(real, code, -2),
        xp.where(real, base, 0),
        xp.where(real, base + blk + clean.astype(i32), 0),
        xp.where(real, code, -1),
    )


def tile_classes(segment_ids, blocks, streams, bq: int, bk: int, xp=jnp):
    """The TILE rule: int32 ``[..., T / bq, T / bk]`` (query block, key
    block), 0 = no pair of the tile is kept, 2 = every pair is, 1 = the
    kernel computes the tile under the compares. From block summaries of the
    codes alone (``slot_codes``): a tile is needed if its blocks' code
    ranges meet (a shared stream and block: codes rise along a row, so two
    ranges that meet share a code) or the key block's clean codes reach into
    some query's range; full if every slot of both is real, every key clean,
    and every key's code inside every query's range. A superset of the tiles
    that hold a kept pair (a query block that spans two sequences may keep a
    tile neither needs), never the reverse. ``xp``: ``jnp`` for the kernel's
    table, ``numpy`` for a host's count of the same tiles."""
    i32 = xp.int32
    code, base, hi, _ = slot_codes(segment_ids, blocks, streams, xp)
    real = code >= 0
    clean = real & (streams == 0)

    def by(a, n):  # [..., T] -> [..., T / n, n]
        return a.reshape(*a.shape[:-1], -1, n)

    def least(a, on, n):
        return by(xp.where(on, a, _INT_MAX), n).min(-1)

    def most(a, on, n):
        return by(xp.where(on, a, -1), n).max(-1)

    # query blocks (rows of the table), then key blocks (its columns)
    q_min, q_max = least(code, real, bq), most(code, real, bq)
    lo_min, hi_max = least(base, real, bq), most(hi, real, bq)
    lo_max = by(xp.where(real, base, _INT_MAX), bq).max(-1)
    hi_min = by(xp.where(real, hi, 0), bq).min(-1)
    k_min, k_max = least(code, real, bk), most(code, real, bk)
    c_min, c_max = least(code, clean, bk), most(code, clean, bk)
    all_clean = by(clean, bk).all(-1)

    def q(a):
        return a[..., :, None]

    def k(a):
        return a[..., None, :]

    own = (q(q_min) <= k(k_max)) & (k(k_min) <= q(q_max))
    before = (k(c_min) < q(hi_max)) & (k(c_max) >= q(lo_min))
    full = k(all_clean) & (q(lo_max) <= k(c_min)) & (k(c_max) < q(hi_min))
    return xp.where(full, 2, (own | before).astype(i32)).astype(i32)


def _next_kept(table):
    """For every step of a walk over ``table`` [rows, cols] (the last axis
    fastest), the column of the next step that is on, this one included, in
    the order the grid walks them: what an empty tile's block index is, so
    that the pipeline fetches nothing for it (the last kept step's after the
    last)."""
    rows, cols = table.shape
    on = (table > 0).reshape(-1)
    at = jnp.where(on, jnp.arange(on.size), on.size - 1)
    nxt = jax.lax.cummin(at, reverse=True)
    last = jnp.max(jnp.where(on, jnp.arange(on.size), 0))
    nxt = jnp.where(on[nxt], nxt, last)
    return (nxt % cols).reshape(rows, cols).astype(jnp.int32)


# ----------------------------------------------------------------- kernels --
def _tile_lanes(x, reps: int):
    return x if reps == 1 else jnp.concatenate([x] * reps, axis=1)


def _stacked(ref, rep: int, d: int):
    """A block ``[bq, rep * d]`` of a key head's group as ``[rep * bq, d]``."""
    return jnp.concatenate(
        [ref[:, r * d:(r + 1) * d] for r in range(rep)], axis=0
    )


def _scaled_queries(q_ref, rep: int, d: int):
    q = _stacked(q_ref, rep, d)
    return (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)


def _in_a_row(ref, rep: int):
    """``[rep, bq]`` per-query float32 as one row ``[1, rep * bq]``."""
    return jnp.concatenate([ref[r:r + 1, :] for r in range(rep)], axis=1)


def _row_dots(a_ref, b_ref, rep: int, d: int):
    """``di = dO . O`` a query and head, one row ``[1, rep * bq]``."""
    return jnp.concatenate([
        (a_ref[:, r * d:(r + 1) * d].astype(jnp.float32)
         * b_ref[:, r * d:(r + 1) * d].astype(jnp.float32)
         ).T.sum(axis=0, keepdims=True)
        for r in range(rep)
    ], axis=1)


def _kept(kcode_ref, qrows_ref, rep: int):
    """Which pairs of a tile are kept, ``[bk keys, rep * bq queries]``."""
    rows = rep * qrows_ref.shape[1]
    kc = _tile_lanes(kcode_ref[...], rows // _LANES)
    lo = _tile_lanes(qrows_ref[0:1, :], rep)
    hi = _tile_lanes(qrows_ref[1:2, :], rep)
    own = _tile_lanes(qrows_ref[2:3, :], rep)
    return jnp.logical_or(
        jnp.logical_and(kc >= lo, kc < hi), kc == own
    )


def _fwd_kernel(
    tile_ref, next_ref,  # scalar-prefetched [B, n_q, n_k]
    q_ref, k_ref, v_ref, qrows_ref, kcode_ref,
    out_ref, lse_ref,
    m_acc, l_acc, out_acc,
    *, rep: int, d: int, n_k: int,
):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _MASKED)
        l_acc[...] = jnp.zeros_like(l_acc)
        out_acc[...] = jnp.zeros_like(out_acc)

    def tile(masked: bool):
        q = _scaled_queries(q_ref, rep, d)
        st = jax.lax.dot_general(
            k_ref[...], q, _NT, preferred_element_type=jnp.float32
        )
        if masked:
            st = jnp.where(_kept(kcode_ref, qrows_ref, rep), st, _MASKED)
        m_prev = m_acc[...]
        m_next = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
        # a query that has met none of its keys yet reads exp(0) here; the
        # first real score it meets rescales that away (alpha = 0), and a
        # real query always meets its own key
        p = jnp.exp(st - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_acc[...] = alpha * l_acc[...] + p.sum(axis=0, keepdims=True)
        m_acc[...] = m_next
        v = v_ref[...]
        out_acc[...] = alpha * out_acc[...] + jnp.dot(
            v.T, p.astype(v.dtype), preferred_element_type=jnp.float32
        )

    cls = tile_ref[b, i, j]
    pl.when(cls == 1)(functools.partial(tile, True))
    pl.when(cls == 2)(functools.partial(tile, False))

    @pl.when(j == n_k - 1)
    def _write():
        bq = qrows_ref.shape[1]
        real = _tile_lanes(qrows_ref[2:3, :], rep) >= 0
        l = l_acc[...]
        out = out_acc[...] * jnp.where(real, 1.0 / l, 0.0)
        lse = jnp.where(real, m_acc[...] + jnp.log(l), 0.0)
        for r in range(rep):
            mine = slice(r * bq, (r + 1) * bq)
            out_ref[:, r * d:(r + 1) * d] = out[:, mine].T.astype(
                out_ref.dtype
            )
            lse_ref[r:r + 1, :] = lse[:, mine]


def _tile_terms(q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref,
                kcode_ref, rep: int, d: int, masked: bool):
    """What a backward kernel makes of a kept tile, from the forward's output
    and logsumexp: (q scaled and dO, stacked ``[rows, d]``; k; the
    probabilities P float32 and dS in the keys' dtype, ``[bk, rows]``)."""
    q = _scaled_queries(q_ref, rep, d)
    do = _stacked(do_ref, rep, d)
    k = k_ref[...]
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    p = jnp.exp(st - _in_a_row(lse_ref, rep))
    if masked:
        # a padding query's logsumexp is 0 and its scores are not: the
        # compares zero its exponentials, and a full tile holds none
        p = jnp.where(_kept(kcode_ref, qrows_ref, rep), p, 0.0)
    dp = jax.lax.dot_general(
        v_ref[...], do, _NT, preferred_element_type=jnp.float32
    )
    ds = (p * (dp - _row_dots(do_ref, out_ref, rep, d))).astype(k.dtype)
    return q, do, k, p, ds


def _write_dq(dq_acc, dq_ref, bq: int, rep: int, d: int):
    for r in range(rep):
        dq = dq_acc[:, r * bq:(r + 1) * bq].T * d ** -0.5
        dq_ref[:, r * d:(r + 1) * d] = dq.astype(dq_ref.dtype)


def _dq_kernel(
    tile_ref, next_ref,
    q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref, kcode_ref,
    dq_ref,
    dq_acc,
    *, rep: int, d: int, n_k: int,
):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(masked: bool):
        _, _, k, _, ds = _tile_terms(
            q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref,
            kcode_ref, rep, d, masked,
        )
        dq_acc[...] += jnp.dot(k.T, ds, preferred_element_type=jnp.float32)

    cls = tile_ref[b, i, j]
    pl.when(cls == 1)(functools.partial(tile, True))
    pl.when(cls == 2)(functools.partial(tile, False))

    @pl.when(j == n_k - 1)
    def _write():
        _write_dq(dq_acc, dq_ref, qrows_ref.shape[1], rep, d)


def _dkv_kernel(
    tile_ref, next_ref,  # [B, n_k, n_q]: the table transposed
    q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref, kcode_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, rep: int, d: int, n_q: int,
):
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        q, do, _, p, ds = _tile_terms(
            q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref,
            kcode_ref, rep, d, masked,
        )
        dv_acc[...] += jnp.dot(
            p.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)

    cls = tile_ref[b, j, i]
    pl.when(cls == 1)(functools.partial(tile, True))
    pl.when(cls == 2)(functools.partial(tile, False))

    @pl.when(i == n_q - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_kernel(
    tile_ref, next_ref,
    q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref, kcode_ref,
    dq_ref, dk_ref, dv_ref,  # dk, dv: a key head's whole column [T, d]
    dq_acc, dk_acc, dv_acc,
    *, rep: int, d: int, n_q: int, n_k: int,
):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bk = k_ref.shape[0]

    def key_blocks(body):
        def step(n, _):
            body(pl.ds(pl.multiple_of(n * bk, bk), bk))

        jax.lax.fori_loop(0, n_k, step, None)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_keys():
        def zero(at):
            dk_acc[at, :] = jnp.zeros((bk, d), jnp.float32)
            dv_acc[at, :] = jnp.zeros((bk, d), jnp.float32)

        key_blocks(zero)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(masked: bool):
        q, do, k, p, ds = _tile_terms(
            q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, qrows_ref,
            kcode_ref, rep, d, masked,
        )
        # a kept tile's key block is the grid's own (``next`` of a kept step)
        mine = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dv_acc[mine, :] += jnp.dot(
            p.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dk_acc[mine, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_acc[...] += jnp.dot(k.T, ds, preferred_element_type=jnp.float32)

    cls = tile_ref[b, i, j]
    pl.when(cls == 1)(functools.partial(tile, True))
    pl.when(cls == 2)(functools.partial(tile, False))

    @pl.when(j == n_k - 1)
    def _write():
        _write_dq(dq_acc, dq_ref, qrows_ref.shape[1], rep, d)

    @pl.when(jnp.logical_and(i == n_q - 1, j == n_k - 1))
    def _write_keys():
        def cast(at):
            dk_ref[at, :] = dk_acc[at, :].astype(dk_ref.dtype)
            dv_ref[at, :] = dv_acc[at, :].astype(dv_ref.dtype)

        key_blocks(cast)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, resident: int = 0):
    """``resident``: the bytes a kernel keeps across a (row, key head)'s whole
    walk (``bwd_resident_bytes``; 0: a tile's alone). Such a kernel walks the
    query blocks in order too, under a VMEM limit that is those bytes and
    ``_WORKING`` beside them."""
    outer = "arbitrary" if resident else "parallel"
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", outer, "arbitrary"),
            vmem_limit_bytes=resident + _WORKING if resident else None,
        ),
        out_shape=out_shape, name=name, interpret=interpret,
    )


def _layout(segment_ids, blocks, streams, bq: int, bk: int):
    """What the kernels read of the three rows (scope ``bd_layout``): the
    tile table and the next kept key block of a query block's walk, the
    queries' rows ``[B, 8, T]`` (lo, hi, own; five rows of padding to a
    sublane tile) and the keys' codes across lanes ``[B, T, 128]``."""
    with device_scope("bd_layout"):
        code, lo, hi, own = slot_codes(segment_ids, blocks, streams)
        table = tile_classes(segment_ids, blocks, streams, bq, bk)
        bsz, t = code.shape
        qrows = jnp.concatenate(
            [jnp.stack([lo, hi, own], axis=1),
             jnp.zeros((bsz, 5, t), jnp.int32)], axis=1,
        )
        kcode = jnp.broadcast_to(code[:, :, None], (bsz, t, _LANES))
        return table, jax.vmap(_next_kept)(table), qrows, kcode


def _specs(rep: int, bq: int, bk: int, d: int, kv_major: bool):
    """Block specs over a (row, key head, outer block, inner block) grid:
    the forward's and dq's grid is (.., query block, key block), dkv's (..,
    key block, query block); the INNER block's index comes from the walk's
    ``next`` table, so an empty tile fetches nothing."""
    if kv_major:
        def qi(b, h, j, i, tile, nxt):
            return nxt[b, j, i]

        def kj(b, h, j, i, tile, nxt):
            return j
    else:
        def qi(b, h, i, j, tile, nxt):
            return i

        def kj(b, h, i, j, tile, nxt):
            return nxt[b, i, j]

    return (
        pl.BlockSpec((None, bq, rep * d),
                     lambda b, h, *a: (b, qi(b, h, *a), h)),
        pl.BlockSpec((None, bk, d), lambda b, h, *a: (b, kj(b, h, *a), h)),
        pl.BlockSpec((None, None, rep, bq),
                     lambda b, h, *a: (b, h, 0, qi(b, h, *a))),
        pl.BlockSpec((None, 8, bq), lambda b, h, *a: (b, 0, qi(b, h, *a))),
        pl.BlockSpec((None, bk, _LANES),
                     lambda b, h, *a: (b, kj(b, h, *a), 0)),
    )


@functools.partial(jax.jit, static_argnames=("heads", "bq", "bk", "interpret"))
def bd_forward(q, k, v, segment_ids, blocks, streams, *, heads, bq: int,
               bk: int, interpret: bool = False):
    """(out [B, T, Hq * D], logsumexp [B, Hkv, rep, T] float32) of attention
    under M over packed rows: ``q`` [B, T, Hq * D] unscaled, ``k``, ``v`` [B,
    T, Hkv * D], ``heads`` = (Hq, Hkv), the three rows [B, T]."""
    bsz, t, _ = q.shape
    hq, hkv = heads
    d = q.shape[2] // hq
    rep, n_q, n_k = hq // hkv, t // bq, t // bk
    table, nxt, qrows, kcode = _layout(segment_ids, blocks, streams, bq, bk)
    q_spec, k_spec, row_spec, qrows_spec, kcode_spec = _specs(
        rep, bq, bk, d, False
    )
    return _call(
        functools.partial(_fwd_kernel, rep=rep, d=d, n_k=n_k),
        "bd_attn_fwd", (bsz, hkv, n_q, n_k),
        [q_spec, k_spec, k_spec, qrows_spec, kcode_spec],
        [q_spec, row_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bsz, hkv, rep, t), jnp.float32)],
        [pltpu.VMEM((1, rep * bq), jnp.float32),
         pltpu.VMEM((1, rep * bq), jnp.float32),
         pltpu.VMEM((d, rep * bq), jnp.float32)],
        interpret,
    )(table, nxt, q, k, v, qrows, kcode)


@functools.partial(
    jax.jit, static_argnames=("heads", "bq", "bk", "fused", "interpret")
)
def bd_backward(q, k, v, segment_ids, blocks, streams, out, lse, do, *,
                heads, bq: int, bk: int, fused: bool,
                interpret: bool = False):
    """(dq, dk, dv) of ``bd_forward`` from its ``out`` and ``lse`` and the
    cotangent ``do`` [B, T, Hq * D]. Padding gives and takes no gradient.
    ``fused``: the one kernel with a key head's dK and dV resident, or the
    pair (``flash.instance`` says which a row takes)."""
    bsz, t, _ = q.shape
    hq, hkv = heads
    d = q.shape[2] // hq
    rep, n_q, n_k = hq // hkv, t // bq, t // bk
    table, nxt, qrows, kcode = _layout(segment_ids, blocks, streams, bq, bk)
    q_spec, k_spec, row_spec, qrows_spec, kcode_spec = _specs(
        rep, bq, bk, d, False
    )
    ins = [q_spec, k_spec, k_spec, q_spec, q_spec, row_spec, qrows_spec,
           kcode_spec]
    args = (q, k, v, do, out, lse, qrows, kcode)
    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dkv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    dq_acc = pltpu.VMEM((d, rep * bq), jnp.float32)
    if fused:
        # a key head's whole column, fetched and written once a walk
        column = pl.BlockSpec(
            (None, t, d), lambda b, h, i, j, tile, nxt: (b, 0, h),
            pipeline_mode=pl.Buffered(1),
        )
        return _call(
            functools.partial(_bwd_kernel, rep=rep, d=d, n_q=n_q, n_k=n_k),
            "bd_attn_bwd", (bsz, hkv, n_q, n_k), ins,
            [q_spec, column, column], [dq_shape, *dkv_shape],
            [dq_acc, pltpu.VMEM((t, d), jnp.float32),
             pltpu.VMEM((t, d), jnp.float32)],
            interpret, bwd_resident_bytes(t, d, k.dtype.itemsize),
        )(table, nxt, *args)
    dq = _call(
        functools.partial(_dq_kernel, rep=rep, d=d, n_k=n_k),
        "bd_attn_dq", (bsz, hkv, n_q, n_k), ins, [q_spec], [dq_shape],
        [dq_acc], interpret,
    )(table, nxt, *args)[0]
    q_spec, k_spec, row_spec, qrows_spec, kcode_spec = _specs(
        rep, bq, bk, d, True
    )
    ins = [q_spec, k_spec, k_spec, q_spec, q_spec, row_spec, qrows_spec,
           kcode_spec]
    with device_scope("bd_layout"):  # a key block's walk over query blocks
        table_t = table.swapaxes(-1, -2)
        nxt_t = jax.vmap(_next_kept)(table_t)
    dk, dv = _call(
        functools.partial(_dkv_kernel, rep=rep, d=d, n_q=n_q),
        "bd_attn_dkv", (bsz, hkv, n_k, n_q), ins, [k_spec, k_spec],
        dkv_shape,
        [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32)],
        interpret,
    )(table_t, nxt_t, *args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def bd_attention(q, k, v, segment_ids, blocks, streams, heads, bq, bk, fused,
                 interpret):
    """Attention under M of packed rows through the kernels: ``q`` [B, T, Hq
    * D], ``k``, ``v`` [B, T, Hkv * D], ``heads`` = (Hq, Hkv), the three rows
    [B, T] -> [B, T, Hq * D], padding's rows 0. Differentiable in q, k, v;
    ``fused``: the backward's form (``bd_backward``)."""
    return _bd_fwd(
        q, k, v, segment_ids, blocks, streams, heads, bq, bk, fused, interpret
    )[0]


def _bd_fwd(q, k, v, segment_ids, blocks, streams, heads, bq, bk, fused,
            interpret):
    out, lse = bd_forward(
        q, k, v, segment_ids, blocks, streams, heads=heads, bq=bq, bk=bk,
        interpret=interpret,
    )
    # what a remat policy saving "attn_out" keeps, so that the backward does
    # not run the forward kernel again (as the splash kernel names its own)
    out = _ad_checkpoint.checkpoint_name(out, "attn_out")
    lse = _ad_checkpoint.checkpoint_name(lse, "attn_out")
    return out, (q, k, v, segment_ids, blocks, streams, out, lse)


def _bd_bwd(heads, bq, bk, fused, interpret, res, do):
    q, k, v, segment_ids, blocks, streams, out, lse = res
    dq, dk, dv = bd_backward(
        q, k, v, segment_ids, blocks, streams, out, lse, do.astype(q.dtype),
        heads=heads, bq=bq, bk=bk, fused=fused, interpret=interpret,
    )
    return dq, dk, dv, None, None, None


bd_attention.defvjp(_bd_fwd, _bd_bwd)
