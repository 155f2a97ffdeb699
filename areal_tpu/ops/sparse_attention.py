"""Learned sparse attention over packed rows: each query attends to the
``topk`` keys of its own sequence that a small indexer scores highest
(DeepSeek-Sparse-Attention, as the KeyeVL2 family's ``sa_config`` states it).

For token t of a packed row (s ranges over t's own sequence, s <= t):

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])        float32
    S_t     = the ``topk`` keys s of largest I[t, s]; every s while t has
              at most ``topk``; ties go to the lower key index
    o[t, i] = sum_{s in S_t} softmax_{s in S_t}(q[t, i] . k[s, g(i)] / sqrt(d))
              v[s, g(i)]

Three steps, each under its own device scope (``utils/tracing``):

- ``dsa_index``: the index scores, in query blocks of ``_INDEX_Q_BLOCK`` (the
  published ``q_chunk_size``; a tiling, which changes no result). The three
  projections that make ``qi``, ``ki``, ``w`` are the model's
  (``models/transformer._layer_body``), under the same scope.
- ``dsa_select``: an EXACT selection without a sort. A float32's bit pattern,
  sign folded, orders as the float does, so the ``topk``-th largest score of a
  query is found bit by bit in 32 counting passes; a query then keeps what
  lies above that threshold and, of the scores equal to it, the lowest key
  indices (``_tie_cutoff``, run only when some query has more ties than
  places). ``select_impl`` picks the form of the counting as ``core_impl``
  picks the core: on a TPU, for keys that are a whole number of lane tiles,
  one Pallas kernel (``dsa_select_kth``) that holds a block of queries' keys
  in VMEM through all 32 passes and the two counts after them (above the
  threshold, equal to it), reading the keys from HBM once; elsewhere (the
  CPU, a row that is no whole number of tiles) ``_kth_largest``, the same
  bisection as an XLA loop whose every pass reads the keys from HBM, which
  is what the kernel is held equal to, bit for bit, in interpret mode. The
  selection of a query chunk is the pair (threshold,
  cutoff), [T] each: named ``dsa_tau`` so that a remat policy keeps it with
  ``attn_out``, and the recomputed forward and the backward use the forward's
  keys without selecting again. Queries go in chunks of ``topk`` rows (at
  least ``_SELECT_Q_CHUNK``) and a chunk scores only the keys up to its own
  end (a row is causal), so no [T, T] float32 array is ever whole, and a
  chunk that ends within ``topk`` keys scores nothing.
- ``attention``: the core, restricted to the selection as a boolean mask
  [T, T] (one byte a pair, shared by every head). ``core_impl`` picks the
  form as ``ops/moe.grouped_impl`` does: on a TPU a Pallas flash core that
  takes the selection as data (tiles in which nothing is selected,
  everything above the diagonal among them, are skipped; scores never leave
  VMEM), a forward kernel and ONE backward kernel that makes dQ, dK and dV
  of a tile's scores formed once, a kv head's dK and dV resident in VMEM
  over the forward's own walk (so the selection is in HBM once, query-major:
  no transposed copy for a second walk); elsewhere and for rows that are not
  a whole number of tiles a plain XLA form in query blocks, which is what
  the kernel is held equal to in interpret mode.

The selection is by index, so nothing differentiable reaches the indexer:
its three weights get a gradient of exactly zero from the policy loss (the
family trains them with an alignment loss of their own, which this repo does
not have: ROADMAP). Padding (segment 0) selects nothing, is selected by
nothing and counts in no statistic.

Under a data / fsdp mesh the op runs per device on its own rows inside a
``shard_map`` (``make_attend``, its mesh form); it has no sequence-parallel
form and says so.
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import ad_checkpoint as _ad_checkpoint
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.utils.tracing import device_scope

MECHANISM = "learned sparse attention (top-k keys picked by an indexer)"

# query rows of one block of the index computation (sa_config.q_chunk_size)
_INDEX_Q_BLOCK = 512
# query rows of one chunk of the selection, where ``topk`` is smaller (a
# chunk's keys end at its own last query: fewer chunks score more keys
# above the diagonal, more chunks are more programs to compile)
_SELECT_Q_CHUNK = 512
# query rows of one block of the XLA core: [heads, block, T] float32 scores
_CORE_Q_BLOCK = 512
# query rows of one block of the selection kernel ([block, keys] uint32 in
# VMEM, twice: 4 MB a buffer at 16,384 keys) and the lane tiles of one
# unrolled step of its counting loops (tools/microbench_select.py)
_KTH_BLOCK_Q = 64
_KTH_TILE_GROUP = 8
# the kernel's smallest tile edge
_LANES = 128
_MASKED = -2.3819763e38  # ops/basic.segment_attention's


class DSAStats(NamedTuple):
    """What one sparse-attention layer reports beside its output: int32
    scalars, exact (a 16k-token row has 134 M causal pairs, more than a
    float32 counts), which a ``scan`` over layers stacks."""

    kept_pairs: jnp.ndarray  # selected (query, key) pairs
    causal_pairs: jnp.ndarray  # (query, key) pairs of full causal attention


def _fit_edge(n: int, want: int, least: int) -> int:
    """The largest ``least`` x 2^i that is at most ``want`` and divides
    ``n`` (``least`` itself if nothing larger does)."""
    edge = least
    while edge * 2 <= want and n % (edge * 2) == 0:
        edge *= 2
    return edge


def select_impl(shape: Optional[Tuple[int, int]] = None) -> str:
    """The form of the selection's counting passes this process runs:
    ``"kernel"`` (``dsa_select_kth``: a query block's keys stay in VMEM
    through the 32 passes) on a TPU, ``"xla"`` (``_kth_largest``) elsewhere,
    and for a query chunk's keys of ``shape`` (queries, keys) that are not
    whole sublane and lane tiles."""
    if jax.default_backend() != "tpu":
        return "xla"
    if shape is not None and (shape[0] % 8 or shape[1] % _LANES):
        return "xla"
    return "kernel"


def core_impl(t: int, head_dim: int) -> str:
    """The attention core this process runs for rows of ``t`` tokens:
    ``"kernel"`` (the Pallas flash core that takes the selection as data)
    on a TPU, ``"xla"`` elsewhere and for a row the kernel's tiles do not
    divide."""
    if jax.default_backend() != "tpu" or t % _LANES or head_dim % 64:
        return "xla"
    return "kernel"


# ------------------------------------------------------------------ index --
def index_scores(
    qi: jnp.ndarray,  # [Tq, Hi, Di]
    ki: jnp.ndarray,  # [Tk, Di]
    w: jnp.ndarray,  # [Tq, Hi]
) -> jnp.ndarray:
    """I [Tq, Tk] float32 = sum_j w[:, j] * relu(qi[:, j] @ ki.T), in query
    blocks so that the per-head scores [Hi, block, Tk] stay small."""

    def block(args):
        q, wt = args
        s = jnp.einsum(
            "qhd,kd->hqk", q, ki, preferred_element_type=jnp.float32
        )
        return jnp.einsum(
            "hqk,qh->qk", jax.nn.relu(s), wt.astype(jnp.float32)
        )

    tq = qi.shape[0]
    if tq <= _INDEX_Q_BLOCK or tq % _INDEX_Q_BLOCK:
        return block((qi, w))
    n = tq // _INDEX_Q_BLOCK
    out = jax.lax.map(block, (
        qi.reshape(n, _INDEX_Q_BLOCK, *qi.shape[1:]),
        w.reshape(n, _INDEX_Q_BLOCK, w.shape[1]),
    ))
    return out.reshape(tq, ki.shape[0])


# ----------------------------------------------------------------- select --
def _order_keys(scores: jnp.ndarray) -> jnp.ndarray:
    """uint32 keys that order as the float32 scores do (-0.0 as +0.0)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)
    negative = (bits >> 31) == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(0x80000000))


def _kth_largest(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """[C] uint32: per row of ``keys`` [C, K] the largest value v with at
    least ``k`` keys >= v (0 where the row has fewer than ``k`` keys above
    0), built from the top bit down in 32 counting passes. The XLA form:
    each pass is a fusion that reads ``keys`` from HBM (25.6 ms a 16k-token
    row and layer on a v5e, where ``_kth_counts_kernel`` takes 6.8 and runs
    instead). It stays as the CPU's form, the form of a chunk that is no
    whole number of tiles, and what the kernel is held equal to."""

    def body(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, v)

    return jax.lax.fori_loop(
        0, 32, body, jnp.zeros(keys.shape[0], jnp.uint32)
    )


def _kth_counts_xla(keys: jnp.ndarray, topk: int):
    """(threshold, keys above it, keys equal to it), [C] each, of ``keys``
    [C, K]: ``_kth_largest`` and two more passes."""
    tau = _kth_largest(keys, topk)
    return (
        tau,
        jnp.sum(keys > tau[:, None], axis=1, dtype=jnp.int32),
        jnp.sum(keys == tau[:, None], axis=1, dtype=jnp.int32),
    )


def _kth_kernel(keys_ref, tau_ref, above_ref, eq_ref, *, topk, group):
    """``_kth_counts_xla`` of one block of queries, on keys that stay where
    they are. A count is a [bq, 128] partial sum over the lane tiles, then
    one cross-lane sum."""
    bq, n_keys = keys_ref.shape
    n_tiles = n_keys // _LANES

    def wide(x):  # [bq, 1] -> [bq, 128]
        return jnp.broadcast_to(x, (bq, _LANES))

    def count(*preds):
        """Per predicate [bq, 1] int32: the keys of each query it holds for."""

        def tiles(j, n, accs):
            for d in range(n):
                x = keys_ref[
                    :, pl.ds(pl.multiple_of((j + d) * _LANES, _LANES), _LANES)
                ]
                accs = tuple(
                    a + p(x).astype(jnp.int32) for a, p in zip(accs, preds)
                )
            return accs

        accs = tuple(jnp.zeros((bq, _LANES), jnp.int32) for _ in preds)
        accs = jax.lax.fori_loop(
            0, n_tiles // group, lambda g, a: tiles(g * group, group, a), accs
        )
        accs = tiles(n_tiles // group * group, n_tiles % group, accs)
        return tuple(jnp.sum(a, axis=1, keepdims=True) for a in accs)

    def step(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        wide_cand = wide(cand)
        (n,) = count(lambda x: x >= wide_cand)
        return jnp.where(n >= topk, cand, v)

    tau = wide(jax.lax.fori_loop(0, 32, step, jnp.zeros((bq, 1), jnp.uint32)))
    above, eq = count(lambda x: x > tau, lambda x: x == tau)
    tau_ref[...] = tau
    above_ref[...] = wide(above)
    eq_ref[...] = wide(eq)


def _kth_counts_kernel(keys, topk: int, interpret: bool = False):
    """``_kth_counts_xla``, to the bit, as one kernel over blocks of queries:
    the keys are read from HBM once.
    (A block could stop at the lane tile of its own last query, 10% fewer
    keys a 16k row: measured on the chip, no faster: PERF.md, PR 32.)"""
    c, k = keys.shape
    bq = _fit_edge(c, _KTH_BLOCK_Q, 8)
    out_spec = pl.BlockSpec((bq, _LANES), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((c, _LANES), dtype)
        for dtype in (jnp.uint32, jnp.int32, jnp.int32)
    ]
    tau, above, eq = pl.pallas_call(
        functools.partial(_kth_kernel, topk=topk, group=_KTH_TILE_GROUP),
        grid=(c // bq,),
        in_specs=[pl.BlockSpec((bq, k), lambda i: (i, 0))],
        out_specs=[out_spec] * 3, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        name="dsa_select_kth", interpret=interpret,
    )(keys)
    return tau[:, 0], above[:, 0], eq[:, 0]


def _tie_cutoff(eq: jnp.ndarray, need: jnp.ndarray) -> jnp.ndarray:
    """[C] int32: per row the key index of the ``need``-th True of ``eq``
    [C, K] (the last tie that still has a place), by bisection on the index."""
    n_bits = max(1, (eq.shape[1] - 1).bit_length())
    idx = jnp.arange(eq.shape[1], dtype=jnp.int32)

    def body(i, m):
        cand = m | (jnp.int32(1) << (n_bits - 1 - i))
        below = jnp.sum(
            eq & (idx[None] < cand[:, None]), axis=1, dtype=jnp.int32
        )
        return jnp.where(below < need, cand, m)

    return jax.lax.fori_loop(
        0, n_bits, body, jnp.zeros(eq.shape[0], jnp.int32)
    )


def _select_chunk(keys: jnp.ndarray, topk: int, interpret: bool = False):
    """(threshold [C] uint32, cutoff [C] int32) of one query chunk's
    ``keys`` [C, K] (0 = not a key of this query): a query keeps the keys
    above its threshold and, of those equal to it, the ones at or before
    its cutoff."""
    if select_impl(keys.shape) == "kernel":
        tau, above, n_eq = _kth_counts_kernel(keys, topk, interpret)
    else:
        tau, above, n_eq = _kth_counts_xla(keys, topk)
    need = topk - above
    contested = (tau > 0) & (n_eq > need)
    everything = jnp.full(keys.shape[0], keys.shape[1], jnp.int32)
    cutoff = jax.lax.cond(
        jnp.any(contested),
        lambda: jnp.where(
            contested, _tie_cutoff(keys == tau[:, None], need), everything
        ),
        lambda: everything,
    )
    return tau, cutoff


def select_keys(
    qi: jnp.ndarray,  # [T, Hi, Di]
    ki: jnp.ndarray,  # [T, Di]
    w: jnp.ndarray,  # [T, Hi]
    segment_ids: jnp.ndarray,  # [T]; 0 = padding
    topk: int,
    interpret: bool = False,  # Pallas interpret mode: the CPU's tests
) -> Tuple[jnp.ndarray, DSAStats]:
    """The selection of one packed row as a mask [T, T] bool (query, key),
    with its counts. A padding token keeps itself alone (a query with no
    key at all is a division by zero in a softmax), and counts nowhere."""
    t = segment_ids.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    real = segment_ids > 0
    rows = []
    kept = causal = jnp.zeros((), jnp.int32)
    chunk = max(topk, _SELECT_Q_CHUNK)
    for a in range(0, t, chunk):
        b = min(a + chunk, t)
        own = (
            (segment_ids[a:b, None] == segment_ids[None, :b])
            & real[a:b, None] & (idx[None, :b] <= idx[a:b, None])
        )
        if b > topk:  # some query of the chunk may have more than topk keys
            with device_scope("dsa_index"):
                scores = index_scores(qi[a:b], ki[:b], w[a:b])
            with device_scope("dsa_select"):
                keys = jnp.where(own, _order_keys(scores), jnp.uint32(0))
                tau, cutoff = (
                    _ad_checkpoint.checkpoint_name(x, "dsa_tau")
                    for x in _select_chunk(keys, topk, interpret)
                )
                chosen = own & (
                    (keys > tau[:, None])
                    | ((keys == tau[:, None])
                       & (idx[None, :b] <= cutoff[:, None]))
                )
        else:
            chosen = own
        with device_scope("dsa_select"):
            kept = kept + jnp.sum(chosen, dtype=jnp.int32)
            causal = causal + jnp.sum(own, dtype=jnp.int32)
            chosen = chosen | (
                ~real[a:b, None] & (idx[None, :b] == idx[a:b, None])
            )
            rows.append(jnp.pad(chosen, ((0, 0), (0, t - b))))
    with device_scope("dsa_select"):
        mask = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    return mask, DSAStats(kept_pairs=kept, causal_pairs=causal)


# ------------------------------------------------------------------- core --
def _masked_attention_xla(q, k, v, mask):
    """softmax over the masked keys, one packed row: q [T, Hq, D], k and v
    [T, Hkv, D], mask [T, T]; float32 scores in query blocks."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, hq // hkv, d)

    def block(args):
        qb, mb = args  # [C, Hkv, rep, D], [C, T]
        logits = jnp.einsum(
            "qgrd,kgd->grqk", qb, k, preferred_element_type=jnp.float32
        ) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(mb[None, None], logits, _MASKED), axis=-1
        )
        return jnp.einsum(
            "grqk,kgd->qgrd", probs, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    if t <= _CORE_Q_BLOCK or t % _CORE_Q_BLOCK:
        out = block((qg, mask))
    else:
        n = t // _CORE_Q_BLOCK
        out = jax.lax.map(block, (
            qg.reshape(n, _CORE_Q_BLOCK, *qg.shape[1:]),
            mask.reshape(n, _CORE_Q_BLOCK, t),
        ))
    return out.reshape(t, hq, d).astype(q.dtype)


# --------------------------------------------------------- the kernel core --
# A flash core that takes the selection as data. The splash kernel's dynamic
# mask would serve (it was this op's first form), but Mosaic takes a boolean
# operand as int32 and every query head reads its own copy: 4 bytes a
# (query, key) pair and head, 34 GB a 16k-token row, layer and pass. Here the
# selection is one int8 a pair and a grid step works through ALL the query
# heads of one kv head on one tile of it: 1 GB a row, layer and pass.
#
# Two kernels over a (kv head, query block, key block) grid, each a softmax
# in float32 as the splash kernels' is (``m``/``l`` kept lane-wide):
# ``dsa_core_fwd`` (output and logsumexp, an online softmax) and
# ``dsa_core_bwd``, which walks the forward's tiles in the forward's order
# and forms a kept tile's scores ONCE a query head: S, P (from the saved
# logsumexp), dP and dS, and of them dV += P^T dO, dK += dS^T q and dQ += dS k,
# five products, one exponential, one read of the mask. The tile is formed
# transposed, keys on sublanes (the int8 tile is turned once for all its
# heads), so that the per-query logsumexp broadcasts along sublanes and every
# product is a plain matmul (dQ accumulates as ``[D, queries]`` against
# ``k.T``, turned back where the query block is written, at its last key
# block). dK and dV of a kv head's WHOLE ``[T, D]`` stay in float32 VMEM
# scratch across the head's walk and are written once at its end
# (``bwd_resident_bytes``: 24 MiB at 16,384 tokens, 48 at 32,768; the call
# states its VMEM limit from it), each key block receiving its query blocks
# ascending and the heads ascending within one. That took the place of a
# pair (``dsa_core_dq``, and ``dsa_core_dkv`` on a second, transposed copy of
# the selection with a walk of its own), which formed every tile's scores
# twice, seven products: 33.0 -> 23.0 ms a layer on a 16,384-token row of a
# v5e at the pair's 512 x 1024 tile, the same bits (PERF.md, PR 60).
# ``tiles`` [query blocks, key blocks] says which tiles hold any selected
# pair: the others (everything above the diagonal, and what the selection
# leaves empty) are not computed, and past a query block's last kept tile
# not fetched either (their block index is clamped to that tile's, which
# the pipeline does not re-copy).
# Queries and keys a tile, the tile table's granularity and both kernels':
# the sweep of {128..1024} x {256..1024} on the cell's packed rows (PERF.md,
# PR 60) reads 512 x 512 lowest for the forward (10.5 ms a layer; 11.0 at
# 512 x 1024, which it was) and for the backward (22.4; 23.0): finer tiles
# leave more of the row's empty area out, and below 512 keys a tile the
# steps' overhead takes it back.
_BLOCK_Q = 512
_BLOCK_KV = 512
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
# What a tile's blocks, their pipeline buffers and the compiler's temporaries
# (a head's float32 scores and what is made of them) may take of VMEM beside
# what the backward keeps resident (``ops/bd_attention._WORKING``'s rule).
_WORKING = 32 << 20


def bwd_resident_bytes(t: int, d: int, itemsize: int) -> int:
    """The bytes the backward (``dsa_core_bwd``) keeps in VMEM across a kv
    head's walk: dK and dV of its ``t`` keys in float32 scratch, and their
    two output blocks ``[t, d]`` of ``itemsize`` bytes an element, one
    buffer each."""
    return 2 * t * d * 4 + 2 * t * d * itemsize


def _core_blocks(t: int) -> Tuple[int, int]:
    return _fit_edge(t, _BLOCK_Q, _LANES), _fit_edge(t, _BLOCK_KV, _LANES)


def _lane_tile(x, width):
    """[rows, 128] lane-replicated -> [rows, width]."""
    reps = -(-width // _LANES)
    return jnp.tile(x, (1, reps))[:, :width]


def _fwd_kernel(tiles_ref, last_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_s, l_s, acc_s, *, rep, n_kv):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _MASKED)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(tiles_ref[i, j] != 0)
    def _():
        keep = mask_ref[...].astype(jnp.int32) != 0  # [bq, bkv]
        k, v = k_ref[...], v_ref[...]
        for r in range(rep):
            s = jax.lax.dot_general(
                q_ref[r], k, _NT, preferred_element_type=jnp.float32
            )
            s = jnp.where(keep, s, _MASKED)
            m_prev, l_prev = m_s[r], l_s[r]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - _lane_tile(m_next, s.shape[1]))
            alpha = jnp.exp(m_prev - m_next)
            l_s[r] = alpha * l_prev + jax.lax.broadcast_in_dim(
                p.sum(axis=-1), l_prev.shape, (0,)
            )
            m_s[r] = m_next
            acc_s[r] = _lane_tile(alpha, v.shape[1]) * acc_s[r] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )

    @pl.when(j == n_kv - 1)
    def _():
        for r in range(rep):
            l = l_s[r]
            o_ref[r] = (
                acc_s[r] * _lane_tile(1.0 / l, acc_s.shape[2])
            ).astype(o_ref.dtype)
            lse_ref[r] = jnp.log(l) + m_s[r]


def _bwd_kernel(tiles_ref, last_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
                lse_ref, di_ref, dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s,
                *, rep, n_q, n_kv):
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bkv = mask_ref.shape
    d = k_ref.shape[1]

    def key_blocks(body):
        def step(n, _):
            body(pl.ds(pl.multiple_of(n * bkv, bkv), bkv))

        jax.lax.fori_loop(0, n_kv, step, None)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        def zero(at):
            dk_s[at, :] = jnp.zeros((bkv, d), jnp.float32)
            dv_s[at, :] = jnp.zeros((bkv, d), jnp.float32)

        key_blocks(zero)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(tiles_ref[i, j] != 0)
    def _():
        # the tile transposed, once for all its heads: [bkv, bq]
        keep = mask_ref[...].astype(jnp.float32).T != 0
        k, v = k_ref[...], v_ref[...]
        k_t = k.T
        # a kept tile's key block is the grid's own (j <= last[i])
        mine = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        dk, dv = dk_s[mine, :], dv_s[mine, :]
        for r in range(rep):
            q, do = q_ref[r], do_ref[r]
            s_t = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32
            )
            p_t = jnp.where(
                keep, jnp.exp(s_t - lse_ref[pl.ds(r, 1), :]), 0.0
            )
            dv = dv + jnp.dot(
                p_t.astype(do.dtype), do, preferred_element_type=jnp.float32
            )
            dp_t = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32
            )
            ds_t = (p_t * (dp_t - di_ref[pl.ds(r, 1), :])).astype(q.dtype)
            dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
            dq_s[:, r * bq:(r + 1) * bq] += jnp.dot(
                k_t, ds_t, preferred_element_type=jnp.float32
            )
        dk_s[mine, :], dv_s[mine, :] = dk, dv

    @pl.when(j == n_kv - 1)
    def _():
        for r in range(rep):
            dq_ref[r] = dq_s[:, r * bq:(r + 1) * bq].T.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(i == n_q - 1, j == n_kv - 1))
    def _():
        def cast(at):
            dk_ref[at, :] = dk_s[at, :].astype(dk_ref.dtype)
            dv_ref[at, :] = dv_s[at, :].astype(dv_ref.dtype)

        key_blocks(cast)


def _specs(rep, bq, bkv, d):
    """Block specs of q-like [Hkv, rep, T, D], k-like [Hkv, T, D], the
    mask tile and per-query rows [Hkv, rep, T], for a (kv head, query
    block, key block) grid. The scalar-prefetched bound clamps the key
    block of what the skipped tiles would fetch."""
    def kj(h, i, j, tiles, last):
        return jnp.minimum(j, last[i])

    q_spec = pl.BlockSpec((None, rep, bq, d), lambda h, i, *a: (h, 0, i, 0))
    k_spec = pl.BlockSpec((None, bkv, d), lambda h, *a: (h, kj(h, *a), 0))
    mask_spec = pl.BlockSpec((bq, bkv), lambda h, i, *a: (i, kj(h, i, *a)))
    row_spec = pl.BlockSpec((None, rep, bq), lambda h, i, *a: (h, 0, i))
    return q_spec, k_spec, mask_spec, row_spec


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, resident: int = 0):
    """``resident``: the bytes a kernel keeps across a kv head's whole walk
    (``bwd_resident_bytes``; 0: a tile's alone). Such a kernel walks the
    query blocks in order too, under a VMEM limit that is those bytes and
    ``_WORKING`` beside them."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if resident else "parallel",
                "arbitrary",
            ),
            vmem_limit_bytes=resident + _WORKING if resident else None,
        ),
        out_shape=out_shape, name=name, interpret=interpret,
    )


def _core_fwd(q, k, v, sel, interpret):
    hkv, rep, t, d = q.shape
    mask8, tiles, last = sel
    bq, bkv = mask8.shape[0] // tiles.shape[0], mask8.shape[1] // tiles.shape[1]
    q_spec, k_spec, mask_spec, _ = _specs(rep, bq, bkv, d)
    lse_spec = pl.BlockSpec(
        (None, rep, bq, _LANES), lambda h, i, j, *_: (h, 0, i, 0)
    )
    out, lse = _call(
        functools.partial(_fwd_kernel, rep=rep, n_kv=t // bkv),
        "dsa_core_fwd", (hkv, t // bq, t // bkv),
        [q_spec, k_spec, k_spec, mask_spec], [q_spec, lse_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((hkv, rep, t, _LANES), jnp.float32)],
        [pltpu.VMEM((rep, bq, _LANES), jnp.float32),
         pltpu.VMEM((rep, bq, _LANES), jnp.float32),
         pltpu.VMEM((rep, bq, d), jnp.float32)],
        interpret,
    )(tiles, last, q, k, v, mask8)
    return out, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _masked_flash(q, k, v, sel, interpret):
    """q [Hkv, rep, T, D] (scaled), k, v [Hkv, T, D], ``sel`` the selection
    as the kernels read it (``_kernel_selection``) -> [Hkv, rep, T, D]."""
    return _core_fwd(q, k, v, sel, interpret)[0]


def _masked_flash_fwd(q, k, v, sel, interpret):
    out, lse = _core_fwd(q, k, v, sel, interpret)
    # what a remat policy saving "attn_out" keeps, so that the backward does
    # not run the forward kernel again
    out = _ad_checkpoint.checkpoint_name(out, "attn_out")
    lse = _ad_checkpoint.checkpoint_name(lse, "attn_out")
    return out, (q, k, v, sel, out, lse)


def _masked_flash_bwd(interpret, res, do):
    q, k, v, sel, out, lse = res
    mask8, tiles, last = sel
    hkv, rep, t, d = q.shape
    n_q, n_kv = tiles.shape
    bq, bkv = t // n_q, t // n_kv
    di = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    q_spec, k_spec, mask_spec, row_spec = _specs(rep, bq, bkv, d)
    # a kv head's whole dK / dV, written once a walk
    column = pl.BlockSpec(
        (None, t, d), lambda h, *_: (h, 0, 0), pipeline_mode=pl.Buffered(1)
    )
    dq, dk, dv = _call(
        functools.partial(_bwd_kernel, rep=rep, n_q=n_q, n_kv=n_kv),
        "dsa_core_bwd", (hkv, n_q, n_kv),
        [q_spec, k_spec, k_spec, mask_spec, q_spec, row_spec, row_spec],
        [q_spec, column, column],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((d, rep * bq), jnp.float32),
         pltpu.VMEM((t, d), jnp.float32), pltpu.VMEM((t, d), jnp.float32)],
        interpret, bwd_resident_bytes(t, d, k.dtype.itemsize),
    )(tiles, last, q, k, v, mask8, do, lse, di)
    return dq, dk, dv, None


_masked_flash.defvjp(_masked_flash_fwd, _masked_flash_bwd)


def _kernel_selection(mask: jnp.ndarray):
    """The mask [T, T] as the kernels read it: one int8 a pair, which tiles
    hold anything, and per query block the last such key block."""
    t = mask.shape[0]
    bq, bkv = _core_blocks(t)
    tiles = mask.reshape(t // bq, bq, t // bkv, bkv).any(axis=(1, 3))
    last = jnp.max(
        jnp.where(tiles, jnp.arange(tiles.shape[1])[None], 0), axis=1
    ).astype(jnp.int32)
    return mask.astype(jnp.int8), tiles.astype(jnp.int32), last


def _masked_attention_kernel(q, k, v, mask, interpret=False):
    """``_masked_attention_xla`` through the kernels."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    qs = qs.transpose(1, 0, 2).reshape(hkv, hq // hkv, t, d)
    out = _masked_flash(
        qs, k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        jax.lax.stop_gradient(_kernel_selection(mask)), interpret,
    )
    return out.reshape(hq, t, d).transpose(1, 0, 2)


def sparse_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, T]
    qi: jnp.ndarray,  # [B, T, Hi, Di]
    ki: jnp.ndarray,  # [B, T, Di]
    w: jnp.ndarray,  # [B, T, Hi]
    topk: int,
    core: str = "auto",  # "auto" | "xla" | "kernel"
    interpret: bool = False,  # Pallas interpret mode: the CPU's tests
) -> Tuple[jnp.ndarray, DSAStats]:
    """Attention over each query's selected keys: (out [B, T, Hq, D],
    ``DSAStats`` summed over the rows). A row never reads another row."""
    t, d = q.shape[1], q.shape[3]
    if core == "auto":
        core = core_impl(t, d)
    qi, ki, w = jax.lax.stop_gradient((qi, ki, w))
    outs, stats = [], []
    for r in range(q.shape[0]):  # rows of a microbatch: few, and each its
        # own mask, which a kernel's scalar-prefetched tile table cannot batch
        mask, st = select_keys(
            qi[r], ki[r], w[r], segment_ids[r], topk, interpret
        )
        with device_scope("attention"):
            if core == "kernel":
                o = _masked_attention_kernel(q[r], k[r], v[r], mask, interpret)
            else:
                o = _masked_attention_xla(q[r], k[r], v[r], mask)
            outs.append(jnp.where((segment_ids[r] > 0)[:, None, None], o, 0))
        stats.append(st)
    return jnp.stack(outs), DSAStats(*(sum(f) for f in zip(*stats)))


# -------------------------------------------------------------- mesh form --
def make_attend(mesh: Mesh, topk: int):
    """``attend(q, k, v, segment_ids, qi, ki, w) -> (out, DSAStats)`` for
    ``transformer.apply`` on ``mesh``: the plain call on one device; under a
    data / fsdp (/ tensor) mesh each device runs it on its own rows (and
    heads; the indexer is repeated over the tensor axis) inside a
    ``shard_map`` with the counts summed over the axes that split rows (a
    Mosaic kernel cannot be partitioned, and a selection over a token axis
    GSPMD has sharded would gather it). No sequence-parallel form: a query's
    keys lie anywhere before it."""
    attend = functools.partial(sparse_attention, topk=topk)
    if mesh.size == 1:
        return attend
    if mesh.shape["seq"] > 1:
        raise ValueError(
            f"{MECHANISM} has no sequence-parallel form "
            f"(seq_parallel_size={mesh.shape['seq']}): ROADMAP B"
        )
    rows = ("data", "fsdp")

    def per_device(*args):
        out, stats = attend(*args)
        return out, DSAStats(*(jax.lax.psum(s, rows) for s in stats))

    qkv = P(rows, None, "tensor", None)
    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(rows, None), P(rows, None, None, None),
                  P(rows, None, None), P(rows, None, None)),
        out_specs=(qkv, P()),
        check_vma=False,
    )
