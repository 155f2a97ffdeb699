"""Mixture-of-Experts FFN: top-k routing, every (token, expert) pair computed.

Role of reference realhf/impl/model/modules/moe/{router,experts,
token_dispatcher,layer}.py (top-k router + grouped GEMM + token
dispatcher), TPU-first and dropless: the pairs of one call are sorted by
expert, the three expert matmuls run as GROUPED matmuls over the ragged
groups (one group an expert, as long as that expert's load), and the result
is un-permuted and summed with the routing weights. There is no capacity and
nothing is dropped, so a token's output never depends on what it was packed
with: the recomputed logprobs, the gradient pass and the generation engine
agree whatever their batches (a capped dispatch made them differ by
construction). Every shape is static: the sorted stream has tokens x k rows
(a share's: slabs of a bounded buffer's rows, below), and the group sizes
are data.

Padding (``valid`` false) takes no part: its pairs sort behind every group,
are zeroed on the way in and on the way out, and count in no statistic.

The layer may HOLD fewer experts than the router scores (one chip's share of
an expert-parallel deployment: ``w_gate`` has fewer experts than ``w_router``
has outputs). It then routes over all of them, as the whole layer would,
and computes the part of the result that its own experts give. A pair routed
to an expert held elsewhere sorts behind the held ones, as padding's do, and
the expert path never touches it: the dispatch gathers, the grouped matmuls
read and the combine un-permutes SLABS of the sorted stream, each a bounded
buffer of static size (the share's expected pairs times ``_HELD_SLACK``, in
whole row tiles). The first slab always runs; a chunk whose held pairs pass
it runs as many more as they fill, in a loop (forward, and again backward:
no loop is differentiated through), so there is still no capacity and no
drop at any load. The shares of all the holders add up to the whole layer's
output: on a mesh that splits the experts (``make_sharded_moe_ffn``) every
chip is a holder, the tokens are gathered to it and the shares are summed
and scattered back to their owners. The statistics then count the pairs
routed to experts held, and two more say how the buffer fared: the rows the
expert path ran over and the chunks that needed more than one slab.

Which grouped matmul runs is resolved from the platform (``grouped_impl``),
as the trainer's ``attn_impl: auto`` is: the Pallas ``megablox`` kernel on a
TPU, ``jax.lax.ragged_dot`` elsewhere (the CPU's path and the form the
kernel is held equal to). Under a device mesh the layer runs per device
inside a ``shard_map`` (``make_sharded_moe_ffn``, the layer's mesh form): a
sort over a token axis that GSPMD has sharded would gather it.

The router has two forms (``router_topk``): a softmax over all experts whose
``k`` largest probabilities are the weights (renormalised or not), and
sigmoid scores of which the ``k`` largest AFTER a correction bias are chosen,
weighted by the scores without the bias, renormalised, times a scaling
factor. The scores are ``x @ w_router`` (``moe_ffn``) or come from outside
(``moe_ffn_scored``, the part of the layer from the choice on: a family
whose router is an MLP with an input carried from layer to layer,
``router_mlp``, hands its scores in). A family's shared expert (a dense gated FFN on every token, times a
per-token sigmoid gate where the family has one) is part of the sparse layer
(``moe_ffn_from_params``), so under a mesh it runs inside the same
``shard_map``.

Device scopes (``utils/tracing.DEVICE_SCOPES``), nested inside the layer's
``mlp``: ``moe_route`` (router matmul, softmax, top-k, load statistics),
``moe_dispatch`` (sort, permute, un-permute, weighted sum; of a share: the
sort of every pair, a slab's row gather, and its rows summed back onto
their tokens: on a TPU in token order by a grouped product over token
tiles, ``onto_tokens_banded``; elsewhere the [tokens, slab] product),
``moe_experts`` (grouped matmuls, gate), ``moe_exchange`` (the mesh form's).
"""

import functools
import importlib
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.ops.basic import rms_norm
from areal_tpu.utils.tracing import device_scope

# megablox tiles (m, k, n). m must divide the sorted stream, so a stream
# that is not a whole number of tiles takes the XLA form. Measured on the
# v5e at OLMoE's widths (4096 tokens x 8 of 64 experts, D 2048, F 1024;
# ``tools/microbench_moe.py``, PERF.md PR 27), the three matmuls forward /
# forward + backward in ms: (256, 1024, 1024) 4.00 / 12.2; (128, 1024, 1024)
# 5.09 / 14.4; (512, 1024, 1024) 4.40 / 13.7; (512, 512, 1024) 4.79 / 15.6;
# (512, 2048, 1024) 5.55 / 18.6; (1024, 1024, 1024) 6.45 / 19.7;
# ``ragged_dot`` 5.6 / 24.2 (outside any scope). A group is 440 rows on
# average: a row tile of 256 wastes less of each group's last tile than 512,
# and 128 pays more steps than it saves.
_GMM_TILE_M = 256
_GMM_TILING = (_GMM_TILE_M, 1024, 1024)


class MoEStats(NamedTuple):
    """What one MoE layer reports beside its output (all float32 scalars,
    so a ``scan`` over layers stacks them and a sum makes the step's)."""

    aux_loss: jnp.ndarray  # Switch-style load-balancing loss
    assignments: jnp.ndarray  # valid tokens x k
    max_expert_assignments: jnp.ndarray  # the busiest expert's count
    # of a layer that holds a share of its experts only (None, no leaf at
    # all, otherwise): the rows its expert path ran over (a slab's, times
    # the slabs each chunk's held pairs filled) and the chunks whose held
    # pairs filled more than one
    stream_rows: Optional[jnp.ndarray] = None
    overflow_chunks: Optional[jnp.ndarray] = None


def router_topk(
    logits: jnp.ndarray,  # [G, E] fp32
    k: int,
    norm_topk_prob: bool,
    act: str = "softmax",
    bias: Optional[jnp.ndarray] = None,  # [E], the sigmoid form's
    scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (topk_probs [G,k], topk_idx [G,k], full probs [G,E]).
    ``act`` "softmax": the ``k`` largest probabilities. "sigmoid": the
    experts of the ``k`` largest ``sigmoid(logits)``. With a ``bias`` the
    ``k`` largest of scores + bias are chosen (the bias moves the choice
    only, and no gradient reaches it), weighted by their scores WITHOUT it.
    Either renormalised where ``norm_topk_prob`` (at ``k`` = 1 that would
    make every weight 1 and cut the router off from the loss: one a token
    takes the probability as it is), then times ``scale``."""
    if act == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if act == "sigmoid" or bias is not None:
        ranked = probs if bias is None else probs + jax.lax.stop_gradient(
            bias.astype(jnp.float32)
        )
        topk_i = jax.lax.top_k(ranked, k)[1]
        topk_p = jnp.take_along_axis(probs, topk_i, axis=-1)
    else:
        topk_p, topk_i = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        topk_p = topk_p / jnp.maximum(
            topk_p.sum(-1, keepdims=True), 1e-9
        )
    if scale != 1.0:
        topk_p = topk_p * scale
    return topk_p, topk_i, probs


def load_balancing_loss(
    probs: jnp.ndarray,  # [G, E] full router probs
    topk_idx: jnp.ndarray,  # [G, k]
    num_experts: int,
    valid: Optional[jnp.ndarray] = None,  # [G] bool
    token_axes: Optional[Tuple[str, ...]] = None,
) -> jnp.ndarray:
    """Switch-style aux loss: E * Σ_e f_e · P_e, where f_e is the fraction
    of (valid) tokens routed to e and P_e their mean router prob
    (reference modules/moe/router.py aux losses). ``token_axes``: the mesh
    axes the tokens are split over when called per device, so that the
    fractions are the whole batch's."""
    assign = jax.nn.one_hot(topk_idx, num_experts, dtype=jnp.float32)
    w = (
        jnp.ones((probs.shape[0], 1), jnp.float32) if valid is None
        else valid.astype(jnp.float32)[:, None]
    )
    sums = ((assign.sum(1) * w).sum(0), (probs * w).sum(0), w.sum())
    if token_axes:
        sums = jax.lax.psum(sums, token_axes)
    denom = jnp.maximum(sums[2], 1.0)
    f, p = sums[0] / denom, sums[1] / denom
    return num_experts * jnp.sum(f * p) / topk_idx.shape[-1]


def grouped_impl(shape: Optional[Tuple[int, int, int]] = None) -> str:
    """The grouped matmul this process runs: ``"megablox"`` on a TPU,
    ``"ragged_dot"`` elsewhere, and for a ``shape`` (rows of the sorted
    stream, model width, expert width) the kernel's tiles do not divide (a
    decode step's few rows, a test-sized model)."""
    if jax.default_backend() != "tpu":
        return "ragged_dot"
    if shape is not None and (
        shape[0] % _GMM_TILE_M or shape[1] % 128 or shape[2] % 128
    ):
        return "ragged_dot"
    return "megablox"


def _grouped_matmul(
    lhs: jnp.ndarray,  # [M, K], rows sorted by group
    rhs: jnp.ndarray,  # [E, K, N]
    group_sizes: jnp.ndarray,  # [E] int32, sum <= M
    impl: str,
) -> jnp.ndarray:
    """[M, N] in ``lhs``'s dtype, float32 accumulation. Rows behind the
    last group are the caller's to zero (the kernel leaves them unwritten)."""
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(
            lhs, rhs, group_sizes, lhs.dtype, _GMM_TILING
        )
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32
    ).astype(lhs.dtype)


# The two permutations of the dispatch, each with its own differentiation
# rule: the transpose of a row gather is a scatter-add, which the TPU runs
# row by row, and for a permutation it is just the gather by the inverse.
# ``order[i]`` is the pair at sorted position i, ``inv[j]`` the sorted
# position of pair j (pair j = token j // k, slot j % k), ``pair_ok[j]``
# whether pair j's token is real. Real pairs sort before padding's, so
# sorted position i lies in a group iff i < pair_ok.sum(). Rows behind the
# groups come out of the grouped matmul unwritten: they are never read
# forward (zeros go in, a select takes them out) nor backward.
@jax.custom_vjp
def _dispatch(xf, order, inv, pair_ok):
    """[G, D] tokens -> [G*k, D] rows sorted by expert, zeros behind the
    groups (gathered from one zero row: no pass of its own)."""
    g, k = xf.shape[0], order.shape[0] // xf.shape[0]
    in_group = jnp.arange(order.shape[0]) < pair_ok.sum()
    src = jnp.concatenate([xf, jnp.zeros((1, xf.shape[1]), xf.dtype)])
    return src[jnp.where(in_group, order // k, g)]


def _dispatch_fwd(xf, order, inv, pair_ok):
    return _dispatch(xf, order, inv, pair_ok), (inv, pair_ok, xf.shape[0])


def _dispatch_bwd(res, g_xs):
    inv, pair_ok, g = res
    g_pairs = jnp.where(pair_ok[:, None], g_xs[inv], 0)
    g_xf = g_pairs.reshape(g, -1, g_xs.shape[-1]).sum(1, dtype=jnp.float32)
    return g_xf.astype(g_xs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _weighted_sum(ys, w, inv, pair_ok):
    """(out [G, D], each token's k un-permuted rows [G, k, D])."""
    y = jnp.where(pair_ok[:, None], ys[inv], 0).reshape(
        w.shape[0], -1, ys.shape[-1]
    )
    out = jnp.einsum(
        "gkd,gk->gd", y, w.astype(y.dtype), preferred_element_type=jnp.float32
    )
    return out.astype(ys.dtype), y


@jax.custom_vjp
def _combine(ys, w, order, inv, pair_ok):
    """[G*k, D] sorted expert outputs and [G, k] float32 routing weights ->
    [G, D]: each token's k rows, un-permuted and summed with its weights."""
    return _weighted_sum(ys, w, inv, pair_ok)[0]


def _combine_fwd(ys, w, order, inv, pair_ok):
    out, y = _weighted_sum(ys, w, inv, pair_ok)
    return out, (y, w, order, pair_ok)


def _combine_bwd(res, g_out):
    y, w, order, pair_ok = res
    k = w.shape[1]
    g_w = jnp.einsum(
        "gd,gkd->gk", g_out, y, preferred_element_type=jnp.float32
    ).astype(w.dtype)
    # row i of the sorted stream belongs to pair order[i]: its token's
    # cotangent times that pair's weight (0 for padding's pairs, which are
    # exactly the rows behind the groups)
    wm = jnp.where(pair_ok, w.reshape(-1), 0).astype(g_out.dtype)
    g_ys = g_out[order // k] * wm[order][:, None]
    return g_ys, g_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# rows of the sorted stream (tokens x k) above which the dispatch and the
# grouped matmuls run in token chunks of at most this many rows: one
# 16,384-token row of 8 a token is 131,072 rows, 0.5 GB in bf16 for each of
# the stream's half-dozen arrays, forward and backward. A layer that holds a
# share of the experts sorts every pair of a chunk and runs the rest over
# slabs of it (``_HELD_SLACK``). 32,768 is one chip's 4,096-token row of the
# four-chip sparse cell: the host's tokens, gathered, run in four chunks.
_STREAM_ROWS = 32768


def _token_chunks(g: int, k: int) -> int:
    """How many equal token chunks a stream of ``g`` x ``k`` rows runs in."""
    n = 1
    while g * k > n * _STREAM_ROWS and g % (2 * n) == 0:
        n *= 2
    return n


def _group_sizes(pair_expert, e):
    """[E] int32: how many of the pairs go to each of the ``e`` experts."""
    return jnp.sum(
        pair_expert[:, None] == jnp.arange(e, dtype=jnp.int32)[None],
        axis=0, dtype=jnp.int32,
    )


def _run_experts(xf, pair_expert, topk_p, group_sizes, w_gate, w_up, w_down):
    """[G, D] tokens through their pairs' experts: sort the pairs by
    expert, the three grouped matmuls, un-permute and sum with the routing
    weights. ``pair_expert`` [G*k] holds E for a pair that is nobody's here
    (padding; an expert held elsewhere); ``group_sizes`` [E] the count of
    each expert's pairs (None: counted here)."""
    g, d = xf.shape
    e, k = w_gate.shape[0], topk_p.shape[1]
    if group_sizes is None:
        with device_scope("moe_dispatch"):
            group_sizes = _group_sizes(pair_expert, e)
    impl = grouped_impl((g * k, d, w_gate.shape[-1]))
    pair_ok = pair_expert < e
    with device_scope("moe_dispatch"):
        order = jnp.argsort(pair_expert, stable=True)  # pairs by expert
        inv = jnp.argsort(order)
        xs = _dispatch(xf, order, inv, pair_ok)
    with device_scope("moe_experts"):
        h = jax.nn.silu(
            _grouped_matmul(xs, w_gate, group_sizes, impl)
        ) * _grouped_matmul(xs, w_up, group_sizes, impl)
        in_group = (jnp.arange(g * k) < group_sizes.sum())[:, None]
        ys = _grouped_matmul(
            jnp.where(in_group, h, 0), w_down, group_sizes, impl
        )
    with device_scope("moe_dispatch"):
        return _combine(ys, topk_p, order, inv, pair_ok)


# A layer that HOLDS A SHARE of the experts it routes over (``e`` of
# ``n_routed``) expects rows x e / n_routed of a stream's pairs. Its expert
# path runs over SLABS of the sorted stream of that many rows times this
# slack, in whole ``_GMM_TILE_M`` tiles: the first slab always, and one more
# for every further slab's worth of held pairs (``_run_held``). Nothing is
# capped and nothing dropped; every shape is the slab's. Read on the chip
# (PERF.md, PR 37, over the two cells' own batches, held pairs a 32,768-row
# chunk against the even share): Kimi's sigmoid router over 256, 8 held, mean
# 888, largest 1,127 of 1,024 even (1.1 x); Keye's random softmax router over
# 128, 16 held, mean 4,141, median 3,585, 99th percentile 13,299, largest
# 16,082 of 4,096 even (3.9 x), 42 of 440 chunks past twice the even share.
# At 2 a Kimi chunk is one slab always and a Keye chunk one slab nine times
# in ten; at 1 a second slab (three more grouped matmuls that each read all
# the held weights) would run in 15% and 42% of the chunks; at 3 the slab's
# row gathers and the token side below cost half as much again in every
# chunk to save 7% of them a second slab. A layer that holds HALF of its
# experts (one a token over 16, 8 held: even share rows / 2) has a slab of
# rows x 1/2 x 2 = the whole stream: ``_held_rows`` gives ``rows``,
# ``_run_held`` takes the whole form (``_run_experts``: every pair sorted
# and gathered, the other holder's half as zero rows behind the groups),
# nothing is compacted and no chunk can overflow. The slack's price there
# is nil and so is its gain; a slab of the even share alone (slack 1) would
# overflow in about every second chunk.
_HELD_SLACK = 2


def _held_rows(rows: int, e: int, n_routed: int) -> int:
    """Rows of a slab of a ``rows``-row stream of which the layer holds
    ``e`` of ``n_routed`` experts (``rows`` where that would be no fewer)."""
    c = -(-rows * e * _HELD_SLACK // n_routed)
    return min(-(-c // _GMM_TILE_M) * _GMM_TILE_M, rows)


# The token side of a slab: its [C, D] rows summed onto their [G, D] tokens.
# As a product with the [G, C] matrix that holds ``w[i]`` at (tok[i], i) it
# spends 2 G C D FLOP on C D useful ones (each column holds ONE non-zero).
# With the slab's rows in TOKEN order the matrix is block-banded: a tile of
# ``_ONTO_TILE`` tokens owns one contiguous run of rows, and the product is a
# grouped one over those runs (``megablox``' transposed kernel, one group a
# token tile): 2 C ``_ONTO_TILE`` D FLOP, a sixteenth at G 4,096. A grid step
# takes ``_ONTO_ROWS`` of the slab's rows (a slab is whole ``_GMM_TILE_M``
# tiles, so they always divide it) by the whole width. Measured on the v5e
# (``tools/microbench_moe --held``, PR 46; the table under ``_onto_tokens``):
# (rows, token tile, width tile) = (256, 256, D) is ahead at all four cells'
# shapes; (512, 128, D) within 3%; a width tile of 1,024 costs 0.01-0.06 ms,
# 1,024 rows a step 0.05-0.09, a token tile of 512 0.03.
_ONTO_TILE = 256
_ONTO_ROWS = 256


def onto_impl(shape: Optional[Tuple[int, int, int]] = None) -> str:
    """How a slab's rows are summed onto their tokens: ``"banded"`` (the
    grouped product over token tiles) on a TPU, ``"dense"`` (the product
    with the [G, C] matrix) elsewhere and for a ``shape`` (slab rows,
    tokens, model width) the tiles do not divide. The dense product is the
    form the kernel is held equal to; a test off the chip names
    ``"interpret"``, the banded form with its kernel in Pallas interpret
    mode."""
    if jax.default_backend() != "tpu":
        return "dense"
    if shape is not None and (
        shape[0] % _ONTO_ROWS or shape[1] % _ONTO_TILE or shape[2] % 128
    ):
        return "dense"
    return "banded"


@functools.partial(jax.jit, static_argnames=("g", "tiling", "interpret"))
def onto_tokens_banded(rows, tok, w, g, tiling, interpret=False):
    """``_onto_tokens`` as a grouped product: the slab's rows in token
    order (rows of no token behind every token, never read), each token
    tile's run of them against the [tile, run] matrix of their weights.
    Jitted, so a program lowers the kernel once a shape, and under its OWN
    name, which the custom call carries: ``moe_experts_roofline.train``
    reads every operation named ``gmm*`` / ``tgmm*`` as an expert matmul."""
    # the module (the package's ``gmm`` is the function), and its kernel
    # un-jitted: the custom call takes the name of the innermost jit
    tgmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    ).tgmm.__wrapped__
    tile = tiling[1]
    ts, ws, by_tok = jax.lax.sort(
        (tok, w, jnp.arange(tok.shape[0], dtype=jnp.int32)), num_keys=1
    )
    # [tile, C]: w[i] at (its token's place in its tile, i)
    m = jnp.where(
        jnp.arange(tile, dtype=ts.dtype)[:, None] == (ts % tile)[None],
        ws.astype(rows.dtype)[None], 0,
    )
    out = tgmm(
        m, rows[by_tok], _group_sizes(ts // tile, g // tile), rows.dtype,
        tiling, interpret=interpret,
    )
    return out.reshape(g, rows.shape[1])


def _onto_tokens(rows, tok, w, g):
    """[C, D] rows -> [G, D]: token t's sum of ``w[i] * rows[i]`` over
    the rows i with ``tok[i] == t`` (``w`` None: 1); a row of no token
    (``tok[i] == g``) adds to nothing, whatever it holds (the grouped matmul
    leaves it unwritten). The transpose of a row gather with no scatter:
    bf16 products summed in float32, as the whole form's weighted sum, in
    one of two forms (``onto_impl``) that differ in the order of a token's
    sum at most. On the chip (``tools/microbench_moe --held``, PR 46, one
    4,096-token chunk, device ms; the banded form with its sort and row
    gather, its kernel alone in brackets):

        slab C x width D           dense    banded          gather k a token
        8,192 x 2,304 (Mellum)     0.878    0.281 (0.132)   0.777
        8,192 x 2,048 (long-row)   0.789    0.234 (0.099)   0.654
        4,096 x 2,048 (Laguna)     0.398    0.176 (0.061)   0.818
        2,048 x 2,304 (Kimi)       0.227    0.120 (0.052)   0.946

    and equal to the dense product to the bit or to one bf16 step."""
    impl = onto_impl((rows.shape[0], g, rows.shape[1]))
    if impl != "dense":
        if w is None:
            w = jnp.ones(tok.shape, jnp.float32)
        return onto_tokens_banded(
            rows, tok, w.astype(jnp.float32), g,
            (_ONTO_ROWS, _ONTO_TILE, rows.shape[1]),
            interpret=impl == "interpret",
        )
    # 0 x what is not a number is not 0: the rows of no token go in as zeros
    rows = jnp.where((tok < g)[:, None], rows, 0)
    onto = jnp.arange(g, dtype=tok.dtype)[:, None] == tok[None]
    m = onto.astype(rows.dtype) if w is None else jnp.where(
        onto, w.astype(rows.dtype)[None], 0
    )
    return jnp.dot(m, rows, preferred_element_type=jnp.float32).astype(
        rows.dtype
    )


# The two permutations again, over the ``c`` rows of a slab: ``tok[i]`` is
# the token of the pair at the slab's position i, ``g`` behind the held
# pairs; ``w_c[i]`` that pair's routing weight, 0 there.
@jax.custom_vjp
def _dispatch_held(xf, tok):
    """[G, D] tokens -> [C, D] rows sorted by expert, zeros behind them."""
    return jnp.concatenate([xf, jnp.zeros((1, xf.shape[1]), xf.dtype)])[tok]


def _dispatch_held_fwd(xf, tok):
    return _dispatch_held(xf, tok), (tok, xf.shape[0])


def _dispatch_held_bwd(res, g_xs):
    tok, g = res
    return _onto_tokens(g_xs, tok, None, g), None


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_held(ys, w_c, tok, g):
    """[C, D] sorted expert outputs and their [C] float32 routing weights
    -> [G, D]: what the slab's pairs add to their tokens."""
    return _onto_tokens(ys, tok, w_c, g)


def _combine_held_fwd(ys, w_c, tok, g):
    return _combine_held(ys, w_c, tok, g), (ys, w_c, tok)


def _combine_held_bwd(g, res, g_out):
    ys, w_c, tok = res
    # row i of the slab: its token's cotangent (zeros behind the held
    # pairs), times that pair's weight for the row, dotted with the row for
    # the weight
    gg = jnp.concatenate(
        [g_out, jnp.zeros((1, g_out.shape[1]), g_out.dtype)]
    )[tok]
    g_wc = jnp.where(tok < g, jnp.einsum(
        "cd,cd->c", gg, ys, preferred_element_type=jnp.float32
    ), 0)
    return gg * w_c.astype(ys.dtype)[:, None], g_wc.astype(w_c.dtype), None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def _slab(lo, c, order, w, group_sizes):
    """The slab at sorted positions [lo, lo + c): (each expert's pairs in it
    [E], the token of each position [C], its routing weight [C]).
    ``order`` is the sort, padded to a whole number of slabs."""
    g, k = w.shape
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(ends, lo, lo + c) - jnp.clip(
        ends - group_sizes, lo, lo + c
    )
    live = lo + jnp.arange(c) < ends[-1]
    pair = jax.lax.dynamic_slice_in_dim(order, lo, c)
    tok = jnp.where(live, pair // k, g)
    w_c = jnp.where(live, w.reshape(-1)[pair], 0)
    return sizes, tok, w_c


def _slab_experts(xf, w_c, w_gate, w_up, w_down, sizes, tok):
    """[G, D]: what one slab's pairs add to their tokens' outputs.
    Differentiable in the first five."""
    g, d = xf.shape
    c = tok.shape[0]
    impl = grouped_impl((c, d, w_gate.shape[-1]))
    with device_scope("moe_dispatch"):
        xs = _dispatch_held(xf, tok)
    with device_scope("moe_experts"):
        h = jax.nn.silu(
            _grouped_matmul(xs, w_gate, sizes, impl)
        ) * _grouped_matmul(xs, w_up, sizes, impl)
        ys = _grouped_matmul(
            jnp.where((tok < g)[:, None], h, 0), w_down, sizes, impl
        )
    with device_scope("moe_dispatch"):
        return _combine_held(ys, w_c, tok, g)


def _sorted_pairs(pair_expert, c):
    """The pairs by expert, held ones first, padded to a whole number of
    ``c``-row slabs."""
    order = jnp.argsort(pair_expert, stable=True)
    return jnp.pad(order, (0, -order.shape[0] % c))


def _slabs(group_sizes, c):
    """How many ``c``-row slabs the held pairs fill: one at least."""
    return jnp.maximum(-(-group_sizes.sum() // c), 1)


def _add(a, b):
    """A further slab's part onto the sum so far, summed in float32."""
    return (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype)


def _slabs_forward(xf, pair_expert, topk_p, group_sizes, ws, c):
    """``_run_experts`` over ``c``-row slabs of the sorted stream, in a loop
    of as many trips as the held pairs fill slabs (one, where they fit).
    The backward (``_run_slabs_bwd``) runs the first slab in line and the
    others in a loop of the same kind, so that no loop is differentiated
    through and an expert stack's cotangent is made once a slab that ran and
    added to nothing where one did; the same here would lower every kernel
    twice for the sake of one add of a [tokens, D] sum to zeros. ``ws``: the
    three expert stacks."""
    with device_scope("moe_dispatch"):
        order = _sorted_pairs(pair_expert, c)

    def slab(carry):
        s, out = carry
        with device_scope("moe_dispatch"):
            sizes, tok, w_c = _slab(s * c, c, order, topk_p, group_sizes)
        return s + 1, _add(out, _slab_experts(xf, w_c, *ws, sizes, tok))

    n = _slabs(group_sizes, c)
    with device_scope("moe_dispatch"):
        return jax.lax.while_loop(
            lambda sc: sc[0] < n, slab, (0, jnp.zeros_like(xf))
        )[1]


_run_slabs = jax.custom_vjp(_slabs_forward, nondiff_argnums=(5,))


def _run_slabs_fwd(xf, pair_expert, topk_p, group_sizes, ws, c):
    return _slabs_forward(xf, pair_expert, topk_p, group_sizes, ws, c), (
        xf, pair_expert, topk_p, group_sizes, ws
    )


def _run_slabs_bwd(c, res, g_out):
    xf, pair_expert, topk_p, group_sizes, ws = res
    with device_scope("moe_dispatch"):
        order = _sorted_pairs(pair_expert, c)
        # pair j sits at sorted position inv[j]
        inv = jnp.argsort(order[:pair_expert.shape[0]])

    def grads(lo):
        with device_scope("moe_dispatch"):
            sizes, tok, w_c = _slab(lo, c, order, topk_p, group_sizes)
        g_xf, g_wc, *g_ws = jax.vjp(
            lambda *a: _slab_experts(*a, sizes, tok), xf, w_c, *ws
        )[1](g_out)
        with device_scope("moe_dispatch"):
            # back to the pairs' own order: every pair outside the slab
            # reads the one zero
            at = inv - lo
            g_w = jnp.concatenate([g_wc, jnp.zeros((1,), g_wc.dtype)])[
                jnp.where((at >= 0) & (at < c), at, c)
            ]
        return (g_xf, g_w.reshape(topk_p.shape).astype(topk_p.dtype), *g_ws)

    def more(carry):
        s, acc = carry
        return s + 1, tuple(map(_add, acc, grads(s * c)))

    n = _slabs(group_sizes, c)
    with device_scope("moe_dispatch"):
        _, (g_xf, g_w, *g_ws) = jax.lax.while_loop(
            lambda sc: sc[0] < n, more, (1, grads(0))
        )
    return g_xf, None, g_w, None, tuple(g_ws)


_run_slabs.defvjp(_run_slabs_fwd, _run_slabs_bwd)


def _run_held(xf, pair_expert, topk_p, group_sizes, w_gate, w_up, w_down, c):
    """``_run_experts`` of a layer that holds a share, over slabs of ``c``
    rows: (out [G, D], how many slabs ran)."""
    if group_sizes is None:
        with device_scope("moe_dispatch"):
            group_sizes = _group_sizes(pair_expert, w_gate.shape[0])
    if c == pair_expert.shape[0]:  # a slab no shorter than the stream
        return _run_experts(
            xf, pair_expert, topk_p, group_sizes, w_gate, w_up, w_down
        ), jnp.ones((), jnp.int32)
    with device_scope("moe_dispatch"):
        n = _slabs(group_sizes, c)
    return _run_slabs(
        xf, pair_expert, topk_p, group_sizes, (w_gate, w_up, w_down), c
    ), n


def moe_ffn(
    x: jnp.ndarray,  # [B, T, D]
    w_router: jnp.ndarray,  # [D, E]
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
    valid: Optional[jnp.ndarray] = None,  # [B, T] bool
    token_axes: Optional[Tuple[str, ...]] = None,
    first_expert: int = 0,
    router_act: str = "softmax",
    router_bias: Optional[jnp.ndarray] = None,
    routed_scaling_factor: float = 1.0,
) -> Tuple[jnp.ndarray, MoEStats]:
    """Returns (output [B, T, D], ``MoEStats``). ``valid`` masks padding
    and inactive slots out: zero output, no load, no statistic.
    ``token_axes`` names the mesh axes the tokens are split over when this
    runs per device inside a ``shard_map``: the statistics (and the
    auxiliary loss) are then the whole batch's, summed over those axes.
    Where ``w_gate`` holds fewer experts than ``w_router`` scores, they are
    experts ``first_expert`` and following, and the output is their part.
    The scores here are ``x @ w_router``; scores made elsewhere go to
    ``moe_ffn_scored``, which is this layer from the choice on."""

    def scores(xf):
        # float32 and full precision (``router_scores``): with near-equal
        # probabilities a bf16-pass product can swap a token's k-th and
        # (k+1)-th expert. The mesh form makes the same scores, of a
        # chip's own tokens before they travel, so a token's choice does
        # not depend on the form
        return router_scores(xf, w_router)

    return _moe_ffn(
        x, scores, w_router.shape[-1], w_gate, w_up, w_down,
        num_experts_per_tok, norm_topk_prob, valid, token_axes, first_expert,
        router_act, router_bias, routed_scaling_factor,
    )


def moe_ffn_scored(
    x: jnp.ndarray,  # [B, T, D]
    logits: jnp.ndarray,  # [B, T, E] float32: a score an expert, however made
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
    valid: Optional[jnp.ndarray] = None,
    token_axes: Optional[Tuple[str, ...]] = None,
    first_expert: int = 0,
    router_act: str = "softmax",
    router_bias: Optional[jnp.ndarray] = None,
    routed_scaling_factor: float = 1.0,
) -> Tuple[jnp.ndarray, MoEStats]:
    """``moe_ffn`` from the choice on, for a router whose scores are not
    ``x @ w_router`` (``router_mlp``): the activation, the top-k, the
    statistics, the dispatch and the experts are the same code."""
    n_routed = logits.shape[-1]
    return _moe_ffn(
        x, lambda xf: logits.reshape(-1, n_routed), n_routed, w_gate, w_up,
        w_down, num_experts_per_tok, norm_topk_prob, valid, token_axes,
        first_expert, router_act, router_bias, routed_scaling_factor,
    )


def router_mlp(
    lp: Dict, h: jnp.ndarray, eps: float,
    carried: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(scores [B, T, E], state [B, T, R]) float32 of a router that is an
    MLP (``models/config.RouterConfig``). The state: the layer's normed
    input ``h`` projected to R plus the previous layer's state
    (``carried``; None: there is none) times the layer's learned scalar.
    The scores: the state under an RMS norm and two GELU layers of width R,
    one score an expert. Float32 at full precision, as the product it
    replaces and for the same reason (``moe_ffn``); R is an eighth of the
    model's width, so the products are small beside one expert's."""
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)

    def w(name):
        return lp[name].astype(f32)

    with device_scope("moe_route"):
        r = dot(h.astype(f32), w("w_router_in")) + w("b_router_in")
        if carried is not None:
            r = r + w("router_carry") * carried
        z = rms_norm(r, w("router_norm"), eps)
        for i in ("1", "2"):
            z = jax.nn.gelu(
                dot(z, w(f"w_router_{i}")) + w(f"b_router_{i}"),
                approximate=False,
            )
        return dot(z, w("w_router_out")) + w("b_router_out"), r


def _moe_ffn(
    x, scores, n_routed, w_gate, w_up, w_down, num_experts_per_tok,
    norm_topk_prob, valid, token_axes, first_expert, router_act, router_bias,
    routed_scaling_factor,
):
    """The sparse layer; ``scores(xf)`` gives the [G, E] float32 router
    scores of the flattened tokens, under ``moe_route``."""
    b, t, d = x.shape
    e = w_gate.shape[0]  # experts held: the groups of the grouped matmuls
    k = num_experts_per_tok
    xf = x.reshape(-1, d)  # [G, D]
    g = xf.shape[0]
    vf = None if valid is None else valid.reshape(-1)
    with device_scope("moe_route"):
        logits = scores(xf)
        topk_p, topk_i, probs = router_topk(
            logits, k, norm_topk_prob, router_act, router_bias,
            routed_scaling_factor,
        )
        aux = load_balancing_loss(
            probs, topk_i, n_routed, valid=vf, token_axes=token_axes
        )
        # a pair of a padding token belongs to no expert: id E sorts last
        pair_expert = topk_i.reshape(-1).astype(jnp.int32)  # [G*k]
        if e < n_routed:  # so does a pair routed to an expert held elsewhere
            pair_expert = pair_expert - first_expert
            pair_expert = jnp.where(
                (pair_expert >= 0) & (pair_expert < e), pair_expert, e
            )
        if vf is not None:
            pair_expert = jnp.where(jnp.repeat(vf, k), pair_expert, e)
        group_sizes = _group_sizes(pair_expert, e)
        load = (
            jax.lax.psum(group_sizes, token_axes) if token_axes
            else group_sizes
        )
        stats = MoEStats(
            aux_loss=aux,
            assignments=load.sum().astype(jnp.float32),
            max_expert_assignments=load.max().astype(jnp.float32),
        )
    n_chunks = _token_chunks(g, k)
    rows = g * k // n_chunks  # of one chunk's stream
    run = _run_experts
    if e < n_routed:  # a share: a chunk's expert path runs over a buffer
        c = _held_rows(rows, e, n_routed)
        run = functools.partial(_run_held, c=c)
    if n_chunks == 1:
        out = run(xf, pair_expert, topk_p, group_sizes, w_gate, w_up, w_down)
    else:
        # a long stream in token chunks, one after the other, each chunk's
        # intermediates dropped after it (recomputed in its backward): the
        # stream's buffers are a chunk's. Dropless, so a token's output does
        # not depend on its chunk-mates
        def chunk(args):
            return run(*args, None, w_gate, w_up, w_down)

        out = jax.lax.map(jax.checkpoint(chunk), (
            xf.reshape(n_chunks, -1, d),
            pair_expert.reshape(n_chunks, -1),
            topk_p.reshape(n_chunks, -1, k),
        ))
    if e < n_routed:
        out, slabs = out
        with device_scope("moe_route"):
            ran = (c * slabs.sum()).astype(jnp.float32)
            over = (slabs > 1).sum().astype(jnp.float32)
            if token_axes:
                ran, over = jax.lax.psum((ran, over), token_axes)
            stats = stats._replace(stream_rows=ran, overflow_chunks=over)
    out = out.reshape(g, d).astype(x.dtype)
    return out.reshape(b, t, d), stats


def moe_ffn_from_params(
    cfg, lp: Dict, h: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
    token_axes: Optional[Tuple[str, ...]] = None,
    router_logits: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, MoEStats]:
    """The sparse layer, for training and serving layer bodies alike: the
    routed experts, and the family's shared expert where it has one.
    ``router_logits`` [B, T, E]: the scores of a model whose router is not
    ``h @ w_router`` (``cfg.router``; ``router_mlp`` makes them)."""
    routed = dict(
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        valid=valid,
        token_axes=token_axes,
        router_act=cfg.router_act,
        router_bias=lp.get("router_bias"),
        routed_scaling_factor=cfg.routed_scaling_factor,
    )
    experts = (lp["w_gate"], lp["w_up"], lp["w_down"])
    if router_logits is None:
        out, stats = moe_ffn(h, lp["w_router"], *experts, **routed)
    else:
        out, stats = moe_ffn_scored(h, router_logits, *experts, **routed)
    if cfg.shared_expert_size:
        out = out + shared_expert_from_params(cfg, lp, h)
    return out, stats


# the leaves of one layer that the routed experts read
EXPERT_WEIGHTS = ("w_router", "w_gate", "w_up", "w_down")


def sparse_leaves(cfg) -> Tuple[str, ...]:
    """The leaves of one layer that ``moe_ffn_from_params`` reads (a model
    whose router is an MLP hands it scores, not ``w_router``)."""
    names = EXPERT_WEIGHTS if cfg.router is None else EXPERT_WEIGHTS[1:]
    if cfg.router_act == "sigmoid" or cfg.router is not None:
        names += ("router_bias",)
    if cfg.shared_expert_size:
        names += ("w_shared_gate", "w_shared_up", "w_shared_down")
        if cfg.shared_expert_gate:
            names += ("w_shared_router",)
    return names


def router_scores(x: jnp.ndarray, w_router: jnp.ndarray) -> jnp.ndarray:
    """[..., E] float32: ``x @ w_router`` in float32 at full precision."""
    return jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


# The exchange of the mesh form, as two functions that are each other's
# transpose: rows go out to every chip, sums come back to the rows' owners.
# ``rows``: the mesh axes that split both the rows and the experts (the rows
# are gathered over them, the chips' parts sent to their owners); ``same``:
# those that split the experts among chips which hold the SAME rows (summed
# over, nothing moves). A sum across chips accumulates in float32 and is cast
# once, after, as a token's k pairs (``_weighted_sum``) and a chunk's slabs
# (``_add``) are: every chip's part travels to the row's owner in its own
# dtype (an all-to-all: in bf16 half the bytes of a float32 reduce-scatter,
# which the v5e's compiler also turns into an all-reduce of the whole array
# when it scatters rows) and the owner widens and sums. The hand-written rule
# is there for that: the transpose of a bf16 gather would sum the chips'
# cotangents in bf16.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _to_every_chip(x, rows, same):
    """[G, ...] -> [n G, ...]: every chip's rows along ``rows``."""
    if rows:
        x = jax.lax.all_gather(x, rows, axis=0, tiled=True)
    if same:
        x = jax.lax.psum(x.astype(jnp.float32), same).astype(x.dtype)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _to_their_owners(y, rows, same):
    """[n G, ...] -> [G, ...]: the chips' parts of every row summed, each
    row's sum at the chip that owns the row."""
    if not (rows or same):
        return y
    total = y.astype(jnp.float32)
    if rows:
        n = jax.lax.psum(1, rows)  # chips along ``rows``: a Python int
        total = jax.lax.all_to_all(
            y.reshape(n, -1, *y.shape[1:]), rows, 0, 0
        ).sum(0, dtype=jnp.float32)
    if same:
        total = jax.lax.psum(total, same)
    return total.astype(y.dtype)


_to_every_chip.defvjp(
    lambda x, rows, same: (_to_every_chip(x, rows, same), None),
    lambda rows, same, _, g: (_to_their_owners(g, rows, same),),
)
_to_their_owners.defvjp(
    lambda y, rows, same: (_to_their_owners(y, rows, same), None),
    lambda rows, same, _, g: (_to_every_chip(g, rows, same),),
)


def make_sharded_moe_ffn(cfg, mesh: Mesh, weight_specs: Dict[str, P]):
    """The sparse layer's mesh form: ``moe_fn(lp, h, valid)`` (and the
    router's scores after them, for a model whose router is an MLP) for
    ``transformer.apply``, running per device inside a ``shard_map`` (None
    on one device: the plain call serves). The dispatch sorts the (token,
    expert) pairs, and a sort over a token axis that GSPMD has sharded would
    gather it; a Mosaic grouped matmul cannot be partitioned either.

    The experts stay where they are and the tokens travel. The weights come
    in as ``weight_specs`` says they are sharded (one ``PartitionSpec`` for
    each of ``sparse_leaves``, the layer axis gone): a chip holds WHOLE
    experts, its share of the three expert stacks' first dimension
    (``parallel/sharding.mesh_rules``: over ``expert`` and ``fsdp``), and
    no collective ever carries an expert stack or its gradient over those
    axes. A chip scores ITS OWN tokens (the router's product, or an MLP
    router's scores handed in, split as the tokens are); the tokens, their
    validity and their float32 scores are gathered over the axes that split
    both tokens and experts (``fsdp``); the chip runs the layer that holds a
    share (``moe_ffn_scored`` with its ``first_expert``: the same choice
    from the same scores, then the slab path over the gathered tokens in
    chunks of ``_STREAM_ROWS``, dropless at any load); the chips' parts are
    sent to the tokens' owners, which sum them in float32
    (``_to_their_owners``; over ``expert``, where the chips hold the same
    tokens, summed in place). What a leaf is split on otherwise (the
    router's and a shared expert's model width over ``fsdp``, an expert's
    own width over ``tensor``) is gathered whole inside, as ZeRO-3 gathers a
    dense layer's, and the transpose reduce-scatters its gradient; devices
    that hold the same tokens AND the same experts (``tensor``) repeat the
    work. A shared expert runs on the chip's own tokens. The statistics are
    the whole layer's: summed (the busiest expert: the largest) over the
    chips, ``stream_rows`` and ``overflow_chunks`` among them wherever the
    mesh splits the experts. A mesh whose expert-splitting axes do not
    divide the experts held is refused here, when the program is built."""
    from areal_tpu.parallel.sharding import split_axes

    if mesh.size == 1:
        return None
    specs = {k: weight_specs[k] for k in sparse_leaves(cfg)}
    split = split_axes(mesh, specs["w_gate"][0])
    sizes = {a: mesh.shape[a] for a in split}
    chips = functools.reduce(lambda a, b: a * b, sizes.values(), 1)
    if cfg.num_held_experts % chips or "seq" in split:
        raise ValueError(
            f"the mesh splits the expert stacks over {sizes} ({chips} "
            f"shares), which must divide the {cfg.num_held_experts} experts "
            f"held (family {cfg.family!r}) and not split a row's tokens "
            "(``seq``): a chip holds whole experts, an equal share of them"
        )
    share = cfg.num_held_experts // chips
    token_axes = ("data", "fsdp", "seq")
    rows = tuple(a for a in split if a in token_axes)
    same = tuple(a for a in split if a not in token_axes)

    def per_device(weights, h, valid, *router_logits):
        lp = {}
        for k, w in weights.items():
            for dim, names in enumerate(specs[k]):
                if dim == 0 and k in EXPERT_WEIGHTS[1:]:
                    continue  # whole experts, where they are
                axes = split_axes(mesh, names)
                if axes:
                    w = jax.lax.all_gather(w, axes, axis=dim, tiled=True)
            lp[k] = w
        if router_logits:
            (logits,) = router_logits
        else:
            with device_scope("moe_route"):
                logits = router_scores(h, lp["w_router"])
        with device_scope("moe_exchange"):
            h_all = _to_every_chip(h, rows, ())
            logits_all = _to_every_chip(logits, rows, ())
            valid_all = valid if not rows else jax.lax.all_gather(
                valid, rows, axis=0, tiled=True
            )
        out, stats = moe_ffn_scored(
            h_all, logits_all, lp["w_gate"], lp["w_up"], lp["w_down"],
            num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            valid=valid_all,
            token_axes=tuple(a for a in token_axes if a not in rows),
            first_expert=jax.lax.axis_index(split) * share if split else 0,
            router_act=cfg.router_act,
            router_bias=lp.get("router_bias"),
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
        with device_scope("moe_exchange"):
            out = _to_their_owners(out, rows, same)
        if split:  # each chip counted its own experts' pairs and rows
            with device_scope("moe_route"):
                stats = stats._replace(**jax.lax.psum({
                    k: getattr(stats, k) for k in
                    ("assignments", "stream_rows", "overflow_chunks")
                }, split), max_expert_assignments=jax.lax.pmax(
                    stats.max_expert_assignments, split
                ))
        if cfg.shared_expert_size:
            out = out + shared_expert_from_params(cfg, lp, h)
        return out, stats

    x_spec = P(("data", "fsdp"), "seq", None)
    # scores made outside (an MLP router's) come in split as the tokens are
    scored = (x_spec,) if cfg.router is not None else ()
    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(specs, x_spec, P(("data", "fsdp"), "seq")) + scored,
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return lambda lp, h, valid, *router_logits: sharded(
        {k: lp[k] for k in specs}, h, valid, *router_logits
    )


def shared_expert_from_params(cfg, lp: Dict, h: jnp.ndarray) -> jnp.ndarray:
    """The shared expert: a dense SiLU-gated FFN on EVERY token, added as
    it is, or (``cfg.shared_expert_gate``: qwen2_moe, HF
    Qwen2MoeSparseMoeBlock) scaled by a per-token sigmoid gate."""
    shared = (
        jax.nn.silu(h @ lp["w_shared_gate"]) * (h @ lp["w_shared_up"])
    ) @ lp["w_shared_down"]
    if not cfg.shared_expert_gate:
        return shared
    gate = jax.nn.sigmoid(
        (h @ lp["w_shared_router"]).astype(jnp.float32)
    ).astype(h.dtype)
    return gate * shared
