"""The yardstick's arithmetic for Keye-VL-2.0's language model as one chip of
eight holds it: operations of a trained token where attention reads the
``sa_config.topk`` keys an indexer picks and the chip computes the experts it
holds, and what the new kernels are asked for.

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys (``num_local_experts`` = the experts held here, ``num_experts`` = the
router's width), importing nothing of the program; tested against hand-worked
values. MFU convention: backward = 2 x forward, recomputation under
checkpointing NOT counted. Three kinds of pairs carry the work that is not a
weight matmul:

- SELECTED (query, key) pairs, sum_t min(t + 1, topk) a sequence: the
  attention core, 2 matmuls forward and 4 backward of ``head_dim`` a head;
- CAUSAL pairs, n (n + 1) / 2: the index scores, one product of
  ``indexer_head_dim`` an indexer head, forward only (the selection is by
  index and has no backward);
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_tok * num_local_experts / num_experts`` a token and layer
  (one, as published); a run's own count is the program's ``moe_assignments``.
"""

from benchmark.arith import load_peaks  # noqa: F401


def selected_pairs(n, topk):
    """sum over positions p < n of min(p + 1, topk)."""
    n, topk = int(n), int(topk)
    if n <= topk:
        return n * (n + 1) // 2
    return topk * (topk + 1) // 2 + (n - topk) * topk


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def _widths(c):
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d, q, kv, hi, di


def indexer_weights_per_layer(c):
    """The indexer's three projections: query heads, one key head, a weight
    a head."""
    d, _, _, hi, di = _widths(c)
    return d * hi * di + d * di + d * hi


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return (
        c["num_experts_per_tok"] * c["num_local_experts"] / c["num_experts"]
    )


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates: attention projections, the router over all experts, the
    expected share of the held experts, the head over the vocabulary held."""
    d, q, kv, _, _ = _widths(c)
    per_layer = (
        d * q + 2 * d * kv + q * d
        + d * c["num_experts"]
        + expert_pairs_per_token(c) * 3 * d * c["moe_intermediate_size"]
    )
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def param_count(c):
    """Stored parameters of the share: the held experts, the router, the
    indexer, two layer norms and the per-head q and k norms a layer;
    embedding, untied head and the final norm."""
    d, q, kv, _, _ = _widths(c)
    per_layer = (
        d * q + 2 * d * kv + q * d
        + indexer_weights_per_layer(c)
        + d * c["num_experts"]
        + c["num_local_experts"] * 3 * d * c["moe_intermediate_size"]
        + 2 * d + 2 * c["head_dim"]
    )
    return c["num_hidden_layers"] * per_layer + 2 * d * c["vocab_size"] + d


def core_flops_per_pair(c):
    """QK^T and PV of one selected pair over all query heads, one forward."""
    return 2 * 2.0 * c["num_attention_heads"] * c["head_dim"]


def index_flops_per_pair(c):
    """One causal pair's index score: a product an indexer head."""
    _, _, _, hi, di = _widths(c)
    return 2.0 * hi * di


def forward_flops(c, seq_lens):
    """One forward over whole sequences: 2 x weights a token (the indexer's
    projections among them), the core over selected pairs, the index scores
    over causal pairs."""
    topk, layers = c["sa_config"]["topk"], c["num_hidden_layers"]
    n_tok = sum(int(n) for n in seq_lens)
    sel = sum(selected_pairs(n, topk) for n in seq_lens)
    cau = sum(causal_pairs(n) for n in seq_lens)
    return (
        2.0 * n_tok * (trained_weights(c)
                       + layers * indexer_weights_per_layer(c))
        + layers * (sel * core_flops_per_pair(c)
                    + cau * index_flops_per_pair(c))
    )


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute). The indexer has no backward: its
    projections and scores count once a forward."""
    topk, layers = c["sa_config"]["topk"], c["num_hidden_layers"]
    n_tok = sum(int(n) for n in seq_lens)
    cau = sum(causal_pairs(n) for n in seq_lens)
    indexer = layers * (
        2.0 * n_tok * indexer_weights_per_layer(c)
        + cau * index_flops_per_pair(c)
    )
    whole = forward_flops(c, seq_lens)
    return (3.0 + n_forward_only) * (whole - indexer) + (
        1.0 + n_forward_only
    ) * indexer


# ------------------------------------------------ what the kernels are asked --
def sparse_core_kernel_flops(c, kept_pairs, n_forward_only=1):
    """The attention core's matmul operations over ``kept_pairs`` selected
    pairs (summed over layers, the program's ``dsa_kept_pairs`` of the train
    microbatches) for the passes the trainer's programs execute: the logprob
    recompute and the forward at 2 matmuls a pair and head, one backward at
    4. The forward is NOT run again under checkpointing (the remat policy
    saves ``attn_out``). What a masked tile computes beyond the selection is
    not counted."""
    return (n_forward_only + 1 + 2) * kept_pairs * core_flops_per_pair(c)


def sparse_core_kernel_bytes(c, row_tokens, n_forward_only=1):
    """Bytes one packed row of ``row_tokens`` makes the core move a layer,
    at the least: q, k, v and the output in bf16 each pass, and the
    selection as one byte a (query, key) pair under the diagonal, once a
    kv head group."""
    _, q, kv, _, _ = _widths(c)
    t = int(row_tokens)
    passes = n_forward_only + 1 + 2
    qkvo = t * (2 * q + 2 * kv) * 2
    mask = c["num_key_value_heads"] * t * (t + 1) // 2
    return passes * (qkvo + mask)


def index_kernel_flops(c, causal_pairs_, checkpointed, n_forward_only=1):
    """The index scores' operations over ``causal_pairs_`` (summed over
    layers) for the passes executed: the logprob recompute, the forward, and
    the forward again where the layer is recomputed under checkpointing (the
    selection's thresholds are saved, the scores are not)."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0)
    return passes * causal_pairs_ * index_flops_per_pair(c)


def expert_kernel_flops(c, routed_pairs, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (summed over layers, the
    program's ``moe_assignments`` of the train microbatches): as
    ``arith_olmoe.expert_kernel_flops``, 3 matmuls a pair; the plain
    forwards, the gradient pass's forward, that forward again under
    checkpointing, the backward's two products a matmul."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return (
        passes * 2.0 * routed_pairs * 3
        * c["hidden_size"] * c["moe_intermediate_size"]
    )
