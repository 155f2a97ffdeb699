"""The yardstick's arithmetic for ZAYA1's language model as one chip of 8
holds it: stored parameters, operations of a trained token where attention
runs in a latent half as wide as the residual stream and a token takes ONE
expert as wide as that stream, and what the attention core and the grouped
matmuls are asked for.

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys (``num_local_experts`` = the experts held here, absent = all of
``num_experts``, the router's width), importing nothing of the program;
tested against hand-worked values. MFU convention: backward = 2 x forward,
recomputation under checkpointing NOT counted. What counts as a matmul
weight: the mixer's four projections and its second convolution (a [d, d]
matrix a head and tap), the router's four matrices, the experts, the head
(the tied table: the same matmul). What does not: the first convolution (a
tap a channel), biases, norms, scales, the temperature. Beside the weights:

- the core over CAUSAL pairs, n (n + 1) / 2 a sequence and layer: a pair
  and head costs 2 d for the score and 2 d for the value, over the
  ``num_attention_heads`` QUERY heads (the values' two heads are read by
  four query heads each); what a kernel's tile computes above the diagonal
  or across two sequences is nobody's need and is not counted;
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_tok * num_local_experts / num_experts`` a token and
  layer (a half here: the other half of a row's tokens are the partner
  chip's work and are not counted as this chip's); a run's own count is the
  program's ``moe_assignments``.
"""

from benchmark.arith import load_peaks  # noqa: F401


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def experts_held(c):
    return c.get("num_local_experts", c["num_experts"])


def heads(c):
    """Query heads + key heads: the groups of the second convolution."""
    return c["num_attention_heads"] + c["num_key_value_heads"]


def mixer_matmul_weights(c):
    """q, k, the two value heads, o, and the second convolution's matrices."""
    d, dh = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * dh
    return (
        2 * d * q + d * c["num_key_value_heads"] * dh + 2 * d * dh
        + heads(c) * c["cca_time1"] * dh * dh
    )


def mixer_params(c):
    """The matmul weights, the first convolution's taps, both biases and a
    temperature a key head."""
    channels = heads(c) * c["head_dim"]
    return (
        mixer_matmul_weights(c) + channels * c["cca_time0"] + 2 * channels
        + c["num_key_value_heads"]
    )


def router_matmul_weights(c):
    d, r = c["hidden_size"], c["router_hidden_size"]
    return d * r + 2 * r * r + r * c["num_experts"]


def router_params(c):
    """The four matrices, their biases, the norm, the carried scalar and the
    balancing bias (state that nothing trains)."""
    r, e = c["router_hidden_size"], c["num_experts"]
    return router_matmul_weights(c) + 3 * r + e + r + 1 + e


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c, held=None):
    """A layer with ``held`` experts (the file's own count by default):
    mixer, router, experts, two norms and the eight residual vectors."""
    held = experts_held(c) if held is None else held
    return (
        mixer_params(c) + router_params(c) + held * expert_params(c)
        + 10 * c["hidden_size"]
    )


def table_params(c):
    return c["vocab_size"] * c["hidden_size"]


def param_count(c):
    """Stored parameters of the share (of the whole model where the file
    holds every expert): the layers, the tied table, the final norm."""
    assert c["tie_word_embeddings"]
    return (
        c["num_hidden_layers"] * layer_params(c) + table_params(c)
        + c["hidden_size"]
    )


def active_params(c):
    """Beside the table: what one token's forward touches, whole model (one
    expert a token and layer)."""
    return c["num_hidden_layers"] * layer_params(
        c, held=c["num_experts_per_tok"]
    )


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return c["num_experts_per_tok"] * experts_held(c) / c["num_experts"]


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates, on this chip."""
    per_layer = (
        mixer_matmul_weights(c) + router_matmul_weights(c)
        + expert_pairs_per_token(c) * expert_params(c)
    )
    return c["num_hidden_layers"] * per_layer + table_params(c)


def core_flops_per_pair(c):
    """Score and value product of one (query, key) pair, all query heads,
    one forward."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def attended_pairs(seq_lens):
    """The causal pairs of whole sequences, ONE layer."""
    return sum(causal_pairs(n) for n in seq_lens)


def starts(seq_lens):
    """The tokens at which the convolutions and the shift reset, ONE layer."""
    return sum(1 for n in seq_lens if int(n) > 0)


def forward_flops(c, seq_lens):
    """One forward over whole sequences: 2 x weights a token, and every
    layer's core over the causal pairs."""
    n_tok = sum(int(n) for n in seq_lens)
    return 2.0 * n_tok * trained_weights(c) + (
        c["num_hidden_layers"] * attended_pairs(seq_lens)
        * core_flops_per_pair(c)
    )


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute)."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens)


# ------------------------------------------------ what the kernels are asked --
def core_kernel_flops(c, pairs, n_forward_only=1):
    """What the attention core has to compute for ``pairs`` (query, key)
    pairs (summed over the layers and the train microbatches: the program's
    ``cca_causal_pairs``) in the passes the trainer's programs execute: 2
    matmuls a pair and query head in the logprob recompute and in the
    forward (NOT run again under checkpointing: its output is kept), 4 in
    the backward, 2 d operations each."""
    matmuls = 2 * (n_forward_only + 1) + 4
    return (
        float(pairs) * c["num_attention_heads"] * 2.0 * c["head_dim"]
        * matmuls
    )


def expert_kernel_flops(c, routed_pairs, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches): 3 matmuls a pair of the
    expert's width; the plain forwards, the gradient pass's forward, that
    forward again under checkpointing, the backward's two products a
    matmul."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return passes * 2.0 * routed_pairs * expert_params(c)
