"""The yardstick's arithmetic for Laguna's language model as one chip of 16
holds it: stored parameters, operations of a trained token where three
layers in four attend to a window and the chip computes the experts it
holds, and what the two attention cores are asked for.

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys (``num_local_experts`` = the experts held here, absent = all of
``num_experts``, the router's width), importing nothing of the program;
tested against hand-worked values. MFU convention: backward = 2 x forward,
recomputation under checkpointing NOT counted. What is not a weight matmul:

- a full-attention layer's core over CAUSAL pairs, n (n + 1) / 2 a sequence,
  and a window layer's over the pairs INSIDE its window, sum_t min(t + 1,
  window): a pair and head costs 2 d for the score and 2 d for the value;
  what a kernel's tile computes outside the band is nobody's need and is
  not counted;
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_tok * num_local_experts / num_experts`` a token and
  layer; a run's own count is the program's ``moe_assignments``.
"""

from benchmark.arith import load_peaks  # noqa: F401


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def window_pairs(n, window):
    """sum_t min(t + 1, window) over a sequence of ``n`` tokens: the
    (query, key) pairs a window layer attends, the query's own key among
    its ``window``."""
    n, w = int(n), int(window)
    if n <= w:
        return causal_pairs(n)
    return causal_pairs(w) + (n - w) * w


def layers(c):
    """(mixer, ffn, query heads) of every layer: mixers ``full`` and
    ``swa``, FFNs ``dense`` and ``moe``."""
    out = [
        ("full" if t == "full_attention" else "swa",
         "dense" if m == "dense" else "moe", h)
        for t, m, h in zip(
            c["layer_types"], c["mlp_layer_types"],
            c["num_attention_heads_per_layer"],
        )
    ]
    assert len(out) == c["num_hidden_layers"]
    return out


def mixer_layers(c, mixer):
    return sum(m == mixer for m, _, _ in layers(c))


def mixer_heads(c, mixer):
    return next(h for m, _, h in layers(c) if m == mixer)


def experts_held(c):
    return c.get("num_local_experts", c["num_experts"])


def mixer_params(c, heads):
    """q and o over ``heads``, k and v over the key heads, the gate a head:
    all of them matmul weights."""
    d, dh = c["hidden_size"], c["head_dim"]
    return (
        2 * d * heads * dh + 2 * d * c["num_key_value_heads"] * dh
        + d * heads
    )


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c):
    return 3 * c["hidden_size"] * c.get("shared_expert_intermediate_size", 0)


def ffn_params(c, ffn):
    """A dense FFN; or the router, the held experts and the shared one."""
    d = c["hidden_size"]
    if ffn == "dense":
        return 3 * d * c["intermediate_size"]
    return (
        d * c["num_experts"] + experts_held(c) * expert_params(c)
        + shared_expert_params(c)
    )


def param_count(c):
    """Stored parameters of the share (of the whole model where the file
    holds every expert): every layer's mixer, FFN and two norms; embedding,
    untied head and the final norm."""
    d = c["hidden_size"]
    total = 2 * d * c["vocab_size"] + d
    for _, ffn, heads in layers(c):
        total += mixer_params(c, heads) + ffn_params(c, ffn) + 2 * d
    return total


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return c["num_experts_per_tok"] * experts_held(c) / c["num_experts"]


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates."""
    d = c["hidden_size"]
    total = d * c["vocab_size"]
    for _, ffn, heads in layers(c):
        total += mixer_params(c, heads)
        if ffn == "dense":
            total += ffn_params(c, "dense")
        else:
            total += (
                d * c["num_experts"]
                + expert_pairs_per_token(c) * expert_params(c)
                + shared_expert_params(c)
            )
    return total


def core_flops_per_pair(c, heads):
    """Score and value product of one (query, key) pair, all heads, one
    forward."""
    return 4.0 * heads * c["head_dim"]


def attended_pairs(c, seq_lens):
    """{"full": causal pairs, "swa": pairs inside the window} of whole
    sequences, ONE layer of each kind."""
    return {
        "full": sum(causal_pairs(n) for n in seq_lens),
        "swa": sum(window_pairs(n, c["sliding_window"]) for n in seq_lens),
    }


def forward_flops(c, seq_lens):
    """One forward over whole sequences: 2 x weights a token, and each
    layer's core over the pairs its kind attends."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = attended_pairs(c, seq_lens)
    return 2.0 * n_tok * trained_weights(c) + sum(
        pairs[mixer] * core_flops_per_pair(c, heads)
        for mixer, _, heads in layers(c)
    )


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute)."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens)


# ------------------------------------------------ what the kernels are asked --
def core_kernel_flops(c, mixer, pairs, n_forward_only=1):
    """What the attention core of ``mixer``'s layers has to compute for
    ``pairs`` (query, key) pairs (summed over those layers and the train
    microbatches: the program's ``swa_pairs`` for the window layers; the
    causal pairs a window layer would attend without its window, times the
    full layers over the window layers, for the full ones) in the passes the
    trainer's programs execute: 2 matmuls a pair and head in the logprob
    recompute and in the forward (NOT run again under checkpointing: its
    output is kept), 4 in the backward, 2 d operations each."""
    matmuls = 2 * (n_forward_only + 1) + 4
    return (
        float(pairs) * mixer_heads(c, mixer) * 2.0 * c["head_dim"] * matmuls
    )


def full_pairs_from_counters(c, swa_causal_pairs):
    """The causal pairs the full-attention layers attended, from the window
    layers' counter of what THEY would attend without a window (the same
    sequences, a layer each)."""
    return (
        float(swa_causal_pairs) * mixer_layers(c, "full")
        / mixer_layers(c, "swa")
    )


def expert_kernel_flops(c, routed_pairs, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches): 3 matmuls a pair; the
    plain forwards, the gradient pass's forward, that forward again under
    checkpointing, the backward's two products a matmul."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return passes * 2.0 * routed_pairs * expert_params(c)
