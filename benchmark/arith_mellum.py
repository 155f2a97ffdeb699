"""The yardstick's arithmetic for Mellum2's language model as one chip of 8
holds it: stored parameters, operations of a trained token where three
layers in four attend to a 1024-key window and the chip computes the experts
it holds, and what the two attention cores are asked for.

As ``benchmark/arith_laguna.py``: kept with the benchmark so that no later PR
can change how a number is worked out, written from a configuration file's
own keys (``num_local_experts`` = the experts held here, absent = all of
``num_experts``, the router's width), importing nothing of the program;
tested against hand-worked values. What differs from Laguna's: every layer
has ``num_attention_heads`` query heads, no gate and no shared expert; q and
k are normed a head (256 numbers a layer, stored, no matmul); and THE
FORWARDS ARE COUNTED AS THE CHIP RUNS THEM: a step of ``n`` minibatches
recomputes the proximal logprobs of minibatches 2..n only (the first takes
its own forward's, PERF.md PR 43), so a trained token costs forward +
backward (2 x forward) + (n - 1) / n recompute forwards, 3.75 forwards at
the recipe's four, where the older cells' arithmetic counts 4. MFU
convention otherwise theirs: recomputation under checkpointing NOT counted.
What is not a weight matmul:

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
    """(mixer, ffn) of every layer: mixers ``full`` and ``swa``, FFNs
    ``dense`` and ``moe``."""
    out = [
        ("full" if t == "full_attention" else "swa",
         "dense" if m == "dense" else "moe")
        for t, m in zip(c["layer_types"], c["mlp_layer_types"])
    ]
    assert len(out) == c["num_hidden_layers"]
    return out


def mixer_layers(c, mixer):
    return sum(m == mixer for m, _ in layers(c))


def experts_held(c):
    return c.get("num_local_experts", c["num_experts"])


def mixer_params(c):
    """q and o over the query heads, k and v over the key heads: the matmul
    weights of a mixer of either kind."""
    d, dh = c["hidden_size"], c["head_dim"]
    return (
        2 * d * c["num_attention_heads"] * dh
        + 2 * d * c["num_key_value_heads"] * dh
    )


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def ffn_params(c, ffn):
    """A dense FFN; or the router and the held experts."""
    d = c["hidden_size"]
    if ffn == "dense":
        return 3 * d * c["intermediate_size"]
    return d * c["num_experts"] + experts_held(c) * expert_params(c)


def param_count(c):
    """Stored parameters of the share (of the whole model where the file
    holds every expert): every layer's mixer, its q and k norms, FFN and two
    norms; embedding, untied head and the final norm."""
    d = c["hidden_size"]
    total = 2 * d * c["vocab_size"] + d
    for _, ffn in layers(c):
        total += (
            mixer_params(c) + 2 * c["head_dim"] + ffn_params(c, ffn) + 2 * d
        )
    return total


def active_param_count(c):
    """Parameters a token's forward reads in the WHOLE model the keys
    describe: as ``param_count`` with ``num_experts_per_tok`` experts a
    sparse layer."""
    whole = dict(c, num_local_experts=c["num_experts_per_tok"])
    return param_count(whole)


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return c["num_experts_per_tok"] * experts_held(c) / c["num_experts"]


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates."""
    d = c["hidden_size"]
    total = d * c["vocab_size"]
    for _, ffn in layers(c):
        total += mixer_params(c)
        if ffn == "dense":
            total += ffn_params(c, "dense")
        else:
            total += (
                d * c["num_experts"]
                + expert_pairs_per_token(c) * expert_params(c)
            )
    return total


def core_flops_per_pair(c):
    """Score and value product of one (query, key) pair, all heads, one
    forward."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


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
    return 2.0 * n_tok * trained_weights(c) + core_flops_per_pair(c) * sum(
        pairs[mixer] for mixer, _ in layers(c)
    )


def recompute_forwards(n_minibatches):
    """Plain forwards a trained token, beside its gradient pass: minibatches
    2..n of a step's n recompute their proximal logprobs."""
    return (n_minibatches - 1) / n_minibatches


def train_step_flops(c, seq_lens, n_minibatches):
    """Forward + backward (2 x forward) + the recompute forwards the chip
    runs (``recompute_forwards``)."""
    return (3.0 + recompute_forwards(n_minibatches)) * forward_flops(
        c, seq_lens
    )


# ------------------------------------------------ what the kernels are asked --
def core_kernel_flops(c, pairs, n_minibatches):
    """What an attention core has to compute for ``pairs`` (query, key)
    pairs (summed over its layers and the train microbatches: the program's
    ``swa_pairs`` for the window layers, ``full_pairs_from_counters`` for
    the full ones) in the passes the trainer's programs execute: 2 matmuls a
    pair and head in a recompute forward and in the gradient pass's forward
    (NOT run again under checkpointing: its output is kept), 4 in the
    backward, 2 d operations each."""
    matmuls = 2 * (recompute_forwards(n_minibatches) + 1) + 4
    return (
        float(pairs) * c["num_attention_heads"] * 2.0 * c["head_dim"]
        * matmuls
    )


def full_pairs_from_counters(c, swa_causal_pairs):
    """The causal pairs the full-attention layers attended, from the window
    layers' counter of what THEY would attend without a window (the same
    sequences, a layer each)."""
    return (
        float(swa_causal_pairs) * mixer_layers(c, "full")
        / mixer_layers(c, "swa")
    )


def expert_kernel_flops(c, routed_pairs, checkpointed, n_minibatches):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches): 3 matmuls a pair; the
    recompute forwards, the gradient pass's forward, that forward again
    under checkpointing, the backward's two products a matmul."""
    passes = (
        recompute_forwards(n_minibatches) + 1 + (1 if checkpointed else 0) + 2
    )
    return passes * 2.0 * routed_pairs * expert_params(c)
