"""The yardstick's arithmetic for Qwen3-Next's language model as one chip of
32 holds it: stored parameters, operations of a trained token where three
layers in four are a scalar-decay gated delta rule and the chip computes the
experts it holds, the full layers' core at heads of 256, and what the
``kda_*`` state-pass kernels are asked for.

As ``benchmark/arith.py`` and ``benchmark/arith_kimi.py``: kept with the
benchmark so that no later PR can change how a number is worked out, written
from a configuration file's own keys (``num_local_experts`` = the experts
held here, ``num_experts`` = the router's width), importing nothing of the
program; tested against hand-worked values. MFU convention: backward = 2 x
forward, recomputation under checkpointing NOT counted. What is not a weight
matmul:

- the delta rule, a token and VALUE head: the three products of the
  token-by-token rule over the [dk, dv] state (reading it with k, the
  rank-one update, reading it with q), 6 dk dv operations. The chunked form
  the program runs does more; the rule's own count is the yardstick;
- the full layers' core over CAUSAL pairs, n (n + 1) / 2 a sequence: a pair
  and query head costs 2 D for the score and 2 D for the value, D = 256;
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_tok * num_local_experts / num_experts`` a token and
  layer; a run's own count is the program's ``moe_assignments``.
"""

from benchmark.arith import load_peaks  # noqa: F401

CHUNK = 64  # tokens a chunk of the program's scan (ops/kda.CHUNK)


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def layer_kinds(c):
    """The mixer of every layer: full attention where ``(i + 1) %
    full_attention_interval == 0``, the delta rule elsewhere. Every FFN is
    sparse."""
    every = c.get("full_attention_interval", 4)
    return [
        "full" if (i + 1) % every == 0 else "gdn"
        for i in range(c["num_hidden_layers"])
    ]


def gdn_layers(c):
    return sum(m == "gdn" for m in layer_kinds(c))


def full_layers(c):
    return c["num_hidden_layers"] - gdn_layers(c)


def _gdn_width(c):
    """(key heads, value heads, key width, value width)."""
    return (c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"])


def gdn_mixer_matmul_weights(c):
    """W_qkvz (q, k a key head; v, z a value head), W_ba (beta and the
    decay's input a value head) and W_out."""
    d = c["hidden_size"]
    hk, hv, dk, dv = _gdn_width(c)
    return d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d


def gdn_mixer_params(c):
    """The matmul weights, the one convolution over q, k and v's channels,
    A_log and dt_bias a value head, the output norm."""
    hk, hv, dk, dv = _gdn_width(c)
    conv = (2 * hk * dk + hv * dv) * c["linear_conv_kernel_dim"]
    return gdn_mixer_matmul_weights(c) + conv + 2 * hv + dv


def full_mixer_matmul_weights(c):
    """q with its gate beside it (2 x heads x D), k, v, o."""
    d, hq, hkv = (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    )
    dh = c["head_dim"]
    return d * 2 * hq * dh + 2 * d * hkv * dh + hq * dh * d


def full_mixer_params(c):
    return full_mixer_matmul_weights(c) + 2 * c["head_dim"]  # q, k norms


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c):
    """The shared expert's three matrices and its gate's one column."""
    d = c["hidden_size"]
    return 3 * d * c["shared_expert_intermediate_size"] + d


def ffn_params(c):
    """The router, the held experts, the shared one and its gate."""
    return (
        c["hidden_size"] * c["num_experts"]
        + c["num_local_experts"] * expert_params(c) + shared_expert_params(c)
    )


def param_count(c):
    """Stored parameters of the share: every layer's mixer, FFN and two
    norms; embedding, untied head and the final norm."""
    d = c["hidden_size"]
    total = 2 * d * c["vocab_size"] + d
    for mixer in layer_kinds(c):
        total += (
            gdn_mixer_params(c) if mixer == "gdn" else full_mixer_params(c)
        ) + ffn_params(c) + 2 * d
    return total


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return c["num_experts_per_tok"] * c["num_local_experts"] / c["num_experts"]


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates."""
    d = c["hidden_size"]
    total = d * c["vocab_size"]
    for mixer in layer_kinds(c):
        total += (
            gdn_mixer_matmul_weights(c) if mixer == "gdn"
            else full_mixer_matmul_weights(c)
        )
        total += (
            d * c["num_experts"] + expert_pairs_per_token(c) * expert_params(c)
            + shared_expert_params(c)
        )
    return total


def gdn_rule_flops_per_token(c):
    """The rule's three products over the state, all value heads, one
    forward."""
    _, hv, dk, dv = _gdn_width(c)
    return 6.0 * hv * dk * dv


def core_flops_per_pair(c):
    """Score and value product of one causal pair, all query heads, one
    forward: 4 D a head, D = ``head_dim`` (256)."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def forward_flops(c, seq_lens):
    """One forward over whole sequences: 2 x weights a token, the rule a
    token and gdn layer, the core over causal pairs a full layer."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = sum(causal_pairs(n) for n in seq_lens)
    return (
        2.0 * n_tok * trained_weights(c)
        + gdn_layers(c) * n_tok * gdn_rule_flops_per_token(c)
        + full_layers(c) * pairs * core_flops_per_pair(c)
    )


def recompute_forwards(n_minibatches):
    """Plain forwards a trained token, beside its gradient pass: minibatches
    2..n of a step's n recompute their proximal logprobs (the first takes
    its own forward's), as ``arith_mellum`` counts them."""
    return (n_minibatches - 1) / n_minibatches


def train_step_flops(c, seq_lens, n_minibatches):
    """Forward + backward (2 x forward) + the recompute forwards the chip
    runs (``recompute_forwards``)."""
    return (3.0 + recompute_forwards(n_minibatches)) * forward_flops(
        c, seq_lens
    )


# ------------------------------------------------ what the kernels are asked --
def _state_pass_bytes(c, itemsize):
    """Bytes a token and value head of the state-pass kernels' three calls,
    at the least (``ops/kda.py``; the scalar-decay form hands them the same
    operands): Wk, Wv, Qb, Kt [C, d] and Aqk [C, C] in the compute dtype and
    the float32 decay [d] (the chunk's one number laid over the channels);
    the output [C, d]; the chunk-start state [d, d] in the compute dtype."""
    _, _, d, _ = _gdn_width(c)
    operands = (4 * d + CHUNK) * itemsize + 4.0 * d / CHUNK
    out = d * itemsize
    state = d * d * itemsize / CHUNK
    return {
        "kda_fwd_h": operands + out,
        "kda_fwd_h_states": operands + out + state,
        "kda_bwd_dh": operands + state + out + operands,
    }


def kda_kernel_bytes(c, kda_tokens, n_minibatches, itemsize=2):
    """What the ``kda_*`` kernels have to move for ``kda_tokens`` real tokens
    (summed over gdn layers: the program's counter of the train
    microbatches) in the passes the trainer's programs execute: the logprob
    recomputes (``recompute_forwards``) and the forward (``kda_fwd_h`` each;
    the forward is NOT run
    again under checkpointing, its output is kept), and the backward
    (``kda_fwd_h_states`` to recompute the chunk states, ``kda_bwd_dh``).
    Chunk slots that padding fills are not counted."""
    _, hv, _, _ = _gdn_width(c)
    b = _state_pass_bytes(c, itemsize)
    a_token = (
        (recompute_forwards(n_minibatches) + 1) * b["kda_fwd_h"]
        + b["kda_fwd_h_states"] + b["kda_bwd_dh"]
    )
    return float(kda_tokens) * hv * a_token


def core_kernel_flops(c, causal_pairs_traced, n_minibatches):
    """What the full layers' core had to compute for ``causal_pairs_traced``
    causal (query, key) pairs, a full layer each (summed over the full
    layers): two matmuls a pair and query head (2 D each) in a recompute
    forward and in the gradient pass's forward (kept: not run again under
    checkpointing), four in the backward."""
    a_pair = c["num_attention_heads"] * 2.0 * c["head_dim"]
    return float(causal_pairs_traced) * a_pair * (
        2 * (recompute_forwards(n_minibatches) + 1) + 4
    )


def expert_kernel_flops(c, routed_pairs, checkpointed, n_minibatches):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches): 3 matmuls a pair; the
    recompute forwards, the gradient pass's forward, that forward again under
    checkpointing, the backward's two products a matmul."""
    passes = (
        recompute_forwards(n_minibatches) + 1 + (1 if checkpointed else 0) + 2
    )
    return passes * 2.0 * routed_pairs * expert_params(c)
