"""The yardstick's arithmetic for Kimi-Linear's language model as one chip of
32 holds it: stored parameters, operations of a trained token where most
layers are a gated delta rule and the chip computes the experts it holds, and
what the ``kda_*`` kernels are asked for.

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys (``num_local_experts`` = the experts held here, ``num_experts`` = the
router's width), importing nothing of the program; tested against hand-worked
values. MFU convention: backward = 2 x forward, recomputation under
checkpointing NOT counted. What is not a weight matmul:

- the delta rule, a token and head: the three products of the token-by-token
  rule over the [dk, dv] state (reading it with k, the rank-one update,
  reading it with q), 6 dk dv operations. The chunked form the program runs
  does more (about 11 dk dv); the rule's own count is the yardstick;
- the latent-attention core over CAUSAL pairs, n (n + 1) / 2 a sequence:
  a pair and head costs 2 (nope + rope) for the score and 2 dv for the value;
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_token * num_local_experts / num_experts`` a token and
  layer; a run's own count is the program's ``moe_assignments``.
"""

from benchmark.arith import load_peaks  # noqa: F401

CHUNK = 64  # tokens a chunk of the program's scan (ops/kda.CHUNK)


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def layer_kinds(c):
    """The (mixer, ffn) of every layer, from the published 1-indexed lists."""
    la = c["linear_attn_config"]
    return [
        ("kda" if i in la["kda_layers"] else "mla",
         "dense" if i <= c.get("first_k_dense_replace", 0) else "moe")
        for i in range(1, c["num_hidden_layers"] + 1)
    ]


def kda_layers(c):
    return sum(m == "kda" for m, _ in layer_kinds(c))


def _kda_width(c):
    la = c["linear_attn_config"]
    return la["num_heads"], la["head_dim"]


def kda_mixer_matmul_weights(c):
    """q, k, v and o projections, the two low-rank gates (through
    ``head_dim``) and beta."""
    d = c["hidden_size"]
    h, dh = _kda_width(c)
    return 4 * d * h * dh + 2 * (d * dh + dh * h * dh) + d * h


def kda_mixer_params(c):
    """The matmul weights, three convolutions, A_log, dt_bias, o_norm."""
    h, dh = _kda_width(c)
    conv = 3 * h * dh * c["linear_attn_config"]["short_conv_kernel_size"]
    return kda_mixer_matmul_weights(c) + conv + h + h * dh + dh


def mla_mixer_matmul_weights(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, dv = c["kv_lora_rank"], c["v_head_dim"]
    return (
        d * h * (nope + rope) + d * (rank + rope)
        + rank * h * (nope + dv) + h * dv * d
    )


def mla_mixer_params(c):
    return mla_mixer_matmul_weights(c) + c["kv_lora_rank"]


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def ffn_params(c, ffn):
    """A dense FFN; or the router with its bias, the held experts and the
    shared ones."""
    d = c["hidden_size"]
    if ffn == "dense":
        return 3 * d * c["intermediate_size"]
    return (
        d * c["num_experts"] + c["num_experts"]
        + (c["num_local_experts"] + c.get("num_shared_experts", 0))
        * expert_params(c)
    )


def param_count(c):
    """Stored parameters of the share: every layer's mixer, FFN and two
    norms; embedding, untied head and the final norm."""
    d = c["hidden_size"]
    total = 2 * d * c["vocab_size"] + d
    for mixer, ffn in layer_kinds(c):
        total += (
            kda_mixer_params(c) if mixer == "kda" else mla_mixer_params(c)
        ) + ffn_params(c, ffn) + 2 * d
    return total


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return (
        c["num_experts_per_token"] * c["num_local_experts"] / c["num_experts"]
    )


def trained_weights(c):
    """Weight elements one token's forward multiplies with and the backward
    differentiates."""
    d = c["hidden_size"]
    total = d * c["vocab_size"]
    for mixer, ffn in layer_kinds(c):
        total += (
            kda_mixer_matmul_weights(c) if mixer == "kda"
            else mla_mixer_matmul_weights(c)
        )
        if ffn == "dense":
            total += 3 * d * c["intermediate_size"]
        else:
            total += d * c["num_experts"] + (
                expert_pairs_per_token(c) + c.get("num_shared_experts", 0)
            ) * expert_params(c)
    return total


def kda_rule_flops_per_token(c):
    """The rule's three products over the state, all heads, one forward."""
    h, dh = _kda_width(c)
    return 6.0 * h * dh * dh


def mla_core_flops_per_pair(c):
    """Score and value product of one causal pair, all heads, one forward."""
    return 2.0 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    )


def forward_flops(c, seq_lens):
    """One forward over whole sequences: 2 x weights a token, the rule a
    token and KDA layer, the core over causal pairs a latent layer."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = sum(causal_pairs(n) for n in seq_lens)
    n_kda = kda_layers(c)
    n_mla = c["num_hidden_layers"] - n_kda
    return (
        2.0 * n_tok * trained_weights(c)
        + n_kda * n_tok * kda_rule_flops_per_token(c)
        + n_mla * pairs * mla_core_flops_per_pair(c)
    )


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute)."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens)


# ------------------------------------------------ what the kernels are asked --
def _state_pass_bytes(c, itemsize):
    """Bytes a token and head of the state-pass kernels' three calls, at
    the least (``ops/kda.py``): the chunk operands Wk, Wv, Qb, Kt [C, d]
    and Aqk [C, C] in the compute dtype and the float32 decay [d]; the
    output [C, d]; the chunk-start state [d, d] in the compute dtype."""
    _, d = _kda_width(c)
    operands = (4 * d + CHUNK) * itemsize + 4.0 * d / CHUNK
    out = d * itemsize
    state = d * d * itemsize / CHUNK
    return {
        "kda_fwd_h": operands + out,
        "kda_fwd_h_states": operands + out + state,
        # reads the operands, the states and the output's cotangent,
        # writes the operands' cotangents
        "kda_bwd_dh": operands + state + out + operands,
    }


def kda_kernel_bytes(c, kda_tokens, itemsize=2, n_forward_only=1):
    """What the ``kda_*`` kernels have to move for ``kda_tokens`` real tokens
    (summed over KDA layers: the program's counter of the train
    microbatches) in the passes the trainer's programs execute: the logprob
    recompute and the forward (``kda_fwd_h`` each; the forward is NOT run
    again under checkpointing, its output is kept), and the backward
    (``kda_fwd_h_states`` to recompute the chunk states, ``kda_bwd_dh``).
    Chunk slots that padding fills are not counted."""
    h, _ = _kda_width(c)
    b = _state_pass_bytes(c, itemsize)
    a_token = (
        (n_forward_only + 1) * b["kda_fwd_h"] + b["kda_fwd_h_states"]
        + b["kda_bwd_dh"]
    )
    return float(kda_tokens) * h * a_token


def kda_kernel_flops(c, kda_tokens, n_forward_only=1):
    """The same passes' matmul operations: a forward call's four products a
    chunk (Wk S, Qb S, Aqk U, Kt^T U: 6 d d + 2 C d a token and head), the
    backward call's nine (14 d d + 4 C d)."""
    h, d = _kda_width(c)
    fwd = 6.0 * d * d + 2.0 * CHUNK * d
    bwd = 14.0 * d * d + 4.0 * CHUNK * d
    return float(kda_tokens) * h * ((n_forward_only + 2) * fwd + bwd)


def expert_kernel_flops(c, routed_pairs, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches): 3 matmuls a pair; the
    plain forwards, the gradient pass's forward, that forward again under
    checkpointing, the backward's two products a matmul."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return passes * 2.0 * routed_pairs * expert_params(c)
