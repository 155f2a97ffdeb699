"""The yardstick's arithmetic for JoyAI-LLM-Flash's language model as one chip
of 32 holds it: stored parameters, operations of a trained token where every
layer's mixer is latent attention at heads of 192 | 128 and the chip computes
the experts it holds, the draft module that runs in ``grad_step`` only, and
what the core and the grouped-matmul kernels are asked for.

As ``benchmark/arith.py`` and ``benchmark/arith_kimi.py``: kept with the
benchmark so that no later PR can change how a number is worked out, written
from a configuration file's own keys (``num_local_experts`` = the experts
held here, ``n_routed_experts`` = the router's width), importing nothing of
the program; tested against hand-worked values. MFU convention: backward =
2 x forward, recomputation under checkpointing NOT counted. What is not a
weight matmul, and what the module changes:

- the latent-attention core over CAUSAL pairs, n (n + 1) / 2 a sequence: a
  pair and head costs 2 (nope + rope) for the score and 2 dv for the value,
  2 x 192 + 2 x 128 forward;
- (token, expert) pairs ROUTED TO EXPERTS HELD: in expectation
  ``num_experts_per_tok * num_local_experts / n_routed_experts`` a token and
  layer; a run's own count is the program's ``moe_assignments`` (of which
  ``mtp_assignments`` are the module's block's);
- the draft module (``num_nextn_predict_layers`` 1: W_eh, one sparse block,
  the trunk's head) runs in the gradient pass and in NO recompute forward
  (it gives no policy logprob). Its head's weight is the trunk's under a
  stop-gradient: the backward there is the ONE product that hands the
  module's stream its gradient, so the head counts forward + 1, not + 2.
"""

from benchmark.arith import load_peaks  # noqa: F401


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def layer_ffns(c):
    """The FFN of every layer of the trunk: "dense" the first
    ``first_k_dense_replace``, "moe" the others."""
    dense = c.get("first_k_dense_replace", 0)
    return [
        "dense" if i < dense else "moe" for i in range(c["num_hidden_layers"])
    ]


def draft_blocks(c):
    """The module's blocks: ``num_nextn_predict_layers`` (sparse ones)."""
    return c.get("num_nextn_predict_layers", 0)


def mixer_matmul_weights(c):
    """W_qa, W_qb, W_kva, W_kvb and W_o."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, qrank, dv = c["kv_lora_rank"], c["q_lora_rank"], c["v_head_dim"]
    return (
        d * qrank + qrank * h * (nope + rope) + d * (rank + rope)
        + rank * h * (nope + dv) + h * dv * d
    )


def mixer_params(c):
    """The matmul weights and the two latent norms."""
    return mixer_matmul_weights(c) + c["q_lora_rank"] + c["kv_lora_rank"]


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def ffn_params(c, ffn):
    """A dense FFN; or the router with its bias, the held experts and the
    shared ones."""
    d = c["hidden_size"]
    if ffn == "dense":
        return 3 * d * c["intermediate_size"]
    return (
        d * c["n_routed_experts"] + c["n_routed_experts"]
        + (c["num_local_experts"] + c.get("n_shared_experts", 0))
        * expert_params(c)
    )


def layer_params(c, ffn):
    """A layer: its mixer, its FFN, its two norms."""
    return mixer_params(c) + ffn_params(c, ffn) + 2 * c["hidden_size"]


def draft_params(c):
    """The module: a sparse block each, W_eh [2 D, D] and three norms; its
    embedding and head are the trunk's."""
    d = c["hidden_size"]
    return draft_blocks(c) * (layer_params(c, "moe") + 2 * d * d + 3 * d)


def param_count(c):
    """Stored parameters of the share: every layer; the module; embedding,
    untied head and the final norm."""
    d = c["hidden_size"]
    return (
        2 * d * c["vocab_size"] + d + draft_params(c)
        + sum(layer_params(c, ffn) for ffn in layer_ffns(c))
    )


def expert_pairs_per_token(c):
    """Expected pairs a token and layer routed to the experts held here."""
    return (
        c["num_experts_per_tok"] * c["num_local_experts"]
        / c["n_routed_experts"]
    )


def _layer_weights(c, ffn):
    """Weight elements one token multiplies with in a layer."""
    d = c["hidden_size"]
    if ffn == "dense":
        return mixer_matmul_weights(c) + 3 * d * c["intermediate_size"]
    return mixer_matmul_weights(c) + d * c["n_routed_experts"] + (
        expert_pairs_per_token(c) + c.get("n_shared_experts", 0)
    ) * expert_params(c)


def trained_weights(c):
    """Weight elements one token's TRUNK forward multiplies with and the
    backward differentiates: every layer and the head."""
    return c["hidden_size"] * c["vocab_size"] + sum(
        _layer_weights(c, ffn) for ffn in layer_ffns(c)
    )


def draft_weights(c):
    """(the module's own weight elements a token: W_eh and a sparse block
    each; the trunk's head it reads beside them)."""
    d = c["hidden_size"]
    own = draft_blocks(c) * (2 * d * d + _layer_weights(c, "moe"))
    return own, draft_blocks(c) * d * c["vocab_size"]


def core_flops_per_pair(c):
    """Score and value product of one causal pair, all heads, one forward:
    2 x 192 + 2 x 128 a head."""
    return 2.0 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    )


def forward_flops(c, seq_lens):
    """One forward of the TRUNK over whole sequences: 2 x weights a token,
    the core over causal pairs a layer."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = sum(causal_pairs(n) for n in seq_lens)
    return (
        2.0 * n_tok * trained_weights(c)
        + c["num_hidden_layers"] * pairs * core_flops_per_pair(c)
    )


def draft_flops(c, seq_lens):
    """The module's forward + backward over whole sequences: three times
    its own weights' and its core's forward, twice the head's (above)."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = sum(causal_pairs(n) for n in seq_lens)
    own, head = draft_weights(c)
    return (
        3.0 * (2.0 * n_tok * own
               + draft_blocks(c) * pairs * core_flops_per_pair(c))
        + 2.0 * 2.0 * n_tok * head
    )


def recompute_forwards(n_minibatches):
    """Plain forwards a trained token, beside its gradient pass: minibatches
    2..n of a step's n recompute their proximal logprobs (the first takes
    its own forward's), as ``arith_mellum`` counts them."""
    return (n_minibatches - 1) / n_minibatches


def train_step_flops(c, seq_lens, n_minibatches):
    """The trunk's forward + backward (2 x forward) + the recompute forwards
    the chip runs (``recompute_forwards``), and the module's gradient pass."""
    return (3.0 + recompute_forwards(n_minibatches)) * forward_flops(
        c, seq_lens
    ) + draft_flops(c, seq_lens)


# ------------------------------------------------ what the kernels are asked --
def core_kernel_flops(c, causal_pairs_traced, n_minibatches):
    """What the cores had to compute for ``causal_pairs_traced`` causal
    (query, key) pairs of the traced sequences (ONE core's: every core of a
    step sees the same): two matmuls a pair and head (192 for the score, 128
    for the value) in a forward, four in the backward; every trunk layer's
    core in the recompute forwards and in the gradient pass (its forward
    kept, not run again under checkpointing), the module's in the gradient
    pass alone: SIX cores in ``grad_step`` and five in the recompute at the
    published cut."""
    a_pair = c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    )
    trunk = c["num_hidden_layers"] * (
        2 * (recompute_forwards(n_minibatches) + 1) + 4
    )
    return float(causal_pairs_traced) * a_pair * (
        trunk + draft_blocks(c) * (2 + 4)
    )


def expert_kernel_flops(
    c, routed_pairs, draft_pairs, checkpointed, n_minibatches
):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (token,
    expert) pairs routed to the experts held (the program's
    ``moe_assignments`` of the train microbatches), ``draft_pairs`` of them
    the module's block's (``mtp_assignments``): 3 matmuls a pair; the
    gradient pass's forward, that forward again under checkpointing, the
    backward's two products a matmul, and for the TRUNK's pairs the
    recompute forwards beside them."""
    grad = 1 + (1 if checkpointed else 0) + 2
    trunk_pairs = float(routed_pairs) - float(draft_pairs)
    return 2.0 * expert_params(c) * (
        trunk_pairs * (recompute_forwards(n_minibatches) + grad)
        + float(draft_pairs) * grad
    )
