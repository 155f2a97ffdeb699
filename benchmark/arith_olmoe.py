"""The yardstick's arithmetic for the sparse-expert configuration: matmul
operations of a token with ``num_experts_per_tok`` ACTIVE experts and the
router, and the expert matmuls' operations alone (what the grouped-matmul
kernel is asked for).

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys, importing nothing of the program; tested against hand-worked values
and against ``areal_tpu/utils/flops.py``. MFU convention: backward = 2 x
forward, recomputation under checkpointing NOT counted as useful work. The
kernel's roofline counts what the kernel EXECUTES, so there the recomputed
forward counts (``expert_kernel_flops``).
"""

from benchmark.arith import head_dim, kv_bytes_per_token, load_peaks  # noqa: F401


def expert_weights_per_layer(c):
    """Weight elements of the experts one token multiplies with in one
    layer: gate, up and down of each of its active experts (the config's
    ``intermediate_size`` is one expert's width)."""
    return (
        c["num_experts_per_tok"] * 3 * c["hidden_size"] * c["intermediate_size"]
    )


def matmul_weights(c):
    """Weight elements one token's forward multiplies with: attention
    projections, the router, the active experts, the head."""
    d = c["hidden_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    per_layer = (
        d * q + 2 * d * kv + q * d  # wq, wk, wv, wo
        + d * c["num_experts"]  # router
        + expert_weights_per_layer(c)
    )
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def param_count(c):
    """Stored parameters: every expert, the router, the two layer norms and
    the projection-wide q and k norms, embedding and untied head."""
    d = c["hidden_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    per_layer = (
        d * q + 2 * d * kv + q * d
        + d * c["num_experts"]
        + c["num_experts"] * 3 * d * c["intermediate_size"]
        + 2 * d + q + kv
    )
    n = c["num_hidden_layers"] * per_layer + d * c["vocab_size"] + d
    if not c.get("tie_word_embeddings", False):
        n += d * c["vocab_size"]
    return n


def forward_flops(c, seq_lens):
    """One causal forward over whole sequences: 2 x weights per token, plus
    QK^T and PV at 2 * n^2/2 * Hq * Dh each per layer."""
    seq_lens = [int(n) for n in seq_lens]
    hd = c["num_attention_heads"] * head_dim(c)
    attn = sum(2.0 * n * n * hd * c["num_hidden_layers"] for n in seq_lens)
    return 2.0 * sum(seq_lens) * matmul_weights(c) + attn


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute) over the same sequences."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens)


def expert_forward_flops(c, n_tokens):
    """The expert matmuls alone, one forward over ``n_tokens`` tokens."""
    return (
        2.0 * n_tokens * c["num_hidden_layers"] * expert_weights_per_layer(c)
    )


def expert_kernel_flops(c, n_tokens, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for one trainer step over
    ``n_tokens`` tokens: the plain forwards, the gradient pass's forward,
    that forward again where the layer is recomputed under checkpointing,
    and the backward's two products a matmul (2 x forward)."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return passes * expert_forward_flops(c, n_tokens)
