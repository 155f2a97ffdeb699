"""The yardstick's arithmetic for the ``jamba`` family's language model (AI21
Jamba2 3B's keys): parameters, the operations a trained token asks for, and
what a selective scan has to move. From the configuration's keys alone; it
imports nothing from ``areal_tpu``.

A layer is a mixer and a dense SwiGLU FFN under two RMS norms; the mixer is
attention where ``i % attn_layer_period == attn_layer_offset`` (q, k, v, o; no
bias, no rotation), else mamba: ``in_proj`` (D -> 2 C), a depthwise
convolution of ``mamba_d_conv`` taps with a bias, ``x_proj`` (C -> R + 2 N),
three inner norms (R, N, N), ``dt_proj`` (R -> C) with a bias, ``A_log`` [C,
N], ``D`` [C], ``out_proj`` (C -> D); C = ``mamba_expand`` x D. The head is
the embedding where ``tie_word_embeddings``.

Operations: 2 a multiply-add. A product's weight element is one multiply-add
a token forward; the attention cores' score and value products are counted
over CAUSAL pairs (``L (L + 1) / 2`` a sequence); the selective scan is three
multiply-adds a (channel, state) pair a token forward (the decay times the
state, the input's outer product into it, the output's sum over the states):
3 x 2 x C x N, beside which its ``exp`` is not counted. Forward + backward
(twice the forward) + GRPO's one logprob recompute; recomputation under
checkpointing is not counted.
"""

STATE_CHUNK = 256  # tokens a saved chunk-start state, at the coarsest


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def d_inner(c):
    return c.get("mamba_expand", 2) * c["hidden_size"]


def attn_layers(c):
    return sum(
        i % c["attn_layer_period"] == c["attn_layer_offset"]
        for i in range(c["num_hidden_layers"])
    )


def ssm_layers(c):
    return c["num_hidden_layers"] - attn_layers(c)


def ssm_mixer_matmul_weights(c):
    """in_proj, x_proj, dt_proj, out_proj."""
    d, ci = c["hidden_size"], d_inner(c)
    r, n = c["mamba_dt_rank"], c["mamba_d_state"]
    return d * 2 * ci + ci * (r + 2 * n) + r * ci + ci * d


def ssm_mixer_params(c):
    """The matmul weights, the convolution and its bias, the three inner
    norms, dt's bias, A_log and D."""
    ci, r, n = d_inner(c), c["mamba_dt_rank"], c["mamba_d_state"]
    return (
        ssm_mixer_matmul_weights(c) + ci * c["mamba_d_conv"] + ci
        + r + 2 * n + ci + ci * n + ci
    )


def attn_mixer_params(c):
    d, dh = c["hidden_size"], head_dim(c)
    hq, hk = c["num_attention_heads"], c["num_key_value_heads"]
    return d * hq * dh + 2 * d * hk * dh + hq * dh * d


def ffn_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def param_count(c):
    """Stored parameters: every layer's mixer, FFN and two norms; the
    embedding (the head too where tied, else a head of its own) and the final
    norm."""
    d = c["hidden_size"]
    vocab = c["vocab_size"] * d * (1 if c.get("tie_word_embeddings") else 2)
    return (
        ssm_layers(c) * ssm_mixer_params(c)
        + attn_layers(c) * attn_mixer_params(c)
        + c["num_hidden_layers"] * (ffn_params(c) + 2 * d) + vocab + d
    )


def trained_weights(c):
    """Weight elements one token's forward multiplies with: every product's,
    the head's among them (the embedding's lookup is no product)."""
    return (
        ssm_layers(c) * ssm_mixer_matmul_weights(c)
        + attn_layers(c) * attn_mixer_params(c)
        + c["num_hidden_layers"] * ffn_params(c)
        + c["vocab_size"] * c["hidden_size"]
    )


def ssm_scan_flops_per_token(c):
    """The scan's three multiply-adds a (channel, state), one forward."""
    return 3 * 2.0 * d_inner(c) * c["mamba_d_state"]


def attn_core_flops_per_pair(c):
    """Score and value product of one causal pair, all heads, one forward."""
    return 4.0 * c["num_attention_heads"] * head_dim(c)


def forward_flops(c, seq_lens, parts=False):
    """One forward over whole sequences; ``parts``: (products, the attention
    cores, the scans) apart."""
    n_tok = sum(int(n) for n in seq_lens)
    pairs = sum(causal_pairs(n) for n in seq_lens)
    each = (
        2.0 * n_tok * trained_weights(c),
        attn_layers(c) * pairs * attn_core_flops_per_pair(c),
        ssm_layers(c) * n_tok * ssm_scan_flops_per_token(c),
    )
    return each if parts else sum(each)


def train_step_flops(c, seq_lens, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute)."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens)


def ssm_scan_train_flops(c, seq_lens, n_forward_only=1):
    """The scans' part of ``train_step_flops``, returned apart."""
    return (3.0 + n_forward_only) * forward_flops(c, seq_lens, parts=True)[2]


# ------------------------------------------------ what the kernels are asked --
def _scan_bytes(c, itemsize):
    """Bytes a token and mamba layer a scan NEEDS to move, whatever
    implements it: ``x, dt, z`` in and ``y`` out in the compute dtype, ``B``
    and ``C`` in float32; with the chunk-start states (float32 [C, N] every
    ``STATE_CHUNK`` tokens) where a backward follows; the backward reads the
    forward's operands, the states and ``dy``, and writes ``dx, ddt, dz`` and
    ``dB, dC``."""
    ci, n = d_inner(c), c["mamba_d_state"]
    operands = 3 * ci * itemsize + 2 * n * 4.0
    state = ci * n * 4.0 / STATE_CHUNK
    return {
        "fwd": operands + ci * itemsize,
        "fwd_states": operands + ci * itemsize + state,
        "bwd": operands + state + ci * itemsize + 3 * ci * itemsize
        + 2 * n * 4.0,
    }


def ssm_kernel_bytes(c, ssm_tokens, checkpointed, itemsize=2,
                     n_forward_only=1):
    """What the scans have to move for ``ssm_tokens`` real tokens (summed
    over the mamba layers: the program's counter of the train microbatches)
    in the passes the trainer's programs execute: the logprob recompute, the
    gradient pass's forward, that forward again under checkpointing (the one
    that leaves the states), the backward. Padding is not counted."""
    b = _scan_bytes(c, itemsize)
    a_token = (
        n_forward_only * b["fwd"]
        + (b["fwd"] if checkpointed else 0.0) + b["fwd_states"] + b["bwd"]
    )
    return float(ssm_tokens) * a_token
