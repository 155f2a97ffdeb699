"""The yardstick's arithmetic: operations, bytes, peaks, live contexts.

Kept with the benchmark so that no later PR can change how a number is
worked out. The FLOP counts follow ``areal_tpu/utils/flops.py`` (matmul
operations only; MFU convention: backward = 2 x forward, recomputation under
checkpointing not counted); they are written out here from a configuration
file's own keys and do not import the program.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------- sizes --
def head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def matmul_weights(c):
    """Weight elements one token's forward multiplies with (head included,
    tied or not: it is the same matmul)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def param_count(c):
    """Stored parameters (tied embedding counted once, biases and norms in)."""
    d = c["hidden_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    per_layer = (
        d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]
        + q + 2 * kv  # q/k/v bias
        + 2 * d  # the two norms
    )
    n = c["num_hidden_layers"] * per_layer + d * c["vocab_size"] + d
    if not c.get("tie_word_embeddings", False):
        n += d * c["vocab_size"]
    return n


def kv_bytes_per_token(c, dtype_bytes=2):
    """K and V of one token over all layers, in the served dtype."""
    return (
        2 * c["num_hidden_layers"] * c["num_key_value_heads"]
        * head_dim(c) * dtype_bytes
    )


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


# ---------------------------------------------------------------- peaks --
def load_peaks(device_kind, path=None):
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or not isinstance(table[device_kind], dict):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: "
            "add it with its source, there is no default"
        )
    return table[device_kind]


# ------------------------------------------------------- live contexts --
def interp_count(t, t_first, t_end, n_end):
    """Tokens a request had produced by time ``t`` if it produced ``n_end``
    evenly between its first token and ``t_end`` (decode steps are shared by
    all rows in flight, so a request's tokens come at the engine's step
    rate)."""
    if t <= t_first or n_end <= 0:
        return 0.0
    if t >= t_end or t_end <= t_first:
        return float(n_end)
    return n_end * (t - t_first) / (t_end - t_first)


def kv_tokens_read(spans, t0, t1):
    """Sum over decode steps in [t0, t1] of the live context each step had
    to read, in tokens. ``spans`` are (prompt_len, t_first, t_end, n_end)
    per request. A request that produced dn tokens in the window at contexts
    from c0 to c1 read dn * (c0 + c1) / 2 tokens of KV."""
    total = 0.0
    for prompt_len, t_first, t_end, n_end in spans:
        n0 = interp_count(t0, t_first, t_end, n_end)
        n1 = interp_count(t1, t_first, t_end, n_end)
        dn = n1 - n0
        if dn > 0:
            total += dn * (prompt_len + (n0 + n1) / 2.0)
    return total
