"""The plain reference: Qwen2's forward pass in float32 ``jax.numpy``.

Written from the published architecture (pre-norm decoder; RMSNorm; rotary
position embedding in the "rotate-half" pairing; grouped-query causal
attention with q/k/v bias and no o bias; SiLU-gated MLP; tied output head),
one sequence at a time, no cache, no kernels, no batching, matmuls at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise run in bf16 passes). It imports nothing from ``areal_tpu.models``.

What it takes from the program is the weight tree's layout only: leaves
stacked over layers under ``params["layers"]`` (``wq`` [L, D, Hq*Dh] applied
as ``x @ w``, ``bq``, ``wk``, ``bk``, ``wv``, ``bv``, ``wo``, ``input_norm``,
``post_attn_norm``, ``w_gate``, ``w_up``, ``w_down``), ``params["embedding"]``
[V, D] and ``params["final_norm"]``. Weights are upcast one layer at a time,
so the reference never holds a float32 copy of the model.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, cos, sin):
    # x [T, H, Dh]; cos/sin [T, Dh/2]; pairs are (i, i + Dh/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(c, x, lp, cos, sin, mask):
    t = x.shape[0]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // hq
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rms_norm(x, f32(lp["input_norm"]), c["rms_norm_eps"])
    q = (h @ f32(lp["wq"]) + f32(lp["bq"])).reshape(t, hq, dh)
    k = (h @ f32(lp["wk"]) + f32(lp["bk"])).reshape(t, hkv, dh)
    v = (h @ f32(lp["wv"]) + f32(lp["bv"])).reshape(t, hkv, dh)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, hq * dh) @ f32(lp["wo"])
    h = _rms_norm(x, f32(lp["post_attn_norm"]), c["rms_norm_eps"])
    gated = jax.nn.silu(h @ f32(lp["w_gate"])) * (h @ f32(lp["w_up"]))
    return x + gated @ f32(lp["w_down"])


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    hq = c["num_attention_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // hq
    inv = 1.0 / (
        c["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    mask = jnp.tril(jnp.ones((t, t), bool))
    with jax.default_matmul_precision("highest"):
        emb = params["embedding"]
        x = emb[tokens].astype(jnp.float32)
        layer = jax.jit(
            lambda x, lp: _layer(c, x, lp, cos, sin, mask)
        )
        n_layers = c["num_hidden_layers"]
        for i in range(n_layers):
            lp = {k: v[i] for k, v in params["layers"].items()}
            x = layer(x, lp)
        x = _rms_norm(
            x, params["final_norm"].astype(jnp.float32), c["rms_norm_eps"]
        )
        if c.get("tie_word_embeddings", False):
            head = emb.astype(jnp.float32).T
        else:
            head = params["lm_head"].astype(jnp.float32)
        return x @ head


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    lg = logits(c, params, tokens)[:-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    picked = jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0]
    return np.asarray(picked, np.float64)
