"""The plain reference for OLMoE: its forward pass in float32 ``jax.numpy``.

Written from the published architecture (OLMoE-1B-7B, arXiv:2409.02060, and
the model's ``config.json``): pre-norm decoder; RMSNorm; q and k normed over
the WHOLE projection before the split into heads; rotary position embedding
in the "rotate-half" pairing; causal multi-head attention without bias;
a sparse feed-forward of ``num_experts`` SiLU-gated experts of which every
token takes its ``num_experts_per_tok`` most probable, weighted by the
router's softmax probabilities as they are (``norm_topk_prob`` false: not
renormalised), no shared expert, no capacity and no drop; untied output head.
One sequence at a time, no cache, no kernels, no batching, no sorting,
matmuls at ``jax.default_matmul_precision("highest")`` (on a TPU a float32
matmul is otherwise run in bf16 passes). It imports nothing from
``areal_tpu.models`` or ``areal_tpu.ops``.

    h  = rms(x, w_in)
    q  = rms(h Wq, w_qn);  k = rms(h Wk, w_kn);  v = h Wv
    q, k, v -> heads;  q, k rotated;  a = softmax(q k^T / sqrt(dh), causal) v
    x  = x + a Wo
    h2 = rms(x, w_post)
    p  = softmax_E(h2 Wr)                      (float32)
    S  = the k experts of largest p
    x  = x + sum_{e in S} p_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    logits = rms(x_L, w_final) W_head

Departures from the published description, each with its reason:

- every expert is computed for every token and the ones outside S get the
  weight 0, where the description gathers the chosen ones: the same sum, and
  the plainest form there is (no gather, no sort, no group);
- ``clip_qkv`` is null in the published config and is not implemented;
- ``rope_scaling`` is null likewise.

What it takes from the program is the weight tree's layout only: leaves
stacked over layers under ``params["layers"]`` (``wq`` [L, D, H*Dh] applied
as ``x @ w``, ``wk``, ``wv``, ``wo``, ``q_norm`` [L, H*Dh], ``k_norm``
[L, Hkv*Dh], ``input_norm``, ``post_attn_norm``, ``w_router`` [L, D, E],
``w_gate`` and ``w_up`` [L, E, D, F], ``w_down`` [L, E, F, D]),
``params["embedding"]`` [V, D], ``params["final_norm"]`` and
``params["lm_head"]`` [D, V]. Weights are upcast one layer at a time.
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


def _experts(c, h, lp):
    """sum over each token's chosen experts of p_e * expert_e(h): [T, D]."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    p = jax.nn.softmax(h @ f32(lp["w_router"]), axis=-1)  # [T, E]
    chosen = jax.lax.top_k(p, c["num_experts_per_tok"])[1]  # [T, k]
    weight = p * jax.nn.one_hot(chosen, p.shape[-1]).sum(1)  # 0 off S
    if c.get("norm_topk_prob", False):
        weight = weight / weight.sum(-1, keepdims=True)
    gate = jnp.einsum("td,edf->tef", h, f32(lp["w_gate"]))
    up = jnp.einsum("td,edf->tef", h, f32(lp["w_up"]))
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, f32(lp["w_down"]))
    return jnp.einsum("te,ted->td", weight, out)


def _layer(c, x, lp, cos, sin, mask):
    t = x.shape[0]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // hq
    eps = c["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rms_norm(x, f32(lp["input_norm"]), eps)
    q = _rms_norm(h @ f32(lp["wq"]), f32(lp["q_norm"]), eps).reshape(t, hq, dh)
    k = _rms_norm(h @ f32(lp["wk"]), f32(lp["k_norm"]), eps).reshape(t, hkv, dh)
    v = (h @ f32(lp["wv"])).reshape(t, hkv, dh)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, hq * dh) @ f32(lp["wo"])
    h2 = _rms_norm(x, f32(lp["post_attn_norm"]), eps)
    return x + _experts(c, h2, lp)


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
        x = params["embedding"][tokens].astype(jnp.float32)
        layer = jax.jit(lambda x, lp: _layer(c, x, lp, cos, sin, mask))
        for i in range(c["num_hidden_layers"]):
            x = layer(x, {k: v[i] for k, v in params["layers"].items()})
        x = _rms_norm(
            x, params["final_norm"].astype(jnp.float32), c["rms_norm_eps"]
        )
        return x @ params["lm_head"].astype(jnp.float32)


def _picked_logprobs(c, params, tokens, temperature):
    lg = logits(c, params, tokens)[:-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0]


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    return np.asarray(
        _picked_logprobs(c, params, tokens, temperature), np.float64
    )


def summed_logprob_grad(c, params, tokens, temperature=1.0):
    """Gradient of the summed target logprob of one sequence with respect to
    every weight (the loss's gradient, through ``jax.grad`` of the function
    above): the same tree as ``params``."""
    return jax.grad(
        lambda p: _picked_logprobs(c, p, tokens, temperature).sum()
    )(params)
