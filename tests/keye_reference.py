"""The tests' twin of ``benchmark/reference_keye.py``: the same six steps
(ISSUE 31) written a second time, whole arrays and no blocks, with the loss
and its gradient. Float32 ``jax.numpy``; imports nothing from ``areal_tpu``
or ``benchmark``. ``c`` is the configuration file's keys; ``params`` the
program's weight tree (layout in the benchmark's copy).

Departures from the published description: the benchmark copy's (the absent
experts' part is left out; plain rope for text positions; every held expert
computed and weighted by 0 where not chosen; the ``assumed`` list). The
selection S_t is chosen by index (``jax.lax.top_k``), so ``jax.grad`` gives
the indexer's three weights exactly zero.
"""

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def selection(c, h, lp):
    """[T, T] bool: S_t as a row of the mask."""
    sa = c["sa_config"]
    t = h.shape[0]
    qi = (h @ lp["w_idx_q"]).reshape(
        t, sa["indexer_num_heads"], sa["indexer_head_dim"]
    )
    ki, w = h @ lp["w_idx_k"], h @ lp["w_idx_w"]
    scores = jnp.einsum(
        "qj,jqk->qk", w, jax.nn.relu(jnp.einsum("qjd,kd->jqk", qi, ki))
    )
    causal = jnp.tril(jnp.ones((t, t), bool))
    if t <= sa["topk"]:
        return causal
    chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), sa["topk"])[1]
    picked = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], chosen].set(True)
    return picked & causal


def experts(c, h, lp, first=0):
    """The part of the sparse layer's output that experts ``first`` ..
    ``first + held - 1`` give (held = the experts ``lp`` has)."""
    held = lp["w_gate"].shape[0]
    p = jax.nn.softmax(h @ lp["w_router"], axis=-1)
    chosen = jax.lax.top_k(p, c["num_experts_per_tok"])[1]
    weight = p * jax.nn.one_hot(chosen, p.shape[-1]).sum(1)
    if c.get("norm_topk_prob", False):
        weight = weight / weight.sum(-1, keepdims=True)
    y = jnp.einsum(
        "tef,efd->ted",
        jax.nn.silu(jnp.einsum("td,edf->tef", h, lp["w_gate"]))
        * jnp.einsum("td,edf->tef", h, lp["w_up"]),
        lp["w_down"],
    )
    return jnp.einsum("te,ted->td", weight[:, first:first + held], y)


def logits(c, params, tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    t, dh = tokens.shape[0], c["head_dim"]
    hq, hkv, eps = (
        c["num_attention_heads"], c["num_key_value_heads"], c["rms_norm_eps"]
    )
    inv = 1.0 / c["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][tokens].astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            lp = {k: v[i].astype(jnp.float32) for k, v in params["layers"].items()}
            h = _rms(x, lp["input_norm"], eps)
            q = _rope(_rms((h @ lp["wq"]).reshape(t, hq, dh), lp["q_norm"], eps), cos, sin)
            k = _rope(_rms((h @ lp["wk"]).reshape(t, hkv, dh), lp["k_norm"], eps), cos, sin)
            v = (h @ lp["wv"]).reshape(t, hkv, dh)
            k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
            mask = selection(c, jax.lax.stop_gradient(h), lp)
            s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
            a = jnp.einsum(
                "hqk,khd->qhd",
                jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1), v,
            )
            x = x + a.reshape(t, hq * dh) @ lp["wo"]
            x = x + experts(c, _rms(x, lp["post_attn_norm"], eps), lp)
        x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
        return x @ params["lm_head"].astype(jnp.float32)


def summed_logprob(c, params, tokens, temperature=1.0):
    """The loss of the comparison: the summed log p(tokens[i] | tokens[:i])."""
    lp = jax.nn.log_softmax(logits(c, params, tokens)[:-1] / temperature, -1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return jnp.take_along_axis(lp, tgt[:, None], axis=-1).sum()


def summed_logprob_grad(c, params, tokens, temperature=1.0):
    return jax.grad(lambda p: summed_logprob(c, p, tokens, temperature))(params)
