"""The plain reference for Keye-VL-2.0's language model: its forward pass in
float32 ``jax.numpy``, one chip's share of the experts and the vocabulary.

Written from the model's public ``config.json`` (``model_type`` KeyeVL2: a
qwen3-moe-shaped block whose attention reads only the ``sa_config.topk`` keys
that a DeepSeek-Sparse-Attention indexer picks) and the six steps of
ISSUE 31. Token t of ONE sequence, s <= t, h_t = rms(x_t, w_in):

    1. q, k, v = h Wq, h Wk, h Wv in heads of ``head_dim``; q and k normed
       PER HEAD (RMSNorm, weight [head_dim]); q, k rotated (rotate-half)
    2. qI[t, j] = (h_t W_qI)_j in R^Di, j < Hi;  kI[s] = h_s W_kI in R^Di;
       w[t] = h_t W_wI in R^Hi;
       I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])            (float32)
    3. S_t = the ``topk`` keys of largest I[t, .] (``jax.lax.top_k``: of equal
       scores the lower index first); every s <= t while t < topk
    4. o[t, i] = sum_{s in S_t} softmax_{S_t}(q[t, i] . k[s, g(i)] / sqrt(dh))
       v[s, g(i)];  x = x + o Wo
    5. h2 = rms(x, w_post);  p = softmax over all ``num_experts`` router
       outputs; the ``num_experts_per_tok`` largest, renormalised to sum 1;
       x = x + sum over the chosen experts HELD HERE (``num_local_experts``:
       experts 0 .. held-1) of p_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    6. logits = rms(x_L, w_final) W_head over the ``vocab_size`` rows held

One sequence at a time, no cache, no kernels, no sorting; queries in blocks of
``BLOCK`` so that 8,192 tokens fit a chip beside the weights; matmuls at
``jax.default_matmul_precision("highest")``. It imports nothing from
``areal_tpu``.

Departures from the published description, each with its reason:

- what the 112 absent experts would add is LEFT OUT, as in the program: the
  configuration is one chip's share of an expert-parallel deployment, and the
  partial result goes on (model-configs guide, section 4). With
  ``num_local_experts`` = ``num_experts`` this is the whole layer;
- positions are one coordinate and the rotation plain rope: a text token's
  three mrope coordinates are all its position, and ``mrope_section`` then
  only says which coordinate each channel reads, so the result is the same;
- every held expert is computed for every token and weighted by 0 where not
  chosen, where the description gathers: the same sum;
- what the config leaves open is the configuration file's ``assumed``: no norm
  and no rotation on the indexer's query and key, no scale on I,
  ``q_chunk_size`` / ``kv_chunk_size`` a tiling, the indexer reads the layer's
  normed input, no vision tower.

The weight tree's layout is the program's (leaves stacked over layers under
``params["layers"]``): ``wq`` [L, D, H*Dh] applied as ``x @ w``, ``wk``,
``wv``, ``wo``, ``q_norm`` / ``k_norm`` [L, Dh], ``input_norm``,
``post_attn_norm``, ``w_idx_q`` [L, D, Hi*Di], ``w_idx_k`` [L, D, Di],
``w_idx_w`` [L, D, Hi], ``w_router`` [L, D, E], ``w_gate`` / ``w_up``
[L, held, D, F], ``w_down`` [L, held, F, D]; ``embedding`` [V, D],
``final_norm``, ``lm_head`` [D, V]. Weights are upcast a layer at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the expert layer) at a time


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, cos, sin):
    # x [T, H, Dh]; cos/sin [T, Dh/2]; pairs are (i, i + Dh/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def _selection(c, h, lp):
    """Steps 2 and 3: the mask [T, T] of (query, selected key)."""
    sa = c["sa_config"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    t = h.shape[0]
    qi = (h @ _f32(lp["w_idx_q"])).reshape(t, hi, di)
    ki = h @ _f32(lp["w_idx_k"])
    w = h @ _f32(lp["w_idx_w"])
    idx = jnp.arange(t)
    rows = []
    for a, b in _blocks(t):
        causal = idx[None, :] <= idx[a:b, None]
        if t <= topk:
            rows.append(causal)
            continue
        s = jnp.einsum("qjd,kd->jqk", qi[a:b], ki)
        scores = jnp.einsum("jqk,qj->qk", jax.nn.relu(s), w[a:b])
        scores = jnp.where(causal, scores, -jnp.inf)
        chosen = jax.lax.top_k(scores, topk)[1]  # [C, topk]
        picked = jnp.zeros((b - a, t), bool).at[
            jnp.arange(b - a)[:, None], chosen
        ].set(True)
        rows.append(picked & causal)  # a query with fewer keys has them all
    return jnp.concatenate(rows, axis=0)


def _attention(c, q, k, v, mask):
    """Step 4, queries in blocks: [T, Hq * Dh]."""
    t, hq, dh = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = []
    for a, b in _blocks(t):
        scores = jnp.einsum("qhd,khd->hqk", q[a:b], k) / jnp.sqrt(
            jnp.float32(dh)
        )
        scores = jnp.where(mask[a:b][None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(b - a, hq * dh))
    return jnp.concatenate(out, axis=0)


def _experts(c, h, lp):
    """Step 5's sum over the chosen experts held here: [T, D]."""
    held = lp["w_gate"].shape[0]
    p = jax.nn.softmax(h @ _f32(lp["w_router"]), axis=-1)  # [T, E] all
    chosen = jax.lax.top_k(p, c["num_experts_per_tok"])[1]
    weight = p * jax.nn.one_hot(chosen, p.shape[-1]).sum(1)  # 0 off S
    if c.get("norm_topk_prob", False):
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight[:, :held]  # the others' part is left out
    out = []
    for a, b in _blocks(h.shape[0]):
        gate = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_gate"]))
        up = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_up"]))
        y = jnp.einsum(
            "tef,efd->ted", jax.nn.silu(gate) * up, _f32(lp["w_down"])
        )
        out.append(jnp.einsum("te,ted->td", weight[a:b], y))
    return jnp.concatenate(out, axis=0)


def _layer(c, x, lp, cos, sin):
    """(x after the layer, the layer's normed input, its selection)."""
    t = x.shape[0]
    hq, hkv, dh = (
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    )
    eps = c["rms_norm_eps"]
    h = _rms_norm(x, _f32(lp["input_norm"]), eps)
    q = _rms_norm(
        (h @ _f32(lp["wq"])).reshape(t, hq, dh), _f32(lp["q_norm"]), eps
    )
    k = _rms_norm(
        (h @ _f32(lp["wk"])).reshape(t, hkv, dh), _f32(lp["k_norm"]), eps
    )
    v = (h @ _f32(lp["wv"])).reshape(t, hkv, dh)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    mask = _selection(c, h, lp)
    x = x + _attention(c, q, k, v, mask) @ _f32(lp["wo"])
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    return x + _experts(c, h2, lp), h, mask


def _run(c, params, tokens, keep_selections=False):
    tokens = jnp.asarray(tokens, jnp.int32)
    t, dh = tokens.shape[0], c["head_dim"]
    inv = 1.0 / (
        c["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][np.asarray(tokens)])
        layer = jax.jit(lambda x, lp: _layer(c, x, lp, cos, sin))
        for i in range(c["num_hidden_layers"]):
            x, h, mask = layer(
                x, {k: v[i] for k, v in params["layers"].items()}
            )
            if keep_selections:
                kept.append((np.asarray(h), np.asarray(mask)))
        x = _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
        out = []
        for a, b in _blocks(t):
            out.append(x[a:b] @ _f32(params["lm_head"]))
        return jnp.concatenate(out, axis=0), kept


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    return _run(c, params, tokens)[0]


def selections(c, params, tokens):
    """Per layer (the layer's normed input [T, D] float32, the selection
    [T, T] bool) of one sequence: what ``selection_exact`` holds the
    program's selection against, given the same input."""
    return _run(c, params, tokens, keep_selections=True)[1]


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    lg = logits(c, params, tokens)[:-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return np.asarray(
        jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0], np.float64
    )
