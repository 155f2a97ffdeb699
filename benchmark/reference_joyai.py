"""The plain reference for JoyAI-LLM-Flash's language model and its draft
module: forward pass, draft loss and (through ``jax.grad``) gradients in
float32 ``jax.numpy``, one chip's share of the experts and the vocabulary.

Written from the model's public ``config.json`` (``model_type``
joyai_llm_flash, the DeepSeek-V3 key set), the DeepSeek-V3 report
(arXiv:2412.19437, sections 2.1-2.2) and the equations of ISSUE 54. ``x`` is
the residual stream of ONE sequence [T, D], token t at position t; ``h =
rms(x, w_in)``; every layer has the same mixer over one of two FFNs:

Mixer (H heads):
    cq = rms(h Wq_a; w_q);  q = cq Wq_b, a head [q_nope | q_rope]
    [c | k_rope] = h Wkv_a;  c = rms(c; w_kv);  [k_nope | v] = c Wkv_b a head
    q_rope a head and the ONE k_rope turn at the token's position: frequencies
    theta^(-2j / rope), j = 0 .. rope/2 - 1, no scaling; the published
    pairing is INTERLEAVED (``rope_interleave``): channels 2j and 2j + 1 turn
    together
    k = [k_nope | k_rope, the same for every head];  causal softmax of
    q.k / sqrt(nope + rope);  y = concat_heads(attn v) Wo
FFN, on ``h2 = rms(x + y, w_post)``: the first ``first_k_dense_replace``
layers a SiLU-gated MLP of ``intermediate_size``; the others
    s = sigmoid(h2 Wr) over all ``n_routed_experts``; the
    ``num_experts_per_tok`` largest of s + e_score_correction_bias (``n_group``
    1: no group limit); weights s of the chosen (without the bias), over
    their sum (``norm_topk_prob``), times ``routed_scaling_factor``; summed
    over the chosen experts HELD HERE (``num_local_experts``: experts
    0 .. held-1); plus the shared expert, ungated.
logits = rms(x_L, w_final) W_head over the ``vocab_size`` rows held.

Draft module (``num_nextn_predict_layers`` 1; the report's section 2.2 at
depth 1), slot i of a sequence of T tokens, ``xn = rms(x_L, w_final)``:
    e_i = Emb(t_{i+1}) for i < T - 1, ZERO at the last slot
    u_i = [rms(e_i; w_e) ; rms(xn_i; w_h)] W_eh
    y = Block(u): one sparse layer of the equations above, its own weights,
    the same positions and causal mask
    draft logits_i = rms(y_i; w_s) W_head, the TRUNK's embedding and head
    L_mtp = mean over the slots i <= T - 3 whose target t_{i+2} the loss mask
    holds of -log softmax(draft logits_i)[t_{i+2}]

One sequence at a time, no cache, no kernels; queries and tokens in blocks of
``BLOCK`` so that thousands of tokens fit a chip; matmuls at
``jax.default_matmul_precision("highest")``. It imports nothing from
``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (the configuration is
one chip's share of an expert-parallel deployment; with ``num_local_experts``
= ``n_routed_experts`` this is the whole layer); every held expert is
computed for every token and weighted by 0 where not chosen, where the
description gathers (the same sum); the module's inputs are joined
[embedding ; hidden], the hidden taken AFTER the final norm, and the trunk
is no function of the module's loss (``jax.lax.stop_gradient`` where the
module reads the trunk's output, embedding and head): the configuration
file's ``assumed`` says why each.

The weight tree's layout is the program's (``params["layers"][kind]`` the
layers of block kind ``"mla+dense"`` / ``"mla+moe"`` stacked in order,
``params["mtp"]`` the module: ``norm_e``, ``norm_h``, ``w_eh``, ``block``
(one stacked layer), ``head_norm``; linear weights applied as ``x @ w``) but
for ONE thing: the program turns the rotated channels rotate-half (channel
j with j + rope/2) and this file as published (2j with 2j + 1), so
``published_layout`` permutes the rope columns of ``wq_b`` and ``w_kv_a``
from the program's order to the published one before this file reads a
tree of the program's: the same function of freshly drawn weights, and the
permutation a checkpoint loader owes in the other direction.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the FFN and the head) at a time
KINDS = ("mla+dense", "mla+moe")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def layer_kinds(c):
    """[(block kind, index in the kind's stack)] of every layer."""
    dense = c.get("first_k_dense_replace", 0)
    return [
        (KINDS[0], i) if i < dense else (KINDS[1], i - dense)
        for i in range(c["num_hidden_layers"])
    ]


# ------------------------------------------------------------ the layout --
def _interleave(rope):
    """Where the published order reads the program's rope channels from:
    published channel 2j is the program's j, 2j + 1 its j + rope / 2."""
    half = rope // 2
    return np.stack([np.arange(half), np.arange(half) + half], 1).reshape(-1)


def _published_mixer(c, lp):
    """One (stacked or single) ``mla`` layer's leaves with the rope columns
    of ``wq_b`` (a head's last ``qk_rope_head_dim``) and ``w_kv_a`` (the
    last ``qk_rope_head_dim`` of all) in the published, interleaved order."""
    hq, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    rope, rank = c["qk_rope_head_dim"], c["kv_lora_rank"]
    at = _interleave(rope)
    wq, kv = jnp.asarray(lp["wq_b"]), jnp.asarray(lp["w_kv_a"])
    heads = wq.reshape(wq.shape[:-1] + (hq, nope + rope))
    heads = jnp.concatenate(
        [heads[..., :nope], heads[..., nope:][..., at]], axis=-1
    )
    kv = jnp.concatenate([kv[..., :rank], kv[..., rank:][..., at]], axis=-1)
    return dict(lp, wq_b=heads.reshape(wq.shape), w_kv_a=kv)


def published_layout(c, params):
    """A weight tree of the PROGRAM's as this file reads one: every mixer's
    rope columns in the published order, the module's block's too (a
    gather: a gradient taken through it comes back in the program's order).
    Everything else is the tree as it is."""
    out = dict(params, layers={
        kind: _published_mixer(c, lp)
        for kind, lp in params["layers"].items()
    })
    if "mtp" in params:
        out["mtp"] = dict(
            params["mtp"], block=_published_mixer(c, params["mtp"]["block"])
        )
    return out


# ----------------------------------------------------------------- mixer --
def rotate_interleaved(x, theta):
    """``x`` [T, ..., R] turned at positions 0 .. T-1, the published pairing:
    channels 2j and 2j + 1 by the angle t * theta^(-2j / R)."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32
    )
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape
    )


def _mixer(c, h, lp):
    t, hq = h.shape[0], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, rank, eps = c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"]
    theta = float(c["rope_theta"])
    cq = _rms_norm(h @ _f32(lp["wq_a"]), _f32(lp["q_a_norm"]), eps)
    q = (cq @ _f32(lp["wq_b"])).reshape(t, hq, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotate_interleaved(q[..., nope:], theta)], axis=-1
    )
    ckr = h @ _f32(lp["w_kv_a"])
    lat = _rms_norm(ckr[:, :rank], _f32(lp["kv_norm"]), eps)
    kv = (lat @ _f32(lp["w_kv_b"])).reshape(t, hq, nope + dv)
    k_rope = rotate_interleaved(ckr[:, rank:], theta)
    k = jnp.concatenate([
        kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (t, hq, rope)),
    ], axis=-1)
    v = kv[..., nope:]
    idx = jnp.arange(t)
    out = []
    for a, b in _blocks(t):
        scores = jnp.einsum("qhd,khd->hqk", q[a:b], k) / jnp.sqrt(
            jnp.float32(nope + rope)
        )
        scores = jnp.where(
            (idx[None, :] <= idx[a:b, None])[None], scores, -jnp.inf
        )
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(b - a, hq * dv))
    return jnp.concatenate(out, axis=0) @ _f32(lp["wo"])


# ------------------------------------------------------------------- FFN --
def routing(c, h, lp):
    """[T, E] float32: a token's weight on each of ALL experts (0 where
    not chosen)."""
    s = jax.nn.sigmoid(h @ _f32(lp["w_router"]))
    chosen = jax.lax.top_k(
        s + _f32(lp["router_bias"]), c["num_experts_per_tok"]
    )[1]
    weight = s * jax.nn.one_hot(chosen, s.shape[-1]).sum(1)
    if c.get("norm_topk_prob", True):
        weight = weight / weight.sum(-1, keepdims=True)
    return weight * c.get("routed_scaling_factor", 1.0)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts(c, h, lp, first_expert=0):
    """The part of the routed sum that the experts held in ``lp`` give
    (experts ``first_expert`` and following): [T, D]."""
    held = lp["w_gate"].shape[0]
    weight = routing(c, h, lp)[:, first_expert:first_expert + held]
    out = []
    for a, b in _blocks(h.shape[0]):
        gate = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_gate"]))
        up = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_up"]))
        y = jnp.einsum(
            "tef,efd->ted", jax.nn.silu(gate) * up, _f32(lp["w_down"])
        )
        out.append(jnp.einsum("te,ted->td", weight[a:b], y))
    return jnp.concatenate(out, axis=0)


def shared_expert(h, lp):
    return _swiglu(
        h, _f32(lp["w_shared_gate"]), _f32(lp["w_shared_up"]),
        _f32(lp["w_shared_down"]),
    )


def _ffn(c, kind, h, lp):
    if kind.endswith("+dense"):
        return _swiglu(
            h, _f32(lp["w_gate"]), _f32(lp["w_up"]), _f32(lp["w_down"])
        )
    out = experts(c, h, lp)
    if c.get("n_shared_experts", 0):
        out = out + shared_expert(h, lp)
    return out


# ----------------------------------------------------------------- model --
def layer(c, kind, x, lp):
    """x after the layer of block ``kind``."""
    eps = c["rms_norm_eps"]
    x = x + _mixer(c, _rms_norm(x, _f32(lp["input_norm"]), eps), lp)
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    return x + _ffn(c, kind, h2, lp)


def head(params, xn):
    """[T, V] float32 of final-normed rows ``xn``, in blocks."""
    out = [
        xn[a:b] @ _f32(params["lm_head"]) for a, b in _blocks(xn.shape[0])
    ]
    return jnp.concatenate(out, axis=0)


def trunk(c, params, tokens):
    """The stack's output after the final norm, [T, D]: the traceable core
    of ``hidden`` (``tokens`` may be traced)."""
    x = _f32(params["embedding"])[tokens]
    for kind, i in layer_kinds(c):
        lp = {k: v[i] for k, v in params["layers"][kind].items()}
        x = layer(c, kind, x, lp)
    return _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])


def draft(c, params, tokens, xn):
    """The draft module's logits [T, V] of one sequence, from the trunk's
    normed output ``xn`` [T, D]; slot i speaks of token i + 2. The trunk's
    output, embedding and head are constants to the module's loss."""
    mp, eps = params["mtp"], c["rms_norm_eps"]
    frozen = jax.lax.stop_gradient
    tokens = jnp.asarray(tokens, jnp.int32)
    e = frozen(_f32(params["embedding"]))[jnp.roll(tokens, -1)]
    e = e.at[-1].set(0.0)  # the last slot has no next token
    u = jnp.concatenate([
        _rms_norm(e, _f32(mp["norm_e"]), eps),
        _rms_norm(frozen(xn), _f32(mp["norm_h"]), eps),
    ], axis=-1) @ _f32(mp["w_eh"])
    lp = {k: v[0] for k, v in mp["block"].items()}
    y = _rms_norm(layer(c, KINDS[1], u, lp), _f32(mp["head_norm"]), eps)
    return head({"lm_head": frozen(_f32(params["lm_head"]))}, y)


def _target_logprobs(lg, targets, temperature=1.0):
    lp = jax.nn.log_softmax(lg / temperature, axis=-1)
    return jnp.take_along_axis(lp, targets[:, None], axis=-1)[:, 0]


def draft_loss(c, params, tokens, xn, mask=None):
    """L_mtp of one sequence: the mean of -log p_draft(t_{i+2}) over the
    slots i <= T - 3 (``mask`` [T - 2] bool: only where it holds)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logp = _target_logprobs(draft(c, params, tokens, xn)[:-2], tokens[2:])
    if mask is None:
        return -logp.mean()
    return -jnp.where(mask, logp, 0.0).sum() / jnp.maximum(mask.sum(), 1)


# ------------------------------------- eager entries, a program a layer --
def hidden(c, params, tokens):
    """``trunk`` of concrete token ids, one compiled program a layer (what
    fits a chip at thousands of tokens): float32 [T, D]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][np.asarray(tokens)])
        for kind, i in layer_kinds(c):
            lp = {k: v[i] for k, v in params["layers"][kind].items()}
            x = jax.jit(lambda x, lp, kind=kind: layer(c, kind, x, lp))(x, lp)
        return _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    with jax.default_matmul_precision("highest"):
        return head(params, hidden(c, params, tokens))


def token_logprobs(c, params, tokens, temperature=1.0, xn=None):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1];
    ``xn``: the trunk's normed output where the caller has it (``hidden``)."""
    with jax.default_matmul_precision("highest"):
        if xn is None:
            xn = hidden(c, params, tokens)
        lg = head(params, jnp.asarray(xn))[:-1]
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return np.asarray(_target_logprobs(lg, tgt, temperature), np.float64)


def draft_token_logprobs(c, params, tokens, xn=None):
    """log p_draft(tokens[i + 2] | tokens[:i + 2]) for i <= T - 3, float64
    numpy [T - 2]; ``xn``: the trunk's normed output where the caller has
    it (``hidden``), else it is computed."""
    with jax.default_matmul_precision("highest"):
        if xn is None:
            xn = hidden(c, params, tokens)
        lg = jax.jit(lambda p, tok, xn: draft(c, p, tok, xn))(
            {k: params[k] for k in ("embedding", "lm_head", "mtp")},
            jnp.asarray(tokens, jnp.int32), jnp.asarray(xn),
        )
        tgt = jnp.asarray(tokens[2:], jnp.int32)
        return np.asarray(_target_logprobs(lg[:-2], tgt), np.float64)
