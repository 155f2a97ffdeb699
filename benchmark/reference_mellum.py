"""The plain reference for Mellum2's language model (``model_type`` mellum):
its forward pass in float32 ``jax.numpy``, one chip's share of the experts
and the vocabulary; differentiable as it stands, which the CPU tests use for
the gradients.

Written from the model's public ``config.json`` and the equations of
ISSUE 45; no modelling code of the family is on this machine, so what no key
states is the configuration file's ``assumed``. ``x`` is the residual stream
of ONE sequence [T, D], ``h = rms(x, w_in)``; d = ``head_dim``, H =
``num_attention_heads``, Hkv = ``num_key_value_heads``:

Mixer (``layer_types``: ``full_attention`` or ``sliding_attention``):
    q = h Wq in [T, H, d];  k = h Wk, v = h Wv in [T, Hkv, d]; no bias.
    q and k each normed A HEAD: q_i <- rms(q_i, w_q), k_i <- rms(k_i, w_k),
    w_q and w_k in [d], ``rms_norm_eps`` (assumed: qwen3-moe's block).
    Rotation of the WHOLE head, rotate-half pairing (channel j with
    j + d / 2), by the kind's entry of ``rope_parameters``, positions a
    token's index in the sequence, f_j = theta^(-2j / d):
      sliding: inv_freq_j = f_j
      full: yarn: lo, hi = floor and ceiling of
        d ln(original_max / (2 pi b)) / (2 ln theta) at b = beta_fast and
        beta_slow, clipped to [0, d - 1]; g_j = clip((j - lo) / (hi - lo),
        0, 1); inv_freq_j = f_j (1 - g_j) + (f_j / factor) g_j;
        cos and sin both times attention_factor
    scores q_t . k_s / sqrt(d) (query head i reads key head i // (H / Hkv)),
    softmax in float32 over the keys s with 0 <= t - s (full) or
    0 <= t - s < sliding_window (sliding: the query's own key counts)
    y = concat_heads(attn_i) Wo
FFN, on ``h2 = rms(x + y, w_post)`` (``mlp_layer_types``): ``dense`` a
SiLU-gated MLP of ``intermediate_size``; ``sparse``
    p = softmax(h2 Wr) over all ``num_experts`` in float32; the
    ``num_experts_per_tok`` largest (ties to the lower index); weights p of
    the chosen over their sum (``norm_topk_prob``), on the experts' OUTPUT;
    summed over the chosen experts HELD HERE (``num_local_experts``: experts
    0 .. held-1). No shared expert.
logits = rms(x_L, w_final) W_head over the ``vocab_size`` rows held.

One sequence at a time, no cache, no kernels, no packing; queries (and the
tokens of the FFN and the head) in blocks of ``BLOCK`` so that a sequence of
20,480 tokens fits a chip (a block's scores are [32 heads, 512, 20,480]
float32, 1.3 GB); matmuls at ``jax.default_matmul_precision("highest")``.
It imports nothing from ``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (the configuration
is one chip's share of an expert-parallel deployment; with
``num_local_experts`` = ``num_experts`` this is the whole layer); every held
expert is computed for every token and weighted by 0 where not chosen, where
the description gathers (the same sum); a window layer's scores are computed
against every key and masked, where an implementation would read the band
only (the same softmax); the multi-token-prediction module that the family's
description mentions has no key in the configuration and is not built.

The weight tree's layout is the program's: ``params["layers"][kind]`` holds
the layers of block kind ``"<mixer>+<ffn>"`` (mixers ``full`` and ``swa``,
FFNs ``dense`` and ``moe``) stacked in order, linear weights applied as
``x @ w``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the FFN and the head) at a time

_MIXERS = {"full_attention": "full", "sliding_attention": "swa"}
_FFNS = {"dense": "dense", "sparse": "moe"}


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def layer_kinds(c):
    """[(block kind, index in the kind's stack)] a layer."""
    seen, out = {}, []
    for mixer, ffn in zip(c["layer_types"], c["mlp_layer_types"]):
        kind = f"{_MIXERS[mixer]}+{_FFNS[ffn]}"
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    assert len(out) == c["num_hidden_layers"]
    return out


# -------------------------------------------------------------- rotation --
def rotation(c, kind):
    """(inv_freq [d / 2] float32, the factor on cos and sin) of a layer of
    block ``kind``: the whole head is rotated."""
    full = kind.startswith("full")
    rope = c["rope_parameters"]["full_attention" if full else "sliding_attention"]
    d = c["head_dim"]
    theta = float(rope["rope_theta"])
    j = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / d)
    if rope.get("rope_type", "default") != "yarn":
        return f.astype(np.float32), 1.0
    bound = lambda b: d * math.log(  # noqa: E731
        rope["original_max_position_embeddings"] / (2 * math.pi * b)
    ) / (2 * math.log(theta))
    lo = max(math.floor(bound(rope["beta_fast"])), 0)
    hi = min(math.ceil(bound(rope["beta_slow"])), d - 1)
    g = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    inv = f * (1 - g) + f / rope["factor"] * g
    return inv.astype(np.float32), float(rope["attention_factor"])


def rotate(x, inv_freq, scale):
    """[T, H, d] rotated by the token's index, pairing channel j with
    j + d / 2."""
    t, n = x.shape[0], inv_freq.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    a, b = x[..., :n], x[..., n:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------- attention --
def masked_softmax_attention(q, k, v, window=0):
    """[T, H, d] out of q [T, H, d], k and v [T, Hkv, d]: softmax over the
    keys s with 0 <= t - s (< ``window`` where it is not 0), query head i
    on key head i // (H / Hkv); in blocks of queries."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    blk = min(BLOCK, t)
    n_blk = -(-t // blk)
    pad = n_blk * blk - t  # queries past the end: computed, then dropped
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blk, blk, k.shape[1], rep, d
    )
    rows = jnp.arange(n_blk * blk).reshape(n_blk, blk)
    keys = jnp.arange(t)

    def block(args):  # one block of queries at a time, in turn
        q_blk, row = args
        back = row[:, None] - keys[None, :]  # t - s
        ok = back >= 0
        if window:
            ok = ok & (back < window)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d)
        )
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(blk, h, d)

    return jax.lax.map(block, (qg, rows)).reshape(n_blk * blk, h, d)[:t]


def qkv(c, kind, h, lp):
    """The normed and rotated q [T, H, d], k and the plain v [T, Hkv, d] of
    one layer on its normed input ``h``."""
    t, d = h.shape[0], c["head_dim"]
    heads, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    eps = c["rms_norm_eps"]
    inv_freq, scale = rotation(c, kind)
    q = _rms_norm((h @ _f32(lp["wq"])).reshape(t, heads, d),
                  _f32(lp["q_norm"]), eps)
    k = _rms_norm((h @ _f32(lp["wk"])).reshape(t, hkv, d),
                  _f32(lp["k_norm"]), eps)
    return (
        rotate(q, inv_freq, scale), rotate(k, inv_freq, scale),
        (h @ _f32(lp["wv"])).reshape(t, hkv, d),
    )


def _mixer(c, kind, h, lp):
    q, k, v = qkv(c, kind, h, lp)
    window = c["sliding_window"] if kind.startswith("swa") else 0
    attn = masked_softmax_attention(q, k, v, window)
    return attn.reshape(h.shape[0], -1) @ _f32(lp["wo"])


# ------------------------------------------------------------------- FFN --
def routing(c, h, lp):
    """[T, E] float32: a token's weight on each of ALL experts (0 where
    not chosen)."""
    p = jax.nn.softmax(h @ _f32(lp["w_router"]), axis=-1)
    chosen = jax.lax.top_k(p, c["num_experts_per_tok"])[1]
    weight = p * jax.nn.one_hot(chosen, p.shape[-1]).sum(1)
    if c.get("norm_topk_prob", True):
        weight = weight / weight.sum(-1, keepdims=True)
    return weight


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


def _ffn(c, kind, h, lp):
    if kind.endswith("+dense"):
        return _swiglu(
            h, _f32(lp["w_gate"]), _f32(lp["w_up"]), _f32(lp["w_down"])
        )
    return experts(c, h, lp)


# ----------------------------------------------------------------- model --
def layer(c, kind, x, lp):
    """(x after the layer of block ``kind``, the layer's normed input)."""
    eps = c["rms_norm_eps"]
    h = _rms_norm(x, _f32(lp["input_norm"]), eps)
    x = x + _mixer(c, kind, h, lp)
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    return x + _ffn(c, kind, h2, lp), h


def _run(c, params, tokens, keep_window_inputs=False):
    """(logits [T, V], [(q, k, v) of every window layer, float32 numpy] where
    asked). One jitted function a block kind, so that the layers of one kind
    compile once a sequence length."""
    kept, fns = [], {}

    def layer_fn(kind):
        keep = keep_window_inputs and kind.startswith("swa")

        def fn(x, lp):
            out, h = layer(c, kind, x, lp)
            return out, (qkv(c, kind, h, lp) if keep else None)

        return jax.jit(fn)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"])[jnp.asarray(tokens, jnp.int32)]
        for kind, i in layer_kinds(c):
            lp = {k: v[i] for k, v in params["layers"][kind].items()}
            if kind not in fns:
                fns[kind] = layer_fn(kind)
            x, inputs = fns[kind](x, lp)
            if inputs is not None:
                kept.append(tuple(np.asarray(a) for a in inputs))
        x = _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
        out = [x[a:b] @ _f32(params["lm_head"]) for a, b in _blocks(len(tokens))]
        return jnp.concatenate(out, axis=0), kept


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    return _run(c, params, tokens)[0]


def logprobs_of(lg, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1 out of logits [T, V]: a
    float32 jax array [T - 1] (what a test differentiates)."""
    lp = jax.nn.log_softmax(lg[:-1] / temperature, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0]


def token_logprobs(c, params, tokens, temperature=1.0):
    """``logprobs_of`` the sequence's own logits, float64 numpy [T - 1]."""
    return np.asarray(
        logprobs_of(logits(c, params, tokens), tokens, temperature),
        np.float64,
    )


def logprobs_and_window_inputs(c, params, tokens, temperature=1.0):
    """``token_logprobs`` and, from the same pass, for each window layer the
    normed and rotated q [T, H, d], k and v [T, Hkv, d] as the reference has
    them, float32 numpy: what ``window_exact`` feeds, rounded to the compute
    dtype, to the program's timed core and to ``masked_softmax_attention``."""
    lg, kept = _run(c, params, tokens, keep_window_inputs=True)
    return np.asarray(logprobs_of(lg, tokens, temperature), np.float64), kept
