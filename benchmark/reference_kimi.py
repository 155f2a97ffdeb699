"""The plain reference for Kimi-Linear's language model: its forward pass in
float32 ``jax.numpy``, one chip's share of the experts and the vocabulary.

Written from the model's public ``config.json`` (``model_type`` kimi_linear),
the Kimi Linear report (arXiv:2510.26692) and the equations of ISSUE 33. A
layer is one of two mixers over one of two FFNs; ``x`` the residual stream
of ONE sequence [T, D], ``h = rms(x, w_in)``:

KDA mixer (``linear_attn_config.kda_layers``, 1-indexed), H heads of dk = dv:
    q = l2norm(silu(conv(h Wq))), k = l2norm(silu(conv(h Wk))),
    v = silu(conv(h Wv));  conv: depthwise, causal, ``short_conv_kernel_size``
    taps, zeros before the sequence's first token, no bias
    g_t = -exp(A_log[head]) * softplus(Wf_b (Wf_a h_t) + dt_bias)   in [H, dk]
    beta_t = sigmoid(h_t Wb)                                        in [H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
    S_0 = 0;  o_t = S_t^T q_t / sqrt(dk)           TOKEN BY TOKEN, no chunks
    y = (rms_head(o_t; w_o) * sigmoid(Wg_b (Wg_a h_t))) Wo
MLA mixer (``full_attn_layers``), no positions (``mla_use_nope``):
    q = h Wq, a head (nope, rope) wide;  [c, kr] = h Wkv_a;  c = rms(c; w_kv)
    [k_nope, v] = c Wkv_b a head;  k = [k_nope, kr];  causal softmax of
    q.k / sqrt(nope + rope);  y = concat_heads(attn v) Wo
FFN, on ``h2 = rms(x + y, w_post)``: the first ``first_k_dense_replace``
layers a SiLU-gated MLP; the others
    s = sigmoid(h2 Wr) over all ``num_experts``; the ``num_experts_per_token``
    largest of s + e_score_correction_bias; weights s of the chosen (without
    the bias), over their sum (``moe_renormalize``), times
    ``routed_scaling_factor``; summed over the chosen experts HELD HERE
    (``num_local_experts``: experts 0 .. held-1); plus the shared expert,
    ungated.
logits = rms(x_L, w_final) W_head over the ``vocab_size`` rows held.

One sequence at a time, no cache, no kernels, no chunks of the rule; queries
and tokens in blocks of ``BLOCK`` so that thousands of tokens fit a chip;
matmuls at ``jax.default_matmul_precision("highest")``. It imports nothing
from ``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (the configuration
is one chip's share of an expert-parallel deployment; with
``num_local_experts`` = ``num_experts`` this is the whole layer); every held
expert is computed for every token and weighted by 0 where not chosen, where
the description gathers (the same sum); what the config leaves open is the
configuration file's ``assumed`` (no convolution bias, SiLU after it, the
l2norm's and the norms' epsilons, the gates' activations, the attention's
scale, the initial ``A_log`` / ``dt_bias``).

The weight tree's layout is the program's: ``params["layers"][kind]`` holds
the layers of block kind ``"<mixer>+<ffn>"`` stacked in order, linear weights
applied as ``x @ w``, a convolution's [channels, taps] with the LAST tap the
token itself.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the FFN and the head) at a time


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def layer_kinds(c):
    """[(block kind, index in the kind's stack)] of every layer."""
    la = c["linear_attn_config"]
    seen, out = {}, []
    for i in range(1, c["num_hidden_layers"] + 1):
        kind = ("kda" if i in la["kda_layers"] else "mla") + (
            "+dense" if i <= c.get("first_k_dense_replace", 0) else "+moe"
        )
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


# ------------------------------------------------------------------- KDA --
def _conv(x, w):
    """[T, C] through a depthwise causal convolution, w [C, K]."""
    taps = w.shape[-1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(
        padded[i:i + x.shape[0]] * w[:, i] for i in range(taps)
    )


def kda_inputs(c, h, lp):
    """q, k [T, H, dk], v [T, H, dv], g [T, H, dk], beta [T, H] of one
    KDA layer on its normed input ``h``."""
    la = c["linear_attn_config"]
    hh, dh = la["num_heads"], la["head_dim"]
    t = h.shape[0]

    def branch(n):
        y = jax.nn.silu(_conv(h @ _f32(lp[f"w{n}"]), _f32(lp[f"conv_{n}"])))
        return y.reshape(t, hh, dh)

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    f = (h @ _f32(lp["w_f_a"])) @ _f32(lp["w_f_b"]) + _f32(lp["dt_bias"])
    g = -jnp.exp(_f32(lp["a_log"]))[:, None] * jax.nn.softplus(f).reshape(
        t, hh, dh
    )
    beta = jax.nn.sigmoid(h @ _f32(lp["w_beta"]))
    return l2norm(branch("q")), l2norm(branch("k")), branch("v"), g, beta


def kda_rule(q, k, v, g, beta):
    """The gated delta rule token by token: o [T, H, dv]."""
    hh, dk = q.shape[1:]
    scale = dk ** -0.5

    def step(s, x):  # s [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        read = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - read)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t) * scale

    s0 = jnp.zeros((hh, dk, v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def _kda_mixer(c, h, lp):
    q, k, v, g, beta = kda_inputs(c, h, lp)
    o = kda_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ _f32(lp["w_g_a"])) @ _f32(lp["w_g_b"]))
    y = _rms_norm(o, _f32(lp["o_norm"]), c["rms_norm_eps"])
    return (y.reshape(h.shape[0], -1) * gate) @ _f32(lp["wo"])


# ------------------------------------------------------------------- MLA --
def _mla_mixer(c, h, lp):
    t, hq = h.shape[0], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, rank = c["v_head_dim"], c["kv_lora_rank"]
    q = (h @ _f32(lp["wq"])).reshape(t, hq, nope + rope)
    ckr = h @ _f32(lp["w_kv_a"])
    lat = _rms_norm(ckr[:, :rank], _f32(lp["kv_norm"]), c["rms_norm_eps"])
    kv = (lat @ _f32(lp["w_kv_b"])).reshape(t, hq, nope + dv)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(ckr[:, None, rank:], (t, hq, rope)),
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
        s + _f32(lp["router_bias"]), c["num_experts_per_token"]
    )[1]
    weight = s * jax.nn.one_hot(chosen, s.shape[-1]).sum(1)
    if c.get("moe_renormalize", True):
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
    if c.get("num_shared_experts", 0):
        out = out + shared_expert(h, lp)
    return out


# ----------------------------------------------------------------- model --
def layer(c, kind, x, lp):
    """(x after the layer of block ``kind``, the layer's normed input)."""
    eps = c["rms_norm_eps"]
    h = _rms_norm(x, _f32(lp["input_norm"]), eps)
    mixer = _kda_mixer if kind.startswith("kda") else _mla_mixer
    x = x + mixer(c, h, lp)
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    return x + _ffn(c, kind, h2, lp), h


def _run(c, params, tokens, keep_inputs=False):
    tokens = jnp.asarray(tokens, jnp.int32)
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][np.asarray(tokens)])
        for kind, i in layer_kinds(c):
            lp = {k: v[i] for k, v in params["layers"][kind].items()}
            x, h = jax.jit(lambda x, lp, kind=kind: layer(c, kind, x, lp))(
                x, lp
            )
            if keep_inputs and kind.startswith("kda"):
                kept.append((np.asarray(h), lp))
        x = _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
        out = [x[a:b] @ _f32(params["lm_head"]) for a, b in _blocks(len(tokens))]
        return jnp.concatenate(out, axis=0), kept


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    return _run(c, params, tokens)[0]


def kda_layers(c, params, tokens):
    """For each KDA layer of one sequence: q, k, v, g, beta as the reference
    has them, float32 numpy: what ``scan_exact`` feeds, rounded to the
    compute dtype, to the program's timed scan and to ``kda_rule``."""
    out = []
    with jax.default_matmul_precision("highest"):
        for h, lp in _run(c, params, tokens, keep_inputs=True)[1]:
            inputs = jax.jit(lambda h, lp: kda_inputs(c, h, lp))(
                jnp.asarray(h), lp
            )
            out.append(tuple(np.asarray(a) for a in inputs))
    return out


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    lg = logits(c, params, tokens)[:-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return np.asarray(
        jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0], np.float64
    )
