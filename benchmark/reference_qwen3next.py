"""The plain reference for Qwen3-Next's language model: its forward pass in
float32 ``jax.numpy``, one chip's share of the experts and the vocabulary.

Written from the model's public ``config.json`` (``model_type`` qwen3_next),
the family's public modelling code as ISSUE 52 restates it, and the Gated
Delta Networks paper (arXiv:2412.06464). ``x`` is the residual stream of ONE
sequence [T, D]; every norm of the stack is ``norm(x; w) = x * rsqrt(mean(x^2)
+ eps) * (1 + w)`` (``w`` starts at zero) but the delta rule's output norm;
``h = norm(x, w_in)``. Layer ``i`` (0-indexed) is ``full`` where ``(i + 1) %
full_attention_interval == 0``, else ``gdn``; every FFN is sparse.

gdn mixer, Hk key heads and Hv value heads of dk = dv:
    [q, k, v, z] = h W_qkvz (q, k a key head; v, z a value head), [b, a] = h
    W_ba (a value head);  [q, k, v] through ONE depthwise causal convolution
    of ``linear_conv_kernel_dim`` taps (zeros before the sequence's first
    token, no bias), then SiLU;  q, k divided by sqrt(sum of squares + 1e-6)
    a head;  key head ``h // (Hv / Hk)`` serves value head ``h``
    beta_t = sigmoid(b_t),  g_t = -exp(A_log) * softplus(a_t + dt_bias)  [Hv]
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t / sqrt(dk)                      TOKEN BY TOKEN, no chunks
    y = (o * rsqrt(mean(o^2) + eps) * w_o  *  silu(z)) W_out    (w_o PLAIN)
full mixer, Hq query heads over Hkv key heads of D:
    h W_q is, a head, a query of D then a gate of D;  k, v = h W_k, h W_v
    q, k through ``norm`` a head;  the first ``partial_rotary_factor * D``
    channels of a head rotated (rotate-half inside them, ``rope_theta``)
    causal softmax of q.k / sqrt(D), query head ``h`` reading key head
    ``h // (Hq / Hkv)``;  (attn * sigmoid(gate)) W_o
FFN, on ``h2 = norm(x + y, w_post)``:
    p = softmax(h2 W_r) over all ``num_experts``; the ``num_experts_per_tok``
    largest, their p over their sum (``norm_topk_prob``); summed over the
    chosen experts HELD HERE (``num_local_experts``: experts 0 .. held-1);
    plus sigmoid(h2 w_sg) * SwiGLU_shared(h2).
logits = norm(x_L, w_final) W_head over the ``vocab_size`` rows held.

One sequence at a time, no cache, no kernels, no chunks of the rule; queries
and tokens in blocks of ``BLOCK`` so that thousands of tokens fit a chip;
matmuls at ``jax.default_matmul_precision("highest")``. It imports nothing
from ``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (the configuration
is one chip's share of an expert-parallel deployment; with
``num_local_experts`` = ``num_experts`` this is the whole layer); every held
expert is computed for every token and weighted by 0 where not chosen, where
the description gathers (the same sum); the columns of ``W_qkvz`` and ``W_ba``
lie side by side ([q | k | v | z], [b | a]) where the published tensors
interleave them a key head (a permutation of columns: the same function of
freshly drawn weights); the multi-token-prediction head is not there (no key
of the config describes it, and the update does not train it); what the
config leaves open is the configuration file's ``assumed``.

The weight tree's layout is the program's: ``params["layers"][kind]`` holds
the layers of block kind ``"<mixer>+moe"`` stacked in order, linear weights
applied as ``x @ w``, a convolution's [channels, taps] with the LAST tap the
token itself.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the FFN and the head) at a time


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _norm(x, w, eps):
    """The stack's norm: scaled by (1 + w)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def layer_kinds(c):
    """[(block kind, index in the kind's stack)] of every layer."""
    every = c.get("full_attention_interval", 4)
    seen, out = {}, []
    for i in range(c["num_hidden_layers"]):
        kind = ("full" if (i + 1) % every == 0 else "gdn") + "+moe"
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


# ------------------------------------------------------------------- gdn --
def _conv(x, w):
    """[T, C] through a depthwise causal convolution, w [C, K]."""
    taps = w.shape[-1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(
        padded[i:i + x.shape[0]] * w[:, i] for i in range(taps)
    )


def _gdn_sizes(c):
    return (c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"])


def gdn_inputs(c, h, lp):
    """q, k [T, Hk, dk], v [T, Hv, dv], g, beta [T, Hv] of one gdn layer on
    its normed input ``h``, and the output's gate z [T, Hv, dv]."""
    hk, hv, dk, dv = _gdn_sizes(c)
    t = h.shape[0]
    kd, vd = hk * dk, hv * dv
    qkvz = h @ _f32(lp["w_qkvz"])
    qkv = jax.nn.silu(_conv(qkvz[:, :2 * kd + vd], _f32(lp["conv_qkv"])))

    def l2norm(y):
        y = y.reshape(t, hk, dk)
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    ba = h @ _f32(lp["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(lp["a_log"])) * jax.nn.softplus(
        ba[:, hv:] + _f32(lp["dt_bias"])
    )
    return (
        l2norm(qkv[:, :kd]), l2norm(qkv[:, kd:2 * kd]),
        qkv[:, 2 * kd:].reshape(t, hv, dv), g, beta,
        qkvz[:, 2 * kd + vd:].reshape(t, hv, dv),
    )


def gdn_rule(q, k, v, g, beta):
    """The gated delta rule with one decay a head, token by token: o
    [T, Hv, dv]. ``q, k`` [T, Hk, dk]: a key head serves ``Hv / Hk``
    consecutive value heads."""
    hv, dk = v.shape[1], q.shape[-1]
    r = hv // q.shape[1]
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    scale = dk ** -0.5

    def step(s, x):  # s [Hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        read = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - read)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t) * scale

    s0 = jnp.zeros((hv, dk, v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def _gdn_mixer(c, h, lp):
    q, k, v, g, beta, z = gdn_inputs(c, h, lp)
    o = gdn_rule(q, k, v, g, beta)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    y = o * jax.lax.rsqrt(var + c["rms_norm_eps"]) * _f32(lp["o_norm"])
    return (y * jax.nn.silu(z)).reshape(h.shape[0], -1) @ _f32(lp["wo"])


# ------------------------------------------------------------------ full --
def _rotate(x, positions, rot, theta):
    """The first ``rot`` channels of every head of ``x`` [T, H, D] rotated
    (rotate-half inside them), the rest as they are."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv  # [T, rot / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1
    )


def _full_mixer(c, h, lp):
    t, hq, hkv = h.shape[0], c["num_attention_heads"], c["num_key_value_heads"]
    d, eps = c["head_dim"], c["rms_norm_eps"]
    rot = int(d * c.get("partial_rotary_factor", 1.0))
    qg = (h @ _f32(lp["wq"])).reshape(t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ _f32(lp["wk"])).reshape(t, hkv, d)
    v = (h @ _f32(lp["wv"])).reshape(t, hkv, d)
    idx = jnp.arange(t)
    q = _rotate(_norm(q, _f32(lp["q_norm"]), eps), idx, rot, c["rope_theta"])
    k = _rotate(_norm(k, _f32(lp["k_norm"]), eps), idx, rot, c["rope_theta"])
    group = hq // hkv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for a, b in _blocks(t):
        scores = jnp.einsum("qhd,khd->hqk", q[a:b], k) / jnp.sqrt(
            jnp.float32(d)
        )
        scores = jnp.where(
            (idx[None, :] <= idx[a:b, None])[None], scores, -jnp.inf
        )
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
        ))
    attn = jnp.concatenate(out, axis=0) * jax.nn.sigmoid(gate)
    return attn.reshape(t, hq * d) @ _f32(lp["wo"])


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


def shared_expert(h, lp):
    """The shared expert behind its sigmoid gate, one number a token."""
    return jax.nn.sigmoid(h @ _f32(lp["w_shared_router"])) * _swiglu(
        h, _f32(lp["w_shared_gate"]), _f32(lp["w_shared_up"]),
        _f32(lp["w_shared_down"]),
    )


def _ffn(c, h, lp):
    out = experts(c, h, lp)
    if c.get("shared_expert_intermediate_size", 0):
        out = out + shared_expert(h, lp)
    return out


# ----------------------------------------------------------------- model --
def layer(c, kind, x, lp):
    """(x after the layer of block ``kind``, the layer's normed input)."""
    eps = c["rms_norm_eps"]
    h = _norm(x, _f32(lp["input_norm"]), eps)
    mixer = _gdn_mixer if kind.startswith("gdn") else _full_mixer
    x = x + mixer(c, h, lp)
    h2 = _norm(x, _f32(lp["post_attn_norm"]), eps)
    return x + _ffn(c, h2, lp), h


def head(c, params, x):
    """Logits [T, V] of the stack's output ``x``."""
    x = _norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
    return x @ _f32(params["lm_head"])


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
            if keep_inputs and kind.startswith("gdn"):
                kept.append((np.asarray(h), lp))
        out = [head(c, params, x[a:b]) for a, b in _blocks(len(tokens))]
        return jnp.concatenate(out, axis=0), kept


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    return _run(c, params, tokens)[0]


def gdn_layers(c, params, tokens):
    """For each gdn layer of one sequence: q, k, v, g, beta as the reference
    has them, float32 numpy: what ``scan_exact`` feeds, rounded to the
    compute dtype, to the program's timed scan and to ``gdn_rule``."""
    out = []
    with jax.default_matmul_precision("highest"):
        for h, lp in _run(c, params, tokens, keep_inputs=True)[1]:
            inputs = jax.jit(lambda h, lp: gdn_inputs(c, h, lp)[:5])(
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
