"""The plain reference for ZAYA1's language model (``model_type`` zaya): its
forward pass in float32 ``jax.numpy``, one chip's share of the experts and
the vocabulary.

Written from the model's public ``config.json``, the two public descriptions
it rests on (Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1
report, arXiv:2511.17127) and the equations of ISSUE 41; the family's
modelling code is not in the installed ``transformers`` (4.57.6), so what no
key states is the configuration file's ``assumed``. ``x`` is the residual
stream of ONE sequence [T, D]; d = ``head_dim``, H = ``num_attention_heads``,
G = ``num_key_value_heads``, query head i reads key head i // (H / G);
"previous token" is the previous token of the sequence, zeros at its first.

Every sublayer f updates the stream as
    x <- (x + b_res) * a_res + (f(rms(x, w_norm)) + b_out) * a_out
with four learned vectors of D a sublayer (ones and zeros when drawn).

Mixer (CCA), on h = rms(x, w_in):
    q0 = h Wq in [T, H, d];  k0 = h Wk in [T, G, d]; no bias
    mean:  m_q[t, i] = (q0[t, i] + k0[t, i // (H / G)]) / 2;
           m_k[t, g] = mean of m_q[t, i] over the H / G heads i of group g
    convolutions on c = concat(q0, k0) in [T, (H + G) d], causal, zeros
    before the sequence's first token, each with a bias:
      c1[t, j] = sum_s a[j, K0-1-s] c[t-s, j] + b1[j]       (a tap a channel)
      c2[t, head] = sum_s c1[t-s, head] A[head, K1-1-s] + b2[head]
                                      (a [d, d] matrix a head and tap)
      K0 = ``cca_time0``, K1 = ``cca_time1``
    q = c2[q part] + m_q;  k = c2[k part] + m_k
    norm:  q <- q sqrt(d) / |q| a head;  k <- k sqrt(d) / |k| * exp(tau_g)
           (|y|^2 / d + ``rms_norm_eps`` under the root: an RMS norm
           without a weight)
    rotation: the first r = d * ``partial_rotary_factor`` channels of each
      head of q and k, rotate-half pairing over those r, inv_freq_j =
      theta^(-2j / r), theta = ``rope_parameters.hybrid.rope_theta``,
      positions a token's index in the sequence; the other channels pass
    values: v[t, 0] = h_t Wv_own, v[t, 1] = h_{t-1} Wv_prev: two heads
    scores q_t . k_s / sqrt(d), softmax in float32 over the keys s <= t;
    y = concat_heads(attn) Wo, Wo [H d, D]
Router and experts, on h2 = rms(x, w_post):
    r_l = h2 W_in + b_in in [T, R], R = ``router_hidden_size``;
    r_l <- r_l + gamma_l r_{l-1} for l > 0 (the previous layer's AFTER its
    own addition);  z = rms(r_l, w_r);  s = W_out gelu(W_2 gelu(W_1 z + b_1)
    + b_2) + b_out (exact GELU);  p = softmax(s) over all ``num_experts``;
    the expert is argmax(p + beta) (ties to the lower index); the weight is
    p of the chosen, not renormalised, on the expert's OUTPUT
    expert e: (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    summed over the chosen experts HELD HERE (``num_local_experts``:
    experts 0 .. held-1): a token whose expert is held elsewhere gets zeros
    from this sublayer and still passes r_l on.
logits = rms(x_L, w_final) E^T over the ``vocab_size`` rows of the table E
the ids are looked up in (``tie_word_embeddings``).

One sequence at a time, no cache, no kernels, no packing (so a sequence's
borders are true by construction); queries (and the tokens of the experts
and the head) in blocks of ``BLOCK`` so that 8,192 tokens fit a chip;
matmuls at ``jax.default_matmul_precision("highest")``. It imports nothing
from ``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (the configuration
is one chip's share of an expert-parallel deployment; with
``num_local_experts`` = ``num_experts`` this is the whole layer); every held
expert is computed for every token and weighted by 0 where not chosen, where
the description gathers (the same sum); there is no seventeenth "skip"
output of the router (``num_experts`` says 16).

The weight tree's layout is the program's: ``params["layers"]["cca+moe"]``
holds the layers stacked in order, linear weights applied as ``x @ w``.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the experts and the head) at a time
KIND = "cca+moe"


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def _previous(x, s=1):
    """x [T, ...] ``s`` tokens late, zeros before the sequence's first."""
    if s == 0:
        return x
    return jnp.pad(x, ((s, 0),) + ((0, 0),) * (x.ndim - 1))[:-s]


# -------------------------------------------------------------- rotation --
def rotation(c):
    """inv_freq [r / 2] float32 of the rotated part of a head."""
    rope = c["rope_parameters"]["hybrid"]
    r = int(c["head_dim"] * rope.get("partial_rotary_factor", 1.0))
    j = np.arange(r // 2, dtype=np.float64)
    return (float(rope["rope_theta"]) ** (-2.0 * j / r)).astype(np.float32)


def rotate(x, inv_freq):
    """[T, heads, d] with the first 2 len(inv_freq) channels of every head
    rotated by the token's index, pairing channel i with i + len(inv_freq)."""
    t, n = x.shape[0], inv_freq.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b, rest = x[..., :n], x[..., n:2 * n], x[..., 2 * n:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


# ----------------------------------------------------------------- mixer --
def causal_attention(q, k, v):
    """[T, H, d] out of q [T, H, d], k and v [T, G, d]: softmax over the
    keys s <= t, query head i on key head i // (H / G); in blocks of
    queries."""
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
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d)
        )
        ok = row[:, None] >= keys[None, :]
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(blk, h, d)

    return jax.lax.map(block, (qg, rows)).reshape(n_blk * blk, h, d)[:t]


def qkv(c, h, lp):
    """The q [T, H, d] and k [T, G, d] (mixed, normed, rotated) and v
    [T, 2, d] that enter the core of one layer, on its normed input ``h``."""
    t, d = h.shape[0], c["head_dim"]
    H, G = c["num_attention_heads"], c["num_key_value_heads"]
    q0 = (h @ _f32(lp["wq"])).reshape(t, G, H // G, d)
    k0 = (h @ _f32(lp["wk"])).reshape(t, G, 1, d)
    mean_q = (q0 + k0) / 2
    mean_k = mean_q.mean(axis=2)
    x = jnp.concatenate(
        [q0.reshape(t, H * d), k0.reshape(t, G * d)], axis=-1
    )
    a = _f32(lp["cca_conv0"])  # [(H + G) d, K0], tap K0-1 the token itself
    k0_taps = a.shape[1]
    c1 = sum(
        _previous(x, s) * a[:, k0_taps - 1 - s] for s in range(k0_taps)
    ) + _f32(lp["cca_conv0_bias"])
    A = _f32(lp["cca_conv1"])  # [H + G, K1, d, d]
    k1_taps = A.shape[1]
    c1 = c1.reshape(t, H + G, d)
    c2 = sum(
        jnp.einsum("thi,hio->tho", _previous(c1, s), A[:, k1_taps - 1 - s])
        for s in range(k1_taps)
    ) + _f32(lp["cca_conv1_bias"]).reshape(H + G, d)

    def unit(y):
        return y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + c["rms_norm_eps"]
        )

    q = unit(c2[:, :H] + mean_q.reshape(t, H, d))
    k = unit(c2[:, H:] + mean_k) * jnp.exp(_f32(lp["cca_temp"]))[:, None]
    inv_freq = rotation(c)
    v = jnp.stack(
        [h @ _f32(lp["wv_own"]), _previous(h @ _f32(lp["wv_prev"]))], axis=1
    )
    return rotate(q, inv_freq), rotate(k, inv_freq), v


def _mixer(c, h, lp):
    q, k, v = qkv(c, h, lp)
    return causal_attention(q, k, v).reshape(h.shape[0], -1) @ _f32(lp["wo"])


# ------------------------------------------------------ router and experts --
def router(c, h, lp, r_prev):
    """(p [T, E] over ALL experts, the router's state r_l [T, R])."""
    r = h @ _f32(lp["w_router_in"]) + _f32(lp["b_router_in"])
    if r_prev is not None:
        r = r + _f32(lp["router_carry"]) * r_prev
    z = _rms_norm(r, _f32(lp["router_norm"]), c["rms_norm_eps"])
    for i in ("1", "2"):
        z = jax.nn.gelu(
            z @ _f32(lp[f"w_router_{i}"]) + _f32(lp[f"b_router_{i}"]),
            approximate=False,
        )
    s = z @ _f32(lp["w_router_out"]) + _f32(lp["b_router_out"])
    return jax.nn.softmax(s, axis=-1), r


def chosen(p, lp):
    """[T] the expert of each token: the largest p + beta, ties to the
    lower index."""
    return jnp.argmax(p + _f32(lp["router_bias"]), axis=-1)


def experts(h, lp, p, first_expert=0):
    """The part of the routed result that the experts held in ``lp`` give
    (experts ``first_expert`` and following): [T, D]."""
    held = lp["w_gate"].shape[0]
    weight = (p * jax.nn.one_hot(chosen(p, lp), p.shape[-1]))[
        :, first_expert:first_expert + held
    ]
    out = []
    for a, b in _blocks(h.shape[0]):
        gate = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_gate"]))
        up = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_up"]))
        y = jnp.einsum(
            "tef,efd->ted", jax.nn.silu(gate) * up, _f32(lp["w_down"])
        )
        out.append(jnp.einsum("te,ted->td", weight[a:b], y))
    return jnp.concatenate(out, axis=0)


# ----------------------------------------------------------------- model --
def _residual(x, y, lp, sub):
    return (
        (x + _f32(lp[f"{sub}_res_bias"])) * _f32(lp[f"{sub}_res_scale"])
        + (y + _f32(lp[f"{sub}_out_bias"])) * _f32(lp[f"{sub}_out_scale"])
    )


def layer(c, x, r_prev, lp, first_expert=0):
    """(x after the layer, the router's state it passes on, the mixer's
    normed input ``h``)."""
    eps = c["rms_norm_eps"]
    h = _rms_norm(x, _f32(lp["input_norm"]), eps)
    x = _residual(x, _mixer(c, h, lp), lp, "attn")
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    p, r = router(c, h2, lp, r_prev)
    x = _residual(x, experts(h2, lp, p, first_expert), lp, "ffn")
    return x, r, h


def _run(c, params, tokens, keep=False):
    """(logits [T, V], a dict a layer where asked: the mixer's normed input
    ``h`` and the q, k, v entering the core, float32 numpy). One jitted
    function for all layers (they are of one kind), compiled once a sequence
    length."""
    kept = []

    @jax.jit
    def fn(x, r_prev, lp):
        x, r, h = layer(c, x, r_prev, lp)
        if not keep:
            return x, r, None
        q, k, v = qkv(c, h, lp)
        return x, r, {"h": h, "q": q, "k": k, "v": v}

    with jax.default_matmul_precision("highest"):
        table = _f32(params["embedding"])
        x = table[jnp.asarray(tokens, jnp.int32)]
        # zeros into the first layer: gamma_0 times nothing
        r = jnp.zeros((len(tokens), c["router_hidden_size"]), jnp.float32)
        stack = params["layers"][KIND]
        for i in range(c["num_hidden_layers"]):
            lp = {k: v[i] for k, v in stack.items()}
            x, r, saw = fn(x, r, lp)
            if saw is not None:
                kept.append({k: np.asarray(v) for k, v in saw.items()})
        x = _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
        out = [x[a:b] @ table.T for a, b in _blocks(len(tokens))]
        return jnp.concatenate(out, axis=0), kept


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids."""
    return _run(c, params, tokens)[0]


def _logprobs_of(lg, tokens, temperature):
    lp = jax.nn.log_softmax(lg[:-1] / temperature, axis=-1)
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    return np.asarray(
        jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0], np.float64
    )


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    return _logprobs_of(logits(c, params, tokens), tokens, temperature)


def logprobs_and_mix(c, params, tokens, temperature=1.0):
    """``token_logprobs`` and, from the same pass, what every layer saw
    (``_run``'s dicts): what ``mix_exact`` compares the program's mixer
    with."""
    lg, kept = _run(c, params, tokens, keep=True)
    return _logprobs_of(lg, tokens, temperature), kept
