"""The plain reference for the ``jamba`` family's language model (AI21 Jamba2
3B's shape: Mamba-1 layers, NoPE attention layers, a dense FFN in each): its
forward pass, the GRPO loss and its gradients in float32 ``jax.numpy``.

Written from the model's public ``config.json`` (``model_type`` jamba), the
family's published modelling code's equations as ISSUE 61 sets them out, and
the Mamba paper (arXiv:2312.00752, S6). Every layer, ``x`` the residual
stream of ONE sequence [T, D]:

    h = x + Mixer(rms(x, w_in_norm));  y = h + FFN(rms(h, w_post_norm))
    FFN(u) = (silu(u Wg) * (u Wu)) Wd          dense, in EVERY layer

Layer i is attention where ``i % attn_layer_period == attn_layer_offset``,
else mamba.

attention: q = u Wq (H heads of d), k = u Wk, v = u Wv (ONE key head, or
    ``num_key_value_heads``), NO rotation and no position of any kind;
    causal softmax of q.k / sqrt(d); heads joined, times Wo.
mamba (d_inner channels C, state N, dt rank R, convolution K):
    [x, z] = u W_in;   x = silu(conv_K(x) + b_conv)     depthwise, causal,
                                     zeros before the sequence's first token
    [r, B, C] = x W_x (R + N + N);  r, B, C = rms(r; w_dt_norm),
                                     rms(B; w_b_norm), rms(C; w_c_norm)
    dt = softplus(r W_dt + b_dt);   A = -exp(A_log)               [C, N]
    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t,  h_0 = 0
    y_t = h_t . C_t + D * x_t           TOKEN BY TOKEN: ``lax.scan`` over t
    out = (y * silu(z)) W_out
logits = rms(x_L, w_final) E^T (the head tied to the embedding, or
``lm_head``).

One sequence at a time, no packing, no cache, no kernel; attention's queries
and the head's tokens in blocks of ``BLOCK`` so that thousands of tokens fit
a chip; matmuls at ``jax.default_matmul_precision("highest")``. It imports
nothing from ``areal_tpu``.

The gradient (``loss_and_grads``) is ``jax.vjp`` layer by layer over the
same functions, the stream kept at every layer's input: ``jax.grad`` of the
whole model at the published depth holds 12 GB of weights and as much of
gradients at once, more than a chip has; a layer at a time is the same
derivative. The weights may live on the host (numpy): a layer's go to the
device when it runs.

Departures from the published code: none intended. What the config leaves
open is the configuration file's ``assumed`` (the order of the layer types,
``head_dim``, the three inner norms, the initial ``A_log`` / ``dt_bias``).

The weight tree's layout is the program's: ``params["layers"][kind]`` holds
the layers of block kind ``"ssm+dense"`` / ``"full+dense"`` stacked in order,
linear weights applied as ``x @ w``, the convolution's [channels, taps] with
the LAST tap the token itself.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the head) at a time


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _blocks(t):
    return [(a, min(a + BLOCK, t)) for a in range(0, t, BLOCK)]


def head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c):
    """[(block kind, index in the kind's stack)] of every layer."""
    seen, out = {}, []
    for i in range(c["num_hidden_layers"]):
        attn = i % c["attn_layer_period"] == c["attn_layer_offset"]
        kind = ("full" if attn else "ssm") + "+dense"
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


# ----------------------------------------------------------------- mamba --
def _conv(x, w, bias):
    """[T, C] through a depthwise causal convolution, w [C, K], + bias."""
    taps = w.shape[-1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(
        padded[i:i + x.shape[0]] * w[:, i] for i in range(taps)
    ) + bias


def ssm_inputs(c, u, lp):
    """x, dt [T, C], B, C [T, N], z [T, C] of one mamba layer on its normed
    input ``u``."""
    n, r = c["mamba_d_state"], c["mamba_dt_rank"]
    eps = c["rms_norm_eps"]
    x, z = jnp.split(u @ _f32(lp["w_in"]), 2, axis=-1)
    x = jax.nn.silu(_conv(x, _f32(lp["conv_x"]), _f32(lp["conv_x_bias"])))
    rbc = x @ _f32(lp["w_x"])
    step = _rms_norm(rbc[:, :r], _f32(lp["dt_norm"]), eps)
    bm = _rms_norm(rbc[:, r:r + n], _f32(lp["b_norm"]), eps)
    cm = _rms_norm(rbc[:, r + n:], _f32(lp["c_norm"]), eps)
    dt = jax.nn.softplus(step @ _f32(lp["w_dt"]) + _f32(lp["dt_bias"]))
    return x, dt, bm, cm, z


def ssm_rule(x, dt, a, bm, cm, state_dtype=jnp.float32):
    """The selective scan token by token: y [T, C] (without the skip).
    ``state_dtype``: what the state is rounded to between tokens; float32 is
    the model, anything narrower is the "precision below" that the
    configuration's limits are shown to refuse (PERF.md, PR 61)."""

    def step(h, xs):  # h [C, N]
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
        h = h.astype(state_dtype).astype(jnp.float32)
        return h, h @ c_t

    h0 = jnp.zeros(a.shape, jnp.float32)
    # ``unroll``: the same steps in the same order, eight a loop trip (a trip
    # of ONE token costs a third of a millisecond on the chip, and a check
    # walks 88 layer-sequences of up to 4,096 tokens forward and back)
    return jax.lax.scan(step, h0, (x, dt, bm, cm), unroll=8)[1]


def _ssm_mixer(c, u, lp):
    x, dt, bm, cm, z = ssm_inputs(c, u, lp)
    y = ssm_rule(x, dt, -jnp.exp(_f32(lp["a_log"])), bm, cm)
    y = y + _f32(lp["d_skip"]) * x
    return (y * jax.nn.silu(z)) @ _f32(lp["wo"])


# ------------------------------------------------------------- attention --
def _attention(c, u, lp):
    t, hq, hk, d = (
        u.shape[0], c["num_attention_heads"], c["num_key_value_heads"],
        head_dim(c),
    )
    q = (u @ _f32(lp["wq"])).reshape(t, hq, d)
    k = jnp.repeat((u @ _f32(lp["wk"])).reshape(t, hk, d), hq // hk, axis=1)
    v = jnp.repeat((u @ _f32(lp["wv"])).reshape(t, hk, d), hq // hk, axis=1)
    idx = jnp.arange(t)
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
        ).reshape(b - a, hq * d))
    return jnp.concatenate(out, axis=0) @ _f32(lp["wo"])


# ----------------------------------------------------------------- model --
def layer(c, kind, x, lp):
    """(x after the layer of block ``kind``, the layer's normed input)."""
    eps = c["rms_norm_eps"]
    u = _rms_norm(x, _f32(lp["input_norm"]), eps)
    mixer = _ssm_mixer if kind.startswith("ssm") else _attention
    x = x + mixer(c, u, lp)
    u2 = _rms_norm(x, _f32(lp["post_attn_norm"]), eps)
    ffn = (
        jax.nn.silu(u2 @ _f32(lp["w_gate"])) * (u2 @ _f32(lp["w_up"]))
    ) @ _f32(lp["w_down"])
    return x + ffn, u


def _head_weight(c, params):
    if c.get("tie_word_embeddings"):
        return _f32(params["embedding"]).T
    return _f32(params["lm_head"])


def _layer_of(params, kind, i):
    return {k: v[i] for k, v in params["layers"][kind].items()}


def logits(c, params, tokens):
    """Float32 logits [T, V] of one sequence of token ids. Traceable: the
    tests call it under one ``jit`` (and ``jax.grad``) at a small size."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"])[jnp.asarray(tokens, jnp.int32)]
        for kind, i in layer_kinds(c):
            x = layer(c, kind, x, _layer_of(params, kind, i))[0]
        x = _rms_norm(x, _f32(params["final_norm"]), c["rms_norm_eps"])
        head = _head_weight(c, params)
        return jnp.concatenate(
            [x[a:b] @ head for a, b in _blocks(x.shape[0])], axis=0
        )


def _a_kind(fn):
    """kind -> ``jax.jit(fn(kind, ...))``, ONE compiled program a block kind
    and shape however many layers call it."""
    return functools.lru_cache(maxsize=None)(
        lambda kind: jax.jit(functools.partial(fn, kind))
    )


def _same_length(sequences):
    """``sequences`` as int32 arrays, each followed by zeros to the longest
    one's length: every layer is CAUSAL (the convolution, the scan and the
    attention look back only), so a sequence's numbers are the first rows of
    its padded self's, and the calls of one check compile ONE program a block
    kind instead of one a length."""
    n = max(len(s) for s in sequences)
    out = np.zeros((len(sequences), n), np.int32)
    for i, s in enumerate(sequences):
        out[i, :len(s)] = np.asarray(s, np.int32)
    return list(out)


def _embed(params, tokens):
    return jnp.asarray(
        np.asarray(params["embedding"])[np.asarray(tokens)], jnp.float32
    )


def _stack(c, params, sequences, keep=None):
    """The streams of ``sequences`` after the whole stack, a layer's weights
    on the device once for all of them. ``keep``: a list that takes, a layer,
    (kind, the layer's weights, [each sequence's stream INTO it])."""
    xs = [_embed(params, s) for s in sequences]
    run = _a_kind(lambda kind, x, lp: layer(c, kind, x, lp)[0])
    for kind, i in layer_kinds(c):
        lp = jax.device_put(_layer_of(params, kind, i))
        if keep is not None:
            keep.append((kind, i, [np.asarray(x) for x in xs]))
        xs = [run(kind)(x, lp) for x in xs]
    return xs


def _logp_of_stream(c, x, final_norm, head, tokens, temperature):
    """log p(tokens[i] | tokens[:i]) for i >= 1 from the stack's output."""
    x = _rms_norm(x, final_norm, c["rms_norm_eps"])
    tgt = jnp.asarray(tokens[1:], jnp.int32)
    out = []
    for a, b in _blocks(x.shape[0] - 1):
        lp = jax.nn.log_softmax((x[a:b] @ head) / temperature, axis=-1)
        out.append(jnp.take_along_axis(lp, tgt[a:b, None], axis=-1)[:, 0])
    return jnp.concatenate(out)


def sequences_logprobs(c, params, sequences, temperature=1.0):
    """``token_logprobs`` of each of ``sequences``, float64 numpy [T - 1]."""
    with jax.default_matmul_precision("highest"):
        padded = _same_length(sequences)
        xs = _stack(c, params, padded)
        norm, head = _f32(params["final_norm"]), _head_weight(c, params)
        # the head an ARGUMENT: closed over, its 0.67 GB would be a constant
        # of every length's program
        fn = jax.jit(lambda x, norm, head, tok: _logp_of_stream(
            c, x, norm, head, tok, temperature
        ))
        return [
            np.asarray(fn(x, norm, head, p), np.float64)[:len(s) - 1]
            for x, p, s in zip(xs, padded, sequences)
        ]


def token_logprobs(c, params, tokens, temperature=1.0):
    """log p(tokens[i] | tokens[:i]) for i >= 1, float64 numpy [T - 1]."""
    return sequences_logprobs(c, params, [tokens], temperature)[0]


def ssm_layers(c, params, sequences):
    """For each of ``sequences``, for each mamba layer: (x, dt, B, C, z, A)
    as the reference has them, float32 numpy: what ``scan_exact`` feeds to
    the program's timed scan and to ``ssm_rule``. One pass over the stack for
    all of them."""
    out, keep = [[] for _ in sequences], []

    @jax.jit
    def inputs(x, lp):
        u = _rms_norm(x, _f32(lp["input_norm"]), c["rms_norm_eps"])
        return ssm_inputs(c, u, lp) + (-jnp.exp(_f32(lp["a_log"])),)

    with jax.default_matmul_precision("highest"):
        _stack(c, params, _same_length(sequences), keep)
        for kind, i, streams in keep:
            if not kind.startswith("ssm"):
                continue
            lp = jax.device_put(_layer_of(params, kind, i))
            for n, x in enumerate(streams):
                *tokenwise, a = inputs(jnp.asarray(x), lp)
                out[n].append(tuple(
                    np.asarray(v)[:len(sequences[n])] for v in tokenwise
                ) + (np.asarray(a),))
    return out


# ------------------------------------------------------------------ loss --
def grpo_loss(logp, behav_logp, prox_logp, advantages, mask, eps_clip,
              behav_cap):
    """The decoupled clipped policy-gradient loss, SUMMED over the tokens of
    ``mask`` (the caller divides by their count): the ratio against the
    proximal policy clipped at 1 +- ``eps_clip``, each token weighted by
    exp(prox - behaviour), a weight above ``behav_cap`` dropped."""
    ratio = jnp.exp(logp - prox_logp)
    pg = jnp.maximum(
        -advantages * ratio,
        -advantages * jnp.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip),
    )
    w = jnp.exp(prox_logp - behav_logp)
    on = (mask > 0) & (w <= behav_cap)
    return jnp.sum(jnp.where(on, pg * w, 0.0))


def _counted(s, behav_cap):
    w = np.exp(np.asarray(s["prox_logp"], np.float64)
               - np.asarray(s["behav_logp"], np.float64))
    return int(((np.asarray(s["mask"]) > 0) & (w <= behav_cap)).sum())


def loss_and_grads(c, params, sequences, eps_clip=0.2, behav_cap=5.0,
                   temperature=1.0):
    """(loss, d loss / d params as numpy, the tree's layout) of the GRPO loss
    over ``sequences``: dicts of ``tokens`` [n] and ``behav_logp``,
    ``prox_logp``, ``advantages``, ``mask`` [n - 1] (target-aligned: entry i
    is token i + 1's); ONE mean over all their counted tokens. ``jax.vjp`` a
    layer at a time, backwards over the kept streams."""
    total = max(sum(_counted(s, behav_cap) for s in sequences), 1)
    tied = bool(c.get("tie_word_embeddings"))
    grads = jax.tree_util.tree_map(
        lambda a: np.zeros(np.shape(a), np.float32), params
    )
    padded = _same_length([s["tokens"] for s in sequences])

    def late(v, n):  # a target-aligned array, zeros after the sequence
        out = np.zeros(n - 1, np.float32)
        out[:len(v)] = np.asarray(v, np.float32)
        return out

    sequences = [
        dict({k: late(s[k], len(p)) for k in (
            "behav_logp", "prox_logp", "advantages", "mask"
        )}, tokens=p)
        for s, p in zip(sequences, padded)
    ]
    with jax.default_matmul_precision("highest"):
        keep = []
        xs = _stack(c, params, padded, keep)
        head_w = params["embedding"] if tied else params["lm_head"]

        def top(x, norm, w, s):
            logp = _logp_of_stream(
                c, x, norm, _f32(w).T if tied else _f32(w), s["tokens"],
                temperature,
            )
            return grpo_loss(
                logp, *(jnp.asarray(s[k], jnp.float32) for k in (
                    "behav_logp", "prox_logp", "advantages", "mask"
                )), eps_clip, behav_cap,
            ) / total

        top_grad = jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))
        loss, dxs = 0.0, []
        norm_d, head_d = _f32(params["final_norm"]), jnp.asarray(head_w)
        for x, s in zip(xs, sequences):
            value, (dx, dn, dw) = top_grad(x, norm_d, head_d, s)
            loss += float(value)
            grads["final_norm"] += np.asarray(dn)
            grads["embedding" if tied else "lm_head"] += np.asarray(dw)
            dxs.append(dx)
        del head_d
        back = _a_kind(lambda kind, x, lp, dy: jax.vjp(
            lambda x, lp: layer(c, kind, x, lp)[0], x, lp
        )[1](dy))
        for kind, i, streams in reversed(keep):
            lp = jax.device_put(_layer_of(params, kind, i))
            for n, x in enumerate(streams):
                dxs[n], dlp = back(kind)(jnp.asarray(x), lp, dxs[n])
                for k, g in dlp.items():
                    grads["layers"][kind][k][i] += np.asarray(g)
        for dx, s in zip(dxs, sequences):
            np.add.at(
                grads["embedding"], np.asarray(s["tokens"]), np.asarray(dx)
            )
    return loss, grads
