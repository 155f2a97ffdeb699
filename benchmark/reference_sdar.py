"""The plain reference for SDAR-30B-A3B-Chat as a block-diffusion POLICY under
training: its forward pass over ``[x_0 ; x_s]``, the logprob of every
completion token at its own masked slot, and the GRPO loss with its gradients
by ``jax.grad``; float32 ``jax.numpy``, one chip's share of the experts and
the vocabulary.

Written from the model's public ``config.json`` (``model_type`` sdar_moe: the
qwen3-moe block, 32 / 4 heads of 128 normed a head and rotated whole, 128
softmax-routed experts of which 8 a token, renormalised), from BD3-LM's
training layout (arXiv:2503.09573, which the family's published training
forward uses) and from TraceRL's policy gradient (arXiv:2509.06949) as ISSUE
58 sets them out. With block length B, token p of a sequence is in block
p // B, the prompt counted from position 0. ``states[i]`` is -1 on the prompt
and s in [0, K) on a completion token revealed in its block's state s. For
ONE sequence x of L tokens and each state s:

    1. x_s = x with every token of state >= s replaced by the mask token (the
       prompt, state -1, and what earlier states revealed stay); the row is
       [x_0 ; x_s], 2L slots, slot j and slot L + j at position j
    2. the mask [2L, 2L], the union of three terms (q = query, k = key):
       block-causal        q clean, k clean, block(k) <= block(q)
       offset-block-causal q noisy, k clean, block(k) <  block(q)
       block-diagonal      q noisy, k noisy, block(k) == block(q)
    3. every layer: h = x + Attn(norm(x)), y = h + MoE(norm(h)); attention
       softmax(q k^T / sqrt(128) + M) v with q, k normed a head (RMSNorm,
       weight [head_dim]) and rotated (rotate-half, theta) at the slot's
       position; MoE: softmax over all ``num_experts`` router outputs, the
       ``num_experts_per_tok`` largest renormalised to sum 1, the sum over
       the chosen experts HELD HERE of p_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    4. token i of state s is scored at slot L + i:
       logp_i = log softmax(norm(y_{L+i}) W_head / temperature)[x_i], NO shift

One sequence at a time, no packing, no kernel, no cache: the noisy half is
the WHOLE sequence masked (its prompt slots are read by nothing that is
scored: a noisy query sees its own block alone of the noisy half), where the
program lays out only what is read. Queries in blocks of ``BLOCK`` so that a
4,099-token check sequence (8,198 slots) fits a chip beside the weights;
matmuls at ``jax.default_matmul_precision("highest")``. It imports nothing
from ``areal_tpu``.

Departures from the published description, each with its reason: what the
absent experts would add is LEFT OUT, as in the program (one chip's share of
an expert-parallel deployment; with ``num_local_experts`` = ``num_experts``
this is the whole layer; ``first`` says which experts a share holds, for the
test that adds the shares up); every held expert is computed for every token
and weighted by 0 where not chosen (the same sum); block length and mask
token are the configuration file's ``assumed``.

The weight tree's layout is the program's: a layer's leaves stacked over
layers under ``params["layers"]["full+moe"]`` (``wq`` [L, D, H*Dh] applied as
``x @ w``, ``wk``, ``wv``, ``wo``, ``q_norm`` / ``k_norm`` [L, Dh],
``input_norm``, ``post_attn_norm``, ``w_router`` [L, D, E], ``w_gate`` /
``w_up`` [L, held, D, F], ``w_down`` [L, held, F, D]); ``embedding`` [V, D],
``final_norm``, ``lm_head`` [D, V].
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # queries (and tokens of the expert layer) at a time
# ... of a pass that keeps nothing for a backward: a quarter of the blocks to
# unroll, and a layer's program compiles in a quarter of the time
FORWARD_BLOCK = 2048
KIND = "full+moe"


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


def _blocks(t, size=BLOCK):
    return [(a, min(a + size, t)) for a in range(0, t, size)]


def block_length(c):
    return int(c.get("block_length", 4))


def noisy_tokens(c, tokens, states, s):
    """Step 1: x_s."""
    tokens, states = np.asarray(tokens), np.asarray(states)
    return np.where(states >= s, c["mask_token_id"], tokens)


def mask_rows(c, n, a, b):
    """Step 2: rows ``a:b`` of the mask of a sequence of ``n`` tokens, bool
    [b - a, 2n], the union of the three terms."""
    size = block_length(c)
    slot = jnp.arange(2 * n)
    noisy, blk = slot >= n, (slot % n) // size
    qn, kn = noisy[a:b, None], noisy[None, :]
    qb, kb = blk[a:b, None], blk[None, :]
    block_causal = ~qn & ~kn & (kb <= qb)
    offset_block_causal = qn & ~kn & (kb < qb)
    block_diagonal = qn & kn & (kb == qb)
    return block_causal | offset_block_causal | block_diagonal


def masked_softmax_attention(c, q, k, v, n, block=BLOCK):
    """Step 3's core over the 2n slots, queries in blocks: q [2n, Hq, Dh],
    k, v [2n, Hkv, Dh] -> [2n, Hq * Dh]."""
    t, hq, dh = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = []
    for a, b in _blocks(t, block):
        scores = jnp.einsum("qhd,khd->hqk", q[a:b], k) / jnp.sqrt(
            jnp.float32(dh)
        )
        scores = jnp.where(mask_rows(c, n, a, b)[None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(b - a, hq * dh))
    return jnp.concatenate(out, axis=0)


def experts(c, h, lp, first=0, block=BLOCK):
    """The sum over the chosen experts a share holds (experts ``first`` ..
    ``first`` + held - 1): [T, D]."""
    held = lp["w_gate"].shape[0]
    p = jax.nn.softmax(h @ _f32(lp["w_router"]), axis=-1)  # [T, E] all
    chosen = jax.lax.top_k(p, c["num_experts_per_tok"])[1]
    weight = p * jax.nn.one_hot(chosen, p.shape[-1]).sum(1)  # 0 off S
    if c.get("norm_topk_prob", False):
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight[:, first:first + held]  # the others' part is left out
    out = []
    for a, b in _blocks(h.shape[0], block):
        gate = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_gate"]))
        up = jnp.einsum("td,edf->tef", h[a:b], _f32(lp["w_up"]))
        y = jnp.einsum(
            "tef,efd->ted", jax.nn.silu(gate) * up, _f32(lp["w_down"])
        )
        out.append(jnp.einsum("te,ted->td", weight[a:b], y))
    return jnp.concatenate(out, axis=0)


def qkv(c, x, lp, cos, sin):
    """A layer's q, k, v as its core takes them, from the layer's input."""
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
    return _rope(q, cos, sin), _rope(k, cos, sin), v


def layer(c, x, lp, cos, sin, n, block=BLOCK):
    q, k, v = qkv(c, x, lp, cos, sin)
    x = x + masked_softmax_attention(c, q, k, v, n, block) @ _f32(lp["wo"])
    h2 = _rms_norm(x, _f32(lp["post_attn_norm"]), c["rms_norm_eps"])
    return x + experts(c, h2, lp, block=block)


def rotation(c, n):
    """cos, sin [2n, Dh / 2]: slot j and slot n + j at position j."""
    dh = c["head_dim"]
    inv = 1.0 / (
        c["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    )
    pos = jnp.concatenate([jnp.arange(n), jnp.arange(n)]).astype(jnp.float32)
    ang = pos[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def stack_of(params):
    layers = params["layers"]
    return layers[KIND] if KIND in layers else layers


_KEYS = (
    "hidden_size", "num_hidden_layers", "head_dim", "num_attention_heads",
    "num_key_value_heads", "rope_theta", "rms_norm_eps",
    "num_experts_per_tok", "norm_topk_prob", "block_length", "mask_token_id",
)
_PROGRAMS = {}  # (the configuration's keys, n, temperature, remat, jitted)


def _programs(c, n, temperature, remat, jitted):
    """(a layer, the head's logprobs, a layer's q, k, v) for sequences of
    ``n`` tokens, each ONE jitted program a length where ``jitted`` (made
    once: a reference that ran an operation at a time spent twenty minutes
    of a chip run dispatching them)."""
    key = (tuple(c.get(k) for k in _KEYS), n, float(temperature), remat,
           jitted)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    cos, sin = rotation(c, n)
    block = BLOCK if remat else FORWARD_BLOCK

    def step(x, lp):
        return layer(c, x, lp, cos, sin, n, block)

    def head(x, final_norm, lm_head, own):
        x = _rms_norm(x[n:], _f32(final_norm), c["rms_norm_eps"])
        out = []
        for a, b in _blocks(n, block):
            lg = x[a:b] @ _f32(lm_head) / temperature
            out.append(jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), own[a:b, None], axis=-1
            )[:, 0])
        return jnp.concatenate(out)

    def kept(x, lp):
        return qkv(c, x, lp, cos, sin)

    if remat:
        step = jax.checkpoint(step)
    if jitted:
        step, head, kept = jax.jit(step), jax.jit(head), jax.jit(kept)
    _PROGRAMS[key] = step, head, kept
    return _PROGRAMS[key]


def state_logprobs(c, params, tokens, states, s, temperature=1.0,
                   jit_layers=True, keep=None, remat=False):
    """Steps 1-4 for state ``s`` of one sequence: float32 [n], log p of every
    token at its slot of the noisy half (the caller reads those of state s).
    ``keep``: a list that receives each layer's (q, k, v) [2n, heads, Dh].
    ``remat``: a layer keeps its input alone for a backward pass (what lets
    ``jax.grad`` run at the check's lengths)."""
    n = len(tokens)
    row = np.concatenate(
        [np.asarray(tokens), noisy_tokens(c, tokens, states, s)]
    )
    step, head, kept = _programs(c, n, temperature, remat, jit_layers)
    stack = stack_of(params)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"])[jnp.asarray(row, jnp.int32)]
        for i in range(c["num_hidden_layers"]):
            lp = {k: v[i] for k, v in stack.items()}
            if keep is not None:
                keep.append(kept(x, lp))
            x = step(x, lp)
        return head(
            x, params["final_norm"], params["lm_head"],
            jnp.asarray(tokens, jnp.int32),
        )


def token_logprobs(c, params, tokens, states, temperature=1.0,
                   jit_layers=True, remat=False):
    """log p of every completion token at its own masked slot (step 4), 0 on
    the prompt: float32 jnp [n], differentiable in ``params``."""
    states = np.asarray(states)
    out = jnp.zeros(len(tokens), jnp.float32)
    for s in range(int(states.max(initial=-1)) + 1):
        lp = state_logprobs(
            c, params, tokens, states, s, temperature, jit_layers,
            remat=remat,
        )
        out = jnp.where(jnp.asarray(states == s), lp, out)
    return out


def grpo_loss(logp, behav_logp, prox_logp, advantages, mask, eps_clip,
              behav_cap):
    """The decoupled clipped policy-gradient loss over the tokens of
    ``mask`` (TraceRL's form is the trainer's as it stands): the ratio
    against the proximal policy clipped at 1 +- ``eps_clip``, each token
    weighted by exp(prox - behaviour), a weight above ``behav_cap`` dropped."""
    ratio = jnp.exp(logp - prox_logp)
    pg = jnp.maximum(
        -advantages * ratio,
        -advantages * jnp.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip),
    )
    w = jnp.exp(prox_logp - behav_logp)
    on = (mask > 0) & (w <= behav_cap)
    return jnp.sum(jnp.where(on, pg * w, 0.0)) / jnp.maximum(on.sum(), 1)


def loss_and_grads(c, params, sequences, eps_clip=0.2, behav_cap=5.0,
                   temperature=1.0, remat=False):
    """(loss, d loss / d params) of the GRPO loss over ``sequences``: dicts
    of ``tokens``, ``states``, ``behav_logp``, ``prox_logp``, ``advantages``,
    ``mask`` [n] each; one mean over all their masked tokens."""
    def total(p):
        cat = {k: [] for k in ("logp", "behav_logp", "prox_logp",
                               "advantages", "mask")}
        for s in sequences:
            cat["logp"].append(token_logprobs(
                c, p, s["tokens"], s["states"], temperature, remat=remat,
            ))
            for k in list(cat)[1:]:
                cat[k].append(jnp.asarray(s[k], jnp.float32))
        cat = {k: jnp.concatenate(v) for k, v in cat.items()}
        return grpo_loss(
            cat["logp"], cat["behav_logp"], cat["prox_logp"],
            cat["advantages"], cat["mask"], eps_clip, behav_cap,
        )

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(params)
