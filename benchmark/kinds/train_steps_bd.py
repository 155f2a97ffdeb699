"""Trainer steps of a block-diffusion policy: rows that hold each sequence's
clean stream and a masked copy a denoising state, under a mask that is causal
between blocks and two-way inside one, on one chip's share of the experts and
the vocabulary.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window), as
``kinds/train_steps_dsa.py`` takes it; ``run`` is one function there and
cannot be imported in parts, so the window loop is restated here in the same
order. What differs:

- every batch carries a per-token ``denoise_state`` (``trace_states``: inside
  each block of ``block_length`` positions a seeded order of its completion
  tokens, the first two revealed state 0, the rest state 1, from the
  LAYOUT's seed, so every seed packs the same rows);
- THE WEIGHTS AND THE BATCHES' TOKEN IDS ARE THE LAYOUT'S (``layout_batches``;
  the weights as ``kinds/train_steps_swa_long.py``'s and for its reason):
  the engine is initialised from the traffic file's ``layout_seed`` and the
  ids are drawn from it; ``--seed`` draws the rewards, the behaviour
  logprobs, the order of the batches and the check's sequences. 16 of 128
  experts are held and a third of a row's slots hold ONE token, the mask,
  whose slots share their residual stream at random weights: a 4,096-token
  chunk of a masked copy sends its pairs the same few ways, and whether the
  held share's slab overflows there (a second run of the grouped matmuls,
  ``ops/moe._run_held``: ``moe_overflow_chunks``) turns on the draw and, at
  the margin, on the chunk's other tokens. Measured (my chip runs, PR 58):
  with every weight from ``--seed`` four seeds read 11,468-11,574 trained
  tokens/s and 56-112 overflow chunks a window, the rate a line in the
  chunks (6 ms each); with the weights alone the layout's two seeds still
  read 85 and 118 chunks a three-pass window, and the driver's check read
  a quartile spread of 1.55% and 1.60% in its two sets of six runs against
  the 1% of the bound (``BENCHMARK_REFUSED.md``, PR 58's first check); with
  the ids the layout's too every seed's first forward routes alike;
- ``trained_tokens`` counts a sequence's own P + R tokens ONCE, not its
  slots; ``train_flops`` is ``arith_sdar``'s (the layers over slots, the core
  over kept pairs, the head over scored tokens);
- the reference and the arithmetic are this configuration's
  (``benchmark/reference_sdar.py``, ``benchmark/arith_sdar.py``); the engine's
  state leaves the chip before the reference runs;
- a rehearsal builds the program's tiny configuration of the family itself;
- ``correct`` gains ``packing_invariant`` (the check's sequences read the same
  alone and among a batch's) and ``mask_exact``: (a) on each layer's q, k, v
  as the REFERENCE has them (rounded to the dtype the program computes in),
  the timed core's instance at the window's row length against the
  reference's masked softmax, every check sequence and state; (b) the edges,
  bit for bit, on a packed row of the program's own layout: moving v at one
  clean key of block b changes the clean queries of blocks >= b and the
  noisy queries of blocks > b of that sequence, every one, and no other
  slot; moving v at one stream-s key of block b changes exactly the stream-s
  queries of block b; (c) the window's ``bd_pairs``, ``bd_causal_pairs`` and
  ``bd_scored_tokens`` are what the trained lengths make them, and
  ``bd_sequence_tokens`` + ``bd_stream_slots`` the slots dispatched;
- ``correct`` gains ``gradient_direction``: on the check's sequences of at
  most ``grad_check_max_len`` tokens, packed to a row, the TIMED ``grad_step``
  (the engine's own cached program, at that row's length) run once without
  an optimizer step, against ``jax.grad`` of the reference's GRPO loss over
  the same sequences one at a time: the losses within
  ``gradient_limits.loss_abs_diff`` and the cosine of the two whole gradients
  at least ``gradient_limits.cosine``;
- counters of the layout and of the routing to the experts held (``bd_*``,
  ``moe_*``, ``real_tokens`` from ``SPMDTrainEngine.counters()``; a program
  without them leaves them out) and the traced steps' kernel operations
  (``bd_core_flops_traced``, ``moe_expert_flops_traced_per_chip``).
"""

import functools
import gc
import math
import time

import numpy as np

from benchmark import arith_sdar, harness, reference_sdar
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "sdar_moe"
REHEARSE_CORE = 1e-4  # float32 against float32


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal."""
    from areal_tpu.models.config import tiny_config

    t = tiny_config(FAMILY)
    return t, {
        "hidden_size": t.hidden_size, "num_hidden_layers": t.num_layers,
        "head_dim": t.head_dim, "num_attention_heads": t.num_heads,
        "num_key_value_heads": t.num_kv_heads, "rope_theta": t.rope_theta,
        "rms_norm_eps": t.rms_norm_eps, "vocab_size": t.vocab_size,
        "num_experts": t.num_experts, "num_local_experts": t.num_held_experts,
        "num_experts_per_tok": t.num_experts_per_tok,
        "moe_intermediate_size": t.expert_ffn_size,
        "norm_topk_prob": t.norm_topk_prob,
        "tie_word_embeddings": t.tie_word_embeddings,
        "block_length": t.block_diffusion.block_length,
        "mask_token_id": t.block_diffusion.mask_token_id,
    }


def layout_batches(p, vocab, seed):
    """``make_batches`` with the token ids the LAYOUT's as the lengths are:
    every seed's rows then route alike through the experts held (the module's
    docstring); the rewards and the behaviour logprobs stay ``seed``'s."""
    batches = make_batches(p, vocab, seed)
    for batch, fixed in zip(batches, make_batches(p, vocab, p["layout_seed"])):
        batch["input_ids"] = fixed["input_ids"]
    return batches


def trace_states(prompt, n, size, states, rng):
    """A sequence's ``denoise_state`` [n]: -1 on the ``prompt`` tokens;
    inside each block of ``size`` positions (counted from 0) ``rng`` orders
    the block's completion tokens, the first two revealed are state 0 and
    the rest state 1 (``states`` 1: every one state 0)."""
    out = np.full(int(n), -1, np.int32)
    for a in range(int(prompt) // size * size, int(n), size):
        own = np.arange(max(a, int(prompt)), min(a + size, int(n)))
        order = rng.permutation(len(own))
        out[own[order]] = (np.arange(len(own)) >= 2) if states > 1 else 0
    return out


def with_states(batch, prompts, size, states, rng):
    """``batch`` with its ``denoise_state`` [B, L] (-1 on padding too)."""
    lens = np.asarray(batch["attention_mask"]).sum(1)
    d = np.full(np.asarray(batch["input_ids"]).shape, -1, np.int32)
    for i, (p, n) in enumerate(zip(prompts, lens)):
        d[i, :n] = trace_states(p, n, size, states, rng)
    return dict(batch, denoise_state=d)


REHEARSE_GRADIENT = {"cosine": 0.9999, "loss_abs_diff": 1e-4}


def gradient_inputs(seqs, states, vocab, seed):
    """A padded batch of the check's ``seqs`` as a minibatch of the update
    has them: behaviour logprobs near -log vocab, proximal ones near those,
    an advantage of +1 or -1 a sequence, the loss over every completion
    token."""
    from areal_tpu.utils import data as data_utils

    rng = np.random.default_rng(seed)
    batch = data_utils.pad_sequences_to_tensors(seqs)
    d = np.full(batch["input_ids"].shape, -1, np.int32)
    for i, s in enumerate(states):
        d[i, :len(s)] = s
    lm = (d >= 0).astype(np.int32)
    behav = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1
        - np.float32(math.log(vocab))
    ) * lm
    sign = np.where(np.arange(len(seqs)) % 2 == 0, 1.0, -1.0)
    batch.update(
        denoise_state=d, loss_mask=lm, logprobs=behav,
        prox_logp=behav + rng.standard_normal(lm.shape).astype(np.float32)
        * np.float32(0.05) * lm,
        advantages=(sign[:, None] * lm).astype(np.float32),
    )
    return batch


def system_loss_and_grads(engine, actor, batch):
    """The engine's own ``grad_step`` over ``batch`` with no optimizer step:
    (loss, the gradient tree on the host), each the microbatches' by their
    weights as ``train_batches`` has them."""
    import jax

    from areal_tpu.engine import programs
    from areal_tpu.engine.ppo.actor import _ppo_loss_weight_fn

    mbs = engine._split(batch, backward=True)
    accum, losses, weights = engine._zero_grads(), [], []
    for mb, row_len in zip(mbs.mbs, mbs.row_lens):
        _, arrays = engine._pack_for_device(mb, pad_to=row_len)
        grad_fn = engine._program(
            ("grad", actor._loss_fn, _ppo_loss_weight_fn),
            programs.build_grad_step,
            remat=engine.config.gradient_checkpointing,
            loss_fn=actor._loss_fn, loss_weight_fn=_ppo_loss_weight_fn,
        )
        accum, loss, _, w = grad_fn(engine.params, accum, arrays)
        losses.append(loss)
        weights.append(w)
    accum, losses, weights = jax.device_get((accum, losses, weights))
    total = float(np.sum(weights))
    loss = float(np.dot(np.asarray(losses), np.asarray(weights)) / total)
    return loss, jax.tree_util.tree_map(lambda g: g / total, accum), list(
        mbs.row_lens
    )


def gradient_check(ref_config, params, batch, system, acfg):
    """``gradient_direction``: the system's (loss, gradients) against the
    reference's over the same sequences."""
    import jax

    lens = np.asarray(batch["attention_mask"]).sum(1)
    want_loss, want = reference_sdar.loss_and_grads(
        ref_config, params, [
            dict(tokens=batch["input_ids"][i, :n].tolist(),
                 states=batch["denoise_state"][i, :n],
                 behav_logp=batch["logprobs"][i, :n],
                 prox_logp=batch["prox_logp"][i, :n],
                 advantages=batch["advantages"][i, :n],
                 mask=batch["loss_mask"][i, :n])
            for i, n in enumerate(lens)
        ], eps_clip=acfg.eps_clip, behav_cap=acfg.behav_imp_weight_cap,
        temperature=acfg.temperature, remat=True,
    )
    loss, grads, _ = system
    dot = norm_s = norm_r = 0.0
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g = np.asarray(got[path], np.float64).ravel()
        w = np.asarray(w, np.float64).ravel()
        dot += float(g @ w)
        norm_s += float(g @ g)
        norm_r += float(w @ w)
    return {
        "loss": loss, "reference_loss": float(want_loss),
        "loss_abs_diff": abs(loss - float(want_loss)),
        "cosine": dot / math.sqrt(norm_s * norm_r),
        "norm": math.sqrt(norm_s), "reference_norm": math.sqrt(norm_r),
        "tokens": int(np.asarray(batch["loss_mask"]).sum()),
    }


def edge_check(attend, t, heads, d, size, seed, dtype):
    """``mask_exact`` (b) on one packed row of the program's own layout (three
    sequences, two states) at ``t`` slots: move v at one clean key and at one
    noisy key of the middle sequence, and compare WHICH slots' outputs change
    with the mask's statement of who sees that key."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.utils import data as data_utils

    rng = np.random.default_rng(seed)
    total = t // 3  # slots a sequence, about: n + 2 (n - p) with p = n / 4
    lens = [int(total / 2.6) + k for k in (3, 1, 2)]
    prompts = [n // 4 + 1 for n in lens]
    batch = with_states(
        data_utils.pad_sequences_to_tensors(
            [np.zeros(n, np.int32) for n in lens]
        ), prompts, size, 2, rng,
    )
    packed = data_utils.pack_batch_rows(batch, 1, pad_to=t, bd=(size, 1))
    seg, pos = packed.segment_ids, packed.positions
    stream = packed.per_token["bd_stream"]
    blk = pos // size
    hq, hkv = heads
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    q = jax.random.normal(keys[0], (1, t, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (1, t, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (1, t, hkv, d), jnp.float32).astype(dtype)
    rows = tuple(jnp.asarray(a) for a in (seg, blk, stream))
    run = jax.jit(lambda v_: attend(q, k, v_, *rows))
    base = np.asarray(run(v).astype(jnp.float32))
    mid = (seg[0] == 2)
    b_mid = int(blk[0][mid].max()) // 2
    out = {}
    for name, u in (("clean", 0), ("noisy", 2)):
        at = int(np.nonzero(mid & (stream[0] == u) & (blk[0] == b_mid))[0][0])
        moved = np.asarray(
            run(v.at[0, at].add(4.0)).astype(jnp.float32)
        )
        changed = (moved != base).any(axis=(0, 2, 3))
        if u == 0:
            want = mid & np.where(
                stream[0] == 0, blk[0] >= b_mid, blk[0] > b_mid
            )
        else:
            want = mid & (stream[0] == u) & (blk[0] == b_mid)
        out[name] = {
            "key": at, "block": b_mid, "expected": int(want.sum()),
            "changed": int(changed.sum()),
            "exact": bool((changed == want).all()),
        }
    out["ok"] = all(o["exact"] and o["expected"] > 0 for o in out.values())
    return out


def core_check(attend, ref_config, kept, lens, t, dtype):
    """``mask_exact`` (a): each layer's q, k, v of every check sequence and
    state as the REFERENCE has them (``kept[i][s]``: a layer's three arrays
    [2n, heads, Dh] already rounded to ``dtype``), all sequences that fit in
    one row of ``t`` slots in the reference's own order ([x_0 ; x_s] a
    sequence), through the timed core's instance, against the reference's
    masked softmax of the same rounded values. The largest absolute
    difference."""
    import jax
    import jax.numpy as jnp

    size = reference_sdar.block_length(ref_config)
    fits, used = [], 0
    for i, n in enumerate(lens):  # the sequences that fit one row together
        if used + 2 * n <= t:
            fits.append(i)
            used += 2 * n
    seg = np.zeros(t, np.int32)
    blk = np.zeros(t, np.int32)
    stream = np.zeros(t, np.int32)
    at, spans = 0, []
    for j, i in enumerate(fits):
        n = lens[i]
        seg[at:at + 2 * n] = j + 1
        blk[at:at + 2 * n] = np.tile(np.arange(n) // size, 2)
        stream[at + n:at + 2 * n] = 1
        spans.append((at, n))
        at += 2 * n
    rows = tuple(jnp.asarray(a[None]) for a in (seg, blk, stream))
    run = jax.jit(lambda q, k, v: attend(q, k, v, *rows))

    @functools.partial(jax.jit, static_argnums=3)
    def softmax(q, k, v, n):  # the reference's, a program a length
        with jax.default_matmul_precision("highest"):
            return reference_sdar.masked_softmax_attention(
                ref_config, q, k, v, n, reference_sdar.FORWARD_BLOCK
            )

    n_states = max(len(kept[i]) for i in fits)
    worst, compared = 0.0, 0
    for s in range(n_states):
        for layer in range(ref_config["num_hidden_layers"]):
            parts = [kept[i][s][layer] for i in fits]
            full = []
            for w in range(3):
                a = np.zeros((t,) + parts[0][w].shape[1:], parts[0][w].dtype)
                for (start, n), p in zip(spans, parts):
                    a[start:start + 2 * n] = p[w]
                full.append(jnp.asarray(a[None]))
            got = np.asarray(run(*full).astype(jnp.float32))[0]
            for (start, n), p in zip(spans, parts):
                want = np.asarray(softmax(
                    *(jnp.asarray(a).astype(jnp.float32) for a in p), n
                ))
                diff = np.abs(
                    got[start:start + 2 * n].reshape(2 * n, -1) - want
                )
                worst = max(worst, float(diff.max()))
                compared += diff.size
    return {"max_abs_diff": worst, "compared": compared,
            "sequences": len(fits), "states": n_states}


def run(ctx):
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.ops.ring_attention import attention_fn
    from areal_tpu.utils import data as data_utils

    p = ctx.traffic
    model_config, ref_config = (
        tiny_configs() if ctx.rehearse
        else (ctx.model_config, ctx.reference_config)
    )
    acfg = actor_config(p, ctx.rehearse)
    engine = SPMDTrainEngine(acfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, p["batch_seqs"] * 10000, p["batch_seqs"]),
        model_config=model_config, seed=p["layout_seed"] % (2 ** 31),
    )
    actor = PPOActor(acfg, engine)
    chips = int(engine.mesh.size)
    harness.log(
        f"trainer up: dtype={acfg.dtype} param_dtype={acfg.param_dtype} "
        f"attn_impl={engine.attn_impl} moe_impl={engine.moe_impl} "
        f"mesh={chips} max_tokens_per_mb="
        f"{acfg.mb_spec.max_tokens_per_mb} minibatches={acfg.ppo_n_minibatches}"
    )
    # the mask token is never drawn as a token
    vocab = model_config.vocab_size - 1
    size = model_config.block_diffusion.block_length
    n_states = int(p["denoise_states"])
    layout = np.random.default_rng(p["layout_seed"] + 1)
    batches = []
    for batch in layout_batches(p, vocab, ctx.seed):
        prompts = np.asarray(batch["loss_mask"]).argmax(1)
        b = with_states(batch, prompts, size, n_states, layout)
        b["_prompt_lens"] = prompts
        batches.append(b)
    dispatched = {"padded": 0, "real": 0}
    pack = engine._pack_for_device

    def counting_pack(mb, pad_to=None):
        packed, arrays = pack(mb, pad_to=pad_to)
        seg = np.asarray(packed.segment_ids)  # 0 = padding
        dispatched["padded"] += int(seg.size)
        dispatched["real"] += int(np.count_nonzero(seg))
        return packed, arrays

    engine._pack_for_device = counting_pack

    def step(b):
        batch = {k: v for k, v in batches[b].items() if not k.startswith("_")}
        np.random.seed(p["layout_seed"] + b)  # the same minibatch split
        with harness.span("train.compute_advantages"):
            batch = actor.compute_advantages(batch)
        with harness.span("train.ppo_update"):
            return actor.ppo_update(batch)

    # the check's sequences: lengths and states the traffic file's, tokens
    # the seed's; a quarter of each the prompt
    rng = np.random.default_rng(ctx.seed + 11)
    seqs = [
        rng.integers(0, vocab, size=int(n)).astype(np.int32)
        for n in p["check_lens"]
    ]
    check_prompts = [len(s) // 4 for s in seqs]
    check_layout = np.random.default_rng(p["layout_seed"] + 2)
    states_of = [
        trace_states(pl, len(s), size, n_states, check_layout)
        for pl, s in zip(check_prompts, seqs)
    ]

    def logp_of(sequences, states):
        batch = data_utils.pad_sequences_to_tensors(sequences)
        d = np.full(batch["input_ids"].shape, -1, np.int32)
        for i, s in enumerate(states):
            d[i, :len(s)] = s
        batch["denoise_state"] = d
        batch["loss_mask"] = (d >= 0).astype(np.int32)
        return actor.compute_logp(batch)

    def among_a_batch():
        """The check's sequences among the first batch's, in full rows."""
        first = batches[0]
        others = [
            np.asarray(first["input_ids"][i, :n], np.int32)
            for i, n in enumerate(first["_seq_lens"])
        ]
        other_states = [
            np.asarray(first["denoise_state"][i, :n])
            for i, n in enumerate(first["_seq_lens"])
        ]
        at = [(i + 1) * len(others) // (len(seqs) + 1) for i in range(len(seqs))]
        mixed, mixed_states = list(others), list(other_states)
        for k, i in enumerate(at):
            mixed.insert(i + k, seqs[k])
            mixed_states.insert(i + k, states_of[k])
        got = logp_of(mixed, mixed_states)
        return [got[i + k, :len(seqs[k])] for k, i in enumerate(at)]

    for b in range(len(batches)):  # warm-up: every packed shape compiles
        step(b)
    dispatched.update(padded=0, real=0)

    counters0 = engine.counters()
    compile_mark = ctx.watch.mark()
    ctx.mark_window_start()
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    t0 = time.monotonic()
    n_steps, tokens, flops, all_stats, step_s = 0, 0, 0.0, [], []
    expect = {"bd_pairs": 0, "bd_causal_pairs": 0, "bd_scored_tokens": 0}
    at_trace_end = {}

    def window_steps():
        k = 0
        while k == 0 or time.monotonic() - t0 < ctx.seconds:
            yield from pass_order(len(batches), ctx.seed, k)
            k += 1

    def measured_step(b):
        nonlocal n_steps, tokens, flops
        t = time.monotonic()
        all_stats.extend(step(b))
        step_s.append(round(time.monotonic() - t, 3))
        lens, prompts = batches[b]["_seq_lens"], batches[b]["_prompt_lens"]
        n_steps += 1
        tokens += int(lens.sum())  # a sequence's own tokens, once
        flops += arith_sdar.train_step_flops(
            ref_config, prompts, lens, n_states
        )
        for pl, n in zip(prompts, lens):
            expect["bd_pairs"] += arith_sdar.kept_pairs(pl, n, n_states, size)
            expect["bd_causal_pairs"] += arith_sdar.causal_pairs(n)
            expect["bd_scored_tokens"] += int(n) - int(pl)

    steps = window_steps()
    with traced:  # a traced run profiles its first steps only
        for _ in range(p["trace_steps"] if ctx.trace else 0):
            measured_step(next(steps))
        at_trace_end = harness.counter_delta(engine.counters(), counters0)
    for b in steps:
        measured_step(b)
    window_s = time.monotonic() - t0 - traced.profiler_s
    compiles_in_window, loads_in_window = ctx.watch.since(compile_mark)
    counted = harness.counter_delta(engine.counters(), counters0)
    sent = dict(dispatched)  # the window's own; the checks dispatch more
    if not sent["padded"]:
        raise RuntimeError(
            "no microbatch went through SPMDTrainEngine._pack_for_device: "
            "pad_share.train has lost its hook"
        )
    bad = [
        s for s in all_stats
        if not (math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"])
                and s["update_successful"] == 1.0)
    ]
    row_len = max(engine._split(
        {k: v for k, v in batches[0].items() if not k.startswith("_")}, True
    ).row_lens)

    # (a) compute_logp against the plain reference and (b) against itself in
    # other company, outside the window, on the weights the window left;
    # then the state leaves the chip and the reference has it to itself
    alone = logp_of(seqs, states_of)
    packed_logp = among_a_batch()
    mem_peak = harness.memory_peak_bytes()
    short = [
        i for i, s in enumerate(seqs) if len(s) <= p["grad_check_max_len"]
    ]
    grad_batch = gradient_inputs(
        [seqs[i] for i in short], [states_of[i] for i in short], vocab,
        ctx.seed + 17,
    )
    if acfg.c_clip is not None or acfg.eps_clip_higher is not None:
        raise SystemExit(
            "gradient_direction: the reference's loss has no dual or "
            "asymmetric clip, and the recipe now sets one"
        )
    system_grads = system_loss_and_grads(engine, actor, grad_batch)
    params = jax.device_get(engine.params)
    compute_dtype = engine.compute_dtype
    attend = attention_fn(
        engine.attn_impl, engine.mesh, block_cap=engine.splash_block or 0
    )
    engine.destroy()
    del actor, engine
    gc.collect()
    system, ref, kept = [], [], []
    for i, s in enumerate(seqs):
        scored = states_of[i] >= 0  # every completion token, at its own slot
        system.append(np.asarray(alone[i, :len(s)], np.float64)[scored])
        want, kept_i = np.zeros(len(s), np.float64), []
        for state in range(int(states_of[i].max()) + 1):
            qkv = []  # ONE reference forward a state: its logprobs, its q, k, v
            lp = reference_sdar.state_logprobs(
                ref_config, params, s.tolist(), states_of[i], state,
                acfg.temperature, keep=qkv,
            )
            want = np.where(states_of[i] == state, np.asarray(lp), want)
            kept_i.append([
                [np.asarray(a.astype(compute_dtype)) for a in layer]
                for layer in qkv
            ])
        ref.append(want[scored])
        kept.append(kept_i)
        packed_logp[i] = np.asarray(packed_logp[i], np.float64)[scored]
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    gradient = gradient_check(
        ref_config, params, grad_batch, system_grads, acfg
    )
    gradient["row_lens"] = system_grads[2]
    gradient["limits"] = (
        REHEARSE_GRADIENT if ctx.rehearse
        else ctx.cell.config["gradient_limits"]
    )
    gradient["ok"] = bool(
        gradient["cosine"] >= gradient["limits"]["cosine"]
        and gradient["loss_abs_diff"] <= gradient["limits"]["loss_abs_diff"]
    )
    del params, system_grads
    gc.collect()
    core = core_check(
        attend, ref_config, kept, [len(s) for s in seqs], row_len,
        compute_dtype,
    )
    del kept
    gc.collect()
    core["limit"] = (
        REHEARSE_CORE if ctx.rehearse
        else ctx.cell.config["mask_limits"]["core_max_abs_diff"]
    )
    edges = edge_check(
        attend, row_len,
        (model_config.num_heads, model_config.num_kv_heads),
        model_config.head_dim, size, ctx.seed + 13, compute_dtype,
    )
    counts_ok = all(counted.get(k) == v for k, v in expect.items()) and (
        counted.get("bd_sequence_tokens", 0) + counted.get("bd_stream_slots", 0)
        == counted.get("real_tokens") == sent["real"]
    )
    mask_exact = {
        "core": core, "edges": edges, "window_counts_expected": expect,
        "counts_ok": bool(counts_ok),
    }
    mask_ok = (
        core["max_abs_diff"] <= core["limit"] and core["compared"] > 0
        and edges["ok"] and counts_ok
    )

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(sent["padded"]),
        "dispatched_real_tokens": float(sent["real"]),
    }
    for k in ("real_tokens", "bd_pairs", "bd_causal_pairs",
              "bd_scored_tokens", "bd_sequence_tokens", "bd_stream_slots",
              "moe_assignments", "moe_max_expert_assignments",
              "moe_stream_rows"):
        if counted.get(k):
            counters[k] = float(counted[k])
    if counted.get("moe_assignments"):
        counters["moe_mean_expert_assignments"] = (
            counted["moe_assignments"] / ref_config["num_local_experts"]
        )
    ckpt = acfg.gradient_checkpointing
    obs = harness.Observation(
        window_s=window_s, setup_s=ctx.setup_s, counters=counters,
        values={
            "bd_core_flops_traced": arith_sdar.bd_core_kernel_flops(
                ref_config, at_trace_end.get("bd_pairs", 0)
            ),
            "moe_expert_flops_traced_per_chip": (
                arith_sdar.expert_kernel_flops(
                    ref_config, at_trace_end.get("moe_assignments", 0), ckpt
                ) / chips
            ),
        },
        trace=traced.reduce(), peaks=ctx.peaks,
    )
    return harness.Outcome(
        obs=obs, attempted=n_steps, failed=len(bad) and n_steps,
        checks={
            "finite_loss_and_grad_norm": not bad and n_steps > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "reference_agrees": cmp["ok"],
            "packing_invariant": packing["ok"],
            "gradient_direction": gradient["ok"],
            "mask_exact": bool(mask_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips, "row_len": row_len,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "gradient": gradient,
               "mask_exact": mask_exact,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": sent["real"],
               "dispatched_padded_tokens": sent["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
