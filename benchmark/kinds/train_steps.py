"""Trainer steps: ``SPMDTrainEngine`` + ``PPOActor`` with the trainer settings
of the program's own shipped recipe (the traffic file names the yaml and its
section; the cell sets sizes only). One step is what
``examples/gsm8k_grpo.py`` runs between receiving a batch and pushing weights:
``compute_advantages`` then ``ppo_update`` (which recomputes the proximal
logprobs first).

A few seeded batches are replayed. Their sequence lengths are the length
law's pool in a FIXED arrangement (``layout_seed``), so every seed packs to
the same microbatch shapes and finds every program in the cache; ``--seed``
draws the weights, the token ids, the rewards, the behaviour logprobs and the
order of the batches in each pass. The batches differ in tokens and padding,
so the window runs WHOLE PASSES over them and ends at the end of the first
pass that passes ``--seconds``: every seed and every run then trains the same
multiset of batches, in another order, and the rate divides by the actual
elapsed time. (With whole steps only, six steps over four batches made seeds
differ by 1.7% where two runs of one seed differed by 0.04%: PERF.md, PR 24.)
Warm-up is one pass in the file's order.
"""

import math
import os
import time

import numpy as np

from benchmark import arith, harness, lengths, reference


# what a traffic file may set under "sizes" (dotted, inside the section)
SIZE_KEYS = ("mb_spec.max_tokens_per_mb",)


def make_batches(p, vocab, seed):
    """``n_batches`` padded GRPO batches: groups of ``group_size`` samples
    share a prompt; lengths fixed by ``layout_seed``, contents by ``seed``."""
    from areal_tpu.utils import data as data_utils

    n_seq, g, nb = p["batch_seqs"], p["group_size"], p["n_batches"]
    layout = np.random.default_rng(p["layout_seed"])
    outs = lengths.ordered(p["output_len"], nb * n_seq, layout)
    prompts = lengths.ordered(p["prompt_len"], nb * n_seq // g, layout)
    rng = np.random.default_rng(seed)
    batches = []
    for b in range(nb):
        seqs, plens = [], []
        for i in range(n_seq):
            gi = (b * n_seq + i) // g
            if i % g == 0:
                prompt = rng.integers(0, vocab, size=int(prompts[gi]))
            out = rng.integers(0, vocab, size=int(outs[b * n_seq + i]))
            seqs.append(np.concatenate([prompt, out]).astype(np.int32))
            plens.append(len(prompt))
        batch = data_utils.pad_sequences_to_tensors(seqs)
        lm = np.zeros_like(batch["input_ids"], np.int32)
        for i, s in enumerate(seqs):
            lm[i, plens[i]:len(s)] = 1
        batch["loss_mask"] = lm
        # behaviour logprobs where random weights put them (about
        # -log vocab): far from the policy's own, the decoupled loss's
        # importance weight would vanish and the gradient with it
        batch["logprobs"] = (
            rng.standard_normal(lm.shape).astype(np.float32) * 0.1
            - np.float32(math.log(vocab))
        ) * lm
        batch["versions"] = np.where(lm > 0, 0, -1).astype(np.int32)
        # siblings of a group must disagree, or every advantage is zero
        rewards = np.zeros(n_seq, np.float32)
        for k in range(0, n_seq, g):
            r = rng.integers(0, 2, size=g).astype(np.float32)
            r[0], r[-1] = 0.0, 1.0
            rewards[k:k + g] = r
        batch["rewards"] = rewards
        batch["_seq_lens"] = np.asarray([len(s) for s in seqs])
        batches.append(batch)
    return batches


def pass_order(n_batches, seed, k):
    """The batches of pass ``k`` of the window, in the seed's order."""
    return [int(b) for b in
            np.random.default_rng([int(seed), int(k)]).permutation(n_batches)]


def actor_config(p, rehearse):
    """The trainer settings of the program's shipped recipe."""
    from areal_tpu.api import cli_args

    cfg, _ = cli_args.load_expr_config(
        ["--config", os.path.join(harness.ROOT, p["recipe"]["file"])],
        getattr(cli_args, p["recipe"]["config_class"]),
    )
    acfg = getattr(cfg, p["recipe"]["section"])
    acfg.path = ""  # weights come from the seed, not from a checkpoint
    for key, value in p.get("sizes", {}).items():
        if key not in SIZE_KEYS:
            raise SystemExit(f"a cell sets sizes only; not a size: {key}")
        cli_args.apply_override(acfg, key, str(value))
    if rehearse:
        acfg.dtype = "float32"
    return acfg


def run(ctx):
    import gc

    import jax

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine

    p = ctx.traffic
    acfg = actor_config(p, ctx.rehearse)
    engine = SPMDTrainEngine(acfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, p["batch_seqs"] * 10000, p["batch_seqs"]),
        model_config=ctx.model_config, seed=ctx.seed % (2 ** 31),
    )
    actor = PPOActor(acfg, engine)
    harness.log(
        f"trainer up: dtype={acfg.dtype} param_dtype={acfg.param_dtype} "
        f"attn_impl={acfg.attn_impl} max_tokens_per_mb="
        f"{acfg.mb_spec.max_tokens_per_mb} minibatches={acfg.ppo_n_minibatches}"
    )
    vocab = ctx.model_config.vocab_size
    batches = make_batches(p, vocab, ctx.seed)
    # a span of the benchmark's around the one place every microbatch is
    # packed (logprob recompute and train alike): what is dispatched, real
    # tokens and padding, counted from the packed rows themselves
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
        # the minibatch split inside ppo_update draws from numpy's global
        # generator: the same split for batch b in every pass and every seed
        np.random.seed(p["layout_seed"] + b)
        with harness.span("train.compute_advantages"):
            batch = actor.compute_advantages(batch)
        with harness.span("train.ppo_update"):
            return actor.ppo_update(batch)

    for b in range(len(batches)):  # warm-up: every packed shape compiles
        step(b)
    dispatched.update(padded=0, real=0)

    compile_mark = ctx.watch.mark()
    ctx.mark_window_start()
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    t0 = time.monotonic()
    n_steps, tokens, flops, all_stats, step_s = 0, 0, 0.0, [], []

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
        n_steps += 1
        tokens += int(batches[b]["_seq_lens"].sum())
        flops += arith.train_step_flops(
            ctx.reference_config, batches[b]["_seq_lens"]
        )

    steps = window_steps()
    with traced:  # a traced run profiles its first steps only
        for _ in range(p["trace_steps"] if ctx.trace else 0):
            measured_step(next(steps))
    for b in steps:
        measured_step(b)
    # a traced run's window leaves out the profiler's own start and stop
    # (about 11 s for two steps), in which no step runs
    window_s = time.monotonic() - t0 - traced.profiler_s
    compiles_in_window, loads_in_window = ctx.watch.since(compile_mark)
    if not dispatched["padded"]:
        raise RuntimeError(
            "no microbatch went through SPMDTrainEngine._pack_for_device: "
            "pad_share.train has lost its hook"
        )
    padded_tokens, real_tokens = dispatched["padded"], dispatched["real"]
    bad = [
        s for s in all_stats
        if not (math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"])
                and s["update_successful"] == 1.0)
    ]

    # (a) compute_logp against the plain reference, outside the window. The
    # lengths are the traffic file's, the same for every seed (the reference
    # compiles per length: a seed that drew new ones spent minutes on it
    # after its window); the seed draws the tokens
    rng = np.random.default_rng(ctx.seed + 11)
    seqs = [
        rng.integers(0, vocab, size=int(n)).astype(np.int32)
        for n in p["check_lens"]
    ]
    from areal_tpu.utils import data as data_utils

    check = data_utils.pad_sequences_to_tensors(seqs)
    check["loss_mask"] = check["attention_mask"].astype(np.int32)
    got = actor.compute_logp(check)
    params = engine.params
    system, ref = [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(got[i, 1:len(s)], np.float64))
        ref.append(reference.token_logprobs(
            ctx.reference_config, params, s.tolist(), acfg.temperature
        ))
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    mem_peak = harness.memory_peak_bytes()
    engine.destroy()
    del actor, engine, params
    gc.collect()

    obs = harness.Observation(
        window_s=window_s, setup_s=ctx.setup_s,
        counters={"trained_tokens": float(tokens), "train_flops": flops,
                  "steps": float(n_steps),
                  "padded_tokens": float(padded_tokens),
                  "dispatched_real_tokens": float(real_tokens)},
        trace=traced.reduce(), peaks=ctx.peaks,
    )
    return harness.Outcome(
        obs=obs, attempted=n_steps, failed=len(bad) and n_steps,
        checks={
            "finite_loss_and_grad_norm": not bad and n_steps > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "reference_agrees": cmp["ok"],
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "trained_tokens": tokens, "dispatched_real_tokens": real_tokens,
               "dispatched_padded_tokens": padded_tokens,
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
