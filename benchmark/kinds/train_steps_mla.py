"""Trainer steps of a model whose every layer's mixer is latent attention with
a compressed query and a ROTATED shared key part (heads of 192 | 128), a
leading dense FFN over a chip's share of 256 sigmoid-routed experts and a
shared one, a slice of the vocabulary, and the model's own draft module
(multi-token prediction at depth 1) trained beside the GRPO loss.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window), as
``kinds/train_steps_gdn.py`` takes it. ``run`` is one function there and
cannot be imported in parts, so the window loop is restated here in the same
order. What differs from that kind:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_joyai.py``, ``benchmark/arith_joyai.py``); the
  reference reads the weights in the PUBLISHED layout (its rotation pairs
  channels 2j and 2j + 1; ``reference.published_layout`` permutes the rope
  columns of the program's tree, which turns them rotate-half);
- a rehearsal builds the program's tiny configuration of the family itself;
- ``mtp_exact`` in place of ``scan_exact``: the program's own draft-module
  function, the one ``grad_step`` calls (``models/transformer.draft_forward``
  under ``ops/functional.draft_aligned_logprobs``, the engine's attention,
  the compute dtype), fed the REFERENCE's final-normed trunk output of the
  check's sequences, gives log p_draft(t_{i+2}) within the configuration's
  ``mtp_limits`` of the reference's, each sequence ALONE in a row and all of
  them PACKED in one (a shift that read across a border would fail the
  second); the window's ``mtp_tokens`` are exactly what the trained batches'
  loss masks make them (a response token is a draft target once a pass);
  and every step's ``mtp_loss`` is finite beside its loss and grad-norm;
- the arithmetic counts the recompute forwards the chip runs, (n - 1) / n of
  one a trained token at n minibatches, in which the module does NOT run,
  and hands the cores their causal pairs from the trained lengths
  (``attn_full_flops_traced``: the program counts none for an ``mla``
  mixer), beside ``moe_expert_flops_traced_per_chip`` with the module's
  block's pairs (``mtp_assignments``) counted for the gradient pass alone.
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_joyai as arith
from benchmark import harness
from benchmark import reference_joyai as reference
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "joyai_llm_flash"


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal: the keys are the program's own
    rendering of that configuration as a ``config.json``."""
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf_io import default_hf_config_dict

    t = tiny_config(FAMILY)
    return t, default_hf_config_dict(t)


def _row(seqs, total):
    """``seqs`` packed into ONE row of ``total`` tokens: tokens, segment ids
    (0 = padding) and positions [1, total], and where each starts."""
    tok, seg, pos = (np.zeros((1, total), np.int32) for _ in range(3))
    starts, at = [], 0
    for i, s in enumerate(seqs):
        n = len(s)
        tok[0, at:at + n], seg[0, at:at + n] = s, i + 1
        pos[0, at:at + n] = np.arange(n)
        starts.append(at)
        at += n
    return tok, seg, pos, starts


def draft_check(model_config, ref_config, params, published, seqs, hidden,
                compute_dtype, attend_fn, limits):
    """The program's draft-module function on the reference's trunk output
    ``hidden`` (a [T, D] float32 a sequence), alone and packed, against the
    reference's draft logprobs. Returns what ``mtp_exact`` decides on."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from areal_tpu.ops import functional
    from areal_tpu.utils.data import ROW_QUANTUM

    held = {
        k: jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).astype(compute_dtype), params[k]
        )
        for k in ("embedding", "lm_head", "mtp")
    }

    @jax.jit
    def program(p, x, tok, seg, pos):
        logits, _ = transformer.draft_forward(
            p, model_config, x, tok, seg, pos, remat=False,
            attend_fn=attend_fn,
        )
        return functional.draft_aligned_logprobs(
            logits, {"tokens": tok, "segment_ids": seg}
        )

    slots_wrong = []  # (the row's sequences, the one whose targets are off)

    def whole_rows(n):
        return -(-n // ROW_QUANTUM) * ROW_QUANTUM

    def run(group, total):
        tok, seg, pos, starts = _row([seqs[i] for i in group], total)
        x = np.zeros((1, tok.shape[1], hidden[0].shape[-1]), np.float32)
        for i, at in zip(group, starts):
            x[0, at:at + len(seqs[i])] = hidden[i]
        logp, has = program(
            held, jnp.asarray(x).astype(compute_dtype), tok, seg, pos
        )
        logp, has = np.asarray(logp, np.float64), np.asarray(has)
        out = []
        for i, at in zip(group, starts):
            n = len(seqs[i])
            # a sequence's last two slots have no target, and say so
            mine = has[0, at:at + n]
            if not mine[:n - 2].all() or mine[n - 2:].any():
                slots_wrong.append((group, i))
            out.append(logp[0, at:at + n - 2])
        return out

    want = [
        reference.draft_token_logprobs(ref_config, published, s.tolist(), x)
        for s, x in zip(seqs, hidden)
    ]
    # one row length for every sequence alone: one compiled program
    longest = whole_rows(max(len(s) for s in seqs))
    alone = [run([i], longest)[0] for i in range(len(seqs))]
    packed = run(
        list(range(len(seqs))), whole_rows(sum(len(s) for s in seqs))
    )
    return {
        "alone": harness.compare_logprobs(alone, want, limits),
        "packed": harness.compare_logprobs(packed, want, limits),
        "slots_wrong": slots_wrong,
    }


def run(ctx):
    import jax

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
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
        model_config=model_config, seed=ctx.seed % (2 ** 31),
    )
    actor = PPOActor(acfg, engine)
    chips = int(engine.mesh.size)
    harness.log(
        f"trainer up: dtype={acfg.dtype} param_dtype={acfg.param_dtype} "
        f"attn_impl={engine.attn_impl} moe_impl={engine.moe_impl} "
        f"mesh={chips} max_tokens_per_mb={acfg.mb_spec.max_tokens_per_mb} "
        f"minibatches={acfg.ppo_n_minibatches} "
        f"mtp_loss_coef={model_config.mtp_loss_coef}"
    )
    vocab = model_config.vocab_size
    n_mb = acfg.ppo_n_minibatches
    batches = make_batches(p, vocab, ctx.seed)
    # as train_steps: what is dispatched, real tokens and padding, counted
    # from the packed rows themselves where every microbatch is packed
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

    # the check's sequences: lengths the traffic file's, tokens the seed's
    rng = np.random.default_rng(ctx.seed + 11)
    seqs = [
        rng.integers(0, vocab, size=int(n)).astype(np.int32)
        for n in p["check_lens"]
    ]

    def logp_of(sequences):
        batch = data_utils.pad_sequences_to_tensors(sequences)
        batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
        return actor.compute_logp(batch)

    def among_a_batch():
        """The check's sequences among the first batch's, in full rows."""
        others = [
            np.asarray(batches[0]["input_ids"][i, :n], np.int32)
            for i, n in enumerate(batches[0]["_seq_lens"])
        ]
        at = [(i + 1) * len(others) // (len(seqs) + 1) for i in range(len(seqs))]
        mixed = list(others)
        for k, i in enumerate(at):
            mixed.insert(i + k, seqs[k])
        got = logp_of(mixed)
        return [got[i + k, 1:len(seqs[k])] for k, i in enumerate(at)]

    for b in range(len(batches)):  # warm-up: every packed shape compiles
        step(b)
    dispatched.update(padded=0, real=0)

    counters0 = engine.counters()
    compile_mark = ctx.watch.mark()
    ctx.mark_window_start()
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    t0 = time.monotonic()
    n_steps, tokens, flops, all_stats, step_s = 0, 0, 0.0, [], []
    # from the batches: a response token two or more into its sequence is
    # the module's target once a pass
    expected = {"mtp_tokens": 0}
    core_pairs = 0  # ONE core's causal pairs, from the lengths
    core_pairs_at_trace_end = 0
    at_trace_end = {}

    def window_steps():
        k = 0
        while k == 0 or time.monotonic() - t0 < ctx.seconds:
            yield from pass_order(len(batches), ctx.seed, k)
            k += 1

    def measured_step(b):
        nonlocal n_steps, tokens, flops, core_pairs
        t = time.monotonic()
        all_stats.extend(step(b))
        step_s.append(round(time.monotonic() - t, 3))
        lens = batches[b]["_seq_lens"]
        n_steps += 1
        tokens += int(lens.sum())
        flops += arith.train_step_flops(ref_config, lens, n_mb)
        expected["mtp_tokens"] += int(batches[b]["loss_mask"][:, 2:].sum())
        core_pairs += sum(arith.causal_pairs(n) for n in lens)

    steps = window_steps()
    with traced:  # a traced run profiles its first steps only
        for _ in range(p["trace_steps"] if ctx.trace else 0):
            measured_step(next(steps))
        at_trace_end = harness.counter_delta(engine.counters(), counters0)
        core_pairs_at_trace_end = core_pairs
    for b in steps:
        measured_step(b)
    window_s = time.monotonic() - t0 - traced.profiler_s
    compiles_in_window, loads_in_window = ctx.watch.since(compile_mark)
    counted = harness.counter_delta(engine.counters(), counters0)
    if not dispatched["padded"]:
        raise RuntimeError(
            "no microbatch went through SPMDTrainEngine._pack_for_device: "
            "pad_share.train has lost its hook"
        )
    bad = [
        s for s in all_stats
        if not (math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"])
                and math.isfinite(s.get("mtp_loss", math.nan))
                and s["update_successful"] == 1.0)
    ]

    # (a) compute_logp against the plain reference and (b) against itself in
    # other company, outside the window, on the weights the window left;
    # then the state leaves the chip and the reference has it to itself
    alone = logp_of(seqs)
    packed_logp = among_a_batch()
    mem_peak = harness.memory_peak_bytes()
    params = jax.device_get(engine.params)
    compute_dtype = engine.compute_dtype
    attend_fn = engine._forward_values(0)["attend_fn"]  # the engine's core
    engine.destroy()
    del actor, engine
    gc.collect()
    published = reference.published_layout(ref_config, params)
    system, ref, hidden = [], [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(alone[i, 1:len(s)], np.float64))
        hidden.append(np.asarray(
            reference.hidden(ref_config, published, s.tolist())
        ))
        ref.append(reference.token_logprobs(
            ref_config, published, s.tolist(), acfg.temperature, hidden[-1]
        ))
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    mtp = draft_check(
        model_config, ref_config, params, published, seqs, hidden,
        compute_dtype, attend_fn,
        ctx.logprob_limits if ctx.rehearse else ctx.cell.config["mtp_limits"],
    )
    mtp["window_expected"] = expected
    mtp["loss_first_last"] = [
        s.get("mtp_loss") for s in (all_stats[:1] + all_stats[-1:])
    ]
    mtp_ok = (
        mtp["alone"]["ok"] and mtp["packed"]["ok"] and not mtp["slots_wrong"]
        and all(counted.get(k) == v for k, v in expected.items())
    )
    del params, published, hidden
    gc.collect()

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    for k in ("moe_assignments", "moe_max_expert_assignments",
              "moe_stream_rows", "moe_overflow_chunks", "mtp_tokens",
              "mtp_assignments"):
        if counted.get(k):
            counters[k] = float(counted[k])
    if counted.get("moe_assignments"):
        counters["moe_mean_expert_assignments"] = (
            counted["moe_assignments"] / ref_config["num_local_experts"]
        )
    values = {}
    if core_pairs_at_trace_end:
        values["attn_full_flops_traced"] = arith.core_kernel_flops(
            ref_config, core_pairs_at_trace_end, n_mb
        )
    if at_trace_end.get("moe_assignments"):
        values["moe_expert_flops_traced_per_chip"] = (
            arith.expert_kernel_flops(
                ref_config, at_trace_end["moe_assignments"],
                at_trace_end.get("mtp_assignments", 0),
                acfg.gradient_checkpointing, n_mb,
            ) / chips
        )
    obs = harness.Observation(
        window_s=window_s, setup_s=ctx.setup_s, counters=counters,
        values=values, trace=traced.reduce(), peaks=ctx.peaks,
    )
    return harness.Outcome(
        obs=obs, attempted=n_steps, failed=len(bad) and n_steps,
        checks={
            "finite_loss_and_grad_norm": not bad and n_steps > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "reference_agrees": cmp["ok"],
            "packing_invariant": packing["ok"],
            "mtp_exact": bool(mtp_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "mtp": mtp,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
