"""Trainer steps of a model of window and full attention layers whose every
layer has the same heads, at rows of 32,768 tokens: a 1,024-key window three
layers in four, the whole sequence (under yarn) the fourth, q and k normed a
head, on one chip's share of its experts and vocabulary.

``kinds/train_steps_swa.py`` restated for another configuration (that file
reads Laguna's reference and arithmetic by name and stays as it is; ``run``
is one function there and cannot be imported in parts): the traffic of
``kinds/train_steps.py`` (imported, not copied: ``make_batches``,
``pass_order``, ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window). What differs
from that kind:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_mellum.py``, ``benchmark/arith_mellum.py``); the
  arithmetic counts the forwards the chip runs: a step of n minibatches
  recomputes the proximal logprobs of minibatches 2..n, so a trained token
  costs 3 + (n - 1) / n forwards (``arith_mellum.train_step_flops``), and
  the kernels' needs follow the same count;
- the check's four sequences fill ONE row of the trainer's 32,768 tokens,
  the longest 20,480: the engine's state leaves the chip before the
  reference runs;
- ``window_exact`` (b) moves ``v`` at key ``EDGE_KEY`` of the longest check
  sequence (a third of the way in where the sequence is shorter: a
  rehearsal's);
- THE WEIGHTS ARE THE LAYOUT'S: the engine is initialised from the traffic
  file's ``layout_seed``, the one seed the traffic holds fixed for every
  ``--seed`` (the lengths, the minibatch split); ``--seed`` draws the token
  ids, the rewards, the behaviour logprobs, the order of the batches and the
  check's sequences. 8 of 64 experts are held and the experts are a tenth
  of the step, so how many (token, expert) pairs are this chip's is part of
  how much work a window holds, and at random weights that is the luck of
  the draw: attention is nearly uniform there, a sequence's tokens share
  most of their residual stream, and a router sends them all the same way.
  Measured (my chip runs, PR 45, calls 3, 4 and 6): with every weight from
  ``--seed`` the held pairs read 0.82-1.04 of the expected 623k a step and
  ``trained_tokens_per_s`` 33,226-33,697 over three seeds; with the routers
  alone from ``layout_seed`` (PR 41's rule) 0.78-1.05 and 33,010-33,855
  over six, a quartile spread of 1.59% against the 0.5% a new cell is
  admitted at (0.69 us a held pair: the grouped matmuls at 43% of the
  peak); a forward-only probe of six rows under four seeds read 0.78-1.04
  with the routers fixed, 0.92-1.07 with every layer fixed and the
  embedding the seed's, 0.96-1.04 with every weight fixed: what is left
  then is the token ids' own draw.

``correct`` is that kind's: ``finite_loss_and_grad_norm``,
``no_compile_in_window``, ``reference_agrees``, ``packing_invariant`` (the
check's sequences read the same alone and among a batch's) and
``window_exact``: (a) given each window layer's q, k, v as the REFERENCE has
them, rounded to the dtype the program computes in, the program's timed core
(the attention the engine resolved, at the row the trainer packs) agrees
with the reference's masked softmax within the configuration's
``window_limits`` (mean and worst absolute difference over the mean and the
largest absolute output); (b) the edge, exactly: with ``v`` at one key s of
the longest check sequence replaced, the outputs of queries s .. s + window
- 1 change and every other query's is bit-for-bit what it was (s + window's
among them); (c) the window's ``swa_pairs`` and ``swa_causal_pairs`` are
exactly what the trained lengths make them. Counters of the window layers
and of the routing to the experts held (``swa_*``, ``moe_*`` from
``SPMDTrainEngine.counters()``; a program without them leaves them out) and
what the traced steps ask of the kernels (``attn_window_flops_traced``,
``attn_full_flops_traced``, ``moe_expert_flops_traced_per_chip``).
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_mellum, harness, reference_mellum
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "mellum"
EDGE_KEY = 2730  # the key whose value window_exact (b) moves
REHEARSE_WINDOW = {"mean": 1e-4, "worst": 1e-4}  # float32 against float32


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal: the keys are the program's own
    rendering of that configuration as a ``config.json``."""
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf_io import default_hf_config_dict

    t = tiny_config(FAMILY)
    return t, default_hf_config_dict(t)


def window_check(ref_config, inputs, compute_dtype, attn_impl, row_len):
    """The program's timed window core against the reference's masked
    softmax, window layer by window layer on the reference's own q, k, v
    (``inputs``: a list a check sequence of ``reference_mellum.
    logprobs_and_window_inputs``' second value; rounded to the compute dtype
    for both), and the window's edge on the longest sequence. Returns what
    ``window_exact`` decides on."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.basic import segment_attention
    from areal_tpu.ops.flash import flash_segment_attention

    window = ref_config["sliding_window"]
    if attn_impl == "flash":  # the kernel instance the trained rows ran
        core, pad_to = jax.jit(lambda q, k, v, seg: flash_segment_attention(
            q, k, v, seg, layer_window=window
        )), row_len
    else:
        core, pad_to = jax.jit(lambda q, k, v, seg: segment_attention(
            q, k, v, seg, window=window
        )), 0
    want_fn = jax.jit(
        lambda q, k, v: reference_mellum.masked_softmax_attention(
            q, k, v, window
        )
    )

    def program(q, k, v):
        n = q.shape[0]
        pad = max(pad_to - n, 0)
        seg = jnp.asarray([[1] * n + [0] * pad], jnp.int32)
        q, k, v = (
            jnp.pad(a, ((0, pad), (0, 0), (0, 0)))[None] for a in (q, k, v)
        )
        return np.asarray(core(q, k, v, seg)[0, :n], np.float64)

    abs_diff, abs_want, worst, largest, n_out, edge = 0.0, 0.0, 0.0, 0.0, 0, {}
    lens = [layers[0][0].shape[0] for layers in inputs]
    longest = lens.index(max(lens))
    for i, layers in enumerate(inputs):
        for layer, (q, k, v) in enumerate(layers):
            q, k, v = (jnp.asarray(a).astype(compute_dtype) for a in (q, k, v))
            got = program(q, k, v)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(want_fn(
                    *(a.astype(jnp.float32) for a in (q, k, v))
                ), np.float64)
            diff = np.abs(got - want)
            abs_diff += float(diff.sum())
            abs_want += float(np.abs(want).sum())
            worst = max(worst, float(diff.max()))
            largest = max(largest, float(np.abs(want).max()))
            n_out += diff.size
            if i == longest and layer == 0:
                at = min(EDGE_KEY, lens[i] // 3)
                moved = program(q, k, v.at[at].add(3.0))
                changed = np.abs(moved - got).max(axis=(1, 2)) > 0
                inside = changed[at:at + window]
                edge = {
                    "sequence": lens[i], "key": at, "window": window,
                    "queries_inside_changed": int(inside.sum()),
                    "queries_inside": int(inside.size),
                    "queries_outside_changed": int(
                        changed.sum() - inside.sum()
                    ),
                    "first_beyond_unchanged": bool(
                        at + window >= lens[i] or not changed[at + window]
                    ),
                }
    return {
        "compared": n_out,
        "layers": arith_mellum.mixer_layers(ref_config, "swa"),
        "mean_rel": abs_diff / abs_want if abs_want else float("nan"),
        "worst_rel": worst / largest if largest else float("nan"),
        "edge": edge, "impl": attn_impl, "row": pad_to,
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
        model_config=model_config, seed=p["layout_seed"],
    )
    actor = PPOActor(acfg, engine)
    chips = int(engine.mesh.size)
    harness.log(
        f"trainer up: dtype={acfg.dtype} param_dtype={acfg.param_dtype} "
        f"attn_impl={engine.attn_impl} moe_impl={engine.moe_impl} "
        f"layer_window={ref_config['sliding_window']} mesh={chips} "
        f"max_tokens_per_mb={acfg.mb_spec.max_tokens_per_mb} "
        f"minibatches={acfg.ppo_n_minibatches} weights drawn from "
        f"layout_seed {p['layout_seed']}"
    )
    vocab = model_config.vocab_size
    n_swa = arith_mellum.mixer_layers(ref_config, "swa")
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
    expected = {"swa_pairs": 0, "swa_causal_pairs": 0}  # from the lengths
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
        lens = batches[b]["_seq_lens"]
        n_steps += 1
        tokens += int(lens.sum())
        flops += arith_mellum.train_step_flops(ref_config, lens, n_mb)
        pairs = arith_mellum.attended_pairs(ref_config, lens)
        expected["swa_pairs"] += n_swa * pairs["swa"]
        expected["swa_causal_pairs"] += n_swa * pairs["full"]

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
    if not dispatched["padded"]:
        raise RuntimeError(
            "no microbatch went through SPMDTrainEngine._pack_for_device: "
            "pad_share.train has lost its hook"
        )
    bad = [
        s for s in all_stats
        if not (math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"])
                and s["update_successful"] == 1.0)
    ]

    # (a) compute_logp against the plain reference and (b) against itself in
    # other company, outside the window, on the weights the window left;
    # then the state leaves the chip and the reference has it to itself
    harness.log(f"window over: {n_steps} steps in {window_s:.1f} s")
    alone = logp_of(seqs)
    packed_logp = among_a_batch()
    mem_peak = harness.memory_peak_bytes()
    params = jax.device_get(engine.params)
    compute_dtype, attn_impl = engine.compute_dtype, engine.attn_impl
    engine.destroy()
    del actor, engine
    gc.collect()
    system, ref, window_inputs = [], [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(alone[i, 1:len(s)], np.float64))
        logp, inputs = reference_mellum.logprobs_and_window_inputs(
            ref_config, params, s.tolist(), acfg.temperature
        )
        ref.append(logp)
        window_inputs.append(inputs)
    del params
    gc.collect()
    harness.log("the reference has read the check's sequences")
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    win = window_check(
        ref_config, window_inputs, compute_dtype, attn_impl,
        acfg.mb_spec.max_tokens_per_mb,
    )
    del window_inputs
    harness.log("window_exact has its readings")
    win["limits"] = (
        REHEARSE_WINDOW if ctx.rehearse else ctx.cell.config["window_limits"]
    )
    win["window_expected"] = expected
    edge = win["edge"]
    window_ok = (
        win["mean_rel"] <= win["limits"]["mean"]
        and win["worst_rel"] <= win["limits"]["worst"]
        and edge["queries_inside_changed"] == edge["queries_inside"] > 0
        and edge["queries_outside_changed"] == 0
        and edge["first_beyond_unchanged"]
        and all(counted.get(k) == v for k, v in expected.items())
    )

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    for k in ("swa_pairs", "swa_causal_pairs", "moe_assignments",
              "moe_max_expert_assignments", "moe_stream_rows",
              "moe_overflow_chunks"):
        if counted.get(k):
            counters[k] = float(counted[k])
    if counted.get("moe_assignments"):
        counters["moe_mean_expert_assignments"] = (
            counted["moe_assignments"] / arith_mellum.experts_held(ref_config)
        )
    values = {}
    if at_trace_end.get("swa_pairs"):
        values["attn_window_flops_traced"] = arith_mellum.core_kernel_flops(
            ref_config, at_trace_end["swa_pairs"], n_mb
        )
        values["attn_full_flops_traced"] = arith_mellum.core_kernel_flops(
            ref_config, arith_mellum.full_pairs_from_counters(
                ref_config, at_trace_end["swa_causal_pairs"]
            ), n_mb,
        )
    if at_trace_end.get("moe_assignments"):
        values["moe_expert_flops_traced_per_chip"] = (
            arith_mellum.expert_kernel_flops(
                ref_config, at_trace_end["moe_assignments"],
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
            "window_exact": bool(window_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "window": win,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
