"""Trainer steps of a model whose layers are of two kinds: the gated delta
rule with ONE decay a head and token (``gdn``: 32 value heads over 16 key
heads) three layers in four, gated softmax attention at heads of 256 the
fourth, every FFN a chip's share of 512 experts and a gated shared one, over
a slice of the vocabulary.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window), as
``kinds/train_steps_kda.py`` takes it. ``run`` is one function there and
cannot be imported in parts, so the window loop is restated here in the same
order. What differs from that kind:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_qwen3next.py``, ``benchmark/arith_qwen3next.py``);
  the check's sequences are thousands of tokens long, so the engine's state
  leaves the chip (the weights go to the host) before the reference runs;
- a rehearsal builds the program's tiny configuration of the family itself;
- ``scan_exact`` feeds the program's timed scan (``ops/kda.kda_scan`` in its
  scalar-decay form: ``g`` [T, 32], ``q, k`` of 16 heads) each gdn layer's
  q, k, v, g, beta as the REFERENCE has them, rounded to the dtype the program
  computes in, and holds it to the reference's token-by-token rule within
  the configuration's ``scan_limits``; and the window's ``kda_tokens`` and
  ``kda_segments`` are exactly what the trained lengths make them;
- the arithmetic counts the recompute forwards the chip runs, (n - 1) / n of
  one a trained token at n minibatches (``arith_qwen3next
  .recompute_forwards``), and hands the full layers' core its causal pairs
  from the trained lengths (``attn_full_flops_traced``: the program counts
  none for a ``full`` mixer), beside ``kda_kernel_bytes_traced`` and
  ``moe_expert_flops_traced_per_chip``.
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_qwen3next as arith
from benchmark import harness
from benchmark import reference_qwen3next as reference
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "qwen3_next"
REHEARSE_SCAN = {"mean": 1e-4, "worst": 1e-4}  # float32 against float32


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal: the keys are the program's own
    rendering of that configuration as a ``config.json``."""
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf_io import default_hf_config_dict

    t = tiny_config(FAMILY)
    return t, default_hf_config_dict(t)


def scan_check(ref_config, params, seqs, compute_dtype):
    """The program's timed scan, in its scalar-decay form, against the
    reference's token-by-token rule, gdn layer by gdn layer on the reference's
    own q, k [T, 16, 128], v [T, 32, 128], g and beta [T, 32] (q, k, v rounded
    to the compute dtype for both). Returns what ``scan_exact`` decides on."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kda

    program = jax.jit(kda.kda_scan)
    rule = jax.jit(reference.gdn_rule)
    abs_diff, abs_want, worst, largest, n = 0.0, 0.0, 0.0, 0.0, 0
    for s in seqs:
        for q, k, v, g, beta in reference.gdn_layers(
            ref_config, params, s.tolist()
        ):
            q, k, v = (jnp.asarray(a).astype(compute_dtype) for a in (q, k, v))
            got, _ = program(
                q[None], k[None], v[None], jnp.asarray(g)[None],
                jnp.asarray(beta)[None], jnp.ones((1, len(s)), jnp.int32),
            )
            with jax.default_matmul_precision("highest"):
                want = np.asarray(rule(
                    *(a.astype(jnp.float32) for a in (q, k, v)), g, beta
                ), np.float64)
            diff = np.abs(np.asarray(got[0], np.float64) - want)
            abs_diff += float(diff.sum())
            abs_want += float(np.abs(want).sum())
            worst = max(worst, float(diff.max()))
            largest = max(largest, float(np.abs(want).max()))
            n += diff.size
    return {
        "compared": n, "layers": arith.gdn_layers(ref_config),
        "decay": "scalar" if g.ndim == 2 else "channel",
        "mean_rel": abs_diff / abs_want if abs_want else float("nan"),
        "worst_rel": worst / largest if largest else float("nan"),
        "impl": kda.scan_impl(q.shape[-1], v.shape[-1]),
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
        f"kda_impl={getattr(engine, 'kda_impl', None)} mesh={chips} "
        f"max_tokens_per_mb={acfg.mb_spec.max_tokens_per_mb} "
        f"minibatches={acfg.ppo_n_minibatches}"
    )
    vocab = model_config.vocab_size
    n_kda = arith.gdn_layers(ref_config)
    n_full = arith.full_layers(ref_config)
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
    expected = {"kda_tokens": 0, "kda_segments": 0}  # from the lengths
    full_pairs = 0  # the full layers' causal pairs, from the lengths too
    full_pairs_at_trace_end = 0
    at_trace_end = {}

    def window_steps():
        k = 0
        while k == 0 or time.monotonic() - t0 < ctx.seconds:
            yield from pass_order(len(batches), ctx.seed, k)
            k += 1

    def measured_step(b):
        nonlocal n_steps, tokens, flops, full_pairs
        t = time.monotonic()
        all_stats.extend(step(b))
        step_s.append(round(time.monotonic() - t, 3))
        lens = batches[b]["_seq_lens"]
        n_steps += 1
        tokens += int(lens.sum())
        flops += arith.train_step_flops(ref_config, lens, n_mb)
        expected["kda_tokens"] += n_kda * int(lens.sum())
        expected["kda_segments"] += n_kda * len(lens)
        full_pairs += n_full * sum(arith.causal_pairs(n) for n in lens)

    steps = window_steps()
    with traced:  # a traced run profiles its first steps only
        for _ in range(p["trace_steps"] if ctx.trace else 0):
            measured_step(next(steps))
        at_trace_end = harness.counter_delta(engine.counters(), counters0)
        full_pairs_at_trace_end = full_pairs
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
    alone = logp_of(seqs)
    packed_logp = among_a_batch()
    mem_peak = harness.memory_peak_bytes()
    params = jax.device_get(engine.params)
    compute_dtype = engine.compute_dtype
    engine.destroy()
    del actor, engine
    gc.collect()
    system, ref = [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(alone[i, 1:len(s)], np.float64))
        ref.append(reference.token_logprobs(
            ref_config, params, s.tolist(), acfg.temperature
        ))
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    scan = scan_check(ref_config, params, seqs, compute_dtype)
    scan["limits"] = (
        REHEARSE_SCAN if ctx.rehearse else ctx.cell.config["scan_limits"]
    )
    scan["window_expected"] = expected
    scan_ok = (
        scan["mean_rel"] <= scan["limits"]["mean"]
        and scan["worst_rel"] <= scan["limits"]["worst"]
        and all(counted.get(k) == v for k, v in expected.items())
    )
    del params
    gc.collect()

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    for k in ("kda_tokens", "kda_chunk_slots", "kda_segments",
              "moe_assignments", "moe_max_expert_assignments",
              "moe_stream_rows", "moe_overflow_chunks"):
        if counted.get(k):
            counters[k] = float(counted[k])
    if counted.get("moe_assignments"):
        counters["moe_mean_expert_assignments"] = (
            counted["moe_assignments"] / ref_config["num_local_experts"]
        )
    values = {}
    if at_trace_end.get("kda_tokens"):
        values["kda_kernel_bytes_traced"] = arith.kda_kernel_bytes(
            ref_config, at_trace_end["kda_tokens"], n_mb,
            itemsize=np.dtype(compute_dtype).itemsize,
        )
    if full_pairs_at_trace_end:
        values["attn_full_flops_traced"] = arith.core_kernel_flops(
            ref_config, full_pairs_at_trace_end, n_mb
        )
    if at_trace_end.get("moe_assignments"):
        values["moe_expert_flops_traced_per_chip"] = (
            arith.expert_kernel_flops(
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
            "scan_exact": bool(scan_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "scan": scan,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
