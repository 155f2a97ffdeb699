"""Trainer steps of a sparse-expert model across the chips of one host.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes
in the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window). ``run`` itself
is one function there and cannot be imported in parts, so the window loop is
restated here in the same order. What differs:

- the reference and the arithmetic are the sparse configuration's
  (``benchmark/reference_olmoe.py``, ``benchmark/arith_olmoe.py``);
- a cell may set the mesh as a size (``parallel.*``) beside
  ``mb_spec.max_tokens_per_mb``: the state of this configuration exists only
  across chips;
- a rehearsal builds the program's tiny configuration of the family itself
  (``harness.model_config`` hands out the dense one), on as many of the
  mesh's devices as the CPU backend has;
- ``correct`` gains ``packing_invariant``: the ``check_lens`` sequences read
  the same ``compute_logp`` by themselves (one a row, which is also what the
  reference is compared with) and packed into full rows among a training
  batch's sequences, within the configuration's ``logprob_limits``. A
  dispatch that drops what exceeds a capacity fails it: what a token reads
  would depend on its row-mates;
- counters per chip (``train_flops_per_chip``), of the routing
  (``moe_assignments``, ``moe_max_expert_assignments``,
  ``moe_mean_expert_assignments`` from ``SPMDTrainEngine.counters()``; a
  program without them leaves them out) and the traced steps' expert
  operations a chip (``moe_expert_flops_traced_per_chip``).
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_olmoe, harness, reference_olmoe
from benchmark.kinds import train_steps
from benchmark.kinds.train_steps import make_batches, pass_order

FAMILY = "olmoe"
# what a traffic file of this kind may set under "sizes" beyond
# train_steps.SIZE_KEYS: the mesh
MESH_KEYS = (
    "parallel.fsdp_parallel_size", "parallel.data_parallel_size",
    "parallel.tensor_parallel_size", "parallel.expert_parallel_size",
)


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal."""
    from areal_tpu.models.config import tiny_config

    t = tiny_config(FAMILY)
    return t, {
        "hidden_size": t.hidden_size, "intermediate_size": t.expert_ffn_size,
        "num_hidden_layers": t.num_layers, "head_dim": t.head_dim,
        "num_attention_heads": t.num_heads, "vocab_size": t.vocab_size,
        "num_key_value_heads": t.num_kv_heads, "rope_theta": t.rope_theta,
        "rms_norm_eps": t.rms_norm_eps, "num_experts": t.num_experts,
        "num_experts_per_tok": t.num_experts_per_tok,
        "norm_topk_prob": t.norm_topk_prob,
        "tie_word_embeddings": t.tie_word_embeddings,
    }


def actor_config(p, rehearse):
    """``train_steps.actor_config`` with the mesh admitted as a size."""
    import jax

    from areal_tpu.api import cli_args

    sizes = p.get("sizes", {})
    acfg = train_steps.actor_config(
        dict(p, sizes={k: v for k, v in sizes.items() if k not in MESH_KEYS}),
        rehearse,
    )
    for key in MESH_KEYS:
        if key in sizes:
            n = int(sizes[key])
            if rehearse:  # the CPU backend's devices, where it has fewer
                n = min(n, jax.device_count())
            cli_args.apply_override(acfg, key, str(n))
    return acfg


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
        f"mesh={chips} max_tokens_per_mb="
        f"{acfg.mb_spec.max_tokens_per_mb} minibatches={acfg.ppo_n_minibatches}"
    )
    vocab = model_config.vocab_size
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
        # spread through the batch, so that they land in different rows
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
    traced_tokens = 0

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
        flops += arith_olmoe.train_step_flops(
            ref_config, batches[b]["_seq_lens"]
        )

    steps = window_steps()
    with traced:  # a traced run profiles its first steps only
        for _ in range(p["trace_steps"] if ctx.trace else 0):
            measured_step(next(steps))
        traced_tokens = tokens
    for b in steps:
        measured_step(b)
    window_s = time.monotonic() - t0 - traced.profiler_s
    compiles_in_window, loads_in_window = ctx.watch.since(compile_mark)
    routed = harness.counter_delta(engine.counters(), counters0)
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
    # other company, outside the window, on the weights the window left
    alone = logp_of(seqs)
    packed_logp = among_a_batch()
    params = engine.params
    system, ref = [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(alone[i, 1:len(s)], np.float64))
        ref.append(reference_olmoe.token_logprobs(
            ref_config, params, s.tolist(), acfg.temperature
        ))
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    mem_peak = harness.memory_peak_bytes()
    engine.destroy()
    del actor, engine, params
    gc.collect()

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    if routed.get("moe_assignments"):
        counters["moe_assignments"] = float(routed["moe_assignments"])
        counters["moe_max_expert_assignments"] = float(
            routed["moe_max_expert_assignments"]
        )
        counters["moe_mean_expert_assignments"] = (
            routed["moe_assignments"] / ref_config["num_experts"]
        )
    obs = harness.Observation(
        window_s=window_s, setup_s=ctx.setup_s, counters=counters,
        values={"moe_expert_flops_traced_per_chip": (
            arith_olmoe.expert_kernel_flops(
                ref_config, traced_tokens, acfg.gradient_checkpointing
            ) / chips
        )},
        trace=traced.reduce(), peaks=ctx.peaks,
    )
    return harness.Outcome(
        obs=obs, attempted=n_steps, failed=len(bad) and n_steps,
        checks={
            "finite_loss_and_grad_norm": not bad and n_steps > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "reference_agrees": cmp["ok"],
            "packing_invariant": packing["ok"],
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "routed": routed,
               "trained_tokens": tokens, "traced_tokens": traced_tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
