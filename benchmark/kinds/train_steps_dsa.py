"""Trainer steps of a model whose attention reads the keys a learned indexer
picks, on one chip's share of its experts and vocabulary.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window), as
``kinds/train_steps_sparse.py`` takes it. ``run`` is one function there and
cannot be imported in parts, so the window loop is restated here in the same
order. What differs:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_keye.py``, ``benchmark/arith_keye.py``); the check's
  sequences are thousands of tokens long, so the engine's state leaves the
  chip (the weights go to the host) before the reference runs;
- a rehearsal builds the program's tiny configuration of the family itself;
- ``correct`` gains ``packing_invariant`` (as the sparse kind: the check's
  sequences read the same alone and among a batch's; a selection that looks
  across a segment border fails it) and ``selection_exact``: given each
  layer's input as the REFERENCE has it (rounded to the dtype the program
  computes in), the program's own selection (``ops/sparse_attention``) keeps
  exactly sum_t min(t + 1, topk) pairs of every check sequence, and agrees
  with the reference's ``jax.lax.top_k`` on at least the configuration's
  ``selection_limits.agree_share`` of the (query, key) choices of the queries
  that have more than ``topk`` keys; and the window's ``dsa_kept_pairs`` and
  ``dsa_causal_pairs`` are what the trained lengths make them;
- counters of the selection and of the routing to the experts held
  (``dsa_*``, ``moe_*`` from ``SPMDTrainEngine.counters()``; a program
  without them leaves them out) and the traced steps' kernel operations
  (``sparse_core_flops_traced``, ``moe_expert_flops_traced_per_chip``).
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_keye, harness, reference_keye
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "KeyeVL2"
REHEARSE_AGREE = 0.999  # float32 against float32


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
        "sa_config": {"indexer_num_heads": t.dsa_index_heads,
                      "indexer_head_dim": t.dsa_index_head_dim,
                      "topk": t.dsa_topk},
    }


def selection_check(ref_config, model_config, params, seqs, compute_dtype):
    """The program's selection against the reference's, layer by layer on
    the reference's own layer inputs. Returns the counts ``selection_exact``
    decides on."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import sparse_attention as sa

    topk = ref_config["sa_config"]["topk"]
    hi, di = model_config.dsa_index_heads, model_config.dsa_index_head_dim

    @jax.jit
    def program_selection(h, wq, wk, ww):
        h = h.astype(compute_dtype)
        wq, wk, ww = (w.astype(compute_dtype) for w in (wq, wk, ww))
        return sa.select_keys(
            (h @ wq).reshape(h.shape[0], hi, di), h @ wk, h @ ww,
            jnp.ones(h.shape[0], jnp.int32), topk,
        )

    out = {"kept": 0, "kept_expected": 0, "choices": 0, "agree": 0}
    for s in seqs:
        n = len(s)
        out["kept_expected"] += (
            arith_keye.selected_pairs(n, topk) * ref_config["num_hidden_layers"]
        )
        for layer, (h, want) in enumerate(
            reference_keye.selections(ref_config, params, s.tolist())
        ):
            lay = params["layers"]
            got, stats = program_selection(
                h, lay["w_idx_q"][layer], lay["w_idx_k"][layer],
                lay["w_idx_w"][layer],
            )
            out["kept"] += int(stats.kept_pairs)
            if n > topk:  # the queries that had to choose
                got = np.asarray(got)[topk:]
                out["choices"] += int(want[topk:].sum())
                out["agree"] += int((got & want[topk:]).sum())
    out["agree_share"] = out["agree"] / out["choices"] if out["choices"] else 1.0
    return out


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
    layers, topk = model_config.num_layers, model_config.dsa_topk
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
    pairs = {"kept": 0, "causal": 0}  # what the trained lengths make them
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
        flops += arith_keye.train_step_flops(ref_config, lens)
        pairs["kept"] += layers * sum(
            arith_keye.selected_pairs(n, topk) for n in lens
        )
        pairs["causal"] += layers * sum(
            arith_keye.causal_pairs(n) for n in lens
        )

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
        ref.append(reference_keye.token_logprobs(
            ref_config, params, s.tolist(), acfg.temperature
        ))
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    selection = selection_check(
        ref_config, model_config, params, seqs, compute_dtype
    )
    agree_limit = (
        REHEARSE_AGREE if ctx.rehearse
        else ctx.cell.config["selection_limits"]["agree_share"]
    )
    selection["agree_limit"] = agree_limit
    selection["window_pairs_expected"] = pairs
    selection_ok = (
        selection["kept"] == selection["kept_expected"]
        and selection["agree_share"] >= agree_limit
        and counted.get("dsa_kept_pairs") == pairs["kept"]
        and counted.get("dsa_causal_pairs") == pairs["causal"]
    )
    del params
    gc.collect()

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    for k in ("dsa_kept_pairs", "dsa_causal_pairs", "moe_assignments",
              "moe_max_expert_assignments"):
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
            "sparse_core_flops_traced": arith_keye.sparse_core_kernel_flops(
                ref_config, at_trace_end.get("dsa_kept_pairs", 0)
            ),
            "moe_expert_flops_traced_per_chip": (
                arith_keye.expert_kernel_flops(
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
            "selection_exact": bool(selection_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "selection": selection,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
