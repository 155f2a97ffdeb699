"""Trainer steps of a state-space hybrid across the chips of one host: a
selective scan (a decay a channel and a state) most layers, NoPE attention
the others, a dense FFN in each, the whole trained state under ``fsdp``.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, and the same window
rule: whole passes in the seed's order, ending at the end of the first pass
that passes ``--seconds``; a traced run profiles its first ``trace_steps``
steps and leaves the profiler's own start and stop out of the window), the
mesh as a size and the per-chip arithmetic of ``kinds/train_steps_sparse.py``
(its ``actor_config``), the timed ``grad_step`` without an optimizer step of
``kinds/train_steps_bd.py`` (its ``system_loss_and_grads``). ``run`` is one
function in each and cannot be imported in parts, so the window loop is
restated here in the same order. What differs:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_jamba.py``, ``benchmark/arith_jamba.py``); the
  check's sequences are thousands of tokens long and the model is 12 GB of
  float32, so the engine's state leaves the chips (the weights and the
  check's gradients go to the host) before the reference runs, a layer's
  weights at a time;
- a rehearsal builds the program's tiny configuration of the family itself,
  on as many of the mesh's devices as the CPU backend has;
- ``correct`` gains ``packing_invariant`` (the check's sequences read the
  same alone and among a batch's: a state or a convolution that leaks across
  a segment border fails it), ``gradient_direction`` (the TIMED ``grad_step``
  over the check's sequences, no optimizer step, against the reference's
  GRPO loss and gradients: the loss within ``gradient_limits.loss_abs_diff``
  and the cosine of the two whole gradients at least
  ``gradient_limits.cosine``) and ``scan_exact``: (a) the program's timed
  scan (``ops/ssm_scan.ssm_scan``, the instance a row of the cell's length
  takes) on each mamba layer's x, dt, B, C as the REFERENCE has them (x
  rounded to the dtype the program computes in), the check's sequences packed
  into ONE row, against the reference's token-by-token rule a sequence,
  within the configuration's ``scan_limits``; (b) the border, bit for bit:
  moving ``x`` at one token of a packed sequence changes that sequence's
  outputs at that token and after, and no other slot of the row
  (``border_check``); (c) the window's ``ssm_tokens`` and ``ssm_segments``
  are exactly what the trained lengths make them;
- counters per chip (``train_flops_per_chip``), of the scans (``ssm_*`` from
  ``SPMDTrainEngine.counters()``; a program without them leaves them out)
  and what the traced steps ask of the kernels (``ssm_kernel_bytes_traced``).
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_jamba, harness, reference_jamba
from benchmark.kinds.train_steps import make_batches, pass_order
from benchmark.kinds.train_steps_bd import system_loss_and_grads
from benchmark.kinds.train_steps_sparse import actor_config

FAMILY = "jamba"
REHEARSE_SCAN = {"mean": 1e-5, "worst": 1e-5}  # float32 against float32
REHEARSE_GRADIENT = {"cosine": 0.9999, "loss_abs_diff": 1e-4}


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal: the keys are the program's own
    rendering of that configuration as a ``config.json``."""
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf_io import default_hf_config_dict

    t = tiny_config(FAMILY)
    return t, default_hf_config_dict(t)


def gradient_inputs(seqs, vocab, seed):
    """A padded batch of the check's ``seqs`` as a minibatch of the update
    has them: behaviour logprobs near -log vocab, proximal ones near those,
    an advantage of +1 or -1 a sequence, the loss over every token after a
    sequence's first quarter (its completion)."""
    from areal_tpu.utils import data as data_utils

    rng = np.random.default_rng(seed)
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm = np.zeros(batch["input_ids"].shape, np.int32)
    for i, s in enumerate(seqs):
        lm[i, max(len(s) // 4, 1):len(s)] = 1
    behav = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1
        - np.float32(math.log(vocab))
    ) * lm
    sign = np.where(np.arange(len(seqs)) % 2 == 0, 1.0, -1.0)
    batch.update(
        loss_mask=lm, logprobs=behav,
        prox_logp=behav + rng.standard_normal(lm.shape).astype(np.float32)
        * np.float32(0.05) * lm,
        advantages=(sign[:, None] * lm).astype(np.float32),
    )
    return batch


def gradient_check(ref_config, params, batch, system, acfg):
    """``gradient_direction``: the system's (loss, gradients) against the
    reference's over the same sequences."""
    import jax

    lens = np.asarray(batch["attention_mask"]).sum(1)
    want_loss, want = reference_jamba.loss_and_grads(
        ref_config, params, [
            # target-aligned: entry t - 1 is token t's
            dict(tokens=batch["input_ids"][i, :n],
                 behav_logp=batch["logprobs"][i, 1:n],
                 prox_logp=batch["prox_logp"][i, 1:n],
                 advantages=batch["advantages"][i, 1:n],
                 mask=batch["loss_mask"][i, 1:n])
            for i, n in enumerate(lens)
        ], eps_clip=acfg.eps_clip, behav_cap=acfg.behav_imp_weight_cap,
        temperature=acfg.temperature,
    )
    loss, grads, _ = system
    dot = norm_s = norm_r = 0.0
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g = np.asarray(got[path])
        # float64 a slab at a time: a stacked leaf is 0.5 G elements
        g, w = (a.reshape(-1, a.shape[-1]) if a.ndim > 1 else a[None]
                for a in (g, np.asarray(w)))
        for i in range(0, len(w), 4096):
            u = g[i:i + 4096].astype(np.float64).ravel()
            v = w[i:i + 4096].astype(np.float64).ravel()
            dot += float(u @ v)
            norm_s += float(u @ u)
            norm_r += float(v @ v)
    return {
        "loss": loss, "reference_loss": float(want_loss),
        "loss_abs_diff": abs(loss - float(want_loss)),
        "cosine": dot / math.sqrt(norm_s * norm_r),
        "norm": math.sqrt(norm_s), "reference_norm": math.sqrt(norm_r),
        "tokens": int(np.asarray(batch["loss_mask"]).sum()),
    }


def border_check(scan, x, dt, a, bm, cm, seg, which=1):
    """``scan_exact`` (b): ``scan(x, dt, a, bm, cm, seg) -> y [1, T, C]``
    twice, the second time with ``x`` moved at ONE token in the middle of
    sequence ``which`` of the row; every output of that sequence from that
    token on is another number, every other slot of the row the same bits."""
    ids = np.asarray(seg)[0]
    at = np.flatnonzero(ids == ids[ids > 0][0] + which)
    t, end = int(at[len(at) // 2]), int(at[-1]) + 1
    moved = np.array(x, copy=True)
    moved[0, t] = moved[0, t] + np.asarray(3.0, moved.dtype)
    base = np.asarray(scan(x, dt, a, bm, cm, seg))[0]
    other = np.asarray(scan(moved, dt, a, bm, cm, seg))[0]
    differs = (base != other).any(axis=-1)
    inside = np.zeros(len(ids), bool)
    inside[t:end] = True
    return {
        "token": t, "sequence_end": end,
        "moved_inside": int(differs[inside].sum()),
        "inside": int(inside.sum()),
        "moved_outside": int(differs[~inside].sum()),
        "ok": bool(differs[inside].all() and not differs[~inside].any()),
    }


def scan_check(ref_config, params, seqs, compute_dtype, row_len):
    """``scan_exact`` (a) and (b): the program's timed scan on ONE row of
    ``row_len`` slots that packs the check's sequences, mamba layer by mamba
    layer on the reference's own x, dt, B, C (x rounded to the compute dtype
    for both), against the reference's token-by-token rule a sequence; and
    the border check on the first layer's row."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import ssm_scan

    lens = [len(s) for s in seqs]
    assert sum(lens) <= row_len, (lens, row_len)
    seg = np.zeros((1, row_len), np.int32)
    at = 0
    for i, n in enumerate(lens):
        seg[0, at:at + n] = i + 1
        at += n
    program = jax.jit(lambda *args: ssm_scan.ssm_scan(*args)[0])
    rule = jax.jit(reference_jamba.ssm_rule)
    per_seq = reference_jamba.ssm_layers(
        ref_config, params, [s.tolist() for s in seqs]
    )
    abs_diff, abs_want, worst, largest, n_out = 0.0, 0.0, 0.0, 0.0, 0
    border = None
    for layer in range(len(per_seq[0])):
        def row(k, width, dtype=np.float32):
            out = np.zeros((1, row_len, width), dtype)
            at = 0
            for s in per_seq:
                v = s[layer][k]
                out[0, at:at + len(v)] = v
                at += len(v)
            return out

        a = per_seq[0][layer][5]
        x = np.asarray(
            jnp.asarray(row(0, a.shape[0])).astype(compute_dtype)
        )
        dt = row(1, a.shape[0])
        bm, cm = row(2, a.shape[1]), row(3, a.shape[1])
        got = np.asarray(program(x, dt, a, bm, cm, seg), np.float64)[0]
        at = 0
        for n in lens:
            with jax.default_matmul_precision("highest"):
                want = np.asarray(rule(
                    jnp.asarray(x[0, at:at + n]).astype(jnp.float32),
                    dt[0, at:at + n], a, bm[0, at:at + n], cm[0, at:at + n],
                ), np.float64)
            diff = np.abs(got[at:at + n] - want)
            abs_diff += float(diff.sum())
            abs_want += float(np.abs(want).sum())
            worst = max(worst, float(diff.max()))
            largest = max(largest, float(np.abs(want).max()))
            n_out += diff.size
            at += n
        if border is None:
            border = border_check(program, x, dt, a, bm, cm, seg)
    return {
        "compared": n_out, "layers": len(per_seq[0]), "row_len": row_len,
        "mean_rel": abs_diff / abs_want if abs_want else float("nan"),
        "worst_rel": worst / largest if largest else float("nan"),
        "impl": ssm_scan.scan_impl(a.shape[0]), "border": border,
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
        f"attn_impl={engine.attn_impl} "
        f"ssm_impl={getattr(engine, 'ssm_impl', None)} mesh={chips} "
        f"max_tokens_per_mb={acfg.mb_spec.max_tokens_per_mb} "
        f"minibatches={acfg.ppo_n_minibatches}"
    )
    vocab = model_config.vocab_size
    n_ssm = arith_jamba.ssm_layers(ref_config)
    batches = make_batches(p, vocab, ctx.seed)
    # as train_steps: what is dispatched, real tokens and padding, counted
    # from the packed rows themselves where every microbatch is packed
    dispatched = {"padded": 0, "real": 0, "row_len": 0}
    pack = engine._pack_for_device

    def counting_pack(mb, pad_to=None):
        packed, arrays = pack(mb, pad_to=pad_to)
        seg = np.asarray(packed.segment_ids)  # 0 = padding
        dispatched["padded"] += int(seg.size)
        dispatched["real"] += int(np.count_nonzero(seg))
        dispatched["row_len"] = max(dispatched["row_len"], seg.shape[-1])
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
    row_len = dispatched["row_len"]  # the rows the window trains
    dispatched.update(padded=0, real=0)

    counters0 = engine.counters()
    compile_mark = ctx.watch.mark()
    ctx.mark_window_start()
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    t0 = time.monotonic()
    n_steps, tokens, flops, all_stats, step_s = 0, 0, 0.0, [], []
    expected = {"ssm_tokens": 0, "ssm_segments": 0}  # from the lengths
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
        flops += arith_jamba.train_step_flops(ref_config, lens)
        expected["ssm_tokens"] += n_ssm * int(lens.sum())
        expected["ssm_segments"] += n_ssm * len(lens)

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

    # outside the window, on the weights the window left: compute_logp alone
    # and in other company, the timed grad_step over the check's sequences;
    # then the state leaves the chips and the reference has them to itself
    harness.log(f"window done: {n_steps} steps in {window_s:.1f} s; checks")
    alone = logp_of(seqs)
    packed_logp = among_a_batch()
    if acfg.c_clip is not None or acfg.eps_clip_higher is not None:
        raise SystemExit(
            "gradient_direction: the reference's loss has no dual or "
            "asymmetric clip, and the recipe now sets one"
        )
    short = [s for s in seqs if len(s) <= p["grad_check_max_len"]]
    grad_batch = gradient_inputs(short, vocab, ctx.seed + 17)
    system_grads = system_loss_and_grads(engine, actor, grad_batch)
    mem_peak = harness.memory_peak_bytes()
    params = jax.device_get(engine.params)
    compute_dtype = engine.compute_dtype
    engine.destroy()
    del actor, engine
    gc.collect()
    harness.log("the state is on the host; the reference's logprobs")
    want = reference_jamba.sequences_logprobs(
        ref_config, params, [s.tolist() for s in seqs], acfg.temperature
    )
    # target-aligned: position t holds log p(token t | tokens < t)
    system = [
        np.asarray(alone[i, 1:len(s)], np.float64) for i, s in enumerate(seqs)
    ]
    cmp = harness.compare_logprobs(system, want, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    harness.log("the reference's loss and gradients")
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
    del system_grads
    gc.collect()
    harness.log("scan_exact")
    scan = scan_check(ref_config, params, seqs, compute_dtype, row_len)
    harness.log("checks done")
    scan["limits"] = (
        REHEARSE_SCAN if ctx.rehearse else ctx.cell.config["scan_limits"]
    )
    scan["window_expected"] = expected
    scan_ok = (
        scan["mean_rel"] <= scan["limits"]["mean"]
        and scan["worst_rel"] <= scan["limits"]["worst"]
        and scan["border"]["ok"]
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
    for k in ("ssm_tokens", "ssm_segments"):
        if counted.get(k):
            counters[k] = float(counted[k])
    values = {}
    if at_trace_end.get("ssm_tokens"):
        # a chip's share: the roofline reads one chip's kernel time
        values["ssm_kernel_bytes_traced"] = arith_jamba.ssm_kernel_bytes(
            ref_config, at_trace_end["ssm_tokens"],
            acfg.gradient_checkpointing,
        ) / chips
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
            "gradient_direction": gradient["ok"],
            "scan_exact": bool(scan_ok),
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "gradient": gradient, "scan": scan,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
