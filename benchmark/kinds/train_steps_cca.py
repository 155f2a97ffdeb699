"""Trainer steps of a model whose attention runs inside a compressed latent
that two causal convolutions mix (CCA), over experts as wide as the residual
stream of which a token takes ONE, chosen by an MLP router whose state passes
from layer to layer; one chip's share of its experts and vocabulary.

The traffic of ``kinds/train_steps.py`` (imported, not copied: the batches of
``make_batches``, the whole-pass order of ``pass_order``, the recipe's
settings through ``actor_config``, and the same window rule: whole passes in
the seed's order, ending at the end of the first pass that passes
``--seconds``; a traced run profiles its first ``trace_steps`` steps and
leaves the profiler's own start and stop out of the window), as
``kinds/train_steps_swa.py`` takes it. ``run`` is one function there and
cannot be imported in parts, so the window loop is restated here in the same
order. What differs:

- the reference and the arithmetic are this configuration's
  (``benchmark/reference_zaya.py``, ``benchmark/arith_zaya.py``); the check's
  sequences are thousands of tokens long, so the engine's state leaves the
  chip (the weights go to the host) before the reference runs;
- a rehearsal builds the program's tiny configuration of the family itself;
- every weight is ``--seed``'s but the router's leaves, which are drawn, by
  the program's own ``init_params``, from the traffic file's ``layout_seed``
  (``seed_router``): with ONE expert a token and half of them held, the
  router's draw decides how much of every row is this chip's work (0.48-0.60
  of the pairs over six ``--seed`` draws on the chip, which spread
  ``trained_tokens_per_s`` by 0.71% of its median, PERF.md PR 41), and every
  seed has to do the same work (``benchmark/README.md``): what sets a pass's
  work, the lengths, the minibatch split and now the router's draw, is the
  layout's, whatever ``--seed`` is. The scalar a layer multiplies the
  previous layer's router state by, which the program starts at zero, is set
  to 0.5 in every layer (``seed_carry``; the configuration's
  ``assumed.weights`` says why: at zero the carried path is multiplied away
  and a dropped carry would pass every comparison);
- ``correct`` gains ``packing_invariant`` (the check's sequences read the
  same alone and among a batch's: a convolution or a shift that reaches
  across a segment border fails it) and ``mix_exact``: (a) each layer's q, k,
  v as they ENTER the core, the program's mixer (``cca_qkv``, the function
  the timed programs trace, jitted here in the compute dtype: the check's
  sequences packed in one row of the trained length) against the
  reference's, both on the reference's own normed input, within the
  configuration's ``mix_limits``; (b) the borders, exactly: in that packed
  row, replacing the LAST token's input of one sequence leaves every other
  sequence's q, k, v bit-for-bit what they were, and replacing token t
  inside a sequence changes q and k at t .. t + K0 + K1 - 2 and v at t,
  t + 1 and nowhere else; (c) ``cca_causal_pairs`` and ``cca_starts``, the
  timed ``grad_step``'s own counts, are exactly what the trained lengths
  make them. The routing has no check of its own: a token sent to another
  expert than the reference's reads another logprob, which
  ``reference_agrees`` holds through the timed entry;
- counters of the mixer and of the routing to the experts held (``cca_*``,
  ``moe_*`` from ``SPMDTrainEngine.counters()``; a program without them
  leaves them out) and what the traced steps ask of the kernels
  (``attn_full_flops_traced``: the core is full causal attention under
  ``attention``, the layer ``attn_full_roofline.train`` reads;
  ``moe_expert_flops_traced_per_chip``).
"""

import gc
import math
import time

import numpy as np

from benchmark import arith_zaya, harness, reference_zaya
from benchmark.kinds.train_steps import actor_config, make_batches, pass_order

FAMILY = "zaya"
KIND = reference_zaya.KIND
# float32 against float32
REHEARSE_MIX = {"mean": 1e-4, "worst": 1e-4}
# the mixer's leaves: what ``cca_qkv`` reads
MIXER_LEAVES = ("wq", "wk", "wv_own", "wv_prev", "cca_conv0",
                "cca_conv0_bias", "cca_conv1", "cca_conv1_bias", "cca_temp")
COUNTED = ("cca_causal_pairs", "cca_starts", "moe_assignments",
           "moe_max_expert_assignments", "moe_stream_rows",
           "moe_overflow_chunks")


def tiny_configs():
    """(program's ModelConfig, the reference's keys) of the family's tiny
    test configuration, for a rehearsal: the keys are the program's own
    rendering of that configuration as a ``config.json``."""
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf_io import default_hf_config_dict

    t = tiny_config(FAMILY)
    return t, default_hf_config_dict(t)


def seed_carry(params):
    """The scalar every layer multiplies the previous layer's router state
    by, set to 0.5 (``init_params`` leaves the zero a fresh model stores)."""
    import jax
    import jax.numpy as jnp

    stack = params["layers"][KIND]
    leaf = stack["router_carry"]
    stack["router_carry"] = jax.device_put(
        jnp.full_like(leaf, 0.5), leaf.sharding
    )


def seed_router(params, model_config, layout_seed):
    """Every layer's router leaves drawn again, by the program's own
    ``init_params``, from ``layout_seed`` (only they are computed: the jit
    drops the rest), and the carried scalar at 0.5."""
    import jax

    from areal_tpu.models.transformer import init_params

    stack = params["layers"][KIND]
    names = [k for k in stack if "router" in k]
    dtype = stack[names[0]].dtype

    def draw(key):
        layers = init_params(model_config, key, dtype=dtype)["layers"][KIND]
        return {k: layers[k] for k in names}

    drawn = jax.jit(draw)(jax.random.PRNGKey(layout_seed))
    for k in names:
        stack[k] = jax.device_put(drawn[k], stack[k].sharding)
    seed_carry(params)
    return names


def pack_row(seqs, pad_to):
    """(segment ids, positions) [1, T] of ``seqs`` packed one after the
    other in a row of ``pad_to`` tokens (their own sum where that is 0), and
    where each starts."""
    total = sum(len(s) for s in seqs)
    t = max(pad_to, total)
    seg, pos, at, starts = np.zeros((1, t), np.int32), np.zeros(
        (1, t), np.int32
    ), 0, []
    for i, s in enumerate(seqs):
        seg[0, at:at + len(s)] = i + 1
        pos[0, at:at + len(s)] = np.arange(len(s))
        starts.append(at)
        at += len(s)
    return seg, pos, starts


def in_row(pieces, t):
    """[1, T, ...] of per-sequence arrays laid one after the other."""
    row = np.concatenate(pieces, axis=0)
    return np.pad(row, ((0, t - row.shape[0]),) + ((0, 0),) * (row.ndim - 1))[
        None
    ]


def _rel(got, want):
    """(sum |difference|, sum |want|, max |difference|, max |want|)."""
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return np.array([
        diff.sum(), np.abs(want).sum(dtype=np.float64), diff.max(),
        np.abs(want).max(),
    ])


def mix_check(model_config, params_mix, saw, compute_dtype, pad_to):
    """What ``mix_exact`` decides on. ``saw``: a list a check sequence of
    ``reference_zaya.logprobs_and_mix``'s dicts a layer; ``params_mix``: the
    mixers' leaves, stacked, on the host."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer

    mc = model_config
    lens = [s[0]["h"].shape[0] for s in saw]
    n = sum(lens)
    seg, pos, starts = pack_row([np.zeros(m) for m in lens], pad_to)
    t = seg.shape[1]
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)

    @jax.jit
    def mixer(h, lp):
        lp = jax.tree_util.tree_map(lambda a: a.astype(compute_dtype), lp)
        return transformer.cca_qkv(
            mc, h.astype(compute_dtype), lp, seg,
            *transformer._rotations(mc, pos)["cca"],
        )

    def program(h, lp):
        return [np.asarray(a[0, :n], np.float32) for a in mixer(h, lp)]

    # (a) layer by layer, the check's sequences packed in one row
    acc, borders = {name: np.zeros(4) for name in "qkv"}, {}
    for layer in range(mc.num_layers):
        lp = {k: v[layer] for k, v in params_mix.items()}
        h = in_row([s[layer]["h"] for s in saw], t)
        got = program(h, lp)
        for name, g in zip("qkv", got):
            want = np.concatenate([s[layer][name] for s in saw], axis=0)
            r = _rel(g, want)
            acc[name] = np.array([
                acc[name][0] + r[0], acc[name][1] + r[1],
                max(acc[name][2], r[2]), max(acc[name][3], r[3]),
            ])
        if layer == 0:
            borders = border_check(
                program, h, lp, got, lens, starts, sum(mc.cca.conv_taps) - 2
            )
    per = {
        name: {"mean_rel": a[0] / a[1], "worst_rel": a[2] / a[3]}
        for name, a in acc.items()
    }
    return {
        "compared": int(n * mc.num_layers), "layers": mc.num_layers,
        "row": t, "per_tensor": per,
        "mean_rel": max(v["mean_rel"] for v in per.values()),
        "worst_rel": max(v["worst_rel"] for v in per.values()),
        "borders": borders,
    }


def border_check(program, h, lp, base, lens, starts, reach):
    """(b): ``h`` [1, T, D] holds the check's sequences one after the other.
    Replace the LAST token of the first sequence: nothing of any other
    sequence may move, and of the first only that token. Replace a token
    inside the longest: q and k move at it and the ``reach`` tokens after
    it, v at it and the one after, and nothing else does."""
    def moved(at):
        h2 = h.copy()
        h2[0, at] = h2[0, at] + 8.0
        return [
            np.abs(a - b).reshape(a.shape[0], -1).max(axis=1) > 0
            for a, b in zip(program(h2, lp), base)
        ]

    last = starts[0] + lens[0] - 1
    at_border = moved(last)
    after = [m[last + 1:].sum() for m in at_border]
    before = [m[:last].sum() for m in at_border]
    at_last = [bool(m[last]) for m in at_border]
    longest = int(np.argmax(lens))
    t = starts[longest] + lens[longest] // 3
    inside = moved(t)
    want = {"q": reach + 1, "k": reach + 1, "v": 2}
    got_in = {
        name: int(m[t:t + want[name]].sum())
        for name, m in zip("qkv", inside)
    }
    got_out = {
        name: int(m.sum() - m[t:t + want[name]].sum())
        for name, m in zip("qkv", inside)
    }
    return {
        "last_token": int(last), "moved_after_border": int(sum(after)),
        "moved_before_last": int(sum(before)), "moved_at_last": at_last,
        "token": int(t), "want_moved": want, "moved_in_reach": got_in,
        "moved_elsewhere": got_out,
    }


def mix_ok(mix, limits, counted, expected):
    b = mix["borders"]
    return bool(
        mix["mean_rel"] <= limits["mean"]
        and mix["worst_rel"] <= limits["worst"]
        and b["moved_after_border"] == 0 and b["moved_before_last"] == 0
        and all(b["moved_at_last"])
        and b["moved_in_reach"] == b["want_moved"]
        and not any(b["moved_elsewhere"].values())
        and all(counted.get(k) == v for k, v in expected.items())
    )


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
    redrawn = seed_router(engine.params, model_config, p["layout_seed"])
    actor = PPOActor(acfg, engine)
    chips = int(engine.mesh.size)
    row_len = acfg.mb_spec.max_tokens_per_mb
    harness.log(
        f"trainer up: dtype={acfg.dtype} param_dtype={acfg.param_dtype} "
        f"attn_impl={engine.attn_impl} moe_impl={engine.moe_impl} "
        f"mesh={chips} max_tokens_per_mb={row_len} "
        f"minibatches={acfg.ppo_n_minibatches} router drawn from "
        f"layout_seed {p['layout_seed']} ({len(redrawn)} leaves a layer)"
    )
    vocab = model_config.vocab_size
    n_layers = ref_config["num_hidden_layers"]
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
    expected = {"cca_causal_pairs": 0, "cca_starts": 0}  # from the lengths
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
        flops += arith_zaya.train_step_flops(ref_config, lens)
        expected["cca_causal_pairs"] += (
            n_layers * arith_zaya.attended_pairs(lens)
        )
        expected["cca_starts"] += n_layers * arith_zaya.starts(lens)

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
    pad_to = row_len if engine.attn_impl == "flash" else 0
    mem_peak = harness.memory_peak_bytes()
    params = jax.device_get(engine.params)
    compute_dtype = engine.compute_dtype
    engine.destroy()
    del actor, engine
    gc.collect()
    system, ref, saw = [], [], []
    for i, s in enumerate(seqs):
        # target-aligned: position t holds log p(token t | tokens < t)
        system.append(np.asarray(alone[i, 1:len(s)], np.float64))
        logp, layers = reference_zaya.logprobs_and_mix(
            ref_config, params, s.tolist(), acfg.temperature
        )
        ref.append(logp)
        saw.append(layers)
    params_mix = {k: params["layers"][KIND][k] for k in MIXER_LEAVES}
    del params
    gc.collect()
    harness.log("the reference has read the check's sequences")
    cmp = harness.compare_logprobs(system, ref, ctx.logprob_limits)
    packing = harness.compare_logprobs(packed_logp, system, ctx.logprob_limits)
    limits = {
        k: v for k, v in (
            REHEARSE_MIX if ctx.rehearse else ctx.cell.config["mix_limits"]
        ).items() if k != "why"
    }
    mix = mix_check(model_config, params_mix, saw, compute_dtype, pad_to)
    del saw
    harness.log("mix_exact has its readings")
    mix.update(limits=limits, expected=expected)
    ok = mix_ok(mix, limits, counted, expected)

    counters = {
        "trained_tokens": float(tokens), "train_flops": flops,
        "train_flops_per_chip": flops / chips, "steps": float(n_steps),
        "padded_tokens": float(dispatched["padded"]),
        "dispatched_real_tokens": float(dispatched["real"]),
    }
    for k in COUNTED:
        if counted.get(k):
            counters[k] = float(counted[k])
    if counted.get("moe_assignments"):
        counters["moe_mean_expert_assignments"] = (
            counted["moe_assignments"] / arith_zaya.experts_held(ref_config)
        )
    values = {}
    if at_trace_end.get("cca_causal_pairs"):
        values["attn_full_flops_traced"] = arith_zaya.core_kernel_flops(
            ref_config, at_trace_end["cca_causal_pairs"]
        )
    if at_trace_end.get("moe_assignments"):
        values["moe_expert_flops_traced_per_chip"] = (
            arith_zaya.expert_kernel_flops(
                ref_config, at_trace_end["moe_assignments"],
                acfg.gradient_checkpointing,
            ) / chips
        )
    obs = harness.Observation(
        window_s=window_s, setup_s=ctx.setup_s, counters=counters,
        values=values, trace=traced.reduce(), peaks=ctx.peaks,
    )
    held_share = (
        counted["moe_assignments"] / (n_layers * tokens)
        if counted.get("moe_assignments") and tokens else None
    )
    return harness.Outcome(
        obs=obs, attempted=n_steps, failed=len(bad) and n_steps,
        checks={
            "finite_loss_and_grad_norm": not bad and n_steps > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "reference_agrees": cmp["ok"],
            "packing_invariant": packing["ok"],
            "mix_exact": ok,
        },
        notes={"steps": n_steps, "passes": n_steps // len(batches),
               "step_s": step_s, "chips": chips,
               "minibatch_updates": len(all_stats),
               "compiles_in_window": compiles_in_window,
               "cache_loads_in_window": loads_in_window, "reference": cmp,
               "packing": packing, "mix": mix,
               "counted": counted, "counted_at_trace_end": at_trace_end,
               # of the trained tokens' pairs, a layer: routed to experts held
               "held_share_of_pairs": held_share,
               "trained_tokens": tokens,
               "dispatched_real_tokens": dispatched["real"],
               "dispatched_padded_tokens": dispatched["padded"],
               "profiler_s": traced.profiler_s,
               "memory_peak_before_reference": mem_peak,
               "grad_norm_last": all_stats[-1]["grad_norm"] if all_stats else None,
               "live_arrays_after_destroy": len(jax.live_arrays())},
    )
