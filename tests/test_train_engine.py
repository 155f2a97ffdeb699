"""SPMD train engine: loss descent, microbatch invariance, forward logprobs.

Mirrors reference areal/tests/test_train_engine.py (FSDP train_batch loss
descent) on the virtual 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import (
    MicroBatchSpec,
    OptimizerConfig,
    ParallelismConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta
from areal_tpu.engine.sft.lm_engine import LMEngine, sft_loss_fn, sft_loss_weight_fn
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.utils import data as data_utils


def _engine(parallel=None, max_tokens_per_mb=32768, lr=1e-2, family="qwen2"):
    cfg = TrainEngineConfig(
        dtype="float32",
        param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb),
        optimizer=OptimizerConfig(
            type="adamw", lr=lr, weight_decay=0.0,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
            gradient_clipping=100.0,
        ),
        parallel=parallel or ParallelismConfig(),
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8),
        model_config=tiny_config(family),
        seed=0,
    )
    return eng


def _toy_batch(n=8, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 12, size=n)
    seqs = [rng.integers(0, vocab, size=L) for L in lens]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    return batch


def test_sft_loss_descends():
    eng = _engine()
    lm = LMEngine(eng)
    batch = _toy_batch()
    losses = [lm.train_lm(batch)["loss"] for _ in range(8)]
    assert losses[-1] < losses[0] * 0.9, losses
    assert all(s == 1.0 for s in [lm.train_lm(batch)["update_successful"]])


def test_every_trainer_program_is_in_the_compile_log_by_name():
    """From ``initialize`` on the trainer's compile tracker is current for
    the thread: ``init_params``, the optimizer state, the zeroed accumulator
    and ``logp_forward`` are in its table with no line at their call sites,
    and it counts what the benchmark's own watch counts from outside."""
    from areal_tpu.utils import goodput
    from benchmark import harness

    goodput.reset_trainer_ledger()
    watch = harness.CompileWatch()  # before the engine, as benchmark/run.py
    eng = _engine()
    batch = _toy_batch()
    eng.forward(batch)
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    tracker = goodput.trainer_tracker()
    table = {r["fun_name"]: r for r in tracker.program_table()}
    assert {"init_params", "init_fn", "zero_grads", "logp_forward",
            "grad_step", "apply_step"} <= set(table)
    for name in ("init_params", "logp_forward", "grad_step", "apply_step"):
        row = table[name]
        assert row["traces"] == 1 and row["compiles"] + row["loads"] == 1
        assert row["trace_s"] > 0 and row["lower_s"] > 0
    assert tracker.programs() == watch.compiles > 0
    secs = tracker.phase_seconds()
    assert secs["compile"] + secs["cache_load"] == pytest.approx(watch.seconds)
    # the two dispatch scopes keep their phase and shape; the rest is the
    # thread's default
    assert any(ph == "fwd_bwd" and sig.startswith("pad")
               for ph, sig in tracker.signatures)
    assert {("optim", ""), ("trainer", "")} <= set(tracker.signatures)
    # a destroyed engine takes the thread's default with it
    eng.destroy()
    n = tracker.programs()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7))
    assert tracker.programs() == n


def test_a_step_makes_the_named_programs_and_no_other(monkeypatch):
    """A GRPO step's host path makes no device program of its own: what
    ``ppo_update`` adds to the compile log is ``logp_forward``, ``grad_step``,
    ``apply_step``, the accumulator's one ``zero_grads`` and the sum of a
    minibatch's weights; no ``convert_element_type``, ``concatenate``,
    ``broadcast_in_dim`` or ``reshape`` a scalar, and no program of the
    learning-rate schedule. A second step compiles nothing, zeroes ONE
    accumulator for its four minibatches, and leaves nothing queued: every
    ``apply_step`` it dispatched is fetched, the last fetch with nothing
    behind it."""
    from areal_tpu.utils import goodput
    from tests.test_ppo_actor import _actor, _rollout_batch

    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 16)
    goodput.reset_trainer_ledger()
    actor = _actor(ppo_n_minibatches=4, max_tokens_per_mb=16)
    eng = actor.engine
    zeroed, zero_grads = [], eng._zero_grads
    monkeypatch.setattr(
        eng, "_zero_grads", lambda: zeroed.append(1) or zero_grads()
    )
    spans = _record_spans(monkeypatch)
    tracker = goodput.trainer_tracker()

    def traces():
        return {r["fun_name"]: r["traces"] for r in tracker.program_table()}

    def step(seed):
        batch = actor.compute_advantages(_rollout_batch(n=16, seed=seed))
        before, n_programs, n_spans = traces(), tracker.programs(), len(spans())
        stats = actor.ppo_update(batch)
        made = {k for k, v in traces().items() if v > before.get(k, 0)}
        return stats, made, tracker.programs() - n_programs, [
            (s.name, s.attrs.get("behind")) for s in spans()[n_spans:]
        ]

    named = {"logp_forward", "grad_step", "apply_step", "zero_grads", "add"}
    stats, made, compiled, _ = step(0)
    assert {"logp_forward", "grad_step", "apply_step", "zero_grads"} <= made
    assert made <= named and compiled > 0
    assert max(s["n_mbs"] for s in stats) > 1  # so a sum of weights ran
    stats, made, compiled, seen = step(1)
    assert made <= named and compiled == 0
    assert len(zeroed) == 2  # one a step, none between its minibatches
    # nothing pending when the step returns
    assert len(stats) == 4 and all(
        isinstance(v, float) for s in stats for v in s.values()
    )
    fetches = [b for n, b in seen if n == "train.scalar_fetch"]
    assert fetches == [1, 1, 1, 0]
    assert sum(n == "train.apply_dispatch" for n, _ in seen) == 4
    assert seen[-1] == ("train.scalar_fetch", 0)


def test_microbatching_matches_single_batch():
    """Grad accumulation over token-budget microbatches must equal one big
    batch (reference base_hf_engine train_batch weighting semantics)."""
    batch = _toy_batch(n=8)
    eng1 = _engine(max_tokens_per_mb=32768)
    r1 = eng1.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    p1 = jax.device_get(eng1.params)

    eng2 = _engine(max_tokens_per_mb=32)  # forces several microbatches
    r2 = eng2.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    p2 = jax.device_get(eng2.params)
    assert r2["n_mbs"] > 1
    np.testing.assert_allclose(r1["loss"], r2["loss"], rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p1, p2,
    )


def _split_engine(family, rows, max_tokens_per_mb):
    """Float32, plain SGD at lr 1 with no clipping: the step IS the summed
    gradient, so two splits of one minibatch can be held to each other."""
    import dataclasses

    cfg = TrainEngineConfig(
        dtype="float32", param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb),
        optimizer=OptimizerConfig(
            type="sgd", lr=1.0, weight_decay=0.0, gradient_clipping=1e9,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
        ),
        parallel=ParallelismConfig(fsdp_parallel_size=rows),
    )
    # the balancing loss is a product of two means over a microbatch's
    # tokens: it alone depends on the split, by design
    mc = dataclasses.replace(tiny_config(family), router_aux_loss_coef=0.0)
    eng = SPMDTrainEngine(cfg)
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 8), model_config=mc, seed=0)
    return eng


@pytest.mark.parametrize("family,rows", [("qwen2", 1), ("olmoe", 4)])
def test_same_step_under_two_splits(family, rows, monkeypatch):
    """One minibatch through ``train_batch`` as rows-first microbatches of
    several row lengths and as ONE whole microbatch: the same loss, gradient
    norm and updated parameters to float32 summation order, and ``forward``
    the same logprobs in the original order. The ladder is shrunk to steps of
    16 tokens so that tiny sequences reach several rungs."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 16)
    rng = np.random.default_rng(3)
    lens = [60, 9, 3, 14, 58, 30, 61, 7, 56, 12, 5]
    seqs = [rng.integers(0, 128, size=n) for n in lens]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)

    whole = _split_engine(family, rows, max_tokens_per_mb=1 << 20)
    split = _split_engine(family, rows, max_tokens_per_mb=64 * rows)
    mbl = split._split(batch, backward=True)
    assert len(mbl.mbs) > 1 and len(set(mbl.row_lens)) > 1, mbl.row_lens
    assert len(whole._split(batch, backward=True).mbs) == 1
    # a pass with no backward: the same microbatches at one length, the
    # longest's
    fwd = split._split(batch, backward=False)
    assert fwd.groups == mbl.groups
    assert fwd.row_lens == [max(mbl.row_lens)] * len(mbl.mbs)

    np.testing.assert_allclose(
        split.forward(dict(batch)), whole.forward(dict(batch)),
        rtol=1e-4, atol=1e-5,
    )
    r_whole = whole.train_batch(dict(batch), sft_loss_fn, sft_loss_weight_fn)
    r_split = split.train_batch(dict(batch), sft_loss_fn, sft_loss_weight_fn)
    assert r_split["n_mbs"] == len(mbl.mbs) and r_whole["n_mbs"] == 1
    np.testing.assert_allclose(r_split["loss"], r_whole["loss"], rtol=1e-5)
    np.testing.assert_allclose(
        r_split["grad_norm"], r_whole["grad_norm"], rtol=1e-4
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5),
        jax.device_get(split.params), jax.device_get(whole.params),
    )
    # every token once in each pass, whatever the split
    assert (
        split.counters()["real_tokens"] == whole.counters()["real_tokens"]
        == 2 * sum(lens)
    )


def test_sharded_matches_single_device():
    """The same batch must produce the same update on a 1-device and an
    8-device (fsdp=2, seq=2, tensor=2) mesh — sharding is semantics-free."""
    batch = _toy_batch(n=8)
    eng1 = _engine()
    eng8 = _engine(parallel=ParallelismConfig(1, 2, 2, 2))
    r1 = eng1.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    r8 = eng8.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    np.testing.assert_allclose(r1["loss"], r8["loss"], rtol=1e-4)
    p1 = jax.device_get(eng1.params)
    p8 = jax.device_get(eng8.params)
    # step 0 runs at full lr: adam's first step is sign(g)-like, so
    # reduction-order noise on near-zero grads shifts updates by O(lr·rel)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-4),
        p1, p8,
    )


def test_fresh_optimizer_state_is_sharded_like_params():
    """Before the first update: on an fsdp=4 mesh no device holds more than
    its quarter of Adam's moments (plus replicated scalars). Checked on the
    shards each device really holds, not on the sharding's name — a bare
    ``jit(optimizer.init)`` left every moment whole on the first device."""
    eng = _engine(parallel=ParallelismConfig(fsdp_parallel_size=4))
    held = {}
    for leaf in jax.tree_util.tree_leaves(eng.opt_state):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    moments = 2 * sum(
        p.nbytes for p in jax.tree_util.tree_leaves(eng.params)
    )
    assert len(held) == 4
    # tiny_config has leaves fsdp cannot split 4 ways; they replicate
    assert max(held.values()) <= 0.3 * moments, held
    for p, mu in zip(
        jax.tree_util.tree_leaves(eng.params),
        jax.tree_util.tree_leaves(eng.opt_state[1][0].mu),
    ):
        assert mu.sharding == p.sharding


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sharded_attn_impl_matches_single_device(impl):
    """Training with the explicit ring/Ulysses SP kernels must produce the
    same update as the plain single-device path."""
    batch = _toy_batch(n=8)
    eng1 = _engine()
    r1 = eng1.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    cfg = TrainEngineConfig(
        dtype="float32", param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=32768),
        optimizer=OptimizerConfig(
            type="adamw", lr=1e-2, weight_decay=0.0,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
            gradient_clipping=100.0,
        ),
        parallel=ParallelismConfig(
            1, 2, tensor_parallel_size=2, seq_parallel_size=2
        ),
        attn_impl=impl,
    )
    eng2 = SPMDTrainEngine(cfg)
    eng2.initialize(
        ft_spec=FinetuneSpec(1, 64, 8),
        model_config=__import__(
            "areal_tpu.models.config", fromlist=["tiny_config"]
        ).tiny_config("qwen2"),
        seed=0,
    )
    r2 = eng2.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    np.testing.assert_allclose(r1["loss"], r2["loss"], rtol=1e-4)
    p1 = jax.device_get(eng1.params)
    p2 = jax.device_get(eng2.params)
    # see test_sharded_matches_single_device on the first-adam-step noise
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-4),
        p1, p2,
    )


def test_forward_logprobs_match_manual():
    eng = _engine()
    batch = _toy_batch(n=4)
    logps = eng.forward(batch)  # [B, L] next-token logprobs
    # manual: per-sequence forward
    from areal_tpu.models.transformer import apply
    from areal_tpu.ops.functional import gather_logprobs

    params = jax.device_get(eng.params)
    mask = batch["attention_mask"]
    for b in range(4):
        L = int(mask[b].sum())
        toks = jnp.asarray(batch["input_ids"][b, :L], jnp.int32)[None]
        seg = jnp.ones((1, L), jnp.int32)
        pos = jnp.arange(L, dtype=jnp.int32)[None]
        logits = apply(params, eng.model_config, toks, seg, pos, remat=False)
        ref = np.asarray(gather_logprobs(logits[0, :-1], toks[0, 1:]))
        np.testing.assert_allclose(logps[b, 1:L], ref, rtol=1e-4, atol=1e-5)
        assert logps[b, 0] == 0.0  # first token has no prediction


def test_save_load_roundtrip_hf(tmp_path):
    eng = _engine()
    batch = _toy_batch()
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    meta = SaveLoadMeta(path=str(tmp_path / "ckpt"), weight_format="hf", with_optim=True)
    eng.save(meta)
    before = eng.forward(batch)

    eng2 = _engine()
    eng2.load(meta)
    after = eng2.forward(batch)
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-6)
    assert eng2.step_count == eng.step_count


# --------------------------------------------------------------------------
# The host path: a result is fetched one dispatch behind
# --------------------------------------------------------------------------
def _minibatches(poisoned=None):
    """Four minibatches of sequences that split into several microbatches
    at 64-token rows; ``scale`` multiplies the loss (``_scaled_loss_fn``),
    and is infinite in the ``poisoned`` one."""
    out = []
    for k in range(4):
        rng = np.random.default_rng(20 + k)
        lens = rng.integers(20, 60, size=5 + k % 2)
        batch = data_utils.pad_sequences_to_tensors(
            [rng.integers(0, 128, size=n) for n in lens]
        )
        batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
        batch["scale"] = np.full(
            len(lens), np.inf if k == poisoned else 1.0, np.float32
        )
        out.append(batch)
    return out


def _scaled_loss_fn(logits, arrays):
    loss, stats = sft_loss_fn(logits, arrays)
    return loss * arrays["s_scale"].max(), stats


def _record_spans(monkeypatch):
    """Every ``tracing.span`` of the code under test into an in-memory
    tracer: ``spans()`` gives them in the order they opened."""
    from areal_tpu.api.cli_args import TracingConfig
    from areal_tpu.utils import tracing

    ring = tracing.SpanTracer(TracingConfig(enabled=True, max_spans=100000))
    span = tracing.span
    monkeypatch.setattr(
        tracing, "span",
        lambda name, tracer=None, rid="", **attrs: span(
            name, ring, rid, **attrs
        ),
    )
    return lambda: sorted(ring.snapshot(), key=lambda s: s.t_start)


@pytest.mark.parametrize("family", ["qwen2", "KeyeVL2", "kimi_linear"])
def test_pipelined_minibatches_equal_one_at_a_time(family, monkeypatch):
    """``train_batches`` over a step's minibatches (k + 1 dispatched before
    k is fetched) against the same minibatches through ``train_batch`` one
    at a time: stats, parameters, optimizer state, step count and counters
    bit for bit, for a dense model, one that holds a share of its experts
    and selects its keys (float MoE counts, int32 pair counts) and one with
    delta-rule layers (int32 token counts). The third minibatch's gradient
    is not finite: its update alone reports failure, the parameters skip it
    on both paths, and the accumulator it hands the fourth is zeros, not
    NaN."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 64)
    pipelined = _engine(max_tokens_per_mb=128, family=family)
    one_by_one = _engine(max_tokens_per_mb=128, family=family)
    got = pipelined.train_batches(
        iter(_minibatches(poisoned=2)), _scaled_loss_fn, sft_loss_weight_fn
    )
    want = [
        one_by_one.train_batch(mb, _scaled_loss_fn, sft_loss_weight_fn)
        for mb in _minibatches(poisoned=2)
    ]
    assert min(s["n_mbs"] for s in want) > 1
    np.testing.assert_equal(got, want)  # NaN equals NaN here
    assert [s["update_successful"] for s in got] == [1.0, 1.0, 0.0, 1.0]
    assert all(
        isinstance(v, float) and np.isfinite(v)
        for s in (got[0], got[1], got[3]) for v in s.values()
    )
    if family == "KeyeVL2":
        assert {"moe_stream_rows", "dsa_kept_pairs"} <= set(got[0])
    if family == "kimi_linear":
        assert "kda_tokens" in got[0]
    for a, b in (
        (pipelined.params, one_by_one.params),
        (pipelined.opt_state, one_by_one.opt_state),
    ):
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            jax.device_get(a), jax.device_get(b),
        )
    assert pipelined.step_count == one_by_one.step_count == 4
    assert pipelined.counters() == one_by_one.counters()
    assert pipelined.fetch_counters() == {"fetches": 4, "fetches_behind": 3}
    assert one_by_one.fetch_counters() == {"fetches": 4, "fetches_behind": 0}


def test_apply_step_hands_the_accumulator_back_zeroed_in_its_layout():
    """``apply_step``'s third output is the donated accumulator zeroed (of a
    non-finite one too: zeros, not 0 x NaN) and laid out as the parameters
    are, which is what lets it take the donated buffers under ``fsdp=4``."""
    eng = _engine(parallel=ParallelismConfig(fsdp_parallel_size=4))
    eng.train_batch(_toy_batch(), sft_loss_fn, sft_loss_weight_fn)
    before = jax.device_get(eng.params)
    poisoned = jax.device_put(
        jax.tree_util.tree_map(
            lambda p: np.full(p.shape, np.nan, np.float32), before
        ),
        eng._param_shardings,
    )
    eng.params, eng.opt_state, zeroed, grad_norm, ok = eng._jit_cache["apply"](
        eng.params, eng.opt_state, poisoned, jnp.ones((), jnp.float32)
    )
    assert not bool(ok) and not np.isfinite(float(grad_norm))
    for z, p, sh in zip(
        jax.tree_util.tree_leaves(zeroed),
        jax.tree_util.tree_leaves(eng.params),
        jax.tree_util.tree_leaves(eng._param_shardings),
    ):
        assert z.dtype == jnp.float32 and z.shape == p.shape
        assert z.sharding.is_equivalent_to(sh, z.ndim)
        assert not np.asarray(z).any()
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, jax.device_get(eng.params), before
    )


def test_fetches_run_one_dispatch_behind(monkeypatch):
    """The order on the host: minibatch k + 1's ``grad_step`` dispatches open
    before minibatch k's scalar fetch, microbatch k + 1's ``logp_forward``
    dispatch before microbatch k's fetch; the spans say ``behind`` and the
    counters count it: 3 of 4 minibatches, m - 1 of m recompute
    microbatches, none for a lone ``train_batch``. ``forward`` returns what
    the plain loop (pack, dispatch, fetch, unpack, one microbatch at a time)
    returns, to the bit."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 64)
    spans = _record_spans(monkeypatch)
    eng = _engine(max_tokens_per_mb=128)

    def order(*names):
        return [
            (s.name.split(".")[1], s.attrs["step"], s.attrs.get("behind"))
            for s in spans() if s.name in names
        ]

    eng.train_batches(_minibatches(), sft_loss_fn, sft_loss_weight_fn)
    seen = order("train.apply_dispatch", "train.scalar_fetch")
    assert seen == [
        ("apply_dispatch", 0, None), ("apply_dispatch", 1, None),
        ("scalar_fetch", 0, 1), ("apply_dispatch", 2, None),
        ("scalar_fetch", 1, 1), ("apply_dispatch", 3, None),
        ("scalar_fetch", 2, 1), ("scalar_fetch", 3, 0),
    ]
    by_name = order("train.grad_dispatch", "train.scalar_fetch")
    for k in range(3):
        assert by_name.index(("grad_dispatch", k + 1, None)) < by_name.index(
            ("scalar_fetch", k, 1)
        )
    assert eng.fetch_counters() == {"fetches": 4, "fetches_behind": 3}

    eng.train_batch(_minibatches()[0], sft_loss_fn, sft_loss_weight_fn)
    assert order("train.scalar_fetch")[-1] == ("scalar_fetch", 4, 0)
    assert eng.fetch_counters() == {"fetches": 5, "fetches_behind": 3}

    rng = np.random.default_rng(5)
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, size=n) for n in (60, 55, 50, 45, 40, 35, 30)]
    )
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    n_before = len(spans())
    got = eng.forward(dict(batch))
    seen = [
        (s.name.split(".")[1], s.attrs.get("behind"))
        for s in spans()[n_before:]
        if s.name in ("train.logp_dispatch", "train.logp_fetch")
    ]
    assert seen == [
        ("logp_dispatch", None), ("logp_dispatch", None), ("logp_fetch", 1),
        ("logp_dispatch", None), ("logp_fetch", 1), ("logp_fetch", 0),
    ]
    assert eng.fetch_counters() == {"fetches": 8, "fetches_behind": 5}

    # the plain loop, on the engine's own program
    mbs = eng._split(batch, backward=False)
    logp_fn = next(
        f for k, f in eng._jit_cache.items()
        if isinstance(k, tuple) and k[0] == "fwd"
    )
    want = np.zeros_like(got)
    for mb, row_len, group in zip(mbs.mbs, mbs.row_lens, mbs.groups):
        packed, arrays = eng._pack_for_device(mb, pad_to=row_len)
        out = data_utils.unpack_rows_per_token(
            packed, np.asarray(logp_fn(eng.params, arrays))
        )
        want[np.asarray(group), : out.shape[1]] = out
    assert len(mbs.mbs) == 3
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("scheduler", ["constant", "linear", "cosine"])
@pytest.mark.parametrize("warmup", [0.0, 0.2])
def test_host_learning_rate_is_the_optax_schedule(scheduler, warmup):
    """What a step reports as its learning rate is computed on the host;
    it is the optax schedule's value (which the optimizer reads on the
    device) to float32, over warm-up, decay and the steps past the end."""
    from areal_tpu.engine.spmd_engine import _host_lr, _lr_schedule

    cfg = TrainEngineConfig(optimizer=OptimizerConfig(
        lr=3e-4, min_lr_ratio=0.1, warmup_steps_proportion=warmup,
        lr_scheduler_type=scheduler,
    ))
    on_device, on_host = _lr_schedule(cfg, 40), _host_lr(cfg, 40)
    # numpy's cosine and XLA's differ in the last bit of some steps
    rel = 2e-6 if scheduler == "cosine" else 0.0
    for step in range(50):
        assert on_host(step) == pytest.approx(
            float(on_device(step)), rel=rel, abs=0.0
        ), step
    assert isinstance(on_host(3), float)
