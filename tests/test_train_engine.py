"""SPMD train engine: loss descent, microbatch invariance, forward logprobs.

Mirrors reference areal/tests/test_train_engine.py (FSDP train_batch loss
descent) on the virtual 8-device CPU mesh.
"""

import dataclasses

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


# Engines of one configuration run ONE set of compiled programs. A program of
# ``engine/programs.py`` is a function of the configuration's values (model,
# mesh, dtype, attention, optimizer) and of no engine's state, and a fresh
# engine would trace and compile every one of them again: most of this
# file's seconds, and all of what a second engine of a test added.
_PROGRAMS = {}


def _engine(
    parallel=None, max_tokens_per_mb=32768, lr=1e-2, family="qwen2",
    own_programs=False, attn_impl="auto",
):
    """``own_programs``: for a test that is about what an engine compiles."""
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
        attn_impl=attn_impl,
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8),
        model_config=tiny_config(family),
        seed=0,
    )
    if not own_programs:
        key = (family, lr, dataclasses.astuple(cfg.parallel), cfg.attn_impl)
        eng._jit_cache = _PROGRAMS.setdefault(key, eng._jit_cache)
    return eng


@pytest.fixture(scope="module")
def one_step():
    """A fresh engine of the default configuration after ONE update on the
    toy batch: (its result, its parameters) are what four tests hold a
    sharded, a split or a sequence-parallel engine to."""
    eng = _engine()
    batch = _toy_batch(n=8)
    result = eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    return eng, batch, result, jax.device_get(eng.params)


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
    eng = _engine(own_programs=True)
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


def test_microbatching_matches_single_batch(one_step):
    """Grad accumulation over token-budget microbatches must equal one big
    batch (reference base_hf_engine train_batch weighting semantics)."""
    _, batch, r1, p1 = one_step

    eng2 = _engine(max_tokens_per_mb=32)  # forces several microbatches
    r2 = eng2.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    p2 = jax.device_get(eng2.params)
    assert r2["n_mbs"] > 1
    np.testing.assert_allclose(r1["loss"], r2["loss"], rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p1, p2,
    )


def test_sharded_matches_single_device(one_step):
    """The same batch must produce the same update on a 1-device and an
    8-device (fsdp=2, seq=2, tensor=2) mesh — sharding is semantics-free."""
    _, batch, r1, p1 = one_step
    eng8 = _engine(parallel=ParallelismConfig(1, 2, 2, 2))
    r8 = eng8.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    np.testing.assert_allclose(r1["loss"], r8["loss"], rtol=1e-4)
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
def test_sharded_attn_impl_matches_single_device(impl, one_step):
    """Training with the explicit ring/Ulysses SP kernels must produce the
    same update as the plain single-device path."""
    _, batch, r1, p1 = one_step
    eng2 = _engine(
        parallel=ParallelismConfig(
            1, 2, tensor_parallel_size=2, seq_parallel_size=2
        ),
        attn_impl=impl,
    )
    r2 = eng2.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    np.testing.assert_allclose(r1["loss"], r2["loss"], rtol=1e-4)
    p2 = jax.device_get(eng2.params)
    # see test_sharded_matches_single_device on the first-adam-step noise
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-4),
        p1, p2,
    )


def test_forward_logprobs_match_manual(one_step):
    eng = one_step[0]
    batch = _toy_batch(n=4)
    logps = eng.forward(batch)  # [B, L] next-token logprobs
    # manual: per-sequence forward, every sequence alone in a row of the
    # batch's width (one compiled program for the four)
    from areal_tpu.models.transformer import apply
    from areal_tpu.ops.functional import gather_logprobs

    params = jax.device_get(eng.params)
    mask = batch["attention_mask"]
    width = mask.shape[1]

    @jax.jit
    def manual(params, toks, seg, pos):
        logits = apply(params, eng.model_config, toks, seg, pos, remat=False)
        return gather_logprobs(logits[0, :-1], toks[0, 1:])

    for b in range(4):
        L = int(mask[b].sum())
        toks = np.zeros((1, width), np.int32)
        toks[0, :L] = batch["input_ids"][b, :L]
        seg = (np.arange(width) < L).astype(np.int32)[None]
        pos = (np.arange(width) * seg[0]).astype(np.int32)[None]
        ref = np.asarray(manual(params, toks, seg, pos))[: L - 1]
        np.testing.assert_allclose(logps[b, 1:L], ref, rtol=1e-4, atol=1e-5)
        assert logps[b, 0] == 0.0  # first token has no prediction


def test_save_load_roundtrip_hf(tmp_path, one_step):
    eng, batch = one_step[:2]  # a trained engine: step count and moments
    meta = SaveLoadMeta(path=str(tmp_path / "ckpt"), weight_format="hf", with_optim=True)
    eng.save(meta)
    before = eng.forward(batch)

    eng2 = _engine()
    eng2.load(meta)
    after = eng2.forward(batch)
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-6)
    assert eng2.step_count == eng.step_count


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
