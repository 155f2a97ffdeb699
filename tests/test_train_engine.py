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


def _engine(parallel=None, max_tokens_per_mb=32768, lr=1e-2):
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
        model_config=tiny_config("qwen2"),
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
