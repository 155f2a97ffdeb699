"""SPMD train engine, the host's half of a step: a minibatch split into
rows-first microbatches is the same step, and a result is fetched one
dispatch behind. A file of its own beside ``test_train_engine.py`` (whose
helpers it takes) because ``--dist loadfile`` hands a file to one worker."""

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
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.sft.lm_engine import sft_loss_fn, sft_loss_weight_fn
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.utils import data as data_utils
from test_train_engine import _PROGRAMS, _engine, _record_spans, _toy_batch


def _split_engine(family, rows, max_tokens_per_mb):
    """Float32, plain SGD at lr 1 with no clipping: the step IS the summed
    gradient, so two splits of one minibatch can be held to each other."""
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
    eng._jit_cache = _PROGRAMS.setdefault(("sgd", family, rows), eng._jit_cache)
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
