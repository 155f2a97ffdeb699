"""The yardstick's arithmetic on synthetic records and hand-worked sizes."""

import json
import os

import pytest

from benchmark import arith, harness
from benchmark.readers import (
    counter_ratio, peak_share, window_per_count, window_rate,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# hand-worked: per layer d*q + 2*d*kv + q*d + 3*d*f, times layers, + d*vocab
SIZES = {
    "qwen2-0.5b": dict(weights=24 * 14_909_440 + 896 * 151_936,
                       params=494_032_768, kv=12_288),
    "qwen2-1.5b": dict(weights=28 * 46_792_704 + 1536 * 151_936,
                       params=1_543_714_304, kv=28_672),
}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_sizes_against_hand_worked_values(name):
    c, want = _config(name), SIZES[name]
    assert arith.matmul_weights(c) == want["weights"]
    assert arith.param_count(c) == want["params"]
    assert arith.kv_bytes_per_token(c) == want["kv"]


def test_flops_against_hand_worked_values():
    c = _config("qwen2-0.5b")
    assert arith.matmul_weights(c) == 493_961_216
    one = 2 * 1000 * 493_961_216 + 2 * 1000 * 1000 * 896 * 24
    assert arith.forward_flops(c, [1000]) == one == 1_030_930_432_000
    # forward + backward (2x) + the one logprob recompute
    assert arith.train_step_flops(c, [1000]) == 4 * one
    assert arith.train_step_flops(c, [1000], n_forward_only=0) == 3 * one
    # attention is quadratic in each sequence, not in the batch
    assert arith.forward_flops(c, [500, 500]) < arith.forward_flops(c, [1000])


def test_flops_agree_with_the_programs_own_counter():
    from areal_tpu.models.config import from_hf_config
    from areal_tpu.utils import flops

    for name in SIZES:
        c = _config(name)
        mc = from_hf_config(c)
        assert arith.matmul_weights(c) == flops.matmul_weights(mc)
        lens = [37, 512, 4096]
        assert arith.train_step_flops(c, lens) == pytest.approx(
            flops.train_step_flops(mc, lens, n_forward_only=1)
        )


def _obs(**kw):
    return harness.Observation(window_s=kw.pop("window_s", 10.0),
                               setup_s=1.0, **kw)


def test_whole_pass_rate_divides_by_the_actual_window():
    spec = {"counter": "trained_tokens"}
    # one whole pass of 4 x 37,000 tokens took 29.3 s, not the 25 s asked for
    obs = _obs(window_s=29.3, counters={"trained_tokens": 4 * 37_000.0})
    assert window_rate.read(spec, obs) == pytest.approx(148_000 / 29.3)
    assert window_rate.read({"counter": "absent"}, obs) is None


def test_counter_readers():
    obs = _obs(window_s=2.0,
               counters={"a": 30.0, "b": 40.0, "total_decode_chunks": 25.0},
               values={"decode_chunk": 8.0})
    assert counter_ratio.read({"num": "a", "den": "b", "scale": 100}, obs) == 75
    assert counter_ratio.read(
        {"num": "a", "den": "b", "complement": True, "scale": 100}, obs
    ) == 25
    assert counter_ratio.read({"num": "a", "den": "zero"}, obs) is None
    assert window_per_count.read(
        {"counter": "total_decode_chunks", "times": "decode_chunk"}, obs
    ) == 10.0
    obs.peaks = {"flops_per_s": 197e12}
    obs.counters["train_flops"] = 197e12 * 2.0 * 0.25
    assert peak_share.read(
        {"counter": "train_flops", "peak": "flops_per_s"}, obs
    ) == pytest.approx(25.0)


def test_kv_tokens_read_is_the_sum_of_live_contexts_over_steps():
    # one request, prompt 100, 10 tokens evenly over t = 0..10: the steps
    # read contexts 100, 101, ... 109 (mid-step: 100.5 ... 109.5) = 1050
    assert arith.kv_tokens_read([(100, 0.0, 10.0, 10)], 0.0, 10.0) == 1050.0
    # the second half only: 5 steps at contexts 105..110 (mean 107.5)
    assert arith.kv_tokens_read([(100, 0.0, 10.0, 10)], 5.0, 10.0) == 537.5
    # not yet started, or finished before the window: nothing
    assert arith.kv_tokens_read([(100, 20.0, 30.0, 10)], 0.0, 10.0) == 0.0
    assert arith.kv_tokens_read([(100, 0.0, 1.0, 10)], 5.0, 10.0) == 0.0


# mean |diff| each configuration read on the chip (its file's "why")
CHIP_MEAN = {"qwen2-0.5b": 0.010, "qwen2-1.5b": 0.017}


@pytest.mark.parametrize("name", sorted(CHIP_MEAN))
def test_logprob_limits_are_the_configurations_and_catch_a_lower_precision(name):
    import numpy as np

    limits = _config(name)["logprob_limits"]
    assert limits["worst"] == pytest.approx(4 * limits["mean"], rel=0.05)
    assert 1.5 * CHIP_MEAN[name] <= limits["mean"] <= 2.5 * CHIP_MEAN[name]
    rng = np.random.default_rng(0)
    ref = [rng.normal(-11.9, 0.3, size=400)]
    # |N(0, s)| has mean 0.8 s: noise of the size the chip showed passes
    bf16 = [ref[0] + rng.normal(0, CHIP_MEAN[name] / 0.8, size=400)]
    got = harness.compare_logprobs(bf16, ref, limits)
    assert got["ok"] and got["mean_tol"] == limits["mean"]
    fp8 = [ref[0] + rng.normal(0, 32 * CHIP_MEAN[name] / 0.8, size=400)]
    assert not harness.compare_logprobs(fp8, ref, limits)["ok"]
    # twice the chip's error already fails the mean
    twice = [ref[0] + rng.normal(0, 2.2 * CHIP_MEAN[name] / 0.8, size=400)]
    assert not harness.compare_logprobs(twice, ref, limits)["ok"]
    rehearse = {"mean": harness.REHEARSE_TOL, "worst": harness.REHEARSE_TOL}
    assert not harness.compare_logprobs(bf16, ref, rehearse)["ok"]


def test_compile_watch_counts_cache_loads_as_compile_events():
    watch = harness.CompileWatch()
    mark = watch.mark()
    assert watch.since(mark) == (0, 0)
    # a program served by the persistent cache fires both events
    watch._on_event("/jax/compilation_cache/cache_hits")
    watch._on_duration("/jax/core/compile/backend_compile_duration", 0.004)
    watch._on_duration("/jax/core/compile/backend_compile_duration", 31.0)
    watch._on_duration("/jax/core/compile/jaxpr_trace_duration", 1.0)
    assert watch.since(mark) == (2, 1)  # (c) asks for (0, 0)
    assert watch.since(watch.mark()) == (0, 0)
