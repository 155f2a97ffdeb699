"""The yardstick's arithmetic on synthetic records and hand-worked sizes."""

import json
import os

import pytest

from benchmark import arith, harness
from benchmark.readers import (
    counter_ratio, histogram_p, peak_share, request_p, window_per_count,
    window_rate,
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


def test_percentile_and_misses_ranked_worst():
    assert arith.percentile([1, 2, 3, 4, 5], 50) == 3
    assert arith.percentile(list(range(101)), 95) == 95
    assert arith.percentile([10], 95) == 10
    ranked = arith.ranked_with_misses([10, 20, 30], [5, 7])
    assert sorted(ranked) == [10, 20, 30, 30, 30]  # never below a measured one
    assert max(arith.ranked_with_misses([10], [99])) == 99


def _obs(**kw):
    return harness.Observation(window_s=kw.pop("window_s", 10.0),
                               setup_s=1.0, **kw)


def test_ttft_tail_counts_lateness_and_unfinished_requests():
    spec = {"field": "ttft_ms", "percentile": 95}
    done = [{"ttft_ms": 100.0 + i} for i in range(90)]
    assert request_p.read(spec, _obs(requests=done)) == pytest.approx(184.55)
    # ten of a hundred never finished: the tail is theirs, above all measured
    missed = [{"miss": True, "ttft_ms": 50.0} for _ in range(10)]
    assert request_p.read(spec, _obs(requests=done + missed)) == 189.0
    waited = [{"miss": True, "ttft_ms": 9000.0} for _ in range(10)]
    assert request_p.read(spec, _obs(requests=done + waited)) == 9000.0
    assert request_p.read(spec, _obs(requests=[])) is None
    # a one-token answer has no time per output token: left out, not zero
    tp = {"field": "tpot_ms", "percentile": 95}
    assert request_p.read(
        tp, _obs(requests=[{"tpot_ms": None}, {"tpot_ms": 4.0}])
    ) == 4.0


def test_whole_step_rate_divides_by_the_actual_window():
    spec = {"counter": "trained_tokens"}
    # 11 whole steps of 37,000 tokens took 41.8 s, not the 40 s asked for
    obs = _obs(window_s=41.8, counters={"trained_tokens": 11 * 37_000.0})
    assert window_rate.read(spec, obs) == pytest.approx(407_000 / 41.8)
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


def test_histogram_quantile_interpolates_inside_its_bucket():
    bounds, counts = [0.01, 0.1, 1.0], [0, 10, 10, 0]
    assert arith.histogram_quantile(bounds, counts, 0.5) == pytest.approx(0.1)
    assert arith.histogram_quantile(bounds, counts, 0.75) == pytest.approx(0.55)
    assert arith.histogram_quantile(bounds, [0, 0, 0, 0], 0.95) is None
    assert arith.histogram_quantile(bounds, [0, 0, 0, 5], 0.95) == 1.0
    obs = _obs(histograms={"h": {"bounds": bounds, "counts": counts}})
    assert histogram_p.read(
        {"histogram": "h", "percentile": 75, "scale": 1000}, obs
    ) == pytest.approx(550.0)
    assert histogram_p.read({"histogram": "none", "percentile": 75}, obs) is None


def test_kv_tokens_read_is_the_sum_of_live_contexts_over_steps():
    # one request, prompt 100, 10 tokens evenly over t = 0..10: the steps
    # read contexts 100, 101, ... 109 (mid-step: 100.5 ... 109.5) = 1050
    assert arith.kv_tokens_read([(100, 0.0, 10.0, 10)], 0.0, 10.0) == 1050.0
    # the second half only: 5 steps at contexts 105..110 (mean 107.5)
    assert arith.kv_tokens_read([(100, 0.0, 10.0, 10)], 5.0, 10.0) == 537.5
    # not yet started, or finished before the window: nothing
    assert arith.kv_tokens_read([(100, 20.0, 30.0, 10)], 0.0, 10.0) == 0.0
    assert arith.kv_tokens_read([(100, 0.0, 1.0, 10)], 5.0, 10.0) == 0.0


def test_logprob_limits_catch_a_lower_precision():
    import numpy as np

    rng = np.random.default_rng(0)
    ref = [rng.normal(-11.9, 0.3, size=200)]
    bf16 = [ref[0] + rng.normal(0, 0.008, size=200)]  # what the chip showed
    assert harness.compare_logprobs(bf16, ref, rehearse=False)["ok"]
    fp8 = [ref[0] + rng.normal(0, 0.25, size=200)]  # 32x coarser mantissa
    assert not harness.compare_logprobs(fp8, ref, rehearse=False)["ok"]
    assert not harness.compare_logprobs(bf16, ref, rehearse=True)["ok"]
