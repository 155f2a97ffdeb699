"""Decode tail compaction (r6): token-exact parity and occupancy.

The tentpole invariant: for a fixed seed and request set, the token AND
logprob streams a request produces are IDENTICAL with ``decode_compact``
on vs off — across greedy and sampled requests, device/host stop paths,
and finish/preempt/re-admit races while ``decode_pipeline=2`` chunks are
in flight. This holds because (a) sampling is keyed by SLOT id, not row
position (model_runner._sample_impl), (b) the forward is per-row
independent for dense models, and (c) compaction changes only the shape
of each dispatch, never the scheduler's decision sequence.

Determinism discipline: all requests are submitted BEFORE the engine
loop starts and ``admit_hold_s=0`` — the admission wave composition is
then a pure function of the config, not of thread timing.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import JaxGenConfig
from areal_tpu.inference.engine import GenerationEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import init_params


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _run_cohort(model, payloads, **cfg_kw):
    """Submit every payload BEFORE starting the loop (deterministic
    admission), run to completion, return (results, metrics, hist)."""
    cfg, params = model
    eng = GenerationEngine(
        JaxGenConfig(
            dtype="float32", admit_hold_s=0.0, prefill_chunk=16,
            **cfg_kw,
        ),
        model_config=cfg,
        params=params,
    )
    futs = [eng.submit(dict(p)) for p in payloads]
    eng.start()
    try:
        outs = [f.result(timeout=600) for f in futs]
        metrics = eng.metrics()
        hist = dict(eng.rows_dispatched_hist)
    finally:
        eng.stop()
    return outs, metrics, hist


def _randomized_payloads(seed, n):
    """Mixed cohort: greedy + sampled, ragged budgets, stop lists longer
    than the 8-id device buffer (host-backstop coverage), min_new."""
    rng = np.random.default_rng(seed)
    payloads = []
    for i in range(n):
        plen = int(rng.integers(4, 14))
        sp = {
            "max_new_tokens": int(rng.integers(14, 30)),
            "temperature": float(rng.choice([0.7, 1.0, 1.3])),
            "greedy": bool(rng.random() < 0.4),
            "top_p": float(rng.choice([1.0, 0.9])),
            "top_k": int(rng.choice([0, 8])),
        }
        if rng.random() < 0.5:
            # 12 stop ids: the device buffer holds 8, so hits on the
            # tail 4 exercise the vectorized host backstop
            sp["stop_token_ids"] = rng.integers(
                1, 128, size=12
            ).tolist()
            sp["min_new_tokens"] = int(rng.integers(0, 4))
        payloads.append(
            {
                "rid": f"r{i}",
                "input_ids": rng.integers(1, 128, size=plen).tolist(),
                "sampling_params": sp,
            }
        )
    return payloads


@pytest.mark.parametrize(
    "seed",
    # tier-1 cap shave (r11): seed 0 stays in budget, seed 1 slow
    [0, pytest.param(1, marks=pytest.mark.slow)],
)
def test_compact_on_off_streams_identical_under_races(model, seed):
    """The acceptance invariant, under the hard regime: oversubscribed
    pool (preempt + re-admit), decode_pipeline=2 (in-flight chunks when
    slots finish), randomized sampling params, host-backstop stops."""
    payloads = _randomized_payloads(seed, n=8)
    kw = dict(
        max_num_seqs=4, max_model_len=64, page_size=8,
        decode_chunk=4, decode_pipeline=2, admit_wave=4,
        prefix_reuse_min=8, num_pages=12,
        decode_compact_min_rows=1, decode_compact_hysteresis=2,
    )
    on, m_on, _ = _run_cohort(model, payloads, decode_compact=True, **kw)
    off, m_off, _ = _run_cohort(
        model, payloads, decode_compact=False, **kw
    )
    assert m_on["total_preemptions"] > 0, (
        "pool was not oversubscribed — the preempt/re-admit race under "
        "in-flight chunks never ran"
    )
    for i, (a, b) in enumerate(zip(on, off)):
        assert a["output_ids"] == b["output_ids"], f"req {i} tokens"
        assert a["output_logprobs"] == b["output_logprobs"], (
            f"req {i} logprobs"
        )
        assert (
            a["meta_info"]["finish_reason"]
            == b["meta_info"]["finish_reason"]
        ), f"req {i} finish reason"


def test_straggler_tail_dispatches_compact_rows(model):
    """Synthetic occupancy accounting (acceptance criterion): with 2
    stragglers left of a 64-slot cohort, decode chunks dispatch <= 4
    rows — asserted via the rows_dispatched gauge and histogram."""
    short = [
        {
            "input_ids": [i + 1] * 6,
            "sampling_params": {"max_new_tokens": 4, "greedy": True},
        }
        for i in range(62)
    ]
    long = [
        {
            "input_ids": [100 + i] * 6,
            "sampling_params": {"max_new_tokens": 96, "greedy": True},
        }
        for i in range(2)
    ]
    outs, metrics, hist = _run_cohort(
        model, short + long,
        max_num_seqs=64, max_model_len=128, page_size=8,
        decode_chunk=4, admit_wave=64,
        decode_compact_min_rows=2, decode_compact_hysteresis=2,
    )
    for o in outs[-2:]:
        assert len(o["output_ids"]) == 96
    # the tail (2 active of 64 slots) must compact: the LAST dispatched
    # chunk — stragglers only — paid for <= 4 rows, not 64
    assert metrics["decode_rows_dispatched"] <= 4, metrics
    # and the tail dominates the chunk count: most chunks ran compact
    tail_chunks = sum(c for b, c in hist.items() if b <= 4)
    assert tail_chunks >= 10, hist
    # lifetime accounting is consistent and the win is visible
    assert metrics["total_rows_dispatched"] < (
        metrics["total_decode_chunks"] * 64
    )
    assert 0 < metrics["decode_occupancy"] <= 1.0


def test_rows_bucket_hysteresis(model):
    """Bucket grows immediately (correctness), shrinks only after the
    configured streak (recompile damping), and never exceeds
    max_num_seqs."""
    cfg, params = model
    eng = GenerationEngine(
        JaxGenConfig(
            dtype="float32", max_num_seqs=16, max_model_len=32,
            page_size=8, decode_compact_min_rows=2,
            decode_compact_hysteresis=3,
        ),
        model_config=cfg,
        params=params,
    )
    assert eng._decode_rows_bucket(5) == 8
    # active drops: stays 8 for hysteresis-1 chunks, then shrinks
    assert eng._decode_rows_bucket(2) == 8
    assert eng._decode_rows_bucket(2) == 8
    assert eng._decode_rows_bucket(2) == 2
    # growth is immediate, jumping straight to the needed bucket
    assert eng._decode_rows_bucket(9) == 16
    # floor and cap
    assert eng._decode_rows_bucket(1) == 16  # streak 1
    assert eng._decode_rows_bucket(1) == 16  # streak 2
    assert eng._decode_rows_bucket(1) == 2  # floored at min_rows=2
    assert eng._decode_rows_bucket(100) == 16  # capped at max_num_seqs


def test_compact_disabled_dispatches_full_width(model):
    """decode_compact=False is the legacy full-slot dispatch: every
    chunk pays max_num_seqs rows (the A/B baseline shape)."""
    payloads = [
        {
            "input_ids": [7] * 5,
            "sampling_params": {"max_new_tokens": 8, "greedy": True},
        }
    ]
    _, metrics, hist = _run_cohort(
        model, payloads,
        max_num_seqs=8, max_model_len=64, page_size=8,
        decode_chunk=4, decode_compact=False,
    )
    assert set(hist) == {8}
    assert metrics["decode_rows_active"] <= 1


def test_compilation_cache_helper(tmp_path, monkeypatch):
    """enable_compilation_cache points jax at the directory (and with
    nothing asked for — empty dir string, no inherited
    JAX_COMPILATION_CACHE_DIR — it is a no-op returning False)."""
    from areal_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    assert not compile_cache.enable_compilation_cache("")
    d = str(tmp_path / "xla_cache")
    assert compile_cache.enable_compilation_cache(d)
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.enabled_dir() == d
    # idempotent re-enable
    assert compile_cache.enable_compilation_cache(d)
