"""Zero-pause weight plane (r13): double-buffered streamed updates +
trajectory-level staleness admission.

The acceptance story: a chunked weight push lands on a server serving
LIVE decode traffic and (a) emits ZERO pause spans, (b) every in-flight
sequence completes with a correctly fenced per-token weight version —
the pinned request's greedy stream is BIT-IDENTICAL to a pure-old-
version engine while a concurrent post-flip request matches a
pure-new-version engine, (c) the old buffer is dropped the moment its
last pinned request drains, and (d) an abandoned mid-push stream (dead
client) is TTL-swept and a retry with a different FFD chunking re-keys
the staging and converges.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import (
    InferenceEngineConfig,
    JaxGenConfig,
    TracingConfig,
    WeightTransferConfig,
)
from areal_tpu.api.workflow_api import RolloutWorkflow, WorkflowExecutor
from areal_tpu.inference.engine import GenerationEngine
from areal_tpu.inference.weights import WeightStore
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import init_params
from areal_tpu.utils import weight_transfer as wt


MODEL_CFG = tiny_config("qwen2")


@pytest.fixture(scope="module")
def param_sets():
    p0 = init_params(MODEL_CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    p1 = init_params(MODEL_CFG, jax.random.PRNGKey(7), dtype=jnp.float32)
    return jax.device_get(p0), jax.device_get(p1)


def _gen_cfg(**kw) -> JaxGenConfig:
    base = dict(
        dtype="float32", max_num_seqs=4, max_model_len=2048,
        prefill_chunk=16, decode_chunk=4, num_pages=48, page_size=64,
        tracing=TracingConfig(enabled=True),
    )
    base.update(kw)
    return JaxGenConfig(**base)


def _greedy(eng, rid, ids, n, timeout=300):
    return eng.generate(
        {
            "rid": rid,
            "input_ids": list(ids),
            "sampling_params": {"max_new_tokens": n, "greedy": True},
        },
        timeout=timeout,
    )


def _push_chunks(eng, params, version, chunk_bytes=64 * 1024):
    """Stream one full chunked push through the real wire format."""
    leaves = [(k, np.asarray(v)) for k, v in wt.flatten_params(params)]
    plan = wt.chunk_leaves(leaves, chunk_bytes)
    n = len(plan)
    out = None
    for i, items in enumerate(plan):
        body = wt.encode_chunk(version, i, n, items)
        header, arrays = wt.decode_chunk(body)
        out = eng.update_weights_chunk(header, arrays)
    return out, n


def _wait_decoding(eng, deadline_s=60.0):
    """Block until some active request has emitted at least one token —
    the flip-under-live-decode premise, made timing-independent."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        reqs = list(eng._active.values())
        if reqs and any(len(r.output_ids) > 0 for r in reqs):
            return
        time.sleep(0.01)
    raise AssertionError("request never started decoding")


def _hold_decode_until_a_flip_is_queued(eng):
    """From now until a weight flip is queued the loop thread waits at the
    top of its iteration: the in-flight request cannot run to its length
    first, however long the copy that precedes the flip takes on a busy
    machine (420 tokens of this model are a third of a second)."""
    queued = threading.Event()
    queue_flip, top = eng.weights.queue_flip, eng._maybe_start_profile

    def flip(*args, **kwargs):
        fut = queue_flip(*args, **kwargs)
        queued.set()
        return fut

    def gated():
        queued.wait(timeout=300)
        return top()

    eng.weights.queue_flip, eng._maybe_start_profile = flip, gated


# ---------------------------------------------------------------------------
# Streamed flip under live decode: zero pause, exact version fence
# ---------------------------------------------------------------------------
def test_streamed_push_under_live_decode_zero_pause_pin_fence(param_sets):
    p0, p1 = param_sets
    eng = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p0
    ).start()
    try:
        _greedy(eng, "warm", [1, 2, 3], 8)
        fut = eng.submit(
            {
                "rid": "pinned",
                "input_ids": [5, 6, 7],
                "sampling_params": {"max_new_tokens": 440, "greedy": True},
            }
        )
        _wait_decoding(eng)
        out, n_chunks = _push_chunks(eng, p1, version=5)
        assert out == {"version": 5, "complete": True}
        assert n_chunks >= 3, "pick chunk_bytes small enough to stream"
        assert eng.model_version == 5
        m = eng.metrics()
        assert m["weight_flips_total"] == 1.0
        assert m["paused"] == 0.0
        # the in-flight request is pinned: old buffer retained
        assert m["weight_pinned_requests"] == 1.0
        assert m["weight_buffer_versions"] == 1.0
        # a post-flip request decodes on the new weights concurrently
        newer = _greedy(eng, "post-flip", [9, 8, 7], 32, timeout=120)
        assert set(newer["output_versions"]) == {5}
        pinned = fut.result(timeout=300)
        # fence: every pinned token carries the OLD version, end to end
        assert set(pinned["output_versions"]) == {0}
        assert pinned["meta_info"]["finish_reason"]["type"] == "length"
        assert len(pinned["output_ids"]) == 440
        assert pinned["meta_info"]["preemptions"] == 0
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            m = eng.metrics()
            if m["weight_pinned_requests"] == 0.0:
                break
            time.sleep(0.05)
        # last pin out drops the buffer (HBM back)
        assert m["weight_pinned_requests"] == 0.0
        assert m["weight_buffer_versions"] == 0.0
        assert m["total_aborted"] == 0, "zero-pause = zero aborts"
        # ZERO pause spans; the plane's own spans present instead
        names = [s.name for s in eng.tracer.snapshot()]
        assert "pause_window" not in names
        assert "weight_update_pause" not in names
        assert "weight_flip" in names
        assert names.count("weight_stream_chunk") == n_chunks
    finally:
        eng.stop()

    # bit-exact pin fence: the pinned stream matches a pure-v0 engine,
    # the post-flip stream matches a pure-v1 engine
    ref0 = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p0
    ).start()
    try:
        r0 = _greedy(ref0, "ref0", [5, 6, 7], 440)
        assert pinned["output_ids"] == r0["output_ids"]
    finally:
        ref0.stop()
    ref1 = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p1
    ).start()
    try:
        r1 = _greedy(ref1, "ref1", [9, 8, 7], 32)
        assert newer["output_ids"] == r1["output_ids"]
    finally:
        ref1.stop()


def test_resume_policy_aborts_into_suffix_resume(param_sets):
    p0, p1 = param_sets
    cfg = _gen_cfg()
    cfg.weights = WeightTransferConfig(flip_policy="resume")
    eng = GenerationEngine(cfg, model_config=MODEL_CFG, params=p0).start()
    try:
        _greedy(eng, "warm", [1, 2, 3], 8)
        fut = eng.submit(
            {
                "rid": "moved",
                "input_ids": [5, 6, 7],
                "sampling_params": {"max_new_tokens": 420, "greedy": True},
            }
        )
        _wait_decoding(eng)
        _hold_decode_until_a_flip_is_queued(eng)
        v = eng.update_weights_from_tensors(p1, version=3)
        assert v == 3
        first = fut.result(timeout=120)
        # the in-flight request resolved as an abort (suffix-resume
        # contract) with its pre-flip tokens stamped v0
        assert first["meta_info"]["finish_reason"]["type"] == "abort"
        assert set(first["output_versions"]) <= {0}
        # the client-side resume: accumulated tokens re-submitted, the
        # continuation decodes on v3 — the RECORDED switch
        cont = _greedy(
            eng, "moved",
            [5, 6, 7] + first["output_ids"],
            420 - len(first["output_ids"]),
        )
        assert set(cont["output_versions"]) == {3}
        names = [s.name for s in eng.tracer.snapshot()]
        assert "pause_window" not in names
        assert "weight_update_pause" not in names
        # no pins in resume mode: nothing retains the old buffer
        assert eng.metrics()["weight_buffer_versions"] == 0.0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Staging: re-key, TTL, abandoned-stream retry convergence
# ---------------------------------------------------------------------------
def test_abandoned_stream_rekey_retry_converges(param_sets):
    """Chaos: the client dies mid-push (chunks 0..k of n staged, never
    completed), then retries the SAME version with a different FFD
    grouping. The re-key must discard the stale leaves and the retry
    must converge to exactly the retried weights."""
    p0, p1 = param_sets
    eng = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p0
    ).start()
    try:
        leaves = [(k, np.asarray(v)) for k, v in wt.flatten_params(p1)]
        plan = wt.chunk_leaves(leaves, 32 * 1024)
        n = len(plan)
        assert n >= 4
        # partial push: client "dies" after n-2 chunks
        for i in range(n - 2):
            header, arrays = wt.decode_chunk(
                wt.encode_chunk(4, i, n, plan[i])
            )
            out = eng.update_weights_chunk(header, arrays)
            assert out == {"staged": i + 1}
        assert eng.metrics()["weight_staging_bytes"] > 0
        assert eng.model_version == 0  # nothing flipped
        # retry with a coarser chunking → different n_chunks → re-key
        out, _ = _push_chunks(eng, p1, version=4, chunk_bytes=256 * 1024)
        assert out == {"version": 4, "complete": True}
        m = eng.metrics()
        assert m["weight_staging_bytes"] == 0
        assert m["weight_staging_aborts_total"] >= 1.0
        got = _greedy(eng, "after-retry", [2, 4, 6], 24)
    finally:
        eng.stop()
    ref = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p1
    ).start()
    try:
        want = _greedy(ref, "want", [2, 4, 6], 24)
        assert got["output_ids"] == want["output_ids"]
    finally:
        ref.stop()


def test_legacy_paused_stage_key_rekey_branch(param_sets):
    """The LEGACY (streaming=False) command-queue ingest keeps the same
    re-key contract: a retry with a different FFD grouping must discard
    stale staged leaves instead of merging two inconsistent streams
    (the engine.py stage_key branch)."""
    p0, p1 = param_sets
    cfg = _gen_cfg()
    cfg.weights = WeightTransferConfig(streaming=False)
    eng = GenerationEngine(cfg, model_config=MODEL_CFG, params=p0).start()
    try:
        leaves = [(k, np.asarray(v)) for k, v in wt.flatten_params(p1)]
        plan = wt.chunk_leaves(leaves, 32 * 1024)
        n = len(plan)
        for i in range(n - 2):  # abandoned fine-grained push
            header, arrays = wt.decode_chunk(
                wt.encode_chunk(6, i, n, plan[i])
            )
            eng.update_weights_chunk(header, arrays)
        assert eng._staged, "legacy staging holds the partial push"
        out, _ = _push_chunks(eng, p1, version=6, chunk_bytes=256 * 1024)
        assert out == {"version": 6, "complete": True}
        assert eng.model_version == 6
        assert not eng._staged
        got = _greedy(eng, "legacy-after", [2, 4, 6], 24)
    finally:
        eng.stop()
    ref = GenerationEngine(
        _gen_cfg(), model_config=MODEL_CFG, params=p1
    ).start()
    try:
        want = _greedy(ref, "legacy-want", [2, 4, 6], 24)
        assert got["output_ids"] == want["output_ids"]
    finally:
        ref.stop()


def test_legacy_server_streamed_client_fences_unpaused_swap(param_sets):
    """A streamed client never pauses; a --no-weight-streaming server
    receiving that push mid-decode must ABORT in-flight slots into the
    suffix-resume contract before the legacy swap — silently continuing
    on old KV + new weights (unpinned, mis-stamped) would corrupt the
    version fence."""
    p0, p1 = param_sets
    cfg = _gen_cfg()
    cfg.weights = WeightTransferConfig(streaming=False)
    eng = GenerationEngine(cfg, model_config=MODEL_CFG, params=p0).start()
    try:
        _greedy(eng, "warm", [1, 2, 3], 8)
        fut = eng.submit(
            {
                "rid": "live",
                "input_ids": [5, 6, 7],
                "sampling_params": {"max_new_tokens": 420, "greedy": True},
            }
        )
        _wait_decoding(eng)
        # no pause_generation — exactly what a streamed client does
        out, _ = _push_chunks(eng, p1, version=2, chunk_bytes=256 * 1024)
        assert out == {"version": 2, "complete": True}
        res = fut.result(timeout=120)
        assert res["meta_info"]["finish_reason"]["type"] == "abort"
        assert set(res["output_versions"]) <= {0}
        after = _greedy(eng, "after", [9, 9, 9], 8)
        assert set(after["output_versions"]) == {2}
    finally:
        eng.stop()


def test_store_close_fails_pending_and_future_flips():
    store = WeightStore()
    pending = store.queue_flip(5, {"w": 1})
    store.close()
    with pytest.raises(RuntimeError, match="stopped"):
        pending.result(timeout=1)
    # a flip queued after close (stop() raced an ingest) fails FAST
    # instead of blocking its caller out a 600 s result() timeout
    late = store.queue_flip(6, {"w": 2})
    with pytest.raises(RuntimeError, match="closed"):
        late.result(timeout=1)


def test_weight_store_staging_ttl_and_flip_queue():
    clock = [0.0]
    store = WeightStore(staging_ttl_s=10.0, clock=lambda: clock[0])
    header = {
        "version": 2, "chunk_index": 0, "n_chunks": 3,
        "params": [{"name": "a", "nbytes": 64}],
    }
    out = store.ingest_chunk(
        header, {"a": np.zeros(16, np.float32)}, lambda n, a: a
    )
    assert out is None
    assert store.staging_bytes == 64
    # TTL: the abandoned stream is swept, visibly
    clock[0] = 11.0
    store.sweep()
    assert store.staging_bytes == 0
    assert store.staging_aborts_total == 1
    # a later flip superseding an unapplied one fails the old future
    f1 = store.queue_flip(3, {"w": 1})
    f2 = store.queue_flip(4, {"w": 2})
    with pytest.raises(RuntimeError, match="superseded"):
        f1.result(timeout=1)
    version, params, fut = store.take_flip()
    assert (version, params) == (4, {"w": 2})
    assert fut is f2
    # pin lifecycle: buffer lives exactly as long as its pins
    store.retain(3, {"old": True})
    store.retain(3, {"old": True})
    assert store.pinned_requests() == 2
    store.release(3)
    assert store.params_for(3) is not None
    store.release(3)
    assert store.params_for(3) is None
    assert store.buffer_versions() == []


# ---------------------------------------------------------------------------
# Trajectory-level staleness admission (WorkflowExecutor)
# ---------------------------------------------------------------------------
class _StubInferEngine:
    def __init__(self, version=0):
        self._version = version
        self.tracer = None

    def get_version(self):
        return self._version

    def set_version(self, v):
        self._version = v


class _VersionedWorkflow(RolloutWorkflow):
    """Returns a 1-row batch whose per-token versions are data-driven —
    the trajectory fence's fallback input when no ledger record has
    segments."""

    async def arun_episode(self, engine, data):
        v = int(data["version"])
        return {
            "input_ids": np.asarray([[1, 2, 3, 4]], np.int32),
            "attention_mask": np.ones((1, 4), np.bool_),
            "rewards": np.asarray([1.0], np.float32),
            "versions": np.asarray([[-1, -1, v, v]], np.int32),
        }


def _executor(mode, eta=0, version=0):
    cfg = InferenceEngineConfig(
        consumer_batch_size=1, max_concurrent_rollouts=4,
        max_head_offpolicyness=eta, request_timeout=30,
        staleness_mode=mode,
    )
    eng = _StubInferEngine(version=version)
    ex = WorkflowExecutor(cfg, eng).initialize()
    return ex, eng


def test_trajectory_mode_drops_stale_samples_and_backfills():
    ex, eng = _executor("trajectory", eta=0, version=3)
    try:
        wf = _VersionedWorkflow()
        # a sample whose tokens came from v2 while the trainer is at v3
        # and eta=0: must be DROPPED at consumption, not delivered
        assert ex.submit({"qid": "q-stale", "version": 2}, wf)
        with pytest.raises(TimeoutError):
            ex.wait(count=1, timeout=2)
        assert ex.rollout_stat.stale_dropped == 1
        assert ex.rollout_stat.accepted == 0  # budget released
        # a fresh sample sails through
        assert ex.submit({"qid": "q-fresh", "version": 3}, wf)
        batch = ex.wait(count=1, timeout=15)
        assert batch["rewards"].shape[0] == 1
        assert ex.rollout_stat.stale_dropped == 1
    finally:
        ex.destroy()


def test_trajectory_mode_capacity_ignores_version_gate():
    # step mode at version 0 / eta 0: capacity is version-bounded
    ex_step, _ = _executor("step", eta=0, version=0)
    try:
        assert ex_step.get_capacity() == 1  # (0+0+1)*1 - 0
    finally:
        ex_step.destroy()
    # trajectory mode: concurrency-bounded only — the fence moved to
    # consumption
    ex_tr, _ = _executor("trajectory", eta=0, version=0)
    try:
        assert ex_tr.get_capacity() == 4
    finally:
        ex_tr.destroy()


def test_step_mode_still_delivers_stale_samples():
    """Control: the legacy mode has no consumption fence — behavior
    unchanged (its gate acts at admission via version arithmetic)."""
    ex, eng = _executor("step", eta=8, version=3)
    try:
        wf = _VersionedWorkflow()
        assert ex.submit({"qid": "q", "version": 0}, wf)
        batch = ex.wait(count=1, timeout=15)
        assert batch["rewards"].shape[0] == 1
        assert ex.rollout_stat.stale_dropped == 0
    finally:
        ex.destroy()


def test_invalid_staleness_mode_raises():
    cfg = InferenceEngineConfig(staleness_mode="bogus")
    with pytest.raises(ValueError, match="staleness_mode"):
        WorkflowExecutor(cfg, _StubInferEngine())


# ---------------------------------------------------------------------------
# trace_report --weights / --require-zero-pause
# ---------------------------------------------------------------------------
def test_trace_report_weights_and_zero_pause_gate(tmp_path, capsys):
    from tools import trace_report

    clean = tmp_path / "streamed.jsonl"
    spans = [
        {
            "name": "weight_stream_chunk", "rid": "__engine__",
            "ts": 1.0, "dur": 0.2,
            "attrs": {
                "chunk_index": 0, "n_chunks": 2, "leaves": 3,
                "bytes": 1000, "model_version": 5,
            },
        },
        {
            "name": "weight_stream_chunk", "rid": "__engine__",
            "ts": 1.3, "dur": 0.1,
            "attrs": {
                "chunk_index": 1, "n_chunks": 2, "leaves": 2,
                "bytes": 500, "model_version": 5,
            },
        },
        {
            "name": "weight_flip", "rid": "__engine__",
            "ts": 1.5, "dur": 0.0,
            "attrs": {
                "model_version": 5, "policy": "pin", "pinned": 2,
                "flip_ms": 0.4,
            },
        },
        {
            "name": "weight_stream", "rid": "__controller__",
            "ts": 0.9, "dur": 0.7, "attrs": {"model_version": 5},
        },
    ]
    clean.write_text(
        "\n".join(json.dumps(s) for s in spans) + "\n"
    )
    assert trace_report.main([
        str(clean), "--weights", "--require-zero-pause", "--json",
    ]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["pause_spans"] == 0
    assert rep["pushes"][0]["chunks"] == 2
    assert rep["pushes"][0]["bytes"] == 1500
    assert rep["pushes"][0]["flip_ms"] == 0.4
    assert rep["pushes"][0]["policy"] == "pin"
    # a paused push fails the gate
    dirty = tmp_path / "paused.jsonl"
    dirty.write_text(
        clean.read_text()
        + json.dumps(
            {"name": "pause_window", "rid": "__engine__",
             "ts": 2.0, "dur": 1.0, "attrs": {}}
        )
        + "\n"
    )
    assert trace_report.main([
        str(dirty), "--weights", "--require-zero-pause", "--json",
    ]) == 1
    capsys.readouterr()
    # without the gate the report still renders (census visible)
    assert trace_report.main([str(dirty), "--weights", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["pause_spans"] == 1


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------
def test_build_cmd_emits_weight_plane_flags():
    cfg = JaxGenConfig(model_path="/m")
    cmd = " ".join(JaxGenConfig.build_cmd(cfg, "h", 1))
    assert "--weight-flip-policy=pin" in cmd
    assert "--weight-staging-ttl=120.0" in cmd
    assert "--no-weight-streaming" not in cmd
    cfg.weights.streaming = False
    cfg.weights.flip_policy = "resume"
    cmd = " ".join(JaxGenConfig.build_cmd(cfg, "h", 1))
    assert "--no-weight-streaming" in cmd
    assert "--weight-flip-policy=resume" in cmd


def test_bad_flip_policy_rejected_at_init(param_sets):
    p0, _ = param_sets
    cfg = _gen_cfg()
    cfg.weights = WeightTransferConfig(flip_policy="yolo")
    with pytest.raises(ValueError, match="flip_policy"):
        GenerationEngine(cfg, model_config=MODEL_CFG, params=p0)
