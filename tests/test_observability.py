"""End-to-end observability plane: engine request-lifecycle spans
(queue-wait / prefill / decode / pause windows for a known rid), Chrome
trace-event export, the server's Prometheus /metrics and /trace drain
endpoints, the hot-loop no-op guard when tracing is off, and the
consumed-batch staleness histogram landing in StatsLogger JSONL."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from areal_tpu.api.cli_args import JaxGenConfig, SpecConfig, TracingConfig
from areal_tpu.inference.engine import GenerationEngine
from areal_tpu.inference.server import serve
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import init_params
from areal_tpu.utils import tracing as tracing_util


@pytest.fixture(scope="module")
def traced_engine():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gcfg = JaxGenConfig(
        dtype="float32", max_num_seqs=4, max_model_len=64,
        prefill_chunk=16,
        tracing=TracingConfig(enabled=True, max_spans=10_000),
    )
    eng = GenerationEngine(gcfg, model_config=cfg, params=params).start()
    httpd = serve(eng, host="127.0.0.1", port=0, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    yield eng, addr, cfg, params
    httpd.shutdown()
    eng.stop()


def _generate(eng, rid, max_new=4):
    return eng.generate(
        {
            "rid": rid,
            "input_ids": [1, 2, 3, 4, 5],
            "sampling_params": {"max_new_tokens": max_new},
        }
    )


class TestEngineSpans:
    def test_request_lifecycle_spans_for_known_rid(
        self, traced_engine, tmp_path
    ):
        eng, _, _, params = traced_engine
        eng.tracer.drain()  # isolate this test's timeline
        out = _generate(eng, "rid-lifecycle", max_new=4)
        assert len(out["output_ids"]) == 4
        # weight-update window: pause → swap (device path) → continue
        eng.pause()
        eng.update_weights_from_tensors(params, version=1)
        eng.continue_generation()

        spans = eng.tracer.snapshot()
        by_rid = {}
        for s in spans:
            by_rid.setdefault(s.rid, []).append(s.name)
        assert {"queue_wait", "prefill", "decode", "request"} <= set(
            by_rid["rid-lifecycle"]
        )
        assert "weight_update" in by_rid.get("__engine__", [])
        assert "pause_window" in by_rid.get("__engine__", [])
        # span ordering within the request lifecycle
        named = {
            s.name: s for s in spans if s.rid == "rid-lifecycle"
        }
        assert named["queue_wait"].t_end <= named["prefill"].t_start + 1e-6
        assert named["prefill"].t_start <= named["decode"].t_start
        assert named["request"].t_start <= named["queue_wait"].t_start + 1e-6
        assert named["request"].attrs["completion_tokens"] == 4
        assert named["prefill"].attrs["prompt_tokens"] == 5

        # exported Chrome trace validates against the trace-event schema
        path = str(tmp_path / "rollout_trace.json")
        eng.tracer.export_chrome(path)
        doc = json.load(open(path))
        xevents = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xevents, "trace must contain complete events"
        for e in xevents:
            assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
            assert e["dur"] >= 0
        names = {e["name"] for e in xevents}
        assert {"queue_wait", "prefill", "decode", "pause_window"} <= names
        eng.model_version = 0  # reset for fixture reuse

    def test_throughput_and_utilization_gauges(self, traced_engine):
        eng, _, _, _ = traced_engine
        _generate(eng, "rid-gauges", max_new=8)
        m = eng.metrics()
        assert 0.0 <= m["kv_page_utilization"] <= 1.0
        assert m["prefill_tokens_per_sec"] > 0
        assert m["decode_tokens_per_sec"] >= 0
        assert m["total_generated_tokens"] >= 8


class TestServerEndpoints:
    def test_metrics_prometheus_format(self, traced_engine):
        eng, addr, _, _ = traced_engine
        _generate(eng, "rid-metrics", max_new=2)
        with urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=30
        ) as r:
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        assert "# TYPE areal_tpu_gen_running_requests gauge" in text
        assert "# TYPE areal_tpu_gen_total_requests counter" in text
        assert "# HELP areal_tpu_gen_kv_page_utilization" in text
        for required in (
            "areal_tpu_gen_running_requests",
            "areal_tpu_gen_queued_requests",
            "areal_tpu_gen_kv_page_utilization",
            "areal_tpu_gen_decode_tokens_per_sec",
            "areal_tpu_gen_prefill_tokens_per_sec",
            "areal_tpu_gen_total_preemptions",
            "areal_tpu_gen_model_version",
            # r6 decode tail compaction occupancy gauges
            "areal_tpu_gen_decode_rows_dispatched",
            "areal_tpu_gen_decode_rows_active",
            "areal_tpu_gen_decode_occupancy",
            "areal_tpu_gen_total_decode_chunks",
            "areal_tpu_gen_total_rows_dispatched",
            "areal_tpu_gen_total_rows_active",
        ):
            assert any(
                line.startswith(required + " ")
                for line in text.splitlines()
            ), f"missing sample line for {required}"
        # lifetime row counters render as Prometheus counters
        assert "# TYPE areal_tpu_gen_total_rows_dispatched counter" in text

    def test_decode_chunk_occupancy_spans(self, traced_engine):
        """Compaction emits per-chunk rows_dispatched/rows_active attrs
        onto the trace timeline (what --occupancy summarizes)."""
        eng, _, _, _ = traced_engine
        eng.tracer.drain()
        _generate(eng, "rid-occupancy", max_new=8)
        chunks = [
            s for s in eng.tracer.snapshot() if s.name == "decode_chunk"
        ]
        assert chunks, "no decode_chunk spans recorded"
        for s in chunks:
            assert s.attrs["rows_dispatched"] >= s.attrs["rows_active"]
            assert s.attrs["rows_active"] >= 0

    def test_trace_endpoint_drains(self, traced_engine):
        eng, addr, _, _ = traced_engine
        eng.tracer.drain()
        _generate(eng, "rid-http-trace", max_new=2)
        with urllib.request.urlopen(
            f"http://{addr}/trace", timeout=30
        ) as r:
            doc = json.loads(r.read())
        rids = {
            e["args"]["rid"]
            for e in doc["traceEvents"]
            if e["ph"] == "X"
        }
        assert "rid-http-trace" in rids
        # the endpoint DRAINS: a second scrape starts empty — but for the
        # loop thread's own span around the chunk that finished the
        # request (decode.process closes after it resolved the future, so
        # it may land after the first scrape)
        with urllib.request.urlopen(
            f"http://{addr}/trace", timeout=30
        ) as r:
            doc2 = json.loads(r.read())
        assert [
            e for e in doc2["traceEvents"]
            if e["ph"] == "X" and e["args"]["rid"] != "__engine__"
        ] == []

    def test_trace_endpoint_jsonl(self, traced_engine):
        eng, addr, _, _ = traced_engine
        _generate(eng, "rid-jsonl", max_new=2)
        with urllib.request.urlopen(
            f"http://{addr}/trace?format=jsonl", timeout=30
        ) as r:
            lines = [
                json.loads(x)
                for x in r.read().decode().splitlines()
                if x.strip()
            ]
        assert any(s["rid"] == "rid-jsonl" for s in lines)
        assert all({"name", "rid", "ts", "dur"} <= set(s) for s in lines)


class TestSpecObservability:
    """Speculative-decoding gauges: present (and Prometheus-rendered)
    exactly when spec is configured; decode_chunk spans carry draft
    attrs and verify rounds emit spec_verify instants."""

    @pytest.fixture(scope="class")
    def spec_engine(self):
        cfg = tiny_config("qwen2")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        gcfg = JaxGenConfig(
            dtype="float32", max_num_seqs=4, max_model_len=256,
            prefill_chunk=16, page_size=8, decode_chunk=4,
            prefix_reuse_min=0,
            spec=SpecConfig(
                enabled=True, max_draft=3, ngram_min=2, ngram_max=3,
                accept_floor=0.0,
            ),
            tracing=TracingConfig(enabled=True, max_spans=10_000),
        )
        eng = GenerationEngine(gcfg, model_config=cfg, params=params).start()
        httpd = serve(eng, host="127.0.0.1", port=0, background=True)
        addr = f"127.0.0.1:{httpd.server_address[1]}"
        yield eng, addr
        httpd.shutdown()
        eng.stop()

    def test_spec_gauges_on_metrics_endpoint(self, spec_engine):
        eng, addr = spec_engine
        # long greedy run: tiny random models loop, so n-gram drafts
        # fire and accepted counts move
        eng.generate(
            {
                "rid": "rid-spec",
                "input_ids": [3, 9, 4, 1, 7, 2, 8, 6, 5, 11],
                "sampling_params": {"max_new_tokens": 80, "greedy": True},
            }
        )
        m = eng.metrics()
        assert m["spec_chunks_total"] > 0, "no verify dispatch ran"
        assert m["spec_draft_tokens_total"] > 0
        assert 0.0 <= m["spec_accept_rate"] <= 1.0
        with urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        for required in (
            "areal_tpu_gen_spec_enabled",
            "areal_tpu_gen_spec_accept_rate",
            "areal_tpu_gen_spec_draft_tokens_total",
            "areal_tpu_gen_spec_accepted_tokens_total",
            "areal_tpu_gen_spec_chunks_total",
        ):
            assert any(
                line.startswith(required + " ")
                for line in text.splitlines()
            ), f"missing sample line for {required}"
        assert "# TYPE areal_tpu_gen_spec_draft_tokens_total counter" in text

    def test_spec_spans_on_trace(self, spec_engine):
        eng, _ = spec_engine
        # self-sufficient traffic (must not depend on sibling tests
        # having already driven the shared engine)
        eng.generate(
            {
                "rid": "rid-spec-spans",
                "input_ids": [2, 8, 5, 1, 9, 3, 7, 4, 6, 12],
                "sampling_params": {"max_new_tokens": 80, "greedy": True},
            }
        )
        spans = eng.tracer.snapshot()
        verify = [s for s in spans if s.name == "spec_verify"]
        assert verify, "verify rounds must emit spec_verify instants"
        for s in verify:
            assert s.attrs["accepted"] <= s.attrs["drafted"]
        chunk_attrs = [
            s.attrs for s in spans
            if s.name == "decode_chunk" and "spec_draft_tokens" in s.attrs
        ]
        assert chunk_attrs, "verify decode_chunk spans carry draft attrs"
        for a in chunk_attrs:
            assert a["spec_draft_tokens"] >= a["spec_draft_rows"] >= 1

    def test_spec_off_metrics_have_no_spec_keys(self, traced_engine):
        eng, addr, _, _ = traced_engine
        assert not any(k.startswith("spec_") for k in eng.metrics())
        with urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        assert "areal_tpu_gen_spec_" not in text


class TestTraceContext:
    def test_generate_binds_trace_header_onto_spans(self, traced_engine):
        """X-Areal-Trace/X-Areal-Rid on /generate: the server's spans
        for that rid carry the episode's trace id (the stitch key)."""
        import urllib.request as _rq

        eng, addr, _, _ = traced_engine
        eng.tracer.drain()
        req = _rq.Request(
            f"http://{addr}/generate",
            data=json.dumps(
                {
                    "input_ids": [1, 2, 3],
                    "sampling_params": {"max_new_tokens": 2},
                }
            ).encode(),
            headers={
                "Content-Type": "application/json",
                "X-Areal-Trace": "trace-e2e",
                "X-Areal-Rid": "rid-hdr",
            },
        )
        with _rq.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        assert len(out["output_ids"]) == 2
        spans = [s for s in eng.tracer.snapshot() if s.rid == "rid-hdr"]
        assert spans, "header rid must name the request's spans"
        by_name = {s.name: s for s in spans}
        assert by_name["request"].attrs["trace"] == "trace-e2e"
        assert by_name["queue_wait"].attrs["trace"] == "trace-e2e"
        # completion unbinds: an unrelated later request is clean
        _generate(eng, "rid-hdr-2", max_new=2)
        later = [s for s in eng.tracer.snapshot() if s.rid == "rid-hdr-2"]
        assert later and all("trace" not in s.attrs for s in later)

    def test_dropped_spans_surface_on_metrics(self):
        """Satellite: ring overflow is counted and exported, so a
        truncated trace is visibly truncated."""
        from areal_tpu.utils.tracing import render_prometheus

        cfg = tiny_config("qwen2")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
                tracing=TracingConfig(enabled=True, max_spans=4),
            ),
            model_config=cfg, params=params,
        ).start()
        try:
            _generate(eng, "rid-overflow", max_new=8)
            m = eng.metrics()
            assert m["tracing_dropped_spans_total"] >= 1
            text = render_prometheus(m, prefix="areal_tpu_gen_")
            assert (
                "# TYPE areal_tpu_gen_tracing_dropped_spans_total counter"
                in text
            )
        finally:
            eng.stop()


class TestTelemetryHubLive:
    """The collector aggregates ≥2 LIVE server endpoints' /metrics into
    fleet-wide gauges, draining their /trace buffers along the way.
    (Two real server PROCESSES are covered end-to-end by
    test_failover.py::test_lineage_ledger_and_stitched_trace_across_kill;
    here a second HTTP shell fronts the same engine to keep tier-1
    cheap.)"""

    def test_collector_aggregates_two_live_servers(self, traced_engine):
        from areal_tpu.api.cli_args import TelemetryConfig
        from areal_tpu.utils.telemetry import TelemetryCollector

        eng1, addr1, _, _ = traced_engine
        httpd2 = serve(eng1, host="127.0.0.1", port=0, background=True)
        addr2 = f"127.0.0.1:{httpd2.server_address[1]}"
        try:
            _generate(eng1, "rid-hub-1", max_new=4)
            collector = TelemetryCollector(
                addresses=[addr1, addr2], config=TelemetryConfig()
            )
            collector.scrape_once()
            r = collector.rollup()
            assert r["servers_total"] == 2.0
            assert r["servers_scraped"] == 2.0
            assert r["generated_tokens_total"] >= 8
            assert 0.0 <= r["kv_page_utilization_mean"] <= 1.0
            assert r["queue_wait_samples"] >= 1  # /trace drained
            man = collector.manifest()
            assert set(man["servers"]) >= {addr1, addr2}
        finally:
            httpd2.shutdown()


class TestProfileEndpoint:
    def test_profile_captures_and_gates(self, traced_engine, tmp_path):
        """POST /profile?steps=N arms a jax.profiler capture of the next
        N busy loop iterations; the CLI gate (no --enable-profile)
        answers 403 — same contract as POST /chaos."""
        import urllib.error as _err
        import urllib.request as _rq

        eng, addr, _, _ = traced_engine
        out_dir = str(tmp_path / "prof")
        req = _rq.Request(
            f"http://{addr}/profile?steps=2",
            data=json.dumps({"out_dir": out_dir}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with _rq.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["success"] and out["steps"] == 2
        assert out["trace_dir"].startswith(out_dir)

        def run_out(rid):
            # drive busy iterations so the capture opens and closes
            _generate(eng, rid, max_new=4)
            deadline = __import__("time").monotonic() + 30
            while eng._profile_stack is not None or eng._profile_pending:
                assert __import__("time").monotonic() < deadline
                __import__("time").sleep(0.05)

        run_out("rid-profiled")
        # engine still serves, and a second capture can be armed
        _generate(eng, "rid-after-profile", max_new=2)

        # double-arm is an explicit error: a REAL capture is armed (the
        # engine is idle, and idle iterations end none, so it is pending or
        # running until the next request), never a fake planted on the
        # live engine, which its loop thread could pick up and run
        eng.request_profile(1, out_dir=str(tmp_path / "prof2"))
        with pytest.raises(RuntimeError):
            eng.request_profile(1)
        run_out("rid-profiled-again")

        # gated server: 403, nothing armed
        httpd = serve(
            eng, host="127.0.0.1", port=0, background=True,
            profile_endpoint=False,
        )
        gated = f"127.0.0.1:{httpd.server_address[1]}"
        try:
            req = _rq.Request(
                f"http://{gated}/profile?steps=1", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(_err.HTTPError) as ei:
                _rq.urlopen(req, timeout=30)
            assert ei.value.code == 403
            assert eng._profile_pending is None
        finally:
            httpd.shutdown()


class TestTrafficPlaneObservability:
    """r10 SLO traffic plane: the new metric families land on /metrics
    with HELP text, /health reports running vs queued separately, and
    trace_report --slo reads the class-tagged span stream."""

    def test_traffic_metrics_and_help_on_endpoint(self, traced_engine):
        eng, addr, _, _ = traced_engine
        _generate(eng, "rid-slo-metrics", max_new=2)
        with urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        for required in (
            "areal_tpu_gen_requests_shed_total",
            "areal_tpu_gen_deadline_preemptions_total",
            "areal_tpu_gen_deadline_misses_total",
            "areal_tpu_gen_sched_class_interactive_running",
            "areal_tpu_gen_sched_class_bulk_running",
            "areal_tpu_gen_sched_class_interactive_queued",
            "areal_tpu_gen_sched_class_bulk_queued",
            "areal_tpu_gen_sched_class_bulk_submitted_total",
        ):
            assert any(
                line.startswith(required + " ")
                for line in text.splitlines()
            ), f"missing sample line for {required}"
        assert "# HELP areal_tpu_gen_requests_shed_total" in text
        assert "# HELP areal_tpu_gen_deadline_preemptions_total" in text
        assert (
            "# TYPE areal_tpu_gen_requests_shed_total counter" in text
        )

    def test_health_reports_running_and_queued_separately(
        self, traced_engine
    ):
        _, addr, _, _ = traced_engine
        with urllib.request.urlopen(
            f"http://{addr}/health", timeout=30
        ) as r:
            body = json.loads(r.read())
        # r11 readiness: this module's engine may still be inside its
        # compile-quiet window (warming) or already latched (ok); either
        # way coverage rides along and the load view stays intact
        assert body["status"] in ("ok", "warming")
        assert "ladder_coverage" in body
        # separate fields, NOT one summed in_flight integer — the
        # autoscaler distinguishes backlog from busy decode
        assert body["running_requests"] == 0
        assert body["queued_requests"] == 0
        assert body["max_num_seqs"] == 4

    def test_trace_report_slo_reads_class_tagged_spans(
        self, traced_engine, tmp_path
    ):
        eng, _, _, _ = traced_engine
        eng.tracer.drain()
        _generate(eng, "rid-slo-report", max_new=2)
        path = str(tmp_path / "slo.jsonl")
        eng.tracer.export_jsonl(path)
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import trace_report

        assert trace_report.main([path, "--slo"]) == 0
        sl = trace_report.slo_summary(trace_report.load_spans(path))
        # a default-stamped request is bulk class with a measured wait
        assert "bulk" in sl["queue_wait_by_class"]
        assert sl["queue_wait_by_class"]["bulk"]["n"] >= 1
        # an eventless trace exits 1 (CI smoke contract)
        empty = str(tmp_path / "empty.jsonl")
        open(empty, "w").close()
        assert trace_report.main([empty, "--slo"]) == 1


def test_a_loop_that_dies_fails_its_callers_and_later_submits(traced_engine):
    """What a hang in this module was (ROADMAP C.14): an error inside the
    loop thread ended it and left ``generate`` on a future nothing resolved.
    The waiting request, a queued command and a later submit all get the
    error."""
    _, _, cfg, params = traced_engine
    eng = GenerationEngine(
        JaxGenConfig(dtype="float32", max_num_seqs=2, max_model_len=64,
                     prefill_chunk=16),
        model_config=cfg, params=params,
    )

    def broken():
        raise RuntimeError("the device went away")

    eng._admit = broken
    eng.start()
    try:
        fut = eng.submit({
            "rid": "lost", "input_ids": [1, 2, 3],
            "sampling_params": {"max_new_tokens": 2},
        })
        with pytest.raises(RuntimeError, match="device went away"):
            fut.result(timeout=60)
        with pytest.raises(RuntimeError, match="device went away"):
            _generate(eng, "after")
        assert not eng._running
    finally:
        eng.stop()


class TestDisabledNoOp:
    @pytest.fixture(scope="class")
    def plain_engine(self):
        cfg = tiny_config("qwen2")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=cfg, params=params,
        ).start()
        yield eng
        eng.stop()

    def test_no_spans_no_allocations(self, plain_engine):
        eng = plain_engine
        assert not eng.tracer.enabled
        _generate(eng, "rid-off", max_new=4)
        # nothing recorded anywhere on the scheduler path
        assert len(eng.tracer) == 0
        assert eng.metrics()["trace_spans"] == 0
        # the loop thread's spans ran (its host-second counters read them)
        # and left the disabled ring untouched
        with eng.tracer.span("decode", "r"):
            pass
        assert len(eng.tracer) == 0
        assert eng.metrics()["total_loop_iterations"] > 0


class TestStalenessInStatsLogger:
    def test_histogram_lands_in_jsonl(self, tmp_path, no_tensorboard):
        from areal_tpu.api.cli_args import PPOActorConfig
        from areal_tpu.engine.ppo.actor import PPOActor
        from areal_tpu.utils import stats_tracker
        from areal_tpu.utils.stats_logger import StatsLogger

        class _Trainer:  # only get_version is consulted
            def get_version(self):
                return 3

        actor = PPOActor(PPOActorConfig(), _Trainer())
        B, L, plen = 4, 12, 4
        olen = L - plen
        versions = np.full((B, L), -1, np.int32)
        # consumed tokens generated at versions 3,3,2,1 → lags 0,0,1,2
        for i, v in enumerate([3, 3, 2, 1]):
            versions[i, plen:] = v
        batch = {
            "input_ids": np.ones((B, L), np.int32),
            "attention_mask": np.ones((B, L), np.bool_),
            "loss_mask": np.asarray(
                [[0] * plen + [1] * olen] * B, np.int32
            ),
            "logprobs": np.zeros((B, L), np.float32),
            "versions": versions,
            "rewards": np.asarray([1.0, 0.0, 1.0, 0.0], np.float32),
        }
        stats_tracker.export_all()  # clear anything other tests left
        actor.compute_advantages(dict(batch))
        stats = stats_tracker.export_all()
        assert stats["staleness/lag0_frac"] == pytest.approx(0.5)
        assert stats["staleness/lag1_frac"] == pytest.approx(0.25)
        assert stats["staleness/lag2_frac"] == pytest.approx(0.25)
        assert stats["staleness/lag_mean"] == pytest.approx(0.75)
        assert stats["staleness/lag_max"] == 2.0
        assert stats["staleness/n_tokens"] == B * olen

        # ...and a train-step commit persists it as parseable JSONL
        slog = StatsLogger("obs", "t0", str(tmp_path))
        slog.commit(0, 0, 0, stats)
        slog.close()
        line = open(
            os.path.join(str(tmp_path), "obs", "t0", "stats.jsonl")
        ).read().strip()
        rec = json.loads(line)
        assert rec["staleness/lag_mean"] == pytest.approx(0.75)
        assert {
            "staleness/lag0_frac", "staleness/lag1_frac",
            "staleness/lag_ge4_frac", "staleness/lag_max",
        } <= set(rec)
