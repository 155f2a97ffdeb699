"""SpanTracer unit behavior: recording, bounded memory, thread safety,
no-op guarantees when disabled, Chrome trace-event export schema,
Prometheus text rendering, and the trace_report summarizer."""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from areal_tpu.api.cli_args import TracingConfig
from areal_tpu.utils import tracing
from areal_tpu.utils.tracing import SpanTracer, render_prometheus


def _enabled_tracer(max_spans: int = 1000) -> SpanTracer:
    return SpanTracer(TracingConfig(enabled=True, max_spans=max_spans))


class TestSpanTracer:
    def test_record_and_drain(self):
        t = _enabled_tracer()
        t.record("prefill", "r1", 1.0, 1.5, slot=3)
        with t.span("decode", "r1", tokens=7):
            pass
        t.instant("preempt", "r1")
        assert len(t) == 3
        spans = t.drain()
        assert len(t) == 0  # drained
        names = [s.name for s in spans]
        assert names == ["prefill", "decode", "preempt"]
        assert spans[0].duration == pytest.approx(0.5)
        assert spans[0].attrs == {"slot": 3}
        assert spans[2].duration == 0.0

    def test_bounded_memory(self):
        t = _enabled_tracer(max_spans=10)
        for i in range(25):
            t.record("s", f"r{i}", 0.0, 1.0)
        assert len(t) == 10
        assert t.dropped == 15
        # oldest dropped, newest kept
        assert t.snapshot()[-1].rid == "r24"

    def test_disabled_is_noop(self):
        t = SpanTracer(TracingConfig(enabled=False))
        assert not t.enabled
        # span() is the module's one primitive: in this process (jax is
        # loaded, a profile may run) it still times its body, callers count
        # host seconds from it, but the ring stays untouched. Without jax
        # it is the shared null object: TestWithoutJax below
        with t.span("decode", "r1", tokens=1) as cm1:
            pass
        assert cm1.duration >= 0.0
        t.record("x", "r", 0.0, 1.0)
        t.instant("y", "r")
        assert len(t) == 0
        assert t.drain() == []

    def test_default_config_is_disabled(self):
        assert not SpanTracer().enabled

    def test_thread_safety(self):
        t = _enabled_tracer(max_spans=100_000)
        n_threads, per = 8, 500

        def work(i):
            for j in range(per):
                t.record("s", f"t{i}-{j}", 0.0, 1.0)

        threads = [
            threading.Thread(target=work, args=(i,))
            for i in range(n_threads)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(t) == n_threads * per

    def test_span_ctx_measures_wall_time(self):
        t = _enabled_tracer()
        with t.span("sleepy", "r1"):
            time.sleep(0.02)
        (s,) = t.snapshot()
        assert s.duration >= 0.015


class TestChromeExport:
    def test_schema(self, tmp_path):
        t = _enabled_tracer()
        t.record("queue_wait", "rid-A", 1.0, 1.1)
        t.record("prefill", "rid-A", 1.1, 1.3, slot=0)
        t.record("decode", "rid-B", 1.3, 2.0)
        path = str(tmp_path / "trace.json")
        t.export_chrome(path)
        doc = json.load(open(path))
        assert "traceEvents" in doc
        xevents = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xevents) == 3
        for e in xevents:
            # required trace-event fields for a complete event
            assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
            assert isinstance(e["ts"], float)
            assert e["dur"] >= 0
            assert e["args"]["rid"] in ("rid-A", "rid-B")
        # one row (tid) per rid, named via metadata events
        tids = {e["args"]["rid"]: e["tid"] for e in xevents}
        assert len(set(tids.values())) == 2
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} == {"rid-A", "rid-B"}

    def test_flush_to_export_path(self, tmp_path):
        path = str(tmp_path / "sink.jsonl")
        t = SpanTracer(
            TracingConfig(enabled=True, export_path=path)
        )
        t.record("a", "r1", 0.0, 0.5)
        t.flush()
        assert len(t) == 0  # flush drains
        t.record("b", "r2", 1.0, 1.5)
        t.flush()  # appends
        lines = [json.loads(x) for x in open(path) if x.strip()]
        assert [s["name"] for s in lines] == ["a", "b"]
        # no export_path configured → flush is a no-op
        t2 = SpanTracer(TracingConfig(enabled=True))
        t2.record("c", "r", 0.0, 1.0)
        t2.flush()
        assert len(t2) == 1

    def test_jsonl_roundtrip(self, tmp_path):
        t = _enabled_tracer()
        t.record("a", "r1", 0.0, 0.25, k="v")
        path = str(tmp_path / "spans.jsonl")
        t.export_jsonl(path, drain=True)
        assert len(t) == 0
        lines = [json.loads(x) for x in open(path) if x.strip()]
        assert lines == [
            {"name": "a", "rid": "r1", "ts": 0.0, "dur": 0.25,
             "attrs": {"k": "v"}}
        ]


class TestRenderPrometheus:
    def test_format(self):
        text = render_prometheus(
            {"running_requests": 3, "total_requests": 11,
             "kv_page_utilization": 0.25},
            prefix="areal_tpu_gen_",
            help_text={"running_requests": "live requests"},
        )
        assert "# HELP areal_tpu_gen_running_requests live requests" in text
        assert "# TYPE areal_tpu_gen_running_requests gauge" in text
        assert "# TYPE areal_tpu_gen_total_requests counter" in text
        assert "areal_tpu_gen_running_requests 3\n" in text
        assert "areal_tpu_gen_kv_page_utilization 0.25" in text
        assert text.endswith("\n")

    def test_nonfinite_values(self):
        text = render_prometheus(
            {"a": float("nan"), "b": float("inf"), "c": float("-inf")}
        )
        assert "a NaN" in text and "b +Inf" in text and "c -Inf" in text

    def test_type_override(self):
        text = render_prometheus(
            {"accepted": 5}, types={"accepted": "counter"}
        )
        assert "# TYPE accepted counter" in text


class TestTraceReport:
    def _write_synthetic(self, tmp_path):
        t = _enabled_tracer()
        for i in range(20):
            t.record("queue_wait", f"r{i}", i * 1.0, i * 1.0 + 0.001 * i)
            t.record("prefill", f"r{i}", i + 0.1, i + 0.15)
            t.record("decode", f"r{i}", i + 0.15, i + 0.9)
        t.record("pause_window", "__engine__", 5.0, 5.6)
        path = str(tmp_path / "trace.jsonl")
        t.export_jsonl(path)
        return path, t

    def test_summarize_jsonl(self, tmp_path):
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import trace_report

        path, _ = self._write_synthetic(tmp_path)
        spans = trace_report.load_spans(path)
        summary = trace_report.summarize(spans)
        assert set(summary) == {
            "queue_wait", "prefill", "decode", "pause_window",
        }
        assert summary["decode"]["count"] == 20
        assert summary["decode"]["p50"] == pytest.approx(0.75)
        assert summary["pause_window"]["total"] == pytest.approx(0.6)
        # p95 >= p50 always
        for st in summary.values():
            assert st["p95"] >= st["p50"]
        table = trace_report.format_table(summary)
        assert "queue_wait" in table and "p95_ms" in table

    def test_chrome_input_and_cli_smoke(self, tmp_path, capsys):
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import trace_report

        _, t = self._write_synthetic(tmp_path)
        chrome = str(tmp_path / "trace.json")
        t.export_chrome(chrome)
        rc = trace_report.main(
            [chrome, "--require", "queue_wait,prefill,decode"]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "decode" in out
        # a missing required phase fails the CI smoke check
        rc = trace_report.main([chrome, "--require", "nonexistent_phase"])
        assert rc == 1

    def test_occupancy_mode(self, tmp_path, capsys):
        """--occupancy summarizes decode_chunk rows_dispatched /
        rows_active gauges from BOTH export formats, and fails the CI
        smoke when a trace carries none."""
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import trace_report

        t = _enabled_tracer()
        # 3 full chunks at 64 rows, 5 compacted tail chunks at 4 rows
        for _ in range(3):
            t.instant(
                "decode_chunk", "__engine__",
                rows_dispatched=64, rows_active=60, steps=8,
            )
        for _ in range(5):
            t.instant(
                "decode_chunk", "__engine__",
                rows_dispatched=4, rows_active=2, steps=8,
            )
        t.record("decode", "r0", 0.0, 1.0)  # unrelated span is ignored
        jsonl = str(tmp_path / "occ.jsonl")
        chrome = str(tmp_path / "occ.json")
        t.export_jsonl(jsonl)
        t.export_chrome(chrome)
        for path in (jsonl, chrome):
            occ = trace_report.occupancy_summary(
                trace_report.load_spans(path)
            )
            assert occ["chunks"] == 8
            assert occ["rows_dispatched"] == 3 * 64 + 5 * 4
            assert occ["rows_active"] == 3 * 60 + 5 * 2
            assert occ["rows_dispatched_hist"] == {"4": 5, "64": 3}
            assert occ["occupancy"] == pytest.approx(
                (3 * 60 + 5 * 2) / (3 * 64 + 5 * 4), abs=1e-4
            )
        rc = trace_report.main([jsonl, "--occupancy"])
        assert rc == 0
        assert "mean occupancy" in capsys.readouterr().out
        # a trace with no occupancy gauges fails the smoke check
        bare = _enabled_tracer()
        bare.record("decode", "r0", 0.0, 1.0)
        empty = str(tmp_path / "bare.jsonl")
        bare.export_jsonl(empty)
        assert trace_report.main([empty, "--occupancy"]) == 1


# --------------------------------------------------------------------------
# The one span primitive: tree, threads, profile
# --------------------------------------------------------------------------
def _profile_events(trace_dir, prefix="areal:"):
    """{event name: [stats dict, ...]} of the host spans in a CPU profile."""
    import glob

    from jax.profiler import ProfileData

    path = max(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                               "*.xplane.pb")),
        key=os.path.getmtime,
    )
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


class TestSpanPrimitive:
    def test_nesting_records_parents_and_children_fit_inside(self):
        t = _enabled_tracer()
        with tracing.span("outer", t, "row", step=1) as outer:
            with tracing.span("a", t, "row"):
                time.sleep(0.01)
            with tracing.span("b", t, "row") as b:
                with tracing.span("leaf", t, "row") as leaf:
                    leaf.set(real_tokens=5)
                    time.sleep(0.01)
        by = {s.name: s for s in t.snapshot()}
        assert by["outer"].parent == 0 and by["outer"].sid == outer.sid
        assert by["a"].parent == by["b"].parent == outer.sid
        assert by["leaf"].parent == b.sid
        assert by["leaf"].attrs == {"real_tokens": 5}
        assert by["outer"].attrs == {"step": 1}
        # self time (duration minus children) is never negative: children
        # run one after the other inside their parent
        assert by["a"].duration + by["b"].duration <= by["outer"].duration
        assert by["leaf"].duration <= by["b"].duration
        assert by["a"].t_end <= by["b"].t_start
        d = by["leaf"].to_dict()
        assert d["id"] == leaf.sid and d["parent"] == b.sid
        # after the fact (record): outside any tree
        t.record("old", "row", 0.0, 1.0)
        assert t.snapshot()[-1].sid == 0
        assert "id" not in t.snapshot()[-1].to_dict()

    def test_each_thread_has_its_own_stack(self):
        t = _enabled_tracer()
        started, release = threading.Event(), threading.Event()

        def worker():
            with tracing.span("w.outer", t):
                started.set()
                release.wait(5)
                with tracing.span("w.inner", t):
                    pass

        th = threading.Thread(target=worker, name="worker-7")
        with tracing.span("main.outer", t):
            th.start()
            started.wait(5)
            with tracing.span("main.inner", t):
                release.set()
                th.join()
        by = {s.name: s for s in t.snapshot()}
        assert by["w.outer"].parent == 0 and by["main.outer"].parent == 0
        assert by["w.inner"].parent == by["w.outer"].sid
        assert by["main.inner"].parent == by["main.outer"].sid
        # no rid given: one row per thread
        assert by["w.inner"].rid == "worker-7"
        assert by["main.inner"].rid == threading.current_thread().name

    @pytest.mark.parametrize("tracer", [None, "disabled"])
    def test_ring_untouched_without_an_enabled_tracer(self, tracer):
        t = SpanTracer(TracingConfig(enabled=False))
        with tracing.span("x", t if tracer else None, n=1) as sp:
            with tracing.span("p1", t if tracer else None):
                pass
        assert len(t) == 0 and sp.duration >= 0.0
        assert tracing._TLS.stack == []  # every span closed

    def test_phases_are_consecutive_siblings_closed_on_return(self):
        """The engine's admission is one long linear function: its
        stretches are sibling spans, each closed by the next."""
        from areal_tpu.inference.engine import _Phases

        t = _enabled_tracer()

        class Loop:  # what _Phases needs of an engine
            def _loop_span(self, name):
                return tracing.span(name, t, "loop", seq=4)

        def admit(stop_early):
            with _Phases(Loop()) as ph:
                ph.enter("admit.schedule")
                if stop_early:
                    return False
                ph.enter("admit.prefill_dispatch")
                ph.enter("admit.install")
            return True

        with tracing.span("engine.prefill", t, "loop") as outer:
            assert admit(True) is False
            assert admit(False) is True
        spans = t.snapshot()
        assert [s.name for s in spans] == [
            "admit.schedule", "admit.schedule", "admit.prefill_dispatch",
            "admit.install", "engine.prefill",
        ]
        assert all(s.parent == outer.sid for s in spans[:4])
        assert all(s.attrs == {"seq": 4} for s in spans[:4])
        for a, b in zip(spans[1:3], spans[2:4]):
            assert a.t_end <= b.t_start
        assert tracing._TLS.stack == []

    def test_chrome_trace_nests_a_threads_spans_on_one_row(self):
        t = _enabled_tracer()
        with tracing.span("outer", t):
            with tracing.span("inner", t, k=2):
                pass
        ev = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in ev} == {"outer", "inner"}
        assert len({e["tid"] for e in ev}) == 1
        inner = next(e for e in ev if e["name"] == "inner")
        assert inner["args"]["k"] == 2

    def test_device_scope_takes_the_vocabulary_only(self):
        import jax
        import jax.numpy as jnp

        with pytest.raises(ValueError):
            tracing.device_scope("fusion.530")

        @tracing.device_scope("mlp")
        def f(x):
            with tracing.device_scope("attention"):
                y = x * 2
            return y + 1

        assert f.__name__ == "f"
        lowered = jax.jit(f).lower(jnp.ones(4))
        text = lowered.as_text(debug_info=True)
        assert "mlp/attention/mul" in text and "mlp/add" in text
        # and in the program itself, not only its debug metadata: the
        # persistent compile cache keys on the text without locations
        bare = lowered.as_text()
        assert 'areal_scope = "attention"' in bare
        assert 'areal_scope = "mlp"' in bare and "loc(" not in bare

    def test_spans_are_in_a_cpu_profile_with_their_attrs_as_stats(
        self, tmp_path
    ):
        import jax

        t = SpanTracer(TracingConfig(enabled=False))  # no switch of its own
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tracing.span("train.pack", step=3, pad_to=256) as sp:
                sp.set(real_tokens=5, padded_tokens=8)
                with t.span("decode", "rid-1", tokens=7):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        found = _profile_events(str(tmp_path))
        assert found["areal:train.pack"] == [
            {"step": 3, "pad_to": 256, "real_tokens": 5, "padded_tokens": 8}
        ]
        # a request-keyed span goes through the same primitive
        assert found["areal:decode"] == [{"tokens": 7}]
        assert len(t) == 0


# --------------------------------------------------------------------------
# A process that has not loaded jax (an env worker, the router)
# --------------------------------------------------------------------------
_WITHOUT_JAX = r"""
import asyncio, json, sys
from areal_tpu.api.cli_args import EnvServiceConfig, TracingConfig
from areal_tpu.env import service as ES
from areal_tpu.utils import tracing
from areal_tpu.utils.tracing import SpanTracer

off = SpanTracer(TracingConfig(enabled=False))
# disabled and no profiler in the process: ONE shared null object, nothing
# allocated per call
assert off.span("a", "r1") is off.span("b", "r2", k=1) is tracing._NULL_CTX
with off.span("a", "r1") as sp:
    sp.set(k=2)
assert sp.duration == 0.0 and len(off) == 0 and tracing._TLS.stack == []
# the module's span still times its body (a ledger counts seconds from it)
with tracing.span("x", off, n=1) as sp:
    pass
assert sp.duration >= 0.0 and len(off) == 0

on = SpanTracer(TracingConfig(enabled=True))
httpd = ES.serve_env(ES.countdown_env, background=True, tracer=on)
addr = "127.0.0.1:%d" % httpd.server_address[1]

async def run():
    env = ES.RemoteEnv(addrs=[addr], config=EnvServiceConfig(
        call_retries=2, call_timeout_s=10.0, reset_timeout_s=10.0))
    await env.areset(numbers=[3, 5, 2], target=21)
    o, r, d, _ = await env.astep({
        "name": "eval_expression",
        "arguments": json.dumps({"expression": "3*7"}),
    })
    assert o == "21"
    await env.aclose()

asyncio.run(run())
httpd.shutdown()
names = [s.name for s in on.snapshot()]
assert "env_reset" in names and "env_step" in names, names
assert all(s.sid for s in on.snapshot() if s.name == "env_step")
assert "jax" not in sys.modules, "a span imported jax"
print("WITHOUT_JAX_OK")
"""


class TestWithoutJax:
    def test_env_worker_serves_a_request_without_importing_jax(self):
        import subprocess

        root = os.path.join(os.path.dirname(__file__), "..")
        env = dict(os.environ, PYTHONPATH=root)
        out = subprocess.run(
            [sys.executable, "-c", _WITHOUT_JAX], env=env, cwd=root,
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "WITHOUT_JAX_OK" in out.stdout

    def test_a_span_opened_before_jax_loads_closes_cleanly(self, monkeypatch):
        # the annotation is decided at entry: a span entered while jax was
        # absent has none to close, whatever was imported since
        monkeypatch.setattr(tracing, "_ANNOTATION", None)
        monkeypatch.delitem(sys.modules, "jax")
        t = _enabled_tracer()
        sp = tracing.span("x", t, "r", k=1)
        sp.__enter__()
        assert sp._ann is None
        monkeypatch.undo()  # jax is back in sys.modules
        sp.set(n=2)
        sp.__exit__(None, None, None)
        assert t.snapshot()[-1].attrs == {"k": 1, "n": 2}
        with tracing.span("y", t, "r") as sp2:
            pass
        assert sp2._ann is not None


def test_trace_rungs_names_each_run_by_the_row_length_its_pack_gave():
    """``tools/trace_rungs``: the k-th ``train.pack`` span's ``pad_to`` is
    the k-th ``grad_step`` / ``logp_forward`` run's row length (the recorded
    slice of a chip trace the benchmark's tests keep: two packs at 4096)."""
    import os

    from benchmark import program_trace
    from tools import trace_rungs

    events = program_trace.read_events(os.path.join(
        os.path.dirname(__file__), "benchmark_suite",
        "recorded_program_trace.tsv",
    ))
    table = trace_rungs.by_row_length(events)
    assert list(table) == [("grad_step", 4096)]
    runs, seconds = table[("grad_step", 4096)]
    assert runs == 2 and 0.04 < seconds < 0.4
    # a window cut in the middle of a step is refused, not misread
    packs = [e for e in events if e[2] == "areal:train.pack"]
    cut = [e for e in events if e is not packs[0]]
    with pytest.raises(SystemExit):
        trace_rungs.by_row_length(cut)
    # what a row length's grad dispatches said of their cores' backward
    assert trace_rungs.backward_forms(events) == {4096: ""}
    said = [e for e in events if e[2] == "areal:train.grad_dispatch"]
    said[0][5].update(bd_bwd="fused", bd_tile="256x512")
    said[1][5].update(bd_bwd="fused", attn_bwd_full="split")
    assert trace_rungs.backward_forms(events) == {
        4096: "attn_bwd_full=split bd_bwd=fused"
    }
