"""Goodput attribution plane (r11): wall-clock ledger exclusivity,
recompile attribution + compile_events stream, engine readiness
(warming → ready on /health with ladder coverage), FleetMonitor WARMING
classification + cold→serving lead time, the autoscaler's lead-time
metric, native latency histograms end to end, the telemetry hub's
per-class rollup + goodput-collapse anomaly, and trace_report
--goodput."""

import json
import os
import sys
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from areal_tpu.api.cli_args import (
    FleetConfig,
    JaxGenConfig,
    TelemetryConfig,
    TrafficConfig,
)
from areal_tpu.utils import goodput
from areal_tpu.utils.tracing import (
    Histogram,
    parse_prometheus_histograms,
    render_prometheus,
)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


# ==========================================================================
# GoodputLedger
# ==========================================================================
class TestGoodputLedger:
    def test_fractions_sum_to_one_with_remainder(self):
        clk = FakeClock()
        led = goodput.GoodputLedger(
            "trainer", goodput.TRAINER_BUCKETS, remainder="other",
            productive=goodput.TRAINER_PRODUCTIVE, time_fn=clk,
        )
        with led.bucket("fwd_bwd"):
            clk.tick(3.0)
        with led.bucket("rollout_wait"):
            clk.tick(5.0)
        clk.tick(2.0)  # unclaimed → other
        fr = led.fractions()
        assert abs(sum(fr.values()) - 1.0) < 1e-9
        assert fr["fwd_bwd"] == pytest.approx(0.3)
        assert fr["rollout_wait"] == pytest.approx(0.5)
        assert fr["other"] == pytest.approx(0.2)
        assert led.duty_cycle() == pytest.approx(0.3)

    def test_reentrant_bucket_is_noop_outer_wins(self):
        clk = FakeClock()
        led = goodput.GoodputLedger(
            "trainer", goodput.TRAINER_BUCKETS, time_fn=clk
        )
        with led.bucket("weight_push"):
            clk.tick(1.0)
            with led.bucket("fwd_bwd"):  # nested: must not double-book
                clk.tick(2.0)
            clk.tick(1.0)
        secs = led.seconds()
        assert secs["weight_push"] == pytest.approx(4.0)
        assert secs["fwd_bwd"] == 0.0

    def test_unknown_bucket_raises(self):
        led = goodput.GoodputLedger("x", ("a", "other"))
        with pytest.raises(KeyError):
            led.bucket("nope")
        with pytest.raises(ValueError):
            goodput.GoodputLedger("x", ("a",), remainder="idle")

    def test_compile_carveout_into_compile_bucket(self):
        clk = FakeClock()
        tracker = goodput.CompileTracker(time_fn=clk)
        led = goodput.GoodputLedger(
            "engine", goodput.ENGINE_BUCKETS, remainder="idle",
            compile_tracker=tracker, time_fn=clk,
        )
        with led.bucket("prefill"):
            clk.tick(4.0)
            # a compile observed on this thread mid-bucket
            tracker._observe(
                "prefill", "rows1", 3.0,
                "/jax/core/compile/backend_compile_duration",
            )
        secs = led.seconds()
        assert secs["compile"] == pytest.approx(3.0)
        assert secs["prefill"] == pytest.approx(1.0)

    def test_effective_tokens_and_snapshot_jsonl(self, tmp_path):
        clk = FakeClock()
        path = str(tmp_path / "gp.jsonl")
        led = goodput.GoodputLedger(
            "engine", goodput.ENGINE_BUCKETS, remainder="idle",
            productive=goodput.ENGINE_PRODUCTIVE, jsonl_path=path,
            time_fn=clk,
        )
        with led.bucket("decode"):
            clk.tick(2.0)
        led.note_tokens(100)
        led.export_jsonl()
        rec = json.loads(open(path).read().strip())
        assert rec["kind"] == "goodput" and rec["role"] == "engine"
        assert rec["effective_tokens_per_sec"] == pytest.approx(50.0)
        assert abs(sum(rec["fractions"].values()) - 1.0) < 1e-3

    def test_trainer_singleton_reentrancy_and_reset(self):
        goodput.reset_trainer_ledger()
        led = goodput.trainer_ledger()
        assert goodput.trainer_ledger() is led
        with goodput.trainer_bucket("rollout_wait"):
            pass
        goodput.reset_trainer_ledger()
        assert goodput.trainer_ledger() is not led


# ==========================================================================
# CompileTracker: real-jit attribution + the events stream
# ==========================================================================
class TestCompileTracker:
    def test_dispatch_scope_attributes_real_compiles(self, tmp_path):
        events = str(tmp_path / "compile_events.jsonl")
        tracker = goodput.CompileTracker(
            events_path=events, ladder_size=2
        )

        def f(x):
            return x * 2 + 1

        with goodput.dispatch_scope(tracker, "decode", "rows4|steps8"):
            jax.jit(f)(jnp.ones(7)).block_until_ready()
        assert tracker.compiles_total >= 1
        assert tracker.compile_seconds_total > 0
        assert ("decode", "rows4|steps8") in tracker.signatures
        assert tracker.coverage() == pytest.approx(0.5)
        assert tracker.quiet_s() < 60.0
        recs = [
            json.loads(line) for line in open(events) if line.strip()
        ]
        # r14: the stream opens with a fingerprint header line
        assert recs and recs[0]["kind"] == "header"
        assert recs[0]["jax"]
        compiles = [r for r in recs if r["kind"] == "compile"]
        assert compiles and compiles[0]["phase"] == "decode"
        assert compiles[0]["signature"] == "rows4|steps8"
        assert compiles[0]["duration_s"] > 0
        assert "cached" in compiles[0]
        # cached second call: no new compile events
        n = tracker.compiles_total
        with goodput.dispatch_scope(tracker, "decode", "rows4|steps8"):
            jax.jit(f)(jnp.ones(7)).block_until_ready()
        # jax.jit(f) creates a fresh wrapper but XLA-level caching may
        # still compile; only assert the tracker never loses events
        assert tracker.compiles_total >= n

    def test_thread_default_tracker_catches_untagged(self):
        tracker = goodput.CompileTracker()
        goodput.set_thread_tracker(tracker, phase="engine")
        try:
            tracker_seen = tracker.compiles_total

            def g(x):
                return x - 3

            jax.jit(g)(jnp.ones(11)).block_until_ready()
            assert tracker.compiles_total >= tracker_seen + 1
            assert ("engine", "") in tracker.signatures
        finally:
            goodput.set_thread_tracker(None)

    def test_events_stream_names_the_program_and_its_phases(self, tmp_path):
        """A backend line carries the function's name, whether the compiler
        or the cache made it, and the tracing and lowering seconds of the
        same program; ``trace_report --coldstart`` builds the table by
        program from such lines."""
        from tools import trace_report

        events = str(tmp_path / "compile_events.jsonl")
        tracker = goodput.CompileTracker(events_path=events)

        def halve_then_add(x):
            return x / 2 + 3

        with goodput.dispatch_scope(tracker, "fwd_bwd", "pad13"):
            jax.jit(halve_then_add)(jnp.ones(13)).block_until_ready()
        recs = [json.loads(line) for line in open(events) if line.strip()]
        mine = [r for r in recs if r.get("fun_name") == "halve_then_add"]
        assert len(mine) == 1 and mine[0]["kind"] == "compile"
        assert mine[0]["backend"] in ("compile", "cache_load")
        assert mine[0]["trace_s"] > 0 and mine[0]["lower_s"] > 0
        row = next(
            r for r in tracker.program_table()
            if r["fun_name"] == "halve_then_add"
        )
        assert row["trace_s"] == pytest.approx(mine[0]["trace_s"], abs=1e-5)
        assert row["lower_s"] == pytest.approx(mine[0]["lower_s"], abs=1e-5)
        cw = trace_report.coldstart_summary(trace_report.load_coldstart(events))
        prow = next(
            r for r in cw["programs"] if r["fun_name"] == "halve_then_add"
        )
        assert prow["count"] == 1
        assert prow["trace_s"] == pytest.approx(mine[0]["trace_s"], abs=1e-3)
        assert "halve_then_add" in trace_report.format_coldstart(cw)


# ==========================================================================
# CompileTracker's log: set-up by program and by phase
# ==========================================================================
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


class TestCompileLog:
    def _tracked(self, tracker):
        goodput.set_thread_tracker(tracker, phase="trainer")

    def teardown_method(self):
        goodput.set_thread_tracker(None)

    def test_a_function_traced_inside_anothers_trace_counts_once(self):
        tracker = goodput.CompileTracker()
        self._tracked(tracker)

        @jax.jit
        def inner_helper(x):
            return jnp.tanh(x) * 3

        def outer_program(x):
            return inner_helper(x) + inner_helper(x * 2)

        jax.jit(outer_program)(jnp.ones(17)).block_until_ready()
        with tracker._lock:
            log = list(tracker._log)
        inner = [e for e in log if e.fun_name == "inner_helper"]
        outer = [e for e in log if e.fun_name == "outer_program"]
        assert inner and all(e.nested and e.kind == "trace" for e in inner)
        assert {e.parent.fun_name for e in inner} == {"outer_program"}
        assert [e.kind for e in outer] == ["trace", "lower", "compile"]
        assert not any(e.nested for e in outer)
        secs = tracker.phase_seconds()
        by_hand = sum(
            e.seconds for e in log if e.kind == "trace" and not e.nested
        )
        assert secs["trace"] == pytest.approx(by_hand)
        assert secs["trace"] < sum(
            e.seconds for e in log if e.kind == "trace"
        )
        # the totals every other reader uses are the same seconds
        assert tracker.compile_seconds_total == pytest.approx(
            sum(secs.values())
        )
        assert tracker.thread_seconds() == pytest.approx(sum(secs.values()))
        table = {r["fun_name"]: r for r in tracker.program_table()}
        assert "inner_helper" not in table  # never made on its own
        row = table["outer_program"]
        assert row["traces"] == 1 and row["compiles"] + row["loads"] == 1
        nested = {n["fun_name"]: n for n in row["nested"]}
        assert nested["inner_helper"]["kind"] == "trace"
        assert nested["inner_helper"]["seconds"] <= row["trace_s"]

    def test_a_cache_miss_is_compile_and_a_hit_is_cache_load(self, tmp_path):
        from areal_tpu.utils import compile_cache

        inherited = os.environ.pop(compile_cache.CACHE_DIR_ENV, None)
        x = jnp.ones(19)  # made before the tracker listens: one program
        tracker = goodput.CompileTracker()
        self._tracked(tracker)

        def cached_program(x):
            return jnp.cos(x) * 5 + 1

        try:
            # scoped: the enable is process-global (tests/test_precompile.py)
            assert compile_cache.enable_compilation_cache(str(tmp_path))
            jax.jit(cached_program)(x).block_until_ready()
            miss = tracker.phase_seconds()
            assert miss["compile"] > 0 and miss["cache_load"] == 0
            jax.clear_caches()  # the in-memory executable goes, the file stays
            jax.jit(cached_program)(x).block_until_ready()
        finally:
            compile_cache.disable_compilation_cache()
            if inherited is not None:
                os.environ[compile_cache.CACHE_DIR_ENV] = inherited
        hit = tracker.phase_seconds()
        assert hit["compile"] == miss["compile"] and hit["cache_load"] > 0
        row = next(
            r for r in tracker.program_table()
            if r["fun_name"] == "cached_program"
        )
        assert (row["compiles"], row["loads"], row["traces"]) == (1, 1, 2)
        assert row["load_s"] == pytest.approx(hit["cache_load"])
        assert tracker.programs() == 2 == tracker.compiles_total
        m = tracker.metrics()
        assert m["compile_backend_seconds_total"] == pytest.approx(
            hit["compile"], abs=1e-4
        )
        assert m["compile_cache_load_seconds_total"] == pytest.approx(
            hit["cache_load"], abs=1e-4
        )
        assert m["compile_trace_seconds_total"] > 0
        assert m["compile_lower_seconds_total"] > 0

    def _fed(self, clk):
        """trace f 1 s, lower f 0.5 s, compile f 2 s, then g from the cache
        in 1 s: ends at 1001, 1003, 1006, 1008 on the fake clock."""
        tracker = goodput.CompileTracker(time_fn=clk)
        clk.tick(1.0)
        tracker._observe("trainer", "", 1.0, TRACE, fun_name="f")
        clk.tick(2.0)
        tracker._observe("trainer", "", 0.5, LOWER, fun_name="jit(f)")
        clk.tick(3.0)
        tracker._observe("trainer", "", 2.0, BACKEND, False, fun_name="jit(f)")
        clk.tick(2.0)
        tracker._observe("trainer", "", 1.0, BACKEND, True, fun_name="jit(g)")
        return tracker

    def test_until_cuts_between_two_events(self):
        tracker = self._fed(FakeClock())
        assert tracker.phase_seconds(1004.0) == {
            "trace": 1.0, "lower": 0.5, "compile": 0.0, "cache_load": 0.0,
        }
        assert tracker.programs(1004.0) == 0
        assert tracker.programs(1007.0) == 1 and tracker.programs() == 2
        assert tracker.phase_seconds(1007.0)["compile"] == 2.0
        assert tracker.phase_seconds() == {
            "trace": 1.0, "lower": 0.5, "compile": 2.0, "cache_load": 1.0,
        }
        assert [r["fun_name"] for r in tracker.program_table(1007.0)] == ["f"]

    def test_program_table_is_one_row_a_function_most_expensive_first(self):
        clk = FakeClock()
        tracker = self._fed(clk)
        clk.tick(4.0)  # f again, at another shape: the same row
        tracker._observe("trainer", "", 3.0, BACKEND, False, fun_name="jit(f)")
        rows = tracker.program_table()
        assert [r["fun_name"] for r in rows] == ["f", "g"]
        assert rows[0] == {
            "fun_name": "f", "traces": 1, "trace_s": 1.0, "lower_s": 0.5,
            "compiles": 2, "compile_s": 5.0, "loads": 0, "load_s": 0.0,
            "nested": [], "seconds": 6.5,
        }
        assert (rows[1]["loads"], rows[1]["load_s"]) == (1, 1.0)
        assert tracker.warmup_eta_s() == 0.0  # ladder unknown

    def test_the_log_is_bounded_and_what_it_drops_stays_in_the_totals(
        self, monkeypatch
    ):
        monkeypatch.setattr(goodput, "_LOG_ENTRIES", 3)
        tracker = self._fed(FakeClock())  # four events into a log of three
        assert tracker.dropped_total == 1 and len(tracker._log) == 3
        assert tracker.phase_seconds() == {
            "trace": 1.0, "lower": 0.5, "compile": 2.0, "cache_load": 1.0,
        }
        assert tracker.programs() == 2
        assert tracker.compile_seconds_total == pytest.approx(4.5)
        # a cut the log still reaches back to is answered as before (the
        # entry dropped ended before it), one it does not reach raises
        assert tracker.programs(1007.0) == 1
        assert tracker.phase_seconds(1007.0)["trace"] == 1.0
        with pytest.raises(LookupError):
            tracker.phase_seconds(1000.5)
        with pytest.raises(LookupError):
            tracker.programs(1000.5)
        # and the table says that it is of the newest entries only
        last = tracker.program_table()[-1]
        assert (last["fun_name"], last["entries"]) == ("(dropped)", 1)

    def test_a_second_goes_to_the_innermost_entrys_kind(self):
        """An eager program loaded while a program is being traced: its
        second is ``cache_load``'s and leaves the trace's, so the backend
        kinds equal what a count from outside sums (``CompileWatch``)."""
        clk = FakeClock()
        tracker = goodput.CompileTracker(time_fn=clk)
        clk.tick(1.0)
        tracker._observe("trainer", "", 0.25, BACKEND, True, fun_name="jit(ones)")
        clk.tick(1.0)
        tracker._observe("trainer", "", 2.0, TRACE, fun_name="grad_step")
        assert tracker.phase_seconds() == {
            "trace": 1.75, "lower": 0.0, "compile": 0.0, "cache_load": 0.25,
        }
        assert tracker.programs() == 1
        assert tracker.compile_seconds_total == pytest.approx(2.0)
        (row,) = tracker.program_table()
        assert row["fun_name"] == "grad_step" and row["trace_s"] == 2.0
        assert row["nested"] == [{"fun_name": "ones", "kind": "cache_load",
                                  "count": 1, "seconds": 0.25}]

    def test_the_ledgers_compile_carve_out_counts_nested_seconds_once(self):
        clk = FakeClock()
        tracker = goodput.CompileTracker(time_fn=clk)
        led = goodput.GoodputLedger(
            "trainer", goodput.TRAINER_BUCKETS, compile_tracker=tracker,
            time_fn=clk,
        )
        with led.bucket("fwd_bwd"):
            clk.tick(1.0)  # a kernel wrapper traced inside grad_step's trace
            tracker._observe("fwd_bwd", "", 0.4, TRACE, fun_name="wrapper")
            clk.tick(0.5)
            tracker._observe("fwd_bwd", "", 1.5, TRACE, fun_name="grad_step")
            clk.tick(1.0)  # the program running
        secs = led.seconds()
        assert secs["compile"] == pytest.approx(1.5)  # not 1.9
        assert secs["fwd_bwd"] == pytest.approx(1.0)
        assert secs["compile"] <= 2.5  # the bucket's wall time
        assert tracker.thread_seconds() == pytest.approx(1.5)


# ==========================================================================
# Native Prometheus histograms
# ==========================================================================
class TestHistograms:
    def test_observe_quantile_merge(self):
        h = Histogram((0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.6, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5 and h.sum == pytest.approx(56.15)
        assert 0.1 < h.quantile(0.5) <= 1.0
        other = Histogram((0.1, 1.0, 10.0))
        other.observe(0.01)
        h.merge(other)
        assert h.count == 6 and h.counts[0] == 2
        with pytest.raises(ValueError):
            h.merge(Histogram((1.0, 2.0)))

    def test_render_parse_round_trip_all_three_types(self):
        h = Histogram((0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(3.0)
        text = render_prometheus(
            {"a_gauge": 1.5, "things_total": 7},
            prefix="p_",
            types={"a_gauge": "gauge", "things_total": "counter"},
            histograms={
                'lat_seconds{sched_class="bulk"}': h,
                "plain_seconds": h,
            },
        )
        assert "# TYPE p_a_gauge gauge" in text
        assert "# TYPE p_things_total counter" in text
        assert "# TYPE p_lat_seconds histogram" in text
        assert 'p_lat_seconds_bucket{sched_class="bulk",le="+Inf"} 3' in text
        assert 'p_lat_seconds_count{sched_class="bulk"} 3' in text
        from areal_tpu.utils.tracing import parse_prometheus

        flat = parse_prometheus(text, prefix="p_")
        assert flat["a_gauge"] == 1.5 and flat["things_total"] == 7
        back = parse_prometheus_histograms(text, prefix="p_")
        got = back['lat_seconds{sched_class="bulk"}']
        assert got.counts == h.counts
        assert got.count == h.count
        assert got.sum == pytest.approx(h.sum)
        assert back["plain_seconds"].counts == h.counts


# ==========================================================================
# FleetMonitor WARMING + autoscaler lead time (sleep-free)
# ==========================================================================
class TestWarmingFleet:
    def _monitor(self, statuses, clk):
        from areal_tpu.inference.fleet import FleetMonitor

        recovered = []

        def probe(addr):
            return statuses[addr], 0.01, dict(
                ladder_coverage=statuses.get(addr + "_cov", 0.5)
            )

        mon = FleetMonitor(
            ["a:1", "b:2"],
            config=FleetConfig(enabled=False),
            probe_fn=probe,
            time_fn=clk,
            on_recover=recovered.append,
        )
        return mon, recovered

    def test_warming_out_of_rotation_but_update_target(self):
        clk = FakeClock()
        statuses = {"a:1": "warming", "b:2": "ok"}
        mon, recovered = self._monitor(statuses, clk)
        mon.probe_once()
        from areal_tpu.inference.fleet import ServerState

        assert mon.state("a:1") is ServerState.WARMING
        assert not mon.is_schedulable("a:1")
        assert mon.is_update_target("a:1")  # weight pushes still land
        assert mon.schedulable_addresses() == ["b:2"]
        m = mon.state_metrics()
        assert m["fleet_warming_servers"] == 1.0
        assert m["fleet_cold_to_serving_total"] == 0.0

    def test_warming_to_healthy_records_lead_and_fires_recover(self):
        clk = FakeClock()
        statuses = {"a:1": "warming", "b:2": "ok"}
        mon, recovered = self._monitor(statuses, clk)
        mon.probe_once()
        clk.tick(7.5)
        statuses["a:1"] = "ok"
        mon.probe_once()
        from areal_tpu.inference.fleet import ServerState

        assert mon.state("a:1") is ServerState.HEALTHY
        assert mon.is_schedulable("a:1")
        assert recovered == ["a:1"]  # owner re-verifies weight version
        m = mon.state_metrics()
        assert m["fleet_cold_to_serving_total"] == 1.0
        assert m["fleet_cold_to_serving_last_s"] == pytest.approx(7.5)
        assert mon.per_server()["a:1"]["ready_lead_s"] == pytest.approx(
            7.5
        )

    def test_passive_success_does_not_end_warming(self):
        clk = FakeClock()
        statuses = {"a:1": "warming", "b:2": "ok"}
        mon, _ = self._monitor(statuses, clk)
        mon.probe_once()
        mon.report_success("a:1")  # pre-warm in-flight work finishing
        from areal_tpu.inference.fleet import ServerState

        assert mon.state("a:1") is ServerState.WARMING

    def test_completed_requests_latch_ready_under_traffic(self):
        """Sustained traffic never yields a compile-quiet window; a
        server that COMPLETES requests end-to-end must still latch
        ready (the default ready_min_requests=1 path) or it would sit
        out of rotation forever while serving fine."""
        import jax as _jax
        import jax.numpy as _jnp

        from areal_tpu.inference.engine import GenerationEngine
        from areal_tpu.models.config import tiny_config
        from areal_tpu.models.transformer import init_params

        cfg = tiny_config("qwen2")
        params = init_params(
            cfg, _jax.random.PRNGKey(0), dtype=_jnp.float32
        )
        gcfg = JaxGenConfig(
            dtype="float32", max_num_seqs=2, max_model_len=64,
            prefill_chunk=16,
        )
        gcfg.goodput.ready_quiet_s = 3600.0  # quiet path unreachable
        eng = GenerationEngine(
            gcfg, model_config=cfg, params=params
        ).start()
        try:
            eng.generate(
                {
                    "rid": "latch-1",
                    "input_ids": [1, 2, 3],
                    "sampling_params": {"max_new_tokens": 2},
                }
            )
            rd = eng.readiness()
            assert rd["state"] == "ready"
            assert eng._ready_latched
        finally:
            eng.stop()

    def test_warming_server_that_dies_goes_dead(self):
        clk = FakeClock()
        statuses = {"a:1": "warming", "b:2": "ok"}
        mon, _ = self._monitor(statuses, clk)
        mon.probe_once()
        statuses["a:1"] = "fail"
        for _ in range(FleetConfig().dead_threshold):
            mon.probe_once()
            clk.tick(0.1)
        from areal_tpu.inference.fleet import ServerState

        assert mon.state("a:1") is ServerState.DEAD

    def test_autoscaler_cold_to_serving_metric(self):
        from areal_tpu.inference.fleet import FleetAutoscaler

        clk = FakeClock()
        cfg = TrafficConfig(
            autoscale=True, min_servers=1, max_servers=4,
            up_queued_per_server=1.0, up_consecutive=1, cooldown_s=0.0,
        )
        obs = {
            "a:1": {"running": 1.0, "queued": 5.0, "kv_util": 0.2,
                    "warming": 0.0, "draining": 0.0},
        }
        launched = []
        sc = FleetAutoscaler(
            cfg,
            launch_fn=lambda: launched.append(clk()),
            drain_fn=lambda a: None,
            addresses_fn=lambda: list(obs),
            observe_fn=lambda a: dict(obs[a]),
            time_fn=clk,
        )
        assert sc.evaluate_once() == "up"
        assert launched
        # the spawned server appears WARMING: no double-launch, and the
        # lead clock runs from the launch decision
        obs["b:2"] = {"running": 0.0, "queued": 0.0, "kv_util": 0.0,
                      "warming": 1.0, "draining": 0.0}
        clk.tick(1.0)
        assert sc.evaluate_once() is None
        assert sc.last_decision == "warming_pending"
        clk.tick(9.0)
        obs["b:2"]["warming"] = 0.0
        sc.evaluate_once()
        m = sc.metrics()
        assert m["autoscale_cold_to_serving_total"] == 1.0
        assert m["autoscale_cold_to_serving_s"] == pytest.approx(10.0)


# ==========================================================================
# Telemetry hub: per-class histogram rollup + goodput-collapse anomaly
# ==========================================================================
class TestHubGoodput:
    def _collector(self, metrics_by_addr, hists_by_addr, cfg=None):
        from areal_tpu.utils.telemetry import TelemetryCollector

        return TelemetryCollector(
            addresses=list(metrics_by_addr),
            config=cfg
            or TelemetryConfig(drain_traces=False, goodput_baseline_sweeps=1),
            fetch_metrics_fn=lambda a: (
                dict(metrics_by_addr[a]),
                {k: h for k, h in hists_by_addr.get(a, {}).items()},
            ),
            fetch_trace_fn=lambda a: ([], 0.0, 0),
        )

    def test_per_class_histogram_rollup(self):
        h1 = Histogram((0.1, 1.0))
        h1.observe(0.05)
        h2 = Histogram((0.1, 1.0))
        h2.observe(0.5)
        key = 'queue_wait_seconds{sched_class="interactive"}'
        col = self._collector(
            {"a:1": {}, "b:2": {}},
            {"a:1": {key: h1}, "b:2": {key: h2}},
        )
        col.scrape_once()
        roll = col.rollup()
        assert roll["queue_wait_interactive_count"] == 2.0
        assert roll["queue_wait_interactive_p95_s"] > 0
        # the merged histogram becomes THE fleet queue-wait number
        assert roll["queue_wait_samples"] == 2.0
        # and the hub re-exports the merged series
        text = col.render_metrics()
        assert (
            "# TYPE areal_tpu_fleet_queue_wait_seconds histogram" in text
        )
        back = parse_prometheus_histograms(
            text, prefix="areal_tpu_fleet_"
        )
        assert back[key].count == 2

    def test_goodput_collapse_anomaly_flip_and_clear(self):
        m = {
            "goodput_weight_pause_frac": 0.05,
            "goodput_idle_frac": 0.05,
            "goodput_duty_cycle": 0.9,
            "goodput_effective_tokens_per_sec": 100.0,
        }
        cfg = TelemetryConfig(
            drain_traces=False, goodput_baseline_sweeps=1,
            goodput_collapse_margin=0.2, goodput_collapse_floor=0.5,
        )
        col = self._collector({"a:1": m}, {}, cfg=cfg)
        col.scrape_once()  # baseline = 0.10
        assert col.anomalies()["anomaly_goodput_collapse"] is False
        assert col.manifest()[
            "goodput_baseline_pause_idle_frac"
        ] == pytest.approx(0.1)
        # pause+idle runs away past margin AND floor → anomaly
        m["goodput_weight_pause_frac"] = 0.6
        m["goodput_idle_frac"] = 0.2
        col.scrape_once()
        assert col.anomalies()["anomaly_goodput_collapse"] is True
        roll = col.rollup()
        assert roll["goodput_pause_idle_frac"] == pytest.approx(0.8)
        assert roll["anomaly_goodput_collapse"] == 1.0
        # symmetric clear
        m["goodput_weight_pause_frac"] = 0.05
        m["goodput_idle_frac"] = 0.05
        col.scrape_once()
        assert col.anomalies()["anomaly_goodput_collapse"] is False


# ==========================================================================
# Engine integration: the acceptance scenario (weight update + cold
# start → fractions sum to 1 with weight_pause and compile visible)
# ==========================================================================
@pytest.fixture(scope="module")
def goodput_engine(tmp_path_factory):
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params

    tmp = tmp_path_factory.mktemp("goodput")
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # DELIBERATELY odd shapes (5 slots / chunk 7 / prefill 24): the
    # engine's jitted entry points are module-level, so a full-suite
    # run reaches this module with the common tiny-engine shapes
    # already compiled — the cold-start assertions below need programs
    # no earlier test warmed
    gcfg = JaxGenConfig(
        dtype="float32", max_num_seqs=5, max_model_len=96,
        prefill_chunk=24, decode_chunk=7,
    )
    gcfg.goodput.ready_quiet_s = 0.8
    # quiet-driven readiness: with the default (1 completed request
    # latches ready) the warming window would close the moment the
    # first generate returns — this fixture observes the storm itself
    gcfg.goodput.ready_min_requests = 10_000
    gcfg.goodput.compile_events_path = str(tmp / "compile_events.jsonl")
    gcfg.goodput.jsonl_path = str(tmp / "goodput.jsonl")
    eng = GenerationEngine(gcfg, model_config=cfg, params=params)
    yield eng, params, gcfg
    if eng._running:
        eng.stop()


class TestEngineGoodput:
    def test_cold_start_weight_update_ledger_and_readiness(
        self, goodput_engine
    ):
        eng, params, gcfg = goodput_engine
        # pre-start, pre-compile: a fresh idle server is servable (no
        # warming deadlock) but NOT latched warm
        assert eng.readiness()["state"] == "ready"
        assert not eng._ready_latched
        eng.start()
        out = eng.generate(
            {
                "rid": "gp-1",
                "input_ids": [1, 2, 3, 4, 5],
                "sampling_params": {"max_new_tokens": 6},
            }
        )
        assert len(out["output_ids"]) == 6
        # mid/just-post compile storm: warming, with coverage + ETA
        rd = eng.readiness()
        assert rd["state"] == "warming"
        assert 0 < rd["ladder_coverage"] <= 1.0
        assert rd["compiled_shapes"] >= 2
        # weight update opens a pause window
        eng.pause()
        time.sleep(0.15)
        eng.update_weights_from_tensors(params, version=1)
        eng.continue_generation()
        fr = eng.ledger.fractions()
        assert abs(sum(fr.values()) - 1.0) < 0.02  # acceptance bound
        assert fr["compile"] > 0  # cold start visible
        assert fr["weight_pause"] > 0  # pause window visible
        m = eng.metrics()
        assert m["compile_events_total"] > 0
        assert 0 < m["shape_ladder_coverage"] <= 1.0
        assert m["goodput_compile_frac"] == pytest.approx(
            fr["compile"], abs=0.2
        )
        # compile events streamed with shape signatures
        recs = [
            json.loads(line)
            for line in open(gcfg.goodput.compile_events_path)
            if line.strip()
        ]
        # r14: the stream opens with the ladder-fingerprint header
        assert recs[0]["kind"] == "header" and recs[0]["fingerprint"]
        compiles = [r for r in recs if r.get("kind") == "compile"]
        assert any(r["phase"] == "prefill" for r in compiles)
        assert any(
            r["phase"] == "decode" and "rows" in r["signature"]
            for r in compiles
        )
        # quiet window passes → ready, and it LATCHES
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if eng.readiness()["state"] == "ready":
                break
            time.sleep(0.1)
        assert eng.readiness()["state"] == "ready"
        assert eng._ready_latched
        assert eng.metrics()["server_ready"] == 1.0

    def test_latency_histograms_observe_and_render(self, goodput_engine):
        eng, _, _ = goodput_engine
        hists = eng.latency_histograms()
        key = 'queue_wait_seconds{sched_class="bulk"}'
        assert hists[key].count >= 1
        assert hists['ttft_seconds{sched_class="bulk"}'].count >= 1
        text = render_prometheus(
            {}, prefix="areal_tpu_gen_", histograms=hists
        )
        assert (
            "# TYPE areal_tpu_gen_queue_wait_seconds histogram" in text
        )

    def test_goodput_jsonl_and_trace_report(
        self, goodput_engine, tmp_path, capsys
    ):
        eng, _, gcfg = goodput_engine
        eng.ledger.export_jsonl()
        # one file carrying both record kinds: ledger snapshots +
        # compile events
        merged = tmp_path / "stream.jsonl"
        with open(merged, "w") as f:
            f.write(open(gcfg.goodput.jsonl_path).read())
            f.write(open(gcfg.goodput.compile_events_path).read())
        from tools.trace_report import main as report_main

        assert report_main(["--goodput", str(merged)]) == 0
        out = capsys.readouterr().out
        assert "goodput [engine]" in out
        assert "compile bill" in out
        assert "SUM" in out
        assert report_main(["--goodput", "--json", str(merged)]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert "engine" in doc["roles"]
        assert doc["shapes"]

    def test_health_endpoint_reports_readiness(self, goodput_engine):
        from areal_tpu.inference.server import serve

        eng, _, _ = goodput_engine
        httpd = serve(eng, host="127.0.0.1", port=0, background=True)
        addr = f"127.0.0.1:{httpd.server_address[1]}"
        try:
            with urllib.request.urlopen(
                f"http://{addr}/health", timeout=10
            ) as r:
                body = json.loads(r.read())
            # the module fixture latched ready in the first test
            assert body["status"] == "ok"
            assert "ladder_coverage" in body
            with urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=10
            ) as r:
                text = r.read().decode()
            assert "areal_tpu_gen_goodput_duty_cycle" in text
            assert "areal_tpu_gen_shape_ladder_coverage" in text
            assert (
                'areal_tpu_gen_request_latency_seconds_bucket{'
                'sched_class="bulk",le="+Inf"}' in text
            )
        finally:
            httpd.shutdown()


# --------------------------------------------------------------------------
# Buckets are spans (utils/tracing.span): in the profile, in the ring
# --------------------------------------------------------------------------
class TestBucketsAreSpans:
    def test_bucket_is_in_a_cpu_profile_under_role_and_name(self, tmp_path):
        from tests.test_tracing import _profile_events

        led = goodput.GoodputLedger(
            "trainer", goodput.TRAINER_BUCKETS, remainder="other"
        )
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with led.bucket("rollout_wait"):
                time.sleep(0.002)
            with led.bucket("fwd_bwd") as outer:
                with led.bucket("optim") as nested:  # reentrant: a no-op
                    from areal_tpu.utils import tracing

                    with tracing.span("train.grad_dispatch", step=7):
                        time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        found = _profile_events(str(tmp_path))
        # (an engine of an earlier test may still be idling in its own
        # thread: its areal:engine.* spans are in the same profile)
        assert {n for n in found if n.startswith("areal:train")} == {
            "areal:trainer.rollout_wait", "areal:trainer.fwd_bwd",
            "areal:train.grad_dispatch",
        }
        assert found["areal:train.grad_dispatch"] == [{"step": 7}]
        assert nested.duration == 0.0 < outer.duration
        assert led.seconds()["fwd_bwd"] == pytest.approx(
            outer.duration, abs=2e-3
        )

    def test_bucket_is_the_parent_of_the_spans_inside_it_never_in_a_ring(self):
        from areal_tpu.api.cli_args import TracingConfig
        from areal_tpu.utils.tracing import SpanTracer, span

        tr = SpanTracer(TracingConfig(enabled=True))
        led = goodput.GoodputLedger(
            "engine", goodput.ENGINE_BUCKETS, remainder="idle"
        )
        with led.bucket("weight_pause"):  # an idle or paused poll: 1 kHz
            pass
        with led.bucket("decode") as b:
            with span("decode.fetch", tr, "__engine__", seq=3):
                pass
        spans = tr.snapshot()
        assert [s.name for s in spans] == ["decode.fetch"]
        # the bucket's span is in a profile only; in the ring its id reads
        # as a root's parent
        assert spans[0].parent == b._span.sid != 0

    def test_a_poll_stretch_is_one_span_until_other_work_enters(
        self, tmp_path
    ):
        """An idle or paused loop sleeps a millisecond at a time: the
        seconds are booked per poll, the span is one per quiet stretch."""
        from areal_tpu.utils import tracing
        from tests.test_tracing import _profile_events

        led = goodput.GoodputLedger(
            "poller", ("idle", "weight_pause", "decode", "other"),
            remainder="other",
        )
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            polls = []
            for _ in range(5):
                with led.bucket("idle", poll=True) as b:
                    time.sleep(0.001)
                polls.append(b.duration)
            held = led._tls.held
            assert held is not None and tracing._TLS.stack == [held.sid]
            with led.bucket("decode"):  # work arrives: the stretch ends
                assert led._tls.held is None
            for _ in range(3):  # a pause: the command poll and the sleep
                with led.bucket("weight_pause"):
                    pass
                with led.bucket("weight_pause", poll=True):
                    time.sleep(0.001)
            with led.bucket("idle", poll=True) as b:  # another name ends it
                pass
            polls.append(b.duration)
            led.end_poll()
            led.end_poll()  # nothing open: a no-op
        finally:
            jax.profiler.stop_trace()
        assert led._tls.held is None and tracing._TLS.stack == []
        found = _profile_events(str(tmp_path), prefix="areal:poller.")
        assert {k: len(v) for k, v in found.items()} == {
            "areal:poller.idle": 2, "areal:poller.decode": 1,
            # one command poll before the first sleep, then one stretch
            "areal:poller.weight_pause": 2,
        }
        # seconds are still booked per poll
        assert all(d >= 0.001 for d in polls[:5])
        assert led.seconds()["idle"] == pytest.approx(sum(polls), abs=1e-4)

    def test_loop_seconds_add_up_to_the_ledgers_non_idle_wall(self):
        from areal_tpu.api.cli_args import JaxGenConfig
        from areal_tpu.inference.engine import GenerationEngine
        from areal_tpu.models.config import tiny_config
        from areal_tpu.models.transformer import init_params

        cfg = tiny_config("qwen2")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        gcfg = JaxGenConfig(
            dtype="float32", max_num_seqs=4, max_model_len=64,
            prefill_chunk=16, decode_chunk=4,
        )
        gcfg.tracing.enabled = True
        eng = GenerationEngine(gcfg, model_config=cfg, params=params).start()
        try:
            for i in range(3):
                eng.generate({
                    "rid": f"loop-{i}", "input_ids": [1, 2, 3, 4 + i],
                    "sampling_params": {"max_new_tokens": 9},
                })
            m = eng.metrics()
            secs = eng.ledger.seconds()
        finally:
            eng.stop()
        loop = sum(
            m[f"total_loop_{k}_s"] for k in
            ("admit", "decode_dispatch", "decode_fetch", "decode_process")
        )
        non_idle = sum(v for b, v in secs.items() if b != "idle")
        assert m["total_loop_iterations"] > 0 and m["total_loop_idle_s"] >= 0
        assert loop == pytest.approx(non_idle, rel=0.05)
        # the same intervals are spans of the loop thread, chunk-stamped
        names = {s.name for s in eng.tracer.snapshot()}
        assert {
            "admit.schedule",
            "admit.prefill_dispatch", "admit.first_token_fetch",
            "admit.install", "decode.ensure_pages", "decode.dispatch",
            "decode.fetch", "decode.process",
        } <= names
        fetch = [s for s in eng.tracer.snapshot() if s.name == "decode.fetch"]
        assert all(s.rid == "__engine__" and "seq" in s.attrs for s in fetch)
        assert all(s.parent for s in fetch)
        # one fetch and one process per chunk, whoever retired it (the
        # decode path or a drain): every chunk is counted once
        # (but for one still in flight when the engine stopped)
        seqs = sorted(s.attrs["seq"] for s in fetch)
        assert seqs == sorted(set(seqs))
        assert 0 <= m["total_decode_chunks"] - len(seqs) <= gcfg.decode_pipeline
        process = [
            s.attrs["seq"] for s in eng.tracer.snapshot()
            if s.name == "decode.process"
        ]
        assert sorted(process) == seqs
