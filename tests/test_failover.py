"""Kill-one-of-N resilience, end to end and deterministic: two real
generation-server subprocesses with IDENTICAL weights (same init seed),
the chaos harness hard-kills one (``os._exit``) on its 3rd /generate —
mid-wave, by construction — and every in-flight rollout must complete on
the survivor with a token-exact resumed suffix (greedy streams equal to
an uninterrupted single-server run). The client's FleetMonitor and a
router fronting the pair must both reflect the event
(failovers_total / requests_migrated_total / fleet_healthy_servers)."""

import asyncio
import json
import os
import sys
import time
import urllib.request

import pytest

import jax

from tests import fixtures

# dies on its 3rd /generate: with 4 concurrent rollouts round-robined
# 2-per-server, calls 1+2 are its two rids' FIRST chunks (both issued at
# wave start), so the kill always lands on a SECOND chunk — every
# migrated request carries a non-empty accumulated suffix
VICTIM_CHAOS = "kill:side=server,match=/generate,start=2"


def _spawn_worker(env_extra=None):
    worker = os.path.join(os.path.dirname(__file__), "genserver_worker.py")
    env = dict(os.environ)
    # near-zero warming window (r11 readiness): these chaos tests pin
    # exact /generate call schedules, and a WARMING classification
    # diverting a wave's round-robin placement would break them — the
    # warming plane has its own tests (test_goodput.py)
    env["AREAL_WORKER_READY_QUIET"] = "0.01"
    if env_extra:
        env.update(env_extra)
    return fixtures.spawn_worker([sys.executable, worker, "0"], env)


@pytest.fixture(scope="module")
def survivor_server():
    """One long-lived survivor shared by BOTH chaos tests (each test
    brings its own victim): tracing on (needed by the stitch test,
    harmless to the kill test) and weight-version LABEL 1 over the same
    seed-0 weights (versions are accounting, not tokens — the kill
    test's token-exact assertion is version-blind). Yields a LAZY
    getter so each test's victim boots concurrently with it — the
    fixture body spawns and returns immediately; the first getter call
    blocks for the port."""
    survivor, slines = _spawn_worker(
        {"AREAL_WORKER_TRACE": "1", "AREAL_INIT_VERSION": "1"}
    )
    holder = {}

    def get_addr() -> str:
        if "addr" not in holder:
            sport = fixtures.wait_port(survivor, slines)
            holder["addr"] = f"127.0.0.1:{sport}"
        return holder["addr"]

    try:
        yield get_addr
    finally:
        fixtures.reap(survivor)


def _victim_and_survivor(env_extra, survivor_getter):
    """Spawn a victim with its chaos rules, booting concurrently with
    the (possibly still starting) shared survivor."""
    victim, vlines = _spawn_worker(env_extra)
    try:
        vport = fixtures.wait_port(victim, vlines)
        survivor_addr = survivor_getter()
    except BaseException:  # the per-test limit raises no Exception
        victim.kill()
        raise
    return victim, f"127.0.0.1:{vport}", survivor_addr


@pytest.fixture()
def two_servers(survivor_server):
    """(victim_addr, survivor_addr): same seed-0 weights; the victim
    carries the chaos kill rule in its environment."""
    victim, victim_addr, survivor_addr = _victim_and_survivor(
        {"AREAL_CHAOS": VICTIM_CHAOS}, survivor_server
    )
    try:
        yield victim_addr, survivor_addr
    finally:
        fixtures.reap(victim)


PROMPTS = [[7, 6, 5, 4], [1, 2, 3], [9, 8, 7], [2, 4, 6, 8]]
MAX_NEW = 12


@pytest.mark.chaos
def test_hard_kill_migrates_inflight_rollouts_token_exact(two_servers):
    from areal_tpu.api.cli_args import (
        FleetConfig,
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxGenConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.remote import RemoteInferenceEngine
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.inference.router import serve_router
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params

    victim_addr, survivor_addr = two_servers
    router = serve_router(
        addresses=[victim_addr, survivor_addr],
        fleet_config=FleetConfig(
            probe_interval_s=0.3, probe_timeout_s=2.0, dead_threshold=2,
            halfopen_interval_s=60.0, watch_membership=False,
        ),
    )
    router_addr = f"127.0.0.1:{router.server_address[1]}"
    client = RemoteInferenceEngine(
        InferenceEngineConfig(
            experiment_name="failover", trial_name="t0",
            consumer_batch_size=4, max_concurrent_rollouts=8,
            request_timeout=60, request_retries=2, setup_timeout=120,
            schedule_policy="round_robin",
            # small chunks: weight-version interleave points AND the
            # suffix-resume granularity the migration rides on
            new_tokens_per_chunk=4,
            fleet=FleetConfig(
                probe_interval_s=0.3, probe_timeout_s=2.0,
                dead_threshold=2, halfopen_interval_s=60.0,
            ),
        )
    ).initialize(addrs=[victim_addr, survivor_addr])

    try:
        async def wave():
            reqs = [
                ModelRequest(
                    rid=f"r{i}",
                    input_ids=p,
                    gconfig=GenerationHyperparameters(
                        n_samples=1, max_new_tokens=MAX_NEW, greedy=True
                    ),
                )
                for i, p in enumerate(PROMPTS)
            ]
            return await asyncio.gather(
                *[client.agenerate(r) for r in reqs]
            )

        results = asyncio.run(wave())

        # zero lost rollouts: every request ran to its full budget
        assert len(results) == len(PROMPTS)
        for out in results:
            assert len(out.output_tokens) == MAX_NEW
            assert out.stop_reason in ("stop", "length")

        # the kill actually happened and in-flight work MIGRATED (resumed
        # from accumulated tokens, not restarted)
        fm = client.fleet.metrics()
        assert fm["failovers_total"] >= 1, fm
        assert fm["requests_migrated_total"] >= 1, fm

        # token-exact: greedy streams equal an uninterrupted run on one
        # engine holding the same seed-0 weights (the migration boundary
        # is invisible in the output)
        cfg = tiny_config("qwen2")
        params = init_params(
            cfg, jax.random.PRNGKey(0), dtype=jax.numpy.float32
        )
        ref = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=cfg,
            params=params,
        ).start()
        try:
            for prompt, out in zip(PROMPTS, results):
                expect = ref.generate(
                    {
                        "input_ids": prompt,
                        "sampling_params": {
                            "max_new_tokens": MAX_NEW, "greedy": True
                        },
                    }
                )
                assert out.output_tokens == expect["output_ids"], (
                    f"prompt {prompt}: migrated stream diverged"
                )
        finally:
            ref.stop()

        # the client's prober opens the circuit on the corpse
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            fm = client.fleet.metrics()
            if fm["fleet_healthy_servers"] == 1.0:
                break
            time.sleep(0.2)
        assert fm["fleet_healthy_servers"] == 1.0, fm
        assert fm["fleet_circuit_open"] == 1.0, fm

        # ... and the event is visible on the router's /metrics plane
        deadline = time.monotonic() + 20
        text = ""
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                f"http://{router_addr}/metrics", timeout=10
            ) as r:
                text = r.read().decode()
            if "areal_tpu_router_fleet_healthy_servers 1" in text:
                break
            time.sleep(0.2)
        assert "areal_tpu_router_fleet_healthy_servers 1" in text
        assert "areal_tpu_router_fleet_circuit_open 1" in text
        assert "areal_tpu_router_failovers_total" in text
        assert "areal_tpu_router_requests_migrated_total" in text
    finally:
        client.destroy()
        router.shutdown()


# ==========================================================================
# End-to-end lineage + cross-process trace stitching through a real kill
# ==========================================================================
# victim call schedule (0-based /generate index): wave A's rid runs its 3
# chunks (idx 0-2); wave B's victim rid prefills at idx 3, its second
# chunk (idx 4) is delayed 1.2 s — the deterministic window in which the
# test drains the victim's span buffer — and its third chunk (idx 5) hard
# -kills the process mid-wave, so the migrated request resumes 8 tokens
# deep on the survivor
LINEAGE_CHAOS = (
    "latency:side=server,match=/generate,start=4,count=1,latency_s=1.2;"
    "kill:side=server,match=/generate,start=5"
)


@pytest.fixture()
def lineage_servers(survivor_server):
    """(victim, survivor), both tracing, with distinct weight-version
    LABELS (identical seed-0 weights): victim serves v0, survivor v1 —
    so a migrated sample's ledger must show two weight versions."""
    victim, victim_addr, survivor_addr = _victim_and_survivor(
        {
            "AREAL_CHAOS": LINEAGE_CHAOS,
            "AREAL_WORKER_TRACE": "1",
            "AREAL_INIT_VERSION": "0",
        },
        survivor_server,
    )
    try:
        yield victim_addr, survivor_addr
    finally:
        fixtures.reap(victim)


@pytest.mark.chaos
def test_lineage_ledger_and_stitched_trace_across_kill(
    lineage_servers, tmp_path
):
    """The tentpole contract: one kill-one-of-two chaos run yields (a) a
    lineage ledger that reconstructs the migrated sample's full path —
    two servers, two weight versions, the consuming step — and (b) ONE
    stitched Perfetto timeline where client, router, and server spans
    share the episode's trace id, with the migration linked."""
    import json as _json

    import numpy as np

    from areal_tpu.api.cli_args import (
        FleetConfig,
        GenerationHyperparameters,
        InferenceEngineConfig,
        TelemetryConfig,
        TracingConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest, unique_rid
    from areal_tpu.api.workflow_api import RolloutWorkflow
    from areal_tpu.engine.remote import RemoteInferenceEngine
    from areal_tpu.inference.router import serve_router
    from areal_tpu.utils.telemetry import TelemetryCollector

    victim_addr, survivor_addr = lineage_servers
    router = serve_router(
        addresses=[victim_addr, survivor_addr],
        fleet_config=FleetConfig(
            probe_interval_s=0.3, probe_timeout_s=2.0, dead_threshold=2,
            halfopen_interval_s=60.0, watch_membership=False,
        ),
        tracing=TracingConfig(enabled=True, max_spans=100_000),
        schedule_policy="round_robin",
    )
    router_addr = f"127.0.0.1:{router.server_address[1]}"
    lineage_path = str(tmp_path / "lineage.jsonl")
    client = RemoteInferenceEngine(
        InferenceEngineConfig(
            experiment_name="lineage", trial_name="t0",
            consumer_batch_size=2, max_concurrent_rollouts=8,
            # the trainer version never moves in this test: without a
            # loose staleness gate, wave B would never be admitted
            max_head_offpolicyness=100,
            request_timeout=60, request_retries=2, setup_timeout=120,
            schedule_policy="round_robin",
            new_tokens_per_chunk=4,
            tracing=TracingConfig(enabled=True, max_spans=100_000),
            fleet=FleetConfig(
                probe_interval_s=0.3, probe_timeout_s=2.0,
                dead_threshold=2, halfopen_interval_s=60.0,
            ),
            router_addr=router_addr,
            lineage_path=lineage_path,
        )
    ).initialize(addrs=[victim_addr, survivor_addr])
    collector = TelemetryCollector(
        addresses=[victim_addr, survivor_addr],
        config=TelemetryConfig(),  # scraped manually: no thread, no races
    )

    gconfig = GenerationHyperparameters(
        n_samples=1, max_new_tokens=MAX_NEW, greedy=True
    )

    class _OneRequest(RolloutWorkflow):
        async def arun_episode(self, engine, data):
            resp = await engine.agenerate(
                ModelRequest(
                    rid=unique_rid(),
                    input_ids=list(data["input_ids"]),
                    gconfig=gconfig.new(n_samples=1),
                )
            )
            seq = list(data["input_ids"]) + resp.output_tokens
            return {
                "input_ids": np.asarray([seq], np.int32),
                "attention_mask": np.ones((1, len(seq)), np.bool_),
                "rewards": np.asarray([1.0], np.float32),
            }

    workflow = _OneRequest()
    executor = client.workflow_executor
    try:
        # -- wave A: uneventful; lands one full rollout on EACH server --
        for i, prompt in enumerate(PROMPTS[:2]):
            assert client.submit(
                {"qid": f"wavea-{i}", "input_ids": prompt}, workflow
            )
        client.wait(2, timeout=120)
        collector.scrape_once()
        rollup_a = collector.rollup()
        # the hub aggregated two LIVE servers' /metrics into fleet gauges
        assert rollup_a["servers_scraped"] == 2.0
        assert rollup_a["generated_tokens_total"] >= 2 * MAX_NEW
        assert rollup_a["queue_wait_samples"] >= 2

        # wave B's deterministic placement (one rid per server, round
        # robin) needs BOTH servers in rotation at submit time: wait
        # out any residual WARMING classification from wave A's compile
        # storm (the victim latched ready on its first completion; the
        # router's next probe picks that up)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            states = {
                info["state"]
                for info in router.router_state.fleet.per_server().values()
            }
            if states <= {"healthy", "suspect"}:
                break
            time.sleep(0.1)

        # -- wave B: the victim dies on its 3rd wave-B call, mid-wave --
        for i, prompt in enumerate(PROMPTS[2:4]):
            assert client.submit(
                {"qid": f"waveb-{i}", "input_ids": prompt}, workflow
            )
        deadline = time.monotonic() + 120
        while True:
            # keep draining /trace while the wave runs: the victim's
            # spans survive its death up to the last scrape (the 1.2 s
            # latency window makes one pre-kill drain deterministic)
            collector.scrape_once()
            try:
                client.wait(2, timeout=0.3)
                break
            except TimeoutError:
                assert time.monotonic() < deadline, "wave B never finished"

        # -- lineage: the migrated sample's full path, ledger-only -----
        records = {
            r["uid"]: r for r in executor.lineage.snapshot()
        }
        assert len(records) == 4
        migrated = [
            r for r in records.values()
            if r["uid"].startswith("qid:waveb") and len(r["servers"]) > 1
        ]
        assert len(migrated) == 1, (
            f"exactly one wave-B sample must migrate: "
            f"{[(r['uid'], r['servers']) for r in records.values()]}"
        )
        mig = migrated[0]
        assert mig["status"] == "collected"
        assert mig["servers"] == [victim_addr, survivor_addr]
        assert mig["weight_versions"] == [0, 1]  # two weight versions
        assert mig["migrations"] >= 1
        assert mig["attempts"] == 1  # failover is not an episode retry
        assert mig["consumed_step"] is not None
        assert mig["rewards"] == [1.0]
        segs = mig["requests"][0]["segments"]
        assert segs[0]["server"] == victim_addr
        assert segs[0]["versions"] == [0] and segs[0]["tokens"] == 8
        assert segs[-1]["server"] == survivor_addr
        assert segs[-1]["versions"] == [1] and segs[-1]["tokens"] == 4
        # the un-migrated wave-B sibling stayed single-server
        other = [
            r for r in records.values()
            if r["uid"].startswith("qid:waveb") and r is not mig
        ][0]
        assert other["servers"] == [survivor_addr]

        # -- ONE stitched Perfetto timeline across all four processes --
        doc = collector.stitched_trace(
            extra_sources=[
                ("client", client.tracer),
                ("router", router.router_state.tracer),
            ]
        )
        procs = {
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e.get("name") == "process_name"
        }
        assert procs == {
            f"server:{victim_addr}", f"server:{survivor_addr}",
            "client", "router",
        }
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        mig_rid = mig["requests"][0]["rid"]
        mig_events = [e for e in xs if e["args"].get("rid") == mig_rid]
        mig_pids = {e["pid"] for e in mig_events}
        # client + router + at least the survivor carry the migrated rid
        assert len(mig_pids) >= 3, mig_events
        # ...and every trace-tagged span of that rid shares ONE trace id
        mig_traces = {
            e["args"]["trace"]
            for e in mig_events
            if "trace" in e["args"]
        }
        assert mig_traces == {mig["trace_id"]}
        assert any(e["name"] == "route" for e in mig_events)
        assert any(e["name"] == "migration" for e in mig_events)
        # migration is LINKED: flow arrows pair up by id
        flows = [
            e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")
        ]
        starts = {e["id"] for e in flows if e["ph"] == "s"}
        finishes = {e["id"] for e in flows if e["ph"] == "f"}
        assert starts and starts == finishes
        # wave A's victim-served rollout is on the same timeline with a
        # client↔server shared trace id (drained before the kill)
        victim_pid = next(
            e["pid"] for e in doc["traceEvents"]
            if e.get("name") == "process_name"
            and e["args"]["name"] == f"server:{victim_addr}"
        )
        victim_traces = {
            e["args"]["trace"]
            for e in xs
            if e["pid"] == victim_pid and "trace" in e["args"]
        }
        wavea_traces = {
            records[f"qid:wavea-{i}"]["trace_id"] for i in range(2)
        }
        assert victim_traces & wavea_traces

        # -- post-kill fleet view + the report tooling ------------------
        collector.scrape_once()
        rollup_b = collector.rollup()
        assert rollup_b["servers_scraped"] == 1.0
        assert rollup_b["scrape_failures_total"] >= 1.0

        sys.path.insert(
            0,
            os.path.join(os.path.dirname(__file__), "..", "tools"),
        )
        import trace_report

        # the ledger ALONE reconstructs the migrated sample's path
        assert trace_report.main([lineage_path, "--lineage"]) == 0
        ln = trace_report.lineage_summary(
            trace_report.load_lineage(lineage_path)
        )
        assert ln["consumed"] == 4
        assert ln["migrated"] == 1
        assert ln["multi_server"] == 1 and ln["multi_version"] == 1

        manifest_path = str(tmp_path / "manifest.json")
        with open(manifest_path, "w") as f:
            _json.dump(collector.manifest(), f)
        assert trace_report.main([manifest_path, "--fleet"]) == 0

        # CI smoke: the new span names are required-present in the
        # client+router span stream
        spans_path = str(tmp_path / "client_router.jsonl")
        client.tracer.export_jsonl(spans_path)
        router.router_state.tracer.export_jsonl(spans_path)
        assert trace_report.main(
            [
                spans_path,
                "--require",
                "route,generate_call,rollout_request,failover,migration",
            ]
        ) == 0
    finally:
        client.destroy()
        router.shutdown()


# ==========================================================================
# Autoscaler scale-down under live traffic (r10 traffic plane)
# ==========================================================================
@pytest.fixture()
def drainee_server():
    """(drainee_addr, survivor_addr): TWO real generation engines with
    identical seed-0 weights, each behind its own real HTTP shell
    (drain mode, /health, /metrics are all per-shell ServerControl
    state). In-process rather than subprocess — drain needs no process
    death, and the wall-time budget note from r7 applies (the live-hub
    test set this precedent); the /drain → finish-in-flight → 503 →
    suffix-resume path is byte-for-byte the production one."""
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.inference.server import serve
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params

    cfg = tiny_config("qwen2")
    engines, shells, addrs = [], [], []
    for _ in range(2):
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=cfg, params=params,
        ).start()
        httpd = serve(eng, host="127.0.0.1", port=0, background=True)
        engines.append(eng)
        shells.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    yield addrs[0], addrs[1]
    for httpd in shells:
        httpd.shutdown()
    for eng in engines:
        eng.stop()


@pytest.mark.chaos
def test_autoscaler_drain_live_server_zero_loss_token_exact(
    drainee_server,
):
    """Scale-down composes with the chaos-harness invariants: the
    autoscaler decides the fleet is oversized mid-generation and drains
    one of two REAL servers. Zero rollouts are lost, greedy streams are
    bit-identical to an undrained run (in-flight chunks finish on the
    drainee; later chunks suffix-resume on the survivor), and the drain
    is visible on the client's fleet metrics + the autoscaler gauges."""
    from areal_tpu.api.cli_args import (
        FleetConfig,
        GenerationHyperparameters,
        InferenceEngineConfig,
        TrafficConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.remote import RemoteInferenceEngine
    from areal_tpu.inference.fleet import FleetAutoscaler

    drainee_addr, survivor_addr = drainee_server
    MAX_NEW_DRAIN = 16

    client = RemoteInferenceEngine(
        InferenceEngineConfig(
            experiment_name="autoscale", trial_name="t0",
            consumer_batch_size=4, max_concurrent_rollouts=8,
            request_timeout=60, request_retries=2, setup_timeout=120,
            schedule_policy="round_robin",
            # small chunks: the drain lands between chunks, and the
            # post-drain 503s suffix-resume onto the survivor
            new_tokens_per_chunk=4,
            fleet=FleetConfig(
                probe_interval_s=0.3, probe_timeout_s=2.0,
                dead_threshold=2, halfopen_interval_s=60.0,
            ),
        )
    ).initialize(addrs=[drainee_addr, survivor_addr])

    # the control law is driven manually mid-wave (deterministic);
    # the DRAIN ACTION is the real POST /drain against a live server
    def real_drain(addr):
        import urllib.request as _rq

        req = _rq.Request(
            f"http://{addr}/drain", data=b"{}",
            headers={"Content-Type": "application/json"},
        )
        with _rq.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["status"] == "draining"

    quiet = {"running": 0.0, "queued": 0.0, "kv_util": 0.0}
    scaler = FleetAutoscaler(
        TrafficConfig(
            autoscale=True, min_servers=1, max_servers=2,
            down_consecutive=1, cooldown_s=0.0, down_kv_util=0.9,
        ),
        launch_fn=lambda: None,
        drain_fn=real_drain,
        addresses_fn=lambda: [drainee_addr, survivor_addr],
        # steer the victim choice: the drainee reports idle, the
        # survivor busy — least-loaded selection must drain the drainee
        observe_fn=lambda a: dict(
            quiet if a == drainee_addr
            else {"running": 2.0, "queued": 0.0, "kv_util": 0.0}
        ),
    )

    try:
        results_holder = {}

        async def wave():
            reqs = [
                ModelRequest(
                    rid=f"dr{i}",
                    input_ids=p,
                    gconfig=GenerationHyperparameters(
                        n_samples=1, max_new_tokens=MAX_NEW_DRAIN,
                        greedy=True,
                    ),
                )
                for i, p in enumerate(PROMPTS)
            ]
            tasks = [
                asyncio.ensure_future(client.agenerate(r)) for r in reqs
            ]
            # drain mid-wave: once BOTH servers have produced tokens
            # for this wave, the fleet is live-traffic by construction
            def tokens(addr):
                try:
                    with urllib.request.urlopen(
                        f"http://{addr}/metrics", timeout=5
                    ) as r:
                        text = r.read().decode()
                    for line in text.splitlines():
                        if line.startswith(
                            "areal_tpu_gen_total_generated_tokens "
                        ):
                            return float(line.rsplit(" ", 1)[1])
                except Exception:
                    return 0.0
                return 0.0

            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (
                    tokens(drainee_addr) > 0
                    and tokens(survivor_addr) > 0
                ):
                    break
                await asyncio.sleep(0.05)
            assert tokens(drainee_addr) > 0, "drainee never took traffic"
            assert scaler.evaluate_once() == f"down:{drainee_addr}"
            results_holder["out"] = await asyncio.gather(*tasks)

        asyncio.run(wave())
        results = results_holder["out"]

        # zero lost rollouts
        assert len(results) == len(PROMPTS)
        for out in results:
            assert len(out.output_tokens) == MAX_NEW_DRAIN

        # token-exact: greedy streams equal an UNDRAINED single-server
        # run over the same seed-0 weights — the live survivor serves
        # the reference (one uninterrupted /generate per prompt)
        for prompt, out in zip(PROMPTS, results):
            req = urllib.request.Request(
                f"http://{survivor_addr}/generate",
                data=json.dumps(
                    {
                        "input_ids": prompt,
                        "sampling_params": {
                            "max_new_tokens": MAX_NEW_DRAIN,
                            "greedy": True,
                        },
                    }
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                expect = json.loads(r.read())
            assert out.output_tokens == expect["output_ids"], (
                f"prompt {prompt}: drained stream diverged"
            )

        # autoscaler gauges reflect the action
        sm = scaler.metrics()
        assert sm["autoscale_down_total"] == 1.0
        assert sm["fleet_target_size"] == 1.0

        # the drain is visible on /metrics planes: the drainee's own
        # /health says draining, and the client fleet monitor moves it
        # out of rotation without opening a circuit
        with urllib.request.urlopen(
            f"http://{drainee_addr}/health", timeout=5
        ) as r:
            health = json.loads(r.read())
        assert health["status"] == "draining"
        deadline = time.monotonic() + 20
        fm = {}
        while time.monotonic() < deadline:
            fm = client.fleet.metrics()
            if fm["fleet_draining_servers"] == 1.0:
                break
            time.sleep(0.2)
        assert fm["fleet_draining_servers"] == 1.0, fm
        assert fm["fleet_circuit_open"] == 0.0, fm
        assert fm["fleet_healthy_servers"] == 1.0, fm
    finally:
        client.destroy()


# ==========================================================================
# Multi-policy pin lifecycle across failover (r19)
# ==========================================================================
@pytest.fixture()
def policy_servers():
    """(engines, addrs): TWO in-process engines, each serving the SAME
    named policy line ``actor`` (seed-7 weights, distinct from the
    seed-0 default line) behind a real HTTP shell. In-process so the
    test can audit each server's policy buffer ACCOUNT (pins) directly
    — the satellite invariant is about accounting, not process death."""
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.inference.server import serve
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params
    from areal_tpu.utils import weight_transfer as wt

    cfg = tiny_config("qwen2")
    actor_params = jax.device_get(
        init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    )
    engines, shells, addrs = [], [], []
    for _ in range(2):
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=cfg, params=params,
        ).start()
        # push the named line through the real chunked wire format
        leaves = [
            (k, np.asarray(v)) for k, v in wt.flatten_params(actor_params)
        ]
        plan = wt.chunk_leaves(leaves, 1 << 30)
        for i, items in enumerate(plan):
            header, arrays = wt.decode_chunk(
                wt.encode_chunk(1, i, len(plan), items)
            )
            out = eng.update_policy_chunk("actor", header, arrays)
        assert out["complete"] and out["policy"] == "actor"
        httpd = serve(eng, host="127.0.0.1", port=0, background=True)
        engines.append(eng)
        shells.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    yield engines, addrs
    for httpd in shells:
        httpd.shutdown()
    for eng in engines:
        eng.stop()


@pytest.mark.chaos
@pytest.mark.slow
def test_policy_pins_released_across_drain_failover_and_abort(
    policy_servers,
):
    """Pin-lifecycle regression (r19): a named-policy request failing
    over mid-decode must release its pin on the dead server's buffer
    account — after the wave migrates off the drained victim, NEITHER
    server holds a pinned policy buffer, and a hard mid-decode abort
    (the failover/preemption finish path) releases its pin too. A leak
    here would make the victim's buffer permanently unretirable."""
    from areal_tpu.api.cli_args import (
        FleetConfig,
        GenerationHyperparameters,
        InferenceEngineConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.remote import RemoteInferenceEngine

    (victim_eng, survivor_eng), (victim_addr, survivor_addr) = (
        policy_servers
    )
    MAX_NEW_POL = 16
    client = RemoteInferenceEngine(
        InferenceEngineConfig(
            experiment_name="polpins", trial_name="t0",
            consumer_batch_size=4, max_concurrent_rollouts=8,
            request_timeout=60, request_retries=2, setup_timeout=120,
            schedule_policy="round_robin",
            # small chunks: the drain lands between chunks and later
            # chunks suffix-resume on the survivor
            new_tokens_per_chunk=4,
            fleet=FleetConfig(
                probe_interval_s=0.3, probe_timeout_s=2.0,
                dead_threshold=2, halfopen_interval_s=60.0,
            ),
        )
    ).initialize(addrs=[victim_addr, survivor_addr])

    try:
        async def wave():
            reqs = [
                ModelRequest(
                    rid=f"pp{i}",
                    input_ids=p,
                    gconfig=GenerationHyperparameters(
                        n_samples=1, max_new_tokens=MAX_NEW_POL,
                        greedy=True,
                    ),
                    metadata={"policy": "actor"},
                )
                for i, p in enumerate(PROMPTS)
            ]
            tasks = [
                asyncio.ensure_future(client.agenerate(r)) for r in reqs
            ]
            # drain the victim once BOTH servers hold live policy work
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (
                    victim_eng.policy_status()["actor"]["requests_total"]
                    and survivor_eng.policy_status()["actor"][
                        "requests_total"
                    ]
                ):
                    break
                await asyncio.sleep(0.05)
            assert victim_eng.policy_status()["actor"]["requests_total"], (
                "victim never took policy traffic"
            )
            req = urllib.request.Request(
                f"http://{victim_addr}/drain", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                assert json.loads(r.read())["status"] == "draining"
            return await asyncio.gather(*tasks)

        results = asyncio.run(wave())

        # zero lost rollouts, token-exact vs a dedicated seed-7 engine
        assert len(results) == len(PROMPTS)
        import jax.numpy as jnp

        from areal_tpu.api.cli_args import JaxGenConfig
        from areal_tpu.inference.engine import GenerationEngine
        from areal_tpu.models.config import tiny_config
        from areal_tpu.models.transformer import init_params

        cfg = tiny_config("qwen2")
        ref = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=cfg,
            params=init_params(
                cfg, jax.random.PRNGKey(7), dtype=jnp.float32
            ),
        ).start()
        try:
            for prompt, out in zip(PROMPTS, results):
                expect = ref.generate(
                    {
                        "input_ids": prompt,
                        "sampling_params": {
                            "max_new_tokens": MAX_NEW_POL, "greedy": True
                        },
                    }
                )
                assert out.output_tokens == expect["output_ids"], (
                    f"prompt {prompt}: migrated policy stream diverged"
                )
        finally:
            ref.stop()

        # THE satellite invariant: no pinned-buffer leak on either
        # account after the failover — every migrated chunk released
        # its pin at finish, on the drained victim included
        for eng in (victim_eng, survivor_eng):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if eng.metrics()["policy_pinned_requests"] == 0.0:
                    break
                time.sleep(0.05)
            assert eng.metrics()["policy_pinned_requests"] == 0.0
            assert eng.policy_status()["actor"]["pinned_requests"] == 0
        # ...which is exactly what keeps the line retirable
        victim_eng.retire_policy("actor")
        assert victim_eng.policy_status() == {}

        # hard mid-decode abort on the survivor (the preempt/failover
        # finish path): the pin must drop with the abort, and the line
        # must keep serving afterwards
        fut = survivor_eng.submit({
            "rid": "abort-me", "input_ids": [3, 1, 4],
            "policy": "actor",
            "sampling_params": {"max_new_tokens": 40, "greedy": True},
        })
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if survivor_eng.metrics()["policy_pinned_requests"] == 1.0:
                break
            time.sleep(0.01)
        assert survivor_eng.metrics()["policy_pinned_requests"] == 1.0
        survivor_eng.pause()
        out = fut.result(timeout=60)
        assert out["meta_info"]["finish_reason"]["type"] == "abort"
        assert survivor_eng.metrics()["policy_pinned_requests"] == 0.0
        survivor_eng.continue_generation()
        alive = survivor_eng.generate(
            {
                "rid": "after-abort", "input_ids": [3, 1, 4],
                "policy": "actor",
                "sampling_params": {"max_new_tokens": 4, "greedy": True},
            },
            timeout=60,
        )
        assert alive["meta_info"]["policy"] == "actor"
    finally:
        client.destroy()
