"""Environment service plane (env/service.py): sessionful workers,
fleet health classification, journaled replay on worker death, bounded
tool execution, and the no-silent-reward-poisoning verifier contract.

The headline chaos test hard-kills one of two REAL env-worker
subprocesses mid-multi-turn-episode and proves zero lost rollouts with
a trajectory + final reward bit-identical to an uninterrupted run
(deterministic journal replay)."""

import asyncio
import json
import os
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tests import fixtures  # noqa: E402

from areal_tpu.api.cli_args import (
    DurabilityConfig,
    EnvServiceConfig,
    FleetConfig,
    GenerationHyperparameters,
    InferenceEngineConfig,
)
from areal_tpu.api.env_api import Env
from areal_tpu.api.io_struct import ModelResponse
from areal_tpu.api.workflow_api import (
    EpisodeQuarantinedError,
    RolloutWorkflow,
    WorkflowExecutor,
)
from areal_tpu.env import service as ES
from areal_tpu.inference.fleet import FleetMonitor, ServerState
from areal_tpu.reward import verifier_service as VS
from areal_tpu.utils import chaos, telemetry
from areal_tpu.utils.http import HttpRequestError
from areal_tpu.utils.tracing import SpanTracer, TracingConfig
from areal_tpu.workflow.agentic import AgenticToolWorkflow
from examples.countdown_agent import ToyToolTokenizer, toy_tool_parser

CFG = EnvServiceConfig(
    call_retries=2, call_timeout_s=10.0, reset_timeout_s=10.0,
    retry_delay_s=0.05,
)


# ------------------------------------------------------------------ helpers
class _ScriptedEngine:
    """Deterministic engine: pops scripted completions (the
    test_agentic_countdown idiom)."""

    def __init__(self, tok, outputs):
        self.tok = tok
        self.outputs = list(outputs)

    def get_version(self):
        return 0

    async def agenerate(self, req):
        out = self.tok.encode(self.outputs.pop(0))
        return ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=out,
            output_logprobs=[-0.3] * len(out),
            output_versions=[0] * len(out),
            stop_reason="stop",
        )


SCRIPT = [
    "<call>3*7</call>",
    "<call>5+2</call>",
    "<submit>3*(5+2)</submit>",
]


def _agentic_episode(addrs, capture, tracer=None):
    """One scripted countdown episode against remote env workers."""
    tok = ToyToolTokenizer()
    eng = _ScriptedEngine(tok, SCRIPT)
    inner = ES.make_remote_tool_env_factory(
        addrs=addrs, config=CFG, tracer=tracer,
        reset_keys=["numbers", "target"],
    )

    def factory(data):
        env = inner(data)
        capture.append(env)
        return env

    wf = AgenticToolWorkflow(
        env_factory=factory,
        gconfig=GenerationHyperparameters(n_samples=1, max_new_tokens=16),
        tokenizer=tok,
        max_tool_rounds=4,
        turn_discount=0.5,
        tool_parser=toy_tool_parser,
        tool_timeout_s=15.0,
    )
    return asyncio.run(
        wf.arun_episode(eng, {"numbers": [3, 5, 2], "target": 21})
    )


# ---------------------------------------------------------- session protocol
def test_session_protocol_roundtrip():
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def run():
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            obs = await env.areset(numbers=[3, 5, 2], target=21)
            assert env.replay_safe  # mirrored from the worker's env
            assert "21" in obs["prompt"] and len(obs["tools"]) == 2
            o, r, d, _ = await env.astep({
                "name": "eval_expression",
                "arguments": json.dumps({"expression": "3*7"}),
            })
            assert (o, r, d) == ("21", 0.0, False)
            o, r, d, info = await env.astep({
                "name": "submit_expression",
                "arguments": json.dumps({"expression": "3*(5+2)"}),
            })
            assert d and r == 1.0 and info["detail"] == "correct"
            await env.aclose()

        asyncio.run(run())
        # metrics surface the session lifecycle
        body = urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=5
        ).read().decode()
        assert "areal_tpu_env_steps_total 2" in body
        assert "areal_tpu_env_resets_total 1" in body
        assert "areal_tpu_env_closes_total 1" in body
        assert "areal_tpu_env_sessions_active 0" in body
    finally:
        httpd.shutdown()


def test_unknown_session_is_404_and_bad_reset_is_4xx():
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        req = urllib.request.Request(
            f"http://{addr}/step",
            data=json.dumps({"session": "nope", "action": {}}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 404
    finally:
        httpd.shutdown()


def _post_json(addr, path, payload):
    req = urllib.request.Request(
        f"http://{addr}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return json.loads(urllib.request.urlopen(req, timeout=5).read())


def test_step_idempotency_and_desync_conflict():
    """/step is a non-idempotent POST behind a retrying client, so each
    step carries its journal index: an exact retry of the last applied
    step replays the cached response (never double-applies), and any
    other mismatch answers 409 — the session-desync signal."""
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        sid = _post_json(addr, "/reset", {
            "kwargs": {"numbers": [3, 5, 2], "target": 21}
        })["session"]
        act = {"name": "eval_expression",
               "arguments": json.dumps({"expression": "3*7"})}
        first = _post_json(addr, "/step", {
            "session": sid, "action": act, "seq": 0
        })
        assert first["observation"] == "21"
        # lost-response retry: same seq + same action → cached answer,
        # and the env was NOT stepped again
        retry = _post_json(addr, "/step", {
            "session": sid, "action": act, "seq": 0
        })
        assert retry == first
        body = urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=5
        ).read().decode()
        assert "areal_tpu_env_steps_total 1" in body
        # same seq with a DIFFERENT action = half-applied/cancelled call:
        # 409, the client rebuilds via replay
        other = {"name": "eval_expression",
                 "arguments": json.dumps({"expression": "5+2"})}
        for bad in (
            {"session": sid, "action": other, "seq": 0},
            {"session": sid, "action": other, "seq": 5},
        ):
            req = urllib.request.Request(
                f"http://{addr}/step", data=json.dumps(bad).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=5)
            assert ei.value.code == 409
    finally:
        httpd.shutdown()


def test_desynced_session_replays_onto_same_worker():
    """A 409/404 comes from a LIVE worker (restarted or desynced) — with
    a single-worker pool the replay must target that same worker, not
    exclude it and strand the episode."""
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def drive():
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            await env.areset(numbers=[3, 5, 2], target=21)
            # desync the server session out-of-band: apply a step the
            # client journal will never know about (the half-applied /
            # cancelled-call shape)
            _post_json(addr, "/step", {
                "session": env._sid,
                "action": {"name": "eval_expression",
                           "arguments": json.dumps({"expression": "9"})},
                "seq": 0,
            })
            o, r, d, _ = await env.astep({
                "name": "eval_expression",
                "arguments": json.dumps({"expression": "3*7"}),
            })
            assert (o, d) == ("21", False)
            assert env.stats["replays"] == 1  # rebuilt on the SAME worker
            _, r, d, _ = await env.astep({
                "name": "submit_expression",
                "arguments": json.dumps({"expression": "3*(5+2)"}),
            })
            assert d and r == 1.0
            await env.aclose()

        asyncio.run(drive())
    finally:
        httpd.shutdown()


def test_env_raised_error_is_action_error_not_failover():
    """An env exception is 422 → EnvActionError (workflows feed it back
    as an error observation), NOT a worker failure: a poison action must
    not trigger a replay storm or mark healthy workers failed, and the
    session stays usable."""
    from areal_tpu.api.env_api import EnvActionError, EnvServiceError

    class AngryEnv(Env):
        replay_safe = True

        async def areset(self, **kwargs):
            return "ready"

        async def astep(self, action):
            if action.get("boom"):
                raise ValueError("poison action")
            return "ok", 0.0, False, {}

    assert not issubclass(EnvActionError, EnvServiceError)
    httpd = ES.serve_env(lambda: AngryEnv(), background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def drive():
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            await env.areset()
            o, _, _, _ = await env.astep({"boom": False})
            assert o == "ok"
            with pytest.raises(EnvActionError):
                await env.astep({"boom": True})
            assert env.stats["failovers"] == 0
            assert env.stats["replays"] == 0
            # the session survived the poison action, journal intact
            o, _, _, _ = await env.astep({"boom": False})
            assert o == "ok"
            await env.aclose()

        asyncio.run(drive())
    finally:
        httpd.shutdown()


def test_idle_sessions_expire():
    """Leaked sessions (crashed client, failed close) are TTL-swept so a
    worker can't ratchet to max_sessions and 429 forever."""
    httpd = ES.serve_env(
        ES.countdown_env, background=True, session_ttl_s=0.2
    )
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        sid = _post_json(addr, "/reset", {
            "kwargs": {"numbers": [1, 2], "target": 3}
        })["session"]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            body = urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=5
            ).read().decode()
            if "areal_tpu_env_sessions_expired_total 1" in body:
                break
            time.sleep(0.05)
        assert "areal_tpu_env_sessions_expired_total 1" in body
        assert "areal_tpu_env_sessions_active 0" in body
        req = urllib.request.Request(
            f"http://{addr}/step",
            data=json.dumps({"session": sid, "action": {}}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 404
    finally:
        httpd.shutdown()


def test_draining_semantics_and_fleet_classification():
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def drive():
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            await env.areset(numbers=[1, 2], target=3)
            # drain: health flips, new resets get 503
            req = urllib.request.Request(
                f"http://{addr}/drain", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=5).read()
            health = json.loads(urllib.request.urlopen(
                f"http://{addr}/health", timeout=5
            ).read())
            assert health["status"] == "draining"
            # FleetMonitor (env service label) classifies it out of
            # rotation WITHOUT opening a circuit — exactly like a
            # draining gen server
            mon = FleetMonitor([addr], config=FleetConfig(), service="env")
            mon.probe_once()
            assert mon.state(addr) is ServerState.DRAINING
            assert not mon.is_schedulable(addr)
            assert mon.per_server()[addr]["service"] == "env"
            # new sessions are refused...
            env2 = ES.RemoteEnv(addrs=[addr], config=CFG)
            with pytest.raises(ES.EnvWorkerUnavailableError):
                await env2.areset(numbers=[1], target=1)
            await env2.aclose()
            # ...but the in-flight session may still step to completion
            _, r, d, _ = await env.astep({
                "name": "submit_expression",
                "arguments": json.dumps({"expression": "1+2"}),
            })
            assert d and r == 1.0
            await env.aclose()

        asyncio.run(drive())
    finally:
        httpd.shutdown()


def test_fleet_transitions_from_env_worker_death():
    """FleetMonitor state machine driven by a real env worker's /health:
    HEALTHY while alive, SUSPECT→DEAD as probes fail after death (each
    probe opens a fresh connection, so an in-process shutdown IS a
    death as far as the prober can tell)."""
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    mon = FleetMonitor(
        [addr],
        config=FleetConfig(suspect_threshold=1, dead_threshold=2),
        service="env",
    )
    mon.probe_once()
    assert mon.state(addr) is ServerState.HEALTHY
    httpd.shutdown()
    httpd.server_close()
    mon.probe_once()
    assert mon.state(addr) is ServerState.SUSPECT
    assert mon.is_schedulable(addr)  # one failed probe must not evict
    mon.probe_once()
    assert mon.state(addr) is ServerState.DEAD
    assert mon.schedulable_addresses() == []


# ------------------------------------------------------------- chaos replay
@pytest.mark.chaos
def test_kill_one_of_two_env_workers_bit_identical_episode():
    """THE acceptance chaos test: two live env workers, the one serving
    the episode hard-kills (os._exit) on its 3rd /step — mid-multi-turn-
    episode by construction — and the episode must finish on the
    survivor via journal replay with a trajectory + final reward
    BIT-IDENTICAL to an uninterrupted run. Zero lost rollouts."""
    # two live workers: the victim dies on its 3rd /step, the survivor
    # doubles as the baseline host (sessions are independent, so the
    # uninterrupted run beforehand shares it without interference)
    with fixtures.children() as procs:
        victim_proc, victim_addr = fixtures.spawn_env_worker(
            "countdown_env",
            {"AREAL_CHAOS": "kill:side=server,match=/step,start=2"},
        )
        procs.append(victim_proc)
        surv_proc, surv_addr = fixtures.spawn_env_worker("countdown_env")
        procs.append(surv_proc)
        base_envs = []
        baseline = _agentic_episode([surv_addr], base_envs)
        assert baseline is not None
        assert base_envs[0].stats["replays"] == 0

        # chaos: the client opens the session on the victim (first
        # address, fresh round-robin)
        chaos_envs = []
        batch = _agentic_episode([victim_addr, surv_addr], chaos_envs)
        assert victim_proc.poll() is not None, "chaos kill never fired"

    # zero lost rollouts: the episode completed, exactly one replay
    assert batch is not None
    st = chaos_envs[0].stats
    assert st["replays"] == 1 and st["failovers"] >= 1
    # bit-identical trajectory + reward vs the uninterrupted run
    assert set(batch) == set(baseline)
    for key in baseline:
        np.testing.assert_array_equal(
            batch[key], baseline[key], err_msg=f"key {key} diverged"
        )
    assert float(batch["rewards"].reshape(-1)[-1]) > 0  # real reward rows
    assert batch["tool_errors"].sum() == 0  # replay, not error-feedback


@pytest.mark.chaos
def test_non_replayable_env_routes_to_session_lost():
    """A non-replay-safe env whose worker dies mid-episode must raise
    the typed session-lost error (feeding episode retry/quarantine),
    not hang and not silently resume."""

    class OpaqueEnv(Env):
        replay_safe = False  # e.g. wall-clock / external state inside

        async def areset(self, **kwargs):
            return "ready"

        async def astep(self, action):
            return "ok", 0.0, False, {}

    httpd = ES.serve_env(lambda: OpaqueEnv(), background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    # counted chaos: first /step works, every later one drops the
    # connection — the client sees its worker die under the session
    chaos.configure("connect_drop:side=server,match=/step,start=1")
    try:
        async def drive():
            env = ES.RemoteEnv(
                addrs=[addr],
                config=EnvServiceConfig(
                    call_retries=2, call_timeout_s=5, reset_timeout_s=5,
                    retry_delay_s=0.02,
                ),
            )
            await env.areset()
            assert not env.replay_safe
            o, _, _, _ = await env.astep({"k": 1})
            assert o == "ok"
            with pytest.raises(ES.EnvSessionLostError):
                await env.astep({"k": 2})
            await env.aclose()

        asyncio.run(drive())
    finally:
        chaos.reset()
        httpd.shutdown()


# ------------------------------------------------- bounded tool execution
class _SlowEnv:
    """Local tool env whose first eval call hangs (sleeps) and whose
    second raises; the episode must keep going on error observations."""

    def __init__(self):
        from areal_tpu.env.countdown import CountdownEnv

        self._inner = CountdownEnv(numbers=[3, 5, 2], target=21)
        self.calls = 0

    @property
    def tools(self):
        return self._inner.tools

    def prompt(self):
        return self._inner.prompt()

    @property
    def done(self):
        return self._inner.done

    @property
    def reward(self):
        return self._inner.reward

    def call(self, name, arguments):
        self.calls += 1
        if self.calls == 1:
            time.sleep(1.0)  # way past the tool timeout
        if self.calls == 2:
            raise RuntimeError("tool backend exploded")
        return self._inner.call(name, arguments)


def test_tool_timeout_and_exception_become_observations():
    tok = ToyToolTokenizer()
    eng = _ScriptedEngine(tok, [
        "<call>3*7</call>",          # -> timeout
        "<call>5+2</call>",          # -> raised exception
        "<submit>3*(5+2)</submit>",  # -> executes normally
    ])
    env = _SlowEnv()
    wf = AgenticToolWorkflow(
        env_factory=lambda d: env,
        gconfig=GenerationHyperparameters(n_samples=1, max_new_tokens=16),
        tokenizer=tok,
        max_tool_rounds=4,
        tool_parser=toy_tool_parser,
        tool_timeout_s=0.2,
    )
    async def run():
        # measured INSIDE the loop: asyncio.run's teardown joins the
        # still-sleeping to_thread worker, which is loop-close cost the
        # long-lived executor never pays
        t0 = time.monotonic()
        batch = await wf.arun_episode(eng, {})
        return batch, time.monotonic() - t0

    batch, dt = asyncio.run(run())
    # the hung tool cost ~tool_timeout_s, not its 1 s sleep
    assert dt < 0.8
    assert batch is not None
    assert batch["tool_calls"].tolist() == [1, 1, 1]
    assert batch["tool_errors"].tolist() == [1, 1, 0]
    assert env.done and env.reward == 1.0  # episode still finished


def test_tool_error_observation_shape():
    from areal_tpu.workflow.agentic import tool_error_observation

    obs = json.loads(tool_error_observation(
        "eval_expression", "ToolTimeout", "too slow", timeout_s=0.5
    ))
    assert obs["error"]["tool"] == "eval_expression"
    assert obs["error"]["type"] == "ToolTimeout"
    assert obs["error"]["timeout_s"] == 0.5


def test_reward_timeout_is_typed():
    from areal_tpu.api.reward_api import AsyncRewardWrapper, RewardTimeoutError

    wrapped = AsyncRewardWrapper(
        lambda *a, **k: time.sleep(5.0) or 1.0, timeout_s=0.2
    )

    async def run():
        with pytest.raises(RewardTimeoutError):
            await wrapped("p", "c", [], [])

    t0 = time.monotonic()
    asyncio.run(run())
    assert time.monotonic() - t0 < 4.0


# --------------------------------------------------- verifier retry split
class _CountingStub:
    """HTTP stub answering every POST with one fixed status; counts
    requests and captures headers."""

    def __init__(self, status=200, body=None):
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                stub.requests += 1
                stub.headers.append(dict(self.headers))
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                payload = json.dumps(stub.body or {"reward": 1.0}).encode()
                self.send_response(stub.status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        self.status = status
        self.body = body
        self.requests = 0
        self.headers = []
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        ).start()
        self.addr = f"127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


def test_verifier_4xx_never_retried_5xx_retried():
    # 4xx: ONE request total — no transient retry, no pool failover —
    # and the typed transport error surfaces with the status attached
    bad = _CountingStub(status=400)
    try:
        v = VS.RemoteVerifier(
            [bad.addr, bad.addr], retries=3, timeout=5,
            local_fallback=False,
        )
        with pytest.raises(HttpRequestError) as ei:
            v.verify({"kind": "math", "completion": "x", "answer": "1"})
        assert ei.value.status == 400
        assert bad.requests == 1
    finally:
        bad.close()

    # 5xx: retried `retries` times on the address, then the lap moves on
    # (same stub twice = 2 lap entries), then the typed unavailability
    sick = _CountingStub(status=500)
    try:
        v = VS.RemoteVerifier(
            [sick.addr], retries=2, timeout=5, local_fallback=False,
            retry_delay=0.02,
        )
        with pytest.raises(VS.VerifierUnavailableError):
            v.verify({"kind": "math", "completion": "x", "answer": "1"})
        assert sick.requests == 2  # retried, unlike the 4xx case
    finally:
        sick.close()


def test_verifier_unavailable_feeds_quarantine_no_zero_rewards():
    """Acceptance: whole pool down + local_fallback=False surfaces
    VerifierUnavailableError into episode retry/quarantine — the output
    queue never sees a fabricated 0.0-reward row."""
    from areal_tpu.env.math_code_env import MathCodeSingleStepEnv

    class WF(RolloutWorkflow):
        async def arun_episode(self, engine, data):
            env = MathCodeSingleStepEnv(verifier_addrs=["127.0.0.1:1"])
            env._remote.timeout = 0.5
            env._remote.retries = 1
            await env.areset(task="math", answer="8", prompt="q")
            _, reward, _, _ = await env.astep("\\boxed{8}")
            return {"rewards": np.asarray([[reward]], np.float32)}

    class Eng:
        def get_version(self):
            return 0

    cfg = InferenceEngineConfig(
        consumer_batch_size=1,
        durability=DurabilityConfig(
            max_episode_retries=1, retry_delay=0.01, max_retry_delay=0.02,
            retry_jitter=0.0,
        ),
    )
    ex = WorkflowExecutor(cfg, Eng()).initialize()
    try:
        assert ex.submit({"uid": "poisoned"}, WF())
        with pytest.raises(EpisodeQuarantinedError):
            ex.wait(count=1, timeout=30)
        assert ex.rollout_stat.quarantined == 1
        assert ex.quarantine_snapshot() == ["uid:poisoned"]
        assert ex.output_queue.qsize() == 0  # no 0.0-reward rows, ever
    finally:
        ex.destroy()


# -------------------------------------------------------- trace plumbing
def test_trace_headers_bind_env_and_verifier_calls():
    ep = telemetry.EpisodeLineage(uid="s0")
    token = telemetry.set_episode(ep)
    try:
        # env worker: incoming X-Areal-Trace binds onto its spans
        httpd = ES.serve_env(ES.countdown_env, background=True)
        addr = f"127.0.0.1:{httpd.server_address[1]}"
        try:
            async def run():
                env = ES.RemoteEnv(addrs=[addr], config=CFG)
                await env.areset(numbers=[1, 2], target=3)
                await env.astep({
                    "name": "eval_expression",
                    "arguments": json.dumps({"expression": "1+2"}),
                })
                await env.aclose()

            asyncio.run(run())
            spans = httpd.env_state.tracer.drain()
            steps = [s for s in spans if s.name == "env_step"]
            assert steps and all(
                s.attrs.get("trace") == ep.trace_id for s in steps
            )
        finally:
            httpd.shutdown()

        # verifier client: forwards the same headers
        stub = _CountingStub(status=200, body={"reward": 1.0})
        try:
            VS.RemoteVerifier([stub.addr], retries=1).verify(
                {"kind": "math", "completion": "x", "answer": "1"}
            )
            assert stub.headers[0].get("X-Areal-Trace") == ep.trace_id
            assert stub.headers[0].get("X-Areal-Rid") == "s0"
        finally:
            stub.close()
    finally:
        telemetry.reset_episode(token)


def test_client_side_env_spans_and_trace_report(tmp_path):
    """RemoteEnv records env_reset/env_step spans + env_replay instants
    a tracer owns; tools/trace_report.py --env summarizes them."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import trace_report

    tracer = SpanTracer(TracingConfig(enabled=True), service="client")
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def run():
            env = ES.RemoteEnv(addrs=[addr], config=CFG, tracer=tracer)
            await env.areset(numbers=[3, 5, 2], target=21)
            for expr in ("3*7", "5+2"):
                await env.astep({
                    "name": "eval_expression",
                    "arguments": json.dumps({"expression": expr}),
                })
            await env.aclose()

        asyncio.run(run())
    finally:
        httpd.shutdown()
    tracer.instant("env_replay", "sX", addr="w2", steps=2)  # synth event
    path = tmp_path / "env_spans.jsonl"
    with open(path, "w") as f:
        for s in tracer.drain():
            f.write(json.dumps(s.to_dict()) + "\n")
    ev = trace_report.env_summary(trace_report.load_spans(str(path)))
    assert ev["steps"] == 2 and ev["sessions"] == 1
    assert ev["replays"] == 1 and ev["replayed_steps"] == 2
    assert ev["ops"]["env_step"]["count"] == 2
    assert addr in ev["step_by_worker"]
    assert trace_report.main([str(path), "--env"]) == 0
    assert trace_report.main([str(path), "--env", "--json"]) == 0
    # an env-less trace exits 1 (CI smoke contract)
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert trace_report.main([str(empty), "--env"]) == 1


def test_env_failovers_land_in_lineage_records():
    """RemoteEnv feeds worker hops/replays into the episode-lineage
    contextvar, so the ledger shows which samples rode out env-worker
    deaths (trace_report --lineage renders the rollup)."""
    ep = telemetry.EpisodeLineage(uid="uid:x")
    token = telemetry.set_episode(ep)
    try:
        async def run():
            env = ES.RemoteEnv(
                addrs=["127.0.0.1:1"],
                config=EnvServiceConfig(
                    call_retries=1, reset_timeout_s=0.5,
                    retry_delay_s=0.02,
                ),
            )
            with pytest.raises(ES.EnvWorkerUnavailableError):
                await env.areset()
            await env.aclose()

        asyncio.run(run())
        assert ep.env_failovers >= 1
        rec = telemetry.LineageLedger().record_episode(
            ep, status="quarantined"
        )
        assert rec["env_failovers"] >= 1 and rec["env_replays"] == 0
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import trace_report

        ln = trace_report.lineage_summary([rec])
        assert ln["env_failovers"] >= 1 and ln["env_replayed"] == 0
        assert "env-worker failovers" in trace_report.format_lineage(ln)
    finally:
        telemetry.reset_episode(token)


# ----------------------------------------------------------- registration
def test_worker_registration_and_discovery(memory_name_resolve):
    httpd = ES.serve_env(
        ES.countdown_env, background=True,
        experiment_name="e1", trial_name="t1",
    )
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        assert ES.discover_env_workers("e1", "t1") == [addr]
        mon = ES.env_fleet_monitor(
            EnvServiceConfig(), experiment_name="e1", trial_name="t1"
        )
        assert mon.addresses() == [addr]
        assert mon.service == "env"
        # a drain deregisters once the (zero) sessions finish
        req = urllib.request.Request(
            f"http://{addr}/drain", data=b"{}",
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req, timeout=5).read()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if not ES.discover_env_workers("e1", "t1"):
                break
            time.sleep(0.05)
        assert ES.discover_env_workers("e1", "t1") == []
    finally:
        httpd.shutdown()


def test_resolve_env_factory_and_replay_safety_declarations():
    factory = ES.resolve_env_factory("areal_tpu.env.service:countdown_env")
    env = factory()
    assert isinstance(env, ES.ToolEnvAdapter) and env.replay_safe
    from areal_tpu.env.math_code_env import MathCodeSingleStepEnv

    assert MathCodeSingleStepEnv.replay_safe
    assert Env.replay_safe is False  # conservative default
    with pytest.raises(ValueError):
        ES.resolve_env_factory("no-colon")
