"""Self-play episode plane (workflow/selfplay.py + env/selfplay.py):
grader-family validation of proposed instances, the proposer tool env,
two-sided scripted episodes with per-agent credit assignment and
metadata stamping, per-agent lineage reporting, the strict-no-op
contract, replay-safe multi-session episodes through the env service
(chaos kill mid-episode → bit-identical), and e2e against the real
generation engine on the shared race geometry.
"""

import asyncio
import json
import os
import sys
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tests import fixtures  # noqa: E402

from areal_tpu.api.cli_args import (
    EnvServiceConfig,
    GenerationHyperparameters,
    SelfPlayConfig,
)
from areal_tpu.api.io_struct import ModelResponse
from areal_tpu.env import selfplay as SP
from areal_tpu.env import service as ES
from areal_tpu.env.countdown import sample_instance
from areal_tpu.utils.telemetry import RequestLineage
from areal_tpu.workflow.selfplay import (
    AgentSpec,
    CountdownSelfPlayWorkflow,
    SelfPlayWorkflow,
    make_countdown_selfplay_workflow,
)
from examples.countdown_agent import ToyToolTokenizer, toy_tool_parser
from examples.countdown_selfplay import toy_proposer_parser
from tools.trace_report import format_lineage, lineage_summary

CFG = EnvServiceConfig(
    call_retries=2, call_timeout_s=10.0, reset_timeout_s=10.0,
    retry_delay_s=0.05,
)


# --------------------------------------------------- unit: instance grammar
@pytest.mark.parametrize(
    "text,numbers,target",
    [
        ("3 5 2 = 21", [3, 5, 2], 21),
        ('{"numbers": [3, 5, 2], "target": 21}', [3, 5, 2], 21),
        ("  10 9 1 =  -5 ", [10, 9, 1], -5),
        # integral floats pass (the countdown pool is integer by value)
        ('{"numbers": [4.0, 2, 8], "target": 8}', [4, 2, 8], 8),
    ],
)
def test_parse_instance_accepts(text, numbers, target):
    assert SP.parse_instance(text) == (numbers, target)


@pytest.mark.parametrize(
    "text",
    [
        "",
        "3 5 2",  # no '='
        "= 21",
        "3 5 2 =",
        "3 x 2 = 21",
        "3 5 2 = 2.5",  # fractional target
        '{"numbers": "3 5 2", "target": 21}',
        '{"target": 21}',
        '{"numbers": [3, 5, 2], "target": true}',  # bool is not an int
        '{"numbers": [3.5, 5, 2], "target": 21}',
        "{not json",
        "[1, 2, 3]",  # JSON but not an object... parsed as compact, fails
    ],
)
def test_parse_instance_rejects(text):
    with pytest.raises(ValueError):
        SP.parse_instance(text)


# ------------------------------------------------ unit: grader families
@pytest.mark.parametrize(
    "numbers,target,family",
    [
        ([3, 5], 8, "count"),
        ([3, 5, 2, 4, 6], 20, "count"),
        ([3, 0, 2], 5, "range"),
        ([3, 25, 2], 30, "range"),
        ([3, 5, 2], 5000, "target"),
        ([3, 5, 2], 977, "unsolvable"),
        ([3, 5, 2], 21, "ok"),
        ([3, 5, 2], -2, "ok"),  # 3 - 5 (subsets allowed)
    ],
)
def test_validate_instance_families(numbers, target, family):
    ok, fam, detail = SP.validate_instance(numbers, target)
    assert fam == family
    assert ok == (family == "ok")
    assert detail  # every verdict carries a human-readable detail


def test_validate_instance_solvability_gate():
    # the same unsolvable instance passes with the gate off
    ok, fam, _ = SP.validate_instance([3, 5, 2], 977, require_solvable=False)
    assert ok and fam == "ok"


def test_instance_solvable():
    assert SP.instance_solvable([3, 5, 2], 21)  # 3*(5+2)
    assert SP.instance_solvable([3, 5, 2], -2)  # 3-5
    assert SP.instance_solvable([8, 2], 4)  # 8/2
    assert not SP.instance_solvable([3, 5, 2], 977)
    assert not SP.instance_solvable([2, 2], 5)


@pytest.mark.parametrize(
    "numbers,target,band",
    [
        ([3, 5, 2], 21, 0),
        ([3, 5, 2, 7], 21, 1),  # +1 four numbers
        ([3, 5, 2], 60, 1),  # +1 |target| > 50
        ([3, 5, 2], -2, 1),  # +1 negative target
        ([3, 5, 2, 7], 210, 3),  # four numbers + >50 + >200
        ([9, 9, 9, 9], 6561, 3),  # capped at 3
    ],
)
def test_difficulty_band_vectors(numbers, target, band):
    assert SP.difficulty_band(numbers, target) == band


def test_difficulty_band_deterministic_and_order_free():
    """Banding is pure arithmetic of the instance: repeated calls and
    number-order permutations agree (bit-stable under journal replay)."""
    cases = [([3, 5, 2], 21), ([7, 2, 5, 3], 210), ([10, 9, 1], -5)]
    for numbers, target in cases:
        b = SP.difficulty_band(numbers, target)
        assert SP.difficulty_band(numbers, target) == b
        assert SP.difficulty_band(list(reversed(numbers)), target) == b


def test_proposer_reward_mapping():
    # invalid proposals earn nothing in either mode
    assert SP.proposer_reward(False, 3, 1.0, "banded") == 0.0
    assert SP.proposer_reward(False, -1, 0.0, "zero_sum") == 0.0
    # banded: (1 + band) / 4, clamped to the 0..3 band range
    assert SP.proposer_reward(True, 0, 0.0, "banded") == pytest.approx(0.25)
    assert SP.proposer_reward(True, 1, 0.0, "banded") == pytest.approx(0.50)
    assert SP.proposer_reward(True, 3, 0.0, "banded") == pytest.approx(1.0)
    assert SP.proposer_reward(True, 7, 0.0, "banded") == pytest.approx(1.0)
    assert SP.proposer_reward(True, -1, 0.0, "banded") == pytest.approx(0.25)
    # zero-sum: the proposer wins what the solver loses
    assert SP.proposer_reward(True, 2, 1.0, "zero_sum") == pytest.approx(0.0)
    assert SP.proposer_reward(True, 2, 0.1, "zero_sum") == pytest.approx(0.9)
    with pytest.raises(ValueError):
        SP.proposer_reward(True, 1, 0.0, "tournament")


# ------------------------------------------------ unit: proposer tool env
def test_check_instance_is_diagnostic_not_commit():
    env = SP.ProposerEnv()
    out = env.call("check_instance", json.dumps({"instance": "3 5 2 = 21"}))
    assert out == "valid (band 0)"
    assert not env.done and env.attempts == 0
    out = env.call("check_instance", json.dumps({"instance": "1 1 = 50"}))
    assert out.startswith("invalid [count]")
    assert not env.done and env.attempts == 0  # checks never burn attempts


def test_propose_valid_commits_through_observation():
    env = SP.ProposerEnv()
    out = env.call(
        "propose_instance", json.dumps({"instance": "3 5 2 7 = 105"})
    )
    # 3*5*7 reaches 105; band 2 (four numbers, |target| > 50)
    assert env.done and env.reward == 1.0 and env.band == 2
    assert out.startswith("accepted ")
    assert env.info == {"selfplay": {"valid": True, "band": 2}}
    # the workflow reads the instance ONLY from the observation (possibly
    # wrapped with the tool name) — the replay-bit-reproduced channel
    assert SP.parse_accepted_observation("propose_instance -> " + out) == (
        [3, 5, 2, 7], 105, 2,
    )


def test_propose_invalid_exhausts_attempt_budget():
    env = SP.ProposerEnv(max_attempts=2)
    r1 = env.call("propose_instance", json.dumps({"instance": "1 1 = 99"}))
    assert r1.startswith("rejected [count]") and not env.done
    r2 = env.call("propose_instance", json.dumps({"instance": "nope"}))
    assert r2.startswith("rejected [parse]")
    assert env.done and env.reward == 0.0
    assert env.info == {"selfplay": {"valid": False, "band": -1}}


def test_proposer_env_bad_tool_and_bad_args():
    env = SP.ProposerEnv()
    assert env.call("launch_missiles", "{}").startswith("error: unknown")
    assert env.call("propose_instance", "{bad").startswith("error:")
    assert not env.done and env.attempts == 0


@pytest.mark.parametrize(
    "text",
    [
        "",
        "rejected [count]: need 3-4 numbers, got 2",
        "check_instance -> valid (band 0)",
        "accepted notjson",
        'accepted {"numbers": [3, 5, 2]}',  # missing target
    ],
)
def test_parse_accepted_observation_rejects(text):
    assert SP.parse_accepted_observation(text) is None


def test_build_side_env_dispatch():
    penv = SP.build_side_env(
        {"side": "proposer", "min_numbers": 3, "max_numbers": 3,
         "max_target": 64, "numbers": [1, 1], "target": 9}  # extras ignored
    )
    assert isinstance(penv, SP.ProposerEnv)
    assert (penv.min_numbers, penv.max_numbers, penv.max_target) == (3, 3, 64)
    senv = SP.build_side_env(
        {"side": "solver", "numbers": [3, 5, 2], "target": 21}
    )
    assert senv.numbers == [3, 5, 2] and senv.target == 21
    with pytest.raises(ValueError):
        SP.build_side_env({"side": "referee"})


def test_toy_proposer_parser():
    calls = toy_proposer_parser(
        "<call>3 5 2 = 21</call> then <submit>3 5 2 = 21"
    )
    assert [c.function.name for c in calls] == [
        "check_instance",
        "propose_instance",
    ]
    assert json.loads(calls[0].function.arguments)["instance"] == "3 5 2 = 21"


# -------------------------------------------- scripted two-sided episodes
class _ScriptedEngine:
    """Deterministic engine (test_agentic_countdown idiom) that also
    records each request's metadata — the self-play stamping surface."""

    def __init__(self, tok, outputs):
        self.tok = tok
        self.outputs = list(outputs)
        self.calls = []
        self.metas = []

    def get_version(self):
        return 0

    async def agenerate(self, req):
        self.calls.append(list(req.input_ids))
        self.metas.append(req.metadata)
        out = self.tok.encode(self.outputs.pop(0))
        return ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=out,
            output_logprobs=[-0.3] * len(out),
            output_versions=[0] * len(out),
            stop_reason="stop",
        )


# proposer checks then commits "3 5 2 = 21" (band 0); the solver cracks it
EPISODE_SCRIPT = [
    "<call>3 5 2 = 21</call>",
    "<submit>3 5 2 = 21</submit>",
    "<call>3*7</call>",
    "<submit>3*(5+2)</submit>",
]


def _wf(**kw):
    tok = ToyToolTokenizer()
    defaults = dict(
        env_factory=SP.build_side_env,
        gconfig=GenerationHyperparameters(n_samples=1, max_new_tokens=16),
        tokenizer=tok,
        proposer=AgentSpec(
            name="proposer", role="proposer", max_rounds=3,
            tool_parser=toy_proposer_parser,
        ),
        solver=AgentSpec(
            name="solver", role="solver", max_rounds=4,
            tool_parser=toy_tool_parser,
        ),
        turn_discount=0.5,
    )
    defaults.update(kw)
    return tok, CountdownSelfPlayWorkflow(**defaults)


def test_scripted_selfplay_episode_banded():
    """Both sides play over ONE transcript; each side's rows carry its
    own reward: solver 1.0 (binary countdown), proposer 0.25 (band 0),
    each discounted back through that side's earlier turns."""
    tok, wf = _wf()
    eng = _ScriptedEngine(tok, EPISODE_SCRIPT)
    # the dataset fallback is deliberately UNSOLVABLE by the solver's
    # submission — reward 1.0 proves the PROPOSED instance was played
    batch = asyncio.run(
        wf.arun_episode(eng, {"numbers": [1, 1, 1], "target": 9})
    )
    assert batch["input_ids"].shape[0] == 4
    assert batch["agent_idx"].tolist() == [0, 0, 1, 1]
    assert batch["tool_calls"].tolist() == [1, 1, 1, 1]
    rewards = [float(r) for r in batch["rewards"]]
    assert rewards == [
        pytest.approx(0.125),  # proposer turn 1 (0.5 * 0.25)
        pytest.approx(0.25),   # proposer commit: banded, band 0
        pytest.approx(0.5),    # solver turn 1 (0.5 * 1.0)
        pytest.approx(1.0),    # solver solved the proposed instance
    ]
    # shared transcript: the solver's first request sees the proposer's
    # committed instance in its context
    ctx_solver = tok.decode(eng.calls[2])
    assert "3 5 2 = 21" in ctx_solver
    # only each agent's own tokens are trained on
    lm, am = batch["loss_mask"], batch["attention_mask"]
    assert (lm.sum(1) > 0).all() and (lm <= am).all()


def test_scripted_selfplay_episode_zero_sum():
    tok, wf = _wf(reward_mode="zero_sum")
    eng = _ScriptedEngine(tok, EPISODE_SCRIPT)
    batch = asyncio.run(
        wf.arun_episode(eng, {"numbers": [1, 1, 1], "target": 9})
    )
    rewards = [float(r) for r in batch["rewards"]]
    # solver won (1.0), so the proposer gets 1.0 - 1.0 = 0.0
    assert rewards[:2] == [pytest.approx(0.0), pytest.approx(0.0)]
    assert rewards[2:] == [pytest.approx(0.5), pytest.approx(1.0)]


def test_proposer_failure_falls_back_to_dataset_instance():
    """No valid proposal → the solver plays the dataset's own instance
    (the episode still trains the solver) and the proposer earns 0."""
    tok, wf = _wf()
    eng = _ScriptedEngine(tok, ["?", "<submit>3*(5+2)</submit>"])
    batch = asyncio.run(
        wf.arun_episode(eng, {"numbers": [3, 5, 2], "target": 21})
    )
    assert batch["input_ids"].shape[0] == 2
    assert batch["agent_idx"].tolist() == [0, 1]
    rewards = [float(r) for r in batch["rewards"]]
    assert rewards == [pytest.approx(0.0), pytest.approx(1.0)]


def test_proposer_failure_without_fallback_drops_episode():
    tok, wf = _wf()
    eng = _ScriptedEngine(tok, ["?"])
    assert asyncio.run(wf.arun_episode(eng, {})) is None


def test_frozen_opponent_exports_solver_rows_only():
    """An untrained proposer contributes only loss-masked context: zero
    proposer rows, and its turns ride the interactive class."""
    tok, wf = _wf(
        proposer=AgentSpec(
            name="proposer", role="proposer", trained=False,
            priority="interactive", max_rounds=3,
            tool_parser=toy_proposer_parser,
        )
    )
    eng = _ScriptedEngine(tok, EPISODE_SCRIPT)
    batch = asyncio.run(
        wf.arun_episode(eng, {"numbers": [1, 1, 1], "target": 9})
    )
    assert batch["agent_idx"].tolist() == [1, 1]
    assert eng.metas[0]["priority"] == "interactive"  # opponent turns
    assert eng.metas[2]["priority"] == "bulk"  # trained side stays bulk


def test_episode_metadata_stamping():
    """Every request carries the episode session id plus its agent's
    stamps, through ONE metadata dict per client — the r19 contract that
    lets the router's canary resolution stick for later turns."""
    tok, wf = _wf(
        proposer=AgentSpec(
            name="proposer", role="proposer", policy="proposer@stable",
            max_rounds=3, tool_parser=toy_proposer_parser,
        ),
        solver=AgentSpec(
            name="solver", role="solver", policy="solver@canary",
            tool_parser=toy_tool_parser,
        ),
    )
    eng = _ScriptedEngine(tok, EPISODE_SCRIPT)
    asyncio.run(wf.arun_episode(eng, {"numbers": [1, 1, 1], "target": 9}))
    metas = eng.metas
    assert len(metas) == 4
    assert len({m["qid"] for m in metas}) == 1  # one shared-history key
    assert metas[0]["agent"] == "proposer" and metas[0]["role"] == "proposer"
    assert metas[0]["policy"] == "proposer@stable"
    assert metas[2]["agent"] == "solver" and metas[2]["policy"] == "solver@canary"
    # same OBJECT across a side's turns: a router write-back
    # (policy -> "name@vN") is visible to that side's next turn
    assert metas[0] is metas[1]
    assert metas[2] is metas[3]
    assert metas[0] is not metas[2]  # but never shared across sides


def test_workflow_constructor_validation():
    class _Noop(SelfPlayWorkflow):  # SelfPlayWorkflow itself is abstract
        async def arun_episode(self, engine, data):
            return None

    tok = ToyToolTokenizer()
    g1 = GenerationHyperparameters(n_samples=1, max_new_tokens=8)
    with pytest.raises(ValueError):  # group sampling is prompt-level
        _Noop(
            SP.build_side_env,
            GenerationHyperparameters(n_samples=2, max_new_tokens=8),
            tok, agents=[AgentSpec(name="a")],
        )
    with pytest.raises(ValueError):  # duplicate names
        _Noop(
            SP.build_side_env, g1, tok,
            agents=[AgentSpec(name="a"), AgentSpec(name="a")],
        )
    with pytest.raises(ValueError):  # nobody trains -> no rows ever
        _Noop(
            SP.build_side_env, g1, tok,
            agents=[AgentSpec(name="a", trained=False)],
        )
    with pytest.raises(ValueError):  # unknown reward mode
        CountdownSelfPlayWorkflow(
            SP.build_side_env, g1, tok, reward_mode="tournament"
        )


# ------------------------------------------------- config factory contract
def test_make_workflow_disabled_is_none():
    """SelfPlayConfig.enabled=False → None: the caller keeps its
    single-agent workflow and nothing changes (strict no-op)."""
    cfg = SimpleNamespace(selfplay=SelfPlayConfig())
    tok = ToyToolTokenizer()
    g = GenerationHyperparameters(n_samples=1, max_new_tokens=8)
    assert make_countdown_selfplay_workflow(cfg, SP.build_side_env, g, tok) is None


def test_make_workflow_maps_every_config_field():
    sp = SelfPlayConfig(
        enabled=True, proposer_policy="p@stable", solver_policy="s@canary",
        train_proposer=False, train_solver=True,
        opponent_priority="interactive", reward_mode="zero_sum",
        turn_discount=0.7, max_propose_rounds=2, max_solver_rounds=5,
        min_numbers=3, max_numbers=3, max_target=64,
    )
    tok = ToyToolTokenizer()
    g = GenerationHyperparameters(n_samples=1, max_new_tokens=8)
    wf = make_countdown_selfplay_workflow(
        SimpleNamespace(selfplay=sp), SP.build_side_env, g, tok
    )
    assert wf.proposer.policy == "p@stable" and not wf.proposer.trained
    assert wf.proposer.priority == "interactive"  # frozen opponent
    assert wf.solver.policy == "s@canary" and wf.solver.trained
    assert wf.solver.priority == "bulk"  # trained sides stay shed-able
    assert wf.proposer.max_rounds == 2 and wf.solver.max_rounds == 5
    assert wf.reward_mode == "zero_sum"
    assert wf.turn_discount == pytest.approx(0.7)
    assert wf.proposer_env_kwargs == {
        "min_numbers": 3, "max_numbers": 3, "max_target": 64,
    }


# --------------------------------------------------- per-agent lineage
def test_request_lineage_agent_role_round_trip():
    rl = RequestLineage(
        rid="r1", policy="proposer@2", agent="proposer", role="proposer"
    )
    rl.add_segment("s0", 4, [3])
    d = rl.to_dict()
    assert d["agent"] == "proposer" and d["role"] == "proposer"
    # single-agent requests stay byte-identical: no new keys when unset
    bare = RequestLineage(rid="r2")
    bare.add_segment("s0", 1, [0])
    assert "agent" not in bare.to_dict() and "role" not in bare.to_dict()


def _mk_request(rid, agent="", role="", policy="", versions=(0,)):
    rq = {"rid": rid, "weight_versions": list(versions)}
    if agent:
        rq.update(agent=agent, role=role, policy=policy)
    return rq


def test_trace_report_per_agent_rows():
    records = [
        {
            "uid": "ep0", "status": "consumed", "attempts": 1,
            "consumed_step": 0, "rewards": [0.25, 1.0],
            "requests": [
                _mk_request("a", "proposer", "proposer", "prop@2", (2,)),
                _mk_request("b", "proposer", "proposer", "prop@2", (2,)),
                _mk_request("c", "solver", "solver", "solv", (3,)),
            ],
        },
        {
            "uid": "ep1", "status": "consumed", "attempts": 1,
            "consumed_step": 1, "rewards": [1.0],
            "requests": [_mk_request("d", "solver", "solver", "solv", (4,))],
        },
    ]
    ln = lineage_summary(records)
    agents = {a["agent"]: a for a in ln["agents"]}
    assert agents["proposer"]["turns"] == 2
    assert agents["proposer"]["episodes"] == 1  # two turns, ONE episode
    assert agents["proposer"]["policies"] == ["prop@2"]
    assert agents["solver"]["turns"] == 2 and agents["solver"]["episodes"] == 2
    assert agents["solver"]["versions"] == [3, 4]  # per-side versions
    text = format_lineage(ln)
    assert "per-agent" in text and "proposer" in text and "solv" in text


def test_trace_report_no_agents_no_section():
    """Single-agent ledgers render exactly as before — the per-agent
    table appears only when some request carries an agent stamp."""
    ln = lineage_summary(
        [{"uid": "s0", "status": "consumed", "attempts": 1,
          "consumed_step": 0, "requests": [_mk_request("a")]}]
    )
    assert ln["agents"] == []
    assert "per-agent" not in format_lineage(ln)


# ------------------------------------------- env service: metrics contract
def test_selfplay_metrics_strict_noop_for_plain_envs():
    """A countdown-only worker must expose ZERO selfplay_* metric keys —
    the metric families exist only when a self-play env stamps its
    grading summary into step info."""
    httpd = ES.serve_env(ES.countdown_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def run():
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            await env.areset(numbers=[3, 5, 2], target=21)
            o, r, d, _ = await env.astep({
                "name": "submit_expression",
                "arguments": json.dumps({"expression": "3*(5+2)"}),
            })
            assert d and r == 1.0
            await env.aclose()

        asyncio.run(run())
        body = urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=5
        ).read().decode()
        assert "areal_tpu_env_steps_total 1" in body
        assert "selfplay" not in body
    finally:
        httpd.shutdown()


def test_selfplay_env_worker_serves_both_sides_and_counts_proposals():
    """One selfplay_env worker pool serves proposer AND solver sessions
    (keyed by the 'side' reset kwarg — multi-session episodes need one
    address list), and proposal outcomes surface as counters."""
    httpd = ES.serve_env(ES.selfplay_env, background=True)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        async def run():
            # a valid proposal
            env = ES.RemoteEnv(addrs=[addr], config=CFG)
            obs = await env.areset(side="proposer")
            assert env.replay_safe
            assert "propose_instance" in json.dumps(obs["tools"])
            o, r, d, info = await env.astep({
                "name": "propose_instance",
                "arguments": json.dumps({"instance": "3 5 2 = 21"}),
            })
            assert d and r == 1.0
            assert info["selfplay"] == {"valid": True, "band": 0}
            assert str(o).startswith("accepted ")
            # an invalid proposal exhausting a 1-attempt budget
            env2 = ES.RemoteEnv(addrs=[addr], config=CFG)
            await env2.areset(side="proposer", max_attempts=1)
            o, r, d, info = await env2.astep({
                "name": "propose_instance",
                "arguments": json.dumps({"instance": "1 1 = 5"}),
            })
            assert d and r == 0.0
            assert info["selfplay"] == {"valid": False, "band": -1}
            # the same worker hosts the solver side of the episode
            env3 = ES.RemoteEnv(addrs=[addr], config=CFG)
            obs3 = await env3.areset(
                side="solver", numbers=[3, 5, 2], target=21
            )
            assert "21" in obs3["prompt"]
            await env.aclose()
            await env2.aclose()
            await env3.aclose()

        asyncio.run(run())
        body = urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=5
        ).read().decode()
        assert "areal_tpu_env_selfplay_proposals_total 2" in body
        assert "areal_tpu_env_selfplay_valid_proposals_total 1" in body
        assert "areal_tpu_env_selfplay_invalid_proposals_total 1" in body
    finally:
        httpd.shutdown()


# ------------------------------------ chaos: multi-session episode replay
def _selfplay_episode(addrs, capture):
    """One scripted two-sided episode against remote env workers; both
    env sessions (proposer, then solver) ride the same address pool."""
    tok = ToyToolTokenizer()
    eng = _ScriptedEngine(tok, EPISODE_SCRIPT)
    inner = ES.make_remote_tool_env_factory(
        addrs=addrs, config=CFG,
        reset_keys=["side", "numbers", "target", "min_numbers",
                    "max_numbers", "max_target"],
    )

    def factory(data):
        env = inner(data)
        capture.append(env)
        return env

    wf = CountdownSelfPlayWorkflow(
        env_factory=factory,
        gconfig=GenerationHyperparameters(n_samples=1, max_new_tokens=16),
        tokenizer=tok,
        proposer=AgentSpec(
            name="proposer", role="proposer", max_rounds=3,
            tool_parser=toy_proposer_parser,
        ),
        solver=AgentSpec(
            name="solver", role="solver", max_rounds=4,
            tool_parser=toy_tool_parser,
        ),
        turn_discount=0.5,
        tool_timeout_s=15.0,
    )
    return asyncio.run(
        wf.arun_episode(eng, {"numbers": [1, 1, 1], "target": 9})
    )


@pytest.mark.chaos
def test_kill_env_worker_mid_selfplay_episode_bit_identical():
    """THE self-play acceptance chaos test: an episode holds TWO env
    sessions (proposer + solver); the worker serving the proposer session
    hard-kills on its 2nd /step — mid-episode, on the committing
    propose_instance call — and the episode must finish via journal
    replay with trajectory AND both sides' rewards bit-identical to an
    uninterrupted run."""
    with fixtures.children() as procs:
        victim_proc, victim_addr = fixtures.spawn_env_worker(
            "selfplay_env",
            {"AREAL_CHAOS": "kill:side=server,match=/step,start=1"},
        )
        procs.append(victim_proc)
        surv_proc, surv_addr = fixtures.spawn_env_worker("selfplay_env")
        procs.append(surv_proc)
        base_envs = []
        baseline = _selfplay_episode([surv_addr], base_envs)
        assert baseline is not None
        assert all(e.stats["replays"] == 0 for e in base_envs)

        # round-robin striping opens the proposer session on the victim
        # (first address) and the solver session on the survivor
        chaos_envs = []
        batch = _selfplay_episode([victim_addr, surv_addr], chaos_envs)
        assert victim_proc.poll() is not None, "chaos kill never fired"

    # zero lost episodes: exactly one replay, on the proposer session
    assert batch is not None
    assert len(chaos_envs) == 2
    st = chaos_envs[0].stats
    assert st["replays"] == 1 and st["failovers"] >= 1
    assert chaos_envs[1].stats["replays"] == 0
    # bit-identical trajectory + rewards vs the uninterrupted run
    assert set(batch) == set(baseline)
    for key in baseline:
        np.testing.assert_array_equal(
            batch[key], baseline[key], err_msg=f"key {key} diverged"
        )
    rewards = [float(r) for r in batch["rewards"]]
    assert rewards[1] == pytest.approx(0.25)  # proposer: banded, band 0
    assert rewards[3] == pytest.approx(1.0)  # solver cracked the instance
    assert batch["tool_errors"].sum() == 0  # replay, not error-feedback


# ----------------------------------- e2e: real engine, shared race geometry
def _race_common():
    """Byte-identical to test_radix_cache / test_chunked_prefill's race
    geometry: whichever module runs first pays the compile storm, this
    one rides the process jit cache (the tier-1 wall-time guard)."""
    from areal_tpu.api.cli_args import SpecConfig

    return dict(
        page_size=16, max_num_seqs=8, max_model_len=256,
        num_pages=24,
        decode_chunk=4, decode_pipeline=2, decode_compact=True,
        decode_compact_min_rows=2, decode_compact_hysteresis=1,
        admit_wave=4, prefix_reuse_min=4,
        spec=SpecConfig(
            enabled=True, max_draft=3, ngram_min=2, ngram_max=3,
            accept_floor=0.0,
        ),
    )


@pytest.fixture(scope="module")
def race_engine():
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params

    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = GenerationEngine(
        JaxGenConfig(
            dtype="float32", prefill_chunk=16, admit_hold_s=0.0,
            **_race_common(),
        ),
        model_config=cfg,
        params=params,
    ).start()
    yield eng
    eng.stop()


class _RealAdapter:
    """GenerationEngine → the InferenceEngine surface ArealOpenAI speaks,
    forwarding the traffic class the self-play clients stamp."""

    def __init__(self, eng):
        self._eng = eng

    def get_version(self):
        return 0

    async def agenerate(self, req):
        loop = asyncio.get_running_loop()
        fut = self._eng.submit(
            {
                "input_ids": list(req.input_ids),
                "priority": str(req.metadata.get("priority") or "bulk"),
                "sampling_params": {
                    "max_new_tokens": req.gconfig.max_new_tokens,
                    "temperature": 1.0,
                },
            }
        )
        r = await loop.run_in_executor(None, fut.result, 300)
        return ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=r["output_ids"],
            output_logprobs=r["output_logprobs"],
            output_versions=r["output_versions"],
            stop_reason="stop",
        )


def _e2e_workflow(tok, reward_mode="banded"):
    return CountdownSelfPlayWorkflow(
        env_factory=SP.build_side_env,
        gconfig=GenerationHyperparameters(n_samples=1, max_new_tokens=24),
        tokenizer=tok,
        proposer=AgentSpec(
            name="proposer", role="proposer", max_rounds=2,
            tool_parser=toy_proposer_parser,
        ),
        solver=AgentSpec(
            name="solver", role="solver", max_rounds=2,
            tool_parser=toy_tool_parser,
        ),
        reward_mode=reward_mode,
        turn_discount=0.5,
    )


def test_selfplay_e2e_real_engine(race_engine):
    """Two-sided episodes through the REAL generation engine: a random
    toy policy rarely lands a valid proposal, so the dataset fallback
    keeps the solver side training — every episode must export rows."""
    tok = ToyToolTokenizer()
    wf = _e2e_workflow(tok)
    rng = np.random.default_rng(0)
    rows = 0
    for _ in range(3):
        env = sample_instance(rng)
        batch = asyncio.run(
            wf.arun_episode(
                _RealAdapter(race_engine),
                {"numbers": env.numbers, "target": env.target},
            )
        )
        assert batch is not None
        assert set(np.unique(batch["agent_idx"])) <= {0, 1}
        lm, am = batch["loss_mask"], batch["attention_mask"]
        assert (lm.sum(1) > 0).all() and (lm <= am).all()
        rows += batch["input_ids"].shape[0]
    assert rows >= 6  # both sides produce at least one row per episode


@pytest.mark.slow
def test_selfplay_e2e_zero_sum_cohort(race_engine):
    """Heaviest cell (slow-marked per the wall-time guard): a larger
    zero-sum cohort through the real engine; rewards stay in [0, 1] on
    both sides and every episode exports both sides' rows."""
    tok = ToyToolTokenizer()
    wf = _e2e_workflow(tok, reward_mode="zero_sum")
    rng = np.random.default_rng(1)
    for _ in range(6):
        env = sample_instance(rng)
        batch = asyncio.run(
            wf.arun_episode(
                _RealAdapter(race_engine),
                {"numbers": env.numbers, "target": env.target},
            )
        )
        assert batch is not None
        rewards = batch["rewards"].reshape(-1)
        assert ((rewards >= -1e-6) & (rewards <= 1.0 + 1e-6)).all()
        assert {0, 1} == set(np.unique(batch["agent_idx"]))
