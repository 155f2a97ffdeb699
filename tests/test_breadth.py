"""Breadth components: platforms, pod launcher, OpenAI-compatible client,
vision workflow, offline eval harness, dataset processors.
"""

import asyncio
import json
import os

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# Platforms
# ---------------------------------------------------------------------------
class TestPlatforms:
    def test_current_platform_detects(self):
        from areal_tpu.platforms import CpuPlatform, current_platform

        p = current_platform()
        # tests run on the forced-CPU backend
        assert isinstance(p, CpuPlatform)
        assert p.communication_backend == "gloo"
        assert p.local_device_count() >= 1

    def test_tpu_pod_discovery_env(self, monkeypatch):
        from areal_tpu.platforms import TpuPlatform

        monkeypatch.setenv("TPU_WORKER_ID", "2")
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1,h2,h3")
        monkeypatch.setenv("TPU_CHIPS_PER_HOST", "4")
        p = TpuPlatform()
        assert p.pod_worker_id() == 2
        assert p.pod_worker_hosts() == ["h0", "h1", "h2", "h3"]
        assert p.chips_per_host() == 4
        assert p.visible_devices_envvars([0, 1]) == {
            "TPU_VISIBLE_CHIPS": "0,1"
        }


# ---------------------------------------------------------------------------
# Pod launcher
# ---------------------------------------------------------------------------
def test_pod_launcher_command_construction(tmp_path, monkeypatch):
    from areal_tpu.launcher.pod import PodLauncher
    from areal_tpu.parallel.distributed import (
        COORDINATOR_ENV,
        NUM_PROCESSES_ENV,
        PROCESS_ID_ENV,
    )

    launched = []

    class FakeProc:
        def poll(self):
            return 0

    def fake_runner(host, cmd, env, log_path):
        launched.append((host, cmd, env))
        return FakeProc()

    monkeypatch.setenv("AREAL_POD_HOSTS", "tpu-w0,tpu-w1,tpu-w2")
    pl = PodLauncher("exp", "t0", str(tmp_path), runner=fake_runner)
    names = pl.launch_trainers(
        "train.py", ["--config", "c.yaml"], coordinator_port=9999
    )
    assert names == ["trainer", "trainer_1", "trainer_2"]
    assert len(launched) == 3
    for rank, (host, cmd, env) in enumerate(launched):
        assert host == f"tpu-w{rank}"
        assert cmd[-3:] == ["train.py", "--config", "c.yaml"]
        assert env[COORDINATOR_ENV] == "tpu-w0:9999"
        assert env[NUM_PROCESSES_ENV] == "3"
        assert env[PROCESS_ID_ENV] == str(rank)
    pl.wait(timeout=5)  # all FakeProcs report success


# ---------------------------------------------------------------------------
# OpenAI-compatible client
# ---------------------------------------------------------------------------
class _FakeTokenizer:
    def apply_chat_template(self, messages, tokenize=True, **kw):
        text = " ".join(m["content"] for m in messages)
        return [ord(c) % 120 + 1 for c in text][:32]

    def encode(self, s, add_special_tokens=False):
        return [ord(s[-1]) % 120 + 1]

    def decode(self, ids):
        return "answer-" + "".join(chr(96 + (i % 26) + 1) for i in ids)


class _FakeEngine:
    async def agenerate(self, req):
        from areal_tpu.api.io_struct import ModelResponse

        n = min(4, req.gconfig.max_new_tokens)
        return ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=[7, 8, 9, 10][:n],
            output_logprobs=[-0.5] * n,
            output_versions=[3] * n,
            stop_reason="stop",
        )


def test_openai_client_chat_and_export():
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.api.openai_client import ArealOpenAI

    client = ArealOpenAI(
        _FakeEngine(), _FakeTokenizer(),
        GenerationHyperparameters(max_new_tokens=16, temperature=0.7),
    )

    async def agent():
        r1 = await client.chat.completions.create(
            messages=[{"role": "user", "content": "What is 2+2?"}],
            max_tokens=4,
        )
        r2 = await client.chat.completions.create(
            messages=[
                {"role": "user", "content": "What is 2+2?"},
                {"role": "assistant", "content": r1.choices[0].message.content},
                {"role": "user", "content": "Double it."},
            ],
        )
        return r1, r2

    r1, r2 = asyncio.run(agent())
    assert r1.choices[0].message.content.startswith("answer-")
    assert r1.usage.completion_tokens == 4
    assert r1.choices[0].finish_reason == "stop"
    # RL cache: token ids/logprobs/versions captured
    c1 = client.get_completions(r1.id)
    assert c1.output_tokens == [7, 8, 9, 10]
    assert c1.output_versions == [3, 3, 3, 3]
    # reward on the final turn discounts back through the conversation
    client.set_reward(r2.id, 1.0)
    exported = client.export_completions(turn_discount=0.5)
    assert exported[r2.id].reward == 1.0
    assert exported[r1.id].reward == 0.5
    row = exported[r1.id].to_training_row()
    assert row["input_ids"].shape[1] == len(c1.input_tokens) + 4
    assert float(row["rewards"][0]) == 0.5


# ---------------------------------------------------------------------------
# Vision workflow
# ---------------------------------------------------------------------------
def test_vision_workflow_ships_images_and_pixel_rows():
    from PIL import Image

    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.api.io_struct import ModelResponse
    from areal_tpu.workflow.vision_rlvr import VisionRLVRWorkflow

    seen = {}

    class Eng:
        async def agenerate(self, req):
            seen["image_data"] = req.image_data
            return ModelResponse(
                input_tokens=list(req.input_ids),
                output_tokens=[5, 6],
                output_logprobs=[-0.1, -0.2],
                output_versions=[0, 0],
                stop_reason="stop",
            )

    def reward(prompt, completion, pids, cids, answer="", **kw):
        return 1.0 if answer == "3" else 0.0

    wf = VisionRLVRWorkflow(
        reward, GenerationHyperparameters(n_samples=2, max_new_tokens=4)
    )
    img = Image.new("RGB", (8, 8), color=(255, 0, 0))
    data = {
        "input_ids": [1, 2, 3],
        "images": [img],
        "pixel_values": np.zeros((4, 6), np.float32),
        "answer": "3",
    }
    out = asyncio.run(wf.arun_episode(Eng(), data))
    assert len(seen["image_data"]) == 1 and isinstance(seen["image_data"][0], str)
    assert np.asarray(out["rewards"]).reshape(-1).tolist() == [1.0, 1.0]
    assert out["pixel_values"].shape == (2, 4, 6)


def test_vision_dataset_processor(tmp_path):
    from PIL import Image

    from areal_tpu.api.cli_args import DatasetConfig
    from areal_tpu.dataset import get_custom_dataset

    img_path = str(tmp_path / "img.png")
    Image.new("RGB", (4, 4)).save(img_path)
    p = tmp_path / "train.jsonl"
    with open(p, "w") as f:
        f.write(
            json.dumps(
                {"images": [img_path], "question": "How many?", "answer": "3"}
            )
            + "\n"
        )
    ds = get_custom_dataset(DatasetConfig(path=str(p), type="clevr_count"))
    assert len(ds) == 1
    assert ds[0]["answer"] == "3"
    # lazy: paths, not decoded images (the workflow opens them per episode)
    assert ds[0]["images"] == [img_path]
    assert ds[0]["messages"][0]["content"] == "How many?"


def test_vision_rewards():
    from areal_tpu.reward.vision import (
        clevr_count_reward_fn,
        extract_final_answer,
    )

    assert extract_final_answer("I count <answer>7</answer>") == "7"
    assert extract_final_answer("thus \\boxed{12} objects") == "12"
    # nested braces must not fall through to the trailing-number heuristic
    assert extract_final_answer("so \\boxed{\\frac{1}{2}}") == "\\frac{1}{2}"
    assert extract_final_answer("there are 3 spheres") == "3"
    assert extract_final_answer("no clue") is None
    assert clevr_count_reward_fn("p", "<answer>4</answer>", answer="4") == 1.0
    assert clevr_count_reward_fn("p", "I see 4.0 cubes", answer="4") == 1.0
    assert clevr_count_reward_fn("p", "<answer>5</answer>", answer="4") == 0.0


def test_phase_profiler(tmp_path):
    """Selected steps run under jax.profiler.trace and produce a trace
    directory; unselected steps are no-ops."""
    from areal_tpu.api.cli_args import ProfilingConfig
    from areal_tpu.utils import tracing
    from areal_tpu.utils.profiling import PhaseProfiler

    import jax.numpy as jnp

    prof = PhaseProfiler(
        ProfilingConfig(enabled=True, steps=[2]), str(tmp_path), "exp", "t0"
    )
    assert not prof.should_trace(1) and prof.should_trace(2)
    with prof.step(1):
        pass  # no-op
    assert not os.path.exists(os.path.join(prof.trace_root, "step1"))
    with prof.step(2):
        with tracing.span("tiny"):
            (jnp.ones(8) * 2).sum().block_until_ready()
    d = os.path.join(prof.trace_root, "step2")
    assert os.path.isdir(d)
    # something was written (xplane pb under plugins/profile/...)
    found = [
        os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
    ]
    assert found, "no trace artifacts written"
    # exceptions inside the profiled body propagate with their own type
    # (the profiler guard must not swallow them)
    with pytest.raises(ValueError, match="boom"):
        with prof.step(2):
            raise ValueError("boom")


# ---------------------------------------------------------------------------
# Offline eval harness
# ---------------------------------------------------------------------------
def test_eval_runner_pass_at_k_math():
    from areal_tpu.evaluation import evaluate_dataset
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.api.io_struct import ModelResponse

    class Eng:
        """Succeeds only on even prompts (success encoded in token count,
        so concurrent episodes can't race)."""

        async def agenerate(self, req):
            ok = req.input_ids[0] % 2 == 0
            toks = [1] * (8 if ok else 3)
            return ModelResponse(
                input_tokens=list(req.input_ids),
                output_tokens=toks,
                output_logprobs=[-0.1] * len(toks),
                output_versions=[0] * len(toks),
                stop_reason="stop",
            )

    eng = Eng()

    class Tok:
        def decode(self, ids):
            return "The answer is \\boxed{42}" if len(ids) == 8 else "nope"

    def reward(prompt, completion, pids, cids, answer="", **kw):
        return 1.0 if "42" in completion else 0.0

    items = [
        {"input_ids": [i, 2, 3], "answer": "42"} for i in range(4)
    ]
    from areal_tpu.workflow import rlvr

    report = evaluate_dataset(
        eng,
        items,
        reward,
        GenerationHyperparameters(n_samples=2, max_new_tokens=8),
        tokenizer=Tok(),
    )
    assert report.n_prompts == 4 and report.n_samples == 2
    assert 0.0 < report.accuracy < 1.0
    assert set(report.pass_at_k) == {1, 2}
    assert report.pass_at_k[2] >= report.pass_at_k[1]
    assert len(report.rows) == 4
