"""Round-3 breadth: env impl, KV rendezvous backend, Slurm launcher,
gated stats sinks (reference parity: math_code_single_step_env, etcd3
name_resolve backend, SlurmLauncher, wandb/swanlab sinks)."""

import asyncio
import os

import pytest

from areal_tpu.env import MathCodeSingleStepEnv
from areal_tpu.launcher.slurm import SlurmLauncher
from areal_tpu.utils import name_resolve
from areal_tpu.utils.kv_server import serve_kv


def test_math_code_env_single_step():
    async def run():
        env = MathCodeSingleStepEnv()
        obs = await env.areset(
            task="math", prompt="what is 2+2?", answer="4"
        )
        assert obs == "what is 2+2?"
        _, r, done, info = await env.astep("the answer is 4")
        assert r == 1.0 and done and info["task"] == "math"
        await env.areset(task="math", answer="4")
        _, r, done, _ = await env.astep("it is 5")
        assert r == 0.0 and done
        # code task
        await env.areset(
            task="code",
            test_code="assert solve(2) == 4",
        )
        _, r, done, _ = await env.astep(
            "```python\ndef solve(x):\n    return x * 2\n```"
        )
        assert r == 1.0 and done
        await env.aclose()

    asyncio.run(run())


def test_kv_rendezvous_backend():
    httpd = serve_kv(host="127.0.0.1", port=0)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    try:
        repo = name_resolve.reconfigure("kv", address=addr)
        repo.add("exp/trial/servers/a", "h1:1", replace=False)
        repo.add("exp/trial/servers/b", "h2:2", replace=False)
        assert repo.get("exp/trial/servers/a") == "h1:1"
        assert sorted(repo.get_subtree("exp/trial/servers")) == [
            "h1:1", "h2:2"
        ]
        with pytest.raises(name_resolve.NameEntryExistsError):
            repo.add("exp/trial/servers/a", "zzz", replace=False)
        repo.add("exp/trial/servers/a", "h9:9", replace=True)
        assert repo.get("exp/trial/servers/a") == "h9:9"
        repo.delete("exp/trial/servers/a")
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            repo.get("exp/trial/servers/a")
        # TTL expiry (server-side)
        repo.add("exp/ttl", "v", keepalive_ttl=0.2)
        repo._keepalive.clear()  # stop the client refresh
        import time

        time.sleep(0.5)
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            repo.get("exp/ttl")
        repo.reset()
    finally:
        httpd.shutdown()
        name_resolve.reconfigure("memory")


def test_slurm_launcher_scripts(tmp_path):
    submitted = []

    def fake_submit(path):
        submitted.append(path)
        return str(1000 + len(submitted))

    sl = SlurmLauncher(
        "exp", "t0", fileroot=str(tmp_path), partition="tpu",
        trainer_nodes=4, server_count=2, container_env={"FOO": "bar"},
        submit=fake_submit,
    )
    sids = sl.launch_servers(
        ["python", "-m", "areal_tpu.inference.server", "--port", "0"]
    )
    tid = sl.launch_trainer(["python", "train.py", "--config", "c.yaml"])
    assert len(sids) == 2 and tid == "1003"
    trainer_script = open(submitted[-1]).read()
    assert "#SBATCH --nodes=4" in trainer_script
    assert "#SBATCH --partition=tpu" in trainer_script
    assert "export AREAL_NUM_PROCESSES=4" in trainer_script
    # rank must be evaluated PER TASK inside srun (the batch body runs
    # once on the head node), and the coordinator port per job on the
    # compute nodes, not probed on the submit host
    assert "AREAL_PROCESS_ID=$SLURM_PROCID" in trainer_script
    assert "port=$((20000 + SLURM_JOB_ID % 20000))" in trainer_script
    assert "export AREAL_COORDINATOR=$head:$port" in trainer_script
    assert "export FOO=bar" in trainer_script
    assert "srun bash -c" in trainer_script
    assert "python train.py --config c.yaml" in trainer_script
    server_script = open(submitted[0]).read()
    assert "areal_tpu.inference.server" in server_script


def test_stats_logger_sinks_gated(tmp_path, monkeypatch, no_tensorboard):
    """Without the opt-in env vars (and without the packages) the wandb /
    swanlab sinks stay dormant and commits still work."""
    monkeypatch.delenv("AREAL_TPU_WANDB", raising=False)
    monkeypatch.delenv("AREAL_TPU_SWANLAB", raising=False)
    from areal_tpu.utils.stats_logger import StatsLogger

    sl = StatsLogger("exp", "t0", str(tmp_path))
    assert sl._wandb is None and sl._swanlab is None
    sl.commit(0, 0, 0, {"a": 1.0})
    sl.close()
    # opting in without the package installed degrades gracefully
    monkeypatch.setenv("AREAL_TPU_WANDB", "1")
    sl2 = StatsLogger("exp", "t1", str(tmp_path))
    assert sl2._wandb is None  # wandb not installed in this image
    sl2.commit(0, 0, 0, {"a": 2.0})
    sl2.close()
