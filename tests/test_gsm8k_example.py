"""Golden e2e truth test: the GSM8K GRPO example runs end-to-end with a tiny
tokenizer + tiny model + synthetic data (reference areal/tests/grpo/).
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tests.fixtures import (
    make_gsm8k_jsonl,
    make_tiny_checkpoint,
    make_tiny_tokenizer,
)


@pytest.fixture
def scoped_compile_cache(monkeypatch):
    """The example turns the persistent compile cache on (process-global
    jax config); keep that to this test. The placement rule under test is
    the unset-variable branch: <checkout>/.jax_compile_cache."""
    from areal_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    yield compile_cache
    compile_cache.disable_compilation_cache()


def test_gsm8k_grpo_example_runs(tmp_path, scoped_compile_cache):
    from examples.gsm8k_grpo import main

    model_dir = str(tmp_path / "model")
    tok_dir = str(tmp_path / "tok")
    data_file = str(tmp_path / "data" / "train.jsonl")
    fileroot = str(tmp_path / "out")
    make_tiny_checkpoint(model_dir)
    make_tiny_tokenizer(tok_dir)
    make_gsm8k_jsonl(data_file, n=8)

    argv = [
        "experiment_name=grpo-e2e",
        "trial_name=t0",
        f"cluster.fileroot={fileroot}",
        f"tokenizer_path={tok_dir}",
        f"actor.path={model_dir}",
        f"train_dataset.path={data_file}",
        "train_dataset.batch_size=2",
        "total_train_steps=2",
        "async_training=true",
        "gconfig.n_samples=2",
        "gconfig.max_new_tokens=8",
        "rollout.consumer_batch_size=4",
        "rollout.max_concurrent_rollouts=8",
        "rollout.max_head_offpolicyness=2",
        "server.dtype=float32",
        "server.max_num_seqs=8",
        "server.max_model_len=64",
        "server.prefill_chunk=16",
        "actor.dtype=float32",
        "actor.param_dtype=float32",
        "actor.gradient_checkpointing=false",
        "actor.optimizer.lr=1e-4",
        "actor.group_size=2",
        "actor.ppo_n_minibatches=2",
        "actor.group_reward_norm=true",
        "recover.mode=disabled",
        "saver.freq_steps=null",
    ]
    summary = main(argv)
    assert summary["steps"] == 2 and summary["model_version"] == 2
    # on the CPU "auto" resolves to the jnp gather; the trainer entry
    # enabled the cache at the checkout's fixed directory
    assert summary["server_attn_impl"] == "jnp"
    assert (
        summary["compile_cache_dir"]
        == scoped_compile_cache.default_cache_dir()
    )

    stats_file = os.path.join(fileroot, "grpo-e2e", "t0", "stats.jsonl")
    assert os.path.exists(stats_file)
    lines = [json.loads(l) for l in open(stats_file)]
    assert len(lines) == 2
    for rec in lines:
        assert rec["ppo_actor/update_successful"] == 1.0
        assert "timeperf/e2e" in rec
        assert "reward/mean" in rec
        assert np.isfinite(rec["ppo_actor/grad_norm"])
    # generation dump exists (one file per weight version)
    gen_dir = os.path.join(fileroot, "grpo-e2e", "t0", "generated")
    assert os.path.isdir(gen_dir) and len(os.listdir(gen_dir)) >= 1
