"""PPO actor end-to-end on the tiny model: advantages + update mechanics.

Mirrors reference ppo actor behavior: GRPO (no critic) advantage layout,
decoupled-loss update improving the objective, dynamic sampling filtering.
"""

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    AdvNormConfig,
    MicroBatchSpec,
    OptimizerConfig,
    ParallelismConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.ppo.actor import PPOActor
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.utils import data as data_utils


def _actor(group_size=2, ppo_n_minibatches=2, max_tokens_per_mb=32768, **kw):
    cfg = PPOActorConfig(
        dtype="float32",
        param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb),
        optimizer=OptimizerConfig(lr=1e-3, weight_decay=0.0,
                                  warmup_steps_proportion=0.0,
                                  gradient_clipping=10.0),
        parallel=ParallelismConfig(),
        group_size=group_size,
        ppo_n_minibatches=ppo_n_minibatches,
        group_reward_norm=True,
        adv_norm=AdvNormConfig(mean_level="batch", std_level="batch"),
        **kw,
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 8),
                   model_config=tiny_config("qwen2"), seed=0)
    return PPOActor(cfg, eng)


def _rollout_batch(n=8, vocab=128, seed=0, prompt_len=3):
    """Fake rollout: prompts + completions with behavior logprobs."""
    rng = np.random.default_rng(seed)
    seqs, loss_masks = [], []
    for _ in range(n):
        total = int(rng.integers(6, 14))
        seqs.append(rng.integers(0, vocab, size=total))
        lm = np.zeros(total, np.int32)
        lm[prompt_len:] = 1
        loss_masks.append(lm)
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm_batch = data_utils.pad_sequences_to_tensors(loss_masks)
    batch["loss_mask"] = lm_batch["input_ids"].astype(np.int32)
    mask = batch["attention_mask"]
    batch["logprobs"] = (
        rng.standard_normal(mask.shape).astype(np.float32) * 0.1 - 1.0
    ) * batch["loss_mask"]
    batch["versions"] = np.where(batch["loss_mask"] > 0, 0, -1).astype(np.int32)
    batch["rewards"] = rng.integers(0, 2, size=n).astype(np.float32)
    return batch


def test_compute_advantages_grpo_layout():
    actor = _actor()
    batch = _rollout_batch()
    out = actor.compute_advantages(dict(batch))
    adv = out["advantages"]
    lm = batch["loss_mask"].astype(bool)
    assert adv.shape == batch["input_ids"].shape
    assert (adv[~lm] == 0).all()
    m = adv[lm]
    np.testing.assert_allclose(m.mean(), 0.0, atol=1e-4)  # batch-whitened


def test_ppo_update_runs_and_improves_objective():
    actor = _actor()
    batch = _rollout_batch()
    # proximal logprobs = current-policy recompute (decoupled loss path)
    batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    out = actor.compute_advantages(dict(batch))
    stats = actor.ppo_update(out)
    assert len(stats) == 2  # two minibatches
    for s in stats:
        assert s["update_successful"] == 1.0
        assert np.isfinite(s["grad_norm"])
    assert actor.engine.step_count == 2


def test_dynamic_sampling_filters_uniform_groups():
    actor = _actor(dynamic_sampling=True)
    batch = _rollout_batch()
    # make group 0 uniform (both rewards 1) and group 1 mixed
    batch["rewards"] = np.asarray([1, 1, 0, 1, 1, 1, 0, 1], np.float32)
    batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    out = actor.compute_advantages(dict(batch))
    stats = actor.ppo_update(out)
    assert len(stats) >= 1
