"""Vision-RLVR end to end on a tiny qwen2_vl model (runnable anywhere).

The full multimodal RL slice with synthetic data — the same wiring a real
Qwen2-VL + clevr/geometry run uses (reference areal/workflow/vision_rlvr.py
+ examples/*vision*), at from-scratch feasible scale:

  HF-style processed inputs (pixel patches + grids)
    → VisionRLVRWorkflow (host-side mrope/ordinal meta, mm payload)
    → generation engine serving IMAGE-CONDITIONED completions
      (vision embeds spliced at admission, mrope prefill, rope-delta decode)
    → verifiable reward
    → PPO update whose logp recompute runs THROUGH the vision tower.

Run: python examples/vlm_rlvr.py          (~4 min on one CPU core)

The demo model is ~0.1M params: at that scale an accelerator is pure
dispatch latency, so the script pins itself to the host CPU platform (a
real VLM run uses the chip via the normal configs).
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from __graft_entry__ import _ensure_virtual_devices  # noqa: E402

_ensure_virtual_devices(1)


def main():
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxGenConfig,
        MicroBatchSpec,
        OptimizerConfig,
        ParallelismConfig,
        PPOActorConfig,
    )
    from areal_tpu.api.io_struct import (
        FinetuneSpec,
        WeightUpdateMeta,
        WeightUpdateMethod,
    )
    from areal_tpu.engine.local import LocalSyncInferenceEngine
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.models.config import tiny_vlm_config
    from areal_tpu.workflow.vision_rlvr import VisionRLVRWorkflow

    cfg = tiny_vlm_config()
    img_id = cfg.image_token_id
    rng = np.random.default_rng(0)

    # --- trainer + colocated serving engine share the weights ---
    pcfg = PPOActorConfig(
        dtype="float32", param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        parallel=ParallelismConfig(),
        group_size=2, group_reward_norm=True, ppo_n_minibatches=1,
        recompute_logprob=True, use_decoupled_loss=True,
    )
    trainer = SPMDTrainEngine(pcfg)
    trainer.initialize(FinetuneSpec(1, 64, 4), model_config=cfg, seed=0)
    actor = PPOActor(pcfg, trainer)

    rollout = LocalSyncInferenceEngine(
        InferenceEngineConfig(
            experiment_name="vlm-demo", trial_name="t0",
            consumer_batch_size=4, max_head_offpolicyness=2,
        ),
        JaxGenConfig(
            dtype="float32", max_num_seqs=16, max_model_len=64,
            prefill_chunk=16,
        ),
        model_config=cfg,
        params=jax.device_get(trainer.params),
    ).initialize(train_engine=trainer)

    # --- synthetic VLM items: a 4x4-patch "image" + a question prompt;
    # reward = completion mentions the image's dominant-intensity quadrant
    # parity (a verifiable function OF THE PIXELS, so image-blind serving
    # scores at chance) ---
    def make_item(i):
        pix = rng.standard_normal((16, cfg.vision.patch_dim)).astype(
            np.float32
        )
        bright = int(np.abs(pix).mean() * 10) % 2
        return {
            "input_ids": [3, 4] + [img_id] * 4 + [5 + (i % 3)],
            "pixel_values": pix,
            "image_grid_thw": np.asarray([[1, 4, 4]]),
            "answer": str(bright),
        }

    def reward_fn(prompt, completion, prompt_ids, completion_ids,
                  answer="", **kw):
        # toy verifiable reward: first generated token's parity
        if not completion_ids:
            return 0.0
        return float(completion_ids[0] % 2 == int(answer))

    wf = VisionRLVRWorkflow(
        reward_fn,
        GenerationHyperparameters(n_samples=2, max_new_tokens=6,
                                  temperature=1.0),
        image_token_id=img_id,
        spatial_merge_size=cfg.vision.spatial_merge_size,
    )

    for step in range(2):
        batch = rollout.rollout_batch([make_item(i) for i in range(2)], wf)
        out = actor.compute_advantages(dict(batch))
        stats = actor.ppo_update(out)
        print(
            f"step {step}: reward={float(np.mean(batch['rewards'])):.3f} "
            f"loss={stats[0]['loss']:.5f} "
            f"grad_norm={stats[0]['grad_norm']:.3f} "
            f"mm_tokens={int((np.asarray(batch['mm_index']) >= 0).sum())}",
            flush=True,
        )
        assert stats[0]["update_successful"] == 1.0
        # push updated weights into the server (bumps the version; the
        # staleness gate budgets future rollouts against it)
        new_version = trainer.get_version() + 1
        rollout.update_weights(
            WeightUpdateMeta(
                type=WeightUpdateMethod.DEVICE, model_version=new_version
            )
        ).result(timeout=600)
        trainer.set_version(new_version)
    rollout.destroy()
    print("vision RLVR slice OK: pixels -> rollout -> reward -> update")


if __name__ == "__main__":
    main()
