"""Worker for the 2-process multi-host test (reference pattern:
areal/tests/torchrun/ scripts driven by thin pytest wrappers).

Each process: join the jax.distributed world (2 CPU processes × 2 virtual
devices), build ONE global (data=2, fsdp=2) mesh, broadcast the batch from
process 0, run a real SPMDTrainEngine train_batch, and print the packed
stats so the wrapper can assert cross-process agreement.
"""

import os
import sys

# must be set before the backend initializes; the live jax config is
# updated too, so the worker runs on the CPU whatever platform the
# environment selected (same recipe as tests/conftest.py)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    rank = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["AREAL_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["AREAL_NUM_PROCESSES"] = "2"
    os.environ["AREAL_PROCESS_ID"] = str(rank)

    from areal_tpu.parallel.distributed import (
        broadcast_pytree,
        maybe_init_distributed,
        process_allgather_scalars,
    )

    assert maybe_init_distributed()

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()

    import jax.numpy as jnp

    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        ParallelismConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.models.config import tiny_config
    from areal_tpu.engine.sft.lm_engine import (
        sft_loss_fn,
        sft_loss_weight_fn,
    )

    cfg = TrainEngineConfig(
        dtype="float32",
        param_dtype="float32",
        init_from_scratch=True,
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        parallel=ParallelismConfig(
            data_parallel_size=2, fsdp_parallel_size=2
        ),
    )
    engine = SPMDTrainEngine(cfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, 8, 4),
        model_config=tiny_config("qwen2"),
        seed=0,
    )

    # DP-head batch broadcast: process 0 owns the data
    if rank == 0:
        rng = np.random.default_rng(0)
        L = 24
        batch = {
            "input_ids": rng.integers(
                0, 128, size=(8, L), dtype=np.int64
            ).astype(np.int32),
            "attention_mask": np.ones((8, L), np.bool_),
            "loss_mask": np.ones((8, L), np.int32),
        }
    else:
        batch = None
    batch = broadcast_pytree(batch)
    assert batch is not None and batch["input_ids"].shape == (8, 24)

    losses = []
    for _ in range(3):
        stats = engine.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
        assert stats["update_successful"] == 1.0, stats
        losses.append(stats["loss"])
    # loss must agree bit-for-bit across processes (same SPMD program)
    gathered = process_allgather_scalars(losses[-1])
    assert len(gathered) == 2
    assert abs(gathered[0] - gathered[1]) < 1e-6, gathered
    # and training must make progress
    assert losses[-1] < losses[0], losses
    print(f"MULTIHOST_OK rank={rank} losses={losses}")


if __name__ == "__main__":
    main()
