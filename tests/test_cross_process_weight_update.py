"""Cross-PROCESS weight update: the trainer streams device-path FFD
chunks over real HTTP to a generation server running in a separate OS
process, then remote greedy generation matches a local engine holding the
trainer's weights (the true multi-host semantics of the reference's NCCL
trainer->server broadcast, fsdp_engine.py:414-444 + sglang_remote.py:411)."""

import sys

import pytest

import jax

from tests import fixtures


@pytest.fixture(scope="module")
def remote_server():
    with fixtures.children() as procs:
        proc, lines = fixtures.spawn_worker(
            [sys.executable, fixtures.GENSERVER_WORKER, "0"]
        )
        procs.append(proc)
        yield f"127.0.0.1:{fixtures.wait_port(proc, lines, 180)}"


def test_streamed_update_reaches_other_process(remote_server):
    from areal_tpu.api.cli_args import (
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
    from areal_tpu.engine.remote import RemoteInferenceEngine
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.config import tiny_config

    model_cfg = tiny_config("qwen2")
    client = RemoteInferenceEngine(
        InferenceEngineConfig(
            experiment_name="xproc", trial_name="t0",
            consumer_batch_size=2, max_concurrent_rollouts=4,
            request_timeout=120, setup_timeout=60,
        )
    ).initialize(addrs=[remote_server])
    try:
        # trainer in THIS process with different weights (seed 5)
        pcfg = PPOActorConfig(
            dtype="float32", param_dtype="float32",
            mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
            optimizer=OptimizerConfig(lr=1e-4),
            parallel=ParallelismConfig(),
        )
        train = SPMDTrainEngine(pcfg)
        train.initialize(
            FinetuneSpec(1, 16, 4), model_config=model_cfg, seed=5
        )
        meta = WeightUpdateMeta(
            type=WeightUpdateMethod.DEVICE,
            model_version=3,
            chunk_bytes=64 * 1024,  # forces multiple HTTP chunks
            addrs=[remote_server],
        )
        fut = client.update_weights(meta)
        train.upload_weights(meta)
        fut.result(timeout=120)
        assert client.get_version() == 3

        # the OTHER process now serves the trainer's weights: greedy
        # outputs match a local engine holding them
        host = jax.device_get(train.params)
        ref = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16,
            ),
            model_config=model_cfg, params=host,
        ).start()
        try:
            import asyncio

            from areal_tpu.api.cli_args import GenerationHyperparameters
            from areal_tpu.api.io_struct import ModelRequest

            req = ModelRequest(
                input_ids=[7, 6, 5, 4],
                gconfig=GenerationHyperparameters(
                    n_samples=1, max_new_tokens=6, greedy=True
                ),
            )
            remote_out = asyncio.run(client.agenerate(req))
            local_out = ref.generate(
                {
                    "input_ids": [7, 6, 5, 4],
                    "sampling_params": {"max_new_tokens": 6, "greedy": True},
                }
            )
            assert remote_out.output_tokens == local_out["output_ids"]
            assert set(remote_out.output_versions) == {3}
        finally:
            ref.stop()
    finally:
        client.destroy()
