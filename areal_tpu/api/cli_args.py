"""Experiment configuration dataclasses + YAML/CLI loading.

Role of reference areal/api/cli_args.py: every experiment is a nested
dataclass tree, loaded from a YAML file (``--config path.yaml``) and
overridden by dotted CLI args (``actor.optimizer.lr=1e-5``). The reference
uses OmegaConf; here a small recursive merge over ``dataclasses.fields`` does
the same job dependency-free.
"""

import argparse
import dataclasses
import enum
import os
import sys
import typing
from typing import Any, Dict, List, Optional, Tuple, Type, TypeVar

import yaml

T = TypeVar("T")


# --------------------------------------------------------------------------
# Generation
# --------------------------------------------------------------------------
@dataclasses.dataclass
class GenerationHyperparameters:
    """Sampling options for rollout (reference cli_args.py:82)."""

    n_samples: int = 1
    max_new_tokens: int = 512
    min_new_tokens: int = 0
    greedy: bool = False
    top_p: float = 1.0
    top_k: int = 0  # 0 disables top-k
    temperature: float = 1.0
    stop_token_ids: List[int] = dataclasses.field(default_factory=list)

    def new(self, **kwargs) -> "GenerationHyperparameters":
        return dataclasses.replace(self, **kwargs)


# --------------------------------------------------------------------------
# Training
# --------------------------------------------------------------------------
@dataclasses.dataclass
class OptimizerConfig:
    """optax optimizer spec (reference cli_args.py:140)."""

    type: str = "adamw"
    lr: float = 2e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    min_lr_ratio: float = 0.0
    lr_scheduler_type: str = "constant"  # constant | linear | cosine
    warmup_steps_proportion: float = 0.001
    gradient_clipping: float = 1.0
    offload_optimizer_state: bool = False


@dataclasses.dataclass
class MicroBatchSpec:
    """Token-budget micro-batching (reference api/cli_args MicroBatchSpec)."""

    n_mbs: int = 1
    max_tokens_per_mb: int = 32768


@dataclasses.dataclass
class ParallelismConfig:
    """Trainer mesh axis sizes. On TPU these build one
    jax.sharding.Mesh with axes (data, fsdp, seq, tensor); data×fsdp shards
    the batch + optimizer state, seq is Ulysses-style sequence parallelism,
    tensor shards weights within attention/MLP blocks."""

    data_parallel_size: int = 1
    fsdp_parallel_size: int = 1
    tensor_parallel_size: int = 1
    seq_parallel_size: int = 1
    # MoE expert-parallel degree (experts shard over this mesh axis)
    expert_parallel_size: int = 1
    # cross-SLICE data parallelism over DCN: the mesh's data axis becomes
    # (dcn_data * data) with device order arranged slice-major, so only
    # the once-per-step grad psum crosses DCN while fsdp/seq/tensor/expert
    # collectives stay on each slice's ICI (how meshes larger than one ICI
    # domain scale — the reference's multi-node 32B recipes' analog)
    dcn_data_parallel_size: int = 1
    # cross-SLICE fsdp over DCN: the fsdp axis becomes (dcn_fsdp * fsdp)
    # with the OUTER fsdp positions striding across slices — parameter and
    # optimizer shards span slices, so a model too big for ONE slice's HBM
    # (the 32B recipe) still fits, at the cost of fsdp all-gathers riding
    # DCN. Prefer dcn_data when the model fits a slice.
    dcn_fsdp_parallel_size: int = 1

    @property
    def world_size(self) -> int:
        return (
            self.dcn_data_parallel_size
            * self.dcn_fsdp_parallel_size
            * self.data_parallel_size
            * self.fsdp_parallel_size
            * self.tensor_parallel_size
            * self.seq_parallel_size
            * self.expert_parallel_size
        )


@dataclasses.dataclass
class TrainEngineConfig:
    """Train-engine spec (reference cli_args.py:223)."""

    experiment_name: str = ""
    trial_name: str = ""
    path: str = ""  # HF checkpoint path or model preset name
    init_from_scratch: bool = False
    dtype: str = "bfloat16"  # compute dtype (MXU-friendly)
    param_dtype: str = "float32"  # parameter/optimizer storage (master weights)
    disable_dropout: bool = True
    gradient_checkpointing: bool = True
    # the attention core. "auto" is resolved once at initialize() from what
    # the process observes (SPMDTrainEngine.attn_impl holds the result): on
    # a TPU with seq_parallel_size == 1 and a head dimension the kernel
    # takes, the fused Pallas splash kernel (one chip: the kernel itself; a
    # mesh: under shard_map, rows and heads split); on the CPU backend, or
    # with seq_parallel_size > 1, the XLA form, which GSPMD shards. "flash"
    # names the kernel outright; "ring"/"ulysses" are the explicit
    # sequence-parallel shard_map kernels (the XLA form with nothing to split)
    attn_impl: str = "auto"
    mb_spec: MicroBatchSpec = dataclasses.field(default_factory=MicroBatchSpec)
    optimizer: Optional[OptimizerConfig] = dataclasses.field(default_factory=OptimizerConfig)
    parallel: ParallelismConfig = dataclasses.field(default_factory=ParallelismConfig)
    backend: str = "spmd"


@dataclasses.dataclass
class AdvNormConfig:
    """Advantage normalization (reference ppo/actor.py:370 `AdvNorm`)."""

    mean_level: str = "batch"  # batch | group | none
    std_level: str = "batch"  # batch | group | none
    group_size: int = 1


@dataclasses.dataclass
class PPOActorConfig(TrainEngineConfig):
    """GRPO/PPO algorithm options (reference cli_args.py:274)."""

    group_size: int = 1  # answers per prompt (GRPO group)
    ppo_n_minibatches: int = 4
    eps_clip: float = 0.2
    eps_clip_higher: Optional[float] = None  # asymmetric upper clip (DAPO)
    c_clip: Optional[float] = None  # dual clip
    temperature: float = 1.0
    gamma: float = 1.0
    lam: float = 1.0
    reward_scaling: float = 1.0
    reward_bias: float = 0.0
    reward_clip: float = 20.0
    group_reward_norm: bool = False
    adv_norm: AdvNormConfig = dataclasses.field(default_factory=AdvNormConfig)
    kl_ctl: float = 0.0
    recompute_logprob: bool = True
    use_decoupled_loss: bool = True
    behav_imp_weight_cap: Optional[float] = None
    dynamic_sampling: bool = False
    # overlong reward penalty (DAPO; reference utils/functional.py:237)
    overlong_reward_penalty: bool = False
    overlong_tokens: int = 0
    overlong_penalty_factor: float = 0.0
    max_new_tokens: int = 512
    # adaptive KL controller (reference
    # realhf/impl/model/utils/ppo_functional.py:14-49): when kl_adaptive,
    # kl_ctl is the INITIAL coefficient, adapted toward kl_target over
    # kl_horizon tokens
    kl_adaptive: bool = False
    kl_target: float = 0.1
    kl_horizon: float = 10000.0


@dataclasses.dataclass
class PPOCriticConfig(TrainEngineConfig):
    """Value-model options (reference PPOCriticInterface,
    realhf/impl/model/interface/ppo_interface.py:984)."""

    is_critic: bool = True
    value_eps_clip: float = 0.2
    ppo_n_minibatches: int = 4
    temperature: float = 1.0


# --------------------------------------------------------------------------
# Inference / rollout
# --------------------------------------------------------------------------
@dataclasses.dataclass
class InferenceEngineConfig:
    """Async rollout control (reference cli_args.py:531)."""

    experiment_name: str = ""
    trial_name: str = ""
    max_concurrent_rollouts: Optional[int] = None
    queue_size: Optional[int] = None
    # Unit = episodes (prompts), NOT sequences: wait()/get_capacity() count
    # one per submitted workflow item, and each RLVR episode carries
    # gconfig.n_samples sequences. Set this to the dataloader batch size.
    consumer_batch_size: int = 1
    max_head_offpolicyness: int = 0  # staleness η: max model-version lead
    enable_rollout_tracing: bool = False
    schedule_policy: str = "round_robin"  # round_robin | least_requests
    request_timeout: float = 3600.0
    request_retries: int = 3
    setup_timeout: float = 120.0
    # bound on the pause→transfer→version-bump window of a weight update:
    # a failed upload must not hold servers paused for request_timeout
    weight_update_timeout: float = 300.0
    pause_grace_period: float = 0.0
    # chunked partial rollout (reference realhf/system/partial_rollout.py:29
    # PartialRolloutManager): each /generate asks for at most this many new
    # tokens, so weight updates interleave at chunk boundaries even without
    # server-side aborts; 0 = request everything at once
    new_tokens_per_chunk: int = 0
    # client-side request-lifecycle spans (submit→first-token→complete,
    # weight-update pause windows)
    tracing: "TracingConfig" = dataclasses.field(
        default_factory=lambda: TracingConfig()
    )
    # fleet resilience plane (inference/fleet.py): health probing, circuit
    # breaking, failover-aware generation, dynamic membership
    fleet: "FleetConfig" = dataclasses.field(
        default_factory=lambda: FleetConfig()
    )
    # trainer-side durability plane (api/workflow_api.py): episode retry
    # with poison quarantine, sliding-window failure budget → DEGRADED
    # state, prepare_batch deadline + dead-fleet health probe
    durability: "DurabilityConfig" = dataclasses.field(
        default_factory=lambda: DurabilityConfig()
    )
    # fleet telemetry hub (utils/telemetry.TelemetryCollector): when
    # enabled, the remote engine starts a collector over its fleet
    # (FleetMonitor membership + the executor's lineage ledger) and
    # serves the consolidated /metrics + /manifest hub endpoint
    telemetry: "TelemetryConfig" = dataclasses.field(
        default_factory=lambda: TelemetryConfig()
    )
    # router-scheduled mode: when set ("host:port"), agenerate asks the
    # fronting router's POST /schedule_request for a server each chunk
    # (qid affinity + global load view) instead of the client-local
    # policy, forwarding the trace context so the router lands on the
    # same stitched timeline; empty = client-local choose_server
    router_addr: str = ""
    # SLO-aware traffic plane: class weights, tenant caps, router
    # shedding thresholds, and the fleet autoscaler envelope. The client
    # reads `tenant` (default stamp) here; the router and launcher read
    # the admission/autoscale knobs
    traffic: "TrafficConfig" = dataclasses.field(
        default_factory=lambda: TrafficConfig()
    )
    # zero-pause weight plane (r13): when True, update_weights never
    # POSTs /pause_generation — the trainer streams chunks at live
    # servers, each server applies them into a shadow buffer
    # (inference/weights.WeightStore) and flips at a dispatch boundary.
    # The client records a `weight_stream` span instead of a
    # `weight_update_pause` window. False restores the r2 pause
    # protocol (the bench A/B baseline; also the right setting against
    # pre-r13 servers, whose chunk ingest stalls decode per chunk).
    streamed_weight_updates: bool = True
    # staleness admission mode (api/workflow_api.WorkflowExecutor):
    # "step" = the legacy global gate ((eta + version + 1) * batch
    # bounds accepted+running); "trajectory" = per-sample admission —
    # capacity is bounded by max_concurrent_rollouts alone and wait()
    # drops any sample whose staleness-at-consumption (trainer version
    # minus the oldest weight version that produced one of its tokens,
    # from the LineageLedger) exceeds max_head_offpolicyness, refilling
    # the batch with a fresh generation. Trajectory mode is what makes
    # streamed weight flips safe at eta=0-ish targets: the fence is
    # enforced on what the trainer CONSUMES, not on what may run.
    staleness_mode: str = "step"
    # trajectory lineage ledger (utils/telemetry.LineageLedger): consumed
    # records are appended here as JSONL when set (the in-memory ledger
    # is always on; recover checkpoints snapshot it either way)
    lineage_path: str = ""
    # bounded in-memory lineage records (oldest consumed drop first)
    lineage_max_records: int = 8192


@dataclasses.dataclass
class JaxGenConfig:
    """Generation-engine/server spec — the analog of the reference's
    SGLangConfig (cli_args.py:458), but describing the in-repo JAX engine."""

    model_path: str = ""
    dtype: str = "bfloat16"
    seed: int = 1
    max_num_seqs: int = 64  # decode slots
    max_model_len: int = 4096
    prefill_chunk: int = 512
    # --- chunked prefill (r15): bounded interactive TTFT ---
    # split a long prompt's prefill into page-aligned chunks admitted
    # across successive waves and interleaved with decode dispatches:
    # each chunk publishes its committed pages into the prefix cache
    # (publish-at-chunk-commit) and the next chunk resumes by claiming
    # them, so time-to-first-token for a request admitted behind a bulk
    # prompt is bounded by ~one chunk's latency instead of the longest
    # prefill in flight — and chunk boundaries become cheap preemption
    # points for deadline-pressed interactive traffic. Requires a
    # prefix cache (prefix_reuse_min > 0). Greedy streams are
    # bit-identical chunked on/off; off is a strict no-op (unchanged
    # programs, no new metric keys).
    chunked_prefill: bool = False
    # per-dispatch prefill token budget when chunking (floored to a
    # page multiple, min one page, must be >= prefix_reuse_min;
    # 0 = auto: 2 x prefill_chunk)
    prefill_chunk_tokens: int = 0
    # decode steps fused into one device dispatch (amortizes the host
    # round-trip; stop handling happens on device so at most one dispatch
    # of latency is added to a finished request)
    decode_chunk: int = 8
    # decode chunks kept in flight while the previous chunk's results are
    # fetched/processed on host (0 = fully synchronous). Overlapping hides
    # the host round-trip, at the price of one dispatch of latency
    decode_pipeline: int = 1
    # --- decode tail compaction (r6) ---
    # dispatch decode over a pow2 bucket of ACTIVE slots instead of the
    # full max_num_seqs slot array: during the straggler tail of a GRPO
    # wave the fused scan, paged attention, and sampling stop paying for
    # finished rows. Per-slot state is gathered into the compact row
    # space before dispatch and scattered back after; sampling is keyed
    # by SLOT id (not row position), so token streams are identical with
    # compaction on or off. Single-device only (TP serving keeps the
    # full-slot dispatch)
    decode_compact: bool = True
    # smallest compact row bucket — floors the recompile ladder (row
    # shapes are pow2: min_rows, 2*min_rows, ..., max_num_seqs)
    decode_compact_min_rows: int = 4
    # consecutive chunks the active count must sit below the current
    # bucket's shrink target before the bucket shrinks (growth is always
    # immediate); damps recompile thrash when requests finish raggedly
    decode_compact_hysteresis: int = 4
    # unique prompts prefilled in one batched dispatch (rows are padded to
    # this wave size so the program shape is static per bucket); identical
    # prompts (GRPO siblings) share one row + a KV line copy
    admit_wave: int = 8
    # newly queued requests are held up to this long (while decode has work
    # or the queue is still filling) so admission waves arrive full — every
    # distinct wave shape is a separate XLA compile
    admit_hold_s: float = 0.05
    # decode attention reads cache lines bucketed to this quantum above the
    # longest active sequence (instead of always max_model_len)
    kv_bucket: int = 256
    # lax.top_k candidate count for truncated sampling (raised to the max
    # requested per-slot top_k); 0 would force the exact full-vocab sort
    sample_topk_bound: int = 64
    # reuse freed requests' cached KV (prefix cache) when >= this many
    # prompt tokens match (0 disables prefix reuse); matches are shared at
    # page granularity by refcount, not copied
    prefix_reuse_min: int = 16
    # prefix-cache implementation: "radix" (r9 default — refcounted radix
    # tree over the paged pool, O(prompt) descent, publish-at-prefill-
    # commit so GRPO siblings/agentic turns claim a live request's prompt
    # pages, COW claims for divergence within a partial tail page) or
    # "flat" (the r1-r8 free-time-only linear-scan registry, kept as the
    # bench A/B baseline). prefix_reuse_min=0 disables both.
    prefix_cache_mode: str = "radix"
    # --- hierarchical KV tiers (r16, inference/kv_tiers.py) ---
    # spill radix leaves to a host-RAM tier on eviction instead of
    # dropping them; claims promote spilled pages back to the device
    # pool (batched scatter) BEFORE the wave dispatches. Radix mode
    # only. Off = strict no-op (greedy streams bit-identical, no new
    # metric keys).
    kv_spill: bool = False
    # host-tier capacity in bytes (per server); LRU pages past the
    # budget drop to disk when kv_disk_path is set, else vanish
    host_kv_bytes: int = 1 << 30
    # optional third tier: directory for LRU-overflow page files
    # (empty = no disk tier)
    kv_disk_path: str = ""
    # cross-server prefix shipping: serve GET/POST /kv_export and
    # accept /kv_import + /generate kv_ship_from hints, so a router
    # affinity miss re-homes a session's committed prefix instead of
    # re-prefilling it. Radix mode only; independent of kv_spill.
    kv_ship: bool = False
    # --- paged KV pool (the radix/paged-cache analog) ---
    page_size: int = 256  # tokens per KV page
    # total pages in the pool; 0 = auto (full provisioning: every slot can
    # reach max_model_len). Set explicitly to oversubscribe — the engine
    # preempts transparently under pool pressure, which is what makes
    # 16k+ max_model_len serveable without 16k*slots of HBM
    num_pages: int = 0
    # paged-attention backend: "auto" (Pallas kernel on single-device TPU,
    # jnp gather elsewhere), "kernel", or "jnp"
    attn_impl: str = "auto"
    pages_per_compute_block: int = 4  # kernel flash-block size, in pages
    slots_per_block: int = 8  # kernel grid-step slot grouping
    # KV pool row layout: "token_packed" (row = 128//D tokens of one head)
    # or "head_merged" (row = all kv heads of 128//(Hkv*D) tokens — one
    # DMA per page moves every head; needs Hkv*D | 128). r6: "auto" now
    # resolves to head_merged whenever the geometry allows it on a
    # single-device engine (ops/paged_attention.resolve_pool_layout —
    # parity-pinned in tests/test_pool_layout.py and
    # tests/test_paged_kernel_parity.py); TP serving stays token_packed
    # (the pool's kv-head dim is the TP shard axis).
    pool_layout: str = "auto"
    # --- SLO traffic plane (server side) ---
    # bounded admission queue: with more than this many requests queued
    # (admit queue + pending), new BULK submissions are shed with a
    # typed 429 + Retry-After instead of queueing unboundedly behind
    # max_num_seqs; interactive submissions are shed only past twice
    # the bound (protected, not unbounded). 0 = unbounded (legacy).
    max_queued_requests: int = 0
    # Retry-After seconds attached to shed responses
    shed_retry_after_s: float = 1.0
    # deadline-aware preemption: a queued INTERACTIVE request carrying a
    # soft deadline that is about to miss it (inside this margin, or
    # having already waited half its deadline budget with no free slot)
    # preempts the youngest BULK request; the victim resumes via the
    # prefix-cache re-queue path (zero lost rollouts). False disables.
    deadline_preemption: bool = True
    deadline_margin_s: float = 0.25
    # persistent XLA compilation cache directory ("" = disabled). The
    # decode bucket ladder compiles O(100) programs on a cold engine
    # (378 s of warmup in the r5 bench capture); a warm cache replays
    # them from disk. Wired through the server CLI and launcher env
    # (JAX_COMPILATION_CACHE_DIR) so subprocess servers share it.
    compilation_cache_dir: str = ""
    tensor_parallel_size: int = 1
    mem_fraction: float = 0.85
    enable_metrics: bool = True
    # draft-free speculative decoding (r7): host-side n-gram proposals
    # verified by one multi-token dispatch with KV rollback
    # (inference/spec.py + model_runner.spec_verify). Off by default —
    # disabled is a strict no-op (no extra dispatches, no metric keys)
    spec: "SpecConfig" = dataclasses.field(
        default_factory=lambda: SpecConfig()
    )
    # engine-side request-lifecycle spans (queue-wait, prefill, decode,
    # preemption, weight-update windows); drained over GET /trace
    tracing: "TracingConfig" = dataclasses.field(
        default_factory=lambda: TracingConfig()
    )
    # goodput attribution (utils/goodput.py): engine wall-clock ledger
    # (prefill/decode/spec_verify/weight_pause/compile/idle), compile
    # event stream, and the warming→ready /health readiness rule
    goodput: "GoodputConfig" = dataclasses.field(
        default_factory=lambda: GoodputConfig()
    )
    # zero-pause weight plane (inference/weights.WeightStore): streamed
    # double-buffered weight ingest + atomic flip at a dispatch
    # boundary, in-flight-request version pinning, staging TTL
    weights: "WeightTransferConfig" = dataclasses.field(
        default_factory=lambda: WeightTransferConfig()
    )
    # multi-policy serving plane (inference/policies.PolicyRegistry):
    # named policy handles with independent version lines, canary
    # splits, per-(policy, version) KV namespaces, and LRU HBM→host
    # demotion of cold policy buffers
    policy: "PolicyConfig" = dataclasses.field(
        default_factory=lambda: PolicyConfig()
    )
    # cold-start elimination (inference/precompile.py): AOT-precompile
    # the exact shape ladder (or replay a prior run's compile events)
    # before/while serving, seeding the persistent compile cache
    precompile: "PrecompileConfig" = dataclasses.field(
        default_factory=lambda: PrecompileConfig()
    )
    log_level: str = "info"
    host: str = "127.0.0.1"
    port: int = 0  # 0 = auto

    @staticmethod
    def build_cmd(
        config: "JaxGenConfig",
        host: str,
        port: int,
        experiment_name: str = "",
        trial_name: str = "",
    ) -> List[str]:
        """Command line for a standalone generation server process."""
        args = [
            sys.executable,
            "-m",
            "areal_tpu.inference.server",
            f"--model-path={config.model_path}",
            f"--host={host}",
            f"--port={port}",
            f"--max-num-seqs={config.max_num_seqs}",
            f"--max-model-len={config.max_model_len}",
            f"--dtype={config.dtype}",
            f"--tensor-parallel-size={config.tensor_parallel_size}",
            f"--seed={config.seed}",
        ]
        if experiment_name:
            args.append(f"--experiment-name={experiment_name}")
        if trial_name:
            args.append(f"--trial-name={trial_name}")
        if config.tracing.enabled:
            args.append("--trace")
        if config.compilation_cache_dir:
            args.append(
                f"--compilation-cache-dir={config.compilation_cache_dir}"
            )
        # engine shape/batching knobs: forwarded unconditionally so a
        # launched server always serves exactly this config — a flag
        # missing here means subprocess servers silently run defaults
        # (the deadline_margin_s bug class; arealint ARL002 pins the
        # field ↔ flag ↔ build_cmd parity)
        if config.chunked_prefill:
            args.append("--chunked-prefill")
        args += [
            f"--prefill-chunk-tokens={config.prefill_chunk_tokens}",
            f"--prefill-chunk={config.prefill_chunk}",
            f"--decode-chunk={config.decode_chunk}",
            f"--decode-pipeline={config.decode_pipeline}",
            f"--decode-compact-min-rows={config.decode_compact_min_rows}",
            (
                "--decode-compact-hysteresis="
                f"{config.decode_compact_hysteresis}"
            ),
            f"--admit-wave={config.admit_wave}",
            f"--admit-hold={config.admit_hold_s}",
            f"--kv-bucket={config.kv_bucket}",
            f"--sample-topk-bound={config.sample_topk_bound}",
            f"--page-size={config.page_size}",
            f"--num-pages={config.num_pages}",
            f"--attn-impl={config.attn_impl}",
            f"--pages-per-compute-block={config.pages_per_compute_block}",
            f"--slots-per-block={config.slots_per_block}",
            f"--pool-layout={config.pool_layout}",
            f"--mem-fraction={config.mem_fraction}",
            f"--log-level={config.log_level}",
        ]
        if not config.decode_compact:
            args.append("--no-decode-compact")
        if not config.enable_metrics:
            args.append("--disable-metrics")
        # hierarchical KV tiers (r16): spill/ship servers must agree
        # with the client's config or affinity misses re-prefill
        if config.kv_spill:
            args += [
                "--kv-spill",
                f"--host-kv-bytes={config.host_kv_bytes}",
            ]
            if config.kv_disk_path:
                args.append(f"--kv-disk-path={config.kv_disk_path}")
        if config.kv_ship:
            args.append("--kv-ship")
        args += [
            f"--prefix-cache-mode={config.prefix_cache_mode}",
            f"--prefix-reuse-min={config.prefix_reuse_min}",
            f"--ready-quiet={config.goodput.ready_quiet_s}",
            f"--ready-min-requests={config.goodput.ready_min_requests}",
        ]
        if config.tracing.enabled:
            args.append(f"--trace-max-spans={config.tracing.max_spans}")
        if config.goodput.compile_events_path:
            args.append(
                f"--compile-events={config.goodput.compile_events_path}"
            )
        args.append(
            "--compile-events-max-bytes="
            f"{config.goodput.compile_events_max_bytes}"
        )
        # cold-start elimination (r14): launched servers warm their
        # shape ladder before/while opening for traffic
        if config.precompile.mode != "off":
            args.append(f"--precompile={config.precompile.mode}")
            if config.precompile.replay_path:
                args.append(
                    f"--precompile-replay={config.precompile.replay_path}"
                )
        if config.goodput.jsonl_path:
            args.append(f"--goodput-jsonl={config.goodput.jsonl_path}")
        if config.max_queued_requests > 0:
            args += [
                f"--max-queued-requests={config.max_queued_requests}",
                f"--shed-retry-after={config.shed_retry_after_s}",
            ]
        args.append(f"--deadline-margin={config.deadline_margin_s}")
        if not config.deadline_preemption:
            args.append("--no-deadline-preemption")
        # zero-pause weight plane (r13): streamed servers must agree
        # with the client's streamed_weight_updates setting, so the
        # whole weight config always rides the command line
        args += [
            f"--weight-flip-policy={config.weights.flip_policy}",
            f"--weight-staging-ttl={config.weights.staging_ttl_s}",
            f"--policy-max-resident={config.policy.max_resident}",
        ]
        if not config.weights.streaming:
            args.append("--no-weight-streaming")
        if config.spec.enabled:
            args += [
                "--spec",
                f"--spec-max-draft={config.spec.max_draft}",
                f"--spec-ngram-min={config.spec.ngram_min}",
                f"--spec-ngram-max={config.spec.ngram_max}",
                f"--spec-accept-floor={config.spec.accept_floor}",
                f"--spec-disable-patience={config.spec.disable_patience}",
            ]
        return args


# --------------------------------------------------------------------------
# Aux subsystems
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SpecConfig:
    """Draft-free speculative decoding (inference/spec.py proposers +
    the multi-token verify dispatch in inference/model_runner.py).

    A host-side proposer (n-gram self-speculation: suffix match against
    the request's own prompt+output — no draft model) guesses up to
    ``max_draft`` continuation tokens per slot; ONE device dispatch
    scores every position causally and accepts the longest prefix the
    model itself would have produced. Greedy streams are bit-identical
    with speculation on or off (exact-match acceptance); sampled streams
    keep their exact distribution (every kept token is drawn from the
    true conditional under an independent key). Rejected positions roll
    back: their K/V never reach the paged pool and cache-length
    accounting matches a non-speculative run. Single-device dense
    serving only (TP keeps the full-slot dispatch; MoE capacity routing
    is batch-dependent)."""

    enabled: bool = False
    # draft tokens proposed per verify round; the verify window is
    # max_draft + 1 positions (current token + drafts)
    max_draft: int = 4
    # suffix n-gram lengths tried for the history match (longest first)
    ngram_min: int = 2
    ngram_max: int = 4
    # auto-disable hysteresis: speculation turns off (sticky) when the
    # accept-rate EWMA stays below this floor for ``disable_patience``
    # consecutive verify chunks; <= 0 never disables
    accept_floor: float = 0.1
    disable_patience: int = 32


@dataclasses.dataclass
class WeightTransferConfig:
    """Zero-pause weight plane, server side (inference/weights.py
    `WeightStore` + the engine flip machinery).

    With ``streaming`` on, weight updates never stop decode: chunked
    device-path pushes (and disk reloads) are staged into a shadow
    buffer on the HTTP handler thread while the engine loop keeps
    dispatching on version N, then the completed buffer flips in
    atomically BETWEEN dispatches — no ``pause_window`` span is ever
    emitted. Correctness across the flip is a version fence, not
    bit-exactness: every token records the weight version that produced
    it, and in-flight sequences either finish pinned to N
    (``flip_policy="pin"`` — the store keeps N's buffer alive until its
    last pinned request drains, and the engine dispatches each version
    cohort with its own params) or resolve with ``stop_reason="abort"``
    and resume suffix-exact on N+1 (``flip_policy="resume"`` — the
    existing interruption contract, minus the fleet-wide pause).
    ``pin`` needs the compacted decode dispatch (single-device); TP and
    compaction-off engines degrade to ``resume`` at the flip."""

    streaming: bool = True
    # "pin" | "resume" (see above). Unknown values are an init error.
    flip_policy: str = "pin"
    # abandoned-staging GC: a client that dies mid-stream must not pin
    # host/HBM staging bytes forever — staging older than this is
    # dropped (visible via the weight_staging_bytes gauge and the
    # weight_staging_aborts_total counter); <= 0 disables the sweep
    staging_ttl_s: float = 120.0


@dataclasses.dataclass
class PolicyConfig:
    """Multi-policy serving plane (inference/policies.PolicyRegistry).

    Named policy handles (``actor``, ``opponent``, ...) each carry an
    independent version line on one engine: per-line stable + canary
    buffers, deterministic canary traffic splits, per-(policy, version)
    KV namespaces in the radix cache, and per-request pins so a buffer
    serving in-flight decodes can never be dropped. Single-policy mode
    (no named push) is a strict no-op — greedy streams and the metric
    namespace are bit-identical to an engine without this plane."""

    # named policy weight buffers kept resident in HBM; colder
    # (unpinned) buffers LRU-demote to host RAM and reload on the next
    # request targeting them (<= 0 disables demotion)
    max_resident: int = 2


@dataclasses.dataclass
class GoodputConfig:
    """Goodput attribution plane (utils/goodput.py): wall-clock bucket
    ledger + recompile attribution for one owning loop. Always on — the
    ledger costs a few monotonic reads per loop iteration — but the
    JSONL streams only flow when paths are set."""

    # goodput ledger snapshots appended here (one JSON line per export)
    jsonl_path: str = ""
    # one line per XLA backend compile with its triggering phase + shape
    # signature — the input the shape-ladder AOT precompiler consumes.
    # The stream opens with a header line (ladder fingerprint + jax
    # version) and rotates to <path>.1 past compile_events_max_bytes,
    # so restarts can't grow it without bound
    compile_events_path: str = ""
    compile_events_max_bytes: int = 8_000_000
    # readiness: a server reports /health "warming" from its first XLA
    # compile until its shape ladder is covered, it goes ready_quiet_s
    # without compiling, or it has COMPLETED ready_min_requests
    # requests end-to-end (a server successfully serving is
    # serving-ready even while incremental shapes still compile —
    # without this, sustained traffic would hold a healthy server out
    # of rotation indefinitely; <= 0 disables the completion path).
    # Keeps cold servers out of fleet rotation through the compile
    # storm without deadlocking an idle fresh one.
    ready_quiet_s: float = 3.0
    ready_min_requests: int = 1


@dataclasses.dataclass
class PrecompileConfig:
    """Shape-ladder AOT precompilation (inference/precompile.py): drive
    the engine's exact compiled-program ladder ahead of traffic so a
    cold server reaches /health ``ready`` without a traffic-driven
    compile storm.

    ``mode``: "off" (default), "ladder" (AOT-compile the full
    enumerated ladder at startup — with a seeded persistent compile
    cache this is seconds of disk retrieval, not minutes of XLA), or
    "replay" (warm only the shapes a prior run's compile_events stream
    actually hit; refuses a stream whose ladder fingerprint doesn't
    match). The server CLI accepts ``--precompile replay:<path>`` as
    shorthand for mode=replay + replay_path."""

    mode: str = "off"
    # compile_events.jsonl from a prior run (mode="replay")
    replay_path: str = ""
    # seed artifact (utils/compile_cache.pack_seed tarball) the LAUNCHER
    # unpacks into compilation_cache_dir before spawning servers —
    # autoscaler scale-ups and supervisor full-constellation restarts
    # then warm from disk instead of re-paying the compile storm.
    # Launcher-side: the server process never reads it (deliberately
    # not CLI-plumbed; see arealint ARL002 exemption).
    seed_artifact: str = ""


@dataclasses.dataclass
class TracingConfig:
    """Request-lifecycle span tracing (utils/tracing.py): per-rid spans
    recorded by the inference engine / remote rollout controller, exported
    as JSONL or Chrome trace-event JSON (Perfetto / chrome://tracing).
    Disabled by default — the tracer is a strict no-op then (no per-token
    allocations on the scheduler hot loop)."""

    enabled: bool = False
    # ring-buffer bound: oldest spans are dropped past this count, so a
    # long-running server never grows without bound
    max_spans: int = 100_000
    # optional JSONL sink written by flush()/export helpers (empty = only
    # in-memory draining via GET /trace or tracer.drain())
    export_path: str = ""


@dataclasses.dataclass
class TelemetryConfig:
    """Fleet telemetry hub (utils/telemetry.TelemetryCollector): a
    background thread scrapes every generation server's ``/metrics`` and
    drains ``/trace``, computes fleet-wide rollups (queue-wait p95, KV
    utilization, accept rate, staleness distribution), runs the
    deterministic anomaly rules below (gauge flip + ERROR log, cleared
    symmetrically), and serves the consolidated ``GET /metrics`` + a
    run-manifest JSON — the inputs a queue-wait/KV-util-driven
    autoscaler consumes."""

    enabled: bool = False
    scrape_interval_s: float = 2.0
    # also drain each server's GET /trace per sweep (keeps the spans of
    # a later-killed server; feeds the stitched fleet timeline and the
    # queue-wait rollup). Off = metrics-only scraping.
    drain_traces: bool = True
    # spans kept per server for rollups/stitching (bounded ring)
    span_window: int = 4096
    # --- anomaly rules (all deterministic; each drives one 0/1 gauge) ---
    # decode stall: a server reports running_requests > 0 with
    # decode_tokens_per_sec == 0 for this many consecutive scrapes
    decode_stall_scrapes: int = 3
    # queue-wait breach: fleet queue_wait p95 over the span window
    queue_wait_p95_s: float = 30.0
    # accept-rate collapse: spec is enabled somewhere but the fleet
    # accept rate sits below this floor (after min_draft_tokens drafted)
    accept_rate_floor: float = 0.05
    min_draft_tokens: int = 256
    # staleness runaway: max staleness-at-consumption in the lineage
    # ledger exceeds this many versions
    staleness_max: int = 8
    # goodput collapse (r11): the fleet-mean pause+idle wall fraction
    # (from the engines' goodput ledgers) runs away from the run's own
    # baseline — the first `goodput_baseline_sweeps` observations set
    # the manifest baseline; the anomaly fires when the current value
    # exceeds baseline + margin AND the absolute floor
    goodput_baseline_sweeps: int = 3
    goodput_collapse_margin: float = 0.25
    goodput_collapse_floor: float = 0.5
    # consolidated hub endpoint (serve() binds here; port 0 = auto)
    host: str = "127.0.0.1"
    port: int = 0


@dataclasses.dataclass
class TrafficConfig:
    """SLO-aware traffic plane (router admission + server shedding +
    fleet autoscaling). Two request classes exist: ``interactive``
    (latency-sensitive — eval sweeps, agentic sessions driven by a live
    caller) and ``bulk`` (throughput work — GRPO training rollouts).
    Workflows stamp the class into ``ModelRequest.metadata["priority"]``;
    anything unstamped is bulk. Under contention the plane sheds or
    preempts BULK first, never interactive: the router answers
    ``429 + Retry-After`` (which utils/http honors as backoff, not
    failure), the server's bounded admission queue sheds overflow, and
    the engine preempts a bulk request when an interactive one would
    miss its soft deadline (the preempted rollout resumes via the prefix
    cache — zero lost work). The autoscaler grows/drains the fleet from
    observed queue backlog and KV utilization inside
    ``[min_servers, max_servers]`` with hysteresis."""

    # default tenant label stamped on requests from this client when the
    # workflow doesn't carry one (per-tenant fairness needs SOME key)
    tenant: str = "default"
    # weighted fairness between classes while the fleet is contended:
    # bulk may hold at most bulk_weight/(bulk_weight+interactive_weight)
    # of contended in-flight capacity when interactive traffic is
    # present (work-conserving: with no interactive in flight, bulk
    # takes everything; bulk is also never starved below ONE in-flight
    # request, since small counts round the share to zero)
    interactive_weight: int = 4
    bulk_weight: int = 1
    # per-tenant in-flight cap at the router (0 = uncapped): one tenant
    # flooding the fleet cannot starve the rest regardless of class
    max_inflight_per_tenant: int = 0
    # router-side overload shed: when the fleet's summed queued_requests
    # (from /health probes) reaches this depth, new BULK schedules are
    # shed with 429 + Retry-After until the backlog drains (0 disables)
    shed_queue_depth: int = 0
    # Retry-After seconds attached to router 429s
    retry_after_s: float = 1.0
    # router-side in-flight ledger entries expire after this long
    # without a /finish_request (crashed clients must not leak tenant
    # capacity forever)
    inflight_ttl_s: float = 600.0
    # --- FleetMonitor-driven autoscaler (inference/fleet.FleetAutoscaler) ---
    autoscale: bool = False
    min_servers: int = 1
    max_servers: int = 4
    # evaluation period of the control loop
    autoscale_interval_s: float = 5.0
    # scale up when queued-per-server exceeds this, or KV utilization
    # exceeds up_kv_util, or queue-wait p95 (when a telemetry rollup is
    # wired) exceeds up_queue_wait_s
    up_queued_per_server: float = 4.0
    up_kv_util: float = 0.9
    up_queue_wait_s: float = 10.0
    # scale down only when the fleet is quiet: zero queued and KV
    # utilization below this on every server
    down_kv_util: float = 0.3
    # hysteresis: consecutive evaluations the condition must hold
    up_consecutive: int = 2
    down_consecutive: int = 6
    # minimum seconds between scaling actions (either direction)
    cooldown_s: float = 30.0
    # cross-server prefix shipping (r16): when a qid's affine server
    # dies or is rebalanced away, attach the previous owner's address
    # to the fresh assignment (kv_ship_from) so the replacement server
    # fetches the session's committed prefix over /kv_export instead of
    # re-prefilling it. Requires --kv-ship on the target servers.
    kv_ship: bool = False
    # multi-policy canary routing (r19): per-line canary splits the
    # router resolves BEFORE scheduling, grammar
    # "name=STABLE[:CANARY:FRACTION][,name=...]" (e.g.
    # "actor=12:13:0.1,opponent=7" routes 10% of actor traffic to v13).
    # Empty = requests pass their policy handle through unresolved and
    # the server's registry split applies instead.
    policy_split: str = ""


@dataclasses.dataclass
class FleetConfig:
    """Rollout-fleet resilience plane (inference/fleet.py `FleetMonitor`):
    per-server health state machine (HEALTHY → SUSPECT → DEAD →
    RECOVERING), circuit breaker with half-open probes, graceful drain,
    and dynamic membership via the name_resolve gen_servers subtree.
    `engine/remote.py` consults it for failover-aware generation: on a
    connect failure / timeout / exhausted 5xx retries the in-flight
    request migrates to a healthy server and RESUMES from its
    accumulated tokens (token-exact, courtesy of the interruptible
    suffix-resume loop)."""

    # start the background prober/membership thread (passive failure
    # reports and failover still work when disabled)
    enabled: bool = True
    probe_interval_s: float = 2.0
    probe_timeout_s: float = 2.0
    # consecutive failures (probe or passive report) HEALTHY → SUSPECT
    suspect_threshold: int = 1
    # consecutive failures → DEAD (circuit opens; affinity evicted)
    dead_threshold: int = 3
    # consecutive half-open probe successes RECOVERING → HEALTHY
    recover_threshold: int = 2
    # DEAD servers are probed at most this often (the half-open window)
    halfopen_interval_s: float = 5.0
    # follow name_resolve gen_servers registrations live (only applies
    # when the fleet was DISCOVERED there — explicit addrs stay static)
    watch_membership: bool = True
    membership_poll_s: float = 2.0
    # per-request bound on server hops before the failure propagates
    max_failovers_per_request: int = 8


@dataclasses.dataclass
class EnvServiceConfig:
    """Environment service plane (env/service.py): sessionful env workers
    behind HTTP, health-probed/circuit-broken by the same FleetMonitor
    machinery as the generation fleet, with client-side failover. A
    ``RemoteEnv`` journals ``(reset_kwargs, action log)`` per session and,
    when a worker dies mid-episode, deterministically replays the journal
    onto a healthy worker (envs declare ``replay_safe``; non-replayable
    envs surface :class:`EnvSessionLostError` into the executor's episode
    retry/quarantine path instead of hanging the rollout thread)."""

    enabled: bool = False
    # workers the launcher spawns (python -m areal_tpu.env.service)
    n_workers: int = 1
    # env served by each worker: "module:attr" where attr is a zero-arg
    # factory (or Env subclass) producing one Env instance per session,
    # e.g. "areal_tpu.env.service:countdown_env"
    env_spec: str = ""
    host: str = "127.0.0.1"
    # concurrent sessions one worker admits before /reset answers 429
    max_sessions: int = 512
    # idle seconds before a worker expires a leaked session (crashed
    # client, failed best-effort close); <= 0 disables the sweeper
    session_ttl_s: float = 3600.0
    # --- client-side call bounds (RemoteEnv) ---
    reset_timeout_s: float = 30.0
    call_timeout_s: float = 30.0
    # transient-retry budget per worker per call (utils/http policy:
    # connect/timeout/5xx retry with jittered backoff; 4xx never retry)
    call_retries: int = 3
    # first transient-retry backoff, doubled per attempt (jittered)
    retry_delay_s: float = 0.5
    # worker hops one session may make before the failure propagates
    max_failovers: int = 4
    # compare replayed (observation, reward, done) against the journal
    # and fail the session on divergence — a worker pair that disagrees
    # is a determinism bug, not a resumable state
    verify_replay: bool = True
    # --- workflow-side tool bound (satellite: bounded in-process tools;
    # a timeout/exception becomes an error observation, not a crash) ---
    tool_timeout_s: float = 30.0
    # env workers the local launcher will respawn after a crash before
    # giving up (replacements re-register; membership finds them)
    max_worker_respawns: int = 8
    # health/circuit parameters for the env fleet monitor
    fleet: "FleetConfig" = dataclasses.field(
        default_factory=lambda: FleetConfig()
    )


@dataclasses.dataclass
class DurabilityConfig:
    """Training-loop durability plane (api/workflow_api.py
    `WorkflowExecutor`): a flaky reward/env call must not silently drop a
    sample forever, a poison sample must not burn retry budget forever,
    and a dead fleet must produce a clean error in bounded time instead
    of an infinite 1-s-timeout loop. Retry/backoff mirrors the
    utils/http.py policy shape (exponential, bounded jitter)."""

    # additional attempts after the first failure before the sample is
    # quarantined (0 = fail-fast quarantine, matching the old behavior of
    # dropping on first exception — but visibly)
    max_episode_retries: int = 2
    retry_delay: float = 0.5  # first backoff, doubled per attempt
    max_retry_delay: float = 30.0
    retry_jitter: float = 0.5  # uniform extra in [0, jitter*delay)
    # sliding window of episode-attempt outcomes driving the DEGRADED
    # state: when at least half the window is populated and the failure
    # fraction reaches `degraded_threshold`, the executor flips DEGRADED
    # (gauge + log) instead of silently shrinking throughput
    failure_window: int = 64
    degraded_threshold: float = 0.5
    # hard deadline for one prepare_batch() call; None = request_timeout
    prepare_batch_timeout: Optional[float] = None
    # with zero accepted progress for this long, prepare_batch consults
    # the engine's FleetMonitor — a fully-dead fleet raises immediately
    # rather than burning the rest of the deadline
    health_probe_after: float = 30.0


@dataclasses.dataclass
class ProfilingConfig:
    """jax-profiler trace capture for selected steps (reference
    model_worker.py:829-910 per-MFC torch profiler)."""

    enabled: bool = False
    # 0-based global step numbers to trace (empty + enabled = trace the
    # first step)
    steps: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class SaverConfig:
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = "/tmp/areal_tpu"
    freq_epochs: Optional[int] = None
    freq_steps: Optional[int] = None
    freq_secs: Optional[int] = None


@dataclasses.dataclass
class EvaluatorConfig:
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = "/tmp/areal_tpu"
    freq_epochs: Optional[int] = None
    freq_steps: Optional[int] = None
    freq_secs: Optional[int] = None


@dataclasses.dataclass
class RecoverConfig:
    mode: str = "disabled"  # disabled | auto | fault | resume
    retries: int = 3
    freq_epochs: Optional[int] = None
    freq_steps: Optional[int] = None
    freq_secs: Optional[int] = 600
    # committed recover checkpoints retained (recover/step_<g>/ dirs with
    # a COMMIT marker); older ones are GC'd after each successful dump
    keep_last: int = 2


@dataclasses.dataclass
class NameResolveConfig:
    type: str = "nfs"  # memory | nfs | kv
    nfs_record_root: str = "/tmp/areal_tpu/name_resolve"
    # kv backend rendezvous address (utils/kv_server.py), host:port
    kv_address: str = ""


@dataclasses.dataclass
class ClusterSpecConfig:
    name_resolve: NameResolveConfig = dataclasses.field(default_factory=NameResolveConfig)
    cluster_name: str = "local"
    fileroot: str = "/tmp/areal_tpu"
    n_devices_per_node: int = 8


@dataclasses.dataclass
class DatasetConfig:
    path: str = ""
    type: str = "gsm8k"
    batch_size: int = 8
    shuffle: bool = True
    max_length: Optional[int] = None
    drop_last: bool = True


@dataclasses.dataclass
class LauncherConfig:
    inference_server_cpus_per_task: int = 4
    inference_server_mem: int = 32768
    trainer_cpus_per_task: int = 4
    trainer_mem: int = 32768
    # >1 spawns that many trainer processes joined into one
    # jax.distributed world (multi-host SPMD; on TPU pods the per-host
    # runtime provides this instead)
    trainer_processes: int = 1


@dataclasses.dataclass
class SelfPlayConfig:
    """Self-play episode plane (workflow/selfplay.py): multi-agent
    episodes over one shared transcript, shipped as the countdown
    proposer/solver workload. Off by default — with ``enabled=False``
    the engine and workflow paths are a strict no-op. Every field here
    is machine-checked against workflow/selfplay.py by arealint ARL002
    (a field the workflow never reads is a silent default)."""

    enabled: bool = False
    # named policy handles (r19) for the two sides; "" rides the default
    # line. Different handles play different checkpoints on one engine
    # (e.g. "proposer@stable" vs "solver@canary").
    proposer_policy: str = ""
    solver_policy: str = ""
    # which sides export training rows; an untrained side is a frozen
    # opponent contributing only loss-masked context tokens
    train_proposer: bool = True
    train_solver: bool = True
    # traffic class for UNTRAINED (opponent) sides: interactive gives
    # opponent turns the bounded TTFT of PR 10/15 inside bulk rollouts
    # (the opponent is on the episode's critical path); trained sides
    # always ride bulk
    opponent_priority: str = "interactive"
    # proposer reward mapping: "banded" (difficulty band of the accepted
    # instance) or "zero_sum" (1 - solver reward)
    reward_mode: str = "banded"
    # reward discount across an agent's own turns (export_completions)
    turn_discount: float = 0.9
    # per-side turn budgets within one episode
    max_propose_rounds: int = 3
    max_solver_rounds: int = 4
    # proposer instance-schema bounds (env/selfplay.py grader families)
    min_numbers: int = 3
    max_numbers: int = 4
    max_target: int = 1000


# --------------------------------------------------------------------------
# Experiments
# --------------------------------------------------------------------------
@dataclasses.dataclass
class BaseExperimentConfig:
    experiment_name: str = "experiment"
    trial_name: str = "trial"
    cluster: ClusterSpecConfig = dataclasses.field(default_factory=ClusterSpecConfig)
    allocation_mode: str = ""
    seed: int = 1
    total_train_epochs: int = 1
    total_train_steps: Optional[int] = None
    tokenizer_path: str = ""
    train_dataset: DatasetConfig = dataclasses.field(default_factory=DatasetConfig)
    valid_dataset: Optional[DatasetConfig] = None
    saver: SaverConfig = dataclasses.field(default_factory=SaverConfig)
    checkpointer: SaverConfig = dataclasses.field(default_factory=SaverConfig)
    evaluator: EvaluatorConfig = dataclasses.field(default_factory=EvaluatorConfig)
    recover: RecoverConfig = dataclasses.field(default_factory=RecoverConfig)
    launcher: LauncherConfig = dataclasses.field(default_factory=LauncherConfig)
    # trainer → generation-server weight path: "disk" (HF checkpoint +
    # reload) or "device" (host-staged chunked transfer, no disk —
    # reference NCCL-broadcast analog). Colocated runs always use the
    # in-memory device path regardless.
    weight_update_mode: str = "disk"
    profiling: ProfilingConfig = dataclasses.field(
        default_factory=ProfilingConfig
    )


@dataclasses.dataclass
class SFTConfig(BaseExperimentConfig):
    model: TrainEngineConfig = dataclasses.field(default_factory=TrainEngineConfig)


@dataclasses.dataclass
class GRPOConfig(BaseExperimentConfig):
    async_training: bool = True
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    rollout: InferenceEngineConfig = dataclasses.field(default_factory=InferenceEngineConfig)
    server: JaxGenConfig = dataclasses.field(default_factory=JaxGenConfig)
    # environment service plane (env/service.py): remote sessionful env
    # workers with replay-based failover for agentic rollouts
    env_service: EnvServiceConfig = dataclasses.field(
        default_factory=EnvServiceConfig
    )
    actor: PPOActorConfig = dataclasses.field(default_factory=PPOActorConfig)
    ref: Optional[PPOActorConfig] = None
    # self-play episode plane (workflow/selfplay.py): off = strict no-op
    selfplay: SelfPlayConfig = dataclasses.field(
        default_factory=SelfPlayConfig
    )


# --------------------------------------------------------------------------
# Loading / merging
# --------------------------------------------------------------------------
def _is_optional(tp) -> Tuple[bool, Any]:
    if typing.get_origin(tp) is typing.Union:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return True, args[0]
    return False, tp


def from_dict(cls: Type[T], data: Dict[str, Any]) -> T:
    """Recursively build a dataclass from a nested dict."""
    if data is None:
        data = {}
    if not dataclasses.is_dataclass(cls):
        raise TypeError(f"{cls} is not a dataclass")
    kwargs = {}
    fields = {f.name: f for f in dataclasses.fields(cls)}
    for key, value in data.items():
        if key not in fields:
            raise ValueError(f"unknown config key {key!r} for {cls.__name__}")
        ftype = fields[key].type
        if isinstance(ftype, str):
            ftype = typing.get_type_hints(cls)[key]
        _, inner = _is_optional(ftype)
        if dataclasses.is_dataclass(inner) and isinstance(value, dict):
            kwargs[key] = from_dict(inner, value)
        else:
            kwargs[key] = value
    return cls(**kwargs)


def to_dict(obj) -> Dict[str, Any]:
    return dataclasses.asdict(obj)


def _coerce(existing: Any, raw: str) -> Any:
    s = raw.strip()
    low = s.lower()
    if low in ("null", "none"):
        return None
    if low in ("true", "false"):
        return low == "true"
    if isinstance(existing, bool):
        return low in ("true", "1", "yes")
    for caster in (int, float):
        try:
            return caster(s)
        except ValueError:
            pass
    if s.startswith("[") or s.startswith("{"):
        return yaml.safe_load(s)
    return s


def apply_override(obj: Any, dotted: str, raw_value: str) -> None:
    """Apply ``a.b.c=value`` onto a dataclass tree in place-ish (rebuilds
    leaves as needed; dataclasses here are mutable so set directly)."""
    parts = dotted.split(".")
    target = obj
    for p in parts[:-1]:
        if not hasattr(target, p):
            raise ValueError(f"unknown config key {dotted!r}")
        nxt = getattr(target, p)
        if nxt is None:
            # instantiate Optional[dataclass] nodes on demand
            hints = typing.get_type_hints(type(target))
            _, inner = _is_optional(hints[p])
            if dataclasses.is_dataclass(inner):
                nxt = inner()
                setattr(target, p, nxt)
            else:
                raise ValueError(f"cannot descend into None field {p!r}")
        target = nxt
    leaf = parts[-1]
    if not hasattr(target, leaf):
        raise ValueError(f"unknown config key {dotted!r}")
    setattr(target, leaf, _coerce(getattr(target, leaf), raw_value))


def load_expr_config(argv: List[str], config_cls: Type[T]) -> Tuple[T, str]:
    """Parse ``--config file.yaml key=value ...`` into `config_cls`
    (reference cli_args.py:922 `load_expr_config`). Returns (config, path)."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default=None)
    args, overrides = parser.parse_known_args(argv)
    data = {}
    if args.config:
        with open(args.config) as f:
            data = yaml.safe_load(f) or {}
    cfg = from_dict(config_cls, data)
    for ov in overrides:
        if "=" not in ov:
            raise ValueError(f"override {ov!r} must look like key=value")
        key, value = ov.split("=", 1)
        apply_override(cfg, key, value)
    _propagate_names(cfg)
    return cfg, args.config or ""


def _propagate_names(cfg) -> None:
    """Copy experiment/trial names into sub-configs that carry them
    (the reference does this in each entry point)."""
    exp = getattr(cfg, "experiment_name", None)
    trial = getattr(cfg, "trial_name", None)
    fileroot = None
    cluster = getattr(cfg, "cluster", None)
    if cluster is not None:
        fileroot = cluster.fileroot
    if not exp:
        return
    for f in dataclasses.fields(cfg):
        sub = getattr(cfg, f.name)
        if dataclasses.is_dataclass(sub) and not isinstance(sub, type):
            if hasattr(sub, "experiment_name") and not sub.experiment_name:
                sub.experiment_name = exp
            if hasattr(sub, "trial_name") and not sub.trial_name:
                sub.trial_name = trial
            if fileroot and hasattr(sub, "fileroot"):
                sub.fileroot = fileroot
