"""SPMD train engine: sharded train state + jitted update on a device mesh.

Role of reference areal/engine/fsdp_engine.py + base_hf_engine.py, re-designed
TPU-first. Where the reference composes FSDP2 module wrapping + DTensor TP
plans + NCCL process groups, here ONE jitted train step over a
(data, fsdp, seq, tensor) mesh does everything: params carry NamedShardings
derived from logical-axis rules, XLA inserts the collectives (all-gather for
fsdp params, psum for grads — the ZeRO-3 schedule falls out of sharding
propagation), and microbatch gradient accumulation happens on device.
The device programs themselves are built in ``engine/programs.py``; this
class keeps lifecycle, the cache of jitted programs and the host path.

Contracts:
- ``loss_fn(logits, arrays) -> (loss, stats_dict)`` — pure, jit-traced.
  ``arrays`` holds tokens/segment_ids/positions plus packed per_token/per_seq
  aux data ("t_" / "s_" key prefixes).
- ``loss_weight_fn(arrays) -> scalar`` — each microbatch's contribution
  weight (e.g. valid token count); total is summed host-side so microbatch
  grads combine exactly as one big batch would (reference
  base_hf_engine.py:423-486 train_batch).
"""

import dataclasses
import functools
import os
import time
import urllib.request
from typing import (
    Any, Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.api.cli_args import TrainEngineConfig
from areal_tpu.api.engine_api import TrainEngine
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta, WeightUpdateMeta
from areal_tpu.engine import programs
from areal_tpu.models import hf_io, transformer
from areal_tpu.models.config import ModelConfig, load_hf_config
from areal_tpu.models.transformer import (
    count_params,
    init_params,
    param_logical_axes,
)
from areal_tpu.ops import flash as flash_ops
from areal_tpu.ops.functional import (
    slot_aligned_logprobs,
    target_aligned_logprobs,
)
from areal_tpu.ops.ring_attention import attention_fn
from areal_tpu.parallel import distributed as distributed_lib
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.parallel import sharding as sharding_lib
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import goodput, stats_tracker, tracing
from areal_tpu.utils import logging as logging_util
from areal_tpu.utils.data import Batch

logger = logging_util.getLogger("SPMDTrainEngine")

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def _schedule_shape(opt, total_steps: int) -> Tuple[int, int, float]:
    """(warm-up steps, steps of the main schedule, its last value)."""
    # proportion 0 means NO warmup: the first step must run at full lr
    # (max(1, ...) here made step 0 a silent no-op update)
    warmup = int(opt.warmup_steps_proportion * total_steps)
    if opt.warmup_steps_proportion > 0:
        warmup = max(1, warmup)
    return warmup, max(1, total_steps - warmup), opt.lr * opt.min_lr_ratio


def _lr_schedule(cfg, total_steps: int) -> optax.Schedule:
    opt = cfg.optimizer
    warmup, main_steps, end = _schedule_shape(opt, total_steps)
    if opt.lr_scheduler_type == "cosine":
        main = optax.cosine_decay_schedule(
            opt.lr, main_steps, alpha=opt.min_lr_ratio
        )
    elif opt.lr_scheduler_type == "linear":
        main = optax.linear_schedule(opt.lr, end, main_steps)
    else:
        main = optax.constant_schedule(opt.lr)
    if warmup == 0:
        return main
    return optax.join_schedules(
        [optax.linear_schedule(0.0, opt.lr, warmup), main], [warmup]
    )


def _host_lr(cfg, total_steps: int) -> Callable[[int], float]:
    """``_lr_schedule``'s value at a step, computed on the host in float32
    as optax computes it on the device: what a step REPORTS as its learning
    rate (the optimizer reads its own count inside ``apply_step``). Asking
    the optax schedule from Python runs eager device programs and waits
    for the device's queue behind them; a test holds the two equal."""
    opt = cfg.optimizer
    warmup, main_steps, end = _schedule_shape(opt, total_steps)
    f32 = np.float32

    def linear(init, last, steps, count):
        frac = f32(1) - f32(min(max(count, 0), steps)) / f32(steps)
        return f32(init - last) * frac + f32(last)

    def main(count):
        if opt.lr_scheduler_type == "cosine":
            cos = f32(0.5) * (f32(1) + np.cos(
                f32(np.pi) * f32(min(count, main_steps)) / f32(main_steps)
            ))
            alpha = opt.min_lr_ratio
            return f32(opt.lr) * (f32(1 - alpha) * cos + f32(alpha))
        if opt.lr_scheduler_type == "linear":
            return linear(opt.lr, end, main_steps, count)
        return opt.lr  # optax hands back the Python number it was given

    def lr_at(step: int) -> float:
        if step < warmup:
            return float(linear(0.0, opt.lr, warmup, step))
        value = main(step - warmup)
        # joined to a warm-up, optax selects between the two in float32
        return float(f32(value) if warmup else value)

    return lr_at


@dataclasses.dataclass
class _PendingUpdate:
    """A minibatch update that is dispatched and not yet fetched: what
    ``_resolve_minibatch`` needs to turn the device's scalars into the
    update's stats."""

    step: int
    lr: float
    # ok, grad_norm, total_weight, then a list a microbatch of losses,
    # weights and stats dicts: device arrays, fetched together
    scalars: Tuple[Any, ...]


def _one_behind(dispatched: Iterable) -> Iterator[Tuple[Any, bool]]:
    """Each item of ``dispatched`` after the NEXT one has been made, with
    whether a next one was: the one way the host path fetches a result, so
    that between two dispatches it never waits for the device. Making an
    item queues device work; one item is in flight beside the one being
    made, no more, which bounds what is queued."""
    items = iter(dispatched)
    for held in items:
        for made in items:
            yield held, True
            held = made
        yield held, False


def _make_optimizer(opt, schedule) -> optax.GradientTransformation:
    """OptimizerConfig.type dispatch (reference cli_args.py:140 `type`):
    adamw (default; f32 moments = the reference's mixed-precision Adam) or
    sgd (stateless — lets model sizes whose Adam moments exceed HBM, e.g.
    the 1.5B bench anchor on one 16 GB chip, still take real steps)."""
    if opt.type == "sgd":
        return optax.chain(
            optax.clip_by_global_norm(opt.gradient_clipping),
            # decay is stateless — dropping Adam's moments to fit HBM is
            # no reason to silently drop the configured regularizer
            optax.add_decayed_weights(opt.weight_decay),
            optax.sgd(learning_rate=schedule),
        )
    if opt.type != "adamw":
        raise ValueError(f"unknown optimizer type {opt.type!r}")
    return optax.chain(
        optax.clip_by_global_norm(opt.gradient_clipping),
        optax.adamw(
            learning_rate=schedule,
            b1=opt.beta1,
            b2=opt.beta2,
            eps=opt.eps,
            weight_decay=opt.weight_decay,
            mu_dtype=jnp.float32,
        ),
    )


class SPMDTrainEngine(TrainEngine):
    """The TPU analog of FSDPEngine: one SPMD program over one mesh."""

    def __init__(self, config: TrainEngineConfig):
        self.config = config
        self.model_config: Optional[ModelConfig] = None
        self.mesh = None
        self.params = None
        self.opt_state = None
        self.optimizer = None
        self.lr_schedule = None
        self._host_lr: Optional[Callable[[int], float]] = None
        self.step_count = 0
        # cumulative tokens dispatched (counters()), counted where every
        # microbatch is packed, from its segment ids; initialize adds what
        # the model's mechanisms count over the train microbatches
        self._counters = {"real_tokens": 0, "padded_tokens": 0}
        # results fetched from the device (a train minibatch's scalars, a
        # recompute microbatch's logprobs) and those of them fetched after
        # a later dispatch was queued (fetch_counters())
        self._fetches = {"fetches": 0, "fetches_behind": 0}
        self._version = 0
        self.compute_dtype = _DTYPES[config.dtype]
        self.param_dtype = _DTYPES[config.param_dtype]
        self._jit_cache: Dict[Any, Callable] = {}
        # the attention core in use: config.attn_impl with "auto" resolved at
        # initialize() against the backend, the mesh and the model ("flash",
        # "ring", "ulysses" or "xla"), and the largest splash block edge the
        # backend ran when that is "flash" on a TPU (None: kernel not in use)
        self.attn_impl: Optional[str] = None
        self.splash_block: Optional[int] = None
        # what a dispatch span says of the model's mechanisms beside
        # ``attn_impl`` (``transformer.span_attrs``) and, out of it, the
        # forms its ops resolved to in this process: the
        # experts' grouped matmul ("megablox" | "ragged_dot"), the learned
        # selection's passes, the delta rule's and its convolution's, the
        # selective scan's ("kernel" | "xla")
        self.moe_impl: Optional[str] = None
        self.dsa_select_impl: Optional[str] = None
        self.kda_impl: Optional[str] = None
        self.prep_impl: Optional[str] = None
        self.ssm_impl: Optional[str] = None
        self._span_attrs: Dict[str, Any] = {}
        self.initialized = False

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def initialize(
        self,
        ft_spec: Optional[FinetuneSpec] = None,
        model_config: Optional[ModelConfig] = None,
        seed: int = 0,
    ):
        cfg = self.config
        # every program this thread makes from here on is in the trainer's
        # compile log by name (init_params, the optimizer state, the sum of
        # a minibatch's weights, ...), with no line at its call site; the
        # two dispatch scopes of a step add their phase and shape
        goodput.set_thread_tracker(goodput.trainer_tracker(), phase="trainer")
        self.mesh = mesh_lib.make_mesh(cfg.parallel)
        if model_config is not None:
            self.model_config = model_config
        elif cfg.path:
            self.model_config = load_hf_config(cfg.path)
        else:
            raise ValueError("need config.path or explicit model_config")
        mc = self.model_config
        is_critic = bool(getattr(cfg, "is_critic", False))
        logical = param_logical_axes(mc, value_head=is_critic)
        self._param_shardings = sharding_lib.tree_shardings(self.mesh, logical)
        if cfg.path and not cfg.init_from_scratch:
            host_params = hf_io.load_params(cfg.path, mc, dtype=self.param_dtype)
            if is_critic:
                # fresh scalar head on top of the pretrained trunk
                # (reference critic init: actor trunk + new value head)
                import numpy as _np

                host_params["value_head"] = (
                    _np.asarray(
                        jax.random.normal(
                            jax.random.PRNGKey(seed + 101),
                            (mc.hidden_size, 1),
                        )
                    )
                    * 0.02
                ).astype(self.param_dtype)
                host_params.pop("lm_head", None)
            self.params = jax.tree_util.tree_map(
                lambda a, sh: distributed_lib.make_global_array(
                    np.asarray(a), sh
                ),
                host_params,
                self._param_shardings,
            )
            del host_params
        else:
            # drawn where it lives: every leaf comes out of one program in
            # its own sharding, so no device (and no host) ever holds the
            # whole model — a from-scratch model may exist only across chips
            draw = functools.partial(
                init_params, mc, dtype=self.param_dtype, value_head=is_critic
            )
            # a partial has no name of its own, and the module would be
            # jit(<unknown>) in the compile log and in a profile
            draw.__name__ = init_params.__name__
            self.params = jax.jit(
                draw, out_shardings=self._param_shardings
            )(jax.random.PRNGKey(seed))
        if cfg.optimizer is not None:
            total_steps = ft_spec.total_train_steps if ft_spec else 10000
            self.lr_schedule = _lr_schedule(cfg, total_steps)
            self._host_lr = _host_lr(cfg, total_steps)
            self.optimizer = _make_optimizer(
                cfg.optimizer, self.lr_schedule
            )
            self.opt_state = self._init_opt_state()
        self.attn_impl = flash_ops.resolve_attn_impl(
            cfg.attn_impl, cfg.parallel.seq_parallel_size,
            mc.attn_head_dims, *transformer.seq_parallel_refusal(mc),
        )
        if self.attn_impl == "flash" and jax.default_backend() != "cpu":
            # what the scoped-VMEM limit of this process allows, once
            self.splash_block = flash_ops.probe_block_size()
        if self.attn_impl == "flash":
            self._counters.update(attn_tiles=0, attn_tiles_kept=0)
        # what the model's mechanisms declare (``transformer.MECHANISMS``)
        self._counters.update(dict.fromkeys(self._count_names(), 0))
        if mc.block_diffusion is not None:  # counted where rows are packed
            self._counters.update(bd_sequence_tokens=0, bd_stream_slots=0)
        self._span_attrs = transformer.span_attrs(mc, self._experts_split())
        (self.moe_impl, self.dsa_select_impl, self.kda_impl,
         self.prep_impl, self.ssm_impl) = map(
            self._span_attrs.get,
            ("moe_impl", "dsa_select_impl", "kda_impl", "prep_impl",
             "ssm_impl"),
        )
        n = count_params(self.params)
        logger.info(
            f"initialized {mc.family} model: {n/1e6:.1f}M params on mesh "
            f"{dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}; "
            f"attention: attn_impl={cfg.attn_impl!r} resolved to "
            f"{self.attn_impl!r} on {jax.default_backend()}"
            + (
                f", splash block edge <= {self.splash_block}"
                if self.splash_block else ""
            )
            + (f"; experts: {self.moe_impl}" if self.moe_impl else "")
        )
        self.initialized = True
        return self

    def rebuild_optimizer(
        self, opt_config, total_steps: int = 10000
    ) -> None:
        """Swap the optimizer (fresh state) without touching params —
        e.g. an RL phase following SFT needs a far smaller step size.
        Clears the jitted-program cache (apply programs close over the
        optimizer)."""
        cfg = self.config
        old_opt = cfg.optimizer
        cfg.optimizer = opt_config
        try:
            self.lr_schedule = _lr_schedule(cfg, total_steps)
            self._host_lr = _host_lr(cfg, total_steps)
        finally:
            cfg.optimizer = old_opt
        self.optimizer = _make_optimizer(opt_config, self.lr_schedule)
        self.opt_state = self._init_opt_state()
        self._jit_cache.clear()

    def _init_opt_state(self):
        """Fresh optimizer state with every moment laid out like its param
        (the ZeRO "shard the optimizer state" property), scalars replicated.

        The layout is STATED, not left to sharding propagation: the moments
        are zeros with no data dependence on the params, so a bare
        ``jit(optimizer.init)`` has nothing to propagate from and puts them
        whole on the first device — on a four-chip host, 2x the model on
        device 0 until the first update re-lays them out (and no first
        update for a model that needs the sharding to fit)."""
        pdef = jax.tree_util.tree_structure(self.params)

        def like_params(node) -> bool:
            return jax.tree_util.tree_structure(node) == pdef

        rep = sharding_lib.replicated(self.mesh)
        out_shardings = jax.tree_util.tree_map(
            lambda n: self._param_shardings if like_params(n) else rep,
            jax.eval_shape(self.optimizer.init, self.params),
            is_leaf=like_params,
        )
        return jax.jit(self.optimizer.init, out_shardings=out_shardings)(
            self.params
        )

    def destroy(self):
        # what this thread makes from here on is no trainer program
        goodput.set_thread_tracker(None)
        self.params = None
        self.opt_state = None
        self._jit_cache.clear()
        self.initialized = False

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------
    @property
    def data_parallel_rank(self) -> int:
        return jax.process_index()

    @property
    def data_parallel_world_size(self) -> int:
        return jax.process_count()

    def counters(self) -> Dict[str, int]:
        """Cumulative counts since construction (as
        ``GenerationEngine.metrics()`` is for the engine): tokens
        dispatched, real and padded, over recompute and train microbatches;
        what the model's mechanisms count over the train microbatches
        (``models.transformer.count_names``: which, and what each is);
        where ``attn_impl`` is ``flash``, ``attn_tiles`` and
        ``attn_tiles_kept`` (the tiles on and under the diagonal of the rows
        dispatched, recompute and train alike, at the edge the
        full-attention layers' kernel walks them, and those of them it
        computes: ``ops.flash.tile_counts``, a row each and not a layer);
        for a block-diffusion policy, whose ``real_tokens`` are SLOTS,
        ``bd_sequence_tokens`` (the sequences' own tokens) and
        ``bd_stream_slots`` (the noisy streams' slots), recompute and train
        alike as ``real_tokens`` is.
        Their per-step increments are the stats plane's ``spmd/<name>``."""
        return dict(self._counters)

    def fetch_counters(self) -> Dict[str, int]:
        """Cumulative since construction: ``fetches``, the results the host
        fetched from the device (one a train minibatch, one a recompute
        microbatch), and ``fetches_behind``, those it fetched after queueing
        a later dispatch, so with the device at work through the wait: 3 of
        a GRPO step's 4 minibatches, all but the last microbatch of a
        recompute pass. A share near 0 says the host waits between
        dispatches again. (Beside ``counters()`` and not in it: a test of
        the benchmark's holds that one's keys, and the benchmark's files
        change in a PR of their own.)"""
        return dict(self._fetches)

    def get_version(self) -> int:
        return self._version

    def set_version(self, version: int):
        self._version = version

    # ------------------------------------------------------------------
    # Packing
    # ------------------------------------------------------------------
    def _dp_rows(self) -> int:
        p = self.config.parallel
        return (
            getattr(p, "dcn_data_parallel_size", 1)
            * getattr(p, "dcn_fsdp_parallel_size", 1)
            * p.data_parallel_size
            * p.fsdp_parallel_size
        )

    def _batch_sharding(self):
        return sharding_lib.batch_sharding(self.mesh)

    def _row_quantum(self) -> int:
        """The step of the ladder of row lengths: a row divides evenly
        across the seq axis at every rung."""
        return data_utils.ROW_QUANTUM * self.config.parallel.seq_parallel_size

    def _split(
        self, input_: Batch, backward: bool,
        rows: Optional[Sequence[int]] = None,
    ) -> data_utils.MicroBatchList:
        """A batch as microbatches of rows, the one split ``train_batch``,
        ``eval_batch`` and ``forward`` share: rows first
        (``data_utils.split_padded_batch_into_mb_list``). A train step runs
        each microbatch at its own row length: its minibatches end in a
        short microbatch every time, and a 4096-token row of ``grad_step``
        is 155 ms against 80 ms at 2048 (Qwen2-0.5B on a v5e). A pass with no
        ``backward`` runs a call's microbatches at ONE length, the longest's:
        its rows cost a third of that, it ends in one short microbatch among
        many, and a length of its own is one more program to compile and,
        from the cache, to trace and load (1.2 s; PERF.md, PR 30). With
        ``rows`` such a pass runs those sequences of ``input_`` only
        (``groups`` still index ``input_``) and keeps the WHOLE input's
        length: a subset whose own longest microbatch fits a shorter rung
        does not ask for a program the whole batch never built."""
        spec = dict(
            max_tokens_per_mb=self.config.mb_spec.max_tokens_per_mb,
            min_n_mbs=self.config.mb_spec.n_mbs,
            n_rows=self._dp_rows(), quantum=self._row_quantum(),
        )
        bd = self.model_config.block_diffusion
        if bd is not None and "denoise_state" not in input_:
            raise ValueError(
                f"family {self.model_config.family!r} generates by diffusion "
                "over blocks: its batches carry a per-token denoise_state "
                "(the state of the decoding trace in which each completion "
                "token was revealed, -1 on the prompt); without one the rows "
                "would run causally, which the model was never trained to"
            )
        bd_block = bd.block_length if bd is not None else 0
        mbs = data_utils.split_padded_batch_into_mb_list(
            input_ if rows is None else data_utils.batch_select(input_, rows),
            bd_block=bd_block, **spec,
        )
        if rows is not None:
            rows = np.asarray(rows, np.int64)
            mbs.groups = [rows[g].tolist() for g in mbs.groups]
            mbs.forward_indices = rows[mbs.forward_indices].tolist()
            whole = max(data_utils.split_lens(
                data_utils.row_lens_of(input_, bd_block), **spec
            )[1], default=0)
            if max(mbs.row_lens, default=0) > whole:
                # first-fit is not monotone; no cell's layout does this
                raise ValueError(
                    f"rows: a microbatch of {max(mbs.row_lens)}-token rows,"
                    f" the whole input's longest is {whole}"
                )
            mbs.row_lens = [whole] * len(mbs.mbs)
        elif not backward:
            mbs.row_lens = [max(mbs.row_lens, default=0)] * len(mbs.mbs)
        return mbs

    def _pack_for_device(
        self, mb: Batch, pad_to: Optional[int] = None
    ) -> Tuple[data_utils.PackedRows, Dict[str, jnp.ndarray]]:
        """One microbatch packed into rows and put on the device: the one
        place every dispatched token passes (logprob recompute and train
        alike), so the one place they are counted, real and padded.
        ``pad_to`` is the row length the split gave the microbatch; without
        it the pack finds the same length by the same rule."""
        with tracing.span("train.pack", step=self.step_count) as sp:
            bd = self.model_config.block_diffusion
            packed = data_utils.pack_batch_rows(
                mb, n_rows=self._dp_rows(), quantum=self._row_quantum(),
                pad_to=pad_to,
                bd=bd and (bd.block_length, bd.mask_token_id),
            )
            arrays: Dict[str, Any] = dict(
                tokens=packed.tokens,
                segment_ids=packed.segment_ids,
                positions=packed.positions,
            )
            for k, v in packed.per_token.items():
                arrays[f"t_{k}"] = v
            for k, v in packed.per_seq.items():
                arrays[f"s_{k}"] = v
            bsh = self._batch_sharding()
            rep = sharding_lib.replicated(self.mesh)
            shardings = {}
            for k, v in arrays.items():
                shardings[k] = bsh if (
                    v.ndim >= 2 and v.shape[:2] == packed.tokens.shape
                ) else (
                    NamedSharding(self.mesh, P(("data", "fsdp")))
                    if v.ndim >= 1 and v.shape[0] == packed.tokens.shape[0]
                    else rep
                )
            if jax.process_count() == 1:
                # ONE tree-wide transfer instead of a host round-trip per key
                dev = jax.device_put(
                    {k: np.asarray(v) for k, v in arrays.items()}, shardings
                )
            else:
                # multi-host: every process holds the identical full batch (the
                # DP-head broadcast guarantees it) and contributes only its
                # addressable shards to the global array
                dev = {
                    k: distributed_lib.make_global_array(
                        np.asarray(v), shardings[k]
                    )
                    for k, v in arrays.items()
                }
            real = int(np.count_nonzero(packed.segment_ids))  # 0 = padding
            padded = int(packed.segment_ids.size)
            self._counters["real_tokens"] += real
            self._counters["padded_tokens"] += padded
            sp.set(
                real_tokens=real, padded_tokens=padded, pad_to=packed.bucket
            )
            # per step through the stats plane (increments: the step's
            # export sums them over its recompute and train microbatches)
            stats_tracker.counter(**{
                "spmd/real_tokens": real, "spmd/padded_tokens": padded,
            })
            if bd is not None:
                # a block-diffusion policy's rows: ``real_tokens`` counts
                # SLOTS; the sequences' own tokens and the noisy streams'
                # slots are counted beside them, each once a pass of a
                # program as they are
                streams = packed.per_token["bd_stream"]
                counted = {
                    "bd_sequence_tokens": int(packed.seq_lens.sum()),
                    "bd_stream_slots": int(np.count_nonzero(streams)),
                }
                for k, n in counted.items():
                    self._counters[k] += n
                stats_tracker.counter(
                    **{f"spmd/{k}": n for k, n in counted.items()}
                )
                sp.set(stream_slots=counted["bd_stream_slots"])
        return packed, dev

    # ------------------------------------------------------------------
    # Train
    # ------------------------------------------------------------------
    def _count_attn_tiles(self, segment_ids: np.ndarray) -> Dict[str, int]:
        """A dispatched microbatch's rows as the full-attention layers'
        splash kernel walks them: counted (``counters()``, the stats plane)
        and returned as the dispatch span's attributes. The host knows the
        rows' borders when it packs them, so the count costs the device
        nothing; nothing where another attention runs."""
        if self.attn_impl != "flash":
            return {}
        tiles, kept = flash_ops.tile_counts(
            segment_ids, self.model_config.attn_head_dims[0],
            self.splash_block or 0, heads=self.model_config.num_heads,
        )
        counted = {"attn_tiles": tiles, "attn_tiles_kept": kept}
        for k, n in counted.items():
            self._counters[k] += n
        stats_tracker.counter(**{f"spmd/{k}": n for k, n in counted.items()})
        return counted

    @staticmethod
    def _bd_states(packed: data_utils.PackedRows) -> Dict[str, int]:
        """What a dispatch span says of a block-diffusion policy's rows: K,
        the most denoising states a sequence of them has."""
        streams = packed.per_token.get("bd_stream")
        return {} if streams is None else {
            "bd_states": int(streams.max(initial=0))
        }

    def _forward_values(self, _unused: int = 0) -> Dict[str, Any]:
        """What every forward program of ``engine/programs.py`` is built
        from: this engine's model, mesh, compute dtype and attention. (The
        argument is read by nothing: ``benchmark/kinds/train_steps_mla.py``
        still hands in the 0 that was "no packing window" until PR 56, and
        a program PR does not edit the benchmark's files.)"""
        return dict(
            model_config=self.model_config, mesh=self.mesh,
            compute_dtype=self.compute_dtype,
            attend_fn=attention_fn(
                self.attn_impl, self.mesh, block_cap=self.splash_block or 0,
                topk=self.model_config.dsa_topk,
            ),
        )

    def _program(self, key: tuple, build: Callable, **values) -> Callable:
        """The cached device program ``build`` makes for ``key``: what the
        caller handed in (loss, hook) and nothing else. jit retraces it a
        row length, and which kernel instance a row then runs is
        ``ops.flash.instance``'s to say from the row and the layer."""
        if key not in self._jit_cache:
            self._jit_cache[key] = build(**self._forward_values(), **values)
        return self._jit_cache[key]

    def _zero_grads(self):
        key = "zeros"
        if key not in self._jit_cache:
            def zero_grads(params):
                return jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )

            self._jit_cache[key] = jax.jit(
                zero_grads, out_shardings=self._param_shardings
            )
        return self._jit_cache[key](self.params)

    def train_batch(
        self,
        input_: Batch,
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> Dict[str, float]:
        return self.train_batches([input_], loss_fn, loss_weight_fn)[0]

    def train_batches(
        self,
        inputs: Iterable[Batch],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> List[Dict[str, float]]:
        """One optimizer step a minibatch of ``inputs``, in order, and each
        one's stats. ``inputs`` is taken lazily: minibatch k + 1 is drawn
        from it, split, packed and dispatched BEFORE minibatch k's scalars
        are fetched, so at a minibatch border the device has its next work
        queued while the host fetches and prepares (``_one_behind``). It
        returns when the last minibatch's scalars are on the host: no device
        work of the call is left queued."""
        if self.optimizer is None:
            raise RuntimeError("no optimizer configured")

        def dispatched():
            # ONE float32 accumulator a call: zeroed at its first minibatch,
            # then each apply_step hands it back zeroed for the next, so a
            # minibatch queued ahead allocates none beside the one still in
            # the queue; it goes when the last minibatch is dispatched, and
            # between calls the engine holds none (as before)
            grad_accum = None
            for input_ in inputs:
                pending, grad_accum = self._dispatch_minibatch(
                    input_, loss_fn, loss_weight_fn, grad_accum
                )
                yield pending

        return [
            self._resolve_minibatch(pending, behind)
            for pending, behind in _one_behind(dispatched())
        ]

    def _fetch(self, tree, behind: bool):
        """``tree``'s device arrays on the host, in one go (every leaf's
        copy is started before the first is waited for). ``behind``: a later
        dispatch is already queued, so the device works through the wait."""
        self._fetches["fetches"] += 1
        self._fetches["fetches_behind"] += int(behind)
        return jax.device_get(tree)

    def _dispatch_minibatch(
        self, input_: Batch, loss_fn: Callable, loss_weight_fn: Callable,
        grad_accum,
    ) -> Tuple[_PendingUpdate, Any]:
        """The first half of a minibatch's update: split, pack, every
        ``grad_step``, ``apply_step``, all queued and none waited for.
        ``grad_accum`` is the zeroed accumulator the minibatch before handed
        on (None: make one); the one ``apply_step`` hands back is returned
        beside the pending record."""
        step = self.step_count
        with tracing.span("train.minibatch", step=step):
            with tracing.span("train.split", step=step):
                mbs = self._split(input_, backward=True)
                if grad_accum is None:
                    grad_accum = self._zero_grads()
            losses, weights, all_stats = [], [], []
            # goodput attribution: host packing books data_h2d, the grad
            # dispatches book fwd_bwd (minus any compile, which the trainer
            # CompileTracker carves into the compile bucket with this
            # microbatch's shape signature), the apply and the scalar fetch
            # book optim. Where the device's time goes is the profile's to
            # say (programs grad_step / apply_step); these are the host's
            # intervals in it
            for mb, row_len in zip(mbs.mbs, mbs.row_lens):
                with goodput.trainer_bucket("data_h2d"):
                    packed, arrays = self._pack_for_device(mb, pad_to=row_len)
                grad_fn = self._program(
                    ("grad", loss_fn, loss_weight_fn),
                    programs.build_grad_step,
                    remat=self.config.gradient_checkpointing,
                    loss_fn=loss_fn, loss_weight_fn=loss_weight_fn,
                )
                with goodput.trainer_bucket("fwd_bwd"), tracing.span(
                    "train.grad_dispatch", step=step,
                    attn_impl=self.attn_impl,
                    **self._count_attn_tiles(packed.segment_ids),
                    **self._span_attrs, **self._bd_states(packed),
                    **transformer.grad_span_attrs(
                        self.model_config, self.attn_impl, row_len,
                        self.splash_block or 0,
                    ),
                ), goodput.dispatch_scope(
                    goodput.trainer_tracker(), "fwd_bwd", f"pad{row_len}"
                ):
                    grad_accum, loss, stats, w = grad_fn(
                        self.params, grad_accum, arrays
                    )
                losses.append(loss)
                weights.append(w)
                all_stats.append(stats)
            total_w = functools.reduce(lambda a, b: a + b, weights)
            if "apply" not in self._jit_cache:
                self._jit_cache["apply"] = programs.build_apply_step(
                    self.optimizer, self._param_shardings
                )
            with goodput.trainer_bucket("optim"), tracing.span(
                "train.apply_dispatch", step=step
            ), goodput.dispatch_scope(goodput.trainer_tracker(), "optim"):
                (
                    self.params, self.opt_state, grad_accum, grad_norm, ok,
                ) = self._jit_cache["apply"](
                    self.params, self.opt_state, grad_accum, total_w
                )
            self.step_count += 1
        return _PendingUpdate(
            step=step, lr=self._host_lr(step),
            scalars=(ok, grad_norm, total_w, losses, weights, all_stats),
        ), grad_accum

    def _resolve_minibatch(
        self, pending: _PendingUpdate, behind: bool
    ) -> Dict[str, float]:
        """The second half: the one fetch of every scalar the update
        produced (each separate ``float()`` would be a device round-trip of
        its own), and the host's arithmetic on them."""
        # the optim bucket takes the fetch as it takes the apply: a fetch
        # with nothing queued behind it is where the device's work lands on
        # the wall clock
        with goodput.trainer_bucket("optim"), tracing.span(
            "train.scalar_fetch", step=pending.step, behind=int(behind)
        ):
            ok, grad_norm, total_w, losses, weights, all_stats = self._fetch(
                pending.scalars, behind
            )
        # a stat is a count because the model names it as one; an int32 one
        # (a float32 cannot count a 16k-token row's pairs) is summed exactly
        count_keys = set(self._count_names())
        int_keys = [
            k for k in all_stats[0] if k in count_keys
            and np.issubdtype(np.asarray(all_stats[0][k]).dtype, np.integer)
        ]
        stat_keys = sorted(set(all_stats[0]) - set(int_keys))
        h_total_w = np.float32(total_w)
        h_losses = np.asarray(losses, np.float32)
        h_weights = np.asarray(weights, np.float32)
        h_stats = np.asarray(
            [[s[k] for k in stat_keys] for s in all_stats], np.float32
        )
        out = {
            "update_successful": float(ok),
            "grad_norm": float(grad_norm),
            "lr": pending.lr,
            "loss": float((h_losses * h_weights).sum() / h_total_w),
            "n_mbs": float(len(losses)),
        }
        for j, k in enumerate(stat_keys):
            # a count: summed, counted, and per step
            if k in count_keys:
                out[k] = float(h_stats[:, j].sum())
                self._counters[k] += int(out[k])
                stats_tracker.counter(**{f"spmd/{k}": int(out[k])})
            else:
                out[k] = float((h_stats[:, j] * h_weights).sum() / h_total_w)
        for k in int_keys:
            n = sum(int(np.asarray(s[k], np.int64).sum()) for s in all_stats)
            out[k] = float(n)
            self._counters[k] += n
            stats_tracker.counter(**{f"spmd/{k}": n})
        return out

    def eval_batch(
        self, input_: Batch, loss_fn: Callable, loss_weight_fn: Callable
    ) -> Dict[str, float]:
        mbs = self._split(input_, backward=False)
        losses, weights = [], []
        for mb, row_len in zip(mbs.mbs, mbs.row_lens):
            _, arrays = self._pack_for_device(mb, pad_to=row_len)
            eval_fn = self._program(
                ("eval", loss_fn, loss_weight_fn), programs.build_eval_step,
                loss_fn=loss_fn, loss_weight_fn=loss_weight_fn,
            )
            loss, stats, w = eval_fn(self.params, arrays)
            losses.append(loss)
            weights.append(w)
        blob = np.asarray(
            jnp.stack(
                [jnp.asarray(x, jnp.float32).reshape(()) for x in losses + weights]
            )
        )
        n = len(losses)
        return {
            "loss": float(
                (blob[:n] * blob[n:]).sum() / max(blob[n:].sum(), 1.0)
            )
        }

    # ------------------------------------------------------------------
    # Forward (inference over the train model, e.g. logprob recompute)
    # ------------------------------------------------------------------
    def forward(
        self,
        input_: Batch,
        post_hook: Optional[Callable] = None,
        rows: Optional[Sequence[int]] = None,
    ) -> np.ndarray:
        """Run the model over `input_` and return a padded [B, L] per-token
        array in the original order, where L is the input's padded width
        (reference base_hf_engine.py:525).

        `post_hook(logits, arrays) -> [R, T] array` must be jittable; default
        returns target-aligned logprobs. ``rows``: run these sequences of
        `input_` only (the others read 0 in the result), at the row length
        of the WHOLE input, so the pass runs programs a pass over all of it
        would have built.
        """
        step = self.step_count
        with tracing.span("train.logp_recompute", step=step):
            return self._forward(input_, post_hook, step, rows)

    def _forward(
        self, input_: Batch, post_hook: Optional[Callable], step: int,
        rows: Optional[Sequence[int]] = None,
    ) -> np.ndarray:
        mbs = self._split(input_, backward=False, rows=rows)
        # a block-diffusion policy's rows score a token at its own slot
        own = self.model_config.block_diffusion is not None
        hook = post_hook or (
            slot_aligned_logprobs if own else target_aligned_logprobs
        )

        def dispatched():
            for mb, row_len in zip(mbs.mbs, mbs.row_lens):
                packed, arrays = self._pack_for_device(mb, pad_to=row_len)
                logp_fn = self._program(
                    ("fwd", hook), programs.build_logp_forward, hook=hook
                )
                with tracing.span(
                    "train.logp_dispatch", step=step,
                    attn_impl=self.attn_impl,
                    **self._count_attn_tiles(packed.segment_ids),
                    **self._span_attrs, **self._bd_states(packed),
                ):
                    vals = logp_fn(self.params, arrays)
                yield packed, vals

        # microbatch k + 1 is packed and queued before k's result is
        # fetched and unpacked: the device computes through both
        outs = []
        for (packed, vals), behind in _one_behind(dispatched()):
            with tracing.span(
                "train.logp_fetch", step=step, behind=int(behind)
            ):
                vals = self._fetch(vals, behind)
            with tracing.span("train.unpack", step=step):
                outs.append(data_utils.unpack_rows_per_token(packed, vals))
        # scatter back to original order at the input's padded width
        bsz = data_utils.batch_size(input_)
        width = np.asarray(input_["attention_mask"]).shape[1]
        out = np.zeros((bsz, width) + outs[0].shape[2:], outs[0].dtype)
        for group, o in zip(mbs.groups, outs):
            out[np.asarray(group), : o.shape[1]] = o
        return out

    # ------------------------------------------------------------------
    # Save / load / weight push
    # ------------------------------------------------------------------
    def _experts_split(self) -> bool:
        """Whether the mesh splits the model's experts among its chips: the
        sparse layer's mesh form (``ops/moe.make_sharded_moe_ffn``) then runs
        a share a chip, and the model counts and says what a share does."""
        return self.model_config.is_moe and bool(
            sharding_lib.experts_split(self.mesh)
        )

    def _count_names(self) -> Dict[str, str]:
        return transformer.count_names(
            self.model_config, self._experts_split()
        )

    def _host_tree(self, tree, dtype=None):
        """Gather a (possibly cross-process-sharded) pytree to host.

        Multi-process arrays are not fully addressable, so they are first
        replicated through a jitted identity (one all-gather — every rank
        participates: this is a COLLECTIVE and must be called on all
        processes) and then fetched."""
        if dtype is not None:
            tree = jax.tree_util.tree_map(
                lambda p: p.astype(dtype), tree
            )
        if jax.process_count() > 1:
            # memoized per tree structure: a fresh lambda per call would
            # recompile the full all-gather program on every weight push
            treedef = jax.tree_util.tree_structure(tree)
            key = ("host_gather", treedef)
            if key not in self._jit_cache:
                rep = sharding_lib.replicated(self.mesh)
                self._jit_cache[key] = jax.jit(
                    lambda t: t,
                    out_shardings=jax.tree_util.tree_unflatten(
                        treedef,
                        [rep] * treedef.num_leaves,
                    ),
                )
            tree = self._jit_cache[key](tree)
        return jax.device_get(tree)

    def save(self, meta: SaveLoadMeta):
        if meta.weight_format == "hf":
            host = self._host_tree(self.params)
            if jax.process_index() == 0:
                hf_io.save_params(host, self.model_config, meta.path)
            if meta.with_optim:
                self._save_optim(os.path.join(meta.path, "optim"))
        else:
            import orbax.checkpoint as ocp

            ckpt = {"params": self.params, "step": self.step_count}
            if meta.with_optim and self.opt_state is not None:
                ckpt["opt_state"] = self.opt_state
            ocp.StandardCheckpointer().save(
                os.path.abspath(meta.path), ckpt, force=True
            )

    def _save_optim(self, path: str):
        flat, _ = jax.tree_util.tree_flatten(self._host_tree(self.opt_state))
        if jax.process_index() != 0:
            return
        os.makedirs(path, exist_ok=True)
        np.savez(
            os.path.join(path, "opt_state.npz"),
            *[np.asarray(x) for x in flat],
            step=self.step_count,
        )

    def load(self, meta: SaveLoadMeta):
        if meta.weight_format == "hf":
            host = hf_io.load_params(
                meta.path, self.model_config, dtype=self.param_dtype
            )
            self.params = jax.device_put(host, self._param_shardings)
            optim_path = os.path.join(meta.path, "optim", "opt_state.npz")
            if meta.with_optim and os.path.exists(optim_path):
                data = np.load(optim_path)
                flat, treedef = jax.tree_util.tree_flatten(self.opt_state)
                arrs = [data[f"arr_{i}"] for i in range(len(flat))]
                host_opt = jax.tree_util.tree_unflatten(treedef, arrs)
                shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding, self.opt_state
                )
                self.opt_state = jax.device_put(host_opt, shardings)
                self.step_count = int(data["step"])
        else:
            import orbax.checkpoint as ocp

            restored = ocp.StandardCheckpointer().restore(
                os.path.abspath(meta.path)
            )
            self.params = jax.device_put(
                restored["params"], self._param_shardings
            )
            self.step_count = int(restored["step"])
            if meta.with_optim and "opt_state" in restored:
                shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding, self.opt_state
                )
                self.opt_state = jax.device_put(
                    restored["opt_state"], shardings
                )

    def iter_weight_chunks(self, chunk_bytes: int, dtype=None):
        """Yield ``(chunk_index, n_chunks, [(name, np.ndarray)])`` one FFD
        chunk at a time, gathering each chunk to host independently — peak
        host memory is O(chunk_bytes), never O(model) (the reference
        streams ≤1 GB FFD chunks the same way, fsdp_engine.py:435-444;
        round-2 verdict flagged the full-model host gather as a v5e-host
        OOM risk at 7B).

        COLLECTIVE in multi-process runs: every rank must drain the
        generator in the same order (each chunk's replication is an
        all-gather)."""
        from areal_tpu.utils import weight_transfer as wt

        leaves = wt.flatten_params(self.params)  # (name, jax.Array)
        plan = wt.chunk_leaves(leaves, chunk_bytes)
        n = len(plan)
        multiproc = jax.process_count() > 1
        for i, items in enumerate(plan):
            arrs = [a for _, a in items]
            if dtype is not None or multiproc:
                key = ("chunk_gather", dtype, i, n)
                if key not in self._jit_cache:
                    kwargs = {}
                    if multiproc:
                        rep = sharding_lib.replicated(self.mesh)
                        kwargs["out_shardings"] = [rep] * len(arrs)
                    dt = dtype

                    def _g(xs, dt=dt):
                        return [
                            x if dt is None else x.astype(dt) for x in xs
                        ]

                    self._jit_cache[key] = jax.jit(_g, **kwargs)
                arrs = self._jit_cache[key](arrs)
            fetched = jax.device_get(arrs)
            yield i, n, [
                (name, np.asarray(a))
                for (name, _), a in zip(items, fetched)
            ]

    def upload_weights(self, meta: WeightUpdateMeta):
        """Push fresh weights to the generation side.

        DISK: write an HF checkpoint the generation engine reloads
        (reference fsdp_engine.py:384-395).

        DEVICE: stream the sharded params chunk-by-chunk
        (``iter_weight_chunks``): each ≤chunk_bytes FFD chunk is gathered
        to host, posted as one binary POST to every generation server, and
        freed before the next gather — no disk round-trip and no
        full-model host copy (reference _update_weights_from_distributed,
        fsdp_engine.py:414-433). Server addresses come from meta.addrs or
        the AREAL_LLM_SERVER_ADDRS environment.
        """
        from areal_tpu.api.io_struct import WeightUpdateMethod
        from areal_tpu.utils import stats_tracker

        t_upload = time.perf_counter()

        if meta.type == WeightUpdateMethod.DISK:
            with goodput.trainer_bucket("weight_push"):
                host = self._host_tree(self.params)  # collective
                if jax.process_index() == 0:
                    hf_io.save_params(host, self.model_config, meta.path)
            stats_tracker.scalar(**{
                "spmd/upload_weights_s": time.perf_counter() - t_upload
            })
            return
        from areal_tpu.utils import weight_transfer as wt

        addrs = list(meta.addrs or [])
        if not addrs:
            env = os.environ.get("AREAL_LLM_SERVER_ADDRS", "")
            addrs = [a for a in env.split(",") if a]
        if not addrs:
            raise ValueError(
                "WeightUpdateMethod.DEVICE needs server addresses "
                "(meta.addrs or AREAL_LLM_SERVER_ADDRS)"
            )
        import json as _json
        from concurrent.futures import ThreadPoolExecutor

        def _post(addr: str, i: int, body: bytes):
            req = urllib.request.Request(
                f"http://{addr}/update_weights_from_distributed",
                data=body,
                headers={"Content-Type": "application/octet-stream"},
            )
            with urllib.request.urlopen(req, timeout=600) as r:
                resp = _json.loads(r.read())
            if resp.get("success") is not True:
                raise RuntimeError(
                    f"weight chunk {i} rejected by {addr}: {resp}"
                )

        # fan each chunk out to all servers concurrently (the reference's
        # broadcast reaches every server at once). Streamed-mode servers
        # (r13, the default) stay LIVE through the transfer — each chunk
        # lands in a shadow buffer while decode runs — but wall time
        # still matters: it bounds how stale the flip is by the time it
        # applies, and legacy servers sit paused for all of it. The
        # generator is collective: non-zero ranks drain it without
        # posting.
        with goodput.trainer_bucket("weight_push"), ThreadPoolExecutor(
            max_workers=max(1, len(addrs))
        ) as pool:
            for i, n_chunks, chunk in self.iter_weight_chunks(
                meta.chunk_bytes, dtype=self.compute_dtype
            ):
                if jax.process_index() != 0:
                    continue
                body = wt.encode_chunk(
                    meta.model_version, i, n_chunks, chunk
                )
                del chunk
                futs = [
                    pool.submit(_post, addr, i, body) for addr in addrs
                ]
                for f in futs:
                    f.result()
        stats_tracker.scalar(**{
            "spmd/upload_weights_s": time.perf_counter() - t_upload
        })

