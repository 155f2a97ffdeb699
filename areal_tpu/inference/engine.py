"""Generation engine: continuous batching over the paged KV-block pool.

Role of the SGLang server the reference drives over HTTP (areal/engine/
sglang_remote.py + realhf/system/generation_server.py), rebuilt TPU-native:
a single background loop thread owns the device state (params, page pool)
and interleaves admissions (prefill) with fused multi-step decode. Every
device program is compiled once per shape bucket — continuous batching
never recompiles.

Chunked prefill (r15, ``JaxGenConfig.chunked_prefill``): a long prompt's
admission is capped at ``prefill_chunk_tokens`` suffix tokens per wave;
the committed page-aligned prefix is published into the prefix cache at
chunk commit and the request requeues, so the NEXT wave's claim resumes
exactly there — later chunks are claims against the prompt's own
committed pages, decode dispatches interleave between chunks, and
time-to-first-token for a request admitted behind a bulk prompt is
bounded by ~one chunk's latency instead of the whole prefill. Chunk
boundaries double as cheap preemption points (deadline pressure defers
the next bulk chunk). Greedy streams are bit-identical chunked on/off;
off is a strict no-op.

Memory model (the radix prefix cache, inference/cache.py):
- prompts and generations live in refcounted pages; GRPO siblings *share*
  full prompt pages (one prefill, no copy) and copy at most one partial
  tail page. A refcounted RADIX TREE over the pool (r9 default) is
  populated at PREFILL COMMIT — the first sibling's prompt pages are
  claimable the moment its prefill dispatch lands, so siblings/turns
  arriving in later waves ride them while the owner is still decoding —
  and extended at free time with the full generated sequence. Claims
  descend the tree in O(prompt): full pages share by refcount; a match
  that diverges *within* a page (partial tails included) is served
  copy-on-write at the pool's row grain, and prefill resumes mid-page.
- decode allocates pages lazily as sequences grow. When the pool runs dry
  the engine evicts the tree leaf-LRU-first and then *preempts* the
  youngest running requests: their pages move to the tree and the
  request transparently re-queues (it usually re-claims its own pages, so
  preemption costs one partial-page re-prefill at most). This is what lets
  max_model_len be 16k+ without reserving 16k tokens per slot.

Interruption protocol (matches reference semantics
sglang_remote.py:186-234): ``pause()`` aborts in-flight requests — they
resolve with ``stop_reason="abort"`` and their tokens; the client
re-submits with accumulated tokens after ``continue_generation``; the
registry serves the already-cached prefix back without recompute.
"""

import dataclasses
import functools
import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.cli_args import JaxGenConfig
from areal_tpu.inference import model_runner
from areal_tpu.inference import precompile as precompile_lib
from areal_tpu.inference.cache import (
    CacheConfig,
    PageManager,
    PrefixRegistry,
    RadixPrefixCache,
    init_kv_pool,
)
from areal_tpu.inference.policies import PolicyRegistry, UnknownPolicyError
from areal_tpu.inference.weights import WeightStore
from areal_tpu.models import hf_io, transformer
from areal_tpu.models.config import ModelConfig, load_hf_config
from areal_tpu.models.transformer import Params
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import goodput, tracing
from areal_tpu.utils import logging as logging_util
from areal_tpu.utils.tracing import Histogram, SpanTracer

logger = logging_util.getLogger("GenerationEngine")

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

# the traffic plane's two scheduling classes (api/cli_args.TrafficConfig):
# interactive = latency-sensitive (eval, live agentic sessions), bulk =
# throughput rollouts. Unknown labels degrade to bulk — the shed/preempt
# machinery must never promote unlabeled traffic.
SCHED_CLASSES = ("interactive", "bulk")
# the rid the loop thread's own spans share in the tracer's ring
_LOOP_RID = "__engine__"


def _loop_phase(name: str, counter: str):
    """The decorated method of the loop thread is one span ``name``, and
    its host seconds add to ``metrics()["total_loop_<counter>_s"]`` — for
    every caller, so nothing is counted twice or left out."""

    def deco(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            with self._loop_span(name) as sp:
                out = fn(self, *args, **kwargs)
            self._loop_s[counter] += sp.duration
            return out

        return timed

    return deco


class _Phases:
    """Consecutive sibling spans over one long linear stretch of the loop
    thread (``_admit``): each ``enter`` closes the phase before it;
    leaving the block (an early ``return`` too) closes the last."""

    __slots__ = ("_engine", "_open")

    def __init__(self, engine: "GenerationEngine"):
        self._engine = engine
        self._open = None

    def __enter__(self):
        return self

    def enter(self, name: str) -> None:
        self.__exit__(None, None, None)
        self._open = self._engine._loop_span(name)
        self._open.__enter__()

    def __exit__(self, *exc):
        if self._open is not None:
            self._open.__exit__(*exc)
            self._open = None
        return False


class AdmissionRejectedError(RuntimeError):
    """The bounded admission queue is full and this request's class is
    being shed (load shedding, not failure). ``retry_after`` is the
    backpressure hint an HTTP shell forwards as ``429 + Retry-After`` —
    utils/http treats that as "back off and retry", so a shed never
    burns the client's episode-retry budget."""

    def __init__(
        self,
        message: str,
        retry_after: float = 1.0,
        sched_class: str = "bulk",
    ):
        super().__init__(message)
        self.retry_after = retry_after
        self.sched_class = sched_class


@dataclasses.dataclass
class _Request:
    rid: str
    input_ids: List[int]
    max_new_tokens: int
    min_new_tokens: int
    temperature: float
    top_p: float
    top_k: int
    greedy: bool
    stop_token_ids: List[int]
    future: Future
    slot: Optional[int] = None
    output_ids: List[int] = dataclasses.field(default_factory=list)
    output_logprobs: List[float] = dataclasses.field(default_factory=list)
    output_versions: List[int] = dataclasses.field(default_factory=list)
    submit_time: float = dataclasses.field(default_factory=time.monotonic)
    first_token_time: Optional[float] = None
    preemptions: int = 0
    # --- SLO traffic plane (r10) ---
    # scheduling class ("interactive" | "bulk") + tenant label, stamped
    # by workflows through engine/remote.py; unknown/absent = bulk
    priority: str = "bulk"
    tenant: str = ""
    # absolute soft deadline (monotonic clock); a queued interactive
    # request about to miss it may preempt a bulk request
    deadline_at: Optional[float] = None
    # a suffix-resume continuation of an in-flight episode request: it
    # already holds client-side progress, so admission never sheds it
    resumed: bool = False
    # --- chunked prefill (r15) ---
    # prefill chunks this request has committed so far (0 = never
    # chunk-capped); prefill_pos is the committed token position after
    # the last chunk — the next wave's prefix-cache claim resumes at or
    # beyond it, and a claim that regresses below it counts a stall
    # (chunk_stalls >= 2 admits the remainder whole: cache thrash must
    # never livelock a prompt). chunk_deferred marks an in-progress
    # deferral episode (the preemption counter records it ONCE, not
    # once per scheduler tick); first_dispatch_time is the wave that
    # first served this request (chunk 0) — queue-wait metrics end
    # there, not at the final chunk (being prefilled is not queueing).
    # All are reset at _install (one slot life per admission).
    chunk_index: int = 0
    prefill_pos: int = 0
    chunk_stalls: int = 0
    chunk_deferred: bool = False
    first_dispatch_time: Optional[float] = None
    # prompt tokens served from the prefix cache at the LAST install
    # (sibling fan-out counts the whole shared prompt) — surfaced in
    # the result's meta_info so clients (and the cross-server shipping
    # e2e test) can observe cache effectiveness per request
    cached_tokens: int = 0
    # weight version this request decodes under (and whose KV its pages
    # hold) — stamped at admission, left behind by a pin-policy flip so
    # the request drains on the buffer that prefilled it (the store
    # holds one pin per such request until it finishes/preempts)
    weight_version: int = 0
    # --- multi-policy plane (r19) ---
    # named policy line this request decodes on ("" = the default line:
    # self.params / WeightStore, exactly the pre-r19 engine). submit()
    # resolves the raw handle (name[@vN|@stable|@canary], canary split
    # applied ONCE there) to (policy, policy_version); admission
    # re-checks liveness and stamps weight_version = the line's version
    # (version ints are per-line, so every (policy, weight_version)
    # comparison must carry the name)
    policy: str = ""
    policy_version: int = 0
    # multimodal payload (VLM serving): pixel_values [P, Dp],
    # vis_seg/vis_pos_h/vis_pos_w [P], mm_index [plen] (-1 = text),
    # mrope_pos [plen, 3]; rope_delta shifts decode rope positions
    # (mrope compresses image blocks, so text positions lag cache length)
    mm: Optional[Dict[str, np.ndarray]] = None
    rope_delta: int = 0
    _mm_key: Optional[bytes] = None

    @property
    def mm_key(self) -> bytes:
        """Identity of the visual inputs: GRPO sibling grouping and page
        sharing must distinguish same-token prompts with different
        pixels."""
        if self.mm is None:
            return b""
        if self._mm_key is None:
            import hashlib

            h = hashlib.blake2b(digest_size=12)
            h.update(np.ascontiguousarray(self.mm["pixel_values"]).tobytes())
            self._mm_key = h.digest()
        return self._mm_key

    @property
    def all_tokens(self) -> List[int]:
        """Prompt for (re-)prefill: original prompt + everything generated
        (a preempted request resumes by re-prefilling its own output)."""
        return self.input_ids + self.output_ids

    @property
    def budget_left(self) -> int:
        return self.max_new_tokens - len(self.output_ids)

    @property
    def min_left(self) -> int:
        return self.min_new_tokens - len(self.output_ids)


_MM_KEYS = (
    "pixel_values", "vis_seg", "vis_pos_h", "vis_pos_w", "mm_index",
    "mrope_pos",
)
_MM_DTYPES = {"pixel_values": np.float32, "mrope_pos": np.int32}


def _parse_request(payload: Dict[str, Any], fut: Future) -> _Request:
    sp = payload.get("sampling_params", {})
    mm = None
    rope_delta = 0
    if payload.get("mm"):
        raw = dict(payload["mm"])
        if "pixel_values_b64" in raw:
            # binary transport (remote client): base64 float32 + shape
            import base64

            raw["pixel_values"] = np.frombuffer(
                base64.b64decode(raw.pop("pixel_values_b64")), np.float32
            ).reshape(raw.pop("pixel_values_shape"))
        required = ("pixel_values", "vis_seg", "vis_pos_h", "vis_pos_w",
                    "mm_index")
        missing = [k for k in required if k not in raw]
        if missing:
            # reject on the CALLER thread — a KeyError later inside the
            # engine loop would kill serving for every request
            raise ValueError(f"mm payload missing keys: {missing}")
        mm = {
            k: np.asarray(raw[k], _MM_DTYPES.get(k, np.int32))
            for k in _MM_KEYS
            if k in raw
        }
        if "rope_delta" in raw:
            rope_delta = int(raw["rope_delta"])
        elif "mrope_pos" in mm and len(mm["mrope_pos"]):
            # text token at sequence index i has rope position i + delta
            # (mrope compresses each image block to max(t, h/m, w/m) slots)
            rope_delta = int(mm["mrope_pos"].max()) + 1 - len(
                mm["mrope_pos"]
            )
    priority = str(payload.get("priority") or "bulk")
    if priority not in SCHED_CLASSES:
        priority = "bulk"
    deadline_s = payload.get("deadline_s")
    submit_time = time.monotonic()
    return _Request(
        rid=payload.get("rid", f"req-{time.time_ns()}"),
        priority=priority,
        tenant=str(payload.get("tenant") or ""),
        deadline_at=(
            submit_time + float(deadline_s)
            if deadline_s is not None and float(deadline_s) > 0
            else None
        ),
        resumed=bool(payload.get("resumed")),
        policy=str(payload.get("policy") or ""),
        submit_time=submit_time,
        input_ids=list(payload["input_ids"]),
        max_new_tokens=int(sp.get("max_new_tokens", 128)),
        min_new_tokens=int(sp.get("min_new_tokens", 0)),
        temperature=float(sp.get("temperature", 1.0)),
        top_p=float(sp.get("top_p", 1.0)),
        top_k=int(sp.get("top_k", 0)),
        greedy=bool(sp.get("greedy", False)),
        stop_token_ids=list(sp.get("stop_token_ids", [])),
        future=fut,
        mm=mm,
        rope_delta=rope_delta,
    )


class GenerationEngine:
    """In-process generation engine; the HTTP server is a thin shell."""

    def __init__(
        self,
        config: JaxGenConfig,
        model_config: Optional[ModelConfig] = None,
        params: Optional[Params] = None,
    ):
        self.config = config
        self.dtype = _DTYPES[config.dtype]
        from areal_tpu.utils.compile_cache import enable_compilation_cache

        # an inherited JAX_COMPILATION_CACHE_DIR wins over the config's
        # directory; with neither the cache stays off
        enable_compilation_cache(getattr(config, "compilation_cache_dir", ""))
        if model_config is None:
            model_config = load_hf_config(config.model_path)
        self.model_config = model_config
        refused = transformer.generation_refusal(model_config)
        if refused:  # a mechanism's own reason (``transformer.MECHANISMS``)
            raise NotImplementedError(refused)
        if params is None:
            params = hf_io.load_params(
                config.model_path, model_config, dtype=self.dtype
            )
        # --- tensor-parallel serving mesh (per-server tp, the analog of the
        # reference's SGLang tp inside one server, areal/api/cli_args.py:399;
        # required to fit 7B+ params on small-HBM chips) ---
        tp = max(1, config.tensor_parallel_size)
        if tp > 1:
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tensor_parallel_size={tp} but only {len(devs)} devices"
                )
            if (
                model_config.num_kv_heads % tp != 0
                or model_config.num_heads % tp != 0
            ):
                raise ValueError(
                    f"tensor_parallel_size={tp} must divide num_heads="
                    f"{model_config.num_heads} and num_kv_heads="
                    f"{model_config.num_kv_heads}"
                )
            if model_config.is_moe and model_config.num_experts % tp != 0:
                raise ValueError(
                    f"tensor_parallel_size={tp} must divide num_experts="
                    f"{model_config.num_experts} for MoE serving"
                )
            from areal_tpu.parallel import sharding as sharding_lib

            self.mesh = jax.sharding.Mesh(
                np.asarray(devs[:tp]), ("tensor",)
            )
            rules = {
                "embed": None, "heads": "tensor",
                # MoE serving: the expert dim shards over the per-server
                # axis (one PartitionSpec can't use the axis twice, so the
                # within-expert ffn dim stays replicated)
                "mlp": None if model_config.is_moe else "tensor",
                "expert": "tensor",
                "vocab": None, "layer": None,
            }
            self._param_shardings = sharding_lib.tree_shardings(
                self.mesh, transformer.param_logical_axes(model_config), rules
            )
            # paged pool [L, Hkv, NP, BS//f, f*D]: kv heads follow the
            # tensor axis
            self._kv_sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(
                    None, "tensor", None, None, None
                )
            )
            self._replicated = sharding_lib.replicated(self.mesh)
        else:
            self.mesh = None
            self._param_shardings = None
            self._kv_sharding = None
            self._replicated = None
        self.params = self._place_params(params)

        # --- paged KV pool ---
        bs = config.page_size
        num_pages = config.num_pages
        if num_pages <= 0:
            # conservative auto: full provisioning (every slot can reach
            # max_model_len) — set num_pages explicitly to oversubscribe.
            # +1 for the permanently reserved trash page
            num_pages = (
                config.max_num_seqs * (-(-config.max_model_len // bs)) + 1
            )
        self.cache_config = CacheConfig(
            num_pages=num_pages,
            page_size=bs,
            max_model_len=config.max_model_len,
        )
        from areal_tpu.ops.paged_attention import (
            can_head_merge,
            resolve_pool_layout,
        )

        layout = getattr(config, "pool_layout", "auto")
        if layout not in ("auto", "token_packed", "head_merged"):
            raise ValueError(
                f"pool_layout={layout!r}: expected auto | token_packed | "
                "head_merged"
            )
        # "auto" resolves to head_merged where the geometry + placement
        # allow (the r6 default — built for the decode-DMA bottleneck and
        # parity-pinned in tests/test_pool_layout.py)
        layout = resolve_pool_layout(
            layout, model_config.num_kv_heads, model_config.head_dim,
            single_device=self.mesh is None,
        )
        if layout == "head_merged":
            if not can_head_merge(
                model_config.num_kv_heads, model_config.head_dim
            ):
                raise ValueError(
                    "pool_layout=head_merged needs Hkv*head_dim | 128 "
                    f"(got {model_config.num_kv_heads}x"
                    f"{model_config.head_dim})"
                )
            if self.mesh is not None:
                # TP shards the pool's kv-head dim, which merged collapses
                # — silently downgrading would make layout A/Bs bogus
                raise ValueError(
                    "pool_layout=head_merged is single-device only "
                    "(tensor parallelism shards the pool's kv-head dim)"
                )
        self._head_merge = layout == "head_merged"
        if self.mesh is None:
            self.cache = init_kv_pool(
                model_config, self.cache_config, self.dtype,
                head_merge=self._head_merge,
            )
        else:
            # allocate directly sharded — materializing on one device
            # first would OOM exactly the small-HBM configs TP exists for
            self.cache = jax.jit(
                lambda: init_kv_pool(
                    model_config, self.cache_config, self.dtype
                ),
                out_shardings={
                    "k": self._kv_sharding,
                    "v": self._kv_sharding,
                },
            )()
        # page 0 is the trash target for dropped merge rows — reserved
        self.pm = PageManager(num_pages, reserve_first=True)
        cache_mode = getattr(config, "prefix_cache_mode", "radix")
        if cache_mode not in ("radix", "flat"):
            raise ValueError(
                f"prefix_cache_mode={cache_mode!r}: expected radix | flat"
            )
        if cache_mode == "radix":
            # COW grain = the token-packed row (pack_factor tokens): a
            # multiple of BOTH layouts' tokens-per-row, so mid-page
            # claim resumes stay row-aligned (assemble_rows never reads
            # the pool) and cached-token counts are layout-independent
            from areal_tpu.ops.paged_attention import pack_factor

            self.registry = RadixPrefixCache(
                bs, config.prefix_reuse_min,
                grain=pack_factor(model_config.head_dim),
            )
        else:
            self.registry = PrefixRegistry(bs, config.prefix_reuse_min)
        self._radix = cache_mode == "radix"
        # --- hierarchical KV tiers (r16): host-RAM (optionally disk)
        # spill store under the radix tree. Strictly no-op when off:
        # no manager, no tree hook, no metric keys.
        self._kv_tiers = None
        if getattr(config, "kv_spill", False):
            if not self._radix:
                raise ValueError(
                    "kv_spill requires prefix_cache_mode='radix' "
                    "(the spill tier lives under the radix tree)"
                )
            from areal_tpu.inference.kv_tiers import KvTierManager

            self._kv_tiers = KvTierManager(
                host_bytes=int(getattr(config, "host_kv_bytes", 1 << 30)),
                gather_fn=self._gather_pages_host,
                disk_path=getattr(config, "kv_disk_path", "") or "",
            )
            self.registry.attach_tiers(self._kv_tiers)
        # cross-server prefix shipping (r16): /kv_export service + the
        # /generate-side fetch from a session's previous owner
        self._kv_ship = bool(getattr(config, "kv_ship", False))
        if self._kv_ship and not self._radix:
            raise ValueError(
                "kv_ship requires prefix_cache_mode='radix' (shipped "
                "pages enter through the radix publish/claim contract)"
            )
        s = config.max_num_seqs
        self._free_slots: List[int] = list(range(s - 1, -1, -1))
        self._tables = np.full(
            (s, self.cache_config.max_pages_per_seq), num_pages, np.int32
        )
        self._slot_pages: Dict[int, List[int]] = {}
        self._cached_len = np.zeros(s, np.int64)
        # attention backend: Pallas kernel on single-device TPU, jnp
        # gather fallback elsewhere (CPU tests, TP serving)
        if config.attn_impl == "auto":
            on_tpu = jax.devices()[0].platform == "tpu"
            self._attn_impl = "kernel" if (tp == 1 and on_tpu) else "jnp"
        else:
            self._attn_impl = config.attn_impl
        logger.info(
            f"paged attention: attn_impl={config.attn_impl!r} resolved to "
            f"{self._attn_impl!r} on {jax.devices()[0].platform} (tp={tp})"
        )
        self.model_version = 0
        self._rng_key = jax.random.PRNGKey(config.seed)

        self._jit_cache: Dict[str, Any] = {}
        self._admit_queue: "queue.Queue[_Request]" = queue.Queue()
        self._command_queue: "queue.Queue" = queue.Queue()
        self._active: Dict[int, _Request] = {}  # slot -> request
        self._pending: List[_Request] = []  # drained but not yet admitted
        self._pending_since: Optional[float] = None
        # device-path weight staging (chunked receive — the LEGACY
        # paused path; streamed ingest stages in self.weights instead)
        self._staged: Dict[str, Any] = {}
        self._staging_key = None
        self._staged_chunks: set = set()
        # --- zero-pause weight plane (r13): versioned buffers + shadow
        # staging + the flip the loop applies between dispatches ---
        wt = getattr(config, "weights", None)
        if wt is None:
            from areal_tpu.api.cli_args import WeightTransferConfig

            wt = WeightTransferConfig()
        if wt.flip_policy not in ("pin", "resume"):
            raise ValueError(
                f"weights.flip_policy={wt.flip_policy!r}: expected "
                "pin | resume"
            )
        self._wt_cfg = wt
        self._weights_streaming = bool(wt.streaming)
        self.weights = WeightStore(staging_ttl_s=wt.staging_ttl_s)
        self._leaf_shardings: Optional[Dict[str, Any]] = None
        self._cohort_rr = 0  # round-robin cursor over version cohorts
        # --- multi-policy serving plane (r19): N named policy lines on
        # this one engine, each with its own buffers/pins/KV namespace.
        # Strictly no-op until the first named push: `active` stays
        # False (the hot-loop gate), no namespace caches exist, and
        # metrics() emits zero policy keys. Cold named buffers demote
        # to host RAM (LRU past max_resident; pinned = undemotable) and
        # reload on the next request that resolves to them.
        pol = getattr(config, "policy", None)
        self._policies = PolicyRegistry(
            to_host=jax.device_get,
            to_device=self._place_params,
            max_resident=int(getattr(pol, "max_resident", 2) or 0),
            staging_ttl_s=wt.staging_ttl_s,
        )
        # (name, version) -> that namespace's own prefix cache sharing
        # self.pm — a canary's pages can never be claimed by the stable
        # line because claims/publishes never cross namespaces. KV
        # tiers/shipping stay default-namespace-only (the spill store
        # and /kv_export are keyed by token content, not policy).
        self._policy_caches: Dict[tuple, Any] = {}
        self._sweep_tick = 0
        self._paused = threading.Event()
        self._running = False
        self._loop_error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        # device-resident decode state: the generation loop's only host
        # traffic per step is ONE result fetch (tokens+logprobs).
        # INVARIANT (decode tail compaction): a new per-slot array must
        # join _dispatch_chunk's `plain_attrs` gather spec (or its
        # special-case block for non-1-D/conditional arrays), and its
        # decode_multi-returned update must join the `updates` dict —
        # otherwise compacted dispatches silently diverge from
        # full-width ones. tests/test_decode_compaction.py pins parity
        # for the current set. (_align_base_dev is such a special case:
        # gathered per row with padding forced to 0, read-only on
        # device — r7 speculative canonical alignment.)
        self._cur_tokens = jnp.zeros(s, jnp.int32)
        # identity slot map for full-width dispatches (uncommitted, like
        # the arange decode_multi would otherwise build per dispatch)
        self._identity_slots = jnp.arange(s, dtype=jnp.int32)
        self._active_dev = jnp.zeros(s, bool)
        self._temp_dev = jnp.ones(s, jnp.float32)
        self._top_p_dev = jnp.ones(s, jnp.float32)
        self._top_k_dev = jnp.zeros(s, jnp.int32)
        self._greedy_dev = jnp.zeros(s, bool)
        self._remaining = jnp.zeros(s, jnp.int32)
        self._no_stop = jnp.zeros(s, jnp.int32)
        self._stop_tokens = jnp.full((s, 8), -1, jnp.int32)
        # device-resident cached length per slot: decode chunk N+1 can
        # dispatch before chunk N's results reach the host
        self._lens_dev = jnp.zeros(s, jnp.int32)
        # per-slot admission cache length — the canonical chunk-alignment
        # base for speculative serving (a partial draft accept leaves a
        # slot between decode_chunk boundaries; every later dispatch
        # replays boundary-to-now K/V from the pool so per-position
        # numerics stay bit-identical to a non-speculative run). Only
        # consulted when spec is configured
        self._align_base_dev = jnp.zeros(s, jnp.int32)
        self._align_base = np.zeros(s, np.int64)  # host mirror
        # VLM slots: mrope text positions lag the cache index by a
        # per-request constant; tracked per slot, passed to decode only
        # when some active slot is multimodal (text-only serving keeps
        # its compiled programs)
        self._rope_delta_dev = jnp.zeros(s, jnp.int32)
        self._slot_mm = np.zeros(s, bool)
        # per-slot last (partial) pool row — lets merges avoid reading the
        # pool (see model_runner.init_last_rows)
        from areal_tpu.inference.model_runner import init_last_rows

        # last-row buffers mirror the POOL's row layout
        _, hkv_pool, _, _, lane = self.cache["k"].shape
        self._last_rows = init_last_rows(
            model_config.num_layers, s, hkv_pool, lane, self.dtype
        )
        # pipelined decode: dispatched-but-unprocessed chunks, and page
        # releases deferred until the pipeline drains (an in-flight chunk
        # may still write to a host-finished slot's pages)
        self._inflight: List[Dict[str, Any]] = []
        self._deferred_release: List[tuple] = []
        # --- decode tail compaction (r6): dispatch over a pow2 bucket of
        # ACTIVE slots. Single-device only: under TP the per-slot state
        # is explicitly replicated on the mesh and the full-slot dispatch
        # is kept. Sampling is slot-keyed (model_runner._sample_impl), so
        # compaction never changes a request's token stream.
        self._compact_enabled = (
            bool(getattr(config, "decode_compact", True))
            and self.mesh is None
        )
        self._compact_rows: Optional[int] = None  # current sticky bucket
        self._compact_shrink_streak = 0
        # occupancy accounting: how many rows each decode chunk paid for
        # vs how many carried live requests (the compaction win, measured)
        self.total_decode_chunks = 0
        self.total_rows_dispatched = 0
        self.total_rows_active = 0
        self._decode_rows_dispatched = 0  # last chunk (gauge)
        self._decode_rows_active = 0  # last chunk (gauge)
        self.rows_dispatched_hist: Dict[int, int] = {}
        if self.mesh is not None:
            # small state must be explicitly replicated on the mesh so jit
            # doesn't mix committed single-device and sharded inputs
            for attr in (
                "_cur_tokens", "_active_dev", "_temp_dev", "_top_p_dev",
                "_top_k_dev", "_greedy_dev", "_remaining", "_no_stop",
                "_stop_tokens", "_lens_dev", "_rope_delta_dev",
                "_align_base_dev",
            ):
                setattr(
                    self, attr,
                    jax.device_put(getattr(self, attr), self._replicated),
                )
            self._last_rows = jax.device_put(
                self._last_rows, self._replicated
            )
        # --- speculative decoding (r7): host-side draft-free n-gram
        # proposals (inference/spec.py) verified by one multi-token
        # dispatch (model_runner.spec_verify). Single-device dense models
        # only: TP keeps the replicated full-slot dispatch, and MoE
        # capacity routing is batch-composition-dependent (a K-position
        # verify would route differently than K sequential steps).
        sc = getattr(config, "spec", None)
        spec_wanted = bool(sc is not None and sc.enabled)
        # decode_chunk < 2 leaves no room for even one draft inside the
        # canonical window (_propose_drafts trims to decode_chunk-1-rl),
        # so speculation could never verify anything — but the
        # drain-for-drafts branch would still fire on raw n-gram
        # candidates, silently destroying pipelining forever
        self._spec_configured = (
            spec_wanted
            and self.mesh is None
            and not model_config.is_moe
            and config.decode_chunk >= 2
        )
        if spec_wanted and not self._spec_configured:
            logger.warning(
                "speculative decoding requested but unavailable: needs "
                "single-device serving, a dense model, and "
                "decode_chunk >= 2 — running without speculation"
            )
        if self._spec_configured:
            from areal_tpu.inference.spec import (
                AcceptRateGate,
                NgramProposer,
            )

            self._proposer = NgramProposer(sc.ngram_min, sc.ngram_max)
            self._spec_gate = AcceptRateGate(
                sc.accept_floor, sc.disable_patience
            )
        else:
            self._proposer = None
            self._spec_gate = None
        self._spec_disable_logged = False
        # set once the gate has sticky-disabled AND every active slot is
        # back on a canonical boundary: later dispatches skip the
        # alignment-replay machinery entirely (plain spec-off program)
        self._spec_replay_off = False
        self.total_spec_chunks = 0
        self.spec_draft_tokens_total = 0
        self.spec_accepted_tokens_total = 0
        self._step_counter = 0
        # metrics
        self.total_generated_tokens = 0
        self.total_prompt_tokens = 0
        self.total_cached_prompt_tokens = 0  # prompt tokens served from KV reuse
        self.total_cow_copies = 0  # COW page copies for mid-page claims
        # r16 KV tiers: cached prompt tokens that came back from the
        # HOST tier (a subset of total_cached_prompt_tokens — the rest
        # were device-resident hits); ship counters cover the
        # cross-server /kv_export import/export traffic
        self.total_host_cached_tokens = 0
        self.kv_ship_exports_total = 0
        self.kv_ship_imports_total = 0
        self.kv_ship_pages_out_total = 0
        self.kv_ship_pages_in_total = 0
        self.kv_ship_failures_total = 0
        self.total_requests = 0
        self.total_aborted = 0
        self.total_preemptions = 0
        # --- SLO traffic plane (r10) ---
        # admission-queue composition: per-class count of requests
        # sitting in _admit_queue (submit increments on handler threads,
        # _admit decrements on the loop thread); _pending composition is
        # scanned directly at metrics time
        self._aq_lock = threading.Lock()
        self._aq_class = {c: 0 for c in SCHED_CLASSES}
        self._aq_resumed = 0  # resumed (bound-exempt) entries in-queue
        self._class_submitted = {c: 0 for c in SCHED_CLASSES}
        self.requests_shed_total = 0
        self.deadline_preemptions_total = 0
        self.deadline_misses_total = 0
        # --- chunked prefill (r15): bounded interactive TTFT ---
        # resolved per-dispatch prefill token budget (0 = off). A long
        # prompt's admission is capped at this many suffix tokens; the
        # committed page-aligned prefix is published into the prefix
        # cache at chunk commit and the request requeues — the next
        # wave's claim resumes exactly there, so later chunks are
        # claims against the prompt's own committed pages and every
        # admission dispatch stays ~one chunk wide. Chunk boundaries
        # are the new preemption points: deadline pressure defers bulk
        # chunks instead of killing whole prefills.
        self._chunk_budget = precompile_lib.resolve_chunk_budget(config)
        if (
            getattr(config, "chunked_prefill", False)
            and self._chunk_budget <= 0
        ):
            logger.warning(
                "chunked prefill requested but unavailable: needs a "
                "prefix cache (0 < prefix_reuse_min <= the page-aligned "
                "chunk budget — committed chunks resume via claims) and "
                "a budget below max_model_len — admitting prompts whole"
            )
        self.prefill_chunks_total = 0
        self.prefill_chunk_preemptions_total = 0
        # stall-escape admissions (uncapped dispatches under cache
        # thrash): ttft_bounded reports whether the chunk bound has
        # held for EVERY admission dispatch so far — a gauge that
        # echoed the config while an escape re-created the
        # head-of-line block would lie to the CI gate reading it
        self.prefill_chunk_stall_escapes = 0
        # unsynced chunk-wave dispatch handles: chunk waves never fetch
        # logits, so without a bound the loop could queue an entire
        # prompt's chunks on device ahead of a just-arrived interactive
        # request — recreating the head-of-line block chunking removes
        self._prefill_inflight: List[Any] = []
        # request-lifecycle spans (strict no-op unless config.tracing is
        # enabled — the scheduler loop only ever pays an attribute read)
        self.tracer = SpanTracer(getattr(config, "tracing", None))
        # --- goodput attribution plane (r11) ---
        # every XLA compile is attributed to the dispatch that triggered
        # it (phase + shape signature → compile_events.jsonl + the
        # shape_ladder_coverage gauge readiness consumes), and the loop
        # below books its wall time into exclusive buckets whose
        # fractions sum to 1.0 of observed wall. The coverage
        # denominator is the EXACT enumerated shape ladder (r14,
        # inference/precompile.py) — the same rung list the AOT
        # precompiler drives — so a fully-precompiled engine reads
        # coverage 1.0 and latches ready with zero traffic.
        gp = getattr(config, "goodput", None)
        self._ladder = precompile_lib.enumerate_ladder(
            config, model_config, single_device=self.mesh is None
        )
        self._ladder_fingerprint = precompile_lib.ladder_fingerprint(
            config, model_config, single_device=self.mesh is None,
            attn_impl=self._attn_impl,
        )
        self.compiles = goodput.CompileTracker(
            events_path=getattr(gp, "compile_events_path", "") or "",
            ladder_size=len(self._ladder),
            fingerprint=self._ladder_fingerprint,
            max_events_bytes=int(
                getattr(gp, "compile_events_max_bytes", 8_000_000)
            ),
        )
        self.ledger = goodput.GoodputLedger(
            "engine", goodput.ENGINE_BUCKETS, remainder="idle",
            productive=goodput.ENGINE_PRODUCTIVE,
            jsonl_path=getattr(gp, "jsonl_path", "") or "",
            compile_tracker=self.compiles,
        )
        # cumulative host seconds of the loop thread per phase, read off
        # the same spans (metrics(): total_loop_*_s, total_loop_iterations)
        self._loop_s = dict.fromkeys(
            ("admit", "decode_dispatch", "decode_fetch", "decode_process",
             "idle"), 0.0,
        )
        self.total_loop_iterations = 0
        self._ready_quiet_s = float(getattr(gp, "ready_quiet_s", 3.0))
        self._ready_min_requests = int(
            getattr(gp, "ready_min_requests", 1)
        )
        self._started_at = time.monotonic()
        self._ready_latched = False
        self._completed_requests = 0  # non-abort finishes (readiness)
        # native latency histograms per scheduling class (always on —
        # span-derived percentiles only exist while tracing is enabled
        # AND the spans haven't been drained; these are the durable
        # latency source the fleet rollup consumes)
        self._hists = {
            name: {cls: Histogram() for cls in SCHED_CLASSES}
            for name in (
                "queue_wait_seconds", "ttft_seconds",
                "request_latency_seconds",
            )
        }
        # EWMA throughput gauges (tokens/s), updated by the loop thread
        self._decode_tps = 0.0
        self._prefill_tps = 0.0
        self._last_decode_mark: Optional[float] = None
        # pause-window bookkeeping: pause() stamps, continue_generation()
        # records the span (the weight-update window the client sits out)
        self._pause_start: Optional[float] = None
        # on-demand jax.profiler capture (POST /profile → request_profile):
        # (n_busy_steps, PhaseProfiler) armed here, consumed on the loop
        # thread — the profiler must bracket the device dispatches, which
        # only the loop thread issues. The lock makes arm-vs-arm (HTTP
        # handler threads) and arm-vs-consume (loop thread) atomic.
        self._profile_lock = threading.Lock()
        self._profile_pending: Optional[tuple] = None
        self._profile_stack = None
        self._profile_left = 0

    def _place_params(self, params: Params) -> Params:
        """Host or device pytree → this engine's param placement."""
        if self.mesh is None:
            return jax.device_put(params)
        return jax.device_put(params, self._param_shardings)

    def _copy_params_placed(self, params: Params) -> Params:
        """Fresh, correctly-placed COPY of a (possibly device-resident)
        pytree — the source may later be donated by its owner, so aliasing
        is never acceptable."""
        if self.mesh is None:
            return jax.tree_util.tree_map(
                lambda p: jnp.array(p, dtype=self.dtype, copy=True), params
            )
        key = "copy_params"
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                lambda t: jax.tree_util.tree_map(
                    lambda p: jnp.copy(p.astype(self.dtype)), t
                ),
                out_shardings=self._param_shardings,
            )
        placed = jax.device_put(params, self._param_shardings)
        return self._jit_cache[key](placed)

    def _place_leaf(self, name: str, arr) -> Any:
        """Host array → this engine's placement for ONE named parameter
        leaf. The streamed ingest path places per chunk on the HTTP
        handler thread, so h2d transfer overlaps live decode instead of
        bursting at the flip."""
        x = jnp.asarray(arr, dtype=self.dtype)
        if self.mesh is None:
            return x
        if self._leaf_shardings is None:
            from areal_tpu.utils.weight_transfer import flatten_params

            # the shardings tree mirrors the params tree, so flattening
            # it yields the same '/'-joined leaf names the wire uses
            self._leaf_shardings = dict(
                flatten_params(self._param_shardings)
            )
        sh = self._leaf_shardings.get(name)
        return jax.device_put(x, sh) if sh is not None else x

    @property
    def attn_impl(self) -> str:
        """The paged-attention backend in use ("kernel" | "jnp"): the
        config's ``attn_impl`` with "auto" resolved against the device."""
        return self._attn_impl

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def start(self):
        if self._running:
            raise RuntimeError("engine already started")
        self._running = True
        self._loop_error = None
        self._thread = threading.Thread(target=self._run_loop, daemon=True)
        self._thread.start()
        return self

    def _run_loop(self):
        """The loop thread. An error that ends ``_loop`` (a device failure
        inside an admission or a decode) fails every request and command
        that waits on it, and every later ``submit``, with that error: a
        dead loop used to leave its callers on futures nothing resolves."""
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — handed to the callers
            logger.exception("engine loop died; failing its callers")
            self._loop_error = e
            self._running = False
            waiting = list(self._active.values()) + list(self._pending)
            for q in (self._admit_queue, self._command_queue):
                while True:
                    try:
                        waiting.append(q.get_nowait())
                    except queue.Empty:
                        break
            for item in waiting:
                fut = item[2] if isinstance(item, tuple) else item.future
                if not fut.done():
                    fut.set_exception(e)

    def stop(self):
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # a flip queued after (or racing) the loop's last iteration
        # would leave its waiter blocked out the full timeout — close
        # the store: the pending flip fails now and later queue_flip
        # calls fail fast
        self.weights.close()
        self._policies.close()
        # non-HTTP deployments: drain remaining spans to the configured
        # JSONL sink (the server path drains via GET /trace instead)
        self.tracer.flush()
        # final goodput snapshot to the configured stream (no-op
        # without a path; live deployments scrape /metrics instead)
        self.ledger.export_jsonl()

    # ------------------------------------------------------------------
    # Public API (thread-safe)
    # ------------------------------------------------------------------
    def submit(self, payload: Dict[str, Any]) -> Future:
        fut: Future = Future()
        if self._loop_error is not None:
            fut.set_exception(self._loop_error)
            return fut
        req = _parse_request(payload, fut)
        trace_ctx = payload.get("trace_ctx")
        if trace_ctx:
            # incoming cross-process trace context (X-Areal-Trace): every
            # span this engine records for the rid joins the originating
            # episode's timeline
            self.tracer.bind_trace(req.rid, str(trace_ctx))
        if req.policy:
            # resolve the handle on the CALLER thread — an unknown name
            # or dead selector is the client's mistake, rejected as a
            # typed 4xx (never retried) before the request touches the
            # queue. The canary split advances HERE, exactly once per
            # request (admission only re-checks liveness).
            try:
                name, ver = self._policies.resolve(req.policy)
            except UnknownPolicyError as e:
                self.tracer.unbind_trace(req.rid)
                fut.set_exception(e)
                return fut
            req.policy, req.policy_version = name, ver
        bs = self.cache_config.page_size
        if len(req.input_ids) >= self.config.max_model_len:
            fut.set_exception(
                ValueError(
                    f"prompt length {len(req.input_ids)} >= max_model_len "
                    f"{self.config.max_model_len}"
                )
            )
            return fut
        if -(-len(req.input_ids) // bs) + 1 > self.cache_config.num_pages:
            fut.set_exception(
                ValueError(
                    f"prompt needs more pages than the pool has "
                    f"({self.cache_config.num_pages} x {bs} tokens)"
                )
            )
            return fut
        # bounded admission queue (traffic plane): overflow sheds BULK at
        # the bound and interactive only past twice the bound — queueing
        # unboundedly behind max_num_seqs turns saturation into silent
        # multi-minute tail latency for everyone. Suffix-resume
        # continuations are never shed (they carry client-side progress
        # a 429 would strand). The queue-depth read is racy vs the loop
        # thread, which only makes the bound soft by one or two entries.
        bound = int(getattr(self.config, "max_queued_requests", 0) or 0)
        if bound > 0 and not req.resumed:
            pending_snapshot = list(self._pending)
            queued = self._admit_queue.qsize() + len(pending_snapshot)
            if req.priority != "bulk":
                # the interactive limit must not count RESUMED entries:
                # they bypass the bound themselves (a post-pause resume
                # storm would otherwise shed the protected class while
                # admitting unlimited exempt bulk — priority inversion)
                with self._aq_lock:
                    resumed_q = self._aq_resumed
                resumed_q += sum(
                    r.resumed for r in pending_snapshot
                )
                queued = max(0, queued - resumed_q)
            limit = bound if req.priority == "bulk" else 2 * bound
            if queued >= limit:
                retry_after = float(
                    getattr(self.config, "shed_retry_after_s", 1.0)
                )
                with self._aq_lock:  # handler threads race here too
                    self.requests_shed_total += 1
                self.tracer.instant(
                    "shed", req.rid, sched_class=req.priority,
                    tenant=req.tenant, queued=queued,
                )
                self.tracer.unbind_trace(req.rid)
                fut.set_exception(
                    AdmissionRejectedError(
                        f"admission queue full ({queued} >= {limit} for "
                        f"class {req.priority}); retry after "
                        f"{retry_after}s",
                        retry_after=retry_after,
                        sched_class=req.priority,
                    )
                )
                return fut
        with self._aq_lock:
            # both counters under the lock: concurrent handler threads
            # must not lose submitted_total increments
            self._class_submitted[req.priority] += 1
            self._aq_class[req.priority] += 1
            if req.resumed:
                self._aq_resumed += 1
        self._admit_queue.put(req)
        return fut

    def generate(self, payload: Dict[str, Any], timeout: float = 3600.0) -> Dict:
        return self.submit(payload).result(timeout=timeout)

    def pause(self):
        """Abort in-flight requests; stop admitting until continue."""
        done = Future()
        if not self._paused.is_set():
            self._pause_start = time.monotonic()
        self._paused.set()
        self._command_queue.put(("abort_all", None, done))
        done.result(timeout=60)

    def continue_generation(self):
        self._paused.clear()
        t0, self._pause_start = self._pause_start, None
        if t0 is not None:
            self.tracer.record(
                "pause_window", "__engine__", t0, time.monotonic(),
                model_version=self.model_version,
            )

    def streams_weight_updates(self, method: str = "chunk") -> bool:
        """True when ``method`` ("chunk" | "disk" | "tensors") takes the
        zero-pause streamed route on this engine. The tensors path needs
        single-device serving (its donation-safe copy would race the
        loop thread's jit cache under TP); chunk/disk stream anywhere.
        A stopped engine always uses the legacy command path — there is
        no loop to apply a flip."""
        if not (self._weights_streaming and self._running):
            return False
        if method == "tensors":
            return self.mesh is None
        return True

    def update_weights_from_disk(self, path: str, version: Optional[int] = None):
        if self.streams_weight_updates("disk"):
            # load + place on THIS (handler) thread while decode runs;
            # the loop applies the flip between dispatches and the
            # future resolves once the new version serves
            host = hf_io.load_params(
                path, self.model_config, dtype=self.dtype
            )
            placed = self._place_params(host)
            # a half-streamed chunked push is now obsolete: drop its
            # staged leaves (same supersede rule as the legacy path) so
            # they don't sit pinned until the TTL — and so its straggler
            # chunks can't later queue a stale flip
            self.weights.abort_staging("superseded by disk update")
            v = version if version is not None else self.model_version + 1
            out = self.weights.queue_flip(v, placed).result(timeout=600)
            logger.info(
                f"weights streamed from {path} → v{out} (no pause)"
            )
            return out
        done = Future()
        self._command_queue.put(("update_weights", (path, version), done))
        return done.result(timeout=600)

    def update_weights_from_tensors(
        self, params: Params, version: Optional[int] = None
    ):
        """Colocated path: swap in an already-materialized param pytree
        (role of the reference's NCCL broadcast receive path). The
        caller may later DONATE the source buffers, so both routes copy."""
        if self.streams_weight_updates("tensors"):
            # single-device only (streams_weight_updates gates it), so
            # this is the jit-cache-free branch of the placed copy —
            # safe off the loop thread
            copied = self._copy_params_placed(params)
            self.weights.abort_staging("superseded by tensor update")
            v = version if version is not None else self.model_version + 1
            return self.weights.queue_flip(v, copied).result(timeout=600)
        done = Future()
        self._command_queue.put(("update_weights_tensors", (params, version), done))
        return done.result(timeout=600)

    def update_weights_chunk(self, header: Dict, arrays: Dict[str, Any]):
        """Device-path receive: stage one FFD chunk of host tensors; the
        final chunk assembles + swaps the full pytree (reference NCCL
        receive side, areal/engine/sglang_remote.py:411). Streaming
        engines stage into the WeightStore's shadow buffer on this
        thread — decode never stops — and flip at a dispatch boundary;
        legacy engines stage on the loop thread under the pause."""
        if self.streams_weight_updates("chunk"):
            t0 = time.monotonic()
            self.weights.sweep()
            out = self.weights.ingest_chunk(
                header, arrays, self._place_leaf
            )
            if self.tracer.enabled:
                self.tracer.record(
                    "weight_stream_chunk", "__engine__", t0,
                    time.monotonic(),
                    chunk_index=int(header["chunk_index"]),
                    n_chunks=int(header["n_chunks"]),
                    leaves=len(arrays),
                    bytes=sum(
                        int(spec.get("nbytes", 0))
                        for spec in header.get("params", [])
                    ),
                    model_version=int(header["version"]),
                )
            if out is None:
                return {"staged": self.weights.staged_chunks}
            version, tree = out
            v = self.weights.queue_flip(version, tree).result(timeout=600)
            return {"version": v, "complete": True}
        done = Future()
        self._command_queue.put(("update_weights_chunk", (header, arrays), done))
        return done.result(timeout=600)

    # ------------------------------------------------------------------
    # Multi-policy plane (r19): named-handle weight pushes + lifecycle.
    # All of these run on HTTP handler threads — the registry is
    # thread-safe and a push never touches self.params, so there is no
    # flip, no pipeline drain, and NO pause span by construction: a new
    # named version simply starts serving at its next admission wave.
    # ------------------------------------------------------------------
    def _check_policy_capable(self):
        if not self._compact_enabled:
            # named cohorts ride the row-gathered (compact) decode
            # dispatch; the full-slot TP dispatch cannot split params
            # per cohort
            raise RuntimeError(
                "multi-policy serving needs the compacted decode "
                "dispatch (single-device serving with "
                "decode_compact=true)"
            )

    def update_policy_from_disk(
        self,
        name: str,
        path: str,
        version: Optional[int] = None,
        canary_fraction: float = 0.0,
    ) -> int:
        """Install checkpoint ``path`` on named line ``name`` (register
        on first push; ``canary_fraction > 0`` stages it as the line's
        canary at that traffic split). Load + place happen on THIS
        handler thread while decode runs."""
        self._check_policy_capable()
        host = hf_io.load_params(path, self.model_config, dtype=self.dtype)
        placed = self._place_params(host)
        v = self._policies.push(
            name, placed, version=version,
            canary_fraction=canary_fraction,
        )
        self.tracer.instant(
            "policy_push", "__engine__", policy=name, version=v,
            canary_fraction=canary_fraction,
        )
        return v

    def update_policy_chunk(
        self, name: str, header: Dict, arrays: Dict[str, Any]
    ):
        """Streamed FFD-chunk push targeting a named line (the wire
        format of ``update_weights_chunk`` plus a policy name; the
        final chunk's header may carry ``canary_fraction``)."""
        self._check_policy_capable()
        out = self._policies.ingest_chunk(
            name, header, arrays, self._place_leaf
        )
        if out is None:
            return {"staged": int(header["chunk_index"]) + 1}
        self.tracer.instant(
            "policy_push", "__engine__", policy=name, version=out,
            canary_fraction=float(header.get("canary_fraction", 0.0)),
        )
        return {"version": out, "complete": True, "policy": name}

    def promote_policy(self, name: str) -> int:
        """Canary → stable on line ``name``. Registry state only: no
        buffer movement, no pause span, and the promoted version's KV
        namespace survives (its version int is unchanged)."""
        v = self._policies.promote(name)
        self.tracer.instant(
            "policy_promote", "__engine__", policy=name, version=v
        )
        return v

    def retire_policy(self, name: str):
        self._policies.retire(name)
        self.tracer.instant(
            "policy_retire", "__engine__", policy=name
        )

    def set_policy_split(self, name: str, canary_fraction: float):
        self._policies.set_split(name, canary_fraction)

    def policy_status(self) -> Dict[str, Any]:
        return self._policies.stats()

    def precompile(self) -> Optional[Dict[str, Any]]:
        """AOT-precompile the shape ladder per ``config.precompile``
        (off | ladder | replay). Safe to run concurrently with serving
        — /health reports ``warming`` with rising coverage until the
        ladder lands, then latches ready with zero traffic. Returns the
        precompiler summary (None when mode is off); a mismatched
        replay stream raises ``precompile_lib.ReplayMismatchError``."""
        pc = getattr(self.config, "precompile", None)
        mode = getattr(pc, "mode", "off") if pc is not None else "off"
        if mode == "off":
            return None
        return precompile_lib.Precompiler(self).run(
            mode, replay_path=getattr(pc, "replay_path", "")
        )

    def readiness(self) -> Dict[str, Any]:
        """Server readiness for /health: ``warming`` while the initial
        compile storm runs, ``ready`` after.

        Warming begins at the FIRST observed XLA compile (an idle fresh
        server is ready — it has nothing to warm yet, and reporting
        warming before any traffic would deadlock it out of rotation
        forever) and ends when the shape ladder is covered, the engine
        goes ``ready_quiet_s`` without compiling, or it has COMPLETED
        ``ready_min_requests`` requests end-to-end (under sustained
        traffic a serving engine may never see a compile-quiet window —
        successfully finishing requests is the stronger proof). Ready
        LATCHES: a long-serving engine compiling one incremental shape
        must not drop out of fleet rotation mid-run — readiness answers
        "is the cold-start storm over", not "did anything ever compile
        again". An AOT-precompiled or warmup-driven engine therefore
        reports warming from its first startup compile until its
        ladder lands or its first real completions prove it serves."""
        now = time.monotonic()
        cov = self.compiles.coverage()
        quiet = self.compiles.quiet_s(now)  # inf before the 1st compile
        served = (
            self._ready_min_requests > 0
            and self._completed_requests >= self._ready_min_requests
        )
        ready = (
            self._ready_latched
            or cov >= 1.0
            or served
            or quiet >= self._ready_quiet_s
        )
        if ready and (cov >= 1.0 or served or quiet != float("inf")):
            # latch only once a real warmup ran its course — an idle
            # fresh server is *servable* but still cold, and its first
            # compile storm must still read as warming
            if not self._ready_latched:
                self._ready_latched = True
                # cold-start timeline mark for trace_report --coldstart:
                # the events stream now spans header → compiles → ready
                self.compiles.append_event(
                    {
                        "kind": "lifecycle",
                        "event": "ready",
                        "ladder_coverage": round(cov, 4),
                        "compiles_total": self.compiles.compiles_total,
                        "uncached_total": (
                            self.compiles.uncached_compiles_total
                        ),
                        "cache_hits_total": (
                            self.compiles.cache_hits_total
                        ),
                    }
                )
        return {
            "state": "ready" if ready else "warming",
            "ladder_coverage": round(cov, 4),
            "compiled_shapes": self.compiles.compiled_shapes(),
            "shape_ladder_size": self.compiles.ladder_size,
            "warmup_eta_s": self.compiles.warmup_eta_s(),
            "quiet_s": round(min(quiet, now - self._started_at), 3),
        }

    def latency_histograms(self) -> Dict[str, Histogram]:
        """Per-class native Prometheus histograms keyed the way
        ``render_prometheus(histograms=...)`` wants them."""
        return {
            f'{name}{{sched_class="{cls}"}}': h
            for name, per_cls in self._hists.items()
            for cls, h in per_cls.items()
        }

    def metrics(self) -> Dict[str, float]:
        num_pages = max(1, self.cache_config.num_pages)
        m = dict(
            running_requests=len(self._active),
            queued_requests=self._admit_queue.qsize() + len(self._pending),
            free_slots=len(self._free_slots),
            free_pages=self.pm.n_free,
            # fraction of the pool holding live KV (active slots + parked
            # prefix-registry pages + the reserved trash page)
            kv_page_utilization=1.0 - self.pm.n_free / num_pages,
            registry_entries=len(self.registry),
            # EWMA throughput over recent dispatches (0 while idle-fresh)
            decode_tokens_per_sec=round(self._decode_tps, 2),
            prefill_tokens_per_sec=round(self._prefill_tps, 2),
            # decode tail compaction occupancy: rows the last chunk
            # dispatched vs rows carrying live requests, plus lifetime
            # totals (rows_active/rows_dispatched → mean occupancy)
            decode_rows_dispatched=self._decode_rows_dispatched,
            decode_rows_active=self._decode_rows_active,
            total_decode_chunks=self.total_decode_chunks,
            total_loop_iterations=self.total_loop_iterations,
            total_loop_admit_s=round(self._loop_s["admit"], 6),
            total_loop_decode_dispatch_s=round(
                self._loop_s["decode_dispatch"], 6
            ),
            total_loop_decode_fetch_s=round(self._loop_s["decode_fetch"], 6),
            total_loop_decode_process_s=round(
                self._loop_s["decode_process"], 6
            ),
            total_loop_idle_s=round(self._loop_s["idle"], 6),
            total_rows_dispatched=self.total_rows_dispatched,
            total_rows_active=self.total_rows_active,
            decode_occupancy=round(
                self.total_rows_active
                / max(1, self.total_rows_dispatched), 4
            ),
            total_generated_tokens=self.total_generated_tokens,
            total_prompt_tokens=self.total_prompt_tokens,
            total_cached_prompt_tokens=self.total_cached_prompt_tokens,
            # prefix-cache observability (radix and flat modes alike):
            # token-level hit rate is the sibling-dedup + claim signal,
            # claim-level is the tree's match success rate
            prefix_cache_hit_rate=round(
                self.total_cached_prompt_tokens
                / max(1, self.total_prompt_tokens), 4
            ),
            prefix_cached_tokens_total=self.total_cached_prompt_tokens,
            prefix_claim_hit_rate=round(
                getattr(self.registry, "hits", 0)
                / max(1, getattr(self.registry, "claims", 0)), 4
            ),
            prefix_cache_nodes=len(self.registry),
            prefix_cache_pages=getattr(
                self.registry, "pages", len(self.registry)
            ),
            prefix_cow_copies_total=self.total_cow_copies,
            prefix_evicted_pages_total=getattr(
                self.registry, "evicted_pages", 0
            ),
            total_requests=self.total_requests,
            total_aborted=self.total_aborted,
            total_preemptions=self.total_preemptions,
            requests_shed_total=self.requests_shed_total,
            deadline_preemptions_total=self.deadline_preemptions_total,
            deadline_misses_total=self.deadline_misses_total,
            model_version=self.model_version,
            paused=float(self._paused.is_set()),
            # zero-pause weight plane (r13): shadow staging + pinned
            # old-version buffers + applied flips
            weight_staging_bytes=self.weights.staging_bytes,
            weight_staging_aborts_total=float(
                self.weights.staging_aborts_total
            ),
            weight_pinned_requests=float(self.weights.pinned_requests()),
            weight_buffer_versions=float(
                len(self.weights.buffer_versions())
            ),
            weight_flips_total=float(self.weights.flips_total),
            trace_spans=len(self.tracer) if self.tracer.enabled else 0,
            # ring-buffer overflow count: a truncated trace must be
            # VISIBLY truncated, not silently missing its oldest spans
            tracing_dropped_spans_total=float(self.tracer.dropped),
        )
        # goodput attribution (r11): exclusive wall-time bucket
        # fractions + duty cycle + effective tok/s, recompile bill, and
        # the readiness gauge the fleet plane mirrors from /health
        m.update(self.ledger.metrics())
        m.update(self.compiles.metrics())
        m["server_ready"] = float(self.readiness()["state"] == "ready")
        # per-class composition (traffic plane): running from an active
        # snapshot, queued = admit-queue class counters + a pending-list
        # scan (both metrics-grade racy reads — the loop thread owns the
        # structures)
        active_reqs = list(self._active.values())
        pending_reqs = list(self._pending)
        with self._aq_lock:
            aq = dict(self._aq_class)
        for cls in SCHED_CLASSES:
            m[f"sched_class_{cls}_running"] = sum(
                r.priority == cls for r in active_reqs
            )
            m[f"sched_class_{cls}_queued"] = max(0, aq[cls]) + sum(
                r.priority == cls for r in pending_reqs
            )
            m[f"sched_class_{cls}_submitted_total"] = (
                self._class_submitted[cls]
            )
        if self._chunk_budget > 0:
            # chunked-prefill surface (r15): present ONLY when chunking
            # resolved on — chunking off is a strict no-op, metric keys
            # included
            m.update(
                prefill_chunks_total=self.prefill_chunks_total,
                prefill_chunk_preemptions_total=(
                    self.prefill_chunk_preemptions_total
                ),
                # 1 while EVERY admission dispatch so far stayed within
                # ~one chunk of prefill — a stall-escape admission
                # (uncapped dispatch under cache thrash) zeroes it, so
                # the gauge is a measurement of the serving history,
                # not a config echo
                ttft_bounded=float(
                    self.prefill_chunk_stall_escapes == 0
                ),
            )
        if self._spec_configured:
            # spec gauges exist ONLY when speculation is configured —
            # spec off is a strict no-op, metric surface included
            gate = self._spec_gate
            m.update(
                spec_enabled=float(not gate.disabled),
                spec_chunks_total=self.total_spec_chunks,
                spec_draft_tokens_total=self.spec_draft_tokens_total,
                spec_accepted_tokens_total=self.spec_accepted_tokens_total,
                spec_accept_rate=round(
                    self.spec_accepted_tokens_total
                    / max(1, self.spec_draft_tokens_total), 4
                ),
                spec_accept_rate_ewma=round(gate.ewma or 0.0, 4),
            )
        if self._kv_tiers is not None:
            # KV tier surface (r16): present ONLY with kv_spill on —
            # spill off is a strict no-op, metric keys included
            t = self._kv_tiers
            m.update(
                kv_tier_host_pages=t.host_pages,
                kv_tier_host_bytes=t.host_bytes_used,
                kv_tier_host_capacity_bytes=t.host_capacity,
                kv_tier_pending_pages=t.pending_pages,
                kv_tier_spilled_pages_total=t.spilled_pages_total,
                kv_tier_spilled_bytes_total=t.spilled_bytes_total,
                kv_tier_promoted_pages_total=t.promoted_pages_total,
                kv_tier_promoted_bytes_total=t.promoted_bytes_total,
                kv_tier_dropped_pages_total=t.dropped_pages_total,
                kv_tier_dropped_bytes_total=t.dropped_bytes_total,
                kv_tier_host_claim_hits_total=t.claims_promoted_total,
                # fraction of claims that touched the host tier — the
                # "returning session saved by spill" signal
                kv_tier_host_claim_hit_rate=round(
                    t.claims_promoted_total
                    / max(1, getattr(self.registry, "claims", 0)), 4
                ),
                kv_tier_host_cached_tokens_total=(
                    self.total_host_cached_tokens
                ),
                kv_tier_disk_pages=t.disk_pages,
                kv_tier_disk_bytes=t.disk_bytes_used,
                kv_tier_disk_spilled_pages_total=t.disk_spilled_pages_total,
                kv_tier_disk_loaded_pages_total=t.disk_loaded_pages_total,
            )
        if self._kv_ship:
            # shipping surface (r16): present ONLY with kv_ship on
            m.update(
                kv_ship_exports_total=self.kv_ship_exports_total,
                kv_ship_imports_total=self.kv_ship_imports_total,
                kv_ship_pages_out_total=self.kv_ship_pages_out_total,
                kv_ship_pages_in_total=self.kv_ship_pages_in_total,
                kv_ship_failures_total=self.kv_ship_failures_total,
            )
        if self._policies.active:
            # multi-policy surface (r19): present ONLY once a named
            # policy has been pushed — single-policy mode is a strict
            # no-op, metric keys included. Literal kwargs (not a blind
            # dict merge) so ARL003's static extraction sees every name.
            pstats = self._policies.metrics()
            m.update(
                policy_lines=pstats["policy_lines"],
                policy_buffers_resident=pstats["policy_buffers_resident"],
                policy_buffers_host=pstats["policy_buffers_host"],
                policy_pinned_requests=pstats["policy_pinned_requests"],
                policy_pushes_total=pstats["policy_pushes_total"],
                policy_promotes_total=pstats["policy_promotes_total"],
                policy_demotions_total=pstats["policy_demotions_total"],
                policy_reloads_total=pstats["policy_reloads_total"],
                policy_staging_bytes=pstats["policy_staging_bytes"],
                policy_cache_namespaces=float(len(self._policy_caches)),
            )
        return m

    # ------------------------------------------------------------------
    # Engine loop (single owner of device state)
    # ------------------------------------------------------------------
    def _loop(self):
        # compiles fired outside an explicit dispatch_scope (helper jits
        # like pack_host) still attribute to this engine's tracker
        goodput.set_thread_tracker(self.compiles, phase="engine")
        led = self.ledger
        while self._running:
            self.total_loop_iterations += 1
            self._maybe_start_profile()
            did_flip = False
            if self.weights.flip_pending:
                # the atomic weight flip — what remains of the old pause
                # window; booking it to weight_pause keeps the ledger
                # honest about how little that is (one pipeline drain)
                with led.bucket("weight_pause"):
                    did_flip = self._maybe_flip_weights()
            self._sweep_tick += 1
            if self._sweep_tick >= 256:
                # abandoned-staging TTL sweep (cheap, amortized): a
                # client that died mid-stream must not pin staging
                self._sweep_tick = 0
                self.weights.sweep()
                if self._policies.active:
                    self._policies.sweep()
            if self._policies.dirty:
                # a push/promote/retire superseded a (policy, version):
                # its KV namespace is garbage for future claimants —
                # flush it here because the loop thread owns the
                # namespace map (the registry only signals)
                self._flush_retired_policies()
            if self._paused.is_set() or not self._command_queue.empty():
                # command work (weight swaps, aborts) and every paused
                # moment book to weight_pause — the capacity a weight
                # update takes from serving, measured from the server's
                # own clock
                with led.bucket("weight_pause"):
                    did_work = self._drain_commands() or did_flip
            else:
                did_work = self._drain_commands() or did_flip
            if not self._paused.is_set():
                if (
                    self._pending
                    or self._active
                    or not self._admit_queue.empty()
                ):
                    ls = self._loop_s
                    retire_s = ls["decode_fetch"] + ls["decode_process"]
                    with led.bucket("prefill") as b:
                        did_work |= self._admit()
                    # chunks retired inside an admission (a deadline
                    # preemption drains the pipeline) stay decode's
                    retire_s -= ls["decode_fetch"] + ls["decode_process"]
                    ls["admit"] += b.duration + retire_s
                else:
                    did_work |= self._admit()
                did_work |= self._decode()  # buckets decode/spec inside
            self._maybe_stop_profile(did_work)
            if not did_work:
                # idle/pause gap: the decode-rate EWMA must not absorb it
                # (the next chunk's dt would span the whole quiet period
                # and crater the gauge)
                self._last_decode_mark = None
                with led.bucket(
                    "weight_pause" if self._paused.is_set() else "idle",
                    poll=True,  # one span per quiet stretch, not per sleep
                ) as b:
                    time.sleep(0.001)
                self._loop_s["idle"] += b.duration
        led.end_poll()
        self._maybe_stop_profile(did_work=True, force=True)
        goodput.set_thread_tracker(None)

    # ------------------------------------------------------------------
    # On-demand profiler capture (POST /profile)
    # ------------------------------------------------------------------
    def request_profile(self, steps: int, out_dir: Optional[str] = None) -> str:
        """Arm a jax.profiler capture of the next ``steps`` BUSY engine
        loop iterations (admission/decode/command work; idle spins don't
        count). Returns the directory the XPlane trace will land in.
        One capture at a time — a second request while armed/running is
        an error, not a silent re-arm."""
        from areal_tpu.api.cli_args import ProfilingConfig
        from areal_tpu.utils.profiling import PhaseProfiler

        if steps <= 0:
            raise ValueError(f"profile steps must be positive, got {steps}")
        if out_dir is None:
            import tempfile

            out_dir = tempfile.mkdtemp(prefix="areal_tpu_profile_")
        prof = PhaseProfiler(
            ProfilingConfig(enabled=True, steps=[0]), out_dir, "", ""
        )
        trace_dir = os.path.join(prof.trace_root, "step0")
        with self._profile_lock:
            # check-and-arm atomically: concurrent POST /profile handler
            # threads must not silently overwrite each other's capture
            if (
                self._profile_pending is not None
                or self._profile_stack is not None
            ):
                raise RuntimeError(
                    "a profile capture is already in progress"
                )
            self._profile_pending = (int(steps), prof)
        return trace_dir

    def _maybe_start_profile(self):
        with self._profile_lock:
            if (
                self._profile_pending is None
                or self._profile_stack is not None
            ):
                return
            steps, prof = self._profile_pending
            # pending → running in one critical section: request_profile
            # sees exactly one of the two slots occupied at all times
            import contextlib

            stack = contextlib.ExitStack()
            self._profile_stack = stack
            self._profile_pending = None
            self._profile_left = steps
        stack.enter_context(prof.step(0))

    def _maybe_stop_profile(self, did_work: bool, force: bool = False):
        if self._profile_stack is None:  # loop thread owns the stack
            return
        if did_work:
            self._profile_left -= 1
        if force or self._profile_left <= 0:
            with self._profile_lock:
                stack, self._profile_stack = self._profile_stack, None
            try:
                stack.close()
            except Exception as e:  # profiling must never kill serving
                logger.warning(f"profiler stop failed: {e}")

    def _maybe_flip_weights(self) -> bool:
        """Apply a pending streamed weight flip at a dispatch boundary
        (loop thread). The pipeline is drained first — bounded by
        ``decode_pipeline`` in-flight chunks, milliseconds, with no
        client-visible abort — so chunk version attribution stays exact;
        then the swap is a pointer flip plus a registry flush. Under
        ``flip_policy="pin"`` the requests in flight keep decoding on
        the outgoing buffer (one store pin each; the decode loop
        dispatches each version cohort with its own params); under
        ``"resume"`` they resolve with ``stop_reason="abort"`` and the
        client's suffix-resume loop continues them on the new version —
        either way every token's recorded weight version is exact."""
        flip = self.weights.take_flip()
        if flip is None:
            return False
        version, params, fut = flip
        t0 = time.monotonic()
        try:
            if version < self.model_version:
                raise ValueError(
                    f"stale weight flip: v{version} < served "
                    f"v{self.model_version}"
                )
            self._drain_pipeline()
            policy = self._wt_cfg.flip_policy
            if policy == "pin" and not self._compact_enabled:
                # pinning needs the compacted (cohort-capable) decode
                # dispatch; full-slot engines abort-and-resume instead
                policy = "resume"
            old_version, old_params = self.model_version, self.params
            pinned = 0
            if policy == "resume":
                # a default-line flip only aborts DEFAULT-line requests:
                # named policy cohorts decode on their own registry
                # buffers and are untouched (a canary push on `actor`
                # must not disturb `opponent` traffic — same rule)
                for slot in list(self._active):
                    if not self._active[slot].policy:
                        self._finish(slot, "abort")
            elif version != old_version:
                for req in self._active.values():
                    if not req.policy and req.weight_version == old_version:
                        self.weights.retain(old_version, old_params)
                        pinned += 1
            self.params = params
            self.model_version = version
            # cached KV (radix tree included) is old-policy: a new
            # claimant must never ride it. Active slots' own pages are
            # request-owned and survive the flush.
            self.registry.flush(self.pm)
            self.weights.flips_total += 1
            now = time.monotonic()
            self.tracer.record(
                "weight_update", "__engine__", t0, now, cmd="flip",
                model_version=version,
            )
            self.tracer.instant(
                "weight_flip", "__engine__", model_version=version,
                policy=policy, pinned=pinned,
                flip_ms=round((now - t0) * 1e3, 3),
            )
            logger.info(
                f"weights flipped → v{version} (policy={policy}, "
                f"{pinned} request(s) pinned to v{old_version}, "
                f"{(now - t0) * 1e3:.1f} ms, no pause)"
            )
            fut.set_result(version)
        except Exception as e:
            fut.set_exception(e)
        return True

    def _fence_unpaused_swap(self) -> None:
        """Guard the LEGACY (command-path) weight swaps against a live
        engine: a streamed client never pauses, so a
        ``--no-weight-streaming`` server can receive a swap mid-decode —
        silently continuing in-flight slots on old KV + new weights
        (unpinned, mis-stamped) would corrupt the version fence. Abort
        them into the suffix-resume contract instead; under the legacy
        paused protocol the pause already aborted everything, so this
        is a no-op there."""
        default_slots = [
            sl for sl, r in self._active.items() if not r.policy
        ]
        if default_slots and not self._paused.is_set():
            logger.warning(
                f"legacy weight swap on an unpaused engine: aborting "
                f"{len(default_slots)} in-flight request(s) into "
                f"suffix-resume (enable weights.streaming for "
                f"zero-pause flips)"
            )
            # named policy cohorts keep decoding: the swap replaces the
            # DEFAULT line's params only, and their KV namespaces are
            # (policy, version)-keyed
            for slot in default_slots:
                self._finish(slot, "abort")

    def _loop_span(self, name: str, seq: Optional[int] = None, **attrs):
        """A span of the loop thread (``utils.tracing.span``), stamped with
        the decode-chunk sequence number it belongs to."""
        return tracing.span(
            name, self.tracer, _LOOP_RID,
            seq=self.total_decode_chunks if seq is None else seq, **attrs,
        )

    def _drain_commands(self) -> bool:
        did = False
        while True:
            try:
                cmd, arg, done = self._command_queue.get_nowait()
            except queue.Empty:
                return did
            did = True
            t_cmd = time.monotonic()
            cmd_span = self._loop_span("engine.commands", command=cmd)
            cmd_span.__enter__()
            try:
                # every command needs a quiesced device pipeline: aborts
                # must not race in-flight chunks, and weight swaps would
                # mis-attribute in-flight tokens to the new version
                self._drain_pipeline()
                if cmd == "abort_all":
                    for slot in list(self._active):
                        self._finish(slot, "abort")
                    done.set_result(True)
                elif cmd == "update_weights":
                    path, version = arg
                    self._fence_unpaused_swap()
                    host = hf_io.load_params(
                        path, self.model_config, dtype=self.dtype
                    )
                    self.params = self._place_params(host)
                    # cached KV is from the old policy — never reuse it;
                    # drop any abandoned device-path staging too
                    self.registry.flush(self.pm)
                    self._staged = {}
                    self._staging_key = None
                    self.weights.abort_staging("superseded by disk update")
                    self.model_version = (
                        version
                        if version is not None
                        else self.model_version + 1
                    )
                    logger.info(
                        f"weights updated from {path} → v{self.model_version}"
                    )
                    done.set_result(self.model_version)
                elif cmd == "update_weights_chunk":
                    header, arrays = arg
                    version = int(header["version"])
                    # key staging on (version, n_chunks): a retry with a
                    # different FFD grouping must not merge stale leaves
                    stage_key = (version, int(header["n_chunks"]))
                    if getattr(self, "_staging_key", None) != stage_key:
                        self._staging_key = stage_key
                        self._staged: Dict[str, Any] = {}
                        self._staged_chunks = set()
                    self._staged.update(arrays)
                    self._staged_chunks.add(int(header["chunk_index"]))
                    if len(self._staged_chunks) < int(header["n_chunks"]):
                        done.set_result({"staged": len(self._staged_chunks)})
                        continue
                    from areal_tpu.utils.weight_transfer import (
                        unflatten_params,
                    )

                    self._fence_unpaused_swap()
                    host = jax.tree_util.tree_map(
                        lambda a: jnp.asarray(a, dtype=self.dtype),
                        unflatten_params(self._staged),
                    )
                    self.params = self._place_params(host)
                    self._staged = {}
                    self._staged_chunks = set()
                    self._staging_key = None
                    self.model_version = version
                    self.registry.flush(self.pm)
                    logger.info(
                        f"weights updated via device path → v{version}"
                    )
                    done.set_result({"version": version, "complete": True})
                elif cmd == "update_weights_tensors":
                    params, version = arg
                    self._fence_unpaused_swap()
                    # the caller may later DONATE these buffers — copy
                    self.params = self._copy_params_placed(params)
                    self.registry.flush(self.pm)
                    self._staged = {}
                    self._staging_key = None
                    self.weights.abort_staging(
                        "superseded by tensor update"
                    )
                    self.model_version = (
                        version
                        if version is not None
                        else self.model_version + 1
                    )
                    done.set_result(self.model_version)
                elif cmd == "kv_export":
                    done.set_result(self._kv_export(arg))
                elif cmd == "kv_import":
                    done.set_result(self._kv_import(*arg))
                else:  # pragma: no cover
                    done.set_exception(ValueError(f"unknown command {cmd}"))
                if cmd.startswith("update_weights"):
                    self.tracer.record(
                        "weight_update", "__engine__", t_cmd,
                        time.monotonic(), cmd=cmd,
                        model_version=self.model_version,
                    )
            except Exception as e:  # surface errors to the caller
                done.set_exception(e)
            finally:
                cmd_span.__exit__(None, None, None)

    # ------------------------------------------------------------------
    # Page accounting
    # ------------------------------------------------------------------
    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocate n pages, evicting the prefix registry if needed
        (default namespace first — it is the hot one — then the named
        policy namespaces)."""
        pages = self.pm.alloc(n)
        if pages is None:
            self.registry.evict(self.pm, n)
            for cache in self._policy_caches.values():
                if self.pm.n_free >= n:
                    break
                cache.evict(self.pm, n - self.pm.n_free)
            pages = self.pm.alloc(n)
        return pages

    # ------------------------------------------------------------------
    # Multi-policy plane (r19): per-(policy, version) KV namespaces
    # ------------------------------------------------------------------
    def _policy_cache(self, name: str, version: int):
        """The prefix cache for one (policy, version) namespace, built
        lazily on first admission. Same mode/grain as the default
        registry, same page pool — isolation is by construction: claims
        and publishes never cross namespaces, so a canary's pages can
        never serve the stable line (or vice versa)."""
        key = (name, version)
        cache = self._policy_caches.get(key)
        if cache is None:
            bs = self.cache_config.page_size
            if self._radix:
                from areal_tpu.ops.paged_attention import pack_factor

                cache = RadixPrefixCache(
                    bs, self.config.prefix_reuse_min,
                    grain=pack_factor(self.model_config.head_dim),
                )
            else:
                cache = PrefixRegistry(bs, self.config.prefix_reuse_min)
            self._policy_caches[key] = cache
        return cache

    def _flush_retired_policies(self):
        """Flush KV namespaces whose (policy, version) no longer serves
        (superseded by a push, or the line retired). Loop thread only —
        it owns the namespace map. Active slots' pages are request-owned
        and survive (same contract as the default registry flush at a
        weight flip)."""
        for key in self._policies.drain_retired():
            cache = self._policy_caches.pop(key, None)
            if cache is not None:
                cache.flush(self.pm)

    # ------------------------------------------------------------------
    # Hierarchical KV tiers (r16): demotion gather / promotion scatter,
    # and the cross-server prefix shipping export/import pair
    # ------------------------------------------------------------------
    def _gather_pages_host(self, pages: List[int]):
        """Blocking device→host read of ``pages``: [L, Hp, n, rows,
        lane] per tensor in the pool's native layout. The device_get
        orders after every dispatched write to those pages, so demotion
        snapshots and exports always see committed content."""
        n = len(pages)
        pad = data_utils.next_bucket_size(n, 8)
        idx = np.zeros(pad, np.int32)  # padding reads the trash page
        idx[:n] = pages
        idx_dev = jnp.asarray(idx)
        with goodput.dispatch_scope(
            self.compiles, "kv_gather", precompile_lib.kv_gather_sig(pad)
        ):
            k, v = model_runner.gather_pages(self.cache, idx_dev)
        k = np.asarray(jax.device_get(k))[:, :, :n]
        v = np.asarray(jax.device_get(v))[:, :, :n]
        return k, v

    def _scatter_pages(self, pages: List[int], k_pool, v_pool) -> None:
        """One batched host→device write of pool-layout page data into
        ``pages`` (promotion flush and shipping import share it)."""
        n = len(pages)
        pad = data_utils.next_bucket_size(n, 8)
        num_pages = self.cache_config.num_pages
        nl, hp, _, rows, lane = self.cache["k"].shape
        dt = self.cache["k"].dtype
        dst = np.full(pad, num_pages, np.int32)
        dst[:n] = pages
        k_np = np.zeros((nl, hp, pad, rows, lane), dt)
        v_np = np.zeros_like(k_np)
        k_np[:, :, :n] = k_pool
        v_np[:, :, :n] = v_pool
        dst_dev = jnp.asarray(dst)
        k_dev, v_dev = jnp.asarray(k_np), jnp.asarray(v_np)
        with goodput.dispatch_scope(
            self.compiles, "kv_scatter", precompile_lib.kv_scatter_sig(pad)
        ):
            self.cache = model_runner.scatter_pages(
                self.cache, dst_dev, k_dev, v_dev
            )

    def _flush_kv_promotions(self) -> None:
        """Dispatch every queued spill-tier promotion as one batched
        scatter. MUST run after a claim loop and before any dispatch
        that could read the promoted pages (the wave prefill and the
        COW copies attend through them); flushing when the wave later
        defers is harmless — the pages are tree-owned and resident."""
        if self._kv_tiers is None:
            return
        pend = self._kv_tiers.drain_pending()
        if not pend:
            return
        nl, hp, _, rows, lane = self.cache["k"].shape
        dt = self.cache["k"].dtype
        n = len(pend)
        k_pool = np.zeros((nl, hp, n, rows, lane), dt)
        v_pool = np.zeros_like(k_pool)
        for i, (_page, sp) in enumerate(pend):
            k_pool[:, :, i] = sp.k
            v_pool[:, :, i] = sp.v
        self._scatter_pages([p for p, _ in pend], k_pool, v_pool)

    def _kv_export(self, tokens: List[int]) -> Dict[str, Any]:
        """Loop-thread kv_export command: the longest committed
        full-page prefix of ``tokens``, in the layout-independent
        canonical form ([L, Hkv, T, D] token-major) shipping needs.
        Reads replicas only — no refcount or LRU effects; spilled pages
        are served straight from the host/disk tier."""
        from areal_tpu.inference import kv_tiers as kv_tiers_lib

        bs = self.cache_config.page_size
        out: Dict[str, Any] = {
            "pages": 0,
            "tokens_matched": 0,
            "page_size": bs,
            "model_version": self.model_version,
        }
        if not self._radix:
            return out
        # promoted-but-unflushed pages hold garbage on device and truth
        # in the pending queue — flush first so resident means readable
        self._flush_kv_promotions()
        nodes = self.registry.match_pages(np.asarray(tokens, np.int32))
        if not nodes:
            return out
        nl, hp, _, rows, lane = self.cache["k"].shape
        dt = self.cache["k"].dtype
        n = len(nodes)
        k_all = np.zeros((nl, hp, n, rows, lane), dt)
        v_all = np.zeros_like(k_all)
        res_idx = [i for i, nd in enumerate(nodes) if nd.page is not None]
        if res_idx:
            k_res, v_res = self._gather_pages_host(
                [nodes[i].page for i in res_idx]
            )
            for j, i in enumerate(res_idx):
                k_all[:, :, i] = k_res[:, :, j]
                v_all[:, :, i] = v_res[:, :, j]
        for i, nd in enumerate(nodes):
            if nd.page is None:
                k_sp, v_sp = self._kv_tiers.export_data(nd)
                k_all[:, :, i] = k_sp
                v_all[:, :, i] = v_sp
        canon_k = kv_tiers_lib.canonical_from_pool(
            k_all, self.model_config.num_kv_heads,
            self.model_config.head_dim,
        )
        canon_v = kv_tiers_lib.canonical_from_pool(
            v_all, self.model_config.num_kv_heads,
            self.model_config.head_dim,
        )
        out.update(
            pages=n,
            tokens_matched=n * bs,
            dtype=canon_k.dtype.name,
            k=canon_k,
            v=canon_v,
        )
        self.kv_ship_exports_total += 1
        self.kv_ship_pages_out_total += n
        return out

    def _kv_import(
        self, tokens: List[int], k, v, src_version: Optional[int]
    ) -> int:
        """Loop-thread kv_import command: re-pack shipped canonical
        pages into this pool's layout, scatter them into freshly
        allocated pages, and hand them to the radix tree as an
        ownership transfer (``add``) — the very next claim serves them
        like any locally-cached prefix. Soft-fails (returns 0) on
        version/geometry mismatch or a dry pool: shipping is an
        optimization, never a correctness dependency."""
        from areal_tpu.inference import kv_tiers as kv_tiers_lib

        if not self._radix:
            return 0
        if (
            src_version is not None
            and int(src_version) != int(self.model_version)
        ):
            # the exporter prefilled under different weights: its KV is
            # another policy's cache, not ours
            self.kv_ship_failures_total += 1
            return 0
        bs = self.cache_config.page_size
        k = np.asarray(k)
        v = np.asarray(v)
        mc = self.model_config
        if (
            k.ndim != 4
            or k.shape[0] != mc.num_layers
            or k.shape[1] != mc.num_kv_heads
            or k.shape[3] != mc.head_dim
            or k.shape[2] % bs
            or k.shape != v.shape
        ):
            self.kv_ship_failures_total += 1
            return 0
        n = min(k.shape[2] // bs, len(tokens) // bs)
        if n <= 0:
            return 0
        dt = self.cache["k"].dtype
        k_pool = kv_tiers_lib.pool_from_canonical(
            np.ascontiguousarray(k[:, :, : n * bs]).astype(dt),
            self.cache["k"].shape,
        )
        v_pool = kv_tiers_lib.pool_from_canonical(
            np.ascontiguousarray(v[:, :, : n * bs]).astype(dt),
            self.cache["v"].shape,
        )
        pages = self._alloc_pages(n)
        if pages is None:
            return 0  # pool dry: the turn just re-prefills
        self._scatter_pages(pages, k_pool, v_pool)
        # ownership transfer: the tree becomes the prefix's only holder
        # (pages duplicating existing tree content are freed by add)
        self.registry.add(
            self.pm, np.asarray(tokens[: n * bs], np.int32), pages
        )
        self.kv_ship_imports_total += 1
        self.kv_ship_pages_in_total += n
        return n * bs

    @property
    def kv_ship_enabled(self) -> bool:
        return self._kv_ship

    def export_prefix(
        self, tokens: List[int], timeout: float = 120.0
    ) -> Dict[str, Any]:
        """Cross-thread kv export (server /kv_export): runs on the loop
        thread behind a pipeline drain, like every engine command."""
        done = Future()
        self._command_queue.put(("kv_export", list(tokens), done))
        return done.result(timeout=timeout)

    def import_prefix(
        self,
        tokens: List[int],
        k,
        v,
        src_version: Optional[int] = None,
        timeout: float = 120.0,
    ) -> int:
        """Cross-thread kv import (server /kv_import and the
        /generate-side ship fetch). Returns tokens entered into the
        prefix cache (0 = soft-dropped)."""
        done = Future()
        self._command_queue.put(
            ("kv_import", (list(tokens), k, v, src_version), done)
        )
        return done.result(timeout=timeout)

    def _preempt_youngest(
        self,
        victims: Optional[tuple] = None,
        reason: str = "pool pressure",
    ) -> bool:
        """Preempt the most recently submitted active request: its pages
        go to the registry (the transparent re-queue usually re-claims
        them) and the request returns to the FRONT of the pending list.
        ``victims`` restricts candidates to those scheduling classes
        (deadline preemption may only evict bulk; pool pressure prefers
        bulk but may fall back to anyone)."""
        candidates = [
            sl for sl, r in self._active.items()
            if victims is None or r.priority in victims
        ]
        if not candidates:
            return False
        slot = max(
            candidates, key=lambda sl: self._active[sl].submit_time
        )
        req = self._active.pop(slot)
        # a pinned victim's pages hold OLD-version KV: parking them in
        # the (already-flushed) registry would let a new-version request
        # claim stale state — release outright, and drop the store pin
        # (the request re-prefills under the current weights). A NAMED
        # victim parks into its own (policy, version) namespace while
        # that pair still serves, and always drops its registry pin.
        if req.policy:
            self._release_slot(
                slot,
                park_tokens=(
                    req.all_tokens
                    if self._policies.is_live(
                        req.policy, req.weight_version
                    )
                    else None
                ),
                ns=(req.policy, req.weight_version),
            )
            self._policies.release(req.policy, req.weight_version)
        else:
            self._release_slot(
                slot,
                park_tokens=(
                    req.all_tokens
                    if req.weight_version == self.model_version
                    else None
                ),
            )
            if req.weight_version != self.model_version:
                self.weights.release(req.weight_version)
        req.slot = None
        req.preemptions += 1
        self.total_preemptions += 1
        self.tracer.instant(
            "preempt", req.rid, tokens_in=len(req.output_ids),
            sched_class=req.priority, reason=reason,
        )
        self._pending.insert(0, req)
        logger.info(
            f"preempted {req.rid} ({len(req.output_ids)} tokens in) — "
            f"{reason}"
        )
        return True

    def _deadline_waiter(self) -> Optional[_Request]:
        """The first queued INTERACTIVE request about to miss its soft
        deadline: inside ``deadline_margin_s`` of it, or having burned
        half its deadline budget waiting. This one predicate drives
        BOTH deadline preemption (evict a running bulk victim) and the
        chunked-prefill scheduler's chunk-boundary deferral (hold the
        next bulk chunk so the wave belongs to the waiter)."""
        margin = float(getattr(self.config, "deadline_margin_s", 0.25))
        now = time.monotonic()
        for r in self._pending:
            if r.priority != "interactive" or r.deadline_at is None:
                continue
            budget = r.deadline_at - r.submit_time
            if (
                now >= r.deadline_at - margin
                or now - r.submit_time >= 0.5 * budget
            ):
                return r
        return None

    def _maybe_deadline_preempt(self) -> bool:
        """Deadline-aware preemption: a queued INTERACTIVE request that
        would miss its soft deadline — already inside the margin, or
        having burned half its deadline budget waiting with no free slot
        — evicts the youngest BULK request. The victim re-queues through
        the existing preemption path (its KV parks in the prefix cache,
        so resuming costs at most one partial-page re-prefill): bulk
        loses latency, never work."""
        now = time.monotonic()
        waiter = self._deadline_waiter()
        if waiter is None:
            return False
        if not any(
            r.priority == "bulk" for r in self._active.values()
        ):
            return False  # nothing shed-able holds a slot
        # preemption needs a quiesced pipeline (in-flight chunks may
        # still write the victim's pages) — and draining may itself
        # finish a request, making the eviction unnecessary
        self._drain_pipeline()
        if self._free_slots:
            return False
        if not self._preempt_youngest(
            victims=("bulk",), reason="deadline"
        ):
            return False
        self.deadline_preemptions_total += 1
        self.tracer.instant(
            "deadline_preempt", waiter.rid,
            deadline_in_s=round(waiter.deadline_at - now, 4),
            waited_s=round(now - waiter.submit_time, 4),
        )
        return True

    def _release_slot(
        self,
        slot: int,
        park_tokens: Optional[List[int]],
        ns: Optional[tuple] = None,
    ):
        """Free a slot; its pages go to the registry (shared-prefix pool)
        or straight back to the allocator. While decode chunks are in
        flight the release is DEFERRED — an in-flight chunk may still
        write into these pages (host-backstop stops finish a slot the
        device considers active). ``ns`` = the (policy, version) KV
        namespace the pages belong to (None = the default registry);
        carried by KEY through the deferral so a namespace retired
        while the release waits degrades to a plain free, never a park
        into an orphaned cache."""
        pages = self._slot_pages.pop(slot, [])
        cached = int(self._cached_len[slot])
        if self._proposer is not None:
            self._proposer.drop(slot)
        if self._slot_mm[slot]:
            # pixel-conditioned KV must not enter the token-keyed prefix
            # registry (a text request with the same tokens would claim it)
            park_tokens = None
            self._slot_mm[slot] = False
            # a later text request reusing this slot may be admitted while
            # the delta scatter is gated off — never leave a stale shift
            self._rope_delta_dev = self._rope_delta_dev.at[slot].set(0)
        self._active_dev = self._active_dev.at[slot].set(False)
        # the device-side length must be zeroed too: a stale length with a
        # reset table row would make the next decode dispatch DMA pages at
        # the table fill value (one past the pool)
        self._lens_dev = self._lens_dev.at[slot].set(0)
        self._tables[slot] = self.cache_config.num_pages
        self._cached_len[slot] = 0
        self._free_slots.append(slot)
        tokens = (
            np.asarray(park_tokens[:cached], np.int32)
            if park_tokens is not None and cached > 0
            else None
        )
        if self._inflight:
            self._deferred_release.append((pages, tokens, ns))
        else:
            self._do_release(pages, tokens, ns)

    def _do_release(
        self,
        pages: List[int],
        tokens: Optional[np.ndarray],
        ns: Optional[tuple] = None,
    ):
        cache = (
            self.registry if ns is None else self._policy_caches.get(ns)
        )
        if tokens is not None and cache is not None:
            cache.add(self.pm, tokens, pages)
        else:
            self.pm.release(pages)

    def _flush_deferred(self):
        if not self._inflight:
            for pages, tokens, ns in self._deferred_release:
                self._do_release(pages, tokens, ns)
            self._deferred_release.clear()

    def _drain_pipeline(self):
        """Process every in-flight decode chunk (and release deferrals)."""
        while self._inflight:
            self._process_chunk(self._inflight.pop(0))
        self._flush_deferred()  # nothing was in flight: release anyway

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def _prefill_bucket(self, n: int) -> int:
        quantum = min(self.config.prefill_chunk, self.config.max_model_len)
        b = data_utils.next_bucket_size(n, quantum)
        return min(b, self.config.max_model_len)

    def _has_chunkable_pending(self) -> bool:
        """Some pending request's next wave is expected to be a
        SLOTLESS chunk dispatch (remaining suffix beyond the budget):
        admission can make prefill progress even with zero free decode
        slots. Preempted requests are excluded — their re-admission
        usually re-claims its cached prefix whole and needs a slot
        immediately (a wrong guess here only costs one deferred claim
        per loop iteration, never correctness)."""
        if self._chunk_budget <= 0:
            return False
        return any(
            r.mm is None
            and r.preemptions == 0
            and len(r.all_tokens) - r.prefill_pos > self._chunk_budget
            for r in self._pending
        )

    def _prefill_backlog_ok(self) -> bool:
        """Bound the UNSYNCED chunk-wave dispatches in flight (chunked
        prefill only). Chunk waves never fetch logits — there is no
        first token yet — so without this gate the admission loop could
        queue an entire long prompt's chunks on device ahead of a
        just-arrived interactive request, recreating exactly the
        head-of-line blocking chunking exists to break. Completed
        dispatches are pruned via ``Array.is_ready``; a jax without it
        degrades to an unbounded backlog (never a stall)."""
        keep = []
        for h in self._prefill_inflight:
            try:
                ready = bool(h.is_ready())
            except AttributeError:
                ready = True
            if not ready:
                keep.append(h)
        self._prefill_inflight = keep
        return len(keep) <= max(1, self.config.decode_pipeline)

    def _admit(self) -> bool:
        """Admit queued requests: identical prompts (GRPO siblings) group
        behind ONE prefill row, sharing full prompt pages and copying at
        most one partial tail page; unique prompts prefill as one batched
        [N, Tp] dispatch, each row resuming from its registry-claimed
        prefix (offset). Its stretches are consecutive spans of the loop
        thread: ``admit.schedule`` (selection, prefix claims, pages),
        ``admit.prefill_dispatch``, ``admit.install`` (publish, sibling
        fan-out, per-slot state), ``admit.first_token_fetch`` (sampling
        and the device→host wait for the first tokens)."""
        with _Phases(self) as phase:
            return self._admit_wave(phase)

    def _admit_wave(self, phase: _Phases) -> bool:
        got_new = 0
        while True:
            try:
                req = self._admit_queue.get_nowait()
            except queue.Empty:
                break
            with self._aq_lock:
                self._aq_class[req.priority] -= 1
                if req.resumed:
                    self._aq_resumed -= 1
            self._pending.append(req)
            got_new += 1
        if (
            self._pending
            and not self._free_slots
            and getattr(self.config, "deadline_preemption", True)
        ):
            self._maybe_deadline_preempt()
        if any(r.priority == "interactive" for r in self._pending):
            # priority admission: interactive requests jump every queued
            # bulk request — including a just-preempted victim re-queued
            # at the front, so the slot a deadline preemption freed goes
            # to the interactive waiter THIS wave, not back to its
            # victim (stable within each class, so bulk FIFO is
            # preserved)
            self._pending.sort(key=lambda r: r.priority != "interactive")
        # chunked prefill: capture deadline pressure BEFORE wave
        # selection moves the waiter out of _pending — a
        # deadline-critical interactive request defers this wave's bulk
        # chunks, so its first token rides an interactive-only dispatch
        # instead of sharing the wave with a bulk chunk
        deadline_pressed = (
            self._chunk_budget > 0
            and self._deadline_waiter() is not None
        )
        if not self._pending:
            return False
        phase.enter("admit.schedule")
        if not self._free_slots and not self._has_chunkable_pending():
            # slotless chunk work may still proceed: a mid-prefill
            # prompt's next chunk needs no slot until its FINAL chunk,
            # so a fully-occupied decode house must not stall bulk
            # prefill (that would serialize the prefill behind decode
            # completions — exactly the head-of-line coupling chunking
            # exists to break)
            return False
        if self._pending_since is None:
            self._pending_since = time.monotonic()
        # hold while the queue is still filling (or decode has work) so
        # admission waves arrive full — every distinct wave shape compiles
        # its own XLA program
        wave = max(1, self.config.admit_wave)
        age = time.monotonic() - self._pending_since
        saturated = (
            len(self._pending) >= len(self._free_slots)
            or len({tuple(r.all_tokens) for r in self._pending}) >= wave
            # chunk continuations bypass the wave-filling hold: a
            # mid-prefill prompt's next chunk must dispatch this
            # iteration, not admit_hold_s from now (prefill_pos > 0 is
            # the mid-chunk marker — it resets at install, so a
            # once-chunked request that later re-queues does not
            # disable wave batching forever)
            or any(r.prefill_pos > 0 for r in self._pending)
        )
        if (
            not saturated
            and age < self.config.admit_hold_s
            and (got_new or self._active)
        ):
            return False
        self._pending_since = None
        # --- one modality per wave: mm waves carry an embeds tensor the
        # text prefill program doesn't, so mixing would recompile ---
        later: List[_Request] = []
        if self.model_config.vision is not None and any(
            r.mm is not None for r in self._pending
        ):
            kind_mm = self._pending[0].mm is not None
            later = [
                r for r in self._pending if (r.mm is not None) != kind_mm
            ]
            self._pending = [
                r for r in self._pending if (r.mm is not None) == kind_mm
            ]
        # --- one policy cohort per wave (r19): each wave prefills under
        # ONE param buffer, so mixed-policy pendings split across waves
        # (the modality-split deferral pattern above). Named requests
        # re-resolve to their line's CURRENT effective version — a push
        # that dropped the version they resolved at submit redirects
        # them to the new stable instead of failing them; a line
        # retired while they queued fails them typed. The whole block
        # is gated on `active`, so the single-policy path never runs it.
        wave_params = self.params
        wave_ns: Optional[tuple] = None
        if self._policies.active and self._pending:
            keep: List[_Request] = []
            have_key = False
            wave_key: Optional[tuple] = None
            for r in self._pending:
                try:
                    key = (
                        (
                            r.policy,
                            self._policies.effective_version(
                                r.policy, r.policy_version
                            ),
                        )
                        if r.policy
                        else None
                    )
                except UnknownPolicyError as e:
                    self.tracer.unbind_trace(r.rid)
                    if not r.future.done():
                        r.future.set_exception(e)
                    continue
                if not have_key:
                    wave_key, have_key = key, True
                if key == wave_key:
                    if r.policy:
                        r.policy_version = key[1]
                    keep.append(r)
                else:
                    later.append(r)
            if wave_key is not None and keep:
                try:
                    # fetch (and, for a host-demoted buffer, reload)
                    # the cohort's params now — the wave's prefill and
                    # mm-embed dispatches both run under this buffer
                    wave_params = self._policies.params_for(*wave_key)
                    wave_ns = wave_key
                except UnknownPolicyError:
                    # the version died between resolve and fetch (push
                    # race): requeue — next tick re-resolves to the
                    # line's new stable or fails typed
                    later.extend(keep)
                    keep = []
            self._pending = keep
            if not self._pending:
                self._pending = later
                return False
        wave_cache = (
            self.registry
            if wave_ns is None
            else self._policy_cache(*wave_ns)
        )
        # --- select: group identical prompts; <= wave unique prompts,
        # total admitted <= free slots ---
        groups: Dict[tuple, List[_Request]] = {}
        rest: List[_Request] = []
        budget = len(self._free_slots)
        for req in self._pending:
            key = (tuple(req.all_tokens), req.mm_key)
            # a request whose next wave is expected to be a SLOTLESS
            # chunk dispatch may open a group without consuming slot
            # budget — chunk prefill progresses through a fully-busy
            # decode house (the claim loop defers it back if its claim
            # turns out to leave a one-wave suffix needing a slot)
            chunkable = (
                self._chunk_budget > 0
                and req.mm is None
                and req.preemptions == 0
                and len(req.all_tokens) - req.prefill_pos
                > self._chunk_budget
            )
            if budget > 0 and key in groups:
                groups[key].append(req)
                budget -= 1
            elif len(groups) < wave and (budget > 0 or chunkable):
                groups[key] = [req]
                if budget > 0:
                    budget -= 1
            else:
                rest.append(req)
        self._pending = rest + later
        if not groups:
            return False

        m = self.config.max_model_len
        bs = self.cache_config.page_size
        num_pages = self.cache_config.num_pages
        s = self.config.max_num_seqs
        reps = [g[0] for g in groups.values()]
        # --- chunked prefill (r15): one chunk-capped row per wave (the
        # dispatch wall stays ~one chunk even with several long prompts
        # queued — they alternate chunks across waves), gated on the
        # unsynced-chunk backlog; deadline pressure defers BULK chunks
        # entirely, so the wave belongs to the interactive waiter
        # (chunk boundaries are the preemption points) ---
        budget_c = self._chunk_budget
        chunk_quota = (
            1 if budget_c > 0 and self._prefill_backlog_ok() else 0
        )
        pressure = deadline_pressed
        deferred: List[_Request] = []
        # --- prefix claim + page allocation per representative ---
        rep_slots: List[int] = []  # s = slotless chunk-capped row
        offsets: List[int] = []
        # cache-served tokens NET of the request's own chunk commits: a
        # continuation re-claiming the prefix it committed last wave is
        # not a cache hit — counting it would inflate the hit-rate
        # gauges quadratically in chunk count (only tokens beyond the
        # request's own committed position are cross-request reuse)
        novel_offs: List[int] = []
        host_offs: List[int] = []  # claim tokens served from host tier
        rep_pages: List[List[int]] = []
        admitted_groups: List[List[_Request]] = []
        chunk_ends: List[int] = []  # committed end (== plen: complete)
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for rep, group in zip(reps, groups.values()):
            prompt = rep.all_tokens
            plen = len(prompt)
            src = None
            host_toks = 0
            if (
                budget_c > 0
                and rep.mm is None
                and rep.chunk_stalls < 2
                and plen - rep.prefill_pos > budget_c
                and (
                    chunk_quota <= 0
                    or (pressure and rep.priority == "bulk")
                )
            ):
                # chunk-boundary deferral BEFORE the claim: a group
                # expected to need a chunk this wave (remaining suffix
                # beyond the budget) defers under quota/deadline
                # pressure without touching the prefix cache — a
                # deferred group re-forms every scheduler tick, and
                # paying a claim per tick would make
                # prefix_claim_hit_rate measure ticks, refresh LRU
                # stamps spuriously, and churn refcounts. Committed
                # chunks stay published; nothing is lost. The deferral
                # is counted ONCE per episode (chunk_deferred), not
                # once per tick.
                if pressure and rep.priority == "bulk":
                    if not rep.chunk_deferred:
                        rep.chunk_deferred = True
                        self.prefill_chunk_preemptions_total += 1
                        self.tracer.instant(
                            "prefill_chunk_preempt", rep.rid,
                            committed=rep.prefill_pos,
                            prompt_tokens=plen,
                        )
                deferred.extend(group)
                continue
            if rep.mm is not None:
                # pixel-conditioned KV: no token-keyed prefix reuse
                shared, off = [], 0
            elif self._radix:
                shared, off, src, _cow_n = wave_cache.claim_cow(
                    self.pm, prompt
                )
                if self._kv_tiers is not None and wave_ns is None:
                    # pages the descent promoted from the host tier —
                    # the hit-rate split between device and host tiers
                    # (tiers attach to the DEFAULT tree only; a named
                    # wave's claim never touches the spill store)
                    host_toks = self._kv_tiers.last_claim_promoted * bs
            else:
                shared, off = wave_cache.claim(self.pm, prompt)
            end = plen
            stalled = escaped = False
            if budget_c > 0 and rep.mm is None and plen - off > budget_c:
                if chunk_quota <= 0 or (
                    pressure and rep.priority == "bulk"
                ):
                    # the pre-claim expectation missed (the claim
                    # regressed below prefill_pos, so the suffix is
                    # chunk-sized after all): same deferral, same
                    # once-per-episode counting, claim refs returned
                    self.pm.release(shared)
                    if src is not None:
                        self.pm.release([src])
                    if pressure and rep.priority == "bulk":
                        if not rep.chunk_deferred:
                            rep.chunk_deferred = True
                            self.prefill_chunk_preemptions_total += 1
                            self.tracer.instant(
                                "prefill_chunk_preempt", rep.rid,
                                committed=rep.prefill_pos,
                                prompt_tokens=plen,
                            )
                    deferred.extend(group)
                    continue
                # stall escape: a continuation whose claims regressed
                # on two DISPATCHED waves (eviction keeps eating the
                # committed prefix) admits in full — chunking must
                # never livelock a prompt under cache thrash. Both the
                # strike and the escape's side effects are recorded
                # only when this row actually dispatches (below), so
                # deferrals/alloc failures can neither double-count a
                # single regression nor spam the counter per loop tick.
                stalled = rep.chunk_index > 0 and off < rep.prefill_pos
                if rep.chunk_stalls + (1 if stalled else 0) >= 2:
                    escaped = True  # end stays plen: uncapped dispatch
                else:
                    # cap this row at a PAGE-ALIGNED end: commits must
                    # publish full pages so both cache modes (and the
                    # flat registry's full-page claims) resume exactly
                    # here. budget >= page_size guarantees end > off.
                    end = ((off + budget_c) // bs) * bs
                    chunk_quota -= 1
            if end == plen and not self._free_slots:
                # selected on chunk eligibility, but the claim leaves a
                # suffix that fits one wave — the FINAL chunk samples a
                # first token and needs a decode slot; wait for one
                self.pm.release(shared)
                if src is not None:
                    self.pm.release([src])
                deferred.extend(group)
                continue
            need = -(-end // bs) - len(shared)
            fresh = self._alloc_pages(need)
            if fresh is None:
                # pool exhausted — return the whole group to pending
                self.pm.release(shared)
                if src is not None:
                    self.pm.release([src])
                self._pending = group + self._pending
                continue
            if stalled:
                rep.chunk_stalls += 1
            if escaped:
                # the uncapped dispatch is now certain: the TTFT bound
                # is violated for this wave and ttft_bounded reports it
                self.prefill_chunk_stall_escapes += 1
                self.tracer.instant(
                    "prefill_chunk_stall_escape", rep.rid,
                    committed=rep.prefill_pos,
                    prompt_tokens=plen,
                )
                logger.warning(
                    f"chunked prefill stall escape for {rep.rid}: "
                    f"claims regressed twice (cache thrash) — "
                    f"admitting {plen - off} suffix tokens whole"
                )
            if src is not None:
                # COW claim: the match extends into a cached page (a
                # partial tail, or divergence within a full page) —
                # copy it into the claimant's first fresh page and
                # resume prefill mid-page from the row-aligned offset
                cow_src.append(src)
                cow_dst.append(fresh[0])
            pages = shared + fresh
            if end < plen:
                # chunk-capped: the row rides the wave SLOTLESS — no
                # first token is sampled yet, so slot/sampling state
                # and the install wait for the final chunk's wave
                rep_slots.append(s)
            else:
                rep_slots.append(self._free_slots.pop())
            offsets.append(off)
            novel_offs.append(off - min(off, rep.prefill_pos))
            host_offs.append(min(host_toks, off))
            rep_pages.append(pages)
            admitted_groups.append(group)
            chunk_ends.append(end)
            # the deferral episode (if any) ended in a dispatch: the
            # next pressure deferral is a new episode and counts again
            rep.chunk_deferred = False
        # flush claim-time promotions NOW, before anything downstream
        # can read the promoted pages: the COW copy dispatch below and
        # the wave prefill both attend through shared pages, and a page
        # promoted this loop holds garbage until its scatter lands.
        # Deferred/failed claims above may also have queued promotions —
        # their pages are tree-owned and resident, so flushing them
        # unconditionally is correct (and keeps them claimable).
        self._flush_kv_promotions()
        if deferred:
            self._pending = deferred + self._pending
        if not admitted_groups:
            # a COW claim with no admitted rep cannot happen (the claim
            # only survives when its rep allocates), but release holds
            # defensively if a future edit changes that
            if cow_src:
                raise RuntimeError(
                    "COW source pages on a non-allocating claim path"
                )
            return False
        if cow_src:
            # dispatch the COW copies BEFORE the wave prefill: the
            # claimants' prefix-window attention reads the copied pages.
            # Device program order also protects the sources against
            # reallocation — any later write lands after this copy.
            pad = data_utils.next_bucket_size(len(cow_src), 8)
            src_np = np.zeros(pad, np.int32)
            dst_np = np.full(pad, num_pages, np.int32)
            src_np[: len(cow_src)] = cow_src
            dst_np[: len(cow_dst)] = cow_dst
            src_dev, dst_dev = jnp.asarray(src_np), jnp.asarray(dst_np)
            with goodput.dispatch_scope(
                self.compiles, "copy", precompile_lib.copy_sig(pad)
            ):
                self.cache = model_runner.copy_pages(
                    self.cache, src_dev, dst_dev
                )
            self.total_cow_copies += len(cow_src)
            # the claim's protective refs on the sources: the copy is
            # now ordered before any later pool write, so registry
            # eviction can no longer race it
            self.pm.release(cow_src)

        # suffix bucket (offsets are pool-ROW-aligned — page-aligned for
        # full-page claims, mid-page for COW claims — and < prompt len).
        # Chunk-capped rows contribute their CHUNK's suffix, so with
        # chunking on every admission dispatch is bounded by ~one chunk
        tp = self._prefill_bucket(
            max(
                end - off
                for end, off in zip(chunk_ends, offsets)
            )
        )
        # rows whose suffix exceeds the bucket fall back to offset 0?
        # cannot happen: offset <= len(prompt)-1 and bucket >= max suffix.
        self.total_cached_prompt_tokens += sum(novel_offs)
        self.total_host_cached_tokens += sum(host_offs)
        pf_prefix_bound = 0
        if max(offsets) > 0:
            pf_prefix_bound = min(
                m,
                data_utils.next_bucket_size(
                    max(offsets), self.config.kv_bucket
                ),
            )
        # page window covers each row's COMMITTED end (chunk-capped rows
        # only write/attend up to their chunk), not the full prompt
        pps_pf = max(
            1,
            -(-data_utils.next_bucket_size(
                max(chunk_ends),
                self.config.kv_bucket,
            ) // bs),
        )
        pps_pf = min(pps_pf, self.cache_config.max_pages_per_seq)
        # pow2 row bucket: a lone unique prompt (a GRPO group) doesn't pay
        # for wave-1 padding rows of compute
        n_rows = (
            1 << (len(rep_slots) - 1).bit_length() if len(rep_slots) > 1 else 1
        )
        tokens = np.zeros((n_rows, tp), np.int32)
        true_lens = np.zeros(n_rows, np.int32)
        row_offsets = np.zeros(n_rows, np.int32)
        row_tables = np.full((n_rows, pps_pf), num_pages, np.int32)
        for i, (group, slot, off, pages) in enumerate(
            zip(admitted_groups, rep_slots, offsets, rep_pages)
        ):
            prompt = group[0].all_tokens
            suffix = prompt[off : chunk_ends[i]]
            tokens[i, : len(suffix)] = suffix
            true_lens[i] = len(suffix)
            row_offsets[i] = off
            row_tables[i, : len(pages)] = pages
        row_slots = np.zeros(n_rows, np.int32)
        for i, slot in enumerate(rep_slots):
            row_slots[i] = slot
        # --- VLM wave: splice vision embeds once, build mrope positions
        # (offsets are 0 for mm rows — no prefix reuse — so the suffix IS
        # the full prompt + any accumulated text) ---
        pf_embeds = pf_pos3 = None
        if (
            self.model_config.vision is not None
            and any(g[0].mm is not None for g in admitted_groups)
        ):
            vc = self.model_config.vision
            p_pad = data_utils.next_bucket_size(
                max(
                    g[0].mm["pixel_values"].shape[0]
                    for g in admitted_groups
                    if g[0].mm is not None
                ),
                64,
            )
            pix = np.zeros((n_rows, p_pad, vc.patch_dim), np.float32)
            seg = np.zeros((n_rows, p_pad), np.int32)
            ph = np.zeros((n_rows, p_pad), np.int32)
            pw = np.zeros((n_rows, p_pad), np.int32)
            ords = np.full((n_rows, tp), -1, np.int32)
            pos3 = np.zeros((n_rows, tp, 3), np.int32)
            for i, group in enumerate(admitted_groups):
                mm = group[0].mm
                if mm is None:
                    continue
                p_n = mm["pixel_values"].shape[0]
                pix[i, :p_n] = mm["pixel_values"]
                seg[i, :p_n] = mm["vis_seg"][:p_n]
                ph[i, :p_n] = mm["vis_pos_h"][:p_n]
                pw[i, :p_n] = mm["vis_pos_w"][:p_n]
                L = min(len(group[0].all_tokens), tp)
                n_ord = min(len(mm["mm_index"]), L)
                ords[i, :n_ord] = mm["mm_index"][:n_ord]
                mp = mm.get("mrope_pos")
                n_p = min(len(mp), L) if mp is not None else 0
                if n_p:
                    pos3[i, :n_p] = mp[:n_p]
                if n_p < L:  # accumulated text continues at idx + delta
                    ext = np.arange(n_p, L, dtype=np.int32) + np.int32(
                        group[0].rope_delta
                    )
                    pos3[i, n_p:L] = ext[:, None]
            pf_embeds = model_runner.mm_prompt_embeds(
                wave_params, self.model_config, jnp.asarray(tokens),
                jnp.asarray(pix), jnp.asarray(seg), jnp.asarray(ph),
                jnp.asarray(pw), jnp.asarray(ords),
            )
            pf_pos3 = jnp.asarray(pos3)
        phase.enter("admit.prefill_dispatch")
        t_pf_start = time.monotonic()
        # host→device conversions hoisted OUT of the dispatch scope:
        # their tiny eager-op compiles belong to the ("engine", "")
        # catch-all rung, so the prefill rung's compile bill is exactly
        # the programs the AOT precompiler covers
        tokens_dev = jnp.asarray(tokens)
        offsets_dev = jnp.asarray(row_offsets)
        lens_dev = jnp.asarray(true_lens)
        tables_dev = jnp.asarray(row_tables)
        slots_dev = jnp.asarray(row_slots)
        with goodput.dispatch_scope(
            self.compiles, "prefill",
            precompile_lib.prefill_sig(
                n_rows, tp, pps_pf, pf_prefix_bound,
                int(pf_embeds is not None),
            ),
        ):
            self.cache, wave_logits, pf_last = model_runner.prefill_batch(
                wave_params, self.model_config, self.cache,
                tokens_dev, offsets_dev,
                lens_dev, tables_dev,
                prefix_bound=pf_prefix_bound,
                last_rows=self._last_rows,
                slot_ids=slots_dev,
                embeds=pf_embeds,
                pos3=pf_pos3,
            )
        phase.enter("admit.install")
        if self._radix:
            # publish-at-prefill-commit: the wave's prompt pages enter
            # the radix tree NOW (the merge dispatch is already ordered
            # on device), so siblings/turns arriving in later waves
            # claim them while these owners are still decoding — the
            # flat registry only ever parked pages at free time.
            # Chunk-capped rows are handled below (publish-at-CHUNK-
            # commit is an ownership transfer, not a share)
            for i, (group, pages) in enumerate(
                zip(admitted_groups, rep_pages)
            ):
                if group[0].mm is None and chunk_ends[i] == len(
                    group[0].all_tokens
                ):
                    wave_cache.publish(
                        self.pm,
                        np.asarray(group[0].all_tokens, np.int32),
                        pages,
                    )

        # --- publish-at-chunk-commit (r15): a chunk-capped row's
        # committed page-aligned prefix enters the prefix cache as an
        # OWNERSHIP TRANSFER (`add` publishes, then releases this
        # wave's claim+alloc refs — between chunks the cache is the
        # prefix's only holder), and the group requeues at the front of
        # pending. The next wave's claim resumes exactly here; GRPO
        # siblings and overlapping prompts already ride the finished
        # chunks while the owner is still prefilling. ---
        requeue: List[_Request] = []
        if budget_c > 0:
            t_commit = time.monotonic()
            for i, (group, pages) in enumerate(
                zip(admitted_groups, rep_pages)
            ):
                end = chunk_ends[i]
                rep = group[0]
                plen = len(rep.all_tokens)
                if end == plen:
                    continue
                wave_cache.add(
                    self.pm,
                    np.asarray(rep.all_tokens[:end], np.int32),
                    pages,
                )
                rep.chunk_index += 1
                rep.prefill_pos = end
                if rep.first_dispatch_time is None:
                    # the wave that first served this request: queue
                    # wait ends HERE — the later chunk waves are the
                    # prompt being prefilled, not queued
                    rep.first_dispatch_time = t_pf_start
                self.prefill_chunks_total += 1
                if self.tracer.enabled:
                    # chunk spans measure DISPATCH wall (the wave is
                    # not synced — no first token to fetch); the final
                    # chunk's span carries end-to-end timing as usual
                    self.tracer.record(
                        "prefill", rep.rid, t_pf_start, t_commit,
                        slot=-1, wave_rows=len(rep_slots),
                        prompt_tokens=plen,
                        cached_offset=int(offsets[i]),
                        cached_tokens=int(novel_offs[i]),
                        chunk_index=rep.chunk_index - 1,
                        chunk_count=rep.chunk_index
                        + max(1, -(-(plen - end) // budget_c)),
                        committed=end,
                        partial=1,
                        **(
                            {"host_cached_tokens": int(host_offs[i])}
                            if self._kv_tiers is not None
                            else {}
                        ),
                    )
                requeue.extend(group)
            if requeue:
                # keep one unsynced-dispatch handle per chunk wave so
                # _prefill_backlog_ok can bound device queue depth
                self._prefill_inflight.append(wave_logits)
                self._pending = requeue + self._pending

        # --- sibling fan-out: share full prompt pages, copy the partial
        # tail page (if any) — chunk-capped rows skip (their installs
        # and sibling fan-out wait for the final chunk's wave) ---
        copy_src: List[int] = []
        copy_dst: List[int] = []
        admitted: List[tuple] = []  # (req, slot, logits_row)
        adm_cached: List[int] = []  # cache-served prompt tokens per req
        adm_host: List[int] = []  # of those, tokens from the host tier
        # (chunk_index, first_dispatch_time) captured BEFORE _install
        # resets them: the final chunk's span attrs and the queue-wait
        # end need this admission's values, not the fresh slot life's
        adm_meta: List[tuple] = []
        for i, (group, slot, pages) in enumerate(
            zip(admitted_groups, rep_slots, rep_pages)
        ):
            plen = len(group[0].all_tokens)
            if chunk_ends[i] < plen:
                continue
            adm_meta.append(
                (group[0].chunk_index, group[0].first_dispatch_time)
            )
            group[0].cached_tokens = int(novel_offs[i])
            self._install(group[0], slot, pages, plen)
            admitted.append((group[0], slot, i))
            adm_cached.append(int(novel_offs[i]))
            adm_host.append(int(min(host_offs[i], novel_offs[i])))
            n_full = plen // bs
            for sib in group[1:]:
                if not self._free_slots:
                    self._pending.insert(0, sib)
                    continue
                shared = pages[:n_full]
                sib_pages = list(shared)
                self.pm.share(shared)
                if plen % bs:
                    tail = self._alloc_pages(1)
                    if tail is None:
                        # pool dry mid-fanout: requeue the sibling
                        self.pm.release(shared)
                        self._pending.insert(0, sib)
                        continue
                    copy_src.append(pages[n_full])
                    copy_dst.append(tail[0])
                    sib_pages += tail
                sslot = self._free_slots.pop()
                adm_meta.append((0, None))
                sib.cached_tokens = plen
                self._install(sib, sslot, sib_pages, plen)
                admitted.append((sib, sslot, i))
                adm_cached.append(plen)
                # siblings ride the representative's DEVICE pages —
                # their cache hit never touches the host tier
                adm_host.append(0)
                self.total_cached_prompt_tokens += plen
        if copy_src:
            pad = data_utils.next_bucket_size(len(copy_src), 8)
            src = np.zeros(pad, np.int32)
            dst = np.full(pad, num_pages, np.int32)
            src[: len(copy_src)] = copy_src
            dst[: len(copy_dst)] = copy_dst
            src_dev, dst_dev = jnp.asarray(src), jnp.asarray(dst)
            with goodput.dispatch_scope(
                self.compiles, "copy", precompile_lib.copy_sig(pad)
            ):
                self.cache = model_runner.copy_pages(
                    self.cache, src_dev, dst_dev
                )

        # --- batched per-slot state update (one scatter per state array) ---
        if not admitted:
            # chunk-only wave: nothing installed, no first token to
            # fetch — the dispatch stays unsynced (the backlog handle
            # above bounds device queue depth) and the loop proceeds
            # straight to decode, which is the whole point: decode
            # dispatches interleave between a long prompt's chunks
            return True
        n = len(admitted)
        slots_np = np.zeros(n, np.int32)
        deltas = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        top_ps = np.zeros(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        greedys = np.zeros(n, bool)
        remainings = np.zeros(n, np.int32)
        no_stops = np.zeros(n, np.int32)
        plens = np.zeros(n, np.int32)
        stops = np.full((n, 8), -1, np.int32)
        for j, (req, slot, _) in enumerate(admitted):
            plen = len(req.all_tokens)
            self.total_prompt_tokens += plen
            self.total_requests += 1
            slots_np[j] = slot
            temps[j] = req.temperature
            top_ps[j] = req.top_p
            top_ks[j] = req.top_k
            greedys[j] = req.greedy
            plens[j] = plen
            # the first token is sampled at admission (below), so the
            # device-side budget starts at allowed − 1
            remainings[j] = min(req.budget_left, m - plen) - 1
            no_stops[j] = req.min_left - 1
            deltas[j] = req.rope_delta
            ids = np.asarray(req.stop_token_ids[:8], np.int32)
            stops[j, : len(ids)] = ids
        sl = jnp.asarray(slots_np)
        self._lens_dev = self._lens_dev.at[sl].set(jnp.asarray(plens))
        if self._spec_configured:
            # canonical alignment base = cache length at admission (the
            # off-run's chunk boundaries are multiples of decode_chunk
            # from here)
            self._align_base_dev = self._align_base_dev.at[sl].set(
                jnp.asarray(plens)
            )
        self._temp_dev = self._temp_dev.at[sl].set(jnp.asarray(temps))
        self._top_p_dev = self._top_p_dev.at[sl].set(jnp.asarray(top_ps))
        self._top_k_dev = self._top_k_dev.at[sl].set(jnp.asarray(top_ks))
        self._greedy_dev = self._greedy_dev.at[sl].set(jnp.asarray(greedys))
        self._active_dev = self._active_dev.at[sl].set(True)
        self._remaining = self._remaining.at[sl].set(jnp.asarray(remainings))
        self._no_stop = self._no_stop.at[sl].set(jnp.asarray(no_stops))
        self._stop_tokens = self._stop_tokens.at[sl].set(jnp.asarray(stops))
        if any(self._slot_mm) or deltas.any():
            self._rope_delta_dev = self._rope_delta_dev.at[sl].set(
                jnp.asarray(deltas)
            )

        # --- last-row state for every admitted slot (siblings share the
        # representative's prefill row content) ---
        adm_rows = np.asarray([r for (_, _, r) in admitted], np.int32)
        adm_slots = np.asarray([sl_ for (_, sl_, _) in admitted], np.int32)
        onehot = jnp.asarray(
            (adm_slots[:, None]
             == np.arange(self.config.max_num_seqs)[None, :]).astype(
                np.float32
            )
        )
        sel = {
            k_: jnp.take(v_, jnp.asarray(adm_rows), axis=1)
            for k_, v_ in pf_last.items()
        }
        mask = (onehot.sum(0) > 0)[None, :, None, None]
        self._last_rows = {
            k_: jnp.where(
                mask,
                jnp.einsum(
                    "lnhf,ns->lshf", sel[k_].astype(jnp.float32), onehot
                ).astype(v_.dtype),
                v_,
            )
            for k_, v_ in self._last_rows.items()
        }

        # --- first token for every admitted slot: siblings share the
        # representative's last-token logits row ---
        phase.enter("admit.first_token_fetch")
        rows = jnp.asarray([r for (_, _, r) in admitted])
        full = jnp.zeros(
            (self.config.max_num_seqs, wave_logits.shape[-1]),
            wave_logits.dtype,
        ).at[sl].set(wave_logits[rows])
        self._sample_and_append(full, only_slots=[int(x) for x in slots_np])
        t_pf_end = time.monotonic()
        phase.enter("admit.install")
        pf_tokens = int(true_lens.sum())
        if t_pf_end > t_pf_start:
            # EWMA over waves: the dispatch wall time includes the logits
            # fetch in _sample_and_append, so this is end-to-end prefill
            # throughput as a client would see it
            inst = pf_tokens / (t_pf_end - t_pf_start)
            self._prefill_tps = (
                inst if self._prefill_tps == 0.0
                else 0.8 * self._prefill_tps + 0.2 * inst
            )
        for (req, _, _), (_, first_disp) in zip(admitted, adm_meta):
            # native queue-wait histogram per class: the durable latency
            # source (span percentiles vanish with every /trace drain).
            # A chunked prompt's wait ends at its FIRST chunk wave —
            # the later waves are the prompt being prefilled, and
            # counting them as queueing would corrupt the bulk class's
            # priority-isolation SLO signal
            self._hists["queue_wait_seconds"][req.priority].observe(
                (first_disp or t_pf_start) - req.submit_time
            )
        if self.tracer.enabled:
            for (req, slot, row), ctok, htok, (
                chunk_idx, first_disp,
            ) in zip(admitted, adm_cached, adm_host, adm_meta):
                self.tracer.record(
                    "queue_wait", req.rid, req.submit_time,
                    first_disp or t_pf_start,
                    preemptions=req.preemptions,
                    # per-class queue-wait is THE priority-isolation SLO
                    # signal (trace_report --slo aggregates it)
                    sched_class=req.priority,
                    **({"tenant": req.tenant} if req.tenant else {}),
                )
                chunk_attrs = {}
                if self._chunk_budget > 0:
                    # chunked engines stamp every prefill span with its
                    # chunk position (final chunk = index chunk_index of
                    # chunk_index+1) — trace_report --ttft builds the
                    # chunks-per-prompt histogram from these
                    chunk_attrs = dict(
                        chunk_index=chunk_idx,
                        chunk_count=chunk_idx + 1,
                    )
                self.tracer.record(
                    "prefill", req.rid, t_pf_start, t_pf_end,
                    slot=slot, wave_rows=len(rep_slots),
                    # _sample_and_append already appended this wave's first
                    # token, so the prefilled length is one shy of all_tokens
                    prompt_tokens=len(req.all_tokens) - 1,
                    cached_offset=int(offsets[row]),
                    # prompt tokens THIS request served from cache (a
                    # sibling's whole prompt rode the representative's
                    # prefill; a claimant's = its claim offset) —
                    # trace_report --cache aggregates these
                    cached_tokens=int(ctok),
                    **chunk_attrs,
                    **(
                        {"host_cached_tokens": int(htok)}
                        if self._kv_tiers is not None
                        else {}
                    ),
                )
        return True

    def _install(
        self, req: _Request, slot: int, pages: List[int], cached: int
    ):
        req.slot = slot
        # (re-)admission decodes under the CURRENT weights: a preempted
        # pin-policy request re-prefills here on the new version (its
        # already-emitted tokens keep their old per-token version stamps
        # — the recorded-switch half of the fence invariant). A NAMED
        # request decodes under its line's resolved version instead,
        # and holds one registry pin for this slot life — the buffer is
        # undemotable and undroppable until _finish/_preempt releases.
        if req.policy:
            req.weight_version = req.policy_version
            self._policies.retain(req.policy, req.policy_version)
        else:
            req.weight_version = self.model_version
        self._active[slot] = req
        self._slot_pages[slot] = pages
        self._cached_len[slot] = cached
        self._tables[slot] = self.cache_config.num_pages
        self._tables[slot, : len(pages)] = pages
        self._slot_mm[slot] = req.mm is not None
        self._align_base[slot] = cached
        # a fresh slot life resets the chunk bookkeeping: a preempted
        # request's next life may legitimately re-claim less (no stall
        # strike), and its re-claims of its own PARKED pages count as
        # cache hits again (pre-chunking accounting — prefill_pos only
        # discounts a still-prefilling prompt's own chunk commits)
        req.chunk_stalls = 0
        req.prefill_pos = 0
        req.chunk_index = 0
        req.chunk_deferred = False
        req.first_dispatch_time = None
        if self._proposer is not None:
            # full history (resumed/preempted requests re-enter with
            # their accumulated output): the n-gram index rebuilds here
            self._proposer.begin(slot, req.all_tokens)

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------
    @_loop_phase("decode.ensure_pages", "decode_dispatch")
    def _ensure_decode_pages(self, margin_tokens: int) -> bool:
        """Grow every active slot's page table to cover its cached length
        plus ``margin_tokens`` (the host view lags in-flight chunks, so
        the margin covers pipeline depth × chunk). Preempts under pool
        pressure ONLY when the pipeline is empty — an in-flight chunk may
        still write to a victim's pages. Returns False if nothing can be
        dispatched right now."""
        bs = self.cache_config.page_size
        while self._active:
            shortfall = 0
            grow: List[tuple] = []
            for slot, req in self._active.items():
                cached = int(self._cached_len[slot])
                need = -(
                    -min(cached + margin_tokens, self.config.max_model_len)
                    // bs
                )
                have = len(self._slot_pages[slot])
                if need > have:
                    grow.append((slot, need - have))
                    shortfall += need - have
            if shortfall == 0:
                return True
            if shortfall > self.pm.n_free:
                if self._inflight:
                    # drain before evicting: deferred releases (blocked on
                    # the in-flight pipeline) may cover the shortfall, and
                    # evicting the registry now would destroy parked KV of
                    # preempted requests — forcing full re-prefills with
                    # fresh shape compiles (the r4 catastrophic-round
                    # mechanism at decode_pipeline=2)
                    return False
                self.registry.evict(self.pm, shortfall)
                for cache in self._policy_caches.values():
                    if shortfall <= self.pm.n_free:
                        break
                    cache.evict(self.pm, shortfall - self.pm.n_free)
            if shortfall <= self.pm.n_free:
                for slot, n in grow:
                    pages = self.pm.alloc(n)
                    if pages is None:
                        raise RuntimeError(
                            "page allocation failed after preemption "
                            "freed the pool"
                        )
                    sp = self._slot_pages[slot]
                    self._tables[slot, len(sp) : len(sp) + n] = pages
                    sp.extend(pages)
                return True
            if len(self._active) == 1:
                # a lone request larger than the whole pool cannot be
                # preempted into progress — truncate it
                slot = next(iter(self._active))
                logger.warning(
                    f"pool smaller than one request; truncating "
                    f"{self._active[slot].rid}"
                )
                self._finish(slot, "length")
                return False
            # pool pressure prefers BULK victims (priority isolation);
            # an all-interactive batch still preempts its youngest
            if not (
                self._preempt_youngest(victims=("bulk",))
                or self._preempt_youngest()
            ):
                return False
        return False

    def _pages_bound(
        self, margin_tokens: int, slots: Optional[List[int]] = None
    ) -> int:
        """Static page-window bound: bucketed longest cached length plus
        the in-flight margin (over ``slots`` when a cohort dispatch
        passes one, else over every active slot)."""
        bs = self.cache_config.page_size
        max_len = (
            max(int(self._cached_len[s]) for s in (slots or self._active))
            + margin_tokens
        )
        tokens = min(
            self.config.max_model_len,
            data_utils.next_bucket_size(max_len, self.config.kv_bucket),
        )
        return min(-(-tokens // bs), self.cache_config.max_pages_per_seq)

    def _sampling_mode(self) -> int:
        """Static topk_bound for the sampling kernel, from the live mix of
        requests: -1 (pure categorical) when nothing truncates, else a
        lax.top_k bound covering every slot's top_k."""
        reqs = self._active.values()
        if all(r.top_p >= 1.0 and r.top_k <= 0 for r in reqs):
            return -1
        if self.config.sample_topk_bound <= 0:
            return 0  # exact full-vocab sort requested
        mx = max((r.top_k for r in reqs), default=0)
        return data_utils.next_bucket_size(
            max(self.config.sample_topk_bound, mx),
            self.config.sample_topk_bound,
        )

    def _spec_on(self) -> bool:
        """Speculation configured and not auto-disabled by the gate."""
        return self._spec_configured and not self._spec_gate.disabled

    def _spec_has_candidates(self) -> bool:
        """Cheap probe: would any active slot propose a draft? (Used to
        decide whether draining the pipeline for fresh drafts pays.)
        Applies the same boundary trim as _propose_drafts — a slot whose
        next token lands ON its canonical boundary cannot carry a draft
        this round, so its n-gram candidates must not trigger a drain."""
        cq = max(1, self.config.decode_chunk)
        for slot in self._active:
            rl = int(
                (self._cached_len[slot] - self._align_base[slot]) % cq
            )
            if cq - 1 - rl <= 0:
                continue
            if self._proposer.has_candidate(slot):
                return True
        return False

    def _propose_drafts(self) -> Dict[int, List[int]]:
        """Per-slot n-gram drafts from CURRENT host history (callers
        guarantee the pipeline is empty, so the history is exact).

        Drafts are trimmed to the slot's canonical-boundary distance:
        acceptance can never run past the boundary (the verify would
        need this window's own pre-boundary K/V as merged pool entries
        — model_runner._spec_verify_forward caps there), so proposing
        past it only lowers the measured accept rate."""
        kd = max(1, self.config.spec.max_draft)
        cq = max(1, self.config.decode_chunk)
        out: Dict[int, List[int]] = {}
        for slot in self._active:
            rl = int(
                (self._cached_len[slot] - self._align_base[slot]) % cq
            )
            kd_s = min(kd, cq - 1 - rl)
            if kd_s <= 0:
                continue
            d = self._proposer.propose(slot, kd_s)
            if d:
                out[slot] = d
        return out

    def _margin(self, new_steps: int) -> int:
        """Page-growth margin for a new dispatch: its own worst case plus
        every in-flight chunk's (the host view lags the device by the
        pipeline; verify chunks may grow by K, regular chunks by
        decode_chunk — sizes can mix)."""
        return new_steps + sum(c["max_tokens"] for c in self._inflight)

    def _decode(self) -> bool:
        """Pipelined decode: dispatch chunk N+1, then process chunk N's
        results while N+1 executes on device — the result fetch (a full
        round-trip) overlaps device compute.

        Speculation composition (r7): a verify dispatch needs drafts, and
        drafts need CURRENT host history — so verify chunks are only
        dispatched on an empty pipeline, and when the proposer has
        candidates the loop drains in-flight chunks instead of stacking
        more regular ones ("drain-for-drafts": speculation trades
        pipeline overlap for multi-token verify; the accept-rate gate
        auto-disables it when that trade loses). Slots with no candidate
        n-grams ride along in the verify dispatch with draft_len 0 (a
        plain single-token step for them); when NO slot has a candidate
        the regular pipelined path runs untouched."""
        depth = max(0, self.config.decode_pipeline)
        led = self.ledger
        did = False
        dispatched = False
        drafts: Optional[Dict[int, List[int]]] = None
        # version cohorts (r13 pin-policy flips): while ANY active
        # request is pinned off the current version — including the tail
        # case where only the pinned cohort remains — each dispatch
        # covers ONE cohort with its own params (round-robin so neither
        # starves); speculation sits out the transient — its
        # drain-for-drafts scheduling assumes one dispatch serves every
        # active slot
        # cohort keys are (policy, version) — version ints are per-LINE
        # (actor@v12 and opponent@v12 are different buffers), so the
        # bare int the r13 flip machinery used would collide across
        # lines. The default line's key is ("", model_version).
        versions = (
            {(r.policy, r.weight_version) for r in self._active.values()}
            if self._active
            else set()
        )
        mixed = bool(versions - {("", self.model_version)})
        if self._spec_on() and self._active and not mixed:
            if not self._inflight:
                drafts = self._propose_drafts() or None
            elif self._spec_has_candidates():
                # drain-for-drafts (see docstring); the drained chunk
                # may itself be a verify chunk — attribute its wall
                # time to the bucket that dispatched it
                chunk = self._inflight.pop(0)
                spec_chunk = chunk.get("spec_draft_lens") is not None
                with led.bucket(
                    "spec_verify" if spec_chunk else "decode"
                ):
                    self._process_chunk(chunk)
                return True
        if self._active and len(self._inflight) <= depth:
            if drafts:
                # drafts are trimmed to <= decode_chunk-1 tokens and the
                # verify boundary cap makes positions past that
                # unemittable — clamp the window (and the page margin)
                # to what can actually land
                k = min(
                    max(1, self.config.spec.max_draft),
                    max(1, self.config.decode_chunk) - 1,
                ) + 1
                margin = self._margin(k)
                with led.bucket("spec_verify"):
                    if self._ensure_decode_pages(margin):
                        self._dispatch_chunk(k, margin, drafts=drafts)
                        dispatched = did = True
            else:
                steps = max(1, self.config.decode_chunk)
                margin = self._margin(steps)
                with led.bucket("decode"):
                    if self._ensure_decode_pages(margin):
                        # recompute the cohort picture AFTER the page
                        # walk: _ensure_decode_pages may have preempted
                        # (or truncated) the last pinned request, which
                        # releases its pin and drops the old buffer — a
                        # stale pre-walk snapshot would dispatch an
                        # empty cohort against a freed buffer and kill
                        # the loop thread
                        versions = {
                            (r.policy, r.weight_version)
                            for r in self._active.values()
                        }
                        mixed = bool(
                            versions - {("", self.model_version)}
                        )
                        if mixed:
                            order = sorted(versions)
                            ck = order[self._cohort_rr % len(order)]
                            self._cohort_rr += 1
                            cohort_slots = sorted(
                                sl
                                for sl, r in self._active.items()
                                if (r.policy, r.weight_version) == ck
                            )
                            if cohort_slots:
                                self._dispatch_chunk(
                                    steps, margin,
                                    cohort=(cohort_slots, ck),
                                )
                                dispatched = did = True
                        elif self._active:
                            self._dispatch_chunk(steps, margin)
                            dispatched = did = True
        if self._inflight and (
            len(self._inflight) > depth or not dispatched
        ):
            chunk = self._inflight.pop(0)
            spec_chunk = chunk.get("spec_draft_lens") is not None
            with led.bucket("spec_verify" if spec_chunk else "decode"):
                self._process_chunk(chunk)
            did = True
        return did

    def _decode_rows_bucket(self, n_active: int) -> int:
        """Pow2 row bucket for a compacted decode dispatch: grows
        immediately (correctness — every active slot needs a row),
        shrinks only after ``decode_compact_hysteresis`` consecutive
        chunks below the current bucket (each distinct row count is its
        own compiled program; ragged finishes must not thrash the
        compile cache)."""
        s = self.config.max_num_seqs
        floor = max(1, self.config.decode_compact_min_rows)
        target = max(n_active, floor)
        target = min(1 << (target - 1).bit_length(), s)
        cur = self._compact_rows
        if cur is None or target > cur:
            self._compact_rows = target
            self._compact_shrink_streak = 0
        elif target < cur:
            self._compact_shrink_streak += 1
            if self._compact_shrink_streak >= max(
                1, self.config.decode_compact_hysteresis
            ):
                self._compact_rows = target
                self._compact_shrink_streak = 0
        else:
            self._compact_shrink_streak = 0
        return self._compact_rows

    @_loop_phase("decode.dispatch", "decode_dispatch")
    def _dispatch_chunk(
        self,
        steps: int,
        margin: int,
        drafts: Optional[Dict[int, List[int]]] = None,
        cohort: Optional[tuple] = None,
    ):
        """One decode dispatch over the (possibly compacted) row bucket.

        With ``drafts`` (slot -> proposed tokens) this is a speculative
        VERIFY dispatch: ``steps`` is the verify window K = max_draft + 1
        and the device scores all K positions in one forward
        (model_runner.spec_verify) — otherwise it is the regular fused
        ``steps``-iteration decode. Both return the same state/result
        contract, so everything downstream (row→slot scatter, packed
        fetch, _process_chunk) is shared.

        ``cohort`` = ``(slots, (policy, version))`` restricts the
        dispatch to one weight cohort — a pin-policy flip's survivors
        decode with the store's retained buffer, a NAMED policy's
        requests with its registry buffer, while current default slots
        decode with ``self.params`` — interleaved dispatches, each with
        exact per-token version attribution.
        Cohort dispatches always take the compact gather path (a
        full-width dispatch would run the other cohort's rows under the
        wrong params)."""
        self._step_counter += 1
        key = jax.random.fold_in(self._rng_key, self._step_counter)
        s = self.config.max_num_seqs
        if cohort is None:
            slots = sorted(self._active)
            params = self.params
            version = self.model_version
        else:
            slots, (cname, version) = cohort
            if cname:
                # named cohort: the registry holds the buffer (the
                # cohort's requests pin it, so it cannot have been
                # demoted or dropped; a host reload here is impossible
                # while pins are held but would be correct anyway)
                params = self._policies.params_for(cname, version)
            else:
                params = (
                    self.params
                    if version == self.model_version
                    else self.weights.params_for(version)
                )
            if params is None:
                # cannot happen while the cohort exists (its requests
                # hold pins) — decoding them on the wrong weights would
                # silently corrupt the version fence, so fail loudly
                raise RuntimeError(
                    f"no weight buffer for pinned version "
                    f"{cname or 'default'}@v{version}"
                )
        pps = self._pages_bound(margin, slots)
        n_active = len(slots)
        rows = self._decode_rows_bucket(n_active) if self._compact_enabled else s
        want_rope = bool(self._slot_mm.any())
        # after the gate's STICKY auto-disable, slots realign to their
        # canonical boundaries within one chunk each (emission caps
        # there) and full regular chunks preserve alignment forever —
        # once every active slot sits on a boundary, latch the replay
        # machinery off so every later dispatch runs the plain spec-off
        # program instead of paying the boundary-to-now pool gather per
        # chunk. In-flight REGULAR full chunks are tolerated (the host
        # length view lags them, but they advance every surviving slot
        # by exactly decode_chunk — or cap it at its boundary — so
        # alignment mod decode_chunk is unchanged when they land); an
        # in-flight verify chunk (partial accepts move slots off
        # boundaries) defers the latch to a later dispatch.
        if (
            self._spec_configured
            and not self._spec_replay_off
            and self._spec_gate.disabled
        ):
            cq = max(1, self.config.decode_chunk)
            if all(
                c["spec_draft_lens"] is None and c["steps"] == cq
                for c in self._inflight
            ) and all(
                (self._cached_len[sl] - self._align_base[sl]) % cq == 0
                for sl in self._active
            ):
                self._spec_replay_off = True
        spec_align = self._spec_configured and not self._spec_replay_off
        # plain per-slot 1-D arrays: listed ONCE, gathered/aliased by the
        # loop below. Arrays with extra semantics (active &valid, stops
        # axis=0, lens zeroed on padding, rope conditional, last_rows) are
        # handled explicitly after.
        plain_attrs = (
            "_cur_tokens", "_temp_dev", "_top_p_dev", "_top_k_dev",
            "_greedy_dev", "_remaining", "_no_stop",
        )
        # full-width = identity row map (row r IS slot r). Cohort
        # dispatches never take it — the identity map would cover the
        # OTHER cohort's slots too — and BOTH the gather below and the
        # post-dispatch scatter key off this one flag (a rows==s cohort
        # dispatch is still row-gathered, so assigning its row-space
        # results as slot-space state would corrupt the other cohort)
        full_width = rows >= s and cohort is None
        if full_width:
            rows = s
            row_slots = np.arange(s, dtype=np.int32)
            tables_dev = jnp.asarray(self._tables[:, :pps])
            st = {a: getattr(self, a) for a in plain_attrs}
            active = self._active_dev
            stops, lens = self._stop_tokens, self._lens_dev
            rope = self._rope_delta_dev if want_rope else None
            # identity row→slot map, built ONCE (letting decode_multi
            # default it would re-create the arange eagerly inside the
            # dispatch scope — a stray compile on the rung's bill)
            slot_ids_dev = self._identity_slots
            align_dev = self._align_base_dev if spec_align else None
        else:
            # compact dispatch: gather per-slot state into the row space.
            # Padding rows carry slot id `s` — their gathers CLIP to slot
            # s-1 but `valid` forces them inactive (no emission, no KV
            # write), and the post-dispatch scatter DROPS them.
            row_slots = np.full(rows, s, np.int32)
            row_slots[:n_active] = slots
            clipped = jnp.asarray(np.minimum(row_slots, s - 1))
            valid = jnp.asarray(row_slots < s)
            tables_np = np.full(
                (rows, pps), self.cache_config.num_pages, np.int32
            )
            tables_np[:n_active] = self._tables[slots, :pps]
            tables_dev = jnp.asarray(tables_np)
            st = {
                a: jnp.take(getattr(self, a), clipped)
                for a in plain_attrs
            }
            active = jnp.take(self._active_dev, clipped) & valid
            stops = jnp.take(self._stop_tokens, clipped, axis=0)
            lens = jnp.where(valid, jnp.take(self._lens_dev, clipped), 0)
            rope = (
                jnp.take(self._rope_delta_dev, clipped) if want_rope
                else None
            )
            slot_ids_dev = jnp.asarray(row_slots)
            align_dev = (
                jnp.where(valid, jnp.take(self._align_base_dev, clipped), 0)
                if spec_align else None
            )
        # canonical-alignment replay width (spec engines only): partial
        # draft accepts leave slots mid-chunk; the program replays
        # boundary-to-now K/V so numerics never depend on dispatch
        # boundaries (rl = 0 everywhere reduces to the plain program)
        replay = max(1, self.config.decode_chunk) - 1 if spec_align else 0
        spec_draft_lens: Optional[np.ndarray] = None
        if drafts is not None:
            # draft rows in ROW space (compact or full-width alike):
            # rows without a proposal carry draft_len 0 — a plain
            # single-token step for them inside the same dispatch
            kd = steps - 1
            draft_np = np.zeros((rows, kd), np.int32)
            spec_draft_lens = np.zeros(rows, np.int32)
            for r_ in range(rows):
                sl_ = int(row_slots[r_])
                toks_d = drafts.get(sl_) if sl_ < s else None
                if toks_d:
                    m_ = min(len(toks_d), kd)
                    draft_np[r_, :m_] = toks_d[:m_]
                    spec_draft_lens[r_] = m_
            # hoisted eager conversions (see the prefill dispatch note)
            draft_dev = jnp.asarray(draft_np)
            draft_lens_dev = jnp.asarray(spec_draft_lens)
            with goodput.dispatch_scope(
                self.compiles, "spec_verify",
                precompile_lib.spec_sig(rows, steps, pps, replay),
            ):
                (
                    self.cache, toks, logps, emitted, active_after,
                    remaining_a, no_stop_a, lens_a, new_last, cur_next,
                ) = model_runner.spec_verify(
                    params, self.model_config, self.cache,
                    tables_dev, lens,
                    st["_cur_tokens"], draft_dev,
                    draft_lens_dev, active, st["_remaining"],
                    st["_no_stop"], stops, key,
                    st["_temp_dev"], st["_top_p_dev"], st["_top_k_dev"],
                    st["_greedy_dev"], k=steps,
                    topk_bound=self._sampling_mode(),
                    attn_impl=self._attn_impl,
                    ppcb=self.config.pages_per_compute_block,
                    spb=self.config.slots_per_block,
                    last_rows=self._last_rows,
                    rope_delta=rope,
                    slot_ids=slot_ids_dev,
                    align_base=align_dev,
                    replay=replay,
                )
        else:
            with goodput.dispatch_scope(
                self.compiles, "decode",
                precompile_lib.decode_sig(rows, steps, pps, replay),
            ):
                out = model_runner.decode_multi(
                    params, self.model_config, self.cache,
                    tables_dev, lens,
                    st["_cur_tokens"], active, st["_remaining"],
                    st["_no_stop"], stops, key,
                    st["_temp_dev"], st["_top_p_dev"], st["_top_k_dev"],
                    st["_greedy_dev"], steps=steps,
                    topk_bound=self._sampling_mode(),
                    attn_impl=self._attn_impl,
                    ppcb=self.config.pages_per_compute_block,
                    spb=self.config.slots_per_block,
                    last_rows=self._last_rows,
                    rope_delta=rope,
                    slot_ids=slot_ids_dev,
                    align_base=align_dev,
                    replay=replay,
                )
            (
                self.cache, toks, logps, emitted, active_after,
                remaining_a, no_stop_a, lens_a, new_last, cur_next,
            ) = out
            # next_tokens is the device-computed next input per row: a
            # replay-mode row that hit its chunk boundary mid-dispatch
            # resumes from its LAST emitted token; for plain chunks it
            # equals step steps-1's sample for every live row
        # updated per-slot state: ONE dict drives both the full-width
        # assignment and the compact row→slot scatter (padding rows drop)
        updates = {
            "_cur_tokens": cur_next,
            "_active_dev": active_after,
            "_remaining": remaining_a,
            "_no_stop": no_stop_a,
            "_lens_dev": lens_a,
        }
        if full_width:
            for a, v in updates.items():
                setattr(self, a, v)
            self._last_rows = new_last
        else:
            scat = jnp.asarray(row_slots)
            for a, v in updates.items():
                setattr(
                    self, a,
                    getattr(self, a).at[scat].set(v, mode="drop"),
                )
            self._last_rows = {
                k_: v_.at[:, scat].set(new_last[k_], mode="drop")
                for k_, v_ in self._last_rows.items()
            }
        self.total_decode_chunks += 1
        self.total_rows_dispatched += rows
        self.total_rows_active += n_active
        self._decode_rows_dispatched = rows
        self._decode_rows_active = n_active
        self.rows_dispatched_hist[rows] = (
            self.rows_dispatched_hist.get(rows, 0) + 1
        )
        if self.tracer.enabled:
            span_attrs = dict(
                rows_dispatched=rows, rows_active=n_active, steps=steps,
            )
            if spec_draft_lens is not None:
                span_attrs["spec_draft_tokens"] = int(
                    spec_draft_lens.sum()
                )
                span_attrs["spec_draft_rows"] = int(
                    (spec_draft_lens > 0).sum()
                )
            self.tracer.instant("decode_chunk", "__engine__", **span_attrs)
        # ONE packed fetch per chunk (lazy: np.asarray in _process_chunk
        # blocks; until then the device crunches the next chunk). The
        # pack program's shape follows the dispatch's (rows, steps), so
        # its compile is attributed to the same ladder rung — the AOT
        # precompiler compiles it alongside the forward + merge.
        if drafts is not None:
            pack_scope = goodput.dispatch_scope(
                self.compiles, "spec_verify",
                precompile_lib.spec_sig(rows, steps, pps, replay),
            )
        else:
            pack_scope = goodput.dispatch_scope(
                self.compiles, "decode",
                precompile_lib.decode_sig(rows, steps, pps, replay),
            )
        with pack_scope:
            packed = model_runner.pack_host(
                toks, logps, emitted, active_after
            )
        self._inflight.append(
            {
                "packed": packed,
                "seq": self.total_decode_chunks,
                "steps": steps,
                # worst-case token growth of this chunk (for later
                # dispatches' page margins — verify and regular chunk
                # sizes can mix in the pipeline)
                "max_tokens": steps,
                # per-row draft lengths of a verify chunk (accept-rate
                # accounting happens at process time, None = regular)
                "spec_draft_lens": spec_draft_lens,
                # dispatch-time row→slot snapshot + slot→request snapshot:
                # a slot finished and re-admitted between dispatch and
                # processing must not absorb this chunk's stale results
                "row_slots": row_slots,
                "reqs": dict(self._active),
                "version": version,
            }
        )

    def _process_chunk(self, chunk: Dict[str, Any]):
        """Retire one in-flight chunk: wait for its packed result
        (``decode.fetch``), then book it and release what it deferred
        (``decode.process``)."""
        with self._loop_span("decode.fetch", chunk["seq"]) as sp:
            packed = np.asarray(chunk["packed"])  # blocks on the device here
        self._loop_s["decode_fetch"] += sp.duration
        with self._loop_span("decode.process", chunk["seq"]) as sp:
            self._book_chunk(chunk, packed)
            self._flush_deferred()
        self._loop_s["decode_process"] += sp.duration

    def _book_chunk(self, chunk: Dict[str, Any], packed: np.ndarray):
        steps = chunk["steps"]
        row_slots = chunk["row_slots"]
        s = self.config.max_num_seqs
        r = len(row_slots)
        n = steps * r
        h_toks = packed[:n].reshape(steps, r).astype(np.int64)
        h_logps = packed[n : 2 * n].reshape(steps, r)
        h_emitted = packed[2 * n : 3 * n].reshape(steps, r) > 0.5
        h_active = packed[3 * n : 3 * n + r] > 0.5
        now = time.monotonic()
        n_emitted = int(h_emitted.sum())
        if self._last_decode_mark is not None and n_emitted:
            dt = now - self._last_decode_mark
            if dt > 0:
                inst = n_emitted / dt
                self._decode_tps = (
                    inst if self._decode_tps == 0.0
                    else 0.8 * self._decode_tps + 0.2 * inst
                )
        self._last_decode_mark = now
        # per-row emitted prefix length (device emission is a prefix —
        # `emitted` is the step-entry active flag, which only falls)
        n_emit = np.where(
            h_emitted.all(axis=0), steps, h_emitted.argmin(axis=0)
        )
        dl = chunk.get("spec_draft_lens")
        # verify-chunk acceptance accounting runs AFTER the row loop on
        # the HOST-truncated emit counts: the device buffer only holds
        # the first 8 stop ids, so a stop landing inside an accepted
        # draft is caught below — those positions are never delivered
        # and must not count as accepted (they would inflate the gate's
        # EWMA and delay auto-disable)
        n_emit_host = n_emit.copy() if dl is not None else None
        for row in range(r):
            slot = int(row_slots[row])
            if slot >= s:
                continue  # compaction padding row
            req = chunk["reqs"].get(slot)
            if req is None or self._active.get(slot) is not req:
                continue  # finished/preempted since dispatch
            k = int(n_emit[row])
            stopped_host = False
            if k:
                # host backstop over the FULL stop list (the device buffer
                # only holds the first 8 stop ids), honoring
                # min_new_tokens: the token at step t is output index
                # len(output_ids) + t + 1
                if req.stop_token_ids:
                    hits = np.isin(
                        h_toks[:k, row],
                        np.asarray(req.stop_token_ids, np.int64),
                    )
                    t0 = req.min_new_tokens - len(req.output_ids) - 1
                    if t0 > 0:
                        hits[:t0] = False
                    if hits.any():
                        k = int(np.argmax(hits)) + 1
                        stopped_host = True
                        if n_emit_host is not None:
                            n_emit_host[row] = k
                if req.first_token_time is None:
                    req.first_token_time = now
                req.output_ids.extend(int(t) for t in h_toks[:k, row])
                req.output_logprobs.extend(
                    float(x) for x in h_logps[:k, row]
                )
                req.output_versions.extend([chunk["version"]] * k)
                if self._proposer is not None:
                    self._proposer.extend(
                        slot, [int(t) for t in h_toks[:k, row]]
                    )
                # each emitted step cached the slot's previous input token
                self._cached_len[slot] += k
                self.total_generated_tokens += k
                self.ledger.note_tokens(k)
            if stopped_host:
                self._finish(slot, "stop")
            elif not h_active[row]:
                self._finish(slot, "length")
        if dl is not None:
            # per-row accepted drafts = delivered - 1 (the bonus token is
            # free, not a draft), capped by what was actually drafted
            self._observe_spec(
                int(dl.sum()),
                int(np.minimum(np.maximum(n_emit_host - 1, 0), dl).sum()),
                rows=int((n_emit_host > 0).sum()),
            )

    def _observe_spec(
        self, drafted: int, accepted: int, rows: int = 0
    ) -> None:
        """Accept-rate accounting for one verify chunk + the auto-disable
        gate (sustained accept rates below the floor make drafting pure
        overhead — the gate turns speculation off sticky)."""
        self.total_spec_chunks += 1
        self.spec_draft_tokens_total += drafted
        self.spec_accepted_tokens_total += accepted
        gate = self._spec_gate
        still_on = gate.observe(drafted, accepted)
        if not still_on and not self._spec_disable_logged:
            self._spec_disable_logged = True
            logger.warning(
                f"speculative decoding auto-disabled: accept-rate EWMA "
                f"{gate.ewma:.3f} stayed below floor {gate.floor} for "
                f"{gate.patience} verify chunks"
            )
        if self.tracer.enabled:
            # rows = rows that emitted this round (each contributes one
            # guaranteed base token on top of its accepted drafts —
            # trace_report --spec needs it for verified tok/s)
            self.tracer.instant(
                "spec_verify", "__engine__",
                drafted=drafted, accepted=accepted, rows=rows,
            )

    def _sample_and_append(
        self, logits: jnp.ndarray, only_slots: List[int]
    ):
        """Sample one token per slot from a full [S, V] stack (one static
        shape for every admission/decode step) and handle stops for
        `only_slots`."""
        self._step_counter += 1
        key = jax.random.fold_in(self._rng_key, self._step_counter)
        mode = self._sampling_mode()
        with goodput.dispatch_scope(
            self.compiles, "sample", precompile_lib.sample_sig(mode)
        ):
            toks, logps = model_runner.sample_tokens(
                logits, key, self._temp_dev, self._top_p_dev,
                self._top_k_dev, self._greedy_dev,
                topk_bound=mode,
            )
            # the packed fetch's program shape is fixed ([S]+[S]) — it
            # rides the sample rung so its compile never lands untagged
            fetched = model_runner.pack_host(toks, logps)
        # record sampled tokens as the next decode inputs for these slots
        sl = jnp.asarray(np.asarray(only_slots, np.int32))
        self._cur_tokens = self._cur_tokens.at[sl].set(toks[sl])
        s = self.config.max_num_seqs
        packed = np.asarray(fetched)
        host_toks = packed[:s].astype(np.int64)
        host_logps = packed[s:]
        self._append_sampled(host_toks, host_logps, only_slots)

    def _append_sampled(
        self, toks: np.ndarray, logps: np.ndarray, only_slots: List[int]
    ):
        for slot in sorted(only_slots):
            i = slot
            req = self._active[slot]
            if req.first_token_time is None:
                req.first_token_time = time.monotonic()
            req.output_ids.append(int(toks[i]))
            req.output_logprobs.append(float(logps[i]))
            # the admission-time first token: _install just stamped
            # weight_version (== model_version on the default line, the
            # resolved line version on a named one) — exact either way
            req.output_versions.append(req.weight_version)
            if self._proposer is not None:
                self._proposer.extend(slot, [int(toks[i])])
            self.total_generated_tokens += 1
            self.ledger.note_tokens(1)
            out_len = len(req.output_ids)
            total_len = len(req.input_ids) + out_len
            stop_hit = (
                int(toks[i]) in req.stop_token_ids
                and out_len >= req.min_new_tokens
            )
            if stop_hit:
                self._finish(slot, "stop")
            elif (
                out_len >= req.max_new_tokens
                or total_len >= self.config.max_model_len
            ):
                self._finish(slot, "length")

    def _finish(self, slot: int, reason: str):
        req = self._active.pop(slot)
        if reason == "abort":
            self.total_aborted += 1
        elif req.deadline_at is not None:
            # soft-deadline outcome, counted only on real completions
            # (an abort is a pause-window resume, not a final answer)
            if time.monotonic() > req.deadline_at:
                self.deadline_misses_total += 1
                self.tracer.instant(
                    "deadline_miss", req.rid,
                    sched_class=req.priority,
                    late_s=round(
                        time.monotonic() - req.deadline_at, 4
                    ),
                )
        # the slot's pages hold the prompt plus all generated tokens
        # except the last sampled one (it was never fed back). A request
        # that finished pinned to a pre-flip version holds OLD-version
        # KV: never park it for new-version claimants. A NAMED request
        # parks into its own (policy, version) namespace — but only
        # while that pair still serves (no future claimants otherwise)
        # — and always drops its registry pin.
        if req.policy:
            self._release_slot(
                slot,
                park_tokens=(
                    req.all_tokens
                    if self.config.prefix_reuse_min > 0
                    and self._policies.is_live(
                        req.policy, req.weight_version
                    )
                    else None
                ),
                ns=(req.policy, req.weight_version),
            )
            self._policies.release(req.policy, req.weight_version)
            self._policies.note_tokens(req.policy, len(req.output_ids))
        else:
            self._release_slot(
                slot,
                park_tokens=(
                    req.all_tokens
                    if self.config.prefix_reuse_min > 0
                    and req.weight_version == self.model_version
                    else None
                ),
            )
            if req.weight_version != self.model_version:
                # last pin out drops the old buffer (HBM back)
                self.weights.release(req.weight_version)
        now = time.monotonic()
        if reason != "abort":
            # aborts are pause-window resumes, not client-visible
            # completions — they'd poison the latency distributions
            # (and must not count toward serving-readiness either)
            self._completed_requests += 1
            self._hists["ttft_seconds"][req.priority].observe(
                (req.first_token_time or now) - req.submit_time
            )
            self._hists["request_latency_seconds"][req.priority].observe(
                now - req.submit_time
            )
        if self.tracer.enabled:
            # decode covers first-token → finish; request is the full
            # submit → finish lifecycle (what a client timeline wants)
            self.tracer.record(
                "decode", req.rid, req.first_token_time or now, now,
                completion_tokens=len(req.output_ids), reason=reason,
                preemptions=req.preemptions,
            )
            self.tracer.record(
                "request", req.rid, req.submit_time, now,
                prompt_tokens=len(req.input_ids),
                completion_tokens=len(req.output_ids), reason=reason,
                model_version=self.model_version,
            )
        # drop the rid's trace binding (an aborted request that resumes
        # re-binds from its next /generate call's header)
        self.tracer.unbind_trace(req.rid)
        result = {
            "output_ids": req.output_ids,
            "output_logprobs": req.output_logprobs,
            "output_versions": req.output_versions,
            "meta_info": {
                "finish_reason": {"type": reason},
                "prompt_tokens": len(req.input_ids),
                "completion_tokens": len(req.output_ids),
                "latency": now - req.submit_time,
                "ttft": (req.first_token_time or now) - req.submit_time,
                "model_version": self.model_version,
                "preemptions": req.preemptions,
                "cached_tokens": req.cached_tokens,
                # named requests carry their handle resolution; the
                # default line adds NO new keys (strict no-op contract)
                **(
                    {
                        "policy": req.policy,
                        "policy_version": req.weight_version,
                    }
                    if req.policy
                    else {}
                ),
            },
        }
        if not req.future.done():
            req.future.set_result(result)
