"""Request-lifecycle span tracing + Prometheus text rendering.

The observability core of the async-RL plane (role of the request-event
logs Laminar/ROLL-Flash build their analyses on): a thread-safe,
bounded-memory span recorder keyed by request id. Producers are the
inference engine scheduler loop (queue-wait / prefill / decode /
preemption / weight-update windows), the remote rollout controller
(submit→first-token→complete, pause windows), and anything else that
wants onto the same timeline.

Design constraints, in order:

1. **Disabled must be cheap.** The scheduler loop calls into the tracer
   per admission wave and per finished request, and that loop is
   host-bound at high slot counts. So `record()` on a disabled
   tracer returns before touching the lock. In a process that has jax
   loaded a `span()` costs one small object, two clock reads and an inert
   `TraceAnnotation` (about a microsecond) — nothing is recorded per
   token or per row. In a process that has not (an env worker, the
   router) no span ever imports it, and a disabled tracer's `span()` is
   one shared null object.
2. **Bounded memory.** Spans live in a `deque(maxlen=max_spans)`; a
   long-running server drops the oldest and counts them (`dropped`).
3. **Two export formats.** JSONL (one span per line — what
   `tools/trace_report.py` consumes) and Chrome trace-event JSON
   (loadable in Perfetto / chrome://tracing: one `ph:"X"` complete event
   per span, rows grouped per rid via stable tids).

Span times are `time.monotonic()` seconds; exports convert to the
microseconds the trace-event format wants. Each tracer also remembers
the unix time of its monotonic epoch (``epoch_unix_s``) so traces from
DIFFERENT processes — each with its own monotonic zero — can be shifted
onto one shared timeline (``utils/telemetry.stitch_chrome_traces``).

One primitive (:func:`span`): every layer boundary of the trainer and of the
engine's loop thread, and every request-keyed ``SpanTracer.span``, is one
``with span(name, **attrs)``. It always enters a
``jax.profiler.TraceAnnotation("areal:<name>", **attrs)`` — inert unless a
profile is running, and then an event on the device trace's own clock with
its attributes as stats — and, when a tracer is handed in and enabled,
records the same interval into that tracer's ring with the id of the span
that encloses it on this thread (``parent``), so ``/trace`` shows the tree
the profile shows (a span's self time is its duration minus its
children's). ``DEVICE_SCOPES`` is the one vocabulary of
names for device work (:func:`device_scope`): they land in every HLO
instruction's ``op_name`` and so survive a recompile that renumbers the
fusions.

Cross-process trace context (r9): requests propagate a trace id over
HTTP via the ``X-Areal-Trace`` / ``X-Areal-Rid`` headers. A receiving
process calls ``bind_trace(rid, trace_id)`` and every span it records
for that rid carries a ``trace`` attr — the join key that stitches
client, router, and server spans into one end-to-end timeline.
"""

import contextlib
import itertools
import json
import sys
import threading
import time
import uuid
from collections import OrderedDict, deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

from areal_tpu.api.cli_args import TracingConfig

# HTTP propagation headers: the trace id (one per rollout episode,
# surviving retries and suffix-resume migrations) and the request id
TRACE_HEADER = "X-Areal-Trace"
RID_HEADER = "X-Areal-Rid"


def new_trace_id() -> str:
    return uuid.uuid4().hex


def trace_headers(trace_id: str, rid: str = "") -> Dict[str, str]:
    """Outbound header dict for one traced request."""
    h = {TRACE_HEADER: trace_id}
    if rid:
        h[RID_HEADER] = rid
    return h


class Span:
    """``sid``/``parent`` are process-wide span ids (0 = recorded after the
    fact through ``SpanTracer.record``, outside any tree / no enclosing
    span on its thread)."""

    __slots__ = ("name", "rid", "t_start", "t_end", "attrs", "sid", "parent")

    def __init__(
        self,
        name: str,
        rid: str,
        t_start: float,
        t_end: float,
        attrs: Optional[Dict[str, Any]] = None,
        sid: int = 0,
        parent: int = 0,
    ):
        self.name = name
        self.rid = rid
        self.t_start = t_start
        self.t_end = t_end
        self.attrs = attrs or {}
        self.sid = sid
        self.parent = parent

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "name": self.name,
            "rid": self.rid,
            "ts": self.t_start,
            "dur": self.duration,
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.sid:
            d["id"] = self.sid
            d["parent"] = self.parent
        return d

    def __repr__(self):  # pragma: no cover
        return (
            f"Span({self.name!r}, rid={self.rid!r}, "
            f"dur={self.duration * 1e3:.2f}ms)"
        )


# --------------------------------------------------------------------------
# The one span primitive
# --------------------------------------------------------------------------
SPAN_PREFIX = "areal:"  # every span's name in the profiler's trace

# jax.named_scope names for device work, each entered at the one place that
# work is computed (models/transformer.py, engine/spmd_engine.py,
# inference/model_runner.py, around the ops/ kernels)
DEVICE_SCOPES = (
    "embed", "attention", "mlp", "lm_head", "loss", "optimizer", "sample",
    "kv_write",
    # inside ``mlp`` where the layer is sparse (ops/moe.py); innermost wins
    "moe_route", "moe_dispatch", "moe_experts",
    # the sparse layer's mesh form (ops/moe.make_sharded_moe_ffn): the
    # tokens and their scores gathered to the chips that hold the experts,
    # the chips' parts summed and scattered back
    "moe_exchange",
    # learned sparse attention (ops/sparse_attention.py): the indexer's
    # projections and scores, and the top-k selection; the core it feeds
    # stays under ``attention``
    "dsa_index", "dsa_select",
    # gated delta-rule linear attention (ops/kda.py): the chunked rule, all
    # passes; and what the layer computes around it (the short convolution,
    # SiLU, l2norm, decay, beta, the gated output norm)
    "kda_scan", "kda_prep",
    # inside ``kda_scan`` where the decay is one number a head and token:
    # the chunk operands (K K^T, Q K^T, the [64, 64] decay matrix, the
    # triangular inverse) and their backward, as the kernels ``gdn_ops_fwd``
    # / ``gdn_ops_bwd`` on a TPU (since PR 53; their custom calls keep the
    # scope) and as XLA's products elsewhere (ops/kda._prep_scalar)
    "gdn_operands",
    # the core of a window-attention layer (models/transformer.
    # _rotary_mixer): the splash kernel over the window's band and the
    # relayouts around it. NOT inside ``attention``, which keeps the
    # full-attention layers' core
    "attn_window",
    # what a ``cca`` mixer computes between its projections and its core
    # (models/transformer.cca_qkv): the q-k mean, the two causal
    # convolutions, the norm and temperature, the value shift, and their
    # backward. The rotation is outside, the core under ``attention``
    "cca_mix",
    # what a layer computes around its mixer's core and its FFN, each opened
    # at its call sites in models/transformer.py and never inside a helper
    # that another scope's code calls too (PR 50). From the layer's normed
    # input to what the core or the mixer's own scope takes (the q, k, v
    # projections, their biases, the q / k norms, a latent's compression
    # and expansion); the rotation of q and k and the tables it reads; a
    # rotary kind's gate a head; the product with ``wo``
    "qkv_proj", "rope", "attn_gate", "o_proj",
    # the residual stream: its norms (a layer's two and the final one) and
    # the sums back onto it
    "stream_norm", "residual",
    # engine/programs.py: the parameter tree cast to the compute dtype in
    # every run of a forward program (backward: the gradient cast back), and
    # a microbatch's gradient added to the float32 accumulator
    "param_cast", "grad_add",
    # around the loop over the layers: what the loop itself does (a layer's
    # weights sliced out of the stack, what is stacked for the backward, a
    # layer's gradient written into the stacked one). Every scope above is
    # opened inside it and the innermost scope names an operation
    "layer_stack",
    # a ROTATED latent mixer (models/transformer._mla_mixer): from the normed
    # latent to what the core takes, ``c @ w_kv_b``, its split into keys and
    # values, the one rotated key part repeated over the heads and joined to
    # them, the query's two parts joined again: what a core that took the
    # shared key ONCE would not write. The compressions and their norms stay
    # under ``qkv_proj``, the turn under ``rope``; the NoPE mixer's expansion
    # stays under ``qkv_proj`` too, where its cell's shares have read it
    "mla_expand",
    # the draft module beside the stack (models/transformer.draft_forward):
    # what the module ALONE computes, the shifted embedding lookup and its
    # masks, its two input norms, the product that joins them, the norm
    # under the head. Its block, head and loss read ``attention``, ``mlp``,
    # ``lm_head``, ``loss`` ... as any layer's
    "mtp",
    # a block-diffusion policy's training rows (ops/bd_attention.py): the
    # core under the mask that is causal between blocks and two-way inside
    # one (the kernels ``bd_attn_fwd`` / ``bd_attn_bwd``, or ``bd_attn_dq`` /
    # ``bd_attn_dkv`` for a row beyond the one backward kernel's VMEM, and
    # the reshapes around them; NOT inside ``attention``, which stays a
    # causal core's), and what makes the kernels' integer rows and tile
    # table out of the rows' (sequence, block, stream) on the device, and
    # counts the pairs
    "attn_bd", "bd_layout",
    # a state-space mixer (models/transformer._ssm_mixer): the selective
    # scan itself (ops/ssm_scan.py: the kernels ``ssm_scan_fwd`` /
    # ``ssm_scan_bwd``, the relayouts into their tiles, the resets' steps),
    # and what the layer computes around it between its in and out
    # projections (the convolution, ``x_proj``, the three inner norms,
    # ``dt_proj``, softplus, the skip and the gate). The in and out
    # projections stay under ``qkv_proj`` / ``o_proj``
    "ssm_scan", "ssm_prep",
)


def device_scope(name: str):
    """The device work computed inside is ``name``, one of
    ``DEVICE_SCOPES``: a ``jax.named_scope`` (the HLO's ``op_name``, which
    a profile's events carry) and the frontend attribute ``areal_scope``
    on the same operations. Neither changes a number or a fusion (the
    optimized v5e program of the trainer's ``grad_step`` is the same text
    with and without them, PERF.md PR 25). The attribute is there for the
    persistent compile cache: its key is the program WITHOUT debug
    metadata, so with the named scope alone a program compiled before a
    scope was added or moved would be loaded from the cache under its old
    names; the attribute puts the names into the key. A context manager,
    or a decorator over a whole function body (the function keeps its
    name, so a jitted one keeps its program's)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is not one of {DEVICE_SCOPES}")
    return _device_scope(name)


@contextlib.contextmanager
def _device_scope(name: str):
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata

    with jax.named_scope(name), set_xla_metadata(areal_scope=name):
        yield


class _SpanTLS(threading.local):
    def __init__(self):
        self.stack: List[int] = []  # ids of the spans open on this thread


_TLS = _SpanTLS()
_IDS = itertools.count(1)
# jax.profiler.TraceAnnotation, once this process has jax loaded: a span
# never imports jax itself (a process without it has no profile to be in)
_ANNOTATION = None


class _SpanCtx:
    __slots__ = (
        "name", "tracer", "rid", "attrs", "t_start", "t_end", "sid",
        "parent", "_ann",
    )

    def __init__(self, name, tracer, rid, attrs):
        self.name = name
        self.tracer = tracer
        self.rid = rid
        self.attrs = attrs

    def __enter__(self):
        global _ANNOTATION
        if _ANNOTATION is None and "jax" in sys.modules:
            from jax.profiler import TraceAnnotation as _ANNOTATION
        self._ann = None
        if _ANNOTATION is not None:
            self._ann = _ANNOTATION(SPAN_PREFIX + self.name, **self.attrs)
            self._ann.__enter__()
        stack = _TLS.stack
        self.parent = stack[-1] if stack else 0
        self.sid = next(_IDS)
        stack.append(self.sid)
        self.t_start = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t_end = time.monotonic()
        _TLS.stack.pop()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self.tracer
        if tr is not None and tr.config.enabled:
            tr._append(
                self.name,
                self.rid or threading.current_thread().name,
                self.t_start, self.t_end, self.attrs, self.sid, self.parent,
            )
        return False

    def set(self, **attrs) -> None:
        """Attributes known only inside the body (what a pack counted):
        stats of the profile's event and attrs of the ring's span alike."""
        self.attrs = {**self.attrs, **attrs}
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    @property
    def duration(self) -> float:
        """Host seconds between entry and exit (``time.monotonic``)."""
        return self.t_end - self.t_start


def span(name: str, tracer: Optional["SpanTracer"] = None, rid: str = "",
         **attrs):
    """``with span("train.pack", real_tokens=5):`` — see the module
    docstring. ``tracer`` is the ring to record into when it is enabled
    (none: the profile only); ``rid`` groups a request's spans (default:
    the thread's name, one row per thread). The context object keeps
    ``duration`` for callers that also count host seconds."""
    return _SpanCtx(name, tracer, rid, attrs)


class _NullSpanCtx:
    """What a disabled tracer's ``span()`` hands back in a process without
    jax: nothing to time, nothing to annotate, nothing allocated."""

    __slots__ = ()
    duration = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_CTX = _NullSpanCtx()


class SpanTracer:
    """Thread-safe bounded span recorder; strict no-op when disabled."""

    # rid → trace-id bindings kept at most this many at a time (the live
    # request set, not history — completed requests unbind)
    MAX_TRACE_BINDINGS = 8192

    def __init__(
        self, config: Optional[TracingConfig] = None, service: str = ""
    ):
        self.config = config or TracingConfig()
        # which process/role recorded these spans ("client", "router",
        # "server:<addr>"): stitched multi-process exports group rows
        # under one named track per service
        self.service = service
        # unix time of this process's monotonic zero: ts_unix = ts + epoch
        self.epoch_unix_s = time.time() - time.monotonic()
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(
            maxlen=max(1, self.config.max_spans)
        )
        # incoming trace context per live rid (LRU-bounded)
        self._trace_ids: "OrderedDict[str, str]" = OrderedDict()
        self.dropped = 0

    @property
    def enabled(self) -> bool:
        return self.config.enabled

    # ------------------------------------------------------------------
    # Cross-process trace context
    # ------------------------------------------------------------------
    def bind_trace(self, rid: str, trace_id: str) -> None:
        """Attach an incoming trace id to a rid: every span recorded for
        that rid until ``unbind_trace`` carries a ``trace`` attr."""
        if not self.config.enabled or not trace_id:
            return
        with self._lock:
            self._trace_ids[rid] = trace_id
            self._trace_ids.move_to_end(rid)
            while len(self._trace_ids) > self.MAX_TRACE_BINDINGS:
                self._trace_ids.popitem(last=False)

    def unbind_trace(self, rid: str) -> None:
        if not self.config.enabled:
            return
        with self._lock:
            self._trace_ids.pop(rid, None)

    def trace_of(self, rid: str) -> Optional[str]:
        with self._lock:
            return self._trace_ids.get(rid)

    # ------------------------------------------------------------------
    # Recording
    # ------------------------------------------------------------------
    def record(
        self, name: str, rid: str, t_start: float, t_end: float, **attrs
    ) -> None:
        """Append one finished span (times are time.monotonic seconds)."""
        if not self.config.enabled:
            return
        self._append(name, rid, t_start, t_end, attrs)

    def _append(self, name, rid, t_start, t_end, attrs, sid=0, parent=0):
        with self._lock:
            tr = self._trace_ids.get(rid)
            if tr is not None and "trace" not in attrs:
                attrs["trace"] = tr
            if len(self._spans) == self._spans.maxlen:
                # ring overflow: the oldest span silently vanishing would
                # make a truncated trace read as a complete one — count it
                # (exported as tracing_dropped_spans_total on /metrics)
                self.dropped += 1
            self._spans.append(
                Span(name, rid, t_start, t_end, attrs, sid, parent)
            )

    def instant(self, name: str, rid: str, **attrs) -> None:
        """Zero-duration event (e.g. a preemption)."""
        now = time.monotonic()
        self.record(name, rid, now, now, **attrs)

    def span(self, name: str, rid: str, **attrs):
        """Context manager measuring its body: the module's :func:`span`
        keyed by ``rid`` (in the profile whenever one runs; in the ring
        when this tracer is enabled). Disabled and with no profiler in the
        process, there is neither: a shared null object."""
        if not self.config.enabled and "jax" not in sys.modules:
            return _NULL_CTX
        return _SpanCtx(name, self, rid, attrs)

    # ------------------------------------------------------------------
    # Access / export
    # ------------------------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> List[Span]:
        """Return all spans and clear the buffer (GET /trace semantics)."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            return out

    def to_chrome_trace(
        self, spans: Optional[Iterable[Span]] = None
    ) -> Dict[str, Any]:
        """Chrome trace-event JSON: every span is a complete ("X") event;
        rids map to stable tids so Perfetto renders one row per request."""
        if spans is None:
            spans = self.snapshot()
        tids: Dict[str, int] = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s.rid, len(tids) + 1)
            events.append(
                {
                    "name": s.name,
                    "cat": "areal_tpu",
                    "ph": "X",
                    "ts": s.t_start * 1e6,
                    "dur": max(0.0, s.duration) * 1e6,
                    "pid": 0,
                    "tid": tid,
                    "args": {"rid": s.rid, **s.attrs},
                }
            )
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 0,
                "tid": tid,
                "args": {"name": rid},
            }
            for rid, tid in tids.items()
        ]
        if self.service:
            meta.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": 0,
                    "args": {"name": self.service},
                }
            )
        return {
            "traceEvents": events + meta,
            "displayTimeUnit": "ms",
            # cross-process stitching needs to re-base each process's
            # monotonic clock; dropped makes ring truncation visible
            "otherData": {
                "service": self.service,
                "epoch_unix_s": self.epoch_unix_s,
                "dropped_spans": self.dropped,
            },
        }

    def export_chrome(self, path: str, drain: bool = False) -> None:
        spans = self.drain() if drain else self.snapshot()
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(spans), f)

    def export_jsonl(self, path: str, drain: bool = False) -> None:
        spans = self.drain() if drain else self.snapshot()
        with open(path, "a") as f:
            for s in spans:
                f.write(json.dumps(s.to_dict()) + "\n")

    def flush(self) -> None:
        """Drain to config.export_path (JSONL) when one is set — owners
        call this on shutdown so non-HTTP deployments still get a trace
        file without polling /trace."""
        if self.config.export_path:
            self.export_jsonl(self.config.export_path, drain=True)


# --------------------------------------------------------------------------
# HTTP export helpers
# --------------------------------------------------------------------------
def trace_response(tracer: "SpanTracer", query: str):
    """The one GET /trace contract (generation server AND router):
    DRAIN the tracer's buffer; ``?format=jsonl`` yields the line format
    ``tools/trace_report.py`` consumes, anything else the Chrome
    trace-event document. Returns ``(body_bytes, content_type)``."""
    import urllib.parse

    spans = tracer.drain()
    fmt = urllib.parse.parse_qs(query).get("format", [""])[0]
    if fmt == "jsonl":
        body = "".join(
            json.dumps(s.to_dict()) + "\n" for s in spans
        ).encode()
        return body, "application/jsonl"
    return (
        json.dumps(tracer.to_chrome_trace(spans)).encode(),
        "application/json",
    )


# --------------------------------------------------------------------------
# Prometheus text exposition
# --------------------------------------------------------------------------
# Default latency bucket ladder (seconds) for the native histograms —
# wide enough for queue waits under load shedding and TTFT under cold
# compiles; +Inf is implicit.
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)


class Histogram:
    """A native Prometheus histogram: fixed cumulative ``le`` buckets
    plus ``_sum``/``_count``. Thread-safe observe; mergeable across
    servers (same ladder) for fleet rollups; quantile estimates by
    linear interpolation within the winning bucket."""

    __slots__ = ("bounds", "counts", "sum", "count", "_lock")

    def __init__(self, bounds: tuple = LATENCY_BUCKETS):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +1 = +Inf bucket
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        i = 0
        for i, b in enumerate(self.bounds):
            if v <= b:
                break
        else:
            i = len(self.bounds)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def merge(self, other: "Histogram") -> "Histogram":
        if other.bounds != self.bounds:
            raise ValueError(
                "cannot merge histograms with different bucket ladders"
            )
        with self._lock:
            for i, c in enumerate(other.counts):
                self.counts[i] += c
            self.sum += other.sum
            self.count += other.count
        return self

    def quantile(self, q: float) -> float:
        """Estimated q-quantile from the cumulative buckets (0 when
        empty; the +Inf bucket answers its lower bound)."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if total <= 0:
            return 0.0
        target = q * total
        cum = 0
        for i, c in enumerate(counts):
            prev_cum = cum
            cum += c
            if cum >= target and c > 0:
                if i >= len(self.bounds):
                    return self.bounds[-1] if self.bounds else 0.0
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                frac = (target - prev_cum) / c
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
        return self.bounds[-1] if self.bounds else 0.0

    def cumulative(self) -> List[Tuple[float, int]]:
        """``[(le_bound, cumulative_count), ...]`` ending at +Inf."""
        with self._lock:
            counts = list(self.counts)
        out: List[Tuple[float, int]] = []
        cum = 0
        for b, c in zip(self.bounds, counts):
            cum += c
            out.append((b, cum))
        out.append((float("inf"), cum + counts[-1]))
        return out

    @classmethod
    def from_cumulative(
        cls, pairs: List[Tuple[float, float]], total_sum: float,
        total_count: float,
    ) -> "Histogram":
        """Reconstruct from parsed ``_bucket``/``_sum``/``_count``
        samples (the scrape-side inverse of rendering)."""
        finite = sorted(
            (le, c) for le, c in pairs if le != float("inf")
        )
        h = cls(tuple(le for le, _ in finite) or LATENCY_BUCKETS)
        if not finite:
            h.counts = [0] * (len(h.bounds) + 1)
        prev = 0.0
        counts = []
        for _, c in finite:
            counts.append(int(c - prev))
            prev = c
        inf_cum = next(
            (c for le, c in pairs if le == float("inf")), total_count
        )
        counts.append(int(inf_cum - prev))
        if len(counts) == len(h.bounds) + 1:
            h.counts = counts
        h.sum = float(total_sum)
        h.count = int(total_count)
        return h


# Explicit metric-type registry: surfaces register every name they emit
# (gauge | counter | histogram) so a new metric can't silently export as
# the wrong TYPE on the strength of a name suffix. The legacy suffix
# heuristic survives only as the fallback for unregistered names; the
# metrics-hygiene lint (tests/test_metrics_hygiene.py) enforces that no
# real surface relies on it.
METRIC_TYPES: Dict[str, str] = {}


def register_metric_types(types: Dict[str, str]) -> None:
    for name, t in types.items():
        if t not in ("gauge", "counter", "histogram"):
            raise ValueError(f"metric {name!r}: unknown type {t!r}")
        prev = METRIC_TYPES.get(name)
        if prev is not None and prev != t:
            raise ValueError(
                f"metric {name!r} re-registered as {t!r} (was {prev!r})"
            )
        METRIC_TYPES[name] = t


def parse_prometheus(text: str, prefix: str = "") -> Dict[str, float]:
    """Inverse of ``render_prometheus`` for scrape aggregation: flat
    ``{name: value}`` from text exposition. HELP/TYPE preambles are
    skipped; a label suffix (``name{...}``) is stripped to the base name
    (last sample wins); ``prefix`` is removed from matching names and
    non-matching names are kept verbatim."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        if not key:
            continue
        if "{" in key:
            key = key[: key.index("{")]
        if prefix and key.startswith(prefix):
            key = key[len(prefix):]
        try:
            out[key] = float(value)
        except ValueError:
            continue
    return out



def _prom_type(name: str, types: Optional[Dict[str, str]]) -> str:
    # precedence: caller-local types > the explicit process registry >
    # the legacy suffix heuristic (unregistered names only — the
    # metrics-hygiene lint keeps real surfaces off this fallback)
    if types and name in types:
        return types[name]
    if name in METRIC_TYPES:
        return METRIC_TYPES[name]
    if name.startswith("total_") or name.endswith("_total"):
        return "counter"
    return "gauge"


def _prom_value(v: float) -> str:
    # prometheus value spellings: NaN/+Inf/-Inf, integers without the
    # trailing .0 noise
    v = float(v)
    if v != v:
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v):
        return str(int(v))
    return str(v)


def _split_labels(key: str) -> Tuple[str, str]:
    """``'name{a="b"}'`` → ``("name", 'a="b"')``; bare names pass."""
    if "{" in key and key.endswith("}"):
        base, rest = key.split("{", 1)
        return base, rest[:-1]
    return key, ""


def render_prometheus(
    metrics: Dict[str, float],
    prefix: str = "",
    types: Optional[Dict[str, str]] = None,
    help_text: Optional[Dict[str, str]] = None,
    histograms: Optional[Dict[str, "Histogram"]] = None,
) -> str:
    """Render a flat metric dict in Prometheus text-exposition format
    (# HELP / # TYPE preamble per metric, sorted by name).

    ``histograms`` maps series keys to :class:`Histogram` instances; a
    key may carry a label set (``'queue_wait_seconds{sched_class="bulk"}'``)
    — the HELP/TYPE preamble is emitted once per base name and each
    series renders cumulative ``_bucket{...,le="..."}`` samples plus
    ``_sum``/``_count``."""
    lines: List[str] = []
    for name in sorted(metrics):
        full = f"{prefix}{name}"
        if help_text and name in help_text:
            lines.append(f"# HELP {full} {help_text[name]}")
        lines.append(f"# TYPE {full} {_prom_type(name, types)}")
        lines.append(f"{full} {_prom_value(metrics[name])}")
    if histograms:
        by_base: Dict[str, List[Tuple[str, Histogram]]] = {}
        for key in sorted(histograms):
            base, labels = _split_labels(key)
            by_base.setdefault(base, []).append((labels, histograms[key]))
        for base, series in by_base.items():
            full = f"{prefix}{base}"
            if help_text and base in help_text:
                lines.append(f"# HELP {full} {help_text[base]}")
            lines.append(f"# TYPE {full} histogram")
            for labels, hist in series:
                sep = f"{labels}," if labels else ""
                for le, cum in hist.cumulative():
                    le_s = "+Inf" if le == float("inf") else _prom_value(le)
                    lines.append(
                        f'{full}_bucket{{{sep}le="{le_s}"}} {cum}'
                    )
                suffix = f"{{{labels}}}" if labels else ""
                lines.append(
                    f"{full}_sum{suffix} {_prom_value(hist.sum)}"
                )
                lines.append(f"{full}_count{suffix} {hist.count}")
    return "\n".join(lines) + "\n"


def parse_prometheus_histograms(
    text: str, prefix: str = ""
) -> Dict[str, "Histogram"]:
    """Scrape-side inverse of the histogram rendering: reconstructs
    ``{series_key: Histogram}`` from ``_bucket``/``_sum``/``_count``
    samples. Series keys mirror the render input (base name plus any
    non-``le`` labels), with ``prefix`` stripped."""
    buckets: Dict[str, List[Tuple[float, float]]] = {}
    sums: Dict[str, float] = {}
    counts: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        if not key:
            continue
        try:
            val = float(value)
        except ValueError:
            continue
        base, labels = _split_labels(key)
        if prefix and base.startswith(prefix):
            base = base[len(prefix):]
        if base.endswith("_bucket"):
            le = None
            rest = []
            for part in labels.split(","):
                if part.startswith("le="):
                    raw = part[3:].strip('"')
                    le = float("inf") if raw == "+Inf" else float(raw)
                elif part:
                    rest.append(part)
            if le is None:
                continue
            series = base[: -len("_bucket")]
            if rest:
                series = f"{series}{{{','.join(rest)}}}"
            buckets.setdefault(series, []).append((le, val))
        elif base.endswith("_sum") or base.endswith("_count"):
            stem = base.rsplit("_", 1)[0]
            series = f"{stem}{{{labels}}}" if labels else stem
            (sums if base.endswith("_sum") else counts)[series] = val
    out: Dict[str, Histogram] = {}
    for series, pairs in buckets.items():
        out[series] = Histogram.from_cumulative(
            pairs, sums.get(series, 0.0), counts.get(series, 0.0)
        )
    return out
