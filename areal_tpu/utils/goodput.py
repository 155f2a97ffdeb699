"""Goodput attribution plane: wall-clock ledgers + recompile attribution.

The two biggest perf items on the roadmap (zero-pause weight updates,
cold-start elimination) are blocked on *measurement*, not mechanism —
before a cost can be eliminated it must be a first-class, continuously
exported signal. This module accounts for every second of wall time on
both sides of the system:

1. :class:`GoodputLedger` — segments an owning loop's wall time into
   named EXCLUSIVE buckets. The trainer step loop uses
   ``TRAINER_BUCKETS`` (``rollout_wait`` / ``weight_push`` / ``compile``
   / ``data_h2d`` / ``fwd_bwd`` / ``optim`` / ``checkpoint`` /
   ``other``); the inference engine loop uses ``ENGINE_BUCKETS``
   (``prefill`` / ``decode`` / ``spec_verify`` / ``weight_pause`` /
   ``compile`` / ``idle``). Whatever no bucket claims lands in the
   remainder bucket (``other`` / ``idle``), so per-bucket fractions sum
   to 1.0 of observed wall time BY CONSTRUCTION — nothing hides.
   ``bucket()`` contexts are reentrancy-safe per thread (the outermost
   wins; nested entries are no-ops), which lets every layer self-wrap
   without double counting when a caller already opened a bucket.

2. :class:`CompileTracker` — every XLA compilation is recorded with the
   dispatch that triggered it. A ``jax.monitoring`` listener (installed
   once per process) attributes each ``/jax/core/compile/*`` event to
   the thread's current :func:`dispatch_scope` (phase + shape
   signature, e.g. ``rows8|steps8|pps16``) and keeps it as one entry of
   a bounded log: which program (the event's ``fun_name``), which phase
   of making it (``trace`` | ``lower`` | ``compile`` | ``cache_load``),
   how long, and whether it ran inside another entry (a jitted function
   traced inside a program's trace), so that the seconds add up once.
   ``phase_seconds`` / ``programs`` / ``program_table`` read the log —
   set-up by program, measured where it happens. It appends one line per
   backend compile to a ``compile_events.jsonl`` stream — the exact
   input a shape-ladder AOT precompiler consumes — and feeds the
   ``shape_ladder_coverage`` gauge (compiled shapes / ladder size) that
   drives server readiness (``warming`` vs ``ready`` on ``/health``).

   A ledger constructed with a ``compile_tracker`` CARVES compile time
   out of whatever bucket it occurred in and credits it to the
   ``compile`` bucket: a prefill dispatch that spent 4 s compiling and
   40 ms running books 4 s of ``compile`` and 40 ms of ``prefill``.

The trainer side is wired through a process singleton
(:func:`trainer_ledger` / :func:`trainer_bucket`) because the step loop
spans many layers (workflow executor, SPMD engine, recover handler)
that should not all thread a ledger handle through their APIs.
"""

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from areal_tpu.utils import logging as logging_util
from areal_tpu.utils import tracing

logger = logging_util.getLogger("goodput")


def jax_version() -> str:
    """The running jax version, or "unknown" without a backend — one
    helper feeding BOTH the compile-events header and the ladder
    fingerprint (inference/precompile.py), so the two identity fields
    can never drift apart."""
    try:
        import jax

        return jax.__version__
    except Exception:  # pragma: no cover - jax is a baked-in dep
        return "unknown"

# trainer step loop: what the wall clock of one training process buys
TRAINER_BUCKETS = (
    "rollout_wait", "weight_push", "compile", "data_h2d", "fwd_bwd",
    "optim", "checkpoint", "other",
)
# inference engine loop: what a generation server's wall clock buys
ENGINE_BUCKETS = (
    "prefill", "decode", "spec_verify", "weight_pause", "compile", "idle",
)
# buckets counted as productive for the duty-cycle gauge
TRAINER_PRODUCTIVE = ("data_h2d", "fwd_bwd", "optim")
ENGINE_PRODUCTIVE = ("prefill", "decode", "spec_verify")

# the jax.monitoring duration events of making a program, and the kind
# each is logged as; the backend event is the one counted as "a compile
# happened", and is a ``cache_load`` when the persistent cache served it
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND_COMPILE_EVENT: "compile",
}
PHASE_KINDS = ("trace", "lower", "compile", "cache_load")
_BACKEND_KINDS = ("compile", "cache_load")
# program_table's columns of each kind: (count, seconds)
_TABLE_COLUMNS = {
    "trace": ("traces", "trace_s"), "lower": (None, "lower_s"),
    "compile": ("compiles", "compile_s"), "cache_load": ("loads", "load_s"),
}
# entries the log keeps: a trainer process of the benchmark's cells makes
# 2.8 thousand (dense) to 16.9 thousand (Kimi), one a jitted function
# traced, most of them nested (PERF.md section 5)
_LOG_ENTRIES = 65536
# persistent-compilation-cache outcome events (plain count events, not
# durations). On this jax a cache HIT still fires a backend_compile
# event for the retrieval, so the hit/miss event that precedes it on
# the same thread is what distinguishes a real XLA compile from a
# disk replay — the cold-vs-seeded diagnosis depends on the split.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


# --------------------------------------------------------------------------
# Compile attribution
# --------------------------------------------------------------------------
class _ScopeState(threading.local):
    """Per-thread dispatch context consumed by the monitoring listener."""

    def __init__(self):
        self.stack: List[Tuple["CompileTracker", str, str]] = []
        self.default: Optional[Tuple["CompileTracker", str]] = None
        # persistent-cache outcome of the compile currently in flight on
        # this thread ("hit" | "miss" | None); the cache event fires
        # just before its backend_compile event, which consumes it
        self.cache_pending: Optional[str] = None


_TLS = _ScopeState()
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _current_tracker() -> Optional[Tuple["CompileTracker", str, str]]:
    if _TLS.stack:
        return _TLS.stack[-1]
    if _TLS.default is not None:
        tracker, phase = _TLS.default
        return tracker, phase, ""
    return None


def _on_monitoring_event(event: str, duration: float, **kw) -> None:
    if event not in _EVENT_KINDS:
        return
    cur = _current_tracker()
    if cur is None:
        return
    tracker, phase, signature = cur
    cached = None
    if event == _BACKEND_COMPILE_EVENT:
        cached = _TLS.cache_pending == "hit"
        _TLS.cache_pending = None
    tracker._observe(
        phase, signature, float(duration), event, cached,
        fun_name=str(kw.get("fun_name", "")),
    )


def _on_count_event(event: str, **kw) -> None:
    """Plain (count) monitoring events: the persistent-compile-cache
    hit/miss outcome that classifies the backend compile that follows
    on the same thread."""
    if event == _CACHE_HIT_EVENT:
        kind = "hit"
    elif event == _CACHE_MISS_EVENT:
        kind = "miss"
    else:
        return
    _TLS.cache_pending = kind
    cur = _current_tracker()
    if cur is not None:
        cur[0]._observe_cache(kind)


def _install_listener() -> bool:
    """Register the process-wide jax.monitoring listeners (idempotent).
    Returns False when jax is unavailable — the tracker then only sees
    durations fed to it directly (unit tests, stub environments)."""
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return True
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover - jax is a baked-in dep
            return False
        monitoring.register_event_duration_secs_listener(
            _on_monitoring_event
        )
        # plain count events carry the compilation-cache outcome
        monitoring.register_event_listener(_on_count_event)
        _LISTENER_INSTALLED = True
        return True


class _DispatchScope:
    __slots__ = ("_entry",)

    def __init__(self, entry):
        self._entry = entry

    def __enter__(self):
        _TLS.stack.append(self._entry)
        return self

    def __exit__(self, *exc):
        _TLS.stack.pop()
        return False


def dispatch_scope(
    tracker: "CompileTracker", phase: str, signature: str = ""
) -> _DispatchScope:
    """Tag the current thread's dispatches: any XLA compile fired while
    the scope is open is attributed to ``(phase, signature)``."""
    return _DispatchScope((tracker, phase, signature))


def set_thread_tracker(
    tracker: Optional["CompileTracker"], phase: str = "untagged"
) -> None:
    """Fallback attribution for this thread: compiles fired OUTSIDE any
    dispatch_scope still land on ``tracker`` (signature empty) instead
    of vanishing. The engine loop thread sets this once at start."""
    _TLS.default = None if tracker is None else (tracker, phase)


def _program_name(fun_name: str) -> str:
    """The function's name for all of a program's events: the trace event
    carries it bare, the lower and backend events as the module's
    ``jit(<function>)``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class CompileEntry:
    """One compile-phase event of a tracker's log. ``t_end`` is
    ``time.monotonic()`` when the listener ran, so the event covered
    (``t_end - seconds``, ``t_end``); ``nested`` says that interval lies
    inside a later-ending entry's on the same thread (``parent``), whose
    ``seconds`` already hold these. ``self_seconds`` is ``seconds`` less
    the entries that ran directly inside this one: summed over ALL
    entries they count every second once, under the kind that spent it
    (an eager program compiled inside a trace is ``compile``, not
    ``trace``)."""

    __slots__ = (
        "t_end", "kind", "fun_name", "seconds", "self_seconds", "tid",
        "parent",
    )

    def __init__(self, t_end, kind, fun_name, seconds, tid):
        self.t_end, self.kind, self.fun_name = t_end, kind, fun_name
        self.seconds = self.self_seconds = seconds
        self.tid, self.parent = tid, None

    @property
    def nested(self) -> bool:
        return self.parent is not None

    @property
    def t_start(self) -> float:
        return self.t_end - self.seconds

    def root(self) -> "CompileEntry":
        e = self
        while e.parent is not None:
            e = e.parent
        return e


class CompileTracker:
    """Per-owner recompile ledger fed by the jax.monitoring listener.

    Keeps one :class:`CompileEntry` per compile-phase event in a bounded
    log (``phase_seconds``, ``programs``, ``program_table`` read it; the
    oldest entries fall out of the table and stay in the totals;
    ``dropped_total`` counts them: the table says so in a row, and a cut
    at a time the log no longer reaches raises), total compiles / compile
    seconds with nested entries counted once, the distinct ``(phase, signature)`` shapes
    compiled (the shape ladder actually paid for), per-thread compile
    seconds (the ledger carve-out input), persistent-compile-cache
    hit/miss counters, and optionally appends one JSONL line per backend
    compile to ``events_path`` — a stream that starts with a HEADER line
    (``fingerprint`` of the owner's shape ladder + jax version) so a
    later AOT replay can refuse a mismatched ladder, and that rotates to
    ``<path>.1`` once it exceeds ``max_events_bytes`` (the stream is
    otherwise unbounded append across restarts)."""

    def __init__(
        self,
        events_path: str = "",
        ladder_size: int = 0,
        time_fn=time.monotonic,
        fingerprint: str = "",
        max_events_bytes: int = 8_000_000,
    ):
        self.events_path = events_path
        # expected distinct (phase, signature) programs for a fully-warm
        # owner; 0 = unknown (coverage reports 0 and readiness falls
        # back to the compile-quiet rule alone)
        self.ladder_size = int(ladder_size)
        self.fingerprint = fingerprint
        self.max_events_bytes = int(max_events_bytes)
        self._time = time_fn
        self._lock = threading.Lock()
        self._events_lock = threading.Lock()
        self.compiles_total = 0
        # every entry's self_seconds by kind: each second once
        self._seconds = dict.fromkeys(PHASE_KINDS, 0.0)
        # backend compiles NOT served by the persistent cache: the true
        # XLA bill (a seeded engine's "compiles" are disk retrievals)
        self.uncached_compiles_total = 0
        self.cache_hits_total = 0
        self.cache_misses_total = 0
        # (phase, signature) -> {"count", "uncached"}
        self.signatures: Dict[Tuple[str, str], Dict[str, int]] = {}
        self.last_compile_t: Optional[float] = None
        self._thread_seconds: Dict[int, float] = {}
        self._log: "collections.deque[CompileEntry]" = collections.deque()
        # per thread, the entries no later one has enclosed yet, oldest
        # first: what the next event's interval is held against
        self._open: Dict[int, "collections.deque[CompileEntry]"] = {}
        self.dropped_total = 0  # entries the log no longer holds
        self._epoch_unix = time.time()
        self._epoch_mono = time.monotonic()
        _install_listener()
        if self.events_path:
            # write the header EAGERLY: its timestamp is the stream's
            # launch anchor (trace_report --coldstart measures port /
            # warming / ready leads against it), so it must mark owner
            # construction, not whenever the first compile happens. An
            # EXISTING stream whose header fingerprint doesn't match
            # this owner is rotated out first — appending new-config
            # compiles under an old header would make a later replay
            # trust (and drive) the wrong ladder.
            try:
                with self._events_lock:
                    fresh = (
                        not os.path.exists(self.events_path)
                        or os.path.getsize(self.events_path) == 0
                    )
                    if not fresh:
                        with open(self.events_path) as f:
                            try:
                                head = json.loads(f.readline())
                            except json.JSONDecodeError:
                                head = {}
                        if (
                            head.get("kind") != "header"
                            or head.get("fingerprint") != self.fingerprint
                        ):
                            os.replace(
                                self.events_path, self.events_path + ".1"
                            )
                            logger.info(
                                f"compile events {self.events_path}: "
                                f"prior stream has a different ladder "
                                f"fingerprint — rotated to .1"
                            )
                            fresh = True
                    if fresh:
                        with open(self.events_path, "a") as f:
                            self._write_header(f)
            except OSError as e:  # never kill the owner
                logger.warning(
                    f"compile events header write failed: {e}"
                )

    # -- ingestion -----------------------------------------------------
    def _observe(
        self,
        phase: str,
        signature: str,
        duration: float,
        event: str,
        cached: Optional[bool] = None,
        fun_name: str = "",
    ) -> None:
        tid = threading.get_ident()
        kind = _EVENT_KINDS[event]
        is_backend = event == _BACKEND_COMPILE_EVENT
        if is_backend and cached:
            kind = "cache_load"
        entry = CompileEntry(
            self._time(), kind, _program_name(fun_name), duration, tid
        )
        made = {"trace": 0.0, "lower": 0.0}  # this program's, for its line
        with self._lock:
            self.last_compile_t = entry.t_end
            open_ = self._open.setdefault(tid, collections.deque())
            # whatever began after this event did ran inside it: its
            # seconds are this entry's already
            inner_s = 0.0
            while open_ and open_[-1].t_start >= entry.t_start:
                inner = open_.pop()
                inner.parent = entry
                inner_s += inner.seconds
            # the two clocks (jax times with time.time()) may disagree by
            # microseconds: never below zero
            entry.self_seconds = own = max(0.0, duration - inner_s)
            if is_backend:
                # the same program's lowering and tracing ended just
                # before, in that order, where this process made them
                for e in itertools.islice(reversed(open_), 2):
                    if e.fun_name == entry.fun_name and e.kind in made:
                        made[e.kind] += e.seconds
            open_.append(entry)
            if len(self._log) >= _LOG_ENTRIES:
                self._drop_oldest()
            self._log.append(entry)
            self._seconds[kind] += own
            self._thread_seconds[tid] = (
                self._thread_seconds.get(tid, 0.0) + own
            )
            if is_backend:
                self.compiles_total += 1
                sig = self.signatures.setdefault(
                    (phase, signature), {"count": 0, "uncached": 0}
                )
                sig["count"] += 1
                if not cached:
                    self.uncached_compiles_total += 1
                    sig["uncached"] += 1
        if is_backend and self.events_path:
            self.append_event(
                {
                    "kind": "compile",
                    "phase": phase,
                    "signature": signature,
                    "fun_name": entry.fun_name,
                    "backend": kind,
                    "duration_s": round(duration, 6),
                    "trace_s": round(made["trace"], 6),
                    "lower_s": round(made["lower"], 6),
                    "cached": bool(cached),
                    "event": event,
                }
            )

    def _drop_oldest(self) -> None:
        old = self._log.popleft()
        self.dropped_total += 1
        open_ = self._open.get(old.tid)
        if open_ and open_[0] is old:  # nothing later can enclose it now
            open_.popleft()

    def _observe_cache(self, kind: str) -> None:
        with self._lock:
            if kind == "hit":
                self.cache_hits_total += 1
            else:
                self.cache_misses_total += 1

    def mark_compiled(self, phase: str, signature: str) -> None:
        """Record ``(phase, signature)`` as covered WITHOUT counting a
        compile: the AOT precompiler calls this per driven ladder rung
        so coverage reaches 1.0 even when the persistent cache already
        held every program (a seeded engine compiles nothing, but its
        ladder is just as warm)."""
        with self._lock:
            self.signatures.setdefault(
                (phase, signature), {"count": 0, "uncached": 0}
            )

    # -- events stream -------------------------------------------------
    def _write_header(self, f) -> None:
        f.write(
            json.dumps(
                {
                    "kind": "header",
                    "ts_unix": self._epoch_unix
                    + (time.monotonic() - self._epoch_mono),
                    "fingerprint": self.fingerprint,
                    "jax": jax_version(),
                    "ladder_size": self.ladder_size,
                }
            )
            + "\n"
        )

    def append_event(self, rec: Dict[str, Any]) -> None:
        """Append one JSONL record to the events stream (compile lines,
        server lifecycle marks). Creates the stream with its header
        line, and rotates to ``<path>.1`` past ``max_events_bytes`` —
        the stream must stay bounded across restarts. Never raises."""
        if not self.events_path:
            return
        rec.setdefault(
            "ts_unix",
            self._epoch_unix + (time.monotonic() - self._epoch_mono),
        )
        try:
            with self._events_lock:
                fresh = (
                    not os.path.exists(self.events_path)
                    or os.path.getsize(self.events_path) == 0
                )
                if (
                    not fresh
                    and self.max_events_bytes > 0
                    and os.path.getsize(self.events_path)
                    >= self.max_events_bytes
                ):
                    os.replace(self.events_path, self.events_path + ".1")
                    fresh = True
                with open(self.events_path, "a") as f:
                    if fresh:
                        self._write_header(f)
                    f.write(json.dumps(rec) + "\n")
        except OSError as e:  # attribution must never kill the owner
            logger.warning(
                f"compile event append to {self.events_path} failed: {e}"
            )

    # -- carve-out support ---------------------------------------------
    def thread_seconds(self) -> float:
        """Cumulative compile seconds observed on THIS thread, nested
        entries counted once (the ledger bucket carve-out reads the
        delta across its window)."""
        with self._lock:
            return self._thread_seconds.get(threading.get_ident(), 0.0)

    # -- the log's readers ---------------------------------------------
    @property
    def compile_seconds_total(self) -> float:
        with self._lock:
            return sum(self._seconds.values())

    def _after(self, until: Optional[float]) -> List[CompileEntry]:
        """The log's entries that ended after ``until``, newest first (the
        log is in the order of ending); the lock is held. LookupError
        where the log has dropped entries and no longer reaches back to
        ``until``: what ended after it can then not be told from what
        ended before."""
        out = []
        if until is not None:
            if self.dropped_total and self._log[0].t_end > until:
                raise LookupError(
                    f"the compile log dropped {self.dropped_total} entries "
                    f"and starts after the time asked for"
                )
            for e in reversed(self._log):
                if e.t_end <= until:
                    break
                out.append(e)
        return out

    def phase_seconds(
        self, until: Optional[float] = None
    ) -> Dict[str, float]:
        """Seconds by kind (``PHASE_KINDS``) over the events that ended
        by ``until`` (``time.monotonic()``; None: all): each entry's
        ``self_seconds``, so the four add up to the wall time spent and a
        second inside two entries is counted once, for the inner one.
        LookupError where the log no longer reaches back to ``until``."""
        with self._lock:
            out = dict(self._seconds)
            for e in self._after(until):
                out[e.kind] -= e.self_seconds
        return out

    def programs(self, until: Optional[float] = None) -> int:
        """Backend events by ``until``: programs compiled or loaded
        (LookupError as ``phase_seconds``)."""
        with self._lock:
            return self.compiles_total - sum(
                e.kind in _BACKEND_KINDS for e in self._after(until)
            )

    def program_table(
        self, until: Optional[float] = None
    ) -> List[Dict[str, Any]]:
        """Set-up by program, most expensive first: one row a
        ``fun_name`` over its un-nested entries (a program at several
        shapes is one row with its counts), and under ``nested`` the
        entries that ran inside those, by name and kind. Every second
        here is INCLUSIVE, as ``jax_log_compiles`` would print it: a
        nested entry's are part of its row's already, and of each
        other's where they nest deeper. ``traces`` counts events, and
        jax fires one, of microseconds, for a call that finds its jaxpr
        in the Python-side cache. Where the log has dropped entries
        the table is of the newest only, and says so in a last row
        ``(dropped)`` whose ``entries`` is their number."""
        rows: Dict[str, Dict[str, Any]] = {}

        def row(name):
            return rows.setdefault(name, {
                "fun_name": name, "traces": 0, "trace_s": 0.0,
                "lower_s": 0.0, "compiles": 0, "compile_s": 0.0,
                "loads": 0, "load_s": 0.0, "nested": {},
            })

        with self._lock:
            dropped = self.dropped_total
            for e in self._log:
                if until is not None and e.t_end > until:
                    continue
                if e.nested:
                    inner = row(e.root().fun_name)["nested"].setdefault(
                        (e.fun_name, e.kind),
                        {"fun_name": e.fun_name, "kind": e.kind,
                         "count": 0, "seconds": 0.0},
                    )
                    inner["count"] += 1
                    inner["seconds"] += e.seconds
                    continue
                r = row(e.fun_name)
                count, seconds = _TABLE_COLUMNS[e.kind]
                r[seconds] += e.seconds
                if count:
                    r[count] += 1
        out = []
        for r in rows.values():
            r["nested"] = sorted(
                r["nested"].values(), key=lambda n: -n["seconds"]
            )
            r["seconds"] = sum(r[col] for _, col in _TABLE_COLUMNS.values())
            out.append(r)
        out.sort(key=lambda r: -r["seconds"])
        if dropped:
            out.append(dict(
                row("(dropped)"), nested=[], seconds=0.0, entries=dropped
            ))
        return out

    # -- derived gauges ------------------------------------------------
    def compiled_shapes(self) -> int:
        with self._lock:
            return len(self.signatures)

    def coverage(self) -> float:
        """Compiled distinct shapes / ladder size, clamped to [0, 1].
        0 when the ladder size is unknown."""
        if self.ladder_size <= 0:
            return 0.0
        return min(1.0, self.compiled_shapes() / self.ladder_size)

    def mean_compile_s(self) -> float:
        with self._lock:
            if not self.compiles_total:
                return 0.0
            return sum(self._seconds.values()) / self.compiles_total

    def warmup_eta_s(self) -> float:
        """Estimated seconds of compilation left to full ladder
        coverage (remaining shapes x mean observed compile time)."""
        if self.ladder_size <= 0:
            return 0.0
        remaining = max(0, self.ladder_size - self.compiled_shapes())
        return round(remaining * self.mean_compile_s(), 3)

    def quiet_s(self, now: Optional[float] = None) -> float:
        """Seconds since the last observed compile (inf if none yet)."""
        with self._lock:
            last = self.last_compile_t
        if last is None:
            return float("inf")
        return (now if now is not None else self._time()) - last

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            secs = dict(self._seconds)
            out = {
                "compile_events_total": float(self.compiles_total),
                "compile_seconds_total": round(sum(secs.values()), 4),
                "compiled_shapes": float(len(self.signatures)),
                "shape_ladder_size": float(self.ladder_size),
                # cold vs seeded is diagnosable from /metrics alone:
                # a seeded engine shows hits ~= compiles and uncached ~= 0
                "compile_cache_hits_total": float(self.cache_hits_total),
                "compile_cache_misses_total": float(
                    self.cache_misses_total
                ),
                "compile_uncached_total": float(
                    self.uncached_compiles_total
                ),
            }
        out["shape_ladder_coverage"] = round(self.coverage(), 4)
        # where the seconds went: a seeded start pays trace, lower and
        # cache_load, a cold one backend on top
        for name, kind in (
            ("trace", "trace"), ("lower", "lower"), ("backend", "compile"),
            ("cache_load", "cache_load"),
        ):
            out[f"compile_{name}_seconds_total"] = round(secs[kind], 4)
        return out


# --------------------------------------------------------------------------
# Wall-clock ledger
# --------------------------------------------------------------------------
class _LedgerTLS(threading.local):
    depth = 0
    held = None  # the span of a poll stretch still open on this thread


class _BucketCtx:
    __slots__ = (
        "_ledger", "_name", "_poll", "_t0", "_c0", "_outer", "_span",
        "duration",
    )

    def __init__(self, ledger: "GoodputLedger", name: str, poll: bool):
        self._ledger = ledger
        self._name = name
        self._poll = poll
        # host seconds of this entry; stays 0 for a nested (no-op) one
        self.duration = 0.0

    def __enter__(self):
        led = self._ledger
        tls = led._tls
        self._outer = tls.depth == 0
        tls.depth += 1
        if self._outer:
            # the bucket IS a span of the one primitive: in the profiler's
            # trace whenever a profile runs, as areal:<role>.<bucket>. Never
            # in a SpanTracer's ring. A poll (an idle or paused loop enters
            # one every millisecond) opens its span once per stretch: it
            # stays open over the polls that follow and ends when the
            # thread enters a bucket of another name
            name = f"{led.role}.{self._name}"
            held = tls.held
            if held is not None and held.name != name:
                held.__exit__(None, None, None)
                held = tls.held = None
            self._span = None  # inside an open stretch: that one covers it
            if held is None:
                self._span = tracing.span(name)
                self._span.__enter__()
                if self._poll:
                    tls.held = self._span
            self._t0 = led._time()
            tr = led.compile_tracker
            self._c0 = tr.thread_seconds() if tr is not None else 0.0
        return self

    def __exit__(self, *exc):
        led = self._ledger
        led._tls.depth -= 1
        if self._outer:
            self.duration = dt = led._time() - self._t0
            dc = 0.0
            tr = led.compile_tracker
            if tr is not None and "compile" in led._acc:
                dc = max(0.0, min(dt, tr.thread_seconds() - self._c0))
            with led._lock:
                if dc:
                    led._acc["compile"] += dc
                led._acc[self._name] += dt - dc
            if self._span is not None and self._span is not led._tls.held:
                self._span.__exit__(*exc)
        return False


class GoodputLedger:
    """Exclusive wall-time bucket accounting for one owning loop.

    ``bucket(name)`` measures its body into ``name`` (compile time
    observed on the same thread is carved out into ``compile`` when a
    tracker is attached). Reentrant entries on the same thread are
    no-ops — the outermost bucket wins — so layered code can self-wrap
    freely. That outermost entry is also one ``tracing.span``
    (``<role>.<bucket>``), so the buckets are intervals in the profiler's
    trace, not only sums. ``fractions()`` divides by observed wall time
    since the ledger started, with the remainder bucket absorbing
    unclaimed time: the fractions sum to 1.0 by construction."""

    def __init__(
        self,
        role: str,
        buckets: Tuple[str, ...],
        remainder: str = "other",
        productive: Tuple[str, ...] = (),
        jsonl_path: str = "",
        compile_tracker: Optional[CompileTracker] = None,
        time_fn=time.monotonic,
    ):
        if remainder not in buckets:
            raise ValueError(
                f"remainder bucket {remainder!r} must be one of {buckets}"
            )
        self.role = role
        self.buckets = tuple(buckets)
        self.remainder = remainder
        self.productive = tuple(productive)
        self.jsonl_path = jsonl_path
        self.compile_tracker = compile_tracker
        self._time = time_fn
        self._lock = threading.Lock()
        self._tls = _LedgerTLS()
        self._t_start = time_fn()
        self._acc: Dict[str, float] = {b: 0.0 for b in buckets}
        self._tokens = 0
        self._epoch_unix = time.time()

    # -- recording -----------------------------------------------------
    def bucket(self, name: str, poll: bool = False) -> _BucketCtx:
        """``poll``: this entry is one tick of a loop's top-level wait
        (sleep, look again). Its seconds are booked per entry like any
        other; its span is one per stretch of such entries (``end_poll``
        closes a stretch that nothing followed)."""
        if name not in self._acc:
            raise KeyError(f"unknown goodput bucket {name!r}")
        return _BucketCtx(self, name, poll)

    def end_poll(self) -> None:
        held, self._tls.held = self._tls.held, None
        if held is not None:
            held.__exit__(None, None, None)

    def add(self, name: str, seconds: float) -> None:
        """Direct credit (for windows measured elsewhere)."""
        with self._lock:
            self._acc[name] += max(0.0, float(seconds))

    def note_tokens(self, n: int) -> None:
        """Count delivered tokens toward effective tok/s."""
        with self._lock:
            self._tokens += int(n)

    # -- derived views -------------------------------------------------
    def observed_wall_s(self) -> float:
        return max(1e-9, self._time() - self._t_start)

    def seconds(self) -> Dict[str, float]:
        """Per-bucket seconds INCLUDING the remainder: unclaimed wall
        time goes to the remainder bucket (clamped at 0 if concurrent
        threads over-account a window)."""
        wall = self.observed_wall_s()
        with self._lock:
            acc = dict(self._acc)
        claimed = sum(v for b, v in acc.items() if b != self.remainder)
        acc[self.remainder] += max(0.0, wall - claimed - acc[self.remainder])
        return acc

    def fractions(self) -> Dict[str, float]:
        wall = self.observed_wall_s()
        return {b: v / wall for b, v in self.seconds().items()}

    def duty_cycle(self) -> float:
        fr = self.fractions()
        return sum(fr.get(b, 0.0) for b in self.productive)

    def effective_tokens_per_sec(self) -> float:
        with self._lock:
            tokens = self._tokens
        return tokens / self.observed_wall_s()

    def metrics(self, prefix: str = "goodput_") -> Dict[str, float]:
        out = {
            f"{prefix}{b}_frac": round(v, 4)
            for b, v in self.fractions().items()
        }
        out[f"{prefix}duty_cycle"] = round(self.duty_cycle(), 4)
        out[f"{prefix}effective_tokens_per_sec"] = round(
            self.effective_tokens_per_sec(), 2
        )
        out[f"{prefix}wall_s"] = round(self.observed_wall_s(), 3)
        return out

    def snapshot(self) -> Dict[str, Any]:
        """One self-describing record (the JSONL stream line format)."""
        secs = self.seconds()
        wall = self.observed_wall_s()
        with self._lock:
            tokens = self._tokens
        return {
            "kind": "goodput",
            "role": self.role,
            "ts_unix": round(
                self._epoch_unix + (self._time() - self._t_start), 3
            ),
            "wall_s": round(wall, 3),
            "seconds": {b: round(v, 4) for b, v in secs.items()},
            "fractions": {b: round(v / wall, 4) for b, v in secs.items()},
            "duty_cycle": round(self.duty_cycle(), 4),
            "tokens": tokens,
            "effective_tokens_per_sec": round(tokens / wall, 2),
        }

    def export_jsonl(self, path: Optional[str] = None) -> None:
        path = path or self.jsonl_path
        if not path:
            return
        try:
            with open(path, "a") as f:
                f.write(json.dumps(self.snapshot()) + "\n")
        except OSError as e:  # the ledger must never kill its owner
            logger.warning(f"goodput append to {path} failed: {e}")


# --------------------------------------------------------------------------
# Trainer-side process singleton
# --------------------------------------------------------------------------
# RLock: trainer_ledger() constructs with trainer_tracker() under the
# same guard
_TRAINER_LOCK = threading.RLock()
_TRAINER: Optional[GoodputLedger] = None
_TRAINER_TRACKER: Optional[CompileTracker] = None


def trainer_tracker() -> CompileTracker:
    global _TRAINER_TRACKER
    with _TRAINER_LOCK:
        if _TRAINER_TRACKER is None:
            _TRAINER_TRACKER = CompileTracker()
        return _TRAINER_TRACKER


def trainer_ledger() -> GoodputLedger:
    """The process's trainer-side ledger (created on first use; the
    observation window starts then). Layers wrap their own work in
    :func:`trainer_bucket` — reentrancy makes nesting safe — and the
    step-loop owner exports per-step snapshots."""
    global _TRAINER
    with _TRAINER_LOCK:
        if _TRAINER is None:
            _TRAINER = GoodputLedger(
                "trainer", TRAINER_BUCKETS, remainder="other",
                productive=TRAINER_PRODUCTIVE,
                compile_tracker=trainer_tracker(),
            )
        return _TRAINER


def trainer_bucket(name: str) -> _BucketCtx:
    return trainer_ledger().bucket(name)


def configure_trainer(
    jsonl_path: str = "", compile_events_path: str = ""
) -> GoodputLedger:
    """Attach export paths to the trainer singleton (idempotent)."""
    led = trainer_ledger()
    if jsonl_path:
        led.jsonl_path = jsonl_path
    if compile_events_path:
        trainer_tracker().events_path = compile_events_path
    return led


def reset_trainer_ledger() -> None:
    """Drop the singleton (tests; a fresh window starts on next use)."""
    global _TRAINER, _TRAINER_TRACKER
    with _TRAINER_LOCK:
        _TRAINER = None
        _TRAINER_TRACKER = None
