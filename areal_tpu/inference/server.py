"""HTTP server shell around GenerationEngine.

Role of the SGLang HTTP server the reference talks to (endpoints mirrored
from areal/engine/sglang_remote.py + realhf/system/gserver_manager.py usage):
``/generate``, ``/health``, ``/pause_generation``, ``/continue_generation``,
``/update_weights_from_disk``, ``/metrics``, ``/get_model_info``.

Stdlib ThreadingHTTPServer (fastapi is intentionally not a dependency): one
thread per in-flight request, each blocking on its engine Future; the device
work all happens on the engine's single loop thread.

Observability endpoints: ``GET /metrics`` serves the engine gauges and
counters in Prometheus text-exposition format; ``GET /trace`` DRAINS the
engine's span buffer as Chrome trace-event JSON (``?format=jsonl`` for the
line format `tools/trace_report.py` consumes). ``/generate`` honors the
``X-Areal-Trace`` / ``X-Areal-Rid`` trace-context headers: the incoming
trace id is bound onto this server's spans so a rollout's client, router,
and server(s) stitch into one timeline (utils/telemetry.py).
``POST /profile?steps=N`` arms an on-demand jax.profiler capture of the
next N busy engine-loop iterations (gated by ``--enable-profile`` on the
CLI path, exactly like ``POST /chaos``).

Resilience plane: ``POST /drain`` puts the server in drain mode — new
``/generate`` calls get 503, in-flight requests run to completion, and
the name_resolve registration is removed once the engine is empty (so
routers/clients watching membership see the server leave). ``/health``
reports ``{"status": "draining"}`` during the window, which
`inference/fleet.FleetMonitor` classifies as out-of-rotation without
opening a circuit. ``POST /chaos`` installs chaos rules at runtime
(``{"spec": "..."}``, utils/chaos.py grammar; ``{}`` disables) and the
handler honors server-side rules on every request — connection drops,
injected 500s, latency spikes, and hard kills, all deterministic.
"""

import argparse
import base64
import json
import os
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from areal_tpu.api.cli_args import JaxGenConfig
from areal_tpu.inference.engine import (
    AdmissionRejectedError,
    GenerationEngine,
)
from areal_tpu.inference.policies import UnknownPolicyError
from areal_tpu.utils import chaos
from areal_tpu.utils import logging as logging_util, names, network
from areal_tpu.utils import name_resolve
from areal_tpu.utils.tracing import (
    RID_HEADER,
    TRACE_HEADER,
    register_metric_types,
    render_prometheus,
    trace_response,
)

logger = logging_util.getLogger("GenServer")


class ServerControl:
    """Server-shell state that is not the engine's: drain mode + the
    name_resolve registration to tear down on exit."""

    def __init__(self, engine: GenerationEngine):
        self.engine = engine
        self.draining = threading.Event()
        self.registration_key: Optional[str] = None
        self._drain_thread: Optional[threading.Thread] = None

    def deregister(self) -> None:
        key, self.registration_key = self.registration_key, None
        if key is None:
            return
        try:
            name_resolve.delete(key)
            logger.info(f"deregistered {key}")
        except Exception as e:
            logger.warning(f"deregister failed: {e}")

    def start_drain(self) -> int:
        """Enter drain mode; returns the in-flight count at entry. A
        watcher thread deregisters once the engine is empty."""
        self.draining.set()
        m = self.engine.metrics()
        in_flight = int(m["running_requests"] + m["queued_requests"])
        if self._drain_thread is None or not self._drain_thread.is_alive():
            self._drain_thread = threading.Thread(
                target=self._watch_drain, daemon=True
            )
            self._drain_thread.start()
        return in_flight

    def _watch_drain(self) -> None:
        while True:
            m = self.engine.metrics()
            if m["running_requests"] + m["queued_requests"] <= 0:
                break
            time.sleep(0.2)
        self.deregister()
        logger.info("drain complete: engine empty, registration removed")

_METRIC_HELP = {
    "running_requests": "requests currently holding a decode slot",
    "queued_requests": "requests admitted but not yet running",
    "free_slots": "decode slots currently unoccupied",
    "free_pages": "KV pool pages currently unallocated",
    "kv_page_utilization": "fraction of the paged KV pool in use",
    "registry_entries": "prefix-cache entries currently parked",
    "decode_tokens_per_sec": "EWMA decode throughput",
    "prefill_tokens_per_sec": "EWMA prefill throughput",
    "decode_rows_dispatched": "rows the last decode chunk dispatched",
    "decode_rows_active": "rows carrying live requests in the last chunk",
    "decode_occupancy": "lifetime active/dispatched decode-row ratio",
    "total_decode_chunks": "decode chunks dispatched",
    "total_loop_iterations": "iterations of the engine's loop thread",
    "total_loop_admit_s": "loop-thread host seconds inside admission",
    "total_loop_decode_dispatch_s": (
        "loop-thread host seconds walking pages and dispatching chunks"
    ),
    "total_loop_decode_fetch_s": (
        "loop-thread host seconds waiting for a chunk's results"
    ),
    "total_loop_decode_process_s": (
        "loop-thread host seconds booking a chunk's results"
    ),
    "total_loop_idle_s": "loop-thread host seconds idling",
    "total_rows_dispatched": "decode rows dispatched (lifetime)",
    "total_rows_active": "decode rows that carried live requests",
    "total_generated_tokens": "completion tokens emitted",
    "total_prompt_tokens": "prompt tokens admitted",
    "total_cached_prompt_tokens": "prompt tokens served from cached KV",
    "total_requests": "requests admitted to a decode slot",
    "total_aborted": "requests aborted (pause windows)",
    "total_preemptions": "requests preempted under pool pressure",
    "model_version": "weight version currently being served",
    "paused": "1 while generation is paused for a weight update",
    # zero-pause weight plane (r13): streamed double-buffered updates
    "weight_staging_bytes": (
        "bytes currently staged in the shadow weight buffer (a stuck "
        "nonzero value means an abandoned stream awaiting its TTL)"
    ),
    "weight_staging_aborts_total": (
        "weight stagings dropped (TTL expiry, re-keyed retry, or a "
        "superseding full update)"
    ),
    "weight_pinned_requests": (
        "in-flight requests pinned to a pre-flip weight version"
    ),
    "weight_buffer_versions": (
        "old weight buffers kept alive for pinned requests"
    ),
    "weight_flips_total": "streamed weight flips applied (no pause)",
    # goodput attribution plane (r11): exclusive wall-time buckets —
    # fractions sum to 1.0 of observed wall so nothing hides
    "goodput_prefill_frac": "fraction of wall time in prefill dispatches",
    "goodput_decode_frac": "fraction of wall time in decode dispatches",
    "goodput_spec_verify_frac": (
        "fraction of wall time in speculative verify dispatches"
    ),
    "goodput_weight_pause_frac": (
        "fraction of wall time paused for weight updates"
    ),
    "goodput_compile_frac": "fraction of wall time in XLA compilation",
    "goodput_idle_frac": "fraction of wall time with no work",
    "goodput_duty_cycle": (
        "productive fraction of wall time (prefill + decode + verify)"
    ),
    "goodput_effective_tokens_per_sec": (
        "delivered tokens over total observed wall time"
    ),
    "goodput_wall_s": "observed wall seconds since the ledger started",
    # recompile attribution (r11) + cold-start elimination (r14)
    "compile_events_total": "XLA backend compilations observed",
    "compile_seconds_total": (
        "wall seconds spent making programs: the four phases below, a "
        "function traced inside another's trace counted once"
    ),
    "compile_trace_seconds_total": (
        "seconds of Python tracing to jaxprs (paid seeded as cold)"
    ),
    "compile_lower_seconds_total": (
        "seconds of lowering jaxprs to MLIR modules (paid seeded as cold)"
    ),
    "compile_backend_seconds_total": (
        "seconds of backend compilation the persistent cache did not "
        "serve (about 0 on a seeded start)"
    ),
    "compile_cache_load_seconds_total": (
        "seconds of backend events the persistent cache served: "
        "reading, deserialising and loading executables"
    ),
    "compiled_shapes": "distinct (phase, shape signature) programs compiled",
    "shape_ladder_size": (
        "exact enumerated programs for a fully-warm engine"
    ),
    "shape_ladder_coverage": "compiled shapes / ladder size (0..1)",
    "server_ready": "1 once warm (ladder covered or compile-quiet)",
    "compile_cache_hits_total": (
        "backend compiles served from the persistent XLA cache (a "
        "seeded engine's warmup is disk retrieval, not XLA)"
    ),
    "compile_cache_misses_total": (
        "backend compiles the persistent XLA cache could not serve"
    ),
    "compile_uncached_total": (
        "backend compiles that actually ran XLA (cache miss or cache "
        "disabled) — the true cold-start bill"
    ),
    # native latency histograms (per sched class)
    "queue_wait_seconds": (
        "submit-to-prefill wait per scheduling class (histogram)"
    ),
    "ttft_seconds": "submit-to-first-token latency per class (histogram)",
    "request_latency_seconds": (
        "submit-to-finish latency per class (histogram)"
    ),
    # speculative decoding (present only when spec is configured)
    "spec_enabled": "1 while speculation is active (0 = auto-disabled)",
    "spec_accept_rate": "lifetime accepted/drafted speculative tokens",
    "spec_accept_rate_ewma": "recent accept-rate EWMA (the gate's signal)",
    "spec_draft_tokens_total": "draft tokens proposed to verify dispatches",
    "spec_accepted_tokens_total": "draft tokens accepted by the model",
    "spec_chunks_total": "multi-token verify dispatches run",
    # prefix cache (radix tree over the paged pool, r9)
    "prefix_cache_hit_rate": (
        "fraction of prompt tokens served from cached KV (sibling dedup "
        "+ radix claims)"
    ),
    "prefix_cached_tokens_total": "prompt tokens served from cached KV",
    "prefix_claim_hit_rate": "fraction of prefix-cache claims that matched",
    "prefix_cache_nodes": "radix-tree nodes (flat mode: parked entries)",
    "prefix_cache_pages": "pool pages the prefix cache holds references on",
    "prefix_cow_copies_total": (
        "copy-on-write page copies for mid-page prefix claims"
    ),
    "prefix_evicted_pages_total": (
        "prefix-cache pages evicted under allocation pressure"
    ),
    # SLO traffic plane (r10)
    "requests_shed_total": (
        "submissions rejected by the bounded admission queue "
        "(429 + Retry-After)"
    ),
    "deadline_preemptions_total": (
        "bulk requests preempted so a deadline-carrying interactive "
        "request could run"
    ),
    "deadline_misses_total": (
        "requests that completed after their soft deadline"
    ),
    "sched_class_interactive_running": (
        "interactive requests holding a decode slot"
    ),
    "sched_class_bulk_running": "bulk requests holding a decode slot",
    "sched_class_interactive_queued": (
        "interactive requests admitted but not yet running"
    ),
    "sched_class_bulk_queued": (
        "bulk requests admitted but not yet running"
    ),
    "sched_class_interactive_submitted_total": (
        "interactive submissions accepted by admission"
    ),
    "sched_class_bulk_submitted_total": (
        "bulk submissions accepted by admission"
    ),
    "trace_spans": "spans currently buffered (drained by GET /trace)",
    "tracing_dropped_spans_total": (
        "spans lost to ring-buffer overflow (the trace is truncated)"
    ),
    # chunked prefill (r15) — present only when chunking resolved on
    "prefill_chunks_total": (
        "chunk-capped prefill dispatches (each commits a page-aligned "
        "prefix into the prefix cache and resumes next wave)"
    ),
    "prefill_chunk_preemptions_total": (
        "bulk prefill chunks deferred at a chunk boundary for a "
        "deadline-pressed interactive request"
    ),
    "ttft_bounded": (
        "1 while every admission dispatch so far stayed within ~one "
        "chunk of prefill (a stall-escape admission under cache "
        "thrash zeroes it — the TTFT bound is measured, not assumed)"
    ),
    # hierarchical KV tiers (r16) — present only with --kv-spill
    "kv_tier_host_pages": "KV pages currently parked in the host tier",
    "kv_tier_host_bytes": "bytes the host tier currently holds",
    "kv_tier_host_capacity_bytes": "configured host-tier byte budget",
    "kv_tier_pending_pages": (
        "promoted pages awaiting their batched device scatter (nonzero "
        "only mid-admission; stuck nonzero means a missed flush)"
    ),
    "kv_tier_spilled_pages_total": (
        "pages demoted device→host instead of dropped at eviction"
    ),
    "kv_tier_spilled_bytes_total": "bytes moved device→host by demotion",
    "kv_tier_promoted_pages_total": (
        "spilled pages promoted host→device by claim-time prefetch"
    ),
    "kv_tier_promoted_bytes_total": "bytes moved host→device by promotion",
    "kv_tier_dropped_pages_total": (
        "host-tier pages discarded by the LRU byte budget (no disk tier)"
    ),
    "kv_tier_dropped_bytes_total": "bytes discarded by the host-tier LRU",
    "kv_tier_host_claim_hits_total": (
        "prefix claims that promoted at least one spilled page"
    ),
    "kv_tier_host_claim_hit_rate": (
        "fraction of prefix claims served (partly) from the host tier"
    ),
    "kv_tier_host_cached_tokens_total": (
        "claimed prompt tokens whose KV came back from the host tier"
    ),
    "kv_tier_disk_pages": "KV pages currently in the disk tier",
    "kv_tier_disk_bytes": "bytes the disk tier currently holds",
    "kv_tier_disk_spilled_pages_total": (
        "host-tier LRU overflow pages written to the disk tier"
    ),
    "kv_tier_disk_loaded_pages_total": (
        "pages read back from the disk tier (promotion or export)"
    ),
    # cross-server prefix shipping (r16) — present only with --kv-ship
    "kv_ship_exports_total": "prefix exports served to peer servers",
    "kv_ship_imports_total": "prefix imports accepted from peer servers",
    "kv_ship_pages_out_total": "KV pages shipped out via /kv_export",
    "kv_ship_pages_in_total": "KV pages imported into the local pool",
    "kv_ship_failures_total": (
        "shipping attempts dropped (version/geometry mismatch or an "
        "unreachable peer) — shipping soft-fails to a plain re-prefill"
    ),
    # multi-policy serving plane (r19) — present only once a named
    # policy is pushed (single-policy mode is a strict no-op)
    "policy_lines": "named policy lines currently registered",
    "policy_buffers_resident": "policy weight buffers resident in HBM",
    "policy_buffers_host": (
        "cold policy weight buffers demoted to host RAM by the LRU "
        "evictor (reloaded on next request)"
    ),
    "policy_pinned_requests": (
        "in-flight requests pinned to a named policy buffer"
    ),
    "policy_pushes_total": "weight pushes onto named policy lines",
    "policy_promotes_total": "canary→stable promotions applied",
    "policy_demotions_total": (
        "policy buffers demoted HBM→host under residency pressure"
    ),
    "policy_reloads_total": (
        "host-demoted policy buffers reloaded to HBM on demand"
    ),
    "policy_staging_bytes": (
        "bytes staged in per-policy shadow buffers (chunked pushes)"
    ),
    "policy_cache_namespaces": (
        "live per-(policy, version) KV cache namespaces"
    ),
    # per-policy labeled families (hand-rendered with {policy=...}
    # labels in the /metrics assembly below, router-style)
    "policy_stable_version": "stable weight version of a policy line",
    "policy_canary_version": (
        "canary weight version of a policy line (-1 = no canary)"
    ),
    "policy_canary_fraction": (
        "fraction of a line's traffic routed to its canary version"
    ),
    "policy_requests_total": "requests served per policy line",
    "policy_tokens_total": "completion tokens emitted per policy line",
}

# explicit metric-type registry for the engine surface: every name the
# engine emits declares its Prometheus TYPE here (registered globally so
# render_prometheus never falls back to the name-suffix heuristic — the
# metrics-hygiene lint enforces full coverage)
_ENGINE_COUNTERS = (
    "total_decode_chunks", "total_rows_dispatched", "total_rows_active",
    "total_loop_iterations", "total_loop_admit_s",
    "total_loop_decode_dispatch_s", "total_loop_decode_fetch_s",
    "total_loop_decode_process_s", "total_loop_idle_s",
    "total_generated_tokens", "total_prompt_tokens",
    "total_cached_prompt_tokens", "total_requests", "total_aborted",
    "total_preemptions", "prefix_cached_tokens_total",
    "prefix_cow_copies_total", "prefix_evicted_pages_total",
    "requests_shed_total", "deadline_preemptions_total",
    "deadline_misses_total", "tracing_dropped_spans_total",
    "sched_class_interactive_submitted_total",
    "sched_class_bulk_submitted_total",
    "spec_chunks_total", "spec_draft_tokens_total",
    "spec_accepted_tokens_total",
    "compile_events_total", "compile_seconds_total",
    "compile_trace_seconds_total", "compile_lower_seconds_total",
    "compile_backend_seconds_total", "compile_cache_load_seconds_total",
    "compile_cache_hits_total", "compile_cache_misses_total",
    "compile_uncached_total",
    "weight_staging_aborts_total", "weight_flips_total",
    "prefill_chunks_total", "prefill_chunk_preemptions_total",
    "kv_tier_spilled_pages_total", "kv_tier_spilled_bytes_total",
    "kv_tier_promoted_pages_total", "kv_tier_promoted_bytes_total",
    "kv_tier_dropped_pages_total", "kv_tier_dropped_bytes_total",
    "kv_tier_host_claim_hits_total", "kv_tier_host_cached_tokens_total",
    "kv_tier_disk_spilled_pages_total", "kv_tier_disk_loaded_pages_total",
    "kv_ship_exports_total", "kv_ship_imports_total",
    "kv_ship_pages_out_total", "kv_ship_pages_in_total",
    "kv_ship_failures_total",
    "policy_pushes_total", "policy_promotes_total",
    "policy_demotions_total", "policy_reloads_total",
    "policy_requests_total", "policy_tokens_total",
)
_ENGINE_HISTOGRAMS = (
    "queue_wait_seconds", "ttft_seconds", "request_latency_seconds",
)
_ENGINE_GAUGES = (
    "running_requests", "queued_requests", "free_slots", "free_pages",
    "kv_page_utilization", "registry_entries", "decode_tokens_per_sec",
    "prefill_tokens_per_sec", "decode_rows_dispatched",
    "decode_rows_active", "decode_occupancy", "prefix_cache_hit_rate",
    "prefix_claim_hit_rate", "prefix_cache_nodes", "prefix_cache_pages",
    "model_version", "paused", "trace_spans",
    "weight_staging_bytes", "weight_pinned_requests",
    "weight_buffer_versions",
    "sched_class_interactive_running", "sched_class_bulk_running",
    "sched_class_interactive_queued", "sched_class_bulk_queued",
    "spec_enabled", "spec_accept_rate", "spec_accept_rate_ewma",
    "goodput_prefill_frac", "goodput_decode_frac",
    "goodput_spec_verify_frac", "goodput_weight_pause_frac",
    "goodput_compile_frac", "goodput_idle_frac", "goodput_duty_cycle",
    "goodput_effective_tokens_per_sec", "goodput_wall_s",
    "compiled_shapes", "shape_ladder_size", "shape_ladder_coverage",
    "server_ready", "ttft_bounded",
    "kv_tier_host_pages", "kv_tier_host_bytes",
    "kv_tier_host_capacity_bytes", "kv_tier_pending_pages",
    "kv_tier_host_claim_hit_rate", "kv_tier_disk_pages",
    "kv_tier_disk_bytes",
    "policy_lines", "policy_buffers_resident", "policy_buffers_host",
    "policy_pinned_requests", "policy_staging_bytes",
    "policy_cache_namespaces", "policy_stable_version",
    "policy_canary_version", "policy_canary_fraction",
)
_METRIC_TYPES = {
    **{n: "counter" for n in _ENGINE_COUNTERS},
    **{n: "gauge" for n in _ENGINE_GAUGES},
    **{n: "histogram" for n in _ENGINE_HISTOGRAMS},
}
register_metric_types(_METRIC_TYPES)


class _Handler(BaseHTTPRequestHandler):
    engine: GenerationEngine = None  # set by serve()
    control: ServerControl = None  # set by serve()
    # runtime POST /chaos gate: the CLI path (production launchers)
    # closes it unless --enable-chaos; the embedded serve() path (tests,
    # bench harnesses) leaves it open. An open /chaos is a remote kill
    # switch — it must be an operator's opt-in, never a default.
    chaos_endpoint: bool = True
    # same gating story for POST /profile: an open profiler endpoint
    # lets anyone stall the engine loop under jax.profiler overhead, so
    # the CLI path requires --enable-profile
    profile_endpoint: bool = True
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # quiet default access logs
        pass

    def _apply_chaos(self) -> bool:
        """Honor server-side chaos rules for this request (shared
        dispatch, utils/chaos.py — one copy of the drop/kill semantics
        across generation servers, env workers, and verifiers). Returns
        True when a response was already produced (caller must return)."""
        return chaos.apply_server_chaos(self, self._send_json)

    def _send_json(self, obj, code: int = 200, headers=None):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_json(self) -> dict:
        length = int(self.headers.get("Content-Length", 0))
        if length == 0:
            return {}
        return json.loads(self.rfile.read(length))

    # --- cross-server prefix shipping (r16) ---
    @staticmethod
    def _kv_export_body(eng, tokens) -> dict:
        """JSON form of an engine prefix export: canonical-layout K/V
        pages ride base64-encoded raw bytes + (shape, dtype), which is
        layout-independent — the importer re-packs into its own pool."""
        out = eng.export_prefix(tokens)
        body = {
            k: out[k]
            for k in (
                "pages", "tokens_matched", "page_size", "model_version",
            )
        }
        if out.get("pages"):
            k, v = out["k"], out["v"]
            body.update(
                dtype=out["dtype"],
                shape=list(k.shape),
                k=base64.b64encode(
                    np.ascontiguousarray(k).tobytes()
                ).decode(),
                v=base64.b64encode(
                    np.ascontiguousarray(v).tobytes()
                ).decode(),
            )
        return body

    @staticmethod
    def _kv_import_body(eng, payload) -> int:
        from areal_tpu.inference import kv_tiers

        shape = tuple(int(s) for s in payload["shape"])
        dt = kv_tiers.resolve_np_dtype(payload["dtype"])
        k = np.frombuffer(
            base64.b64decode(payload["k"]), dtype=dt
        ).reshape(shape)
        v = np.frombuffer(
            base64.b64decode(payload["v"]), dtype=dt
        ).reshape(shape)
        return eng.import_prefix(
            [int(t) for t in payload["tokens"]], k, v,
            src_version=payload.get("model_version"),
        )

    def _ship_prefix(self, eng, peer: str, payload: dict) -> None:
        """Best-effort prefix fetch from the session's previous owner
        (the router's kv_ship_from hint): ask the peer to export the
        committed prefix of this prompt, import it locally, and let the
        admission claim pick it up. Every failure mode degrades to a
        plain re-prefill — shipping must never fail a request."""
        tokens = payload.get("input_ids") or []
        bs = int(eng.config.page_size)
        if len(tokens) < bs:
            return  # nothing committed could match a sub-page prompt
        base = peer if "://" in peer else f"http://{peer}"
        try:
            req = urllib.request.Request(
                f"{base}/kv_export",
                data=json.dumps(
                    {"tokens": [int(t) for t in tokens]}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10.0) as r:
                out = json.loads(r.read())
            if not out.get("pages"):
                return
            out["tokens"] = tokens[: int(out["tokens_matched"])]
            imported = self._kv_import_body(eng, out)
            logger.info(
                f"kv_ship: imported {imported} prefix tokens "
                f"({out['pages']} pages) from {peer}"
            )
        except Exception as e:
            # metric-only failure: the request re-prefills locally
            eng.kv_ship_failures_total += 1
            logger.warning(f"kv_ship fetch from {peer} failed: {e}")

    def _send_text(self, body: bytes, content_type: str):
        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        eng = self.engine
        if self._apply_chaos():
            return
        url = urllib.parse.urlparse(self.path)
        if url.path == "/health":
            draining = (
                self.control is not None
                and self.control.draining.is_set()
            )
            body = {"status": "draining" if draining else "ok"}
            # readiness (r11): a cold engine reports "warming" with its
            # shape-ladder coverage + warmup ETA until the compile storm
            # quiets — FleetMonitor classifies WARMING out of rotation,
            # the autoscaler times cold→serving from it (drain wins:
            # a draining server is leaving regardless of warmth)
            if hasattr(eng, "readiness"):
                try:
                    rd = eng.readiness()
                    body["ladder_coverage"] = rd["ladder_coverage"]
                    if rd["state"] == "warming":
                        body["warmup_eta_s"] = rd["warmup_eta_s"]
                        if not draining:
                            body["status"] = "warming"
                except Exception:
                    pass
            try:
                # load view for the router map and the autoscaler:
                # running vs queued SEPARATELY — a busy decode and a
                # queue backlog demand different reactions (more
                # servers fixes a backlog; it does nothing for one
                # long decode). Stub engines without metrics() still
                # answer a bare status.
                m = eng.metrics()
                body["running_requests"] = int(m["running_requests"])
                body["queued_requests"] = int(m["queued_requests"])
                body["max_num_seqs"] = int(eng.config.max_num_seqs)
            except Exception:
                pass
            self._send_json(body)
        elif url.path == "/get_model_info":
            import jax

            dev = jax.devices()
            self._send_json(
                {
                    "model_version": eng.model_version,
                    "model_path": eng.config.model_path,
                    "max_model_len": eng.config.max_model_len,
                    # what actually runs: the resolved paged-attention
                    # backend and the device jax reports
                    "attn_impl": getattr(eng, "attn_impl", None),
                    "device": {
                        "platform": dev[0].platform,
                        "kind": dev[0].device_kind,
                        "count": len(dev),
                    },
                }
            )
        elif url.path == "/metrics":
            hists = (
                eng.latency_histograms()
                if hasattr(eng, "latency_histograms")
                else None
            )
            text = render_prometheus(
                eng.metrics(), prefix="areal_tpu_gen_",
                help_text=_METRIC_HELP, histograms=hists,
            )
            pols = getattr(eng, "_policies", None)
            if pols is not None and pols.active:
                # per-policy labeled families: hand-rendered after the
                # scalar block (router-style) because render_prometheus
                # only supports labels on histogram keys. TYPEs come
                # from the module registry; base names are in
                # _METRIC_HELP + the ARL003 extra_names declaration.
                lines = [text.rstrip("\n")]
                for name, st in sorted(eng.policy_status().items()):
                    lab = f'{{policy="{name}"}}'
                    cv = st["canary_version"]
                    lines += [
                        f'areal_tpu_gen_policy_stable_version{lab} '
                        f'{st["stable_version"]}',
                        f'areal_tpu_gen_policy_canary_version{lab} '
                        f'{-1 if cv is None else cv}',
                        f'areal_tpu_gen_policy_canary_fraction{lab} '
                        f'{st["canary_fraction"]}',
                        f'areal_tpu_gen_policy_requests_total{lab} '
                        f'{st["requests_total"]}',
                        f'areal_tpu_gen_policy_tokens_total{lab} '
                        f'{st["tokens_total"]}',
                    ]
                text = "\n".join(lines) + "\n"
            self._send_text(text.encode(), "text/plain; version=0.0.4")
        elif url.path == "/policy":
            # multi-policy status (r19): per-line versions, split,
            # residency, pins — trace_report --policy reads this shape
            self._send_json({"policies": eng.policy_status()})
        elif url.path == "/trace":
            # drains the engine's span buffer: a scraper polling /trace
            # assembles the full timeline without unbounded server memory
            body, ctype = trace_response(eng.tracer, url.query)
            self._send_text(body, ctype)
        elif url.path == "/kv_export":
            # GET form: ?tokens=1,2,3 (the POST body form is canonical;
            # this one exists for curl-ability and the endpoint pair
            # symmetry the shipping contract documents)
            if not getattr(eng, "kv_ship_enabled", False):
                self._send_json(
                    {"error": "kv shipping disabled "
                     "(start the server with --kv-ship)"}, 403
                )
                return
            q = urllib.parse.parse_qs(url.query)
            toks = [
                int(t)
                for t in q.get("tokens", [""])[0].split(",")
                if t != ""
            ]
            self._send_json(self._kv_export_body(eng, toks))
        else:
            self._send_json({"error": f"unknown path {self.path}"}, 404)

    def do_POST(self):
        eng = self.engine
        if self._apply_chaos():
            return
        try:
            if self.path == "/generate":
                # the body must be consumed BEFORE any early response:
                # on an HTTP/1.1 keep-alive connection an unread body
                # desyncs the stream — the peer's next request line is
                # parsed out of the leftover JSON (a 400 the client
                # treats as non-retryable). The drain-path 503 below
                # was exactly that bug until the autoscaler drain test
                # ran mid-wave over pooled aiohttp connections.
                payload = self._read_json()
                if (
                    self.control is not None
                    and self.control.draining.is_set()
                ):
                    # drain mode: no new admissions; in-flight requests
                    # (already inside eng.generate) run to completion
                    self._send_json({"error": "draining"}, 503)
                    return
                # incoming trace context: bind the originating episode's
                # trace id (and rid, when the body doesn't carry one)
                # onto this server's spans so the fleet timeline stitches
                header_rid = self.headers.get(RID_HEADER)
                if header_rid and "rid" not in payload:
                    payload["rid"] = header_rid
                trace_id = self.headers.get(TRACE_HEADER)
                if trace_id and "trace_ctx" not in payload:
                    payload["trace_ctx"] = trace_id
                # router affinity-miss hint (r16): fetch the session's
                # committed prefix from its previous owner BEFORE the
                # claim, so this request's admission serves it cached
                ship_from = payload.pop("kv_ship_from", None)
                if ship_from and getattr(eng, "kv_ship_enabled", False):
                    self._ship_prefix(eng, ship_from, payload)
                try:
                    result = eng.generate(payload)
                except UnknownPolicyError as e:
                    # typed 4xx: utils/http retries 5xx only, so a bad
                    # handle fails fast instead of burning the budget
                    self._send_json(
                        {
                            "error": str(e),
                            "type": "unknown_policy",
                            "policy": e.handle,
                        },
                        e.status,
                    )
                    return
                except AdmissionRejectedError as e:
                    # load shed: typed 429 + Retry-After so utils/http
                    # treats it as backpressure, not failure
                    self._send_json(
                        {
                            "error": "shed",
                            "sched_class": e.sched_class,
                            "retry_after": e.retry_after,
                        },
                        429,
                        headers={"Retry-After": f"{e.retry_after:g}"},
                    )
                    return
                self._send_json(result)
            elif self.path.startswith("/profile"):
                if not self.profile_endpoint:
                    self._send_json(
                        {"error": "profile endpoint disabled "
                         "(start the server with --enable-profile)"}, 403
                    )
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                payload = self._read_json()
                steps = int(
                    payload.get("steps", q.get("steps", ["1"])[0])
                )
                trace_dir = eng.request_profile(
                    steps, payload.get("out_dir") or None
                )
                self._send_json(
                    {"success": True, "steps": steps,
                     "trace_dir": trace_dir}
                )
            elif self.path == "/drain":
                self._read_json()  # drain takes no arguments; drain the body
                if self.control is None:
                    self._send_json({"error": "no server control"}, 500)
                else:
                    n = self.control.start_drain()
                    self._send_json(
                        {"status": "draining", "in_flight": n}
                    )
            elif self.path == "/chaos":
                payload = self._read_json()
                if not self.chaos_endpoint:
                    self._send_json(
                        {"error": "chaos endpoint disabled "
                         "(start the server with --enable-chaos)"}, 403
                    )
                    return
                inj = chaos.configure(payload.get("spec") or None)
                self._send_json({
                    "success": True,
                    "rules": inj.stats() if inj else [],
                })
            elif self.path == "/pause_generation":
                eng.pause()
                self._send_json({"status": "paused"})
            elif self.path == "/continue_generation":
                eng.continue_generation()
                self._send_json({"status": "resumed"})
            elif self.path == "/update_weights_from_disk":
                payload = self._read_json()
                if payload.get("policy"):
                    # named-line push (r19): zero-pause by construction
                    # — no flip, the default line is untouched
                    version = eng.update_policy_from_disk(
                        payload["policy"], payload["model_path"],
                        payload.get("version"),
                        float(payload.get("canary_fraction") or 0.0),
                    )
                    self._send_json({
                        "success": True, "policy": payload["policy"],
                        "version": version,
                    })
                else:
                    version = eng.update_weights_from_disk(
                        payload["model_path"], payload.get("version")
                    )
                    self._send_json(
                        {"success": True, "model_version": version}
                    )
            elif self.path == "/update_weights_from_distributed":
                # binary FFD chunk (reference sglang_remote.py:411 NCCL
                # receive, host-staged over HTTP here)
                from areal_tpu.utils.weight_transfer import decode_chunk

                n = int(self.headers.get("Content-Length", 0))
                header, arrays = decode_chunk(self.rfile.read(n))
                policy = header.pop("policy", None)
                if policy:
                    out = eng.update_policy_chunk(policy, header, arrays)
                else:
                    out = eng.update_weights_chunk(header, arrays)
                self._send_json({"success": True, **out})
            elif self.path == "/policy":
                # registry lifecycle ops (r19): promote / retire /
                # split. Unknown names fail typed 4xx below.
                payload = self._read_json()
                op = payload.get("op", "")
                name = payload.get("policy", "")
                if op == "promote":
                    version = eng.promote_policy(name)
                    self._send_json({
                        "success": True, "policy": name,
                        "stable_version": version,
                    })
                elif op == "retire":
                    eng.retire_policy(name)
                    self._send_json(
                        {"success": True, "policy": name, "retired": True}
                    )
                elif op == "split":
                    frac = float(payload.get("canary_fraction", 0.0))
                    eng.set_policy_split(name, frac)
                    self._send_json({
                        "success": True, "policy": name,
                        "canary_fraction": frac,
                    })
                else:
                    self._send_json(
                        {"error": f"unknown policy op {op!r}"}, 400
                    )
            elif self.path == "/kv_export":
                payload = self._read_json()
                if not getattr(eng, "kv_ship_enabled", False):
                    self._send_json(
                        {"error": "kv shipping disabled "
                         "(start the server with --kv-ship)"}, 403
                    )
                    return
                self._send_json(
                    self._kv_export_body(
                        eng,
                        [int(t) for t in payload.get("tokens", [])],
                    )
                )
            elif self.path == "/kv_import":
                payload = self._read_json()
                if not getattr(eng, "kv_ship_enabled", False):
                    self._send_json(
                        {"error": "kv shipping disabled "
                         "(start the server with --kv-ship)"}, 403
                    )
                    return
                imported = self._kv_import_body(eng, payload)
                self._send_json(
                    {"success": True, "imported_tokens": imported}
                )
            else:
                self._send_json({"error": f"unknown path {self.path}"}, 404)
        except UnknownPolicyError as e:
            # typed 4xx for every policy-plane endpoint: a bad handle
            # is a caller bug, not a server fault — never retried
            self._send_json(
                {
                    "error": str(e),
                    "type": "unknown_policy",
                    "policy": e.handle,
                },
                e.status,
            )
        except Exception as e:  # surface engine errors as 500s
            logger.error(f"{self.path} failed: {e}")
            self._send_json({"error": str(e)}, 500)


def serve(
    engine: GenerationEngine,
    host: str = "127.0.0.1",
    port: int = 0,
    experiment_name: str = "",
    trial_name: str = "",
    server_index: int = 0,
    background: bool = False,
    router_addr: str = "",
    chaos_endpoint: bool = True,
    profile_endpoint: bool = True,
) -> ThreadingHTTPServer:
    if port == 0:
        port = network.find_free_ports(1)[0]
    tracer = getattr(engine, "tracer", None)  # stub engines have none
    if tracer is not None and not tracer.service:
        # label this process's spans for the stitched fleet timeline
        tracer.service = f"server:{host}:{port}"
    control = ServerControl(engine)
    handler = type(
        "Handler", (_Handler,),
        {"engine": engine, "control": control,
         "chaos_endpoint": chaos_endpoint,
         "profile_endpoint": profile_endpoint},
    )
    httpd = ThreadingHTTPServer((host, port), handler)
    httpd.daemon_threads = True
    httpd.server_control = control  # for tests/introspection
    tracker = getattr(engine, "compiles", None)
    if tracker is not None:
        # cold-start timeline mark (trace_report --coldstart): the
        # server answers its port from HERE; ready comes later
        tracker.append_event(
            {"kind": "lifecycle", "event": "port", "port": port}
        )
    if experiment_name and trial_name:
        # register for discovery (reference generation_server.py:159-170);
        # the key is kept so /drain can deregister this server live
        control.registration_key = name_resolve.add_subentry(
            names.gen_servers(experiment_name, trial_name),
            f"{host}:{port}",
        )
    if router_addr:
        # dynamic membership without a shared name_resolve: announce
        # directly to the fronting router (best-effort — the router's
        # prober also finds us through the membership watch)
        try:
            req = urllib.request.Request(
                f"http://{router_addr}/register",
                data=json.dumps({"addr": f"{host}:{port}"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
            logger.info(f"registered with router {router_addr}")
        except Exception as e:
            logger.warning(f"router registration failed: {e}")
    logger.info(f"generation server listening on {host}:{port}")
    if background:
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
    else:
        httpd.serve_forever()
    return httpd


def main(argv: Optional[list] = None):
    p = argparse.ArgumentParser()
    p.add_argument("--model-path", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument("--max-model-len", type=int, default=4096)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--seed", type=int, default=1)
    # engine shape/batching knobs — every scalar JaxGenConfig field has
    # a flag and build_cmd forwards it, so a LAUNCHED server serves the
    # same config a colocated engine would (arealint ARL002 pins the
    # field ↔ flag ↔ build_cmd parity; defaults are read from a default
    # dataclass instance so a dataclass edit cannot leave a manually-
    # launched server on a stale hand-copied default)
    d = JaxGenConfig()
    p.add_argument("--prefill-chunk", type=int, default=d.prefill_chunk)
    p.add_argument(
        "--chunked-prefill", action="store_true",
        help="split long prompts' prefill into page-aligned chunks "
        "interleaved with decode dispatches (bounded interactive TTFT "
        "under bulk saturation; greedy streams stay bit-identical; "
        "needs a prefix cache)",
    )
    p.add_argument(
        "--prefill-chunk-tokens", type=int,
        default=d.prefill_chunk_tokens,
        help="per-dispatch prefill token budget with --chunked-prefill "
        "(page-aligned; 0 = auto: 2x prefill-chunk)",
    )
    p.add_argument("--decode-chunk", type=int, default=d.decode_chunk)
    p.add_argument(
        "--decode-pipeline", type=int, default=d.decode_pipeline
    )
    p.add_argument(
        "--no-decode-compact", action="store_true",
        help="disable decode tail compaction (full-slot dispatch)",
    )
    p.add_argument(
        "--decode-compact-min-rows", type=int,
        default=d.decode_compact_min_rows,
    )
    p.add_argument(
        "--decode-compact-hysteresis", type=int,
        default=d.decode_compact_hysteresis,
    )
    p.add_argument("--admit-wave", type=int, default=d.admit_wave)
    p.add_argument("--admit-hold", type=float, default=d.admit_hold_s)
    p.add_argument("--kv-bucket", type=int, default=d.kv_bucket)
    p.add_argument(
        "--sample-topk-bound", type=int, default=d.sample_topk_bound
    )
    p.add_argument("--page-size", type=int, default=d.page_size)
    p.add_argument(
        "--num-pages", type=int, default=d.num_pages,
        help="KV pool pages (0 = auto full provisioning)",
    )
    p.add_argument(
        "--attn-impl", default=d.attn_impl,
        choices=("auto", "kernel", "jnp"),
    )
    p.add_argument(
        "--pages-per-compute-block", type=int,
        default=d.pages_per_compute_block,
    )
    p.add_argument(
        "--slots-per-block", type=int, default=d.slots_per_block
    )
    p.add_argument(
        "--pool-layout", default=d.pool_layout,
        choices=("auto", "token_packed", "head_merged"),
    )
    p.add_argument("--mem-fraction", type=float, default=d.mem_fraction)
    p.add_argument(
        "--disable-metrics", action="store_true",
        help="turn off the engine metrics counters",
    )
    p.add_argument("--log-level", default=d.log_level)
    p.add_argument(
        "--trace-max-spans", type=int, default=d.tracing.max_spans,
        help="span ring-buffer bound when --trace is on",
    )
    p.add_argument("--experiment-name", default="")
    p.add_argument("--trial-name", default="")
    p.add_argument("--server-index", type=int, default=0)
    p.add_argument(
        "--trace", action="store_true",
        help="record request-lifecycle spans (drain via GET /trace)",
    )
    p.add_argument(
        "--compilation-cache-dir", default="",
        help="persistent XLA compile cache (warm engines skip the "
        "decode bucket-ladder warmup)",
    )
    p.add_argument(
        "--prefix-cache-mode", default="radix",
        choices=("radix", "flat"),
        help="prefix-cache implementation: radix (publish-at-commit "
        "tree, the default) or flat (the legacy free-time registry)",
    )
    p.add_argument(
        "--prefix-reuse-min", type=int, default=16,
        help="minimum matched prompt tokens for a prefix-cache claim "
        "(0 disables prefix reuse entirely)",
    )
    p.add_argument(
        "--kv-spill", action="store_true",
        help="hierarchical KV tiers: spill radix leaves to host RAM "
        "on eviction and promote them back at claim time (radix "
        "cache mode only)",
    )
    p.add_argument(
        "--host-kv-bytes", type=int, default=d.host_kv_bytes,
        help="host spill-tier byte budget with --kv-spill",
    )
    p.add_argument(
        "--kv-disk-path", default=d.kv_disk_path,
        help="directory for the disk tier (host-LRU overflow pages); "
        "empty = no disk tier",
    )
    p.add_argument(
        "--kv-ship", action="store_true",
        help="cross-server prefix shipping: serve /kv_export + "
        "/kv_import and honor the router's kv_ship_from hints",
    )
    p.add_argument(
        "--spec", action="store_true",
        help="enable draft-free speculative decoding (n-gram proposals "
        "+ multi-token verify; greedy streams stay bit-identical)",
    )
    p.add_argument("--spec-max-draft", type=int, default=4)
    p.add_argument("--spec-ngram-min", type=int, default=2)
    p.add_argument("--spec-ngram-max", type=int, default=4)
    p.add_argument("--spec-accept-floor", type=float, default=0.1)
    p.add_argument("--spec-disable-patience", type=int, default=32)
    p.add_argument(
        "--max-queued-requests", type=int, default=0,
        help="bounded admission queue: past this depth new bulk "
        "requests are shed with 429 + Retry-After (interactive past "
        "twice the bound; 0 = unbounded)",
    )
    p.add_argument(
        "--shed-retry-after", type=float, default=1.0,
        help="Retry-After seconds attached to shed (429) responses",
    )
    p.add_argument(
        "--no-deadline-preemption", action="store_true",
        help="disable deadline-aware preemption of bulk requests",
    )
    p.add_argument(
        "--deadline-margin", type=float, default=0.25,
        help="preempt a bulk request when a queued interactive request "
        "is within this many seconds of its soft deadline",
    )
    p.add_argument(
        "--ready-quiet", type=float, default=3.0,
        help="report /health warming until this many seconds pass "
        "without an XLA compile (or the shape ladder is covered)",
    )
    p.add_argument(
        "--ready-min-requests", type=int, default=1,
        help="completed requests that latch the server ready even "
        "while incremental shapes still compile (<= 0 disables)",
    )
    p.add_argument(
        "--compile-events", default="",
        help="append one JSONL line per XLA compile (phase + shape "
        "signature + duration) — the AOT precompiler's input",
    )
    p.add_argument(
        "--compile-events-max-bytes", type=int,
        default=d.goodput.compile_events_max_bytes,
        help="rotate the compile-events stream to <path>.1 past this "
        "size (the stream is otherwise unbounded across restarts)",
    )
    p.add_argument(
        "--precompile", default=d.precompile.mode,
        help="AOT-precompile the shape ladder before serving traffic: "
        "off | ladder | replay (replay:<path> is shorthand for "
        "--precompile replay --precompile-replay <path>)",
    )
    p.add_argument(
        "--precompile-replay", default=d.precompile.replay_path,
        help="compile_events.jsonl from a prior run to replay "
        "(--precompile replay); a mismatched ladder fingerprint is "
        "refused",
    )
    p.add_argument(
        "--goodput-jsonl", default="",
        help="append goodput ledger snapshots (bucket fractions, duty "
        "cycle, effective tok/s) to this JSONL stream",
    )
    p.add_argument(
        "--no-weight-streaming", action="store_true",
        help="disable the zero-pause weight plane: weight updates "
        "apply on the engine loop under the legacy pause protocol "
        "(the bench A/B baseline)",
    )
    p.add_argument(
        "--weight-flip-policy", default=d.weights.flip_policy,
        choices=("pin", "resume"),
        help="in-flight requests at a streamed flip: 'pin' keeps them "
        "decoding on the outgoing buffer until they drain; 'resume' "
        "aborts them into the client's suffix-resume loop",
    )
    p.add_argument(
        "--weight-staging-ttl", type=float,
        default=d.weights.staging_ttl_s,
        help="seconds before an abandoned chunked weight stream's "
        "staging is dropped (<= 0 disables the sweep)",
    )
    p.add_argument(
        "--policy-max-resident", type=int,
        default=d.policy.max_resident,
        help="named policy weight buffers kept resident in HBM; colder "
        "(unpinned) buffers LRU-demote to host RAM and reload on the "
        "next request targeting them (<= 0 disables demotion)",
    )
    p.add_argument(
        "--router-addr", default="",
        help="router host:port to POST /register to at startup "
        "(dynamic fleet membership without shared name_resolve)",
    )
    p.add_argument(
        "--enable-chaos", action="store_true",
        help="open the runtime POST /chaos fault-injection endpoint "
        "(resilience testing only — it can hard-kill the server)",
    )
    p.add_argument(
        "--enable-profile", action="store_true",
        help="open POST /profile?steps=N (on-demand jax.profiler "
        "capture of the next N busy engine-loop iterations)",
    )
    args = p.parse_args(argv)
    # subprocess servers rendezvous in the launcher's namespace: the
    # launcher exports AREAL_NAME_RESOLVE (e.g. "nfs:/shared/root") so
    # registrations land where trainers/routers watch for them
    name_resolve.reconfigure_from_env()
    cfg = JaxGenConfig(
        model_path=args.model_path,
        dtype=args.dtype,
        seed=args.seed,
        max_num_seqs=args.max_num_seqs,
        max_model_len=args.max_model_len,
        tensor_parallel_size=args.tensor_parallel_size,
        host=args.host,
        port=args.port,
        prefill_chunk=args.prefill_chunk,
        chunked_prefill=args.chunked_prefill,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        decode_chunk=args.decode_chunk,
        decode_pipeline=args.decode_pipeline,
        decode_compact=not args.no_decode_compact,
        decode_compact_min_rows=args.decode_compact_min_rows,
        decode_compact_hysteresis=args.decode_compact_hysteresis,
        admit_wave=args.admit_wave,
        admit_hold_s=args.admit_hold,
        kv_bucket=args.kv_bucket,
        sample_topk_bound=args.sample_topk_bound,
        page_size=args.page_size,
        num_pages=args.num_pages,
        attn_impl=args.attn_impl,
        pages_per_compute_block=args.pages_per_compute_block,
        slots_per_block=args.slots_per_block,
        pool_layout=args.pool_layout,
        mem_fraction=args.mem_fraction,
        enable_metrics=not args.disable_metrics,
        log_level=args.log_level,
        compilation_cache_dir=args.compilation_cache_dir,
        prefix_cache_mode=args.prefix_cache_mode,
        prefix_reuse_min=args.prefix_reuse_min,
        kv_spill=args.kv_spill,
        host_kv_bytes=args.host_kv_bytes,
        kv_disk_path=args.kv_disk_path,
        kv_ship=args.kv_ship,
        max_queued_requests=args.max_queued_requests,
        shed_retry_after_s=args.shed_retry_after,
        deadline_preemption=not args.no_deadline_preemption,
        deadline_margin_s=args.deadline_margin,
    )
    cfg.tracing.enabled = args.trace
    cfg.tracing.max_spans = args.trace_max_spans
    cfg.weights.streaming = not args.no_weight_streaming
    cfg.weights.flip_policy = args.weight_flip_policy
    cfg.weights.staging_ttl_s = args.weight_staging_ttl
    cfg.policy.max_resident = args.policy_max_resident
    cfg.goodput.ready_quiet_s = args.ready_quiet
    cfg.goodput.ready_min_requests = args.ready_min_requests
    cfg.goodput.compile_events_path = args.compile_events
    cfg.goodput.compile_events_max_bytes = args.compile_events_max_bytes
    cfg.goodput.jsonl_path = args.goodput_jsonl
    # --precompile replay:<path> shorthand folds into mode + path
    pc_mode, pc_path = args.precompile, args.precompile_replay
    if pc_mode.startswith("replay:"):
        pc_mode, pc_path = "replay", pc_mode.split(":", 1)[1]
    if pc_mode not in ("off", "ladder", "replay"):
        p.error(
            f"--precompile {args.precompile!r}: expected off | ladder "
            f"| replay[:<path>]"
        )
    if pc_mode == "replay" and not pc_path:
        # fail at PARSE time: a pathless replay would only surface as a
        # logged warm-thread error while the server silently serves the
        # full cold storm the operator asked to skip
        p.error(
            "--precompile replay needs a stream: pass "
            "--precompile-replay <path> (or --precompile replay:<path>)"
        )
    cfg.precompile.mode = pc_mode
    cfg.precompile.replay_path = pc_path
    cfg.spec.enabled = args.spec
    if args.spec:
        cfg.spec.max_draft = args.spec_max_draft
        cfg.spec.ngram_min = args.spec_ngram_min
        cfg.spec.ngram_max = args.spec_ngram_max
        cfg.spec.accept_floor = args.spec_accept_floor
        cfg.spec.disable_patience = args.spec_disable_patience
    engine = GenerationEngine(cfg).start()
    if cfg.precompile.mode != "off":
        # warm CONCURRENTLY with serving: the port answers immediately,
        # /health reports warming with rising ladder coverage, and the
        # fleet plane keeps the server out of rotation until ready —
        # a precompile failure degrades to the traffic-driven warmup
        def _warm():
            try:
                engine.precompile()
            except Exception as e:
                logger.error(f"precompile failed (serving cold): {e}")

        threading.Thread(
            target=_warm, name="precompile", daemon=True
        ).start()
    serve(
        engine,
        host=args.host,
        port=args.port,
        experiment_name=args.experiment_name,
        trial_name=args.trial_name,
        server_index=args.server_index,
        router_addr=args.router_addr,
        chaos_endpoint=args.enable_chaos,
        profile_endpoint=args.enable_profile,
    )


if __name__ == "__main__":
    main()
