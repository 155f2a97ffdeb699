"""Summarize a rollout trace into a per-phase latency table.

Input: span JSONL (one ``{"name", "rid", "ts", "dur", ...}`` object per
line — what ``SpanTracer.export_jsonl`` / ``GET /trace?format=jsonl``
emit) or Chrome trace-event JSON (``{"traceEvents": [...]}`` — what
``GET /trace`` / ``SpanTracer.export_chrome`` emit). Output: one row per
span name with count / p50 / p95 / mean / max / total seconds, e.g.::

    phase              count    p50_ms    p95_ms   mean_ms    max_ms  total_s
    queue_wait            64      1.20     15.40      3.10     22.00    0.198
    prefill               64     48.00     95.00     52.00    101.00    3.328
    decode                64   1520.00   2210.00   1604.00   2350.00  102.656
    pause_window           3    610.00    780.00    650.00    780.00    1.950

Runs in CI as a smoke check against a synthetic trace
(tests/test_tracing.py); on a real capture it is the first-look answer to
"where did rollout wall time go" — queue wait vs prefill vs decode vs
weight-update pauses.

``--occupancy`` switches to the decode-row occupancy report instead:
``decode_chunk`` spans carry the engine's per-chunk rows_dispatched /
rows_active gauges (r6 decode tail compaction), and the report prints
lifetime totals, mean occupancy, and a rows-per-chunk histogram.

``--spec`` switches to the speculative-decoding report (r7):
``spec_verify`` instants carry per-round drafted/accepted counts, and
the report prints the accept-rate histogram, draft-length distribution,
and verified-tokens/s over the spec window.

``--durability`` switches to the trainer-durability report (r8):
``checkpoint_dump``/``checkpoint_commit`` spans (utils/recover.py) give
dump/commit latency percentiles, and ``episode_retry``/``quarantine``
instants (api/workflow_api.py) give the retry-attempt histogram and the
quarantined-sample list — the first-look answer to "what is the
checkpoint tax and how sick are my reward/env backends".

``--lineage`` reads a lineage-ledger JSONL (r9:
``utils/telemetry.LineageLedger`` — the per-sample records
``WorkflowExecutor`` appends on consumption and snapshots into recover
checkpoints) instead of a span trace: one row per sample with attempts,
servers, weight versions, migrations, staleness at consumption, and the
consuming step — the full reconstruction of a trajectory's life from
the ledger alone.

``--fleet`` reads a telemetry-hub run-manifest JSON (r9:
``TelemetryCollector.manifest`` / ``GET /manifest``) and prints the
fleet rollup, the anomaly table, and a per-server line.

``--weights`` switches to the zero-pause weight-plane report (r13):
``weight_stream_chunk`` spans give the per-push chunk/byte timeline,
``weight_flip`` instants give flip latency + policy + pinned-request
counts, client ``weight_stream`` spans give end-to-end push wall time,
and the pause-span census answers "did this push ever stop decode".
``--require-zero-pause`` turns a nonzero census into exit 1 — the
streamed-push CI invariant.

``--goodput`` reads a goodput JSONL stream (r11: ``utils/goodput.py``
ledger snapshots and/or ``compile_events.jsonl``) and prints each
role's wall-time bucket breakdown (fractions sum to 1.0 — the direct
answer to "what did every second of trainer/server wall time buy") plus
the per-shape XLA compile bill, most expensive shape first.

``--ttft`` switches to the chunked-prefill TTFT report (r15): the
per-class TTFT p50/p95 table from a ``/metrics`` snapshot's native
``ttft_seconds`` histograms (r11 — the durable latency source), and the
chunks-per-prompt histogram from chunk-stamped ``prefill`` spans. Pass
``--require-max-ttft <s>`` (optionally ``--ttft-class``) to turn a
blown TTFT bound into exit 1 — the bounded-interactive-TTFT CI gate,
mirroring ``--require-max-lead``.
"""

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional


def load_spans(path: str) -> List[Dict[str, Any]]:
    """Load spans from JSONL or Chrome trace-event JSON; returns dicts
    with at least name / dur (seconds)."""
    with open(path) as f:
        text = f.read()
    stripped = text.lstrip()
    if stripped.startswith("{") and '"traceEvents"' in stripped[:200]:
        doc = json.loads(text)
        return [
            {
                "name": e["name"],
                "rid": e.get("args", {}).get("rid", ""),
                "ts": e.get("ts", 0.0) / 1e6,
                "dur": e.get("dur", 0.0) / 1e6,
                # span attrs ride in args next to rid (occupancy gauges
                # like rows_dispatched live here)
                "attrs": {
                    k: v
                    for k, v in e.get("args", {}).items()
                    if k != "rid"
                },
            }
            for e in doc.get("traceEvents", [])
            if e.get("ph") == "X"
        ]
    spans = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        spans.append(json.loads(line))
    return spans


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def summarize(spans: Iterable[Dict[str, Any]]) -> Dict[str, Dict[str, float]]:
    """Per-name latency stats (durations in seconds in, seconds out)."""
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(float(s.get("dur", 0.0)))
    out: Dict[str, Dict[str, float]] = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        out[name] = {
            "count": len(durs),
            "p50": _percentile(durs, 0.50),
            "p95": _percentile(durs, 0.95),
            "mean": sum(durs) / len(durs),
            "max": durs[-1],
            "total": sum(durs),
        }
    return out


def occupancy_summary(
    spans: Iterable[Dict[str, Any]]
) -> Dict[str, Any]:
    """Decode-row occupancy from ``decode_chunk`` spans (the engine's
    per-chunk rows_dispatched / rows_active gauges): lifetime totals,
    mean occupancy, and a rows_dispatched histogram — the first-look
    answer to "is the decode tail compacting, and how hard"."""
    chunks = 0
    dispatched = 0
    active = 0
    hist: Dict[int, int] = {}
    for s in spans:
        if s.get("name") != "decode_chunk":
            continue
        attrs = s.get("attrs") or {}
        rd = attrs.get("rows_dispatched")
        if rd is None:
            continue
        rd = int(rd)
        chunks += 1
        dispatched += rd
        active += int(attrs.get("rows_active", 0))
        hist[rd] = hist.get(rd, 0) + 1
    return {
        "chunks": chunks,
        "rows_dispatched": dispatched,
        "rows_active": active,
        "occupancy": round(active / dispatched, 4) if dispatched else 0.0,
        "rows_dispatched_hist": {
            str(k): hist[k] for k in sorted(hist)
        },
    }


def format_occupancy(occ: Dict[str, Any]) -> str:
    rows = [
        f"decode chunks        {occ['chunks']}",
        f"rows dispatched      {occ['rows_dispatched']}",
        f"rows active          {occ['rows_active']}",
        f"mean occupancy       {occ['occupancy'] * 100:.1f}%",
        "",
        f"{'rows/chunk':<12}{'chunks':>8}{'share':>9}",
    ]
    total = max(1, occ["chunks"])
    for bucket, count in occ["rows_dispatched_hist"].items():
        rows.append(
            f"{bucket:<12}{count:>8}{count / total * 100:>8.1f}%"
        )
    return "\n".join(rows)


def spec_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Speculative-decoding report from ``spec_verify`` instants (one
    per verify round, attrs drafted/accepted) and verify-flavored
    ``decode_chunk`` spans (attrs spec_draft_tokens/spec_draft_rows):
    totals, a per-round accept-rate histogram, the draft-length
    distribution, and verified tokens/s across the spec window — the
    first-look answer to "is speculation paying, and by how much"."""
    rounds = 0
    drafted = 0
    accepted = 0
    base_rows = 0
    rate_hist: Dict[str, int] = {}
    ts: List[float] = []
    draft_rows = 0
    draft_tokens = 0
    for s in spans:
        if s.get("name") == "spec_verify":
            attrs = s.get("attrs") or {}
            d = int(attrs.get("drafted", 0))
            a = int(attrs.get("accepted", 0))
            rounds += 1
            drafted += d
            accepted += a
            # rows that emitted this round: each contributes one
            # guaranteed base token on top of its accepted drafts (a
            # verify chunk covers MANY rows — older traces without the
            # attr fall back to 1/round, understating multi-row runs)
            base_rows += int(attrs.get("rows", 1))
            if d > 0:
                bucket = min(9, int(10 * a / d))
                key = f"{bucket * 10}-{bucket * 10 + 10}%"
                rate_hist[key] = rate_hist.get(key, 0) + 1
            ts.append(float(s.get("ts", 0.0)))
        elif s.get("name") == "decode_chunk":
            attrs = s.get("attrs") or {}
            if "spec_draft_tokens" in attrs:
                draft_tokens += int(attrs["spec_draft_tokens"])
                draft_rows += int(attrs.get("spec_draft_rows", 0))
    window = (max(ts) - min(ts)) if len(ts) > 1 else 0.0
    verified = base_rows + accepted
    return {
        "verify_rounds": rounds,
        "draft_tokens": drafted,
        "accepted_tokens": accepted,
        "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        # accepted drafts ride for free on top of the per-row base
        # tokens — this is the decode speedup numerator
        "verified_tokens_per_round": (
            round(verified / rounds, 3) if rounds else 0.0
        ),
        "verified_tokens_per_sec": (
            round(verified / window, 1) if window > 0 else 0.0
        ),
        "mean_draft_len": (
            round(draft_tokens / draft_rows, 2) if draft_rows else 0.0
        ),
        "accept_rate_hist": {
            k: rate_hist[k]
            for k in sorted(rate_hist, key=lambda x: int(x.split("-")[0]))
        },
    }


def format_spec(sp: Dict[str, Any]) -> str:
    rows = [
        f"verify rounds        {sp['verify_rounds']}",
        f"draft tokens         {sp['draft_tokens']}",
        f"accepted tokens      {sp['accepted_tokens']}",
        f"accept rate          {sp['accept_rate'] * 100:.1f}%",
        f"mean draft length    {sp['mean_draft_len']}",
        f"verified tok/round   {sp['verified_tokens_per_round']}",
        f"verified tok/s       {sp['verified_tokens_per_sec']}",
        "",
        f"{'accept rate':<14}{'rounds':>8}",
    ]
    for bucket, count in sp["accept_rate_hist"].items():
        rows.append(f"{bucket:<14}{count:>8}")
    return "\n".join(rows)


def cache_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Prefix-cache report from ``prefill`` spans (attrs prompt_tokens /
    cached_tokens — a sibling's whole prompt rode the representative's
    prefill, a claimant's cached_tokens is its radix-claim offset):
    token-level hit rate, request-level reuse counts, and a reuse-DEPTH
    histogram (how many tokens each cache-served request reused) — the
    first-look answer to "are GRPO siblings and agentic turns actually
    sharing prefill"."""
    requests = 0
    served = 0
    prompt_tokens = 0
    cached_tokens = 0
    host_cached = 0
    depth_hist: Dict[str, int] = {}
    for s in spans:
        if s.get("name") != "prefill":
            continue
        attrs = s.get("attrs") or {}
        pt = int(attrs.get("prompt_tokens", 0))
        ct = int(attrs.get("cached_tokens", attrs.get("cached_offset", 0)))
        requests += 1
        prompt_tokens += pt
        cached_tokens += ct
        # kv_spill engines stamp the host-tier share of each claim
        host_cached += int(attrs.get("host_cached_tokens", 0))
        if ct > 0:
            served += 1
            # pow2 token buckets: reuse depth spans 1-token partial-page
            # claims to multi-thousand-token shared histories
            b = 1 << max(0, ct - 1).bit_length()
            key = f"<={b}"
            depth_hist[key] = depth_hist.get(key, 0) + 1
    return {
        "prefill_requests": requests,
        "requests_served_from_cache": served,
        "request_hit_rate": round(served / requests, 4) if requests else 0.0,
        "prompt_tokens": prompt_tokens,
        "cached_tokens": cached_tokens,
        "token_hit_rate": (
            round(cached_tokens / prompt_tokens, 4) if prompt_tokens else 0.0
        ),
        "host_cached_tokens": host_cached,
        "host_token_share": (
            round(host_cached / cached_tokens, 4) if cached_tokens else 0.0
        ),
        "mean_reuse_depth": (
            round(cached_tokens / served, 1) if served else 0.0
        ),
        "reuse_depth_hist": {
            k: depth_hist[k]
            for k in sorted(depth_hist, key=lambda x: int(x[2:]))
        },
    }


def format_cache(ca: Dict[str, Any]) -> str:
    rows = [
        f"prefill requests     {ca['prefill_requests']}",
        f"served from cache    {ca['requests_served_from_cache']}"
        f" ({ca['request_hit_rate'] * 100:.1f}%)",
        f"prompt tokens        {ca['prompt_tokens']}",
        f"cached tokens        {ca['cached_tokens']}"
        f" ({ca['token_hit_rate'] * 100:.1f}%)",
    ]
    if ca.get("host_cached_tokens"):
        rows.append(
            f"  from host tier     {ca['host_cached_tokens']}"
            f" ({ca['host_token_share'] * 100:.1f}% of cached)"
        )
    rows += [
        f"mean reuse depth     {ca['mean_reuse_depth']} tokens",
        "",
        f"{'reuse depth':<14}{'requests':>10}",
    ]
    for bucket, count in ca["reuse_depth_hist"].items():
        rows.append(f"{bucket:<14}{count:>10}")
    return "\n".join(rows)


def _parse_cache_metrics(text: str) -> Dict[str, float]:
    """Pull the prefix-cache / KV-tier / shipping series out of a
    Prometheus ``/metrics`` snapshot (names with or without the
    ``areal_tpu_gen_`` prefix). Returns {} for non-snapshot input."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split()
        if len(parts) != 2:
            continue
        name = parts[0].split("{", 1)[0]
        if name.startswith("areal_tpu_gen_"):
            name = name[len("areal_tpu_gen_"):]
        if name.startswith(("prefix_", "kv_tier_", "kv_ship_")) or name in (
            "total_prompt_tokens", "total_cached_prompt_tokens",
        ):
            try:
                out[name] = float(parts[1])
            except ValueError:
                pass
    return out


def load_cache(path: str) -> Dict[str, Any]:
    """Load ``--cache`` input: a ``/metrics`` snapshot (prefix + KV-tier
    counters — the durable source) or a span trace (``prefill`` spans).
    Either file kind works; the report renders whichever is present."""
    with open(path) as f:
        text = f.read()
    metrics = _parse_cache_metrics(text)
    spans: List[Dict[str, Any]] = []
    if not metrics:
        try:
            spans = load_spans(path)
        except (json.JSONDecodeError, KeyError):
            spans = []
    return {"metrics": metrics, "spans": spans}


def cache_metrics_summary(m: Dict[str, float]) -> Dict[str, Any]:
    """Per-tier prefix-cache report from a ``/metrics`` snapshot: the
    device/host/disk hit + volume split a span trace cannot carry (tier
    counters survive /trace drains and tracing-off runs). Tier and
    shipping sections appear only when the snapshot carries their keys
    — i.e. only when the server ran with --kv-spill / --kv-ship."""

    def g(k: str) -> float:
        return m.get(k, 0.0)

    host_tokens = int(g("kv_tier_host_cached_tokens_total"))
    cached = int(g("total_cached_prompt_tokens"))
    out: Dict[str, Any] = {
        "source": "metrics",
        "prompt_tokens": int(g("total_prompt_tokens")),
        "cached_tokens": cached,
        "token_hit_rate": g("prefix_cache_hit_rate"),
        "claim_hit_rate": g("prefix_claim_hit_rate"),
        "cow_copies": int(g("prefix_cow_copies_total")),
        "evicted_pages": int(g("prefix_evicted_pages_total")),
        "tiers": None,
        "ship": None,
    }
    if "kv_tier_spilled_pages_total" in m:
        out["tiers"] = {
            "device_cached_tokens": max(0, cached - host_tokens),
            "host_cached_tokens": host_tokens,
            "host_claim_hit_rate": g("kv_tier_host_claim_hit_rate"),
            "host_claim_hits": int(g("kv_tier_host_claim_hits_total")),
            "host_pages": int(g("kv_tier_host_pages")),
            "host_bytes": int(g("kv_tier_host_bytes")),
            "spilled_pages": int(g("kv_tier_spilled_pages_total")),
            "spilled_bytes": int(g("kv_tier_spilled_bytes_total")),
            "promoted_pages": int(g("kv_tier_promoted_pages_total")),
            "promoted_bytes": int(g("kv_tier_promoted_bytes_total")),
            "dropped_pages": int(g("kv_tier_dropped_pages_total")),
            "disk_pages": int(g("kv_tier_disk_pages")),
            "disk_spilled_pages": int(
                g("kv_tier_disk_spilled_pages_total")
            ),
            "disk_loaded_pages": int(g("kv_tier_disk_loaded_pages_total")),
        }
    if "kv_ship_exports_total" in m:
        out["ship"] = {
            "exports": int(g("kv_ship_exports_total")),
            "imports": int(g("kv_ship_imports_total")),
            "pages_out": int(g("kv_ship_pages_out_total")),
            "pages_in": int(g("kv_ship_pages_in_total")),
            "failures": int(g("kv_ship_failures_total")),
        }
    return out


def format_cache_metrics(ca: Dict[str, Any]) -> str:
    rows = [
        f"prompt tokens        {ca['prompt_tokens']}",
        f"cached tokens        {ca['cached_tokens']}"
        f" ({ca['token_hit_rate'] * 100:.1f}%)",
        f"claim hit rate       {ca['claim_hit_rate'] * 100:.1f}%",
        f"cow copies           {ca['cow_copies']}",
        f"evicted pages        {ca['evicted_pages']}",
    ]
    t = ca.get("tiers")
    if t:
        rows += [
            "",
            "kv tiers (--kv-spill)",
            f"  device cached tok  {t['device_cached_tokens']}",
            f"  host cached tok    {t['host_cached_tokens']}",
            f"  host claim hits    {t['host_claim_hits']}"
            f" ({t['host_claim_hit_rate'] * 100:.1f}% of claims)",
            f"  host pages/bytes   {t['host_pages']} / {t['host_bytes']}",
            f"  spilled pages      {t['spilled_pages']}"
            f" ({t['spilled_bytes']} B)",
            f"  promoted pages     {t['promoted_pages']}"
            f" ({t['promoted_bytes']} B)",
            f"  dropped pages      {t['dropped_pages']}",
            f"  disk pages         {t['disk_pages']}"
            f" (spilled {t['disk_spilled_pages']},"
            f" loaded {t['disk_loaded_pages']})",
        ]
    sh = ca.get("ship")
    if sh:
        rows += [
            "",
            "prefix shipping (--kv-ship)",
            f"  exports            {sh['exports']}"
            f" ({sh['pages_out']} pages out)",
            f"  imports            {sh['imports']}"
            f" ({sh['pages_in']} pages in)",
            f"  failures           {sh['failures']}",
        ]
    return "\n".join(rows)


def failover_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Resilience-event report from ``failover``/``migration`` spans
    (engine/remote.py records one instant per server hop; migrations are
    the hops that carried a non-empty accumulated suffix): totals, the
    failure-reason histogram, per-from-server counts, and resumed-suffix
    length stats — the first-look answer to "what did the fleet lose and
    how gracefully did it move"."""
    fo = [s for s in spans if s.get("name") == "failover"]
    migrations = sum(
        1 for s in spans if s.get("name") == "migration"
    )
    reasons: Dict[str, int] = {}
    from_servers: Dict[str, int] = {}
    resumed: List[int] = []
    for s in fo:
        attrs = s.get("attrs") or {}
        reasons[str(attrs.get("reason", "?"))] = (
            reasons.get(str(attrs.get("reason", "?")), 0) + 1
        )
        src = str(attrs.get("from_server", "?"))
        from_servers[src] = from_servers.get(src, 0) + 1
        resumed.append(int(attrs.get("resumed_tokens", 0)))
    resumed.sort()
    return {
        "failovers": len(fo),
        "migrations": migrations,
        "rids": len({s.get("rid", "") for s in fo}),
        "by_reason": dict(sorted(reasons.items())),
        "by_from_server": dict(sorted(from_servers.items())),
        "resumed_tokens_mean": (
            round(sum(resumed) / len(resumed), 2) if resumed else 0.0
        ),
        "resumed_tokens_p50": _percentile(resumed, 0.50),
        "resumed_tokens_max": resumed[-1] if resumed else 0,
    }


def format_failover(fo: Dict[str, Any]) -> str:
    rows = [
        f"failovers            {fo['failovers']}",
        f"migrations           {fo['migrations']} "
        f"(resumed a non-empty suffix)",
        f"requests affected    {fo['rids']}",
        f"resumed tokens       mean {fo['resumed_tokens_mean']}  "
        f"p50 {fo['resumed_tokens_p50']}  max {fo['resumed_tokens_max']}",
        "",
        f"{'reason':<20}{'count':>7}",
    ]
    for reason, count in fo["by_reason"].items():
        rows.append(f"{reason:<20}{count:>7}")
    if fo["by_from_server"]:
        rows += ["", f"{'failed server':<24}{'count':>7}"]
        for srv, count in fo["by_from_server"].items():
            rows.append(f"{srv:<24}{count:>7}")
    return "\n".join(rows)


def slo_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """SLO / traffic-plane report: per-scheduling-class queue-wait
    percentiles (from ``queue_wait`` spans' ``sched_class`` attr — the
    priority-isolation signal), the shed table (``shed`` instants by
    class / reason / tenant), and deadline outcomes (``deadline_miss``
    lateness + ``deadline_preempt`` events). This is the table that
    answers "did bulk pressure ever reach the interactive class"."""
    spans = list(spans)
    per_class: Dict[str, List[float]] = {}
    for s in spans:
        if s.get("name") != "queue_wait":
            continue
        attrs = s.get("attrs") or {}
        cls = str(attrs.get("sched_class", "?"))
        per_class.setdefault(cls, []).append(float(s.get("dur", 0.0)))
    queue_wait = {}
    for cls, vals in sorted(per_class.items()):
        vals.sort()
        queue_wait[cls] = {
            "n": len(vals),
            "p50_s": _percentile(vals, 0.50),
            "p95_s": _percentile(vals, 0.95),
            "max_s": round(vals[-1], 4) if vals else 0.0,
        }
    sheds = [s for s in spans if s.get("name") == "shed"]
    shed_by_class: Dict[str, int] = {}
    shed_by_reason: Dict[str, int] = {}
    shed_by_tenant: Dict[str, int] = {}
    for s in sheds:
        attrs = s.get("attrs") or {}
        cls = str(attrs.get("sched_class", "?"))
        shed_by_class[cls] = shed_by_class.get(cls, 0) + 1
        # engine sheds carry no reason (queue-full is the only one);
        # router sheds name tenant_cap/overload/fair_share
        reason = str(attrs.get("reason") or "queue_full")
        shed_by_reason[reason] = shed_by_reason.get(reason, 0) + 1
        tenant = str(attrs.get("tenant") or "?")
        shed_by_tenant[tenant] = shed_by_tenant.get(tenant, 0) + 1
    misses = [s for s in spans if s.get("name") == "deadline_miss"]
    late = sorted(
        float((s.get("attrs") or {}).get("late_s", 0.0)) for s in misses
    )
    return {
        "queue_wait_by_class": queue_wait,
        "shed_total": len(sheds),
        "shed_by_class": dict(sorted(shed_by_class.items())),
        "shed_by_reason": dict(sorted(shed_by_reason.items())),
        "shed_by_tenant": dict(sorted(shed_by_tenant.items())),
        "deadline_misses": len(misses),
        "deadline_late_p50_s": _percentile(late, 0.50),
        "deadline_late_max_s": round(late[-1], 4) if late else 0.0,
        "deadline_preemptions": sum(
            1 for s in spans if s.get("name") == "deadline_preempt"
        ),
    }


def format_slo(sl: Dict[str, Any]) -> str:
    rows = [f"{'class':<14}{'n':>7}{'p50':>10}{'p95':>10}{'max':>10}"]
    for cls, st in sl["queue_wait_by_class"].items():
        rows.append(
            f"{cls:<14}{st['n']:>7}{st['p50_s']:>10.4f}"
            f"{st['p95_s']:>10.4f}{st['max_s']:>10.4f}"
        )
    if not sl["queue_wait_by_class"]:
        rows.append("(no queue_wait spans)")
    rows += [
        "",
        f"requests shed        {sl['shed_total']}",
        f"deadline preemptions {sl['deadline_preemptions']}",
        f"deadline misses      {sl['deadline_misses']} "
        f"(late p50 {sl['deadline_late_p50_s']}s, "
        f"max {sl['deadline_late_max_s']}s)",
    ]
    for title, table in (
        ("shed by class", sl["shed_by_class"]),
        ("shed by reason", sl["shed_by_reason"]),
        ("shed by tenant", sl["shed_by_tenant"]),
    ):
        if table:
            rows += ["", f"{title:<20}{'count':>7}"]
            for k, v in table.items():
                rows.append(f"{k:<20}{v:>7}")
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# Chunked-prefill TTFT report (r15)
# ---------------------------------------------------------------------------
_TTFT_SERIES = "ttft_seconds"


def _parse_ttft_histograms(text: str) -> Dict[str, Dict[str, Any]]:
    """Parse the engine's native per-class ``ttft_seconds`` histograms
    out of a Prometheus ``/metrics`` snapshot (r11 format:
    ``..._ttft_seconds_bucket{sched_class="x",le="..."} n`` plus
    ``_sum``/``_count``). Returns {class: {buckets, sum, count}} with
    ``buckets`` as sorted ``(le, cumulative)`` pairs ending at +Inf."""
    out: Dict[str, Dict[str, Any]] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            name_part, val_s = line.rsplit(None, 1)
            val = float(val_s)
        except ValueError:
            continue
        if _TTFT_SERIES not in name_part:
            continue
        labels: Dict[str, str] = {}
        base = name_part
        if "{" in name_part and name_part.endswith("}"):
            base, lab_s = name_part[:-1].split("{", 1)
            for part in lab_s.split(","):
                if "=" in part:
                    k, v = part.split("=", 1)
                    labels[k.strip()] = v.strip().strip('"')
        cls = labels.get("sched_class", "?")
        rec = out.setdefault(
            cls, {"buckets": [], "sum": 0.0, "count": 0.0}
        )
        if base.endswith(f"{_TTFT_SERIES}_bucket"):
            le_s = labels.get("le", "+Inf")
            le = float("inf") if le_s in ("+Inf", "inf") else float(le_s)
            rec["buckets"].append((le, val))
        elif base.endswith(f"{_TTFT_SERIES}_sum"):
            rec["sum"] = val
        elif base.endswith(f"{_TTFT_SERIES}_count"):
            rec["count"] = val
    for rec in out.values():
        rec["buckets"].sort(key=lambda p: p[0])
    return {cls: rec for cls, rec in out.items() if rec["buckets"]}


def _hist_quantile(
    buckets: List[tuple], count: float, q: float
) -> float:
    """q-quantile from cumulative ``(le, cum)`` pairs: linear
    interpolation inside the winning bucket (mirrors the engine's
    ``Histogram.quantile``); the +Inf bucket answers its lower bound."""
    if count <= 0 or not buckets:
        return 0.0
    target = q * count
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in buckets:
        if cum >= target:
            if le == float("inf"):
                return round(prev_le, 6)
            width = cum - prev_cum
            frac = (target - prev_cum) / width if width > 0 else 1.0
            return round(prev_le + frac * (le - prev_le), 6)
        prev_le, prev_cum = le, cum
    return round(prev_le, 6)


def load_ttft(path: str) -> Dict[str, Any]:
    """Load ``--ttft`` input: a Prometheus ``/metrics`` snapshot (the
    per-class TTFT histograms) and/or a span trace (``prefill`` spans
    with chunked-prefill ``chunk_index``/``chunk_count`` attrs). Either
    file kind works; the report renders whatever is present."""
    with open(path) as f:
        text = f.read()
    hists = _parse_ttft_histograms(text)
    spans: List[Dict[str, Any]] = []
    if not hists:
        try:
            spans = load_spans(path)
        except (json.JSONDecodeError, KeyError):
            spans = []
    return {"hists": hists, "spans": spans}


def ttft_summary(data: Dict[str, Any]) -> Dict[str, Any]:
    """Chunked-prefill TTFT report: per-class TTFT p50/p95 from the
    engine's native histograms (the durable latency source — span
    percentiles vanish with every /trace drain), plus the
    chunks-per-prompt histogram from chunk-stamped ``prefill`` spans —
    together the direct answer to "is interactive TTFT bounded by one
    chunk under bulk saturation"."""
    by_class: Dict[str, Dict[str, float]] = {}
    for cls, rec in sorted(data.get("hists", {}).items()):
        count = rec["count"] or (
            rec["buckets"][-1][1] if rec["buckets"] else 0
        )
        by_class[cls] = {
            "n": int(count),
            "p50_s": _hist_quantile(rec["buckets"], count, 0.50),
            "p95_s": _hist_quantile(rec["buckets"], count, 0.95),
            "mean_s": (
                round(rec["sum"] / count, 6) if count else 0.0
            ),
        }
    # chunks-per-prompt: every chunk-capped dispatch and the final
    # admission stamp a prefill span with chunk_index; a prompt's chunk
    # count is its highest index + 1
    per_rid: Dict[str, int] = {}
    chunked_spans = 0
    for s in data.get("spans", []):
        if s.get("name") != "prefill":
            continue
        attrs = s.get("attrs") or {}
        if "chunk_index" not in attrs:
            continue
        chunked_spans += 1
        rid = str(s.get("rid", "?"))
        idx = int(attrs.get("chunk_index", 0))
        per_rid[rid] = max(per_rid.get(rid, 0), idx + 1)
    chunk_hist: Dict[str, int] = {}
    for n in per_rid.values():
        key = str(n)
        chunk_hist[key] = chunk_hist.get(key, 0) + 1
    return {
        "ttft_by_class": by_class,
        "chunked_prefill_spans": chunked_spans,
        "prompts_with_chunk_attrs": len(per_rid),
        "chunks_per_prompt_hist": {
            k: chunk_hist[k] for k in sorted(chunk_hist, key=int)
        },
        "chunks_per_prompt_max": max(per_rid.values(), default=0),
    }


def format_ttft(tt: Dict[str, Any]) -> str:
    rows = [f"{'class':<14}{'n':>7}{'p50_s':>10}{'p95_s':>10}{'mean_s':>10}"]
    for cls, st in tt["ttft_by_class"].items():
        rows.append(
            f"{cls:<14}{st['n']:>7}{st['p50_s']:>10.4f}"
            f"{st['p95_s']:>10.4f}{st['mean_s']:>10.4f}"
        )
    if not tt["ttft_by_class"]:
        rows.append("(no ttft histograms — pass a /metrics snapshot)")
    rows += [
        "",
        f"chunk-stamped prefill spans  {tt['chunked_prefill_spans']}",
        f"prompts with chunk attrs     {tt['prompts_with_chunk_attrs']}",
    ]
    if tt["chunks_per_prompt_hist"]:
        rows += ["", f"{'chunks/prompt':<16}{'prompts':>9}"]
        for k, v in tt["chunks_per_prompt_hist"].items():
            rows.append(f"{k:<16}{v:>9}")
    return "\n".join(rows)


def env_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Env-service-plane report: per-operation latency percentiles from
    ``env_reset``/``env_step``/``verify`` spans (client- or worker-side)
    plus the failover picture (``env_replay``/``env_failover`` instants)
    — the first-look answer to "how slow are my environments and how
    often did sessions hop workers"."""
    spans = list(spans)
    ops: Dict[str, List[float]] = {}
    by_addr: Dict[str, List[float]] = {}
    for s in spans:
        name = s.get("name", "")
        if name not in ("env_reset", "env_step", "env_close", "verify"):
            continue
        ops.setdefault(name, []).append(float(s.get("dur", 0.0)))
        if name == "env_step":
            addr = str((s.get("attrs") or {}).get("addr", "?"))
            by_addr.setdefault(addr, []).append(float(s.get("dur", 0.0)))
    replays = [s for s in spans if s.get("name") == "env_replay"]
    failovers = [s for s in spans if s.get("name") == "env_failover"]
    replayed_steps = sum(
        int((s.get("attrs") or {}).get("steps", 0)) for s in replays
    )
    out: Dict[str, Any] = {
        "steps": len(ops.get("env_step", [])),
        "sessions": len({
            s.get("rid", "") for s in spans
            if s.get("name") == "env_reset"
        }),
        "replays": len(replays),
        "replayed_steps": replayed_steps,
        "failovers": len(failovers),
        "ops": {},
        "step_by_worker": {},
    }
    for name, durs in sorted(ops.items()):
        durs.sort()
        out["ops"][name] = {
            "count": len(durs),
            "p50_s": _percentile(durs, 0.50),
            "p95_s": _percentile(durs, 0.95),
            "max_s": durs[-1] if durs else 0.0,
        }
    for addr, durs in sorted(by_addr.items()):
        durs.sort()
        out["step_by_worker"][addr] = {
            "count": len(durs),
            "p50_s": _percentile(durs, 0.50),
            "p95_s": _percentile(durs, 0.95),
        }
    return out


def format_env(ev: Dict[str, Any]) -> str:
    rows = [
        f"sessions             {ev['sessions']}",
        f"env steps            {ev['steps']}",
        f"session replays      {ev['replays']} "
        f"({ev['replayed_steps']} journaled steps re-applied)",
        f"worker failovers     {ev['failovers']}",
        "",
        f"{'op':<14}{'count':>7}{'p50 s':>10}{'p95 s':>10}{'max s':>10}",
    ]
    for name, st in ev["ops"].items():
        rows.append(
            f"{name:<14}{st['count']:>7}{st['p50_s']:>10.4f}"
            f"{st['p95_s']:>10.4f}{st['max_s']:>10.4f}"
        )
    if ev["step_by_worker"]:
        rows += ["", f"{'worker':<24}{'steps':>7}{'p50 s':>10}{'p95 s':>10}"]
        for addr, st in ev["step_by_worker"].items():
            rows.append(
                f"{addr:<24}{st['count']:>7}{st['p50_s']:>10.4f}"
                f"{st['p95_s']:>10.4f}"
            )
    return "\n".join(rows)


def durability_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Trainer-durability report: checkpoint dump/commit latency from
    ``checkpoint_dump``/``checkpoint_commit`` spans plus the episode
    retry/quarantine picture from the executor's instants."""
    spans = list(spans)
    dump_durs = sorted(
        float(s.get("dur", 0.0))
        for s in spans if s.get("name") == "checkpoint_dump"
    )
    commit_durs = sorted(
        float(s.get("dur", 0.0))
        for s in spans if s.get("name") == "checkpoint_commit"
    )
    retries = [s for s in spans if s.get("name") == "episode_retry"]
    quarantines = [s for s in spans if s.get("name") == "quarantine"]
    # histogram of retry ATTEMPT index (attempt=0 is the first re-try):
    # a tall tail means samples are burning their whole budget
    attempt_hist: Dict[str, int] = {}
    for s in retries:
        a = str((s.get("attrs") or {}).get("attempt", "?"))
        attempt_hist[a] = attempt_hist.get(a, 0) + 1
    return {
        "dumps": len(dump_durs),
        "dump_p50_s": _percentile(dump_durs, 0.50),
        "dump_p95_s": _percentile(dump_durs, 0.95),
        "dump_max_s": dump_durs[-1] if dump_durs else 0.0,
        "commit_p50_s": _percentile(commit_durs, 0.50),
        "retries": len(retries),
        "retried_samples": len({s.get("rid", "") for s in retries}),
        # numeric order ("2" before "10"); unparseable attempts last
        "retry_attempt_hist": dict(sorted(
            attempt_hist.items(),
            key=lambda kv: (0, int(kv[0])) if kv[0].isdigit() else (1, 0),
        )),
        "quarantined": len(quarantines),
        "quarantined_samples": sorted(
            {str(s.get("rid", "?")) for s in quarantines}
        ),
    }


def format_durability(du: Dict[str, Any]) -> str:
    rows = [
        f"checkpoint dumps     {du['dumps']}",
        f"dump latency         p50 {du['dump_p50_s'] * 1e3:.1f}ms  "
        f"p95 {du['dump_p95_s'] * 1e3:.1f}ms  "
        f"max {du['dump_max_s'] * 1e3:.1f}ms",
        f"commit latency       p50 {du['commit_p50_s'] * 1e3:.1f}ms",
        f"episode retries      {du['retries']} "
        f"(over {du['retried_samples']} samples)",
        f"quarantined          {du['quarantined']}",
    ]
    if du["retry_attempt_hist"]:
        rows += ["", f"{'retry attempt':<16}{'count':>7}"]
        for attempt, count in du["retry_attempt_hist"].items():
            rows.append(f"{attempt:<16}{count:>7}")
    if du["quarantined_samples"]:
        rows += ["", "quarantined samples:"]
        rows += [f"  {u}" for u in du["quarantined_samples"]]
    return "\n".join(rows)


def load_lineage(path: str) -> List[Dict[str, Any]]:
    """Lineage-ledger JSONL → list of per-sample records."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def lineage_summary(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-sample lineage table + fleet-shape aggregates: how many
    samples migrated mid-generation, how many needed retries, and the
    staleness-at-consumption distribution."""
    rows = []
    staleness: List[int] = []
    # self-play episode plane: requests stamped with agent/role split
    # one episode's story per side (policy handle + versions + turns)
    agents: Dict[str, Dict[str, Any]] = {}
    for r in records:
        rewards = r.get("rewards") or []
        st = r.get("staleness_max")
        if st is not None:
            staleness.append(int(st))
        seen_agents = set()
        for rq in r.get("requests", []):
            agent = str(rq.get("agent", ""))
            if not agent:
                continue
            a = agents.setdefault(
                agent,
                {
                    "agent": agent,
                    "role": str(rq.get("role", "")),
                    "turns": 0,
                    "episodes": 0,
                    "policies": set(),
                    "versions": set(),
                },
            )
            a["turns"] += 1
            pol = str(rq.get("policy", ""))
            if pol:
                a["policies"].add(pol)
            a["versions"].update(
                int(v) for v in rq.get("weight_versions", [])
            )
            if agent not in seen_agents:
                a["episodes"] += 1
                seen_agents.add(agent)
        rows.append(
            {
                "uid": str(r.get("uid", "?")),
                "status": str(r.get("status", "?")),
                "attempts": int(r.get("attempts", 1)),
                "requests": len(r.get("requests", [])),
                "servers": list(r.get("servers", [])),
                "weight_versions": list(r.get("weight_versions", [])),
                "failovers": int(r.get("failovers", 0)),
                "migrations": int(r.get("migrations", 0)),
                "env_failovers": int(r.get("env_failovers", 0)),
                "env_replays": int(r.get("env_replays", 0)),
                "staleness_max": st,
                "consumed_step": r.get("consumed_step"),
                "reward_mean": (
                    round(sum(rewards) / len(rewards), 4)
                    if rewards else None
                ),
            }
        )
    staleness.sort()
    return {
        "samples": len(rows),
        "consumed": sum(
            1 for r in rows if r["consumed_step"] is not None
        ),
        "migrated": sum(1 for r in rows if r["migrations"] > 0),
        "multi_server": sum(1 for r in rows if len(r["servers"]) > 1),
        "multi_version": sum(
            1 for r in rows if len(r["weight_versions"]) > 1
        ),
        "retried": sum(1 for r in rows if r["attempts"] > 1),
        "quarantined": sum(
            1 for r in rows if r["status"] == "quarantined"
        ),
        # env service plane: samples that rode out an env-worker death
        "env_replayed": sum(1 for r in rows if r["env_replays"] > 0),
        "env_failovers": sum(r["env_failovers"] for r in rows),
        "staleness_p50": _percentile(staleness, 0.50),
        "staleness_max": staleness[-1] if staleness else 0,
        "rows": rows,
        "agents": [
            {
                "agent": a["agent"],
                "role": a["role"],
                "turns": a["turns"],
                "episodes": a["episodes"],
                "policies": sorted(a["policies"]),
                "versions": sorted(a["versions"]),
            }
            for _, a in sorted(agents.items())
        ],
    }


def format_lineage(ln: Dict[str, Any]) -> str:
    out = [
        f"samples              {ln['samples']} "
        f"(consumed {ln['consumed']}, quarantined {ln['quarantined']})",
        f"migrated mid-gen     {ln['migrated']} "
        f"(multi-server {ln['multi_server']}, "
        f"multi-version {ln['multi_version']})",
        f"retried episodes     {ln['retried']}",
        f"env sessions replayed {ln['env_replayed']} "
        f"({ln['env_failovers']} env-worker failovers)",
        f"staleness            p50 {ln['staleness_p50']}  "
        f"max {ln['staleness_max']}",
        "",
        f"{'uid':<22}{'st':<4}{'att':>4}{'req':>4}{'srv':>4}"
        f"{'vers':<12}{'mig':>4}{'stale':>6}{'step':>6}{'reward':>8}",
    ]
    for r in ln["rows"]:
        vers = ",".join(str(v) for v in r["weight_versions"]) or "-"
        out.append(
            f"{r['uid'][:21]:<22}{r['status'][:3]:<4}"
            f"{r['attempts']:>4}{r['requests']:>4}"
            f"{len(r['servers']):>4}{vers[:11]:<12}"
            f"{r['migrations']:>4}"
            f"{r['staleness_max'] if r['staleness_max'] is not None else '-':>6}"
            f"{r['consumed_step'] if r['consumed_step'] is not None else '-':>6}"
            f"{r['reward_mean'] if r['reward_mean'] is not None else '-':>8}"
        )
    if ln.get("agents"):
        out += [
            "",
            "per-agent (self-play episodes):",
            f"{'agent':<12}{'role':<12}{'policy':<20}{'vers':<12}"
            f"{'turns':>6}{'eps':>5}",
        ]
        for a in ln["agents"]:
            pol = ",".join(a["policies"]) or "-"
            vers = ",".join(str(v) for v in a["versions"]) or "-"
            out.append(
                f"{a['agent'][:11]:<12}{a['role'][:11]:<12}"
                f"{pol[:19]:<20}{vers[:11]:<12}"
                f"{a['turns']:>6}{a['episodes']:>5}"
            )
    return "\n".join(out)


def _parse_policy_metrics(text: str) -> Dict[str, Any]:
    """Pull the multi-policy serving plane out of a Prometheus
    ``/metrics`` snapshot: the per-policy labeled families the server
    hand-renders (``policy_stable_version{policy="actor"} 12``) plus
    the unlabeled policy-plane aggregates, engine (``areal_tpu_gen_``)
    and router (``areal_tpu_router_``) prefixes both accepted. Returns
    empty maps for non-snapshot input."""
    per: Dict[str, Dict[str, float]] = {}
    agg: Dict[str, float] = {}
    labeled = (
        "policy_stable_version", "policy_canary_version",
        "policy_canary_fraction", "policy_requests_total",
        "policy_tokens_total",
    )
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split()
        if len(parts) != 2:
            continue
        name = parts[0]
        for prefix in ("areal_tpu_gen_", "areal_tpu_router_"):
            if name.startswith(prefix):
                name = name[len(prefix):]
                break
        try:
            value = float(parts[1])
        except ValueError:
            continue
        base, _, label = name.partition("{")
        if label and base in labeled:
            # {policy="actor"} → actor
            pol = label.split('"')[1] if '"' in label else ""
            if pol:
                per.setdefault(pol, {})[base] = value
        elif not label and (
            base.startswith("policy_")
            or base.startswith("qid_affinity_evictions_")
        ):
            agg[base] = value
    return {"per_policy": per, "aggregates": agg}


def load_policy(path: str) -> Dict[str, Any]:
    """Load ``--policy`` input: a ``/metrics`` snapshot (per-policy
    labeled families) or a lineage-ledger JSONL whose request records
    carry the resolved ``policy`` handle. Either kind works; the report
    renders whichever is present."""
    with open(path) as f:
        text = f.read()
    metrics = _parse_policy_metrics(text)
    records: List[Dict[str, Any]] = []
    if not (metrics["per_policy"] or metrics["aggregates"]):
        try:
            records = load_lineage(path)
        except (json.JSONDecodeError, UnicodeDecodeError):
            records = []
    return {"metrics": metrics, "ledger": records}


def policy_summary(data: Dict[str, Any]) -> Dict[str, Any]:
    """Per-policy serving table: registry state (stable/canary versions
    + split fraction) from a /metrics snapshot, and request/TTFT/
    staleness aggregates from the lineage ledger — including the
    OBSERVED per-version request split, the ground truth a canary
    rollout checks its configured fraction against."""
    rows: Dict[str, Dict[str, Any]] = {}

    def row(name: str) -> Dict[str, Any]:
        return rows.setdefault(
            name,
            {
                "policy": name,
                "stable_version": None,
                "canary_version": None,
                "canary_fraction": None,
                "requests": 0,
                "output_tokens": 0,
                "migrations": 0,
                "failovers": 0,
                "ttft_p50_s": None,
                "ttft_p95_s": None,
                "staleness_p50": None,
                "staleness_max": None,
                "version_requests": {},
            },
        )

    for name, fam in sorted(data["metrics"]["per_policy"].items()):
        r = row(name)
        if "policy_stable_version" in fam:
            r["stable_version"] = int(fam["policy_stable_version"])
        cv = fam.get("policy_canary_version")
        if cv is not None and cv >= 0:
            r["canary_version"] = int(cv)
        if "policy_canary_fraction" in fam:
            r["canary_fraction"] = fam["policy_canary_fraction"]
        if "policy_requests_total" in fam:
            r["requests"] = int(fam["policy_requests_total"])
        if "policy_tokens_total" in fam:
            r["output_tokens"] = int(fam["policy_tokens_total"])

    ttfts: Dict[str, List[float]] = {}
    stales: Dict[str, List[int]] = {}
    for rec in data["ledger"]:
        st = rec.get("staleness_max")
        for rq in rec.get("requests", []):
            handle = str(rq.get("policy") or "")
            name = handle.split("@", 1)[0] or "<default>"
            r = row(name)
            r["requests"] += 1
            r["output_tokens"] += int(rq.get("output_tokens", 0))
            r["migrations"] += int(rq.get("migrations", 0))
            r["failovers"] += int(rq.get("failovers", 0))
            if "@v" in handle:
                v = handle.rsplit("@v", 1)[1]
                vr = r["version_requests"]
                vr[v] = vr.get(v, 0) + 1
            if rq.get("ttft_s") is not None:
                ttfts.setdefault(name, []).append(float(rq["ttft_s"]))
            if st is not None:
                stales.setdefault(name, []).append(int(st))
    for name, vals in ttfts.items():
        vals.sort()
        rows[name]["ttft_p50_s"] = round(_percentile(vals, 0.50), 4)
        rows[name]["ttft_p95_s"] = round(_percentile(vals, 0.95), 4)
    for name, vals in stales.items():
        vals.sort()
        rows[name]["staleness_p50"] = _percentile(vals, 0.50)
        rows[name]["staleness_max"] = vals[-1]
    for r in rows.values():
        total = sum(r["version_requests"].values())
        r["split_observed"] = {
            v: round(n / total, 4)
            for v, n in sorted(r["version_requests"].items())
        } if total else {}
    return {
        "policies": [rows[k] for k in sorted(rows)],
        "aggregates": data["metrics"]["aggregates"],
    }


def format_policy(po: Dict[str, Any]) -> str:
    out = [
        f"{'policy':<14}{'stable':>7}{'canary':>7}{'frac':>6}"
        f"{'req':>7}{'tokens':>9}{'mig':>4}{'ttft p50/p95':>14}"
        f"{'stale p50/max':>15}",
    ]
    for r in po["policies"]:
        def fmt(v, nd=2):
            return "-" if v is None else (
                f"{v:.{nd}f}" if isinstance(v, float) else str(v)
            )
        ttft = (
            f"{fmt(r['ttft_p50_s'])}/{fmt(r['ttft_p95_s'])}"
            if r["ttft_p50_s"] is not None else "-"
        )
        stale = (
            f"{fmt(r['staleness_p50'])}/{fmt(r['staleness_max'])}"
            if r["staleness_p50"] is not None else "-"
        )
        out.append(
            f"{r['policy'][:13]:<14}{fmt(r['stable_version']):>7}"
            f"{fmt(r['canary_version']):>7}"
            f"{fmt(r['canary_fraction']):>6}"
            f"{r['requests']:>7}{r['output_tokens']:>9}"
            f"{r['migrations']:>4}{ttft:>14}{stale:>15}"
        )
        if r.get("split_observed"):
            split = "  ".join(
                f"v{v}: {frac:.1%}"
                for v, frac in r["split_observed"].items()
            )
            out.append(f"    observed split   {split}")
    if po["aggregates"]:
        out.append("")
        for k in sorted(po["aggregates"]):
            out.append(f"{k:<38}{po['aggregates'][k]:>10g}")
    return "\n".join(out)


def fleet_summary(manifest: Dict[str, Any]) -> Dict[str, Any]:
    rollup = manifest.get("rollup", {})
    anomalies = manifest.get("anomalies", {})
    servers = manifest.get("servers", {})
    return {
        "servers": {
            a: {
                "reachable": bool(s.get("reachable")),
                "state": s.get("state", "?"),
                "running": s.get("metrics", {}).get(
                    "running_requests", 0.0
                ),
                "decode_tps": s.get("metrics", {}).get(
                    "decode_tokens_per_sec", 0.0
                ),
                "kv_util": s.get("metrics", {}).get(
                    "kv_page_utilization", 0.0
                ),
                "stall_scrapes": s.get("stall_scrapes", 0),
            }
            for a, s in sorted(servers.items())
        },
        "rollup": rollup,
        "anomalies": anomalies,
        "anomalies_active": sorted(
            a for a, v in anomalies.items() if v
        ),
    }


def format_fleet(fl: Dict[str, Any]) -> str:
    r = fl["rollup"]
    out = [
        f"servers              {int(r.get('servers_total', 0))} "
        f"(scraped {int(r.get('servers_scraped', 0))})",
        f"running requests     {r.get('running_requests', 0.0):.0f} "
        f"(queued {r.get('queued_requests', 0.0):.0f})",
        f"decode tok/s         {r.get('decode_tokens_per_sec', 0.0):.1f}",
        f"kv utilization       mean "
        f"{r.get('kv_page_utilization_mean', 0.0) * 100:.1f}%  max "
        f"{r.get('kv_page_utilization_max', 0.0) * 100:.1f}%",
        f"queue wait           p50 "
        f"{r.get('queue_wait_p50_s', 0.0) * 1e3:.1f}ms  p95 "
        f"{r.get('queue_wait_p95_s', 0.0) * 1e3:.1f}ms",
        f"spec accept rate     {r.get('spec_accept_rate', 0.0):.3f}",
        f"dropped trace spans  "
        f"{int(r.get('tracing_dropped_spans_total', 0))}",
        f"anomalies active     {fl['anomalies_active'] or 'none'}",
        "",
        f"{'server':<24}{'up':<4}{'state':<12}{'run':>5}"
        f"{'tok/s':>9}{'kv%':>7}{'stall':>6}",
    ]
    for addr, s in fl["servers"].items():
        out.append(
            f"{addr:<24}{'y' if s['reachable'] else 'n':<4}"
            f"{str(s['state']):<12}{s['running']:>5.0f}"
            f"{s['decode_tps']:>9.1f}{s['kv_util'] * 100:>6.1f}%"
            f"{s['stall_scrapes']:>6}"
        )
    return "\n".join(out)


def load_goodput(path: str) -> Dict[str, List[Dict[str, Any]]]:
    """Read a goodput JSONL stream: ledger snapshots (``kind: goodput``,
    one per export — latest per role wins) and compile events
    (``kind: compile``, one per XLA backend compile). The two kinds may
    share one file or arrive in separate files."""
    snapshots: List[Dict[str, Any]] = []
    compiles: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("kind")
            if kind == "goodput":
                snapshots.append(rec)
            elif kind == "compile":
                compiles.append(rec)
    return {"snapshots": snapshots, "compiles": compiles}


def goodput_summary(
    records: Dict[str, List[Dict[str, Any]]]
) -> Dict[str, Any]:
    """Latest ledger snapshot per role + the per-shape compile bill."""
    latest: Dict[str, Dict[str, Any]] = {}
    for rec in records["snapshots"]:
        latest[rec.get("role", "?")] = rec  # stream order: last wins
    shapes: Dict[tuple, Dict[str, float]] = {}
    for ev in records["compiles"]:
        key = (ev.get("phase", "?"), ev.get("signature", ""))
        agg = shapes.setdefault(key, {"count": 0, "seconds": 0.0})
        agg["count"] += 1
        agg["seconds"] += float(ev.get("duration_s", 0.0))
    shape_rows = [
        {
            "phase": ph, "signature": sig,
            "count": int(v["count"]), "seconds": round(v["seconds"], 3),
        }
        for (ph, sig), v in shapes.items()
    ]
    shape_rows.sort(key=lambda r: -r["seconds"])
    return {
        "roles": latest,
        "compile_events": len(records["compiles"]),
        "compile_seconds": round(
            sum(r["seconds"] for r in shape_rows), 3
        ),
        "shapes": shape_rows,
    }


def format_goodput(gp: Dict[str, Any]) -> str:
    rows: List[str] = []
    for role, snap in sorted(gp["roles"].items()):
        rows.append(
            f"goodput [{role}]  wall={snap.get('wall_s', 0):.1f}s  "
            f"duty={snap.get('duty_cycle', 0):.3f}  "
            f"eff_tok/s={snap.get('effective_tokens_per_sec', 0):.1f}"
        )
        header = f"  {'bucket':<16}{'seconds':>10}{'frac':>8}"
        rows.append(header)
        rows.append("  " + "-" * (len(header) - 2))
        fracs = snap.get("fractions", {})
        for b, secs in sorted(
            snap.get("seconds", {}).items(), key=lambda kv: -kv[1]
        ):
            rows.append(
                f"  {b:<16}{secs:>10.3f}{fracs.get(b, 0.0):>8.4f}"
            )
        total = sum(fracs.values())
        rows.append(f"  {'SUM':<16}{'':>10}{total:>8.4f}")
    if gp["shapes"]:
        rows.append(
            f"compile bill: {gp['compile_events']} compiles, "
            f"{gp['compile_seconds']:.1f}s across {len(gp['shapes'])} "
            f"shapes (most expensive first)"
        )
        header = f"  {'phase':<12}{'signature':<34}{'count':>6}{'sec':>9}"
        rows.append(header)
        rows.append("  " + "-" * (len(header) - 2))
        for r in gp["shapes"][:15]:
            rows.append(
                f"  {r['phase']:<12}{r['signature']:<34}"
                f"{r['count']:>6d}{r['seconds']:>9.3f}"
            )
    return "\n".join(rows)


def load_coldstart(path: str) -> Dict[str, Any]:
    """Read a compile_events JSONL stream (utils/goodput.CompileTracker):
    header (ladder fingerprint + jax version), per-compile lines (phase,
    signature, duration, cached), lifecycle marks (port/ready), and
    precompile summaries."""
    out: Dict[str, Any] = {
        "header": None, "compiles": [], "lifecycle": {},
        "precompile": None,
    }
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("kind")
            if kind == "header" and out["header"] is None:
                out["header"] = rec
            elif kind == "compile":
                out["compiles"].append(rec)
            elif kind == "lifecycle":
                # first occurrence wins: the timeline measures the COLD
                # start, not a later re-warm
                out["lifecycle"].setdefault(rec.get("event"), rec)
            elif kind == "precompile":
                out["precompile"] = rec
    return out


def coldstart_summary(cs: Dict[str, Any]) -> Dict[str, Any]:
    """launch→port→warming→ready timeline + per-shape compile bill +
    persistent-cache hit rate, from one compile_events stream. 'launch'
    is the header timestamp (written at engine construction — the
    earliest mark the stream itself carries)."""
    header = cs["header"] or {}
    t0 = header.get("ts_unix")
    compiles = cs["compiles"]

    def lead(event: str) -> Optional[float]:
        rec = cs["lifecycle"].get(event)
        if rec is None or t0 is None:
            return None
        # clock anchors share one epoch pair; clamp sub-ms skew to 0
        return round(max(0.0, float(rec["ts_unix"]) - float(t0)), 3)

    first_compile = (
        round(float(compiles[0]["ts_unix"]) - float(t0), 3)
        if compiles and t0 is not None
        else None
    )
    cached = sum(1 for c in compiles if c.get("cached"))
    shapes: Dict[tuple, Dict[str, float]] = {}
    for ev in compiles:
        key = (ev.get("phase", "?"), ev.get("signature", ""))
        agg = shapes.setdefault(
            key, {"count": 0, "cached": 0, "seconds": 0.0}
        )
        agg["count"] += 1
        agg["cached"] += 1 if ev.get("cached") else 0
        agg["seconds"] += float(ev.get("duration_s", 0.0))
    shape_rows = [
        {
            "phase": ph, "signature": sig, "count": int(v["count"]),
            "cached": int(v["cached"]), "seconds": round(v["seconds"], 3),
        }
        for (ph, sig), v in shapes.items()
    ]
    shape_rows.sort(key=lambda r: -r["seconds"])
    # set-up by program, from the backend lines' fun_name / backend /
    # trace_s / lower_s (a stream written before those keys existed has no
    # such rows). The stream holds one line a PROGRAM, so this is less than
    # CompileTracker.program_table() holds in the process: no nested rows,
    # and a function that was traced and never compiled (a jitted helper
    # inside another's trace) is nowhere in it, its seconds part of the
    # trace_s of the program that traced it. What the stream has over the
    # log: it outlives the process, and never drops a line
    programs: Dict[str, Dict[str, Any]] = {}
    for ev in compiles:
        if "fun_name" not in ev:
            continue
        row = programs.setdefault(ev["fun_name"], {
            "fun_name": ev["fun_name"], "count": 0, "trace_s": 0.0,
            "lower_s": 0.0, "compiles": 0, "compile_s": 0.0, "loads": 0,
            "load_s": 0.0,
        })
        row["count"] += 1
        row["trace_s"] += float(ev.get("trace_s", 0.0))
        row["lower_s"] += float(ev.get("lower_s", 0.0))
        made = "load" if ev.get("backend") == "cache_load" else "compile"
        row[made + "s"] += 1
        row[made + "_s"] += float(ev.get("duration_s", 0.0))
    program_rows = sorted(
        programs.values(),
        key=lambda r: -(r["trace_s"] + r["lower_s"] + r["compile_s"]
                        + r["load_s"]),
    )
    ready = cs["lifecycle"].get("ready") or {}
    return {
        "fingerprint": header.get("fingerprint"),
        "jax": header.get("jax"),
        "ladder_size": header.get("ladder_size"),
        "port_s": lead("port"),
        "first_compile_s": first_compile,  # warming begins here
        "ready_s": lead("ready"),
        "ready_coverage": ready.get("ladder_coverage"),
        "compiles": len(compiles),
        "cache_hits": cached,
        "cache_hit_rate": round(cached / max(1, len(compiles)), 4),
        "uncached": len(compiles) - cached,
        "compile_seconds": round(
            sum(r["seconds"] for r in shape_rows), 3
        ),
        "precompile": cs["precompile"],
        "shapes": shape_rows,
        "programs": program_rows,
    }


def format_coldstart(cw: Dict[str, Any]) -> str:
    rows = [
        f"coldstart  ladder={cw['ladder_size']}  "
        f"fingerprint={cw['fingerprint']}  jax={cw['jax']}"
    ]
    for label, key in (
        ("port answered", "port_s"),
        ("warming (first compile)", "first_compile_s"),
        ("READY", "ready_s"),
    ):
        v = cw[key]
        rows.append(
            f"  {label:<26}"
            + (f"+{v:.3f}s" if v is not None else "(not reached)")
        )
    rows.append(
        f"  compile bill: {cw['compiles']} compiles "
        f"({cw['cache_hits']} cache hits, {cw['uncached']} uncached, "
        f"hit rate {cw['cache_hit_rate']:.2%}), "
        f"{cw['compile_seconds']:.1f}s total"
    )
    if cw["precompile"]:
        pc = cw["precompile"]
        rows.append(
            f"  precompile[{pc.get('mode')}]: {pc.get('driven')} rungs "
            f"driven in {pc.get('wall_s')}s "
            f"({pc.get('uncached_compiles')} uncached)"
        )
    if cw["shapes"]:
        header = (
            f"  {'phase':<12}{'signature':<34}{'count':>6}"
            f"{'hit':>5}{'sec':>9}"
        )
        rows.append(header)
        rows.append("  " + "-" * (len(header) - 2))
        for r in cw["shapes"][:15]:
            rows.append(
                f"  {r['phase']:<12}{r['signature']:<34}"
                f"{r['count']:>6d}{r['cached']:>5d}{r['seconds']:>9.3f}"
            )
    if cw.get("programs"):
        header = (
            f"  {'program':<28}{'count':>6}{'trace s':>9}{'lower s':>9}"
            f"{'compiled':>9}{'s':>9}{'loaded':>7}{'s':>8}"
        )
        rows.append(header)
        rows.append("  " + "-" * (len(header) - 2))
        for r in cw["programs"][:20]:
            rows.append(
                f"  {r['fun_name'][:27]:<28}{r['count']:>6d}"
                f"{r['trace_s']:>9.3f}{r['lower_s']:>9.3f}"
                f"{r['compiles']:>9d}{r['compile_s']:>9.3f}"
                f"{r['loads']:>7d}{r['load_s']:>8.3f}"
            )
    return "\n".join(rows)


PAUSE_SPAN_NAMES = ("pause_window", "weight_update_pause")


def weights_summary(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Zero-pause weight-plane report (r13). Inputs: client
    ``weight_stream`` spans (one per push, the transfer wall time),
    engine ``weight_stream_chunk`` spans (one per ingested FFD chunk —
    the per-layer stream timeline), ``weight_update`` spans with
    ``cmd="flip"`` + ``weight_flip`` instants (the atomic flip and its
    latency/policy/pin count), and any legacy pause spans. The report
    groups chunks by target version into per-push rows and counts pause
    spans — a streamed-push trace must carry ZERO
    (``--require-zero-pause`` turns that into an exit code)."""
    pushes: Dict[int, Dict[str, Any]] = {}
    flips: List[Dict[str, Any]] = []
    streams: List[float] = []
    pause_spans = 0
    for s in spans:
        name = s.get("name")
        attrs = s.get("attrs") or {}
        if name in PAUSE_SPAN_NAMES:
            pause_spans += 1
        elif name == "weight_stream":
            streams.append(float(s.get("dur", 0.0)))
        elif name == "weight_stream_chunk":
            v = int(attrs.get("model_version", -1))
            p = pushes.setdefault(
                v,
                {
                    "version": v, "chunks": 0, "n_chunks": 0,
                    "bytes": 0, "leaves": 0, "stream_s": 0.0,
                    "t_first": None, "t_last": None, "flip_ms": None,
                    "policy": None, "pinned": None,
                },
            )
            p["chunks"] += 1
            p["n_chunks"] = max(
                p["n_chunks"], int(attrs.get("n_chunks", 0))
            )
            p["bytes"] += int(attrs.get("bytes", 0))
            p["leaves"] += int(attrs.get("leaves", 0))
            p["stream_s"] += float(s.get("dur", 0.0))
            ts = float(s.get("ts", 0.0))
            end = ts + float(s.get("dur", 0.0))
            p["t_first"] = ts if p["t_first"] is None else min(
                p["t_first"], ts
            )
            p["t_last"] = end if p["t_last"] is None else max(
                p["t_last"], end
            )
        elif name == "weight_flip":
            v = int(attrs.get("model_version", -1))
            flips.append(
                {
                    "version": v,
                    "policy": attrs.get("policy"),
                    "pinned": int(attrs.get("pinned", 0)),
                    "flip_ms": float(attrs.get("flip_ms", 0.0)),
                }
            )
            if v in pushes:
                pushes[v]["flip_ms"] = float(attrs.get("flip_ms", 0.0))
                pushes[v]["policy"] = attrs.get("policy")
                pushes[v]["pinned"] = int(attrs.get("pinned", 0))
    rows = []
    for v in sorted(pushes):
        p = pushes[v]
        wall = (
            (p["t_last"] - p["t_first"])
            if p["t_first"] is not None and p["t_last"] is not None
            else 0.0
        )
        p.pop("t_first", None)
        p.pop("t_last", None)
        rows.append({**p, "wall_s": round(wall, 4)})
    streams.sort()
    return {
        "pushes": rows,
        "flips": flips,
        "stream_spans": len(streams),
        "stream_p50_s": round(_percentile(streams, 0.50), 4),
        "stream_max_s": round(streams[-1], 4) if streams else 0.0,
        "pause_spans": pause_spans,
    }


def format_weights(w: Dict[str, Any]) -> str:
    rows = [
        f"weight pushes (chunked)  {len(w['pushes'])}",
        f"flips observed           {len(w['flips'])}",
        f"client stream spans      {w['stream_spans']}"
        + (
            f"  (p50 {w['stream_p50_s']}s, max {w['stream_max_s']}s)"
            if w["stream_spans"]
            else ""
        ),
        f"pause spans              {w['pause_spans']}"
        + ("  <-- NOT zero-pause" if w["pause_spans"] else "  (zero-pause)"),
    ]
    if w["pushes"]:
        header = (
            f"{'version':>8}{'chunks':>8}{'MBytes':>9}{'leaves':>8}"
            f"{'wall_s':>9}{'flip_ms':>9}{'policy':>8}{'pinned':>8}"
        )
        rows += ["", header, "-" * len(header)]
        for p in w["pushes"]:
            rows.append(
                f"{p['version']:>8}{p['chunks']:>8}"
                f"{p['bytes'] / 1e6:>9.2f}{p['leaves']:>8}"
                f"{p['wall_s']:>9.4f}"
                f"{(p['flip_ms'] if p['flip_ms'] is not None else -1):>9.3f}"
                f"{str(p['policy'] or '?'):>8}"
                f"{(p['pinned'] if p['pinned'] is not None else 0):>8}"
            )
    for f in w["flips"]:
        if not any(p["version"] == f["version"] for p in w["pushes"]):
            rows.append(
                f"flip v{f['version']} (no chunk spans): "
                f"policy={f['policy']} pinned={f['pinned']} "
                f"{f['flip_ms']:.3f} ms"
            )
    return "\n".join(rows)


def format_table(summary: Dict[str, Dict[str, float]]) -> str:
    header = (
        f"{'phase':<24}{'count':>7}{'p50_ms':>10}{'p95_ms':>10}"
        f"{'mean_ms':>10}{'max_ms':>10}{'total_s':>9}"
    )
    rows = [header, "-" * len(header)]
    for name, st in summary.items():
        rows.append(
            f"{name:<24}{st['count']:>7d}{st['p50'] * 1e3:>10.2f}"
            f"{st['p95'] * 1e3:>10.2f}{st['mean'] * 1e3:>10.2f}"
            f"{st['max'] * 1e3:>10.2f}{st['total']:>9.3f}"
        )
    return "\n".join(rows)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace", help="span JSONL or Chrome trace JSON file")
    p.add_argument(
        "--json", action="store_true",
        help="emit the summary as JSON instead of a table",
    )
    p.add_argument(
        "--require", default="",
        help="comma-separated span names that MUST be present (CI smoke "
        "check); exit 1 when any is missing",
    )
    p.add_argument(
        "--occupancy", action="store_true",
        help="summarize decode-row occupancy (rows_dispatched vs "
        "rows_active from decode_chunk spans) instead of the latency "
        "table; exit 1 when the trace carries no occupancy gauges",
    )
    p.add_argument(
        "--spec", action="store_true",
        help="summarize speculative decoding (spec_verify instants + "
        "verify decode_chunk spans) instead of the latency table; exit "
        "1 when the trace carries no verify rounds",
    )
    p.add_argument(
        "--cache", action="store_true",
        help="summarize prefix-cache reuse instead of the latency "
        "table: from prefill spans (hit rates + reuse-depth histogram) "
        "or from a /metrics snapshot (per-tier device/host/disk hit "
        "rates, spill/promote volumes, shipping counters); exit 1 when "
        "the input carries neither",
    )
    p.add_argument(
        "--require-min-hit-rate", type=float, default=0.0,
        help="exit 1 when the prefix-cache TOKEN hit rate falls below "
        "this fraction (or the input carries no cache data) — the "
        "cache-effectiveness CI gate (combine with --cache)",
    )
    p.add_argument(
        "--env", action="store_true",
        help="summarize the environment service plane (env_reset/"
        "env_step/verify span latencies + env_replay/env_failover "
        "instants) instead of the latency table; exit 1 when the trace "
        "carries no env spans",
    )
    p.add_argument(
        "--slo", action="store_true",
        help="summarize the SLO traffic plane (per-class queue-wait "
        "percentiles from queue_wait spans, shed/deadline tables) "
        "instead of the latency table; exit 1 when the trace carries "
        "no class-tagged queue_wait spans and no traffic events",
    )
    p.add_argument(
        "--failover", action="store_true",
        help="summarize resilience events (failover/migration spans "
        "from engine/remote.py) instead of the latency table; exit 1 "
        "when the trace carries none",
    )
    p.add_argument(
        "--durability", action="store_true",
        help="summarize trainer durability (checkpoint_dump/commit "
        "spans + episode_retry/quarantine instants) instead of the "
        "latency table; exit 1 when the trace carries none",
    )
    p.add_argument(
        "--lineage", action="store_true",
        help="treat the input as a lineage-ledger JSONL "
        "(WorkflowExecutor per-sample records) and print the "
        "attempt/migration/staleness table; exit 1 when it is empty",
    )
    p.add_argument(
        "--policy", action="store_true",
        help="per-policy serving table (multi-policy plane): registry "
        "state + request/token counts from a /metrics snapshot's "
        "labeled policy families, and/or request/TTFT/staleness "
        "aggregates with the OBSERVED canary split from a lineage "
        "ledger JSONL; exit 1 when the input carries neither",
    )
    p.add_argument(
        "--goodput", action="store_true",
        help="treat the input as a goodput JSONL stream (ledger "
        "snapshots + compile events — utils/goodput.py) and print the "
        "per-role wall-time bucket breakdown + the per-shape compile "
        "bill; exit 1 when the file carries neither",
    )
    p.add_argument(
        "--weights", action="store_true",
        help="summarize the zero-pause weight plane (weight_stream_chunk"
        "/weight_flip/weight_stream spans: per-push chunk timeline, "
        "flip latency, pin counts, pause-span census) instead of the "
        "latency table; exit 1 when the trace carries no weight events",
    )
    p.add_argument(
        "--require-zero-pause", action="store_true",
        help="exit 1 if the trace contains ANY pause_window/"
        "weight_update_pause span — the streamed-push acceptance "
        "invariant (combine with --weights)",
    )
    p.add_argument(
        "--coldstart", action="store_true",
        help="treat the input as a compile_events JSONL stream "
        "(utils/goodput.CompileTracker) and print the launch→port→"
        "warming→ready timeline, the per-shape compile bill, and the "
        "persistent-cache hit rate; exit 1 when the stream has no "
        "header",
    )
    p.add_argument(
        "--require-max-lead", type=float, default=0.0,
        help="exit 1 when the coldstart ready lead exceeds this many "
        "seconds (or ready was never reached) — the seeded scale-up "
        "CI gate (combine with --coldstart)",
    )
    p.add_argument(
        "--fleet", action="store_true",
        help="treat the input as a telemetry-hub run-manifest JSON "
        "(GET /manifest) and print the fleet rollup + anomaly table; "
        "exit 1 when no server was ever scraped",
    )
    p.add_argument(
        "--ttft", action="store_true",
        help="chunked-prefill TTFT report: per-class TTFT p50/p95 from "
        "a /metrics snapshot's native ttft_seconds histograms, and/or "
        "the chunks-per-prompt histogram from chunk-stamped prefill "
        "spans; exit 1 when the input carries neither",
    )
    p.add_argument(
        "--require-max-ttft", type=float, default=0.0,
        help="exit 1 when the gated class's TTFT p95 exceeds this many "
        "seconds (or the class has no histogram) — the bounded-TTFT CI "
        "gate (combine with --ttft; see --ttft-class)",
    )
    p.add_argument(
        "--ttft-class", default="interactive",
        help="scheduling class --require-max-ttft gates on "
        "(default: interactive)",
    )
    args = p.parse_args(argv)
    if args.ttft:
        tt = ttft_summary(load_ttft(args.trace))
        if args.json:
            print(json.dumps(tt, indent=2))
        else:
            print(format_ttft(tt))
        if not tt["ttft_by_class"] and tt["chunked_prefill_spans"] == 0:
            print(
                "no ttft histograms or chunk-stamped prefill spans in "
                "file (pass a /metrics snapshot or a chunked-engine "
                "trace)",
                file=sys.stderr,
            )
            return 1
        if args.require_max_ttft > 0:
            st = tt["ttft_by_class"].get(args.ttft_class)
            if st is None or st["n"] == 0:
                print(
                    f"REQUIRED {args.ttft_class} TTFT p95 <= "
                    f"{args.require_max_ttft}s but the snapshot carries "
                    f"no {args.ttft_class} ttft histogram",
                    file=sys.stderr,
                )
                return 1
            if st["p95_s"] > args.require_max_ttft:
                print(
                    f"REQUIRED {args.ttft_class} TTFT p95 <= "
                    f"{args.require_max_ttft}s, measured {st['p95_s']}s "
                    f"— the chunked-prefill TTFT bound is blown",
                    file=sys.stderr,
                )
                return 1
        return 0
    if args.coldstart:
        cw = coldstart_summary(load_coldstart(args.trace))
        if args.json:
            print(json.dumps(cw, indent=2))
        else:
            print(format_coldstart(cw))
        if cw["fingerprint"] is None:
            # headerless ≠ usable: the timeline anchors on the header
            # timestamp, so a pre-r14 stream full of compile lines
            # still renders a meaningless report — fail it
            print("no compile-events header in file", file=sys.stderr)
            return 1
        if args.require_max_lead > 0:
            if cw["ready_s"] is None:
                print(
                    "REQUIRED ready lead <= "
                    f"{args.require_max_lead}s but the stream carries "
                    "no ready mark",
                    file=sys.stderr,
                )
                return 1
            if cw["ready_s"] > args.require_max_lead:
                print(
                    f"REQUIRED ready lead <= {args.require_max_lead}s, "
                    f"measured {cw['ready_s']}s — cold-start budget "
                    f"blown",
                    file=sys.stderr,
                )
                return 1
        return 0
    if args.goodput:
        gp = goodput_summary(load_goodput(args.trace))
        if args.json:
            print(json.dumps(gp, indent=2))
        else:
            print(format_goodput(gp))
        if not gp["roles"] and not gp["shapes"]:
            print(
                "no goodput snapshots or compile events in file",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.policy:
        po = policy_summary(load_policy(args.trace))
        if args.json:
            print(json.dumps(po, indent=2))
        else:
            print(format_policy(po))
        if not po["policies"] and not po["aggregates"]:
            print(
                "no per-policy metrics or policy-tagged lineage "
                "records in file (pass a /metrics snapshot from a "
                "multi-policy server, or a ledger whose requests "
                "carry a policy handle)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.lineage:
        ln = lineage_summary(load_lineage(args.trace))
        if args.json:
            print(json.dumps(ln, indent=2))
        else:
            print(format_lineage(ln))
        if ln["samples"] == 0:
            print("no lineage records in file", file=sys.stderr)
            return 1
        return 0
    if args.fleet:
        with open(args.trace) as f:
            fl = fleet_summary(json.load(f))
        if args.json:
            print(json.dumps(fl, indent=2))
        else:
            print(format_fleet(fl))
        if not fl["servers"]:
            print("manifest names no servers", file=sys.stderr)
            return 1
        return 0
    if args.cache:
        # like --ttft, --cache accepts a /metrics snapshot — handle it
        # before load_spans (which would choke on Prometheus text)
        data = load_cache(args.trace)
        if data["metrics"]:
            ca = cache_metrics_summary(data["metrics"])
            empty = ca["prompt_tokens"] == 0
            out_str = format_cache_metrics(ca)
        else:
            ca = cache_summary(data["spans"])
            empty = ca["prefill_requests"] == 0
            out_str = format_cache(ca)
        if args.json:
            print(json.dumps(ca, indent=2))
        else:
            print(out_str)
        if empty:
            print(
                "no prefill spans or cache metrics in file (tracing "
                "off, or the engine never admitted a request)",
                file=sys.stderr,
            )
            return 1
        if args.require_min_hit_rate > 0:
            if ca["token_hit_rate"] < args.require_min_hit_rate:
                print(
                    f"REQUIRED token hit rate >= "
                    f"{args.require_min_hit_rate}, measured "
                    f"{ca['token_hit_rate']} — prefix-cache "
                    f"effectiveness below the gate",
                    file=sys.stderr,
                )
                return 1
        return 0
    spans = load_spans(args.trace)
    if args.require_zero_pause:
        n_pause = sum(
            1 for s in spans if s.get("name") in PAUSE_SPAN_NAMES
        )
        if n_pause:
            print(
                f"REQUIRED zero pause spans, found {n_pause} "
                f"({'/'.join(PAUSE_SPAN_NAMES)}) — this push paused "
                f"the fleet",
                file=sys.stderr,
            )
            if not args.weights:
                return 1
    if args.weights:
        w = weights_summary(spans)
        if args.json:
            print(json.dumps(w, indent=2))
        else:
            print(format_weights(w))
        if args.require_zero_pause and w["pause_spans"]:
            return 1
        if (
            not w["pushes"]
            and not w["flips"]
            and w["stream_spans"] == 0
        ):
            print(
                "no weight-plane spans in trace (tracing off, or no "
                "streamed push ran)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.durability:
        du = durability_summary(spans)
        if args.json:
            print(json.dumps(du, indent=2))
        else:
            print(format_durability(du))
        if du["dumps"] == 0 and du["retries"] == 0 and du["quarantined"] == 0:
            print(
                "no durability spans in trace (tracing off, or an "
                "uneventful trainer)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.spec:
        sp = spec_summary(spans)
        if args.json:
            print(json.dumps(sp, indent=2))
        else:
            print(format_spec(sp))
        if sp["verify_rounds"] == 0:
            print(
                "no spec_verify spans in trace (tracing off, or "
                "speculation never engaged)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.env:
        ev = env_summary(spans)
        if args.json:
            print(json.dumps(ev, indent=2))
        else:
            print(format_env(ev))
        if ev["steps"] == 0 and ev["sessions"] == 0:
            print(
                "no env spans in trace (tracing off, or no remote "
                "environments ran)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.slo:
        sl = slo_summary(spans)
        if args.json:
            print(json.dumps(sl, indent=2))
        else:
            print(format_slo(sl))
        if (
            not sl["queue_wait_by_class"]
            and sl["shed_total"] == 0
            and sl["deadline_preemptions"] == 0
            and sl["deadline_misses"] == 0
        ):
            print(
                "no traffic-plane spans in trace (tracing off, or a "
                "pre-r10 engine)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.failover:
        fo = failover_summary(spans)
        if args.json:
            print(json.dumps(fo, indent=2))
        else:
            print(format_failover(fo))
        if fo["failovers"] == 0:
            print(
                "no failover spans in trace (tracing off, or an "
                "uneventful fleet)",
                file=sys.stderr,
            )
            return 1
        return 0
    if args.occupancy:
        occ = occupancy_summary(spans)
        if args.json:
            print(json.dumps(occ, indent=2))
        else:
            print(format_occupancy(occ))
        if occ["chunks"] == 0:
            print(
                "no decode_chunk occupancy spans in trace "
                "(tracing off, or a pre-r6 engine)",
                file=sys.stderr,
            )
            return 1
        return 0
    summary = summarize(spans)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(format_table(summary))
    missing = [
        n for n in args.require.split(",") if n and n not in summary
    ]
    if missing:
        print(f"MISSING required phases: {missing}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
