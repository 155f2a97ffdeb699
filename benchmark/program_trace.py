"""From a run's own profile to what the PROGRAM's names say about it: device
time per jitted program, per device scope, and every device-idle interval
laid at the door of the program's own host span that was open in it.

``benchmark/xplane.py`` reduces a trace to busy/idle and per-instruction
time under the compiler's names (``fusion.530``), which a recompile renumbers.
The program (``areal_tpu/utils/tracing.py``) gives its work names that survive
one, and this module reads them:

- host spans ``areal:<name>`` (``tracing.span``; a ledger bucket is one too),
  with their attributes as stats of the event;
- programs: one ``XLA Modules`` event per executed program, named
  ``jit_<function>(<program id>)`` — ``grad_step``, ``logp_forward``,
  ``apply_step``, ``_decode_multi_forward`` ...;
- device scopes: ``jax.named_scope`` names (``attention``, ``mlp``, ``lm_head``,
  ``loss``, ``optimizer`` ...) inside an instruction's ``op_name``. On the
  v5e that path is NOT a stat of the ``XLA Ops`` event
  (``jax.profiler.ProfileData`` shows only ``device_offset_ps``,
  ``device_duration_ps``, ``Time Scale Multiplier`` there); it is the stat
  ``tf_op`` of the event's METADATA, beside ``program_id``, ``hlo_category``,
  ``flops`` and ``bytes_accessed``, which ``ProfileData`` does not expose. So
  the metadata table is read from the file itself with a few lines of
  protobuf wire format (``op_names``), and joined to the events through
  (program id of the enclosing ``XLA Modules`` event, instruction text).

Everything is clipped to the benchmark's ``bench:window`` span. A trace of a
program without these names (the parent of the PR that added them) reduces to
empty tables: the readers then return None and the line leaves the metric out.

    python -m benchmark.program_trace <file.xplane.pb or trace dir>

prints the tables PERF.md keeps.
"""

import bisect
import glob
import json
import os
import re
import sys

from benchmark import xplane

PROGRAM_SPAN = "areal:"
NO_SCOPE = "(no scope)"
_LABELS = ("step", "seq", "pad_to")  # span attributes that label, not count
_MODULE = re.compile(r"^jit_(.+?)\((\d+)\)$")

# An event here is (plane, line, name, start_ns, dur_ns, extra): xplane.py's
# tuple plus a dict. For a host span: its stats. For an XLA Modules event:
# {"program": "grad_step", "program_id": n}. For an XLA Ops event:
# {"program": "grad_step", "op_name": "jit(grad_step)/../attention/dot"},
# each where known.


# ------------------------------------------------------------- the file --
def find_run_xplane(root=None):
    """The newest ``.xplane.pb`` under ``benchmark_out/trace/*/`` (a traced
    run removes its cell's directory before it profiles, so the newest is
    the running process's own; ``of_run`` checks that it is)."""
    from benchmark import harness

    root = root or os.path.join(harness.ROOT, "benchmark_out", "trace")
    files = glob.glob(
        os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(files, key=os.path.getmtime) if files else None


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def op_names(path):
    """{(program id, instruction text): op_name} of the device planes, from
    the event metadata's ``tf_op`` stat (tsl ``xplane.proto``: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .uint64 = 3,
    .int64 = 4, .str = 5, .ref = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")
                ).decode()
        if not xplane.DEVICE_PLANE.match(name):
            continue
        wanted = {i for i, n in stat_names.items()
                  if n in ("tf_op", "program_id")}
        for entry in metas:
            meta = next((v for f, v in _fields(entry) if f == 2), None)
            if meta is None:
                continue
            text, found = "", {}
            for f, v in _fields(meta):
                if f == 2:
                    text = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) not in wanted:
                        continue
                    key = stat_names[stat[1]]
                    if 5 in stat:
                        found[key] = bytes(stat[5]).decode()
                    elif 7 in stat:  # an interned string
                        found[key] = stat_names.get(stat[7], "")
                    else:
                        found[key] = stat.get(3, stat.get(4, 0))
            if found.get("tf_op"):
                out[(int(found.get("program_id", 0)), text)] = found["tf_op"]
    return out


def load(path):
    """The device's ``XLA Ops`` / ``XLA Modules`` events and the host's
    ``areal:`` and ``bench:`` spans of one ``.xplane.pb``, as the tuples
    above (instruction text cut to xplane.short_name, scope path in
    ``extra``)."""
    from jax.profiler import ProfileData

    names = op_names(path)
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((PROGRAM_SPAN, xplane.SPAN_PREFIX)):
                        events.append((
                            plane.name, line.name, ev.name, int(ev.start_ns),
                            int(ev.duration_ns), dict(ev.stats),
                        ))
            continue
        lines = {line.name: line for line in plane.lines}
        modules = []
        for ev in getattr(lines.get(xplane.MODULES_LINE), "events", ()):
            m = _MODULE.match(ev.name)
            extra = {"program": m.group(1), "program_id": int(m.group(2))} \
                if m else {"program": ev.name, "program_id": 0}
            modules.append((
                plane.name, xplane.MODULES_LINE, ev.name, int(ev.start_ns),
                int(ev.duration_ns), extra,
            ))
        modules.sort(key=lambda e: e[3])
        starts = [e[3] for e in modules]
        events.extend(modules)
        for ev in getattr(lines.get(xplane.OPS_LINE), "events", ()):
            start = int(ev.start_ns)
            i = bisect.bisect_right(starts, start) - 1
            extra, pid = {}, 0
            if i >= 0 and start < modules[i][3] + modules[i][4]:
                pid = modules[i][5]["program_id"]
                extra["program"] = modules[i][5]["program"]
            op_name = names.get((pid, ev.name))
            if op_name:
                extra["op_name"] = op_name
            events.append((
                plane.name, xplane.OPS_LINE, xplane.short_name(ev.name),
                start, int(ev.duration_ns), extra,
            ))
    return events


def write_events(events, path):
    """A trimmed trace as text (what the tests keep): xplane.py's five
    columns and the extra dict as JSON."""
    with open(path, "w") as f:
        for plane, line, name, start, dur, extra in events:
            f.write(f"{plane}\t{line}\t{name}\t{start}\t{dur}\t"
                    f"{json.dumps(extra, sort_keys=True)}\n")


def read_events(path):
    out = []
    with open(path) as f:
        for row in f:
            plane, line, name, start, dur, extra = row.rstrip("\n").split("\t")
            out.append((plane, line, name, int(start), int(dur),
                        json.loads(extra)))
    return out


# -------------------------------------------------------- the reduction --
_WRAPPED = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")


def device_scopes():
    """The program's own vocabulary of device scopes; empty for a program
    that has none yet, whose operations then all read as unscoped."""
    from areal_tpu.utils import tracing

    return getattr(tracing, "DEVICE_SCOPES", ())


def scope_of(op_name, scopes=None):
    """The innermost device scope on an instruction's ``op_name`` path
    (``jit(grad_step)/transpose(jvp(loss))/while/body/attention/dot`` is
    ``attention``): path components are compared whole, a transformation's
    wrapper (``jvp(..)``, ``transpose(..)``) is looked through, a jitted
    function's own name (``jit(..)``) is not a scope. ``scopes``: the
    vocabulary, ``device_scopes()`` unless given."""
    if scopes is None:
        scopes = device_scopes()
    for part in reversed((op_name or "").split("/")):
        if part.startswith(("jit(", "pjit(")):
            continue
        for word in reversed(_WRAPPED.findall(part)):
            if word in scopes:
                return word
    return None


def direction_of(op_name):
    """``bwd`` for the transposed half of a gradient, ``remat`` for a
    forward recomputed inside it, else ``fwd``."""
    if "rematted_computation" in op_name or "checkpoint/remat" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def _innermost_segments(spans):
    """[(t0, t1, index of the innermost span open in it)] over the union of
    ``spans`` (``(start, end)`` pairs): the innermost is the shortest open
    one, whichever thread it is on."""
    edges = sorted(
        [(s, 1, i) for i, (s, e) in enumerate(spans) if e > s]
        + [(e, 0, i) for i, (s, e) in enumerate(spans) if e > s]
    )
    segments, open_, last = [], set(), None
    for t, opening, i in edges:
        if open_ and t > last:
            inner = min(open_, key=lambda j: spans[j][1] - spans[j][0])
            segments.append((last, t, inner))
        (open_.add if opening else open_.discard)(i)
        last = t
    return segments


def reduce(events, window=None):
    """See the module's docstring. ``window`` = (start_ns, end_ns), default
    the ``bench:window`` span, else first to last device operation."""
    dev = [e for e in events if xplane.DEVICE_PLANE.match(e[0])]
    ops = [e for e in dev if e[1] == xplane.OPS_LINE]
    if window is None:
        window = xplane.span_window([e[:5] for e in events])
    if window is None and ops:
        window = (min(e[3] for e in ops), max(e[3] + e[4] for e in ops))
    empty = {"window_s": 0.0, "busy_s": 0.0, "idle_s": 0.0, "devices": 0,
             "programs": {}, "scopes": {}, "scope_directions": {},
             "scoped_ops": 0, "program_scopes": {}, "idle_by_span": {},
             "idle_in_leaf_s": 0.0,
             "spans": {}, "n_spans": 0}
    if window is None:
        return empty
    lo, hi = window
    planes = sorted({e[0] for e in ops})
    n_dev = max(1, len(planes))

    programs = {}
    for e in dev:
        if e[1] != xplane.MODULES_LINE:
            continue
        s, t = max(e[3], lo), min(e[3] + e[4], hi)
        if t > s:
            p = programs.setdefault(
                e[5]["program"], {"seconds": 0.0, "runs": 0}
            )
            p["seconds"] += (t - s) / 1e9 / n_dev
            p["runs"] += 1

    scopes, directions, scoped_ops = {}, {}, 0
    by_program = {}  # program -> {scope: seconds of its operations}
    per_device = {}
    vocabulary = device_scopes()
    for plane, _line, name, start, dur, extra in ops:
        s, t = max(start, lo), min(start + dur, hi)
        if t <= s:
            continue
        per_device.setdefault(plane, []).append((s, t))
        if xplane.is_container(name):
            continue
        op_name = extra.get("op_name")
        scope = scope_of(op_name, vocabulary)
        scoped_ops += scope is not None
        key = scope or NO_SCOPE
        sec = (t - s) / 1e9 / n_dev
        scopes[key] = scopes.get(key, 0.0) + sec
        mine = by_program.setdefault(extra.get("program"), {})
        mine[key] = mine.get(key, 0.0) + sec
        if op_name:
            d = (key, direction_of(op_name))
            directions[d] = directions.get(d, 0.0) + sec

    busy, gaps = 0.0, []
    for intervals in per_device.values():
        merged = xplane._union(intervals)
        busy += sum(t - s for s, t in merged) / 1e9 / n_dev
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort()

    host = [e for e in events if e[2].startswith(PROGRAM_SPAN)
            and e[3] < hi and e[3] + e[4] > lo]
    pairs = [(e[3], e[3] + e[4]) for e in host]
    names = [e[2][len(PROGRAM_SPAN):] for e in host]
    # the tree, per thread: a span's children are the spans inside it on
    # its own line; self time is what they leave
    child_ns = [0] * len(host)
    by_line = {}
    for i, e in enumerate(host):
        by_line.setdefault((e[0], e[1]), []).append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (pairs[i][0], -pairs[i][1]))
        stack = []
        for i in idx:
            while stack and pairs[stack[-1]][1] <= pairs[i][0]:
                stack.pop()
            if stack:
                child_ns[stack[-1]] += pairs[i][1] - pairs[i][0]
            stack.append(i)
    idle_by_span, idle_leaf = {}, 0.0
    segments = _innermost_segments(pairs)
    seg_starts = [s[0] for s in segments]
    for g0, g1 in gaps:
        covered = 0
        k = max(0, bisect.bisect_right(seg_starts, g0) - 1)
        while k < len(segments) and segments[k][0] < g1:
            s0, s1, i = segments[k]
            over = min(g1, s1) - max(g0, s0)
            if over > 0:
                sec = over / 1e9 / n_dev
                idle_by_span[names[i]] = idle_by_span.get(names[i], 0.0) + sec
                idle_leaf += sec if not child_ns[i] else 0.0
                covered += over
            k += 1
        if g1 - g0 > covered:
            idle_by_span["(no span)"] = idle_by_span.get("(no span)", 0.0) \
                + (g1 - g0 - covered) / 1e9 / n_dev
    spans = {}
    for i, e in enumerate(host):
        row = spans.setdefault(
            names[i], {"count": 0, "seconds": 0.0, "self_s": 0.0, "stats": {}}
        )
        row["count"] += 1
        row["seconds"] += e[4] / 1e9
        row["self_s"] += (e[4] - child_ns[i]) / 1e9
        for k, v in e[5].items():
            if isinstance(v, (int, float)) and k not in _LABELS:
                row["stats"][k] = row["stats"].get(k, 0) + v
    for name, sec in idle_by_span.items():
        if name in spans:
            spans[name]["idle_s"] = sec
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s, "busy_s": busy, "idle_s": window_s - busy,
        "devices": len(planes), "programs": programs, "scopes": scopes,
        "scope_directions": directions, "scoped_ops": scoped_ops,
        "program_scopes": by_program,
        "idle_by_span": idle_by_span, "idle_in_leaf_s": idle_leaf,
        "spans": spans, "n_spans": len(host),
    }


def scoped_share(r, program):
    """The share of a program's operation time that is under any device
    scope: about 0.9 for one compiled with them, 0 for one compiled before
    them (by an older program, or loaded from a persistent-cache entry that
    one wrote)."""
    mine = r["program_scopes"].get(program, {})
    total = sum(mine.values())
    return (total - mine.get(NO_SCOPE, 0.0)) / total if total else 0.0


_RUN = {}  # path -> reduction: one parse a process, whatever the readers ask


def of_run(obs):
    """The reduction of the traced run ``obs`` belongs to (the readers'
    entry): None where the run was not traced, wrote no profile, or ran
    nothing on a device."""
    if not obs.trace:
        return None
    path = find_run_xplane()
    if path is None:
        return None
    if path not in _RUN:
        _RUN[path] = reduce(load(path))
    r = _RUN[path]
    # no operation on a device (a rehearsal on the CPU): nothing to share
    # out. And the file has to be the one obs.trace was reduced from: the
    # same bench:window span, to the nanosecond
    if not r["devices"] or abs(r["window_s"] - obs.trace["window_s"]) > 1e-9:
        return None
    return r


# ------------------------------------------------------------ the tables --
def tables(r):
    w = r["window_s"] or 1.0
    out = [f"window {r['window_s']:.3f} s, device busy {r['busy_s']:.3f} s, "
           f"idle {r['idle_s']:.3f} s ({100 * r['idle_s'] / w:.2f}%), "
           f"{r['devices']} device(s), {r['n_spans']} areal: spans"]
    out.append("\nprogram                          runs   seconds   % window"
               "  % of its operations' time under a scope")
    for name, p in sorted(r["programs"].items(),
                          key=lambda kv: -kv[1]["seconds"])[:16]:
        out.append(f"{name:32s} {p['runs']:4d} {p['seconds']:9.3f} "
                   f"{100 * p['seconds'] / w:9.2f} "
                   f"{100 * scoped_share(r, name):9.1f}")
    out.append("\nscope          seconds  % window      fwd    remat      bwd")
    for name, sec in sorted(r["scopes"].items(), key=lambda kv: -kv[1]):
        d = r["scope_directions"]
        out.append(
            f"{name:12s} {sec:9.3f} {100 * sec / w:9.2f} "
            + " ".join(f"{d.get((name, k), 0.0):8.3f}"
                       for k in ("fwd", "remat", "bwd"))
        )
    out.append("\nhost span                  count   seconds    self_s "
               "device-idle_s  stats")
    for name, s in sorted(r["spans"].items(), key=lambda kv: -kv[1]["seconds"]):
        out.append(f"{name:26s} {s['count']:5d} {s['seconds']:9.3f} "
                   f"{s['self_s']:9.3f} {s.get('idle_s', 0.0):13.4f}  "
                   f"{json.dumps(s['stats']) if s['stats'] else ''}")
    idle = r["idle_s"] or 1.0
    out.append("\ndevice idle by innermost span (s, % of idle):")
    for name, sec in sorted(r["idle_by_span"].items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:26s} {sec:9.4f} {100 * sec / idle:7.2f}")
    out.append(f"  in a leaf span: {r['idle_in_leaf_s']:.4f} s "
               f"({100 * r['idle_in_leaf_s'] / idle:.1f}% of idle)")
    return "\n".join(out)


def main(argv):
    if not argv:
        raise SystemExit(__doc__)
    path = argv[0]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    events = load(path)
    print(tables(reduce(events)))
    if len(argv) >= 4 and argv[1] == "--record":
        # --record <out.tsv> <from_ms>,<to_ms>: a slice of the window, times
        # rebased to its start, for tests/benchmark_suite
        lo = xplane.span_window([e[:5] for e in events])[0]
        a, b = (lo + int(float(x) * 1e6) for x in argv[3].split(","))
        keep = [
            (p, l, n, max(s, a) - a, min(s + d, b) - max(s, a), x)
            for p, l, n, s, d, x in events if s < b and s + d > a
        ]
        write_events(sorted(keep, key=lambda e: (e[0], e[1], e[3])), argv[2])
        print(f"recorded {len(keep)} events to {argv[2]}")


if __name__ == "__main__":
    main(sys.argv[1:])
