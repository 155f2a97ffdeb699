"""From a profiler trace (``.xplane.pb``) to busy/idle share, per-op device
time and a list of idle gaps.

Two halves. ``load`` turns the file into plain tuples with nothing but
``jax.profiler.ProfileData``; everything after it (``reduce``) works on those
tuples, so the arithmetic is checked on a hand-built list and on one small
recorded trace (``tests/benchmark_suite``).

An event is ``(plane, line, name, start_ns, dur_ns)``. Device planes are the
ones named ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event
per executed operation (kernels and fusions, under the names the compiler
gave them) and ``XLA Modules`` one per executed program. Host planes hold the
threads; the benchmark's own ``jax.profiler.TraceAnnotation`` spans are the
events there whose names start with ``bench:``.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"  # harness.TracedWindow's own span


def find_xplane(trace_dir):
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output directory."""
    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path, keep_lines=(OPS_LINE, MODULES_LINE)):
    """Device events of ``keep_lines`` and the host's ``bench:`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name not in keep_lines:
                continue
            for ev in line.events:
                name = ev.name
                if not on_device and not name.startswith(SPAN_PREFIX):
                    continue
                if on_device:
                    name = short_name(name)
                events.append((
                    plane.name, line.name, name,
                    int(ev.start_ns), int(ev.duration_ns),
                ))
    return events


def short_name(name):
    """The trace names a device operation by its whole HLO line
    (``%copy.3 = bf16[28,2,262144,128]{3,2,1,0:T(8,128)} copy(...)``). Kept:
    the instruction's own name and, for an array result, its type and shape
    (``copy.3 bf16[28,2,262144,128]``): operand text would make a pattern
    for one kernel match every operation that consumes its output."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name.lstrip("%")
    head = head.strip().lstrip("%")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} {shape}" if shape and not shape.startswith("(") else head


def is_container(name):
    """Control flow that wraps other operations of the same line (a while
    loop over the layers): its time is its children's, counted there."""
    return name.startswith(("while", "conditional", "call"))


def write_events(events, path):
    """A trimmed trace as text, one event a line (what the tests keep)."""
    with open(path, "w") as f:
        for plane, line, name, start, dur in events:
            f.write(f"{plane}\t{line}\t{name}\t{start}\t{dur}\n")


def read_events(path):
    out = []
    with open(path) as f:
        for row in f:
            plane, line, name, start, dur = row.rstrip("\n").split("\t")
            out.append((plane, line, name, int(start), int(dur)))
    return out


def _union(intervals):
    """Merged, sorted, non-overlapping [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def span_window(events, name=WINDOW_SPAN):
    """(start_ns, end_ns) of the host span ``name``: the traced window as the
    benchmark drew it, idle at its edges included. None without that span."""
    found = [(e[3], e[3] + e[4]) for e in events if e[2] == name]
    if not found:
        return None
    return min(s for s, _ in found), max(e for _, e in found)


def reduce(events, window=None, top=10):
    """Busy and idle seconds, per-op seconds and the longest idle gaps.

    ``window`` = (start_ns, end_ns) restricts everything to that interval
    (a run passes ``span_window``); without one it runs from the first to
    the last device operation, which leaves out idle at the edges. Busy time
    is the union of the ``XLA Ops`` intervals, per device, averaged over the
    devices that ran anything. A gap is an interval with no operation on a
    device; it is attributed to the ``bench:`` host span that covers most of
    it, else ``unattributed``.
    """
    ops = [e for e in events if DEVICE_PLANE.match(e[0]) and e[1] == OPS_LINE]
    spans = [e for e in events if e[2].startswith(SPAN_PREFIX)]
    if not ops:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0,
                "op_seconds": {}, "gaps": [], "n_ops": 0}
    if window is None:
        window = (
            min(e[3] for e in ops), max(e[3] + e[4] for e in ops)
        )
    lo, hi = window
    per_device = {}
    op_seconds = {}
    n_ops = 0
    for plane, _line, name, start, dur in ops:
        s, e = _clip(start, start + dur, lo, hi)
        if e <= s:
            continue
        n_ops += 1
        per_device.setdefault(plane, []).append((s, e))
        if not is_container(name):
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
    busy, gaps = [], []
    for plane, intervals in per_device.items():
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i], edges[i + 1]))
    n_dev = max(1, len(per_device))
    by_cause = {}
    for s, e in gaps:
        cause = _covering_span(spans, s, e)
        by_cause[cause] = by_cause.get(cause, 0.0) + (e - s) / 1e9 / n_dev
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev,
        "devices": len(per_device),
        "n_ops": n_ops,
        "op_seconds": {k: v / n_dev for k, v in op_seconds.items()},
        "gaps": sorted(by_cause.items(), key=lambda kv: -kv[1])[:top],
        "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9,
    }


def _covering_span(spans, s, e):
    """The innermost (shortest) host span that covers at least half of the
    gap [s, e)."""
    best, best_dur = "unattributed", None
    for _plane, _line, name, start, dur in spans:
        overlap = min(e, start + dur) - max(s, start)
        if overlap * 2 >= (e - s) and (best_dur is None or dur < best_dur):
            best, best_dur = name[len(SPAN_PREFIX):], dur
    return best


def matching_seconds(op_seconds, pattern):
    """Device seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in op_seconds.items() if rx.search(k))


def top_ops(op_seconds, top=10):
    return [
        [k, v] for k, v in
        sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
    ]
