"""Runs and device time of the trainer's two forward programs by ROW LENGTH,
from a traced run of a trainer cell:

    python -m tools.trace_rungs <file.xplane.pb or trace dir>

Since PR 30 every microbatch is dispatched at its own row length (a rung of
``utils/data.ROW_QUANTUM``), and ``python -m benchmark.program_trace`` sums a
program over all its shapes. Each ``areal:train.pack`` span carries the row
length it packed to (``pad_to``) and is followed by exactly one run of
``grad_step`` or ``logp_forward``; the device runs them in the order the
host dispatched them, so the k-th pack names the k-th run's row length. The
seconds are the mean over the device planes, as everywhere in PERF.md. The
last column is what the ``train.grad_dispatch`` spans of a row length say of
their cores' backward (``attn_bwd_full``, ``attn_bwd_window``, ``bd_bwd``:
which rungs took which form).
"""

import bisect
import os
import sys

from benchmark import program_trace, xplane

PROGRAMS = ("grad_step", "logp_forward")
FORMS = ("attn_bwd_full", "attn_bwd_window", "bd_bwd")


def _packs(events):
    """(start, row length) of every ``train.pack`` span, in time order."""
    return sorted(
        (e[3], int(e[5].get("pad_to", 0))) for e in events
        if e[2] == program_trace.PROGRAM_SPAN + "train.pack"
    )


def by_row_length(events):
    """{(program, row length): [runs, device seconds]} of one trace."""
    packs = _packs(events)
    planes = {}
    for e in events:
        if (xplane.DEVICE_PLANE.match(e[0]) and e[1] == xplane.MODULES_LINE
                and e[5].get("program") in PROGRAMS):
            planes.setdefault(e[0], []).append(e)
    out = {}
    for runs in planes.values():
        runs.sort(key=lambda e: e[3])
        if len(runs) != len(packs):
            raise SystemExit(
                f"{len(packs)} train.pack spans against {len(runs)} runs of "
                f"{PROGRAMS} on a device: not a whole traced window"
            )
        for (_, row_len), run in zip(packs, runs):
            cell = out.setdefault((run[5]["program"], row_len), [0, 0.0])
            cell[0] += 1
            cell[1] += run[4] / 1e9
    for cell in out.values():
        cell[0] //= len(planes)
        cell[1] /= len(planes)
    return out


def backward_forms(events):
    """{row length: what its grad dispatch spans said of a core's backward},
    each ``train.grad_dispatch`` span under the row length of the pack
    before it."""
    packs = _packs(events)
    starts = [at for at, _ in packs]
    said = {}
    for e in events:
        if e[2] != program_trace.PROGRAM_SPAN + "train.grad_dispatch":
            continue
        k = bisect.bisect_right(starts, e[3]) - 1
        if k >= 0:
            said.setdefault(packs[k][1], set()).update(
                f"{name}={e[5][name]}" for name in FORMS if e[5].get(name)
            )
    return {row_len: " ".join(sorted(forms)) for row_len, forms in said.items()}


def main(argv):
    if not argv:
        raise SystemExit(__doc__)
    path = argv[0]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    events = program_trace.load(path)
    table, forms = by_row_length(events), backward_forms(events)
    total = sum(sec for _, sec in table.values())
    print(f"{'program':<14}{'row length':>11}{'runs':>6}{'seconds':>10}"
          f"{'ms a run':>10}{'% of both':>11}  backward")
    for (program, row_len), (runs, sec) in sorted(table.items()):
        said = forms.get(row_len, "") if program == "grad_step" else ""
        print(f"{program:<14}{row_len:>11}{runs:>6}{sec:>10.3f}"
              f"{1e3 * sec / runs:>10.1f}{100 * sec / total:>11.2f}  {said}")


if __name__ == "__main__":
    main(sys.argv[1:])
