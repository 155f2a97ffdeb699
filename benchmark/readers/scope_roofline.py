"""A device scope's share of its roofline, in %: the least time the chip
could take for the operations (or bytes) the traced steps needed, over the
device time of the operations of the programs named in ``programs`` that run
under the device scopes named in ``scopes`` (as ``trace_scope_share`` reads
them: innermost scope wins). For work that ``trace_roofline`` cannot single
out by operation name: two attention cores that are both ``splash_mqa_*``
kernels, one under each scope. ``bound`` says which peak. None where the
program has no such scope or the run counted nothing to need."""

from benchmark import program_trace


def read(spec, obs):
    r = program_trace.of_run(obs)
    need = obs.values.get(spec["need"])
    if not r or need is None or obs.peaks is None:
        return None
    if any(program_trace.scoped_share(r, p) < 0.5 for p in spec["programs"]):
        return None
    s = sum(
        r["program_scopes"][p].get(name, 0.0)
        for p in spec["programs"] for name in spec["scopes"]
    )
    if s <= 0:
        return None
    peak = obs.peaks[
        "bytes_per_s" if spec["bound"] == "bytes" else "flops_per_s"
    ]
    return 100.0 * (need / peak) / s
