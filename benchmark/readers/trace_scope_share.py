"""Device time of the operations of the programs named in ``programs`` that
run under the device scopes named in ``scopes`` (``jax.named_scope`` names on
the instruction's ``op_name``, innermost scope wins), as a share of the
traced window, in %. None unless every one of those programs ran in the
window with most of its operation time under some scope: a program from
before the scopes, or one loaded from a persistent-cache entry that such a
program wrote, carries none, and a share over the rest would read low."""

from benchmark import program_trace


def read(spec, obs):
    r = program_trace.of_run(obs)
    if not r or r["window_s"] <= 0:
        return None
    if any(program_trace.scoped_share(r, p) < 0.5 for p in spec["programs"]):
        return None
    s = sum(
        r["program_scopes"][p].get(name, 0.0)
        for p in spec["programs"] for name in spec["scopes"]
    )
    return 100.0 * s / r["window_s"]
