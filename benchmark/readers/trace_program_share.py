"""Device time of one jitted program (its ``XLA Modules`` events, under the
program's own function name: ``grad_step``, ``logp_forward``, ``apply_step``)
as a share of the traced window, in %."""

from benchmark import program_trace


def read(spec, obs):
    r = program_trace.of_run(obs)
    if not r or r["window_s"] <= 0:
        return None
    p = r["programs"].get(spec["program"])
    return None if p is None else 100.0 * p["seconds"] / r["window_s"]
