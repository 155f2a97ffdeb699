"""Device-idle time inside the program's own host spans named in ``spans``
(``areal:<name>``; each idle interval goes to the innermost span open in it),
as a share of the traced window, in %. None where the trace has no such
span at all (a program from before them); 0 where it has and none idled."""

from benchmark import program_trace


def read(spec, obs):
    r = program_trace.of_run(obs)
    if not r or r["window_s"] <= 0 or not r["n_spans"]:
        return None
    s = sum(r["idle_by_span"].get(name, 0.0) for name in spec["spans"])
    return 100.0 * s / r["window_s"]
