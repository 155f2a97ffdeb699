"""One --rehearse run of each traffic kind at the program's tiny test
configuration, ending in the contract's last line. A later PR that renames
what the benchmark calls into fails here, not on the chip. All in one file,
so one xdist worker pays for the engines and the trainer."""

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))


def _bench_of(cell):
    """None for a cell of BENCHMARK.json, else the tests' list of cells that
    are built but not yet proven, as a dict of the same form."""
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        if cell in {w["name"] for w in json.load(f)["workloads"]}:
            return None
    with open(os.path.join(HERE, "unproven_cells.json")) as f:
        return json.load(f)


KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _rehearse(capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                   "--seconds", "3", "--trace", str(trace), "--rehearse"],
                  bench=_bench_of(cell))
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and len(lines) >= 2
    return json.loads(lines[-2]), json.loads(lines[-1])


@pytest.mark.parametrize("cell,metric", [
    ("rollout_1p5b_reason", "gen_tokens_per_s"),
    ("train_0p5b_grpo", "trained_tokens_per_s"),
])
def test_rehearsal_ends_in_the_contracts_line(capsys, cell, metric):
    notes, line = _rehearse(capsys, cell, trace=0)
    assert KEYS <= set(line) and "rehearsal" in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {metric, "setup_s"}
    for m in line["metrics"].values():
        # a rate may read 0 here: a CPU compile can fill a 3 s window
        assert m["value"] >= 0 and isinstance(m["unit"], str)
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    # the plain reference agrees with the program at float32, and every
    # request or step came back whole (a compile in a 3 s CPU window, which
    # can also leave it without a token, is the chip's business: the tiny
    # geometry is not what warm-up is written for)
    checks = {k: v for k, v in notes["checks"].items()
              if k not in ("no_compile_in_window", "generated_in_window")}
    assert checks and all(checks.values()), notes
    assert notes["notes"]["reference"]["max_abs_diff"] < 1e-4


def test_traced_rehearsal_reports_per_layer_metrics(capsys):
    notes, line = _rehearse(capsys, "train_0p5b_grpo", trace=1)
    assert KEYS <= set(line)
    assert {"train_mfu", "pad_share.train"} & set(line["metrics"]) == {
        "pad_share.train"
    }  # no peak table off the chip, so no MFU; padding is a count
    assert 0 <= line["metrics"]["pad_share.train"]["value"] < 100
    # whole passes: both batches, each once, however short the window
    assert notes["notes"]["steps"] == 2 * notes["notes"]["passes"] >= 2
    assert "busy_s" in line["device"] or "breakdown" not in line


def test_refuses_to_measure_off_the_chip(capsys):
    rc = run.main(["--workload", "train_0p5b_grpo", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and not any(l.startswith("{") for l in out.out.splitlines())
    assert "not a TPU" in out.err


def test_traced_window_is_its_own_span_in_the_profilers_trace(tmp_path):
    import time

    from benchmark import harness, xplane

    traced = harness.TracedWindow(True, str(tmp_path))
    with traced:
        time.sleep(0.02)  # idle before the first call still counts
        with harness.span("wait"):
            time.sleep(0.05)
    events = xplane.load(xplane.find_xplane(str(tmp_path)))
    lo, hi = xplane.span_window(events)
    assert 0.07 <= (hi - lo) / 1e9 <= traced.host_s + 0.01
    wait = [e for e in events if e[2] == "bench:wait"]
    assert len(wait) == 1 and lo <= wait[0][3] and wait[0][3] + wait[0][4] <= hi
    # no device operation on the CPU: nothing to reduce, and it says so
    assert traced.reduce()["busy_s"] == 0.0
