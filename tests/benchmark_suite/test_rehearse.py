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
    """BENCHMARK.json, or the tests' list of cells not yet proven."""
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        proven = {w["name"] for w in json.load(f)["workloads"]}
    return [] if cell in proven else [
        "--bench", os.path.join(HERE, "unproven_cells.json")
    ]

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _rehearse(capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                   "--seconds", "3", "--trace", str(trace), "--rehearse"]
                  + _bench_of(cell))
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and len(lines) >= 2
    return json.loads(lines[-2]), json.loads(lines[-1])


@pytest.mark.parametrize("cell,metric", [
    ("rollout_1p5b_reason", "gen_tokens_per_s"),
    ("train_0p5b_grpo", "trained_tokens_per_s"),
    ("serve_0p5b_chat", "ttft_p95_ms"),
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
    assert "busy_s" in line["device"] or "breakdown" not in line


def test_refuses_to_measure_off_the_chip(capsys):
    rc = run.main(["--workload", "train_0p5b_grpo", "--seed", "1",
                   "--seconds", "1", "--trace", "0"]
                  + _bench_of("train_0p5b_grpo"))
    out = capsys.readouterr()
    assert rc != 0 and not any(l.startswith("{") for l in out.out.splitlines())
    assert "not a TPU" in out.err
