"""Set-up from inside (``benchmark/readers/setup_phase.py``): a traced
rehearsal's line carries the six ``setup_*`` metrics, the five in seconds
account for ``setup_s``, the count is the runner's own watch's; and the
reader finds the window's start in the runner that is RUNNING."""

import json
import sys
import types

import pytest

from benchmark import harness, run
from benchmark.readers import setup_phase

SIX = ["setup_trace_s.train", "setup_lower_s.train", "setup_compile_s.train",
       "setup_cache_load_s.train", "setup_programs.train",
       "setup_rest_s.train"]


def test_traced_rehearsal_accounts_for_setup_s(capsys):
    from areal_tpu.utils import goodput

    # a process of its own on the chip; here other tests' trainers have
    # written into the singleton's log
    goodput.reset_trainer_ledger()
    rc = run.main(["--workload", "train_0p5b_grpo", "--seed",
                   str(2 ** 31 + 36), "--seconds", "2", "--trace", "1",
                   "--rehearse"])
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert rc == 0 and len(lines) >= 2
    notes, line = json.loads(lines[-2]), json.loads(lines[-1])
    got = {k: line["metrics"][k] for k in SIX}  # all six, or a KeyError
    assert all(m["value"] >= 0 for m in got.values())
    assert [got[k]["unit"] for k in SIX] == ["s"] * 4 + ["programs", "s"]
    in_seconds = sum(got[k]["value"] for k in SIX if k != "setup_programs.train")
    assert in_seconds == pytest.approx(notes["setup_s"], abs=1e-6)
    # entries after the window (the reference's programs) do not count: the
    # runner's own watch stopped counting at the same moment
    watch = notes["setup_programs"]
    assert got["setup_programs.train"]["value"] == watch["programs"] > 0
    assert (got["setup_compile_s.train"]["value"]
            + got["setup_cache_load_s.train"]["value"]
            ) == pytest.approx(watch["compile_seconds"], rel=0.01)
    assert got["setup_trace_s.train"]["value"] > 0
    assert got["setup_lower_s.train"]["value"] > 0
    tracker = goodput.trainer_tracker()
    table = {r["fun_name"] for r in tracker.program_table()}
    assert {"init_params", "grad_step", "logp_forward", "apply_step"} <= table
    # the record costs the window nothing: no event of any kind reaches the
    # listener between the window's start and its end (a traced window's
    # wall time is longer than window_s by the profiler's start and stop,
    # in which no step runs)
    start = run._T_START + notes["setup_s"]
    end = start + notes["window_s"] + notes["notes"].get("profiler_s", 0.0)
    with tracker._lock:
        in_window = [(e.kind, e.fun_name) for e in tracker._log
                     if start < e.t_end <= end]
    assert in_window == []


def test_window_start_is_the_running_runners(monkeypatch):
    obs = harness.Observation(window_s=1.0, setup_s=5.0)
    # imported by a test or a tool: benchmark.run is the runner
    assert setup_phase.window_start(obs) == run._T_START + 5.0
    # python -m benchmark.run: the runner is __main__, and a copy imported
    # beside it (a later _T_START) must not be read
    main = types.ModuleType("__main__")
    main.__spec__ = types.SimpleNamespace(name="benchmark.run")
    main._T_START = run._T_START - 100.0
    monkeypatch.setitem(sys.modules, "__main__", main)
    assert setup_phase.window_start(obs) == run._T_START - 95.0
    # neither: nothing to read, and no second copy is imported to find out
    monkeypatch.setitem(sys.modules, "__main__", types.ModuleType("__main__"))
    monkeypatch.delitem(sys.modules, "benchmark.run")
    assert setup_phase.window_start(obs) is None
    assert "benchmark.run" not in sys.modules


@pytest.mark.parametrize("name", SIX)
def test_a_log_that_no_longer_reaches_the_windows_start_reports_nothing(
    monkeypatch, name
):
    """After an overflow a cut before the oldest kept entry cannot be
    answered: the line leaves the six out, it does not over-count."""
    from areal_tpu.utils import goodput

    monkeypatch.setattr(goodput, "_LOG_ENTRIES", 2)
    tracker = goodput.CompileTracker()
    for _ in range(3):
        tracker._observe("trainer", "", 0.001, goodput._BACKEND_COMPILE_EVENT)
    monkeypatch.setattr(goodput, "trainer_tracker", lambda: tracker)
    obs = harness.Observation(window_s=1.0, setup_s=0.0)  # before them all
    assert tracker.dropped_total == 1
    assert harness.read_metric(name, obs) is None


@pytest.mark.parametrize("name", SIX)
def test_a_program_without_the_log_reports_nothing(monkeypatch, name):
    """The parent of the PR that added the log: every reading is left out
    of the line, none raises."""
    from areal_tpu.utils import goodput

    monkeypatch.setattr(goodput, "trainer_tracker", lambda: object())
    obs = harness.Observation(window_s=1.0, setup_s=5.0)
    assert harness.read_metric(name, obs) is None
