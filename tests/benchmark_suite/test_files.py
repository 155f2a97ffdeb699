"""BENCHMARK.json and every data file it names: they load, their names stay
inside the contract's characters, and every pointer resolves to a file."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


UNPROVEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unproven_cells.json")


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(ROOT, "BENCHMARK.json"))
# cells that are built and rehearsed but not yet proven on the chip: the same
# form, kept with the tests; an entry moves into BENCHMARK.json once proven
BOTH = [BENCH, _load(UNPROVEN)]
METRICS = [m for b in BOTH for m in b["end_to_end"] + b["per_layer"]]
PER_LAYER = [(b, m) for b in BOTH for m in b["per_layer"]]
CELLS = [(b, w) for b in BOTH for w in b["workloads"]]
CONFIGS = {c["name"]: c for b in BOTH for c in b["configs"]}


@pytest.mark.parametrize("BENCH", BOTH, ids=["BENCHMARK.json", "unproven"])
def test_top_level_keys_and_limits(BENCH):
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"]
    )
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0 < m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert all("bound" not in m for m in BENCH["per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert all(c["name"] in {w["config"] for w in BENCH["workloads"]}
               for c in BENCH["configs"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock"
    )
    spec_path = os.path.join(
        ROOT, "benchmark", "metrics", metric["name"] + ".json"
    )
    with open(spec_path) as f:
        spec = json.load(f)
    assert spec["unit"] == metric["unit"]
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    cells = {w["name"] for _, w in CELLS}
    assert set(metric.get("workloads", cells)) <= cells


@pytest.mark.parametrize("bench,metric", PER_LAYER, ids=lambda x: x.get("name", ""))
def test_moves_names_an_end_to_end_metric_its_cells_report(bench, metric):
    cells = {w["name"] for w in bench["workloads"]}
    target = next(
        m for m in bench["end_to_end"] if m["name"] == metric["moves"]
    )
    assert set(metric.get("workloads", cells)) <= set(
        target.get("workloads", cells)
    )
    assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200


@pytest.mark.parametrize("bench,cell", CELLS, ids=lambda x: x.get("name", ""))
def test_cell_files(bench, cell):
    from benchmark import harness

    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = harness.load_cell(cell["name"], bench)
    kind = harness.load_kind(loaded.traffic["kind"])
    assert callable(kind.run)
    assert "setup_s" in loaded.end_to_end and len(loaded.end_to_end) >= 2
    assert loaded.per_layer
    assert "rehearse" in loaded.traffic  # tier-1 can run it at tiny size


@pytest.mark.parametrize("config", list(CONFIGS.values()), ids=lambda c: c["name"])
def test_config_file_matches_the_programs_own_table(config):
    from areal_tpu.models.config import from_hf_config
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS
    from benchmark import arith

    assert NAME.match(config["name"])
    assert config["file"].startswith("benchmark/configs/")
    with open(os.path.join(ROOT, config["file"])) as f:
        c = json.load(f)
    assert c["source"] == config["source"] and c["reduced"] == config["reduced"]
    key = config["name"].replace("-", "_").replace(".", "p")
    assert from_hf_config(c) == MODEL_CONFIGS[key]()
    assert c["kv_bytes_per_token"] == arith.kv_bytes_per_token(c)


def test_paths_hold_only_allowed_file_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_table_refuses_an_unknown_device():
    from benchmark import arith

    v5e = arith.load_peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.load_peaks("TPU v9 imaginary")


def _stems(sub, ext):
    d = os.path.join(ROOT, "benchmark", sub)
    return sorted(f[:-len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("__"))


# nothing under benchmark/ that no cell reaches: a file for a cell that is
# not there yet comes with the PR that proves that cell
@pytest.mark.parametrize("name", _stems("metrics", ".json"))
def test_every_metric_file_belongs_to_a_listed_metric(name):
    assert name in {m["name"] for m in METRICS}


@pytest.mark.parametrize("name", _stems("readers", ".py"))
def test_every_reader_is_named_by_a_metric_file(name):
    used = {
        _load(os.path.join(ROOT, "benchmark", "metrics", m + ".json"))["reader"]
        for m in _stems("metrics", ".json")
    }
    assert name in used


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_every_traffic_file_belongs_to_a_cell_and_names_a_kind(name):
    assert name in {w["traffic"] for _, w in CELLS}
    kind = _load(os.path.join(ROOT, "benchmark", "traffic", name + ".json"))["kind"]
    assert kind in _stems("kinds", ".py")
