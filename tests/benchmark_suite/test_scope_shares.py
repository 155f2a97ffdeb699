"""The nine time shares PR 50 added (``benchmark/metrics/*.json`` over the
reader that was there, ``trace_scope_share``): each through its own file on a
hand-made reduction, and the recorded chip trace under the longer vocabulary."""

import json
import os

import pytest

from benchmark import harness
from benchmark import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAMS = ["grad_step", "logp_forward"]
# metric -> the scopes its file must name
SHARES = {
    "mlp_time_share.train": ["mlp"],
    "proj_time_share.train": ["qkv_proj", "o_proj"],
    "rope_time_share.train": ["rope"],
    "attn_gate_time_share.train": ["attn_gate"],
    "stream_time_share.train": ["stream_norm", "residual"],
    "param_traffic_time_share.train": ["param_cast", "grad_add"],
    "layer_stack_time_share.train": ["layer_stack"],
    "moe_exchange_time_share.train": ["moe_exchange"],
    "unscoped_time_share.train": [pt.NO_SCOPE],
}
EVERY = [s for scopes in SHARES.values() for s in scopes]


def _record():
    """A window of 100 s: every scope above reads 1 s in ``grad_step`` and
    0.5 s in ``logp_forward`` (``(no scope)`` among them), ``attention``
    the rest, and ``apply_step`` is all ``optimizer``."""
    return {
        "window_s": 100.0, "devices": 1,
        "program_scopes": {
            "grad_step": dict({s: 1.0 for s in EVERY}, attention=40.0),
            "logp_forward": dict({s: 0.5 for s in EVERY}, attention=20.0),
            "apply_step": {"optimizer": 4.0, pt.NO_SCOPE: 0.1},
        },
    }


def _obs():
    return harness.Observation(
        window_s=100.0, setup_s=1.0, trace={"window_s": 100.0},
    )


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_a_share_sums_its_scopes_over_both_programs(monkeypatch, metric):
    spec = harness.load_json(
        os.path.join(harness.HERE, "metrics", metric + ".json")
    )
    assert spec["reader"] == "trace_scope_share" and spec["unit"] == "%"
    assert spec["scopes"] == SHARES[metric] and spec["programs"] == PROGRAMS
    assert len(spec["what"]) > 80  # it says which call sites
    record = _record()
    monkeypatch.setattr(pt, "of_run", lambda obs: record)
    # 1 s + 0.5 s a scope of a 100 s window; apply_step's is not asked for
    want = 1.5 * len(SHARES[metric])
    assert harness.read_metric(metric, _obs()) == {
        "value": pytest.approx(want), "unit": "%"
    }
    # a scope the program never opened reads 0, a number
    for table in record["program_scopes"].values():
        for scope in SHARES[metric]:
            table.pop(scope, None)
    assert harness.read_metric(metric, _obs())["value"] == 0.0


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_a_share_reads_nothing_from_a_program_that_is_not_there_or_not_named(
    monkeypatch, metric
):
    record = _record()
    monkeypatch.setattr(pt, "of_run", lambda obs: record)
    del record["program_scopes"]["logp_forward"]  # it did not run
    assert harness.read_metric(metric, _obs()) is None
    # compiled before the scopes, or loaded from a cache entry such a
    # program wrote: most of its time under none
    record["program_scopes"]["logp_forward"] = {pt.NO_SCOPE: 30.0, "mlp": 1.0}
    assert pt.scoped_share(record, "logp_forward") < 0.5
    assert harness.read_metric(metric, _obs()) is None
    # no trace at all
    monkeypatch.setattr(pt, "of_run", lambda obs: None)
    assert harness.read_metric(metric, _obs()) is None


def test_the_shares_split_a_window_without_overlap():
    """No scope is read by two of the time shares of ``BENCHMARK.json``
    over these two programs, so the shares add up to at most the window."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    seen = {}
    for metric in bench["per_layer"]:
        spec = harness.load_json(os.path.join(
            harness.HERE, "metrics", metric["name"] + ".json"
        ))
        if spec["reader"] != "trace_scope_share":
            continue
        if not metric["name"].endswith(".train"):
            continue
        for scope in spec["scopes"]:
            assert scope not in seen, (scope, seen[scope], metric["name"])
            seen[scope] = metric["name"]
    assert set(EVERY) <= set(seen)
    # and every scope a trainer program computes under is some share's,
    # but the embedding's lookup and the engine's own (no cell's programs)
    own = set(pt.device_scopes()) - {"embed", "optimizer", "sample",
                                     "kv_write"}
    assert own <= set(seen), own - set(seen)


def test_the_recorded_chip_trace_takes_no_new_scope_by_accident():
    """PR 25's recorded slice, whose ``op_name``s were written before the
    new names: it reduces to its known numbers under the longer vocabulary,
    so none of its operations reads a new scope."""
    from areal_tpu.utils import tracing

    events = pt.read_events(os.path.join(HERE, "recorded_program_trace.tsv"))
    with open(os.path.join(HERE, "recorded_program_trace.json")) as f:
        want = json.load(f)
    assert set(EVERY) - {pt.NO_SCOPE} <= set(tracing.DEVICE_SCOPES)
    r = pt.reduce(events)
    assert {k: pytest.approx(v, rel=1e-9) for k, v in want["scopes"].items()} \
        == r["scopes"]
    assert not set(r["scopes"]) & set(EVERY) - {pt.NO_SCOPE, "mlp"}
