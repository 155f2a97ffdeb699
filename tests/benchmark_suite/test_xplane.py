"""benchmark/xplane.py: the reduction on a hand-built event list (and on one
small recorded trace, once a traced chip run has left one)."""

import os

import pytest

from benchmark import xplane

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS = xplane.OPS_LINE
MS = 1_000_000


def _events():
    return [
        # device 0: busy 0-10, 10-20 (touching), 40-50, 45-60 (overlapping)
        (D0, OPS, "fusion.1", 0 * MS, 10 * MS),
        (D0, OPS, "paged_decode", 10 * MS, 10 * MS),
        (D0, OPS, "fusion.1", 40 * MS, 10 * MS),
        (D0, OPS, "paged_decode", 45 * MS, 15 * MS),
        (D0, xplane.MODULES_LINE, "jit_step", 0, 60 * MS),  # not an op
        # host spans: the 20-40 gap sits inside bench:wait, itself inside
        # a longer bench:window
        (HOST, "main", "bench:window", 0, 100 * MS),
        (HOST, "main", "bench:wait", 18 * MS, 24 * MS),
        (HOST, "main", "python noise", 0, 100 * MS),
    ]


def test_busy_is_the_union_and_gaps_name_the_innermost_span():
    r = xplane.reduce(_events())
    assert r["devices"] == 1 and r["n_ops"] == 4
    assert r["window_s"] == pytest.approx(0.060)
    assert r["busy_s"] == pytest.approx(0.040)  # 0-20 and 40-60
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.020)
    assert r["op_seconds"]["paged_decode"] == pytest.approx(0.025)
    assert r["gaps"] == [("wait", pytest.approx(0.020))]
    assert r["longest_gap_s"] == pytest.approx(0.020)
    assert xplane.matching_seconds(r["op_seconds"], "paged") == pytest.approx(0.025)
    assert xplane.top_ops(r["op_seconds"], 1)[0][0] == "paged_decode"


def test_window_clips_and_unattributed_gaps():
    r = xplane.reduce(_events(), window=(5 * MS, 100 * MS))
    assert r["window_s"] == pytest.approx(0.095)
    assert r["busy_s"] == pytest.approx(0.035)
    causes = dict(r["gaps"])
    assert causes["wait"] == pytest.approx(0.020)
    assert causes["window"] == pytest.approx(0.040)  # 60-100: only the outer
    no_spans = [e for e in _events() if not e[2].startswith("bench:")]
    assert dict(xplane.reduce(no_spans)["gaps"]) == {
        "unattributed": pytest.approx(0.020)
    }


def test_span_window_is_the_benchmarks_own_span_idle_edges_included():
    ev = _events()
    assert xplane.span_window(ev) == (0, 100 * MS)
    r = xplane.reduce(ev, xplane.span_window(ev))
    # the 40 ms after the last operation count as idle: 60% idle, not 33%
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.040)
    assert dict(r["gaps"]) == {"wait": pytest.approx(0.020),
                               "window": pytest.approx(0.040)}
    late = [(HOST, "main", "bench:window", 30 * MS, 50 * MS)] + ev[:5]
    r = xplane.reduce(late, xplane.span_window(late))
    # idle before the first operation inside the window counts too
    assert r["window_s"] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.020)  # 40-60 of 30-80
    assert xplane.span_window(ev[:5]) is None  # no span: the caller decides


def test_two_devices_are_averaged_and_nothing_is_nothing():
    ev = _events() + [(D1, OPS, "fusion.1", 0, 60 * MS)]
    r = xplane.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.040 + 0.060) / 2)
    empty = xplane.reduce([e for e in ev if e[0] == HOST])
    assert empty["busy_s"] == 0.0 and empty["window_s"] == 0.0


def test_text_round_trip(tmp_path):
    path = tmp_path / "events.tsv"
    xplane.write_events(_events(), str(path))
    assert xplane.read_events(str(path)) == _events()


RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.tsv")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the tree yet")
def test_recorded_chip_trace_reduces_to_its_known_numbers():
    import json

    events = xplane.read_events(RECORDED)
    with open(RECORDED.replace(".tsv", ".json")) as f:
        want = json.load(f)
    r = xplane.reduce(events)
    assert r["n_ops"] == want["n_ops"] and r["devices"] == want["devices"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    top = xplane.top_ops(r["op_seconds"], 3)
    assert [k for k, _ in top] == want["top3"]
