"""benchmark/program_trace.py: the reduction on a hand-built event list
(nesting, innermost-span attribution, clipping to the window, program and
scope sums), on one trimmed chip trace recorded by the PR that added it, and
the program's own token counters against the benchmark's hook."""

import json
import os

import pytest

from benchmark import program_trace as pt
from benchmark import xplane

D0, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = xplane.OPS_LINE, xplane.MODULES_LINE
MS = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))


def _op(name, start, dur, op_name=None):
    extra = {"op_name": op_name} if op_name else {}
    program = next((p for p in ("logp_forward", "grad_step", "apply_step")
                    if 12 <= start < 30 and p == "logp_forward"
                    or 40 <= start < 90 and p == "grad_step"
                    or start >= 100 and p == "apply_step"), None)
    return (D0, OPS, name, start * MS, dur * MS,
            dict(extra, program=program) if program else extra)


def _mod(program, start, dur):
    return (D0, MODS, f"jit_{program}(7)", start * MS, dur * MS,
            {"program": program, "program_id": 7})


def _span(name, start, dur, line="python", **stats):
    return (HOST, line, name, start * MS, dur * MS, stats)


G = "jit(grad_step)/jit(main)/"


def _events():
    return [
        # window 10..110. device: logp_forward 12..30, grad_step 40..90
        # (clipped on neither side), apply_step 100..120 (clipped to 110)
        _mod("logp_forward", 12, 18),
        _op("fusion.1", 12, 8, "jit(logp_forward)/jit(main)/attention/dot"),
        _op("fusion.2", 20, 10, "jit(logp_forward)/jit(main)/loss/lm_head/dot"),
        _mod("grad_step", 40, 50),
        _op("while.3", 40, 50),  # a container: its children count, not it
        _op("fusion.4", 40, 20, G + "jvp(loss)/while/body/attention/dot"),
        _op("fusion.5", 60, 10, G + "transpose(jvp(loss))/while/body/"
                                    "checkpoint/rematted_computation/mlp/dot"),
        _op("fusion.6", 70, 15, G + "transpose(jvp(loss))/while/body/"
                                    "transpose(jvp(attention))/dot"),
        _op("copy.7", 85, 5),  # no metadata at all
        _mod("apply_step", 100, 20),
        _op("fusion.8", 100, 20, "jit(apply_step)/jit(main)/optimizer/mul"),
        # host, main thread: the benchmark's window and its two calls, the
        # program's tree inside
        _span("bench:window", 10, 100),
        _span("areal:train.ppo_update", 10, 95, step=4),
        _span("areal:train.logp_recompute", 10, 30, step=4),
        _span("areal:train.pack", 10, 2, step=4, pad_to=256,
              real_tokens=300, padded_tokens=512),
        _span("areal:train.logp_fetch", 13, 18, step=4),   # idle 30..31
        _span("areal:train.unpack", 31, 9, step=4),        # idle 31..40
        _span("areal:train.minibatch", 40, 65, step=4),
        _span("areal:train.pack", 41, 3, step=4, pad_to=256,
              real_tokens=200, padded_tokens=512),
        _span("areal:train.scalar_fetch", 88, 12, step=4),  # idle 90..100
        # another thread, a shorter span over part of the first gap
        _span("areal:engine.idle", 10, 1, line="loop"),
        _span("python noise", 0, 200),
    ]


def test_programs_scopes_and_directions_are_clipped_to_the_window():
    r = pt.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100) and r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.078)  # 12-30, 40-90, 100-110
    assert r["idle_s"] == pytest.approx(0.022)
    p = r["programs"]
    assert p["logp_forward"] == {"seconds": pytest.approx(0.018), "runs": 1}
    assert p["grad_step"]["seconds"] == pytest.approx(0.050)
    assert p["apply_step"]["seconds"] == pytest.approx(0.010)  # 100-110
    s = r["scopes"]
    assert s["attention"] == pytest.approx(0.008 + 0.020 + 0.015)
    assert s["lm_head"] == pytest.approx(0.010)  # innermost of loss/lm_head
    assert s["mlp"] == pytest.approx(0.010)
    assert s["optimizer"] == pytest.approx(0.010)
    assert s[pt.NO_SCOPE] == pytest.approx(0.005)
    assert "while.3" not in s and sum(s.values()) == pytest.approx(0.078)
    d = r["scope_directions"]
    assert d[("attention", "fwd")] == pytest.approx(0.028)
    assert d[("attention", "bwd")] == pytest.approx(0.015)
    assert d[("mlp", "remat")] == pytest.approx(0.010)
    assert r["scoped_ops"] == 6


def test_idle_goes_to_the_innermost_span_open_in_it():
    r = pt.reduce(_events())
    idle = r["idle_by_span"]
    # 10..12: the other thread's 1 ms span is the shortest open one for its
    # first millisecond, then train.pack
    assert idle["engine.idle"] == pytest.approx(0.001)
    assert idle["train.pack"] == pytest.approx(0.001)
    assert idle["train.logp_fetch"] == pytest.approx(0.001)  # 30..31
    assert idle["train.unpack"] == pytest.approx(0.009)  # 31..40
    assert idle["train.scalar_fetch"] == pytest.approx(0.010)  # 90..100
    assert sum(idle.values()) == pytest.approx(r["idle_s"])
    assert r["idle_in_leaf_s"] == pytest.approx(r["idle_s"])  # all leaves
    # without the leaves the same gaps fall to their parents
    parents = [e for e in _events() if e[2] not in (
        "areal:train.logp_fetch", "areal:train.unpack", "areal:train.pack",
        "areal:train.scalar_fetch", "areal:engine.idle")]
    r2 = pt.reduce(parents)
    assert r2["idle_by_span"] == {
        "train.logp_recompute": pytest.approx(0.012),
        "train.minibatch": pytest.approx(0.010),
    }
    assert r2["idle_in_leaf_s"] == pytest.approx(0.022)
    # and with no program span at all, to nobody
    none = [e for e in _events() if not e[2].startswith("areal:")]
    r3 = pt.reduce(none)
    assert r3["idle_by_span"] == {"(no span)": pytest.approx(0.022)}
    assert r3["n_spans"] == 0 and r3["spans"] == {}


def test_span_table_self_time_and_summed_attributes():
    r = pt.reduce(_events())
    t = r["spans"]
    assert t["train.pack"]["count"] == 2
    assert t["train.pack"]["stats"] == {
        "real_tokens": 500, "padded_tokens": 1024,
    }  # step and pad_to label, they are not summed
    assert t["train.logp_recompute"]["self_s"] == pytest.approx(
        0.030 - 0.002 - 0.018 - 0.009
    )
    assert t["train.ppo_update"]["self_s"] == pytest.approx(0.0)
    assert t["train.unpack"]["idle_s"] == pytest.approx(0.009)
    text = pt.tables(r)
    for needle in ("grad_step", "attention", "train.scalar_fetch",
                   "in a leaf span", '"real_tokens": 500'):
        assert needle in text


def test_an_explicit_window_and_a_trace_without_one():
    r = pt.reduce(_events(), window=(40 * MS, 90 * MS))
    assert r["window_s"] == pytest.approx(0.050) and r["idle_s"] == 0.0
    assert set(r["programs"]) == {"grad_step"}
    no_window = [e for e in _events() if e[2] != "bench:window"]
    r = pt.reduce(no_window)  # first to last operation: 12..120
    assert r["window_s"] == pytest.approx(0.108)
    assert pt.reduce([])["window_s"] == 0.0


@pytest.mark.parametrize("path,want", [
    ("jit(grad_step)/jit(main)/transpose(jvp(loss))/mul", "loss"),
    ("jit(grad_step)/jit(main)/jvp(loss)/while/body/attention/dot", "attention"),
    ("jit(loss)/jit(main)/add", None),  # a jitted function's name: no scope
    ("jit(f)/attention_mask/mul", None),  # whole components only
    ("jit(f)/loss/lm_head/dot_general:", "lm_head"),
    ("", None), (None, None),
])
def test_scope_of(path, want):
    assert pt.scope_of(path) == want
    # a program with no vocabulary yet: every operation reads unscoped
    assert pt.scope_of(path, ()) is None


@pytest.mark.parametrize("reader,spec,want", [
    ("trace_program_share", {"program": "grad_step"}, 50.0),
    ("trace_program_share", {"program": "not_there"}, None),
    ("trace_scope_share", {"scopes": ["lm_head", "loss"],
                           "programs": ["logp_forward", "grad_step"]}, 10.0),
    ("trace_scope_share", {"scopes": ["kv_write"],
                           "programs": ["apply_step"]}, 0.0),
    ("trace_scope_share", {"scopes": ["attention"],
                           "programs": ["write_rows"]}, None),
    ("program_gap_share", {"spans": ["train.logp_fetch", "train.unpack",
                                     "train.scalar_fetch"]}, 20.0),
    ("program_gap_share", {"spans": ["train.split"]}, 0.0),
])
def test_readers_on_the_reduction(monkeypatch, reader, spec, want):
    import importlib
    import types

    r = pt.reduce(_events())
    monkeypatch.setattr(pt, "of_run", lambda obs: r if obs.trace else None)
    read = importlib.import_module(f"benchmark.readers.{reader}").read
    got = read(spec, types.SimpleNamespace(trace={"window_s": 0.1}))
    assert got == (None if want is None else pytest.approx(want))
    assert read(spec, types.SimpleNamespace(trace=None)) is None


def test_one_program_compiled_without_scopes_silences_the_scope_shares(
    monkeypatch
):
    """What the parent read in PR 25's call 3: grad_step out of a cache the
    change had filled (scoped), its own fwd freshly compiled (not)."""
    import types

    from benchmark.readers import trace_scope_share

    mixed = [
        e if e[5].get("program") != "logp_forward" or e[1] != OPS
        else (*e[:5], {"program": "logp_forward"})
        for e in _events()
    ]
    r = pt.reduce(mixed)
    assert pt.scoped_share(r, "logp_forward") == 0.0
    assert pt.scoped_share(r, "grad_step") == pytest.approx(45 / 50)
    assert pt.scoped_share(r, "never_ran") == 0.0
    assert r["scopes"]["attention"] == pytest.approx(0.035)  # low: 0.043
    monkeypatch.setattr(pt, "of_run", lambda obs: r)
    obs = types.SimpleNamespace(trace={"window_s": 0.1})
    spec = {"scopes": ["attention"], "programs": ["grad_step", "logp_forward"]}
    assert trace_scope_share.read(spec, obs) is None
    # the one program that is scoped still reads alone, by its own spec
    assert trace_scope_share.read(
        {"scopes": ["attention"], "programs": ["grad_step"]}, obs
    ) == pytest.approx(35.0)
    whole = pt.reduce(_events())
    monkeypatch.setattr(pt, "of_run", lambda obs: whole)
    assert trace_scope_share.read(spec, obs) == pytest.approx(43.0)


def test_a_program_without_the_names_reads_nothing(monkeypatch):
    """The parent of the PR that added spans and scopes: every reader leaves
    its metric out, none raises."""
    import importlib
    import types

    bare = [
        (e[0], e[1], e[2], e[3], e[4], {}) for e in _events()
        if not e[2].startswith("areal:")
    ]
    bare = [e if e[1] != MODS else
            (e[0], e[1], "jit_fwd(7)", e[3], e[4],
             {"program": "fwd", "program_id": 7}) for e in bare]
    r = pt.reduce(bare)
    monkeypatch.setattr(pt, "of_run", lambda obs: r)
    obs = types.SimpleNamespace(trace={"window_s": 0.1})
    for reader, spec in [
        ("trace_program_share", {"program": "logp_forward"}),
        ("trace_scope_share", {"scopes": ["attention"],
                               "programs": ["grad_step"]}),
        ("program_gap_share", {"spans": ["train.pack"]}),
    ]:
        read = importlib.import_module(f"benchmark.readers.{reader}").read
        assert read(spec, obs) is None


def test_text_round_trip(tmp_path):
    path = tmp_path / "events.tsv"
    pt.write_events(_events(), str(path))
    assert pt.read_events(str(path)) == _events()


def test_wire_format_reads_the_metadata_table(tmp_path):
    """op_names on a hand-assembled XSpace: one device plane whose event
    metadata carries tf_op (a string, and an interned one) and program_id."""

    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        if isinstance(payload, str):
            payload = payload.encode()
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def stat_meta(i, name):
        return field(5, field(1, i) + field(2, field(1, i) + field(2, name)))

    def event_meta(i, name, stats):
        return field(4, field(1, i) + field(
            2, field(1, i) + field(2, name) + b"".join(
                field(5, s) for s in stats)))

    plane = (
        field(2, "/device:TPU:0")
        + stat_meta(1, "tf_op") + stat_meta(2, "program_id")
        + stat_meta(3, "jit(f)/mlp/dot") + stat_meta(4, "flops")
        + event_meta(1, "%fusion.1 = f32[8] fusion()", [
            field(1, 1) + field(5, "jit(f)/attention/dot"),
            field(1, 2) + field(3, 77), field(1, 4) + field(3, 12345)])
        + event_meta(2, "%fusion.2 = f32[8] fusion()", [
            field(1, 1) + field(7, 3), field(1, 2) + field(4, 78)])
        + event_meta(3, "%copy.3 = f32[8] copy()", [field(1, 4) + field(3, 9)])
        + field(3, b"\x08\x01")  # a line: skipped whole
    )
    host = field(2, "/host:CPU") + stat_meta(1, "tf_op") + event_meta(
        1, "x", [field(1, 1) + field(5, "nope")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert pt.op_names(str(path)) == {
        (77, "%fusion.1 = f32[8] fusion()"): "jit(f)/attention/dot",
        (78, "%fusion.2 = f32[8] fusion()"): "jit(f)/mlp/dot",
    }


RECORDED = os.path.join(HERE, "recorded_program_trace.tsv")


def test_recorded_chip_trace_reduces_to_its_known_numbers():
    events = pt.read_events(RECORDED)
    with open(RECORDED.replace(".tsv", ".json")) as f:
        want = json.load(f)
    r = pt.reduce(events)
    assert r["devices"] == 1 and r["n_spans"] == want["n_spans"]
    for key in ("window_s", "busy_s", "idle_in_leaf_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9)
    for table in ("programs", "scopes", "idle_by_span"):
        got = {k: (v["seconds"] if isinstance(v, dict) else v)
               for k, v in r[table].items()}
        assert got == pytest.approx(want[table], rel=1e-9)
    assert r["spans"]["train.pack"]["stats"] == want["pack_stats"]
    # the old reduction sees the same device time in the same window
    old = xplane.reduce([e[:5] for e in events],
                        xplane.span_window([e[:5] for e in events]))
    assert old["busy_s"] == pytest.approx(r["busy_s"], rel=1e-9)
    assert sum(r["scopes"].values()) == pytest.approx(
        sum(old["op_seconds"].values()), rel=1e-9)


def test_rehearsal_program_counters_equal_the_benchmarks_hook(monkeypatch):
    """The trainer's own real_tokens / padded_tokens over the window equal
    what pad_share.train's hook around _pack_for_device counted, exactly."""
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from benchmark import run

    engines = []
    init = SPMDTrainEngine.initialize

    def initialize(self, *a, **kw):
        engines.append(self)
        return init(self, *a, **kw)

    monkeypatch.setattr(SPMDTrainEngine, "initialize", initialize)
    ctx, kind, _ = run.prepare([
        "--workload", "train_0p5b_grpo", "--seed", str(2 ** 31 + 25),
        "--seconds", "1", "--trace", "0", "--rehearse",
    ])
    marks = {}
    mark_start, since = ctx.mark_window_start, ctx.watch.since

    def mark_window_start():
        marks["start"] = engines[0].counters()
        mark_start()

    def since_window_end(mark):  # the kind's first call after its window
        marks.setdefault("end", engines[0].counters())
        return since(mark)

    ctx.mark_window_start = mark_window_start
    ctx.watch.since = since_window_end
    out = kind.run(ctx)
    delta = {k: marks["end"][k] - marks["start"][k] for k in marks["end"]}
    c = out.obs.counters
    assert delta["real_tokens"] == c["dispatched_real_tokens"] > 0
    assert delta["padded_tokens"] == c["padded_tokens"] > delta["real_tokens"]
    assert set(delta) == {"real_tokens", "padded_tokens"}
    # every trained token is dispatched twice: recompute and train
    assert delta["real_tokens"] == 2 * c["trained_tokens"]
