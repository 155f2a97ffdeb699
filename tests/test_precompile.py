"""Cold-start elimination (r14): exact ladder enumeration, AOT
precompile, compile-events replay + fingerprint refusal, seeded-cache
scale-up, and the seed-artifact plumbing.

Wall-time discipline: everything runs on the CPU backend with the
smallest ladder that still exercises every dimension (reuse OFF kills
the pfb axis; tiny model; 2 slots). The one subprocess pair (cold vs
seeded /health lead) IS the acceptance scenario and is kept to two
tiny workers sharing one compile-cache dir.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from __graft_entry__ import _ensure_virtual_devices  # noqa: E402

_ensure_virtual_devices(1)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from areal_tpu.api.cli_args import JaxGenConfig  # noqa: E402
from areal_tpu.inference import precompile as pl  # noqa: E402
from areal_tpu.models.config import tiny_config  # noqa: E402
from areal_tpu.models.transformer import init_params  # noqa: E402
from areal_tpu.utils import compile_cache  # noqa: E402
from tests import fixtures  # noqa: E402


def _tiny_gen_config(**over) -> JaxGenConfig:
    """The minimal-ladder serving shape: reuse off (no pfb axis), two
    slots, chunk 4, one pow2 of everything."""
    kw = dict(
        dtype="float32", max_num_seqs=2, max_model_len=16,
        prefill_chunk=8, kv_bucket=8, page_size=8, decode_chunk=4,
        decode_pipeline=1, decode_compact_min_rows=1, admit_wave=2,
        prefix_reuse_min=0, sample_topk_bound=8, admit_hold_s=0.0,
    )
    kw.update(over)
    return JaxGenConfig(**kw)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module", autouse=True)
def _scoped_compilation_cache():
    """The persistent-cache enable is process-global jax config; leaving
    it on would bleed into LATER test modules — observed to corrupt
    donation-heavy sharded train steps on this jax's CPU backend
    (test_train_engine microbatching/save-load fail with garbage rows
    when the cache stays enabled). Serving-side programs round-trip the
    cache token-exactly (pinned below); the trainer plane never enables
    it in production (the launcher exports the cache dir to gen-server
    subprocesses only). Restore the default when this module ends.

    These tests pin the CONFIGURED directory, so they run with
    JAX_COMPILATION_CACHE_DIR unset (an inherited value would win)."""
    inherited = os.environ.pop(compile_cache.CACHE_DIR_ENV, None)
    yield
    compile_cache.disable_compilation_cache()
    if inherited is not None:
        os.environ[compile_cache.CACHE_DIR_ENV] = inherited


# ==========================================================================
# Enumerator units (pure python — no engine, no compiles)
# ==========================================================================
class TestEnumerator:
    def test_minimal_ladder_contents(self, tiny_model):
        mc, _ = tiny_model
        rungs = pl.enumerate_ladder(_tiny_gen_config(), mc)
        keys = {r.key for r in rungs}
        # prefill: 2 suffix/page buckets × rows {1, 2}; joins collapse
        # onto the single-row chain because both components are
        # monotone in prompt length when offsets are off
        assert {
            "prefill|rows1|tp8|pps1|pfb0|mm0",
            "prefill|rows1|tp16|pps2|pfb0|mm0",
            "prefill|rows2|tp8|pps1|pfb0|mm0",
            "prefill|rows2|tp16|pps2|pfb0|mm0",
        } <= keys
        # no cross-bucket mixes without a second offset dimension
        assert "prefill|rows1|tp8|pps2|pfb0|mm0" not in keys
        # decode: rows {1, 2} × pps {1, 2} (margins 4 and 8), replay 0
        for rows in (1, 2):
            for pps in (1, 2):
                assert f"decode|rows{rows}|steps4|pps{pps}|replay0" in keys
        assert "sample|topk-1" in keys and "sample|topk8" in keys
        assert "copy|pad8" in keys
        assert "engine|" in keys
        assert len(rungs) == len(keys)  # no duplicates

    def test_offset_axis_and_join_closure(self, tiny_model):
        mc, _ = tiny_model
        cfg = _tiny_gen_config(
            max_model_len=64, prefill_chunk=16, kv_bucket=16,
            page_size=16, prefix_reuse_min=16, admit_wave=4,
            max_num_seqs=4,
        )
        keys = {r.key for r in pl.enumerate_ladder(cfg, mc)}
        # single-row: a pfb64 claim means o >= 49, so the row's own
        # suffix caps at 14 → tp 16; bigger tp with that claim is a
        # MULTI-row signature only
        assert "prefill|rows1|tp16|pps4|pfb64|mm0" in keys
        assert "prefill|rows1|tp48|pps4|pfb64|mm0" not in keys
        assert "prefill|rows1|tp64|pps4|pfb64|mm0" not in keys
        # two-row join: one row carries the long no-offset suffix, the
        # other the deep claim — exactly the mixed-wave signature the
        # max-composition closure exists for
        assert "prefill|rows2|tp64|pps4|pfb64|mm0" in keys
        assert "prefill|rows2|tp48|pps4|pfb64|mm0" in keys
        assert "prefill|rows4|tp64|pps4|pfb64|mm0" in keys

    def test_spec_twins_and_compact_rows(self, tiny_model):
        mc, _ = tiny_model
        cfg = _tiny_gen_config(max_num_seqs=6, decode_compact_min_rows=1)
        cfg.spec.enabled = True
        cfg.spec.max_draft = 2
        keys = {r.key for r in pl.enumerate_ladder(cfg, mc)}
        # rows ladder: pow2 clamped at the non-pow2 slot count
        rows = sorted(
            int(k.split("rows")[1].split("|")[0])
            for k in keys
            if k.startswith("decode|")
        )
        assert set(rows) == {1, 2, 4, 6}
        # verify twins: k = min(max_draft, steps-1)+1 = 3, margins = k
        # only (empty pipeline), regular decode replays steps-1
        assert any(k.startswith("spec_verify|rows1|k3|") for k in keys)
        assert all(
            "|replay3" in k
            for k in keys
            if k.startswith("decode|") or k.startswith("spec_verify|")
        )

    def test_fingerprint_tracks_ladder_and_model(self, tiny_model):
        mc, _ = tiny_model
        base = pl.ladder_fingerprint(_tiny_gen_config(), mc)
        assert base == pl.ladder_fingerprint(_tiny_gen_config(), mc)
        assert base != pl.ladder_fingerprint(
            _tiny_gen_config(prefill_chunk=4), mc
        )
        assert base != pl.ladder_fingerprint(
            _tiny_gen_config(), tiny_config("qwen2", vocab_size=160)
        )

    def test_parse_signature_roundtrip(self):
        assert pl.parse_signature(pl.decode_sig(4, 8, 16, 0)) == {
            "rows": 4, "steps": 8, "pps": 16, "replay": 0,
        }
        assert pl.parse_signature(pl.sample_sig(-1)) == {"topk": -1}
        assert pl.parse_signature("") is None
        assert pl.parse_signature("free-form text") is None


# ==========================================================================
# Events stream: header + rotation
# ==========================================================================
class TestEventsStream:
    def test_header_and_rotation(self, tmp_path):
        from areal_tpu.utils.goodput import CompileTracker

        path = str(tmp_path / "events.jsonl")
        tr = CompileTracker(
            events_path=path, fingerprint="fp-test",
            max_events_bytes=600,
        )
        for i in range(40):
            tr.append_event({"kind": "compile", "phase": "decode",
                             "signature": f"rows{i}", "cached": False})
        assert os.path.exists(path + ".1")  # rotated at the bound
        for p in (path, path + ".1"):
            first = json.loads(open(p).readline())
            assert first["kind"] == "header"
            assert first["fingerprint"] == "fp-test"
            assert first["jax"]
        assert os.path.getsize(path + ".1") <= 600 + 400  # one record slop

    def test_stale_header_rotated_on_fingerprint_change(self, tmp_path):
        """A restart with a CHANGED config must not append new-shape
        compiles under the old header — a later replay would trust the
        stale fingerprint and drive the wrong ladder."""
        from areal_tpu.utils.goodput import CompileTracker

        path = str(tmp_path / "events.jsonl")
        tr1 = CompileTracker(events_path=path, fingerprint="fp-old")
        tr1.append_event({"kind": "compile", "phase": "decode",
                          "signature": "rows1", "cached": False})
        CompileTracker(events_path=path, fingerprint="fp-new")
        assert json.loads(open(path).readline())["fingerprint"] == "fp-new"
        rotated = [json.loads(l) for l in open(path + ".1")]
        assert rotated[0]["fingerprint"] == "fp-old"
        assert any(r.get("kind") == "compile" for r in rotated)


# ==========================================================================
# Engine integration: the pin + replay + refusal (one shared engine run)
# ==========================================================================
@pytest.fixture(scope="module")
def pinned_run(tiny_model, tmp_path_factory):
    """ONE traffic run over every ladder bucket of the minimal config,
    shared by the pin/replay/refusal tests (each engine cold-start is
    seconds of compile — pay it once)."""
    from areal_tpu.inference.engine import GenerationEngine

    mc, params = tiny_model
    tmp = tmp_path_factory.mktemp("precompile")
    events = str(tmp / "compile_events.jsonl")
    gcfg = _tiny_gen_config()
    gcfg.goodput.compile_events_path = events
    eng = GenerationEngine(gcfg, model_config=mc, params=params)
    # a full-suite run reaches this module with some shared tiny-shape
    # programs already in the process jit cache — those dispatches
    # would fire no compile events and the coverage pin would read
    # false gaps. Drop the in-process caches so every rung compiles
    # (and streams) fresh, whatever ran before.
    jax.clear_caches()
    eng.start()

    def gen(ids, n=4, **sp):
        return eng.submit(
            {"input_ids": ids, "sampling_params":
             {"max_new_tokens": n, **sp}}
        )

    def wave(*reqs):
        """Deterministic two-row wave: submissions land while admission
        is paused, so the admit loop drains BOTH and saturates (pending
        == free slots) into ONE wave — no racy per-request admits. A
        short drain sleep first empties the pipeline so the wave's
        first decode dispatch sees margin = one chunk (the pps1 rung)."""
        time.sleep(0.3)
        eng.pause()
        futs = [gen(*r[0], **r[1]) for r in reqs]
        eng.continue_generation()
        return [f.result(timeout=120) for f in futs]

    try:
        # rows1 short prompt (tp8/pps1) + its decode (rows1, both pps
        # buckets as the pipeline fills) + sample topk-1
        gen([1, 2, 3], n=8).result(timeout=120)
        # rows1 long prompt (tp16/pps2)
        gen([1, 2, 3, 4, 5, 6, 7, 8, 9], n=4).result(timeout=120)
        # rows2 wave short + long (tp16/pps2 via the long row's max) +
        # rows2 decode + truncated sampling (topk8)
        wave(
            (([5, 6, 7],), dict(n=6, top_k=2, temperature=0.9)),
            (([8, 9, 10, 11, 12, 13, 14, 15, 16],), dict(n=6)),
        )
        # rows2 wave of SHORT prompts only (tp8/pps1 at rows2)
        wave(
            (([2, 3, 4],), dict(n=4)),
            (([3, 4, 5],), dict(n=4)),
        )
        # sibling fan-out: identical prompts → copy|pad8 (partial tail)
        wave(
            (([1, 2, 3, 4, 5],), dict(n=4)),
            (([1, 2, 3, 4, 5],), dict(n=4)),
        )
    finally:
        eng.stop()
    return eng, events


class TestReadinessLatch:
    def test_fully_precompiled_engine_latches_ready_without_traffic(
        self, tiny_model
    ):
        """The r11 latch honors cov >= 1.0: an engine whose ladder the
        precompiler marked fully covered reads ready — and LATCHES —
        with zero traffic-driven backend compiles (the live AOT path is
        pinned by the replay test + the subprocess A/B; this pins the
        latch contract itself at zero wall cost)."""
        from areal_tpu.inference.engine import GenerationEngine

        mc, params = tiny_model
        eng = GenerationEngine(
            _tiny_gen_config(), model_config=mc, params=params
        )
        assert not eng._ready_latched
        before = eng.compiles.compiles_total
        for r in eng._ladder:  # what Precompiler.run does per rung
            eng.compiles.mark_compiled(r.phase, r.signature)
        assert eng.compiles.coverage() == pytest.approx(1.0)
        rd = eng.readiness()
        assert rd["state"] == "ready" and rd["ladder_coverage"] == 1.0
        assert eng._ready_latched
        # marking rungs is accounting, not compiling
        assert eng.compiles.compiles_total == before


class TestEnumeratorPin:
    def test_observed_subset_and_full_coverage(self, pinned_run):
        eng, _ = pinned_run
        ladder = {(r.phase, r.signature) for r in eng._ladder}
        observed = set(eng.compiles.signatures)
        stray = observed - ladder
        assert not stray, f"observed signatures outside the ladder: {stray}"
        missing = ladder - observed
        assert not missing, f"traffic never hit: {missing}"
        assert eng.compiles.coverage() == pytest.approx(1.0)
        # and the readiness latch honored cov >= 1.0
        assert eng.readiness()["state"] == "ready"

    def test_events_stream_carries_the_run(self, pinned_run):
        eng, events = pinned_run
        recs = [json.loads(l) for l in open(events) if l.strip()]
        assert recs[0]["kind"] == "header"
        assert recs[0]["fingerprint"] == eng._ladder_fingerprint
        phases = {r["phase"] for r in recs if r.get("kind") == "compile"}
        assert {"prefill", "decode", "sample", "copy", "engine"} <= phases


class TestReplayPrecompile:
    def test_replay_warms_observed_shapes_with_zero_traffic_compiles(
        self, tiny_model, pinned_run, tmp_path
    ):
        """A second engine that REPLAYS the first run's compile events
        against a fresh persistent cache drives exactly the observed rung
        set, with no failed rung and with zero traffic, and leaves its
        programs in the cache directory.

        What this backend cannot show is the last step — traffic after the
        replay being served from those entries without an XLA compile: the
        installed jaxlib's CPU backend recompiles programs it persisted
        itself. That claim is checked where it holds, on the chip:
        ``chip_smoke.py``'s ``server`` phase starts the server twice over
        one cache directory and requires cache hits on the second start."""
        from areal_tpu.inference.engine import GenerationEngine

        mc, params = tiny_model
        eng1, events = pinned_run
        gcfg = _tiny_gen_config()
        cache_dir = tmp_path / "xla_cache"
        gcfg.compilation_cache_dir = str(cache_dir)
        gcfg.precompile.mode = "replay"
        gcfg.precompile.replay_path = events
        eng = GenerationEngine(gcfg, model_config=mc, params=params)
        assert compile_cache.enabled_dir() == str(cache_dir)
        summary = eng.precompile()
        assert summary["mode"] == "replay"
        assert summary["driven"] > 0 and summary["failed"] == 0
        # replayed rungs == the first run's observed rung set
        assert set(eng.compiles.signatures) == set(
            eng1.compiles.signatures
        )
        # zero traffic: nothing was ever submitted to this engine
        assert eng.metrics()["total_requests"] == 0
        # the replay's programs were persisted where the config said
        assert any(cache_dir.iterdir())

    def test_fingerprint_mismatch_refused(
        self, tiny_model, pinned_run, tmp_path
    ):
        from areal_tpu.inference.engine import GenerationEngine

        mc, params = tiny_model
        _, events = pinned_run
        # a DIFFERENT serving shape must refuse the stream
        gcfg = _tiny_gen_config(prefill_chunk=4)
        gcfg.precompile.mode = "replay"
        gcfg.precompile.replay_path = events
        eng = GenerationEngine(gcfg, model_config=mc, params=params)
        with pytest.raises(pl.ReplayMismatchError, match="fingerprint|ladder"):
            eng.precompile()
        # headerless stream: refused, never trusted
        bare = tmp_path / "bare.jsonl"
        bare.write_text(
            json.dumps(
                {"kind": "compile", "phase": "decode", "signature": "x"}
            )
            + "\n"
        )
        gcfg.precompile.replay_path = str(bare)
        with pytest.raises(pl.ReplayMismatchError, match="header"):
            eng.precompile()


# ==========================================================================
# Subprocess cold vs seeded scale-up (the /health-measured acceptance)
# ==========================================================================
def _spawn_worker(env_extra, cache_dir):
    worker = os.path.join(os.path.dirname(__file__), "genserver_worker.py")
    env = dict(os.environ)
    env["AREAL_WORKER_READY_QUIET"] = "1.0"
    env["AREAL_WORKER_READY_MIN"] = "1000000"
    env["AREAL_WORKER_COMPILE_CACHE"] = cache_dir
    env.update(env_extra)
    return subprocess.Popen(
        [sys.executable, worker, "0"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env,
    )


def _ready_lead(proc, send_traffic=True, deadline_s=300.0):
    t0 = time.monotonic()
    port = None
    deadline = t0 + deadline_s
    assert proc.stdout is not None
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError("worker died before reporting a port")
        if line.startswith("PORT "):
            port = int(line.split()[1])
            break
    assert port is not None, "worker never reported a port"
    # drain remaining output so the worker can't block on a full pipe
    threading.Thread(
        target=lambda: [None for _ in proc.stdout], daemon=True
    ).start()
    addr = f"127.0.0.1:{port}"
    tokens = None
    if send_traffic:
        body = json.dumps(
            {"input_ids": [1, 2, 3, 4, 5],
             "sampling_params": {"max_new_tokens": 6, "greedy": True}}
        ).encode()
        req = urllib.request.Request(
            f"http://{addr}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=240) as r:
            tokens = json.loads(r.read())["output_ids"]
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
            f"http://{addr}/health", timeout=10
        ) as r:
            h = json.loads(r.read())
        if h.get("status") == "ok":
            return time.monotonic() - t0, tokens
        time.sleep(0.1)
    raise RuntimeError("worker never reached ready")


@pytest.mark.parametrize("mode", ["health_lead"])
def test_seeded_subprocess_beats_cold(tmp_path, mode):
    """Cold control vs seeded-cache server, both brought to /health
    "ok": the seeded one must reach ready on a smaller compile bill — the
    headline scale-up number. The cold run doubles as the cache warmer
    (that IS the production seed flow). Both write a compile_events
    stream; what is held to is what the streams count (programs the
    seeded worker LOADED where the cold one compiled them, and the
    seconds XLA took in each process), not the two wall-clock leads:
    those differ by that bill on a quiet machine and by whatever the
    other five workers were doing on a busy one. trace_report --coldstart
    renders the seeded stream, with --require-max-lead as the CI gate."""
    from tools.trace_report import coldstart_summary, load_coldstart

    cache_dir = str(tmp_path / "xla_cache")
    os.makedirs(cache_dir)
    events = str(tmp_path / "seeded_events.jsonl")
    cold_events = str(tmp_path / "cold_events.jsonl")
    procs = []
    try:
        cold = _spawn_worker(
            {"AREAL_WORKER_COMPILE_EVENTS": cold_events}, cache_dir
        )
        procs.append(cold)
        cold_lead, cold_tokens = _ready_lead(cold)
        seeded = _spawn_worker(
            {"AREAL_WORKER_COMPILE_EVENTS": events}, cache_dir
        )
        procs.append(seeded)
        seeded_lead, seeded_tokens = _ready_lead(seeded)
    finally:
        for p in procs:
            fixtures.reap(p, timeout=10)
    was = coldstart_summary(load_coldstart(cold_events))
    now = coldstart_summary(load_coldstart(events))
    assert was["cache_hits"] == 0 and was["uncached"] > 0, was
    assert now["cache_hits"] > 0 and now["uncached"] < was["uncached"], now
    assert now["compile_seconds"] < was["compile_seconds"], (
        f"seeded compile bill {now['compile_seconds']:.1f}s not under "
        f"cold {was['compile_seconds']:.1f}s "
        f"(leads {seeded_lead:.1f}s / {cold_lead:.1f}s)"
    )
    # programs loaded from the seed cache are the SAME programs: greedy
    # streams bit-identical cold vs seeded (same seed-0 worker weights)
    assert seeded_tokens == cold_tokens and cold_tokens
    # the events stream renders as a coldstart report and passes the
    # lead gate at the measured bound (generous slack: the stream's
    # clock starts at engine construction, after interpreter+imports)
    from tools.trace_report import main as report_main

    assert report_main(["--coldstart", events]) == 0
    assert (
        report_main(
            ["--coldstart", events, "--require-max-lead",
             str(max(1.0, cold_lead, seeded_lead))]
        )
        == 0
    )
    assert (
        report_main(
            ["--coldstart", events, "--require-max-lead", "0.001"]
        )
        == 1
    )


# ==========================================================================
# Seed-artifact + launcher/autoscaler plumbing (no subprocesses)
# ==========================================================================
class TestSeedPlumbing:
    def test_pack_and_ensure_seeded(self, tmp_path):
        src = tmp_path / "warm"
        src.mkdir()
        (src / "jit_a-cache").write_bytes(b"AAAA")
        (src / "jit_b-cache").write_bytes(b"BBBB")
        artifact = str(tmp_path / "seed.tar.gz")
        assert compile_cache.pack_seed(str(src), artifact) == 2
        dst = tmp_path / "fresh"
        assert compile_cache.ensure_seeded(str(dst), artifact) == 2
        assert (dst / "jit_a-cache").read_bytes() == b"AAAA"
        # idempotent: existing entries never clobbered
        (dst / "jit_a-cache").write_bytes(b"LIVE")
        assert compile_cache.ensure_seeded(str(dst), artifact) == 0
        assert (dst / "jit_a-cache").read_bytes() == b"LIVE"
        # corrupt artifact degrades to 0, never raises
        bad = tmp_path / "bad.tar.gz"
        bad.write_bytes(b"not a tar")
        assert compile_cache.ensure_seeded(str(dst), str(bad)) == 0

    def test_autoscaler_scale_up_ships_the_seed(self, tmp_path):
        """launch_servers — the path under FleetAutoscaler's
        scale_up_one AND the supervisor's full-constellation restart —
        seeds the cache dir from the artifact and ships the dir to the
        spawned server via env + --compilation-cache-dir."""
        from areal_tpu.launcher.local import launch_servers

        src = tmp_path / "warm"
        src.mkdir()
        (src / "jit_x-cache").write_bytes(b"XX")
        artifact = str(tmp_path / "seed.tar.gz")
        compile_cache.pack_seed(str(src), artifact)
        cache_dir = str(tmp_path / "fleet_cache")
        cfg = _tiny_gen_config()
        cfg.model_path = "/dev/null"
        cfg.compilation_cache_dir = cache_dir
        cfg.precompile.mode = "ladder"
        cfg.precompile.seed_artifact = artifact

        captured = {}

        class StubLauncher:
            experiment_name = "e"
            trial_name = "t"

            def submit(self, name, cmd, env=None):
                captured[name] = (cmd, env or {})

        launch_servers(StubLauncher(), cfg, 1, name_offset=7)
        (cmd, env) = captured["gen_server_7"]
        assert env["JAX_COMPILATION_CACHE_DIR"] == cache_dir
        assert f"--compilation-cache-dir={cache_dir}" in cmd
        assert "--precompile=ladder" in cmd
        # the artifact was unpacked before the spawn
        assert os.path.exists(os.path.join(cache_dir, "jit_x-cache"))

    def test_build_cmd_and_server_flag_parity(self):
        cfg = _tiny_gen_config()
        cfg.model_path = "m"
        cfg.precompile.mode = "replay"
        cfg.precompile.replay_path = "/tmp/ce.jsonl"
        cmd = JaxGenConfig.build_cmd(cfg, "127.0.0.1", 1234)
        assert "--precompile=replay" in cmd
        assert "--precompile-replay=/tmp/ce.jsonl" in cmd
        assert any(
            a.startswith("--compile-events-max-bytes=") for a in cmd
        )
        # the server parser accepts the replay:<path> shorthand
        from areal_tpu.inference.server import main as server_main  # noqa: F401
