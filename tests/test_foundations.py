"""name_resolve, stats_tracker, config loading, csrc interval ops."""

import numpy as np
import pytest

from areal_tpu.api import cli_args
from areal_tpu.utils import name_resolve, stats_tracker


def test_name_resolve_memory(memory_name_resolve):
    name_resolve.add("a/b/c", "1")
    assert name_resolve.get("a/b/c") == "1"
    with pytest.raises(name_resolve.NameEntryExistsError):
        name_resolve.add("a/b/c", "2")
    name_resolve.add("a/b/c", "2", replace=True)
    assert name_resolve.get("a/b/c") == "2"
    name_resolve.add("a/b/d", "3")
    assert name_resolve.get_subtree("a/b") == ["2", "3"]
    name_resolve.clear_subtree("a")
    with pytest.raises(name_resolve.NameEntryNotFoundError):
        name_resolve.get("a/b/c")


def test_name_resolve_nfs(tmp_path):
    repo = name_resolve.NfsNameRecordRepository(str(tmp_path))
    repo.add("x/y", "v1")
    assert repo.get("x/y") == "v1"
    repo.add_subentry("x/subs", "s1")
    repo.add_subentry("x/subs", "s2")
    assert sorted(repo.get_subtree("x/subs")) == ["s1", "s2"]
    repo.reset()
    with pytest.raises(name_resolve.NameEntryNotFoundError):
        repo.get("x/y")


def test_name_resolve_wait_timeout(memory_name_resolve):
    with pytest.raises(TimeoutError):
        name_resolve.wait("never", timeout=0.2, poll_frequency=0.05)


def test_stats_tracker_masked_avg():
    t = stats_tracker.DistributedStatsTracker()
    mask = np.array([True, True, False, False])
    vals = np.array([1.0, 3.0, 100.0, 100.0])
    t.denominator(tokens=mask)
    t.stat(denominator="tokens", loss=vals)
    out = t.export()
    assert out["loss"] == pytest.approx(2.0)
    assert out["tokens"] == 2.0


def test_stats_tracker_scope_and_types():
    t = stats_tracker.DistributedStatsTracker()
    with t.scope("actor"):
        t.denominator(n=np.array([True, True, True]))
        t.stat(denominator="n", adv=np.array([1.0, 2.0, 6.0]),
               reduce_type=stats_tracker.ReduceType.MAX)
        t.scalar(lr=0.1)
    out = t.export()
    assert out["actor/adv"] == 6.0
    assert out["actor/lr"] == pytest.approx(0.1)


def test_stats_tracker_timing():
    t = stats_tracker.DistributedStatsTracker()
    with t.record_timing("step"):
        pass
    out = t.export()
    assert "timeperf/step" in out


def test_config_yaml_and_overrides(tmp_path):
    cfg_file = tmp_path / "c.yaml"
    cfg_file.write_text(
        """
experiment_name: exp1
trial_name: t0
actor:
  group_size: 8
  optimizer:
    lr: 1.0e-4
"""
    )
    cfg, _ = cli_args.load_expr_config(
        ["--config", str(cfg_file), "actor.eps_clip=0.3", "rollout.max_head_offpolicyness=4"],
        cli_args.GRPOConfig,
    )
    assert cfg.actor.group_size == 8
    assert cfg.actor.optimizer.lr == pytest.approx(1e-4)
    assert cfg.actor.eps_clip == pytest.approx(0.3)
    assert cfg.rollout.max_head_offpolicyness == 4
    # name propagation into subconfigs
    assert cfg.saver.experiment_name == "exp1"
    assert cfg.rollout.trial_name == "t0"


def test_config_rejects_unknown_key(tmp_path):
    with pytest.raises(ValueError):
        cli_args.load_expr_config(["nonexistent.key=1"], cli_args.GRPOConfig)


def test_config_optional_instantiation():
    cfg, _ = cli_args.load_expr_config(["ref.path=/x"], cli_args.GRPOConfig)
    assert cfg.ref is not None and cfg.ref.path == "/x"


def test_csrc_interval_ops():
    csrc = pytest.importorskip("areal_tpu.csrc")
    try:
        merged = csrc.merge_intervals([(0, 3), (3, 7), (9, 12), (12, 13)])
    except Exception as e:
        pytest.skip(f"toolchain unavailable: {e}")
    assert merged == [(0, 7), (9, 13)]
    src = np.arange(20, dtype=np.float32)
    out = csrc.slice_intervals(src, [(2, 5), (10, 12)])
    np.testing.assert_array_equal(out, [2, 3, 4, 10, 11])
    dst = np.zeros(20, dtype=np.float32)
    csrc.set_intervals(out, dst, [(0, 3), (5, 7)])
    np.testing.assert_array_equal(dst[:7], [2, 3, 4, 0, 0, 10, 11])
    bf = src.astype(np.float16)
    out16 = csrc.slice_intervals(bf, [(1, 4)])
    np.testing.assert_array_equal(out16, [1, 2, 3])
    groups = csrc.ffd_allocate([5, 9, 3, 7, 2, 8], capacity=10)
    sizes = [5, 9, 3, 7, 2, 8]
    assert sorted(x for g in groups for x in g) == list(range(6))
    for g in groups:
        assert sum(sizes[i] for i in g) <= 10


def test_seeding_deterministic():
    from areal_tpu.utils import seeding

    seeding.set_random_seed(123, "trainer")
    a = seeding.get_seed("dataloader")
    seeding.set_random_seed(123, "trainer")
    assert seeding.get_seed("dataloader") == a
    assert seeding.get_seed("sampling") != a


def test_freq_ctl():
    from areal_tpu.utils.timeutil import EpochStepTimeFreqCtl

    ctl = EpochStepTimeFreqCtl(freq_step=3)
    fires = [ctl.check(0, 1) for _ in range(7)]
    assert fires == [False, False, True, False, False, True, False]
    state = ctl.state_dict()
    ctl2 = EpochStepTimeFreqCtl(freq_step=3)
    ctl2.load_state_dict(state)
    assert ctl2.check(0, 1) is False
    assert ctl2.check(0, 1) is True


def test_stats_tracker_cadence_mismatch():
    # a stat recorded against an earlier mask must reduce with THAT mask
    t = stats_tracker.DistributedStatsTracker()
    t.denominator(m=np.array([True, False]))
    t.stat(denominator="m", x=np.array([1.0, 100.0]))
    t.denominator(m=np.array([False, True]))
    out = t.export()
    assert out["x"] == pytest.approx(1.0)


def test_stats_tracker_scope_is_thread_local():
    """Concurrent recorders must not interleave scope names into each
    other's keys (the scope stack was a shared list mutated outside the
    lock): two threads holding different scopes at the same time must
    each record under their OWN scope."""
    import threading

    t = stats_tracker.DistributedStatsTracker()
    barrier = threading.Barrier(2, timeout=10)
    errors = []

    def worker(scope_name, n_iters=200):
        try:
            barrier.wait()
            for _ in range(n_iters):
                with t.scope(scope_name):
                    # both threads are inside their scopes simultaneously;
                    # with a shared stack the key would come out as e.g.
                    # "a/b/x" or pop() would raise
                    t.scalar(x=1.0)
        except Exception as e:  # pragma: no cover - the regression signal
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(s,)) for s in ("a", "b")
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    out = t.export()
    assert set(out) == {"a/x", "b/x"}
    assert out["a/x"] == 1.0 and out["b/x"] == 1.0


class TestStatsTrackerExport:
    """Per-reduce-type vectors, denominator-count fallback, mask binding
    across minibatches, and reset/key-prefix filtering semantics."""

    def test_all_reduce_types(self):
        t = stats_tracker.DistributedStatsTracker()
        mask = np.array([True, True, True, False])
        vals = np.array([1.0, 2.0, 9.0, 555.0])
        t.denominator(n=mask)
        rt = stats_tracker.ReduceType
        t.stat(denominator="n", avg=vals)  # default AVG
        t.stat(denominator="n", total=vals, reduce_type=rt.SUM)
        t.stat(denominator="n", lo=vals, reduce_type=rt.MIN)
        t.stat(denominator="n", hi=vals, reduce_type=rt.MAX)
        t.scalar(s=2.0)
        t.scalar(s=4.0)
        out = t.export()
        assert out["avg"] == pytest.approx(4.0)  # (1+2+9)/3, mask applied
        assert out["total"] == pytest.approx(12.0)
        assert out["lo"] == 1.0
        assert out["hi"] == 9.0
        assert out["s"] == pytest.approx(3.0)  # scalars average
        assert out["n"] == 3.0  # denominator count rides along

    def test_empty_selection_yields_zero(self):
        t = stats_tracker.DistributedStatsTracker()
        t.denominator(n=np.array([False, False]))
        t.stat(denominator="n", x=np.array([7.0, 7.0]))
        out = t.export()
        assert out["x"] == 0.0
        assert out["n"] == 0.0

    def test_shape_mismatch_falls_back_to_full_mask(self):
        t = stats_tracker.DistributedStatsTracker()
        t.denominator(n=np.array([True, False]))
        # value shape differs from the mask → reduces over everything
        t.stat(denominator="n", x=np.array([1.0, 2.0, 3.0]))
        assert t.export()["x"] == pytest.approx(2.0)

    def test_mask_binding_across_minibatches(self):
        # each stat reduces with the mask current AT RECORD TIME, even
        # when later minibatches register fresh masks
        t = stats_tracker.DistributedStatsTracker()
        t.denominator(m=np.array([True, False]))
        t.stat(denominator="m", x=np.array([1.0, 100.0]))
        t.denominator(m=np.array([False, True]))
        t.stat(denominator="m", x=np.array([100.0, 5.0]))
        out = t.export()
        assert out["x"] == pytest.approx(3.0)  # mean of 1 and 5
        assert out["m"] == 2.0  # both masks counted

    def test_key_prefix_filter_and_reset(self):
        t = stats_tracker.DistributedStatsTracker()
        with t.scope("actor"):
            t.scalar(lr=0.1)
            t.denominator(n=np.array([True]))
            t.stat(denominator="n", loss=np.array([2.0]))
        with t.scope("critic"):
            t.scalar(lr=0.5)
        # prefix export returns only that subtree and resets only it
        out = t.export(key="actor")
        assert set(out) == {"actor/lr", "actor/loss", "actor/n"}
        out2 = t.export()
        assert set(out2) == {"critic/lr"}
        # everything consumed now
        assert t.export() == {}

    def test_export_without_reset_keeps_state(self):
        t = stats_tracker.DistributedStatsTracker()
        t.scalar(a=1.0)
        assert t.export(reset=False)["a"] == 1.0
        assert t.export()["a"] == 1.0  # still there until a reset export
        assert t.export() == {}

    def test_scalar_accumulation_is_bounded(self, monkeypatch):
        # producers without a consumer (eval-only runs never export) must
        # not grow the per-key lists forever; past the cap the key
        # collapses to its running mean
        monkeypatch.setattr(stats_tracker, "_MAX_SCALARS_PER_KEY", 8)
        t = stats_tracker.DistributedStatsTracker()
        for _ in range(100):
            t.scalar(x=2.0)
        assert len(t._scalars["x"]) <= 8
        assert t.export()["x"] == pytest.approx(2.0)

    def test_unknown_denominator_raises(self):
        t = stats_tracker.DistributedStatsTracker()
        with pytest.raises(ValueError, match="unknown denominator"):
            t.stat(denominator="nope", x=np.array([1.0]))
        with pytest.raises(ValueError, match="must be boolean"):
            t.denominator(bad=np.array([1.0, 0.0]))


def test_stats_logger_sanitizes_nonfinite(tmp_path, no_tensorboard):
    """json.dumps(nan) emits a bare ``NaN`` token — not JSON. The JSONL
    sink must write null instead so downstream parsers survive."""
    import json as _json

    from areal_tpu.utils.stats_logger import StatsLogger

    slog = StatsLogger("nanexp", "t0", str(tmp_path))
    slog.commit(
        0, 0, 0,
        {"ok": 1.5, "bad": float("nan"), "inf": float("inf"),
         "ninf": float("-inf")},
    )
    slog.close()
    path = tmp_path / "nanexp" / "t0" / "stats.jsonl"
    line = path.read_text().strip()
    assert "NaN" not in line and "Infinity" not in line
    rec = _json.loads(line)  # strict parse must succeed
    assert rec["ok"] == 1.5
    assert rec["bad"] is None and rec["inf"] is None and rec["ninf"] is None


def test_profiling_env_override_merges_config(monkeypatch, tmp_path):
    """AREAL_PROFILE_STEPS must merge enabled/steps into the EXISTING
    config instead of rebuilding it — other configured fields survive."""
    import dataclasses as _dc

    from areal_tpu.api.cli_args import ProfilingConfig
    from areal_tpu.utils.profiling import PhaseProfiler

    @_dc.dataclass
    class ExtendedProfilingConfig(ProfilingConfig):
        annotate_phases: bool = True  # stand-in for any future YAML field

    cfg = ExtendedProfilingConfig(enabled=False, steps=[99],
                                  annotate_phases=True)
    monkeypatch.setenv("AREAL_PROFILE_STEPS", "3,4")
    prof = PhaseProfiler(cfg, str(tmp_path), "e", "t")
    assert prof.config.enabled is True
    assert prof.config.steps == [3, 4]
    # the non-overridden field survives the merge
    assert isinstance(prof.config, ExtendedProfilingConfig)
    assert prof.config.annotate_phases is True
    assert prof.should_trace(3) and not prof.should_trace(99)
    # malformed env is ignored, config untouched
    monkeypatch.setenv("AREAL_PROFILE_STEPS", "3,x")
    prof2 = PhaseProfiler(cfg, str(tmp_path), "e", "t")
    assert prof2.config.enabled is False and prof2.config.steps == [99]


def test_colocate_backend_roundtrip():
    from areal_tpu.api.alloc_mode import AllocationMode

    am = AllocationMode.from_str("fsdp:d4t2")
    assert am.train_backend == "fsdp"
    assert AllocationMode.from_str(am.to_str()) == am


def test_port_lock_stale_reclaim(tmp_path, monkeypatch):
    from areal_tpu.utils import network

    monkeypatch.setattr(network, "_LOCK_DIR", str(tmp_path))
    lock = tmp_path / "12345"
    lock.write_text("999999999")  # dead pid
    assert network._claim_lock(str(lock)) is True
    assert lock.read_text() == str(__import__("os").getpid())
