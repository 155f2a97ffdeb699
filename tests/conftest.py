"""Test config: run everything on a virtual 8-device CPU mesh.

The tests never use a chip: sharding logic is exercised on XLA's host
platform with 8 virtual devices. The provisioning recipe lives in
``__graft_entry__._ensure_virtual_devices`` (the multi-chip dry run uses the
same helper) — it forces the CPU platform in the environment AND in the live
jax config, because jax may already be imported by the time this runs and
then env vars alone are ignored. What runs on the chip is ``chip_smoke.py``.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from __graft_entry__ import _ensure_virtual_devices  # noqa: E402

_ensure_virtual_devices(8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1"
    )
    config.addinivalue_line(
        "markers",
        "chaos: chaos-injection resilience tests (fleet failover, "
        "deterministic fault harness — utils/chaos.py)",
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Tier-1 wall-time budget tracking: the suite runs against a hard
    cap, so every run prints its slowest tests (setup+call+teardown per
    nodeid) — a PR that regresses the budget is visible in its own CI
    output, not discovered at the next cap overrun."""
    durations = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            d = getattr(rep, "duration", None)
            nodeid = getattr(rep, "nodeid", None)
            if d is None or not nodeid:
                continue
            durations[nodeid] = durations.get(nodeid, 0.0) + d
    if not durations:
        return
    top = sorted(durations.items(), key=lambda kv: -kv[1])[:10]
    total = sum(durations.values())
    tr = terminalreporter
    tr.write_sep("=", "slowest tests (tier-1 budget report)")
    for nodeid, d in top:
        tr.write_line(f"{d:8.2f}s  {nodeid}")
    tr.write_line(
        f"{total:8.2f}s  total across {len(durations)} tests"
    )


@pytest.fixture(scope="session", autouse=True)
def shared_worker_compile_cache(tmp_path_factory):
    """One persistent XLA compile cache shared by every
    genserver_worker SUBPROCESS in the session (r14 cold-start plane,
    tests/genserver_worker.py AREAL_WORKER_COMPILE_CACHE): the chaos /
    failover / weight tests spawn many tiny servers with identical
    shapes, and each used to re-pay the same compile storm — the first
    worker warms the cache, the rest replay from disk. Fresh per
    session (tmp dir), so runs stay hermetic; tests that need a COLD
    subprocess (test_precompile's cold control) override the env var
    per spawn."""
    if os.environ.get("AREAL_WORKER_COMPILE_CACHE"):
        yield
        return
    d = str(tmp_path_factory.mktemp("worker_xla_cache"))
    os.environ["AREAL_WORKER_COMPILE_CACHE"] = d
    yield
    os.environ.pop("AREAL_WORKER_COMPILE_CACHE", None)


@pytest.fixture
def no_tensorboard(monkeypatch):
    """``StatsLogger`` without its tensorboard sink, for the cases that are
    of its JSONL sink: ``torch.utils.tensorboard`` imports TensorFlow, 12 s
    of a worker's time alone and 53 s beside five busy ones (ROADMAP C.17),
    once in each worker that builds a logger."""
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)


@pytest.fixture
def memory_name_resolve():
    from areal_tpu.utils import name_resolve

    repo = name_resolve.reconfigure("memory")
    yield repo
    repo.reset()
