"""Test config: run everything on a virtual 8-device CPU mesh.

The tests never use a chip: sharding logic is exercised on XLA's host
platform with 8 virtual devices. The provisioning recipe lives in
``__graft_entry__._ensure_virtual_devices`` (the multi-chip dry run uses the
same helper) — it forces the CPU platform in the environment AND in the live
jax config, because jax may already be imported by the time this runs and
then env vars alone are ignored. What runs on the chip is ``chip_smoke.py``.
"""

import faulthandler
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from __graft_entry__ import _ensure_virtual_devices  # noqa: E402

_ensure_virtual_devices(8)

import pytest  # noqa: E402

# The most one test's setup, call or teardown may take: three times the
# slowest test of the six-worker run (a CPU rehearsal of the benchmark's,
# 178-215 s beside five busy workers: PERF.md section 7, ah), which a busy
# machine does not reach. A constant, not an option.
TEST_LIMIT_S = 600


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
    nodeid) and its costliest FILES: ``--dist loadfile`` hands a file to
    one worker, so a file's seconds are the least the run's tail can be.
    A PR that regresses the budget is visible in its own CI output, not
    discovered at the next cap overrun."""
    durations, files = {}, {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            d = getattr(rep, "duration", None)
            nodeid = getattr(rep, "nodeid", None)
            if d is None or not nodeid:
                continue
            durations[nodeid] = durations.get(nodeid, 0.0) + d
            path = nodeid.split("::")[0]
            files[path] = files.get(path, 0.0) + d
    if not durations:
        return
    tr = terminalreporter
    for title, table in (("tests", durations), ("files", files)):
        tr.write_sep("=", f"slowest {title} (tier-1 budget report)")
        for name, d in sorted(table.items(), key=lambda kv: -kv[1])[:10]:
            tr.write_line(f"{d:8.2f}s  {name}")
    tr.write_line(
        f"{sum(durations.values()):8.2f}s  total across {len(durations)} "
        f"tests in {len(files)} files"
    )


def _fail_at_the_limit(signum, frame):
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    pytest.fail(
        f"still running after {TEST_LIMIT_S} s (the limit of one test's "
        "setup, call or teardown: tests/conftest.py); every thread's stack "
        "is in the captured stderr",
        pytrace=True,
    )


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    """One phase of one test under ``TEST_LIMIT_S``: at the limit the phase
    fails with the stack it was in (``Failed`` is no ``Exception``: a retry
    loop's ``except Exception`` does not swallow it), its fixtures tear
    down, and the run goes on to the next test. What holds the interpreter
    inside C code and never returns is not interrupted by a signal: the
    worker dumps its stacks and exits a minute past the limit, and xdist
    reports the test as the one that took it down."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    was = signal.signal(signal.SIGALRM, _fail_at_the_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_LIMIT_S + 60, exit=True)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, was)


pytest_runtest_call = pytest_runtest_teardown = pytest_runtest_setup


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
