"""``chip_smoke.py`` on the CPU: the rehearsal's contract, and that the
parents which start chip-owning children never start a jax backend.

The rehearsal runs in a subprocess (it is an orchestrator of child
processes, and the children must pick their own platform); the ``grpo``
phase costs what tests/test_gsm8k_example.py already costs, so tier-1 runs
the ``parity`` and ``server`` phases and the full rehearsal stays a manual
step (``python chip_smoke.py --rehearse``, see the verify skill).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(argv, timeout, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_over)
    proc = subprocess.run(
        [sys.executable, SMOKE] + argv, env=env, cwd=ROOT, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=timeout,
    )
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines, proc.stderr[-2000:]
    return proc, lines


@pytest.mark.parametrize("phase", ["parity", "server"])
def test_rehearsal_phase_passes_and_last_line_is_the_contract(
    tmp_path, phase
):
    proc, lines = _run(
        ["--rehearse", "--phase", phase, "--out", str(tmp_path / "out")],
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    result = json.loads(lines[0])
    assert result["phase"] == phase and result["ok"] is True
    assert all(result["checks"].values()), result["checks"]


def test_without_a_chip_and_without_rehearse_it_fails(tmp_path):
    """No detection, no fallback: the CPU is chosen by ``--rehearse`` only."""
    proc, lines = _run(
        ["--out", str(tmp_path / "out")], timeout=120, JAX_PLATFORMS="cpu"
    )
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and set(last) == {"ok", "device"}
    # it stopped at the first phase instead of trying the others
    assert [json.loads(l).get("phase") for l in lines[:-2]] == ["grpo"]


@pytest.mark.parametrize(
    "module", ["chip_smoke", "areal_tpu.launcher.local",
               "areal_tpu.launcher.pod"],
)
def test_parents_start_no_jax_backend(module):
    """A parent that has touched a backend holds the chip, and the child
    that needs it then fails or hangs. Checked in a fresh interpreter: this
    test process has long since started its own (CPU) backend."""
    code = (
        f"import sys; sys.path.insert(0, {ROOT!r}); import {module}\n"
        "jax = sys.modules.get('jax')\n"
        "if jax is not None:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "print('jax imported:', jax is not None)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, text=True, timeout=120,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    assert out.returncode == 0, out.stdout[-2000:]
    if module == "chip_smoke":
        assert "jax imported: False" in out.stdout


def test_pod_discovery_and_chip_count_need_no_backend(monkeypatch):
    """The launcher parents read the TPU runtime's environment only."""
    from areal_tpu import platforms
    from areal_tpu.launcher.pod import PodLauncher

    monkeypatch.setattr(
        platforms, "_detect",
        lambda: pytest.fail("launcher parent asked jax for its devices"),
    )
    monkeypatch.delenv("AREAL_POD_HOSTS", raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1")
    assert PodLauncher("e", "t", "/tmp").discover_hosts() == ["w0", "w1"]
    monkeypatch.setenv("TPU_CHIPS_PER_HOST", "4")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert platforms.local_chip_count() == 4
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
    assert platforms.local_chip_count() == 1


def test_local_launcher_refuses_servers_plus_trainer_on_one_chip(
    monkeypatch, tmp_path
):
    from areal_tpu import platforms
    from areal_tpu.api.cli_args import GRPOConfig
    from areal_tpu.launcher import local

    monkeypatch.setattr(platforms, "local_chip_count", lambda: 1)
    monkeypatch.setattr(
        local.LocalLauncher, "submit",
        lambda *a, **k: pytest.fail("started a process on a taken chip"),
    )
    cfg = GRPOConfig()
    cfg.cluster.fileroot = str(tmp_path)
    cfg.allocation_mode = "jaxgen.d1+d1"
    with pytest.raises(ValueError, match="colocated"):
        local.local_main(cfg, "examples/gsm8k_grpo.py", [])


def test_inherited_cache_dir_wins_and_is_not_set_in_code(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: the helper
    uses it over a configured directory, jax reads it itself, and compiled
    programs land there. Fresh interpreter: jax reads the variable when it
    is imported."""
    placed = str(tmp_path / "placed")
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from areal_tpu.utils import compile_cache as cc\n"
        f"placed = {placed!r}\n"
        "assert cc.resolve_cache_dir('/configured') == placed\n"
        "assert cc.enable_compilation_cache('/configured')\n"
        "assert cc.enabled_dir() == placed\n"
        "assert jax.config.jax_compilation_cache_dir == placed\n"
        "assert jax.config.jax_persistent_cache_min_compile_time_secs == 0\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(7)).block_until_ready()\n"
        "assert os.listdir(placed), 'nothing was cached'\n"
        "assert not os.path.exists('/configured')\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=placed,
               JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path), text=True,
        timeout=120, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    assert out.returncode == 0, out.stdout[-2000:]


def test_launcher_keeps_an_inherited_cache_dir(monkeypatch, tmp_path):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.launcher.local import launch_servers
    from areal_tpu.utils.compile_cache import CACHE_DIR_ENV

    captured = {}

    class StubLauncher:
        experiment_name = "e"
        trial_name = "t"

        def submit(self, name, cmd, env=None):
            captured[name] = env or {}

    cfg = JaxGenConfig(model_path="/dev/null")
    cfg.compilation_cache_dir = str(tmp_path / "configured")
    monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / "placed"))
    launch_servers(StubLauncher(), cfg, 1)
    # children inherit the placed directory with the rest of the
    # environment; the launcher exports nothing over it
    assert CACHE_DIR_ENV not in captured["gen_server_0"]
    monkeypatch.delenv(CACHE_DIR_ENV)
    launch_servers(StubLauncher(), cfg, 1)
    assert captured["gen_server_0"][CACHE_DIR_ENV] == cfg.compilation_cache_dir
