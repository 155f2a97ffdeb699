"""TPU-pod launcher: one trainer process per pod worker host.

Role of reference areal/launcher/ray.py:66 (`RayLauncher`) and
launcher/slurm.py (`SlurmLauncher`) — place the trainer constellation
across hosts — re-mapped to TPU pods: every worker host of a slice runs
ONE trainer process; they join a single jax.distributed world (the TPU
runtime wires ICI; jax discovers the slice topology itself when the
processes start under the TPU runtime, and the AREAL_* rendezvous env
covers CPU/mixed fleets).

Remote execution is pluggable (`runner`): the default shells out over ssh
(TPU-VM style, the `gcloud compute tpus tpu-vm ssh --worker=all` pattern);
tests inject a recorder. Generation servers launch through the same
mechanism on the hosts listed in `server_hosts`.
"""

import os
import shlex
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

from areal_tpu.launcher.local import JobException
from areal_tpu.parallel.distributed import (
    COORDINATOR_ENV,
    NUM_PROCESSES_ENV,
    PROCESS_ID_ENV,
)
from areal_tpu.utils import logging as logging_util

logger = logging_util.getLogger("PodLauncher")


def _default_runner(
    host: str, cmd: List[str], env: Dict[str, str], log_path: str
) -> subprocess.Popen:
    """Run `cmd` on `host` over ssh with `env` exported; local hosts
    ("localhost"/"127.0.0.1") spawn directly."""
    if host in ("localhost", "127.0.0.1"):
        full_env = dict(os.environ)
        full_env.update(env)
        logf = open(log_path, "a")
        return subprocess.Popen(
            cmd,
            stdout=logf,
            stderr=subprocess.STDOUT,
            env=full_env,
            start_new_session=True,
        )
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in sorted(env.items())
    )
    remote = f"{exports} {' '.join(shlex.quote(c) for c in cmd)}"
    logf = open(log_path, "a")
    return subprocess.Popen(
        ["ssh", "-o", "StrictHostKeyChecking=no", host, remote],
        stdout=logf,
        stderr=subprocess.STDOUT,
        start_new_session=True,
    )


class PodLauncher:
    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        fileroot: str,
        runner: Optional[Callable] = None,
    ):
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.fileroot = fileroot
        self.runner = runner or _default_runner
        self._procs: Dict[str, subprocess.Popen] = {}

    @property
    def log_dir(self) -> str:
        d = os.path.join(
            self.fileroot, self.experiment_name, self.trial_name, "logs"
        )
        os.makedirs(d, exist_ok=True)
        return d

    def discover_hosts(self) -> List[str]:
        """Worker hosts of this slice: the platform's pod discovery, or
        AREAL_POD_HOSTS for explicit fleets."""
        explicit = os.environ.get("AREAL_POD_HOSTS", "")
        if explicit:
            return [h for h in explicit.split(",") if h]
        # the launcher parent must not start a jax backend (it would hold
        # the chip its trainers need): pod discovery is environment-only
        from areal_tpu.platforms import TpuPlatform

        return TpuPlatform().pod_worker_hosts() or ["localhost"]

    def launch_trainers(
        self,
        trainer_entry: str,
        trainer_argv: List[str],
        hosts: Optional[List[str]] = None,
        coordinator_port: int = 8476,
        base_env: Optional[Dict[str, str]] = None,
        python: str = sys.executable,
    ) -> List[str]:
        """One trainer per host, rendezvoused into one jax.distributed
        world (host 0 coordinates). Returns the job names."""
        hosts = hosts or self.discover_hosts()
        names = []
        for rank, host in enumerate(hosts):
            env = dict(base_env or {})
            env[COORDINATOR_ENV] = f"{hosts[0]}:{coordinator_port}"
            env[NUM_PROCESSES_ENV] = str(len(hosts))
            env[PROCESS_ID_ENV] = str(rank)
            name = f"trainer_{rank}" if rank else "trainer"
            cmd = [python, trainer_entry] + list(trainer_argv)
            log_path = os.path.join(self.log_dir, f"{name}.log")
            self._procs[name] = self.runner(host, cmd, env, log_path)
            logger.info(f"launched {name} on {host}")
            names.append(name)
        return names

    def poll(self) -> Optional[JobException]:
        for name, proc in self._procs.items():
            code = proc.poll()
            if code is not None and code != 0:
                return JobException(name, code)
        return None

    def finished(self, name: str) -> bool:
        proc = self._procs.get(name)
        return proc is not None and proc.poll() == 0

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until the rank-0 trainer finishes (or any job fails)."""
        deadline = time.monotonic() + timeout if timeout else None
        while True:
            exc = self.poll()
            if exc is not None:
                self.stop_all()
                raise exc
            if self.finished("trainer"):
                return
            if deadline and time.monotonic() > deadline:
                raise TimeoutError("pod launcher wait timed out")
            time.sleep(1)

    def stop_all(self):
        import signal

        for proc in self._procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    proc.terminate()
        deadline = time.monotonic() + 10
        for proc in self._procs.values():
            while proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if proc.poll() is None:
                proc.kill()
        self._procs.clear()
