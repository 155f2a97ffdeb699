"""Local launcher: spawn generation servers + trainer on one host.

Role of reference areal/launcher/local.py (`LocalLauncher`, `local_main`):
parse the allocation mode, start one generation-server subprocess per gen
replica, pass their addresses to the trainer via ``AREAL_LLM_SERVER_ADDRS``,
run the trainer, watch liveness, and auto-restart the whole constellation on
failure up to ``recover.retries`` when recover mode allows
(local.py:332-359).

TPU notes: device assignment works by sub-slice environment
(``TPU_VISIBLE_CHIPS``/``JAX_PLATFORMS``) rather than CUDA_VISIBLE_DEVICES;
on a single-chip host the colocated mode (no server subprocesses, trainer
owns the chip) is the default and this launcher simply execs the trainer.
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional

from areal_tpu.api.alloc_mode import AllocationMode, AllocationType
from areal_tpu.api.cli_args import BaseExperimentConfig, JaxGenConfig
from areal_tpu.utils import logging as logging_util, network
from areal_tpu.utils.http import backoff_delay
from areal_tpu.utils.recover import RECOVER_ENV

logger = logging_util.getLogger("LocalLauncher")


class JobException(Exception):
    def __init__(self, name: str, code: int):
        super().__init__(f"job {name} exited with code {code}")
        self.name = name
        self.code = code


class LocalLauncher:
    def __init__(self, experiment_name: str, trial_name: str, fileroot: str):
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.fileroot = fileroot
        self._procs: Dict[str, subprocess.Popen] = {}

    @property
    def log_dir(self) -> str:
        d = os.path.join(
            self.fileroot, self.experiment_name, self.trial_name, "logs"
        )
        os.makedirs(d, exist_ok=True)
        return d

    def submit(
        self,
        name: str,
        cmd: List[str],
        env: Optional[Dict[str, str]] = None,
    ) -> subprocess.Popen:
        log_path = os.path.join(self.log_dir, f"{name}.log")
        full_env = dict(os.environ)
        if env:
            full_env.update(env)
        with open(log_path, "a") as logf:
            proc = subprocess.Popen(
                cmd,
                stdout=logf,
                stderr=subprocess.STDOUT,
                env=full_env,
                start_new_session=True,
            )
        self._procs[name] = proc
        logger.info(f"started {name} (pid {proc.pid}) → {log_path}")
        return proc

    def poll(self) -> Optional[JobException]:
        # snapshot: the autoscaler launch/reap threads insert and pop
        # jobs concurrently with this 1 Hz sweep — iterating the live
        # dict would raise "changed size during iteration"
        for name, proc in list(self._procs.items()):
            code = proc.poll()
            if code is not None and code != 0:
                return JobException(name, code)
        return None

    def finished(self, name: str) -> bool:
        proc = self._procs.get(name)
        return proc is not None and proc.poll() == 0

    def alive(self, name: str) -> bool:
        proc = self._procs.get(name)
        return proc is not None and proc.poll() is None

    def stop(self, name: str) -> None:
        """Stop ONE job (TERM, then KILL) and forget it — the supervisor
        restarts the trainer without tearing down live gen servers."""
        proc = self._procs.pop(name, None)
        if proc is None:
            return
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            deadline = time.monotonic() + 10
            while proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass

    def stop_all(self):
        procs = list(self._procs.values())  # concurrent-mutation safe
        for proc in procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + 10
        for proc in procs:
            while proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        self._procs.clear()


def launch_servers(
    launcher: LocalLauncher,
    gen_config: JaxGenConfig,
    n_servers: int,
    base_env: Optional[Dict[str, str]] = None,
    name_offset: int = 0,
) -> List[str]:
    """Start n generation-server subprocesses; returns host:port addrs.
    ``name_offset`` keeps job names unique when the autoscaler adds
    servers after launch."""
    ports = network.find_free_ports(n_servers)
    addrs = []
    if gen_config.compilation_cache_dir:
        # export the cache dir as env too (not only the CLI flag the
        # server forwards to the engine): jax reads
        # JAX_COMPILATION_CACHE_DIR at interpreter start, so every
        # restart of a server replays its compiles from disk instead of
        # re-paying the decode bucket-ladder warmup. An INHERITED value
        # stays: whoever started the launcher placed the cache
        # (utils/compile_cache.resolve_cache_dir), and the children
        # inherit it with the rest of the environment.
        from areal_tpu.utils.compile_cache import CACHE_DIR_ENV

        base_env = dict(base_env or {})
        if not os.environ.get(CACHE_DIR_ENV):
            base_env[CACHE_DIR_ENV] = gen_config.compilation_cache_dir
        seed = getattr(
            getattr(gen_config, "precompile", None), "seed_artifact", ""
        )
        if seed:
            # cold-start elimination (r14): unpack the warmed-cache seed
            # artifact into the cache dir BEFORE the spawn, so
            # autoscaler scale-ups and supervisor full-constellation
            # restarts warm from disk within the spike instead of
            # re-paying the compile storm. Idempotent — existing
            # entries are never clobbered.
            from areal_tpu.utils.compile_cache import (
                ensure_seeded,
                resolve_cache_dir,
            )

            ensure_seeded(
                resolve_cache_dir(gen_config.compilation_cache_dir), seed
            )
    for i in range(n_servers):
        host = gen_config.host or "127.0.0.1"
        cmd = JaxGenConfig.build_cmd(
            gen_config, host, ports[i],
            experiment_name=launcher.experiment_name,
            trial_name=launcher.trial_name,
        )
        cmd.append(f"--server-index={name_offset + i}")
        launcher.submit(f"gen_server_{name_offset + i}", cmd, env=base_env)
        addrs.append(f"{host}:{ports[i]}")
    return addrs


def launch_env_workers(
    launcher: LocalLauncher,
    env_cfg,
    base_env: Optional[Dict[str, str]] = None,
    name_offset: int = 0,
) -> List[str]:
    """Start env-service worker subprocesses (env/service.py); returns
    host:port addrs. Workers self-register under name_resolve
    env_servers, so FleetMonitor membership and name_resolve discovery
    also find RESPAWNED replacements (new ports). The
    AREAL_ENV_SERVER_ADDRS export is a boot-time snapshot only — a
    running trainer's env var cannot be updated, so clients that must
    survive worker replacement discover via name_resolve (pass
    experiment/trial to RemoteEnv, or give it an env_fleet_monitor)."""
    n = max(1, int(env_cfg.n_workers))
    ports = network.find_free_ports(n)
    addrs = []
    for i in range(n):
        host = env_cfg.host or "127.0.0.1"
        cmd = [
            sys.executable,
            "-m",
            "areal_tpu.env.service",
            f"--env={env_cfg.env_spec}",
            f"--host={host}",
            f"--port={ports[i]}",
            f"--max-sessions={env_cfg.max_sessions}",
            f"--session-ttl={env_cfg.session_ttl_s}",
            f"--experiment-name={launcher.experiment_name}",
            f"--trial-name={launcher.trial_name}",
        ]
        launcher.submit(f"env_worker_{name_offset + i}", cmd, env=base_env)
        addrs.append(f"{host}:{ports[i]}")
    return addrs


class TrainerSupervisor:
    """Bounded-restart policy for the trainer process (the durability
    loop the ``RECOVER_ENV`` docstring promises): a budget of ``retries``
    restarts with exponential backoff between attempts, refunded after a
    healthy uptime — a long-lived service that crashes once a week must
    not exhaust a lifetime cap, while a crash-looping trainer still stops
    after ``retries`` tries."""

    def __init__(
        self,
        retries: int,
        backoff_s: float = 2.0,
        max_backoff_s: float = 60.0,
        healthy_uptime_s: float = 600.0,
        attempt: int = 0,
        jitter: float = 0.5,
    ):
        self.retries = retries
        self.backoff_s = backoff_s
        self.max_backoff_s = max_backoff_s
        self.healthy_uptime_s = healthy_uptime_s
        self.attempt = attempt
        # jittered so multi-host supervised restarts don't relaunch (and
        # re-hit shared storage / the fleet) in lockstep
        self.jitter = jitter
        self._started = time.monotonic()

    def note_start(self) -> None:
        self._started = time.monotonic()

    def should_restart(self) -> bool:
        if time.monotonic() - self._started >= self.healthy_uptime_s:
            self.attempt = 0  # a long healthy run refunds the budget
        return self.attempt < self.retries

    def next_backoff(self) -> float:
        """Consume one restart from the budget; returns the delay to
        sleep before relaunching (the repo's one backoff policy —
        utils/http.backoff_delay)."""
        delay = backoff_delay(
            self.attempt, self.backoff_s, self.max_backoff_s, self.jitter
        )
        self.attempt += 1
        return delay


def local_main(
    config: BaseExperimentConfig,
    trainer_entry: str,
    trainer_argv: List[str],
    recover_retries: Optional[int] = None,
    _attempt: int = 0,
):
    """Launch the experiment constellation under a bounded-restart
    supervisor (reference local.py:252-359). On trainer death with
    recover enabled, the trainer is relaunched with
    ``AREAL_TPU_RECOVER_RUN=1`` so `RecoverHandler.load` resumes from the
    last committed checkpoint; live gen servers are kept (their compiled
    programs survive, and load() re-pushes the recovered weights). A
    dead server forces a full-constellation restart instead."""
    alloc = (
        AllocationMode.from_str(config.allocation_mode)
        if config.allocation_mode
        else None
    )
    if alloc is not None and alloc.train is not None:
        # fail fast on factors the TPU backend doesn't implement (p>1)
        alloc.train.to_tpu_parallelism()
    launcher = LocalLauncher(
        config.experiment_name, config.trial_name, config.cluster.fileroot
    )
    retries = (
        recover_retries
        if recover_retries is not None
        else getattr(config.recover, "retries", 0)
    )
    recover_enabled = getattr(config.recover, "mode", "disabled") in (
        "auto",
        "fault",
    )
    supervisor = TrainerSupervisor(
        retries if recover_enabled else 0, attempt=_attempt
    )
    base_env: Dict[str, str] = {}
    # every subprocess (servers AND trainer) rendezvous in the same
    # name_resolve namespace: server registration/deregistration is
    # what drives dynamic fleet membership (inference/fleet.py), so
    # it must land where the trainer's FleetMonitor watches
    nr = getattr(config.cluster, "name_resolve", None)
    if nr is not None:
        from areal_tpu.utils.name_resolve import BACKEND_ENV

        if nr.type == "nfs":
            base_env[BACKEND_ENV] = f"nfs:{nr.nfs_record_root}"
        elif nr.type == "kv" and getattr(nr, "kv_address", ""):
            base_env[BACKEND_ENV] = f"kv:{nr.kv_address}"

    wants_servers = alloc is not None and alloc.type_ in (
        AllocationType.DECOUPLED_TRAIN,
        AllocationType.LLM_SERVER_ONLY,
    )
    wants_trainer = (
        alloc is None or alloc.type_ != AllocationType.LLM_SERVER_ONLY
    )
    n_trainers = max(1, getattr(config.launcher, "trainer_processes", 1))
    if wants_servers and wants_trainer:
        # a chip belongs to one process at a time: on a one-chip host the
        # second process to reach it hangs or dies, so refuse here, in the
        # parent (which itself never starts a jax backend)
        from areal_tpu.platforms import local_chip_count

        chips = local_chip_count()
        wanted = alloc.gen.data_parallel_size + n_trainers
        if chips == 1:
            raise ValueError(
                f"allocation {config.allocation_mode!r} asks for {wanted} processes "
                "(generation servers + trainer) on a host with one TPU "
                "chip, and a chip serves one process. Use colocated mode: "
                "run the trainer entry alone with no allocation_mode / "
                "AREAL_LLM_SERVER_ADDRS, and it generates in-process."
            )
    trainer_names = [
        f"trainer_{r}" if r else "trainer" for r in range(n_trainers)
    ]
    server_names: List[str] = []
    server_addrs: List[str] = []
    env_cfg = getattr(config, "env_service", None)
    wants_env_workers = bool(
        env_cfg is not None
        and getattr(env_cfg, "enabled", False)
        and getattr(env_cfg, "env_spec", "")
    )
    env_worker_names: List[str] = []
    env_worker_addrs: Dict[str, str] = {}  # name -> addr (live view)
    env_respawns = {"n": 0}
    env_worker_seq = {"n": 0}

    def start_env_workers(env: Dict[str, str]) -> None:
        addrs = launch_env_workers(
            launcher, env_cfg, env, name_offset=env_worker_seq["n"]
        )
        for i, addr in enumerate(addrs):
            name = f"env_worker_{env_worker_seq['n'] + i}"
            env_worker_names.append(name)
            env_worker_addrs[name] = addr
        env_worker_seq["n"] += len(addrs)

    server_seq = {"n": 0}
    server_name_by_addr: Dict[str, str] = {}
    # SLO traffic plane: the rollout config's TrafficConfig drives a
    # launcher-hosted autoscaler (the launcher is the one process that
    # can actually SPAWN a server)
    traffic_cfg = getattr(
        getattr(config, "rollout", None), "traffic", None
    )
    autoscaler = None

    def _server_cfg() -> JaxGenConfig:
        server_cfg = getattr(config, "server", None) or JaxGenConfig()
        # per-server tensor parallelism comes from the allocation mode
        # (reference: SGLang tp wired at areal/launcher/local.py:277-306)
        if alloc.gen.tensor_parallel_size > 1:
            server_cfg.tensor_parallel_size = alloc.gen.tensor_parallel_size
        return server_cfg

    def start_servers(env: Dict[str, str]) -> None:
        n_servers = alloc.gen.data_parallel_size
        server_addrs[:] = launch_servers(
            launcher, _server_cfg(), n_servers, env,
            name_offset=server_seq["n"],
        )
        server_names[:] = [
            f"gen_server_{server_seq['n'] + i}" for i in range(n_servers)
        ]
        server_name_by_addr.clear()
        server_name_by_addr.update(
            dict(zip(server_addrs, server_names))
        )
        server_seq["n"] += n_servers

    def scale_up_one() -> None:
        """Autoscaler launch_fn: one more generation server; it
        self-registers under name_resolve so fleet membership (trainer
        client + any router) discovers it without a restart."""
        env = dict(base_env)
        addr = launch_servers(
            launcher, _server_cfg(), 1, env,
            name_offset=server_seq["n"],
        )[0]
        name = f"gen_server_{server_seq['n']}"
        server_seq["n"] += 1
        server_addrs.append(addr)
        server_names.append(name)
        server_name_by_addr[addr] = name

    def scale_down_drain(addr: str) -> None:
        """Autoscaler drain_fn: POST /drain (graceful — the server
        finishes in-flight work, then deregisters), then reap the empty
        process in the background. Zero rollouts are lost: in-flight
        requests complete, and clients suffix-resume anything that
        would have landed here."""
        try:
            req = urllib.request.Request(
                f"http://{addr}/drain", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
        except Exception as e:
            logger.warning(f"autoscaler drain of {addr} failed: {e}")
            return

        def _reap():
            deadline = time.monotonic() + 600
            probe_fails = 0
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(
                        f"http://{addr}/health", timeout=5
                    ) as r:
                        body = json.loads(r.read())
                    probe_fails = 0
                    if (
                        body.get("running_requests", 0)
                        + body.get("queued_requests", 0)
                        <= 0
                    ):
                        break
                except Exception:
                    # one transient probe timeout must not kill a
                    # server that still holds in-flight work — only a
                    # SUSTAINED unreachable drainee counts as gone
                    probe_fails += 1
                    if probe_fails >= 3:
                        break
                time.sleep(0.5)
            name = server_name_by_addr.pop(addr, None)
            if addr in server_addrs:
                server_addrs.remove(addr)
            if name:
                if name in server_names:
                    server_names.remove(name)
                launcher.stop(name)
                logger.info(
                    f"autoscaler: drained + stopped {name} ({addr})"
                )

        threading.Thread(target=_reap, daemon=True).start()

    def start_trainers(env: Dict[str, str]) -> None:
        if n_trainers == 1:
            launcher.submit(
                "trainer",
                [sys.executable, trainer_entry] + trainer_argv,
                env=env,
            )
            return
        # one jax.distributed world of N local trainer processes
        # (multi-host skeleton; reference: torchrun rendezvous)
        from areal_tpu.parallel.distributed import (
            COORDINATOR_ENV,
            NUM_PROCESSES_ENV,
            PROCESS_ID_ENV,
        )

        port = network.find_free_ports(1)[0]
        for rank in range(n_trainers):
            trainer_env = dict(env)
            trainer_env[COORDINATOR_ENV] = f"127.0.0.1:{port}"
            trainer_env[NUM_PROCESSES_ENV] = str(n_trainers)
            trainer_env[PROCESS_ID_ENV] = str(rank)
            launcher.submit(
                trainer_names[rank],
                [sys.executable, trainer_entry] + trainer_argv,
                env=trainer_env,
            )

    try:
        servers_up = False
        while True:
            env = dict(base_env)
            if supervisor.attempt > 0 and recover_enabled:
                env[RECOVER_ENV] = "1"
            if wants_servers and not servers_up:
                start_servers(env)
                servers_up = True
            if (
                autoscaler is None
                and wants_servers
                and traffic_cfg is not None
                and traffic_cfg.autoscale
            ):
                from areal_tpu.inference.fleet import FleetAutoscaler

                autoscaler = FleetAutoscaler(
                    traffic_cfg,
                    launch_fn=scale_up_one,
                    drain_fn=scale_down_drain,
                    addresses_fn=lambda: list(server_addrs),
                ).start()
                logger.info(
                    f"fleet autoscaler on: "
                    f"[{traffic_cfg.min_servers}, "
                    f"{traffic_cfg.max_servers}] servers, "
                    f"eval every {traffic_cfg.autoscale_interval_s}s"
                )
            if wants_env_workers and not env_worker_names:
                start_env_workers(env)
            if server_addrs:
                env["AREAL_LLM_SERVER_ADDRS"] = ",".join(server_addrs)
            if env_worker_addrs:
                env["AREAL_ENV_SERVER_ADDRS"] = ",".join(
                    env_worker_addrs.values()
                )
            if wants_trainer:
                start_trainers(env)
            supervisor.note_start()
            # watch loop
            exc: Optional[JobException] = None
            while True:
                exc = launcher.poll()
                if exc is not None and exc.name in env_worker_names:
                    # env-worker death is survivable BY DESIGN (the env
                    # service plane replays sessions onto healthy
                    # workers) — replace the worker in place instead of
                    # tearing down the constellation, up to a bounded
                    # respawn budget; the replacement re-registers and
                    # membership finds it
                    launcher.stop(exc.name)
                    env_worker_names.remove(exc.name)
                    env_worker_addrs.pop(exc.name, None)
                    if (
                        env_respawns["n"]
                        < getattr(env_cfg, "max_worker_respawns", 8)
                    ):
                        env_respawns["n"] += 1
                        logger.warning(
                            f"{exc}; respawning env worker "
                            f"({env_respawns['n']}/"
                            f"{env_cfg.max_worker_respawns})"
                        )
                        one = dataclasses.replace(env_cfg, n_workers=1)
                        addr = launch_env_workers(
                            launcher, one, env,
                            name_offset=env_worker_seq["n"],
                        )[0]
                        name = f"env_worker_{env_worker_seq['n']}"
                        env_worker_seq["n"] += 1
                        env_worker_names.append(name)
                        env_worker_addrs[name] = addr
                        exc = None
                        continue
                    logger.error(
                        f"{exc}; env-worker respawn budget spent "
                        f"({env_cfg.max_worker_respawns}) — escalating"
                    )
                if exc is not None:
                    break
                if wants_trainer and launcher.finished("trainer"):
                    logger.info("trainer finished")
                    return
                time.sleep(1)
            if not (recover_enabled and supervisor.should_restart()):
                raise exc
            delay = supervisor.next_backoff()
            trainer_died = exc.name in trainer_names
            servers_alive = all(launcher.alive(n) for n in server_names)
            if trainer_died and servers_alive:
                # trainer-only restart: keep the warm fleet, relaunch the
                # trainer with RECOVER_ENV so it resumes from the last
                # committed checkpoint and re-pushes weights on load()
                logger.warning(
                    f"{exc}; restarting trainer only "
                    f"(attempt {supervisor.attempt}/{retries}, "
                    f"backoff {delay:.1f}s, {len(server_names)} servers "
                    f"kept alive)"
                )
                for name in trainer_names:
                    launcher.stop(name)
            else:
                logger.warning(
                    f"{exc}; restarting the full constellation "
                    f"(attempt {supervisor.attempt}/{retries}, "
                    f"backoff {delay:.1f}s)"
                )
                launcher.stop_all()
                servers_up = False
                server_addrs.clear()
                server_names.clear()
                env_worker_names.clear()
                env_worker_addrs.clear()
            time.sleep(delay)
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        launcher.stop_all()
