"""jax-profiler trace capture per training phase.

Role of the reference's per-MFC torch-profiler integration
(realhf/system/model_worker.py:829-910 `__maybe_profile_rpc` dumping
kineto traces; realhf/base/monitor.py trace post-processing): when
enabled, chosen train-loop steps run under `jax.profiler.trace`, dumping
TensorBoard-loadable XPlane traces (device timelines, XLA op breakdown,
HLO cost attribution) under
``{fileroot}/{experiment}/{trial}/traces/step{N}``.

Usage in a train loop:

    profiler = PhaseProfiler(config.profiling, fileroot, exp, trial)
    with profiler.step(step_no):   # no-op unless this step is selected
        ... rollout / update ...

Enable via ProfilingConfig(enabled=True, steps=[3, 4]) or the
AREAL_PROFILE_STEPS env ("3,4").
"""

import contextlib
import dataclasses
import os
from typing import Optional

from areal_tpu.api.cli_args import ProfilingConfig
from areal_tpu.utils import logging as logging_util

logger = logging_util.getLogger("profiling")


class PhaseProfiler:
    def __init__(
        self,
        config: Optional[ProfilingConfig],
        fileroot: str,
        experiment_name: str,
        trial_name: str,
    ):
        self.config = config or ProfilingConfig()
        env_steps = os.environ.get("AREAL_PROFILE_STEPS", "")
        if env_steps:
            try:
                # MERGE the override into the existing config: rebuilding
                # from scratch would silently drop every other field the
                # YAML set (only enabled/steps belong to the env escape
                # hatch)
                self.config = dataclasses.replace(
                    self.config,
                    enabled=True,
                    steps=[int(s) for s in env_steps.split(",") if s],
                )
            except ValueError as e:  # profiling must never kill training
                logger.warning(
                    f"ignoring malformed AREAL_PROFILE_STEPS="
                    f"{env_steps!r}: {e}"
                )
        self.trace_root = os.path.join(
            fileroot, experiment_name, trial_name, "traces"
        )

    def should_trace(self, step: int) -> bool:
        """`step` is the 0-based global step the train loops pass in."""
        if not self.config.enabled:
            return False
        return step in (self.config.steps or [0])

    @contextlib.contextmanager
    def step(self, step: int):
        if not self.should_trace(step):
            yield
            return
        import jax

        out = os.path.join(self.trace_root, f"step{step}")
        os.makedirs(out, exist_ok=True)
        logger.info(f"capturing jax profiler trace → {out}")
        # Only the profiler's OWN setup/teardown is guarded — wrapping the
        # yielded training body in try/except would swallow its exceptions
        # (a @contextmanager that yields twice after throw() destroys the
        # original traceback).
        started = False
        try:
            jax.profiler.start_trace(out)
            started = True
        except Exception as e:  # profiling must never kill training
            logger.warning(f"profiler start failed: {e}")
        try:
            yield
        finally:
            if started:
                try:
                    jax.profiler.stop_trace()
                    logger.info(f"trace written: {out}")
                except Exception as e:
                    logger.warning(f"profiler stop failed: {e}")
