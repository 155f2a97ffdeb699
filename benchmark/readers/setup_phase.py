"""Set-up from inside: what the trainer's compile tracker
(``areal_tpu.utils.goodput.CompileTracker``) logged up to window start, by
phase of making a program. ``phase`` is ``trace`` | ``lower`` | ``compile`` |
``cache_load`` (seconds, an event that ran inside another counted once),
``programs`` (backend events: programs compiled or loaded) or ``rest``
(``setup_s`` minus the four in seconds: imports, the backend's start, the
warm-up pass running, the host's own Python), so that the five in seconds
account for ``setup_s`` whole. None where the program keeps no such log, or
where the log has dropped entries and no longer reaches back to the window's
start."""

import sys


def window_start(obs):
    """The window's start on ``time.monotonic()``: the runner's ``_T_START``
    plus ``setup_s``. The runner is the module that is RUNNING: under
    ``python -m benchmark.run`` that is ``__main__``, and importing
    ``benchmark.run`` from here would make a second copy with a later
    ``_T_START``."""
    main = sys.modules.get("__main__")
    if getattr(getattr(main, "__spec__", None), "name", "") != "benchmark.run":
        main = sys.modules.get("benchmark.run")
    t_start = getattr(main, "_T_START", None)
    if t_start is None or obs.setup_s is None:
        return None
    return t_start + obs.setup_s


def read(spec, obs):
    from areal_tpu.utils import goodput

    tracker = goodput.trainer_tracker()
    until = window_start(obs)
    if until is None or not hasattr(tracker, "phase_seconds"):
        return None
    phase = spec["phase"]
    try:
        if phase == "programs":
            return tracker.programs(until)
        seconds = tracker.phase_seconds(until)
    except LookupError:
        return None
    if phase == "rest":
        return obs.setup_s - sum(seconds.values())
    return seconds[phase]
