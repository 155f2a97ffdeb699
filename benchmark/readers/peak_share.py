"""A counted quantity per second of window over a device peak, in %
(``train_mfu``: matmul FLOPs of the trained tokens / window / peak FLOP/s)."""


def read(spec, obs):
    n = obs.counters.get(spec["counter"])
    if n is None or obs.peaks is None or obs.window_s <= 0:
        return None
    return 100.0 * n / obs.window_s / obs.peaks[spec["peak"]]
