"""1 - (union of device-operation intervals / traced window), in %."""


def read(spec, obs):
    t = obs.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
