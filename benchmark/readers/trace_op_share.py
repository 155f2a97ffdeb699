"""Device time of the operations whose trace name matches ``match``, as a
share of the traced window, in %."""

from benchmark import xplane


def read(spec, obs):
    t = obs.trace
    if not t or t["window_s"] <= 0:
        return None
    s = xplane.matching_seconds(t["op_seconds"], spec["match"])
    return None if s <= 0 else 100.0 * s / t["window_s"]
