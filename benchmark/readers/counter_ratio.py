"""Increase of one counter over the increase of another (``scale`` 100 for a
percentage; ``complement`` for 1 - ratio)."""


def read(spec, obs):
    num, den = obs.counters.get(spec["num"]), obs.counters.get(spec["den"])
    if num is None or not den:
        return None
    r = num / den
    if spec.get("complement"):
        r = 1.0 - r
    return r * spec.get("scale", 1.0)
