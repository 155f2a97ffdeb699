"""A percentile over ALL requests due in the window of one per-request
field. A request with ``miss`` set (failed, refused, unfinished) is ranked
above every measured value."""

from benchmark import arith


def read(spec, obs):
    field = spec["field"]
    measured = [r[field] for r in obs.requests
                if not r.get("miss") and r.get(field) is not None]
    misses = [r.get(field) or 0.0 for r in obs.requests if r.get("miss")]
    if not measured and not misses:
        return None
    return arith.percentile(
        arith.ranked_with_misses(measured, misses), spec["percentile"]
    )
