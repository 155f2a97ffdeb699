"""A quantile of one of the program's bucketed histograms, from the counts
added during the window, interpolated inside its bucket: coarse."""

from benchmark import arith


def read(spec, obs):
    h = obs.histograms.get(spec["histogram"])
    if h is None:
        return None
    v = arith.histogram_quantile(
        h["bounds"], h["counts"], spec["percentile"] / 100.0
    )
    return None if v is None else v * spec.get("scale", 1.0)
