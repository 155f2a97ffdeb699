"""The one length generator every traffic mix reads.

A law is a dict from a traffic file: ``{"law": "uniform" | "lognormal",
...}``. ``pool(law, n)`` returns the law's ``n`` mid-quantiles,
clipped and rounded — the SAME multiset for every seed. A seed only orders
it (``ordered``): runs with different seeds then do the same work in another
order, so a seed cannot change how much work a window holds or which program
shapes it needs.
"""

import math
from statistics import NormalDist

import numpy as np


def _quantile(law, q):
    kind = law["law"]
    if kind == "uniform":
        return law["lo"] + q * (law["hi"] - law["lo"])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(q)
        return law["median"] * math.exp(law["sigma"] * z)
    raise ValueError(f"unknown length law {kind!r}")


def pool(law, n):
    """``n`` mid-quantile draws of ``law`` as ints, clipped to [lo, hi]."""
    lo, hi = law.get("lo"), law.get("hi")
    out = []
    for i in range(n):
        v = _quantile(law, (i + 0.5) / n)
        if lo is not None:
            v = max(lo, v)
        if hi is not None:
            v = min(hi, v)
        out.append(int(round(v)))
    return np.asarray(out, np.int64)


def ordered(law, n, rng):
    """The pool in the seed's order."""
    return rng.permutation(pool(law, n))


def length_biased(values, n):
    """``n`` mid-quantiles of the length-biased law over ``values``: what a
    request in flight at a random instant looks like (a request twice as long
    is twice as likely to be the one in flight)."""
    v = np.sort(np.asarray(values, np.int64))
    cum = np.cumsum(v, dtype=np.float64)
    cum /= cum[-1]
    qs = (np.arange(n) + 0.5) / n
    return v[np.minimum(np.searchsorted(cum, qs), len(v) - 1)]


def tokens(rng, vocab, n):
    return rng.integers(0, vocab, size=int(n)).tolist()
