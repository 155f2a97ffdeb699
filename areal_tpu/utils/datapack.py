"""Bin-packing / balanced-partition algorithms for micro-batching.

Role of reference areal/utils/datapack.py (`ffd_allocate`,
`partition_balanced`): split variable-length sequences into micro-batches
under a token budget (first-fit-decreasing) or into k groups with balanced
total size. Pure numpy here (the reference uses numba; these run on lists of
at most a few thousand sequence lengths so plain Python is fine, and a C++
fast path is provided via areal_tpu.csrc when built).
"""

from typing import List, Sequence

import numpy as np

try:  # optional C++ fast path (areal_tpu/csrc/interval_ops.cpp)
    from areal_tpu.csrc import ffd_allocate as _ffd_allocate_cc
except Exception as _e:  # pragma: no cover - extension not built
    # the .so is built by g++ on first import and never committed: a
    # machine that cannot build it takes the numpy path, and says so
    import logging

    logging.getLogger("areal_tpu.datapack").warning(
        "native interval ops unavailable (%s): using the numpy path", _e
    )
    _ffd_allocate_cc = None


def ffd_allocate(
    sizes: Sequence[int], capacity: int, min_groups: int = 1
) -> List[List[int]]:
    """First-fit-decreasing: pack item indices into the fewest bins of
    `capacity`, but at least `min_groups` bins. Items larger than capacity get
    their own bin. Returns a list of index lists (each non-empty).
    """
    sizes = np.asarray(sizes, dtype=np.int64)
    n = len(sizes)
    if n == 0:
        return []
    if _ffd_allocate_cc is not None:
        groups = [g for g in _ffd_allocate_cc(sizes.tolist(), int(capacity), int(min_groups)) if g]
    else:
        groups = _ffd_py(sizes, capacity, min_groups)
    if len(groups) < min(min_groups, n):
        # FFD collapsed below the required group count (e.g. each DP rank
        # needs >= 1 micro-batch): rebalance into exactly min_groups bins.
        groups = [g for g in partition_balanced(sizes, min(min_groups, n)) if g]
    return groups


def _ffd_py(sizes: np.ndarray, capacity: int, min_groups: int) -> List[List[int]]:
    n = len(sizes)
    order = np.argsort(-sizes, kind="stable")
    bins: List[List[int]] = [[] for _ in range(min_groups)]
    loads = [0] * min_groups
    for idx in order:
        size = int(sizes[idx])
        placed = False
        for b in range(len(bins)):
            # fits, or an empty bin takes an oversize item (mirrors
            # csrc/interval_ops.cpp ffd_allocate)
            if loads[b] + size <= capacity or (not bins[b] and size > capacity):
                bins[b].append(int(idx))
                loads[b] += size
                placed = True
                break
        if not placed:
            bins.append([int(idx)])
            loads.append(size)
    return [b for b in bins if b]


def partition_balanced(sizes: Sequence[int], k: int) -> List[List[int]]:
    """Partition item indices into exactly `k` groups minimizing the max group
    load (greedy longest-processing-time heuristic; reference
    datapack.py:14 uses DP — LPT is within 4/3 of optimal and O(n log n))."""
    sizes = np.asarray(sizes, dtype=np.int64)
    n = len(sizes)
    if k <= 0:
        raise ValueError("k must be positive")
    groups: List[List[int]] = [[] for _ in range(k)]
    loads = np.zeros(k, dtype=np.int64)
    for idx in np.argsort(-sizes, kind="stable"):
        b = int(np.argmin(loads))
        groups[b].append(int(idx))
        loads[b] += sizes[idx]
    return groups


def partition_balanced_contiguous(sizes: Sequence[int], k: int) -> List[List[int]]:
    """Partition [0..n) into k contiguous chunks with balanced load (keeps
    original order — used where order matters, e.g. DP sharding of a batch)."""
    sizes = np.asarray(sizes, dtype=np.int64)
    n = len(sizes)
    prefix = np.concatenate([[0], np.cumsum(sizes)])
    total = prefix[-1]
    groups = []
    start = 0
    for g in range(k):
        target = total * (g + 1) / k
        end = int(np.searchsorted(prefix, target, side="left"))
        end = max(end, start + 1) if g < n - (k - 1 - g) else end
        end = min(end, n - (k - 1 - g))
        end = max(end, start)
        groups.append(list(range(start, end)))
        start = end
    # distribute leftovers (defensive; happens only with degenerate sizes)
    if start < n:
        groups[-1].extend(range(start, n))
    return groups


def flat2d(xs: List[List[int]]) -> List[int]:
    return [x for sub in xs for x in sub]
