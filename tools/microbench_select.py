"""The learned selection's counting passes alone, on one chip: the XLA form
(``ops/sparse_attention._kth_counts_xla``: ``_kth_largest`` and the two sums
after it, every pass a read of the keys from HBM) against the kernel
(``dsa_select_kth``: a query block's keys in VMEM through all of them) over
a few (query block, tile group) pairs, on the seven query chunks of one
16,384-token row that select (2048 queries x 4096, 6144, ..., 16384 keys);
then ``select_keys`` whole (index scores, keys, selection, mask) at the
published indexer widths through either form.

    python -m tools.microbench_select [--tokens 16384] [--topk 2048]

Host clock around ``block_until_ready``, the best of ``RUNS``; every kernel
variant is held equal to the XLA form on the chip, bit for bit.
``ops/sparse_attention.py``'s ``_KTH_BLOCK_Q`` and ``_KTH_TILE_GROUP`` hold
what this found (PERF.md, PR 32).
"""

import argparse
import json
import time

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import sparse_attention as sa

RUNS = 3
VARIANTS = ((64, 8), (32, 8), (128, 8), (64, 4), (64, 16))


def _best_ms(fn):
    out = jax.block_until_ready(fn())  # compiles
    best = float("inf")
    for _ in range(RUNS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--widths", type=int, nargs=2, default=(16, 64),
                    metavar=("HEADS", "DIM"))
    args = ap.parse_args()
    t, topk = args.tokens, args.topk
    print(json.dumps({"device": str(jax.devices()[0].device_kind)}))

    key = jax.random.PRNGKey(0)
    chunks = []
    for a in range(topk, t, topk):
        key, sub = jax.random.split(key)
        # 20 random bits a key: ties at the threshold in some rows
        bits = jax.random.bits(sub, (topk, a + topk), jnp.uint32) << 12
        own = jnp.arange(a + topk)[None] <= (a + jnp.arange(topk))[:, None]
        chunks.append(jnp.where(own, bits, jnp.uint32(0)))

    row = jax.jit(lambda ks: [sa._kth_counts_xla(k, topk) for k in ks])
    want, ms = _best_ms(lambda: row(chunks))
    print(json.dumps({"form": "xla", "row_ms": ms}), flush=True)
    for block, group in VARIANTS:
        sa._KTH_BLOCK_Q, sa._KTH_TILE_GROUP = block, group
        row = jax.jit(lambda ks: [sa._kth_counts_kernel(k, topk) for k in ks])
        got, ms = _best_ms(lambda: row(chunks))
        equal = all(
            bool((g == w).all()) for gs, ws in zip(got, want)
            for g, w in zip(gs, ws)
        )
        print(json.dumps({"form": "kernel", "block_q": block, "group": group,
                          "row_ms": ms, "equal": equal}), flush=True)
    sa._KTH_BLOCK_Q, sa._KTH_TILE_GROUP = VARIANTS[0]
    del chunks, want, got

    hi, di = args.widths
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    qi = jnp.asarray(rng.standard_normal((t, hi, di)), bf)
    ki = jnp.asarray(rng.standard_normal((t, di)), bf)
    w = jnp.asarray(rng.standard_normal((t, hi)), bf)
    seg = jnp.asarray(np.repeat([1, 2, 0], [t // 2, t // 2 - 500, 500]))
    resolved, masks = sa.select_impl, {}
    for form in ("xla", "kernel"):
        sa.select_impl = lambda shape=None, form=form: form
        select = jax.jit(lambda *a: sa.select_keys(*a, topk))
        (masks[form], stats), ms = _best_ms(lambda: select(qi, ki, w, seg))
        print(json.dumps({"select_keys": form, "row_ms": ms,
                          "kept_pairs": int(stats.kept_pairs)}), flush=True)
    sa.select_impl = resolved
    print(json.dumps({"masks_equal": bool(
        (masks["xla"] == masks["kernel"]).all()
    )}))


if __name__ == "__main__":
    main()
