"""The sparse layer alone, on one chip: ``ops/moe.moe_ffn`` (route, sort,
grouped matmuls, un-permute) forward and forward + backward at a model's
published widths, once through each grouped matmul (``megablox`` over a few
tilings, ``ragged_dot``), all under one ``jax.profiler`` capture.

    python -m tools.microbench_moe [--tokens 4096] [--out chiprun_out/moe_sweep]
    python -m tools.microbench_moe --held 16 --widths 2048 768 128 8

``--held N``: the layer HOLDS the first N of the E experts it routes over (one
token chunk of a cell: 4,096 tokens of 16 of 128 in the long-row cell,
``--widths 2304 1024 256 8 --held 8`` in the Kimi cell). The sweep is then the
expert path over the whole sorted stream against the one over the bounded
buffer (``ops/moe._run_held``; ``compact_dense``: with the dense token side,
``compact``: with the platform's), and the three ways the buffer's rows can
get back to their tokens, alone: the product with the [G, C] matrix of routing
weights (``side_product``: ``ops/moe._onto_tokens``' dense form, what the CPU
runs), the gather of every pair's row clamped to one zero row
(``side_gather``, the form PR 37 weighed it against) and the grouped product
over token tiles of the slab's rows in token order (``side_banded_<m>_<token
tile>_<n>``: ``ops/moe.onto_tokens_banded`` over ``ONTO_TILINGS``, the form a
TPU runs since PR 46), each with its device time and its largest difference
from the product.

The times are device times of the jitted programs and of the device scopes
``moe_route`` / ``moe_dispatch`` / ``moe_experts`` from
``benchmark.program_trace`` (what the ``moe_*`` metrics are read by), not
host clocks; the operation names of each variant are printed, since
``moe_experts_roofline.train`` matches the kernels by name. ``ops/moe.py``'s
``_GMM_TILING`` and ``grouped_impl`` hold what this found (PERF.md, PR 27).
"""

import argparse
import json
import os

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import moe
from benchmark import harness, program_trace, xplane

RUNS = 3
TILINGS = ((256, 1024, 1024), (128, 1024, 1024), (512, 1024, 1024),
           (512, 512, 1024), (512, 2048, 1024), (1024, 1024, 1024))
# the banded token side's (slab rows a grid step, token tile, width tile);
# a width tile of 4096 is the whole width in one
ONTO_TILINGS = ((256, 256, 1024), (512, 256, 1024), (1024, 256, 1024),
                (256, 256, 4096), (512, 256, 4096), (1024, 256, 4096),
                (512, 128, 1024), (512, 128, 4096), (512, 512, 1024),
                (512, 512, 4096))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--widths", type=int, nargs=4, default=(2048, 1024, 64, 8),
                    metavar=("D", "F", "E", "K"))
    ap.add_argument("--out", default="chiprun_out/moe_sweep")
    ap.add_argument("--tilings", type=int, default=len(TILINGS),
                    help="the first so many of TILINGS")
    ap.add_argument("--held", type=int, default=0,
                    help="the layer holds the first so many of the E experts")
    args = ap.parse_args()
    t, (d, f, e, k) = args.tokens, args.widths
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((1, t, d)), bf)
    ws = (
        jnp.asarray(rng.standard_normal((d, e)) * 0.02, bf),
        jnp.asarray(rng.standard_normal((e, d, f)) * 0.02, bf),
        jnp.asarray(rng.standard_normal((e, d, f)) * 0.02, bf),
        jnp.asarray(rng.standard_normal((e, f, d)) * 0.02, bf),
    )
    valid = jnp.asarray(np.arange(t) < int(t * 0.86))[None]  # 14% padding
    flops = 2.0 * int(t * 0.86) * k * 3 * d * f
    if args.held:
        return held_sweep(args, x, ws, valid, flops * args.held / e)

    def variant(name, impl, tiling):
        def fwd(x, *ws):
            return moe.moe_ffn(x, *ws, num_experts_per_tok=k,
                               norm_topk_prob=False, valid=valid)[0]

        def loss(x, *ws):
            return fwd(x, *ws).astype(jnp.float32).sum()

        fwd.__name__ = f"fwd_{name}"
        bwd = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
        both = lambda *a: bwd(*a)  # noqa: E731
        both.__name__ = f"fwdbwd_{name}"
        return impl, tiling, jax.jit(fwd), jax.jit(both)

    todo = [variant("ragged_dot", "ragged_dot", None)] + [
        variant("gmm_%d_%d_%d" % tl, "megablox", tl)
        for tl in TILINGS[:args.tilings]
    ]
    real_impl = moe.grouped_impl
    outs, ok = {}, []
    for impl, tiling, fwd, both in todo:  # compile and check, untraced
        moe.grouped_impl = lambda shape=None, impl=impl: impl
        if tiling:
            moe._GMM_TILING = tiling
        try:
            outs[fwd.__name__] = np.asarray(fwd(x, *ws), np.float32)
            jax.block_until_ready(both(x, *ws))
            ok.append((impl, tiling, fwd, both))
        except Exception as err:  # noqa: BLE001 — a tiling the chip refuses
            harness.log(f"{fwd.__name__}: refused: {type(err).__name__}: "
                        f"{str(err)[:300]}")
    base = outs["fwd_ragged_dot"]
    for name, o in outs.items():
        harness.log(f"{name}: max |out - ragged_dot| = "
                    f"{np.abs(o - base).max():.4g}, finite={np.isfinite(o).all()}")
    traced = harness.TracedWindow(True, os.path.join(harness.ROOT, args.out))
    with traced:
        for impl, tiling, fwd, both in ok:
            moe.grouped_impl = lambda shape=None, impl=impl: impl
            if tiling:
                moe._GMM_TILING = tiling
            for _ in range(RUNS):
                jax.block_until_ready(fwd(x, *ws))
                jax.block_until_ready(both(x, *ws))
    moe.grouped_impl = real_impl
    events, rows = report(traced, flops, ("fwd_", "fwdbwd_"))
    # the names the kernels' events carry, per program
    names = {}
    for ev in events:
        if ev[1] == xplane.OPS_LINE and ev[5].get("program", "").startswith("fwd"):
            if program_trace.scope_of(ev[5].get("op_name")) == "moe_experts":
                names.setdefault(ev[5]["program"], set()).add(ev[2].split(" ")[0])
    for prog, ns in sorted(names.items()):
        print("ops under moe_experts in", prog, sorted(ns)[:12], flush=True)
    # does the device's peak_bytes_in_use count a program's temporaries?
    dev = jax.devices()[0]
    before = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    big = jax.jit(lambda a: jnp.cumsum(  # a 4.3 GB product, materialised
        jnp.dot(a, a.T, preferred_element_type=jnp.float32), axis=0).sum())
    jax.block_until_ready(big(jnp.ones((32768, 512), bf)))
    after = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    print("peak_bytes_in_use before/after a program with large temporaries:",
          before, after, flush=True)
    with open(os.path.join(harness.ROOT, args.out, "sweep.json"), "w") as fh:
        json.dump(rows, fh, indent=1)


def report(traced, flops, prefixes, longest=0):
    """One line a traced program whose name starts with one of
    ``prefixes``: device ms a run, ms by device scope, the experts' TFLOP/s;
    ``longest``: and so many of its longest operations."""
    events = program_trace.load(xplane.find_xplane(traced.dir))
    r = program_trace.reduce(events)
    rows = {}
    for prog, p in sorted(r["programs"].items()):
        if not prog.startswith(prefixes):
            continue
        runs = max(p["runs"], 1)
        sc = {s: round(1e3 * v / runs, 3)
              for s, v in r["program_scopes"].get(prog, {}).items()}
        passes = 3 if prog.startswith("fwdbwd_") else 1
        ex = sc.get("moe_experts", 0.0)
        rows[prog] = {"ms": round(1e3 * p["seconds"] / runs, 3), "scopes_ms": sc,
                      "experts_tflops": round(passes * flops / (ex * 1e-3) / 1e12, 1)
                      if ex else None}
        if longest:
            ops = {}
            for ev in events:
                if ev[1] == xplane.OPS_LINE and ev[5].get("program") == prog:
                    key = (ev[2][:64],
                           program_trace.scope_of(ev[5].get("op_name")))
                    ops[key] = ops.get(key, 0) + ev[4]  # ns
            rows[prog]["longest_ops_ms"] = [
                (n, sc_, round(v / 1e6 / runs, 3)) for (n, sc_), v in
                sorted(ops.items(), key=lambda kv: -kv[1])[:longest]
            ]
        print(prog, json.dumps(rows[prog]), flush=True)
    return events, rows


def held_sweep(args, x, ws, valid, flops):
    """A layer that holds ``--held`` of its experts: the whole stream
    against the bounded buffer, and the three candidates for the token side."""
    t, (d, f, e, k) = args.tokens, args.widths
    held = tuple(w[:args.held] for w in ws[1:])
    c = moe._held_rows(t * k // moe._token_chunks(t, k), args.held, e)
    bf = jnp.bfloat16

    def variant(name):
        def fwd(x, wr, *held):
            return moe.moe_ffn(x, wr, *held, num_experts_per_tok=k,
                               norm_topk_prob=True, valid=valid)

        def loss(*a):
            return fwd(*a)[0].astype(jnp.float32).sum()

        fwd.__name__ = f"fwd_{name}"
        bwd = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
        both = lambda *a: bwd(*a)  # noqa: E731
        both.__name__ = f"fwdbwd_{name}"
        return jax.jit(fwd), jax.jit(both)

    # the token side alone: [C, D] rows back to [G, D] tokens
    rng = np.random.default_rng(1)
    g = t // moe._token_chunks(t, k)
    rows = jnp.asarray(rng.standard_normal((c, d)), bf)
    order = jnp.asarray(rng.permutation(g * k), jnp.int32)
    tok = order[:c] // k
    w = jnp.asarray(rng.random((g, k)), jnp.float32)

    def side_product(rows, w, order, tok):
        real, moe.onto_impl = moe.onto_impl, lambda shape=None: "dense"
        try:
            return moe._onto_tokens(
                rows, tok, w.reshape(-1)[order[:c]], g
            ).astype(bf)
        finally:
            moe.onto_impl = real

    def side_gather(rows, w, order, tok):
        inv = jnp.minimum(jnp.argsort(order), c)
        y = jnp.concatenate([rows, jnp.zeros((1, d), bf)])[inv]
        return jnp.einsum(
            "gkd,gk->gd", y.reshape(g, k, d), w.astype(bf),
            preferred_element_type=jnp.float32,
        ).astype(bf)

    def side_gather_rows(rows, w, order, tok):  # the buffer's own gather
        return jnp.concatenate([rows, jnp.zeros((1, d), bf)])[tok]

    def banded(tiling):
        def side(rows, w, order, tok):
            return moe.onto_tokens_banded(
                rows, tok, w.reshape(-1)[order[:c]], g, tiling=tiling
            )

        side.__name__ = "side_banded_%d_%d_%d" % tiling
        return side

    sides = [jax.jit(fn) for fn in
             (side_product, side_gather, side_gather_rows)]
    a, b = (np.asarray(fn(rows, w, order, tok), np.float32)
            for fn in sides[:2])
    harness.log(f"token side, C={c} G={g}: max |product - gather| = "
                f"{np.abs(a - b).max():.4g} of max {np.abs(b).max():.4g}")
    for tm, tile, tn in ONTO_TILINGS:
        fn = jax.jit(banded((tm, tile, min(tn, d))))
        try:
            o = np.asarray(fn(rows, w, order, tok), np.float32)
        except Exception as err:  # noqa: BLE001 — a tiling the chip refuses
            harness.log(f"{fn.__name__}: refused: {type(err).__name__}: "
                        f"{str(err)[:300]}")
            continue
        sides.append(fn)
        harness.log(f"{fn.__name__}: max |banded - product| = "
                    f"{np.abs(o - a).max():.4g} of max {np.abs(a).max():.4g}")

    real_rows, real_onto = moe._held_rows, moe.onto_impl
    progs, outs = {}, {}
    for name, rows_fn, onto in (
        ("whole", lambda rows_, e_, n_: rows_, real_onto),
        ("compact_dense", real_rows, lambda shape=None: "dense"),
        ("compact", real_rows, real_onto),
    ):
        moe._held_rows, moe.onto_impl = rows_fn, onto
        progs[name] = variant(name)
        out, stats = progs[name][0](x, ws[0], *held)  # traces here
        jax.block_until_ready(progs[name][1](x, ws[0], *held))
        outs[name] = np.asarray(out, np.float32)
        harness.log(f"{name}: " + ", ".join(
            f"{fld}={float(v):.0f}" for fld, v in zip(stats._fields, stats)
            if fld != "aux_loss"
        ))
    moe._held_rows, moe.onto_impl = real_rows, real_onto
    for name, base in (("compact", "whole"), ("compact_dense", "whole"),
                       ("compact", "compact_dense")):
        harness.log(
            f"max |{name} - {base}| = "
            f"{np.abs(outs[name] - outs[base]).max():.4g} of max "
            f"{np.abs(outs[base]).max():.4g}"
        )
    traced = harness.TracedWindow(True, os.path.join(harness.ROOT, args.out))
    with traced:
        for _ in range(RUNS):
            for fwd, both in progs.values():
                jax.block_until_ready(fwd(x, ws[0], *held))
                jax.block_until_ready(both(x, ws[0], *held))
            for fn in sides:
                jax.block_until_ready(fn(rows, w, order, tok))
    _, out = report(traced, flops, ("fwd_", "fwdbwd_", "side_"), longest=12)
    with open(os.path.join(harness.ROOT, args.out, "sweep.json"), "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
