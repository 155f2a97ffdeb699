"""The sparse layer alone, on one chip: ``ops/moe.moe_ffn`` (route, sort,
grouped matmuls, un-permute) forward and forward + backward at a model's
published widths, once through each grouped matmul (``megablox`` over a few
tilings, ``ragged_dot``), all under one ``jax.profiler`` capture.

    python -m tools.microbench_moe [--tokens 4096] [--out chiprun_out/moe_sweep]
    python -m tools.microbench_moe --held 16 --widths 2048 768 128 8
    python -m tools.microbench_moe --mesh 4

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

``--mesh N`` (all of a host's chips: ``chiprun --chips 4``): the layer's
MESH FORM, ``ops/moe.make_sharded_moe_ffn`` on an ``fsdp=N`` mesh, N rows of
``--tokens`` (the four-chip sparse cell: 4 x 4,096 tokens, 8 of 64, D 2,048,
F 1,024), forward and forward + backward, the weights laid out as the engine
lays them (``parallel/sharding.tree_shardings``). Beside the milliseconds
(the mean over the chips, by device scope, ``moe_exchange`` among them, and
the longest operations, the collectives by name) it prints what sets a
step's pace there: each chip's held pairs a token chunk, the largest chip
over the mean, with the router drawn as here (``--shared s``: every token
is its own draw plus s times ONE common direction, as tokens of a residual
stream share most of theirs; the choice of a token's experts does not depend
on the router's scale, it does on what the tokens have in common: 0.7 reads
the cell's 3.35 an expert) and the two counts of a share.

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
    ap.add_argument("--mesh", type=int, default=0,
                    help="the mesh form on so many chips, a row a chip")
    ap.add_argument("--shared", type=float, default=0.0,
                    help="--mesh: how much of one common direction each "
                    "token holds beside its own draw (skews the load)")
    args = ap.parse_args()
    if args.mesh:
        return mesh_sweep(args)
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
        # a program's seconds are the mean over the chips; its runs are
        # counted a chip
        every = max(p["runs"], 1)
        runs = every / max(r.get("devices") or 1, 1)
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
                (n, sc_, round(v / 1e6 / every, 3)) for (n, sc_), v in
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


def mesh_sweep(args):
    """The layer's mesh form on ``--mesh`` chips, and how evenly its routers
    load them."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.models.transformer import param_logical_axes
    from areal_tpu.parallel import feasibility
    from areal_tpu.parallel import mesh as mesh_lib
    from areal_tpu.parallel import sharding as sharding_lib

    n, t, (d, f, e, k) = args.mesh, args.tokens, args.widths
    cfg = dataclasses.replace(
        feasibility.olmoe_1b_7b_config(1), hidden_size=d,
        moe_intermediate_size=f, num_experts=e, num_experts_per_tok=k,
    )
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=n))
    laid_out = sharding_lib.tree_shardings(
        mesh, param_logical_axes(cfg)
    )["layers"]
    specs = {name: P(*laid_out[name].spec[1:]) for name in moe.EXPERT_WEIGHTS}
    layer = moe.make_sharded_moe_ffn(cfg, mesh, specs)
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16

    def put(a, spec):
        return jax.device_put(jnp.asarray(a, bf), NamedSharding(mesh, spec))

    x = rng.standard_normal((n, t, d)) + (
        args.shared * rng.standard_normal(d)
    )
    x = put(x, P(("data", "fsdp"), "seq", None))
    valid = np.broadcast_to(np.arange(t) < int(t * 0.86), (n, t))  # 14% padding
    shapes = {"w_router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
              "w_down": (e, f, d)}
    lp = {
        name: put(rng.standard_normal(shape) * 0.02, specs[name])
        for name, shape in shapes.items()
    }

    def fwd(lp, x):
        return layer(lp, x, jnp.asarray(valid))

    def loss(lp, x):
        return fwd(lp, x)[0].astype(jnp.float32).sum()

    fwd.__name__ = "fwd_mesh"
    both = lambda *a: jax.grad(loss, argnums=(0, 1))(*a)  # noqa: E731
    both.__name__ = "fwdbwd_mesh"
    fwd, both = jax.jit(fwd), jax.jit(both)
    out, stats = fwd(lp, x)
    jax.block_until_ready(both(lp, x))
    harness.log(f"specs: {specs}")
    harness.log("stats: " + ", ".join(
        f"{fld}={float(v):.0f}" for fld, v in zip(stats._fields, stats)
        if fld != "aux_loss" and v is not None
    ) + f", finite={bool(np.isfinite(np.asarray(out, np.float32)).all())}")
    # each chip's held pairs a token chunk: the same scores, the same choice
    scores = moe.router_scores(x, lp["w_router"])
    choice = np.asarray(moe.router_topk(
        scores.reshape(-1, e), k, cfg.norm_topk_prob
    )[1])
    chunks = moe._token_chunks(n * t, k)
    chip = np.where(valid.reshape(-1, 1), choice * n // e, n).reshape(chunks, -1)
    held = np.stack([(chip == c).sum(1) for c in range(n)], 1)  # [chunk, chip]
    slab = moe._held_rows(n * t * k // chunks, e // n, e)
    load = np.bincount(choice[valid.reshape(-1)].reshape(-1), minlength=e)
    print(json.dumps({
        "chips": n, "chunks": chunks, "slab_rows": slab,
        "held_pairs_a_chunk_by_chip": held.tolist(),
        "chip_largest_over_mean": round(
            float(held.sum(0).max() / held.sum(0).mean()), 4),
        "chunk_largest_over_slab": round(float(held.max() / slab), 4),
        "expert_largest_over_mean": round(
            float(load.max() / load.mean()), 4),
    }), flush=True)
    traced = harness.TracedWindow(True, os.path.join(harness.ROOT, args.out))
    with traced:
        for _ in range(RUNS):
            jax.block_until_ready(fwd(lp, x))
            jax.block_until_ready(both(lp, x))
    flops = 2.0 * int(t * 0.86) * k * 3 * d * f  # a chip's share of n rows'
    _, rows = report(traced, flops, ("fwd_", "fwdbwd_"), longest=16)
    with open(os.path.join(harness.ROOT, args.out, "sweep.json"), "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
