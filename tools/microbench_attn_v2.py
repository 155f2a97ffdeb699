"""The trainer's attention core alone, on the chip: ``ops/flash.py``'s wrapper
(relayouts included) against the XLA ``segment_attention``, forward and
forward + backward, over block edges and backward forms, on packed
rows of the benchmark's own GRPO batches (``benchmark/traffic``).

    python -m tools.microbench_attn_v2 [--t 4096] [--heads 14 2 64] \
        [--out chiprun_out/attn_sweep]

A window-attention layer's band (PERF.md, PR 39: the tile table of
``ops/flash.BAND_EDGE``) is ``--layer-window 512`` on the rows of the cell
that holds it, at both of its row lengths and both kinds' heads:

    python -m tools.microbench_attn_v2 --t 16384 --heads 64 8 128 \
        --traffic grpo_steps16_mixed16k --layer-window 512 \
        --edges 256 512 --no-xla
    ... --t 8192 ...;  ... --heads 48 8 128 --layer-window 0 --edges 1024 2048

(``--no-xla``: the XLA form's float32 scores [heads, T, T] do not fit a
chip at these sizes; the kernel is then held to nothing here, and to the
reference by the cell's ``window_exact``.)

The band's own kernels over key strips (PERF.md, PR 47:
``ops/band_attention.py``; the table ``STRIP_BQ`` is from) are ``--backward
strip`` at each ``--bq`` (forward and backward both: a ``strip_bq*`` variant
runs no splash kernel), beside the splash kernel's split backward, on the two
cells' rows; ``chosen`` is what the trainer runs:

    python -m tools.microbench_attn_v2 --t 16384 --heads 64 8 128 \
        --traffic grpo_steps16_mixed16k --layer-window 512 \
        --edges 512 --kvc 512 --backward split strip --bq 128 256 512 --no-xla
    ... --t 32768 --heads 32 4 128 --traffic grpo_steps16_long32k \
        --layer-window 1024 ...

The tile mask of a row's own borders (PERF.md, PR 42: ``ops/flash._row_kernel``)
is on in every variant, as in the trainer; ``--static`` also times each variant
WITHOUT it (``<name>_static``: the kernel as ``_make_kernel`` builds it), and
the head line says what share of the tiles the rows keep at each edge. The
sweep ``ops/flash.instance``'s table is from, a cell's rows each (``--rows 8``):

    python -m tools.microbench_attn_v2 --t 4096 --heads 14 2 64 --edges 512 \
        1024 --backward fused --kvc 512 --rows 8 --static --no-xla
    ... --t 4096 --heads 16 16 128 --traffic grpo_steps64_fsdp4
    ... --t 8192 --heads 32 32 192 --dv 128 --traffic grpo_steps16_packed8k \
        --edges 1024 2048
    ... --t 16384 --heads 48 8 128 --traffic grpo_steps16_mixed16k
    ... --t 16384 --heads 8 2 128 --traffic grpo_steps32_packed16k

Every variant is one jitted program with a name of its own, all run under
one ``jax.profiler`` capture; the times are device times of those programs
from ``benchmark.program_trace`` (what ``attn_time_share.train`` is read by),
not host clocks. (The persistent compile cache keys a program without its
name: two variants that lower to the same program run under the first one's
name, and are marked.) ``ops/flash.instance`` holds what this found (PERF.md,
PR 26); run it again when the kernel, the chip or the shape changes. A variant
is an explicit ``flash.Instance`` (``--kvc`` its ``block_kv_compute``) handed
to the wrapper's two halves (``flash._splash``, ``flash._strip_band``); the
rule itself is never swapped. The sweep at heads wider than 128 (PERF.md,
PR 57), every rung the two cells dispatch:

    python -m tools.microbench_attn_v2 --t 16384 --heads 32 32 192 --dv 128 \
        --traffic grpo_steps16_mla_long16k --edges 512 1024 2048 \
        --backward fused split --kvc 256 512 1024 2048 --rows 8 --no-xla
    ... --heads 16 2 256 --traffic grpo_steps16_gdn_long16k
"""

import argparse
import functools
import itertools
import json
import os

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import flash
from areal_tpu.ops.basic import segment_attention
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import tracing
from benchmark import harness, program_trace, xplane
from benchmark.kinds import train_steps

RUNS = 3


def packed_rows(t, traffic="grpo_steps32", n=2):
    """Segment ids of the first ``n`` microbatches of the cell's first batch
    that fill a row of ``t`` tokens, packed as the trainer packs them."""
    with open(os.path.join(harness.ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        p = json.load(f)
    batch = train_steps.make_batches(p, 1000, 0)[0]
    fits = batch["_seq_lens"] <= t
    batch = {k: v[fits] for k, v in batch.items() if not k.startswith("_")}
    mbs = data_utils.split_padded_batch_into_mb_list(batch, t)
    return [
        data_utils.pack_batch_rows(mb, n_rows=1, pad_to=t).segment_ids
        for mb, row_len in zip(mbs.mbs, mbs.row_lens) if row_len == t
    ][:n]


def variants(edges, backward, kvcs, bqs):
    """(name, kind, the tile edge or the strip kernels' query block, fused,
    ``block_kv_compute``): the splash kernel at each edge, backward and
    compute tile, then the band's own kernels at each query block."""
    for edge, fused, kvc in itertools.product(
        edges, [b == "fused" for b in backward if b != "strip"], kvcs
    ):
        if kvc > edge or (kvc == 256 and edge > 512):
            continue
        name = f"e{edge}_{'fused' if fused else 'split'}_c{kvc}"
        yield name, "splash", edge, fused, kvc
    if "strip" in backward:
        for bq in bqs:
            yield f"strip_bq{bq}", "strip", bq, False, 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=4096)
    ap.add_argument("--heads", type=int, nargs=3, default=(14, 2, 64),
                    metavar=("HQ", "HKV", "D"))
    ap.add_argument("--edges", type=int, nargs="*", default=(512, 1024, 2048))
    ap.add_argument("--out", default="chiprun_out/attn_sweep")
    ap.add_argument("--traffic", default="grpo_steps32",
                    help="the traffic file whose packed rows are attended")
    ap.add_argument("--layer-window", type=int, default=0,
                    help="a window-attention layer's window (0 = none)")
    ap.add_argument("--no-xla", action="store_true",
                    help="leave the XLA form out (its scores do not fit)")
    ap.add_argument("--dv", type=int, default=0,
                    help="the value heads' width where it is not D")
    ap.add_argument("--backward", nargs="*", default=("fused", "split"),
                    choices=("fused", "split", "strip"))
    ap.add_argument("--bq", type=int, nargs="*", default=(128, 256, 512),
                    help="the strip kernels' query blocks (a layer's band)")
    ap.add_argument("--kvc", type=int, nargs="*", default=(256, 512, 1024),
                    help="block_kv_compute")
    ap.add_argument("--rows", type=int, default=2,
                    help="how many of the cell's packed rows are attended")
    ap.add_argument("--static", action="store_true",
                    help="also time each variant without the rows' tile mask")
    args = ap.parse_args()
    t, (hq, hkv, d) = args.t, args.heads
    print("device", jax.devices()[0].device_kind,
          "probed edge", flash.probe_block_size(), flush=True)
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (1, t, h, w), jnp.bfloat16)
        for kk, h, w in zip(
            jax.random.split(key, 3), (hq, hkv, hkv), (d, d, args.dv or d)
        )
    )
    rows = packed_rows(t, args.traffic, args.rows)
    segs = [jnp.asarray(s) for s in rows]
    lw = args.layer_window
    for edge in args.edges:  # what the rows' borders leave of the triangle
        kept = sum(int(flash.tiles_needed(s[0], edge, np).sum()) for s in rows)
        n = t // edge
        print(f"edge {edge}: {len(rows)} rows keep {kept} of "
              f"{len(rows) * n * (n + 1) // 2} tiles", flush=True)

    def programs(name, attend):
        def fwd(q, k, v, seg):
            with tracing.device_scope("attention"):
                return attend(q, k, v, seg)

        def grad(q, k, v, seg):
            return jax.grad(
                lambda *a: fwd(*a, seg).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        fwd.__name__, grad.__name__ = f"fwd_{name}", f"grad_{name}"
        return jax.jit(fwd), jax.jit(grad)

    todo = []
    row_kernel = flash._row_kernel
    chosen = flash.instance(t, d, lw, flash.probe_block_size(), heads=hq)
    print("chosen:", chosen, flush=True)
    swept = list(variants(args.edges, args.backward, args.kvc, args.bq))
    named = ([] if args.no_xla else [("xla", "", 0, False, 0)]) + swept + [
        (name + "_static", *rest)
        for name, *rest in (swept if args.static else [])
    ] + [("chosen", chosen.kind, chosen.edge, chosen.fused, chosen.kvc)]
    for name, kind, edge, fused, kvc in named:
        # the instance said outright, with the rule's mask; a kernel
        # without the rows' mask is not the rule's to say
        inst = chosen._replace(kind=kind, edge=edge, fused=fused, kvc=kvc)
        flash._row_kernel = (
            (lambda kernel, seg: kernel) if name.endswith("_static")
            else row_kernel
        )
        flash._make_kernel.cache_clear()
        run = flash._strip_band if kind == "strip" else flash._splash
        fns = programs(
            name,
            functools.partial(segment_attention, window=lw) if name == "xla"
            else functools.partial(run, inst=inst, interpret=False),
        )
        try:  # trace + compile now, while this variant's tiles are in place
            for fn in fns:
                jax.block_until_ready(fn(q, k, v, segs[0]))
            todo.append((name, *fns))
        except Exception as e:  # noqa: BLE001 — mosaic raises various types
            print(f"{name}: refused ({type(e).__name__}: {str(e)[:160]})",
                  flush=True)
    flash._row_kernel = row_kernel
    flash._make_kernel.cache_clear()
    ref, masked = None, {}
    # warm; hold the kernel to the XLA form, and the static kernel to the
    # one under the rows' tile mask (which must be the same to the bit)
    for name, fwd, grad in todo:
        outs = [fwd(q, k, v, segs[0]), *grad(q, k, v, segs[0])]
        if ref is None:  # the XLA form, or without it the first variant
            ref, ref_name = [np.asarray(x, np.float32) for x in outs], name
        else:
            print(name, f"max |delta| to {ref_name}'s out, dq, dk, dv:",
                  [float(np.abs(np.asarray(a, np.float32) - b).max())
                   for a, b in zip(outs, ref)], flush=True)
        if args.static and name != "chosen":
            if name.endswith("_static"):
                print(name, "equal to the masked kernel's out, dq, dk, dv:",
                      [bool(jnp.array_equal(a, b)) for a, b in zip(
                          outs, masked.pop(name[:-len("_static")]))],
                      flush=True)
            elif name != "xla":
                masked[name] = outs
    os.makedirs(args.out, exist_ok=True)
    jax.profiler.start_trace(args.out)
    for _, fwd, grad in todo:
        for i in range(max(RUNS, len(segs))):
            jax.block_until_ready(fwd(q, k, v, segs[i % len(segs)]))
            jax.block_until_ready(grad(q, k, v, segs[i % len(segs)]))
    jax.profiler.stop_trace()
    r = program_trace.reduce(program_trace.load(xplane.find_xplane(args.out)))
    print(f"T={t} heads={hq}/{hkv}x{d} layer_window={lw} rows of "
          f"{args.traffic}: device ms a run; '=' marks a program "
          "the compile cache found equal to an earlier one, which has its runs")
    print(f"{'variant':34s} {'fwd':>8s} {'fwd+bwd':>8s}")
    for name, _, _ in todo:
        cells = []
        for kind in ("fwd", "grad"):
            p = r["programs"].get(f"{kind}_{name}")
            cells.append(f"{1e3 * p['seconds'] / p['runs']:8.3f}" if p
                         else f"{'=':>8s}")
        print(f"{name:34s} {cells[0]} {cells[1]}", flush=True)


if __name__ == "__main__":
    main()
