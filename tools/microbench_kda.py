"""The gated delta rule's chunked scan alone, on one chip, on one packed row
at the published widths (32 heads of 128 | 128): the whole op forward and
forward + backward, as the Pallas kernels against the ``jax.numpy`` forms
they are held equal to (``ops/kda.py``); then its three parts apart, kernel
against ``jax.numpy``: the chunk operands forward (``chunk_ops_fwd`` |
``_prep``), the operands' cotangents (``chunk_ops_bwd`` | XLA's own
differentiation of ``_prep``, a block of 16 chunks at a time), the state
pass (``kda_fwd_h`` | the ``lax.scan``); and the forms' agreement on the
chip.

    python -m tools.microbench_kda [--tokens 8192] [--seqs 3]
    python -m tools.microbench_kda --tokens 16384 --key-heads 16

``--key-heads n`` times the SCALAR-DECAY form (Qwen3-Next's ``gdn`` mixer at
16 key and 32 value heads: ``g`` of ``beta``'s rank, ``q, k`` of ``n`` heads):
the same passes, the operands as ``gdn_ops_fwd`` | ``_prep_scalar`` and their
cotangents as ``gdn_ops_bwd`` | XLA's differentiation of ``_prep_scalar``;
``--ops-chunks 4 8 16`` sweeps the chunks a grid step of the operand kernels
takes together (``ops/kda._OPS_CHUNKS``).

``--prep`` times instead what feeds the scan (``ops/conv_prep.py``): the
short convolution with its ``silu`` and head l2norm, ``conv_prep_fwd`` /
``conv_prep_bwd`` against the ``jax.numpy`` form (``_short_conv`` and the
mixers' lines) on one packed row of ``--tokens`` at ``--channels`` with the
first ``--norm-channels`` normed (Kimi: ``--tokens 8192 --channels 4096
--norm-channels 4096`` twice and ``--norm-channels 0`` once a layer;
Qwen3-Next: ``--tokens 16384 --channels 8192 --norm-channels 4096`` once), and
``--prep-blocks 256,1024 512,2048 ...`` sweeps the kernels' tokens and
channels a block (``conv_prep.BLOCK_TOKENS``, ``BLOCK_CHANNELS``).

Host clock around ``block_until_ready``, the best of ``RUNS``. ``ops/kda.py``
resolves ``scan_impl`` to what this found faster (PERF.md, PR 33, PR 35).
"""

import argparse
import json
import time

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import kda

RUNS = 3


def _best_ms(fn):
    out = jax.block_until_ready(fn())  # compiles
    best = float("inf")
    for _ in range(RUNS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e3


def _worst_rel(xs, ys):
    """The largest difference of a pair, over the second's largest value."""
    return max(
        float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max()
              / (jnp.abs(y.astype(jnp.float32)).max() + 1e-9))
        for x, y in zip(xs, ys)
    )


def _packed_segments(t, seqs):
    """[1, t] int32: ``seqs`` sequences and a padded tail, borders inside
    chunks (and so inside the convolution's blocks)."""
    real = t - 200
    cuts = np.linspace(0, real, seqs + 1).astype(int) + np.arange(
        seqs + 1
    ) * 7 % 61
    seg = np.zeros((1, t), np.int32)
    for i in range(seqs):
        seg[0, cuts[i]:min(cuts[i + 1], real)] = i + 1
    return jnp.asarray(seg)


def prep(args):
    """The convolution, ``silu`` and head l2norm: kernel against
    ``jax.numpy``, forward, forward + backward, and the kernel's backward
    alone; then the kernels at each ``--prep-blocks`` tokens,channels."""
    from areal_tpu.models.transformer import _short_conv
    from areal_tpu.ops import conv_prep as cp

    t, c, nc = args.tokens, args.channels, args.norm_channels
    interp = jax.default_backend() != "tpu"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (1, t, c), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (c, 4)) * 0.5).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[2], (1, t, c), jnp.float32).astype(jnp.bfloat16)
    seg = _packed_segments(t, args.seqs)

    def plain(x, w):
        y = jax.nn.silu(_short_conv(x, w, seg))
        n = y[..., :nc].reshape(1, t, nc // 128, 128)
        n = n * jax.lax.rsqrt(jnp.sum(n * n, axis=-1, keepdims=True) + 1e-6)
        return jnp.concatenate(
            [n.reshape(1, t, nc), y[..., nc:]], axis=-1
        ).astype(x.dtype)

    def kernel(x, w):
        return cp.conv_prep(x, w, seg, nc, interp)

    def both(fn):
        return jax.jit(lambda x, w: jax.vjp(fn, x, w)[1](ct))

    print(json.dumps({"prep": {"tokens": t, "channels": c, "normed": nc},
                      "MB_in_and_out": 4 * t * c / 1e6}))
    out = {}
    for impl, fn in (("kernel", kernel), ("xla", plain)):
        fwd = jax.jit(fn)
        out[impl], ms = _best_ms(lambda: fwd(x, w))
        print(json.dumps({"pass": "prep forward", "impl": impl, "ms": ms}))
        back = both(fn)
        out[impl + "_grads"], ms = _best_ms(lambda: back(x, w))
        print(json.dumps({"pass": "prep forward+backward", "impl": impl,
                          "ms": ms}))
    print(json.dumps({
        "prep_kernel_vs_xla_worst_rel": _worst_rel(
            [out["kernel"]], [out["xla"]]
        ),
        "prep_grads_worst_rel": _worst_rel(
            out["kernel_grads"], out["xla_grads"]
        ),
    }))
    sizes = [(cp.BLOCK_TOKENS, cp.BLOCK_CHANNELS)] + args.prep_blocks
    for block, chans in sizes:
        # read while a kernel's wrapper is traced; the process ends with it
        cp.BLOCK_TOKENS, cp.BLOCK_CHANNELS = block, chans
        jax.clear_caches()
        fwd = jax.jit(kernel)
        _, f_ms = _best_ms(lambda: fwd(x, w))
        back = jax.jit(lambda x, w, g: cp._bwd_rows(
            x, w, seg, g, norm_channels=nc, interpret=interp
        ))
        _, b_ms = _best_ms(lambda: back(x, w, ct))
        print(json.dumps({"pass": "prep kernels", "block_tokens": block,
                          "block_channels": chans, "forward_ms": f_ms,
                          "backward_ms": b_ms}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--seqs", type=int, default=3)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--key-heads", type=int, default=0,
                    help="the scalar-decay form: q, k of this many heads")
    ap.add_argument("--ops-chunks", type=int, nargs="*", default=[],
                    help="also time the operand kernels at these block sizes")
    ap.add_argument("--prep", action="store_true",
                    help="time the convolution's kernels, not the scan")
    ap.add_argument("--channels", type=int, default=4096)
    ap.add_argument("--norm-channels", type=int, default=4096)
    ap.add_argument("--prep-blocks", nargs="*", default=[],
                    type=lambda s: tuple(int(n) for n in s.split(",")),
                    help="tokens,channels a block to time the kernels at")
    args = ap.parse_args()
    print(json.dumps({"device": str(jax.devices()[0].device_kind)}))
    if args.prep:
        return prep(args)
    t, h, d = args.tokens, args.heads, args.dim
    scalar, hk = bool(args.key_heads), args.key_heads or args.heads
    ks = jax.random.split(jax.random.PRNGKey(0), 5)

    def unit(key):
        x = jax.random.normal(key, (1, t, hk, d), jnp.float32)
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True))

    q, k = unit(ks[0]).astype(jnp.bfloat16), unit(ks[1]).astype(jnp.bfloat16)
    v = (jax.random.normal(ks[2], (1, t, h, d)) * 0.1).astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(
        ks[3], (1, t, h) if scalar else (1, t, h, d), minval=-7, maxval=0.5
    ))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h)))
    seg = _packed_segments(t, args.seqs)
    a = (q, k, v, g, beta, seg)
    # off the chip the kernels run in Pallas interpret mode: the control
    # flow's rehearsal, never a time
    interp = jax.default_backend() != "tpu"

    def forward(impl):
        return jax.jit(lambda *x: kda.kda_scan(*x, impl=impl, interpret=interp)[0])

    def both(impl):
        return jax.jit(jax.grad(
            lambda *x: kda.kda_scan(*x, seg, impl=impl, interpret=interp)[0].astype(
                jnp.float32
            ).sum(),
            argnums=(0, 1, 2, 3, 4),
        ))

    out = {}
    for impl in ("kernel", "xla"):
        fn = forward(impl)
        out[impl], ms = _best_ms(lambda: fn(*a))
        print(json.dumps({"pass": "forward", "impl": impl, "ms": ms}))
        gfn = both(impl)
        grads, ms = _best_ms(lambda: gfn(*a[:5]))
        out[impl + "_grads"] = grads
        print(json.dumps({"pass": "forward+backward", "impl": impl, "ms": ms}))
    diff = jnp.abs(
        out["kernel"].astype(jnp.float32) - out["xla"].astype(jnp.float32)
    )
    print(json.dumps({
        "kernel_vs_xla_worst_abs": float(diff.max()),
        "output_abs_mean": float(jnp.abs(out["xla"].astype(jnp.float32)).mean()),
        "grads_worst_rel": _worst_rel(out["kernel_grads"], out["xla_grads"]),
    }))
    ins = kda._chunk_inputs(*a)

    ops = {}
    for impl in ("kernel", "xla"):
        fn = jax.jit(lambda *x, impl=impl: kda._operands(*x, impl, interp))
        ops[impl], ms = _best_ms(lambda: fn(*ins))
        print(json.dumps({"pass": "operands forward", "impl": impl, "ms": ms}))
    cts = jax.tree_util.tree_map(
        lambda x: (jax.random.normal(ks[0], x.shape) * 0.1).astype(x.dtype),
        ops["xla"],
    )

    def cotangents(impl):
        if scalar:
            return lambda *x: kda._scalar_operands_bwd(
                *x[:6], x[6:], impl, interp
            )
        if impl == "kernel":
            return lambda *x: kda._operands_bwd_kernel(
                *x[:6], x[6:], interpret=interp
            )
        return lambda *x: kda._operands_bwd_xla(*x[:6], x[6:])

    d_ins = {}
    for impl in ("kernel", "xla"):
        jit = jax.jit(cotangents(impl))
        d_ins[impl], ms = _best_ms(lambda: jit(*ins, *cts))
        print(json.dumps({"pass": "operands cotangents", "impl": impl, "ms": ms}))
    print(json.dumps({
        "operands_kernel_vs_xla_worst_rel": _worst_rel(ops["kernel"], ops["xla"]),
        "cotangents_kernel_vs_xla_worst_rel": _worst_rel(
            d_ins["kernel"], d_ins["xla"]
        ),
    }))
    for impl, fn in (
        ("kernel", lambda o: kda._state_fwd_kernel(o, interpret=interp)),
        ("xla", kda._state_fwd_xla),
    ):
        jit = jax.jit(fn)
        _, ms = _best_ms(lambda: jit(ops["xla"]))
        print(json.dumps({"pass": "state pass forward", "impl": impl, "ms": ms}))
    for nb in args.ops_chunks:  # read while a kernel's wrapper is traced
        kda._OPS_CHUNKS = nb  # the tool's last pass: the process ends with it
        jax.clear_caches()
        fwd = jax.jit(lambda *x: kda._operands(*x, "kernel", interp))
        _, ms = _best_ms(lambda: fwd(*ins))
        print(json.dumps({"pass": "operands forward", "impl": "kernel",
                          "ops_chunks": nb, "ms": ms}))
        back = jax.jit(cotangents("kernel"))
        _, ms = _best_ms(lambda: back(*ins, *cts))
        print(json.dumps({"pass": "operands cotangents", "impl": "kernel",
                          "ops_chunks": nb, "ms": ms}))


if __name__ == "__main__":
    main()
