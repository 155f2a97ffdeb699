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
    args = ap.parse_args()
    t, h, d = args.tokens, args.heads, args.dim
    scalar, hk = bool(args.key_heads), args.key_heads or args.heads
    print(json.dumps({"device": str(jax.devices()[0].device_kind)}))
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
    # a few sequences and a padded tail, borders inside chunks
    real = t - 200
    cuts = np.linspace(0, real, args.seqs + 1).astype(int) + np.arange(
        args.seqs + 1
    ) * 7 % 61
    seg = np.zeros((1, t), np.int32)
    for i in range(args.seqs):
        seg[0, cuts[i]:min(cuts[i + 1], real)] = i + 1
    seg = jnp.asarray(seg)
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
