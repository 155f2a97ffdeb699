"""The rotation alone, on one chip: ``ops/rotary.rotate`` through its kernel
over a few (tokens, heads) blocks against the ``jnp`` form
(``ops/basic.rotate_leading``), forward and forward + backward, on q and k of
the window-attention cells' layers, all under one ``jax.profiler`` capture.

    python -m tools.microbench_rope [--out chiprun_out/rope_sweep]
    python -m tools.microbench_rope --shapes 16384,64,64 --blocks 512,8 1024,4

A shape is ``tokens,heads,R[,1]`` of ``[1, tokens, heads x 128]`` bf16 as a
projection writes it, R the rotated channels / 2 (64: a whole head; 32: half
of one, Laguna's full layers under yarn), a trailing 1: every head
rms-normed in the same pass (Mellum's and Keye's q and k; the ``jnp`` form
is then ``rms_norm`` and the rotation); a block is ``tokens,heads`` of the
kernel's (``rotary.BLOCK_TOKENS``, ``rotary.BLOCK_HEADS``; the first is the
tree's). The times are device times of the jitted programs from
``benchmark.program_trace`` (the mean of ``RUNS``), not host clocks; GB/s is
what a rotation has to move (x read and written once a pass, the two tables
read once) over that time, against the chip's 819. ``fwdbwd`` is the rotation
and its transpose on a cotangent: two passes. Every kernel variant is held to
the ``jnp`` form on the chip (the largest difference, in bf16 ulps of the
operands' scale). Off the chip (``JAX_PLATFORMS=cpu``) the kernel runs in
interpret mode: control flow only, the device table stays empty.
``ops/rotary.py``'s block holds what this found (PERF.md, PR 51).
"""

import argparse
import json
import os

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import basic, rotary
from benchmark import harness, program_trace, xplane

RUNS = 3
D = 128
# q and k of Laguna's window and full layers, of Zaya's, of Mellum's
SHAPES = ("16384,64,64", "16384,48,32", "16384,8,64", "16384,8,32",
          "32768,32,64,1", "32768,4,64,1")
BLOCKS = ("512,8", "256,8", "1024,8", "512,4", "1024,4", "2048,2")


def _pair(text):
    return tuple(int(v) for v in text.split(","))


def _programs(form, t, heads, norm, name):
    """(forward, forward + transpose) of one form as programs named for the
    trace, on the projection's ``[1, t, heads x 128]``."""
    def rot(x, cos, sin):
        x4 = x.reshape(1, t, heads, D)
        return form(x4, cos, sin, norm).reshape(x.shape)

    def fwd(x, cos, sin, g):
        return rot(x, cos, sin)

    def fwdbwd(x, cos, sin, g):
        out, vjp = jax.vjp(lambda x: rot(x, cos, sin), x)
        return out, vjp(g)[0]

    fwd.__name__, fwdbwd.__name__ = f"fwd_{name}", f"fwdbwd_{name}"
    return jax.jit(fwd), jax.jit(fwdbwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=SHAPES)
    ap.add_argument("--blocks", nargs="+", default=BLOCKS)
    ap.add_argument("--out", default="chiprun_out/rope_sweep")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    print(json.dumps({"device": str(jax.devices()[0].device_kind)}))
    kernel = "kernel" if on_chip else "interpret"
    real = rotary.rotate_impl, rotary.BLOCK_TOKENS, rotary.BLOCK_HEADS

    todo, moved = [], {}
    for shape in args.shapes:
        t, heads, r, normed = (_pair(shape) + (0,))[:4]
        key = jax.random.split(jax.random.PRNGKey(t + heads + r), 2)
        x = jax.random.normal(key[0], (1, t, heads * D), jnp.bfloat16)
        g = jax.random.normal(key[1], x.shape, jnp.bfloat16)
        cos, sin = basic.rope_at(
            jnp.arange(t)[None], basic.rope_inv_freq(2 * r, 10000.0)
        )
        ops = (x, cos, sin, g)
        norm = (jnp.ones((D,), jnp.bfloat16), 1e-6) if normed else None
        tag = f"t{t}_h{heads}_r{r}{'n' * normed}"
        moved[tag] = 2 * x.size * 2 + 2 * cos.size * 4  # bytes a pass
        want = None
        for block in ["xla"] + list(args.blocks):
            if block == "xla":
                rotary.rotate_impl = lambda d, t=None: "xla"
            else:
                rotary.BLOCK_TOKENS, rotary.BLOCK_HEADS = _pair(block)
                rotary._rotate_rows.clear_cache()  # it read the old block
                if t % rotary.BLOCK_TOKENS:
                    continue
                rotary.rotate_impl = lambda d, t=None: kernel
            name = f"{tag}_{block.replace(',', 'x')}"
            fwd, both = _programs(rotary.rotate, t, heads, norm, name)
            try:  # compile and check, untraced
                got = [np.asarray(o, np.float32) for o in both(*ops)]
            except Exception as err:  # noqa: BLE001 — a block the chip refuses
                harness.log(f"{name}: refused: {type(err).__name__}: "
                            f"{str(err)[:300]}")
                continue
            if want is None:
                want = got
            off = max(np.abs(a - b).max() for a, b in zip(got, want))
            harness.log(f"{name}: max |out - xla| = {off:.4g} "
                        f"({off / 2.0 ** -8 / np.abs(want[0]).max():.2f} ulp "
                        "of the scale)")
            jax.block_until_ready(fwd(*ops))
            todo.append((fwd, both, ops))
    rotary.rotate_impl, rotary.BLOCK_TOKENS, rotary.BLOCK_HEADS = real

    traced = harness.TracedWindow(True, os.path.join(harness.ROOT, args.out))
    with traced:
        for fwd, both, ops in todo:
            for _ in range(RUNS):
                jax.block_until_ready(fwd(*ops))
                jax.block_until_ready(both(*ops))
    if not on_chip:
        return
    r = program_trace.reduce(
        program_trace.load(xplane.find_xplane(traced.dir))
    )
    rows = {}
    for prog, p in sorted(r["programs"].items()):
        if not prog.startswith(("fwd_", "fwdbwd_")):
            continue
        ms = 1e3 * p["seconds"] / max(p["runs"], 1)
        tag = "_".join(prog.split("_")[1:4])
        passes = 2 if prog.startswith("fwdbwd_") else 1
        rows[prog] = {
            "ms": round(ms, 4),
            "gb_per_s": round(passes * moved[tag] / ms / 1e6, 1),
        }
        print(prog, json.dumps(rows[prog]), flush=True)
    with open(os.path.join(harness.ROOT, args.out, "sweep.json"), "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
