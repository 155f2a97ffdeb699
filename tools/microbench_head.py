"""The trainer's output head with its log-probability alone, on one chip:
``ops/chunked_head.chunked_gather_logprobs`` forward and forward + backward
at the two heads the benchmark's cells have, over the chunk sizes, against
the form it replaced (JAX's differentiation of a checkpointed scan body that
upcasts both operands and gathers the label's logit), all under one
``jax.profiler`` capture.

    python -m tools.microbench_head [--chunks 512 1024 2048 4096]
        [--out chiprun_out/head_sweep]

The times are device times of the jitted programs and of the device scopes
``lm_head`` / ``loss`` from ``benchmark.program_trace`` (what
``lm_head_time_share.train`` is read by), not host clocks. Before the sweep
it prints the operand precision of the head's three matmuls on this device:
a float32 dot at default precision against the same dot on operands rounded
to bf16, bitwise (``ops/chunked_head.py`` rounds ``dlogits`` to bf16 only
because the chip already does). ``ops/chunked_head._chunk_tokens`` holds what
this found (PERF.md, PR 28).
"""

import argparse
import json
import os

import areal_tpu  # noqa: F401 — the scoped-VMEM flag, before the backend

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import chunked_head
from benchmark import harness, program_trace, xplane

RUNS = 3
# (tokens, model width, vocabulary, tied): Qwen2-0.5B, OLMoE-1B-7B
SHAPES = ((4096, 896, 151936, True), (4096, 2048, 50304, False))


def old_form(hidden, head, labels, chunk=1024):
    """The head as it was before PR 28, a frozen copy kept as the sweep's
    baseline and not maintained with the op: float32 upcasts of both
    operands, ``take_along_axis``, and a ``jax.checkpoint``-ed scan body
    that JAX differentiates."""
    b, t, d = hidden.shape
    chunk = min(chunk, t)
    nc = t // chunk
    hr = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lr = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def body(carry, inp):
        hc, lc = inp
        with chunked_head.device_scope("lm_head"):
            logits = hc.astype(jnp.float32) @ head.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        lp = jnp.take_along_axis(logits, lc[..., None], axis=-1).squeeze(-1)
        return carry, lp - logz

    _, lps = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), 0, (hr, lr))
    return lps.swapaxes(0, 1).reshape(b, t)


def precision_report(rng, c, d, v):
    """Do this device's default-precision float32 dots round their operands
    to bf16? The three matmuls of the head at a chunk's shape."""
    f32, bf = jnp.float32, jnp.bfloat16
    hc = jnp.asarray(rng.standard_normal((c, d)), bf)
    head = jnp.asarray(rng.standard_normal((d, v)) * 0.02, bf)
    dz = jnp.asarray(rng.standard_normal((c, v)) * 1e-3, f32)  # not bf16 values
    dots = {  # (lhs, rhs, contracted axes of each)
        "logits = h @ head": (hc, head, ((1,), (0,))),
        "dh = dlogits @ head^T": (dz, head, ((1,), (1,))),
        "dhead = h^T @ dlogits": (hc, dz, ((0,), (0,))),
    }

    def dot(a, b, dims, dtype, precision=None):
        return jax.lax.dot_general(
            a.astype(dtype), b.astype(dtype), (dims, ((), ())),
            precision=precision, preferred_element_type=f32,
        )

    out = {}
    for name, (a, b, dims) in dots.items():
        today, rounded, exact = (
            jax.jit(dot, static_argnums=(2, 3, 4))(a, b, dims, dtype, prec)
            for dtype, prec in ((f32, None), (bf, None), (f32, "highest"))
        )
        out[name] = {
            "f32_default_bitwise_equal_to_bf16_operands": bool(
                jnp.array_equal(today, rounded)),
            "max_abs_f32_default_minus_bf16": float(
                jnp.abs(today - rounded).max()),
            "max_abs_f32_default_minus_highest": float(
                jnp.abs(today - exact).max()),
            "max_abs_highest": float(jnp.abs(exact).max()),
        }
        print("precision:", name, json.dumps(out[name]), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, nargs="+",
                    default=(512, 1024, 2048, 4096))
    ap.add_argument("--out", default="chiprun_out/head_sweep")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes: the control flow on the CPU, no times")
    args = ap.parse_args()
    shapes = SHAPES
    if args.rehearse:
        shapes = ((64, 32, 512, True), (64, 16, 256, False))
        args.chunks = (16, 64)
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    report = {"device": jax.devices()[0].device_kind,
              "precision": precision_report(
                  rng, min(1024, shapes[0][0]), *shapes[0][1:3]),
              "rows": {}}

    todo, checks = [], []
    for t, d, v, tied in shapes:
        hidden = jnp.asarray(rng.standard_normal((1, t, d)), bf)
        # the parameter as the model stores it
        table = jnp.asarray(
            rng.standard_normal((v, d) if tied else (d, v)) * 0.02, bf)
        labels = jnp.asarray(rng.integers(0, v, (1, t)), jnp.int32)
        weight = jnp.asarray(rng.standard_normal((1, t)), jnp.float32)
        args_ = (hidden, table, labels, weight)

        def variant(name, logp):
            def fwd(hidden, table, labels, weight):
                return logp(hidden, table, labels)

            def loss(hidden, table, labels, weight):
                return (logp(hidden, table, labels) * weight).sum()

            fwd.__name__ = f"fwd_{name}"
            bwd = jax.grad(loss, argnums=(0, 1))
            both = lambda *a: bwd(*a)  # noqa: E731
            both.__name__ = f"fwdbwd_{name}"
            return name, jax.jit(fwd), jax.jit(both)

        tag = f"v{v}"
        ours = [variant(
            f"old_c1024_{tag}",
            lambda h, w, l, tied=tied: old_form(h, w.T if tied else w, l))]
        for c in args.chunks:
            ours.append(variant(
                f"new_c{c}_{tag}",
                lambda h, w, l, c=c, tied=tied:
                    chunked_head.chunked_gather_logprobs(
                        h, w, l, chunk=c, vocab_axis=0 if tied else 1)))
        print(f"{tag}: _chunk_tokens({t}) =",
              chunked_head._chunk_tokens(t), flush=True)
        base = None
        for name, fwd, both in ours:  # compile and check, untraced
            try:
                lp = np.asarray(fwd(*args_), np.float32)
                dh, dw = (np.asarray(g, np.float32) for g in both(*args_))
            except Exception as err:  # noqa: BLE001 — a size the chip refuses
                harness.log(f"{name}: refused: {type(err).__name__}: "
                            f"{str(err)[:300]}")
                continue
            if base is None:
                base = (lp, dh, dw)
            checks.append({
                "variant": name,
                "max_abs_logp_minus_old": float(np.abs(lp - base[0]).max()),
                "max_abs_dhidden_minus_old": float(np.abs(dh - base[1]).max()),
                "max_abs_dhead_minus_old": float(np.abs(dw - base[2]).max()),
                "max_abs_dhidden": float(np.abs(base[1]).max()),
                "max_abs_dhead": float(np.abs(base[2]).max()),
            })
            print("check:", json.dumps(checks[-1]), flush=True)
            todo.append((fwd, both, args_))
    report["checks"] = checks

    traced = harness.TracedWindow(True, os.path.join(harness.ROOT, args.out))
    with traced:
        for fwd, both, args_ in todo:
            for _ in range(RUNS):
                jax.block_until_ready(fwd(*args_))
                jax.block_until_ready(both(*args_))
    events = program_trace.load(xplane.find_xplane(traced.dir))
    r = program_trace.reduce(events)
    for prog, p in sorted(r["programs"].items()):
        if not prog.startswith(("fwd_", "fwdbwd_")):
            continue
        runs = max(p["runs"], 1)
        report["rows"][prog] = {
            "ms": round(1e3 * p["seconds"] / runs, 3),
            "scopes_ms": {
                s: round(1e3 * sec / runs, 3)
                for s, sec in r["program_scopes"].get(prog, {}).items()},
        }
        print(prog, json.dumps(report["rows"][prog]), flush=True)
    # the longest operations of each backward, by name and shape
    longest = {}
    for ev in events:
        prog = ev[5].get("program", "")
        if ev[1] == xplane.OPS_LINE and prog in report["rows"] and (
            prog.startswith("fwdbwd_") and not ev[2].startswith("while")
        ):
            runs = max(r["programs"][prog]["runs"], 1)
            key = (prog, ev[2][:80], program_trace.scope_of(ev[5].get("op_name")))
            longest[key] = longest.get(key, 0.0) + ev[4] / 1e6 / runs
    report["longest_ops_ms"] = {}
    for (prog, name, scope), ms in sorted(
        longest.items(), key=lambda kv: (kv[0][0], -kv[1])
    ):
        ops = report["longest_ops_ms"].setdefault(prog, [])
        if len(ops) < 8:
            ops.append([name, scope, round(ms, 3)])
            print(f"op: {prog} {ms:8.3f} ms  {scope or '-':8s} {name}", flush=True)
    with open(os.path.join(harness.ROOT, args.out, "sweep.json"), "w") as fh:
        json.dump(report, fh, indent=1)


if __name__ == "__main__":
    main()
