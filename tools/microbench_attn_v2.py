"""Attention sweep. Protocol: a warmup call ended by a REAL scalar fetch (so
no compile bleeds into the first timed window), then 5 chained dispatches
with one final fetch. Not re-run on the current machine."""

import os
import sys
import time

_flag = "--xla_tpu_scoped_vmem_limit_kib=65536"
if _flag not in os.environ.get("LIBTPU_INIT_ARGS", ""):
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + _flag
    ).strip()

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk,
    splash_attention_mask as sm,
)

HQ, HKV, D = 14, 2, 64
REP = HQ // HKV
ITERS = 5
SEQ = sk.QKVLayout.SEQ_MINOR


def fetch(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.asarray(leaf).astype(jnp.float32).ravel()[0])


def run(T, window=0):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(key, (1, T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(key, (1, T, HKV, D), jnp.bfloat16)
    seg = jnp.ones((1, T), jnp.int32)
    fwd_flops = 2 * 2 * T * T * (HQ * D) * 0.5
    if window:
        fwd_flops = 2 * 2 * T * window * (HQ * D)

    def make(**kw):
        with jax.ensure_compile_time_eval():
            if 0 < window < T:
                head = sm.LocalMask((T, T), (window, 0), 0)
            else:
                head = sm.CausalMask((T, T))
            mask = sm.MultiHeadMask([head for _ in range(REP)])
            bs = sk.BlockSizes(**kw) if kw else None
            kernel = sk.make_splash_mqa_single_device(mask, block_sizes=bs)

        def attend(q_, k_, v_):
            qg = q_.transpose(0, 2, 1, 3).reshape(1, HKV, REP, T, D)
            kt = k_.transpose(0, 2, 1, 3)
            vt = v_.transpose(0, 2, 1, 3)

            def per_batch(q__, k__, v__, seg_row):
                ids = sk.SegmentIds(q=seg_row, kv=seg_row)
                return jax.vmap(kernel, in_axes=(0, 0, 0, None))(
                    q__, k__, v__, ids
                )

            out = jax.vmap(per_batch)(qg, kt, vt, seg)
            return out.reshape(1, HQ, T, D).transpose(0, 2, 1, 3)

        return attend

    def bench(name, attend, grad=False):
        try:
            if grad:
                fn = jax.jit(
                    jax.grad(
                        lambda q_, k_, v_: attend(q_, k_, v_)
                        .astype(jnp.float32)
                        .sum(),
                        argnums=(0, 1, 2),
                    )
                )
                flops = fwd_flops * 3.5
            else:
                fn = jax.jit(attend)
                flops = fwd_flops
            fetch(fn(q, k, v))  # warmup incl. real compile completion
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            fetch(out)
            dt = (time.perf_counter() - t0) / ITERS
            print(
                f"T={T} w={window} {name:44s} {dt*1e3:8.2f} ms "
                f"{flops/dt/1e12:6.2f} TF/s",
                flush=True,
            )
            return dt
        except Exception as e:
            print(f"T={T} w={window} {name:44s} FAIL "
                  f"{type(e).__name__}: {str(e)[:100]}", flush=True)
            return None

    b = min(1024, T)
    base = dict(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )
    bench("fwd all-1024 (r4 prod)", make(**base))
    bench("fwd kvc512", make(block_q=b, block_kv=b, block_kv_compute=512))
    bench("fwd kSEQ kvc512",
          make(block_q=b, block_kv=b, block_kv_compute=512, k_layout=SEQ))
    bench("grad all-1024 unfused (r4 prod)", make(**base), grad=True)
    fused = dict(
        block_q=b, block_kv=b, block_kv_compute=512,
        block_q_dkv=b, block_kv_dkv=min(2048, T),
        block_kv_dkv_compute=min(2048, T),
        use_fused_bwd_kernel=True,
    )
    bench("grad fused q1024 dkv2048 kvc512", make(**fused), grad=True)
    f2 = dict(fused)
    f2.update(block_kv_dkv=b, block_kv_dkv_compute=b)
    bench("grad fused q1024 dkv1024 kvc512", make(**f2), grad=True)
    f3 = dict(fused)
    f3.update(block_q_dkv=min(2048, T))
    bench("grad fused q2048 dkv2048 kvc512", make(**f3), grad=True)


run(24576)
run(16384)
run(16384, window=2176)
run(8192)
