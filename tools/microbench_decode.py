"""Decode time decomposition at the bench serving shape.

S=128 slots, Qwen2-0.5B geometry, pool 1280x256 pages, ~1.2k cached tokens
per slot. Times (a) the full fused decode chunk (_decode_multi_forward),
(b) the paged attention kernel standalone, (c) the LM-head matmul, (d) the
QKV/MLP matmul block — to see what the 64-step chunk actually spends.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_flag = "--xla_tpu_scoped_vmem_limit_kib=65536"
if _flag not in os.environ.get("LIBTPU_INIT_ARGS", ""):
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + _flag
    ).strip()

import numpy as np
import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig
from areal_tpu.models.transformer import init_params
from areal_tpu.inference import model_runner as mr
from areal_tpu.ops.paged_attention import (
    packed_pool_shape,
    paged_decode_attention,
)

S = 128
STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 64
AVG_LEN = 1200
PAGE, NP = 256, 1280

cfg = ModelConfig(
    vocab_size=32768, hidden_size=896, intermediate_size=4864,
    num_layers=24, num_heads=14, num_kv_heads=2, head_dim=64,
    max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
    tie_word_embeddings=True, attention_bias=True, family="qwen2",
)
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)

kshape = packed_pool_shape(cfg.num_layers, cfg.num_kv_heads, NP, PAGE, 64)
cache = {
    "k": jnp.zeros(kshape, jnp.bfloat16),
    "v": jnp.zeros(kshape, jnp.bfloat16),
}
rng = np.random.default_rng(0)
lengths = jnp.asarray(
    rng.integers(AVG_LEN - 300, AVG_LEN + 300, size=S), jnp.int32
)
pps = 9  # ceil((1500+64)/256)+1
tables = jnp.asarray(
    rng.integers(0, NP, size=(S, pps)), jnp.int32
)


def fetch(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.asarray(leaf).astype(jnp.float32).ravel()[0])


def timeit(name, fn, iters=5, flops=None, tokens=None):
    out = fn()
    jax.block_until_ready(out)
    fetch(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    fetch(out)
    dt = (time.perf_counter() - t0) / iters
    extra = ""
    if flops:
        extra += f" {flops/dt/1e12:6.2f} TF/s"
    if tokens:
        extra += f" {tokens/dt:8.1f} tok/s"
    print(f"{name:50s} {dt*1e3:9.2f} ms{extra}", flush=True)
    return dt


# (a) full fused decode chunk
tokens0 = jnp.ones((S,), jnp.int32)
active = jnp.ones((S,), bool)
remaining = jnp.full((S,), 4096, jnp.int32)
no_stop = jnp.zeros((S,), jnp.int32)
stop_tokens = jnp.full((S, 2), -1, jnp.int32)
key = jax.random.PRNGKey(1)


def chunk():
    return mr._decode_multi_forward(
        params, cfg, cache, tables, lengths, tokens0, active,
        remaining, no_stop, stop_tokens, key,
        jnp.full((S,), 1.0, jnp.float32), jnp.full((S,), 1.0, jnp.float32),
        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool),
        steps=STEPS, topk_bound=0, attn_impl="kernel", ppcb=4, spb=8,
    )[0]


dt_chunk = timeit(
    f"full decode chunk steps={STEPS}", chunk, iters=3,
    tokens=S * STEPS,
)
print(f"  -> per model step: {dt_chunk/STEPS*1e3:.2f} ms", flush=True)

# (b) kernel standalone (one layer's call), chunk buffer T=STEPS
q = jax.random.normal(jax.random.PRNGKey(2), (S, 14, 64), jnp.bfloat16)
ck = jnp.zeros((S, 2, STEPS, 64), jnp.bfloat16)
cv = jnp.zeros((S, 2, STEPS, 64), jnp.bfloat16)
counts = jnp.full((S,), STEPS // 2, jnp.int32)
li = jnp.asarray(0, jnp.int32)


# pools as ARGUMENTS (closing over them bakes 4GB compile constants and
# corrupts the timing — the round-3 memory rule); 50 dependent in-jit
# calls amortize the per-window dispatch + fetch cost
@jax.jit
def kernel_call(q_, k_, v_):
    def body(qc, _):
        o = paged_decode_attention(
            qc, k_, v_, li, lengths, tables, ck, cv, counts,
            pages_per_compute_block=4, slots_per_block=8,
        )
        return (qc + o.astype(qc.dtype) * 1e-6), None
    return jax.lax.scan(body, q_, None, length=50)[0]


kv_bytes = float(2 * S * AVG_LEN * 2 * 64 * 2)  # k+v read per call
dt_k = timeit(
    "paged kernel (50 in-jit calls, per call)",
    lambda: kernel_call(q, cache["k"], cache["v"]), iters=1,
) / 50
print(f"  -> kernel x24 layers x{STEPS} steps: "
      f"{dt_k*24*STEPS*1e3:.1f} ms of chunk; "
      f"HBM {kv_bytes/dt_k/1e9:.0f} GB/s", flush=True)

# (c) LM head
x = jax.random.normal(jax.random.PRNGKey(3), (S, 896), jnp.bfloat16)
emb = params["embedding"]


@jax.jit
def head(x_):
    return (x_.astype(jnp.float32) @ emb.T.astype(jnp.float32))


dt_h = timeit("lm head [128,896]x[896,32k] f32", lambda: head(x), iters=20,
              flops=2 * S * 896 * 32768)
print(f"  -> head x{STEPS} steps: {dt_h*STEPS*1e3:.1f} ms of chunk",
      flush=True)


@jax.jit
def head_bf16(x_):
    return x_ @ emb.T


timeit("lm head bf16", lambda: head_bf16(x), iters=20,
       flops=2 * S * 896 * 32768)

# (d) per-layer matmuls (qkv+o+mlp)
lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])


@jax.jit
def layer_mms(x_):
    h = x_
    q_ = h @ lp["wq"]; k_ = h @ lp["wk"]; v_ = h @ lp["wv"]
    o = (q_ @ lp["wo"])
    g = h @ lp["w_gate"]; u = h @ lp["w_up"]
    dn = (g * u) @ lp["w_down"]
    return o + dn + k_.sum() + v_.sum()


mm_flops = 2 * S * 896 * (896 + 128 + 128 + 896 + 4864 * 3)
dt_m = timeit("layer matmuls (qkv+o+mlp)", lambda: layer_mms(x), iters=20,
              flops=mm_flops)
print(f"  -> matmuls x24 x{STEPS}: {dt_m*24*STEPS*1e3:.1f} ms of chunk",
      flush=True)
