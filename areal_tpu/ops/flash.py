"""Flash (splash) attention wrapper for packed segment streams on TPU.

Role of the reference's flash-attn varlen path (realhf/impl/model/modules/
attn.py wraps flash_attn_varlen_func; areal relies on HF flash-attention-2):
on TPU the analog is the Pallas splash-attention kernel family shipped with
JAX (jax.experimental.pallas.ops.tpu.splash_attention) — fused streaming
softmax, O(T) activation memory, differentiable (custom VJP), with native
segment-id support that matches our packed layout exactly.

This wrapper adapts splash's [H, T, D] MQA-grouped convention to the
framework's [B, T, H, D] packed-stream convention and masks padding
(segment id 0) on the way out. TPU-only: callers gate on backend (the
engine's attn_impl="flash" config) — CPU tests use the XLA kernel.
"""

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _sk,
    splash_attention_mask as _sm,
)


# Probed splash block edge: None = not probed yet, 0 = kernel defaults (CPU
# backend: the kernel never runs there), else the largest edge that compiled
# AND ran on this process's TPU backend.
_PROBED_BLOCK: "int | None" = None

# below this edge the per-grid-step overhead dominates long-context
# attention; a TPU that cannot compile it is misconfigured, not slow
_MIN_PROBED_EDGE = 512


def probe_block_size(max_block: int = 2048, probe_t: int = 2048) -> int:
    """Find the largest splash block edge this backend can actually run.

    Per-grid-step overhead dominates this stack's pallas kernels, so at long
    contexts the kernel's small default blocks cost several times the large
    ones — but the large ones need the scoped-VMEM limit raised
    (LIBTPU_INIT_ARGS=--xla_tpu_scoped_vmem_limit_kib=65536, appended by
    ``areal_tpu/__init__`` when it runs before jax backend init; without it
    the 2048 edge is refused and 1024 compiles). The choice is PROBED:
    compile+run a small fwd+bwd at each candidate edge and keep the largest
    that works. On a TPU, if no edge >= 512 compiles the probe RAISES with
    the compiler's message — an entry point that lost the flag must not go
    on to train on the kernel's defaults. Result is cached process-wide;
    call once from engine init (TPU backends only — never inside a trace).
    """
    global _PROBED_BLOCK
    if _PROBED_BLOCK is not None:
        return _PROBED_BLOCK
    override = os.environ.get("AREAL_TPU_SPLASH_BLOCK", "")
    if override:
        _PROBED_BLOCK = int(override)
        return _PROBED_BLOCK
    if jax.default_backend() == "cpu":
        _PROBED_BLOCK = 0
        return 0
    import logging

    log = logging.getLogger("areal_tpu.flash")
    q = jnp.ones((1, probe_t, 4, 128), jnp.bfloat16)
    k = jnp.ones((1, probe_t, 1, 128), jnp.bfloat16)
    seg = jnp.ones((1, probe_t), jnp.int32)
    b = max_block
    refused = None
    while b >= _MIN_PROBED_EDGE:
        _PROBED_BLOCK = b
        try:
            out = jax.grad(
                lambda q_: flash_segment_attention(q_, k, k, seg).sum()
            )(q)
            jax.block_until_ready(out)
            log.info("splash block edge probed: %d", b)
            return b
        except Exception as e:  # noqa: BLE001 — mosaic raises various types
            log.warning(
                "splash block %d unavailable (%s: %s) — trying smaller",
                b, type(e).__name__, str(e)[:200],
            )
            refused = e
            _PROBED_BLOCK = None
            _make_kernel.cache_clear()
            b //= 2
    raise RuntimeError(
        f"no splash block edge in [{_MIN_PROBED_EDGE}, {max_block}] "
        "compiles on this TPU (is --xla_tpu_scoped_vmem_limit_kib in "
        "LIBTPU_INIT_ARGS — was areal_tpu imported before the jax backend "
        f"started?). The compiler said: {type(refused).__name__}: {refused}"
    ) from refused


def _block_size(t: int) -> int:
    """Largest probed-safe block edge that divides the sequence length
    (>=128, else 0 = kernel defaults)."""
    want = _PROBED_BLOCK or 0
    if want <= 0:
        return 0
    b = 1
    while b * 2 <= min(want, t) and t % (b * 2) == 0:
        b *= 2
    return b if b >= 128 else 0


@functools.lru_cache(maxsize=32)
def _make_kernel(t: int, rep: int, window: int):
    # ensure_compile_time_eval: this may be reached inside a jit trace, but
    # the kernel object (and the mask arrays it processes) must be concrete —
    # it is cached across traces, and a tracer captured here would escape.
    with jax.ensure_compile_time_eval():
        if 0 < window < t:
            # block-sparse local mask: a packed stream of many short
            # sequences must NOT pay full-causal T² block iteration — any
            # same-segment pair is within (max segment length - 1)
            # positions, so a causal local window >= that bound plus the
            # runtime segment-id mask is exact
            head = _sm.LocalMask((t, t), (window, 0), 0)
        else:
            head = _sm.CausalMask((t, t))
        mask = _sm.MultiHeadMask([head for _ in range(rep)])
        b = _block_size(t)
        if b:
            # recipe from tools/microbench_attn_v2.py (not re-measured on
            # the current machine):
            # - block_kv_compute 512 rather than the full edge: smaller
            #   inner compute tiles overlap the kv DMA
            # - the FUSED dq+dkv backward kernel at 2048-edge blocks: one
            #   data pass instead of two (bwd matmuls contract over T, so
            #   they do not pay the head_dim-64 MXU lane tax the forward
            #   does)
            bs = _sk.BlockSizes(
                block_q=b, block_kv=b, block_kv_compute=min(512, b),
                block_q_dkv=b, block_kv_dkv=b,
                block_kv_dkv_compute=min(512, b),
                use_fused_bwd_kernel=True,
            )
            # residual_checkpoint_name marks out+logsumexp so a remat
            # policy saving "attn_out" skips the forward-kernel recompute
            # in the backward (models/transformer.apply remat_save_attn)
            return _sk.make_splash_mqa_single_device(
                mask, block_sizes=bs, residual_checkpoint_name="attn_out"
            )
        return _sk.make_splash_mqa_single_device(
            mask, residual_checkpoint_name="attn_out"
        )


def flash_segment_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, T]
    causal: bool = True,
    window: int = 0,  # 0 = full causal; else >= max segment length
) -> jnp.ndarray:
    """Drop-in replacement for ops.basic.segment_attention on TPU."""
    assert causal, "splash path is causal-only (decoder models)"
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    kernel = _make_kernel(t, rep, int(window))
    scale = d**-0.5
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.transpose(0, 2, 1, 3).reshape(b, hkv, rep, t, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def per_batch(q_, k_, v_, seg_row):
        ids = _sk.SegmentIds(q=seg_row, kv=seg_row)
        return jax.vmap(kernel, in_axes=(0, 0, 0, None))(q_, k_, v_, ids)

    out = jax.vmap(per_batch)(qg, kt, vt, segment_ids)
    out = out.reshape(b, hq, t, d).transpose(0, 2, 1, 3)
    valid = (segment_ids > 0)[:, :, None, None]
    return jnp.where(valid, out, 0).astype(q.dtype)
