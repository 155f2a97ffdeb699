"""The fused splash kernel against the plain XLA form it replaces on a TPU
(``ops/flash.flash_segment_attention`` vs ``ops/basic.segment_attention``):
outputs and the gradients of q, k and v, with the kernel in Pallas interpret
mode on the CPU. Ragged segments with tail padding, Qwen2-0.5B's 14/2 heads
of 64; full causal and a local window smaller than the row; one tile per row
and several (the block-skipping paths)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops import flash as F
from areal_tpu.ops.basic import segment_attention

T, HQ, HKV, D = 256, 14, 2, 64


@pytest.fixture(autouse=True)
def fresh_kernels():
    F._make_kernel.cache_clear()
    yield
    F._make_kernel.cache_clear()


def _row(lens, t=T):
    seg = np.zeros(t, np.int32)  # 0 = padding
    at = 0
    for i, n in enumerate(lens):
        seg[at:at + n] = i + 1
        at += n
    assert at < t, "the cases keep a padded tail"
    return seg


def _inputs(dtype, seg_rows, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    b = len(seg_rows)
    q = jax.random.normal(ks[0], (b, T, HQ, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, T, HKV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, T, HKV, D), jnp.float32).astype(dtype)
    cot = jax.random.normal(ks[3], (b, T, HQ, D), jnp.float32)
    return q, k, v, jnp.asarray(np.stack(seg_rows)), cot


def _out_and_grads(attend, q, k, v, seg, cot):
    def loss(q, k, v):
        out = attend(q, k, v, seg)
        return (out.astype(jnp.float32) * cot).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    return [np.asarray(x, np.float32) for x in (out, *grads)]


# window 0 = full causal; a local window must cover the longest segment
_CASES = [
    pytest.param(0, None, [[100, 60, 70]], jnp.float32, 3e-5, id="causal-f32"),
    pytest.param(0, 128, [[100, 60, 70], [31, 190]], jnp.float32, 3e-5,
                 id="causal-f32-edge128-rows2"),
    pytest.param(128, 128, [[120, 37, 64]], jnp.float32, 3e-5,
                 id="window128-f32-edge128"),
    pytest.param(128, None, [[128, 5, 90]], jnp.float32, 3e-5,
                 id="window128-f32"),
    pytest.param(0, 128, [[100, 60, 70]], jnp.bfloat16, 4e-2,
                 id="causal-bf16-edge128"),
    pytest.param(128, 128, [[120, 37, 64]], jnp.bfloat16, 4e-2,
                 id="window128-bf16-edge128"),
]


@pytest.mark.parametrize("window,edge_cap,lens,dtype,tol", _CASES)
def test_splash_matches_xla_segment_attention(
    monkeypatch, window, edge_cap, lens, dtype, tol
):
    monkeypatch.setattr(F, "_PROBED_BLOCK", edge_cap)
    tiles = F._block_sizes(T, window, D)
    assert tiles.block_q == (edge_cap or T)
    q, k, v, seg, cot = _inputs(dtype, [_row(n) for n in lens])
    want = _out_and_grads(segment_attention, q, k, v, seg, cot)
    got = _out_and_grads(
        lambda *a: F.flash_segment_attention(
            *a, window=window, interpret=True
        ),
        q, k, v, seg, cot,
    )
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        scale = max(1.0, float(np.abs(b).max()))
        assert np.abs(a - b).max() <= tol * scale, name
    # padding contributes exactly nothing, and receives nothing
    pad = np.asarray(seg) == 0
    assert not got[0][pad].any()
    assert not got[1][pad].any()


def test_a_row_shorter_than_a_tile_takes_the_xla_form():
    """By shape, with no kernel built: the CPU backend would refuse one."""
    t = 96
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, t, HQ, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, HKV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, HKV, D), jnp.bfloat16)
    seg = jnp.asarray(_row([40, 30], t)[None])
    got = F.flash_segment_attention(q, k, v, seg, window=64)
    want = segment_attention(q, k, v, seg)
    assert F._make_kernel.cache_info().currsize == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
