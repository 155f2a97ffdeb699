"""``ops/flash.py`` tells a LAYER's window from the PACKING window by kind,
not by size (PR 45): the kernel instances the cells of PR 44 compile keep
their tiles and their backward; a layer's window of 1024 or of 4096 keys
takes the band's path and a packing window of 1024 does not; and the two
kinds of window of one reach give the same numbers."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops import flash as F

# (T, keys back, a layer's band) -> (edge, fused backward) at PR 44, of every
# kernel instance the six cells of PR 44 can compile: their rows' rungs (2048
# x 1, 2, 4, 8 to the cell's ``max_tokens_per_mb``), the packing windows
# ``flash_window`` gives those budgets (1024 x 1, 2, 4 under a quarter of
# it, shorter than the row) and Laguna's 512-key band. Which cell compiles
# which: the dense cell rows to 4096 (14/2 heads of 64, window 1024); OLMoE
# 4096 (16/16 of 128; 1024, 2048); Kimi's latent core to 8192 (192 | 128;
# 1024, 2048); Laguna (48/8 full, 64/8 band) and Zaya (8/2) to 16384 (1024,
# 2048, 4096); Keye's sparse core is another kernel.
_AT_PR44 = {
    (2048, 0, False): (512, True),
    (2048, 511, True): (512, False),
    (2048, 1024, False): (512, True),
    (4096, 0, False): (1024, True),
    (4096, 511, True): (512, False),
    (4096, 1024, False): (512, True),
    (4096, 2048, False): (1024, True),
    (8192, 0, False): (1024, True),
    (8192, 511, True): (512, False),
    (8192, 1024, False): (512, True),
    (8192, 2048, False): (1024, True),
    (8192, 4096, False): (1024, True),
    (16384, 0, False): (2048, True),
    (16384, 511, True): (512, False),
    (16384, 1024, False): (512, True),
    (16384, 2048, False): (2048, True),
    (16384, 4096, False): (2048, True),
}


@pytest.fixture(autouse=True)
def probed_as_the_chip(monkeypatch):
    monkeypatch.setattr(F, "_PROBED_BLOCK", 2048)
    F._make_kernel.cache_clear()
    yield
    F._make_kernel.cache_clear()


@pytest.mark.parametrize(
    "t,back,band", sorted(_AT_PR44), ids=lambda x: str(x)
)
def test_every_kernel_instance_of_pr44_keeps_its_tiles(t, back, band):
    """Through the wrapper's own reading of its arguments: a band is a
    ``layer_window`` of ``back + 1`` keys, a packing window is ``window``."""
    edge, fused = _AT_PR44[t, back, band]
    got = F._local_mask(t, 0 if band else back, back + 1 if band else 0)
    assert got == (back, band)
    for d in (64, 128, 192):  # the head dimension moves nothing
        tiles = F._block_sizes(t, back, d, band)
        assert {tiles.block_q, tiles.block_kv, tiles.block_q_dkv,
                tiles.block_kv_dkv} == {edge}
        assert tiles.use_fused_bwd_kernel == fused
        assert tiles.block_kv_compute == tiles.block_kv_dkv_compute == 512


@pytest.mark.parametrize("t", [4096, 16384, 32768])
@pytest.mark.parametrize("layer_window", [512, 1024, 4096])
def test_a_layers_window_is_a_band_whatever_its_size(t, layer_window):
    back, band = F._local_mask(t, 0, layer_window)
    tiles = F._block_sizes(t, back, 128, band)
    if layer_window > t:  # the window reaches over the whole row: causal
        assert (back, band) == (0, False)
        assert tiles == F._block_sizes(t, 0, 128)
        return
    assert (back, band) == (layer_window - 1, True)
    assert tiles.block_q == F.BAND_EDGE == 512
    assert tiles.use_fused_bwd_kernel is False
    # and the packing window of the same reach takes the packing's tiles:
    # the fused backward to T = 16384, the shape's edge
    packing = F._block_sizes(t, layer_window, 128)
    assert packing.use_fused_bwd_kernel == (t <= 16384)
    assert packing.block_q == (
        512 if layer_window <= 1024 else 1024 if t <= 8192 else 2048
    )
    assert F._local_mask(t, layer_window, 0) == (
        (layer_window, False) if layer_window < t else (0, False)
    )


@pytest.mark.parametrize("window", [0, 1024, 8192])
def test_past_16384_tokens_the_backward_is_split(window):
    """The 32,768-token row: the fused backward's partial dq does not fit
    beside the state; the edge is the shape's."""
    tiles = F._block_sizes(32768, window, 128)
    assert tiles.use_fused_bwd_kernel is False
    assert tiles.block_q == (512 if window == 1024 else 2048)
    assert tiles.block_q_dq == tiles.block_kv_dq == tiles.block_q
    assert F.backward_form(32768, window, 128) == "split"
    assert F.backward_form(16384, window, 128) == "fused"
    assert F.backward_form(32768, window, 128, layer_window=1024) == "split"
    assert F.backward_form(200, 0, 128) == ""  # the XLA form's row


def test_the_shorter_of_the_two_windows_is_the_mask_and_says_its_kind():
    # a layer's 1024 keys under a packing window of 1024 or more: the band
    assert F._local_mask(32768, 1024, 1024) == (1023, True)
    assert F._local_mask(32768, 8192, 1024) == (1023, True)
    # a packing window shorter than the layer's: every sequence of the row
    # is shorter than the layer's window, the mask is the packing's
    assert F._local_mask(32768, 1024, 4096) == (1024, False)
    assert F._local_mask(32768, 0, 0) == (0, False)
    # a mask that reaches over the whole row is none: plain causal
    assert F._local_mask(512, 0, 1024) == (0, False)
    assert F._local_mask(2048, 2048, 0) == (0, False)


def test_the_kind_is_part_of_the_kernels_cache_key():
    a = F._make_kernel(512, 2, 127, 64, True, True)
    b = F._make_kernel(512, 2, 127, 64, True, False)
    assert a is not b and F._make_kernel.cache_info().currsize == 2
    assert a.kwargs["block_sizes"].use_fused_bwd_kernel is False
    assert b.kwargs["block_sizes"].use_fused_bwd_kernel is True
    assert a.dq_mask_info is not None and b.dq_mask_info is None


def test_band_and_packing_windows_of_one_reach_give_the_same_numbers(
    monkeypatch,
):
    """Interpret mode, T = 512 in tiles of 128: a layer's window of 129 keys
    (128 back, the split backward) and a packing window of 128 (the fused
    backward) are one mask; outputs equal to the bit, gradients to
    rounding."""
    monkeypatch.setattr(F, "_PROBED_BLOCK", 128)
    t, hq, hkv, d = 512, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, t, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, t, hkv, d), jnp.float32)
    cot = jax.random.normal(ks[3], (1, t, hq, d), jnp.float32)
    seg = np.zeros((1, t), np.int32)
    seg[0, :100], seg[0, 100:470] = 1, 2  # a border inside a window
    seg = jnp.asarray(seg)

    def run(**window):
        def loss(q, k, v):
            out = F.flash_segment_attention(
                q, k, v, seg, interpret=True, **window
            )
            return (out * cot).sum(), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        return out, grads

    band_out, band_grads = run(layer_window=129)
    pack_out, pack_grads = run(window=128)
    assert F._make_kernel.cache_info().currsize == 2  # one a kind
    np.testing.assert_array_equal(np.asarray(band_out), np.asarray(pack_out))
    for a, b in zip(band_grads, pack_grads):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )
