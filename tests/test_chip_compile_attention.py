"""The main path's attention kernels (paged decode, splash, a window layer's
band, the probe's first candidate, the latent core), compiled for a described
v5e: nothing runs (``chip_compile_fixture`` says what these check)."""

import pytest

from chip_compile_fixture import _sds, one_chip  # noqa: F401


# (head_dim, num_heads, layout, rows, chunk buffer length or 0) at the
# config's defaults: page 256, 4 pages per compute block, 8 slots per block
_PAGED_CASES = [
    pytest.param(64, 14, "token_packed", 1, 32, id="d64-packed-rows1"),
    pytest.param(64, 14, "token_packed", 64, 32, id="d64-packed-rows64"),
    pytest.param(64, 14, "head_merged", 1, 32, id="d64-merged-rows1"),
    pytest.param(64, 14, "head_merged", 64, 32, id="d64-merged-rows64"),
    pytest.param(64, 14, "head_merged", 16, 0, id="d64-merged-nochunk"),
    pytest.param(128, 12, "token_packed", 16, 32, id="d128-packed-rows16"),
]


@pytest.mark.parametrize("d,hq,layout,rows,chunk", _PAGED_CASES)
def test_paged_decode_kernel_compiles_for_v5e(
    one_chip, d, hq, layout, rows, chunk
):
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.ops import paged_attention as pa

    gen = JaxGenConfig()
    hkv, layers, num_pages, pages_per_seq = 2, 24, 64, 4
    pool = pa.packed_pool_shape(
        layers, hkv, num_pages, gen.page_size, d,
        head_merge=layout == "head_merged",
    )
    args = [
        _sds((rows, hq, d), jnp.bfloat16, one_chip),
        _sds(pool, jnp.bfloat16, one_chip),
        _sds(pool, jnp.bfloat16, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((rows, pages_per_seq), jnp.int32, one_chip),
    ]
    if chunk:
        args += [
            _sds((rows, hkv, chunk, d), jnp.bfloat16, one_chip),
            _sds((rows, hkv, chunk, d), jnp.bfloat16, one_chip),
            _sds((rows,), jnp.int32, one_chip),
        ]
    compiled = pa.paged_decode_attention.lower(
        *args,
        pages_per_compute_block=gen.pages_per_compute_block,
        slots_per_block=gen.slots_per_block,
        num_kv_heads=hkv,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (head_dim, q heads, T, the edge): the benchmark cell's packed row
# (Qwen2-0.5B, 4096 tokens), the recipe's 16384-token row, Qwen2-1.5B's heads
_SPLASH_CASES = [
    pytest.param(64, 14, 4096, 1024, id="d64-t4096-causal"),
    pytest.param(64, 14, 16384, 2048, id="d64-t16384-causal"),
    pytest.param(128, 12, 4096, 1024, id="d128-t4096-causal"),
    pytest.param(128, 12, 2048, 512, id="d128-t2048-causal"),
    # the shorter rung a train step's last microbatch runs at since PR 30,
    # at the two cells' heads (OLMoE: 16 of 128)
    # (edge 512 there and 1024 at 8192 since PR 42: the sweep under the
    # rows' tile mask)
    pytest.param(64, 14, 2048, 512, id="d64-t2048-causal"),
    pytest.param(128, 16, 2048, 512, id="d128-h16-t2048-causal"),
    pytest.param(128, 8, 8192, 1024, id="d128-h8-t8192-causal"),
    # PR 57: 16/2 heads of 256 at 16,384 tokens take 1024 (Qwen3-Next)
    pytest.param(256, 16, 16384, 1024, id="d256-h16-t16384-causal"),
]


@pytest.mark.parametrize("d,hq,t,edge", _SPLASH_CASES)
def test_splash_fwd_bwd_compiles_for_v5e(one_chip, d, hq, t, edge):
    """fwd+bwd of the trainer's attention with the tiles ``flash.instance``
    chooses on a chip whose scoped-VMEM limit was raised (the probe reads
    2048 there; ``jax.default_backend()`` is the CPU here, so the cap is
    handed in by the test as the engine hands in what it probed)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    flash._make_kernel.cache_clear()
    hkv = 2

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, block_cap=2048
        ).astype(jnp.float32).sum()

    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            .lower(
                _sds((1, t, hq, d), jnp.bfloat16, one_chip),
                _sds((1, t, hkv, d), jnp.bfloat16, one_chip),
                _sds((1, t, hkv, d), jnp.bfloat16, one_chip),
                _sds((1, t), jnp.int32, one_chip),
            )
            .compile()
        )
    finally:
        flash._make_kernel.cache_clear()
    assert flash.instance(t, d, block_cap=2048, heads=hq).edge == edge
    assert "tpu_custom_call" in compiled.as_text()


# (q heads, key heads, T, the layer's window): the two window families' bands
# as a TPU sends them (PR 47): through ``ops/band_attention.py``'s strips
_STRIP_CASES = [
    pytest.param(64, 8, 16384, 512, id="laguna-h64-w512-t16384"),
    pytest.param(64, 8, 2048, 512, id="laguna-h64-w512-t2048"),
    pytest.param(32, 4, 32768, 1024, id="mellum-h32-w1024-t32768"),
]


@pytest.mark.parametrize("hq,hkv,t,layer_window", _STRIP_CASES)
def test_the_bands_own_kernels_compile_for_v5e(
    one_chip, monkeypatch, hq, hkv, t, layer_window
):
    """On a TPU a layer's band of 128-wide heads takes its own forward and
    its one backward over key strips, no splash kernel: both compile at the
    cells' rows, and the backward holds no temporary but the relayouts of
    its operands (q, dO and dq: 0.27 GB each at Laguna's row)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert flash.backward_form(t, 128, layer_window, heads=hq) == "strip"
    bf = jnp.bfloat16

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, layer_window=layer_window
        ).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _sds((1, t, hq, 128), bf, one_chip),
        _sds((1, t, hkv, 128), bf, one_chip),
        _sds((1, t, hkv, 128), bf, one_chip),
        _sds((1, t), jnp.int32, one_chip),
    )
    text = lowered.as_text()
    assert "band_strip_fwd" in text and "band_strip_bwd" in text
    assert "splash_mqa" not in text
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_the_probes_first_candidate_compiles_for_v5e(one_chip):
    """What ``probe_block_size`` tries first: edge 2048, 4 heads of 128
    over one kv head, T = 2048. It needs the raised scoped-VMEM limit."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    t = 2048
    mask = flash._sm.MultiHeadMask([flash._sm.CausalMask((t, t))] * 4)
    kernel = flash._sk.make_splash_mqa_single_device(
        mask, block_sizes=flash._tiles(
            flash.Instance("splash", 0, 2048, True, 512)
        )
    )
    compiled = (
        jax.jit(jax.grad(
            lambda q, k: kernel(q, k, k).astype(jnp.float32).sum()
        ))
        .lower(
            _sds((4, t, 128), jnp.bfloat16, one_chip),
            _sds((t, 128), jnp.bfloat16, one_chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("t,tiles", [
    pytest.param(8192, (1024, True, 512), id="t8192"),
    # PR 57: the compute tile of 1024 that heads of 192 take at edge 2048
    pytest.param(16384, (2048, True, 1024), id="t16384"),
])
def test_latent_attention_core_compiles_for_v5e(one_chip, t, tiles):
    """The splash kernel at a query/key width of 192 over a value width of
    128, 32 heads each with its own keys, on the two latent cells' longest
    rows (Kimi 8,192, JoyAI 16,384), forward and backward: the width pair
    ``ops/flash.takes_head_dim`` admits, at ``instance``'s tiles."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    flash._make_kernel.cache_clear()
    bf = jnp.bfloat16

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, block_cap=2048
        ).astype(jnp.float32).sum()

    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            .lower(
                _sds((1, t, 32, 192), bf, one_chip),
                _sds((1, t, 32, 192), bf, one_chip),
                _sds((1, t, 32, 128), bf, one_chip),
                _sds((1, t), jnp.int32, one_chip),
            )
            .compile()
        )
    finally:
        flash._make_kernel.cache_clear()
    assert flash.takes_head_dim(192, 128)
    assert flash.instance(t, 192, block_cap=2048, heads=32)[2:] == tiles
    assert "tpu_custom_call" in compiled.as_text()
