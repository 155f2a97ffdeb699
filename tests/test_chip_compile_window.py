"""The window and full cores of the 16,384- and 32,768-token rows and the
selected-key attention, compiled for a described v5e: nothing runs
(``chip_compile_fixture`` says what these check)."""

import re

import pytest

from chip_compile_fixture import _sds, one_chip  # noqa: F401


# (q heads, T, the layer's window, edge, fused backward): Laguna-XS.2's two
# rotary kinds over 8 key heads of 128 at the cell's 16384-token row and the
# 8192-token rung: the window layers' band (its own tiles and the split
# backward, ``flash.BAND_EDGE``) and the full layers' causal core
_LAGUNA_CASES = [
    pytest.param(64, 8, 16384, 512, True, id="swa-h64-t16384"),
    pytest.param(64, 8, 8192, 512, True, id="swa-h64-t8192"),
    pytest.param(48, 8, 16384, 0, False, id="full-h48-t16384"),
    pytest.param(48, 8, 8192, 0, False, id="full-h48-t8192"),
    # Mellum2's 32 / 4 heads at its cell's 32768-token row: a 1024-key band
    # is a band by KIND (PR 45), and past 16384 tokens the full core takes
    # the split backward too
    pytest.param(32, 4, 32768, 1024, True, id="swa-h32-w1024-t32768"),
    pytest.param(32, 4, 32768, 0, False, id="full-h32-t32768"),
]


@pytest.mark.parametrize("hq,hkv,t,layer_window,band", _LAGUNA_CASES)
def test_window_and_full_cores_compile_for_v5e(
    one_chip, hq, hkv, t, layer_window, band
):
    """fwd+bwd of both attention cores of the two window families at the
    published heads: the band's kernel instance is its own (``LocalMask``
    ``layer_window - 1`` back, the split backward: no partial dq a kv
    block), the full core's is the shape's."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    flash._make_kernel.cache_clear()
    bf = jnp.bfloat16

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, block_cap=2048, layer_window=layer_window
        ).astype(jnp.float32).sum()

    try:
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            _sds((1, t, hq, 128), bf, one_chip),
            _sds((1, t, hkv, 128), bf, one_chip),
            _sds((1, t, hkv, 128), bf, one_chip),
            _sds((1, t), jnp.int32, one_chip),
        )
        compiled = lowered.compile()
    finally:
        flash._make_kernel.cache_clear()
    inst = flash.instance(t, 128, layer_window, 2048, heads=hq)
    full_edge = 2048 if t > 8192 else 1024
    assert inst.kind == "splash"  # the CPU's: a TPU sends a band to strips
    assert inst.back == (layer_window - 1 if band else 0)
    assert inst.edge == (flash.BAND_EDGE if band else full_edge)
    split = band or t > 16384
    assert inst.fused == (not split)
    text = lowered.as_text()
    assert ("splash_mqa_dq" in text) == split  # the split backward's own dq
    assert "tpu_custom_call" in compiled.as_text()
    # the fused backward's partial dq a kv block would be [T / edge, rep, T,
    # 128] bf16 a key head: 8.6 GB at the cell's row; the split one has none
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (3e9 if band else 6e9), temp


@pytest.mark.parametrize("t", [8192, 16384, 32768])
def test_sparse_attention_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch, t):
    """``ops/sparse_attention.sparse_attention`` forward and backward at
    Keye-VL-2.0's published widths (32 / 4 heads of 128, an indexer of 16
    heads of 64, topk 2048) on one row of ``t`` tokens: the index in XLA,
    the selection's counting kernel (``dsa_select_kth``, once for each query
    chunk that ends past 2048 keys: 4096, 6144, ... ``t`` keys wide, 64
    queries' keys in VMEM), then the two Pallas kernels of the core
    (``dsa_core_fwd``, ``dsa_core_bwd``) over int8 tiles of the selection,
    all the query heads of a kv head a grid step. The backward keeps a kv
    head's dK and dV (``bwd_resident_bytes``: 12 MiB at 8,192 tokens, 48 at
    32,768, the longest row whose int8 selection, 1 GB, a layer can hold)
    in VMEM under the limit its call states. The selection is in the program
    once (no transposed copy), and no [T, T] float32 array is.
    ``jax.default_backend()`` is the CPU here, so the platform's choice of
    the selection's form is set in the test."""
    import re

    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(sa, "select_impl", lambda shape=None: "kernel")
    bf = jnp.bfloat16
    chunks = t // 2048 - 1

    def loss(q, k, v, seg, qi, ki, w):
        out, stats = sa.sparse_attention(
            q, k, v, seg, qi, ki, w, topk=2048, core="kernel"
        )
        return out.astype(jnp.float32).sum(), stats

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
        .lower(
            _sds((1, t, 32, 128), bf, one_chip),
            _sds((1, t, 4, 128), bf, one_chip),
            _sds((1, t, 4, 128), bf, one_chip),
            _sds((1, t), jnp.int32, one_chip),
            _sds((1, t, 16, 64), bf, one_chip),
            _sds((1, t, 64), bf, one_chip),
            _sds((1, t, 16), bf, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= chunks + 2
    assert len(re.findall(r"dsa_select_kth", text)) >= chunks
    assert re.search(r"dsa_core_fwd", text) and re.search(r"dsa_core_bwd", text)
    assert not re.search(r"dsa_core_d(q|kv)", text)
    # the selection as the kernels read it: made once, never transposed
    made = [
        op for op in re.findall(rf"= s8\[{t},{t}\]\S* ([\w-]+)\(", text)
        if op not in ("get-tuple-element", "parameter", "bitcast")
    ]
    assert len(made) == 1, made
    assert not re.search(rf"f32\[[0-9,]*{t},{t}[0-9,]*\]", text)


# (tokens, heads, rotated half R, normed a head in the pass): q and k of
# Laguna's window and full layers (half a head under yarn), of Mellum's at its
# 32768-token row and of Keye's (both norm q and k a head), k of Zaya's
_ROTATION_CASES = [
    pytest.param(16384, 64, 64, False, id="laguna-swa-q"),
    pytest.param(16384, 48, 32, False, id="laguna-full-q-half-head"),
    pytest.param(16384, 8, 32, False, id="laguna-full-k-half-head"),
    pytest.param(32768, 32, 64, True, id="mellum-q-normed"),
    pytest.param(32768, 4, 64, True, id="mellum-k-normed"),
    pytest.param(16384, 32, 64, True, id="keye-q-normed"),
    pytest.param(16384, 2, 64, False, id="zaya-k"),
]


@pytest.mark.parametrize("t,heads,r,normed", _ROTATION_CASES)
def test_the_rotation_kernel_compiles_for_v5e(
    one_chip, monkeypatch, t, heads, r, normed
):
    """``ops/rotary.rotate`` and its transpose on the projection's
    ``[1, t, heads x 128]``: two custom calls, no copy of the operand on
    either side of them and nothing of its size in float32."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import rotary

    monkeypatch.setattr(rotary, "rotate_impl", lambda d, t=None: "kernel")

    def both(x, w, cos, sin, g):
        out, vjp = jax.vjp(
            lambda x, w: rotary.rotate(
                x.reshape(1, t, heads, 128), cos, sin,
                (w, 1e-6) if normed else None,
            ).reshape(x.shape), x, w,
        )
        return out, vjp(g)

    x = _sds((1, t, heads * 128), jnp.bfloat16, one_chip)
    table = _sds((1, t, r), jnp.float32, one_chip)
    w = _sds((128,), jnp.bfloat16, one_chip)
    compiled = jax.jit(both).lower(x, w, table, table, x).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 2
    assert ("rope_norm_rotate_t" if normed else "rope_rotate_t") in text
    assert not re.search(rf"= bf16\[[0-9,]*{heads * 128}\]\S* copy\(", text)
    assert not re.search(rf"f32\[[0-9,]*{t},{heads * 128}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# (row slots): the block-diffusion cell's rows (32 / 4 heads of 128), the
# ladder's top rung and a short last microbatch's
@pytest.mark.parametrize("t", [16384, 8192])
def test_the_block_diffusion_core_compiles_for_v5e(one_chip, t):
    """``ops/flash.flash_bd_attention`` forward and backward at the instance
    ``instance`` gives a row of ``t`` slots on a TPU: two kernels (forward,
    and the one backward with a key head's dK and dV resident in VMEM under
    the limit the call states), no ``[T, T]`` array and nothing float32 of
    the scores' size."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import bd_attention, flash

    hq, hkv, d = 32, 4, 128
    bq, bk = bd_attention.BQ, bd_attention.BK
    inst = flash.instance(t, d, interpret=True, heads=hq, block_diffusion=True)
    assert inst == flash.Instance("bd", 0, bq, True, bk)

    def both(q, k, v, seg, blk, stream, g):
        out, vjp = jax.vjp(
            lambda q, k, v: bd_attention.bd_attention(
                q, k, v, seg, blk, stream, (hq, hkv), bq, bk, inst.fused,
                False,
            ), q, k, v,
        )
        return out, vjp(g)

    q = _sds((1, t, hq * d), jnp.bfloat16, one_chip)
    k = _sds((1, t, hkv * d), jnp.bfloat16, one_chip)
    row = _sds((1, t), jnp.int32, one_chip)
    compiled = jax.jit(both).lower(q, k, k, row, row, row, q).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 2
    for name in ("bd_attn_fwd", "bd_attn_bwd"):
        assert name in text
    assert not re.search(rf"\[[0-9,]*{t},{t}\]", text)
    # q, k, v, out, do and their gradients in bf16, the keys' codes across
    # lanes: nothing of the scores' size
    assert compiled.memory_analysis().temp_size_in_bytes < 40 * t * hq * d
    assert t % bq == 0 and t % bk == 0
