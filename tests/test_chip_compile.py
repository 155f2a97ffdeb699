"""The main path's Pallas kernels, compiled for a v5e that is described and
not attached (on-chip-measurement guide, section 2).

Nothing runs: these say that the chip's compiler accepts the kernels at the
published widths of Qwen2-0.5B (Hq 14, Hkv 2, D 64) and Qwen2-1.5B (Hq 12,
Hkv 2, D 128), with the scoped-VMEM flag ``import areal_tpu`` puts into
``LIBTPU_INIT_ARGS``. Results and times come only from ``chip_smoke.py`` on
the chip.

The topology is described inside a module-scoped fixture — never while a
module is imported — and every compile happens in the test's own process,
with the persistent compile cache off (a described-chip entry can be written
but not read back without the chip).
"""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    # the flag must be in the environment before the TPU library loads
    import areal_tpu  # noqa: F401

    assert "xla_tpu_scoped_vmem_limit_kib" in os.environ["LIBTPU_INIT_ARGS"]
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


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


# (head_dim, q heads, T, window): the benchmark cell's packed row (Qwen2-0.5B,
# 4096 tokens, full causal and the 2048 window its minibatches bucket to),
# the recipe's 16384-token row, Qwen2-1.5B's heads
_SPLASH_CASES = [
    pytest.param(64, 14, 4096, 0, 1024, id="d64-t4096-causal"),
    pytest.param(64, 14, 4096, 2048, 1024, id="d64-t4096-window2048"),
    pytest.param(64, 14, 4096, 1024, 512, id="d64-t4096-window1024"),
    pytest.param(64, 14, 16384, 0, 2048, id="d64-t16384-causal"),
    pytest.param(128, 12, 4096, 0, 1024, id="d128-t4096-causal"),
    pytest.param(128, 12, 2048, 0, 512, id="d128-t2048-causal"),
    # the shorter rung a train step's last microbatch runs at since PR 30,
    # at the two cells' heads (OLMoE: 16 of 128)
    # (edge 512 there and 1024 at 8192 since PR 42: the sweep under the
    # rows' tile mask)
    pytest.param(64, 14, 2048, 0, 512, id="d64-t2048-causal"),
    pytest.param(128, 16, 2048, 0, 512, id="d128-h16-t2048-causal"),
    pytest.param(128, 8, 8192, 0, 1024, id="d128-h8-t8192-causal"),
]


@pytest.mark.parametrize("d,hq,t,window,edge", _SPLASH_CASES)
def test_splash_fwd_bwd_compiles_for_v5e(
    one_chip, monkeypatch, d, hq, t, window, edge
):
    """fwd+bwd of the trainer's attention with the tiles ``_block_sizes``
    chooses on a chip whose scoped-VMEM limit was raised (the probe reads
    2048 there; ``jax.default_backend()`` is the CPU here, so the cap is
    set in the test rather than probed)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    monkeypatch.setattr(flash, "_PROBED_BLOCK", 2048)
    flash._make_kernel.cache_clear()
    hkv = 2

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, window=window
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
        # the kernel objects cached under the steered cap must not leak
        # into other tests of this worker
        flash._make_kernel.cache_clear()
    assert flash._block_sizes(t, window, d).block_q == edge
    assert "tpu_custom_call" in compiled.as_text()


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
    one_chip, monkeypatch, hq, hkv, t, layer_window, band
):
    """fwd+bwd of both attention cores of the two window families at the
    published heads: the band's kernel instance is its own (``LocalMask``
    ``layer_window - 1`` back, the split backward: no partial dq a kv
    block), the full core's is the shape's."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    monkeypatch.setattr(flash, "_PROBED_BLOCK", 2048)
    flash._make_kernel.cache_clear()
    bf = jnp.bfloat16

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(
            q, k, v, seg, layer_window=layer_window
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
    tiles = flash._block_sizes(
        t, layer_window - 1 if band else 0, 128, band
    )
    full_edge = 2048 if t > 8192 else 1024
    assert tiles.block_q == (flash.BAND_EDGE if band else full_edge)
    split = band or t > 16384
    assert tiles.use_fused_bwd_kernel == (not split)
    text = lowered.as_text()
    assert ("splash_mqa_dq" in text) == split  # the split backward's own dq
    assert "tpu_custom_call" in compiled.as_text()
    # the fused backward's partial dq a kv block would be [T / edge, rep, T,
    # 128] bf16 a key head: 8.6 GB at the cell's row; the split one has none
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (3e9 if band else 6e9), temp


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
    assert flash.backward_form(t, 0, 128, layer_window) == "strip"
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
        mask, block_sizes=flash._tiles(2048)
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


@pytest.mark.parametrize("t", [4096, 2048])
def test_sparse_layer_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch, t):
    """``ops/moe.moe_ffn`` forward and backward at OLMoE-1B-7B's published
    widths on one 4096-token row (the four-chip cell's row a chip; 2048 is
    the rung a train step's last microbatch runs at) through
    the megablox grouped matmul with ``_GMM_TILING``: three ``gmm`` forward,
    and three ``gmm`` + three ``tgmm`` backward. ``jax.default_backend()``
    is the CPU here, so the platform's choice is set in the test."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import moe

    monkeypatch.setattr(moe, "grouped_impl", lambda shape=None: "megablox")
    d, f, e, k = 2048, 1024, 64, 8
    assert (t * k) % moe._GMM_TILE_M == 0

    def loss(x, wr, wg, wu, wd, valid):
        out, stats = moe.moe_ffn(
            x, wr, wg, wu, wd, num_experts_per_tok=k, norm_topk_prob=False,
            valid=valid,
        )
        return out.astype(jnp.float32).sum() + stats.aux_loss

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        .lower(
            _sds((1, t, d), jnp.bfloat16, one_chip),
            _sds((d, e), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, f, d), jnp.bfloat16, one_chip),
            _sds((1, t), jnp.bool_, one_chip),
        )
        .compile()
    )
    assert compiled.as_text().count("tpu_custom_call") >= 9


# (model width, expert width, experts routed over, experts held, a token):
# one 4,096-token chunk of three of the cells whose layer holds a share
_HELD_CASES = [
    pytest.param(2048, 768, 128, 16, 8, id="keye-16-of-128"),
    pytest.param(2304, 1024, 256, 8, 8, id="kimi-8-of-256"),
    pytest.param(2304, 896, 64, 8, 8, id="mellum-8-of-64"),
]


@pytest.mark.parametrize("d,f,n_routed,e,k", _HELD_CASES)
def test_held_share_layer_fwd_bwd_compiles_for_v5e(
    one_chip, monkeypatch, d, f, n_routed, e, k
):
    """``ops/moe.moe_ffn`` of a layer that HOLDS A SHARE, forward and
    backward at one token chunk of the long-row, the Kimi and the Mellum
    cell: the expert path runs over slabs of twice the even share (8,192,
    2,048 and 8,192 rows of the 32,768-row stream): a loop over the slabs
    forward, the first slab in line and a loop over the others backward. No
    array of the whole stream's rows by the model's width is left, the
    expert kernels keep the names ``moe_experts_roofline.train`` matches,
    and their number is held (each is lowered in Python at every
    occurrence), not only bounded below. A slab's rows go back onto their
    tokens by the banded product (``moe.onto_tokens_banded``: the same
    megablox kernel under a name of its own, which that metric does not
    match): once in the forward loop's body (the combine) and once in the
    backward's first slab and in its loop's body (the dispatch's transpose;
    the combine the backward traces again has no reader and is gone), and
    no [tokens, slab] matrix is left."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import moe
    from benchmark import program_trace

    monkeypatch.setattr(moe, "grouped_impl", lambda shape=None: "megablox")
    monkeypatch.setattr(moe, "onto_impl", lambda shape=None: "banded")
    t = 4096
    c = moe._held_rows(t * k, e, n_routed)
    assert c == 2 * t * k * e // n_routed

    def loss(x, wr, wg, wu, wd, valid):
        out, stats = moe.moe_ffn(
            x, wr, wg, wu, wd, num_experts_per_tok=k, norm_topk_prob=True,
            valid=valid,
        )
        # not linear in ``out``: the backward reads the forward's result,
        # so the forward loop stays in the program
        return (out.astype(jnp.float32) ** 2).sum(), stats

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        .lower(
            _sds((1, t, d), jnp.bfloat16, one_chip),
            _sds((d, n_routed), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, f, d), jnp.bfloat16, one_chip),
            _sds((1, t), jnp.bool_, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    # the forward loop's body (3 gmm, the combine's banded product), the
    # backward's first slab and its loop's body (each 3 gmm again, 3 gmm
    # transposed, 3 tgmm, the dispatch's transpose as a banded product)
    kernels = re.findall(
        r"%(t?gmm|onto_tokens_banded)[.\d]* = \S+ custom-call\(", text
    )
    assert sorted(kernels) == (
        ["gmm"] * 15 + ["onto_tokens_banded"] * 3 + ["tgmm"] * 6
    )
    assert text.count("tpu_custom_call") == 24
    assert f"[{c},{d}]" in text and f"[{t * k},{d}]" not in text
    # the token side: a [token tile, slab] matrix of weights, never the
    # [tokens, slab] one
    tile = moe._ONTO_TILE
    assert f"[{tile},{c}]" in text or f"[{c},{tile}]" in text
    assert f"[{t},{c}]" not in text and f"[{c},{t}]" not in text
    # both transposes are hand-written: the only scatters are megablox's
    # own, into its [groups + tiles] metadata
    scattered = re.findall(r"= \w+\[([0-9,]*)\]\S* scatter\(", text)
    assert scattered and all("," not in dims for dims in scattered), scattered
    # the loops' bodies under the layer's scopes, as the first slab is
    looped = {
        n for n in re.findall(r'op_name="([^"]+)"', text) if "/while/" in n
    }
    assert {program_trace.scope_of(n) for n in looped} == {
        "moe_dispatch", "moe_experts"
    }


def test_sparse_attention_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch):
    """``ops/sparse_attention.sparse_attention`` forward and backward at
    Keye-VL-2.0's published widths (32 / 4 heads of 128, an indexer of 16
    heads of 64, topk 2048) on one 8192-token row: the index in XLA, the
    selection's counting kernel (``dsa_select_kth``, once for each of the
    three query chunks that end past 2048 keys: 4096, 6144 and 8192 keys
    wide, 64 queries' keys in VMEM), then the three Pallas kernels of the
    core (``dsa_core_fwd``, ``dsa_core_dq``, ``dsa_core_dkv``) over int8
    tiles of the selection, all the query heads of a kv head a grid step.
    No [T, T] float32 array in the compiled program.
    ``jax.default_backend()`` is the CPU here, so the platform's choice of
    the selection's form is set in the test."""
    import re

    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(sa, "select_impl", lambda shape=None: "kernel")
    t, bf = 8192, jnp.bfloat16

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
    assert text.count("tpu_custom_call") >= 6
    assert len(re.findall(r"dsa_select_kth", text)) >= 3
    assert not re.search(rf"f32\[[0-9,]*{t},{t}[0-9,]*\]", text)


@pytest.mark.parametrize("t", [2048, 8192])
def test_kda_scan_fwd_bwd_compiles_for_v5e(one_chip, t):
    """``ops/kda.kda_scan`` forward and backward at Kimi-Linear's published
    widths (32 heads of 128 | 128) on one packed row: the chunk operands and
    their cotangents as ``chunk_ops_fwd`` / ``chunk_ops_bwd`` (8 chunks a grid
    step taken together, every exponent difference in VMEM, the loops over a
    sub-block's tokens and the substitution's rows rolled: dynamic sublane
    reads, which interpret mode lets through and only this compiler judges),
    then the three state-pass kernels (``kda_fwd_h`` in the forward;
    ``kda_fwd_h_states`` and ``kda_bwd_dh`` in the backward, which recomputes
    the chunk states), 8 chunks a grid step, the state in VMEM.
    ``jax.default_backend()`` is the CPU here, so the platform's choice is
    named in the call."""
    import re

    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kda

    bf, f32 = jnp.bfloat16, jnp.float32

    def loss(q, k, v, g, beta, seg):
        out, stats = kda.kda_scan(q, k, v, g, beta, seg, impl="kernel")
        return out.astype(f32).sum(), stats

    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        .lower(
            *[_sds((1, t, 32, 128), bf, one_chip)] * 3,
            _sds((1, t, 32, 128), f32, one_chip),
            _sds((1, t, 32), f32, one_chip),
            _sds((1, t), jnp.int32, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh",
                 "chunk_ops_fwd", "chunk_ops_bwd"):
        assert re.search(rf"{name}[^_a-z]", text), name
    # the operands' pairwise terms never leave VMEM: no [.., 16, 16, 128]
    assert not re.search(r"f32\[[0-9,]*16,16,128\]", text)
    # no chunk-start states in float32, and none kept beside the inputs
    assert not re.search(rf"f32\[{t // 64},32,128,128\]", text)


def test_kimi_grad_step_lowers_each_operand_kernel_a_bounded_number_of_times(
    one_chip, monkeypatch
):
    """A Pallas kernel is lowered to its Mosaic module in Python at every
    place its call occurs in the lowered text, before the compile cache is
    asked, so a warm start pays for every occurrence (PERF.md, PR 34: nine
    operand kernels a ``grad_step`` cost the cell 95 s of ``setup_s``). The
    kernels sit behind jitted functions, which a module lowers once for
    equal arguments: the tiny ``kimi_linear`` stack laid out as the cell's
    (a leading layer, a scanned run of two, latent attention, a last layer;
    checkpointed) holds ``chunk_ops_fwd`` at most three times (the runs of
    one layer, the scan's body, the backward's recomputation) and
    ``chunk_ops_bwd`` once, however many layers there are. Counted in the
    lowered text (``tools/setup_cost.py``), nothing compiled, nothing timed."""
    import dataclasses

    import jax

    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops import kda
    from areal_tpu.parallel import mesh as mesh_lib
    from tools import setup_cost

    monkeypatch.setattr(kda, "scan_impl", lambda *a: "kernel")
    mc = dataclasses.replace(
        tiny_config("kimi_linear"), num_layers=5,
        layer_kinds=("kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"),
    )
    mesh = mesh_lib.make_mesh(ParallelismConfig(), list(one_chip.device_set))
    grad_step, args = setup_cost.trainer_programs(mc, mesh, 256, None)[
        "grad_step"
    ]
    calls = setup_cost.custom_calls(grad_step.trace(*args).lower().as_text())
    assert 1 <= calls["chunk_ops_fwd"] <= 3, calls
    assert calls["chunk_ops_bwd"] == 1, calls
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh"):
        assert 1 <= calls[name] <= 2, calls


def test_latent_attention_core_compiles_for_v5e(one_chip, monkeypatch):
    """The splash kernel at a query/key width of 192 over a value width of
    128, 32 heads each with its own keys, on an 8192-token row, forward and
    backward: the width pair ``ops/flash.takes_head_dim`` admits."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import flash

    monkeypatch.setattr(flash, "_PROBED_BLOCK", 2048)
    flash._make_kernel.cache_clear()
    t, bf = 8192, jnp.bfloat16

    def loss(q, k, v, seg):
        return flash.flash_segment_attention(q, k, v, seg).astype(
            jnp.float32
        ).sum()

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
    assert "tpu_custom_call" in compiled.as_text()


def test_cca_mixer_and_its_core_compile_for_v5e(one_chip, monkeypatch):
    """ZAYA1-8B's mixer at its published widths on a 16,384-token row,
    forward and backward: what ``cca_qkv`` computes between the projections
    and the core (8 query and 2 key heads of 128, two taps a convolution),
    then the splash kernel at 4 query heads a key head. The second
    convolution is one batched ``einsum`` over the ten heads a tap, which
    this compiler makes an MXU product ([10, 128, 128] bf16 against the
    row, float32 out): no ``lax.conv_general_dilated`` is needed for it."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from areal_tpu.ops import flash
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    monkeypatch.setattr(flash, "_PROBED_BLOCK", 2048)
    flash._make_kernel.cache_clear()
    mc = MODEL_CONFIGS["zaya1_8b"]()
    t, bf = 16384, jnp.bfloat16
    leaves = transformer._kind_leaves(mc, "cca+moe")
    lp = {
        k: _sds(leaves[k][0], bf, one_chip)
        for k in ("wq", "wk", "wv_own", "wv_prev", "cca_conv0",
                  "cca_conv0_bias", "cca_conv1", "cca_conv1_bias", "cca_temp")
    }

    def loss(h, lp, seg, pos):
        q, k, v = transformer.cca_qkv(
            mc, h, lp, seg, *transformer._rotations(mc, pos)["cca"]
        )
        assert (q.shape, k.shape, v.shape) == (
            (1, t, 8, 128), (1, t, 2, 128), (1, t, 2, 128)
        )
        return flash.flash_segment_attention(q, k, v, seg).astype(
            jnp.float32
        ).sum()

    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1)))
            .lower(
                _sds((1, t, 2048), bf, one_chip), lp,
                _sds((1, t), jnp.int32, one_chip),
                _sds((1, t), jnp.int32, one_chip),
            )
            .compile()
        )
    finally:
        flash._make_kernel.cache_clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"f32\[10,128,16384\]\S* convolution\(", text)
    assert re.search(r'areal_scope="cca_mix"', text)
