"""``ops/flash.instance`` is the one answer to "which attention kernel instance
does this row run?" (PR 56): the kernel instances the NINE cells compile, a
case a (cell, row-length rung, core), with ``backward_form`` and
``tile_counts`` held to it; a layer's window is a band whatever its size; the
cap on the tiles is a value each caller hands in; and the premise the packing
window went on (ROADMAP C.31): the rows' run-time tile mask keeps no tile
that window dropped."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops import flash as F
from areal_tpu.ops.ring_attention import attention_fn
from areal_tpu.parallel import feasibility
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.api.cli_args import ParallelismConfig
from areal_tpu.models import transformer as tr

# cell -> (its ``feasibility.MODEL_CONFIGS`` entry, its longest row:
# ``mb_spec.max_tokens_per_mb`` of its traffic file). Its rows' rungs are
# ``data.ROW_QUANTUM`` x 1, 2, 4, ... to that.
_CELLS = {
    "train_0p5b_grpo": ("qwen2_0p5b", 4096),
    "train_olmoe_grpo_fsdp4": ("olmoe_1b_7b", 4096),
    "train_keye_dsa_grpo_long": ("keye_vl_2p0_30b_a3b", 16384),
    "train_kimi_kda_grpo_packed8k": ("kimi_linear_48b_a3b", 8192),
    "train_laguna_swa_grpo_mixed16k": ("laguna_xsp2", 16384),
    "train_zaya_cca_grpo_packed16k": ("zaya1_8b", 16384),
    "train_mellum_swa_grpo_long32k": ("mellum2_12b_a2p5b", 32768),
    "train_qwen3next_gdn_grpo_long16k": ("qwen3_next_80b_a3b", 16384),
    "train_joyai_mla_mtp_grpo_long16k": ("joyai_llm_flash", 16384),
}
# what a full-attention core runs at each rung, whatever its heads (14/2 of
# 64, 16/16 and 8/2 and 48/8 and 32/4 of 128, 16/2 of 256, 32/32 of 192 | 128)
_FULL = {
    2048: ("splash", 0, 512, True),
    4096: ("splash", 0, 1024, True),
    8192: ("splash", 0, 1024, True),
    16384: ("splash", 0, 2048, True),
    32768: ("splash", 0, 2048, False),
}


def _rungs(longest):
    return [t for t in _FULL if t <= longest]


def _cell_cases():
    for cell, (model, longest) in _CELLS.items():
        for t in _rungs(longest):
            yield pytest.param(model, t, "full", id=f"{cell}-{t}-full")
            if model in ("laguna_xsp2", "mellum2_12b_a2p5b"):
                yield pytest.param(model, t, "band", id=f"{cell}-{t}-band")


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("model,t,core", list(_cell_cases()))
def test_every_kernel_instance_of_the_nine_cells(as_tpu, model, t, core):
    """On a TPU under the v5e's cap of 2048, from the cell's own model
    config: the instance, and ``backward_form``, ``tile_counts`` and the
    grad dispatch span's attributes agree with it."""
    mc = feasibility.MODEL_CONFIGS[model]()
    d = mc.attn_head_dims[0]
    if mc.has_indexer:
        # Keye's learned selection is another kernel: no instance is asked
        forced, _ = tr.seq_parallel_refusal(mc)
        assert forced == "sparse_topk"
        assert F.resolve_attn_impl("auto", 1, mc.attn_head_dims, forced) == (
            forced
        )
        return
    assert F.resolve_attn_impl("auto", 1, mc.attn_head_dims) == "flash"
    if core == "band":
        window = mc.swa.window
        # the band's own kernels: heads of 128, a row of whole strips
        want = ("strip", window - 1, 256, False)
        assert F.instance(t, d, window, 2048) == want
        assert F.backward_form(t, d, window) == "strip"
        # where they do not take it (the CPU, heads of 64), the splash
        # kernel's band: ``BAND_EDGE`` and the split backward
        assert F.instance(t, 64, window, 2048) == (
            "splash", window - 1, F.BAND_EDGE, False
        )
        assert F.backward_form(t, 64, window) == "split"
        return
    inst = F.instance(t, d, 0, 2048)
    assert inst == _FULL[t] and inst == F.instance(t, d)  # the cap caps none
    assert F.backward_form(t, d) == ("fused" if inst.fused else "split")
    seg = np.ones((1, t), np.int32)  # one sequence: every tile is kept
    n = t // inst.edge
    assert F.tile_counts(seg, d, 2048) == (n * (n + 1) // 2,) * 2
    said = tr.grad_span_attrs(mc, "flash", t)
    if mc.swa is None:
        assert "attn_bwd_full" not in said
    else:
        assert said["attn_bwd_full"] == F.backward_form(t, d)
        assert said["attn_bwd_window"] == "strip"


@pytest.mark.parametrize("t", [4096, 16384, 32768])
@pytest.mark.parametrize("layer_window", [512, 1024, 4096])
def test_a_layers_window_is_a_band_whatever_its_size(t, layer_window):
    inst = F.instance(t, 128, layer_window, 2048)
    if layer_window > t:  # the window reaches over the whole row: causal
        assert inst == F.instance(t, 128, 0, 2048) and inst.back == 0
        return
    assert inst == ("splash", layer_window - 1, F.BAND_EDGE, False)
    assert F.BAND_EDGE == 512
    tiles = F._tiles(inst.edge, inst.fused)
    assert tiles.block_q_dq == tiles.block_kv_dq == tiles.block_q == 512
    assert tiles.use_fused_bwd_kernel is False


def test_past_16384_tokens_the_backward_is_split():
    """The 32,768-token row: the fused backward's partial dq does not fit
    beside the state; the edge is the shape's."""
    inst = F.instance(32768, 128, 0, 2048)
    tiles = F._tiles(inst.edge, inst.fused)
    assert tiles.use_fused_bwd_kernel is False
    assert tiles.block_q_dq == tiles.block_kv_dq == tiles.block_q == 2048
    assert F.backward_form(32768, 128) == "split"
    assert F.backward_form(16384, 128) == "fused"
    assert F.backward_form(32768, 128, layer_window=1024) == "split"
    assert F.backward_form(200, 128) == ""  # the XLA form's row
    assert F.instance(200, 128, 1024, 2048) == ("", 0, 0, False)
    assert F.tile_counts(np.ones((2, 200), np.int32), 128) == (0, 0)


def test_a_mask_that_reaches_over_the_whole_row_is_none():
    assert F.instance(512, 64, 1024).back == 0
    assert F.instance(1024, 64, 1024).back == 1023
    assert F.instance(1024, 64, 1025).back == 0
    assert F.instance(32768, 64, 0).back == 0


def test_the_instance_is_the_kernels_cache_key():
    """One kernel an instance, whatever the heads' width; a band's kernel
    has the split backward's third mask."""
    F._make_kernel.cache_clear()
    band = F._make_kernel(512, 2, F.instance(512, 64, 128, 128), True)
    full = F._make_kernel(512, 2, F.instance(512, 64, 0, 128), True)
    assert full is F._make_kernel(512, 2, F.instance(512, 192, 0, 128), True)
    assert band is not full and F._make_kernel.cache_info().currsize == 2
    assert band.kwargs["block_sizes"].use_fused_bwd_kernel is False
    assert full.kwargs["block_sizes"].use_fused_bwd_kernel is True
    assert band.dq_mask_info is not None and full.dq_mask_info is None
    F._make_kernel.cache_clear()


# --- the cap is a value ---------------------------------------------------


def _traced_edges(attend, t=4096, d=64):
    """The tile edges of the kernels ``attend`` builds for a row of ``t``."""
    F._make_kernel.cache_clear()
    q = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, t), jnp.int32)
    seen = []
    make = F._sk.make_splash_mqa_single_device

    def spy(mask, block_sizes=None, **kw):
        seen.append(block_sizes.block_q)
        return make(mask, block_sizes=block_sizes, **kw)

    F._sk.make_splash_mqa_single_device = spy
    try:
        jax.eval_shape(lambda *a: attend(*a), q, q, q, seg)  # a fresh trace
    finally:
        F._sk.make_splash_mqa_single_device = make
        F._make_kernel.cache_clear()
    return seen


def test_two_attention_fns_with_two_caps_get_their_own_tiles():
    """Caps of 512 and 2048 in ONE process (at the parent the second engine
    to probe set the first one's tiles): each ``attention_fn`` carries its
    own, and a direct caller that hands none in is uncapped."""
    mesh = mesh_lib.make_mesh(ParallelismConfig(), jax.devices()[:1])
    small = attention_fn("flash", mesh, block_cap=512)
    large = attention_fn("flash", mesh, block_cap=2048)
    assert _traced_edges(small) == [512]
    assert _traced_edges(large) == [1024]
    assert _traced_edges(small) == [512]  # and the first is as it was
    assert _traced_edges(F.flash_segment_attention, t=16384) == [2048]
    assert _traced_edges(small, t=16384) == [512]
    assert F.tile_counts(np.ones((1, 4096), np.int32), 64, 512) == (36, 36)
    assert F.tile_counts(np.ones((1, 4096), np.int32), 64, 2048) == (10, 10)


# --- the premise: the tile mask keeps nothing the packing window dropped --


def _old_packing_window(longest: int, tokens_per_mb: int) -> int:
    """The packing window as ``ops/flash.py`` gave it until PR 55 (the
    reference the premise is stated against): the batch's longest
    sequence in power-of-two buckets from 1024, 0 (full causal) above a
    quarter of a whole microbatch."""
    w = 1024
    while w < longest:
        w *= 2
    return w if 4 * w <= tokens_per_mb else 0


def _tiles_the_window_kept(n: int, edge: int, window: int) -> np.ndarray:
    """The tiles the static mask of that window kept (as PR 55 counted
    them): on and under the diagonal, their nearest pair
    within ``window`` keys."""
    back = np.arange(n)[:, None] - np.arange(n)[None, :]
    near = (back - 1) * edge + 1  # a block's first query to the last key
    return (back >= 0) & ((near <= window) | (window <= 0))


def _packed_row(rng, t, draw):
    """A row of ``t`` tokens as ``pack_batch_rows`` lays it: sequences drawn
    by ``draw`` until the next does not fit, ids rising, padding (0) after."""
    seg = np.zeros(t, np.int32)
    at, i = 0, 0
    while True:
        n = int(draw(rng))
        if at + n > t:
            break
        i += 1
        seg[at:at + n] = i
        at += n
    return seg


def _lognormal(median, sigma, lo, hi):
    return lambda rng: np.clip(
        rng.lognormal(np.log(median), sigma), lo, hi
    )


# (row length, the cell's ``max_tokens_per_mb``, its sequences' law, seed):
# as the cells draw them (prompt + output, ``benchmark/traffic``), and short
# sequences in a 16,384-token row, where the window was on
_ROWS = [
    pytest.param(4096, 4096, _lognormal(900, 0.9, 160, 4096), 0,
                 id="dense-4096"),
    pytest.param(4096, 4096, _lognormal(500, 0.5, 100, 1024), 1,
                 id="dense-4096-window1024"),
    pytest.param(8192, 8192, _lognormal(3100, 0.7, 512, 8192), 2,
                 id="kimi-8192"),
    pytest.param(16384, 16384, _lognormal(3100, 0.8, 512, 8192), 3,
                 id="zaya-16384"),
    pytest.param(16384, 16384, _lognormal(2500, 0.5, 512, 4096), 4,
                 id="zaya-16384-window4096"),
    pytest.param(16384, 16384, _lognormal(700, 0.5, 160, 2048), 5,
                 id="short-sequences-in-16384-window2048"),
    pytest.param(16384, 16384, _lognormal(400, 0.3, 100, 1024), 6,
                 id="shortest-sequences-in-16384-window1024"),
    pytest.param(32768, 32768, _lognormal(5000, 0.5, 1024, 8192), 7,
                 id="mellum-32768-window8192"),
]


@pytest.mark.parametrize("t,tokens_per_mb,draw,seed", _ROWS)
def test_the_tile_mask_keeps_no_tile_the_packing_window_dropped(
    t, tokens_per_mb, draw, seed
):
    """Six rows a case. Every tile off the diagonal that ``tiles_needed``
    keeps holds a pair no farther apart than the row's longest sequence, so
    it lay inside the window the old rule gave; and at the edge the
    parent ran those rows at, ``attn_tiles_kept`` is what the parent
    counted (its static window & the rows' needs)."""
    rng = np.random.default_rng(seed)
    rows = np.stack([_packed_row(rng, t, draw) for _ in range(6)])
    longest = max(np.bincount(r[r > 0]).max() for r in rows)
    window = _old_packing_window(int(longest), tokens_per_mb)
    window = window if window < t else 0  # as the parent's ``_program`` did
    d = 128
    for edge in {F.instance(t, d, 0, 2048).edge, 512}:
        n = t // edge
        needed = F.tiles_needed(rows, edge, np)
        in_window = _tiles_the_window_kept(n, edge, window)
        assert not (needed & ~in_window).any()
        # and by the pairs themselves: a kept tile (i, j), i > j, holds a
        # query and a key of one sequence, so its nearest pair is no farther
        # apart than that sequence is long
        for r, row in enumerate(rows):
            lens = np.bincount(row)
            for i, j in zip(*np.nonzero(np.tril(needed[r], -1))):
                shared = np.intersect1d(
                    row[i * edge:(i + 1) * edge], row[j * edge:(j + 1) * edge]
                )
                shared = shared[shared > 0]
                assert len(shared) == 1, (r, i, j)  # ids rise along a row
                nearest = i * edge - ((j + 1) * edge - 1)
                assert nearest <= lens[shared[0]] - 1 <= longest - 1
    # what the engine counts now against what the parent counted: the
    # parent's edge under its window (512 under a window of 1024), the same
    # kept tiles at that edge
    parent_edge = 512 if 0 < window <= 1024 else F.instance(t, d, 0, 2048).edge
    kept_parent = (
        F.tiles_needed(rows, parent_edge, np)
        & _tiles_the_window_kept(t // parent_edge, parent_edge, window)
    ).sum()
    assert kept_parent == F.tiles_needed(rows, parent_edge, np).sum()
    if parent_edge == F.instance(t, d, 0, 2048).edge:
        assert F.tile_counts(rows, d, 2048)[1] == kept_parent
