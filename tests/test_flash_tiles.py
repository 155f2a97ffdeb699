"""``ops/flash.instance`` is the one answer to "which attention kernel instance
does this row run?" (PR 56): the kernel instances the NINE cells compile, a
case a (cell, row-length rung, core), with ``backward_form`` and
``tile_counts`` held to it; since PR 57 the full core's instance reads the
heads' width and number (heads of 64 and 128 keep the parent's, the fused
backward goes where its partial dq fits); a layer's window is a band whatever
its size; the cap on the tiles is a value each caller hands in; and the
premise the packing window went on (ROADMAP C.31): the rows' run-time tile
mask keeps no tile that window dropped."""

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
# (rung, head width) -> what a full-attention core of the cells' heads runs
# (14/2 of 64, 16/16 and 8/2 and 48/8 and 32/4 of 128, 16/2 of 256, 32/32 of
# 192 | 128); width 0: every width the rung does not name
_FULL = {
    (2048, 0): ("splash", 0, 512, True, 512),
    (4096, 0): ("splash", 0, 1024, True, 512),
    (8192, 0): ("splash", 0, 1024, True, 512),
    (16384, 0): ("splash", 0, 2048, True, 512),
    # PR 57: heads wider than 128 take 1024 at every rung where the fused
    # backward's partial dq fits (Qwen3-Next at 16,384: 2 GiB); JoyAI's 32
    # heads at 16,384 would write 3 GiB at 1024, so 2048, and the compute
    # tile of 1024 that heads of 192 want there
    (2048, 192): ("splash", 0, 1024, True, 512),
    (2048, 256): ("splash", 0, 1024, True, 512),
    (16384, 192): ("splash", 0, 2048, True, 1024),
    (16384, 256): ("splash", 0, 1024, True, 512),
    (32768, 0): ("splash", 0, 2048, False, 512),
}
_RUNGS = (2048, 4096, 8192, 16384, 32768)
_GIB = 1 << 30


def _full(t, d):
    return _FULL.get((t, d), _FULL[t, 0])


def _rungs(longest):
    return [t for t in _RUNGS if t <= longest]


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
    d, heads = mc.attn_head_dims[0], dict(heads=mc.num_heads)
    if mc.has_indexer:
        # Keye's learned selection is another kernel: no instance is asked
        forced, _ = tr.seq_parallel_refusal(mc)
        assert forced == "sparse_topk"
        assert F.resolve_attn_impl("auto", 1, mc.attn_head_dims, forced) == (
            forced
        )
        return
    assert F.resolve_attn_impl("auto", 1, mc.attn_head_dims) == "flash"
    said = tr.grad_span_attrs(mc, "flash", t, 2048)
    if core == "band":
        window, band_heads = mc.swa.window, dict(heads=mc.swa.num_heads)
        # the band's own kernels: heads of 128, a row of whole strips
        want = ("strip", window - 1, 256, False, 0)
        assert F.instance(t, d, window, 2048, **band_heads) == want
        assert F.backward_form(t, d, window, **band_heads) == "strip"
        assert said["attn_bwd_window"] == "strip"
        # where they do not take it (the CPU, heads of 64), the splash
        # kernel's band: ``BAND_EDGE`` and the split backward
        assert F.instance(t, 64, window, 2048, **band_heads) == (
            "splash", window - 1, F.BAND_EDGE, False, 512
        )
        assert F.backward_form(t, 64, window, **band_heads) == "split"
        return
    inst = F.instance(t, d, 0, 2048, **heads)
    assert inst == _full(t, d)
    assert inst == F.instance(t, d, **heads)  # the cap caps none
    form = "fused" if inst.fused else "split"
    assert F.backward_form(t, d, **heads) == form
    seg = np.ones((1, t), np.int32)  # one sequence: every tile is kept
    n = t // inst.edge
    assert F.tile_counts(seg, d, 2048, **heads) == (n * (n + 1) // 2,) * 2
    # every model that runs the splash core says the full core's instance
    assert (said["attn_edge"], said["attn_bwd_full"]) == (inst.edge, form)
    assert ("attn_bwd_window" in said) == (mc.swa is not None)


def _parent(t):
    """The full core's instance until PR 56, whatever its heads."""
    edge = 512 if t <= 2048 else 1024 if t <= 8192 else 2048
    return ("splash", 0, edge, t <= 16384, 512)


def _narrow_cases():
    for cell, (model, longest) in _CELLS.items():
        mc = feasibility.MODEL_CONFIGS[model]()
        if mc.attn_head_dims[0] <= 128 and not mc.has_indexer:
            for t in _RUNGS:
                yield pytest.param(model, t, t <= longest, id=f"{cell}-{t}")


@pytest.mark.parametrize("model,t,dispatched", list(_narrow_cases()))
def test_heads_of_64_and_128_keep_the_parents_instances(
    as_tpu, model, t, dispatched
):
    """The guarantee of the cells whose heads are no wider than 128, at
    every rung to 32,768: the tile edge and the compute tile are the
    parent's whatever the heads; at every rung a cell dispatches so is the
    backward (their programs are the parent's); past those the backward is
    what the ONE budget says of the heads' partial dq, where the parent's
    stand-in said "split" of every row past 16,384 tokens."""
    mc = feasibility.MODEL_CONFIGS[model]()
    d, heads = mc.attn_head_dims[0], mc.num_heads
    assert d in (64, 128)
    inst, was = F.instance(t, d, 0, 2048, heads=heads), _parent(t)
    assert (inst.kind, inst.back, inst.edge, inst.kvc) == (
        was[:3] + was[4:]
    )
    if dispatched:
        assert inst == was
    partial_dq = t // inst.edge * heads * t * d * 2
    assert inst.fused == (partial_dq <= 2.5 * _GIB)


# (query heads, width, row, cap) -> (edge, fused, compute tile), and the
# partial dq of the fused form at edges 1024 and 2048 in GiB
_WIDE = [
    pytest.param(32, 192, 16384, 2048, (2048, True, 1024), (3.0, 1.5),
                 id="joyai-16384-1024-does-not-fit"),
    pytest.param(16, 256, 16384, 2048, (1024, True, 512), (2.0, 1.0),
                 id="qwen3next-16384-fits-at-1024"),
    pytest.param(32, 192, 8192, 2048, (1024, True, 512), (0.75, 0.375),
                 id="kimi-8192-as-it-was"),
    pytest.param(32, 256, 16384, 2048, (1024, False, 512), (4.0, 2.0),
                 id="32-of-256-1024-split-before-2048-fused"),
    pytest.param(32, 192, 2048, 2048, (1024, True, 512), (0.046875, 0.0234375),
                 id="joyai-2048-takes-1024"),
    pytest.param(16, 256, 2048, 2048, (1024, True, 512), (0.03125, 0.015625),
                 id="qwen3next-2048-takes-1024"),
    pytest.param(64, 192, 16384, 2048, (1024, False, 512), (6.0, 3.0),
                 id="64-of-192-fit-at-neither-1024-split"),
    pytest.param(32, 192, 16384, 1024, (1024, False, 512), (3.0, 1.5),
                 id="joyai-capped-at-1024-split"),
    pytest.param(16, 256, 32768, 2048, (1024, False, 512), (8.0, 4.0),
                 id="qwen3next-32768-split"),
    pytest.param(4, 256, 32768, 2048, (1024, True, 512), (2.0, 1.0),
                 id="4-of-256-32768-fused"),
]


@pytest.mark.parametrize("heads,d,t,cap,want,gib", _WIDE)
def test_wide_heads_take_the_fused_backward_where_its_partial_dq_fits(
    heads, d, t, cap, want, gib
):
    """Heads wider than 128: edge 1024 with the fused backward where its
    partial dq, T / edge x heads x T x d x 2 bytes a layer, is within the
    ONE budget of 2.5 GiB; heads of 192 (a lane tile and a half) else edge
    2048 under a compute tile of 1024 where that fits; else 1024 and the
    split kernels, which write none. JoyAI fits at 2048 (1.5 GiB),
    Qwen3-Next at 1024 (2 GiB)."""
    inst = F.instance(t, d, 0, cap, heads=heads)
    assert (inst.edge, inst.fused, inst.kvc) == want
    at = {edge: t // edge * heads * t * d * 2 / _GIB for edge in (1024, 2048)}
    assert (at[1024], at[2048]) == gib
    assert inst.fused == (at[inst.edge] <= 2.5)
    assert F.backward_form(t, d, 0, cap, heads=heads) == (
        "fused" if inst.fused else "split"
    )
    tiles = F._tiles(inst)
    assert tiles.use_fused_bwd_kernel == inst.fused
    assert tiles.block_kv_compute == tiles.block_kv_dkv_compute == inst.kvc
    n = t // inst.edge
    assert F.tile_counts(np.ones((1, t), np.int32), d, cap, heads=heads) == (
        (n * (n + 1) // 2,) * 2
    )


@pytest.mark.parametrize("t", [4096, 16384, 32768])
@pytest.mark.parametrize("layer_window", [512, 1024, 4096])
def test_a_layers_window_is_a_band_whatever_its_size(t, layer_window):
    inst = F.instance(t, 128, layer_window, 2048, heads=32)
    if layer_window > t:  # the window reaches over the whole row: causal
        assert inst == F.instance(t, 128, 0, 2048, heads=32)
        assert inst.back == 0
        return
    assert inst == ("splash", layer_window - 1, F.BAND_EDGE, False, 512)
    assert F.BAND_EDGE == 512
    tiles = F._tiles(inst)
    assert tiles.block_q_dq == tiles.block_kv_dq == tiles.block_q == 512
    assert tiles.use_fused_bwd_kernel is False


def test_past_16384_tokens_the_backward_is_split():
    """The 32,768-token row of 32 heads of 128: the fused backward's partial
    dq (4 GiB) does not fit beside the state; the edge is the shape's."""
    inst = F.instance(32768, 128, 0, 2048, heads=32)
    tiles = F._tiles(inst)
    assert tiles.use_fused_bwd_kernel is False
    assert tiles.block_q_dq == tiles.block_kv_dq == tiles.block_q == 2048
    assert F.backward_form(32768, 128, heads=32) == "split"
    assert F.backward_form(16384, 128, heads=32) == "fused"
    assert F.backward_form(32768, 128, layer_window=1024, heads=32) == "split"
    assert F.backward_form(200, 128, heads=32) == ""  # the XLA form's row
    assert F.instance(200, 128, 1024, 2048, heads=32) == ("", 0, 0, False, 0)
    assert F.tile_counts(np.ones((2, 200), np.int32), 128, heads=32) == (0, 0)


def test_a_mask_that_reaches_over_the_whole_row_is_none():
    assert F.instance(512, 64, 1024, heads=2).back == 0
    assert F.instance(1024, 64, 1024, heads=2).back == 1023
    assert F.instance(1024, 64, 1025, heads=2).back == 0
    assert F.instance(32768, 64, 0, heads=2).back == 0


def test_the_instance_is_the_kernels_cache_key():
    """One kernel an instance, whatever the heads' width; a band's kernel
    has the split backward's third mask."""
    F._make_kernel.cache_clear()
    band = F._make_kernel(512, 2, F.instance(512, 64, 128, 128, heads=2), True)
    full = F._make_kernel(512, 2, F.instance(512, 64, 0, 128, heads=2), True)
    assert full is F._make_kernel(
        512, 2, F.instance(512, 192, 0, 128, heads=2), True
    )
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
    row = np.ones((1, 4096), np.int32)
    assert F.tile_counts(row, 64, 512, heads=2) == (36, 36)
    assert F.tile_counts(row, 64, 2048, heads=2) == (10, 10)


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
    d, heads = 128, dict(heads=8)
    for edge in {F.instance(t, d, 0, 2048, **heads).edge, 512}:
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
    now_edge = F.instance(t, d, 0, 2048, **heads).edge
    parent_edge = 512 if 0 < window <= 1024 else now_edge
    kept_parent = (
        F.tiles_needed(rows, parent_edge, np)
        & _tiles_the_window_kept(t // parent_edge, parent_edge, window)
    ).sum()
    assert kept_parent == F.tiles_needed(rows, parent_edge, np).sum()
    if parent_edge == now_edge:
        assert F.tile_counts(rows, d, 2048, **heads)[1] == kept_parent
