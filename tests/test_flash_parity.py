"""The fused splash kernel against the plain XLA form it replaces on a TPU
(``ops/flash.flash_segment_attention`` vs ``ops/basic.segment_attention``):
outputs and the gradients of q, k and v, with the kernel in Pallas interpret
mode on the CPU. Ragged segments with tail padding, Qwen2-0.5B's 14/2 heads
of 64; full causal and a layer's band smaller than the row; one tile per row
and several (the block-skipping paths). Since PR 42 the kernel also skips the
tiles no sequence of a row needs (``ops/flash.tiles_needed`` laid over the
static mask): rows of 512 tokens in 4 x 4 tiles of 128 with borders of every
kind, held to the XLA form and, where a row is one sequence, to the kernel
without that mask bit for bit; and the rule alone against brute force."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops import flash as F
from areal_tpu.ops.basic import segment_attention

T, HQ, HKV, D = 256, 14, 2, 64
T4 = 512  # 4 x 4 tiles at edge 128


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


def _inputs(dtype, seg_rows, seed=0, heads=(HQ, HKV), d=D, dv=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    b, t = len(seg_rows), len(seg_rows[0])
    hq, hkv = heads
    q = jax.random.normal(ks[0], (b, t, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, dv), jnp.float32).astype(dtype)
    cot = jax.random.normal(ks[3], (b, t, hq, dv), jnp.float32)
    return q, k, v, jnp.asarray(np.stack(seg_rows)), cot


def _out_and_grads(attend, q, k, v, seg, cot):
    def loss(q, k, v):
        out = attend(q, k, v, seg)
        return (out.astype(jnp.float32) * cot).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    return [np.asarray(x, np.float32) for x in (out, *grads)]


# (cap on the edge or None, rows' sequence lengths, dtype, tol, keywords);
# a band of 129 keys (128 back) over sequences it covers and one it does not
_CASES = [
    pytest.param(None, [[100, 60, 70]], jnp.float32, 3e-5, {},
                 id="causal-f32"),
    pytest.param(128, [[100, 60, 70], [31, 190]], jnp.float32, 3e-5, {},
                 id="causal-f32-edge128-rows2"),
    pytest.param(128, [[120, 37, 64]], jnp.float32, 3e-5,
                 dict(layer_window=129), id="band129-f32-edge128"),
    pytest.param(None, [[128, 5, 90]], jnp.float32, 3e-5,
                 dict(layer_window=129), id="band129-f32"),
    pytest.param(128, [[100, 60, 70]], jnp.bfloat16, 4e-2, {},
                 id="causal-bf16-edge128"),
    pytest.param(128, [[120, 37, 64]], jnp.bfloat16, 4e-2,
                 dict(layer_window=129), id="band129-bf16-edge128"),
    pytest.param(128, [[200, 30]], jnp.float32, 3e-5,
                 dict(layer_window=129), id="band129-f32-cuts-a-sequence"),
]

# rows of 512 tokens in 4 x 4 tiles (PR 42: the tiles a row's borders empty).
# (rows' sequence lengths, dtype, tol, keywords: ``layer_window`` a layer's
# band, ``heads`` / ``d`` / ``dv`` the core's shape)
_TILE_CASES = [
    pytest.param([[40, 25, 60, 33, 90, 12, 70, 100, 30]], jnp.float32, 3e-5,
                 {}, id="tiles-many-short-f32"),
    pytest.param([[40, 25, 60, 33, 90, 12, 70, 100, 30]], jnp.bfloat16, 4e-2,
                 {}, id="tiles-many-short-bf16"),
    pytest.param([[128, 200, 56, 100]], jnp.float32, 3e-5, {},
                 id="tiles-border-on-a-tile-edge"),
    pytest.param([[500]], jnp.float32, 3e-5, {}, id="tiles-one-sequence"),
    pytest.param([[500]], jnp.bfloat16, 4e-2, {},
                 id="tiles-one-sequence-bf16"),
    pytest.param([[150, 90]], jnp.float32, 3e-5, {},
                 id="tiles-a-query-block-of-padding"),
    pytest.param([[300, 90, 100], [64, 64, 128, 200]], jnp.float32, 3e-5, {},
                 id="tiles-two-rows-other-borders"),
    pytest.param([[40, 260, 150], [500]], jnp.float32, 3e-5,
                 dict(layer_window=100), id="tiles-band-with-borders"),
    pytest.param([[40, 260, 150]], jnp.bfloat16, 4e-2,
                 dict(layer_window=100), id="tiles-band-with-borders-bf16"),
    pytest.param([[210, 130, 150]], jnp.float32, 3e-5,
                 dict(heads=(4, 4), d=192, dv=128), id="tiles-192-128"),
    pytest.param([[210, 130, 150]], jnp.bfloat16, 4e-2,
                 dict(heads=(4, 4), d=192, dv=128), id="tiles-192-128-bf16"),
]


def _held_to_xla(q, k, v, seg, cot, tol, **kw):
    """The kernel's out, dq, dk, dv (interpret mode), held to the XLA form."""
    lw = kw.get("layer_window", 0)
    want = _out_and_grads(
        lambda *a: segment_attention(*a, window=lw), q, k, v, seg, cot
    )
    got = _out_and_grads(
        lambda *a: F.flash_segment_attention(*a, interpret=True, **kw),
        q, k, v, seg, cot,
    )
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        scale = max(1.0, float(np.abs(b).max()))
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * scale, name
    # padding contributes exactly nothing, and receives nothing
    pad = np.asarray(seg) == 0
    assert not got[0][pad].any()
    assert not got[1][pad].any()
    return got


@pytest.mark.parametrize(
    "edge_cap,lens,dtype,tol,shape",
    list(_CASES) + [
        pytest.param(128, *p.values[:3], dict(p.values[3], t=T4), id=p.id)
        for p in _TILE_CASES
    ],
)
def test_splash_matches_xla_segment_attention(
    monkeypatch, edge_cap, lens, dtype, tol, shape
):
    shape = dict(shape)
    t, lw = shape.pop("t", T), shape.pop("layer_window", 0)
    cap = dict(block_cap=edge_cap or 0, layer_window=lw)
    inst = F.instance(t, D, interpret=True, heads=HQ, **cap)
    assert inst[:3] == ("splash", lw - 1 if lw else 0, edge_cap or t)
    q, k, v, seg, cot = _inputs(dtype, [_row(n, t) for n in lens], **shape)
    got = _held_to_xla(q, k, v, seg, cot, tol, **cap)
    if all(len(n) == 1 for n in lens):
        # a row that is one sequence keeps every tile: the kernel without
        # the rows' tile mask, to the bit, forward and gradients
        monkeypatch.setattr(F, "_row_kernel", lambda kernel, seg_row: kernel)
        F._make_kernel.cache_clear()
        static = _out_and_grads(
            lambda *a: F.flash_segment_attention(*a, interpret=True, **cap),
            q, k, v, seg, cot,
        )
        for a, b in zip(got, static):
            np.testing.assert_array_equal(a, b)


# (heads, d, dv, the hand-made instance): the library's two backward forms
# and a compute tile other than the edge, at the widths the rule tells apart
# since PR 57 (the cells' own instances are the chip's: edge 1024 and 2048)
_WIDE_CASES = [
    pytest.param((4, 4), 192, 128, F.Instance("splash", 0, 128, False, 128),
                 id="split-192-128"),
    pytest.param((4, 2), 256, 256, F.Instance("splash", 0, 128, False, 128),
                 id="split-256"),
    pytest.param((4, 4), 192, 128, F.Instance("splash", 0, 256, True, 128),
                 id="fused-192-128-compute-tile-half-the-edge"),
    pytest.param((4, 2), 256, 256, F.Instance("splash", 0, 256, True, 256),
                 id="fused-256-compute-tile-the-edge"),
]


@pytest.mark.parametrize("heads,d,dv,inst", _WIDE_CASES)
def test_wide_heads_either_backward_matches_xla(heads, d, dv, inst):
    """An instance said outright (as ``tools/microbench_attn_v2`` hands it to
    ``_splash``): out, dq, dk, dv against the XLA form's on a 512-token row
    with borders, under the rows' tile mask."""
    q, k, v, seg, cot = _inputs(
        jnp.float32, [_row([210, 130, 150], T4)], heads=heads, d=d, dv=dv
    )
    kernel = F._make_kernel(T4, heads[0] // heads[1], inst, True)
    assert (kernel.dq_mask_info is None) == inst.fused
    want = _out_and_grads(segment_attention, q, k, v, seg, cot)
    got = _out_and_grads(
        lambda *a: F._splash(*a, inst=inst, interpret=True), q, k, v, seg, cot
    )
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        scale = max(1.0, float(np.abs(b).max()))
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 3e-5 * scale, name
    assert not got[0][np.asarray(seg) == 0].any()


def test_a_row_shorter_than_a_tile_takes_the_xla_form():
    """By shape, with no kernel built: the CPU backend would refuse one."""
    t = 96
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, t, HQ, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, HKV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, HKV, D), jnp.bfloat16)
    seg = jnp.asarray(_row([40, 30], t)[None])
    got = F.flash_segment_attention(q, k, v, seg)
    want = segment_attention(q, k, v, seg)
    assert F._make_kernel.cache_info().currsize == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and a layer's window goes with it to the XLA form
    got = F.flash_segment_attention(q, k, v, seg, layer_window=24)
    want = segment_attention(q, k, v, seg, window=24)
    assert F._make_kernel.cache_info().currsize == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got - segment_attention(q, k, v, seg))).max() > 0


def _random_row(rng, t):
    """1-9 sequences with ids rising, then 0-40% of padding (0) at the tail."""
    real = t - int(rng.integers(0, int(0.4 * t) + 1))
    n = int(rng.integers(1, 10))
    cuts = np.sort(rng.choice(np.arange(1, real), size=n - 1, replace=False))
    seg = np.zeros(t, np.int32)
    for i, (a, b) in enumerate(zip([0, *cuts], [*cuts, real])):
        seg[a:b] = i + 1
    return seg


@pytest.mark.parametrize("edge", [128, 256])
def test_the_tile_rule_against_brute_force(edge):
    """``tiles_needed`` over 100 random rows an edge (200 in all): it never
    empties a tile that holds a live pair, empties every tile off the
    diagonal that holds none, never touches the diagonal, and its numpy form
    (the engine's count) and its ``jnp`` form (the kernel's mask) agree."""
    t = 1024
    n = t // edge
    rng = np.random.default_rng(edge)
    rows = np.stack([_random_row(rng, t) for _ in range(100)])
    at = np.arange(t)
    live = (
        (rows[:, :, None] == rows[:, None, :]) & (rows[:, :, None] > 0)
        & (at[None, :] <= at[:, None])  # [row, query, key]
    )
    holds = live.reshape(len(rows), n, edge, n, edge).any((2, 4))
    needed = F.tiles_needed(rows, edge, np)
    diagonal = np.eye(n, dtype=bool)
    assert needed[:, diagonal].all()
    np.testing.assert_array_equal(needed | diagonal, holds | diagonal)
    assert (~needed).sum() > 100 * n  # and the rows do empty tiles
    on_device = jax.jit(jax.vmap(lambda s: F.tiles_needed(s, edge)))(rows)
    np.testing.assert_array_equal(np.asarray(on_device), needed)
    # one row's rule is the batch's
    np.testing.assert_array_equal(F.tiles_needed(rows[7], edge, np), needed[7])


@pytest.mark.parametrize("back", [0, 128, 256, 300])
def test_laid_over_mask_infos_walk_the_tiles_the_count_counts(back):
    """The three mask infos of a row's kernel (forward, dq, dkv; the grid
    shrunk to a layer's band ``back`` keys behind a query, or not) turn on
    exactly the tiles the static mask has and the row needs, which under the
    causal mask are the ones ``tile_counts`` counts as kept; a step that is
    on names its own block, one that is off the block of the next step that
    is on."""
    t, edge = 1024, 128
    seg = _row([300, 90, 410, 130], t)
    needed = F.tiles_needed(seg, edge, np)
    for fused in (True, False):
        static = F._make_kernel(
            t, 2, F.Instance("splash", back, edge, fused, edge), True
        )
        row = F._row_kernel(static, jnp.asarray(seg))
        # the borders leave 19 of the triangle's 36 (17 of the 28 under the
        # diagonal are off); a band leaves those of them its static mask has
        in_band = np.asarray(static.fwd_mask_info.block_mask[0]) > 0
        if back:  # the band's grid is shrunk: by ``data_next``, the kv block
            kv = np.asarray(static.fwd_mask_info.data_next[0])
            kept = sum(
                bool(needed[i, kv[i, j]]) for i, j in zip(*np.nonzero(in_band))
            )
            assert 8 <= kept <= 19
        else:
            assert in_band.sum() == 36
            kept = 19
            assert F.tile_counts(seg[None], D, edge, heads=HQ) == (36, kept)
        assert (row.dq_mask_info is None) == fused
        for info, kv_major in (
            (row.fwd_mask_info, False), (row.dq_mask_info, False),
            (row.dkv_mask_info, True),
        ):
            if info is None:
                continue
            on = np.asarray(info.block_mask[0]) > 0
            there = np.asarray(info.data_next[0])
            assert on.sum() == kept
            if kv_major:  # the walk: query blocks of a kv block
                on, there = on.T, there.T
            q_or_kv = np.nonzero(on)
            walked = there[q_or_kv]
            # each (query block, kv block) once, and only needed ones
            pairs = set(zip(q_or_kv[0].tolist(), walked.tolist()))
            assert len(pairs) == kept
            for a, b in pairs:
                assert needed[(b, a) if kv_major else (a, b)]
            flat_on, flat = on.reshape(-1), there.reshape(-1)
            nxt = None
            for i in range(flat.size - 1, -1, -1):
                if flat_on[i]:
                    nxt = flat[i]
                elif nxt is not None:
                    assert flat[i] == nxt
