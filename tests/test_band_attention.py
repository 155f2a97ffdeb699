"""A window-attention layer's band through its own kernels
(``ops/band_attention.py``, PR 47): the strip forward's output and the strip
backward's dq, dk, dv, in Pallas interpret mode on the CPU, against the
plain XLA form ``ops.basic.segment_attention(window=)``. The cells' windows
of 512 and 1,024 keys over strips of 128 or 256 queries are 2, 4 or 8 key
blocks behind a query block; here strips of 128 queries under windows of 256
and 512 keys (2 and 4 blocks behind) and one that is no whole number of
blocks, 1 and 8 query heads a key head, rows with a sequence border inside a
strip, on a block edge, a padded tail, a query block of padding alone, one
sequence a row. And the rule that sends a call there, by what it can see."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops import band_attention as BA
from areal_tpu.ops import flash as F
from areal_tpu.ops.basic import segment_attention

D = 128


@pytest.fixture(autouse=True)
def strips_of_128(monkeypatch):
    monkeypatch.setattr(BA, "STRIP_BQ", 128)
    F._make_kernel.cache_clear()
    yield
    F._make_kernel.cache_clear()


def _rows(rows, t):
    seg = np.zeros((len(rows), t), np.int32)  # 0 = padding
    for r, lens in enumerate(rows):
        at = 0
        for i, n in enumerate(lens):
            seg[r, at:at + n] = i + 1
            at += n
        assert at <= t
    return jnp.asarray(seg)


def _out_and_grads(attend, q, k, v, seg, cot):
    def loss(q, k, v):
        out = attend(q, k, v, seg)
        return (out.astype(jnp.float32) * cot).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    return [np.asarray(x, np.float32) for x in (out, *grads)]


# (rows' sequence lengths, T, (query heads, key heads), window, dtype, tol)
_CASES = [
    pytest.param([[300, 250, 150]], 768, (2, 2), 256, jnp.float32, 3e-5,
                 id="w256-rep1-borders-inside-strips-f32"),
    pytest.param([[300, 250, 150]], 768, (8, 1), 256, jnp.float32, 3e-5,
                 id="w256-rep8-borders-inside-strips-f32"),
    pytest.param([[300, 250, 150]], 768, (8, 1), 256, jnp.bfloat16, 4e-2,
                 id="w256-rep8-borders-inside-strips-bf16"),
    pytest.param([[256, 128, 300]], 768, (8, 1), 256, jnp.float32, 3e-5,
                 id="w256-rep8-borders-on-block-edges-f32"),
    pytest.param([[256, 128, 300]], 768, (2, 2), 256, jnp.bfloat16, 4e-2,
                 id="w256-rep1-borders-on-block-edges-bf16"),
    pytest.param([[330, 90]], 768, (8, 1), 256, jnp.float32, 3e-5,
                 id="w256-rep8-query-blocks-of-padding-f32"),
    pytest.param([[330, 90]], 768, (2, 2), 256, jnp.bfloat16, 4e-2,
                 id="w256-rep1-query-blocks-of-padding-bf16"),
    pytest.param([[700]], 768, (8, 1), 256, jnp.float32, 3e-5,
                 id="w256-rep8-one-sequence-padded-tail-f32"),
    pytest.param([[1024]], 1024, (2, 1), 512, jnp.float32, 3e-5,
                 id="w512-rep2-one-sequence-no-padding-f32"),
    pytest.param([[1000]], 1024, (8, 1), 512, jnp.bfloat16, 4e-2,
                 id="w512-rep8-one-sequence-bf16"),
    pytest.param([[600, 40, 300]], 1024, (2, 2), 512, jnp.float32, 3e-5,
                 id="w512-rep1-borders-f32"),
    pytest.param([[600, 40, 300]], 1024, (8, 1), 512, jnp.bfloat16, 4e-2,
                 id="w512-rep8-borders-bf16"),
    pytest.param([[300, 250, 150], [768]], 768, (4, 2), 200, jnp.float32,
                 3e-5, id="w200-rep2-two-rows-f32"),
]


@pytest.mark.parametrize("rows,t,heads,window,dtype,tol", _CASES)
def test_the_strip_kernels_match_xla_segment_attention(
    rows, t, heads, window, dtype, tol
):
    hq, hkv = heads
    # the CPU's, unasked
    assert F.backward_form(t, D, window, heads=hq) == "split"
    assert F.instance(t, D, window, interpret=True, heads=hq) == (
        "strip", window - 1, 128, False, 0
    )
    ks = jax.random.split(jax.random.PRNGKey(len(rows[0]) + window), 4)
    b = len(rows)
    q = jax.random.normal(ks[0], (b, t, hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, D), jnp.float32).astype(dtype)
    cot = jax.random.normal(ks[3], (b, t, hq, D), jnp.float32)
    seg = _rows(rows, t)
    want = _out_and_grads(
        lambda *a: segment_attention(*a, window=window), q, k, v, seg, cot
    )
    got = _out_and_grads(
        lambda *a: F.flash_segment_attention(
            *a, layer_window=window, interpret=True
        ),
        q, k, v, seg, cot,
    )
    for name, a, b_ in zip(("out", "dq", "dk", "dv"), got, want):
        scale = max(1.0, float(np.abs(b_).max()))
        assert np.isfinite(a).all(), name
        assert np.abs(a - b_).max() <= tol * scale, name
    # padding gives exactly nothing and takes nothing, keys and queries
    pad = np.asarray(seg) == 0
    for a in got:
        assert not a[pad].any()


def test_the_strip_kernels_are_the_splash_kernels_numbers(monkeypatch):
    """The band through its own kernels against the same band through the
    splash kernel and its split backward (what every band took before PR 47
    and a band of narrower heads still takes), to rounding: in float32 the
    order of the sums is all that differs."""
    t, hq, hkv, window = 768, 4, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, t, hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, t, hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, t, hkv, D), jnp.float32)
    cot = jax.random.normal(ks[3], (1, t, hq, D), jnp.float32)
    seg = _rows([[300, 250, 150]], t)

    def run():
        return _out_and_grads(
            lambda *a: F.flash_segment_attention(
                *a, layer_window=window, block_cap=128, interpret=True
            ),
            q, k, v, seg, cot,
        )

    strip = run()
    monkeypatch.setattr(BA, "strip_block", lambda *a: 0)  # takes no shape
    assert F.instance(t, D, window, 128, True, heads=hq) == (
        "splash", window - 1, 128, False, 128
    )
    split = run()
    for a, b in zip(strip, split):
        np.testing.assert_allclose(a, b, atol=2e-5)


# (T, head width, layer's window) -> the form on a TPU
_FORMS = [
    (16384, 128, 512, "strip"),
    (2048, 128, 512, "strip"),  # the shortest rung of the cell's rows
    (32768, 128, 1024, "strip"),
    (8192, 128, 1024, "strip"),
    (16384, 256, 512, "strip"),
    (16384, 64, 512, "split"),  # half a lane row a head
    (16384, 192, 512, "split"),
    (32768, 128, 4096, "strip"),  # a band whatever its size
    (512, 128, 1024, "fused"),  # the window reaches over the row: causal
    (16384, 128, 0, "fused"),
    (32768, 128, 0, "split"),
    (8192, 128, 0, "fused"),
    (200, 128, 512, ""),  # the XLA form's row
]


@pytest.mark.parametrize("t,d,layer_window,form", _FORMS)
def test_backward_form_says_strip_where_the_rule_takes_the_kernel(
    monkeypatch, t, d, layer_window, form
):
    said = functools.partial(F.backward_form, t, d, layer_window, heads=32)
    was = said()  # on the CPU: splash's
    assert was == ("split" if form == "strip" else form)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert said() == form
    assert (F.instance(t, d, layer_window, heads=32).kind == "strip") == (
        form == "strip"
    )
    # a row that is no whole number of strips keeps the splash backward
    monkeypatch.setattr(BA, "STRIP_BQ", 3 * 128)
    if form == "strip":
        assert said() == "split"


@pytest.mark.parametrize("model,row_len", [
    ("laguna_xsp2", 16384), ("laguna_xsp2", 2048), ("laguna_xsp2", 8192),
    ("mellum2_12b_a2p5b", 32768), ("mellum2_12b_a2p5b", 16384),
])
def test_a_grad_dispatch_on_a_tpu_says_strip_for_the_two_window_families(
    monkeypatch, model, row_len
):
    """What ``train.grad_dispatch`` carries as ``attn_bwd_window`` at the
    two cells' row lengths: the counter of how often the band's own kernels
    engage; the full layers' core keeps its form."""
    from areal_tpu.models import transformer as tr
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    cfg = MODEL_CONFIGS[model]()
    on_cpu = tr.grad_span_attrs(cfg, "flash", row_len)
    assert on_cpu["attn_bwd_window"] == "split"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_tpu = tr.grad_span_attrs(cfg, "flash", row_len)
    assert on_tpu["attn_bwd_window"] == "strip"
    assert on_tpu["attn_bwd_full"] == on_cpu["attn_bwd_full"]
    assert tr.grad_span_attrs(cfg, "xla", row_len) == {}
