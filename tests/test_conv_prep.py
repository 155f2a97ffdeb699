"""The delta-rule mixers' convolution kernels (``ops/conv_prep.py``, PR 55) in
Pallas interpret mode on the CPU, against what they replace:
``silu(_short_conv(..))`` and the head l2norm as ``models/transformer.py``
writes them, forward and all three cotangents by ``jax.grad`` (``dx``, ``dw``
and, where heads are normed, through the norm): one sequence; several packed
with a border inside a block and one ON a block's border; trailing padding; a
sequence shorter than the taps; no, half and all channels normed; bf16 and
float32. Then the two mixers through the kernels at 128-wide heads against
their ``jax.numpy`` lines, the rule that sends a call to the kernels by what
it can see, and that the CPU's programs hold no kernel and say ``"xla"``.

The backend is the CPU here: "as on one chip" means ``jax.default_backend``
and ``jax.device_count`` are steered in the test, so that ``prep_impl``'s own
rule answers, and its "kernel" is run as "interpret"."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tr
from areal_tpu.models.config import GDNConfig, KDAConfig, tiny_config
from areal_tpu.ops import conv_prep as cp

T = 2 * cp.BLOCK_TOKENS  # two token blocks: a border ON a block's border
C = 256  # two heads
TAPS = 4


@pytest.fixture
def as_on_one_chip(monkeypatch):
    rule = cp.prep_impl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(
        cp, "prep_impl",
        lambda *a: rule(*a).replace("kernel", "interpret"),
    )
    return rule


def _segments(layout):
    seg = np.zeros((1, T), np.int32)
    if layout == "one-sequence":
        seg[:] = 1
    elif layout == "packed-borders-inside-and-on-a-block":
        # 300, 576 and 700 inside a block, 512 a block's border
        for i, (lo, hi) in enumerate(
            [(0, 300), (300, 512), (512, 576), (576, 700), (700, T)]
        ):
            seg[0, lo:hi] = i + 1
    elif layout == "trailing-padding":
        seg[0, :400], seg[0, 400:777] = 1, 2
    elif layout == "shorter-than-the-taps":
        # one, two and three tokens, the last three across a block's border
        for i, (lo, hi) in enumerate(
            [(0, 1), (1, 3), (3, 6), (6, 510), (510, 513), (513, 900)]
        ):
            seg[0, lo:hi] = i + 1
    return jnp.asarray(seg)


_LAYOUTS = [
    "one-sequence", "packed-borders-inside-and-on-a-block",
    "trailing-padding", "shorter-than-the-taps",
]


def _plain(x, w, seg, normed):
    """The mixers' lines: ``_short_conv``, ``silu``, the l2norm of the first
    ``normed`` channels a 128-wide head, one rounding."""
    y = jax.nn.silu(tr._short_conv(x, w, seg))
    b, t, c = y.shape
    n = y[..., :normed].reshape(b, t, normed // 128, 128)
    n = n * jax.lax.rsqrt(jnp.sum(n * n, axis=-1, keepdims=True) + 1e-6)
    return jnp.concatenate(
        [n.reshape(b, t, normed), y[..., normed:]], axis=-1
    ).astype(x.dtype)


def _draw(dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (1, T, C), jnp.float32).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (C, TAPS), jnp.float32)).astype(dtype)
    cot = jax.random.normal(ks[2], (1, T, C), jnp.float32)
    return x, w, cot


def _close(got, want, dtype, scale=None):
    """float32: 1e-5 of the scale (the sum's order and the sigmoid's divide
    differ); bf16: an ulp of the larger beside that."""
    assert got.dtype == want.dtype == dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 * (scale or np.abs(want).max())
    if dtype == jnp.bfloat16:
        top = np.maximum(np.maximum(np.abs(got), np.abs(want)), 1e-30)
        tol = tol + 2.0 ** (np.floor(np.log2(top)) - 7)
    assert (np.abs(got - want) <= tol).all(), np.abs(got - want).max()


_DTYPES = [
    pytest.param(jnp.bfloat16, id="bf16"),
    pytest.param(jnp.float32, id="f32"),
]


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("normed", [0, C // 2, C])
@pytest.mark.parametrize("layout", _LAYOUTS)
def test_the_kernel_is_the_mixers_lines(layout, normed, dtype):
    x, w, _ = _draw(dtype)
    seg = _segments(layout)
    _close(
        cp.conv_prep(x, w, seg, normed, interpret=True),
        _plain(x, w, seg, normed), dtype,
    )


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("normed", [0, C // 2, C])
@pytest.mark.parametrize("layout", _LAYOUTS)
def test_the_kernels_cotangents_are_the_mixers_lines(layout, normed, dtype):
    """``dx`` and ``dw`` by ``jax.grad`` through either form (through the
    norm where ``normed``); the kernel's backward keeps the inputs only."""
    x, w, cot = _draw(dtype)
    seg = _segments(layout)

    def grads(form):
        return jax.grad(
            lambda x, w: (form(x, w).astype(jnp.float32) * cot).sum(),
            argnums=(0, 1),
        )(x, w)

    dx, dw = grads(lambda x, w: cp.conv_prep(x, w, seg, normed, True))
    dx_plain, dw_plain = grads(lambda x, w: _plain(x, w, seg, normed))
    # a bf16 ``dx`` is rounded once from float32 sums of like terms
    _close(dx, dx_plain, dtype, scale=8 * np.abs(np.asarray(
        dx_plain, np.float32)).max() if dtype == jnp.bfloat16 else None)
    _close(dw, dw_plain, dtype)


def test_two_rows_do_not_meet():
    """A row's first tokens take nothing from the row before it, whatever
    the segment ids say (both rows are sequence 1 throughout)."""
    x, w, _ = _draw(jnp.float32)
    x2 = jnp.concatenate([x, x[:, ::-1]], axis=0)
    seg = jnp.ones((2, T), jnp.int32)
    got = cp.conv_prep(x2, w, seg, C, True)
    _close(got, _plain(x2, w, seg, C), jnp.float32)


# ---------------------------------------------------------------- the rule --
def test_prep_impl_is_the_kernel_on_one_tpu_for_whole_heads(
    as_on_one_chip, monkeypatch
):
    rule = as_on_one_chip
    assert rule(4096, 4096, 128) == "kernel"  # Kimi's q and k
    assert rule(4096, 0, 128) == "kernel"  # its v
    assert rule(8192, 4096, 128, 16384) == "kernel"  # Qwen3-Next's row
    assert rule(64, 64, 16) == "xla"  # a test-sized head
    assert rule(4096, 4096, 256) == "xla"  # a head of two lane tiles
    assert rule(4096, 4096, 128, 1000) == "xla"  # no whole token blocks
    assert rule(4096, 4096, 128, None, 17) == "xla"  # taps past the halo
    monkeypatch.setattr(jax, "device_count", lambda: 4)  # a mesh
    assert rule(4096, 4096, 128) == "xla"


# ------------------------------------------------- the mixers, either form --
def _mixer(family, wide):
    """(configuration, mixer, one layer's leaves): the family's tiny
    configuration, ``wide``: with heads of a whole lane tile."""
    cfg = tiny_config(family)
    if family == "kimi_linear":
        if wide:
            cfg = dataclasses.replace(cfg, kda=KDAConfig(2, 128))
        mixer, kind = tr._kda_mixer, "kda+dense"
    else:
        if wide:
            cfg = dataclasses.replace(cfg, gdn=GDNConfig(1, 2, 128, 128))
        mixer, kind = tr._gdn_mixer, "gdn+moe"
    lp = tr._draw(
        tr._kind_leaves(cfg, kind), (), jax.random.PRNGKey(1), jnp.float32
    )
    return cfg, mixer, {  # taps of a size that matters
        k: v * 25.0 if k.startswith("conv_") else v for k, v in lp.items()
    }


def _stand_in_scan(q, k, v, g, beta, seg):
    """In the scan's place (the CPU's costs seconds at these widths): every
    input reaches the output, a key head serving its value heads."""
    qk = jnp.repeat(
        (q * k).sum(-1, keepdims=True), v.shape[2] // q.shape[2], axis=2
    ).astype(jnp.float32)
    decay = g if g.ndim == 4 else g[..., None]
    return (v * (qk + decay) * beta[..., None]).astype(v.dtype), None


def _mixer_loss(cfg, mixer, seg):
    return lambda h, lp: (
        mixer(cfg, h, lp, seg, _stand_in_scan)[0] ** 2
    ).sum()


@pytest.mark.parametrize("family", ["kimi_linear", "qwen3_next"])
def test_the_cpus_mixers_hold_no_kernel_and_say_xla(family):
    """The backend as it is: a mixer traces its ``jax.numpy`` lines, forward
    and backward (no ``pallas_call``), and the dispatch spans' ``prep_impl``
    says so; at heads of a lane tile too."""
    assert tr.span_attrs(tiny_config(family))["prep_impl"] == "xla"
    cfg, mixer, lp = _mixer(family, True)
    assert tr.span_attrs(cfg)["prep_impl"] == "xla"
    h = jnp.zeros((1, cp.BLOCK_TOKENS, cfg.hidden_size))
    seg = jnp.ones(h.shape[:2], jnp.int32)
    traced = jax.make_jaxpr(
        jax.grad(_mixer_loss(cfg, mixer, seg), argnums=(0, 1))
    )(h, lp)
    assert "pallas_call" not in str(traced)


@pytest.mark.parametrize("family", ["kimi_linear", "qwen3_next"])
def test_a_mixer_through_the_kernels_is_its_plain_lines(monkeypatch, family):
    """Only ``prep_impl`` is steered; what q, k, v feed is a stand-in."""
    cfg, mixer, lp = _mixer(family, True)
    t = cp.BLOCK_TOKENS
    h = jax.random.normal(jax.random.PRNGKey(2), (1, t, cfg.hidden_size))
    seg = jnp.asarray(
        np.repeat([1, 2, 3, 0], [200, 3, 250, t - 453])[None].astype(np.int32)
    )
    run = jax.value_and_grad(_mixer_loss(cfg, mixer, seg), argnums=(0, 1))
    monkeypatch.setattr(cp, "prep_impl", lambda *a: "interpret")
    assert tr.span_attrs(cfg)["prep_impl"] == "interpret"
    got = run(h, lp)
    assert "pallas_call" in str(jax.make_jaxpr(run)(h, lp))
    monkeypatch.setattr(cp, "prep_impl", lambda *a: "xla")
    want = run(h, lp)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 2e-4 * scale + 1e-12, path


@pytest.mark.parametrize("block,channels", [(256, 128)])
def test_other_blocks_give_the_same_numbers(monkeypatch, block, channels):
    """Four token blocks with one head a channel block: what
    ``tools/microbench_kda --prep`` sweeps."""
    monkeypatch.setattr(cp, "BLOCK_TOKENS", block)
    monkeypatch.setattr(cp, "BLOCK_CHANNELS", channels)
    jax.clear_caches()  # the wrappers read the two while they are traced
    x, w, cot = _draw(jnp.float32)
    seg = _segments("packed-borders-inside-and-on-a-block")
    form = lambda x, w: cp.conv_prep(x, w, seg, C // 2, True)  # noqa: E731
    out, vjp = jax.vjp(form, x, w)
    want, vjp_plain = jax.vjp(lambda x, w: _plain(x, w, seg, C // 2), x, w)
    _close(out, want, jnp.float32)
    for got, plain in zip(vjp(cot), vjp_plain(cot)):
        _close(got, plain, jnp.float32)
    jax.clear_caches()
