"""The rotation's one-pass kernel (``ops/rotary.py``, PR 51) in Pallas
interpret mode on the CPU, against the plain form ``ops.basic.rotate_leading``:
forward and ``jax.grad`` for a whole head (R = 64) and half a head (R = 32),
plain and yarn-scaled tables, bf16 and float32, 2 to 64 heads, one row and
two; the backward as the transposed map; ``apply_rope`` as ``rotate_leading``
at a table's rows; the rule that sends a call to the kernel, by what it can
see; and ``rope_impl`` on the dispatch spans.

The backend is the CPU here: "as on one chip" means ``jax.default_backend``
and ``jax.device_count`` are steered in the test, so that ``rotate_impl``'s
own rule answers, and its "kernel" is run as "interpret"."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tr
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import basic
from areal_tpu.ops import rotary as R

D = 128
T = R.BLOCK_TOKENS


@pytest.fixture
def as_on_one_chip(monkeypatch):
    rule = R.rotate_impl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(
        R, "rotate_impl",
        lambda d, t=None: rule(d, t).replace("kernel", "interpret"),
    )
    return rule


def _tables(b, t, r, yarn):
    pos = jnp.arange(t)[None] + 977 * jnp.arange(b)[:, None]
    if yarn:  # Laguna's full layers: factor 32 over 4,096, scaled tables
        inv = basic.rope_inv_freq(2 * r, 500000.0, 32.0, 4096)
        return basic.rope_at(pos, inv, 1.3466)
    return basic.rope_at(pos, basic.rope_inv_freq(2 * r, 10000.0))


def _draw(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return (3.0 * x).astype(dtype)


def _close(got, want, dtype, roundings=1):
    """float32: within 1e-6 of the operands' scale (a rotation cancels, so
    no bound holds relative to a single output; the two forms differ by how
    the backend contracts a product and a sum); bf16: within one bf16 ulp of
    the larger of the two beside that (``roundings`` 2, a normed head: its
    rounding before the rotation may fall the other way, an ulp of the
    largest value the rotation then mixes in)."""
    assert got.dtype == want.dtype == dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-6 * np.abs(want).max()
    if dtype == jnp.bfloat16 and roundings > 1:
        tol += 2.0 ** -7 * np.abs(want).max()
    if dtype == jnp.bfloat16:
        top = np.maximum(np.maximum(np.abs(got), np.abs(want)), 1e-30)
        assert (np.abs(got - want) <= 2.0 ** (np.floor(np.log2(top)) - 7)
                + tol).all()
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=tol)


# (rotated half R, heads, rows, yarn); every case in bf16 and in float32
_SHAPES = [
    pytest.param(64, 2, 1, False, id="whole-head-2-heads"),
    pytest.param(64, 8, 2, False, id="whole-head-8-heads-2-rows"),
    pytest.param(64, 48, 1, True, id="whole-head-48-heads-yarn"),
    pytest.param(64, 64, 1, False, id="whole-head-64-heads"),
    pytest.param(32, 2, 2, True, id="half-head-2-heads-2-rows-yarn"),
    pytest.param(32, 8, 1, False, id="half-head-8-heads"),
    pytest.param(32, 48, 1, True, id="half-head-48-heads-yarn"),
    pytest.param(32, 64, 1, False, id="half-head-64-heads"),
]
_DTYPES = [
    pytest.param(jnp.bfloat16, id="bf16"), pytest.param(jnp.float32, id="f32")
]


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("r,heads,rows,yarn", _SHAPES)
def test_the_kernel_rotates_as_the_plain_form(
    as_on_one_chip, r, heads, rows, yarn, dtype
):
    x = _draw((rows, T, heads, D), dtype)
    cos, sin = _tables(rows, T, r, yarn)
    assert R.rotate_impl(D, T) == "interpret"
    _close(R.rotate(x, cos, sin), basic.rotate_leading(x, cos, sin), dtype)


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("r,heads,rows,yarn", _SHAPES)
def test_the_kernels_gradient_is_the_plain_forms(
    as_on_one_chip, r, heads, rows, yarn, dtype
):
    x = _draw((rows, T, heads, D), dtype)
    cot = _draw(x.shape, jnp.float32, seed=1)
    cos, sin = _tables(rows, T, r, yarn)

    def grad_of(rotation):
        return jax.grad(
            lambda x: (rotation(x, cos, sin).astype(jnp.float32) * cot).sum()
        )(x)

    _close(grad_of(R.rotate), grad_of(basic.rotate_leading), dtype)


@pytest.mark.parametrize("r", [64, 32])
def test_the_backward_is_the_transposed_rotation(as_on_one_chip, r):
    """<R x, y> == <x, R^T y>, and R^T y keeps nothing of x."""
    x = _draw((2, T, 4, D), jnp.float32)
    y = _draw(x.shape, jnp.float32, seed=1)
    cos, sin = _tables(2, T, r, True)
    out, vjp = jax.vjp(lambda x: R.rotate(x, cos, sin), x)
    (back,) = vjp(y)
    lhs = float((out * y).sum())
    assert lhs == pytest.approx(float((x * back).sum()), rel=1e-5)
    # the rotation by the opposite angle at the same scale
    _close(back, basic.rotate_leading(y, cos, -sin), jnp.float32)


def _normed(x, w, cos, sin):
    return basic.rotate_leading(basic.rms_norm(x, w, 1e-6), cos, sin)


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("r,heads,rows", [
    pytest.param(64, 8, 2, id="whole-head-8-heads-2-rows"),
    pytest.param(64, 32, 1, id="whole-head-32-heads"),
    pytest.param(32, 4, 1, id="half-head-4-heads"),
])
def test_the_kernel_norms_a_head_in_the_same_pass(
    as_on_one_chip, r, heads, rows, dtype
):
    """``rotate(norm=(weight, eps))``: ``rms_norm`` a head, its rounding to
    the operand's dtype, then the rotation; the backward gives ``dx`` from
    the projection's ``x`` and the weight's cotangent summed over tokens and
    heads."""
    x = _draw((rows, T, heads, D), dtype)
    w = (1 + 0.1 * _draw((D,), jnp.float32, seed=2)).astype(dtype)
    cot = _draw(x.shape, jnp.float32, seed=1)
    cos, sin = _tables(rows, T, r, True)

    def kernel(x, w):
        return R.rotate(x, cos, sin, (w, 1e-6))

    _close(kernel(x, w), _normed(x, w, cos, sin), dtype, roundings=2)

    def grads_of(f):
        return jax.grad(
            lambda x, w: (f(x, w).astype(jnp.float32) * cot).sum(),
            argnums=(0, 1),
        )(x, w)

    (dx, dw), (dx_want, dw_want) = grads_of(kernel), grads_of(
        lambda x, w: _normed(x, w, cos, sin)
    )
    _close(dx, dx_want, dtype, roundings=2)
    assert dw.dtype == dw_want.dtype == dtype and dw.shape == (D,)
    np.testing.assert_allclose(  # a sum over every token and head
        np.asarray(dw, np.float32), np.asarray(dw_want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
        atol=1e-4 * float(np.abs(np.asarray(dw_want, np.float32)).max()),
    )


def test_a_norm_handed_to_the_plain_form_is_rms_norm_then_the_rotation():
    x = _draw((2, 40, 4, D), jnp.bfloat16)
    w = (1 + 0.1 * _draw((D,), jnp.float32, seed=2)).astype(jnp.bfloat16)
    cos, sin = basic.rope_frequencies(D, 512, 1000000.0)
    pos = jnp.arange(40)[None] + jnp.array([[0], [300]])
    want = _normed(x, w, cos[pos], sin[pos])
    assert (R.rotate(x, cos[pos], sin[pos], (w, 1e-6)) == want).all()
    assert (R.apply_rope(x, pos, cos, sin, (w, 1e-6)) == want).all()


@pytest.mark.parametrize("heads,d", [(2, 128), (14, 64)])
def test_apply_rope_is_rotate_leading_at_the_tables_rows(heads, d):
    x = _draw((2, 40, heads, d), jnp.bfloat16)
    cos, sin = basic.rope_frequencies(d, 512, 1000000.0)
    pos = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 40)))
    want = basic.rotate_leading(x, cos[pos], sin[pos])
    assert (basic.apply_rope(x, pos, cos, sin) == want).all()
    assert (R.apply_rope(x, pos, cos, sin) == want).all()


def test_the_three_callers_reach_the_kernel(as_on_one_chip):
    """``apply_rope`` at a table's rows, ``apply_mrope`` at the section-mixed
    tables and ``rotate_leading`` at the tables it is handed."""
    x = _draw((1, T, 4, D), jnp.bfloat16)
    cos, sin = basic.rope_frequencies(D, 2 * T, 1000000.0)
    pos = jnp.arange(T)[None] + 5
    _close(
        R.apply_rope(x, pos, cos, sin), basic.apply_rope(x, pos, cos, sin),
        jnp.bfloat16,
    )
    pos3 = jnp.stack([pos, pos // 2, pos // 3], axis=-1)
    sections = (16, 24, 24)
    _close(
        R.apply_mrope(x, pos3, cos, sin, sections),
        basic.apply_mrope(x, pos3, cos, sin, sections), jnp.bfloat16,
    )
    _close(
        R.rotate_leading(x, cos[pos], sin[pos]),
        basic.rotate_leading(x, cos[pos], sin[pos]), jnp.bfloat16,
    )
    lowered = jax.jit(R.apply_rope).lower(x, pos, cos, sin).as_text()
    assert "_rotate_rows" in lowered


@pytest.mark.parametrize("t,heads,d,why", [
    (T, 14, 64, "64-wide heads"),
    (1, 8, 128, "a decode step's one token"),
    (T - 12, 8, 128, "a row that is no whole number of blocks"),
    (T, 8, 192, "a head of one and a half lane tiles"),
])
def test_a_shape_the_kernel_does_not_take_runs_the_plain_form(
    as_on_one_chip, t, heads, d, why
):
    assert as_on_one_chip(d, t) == "xla", why
    x = _draw((2, t, heads, d), jnp.bfloat16)
    cos, sin = basic.rope_frequencies(d, 1024, 10000.0)
    pos = jnp.arange(t)[None] + jnp.array([[0], [300]])
    want = basic.rotate_leading(x, cos[pos], sin[pos])
    assert (R.rotate(x, cos[pos], sin[pos]) == want).all()
    assert (R.apply_rope(x, pos, cos, sin) == want).all()
    lowered = jax.jit(R.apply_rope).lower(x, pos, cos, sin).as_text()
    assert "_rotate_rows" not in lowered


def test_the_rule_reads_the_backend_the_devices_and_the_shape(monkeypatch):
    assert R.rotate_impl(128, 16384) == "xla"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert R.rotate_impl(128, 16384) == "xla"  # this process's 8 devices
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert R.rotate_impl(128, 16384) == "kernel"
    assert R.rotate_impl(128) == R.rotate_impl(256) == "kernel"
    assert R.rotate_impl(64) == R.rotate_impl(0) == "xla"
    assert R.rotate_impl(128, 2048 + 256) == "xla"
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert R.rotate_impl(128, 16384) == "xla"


@pytest.mark.parametrize("model,devices,says", [
    ("laguna_xsp2", 1, "kernel"),
    ("mellum2_12b_a2p5b", 1, "kernel"),
    ("keye_vl_2p0_30b_a3b", 1, "kernel"),
    ("zaya1_8b", 1, "kernel"),
    ("qwen2_0p5b", 1, "xla"),  # 64-wide heads
    ("kimi_linear_48b_a3b", 1, "xla"),  # rotates nothing
    ("olmoe_1b_7b", 4, "xla"),  # a mesh
])
def test_a_cells_dispatch_spans_say_which_rotation(
    monkeypatch, model, devices, says
):
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    cfg = MODEL_CONFIGS[model]()
    assert tr.span_attrs(cfg)["rope_impl"] == "xla"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert tr.span_attrs(cfg)["rope_impl"] == says


def test_a_tiny_rotary_models_dispatch_spans_carry_rope_impl(monkeypatch):
    from areal_tpu.api.cli_args import (
        MicroBatchSpec, OptimizerConfig, TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.sft.lm_engine import (
        sft_loss_fn, sft_loss_weight_fn,
    )
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.utils import data as data_utils
    from areal_tpu.utils import tracing

    seen = []
    span = tracing.span

    def recording(name, *a, **attrs):
        seen.append((name, attrs))
        return span(name, *a, **attrs)

    monkeypatch.setattr(tracing, "span", recording)
    eng = SPMDTrainEngine(TrainEngineConfig(
        dtype="float32", param_dtype="float32", gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(
            type="adamw", lr=1e-2, weight_decay=0.0,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
        ),
    ))
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8),
        model_config=dataclasses.replace(tiny_config("qwen2"), num_layers=1),
        seed=0,
    )
    rng = np.random.default_rng(0)
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, size=n) for n in (9, 5)]
    )
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    eng.forward(batch)
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = [a for n, a in seen if n == name]
        assert attrs and all(a["rope_impl"] == "xla" for a in attrs), name
