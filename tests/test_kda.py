"""``ops/kda.py``: the gated delta rule in chunks against the rule token by
token (``benchmark/reference_kimi.kda_rule``), its hand-written backward
against XLA's differentiation of the plain chunked form, the state-pass
kernels in Pallas interpret mode against the ``lax.scan`` they are held
equal to, segment borders inside a chunk, decays near 0 and near 1; and the
operand kernels (``chunk_ops_fwd`` / ``chunk_ops_bwd``, interpret mode, at
the lane-tile widths they run at on the chip) against ``_prep`` and XLA's
differentiation of it, and the scalar-decay form's (``gdn_ops_fwd`` /
``gdn_ops_bwd``, with as many key heads as value heads and with fewer) against
``_prep_scalar`` and its. Every comparison runs as ONE jitted program a shape:
op by op the CPU compiles each primitive apart."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import kda

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import reference_kimi  # noqa: E402


def _inputs(t, seg_lens=None, h=2, dk=16, dv=8, decay=1.0, seed=0):
    r = np.random.default_rng(seed + t)
    q = r.standard_normal((1, t, h, dk)).astype(np.float32)
    k = r.standard_normal((1, t, h, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((1, t, h, dv)).astype(np.float32)
    g = -decay * np.abs(r.standard_normal((1, t, h, dk))).astype(np.float32)
    beta = (1 / (1 + np.exp(-r.standard_normal((1, t, h))))).astype(np.float32)
    seg = np.zeros((1, t), np.int32)
    at = 0
    for i, n in enumerate(seg_lens or [t]):
        seg[0, at:at + n] = i + 1
        at += n
    return [jnp.asarray(x) for x in (q, k, v, g, beta, seg)]


# ``hk`` of a form: None, the per-channel rule as ``_inputs`` draws it; else
# the scalar-decay one (``g`` one number a head and token) with ``q, k`` of
# ``hk`` heads, each serving ``h / hk`` value heads
FORMS = [
    pytest.param(None, id="per-channel"),
    pytest.param(2, id="scalar-decay"),
    pytest.param(1, id="scalar-decay-shared-keys"),
]


def _form(a, hk):
    if hk is None:
        return list(a)
    q, k, v, g, beta, seg = a
    return [q[:, :, :hk], k[:, :, :hk], v, g[..., 0], beta, seg]


def _rule(q, k, v, g, beta, seg):
    """The reference's rule, one sequence of the row after the other (a
    scalar-decay row as the per-channel one it is equal to)."""
    if kda._scalar_decay(g, beta):
        r = v.shape[2] // q.shape[2]
        q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))
        g = jnp.broadcast_to(g[..., None], q.shape)
    seg = np.asarray(seg[0])
    out = np.zeros(q.shape[1:3] + v.shape[-1:], np.float32)
    for s in np.unique(seg[seg > 0]):
        at = np.flatnonzero(seg == s)
        out[at] = reference_kimi.kda_rule(
            *(x[0, at[0]:at[-1] + 1] for x in (q, k, v, g, beta))
        )
    return out


@functools.lru_cache(None)
def _scan(impl):
    return jax.jit(functools.partial(kda.kda_scan, impl=impl, interpret=True))


CASES = [
    pytest.param(1, None, id="t1"),
    pytest.param(63, None, id="t63"),
    pytest.param(64, None, id="t64"),
    pytest.param(65, None, id="t65"),
    pytest.param(200, None, id="t200"),
    pytest.param(200, [70, 90], id="two-segments-and-padding"),
    pytest.param(200, [30, 45, 100], id="three-segments-borders-in-a-chunk"),
]


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("t,seg_lens", CASES)
def test_chunked_form_equals_the_rule_token_by_token(t, seg_lens, impl):
    a = _inputs(t, seg_lens)
    got, stats = _scan(impl)(*a)
    np.testing.assert_allclose(got[0], _rule(*a), atol=2e-6)
    real = sum(seg_lens or [t])
    assert [int(x) for x in stats] == [
        real, -(-t // kda.CHUNK) * kda.CHUNK, len(seg_lens or [t])
    ]
    assert not np.asarray(got[0, real:]).any()  # padding reads zero


def _loss(o):
    return (o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.1)).sum()


@pytest.mark.parametrize("t,seg_lens", [CASES[0], CASES[3], CASES[6]])
def test_hand_written_backward_equals_xlas_of_the_plain_chunked_form(
    t, seg_lens
):
    a = _inputs(t, seg_lens)

    def grads(**kw):
        return jax.jit(jax.grad(
            lambda *x: _loss(kda.kda_scan(*x, a[5], **kw)[0]),
            argnums=(0, 1, 2, 3, 4),
        ))(*a[:5])

    want = grads(impl="xla", custom_backward=False)
    rule = jax.jit(jax.grad(
        lambda *x: _loss(jnp.asarray(_rule_traced(*x, a[5]))),
        argnums=(0, 1, 2, 3, 4),
    ))(*a[:5])
    for name, kw in (("scan", dict(impl="xla")),
                     ("kernel", dict(impl="kernel", interpret=True))):
        for got, w, r in zip(grads(**kw), want, rule):
            scale = float(jnp.abs(w).max()) + 1e-9
            assert float(jnp.abs(got - w).max()) / scale < 1e-5, name
            assert float(jnp.abs(got - r).max()) / scale < 1e-4, name


def _rule_traced(q, k, v, g, beta, seg):
    """``_rule`` in traceable form: the state reset where the segment
    changes, for ``jax.grad`` of the token-by-token rule."""
    hh, dk = q.shape[2:]

    def step(carry, x):
        s, prev = carry
        q_t, k_t, v_t, g_t, b_t, seg_t = x
        s = jnp.where(seg_t != prev, 0.0, s) * jnp.exp(g_t)[:, :, None]
        read = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - read)[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t) * dk ** -0.5
        return (s, seg_t), jnp.where(seg_t > 0, o, 0.0)

    s0 = jnp.zeros((hh, dk, v.shape[-1]), jnp.float32)
    xs = tuple(x[0] for x in (q, k, v, g, beta, seg))
    return jax.lax.scan(step, (s0, jnp.int32(-1)), xs)[1][None]


@pytest.mark.parametrize("hk", FORMS)
@pytest.mark.parametrize("decay", [1e-4, 20.0, 80.0])
def test_decays_near_one_and_near_zero_neither_overflow_nor_clamp(decay, hk):
    """exp(g) from 0.9999 down to exp(-80 |n|), which underflows to an
    exact zero: every exponent the chunked form takes is a difference that
    is <= 0, so nothing overflows, and nothing is clamped: the result is
    the rule's."""
    a = _form(_inputs(130, decay=decay), hk)
    for impl in ("xla", "kernel"):
        got, _ = _scan(impl)(*a)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got[0], _rule(*a), atol=5e-6)
    grads = jax.jit(jax.grad(
        lambda *x: _loss(kda.kda_scan(*x, a[5], impl="xla")[0]),
        argnums=(0, 1, 2, 3, 4),
    ))(*a[:5])
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_a_long_row_runs_its_operands_in_blocks_of_chunks(monkeypatch):
    """More chunks than ``_PREP_CHUNKS`` go through ``_in_blocks``' map,
    forward and backward: the same numbers as in one piece."""
    a = _inputs(4 * kda.CHUNK, [100, 140], h=1, dk=8, dv=8)

    def run():  # a new jit a call: the block size is read while tracing
        return jax.jit(jax.value_and_grad(
            lambda *x: _loss(kda.kda_scan(*x, a[5], impl="xla")[0]),
            argnums=(0, 1, 2, 3, 4),
        ))(*a[:5])

    whole = run()
    monkeypatch.setattr(kda, "_PREP_CHUNKS", 2)
    blocked = run()
    for x, y in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(blocked)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_unit_lower_inverse_is_exact_where_powers_would_not_be():
    """Entries near 1 (neighbouring keys nearly parallel, beta near 1): the
    substitution stays at rounding error where the Neumann series' powers
    grow like binomials."""
    r = np.random.default_rng(0)
    n = np.tril(0.9 + 0.1 * r.random((kda.CHUNK, kda.CHUNK)), -1)
    inv = np.asarray(kda._unit_lower_inverse(jnp.asarray(n, jnp.float32)))
    want = np.linalg.inv(np.eye(kda.CHUNK) + n)
    assert np.abs(inv - want).max() < 1e-4 * np.abs(want).max()


def test_scan_impl_is_the_kernel_on_a_tpu_for_whole_lane_tiles(monkeypatch):
    assert kda.scan_impl(128, 128) == "xla"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.scan_impl(128, 128) == "kernel"
    assert kda.scan_impl(16, 16) == "xla"  # a test-sized head
    assert kda.scan_impl() == "kernel"


def test_the_op_runs_per_chip_on_its_own_rows_under_an_fsdp_mesh():
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.parallel import mesh as mesh_lib

    n = min(4, jax.device_count())
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=n))
    rows = [_inputs(70, [30, 25], seed=i) for i in range(n)]
    a = [jnp.concatenate(x, axis=0) for x in zip(*rows)]
    want, want_stats = kda.kda_scan(*a)
    got, stats = jax.jit(kda.make_kda_scan(mesh) or kda.kda_scan)(*a)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert [int(x) for x in stats] == [int(x) for x in want_stats]
    assert kda.make_kda_scan(mesh_lib.make_mesh(ParallelismConfig())) is None
    if n > 1:
        seq = mesh_lib.make_mesh(ParallelismConfig(seq_parallel_size=n))
        with pytest.raises(ValueError, match="no sequence-parallel form"):
            kda.make_kda_scan(seq)


# ------------------------------------------- the operands as kernels --
# One row of three chunks, two heads of 128 | 128 (whole lane tiles, as on
# the chip), every case at that one shape: a program a comparison, compiled
# once. (t, segment lengths, decay): what the row holds.
_T = 3 * kda.CHUNK
OPERAND_CASES = [
    pytest.param(_T, None, 1.0, id="one-sequence"),
    # ends inside a chunk (40), at a chunk border (64), at a sub-block
    # border (80), and the row's end
    pytest.param(_T, [40, 24, 16, 112], 1.0, id="segment-borders"),
    pytest.param(_T, [70, 50], 1.0, id="trailing-padding"),
    pytest.param(_T - 20, [100, 72], 1.0, id="no-whole-number-of-chunks"),
    # exp(g) near exp(-20) a token: G_i - G_j over a chunk is -1,000 and
    # more, which only differences survive
    pytest.param(_T, [150, 42], 20.0, id="strong-decay"),
]


def _chunked_inputs(t, seg_lens, decay, hk=None):
    """The row as ``kda_scan`` hands it to the op."""
    return list(kda._chunk_inputs(
        *_form(_inputs(t, seg_lens, dk=128, dv=128, decay=decay), hk)
    ))


def _cotangents(a):
    r = np.random.default_rng(7)
    return tuple(
        jnp.asarray(r.standard_normal(o.shape).astype(np.float32))
        for o in jax.eval_shape(_prep, *a)
    )


def _close(got, want, tol, decay=1.0):
    """Within ``tol`` of the largest entry; a running sum ``G`` of size
    ``decay`` x 64 rounds at ``decay`` times the float32 step, in the
    kernel's order of summation and in ``cumsum``'s alike."""
    tol *= max(1.0, decay)
    for x, w in zip(got, want):
        assert np.isfinite(np.asarray(x)).all()
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(x - w).max()) / scale < tol


def _xla_prep(a):
    """The form's XLA operands: ``_prep_scalar`` where ``g`` has one number
    a head and token."""
    return kda._prep_scalar if kda._scalar_decay(a[3], a[4]) else kda._prep


@jax.jit
def _prep(*a):
    return _xla_prep(a)(*a, kda._seg_in(a[5]))


@jax.jit
def _prep_vjp(a, cts):
    return kda._operands_bwd_xla(*a, cts, prep=_xla_prep(a))


@pytest.mark.parametrize("hk", FORMS)
@pytest.mark.parametrize("t,seg_lens,decay", OPERAND_CASES)
def test_operand_kernel_equals_prep(t, seg_lens, decay, hk):
    """``chunk_ops_fwd`` | ``gdn_ops_fwd``, operand by operand."""
    a = _chunked_inputs(t, seg_lens, decay, hk)
    _close(kda._operands(*a, "kernel", True), _prep(*a), 1e-5, decay)


@pytest.mark.parametrize("hk", FORMS)
@pytest.mark.parametrize("t,seg_lens,decay", OPERAND_CASES)
def test_operand_cotangent_kernel_equals_xlas_vjp_of_prep(
    t, seg_lens, decay, hk
):
    """The six cotangents' pull-back to q, k, v, g, beta: ``chunk_ops_bwd``
    | ``gdn_ops_bwd`` (``dq, dk`` a key head, its value heads' summed)."""
    a = _chunked_inputs(t, seg_lens, decay, hk)
    cts = _cotangents(a)
    if hk is None:
        got = kda._operands_bwd_kernel(*a, cts, interpret=True)
    else:
        got = kda._gdn_operands_bwd_kernel(*a, cts, interpret=True)
    _close(got, _prep_vjp(a, cts), 2e-5, decay)


@functools.lru_cache(None)
def _op_grads(t, kernels, hk):  # ``hk``: a program a form (its shapes)
    kw = (
        dict(impl="kernel", interpret=True) if kernels
        else dict(impl="xla", custom_backward=False)
    )
    return jax.jit(jax.grad(
        lambda *x: _loss(kda.kda_scan(*x, **kw)[0]), argnums=(0, 1, 2, 3, 4)
    ))


@pytest.mark.parametrize("hk", FORMS)
@pytest.mark.parametrize("t,seg_lens,decay", OPERAND_CASES)
def test_whole_op_gradients_with_the_kernels_equal_the_plain_chunked_forms(
    t, seg_lens, decay, hk
):
    """``kda_scan`` with all five kernels, its backward by hand, against
    ``_chunked_plain`` with XLA's differentiation throughout."""
    a = _form(_inputs(t, seg_lens, dk=128, dv=128, decay=decay), hk)
    want = _op_grads(t, False, hk)(*a)
    _close(_op_grads(t, True, hk)(*a), want, 2e-5, decay)


@pytest.mark.parametrize("hk,backward,wrappers", [
    pytest.param(None, False, {"_operands_kernel", "_state_fwd_kernel"},
                 id="per-channel"),
    pytest.param(
        1, True,
        {"_gdn_operands_kernel", "_gdn_operands_bwd_kernel",
         "_state_fwd_kernel", "_state_bwd_kernel"},
        id="scalar-decay-and-its-backward",
    ),
])
def test_kernel_wrappers_are_nested_beneath_the_program_that_traces_them(
    hk, backward, wrappers
):
    """Each kernel call sits behind a jitted function (PR 35), whose trace
    fires inside the trace of the program that calls it: the compile log
    lists it beneath that program and counts its seconds once."""
    from areal_tpu.utils import goodput

    # a shape no other test of this file traces
    a = _form(_inputs(130, h=3), hk)
    tracker = goodput.CompileTracker()
    goodput.set_thread_tracker(tracker, phase="trainer")
    try:
        def scan_program(*x):
            def run(*y):
                return kda.kda_scan(*y, x[5], impl="kernel", interpret=True)[0]

            if not backward:
                return run(*x[:5])
            return jax.grad(lambda *y: run(*y).sum(), argnums=range(5))(*x[:5])

        jax.block_until_ready(jax.jit(scan_program)(*a))
    finally:
        goodput.set_thread_tracker(None)
    table = {r["fun_name"]: r for r in tracker.program_table()}
    assert not wrappers & set(table)  # no rows of their own
    nested = {n["fun_name"]: n for n in table["scan_program"]["nested"]
              if n["kind"] == "trace"}
    assert wrappers <= set(nested)
    assert all(nested[w]["seconds"] <= table["scan_program"]["trace_s"]
               for w in wrappers)
    with tracker._lock:
        log = list(tracker._log)
    every_trace = sum(e.seconds for e in log if e.kind == "trace")
    secs = tracker.phase_seconds()
    assert secs["trace"] < every_trace  # the wrappers' seconds once
    # every second once: the four kinds sum to the outermost intervals
    assert sum(secs.values()) == pytest.approx(
        sum(e.seconds for e in log if not e.nested)
    )
