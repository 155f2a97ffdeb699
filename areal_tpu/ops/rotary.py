"""The rotation in one pass: a Pallas kernel that reads q (or k) as the
projection wrote it and writes it rotated, float32 only in registers
(PERF.md, PR 51).

``ops/basic.py``'s ``rotate_leading`` is ``astype(float32)``, ``split``, four
products, ``concatenate``, ``astype``: one rounding at the end, and five to
seven trips through HBM as the compiler fuses it (the widened q written for
the two halves to read, the concatenate as ``pad`` + ``maximum``, a
two-output fusion, a token-minor layout the attention core relays). Nothing
in the arithmetic needs them. Here:

- the kernel works on the 2-D view ``[tokens, heads * D]`` (a reshape of the
  projection's output, no copy): a block of ``BLOCK_TOKENS`` tokens by a whole
  number of heads, the tables' ``[BLOCK_TOKENS, R]`` block of those tokens
  laid once a grid step over a head's lanes, ``C = [c, c, 1..]`` and
  ``S = [-s, s, 0..]``, and used by every head of the block;
- a head's rotate-half over its first 2R lanes is ``x * C + partner * S``
  with ``partner[l] = x[l + R]`` in the first R lanes and ``x[l - R]`` in the
  next R: two lane rolls and a select (ONE roll for a whole head, 2R = D).
  Channels past 2R meet ``C = 1``, ``S = 0`` and pass. The same products and
  the same single rounding to ``x``'s dtype as ``rotate_leading``;
- the map is linear in ``x`` and its transpose is the rotation by the
  opposite angle: the backward is the same kernel on the cotangent with ``S``
  negated, keeps nothing but the tables, and writes the cotangent in ``x``'s
  dtype in one pass;
- ``norm=(weight, eps)``: a model that norms q and k a head before the
  rotation (Mellum, Keye) hands the norm over and the kernel reads the
  projection's output itself: ``rms_norm``'s products, its rounding to
  ``x``'s dtype, then the rotation. Left to XLA the norm is computed with
  tokens minor (it will not reduce over lanes) and a Mosaic call takes
  row-major operands, so the operand came through a float32 relayout that
  cost more than the rotation saved (PERF.md, PR 51). Its backward
  (``rope_norm_rotate_t``) turns the cotangent back, rounds it as the normed
  head was rounded, and goes through the norm from the kept ``x``: ``dx``,
  and a block's part of the weight's cotangent, summed outside.

``rotate`` is the entry, and ``apply_rope`` / ``rotate_leading`` /
``apply_mrope`` are ``ops/basic.py``'s by name and by numbers: what
``models/transformer.py`` calls. Which form runs is read off the call
(``rotate_impl``): the kernel on a TPU, in a process that holds one device,
for heads of whole lane tiles on rows of whole token blocks; the ``jnp`` form
everywhere else (64-wide heads, a decode step's one token, the CPU, and a
mesh, whose partitioner cannot split a Mosaic call). One algorithm and one
set of tables, two ways to stream them.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops import basic

_LANES = 128

# Tokens and heads a block. Device ms a pass of q ``[16384, 64 x 128]`` on the
# v5e (``tools/microbench_rope.py``; PERF.md, PR 51), 545 MB moved: 512 x 8
# 0.815 (669 GB/s), 1024 x 8 0.814, 256 x 8 0.822, 1024 x 4 0.820, 512 x 4
# 0.887, 2048 x 2 0.861; the ``jnp`` form standing alone 6.96.
BLOCK_TOKENS = 512
BLOCK_HEADS = 8


def rotate_impl(d: int, t: Optional[int] = None) -> str:
    """The form of the rotation this process runs for heads ``d`` wide (0: a
    model that rotates nothing) on rows of ``t`` tokens (None: on the
    trainer's rows, whole ``data.ROW_QUANTUM``s and so whole blocks):
    ``"kernel"`` on a TPU where the process holds ONE device, for heads of
    whole lane tiles on rows of whole ``BLOCK_TOKENS``; ``"xla"``
    (``ops/basic.py``) elsewhere: 64-wide heads, a decode step's one token,
    the CPU, and a mesh, whose partitioner cannot split a Mosaic call (the
    rotation is under 1% of the four-chip cell's window)."""
    if jax.default_backend() != "tpu" or jax.device_count() != 1:
        return "xla"
    if not d or d % _LANES or (t or 0) % BLOCK_TOKENS:
        return "xla"
    return "kernel"


def _wide_tables(cos_ref, sin_ref, d: int, transposed: bool):
    """The tables' block [bq, R] laid over a head's ``d`` lanes, once a grid
    step: ``C = [c, c, 1..]``, ``S = [-s, s, 0..]`` (``transposed``: the
    rotation by the opposite angle)."""
    c, s = cos_ref[...], sin_ref[...]
    bq, r = c.shape
    if transposed:
        s = -s
    cs, ss = [c, c], [-s, s]
    if d > 2 * r:
        cs.append(jnp.ones((bq, d - 2 * r), jnp.float32))
        ss.append(jnp.zeros((bq, d - 2 * r), jnp.float32))
    return jnp.concatenate(cs, axis=1), jnp.concatenate(ss, axis=1)


def _turned(x, cw, sw, r: int):
    """One head ``x`` [bq, d] float32 rotated by the wide tables."""
    bq, d = x.shape
    partner = pltpu.roll(x, d - r, 1)  # lane l reads lane l + R
    if d > 2 * r:  # lanes R..2R read lane l - R
        first = jax.lax.broadcasted_iota(jnp.int32, (bq, d), 1) < r
        partner = jnp.where(first, partner, pltpu.roll(x, r, 1))
    return x * cw + partner * sw


def _head_lanes(h, d: int):
    return pl.ds(pl.multiple_of(h * d, _LANES), d)


def _unit(x, eps: float):
    """(x / rms(x), 1 / rms(x)) of one head's rows, float32."""
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
    return x * rstd, rstd


def _kernel(*refs, heads: int, d: int, transposed: bool, eps):
    """A block of ``heads`` heads rotated (``eps`` not None: each head
    rms-normed by the weight row first, rounded as ``basic.rms_norm``
    rounds)."""
    if eps is None:
        x_ref, cos_ref, sin_ref, o_ref = refs
    else:
        x_ref, cos_ref, sin_ref, w_ref, o_ref = refs
    cw, sw = _wide_tables(cos_ref, sin_ref, d, transposed)
    r = cos_ref.shape[1]

    def head(h, carry):  # a loop, not ``heads`` copies of the body to trace
        lanes = _head_lanes(h, d)
        x = x_ref[:, lanes].astype(jnp.float32)
        if eps is not None:
            y = _unit(x, eps)[0] * w_ref[...]
            x = y.astype(o_ref.dtype).astype(jnp.float32)
        o_ref[:, lanes] = _turned(x, cw, sw, r).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, None)


def _norm_bwd_kernel(g_ref, x_ref, cos_ref, sin_ref, w_ref, dx_ref, dw_ref,
                     *, heads: int, d: int, eps: float):
    """The transpose of norm-then-rotate on a block: the cotangent turned
    back and rounded as the normed head was, then through the norm (from the
    projection's ``x``, kept instead of anything float32): ``dx``, and this
    block's part of the weight's cotangent, its token rows folded onto 8."""
    cw, sw = _wide_tables(cos_ref, sin_ref, d, True)
    r = cos_ref.shape[1]
    w = w_ref[...]

    def head(h, dw):
        lanes = _head_lanes(h, d)
        gy = _turned(g_ref[:, lanes].astype(jnp.float32), cw, sw, r)
        gy = gy.astype(dx_ref.dtype).astype(jnp.float32)
        xhat, rstd = _unit(x_ref[:, lanes].astype(jnp.float32), eps)
        dxhat = gy * w
        along = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
        dx_ref[:, lanes] = (rstd * (dxhat - xhat * along)).astype(
            dx_ref.dtype
        )
        return dw + (gy * xhat).reshape(-1, 8, d).sum(axis=0)

    dw_ref[...] = jax.lax.fori_loop(
        0, heads, head, jnp.zeros((8, d), jnp.float32)
    )


def _blocks(n: int, width: int, d: int, r: int):
    """(grid, x's block, the tables' block, heads a block): heads inside, so
    that the tables' block stays where it is across them."""
    heads = width // d
    hb = max(g for g in range(1, BLOCK_HEADS + 1) if heads % g == 0)
    return (
        (n // BLOCK_TOKENS, heads // hb),
        pl.BlockSpec((BLOCK_TOKENS, hb * d), lambda i, j: (i, j)),
        pl.BlockSpec((BLOCK_TOKENS, r), lambda i, j: (i, 0)),
        hb,
    )


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel")
)


@functools.partial(
    jax.jit, static_argnames=("d", "transposed", "eps", "interpret")
)
def _rotate_rows(x, cos_t, sin_t, w=None, *, d: int, transposed: bool,
                 eps=None, interpret: bool):
    """``x`` [N, heads * d] rotated by the tables [N, R] (``w`` [1, d]
    float32 and ``eps``: normed a head first); N a whole number of
    ``BLOCK_TOKENS``. Behind a ``jit`` so that the kernel's body is traced
    once a shape, not once a call site."""
    n, width = x.shape
    grid, block, table, hb = _blocks(n, width, d, cos_t.shape[1])
    normed = () if eps is None else (w,)
    return pl.pallas_call(
        functools.partial(
            _kernel, heads=hb, d=d, transposed=transposed, eps=eps
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[block, table, table]
        + [pl.BlockSpec((1, d), lambda i, j: (0, 0))] * len(normed),
        out_specs=block,
        compiler_params=_PARALLEL,
        name="rope_rotate_t" if transposed else "rope_rotate",
        interpret=interpret,
    )(x, cos_t, sin_t, *normed)


@functools.partial(jax.jit, static_argnames=("d", "eps", "interpret"))
def _norm_bwd_rows(g, x, cos_t, sin_t, w, *, d: int, eps: float,
                   interpret: bool):
    """(dx [N, heads * d], the weight's cotangent [d] float32)."""
    n, width = x.shape
    grid, block, table, hb = _blocks(n, width, d, cos_t.shape[1])
    dx, dw = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, heads=hb, d=d, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((grid[0] * grid[1] * 8, d), jnp.float32),
        ),
        grid=grid,
        in_specs=[block, block, table, table,
                  pl.BlockSpec((1, d), lambda i, j: (0, 0))],
        out_specs=(
            block,
            pl.BlockSpec((8, d), lambda i, j: (i * grid[1] + j, 0)),
        ),
        compiler_params=_PARALLEL,
        name="rope_norm_rotate_t",
        interpret=interpret,
    )(g, x, cos_t, sin_t, w)
    return dx, dw.sum(axis=0)


def _rows(x, cos_t, sin_t):
    """The 2-D views the kernels work on (reshapes, no copy)."""
    r = cos_t.shape[-1]
    return (
        x.reshape(-1, x.shape[2] * x.shape[3]),
        cos_t.astype(jnp.float32).reshape(-1, r),
        sin_t.astype(jnp.float32).reshape(-1, r),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_kernel(x, cos_t, sin_t, interpret):
    return _rotated(x, cos_t, sin_t, False, interpret)


def _rotated(x, cos_t, sin_t, transposed, interpret):
    return _rotate_rows(
        *_rows(x, cos_t, sin_t), d=x.shape[3], transposed=transposed,
        interpret=interpret,
    ).reshape(x.shape)


def _rotate_fwd(x, cos_t, sin_t, interpret):
    return _rotated(x, cos_t, sin_t, False, interpret), (cos_t, sin_t)


def _rotate_bwd(interpret, tables, g):
    cos_t, sin_t = tables
    # the tables are functions of integer positions: no cotangent is formed
    # for them (x would have to be kept for it), and none flows anywhere
    return (
        _rotated(g, cos_t, sin_t, True, interpret),
        jnp.zeros_like(cos_t), jnp.zeros_like(sin_t),
    )


_rotate_kernel.defvjp(_rotate_fwd, _rotate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _norm_rotate_kernel(x, weight, cos_t, sin_t, eps, interpret):
    return _norm_rotate_fwd(x, weight, cos_t, sin_t, eps, interpret)[0]


def _norm_rotate_fwd(x, weight, cos_t, sin_t, eps, interpret):
    w = weight.astype(jnp.float32).reshape(1, -1)
    out = _rotate_rows(
        *_rows(x, cos_t, sin_t), w, d=x.shape[3], transposed=False, eps=eps,
        interpret=interpret,
    ).reshape(x.shape)
    return out, (x, weight, cos_t, sin_t)


def _norm_rotate_bwd(eps, interpret, kept, g):
    x, weight, cos_t, sin_t = kept
    dx, dw = _norm_bwd_rows(
        g.reshape(-1, x.shape[2] * x.shape[3]), *_rows(x, cos_t, sin_t),
        weight.astype(jnp.float32).reshape(1, -1), d=x.shape[3], eps=eps,
        interpret=interpret,
    )
    return (
        dx.reshape(x.shape), dw.astype(weight.dtype),
        jnp.zeros_like(cos_t), jnp.zeros_like(sin_t),
    )


_norm_rotate_kernel.defvjp(_norm_rotate_fwd, _norm_rotate_bwd)


def rotate(
    x: jnp.ndarray, cos_t: jnp.ndarray, sin_t: jnp.ndarray,
    norm: Optional[Tuple[jnp.ndarray, float]] = None,
) -> jnp.ndarray:
    """``x`` [B, T, H, D] with the first 2R channels of every head rotated by
    the per-token tables ``cos_t`` / ``sin_t`` [B, T, R], float32
    (``basic.rope_at``, a table's rows ``cos[positions]``, or
    ``basic.mrope_tables``); ``norm`` (weight [D], eps): every head
    rms-normed first, ``basic.rms_norm``'s numbers and its rounding to
    ``x``'s dtype between the two. ``basic.rotate_leading``'s numbers,
    through the kernel where ``rotate_impl`` says so (a test's
    ``"interpret"``: the kernel in Pallas interpret mode, off the chip): one
    pass there, the norm's too (the backward keeps ``x`` and forms the
    weight's cotangent beside ``dx``)."""
    impl = _impl_for(x)
    if impl == "xla":
        if norm is not None:
            x = basic.rms_norm(x, *norm)
        return basic.rotate_leading(x, cos_t, sin_t)
    if norm is None:
        return _rotate_kernel(x, cos_t, sin_t, impl == "interpret")
    return _norm_rotate_kernel(
        x, norm[0], cos_t, sin_t, float(norm[1]), impl == "interpret"
    )


def _impl_for(x: jnp.ndarray) -> str:
    if x.ndim != 4:
        return "xla"
    return rotate_impl(x.shape[3], x.shape[1])


rotate_leading = rotate  # ``basic.rotate_leading`` by name


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, cos: jnp.ndarray,
    sin: jnp.ndarray, norm=None,
) -> jnp.ndarray:
    """``basic.apply_rope``, which IS ``rotate_leading(x, cos[positions],
    sin[positions])`` over a whole head: the kernel at the table's rows where
    it takes the shape; the plain form as it stands elsewhere (its lowered
    text is the dense and the four-chip cells' programs)."""
    if _impl_for(x) == "xla":
        if norm is not None:
            x = basic.rms_norm(x, *norm)
        return basic.apply_rope(x, positions, cos, sin)
    return rotate(x, cos[positions], sin[positions], norm)


def apply_mrope(
    x: jnp.ndarray, positions3: jnp.ndarray, cos: jnp.ndarray,
    sin: jnp.ndarray, sections: Tuple[int, ...], norm=None,
) -> jnp.ndarray:
    """``basic.apply_mrope``: ``rotate`` at the section-mixed tables."""
    return rotate(
        x, *basic.mrope_tables(positions3, cos, sin, sections), norm
    )
