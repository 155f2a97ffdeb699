"""The delta-rule mixers' short convolution in one pass: a Pallas kernel pair
that reads q, k, v as the projection stored them and writes them convolved,
activated and normed a head in the same dtype, float32 only in VMEM (PERF.md,
PR 55).

``models/transformer._short_conv`` with ``silu`` and the head l2norm behind it
is ``astype(float32)``, a ``pad``, a shifted slice, a ``where`` and a
multiply-add for each earlier tap, the activation, a reduction over a head,
``astype``: XLA runs it as many passes over float32 ``[T, C]`` arrays (the
widened input alone is written once and read four times), and its backward as
the ``pad``s' transposes and four reductions over the tokens. Nothing in the
arithmetic needs HBM between the projection's output and the scan's input.
Here:

- ``conv_prep_fwd``: a block is ``BLOCK_TOKENS`` tokens by a whole number of
  128-lane heads, walked a head at a time. The ``taps - 1`` tokens before the
  block are in a second view of ``x`` (the previous block's last 16 rows, a
  bf16 tile), so a sequence that crosses a block's border convolves across
  it. A tap's shifted copy is a sublane roll of the head's rows with those 16
  laid behind them. Which taps a token takes (``_short_conv``'s border rule:
  the earlier token is of this token's sequence, and this token is not
  padding) is a bit each in one int32 a token, made from the segment ids
  outside and laid over the lanes. Every sum in float32, ``silu``, for the
  first ``norm_channels`` channels ``y * rsqrt(sum y^2 + 1e-6)`` over the
  head's lanes, ONE rounding to ``x``'s dtype.
- ``conv_prep_bwd``: the ``custom_vjp`` keeps the INPUTS (``x``, ``w``, the
  segment ids). A block re-forms ``y`` on its rows and the 16 below them (a
  third view of ``x`` and a second of the cotangent), takes the cotangent
  back through the norm and the activation, and writes ``dx`` once: token t
  takes ``w[:, K-1-s] * dy[t + s]`` from the later tokens that took it.
  ``dw`` is summed in a float32 accumulator that stays in VMEM over the whole
  grid (``[taps * 8, C]``: a block's token rows folded onto 8 sublanes),
  written once and folded outside.

Which form runs is read off the call (``prep_impl``), as ``ops/kda.scan_impl``
and ``ops/rotary.rotate_impl`` are: the kernels on a TPU, in a process that
holds one device, for 128-wide heads, channels and normed channels of whole
heads and rows of whole token blocks; ``"xla"`` elsewhere (the CPU, a test's
narrow heads, a mesh, whose partitioner cannot split a Mosaic call), where the
mixers run their ``jax.numpy`` lines as they stand. ``_short_conv`` is this
file's reference in tests, and ZAYA's ``cca_qkv`` keeps it: two taps with a
bias, no activation and a float32 result that feeds a second, matrix-tap
convolution — another need, and no switch here serves it.

The kernels' names do not begin with ``kda_``: the benchmark's roofline of
the state pass reads that prefix. Device scope ``kda_prep``, the callers'.

What it costs on the v5e: ``BLOCK_TOKENS``' table below.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # a head: the l2norm's reduction is one lane tile's
_HALO = 16  # rows of a neighbouring view: a bf16 tile's sublanes
_EPS = 1e-6

# Tokens a block and channels a block (at most). Host ms a pass, forward |
# backward, of one packed row ``[16384, 8192]`` with 4,096 channels normed
# (Qwen3-Next's call; ``python -m tools.microbench_kda --prep``, best of
# three, my chip runs, PR 55, the tree as committed), tokens x channels:
# 256 x 1024 2.11 | 3.56, **512 x 1024 1.99 | 3.39**, 1024 x 1024 1.99 | 3.40,
# 512 x 512 2.07 | 3.53, 512 x 2048 2.04 | 3.49 (2048 x 1024 1.98 | 3.41
# before the last clean-up): flat from 512 tokens on (a head's values are
# past the registers at 64 tokens already, and VMEM serves them; the
# arithmetic binds, about 50 and 95 VPU operations a register of elements);
# the ``jax.numpy`` form standing alone 11.41 | 29.90. The host's clock has a
# floor near 0.8 ms a call there: in the traced cell (PERF.md section 5) the
# same calls are 1.317 | 2.207 ms of device time, 0.54 GB in and out at 408
# GB/s, and Kimi's ``[8192, 4096]`` calls 0.354 | 0.590. A version that
# walked a block in strips of 64 tokens to keep a strip in registers read
# 3.59 | 6.12 (128: 2.69 | 4.26): a loop trip's fixed cost (the neighbouring
# rows' selects, the rolls' set-up) was a tenth of a microsecond, as much as
# a strip's arithmetic.
BLOCK_TOKENS = 512
BLOCK_CHANNELS = 1024


def prep_impl(
    channels: int, norm_channels: int, head: int, t: Optional[int] = None,
    taps: int = 4,
) -> str:
    """The form of convolution + ``silu`` + head l2norm this process runs for
    ``channels`` of which the first ``norm_channels`` are normed over heads
    ``head`` wide, on rows of ``t`` tokens (None: the trainer's rows, whole
    ``data.ROW_QUANTUM``s and so whole blocks): ``"kernel"`` on a TPU where
    the process holds ONE device, for 128-wide heads and whole heads of
    channels; ``"xla"`` (the mixers' ``jax.numpy`` lines) elsewhere."""
    if jax.default_backend() != "tpu" or jax.device_count() != 1:
        return "xla"
    if head != _LANES or channels % _LANES or norm_channels % _LANES:
        return "xla"
    if (t or 0) % BLOCK_TOKENS or not 1 < taps <= _HALO:
        return "xla"
    return "kernel"


def tap_bits(segment_ids: jnp.ndarray, taps: int) -> jnp.ndarray:
    """[B, T, 128] int32, a token's number laid over the lanes: bit s - 1 says
    that token t takes tap s, the token s places back (``_short_conv``'s
    rule: that token is of t's sequence and t is not padding)."""
    bits = jnp.zeros_like(segment_ids, dtype=jnp.int32)
    for s in range(1, taps):
        seg_back = jnp.pad(segment_ids, ((0, 0), (s, 0)))[:, :-s]
        same = (seg_back == segment_ids) & (segment_ids > 0)
        bits = bits | (same.astype(jnp.int32) << (s - 1))
    return jnp.broadcast_to(bits[..., None], bits.shape + (_LANES,))


def _sigmoid(y):
    return 1.0 / (1.0 + jnp.exp(-y))


def _conv(xq, xe, bits, w_ref, lanes, taps: int):
    """(y, the masked shifted copies) on ``xq``'s rows: ``xe`` is ``xq`` with
    the 16 rows above it laid BEHIND it, so that a roll by s brings row p - s
    to row p and the rows above to the first s."""
    rows = xq.shape[0]
    y = xq * w_ref[pl.ds(taps - 1, 1), lanes]
    backs = []
    for s in range(1, taps):
        back = jnp.where(
            (bits & (1 << (s - 1))) != 0, pltpu.roll(xe, s, 0)[:rows], 0.0
        )
        y = y + back * w_ref[pl.ds(taps - 1 - s, 1), lanes]
        backs.append(back)
    return y, backs


def _normed(j, h, cb: int, norm_channels: int, channels: int):
    """None: no head of this call is normed; True: every one; else the
    traced answer for head ``h`` of channel block ``j``."""
    if norm_channels == 0:
        return None
    if norm_channels == channels:
        return True
    return j * cb + h * _LANES < norm_channels


def _fwd_kernel(x_ref, tail_ref, bits_ref, w_ref, o_ref, *, taps: int,
                norm_channels: int, channels: int):
    cb = x_ref.shape[1]
    j = pl.program_id(2)
    bits = bits_ref[...]

    def head(h, carry):  # a loop, not a block's heads as copies of the body
        lanes = pl.ds(pl.multiple_of(h * _LANES, _LANES), _LANES)
        x = x_ref[:, lanes].astype(jnp.float32)
        above = tail_ref[:, lanes].astype(jnp.float32)
        y, _ = _conv(
            x, jnp.concatenate([x, above], axis=0), bits, w_ref, lanes, taps
        )
        a = y * _sigmoid(y)
        normed = _normed(j, h, cb, norm_channels, channels)
        if normed is not None:
            rstd = jax.lax.rsqrt(
                jnp.sum(a * a, axis=1, keepdims=True) + _EPS
            )
            if normed is not True:
                rstd = jnp.where(normed, rstd, 1.0)
            a = a * rstd
        o_ref[:, lanes] = a.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, cb // _LANES, head, None)


def _bwd_kernel(x_ref, tail_ref, head_ref, g_ref, g_head_ref, bits_ref,
                bits_head_ref, w_ref, dx_ref, dw_ref, *, taps: int,
                norm_channels: int, channels: int):
    bt, cb = x_ref.shape
    rows = bt + _HALO  # the block and the 16 rows below it: their ``dy``
    b, i, j = (pl.program_id(a) for a in range(3))

    @pl.when((b == 0) & (i == 0) & (j == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # past the row's end nothing took anything
    bits = jnp.concatenate([
        bits_ref[...],
        jnp.where(i == pl.num_programs(1) - 1, 0, bits_head_ref[...]),
    ], axis=0)

    def head(h, carry):
        lanes = pl.ds(pl.multiple_of(h * _LANES, _LANES), _LANES)
        xq = jnp.concatenate(
            [x_ref[:, lanes], head_ref[:, lanes]], axis=0
        ).astype(jnp.float32)
        above = tail_ref[:, lanes].astype(jnp.float32)
        g = jnp.concatenate(
            [g_ref[:, lanes], g_head_ref[:, lanes]], axis=0
        ).astype(jnp.float32)
        y, backs = _conv(
            xq, jnp.concatenate([xq, above], axis=0), bits, w_ref, lanes, taps
        )
        sig = _sigmoid(y)
        a = y * sig
        normed = _normed(j, h, cb, norm_channels, channels)
        if normed is None:
            da = g
        else:
            rstd = jax.lax.rsqrt(
                jnp.sum(a * a, axis=1, keepdims=True) + _EPS
            )
            n = a * rstd
            along = jnp.sum(g * n, axis=1, keepdims=True)
            if normed is not True:
                rstd = jnp.where(normed, rstd, 1.0)
                along = jnp.where(normed, along, 0.0)
            da = rstd * (g - n * along)
        dy = da * (sig * (1.0 + y * (1.0 - sig)))
        cols = pl.ds(pl.multiple_of(j * cb + h * _LANES, _LANES), _LANES)

        def fold(k, prod):  # the block's rows onto 8 sublanes
            acc = pl.ds(k * 8, 8)
            dw_ref[acc, cols] += prod[:bt].reshape(-1, 8, _LANES).sum(0)

        fold(taps - 1, dy * xq)
        dx = dy[:bt] * w_ref[pl.ds(taps - 1, 1), lanes]
        for s in range(1, taps):
            fold(taps - 1 - s, dy * backs[s - 1])
            took = jnp.where((bits & (1 << (s - 1))) != 0, dy, 0.0)
            dx = dx + pltpu.roll(took, rows - s, 0)[:bt] * w_ref[
                pl.ds(taps - 1 - s, 1), lanes
            ]
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, cb // _LANES, head, None)


def _blocks(t: int, c: int):
    """(token blocks, channel blocks, channels a block)."""
    cb = max(
        m for m in range(_LANES, min(c, BLOCK_CHANNELS) + 1, _LANES)
        if c % m == 0
    )
    return t // BLOCK_TOKENS, c // cb, cb


def _specs(t: int, cb: int):
    """The block of a [B, T, C] array, the 16 rows above it and the 16 below
    (clamped at the row's ends, where the bits say nothing is taken), and the
    bits' block [B, T, 128], channels innermost so that it stays in place."""
    per, units = BLOCK_TOKENS // _HALO, t // _HALO
    block = pl.BlockSpec((None, BLOCK_TOKENS, cb), lambda b, i, j: (b, i, j))
    above = pl.BlockSpec(
        (None, _HALO, cb),
        lambda b, i, j: (b, jnp.maximum(i * per - 1, 0), j),
    )
    below = pl.BlockSpec(
        (None, _HALO, cb),
        lambda b, i, j: (b, jnp.minimum((i + 1) * per, units - 1), j),
    )
    bits = pl.BlockSpec(
        (None, BLOCK_TOKENS, _LANES), lambda b, i, j: (b, i, 0)
    )
    bits_below = pl.BlockSpec(
        (None, _HALO, _LANES),
        lambda b, i, j: (b, jnp.minimum((i + 1) * per, units - 1), 0),
    )
    return block, above, below, bits, bits_below


def _semantics(kind: str):
    return pltpu.CompilerParams(dimension_semantics=(kind,) * 3)


@functools.partial(
    jax.jit, static_argnames=("norm_channels", "interpret")
)
def _fwd_rows(x, w, segment_ids, *, norm_channels: int, interpret: bool):
    """Behind a ``jit`` so that a program lowers the kernel's body once a
    shape, not once a call site (``ops/kda.py``'s docstring)."""
    b, t, c = x.shape
    taps = w.shape[1]
    nt, nc, cb = _blocks(t, c)
    block, above, _, bits, _ = _specs(t, cb)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, taps=taps, norm_channels=norm_channels, channels=c,
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(b, nt, nc),
        in_specs=[
            block, above, bits,
            pl.BlockSpec((taps, cb), lambda b, i, j: (0, j)),
        ],
        out_specs=block,
        compiler_params=_semantics("parallel"),
        name="conv_prep_fwd",
        interpret=interpret,
    )(x, x, tap_bits(segment_ids, taps), w.astype(jnp.float32).T)


@functools.partial(
    jax.jit, static_argnames=("norm_channels", "interpret")
)
def _bwd_rows(x, w, segment_ids, g, *, norm_channels: int, interpret: bool):
    """(dx [B, T, C] in ``x``'s dtype, dw [C, taps] float32)."""
    b, t, c = x.shape
    taps = w.shape[1]
    nt, nc, cb = _blocks(t, c)
    block, above, below, bits, bits_below = _specs(t, cb)
    bits_all = tap_bits(segment_ids, taps)
    dx, dw = pl.pallas_call(
        functools.partial(
            _bwd_kernel, taps=taps, norm_channels=norm_channels, channels=c,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((taps * 8, c), jnp.float32),
        ),
        grid=(b, nt, nc),
        in_specs=[
            block, above, below, block, below, bits, bits_below,
            pl.BlockSpec((taps, cb), lambda b, i, j: (0, j)),
        ],
        out_specs=(
            block,
            # the whole accumulator, in VMEM from the first step to the last
            pl.BlockSpec((taps * 8, c), lambda b, i, j: (0, 0)),
        ),
        # the accumulator is every step's: one walk, in order
        compiler_params=_semantics("arbitrary"),
        name="conv_prep_bwd",
        interpret=interpret,
    )(x, x, x, g, g, bits_all, bits_all, w.astype(jnp.float32).T)
    return dx, dw.reshape(taps, 8, c).sum(axis=1).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_prep(x, w, segment_ids, norm_channels, interpret):
    return _fwd_rows(
        x, w, segment_ids, norm_channels=norm_channels, interpret=interpret
    )


def _conv_prep_fwd(x, w, segment_ids, norm_channels, interpret):
    out = _fwd_rows(
        x, w, segment_ids, norm_channels=norm_channels, interpret=interpret
    )
    return out, (x, w, segment_ids)


def _conv_prep_bwd(norm_channels, interpret, kept, g):
    x, w, segment_ids = kept
    dx, dw = _bwd_rows(
        x, w, segment_ids, g, norm_channels=norm_channels,
        interpret=interpret,
    )
    return dx, dw.astype(w.dtype), None


_conv_prep.defvjp(_conv_prep_fwd, _conv_prep_bwd)


def conv_prep(
    x: jnp.ndarray, w: jnp.ndarray, segment_ids: jnp.ndarray,
    norm_channels: int, interpret: bool = False,
) -> jnp.ndarray:
    """``silu(_short_conv(x, w, segment_ids))`` with the first
    ``norm_channels`` channels l2-normed over 128-wide heads, in ``x``'s
    dtype: ``x`` [B, T, C], ``w`` [C, K] (tap K - 1 the token itself),
    ``segment_ids`` [B, T] (0: padding). The kernels' form: a caller asks
    ``prep_impl`` first."""
    return _conv_prep(x, w, segment_ids, int(norm_channels), bool(interpret))
