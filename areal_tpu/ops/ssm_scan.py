"""The selective scan of a state-space mixer (Mamba-1, S6): a decay a channel
AND a state, from a step that depends on the token.

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t      h [C, N]
    y_t = h_t . C_t                                             y [C]

``x, dt`` [T, C], ``A`` [C, N] < 0, ``B, C`` [T, N]; ``h`` is zero before a
sequence's first token. The decay ``exp(dt_t[c] A[c, n])`` differs for every
(channel, state) pair, so a chunk of tokens has NO matrix form (the delta
rules of ``ops/kda.py`` have one decay a channel or a head, and their chunk
operands are products): the state is walked token by token, in float32, and
what a chip can do about it is keep the state in registers and every other
operand in VMEM. Everything here is float32: ``dt``, ``A``, the decay, the
state and the sums, as the published kernel keeps them.

Two forms of the one function, chosen by what the platform is
(``scan_impl``), no option:

- ``"xla"``: ``lax.scan`` over the tokens (``_scan_xla``), differentiated by
  JAX. The CPU's form and the kernels' oracle in tests.
- ``"kernel"``: the Pallas pair ``ssm_scan_fwd`` / ``ssm_scan_bwd`` on a TPU
  for channels in whole blocks of ``CHANNELS``.

The kernels' layout. A block is ``CHANNELS`` = 8 x 128 channels, ONE vreg a
state index: the state is 16 vregs ``[8, 128]`` carried through a
``fori_loop`` over the tokens of a chunk, ``B_t[n]`` and ``C_t[n]`` are
SCALARS read from SMEM (no broadcast along lanes, no reduction over
sublanes: ``y_t = sum_n C_t[n] h_n`` is 16 multiply-adds of whole vregs).
The callers' ``[B, T, C]`` arrays are handed in as ``[B, T, C / 1024, 8,
128]`` (the same numbers; XLA lays a token's 1,024 channels over a tile, one
relayout an operand that it fuses into the operand's producer where it can).
The grid is (rows, channel blocks, token chunks), the chunks walked in order
(``arbitrary``), the state in a VMEM scratch between them; rows and channel
blocks are ``parallel``.

- forward: writes ``y`` and, a chunk, the state it STARTED from
  (``[B, T / CHUNK, C / 1024, N, 8, 128]`` float32: 8192 / 64 x 5120 x 16 x
  4 B = 42 MB a layer and row, alive from a layer's recomputed forward to
  its backward and no longer).
- backward: walks the chunks in REVERSE. A chunk first re-runs the forward
  from its saved start and keeps every ``h_t`` in VMEM (``CHUNK`` x 64 KB),
  then walks its tokens backwards with ``g_t = dL/dh_t`` in registers:
  ``g_t = C_t dy_t + a_{t+1} g_{t+1}``, ``du_t = sum_n g_t B_t``, ``w_t = g_t
  (h_t - u_t B_t)`` (= ``g_t a_t h_{t-1}``), ``ddt_t = sum_n w_t A``, ``dA +=
  w_t dt_t`` (an accumulator that stays in VMEM over a block's chunks). The
  two sums over CHANNELS, ``dB_t[n] = sum_c g_t u_t`` and ``dC_t[n] = sum_c
  h_t dy_t``, leave the kernel as 128 partial sums a token (a ``[128, 128]``
  transpose on the XLU, then adds of whole vregs; lane ``8 n + s`` holds
  state n's sum over sublane s) and XLA folds the 8 sublanes and the channel
  blocks.

What is NOT in the kernels: ``softplus`` (``dt`` arrives made), ``u = dt *
x``, the skip ``D * x`` and the gate ``silu(z)``: element-wise passes XLA
fuses with the relayouts and their neighbours (scope ``ssm_prep``, the
mixer's). Resets at a packed row's borders are not in them either: the
wrapper hands a sequence's first token (and padding) a step of ``RESET``
(1e30) for the DECAY only, so ``exp(RESET * A)`` is exactly 0 for any rate
``|A|`` above 1e-25, and zeroes ``u`` at padding; a reset token's cotangents
through the decay are then 0 too (``w_t = g_t (h_t - u_t B_t)`` is 0 but for a
rounding, its ``ddt`` is dropped with the step and ``dA`` takes none of it).

On the v5e (my chip runs, PR 61; the traced cell, PERF.md section 5): see
``CHUNK`` below.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.utils.tracing import device_scope

SUBLANES, LANES = 8, 128
CHANNELS = SUBLANES * LANES  # a channel block: one vreg a state index
# Tokens a chunk: the grain of the saved states and of the backward's VMEM
# (``CHUNK`` x N x 4 KB of states re-formed a chunk). Device ms a call of one
# row [8192, 5120], N = 16 (Jamba2-3B's), forward | backward, from the traced
# cell on four chips, a row a chip (PERF.md section 5; my chip run, PR 61):
# **64 x 1024 channels: 1.49 | 8.4** (36 ns and 205 ns a token and channel
# block: 34 and 193 cycles for 16 and 32 exponentials and about 100 and 260
# operations of whole vregs; the backward's two [128, 128] transposes a token
# and ``dA`` through VMEM among them); 0.48 s of the scope's 2.54 s a traced
# window are XLA's relayouts around the calls. What is fused: nothing beyond
# the scan (``softplus``, ``u = dt * x``, the skip and the gate are XLA's).
# Chunks of 128 and 256 compile (``tests/test_chip_compile_layers.py``) and
# were not timed; no other channel block was tried (one vreg a state index is
# what keeps B and C scalars).
CHUNK = 64
RESET = 1e30  # a step at which ``exp(step * A)`` is 0 for |A| > 1e-25
_UNROLL = 2


class SSMStats(NamedTuple):
    """What one state-space layer counts (int32 scalars: the host sums)."""

    tokens: jnp.ndarray  # real tokens through the scan
    segments: jnp.ndarray  # sequence starts the state was reset at


def scan_impl(channels: Optional[int] = None) -> str:
    """The form this process runs: ``"kernel"`` on a TPU for channels in
    whole blocks of 1,024, ``"xla"`` (the ``lax.scan``) elsewhere."""
    if jax.default_backend() != "tpu":
        return "xla"
    if channels is not None and channels % CHANNELS:
        return "xla"
    return "kernel"


# ------------------------------------------------------------- lax.scan --
def _scan_xla(dt, u, a, bm, cm):
    """``dt, u`` [B, T, C], ``a`` [C, N], ``bm, cm`` [B, T, N] -> y [B, T,
    C], all float32: the recurrence a token a step."""

    def step(h, xs):
        dt_t, u_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + u_t[..., None] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=-1)

    b, _, c = dt.shape
    h0 = jnp.zeros((b, c, a.shape[-1]), jnp.float32)
    xs = tuple(jnp.swapaxes(v, 0, 1) for v in (dt, u, bm, cm))
    return jnp.swapaxes(jax.lax.scan(step, h0, xs)[1], 0, 1)


# -------------------------------------------------------------- kernels --
def _walk(steps: int, body, carry):
    """``fori_loop(0, steps, body, carry)`` with ``_UNROLL`` steps a trip
    (Mosaic's loop unrolls whole or not at all)."""

    def trip(i, carry):
        for k in range(_UNROLL):
            carry = body(i * _UNROLL + k, carry)
        return carry

    return jax.lax.fori_loop(0, steps // _UNROLL, trip, carry)


def _fwd_kernel(bc_ref, dt_ref, u_ref, a_ref, y_ref, hs_ref, h_scr, *,
                n_state: int):
    """One chunk of one channel block: ``dt, u, y`` [CHUNK, 8, 128];
    ``bc`` (SMEM) [CHUNK * 2 N]: a token's B then its C; ``a`` [N, 8, 128];
    ``hs`` [N, 8, 128]: the state the chunk starts from; ``h_scr`` the
    state between chunks."""
    n2 = 2 * n_state

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[...] = h_scr[...]

    def token(t, h):
        dt_t, u_t = dt_ref[t], u_ref[t]
        y = jnp.zeros_like(u_t)
        out = []
        for n in range(n_state):
            h_n = jnp.exp(dt_t * a_ref[n]) * h[n] + u_t * bc_ref[t * n2 + n]
            y = y + h_n * bc_ref[t * n2 + n_state + n]
            out.append(h_n)
        y_ref[t] = y
        return tuple(out)

    h = _walk(
        dt_ref.shape[0], token, tuple(h_scr[n] for n in range(n_state))
    )
    for n in range(n_state):
        h_scr[n] = h[n]


def _bwd_kernel(bc_ref, dt_ref, u_ref, a_ref, hs_ref, dy_ref, ddt_ref,
                du_ref, da_ref, dbc_ref, g_scr, h_all, *, n_state: int):
    """One chunk of one channel block, the chunks in reverse: the forward's
    refs and ``dy`` [CHUNK, 8, 128] in; ``ddt, du`` [CHUNK, 8, 128], ``da``
    [N, 8, 128] (summed over the block's chunks in place) and ``dbc`` [CHUNK,
    2, 128] (a token's partial sums of dB, then of dC) out; ``g_scr`` [N, 8,
    128]: ``a_{t+1} g_{t+1}`` between chunks; ``h_all`` [CHUNK, N, 8, 128]."""
    n2 = 2 * n_state
    chunk = dt_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    def forward(t, h):
        dt_t, u_t = dt_ref[t], u_ref[t]
        out = []
        for n in range(n_state):
            h_n = jnp.exp(dt_t * a_ref[n]) * h[n] + u_t * bc_ref[t * n2 + n]
            h_all[t, n] = h_n
            out.append(h_n)
        return tuple(out)

    _walk(chunk, forward, tuple(hs_ref[n] for n in range(n_state)))

    def backward(i, ga):
        t = chunk - 1 - i
        dt_t, u_t, dy_t = dt_ref[t], u_ref[t], dy_ref[t]
        # a reset token's ``w`` is 0 but for a fused multiply-add's rounding
        # of ``h - u B``: its step must not scale that up into ``dA``
        dt_a = jnp.where(dt_t < 0.5 * RESET, dt_t, 0.0)
        du = jnp.zeros_like(u_t)
        ddt = jnp.zeros_like(u_t)
        out, for_b, for_c = [], [], []
        for n in range(n_state):
            b_tn = bc_ref[t * n2 + n]
            a_n = a_ref[n]
            h_n = h_all[t, n]
            g = dy_t * bc_ref[t * n2 + n_state + n] + ga[n]
            for_c.append(h_n * dy_t)
            for_b.append(g * u_t)
            du = du + g * b_tn
            w = g * (h_n - u_t * b_tn)  # g a_t h_{t-1}
            ddt = ddt + w * a_n
            da_ref[n] += w * dt_a
            out.append(jnp.exp(dt_t * a_n) * g)
        du_ref[t] = du
        ddt_ref[t] = ddt
        # [N x 8, 128] -> its transpose, summed over the rows: lane 8 n + s
        # holds state n's sum over the 128 lanes of sublane s
        for k, parts in enumerate((for_b, for_c)):
            dbc_ref[t, pl.ds(k, 1), :] = jnp.sum(
                jnp.concatenate(parts, axis=0).T, axis=0, keepdims=True
            )
        return tuple(out)

    ga = _walk(chunk, backward, tuple(g_scr[n] for n in range(n_state)))
    for n in range(n_state):
        g_scr[n] = ga[n]


def _tiled(v):
    """[B, T, C] -> [B, T, C / 1024, 8, 128]: a token's channel block a tile."""
    b, t, c = v.shape
    return v.reshape(b, t, c // CHANNELS, SUBLANES, LANES)


def _specs(n_state: int, nt: int, reverse: bool):
    def chunk_of(i):
        return nt - 1 - i if reverse else i

    tok = pl.BlockSpec(
        (None, CHUNK, None, SUBLANES, LANES),
        lambda b, j, i: (b, chunk_of(i), j, 0, 0),
    )
    bc = pl.BlockSpec(
        (None, CHUNK * 2 * n_state), lambda b, j, i: (b, chunk_of(i)),
        memory_space=pltpu.SMEM,
    )
    rate = pl.BlockSpec(
        (None, n_state, SUBLANES, LANES), lambda b, j, i: (j, 0, 0, 0)
    )
    start = pl.BlockSpec(
        (None, None, None, n_state, SUBLANES, LANES),
        lambda b, j, i: (b, chunk_of(i), j, 0, 0, 0),
    )
    return tok, bc, rate, start


def _rates(a):
    """[C, N] -> [C / 1024, N, 8, 128]."""
    c, n = a.shape
    return a.T.reshape(n, c // CHANNELS, SUBLANES, LANES).swapaxes(0, 1)


def _scalars(bm, cm):
    """[B, T, N] twice -> [B, T * 2 N]: a token's B, then its C."""
    b, t, n = bm.shape
    return jnp.concatenate([bm, cm], axis=-1).reshape(b, t * 2 * n)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_rows(dt, u, a, bm, cm, *, interpret: bool):
    """(y [B, T, C], the chunks' start states). T a multiple of CHUNK."""
    b, t, c = dt.shape
    n, nc, nt = a.shape[1], c // CHANNELS, t // CHUNK
    tok, bc, rate, start = _specs(n, nt, reverse=False)
    y, hs = pl.pallas_call(
        functools.partial(_fwd_kernel, n_state=n),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, nc, SUBLANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct(
                (b, nt, nc, n, SUBLANES, LANES), jnp.float32
            ),
        ),
        grid=(b, nc, nt),
        in_specs=[bc, tok, tok, rate],
        out_specs=(tok, start),
        scratch_shapes=[pltpu.VMEM((n, SUBLANES, LANES), jnp.float32)],
        compiler_params=_PARAMS,
        name="ssm_scan_fwd",
        interpret=interpret,
    )(_scalars(bm, cm), _tiled(dt), _tiled(u), _rates(a))
    return y.reshape(b, t, c), hs


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_rows(dt, u, a, bm, cm, hs, dy, *, interpret: bool):
    """The cotangents of ``dt, u, a, bm, cm`` from ``dy`` [B, T, C]."""
    b, t, c = dt.shape
    n, nc, nt = a.shape[1], c // CHANNELS, t // CHUNK
    tok, bc, rate, start = _specs(n, nt, reverse=True)
    ddt, du, da, dbc = pl.pallas_call(
        functools.partial(_bwd_kernel, n_state=n),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, nc, SUBLANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, t, nc, SUBLANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, n, SUBLANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, t, 2, LANES), jnp.float32),
        ),
        grid=(b, nc, nt),
        in_specs=[bc, tok, tok, rate, start, tok],
        out_specs=(
            tok, tok,
            pl.BlockSpec(
                (None, None, n, SUBLANES, LANES),
                lambda b, j, i: (b, j, 0, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, CHUNK, 2, LANES),
                lambda b, j, i: (b, j, nt - 1 - i, 0, 0),
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((n, SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((CHUNK, n, SUBLANES, LANES), jnp.float32),
        ],
        compiler_params=_PARAMS,
        name="ssm_scan_bwd",
        interpret=interpret,
    )(_scalars(bm, cm), _tiled(dt), _tiled(u), _rates(a), hs, _tiled(dy))
    # lane 8 n + s of a token's row -> state n, summed over s and the blocks
    dbc = dbc.reshape(b, nc, t, 2, n, SUBLANES).sum(axis=(1, 5))
    da = da.sum(axis=0).swapaxes(0, 1).reshape(n, c).T
    return (
        ddt.reshape(b, t, c), du.reshape(b, t, c), da, dbc[:, :, 0],
        dbc[:, :, 1],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_kernel(dt, u, a, bm, cm, interpret):
    return _fwd_rows(dt, u, a, bm, cm, interpret=interpret)[0]


def _scan_kernel_fwd(dt, u, a, bm, cm, interpret):
    y, hs = _fwd_rows(dt, u, a, bm, cm, interpret=interpret)
    return y, (dt, u, a, bm, cm, hs)


def _scan_kernel_bwd(interpret, kept, dy):
    return _bwd_rows(*kept, dy, interpret=interpret)


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


# ------------------------------------------------------------------- op --
def ssm_scan(
    x: jnp.ndarray,  # [B, T, C], after the convolution and its activation
    dt: jnp.ndarray,  # [B, T, C] float32, > 0 (after softplus)
    a: jnp.ndarray,  # [C, N] float32, < 0
    bm: jnp.ndarray,  # [B, T, N]
    cm: jnp.ndarray,  # [B, T, N]
    segment_ids: jnp.ndarray,  # [B, T] int32; 0 = padding
    impl: Optional[str] = None,  # None: ``scan_impl``'s
    interpret: bool = False,  # Pallas interpret mode: the CPU's tests
    token_axes: Optional[tuple] = None,
):
    """The selective scan over packed rows: (y [B, T, C] float32, zero at
    padding; ``SSMStats``). The state is zero before a sequence's first
    token. ``token_axes``: the mesh axes the rows are split over when this
    runs per device inside a ``shard_map``, so that the counts are the whole
    batch's."""
    b, t, c = x.shape
    impl = impl or scan_impl(c)
    f32 = jnp.float32
    with device_scope("ssm_scan"):
        pad = 0 if impl == "xla" else -t % CHUNK
        if pad:  # whole chunks: what follows the row is padding
            x, dt, bm, cm = (
                jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, bm, cm)
            )
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)))
        real = segment_ids > 0
        starts = real & (
            segment_ids != jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :-1]
        )
        dt = dt.astype(f32)
        u = jnp.where(real[..., None], dt * x.astype(f32), 0.0)
        step = jnp.where((starts | ~real)[..., None], RESET, dt)
        args = (step, u, a.astype(f32), bm.astype(f32), cm.astype(f32))
        if impl == "xla":
            y = _scan_xla(*args)
        else:
            y = _scan_kernel(*args, interpret)[:, :t]
        counts = (real.sum(dtype=jnp.int32), starts.sum(dtype=jnp.int32))
        if token_axes:
            counts = jax.lax.psum(counts, token_axes)
        return y, SSMStats(*counts)


def make_ssm_scan(mesh: Mesh):
    """The op's mesh form: ``ssm_fn(x, dt, a, bm, cm, segment_ids)`` for
    ``transformer.apply``, per device inside a ``shard_map`` over rows (and
    channels over ``tensor``: a channel's state is its own), as the splash
    kernel runs (None on one device: the plain call serves). The state never
    crosses a row, so this is exact; a sequence split would need the state
    handed from device to device."""
    if mesh.size == 1:
        return None
    if mesh.shape["seq"] > 1:
        raise ValueError(
            "a selective scan has no sequence-parallel form "
            f"(seq_parallel_size={mesh.shape['seq']}): ROADMAP B"
        )
    rows = ("data", "fsdp")
    chan, state = P(rows, None, "tensor"), P(rows, None, None)

    def ssm_fn(x, dt, a, bm, cm, segment_ids):
        return jax.shard_map(
            functools.partial(ssm_scan, token_axes=rows),
            mesh=mesh,
            in_specs=(chan, chan, P("tensor", None), state, state,
                      P(rows, None)),
            out_specs=(chan, P()),
            check_vma=False,
        )(x, dt, a, bm, cm, segment_ids)

    return ssm_fn
