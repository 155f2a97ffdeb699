"""Gated delta-rule linear attention (KDA) over packed rows, in chunks.

A head keeps a state ``S`` in [dk, dv], zero at a sequence's first token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

with a per-CHANNEL log-decay ``g_t <= 0`` in [dk] and ``beta_t`` in (0, 1).
Nothing in ``ops/`` computed a recurrence before this file. The rule runs in
chunks of ``CHUNK`` tokens (the WY representation): with ``G`` the inclusive
running sum of ``g`` inside a chunk and ``S0`` the state the chunk starts on,

    A[i, j]   = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])        (j <  i)
    Aqk[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c]) / sqrt(dk) (j <= i)
    T   = (I + Diag(beta) A)^-1                    (unit lower triangular)
    Wk  = T Diag(beta) (k * exp(G)),   Wv = T Diag(beta) v
    U   = Wv - Wk S0
    O   = (q * exp(G) / sqrt(dk)) S0 + Aqk U
    S'  = Diag(exp(G_last)) S0 + (k * exp(G_last - G))^T U

Two steps. ``_prep`` makes a chunk's ``Wk, Wv, Qb, Kt, Aqk, d`` from its own
tokens, every chunk at once (plain XLA: batched products and elementwise
work). The STATE PASS then walks the chunks of a row in order, the only
sequential part: ``U, O, S'`` above, five small matmuls a chunk. It has two
forms, resolved from the platform as ``ops/moe.grouped_impl`` is: a
``lax.scan`` over chunks (the CPU's path and the form the kernels are held
equal to) and the Pallas kernels ``kda_fwd_h`` / ``kda_bwd_dh`` (a TPU's: the
state stays in VMEM over a row, float32; a scan's carry goes through HBM
every chunk). Both have the same hand-written backward (``_state_bwd_*``):
the op is one ``jax.custom_vjp`` that keeps its INPUTS only, and the backward
recomputes the chunk-start states (in the compute dtype, as the forward's
matmuls read them) instead of keeping [T / 64, H, dk, dv] a layer across the
step; ``_prep``'s own backward is XLA's, run over blocks of chunks so that
its [16, 16, dk] temporaries a sub-block stay bounded.

Exponents: per-channel decay makes the usual factorisation ``k_j exp(-G_j)``
unbounded inside a chunk. Every exponent here is a DIFFERENCE that is <= 0:
inside sub-blocks of ``SUB`` tokens the products are computed pair by pair
from ``G_i - G_j`` itself; between sub-blocks they factor through the
row sub-block's start (``exp(G_i - G_start) * exp(G_start - G_j)``, both
<= 0). No clamp anywhere.

Packed rows: a sequence's first token resets the state. ``_prep`` folds that
into what it emits: ``A`` and ``Aqk`` are cut where the segment ids differ,
``S0`` reaches only the tokens of the sequence the last chunk ended in
(``Wk``, ``Qb`` zeroed elsewhere), and ``S'`` keeps only what belongs to the
chunk's last token's sequence (``Kt``, ``d``). Padding (segment 0) takes no
part: its ``q, k, v, beta, g`` are zeroed on the way in. So the state pass
knows nothing of segments.

Device scope ``kda_scan`` (``utils/tracing.DEVICE_SCOPES``): everything in
this file. The convolution, the decays' and gates' projections and the
output norm are the layer's (``kda_prep``, ``models/transformer.py``).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.utils.tracing import device_scope

CHUNK = 64  # tokens a chunk
SUB = 16  # tokens a sub-block: exponent differences pair by pair inside
_NSUB = CHUNK // SUB
# chunks ``_prep`` (and its backward) handles at once: 1,024 tokens, whose
# pairwise [SUB, SUB, dk] float32 terms are 8 MB a head
_PREP_CHUNKS = 16
# chunks one grid step of a kernel walks (512 tokens: a grid step costs
# ~0.35 us, a chunk's arithmetic ~0.1 us)
_KERNEL_CHUNKS = 8
_HI = jax.lax.Precision.HIGHEST


class KDAStats(NamedTuple):
    """What one KDA layer counts (int32 scalars: the host sums them)."""

    tokens: jnp.ndarray  # real tokens through the scan
    chunk_slots: jnp.ndarray  # chunks run x CHUNK
    segments: jnp.ndarray  # sequence starts the scan reset at


def scan_impl(dk: Optional[int] = None, dv: Optional[int] = None) -> str:
    """The state pass this process runs: ``"kernel"`` (``kda_fwd_h`` /
    ``kda_bwd_dh``) on a TPU for head widths that are whole lane tiles,
    ``"xla"`` (the ``lax.scan``) elsewhere."""
    if jax.default_backend() != "tpu":
        return "xla"
    if dk is not None and (dk % 128 or dv % 128):
        return "xla"
    return "kernel"


# ------------------------------------------------------------------ prep --
def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower ``n`` [..., CHUNK, CHUNK], float32:
    the diagonal SUB blocks by forward substitution (a row a step), the
    blocks under them by block substitution. Stable whatever the entries,
    which powers of ``n`` are not."""
    lead = n.shape[:-2]
    n4 = n.reshape(*lead, _NSUB, SUB, _NSUB, SUB)
    eye = jnp.eye(SUB, dtype=n.dtype)
    diag = jnp.stack(
        [n4[..., a, :, a, :] for a in range(_NSUB)], axis=-3
    )  # [..., NSUB, SUB, SUB]
    x = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, SUB):
        # rows < i are final and n[i, j >= i] is 0
        row = eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :], x, precision=_HI
        )
        x = x.at[..., i, :].set(row)
    blocks = {(a, a): x[..., a, :, :] for a in range(_NSUB)}
    for off in range(1, _NSUB):
        for a in range(off, _NSUB):
            b = a - off
            acc = sum(
                jnp.einsum(
                    "...ij,...jk->...ik", n4[..., a, :, c, :],
                    blocks[(c, b)], precision=_HI,
                )
                for c in range(b, a)
            )
            blocks[(a, b)] = -jnp.einsum(
                "...ij,...jk->...ik", blocks[(a, a)], acc, precision=_HI
            )
    zero = jnp.zeros_like(blocks[(0, 0)])
    return jnp.concatenate([
        jnp.concatenate(
            [blocks.get((a, b), zero) for b in range(_NSUB)], axis=-1
        )
        for a in range(_NSUB)
    ], axis=-2)


def _prep(q, k, v, g, beta, seg, seg_in):
    """A chunk's operands for the state pass, from its own tokens.

    ``q, k`` [N, B, H, C, dk], ``v`` [.., dv] in the compute dtype; ``g``
    float32 like ``k``; ``beta`` float32 [N, B, H, C]; ``seg`` int32
    [N, B, C]; ``seg_in`` int32 [N, B]: the segment of the token before the
    chunk (-1 for a row's first chunk). Returns (Wk, Wv, Qb, Kt [.., C, d],
    Aqk [.., C, C]) in the compute dtype and ``d`` [.., 1, dk] float32."""
    f32, dt = jnp.float32, q.dtype
    dk = q.shape[-1]
    scale = dk ** -0.5
    valid = seg > 0  # [N, B, C]
    vm = valid[:, :, None, :, None]
    qf = jnp.where(vm, q, 0).astype(f32)
    kf = jnp.where(vm, k, 0).astype(f32)
    vf = jnp.where(vm, v, 0).astype(f32)
    g = jnp.where(vm, g, 0.0)
    beta = jnp.where(valid[:, :, None], beta, 0.0)
    G = jnp.cumsum(g, axis=3)  # inclusive, <= 0, decreasing
    lead = G.shape[:3]

    # --- inside a sub-block: the exponent differences themselves
    def sub(x):
        return x.reshape(*lead, _NSUB, SUB, x.shape[-1])

    G4, k4, q4 = sub(G), sub(kf), sub(qf)
    tri = jnp.tril(jnp.ones((SUB, SUB), bool))
    diff = G4[..., :, None, :] - G4[..., None, :, :]  # [.., a, i, j, c]
    e = jnp.exp(jnp.where(tri[..., None], diff, -jnp.inf))
    kj = k4[..., None, :, :] * e
    kk_diag = (k4[..., :, None, :] * kj).sum(-1)  # [.., a, i, j]
    qk_diag = (q4[..., :, None, :] * kj).sum(-1)
    # --- between sub-blocks: through the row sub-block's start
    start = G4[..., 0, :] - sub(g)[..., 0, :]  # G before sub-block a
    row = jnp.exp(G4 - start[..., None, :])  # [.., a, i, c], <= 1
    before = (
        jnp.arange(CHUNK)[None, :] < (jnp.arange(_NSUB) * SUB)[:, None]
    )  # [a, j]: token j lies before sub-block a
    col = jnp.exp(jnp.where(
        before[..., None], start[..., :, None, :] - G[..., None, :, :],
        -jnp.inf,
    ))  # [.., a, j, c], <= 1
    kcol = kf[..., None, :, :] * col
    kk_off = jnp.einsum("...aic,...ajc->...aij", k4 * row, kcol)
    qk_off = jnp.einsum("...aic,...ajc->...aij", q4 * row, kcol)
    blockdiag = jnp.eye(_NSUB, dtype=f32)[:, None, :, None]  # [a, 1, b, 1]

    def whole(off, diag):  # [.., a, i, C] + [.., a, i, j] -> [.., C, C]
        off = off.reshape(*lead, _NSUB, SUB, _NSUB, SUB)
        return (off + diag[..., :, :, None, :] * blockdiag).reshape(
            *lead, CHUNK, CHUNK
        )

    same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
    idx = jnp.arange(CHUNK)
    strict = idx[:, None] > idx[None, :]
    a_kk = jnp.where(same & strict, whole(kk_off, kk_diag), 0.0)
    a_qk = jnp.where(
        same & (idx[:, None] >= idx[None, :]), whole(qk_off, qk_diag), 0.0
    ) * scale
    t_inv = _unit_lower_inverse(beta[..., None] * a_kk)

    e_g = jnp.exp(G)
    from_in = (valid & (seg == seg_in[..., None]))[:, :, None, :, None]
    kbar = jnp.where(from_in, kf * e_g, 0.0)
    bcol = beta[..., None]
    wk = jnp.einsum("...ij,...jd->...id", t_inv, bcol * kbar)
    wv = jnp.einsum("...ij,...jd->...id", t_inv, bcol * vf)
    qb = jnp.where(from_in, qf * e_g, 0.0) * scale
    g_last = G[..., -1:, :]
    seg_last = seg[..., -1]  # [N, B]
    to_out = (valid & (seg == seg_last[..., None]))[:, :, None, :, None]
    kt = jnp.where(to_out, kf * jnp.exp(g_last - G), 0.0)
    carries = ((seg_last == seg_in) & (seg_last > 0))[:, :, None, None, None]
    d = jnp.where(carries, jnp.exp(g_last), 0.0)
    return (wk.astype(dt), wv.astype(dt), qb.astype(dt), kt.astype(dt),
            a_qk.astype(dt), d)


def _in_blocks(fn, arrays):
    """``fn`` over blocks of ``_PREP_CHUNKS`` chunks (the leading axis of
    every array), one block after the other."""
    n = arrays[0].shape[0]
    if n <= _PREP_CHUNKS or n % _PREP_CHUNKS:
        return fn(*arrays)
    nb = n // _PREP_CHUNKS
    split = [a.reshape(nb, _PREP_CHUNKS, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda blk: fn(*blk), tuple(split))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n, *a.shape[2:]), out
    )


def _seg_in(seg):
    """[N, B]: the segment of the token before each chunk."""
    first = jnp.full_like(seg[:1, :, -1], -1)
    return jnp.concatenate([first, seg[:-1, :, -1]], axis=0)


# ------------------------------------------------------------ state pass --
def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _chunk_fwd(s, wk, wv, qb, kt, aqk, d_col):
    """One chunk of one head: (o [C, dv] float32, the next state). ``s`` is
    the float32 state [dk, dv]; the matmuls read it in the compute dtype."""
    dt = wk.dtype
    sc = s.astype(dt)
    u = wv.astype(jnp.float32) - _dot(wk, sc, _NN)
    uc = u.astype(dt)
    o = _dot(qb, sc, _NN) + _dot(aqk, uc, _NN)
    return o, d_col * s + _dot(kt, uc, _TN)


def _chunk_bwd(ds, sc, wk, wv, qb, kt, aqk, d_col, do):
    """The transpose of ``_chunk_fwd`` at one chunk: ``ds`` the float32
    cotangent of the state it left, ``sc`` the state it started on (compute
    dtype), ``do`` the output's cotangent. Returns (cotangents of wk, wv,
    qb, kt, aqk float32, of the decay as a [dk, dv] product still to be
    summed over dv, the cotangent of the state it started on)."""
    dt = wk.dtype
    dsc = ds.astype(dt)
    u = wv.astype(jnp.float32) - _dot(wk, sc, _NN)
    uc = u.astype(dt)
    du = _dot(aqk, do, _TN) + _dot(kt, dsc, _NN)
    duc = du.astype(dt)
    d_wk = -_dot(duc, sc, _NT)
    d_qb = _dot(do, sc, _NT)
    d_aqk = _dot(do, uc, _NT)
    d_kt = _dot(uc, dsc, _NT)
    d_decay = ds * sc.astype(jnp.float32)
    ds_in = _dot(qb, do, _TN) + d_col * ds - _dot(wk, duc, _TN)
    return d_wk, du, d_qb, d_kt, d_aqk, d_decay, ds_in


def _state_fwd_xla(ops, save_states=False):
    """The state pass as a ``lax.scan`` over chunks. ``ops`` as ``_prep``
    gives them; returns o [N, B, H, C, dv] (and the chunk-start states)."""
    wk, wv = ops[0], ops[1]
    chunk = jax.vmap(jax.vmap(_chunk_fwd))  # over B, H

    def step(s, x):
        *mats, d = x
        o, s_next = chunk(s, *mats, jnp.swapaxes(d, -1, -2))
        out = (o.astype(wk.dtype),)
        return s_next, out + ((s.astype(wk.dtype),) if save_states else ())

    s0 = jnp.zeros(wk.shape[1:3] + (wk.shape[-1], wv.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, s0, ops)
    return out if save_states else out[0]


def _state_bwd_xla(ops, states, do):
    wk, wv = ops[0], ops[1]
    chunk = jax.vmap(jax.vmap(_chunk_bwd))

    def step(ds, x):
        sc, do_n, *mats, d = x
        *grads, d_decay, ds_in = chunk(
            ds, sc, *mats, jnp.swapaxes(d, -1, -2), do_n
        )
        d_d = d_decay.sum(-1)[..., None, :]
        return ds_in, tuple(x.astype(wk.dtype) for x in grads) + (d_d,)

    ds0 = jnp.zeros(wk.shape[1:3] + (wk.shape[-1], wv.shape[-1]), jnp.float32)
    _, grads = jax.lax.scan(step, ds0, (states, do) + tuple(ops), reverse=True)
    return grads


def _kernel_chunks(n: int) -> int:
    nb = _KERNEL_CHUNKS
    while n % nb:
        nb //= 2
    return nb


def _column(d_row, dv):
    """[1, dk] -> [dk, dv], each row its own decay: a 128 x 128 transpose."""
    return jnp.broadcast_to(d_row, (dv, d_row.shape[-1])).T


def _fwd_kernel(wk, wv, qb, kt, aqk, d, o, *rest, nb, save_states):
    s_scr = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    def body(i, _):
        s = s_scr[...]
        if save_states:
            rest[0][i, 0] = s.astype(rest[0].dtype)
        out, s_scr[...] = _chunk_fwd(
            s, wk[i, 0], wv[i, 0], qb[i, 0], kt[i, 0], aqk[i, 0],
            _column(d[i, 0], s.shape[-1]),
        )
        o[i, 0] = out.astype(o.dtype)

    jax.lax.fori_loop(0, nb, body, None)


def _bwd_kernel(wk, wv, qb, kt, aqk, d, states, do,
                d_wk, d_wv, d_qb, d_kt, d_aqk, d_d, ds_scr, *, nb):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def body(j, _):
        i = nb - 1 - j
        ds = ds_scr[...]
        g_wk, g_wv, g_qb, g_kt, g_aqk, g_decay, ds_scr[...] = _chunk_bwd(
            ds, states[i, 0], wk[i, 0], wv[i, 0], qb[i, 0], kt[i, 0],
            aqk[i, 0], _column(d[i, 0], ds.shape[-1]), do[i, 0],
        )
        for ref, val in ((d_wk, g_wk), (d_wv, g_wv), (d_qb, g_qb),
                         (d_kt, g_kt), (d_aqk, g_aqk)):
            ref[i, 0] = val.astype(ref.dtype)
        d_d[i, 0] = g_decay.T.sum(0, keepdims=True)

    jax.lax.fori_loop(0, nb, body, None)


def _flat(x):  # [N, B, H, ...] -> [N, B*H, ...]
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _state_fwd_kernel(ops, save_states=False, interpret=False):
    wk, wv = ops[0], ops[1]
    n, b, h, c, dk = wk.shape
    dv = wv.shape[-1]
    nb = _kernel_chunks(n)
    flat = [_flat(x) for x in ops]

    def spec(x, at=lambda i, j: (j, i, 0, 0)):
        return pl.BlockSpec((nb, 1) + x.shape[2:], at)

    out_shape = [jax.ShapeDtypeStruct((n, b * h, c, dv), wk.dtype)]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((n, b * h, dk, dv), wk.dtype))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nb=nb, save_states=save_states),
        grid=(b * h, n // nb),
        in_specs=[spec(x) for x in flat],
        out_specs=[spec(x) for x in out_shape],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="kda_fwd_h_states" if save_states else "kda_fwd_h",
    )(*flat)
    out = [x.reshape(n, b, h, *x.shape[2:]) for x in out]
    return tuple(out) if save_states else out[0]


def _state_bwd_kernel(ops, states, do, interpret=False):
    wk, wv = ops[0], ops[1]
    n, b, h, c, dk = wk.shape
    dv = wv.shape[-1]
    nb = _kernel_chunks(n)
    last = n // nb - 1
    flat = [_flat(x) for x in tuple(ops) + (states, do)]

    def spec(x):  # the chunks of a row from its end
        return pl.BlockSpec(
            (nb, 1) + x.shape[2:], lambda i, j: (last - j, i, 0, 0)
        )

    out_shape = [
        jax.ShapeDtypeStruct(x.shape, jnp.float32 if k == 5 else wk.dtype)
        for k, x in enumerate(flat[:6])
    ]
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, nb=nb),
        grid=(b * h, n // nb),
        in_specs=[spec(x) for x in flat],
        out_specs=[spec(x) for x in out_shape],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="kda_bwd_dh",
    )(*flat)
    return tuple(x.reshape(n, b, h, *x.shape[2:]) for x in grads)


def _state_fwd(ops, impl, interpret, save_states=False):
    if impl == "kernel":
        return _state_fwd_kernel(ops, save_states, interpret)
    return _state_fwd_xla(ops, save_states)


# -------------------------------------------------------------- the op --
def _operands(q, k, v, g, beta, seg):
    return _in_blocks(_prep, (q, k, v, g, beta, seg, _seg_in(seg)))


def _chunked_plain(q, k, v, g, beta, seg):
    """The chunked rule with XLA's own differentiation throughout: what the
    hand-written backward is held equal to (tests)."""
    return _state_fwd_xla(_operands(q, k, v, g, beta, seg))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunked(q, k, v, g, beta, seg, impl, interpret):
    return _state_fwd(_operands(q, k, v, g, beta, seg), impl, interpret)


def _chunked_fwd(q, k, v, g, beta, seg, impl, interpret):
    out = _chunked(q, k, v, g, beta, seg, impl, interpret)
    return out, (q, k, v, g, beta, seg)


def _chunked_bwd(impl, interpret, res, do):
    q, k, v, g, beta, seg = res
    ops = _operands(q, k, v, g, beta, seg)
    _, states = _state_fwd(ops, impl, interpret, save_states=True)
    if impl == "kernel":
        d_ops = _state_bwd_kernel(ops, states, do, interpret)
    else:
        d_ops = _state_bwd_xla(ops, states, do)

    def prep_bwd(q, k, v, g, beta, seg, seg_in, *cts):
        _, vjp = jax.vjp(
            lambda *x: _prep(*x, seg, seg_in), q, k, v, g, beta
        )
        return vjp(cts)

    grads = _in_blocks(
        prep_bwd, (q, k, v, g, beta, seg, _seg_in(seg)) + tuple(d_ops)
    )
    return tuple(grads) + (None,)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _to_chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // CHUNK, CHUNK, h, *x.shape[3:])
    return x.transpose((1, 0, 3, 2) + tuple(range(4, x.ndim)))


def kda_scan(
    q: jnp.ndarray,  # [B, T, H, dk], l2-normed
    k: jnp.ndarray,  # [B, T, H, dk], l2-normed
    v: jnp.ndarray,  # [B, T, H, dv]
    g: jnp.ndarray,  # [B, T, H, dk] float32 log-decay, <= 0
    beta: jnp.ndarray,  # [B, T, H] float32 in (0, 1)
    segment_ids: jnp.ndarray,  # [B, T] int32; 0 = padding
    impl: Optional[str] = None,  # None: ``scan_impl``'s
    interpret: bool = False,  # Pallas interpret mode: the CPU's tests
    custom_backward: bool = True,  # False: XLA differentiates (tests)
    token_axes: Optional[tuple] = None,
):
    """The gated delta rule over packed rows: (o [B, T, H, dv] in ``q``'s
    dtype, zero at padding; ``KDAStats``). ``token_axes``: the mesh axes the
    rows are split over when this runs per device inside a ``shard_map``,
    so that the counts are the whole batch's."""
    b, t, h, dk = q.shape
    impl = impl or scan_impl(dk, v.shape[-1])
    with device_scope("kda_scan"):
        seg = segment_ids.astype(jnp.int32)
        pad = -t % CHUNK
        if pad:  # a row that is no whole number of chunks
            q, k, v, g, beta, seg = (
                jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                for x in (q, k, v, g, beta, seg)
            )
        chunks = [_to_chunks(x) for x in (q, k, v, g.astype(jnp.float32))]
        beta_c = _to_chunks(beta.astype(jnp.float32)[..., None])[..., 0]
        seg_c = seg.reshape(b, -1, CHUNK).transpose(1, 0, 2)
        if custom_backward:
            o = _chunked(*chunks, beta_c, seg_c, impl, interpret)
        else:
            o = _chunked_plain(*chunks, beta_c, seg_c)
        o = o.transpose(1, 0, 3, 2, 4).reshape(b, t + pad, h, -1)[:, :t]
        real = segment_ids > 0
        starts = real & (
            segment_ids != jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :-1]
        )
        counts = (
            real.sum(dtype=jnp.int32),
            jnp.asarray(b * (t + pad), jnp.int32),
            starts.sum(dtype=jnp.int32),
        )
        if token_axes:
            counts = jax.lax.psum(counts, token_axes)
        return o, KDAStats(*counts)


def make_kda_scan(mesh: Mesh):
    """The op's mesh form: ``kda_fn(q, k, v, g, beta, segment_ids)`` for
    ``transformer.apply``, per device inside a ``shard_map`` over rows and
    heads as the splash kernel runs (None on one device: the plain call
    serves). The rule never crosses a row, so this is exact; a sequence
    split would need the state handed from device to device."""
    if mesh.size == 1:
        return None
    if mesh.shape["seq"] > 1:
        raise ValueError(
            "gated delta-rule attention has no sequence-parallel form "
            f"(seq_parallel_size={mesh.shape['seq']}): ROADMAP B"
        )
    rows = ("data", "fsdp")
    x_spec = P(rows, None, "tensor", None)
    return jax.shard_map(
        functools.partial(kda_scan, token_axes=rows),
        mesh=mesh,
        in_specs=(x_spec, x_spec, x_spec, x_spec, P(rows, None, "tensor"),
                  P(rows, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
