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

Two steps. The OPERANDS: a chunk's ``Wk, Wv, Qb, Kt, Aqk, d`` from its own
tokens, every chunk independent of the others. The STATE PASS then walks the
chunks of a row in order, the only sequential part: ``U, O, S'`` above, five
small matmuls a chunk. Each step has two forms, resolved together from the
platform as ``ops/moe.grouped_impl`` is (``scan_impl``). ``"xla"``: ``_prep``
(batched products and elementwise work, differentiated by XLA over blocks of
chunks so that its [16, 16, dk] temporaries a sub-block stay bounded) and a
``lax.scan`` over chunks: the CPU's path and the form the kernels are held
equal to. ``"kernel"``, a TPU's: ``chunk_ops_fwd`` / ``chunk_ops_bwd`` make the
operands and pull their cotangents back with a chunk's exponent differences
in VMEM and nowhere else, each exponential evaluated once and shared by every
product and cotangent (as XLA tensors they are 8 MB a head and 16 chunks, and
XLA's differentiation of them is one reduction a cotangent, the exponentials
evaluated again in each); ``kda_fwd_h`` / ``kda_bwd_dh`` walk the chunks with
the state in VMEM over a row, float32 (a scan's carry goes through HBM every
chunk). The op is one ``jax.custom_vjp`` that keeps its INPUTS only: the
backward makes the operands again, recomputes the chunk-start states (in the
compute dtype, as the forward's matmuls read them) instead of keeping
[T / 64, H, dk, dv] a layer across the step, runs the state pass's
hand-written backward (``_state_bwd_*``) and hands its cotangents to the
operands' backward. Only the three state-pass kernels are named ``kda_*``:
the benchmark's roofline of the state pass reads that prefix.

What a kernel costs a program's SET-UP (PERF.md, PR 34 and PR 35): a Pallas
call is traced and lowered to its Mosaic module in Python at every place it
occurs in a program, before the compile cache is asked, on a warm start as on
a cold one. So each kernel call sits behind a jitted function (a module lowers
one once for equal arguments, however many layers call it), and the loops
inside the operand kernels that run 16 times are ``lax.fori_loop``s of four
trips: a body of a few hundred operations. ``tools/setup_cost.py`` reads
both off the chip, and a test counts the bodies in a lowered ``grad_step``.

Exponents: per-channel decay makes the usual factorisation ``k_j exp(-G_j)``
unbounded inside a chunk. Every exponent here is a DIFFERENCE that is <= 0:
inside sub-blocks of ``SUB`` tokens the products are computed pair by pair
from ``G_i - G_j`` itself; between sub-blocks they factor through the
row sub-block's start (``exp(G_i - G_start) * exp(G_start - G_j)``, both
<= 0). No clamp anywhere.

Packed rows: a sequence's first token resets the state. The operands fold
that into what they hold: ``A`` and ``Aqk`` are cut where the segment ids
differ, ``S0`` reaches only the tokens of the sequence the last chunk ended in
(``Wk``, ``Qb`` zeroed elsewhere), and ``S'`` keeps only what belongs to the
chunk's last token's sequence (``Kt``, ``d``). Padding (segment 0) takes no
part: its ``q, k, v, beta, g`` are zeroed on the way in. So the state pass
knows nothing of segments.

The SCALAR-DECAY form (``g`` [B, T, H]: one log-decay a head and token, the
gated delta rule of Qwen3-Next's ``gdn`` mixer; ``q, k`` may have fewer heads
than ``v``, each key head serving ``H / Hk`` consecutive value heads): the
exponent leaves the sum over channels, ``A = (K K^T) * D`` and ``Aqk = (Q K^T)
* D / sqrt(dk)`` with ``D[i, j] = exp(G_i - G_j)`` a [CHUNK, CHUNK] matrix a
head. ``"xla"``: ``_prep_scalar``, batched products that XLA differentiates
but for the inverse (``-T^T dT T^T``). ``"kernel"``: ``gdn_ops_fwd`` /
``gdn_ops_bwd`` (the file's end) keep a key head's chunks in VMEM from q, k,
v to the operands and back. Device scope ``gdn_operands`` either way. ``d`` is
that scalar over the ``dk`` channels, so the state pass (``kda_fwd_h`` / the
``lax.scan``), the packed-row and the padding rules are the per-channel form's
as they stand. It is held EQUAL to the per-channel form with ``g`` repeated
over the channels and ``q, k`` over the value heads (tests).

Device scope ``kda_scan`` (``utils/tracing.DEVICE_SCOPES``): everything in
this file. The convolution, the decays' and gates' projections and the
output norm are the layer's (``kda_prep``, ``models/transformer.py``).

What feeds this op has a kernel pair of its own since PR 55,
``ops/conv_prep.py``: the mixers' short convolution over q, k, v with its
``silu`` and the l2norm of q's and k's heads, bf16 in and out
(``conv_prep_fwd`` / ``conv_prep_bwd``; not named ``kda_*``, for the reason
above). ``conv_prep.prep_impl`` chooses it as ``scan_impl`` chooses here, and
narrower: a TPU backend, ONE device in the process, heads of exactly one lane
tile, rows of whole 512-token blocks; elsewhere the mixers keep their
``jax.numpy`` lines. ``models/transformer._short_conv`` is those lines'
convolution, the kernels' reference in tests, and ZAYA's own form (two taps, a
bias, no activation, a float32 result), which the kernels do not serve.
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
# chunks one grid step of an operand kernel takes TOGETHER, as one leading
# axis of every value: a chunk's operands are chains of dependent steps (15
# rows of substitution, 5 products at ``HIGHEST``), and alone a chunk waits
# out each step's latency
_OPS_CHUNKS = 8
# steps of an operand kernel's 16-step loops (a sub-block's tokens, the
# substitution's rows) taken in one straight line a trip. A layer's scan,
# forward + backward at 8,192 tokens (PERF.md, PR 35): 29.9 ms at 1, 24.7 at
# 2, 22.3 at 4, 20.9 at 8, 19.1 at 16, where the line is the whole loop and
# a kernel costs three times as long to trace and lower (Mosaic takes a
# ``fori_loop``'s own ``unroll`` only as 1 or all)
_UNROLL = 4
_HI = jax.lax.Precision.HIGHEST


class KDAStats(NamedTuple):
    """What one KDA layer counts (int32 scalars: the host sums them)."""

    tokens: jnp.ndarray  # real tokens through the scan
    chunk_slots: jnp.ndarray  # chunks run x CHUNK
    segments: jnp.ndarray  # sequence starts the scan reset at


def scan_impl(dk: Optional[int] = None, dv: Optional[int] = None) -> str:
    """The form this process runs, operands and state pass alike:
    ``"kernel"`` (``chunk_ops_*`` | ``gdn_ops_*``, ``kda_fwd_h`` /
    ``kda_bwd_dh``) on a TPU for head widths that are whole lane tiles,
    ``"xla"`` (``_prep`` | ``_prep_scalar``, the ``lax.scan``) elsewhere."""
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
    """[N, B]: the segment of the token before each chunk (-1 before a row's
    first). Made from the input alone: a value that depends on nothing is
    hoisted out of a scan over layers, and the jitted kernel call it sits in
    is then split there and lowered once more."""
    last = jax.lax.index_in_dim(seg, CHUNK - 1, axis=2, keepdims=False)
    return jnp.concatenate([last[:1] * 0 - 1, last[:-1]], axis=0)


# --------------------------------------------- the scalar-decay operands --
@jax.custom_vjp
def _unit_lower_inverse_t(n):
    """``_unit_lower_inverse`` with its cotangent by hand: for T = (I + N)^-1,
    dN = -T^T dT T^T, two products, where XLA's own would walk the
    substitution's rows back one by one."""
    return _unit_lower_inverse(n)


def _unit_lower_inverse_t_fwd(n):
    t = _unit_lower_inverse(n)
    return t, t


def _unit_lower_inverse_t_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.einsum(
        "...ij,...jk,...kl->...il", tt, dt, tt, precision=_HI
    ),)


_unit_lower_inverse_t.defvjp(
    _unit_lower_inverse_t_fwd, _unit_lower_inverse_t_bwd
)


def _prep_scalar(q, k, v, g, beta, seg, seg_in):
    """``_prep`` where the log-decay is ONE number a head and token: ``q, k``
    [N, B, Hk, C, dk], ``v`` [N, B, H, C, dv] (``H`` a multiple of ``Hk``:
    key head ``h // (H / Hk)`` serves value head ``h``), ``g`` and ``beta``
    float32 [N, B, H, C]. The same six operands, a value head each, ``d``
    [.., 1, dk] the chunk's scalar decay laid over the channels. Every
    exponent is a difference that is <= 0 here too: ``D[i, j] = exp(G_i -
    G_j)`` for j <= i, ``exp(G)``, ``exp(G_last - G)``."""
    f32, dt = jnp.float32, q.dtype
    dk = q.shape[-1]
    n, b, hk = q.shape[:3]
    h = v.shape[2]
    r = h // hk
    scale = dk ** -0.5
    valid = seg > 0  # [N, B, C]
    vm = valid[:, :, None, :, None]
    q, k, v = (jnp.where(vm, x, 0) for x in (q, k, v))
    g = jnp.where(valid[:, :, None], g, 0.0)
    beta = jnp.where(valid[:, :, None], beta, 0.0)
    G = jnp.cumsum(g, axis=-1)  # [N, B, H, C] inclusive, <= 0, decreasing
    idx = jnp.arange(CHUNK)
    lower = idx[:, None] >= idx[None, :]
    decay = jnp.exp(jnp.where(
        lower, G[..., :, None] - G[..., None, :], -jnp.inf
    ))  # [N, B, H, C, C]

    def pairs(x):  # (x K^T) a key head, laid over its value heads
        p = jnp.einsum(
            "...id,...jd->...ij", x, k, preferred_element_type=f32
        )
        return jnp.repeat(p, r, axis=2) if r > 1 else p

    same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
    a_kk = jnp.where(
        same & (idx[:, None] > idx[None, :]), pairs(k) * decay, 0.0
    )
    a_qk = jnp.where(same & lower, pairs(q) * decay, 0.0) * scale
    t_inv = _unit_lower_inverse_t(beta[..., None] * a_kk).astype(dt)

    def heads(x):  # a key head's rows for each of its value heads, float32
        x = x.astype(f32)
        return jnp.repeat(x, r, axis=2) if r > 1 else x

    qh, kh = heads(q), heads(k)
    e_g = jnp.exp(G)[..., None]
    from_in = (valid & (seg == seg_in[..., None]))[:, :, None, :, None]
    kbar = jnp.where(from_in, kh * e_g, 0.0)
    bcol = beta[..., None]

    def solved(x):  # T x, the MXU's operands in the compute dtype
        return jnp.einsum(
            "...ij,...jd->...id", t_inv, x.astype(dt),
            preferred_element_type=f32,
        )

    wk = solved(bcol * kbar)
    wv = solved(bcol * v.astype(f32))
    qb = jnp.where(from_in, qh * e_g, 0.0) * scale
    g_last = G[..., -1:]
    seg_last = seg[..., -1]  # [N, B]
    to_out = (valid & (seg == seg_last[..., None]))[:, :, None, :, None]
    kt = jnp.where(to_out, kh * jnp.exp(g_last - G)[..., None], 0.0)
    carries = ((seg_last == seg_in) & (seg_last > 0))[:, :, None, None]
    d = jnp.where(carries, jnp.exp(g_last), 0.0)  # [N, B, H, 1]
    d = jnp.broadcast_to(d[..., None], (n, b, h, 1, dk))
    return (wk.astype(dt), wv.astype(dt), qb.astype(dt), kt.astype(dt),
            a_qk.astype(dt), d)


# ------------------------------------------------------------ state pass --
def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
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


def _kernel_chunks(n: int, nb: int = _KERNEL_CHUNKS) -> int:
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


@functools.partial(jax.jit, static_argnames=("save_states", "interpret"))
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


@functools.partial(jax.jit, static_argnames="interpret")
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
        return _state_fwd_kernel(
            ops, save_states=save_states, interpret=interpret
        )
    return _state_fwd_xla(ops, save_states)


# ------------------------------------------- the operands as kernels --
def _bdot(a, b, dims, precision=None):
    """``_dot`` a chunk: the chunks' matmuls are independent of each other."""
    return jnp.stack([
        _dot(a[i], b[i], dims, precision) for i in range(a.shape[0])
    ])


def _loop(body, carry):
    """``carry = body(i, carry)`` for the ``SUB`` tokens of a sub-block:
    ``_UNROLL`` steps a trip in one straight line, ``SUB / _UNROLL`` trips."""

    def trip(i, carry):
        for u in range(_UNROLL):
            carry = body(i * _UNROLL + u, carry)
        return carry

    return jax.lax.fori_loop(0, SUB // _UNROLL, trip, carry)


def _lane(x, t):
    """Lane ``t`` (traced) of ``x`` [.., C] as [.., 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape[-2:], 1)
    return jnp.sum(jnp.where(lane == t, x, 0.0), axis=-1, keepdims=True)


def _rows_of(x):  # each sub-block's SUB rows: [nb, C, d] -> NSUB x [nb, SUB, d]
    return [x[:, a * SUB:(a + 1) * SUB] for a in range(_NSUB)]


def _block_parts(q, k, v, g, beta, marks, g_scr, k_scr, n_scr, e_scr=None):
    """``nb`` chunks of one head inside a kernel: ``_prep``'s values
    in VMEM, with the chunks as a leading axis, so that each step of a chain
    of dependent steps (the substitution's rows, the block products) is
    taken for all of them at once: alone a chunk waits out every step's
    latency. The refs: ``q, k, g`` [nb, 1, C, dk], ``v`` [.., dv]; ``beta``
    [nb, 1, 1, C]; ``marks`` [nb, 1, 4, C] float32 rows: the segment ids,
    and as 0 | 1 the tokens S0 reaches, the tokens that reach S', whether
    the state carries (``_marks``). Tokens lie on sublanes and channels on
    lanes, so a token's row is read back from scratch broadcast over the
    sublanes (``g_scr``, ``k_scr`` [nb, C, dk], ``n_scr`` [nb, C, C]
    float32). ``e_scr`` [nb, C * SUB, dk] keeps every exp(G_i - G_j) of the
    sub-blocks for the backward."""
    f32, dt = jnp.float32, q.dtype
    nb, _, c, dk = q.shape
    scale = dk ** -0.5
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = ri == ci

    def column(row):  # [nb, 1, C] -> [nb, C, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)

    marks = marks[:, 0]
    seg_row = marks[:, 0:1]
    seg_col = column(seg_row)
    valid = seg_col > 0
    from_in = column(marks[:, 1:2]) > 0
    to_out = column(marks[:, 2:3]) > 0
    qf = jnp.where(valid, q[:, 0].astype(f32), 0.0)
    kf = jnp.where(valid, k[:, 0].astype(f32), 0.0)
    vf = jnp.where(valid, v[:, 0].astype(f32), 0.0)
    g = jnp.where(valid, g[:, 0], 0.0)
    beta = jnp.where(valid, column(beta[:, 0]), 0.0)  # [nb, C, 1]
    tril = jnp.broadcast_to(jnp.where(ri >= ci, 1.0, 0.0), (nb, c, c))
    big_g = _bdot(tril, g, _NN, _HI)  # the running sum
    g_scr[...] = big_g
    k_scr[...] = kf

    # --- pairs: inside a sub-block the differences themselves, a column of
    # the pair matrices a token; between sub-blocks through the start
    tok = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    sub_i = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    g_a, k_a, q_a = _rows_of(big_g), _rows_of(kf), _rows_of(qf)
    rows, cols, kk, qk = [], [None], [], []
    for a in range(_NSUB):
        start = big_g[:, a * SUB:a * SUB + 1] - g[:, a * SUB:a * SUB + 1]
        row = jnp.exp(g_a[a] - start)  # [nb, SUB, dk], <= 1
        rows.append(row)
        if a:
            col = jnp.exp(jnp.where(tok < a * SUB, start - big_g, -jnp.inf))
            cols.append(col)
            off = _bdot(
                jnp.concatenate([k_a[a] * row, q_a[a] * row], 1).astype(dt),
                (kf * col).astype(dt), _NT,
            )  # [nb, 2 SUB, C]
            kk_a, qk_a = off[:, :SUB], off[:, SUB:]
        else:
            kk_a = qk_a = jnp.zeros((nb, SUB, c), f32)

        def pair(j, acc, a=a):
            t = a * SUB + j
            e = jnp.exp(jnp.where(
                sub_i >= j, g_a[a] - g_scr[:, pl.ds(t, 1), :], -jnp.inf
            ))  # [nb, SUB, dk]: exp(G_i - G_t), rows i >= t
            if e_scr is not None:
                e_scr[:, pl.ds(pl.multiple_of(t * SUB, SUB), SUB), :] = e
            kt_e = k_scr[:, pl.ds(t, 1), :] * e
            return tuple(
                jnp.where(
                    lane == t, jnp.sum(x * kt_e, axis=-1, keepdims=True), y
                )
                for x, y in zip((k_a[a], q_a[a]), acc)
            )

        kk_a, qk_a = _loop(pair, (kk_a, qk_a))
        kk.append(kk_a)
        qk.append(qk_a)
    same = seg_col == seg_row
    strict = same & (ri > ci)
    lower = same & (ri >= ci)
    a_kk = jnp.where(strict, jnp.concatenate(kk, 1), 0.0)
    a_qk = jnp.where(lower, jnp.concatenate(qk, 1), 0.0) * scale

    # --- T^T = (I + Diag(beta) A)^-T: ``_unit_lower_inverse`` transposed.
    # The diagonal sub-blocks a row a step, all four at once (x[k, i] is
    # the inverse's [i, k]); then the blocks under them: with D the
    # diagonal blocks' inverse and L the rest of N, T = D - D L T three
    # times over is the block substitution (L's fourth power is zero)
    n = beta * a_kk
    n_scr[...] = n
    blk = (ri // SUB) == (ci // SUB)
    eye_f = jnp.broadcast_to(jnp.where(eye, 1.0, 0.0), (nb, c, c))

    def substitute(i, x):
        n_row = jnp.concatenate([
            jnp.broadcast_to(
                n_scr[:, pl.ds(a * SUB + i, 1), :], (nb, SUB, c)
            )
            for a in range(_NSUB)
        ], 1)
        hit = jnp.sum(n_row * x, axis=-1, keepdims=True)  # x is 0 off blk
        return jnp.where(blk & (ci % SUB == i), eye_f - hit, x)

    x = _loop(substitute, eye_f)  # row 0 finds nothing above it
    m_t = _bdot(jnp.where(blk, 0.0, n), x, _TN, _HI)  # (D L)^T
    t_t = x
    for _ in range(_NSUB - 1):
        t_t = x - _bdot(t_t, m_t, _NN, _HI)

    e_g = jnp.exp(big_g)
    g_last = big_g[:, c - 1:c]
    e_out = jnp.exp(g_last - big_g)
    kbar = jnp.where(from_in, kf * e_g, 0.0)
    t_c = t_t.astype(dt)
    return dict(
        qf=qf, kf=kf, vf=vf, beta=beta, valid=valid, from_in=from_in,
        to_out=to_out, strict=strict, eye=eye, rows=rows, cols=cols,
        a_kk=a_kk, a_qk=a_qk, t_c=t_c, e_g=e_g, e_out=e_out, kbar=kbar,
        wk=_bdot(t_c, (beta * kbar).astype(dt), _TN),
        wv=_bdot(t_c, (beta * vf).astype(dt), _TN),
        qb=jnp.where(from_in, qf * e_g, 0.0) * scale,
        kt=jnp.where(to_out, kf * e_out, 0.0),
        d=jnp.where(column(marks[:, 3:4]) > 0, e_g, 0.0)[:, c - 1:c],
    )


def _store_rows(ref, x):
    """[nb, 1, d] into a ref [nb, 1, 1, d], a chunk at a time (all at once
    is more than the compiler takes)."""
    for i in range(x.shape[0]):
        ref[i, 0] = x[i]


def _ops_fwd_kernel(q, k, v, g, beta, marks, wk, wv, qb, kt, aqk, d,
                    g_scr, k_scr, n_scr):
    p = _block_parts(q, k, v, g, beta, marks, g_scr, k_scr, n_scr)
    for ref, name in ((wk, "wk"), (wv, "wv"), (qb, "qb"), (kt, "kt"),
                      (aqk, "a_qk")):
        ref[:, 0] = p[name].astype(ref.dtype)
    _store_rows(d, p["d"])


def _ops_bwd_kernel(q, k, v, g, beta, d_wk, d_wv, d_qb, d_kt, d_aqk, d_d,
                    marks, d_q, d_k, d_v, d_g, d_beta,
                    g_scr, k_scr, n_scr, e_scr, kj_scr):
    """``_prep``'s backward by hand, on ``_block_parts``' values: from the
    operands' cotangents to those of ``q, k, v, g, beta``, every exponential
    as the forward part left it (``e_scr``, ``rows``, ``cols``, ``e_g``,
    ``e_out``). ``kj_scr`` [nb, C, dk] gathers a token's sums as a key."""
    f32, dt = jnp.float32, q.dtype
    p = _block_parts(q, k, v, g, beta, marks, g_scr, k_scr, n_scr, e_scr)
    nb, c, dk = p["kf"].shape
    scale = dk ** -0.5
    kf, qf, beta_c = p["kf"], p["qf"], p["beta"]
    # through Wk = T (beta kbar), Wv = T (beta v) to N = Diag(beta) A, with
    # T = (I + N)^-1: dT = dWk (beta kbar)^T + dWv (beta v)^T and
    # dN = -T^T dT T^T, so dN = -(T^T dWk) Wk^T - (T^T dWv) Wv^T
    d_xk = _bdot(p["t_c"], d_wk[:, 0], _NN)
    d_xv = _bdot(p["t_c"], d_wv[:, 0], _NN)
    d_n = -jnp.where(
        p["strict"],
        _bdot(jnp.concatenate([d_xk, d_xv], -1),
              jnp.concatenate([p["wk"], p["wv"]], -1), _NT, _HI),
        0.0,
    )
    g_beta = (
        jnp.sum(d_n * p["a_kk"], axis=-1, keepdims=True)
        + jnp.sum(d_xk * p["kbar"], axis=-1, keepdims=True)
        + jnp.sum(d_xv * p["vf"], axis=-1, keepdims=True)
    )
    d_kbar = beta_c * d_xk
    pm = beta_c * d_n  # dA, j < i
    d_aqk_f = d_aqk[:, 0].astype(f32)
    rm = jnp.where(p["strict"], d_aqk_f, 0.0) * scale  # dAqk, j < i
    r_ii = jnp.sum(
        jnp.where(p["eye"], d_aqk_f, 0.0), axis=-1, keepdims=True
    ) * scale
    # the pairs, from the forward's one evaluation of each exponential
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    k_a, q_a = _rows_of(kf), _rows_of(qf)
    p_a, r_a = _rows_of(pm), _rows_of(rm)
    dk_i, dq_i = [], []
    dk_off = jnp.zeros_like(kf)
    for a in range(_NSUB):
        row = p["rows"][a]
        if a:
            col = p["cols"][a]
            before = lane < a * SUB
            pr = jnp.concatenate([
                jnp.where(before, p_a[a], 0.0), jnp.where(before, r_a[a], 0.0)
            ], 1).astype(dt)  # [nb, 2 SUB, C]
            at_row = _bdot(pr, (kf * col).astype(dt), _NN)  # [nb, 2 SUB, dk]
            dk_a, dq_a = at_row[:, :SUB] * row, at_row[:, SUB:] * row
            dk_off += col * _bdot(
                pr,
                jnp.concatenate([k_a[a] * row, q_a[a] * row], 1).astype(dt),
                _TN,
            )
        else:
            dk_a = dq_a = jnp.zeros((nb, SUB, dk), f32)

        def pair(j, acc, a=a):
            t = a * SUB + j
            e = e_scr[:, pl.ds(pl.multiple_of(t * SUB, SUB), SUB), :]
            kt_e = k_scr[:, pl.ds(t, 1), :] * e
            p_t, r_t = _lane(p_a[a], t), _lane(r_a[a], t)
            kj_scr[:, pl.ds(t, 1), :] = jnp.sum(
                (p_t * k_a[a] + r_t * q_a[a]) * e, axis=1, keepdims=True
            )
            return acc[0] + p_t * kt_e, acc[1] + r_t * kt_e

        dk_a, dq_a = _loop(pair, (dk_a, dq_a))
        dk_i.append(dk_a)
        dq_i.append(dq_a)
    dk_i, dq_i = jnp.concatenate(dk_i, 1), jnp.concatenate(dq_i, 1)
    dk_j = kj_scr[...] + dk_off
    e_g, e_out = p["e_g"], p["e_out"]
    from_in, to_out, valid = p["from_in"], p["to_out"], p["valid"]
    d_qb_f = jnp.where(from_in, d_qb[:, 0].astype(f32) * e_g, 0.0) * scale
    d_kt_f = jnp.where(to_out, d_kt[:, 0].astype(f32) * e_out, 0.0)
    g_q = dq_i + r_ii * kf + d_qb_f
    g_k = (
        dk_i + dk_j + r_ii * qf + jnp.where(from_in, d_kbar * e_g, 0.0)
        + d_kt_f
    )
    kt_term = d_kt_f * kf
    g_big = (
        kf * (dk_i - dk_j) + qf * dq_i + d_kbar * p["kbar"] + d_qb_f * qf
        - kt_term
    )
    g_last = jnp.sum(kt_term, axis=1, keepdims=True) + d_d[:, 0] * p["d"]
    tok = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    g_big = g_big + jnp.where(tok == c - 1, g_last, 0.0)
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    triu = jnp.broadcast_to(jnp.where(ri <= ci, 1.0, 0.0), (nb, c, c))
    g_g = _bdot(triu, g_big, _NN, _HI)  # the running sum from the end
    d_q[:, 0] = jnp.where(valid, g_q, 0.0).astype(d_q.dtype)
    d_k[:, 0] = jnp.where(valid, g_k, 0.0).astype(d_k.dtype)
    d_v[:, 0] = jnp.where(valid, beta_c * d_xv, 0.0).astype(d_v.dtype)
    d_g[:, 0] = jnp.where(valid, g_g, 0.0)
    _store_rows(d_beta, jnp.sum(
        jnp.where(p["eye"] & valid, g_beta, 0.0), axis=1, keepdims=True
    ))


def _marks(seg):
    """[N, B, 4, C] float32 for the operand kernels, a row each: the segment
    ids; 1 where S0 reaches the token; 1 where the token reaches S'; 1
    where the state carries over the chunk."""
    valid = seg > 0
    seg_in, seg_last = _seg_in(seg)[..., None], seg[..., -1:]
    return jnp.stack([
        seg,
        valid & (seg == seg_in),
        valid & (seg == seg_last),
        jnp.broadcast_to((seg_last == seg_in) & (seg_last > 0), seg.shape),
    ], axis=2).astype(jnp.float32)


def _ops_call(backward, arrays, out_like, interpret):
    """``chunk_ops_bwd`` | ``chunk_ops_fwd`` over (head, block of chunks).
    ``arrays``: [N, B, H, ...] a head, except the last, the marks
    [N, B, 4, C]; ``out_like``: (trailing shape, dtype) of each output."""
    n, b, h, c, dk = arrays[0].shape
    nb = _kernel_chunks(n, _OPS_CHUNKS)
    flat = [_flat(x) for x in arrays[:-1]] + [arrays[-1]]
    f32 = jnp.float32
    scratch = [pltpu.VMEM((nb, c, dk), f32), pltpu.VMEM((nb, c, dk), f32),
               pltpu.VMEM((nb, c, c), f32)]
    if backward:  # every exp(G_i - G_j) of the sub-blocks; a token's sums
        scratch += [pltpu.VMEM((nb, c * SUB, dk), f32),
                    pltpu.VMEM((nb, c, dk), f32)]

    def spec(shape, at=lambda i, j: (j, i, 0, 0)):
        return pl.BlockSpec((nb, 1) + tuple(shape), at)

    out_shape = [
        jax.ShapeDtypeStruct((n, b * h) + tuple(shape), dtype)
        for shape, dtype in out_like
    ]
    out = pl.pallas_call(
        _ops_bwd_kernel if backward else _ops_fwd_kernel,
        grid=(b * h, n // nb),
        in_specs=[spec(x.shape[2:]) for x in flat[:-1]]
        + [spec(flat[-1].shape[2:], lambda i, j: (j, i // h, 0, 0))],
        out_specs=[spec(x.shape[2:]) for x in out_shape],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="chunk_ops_bwd" if backward else "chunk_ops_fwd",
    )(*flat)
    return tuple(x.reshape(n, b, h, *x.shape[2:]) for x in out)


@functools.partial(jax.jit, static_argnames="interpret")
def _operands_kernel(q, k, v, g, beta, seg, interpret=False):
    """``_prep`` as the kernel ``chunk_ops_fwd``."""
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    dt = q.dtype
    return _ops_call(
        False, (q, k, v, g, beta[..., None, :], _marks(seg)),
        [((c, dk), dt), ((c, dv), dt), ((c, dk), dt), ((c, dk), dt),
         ((c, c), dt), ((1, dk), jnp.float32)],
        interpret,
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _operands_bwd_kernel(q, k, v, g, beta, seg, d_ops, interpret=False):
    """``jax.vjp(_prep)`` as the kernel ``chunk_ops_bwd``: from the state
    pass's cotangents of the six operands to those of q, k, v, g, beta."""
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    f32 = jnp.float32
    *grads, d_beta = _ops_call(
        True, (q, k, v, g, beta[..., None, :]) + tuple(d_ops) + (_marks(seg),),
        [((c, dk), q.dtype), ((c, dk), k.dtype), ((c, dv), v.dtype),
         ((c, dk), f32), ((1, c), f32)],
        interpret,
    )
    return tuple(grads) + (d_beta[..., 0, :],)


# -------------------------------------------------------------- the op --
def _scalar_decay(g, beta) -> bool:
    """One log-decay a head and token: ``g`` is of ``beta``'s rank."""
    return g.ndim == beta.ndim


def _operands(q, k, v, g, beta, seg, impl="xla", interpret=False):
    if _scalar_decay(g, beta):  # its own two forms, at the file's end
        with device_scope("gdn_operands"):
            return _scalar_operands(
                q, k, v, g, beta, seg, impl, interpret
            )
    if impl == "kernel":
        return _operands_kernel(q, k, v, g, beta, seg, interpret=interpret)
    return _in_blocks(_prep, (q, k, v, g, beta, seg, _seg_in(seg)))


def _chunked_plain(q, k, v, g, beta, seg):
    """The chunked rule with XLA's own differentiation throughout: what the
    hand-written backward is held equal to (tests)."""
    return _state_fwd_xla(_operands(q, k, v, g, beta, seg))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunked(q, k, v, g, beta, seg, impl, interpret):
    ops = _operands(q, k, v, g, beta, seg, impl, interpret)
    return _state_fwd(ops, impl, interpret)


def _chunked_fwd(q, k, v, g, beta, seg, impl, interpret):
    out = _chunked(q, k, v, g, beta, seg, impl, interpret)
    return out, (q, k, v, g, beta, seg)


def _operands_bwd_xla(q, k, v, g, beta, seg, d_ops, prep=_prep):
    """XLA's own differentiation of ``prep`` (``_prep``, or ``_prep_scalar``
    with its inverse's cotangent by hand), a block of chunks at a time, so
    that what the differentiation keeps is a block's: the CPU's path, what
    ``chunk_ops_bwd`` is held equal to and, with ``_prep_scalar``, what
    ``gdn_ops_bwd`` is."""

    def prep_bwd(q, k, v, g, beta, seg, seg_in, *cts):
        _, vjp = jax.vjp(
            lambda *x: prep(*x, seg, seg_in), q, k, v, g, beta
        )
        return vjp(cts)

    return _in_blocks(
        prep_bwd, (q, k, v, g, beta, seg, _seg_in(seg)) + tuple(d_ops)
    )


def _chunked_bwd(impl, interpret, res, do):
    q, k, v, g, beta, seg = res
    ops = _operands(q, k, v, g, beta, seg, impl, interpret)
    _, states = _state_fwd(ops, impl, interpret, save_states=True)
    if impl == "kernel":
        d_ops = _state_bwd_kernel(ops, states, do, interpret=interpret)
    else:
        d_ops = _state_bwd_xla(ops, states, do)
    if _scalar_decay(g, beta):
        with device_scope("gdn_operands"):
            grads = _scalar_operands_bwd(
                q, k, v, g, beta, seg, d_ops, impl, interpret
            )
    elif impl == "kernel":
        grads = _operands_bwd_kernel(
            q, k, v, g, beta, seg, d_ops, interpret=interpret
        )
    else:
        grads = _operands_bwd_xla(q, k, v, g, beta, seg, d_ops)
    return tuple(grads) + (None,)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _to_chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // CHUNK, CHUNK, h, *x.shape[3:])
    return x.transpose((1, 0, 3, 2) + tuple(range(4, x.ndim)))


def _chunk_inputs(q, k, v, g, beta, seg):
    """A batch of rows as the op takes it: padded to whole chunks (segment
    0), ``q, k, v, g`` [N, B, H, C, d] with ``g`` float32, ``beta`` float32
    [N, B, H, C], ``seg`` int32 [N, B, C]."""
    b, t = seg.shape
    pad = -t % CHUNK
    if pad:  # a row that is no whole number of chunks
        q, k, v, g, beta, seg = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta, seg)
        )
    def scalars(x):  # [B, T, H] -> [N, B, H, C] float32
        return _to_chunks(x.astype(jnp.float32)[..., None])[..., 0]

    chunks = [_to_chunks(x) for x in (q, k, v)]
    chunks.append(
        scalars(g) if _scalar_decay(g, beta)
        else _to_chunks(g.astype(jnp.float32))
    )
    beta_c = scalars(beta)
    seg_c = seg.astype(jnp.int32).reshape(b, -1, CHUNK).transpose(1, 0, 2)
    return (*chunks, beta_c, seg_c)


def kda_scan(
    q: jnp.ndarray,  # [B, T, H, dk], l2-normed
    k: jnp.ndarray,  # [B, T, H, dk], l2-normed
    v: jnp.ndarray,  # [B, T, H, dv]
    # [B, T, H, dk] float32 log-decay, <= 0; or [B, T, H], ONE a head and
    # token (the scalar-decay form: then ``q, k`` [B, T, Hk, dk], H % Hk == 0)
    g: jnp.ndarray,
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
    (b, t, _, dk), h = q.shape, v.shape[2]
    impl = impl or scan_impl(dk, v.shape[-1])
    with device_scope("kda_scan"):
        a = _chunk_inputs(q, k, v, g, beta, segment_ids)
        pad = -t % CHUNK
        if custom_backward:
            o = _chunked(*a, impl, interpret)
        else:
            o = _chunked_plain(*a)
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
    serves); ``g`` is split as its rank says, a channel's or a head's decay.
    The rule never crosses a row, so this is exact; a sequence split would
    need the state handed from device to device."""
    if mesh.size == 1:
        return None
    if mesh.shape["seq"] > 1:
        raise ValueError(
            "gated delta-rule attention has no sequence-parallel form "
            f"(seq_parallel_size={mesh.shape['seq']}): ROADMAP B"
        )
    rows = ("data", "fsdp")
    x_spec, head_spec = P(rows, None, "tensor", None), P(rows, None, "tensor")

    def kda_fn(q, k, v, g, beta, segment_ids):
        return jax.shard_map(
            functools.partial(kda_scan, token_axes=rows),
            mesh=mesh,
            in_specs=(x_spec, x_spec, x_spec,
                      head_spec if _scalar_decay(g, beta) else x_spec,
                      head_spec, P(rows, None)),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(q, k, v, g, beta, segment_ids)

    return kda_fn


# ------------------------------- the scalar-decay operands as kernels --
# At the file's end, and the lines above keep their numbers: a Pallas body
# carries the line numbers of its callers, so the per-channel kernels' bodies
# (and the cache entries of the programs that hold them) stay as they were.
def _mdot(a, b, dims, precision=None):
    """``_bdot`` as ONE batched product: an operation in the kernel's body
    where ``_bdot`` leaves one a chunk (this compiler takes the batched form,
    and a body is traced and lowered at every set-up)."""
    (ca,), (cb,) = dims
    return jax.lax.dot_general(
        a, b, (((ca + 1,), (cb + 1,)), ((0,), (0,))), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _items(ref):
    """A ref [nb, r, ..] as [m, ..] items, a value head after the other."""
    return jnp.concatenate([ref[:, j] for j in range(ref.shape[1])])


def _gdn_parts(q, k, v, g, beta, marks, n_scr):
    """``_prep_scalar``'s values in VMEM for ``nb`` chunks of one KEY head
    and its ``r`` value heads, as ``_block_parts`` keeps ``_prep``'s: the
    (value head, chunk) pairs are one leading axis of ``m = r nb`` items, so
    that each step of the substitution is taken for all of them at once,
    and the two pair products ``K K^T``, ``Q K^T`` are made once a key head.
    The refs: ``q, k`` [nb, 1, C, dk]; ``v`` [nb, r, C, dv]; ``g, beta``
    [nb, r, 1, C] float32; ``marks`` [nb, 1, 4, C] (``_marks``); ``n_scr``
    [m, C, C] float32, from which a row of N is read back broadcast over the
    sublanes. A token's scalars lie on the lanes as they arrive; the running
    sum turns them onto the sublanes (a masked sum over the lanes, float32
    on the VPU: a [C] vector has no product worth the MXU), and the row form
    of ``G`` is a copy of that column, so that ``D``'s diagonal is exp(0)."""
    f32, dt = jnp.float32, q.dtype
    nb, r, c, _ = v.shape
    dk = q.shape[-1]
    scale = dk ** -0.5
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = ri == ci

    def column(row):  # [.., 1, C] -> [.., C, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)

    def heads(x):  # a key head's [nb, ..] for each of its value heads
        return jnp.concatenate([x] * r) if r > 1 else x

    marks = marks[:, 0]
    seg_row = marks[:, 0:1]
    seg_col = column(seg_row)
    valid = seg_col > 0  # [nb, C, 1]
    qf = jnp.where(valid, q[:, 0].astype(f32), 0.0)
    kf = jnp.where(valid, k[:, 0].astype(f32), 0.0)
    qc, kc = qf.astype(dt), kf.astype(dt)
    pairs = _mdot(jnp.concatenate([kc, qc], 1), kc, _NT)  # [nb, 2 C, C]
    seg_row, seg_col = heads(seg_row), heads(seg_col)
    valid_m = seg_col > 0  # [m, C, 1]
    from_in = heads(column(marks[:, 1:2])) > 0
    to_out = heads(column(marks[:, 2:3])) > 0
    g_row = jnp.where(seg_row > 0, _items(g), 0.0)  # [m, 1, C]
    beta = jnp.where(valid_m, column(_items(beta)), 0.0)  # [m, C, 1]
    vf = jnp.where(valid_m, _items(v).astype(f32), 0.0)
    big_g = jnp.sum(
        jnp.where(ci <= ri, g_row, 0.0), axis=-1, keepdims=True
    )  # [m, C, 1]: the running sum, <= 0, decreasing
    g_back = jnp.sum(jnp.where(eye, big_g, 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(ri >= ci, big_g - g_back, -jnp.inf))
    same = seg_col == seg_row
    strict = same & (ri > ci)
    lower = same & (ri >= ci)
    a_kk = jnp.where(strict, heads(pairs[:, :c]) * decay, 0.0)
    rd = jnp.where(lower, heads(pairs[:, c:]) * decay, 0.0)  # Aqk / scale

    # --- T^T = (I + Diag(beta) A)^-T, as ``_block_parts`` has it. (The
    # four diagonal blocks side by side on the lanes, a row's sum as four
    # lane rolls in place of the reduction, read 12.1 ms where this reads
    # 7.3: PERF.md, PR 53)
    n = beta * a_kk
    n_scr[...] = n
    m = n.shape[0]
    blk = (ri // SUB) == (ci // SUB)
    eye_f = jnp.broadcast_to(jnp.where(eye, 1.0, 0.0), (m, c, c))

    def substitute(i, x):
        n_row = jnp.concatenate([
            jnp.broadcast_to(n_scr[:, pl.ds(a * SUB + i, 1), :], (m, SUB, c))
            for a in range(_NSUB)
        ], 1)
        hit = jnp.sum(n_row * x, axis=-1, keepdims=True)  # x is 0 off blk
        return jnp.where(blk & (ci % SUB == i), eye_f - hit, x)

    x = _loop(substitute, eye_f)  # row 0 finds nothing above it
    m_t = _mdot(jnp.where(blk, 0.0, n), x, _TN, _HI)  # (D L)^T
    t_t = x
    for _ in range(_NSUB - 1):
        t_t = x - _mdot(t_t, m_t, _NN, _HI)

    e_g = jnp.exp(big_g)
    e_out = jnp.exp(big_g[:, c - 1:c] - big_g)
    kf_m, qf_m = heads(kf), heads(qf)
    kbar = jnp.where(from_in, kf_m * e_g, 0.0)
    t_c = t_t.astype(dt)
    carries = heads(column(marks[:, 3:4])) > 0
    return dict(
        qc=qc, kc=kc, kf=kf_m, qf=qf_m, vf=vf, beta=beta,
        valid=valid, valid_m=valid_m, valid_row=seg_row > 0, from_in=from_in,
        to_out=to_out, strict=strict, lower=lower, eye=eye, decay=decay,
        a_kk=a_kk, rd=rd, t_c=t_c, e_g=e_g, e_out=e_out, kbar=kbar,
        wk=_mdot(t_c, (beta * kbar).astype(dt), _TN),
        wv=_mdot(t_c, (beta * vf).astype(dt), _TN),
        qb=jnp.where(from_in, qf_m * e_g, 0.0) * scale,
        kt=jnp.where(to_out, kf_m * e_out, 0.0),
        aqk=rd * scale,
        d=jnp.where(carries, e_g, 0.0)[:, c - 1:c],  # [m, 1, 1]
    )


def _store_heads(ref, x):
    """[m, ..] items into a ref [nb, r, ..], a value head after the other."""
    nb, r = ref.shape[:2]
    for j in range(r):
        ref[:, j] = x[j * nb:(j + 1) * nb].astype(ref.dtype)


def _gdn_fwd_kernel(q, k, v, g, beta, marks, wk, wv, qb, kt, aqk, d, n_scr):
    p = _gdn_parts(q, k, v, g, beta, marks, n_scr)
    for ref, name in ((wk, "wk"), (wv, "wv"), (qb, "qb"), (kt, "kt"),
                      (aqk, "aqk")):
        _store_heads(ref, p[name])
    nb, r, _, dk = d.shape
    d_lanes = jnp.broadcast_to(p["d"], (nb * r, 1, dk))
    for j in range(r):  # a chunk at a time, as ``_store_rows``
        for i in range(nb):
            d[i, j] = d_lanes[j * nb + i]


def _gdn_bwd_kernel(q, k, v, g, beta, d_wk, d_wv, d_qb, d_kt, d_aqk, d_d,
                    marks, d_q, d_k, d_v, d_g, d_beta, n_scr):
    """``_prep_scalar``'s backward by hand on ``_gdn_parts``' values, as
    ``_ops_bwd_kernel`` without its pair loops: the decay matrix is there
    whole, so the pairs' cotangents are four products a key head (its value
    heads' summed first), and ``G``'s are row and column sums."""
    f32, dt = jnp.float32, q.dtype
    p = _gdn_parts(q, k, v, g, beta, marks, n_scr)
    nb, r, c, _ = v.shape
    scale = q.shape[-1] ** -0.5

    def per_key(x):  # [m, ..] -> [nb, ..]: a key head's value heads summed
        return sum(x[j * nb:(j + 1) * nb] for j in range(r))

    kf, qf, beta_c = p["kf"], p["qf"], p["beta"]
    # dN = -(T^T dWk) Wk^T - (T^T dWv) Wv^T  (``_ops_bwd_kernel``)
    d_xk = _mdot(p["t_c"], _items(d_wk), _NN)
    d_xv = _mdot(p["t_c"], _items(d_wv), _NN)
    d_n = -jnp.where(
        p["strict"],
        _mdot(jnp.concatenate([d_xk, d_xv], -1),
              jnp.concatenate([p["wk"], p["wv"]], -1), _NT, _HI),
        0.0,
    )
    g_beta = (
        jnp.sum(d_n * p["a_kk"], axis=-1, keepdims=True)
        + jnp.sum(d_xk * p["kbar"], axis=-1, keepdims=True)
        + jnp.sum(d_xv * p["vf"], axis=-1, keepdims=True)
    )
    d_kbar = beta_c * d_xk
    d_a = beta_c * d_n  # dA, j < i
    d_rd = jnp.where(p["lower"], _items(d_aqk).astype(f32), 0.0) * scale
    # P = K K^T, R = Q K^T a key head: dP = dA * D, dR = dAqk * D * scale
    pr = jnp.concatenate(
        [per_key(d_a * p["decay"]), per_key(d_rd * p["decay"])], 1
    ).astype(dt)  # [nb, 2 C, C]
    at_row = _mdot(pr, p["kc"], _NN)  # dP K | dR K
    at_col = _mdot(pr, jnp.concatenate([p["kc"], p["qc"]], 1), _TN)
    # D = exp(G_i - G_j): dG_i += sum_j (dD * D)[i, j], dG_j -= sum_i
    d_dd = d_a * p["a_kk"] + d_rd * p["rd"]
    e_g, e_out = p["e_g"], p["e_out"]
    from_in, to_out = p["from_in"], p["to_out"]
    d_qb_f = jnp.where(from_in, _items(d_qb).astype(f32) * e_g, 0.0) * scale
    d_kt_f = jnp.where(to_out, _items(d_kt).astype(f32) * e_out, 0.0)
    g_q = at_row[:, c:] + per_key(d_qb_f)
    g_k = (
        at_row[:, :c] + at_col
        + per_key(jnp.where(from_in, d_kbar * e_g, 0.0) + d_kt_f)
    )
    kt_term = jnp.sum(d_kt_f * kf, axis=-1, keepdims=True)  # [m, C, 1]
    g_big = (
        jnp.sum(d_dd, axis=-1, keepdims=True)
        - jnp.sum(
            jnp.where(p["eye"], jnp.sum(d_dd, axis=1, keepdims=True), 0.0),
            axis=-1, keepdims=True,
        )
        + jnp.sum(d_kbar * p["kbar"] + d_qb_f * qf, axis=-1, keepdims=True)
        - kt_term
    )
    g_last = (
        jnp.sum(kt_term, axis=1, keepdims=True)
        + jnp.sum(_items(d_d), axis=-1, keepdims=True) * p["d"]
    )  # [m, 1, 1]
    tok = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    g_big = g_big + jnp.where(tok == c - 1, g_last, 0.0)
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    g_g = jnp.sum(
        jnp.where(ri >= ci, g_big, 0.0), axis=1, keepdims=True
    )  # [m, 1, C]: the running sum from the end, back on the lanes
    valid, valid_m = p["valid"], p["valid_m"]
    d_q[:, 0] = jnp.where(valid, g_q, 0.0).astype(d_q.dtype)
    d_k[:, 0] = jnp.where(valid, g_k, 0.0).astype(d_k.dtype)
    _store_heads(d_v, jnp.where(valid_m, beta_c * d_xv, 0.0))
    _store_heads(d_g, jnp.where(p["valid_row"], g_g, 0.0))
    _store_heads(d_beta, jnp.sum(
        jnp.where(p["eye"] & valid_m, g_beta, 0.0), axis=1, keepdims=True
    ))


def _gdn_call(backward, key_arrays, head_arrays, marks, out_like, interpret):
    """``gdn_ops_bwd`` | ``gdn_ops_fwd`` over (row x key head, block of
    chunks). ``key_arrays`` [N, B, Hk, ...] a key head, ``head_arrays``
    [N, B, H, ...] a value head (a grid step takes the ``H / Hk`` of its key
    head together), ``marks`` [N, B, 4, C]; ``out_like``: (a key head's or a
    value head's, trailing shape, dtype) of each output."""
    n, b, hk, c, _ = key_arrays[0].shape
    h = head_arrays[0].shape[2]
    r = h // hk
    nb = _kernel_chunks(n, _OPS_CHUNKS)

    def spec(heads, shape):
        return pl.BlockSpec(
            (nb, heads) + tuple(shape), lambda i, j: (j, i, 0, 0)
        )

    out_shape = [
        jax.ShapeDtypeStruct((n, b * (hk if key else h)) + tuple(shape), dtype)
        for key, shape, dtype in out_like
    ]
    out = pl.pallas_call(
        _gdn_bwd_kernel if backward else _gdn_fwd_kernel,
        grid=(b * hk, n // nb),
        in_specs=[spec(1, x.shape[3:]) for x in key_arrays]
        + [spec(r, x.shape[3:]) for x in head_arrays]
        + [pl.BlockSpec(
            (nb, 1) + marks.shape[2:], lambda i, j: (j, i // hk, 0, 0)
        )],
        out_specs=[
            spec(1 if key else r, shape) for key, shape, _ in out_like
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nb * r, c, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="gdn_ops_bwd" if backward else "gdn_ops_fwd",
    )(*[_flat(x) for x in key_arrays + head_arrays], marks)
    return tuple(
        x.reshape(n, b, hk if key else h, *x.shape[2:])
        for x, (key, _, _) in zip(out, out_like)
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _gdn_operands_kernel(q, k, v, g, beta, seg, interpret=False):
    """``_prep_scalar`` as the kernel ``gdn_ops_fwd``."""
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    dt = q.dtype
    return _gdn_call(
        False, (q, k), (v, g[..., None, :], beta[..., None, :]), _marks(seg),
        [(False, (c, dk), dt), (False, (c, dv), dt), (False, (c, dk), dt),
         (False, (c, dk), dt), (False, (c, c), dt),
         (False, (1, dk), jnp.float32)],
        interpret,
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _gdn_operands_bwd_kernel(q, k, v, g, beta, seg, d_ops, interpret=False):
    """``jax.vjp(_prep_scalar)`` as the kernel ``gdn_ops_bwd``: ``dq, dk``
    a key head, the sum over its value heads made in VMEM."""
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    f32 = jnp.float32
    d_q, d_k, d_v, d_g, d_beta = _gdn_call(
        True, (q, k),
        (v, g[..., None, :], beta[..., None, :]) + tuple(d_ops), _marks(seg),
        [(True, (c, dk), q.dtype), (True, (c, dk), k.dtype),
         (False, (c, dv), v.dtype), (False, (1, c), f32),
         (False, (1, c), f32)],
        interpret,
    )
    return d_q, d_k, d_v, d_g[..., 0, :], d_beta[..., 0, :]


def _scalar_operands(q, k, v, g, beta, seg, impl, interpret):
    if impl == "kernel":
        return _gdn_operands_kernel(q, k, v, g, beta, seg, interpret=interpret)
    return _in_blocks(_prep_scalar, (q, k, v, g, beta, seg, _seg_in(seg)))


def _scalar_operands_bwd(q, k, v, g, beta, seg, d_ops, impl, interpret):
    if impl == "kernel":
        return _gdn_operands_bwd_kernel(
            q, k, v, g, beta, seg, d_ops, interpret=interpret
        )
    return _operands_bwd_xla(q, k, v, g, beta, seg, d_ops, prep=_prep_scalar)
