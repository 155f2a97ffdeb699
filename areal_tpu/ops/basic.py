"""Core numeric ops for the transformer stack.

TPU notes: everything here is shape-static and fusible by XLA. Attention is
the segment-ids formulation of packed varlen attention — the TPU analog of
the reference's flash-attn cu_seqlens path (reference
areal/utils/data.py:245-300, realhf/impl/model/modules/attn.py). A Pallas
flash kernel can replace `segment_attention` without touching callers.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def hidden_act_fn(name: str):
    """MLP gate activation by config name — ONE selection shared by the
    training stack and the serving runner (divergence here would desync
    train/serve forward passes silently)."""
    if name == "gelu_tanh":  # gemma's gelu_pytorch_tanh
        return lambda v: jax.nn.gelu(v, approximate=True)
    return jax.nn.silu


def rms_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float,
    add_unit_offset: bool = False,
) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation (reference impl/model/modules/rms.py).
    ``add_unit_offset`` is the gemma convention: scale by (1 + weight)
    with weights initialized at zero."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if add_unit_offset:
        w = 1.0 + w
    return (x * w).astype(dtype)


def rope_frequencies(
    head_dim: int, max_len: int, theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cos/sin tables [max_len, head_dim//2] in fp32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate [..., T, H, D] by per-token positions [..., T].

    Uses the HF "rotate_half" layout (first/second half pairing) so weights
    loaded from HF checkpoints produce identical outputs
    (reference impl/model/modules/rotary.py). It IS ``rotate_leading(x,
    cos[positions], sin[positions])``, kept spelled out: the lowered text of
    the programs that run it is pinned (tests/golden).
    """
    dtype = x.dtype
    c = cos[positions].astype(jnp.float32)[..., None, :]  # [..., T, 1, D/2]
    s = sin[positions].astype(jnp.float32)[..., None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def rope_inv_freq(
    rotary_dim: int, theta: float, yarn_factor: float = 1.0,
    yarn_original_len: int = 0, beta_fast: float = 32.0,
    beta_slow: float = 1.0,
) -> np.ndarray:
    """The ``rotary_dim // 2`` rotation frequencies of a head whose first
    ``rotary_dim`` channels are rotated, float32. Plain rope:
    ``f_j = theta^(-2j / rotary_dim)``. Yarn (``yarn_factor`` > 1; the HF
    ``_compute_yarn_parameters``): the channels that turn more than
    ``beta_fast`` times within ``yarn_original_len`` positions keep ``f_j``,
    those that turn less than ``beta_slow`` times take ``f_j / yarn_factor``,
    a linear ramp between: with ``lo``, ``hi`` the floor and the ceiling of
    ``rotary_dim ln(yarn_original_len / (2 pi b)) / (2 ln theta)`` at the two
    ``b``, clipped to [0, rotary_dim - 1], ``g_j = clip((j - lo) / (hi -
    lo), 0, 1)`` and ``inv_freq_j = f_j (1 - g_j) + (f_j / yarn_factor)
    g_j``."""
    j = np.arange(0, rotary_dim, 2, dtype=np.float64)
    f = 1.0 / theta ** (j / rotary_dim)
    if yarn_factor > 1.0:
        def turns_at(b):
            return rotary_dim * np.log(
                yarn_original_len / (b * 2 * np.pi)
            ) / (2 * np.log(theta))

        lo = max(np.floor(turns_at(beta_fast)), 0)
        hi = min(np.ceil(turns_at(beta_slow)), rotary_dim - 1)
        if lo == hi:
            hi += 0.001  # the family's guard against a ramp of no width
        g = np.clip((np.arange(rotary_dim // 2) - lo) / (hi - lo), 0, 1)
        f = f * (1 - g) + f / yarn_factor * g
    return f.astype(np.float32)


def rope_at(
    positions: jnp.ndarray, inv_freq: np.ndarray, scale: float = 1.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos and sin [..., T, R] of per-token ``positions`` [..., T] at the R
    frequencies ``inv_freq``, float32, each times ``scale`` (yarn's
    attention factor): what a table ``rope_frequencies``-style would hold at
    those rows, without the table (a model of 262,144 positions)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return cos, sin


def rotate_leading(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate the first 2R channels of ``x`` [..., T, H, D] by per-token
    ``cos``/``sin`` [..., T, R] (``rope_at``), rotate-half pairing over
    those 2R as ``apply_rope`` has it over a whole head; channels 2R..D pass
    as they are (a partly rotated head)."""
    dtype = x.dtype
    rot = 2 * cos.shape[-1]
    c, s = cos[..., None, :], sin[..., None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    out = out.astype(dtype)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


def mrope_tables(
    positions3: jnp.ndarray,  # [B, T, 3] int32 (t, h, w) position ids
    cos: jnp.ndarray,  # [max_len, D/2]
    sin: jnp.ndarray,
    sections: Tuple[int, ...],  # rotary channels per dim; sums to D/2
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos and sin [B, T, D/2] of multimodal positions, float32: rotary
    channel j takes the row of the temporal/height/width position stream its
    section assigns it."""
    sec = np.repeat(np.arange(len(sections)), sections)
    onehot = jnp.asarray(
        sec[None, :] == np.arange(len(sections))[:, None], jnp.float32
    )  # [3, D/2]
    c3 = cos[positions3].astype(jnp.float32)  # [B, T, 3, D/2]
    s3 = sin[positions3].astype(jnp.float32)
    return (
        jnp.einsum("btsd,sd->btd", c3, onehot),
        jnp.einsum("btsd,sd->btd", s3, onehot),
    )


def apply_mrope(
    x: jnp.ndarray,  # [B, T, H, D]
    positions3: jnp.ndarray,  # [B, T, 3] int32 (t, h, w) position ids
    cos: jnp.ndarray,  # [max_len, D/2]
    sin: jnp.ndarray,
    sections: Tuple[int, ...],  # rotary channels per dim; sums to D/2
) -> jnp.ndarray:
    """Multimodal 3D rotary (Qwen2-VL "mrope"): rotary channel j uses the
    temporal/height/width position stream its section assigns it (HF
    `apply_multimodal_rotary_pos_emb` layout, rotate-half pairing):
    ``rotate_leading`` at ``mrope_tables``. For text-only tokens all three
    streams are equal and this reduces exactly to `apply_rope`."""
    return rotate_leading(
        x, *mrope_tables(positions3, cos, sin, sections)
    )


def make_segment_mask(
    q_seg: jnp.ndarray, kv_seg: jnp.ndarray, causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    """Boolean attention mask [..., Tq, Tk] for packed streams.

    Token i may attend to token j iff both are real (segment id > 0), they
    belong to the same sequence, and j <= i (causal); with a layer's
    ``window`` also i - j < window (the ``window`` latest keys, i's own
    among them; a sequence's tokens are contiguous in a row, so a distance
    in the row is a distance in the sequence).
    """
    same = (q_seg[..., :, None] == kv_seg[..., None, :]) & (
        q_seg[..., :, None] > 0
    )
    if causal:
        tq, tk = q_seg.shape[-1], kv_seg.shape[-1]
        qi = jnp.arange(tq)[:, None]
        kj = jnp.arange(tk)[None, :]
        same = same & (kj <= qi + (tk - tq))
        if window:
            same = same & (qi + (tk - tq) - kj < window)
    return same


def segment_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    segment_ids: jnp.ndarray,  # [B, T]
    causal: bool = True,
    window: int = 0,  # a window layer's: the keys a query sees (0 = all)
) -> jnp.ndarray:
    """Packed-varlen causal attention with GQA; fp32 softmax.

    XLA-native formulation; the hot path can be swapped for a Pallas splash
    kernel (ops/pallas) with the same signature.
    """
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    scale = d ** -0.5
    # GQA via grouped einsum — no materialized KV repeat (head h reads kv
    # group h // rep, HF layout); bf16 inputs stay on the MXU with fp32
    # accumulation
    qg = q.reshape(b, t, hkv, rep, d)
    logits = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    mask = make_segment_mask(
        segment_ids, segment_ids, causal=causal, window=window
    )
    logits = jnp.where(mask[:, None, None, :, :], logits, -2.3819763e38)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked (padding) rows: softmax of all -inf → near-uniform garbage;
    # zero them so padding tokens contribute exactly nothing downstream.
    valid_q = (segment_ids > 0)[:, None, None, :, None]
    probs = jnp.where(valid_q, probs, 0.0)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, hq, v.shape[-1]).astype(q.dtype)
