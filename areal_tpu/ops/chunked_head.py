"""Chunked LM-head: target logprobs without materializing [T, vocab].

The full-logits tensor is the largest activation in LM training: one 24k
packed row at 32k vocab is 3.2 GB in fp32 — fwd AND bwd — which is what
capped round-3's long-context phase. But every in-repo loss consumes
logits only through ``gather_logprobs(_entropy)``: per-token target logp
(+ entropy). This module computes exactly that as ONE operation with a
hand-written backward (``jax.custom_vjp``), scanning the token axis in
chunks so that only a [chunk, V] block of logits is ever live.

What is saved and what is recomputed. The forward keeps ``hidden``,
``head``, ``labels`` and two floats a token (``logz``, and the entropy when
it was asked for): nothing of size [chunk, V]. The backward recomputes a
chunk's logits once (one matmul), forms ``dlogits`` in one elementwise pass
and feeds it to the two backward matmuls: four matmuls a token in all, as
many as differentiating a checkpointed body took. Neither direction
gathers or scatters: the label's logit is a select against an iota summed
over the vocabulary (it fuses into a reduction that reads the logits
anyway), and the one-hot of ``dlogits`` is the same compare.

Precision. The matmul operands go in as they arrive (bf16 under the
recipe: a product of two bf16 values is exact in float32, so nothing is
lost against upcasting them first) and accumulate in float32
(``preferred_element_type``); logsumexp, softmax and ``dlogits`` are
float32. ``dlogits`` is rounded to the operands' dtype where the two
backward matmuls read it — what a TPU's default-precision float32 dot does
to its operands itself (bitwise equal on the v5e: PERF.md, PR 28) — and the
head's gradient is summed over chunks in float32 and rounded once. With
float32 operands every step is float32.

The head is taken as the model stores it: ``[D, V]``, or a tied model's
``[V, D]`` embedding table contracted over ``D`` (``vocab_axis`` 0), so no
transposed copy of the table is made. The op is plain ``jax.lax``, so GSPMD
partitions it on any mesh (rows over ``fsdp``; the head sharded on its model
axis) and every platform runs the same code.

Role of the reference's fused-linear-cross-entropy kernels (the torch
ecosystem's chunked lm-head / liger-style loss it leans on for memory).

``ChunkedLogits`` is the lazy view the model returns in place of logits;
``functional.gather_logprobs`` dispatches on it, so loss functions are
unchanged. Consumers that need raw logits (the critic's value head, the
serving sampler) never receive this view.
"""

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.utils.tracing import device_scope


def _head_logits(hidden, head, vocab_axis: int):
    """[..., V] float32 = hidden [..., D] times the head, contracting the
    head's model axis wherever it is."""
    return jax.lax.dot_general(
        hidden, head,
        (((hidden.ndim - 1,), (1 - vocab_axis,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@dataclasses.dataclass(frozen=True)
class ChunkedLogits:
    """Lazy logits = hidden @ head. Supports the T-axis slicing the loss
    paths use (``logits[:, :-1]``); anything needing the vocab axis must
    call ``.full()`` (and pay the memory)."""

    hidden: jnp.ndarray  # [B, T, D] (model compute dtype)
    head: jnp.ndarray  # [D, V], or a tied table [V, D] with vocab_axis=0
    vocab_axis: int = 1  # which axis of ``head`` is the vocabulary

    @property
    def shape(self) -> Tuple[int, ...]:
        return (*self.hidden.shape[:-1], self.head.shape[self.vocab_axis])

    @property
    def dtype(self):
        return jnp.float32

    def __getitem__(self, idx) -> "ChunkedLogits":
        return dataclasses.replace(self, hidden=self.hidden[idx])

    def full(self) -> jnp.ndarray:
        with device_scope("lm_head"):
            return _head_logits(self.hidden, self.head, self.vocab_axis)

# The most tokens a chunk holds, from the sweep on the v5e at both cells'
# vocabularies (``tools/microbench_head``; table and reasons in PERF.md
# section 6, PR 28): larger was faster at every size, and one chunk a row is
# left out for the [T, V] float32 logits it keeps and its compile time.
_CHUNK_TOKENS = 2048


def _chunk_tokens(t: int) -> int:
    """Tokens a chunk for ``t`` tokens a row: as few equal chunks as stay
    within ``_CHUNK_TOKENS``, rounded up to the tile (a row of 4095 is two
    chunks of 2048 and pads by one token). A function of T alone: the
    vocabulary did not change the order of the chunk sizes in the sweep
    (151,936 and 50,304), so V does not enter."""
    c = -(-t // -(-t // _CHUNK_TOKENS))
    tile = 128 if c >= 128 else 8
    return -(-c // tile) * tile


def chunked_gather_logprobs(
    hidden: jnp.ndarray,  # [B, T, D]
    head: jnp.ndarray,  # [D, V] ([V, D] with vocab_axis=0)
    labels: jnp.ndarray,  # [B, T] int
    temperature: float = 1.0,
    chunk: Optional[int] = None,  # tokens a chunk; from T unless given
    with_entropy: bool = False,
    vocab_axis: int = 1,
):
    """log p(labels) (and optionally entropy) per token, scanning the
    T axis in chunks. Equal to ``gather_logprobs(hidden @ head, labels)``
    on full float32-accumulated logits, values and gradients, to the
    rounding the module docstring names."""
    b, t, d = hidden.shape
    dt = jnp.result_type(hidden, head)
    c = min(chunk, t) if chunk else _chunk_tokens(t)
    pad = (-t) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = (t + pad) // c
    hr = hidden.astype(dt).reshape(b, nc, c, d).swapaxes(0, 1)  # [nc,B,C,D]
    lr = labels.reshape(b, nc, c).swapaxes(0, 1)
    lp, ent = _head_logprobs(
        float(temperature), with_entropy, vocab_axis, hr, head.astype(dt), lr
    )
    lp = lp.swapaxes(0, 1).reshape(b, t + pad)[:, :t]
    if with_entropy:
        return lp, ent.swapaxes(0, 1).reshape(b, t + pad)[:, :t]
    return lp


def _scaled_logits(hc, head, vocab_axis, temperature):
    with device_scope("lm_head"):
        logits = _head_logits(hc, head, vocab_axis)
    return logits / temperature if temperature != 1.0 else logits


def _is_label(logits, lc):
    """[B, C, V] bool: the label's column, as a compare against an iota
    (a gather forward or a scatter backward relays the whole chunk)."""
    iota = jax.lax.broadcasted_iota(lc.dtype, logits.shape, logits.ndim - 1)
    return iota == lc[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _head_logprobs(temperature, with_entropy, vocab_axis, hr, head, lr):
    return _head_logprobs_fwd(
        temperature, with_entropy, vocab_axis, hr, head, lr
    )[0]


@device_scope("loss")
def _head_logprobs_fwd(temperature, with_entropy, vocab_axis, hr, head, lr):
    """hr [nc, B, C, D], lr [nc, B, C] -> (logp, entropy) [nc, B, C]."""

    def body(carry, inp):
        hc, lc = inp
        logits = _scaled_logits(hc, head, vocab_axis, temperature)
        logz = jax.nn.logsumexp(logits, axis=-1)
        lp = jnp.sum(
            jnp.where(_is_label(logits, lc), logits, 0.0), axis=-1
        ) - logz
        if with_entropy:
            logp_full = logits - logz[..., None]
            ent = -jnp.sum(jnp.exp(logp_full) * logp_full, axis=-1)
        else:
            ent = jnp.zeros_like(lp)
        return carry, (lp, logz, ent)

    _, (lp, logz, ent) = jax.lax.scan(body, 0, (hr, lr))
    return (lp, ent), (hr, head, lr, logz, ent)


@device_scope("loss")
def _head_logprobs_bwd(temperature, with_entropy, vocab_axis, res, g):
    hr, head, lr, logz, ent = res
    g_lp, g_ent = g
    rows = (0, 1)  # the token axes of a chunk, contracted for the head

    def body(dhead, inp):
        hc, lc, zc, ec, glc, gec = inp
        logits = _scaled_logits(hc, head, vocab_axis, temperature)
        # one pass over the chunk: d lp / d logits = onehot - softmax,
        # d entropy / d logits = -softmax * (logp + entropy)
        logp_full = logits - zc[..., None]
        probs = jnp.exp(logp_full)
        dz = glc[..., None] * (
            jnp.where(_is_label(logits, lc), 1.0, 0.0) - probs
        )
        if with_entropy:
            dz = dz - gec[..., None] * probs * (logp_full + ec[..., None])
        if temperature != 1.0:
            dz = dz / temperature
        dz = dz.astype(hc.dtype)
        with device_scope("lm_head"):
            dh = jax.lax.dot_general(
                dz, head, (((2,), (vocab_axis,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dhead = dhead + jax.lax.dot_general(
                *((hc, dz) if vocab_axis else (dz, hc)),
                ((rows, rows), ((), ())),
                preferred_element_type=jnp.float32,
            )
        return dhead, dh.astype(hc.dtype)

    dhead, dhr = jax.lax.scan(
        body, jnp.zeros(head.shape, jnp.float32),
        (hr, lr, logz, ent, g_lp, g_ent),
    )
    return dhr, dhead.astype(head.dtype), None


_head_logprobs.defvjp(_head_logprobs_fwd, _head_logprobs_bwd)
