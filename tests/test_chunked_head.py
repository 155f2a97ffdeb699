"""Chunked LM head: numerics + grads identical to full logits, and the
engine's loss paths run on the lazy view (ops/chunked_head.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops.chunked_head import ChunkedLogits, chunked_gather_logprobs
from areal_tpu.ops.functional import gather_logprobs, gather_logprobs_entropy


def _case(rng, b=2, t=10, d=16, v=64):
    x = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(b, t)), jnp.int32)
    return x, head, labels


def test_matches_full_logits_and_grads():
    rng = np.random.default_rng(0)
    x, head, labels = _case(rng)
    full = x @ head

    for temp in (1.0, 0.7):
        want = gather_logprobs(full, labels, temperature=temp)
        got = chunked_gather_logprobs(
            x, head, labels, temperature=temp, chunk=4  # pad path: 10 % 4
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    w_lp, w_ent = gather_logprobs_entropy(full, labels)
    g_lp, g_ent = chunked_gather_logprobs(
        x, head, labels, chunk=5, with_entropy=True
    )
    np.testing.assert_allclose(np.asarray(g_lp), np.asarray(w_lp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_ent), np.asarray(w_ent),
                               rtol=1e-5, atol=1e-5)

    # gradients wrt hidden AND head agree with the full-logits path
    def loss_full(x_, h_):
        return -gather_logprobs(x_ @ h_, labels).mean()

    def loss_chunk(x_, h_):
        return -chunked_gather_logprobs(x_, h_, labels, chunk=4).mean()

    gx1, gh1 = jax.grad(loss_full, argnums=(0, 1))(x, head)
    gx2, gh2 = jax.grad(loss_chunk, argnums=(0, 1))(x, head)
    np.testing.assert_allclose(np.asarray(gx2), np.asarray(gx1),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gh2), np.asarray(gh1),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tied", [False, True], ids=["head_DV", "table_VD"])
@pytest.mark.parametrize("with_entropy", [False, True], ids=["logp", "entropy"])
@pytest.mark.parametrize("temperature", [1.0, 0.7])
def test_one_op_matches_full_logits_values_and_gradients(
    temperature, with_entropy, tied, dtype
):
    """The hand-written backward against JAX's differentiation of
    ``gather_logprobs(_entropy)`` on full float32-accumulated logits: T = 10
    in chunks of 4 (a padded last chunk), the head as an untied [D, V] matrix
    and as a tied [V, D] table, operands in float32 and in bf16."""
    rng = np.random.default_rng(3)
    x, head, labels = _case(rng)
    x, head = x.astype(dtype), (0.3 * head).astype(dtype)
    stored = head.T if tied else head  # as the model keeps it
    w_lp = jnp.asarray(rng.standard_normal(labels.shape), jnp.float32)
    w_ent = jnp.asarray(rng.standard_normal(labels.shape), jnp.float32)

    def full(x_, stored_):
        h = stored_.T if tied else stored_
        logits = jnp.dot(x_, h, preferred_element_type=jnp.float32)
        if with_entropy:
            return gather_logprobs_entropy(logits, labels, temperature)
        return gather_logprobs(logits, labels, temperature), 0.0 * w_ent

    def one_op(x_, stored_):
        out = chunked_gather_logprobs(
            x_, stored_, labels, temperature=temperature, chunk=4,
            with_entropy=with_entropy, vocab_axis=0 if tied else 1,
        )
        return out if with_entropy else (out, 0.0 * w_ent)

    def loss(fn):
        def f(x_, stored_):
            lp, ent = fn(x_, stored_)
            return jnp.sum(lp * w_lp) + jnp.sum(ent * w_ent)
        return f

    for got, want in zip(one_op(x, stored), full(x, stored)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    got = jax.grad(loss(one_op), argnums=(0, 1))(x, stored)
    want = jax.grad(loss(full), argnums=(0, 1))(x, stored)
    # float32: the file's tolerances; bf16: the gradients come out in bf16
    # (eps 2**-8) and dlogits enters the two backward matmuls in bf16, so
    # a few ulp at the gradients' size (up to 8 here)
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == "float32" else dict(
        rtol=1e-2, atol=1e-2)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.dtype(dtype) and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **tol)


def test_chunk_comes_from_the_shape():
    """No chunk given: as few equal chunks as stay within the swept size,
    tile-aligned; the result does not depend on it."""
    from areal_tpu.ops import chunked_head

    cap = chunked_head._CHUNK_TOKENS
    for t in (10, 1023, 2049, 4095, 4096, 5000, 16383):
        c = chunked_head._chunk_tokens(t)
        nc = -(-t // c)
        assert c % 8 == 0 and c <= cap and nc == -(-t // cap)
        assert nc * c - t < nc * 128  # padding: the tile's rounding only
    assert chunked_head._chunk_tokens(4095) == 2048  # the cells' rows
    rng = np.random.default_rng(4)
    x, head, labels = _case(rng)
    np.testing.assert_allclose(
        np.asarray(chunked_gather_logprobs(x, head, labels)),
        np.asarray(chunked_gather_logprobs(x, head, labels, chunk=3)),
        rtol=1e-5, atol=1e-5,
    )


def test_view_dispatch_and_slicing():
    rng = np.random.default_rng(1)
    x, head, labels = _case(rng)
    view = ChunkedLogits(x, head)
    assert view.shape == (2, 10, 64)
    # the loss-path slice pattern logits[:, :-1]
    sliced = view[:, :-1]
    want = gather_logprobs((x @ head)[:, :-1], labels[:, 1:])
    got = gather_logprobs(sliced, labels[:, 1:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(view.full()), np.asarray(x @ head), rtol=1e-5, atol=1e-5
    )
    # a tied model's view holds the [V, D] table and says so
    tied = ChunkedLogits(x, head.T, vocab_axis=0)
    assert tied.shape == view.shape and tied[:, :-1].vocab_axis == 0
    np.testing.assert_allclose(
        np.asarray(gather_logprobs(tied[:, :-1], labels[:, 1:])),
        np.asarray(want), rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(tied.full()), np.asarray(x @ head), rtol=1e-5, atol=1e-5
    )


def test_engine_sft_same_loss_with_and_without_chunked_head():
    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        ParallelismConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.sft.lm_engine import sft_loss_fn, sft_loss_weight_fn
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops.functional import target_aligned_logprobs
    from areal_tpu.utils import data as data_utils

    rng = np.random.default_rng(2)
    L = 24
    batch = {
        "input_ids": rng.integers(0, 128, size=(4, L)).astype(np.int64),
        "attention_mask": np.ones((4, L), np.bool_),
        "loss_mask": np.ones((4, L), np.int64),
    }

    cfg = TrainEngineConfig(
        dtype="float32", param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        # plain SGD, lr 1, no decay, no clipping: the step IS the gradient
        optimizer=OptimizerConfig(
            type="sgd", lr=1.0, weight_decay=0.0, gradient_clipping=1e9,
            warmup_steps_proportion=0.0,
        ),
        parallel=ParallelismConfig(),
    )
    mc = tiny_config("qwen2")
    eng = SPMDTrainEngine(cfg)
    eng.initialize(FinetuneSpec(1, 8, 4), model_config=mc, seed=0)

    # the plain reference: transformer.apply's real [B, T, V] logits, and
    # jax.grad of the same loss through them, on the arrays the engine packs
    mbs = data_utils.split_padded_batch_into_mb_list(dict(batch), 4096)
    (mb,), (group,) = mbs.mbs, mbs.groups
    packed, arrays = eng._pack_for_device(mb)

    def real_logits(params):
        return transformer.apply(
            params, mc, arrays["tokens"], arrays["segment_ids"],
            arrays["positions"], remat=False,
        )

    p0 = jax.device_get(eng.params)
    loss_ref, g_ref = jax.value_and_grad(
        lambda p: sft_loss_fn(real_logits(p), arrays)[0]
    )(p0)
    r = eng.train_batch(dict(batch), sft_loss_fn, sft_loss_weight_fn)
    np.testing.assert_allclose(r["loss"], loss_ref, rtol=1e-5)
    p1 = jax.device_get(eng.params)
    # the gradients themselves (the step taken), element for element.
    # Through Adam this comparison is ill-conditioned, not loose: the first
    # step is lr * g / (|g| + eps), and layers.w_up[1, 43, 90] has
    # |g| = 1.8e-9 under eps = 1e-8, where the two forms' 6e-11 of float32
    # summation order came out as 5e-5 of a parameter.
    jax.tree_util.tree_map(
        lambda a, z, g: np.testing.assert_allclose(
            a - z, -g, rtol=1e-4, atol=1e-7),
        p1, p0, g_ref,
    )
    # logp recompute path agrees too
    lp = eng.forward(dict(batch))
    lp_ref = np.zeros_like(lp)
    lp_ref[np.asarray(group)] = data_utils.unpack_rows_per_token(
        packed, np.asarray(target_aligned_logprobs(real_logits(p1), arrays))
    )
    np.testing.assert_allclose(lp, lp_ref, rtol=1e-4, atol=1e-5)
