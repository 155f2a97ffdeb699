"""A block-diffusion policy on the trainer's path (family ``sdar_moe``): rows
that hold each sequence's clean stream and a masked copy a denoising state,
under a mask that is causal between blocks of four and two-way inside one.

At test size (hidden 64, 2 layers, 8 experts of which 4 held, blocks of 4; K
in {1, 2, 4}) and seeded weights: the system's ``compute_logp``, loss and
gradients against the plain reference ``benchmark/reference_sdar.py`` (which
builds ``[x_0 ; x_s]`` a state and a dense mask, one sequence at a time),
alone and packed three to a row with a short last block; the kernels of
``ops/bd_attention.py`` in interpret mode against the dense mask, and the tile
table against it; the three edge checks of ``mask_exact``; the split's slot
budget; the refusals; the held shares of the expert layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.cli_args import (
    MicroBatchSpec, OptimizerConfig, PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.ppo.actor import PPOActor
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import tiny_config
from areal_tpu.models.forward import packed_forward
from areal_tpu.ops import bd_attention as bd
from areal_tpu.ops import flash as flash_ops
from areal_tpu.ops import functional as F
from areal_tpu.utils import data as data_utils
from benchmark import arith_sdar
from benchmark import reference_sdar as ref
from benchmark.kinds import train_steps_bd

SIZE = 4  # the block's length
# (prompt, length): the first ends mid-block (a border block that is mixed)
# and its last block is short; the second's prompt is whole blocks
SEQS = [(9, 37), (8, 30), (6, 21)]


@pytest.fixture(scope="module")
def tiny():
    cfg, keys = train_steps_bd.tiny_configs()
    params = tr.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    return cfg, keys, params


def _states(prompt, n, k, rng):
    """A trace of ``k`` states: every completion token a state in [0, k),
    each state present."""
    d = np.full(n, -1, np.int32)
    d[prompt:] = rng.integers(0, k, n - prompt)
    d[prompt:prompt + k] = np.arange(k)
    return d


def _batch(k, seqs=SEQS, seed=0, vocab=120):
    rng = np.random.default_rng(seed)
    tokens = [rng.integers(0, vocab, n).astype(np.int32) for _, n in seqs]
    batch = data_utils.pad_sequences_to_tensors(tokens)
    d = np.full(batch["input_ids"].shape, -1, np.int32)
    for i, (p, n) in enumerate(seqs):
        d[i, :n] = _states(p, n, k, rng)
    lm = (d >= 0).astype(np.int32)
    batch.update(
        denoise_state=d, loss_mask=lm,
        logprobs=(rng.standard_normal(lm.shape).astype(np.float32) * 0.1
                  - np.float32(np.log(128))) * lm,
        prox_logp=(rng.standard_normal(lm.shape).astype(np.float32) * 0.05
                   - np.float32(np.log(128))) * lm,
        advantages=rng.standard_normal(lm.shape).astype(np.float32) * lm,
    )
    return batch, tokens


def _actor(cfg, seed=3, max_tokens=2048):
    acfg = PPOActorConfig(
        path="", dtype="float32", param_dtype="float32",
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens),
        optimizer=OptimizerConfig(lr=1e-3), group_size=1,
        ppo_n_minibatches=1,
    )
    eng = SPMDTrainEngine(acfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 100, 4), model_config=cfg, seed=seed
    )
    return PPOActor(acfg, eng), eng


@pytest.fixture(scope="module")
def engine(tiny):
    return _actor(tiny[0])


# ----------------------------------------------- the system and the reference --
@pytest.mark.parametrize("packed", [False, True], ids=["alone", "packed3"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_compute_logp_is_the_references(tiny, engine, monkeypatch, k, packed):
    """``PPOActor.compute_logp`` through the engine's split, pack, program
    and fetch: every completion token's logprob at its own masked slot, the
    sequences one a row or three to a row."""
    cfg, keys, _ = tiny
    actor, eng = engine
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 256 if packed else 128)
    monkeypatch.setattr(
        # 1: a row holds the longest sequence's slots and no second one
        eng.config.mb_spec, "max_tokens_per_mb", 512 if packed else 1
    )
    batch, tokens = _batch(k)
    got = actor.compute_logp(
        {n: batch[n] for n in ("input_ids", "attention_mask", "loss_mask",
                               "denoise_state")}
    )
    mbs = eng._split(batch, backward=False)
    assert len(mbs.mbs) == (1 if packed else 3)
    params = jax.device_get(eng.params)
    for i, (p, n) in enumerate(SEQS):
        d = batch["denoise_state"][i, :n]
        want = np.asarray(ref.token_logprobs(keys, params, tokens[i], d))
        assert np.all(got[i, :p] == 0) and np.all(got[i, n:] == 0)
        np.testing.assert_allclose(got[i, :n], want, atol=2e-5)
        assert np.all(want[p:] < -1.0)  # a logprob, not a zero left behind


@pytest.mark.parametrize("k", [1, 2, 4])
def test_loss_and_every_gradient_match_the_reference(tiny, k):
    """The GRPO loss over three sequences packed to one row and its gradient
    to every weight, the system's program against ``jax.grad`` of the
    reference's one-sequence-at-a-time forward."""
    cfg, keys, params = tiny
    batch, tokens = _batch(k, seed=k)
    packed = data_utils.pack_batch_rows(
        batch, 1, quantum=256, bd=(SIZE, cfg.block_diffusion.mask_token_id)
    )
    arrays = dict(
        tokens=packed.tokens, segment_ids=packed.segment_ids,
        positions=packed.positions,
        **{f"t_{n}": v for n, v in packed.per_token.items()},
    )
    arrays = {n: jnp.asarray(v) for n, v in arrays.items()}

    def system(p):
        logits = packed_forward(
            p, cfg, arrays, remat=False, return_hidden=True
        )
        loss, _ = F.ppo_actor_loss_fn(
            logprobs=F.token_logprobs(logits, arrays),
            old_logprobs=arrays["t_logprobs"],
            advantages=arrays["t_advantages"], eps_clip=0.2,
            loss_mask=arrays["t_loss_mask"] > 0,
            proximal_logprobs=arrays["t_prox_logp"],
            behav_imp_weight_cap=5.0,
        )
        return loss

    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    want_loss, want = ref.loss_and_grads(keys, params, [
        dict(tokens=tokens[i], states=batch["denoise_state"][i, :n],
             behav_logp=batch["logprobs"][i, :n],
             prox_logp=batch["prox_logp"][i, :n],
             advantages=batch["advantages"][i, :n],
             mask=batch["loss_mask"][i, :n])
        for i, (_, n) in enumerate(SEQS)
    ])
    np.testing.assert_allclose(float(loss), float(want_loss), atol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert len(flat) == len(want_flat)
    for path, g in flat:
        w = np.asarray(want_flat[path])
        scale = max(float(np.abs(w).max()), 1e-6)
        assert float(np.abs(np.asarray(g) - w).max()) <= 2e-3 * scale + 1e-7, (
            jax.tree_util.keystr(path)
        )
    # the mask token's embedding row is trained as any other
    assert float(np.abs(np.asarray(
        grads["embedding"][cfg.block_diffusion.mask_token_id]
    )).max()) > 0


def test_the_held_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's one test: the parts of the expert layer's result that the
    two shares of four experts give add up to what the reference gives for
    all eight (no shared expert to count once)."""
    cfg, keys, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=0)
    params = tr.init_params(
        whole_cfg, jax.random.PRNGKey(11), dtype=jnp.float32
    )
    lp = {k: v[0] for k, v in params["layers"][ref.KIND].items()}
    h = jax.random.normal(jax.random.PRNGKey(12), (33, cfg.hidden_size))
    uncut = ref.experts(dict(keys, num_local_experts=8), h, lp)
    parts = []
    for first in (0, 4):
        share = dict(lp, **{
            w: lp[w][first:first + 4] for w in ("w_gate", "w_up", "w_down")
        })
        parts.append(ref.experts(keys, h, share, first=first))
        if first == 0:  # the program's held share is the reference's first
            from areal_tpu.ops.moe import moe_ffn_from_params

            got, _ = moe_ffn_from_params(
                cfg, share, h[None], valid=jnp.ones((1, 33), bool)
            )
            np.testing.assert_allclose(got[0], parts[0], atol=1e-5)
    np.testing.assert_allclose(parts[0] + parts[1], uncut, atol=1e-5)
    assert float(jnp.abs(parts[1]).max()) > 1e-4


# ------------------------------------------------------------------ the core --
def _rows(k, t, rows):
    """``_row`` of each of ``rows`` (their sequences), a batch."""
    return tuple(
        jnp.concatenate(a) for a in zip(*(
            _row(k, t, seqs, seed) for seed, seqs in enumerate(rows)
        ))
    )


def _row(k, t, seqs=SEQS, seed=0):
    batch, _ = _batch(k, seqs, seed)
    packed = data_utils.pack_batch_rows(batch, 1, pad_to=t, bd=(SIZE, 127))
    return tuple(jnp.asarray(a) for a in (
        packed.segment_ids, packed.positions // SIZE,
        packed.per_token["bd_stream"],
    ))


# (K, query x key slots a tile, row slots, the sequences of each row): three
# sequences a 512-slot row; then ONE long sequence in 768 or 1,024 slots, 4 x
# 4 tiles or more, where a noisy stream's clean keys lie a stream away (a key
# block kept by query blocks that are not neighbours) and the last key block
# is padding alone; then two rows of different layouts
ONE_LONG = {1: [(8, 300)], 2: [(8, 200)], 4: [(8, 180)]}
CORE_CASES = [
    (1, 128, 128, 512, [SEQS]), (2, 64, 128, 512, [SEQS]),
    (4, 128, 256, 512, [SEQS]),
    (1, 128, 128, 768, [ONE_LONG[1]]), (2, 64, 128, 768, [ONE_LONG[2]]),
    (4, 64, 128, 1024, [ONE_LONG[4]]),
    (2, 64, 128, 768, [ONE_LONG[2], SEQS]),
]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize(
    "k,bq,bk,t,seqs", CORE_CASES,
    ids=[f"K{c[0]}-{c[1]}x{c[2]}-{c[3]}-rows{len(c[4])}" for c in CORE_CASES],
)
def test_the_kernels_in_interpret_mode_are_the_dense_masks(
    k, bq, bk, t, seqs, fused
):
    """Forward and backward kernels (``fused``: the one kernel with a key
    head's dK and dV resident across its walk, else the pair dq + dkv)
    against the plain XLA form under the dense [T, T] mask, two key heads and
    padding at a row's end. The accumulators of the fused kernel are a (row,
    key head)'s: zeroed at its first tile, so an all-padding key block's
    gradients are exact zeros and an empty tile adds nothing."""
    hq, hkv, d = 4, 2, 128
    n = len(seqs)
    rows = _rows(k, t, seqs)
    keys = jax.random.split(jax.random.PRNGKey(k), 4)
    q = jax.random.normal(keys[0], (n, t, hq, d))
    kk = jax.random.normal(keys[1], (n, t, hkv, d))
    v = jax.random.normal(keys[2], (n, t, hkv, d))
    do = jax.random.normal(keys[3], (n, t, hq, d))

    def kernels(q, kk, v):
        return bd.bd_attention(
            q.reshape(n, t, -1), kk.reshape(n, t, -1), v.reshape(n, t, -1),
            *rows, (hq, hkv), bq, bk, fused, True,
        ).reshape(q.shape)

    def dense(q, kk, v):
        return bd.bd_segment_attention(q, kk, v, *rows)

    np.testing.assert_allclose(kernels(q, kk, v), dense(q, kk, v), atol=2e-5)
    got = jax.grad(lambda *a: (kernels(*a) * do).sum(), (0, 1, 2))(q, kk, v)
    want = jax.grad(lambda *a: (dense(*a) * do).sum(), (0, 1, 2))(q, kk, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)
    pad = np.asarray(rows[0] == 0)
    assert pad.any() and not np.asarray(kernels(q, kk, v))[pad].any()
    assert not any(np.asarray(g)[pad].any() for g in got)
    if t > 512:
        table = np.asarray(bd.tile_classes(*rows, bq, bk))[0] > 0
        assert min(table.shape) >= 4
        kept_by = [np.flatnonzero(col) for col in table.T]
        assert any((np.diff(on) > 1).any() for on in kept_by)
        assert pad[0, -bk:].all() and not table[:, -1].any()


def test_the_two_backward_forms_are_one_sum():
    """The fused kernel adds a key block's contributions in the order of
    query blocks the pair's dkv kernel walks, and a query block's in the order
    dq walks: the same float32 sums, to the bit."""
    k, bq, bk, t, seqs = CORE_CASES[-1]
    hq, hkv, d = 4, 2, 128
    rows = _rows(k, t, seqs)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, do = (jax.random.normal(a, (2, t, hq * d)) for a in keys[:2])
    kk, v = (jax.random.normal(a, (2, t, hkv * d)) for a in keys[2:])

    def grads(fused):
        return jax.grad(lambda *a: (bd.bd_attention(
            *a, *rows, (hq, hkv), bq, bk, fused, True
        ) * do).sum(), (0, 1, 2))(q, kk, v)

    for one, two in zip(grads(True), grads(False)):
        np.testing.assert_array_equal(one, two)


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("bq,bk", [(32, 64), (64, 64), (128, 32)])
def test_the_tile_table_keeps_every_tile_that_holds_a_pair(k, bq, bk):
    """Against the dense mask: no tile that holds a kept pair is dropped, a
    tile called full is, and what is kept beyond need is a few border tiles."""
    rows = _row(k, 512, seed=k)
    mask = np.asarray(bd.kept_mask(*rows))[0]
    tiles = mask.reshape(512 // bq, bq, 512 // bk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    for xp in (jnp, np):
        got = np.asarray(bd.tile_classes(
            *(np.asarray(r) if xp is np else r for r in rows), bq, bk, xp
        ))[0]
        assert (got[some] > 0).all() and every[got == 2].all()
        assert ((got > 0) & ~some).sum() <= 4
    assert (got == 0).sum() > got.size // 2  # the layout leaves most empty


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_the_edges_of_the_mask_bit_for_bit(impl):
    """``mask_exact`` (b) as the benchmark runs it, on the kernels (interpret
    mode) and on the XLA form: moving v at one clean key of block b changes
    the clean queries of blocks >= b and the noisy queries of blocks > b of
    that sequence, every one, and no other slot; at one stream-s key of
    block b exactly the stream-s queries of block b."""
    def attend(q, k, v, seg, blk, stream):
        return flash_ops.flash_bd_attention(
            q, k, v, seg, blk, stream, interpret=impl == "interpret"
        )

    out = train_steps_bd.edge_check(
        attend, 512, (2, 1), 128, SIZE, 5, jnp.float32
    )
    assert out["ok"], out
    assert out["noisy"]["expected"] == SIZE
    assert out["clean"]["expected"] > 50
    kind = flash_ops.instance(
        512, 128, interpret=impl == "interpret", heads=2,
        block_diffusion=True,
    ).kind
    assert kind == ("bd" if impl == "interpret" else "")


# ------------------------------------------------------------- the data path --
def test_a_sequences_slots_are_the_layouts(tiny):
    """P + R + K x (R + the border block's prompt tokens) slots; the clean
    stream, then a noisy stream a state at the same positions; a token is
    masked from its own state on and scored in the stream of its own."""
    d = np.array([-1] * 5 + [0, 1, 0, 1, 0, 1], np.int32)  # P 5, L 11
    src, stream, masked, scored = data_utils.bd_sequence_slots(d, SIZE)
    assert len(src) == 11 + 2 * 7 == arith_sdar.slots(5, 11, 2, SIZE)
    np.testing.assert_array_equal(src[11:18], np.arange(4, 11))
    np.testing.assert_array_equal(stream[11:], [1] * 7 + [2] * 7)
    # stream 1 (state 0): the border block's prompt token shown, all else
    # masked; stream 2 (state 1): state 0's tokens shown too
    np.testing.assert_array_equal(masked[11:18], [0, 1, 1, 1, 1, 1, 1])
    np.testing.assert_array_equal(masked[18:], [0, 0, 1, 0, 1, 0, 1])
    np.testing.assert_array_equal(scored[11:18], [0, 1, 0, 1, 0, 1, 0])
    np.testing.assert_array_equal(scored[18:], [0, 0, 1, 0, 1, 0, 1])
    assert not masked[:11].any() and not scored[:11].any()
    # per-token arrays ride to the scored slots and come back from them
    batch = {
        "input_ids": np.arange(100, 111)[None], "denoise_state": d[None],
        "attention_mask": np.ones((1, 11), np.int32),
        "loss_mask": (d >= 0).astype(np.int32)[None],
    }
    packed = data_utils.pack_batch_rows(batch, 1, quantum=32, bd=(SIZE, 999))
    assert packed.tokens[0, 12] == 999 and packed.tokens[0, 11] == 104
    np.testing.assert_array_equal(
        packed.per_token["loss_mask"][0], packed.per_token["bd_scored"][0]
    )
    back = data_utils.unpack_rows_per_token(
        packed, np.arange(32, dtype=np.float32)[None]
    )
    np.testing.assert_array_equal(
        back[0], [0, 0, 0, 0, 0, 12, 20, 14, 22, 16, 24]
    )


@pytest.mark.parametrize("seed", range(4))
def test_the_split_never_gives_a_row_more_slots_than_its_budget(seed):
    rng = np.random.default_rng(seed)
    seqs = [
        (int(p), int(p + r)) for p, r in
        zip(rng.integers(3, 40, 24), rng.integers(5, 90, 24))
    ]
    batch, _ = _batch(2, seqs, seed)
    budget = 512
    mbs = data_utils.split_padded_batch_into_mb_list(
        batch, budget, quantum=128, bd_block=SIZE
    )
    slots = data_utils.row_lens_of(batch, SIZE)
    assert sorted(mbs.forward_indices) == list(range(24))
    for mb, group, row_len in zip(mbs.mbs, mbs.groups, mbs.row_lens):
        assert slots[group].sum() <= row_len <= budget
        packed = data_utils.pack_batch_rows(
            mb, 1, pad_to=row_len, quantum=128, bd=(SIZE, 127)
        )
        assert packed.total_tokens == slots[group].sum()
    # counted as tokens the same sequences would overfill a row
    plain = data_utils.split_padded_batch_into_mb_list(
        batch, budget, quantum=128
    )
    assert len(plain.mbs) < len(mbs.mbs)
    for (p, n), s in zip(seqs, slots):
        assert s == arith_sdar.slots(p, n, 2, SIZE)


def test_the_engine_counts_slots_pairs_and_tokens(tiny, monkeypatch):
    """A train step's counters against the arithmetic the benchmark keeps:
    the pairs the mask keeps and a causal model's, the tokens scored (the
    train microbatches'), and the slots beside the sequences' own tokens
    (every dispatch's)."""
    cfg, _, _ = tiny
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 256)
    actor, eng = _actor(cfg, max_tokens=256)
    batch, _ = _batch(2)
    batch["rewards"] = np.array([0.0, 1.0, 0.5], np.float32)
    batch["versions"] = np.where(batch["loss_mask"] > 0, 0, -1).astype(np.int32)
    before = eng.counters()
    stats = actor.ppo_update(actor.compute_advantages(batch))
    counted = {k: v - before[k] for k, v in eng.counters().items()}
    want = {
        "bd_pairs": sum(arith_sdar.kept_pairs(p, n, 2, SIZE) for p, n in SEQS),
        "bd_causal_pairs": sum(arith_sdar.causal_pairs(n) for _, n in SEQS),
        "bd_scored_tokens": sum(n - p for p, n in SEQS),
        "bd_sequence_tokens": sum(n for _, n in SEQS),
        "bd_stream_slots": sum(
            arith_sdar.stream_slots(p, n, 2, SIZE) for p, n in SEQS
        ),
    }
    assert {k: counted[k] for k in want} == want
    assert counted["real_tokens"] == (
        want["bd_sequence_tokens"] + want["bd_stream_slots"]
    )
    assert sum(s["bd_pairs"] for s in stats) == want["bd_pairs"]
    assert eng._span_attrs["bd_block"] == SIZE
    assert eng.attn_impl == "block_diffusion"


# ------------------------------------------------------------- the refusals --
def test_a_batch_without_denoise_state_is_refused_not_run_causally(tiny, engine):
    cfg, _, params = tiny
    actor, _ = engine
    batch, _ = _batch(2)
    del batch["denoise_state"]
    with pytest.raises(ValueError, match="denoise_state") as refused:
        actor.compute_logp(batch)
    assert "would run causally" in str(refused.value)
    rows = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        tr.apply(params, cfg, rows, rows + 1, rows)
    # and no causal model takes such rows
    with pytest.raises(ValueError, match="no other model"):
        tr.apply(
            tr.init_params(tiny_config("qwen2"), jax.random.PRNGKey(0)),
            tiny_config("qwen2"), rows, rows + 1, rows, bd=(rows, rows),
        )


def test_the_generation_engine_refuses_it_in_the_mechanisms_words():
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.ops.flash import resolve_attn_impl

    cfg = tiny_config("sdar_moe")
    declared = tr.MECHANISMS["block_diffusion"]
    why = declared.no_generation(cfg)
    assert "a step yields other than one token a sequence" in why
    assert "ROADMAP B" in why and "\n" not in why
    with pytest.raises(NotImplementedError) as refused:
        GenerationEngine(JaxGenConfig(), model_config=cfg)
    assert str(refused.value) == why == tr.generation_refusal(cfg)
    forced, no_sp = tr.seq_parallel_refusal(cfg)
    assert forced == "block_diffusion"
    assert resolve_attn_impl("auto", 1, cfg.attn_head_dims, forced, no_sp) == (
        "block_diffusion"
    )
    with pytest.raises(ValueError, match="no sequence-parallel form"):
        resolve_attn_impl("auto", 2, cfg.attn_head_dims, forced, no_sp)


def test_the_core_runs_under_its_own_device_scope(tiny):
    """``attn_bd`` around the core and ``bd_layout`` around the counts, and
    no ``attention`` scope: the causal cores' share reads nothing here."""
    cfg, _, params = tiny
    batch, _ = _batch(2)
    packed = data_utils.pack_batch_rows(batch, 1, quantum=256, bd=(SIZE, 127))
    arrays = dict(
        tokens=packed.tokens, segment_ids=packed.segment_ids,
        positions=packed.positions,
        **{f"t_{n}": v for n, v in packed.per_token.items()},
    )
    text = jax.jit(lambda p, a: packed_forward(
        p, cfg, a, remat=False, return_stats=True, return_hidden=True
    )[1].counts).lower(params, arrays).as_text(debug_info=True)
    assert "attn_bd" in text and "bd_layout" in text
    assert "/attention/" not in text


# (row slots, the backward): the cell's rungs, and a row whose dK and dV a
# key head pass the VMEM ``instance`` gives them
@pytest.mark.parametrize("row_len,form", [
    (4096, "fused"), (16384, "fused"), (32768, "fused"), (65536, "split"),
])
def test_a_grad_dispatch_says_the_cores_tile_and_backward(
    tiny, monkeypatch, row_len, form
):
    """What ``train.grad_dispatch`` carries of the core: the counter of how
    often the one backward kernel engages, by row length; nothing where the
    XLA form takes the row (the CPU)."""
    cfg = tiny[0]
    assert tr.grad_span_attrs(cfg, "block_diffusion", row_len) == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = dataclasses.replace(cfg, head_dim=128)
    inst = flash_ops.instance(
        row_len, 128, heads=wide.num_heads, block_diffusion=True
    )
    assert inst == flash_ops.Instance("bd", 0, bd.BQ, form == "fused", bd.BK)
    assert tr.grad_span_attrs(wide, "block_diffusion", row_len) == {
        "bd_tile": f"{bd.BQ}x{bd.BK}", "bd_bwd": form,
    }
