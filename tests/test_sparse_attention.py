"""Learned sparse attention (the KeyeVL2 family: a qwen3-moe block over the
top-k keys an indexer picks, one chip's share of the experts) through the
program against the tests' twin of its plain reference
(``tests/keye_reference.py``), float32 against float32 on the CPU at the
family's tiny configuration: 2 layers, topk 8 (the sequences here are 24 to
48 tokens, so most queries select), two indexer heads, 8 experts held of 16.

Tolerances. Both sides compute in float32 and select the same keys (an index
score would have to tie to 1e-7 relative for a swap), so what differs is the
order of sums: logits and logprobs of magnitude ~1 are held to 1e-3 absolute
(a bf16 forward reads ~1e-2 and fails; measured here ~1e-6), gradients to
1e-2 of each leaf's largest entry (sums over up to 40 positions of such
terms; measured ~1e-5). The kernel (Pallas interpret mode) against the XLA
core is float32 against float32 with another blocking of the softmax: 1e-5.
The selection's kernel (``dsa_select_kth``, interpret mode) against
``_kth_largest`` is integers against integers: equal, to the bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
import keye_reference as twin
from areal_tpu.api.cli_args import ParallelismConfig
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import sparse_attention as sa
from areal_tpu.ops.basic import (
    apply_mrope, apply_rope, rope_frequencies, segment_attention,
)
from areal_tpu.ops.moe import moe_ffn
from areal_tpu.parallel import mesh as mesh_lib
from benchmark.kinds import train_steps_dsa

LOGIT_TOL, GRAD_TOL, KERNEL_TOL = 1e-3, 1e-2, 1e-5


def _seeded(cfg, seed):
    params = fb.init(cfg, seed)
    rng = np.random.default_rng(seed)
    lay = params["layers"]
    for k in ("q_norm", "k_norm", "input_norm", "post_attn_norm"):
        lay[k] = lay[k] * (
            1.0 + 0.3 * rng.standard_normal(lay[k].shape)
        ).astype(np.float32)
    # weights large enough that routing and selection are decided
    lay["w_router"] = lay["w_router"] * np.float32(20.0)
    for k in ("w_idx_q", "w_idx_k", "w_idx_w"):
        lay[k] = lay[k] * np.float32(10.0)
    return params


# the family's tiny configuration and the reference's keys for it; rows of 64
FAMILY = fb.Family(
    "KeyeVL2", train_steps_dsa.tiny_configs, twin.logits, _seeded, total=64
)
tiny = fb.tiny_fixture(FAMILY, seed=11)
trained = fb.trained_fixture()


def _random_op_inputs(rng, t, hq=4, hkv=2, d=64, hi=2, di=8):
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return (f(1, t, hq, d), f(1, t, hkv, d), f(1, t, hkv, d)), (
        f(1, t, hi, di), f(1, t, di), f(1, t, hi)
    )


def test_tiny_config_is_the_family(tiny):
    cfg = tiny[0]
    assert cfg.family == "KeyeVL2" and cfg.qk_norm == "head" and cfg.is_moe
    assert cfg.has_indexer and (cfg.dsa_topk, cfg.dsa_index_heads) == (8, 2)
    assert (cfg.num_experts, cfg.num_held_experts) == (16, 8)
    assert cfg.norm_topk_prob and not cfg.shared_expert_size
    lay = tiny[2]["layers"]
    assert lay["w_router"].shape[-1] == 16 and lay["w_gate"].shape[1] == 8
    assert lay["w_idx_q"].shape[-1] == 2 * 8 and lay["w_idx_w"].shape[-1] == 2


def test_logits_match_the_reference_with_sequences_longer_than_topk(tiny):
    cfg, _, params = tiny
    got, stats, _ = fb.check_logits(FAMILY, tiny, [40], atol=LOGIT_TOL)
    assert np.abs(got[0, :40]).max() > 0.1
    # each layer kept sum_t min(t + 1, 8) of the 40 * 41 / 2 causal pairs
    assert np.asarray(stats.counts["dsa_kept_pairs"]).tolist() == (
        [36 + 32 * 8] * 2
    )
    assert np.asarray(stats.counts["dsa_causal_pairs"]).tolist() == [820] * 2
    # and it is not full attention under another name
    tok, seg, pos, _ = fb.row([40], FAMILY.total, cfg.vocab_size)
    full, _ = fb.forward(
        tiny._replace(cfg=dataclasses.replace(cfg, dsa_topk=0)), tok, seg, pos,
        {**params, "layers": {k: v for k, v in params["layers"].items()
                              if not k.startswith("w_idx")}},
    )
    assert np.abs(full - got)[0, :40].max() > 10 * LOGIT_TOL


def test_loss_and_every_gradient_match_and_the_indexers_is_zero(tiny):
    def summed(seq):  # the summed log p(seq[i] | seq[:i])
        n, tgt = len(seq), np.asarray(seq[1:], np.int32)
        return lambda logits: jnp.take_along_axis(
            jax.nn.log_softmax(logits[:n - 1], axis=-1), tgt[:, None], axis=-1
        ).sum()

    def by_index(g, w):  # chosen by index: exactly zero on both sides
        assert not np.asarray(g).any() and not np.asarray(w).any()

    seen = fb.check_gradients(
        FAMILY, tiny, summed, n=40, seed=1, rtol=GRAD_TOL,
        value_tol=(0.0, LOGIT_TOL * 40),
        special={k: by_index for k in ("w_idx_q", "w_idx_k", "w_idx_w")},
    )
    assert len(seen) == 3 + len(tiny.params["layers"]) - 3


def test_two_sequences_in_one_row_select_nothing_across_the_border(tiny):
    # the program's packed row against the reference's of each alone
    _, stats, _ = fb.check_logits(
        FAMILY, tiny, [30, 24], seed=2, atol=LOGIT_TOL
    )
    kept = 36 + 22 * 8 + 36 + 16 * 8  # padding counts nowhere
    assert np.asarray(stats.counts["dsa_kept_pairs"]).tolist() == [kept] * 2
    # the op's own mask: no (query, key) pair joins two segments
    seg = np.concatenate([np.full(30, 1), np.full(24, 2), np.zeros(10)])
    pos = np.concatenate([np.arange(30), np.arange(24), np.zeros(10)])
    (q, k, v), (qi, ki, w) = _random_op_inputs(np.random.default_rng(2), 64)
    mask, _ = jax.jit(lambda *a: sa.select_keys(*a, 8))(
        qi[0], ki[0], w[0], seg.astype(np.int32)
    )
    mask = np.asarray(mask)
    assert not mask[30:54, :30].any() and not mask[:54, 54:].any()
    assert (mask[:54].sum(1) == np.minimum(pos[:54] + 1, 8)).all()


def test_selection_is_exact_against_a_sort_and_breaks_ties_to_the_lower_index():
    rng = np.random.default_rng(3)
    t, topk = 200, 16
    _, (qi, ki, w) = _random_op_inputs(rng, t)
    seg = np.zeros(t, np.int32)
    seg[:90], seg[90:190] = 1, 2
    scores = np.einsum(
        "hts,th->ts",
        np.maximum(np.einsum("thd,sd->hts", qi[0], ki[0]), 0), w[0],
    )
    want = np.zeros((t, t), bool)
    for q in range(190):
        own = [s for s in range(q + 1) if seg[s] == seg[q]]
        best = np.argsort(-scores[q, own], kind="stable")[:topk]
        want[q, [own[i] for i in best]] = True
    select = jax.jit(lambda *a: sa.select_keys(*a, topk))
    mask, stats = select(qi[0], ki[0], w[0], jnp.asarray(seg))
    assert (np.asarray(mask)[:190] == want[:190]).all()
    assert int(stats.kept_pairs) == want.sum()
    assert int(stats.causal_pairs) == 90 * 91 // 2 + 100 * 101 // 2
    # every score equal (0): the first topk keys of the sequence win
    tied, _ = select(qi[0] * 0, ki[0], w[0], jnp.asarray(seg))
    assert np.nonzero(np.asarray(tied)[150])[0].tolist() == list(range(90, 106))


def test_a_sequence_within_topk_ignores_the_indexer_and_is_causal_attention(tiny):
    cfg, _, params = tiny
    tok, seg, pos, _ = fb.row([8], FAMILY.total, cfg.vocab_size, 4)  # = topk
    base, _ = fb.forward(tiny, tok, seg, pos)
    other = dict(params, layers=dict(params["layers"]))
    for k in ("w_idx_q", "w_idx_k", "w_idx_w"):
        other["layers"][k] = -3.0 * params["layers"][k][::-1]
    assert (fb.forward(tiny, tok, seg, pos, other)[0] == base).all()  # to the bit
    rng = np.random.default_rng(5)
    (q, k, v), (qi, ki, w) = _random_op_inputs(rng, 128)
    seg = jnp.asarray(np.repeat([1, 2, 0], [70, 50, 8])[None], jnp.int32)
    want = segment_attention(q, k, v, seg)
    for core in ("xla", "kernel"):
        got, stats = sa.sparse_attention(
            q, k, v, seg, qi, ki, w, topk=128, core=core, interpret=True
        )
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=0)
        assert int(stats.kept_pairs) == int(stats.causal_pairs)


def test_three_equal_mrope_coordinates_are_plain_rope():
    cfg = tiny_config("KeyeVL2")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 24, 4, cfg.head_dim)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 400, (2, 24)), jnp.int32)
    cos, sin = rope_frequencies(cfg.head_dim, 512, cfg.rope_theta)
    three = jnp.repeat(pos[..., None], 3, axis=-1)
    np.testing.assert_array_equal(
        apply_mrope(x, three, cos, sin, cfg.mrope_sections),
        apply_rope(x, pos, cos, sin),
    )


def _sink_and_recent_index(t, block):
    """Index inputs under which a query keeps the 8 most recent keys and,
    in the EVEN query blocks of ``block`` rows only, its sequence's first 8
    (the odd blocks keep the 16 most recent): key block 0 is read by query
    blocks that are not neighbours, with empty tiles between kept ones."""
    s = np.arange(t, dtype=np.float32)
    ki = np.zeros((1, t, 8), np.float32)
    ki[0, :8, 0] = 10.0
    ki[0, :, 1] = 1.0 + s / t
    qi = np.zeros((1, t, 2, 8), np.float32)
    qi[0, :, 0, 0] = qi[0, :, 1, 1] = 1.0
    w = np.ones((1, t, 2), np.float32)
    w[0, :, 0] = (np.arange(t) // block) % 2 == 0
    return qi, ki, w


# (tile, segments of the 1024-token row, what the case is for)
_CORE_CASES = [
    pytest.param((128, 256), [300, 600, 124], "two-sequences", id="128x256"),
    pytest.param((512, 512), [300, 600, 124], "two-sequences", id="chip-tiles"),
    pytest.param((128, 256), [1024, 0, 0], "skipped-between", id="skipped-between"),
    pytest.param((128, 256), [300, 400, 324], "padding-key-block", id="padding-key-block"),
]


@pytest.mark.parametrize("blocks,lens,case", _CORE_CASES)
def test_the_kernel_in_interpret_mode_equals_the_xla_core_both_ways(
    blocks, lens, case, monkeypatch
):
    """1024 tokens, 2 query heads a kv head. ``two-sequences`` (and
    padding): with 128 x 256 tiles the grid is 8 x 4 of which the tiles above
    the diagonal and the first sequence's keys under the second's queries
    hold nothing and are skipped; with the chip's own tile sizes a row this
    short is 2 x 2, one tile above the diagonal. ``skipped-between``: one sequence that ends with the
    row's last query block, under an index that makes key block 0's resident
    dK / dV take terms from query blocks 0, 1, 2, 4 and 6, the walk of query
    block 6 skipping a tile between two kept ones. ``padding-key-block``:
    the last key block holds padding alone, whose tiles are walked (a padding
    token keeps itself) and whose dK and dV are exactly zero."""
    monkeypatch.setattr(sa, "_BLOCK_Q", blocks[0])
    monkeypatch.setattr(sa, "_BLOCK_KV", blocks[1])
    rng = np.random.default_rng(7)
    (q, k, v), (qi, ki, w) = _random_op_inputs(rng, 1024)
    if case == "skipped-between":
        qi, ki, w = _sink_and_recent_index(1024, blocks[0])
    seg = jnp.asarray(np.repeat([1, 2, 0], lens)[None], jnp.int32)
    mask, _ = sa.select_keys(qi[0], ki[0], w[0], seg[0], 16)
    tiles = np.asarray(sa._kernel_selection(mask)[1])
    assert tiles.shape == (1024 // sa._core_blocks(1024)[0],
                           1024 // sa._core_blocks(1024)[1])
    assert (tiles.size > 2) == (not tiles.all())  # something is skipped
    if case == "skipped-between":
        assert np.nonzero(tiles[:, 0])[0].tolist() == [0, 1, 2, 4, 6]
        assert tiles[6].tolist() == [1, 0, 1, 1] and tiles[7, 3]
    weight = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def run(core):
        def f(q, k, v):
            out, _ = sa.sparse_attention(
                q, k, v, seg, qi, ki, w, topk=16, core=core, interpret=True
            )
            return (out * weight).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, out_x), grads_x = run("xla")
    (_, out_s), grads_s = run("kernel")
    assert np.abs(np.asarray(out_x)).max() > 0.1
    np.testing.assert_allclose(out_s, out_x, atol=KERNEL_TOL, rtol=0)
    for a, b in zip(grads_s, grads_x):
        assert np.abs(np.asarray(b)).max() > 0.1
        np.testing.assert_allclose(a, b, atol=10 * KERNEL_TOL, rtol=0)
    real = sum(lens[:2])
    assert not np.asarray(out_s)[0, real:].any()  # padding reads nothing
    for g in grads_s:  # and gives and takes no gradient, to the bit
        assert not np.asarray(g)[0, real:].any()
    if case == "padding-key-block":
        assert real <= 1024 - blocks[1] and tiles[7, 3]


def test_the_op_runs_per_chip_on_its_own_rows_under_an_fsdp_mesh():
    rng = np.random.default_rng(8)
    ins = [_random_op_inputs(rng, 64) for _ in range(4)]
    qkv = [jnp.concatenate([i[0][j] for i in ins]) for j in range(3)]
    idx = [jnp.concatenate([i[1][j] for i in ins]) for j in range(3)]
    seg = jnp.asarray(
        np.stack([np.repeat([1, 2, 0], [n, 60 - n, 4]) for n in (20, 30, 40, 50)]),
        jnp.int32,
    )
    want, want_stats = sa.sparse_attention(*qkv, seg, *idx, topk=8)
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=4))
    got, got_stats = jax.jit(sa.make_attend(mesh, 8))(*qkv, seg, *idx)
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=0)
    assert int(got_stats.kept_pairs) == int(want_stats.kept_pairs) > 0
    assert int(got_stats.causal_pairs) == int(want_stats.causal_pairs)


def test_sequence_parallelism_and_the_generation_engine_refuse_by_name(tiny):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.ops.flash import resolve_attn_impl

    cfg, _, params = tiny
    from areal_tpu.models.transformer import seq_parallel_refusal

    declared = seq_parallel_refusal(cfg)
    for impl in ("auto", "ring"):  # whatever was asked
        assert resolve_attn_impl(
            impl, 1, cfg.attn_head_dims, *declared
        ) == "sparse_topk"
    with pytest.raises(ValueError, match="sparse attention.*sequence-parallel"):
        resolve_attn_impl("auto", 2, cfg.attn_head_dims, *declared)
    mesh = mesh_lib.make_mesh(ParallelismConfig(seq_parallel_size=2))
    with pytest.raises(ValueError, match="sparse attention.*sequence-parallel"):
        sa.make_attend(mesh, 8)
    with pytest.raises(NotImplementedError, match="sparse attention.*indexer-key cache"):
        GenerationEngine(JaxGenConfig(), model_config=cfg, params=params)


# ------------------------------------------------ the share of the experts --
def _expert_weights(rng, d=32, f=16, e=16):
    n = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)  # noqa: E731
    return n(d, e) * 5, n(e, d, f), n(e, d, f), n(e, f, d)


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(shares):
    """What ties the share to the model: each holder routes over all 16
    experts with the same router and computes its own experts' part; the
    parts add up to the uncut reference's layer output."""
    rng = np.random.default_rng(9)
    wr, wg, wu, wd = _expert_weights(rng)
    x = jnp.asarray(rng.standard_normal((2, 12, 32)), jnp.float32)
    valid = jnp.asarray(rng.random((2, 12)) > 0.2)
    c = {"num_experts_per_tok": 3, "norm_topk_prob": True}
    lp = {"w_router": wr, "w_gate": wg, "w_up": wu, "w_down": wd}
    whole = np.asarray(twin.experts(c, x.reshape(-1, 32), lp)).reshape(x.shape)
    whole = np.where(np.asarray(valid)[..., None], whole, 0)
    n = 16 // shares
    total, pairs = 0, 0
    for i in range(shares):
        held = slice(i * n, (i + 1) * n)
        part, stats = moe_ffn(
            x, wr, wg[held], wu[held], wd[held], 3, True, valid=valid,
            first_expert=i * n,
        )
        want = twin.experts(
            c, x.reshape(-1, 32),
            dict(lp, w_gate=wg[held], w_up=wu[held], w_down=wd[held]),
            first=i * n,
        )
        np.testing.assert_allclose(
            part, np.where(np.asarray(valid)[..., None],
                           np.asarray(want).reshape(x.shape), 0),
            atol=1e-5, rtol=0,
        )
        total, pairs = total + np.asarray(part), pairs + int(stats.assignments)
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=0)
    assert pairs == int(valid.sum()) * 3  # every routed pair has one holder


def test_a_long_stream_in_token_chunks_is_the_same_layer(monkeypatch):
    """A stream longer than ``_STREAM_ROWS`` runs its dispatch and grouped
    matmuls a token chunk at a time (a held share still sorts every pair:
    131,072 rows a 16k-token row); output, gradients and statistics are
    the one-chunk layer's."""
    from areal_tpu.ops import moe

    rng = np.random.default_rng(13)
    wr, wg, wu, wd = _expert_weights(rng)
    x = jnp.asarray(rng.standard_normal((1, 64, 32)), jnp.float32)
    valid = jnp.asarray(rng.random((1, 64)) > 0.1)

    def run(x, wg):
        out, stats = moe_ffn(x, wr, wg[:8], wu[:8], wd[:8], 2, True, valid=valid)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum(), stats

    whole = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(x, wg)
    monkeypatch.setattr(moe, "_STREAM_ROWS", 32)  # 64 x 2 rows: 4 chunks
    assert moe._token_chunks(64, 2) == 4
    chunked = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(x, wg)
    for a, b in zip(jax.tree_util.tree_leaves(chunked),
                    jax.tree_util.tree_leaves(whole)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_a_share_of_all_the_experts_is_the_layer_as_it_was_to_the_bit():
    rng = np.random.default_rng(10)
    wr, wg, wu, wd = _expert_weights(rng)
    x = jnp.asarray(rng.standard_normal((1, 20, 32)), jnp.float32)
    f = lambda **kw: jax.jit(  # noqa: E731
        lambda x: moe_ffn(x, wr, wg, wu, wd, 2, True, **kw)
    )
    assert f().lower(x).as_text() == f(first_expert=0).lower(x).as_text()
    a, b = f()(x), f(first_expert=0)(x)
    assert all((np.asarray(p) == np.asarray(q)).all()
               for p, q in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------- the selection's kernel --
def _causal_keys(scores, seg, first):
    """A query chunk's ordered keys as ``select_keys`` builds them: queries
    are tokens ``first``.. of a row with segments ``seg``, keys its tokens
    up to the chunk's end."""
    c, k = scores.shape
    idx = np.arange(k)
    own = (
        (seg[first:first + c, None] == seg[None, :k])
        & (seg[first:first + c, None] > 0)
        & (idx[None] <= idx[first:first + c, None])
    )
    return jnp.where(
        jnp.asarray(own), sa._order_keys(jnp.asarray(scores)), jnp.uint32(0)
    )


def _kth_case(name):
    """(keys [C, K] uint32, topk) of one case of the kernel's test."""
    rng = np.random.default_rng(sum(map(ord, name)))
    c, k, topk = 64, 640, 16
    scores = rng.standard_normal((c, k)).astype(np.float32)
    seg = np.ones(k, np.int32)
    if name == "more-ties-than-places":
        # a few distinct values, so the threshold is shared by many keys
        scores = np.round(scores).astype(np.float32)
    elif name == "fewer-keys-than-topk":
        c, topk = 16, 48  # queries 0..15 of the row: at most 16 keys each
        scores = scores[:c]
    elif name == "negative-and-minus-zero":
        scores = -np.abs(scores)
        scores[:, ::5] = -0.0
        scores[:, 1::5] = 0.0
    elif name == "two-segments-and-padding":
        seg = np.repeat([1, 2, 0], [300, 320, 20]).astype(np.int32)
    first = 0 if name == "fewer-keys-than-topk" else k - c
    return _causal_keys(scores, seg, first), topk


@pytest.mark.parametrize("block,group", [(16, 2), (64, 8)])
@pytest.mark.parametrize("case", [
    "more-ties-than-places", "fewer-keys-than-topk",
    "negative-and-minus-zero", "two-segments-and-padding", "plain",
])
def test_the_selection_kernel_in_interpret_mode_equals_the_xla_form_to_the_bit(
    case, block, group, monkeypatch
):
    """640 keys are five lane tiles: with groups of 2 the last tile is left
    over, with groups of 8 all five are (the tiles that do not fill a group
    run one by one); 16-row blocks make a grid of 4 (1 where a chunk has 16
    queries), 64-row blocks a grid of 1."""
    monkeypatch.setattr(sa, "_KTH_BLOCK_Q", block)
    monkeypatch.setattr(sa, "_KTH_TILE_GROUP", group)
    keys, topk = _kth_case(case)
    want = sa._kth_counts_xla(keys, topk)
    got = sa._kth_counts_kernel(keys, topk, interpret=True)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    # what each case is for
    tau, above, eq = (np.asarray(x) for x in want)
    if case == "more-ties-than-places":
        assert ((tau > 0) & (eq > topk - above)).any()
    if case == "fewer-keys-than-topk":
        assert not tau.any() and (above == np.arange(1, 17)).all()
    if case == "negative-and-minus-zero":
        assert tau.min() > 0 and tau.max() <= 0x80000000
    if case == "two-segments-and-padding":
        assert not tau[-20:].any() and not above[-20:].any()


@pytest.mark.parametrize("ties", [False, True])
def test_select_keys_gives_the_same_mask_and_counts_through_either_form(
    ties, monkeypatch
):
    """1024 tokens, two sequences and padding, topk 128: the chunks end at
    512 keys and at 1024. With every score equal the threshold is contested
    in every query past 128 keys, and ``_tie_cutoff`` runs on the kernel's
    counts."""
    rng = np.random.default_rng(21)
    _, (qi, ki, w) = _random_op_inputs(rng, 1024)
    if ties:
        qi = qi * 0
    seg = jnp.asarray(np.repeat([1, 2, 0], [300, 600, 124]), jnp.int32)
    calls = []
    kernel = sa._kth_counts_kernel
    monkeypatch.setattr(
        sa, "_kth_counts_kernel",
        lambda *a, **kw: calls.append(a[0].shape) or kernel(*a, **kw),
    )
    mask_x, stats_x = sa.select_keys(qi[0], ki[0], w[0], seg, 128)
    assert not calls  # the CPU's own form
    monkeypatch.setattr(
        sa, "select_impl",
        lambda shape=None: "kernel" if shape is None or not shape[1] % 128
        else "xla",
    )
    mask_k, stats_k = sa.select_keys(
        qi[0], ki[0], w[0], seg, 128, interpret=True
    )
    assert calls == [(512, 512), (512, 1024)]
    np.testing.assert_array_equal(mask_k, mask_x)
    assert [int(x) for x in stats_k] == [int(x) for x in stats_x]
    assert int(stats_x.kept_pairs) < int(stats_x.causal_pairs)


def test_select_impl_is_the_kernel_on_a_tpu_for_whole_tiles_only(monkeypatch):
    assert sa.select_impl() == "xla" and sa.select_impl((2048, 4096)) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.select_impl() == "kernel"
    assert sa.select_impl((2048, 16384)) == "kernel"
    assert sa.select_impl((256, 2304)) == "kernel"
    assert sa.select_impl((2048, 4099)) == "xla"  # no whole lane tile
    assert sa.select_impl((3, 4096)) == "xla"  # no whole sublane tile


# ------------------------------------------------------------ the trainer --
def test_the_trainer_trains_the_family_and_counts_its_pairs_exactly(
    tiny, trained
):
    engine, lens, stats = trained.engine, trained.lens, trained.stats
    assert engine.attn_impl == "sparse_topk" and engine.moe_impl == "ragged_dot"
    layers = tiny.cfg.num_layers
    kept = sum(min(p + 1, 8) for n in lens for p in range(n)) * layers
    causal = sum(n * (n + 1) // 2 for n in lens) * layers
    counters = engine.counters()
    assert counters["dsa_kept_pairs"] == kept
    assert counters["dsa_causal_pairs"] == causal
    assert sum(s["dsa_kept_pairs"] for s in stats) == kept
    # the pairs routed to the 8 experts held, not all 2 a token
    assert 0 < counters["moe_assignments"] < sum(lens) * 2 * layers
    assert counters["moe_stream_rows"] >= counters["moe_assignments"]
    assert counters["moe_stream_rows"] % (2 * layers) == 0
    assert counters["moe_overflow_chunks"] == 0
    after = engine.params["layers"]
    assert (np.asarray(after["w_idx_q"]) == trained.before["w_idx_q"]).all()
    trained.moved(None, "wq")


def test_dispatch_spans_name_the_selection_form_for_an_indexer_model_only(
    trained, monkeypatch
):
    from areal_tpu.api.cli_args import (
        MicroBatchSpec, OptimizerConfig, TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.sft.lm_engine import sft_loss_fn, sft_loss_weight_fn
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.utils import data as data_utils
    from areal_tpu.utils import tracing

    def check(engine, spans, want):
        assert engine.dsa_select_impl == want
        for name in ("train.grad_dispatch", "train.logp_dispatch"):
            attrs = [a for n, a in spans if n == name]
            assert attrs, name
            assert all(a.get("dsa_select_impl") == want for a in attrs), attrs
            assert all(("dsa_select_impl" in a) == bool(want) for a in attrs)

    # the family's engine, in the spans of the update it ran
    check(trained.engine, trained.spans, "xla")
    # and a model without an indexer says nothing
    seen = []
    span = tracing.span
    monkeypatch.setattr(
        tracing, "span",
        lambda name, *a, **attrs: seen.append((name, attrs))
        or span(name, *a, **attrs),
    )
    rng = np.random.default_rng(13)
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, n).astype(np.int32) for n in (9, 5, 7)]
    )
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    engine = SPMDTrainEngine(TrainEngineConfig(
        dtype="float32", param_dtype="float32",
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(
            type="adamw", lr=1e-2, warmup_steps_proportion=0.0,
            lr_scheduler_type="constant",
        ),
    ))
    engine.initialize(
        ft_spec=FinetuneSpec(1, 64, 8), model_config=tiny_config("qwen2"),
        seed=0,
    )
    try:
        engine.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
        engine.forward(batch)
        check(engine, seen, None)
    finally:
        engine.destroy()
