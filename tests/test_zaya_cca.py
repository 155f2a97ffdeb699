"""The zaya family (one block kind, ``cca+moe``: attention inside a compressed
latent that two causal convolutions mix, over experts of which a token takes
ONE, chosen by an MLP router whose state passes from layer to layer) at test
size against the plain reference ``benchmark/reference_zaya.py``: the block,
the logits alone and packed with borders one and two tokens apart, the GRPO
loss's gradient of every parameter group (the router's leaves and the carried
scalar among them), both convolutions and the value shift at a border, the
half rotation against hand-worked values, the carried router against the
uncarried one, the pair count past an int32, the two shares of the experts, one expert a token, and the
refusals by name."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import basic, moe as moe_ops
from benchmark import reference_zaya as ref
from benchmark.kinds import train_steps_cca

CUT = "zaya1-8b.json"
KIND = "cca+moe"


def _seeded(cfg, key):
    """Seeded weights in which nothing is at the value that would hide it:
    biases, scales, the temperature and the norms moved off 0 and 1."""
    params = fb.init(cfg, key)
    rng = np.random.default_rng(key)
    stack = params["layers"][KIND]
    for name, leaf in stack.items():
        if ("bias" in name or "scale" in name or name.startswith("b_router")
                or name in ("cca_temp", "router_norm")):
            stack[name] = leaf + (
                rng.normal(size=leaf.shape) * 0.1
            ).astype(np.float32)
    # the router's matrices larger, its last bias and the balancing bias
    # smaller, so that a token's own state and not a bias picks its expert
    # (at std 0.02 the probabilities differ by 1e-3)
    for name in ("w_router_in", "w_router_1", "w_router_2", "w_router_out"):
        stack[name] = stack[name] * np.float32(20.0)
    for name in ("b_router_out", "router_bias"):
        stack[name] = stack[name] * np.float32(0.1)
    # the carried scalar off the zero it is drawn at, which would multiply
    # the carried path away and let a dropped carry pass
    stack["router_carry"] = np.full_like(stack["router_carry"], 0.5)
    return params


# the row: sequences of one and of two tokens between longer ones
FAMILY = fb.Family(
    "zaya", train_steps_cca.tiny_configs, ref.logits, _seeded, total=32
)
tiny = fb.tiny_fixture(FAMILY)


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("zaya")
    assert t.layer_kinds == (KIND,) * 3 and t.tie_word_embeddings
    assert (t.cca.num_heads, t.cca.num_kv_heads, t.head_dim) == (4, 2, 16)
    assert (t.num_held_experts, t.num_experts, t.num_experts_per_tok) == (
        4, 8, 1
    )
    assert t.router.hidden_size == 8
    assert not t.norm_topk_prob  # one a token takes the probability as it is
    # the program's own rendering of it reads back as itself
    from areal_tpu.models.hf_io import default_hf_config_dict

    assert from_hf_config(default_hf_config_dict(t)) == t
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == (KIND,) * 4
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        16, 8, 32784
    )
    assert (cut.cca.num_heads, cut.cca.num_kv_heads, cut.head_dim) == (
        8, 2, 128
    )
    assert (cut.cca.conv_taps, cut.cca.rotary_dim) == ((2, 2), 64)
    assert cut.cca.rope_theta == 5_000_000.0
    assert cut.router.hidden_size == 256 and cut.q_dim == 1024
    full = from_hf_config(fb.published("ZAYA1-8B"))  # the keys, unchanged
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        40, 16, 0
    )
    assert full.cca == cut.cca and full.router == cut.router
    assert tr._runs(full.layer_kinds) == [(KIND, 40)]  # one scan


@pytest.mark.parametrize("bad,word", [
    ({"layer_types": ["hybrid", "hybrid", "hybrid", "hybrid_sliding"]},
     "hybrid_sliding"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"num_experts_per_tok": 2}, "renormalisation"),
    ({"layer_types": ["hybrid"] * 3}, "entries"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


def test_the_half_rotation_against_hand_worked_values():
    # the published rotation: the first 64 of 128 channels at 5,000,000
    inv = basic.rope_inv_freq(64, 5_000_000.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(
        inv, [5_000_000.0 ** (-2 * j / 64) for j in range(32)], rtol=1e-6
    )
    assert inv[0] == 1.0 and abs(inv[16] - 5_000_000.0 ** -0.5) < 1e-9
    # a head of 8 of which the first 4 turn: channel pairs (j, j + 2) at
    # frequencies 1 and 1/2, position 3; channels 4.. as they were
    cos, sin = basic.rope_at(
        np.asarray([[0, 3]]), np.asarray([1.0, 0.5], np.float32)
    )
    x = np.asarray([[[[1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]]] * 2],
                   np.float32)
    y = np.asarray(basic.rotate_leading(x, cos, sin))
    np.testing.assert_allclose(y[0, 0, 0], x[0, 0, 0])
    c, s = math.cos(3.0), math.sin(3.0)
    c2, s2 = math.cos(1.5), math.sin(1.5)
    np.testing.assert_allclose(y[0, 1, 0], [
        1 * c - 3 * s, 2 * c2 - 4 * s2, 3 * c + 1 * s, 4 * c2 + 2 * s2,
        5, 6, 7, 8,
    ], rtol=1e-5)
    # the reference's own table is the program's, and its rotation the same
    cfg, keys = train_steps_cca.tiny_configs()
    want = ref.rotation(keys)
    np.testing.assert_array_equal(
        basic.rope_inv_freq(cfg.cca.rotary_dim, cfg.cca.rope_theta), want
    )
    z = fb.normal(0, 1, 9, 3, 16)
    cos, sin = basic.rope_at(np.arange(9)[None], want)
    np.testing.assert_allclose(
        basic.rotate_leading(z, cos, sin)[0],
        jax.jit(lambda z: ref.rotate(z, want))(z[0]), atol=1e-6,
    )


def test_a_block_matches_the_reference_and_hands_the_routers_state_on(tiny):
    stats = fb.check_block(
        tiny, KIND, lambda x, r, lp: ref.layer(tiny.keys, x, r, lp)[:2],
        index=1, carry=fb.normal(2, 37, 8),
    )
    # a layer reports (its FFN's, its mixer's): some tokens' expert is away
    assert 0 < int(stats[0].assignments) < 37 and stats[1] is None


def test_logits_match_alone_and_packed_with_borders_one_and_two_apart(tiny):
    cfg = tiny.cfg
    # sequences of one and of two tokens between longer ones: a convolution
    # of two taps after one of two reaches two tokens back, the shift one
    _, stats, seqs = fb.check_logits(FAMILY, tiny, [10, 9, 1, 2, 7])
    causal = sum(len(s) * (len(s) + 1) // 2 for s in seqs)
    # partial sums a stretch of a row, the layers leading
    counts = stats.counts
    assert np.asarray(counts["cca_causal_pairs"]).reshape(3, -1).sum(1).tolist() == (
        [causal] * 3
    )
    assert [int(x) for x in counts["cca_starts"]] == [5] * 3
    # the model's mechanisms' counts and no other family's
    assert set(counts) == set(tr.count_names(cfg)) == {
        "cca_causal_pairs", "cca_starts", "moe_assignments",
        "moe_max_expert_assignments", "moe_stream_rows", "moe_overflow_chunks",
    }
    # a held share of a half: the slab IS the stream, nothing is compacted
    assert moe_ops._held_rows(32, 4, 8) == 32
    assert float(counts["moe_stream_rows"]) == 3 * 32
    assert float(counts["moe_overflow_chunks"]) == 0


def test_both_convolutions_and_the_shift_are_exact_at_a_border(tiny):
    """In the packed form: replacing the LAST token's input of one sequence
    leaves the next sequence's q, k, v bit-for-bit what they were; replacing
    token t inside a sequence moves q and k at t, t + 1, t + 2 and v at t,
    t + 1 and nothing else."""
    cfg, keys, params = tiny
    lp = fb.layer_of(params, KIND)
    lens = [11, 3, 9]
    seg = np.asarray([[1] * 11 + [2] * 3 + [3] * 9 + [0] * 5], np.int32)
    pos = np.asarray(
        [list(range(11)) + list(range(3)) + list(range(9)) + [0] * 5],
        np.int32,
    )
    h = fb.normal(2, 1, 28, cfg.hidden_size)
    mix = jax.jit(lambda h: tr.cca_qkv(
        cfg, h, lp, seg, *tr._rotations(cfg, pos)["cca"]
    ))
    base = [np.asarray(a) for a in mix(h)]
    # and the packed form is the reference's, sequence by sequence (the
    # reference is causal: at one length, a sequence's rows lead)
    reference = jax.jit(lambda h: ref.qkv(keys, h, lp))
    at = 0
    for n in lens:
        alone = np.zeros((max(lens), cfg.hidden_size), np.float32)
        alone[:n] = h[0, at:at + n]
        for got, w in zip(base, reference(alone)):
            np.testing.assert_allclose(got[0, at:at + n], w[:n], atol=1e-5)
        at += n

    def moved(t):
        h2 = h.copy()
        h2[0, t] += 3.0
        return [
            np.flatnonzero(
                np.abs(np.asarray(a) - b).reshape(28, -1).max(axis=1) > 0
            ).tolist()
            for a, b in zip(mix(h2), base)
        ]

    assert moved(10) == [[10], [10], [10]]  # the last token of sequence 1
    assert moved(13) == [[13], [13], [13]]  # and of sequence 2
    assert moved(4) == [[4, 5, 6], [4, 5, 6], [4, 5]]
    assert moved(11) == [[11, 12, 13], [11, 12, 13], [11, 12]]
    assert moved(12) == [[12, 13], [12, 13], [12, 13]]  # the border cuts it
    # padding is nobody's previous token, and takes nobody's either
    assert moved(23) == [[23], [23], [23]] == [[23]] * 3
    assert moved(22) == [[22], [22], [22]]  # the row's last real token


def test_grpo_loss_and_every_gradient_match_the_references(tiny):
    def untrained(g, w):  # state that nothing trains
        assert float(np.abs(g).max()) == float(np.abs(w).max()) == 0

    def carry(g, w):  # the first layer receives no state
        assert float(g[0]) == float(w[0]) == 0
        return g[1:], w[1:]

    seen = fb.check_gradients(
        FAMILY, tiny, n=32,
        special={"router_bias": untrained, "router_carry": carry},
    )
    # one expert a token, its weight the probability as it is: the loss
    # reaches every leaf of the router, and the scalar that carries its state
    assert {"w_router_in", "b_router_in", "router_norm", "w_router_1",
            "b_router_1", "w_router_2", "b_router_2", "w_router_out",
            "b_router_out", "router_carry"} <= set(seen)
    assert {"wq", "wk", "wv_own", "wv_prev", "wo", "cca_conv0",
            "cca_conv0_bias", "cca_conv1", "cca_conv1_bias", "cca_temp",
            "attn_res_scale", "attn_out_bias", "ffn_res_bias",
            "ffn_out_scale", "w_gate", "w_down", "embedding"} <= set(seen)


def test_one_a_token_takes_the_probability_as_it_is():
    logits = jnp.asarray([[0.1, 0.7, 0.2, 0.0], [0.3, 0.3, 0.1, 0.2]])
    p, i, probs = moe_ops.router_topk(logits, 1, False)
    np.testing.assert_allclose(p[:, 0], probs.max(-1))
    assert i[:, 0].tolist() == [1, 0]  # a tie goes to the lower index
    # renormalised it would be 1 whatever the router says
    assert moe_ops.router_topk(logits, 1, True)[0].tolist() == [[1.0], [1.0]]
    # the balancing bias moves the choice and not the weight
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    p_b, i_b, _ = moe_ops.router_topk(logits, 1, False, bias=bias)
    assert i_b[:, 0].tolist() == [2, 2]
    np.testing.assert_allclose(p_b[:, 0], probs[:, 2])
    grad = jax.grad(
        lambda b: moe_ops.router_topk(logits, 1, False, bias=b)[0].sum()
    )(bias)
    assert float(jnp.abs(grad).max()) == 0


def test_a_carried_scalar_of_zero_is_the_uncarried_router_bit_for_bit(tiny):
    cfg, _, params = tiny
    stack = params["layers"][KIND]
    rng = np.random.default_rng(5)
    h = rng.normal(size=(1, 29, cfg.hidden_size)).astype(np.float32)
    r_prev = rng.normal(
        size=(1, 29, cfg.router.hidden_size)
    ).astype(np.float32)

    def router(gamma, carried):
        lp = dict(fb.layer_of(params, KIND, 1), router_carry=np.float32(gamma))
        # op by op: a compiler free to fuse the addition into the product
        # before it rounds the two forms apart in the last bit
        return moe_ops.router_mlp(lp, h, cfg.rms_norm_eps, carried)

    uncarried = router(0.5, None)
    for got, want in zip(router(0.0, r_prev), uncarried):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(router(0.5, r_prev), uncarried):
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    # and through the stack: the scalar reaches the logits
    tok, seg, pos, _ = fb.row([14, 6, 9], 32, cfg.vocab_size, seed=5)

    def logits(gamma):
        p = dict(params, layers={KIND: dict(
            stack, router_carry=np.full((3,), gamma, np.float32)
        )})
        return fb.forward(tiny, tok, seg, pos, p)[0]

    assert np.abs(logits(0.5) - logits(0.0)).max() > 1e-6


def test_the_pair_count_of_a_long_sequence_does_not_pass_an_int32():
    """One 65,536-token sequence attends 2,147,516,416 pairs, more than an
    int32 holds: the count leaves the device in partial sums."""
    cfg = tiny_config("zaya")
    n = 65536 + 4096 + 77
    seg = np.zeros((1, n), np.int32)
    pos = np.zeros((1, n), np.int32)
    seg[0, :65536], pos[0, :65536] = 1, np.arange(65536)
    seg[0, 65536:65536 + 4096], pos[0, 65536:65536 + 4096] = 2, np.arange(4096)
    cca = tr.MECHANISMS["cca"]
    assert list(cca.counts) == ["cca_causal_pairs", "cca_starts"]
    causal_pairs, starts = jax.jit(
        lambda s, p: cca.count(cfg, {}, s, p)
    )(seg, pos)
    assert causal_pairs.dtype == jnp.int32
    pairs = np.asarray(causal_pairs, np.int64)
    assert pairs.shape[0] == 3 and (pairs >= 0).all()
    want = 65536 * 65537 // 2 + 4096 * 4097 // 2
    assert want > 2 ** 31 and pairs.sum(axis=1).tolist() == [want] * 3
    assert np.asarray(starts).tolist() == [2] * 3


def test_the_two_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model: the holders of experts 0..3 and
    4..7 route over all 8 with the same router (the same carried state);
    their parts add up to the uncut reference's expert sublayer."""
    cfg, keys, _ = tiny
    lp = fb.layer_of(fb.whole(FAMILY, tiny)[1], KIND, 1)
    assert lp["w_gate"].shape[0] == 8
    h = fb.normal(5, 1, 29, cfg.hidden_size)
    r_prev = fb.normal(6, 1, 29, 8)
    whole_keys = dict(keys, num_local_experts=8)

    @jax.jit
    def reference(h, r_prev, lp):
        p, _ = ref.router(whole_keys, h, lp, r_prev)
        parts = [
            ref.experts(h, fb.holding(lp, first, 4), p, first)
            for first in (0, 4)
        ]
        return ref.experts(h, lp, p), parts, ref.chosen(p, lp)

    want, want_parts, chosen = reference(h[0], r_prev[0], lp)

    @jax.jit
    def program(h, r_prev, lp):
        logits, _ = moe_ops.router_mlp(lp, h, cfg.rms_norm_eps, r_prev)
        return [
            moe_ops.moe_ffn_scored(
                h, logits, *(lp[k][first:first + 4] for k in fb.EXPERT_LEAVES),
                1, False, first_expert=first, router_bias=lp["router_bias"],
            )
            for first in (0, 4)
        ]

    total, pairs = 0.0, 0
    got = program(h, r_prev, lp)
    for (part, stats), want_part in zip(got, want_parts):
        total = total + np.asarray(part)[0]
        pairs += int(stats.assignments)
        # each share's part is the reference's for the same experts
        np.testing.assert_allclose(part[0], want_part, atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert pairs == 29  # every token's one pair, once
    # a token whose expert is the other holder's gets zeros here
    away = np.asarray(chosen) >= 4
    assert away.any() and not np.asarray(got[0][0])[0, away].any()


def test_scores_from_outside_run_the_layer_the_product_ran():
    """``moe_ffn`` is ``moe_ffn_scored`` on ``x @ w_router``: the same
    numbers, and today's callers lower to today's text."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((32, 8)) * 0.1, jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((8, 32, 16)) * 0.1,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((8, 16, 32)) * 0.1, jnp.float32)
    valid = jnp.asarray(rng.random((2, 16)) > 0.2)
    a, sa = moe_ops.moe_ffn(x, wr, wg, wu, wd, 2, True, valid=valid)
    logits = jnp.dot(
        x, wr, precision=jax.lax.Precision.HIGHEST
    )
    b, sb = moe_ops.moe_ffn_scored(x, logits, wg, wu, wd, 2, True, valid=valid)
    np.testing.assert_array_equal(a, b)
    assert [float(v) for v in sa[:3]] == [float(v) for v in sb[:3]]


def test_the_mesh_form_takes_the_routers_scores_split_as_the_tokens():
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = tiny_config("zaya")
    params = _seeded(cfg, 2)
    lp = fb.layer_of(params, KIND)
    mesh = Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2, 1, 1, 1),
        ("data", "fsdp", "seq", "expert", "tensor"),
    )
    axes = tr.param_logical_axes(cfg)["layers"][KIND]
    assert "w_router" not in moe_ops.sparse_leaves(cfg)
    sharded = moe_ops.make_sharded_moe_ffn(
        cfg, mesh, {k: P(*(None,) * (len(axes[k]) - 1))
                    for k in moe_ops.sparse_leaves(cfg)},
    )
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((2, 16, cfg.hidden_size)), jnp.float32)
    valid = jnp.ones((2, 16), bool)
    logits, _ = moe_ops.router_mlp(lp, h, cfg.rms_norm_eps)
    want, w_stats = moe_ops.moe_ffn_from_params(
        cfg, lp, h, valid=valid, router_logits=logits
    )
    with mesh:
        got, g_stats = jax.jit(sharded)(lp, h, valid, logits)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(g_stats.assignments) == float(w_stats.assignments)


def test_the_refusals_by_name(tiny, tmp_path):
    fb.check_refusals(FAMILY, tiny, tmp_path, "B.4", "zaya", "zaya")


def _seed_the_carry(engine):
    # the carried scalar is drawn at the zero a fresh model stores; the
    # benchmark and these tests move it off
    assert np.asarray(
        engine.params["layers"][KIND]["router_carry"]
    ).tolist() == [0.0] * 3
    train_steps_cca.seed_carry(engine.params)


trained = fb.trained_fixture(_seed_the_carry)


def test_the_trainer_trains_the_family_and_counts_exactly(trained):
    engine, lens = trained.engine, trained.lens
    assert (engine.attn_impl, engine.moe_impl) == ("xla", "ragged_dot")
    assert engine._span_attrs["router"] == "mlp_carried"
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = trained.attrs(name)
        assert all(
            a["router"] == "mlp_carried" and a["moe_impl"] == "ragged_dot"
            for a in attrs
        ), (name, attrs)
    counters = engine.counters()
    assert counters["cca_causal_pairs"] == 3 * sum(
        n * (n + 1) // 2 for n in lens
    )
    assert counters["cca_starts"] == 3 * len(lens)
    assert 0 < counters["moe_assignments"] < 3 * sum(lens)
    assert counters["moe_stream_rows"] > 0  # the held-share counters
    assert counters["moe_overflow_chunks"] == 0
    step = trained.exported
    assert step["spmd/cca_causal_pairs"] == counters["cca_causal_pairs"]
    assert step["spmd/cca_starts"] == counters["cca_starts"]
    trained.moved(KIND, "wq", "cca_conv1", "w_router_in", "w_router_out",
                  "w_down", "attn_out_scale")
    # state that nothing trains stays what it was
    np.testing.assert_array_equal(
        engine.params["layers"][KIND]["router_bias"],
        trained.before[KIND]["router_bias"],
    )
