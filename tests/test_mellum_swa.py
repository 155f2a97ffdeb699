"""The mellum family (window and full rotary GQA layers three to one, every
layer the same heads, q and k normed a head, the whole head rotated, over
softmax-routed experts with no shared one) at test size against the plain
reference ``benchmark/reference_mellum.py``: each block kind, the logits
alone and packed with a border inside a window, the GRPO loss's gradient of
every parameter group, the window's edge, yarn's tables against a hand-written
``_compute_yarn_parameters``, the share of the experts, the published keys'
parameter count, the refusals by name and the trainer's spans."""

import math

import jax
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import basic
from benchmark import arith_mellum, reference_mellum as ref
from benchmark.kinds import train_steps_swa_long

CUT = "mellum2-12b-a2.5b.json"
KINDS = ("swa+moe", "full+moe")
LENS = (41, 23, 5)  # windows of 8 keys: the second sequence starts 3 tokens
# after a query of the first could still see back, the third is shorter than
# a window


def _seeded(cfg, seed):
    """Seeded weights whose norms are not the 1 a fresh model stores: a
    dropped q or k norm would pass every comparison at 1."""
    params = fb.init(cfg, seed)
    rng = np.random.default_rng(seed)
    for group in params["layers"].values():
        for name in ("q_norm", "k_norm", "input_norm", "post_attn_norm"):
            group[name] = group[name] * rng.uniform(
                0.5, 1.5, group[name].shape
            ).astype(np.float32)
    return params


FAMILY = fb.Family(
    "mellum", train_steps_swa_long.tiny_configs, ref.logits, _seeded
)
tiny = fb.tiny_fixture(FAMILY)
trained = fb.trained_fixture()


def test_tiny_config_is_the_family_and_the_cut_builds():
    t = tiny_config("mellum")
    assert t.layer_kinds == ("swa+moe",) * 3 + ("full+moe",)
    assert (t.full.num_heads, t.swa.num_heads, t.num_kv_heads) == (4, 4, 2)
    assert (t.layer_window, t.num_held_experts, t.num_experts) == (8, 4, 16)
    assert t.qk_norm == "head" and t.shared_expert_size == 0
    assert not t.full.head_gate and not t.swa.head_gate
    assert (t.full.rotary_dim, t.swa.rotary_dim) == (0, 0)  # the whole head
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == ("swa+moe",) * 3 + ("full+moe",)
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        64, 8, 12288
    )
    assert (cut.hidden_size, cut.num_kv_heads, cut.head_dim) == (2304, 4, 128)
    assert (cut.full.num_heads, cut.swa.num_heads) == (32, 32)
    assert (cut.layer_window, cut.expert_ffn_size) == (1024, 896)
    assert (cut.full.yarn_factor, cut.full.yarn_original_len) == (16.0, 8192)
    assert cut.swa.yarn_factor == 1.0 and cut.swa.rope_theta == 500000.0
    assert cut.routed_scaling_factor == 1.0 and cut.norm_topk_prob


def test_the_published_keys_give_the_published_model():
    """28 layers and 64 experts: 12.15 B parameters, 2.44 B a token, by the
    yardstick's count; the program's config of them holds every expert."""
    d = fb.published("Mellum2-12B-A2.5B-Instruct")
    full = from_hf_config(d)
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        28, 64, 0
    )
    assert [n for _, n in tr._runs(full.layer_kinds)][:4] == [3, 1, 3, 1]
    assert full.kind_layers("swa+moe") == 21 and full.vocab_size == 98304
    cut = from_hf_config(fb.cut(CUT))
    assert full.full == cut.full and full.swa == cut.swa
    n = arith_mellum.param_count(d)
    assert n == 28 * (21233664 + 147456 + 396361728 + 2 * 2304 + 256) + (
        452984832 + 2304
    )
    assert round(n / 1e9, 2) == 12.15
    assert round(arith_mellum.active_param_count(d) / 1e9, 2) == 2.44


def test_the_yardsticks_count_is_the_programs_own_leaves(tiny):
    cfg, keys, params = tiny
    assert arith_mellum.param_count(keys) == tr.count_params(params)
    c = fb.cut(CUT)
    shapes = jax.eval_shape(
        lambda: tr.init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    n = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
    assert n == arith_mellum.param_count(c) == 340350208
    # a dense entry takes ``intermediate_size``
    dense = dict(keys, mlp_layer_types=["dense"] + keys["mlp_layer_types"][1:])
    dcfg = from_hf_config(dense)
    assert dcfg.layer_kinds[0] == "swa+dense"
    shapes = jax.eval_shape(
        lambda: tr.init_params(dcfg, jax.random.PRNGKey(0))
    )
    assert arith_mellum.param_count(dense) == sum(
        math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)
    )


@pytest.mark.parametrize("bad,word", [
    ({"attention_bias": True}, "attention_bias"),
    ({"rope_parameters": {
        "full_attention": {"rope_type": "llama3", "rope_theta": 5e5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 5e5},
    }}, "rope_type 'llama3'"),
    ({"rope_parameters": {
        "full_attention": {"rope_type": "default", "rope_theta": 5e5},
        "sliding_attention": {"rope_type": "linear", "rope_theta": 5e5},
    }}, "mellum: rope_type 'linear'"),
    ({"layer_types": ["sliding_attention"] * 3}, "one entry a layer"),
    ({"mlp_layer_types": ["sparse"] * 5}, "one entry a layer"),
    ({"layer_types": ["chunked_attention"] * 4}, "unknown layer types"),
    ({"mlp_layer_types": ["shared"] * 4}, "unknown layer types"),
])
def test_what_the_file_cannot_state_is_refused_by_name(bad, word):
    fb.check_refused(CUT, bad, word)


def _hf_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters`` (modeling_rope_utils, the
    default ``truncate``), written out by hand in float64."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1
    )
    extrapolation_factor = 1 - ramp
    inv = (
        interpolation * (1 - extrapolation_factor)
        + extrapolation * extrapolation_factor
    )
    return inv, 0.1 * math.log(factor) + 1.0, low, high


def test_yarn_over_the_whole_head_against_a_hand_written_hf_table():
    # the published full-attention rotation: all 128 channels, factor 16
    want, attention_factor, low, high = _hf_yarn(
        128, 500000.0, 16.0, 8192, 32.0, 1.0
    )
    assert (low, high) == (18, 35)
    assert abs(attention_factor - 1.2772588722239782) < 1e-15
    got = basic.rope_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    assert got.shape == (64,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    c = fb.cut(CUT)
    inv, scale = ref.rotation(c, "full+moe")
    np.testing.assert_array_equal(inv, got)
    assert scale == c["rope_parameters"]["full_attention"]["attention_factor"]
    assert scale == attention_factor
    plain, one = ref.rotation(c, "swa+moe")
    np.testing.assert_array_equal(plain, basic.rope_inv_freq(128, 500000.0))
    assert one == 1.0
    # positions to four times the original length: the slowest channels turn
    # a sixteenth as fast, the fastest as they did
    cos, sin = basic.rope_at(np.asarray([[32767]]), got, scale)
    np.testing.assert_allclose(
        cos[0, 0], scale * np.cos(32767 * want), atol=2e-3
    )
    np.testing.assert_allclose(got[:19], want[:19], rtol=1e-6)
    f = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(got[:19], f[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], f[35:] / 16.0, rtol=1e-6)


def test_the_tiny_rotations_are_the_references(tiny):
    cfg, keys, _ = tiny
    for kind, rc in (("full+moe", cfg.full), ("swa+moe", cfg.swa)):
        want, scale = ref.rotation(keys, kind)
        got = basic.rope_inv_freq(
            cfg.head_dim, rc.rope_theta, rc.yarn_factor,
            rc.yarn_original_len, rc.yarn_beta_fast, rc.yarn_beta_slow,
        )
        np.testing.assert_array_equal(got, want)
        assert scale == rc.attention_factor
    # yarn changes the table: a full layer's is not the window layers'
    assert not np.array_equal(
        ref.rotation(keys, "full+moe")[0], ref.rotation(keys, "swa+moe")[0]
    )


@pytest.mark.parametrize("kind", KINDS)
def test_each_block_kind_matches_the_reference(tiny, kind):
    fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(tiny.keys, kind, x, lp)[0]
    )


@pytest.mark.parametrize("norm", ["q_norm", "k_norm"])
def test_the_head_norms_are_in_the_result(tiny, norm):
    """A norm's weight moved changes the logits of program and reference
    alike: neither drops it."""
    params = tiny.params
    tok, seg, pos, _ = fb.row(LENS, FAMILY.total, tiny.cfg.vocab_size)
    stack = params["layers"]["swa+moe"]
    moved = dict(params, layers=dict(
        params["layers"], **{"swa+moe": dict(stack, **{norm: stack[norm] * 1.5})}
    ))
    base, _ = fb.forward(tiny, tok, seg, pos)
    got, _, _ = fb.check_logits(FAMILY, tiny, LENS, params=moved)
    assert np.abs(got - base)[0, :sum(LENS)].max() > 1e-4


def test_logits_match_alone_and_packed_with_a_border_inside_a_window(tiny):
    _, stats, seqs = fb.check_logits(FAMILY, tiny, LENS)
    inside = sum(sum(min(t + 1, 8) for t in range(len(s))) for s in seqs)
    causal = sum(len(s) * (len(s) + 1) // 2 for s in seqs)
    assert inside == sum(arith_mellum.window_pairs(len(s), 8) for s in seqs)
    assert [int(x) for x in stats.counts["swa_pairs"]] == [inside] * 3
    assert [int(x) for x in stats.counts["swa_causal_pairs"]] == [causal] * 3


def test_logprobs_match_the_references_at_a_temperature(tiny):
    from areal_tpu.ops.functional import target_aligned_logprobs

    tok, seg, pos, seqs = fb.row(LENS, FAMILY.total, tiny.cfg.vocab_size, 6)
    logits, _ = fb.forward(tiny, tok, seg, pos)
    got = np.asarray(jax.jit(lambda lg: target_aligned_logprobs(
        lg, {"tokens": tok, "segment_ids": seg}, temperature=0.7
    ))(logits))
    reference = jax.jit(lambda lg, toks: ref.logprobs_of(lg, toks, 0.7))

    def to_row(a):  # one shape, one compile: rows past the sequence are cut
        pad = np.zeros((FAMILY.total - len(a),) + a.shape[1:], a.dtype)
        return np.concatenate([a, pad])

    at = 0
    for s in seqs:
        lg = fb.reference_logits(FAMILY, tiny, s)
        want = np.asarray(reference(to_row(lg), to_row(s)))[:len(s) - 1]
        np.testing.assert_allclose(
            got[0, at + 1:at + len(s)], want, atol=5e-5
        )
        at += len(s)


def test_grpo_loss_and_every_gradient_match_the_references(tiny):
    seen = fb.check_gradients(FAMILY, tiny)
    assert {"q_norm", "k_norm", "wq", "wk", "wv", "wo", "w_router", "w_gate",
            "w_up", "w_down", "input_norm", "post_attn_norm", "embedding",
            "lm_head", "final_norm"} <= set(seen)


@pytest.mark.parametrize("form", ["xla", "splash"])
def test_the_windows_edge_is_exact(form):
    """The kernel's band here is 128 keys in tiles of 128, the size a band's
    own tile has one border inside."""
    fb.check_window_edge(form, 512, 128, 171, ref.masked_softmax_attention)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_a_share_of_the_experts_is_that_shares_part(tiny, first):
    cfg, keys, _ = tiny
    lp = fb.layer_of(fb.whole(FAMILY, tiny)[1], "swa+moe")
    h = fb.normal(5, 1, 29, cfg.hidden_size)
    mine = fb.holding(lp, first, 4)
    part, stats = fb.share_part(
        cfg, h, lp, [mine[k] for k in fb.EXPERT_LEAVES], first
    )
    want, weight = jax.jit(lambda h, mine, lp: (
        ref.experts(keys, h, mine, first_expert=first),
        ref.routing(keys, h, lp),
    ))(h[0], mine, lp)
    np.testing.assert_allclose(part[0], want, atol=1e-5)
    assert int(stats.assignments) == int(
        (np.asarray(weight)[:, first:first + 4] > 0).sum()
    )


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model (the guide's section 4): the four
    holders of 4 of the 16 experts route over all 16 with the same router;
    their parts add up to the uncut reference's layer (there is no shared
    expert to count once)."""
    fb.check_shares_add_up(FAMILY, tiny, "full+moe", ref.experts)


def test_the_refusals_by_what_the_window_declares(tiny, tmp_path):
    fb.check_refusals(FAMILY, tiny, tmp_path, "B.3", "mellum", "B.3")


@pytest.mark.parametrize("attn_impl,row_len,want", [
    ("flash", 32768, {"attn_bwd_window": "split", "attn_bwd_full": "split",
                      "attn_edge": 2048}),
    ("flash", 16384, {"attn_bwd_window": "split", "attn_bwd_full": "fused",
                      "attn_edge": 2048}),
    ("flash", 2048, {"attn_bwd_window": "split", "attn_bwd_full": "fused",
                     "attn_edge": 512}),
    ("xla", 32768, {}),
])
def test_a_grad_dispatch_says_which_backward_each_core_took(
    attn_impl, row_len, want
):
    from areal_tpu.ops import flash
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["mellum2_12b_a2p5b"]()
    assert tr.grad_span_attrs(cfg, attn_impl, row_len) == want
    if want:  # what the kernel instance of that row is built with
        inst = flash.instance(row_len, 128, cfg.swa.window, heads=32)
        assert inst == ("splash", 1023, flash.BAND_EDGE, False, 512)
    # a model without window layers says the full core's instance alone
    said = tr.grad_span_attrs(tiny_config("qwen2"), attn_impl, row_len)
    assert set(said) == ({"attn_edge", "attn_bwd_full"} if want else set())


def test_the_trainer_trains_the_family_and_counts_its_pairs_exactly(trained):
    engine, lens = trained.engine, trained.lens
    assert (engine.attn_impl, engine.moe_impl) == ("xla", "ragged_dot")
    assert engine._span_attrs["layer_window"] == 8
    assert set(engine.counters()) >= {
        "swa_pairs", "swa_causal_pairs", "moe_assignments",
        "moe_max_expert_assignments", "moe_stream_rows",
        "moe_overflow_chunks",
    }
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = trained.attrs(name)
        assert all(
            a["layer_window"] == 8 and "window" not in a for a in attrs
        ), (name, attrs)
        # the XLA form has no backward forms to name
        assert not any("attn_bwd_window" in a for a in attrs)
    counters = engine.counters()
    assert counters["swa_pairs"] == 3 * sum(
        arith_mellum.window_pairs(n, 8) for n in lens
    )
    assert counters["swa_causal_pairs"] == 3 * sum(
        n * (n + 1) // 2 for n in lens
    )
    for kind in trained.before:  # every group of every kind moved
        trained.moved(kind, "wq", "q_norm", "k_norm", "w_down")
