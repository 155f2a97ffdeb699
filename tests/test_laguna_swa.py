"""The laguna family (a stack of block kinds: rotary GQA layers that attend to
the whole sequence or to a window of it, each kind with its own heads and
rotation and a gate a head, over a dense or a softmax-routed sparse FFN) at
test size against the plain reference ``benchmark/reference_laguna.py``:
every block kind, the logits alone and packed, the GRPO loss's gradient of
every parameter group, the window's edge in both attention forms, the
rotations against hand-worked values, the share of the experts, and the
refusals by name."""

import math

import jax
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import basic
from areal_tpu.ops.flash import flash_segment_attention
from benchmark import reference_laguna as ref
from benchmark.kinds import train_steps_swa

CUT = "laguna-xs.2.json"
LENS = (41, 23, 5)  # windows of 8 keys: the second sequence starts 3 tokens
# after a query of the first could still see back, the third is shorter than
# a window

FAMILY = fb.Family("laguna", train_steps_swa.tiny_configs, ref.logits)
tiny = fb.tiny_fixture(FAMILY)
trained = fb.trained_fixture()


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("laguna")
    assert set(t.layer_kinds) == {"full+dense", "swa+moe", "full+moe"}
    assert (t.full.num_heads, t.swa.num_heads, t.num_kv_heads) == (6, 8, 2)
    assert (t.layer_window, t.num_held_experts, t.num_experts) == (8, 4, 16)
    assert t.router_act == "softmax" and not t.shared_expert_gate
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == (
        "full+dense", "swa+moe", "swa+moe", "swa+moe", "full+moe"
    )
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        256, 16, 12544
    )
    assert (cut.full.num_heads, cut.swa.num_heads) == (48, 64)
    assert (cut.full.rotary_dim, cut.swa.rotary_dim) == (64, 0)
    assert cut.layer_window == 512 and cut.routed_scaling_factor == 2.5
    full = from_hf_config(fb.published("Laguna-XS.2"))  # the keys, unchanged
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        40, 256, 0
    )
    assert full.full == cut.full and full.swa == cut.swa
    assert [n for _, n in tr._runs(full.layer_kinds)][:4] == [1, 3, 1, 3]
    # gating "per-head", a gating_types list, a soft cap of 0
    sibling = from_hf_config(fb.published("Laguna-S-2.1"))
    assert (sibling.swa.num_heads, sibling.full.yarn_factor) == (72, 128.0)


@pytest.mark.parametrize("bad,word", [
    ({"gating": "element-wise"}, "gating"),
    ({"moe_apply_router_weight_on_input": True}, "router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "soft-capped"),
    ({"num_attention_heads_per_layer": [48, 64, 64, 32, 48]}, "one stack"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


def test_yarn_tables_and_the_half_rotation_against_hand_worked_values():
    # the published full-attention rotation: r = 64 of 128 channels
    inv = basic.rope_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    lo = 64 * math.log(4096 / (2 * math.pi * 64)) / (2 * math.log(500000))
    hi = 64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(500000))
    assert (math.floor(lo), math.ceil(hi)) == (5, 16)
    f = [500000.0 ** (-2 * j / 64) for j in range(32)]
    # at and below lo: as they are; at and beyond hi: over the factor; 8 is
    # 3/11 of the way
    np.testing.assert_allclose(inv[:6], f[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], np.asarray(f[16:]) / 64, rtol=1e-6)
    np.testing.assert_allclose(
        inv[8], f[8] * (1 - 3 / 11) + f[8] / 64 * (3 / 11), rtol=1e-6
    )
    # the window layers': plain rope, what rope_frequencies makes
    plain = basic.rope_inv_freq(128, 10000.0)
    cos_t, sin_t = basic.rope_frequencies(128, 40, 10000.0)
    pos = np.arange(40)[None]
    cos, sin = basic.rope_at(pos, plain)
    np.testing.assert_allclose(cos[0], cos_t, atol=2e-6)
    np.testing.assert_allclose(sin[0], sin_t, atol=2e-6)
    x = fb.normal(0, 1, 40, 2, 128)
    np.testing.assert_allclose(
        basic.rotate_leading(x, cos, sin),
        basic.apply_rope(x, pos, cos_t, sin_t), atol=1e-5,
    )
    # a partly rotated head: channel pairs (j, j + 2) of the first 4, the
    # attention factor on cos and sin, channels 4.. untouched
    inv4 = np.asarray([1.0, 0.5], np.float32)
    cos, sin = basic.rope_at(np.asarray([[0, 3]]), inv4, scale=1.5)
    y = np.asarray(basic.rotate_leading(
        np.asarray([[[[1.0, 2.0, 3.0, 4.0, 5.0, 6.0]]] * 2], np.float32), cos, sin
    ))
    np.testing.assert_allclose(y[0, 0, 0], [1.5, 3.0, 4.5, 6.0, 5.0, 6.0])
    c, s = 1.5 * math.cos(3.0), 1.5 * math.sin(3.0)
    c2, s2 = 1.5 * math.cos(1.5), 1.5 * math.sin(1.5)
    np.testing.assert_allclose(y[0, 1, 0], [
        1 * c - 3 * s, 2 * c2 - 4 * s2, 3 * c + 1 * s, 4 * c2 + 2 * s2, 5, 6,
    ], rtol=1e-5)
    # and the reference's own tables are the program's
    cfg, keys = train_steps_swa.tiny_configs()
    for kind, rc in (("full+moe", cfg.full), ("swa+moe", cfg.swa)):
        want, scale = ref.rotation(keys, kind)
        got = basic.rope_inv_freq(
            rc.rotary_dim or cfg.head_dim, rc.rope_theta, rc.yarn_factor,
            rc.yarn_original_len, rc.yarn_beta_fast, rc.yarn_beta_slow,
        )
        np.testing.assert_array_equal(got, want)
        assert scale == rc.attention_factor


@pytest.mark.parametrize("kind", ["full+dense", "swa+moe", "full+moe"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    heads = getattr(tiny.cfg, kind.split("+")[0]).num_heads
    fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(tiny.keys, kind, heads, x, lp)[0]
    )


def test_logits_match_alone_and_packed_with_a_border_inside_a_window(tiny):
    _, stats, seqs = fb.check_logits(FAMILY, tiny, LENS)
    inside = sum(sum(min(t + 1, 8) for t in range(len(s))) for s in seqs)
    causal = sum(len(s) * (len(s) + 1) // 2 for s in seqs)
    assert [int(x) for x in stats.counts["swa_pairs"]] == [inside] * 3
    assert [int(x) for x in stats.counts["swa_causal_pairs"]] == [causal] * 3


def test_grpo_loss_and_every_gradient_match_the_references(tiny):
    seen = fb.check_gradients(FAMILY, tiny)
    assert {"w_head_gate", "wq", "wk", "wv", "wo", "w_router", "w_shared_up",
            "w_gate", "input_norm", "embedding", "lm_head"} <= set(seen)


def _edge_inputs(t, window, seed=0):
    q, k, v = (
        fb.normal(seed + i, 1, t, hh, 64) for i, hh in enumerate((4, 2, 2))
    )
    return q, k, v, np.ones((1, t), np.int32)


@pytest.mark.parametrize("form", ["xla", "splash"])
def test_the_windows_edge_is_exact(form):
    fb.check_window_edge(form, 256, 32, 100, ref.masked_softmax_attention)


def test_the_kernels_band_holds_at_the_rows_borders():
    """The layer's window as the kernel's mask: two sequences and padding
    in the row, forward and backward against the XLA form; a row that is no
    whole number of tiles takes the XLA form."""
    t, window = 256, 32
    q, k, v, _ = _edge_inputs(t, window, seed=1)
    seg = np.asarray([[1] * 120 + [2] * 100 + [0] * 36], np.int32)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2)
        ))(q, k, v)

    want = both(lambda *a: basic.segment_attention(*a, seg, window=window))
    got = both(lambda *a: flash_segment_attention(
        *a, seg, layer_window=window, interpret=True
    ))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    cut = (q[:, :200], k[:, :200], v[:, :200], seg[:, :200])
    odd = jax.jit(
        lambda *a: flash_segment_attention(*a, layer_window=window)
    )(*cut)
    np.testing.assert_allclose(
        odd, jax.jit(
            lambda *a: basic.segment_attention(*a, window=window)
        )(*cut), atol=1e-6,
    )


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model: the four holders of 4 of the 16
    experts route over all 16 with the same router; their parts, with the
    shared expert counted ONCE, are the uncut reference's layer."""
    fb.check_shares_add_up(
        FAMILY, tiny, "swa+moe", ref.experts, ref.shared_expert
    )


def test_the_refusals_by_name(tiny, tmp_path):
    fb.check_refusals(FAMILY, tiny, tmp_path, "B.3", "laguna", "B.3")


def test_the_trainer_trains_the_family_and_counts_its_pairs_exactly(trained):
    engine, lens = trained.engine, trained.lens
    assert (engine.attn_impl, engine.moe_impl) == ("xla", "ragged_dot")
    assert engine._span_attrs["layer_window"] == 8
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = trained.attrs(name)
        assert all(
            a["layer_window"] == 8 and "window" not in a for a in attrs
        ), (name, attrs)
    counters = engine.counters()
    assert counters["swa_pairs"] == 3 * sum(
        sum(min(t + 1, 8) for t in range(n)) for n in lens
    )
    assert counters["swa_causal_pairs"] == 3 * sum(
        n * (n + 1) // 2 for n in lens
    )
    for kind in trained.before:  # every group of every kind moved
        trained.moved(kind, "wq", "w_head_gate", "w_down")
