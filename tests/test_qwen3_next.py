"""The qwen3_next family (a stack of two block kinds: the gated delta rule with
ONE decay a head and token, 4 value heads over 2 key heads at test size, and
rotary attention whose output the query's own projection gates element by
element, over softmax-routed experts and a gated shared one; every norm but
the rule's output norm scaled by 1 + w) at test size against the plain
reference ``benchmark/reference_qwen3next.py``: both block kinds, the logits
alone and packed, the loss's gradient of every parameter group, the
scalar-decay rule EQUAL to the per-channel one, the share of the experts, the
rotation of a quarter of a 256-wide head with its (1 + w) norm in the
kernel's pass, the refusals, and one update by the shipped recipe."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import RotaryConfig, from_hf_config, tiny_config
from areal_tpu.ops import basic, kda
from areal_tpu.ops import rotary as rotary_ops
from benchmark import reference_qwen3next as ref
from benchmark.kinds import train_steps_gdn

CUT = "qwen3-next-80b-a3b.json"
TOTAL = 130


def _seeded(cfg, seed):
    """Norm weights that matter: a (1 + w) norm starts at zero, where a
    plain weight in its place would go unseen."""
    params = fb.init(cfg, seed)
    rng = np.random.default_rng(2)

    def moved(w):
        return (w + 0.3 * rng.standard_normal(w.shape)).astype(np.float32)

    for group in params["layers"].values():
        for name in group:
            if name.endswith("norm"):
                group[name] = moved(group[name])
    params["final_norm"] = moved(params["final_norm"])
    return params


def _reference_logits(keys, params, tokens):
    """``ref.logits`` of traced tokens (``ref._run`` looks its embedding's
    rows up with numpy and jits a lambda a layer a call): its lines around
    ``ref.layer`` and ``ref.head``, the largest functions of it that trace."""
    x = ref._f32(params["embedding"])[tokens]
    for kind, i in ref.layer_kinds(keys):
        x, _ = ref.layer(keys, kind, x, fb.layer_of(params, kind, i))
    return ref.head(keys, params, x)


FAMILY = fb.Family(
    "qwen3_next", train_steps_gdn.tiny_configs, _reference_logits, _seeded,
    total=TOTAL,
)
tiny = fb.tiny_fixture(FAMILY)
trained = fb.trained_fixture()


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("qwen3_next")
    assert t.layer_kinds == ("gdn+moe",) * 3 + ("full+moe",)
    assert (t.num_held_experts, t.num_experts, t.num_experts_per_tok) == (
        4, 16, 4
    )
    assert t.shared_expert_gate and t.norm_add_unit_offset
    assert t.qk_norm == "head" and t.full.output_gate
    assert (t.gdn.num_k_heads, t.gdn.num_v_heads) == (2, 4)
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == t.layer_kinds and cut.num_kda_layers == 3
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        512, 16, 18992
    )
    assert cut.attn_head_dims == (256, 256) and cut.full.rotary_dim == 64
    assert (cut.gdn.key_dim, cut.gdn.value_dim) == (2048, 4096)
    # the reader sets the shared expert's gate from the key that gives one
    assert cut.shared_expert_gate and cut.shared_expert_size == 512
    assert not from_hf_config(
        fb.cut(CUT, shared_expert_intermediate_size=0)
    ).shared_expert_gate
    # the published keys, unchanged
    full = from_hf_config(fb.published("Qwen3-Next-80B-A3B-Instruct"))
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        48, 512, 0
    )
    assert full.num_kda_layers == 36 and full.layer_kinds[47] == "full+moe"
    assert full.gdn == cut.gdn and full.full == cut.full
    assert [n for _, n in tr._runs(full.layer_kinds)][:4] == [3, 1, 3, 1]


@pytest.mark.parametrize("bad,word", [
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"linear_num_value_heads": 24}, "multiple of the key heads"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


@pytest.mark.parametrize("kind", ["gdn+moe", "full+moe"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    stats = fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(tiny.keys, kind, x, lp)[0]
    )
    # a layer reports (its FFN's, its mixer's)
    assert (stats[1] is not None) == kind.startswith("gdn")
    assert stats[0] is not None


def test_logits_match_alone_and_packed_with_padding(tiny):
    # borders fall inside a chunk and inside the conv's reach: the packing
    # invariant, against the reference's sequences alone
    _, stats, _ = fb.check_logits(FAMILY, tiny, [70, 33, 21])
    assert [int(stats.counts[k]) for k in (
        "kda_tokens", "kda_chunk_slots", "kda_segments"
    )] == [124 * 3, 192 * 3, 3 * 3]


def test_loss_and_every_gradient_match_the_references(tiny):
    def weighted(seq):
        n = len(seq)
        w = np.cos(
            np.arange(n * tiny.cfg.vocab_size).reshape(n, -1) * 0.01
        ).astype(np.float32)
        return lambda logits: (logits[:n] * w).sum()

    seen = fb.check_gradients(
        FAMILY, tiny, weighted, n=45, value_tol=(1e-3, 1e-4)
    )
    assert {"w_qkvz", "w_ba", "conv_qkv", "a_log", "dt_bias", "o_norm", "wq",
            "q_norm", "k_norm", "w_router", "w_shared_router",
            "w_shared_gate", "input_norm", "post_attn_norm"} <= set(seen)


def test_the_scalar_decay_rule_is_the_per_channel_rule_with_g_repeated():
    """Forward and every gradient, on a packed row with borders inside a
    chunk and padding: ``kda_scan`` with ``g`` [B, T, H] and two key heads
    under four value heads, against ``kda_scan`` with ``g`` repeated over the
    channels and ``q, k`` over the value heads (the same mathematics at the
    per-channel operands' cost)."""
    b, t, hk, h, d = 1, 150, 2, 4, 16

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q, k = unit(fb.normal(0, b, t, hk, d)), unit(fb.normal(1, b, t, hk, d))
    v, w = fb.normal(2, b, t, h, d), fb.normal(3, b, t, h, d)
    g = -0.3 * np.abs(fb.normal(4, b, t, h))
    beta = 1.0 / (1.0 + np.exp(-fb.normal(5, b, t, h)))
    seg = np.asarray([[1] * 70 + [2] * 33 + [3] * 30 + [0] * 17], np.int32)

    def scalar(q, k, v, g, beta):
        o, stats = kda.kda_scan(q, k, v, g, beta, seg)
        return (o * w).sum(), (o, stats)

    def channel(q, k, v, g, beta):
        qq, kk = (jnp.repeat(x, h // hk, axis=2) for x in (q, k))
        gg = jnp.broadcast_to(g[..., None], g.shape + (d,))
        o, stats = kda.kda_scan(qq, kk, v, gg, beta, seg)
        return (o * w).sum(), (o, stats)

    args = (q, k, v, g, beta)
    (_, (o, stats)), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=range(5), has_aux=True
    ))(*args)
    (_, (o_want, stats_want)), want = jax.jit(jax.value_and_grad(
        channel, argnums=range(5), has_aux=True
    ))(*args)
    assert o.shape == (b, t, h, d) and not np.asarray(o[0, 133:]).any()
    np.testing.assert_allclose(o, o_want, atol=2e-6, rtol=0)
    assert [int(x) for x in stats] == [int(x) for x in stats_want] == [
        133, 192, 3
    ]
    for got, ref_g in zip(grads, want):
        assert got.shape == ref_g.shape
        np.testing.assert_allclose(got, ref_g, atol=5e-6, rtol=0)
    # and XLA's own differentiation of the same chunks agrees with the
    # hand-written backward (the inverse's cotangent among it)
    plain = jax.jit(jax.grad(lambda *a: (kda.kda_scan(
        *a, seg, custom_backward=False
    )[0] * w).sum(), argnums=range(5)))(*args)
    for got, ref_g in zip(grads, plain):
        np.testing.assert_allclose(got, ref_g, atol=5e-6, rtol=0)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the four holders of 4 of the 16 experts route
    over all 16 with the same softmax router; their routed parts, with the
    GATED shared expert counted ONCE, are the uncut reference's layer."""
    assert "w_shared_router" in fb.layer_of(tiny.params, "gdn+moe")
    fb.check_shares_add_up(
        FAMILY, tiny, "gdn+moe", ref.experts, ref.shared_expert
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_quarter_of_a_256_wide_head_turns_under_its_unit_offset_norm(
    monkeypatch, dtype
):
    """The rotation's kernel (interpret mode) at this model's head: 256
    wide, the first 64 channels rotated, normed a head by (1 + w) in the same
    pass, as ``_rotary_mixer`` hands it over (``_head_norm_in_rotation``),
    against ``ops/basic.py``'s ``jnp`` form, forward and backward."""
    rule = rotary_ops.rotate_impl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(
        rotary_ops, "rotate_impl",
        lambda d, t=None: rule(d, t).replace("kernel", "interpret"),
    )
    t, heads, d, rot = rotary_ops.BLOCK_TOKENS, 2, 256, 64
    cfg = dataclasses.replace(
        tiny_config("qwen3_next"), head_dim=d,
        full=RotaryConfig(num_heads=heads, rotary_dim=rot, output_gate=True),
    )
    w = (0.2 * fb.normal(7, d)).astype(dtype)
    weight, eps = tr._head_norm_in_rotation(cfg, {"q_norm": w}, t)("q_norm")
    np.testing.assert_allclose(weight, 1.0 + np.asarray(w, np.float32))
    assert tr._head_norm_in_rotation(cfg, {"q_norm": w}, 40)("q_norm") is None
    x = jnp.asarray(fb.normal(8, 1, t, heads, d)).astype(dtype)
    cot = fb.normal(9, 1, t, heads, d)
    cos, sin = basic.rope_at(
        jnp.arange(t)[None] + 4099, basic.rope_inv_freq(rot, 10000000.0)
    )
    assert cos.shape == (1, t, rot // 2)

    def fused(x, w):
        norm = tr._head_norm_in_rotation(cfg, {"q_norm": w}, t)("q_norm")
        return rotary_ops.rotate(x, cos, sin, norm)

    def plain(x, w):
        return basic.rotate_leading(
            basic.rms_norm(x, w, eps, add_unit_offset=True), cos, sin
        )

    def both(f):
        return jax.value_and_grad(
            lambda x, w: (f(x, w).astype(jnp.float32) * cot).sum(),
            argnums=(0, 1),
        )(x, w)

    # channels 64.. pass: the normed head as it is
    got, want = fused(x, w), plain(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )
    np.testing.assert_array_equal(
        np.asarray(want[..., rot:], np.float32),
        np.asarray(basic.rms_norm(x, w, eps, True)[..., rot:], np.float32),
    )
    (val, (dx, dw)), (val_w, (dx_w, dw_w)) = both(fused), both(plain)
    assert dw.dtype == w.dtype and dw.shape == (d,)
    np.testing.assert_allclose(val, val_w, rtol=1e-5 if tol < 1e-3 else 2e-2)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(dx_w, np.float32),
        atol=tol * float(np.abs(np.asarray(dx_w, np.float32)).max()),
    )
    np.testing.assert_allclose(
        np.asarray(dw, np.float32), np.asarray(dw_w, np.float32),
        atol=(1e-4 if tol < 1e-3 else 3e-2)
        * float(np.abs(np.asarray(dw_w, np.float32)).max()),
    )


def test_the_refusals_by_name(tiny, tmp_path):
    """The generation engine (a recurrent state beside KV pages), a
    checkpoint (the published tensors' interleaving has no mapping here) and
    a sequence-parallel mesh (the state would pass from device to device)
    refuse the family, each by what it lacks."""
    fb.check_refusals(
        FAMILY, tiny, tmp_path, "recurrent state", "qwen3_next",
        "delta rule",
    )
    assert "gdn+moe, full+moe" in tr.generation_refusal(tiny.cfg)
    assert tr.seq_parallel_refusal(tiny.cfg)[0] == ""  # no forced attention


def test_the_trainer_trains_the_family_and_counts_its_scans_exactly(
    tiny, trained
):
    engine, lens, stats = trained.engine, trained.lens, trained.stats
    assert (engine.attn_impl, engine.moe_impl, engine.kda_impl) == (
        "xla", "ragged_dot", "xla"
    )
    assert engine._span_attrs["gdn_decay"] == "scalar"
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        assert all(
            a["gdn_decay"] == "scalar" and a["kda_impl"] == "xla"
            for a in trained.attrs(name)
        ), name
    counters = engine.counters()
    n_gdn = tiny.cfg.num_kda_layers
    assert n_gdn == 3
    assert counters["kda_tokens"] == sum(lens) * n_gdn
    assert counters["kda_segments"] == len(lens) * n_gdn
    assert counters["kda_chunk_slots"] % (64 * n_gdn) == 0
    assert counters["kda_chunk_slots"] >= counters["kda_tokens"]
    assert sum(s["kda_tokens"] for s in stats) == counters["kda_tokens"]
    # four sparse layers, four pairs a token, a quarter of the experts held
    assert 0 < counters["moe_assignments"] < sum(lens) * 4 * 4
    assert counters["moe_stream_rows"] >= counters["moe_assignments"]
    trained.moved(
        "gdn+moe", "a_log", "dt_bias", "conv_qkv", "w_qkvz", "w_ba", "o_norm",
        "input_norm", "w_shared_router",
    )
    trained.moved("full+moe", "wq", "q_norm", "k_norm", "post_attn_norm")
