"""The joyai_llm_flash family (the DeepSeek-V3 key set: latent attention with
a compressed query and a ROTATED shared key part on every layer, a leading
dense FFN over sigmoid-routed experts and a shared one, and the model's own
draft module beside the stack) at test size against the plain reference
``benchmark/reference_joyai.py``, which turns the rotated channels as
published (interleaved) and reads the program's weights under the column
permutation ``published_layout``: both block kinds, the logits alone and
packed, the GRPO loss with the draft's beside it and every gradient, the
stop-gradient to the bit, the shift inside a packed row, the rotated mixer
EQUAL to Kimi's NoPE path at position zero, the slice rotation, the share of
the experts, the refusals, and one update by the shipped recipe."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import basic
from areal_tpu.ops import functional as F
from benchmark import reference_joyai as ref
from benchmark.kinds import train_steps_mla

CUT = "joyai-llm-flash.json"
TOTAL = 96


def _seeded(cfg, seed):
    """A correction bias and norm weights that matter (the module's three
    start at one, where a dropped norm weight would go unseen)."""
    params = fb.init(cfg, seed)
    rng = np.random.default_rng(2)

    def moved(w, by):
        return (w + by * rng.standard_normal(w.shape)).astype(np.float32)

    for group in list(params["layers"].values()) + [params["mtp"]["block"]]:
        if "router_bias" in group:
            group["router_bias"] = moved(group["router_bias"], 0.2)
        for name in ("q_a_norm", "kv_norm"):
            group[name] = moved(group[name], 0.3)
    for name in ("norm_e", "norm_h", "head_norm"):
        params["mtp"][name] = moved(params["mtp"][name], 0.3)
    return params


def _reference_logits(keys, params, tokens):
    """The reference's logits of traced tokens, the program's weights read
    in the published layout."""
    pub = ref.published_layout(keys, params)
    return ref.head(pub, ref.trunk(keys, pub, tokens))


FAMILY = fb.Family(
    "joyai_llm_flash", train_steps_mla.tiny_configs, _reference_logits,
    _seeded, total=TOTAL,
)
tiny = fb.tiny_fixture(FAMILY)


def _keep_the_module(engine):
    engine.mtp_before = fb.host(engine.params["mtp"])


trained = fb.trained_fixture(_keep_the_module)


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("joyai_llm_flash")
    assert t.layer_kinds == ("mla+dense", "mla+moe", "mla+moe")
    assert (t.num_held_experts, t.num_experts, t.num_experts_per_tok) == (
        4, 16, 2
    )
    assert t.router_act == "sigmoid" and not t.shared_expert_gate
    assert t.mla.rotated and t.mla.q_lora_rank == 12 and t.trains_draft
    assert tr.draft_kind(t) == "mla+moe"
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == ("mla+dense",) + ("mla+moe",) * 4
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        256, 8, 16160
    )
    assert cut.attn_head_dims == (192, 128) and cut.head_dim == 192
    assert (cut.mla.q_lora_rank, cut.mla.kv_lora_rank) == (1536, 512)
    assert cut.mla.rotated and cut.mla.rope_theta == 32_000_000.0
    assert (cut.mtp_layers, cut.mtp_loss_coef) == (1, 0.1)
    assert cut.routed_scaling_factor == 2.5 and cut.shared_expert_size == 768
    # the published keys, unchanged: no key for the coefficient, so the
    # reader's 0.1; without a module there is nothing to weigh
    full = from_hf_config(fb.published("JoyAI-LLM-Flash"))
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        40, 256, 0
    )
    assert full.mla == cut.mla and full.mtp_loss_coef == 0.1
    assert [n for _, n in tr._runs(full.layer_kinds)] == [1, 39]
    none = from_hf_config(fb.cut(CUT, num_nextn_predict_layers=0))
    assert (none.mtp_layers, none.mtp_loss_coef, none.trains_draft) == (
        0, 0.0, False
    )
    # an uncompressed query is the same reader's (DeepSeek-V2-Lite's null)
    assert from_hf_config(fb.cut(CUT, q_lora_rank=None)).mla.q_lora_rank == 0


@pytest.mark.parametrize("bad,word", [
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "yarn on a latent"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"num_nextn_predict_layers": 2}, "depth 1"),
    ({"num_key_value_heads": 4}, "one key a head"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


@pytest.mark.parametrize("kind", ["mla+dense", "mla+moe"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    stats = fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(
            tiny.keys, kind, x, ref._published_mixer(tiny.keys, lp)
        ),
    )
    assert stats[1] is None  # the mixer reports nothing
    assert (stats[0] is not None) == kind.endswith("moe")


def test_logits_match_alone_and_packed_with_padding(tiny):
    # positions restart at each border: the rotation is the sequence's own
    _, stats, _ = fb.check_logits(FAMILY, tiny, [50, 27, 11])
    assert "mtp_tokens" not in stats.counts  # ``apply`` runs no module
    assert int(stats.counts["moe_assignments"]) > 0


def _draft_of(cfg, p, tok, seg, pos):
    """The program's trunk and module on one row, as ``grad_step`` composes
    them: (logits, the module's logits, its counts)."""
    logits = tr.apply(p, cfg, tok, seg, pos, return_hidden=True)
    draft, counts = tr.draft_forward(p, cfg, logits.hidden, tok, seg, pos)
    return logits, draft, counts


def test_loss_draft_loss_and_every_gradient_match_the_references(tiny):
    """L = L_GRPO + 0.1 L_mtp of one sequence in the family's row, value and
    the gradient of EVERY leaf (the trunk's from the GRPO term alone, the
    module's from its own), program against reference, each under one
    ``jit``; the reference differentiates through the column permutation,
    so both gradients are in the program's layout."""
    cfg, keys = tiny.cfg, tiny.keys
    n = 45
    tok, seg, pos, (seq,) = fb.row([n], TOTAL, cfg.vocab_size, 3)
    grpo = fb.grpo_loss(seq)
    mask = np.zeros((1, TOTAL), np.int32)
    mask[0, 5:n] = 1  # target-aligned: tokens 5.. are trained
    arrays = {"tokens": tok, "segment_ids": seg, "t_loss_mask": mask}

    def program(p):
        logits, draft, _ = _draft_of(cfg, p, tok, seg, pos)
        mtp, count = F.draft_loss(draft, arrays)
        return grpo(logits.full()[0]) + cfg.mtp_loss_coef * mtp, (mtp, count)

    def reference(p):
        pub = ref.published_layout(keys, p)
        xn = ref.trunk(keys, pub, tok[0, :n])
        mtp = ref.draft_loss(
            keys, pub, tok[0, :n], xn, mask=mask[0, 2:n] > 0
        )
        return grpo(ref.head(pub, xn)) + cfg.mtp_loss_coef * mtp, mtp

    (got_v, (got_mtp, count)), got = jax.jit(
        jax.value_and_grad(program, has_aux=True)
    )(tiny.params)
    (want_v, want_mtp), want = jax.jit(
        jax.value_and_grad(reference, has_aux=True)
    )(tiny.params)
    assert int(count) == n - 5  # tokens 5 .. n-1, each a target once
    np.testing.assert_allclose(got_mtp, want_mtp, rtol=1e-5)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-4, atol=1e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(fb.host(got))
    flat_want = jax.tree_util.tree_leaves(fb.host(want))
    assert len(flat_got) == len(flat_want)
    seen = set()
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(w).max())
        if "router_bias" in name:  # it moves a choice, never a value
            assert not g.any() and not w.any(), name
            continue
        assert scale > 0, name
        assert float(np.abs(g - w).max()) <= 2e-3 * scale, name
        seen.add(name)
    assert {"['mtp']['w_eh']", "['mtp']['norm_e']", "['mtp']['norm_h']",
            "['mtp']['head_norm']", "['mtp']['block']['wq_b']",
            "['mtp']['block']['w_router']", "['embedding']", "['lm_head']",
            "['layers']['mla+moe']['wq_a']", "['layers']['mla+moe']['q_a_norm']",
            "['layers']['mla+dense']['w_kv_a']"} <= seen


def test_the_trunks_gradient_is_the_same_to_the_bit_with_and_without_the_draft(
    tiny
):
    """``grad_step`` itself at ``mtp_loss_coef`` 0.1 and 0: the module's
    loss hands the trunk, the embedding and the head NOTHING (their
    accumulated gradients are equal to the bit), the module's own leaves get
    a gradient where it is trained and none where it is not, and the
    reported loss differs by 0.1 L_mtp."""
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.engine import programs
    from areal_tpu.parallel import mesh as mesh_lib

    cfg = tiny.cfg
    tok, seg, pos, _ = fb.row([40, 23, 17], TOTAL, cfg.vocab_size, 6)
    mask = (seg > 0).astype(np.int32)
    arrays = {"tokens": tok, "segment_ids": seg, "positions": pos,
              "t_loss_mask": mask}
    mesh = mesh_lib.make_mesh(ParallelismConfig(), jax.devices()[:1])
    zeros = jax.tree_util.tree_map(np.zeros_like, tiny.params)

    def loss_fn(logits, arrays):
        logp = F.target_aligned_logprobs(logits, arrays)
        return -(logp * mask).sum() / mask.sum(), {}

    def grads(coef):
        step = programs.build_grad_step(
            dataclasses.replace(cfg, mtp_loss_coef=coef), mesh, jnp.float32,
            None, True, loss_fn, lambda a: jnp.float32(1.0),
        )
        accum, loss, stats, _ = step(tiny.params, zeros, arrays)
        return fb.host(accum), float(loss), stats

    with_draft, loss, stats = grads(0.1)
    without, plain, plain_stats = grads(0.0)
    assert "mtp_loss" in stats and "mtp_loss" not in plain_stats
    np.testing.assert_allclose(
        loss, plain + 0.1 * float(stats["mtp_loss"]), rtol=1e-6
    )
    # every sequence's tokens two or more in: 80 real tokens, three starts
    assert int(stats["mtp_tokens"]) == 80 - 2 * 3
    assert float(stats["moe_assignments"]) == float(
        plain_stats["moe_assignments"]
    ) + float(stats["mtp_assignments"])
    for key in ("embedding", "lm_head", "final_norm", "layers"):
        for a, b in zip(jax.tree_util.tree_leaves(with_draft[key]),
                        jax.tree_util.tree_leaves(without[key])):
            np.testing.assert_array_equal(a, b)
    trained_leaves = jax.tree_util.tree_leaves_with_path(with_draft["mtp"])
    for (path, a), b in zip(trained_leaves,
                            jax.tree_util.tree_leaves(without["mtp"])):
        assert not b.any()
        assert a.any() or "router_bias" in jax.tree_util.keystr(path)


def test_the_shift_stays_inside_a_sequence_of_a_packed_row(tiny):
    """A border mid-row and padding at the end: a sequence's last slot reads
    a ZERO embedding (the next sequence's first token changes nothing before
    the border, to the bit), its last two slots have no target, the row's
    ``mtp_tokens`` is what a hand counts, and the packed row's L_mtp is the
    sequences' alone, weighted by their targets."""
    cfg = tiny.cfg
    lens = [31, 18, 9]
    tok, seg, pos, seqs = fb.row(lens, TOTAL, cfg.vocab_size, 7)
    mask = np.zeros((1, TOTAL), np.int32)
    at = 0
    for n in lens:  # prompts of 4 tokens: the response is the rest
        mask[0, at + 4:at + n] = 1
        at += n

    @jax.jit
    def read(p, tok, seg, pos, mask):
        _, draft, _ = _draft_of(cfg, p, tok, seg, pos)
        arrays = {"tokens": tok, "segment_ids": seg, "t_loss_mask": mask}
        return F.draft_aligned_logprobs(draft, arrays), F.draft_loss(
            draft, arrays
        )

    (logp, has), (loss, count) = read(tiny.params, tok, seg, pos, mask)
    logp, has = np.asarray(logp), np.asarray(has)
    want_has = np.zeros(TOTAL, bool)
    at = 0
    for n in lens:
        want_has[at:at + n - 2] = True
        at += n
    np.testing.assert_array_equal(has[0], want_has)
    assert not logp[0][~want_has].any()
    assert int(count) == sum(n - 4 for n in lens)  # a response token once
    # the second sequence's first token replaced: nothing before the border
    # moves (its embedding would be the first sequence's last slot's input)
    other = tok.copy()
    other[0, lens[0]] = (tok[0, lens[0]] + 1) % cfg.vocab_size
    (moved, _), _ = read(tiny.params, other, seg, pos, mask)
    np.testing.assert_array_equal(np.asarray(moved)[0, :lens[0]],
                                  logp[0, :lens[0]])
    assert (np.asarray(moved)[0, lens[0]:sum(lens[:2])] != logp[
        0, lens[0]:sum(lens[:2])]).any()
    # each sequence alone in the same row shape: the same logprobs, and the
    # packed loss is their mean over all targets
    total, at = 0.0, 0
    for s in seqs:
        n = len(s)
        t1, s1, p1, _ = fb.row([n], TOTAL, cfg.vocab_size)
        t1[0, :n] = s
        m1 = np.zeros((1, TOTAL), np.int32)
        m1[0, 4:n] = 1
        (alone, _), (loss1, count1) = read(tiny.params, t1, s1, p1, m1)
        np.testing.assert_allclose(
            np.asarray(alone)[0, :n], logp[0, at:at + n], atol=2e-6
        )
        total += float(loss1) * int(count1)
        at += n
    np.testing.assert_allclose(float(loss), total / int(count), rtol=1e-5)


def test_the_rotated_mixer_at_position_zero_is_kimis_nope_path():
    """The rotation by angle zero is the identity, so the shared code is ONE
    code: Kimi's mixer (an uncompressed query, nothing rotated) and the
    rotated form of the same configuration at positions all zero give EQUAL
    outputs and EQUAL gradients on the same weights, to the bit; at the
    tokens' own positions the rotated one differs."""
    cfg = tiny_config("kimi_linear")
    rotated = dataclasses.replace(
        cfg, mla=dataclasses.replace(cfg.mla, rotated=True, rope_theta=1e4)
    )
    assert tr._kind_leaves(cfg, "mla+moe").keys() == tr._kind_leaves(
        rotated, "mla+moe"
    ).keys()
    lp = fb.layer_of(fb.init(cfg, 2), "mla+moe")
    t = 29
    h = fb.normal(3, 1, t, cfg.hidden_size)
    w = fb.normal(4, 1, t, cfg.hidden_size)
    seg = np.asarray([[1] * 17 + [2] * 12], np.int32)
    pos = np.concatenate([np.arange(17), np.arange(12)])[None].astype(np.int32)

    def both(cfg, positions):
        def f(h, lp):
            rotation = tr._rotations(cfg, positions).get("mla")
            return (tr._mla_mixer(cfg, h, lp, seg, None, rotation) * w).sum()

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(h, lp)

    assert tr._rotations(cfg, pos) == {}  # NoPE: no table is made
    want = both(cfg, pos)
    got = both(rotated, np.zeros_like(pos))
    for g, x in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, x)
    turned = both(rotated, pos)
    assert float(turned[0]) != float(want[0])


def test_a_slice_of_a_head_turns_as_basic_and_as_published(tiny):
    """The mixer rotates the LAST ``qk_rope_head_dim`` channels of a query
    head and the one shared key part: against ``ops/basic.py``'s ``rope_at``
    + ``rotate_leading`` on the slice (the program's rotate-half pairing),
    and against the reference's interleaved form under the column
    permutation; channels before the slice pass to the bit."""
    m = tiny.cfg.mla
    t, heads, rope = 23, 2, m.qk_rope_head_dim
    x = fb.normal(5, t, heads, m.qk_head_dim)
    pos = (np.arange(t) * 37 + 5).astype(np.int32)

    @jax.jit
    def turn(x):
        cos, sin = basic.rope_at(
            pos[None], basic.rope_inv_freq(rope, m.rope_theta)
        )
        nope, tail = jnp.split(x[None], [m.qk_nope_head_dim], axis=-1)
        return jnp.concatenate(
            [nope, tr.rotate_leading(tail, cos, sin)], axis=-1
        )[0]

    got = np.asarray(turn(x))
    np.testing.assert_array_equal(got[..., :-rope], x[..., :-rope])
    # by hand, rotate-half: channel j with j + rope / 2
    inv = 1.0 / m.rope_theta ** (np.arange(0, rope, 2) / rope)
    ang = pos[:, None, None] * inv
    a, b = x[..., -rope:-rope // 2], x[..., -rope // 2:]
    np.testing.assert_allclose(got[..., -rope:], np.concatenate(
        [a * np.cos(ang) - b * np.sin(ang), b * np.cos(ang) + a * np.sin(ang)],
        axis=-1,
    ), atol=1e-5)
    # as published: the slice's channels interleaved, turned 2j with 2j + 1
    at = ref._interleave(rope)
    plain = np.arange(t)  # the reference turns at positions 0 .. T-1
    got0 = np.asarray(jax.jit(lambda x: tr.rotate_leading(
        x[None, ..., -rope:], *basic.rope_at(
            plain[None], basic.rope_inv_freq(rope, m.rope_theta)
        )
    ))(x))[0]
    want = np.asarray(ref.rotate_interleaved(
        jnp.asarray(x[..., -rope:][..., at]), m.rope_theta
    ))
    np.testing.assert_allclose(got0[..., at], want, atol=1e-5)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the four holders of 4 of the 16 experts route
    over all 16 with the same sigmoid router and bias; their routed parts,
    with the ungated shared expert counted ONCE, are the uncut reference's
    layer."""
    fb.check_shares_add_up(
        FAMILY, tiny, "mla+moe", ref.experts, ref.shared_expert,
        router_bias=np.linspace(-0.3, 0.3, 16).astype(np.float32),
    )


def test_the_refusals_by_name(tiny, tmp_path):
    """The generation engine (no latent paged cache), a checkpoint (the
    published rope columns are interleaved: a loader's permutation) and a
    sequence-parallel mesh (the module's shift across a ring's borders)
    refuse the family, each by what it lacks."""
    fb.check_refusals(
        FAMILY, tiny, tmp_path, "latent paged cache", "joyai_llm_flash",
        "draft module",
    )
    assert "mla+dense, mla+moe" in tr.generation_refusal(tiny.cfg)
    assert tr.seq_parallel_refusal(tiny.cfg)[0] == ""  # no forced attention
    # without its module the family splits a sequence as Kimi's mixer does
    bare = dataclasses.replace(tiny.cfg, mtp_layers=0, mtp_loss_coef=0.0)
    assert tr.seq_parallel_refusal(bare) == ("", "")


def test_the_trainer_trains_the_family_and_its_draft_module(tiny, trained):
    engine, lens, stats = trained.engine, trained.lens, trained.stats
    assert (engine.attn_impl, engine.moe_impl) == ("xla", "ragged_dot")
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        assert all(
            a["mla_query"] == "compressed" and a["mla_rope"] == 4
            and a["mtp_layers"] == 1 and a["mtp_loss_coef"] == 0.1
            and a["rope_impl"] == "xla"
            for a in trained.attrs(name)
        ), name
    counters = engine.counters()
    # the recipe's loss mask leaves a sequence's first three tokens out:
    # every trained token is two or more into its sequence, a target once
    assert counters["mtp_tokens"] == sum(lens) - 3 * len(lens)
    assert sum(s["mtp_tokens"] for s in stats) == counters["mtp_tokens"]
    assert all(np.isfinite(s["mtp_loss"]) and s["mtp_loss"] > 3 for s in stats)
    # three sparse layers a gradient pass (two of the trunk, the module's),
    # two pairs a token, a quarter of the experts held
    assert 0 < counters["mtp_assignments"] < counters["moe_assignments"]
    assert counters["moe_assignments"] < sum(lens) * 2 * 3
    assert counters["moe_stream_rows"] >= counters["moe_assignments"]
    trained.moved("mla+moe", "wq_a", "q_a_norm", "wq_b", "w_kv_a", "kv_norm",
                  "w_router", "input_norm")
    trained.moved("mla+dense", "w_kv_b", "wo", "w_gate")
    after, was = fb.host(engine.params["mtp"]), engine.mtp_before
    for name in ("norm_e", "norm_h", "w_eh", "head_norm"):
        assert np.abs(after[name] - was[name]).max() > 0, name
    assert np.abs(after["block"]["wq_b"] - was["block"]["wq_b"]).max() > 0
    assert (after["block"]["router_bias"] == was["block"]["router_bias"]).all()
