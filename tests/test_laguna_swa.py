"""The laguna family (a stack of block kinds: rotary GQA layers that attend to
the whole sequence or to a window of it, each kind with its own heads and
rotation and a gate a head, over a dense or a softmax-routed sparse FFN) at
test size against the plain reference ``benchmark/reference_laguna.py``:
every block kind, the logits alone and packed, the GRPO loss's gradient of
every parameter group, the window's edge in both attention forms, the
rotations against hand-worked values, the share of the experts, and the
refusals by name."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import basic, moe as moe_ops
from areal_tpu.ops.flash import flash_segment_attention, resolve_attn_impl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_laguna as ref  # noqa: E402
from benchmark.kinds import train_steps_swa  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(ROOT, "benchmark", "configs", "laguna-xs.2.json")


@pytest.fixture(scope="module")
def tiny():
    cfg, keys = train_steps_swa.tiny_configs()
    params = tr.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    return cfg, keys, params


def _row(lens, total, vocab, seed=0):
    rng = np.random.default_rng(seed)
    tok = np.zeros((1, total), np.int32)
    seg = np.zeros((1, total), np.int32)
    pos = np.zeros((1, total), np.int32)
    seqs, at = [], 0
    for i, n in enumerate(lens):
        s = rng.integers(0, vocab, n).astype(np.int32)
        tok[0, at:at + n], seg[0, at:at + n] = s, i + 1
        pos[0, at:at + n] = np.arange(n)
        seqs.append(s)
        at += n
    return jnp.asarray(tok), jnp.asarray(seg), jnp.asarray(pos), seqs


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("laguna")
    assert set(t.layer_kinds) == {"full+dense", "swa+moe", "full+moe"}
    assert (t.full.num_heads, t.swa.num_heads, t.num_kv_heads) == (6, 8, 2)
    assert (t.layer_window, t.num_held_experts, t.num_experts) == (8, 4, 16)
    assert t.router_act == "softmax" and not t.shared_expert_gate
    with open(CUT) as f:
        cut = from_hf_config(json.load(f))
    assert cut.layer_kinds == (
        "full+dense", "swa+moe", "swa+moe", "swa+moe", "full+moe"
    )
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        256, 16, 12544
    )
    assert (cut.full.num_heads, cut.swa.num_heads) == (48, 64)
    assert (cut.full.rotary_dim, cut.swa.rotary_dim) == (64, 0)
    assert cut.layer_window == 512 and cut.routed_scaling_factor == 2.5
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "aguna" in line]
    full = from_hf_config(
        next(r for r in rows if r["name"] == "Laguna-XS.2")["config"]
    )  # the published keys, unchanged
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        40, 256, 0
    )
    assert full.full == cut.full and full.swa == cut.swa
    assert [n for _, n in tr._runs(full.layer_kinds)][:4] == [1, 3, 1, 3]
    sibling = from_hf_config(
        next(r for r in rows if r["name"] == "Laguna-S-2.1")["config"]
    )  # gating "per-head", a gating_types list, a soft cap of 0
    assert (sibling.swa.num_heads, sibling.full.yarn_factor) == (72, 128.0)


@pytest.mark.parametrize("bad,word", [
    ({"gating": "element-wise"}, "gating"),
    ({"moe_apply_router_weight_on_input": True}, "router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "soft-capped"),
    ({"num_attention_heads_per_layer": [48, 64, 64, 32, 48]}, "one stack"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    with open(CUT) as f:
        d = dict(json.load(f), **bad)
    with pytest.raises(ValueError, match=word):
        from_hf_config(d)


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
    pos = jnp.arange(40)[None]
    cos, sin = basic.rope_at(pos, plain)
    np.testing.assert_allclose(cos[0], cos_t, atol=2e-6)
    np.testing.assert_allclose(sin[0], sin_t, atol=2e-6)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 40, 2, 128)), jnp.float32
    )
    np.testing.assert_allclose(
        basic.rotate_leading(x, cos, sin),
        basic.apply_rope(x, pos, cos_t, sin_t), atol=1e-5,
    )
    # a partly rotated head: channel pairs (j, j + 2) of the first 4, the
    # attention factor on cos and sin, channels 4.. untouched
    inv4 = np.asarray([1.0, 0.5], np.float32)
    cos, sin = basic.rope_at(jnp.asarray([[0, 3]]), inv4, scale=1.5)
    y = np.asarray(basic.rotate_leading(
        jnp.asarray([[[[1.0, 2.0, 3.0, 4.0, 5.0, 6.0]]] * 2]), cos, sin
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
    cfg, keys, params = tiny
    lp = {k: v[0] for k, v in params["layers"][kind].items()}
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 37, cfg.hidden_size)), jnp.float32)
    seg = jnp.ones((1, 37), jnp.int32)
    pos = jnp.arange(37)[None]
    mixer = kind.split("+")[0]
    got, _ = jax.jit(lambda x, lp: tr._block_body(
        cfg, kind, x, lp, seg, rotation=tr._rotations(cfg, pos)[mixer]
    ))(x, lp)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(
            keys, kind, getattr(cfg, mixer).num_heads, x[0], lp
        )
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_logits_match_alone_and_packed_with_a_border_inside_a_window(tiny):
    cfg, keys, params = tiny
    # windows of 8 keys: the second sequence starts 3 tokens after a query
    # of the first could still see back, the third is shorter than a window
    tok, seg, pos, seqs = _row([41, 23, 5], 80, cfg.vocab_size)
    got, stats = jax.jit(
        lambda p: tr.apply(p, cfg, tok, seg, pos, return_stats=True)
    )(params)
    at = 0
    for s in seqs:
        np.testing.assert_allclose(
            got[0, at:at + len(s)], ref.logits(keys, params, s), atol=5e-5
        )
        at += len(s)
    inside = sum(sum(min(t + 1, 8) for t in range(len(s))) for s in seqs)
    causal = sum(len(s) * (len(s) + 1) // 2 for s in seqs)
    assert [int(x) for x in stats.counts["swa_pairs"]] == [inside] * 3
    assert [int(x) for x in stats.counts["swa_causal_pairs"]] == [causal] * 3


def test_grpo_loss_and_every_gradient_match_the_references(tiny):
    from areal_tpu.ops.functional import ppo_actor_loss_fn

    cfg, keys, params = tiny
    n = 45
    tok, seg, pos, seqs = _row([n], n, cfg.vocab_size, seed=3)
    rng = np.random.default_rng(8)
    old = jnp.asarray(rng.standard_normal(n - 1) * 0.1 - 4.8, jnp.float32)
    adv = jnp.asarray(rng.standard_normal(n - 1), jnp.float32)
    mask = jnp.asarray(np.arange(n - 1) >= 4)
    tgt = jnp.asarray(seqs[0][1:])

    def grpo(logits):  # [T, V] of one sequence
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits[:-1], axis=-1), tgt[:, None], axis=-1
        )[:, 0]
        return ppo_actor_loss_fn(
            logprobs=logp, old_logprobs=old, advantages=adv, eps_clip=0.2,
            loss_mask=mask, proximal_logprobs=jax.lax.stop_gradient(logp),
            behav_imp_weight_cap=5.0,
        )[0]

    def program(p):
        return grpo(tr.apply(p, cfg, tok, seg, pos, remat=True)[0])

    def reference(p):
        return grpo(ref.logits(keys, p, seqs[0]))

    got_v, got = jax.jit(jax.value_and_grad(program))(params)
    want_v, want = jax.value_and_grad(reference)(params)
    assert abs(float(got_v - want_v)) < 1e-4 * abs(float(want_v)) + 1e-6
    seen = set()
    for (path, g), w_ in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves(want),
    ):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w_).max())
        assert scale > 0, name
        assert float(jnp.abs(g - w_).max()) <= 2e-3 * scale, name
        seen.add(name.split("'")[-2])
    assert {"w_head_gate", "wq", "wk", "wv", "wo", "w_router", "w_shared_up",
            "w_gate", "input_norm", "embedding", "lm_head"} <= seen


def _edge_inputs(t, window, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, hh, 64)), jnp.float32)
               for hh in (4, 2, 2))
    return q, k, v, jnp.ones((1, t), jnp.int32)


@pytest.mark.parametrize("form", ["xla", "splash"])
def test_the_windows_edge_is_exact(form):
    """The key at distance window - 1 is seen, the one at distance window is
    not: with v replaced at one key s, the outputs of queries s .. s +
    window - 1 move and every other row is bit-for-bit what it was."""
    t, window, s = 256, 32, 100
    q, k, v, seg = _edge_inputs(t, window)
    if form == "xla":
        attend = jax.jit(lambda v: basic.segment_attention(
            q, k, v, seg, window=window
        ))
    else:
        attend = jax.jit(lambda v: flash_segment_attention(
            q, k, v, seg, layer_window=window, interpret=True
        ))
    base = np.asarray(attend(v))
    moved = np.asarray(attend(v.at[0, s].add(3.0)))
    changed = np.abs(moved - base).max(axis=(2, 3))[0] > 0
    assert changed[s:s + window].all()
    assert not changed[:s].any() and not changed[s + window:].any()
    # and the form agrees with the reference's masked softmax
    with jax.default_matmul_precision("highest"):
        want = ref.masked_softmax_attention(q[0], k[0], v[0], window)
    np.testing.assert_allclose(base[0], want, atol=2e-5)


def test_the_kernels_two_windows_take_the_shorter_and_borders_hold():
    """A packing window beside the layer's: the mask is the layer's; two
    sequences and padding in the row, forward and backward against the XLA
    form; a row that is no whole number of tiles takes the XLA form."""
    t, window = 256, 32
    q, k, v, _ = _edge_inputs(t, window, seed=1)
    seg = jnp.asarray([[1] * 120 + [2] * 100 + [0] * 36])

    def both(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    want = both(lambda *a: basic.segment_attention(*a, seg, window=window))
    got = both(lambda *a: flash_segment_attention(
        *a, seg, window=128, layer_window=window, interpret=True
    ))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    odd = flash_segment_attention(
        q[:, :200], k[:, :200], v[:, :200], seg[:, :200], layer_window=window
    )
    np.testing.assert_allclose(
        odd, basic.segment_attention(
            q[:, :200], k[:, :200], v[:, :200], seg[:, :200], window=window
        ), atol=1e-6,
    )


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model: the four holders of 4 of the 16
    experts route over all 16 with the same router; their parts, with the
    shared expert counted ONCE, are the uncut reference's layer."""
    cfg, keys, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=0)
    params = tr.init_params(whole_cfg, jax.random.PRNGKey(9), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"]["swa+moe"].items()}
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, 29, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(keys, h[0], lp) + ref.shared_expert(h[0], lp)
    total = np.asarray(moe_ops.shared_expert_from_params(cfg, lp, h))[0]
    pairs = 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        part, stats = moe_ops.moe_ffn(
            h, lp["w_router"], lp["w_gate"][held], lp["w_up"][held],
            lp["w_down"][held], cfg.num_experts_per_tok, cfg.norm_topk_prob,
            first_expert=first, router_act=cfg.router_act,
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
        total = total + np.asarray(part)[0]
        pairs += int(stats.assignments)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert pairs == 29 * cfg.num_experts_per_tok
    # and the layer's own call is the first holder's part plus the shared
    mine = dict(lp, **{k: lp[k][:4] for k in ("w_gate", "w_up", "w_down")})
    own, _ = moe_ops.moe_ffn_from_params(cfg, mine, h)
    with jax.default_matmul_precision("highest"):
        first_part = ref.experts(keys, h[0], mine) + ref.shared_expert(
            h[0], lp
        )
    np.testing.assert_allclose(own[0], first_part, atol=1e-5)


def test_the_refusals_by_name(tiny, tmp_path):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models import hf_io

    cfg, _, params = tiny
    with pytest.raises(NotImplementedError, match="B.3"):
        GenerationEngine(JaxGenConfig(), model_config=cfg, params=params)
    whole = dataclasses.replace(cfg, experts_held=0)
    with pytest.raises(NotImplementedError, match="laguna"):
        hf_io.save_params(
            tr.init_params(whole, jax.random.PRNGKey(1), jnp.float32), whole,
            str(tmp_path),
        )
    for impl, sp in (("ring", 1), ("ulysses", 2), ("auto", 2)):
        with pytest.raises(ValueError, match="B.3"):
            resolve_attn_impl(
                impl, sp, (16, 16), *tr.seq_parallel_refusal(cfg)
            )
    assert resolve_attn_impl(
        "auto", 1, (16, 16), *tr.seq_parallel_refusal(cfg)
    ) == "xla"


def test_the_trainer_trains_the_family_and_counts_its_pairs_exactly(
    tiny, monkeypatch
):
    from areal_tpu.api import cli_args
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.utils import data as data_utils, tracing

    # rows of 256 tokens for the batch's 185 (the ladder's own step is 2048)
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 256)
    full, _ = cli_args.load_expr_config(
        ["--config", os.path.join(ROOT, "examples", "gsm8k_grpo.yaml")],
        cli_args.GRPOConfig,
    )
    acfg = full.actor
    acfg.path, acfg.dtype = "", "float32"
    engine = SPMDTrainEngine(acfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, 1000, 8), model_config=tiny[0], seed=3
    )
    assert (engine.attn_impl, engine.moe_impl) == ("xla", "ragged_dot")
    assert engine._span_attrs["layer_window"] == 8
    actor = PPOActor(acfg, engine)
    rng = np.random.default_rng(12)
    lens = [30, 12, 44, 9, 25, 18, 40, 7]
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in lens]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm = batch["attention_mask"].astype(np.int32)
    lm[:, :3] = 0
    batch["loss_mask"] = lm
    batch["logprobs"] = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1 - 4.8
    ) * lm
    batch["versions"] = np.where(lm > 0, 0, -1).astype(np.int32)
    batch["rewards"] = np.tile(np.array([0, 1], np.float32), 4)
    before = jax.tree_util.tree_map(np.asarray, engine.params["layers"])
    seen = []
    span = tracing.span

    def recording(name, *a, **attrs):
        seen.append((name, attrs))
        return span(name, *a, **attrs)

    monkeypatch.setattr(tracing, "span", recording)
    stats = actor.ppo_update(actor.compute_advantages(batch))
    assert all(s["update_successful"] == 1.0 for s in stats)
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = [a for n, a in seen if n == name]
        assert attrs and all(
            a["layer_window"] == 8 and a["window"] == 0 for a in attrs
        ), (name, attrs)
    counters = engine.counters()
    assert counters["swa_pairs"] == 3 * sum(
        sum(min(t + 1, 8) for t in range(n)) for n in lens
    )
    assert counters["swa_causal_pairs"] == 3 * sum(
        n * (n + 1) // 2 for n in lens
    )
    after = engine.params["layers"]
    for kind, group in before.items():  # every group of every kind moved
        for name in ("wq", "w_head_gate", "w_down"):
            assert np.abs(np.asarray(after[kind][name]) - group[name]).max() > 0
