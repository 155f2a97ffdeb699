"""The mellum family (window and full rotary GQA layers three to one, every
layer the same heads, q and k normed a head, the whole head rotated, over
softmax-routed experts with no shared one) at test size against the plain
reference ``benchmark/reference_mellum.py``: each block kind, the logits
alone and packed with a border inside a window, the GRPO loss's gradient of
every parameter group, the window's edge, yarn's tables against a hand-written
``_compute_yarn_parameters``, the share of the experts, the published keys'
parameter count, the refusals by name and the trainer's spans."""

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
from benchmark import arith_mellum, reference_mellum as ref  # noqa: E402
from benchmark.kinds import train_steps_swa_long  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(ROOT, "benchmark", "configs", "mellum2-12b-a2.5b.json")
KINDS = ("swa+moe", "full+moe")


def _seeded(cfg, seed):
    """Seeded weights whose norms are not the 1 a fresh model stores: a
    dropped q or k norm would pass every comparison at 1."""
    params = tr.init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    for group in params["layers"].values():
        for name in ("q_norm", "k_norm", "input_norm", "post_attn_norm"):
            group[name] = group[name] * jnp.asarray(
                rng.uniform(0.5, 1.5, group[name].shape), jnp.float32
            )
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg, keys = train_steps_swa_long.tiny_configs()
    return cfg, keys, _seeded(cfg, 4)


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


def _published():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Mellum2" in line]
    return next(
        r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"
    )["config"]


def test_tiny_config_is_the_family_and_the_cut_builds():
    t = tiny_config("mellum")
    assert t.layer_kinds == ("swa+moe",) * 3 + ("full+moe",)
    assert (t.full.num_heads, t.swa.num_heads, t.num_kv_heads) == (4, 4, 2)
    assert (t.layer_window, t.num_held_experts, t.num_experts) == (8, 4, 16)
    assert t.qk_norm == "head" and t.shared_expert_size == 0
    assert not t.full.head_gate and not t.swa.head_gate
    assert (t.full.rotary_dim, t.swa.rotary_dim) == (0, 0)  # the whole head
    with open(CUT) as f:
        cut = from_hf_config(json.load(f))
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
    d = _published()
    full = from_hf_config(d)
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        28, 64, 0
    )
    assert [n for _, n in tr._runs(full.layer_kinds)][:4] == [3, 1, 3, 1]
    assert full.kind_layers("swa+moe") == 21 and full.vocab_size == 98304
    with open(CUT) as f:
        cut = from_hf_config(json.load(f))
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
    with open(CUT) as f:
        c = json.load(f)
    shapes = jax.eval_shape(
        lambda: tr.init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    n = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
    assert n == arith_mellum.param_count(c) == 340350208
    # a dense entry takes ``intermediate_size``
    dense = dict(keys, mlp_layer_types=["dense"] + keys["mlp_layer_types"][1:])
    dcfg = from_hf_config(dense)
    assert dcfg.layer_kinds[0] == "swa+dense"
    assert arith_mellum.param_count(dense) == tr.count_params(
        tr.init_params(dcfg, jax.random.PRNGKey(0))
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
    with open(CUT) as f:
        d = dict(json.load(f), **bad)
    with pytest.raises(ValueError, match=word):
        from_hf_config(d)


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
    with open(CUT) as f:
        c = json.load(f)
    inv, scale = ref.rotation(c, "full+moe")
    np.testing.assert_array_equal(inv, got)
    assert scale == c["rope_parameters"]["full_attention"]["attention_factor"]
    assert scale == attention_factor
    plain, one = ref.rotation(c, "swa+moe")
    np.testing.assert_array_equal(plain, basic.rope_inv_freq(128, 500000.0))
    assert one == 1.0
    # positions to four times the original length: the slowest channels turn
    # a sixteenth as fast, the fastest as they did
    cos, sin = basic.rope_at(jnp.asarray([[32767]]), got, scale)
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
        want, _ = ref.layer(keys, kind, x[0], lp)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


@pytest.mark.parametrize("norm", ["q_norm", "k_norm"])
def test_the_head_norms_are_in_the_result(tiny, norm):
    """A norm's weight moved changes the logits of program and reference
    alike: neither drops it."""
    cfg, keys, params = tiny
    tok, seg, pos, seqs = _row([30], 32, cfg.vocab_size, seed=2)
    moved = jax.tree_util.tree_map(lambda a: a, params)
    moved["layers"]["swa+moe"] = dict(
        params["layers"]["swa+moe"],
        **{norm: params["layers"]["swa+moe"][norm] * 1.5},
    )
    base = tr.apply(params, cfg, tok, seg, pos)[0, :30]
    got = tr.apply(moved, cfg, tok, seg, pos)[0, :30]
    assert float(jnp.abs(got - base).max()) > 1e-4
    np.testing.assert_allclose(
        got, ref.logits(keys, moved, seqs[0]), atol=5e-5
    )


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
    assert inside == sum(arith_mellum.window_pairs(len(s), 8) for s in seqs)
    assert [int(x) for x in stats.counts["swa_pairs"]] == [inside] * 3
    assert [int(x) for x in stats.counts["swa_causal_pairs"]] == [causal] * 3


def test_logprobs_match_the_references_at_a_temperature(tiny):
    from areal_tpu.ops.functional import target_aligned_logprobs

    cfg, keys, params = tiny
    tok, seg, pos, seqs = _row([33, 14], 64, cfg.vocab_size, seed=6)
    logits = tr.apply(params, cfg, tok, seg, pos)
    got = np.asarray(target_aligned_logprobs(
        logits, {"tokens": tok, "segment_ids": seg}, temperature=0.7
    ))
    at = 0
    for s in seqs:
        want = ref.token_logprobs(keys, params, s.tolist(), temperature=0.7)
        np.testing.assert_allclose(
            got[0, at + 1:at + len(s)], want, atol=5e-5
        )
        at += len(s)


def test_grpo_loss_and_every_gradient_match_the_references(tiny):
    from areal_tpu.ops.functional import ppo_actor_loss_fn

    cfg, keys, params = tiny
    n = 45
    tok, seg, pos, seqs = _row([n], n, cfg.vocab_size, seed=3)
    rng = np.random.default_rng(8)
    old = jnp.asarray(rng.standard_normal(n - 1) * 0.1 - 4.8, jnp.float32)
    adv = jnp.asarray(rng.standard_normal(n - 1), jnp.float32)
    mask = jnp.asarray(np.arange(n - 1) >= 4)

    def grpo(logits):  # [T, V] of one sequence
        logp = ref.logprobs_of(logits, seqs[0])
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
    assert {"q_norm", "k_norm", "wq", "wk", "wv", "wo", "w_router", "w_gate",
            "w_up", "w_down", "input_norm", "post_attn_norm", "embedding",
            "lm_head", "final_norm"} <= seen


@pytest.mark.parametrize("form", ["xla", "splash"])
def test_the_windows_edge_is_exact(form):
    """The key at distance window - 1 is seen, the one at distance window is
    not: with v replaced at one key s, the outputs of queries s .. s +
    window - 1 move and every other row is bit-for-bit what it was; the
    kernel's band here is 128 keys in tiles of 128, the size a band's own
    tile has one border inside."""
    t, window, s = 512, 128, 171
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, hh, 64)), jnp.float32)
               for hh in (4, 2, 2))
    seg = jnp.ones((1, t), jnp.int32)
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
    with jax.default_matmul_precision("highest"):
        want = ref.masked_softmax_attention(q[0], k[0], v[0], window)
    np.testing.assert_allclose(base[0], want, atol=2e-5)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_a_share_of_the_experts_is_that_shares_part(tiny, first):
    cfg, keys, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=0)
    params = _seeded(whole_cfg, 9)
    lp = {k: v[0] for k, v in params["layers"]["swa+moe"].items()}
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, 29, cfg.hidden_size)), jnp.float32)
    held = slice(first, first + 4)
    part, stats = moe_ops.moe_ffn(
        h, lp["w_router"], lp["w_gate"][held], lp["w_up"][held],
        lp["w_down"][held], cfg.num_experts_per_tok, cfg.norm_topk_prob,
        first_expert=first, router_act=cfg.router_act,
        routed_scaling_factor=cfg.routed_scaling_factor,
    )
    mine = dict(lp, **{k: lp[k][held] for k in ("w_gate", "w_up", "w_down")})
    with jax.default_matmul_precision("highest"):
        want = ref.experts(keys, h[0], mine, first_expert=first)
        weight = ref.routing(keys, h[0], lp)
    np.testing.assert_allclose(part[0], want, atol=1e-5)
    assert int(stats.assignments) == int((weight[:, held] > 0).sum())


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model (the guide's section 4): the four
    holders of 4 of the 16 experts route over all 16 with the same router;
    their parts add up to the uncut reference's layer (there is no shared
    expert to count once)."""
    cfg, keys, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=0)
    params = _seeded(whole_cfg, 9)
    lp = {k: v[0] for k, v in params["layers"]["full+moe"].items()}
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, 29, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(dict(keys, num_local_experts=16), h[0], lp)
    total, pairs = 0.0, 0
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
    # and the layer's own call is the first holder's part
    mine = dict(lp, **{k: lp[k][:4] for k in ("w_gate", "w_up", "w_down")})
    own, _ = moe_ops.moe_ffn_from_params(cfg, mine, h)
    with jax.default_matmul_precision("highest"):
        first_part = ref.experts(keys, h[0], mine)
    np.testing.assert_allclose(own[0], first_part, atol=1e-5)


def test_the_refusals_by_what_the_window_declares(tiny, tmp_path):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models import hf_io

    cfg, _, params = tiny
    with pytest.raises(NotImplementedError, match="B.3"):
        GenerationEngine(JaxGenConfig(), model_config=cfg, params=params)
    whole = dataclasses.replace(cfg, experts_held=0)
    with pytest.raises(NotImplementedError, match="mellum"):
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


@pytest.mark.parametrize("attn_impl,row_len,window,want", [
    ("flash", 32768, 0, {"attn_bwd_window": "split", "attn_bwd_full": None}),
    ("flash", 32768, 8192, {"attn_bwd_window": "split",
                            "attn_bwd_full": None}),
    ("flash", 16384, 0, {"attn_bwd_window": "split",
                         "attn_bwd_full": "fused"}),
    ("xla", 32768, 0, {}),
])
def test_a_grad_dispatch_says_which_backward_each_core_took(
    attn_impl, row_len, window, want
):
    from areal_tpu.ops import flash
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["mellum2_12b_a2p5b"]()
    got = tr.grad_span_attrs(cfg, attn_impl, row_len, window)
    assert set(got) == set(want)
    for key, form in want.items():
        assert got[key] in ("split", "fused")
        if form:
            assert got[key] == form
    if got:  # what the kernel instance of that row is built with
        tiles = flash._block_sizes(row_len, 1023, 128, True)
        assert (got["attn_bwd_window"] == "fused") == (
            tiles.use_fused_bwd_kernel
        )
    # a model without window layers says nothing
    assert tr.grad_span_attrs(
        tiny_config("qwen2"), attn_impl, row_len, window
    ) == {}


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
    assert set(engine.counters()) >= {
        "swa_pairs", "swa_causal_pairs", "moe_assignments",
        "moe_max_expert_assignments", "moe_stream_rows",
        "moe_overflow_chunks",
    }
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
        # the XLA form has no backward forms to name
        assert not any("attn_bwd_window" in a for a in attrs)
    counters = engine.counters()
    assert counters["swa_pairs"] == 3 * sum(
        arith_mellum.window_pairs(n, 8) for n in lens
    )
    assert counters["swa_causal_pairs"] == 3 * sum(
        n * (n + 1) // 2 for n in lens
    )
    after = engine.params["layers"]
    for kind, group in before.items():  # every group of every kind moved
        for name in ("wq", "q_norm", "k_norm", "w_down"):
            assert np.abs(np.asarray(after[kind][name]) - group[name]).max() > 0
