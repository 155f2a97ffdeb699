"""The kimi_linear family (a stack of block kinds: gated delta-rule and
latent-attention mixers over a dense or a sigmoid-routed sparse FFN) at test
size against the plain reference ``benchmark/reference_kimi.py``: every block
kind, the logits, the loss's gradient of every parameter group, packing, the
share of the experts, and what the change leaves alone (models of one block
kind, to the bit)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_kimi as ref  # noqa: E402
from benchmark.kinds import train_steps_kda  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tiny():
    cfg, keys = train_steps_kda.tiny_configs()
    params = tr.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    # a correction bias that matters, and decays off their initial values
    rng = np.random.default_rng(2)
    for kind, group in params["layers"].items():
        if "router_bias" in group:
            group["router_bias"] = jnp.asarray(
                rng.standard_normal(group["router_bias"].shape) * 0.2,
                jnp.float32,
            )
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
    t = tiny_config("kimi_linear")
    assert set(t.layer_kinds) == {"kda+dense", "kda+moe", "mla+moe"}
    assert (t.num_held_experts, t.num_experts, t.kda.num_heads) == (8, 16, 2)
    assert t.router_act == "sigmoid" and not t.shared_expert_gate
    with open(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json"
    )) as f:
        cut = from_hf_config(json.load(f))
    assert cut.layer_kinds == (
        "kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"
    )
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        256, 8, 20480
    )
    assert cut.attn_head_dims == (192, 128) and cut.num_kda_layers == 4
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "Kimi-Linear" in line)
    full = from_hf_config(row["config"])  # the published keys, unchanged
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        27, 256, 0
    )
    assert full.num_kda_layers == 20 and full.layer_kinds[26] == "mla+moe"
    assert full.kda == cut.kda and full.mla == cut.mla
    assert [n for _, n in tr._runs(full.layer_kinds)][:5] == [1, 2, 1, 3, 1]


@pytest.mark.parametrize("bad,word", [
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"num_expert_group": 8}, "num_expert_group"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    with open(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json"
    )) as f:
        d = dict(json.load(f), **bad)
    with pytest.raises(ValueError, match=word):
        from_hf_config(d)


@pytest.mark.parametrize("kind", ["kda+dense", "kda+moe", "mla+moe"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    cfg, keys, params = tiny
    lp = {k: v[0] for k, v in params["layers"][kind].items()}
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 37, cfg.hidden_size)), jnp.float32)
    seg = jnp.ones((1, 37), jnp.int32)
    got, stats = jax.jit(
        lambda x, lp: tr._block_body(cfg, kind, x, lp, seg)
    )(x, lp)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(keys, kind, x[0], lp)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # a layer reports (its FFN's, its mixer's)
    assert (stats[1] is not None) == kind.startswith("kda")
    assert (stats[0] is not None) == kind.endswith("moe")


def test_logits_match_alone_and_packed_with_padding(tiny):
    cfg, keys, params = tiny
    tok, seg, pos, seqs = _row([70, 33, 21], 130, cfg.vocab_size)
    got, stats = jax.jit(
        lambda p: tr.apply(p, cfg, tok, seg, pos, return_stats=True)
    )(params)
    at = 0
    for s in seqs:  # borders fall inside a chunk and inside the conv's reach
        np.testing.assert_allclose(
            got[0, at:at + len(s)], ref.logits(keys, params, s), atol=5e-5
        )
        at += len(s)
    assert [int(stats.counts[k]) for k in (
        "kda_tokens", "kda_chunk_slots", "kda_segments"
    )] == [124 * 3, 192 * 3, 3 * 3]


def test_loss_and_every_gradient_match_the_references(tiny):
    cfg, keys, params = tiny
    tok, seg, pos, seqs = _row([45], 45, cfg.vocab_size, seed=3)
    w = jnp.cos(jnp.arange(45 * cfg.vocab_size).reshape(45, -1) * 0.01)

    def program(p):
        return (tr.apply(p, cfg, tok, seg, pos, remat=True)[0] * w).sum()

    def reference(p):
        return (ref.logits(keys, p, seqs[0]) * w).sum()

    got_v, got = jax.jit(jax.value_and_grad(program))(params)
    want_v, want = jax.value_and_grad(reference)(params)
    assert abs(float(got_v - want_v)) < 1e-3 * abs(float(want_v)) + 1e-4
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    seen = set()
    for (path, g), w_ in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w_).max())
        if "router_bias" in name:  # it moves a choice, never a value
            assert scale == 0.0 and not np.asarray(g).any(), name
            continue
        assert scale > 0, name
        assert float(jnp.abs(g - w_).max()) <= 2e-3 * scale, name
        seen.add(name.split("'")[-2])
    assert {"a_log", "dt_bias", "w_f_a", "w_f_b", "w_g_a", "w_g_b", "conv_q",
            "conv_k", "conv_v", "w_beta", "o_norm", "w_kv_a", "w_kv_b",
            "kv_norm", "w_shared_gate", "w_router"} <= seen


def test_the_short_convolution_stops_at_a_sequences_first_token():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 12, 3)))
    w = jnp.asarray([[1.0, 2.0, 3.0, 4.0]] * 3)
    seg = jnp.asarray([[1] * 5 + [2] * 5 + [0] * 2])
    got = tr._short_conv(x, w, seg)
    for a, b in ((0, 5), (5, 10)):
        np.testing.assert_allclose(
            got[0, a:b], ref._conv(x[0, a:b].astype(jnp.float32), w), atol=1e-6
        )


@pytest.mark.parametrize("t", [128, 256])
def test_the_latent_core_at_192_over_128_through_the_kernel(t):
    """The splash kernel in interpret mode at a query/key width of 192 and a
    value width of 128 (no padding of v) against the XLA form, forward and
    backward, two sequences and padding in the row."""
    from areal_tpu.ops.basic import segment_attention
    from areal_tpu.ops.flash import flash_segment_attention, takes_head_dim

    assert takes_head_dim(192, 128) and not takes_head_dim(192, 64)
    rng = np.random.default_rng(t)
    q, k = (jnp.asarray(rng.standard_normal((1, t, 2, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, t, 2, 128)), jnp.float32)
    seg = jnp.asarray([[1] * (t // 2) + [2] * (t // 4) + [0] * (t // 4)])

    def both(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a, seg) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    want = both(segment_attention)
    got = both(lambda *a: flash_segment_attention(*a, interpret=True))
    assert got[1][2].shape == v.shape
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_the_sigmoid_routers_bias_moves_the_choice_and_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    p, i, s = moe_ops.router_topk(logits, 2, True, "sigmoid", None, 2.0)
    assert sorted(np.asarray(i[0])) == [0, 1]
    sig = np.asarray(jax.nn.sigmoid(logits[0]))
    np.testing.assert_allclose(
        np.sort(p[0]), np.sort(2.0 * sig[:2] / sig[:2].sum()), rtol=1e-6
    )
    bias = jnp.asarray([0.0, -1.0, 0.0, 1.0])  # expert 3 over expert 1
    p2, i2, _ = moe_ops.router_topk(logits, 2, True, "sigmoid", bias, 2.0)
    assert sorted(np.asarray(i2[0])) == [0, 3]
    chosen = sig[np.asarray(i2[0])]  # weighted WITHOUT the bias
    np.testing.assert_allclose(p2[0], 2.0 * chosen / chosen.sum(), rtol=1e-6)
    g = jax.grad(lambda b: moe_ops.router_topk(
        logits, 2, True, "sigmoid", b, 2.0)[0].sum())(bias)
    assert not np.asarray(g).any()
    # the softmax form is as it was
    ps, is_, full = moe_ops.router_topk(logits, 2, False)
    np.testing.assert_allclose(ps[0], np.sort(full[0])[::-1][:2], rtol=1e-6)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """What ties the share to the model: the two holders of 8 of the 16
    experts route over all 16 with the same router and bias; their parts,
    with the shared expert counted ONCE, are the uncut reference's layer."""
    cfg, keys, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=0)
    params = tr.init_params(whole_cfg, jax.random.PRNGKey(9), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"]["kda+moe"].items()}
    lp["router_bias"] = jnp.linspace(-0.3, 0.3, 16)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, 29, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(keys, h[0], lp) + ref.shared_expert(h[0], lp)
    total = np.asarray(moe_ops.shared_expert_from_params(cfg, lp, h))[0]
    pairs = 0
    for first in (0, 8):
        held = slice(first, first + 8)
        part, stats = moe_ops.moe_ffn(
            h, lp["w_router"], lp["w_gate"][held], lp["w_up"][held],
            lp["w_down"][held], cfg.num_experts_per_tok, cfg.norm_topk_prob,
            first_expert=first, router_act=cfg.router_act,
            router_bias=lp["router_bias"],
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
        total = total + np.asarray(part)[0]
        pairs += int(stats.assignments)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert pairs == 29 * cfg.num_experts_per_tok
    # and the layer's own call is one holder's part plus the shared expert
    own, _ = moe_ops.moe_ffn_from_params(
        cfg, dict(lp, **{k: lp[k][:8] for k in ("w_gate", "w_up", "w_down")}), h
    )
    with jax.default_matmul_precision("highest"):
        first_part = ref.experts(
            keys, h[0], dict(lp, **{k: lp[k][:8]
                                    for k in ("w_gate", "w_up", "w_down")})
        ) + ref.shared_expert(h[0], lp)
    np.testing.assert_allclose(own[0], first_part, atol=1e-5)


@pytest.mark.parametrize("family", ["qwen2_moe", "kimi_linear"])
def test_the_shared_expert_runs_inside_the_sparse_layers_shard_map(family):
    """Under a mesh the whole sparse layer, shared expert included, is one
    per-device call: the same numbers as the plain call on one device."""
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.engine.programs import _forward_fn
    from areal_tpu.parallel import mesh as mesh_lib

    n = min(4, jax.device_count())
    if n < 2:
        pytest.skip("one device")
    cfg = tiny_config(family)
    assert cfg.shared_expert_size and "w_shared_up" in moe_ops.sparse_leaves(cfg)
    params = tr.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    rows = [_row([20, 11], 32, cfg.vocab_size, seed=i) for i in range(n)]
    tok, seg, pos = (jnp.concatenate([r[j] for r in rows]) for j in range(3))
    arrays = {"tokens": tok, "segment_ids": seg, "positions": pos}
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=n))
    one = mesh_lib.make_mesh(ParallelismConfig(), jax.devices()[:1])

    def hidden(m):
        forward = _forward_fn(cfg, m, jnp.float32, None, remat=False)
        out = jax.jit(lambda p, a: forward(p, a).hidden)(params, arrays)
        return np.asarray(out)

    np.testing.assert_allclose(hidden(mesh), hidden(one), atol=2e-5)


FINGERPRINTS = {  # the parent commit's, from this file's own function
    "qwen2": (-4.796655654907227, 71445.078125, 808.160400390625),
    "olmoe": (-2.924315929412842, 99889.15625, 783.4895629882812),
    "KeyeVL2": (-5.9150800704956055, 109418.3671875, 804.7096557617188),
    "qwen2_moe": (-5.854315280914307, 57957.9921875, 809.0015869140625),
}


@pytest.mark.parametrize("family", sorted(FINGERPRINTS))
def test_a_model_of_one_block_kind_is_what_it_was(family):
    """A weighted sum of the logits, the summed absolute gradient of every
    parameter and the summed absolute logits of the tiny dense, OLMoE, Keye
    and qwen2_moe models, as the commit before the block kinds read them on
    this backend (equal to the last digit there; the lowered programs were
    compared as text beside, PERF.md PR 33)."""
    cfg = tiny_config(family)
    assert not cfg.layer_kinds
    p = tr.init_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    assert set(p["layers"]) >= {"wq", "wo", "input_norm"}  # the flat stack
    t = 48
    tok = (jnp.arange(t)[None] * 7 + 3) % cfg.vocab_size
    seg = jnp.asarray([[1] * 20 + [2] * 22 + [0] * 6])
    pos = jnp.concatenate(
        [jnp.arange(20), jnp.arange(22), jnp.zeros(6, jnp.int32)]
    )[None]
    w = jnp.cos(jnp.arange(t * cfg.vocab_size).reshape(1, t, -1) * 0.01)
    val, g = jax.value_and_grad(
        lambda p: (tr.apply(p, cfg, tok, seg, pos) * w).sum()
    )(p)
    got = (
        float(val),
        float(sum(jnp.abs(x).astype(jnp.float64).sum()
                  for x in jax.tree_util.tree_leaves(g))),
        float(jnp.abs(tr.apply(p, cfg, tok, seg, pos)).sum()),
    )
    np.testing.assert_allclose(got, FINGERPRINTS[family], rtol=1e-6)


def test_checkpoint_names_round_trip_and_a_share_has_no_layout(tiny, tmp_path):
    from areal_tpu.models import hf_io

    cfg = dataclasses.replace(tiny[0], experts_held=0)
    params = tr.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    hf_io.save_params(params, cfg, str(tmp_path))
    assert hf_io.load_hf_config(str(tmp_path)) == cfg
    back = hf_io.load_params(str(tmp_path), cfg, dtype=jnp.float32)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(
        params
    )
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(a, b)
    from safetensors import safe_open

    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
    assert {"model.layers.0.self_attn.q_conv1d.weight",
            "model.layers.0.mlp.up_proj.weight",
            "model.layers.1.block_sparse_moe.gate.e_score_correction_bias",
            "model.layers.2.self_attn.kv_a_proj_with_mqa.weight",
            "model.layers.3.block_sparse_moe.experts.15.w2.weight"} <= names
    with pytest.raises(NotImplementedError, match="8 of 16 experts"):
        hf_io.save_params(tiny[2], tiny[0], str(tmp_path / "share"))


def test_the_generation_engine_refuses_a_stack_of_block_kinds(tiny):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine

    with pytest.raises(NotImplementedError, match="recurrent state"):
        GenerationEngine(JaxGenConfig(), model_config=tiny[0], params=tiny[2])


def test_the_trainer_trains_the_family_and_counts_its_scans_exactly(
    tiny, monkeypatch
):
    from areal_tpu.api import cli_args
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.utils import data as data_utils

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
    assert (engine.attn_impl, engine.moe_impl, engine.kda_impl) == (
        "xla", "ragged_dot", "xla"
    )
    assert engine._span_attrs["kda_impl"] == "xla"
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
    stats = actor.ppo_update(actor.compute_advantages(batch))
    assert all(s["update_successful"] == 1.0 for s in stats)
    counters = engine.counters()
    n_kda = tiny[0].num_kda_layers
    assert counters["kda_tokens"] == sum(lens) * n_kda
    assert counters["kda_segments"] == len(lens) * n_kda
    assert counters["kda_chunk_slots"] % (64 * n_kda) == 0
    assert counters["kda_chunk_slots"] >= counters["kda_tokens"]
    assert sum(s["kda_tokens"] for s in stats) == counters["kda_tokens"]
    assert 0 < counters["moe_assignments"] < sum(lens) * 2 * 3
    # a share of the experts: the rows its three sparse layers' expert
    # paths ran over (at this size a slab is the whole stream: the two
    # pairs of every token of the train microbatches' rows, padding's too)
    # and no chunk past one slab
    assert counters["moe_stream_rows"] % (2 * 3) == 0
    assert counters["moe_stream_rows"] >= sum(lens) * 2 * 3
    assert counters["moe_overflow_chunks"] == 0
    assert sum(s["moe_stream_rows"] for s in stats) == (
        counters["moe_stream_rows"]
    )
    after = engine.params["layers"]["kda+moe"]
    was = before["kda+moe"]
    assert (np.asarray(after["router_bias"]) == was["router_bias"]).all()
    for moved in ("a_log", "dt_bias", "conv_k", "w_f_b", "wq"):
        assert not (np.asarray(after[moved]) == was[moved]).all(), moved
    engine.destroy()
