"""The kimi_linear family (a stack of block kinds: gated delta-rule and
latent-attention mixers over a dense or a sigmoid-routed sparse FFN) at test
size against the plain reference ``benchmark/reference_kimi.py``: every block
kind, the logits, the loss's gradient of every parameter group, packing, the
share of the experts, and what the change leaves alone (models of one block
kind, to the bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import moe as moe_ops
from benchmark import reference_kimi as ref
from benchmark.kinds import train_steps_kda

CUT = "kimi-linear-48b-a3b.json"
TOTAL = 130


def _seeded(cfg, seed):
    """A correction bias that matters."""
    params = fb.init(cfg, seed)
    rng = np.random.default_rng(2)
    for group in params["layers"].values():
        if "router_bias" in group:
            group["router_bias"] = (
                rng.standard_normal(group["router_bias"].shape) * 0.2
            ).astype(np.float32)
    return params


def _reference_logits(keys, params, tokens):
    """``ref.logits`` of traced tokens. ``ref._run`` looks its embedding's
    rows up with ``np.asarray(tokens)``, which no tracer survives, and jits a
    fresh lambda a layer a call; so its lines around ``ref.layer``, the
    largest function of it that traces, are restated here (the rehearsal in
    ``tests/benchmark_suite`` runs ``ref.logits`` itself)."""
    x = ref._f32(params["embedding"])[tokens]
    for kind, i in ref.layer_kinds(keys):
        x, _ = ref.layer(keys, kind, x, fb.layer_of(params, kind, i))
    x = ref._rms_norm(x, ref._f32(params["final_norm"]), keys["rms_norm_eps"])
    return x @ ref._f32(params["lm_head"])


FAMILY = fb.Family(
    "kimi_linear", train_steps_kda.tiny_configs, _reference_logits, _seeded,
    total=TOTAL,
)
tiny = fb.tiny_fixture(FAMILY)
trained = fb.trained_fixture()


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("kimi_linear")
    assert set(t.layer_kinds) == {"kda+dense", "kda+moe", "mla+moe"}
    assert (t.num_held_experts, t.num_experts, t.kda.num_heads) == (8, 16, 2)
    assert t.router_act == "sigmoid" and not t.shared_expert_gate
    cut = from_hf_config(fb.cut(CUT))
    assert cut.layer_kinds == (
        "kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"
    )
    assert (cut.num_experts, cut.num_held_experts, cut.vocab_size) == (
        256, 8, 20480
    )
    assert cut.attn_head_dims == (192, 128) and cut.num_kda_layers == 4
    # the published keys, unchanged
    full = from_hf_config(fb.published("Kimi-Linear-48B-A3B-Instruct"))
    assert (full.num_layers, full.num_experts, full.experts_held) == (
        27, 256, 0
    )
    assert full.num_kda_layers == 20 and full.layer_kinds[26] == "mla+moe"
    assert full.kda == cut.kda and full.mla == cut.mla
    assert [n for _, n in tr._runs(full.layer_kinds)][:5] == [1, 2, 1, 3, 1]


@pytest.mark.parametrize("bad,word", [
    ({"mla_use_nope": False, "rope_scaling": {"type": "yarn", "factor": 4}},
     "rope_scaling on a rotated"),
    ({"num_expert_group": 8}, "num_expert_group"),
    ({"moe_router_activation_func": "softmax"}, "not sigmoid"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


@pytest.mark.parametrize("said,field,value", [
    ({"mla_use_nope": False}, "rotated", True),
    ({"mla_use_nope": False, "q_lora_rank": 768}, "q_lora_rank", 768),
    ({"q_lora_rank": 1536}, "q_lora_rank", 1536),
])
def test_a_rotated_key_part_and_a_compressed_query_are_the_mixers_fields(
    said, field, value
):
    """What the reader refused by name until PR 54 (``_mla_mixer`` had
    neither) it now reads to ``MLAConfig``; the published file (NoPE, the
    query whole) reads to the configuration it always did."""
    import dataclasses

    from areal_tpu.models.config import MLAConfig

    assert from_hf_config(fb.cut(CUT)).mla == MLAConfig(512, 128, 64, 128)
    changed = from_hf_config(fb.cut(CUT, **said))
    assert getattr(changed.mla, field) == value
    assert dataclasses.replace(
        changed, mla=from_hf_config(fb.cut(CUT)).mla
    ) == from_hf_config(fb.cut(CUT))


@pytest.mark.parametrize("kind", ["kda+dense", "kda+moe", "mla+moe"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    stats = fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(tiny.keys, kind, x, lp)[0]
    )
    # a layer reports (its FFN's, its mixer's)
    assert (stats[1] is not None) == kind.startswith("kda")
    assert (stats[0] is not None) == kind.endswith("moe")


def test_logits_match_alone_and_packed_with_padding(tiny):
    # borders fall inside a chunk and inside the conv's reach
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

    def never(g, w):  # it moves a choice, never a value
        assert not np.asarray(w).any() and not np.asarray(g).any()

    seen = fb.check_gradients(
        FAMILY, tiny, weighted, n=45, value_tol=(1e-3, 1e-4),
        special={"router_bias": never},
    )
    assert {"a_log", "dt_bias", "w_f_a", "w_f_b", "w_g_a", "w_g_b", "conv_q",
            "conv_k", "conv_v", "w_beta", "o_norm", "w_kv_a", "w_kv_b",
            "kv_norm", "w_shared_gate", "w_router"} <= set(seen)


def test_the_short_convolution_stops_at_a_sequences_first_token():
    x = fb.normal(0, 1, 12, 3)
    w = np.asarray([[1.0, 2.0, 3.0, 4.0]] * 3, np.float32)
    seg = np.asarray([[1] * 5 + [2] * 5 + [0] * 2], np.int32)
    got = jax.jit(tr._short_conv)(x, w, seg)
    alone = jax.jit(ref._conv)  # both sequences are 5 tokens: one shape
    for a, b in ((0, 5), (5, 10)):
        np.testing.assert_allclose(got[0, a:b], alone(x[0, a:b], w), atol=1e-6)


@pytest.mark.parametrize("t", [128, 256])
def test_the_latent_core_at_192_over_128_through_the_kernel(t):
    """The splash kernel in interpret mode at a query/key width of 192 and a
    value width of 128 (no padding of v) against the XLA form, forward and
    backward, two sequences and padding in the row."""
    from areal_tpu.ops.basic import segment_attention
    from areal_tpu.ops.flash import flash_segment_attention, takes_head_dim

    assert takes_head_dim(192, 128) and not takes_head_dim(192, 64)
    q, k = (fb.normal(t + i, 1, t, 2, 192) for i in range(2))
    v = fb.normal(t + 2, 1, t, 2, 128)
    seg = np.asarray(
        [[1] * (t // 2) + [2] * (t // 4) + [0] * (t // 4)], np.int32
    )

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a, seg) ** 2).sum(), argnums=(0, 1, 2)
        ))(q, k, v)

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
    fb.check_shares_add_up(
        FAMILY, tiny, "kda+moe", ref.experts, ref.shared_expert,
        router_bias=np.linspace(-0.3, 0.3, 16).astype(np.float32),
    )


@pytest.mark.parametrize("family", ["qwen2_moe", "kimi_linear", "qwen3_next"])
def test_the_shared_expert_runs_inside_the_sparse_layers_shard_map(family):
    """Under a mesh the whole sparse layer, shared expert included, is one
    per-device call: the same numbers as the plain call on one device (for
    ``qwen3_next`` the gated shared expert of a stack of kinds, and the
    delta rule's mesh form splitting ``g`` [B, T, H] by its rank)."""
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.engine.programs import _forward_fn
    from areal_tpu.parallel import mesh as mesh_lib

    n = min(4, jax.device_count())
    if n < 2:
        pytest.skip("one device")
    cfg = tiny_config(family)
    assert cfg.shared_expert_size and "w_shared_up" in moe_ops.sparse_leaves(cfg)
    params = fb.init(cfg, 1)
    rows = [fb.row([20, 11], 32, cfg.vocab_size, seed=i) for i in range(n)]
    tok, seg, pos = (np.concatenate([r[j] for r in rows]) for j in range(3))
    arrays = {"tokens": tok, "segment_ids": seg, "positions": pos}
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=n))
    one = mesh_lib.make_mesh(ParallelismConfig(), jax.devices()[:1])

    def hidden(m):
        forward = _forward_fn(cfg, m, jnp.float32, None, remat=False)
        out = jax.jit(lambda p, a: forward(p, a).hidden)(params, arrays)
        return np.asarray(out)

    np.testing.assert_allclose(hidden(mesh), hidden(one), atol=2e-5)


FINGERPRINTS = {  # from this file's own function, one compiled program
    "qwen2": (-4.796672821044922, 71445.07688094693, 808.160400390625),
    "olmoe": (-2.9243152141571045, 99889.14326616446, 783.4895629882812),
    "KeyeVL2": (-5.91508674621582, 109418.36398591805, 804.7095947265625),
    "qwen2_moe": (-5.8543291091918945, 57958.00637981296, 809.0015258789062),
}


@pytest.mark.parametrize("family", sorted(FINGERPRINTS))
def test_a_model_of_one_block_kind_is_what_it_was(family):
    """A weighted sum of the logits, the summed absolute gradient of every
    parameter and the summed absolute logits of the tiny dense, OLMoE, Keye
    and qwen2_moe models on this backend. The commit before the block kinds
    read them operation by operation (equal to the last digit there; the
    lowered programs were compared as text beside, PERF.md PR 33); PR 48 read
    them again under one ``jit``, the model's code unchanged, within 4e-6 of
    those (another order of the same sums)."""
    cfg = tiny_config(family)
    assert not cfg.layer_kinds
    p = fb.init(cfg, 5)
    assert set(p["layers"]) >= {"wq", "wo", "input_norm"}  # the flat stack
    t = 48
    tok = (np.arange(t, dtype=np.int32)[None] * 7 + 3) % cfg.vocab_size
    seg = np.asarray([[1] * 20 + [2] * 22 + [0] * 6], np.int32)
    pos = np.concatenate(
        [np.arange(20), np.arange(22), np.zeros(6)]
    ).astype(np.int32)[None]
    w = np.cos(
        np.arange(t * cfg.vocab_size).reshape(1, t, -1) * 0.01
    ).astype(np.float32)

    @jax.jit
    def read(p):
        logits = tr.apply(p, cfg, tok, seg, pos)
        return (logits * w).sum(), jnp.abs(logits).sum()

    (val, summed), g = jax.jit(jax.value_and_grad(read, has_aux=True))(p)
    got = (
        float(val),
        float(sum(np.abs(np.asarray(x, np.float64)).sum()
                  for x in jax.tree_util.tree_leaves(g))),
        float(summed),
    )
    np.testing.assert_allclose(got, FINGERPRINTS[family], rtol=1e-6)


def test_checkpoint_names_round_trip_and_a_share_has_no_layout(tiny, tmp_path):
    from areal_tpu.models import hf_io

    cfg, params = fb.whole(FAMILY, tiny)
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
    tiny, trained
):
    engine, lens, stats = trained.engine, trained.lens, trained.stats
    assert (engine.attn_impl, engine.moe_impl, engine.kda_impl) == (
        "xla", "ragged_dot", "xla"
    )
    assert engine._span_attrs["kda_impl"] == "xla"
    counters = engine.counters()
    n_kda = tiny.cfg.num_kda_layers
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
    was = trained.before["kda+moe"]
    assert (np.asarray(after["router_bias"]) == was["router_bias"]).all()
    trained.moved("kda+moe", "a_log", "dt_bias", "conv_k", "w_f_b", "wq")
