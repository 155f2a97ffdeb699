"""The JoyAI-LLM-Flash configuration's benchmark files: its arithmetic against
hand-worked counts and the program's own leaves, the configuration file
against the catalog's row, every new data file, one rehearsal of the cell's
traffic kind, the reference's draft module on a sequence by hand, and the two
new metric files on a record that has no such scope (they read nothing and
raise nothing).
"""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_joyai as arith
from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_joyai_mla_mtp_grpo_long16k"
CONFIG = "joyai-llm-flash"
NEW_METRICS = ("mla_expand_time_share.train", "mtp_time_share.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _published():
    with open(os.path.join(
        ROOT, "benchmark", "configs", CONFIG + ".json"
    )) as f:
        return json.load(f)


def test_parameters_against_the_hand_worked_492_million():
    c = _published()
    qa, qb, kva = 2048 * 1536, 1536 * 32 * 192, 2048 * (512 + 64)
    kvb, o = 512 * 32 * 256, 4096 * 2048
    assert (qa, qb, kva, kvb, o) == (
        3_145_728, 9_437_184, 1_179_648, 4_194_304, 8_388_608
    )
    assert arith.mixer_matmul_weights(c) == qa + qb + kva + kvb + o
    assert arith.mixer_params(c) == qa + qb + kva + kvb + o + 2_048 == (
        26_347_520
    )
    assert arith.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert arith.ffn_params(c, "dense") == 3 * 2048 * 7168 == 44_040_192
    # router 524,288 + bias 256, eight held 37,748,736, the shared one
    assert arith.ffn_params(c, "moe") == (
        524_288 + 256 + 37_748_736 + 4_718_592
    )
    assert arith.layer_params(c, "dense") == 70_391_808
    assert arith.layer_params(c, "moe") == 69_343_488
    assert arith.layer_ffns(c) == ["dense"] + ["moe"] * 4
    # the module: its block, W_eh 4096 x 2048, three norms
    assert arith.draft_params(c) == 69_343_488 + 8_388_608 + 6_144 == (
        77_738_240
    )
    total = (
        70_391_808 + 4 * 69_343_488 + 77_738_240 + 2 * 16_160 * 2048 + 2_048
    )
    assert arith.param_count(c) == total == 491_697_408
    assert round(total * 16 / 1e9, 2) == 7.87  # GB at 16 B a parameter
    assert round(100 * total * 16 / 17_179_869_184, 1) == 45.8
    assert round(total * 22 / 1e9, 2) == 10.82  # at grad_step's peak
    # and the program stores exactly that, leaf by leaf
    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == total
    sparse = shapes["layers"]["mla+moe"]
    assert sparse["wq_a"].shape == (4, 2048, 1536)
    assert sparse["wq_b"].shape == (4, 1536, 32 * 192)
    assert sparse["w_kv_a"].shape == (4, 2048, 576)
    assert sparse["w_kv_b"].shape == (4, 512, 32 * 256)
    assert sparse["w_gate"].shape == (4, 8, 2048, 768)
    assert sparse["w_router"].shape == (4, 2048, 256)
    assert shapes["layers"]["mla+dense"]["w_gate"].shape == (1, 2048, 7168)
    module = shapes["mtp"]
    assert module["w_eh"].shape == (4096, 2048)
    assert module["block"]["wq_b"].shape == (1, 1536, 6144)
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(module)
    ) == 77_738_240
    # sixteen held, the driver's share: no fit beside a 16,384-token row
    more = arith.param_count(dict(c, num_local_experts=16))
    assert round(more / 1e6, 1) == 680.4 and round(more * 16 / 1e9, 2) == 10.89
    assert round(more * 22 / 1e9, 2) == 14.97
    # the whole model from the same keys: the name's 48B, 50.2 with the module
    whole = dict(c, **c["published"])
    assert round(arith.param_count(whole) / 1e9, 1) == 50.2
    assert round(
        (arith.param_count(whole) - arith.draft_params(whole)) / 1e9, 1
    ) == 48.9


def test_flops_and_kernel_counts_against_hand_worked_values():
    c = _published()
    mixer = 26_347_520 - 2_048
    assert arith.expert_pairs_per_token(c) == 8 * 8 / 256 == 0.25
    sparse = mixer + 524_288 + 1.25 * 4_718_592  # router, 1/4 pair, shared
    weights = mixer + 44_040_192 + 4 * sparse + 2048 * 16_160
    assert arith.trained_weights(c) == weights
    own, head = arith.draft_weights(c)
    assert (own, head) == (2 * 2048 * 2048 + sparse, 2048 * 16_160)
    # a pair: 2 x 192 for the score and 2 x 128 for the value, 32 heads
    assert arith.core_flops_per_pair(c) == 32 * (2 * 192 + 2 * 128) == 20_480
    n = 3000
    pairs = n * (n + 1) // 2
    fwd = 2.0 * n * weights + 5 * pairs * 20_480
    assert arith.forward_flops(c, [n]) == fwd
    # the module: forward + backward of its own weights and core, the
    # trunk's head forward and ONE backward product
    draft = 3.0 * (2.0 * n * own + pairs * 20_480) + 4.0 * n * head
    assert arith.draft_flops(c, [n]) == draft
    assert arith.recompute_forwards(4) == 0.75
    assert arith.train_step_flops(c, [n], 4) == 3.75 * fwd + draft
    # about a sixth of a gradient pass is the module's (one block in six,
    # one head pass in two, no recompute forward)
    assert 0.13 < draft / (3.75 * fwd + draft) < 0.19
    # the cores: five in 1.75 forwards and the backward, the module's in
    # one forward and the backward
    assert arith.core_kernel_flops(c, 1, 4) == 32 * 320 * (
        5 * (2 * 1.75 + 4) + (2 + 4)
    )
    # the grouped matmuls: the trunk's pairs through 0.75 recompute
    # forwards beside the gradient pass's four, the module's through four
    assert arith.expert_kernel_flops(c, 500, 100, True, 4) == (
        2.0 * 4_718_592 * (400 * 4.75 + 100 * 4)
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _published()
    bench = harness.load_bench()
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"
    ]
    assert c["source"] == entry["source"]
    for key in ("published", "deployment", "assumed", "logprob_limits",
                "mtp_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    for key in ("draft_module_inputs", "draft_module_hidden",
                "draft_module_gradient", "mtp_loss_coef", "rotation",
                "e_score_correction_bias"):
        assert c["assumed"][key], key
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: the leading dense layer and four more, 8 experts, an eighth
    assert c["num_hidden_layers"] == c["first_k_dense_replace"] + 4
    assert c["num_local_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert "32 chips share each layer" in c["deployment"]
    assert "491,697,408" in c["reduced_note"]
    assert (c["mtp_loss_coef"], c["router_aux_loss_coef"]) == (0.1, 0.0)
    for limits in (c["logprob_limits"], c["mtp_limits"]):
        assert 0 < limits["mean"] < limits["worst"] < 2 and limits["why"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        row = next(
            json.loads(line) for line in f if '"JoyAI-LLM-Flash"' in line
        )
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    # what the catalog's row counts as the experts' number is published
    assert c["published"]["num_local_experts"] == row["config"][
        "n_routed_experts"
    ]


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_mla"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "joyai_llm_flash"
    assert set(NEW_METRICS) | {
        "train_mfu", "attn_time_share.train", "attn_full_roofline.train",
        "moe_held_fill.train", "moe_experts_roofline.train",
        "moe_experts_time_share.train", "moe_dispatch_time_share.train",
        "expert_load_imbalance.train", "rope_time_share.train",
        "proj_time_share.train", "unscoped_time_share.train",
        "setup_compile_s.train", "lm_head_time_share.train",
    } <= set(cell.per_layer)
    assert not [m for m in cell.per_layer if m.startswith(("kda_", "gdn_"))]
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (16, 4, 3)
    assert p["sizes"] == {} and p["trace_steps"] == 2
    assert p["prompt_len"] == {"law": "uniform", "lo": 256, "hi": 1024}
    assert p["output_len"] == {
        "law": "lognormal", "median": 5000, "sigma": 0.7, "lo": 512,
        "hi": 15360,
    }
    assert p["check_lens"] == [8192, 4099, 2304, 640]
    assert sum(p["check_lens"]) <= 16384  # mtp_exact packs them in ONE row
    from areal_tpu.utils.tracing import DEVICE_SCOPES

    for name, scope in zip(NEW_METRICS, ("mla_expand", "mtp")):
        spec = harness.load_json(
            os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        )
        assert spec["scopes"] == [scope] and scope in DEVICE_SCOPES
        assert spec["programs"] == ["grad_step", "logp_forward"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    mc = from_hf_config(cell.config)
    assert mc.family == "joyai_llm_flash" and len(mc.layer_kinds) == 5
    assert mc.trains_draft


def test_rehearsal_of_the_mla_kind_ends_in_the_contracts_line(capsys):
    from benchmark import run

    assert run.main([
        "--workload", CELL, "--seed", "3000000001", "--seconds", "1",
        "--trace", "0", "--rehearse",
    ]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    notes, line = json.loads(lines[-2]), json.loads(lines[-1])
    assert line["correct"] is True and "rehearsal" in line
    assert set(notes["checks"]) == {
        "finite_loss_and_grad_norm", "no_compile_in_window",
        "reference_agrees", "packing_invariant", "mtp_exact",
    }
    assert all(notes["checks"].values())
    mtp = notes["notes"]["mtp"]
    # four sequences, two slots each without a target
    assert mtp["alone"]["compared"] == mtp["packed"]["compared"] == (
        70 + 27 + 19 + 6 - 8
    )
    assert mtp["alone"]["max_abs_diff"] < 1e-4
    assert mtp["packed"]["max_abs_diff"] < 1e-4
    counted = notes["notes"]["counted"]
    assert counted["mtp_tokens"] == mtp["window_expected"]["mtp_tokens"] > 0
    assert 0 < counted["mtp_assignments"] < counted["moe_assignments"]
    assert counted["moe_stream_rows"] >= counted["moe_assignments"]
    assert all(np.isfinite(x) for x in mtp["loss_first_last"])
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_the_references_draft_module_by_hand_on_one_sequence():
    """``reference_joyai.draft`` against its own pieces laid out by hand:
    slot i reads the NEXT token's embedding and the trunk's output at i, the
    last slot a zero embedding; a changed last token moves only the slot
    before it; the loss is the masked mean over the slots with a target."""
    import jax.numpy as jnp

    from benchmark import reference_joyai as ref
    from benchmark.kinds import train_steps_mla

    cfg, keys = train_steps_mla.tiny_configs()
    params = jax.tree_util.tree_map(
        np.asarray, init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    )
    pub = ref.published_layout(keys, params)
    seq = np.asarray([5, 9, 77, 3, 41, 8, 100, 23, 64], np.int32)
    with jax.default_matmul_precision("highest"):
        xn = ref.trunk(keys, pub, seq)
        lg = np.asarray(ref.draft(keys, pub, seq, xn))
        other = seq.copy()
        other[-1] = 7  # only slot T - 2 reads its embedding
        moved = np.asarray(ref.draft(keys, pub, other, xn))
        # by hand: the module's input at the last slot has no embedding part
        mp, eps = pub["mtp"], keys["rms_norm_eps"]
        emb = np.asarray(pub["embedding"], np.float32)
        e = np.concatenate([emb[seq[1:]], np.zeros((1, emb.shape[1]))], 0)
        u = jnp.concatenate([
            ref._rms_norm(jnp.asarray(e, jnp.float32), mp["norm_e"], eps),
            ref._rms_norm(xn, mp["norm_h"], eps),
        ], -1) @ mp["w_eh"]
        lp = {k: v[0] for k, v in mp["block"].items()}
        y = ref._rms_norm(ref.layer(keys, "mla+moe", u, lp), mp["head_norm"],
                          eps)
        np.testing.assert_allclose(lg, y @ pub["lm_head"], atol=1e-5)
        loss = float(ref.draft_loss(
            keys, pub, seq, xn, mask=np.arange(len(seq) - 2) >= 2
        ))
    same = np.abs(moved - lg).max(axis=-1) == 0
    assert same[:-2].all() and not same[-2]
    logp = jax.nn.log_softmax(lg[:-2], axis=-1)[np.arange(len(seq) - 2),
                                                 seq[2:]]
    np.testing.assert_allclose(loss, -float(logp[2:].mean()), rtol=1e-5)
    got = ref.draft_token_logprobs(keys, pub, seq.tolist())
    np.testing.assert_allclose(got, logp, atol=1e-5)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_files_read_nothing_where_the_program_has_no_such_scope(
    metric
):
    """On the parent commit (no ``mla_expand`` or ``mtp`` scope) a new
    metric's reader returns None and raises nothing, with a trace and
    without."""
    recorded = os.path.join(HERE, "recorded_trace.json")
    for trace in (None, harness.load_json(recorded)
                  if os.path.exists(recorded) else None):
        obs = harness.Observation(
            window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
            values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
            trace=trace,
        )
        assert harness.read_metric(metric, obs) is None
