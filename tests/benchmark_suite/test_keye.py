"""The Keye-VL-2.0 configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's rules, the
benchmark's reference against the tests' twin, one rehearsal of the cell's
traffic kind, and the new metric files on the recorded trace (which has none
of their names: they read nothing and raise nothing).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_keye, harness, reference_keye
from benchmark.kinds import train_steps_dsa

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))  # tests/: the reference's twin
CELL = "train_keye_dsa_grpo_long"
NEW_METRICS = ["dsa_index_time_share.train", "dsa_select_time_share.train",
               "sparse_attn_roofline.train", "dsa_kept_share.train"]


def _published():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json"
    )) as f:
        return json.load(f)


@pytest.mark.parametrize("n,want", [
    (1000, 500_500),  # within topk: every causal pair, 1000 * 1001 / 2
    (2048, 2_098_176),  # exactly topk: 2048 * 2049 / 2
    (7000, 2_098_176 + 4952 * 2048),  # = 12,239,872: "12.2 M of 24.5 M"
    (16384, 2_098_176 + 14336 * 2048),  # = 31,458,304: "31.5 M of 134 M"
])
def test_selected_pairs_against_hand_worked_counts(n, want):
    assert arith_keye.selected_pairs(n, 2048) == want
    assert arith_keye.causal_pairs(n) == n * (n + 1) // 2
    assert arith_keye.selected_pairs(n, 2048) <= arith_keye.causal_pairs(n)


def test_flops_of_one_layer_against_hand_worked_values():
    c = dict(_published(), num_hidden_layers=1, vocab_size=0)
    # attention projections 2048 x (4096 + 512 + 512) + 4096 x 2048
    attn = 2048 * 5120 + 4096 * 2048
    assert attn == 18_874_368
    # indexer 2048 x (16 x 64 + 64 + 16); router 2048 x 128; one expert pair
    # in expectation (8 x 16 / 128) of 3 x 2048 x 768
    assert arith_keye.indexer_weights_per_layer(c) == 2_260_992
    assert arith_keye.expert_pairs_per_token(c) == 1.0
    weights = attn + 2048 * 128 + 3 * 2048 * 768
    assert arith_keye.trained_weights(c) == weights == 23_855_104
    n = 7000
    sel, cau = 12_239_872, 24_503_500
    core = sel * 4 * 32 * 128  # QK^T and PV, 2 flops a multiply-add
    index = cau * 2 * 16 * 64
    fwd = 2.0 * n * (weights + 2_260_992) + core + index
    assert arith_keye.forward_flops(c, [n]) == fwd
    indexer = 2.0 * n * 2_260_992 + index
    assert arith_keye.train_step_flops(c, [n]) == 4 * (fwd - indexer) + 2 * indexer
    # the kernels: forward twice (recompute, forward) and a backward of 2x
    assert arith_keye.sparse_core_kernel_flops(c, sel) == 4 * core
    assert arith_keye.index_kernel_flops(c, cau, True) == 3 * index
    assert arith_keye.expert_kernel_flops(c, 1024, True) == (
        5 * 2.0 * 1024 * 3 * 2048 * 768
    )
    assert arith_keye.sparse_core_kernel_bytes(c, 16384) == 4 * (
        16384 * (2 * 4096 + 2 * 512) * 2 + 4 * 16384 * 16385 // 2
    )


def test_param_count_is_the_programs_and_the_configuration_files():
    c = _published()
    cfg = from_hf_config(c)
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert arith_keye.param_count(c) == n == 562_289_920
    # 16 B a parameter as trained: over half of a 16 GiB chip
    assert 0.5 < n * 16 / 2 ** 34 < 0.62


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _published()
    cfg = from_hf_config(c)
    assert c["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_local_experts": 128,
                              "vocab_size": 151936}
    # no width moved: the whole sa_config and every size as published
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048,
    }
    assert (c["hidden_size"], c["moe_intermediate_size"], c["head_dim"],
            c["num_experts"], c["num_experts_per_tok"]) == (2048, 768, 128, 128, 8)
    assert cfg.family == "KeyeVL2" and cfg.has_indexer and cfg.is_moe
    assert (cfg.num_experts, cfg.num_held_experts) == (128, 16)
    assert cfg.mrope_sections == (16, 24, 24) and cfg.qk_norm == "head"
    # floors of a model_config cut: >= 5 layers, >= 8 experts, >= 1/8 vocabulary
    assert cfg.num_layers >= 5 and cfg.num_held_experts >= 8
    assert cfg.vocab_size * 8 >= 151936
    for key in ("indexer_input", "ties", "router_aux_loss_coef", "weights"):
        assert key in c["assumed"]
    assert 0 < c["logprob_limits"]["mean"] < 0.05 < c["logprob_limits"]["worst"] < 2
    assert 0.5 < c["selection_limits"]["agree_share"] <= 1
    # the published file, with all experts held, is the whole layer
    whole = from_hf_config(dict(c, num_local_experts=128))
    assert whole.experts_held == 0 and whole.num_held_experts == 128


def test_the_benchmarks_reference_and_the_tests_twin_agree():
    """Two writings of the six steps, one in blocks: the same logits and the
    same selections on the tiny family (float32, order of sums only)."""
    import keye_reference as twin

    cfg, ref_cfg = train_steps_dsa.tiny_configs()
    params = init_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    for k in ("w_idx_q", "w_idx_k", "w_idx_w", "w_router"):
        params["layers"][k] = params["layers"][k] * 10.0
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 37)
    np.testing.assert_allclose(
        reference_keye.logits(ref_cfg, params, tokens),
        twin.logits(ref_cfg, params, tokens), atol=1e-4, rtol=0,
    )
    kept = reference_keye.selections(ref_cfg, params, tokens)
    assert len(kept) == cfg.num_layers
    for h, mask in kept:
        assert h.shape == (37, cfg.hidden_size)
        assert (mask.sum(1) == np.minimum(np.arange(37) + 1, cfg.dsa_topk)).all()
    lp = {k: v[0] for k, v in params["layers"].items()}
    assert (np.asarray(twin.selection(ref_cfg, jnp.asarray(kept[0][0]), lp))
            == kept[0][1]).all()


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "keye-vl-2.0-30b-a3b"
    assert cell.traffic["kind"] == "train_steps_dsa" and not cell.traffic["sizes"]
    assert harness.load_kind(cell.traffic["kind"]) is train_steps_dsa
    assert cell.end_to_end == ["trained_tokens_per_s", "setup_s"]
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert {"train_mfu", "attn_time_share.train", "moe_experts_roofline.train",
            "expert_load_imbalance.train"} <= set(cell.per_layer)
    lens = cell.traffic["check_lens"]
    assert sum(n > 2048 for n in lens) == 3 and max(lens) == 8192
    out = cell.traffic["output_len"]
    assert out["hi"] + cell.traffic["prompt_len"]["hi"] == 16384


def test_rehearsal_of_the_dsa_kind_ends_in_the_contracts_line(capsys):
    from benchmark import run

    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 31),
                   "--seconds", "2", "--trace", "1", "--rehearse"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and len(lines) >= 2
    notes, line = json.loads(lines[-2]), json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert "rehearsal" in line and line["failed"] == 0 < line["attempted"]
    checks = dict(notes["checks"])
    checks.pop("no_compile_in_window")  # the chip's business
    assert set(checks) == {
        "finite_loss_and_grad_norm", "reference_agrees", "packing_invariant",
        "selection_exact",
    } and all(checks.values()), notes
    n = notes["notes"]
    assert n["reference"]["max_abs_diff"] < 1e-3
    assert n["packing"]["max_abs_diff"] < 1e-3
    sel = n["selection"]
    assert sel["kept"] == sel["kept_expected"] > 0
    assert sel["choices"] > 0 and sel["agree_share"] >= 0.999
    assert n["counted"]["dsa_kept_pairs"] == sel["window_pairs_expected"]["kept"]
    assert n["counted"]["dsa_causal_pairs"] == sel["window_pairs_expected"]["causal"]
    kept = line["metrics"]["dsa_kept_share.train"]["value"]
    assert kept == pytest.approx(
        100 * n["counted"]["dsa_kept_pairs"] / n["counted"]["dsa_causal_pairs"]
    ) and 0 < kept < 100
    # 8 experts held of 16, 2 a token: about half the pairs are routed here
    assert 0 < n["counted"]["moe_assignments"] < n["trained_tokens"] * 2 * 2
    assert 1.0 <= line["metrics"]["expert_load_imbalance.train"]["value"] <= 8.0
    # no peak table and no device trace off the chip: those leave themselves out
    assert not {"train_mfu", "sparse_attn_roofline.train",
                "dsa_index_time_share.train"} & set(line["metrics"])


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the recorded chip trace (a program from before this PR) and on an
    empty observation every new metric is None, not an error; given the
    names, the roofline reads what the arithmetic says."""
    from benchmark import program_trace  # noqa: F401  (the readers' import)

    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    old = harness.Observation(
        window_s=10.0, setup_s=1.0, trace=recorded, peaks=peaks,
        counters={"trained_tokens": 1.0},
    )
    bare = harness.Observation(window_s=10.0, setup_s=1.0)
    for name in NEW_METRICS:
        assert harness.read_metric(name, old) is None, name
        assert harness.read_metric(name, bare) is None, name
    ops = {"dsa_core_fwd.3": 0.2, "dsa_core_dq.1": 0.3, "dsa_core_dkv.1": 0.5,
           "fusion.9": 2.0, "gmm.1": 0.4}
    obs = harness.Observation(
        window_s=10.0, setup_s=1.0, peaks=peaks,
        values={"sparse_core_flops_traced": 0.25 * 197e12},
        trace={"window_s": 10.0, "busy_s": 9.0, "op_seconds": ops},
        counters={"dsa_kept_pairs": 30.0, "dsa_causal_pairs": 120.0},
    )
    roof = harness.read_metric("sparse_attn_roofline.train", obs)
    assert roof["value"] == pytest.approx(25.0) and roof["unit"] == "%"
    assert harness.read_metric("dsa_kept_share.train", obs)["value"] == 25.0
