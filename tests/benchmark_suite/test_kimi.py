"""The Kimi-Linear configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's rules, every
new data file, one rehearsal of the cell's traffic kind, and the new metric
files on the recorded trace (which has none of their names: they read nothing
and raise nothing).
"""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_kimi, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_kimi_kda_grpo_packed8k"
NEW_METRICS = ["kda_scan_time_share.train", "kda_prep_time_share.train",
               "kda_scan_roofline.train", "kda_chunk_fill.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _published():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json"
    )) as f:
        return json.load(f)


def test_parameters_against_the_hand_worked_602_million():
    c = _published()
    # KDA mixer: q, k, v, o 4 x 2304 x 4096; two low-rank gates through 128
    # (2304 x 128 + 128 x 4096 each); beta 2304 x 32; three convolutions of
    # 4096 x 4; A_log 32, dt_bias 4096, o_norm 128
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert arith_kimi.kda_mixer_matmul_weights(c) == kda == 39_460_864
    assert arith_kimi.kda_mixer_params(c) == kda + 3 * 4096 * 4 + 32 + 4096 + 128
    assert arith_kimi.kda_mixer_params(c) == 39_514_272  # "39.51 M"
    # MLA mixer: q 2304 x 32 x 192; kv_a 2304 x 576; kv_b 512 x 32 x 256;
    # o 4096 x 2304; the latent's norm 512
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert arith_kimi.mla_mixer_params(c) == mla + 512 == 29_114_880
    assert arith_kimi.expert_params(c) == 3 * 2304 * 1024 == 7_077_888
    assert arith_kimi.ffn_params(c, "dense") == 3 * 2304 * 9216 == 63_700_992
    sparse = 2304 * 256 + 256 + 9 * 7_077_888  # router, bias, 8 held + shared
    assert arith_kimi.ffn_params(c, "moe") == sparse == 64_291_072
    norms = 2 * 2304
    layers = (
        (39_514_272 + 63_700_992 + norms)  # layer 1: "103.2 M"
        + 3 * (39_514_272 + sparse + norms)  # a KDA expert layer: "103.8 M"
        + (29_114_880 + sparse + norms)  # the MLA expert layer: "93.4 M"
    )
    total = layers + 2 * 20480 * 2304 + 2304  # embedding and head "94.4 M"
    assert arith_kimi.param_count(c) == total == 602_434_432
    assert round(total * 16 / 1e9, 2) == 9.64  # GB at 16 B a parameter
    # and the program stores exactly that
    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == total
    # the whole model from the same keys: the name's 48B
    whole = dict(c, **c["published"])
    whole["linear_attn_config"] = dict(
        c["linear_attn_config"], **c["published"]["linear_attn_config"]
    )
    assert round(arith_kimi.param_count(whole) / 1e9, 1) == 49.1


def test_flops_and_kernel_bytes_against_hand_worked_values():
    c = _published()
    assert arith_kimi.kda_layers(c) == 4
    assert arith_kimi.expert_pairs_per_token(c) == 0.25  # 8 x 8 / 256
    weights = (
        4 * 39_460_864  # four KDA mixers
        + 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304  # the MLA one
        + 63_700_992  # the dense FFN
        + 4 * (2304 * 256 + 1.25 * 7_077_888)  # router, 1/4 pair + shared
        + 2304 * 20480  # the head
    )
    assert arith_kimi.trained_weights(c) == weights == 335_593_472
    # the rule: three products over a [128, 128] state, 32 heads
    assert arith_kimi.kda_rule_flops_per_token(c) == 6 * 32 * 128 * 128
    assert arith_kimi.mla_core_flops_per_pair(c) == 2 * 32 * (192 + 128)
    n = 3000
    fwd = (
        2.0 * n * weights + 4 * n * 3_145_728
        + (n * (n + 1) // 2) * 20_480
    )
    assert arith_kimi.forward_flops(c, [n]) == fwd
    assert arith_kimi.train_step_flops(c, [n]) == 4 * fwd
    # the state-pass kernels, a token and head in bf16: operands
    # (4 x 128 + 64) x 2 + 4 x 128 / 64 = 1160 B; output 256; state
    # 128 x 128 x 2 / 64 = 512
    fwd_b, states_b, bwd_b = 1160 + 256, 1160 + 256 + 512, 2 * 1160 + 512 + 256
    assert arith_kimi.kda_kernel_bytes(c, 1) == 32 * (
        2 * fwd_b + states_b + bwd_b
    ) == 32 * 7848
    # four products forward (6 d d + 2 C d), nine backward (14 d d + 4 C d)
    assert arith_kimi.kda_kernel_flops(c, 1) == 32 * (
        3 * (6 * 16384 + 2 * 8192) + 14 * 16384 + 4 * 8192
    ) == 32 * 606_208
    # bytes bind: 77 operations a byte against the chip's 197e12 / 819e9
    assert 606_208 / 7848 < 197e12 / 819e9
    assert arith_kimi.expert_kernel_flops(c, 256, True) == (
        5 * 2.0 * 256 * 7_077_888
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _published()
    bench = harness.load_bench()
    entry = next(e for e in bench["configs"] if e["name"] == "kimi-linear-48b-a3b")
    assert entry["reduced"] == c["reduced"]
    assert c["source"] == entry["source"]
    for key in ("published", "deployment", "assumed", "logprob_limits",
                "scan_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: the leading dense layer + 4, 8 experts held, an eighth
    assert c["num_hidden_layers"] == c["first_k_dense_replace"] + 4
    assert c["num_local_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    la = c["linear_attn_config"]  # one whole period: 3 KDA to 1 full
    assert (la["kda_layers"], la["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "Kimi-Linear" in line)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "linear_attn_config":  # the group, but for the two lists
            same = {k: v for k, v in value.items() if not k.endswith("_layers")}
            assert {k: c[key][k] for k in same} == same
        elif key not in c["reduced"]:
            assert c[key] == value, key


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_kda"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "kimi_linear"
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (16, 4, 3)
    assert p["sizes"] == {"mb_spec.max_tokens_per_mb": 8192}
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 8192
    assert sum(n % 64 != 0 for n in p["check_lens"]) >= 2
    for name in NEW_METRICS:
        spec = harness.load_json(
            os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        )
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    mc = from_hf_config(cell.config)
    assert mc.family == "kimi_linear" and len(mc.layer_kinds) == 5


def test_rehearsal_of_the_kda_kind_ends_in_the_contracts_line(capsys):
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
        "reference_agrees", "packing_invariant", "scan_exact",
    }
    assert all(notes["checks"].values())
    scan = notes["notes"]["scan"]
    assert scan["layers"] == 3 and scan["mean_rel"] < 1e-5
    counted = notes["notes"]["counted"]
    assert counted["kda_tokens"] == scan["window_expected"]["kda_tokens"] > 0
    assert counted["kda_segments"] == scan["window_expected"]["kda_segments"]
    assert counted["kda_chunk_slots"] >= counted["kda_tokens"]
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the parent commit (no ``kda_*`` scope, kernel or counter) each new
    metric's reader returns None and raises nothing."""
    obs = harness.Observation(
        window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
        values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        trace=harness.load_json(os.path.join(HERE, "recorded_trace.json"))
        if os.path.exists(os.path.join(HERE, "recorded_trace.json")) else None,
    )
    for name in NEW_METRICS:
        assert harness.read_metric(name, obs) is None, name
    # and with the program's counters the fill reads what they say
    obs.counters.update(kda_tokens=300.0, kda_chunk_slots=400.0)
    assert harness.read_metric("kda_chunk_fill.train", obs)["value"] == 75.0
