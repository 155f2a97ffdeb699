"""The Qwen3-Next configuration's benchmark files: its arithmetic against
hand-worked counts and the program's own leaves, the configuration file
against the catalog's row, every new data file, one rehearsal of the cell's
traffic kind, and the new metric file on a record that has no such scope (it
reads nothing and raises nothing).
"""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_qwen3next as arith
from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_qwen3next_gdn_grpo_long16k"
CONFIG = "qwen3-next-80b-a3b"
NEW_METRIC = "gdn_operands_time_share.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _published():
    with open(os.path.join(
        ROOT, "benchmark", "configs", CONFIG + ".json"
    )) as f:
        return json.load(f)


def test_parameters_against_the_hand_worked_424_million():
    c = _published()
    # gdn mixer: W_qkvz 2048 x (2 x 2048 + 2 x 4096), W_ba 2048 x 64, one
    # convolution of 8192 x 4, A_log + dt_bias 64, output norm 128, W_out
    qkvz, ba, out = 2048 * 12288, 2048 * 64, 4096 * 2048
    assert (qkvz, ba, out) == (25_165_824, 131_072, 8_388_608)
    assert arith.gdn_mixer_matmul_weights(c) == qkvz + ba + out
    assert arith.gdn_mixer_params(c) == (
        qkvz + ba + out + 32_768 + 64 + 128
    ) == 33_718_464
    # full mixer: q with its gate 2048 x 16 x 512, k and v 2048 x 512 each,
    # o 4096 x 2048, two head norms of 256
    assert arith.full_mixer_params(c) == (
        16_777_216 + 2 * 1_048_576 + 8_388_608 + 512
    ) == 27_263_488
    assert arith.expert_params(c) == 3 * 2048 * 512 == 3_145_728
    assert arith.shared_expert_params(c) == 3_145_728 + 2_048
    ffn = 2048 * 512 + 16 * 3_145_728 + 3_145_728 + 2_048
    assert arith.ffn_params(c) == ffn == 54_528_000
    gdn_layer, full_layer = 33_718_464 + ffn + 4096, 27_263_488 + ffn + 4096
    assert (gdn_layer, full_layer) == (88_250_560, 81_795_584)
    layers = 3 * gdn_layer + full_layer
    assert layers == 346_547_264
    total = layers + 2 * 18_992 * 2048 + 2048  # embedding and head 77.8 M
    assert arith.param_count(c) == total == 424_340_544
    assert round(total * 16 / 1e9, 2) == 6.79  # GB at 16 B a parameter
    assert round(100 * total * 16 / 17_179_869_184, 1) == 39.5
    # and the program stores exactly that, leaf by leaf
    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == total
    gdn = shapes["layers"]["gdn+moe"]
    assert gdn["w_qkvz"].shape == (3, 2048, 12288)
    assert gdn["conv_qkv"].shape == (3, 8192, 4)
    assert gdn["a_log"].shape == gdn["dt_bias"].shape == (3, 32)
    assert gdn["w_gate"].shape == (3, 16, 2048, 512)
    assert shapes["layers"]["full+moe"]["wq"].shape == (1, 2048, 8192)
    # thirty-two held, Kimi's footing: 625.7 M = 10.0 GB
    more = arith.param_count(dict(c, num_local_experts=32))
    assert round(more / 1e6, 1) == 625.7 and round(more * 16 / 1e9, 1) == 10.0
    # the whole model from the same keys: the name's 80B
    assert round(arith.param_count(dict(c, **c["published"])) / 1e9, 1) == 79.7


def test_flops_and_kernel_counts_against_hand_worked_values():
    c = _published()
    assert (arith.gdn_layers(c), arith.full_layers(c)) == (3, 1)
    assert arith.layer_kinds(c) == ["gdn", "gdn", "gdn", "full"]
    assert arith.expert_pairs_per_token(c) == 10 * 16 / 512 == 0.3125
    sparse = 2048 * 512 + 0.3125 * 3_145_728 + 3_145_728 + 2048
    weights = (
        3 * (25_165_824 + 131_072 + 8_388_608)  # three gdn mixers
        + 16_777_216 + 2 * 1_048_576 + 8_388_608  # the full one
        + 4 * sparse + 2048 * 18_992  # router, 5/16 pair, shared; the head
    )
    assert arith.trained_weights(c) == weights
    # the rule: three products over a [128, 128] state, 32 value heads
    assert arith.gdn_rule_flops_per_token(c) == 6 * 32 * 128 * 128
    assert arith.core_flops_per_pair(c) == 4 * 16 * 256
    n = 3000
    fwd = (
        2.0 * n * weights + 3 * n * 3_145_728 + (n * (n + 1) // 2) * 16_384
    )
    assert arith.forward_flops(c, [n]) == fwd
    # four minibatches: the first takes its own forward's logprobs
    assert arith.recompute_forwards(4) == 0.75
    assert arith.train_step_flops(c, [n], 4) == 3.75 * fwd
    # the state pass, a token and VALUE head in bf16, as arith_kimi counts
    # it (the scalar-decay form hands the kernels the same operands):
    # operands (4 x 128 + 64) x 2 + 4 x 128 / 64 = 1160 B; output 256; state
    # 128 x 128 x 2 / 64 = 512
    fwd_b, states_b, bwd_b = 1160 + 256, 1160 + 256 + 512, 2 * 1160 + 512 + 256
    assert arith.kda_kernel_bytes(c, 1, 4) == 32 * (
        1.75 * fwd_b + states_b + bwd_b
    ) == 32 * 7494
    # the core at 256: 2 matmuls of 2 x 256 a pair and head in 1.75
    # forwards, 4 in the backward
    assert arith.core_kernel_flops(c, 1, 4) == 16 * 512 * (2 * 1.75 + 4)
    assert arith.expert_kernel_flops(c, 320, True, 4) == (
        4.75 * 2.0 * 320 * 3_145_728
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
                "scan_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: one whole period of four layers, 16 experts held, an eighth
    assert c["num_hidden_layers"] == c["full_attention_interval"] == 4
    assert c["num_local_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert "32 chips share each layer" in c["deployment"]
    assert "424,340,544" in c["reduced_note"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        row = next(
            json.loads(line) for line in f
            if '"Qwen3-Next-80B-A3B-Instruct"' in line
        )
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_gdn"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "qwen3_next"
    assert {
        NEW_METRIC, "kda_scan_time_share.train", "kda_prep_time_share.train",
        "kda_scan_roofline.train", "kda_chunk_fill.train", "train_mfu",
        "attn_full_roofline.train", "attn_gate_time_share.train",
        "moe_held_fill.train", "moe_experts_roofline.train",
        "rope_time_share.train", "unscoped_time_share.train",
    } <= set(cell.per_layer)
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (16, 4, 3)
    assert p["sizes"] == {} and p["trace_steps"] == 2
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 16384
    assert p["output_len"] == {
        "law": "lognormal", "median": 5000, "sigma": 0.7, "lo": 512,
        "hi": 15360,
    }
    assert p["check_lens"] == [8192, 4099, 2304, 640]
    assert sum(n % 64 != 0 for n in p["check_lens"]) == 1
    assert sum(n > 2048 for n in p["check_lens"]) == 3
    spec = harness.load_json(
        os.path.join(ROOT, "benchmark", "metrics", NEW_METRIC + ".json")
    )
    assert spec["scopes"] == ["gdn_operands"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", spec["reader"] + ".py"
    ))
    from areal_tpu.utils.tracing import DEVICE_SCOPES

    assert "gdn_operands" in DEVICE_SCOPES
    mc = from_hf_config(cell.config)
    assert mc.family == "qwen3_next" and len(mc.layer_kinds) == 4


def test_rehearsal_of_the_gdn_kind_ends_in_the_contracts_line(capsys):
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
    assert scan["layers"] == 3 and scan["decay"] == "scalar"
    assert scan["mean_rel"] < 1e-5
    counted = notes["notes"]["counted"]
    assert counted["kda_tokens"] == scan["window_expected"]["kda_tokens"] > 0
    assert counted["kda_segments"] == scan["window_expected"]["kda_segments"]
    assert counted["kda_chunk_slots"] >= counted["kda_tokens"]
    assert counted["moe_stream_rows"] >= counted["moe_assignments"] > 0
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_new_metric_file_reads_nothing_where_the_program_has_no_such_scope():
    """On the parent commit (no ``gdn_operands`` scope) the new metric's
    reader returns None and raises nothing, with a trace and without."""
    recorded = os.path.join(HERE, "recorded_trace.json")
    for trace in (None, harness.load_json(recorded)
                  if os.path.exists(recorded) else None):
        obs = harness.Observation(
            window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
            values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
            trace=trace,
        )
        assert harness.read_metric(NEW_METRIC, obs) is None
