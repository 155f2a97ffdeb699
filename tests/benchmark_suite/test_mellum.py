"""The Mellum2 configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's rules, the
traffic file's lengths under its ``layout_seed``, one rehearsal of the cell's
traffic kind with ``correct`` true, and one with a window of the wrong
length, which has to read false.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from benchmark import arith, arith_mellum, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_mellum_swa_grpo_long32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHECKS = {
    "finite_loss_and_grad_norm", "no_compile_in_window", "reference_agrees",
    "packing_invariant", "window_exact",
}


def _cut():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "mellum2-12b-a2.5b.json"
    )) as f:
        return json.load(f)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        return next(
            r for r in map(json.loads, f)
            if r["name"] == "Mellum2-12B-A2.5B-Instruct"
        )


def test_parameters_against_the_hand_worked_340_million():
    c = _cut()
    # a mixer of either kind: q and o 2304 x 32 x 128 each, k and v 2304 x
    # 512 each; the q and k norms are 2 x 128 more, stored, no matmul
    mixer = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert arith_mellum.mixer_params(c) == mixer == 21_233_664
    assert arith_mellum.expert_params(c) == 3 * 2304 * 896 == 6_193_152
    sparse = 2304 * 64 + 8 * 6_193_152  # the router's 64, 8 held
    assert arith_mellum.ffn_params(c, "moe") == sparse == 49_692_672
    assert arith_mellum.ffn_params(c, "dense") == 3 * 2304 * 7168
    layer = mixer + 256 + sparse + 2 * 2304  # "70.93 M"
    total = 4 * layer + 2 * 12288 * 2304 + 2304  # embedding, head "56.6 M"
    assert arith_mellum.param_count(c) == total == 340_350_208
    assert round(total * 16 / 1e9, 2) == 5.45  # GB at 16 B a parameter
    assert round(100 * total * 16 / 17_179_869_184, 1) == 31.7
    # 16 held (the issue's alternative): + 199 M
    more = arith_mellum.param_count(dict(c, num_local_experts=16)) - total
    assert more == 4 * 8 * 6_193_152 == 198_180_864


def test_flops_and_core_counts_against_hand_worked_values():
    c = _cut()
    assert arith_mellum.layers(c) == [("swa", "moe")] * 3 + [("full", "moe")]
    assert arith_mellum.mixer_layers(c, "swa") == 3
    assert arith_mellum.mixer_layers(c, "full") == 1
    assert arith_mellum.experts_held(c) == 8
    assert arith_mellum.expert_pairs_per_token(c) == 1.0  # 8 x 8 / 64
    weights = (
        4 * 21_233_664  # the four mixers
        + 4 * (2304 * 64 + 1.0 * 6_193_152)  # router, one pair a token
        + 2304 * 12288  # the head
    )
    assert arith_mellum.trained_weights(c) == weights == 138_608_640
    # the issue's 278 MFLOP of matmuls a token's forward
    assert round(2 * weights / 1e6) == 277
    assert arith_mellum.window_pairs(640, 1024) == 640 * 641 // 2
    assert arith_mellum.window_pairs(1024, 1024) == 1024 * 1025 // 2
    assert arith_mellum.window_pairs(20480, 1024) == (
        1024 * 1025 // 2 + (20480 - 1024) * 1024
    ) == sum(min(t + 1, 1024) for t in range(20480))
    n = 5000
    causal, inside = n * (n + 1) // 2, 1024 * 1025 // 2 + (n - 1024) * 1024
    assert arith_mellum.attended_pairs(c, [n]) == {
        "full": causal, "swa": inside
    }
    per_pair = 4 * 32 * 128
    assert arith_mellum.core_flops_per_pair(c) == per_pair
    fwd = 2.0 * n * weights + (causal + 3 * inside) * per_pair
    assert arith_mellum.forward_flops(c, [n]) == fwd
    # the forwards the chip runs: minibatches 2..n recompute
    assert arith_mellum.recompute_forwards(4) == 0.75
    assert arith_mellum.recompute_forwards(1) == 0.0
    assert arith_mellum.train_step_flops(c, [n], 4) == 3.75 * fwd
    assert arith_mellum.train_step_flops(c, [n], 1) == 3.0 * fwd
    # what the cores are asked for: 2 x 1.75 + 4 matmuls a pair and head
    assert arith_mellum.core_kernel_flops(c, 1000, 4) == (
        1000 * 32 * 2 * 128 * 7.5
    )
    # the full layer's pairs from the window layers' causal counter: one
    # layer's for three's
    assert arith_mellum.full_pairs_from_counters(c, 3 * causal) == causal
    assert arith_mellum.expert_kernel_flops(c, 256, True, 4) == (
        4.75 * 2.0 * 256 * 6_193_152
    )
    assert arith_mellum.expert_kernel_flops(c, 256, False, 4) == (
        3.75 * 2.0 * 256 * 6_193_152
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _cut()
    bench = harness.load_bench()
    entry = next(
        e for e in bench["configs"] if e["name"] == "mellum2-12b-a2.5b"
    )
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_local_experts", "vocab_size",
    ]
    assert c["source"] == entry["source"]
    for key in ("published", "deployment", "assumed", "logprob_limits",
                "window_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    for said in ("qk_norm", "window", "rotation", "router", "mtp",
                 "router_aux_loss_coef", "weights"):
        assert said in c["assumed"], said
    assert c["published"]["num_hidden_layers"] == 28
    assert c["published"]["num_local_experts"] == 64
    assert "8 chips share each layer" in c["deployment"]
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: a whole period of four, 8 experts held, an eighth
    assert c["num_hidden_layers"] == 4 and c["num_local_experts"] == 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"] == 98304
    assert c["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert c["kv_bytes_per_token"] == arith.kv_bytes_per_token(c) == 8192
    row = _catalog_row()
    assert entry["source"] == row["source_url"]
    n = c["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in ("layer_types", "mlp_layer_types"):
            assert c[key] == value[:n], key  # the depth's consequence
        elif key not in c["reduced"]:
            assert c[key] == value, key
    # every published width, as the program reads them
    mc = from_hf_config(c)
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim) == (
        2304, 32, 4, 128
    )
    assert (mc.layer_window, mc.expert_ffn_size) == (1024, 896)
    assert (mc.num_experts, mc.num_experts_per_tok) == (64, 8)


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_swa_long"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "mellum"
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    assert {
        "train_mfu", "attn_window_roofline.train", "attn_full_roofline.train",
        "attn_window_time_share.train", "swa_kept_share.train",
        "moe_held_fill.train", "moe_experts_roofline.train",
        "expert_load_imbalance.train", "setup_programs.train",
    } <= set(cell.per_layer)
    assert not {"train_mfu_per_chip.train", "collective_time_share.train",
                "cca_mix_time_share.train"} & set(cell.per_layer)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (16, 4, 3)
    assert p["sizes"] == {"mb_spec.max_tokens_per_mb": 32768}
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 32768
    assert p["output_len"] == {"law": "lognormal", "median": 6000,
                               "sigma": 0.9, "lo": 512, "hi": 30720}
    assert p["check_lens"] == [20480, 8195, 2051, 640]
    assert sum(p["check_lens"]) <= 32768  # ONE row of the trainer's
    window = cell.config["sliding_window"]
    assert sum(n > window for n in p["check_lens"]) == 3
    assert sum(n > 8192 for n in p["check_lens"]) == 2  # past yarn's original
    assert sum(n % 128 != 0 for n in p["check_lens"]) >= 2
    mc = from_hf_config(cell.config)
    assert mc.family == "mellum" and mc.layer_window == 1024


def test_the_traffics_lengths_are_what_the_issue_says():
    """The batches' lengths (the layout's, every seed's): about 155k tokens a
    batch, sequences of 1k-32k, at least one past 16,384 in each batch, and
    the window keeps about an eighth of the causal pairs."""
    from benchmark.kinds.train_steps import make_batches

    cell = harness.load_cell(CELL)
    batches = make_batches(cell.traffic, 12288, seed=1)
    again = make_batches(cell.traffic, 12288, seed=2)
    lens = np.concatenate([b["_seq_lens"] for b in batches])
    assert len(lens) == 48 and 1024 <= lens.min() and lens.max() <= 32768
    assert lens.max() > 32000  # a row of its own
    assert 150_000 < lens.sum() / 3 < 160_000
    past = [int((b["_seq_lens"] > 16384).sum()) for b in batches]
    assert past == [1, 4, 3]
    for a, b in zip(batches, again):  # the layout's, whatever the seed
        assert list(a["_seq_lens"]) == list(b["_seq_lens"])
    # the token-weighted mean length: about 15k
    assert 14_000 < (lens ** 2).sum() / lens.sum() < 17_000
    pairs = arith_mellum.attended_pairs(cell.config, lens)
    assert round(100 * pairs["swa"] / pairs["full"], 1) == 12.8


def _rehearse(capsys):
    from benchmark import run

    assert run.main([
        "--workload", CELL, "--seed", "3000000001", "--seconds", "1",
        "--trace", "0", "--rehearse",
    ]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_rehearsal_of_the_kind_ends_in_the_contracts_line(capsys):
    notes, line = _rehearse(capsys)
    assert line["correct"] is True and "rehearsal" in line
    assert set(notes["checks"]) == CHECKS and all(notes["checks"].values())
    win = notes["notes"]["window"]
    assert win["layers"] == 3 and win["mean_rel"] < 1e-5
    edge = win["edge"]
    assert edge["queries_inside_changed"] == edge["window"] == 8
    assert edge["queries_outside_changed"] == 0
    counted = notes["notes"]["counted"]
    assert counted["swa_pairs"] == win["window_expected"]["swa_pairs"] > 0
    assert counted["swa_causal_pairs"] == (
        win["window_expected"]["swa_causal_pairs"]
    ) > counted["swa_pairs"]
    assert counted["moe_stream_rows"] >= counted["moe_assignments"] > 0
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_a_window_one_key_short_reads_not_correct(capsys, monkeypatch):
    """The program attends 7 keys where the configuration says 8 (1,023 for
    1,024): the logprobs leave the reference's, the edge moves 7 queries
    and the pairs counted are not the lengths'."""
    kind = harness.load_kind("train_steps_swa_long")
    tiny = kind.tiny_configs

    def short_window():
        cfg, keys = tiny()
        short = dataclasses.replace(
            cfg, swa=dataclasses.replace(cfg.swa, window=cfg.swa.window - 1)
        )
        return short, keys

    monkeypatch.setattr(kind, "tiny_configs", short_window)
    notes, line = _rehearse(capsys)
    assert line["correct"] is False
    checks = notes["checks"]
    assert checks["window_exact"] is False
    assert checks["reference_agrees"] is False
    assert checks["finite_loss_and_grad_norm"] and checks["packing_invariant"]
    win = notes["notes"]["window"]
    assert win["edge"]["queries_inside_changed"] == 8  # the core is handed 8
    assert notes["notes"]["counted"]["swa_pairs"] < (
        win["window_expected"]["swa_pairs"]
    )
