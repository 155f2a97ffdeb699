"""The Laguna-XS.2 configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's rules, every
new data file, one rehearsal of the cell's traffic kind, and the new metric
files and the new reader on hand-built records (and on the recorded trace,
which has none of their names: they read nothing and raise nothing).
"""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_laguna, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_laguna_swa_grpo_mixed16k"
NEW_METRICS = ["attn_window_time_share.train", "swa_kept_share.train",
               "attn_window_roofline.train", "attn_full_roofline.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cut():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "laguna-xs.2.json"
    )) as f:
        return json.load(f)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        return next(
            r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2"
        )


def test_parameters_against_the_hand_worked_490_million():
    c = _cut()
    # a mixer: q and o 2048 x H x 128 each, k and v 2048 x 1024 each, the
    # gate 2048 x H
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48
    swa = 2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64
    assert arith_laguna.mixer_params(c, 48) == full == 29_458_432
    assert arith_laguna.mixer_params(c, 64) == swa == 37_879_808
    assert arith_laguna.expert_params(c) == 3 * 2048 * 512 == 3_145_728
    assert arith_laguna.ffn_params(c, "dense") == 3 * 2048 * 8192 == 50_331_648
    sparse = 2048 * 256 + 17 * 3_145_728  # router, 16 held + the shared one
    assert arith_laguna.ffn_params(c, "moe") == sparse == 54_001_664
    norms = 2 * 2048
    layers = (
        (full + 50_331_648 + norms)  # layer 0: "79.79 M"
        + 3 * (swa + sparse + norms)  # a window expert layer: "91.88 M"
        + (full + sparse + norms)  # the full expert layer: "83.46 M"
    )
    total = layers + 2 * 12544 * 2048 + 2048  # embedding and head "51.38 M"
    assert arith_laguna.param_count(c) == total == 490_297_344
    assert round(total * 16 / 1e9, 2) == 7.84  # GB at 16 B a parameter
    assert round(total * 22 / 1e9, 2) == 10.79  # at grad_step's peak
    # and the program stores exactly that
    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == total
    # the whole model from the catalog's own keys: the published 33.4 B; an
    # element-wise gate (one number a channel) would add 0.63 B and miss it
    whole = _catalog_row()["config"]
    assert arith_laguna.experts_held(whole) == 256
    assert round(arith_laguna.param_count(whole) / 1e9, 2) == 33.44
    gate_a_channel = 2048 * 128 * (10 * 48 + 30 * 64)
    assert round(gate_a_channel / 1e9, 2) == 0.63


def test_flops_and_core_counts_against_hand_worked_values():
    c = _cut()
    assert [m for m, _, _ in arith_laguna.layers(c)] == [
        "full", "swa", "swa", "swa", "full"
    ]
    assert arith_laguna.mixer_layers(c, "swa") == 3
    assert arith_laguna.expert_pairs_per_token(c) == 0.5  # 8 x 16 / 256
    weights = (
        2 * 29_458_432 + 3 * 37_879_808  # the five mixers
        + 50_331_648  # the dense FFN
        + 4 * (2048 * 256 + 1.5 * 3_145_728)  # router, 1/2 pair + shared
        + 2048 * 12544  # the head
    )
    assert arith_laguna.trained_weights(c) == weights == 269_549_568
    # a sequence under the window attends causally; past it, 512 keys a
    # query beyond the first 512 queries
    assert arith_laguna.window_pairs(320, 512) == 320 * 321 // 2
    assert arith_laguna.window_pairs(512, 512) == 512 * 513 // 2
    assert arith_laguna.window_pairs(8192, 512) == (
        512 * 513 // 2 + (8192 - 512) * 512
    ) == sum(min(t + 1, 512) for t in range(8192))
    n = 3000
    causal, inside = n * (n + 1) // 2, 512 * 513 // 2 + (n - 512) * 512
    assert arith_laguna.attended_pairs(c, [n]) == {
        "full": causal, "swa": inside
    }
    fwd = (
        2.0 * n * weights + 2 * causal * 4 * 48 * 128
        + 3 * inside * 4 * 64 * 128
    )
    assert arith_laguna.forward_flops(c, [n]) == fwd
    assert arith_laguna.train_step_flops(c, [n]) == 4 * fwd
    # what the cores are asked for: 2 + 2 + 4 matmuls a pair and head, 2 d
    # operations each
    assert arith_laguna.core_kernel_flops(c, "swa", 1000) == (
        1000 * 64 * 2 * 128 * 8
    )
    assert arith_laguna.core_kernel_flops(c, "full", 1000) == (
        1000 * 48 * 2 * 128 * 8
    )
    # the full layers' pairs from the window layers' causal counter: two
    # layers' for three's
    assert arith_laguna.full_pairs_from_counters(c, 3 * causal) == 2 * causal
    assert arith_laguna.expert_kernel_flops(c, 256, True) == (
        5 * 2.0 * 256 * 3_145_728
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _cut()
    bench = harness.load_bench()
    entry = next(e for e in bench["configs"] if e["name"] == "laguna-xs.2")
    assert entry["reduced"] == c["reduced"]
    assert c["source"] == entry["source"]
    for key in ("published", "deployment", "assumed", "logprob_limits",
                "window_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    for said in ("router", "qk_norm", "gate", "shared_expert", "window"):
        assert said in c["assumed"], said  # the five of the issue
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: the leading dense layer + 4, 8 experts held, an eighth
    assert c["num_hidden_layers"] == 1 + 4 and c["num_local_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    # one whole period after the leading layer: window x 3, full
    assert c["layer_types"][1:] == ["sliding_attention"] * 3 + [
        "full_attention"
    ]
    row = _catalog_row()
    assert entry["source"] == row["source_url"]
    n = c["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in ("layer_types", "mlp_layer_types",
                   "num_attention_heads_per_layer"):
            assert c[key] == value[:n], key  # the depth's consequence
        elif key not in c["reduced"]:
            assert c[key] == value, key


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_swa"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "laguna"
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (16, 4, 3)
    assert p["sizes"] == {"mb_spec.max_tokens_per_mb": 16384}
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 16384
    assert p["output_len"] == {"law": "lognormal", "median": 4000,
                               "sigma": 0.8, "lo": 256, "hi": 15360}
    window = cell.config["sliding_window"]
    assert sum(n > window for n in p["check_lens"]) == 3
    assert sum(n % 128 != 0 for n in p["check_lens"]) >= 2
    for name in NEW_METRICS:
        spec = harness.load_json(
            os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        )
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    mc = from_hf_config(cell.config)
    assert mc.family == "laguna" and mc.layer_window == 512


def test_the_traffics_lengths_are_what_the_issue_says():
    """The batches' lengths (the layout's, every seed's): about 93k tokens a
    batch, sequences of 0.9k-16k, and the window keeps 11.7% of the causal
    pairs."""
    from benchmark.kinds.train_steps import make_batches

    cell = harness.load_cell(CELL)
    lens = np.concatenate([
        b["_seq_lens"] for b in make_batches(cell.traffic, 12544, seed=1)
    ])
    assert len(lens) == 48 and 800 < lens.min() and lens.max() <= 16384
    assert 90_000 < lens.sum() / 3 < 96_000
    pairs = arith_laguna.attended_pairs(cell.config, lens)
    assert round(100 * pairs["swa"] / pairs["full"], 1) == 11.7


def test_rehearsal_of_the_swa_kind_ends_in_the_contracts_line(capsys):
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
        "reference_agrees", "packing_invariant", "window_exact",
    }
    assert all(notes["checks"].values())
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
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_the_scope_roofline_reader_on_a_hand_built_record(monkeypatch):
    """2 s under ``attn_window`` and 1 s under ``attention`` over the two
    programs; a need of 197e12 operations is 1 s at the peak."""
    from benchmark import program_trace
    from benchmark.readers import scope_roofline

    record = {
        "window_s": 10.0, "devices": 1,
        "program_scopes": {
            "grad_step": {"attn_window": 1.5, "attention": 0.75, "mlp": 3.0,
                          program_trace.NO_SCOPE: 0.25},
            "logp_forward": {"attn_window": 0.5, "attention": 0.25,
                             "mlp": 1.0},
        },
    }
    monkeypatch.setattr(program_trace, "of_run", lambda obs: record)
    obs = harness.Observation(
        window_s=10.0, setup_s=1.0, trace={"window_s": 10.0},
        values={"attn_window_flops_traced": 197e12,
                "attn_full_flops_traced": 0.5 * 197e12},
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
    )
    assert harness.read_metric("attn_window_roofline.train", obs)["value"] == 50.0
    assert harness.read_metric("attn_full_roofline.train", obs)["value"] == 50.0
    assert harness.read_metric("attn_window_time_share.train", obs)["value"] == 20.0
    spec = {"scopes": ["attn_window"], "programs": ["grad_step"],
            "need": "attn_window_flops_traced", "bound": "flops"}
    assert scope_roofline.read(spec, obs) == 100.0 * 1.0 / 1.5
    # a program compiled before the scopes: most of its time under none
    record["program_scopes"]["logp_forward"] = {program_trace.NO_SCOPE: 2.0}
    assert harness.read_metric("attn_window_roofline.train", obs) is None
    # a program without the scope at all (the parent): nothing to divide by
    record["program_scopes"] = {
        "grad_step": {"attention": 1.0, "mlp": 3.0},
        "logp_forward": {"attention": 0.5, "mlp": 1.0},
    }
    assert harness.read_metric("attn_window_roofline.train", obs) is None
    obs.values.clear()  # and no count of what was needed
    assert harness.read_metric("attn_full_roofline.train", obs) is None


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the parent commit (no ``attn_window`` scope, no ``swa_*``
    counter) each new metric's reader returns None and raises nothing."""
    obs = harness.Observation(
        window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
        values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        trace=harness.load_json(os.path.join(HERE, "recorded_trace.json"))
        if os.path.exists(os.path.join(HERE, "recorded_trace.json")) else None,
    )
    for name in NEW_METRICS:
        assert harness.read_metric(name, obs) is None, name
    # and with the program's counters the kept share reads what they say
    obs.counters.update(swa_pairs=125.0, swa_causal_pairs=1000.0)
    assert harness.read_metric("swa_kept_share.train", obs)["value"] == 12.5
