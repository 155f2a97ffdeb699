"""The ZAYA1-8B configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's rules, every
new data file, the traffic's lengths, one rehearsal of the cell's traffic
kind, and the new metric files on hand-built records (and on a record that
has none of their names: they read nothing and raise nothing).
"""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from benchmark import arith_zaya, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_zaya_cca_grpo_packed16k"
NEW_METRICS = ["cca_mix_time_share.train", "moe_held_fill.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cut():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "zaya1-8b.json"
    )) as f:
        return json.load(f)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")


def test_parameters_against_the_hand_worked_494_8_million():
    c = _cut()
    # the mixer: q and o 2048 x 1024 each, k 2048 x 256, two value heads of
    # 2048 x 128, a tap a channel (1280 x 2) and its bias, a [128, 128]
    # matrix a head and tap (10 x 2) and its bias, a temperature a key head
    mixer = (
        2 * 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128
        + 1280 * 2 + 1280 + 10 * 2 * 128 * 128 + 1280 + 2
    )
    assert arith_zaya.mixer_params(c) == mixer == 5_575_682
    # the router: 2048 -> 256, two 256 x 256, 256 -> 16, their four biases,
    # the norm, the carried scalar, the balancing bias
    router = (
        2048 * 256 + 2 * 256 * 256 + 256 * 16 + 3 * 256 + 16 + 256 + 1 + 16
    )
    assert arith_zaya.router_params(c) == router == 660_513
    assert arith_zaya.expert_params(c) == 3 * 2048 * 2048 == 12_582_912
    held_layer = mixer + router + 8 * 12_582_912 + 10 * 2048
    assert arith_zaya.layer_params(c) == held_layer == 106_919_971
    table = 32784 * 2048
    assert arith_zaya.table_params(c) == table == 67_141_632
    total = 4 * held_layer + table + 2048
    assert arith_zaya.param_count(c) == total == 494_823_564
    assert round(total * 16 / 1e9, 2) == 7.92  # GB at 16 B a parameter
    assert round(total * 22 / 1e9, 2) == 10.89  # at grad_step's peak
    # and the program stores exactly that
    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == total
    # the whole model from the catalog's own keys: the published 8.3 B
    # beside the table and 0.76 B active a token
    whole = _catalog_row()["config"]
    assert arith_zaya.experts_held(whole) == 16
    beside = arith_zaya.param_count(whole) - arith_zaya.table_params(whole)
    assert beside == 40 * 207_583_267 + 2048 and round(beside / 1e9, 2) == 8.30
    assert round(arith_zaya.table_params(whole) / 1e9, 3) == 0.537
    assert arith_zaya.active_params(whole) == 40 * (
        mixer + router + 12_582_912 + 10 * 2048
    ) == 753_583_480


def test_flops_and_kernel_counts_against_hand_worked_values():
    c = _cut()
    assert arith_zaya.expert_pairs_per_token(c) == 0.5  # 1 x 8 / 16
    mixer = 2 * 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 10 * 2 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    weights = 4 * (mixer + router + 0.5 * 12_582_912) + 32784 * 2048
    assert arith_zaya.trained_weights(c) == weights == 117_227_520
    n = 3000
    causal = n * (n + 1) // 2
    assert arith_zaya.attended_pairs([n, 2]) == causal + 3
    assert arith_zaya.starts([n, 2, 1]) == 3
    fwd = 2.0 * n * weights + 4 * causal * 4 * 8 * 128
    assert arith_zaya.forward_flops(c, [n]) == fwd
    assert arith_zaya.train_step_flops(c, [n]) == 4 * fwd
    # what the core is asked for: 2 + 2 + 4 matmuls a pair and query head,
    # 2 d operations each
    assert arith_zaya.core_kernel_flops(c, 1000) == 1000 * 8 * 2 * 128 * 8
    # the grouped matmuls: logprob forward, forward, the forward again under
    # checkpointing, two products a matmul backward
    assert arith_zaya.expert_kernel_flops(c, 256, True) == (
        5 * 2.0 * 256 * 12_582_912
    )
    assert arith_zaya.expert_kernel_flops(c, 256, False) == (
        4 * 2.0 * 256 * 12_582_912
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _cut()
    bench = harness.load_bench()
    entry = next(e for e in bench["configs"] if e["name"] == "zaya1-8b")
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"
    ]
    assert c["source"] == entry["source"]
    for key in ("published", "deployment", "assumed", "logprob_limits",
                "mix_limits", "memory_reading", "reduced_note"):
        assert c[key], key
    for said in ("modelling_code", "convolutions", "temperature", "norm",
                 "mean", "values", "rotation", "router", "balancing_bias",
                 "residual", "weights", "router_aux_loss_coef"):
        assert said in c["assumed"], said
    assert "0.5" in c["assumed"]["weights"]  # the carried scalar's start
    forbidden = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in c["reduced"] if any(w in k for w in forbidden)]
    # the floors: four layers, 8 experts held, an eighth of the vocabulary
    assert c["num_hidden_layers"] == 4 and c["num_local_experts"] == 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert "8 chips share each layer" in c["deployment"]
    row = _catalog_row()
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "layer_types":
            assert c[key] == value[:4], key  # the depth's consequence
        elif key not in c["reduced"]:
            assert c[key] == value, key
    for limits in (c["logprob_limits"], c["mix_limits"]):
        assert all(v > 0 for k, v in limits.items() if k != "why")
        assert len(limits["why"]) > 100


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_cca"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "zaya"
    assert set(NEW_METRICS) <= set(cell.per_layer)
    # the core is full causal attention under ``attention``: the layer the
    # accepted ``attn_full_roofline.train`` reads, with this model's count
    assert {"train_mfu", "moe_experts_roofline.train", "pad_share.train",
            "expert_load_imbalance.train", "attn_full_roofline.train"} <= set(
        cell.per_layer
    )
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (32, 4, 3)
    assert p["sizes"] == {"mb_spec.max_tokens_per_mb": 16384}
    assert p["prompt_len"] == {"law": "uniform", "lo": 256, "hi": 1024}
    assert p["output_len"] == {"law": "lognormal", "median": 2500,
                               "sigma": 0.8, "lo": 256, "hi": 7168}
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 8192
    assert p["check_lens"] == [8192, 4099, 1153, 320]
    assert sum(p["check_lens"]) <= 16384  # one row holds all four
    # sizes and lengths only, and no seed but the layout's
    assert p["trace_steps"] == 2
    assert [k for k in p if "seed" in k] == ["layout_seed", "layout_seed_note"]
    for name in NEW_METRICS:
        spec = harness.load_json(
            os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        )
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    mc = from_hf_config(cell.config)
    assert mc.family == "zaya" and mc.router and mc.experts_held == 8


def test_the_traffics_lengths_are_what_the_issue_says():
    """The batches' lengths (the layout's, every seed's): about 119k tokens
    a batch, sequences of 0.6k-8k, 3.7k in the mean and 4.8k token-weighted,
    2,411 causal pairs a token and layer. And the rows the trainer's split
    makes of them (four minibatches of 8 a batch, under the benchmark's
    seeding): one to seven sequences a row, 3.4 in the mean (ISSUE 41
    reckoned two to a dozen and 4.4), all but one row holding a border."""
    from areal_tpu.utils import data as data_utils
    from benchmark.kinds.train_steps import make_batches

    cell = harness.load_cell(CELL)
    p = cell.traffic
    batches = make_batches(p, 32784, seed=1)
    lens = np.concatenate([b["_seq_lens"] for b in batches])
    assert len(lens) == 96 and 550 < lens.min() and lens.max() <= 8192
    assert 117_000 < lens.sum() / 3 < 121_000
    assert 3_600 < lens.mean() < 3_800
    assert 4_700 < (lens * lens).sum() / lens.sum() < 4_900
    pairs = arith_zaya.attended_pairs(lens) / lens.sum()
    assert 2_350 < pairs < 2_450
    # a token's forward: the matmuls and the core at these lengths
    c = cell.config
    matmuls = 2 * arith_zaya.trained_weights(c)
    core = 4 * pairs * arith_zaya.core_flops_per_pair(c)
    assert round(matmuls / 1e6) == 234 and 38e6 < core < 41e6
    per_row, row_lens = [], []
    state = np.random.get_state()
    try:
        for b, batch in enumerate(batches):
            np.random.seed(p["layout_seed"] + b)
            for members in np.array_split(
                np.random.permutation(p["batch_seqs"]), 4
            ):
                n = batch["_seq_lens"][members]
                mask = (np.arange(n.max())[None] < n[:, None]).astype(np.int32)
                mbl = data_utils.split_padded_batch_into_mb_list(
                    {"input_ids": mask, "attention_mask": mask},
                    p["sizes"]["mb_spec.max_tokens_per_mb"], n_rows=1,
                )
                per_row += [len(group) for group in mbl.groups]
                row_lens += list(mbl.row_lens)
    finally:
        np.random.set_state(state)
    assert (min(per_row), max(per_row)) == (1, 7) and len(per_row) == 28
    assert per_row.count(1) == 1 and 3.3 < np.mean(per_row) < 3.6
    # three row lengths on the ladder: three grad_step programs to compile
    assert sorted(set(row_lens)) == [2048, 8192, 16384]
    assert row_lens.count(16384) == 24


def test_rehearsal_of_the_cca_kind_ends_in_the_contracts_line(capsys):
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
        "reference_agrees", "packing_invariant", "mix_exact",
    }
    assert all(notes["checks"].values())
    mix = notes["notes"]["mix"]
    assert mix["layers"] == 3 and mix["mean_rel"] < 1e-5
    b = mix["borders"]
    assert b["moved_after_border"] == 0 and b["moved_before_last"] == 0
    assert b["moved_in_reach"] == b["want_moved"] == {"q": 3, "k": 3, "v": 2}
    assert b["moved_elsewhere"] == {"q": 0, "k": 0, "v": 0}
    counted = notes["notes"]["counted"]
    assert counted["cca_causal_pairs"] == mix["expected"]["cca_causal_pairs"] > 0
    assert counted["cca_starts"] == mix["expected"]["cca_starts"] == 3 * 8
    assert 0 < counted["moe_assignments"] < counted["moe_stream_rows"]
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_the_routers_draw_is_the_layouts_whatever_the_seed():
    """``seed_router`` draws every router leaf again from the traffic file's
    ``layout_seed``, sets the scalar that carries the router's state to 0.5
    in every layer (``init_params`` draws the zero a fresh model stores) and
    touches nothing else."""
    import jax.numpy as jnp

    from benchmark.kinds import train_steps_cca

    cfg, _ = train_steps_cca.tiny_configs()
    layout_seed = harness.load_cell(CELL).traffic["layout_seed"]
    drawn = {}
    for seed in (1, 2):
        params = init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
        before = dict(params["layers"]["cca+moe"])
        assert np.asarray(before["router_carry"]).tolist() == [0.0] * 3
        names = train_steps_cca.seed_router(params, cfg, layout_seed)
        assert len(names) == 11 and all("router" in n for n in names)
        after = params["layers"]["cca+moe"]
        assert np.asarray(after["router_carry"]).tolist() == [0.5] * 3
        for k in after:
            if k not in names:
                assert after[k] is before[k], k
        drawn[seed] = after
    for n in names:
        np.testing.assert_array_equal(drawn[1][n], drawn[2][n])
    assert float(jnp.abs(
        drawn[1]["wq"] - drawn[2]["wq"]
    ).max()) > 0  # the rest is --seed's


def _tiny_mix():
    """(tiny configuration, its mixers' leaves, what the reference saw on
    three sequences): what ``mix_check`` takes."""
    import jax.numpy as jnp

    from benchmark import reference_zaya
    from benchmark.kinds import train_steps_cca as kind

    cfg, keys = kind.tiny_configs()
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    kind.seed_carry(params)
    rng = np.random.default_rng(3)
    saw = [
        reference_zaya.logprobs_and_mix(
            keys, params, rng.integers(0, cfg.vocab_size, n).tolist()
        )[1]
        for n in (19, 7, 11)
    ]
    leaves = {
        k: np.asarray(params["layers"][kind.KIND][k])
        for k in kind.MIXER_LEAVES
    }
    return cfg, leaves, saw


def test_mix_exact_is_false_one_precision_down():
    """The control at the tiny size: the program's mixer on the reference's
    own input passes the rehearsal's limits; on that input rounded one
    precision down (bfloat16 under this float32) it does not."""
    import jax.numpy as jnp

    from benchmark.kinds import train_steps_cca as kind

    cfg, leaves, saw = _tiny_mix()
    mix = kind.mix_check(cfg, leaves, saw, jnp.float32, 0)
    assert kind.mix_ok(mix, kind.REHEARSE_MIX, {}, {})
    low = [
        [dict(layer, h=np.asarray(
            jnp.asarray(layer["h"]).astype(jnp.bfloat16), np.float32
        )) for layer in s]
        for s in saw
    ]
    mix = kind.mix_check(cfg, leaves, low, jnp.float32, 0)
    assert mix["mean_rel"] > kind.REHEARSE_MIX["mean"]
    assert not kind.mix_ok(mix, kind.REHEARSE_MIX, {}, {})
    # and a count that is not what the lengths make it fails it alone
    mix = kind.mix_check(cfg, leaves, saw, jnp.float32, 0)
    assert not kind.mix_ok(
        mix, kind.REHEARSE_MIX, {"cca_starts": 8}, {"cca_starts": 9}
    )


def test_mix_exact_catches_a_look_back_across_a_border(monkeypatch):
    """A shift that takes the previous token of the ROW, whatever sequence
    it is of, moves the next sequence's first tokens when the last token of
    a sequence is replaced: (b) sees it and ``mix_exact`` is false."""
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from benchmark.kinds import train_steps_cca as kind

    cfg, leaves, saw = _tiny_mix()

    def across(x, segment_ids):
        return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]

    monkeypatch.setattr(transformer, "_previous_token", across)
    mix = kind.mix_check(cfg, leaves, saw, jnp.float32, 0)
    assert mix["borders"]["moved_after_border"] > 0
    assert not kind.mix_ok(mix, kind.REHEARSE_MIX, {}, {})


def test_new_metric_files_on_hand_built_records(monkeypatch):
    """1 s under ``cca_mix`` and 2 s under ``attention`` of a 10 s window; a
    need of half a second at the peak; 5,000 pairs held of a 10,000-row
    stream."""
    from benchmark import program_trace

    record = {
        "window_s": 10.0, "devices": 1,
        "program_scopes": {
            "grad_step": {"cca_mix": 0.75, "attention": 1.5, "mlp": 3.0,
                          program_trace.NO_SCOPE: 0.25},
            "logp_forward": {"cca_mix": 0.25, "attention": 0.5, "mlp": 1.0},
        },
    }
    monkeypatch.setattr(program_trace, "of_run", lambda obs: record)
    obs = harness.Observation(
        window_s=10.0, setup_s=1.0, trace={"window_s": 10.0},
        counters={"moe_assignments": 5000.0, "moe_stream_rows": 10000.0},
        values={"attn_full_flops_traced": 0.5 * 197e12},
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
    )
    assert harness.read_metric("cca_mix_time_share.train", obs)["value"] == 10.0
    assert harness.read_metric("attn_full_roofline.train", obs)["value"] == 25.0
    assert harness.read_metric("moe_held_fill.train", obs)["value"] == 50.0


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the parent commit (no ``cca_mix`` scope, no ``cca_*`` counter, no
    held-share counters in a cell that holds every expert) each new metric's
    reader returns None and raises nothing."""
    obs = harness.Observation(
        window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
        values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        trace=harness.load_json(os.path.join(HERE, "recorded_trace.json"))
        if os.path.exists(os.path.join(HERE, "recorded_trace.json")) else None,
    )
    for name in NEW_METRICS:
        assert harness.read_metric(name, obs) is None, name
