"""The Jamba2-3B configuration's benchmark files: its arithmetic against
hand-worked counts, the configuration file against the catalog's row, the
traffic's lengths from the seed, every new data file, one rehearsal of the
cell's traffic kind on the mesh the CPU backend has, and the new metric files
on the recorded trace (which has none of their names: they read nothing and
raise nothing)."""

import json
import os

import jax
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from benchmark import arith_jamba, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_jamba_ssm_grpo_packed8k_fsdp4"
NEW_METRICS = ["ssm_scan_time_share.train", "ssm_prep_time_share.train",
               "ssm_scan_roofline.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cut():
    with open(os.path.join(ROOT, "benchmark", "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def _published():
    """The file's keys at the published depth."""
    c = _cut()
    return dict(c, **c.get("published", {}))


def test_parameters_against_the_hand_worked_3029_million():
    c = _published()
    # mamba mixer: in 2560 x 10240, x_proj 5120 x 192, dt_proj 160 x 5120,
    # out 5120 x 2560; the convolution 5120 x 4 and its bias, the three
    # norms 160 + 16 + 16, dt's bias 5120, A_log 5120 x 16, D 5120
    products = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert arith_jamba.ssm_mixer_matmul_weights(c) == products == 41_123_840
    rest = 5120 * 4 + 5120 + 192 + 5120 + 5120 * 16 + 5120
    assert arith_jamba.ssm_mixer_params(c) == products + rest == 41_241_792
    # attention: q 2560 x 2560, k and v 2560 x 128 each, o 2560 x 2560
    assert arith_jamba.attn_mixer_params(c) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert arith_jamba.attn_mixer_params(c) == 13_762_560
    assert arith_jamba.ffn_params(c) == 3 * 2560 * 8192 == 62_914_560
    assert (arith_jamba.ssm_layers(c), arith_jamba.attn_layers(c)) == (26, 2)
    mamba_layer = 41_241_792 + 62_914_560 + 2 * 2560  # "104.16 M"
    attn_layer = 13_762_560 + 62_914_560 + 2 * 2560  # "76.68 M"
    total = 26 * mamba_layer + 2 * attn_layer + 65536 * 2560 + 2560
    assert arith_jamba.param_count(c) == total == 3_029_337_472
    assert round(total * 16 / 4e9, 2) == 12.12  # GB a chip under fsdp=4
    # and the program stores exactly that
    from areal_tpu.models.transformer import count_params, init_params

    shapes = jax.eval_shape(
        lambda: init_params(from_hf_config(c), jax.random.PRNGKey(0))
    )
    assert count_params(shapes) == total
    assert arith_jamba.trained_weights(c) == total - 26 * rest - 57 * 2560
    # the cut: layers 0-21, 20 mamba layers and both attention layers
    cut = _cut()
    assert (arith_jamba.ssm_layers(cut), arith_jamba.attn_layers(cut)) == (20, 2)
    held = 20 * mamba_layer + 2 * attn_layer + 65536 * 2560 + 2560
    assert arith_jamba.param_count(cut) == held == 2_404_368_640
    assert round(held * 16 / 4e9, 2) == 9.62  # GB a chip under fsdp=4


def test_flops_and_kernel_bytes_against_hand_worked_values():
    c = _published()
    w = arith_jamba.trained_weights(c)
    assert w == 3_026_124_800  # "the 3,026 M in products"
    assert arith_jamba.ssm_scan_flops_per_token(c) == 3 * 2 * 81_920
    assert arith_jamba.attn_core_flops_per_pair(c) == 4 * 20 * 128
    lens = [5, 3]
    products, cores, scans = arith_jamba.forward_flops(c, lens, parts=True)
    assert products == 2.0 * 8 * w
    assert cores == 2 * (15 + 6) * 10_240
    assert scans == 26 * 8 * 491_520
    assert arith_jamba.forward_flops(c, lens) == products + cores + scans
    assert arith_jamba.train_step_flops(c, lens) == 4 * (
        products + cores + scans
    )
    assert arith_jamba.ssm_scan_train_flops(c, lens) == 4 * scans
    assert 0.001 < scans / products < 0.003  # "about 0.2% of the products'"
    # a token and layer, bf16: x, dt, z in and y out 4 x 10,240 B; B and C
    # 128 B; a [5120, 16] float32 state every 256 tokens 1,280 B; the
    # backward reads those and dy and writes dx, ddt, dz and dB, dC
    fwd, states = 40_960 + 128, 40_960 + 128 + 1_280
    bwd = 30_720 + 128 + 1_280 + 10_240 + 30_720 + 128
    assert arith_jamba.ssm_kernel_bytes(c, 1, True) == 2 * fwd + states + bwd
    assert arith_jamba.ssm_kernel_bytes(c, 1, False) == fwd + states + bwd
    assert arith_jamba.ssm_kernel_bytes(c, 7, True) == 7 * 197_760


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _cut()
    bench = harness.load_bench()
    entry = next(e for e in bench["configs"] if e["name"] == "jamba2-3b")
    assert entry["reduced"] == c["reduced"]
    assert c["source"] == entry["source"]
    for key in ("deployment", "assumed", "logprob_limits", "gradient_limits",
                "scan_limits", "memory_peak_reading", "reduced_note"):
        assert c[key], key
    assert {"layer_order", "head_dim", "inner_norms", "weights"} <= set(
        c["assumed"]
    )
    # nothing but the depth may be cut, never under a period and four
    assert set(c["reduced"]) <= {"num_hidden_layers"}
    assert c["num_hidden_layers"] >= 18
    if c["reduced"]:
        assert c["published"] == {"num_hidden_layers": 28}
    cells = [w for w in bench["workloads"] if w["config"] == "jamba2-3b"]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(
        bench["workloads"]
    ) // 4
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic["kind"] == "train_steps_ssm"
    assert harness.load_kind(cell.traffic["kind"]).FAMILY == "jamba"
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert {"train_mfu_per_chip.train", "collective_time_share.train",
            "attn_time_share.train", "unscoped_time_share.train"} <= set(
        cell.per_layer
    )
    assert not {"rope_time_share.train", "train_mfu"} & set(cell.per_layer)
    assert {"trained_tokens_per_s", "setup_s"} == set(cell.end_to_end)
    p = cell.traffic
    assert (p["batch_seqs"], p["group_size"], p["n_batches"]) == (32, 4, 2)
    assert p["sizes"] == {"parallel.fsdp_parallel_size": 4,
                          "mb_spec.max_tokens_per_mb": 32768}
    assert p["prompt_len"]["hi"] + p["output_len"]["hi"] == 8192
    assert p["check_lens"] == [4096, 2051, 1153, 320]
    assert sum(p["check_lens"]) <= 8192  # scan_exact's one row
    assert sum(n % 64 != 0 for n in p["check_lens"]) >= 2
    for name in NEW_METRICS:
        spec = harness.load_json(
            os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        )
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    mc = from_hf_config(cell.config)
    assert mc.family == "jamba" and mc.ssm.d_inner == 5120


def test_the_traffics_lengths_come_from_the_layout_seed():
    """The same lengths whatever the seed (every seed packs to the same
    rows), about 116k tokens a batch, the longest sequence within a row."""
    from benchmark.kinds.train_steps import make_batches

    p = harness.load_cell(CELL).traffic
    a = make_batches(p, 65536, 1)
    b = make_batches(p, 65536, 3000000001)
    assert len(a) == 2
    for x, y in zip(a, b):
        assert (x["_seq_lens"] == y["_seq_lens"]).all()
        assert not (x["input_ids"] == y["input_ids"]).all()
        assert len(x["_seq_lens"]) == 32 and x["_seq_lens"].max() <= 8192
    total = sum(int(x["_seq_lens"].sum()) for x in a)
    assert 180_000 < total < 280_000
    assert total == 232_737


def test_rehearsal_of_the_ssm_kind_ends_in_the_contracts_line(capsys):
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
        "reference_agrees", "packing_invariant", "gradient_direction",
        "scan_exact",
    }
    assert all(notes["checks"].values())
    n = notes["notes"]
    assert n["chips"] == min(4, jax.device_count())
    assert n["gradient"]["cosine"] > 0.99999
    scan = n["scan"]
    assert scan["layers"] == 3 and scan["mean_rel"] < 1e-5
    assert scan["border"]["ok"] and scan["border"]["moved_outside"] == 0
    assert n["counted"]["ssm_tokens"] == scan["window_expected"]["ssm_tokens"] > 0
    assert n["counted"]["ssm_segments"] == scan["window_expected"]["ssm_segments"]
    assert set(line["metrics"]) == {"trained_tokens_per_s", "setup_s"}


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the parent commit (no ``ssm_*`` scope, kernel or counter) each new
    metric's reader returns None and raises nothing."""
    path = os.path.join(HERE, "recorded_trace.json")
    obs = harness.Observation(
        window_s=1.0, setup_s=1.0, counters={"trained_tokens": 1.0},
        values={}, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        trace=harness.load_json(path) if os.path.exists(path) else None,
    )
    for name in NEW_METRICS:
        assert harness.read_metric(name, obs) is None


def test_the_border_check_is_the_kinds_own():
    """``scan_exact`` (b) on a plain row: see ``tests/test_ssm.py`` for the
    scan that fails it."""
    from areal_tpu.ops import ssm_scan
    from benchmark.kinds import train_steps_ssm

    rng = np.random.default_rng(0)
    t, c, n = 40, 8, 4
    x = rng.standard_normal((1, t, c)).astype(np.float32)
    dt = np.full((1, t, c), 0.01, np.float32)
    a = -np.ones((c, n), np.float32)
    bm, cm = rng.standard_normal((2, 1, t, n)).astype(np.float32)
    seg = np.asarray([[1] * 15 + [2] * 15 + [0] * 10], np.int32)
    got = train_steps_ssm.border_check(
        jax.jit(lambda *args: ssm_scan.ssm_scan(*args)[0]), x, dt, a, bm, cm,
        seg,
    )
    assert got["ok"] and (got["token"], got["sequence_end"]) == (22, 30)
