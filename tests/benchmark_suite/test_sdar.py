"""The SDAR-30B-A3B-Chat configuration's benchmark files: its arithmetic
against hand-worked counts (30.5 B parameters whole, 3.3 B a token; slots and
kept pairs of a sequence), the configuration file against the catalog's
rules, the traffic's lengths and slot counts from the seed, one rehearsal of
the cell's traffic kind, and the new metric files on the recorded trace
(which has none of their names: they read nothing and raise nothing).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config
from areal_tpu.models.transformer import init_params
from areal_tpu.utils import data as data_utils
from benchmark import arith_sdar, harness, lengths
from benchmark.kinds import train_steps_bd

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "train_sdar_bd_grpo_packed16k"
NEW_METRICS = ["bd_attn_time_share.train", "bd_attn_roofline.train",
               "bd_kept_share.train", "bd_slots_per_token.train"]


def _published():
    with open(os.path.join(
        ROOT, "benchmark", "configs", "sdar-30b-a3b-chat.json"
    )) as f:
        return json.load(f)


def test_parameter_counts_are_the_published_models():
    c = _published()
    whole, active = arith_sdar.published_param_counts(c)
    # a layer: attention 18.87 M, router 0.26 M, 128 experts of 4.72 M, norms
    layer = 18_874_368 + 262_144 + 128 * 4_718_592 + 2 * 2048 + 2 * 128
    assert whole == 48 * layer + 2 * 151_936 * 2048 + 2048 == 30_532_122_624
    assert round(whole / 1e9, 1) == 30.5
    assert active == 48 * (layer - 120 * 4_718_592) + 2 * 151_936 * 2048 + 2048
    assert active == 3_353_032_704  # "3.3 B a token", the table's head in


def test_param_count_is_the_programs_and_the_configuration_files():
    c = _published()
    cfg = from_hf_config(c)
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert arith_sdar.param_count(c) == n == 550_984_960
    # 16 B a parameter as trained: half of a 16 GiB chip, over the 25% floor
    assert 0.5 < n * 16 / 2 ** 34 < 0.55


@pytest.mark.parametrize("prompt,n,k,slots,pairs", [
    # one block of prompt, one of completion: clean 4*4 + 4*(4+4) = 48; a
    # noisy stream holds block 1 alone, 4 * (4 + 4) = 32 pairs
    (4, 8, 1, 8 + 4, 48 + 32),
    (4, 8, 2, 8 + 8, 48 + 64),
    # the border block is mixed (prompt 5): a noisy stream starts at 4; the
    # last block is short (3 tokens): blocks 4, 4, 3
    (5, 11, 2, 11 + 2 * 7, (16 + 32 + 3 * 11) + 2 * (32 + 3 * 11)),
    (5, 11, 0, 11, 16 + 32 + 33),
])
def test_slots_and_kept_pairs_against_hand_worked_counts(
    prompt, n, k, slots, pairs
):
    assert arith_sdar.slots(prompt, n, k, 4) == slots
    assert arith_sdar.kept_pairs(prompt, n, k, 4) == pairs
    # and against the mask itself, on the program's own layout
    d = np.full(n, -1, np.int32)
    d[prompt:] = np.arange(n - prompt) % max(k, 1) if k else -1
    from areal_tpu.ops import bd_attention as bd

    src, stream, _, _ = data_utils.bd_sequence_slots(d, 4)
    assert len(src) == slots
    mask = np.asarray(bd.kept_mask(
        np.ones(len(src), np.int32), src // 4, stream
    ))
    assert int(mask.sum()) == pairs


def test_flops_of_a_trained_token_count_the_streams_slots():
    c = dict(_published(), num_hidden_layers=1)
    attn = 2048 * 5120 + 4096 * 2048
    weights = attn + 2048 * 128 + 1.0 * 3 * 2048 * 768
    assert arith_sdar.expert_pairs_per_slot(c) == 1.0
    assert arith_sdar.layer_weights_per_slot(c) == weights == 23_855_104
    p, n, k = 512, 2560, 2  # 2,048 completion tokens, whole blocks
    slots = n + k * 2048
    assert arith_sdar.slots(p, n, k, 4) == slots == 6656
    pairs = arith_sdar.kept_pairs(p, n, k, 4)
    clean = sum(4 * (4 * b + 4) for b in range(640))
    assert pairs == clean + 2 * sum(4 * (4 * b + 4) for b in range(128, 640))
    fwd = (2.0 * slots * weights + pairs * 4 * 32 * 128
           + 2.0 * 2048 * 2048 * 18992)
    assert arith_sdar.forward_flops(c, [p], [n], k) == fwd
    assert arith_sdar.train_step_flops(c, [p], [n], k) == 4 * fwd
    assert arith_sdar.flops_per_trained_token(c, p, n, k) == 4 * fwd / n
    # the streams cost: 2.6 slots a token, about three times a causal core
    assert slots / n == 2.6
    assert 2.8 < pairs / arith_sdar.causal_pairs(n) < 3.0
    # the kernels: forward twice (recompute, forward), a backward of 2 x, a
    # row's pairs times the layers
    five = dict(c, num_hidden_layers=5)
    assert arith_sdar.bd_core_kernel_flops(five, pairs) == (
        4 * 5 * pairs * 4 * 32 * 128
    )
    assert arith_sdar.expert_kernel_flops(c, 1024, True) == (
        5 * 2.0 * 1024 * 3 * 2048 * 768
    )


def test_configuration_file_keeps_the_catalog_rows_rules():
    c = _published()
    cfg = from_hf_config(c)
    assert c["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_local_experts": 128,
                              "vocab_size": 151936}
    # the catalog row's every number, a reduced key apart
    row = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
           "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 6144, "max_position_embeddings": 32768,
           "max_window_layers": 48, "mlp_only_layers": [],
           "model_type": "sdar_moe", "moe_intermediate_size": 768,
           "norm_topk_prob": True, "num_attention_heads": 32,
           "num_experts": 128, "num_experts_per_tok": 8,
           "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "use_sliding_window": False}
    assert {k: c[k] for k in row} == row
    assert cfg.family == "sdar_moe" and cfg.is_moe and cfg.qk_norm == "head"
    assert (cfg.num_experts, cfg.num_held_experts) == (128, 16)
    assert cfg.layer_kinds == ("full+moe",) * 5 and cfg.full.num_heads == 32
    bd = cfg.block_diffusion
    assert (bd.block_length, bd.mask_token_id) == (4, 18991)
    assert bd.mask_token_id == cfg.vocab_size - 1
    # floors of a model_config cut: >= 5 layers, >= 8 experts, >= 1/8 vocabulary
    assert cfg.num_layers >= 5 and cfg.num_held_experts >= 8
    assert cfg.vocab_size * 8 >= 151936
    for key in ("block_length", "mask_token_id", "blocks", "shift",
                "noise_schedule", "router_aux_loss_coef", "weights"):
        assert key in c["assumed"]
    assert 0 < c["logprob_limits"]["mean"] < 0.05 < c["logprob_limits"]["worst"] < 2
    assert 0 < c["mask_limits"]["core_max_abs_diff"] < 0.1
    assert 0.9 <= c["gradient_limits"]["cosine"] < 1
    assert 0 < c["gradient_limits"]["loss_abs_diff"] < 0.1
    whole = from_hf_config(dict(c, num_local_experts=128))
    assert whole.experts_held == 0 and whole.num_held_experts == 128
    with pytest.raises(ValueError, match="mask_token_id"):
        from_hf_config({k: v for k, v in c.items() if k != "mask_token_id"})


def test_the_traffics_lengths_and_slots_from_the_seed():
    """What the cell's `why` says of its rows, from the layout's seed: about
    48k trained tokens and 2.6 slots a token a batch, every sequence inside
    a row, most rows two to five sequences."""
    cell = harness.load_cell(CELL)
    p = cell.traffic
    assert p["kind"] == "train_steps_bd" and p["denoise_states"] == 2
    assert p["sizes"] == {"mb_spec.max_tokens_per_mb": 16384}
    assert p["prompt_len"]["hi"] + 3 * p["output_len"]["hi"] + 6 <= 16384
    batches = train_steps_bd.make_batches(p, 18991, 5)
    layout = np.random.default_rng(p["layout_seed"] + 1)
    tokens = slots = 0
    per_row = []
    for batch in batches:
        prompts = np.asarray(batch["loss_mask"]).argmax(1)
        b = train_steps_bd.with_states(batch, prompts, 4, 2, layout)
        lens = batch["_seq_lens"]
        took = data_utils.row_lens_of(b, 4)
        assert [
            arith_sdar.slots(pl, n, 2, 4) for pl, n in zip(prompts, lens)
        ] == took.tolist()
        assert took.max() <= 16006 and (b["denoise_state"].max(1) == 1).all()
        tokens += int(lens.sum())
        slots += int(took.sum())
        groups, row_lens = data_utils.split_lens(took, 16384)
        assert max(row_lens) == 16384
        per_row += [len(g) for g in groups]
    assert (len(batches), tokens, slots) == (3, 142_971, 367_473)
    assert 2.5 < slots / tokens < 2.7
    assert sum(2 <= n <= 5 for n in per_row) > len(per_row) / 2
    # the same multiset whatever the seed: a seed orders, the layout sizes
    again = train_steps_bd.make_batches(p, 18991, 6)
    assert [b["_seq_lens"].tolist() for b in again] == [
        b["_seq_lens"].tolist() for b in batches
    ]
    pool = lengths.pool(p["output_len"], 48)
    assert pool.min() >= 256 and pool.max() == 4992


def test_every_seed_trains_the_layouts_token_ids():
    """The rows route alike through the experts held whatever the seed: the
    ids are the layout's; the rewards and behaviour logprobs the seed's."""
    p = harness.load_cell(CELL).traffic
    p = dict(p, **p["rehearse"])
    a, b = (train_steps_bd.layout_batches(p, 500, s) for s in (5, 2 ** 31 + 6))
    assert len(a) == len(b) == p["n_batches"]
    for x, y in zip(a, b):
        assert (x["input_ids"] == y["input_ids"]).all()
        assert (x["loss_mask"] == y["loss_mask"]).all()
        assert not np.array_equal(x["logprobs"], y["logprobs"])
        assert x["input_ids"].max() < 500
    assert any(not np.array_equal(x["rewards"], y["rewards"]) for x, y in zip(a, b))
    drawn = train_steps_bd.make_batches(p, 500, 5)
    assert not np.array_equal(drawn[0]["input_ids"], a[0]["input_ids"])
    assert np.array_equal(drawn[0]["logprobs"], a[0]["logprobs"])


def test_trace_states_reveal_two_then_the_rest_of_each_block():
    rng = np.random.default_rng(0)
    d = train_steps_bd.trace_states(5, 19, 4, 2, rng)
    assert (d[:5] == -1).all() and (d[5:] >= 0).all()
    # the border block holds 3 completion tokens: two of state 0, one of 1
    assert sorted(d[5:8]) == [0, 0, 1]
    for a in (8, 12):
        assert sorted(d[a:a + 4]) == [0, 0, 1, 1]
    assert sorted(d[16:19]) == [0, 0, 1]  # a short last block
    assert (train_steps_bd.trace_states(5, 19, 4, 1, rng)[5:] == 0).all()


def test_new_files_load_and_resolve():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "sdar-30b-a3b-chat"
    assert harness.load_kind(cell.traffic["kind"]) is train_steps_bd
    assert cell.end_to_end == ["trained_tokens_per_s", "setup_s"]
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert {"train_mfu", "attn_time_share.train", "moe_experts_roofline.train",
            "expert_load_imbalance.train", "moe_held_fill.train",
            "unscoped_time_share.train"} <= set(cell.per_layer)
    lens = cell.traffic["check_lens"]
    assert lens == [4099, 2051, 1153, 320] and sum(n % 4 > 0 for n in lens) == 3
    bench = harness.load_bench()
    for name in NEW_METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "trained_tokens_per_s"


def test_rehearsal_of_the_bd_kind_ends_in_the_contracts_line(capsys):
    from benchmark import run

    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 58),
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
        "gradient_direction", "mask_exact",
    } and all(checks.values()), notes
    n = notes["notes"]
    assert n["reference"]["max_abs_diff"] < 1e-3 and n["reference"]["compared"] > 50
    assert n["packing"]["max_abs_diff"] < 1e-3
    grad = n["gradient"]  # the engine's grad_step against jax.grad
    assert grad["cosine"] > 0.9999 and grad["loss_abs_diff"] < 1e-4
    assert grad["tokens"] > 50 and grad["norm"] > 0
    exact = n["mask_exact"]
    assert exact["core"]["max_abs_diff"] < 1e-4 < exact["core"]["compared"]
    assert exact["edges"]["ok"] and exact["counts_ok"]
    for k, v in exact["window_counts_expected"].items():
        assert n["counted"][k] == v > 0
    assert n["counted"]["bd_sequence_tokens"] + n["counted"]["bd_stream_slots"] == (
        n["counted"]["real_tokens"]
    )
    kept = line["metrics"]["bd_kept_share.train"]["value"]
    assert kept == pytest.approx(
        100 * n["counted"]["bd_pairs"] / n["counted"]["bd_causal_pairs"]
    ) and 200 < kept < 400
    per = line["metrics"]["bd_slots_per_token.train"]
    assert per["unit"] == "slots/token" and 2.0 < per["value"] < 3.2
    # a sequence's own tokens once: the rate's counter is not the slots'
    assert n["trained_tokens"] < n["counted"]["bd_sequence_tokens"]
    assert 1.0 <= line["metrics"]["expert_load_imbalance.train"]["value"] <= 8.0
    # no peak table and no device trace off the chip: those leave themselves out
    assert not {"train_mfu", "bd_attn_roofline.train",
                "bd_attn_time_share.train"} & set(line["metrics"])


def test_new_metric_files_read_nothing_where_the_program_has_no_such_name():
    """On the recorded chip trace (a program from before this PR) and on an
    empty observation every new metric is None, not an error; given the
    names, the ratios read what the counters say."""
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
    obs = harness.Observation(
        window_s=10.0, setup_s=1.0, peaks=peaks,
        counters={"bd_pairs": 290.0, "bd_causal_pairs": 100.0,
                  "real_tokens": 26.0, "bd_sequence_tokens": 10.0},
    )
    assert harness.read_metric("bd_kept_share.train", obs)["value"] == 290.0
    per = harness.read_metric("bd_slots_per_token.train", obs)
    assert per == {"value": 2.6, "unit": "slots/token"}
