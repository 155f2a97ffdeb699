"""OLMoE through the program against its plain reference, float32 against
float32 on the CPU at the family's tiny configuration (2 layers, 8 experts of
which 2, q/k normed over the whole projection): logits and gradients, packing
invariance, the generation engine's prefill and decode, the benchmark's own
arithmetic, and one rehearsal of the cell's traffic kind.

Tolerances: both sides compute in float32, so what differs is the order of
sums only (the program sorts pairs by expert and multiplies group by group;
the reference multiplies every expert and weights the unchosen by zero). On
logits of magnitude ~1 that is a few float32 ulps a layer: 1e-4 absolute holds
with a margin of ~100; a bf16 forward (8 bits of mantissa) reads ~1e-2 and
fails it. Gradients are sums over up to 24 positions of such terms and are
held to 1e-4 relative to each leaf's largest entry.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.models.transformer import apply, init_params
from benchmark import arith_olmoe, reference_olmoe
from benchmark.kinds import train_steps_sparse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOL = 1e-4


def _published():
    """The cell's configuration file: the published config, cut to 5 layers."""
    with open(os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    cfg, ref_cfg = train_steps_sparse.tiny_configs()
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    # norm weights off 1, so that a norm applied per head (or not at all)
    # where the family norms the whole projection would show
    rng = np.random.default_rng(7)
    for k in ("q_norm", "k_norm", "input_norm", "post_attn_norm"):
        params["layers"][k] = params["layers"][k] * jnp.asarray(
            1.0 + 0.3 * rng.standard_normal(params["layers"][k].shape),
            jnp.float32,
        )
    # and weights large enough that the router's choices are decided
    params["layers"]["w_router"] = params["layers"]["w_router"] * 20.0
    return cfg, ref_cfg, params


def _alone(cfg, params, tokens, **kw):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return apply(
        params, cfg, t, jnp.ones_like(t),
        jnp.arange(t.shape[1], dtype=jnp.int32)[None], remat=False, **kw
    )


def test_tiny_config_is_the_family(tiny):
    cfg = tiny[0]
    assert cfg.family == "olmoe" and cfg.qk_norm == "projection"
    assert (cfg.num_layers, cfg.num_experts, cfg.num_experts_per_tok) == (2, 8, 2)
    assert not cfg.norm_topk_prob and not cfg.shared_expert_size
    assert not cfg.attention_bias and not cfg.tie_word_embeddings


def test_logits_match_the_plain_reference(tiny):
    cfg, ref_cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    got = np.asarray(_alone(cfg, params, tokens)[0])
    want = np.asarray(reference_olmoe.logits(ref_cfg, params, tokens))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_gradient_of_a_summed_logprob_matches_for_every_leaf(tiny):
    cfg, ref_cfg, params = tiny
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 24)

    def summed(p):
        lp = jax.nn.log_softmax(_alone(cfg, p, tokens)[0, :-1], axis=-1)
        tgt = jnp.asarray(tokens[1:], jnp.int32)
        return jnp.take_along_axis(lp, tgt[:, None], axis=-1).sum()

    got = jax.grad(summed)(params)
    want = reference_olmoe.summed_logprob_grad(ref_cfg, params, tokens)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 3 + len(params["layers"])
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path  # every leaf, the router among them, is used
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=TOL * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path),
        )


def test_a_sequence_reads_the_same_alone_and_packed_among_others(tiny):
    """No capacity, no drop: what a token reads does not depend on what it
    is packed with (the capped dispatch this replaced made the recomputed
    logprobs, the gradient pass and the rollout engine differ by
    construction, each packing its own way)."""
    cfg, _, params = tiny
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, cfg.vocab_size, n) for n in (9, 14, 5, 11)]
    alone = [np.asarray(_alone(cfg, params, s)[0]) for s in seqs]
    # one row: the four back to back, then padding; a second row of others
    row = np.concatenate(seqs + [np.zeros(9, np.int64)])
    seg = np.concatenate(
        [np.full(len(s), i + 1) for i, s in enumerate(seqs)] + [np.zeros(9)]
    )
    pos = np.concatenate([np.arange(len(s)) for s in seqs] + [np.zeros(9)])
    other = rng.integers(0, cfg.vocab_size, len(row))
    tokens = jnp.asarray(np.stack([row, other]), jnp.int32)
    segs = jnp.asarray(np.stack([seg, np.ones(len(row))]), jnp.int32)
    poss = jnp.asarray(np.stack([pos, np.arange(len(row))]), jnp.int32)
    packed = np.asarray(apply(params, cfg, tokens, segs, poss, remat=False))
    at = 0
    for a in alone:
        np.testing.assert_allclose(
            packed[0, at:at + len(a)], a, atol=TOL, rtol=0
        )
        at += len(a)


def test_generation_engine_prefill_then_decode_matches_the_reference(tiny):
    """Serving: logits of the prompt's last position (prefill) and of each
    decoded position (through the paged cache) against the reference's full
    forward over the same tokens — greedy tokens and their logprobs."""
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine

    cfg, ref_cfg, params = tiny
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 9).tolist()
    eng = GenerationEngine(
        JaxGenConfig(dtype="float32", max_num_seqs=4, max_model_len=64,
                     prefill_chunk=16),
        model_config=cfg, params=params,
    ).start()
    try:
        out = eng.generate({
            "input_ids": prompt,
            "sampling_params": {"max_new_tokens": 6, "greedy": True},
        })
    finally:
        eng.stop()
    seq = prompt + out["output_ids"]
    ref = np.asarray(jax.nn.log_softmax(
        reference_olmoe.logits(ref_cfg, params, seq), axis=-1
    ))
    for i, (tok, lp) in enumerate(zip(out["output_ids"], out["output_logprobs"])):
        at = len(prompt) - 1 + i  # the position that predicts this token
        assert tok == int(ref[at].argmax())
        assert abs(lp - ref[at, tok]) < TOL


def test_from_hf_config_reads_the_published_file():
    c = _published()
    cfg = from_hf_config(c)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_ffn_size) == (
        64, 8, 1024
    )
    assert cfg.qk_norm == "projection" and cfg.head_dim == 128
    assert cfg.norm_topk_prob is False and cfg.shared_expert_size == 0
    assert cfg.router_aux_loss_coef == c["router_aux_loss_coef"]
    assert not cfg.attention_bias and not cfg.tie_word_embeddings
    # the published depth, with nothing else touched, is the published model
    full = from_hf_config(dict(c, num_hidden_layers=16))
    assert arith_olmoe.param_count(dict(c, num_hidden_layers=16)) == (
        6_919_161_856
    ) and full.num_layers == 16


# hand-worked, at the published widths and the cell's depth of 5: a layer's
# attention 4 x 2048^2 = 16,777,216; router 2048 x 64 = 131,072; 8 experts x
# 3 x 2048 x 1024 = 50,331,648; head 2048 x 50,304 = 103,022,592
_LAYER = 16_777_216 + 131_072 + 50_331_648
_HAND = {
    "matmul_weights": 5 * _LAYER + 103_022_592,
    "expert_weights_per_layer": 50_331_648,
    # stored: all 64 experts, two layer norms, q and k norms of 2048 each
    "param_count": 5 * (16_777_216 + 131_072 + 64 * 6_291_456 + 4 * 2048)
    + 2 * 103_022_592 + 2048,
}


@pytest.mark.parametrize("name", sorted(_HAND))
def test_arith_against_hand_worked_values(name):
    c = _published()
    assert getattr(arith_olmoe, name)(c) == _HAND[name]


def test_arith_against_the_programs_flop_model():
    from areal_tpu.utils import flops

    c = _published()
    cfg = from_hf_config(c)
    lens = [4096, 700, 33]
    assert arith_olmoe.matmul_weights(c) == flops.matmul_weights(cfg)
    assert arith_olmoe.forward_flops(c, lens) == flops.forward_flops(cfg, lens)
    assert arith_olmoe.train_step_flops(c, lens) == flops.train_step_flops(
        cfg, lens, n_forward_only=1
    )
    # 503 of 878 MFLOP of a token's forward are expert matmuls
    per_token = arith_olmoe.expert_forward_flops(c, 1)
    assert per_token == 2.0 * 5 * 50_331_648
    assert 0.57 < per_token / (2.0 * arith_olmoe.matmul_weights(c)) < 0.58
    # the kernel executes the recomputed forward too; MFU does not count it
    assert arith_olmoe.expert_kernel_flops(c, 10, checkpointed=True) == (
        5 * 10 * per_token
    )
    assert arith_olmoe.expert_kernel_flops(c, 10, checkpointed=False) == (
        4 * 10 * per_token
    )
    # and the stored parameters are what init_params makes
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=jnp.float32), jax.random.PRNGKey(0)
    )
    assert arith_olmoe.param_count(c) == sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    )


def test_rehearsal_of_the_sparse_kind_ends_in_the_contracts_line(capsys):
    from benchmark import run

    rc = run.main(["--workload", "train_olmoe_grpo_fsdp4", "--seed",
                   str(2 ** 31 + 29), "--seconds", "2", "--trace", "1",
                   "--rehearse"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and len(lines) >= 2
    notes, line = json.loads(lines[-2]), json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert "rehearsal" in line and line["failed"] == 0 < line["attempted"]
    checks = dict(notes["checks"])
    checks.pop("no_compile_in_window")  # the chip's business
    assert set(checks) == {"finite_loss_and_grad_norm", "reference_agrees",
                           "packing_invariant"} and all(checks.values()), notes
    assert notes["notes"]["reference"]["max_abs_diff"] < TOL
    assert notes["notes"]["packing"]["max_abs_diff"] < TOL
    n = notes["notes"]
    assert n["chips"] == 4  # the mesh is a size of this kind
    # every trained token went to 2 experts in each of 2 layers, none dropped
    assert n["routed"]["moe_assignments"] == n["trained_tokens"] * 2 * 2
    imbalance = line["metrics"]["expert_load_imbalance.train"]["value"]
    assert 1.0 <= imbalance <= 8.0
    assert 0 <= line["metrics"]["pad_share.train"]["value"] < 100
    # no peak table and no device trace off the chip: those leave themselves out
    assert not {"train_mfu_per_chip.train", "moe_experts_roofline.train",
                "collective_time_share.train"} & set(line["metrics"])


def _obs(op_seconds, values=None):
    from benchmark import harness

    return harness.Observation(
        window_s=10.0, setup_s=1.0, values=values or {},
        trace={"window_s": 10.0, "busy_s": 9.0, "op_seconds": op_seconds},
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
    )


def test_new_metric_files_read_the_names_the_chip_gave():
    """The names are the v5e's (PERF.md, PR 27, call 2): collectives under
    the compiler's names and under a shard_map's, the megablox kernels as
    ``gmm`` / ``tgmm``. A program without them reads nothing and raises
    nothing."""
    from benchmark import harness

    ops = {
        "reduce_scatter.47 bf16[64,512,1024]": 0.2,
        "all-gather.126 bf16[1,64,1024,2048]": 0.125,
        "async-collective-done.1 bf16[1,64,2048,1024]": 0.115,
        "all-reduce.75": 0.01, "psum.99": 0.05,
        "gmm.3 bf16[32768,1024]": 1.0, "tgmm.1 bf16[64,1024,2048]": 0.5,
        "fusion.762 f32[512,50304]": 0.27, "splash_mqa_fwd.1": 0.09,
    }
    share = harness.read_metric("collective_time_share.train", _obs(ops))
    assert share["value"] == pytest.approx(100 * 0.5 / 10.0)
    need = 0.75 * 197e12  # what 1.5 s of kernel time could do at peak / 2
    roof = harness.read_metric(
        "moe_experts_roofline.train",
        _obs(ops, {"moe_expert_flops_traced_per_chip": need}),
    )
    assert roof["value"] == pytest.approx(50.0)
    bare = _obs({"fusion.1": 1.0})
    assert harness.read_metric("collective_time_share.train", bare) is None
    assert harness.read_metric("moe_experts_roofline.train", bare) is None
    assert harness.read_metric("expert_load_imbalance.train", bare) is None
    assert harness.read_metric("train_mfu_per_chip.train", bare) is None
