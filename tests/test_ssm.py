"""The jamba family (a stack of two block kinds: a Mamba-1 selective scan
with the family's inner norms, and attention without a rotation, over a dense
FFN each) at test size against the plain reference
``benchmark/reference_jamba.py``: every block kind, the logits alone and
packed, the loss's gradient of every leaf, the scan's kernels in interpret
mode against its ``lax.scan`` form, the border of a packed row bit for bit,
``fsdp=4`` against one device, the trainer's counts and span attributes, and
the refusals' sentences."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_battery as fb
from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config, tiny_config
from areal_tpu.ops import ssm_scan as ssm
from benchmark import reference_jamba as ref
from benchmark.kinds import train_steps_ssm

CUT = "jamba2-3b.json"
TOTAL = 150  # no multiple of the scan's chunk (64)

FAMILY = fb.Family(
    "jamba", train_steps_ssm.tiny_configs, ref.logits, total=TOTAL,
)
tiny = fb.tiny_fixture(FAMILY)
trained = fb.trained_fixture()


def test_tiny_config_is_the_family_and_the_catalogs_config_builds():
    t = tiny_config("jamba")
    assert t.layer_kinds == (
        "ssm+dense", "full+dense", "ssm+dense", "ssm+dense"
    )
    assert (t.ssm.d_inner, t.ssm.d_state, t.ssm.dt_rank) == (128, 16, 4)
    assert (t.num_heads, t.num_kv_heads, t.full.nope) == (4, 1, True)
    assert from_hf_config(train_steps_ssm.tiny_configs()[1]) == t
    full = from_hf_config(fb.published("AI21-Jamba2-3B"))
    cut = from_hf_config(fb.cut(CUT))  # the depth, and nothing else
    assert cut == dataclasses.replace(
        full, num_layers=22, layer_kinds=full.layer_kinds[:22]
    )
    assert cut.num_ssm_layers == 20 and cut.layer_kinds[21] == "full+dense"
    assert [i for i, k in enumerate(full.layer_kinds) if k[:4] == "full"] == [
        7, 21
    ]
    assert full.num_ssm_layers == 26 and full.head_dim == 128
    assert tr._runs(full.layer_kinds) == [
        ("ssm+dense", 7), ("full+dense", 1), ("ssm+dense", 13),
        ("full+dense", 1), ("ssm+dense", 6),
    ]
    shapes = jax.eval_shape(
        lambda: tr.init_params(full, jax.random.PRNGKey(0), jnp.float32)
    )
    assert tr.count_params(shapes) == 3_029_337_472
    assert tr._rotated_width(full) == 0  # nothing turns


@pytest.mark.parametrize("bad,word", [
    ({"num_experts": 16, "num_experts_per_tok": 2}, "num_experts 16"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"mamba_conv_bias": False}, "without its bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
])
def test_what_the_family_may_say_and_the_program_lacks_is_refused(bad, word):
    fb.check_refused(CUT, bad, word)


@pytest.mark.parametrize("kind", ["ssm+dense", "full+dense"])
def test_each_block_kind_matches_the_reference(tiny, kind):
    stats = fb.check_block(
        tiny, kind, lambda x, lp: ref.layer(tiny.keys, kind, x, lp)[0]
    )
    assert (stats[1] is not None) == kind.startswith("ssm")
    assert stats[0] is None  # a dense FFN reports nothing


@pytest.mark.parametrize("lens", [[137], [70, 33, 21]])
def test_logits_match_alone_and_packed_with_padding(tiny, lens):
    # borders inside the convolution's reach and inside a chunk
    _, stats, _ = fb.check_logits(FAMILY, tiny, lens)
    assert [int(stats.counts[k]) for k in ("ssm_tokens", "ssm_segments")] == [
        sum(lens) * 3, len(lens) * 3
    ]


def test_loss_and_every_gradient_match_the_references(tiny):
    seen = fb.check_gradients(FAMILY, tiny, n=45)
    assert {"w_in", "conv_x", "conv_x_bias", "w_x", "dt_norm", "b_norm",
            "c_norm", "w_dt", "dt_bias", "a_log", "d_skip", "wo", "wq", "wk",
            "wv", "w_gate", "embedding"} <= set(seen)


def test_the_references_layerwise_gradient_is_jax_grad_of_its_logits(tiny):
    """``ref.loss_and_grads`` (``jax.vjp`` a layer at a time, weights on the
    host) against ``jax.grad`` through ``ref.logits`` whole."""
    rng = np.random.default_rng(3)
    seqs = []
    for n in (23, 9):
        seqs.append(dict(
            tokens=rng.integers(0, 128, n).astype(np.int32),
            behav_logp=(rng.standard_normal(n - 1) * 0.1 - 4.8).astype("f4"),
            advantages=rng.standard_normal(n - 1).astype("f4"),
            mask=(np.arange(n - 1) >= 3).astype(np.int32),
        ))
        seqs[-1]["prox_logp"] = seqs[-1]["behav_logp"] + 0.05
    loss, grads = ref.loss_and_grads(tiny.keys, tiny.params, seqs)

    def whole(p):
        total = sum(int(s["mask"].sum()) for s in seqs)
        out = 0.0
        for s in seqs:
            lg = ref.logits(tiny.keys, p, s["tokens"])[:-1]
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), s["tokens"][1:, None], axis=-1
            )[:, 0]
            out = out + ref.grpo_loss(
                logp, s["behav_logp"], s["prox_logp"], s["advantages"],
                s["mask"], 0.2, 5.0,
            )
        return out / total

    want_loss, want = jax.jit(jax.value_and_grad(whole))(tiny.params)
    assert abs(loss - float(want_loss)) < 1e-6
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(fb.host(want))):
        np.testing.assert_allclose(g, w, atol=2e-6 + 1e-4 * np.abs(w).max())


# ------------------------------------------------------------- the scan --
def _scan_inputs(b, t, c, n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, t, c)).astype(np.float32)
    dt = np.exp(
        rng.uniform(np.log(1e-3), np.log(0.3), (b, t, c))
    ).astype(np.float32)
    a = -(np.arange(1, n + 1, dtype=np.float32)
          * rng.uniform(0.5, 1.5, (c, 1)).astype(np.float32))
    bm, cm = (rng.standard_normal((2, b, t, n))).astype(np.float32)
    return x, dt, a, bm, cm


def _packed(t, lens):
    seg = np.zeros((1, t), np.int32)
    at = 0
    for i, n in enumerate(lens):
        seg[0, at:at + n] = i + 1
        at += n
    return seg


@functools.lru_cache(maxsize=None)
def _both_forms(t, lens):
    """(value, y, cotangents) of the ``lax.scan`` form and of the kernels in
    interpret mode on one seeded packed row [1, t, 1024]."""
    args = _scan_inputs(1, t, ssm.CHANNELS, 16)
    seg = _packed(t, lens)
    w = fb.normal(5, 1, t, ssm.CHANNELS)

    def run(impl, interpret):
        def f(*a):
            y, stats = ssm.ssm_scan(*a, seg, impl=impl, interpret=interpret)
            return (y * w).sum(), (y, stats)

        (_, (y, stats)), g = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(*args)
        return np.asarray(y), fb.host(g), stats

    return run("xla", False), run("kernel", True)


@pytest.mark.parametrize("what", ["y", "x", "dt", "a", "b", "c"])
def test_the_kernels_in_interpret_mode_are_the_lax_scan_form(what):
    """Forward and every cotangent, a row of three sequences and padding
    whose lengths are no multiple of the chunk (150 = 2 chunks + 22)."""
    (y0, g0, s0), (y1, g1, s1) = _both_forms(TOTAL, (70, 47, 20))
    assert (int(s0.tokens), int(s0.segments)) == (137, 3) == (
        int(s1.tokens), int(s1.segments)
    )
    if what == "y":
        np.testing.assert_allclose(y1, y0, atol=1e-5)
        assert not y1[0, 137:].any()  # zero at padding
    else:
        i = ["x", "dt", "a", "b", "c"].index(what)
        scale = np.abs(g0[i]).max()
        np.testing.assert_allclose(g1[i], g0[i], atol=2e-6 * scale + 1e-6)


def _plain(interpret):
    impl = "kernel" if interpret else "xla"
    return jax.jit(lambda *a: ssm.ssm_scan(
        *a, impl=impl, interpret=interpret
    )[0])


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_the_border_of_a_packed_row_bit_for_bit(interpret):
    """``scan_exact`` (b): moving x at one token of the second sequence
    changes that sequence from that token on and no other slot."""
    args = _scan_inputs(1, TOTAL, ssm.CHANNELS, 16, seed=1)
    seg = _packed(TOTAL, (70, 47, 20))
    got = train_steps_ssm.border_check(_plain(interpret), *args, seg)
    assert got["ok"] and got["moved_outside"] == 0
    assert (got["token"], got["sequence_end"]) == (70 + 23, 117)


def test_a_state_kept_across_a_border_fails_the_border_check():
    """The check can see what it guards: a scan that ignores the row's
    segments (one sequence a row) leaks into the next sequence."""
    args = _scan_inputs(1, TOTAL, ssm.CHANNELS, 16, seed=1)
    seg = _packed(TOTAL, (70, 47, 20))
    one = np.where(seg > 0, 1, 0)
    leaky = _plain(False)
    got = train_steps_ssm.border_check(
        lambda x, dt, a, bm, cm, _: leaky(x, dt, a, bm, cm, one), *args, seg
    )
    assert not got["ok"] and got["moved_outside"] > 0


def test_the_kernel_form_is_chosen_by_the_platform_and_the_channels():
    assert ssm.scan_impl(5120) == "xla"  # no TPU here
    assert tr.span_attrs(tiny_config("jamba"))["ssm_impl"] == "xla"
    assert ssm.CHANNELS == 1024 and 5120 % ssm.CHANNELS == 0


# ------------------------------------------------------------ the meshes --
def test_fsdp_4_gives_the_one_device_loss_and_gradients():
    """Four CPU devices, a row a device, every leaf under the mesh's rules
    (the [C, N], [C, K] and [C] leaves over their channels): the hidden
    state's weighted sum and its gradient of every leaf are the one-device
    ones."""
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.engine.programs import _forward_fn
    from areal_tpu.parallel import mesh as mesh_lib
    from areal_tpu.parallel import sharding as sharding_lib

    if jax.device_count() < 4:
        pytest.skip("fewer than four devices")
    cfg = tiny_config("jamba")
    params = fb.init(cfg, 1)
    rows = [fb.row([20, 11], 32, cfg.vocab_size, seed=i) for i in range(4)]
    tok, seg, pos = (np.concatenate([r[j] for r in rows]) for j in range(3))
    arrays = {"tokens": tok, "segment_ids": seg, "positions": pos}
    w = fb.normal(7, 4, 32, cfg.hidden_size)
    mesh = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=4))
    one = mesh_lib.make_mesh(ParallelismConfig(), jax.devices()[:1])
    specs = sharding_lib.tree_shardings(mesh, tr.param_logical_axes(cfg))
    ssm_specs = specs["layers"]["ssm+dense"]
    for leaf, axis in (("a_log", 1), ("conv_x", 1), ("d_skip", 1),
                       ("dt_bias", 1), ("w_x", 1), ("w_dt", 2), ("w_in", 1)):
        assert ssm_specs[leaf].spec[axis] == "fsdp", leaf

    def value_and_grads(m, p):
        forward = _forward_fn(cfg, m, jnp.float32, None, remat=True)
        return jax.jit(jax.value_and_grad(
            lambda p, a: (forward(p, a).hidden * w).sum()
        ))(p, arrays)

    v4, g4 = value_and_grads(mesh, jax.device_put(params, specs))
    v1, g1 = value_and_grads(one, params)
    np.testing.assert_allclose(float(v4), float(v1), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(fb.host(g4)),
                    jax.tree_util.tree_leaves(fb.host(g1))):
        np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max() + 1e-7)


# ----------------------------------------------------------- the trainer --
def test_the_trainer_trains_the_family_and_counts_its_scans_exactly(
    tiny, trained
):
    engine, lens, stats = trained.engine, trained.lens, trained.stats
    assert (engine.attn_impl, engine.ssm_impl) == ("xla", "xla")
    counters = engine.counters()
    assert counters["ssm_tokens"] == sum(lens) * tiny.cfg.num_ssm_layers
    assert counters["ssm_segments"] == len(lens) * tiny.cfg.num_ssm_layers
    assert sum(s["ssm_tokens"] for s in stats) == counters["ssm_tokens"]
    assert trained.exported["spmd/ssm_tokens"] > 0
    trained.moved(
        "ssm+dense", "a_log", "dt_bias", "conv_x", "conv_x_bias", "w_x",
        "w_dt", "d_skip", "dt_norm", "b_norm", "c_norm", "w_in", "wo",
    )
    trained.moved("full+dense", "wq", "wk", "wv", "wo")


@pytest.mark.parametrize("span", ["train.grad_dispatch", "train.logp_dispatch"])
def test_the_dispatch_spans_say_what_the_scan_runs(trained, span):
    attrs = trained.attrs(span)[0]
    assert (attrs["ssm_impl"], attrs["ssm_chunk"],
            attrs["ssm_channel_block"]) == ("xla", ssm.CHUNK, ssm.CHANNELS)


# ---------------------------------------------------------- the refusals --
GENERATION = (
    "the generation engine cannot run a state-space mixer (family 'jamba'): "
    "a layer's cache is a recurrent state [128, 16] and the convolution's "
    "last tokens, not KV pages (ROADMAP B.4); the trainer can"
)
SEQ_PARALLEL = (
    "a selective scan (family 'jamba': a state a channel that passes from "
    "token to token) has no sequence-parallel form (attn_impl='ring', "
    "seq_parallel_size=2): the state would have to be handed from device to "
    "device, ROADMAP B"
)


def test_the_generation_engine_refuses_with_its_sentence(tiny):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine

    assert tr.generation_refusal(tiny.cfg) == GENERATION
    with pytest.raises(NotImplementedError) as e:
        GenerationEngine(JaxGenConfig(), model_config=tiny.cfg,
                         params=tiny.params)
    assert str(e.value) == GENERATION


def test_sequence_parallelism_refuses_with_its_sentence(tiny):
    from areal_tpu.ops.flash import resolve_attn_impl

    forced, why = tr.seq_parallel_refusal(tiny.cfg)
    assert forced == ""
    assert why.format(attn_impl="ring", seq_parallel_size=2) == SEQ_PARALLEL
    with pytest.raises(ValueError) as e:
        resolve_attn_impl("ring", 2, (16, 16), forced, why)
    assert SEQ_PARALLEL in str(e.value)
    assert resolve_attn_impl("auto", 1, (16, 16), forced, why) == "xla"


def test_a_checkpoint_has_no_layout_and_says_so(tiny, tmp_path):
    from areal_tpu.models import hf_io

    with pytest.raises(NotImplementedError, match="mamba.in_proj"):
        hf_io.save_params(tiny.params, tiny.cfg, str(tmp_path))
