"""The sparse layer, the compressed-latent mixer and the delta-rule scan,
compiled for a described v5e: nothing runs (``chip_compile_fixture`` says what
these check)."""

import re

import pytest

from chip_compile_fixture import _sds, one_chip  # noqa: F401


@pytest.mark.parametrize("t", [4096, 2048])
def test_sparse_layer_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch, t):
    """``ops/moe.moe_ffn`` forward and backward at OLMoE-1B-7B's published
    widths on one 4096-token row (the four-chip cell's row a chip; 2048 is
    the rung a train step's last microbatch runs at) through
    the megablox grouped matmul with ``_GMM_TILING``: three ``gmm`` forward,
    and three ``gmm`` + three ``tgmm`` backward. ``jax.default_backend()``
    is the CPU here, so the platform's choice is set in the test."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import moe

    monkeypatch.setattr(moe, "grouped_impl", lambda shape=None: "megablox")
    d, f, e, k = 2048, 1024, 64, 8
    assert (t * k) % moe._GMM_TILE_M == 0

    def loss(x, wr, wg, wu, wd, valid):
        out, stats = moe.moe_ffn(
            x, wr, wg, wu, wd, num_experts_per_tok=k, norm_topk_prob=False,
            valid=valid,
        )
        return out.astype(jnp.float32).sum() + stats.aux_loss

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        .lower(
            _sds((1, t, d), jnp.bfloat16, one_chip),
            _sds((d, e), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, f, d), jnp.bfloat16, one_chip),
            _sds((1, t), jnp.bool_, one_chip),
        )
        .compile()
    )
    assert compiled.as_text().count("tpu_custom_call") >= 9


def test_cca_mixer_and_its_core_compile_for_v5e(one_chip):
    """ZAYA1-8B's mixer at its published widths on a 16,384-token row,
    forward and backward: what ``cca_qkv`` computes between the projections
    and the core (8 query and 2 key heads of 128, two taps a convolution),
    then the splash kernel at 4 query heads a key head. The second
    convolution is one batched ``einsum`` over the ten heads a tap, which
    this compiler makes an MXU product ([10, 128, 128] bf16 against the
    row, float32 out): no ``lax.conv_general_dilated`` is needed for it."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from areal_tpu.ops import flash
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    flash._make_kernel.cache_clear()
    mc = MODEL_CONFIGS["zaya1_8b"]()
    t, bf = 16384, jnp.bfloat16
    leaves = transformer._kind_leaves(mc, "cca+moe")
    lp = {
        k: _sds(leaves[k][0], bf, one_chip)
        for k in ("wq", "wk", "wv_own", "wv_prev", "cca_conv0",
                  "cca_conv0_bias", "cca_conv1", "cca_conv1_bias", "cca_temp")
    }

    def loss(h, lp, seg, pos):
        q, k, v = transformer.cca_qkv(
            mc, h, lp, seg, *transformer._rotations(mc, pos)["cca"]
        )
        assert (q.shape, k.shape, v.shape) == (
            (1, t, 8, 128), (1, t, 2, 128), (1, t, 2, 128)
        )
        return flash.flash_segment_attention(
            q, k, v, seg, block_cap=2048  # what the chip's probe settles on
        ).astype(jnp.float32).sum()

    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1)))
            .lower(
                _sds((1, t, 2048), bf, one_chip), lp,
                _sds((1, t), jnp.int32, one_chip),
                _sds((1, t), jnp.int32, one_chip),
            )
            .compile()
        )
    finally:
        flash._make_kernel.cache_clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"f32\[10,128,16384\]\S* convolution\(", text)
    assert re.search(r'areal_scope="cca_mix"', text)
    # and since PR 50 the projections before it and the rotation after it
    assert re.search(r'areal_scope="qkv_proj"', text)
    assert re.search(r'areal_scope="rope"', text)
    # ``tools/program_text.py``: the text without its names, a kernel's
    # serialized body apart and readable without its callers' line numbers
    from tools import program_text

    bare = program_text.stripped(text)
    assert "areal_scope" not in bare and "op_name=" not in bare
    cut, bodies = program_text.kernel_bodies(bare)
    assert len(bodies) == text.count('"body":"') >= 2
    assert len(cut) < len(bare) and "tpu_custom_call" in cut
    assert all("func.func" in b and "loc(" not in b for b in bodies)
    # the two digests the tool prints on the chip move with no name
    renamed = text.replace('areal_scope="cca_mix"', 'areal_scope="mix"')
    assert renamed != text
    assert program_text.digests(renamed) == program_text.digests(text)
    assert program_text.digests(text.replace("convolution(", "dot(", 1)) \
        != program_text.digests(text)


@pytest.mark.parametrize("t", [2048, 8192])
def test_kda_scan_fwd_bwd_compiles_for_v5e(one_chip, t):
    """``ops/kda.kda_scan`` forward and backward at Kimi-Linear's published
    widths (32 heads of 128 | 128) on one packed row: the chunk operands and
    their cotangents as ``chunk_ops_fwd`` / ``chunk_ops_bwd`` (8 chunks a grid
    step taken together, every exponent difference in VMEM, the loops over a
    sub-block's tokens and the substitution's rows rolled: dynamic sublane
    reads, which interpret mode lets through and only this compiler judges),
    then the three state-pass kernels (``kda_fwd_h`` in the forward;
    ``kda_fwd_h_states`` and ``kda_bwd_dh`` in the backward, which recomputes
    the chunk states), 8 chunks a grid step, the state in VMEM.
    ``jax.default_backend()`` is the CPU here, so the platform's choice is
    named in the call."""
    import re

    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kda

    bf, f32 = jnp.bfloat16, jnp.float32

    def loss(q, k, v, g, beta, seg):
        out, stats = kda.kda_scan(q, k, v, g, beta, seg, impl="kernel")
        return out.astype(f32).sum(), stats

    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        .lower(
            *[_sds((1, t, 32, 128), bf, one_chip)] * 3,
            _sds((1, t, 32, 128), f32, one_chip),
            _sds((1, t, 32), f32, one_chip),
            _sds((1, t), jnp.int32, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh",
                 "chunk_ops_fwd", "chunk_ops_bwd"):
        assert re.search(rf"{name}[^_a-z]", text), name
    # the operands' pairwise terms never leave VMEM: no [.., 16, 16, 128]
    assert not re.search(r"f32\[[0-9,]*16,16,128\]", text)
    # no chunk-start states in float32, and none kept beside the inputs
    assert not re.search(rf"f32\[{t // 64},32,128,128\]", text)


def test_kimi_grad_step_lowers_each_operand_kernel_a_bounded_number_of_times(
    one_chip, monkeypatch
):
    """A Pallas kernel is lowered to its Mosaic module in Python at every
    place its call occurs in the lowered text, before the compile cache is
    asked, so a warm start pays for every occurrence (PERF.md, PR 34: nine
    operand kernels a ``grad_step`` cost the cell 95 s of ``setup_s``). The
    kernels sit behind jitted functions, which a module lowers once for
    equal arguments: the tiny ``kimi_linear`` stack laid out as the cell's
    (a leading layer, a scanned run of two, latent attention, a last layer;
    checkpointed) holds ``chunk_ops_fwd`` at most three times (the runs of
    one layer, the scan's body, the backward's recomputation) and
    ``chunk_ops_bwd`` once, however many layers there are. Counted in the
    lowered text (``tools/setup_cost.py``), nothing compiled, nothing timed."""
    import dataclasses

    import jax

    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops import kda
    from areal_tpu.parallel import mesh as mesh_lib
    from tools import setup_cost

    monkeypatch.setattr(kda, "scan_impl", lambda *a: "kernel")
    mc = dataclasses.replace(
        tiny_config("kimi_linear"), num_layers=5,
        layer_kinds=("kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"),
    )
    mesh = mesh_lib.make_mesh(ParallelismConfig(), list(one_chip.device_set))
    grad_step, args = setup_cost.trainer_programs(mc, mesh, 256, None)[
        "grad_step"
    ]
    calls = setup_cost.custom_calls(grad_step.trace(*args).lower().as_text())
    assert 1 <= calls["chunk_ops_fwd"] <= 3, calls
    assert calls["chunk_ops_bwd"] == 1, calls
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh"):
        assert 1 <= calls[name] <= 2, calls


def test_gdn_scan_fwd_bwd_compiles_for_v5e(one_chip):
    """The scalar-decay op at Qwen3-Next's published widths (16 key and 32
    value heads of 128 | 128) on the cell's 16,384-token row, forward and
    backward: its operands and their cotangents as ``gdn_ops_fwd`` /
    ``gdn_ops_bwd`` (8 chunks of a key head's two value heads a grid step,
    sixteen items taken together, the [64, 64] decay matrices and the
    inverse in VMEM), the per-channel form's operand kernels nowhere, and
    the three state-pass kernels the two forms share."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kda

    bf, f32, t = jnp.bfloat16, jnp.float32, 16384

    def loss(q, k, v, g, beta, seg):
        out, stats = kda.kda_scan(q, k, v, g, beta, seg, impl="kernel")
        return out.astype(f32).sum(), stats

    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=range(5), has_aux=True))
        .lower(
            *[_sds((1, t, 16, 128), bf, one_chip)] * 2,
            _sds((1, t, 32, 128), bf, one_chip),
            *[_sds((1, t, 32), f32, one_chip)] * 2,
            _sds((1, t), jnp.int32, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh",
                 "gdn_ops_fwd", "gdn_ops_bwd"):
        assert re.search(rf"{name}[^_a-z]", text), name
    assert "chunk_ops" not in text
    # no decay matrix, mask or inverse a chunk and head goes through HBM
    assert not re.search(r"f32\[[0-9,]*64,64\]", text)


def test_gdn_grad_step_lowers_each_operand_kernel_a_bounded_number_of_times(
    one_chip, monkeypatch
):
    """The twin of the ``chunk_ops_*`` count for the scalar-decay kernels:
    the tiny ``qwen3_next`` stack with a fifth layer behind its full
    attention (a scanned run of three ``gdn`` layers, the full layer, a run
    of one; checkpointed) holds ``gdn_ops_fwd`` at most three times and
    ``gdn_ops_bwd`` once, however many layers there are, and no per-channel
    operand kernel."""
    import dataclasses

    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops import kda
    from areal_tpu.parallel import mesh as mesh_lib
    from tools import setup_cost

    monkeypatch.setattr(kda, "scan_impl", lambda *a: "kernel")
    mc = tiny_config("qwen3_next")
    mc = dataclasses.replace(
        mc, num_layers=5, layer_kinds=mc.layer_kinds + ("gdn+moe",)
    )
    mesh = mesh_lib.make_mesh(ParallelismConfig(), list(one_chip.device_set))
    grad_step, args = setup_cost.trainer_programs(mc, mesh, 256, None)[
        "grad_step"
    ]
    calls = setup_cost.custom_calls(grad_step.trace(*args).lower().as_text())
    assert 1 <= calls["gdn_ops_fwd"] <= 3, calls
    assert calls["gdn_ops_bwd"] == 1, calls
    assert not any(name.startswith("chunk_ops") for name in calls), calls
    for name in ("kda_fwd_h", "kda_fwd_h_states", "kda_bwd_dh"):
        assert 1 <= calls[name] <= 2, calls


@pytest.mark.parametrize("t,c,normed", [
    pytest.param(16384, 8192, 4096, id="qwen3-next-qkv"),
    pytest.param(8192, 4096, 4096, id="kimi-q-or-k"),
    pytest.param(8192, 4096, 0, id="kimi-v"),
])
def test_conv_prep_fwd_bwd_compiles_for_v5e(one_chip, t, c, normed):
    """The delta-rule mixers' convolution kernels (``ops/conv_prep.py``) at
    the two cells' widths on a cell's row, forward and backward: the sublane
    rolls of a 528-row head, the 16-row views above and below a block, the
    float32 accumulator of the taps' cotangent held in VMEM over the grid;
    and nothing float32 of the row's size between them."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import conv_prep

    bf = jnp.bfloat16

    def loss(x, w, seg):
        return conv_prep.conv_prep(x, w, seg, normed).astype(
            jnp.float32
        ).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(
            _sds((1, t, c), bf, one_chip), _sds((c, 4), bf, one_chip),
            _sds((1, t), jnp.int32, one_chip),
        )
        .compile()
        .as_text()
    )
    assert re.search(r"conv_prep_bwd[^_a-z]", text)
    assert not re.search(rf"f32\[1,{t},{c}\]", text)


@pytest.mark.parametrize("chunk", [64, 128])
def test_ssm_scan_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch, chunk):
    """The selective scan's kernels (``ops/ssm_scan.py``) at Jamba2-3B's
    widths on the cell's row [8192, 5120], state 16, forward and backward:
    B and C as scalars from SMEM blocks, 16 vregs of state through a chunk's
    tokens, the backward's VMEM of re-formed states (``chunk`` x 64 KB) and
    its [128, 128] transposes for the sums over the channels."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import ssm_scan

    monkeypatch.setattr(ssm_scan, "CHUNK", chunk)
    f32, t, c, n = jnp.float32, 8192, 5120, 16

    def loss(x, dt, a, bm, cm, seg):
        y, _ = ssm_scan.ssm_scan(x, dt, a, bm, cm, seg, impl="kernel")
        return y.sum()

    # a fresh jit a chunk: the kernels' own ``jit`` is keyed by shapes only
    monkeypatch.setattr(ssm_scan, "_fwd_rows", ssm_scan._fwd_rows.__wrapped__)
    monkeypatch.setattr(ssm_scan, "_bwd_rows", ssm_scan._bwd_rows.__wrapped__)
    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        .lower(
            _sds((1, t, c), jnp.bfloat16, one_chip),
            _sds((1, t, c), f32, one_chip), _sds((c, n), f32, one_chip),
            _sds((1, t, n), f32, one_chip), _sds((1, t, n), f32, one_chip),
            _sds((1, t), jnp.int32, one_chip),
        )
        .compile()
        .as_text()
    )
    assert re.search(r"ssm_scan_fwd[^_a-z]", text)
    assert re.search(r"ssm_scan_bwd[^_a-z]", text)
