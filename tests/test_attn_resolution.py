"""``TrainEngineConfig.attn_impl`` resolved once at ``initialize()`` from what
the process observes (backend, mesh, the model's head dimension), and read
from there by the kernel override (``ops.ring_attention.attention_fn``, where
each kernel's mesh form lives; the probed cap on its tiles goes with it, as a
value) and the dispatch spans.

The backend is the CPU here; "reported as a TPU" means ``jax.default_backend``
is steered in the test (and the block probe with it: it would run the kernel).
Nothing is compiled for the kernel in this file; its numbers are in
``test_flash_parity.py``, its compile for the chip in ``test_chip_compile_attention.py``.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    MicroBatchSpec,
    OptimizerConfig,
    ParallelismConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.sft.lm_engine import sft_loss_fn, sft_loss_weight_fn
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import flash
from areal_tpu.ops.ring_attention import attention_fn
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import tracing


def _engine(attn_impl="auto", parallel=None, head_dim=64, cap=4096):
    cfg = TrainEngineConfig(
        dtype="float32", param_dtype="float32", gradient_checkpointing=False,
        attn_impl=attn_impl,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=cap),
        optimizer=OptimizerConfig(
            type="adamw", lr=1e-2, weight_decay=0.0,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
        ),
        parallel=parallel or ParallelismConfig(),
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8),
        model_config=dataclasses.replace(
            tiny_config("qwen2"), head_dim=head_dim
        ),
        seed=0,
    )
    return eng


@pytest.fixture
def probes(monkeypatch):
    """The block probe, counted and answered without a kernel."""
    calls = []
    monkeypatch.setattr(
        flash, "probe_block_size", lambda: calls.append(1) or 1024
    )
    return calls


@pytest.fixture
def as_tpu(monkeypatch, probes):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return probes


@pytest.fixture
def seen(monkeypatch):
    """Every span opened from here on, as (name, attributes)."""
    spans = []
    span = tracing.span

    def recording(name, *a, **attrs):
        spans.append((name, attrs))
        return span(name, *a, **attrs)

    monkeypatch.setattr(tracing, "span", recording)
    return spans


def _batch(lens):
    rng = np.random.default_rng(0)
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, size=n) for n in lens]
    )
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    return batch


def test_auto_on_the_cpu_backend_is_the_xla_form(probes):
    eng = _engine()
    assert eng.attn_impl == "xla"
    assert attention_fn(eng.attn_impl, eng.mesh) is None
    assert eng._forward_values()["attend_fn"] is None
    assert eng.splash_block is None and not probes


def test_auto_on_a_tpu_is_the_kernel_on_one_device(as_tpu):
    eng = _engine()
    assert eng.attn_impl == "flash"
    assert eng.splash_block == 1024 and len(as_tpu) == 1
    attend = eng._forward_values()["attend_fn"]  # with the engine's own cap
    assert isinstance(attend, functools.partial)
    assert attend.func is flash.flash_segment_attention
    assert attend.keywords == {"block_cap": 1024}


def test_auto_on_a_tpu_mesh_is_the_kernel_under_shard_map(
    as_tpu, monkeypatch
):
    eng = _engine(parallel=ParallelismConfig(
        fsdp_parallel_size=2, tensor_parallel_size=2
    ))
    assert eng.attn_impl == "flash" and eng.mesh.size == 4
    seen = {}

    def shard_map(fn, **kw):
        seen.update(kw, fn=fn)
        return lambda *arrays: "sharded"

    monkeypatch.setattr(jax, "shard_map", shard_map)
    # the shard_map is made where the layer calls (a window-attention layer
    # hands its own window in there)
    assert eng._forward_values()["attend_fn"](*"qkvs") == "sharded"
    assert seen["fn"].func is flash.flash_segment_attention
    assert seen["fn"].keywords == {"block_cap": 1024, "layer_window": 0}
    assert seen["mesh"] is eng.mesh
    # rows over (data, fsdp), heads over tensor, the sequence whole
    assert tuple(seen["out_specs"]) == (("data", "fsdp"), None, "tensor", None)


@pytest.mark.parametrize(
    "attn_impl,parallel,head_dim,resolved",
    [
        pytest.param("auto", ParallelismConfig(seq_parallel_size=2), 64, "xla",
                     id="auto-seq2-as-before"),
        pytest.param("auto", None, 16, "xla", id="auto-head16-refused"),
        pytest.param("auto", None, 96, "xla", id="auto-head96-refused"),
        pytest.param("auto", None, 128, "flash", id="auto-head128"),
        pytest.param("ulysses", None, 64, "xla", id="ulysses-nothing-to-split"),
        pytest.param("ring", ParallelismConfig(seq_parallel_size=2), 64, "ring",
                     id="ring-seq2-kept"),
        pytest.param("flash", None, 16, "flash", id="flash-explicit-kept"),
    ],
)
def test_resolution_on_a_tpu(as_tpu, attn_impl, parallel, head_dim, resolved):
    eng = _engine(attn_impl, parallel, head_dim)
    assert eng.attn_impl == resolved
    assert len(as_tpu) == (resolved == "flash")
    if resolved == "xla":
        assert attention_fn(resolved, eng.mesh) is None
        assert eng.splash_block is None
    if resolved == "ring":
        assert callable(attention_fn(resolved, eng.mesh))


def test_explicit_flash_on_the_cpu_backend_keeps_its_name_unprobed(probes):
    eng = _engine("flash")
    assert eng.attn_impl == "flash"
    assert eng.splash_block is None and not probes


@pytest.mark.parametrize(
    "caps", [(512, 2048), (2048, 512), (1024, 1024), (512, 1024, 2048)],
    ids=lambda caps: "-".join(map(str, caps)),
)
def test_engines_in_one_process_keep_their_own_caps(monkeypatch, caps):
    """The probed cap is the engine's value, handed down with its attention:
    an engine that probes later (another backend state, another answer)
    moves no other engine's tiles. At the parent the cap was a module global
    the last probe wrote."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engines = []
    for cap in caps:
        monkeypatch.setattr(flash, "probe_block_size", lambda cap=cap: cap)
        engines.append(_engine())
    row = np.ones((1, 4096), np.int32)  # one sequence: the whole triangle
    for eng, cap in zip(engines, caps):
        assert eng.splash_block == cap
        assert eng._forward_values()["attend_fn"].keywords == {
            "block_cap": cap
        }
        n = 4096 // min(cap, 1024)  # the rule's 1024 at this row, capped
        assert eng._count_attn_tiles(row) == {
            "attn_tiles": n * (n + 1) // 2, "attn_tiles_kept": n * (n + 1) // 2
        }


# batches of ONE row length whose longest sequences fell in different
# buckets of the packing window PR 55 had (1024 | full causal at a 2048-token
# row), each of which was a program of its own on the kernel's path
_ONE_ROW_LENGTH = [
    pytest.param(2048, [[600, 700, 500], [1500, 300], [1100, 900]],
                 id="row2048"),
    pytest.param(2048, [[30, 17, 1024], [1025, 900]],
                 id="row2048-bucket-edges"),
]


@pytest.mark.parametrize("row_len,batches", _ONE_ROW_LENGTH)
def test_one_row_length_is_one_program_whatever_the_sequences(
    as_tpu, monkeypatch, seen, row_len, batches
):
    """``grad_step`` and ``logp_forward`` are keyed by what the caller handed
    in and traced once a row length. (The kernel is the chip's: the programs
    here run the XLA form under the resolved name.)"""
    import areal_tpu.engine.spmd_engine as spmd

    monkeypatch.setattr(spmd, "attention_fn", lambda *a, **kw: None)
    eng = _engine(cap=16384)
    assert eng.attn_impl == "flash"
    traces = []

    def loss_fn(*a, **kw):  # runs when ``grad_step`` is traced, only then
        traces.append(1)
        return sft_loss_fn(*a, **kw)

    for i, lens in enumerate(batches):
        batch = _batch(lens)
        eng.train_batch(batch, loss_fn, sft_loss_weight_fn)
        eng.forward(batch)
        if i == 0:
            first = len(traces)
    assert len(traces) == first > 0  # the first batch's trace serves all
    assert set(eng._jit_cache) == {
        "zeros", "apply", ("grad", loss_fn, sft_loss_weight_fn),
        ("fwd", spmd.target_aligned_logprobs),
    }
    logp_fn = eng._jit_cache["fwd", spmd.target_aligned_logprobs]
    assert logp_fn._cache_size() == 1
    # one microbatch a batch: a grad, an apply and a logp dispatch each
    spans = [a for n, a in seen if n.endswith("_dispatch")]
    assert len(spans) == 3 * len(batches)
    assert not any("window" in a for a in spans)
    assert eng.counters()["padded_tokens"] == 2 * len(batches) * row_len


def test_dispatch_spans_carry_the_resolved_name_and_no_window(seen):
    eng = _engine()
    batch = _batch([9, 5, 7])
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    eng.forward(batch)
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        attrs = [a for n, a in seen if n == name]
        assert attrs, name
        assert all(
            a["attn_impl"] == "xla" and "window" not in a for a in attrs
        ), attrs


def test_flash_dispatches_count_the_tiles_their_rows_keep(
    as_tpu, monkeypatch, seen
):
    """Where ``attn_impl`` is ``flash`` the engine counts, on the host and by
    ``ops.flash.tile_counts``, the tiles of every dispatched row and those
    the kernel computes: in ``counters()``, and on the dispatch spans. The
    kernel itself is the chip's; the programs here run the
    XLA form under the resolved name."""
    import areal_tpu.engine.spmd_engine as spmd

    monkeypatch.setattr(spmd, "attention_fn", lambda *a, **kw: None)
    eng = _engine()  # 2048-token rows: 4 x 4 tiles of 512
    assert eng.attn_impl == "flash"
    assert eng.counters() == {
        "real_tokens": 0, "padded_tokens": 0,
        "attn_tiles": 0, "attn_tiles_kept": 0,
    }
    # one row of 2048, longest first: borders at 700 and 1300, padding from
    # 1800. Of the 10 tiles on and under the diagonal three are off: (2, 0)
    # (block 2 starts in the second sequence, block 0 ends in the first) and
    # (3, 0), (3, 1) (block 3 holds the third sequence and padding)
    batch = _batch([600, 700, 500])
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    assert eng.counters()["attn_tiles"] == 10
    assert eng.counters()["attn_tiles_kept"] == 7
    eng.forward(batch)
    assert eng.counters()["attn_tiles"] == 20
    assert eng.counters()["attn_tiles_kept"] == 14
    for name in ("train.grad_dispatch", "train.logp_dispatch"):
        (attrs,) = [a for n, a in seen if n == name]
        assert attrs["attn_impl"] == "flash" and "window" not in attrs
        assert (attrs["attn_tiles"], attrs["attn_tiles_kept"]) == (10, 7)


def test_a_grad_dispatch_of_a_model_without_a_window_says_its_instance(
    as_tpu, monkeypatch, seen
):
    """Every model that runs the splash core says the full core's tile edge
    and backward on its grad dispatch spans (PR 57), from
    ``ops.flash.instance`` at the row's length, the model's heads and the
    engine's own cap; a recompute dispatch runs no backward and says none."""
    import areal_tpu.engine.spmd_engine as spmd

    monkeypatch.setattr(spmd, "attention_fn", lambda *a, **kw: None)
    eng = _engine()  # 2048-token rows; the probe's answer here: 1024
    mc = eng.model_config
    assert eng.attn_impl == "flash" and mc.swa is None
    batch = _batch([600, 700, 500])
    eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
    eng.forward(batch)
    (attrs,) = [a for n, a in seen if n == "train.grad_dispatch"]
    inst = flash.instance(
        2048, mc.head_dim, block_cap=eng.splash_block, heads=mc.num_heads
    )
    assert inst == ("splash", 0, 512, True, 512)
    assert (attrs["attn_edge"], attrs["attn_bwd_full"]) == (512, "fused")
    assert "attn_bwd_window" not in attrs and attrs["attn_tiles"] == 10
    for name, attrs in seen:
        if name == "train.logp_dispatch":
            assert "attn_edge" not in attrs and "attn_bwd_full" not in attrs


@pytest.mark.parametrize("family", ["qwen2", "KeyeVL2"])
def test_other_attentions_count_no_tiles(seen, family):
    """``xla`` (the CPU's ``auto``) and ``sparse_topk`` (a model with an
    indexer) carry neither counter nor span attribute."""
    cfg = TrainEngineConfig(
        dtype="float32", param_dtype="float32", gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8), model_config=tiny_config(family),
        seed=0,
    )
    assert eng.attn_impl == ("sparse_topk" if family == "KeyeVL2" else "xla")
    eng.forward(_batch([9, 5, 7]))
    assert not {"attn_tiles", "attn_tiles_kept"} & set(eng.counters())
    (attrs,) = [a for n, a in seen if n == "train.logp_dispatch"]
    assert not {"attn_tiles", "attn_tiles_kept"} & set(attrs)
