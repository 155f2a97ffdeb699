"""AOT memory-feasibility analysis (parallel/feasibility.py): the real
GRPO grad+apply programs lower against a virtual mesh without
materializing weights, and the per-device verdict is sane."""

import jax
import pytest

from areal_tpu.api.cli_args import ParallelismConfig
from areal_tpu.models.config import tiny_config
from areal_tpu.parallel import feasibility as F


def test_tiny_model_fits_and_reports():
    rep = F.grpo_step_memory(
        tiny_config("qwen2"),
        ParallelismConfig(fsdp_parallel_size=8),
        bucket=1024,
        hbm_limit_gb=16.0,
    )
    assert rep["n_devices"] == 8
    assert rep["mesh"] == {"fsdp": 8}
    assert rep["model_params_m"] > 0
    for prog in ("grad_step", "apply_step"):
        assert rep[prog]["live_gb"] >= 0
    # a 0.1M-param step trivially fits 16 GB
    assert rep["fits"]
    assert 0 < rep["peak_per_device_gb"] <= 16.0


def test_limit_verdict_flips():
    rep = F.grpo_step_memory(
        tiny_config("qwen2"),
        ParallelismConfig(fsdp_parallel_size=8),
        bucket=1024,
        hbm_limit_gb=1e-6,  # nothing fits a 1 KB chip
    )
    assert not rep["fits"]


def test_flagship_configs_shapes():
    assert F.qwen2_7b_config().hidden_size == 3584
    assert F.qwen2_1p5b_config().tie_word_embeddings


@pytest.mark.parametrize(
    "family,mesh_forms",
    [
        pytest.param("olmoe", ["make_sharded_moe_ffn"], id="sparse"),
        pytest.param("qwen2", [], id="dense"),
    ],
)
def test_the_priced_program_is_the_engines_with_each_layers_mesh_form(
    monkeypatch, family, mesh_forms
):
    """``grpo_step_memory`` lowers the builders the engine dispatches from
    (``engine/programs.py``), so under a mesh a sparse model's layers run
    per device inside their ``shard_map`` as they do in the engine: a copy
    of the step that passed no ``moe_fn`` priced GSPMD over the sort, a
    program no engine runs."""
    from areal_tpu.ops import moe

    built = []
    shard_map = jax.shard_map

    def recording(fn, **kw):
        built.append((fn.__qualname__.split(".")[0], kw))
        return shard_map(fn, **kw)

    monkeypatch.setattr(jax, "shard_map", recording)
    rep = F.grpo_step_memory(
        tiny_config(family),
        ParallelismConfig(fsdp_parallel_size=4),
        bucket=256,
        seqs_per_row=4,
    )
    assert [name for name, _ in built] == mesh_forms
    for _, kw in built:
        weights, tokens, valid = kw["in_specs"]
        assert set(weights) == set(moe.EXPERT_WEIGHTS)
        assert tuple(tokens) == (("data", "fsdp"), "seq", None)
        # a chip holds whole experts, a quarter of them here: the stacks
        # come in split on their expert dimension and on no model width,
        # and the router as ZeRO-3 holds it
        for stack in moe.EXPERT_WEIGHTS[1:]:
            assert weights[stack][0] == ("expert", "fsdp")
            assert "fsdp" not in tuple(weights[stack])[1:]
        assert tuple(weights["w_router"]) == ("fsdp", None)
    assert rep["mesh"] == {"fsdp": 4} and rep["fits"]
    assert "remat_save_attn" not in rep
