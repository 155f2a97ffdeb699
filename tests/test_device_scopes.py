"""Device work keeps its names: every scope of ``tracing.DEVICE_SCOPES`` is in
the ``op_name`` metadata of the program that computes it, every program of
the contract (PERF.md, "Spans and counters") lowers under its name, the
scopes change no number, and the trainer counts what it dispatches."""

import contextlib
import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import (
    MicroBatchSpec,
    OptimizerConfig,
    ParallelismConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine import programs
from areal_tpu.engine.ppo.actor import PPOActor, _ppo_loss_weight_fn
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.inference import model_runner
from areal_tpu.inference.cache import CacheConfig, init_kv_pool
from areal_tpu.models.config import tiny_config
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import tracing

S, BS, PPS = 4, 16, 4


def _actor(remat=True, family="qwen2", **parallel):
    cfg = PPOActorConfig(
        parallel=ParallelismConfig(**parallel),
        dtype="float32", param_dtype="float32",
        gradient_checkpointing=remat,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=64),
        optimizer=OptimizerConfig(
            type="adamw", lr=1e-2, weight_decay=0.0,
            warmup_steps_proportion=0.0, lr_scheduler_type="constant",
        ),
        ppo_n_minibatches=1, group_size=2, recompute_logprob=True,
        use_decoupled_loss=True,
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(
        ft_spec=FinetuneSpec(1, 64, 8), model_config=tiny_config(family),
        seed=0,
    )
    return PPOActor(cfg, eng), eng


def _batch(lens=(5, 9, 12, 7), vocab=128, seed=0, denoise=False):
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm = np.array(batch["attention_mask"], np.int32)
    lm[:, :2] = 0
    batch["loss_mask"] = lm
    if denoise:  # a block-diffusion policy's: two states, -1 off the loss
        batch["denoise_state"] = np.where(
            lm > 0, np.arange(lm.shape[1])[None] % 2, -1
        ).astype(np.int32)
    batch["logprobs"] = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1 - 4.8
    ) * lm
    batch["versions"] = np.where(lm > 0, 0, -1).astype(np.int32)
    batch["rewards"] = np.asarray([0.0, 1.0] * (len(lens) // 2), np.float32)
    return batch


def _op_names(lowered):
    """(HLO text, the op_name metadata of its instructions) as compiled:
    what the profiler's trace carries per operation."""
    text = lowered.compile().as_text()
    return text, set(re.findall(r'op_name="([^"]+)"', text))


def _scopes_in(names):
    return {
        s for s in tracing.DEVICE_SCOPES
        if any(re.search(rf"(^|[/(]){s}([/)]|$)", n) for n in names)
    }


_ENGINES = {}  # family -> the engine ``_train_programs`` built for it
_PACKED = {}  # family -> (its actor, the arrays its ``grad_step`` was lowered on)


def _lower_grad_step(eng, actor, arrays, **values):
    """``grad_step`` of ``eng`` traced and lowered on ``arrays`` (under
    whatever scopes are patched in), with ``values`` over the engine's own
    (``compute_dtype=``)."""
    grad_fn = programs.build_grad_step(
        **dict(eng._forward_values(), **values), remat=True,
        loss_fn=actor._loss_fn, loss_weight_fn=_ppo_loss_weight_fn,
    )
    return grad_fn.lower(eng.params, eng._zero_grads(), arrays)


def _lower_again(family, **values):
    """The same of the engine ``_train_programs`` built for ``family``."""
    return _lower_grad_step(_ENGINES[family], *_PACKED[family], **values)


def _train_programs(family="qwen2", only_grad=False, **parallel):
    """(name, lowered) of the trainer's three programs at the tiny config;
    ``only_grad``: of ``grad_step`` alone, and no program is compiled or run
    on the way (a lowering reads the proximal logprobs' shape, so zeros
    serve where ``logp_forward`` would have to be built to compute them)."""
    actor, eng = _actor(family=family, **parallel)
    denoise = eng.model_config.block_diffusion is not None
    batch = actor.compute_advantages(_batch(denoise=denoise))
    if only_grad:
        batch["prox_logp"] = np.zeros_like(batch["logprobs"])
    else:
        batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    _, arrays = eng._pack_for_device(batch)
    if not parallel:
        _ENGINES[family], _PACKED[family] = eng, (actor, arrays)
    grad_step = ("grad_step", _lower_grad_step(eng, actor, arrays))
    if only_grad:
        return [grad_step]
    logp_fn = next(
        f for k, f in eng._jit_cache.items()
        if isinstance(k, tuple) and k[0] == "fwd"
    )
    w = jnp.ones((), jnp.float32)
    return [
        grad_step,
        ("logp_forward", logp_fn.lower(eng.params, arrays)),
        ("apply_step", programs.build_apply_step(
            eng.optimizer, eng._param_shardings
        ).lower(
            eng.params, eng.opt_state, eng._zero_grads(), w)),
    ]


def _decode_programs():
    cfg = tiny_config("qwen2")
    from areal_tpu.models.transformer import init_params

    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ccfg = CacheConfig(num_pages=S * PPS + 1, page_size=BS, max_model_len=64)
    cache = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(S)[:, None] * PPS + np.arange(PPS)[None], jnp.int32
    )
    zi, ones = jnp.zeros(S, jnp.int32), jnp.ones(S)
    fwd = model_runner._decode_multi_forward.lower(
        params, cfg, cache, tables, zi + 3, zi, jnp.ones(S, bool),
        zi + 100, zi, jnp.full((S, 4), -1, jnp.int32),
        jax.random.PRNGKey(0), ones, ones, zi, jnp.zeros(S, bool),
        steps=3, slot_ids=jnp.arange(S, dtype=jnp.int32),
    )
    nl, hkv = cfg.num_layers, cache["k"].shape[1]
    fd = cache["k"].shape[-1]
    rows = jnp.zeros((8, nl, hkv, fd), jnp.float32)
    write = model_runner.write_rows.lower(
        cache, jnp.zeros(8, jnp.int32), rows, rows
    )
    return [("_decode_multi_forward", fwd), ("write_rows", write)]


# what PR 50 named of the third of every window that had no name: opened
# only around code that was under no scope, so no older scope's border moved
NEW_SCOPES = (
    "qkv_proj", "rope", "attn_gate", "o_proj", "stream_norm", "residual",
    "param_cast", "grad_add", "layer_stack",
)
OLD_SCOPES = tuple(s for s in tracing.DEVICE_SCOPES if s not in NEW_SCOPES)
# around every mixer's core and FFN, whatever the family
_AROUND = {"qkv_proj", "o_proj", "stream_norm", "residual", "layer_stack"}
WANT = {
    "grad_step": {"embed", "attention", "mlp", "lm_head", "loss", "rope",
                  "grad_add"} | _AROUND,
    "logp_forward": {"embed", "attention", "mlp", "lm_head", "loss", "rope"}
    | _AROUND,
    # the float32 tree cast to a compute dtype that is another (the tiny
    # engines compute in float32, where the cast is no operation)
    "bf16_grad_step": {"param_cast", "grad_add"} | _AROUND,
    "apply_step": {"optimizer"},
    "_decode_multi_forward": {"attention", "mlp", "lm_head", "sample"},
    "write_rows": {"kv_write"},
    # a sparse layer's three, inside its mlp (innermost wins in a profile)
    "sparse_grad_step": {"mlp", "moe_route", "moe_dispatch", "moe_experts",
                         "rope"} | _AROUND,
    # on a mesh that splits the experts: the tokens gathered to the chips
    # that hold them, the chips' parts summed and scattered back
    "mesh_sparse_grad_step": {"mlp", "moe_route", "moe_exchange",
                              "moe_dispatch", "moe_experts"},
    # learned sparse attention: the indexer and the selection before the
    # core, which stays under attention
    "dsa_grad_step": {"attention", "dsa_index", "dsa_select", "moe_experts",
                      "rope"} | _AROUND,
    # a stack of block kinds: the delta rule and what the layer computes
    # around it, beside the latent layer's attention and the experts
    "kda_grad_step": {"kda_scan", "kda_prep", "attention", "moe_experts",
                      "mlp"} | _AROUND,
    # two rotary kinds: the window layers' core under its own scope, NOT
    # inside attention, which keeps the full layers' core
    "swa_grad_step": {"attn_window", "attention", "moe_experts", "mlp",
                      "rope", "attn_gate"} | _AROUND,
    # a convolutional latent mixer: what it computes between its projections
    # and its core under its own scope, the core under attention, the MLP
    # router under moe_route
    "cca_grad_step": {"cca_mix", "attention", "moe_route", "moe_experts",
                      "mlp", "rope"} | _AROUND,
    # the delta rule with one decay a head: its operands under a scope of
    # their own inside kda_scan, beside the gated full layer's core and gate
    "gdn_grad_step": {"kda_scan", "kda_prep", "gdn_operands", "attention",
                      "attn_gate", "moe_experts", "mlp", "rope"} | _AROUND,
    # a rotated latent mixer on every layer and the draft module beside the
    # stack: the latent's expansion and what the module alone computes under
    # scopes of their own; the module runs in grad_step and nowhere else
    "mla_grad_step": {"mla_expand", "mtp", "attention", "moe_experts", "mlp",
                      "rope", "lm_head", "loss"} | _AROUND,
    "mla_logp_forward": {"mla_expand", "attention", "mlp", "rope", "lm_head"},
    # a block-diffusion policy's rows: the core under its own scope, NOT
    # inside attention (no causal core runs), and what makes its rows' codes
    # and counts the pairs
    "bd_grad_step": {"attn_bd", "bd_layout", "moe_experts", "mlp", "rope",
                     "lm_head", "loss"} | _AROUND,
    # a state-space hybrid: the selective scan and what the layer computes
    # around it under scopes of their own, the NoPE layers' core under
    # attention; nothing turns, so no rope
    "ssm_grad_step": {"ssm_scan", "ssm_prep", "attention", "mlp", "lm_head",
                      "loss"} | _AROUND,
}
# a family's ``grad_step`` among the programs of ``lowered``
FAMILIES = {"qwen2": "grad_step", "olmoe": "sparse_grad_step",
            "KeyeVL2": "dsa_grad_step", "kimi_linear": "kda_grad_step",
            "laguna": "swa_grad_step", "zaya": "cca_grad_step",
            "qwen3_next": "gdn_grad_step",
            "joyai_llm_flash": "mla_grad_step", "sdar_moe": "bd_grad_step",
            "jamba": "ssm_grad_step"}
# the five families whose programs a change for a sixth may not move: the
# lowered grad_step's text (the persistent compile cache's key is made of
# it), by sha256, in tests/golden/grad_step_stablehlo.json. A PR that means
# to change one of these programs writes the file anew and says so:
#     AREAL_REGEN_GOLDEN=1 python -m pytest tests/test_device_scopes.py -k text_it_was
STABLE = {"grad_step": "qwen2", "sparse_grad_step": "olmoe",
          "dsa_grad_step": "KeyeVL2", "kda_grad_step": "kimi_linear",
          "swa_grad_step": "laguna"}
GOLDEN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "golden",
    "grad_step_stablehlo.json",
)
_STABLEHLO = {}  # family -> sha256 of the lowered text, filled by ``lowered``


@pytest.fixture(scope="module")
def lowered():
    dense = _train_programs()
    out = {name: _op_names(low) for name, low in dense + _decode_programs()}
    grad_steps = {"grad_step": dense[0][1]}
    for program, family in list(STABLE.items())[1:] + [
        ("cca_grad_step", "zaya"), ("gdn_grad_step", "qwen3_next"),
        ("bd_grad_step", "sdar_moe"), ("ssm_grad_step", "jamba"),
    ]:
        grad_steps[program] = _train_programs(family, only_grad=True)[0][1]
        out[program] = _op_names(grad_steps[program])
    mla = dict(_train_programs("joyai_llm_flash")[:2])
    out["mla_grad_step"] = _op_names(mla["grad_step"])
    out["mla_logp_forward"] = _op_names(mla["logp_forward"])
    out["mesh_sparse_grad_step"] = _op_names(_train_programs(
        "olmoe", only_grad=True, fsdp_parallel_size=2
    )[0][1])
    out["bf16_grad_step"] = _op_names(
        _lower_again("qwen2", compute_dtype=jnp.bfloat16)
    )
    for program, family in STABLE.items():
        _STABLEHLO[family] = hashlib.sha256(
            grad_steps[program].as_text().encode()
        ).hexdigest()
    return out


@pytest.mark.parametrize("program", sorted(WANT))
def test_program_lowers_under_its_contract_name_with_its_scopes(
    lowered, program
):
    text, names = lowered[program]
    jitted = program.removeprefix("mesh_").removeprefix("sparse_")
    jitted = jitted.removeprefix("bf16_")
    jitted = jitted.removeprefix("dsa_")
    jitted = jitted.removeprefix("kda_").removeprefix("swa_")
    jitted = jitted.removeprefix("cca_").removeprefix("gdn_")
    jitted = jitted.removeprefix("mla_").removeprefix("bd_")
    jitted = jitted.removeprefix("ssm_")
    assert re.search(rf"^HloModule jit_{jitted}[, ]", text, re.M)
    assert WANT[program] <= _scopes_in(names), sorted(names)[:40]


def test_the_draft_module_runs_in_grad_step_and_not_in_logp_forward(lowered):
    from benchmark import program_trace

    _text, grad = lowered["mla_grad_step"]
    _text, logp = lowered["mla_logp_forward"]
    module = [n for n in grad if program_trace.scope_of(n) == "mtp"]
    # forward and backward of what the module alone computes (its block is
    # checkpointed, these are outside it: no recomputed forward)
    assert {program_trace.direction_of(n) for n in module} == {"fwd", "bwd"}
    assert not [n for n in logp if program_trace.scope_of(n) == "mtp"]
    expand = [n for n in grad if program_trace.scope_of(n) == "mla_expand"]
    assert {program_trace.direction_of(n) for n in expand} == {
        "fwd", "remat", "bwd"
    }
    assert [n for n in logp if program_trace.scope_of(n) == "mla_expand"]
    # Kimi's NoPE mixer keeps its expansion where its cell's shares read it
    _text, kimi = lowered["kda_grad_step"]
    assert not [n for n in kimi if "mla_expand" in n or "/mtp/" in n]


def test_the_window_core_is_not_nested_in_attention(lowered):
    _text, names = lowered["swa_grad_step"]
    window = [n for n in names if "attn_window" in n]
    assert window and not [n for n in window if "/attention/" in n]


def test_the_latent_mix_is_beside_the_core_and_the_router_in_moe_route(
    lowered
):
    from benchmark import program_trace

    _text, names = lowered["cca_grad_step"]
    mix = [n for n in names if program_trace.scope_of(n) == "cca_mix"]
    assert mix and not [n for n in mix if "/attention/" in n]
    # forward, the forward recomputed under checkpointing, and backward
    assert {program_trace.direction_of(n) for n in mix} == {
        "fwd", "remat", "bwd"
    }
    # the second convolution's batched product over the heads, and the shift
    assert [n for n in mix if "bthi,hio->btho" in n]
    # the router's MLP (exact GELU: an erfc) is all under moe_route
    route = [n for n in names if program_trace.scope_of(n) == "moe_route"]
    assert [n for n in route if n.endswith("/erfc")]
    assert not [n for n in names if n.endswith("/erfc") and n not in route]


@pytest.mark.parametrize("family", sorted(STABLE.values()))
def test_the_lowered_grad_step_is_the_text_it_was(lowered, family):
    if os.environ.get("AREAL_REGEN_GOLDEN"):
        with open(GOLDEN, "w") as f:
            json.dump(dict(sorted(_STABLEHLO.items())), f, indent=1)
    with open(GOLDEN) as f:
        assert _STABLEHLO[family] == json.load(f)[family], (
            f"the {family} tiny model's grad_step lowers to another text "
            "than the golden file's: its compile-cache key has moved"
        )


def test_every_scope_of_the_vocabulary_is_computed_somewhere(lowered):
    seen = set()
    for _text, names in lowered.values():
        seen |= _scopes_in(names)
    assert seen == set(tracing.DEVICE_SCOPES)
    from benchmark import program_trace

    # the benchmark's reader takes the program's own vocabulary
    assert program_trace.device_scopes() == tracing.DEVICE_SCOPES


def test_backward_and_remat_keep_the_scope_on_the_path(lowered):
    from benchmark import program_trace

    _, names = lowered["grad_step"]
    attn = [n for n in names if program_trace.scope_of(n) == "attention"]
    assert {program_trace.direction_of(n) for n in attn} == {
        "fwd", "remat", "bwd"
    }
    assert program_trace.scope_of("jit(loss)/jit(main)/mul") is None
    assert program_trace.scope_of(
        "jit(grad_step)/transpose(jvp(loss))/while/body/lm_head/dot"
    ) == "lm_head"
    assert program_trace.scope_of("jit(g)/transpose(jvp(loss))/mul") == "loss"


# where a new scope's operations must be, by ``program_trace.direction_of``.
# A plain sum's transpose is no operation (the cotangents' own sums take the
# name of the place that made the second one), a cca block's scaled sum has
# one; the cast is outside the checkpointed layers and the accumulator
# outside the gradient; a loop of one layer (the tiny Kimi stack) stacks
# nothing to recompute from
_ON_THE_PATH = {
    "residual": {"fwd", "remat"}, "param_cast": {"fwd", "bwd"},
    "grad_add": {"fwd"}, "layer_stack": {"fwd", "bwd"},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_new_scope_is_on_the_path_forward_recomputed_and_backward(
    lowered, family
):
    from benchmark import program_trace

    _, names = lowered[FAMILIES[family]]
    for scope in sorted(WANT[FAMILIES[family]] & set(NEW_SCOPES)):
        found = {
            program_trace.direction_of(n) for n in names
            if program_trace.scope_of(n) == scope
        }
        want = _ON_THE_PATH.get(scope, {"fwd", "remat", "bwd"})
        assert want <= found, (family, scope, found)
    _, names = lowered["bf16_grad_step"]
    cast = {
        program_trace.direction_of(n) for n in names
        if program_trace.scope_of(n) == "param_cast"
    }
    assert cast == _ON_THE_PATH["param_cast"]


def _matmul(op_name):
    return re.search(r"dot_general|conv_general", op_name.split("/")[-1])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_matmul_is_the_loops_own_or_nobodys(lowered, family):
    """``layer_stack`` reads the loop's plumbing and whatever of a layer's
    body a scope missed: no product may be innermost under it, nor under no
    scope at all."""
    from benchmark import program_trace

    _, names = lowered[FAMILIES[family]]
    products = [n for n in names if _matmul(n)]
    assert products
    astray = [
        n for n in products
        if program_trace.scope_of(n) in (None, "layer_stack")
    ]
    assert not astray, astray[:10]
    # and what a new scope names was nobody's: no older scope is on its path
    # (operations the compiler merged keep both names, ``;`` between them)
    stolen = [
        n for merged in names for n in merged.split(";")
        if program_trace.scope_of(n) in NEW_SCOPES
        and program_trace.scope_of(n, OLD_SCOPES) is not None
    ]
    assert not stolen, stolen[:10]


def _scope_by_instruction(text, scopes):
    """{instruction: the innermost of ``scopes`` on its op_name} of a
    compiled text."""
    from benchmark import program_trace

    found = re.findall(
        r'^\s*(?:ROOT )?(\S+) = .*op_name="([^"]+)"', text, re.M
    )
    return {
        name: scope for name, op_name in found
        if (scope := program_trace.scope_of(op_name, scopes)) is not None
    }


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_new_scopes_move_no_older_scopes_border(
    lowered, family, monkeypatch
):
    """The family's ``grad_step`` traced again with the NEW scopes alone
    patched off (the parent's naming): the compiled program is the same
    text but for its names (``tools/program_text.stripped``, what a chip
    run of that tool shows at the real size), each older scope names the
    same instructions of it, and no ``op_name`` of the parent's holds a new
    name as a whole path word (so a new name reads nothing that was there
    before it)."""
    from areal_tpu.models import transformer
    from benchmark import program_trace
    from tools import program_text

    device_scope = tracing.device_scope

    def older_only(name):
        if name in NEW_SCOPES:
            return contextlib.nullcontext()
        return device_scope(name)

    for mod in (tracing, transformer):
        monkeypatch.setattr(mod, "device_scope", older_only)
    text, names = _op_names(_lower_again(family))
    for n in names:
        assert program_trace.scope_of(n, NEW_SCOPES) is None, n
    now, _ = lowered[FAMILIES[family]]
    assert program_text.stripped(text) == program_text.stripped(now)
    was = _scope_by_instruction(text, OLD_SCOPES)
    assert was and was == _scope_by_instruction(now, OLD_SCOPES)


def test_the_heads_hand_written_backward_keeps_lm_head_and_loss(lowered):
    """``ops/chunked_head.py`` differentiates itself (``jax.custom_vjp``):
    its matmuls read ``lm_head`` forward and backward and the rest of it
    ``loss``, so ``lm_head_time_share.train`` reads what it read."""
    from benchmark import program_trace

    def under(program, scope, word):
        _, names = lowered[program]
        return {
            program_trace.direction_of(n) for n in names
            if program_trace.scope_of(n) == scope and word in n
        }

    assert under("grad_step", "lm_head", "dot_general") == {"fwd", "bwd"}
    assert under("logp_forward", "lm_head", "dot_general") == {"fwd"}
    # the softmax: logsumexp forward, exp(logits - logz) in dlogits
    assert under("grad_step", "loss", "exp") == {"fwd", "bwd"}
    assert under("logp_forward", "loss", "exp") == {"fwd"}


def test_the_head_gathers_and_scatters_nothing(lowered):
    """The label's logit is a select against an iota, forward and backward:
    the only gather and scatter that see the vocabulary are the embedding's
    lookup and its gradient."""
    from benchmark import program_trace

    vocab = str(tiny_config("qwen2").vocab_size)
    for program in ("grad_step", "logp_forward"):
        text, _ = lowered[program]
        found = re.findall(
            r'= \w+\[([\d,]*)\]\S* (gather|scatter)\(.*op_name="([^"]+)"', text
        )
        assert {"embed"} <= {program_trace.scope_of(n) for _, _, n in found}
        for dims, op, name in found:
            scope = program_trace.scope_of(name)
            assert scope not in ("lm_head", "loss"), (program, op, name)
            assert scope == "embed" or vocab not in dims.split(","), (
                program, op, dims, name
            )


def _loss_and_logp(scoped, monkeypatch):
    if not scoped:
        from areal_tpu.models import transformer
        from areal_tpu.ops import chunked_head

        null = lambda name: contextlib.nullcontext()  # noqa: E731
        for mod in (tracing, transformer, chunked_head):
            monkeypatch.setattr(mod, "device_scope", null)
    actor, eng = _actor()
    batch = actor.compute_advantages(_batch())
    np.random.seed(3)
    logp = actor.compute_logp(batch)
    stats = actor.ppo_update(batch)
    return logp, stats, jax.device_get(eng.params["final_norm"])


def test_scopes_change_no_bit(monkeypatch):
    logp_a, stats_a, norm_a = _loss_and_logp(True, monkeypatch)
    logp_b, stats_b, norm_b = _loss_and_logp(False, monkeypatch)
    np.testing.assert_array_equal(logp_a, logp_b)
    assert stats_a == stats_b  # loss, grad norm, every stat: same floats
    np.testing.assert_array_equal(norm_a, norm_b)


def test_trainer_counts_what_it_packs_from_the_segment_ids():
    actor, eng = _actor(remat=False)
    assert eng.counters() == {"real_tokens": 0, "padded_tokens": 0}
    lens = (5, 9, 12, 7)
    batch = _batch(lens)
    packed, _ = eng._pack_for_device(batch)
    seg = np.asarray(packed.segment_ids)
    assert eng.counters()["real_tokens"] == sum(lens) == np.count_nonzero(seg)
    assert eng.counters()["padded_tokens"] == seg.size
    # one step of ONE minibatch: its proximal logprobs are its own forward's
    # (PR 43), so no recompute pass runs and every token is packed once
    from areal_tpu.utils import stats_tracker

    stats_tracker.export_all()  # drop what the pack above exported
    before = eng.counters()
    batch = actor.compute_advantages(batch)
    actor.ppo_update(batch)
    after = eng.counters()
    assert after["real_tokens"] - before["real_tokens"] == sum(lens)
    assert eng.step_count == 1
    step = stats_tracker.export_all()
    assert step["spmd/real_tokens"] == sum(lens)
    assert step["spmd/padded_tokens"] == (
        after["padded_tokens"] - before["padded_tokens"]
    )
    assert not hasattr(eng, "last_timing")
    assert not [k for k in step if k.endswith(("_dispatch_s", "apply_fetch_s"))]


# --------------------------------------------------------------------------
# The seam between a model's mechanisms and their readers (PR 44): what a
# mixer or a sparse FFN counts, where it cannot run and what its spans say
# is declared beside it (``transformer.MECHANISMS``); the trainer, the
# resolver and the generation engine read the declarations and name no family
# --------------------------------------------------------------------------
_THE_ENGINES_OWN = {
    "real_tokens", "padded_tokens", "attn_tiles", "attn_tiles_kept",
}


def test_a_count_added_to_a_declaration_alone_reaches_every_reader(
    monkeypatch
):
    """One toy count beside an existing mixer, by ``monkeypatch`` alone: it
    is in ``train_batch``'s output, in ``counters()`` and on the stats
    plane, with nothing under ``engine/`` knowing its name."""
    from areal_tpu.models import transformer
    from areal_tpu.utils import stats_tracker

    full = transformer.MECHANISMS["full"]
    assert not full.counts and full.count is None
    monkeypatch.setitem(transformer.MECHANISMS, "full", full._replace(
        counts={"toy_tokens": "the real tokens of the rows"},
        count=lambda cfg, reported, segment_ids, positions: [
            (segment_ids > 0).sum().astype(jnp.int32)
        ],
    ))
    # rows of 64 tokens for the batch's 33 (the ladder's own step is 2048)
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 64)
    lens = (5, 9, 12, 7)
    actor, eng = _actor(remat=False, family="laguna")
    assert eng.counters()["toy_tokens"] == 0
    stats_tracker.export_all()
    (out,) = actor.ppo_update(actor.compute_advantages(_batch(lens)))
    assert out["toy_tokens"] == sum(lens)
    assert eng.counters()["toy_tokens"] == sum(lens)
    assert stats_tracker.export_all()["spmd/toy_tokens"] == sum(lens)
    # and the family's own counts beside it, as they were
    assert out["swa_causal_pairs"] == 3 * sum(n * (n + 1) // 2 for n in lens)


@pytest.mark.parametrize(
    "family", ["qwen2", "olmoe", "KeyeVL2", "kimi_linear", "laguna", "zaya"]
)
def test_the_trainer_counts_what_the_models_mechanisms_declare(
    lowered, family
):
    from areal_tpu.models import transformer

    eng = _ENGINES[family]  # as ``lowered`` initialised it
    names = transformer.count_names(eng.model_config)
    assert set(eng.counters()) - _THE_ENGINES_OWN == set(names)
    assert all(says for says in names.values())  # each says what it counts
    # what the engine holds of the ops' resolved forms is the spans' too
    attrs = eng._span_attrs
    assert attrs == transformer.span_attrs(eng.model_config)
    for attr in ("moe_impl", "dsa_select_impl", "kda_impl", "prep_impl"):
        assert getattr(eng, attr) == attrs.get(attr)


@pytest.mark.parametrize("mechanism,family,says", [
    ("indexer", "KeyeVL2", "no indexer-key cache beside its KV pages"),
    ("swa", "laguna", "8-key window among full ones"),
    ("cca", "zaya", "recurrent state beside paged state (ROADMAP B.4)"),
    ("kda", "kimi_linear",
     "block kinds (kda+dense, kda+moe, mla+moe): it has no recurrent state"),
])
def test_a_refused_mechanism_is_refused_in_its_declarations_words(
    mechanism, family, says
):
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models import transformer
    from areal_tpu.ops.flash import resolve_attn_impl

    cfg = tiny_config(family)
    declared = transformer.MECHANISMS[mechanism]
    why = declared.no_generation(cfg)
    assert says in why
    with pytest.raises(NotImplementedError) as refused:
        GenerationEngine(JaxGenConfig(), model_config=cfg)
    assert str(refused.value) == why == transformer.generation_refusal(cfg)

    forced, no_sp = transformer.seq_parallel_refusal(cfg)
    assert forced == declared.forced_attn
    asked = ("ulysses", 2, cfg.attn_head_dims, forced, no_sp)
    if declared.no_seq_parallel is None:  # the delta rule's mesh form refuses
        assert no_sp == "" and resolve_attn_impl(*asked) == "ulysses"
        return
    with pytest.raises(ValueError) as refused:
        resolve_attn_impl(*asked)
    assert str(refused.value) == declared.no_seq_parallel(cfg).format(
        attn_impl="ulysses", seq_parallel_size=2
    )
    assert "has no sequence-parallel form" in str(refused.value)
    assert "seq_parallel_size=2" in str(refused.value)


def test_rehearsal_counts_a_trained_token_once_and_a_recomputed_one_twice(
    monkeypatch
):
    """The dense cell's rehearsal: the trainer's own ``real_tokens`` /
    ``padded_tokens`` over the window equal what the benchmark's hook around
    ``_pack_for_device`` counted, and a token is dispatched once to train
    and once more if its minibatch is not the step's first (what
    ``tests/benchmark_suite/test_program_trace.py``'s test of the same run
    holds as "twice": ``tests/conftest.py``)."""
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from benchmark import run

    engines, recomputed = [], []
    init, forward = SPMDTrainEngine.initialize, SPMDTrainEngine.forward

    def initialize(self, *a, **kw):
        engines.append(self)
        return init(self, *a, **kw)

    def counting_forward(self, input_, post_hook=None, rows=None):
        lens = np.asarray(input_["attention_mask"]).sum(1)
        recomputed.append(int(lens.sum() if rows is None else lens[rows].sum()))
        return forward(self, input_, post_hook=post_hook, rows=rows)

    monkeypatch.setattr(SPMDTrainEngine, "initialize", initialize)
    monkeypatch.setattr(SPMDTrainEngine, "forward", counting_forward)
    ctx, kind, _ = run.prepare([
        "--workload", "train_0p5b_grpo", "--seed", str(2 ** 31 + 25),
        "--seconds", "1", "--trace", "0", "--rehearse",
    ])
    marks = {}
    mark_start, since = ctx.mark_window_start, ctx.watch.since

    def mark_window_start():
        marks["start"] = (engines[0].counters(), len(recomputed))
        mark_start()

    def since_window_end(mark):  # the kind's first call after its window
        marks.setdefault("end", (engines[0].counters(), len(recomputed)))
        return since(mark)

    ctx.mark_window_start = mark_window_start
    ctx.watch.since = since_window_end
    out = kind.run(ctx)
    (c0, n0), (c1, n1) = marks["start"], marks["end"]
    delta = {k: c1[k] - c0[k] for k in c1}
    c = out.obs.counters
    assert delta["real_tokens"] == c["dispatched_real_tokens"] > 0
    assert delta["padded_tokens"] == c["padded_tokens"] > delta["real_tokens"]
    assert set(delta) == {"real_tokens", "padded_tokens"}
    passes = recomputed[n0:n1]
    assert len(passes) == c["steps"]  # one pass a step
    assert delta["real_tokens"] == c["trained_tokens"] + sum(passes)
    # the recipe's four minibatches: about three quarters are recomputed
    assert 0.6 < sum(passes) / c["trained_tokens"] < 0.9


@pytest.mark.parametrize("onto", ["dense", "banded"])
def test_a_held_shares_slabs_sit_under_the_layers_scopes(onto, monkeypatch):
    """A layer that holds a share runs a chunk's expert path over slabs of
    the sorted stream, forward in a loop, backward the first in line and the
    others in a loop (``ops/moe.py``): every operation the layer adds, in the
    loops' bodies as in line, is under ``moe_route``, ``moe_dispatch`` or
    ``moe_experts``, so ``moe_dispatch_time_share.train`` and
    ``moe_experts_time_share.train`` read all of it however many slabs ran
    (a ``while`` itself is a container to the trace's reader). ``banded``:
    the token side as a TPU runs it (``moe.onto_tokens_banded``, here with
    its kernel in interpret mode: a jitted function, whose operations keep
    the scope of the place that calls it): under ``moe_dispatch`` in the
    forward loop's body, in the backward's first slab and in its loop's."""
    from areal_tpu.ops import moe
    from benchmark import program_trace

    t, d, f, routed, held, k = 512, 16, 16, 16, 2, 2
    assert moe._held_rows(t * k, held, routed) < t * k  # more than one slab
    if onto == "banded":
        monkeypatch.setattr(moe, "onto_impl", lambda shape=None: "interpret")
        monkeypatch.setattr(moe, "_ONTO_TILE", 128)
        monkeypatch.setattr(moe, "_ONTO_ROWS", 128)

    def loss(x, wr, wg, wu, wd):
        out, _ = moe.moe_ffn(
            x, wr, wg, wu, wd, num_experts_per_tok=k, norm_topk_prob=True
        )
        return (out ** 2).sum()  # the backward reads the forward's result

    z = jnp.zeros
    _, names = _op_names(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        z((1, t, d)), z((d, routed)), z((held, d, f)), z((held, d, f)),
        z((held, f, d)),
    ))
    looped = {n for n in names if "/while/" in n}
    assert {program_trace.scope_of(n) for n in looped} == {
        "moe_dispatch", "moe_experts"
    }
    scoped = {program_trace.scope_of(n) for n in names}
    assert {"moe_route", "moe_dispatch", "moe_experts"} <= scoped
    stray = {
        n for n in names
        if program_trace.scope_of(n) is None and "/" in n  # a traced op
        and any(w in n for w in ("gather", "sort", "dot_general", "ragged"))
    }
    assert not stray, sorted(stray)[:10]
    ours = {n for n in names if "jit(onto_tokens_banded)" in n}
    assert bool(ours) == (onto == "banded")
    if ours:
        assert {program_trace.scope_of(n) for n in ours} == {"moe_dispatch"}
        where = {
            (program_trace.direction_of(n), "/while/" in n) for n in ours
        }
        assert where == {("fwd", True), ("bwd", False), ("bwd", True)}
