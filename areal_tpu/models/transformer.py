"""Functional decoder-only transformer over packed token streams.

Role of reference realhf/impl/model/nn/real_llm_api.py (`ReaLModel`) — the
from-scratch parallel causal LM — re-designed TPU-first:

- Params are a plain pytree; per-layer weights are **stacked along a leading
  layer axis** and the stack is traversed with `jax.lax.scan`, so XLA
  compiles one layer body regardless of depth (compile time O(1) in layers).
- Parallelism is declarative: `param_logical_axes` returns a same-structure
  tree of logical axis names; `areal_tpu.parallel.sharding` maps those to
  mesh axes (fsdp/tensor). No parallel modules, no explicit collectives —
  pjit inserts them.
- Inputs are packed streams (`[B, T]` tokens + segment_ids + positions),
  the TPU analog of the reference's cu_seqlens varlen batches.
- `jax.checkpoint` (remat) on the scanned layer body trades FLOPs for HBM,
  replacing torch gradient checkpointing (reference base_hf_engine.py).
- A model whose layers are of more than one kind (``ModelConfig.layer_kinds``:
  gated delta-rule mixers of either decay (``kda`` a channel, ``gdn`` a
  head), latent-attention and rotary mixers, the last with or without a
  window (or a rotation), the convolutional latent mixer ``cca`` and the
  state-space mixer ``ssm``, over dense or sparse FFNs) holds ONE STACK A
  BLOCK KIND
  under ``params["layers"][kind]`` and ``apply`` walks the pattern: every run
  of consecutive layers of one kind is a scan over that kind's slice, under
  the same checkpoint policy. A model of one kind keeps the flat
  ``params["layers"]`` and the one scan. Where the sparse layers' router
  is an MLP (``RouterConfig``), its input passes from layer to layer and
  rides the scans beside ``x``, for that model only.
- The one thing a model may carry beside its stack is a draft module
  (``ModelConfig.mtp_layers``; ``params["mtp"]``, ``draft_forward``): one
  block of the stack's last kind over the next token's embedding and the
  stack's normed output, under the trunk's embedding and head. ``apply``
  never runs it; ``engine/programs.build_grad_step`` does, beside the loss.
"""

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint as _ad_checkpoint

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import bd_attention as bd_ops
from areal_tpu.ops import conv_prep as conv_prep_ops
from areal_tpu.ops import flash as flash_ops
from areal_tpu.ops import kda as kda_ops
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import rotary as rotary_ops
from areal_tpu.ops import sparse_attention as dsa_ops
from areal_tpu.ops import ssm_scan as ssm_ops
from areal_tpu.ops.kda import kda_scan
from areal_tpu.ops.moe import moe_ffn_from_params, router_mlp
from areal_tpu.ops.sparse_attention import sparse_attention
from areal_tpu.utils.tracing import device_scope
from areal_tpu.ops.basic import (
    hidden_act_fn,
    rms_norm,
    rope_at,
    rope_frequencies,
    rope_inv_freq,
    segment_attention,
)
# the rotation: ``ops/basic.py``'s three by name and numbers, through the
# one-pass kernel where ``rotary.rotate_impl`` takes the shape
from areal_tpu.ops.rotary import apply_mrope, apply_rope, rotate_leading

Params = Dict[str, Any]

# the rotary GQA mixers among the block kinds: a ``RotaryConfig`` each, under
# ``ModelConfig.full`` / ``.swa``
_ROTARY_MIXERS = ("full", "swa")


class LayerStats(NamedTuple):
    """What ``apply(return_stats=True)`` hands back beside the logits: the
    sparse layers' mean load-balancing loss (zero for a dense model), and
    what the model's mechanisms count on these rows (``count_names``)."""

    aux_loss: jnp.ndarray
    counts: Dict[str, jnp.ndarray]


# --------------------------------------------------------------------------
# Block kinds (``ModelConfig.layer_kinds``): one table says what a kind holds
# --------------------------------------------------------------------------
def _kind_leaves(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    """One layer of block ``kind``: leaf -> (shape, how it is drawn, logical
    axes). Drawn: "normal" (std 0.02), "ones", "zeros", or an array."""
    mixer, ffn = kind.split("+")
    D = cfg.hidden_size
    # a norm that scales by (1 + w) is the identity at ZEROS
    unit = "zeros" if cfg.norm_add_unit_offset else "ones"
    leaves = {
        "input_norm": ((D,), unit, (None,)),
        "post_attn_norm": ((D,), unit, (None,)),
    }
    if mixer == "gdn":
        gc = cfg.gdn
        Hv, conv_dim = gc.num_v_heads, 2 * gc.key_dim + gc.value_dim
        # the decay: -exp(a_log) softplus(a + dt_bias), ONE a value head;
        # the rates spread over 1..16 and the steps over 1e-3..1e-1 as the
        # ``kda`` mixer's are, a number a head here
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), Hv))
        leaves.update({
            # one product: q, k (the key heads), v, z (the value heads)
            "w_qkvz": ((D, conv_dim + gc.value_dim), "normal",
                       ("embed", "heads")),
            "w_ba": ((D, 2 * Hv), "normal", ("embed", None)),  # beta, a
            "conv_qkv": ((conv_dim, gc.conv_size), "normal", ("heads", None)),
            "a_log": ((Hv,), jnp.log(jnp.linspace(1.0, 16.0, Hv)), (None,)),
            "dt_bias": ((Hv,), dt + jnp.log(-jnp.expm1(-dt)), (None,)),
            # a PLAIN weight, whatever the stack's other norms do
            "o_norm": ((gc.head_v_dim,), "ones", (None,)),
            "wo": ((gc.value_dim, D), "normal", ("heads", "embed")),
        })
    elif mixer == "kda":
        kc = cfg.kda
        H, dh = kc.num_heads, kc.head_dim
        for n in "qkv":
            leaves[f"w{n}"] = ((D, H * dh), "normal", ("embed", "heads"))
            leaves[f"conv_{n}"] = (
                (H * dh, kc.conv_size), "normal", ("heads", None)
            )
        # the decay: -exp(a_log) softplus(low rank + dt_bias), a head's
        # rates spread over 1..16 and the steps over 1e-3..1e-1 (the
        # family's initial ranges, laid out evenly instead of drawn)
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), dh))
        leaves.update({
            "w_f_a": ((D, dh), "normal", ("embed", None)),
            "w_f_b": ((dh, H * dh), "normal", (None, "heads")),
            "a_log": ((H,), jnp.log(jnp.linspace(1.0, 16.0, H)), (None,)),
            "dt_bias": (
                (H * dh,), jnp.tile(dt + jnp.log(-jnp.expm1(-dt)), H),
                ("heads",),
            ),
            "w_beta": ((D, H), "normal", ("embed", None)),
            "w_g_a": ((D, dh), "normal", ("embed", None)),
            "w_g_b": ((dh, H * dh), "normal", (None, "heads")),
            "o_norm": ((dh,), "ones", (None,)),
            "wo": ((H * dh, D), "normal", ("heads", "embed")),
        })
    elif mixer == "mla":
        m, H = cfg.mla, cfg.num_heads
        if m.q_lora_rank:  # the query through a compression of its own
            leaves.update({
                "wq_a": ((D, m.q_lora_rank), "normal", ("embed", None)),
                "q_a_norm": ((m.q_lora_rank,), "ones", (None,)),
                "wq_b": ((m.q_lora_rank, H * m.qk_head_dim), "normal",
                         (None, "heads")),
            })
        else:
            leaves["wq"] = (
                (D, H * m.qk_head_dim), "normal", ("embed", "heads")
            )
        leaves.update({
            "w_kv_a": (
                (D, m.kv_lora_rank + m.qk_rope_head_dim), "normal",
                ("embed", None),
            ),
            "kv_norm": ((m.kv_lora_rank,), "ones", (None,)),
            "w_kv_b": (
                (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)),
                "normal", (None, "heads"),
            ),
            "wo": ((H * m.v_head_dim, D), "normal", ("heads", "embed")),
        })
    elif mixer in _ROTARY_MIXERS:  # rotary GQA, the kind's own heads
        rc = getattr(cfg, mixer)
        Qd, KVd = rc.num_heads * cfg.head_dim, cfg.kv_dim
        leaves.update({
            # ``output_gate``: a head's query, then its gate as wide
            "wq": ((D, Qd * (2 if rc.output_gate else 1)), "normal",
                   ("embed", "heads")),
            "wk": ((D, KVd), "normal", ("embed", "heads")),
            "wv": ((D, KVd), "normal", ("embed", "heads")),
            "wo": ((Qd, D), "normal", ("heads", "embed")),
        })
        if rc.head_gate:  # one number a head and token
            leaves["w_head_gate"] = (
                (D, rc.num_heads), "normal", ("embed", None)
            )
        if cfg.qk_norm == "head":  # each head by itself, before the rotation
            leaves["q_norm"] = ((cfg.head_dim,), unit, (None,))
            leaves["k_norm"] = ((cfg.head_dim,), unit, (None,))
    elif mixer == "cca":
        cc, dh = cfg.cca, cfg.head_dim
        Qd, heads = cc.num_heads * dh, cc.num_heads + cc.num_kv_heads
        leaves.update({
            "wq": ((D, Qd), "normal", ("embed", "heads")),
            "wk": ((D, cc.num_kv_heads * dh), "normal", ("embed", "heads")),
            # the values' two heads: the token's own, the previous token's
            "wv_own": ((D, dh), "normal", ("embed", None)),
            "wv_prev": ((D, dh), "normal", ("embed", None)),
            "wo": ((Qd, D), "normal", ("heads", "embed")),
            # over concat(q, k): a tap a channel, then a matrix a head and
            # tap (tap K-1 the token itself, as ``_short_conv`` has it)
            "cca_conv0": ((heads * dh, cc.conv_taps[0]), "normal",
                          (None, None)),
            "cca_conv0_bias": ((heads * dh,), "zeros", (None,)),
            "cca_conv1": ((heads, cc.conv_taps[1], dh, dh), "normal",
                          (None, None, None, None)),
            "cca_conv1_bias": ((heads * dh,), "zeros", (None,)),
            "cca_temp": ((cc.num_kv_heads,), "zeros", (None,)),
        })
        # both sublayers scale their residual and their output:
        # x <- (x + b_res) a_res + (f(norm(x)) + b_out) a_out
        for sub in ("attn", "ffn"):
            leaves.update({
                f"{sub}_res_scale": ((D,), "ones", (None,)),
                f"{sub}_res_bias": ((D,), "zeros", (None,)),
                f"{sub}_out_scale": ((D,), "ones", (None,)),
                f"{sub}_out_bias": ((D,), "zeros", (None,)),
            })
    elif mixer == "ssm":
        sc = cfg.ssm
        C, N, R = sc.d_inner, sc.d_state, sc.dt_rank
        # the Mamba initialisation, laid out evenly instead of drawn: a
        # channel's rates 1..N, the steps over 1e-3..1e-1 through the
        # inverse softplus, so that decays are neither 0 nor 1
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), C))
        leaves.update({
            # one product: x, then the gate z
            "w_in": ((D, 2 * C), "normal", ("embed", "heads")),
            "conv_x": ((C, sc.conv_size), "normal", ("channel", None)),
            "conv_x_bias": ((C,), "zeros", ("channel",)),
            # dt's input (rank R), B and C (N each)
            "w_x": ((C, R + 2 * N), "normal", ("channel", None)),
            "dt_norm": ((R,), "ones", (None,)),
            "b_norm": ((N,), "ones", (None,)),
            "c_norm": ((N,), "ones", (None,)),
            "w_dt": ((R, C), "normal", (None, "channel")),
            "dt_bias": ((C,), dt + jnp.log(-jnp.expm1(-dt)), ("channel",)),
            "a_log": (
                (C, N),
                jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1.0)), (C, N)),
                ("channel", None),
            ),
            "d_skip": ((C,), "ones", ("channel",)),
            "wo": ((C, D), "normal", ("heads", "embed")),
        })
    else:
        raise ValueError(f"no mixer {mixer!r} among the block kinds")
    if ffn == "moe":
        E, Fe = cfg.num_held_experts, cfg.expert_ffn_size
        if cfg.router is None:
            leaves["w_router"] = (
                (D, cfg.num_experts), "normal", ("embed", None)
            )
        else:  # an MLP's scores (``ops/moe.router_mlp``)
            R = cfg.router.hidden_size
            leaves.update({
                "w_router_in": ((D, R), "normal", ("embed", None)),
                "b_router_in": ((R,), "zeros", (None,)),
                "router_norm": ((R,), "ones", (None,)),
                "w_router_1": ((R, R), "normal", (None, None)),
                "b_router_1": ((R,), "zeros", (None,)),
                "w_router_2": ((R, R), "normal", (None, None)),
                "b_router_2": ((R,), "zeros", (None,)),
                "w_router_out": ((R, cfg.num_experts), "normal",
                                 (None, None)),
                "b_router_out": ((cfg.num_experts,), "zeros", (None,)),
                # what the previous layer's state is multiplied by
                "router_carry": ((), "zeros", ()),
            })
        leaves.update({
            "w_gate": ((E, D, Fe), "normal", ("expert", "exp_embed", "mlp")),
            "w_up": ((E, D, Fe), "normal", ("expert", "exp_embed", "mlp")),
            "w_down": ((E, Fe, D), "normal", ("expert", "mlp", "exp_embed")),
        })
        # the choice's correction bias: state that no gradient reaches
        if cfg.router_act == "sigmoid" or cfg.router is not None:
            leaves["router_bias"] = ((cfg.num_experts,), "zeros", (None,))
        if cfg.shared_expert_size:
            Fs = cfg.shared_expert_size
            leaves.update({
                "w_shared_gate": ((D, Fs), "normal", ("embed", "mlp")),
                "w_shared_up": ((D, Fs), "normal", ("embed", "mlp")),
                "w_shared_down": ((Fs, D), "normal", ("mlp", "embed")),
            })
            if cfg.shared_expert_gate:
                leaves["w_shared_router"] = (
                    (D, 1), "normal", ("embed", None)
                )
    else:
        F = cfg.intermediate_size
        leaves.update({
            "w_gate": ((D, F), "normal", ("embed", "mlp")),
            "w_up": ((D, F), "normal", ("embed", "mlp")),
            "w_down": ((F, D), "normal", ("mlp", "embed")),
        })
    return leaves


def block_kinds(cfg: ModelConfig) -> tuple:
    """The model's block kinds, in the order they first appear."""
    return tuple(dict.fromkeys(cfg.layer_kinds))


def _draw(leaves: Dict[str, tuple], lead: tuple, rng: jax.Array, dtype):
    """``leaves`` (``_kind_leaves``' form) drawn, each under ``lead`` leading
    axes: one key a leaf, in the table's order."""
    keys = jax.random.split(rng, len(leaves))
    group = {}
    for key, (name, (shape, how, _)) in zip(keys, leaves.items()):
        if isinstance(how, str) and how == "normal":
            w = jax.random.normal(key, lead + shape, jnp.float32) * 0.02
        elif isinstance(how, str):
            w = (jnp.ones if how == "ones" else jnp.zeros)(lead + shape)
        else:
            w = jnp.broadcast_to(how, lead + shape)
        group[name] = w.astype(dtype)
    return group


def _init_kinds(cfg: ModelConfig, rng: jax.Array, dtype) -> Params:
    return {
        kind: _draw(
            _kind_leaves(cfg, kind), (cfg.kind_layers(kind),),
            jax.random.fold_in(rng, 200 + g), dtype,
        )
        for g, kind in enumerate(block_kinds(cfg))
    }


# --------------------------------------------------------------------------
# The draft module beside the stack (``ModelConfig.mtp_layers``)
# --------------------------------------------------------------------------
def draft_kind(cfg: ModelConfig) -> str:
    """The block kind of the draft module's one block: the stack's last."""
    return cfg.layer_kinds[-1]


def _draft_leaves(cfg: ModelConfig) -> Dict[str, tuple]:
    """What the module holds beside its block, ``_kind_leaves``' form: the
    norms of its two inputs, the product that joins them, the norm under
    the (trunk's) head."""
    D = cfg.hidden_size
    unit = "zeros" if cfg.norm_add_unit_offset else "ones"
    return {
        "norm_e": ((D,), unit, (None,)),
        "norm_h": ((D,), unit, (None,)),
        "w_eh": ((2 * D, D), "normal", (None, "embed")),
        "head_norm": ((D,), unit, (None,)),
    }


def _init_draft(cfg: ModelConfig, rng: jax.Array, dtype) -> Params:
    """``params["mtp"]``: ``_draft_leaves`` and, under ``"block"``, one layer
    of ``draft_kind`` stacked as a kind's layers are (a leading axis of 1)."""
    out = _draw(_draft_leaves(cfg), (), jax.random.fold_in(rng, 300), dtype)
    out["block"] = _draw(
        _kind_leaves(cfg, draft_kind(cfg)), (1,),
        jax.random.fold_in(rng, 301), dtype,
    )
    return out


# --------------------------------------------------------------------------
# Mechanisms: one table says what a mixer or a sparse FFN tells the trainer,
# ``ops.flash.resolve_attn_impl`` and the generation engine (none names one)
# --------------------------------------------------------------------------
class Mechanism(NamedTuple):
    """What a mixer (a block kind's first half; ``indexer``: the one-kind
    stack's learned selection) or a sparse FFN declares. The last three are
    functions of the ``ModelConfig``; None: nothing to refuse or to say."""

    # count -> what it counts, summed over its layers: a key of
    # ``train_batch``'s output, of ``SPMDTrainEngine.counters()`` (and over
    # the train microbatches) and of the stats plane (``spmd/<count>``)
    counts: Dict[str, str] = {}
    # (cfg, reported, segment_ids, positions) -> the counts' arrays of one
    # microbatch, in ``counts``' order; ``reported``: mechanism -> its op's
    # tuples, a run of layers each, the layers leading. The host sums every
    # element, an integer array exactly and a float32 one as float32
    count: Optional[Callable] = None
    forced_attn: str = ""  # the attention it runs whatever was asked
    # why it has no sequence-parallel form (a ``str.format`` template over
    # ``attn_impl`` and ``seq_parallel_size``), why the generation engine
    # cannot run it, what a dispatch span says of it
    no_seq_parallel: Optional[Callable] = None
    no_generation: Optional[Callable] = None
    span_attrs: Optional[Callable] = None
    # (cfg, attn_impl, row_len, block_cap) -> what a GRAD dispatch span
    # says beside them of the program it runs: the row's length is the
    # dispatch's, not the model's
    grad_span_attrs: Optional[Callable] = None


def _reported(source: str, *fields, reduce=jnp.sum) -> Callable:
    """A ``Mechanism.count``: ``reduce`` over the model's layers of each of
    ``fields`` of what ``source``'s op reports a layer."""

    def count(cfg, reported, segment_ids, positions):
        runs = reported[source]
        return [
            reduce(jnp.concatenate([getattr(r, f) for r in runs]))
            for f in fields
        ]

    return count


def _swa_counts(cfg, reported, segment_ids, positions):
    """int32 [window layers], every layer the same: a sequence of length L
    gives ``sum_t min(t + 1, window)`` pairs, ``L (L + 1) / 2`` without."""
    n = sum(kind.startswith("swa+") for kind in cfg.layer_kinds)
    seen = jnp.where(segment_ids > 0, positions.astype(jnp.int32) + 1, 0)
    return (
        jnp.full((n,), jnp.minimum(seen, cfg.swa.window).sum(), jnp.int32),
        jnp.full((n,), seen.sum(), jnp.int32),
    )


_PAIR_STRETCH = 4096  # tokens of a row a partial sum of its pairs covers


def _cca_counts(cfg, reported, segment_ids, positions):
    """int32, the ``cca`` layers leading, every layer the same: the pairs in
    partial sums a stretch of a row (ONE 65,536-token sequence's pass an
    int32), and the sequence starts."""
    n = sum(kind.startswith("cca+") for kind in cfg.layer_kinds)
    real = segment_ids > 0
    before = jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :-1]
    seen = jnp.where(real, positions.astype(jnp.int32) + 1, 0)
    # a stretch's sum fits an int32 at any position under 2^31 / 4096
    seen = jnp.pad(seen, ((0, 0), (0, -seen.shape[1] % _PAIR_STRETCH)))
    pairs = seen.reshape(-1, _PAIR_STRETCH).sum(-1)
    return (
        jnp.broadcast_to(pairs, (n,) + pairs.shape),
        jnp.full((n,), (real & (before != segment_ids)).sum(), jnp.int32),
    )


def _bd_counts(cfg, reported, segment_ids, positions):
    """int32 [rows] each, a ROW's and not a layer's (every layer attends
    the same pairs): the pairs M keeps (a query's clean keys before its
    block, ``block * block_length`` of them, and the slots of its own stream
    and block, itself among them), the pairs a causal model would keep over
    the sequences' own tokens, the tokens scored. From the rows' (sequence,
    position, stream) on the device (scope ``bd_layout``)."""
    streams, scored = reported["block_diffusion"]
    size = cfg.block_diffusion.block_length
    with device_scope("bd_layout"):
        real = segment_ids > 0
        pos = positions.astype(jnp.int32)
        code = bd_ops.slot_codes(segment_ids, pos // size, streams)[0]
        starts = jnp.pad(
            code[:, 1:] != code[:, :-1], ((0, 0), (1, 0)),
            constant_values=True,
        )
        # the slots of a (sequence, stream, block) lie side by side
        groups = jax.vmap(functools.partial(
            jax.ops.segment_sum, num_segments=code.shape[1]
        ))(real.astype(jnp.int32), jnp.cumsum(starts, axis=1) - 1)
        before = jnp.where(real, pos // size * size, 0).sum(-1)
        return (
            before + (groups * groups).sum(-1),
            jnp.where(real & (streams == 0), pos + 1, 0).sum(-1),
            (scored > 0).sum(-1).astype(jnp.int32),
        )


def _bd_tiles(cfg, attn_impl, row_len, block_cap):
    """The kernels' tile a grad dispatch of ``row_len``-slot rows runs and
    the core's backward, its one kernel or its two (``ops.flash.instance``);
    nothing where the XLA form takes the row."""
    inst = flash_ops.instance(
        row_len, cfg.head_dim, block_cap=block_cap, heads=cfg.num_heads,
        block_diffusion=True,
    )
    if not inst.kind:
        return {}
    return {"bd_tile": f"{inst.edge}x{inst.kvc}", "bd_bwd": inst.backward}


def _swa_backwards(cfg, attn_impl, row_len, block_cap):
    """The backward the window layers' kernel instance took
    (``ops.flash.backward_form``); nothing where another attention runs."""
    if attn_impl != "flash":
        return {}
    return {
        "attn_bwd_window": flash_ops.backward_form(
            row_len, cfg.head_dim, cfg.swa.window, block_cap,
            heads=cfg.swa.num_heads,
        ),
    }


def _no_kinds_stack(cfg: ModelConfig) -> str:
    return (
        "the generation engine cannot run a stack of block kinds "
        f"({', '.join(block_kinds(cfg))}): it has no recurrent state beside "
        "its KV pages and no latent paged cache (ROADMAP B); the trainer can"
    )


# what the chunked delta rule counts (``ops/kda.KDAStats``), either decay
_RULE_COUNTS = {
    "kda_tokens": "the real tokens the layers' scans ran over",
    "kda_chunk_slots": "the chunk slots they ran, 64 a chunk",
    "kda_segments": "the sequence starts they reset at",
}

# In the order a model's refusals are raised and its counts are made. A
# kind's two halves are keys (``dense`` declares nothing); ``moe_held``: the
# sparse FFN of a chip that holds a share (a model's, or a mesh's split).
MECHANISMS: Dict[str, Mechanism] = {
    "moe": Mechanism(
        counts={
            "moe_assignments": "the routed (token, expert) pairs, summed "
            "over the sparse layers (of the experts held, where a share is)",
            "moe_max_expert_assignments": "the busiest expert's of them",
        },
        count=_reported("moe", "assignments", "max_expert_assignments"),
        span_attrs=lambda cfg: {  # the router where not ``h @ w_router``
            "moe_impl": moe_ops.grouped_impl(),
            "router": cfg.router and "mlp_carried",
        },
    ),
    "moe_held": Mechanism(
        counts={
            "moe_stream_rows": "the sorted stream's rows the expert paths "
            "ran over: a slab's times the slabs each chunk's held pairs fill",
            "moe_overflow_chunks": "the chunks that filled more than one",
        },
        count=_reported("moe", "stream_rows", "overflow_chunks"),
    ),
    "indexer": Mechanism(
        counts={
            "dsa_kept_pairs": "the selected (query, key) pairs",
            "dsa_causal_pairs": "the causal pairs they were chosen among",
        },
        # int32 [L] as they are: a 16k-token row's pairs pass a float32
        count=_reported(
            "indexer", "kept_pairs", "causal_pairs", reduce=lambda a: a
        ),
        forced_attn="sparse_topk",
        no_seq_parallel=lambda cfg: (
            f"{dsa_ops.MECHANISM} has no sequence-parallel form "
            "(seq_parallel_size={seq_parallel_size}): ROADMAP B"
        ),
        no_generation=lambda cfg: (
            f"the generation engine cannot run {dsa_ops.MECHANISM}: it has "
            "no indexer-key cache beside its KV pages and no top-k decode "
            "(ROADMAP B); the trainer can (attn_impl 'sparse_topk')"
        ),
        span_attrs=lambda cfg: {"dsa_select_impl": dsa_ops.select_impl()},
    ),
    # a model that generates by diffusion over blocks (``ModelConfig.
    # block_diffusion``): a trainer's rows hold a clean stream and its masked
    # copies (``utils/data.pack_batch_rows``), the core's mask is causal
    # between blocks and two-way inside one (``ops/bd_attention.py``). Before
    # the mixers' entries: its refusals are the model's
    "block_diffusion": Mechanism(
        counts={
            "bd_pairs": "the (query, key) pairs the mask keeps, a row's "
            "(every layer attends them)",
            "bd_causal_pairs": "the pairs a causal model would keep over "
            "the same sequences' own tokens",
            "bd_scored_tokens": "the tokens scored, each once, at the slot "
            "of its own denoising state",
        },
        count=_bd_counts,
        forced_attn="block_diffusion",
        no_seq_parallel=lambda cfg: (
            f"a block-diffusion policy's rows (family {cfg.family!r}: a "
            "noisy stream's queries read clean keys a stream away) have no "
            "sequence-parallel form (seq_parallel_size={seq_parallel_size})"
            ": ROADMAP B"
        ),
        no_generation=lambda cfg: (
            "the generation engine cannot run a model that generates by "
            f"diffusion over blocks (family {cfg.family!r}, blocks of "
            f"{cfg.block_diffusion.block_length}): a step yields other than "
            "one token a sequence, and its scheduler, cache and metrics "
            "assume one (ROADMAP B); the trainer can, on batches that carry "
            "a per-token denoise_state"
        ),
        span_attrs=lambda cfg: {
            "bd_block": cfg.block_diffusion.block_length,
        },
        grad_span_attrs=_bd_tiles,
    ),
    "swa": Mechanism(
        counts={
            "swa_pairs": "the (query, key) pairs inside the windows",
            "swa_causal_pairs": "what those layers would attend without one",
        },
        count=_swa_counts,
        no_seq_parallel=lambda cfg: (
            f"window attention (a layer's window of {cfg.swa.window} keys) "
            "has no sequence-parallel form (attn_impl={attn_impl!r}, "
            "seq_parallel_size={seq_parallel_size}): ROADMAP B.3"
        ),
        no_generation=lambda cfg: (
            "the generation engine cannot run window attention (family "
            f"{cfg.family!r}: layers of a {cfg.swa.window}-key window among "
            "full ones, each kind with its own heads): window layers want "
            "pages that stop growing and an allocator keyed by layer kind "
            "(ROADMAP B.3); the trainer can"
        ),
        span_attrs=lambda cfg: {"layer_window": cfg.swa.window},
        grad_span_attrs=_swa_backwards,
    ),
    "cca": Mechanism(
        counts={
            "cca_causal_pairs": "the (query, key) pairs the cores attended, "
            "L (L + 1) / 2 a sequence of L tokens",
            "cca_starts": "the tokens at which the convolutions and the "
            "value shift reset, one a sequence",
        },
        count=_cca_counts,
        no_seq_parallel=lambda cfg: (
            "compressed convolutional attention (family 'zaya': causal "
            "convolutions and a one-token value shift before the core) has "
            "no sequence-parallel form (attn_impl={attn_impl!r}, "
            "seq_parallel_size={seq_parallel_size}): a convolution and a "
            "shift across a ring's borders are not built"
        ),
        no_generation=lambda cfg: (
            "the generation engine cannot run compressed convolutional "
            f"attention (family {cfg.family!r}): a decode step needs the "
            "last tokens' pre-convolution q and k and the last token's "
            "second value beside the KV pages, recurrent state beside paged "
            "state (ROADMAP B.4), and its router's state passes from layer "
            "to layer; the trainer can"
        ),
    ),
    "kda": Mechanism(
        counts=_RULE_COUNTS,
        count=_reported("kda", "tokens", "chunk_slots", "segments"),
        no_generation=_no_kinds_stack,
        span_attrs=lambda cfg: {
            "kda_impl": kda_ops.scan_impl(cfg.kda.head_dim, cfg.kda.head_dim),
            "prep_impl": _kda_prep_impl(cfg),
        },
    ),
    "gdn": Mechanism(  # the same rule, one decay a head: the op's names
        counts=_RULE_COUNTS,
        count=_reported("gdn", "tokens", "chunk_slots", "segments"),
        no_seq_parallel=lambda cfg: (
            f"the gated delta rule (family {cfg.family!r}: a state a head "
            "that passes from token to token) has no sequence-parallel form "
            "(attn_impl={attn_impl!r}, seq_parallel_size={seq_parallel_size})"
            ": the state would have to be handed from device to device, "
            "ROADMAP B"
        ),
        no_generation=_no_kinds_stack,
        span_attrs=lambda cfg: {
            "kda_impl": kda_ops.scan_impl(
                cfg.gdn.head_k_dim, cfg.gdn.head_v_dim
            ),
            "gdn_decay": "scalar",
            "prep_impl": _gdn_prep_impl(cfg),
        },
    ),
    "ssm": Mechanism(
        counts={
            "ssm_tokens": "the real tokens the layers' selective scans ran "
            "over",
            "ssm_segments": "the sequence starts their states were reset at",
        },
        count=_reported("ssm", "tokens", "segments"),
        no_seq_parallel=lambda cfg: (
            f"a selective scan (family {cfg.family!r}: a state a channel "
            "that passes from token to token) has no sequence-parallel form "
            "(attn_impl={attn_impl!r}, seq_parallel_size={seq_parallel_size})"
            ": the state would have to be handed from device to device, "
            "ROADMAP B"
        ),
        no_generation=lambda cfg: (
            "the generation engine cannot run a state-space mixer (family "
            f"{cfg.family!r}): a layer's cache is a recurrent state "
            f"[{cfg.ssm.d_inner}, {cfg.ssm.d_state}] and the convolution's "
            "last tokens, not KV pages (ROADMAP B.4); the trainer can"
        ),
        span_attrs=lambda cfg: {
            "ssm_impl": ssm_ops.scan_impl(cfg.ssm.d_inner),
            "ssm_chunk": ssm_ops.CHUNK,
            "ssm_channel_block": ssm_ops.CHANNELS,
        },
    ),
    "mla": Mechanism(
        no_generation=_no_kinds_stack,
        span_attrs=lambda cfg: {
            "mla_query": "compressed" if cfg.mla.q_lora_rank else "full",
            "mla_rope": cfg.mla.rotated and cfg.mla.qk_rope_head_dim,
        },
    ),
    "full": Mechanism(no_generation=_no_kinds_stack),
    # the draft module beside the stack, where ``grad_step`` trains it: its
    # counts come with its loss (``ops/functional.draft_loss``, ``draft_
    # forward``'s stats), not from the stack's layers
    "mtp": Mechanism(
        counts={
            "mtp_tokens": "the draft module's targets: the tokens two "
            "slots on in a slot's own sequence that the loss mask holds",
            "mtp_assignments": "the module's block's part of "
            "moe_assignments",
        },
        no_seq_parallel=lambda cfg: (
            f"the draft module (family {cfg.family!r}: the next token's "
            "embedding beside every slot, a shift inside a packed row) has "
            "no sequence-parallel form (attn_impl={attn_impl!r}, "
            "seq_parallel_size={seq_parallel_size}): the shift across a "
            "ring's borders is not built"
        ),
        no_generation=lambda cfg: (
            "the generation engine cannot draft with the model's own module "
            f"(family {cfg.family!r}): inference/spec.py drafts by n-gram "
            "lookup and has no second head to run (ROADMAP B.7)"
        ),
        span_attrs=lambda cfg: {
            "mtp_layers": cfg.mtp_layers, "mtp_loss_coef": cfg.mtp_loss_coef,
        },
    ),
}


def _mechanisms(cfg: ModelConfig, split=False) -> Tuple[Mechanism, ...]:
    """Its entries of ``MECHANISMS``, in order (``split``: on such a mesh)."""
    has = {half for kind in cfg.layer_kinds for half in kind.split("+")}
    if not cfg.layer_kinds:  # the one-kind stack
        has = {"indexer"} if cfg.has_indexer else set()
        has |= {"moe"} if cfg.is_moe else set()
    if "moe" in has and (cfg.experts_held or split):
        has.add("moe_held")
    if cfg.trains_draft:
        has.add("mtp")
    if cfg.block_diffusion is not None:
        has.add("block_diffusion")
    return tuple(m for name, m in MECHANISMS.items() if name in has)


def _declared(cfg: ModelConfig, field: str, *args, split=False) -> list:
    """What the model's mechanisms declare under ``field`` (those that
    declare nothing left out, a function called with ``cfg`` and ``args``)."""
    said = [getattr(m, field) for m in _mechanisms(cfg, split)]
    return [say(cfg, *args) if callable(say) else say for say in said if say]


def count_names(cfg: ModelConfig, split=False) -> Dict[str, str]:
    """count -> what it counts a microbatch: ``LayerStats.counts``'s keys."""
    said = _declared(cfg, "counts", split=split)
    return {k: v for c in said for k, v in c.items()}


def seq_parallel_refusal(cfg: ModelConfig) -> Tuple[str, str]:
    """What ``ops.flash.resolve_attn_impl`` takes of a model: the attention
    it forces whatever was asked, and why it has no sequence-parallel form."""
    fields = ("forced_attn", "no_seq_parallel")
    return tuple((_declared(cfg, f) + [""])[0] for f in fields)


def generation_refusal(cfg: ModelConfig) -> str:
    """Why the generation engine cannot run the model ("": it can)."""
    return (_declared(cfg, "no_generation") + [""])[0]


def _rotated_width(cfg: ModelConfig) -> int:
    """The width of the heads the model rotates; 0: it rotates none (a stack
    of kinds without a rotary or a ``cca`` mixer, its latent mixer NoPE). A
    rotated latent mixer turns a slice of its head by itself, that wide."""
    rotates = not cfg.layer_kinds or cfg.cca is not None or any(
        getattr(cfg, mixer) is not None and not getattr(cfg, mixer).nope
        for mixer in _ROTARY_MIXERS
    )
    if not rotates and cfg.mla is not None and cfg.mla.rotated:
        return cfg.mla.qk_rope_head_dim
    return cfg.head_dim if rotates else 0


def span_attrs(cfg: ModelConfig, split=False) -> Dict[str, Any]:
    """What a trainer's dispatch span says of the model's mechanisms, and
    of the rotation every rotary mixer shares (``rope_impl``: "kernel" |
    "xla", ``ops/rotary.rotate_impl`` on the trainer's rows)."""
    said = _declared(cfg, "span_attrs") + [{
        "moe_mesh": split and "exchange",
        "rope_impl": rotary_ops.rotate_impl(_rotated_width(cfg)),
    }]
    return {k: v for attrs in said for k, v in attrs.items() if v}


def grad_span_attrs(
    cfg: ModelConfig, attn_impl: str, row_len: int, block_cap: int = 0
) -> Dict[str, Any]:
    """What a grad dispatch of ``row_len``-token rows says of the model's
    mechanisms beside ``span_attrs``, and of the splash core every model
    that runs it shares: the full-attention layers' tile edge and backward
    (``ops.flash.instance`` under ``block_cap``, the engine's probed cap)."""
    said = _declared(cfg, "grad_span_attrs", attn_impl, row_len, block_cap)
    if attn_impl == "flash":
        full = flash_ops.instance(
            row_len, cfg.attn_head_dims[0], block_cap=block_cap,
            heads=cfg.num_heads,
        )
        said.append({"attn_edge": full.edge, "attn_bwd_full": full.backward})
    return {k: v for attrs in said for k, v in attrs.items() if v}


def _stack_stats(cfg, reported, segment_ids, positions) -> LayerStats:
    """``LayerStats`` of the whole stack on these rows; ``reported`` as
    ``Mechanism.count`` takes it."""
    aux_loss, counts, split = jnp.zeros((), jnp.float32), {}, False
    if "moe" in reported:  # the mean over the sparse layers
        aux_loss = jnp.mean(
            jnp.concatenate([run.aux_loss for run in reported["moe"]])
        )
        split = reported["moe"][0].stream_rows is not None  # shares ran
    for m in _mechanisms(cfg, split):
        if m.count is not None:
            arrays = m.count(cfg, reported, segment_ids, positions)
            counts.update(zip(m.counts, arrays, strict=True))
    return LayerStats(aux_loss, counts)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def init_params(
    cfg: ModelConfig, rng: jax.Array, dtype=jnp.bfloat16,
    value_head: bool = False,
) -> Params:
    """Random init (scaled normal), HF-compatible structure.
    ``value_head`` adds a scalar head [D, 1] (critic models — reference
    SequenceParallelCriticHead, realhf/impl/model/nn/real_llm_base.py)."""
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    Qd, KVd = cfg.q_dim, cfg.kv_dim
    keys = jax.random.split(rng, 9)

    def nrm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    std = 0.02
    # gemma norms scale by (1 + w): identity init is ZEROS there
    norm_init = jnp.zeros if cfg.norm_add_unit_offset else jnp.ones
    layers = {
        "input_norm": norm_init((L, D), dtype),
        "post_attn_norm": norm_init((L, D), dtype),
        "wq": nrm(keys[0], (L, D, Qd), std),
        "wk": nrm(keys[1], (L, D, KVd), std),
        "wv": nrm(keys[2], (L, D, KVd), std),
        "wo": nrm(keys[3], (L, Qd, D), std),
    }
    if cfg.is_moe:
        # the router scores every expert; the weights are of those held
        E, Fe = cfg.num_held_experts, cfg.expert_ffn_size
        layers["w_router"] = nrm(keys[8], (L, D, cfg.num_experts), std)
        layers["w_gate"] = nrm(keys[4], (L, E, D, Fe), std)
        layers["w_up"] = nrm(keys[5], (L, E, D, Fe), std)
        layers["w_down"] = nrm(keys[6], (L, E, Fe, D), std)
        if cfg.shared_expert_size:  # qwen2_moe shared expert + gate
            Fs = cfg.shared_expert_size
            layers["w_shared_gate"] = nrm(
                jax.random.fold_in(rng, 31), (L, D, Fs), std
            )
            layers["w_shared_up"] = nrm(
                jax.random.fold_in(rng, 32), (L, D, Fs), std
            )
            layers["w_shared_down"] = nrm(
                jax.random.fold_in(rng, 33), (L, Fs, D), std
            )
            layers["w_shared_router"] = nrm(
                jax.random.fold_in(rng, 34), (L, D, 1), std
            )
    else:
        layers["w_gate"] = nrm(keys[4], (L, D, F), std)
        layers["w_up"] = nrm(keys[5], (L, D, F), std)
        layers["w_down"] = nrm(keys[6], (L, F, D), std)
    if cfg.has_indexer:  # query heads, one key head, a weight a head
        Hi, Di = cfg.dsa_index_heads, cfg.dsa_index_head_dim
        ikeys = jax.random.split(jax.random.fold_in(rng, 41), 3)
        layers["w_idx_q"] = nrm(ikeys[0], (L, D, Hi * Di), std)
        layers["w_idx_k"] = nrm(ikeys[1], (L, D, Di), std)
        layers["w_idx_w"] = nrm(ikeys[2], (L, D, Hi), std)
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, Qd), dtype)
        layers["bk"] = jnp.zeros((L, KVd), dtype)
        layers["bv"] = jnp.zeros((L, KVd), dtype)
    if cfg.qk_norm:  # one weight a head, or one over the whole projection
        per_head = cfg.qk_norm == "head"
        layers["q_norm"] = jnp.ones(
            (L, cfg.head_dim if per_head else Qd), dtype
        )
        layers["k_norm"] = jnp.ones(
            (L, cfg.head_dim if per_head else KVd), dtype
        )
    if cfg.layer_kinds:  # one stack a block kind, none of the above
        layers = _init_kinds(cfg, rng, dtype)
    params: Params = {
        "embedding": nrm(keys[7], (cfg.vocab_size, D), std),
        "layers": layers,
        "final_norm": norm_init((D,), dtype),
    }
    if value_head:
        # critics replace the LM head with the scalar head entirely
        params["value_head"] = nrm(
            jax.random.fold_in(rng, 101), (D, 1), std
        )
    elif not cfg.tie_word_embeddings:
        params["lm_head"] = nrm(
            jax.random.fold_in(rng, 99), (D, cfg.vocab_size), std
        )
    if cfg.vision is not None:
        from areal_tpu.models import vision as vision_lib

        params["vision"] = vision_lib.init_vision_params(
            cfg.vision, jax.random.fold_in(rng, 7), dtype=dtype
        )
    if cfg.mtp_layers:
        params["mtp"] = _init_draft(cfg, rng, dtype)
    return params


def param_logical_axes(cfg: ModelConfig, value_head: bool = False) -> Params:
    """Same-structure tree of logical axis name tuples.

    Logical names: "vocab", "embed" (fsdp-sharded model dim), "heads" and
    "mlp" (tensor-parallel attention and ffn dims), "expert" and
    "exp_embed" (``parallel/sharding``), "layer" (scanned), None (no axis).
    """
    layers = {
        "input_norm": ("layer", None),
        "post_attn_norm": ("layer", None),
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "heads"),
        "wv": ("layer", "embed", "heads"),
        "wo": ("layer", "heads", "embed"),
    }
    if cfg.is_moe:
        layers["w_router"] = ("layer", "embed", None)
        layers["w_gate"] = ("layer", "expert", "exp_embed", "mlp")
        layers["w_up"] = ("layer", "expert", "exp_embed", "mlp")
        layers["w_down"] = ("layer", "expert", "mlp", "exp_embed")
        if cfg.shared_expert_size:
            layers["w_shared_gate"] = ("layer", "embed", "mlp")
            layers["w_shared_up"] = ("layer", "embed", "mlp")
            layers["w_shared_down"] = ("layer", "mlp", "embed")
            layers["w_shared_router"] = ("layer", "embed", None)
    else:
        layers["w_gate"] = ("layer", "embed", "mlp")
        layers["w_up"] = ("layer", "embed", "mlp")
        layers["w_down"] = ("layer", "mlp", "embed")
    if cfg.has_indexer:  # small, and every device selects for its own rows
        layers["w_idx_q"] = ("layer", "embed", None)
        layers["w_idx_k"] = ("layer", "embed", None)
        layers["w_idx_w"] = ("layer", "embed", None)
    if cfg.attention_bias:
        layers["bq"] = ("layer", "heads")
        layers["bk"] = ("layer", "heads")
        layers["bv"] = ("layer", "heads")
    if cfg.qk_norm:
        layers["q_norm"] = ("layer", None)
        layers["k_norm"] = ("layer", None)
    if cfg.layer_kinds:
        layers = {
            kind: {
                name: ("layer",) + leaf[2]
                for name, leaf in _kind_leaves(cfg, kind).items()
            }
            for kind in block_kinds(cfg)
        }
    axes: Params = {
        "embedding": ("vocab", "embed"),
        "layers": layers,
        "final_norm": (None,),
    }
    if value_head:
        axes["value_head"] = ("embed", None)
    elif not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.vision is not None:
        from areal_tpu.models import vision as vision_lib

        axes["vision"] = vision_lib.vision_logical_axes(cfg.vision)
    if cfg.mtp_layers:
        axes["mtp"] = {
            name: leaf[2] for name, leaf in _draft_leaves(cfg).items()
        }
        axes["mtp"]["block"] = {
            name: ("layer",) + leaf[2]
            for name, leaf in _kind_leaves(cfg, draft_kind(cfg)).items()
        }
    return axes


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------
def _layer_body(
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B, T, D]
    lp: Params,  # one layer's params (leading layer axis removed)
    segment_ids: jnp.ndarray,
    positions: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attend_fn: Optional[Any] = None,
    moe_fn: Optional[Any] = None,
):
    b, t, d = x.shape
    uo = cfg.norm_add_unit_offset
    # device scopes (utils/tracing.DEVICE_SCOPES): names only — what a
    # profile's per-op shares are read by; no number and no fusion changes.
    # Each is opened HERE, at the call site, never inside a helper that
    # another scope's code calls too (``rms_norm``, ``apply_rope``)
    with device_scope("stream_norm"):
        h = rms_norm(
            x, lp["input_norm"], cfg.rms_norm_eps, add_unit_offset=uo
        )
    with device_scope("qkv_proj"):
        q = h @ lp["wq"]
        k = h @ lp["wk"]
        v = h @ lp["wv"]
        if cfg.attention_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        if cfg.qk_norm == "projection":  # olmoe: before the split into heads
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        q = q.reshape(b, t, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        in_rotation = _head_norm_in_rotation(cfg, lp, t)
        q_norm, k_norm = in_rotation("q_norm"), in_rotation("k_norm")
        if cfg.qk_norm == "head" and q_norm is None:  # qwen3: each head
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    with device_scope("rope"):
        if positions.ndim == 3:  # [B, T, 3] multimodal (t, h, w) positions
            q = apply_mrope(
                q, positions, cos, sin, cfg.mrope_sections, q_norm
            )
            k = apply_mrope(
                k, positions, cos, sin, cfg.mrope_sections, k_norm
            )
        else:
            q = apply_rope(q, positions, cos, sin, q_norm)
            k = apply_rope(k, positions, cos, sin, k_norm)
    dsa = None
    if cfg.has_indexer:
        # the indexer reads the layer's normed input, detached: it picks
        # keys, and the policy loss has nothing to tell it (ops/
        # sparse_attention.py, which opens the scopes of its own steps)
        with device_scope("dsa_index"):
            hi = jax.lax.stop_gradient(h)
            qi = (hi @ lp["w_idx_q"]).reshape(
                b, t, cfg.dsa_index_heads, cfg.dsa_index_head_dim
            )
            ki = hi @ lp["w_idx_k"]
            wi = hi @ lp["w_idx_w"]
        attn, dsa = (
            attend_fn or functools.partial(sparse_attention, topk=cfg.dsa_topk)
        )(q, k, v, segment_ids, qi, ki, wi)
    else:
        with device_scope("attention"):
            if attend_fn is None:
                attn = segment_attention(q, k, v, segment_ids, causal=True)
            else:  # explicit SP kernel (ring / ulysses shard_map)
                attn = attend_fn(q, k, v, segment_ids)
    # named so a remat policy can SAVE attention outputs: recomputing the
    # forward inside the backward costs 0.6 ms a layer with the splash
    # kernel and 3.8 ms with the XLA form at 4096 tokens (v5e, PERF.md
    # PR 26), against [B,T,Hq,D] bf16 of storage
    attn = _ad_checkpoint.checkpoint_name(attn, "attn_out")
    with device_scope("o_proj"):
        y = attn.reshape(b, t, cfg.q_dim) @ lp["wo"]
    with device_scope("residual"):
        x = x + y
    return _ffn_residual(cfg, x, lp, segment_ids, moe_fn, cfg.is_moe, dsa)


def _ffn_residual(
    cfg, x, lp, segment_ids, moe_fn, sparse: bool, mixer_stats=None,
):
    """The second half of every block: (x + FFN(norm(x)), what the layer's
    ops report: (the routed experts' ``MoEStats``, ``mixer_stats``)).
    ``sparse``: routed experts (with the shared one), else the dense MLP."""
    uo = cfg.norm_add_unit_offset
    with device_scope("stream_norm"):
        h = rms_norm(
            x, lp["post_attn_norm"], cfg.rms_norm_eps, add_unit_offset=uo
        )
    if sparse:
        # padding tokens (segment 0) are no expert's load
        with device_scope("mlp"):
            if moe_fn is None:
                ffn, stats = moe_ffn_from_params(
                    cfg, lp, h, valid=segment_ids > 0
                )
            else:  # per device under a mesh (a shard_map of the same)
                ffn, stats = moe_fn(lp, h, segment_ids > 0)
    else:
        stats = None
        with device_scope("mlp"):
            ffn = (
                hidden_act_fn(cfg.hidden_act)(h @ lp["w_gate"])
                * (h @ lp["w_up"])
            ) @ lp["w_down"]
    with device_scope("residual"):  # outside ``mlp``, as the norm above is
        x = x + ffn
    return x, (stats, mixer_stats)


def _short_conv(x, w, segment_ids, bias=None):
    """Depthwise causal convolution over the tokens of ONE sequence:
    y_t = sum_s w[:, K-1-s] x_{t-s} (+ ``bias`` [C]), over the s for which
    token t - s is of token t's sequence. ``x`` [B, T, C]; ``w`` [C, K], tap
    K-1 the token itself. Float32."""
    taps = w.shape[-1]
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    y = x * w[:, taps - 1]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    for s in range(1, taps):
        back = jnp.pad(x, ((0, 0), (s, 0), (0, 0)))[:, :-s]
        seg_back = jnp.pad(segment_ids, ((0, 0), (s, 0)))[:, :-s]
        same = (seg_back == segment_ids) & (segment_ids > 0)
        y = y + jnp.where(same[..., None], back, 0.0) * w[:, taps - 1 - s]
    return y


def _kda_prep_impl(cfg, t=None) -> str:
    """``ops/conv_prep.prep_impl`` for the ``kda`` mixer's three convolutions
    on rows of ``t`` tokens (None: the trainer's): q and k normed a head, v
    not, all of one width."""
    kc = cfg.kda
    c = kc.num_heads * kc.head_dim
    return conv_prep_ops.prep_impl(c, c, kc.head_dim, t, kc.conv_size)


def _gdn_prep_impl(cfg, t=None) -> str:
    """The same for the ``gdn`` mixer's one convolution over q, k (normed a
    key head) and v together."""
    gc = cfg.gdn
    return conv_prep_ops.prep_impl(
        2 * gc.key_dim + gc.value_dim, 2 * gc.key_dim, gc.head_k_dim, t,
        gc.conv_size,
    )


def _kda_mixer(cfg, h, lp, segment_ids, kda_fn):
    """The gated delta-rule mixer on the normed input ``h`` [B, T, D]:
    (y [B, T, D], ``KDAStats``). ``ops/kda.py`` has the rule; here is what
    feeds it and what follows it (scope ``kda_prep``)."""
    b, t, _ = h.shape
    kc = cfg.kda
    H, dh = kc.num_heads, kc.head_dim
    with device_scope("qkv_proj"):
        proj = {n: h @ lp[f"w{n}"] for n in "qkv"}
    with device_scope("kda_prep"):
        def conv(n):
            y = jax.nn.silu(_short_conv(proj[n], lp[f"conv_{n}"], segment_ids))
            return y.reshape(b, t, H, dh)

        def l2norm(y):
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6
            )

        impl = _kda_prep_impl(cfg, t)
        if impl == "xla":
            q = l2norm(conv("q")).astype(h.dtype)
            k = l2norm(conv("k")).astype(h.dtype)
            v = conv("v").astype(h.dtype)
        else:  # one kernel a projection: ``ops/conv_prep.py``
            q, k, v = (
                conv_prep_ops.conv_prep(
                    proj[n], lp[f"conv_{n}"], segment_ids, normed,
                    impl == "interpret",
                ).reshape(b, t, H, dh)
                for n, normed in (("q", H * dh), ("k", H * dh), ("v", 0))
            )
        f = ((h @ lp["w_f_a"]) @ lp["w_f_b"]).astype(jnp.float32)
        rate = jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]
        g = -rate * jax.nn.softplus(
            f + lp["dt_bias"].astype(jnp.float32)
        ).reshape(b, t, H, dh)
        beta = jax.nn.sigmoid((h @ lp["w_beta"]).astype(jnp.float32))
    o, stats = (kda_fn or kda_scan)(q, k, v, g, beta, segment_ids)
    # kept across forward -> backward as an attention output is: the
    # backward recomputes the rule's chunk states from q, k, v, g, beta,
    # and the rule's forward is not run a second time for its output
    o = _ad_checkpoint.checkpoint_name(o, "attn_out")
    with device_scope("kda_prep"):
        gate = jax.nn.sigmoid(
            ((h @ lp["w_g_a"]) @ lp["w_g_b"]).astype(jnp.float32)
        ).reshape(b, t, H, dh)
        y = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
        y = (y.astype(jnp.float32) * gate).astype(h.dtype)
    with device_scope("o_proj"):
        return y.reshape(b, t, H * dh) @ lp["wo"], stats


def _gdn_mixer(cfg, h, lp, segment_ids, kda_fn):
    """The scalar-decay gated delta-rule mixer on the normed input ``h``
    [B, T, D]: (y [B, T, D], ``KDAStats``). One product makes q, k (the key
    heads), v and the output's gate z (the value heads), one more beta and
    the decay's input; q, k and v pass ONE convolution together;
    ``ops/kda.py`` has the rule in its scalar-decay form (a key head serves
    its value heads there); the output is normed a head by a plain weight
    and gated by ``silu(z)`` (scope ``kda_prep`` around the rule, as the
    ``kda`` mixer's)."""
    b, t, _ = h.shape
    gc = cfg.gdn
    Hk, Hv, dk, dv = (
        gc.num_k_heads, gc.num_v_heads, gc.head_k_dim, gc.head_v_dim
    )
    with device_scope("qkv_proj"):
        qkv, z = jnp.split(h @ lp["w_qkvz"], [2 * gc.key_dim + gc.value_dim],
                           axis=-1)
        ba = h @ lp["w_ba"]
    with device_scope("kda_prep"):
        def l2norm(y):
            y = y.reshape(b, t, Hk, dk)
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6
            )

        impl = _gdn_prep_impl(cfg, t)
        if impl == "xla":
            qkv = jax.nn.silu(_short_conv(qkv, lp["conv_qkv"], segment_ids))
            q, k, v = jnp.split(qkv, [gc.key_dim, 2 * gc.key_dim], axis=-1)
            q, k = l2norm(q).astype(h.dtype), l2norm(k).astype(h.dtype)
            v = v.reshape(b, t, Hv, dv).astype(h.dtype)
        else:  # q, k, v through ONE kernel call: ``ops/conv_prep.py``
            q, k, v = jnp.split(
                conv_prep_ops.conv_prep(
                    qkv, lp["conv_qkv"], segment_ids, 2 * gc.key_dim,
                    impl == "interpret",
                ),
                [gc.key_dim, 2 * gc.key_dim], axis=-1,
            )
            q, k = q.reshape(b, t, Hk, dk), k.reshape(b, t, Hk, dk)
            v = v.reshape(b, t, Hv, dv)
        beta_in, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(beta_in)
        g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + lp["dt_bias"].astype(jnp.float32)
        )  # [B, T, Hv]: one number a value head and token
    o, stats = (kda_fn or kda_scan)(q, k, v, g, beta, segment_ids)
    o = _ad_checkpoint.checkpoint_name(o, "attn_out")  # as ``_kda_mixer``
    with device_scope("kda_prep"):
        y = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
        y = (
            y.astype(jnp.float32)
            * jax.nn.silu(z.astype(jnp.float32)).reshape(b, t, Hv, dv)
        ).astype(h.dtype)
    with device_scope("o_proj"):
        return y.reshape(b, t, Hv * dv) @ lp["wo"], stats


def _ssm_mixer(cfg, h, lp, segment_ids, ssm_fn):
    """The state-space mixer (Mamba-1 with the ``jamba`` family's three inner
    norms) on the normed input ``h`` [B, T, D]: (y [B, T, D], ``SSMStats``).
    One product makes x and the gate z; x passes the biased causal
    convolution and ``silu``; one more product makes the step's input (normed,
    then through ``w_dt``, its bias and ``softplus``: float32 from the
    product on) and the token's ``B`` and ``C`` (normed); ``ops/ssm_scan.py``
    has the scan, in float32; its output takes the skip ``d_skip * x`` and
    the gate ``silu(z)`` (scope ``ssm_prep`` around the scan; the in and out
    projections under ``qkv_proj`` / ``o_proj``)."""
    sc, f32 = cfg.ssm, jnp.float32
    with device_scope("qkv_proj"):
        x, z = jnp.split(h @ lp["w_in"], 2, axis=-1)
    with device_scope("ssm_prep"):
        x = jax.nn.silu(_short_conv(
            x, lp["conv_x"], segment_ids, lp["conv_x_bias"]
        )).astype(h.dtype)
        r, bm, cm = jnp.split(
            x @ lp["w_x"], [sc.dt_rank, sc.dt_rank + sc.d_state], axis=-1
        )
        r = rms_norm(r, lp["dt_norm"], cfg.rms_norm_eps)
        bm = rms_norm(bm, lp["b_norm"], cfg.rms_norm_eps)
        cm = rms_norm(cm, lp["c_norm"], cfg.rms_norm_eps)
        dt = jax.nn.softplus(
            jnp.dot(r, lp["w_dt"], preferred_element_type=f32)
            + lp["dt_bias"].astype(f32)
        )
        rate = -jnp.exp(lp["a_log"].astype(f32))
    y, stats = (ssm_fn or ssm_ops.ssm_scan)(x, dt, rate, bm, cm, segment_ids)
    with device_scope("ssm_prep"):
        y = (
            (y + lp["d_skip"].astype(f32) * x.astype(f32))
            * jax.nn.silu(z.astype(f32))
        ).astype(h.dtype)
    with device_scope("o_proj"):
        return y @ lp["wo"], stats


def _mla_mixer(cfg, h, lp, segment_ids, attend_fn, rotation=None):
    """Latent attention on the normed input ``h``: the keys' and values' one
    compression, a key part all heads share, and a causal softmax core whose
    query/key width differs from its value's. The query is ``h @ wq`` or,
    where ``MLAConfig.q_lora_rank`` says so, passes a compression and a norm
    of its own. ``rotation``: (cos, sin) of the tokens' positions where the
    mixer is ``rotated`` (the shared key part and the last
    ``qk_rope_head_dim`` channels of every query head turn, rotate-half over
    those channels); None: NoPE, the same program without the turn."""
    b, t, _ = h.shape
    m, H = cfg.mla, cfg.num_heads
    with device_scope("qkv_proj"):
        if m.q_lora_rank:
            q = rms_norm(
                h @ lp["wq_a"], lp["q_a_norm"], cfg.rms_norm_eps
            ) @ lp["wq_b"]
        else:
            q = h @ lp["wq"]
        q = q.reshape(b, t, H, m.qk_head_dim)
        c, k_shared = jnp.split(h @ lp["w_kv_a"], [m.kv_lora_rank], axis=-1)
        c = rms_norm(c, lp["kv_norm"], cfg.rms_norm_eps)
    if rotation is not None:  # a slice of a head beside a pass-through
        with device_scope("rope"):
            q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
            q_rope = rotate_leading(q_rope, *rotation)
            k_shared = rotate_leading(
                k_shared[:, :, None, :], *rotation
            )[:, :, 0]
    # from the normed latent to what the core takes: what a core that took
    # the shared key ONCE would not write. The NoPE mixer's (Kimi's) stays
    # under the name its cell's shares have read it by
    with device_scope("mla_expand" if m.rotated else "qkv_proj"):
        k_nope, v = jnp.split(
            (c @ lp["w_kv_b"]).reshape(
                b, t, H, m.qk_nope_head_dim + m.v_head_dim
            ),
            [m.qk_nope_head_dim], axis=-1,
        )
        k = jnp.concatenate([
            k_nope,
            jnp.broadcast_to(
                k_shared[:, :, None, :], (b, t, H, m.qk_rope_head_dim)
            ),
        ], axis=-1)
        if rotation is not None:
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
    with device_scope("attention"):
        if attend_fn is None:
            attn = segment_attention(q, k, v, segment_ids, causal=True)
        else:
            attn = attend_fn(q, k, v, segment_ids)
    attn = _ad_checkpoint.checkpoint_name(attn, "attn_out")
    with device_scope("o_proj"):
        return attn.reshape(b, t, H * m.v_head_dim) @ lp["wo"]


def _head_norm_in_rotation(cfg, lp, t):
    """name -> q's or k's head norm as ``ops/rotary.rotate`` takes one,
    (weight, eps), where the rotation's kernel runs on rows of ``t`` tokens
    and norms a head in its own pass (a norm that scales by (1 + w) hands it
    that sum); name -> None where the caller norms (the ``jnp`` form) and for
    a model that does not norm q and k a head."""
    fused = cfg.qk_norm == "head" and rotary_ops.rotate_impl(
        cfg.head_dim, t
    ) != "xla"

    def weight(name):  # a (1 + w) norm hands the kernel the sum
        w = lp[name]
        if cfg.norm_add_unit_offset:
            w = 1.0 + w.astype(jnp.float32)
        return w

    return lambda name: (weight(name), cfg.rms_norm_eps) if fused else None


def _rotary_mixer(cfg, rc, h, lp, segment_ids, cos, sin, attend_fn, bd=None):
    """Rotary GQA attention on the normed input ``h``, as ``rc`` (a
    ``RotaryConfig``) describes this kind: its own query heads, rotation
    (``cos``/``sin`` of the tokens' positions, made once a kind in
    ``apply``) and window, each head's output gated where it says so; q and
    k normed a head before the rotation where ``cfg.qk_norm`` is "head". A
    window layer's core is scope ``attn_window``, not ``attention``; the
    norms, the rotation and the gate are outside both, as the projections
    are. ``bd``: a block-diffusion policy's rows, (blocks, streams) of the
    slots; the core then runs under their mask (scope ``attn_bd``, not
    ``attention``; ``ops/bd_attention.py``). ``rc.nope``: no rotation (``cos``
    and ``sin`` None), the same mixer without the turn."""
    b, t, _ = h.shape
    H, dh = rc.num_heads, cfg.head_dim
    uo = cfg.norm_add_unit_offset
    in_rotation = _head_norm_in_rotation(cfg, lp, t)
    out_gate = None

    def rotated(w, heads, norm):
        nonlocal out_gate
        with device_scope("qkv_proj"):
            x = h @ lp[w]
            if rc.output_gate and w == "wq":  # a head: its query, its gate
                x, out_gate = jnp.split(
                    x.reshape(b, t, heads, 2 * dh), 2, axis=-1
                )
            x = x.reshape(b, t, heads, dh)
            if cfg.qk_norm == "head" and in_rotation(norm) is None:
                x = rms_norm(
                    x, lp[norm], cfg.rms_norm_eps, add_unit_offset=uo
                )
        if rc.nope:
            return x
        with device_scope("rope"):
            return rotate_leading(x, cos, sin, in_rotation(norm))

    q = rotated("wq", H, "q_norm")
    k = rotated("wk", cfg.num_kv_heads, "k_norm")
    with device_scope("qkv_proj"):
        v = (h @ lp["wv"]).reshape(b, t, cfg.num_kv_heads, dh)
    if bd is not None:
        with device_scope("attn_bd"):
            attn = (attend_fn or bd_ops.bd_segment_attention)(
                q, k, v, segment_ids, *bd
            )
    else:
        with device_scope("attn_window" if rc.window else "attention"):
            if attend_fn is None:
                attn = segment_attention(
                    q, k, v, segment_ids, causal=True, window=rc.window
                )
            else:  # only a window layer hands a window in
                own = {"layer_window": rc.window} if rc.window else {}
                attn = attend_fn(q, k, v, segment_ids, **own)
    attn = _ad_checkpoint.checkpoint_name(attn, "attn_out")
    if rc.head_gate:
        with device_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                (h @ lp["w_head_gate"]).astype(jnp.float32)
            )
            attn = (
                attn.astype(jnp.float32) * gate[..., None]
            ).astype(h.dtype)
    if out_gate is not None:  # element by element, from the query's product
        with device_scope("attn_gate"):
            attn = (
                attn.astype(jnp.float32)
                * jax.nn.sigmoid(out_gate.astype(jnp.float32))
            ).astype(h.dtype)
    with device_scope("o_proj"):
        return attn.reshape(b, t, H * dh) @ lp["wo"]


def _previous_token(x, segment_ids):
    """``x`` [B, T, ...] one token late: position t holds x_{t-1} where
    token t - 1 is of token t's sequence, zeros at a sequence's first token
    and at padding (``_short_conv``'s border rule)."""
    back = jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]
    seg_back = jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :-1]
    same = (seg_back == segment_ids) & (segment_ids > 0)
    return jnp.where(same.reshape(same.shape + (1,) * (x.ndim - 2)), back, 0)


def _head_conv(x, w, segment_ids):
    """Causal convolution over the tokens of ONE sequence whose taps are
    matrices, a group of channels a head: y_t[head] = sum_s x_{t-s}[head] @
    w[head, K-1-s], zeros before a sequence's first token. ``x`` [B, T,
    heads, d] float32; ``w`` [heads, K, d, d]. One batched product over the
    heads a tap, operands in ``w``'s dtype (the compute dtype: the MXU's),
    float32 accumulation."""
    taps = w.shape[1]
    y = 0.0
    for s in range(taps):
        y = y + jnp.einsum(
            "bthi,hio->btho", x.astype(w.dtype), w[:, taps - 1 - s],
            preferred_element_type=jnp.float32,
        )
        if s + 1 < taps:
            x = _previous_token(x, segment_ids)
    return y


def cca_qkv(cfg, h, lp, segment_ids, cos, sin):
    """What a ``cca`` mixer hands its attention core, from the layer's
    normed input ``h`` [B, T, D]: the rotated q [B, T, H, d] and k, and v
    [B, T, 2, d]. Between the projections and the rotation (scope
    ``cca_mix``, float32): the mean of each query head and its key head,
    added back after the convolutions; over concat(q, k) a depthwise causal
    convolution, then one whose taps are a matrix a head, each with a bias
    and zeros before a sequence's first token; q and k normed to length
    sqrt(d) a head, k times its key head's ``exp(cca_temp)``; the second
    value head one token late."""
    b, t, _ = h.shape
    cc, dh = cfg.cca, cfg.head_dim
    H, G = cc.num_heads, cc.num_kv_heads
    with device_scope("qkv_proj"):
        q0, k0 = h @ lp["wq"], h @ lp["wk"]
        v_own, v_prev = h @ lp["wv_own"], h @ lp["wv_prev"]
    with device_scope("cca_mix"):
        f32 = jnp.float32
        qh = q0.astype(f32).reshape(b, t, G, H // G, dh)
        kh = k0.astype(f32).reshape(b, t, G, 1, dh)
        mean_q = (qh + kh) * 0.5
        mean_k = mean_q.mean(axis=3)
        c = jnp.concatenate([q0, k0], axis=-1)
        c = _short_conv(c, lp["cca_conv0"], segment_ids) + (
            lp["cca_conv0_bias"].astype(f32)
        )
        c = _head_conv(
            c.reshape(b, t, H + G, dh), lp["cca_conv1"], segment_ids
        ) + lp["cca_conv1_bias"].astype(f32).reshape(H + G, dh)

        def unit(y):  # length sqrt(d): an RMS norm without a weight
            return y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps
            )

        q = unit(c[:, :, :H] + mean_q.reshape(b, t, H, dh))
        k = unit(c[:, :, H:] + mean_k) * jnp.exp(
            lp["cca_temp"].astype(f32)
        )[:, None]
        q, k = q.astype(h.dtype), k.astype(h.dtype)
        v = jnp.stack(
            [v_own, _previous_token(v_prev, segment_ids)], axis=2
        )
    with device_scope("rope"):
        return rotate_leading(q, cos, sin), rotate_leading(k, cos, sin), v


def _cca_mixer(cfg, h, lp, segment_ids, cos, sin, attend_fn):
    """Compressed convolutional attention on the normed input ``h``: GQA in
    a latent narrower than the residual stream (``cca_qkv``), the causal
    softmax core over a sequence's own tokens, and the way back out."""
    b, t, _ = h.shape
    q, k, v = cca_qkv(cfg, h, lp, segment_ids, cos, sin)
    with device_scope("attention"):
        if attend_fn is None:
            attn = segment_attention(q, k, v, segment_ids, causal=True)
        else:
            attn = attend_fn(q, k, v, segment_ids)
    attn = _ad_checkpoint.checkpoint_name(attn, "attn_out")
    with device_scope("o_proj"):
        return attn.reshape(b, t, -1) @ lp["wo"]


def _scaled_residual(x, y, lp, sub):
    """A ``cca`` block's sublayer ``sub`` onto the stream: (x + b_res) a_res
    + (y + b_out) a_out, in float32."""
    f32 = jnp.float32
    return (
        (x.astype(f32) + lp[f"{sub}_res_bias"].astype(f32))
        * lp[f"{sub}_res_scale"].astype(f32)
        + (y.astype(f32) + lp[f"{sub}_out_bias"].astype(f32))
        * lp[f"{sub}_out_scale"].astype(f32)
    ).astype(x.dtype)


def _cca_block(cfg, carry, lp, segment_ids, rotation, attend_fn, moe_fn):
    """One ``cca+moe`` layer. ``carry``: ``(x, r)``, the stream and the
    router's state that passes from layer to layer (``r`` [B, T, R]
    float32, zeros into the first layer)."""
    x, r_prev = carry
    with device_scope("stream_norm"):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    y = _cca_mixer(cfg, h, lp, segment_ids, *rotation, attend_fn)
    with device_scope("residual"):
        x = _scaled_residual(x, y, lp, "attn")
    with device_scope("stream_norm"):
        h = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
    with device_scope("mlp"):
        logits, r = router_mlp(lp, h, cfg.rms_norm_eps, r_prev)
        valid = segment_ids > 0  # padding tokens are no expert's load
        if moe_fn is None:
            ffn, stats = moe_ffn_from_params(
                cfg, lp, h, valid=valid, router_logits=logits
            )
        else:
            ffn, stats = moe_fn(lp, h, valid, logits)
    with device_scope("residual"):
        x = _scaled_residual(x, ffn, lp, "ffn")
    return (x, r), (stats, None)


def _block_body(
    cfg, kind, x, lp, segment_ids, attend_fn=None, moe_fn=None, kda_fn=None,
    rotation=None, bd=None, ssm_fn=None,
):
    """One layer of block ``kind`` (``ModelConfig.layer_kinds``).
    ``rotation``: a rotary kind's (cos, sin). ``x`` is the scan's carry: the
    stream, with the router's state beside it in a ``cca`` block whose
    router carries one."""
    mixer, ffn = kind.split("+")
    if mixer == "cca":
        return _cca_block(
            cfg, x, lp, segment_ids, rotation, attend_fn, moe_fn
        )
    with device_scope("stream_norm"):
        h = rms_norm(
            x, lp["input_norm"], cfg.rms_norm_eps,
            add_unit_offset=cfg.norm_add_unit_offset,
        )
    mixer_stats = None
    if mixer == "kda":
        y, mixer_stats = _kda_mixer(cfg, h, lp, segment_ids, kda_fn)
    elif mixer == "gdn":
        y, mixer_stats = _gdn_mixer(cfg, h, lp, segment_ids, kda_fn)
    elif mixer == "ssm":
        y, mixer_stats = _ssm_mixer(cfg, h, lp, segment_ids, ssm_fn)
    elif mixer in _ROTARY_MIXERS:
        y = _rotary_mixer(
            cfg, getattr(cfg, mixer), h, lp, segment_ids,
            *(rotation or (None, None)), attend_fn, bd,
        )
    else:
        y = _mla_mixer(cfg, h, lp, segment_ids, attend_fn, rotation)
    with device_scope("residual"):
        x = x + y
    return _ffn_residual(
        cfg, x, lp, segment_ids, moe_fn, ffn == "moe", mixer_stats
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _split_stack(a, sizes):
    """A kind's stacked leaf [L, ...] as the pieces its runs scan over. The
    transpose of a slice is a zero-padded array of the WHOLE leaf's size, a
    run each, and their sum; the pieces' cotangents are just the leaf's,
    side by side."""
    out, at = [], 0
    for n in sizes:
        out.append(a[at:at + n])
        at += n
    return tuple(out)


_split_stack.defvjp(
    lambda a, sizes: (_split_stack(a, sizes), None),
    lambda sizes, _, cts: (jnp.concatenate(cts, axis=0),),
)


def _runs(kinds):
    """[(kind, length)] of the consecutive runs of one block kind."""
    out = []
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def _rotations(cfg, positions):
    """{rotary mixer: (cos, sin) of the tokens' ``positions``}: each rotary
    kind's own frequencies (``RotaryConfig``), made once a kind."""
    out = {}
    for mixer in _ROTARY_MIXERS:
        rc = getattr(cfg, mixer)
        if rc is not None and not rc.nope:
            out[mixer] = rope_at(
                positions,
                rope_inv_freq(
                    rc.rotary_dim or cfg.head_dim, rc.rope_theta,
                    rc.yarn_factor, rc.yarn_original_len, rc.yarn_beta_fast,
                    rc.yarn_beta_slow,
                ),
                rc.attention_factor,
            )
    if cfg.cca is not None:
        out["cca"] = rope_at(positions, rope_inv_freq(
            cfg.cca.rotary_dim or cfg.head_dim, cfg.cca.rope_theta
        ))
    if cfg.mla is not None and cfg.mla.rotated:
        out["mla"] = rope_at(positions, rope_inv_freq(
            cfg.mla.qk_rope_head_dim, cfg.mla.rope_theta
        ))
    return out


def _apply_kinds(
    cfg, params, x, segment_ids, positions, remat, attend_fn, moe_fn, kda_fn,
    act_sharding, bd=None, ssm_fn=None,
):
    """The stack of a model of several block kinds: every run of consecutive
    layers of one kind is a scan over that kind's slice of its stack.
    Returns (x, ``LayerStats`` of the whole stack)."""
    runs = _runs(cfg.layer_kinds)
    with device_scope("rope"):
        rotations = _rotations(cfg, positions)
    # ``layer_stack``: what the loop over the layers does itself (a run's
    # slice of a kind's stack and its transpose, a layer's weights out of
    # the stack, what is saved for the backward, a layer's gradient into
    # the stacked one). Everything a layer computes has a scope of its own
    # inside, and the innermost scope names an operation
    with device_scope("layer_stack"):
        pieces = {
            kind: iter(zip(*(
                _split_stack(a, tuple(n for k, n in runs if k == kind))
                for a in jax.tree_util.tree_leaves(stack)
            )))
            for kind, stack in params["layers"].items()
        }
    reported = {}
    bd_rows = None
    if bd is not None:  # (streams, scored) -> what a core takes, what counts
        reported["block_diffusion"] = bd
        bd_rows = (
            positions.astype(jnp.int32) // cfg.block_diffusion.block_length,
            bd[0],
        )
    if cfg.router is not None:
        # the router's state beside the stream, zeros into the first layer
        x = (x, jnp.zeros(
            x.shape[:2] + (cfg.router.hidden_size,), jnp.float32
        ))
    for kind, n in runs:
        stack = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params["layers"][kind]),
            next(pieces[kind]),
        )

        def body(carry, lp, kind=kind):
            out, aux = _block_body(
                cfg, kind, carry, lp, segment_ids, attend_fn, moe_fn, kda_fn,
                rotations.get(kind.split("+")[0]), bd_rows, ssm_fn,
            )
            if act_sharding is not None:  # the carried state's too
                out = jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, act_sharding
                    ), out,
                )
            return out, aux

        if remat:
            # as the one-kind stack: only mixer outputs are kept. A run of
            # one layer is no loop: there XLA would merge the recomputed
            # forward with the first one (and keep every activation), which
            # a loop's boundary prevents and prevent_cse must
            body = jax.checkpoint(
                body, prevent_cse=n == 1,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_out"
                ),
            )
        with device_scope("layer_stack"):
            if n == 1:
                x, aux = body(
                    x, jax.tree_util.tree_map(lambda a: a[0], stack)
                )
                aux = jax.tree_util.tree_map(lambda a: a[None], aux)
            else:
                x, aux = jax.lax.scan(body, x, stack)
        # a kind's halves in the order a layer reports: FFN, mixer
        for name, stats in zip(reversed(kind.split("+")), aux):
            if stats is not None:
                reported.setdefault(name, []).append(stats)
    if isinstance(x, tuple):  # the router's last state goes nowhere
        x = x[0]
    return x, _stack_stats(cfg, reported, segment_ids, positions)


def apply(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32
    segment_ids: jnp.ndarray,  # [B, T] int32; 0 = padding
    positions: jnp.ndarray,  # [B, T] int32 (or [B, T, 3] mrope)
    remat: bool = True,
    attend_fn: Optional[Any] = None,
    moe_fn: Optional[Any] = None,
    return_stats: bool = False,
    kda_fn: Optional[Any] = None,
    mm_embeds: Optional[jnp.ndarray] = None,  # [B, N, D] vision embeds
    mm_index: Optional[jnp.ndarray] = None,  # [B, T] int32; -1 = text
    return_hidden: bool = False,  # lazy ChunkedLogits instead of [B,T,V]
    act_sharding: Optional[Any] = None,  # NamedSharding for [B, T, D] acts
    # a block-diffusion policy's rows: (streams, scored), [B, T] each
    bd: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    ssm_fn: Optional[Any] = None,
):
    """Forward to logits [B, T, vocab] (fp32); with ``return_stats=True``
    returns (logits, ``LayerStats`` of the whole stack: the load-balancing
    loss, and what the model's mechanisms count on these rows).

    For a model with an indexer ``attend_fn`` takes the indexer's query,
    key and head weights after ``segment_ids`` and returns ``DSAStats``
    beside the output (``ops/sparse_attention.make_attend``).

    `moe_fn(lp, h, valid)` overrides the sparse layer's call the way
    `attend_fn` overrides attention: the engine's per-device ``shard_map``
    of ``moe_ffn_from_params`` under a mesh. `kda_fn(q, k, v, g, beta,
    segment_ids)` does the same for the gated delta rule
    (``ops/kda.make_kda_scan``), `ssm_fn(x, dt, a, bm, cm, segment_ids)` for
    the selective scan (``ops/ssm_scan.make_ssm_scan``). Window-attention layers call ``attend_fn``
    with ``layer_window=`` (``ops.flash.flash_segment_attention`` takes it;
    the XLA form when ``attend_fn`` is None).

    `attend_fn(q, k, v, segment_ids)` overrides the attention kernel (e.g.
    ring / Ulysses shard_map from ops/ring_attention.py); default is the
    XLA segment-masked kernel with GSPMD-propagated sharding.

    ``mm_embeds``/``mm_index`` splice vision embeds into the token stream:
    position t takes mm_embeds[b, mm_index[b, t]] when mm_index >= 0
    (image-pad tokens), else its text embedding — differentiable through
    the vision tower (reference: HF VLM inputs_embeds masked-scatter).

    ``bd``: the rows of a model that generates by diffusion over blocks
    (``ModelConfig.block_diffusion``) hold each sequence's clean stream and
    its masked copies (``utils/data.pack_batch_rows(bd=)``): a slot's stream
    (0 clean, 1..K) and whether its token is scored there. Such a model runs
    no other rows: without them it would attend causally to inputs it was
    never trained on, and ``apply`` refuses.

    ``act_sharding`` pins the [B, T, D] activation layout (rows over
    (data, fsdp), tokens over seq). Without the constraint GSPMD is free
    to propagate the embedding table's column sharding onto the batch —
    replicating activations across the fsdp axis (measured: a 7B/16-dev
    AOT lowering allocated 81 GB of per-device layer temps).
    """
    if (cfg.block_diffusion is None) != (bd is None):
        raise ValueError(
            "a model that generates by diffusion over blocks runs rows that "
            "hold a clean stream and its masked copies, and no other model "
            "does: the batch must carry a per-token denoise_state "
            f"(family {cfg.family!r}, bd rows "
            f"{'given' if bd is not None else 'missing'})"
        )
    with device_scope("rope"):  # the tables, made once a program
        cos, sin = rope_frequencies(
            cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta
        )
    with device_scope("embed"):
        x = params["embedding"][tokens]
        if cfg.scale_embeddings:  # gemma: sqrt(d)-scaled embeddings
            x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    if mm_embeds is not None and mm_index is not None:
        gathered = jnp.take_along_axis(
            mm_embeds,
            jnp.clip(mm_index, 0)[..., None].astype(jnp.int32),
            axis=1,
        ).astype(x.dtype)
        x = jnp.where(mm_index[..., None] >= 0, gathered, x)

    if act_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, act_sharding)

    if cfg.layer_kinds:
        x, stats = _apply_kinds(
            cfg, params, x, segment_ids, positions, remat, attend_fn, moe_fn,
            kda_fn, act_sharding, bd, ssm_fn,
        )
        logits = _head(params, cfg, x, return_hidden)
        return (logits, stats) if return_stats else logits

    def body(carry, lp):
        out, aux = _layer_body(
            cfg, carry, lp, segment_ids, positions, cos, sin, attend_fn,
            moe_fn,
        )
        if act_sharding is not None:
            out = jax.lax.with_sharding_constraint(out, act_sharding)
        return out, aux

    if remat:
        # each layer's attention output is kept across the forward->backward
        # boundary (no recompute of the attention forward, [B,T,Hq,D] of
        # storage a layer); everything else remats. A sparse-attention
        # layer also keeps what pins its selection ([T] thresholds), so the
        # recomputed forward and the backward read the forward's keys
        # without selecting again
        saved = ("attn_out",) + (("dsa_tau",) if cfg.has_indexer else ())
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(*saved),
        )
    with device_scope("layer_stack"):  # as ``_apply_kinds`` says
        x, aux = jax.lax.scan(body, x, params["layers"])
    logits = _head(params, cfg, x, return_hidden)
    if return_stats:
        pairs = zip(("moe", "indexer"), aux)  # as a layer reports them
        reported = {n: [s] for n, s in pairs if s is not None}
        return logits, _stack_stats(cfg, reported, segment_ids, positions)
    return logits


def _lm_head(params, cfg):
    """(the output head's weight, which of its axes is the vocabulary): a
    tied model's [V, D] table as it is stored, else ``lm_head`` [D, V]."""
    if cfg.tie_word_embeddings:
        return params["embedding"], 0
    return params["lm_head"], 1


def _head(params, cfg, x, return_hidden):
    """The final norm and the output head over the stack's output: logits
    (or their lazy view)."""
    with device_scope("stream_norm"):
        x = rms_norm(
            x, params["final_norm"], cfg.rms_norm_eps,
            add_unit_offset=cfg.norm_add_unit_offset,
        )
    if "value_head" in params:
        # critic: scalar head — "logits" [B, T, 1] (value per position);
        # tiny, never worth the lazy view
        head, vocab_axis = params["value_head"], 1
    else:
        head, vocab_axis = _lm_head(params, cfg)
    if return_hidden and "value_head" not in params:
        from areal_tpu.ops.chunked_head import ChunkedLogits

        logits = ChunkedLogits(x, head, vocab_axis)
    else:
        if vocab_axis == 0:
            head = head.T
        with device_scope("lm_head"):
            logits = (x.astype(jnp.float32)) @ head.astype(jnp.float32)
    return logits


def draft_forward(
    params: Params,
    cfg: ModelConfig,
    hidden: jnp.ndarray,  # [B, T, D]: the stack's output after the final norm
    tokens: jnp.ndarray,
    segment_ids: jnp.ndarray,
    positions: jnp.ndarray,
    remat: bool = True,
    attend_fn: Optional[Any] = None,
    moe_fn: Optional[Any] = None,
    kda_fn: Optional[Any] = None,
    act_sharding: Optional[Any] = None,
):
    """The draft module beside the stack (``ModelConfig.mtp_layers``: the
    DeepSeek-V3 report's multi-token prediction, section 2.2, at depth 1) on
    the rows ``apply`` ran: (the lazy logits of slot i for token i + 2, what
    its block's experts count on these rows, ``moe_*`` as the stack's).

    Slot i of a sequence reads ``[norm_e(Emb(t_{i+1})) ; norm_h(hidden_i)]
    @ w_eh``; a sequence's last slot has no next token INSIDE the row's
    sequence and reads a zero embedding, never the next sequence's first
    token. One block of the stack's last kind (``params["mtp"]["block"]``)
    follows, at the same positions under the same mask, then ``head_norm``
    and the TRUNK's head. ``hidden``, the embedding and the head enter
    under ``stop_gradient``: the module follows the policy and hands the
    trunk no gradient (``ops/functional.draft_loss`` has the targets)."""
    from areal_tpu.ops.chunked_head import ChunkedLogits

    mp, uo, eps = params["mtp"], cfg.norm_add_unit_offset, cfg.rms_norm_eps
    frozen = jax.lax.stop_gradient
    with device_scope("mtp"):
        late = ((0, 0), (0, 1))
        has_next = (
            jnp.pad(segment_ids[:, 1:], late) == segment_ids
        ) & (segment_ids > 0)
        e = frozen(params["embedding"])[jnp.pad(tokens[:, 1:], late)]
        if cfg.scale_embeddings:
            e = e * jnp.asarray(cfg.hidden_size**0.5, e.dtype)
        e = jnp.where(has_next[..., None], e, 0)
        u = jnp.concatenate([
            rms_norm(e, mp["norm_e"], eps, add_unit_offset=uo),
            rms_norm(frozen(hidden), mp["norm_h"], eps, add_unit_offset=uo),
        ], axis=-1) @ mp["w_eh"]
    if act_sharding is not None:
        u = jax.lax.with_sharding_constraint(u, act_sharding)
    kind = draft_kind(cfg)
    with device_scope("rope"):
        rotation = _rotations(cfg, positions).get(kind.split("+")[0])

    def body(x, lp):
        return _block_body(
            cfg, kind, x, lp, segment_ids, attend_fn, moe_fn, kda_fn,
            rotation,
        )

    if remat:  # a run of one layer, as ``_apply_kinds`` checkpoints one
        body = jax.checkpoint(
            body, prevent_cse=True,
            policy=jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    with device_scope("layer_stack"):
        y, (moe_stats, _) = body(
            u, jax.tree_util.tree_map(lambda a: a[0], mp["block"])
        )
    with device_scope("mtp"):
        y = rms_norm(y, mp["head_norm"], eps, add_unit_offset=uo)
    counts = {}
    if moe_stats is not None:
        reported = {
            "moe": [jax.tree_util.tree_map(lambda a: a[None], moe_stats)]
        }
        held = moe_stats.stream_rows is not None
        for name in ("moe", "moe_held")[:1 + held]:
            m = MECHANISMS[name]
            counts.update(zip(
                m.counts, m.count(cfg, reported, segment_ids, positions),
                strict=True,
            ))
    head, vocab_axis = _lm_head(params, cfg)
    return ChunkedLogits(y, frozen(head), vocab_axis), counts


def count_params(params: Params) -> int:
    return int(
        sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    )
