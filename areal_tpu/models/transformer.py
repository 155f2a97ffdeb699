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
"""

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint as _ad_checkpoint

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.moe import MoEStats
from areal_tpu.ops.sparse_attention import DSAStats, sparse_attention
from areal_tpu.utils.tracing import device_scope
from areal_tpu.ops.basic import (
    apply_mrope,
    apply_rope,
    hidden_act_fn,
    rms_norm,
    rope_frequencies,
    segment_attention,
)

Params = Dict[str, Any]


class LayerStats(NamedTuple):
    """What a layer reports beside its output; ``apply`` hands back the
    stack's. ``dsa`` is None (no leaf at all) for a model without an
    indexer, which therefore lowers as it did before the field existed."""

    moe: MoEStats  # zeros for a dense model
    dsa: Optional[DSAStats] = None  # sparse-attention models only


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
    return params


def param_logical_axes(cfg: ModelConfig, value_head: bool = False) -> Params:
    """Same-structure tree of logical axis name tuples.

    Logical names: "vocab" (vocab-parallel), "embed" (fsdp-sharded model
    dim), "heads" (tensor-parallel attention dim), "mlp" (tensor-parallel
    ffn dim), "layer" (scanned, never sharded), None (replicated).
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
        layers["w_gate"] = ("layer", "expert", "embed", "mlp")
        layers["w_up"] = ("layer", "expert", "embed", "mlp")
        layers["w_down"] = ("layer", "expert", "mlp", "embed")
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
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps, add_unit_offset=uo)
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
    if cfg.qk_norm == "head":  # qwen3: each head by itself
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if positions.ndim == 3:  # [B, T, 3] multimodal (t, h, w) positions
        q = apply_mrope(q, positions, cos, sin, cfg.mrope_sections)
        k = apply_mrope(k, positions, cos, sin, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
    # device scopes (utils/tracing.DEVICE_SCOPES): names only — what a
    # profile's per-op shares are read by; no number and no fusion changes
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
    x = x + attn.reshape(b, t, cfg.q_dim) @ lp["wo"]
    h = rms_norm(
        x, lp["post_attn_norm"], cfg.rms_norm_eps, add_unit_offset=uo
    )
    if cfg.is_moe:
        from areal_tpu.ops.moe import (
            moe_ffn_from_params,
            shared_expert_from_params,
        )

        # padding tokens (segment 0) are no expert's load
        with device_scope("mlp"):
            if moe_fn is None:
                ffn, stats = moe_ffn_from_params(
                    cfg, lp, h, valid=segment_ids > 0
                )
            else:  # per device under a mesh (a shard_map of the same)
                ffn, stats = moe_fn(lp, h, segment_ids > 0)
            if cfg.shared_expert_size:
                ffn = ffn + shared_expert_from_params(cfg, lp, h)
        return x + ffn, LayerStats(stats, dsa)
    with device_scope("mlp"):
        ffn = (
            hidden_act_fn(cfg.hidden_act)(h @ lp["w_gate"])
            * (h @ lp["w_up"])
        ) @ lp["w_down"]
    return x + ffn, LayerStats(
        MoEStats(*(jnp.zeros((), jnp.float32),) * 3), dsa
    )


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
    mm_embeds: Optional[jnp.ndarray] = None,  # [B, N, D] vision embeds
    mm_index: Optional[jnp.ndarray] = None,  # [B, T] int32; -1 = text
    return_hidden: bool = False,  # lazy ChunkedLogits instead of [B,T,V]
    act_sharding: Optional[Any] = None,  # NamedSharding for [B, T, D] acts
):
    """Forward to logits [B, T, vocab] (fp32); with ``return_stats=True``
    returns (logits, ``LayerStats`` of the whole stack): ``moe`` the mean
    per-layer load-balancing loss, and the assignments and the busiest
    experts' loads summed over the layers (zeros for dense models); ``dsa``,
    for a model with an indexer, the selected and the causal (query, key)
    pairs of each layer, int32 [L] (exact: the host sums them).

    For a model with an indexer ``attend_fn`` takes the indexer's query,
    key and head weights after ``segment_ids`` and returns ``DSAStats``
    beside the output (``ops/sparse_attention.make_attend``).

    `moe_fn(lp, h, valid)` overrides the sparse layer's call the way
    `attend_fn` overrides attention: the engine's per-device ``shard_map``
    of ``moe_ffn_from_params`` under a mesh.

    `attend_fn(q, k, v, segment_ids)` overrides the attention kernel (e.g.
    ring / Ulysses shard_map from ops/ring_attention.py); default is the
    XLA segment-masked kernel with GSPMD-propagated sharding.

    ``mm_embeds``/``mm_index`` splice vision embeds into the token stream:
    position t takes mm_embeds[b, mm_index[b, t]] when mm_index >= 0
    (image-pad tokens), else its text embedding — differentiable through
    the vision tower (reference: HF VLM inputs_embeds masked-scatter).

    ``act_sharding`` pins the [B, T, D] activation layout (rows over
    (data, fsdp), tokens over seq). Without the constraint GSPMD is free
    to propagate the embedding table's column sharding onto the batch —
    replicating activations across the fsdp axis (measured: a 7B/16-dev
    AOT lowering allocated 81 GB of per-device layer temps).
    """
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
    x, aux = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(
        x, params["final_norm"], cfg.rms_norm_eps,
        add_unit_offset=cfg.norm_add_unit_offset,
    )
    if "value_head" in params:
        # critic: scalar head — "logits" [B, T, 1] (value per position);
        # tiny, never worth the lazy view
        head, vocab_axis = params["value_head"], 1
    elif cfg.tie_word_embeddings:  # the [V, D] table as it is stored
        head, vocab_axis = params["embedding"], 0
    else:
        head, vocab_axis = params["lm_head"], 1
    if return_hidden and "value_head" not in params:
        from areal_tpu.ops.chunked_head import ChunkedLogits

        logits = ChunkedLogits(x, head, vocab_axis)
    else:
        if vocab_axis == 0:
            head = head.T
        with device_scope("lm_head"):
            logits = (x.astype(jnp.float32)) @ head.astype(jnp.float32)
    if return_stats:
        moe = aux.moe
        return logits, LayerStats(
            MoEStats(
                aux_loss=jnp.mean(moe.aux_loss),
                assignments=jnp.sum(moe.assignments),
                max_expert_assignments=jnp.sum(moe.max_expert_assignments),
            ),
            aux.dsa,
        )
    return logits


def count_params(params: Params) -> int:
    return int(
        sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    )
