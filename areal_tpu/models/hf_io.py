"""HF checkpoint ↔ param-pytree conversion.

Role of reference realhf/api/from_hf/ (per-family `from_/to_{family}`
converters) and areal's HF save/load (fsdp_engine.py save/load): the
framework speaks HF safetensors on disk so checkpoints interoperate with the
rest of the ecosystem (tokenizers, eval harnesses, serving).

Torch linear weights are [out, in]; our kernels keep [in, out] so the matmul
is `x @ W` with no transpose at run time.
"""

import json
import os
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np
from safetensors import safe_open
from safetensors.numpy import save_file

from areal_tpu.models.config import ModelConfig, load_hf_config
from areal_tpu.models.transformer import Params

_LAYER_MAP = {
    # our key -> (hf suffix, transpose?)
    "input_norm": ("input_layernorm.weight", False),
    "post_attn_norm": ("post_attention_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "bq": ("self_attn.q_proj.bias", False),
    "bk": ("self_attn.k_proj.bias", False),
    "bv": ("self_attn.v_proj.bias", False),
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
}

# Vision tower (qwen2_vl): per-block weights stack along a leading depth
# axis; the HF fused qkv Linear stays fused in our pytree.
_VISION_BLOCK_MAP = {
    # our key -> (hf suffix under visual.blocks.{i}., transpose?)
    "norm1_w": ("norm1.weight", False),
    "norm1_b": ("norm1.bias", False),
    "norm2_w": ("norm2.weight", False),
    "norm2_b": ("norm2.bias", False),
    "wqkv": ("attn.qkv.weight", True),
    "bqkv": ("attn.qkv.bias", False),
    "wo": ("attn.proj.weight", True),
    "bo": ("attn.proj.bias", False),
    "w_fc1": ("mlp.fc1.weight", True),
    "b_fc1": ("mlp.fc1.bias", False),
    "w_fc2": ("mlp.fc2.weight", True),
    "b_fc2": ("mlp.fc2.bias", False),
}
_VISION_TOP_MAP = {
    # our key -> (hf name under visual., transpose?)
    "ln_q_w": ("merger.ln_q.weight", False),
    "ln_q_b": ("merger.ln_q.bias", False),
    "w_merge1": ("merger.mlp.0.weight", True),
    "b_merge1": ("merger.mlp.0.bias", False),
    "w_merge2": ("merger.mlp.2.weight", True),
    "b_merge2": ("merger.mlp.2.bias", False),
}

# MoE families: per-expert FFN weights stack along a leading expert axis.
# our key -> (hf suffix template with {e}, transpose?)
_MOE_MAPS = {
    "qwen3_moe": {
        "w_router": ("mlp.gate.weight", True),
        "w_gate": ("mlp.experts.{e}.gate_proj.weight", True),
        "w_up": ("mlp.experts.{e}.up_proj.weight", True),
        "w_down": ("mlp.experts.{e}.down_proj.weight", True),
    },
    # olmoe names its router and experts as qwen3_moe does; its q_norm and
    # k_norm (in _LAYER_MAP) are [q_dim] and [kv_dim] wide, not [head_dim]
    "olmoe": {
        "w_router": ("mlp.gate.weight", True),
        "w_gate": ("mlp.experts.{e}.gate_proj.weight", True),
        "w_up": ("mlp.experts.{e}.up_proj.weight", True),
        "w_down": ("mlp.experts.{e}.down_proj.weight", True),
    },
    "qwen2_moe": {
        "w_router": ("mlp.gate.weight", True),
        "w_gate": ("mlp.experts.{e}.gate_proj.weight", True),
        "w_up": ("mlp.experts.{e}.up_proj.weight", True),
        "w_down": ("mlp.experts.{e}.down_proj.weight", True),
        "w_shared_gate": ("mlp.shared_expert.gate_proj.weight", True),
        "w_shared_up": ("mlp.shared_expert.up_proj.weight", True),
        "w_shared_down": ("mlp.shared_expert.down_proj.weight", True),
        "w_shared_router": ("mlp.shared_expert_gate.weight", True),
    },
    "mixtral": {
        "w_router": ("block_sparse_moe.gate.weight", True),
        "w_gate": ("block_sparse_moe.experts.{e}.w1.weight", True),
        "w_up": ("block_sparse_moe.experts.{e}.w3.weight", True),
        "w_down": ("block_sparse_moe.experts.{e}.w2.weight", True),
    },
}


# A stack of block kinds (kimi_linear): our key -> (hf suffix under
# model.layers.{i}., how it is stored). "T": a torch linear [out, in];
# "conv": a depthwise conv1d [C, 1, K]; "": as it is. A key's suffix may
# depend on the block kind (the dense FFN and the experts share our names).
_KIMI_LINEAR_MAP = {
    "input_norm": ("input_layernorm.weight", ""),
    "post_attn_norm": ("post_attention_layernorm.weight", ""),
    "wq": ("self_attn.q_proj.weight", "T"),
    "wk": ("self_attn.k_proj.weight", "T"),
    "wv": ("self_attn.v_proj.weight", "T"),
    "wo": ("self_attn.o_proj.weight", "T"),
    "conv_q": ("self_attn.q_conv1d.weight", "conv"),
    "conv_k": ("self_attn.k_conv1d.weight", "conv"),
    "conv_v": ("self_attn.v_conv1d.weight", "conv"),
    "w_f_a": ("self_attn.f_a_proj.weight", "T"),
    "w_f_b": ("self_attn.f_b_proj.weight", "T"),
    "a_log": ("self_attn.A_log", ""),
    "dt_bias": ("self_attn.dt_bias", ""),
    "w_beta": ("self_attn.b_proj.weight", "T"),
    "w_g_a": ("self_attn.g_a_proj.weight", "T"),
    "w_g_b": ("self_attn.g_b_proj.weight", "T"),
    "o_norm": ("self_attn.o_norm.weight", ""),
    "w_kv_a": ("self_attn.kv_a_proj_with_mqa.weight", "T"),
    "kv_norm": ("self_attn.kv_a_layernorm.weight", ""),
    "w_kv_b": ("self_attn.kv_b_proj.weight", "T"),
    "w_router": ("block_sparse_moe.gate.weight", "T"),
    "router_bias": ("block_sparse_moe.gate.e_score_correction_bias", ""),
    "w_shared_gate": ("block_sparse_moe.shared_experts.gate_proj.weight", "T"),
    "w_shared_up": ("block_sparse_moe.shared_experts.up_proj.weight", "T"),
    "w_shared_down": ("block_sparse_moe.shared_experts.down_proj.weight", "T"),
}
_KIMI_LINEAR_FFN = {  # (dense suffix, expert suffix with {e})
    "w_gate": ("mlp.gate_proj.weight", "block_sparse_moe.experts.{e}.w1.weight"),
    "w_up": ("mlp.up_proj.weight", "block_sparse_moe.experts.{e}.w3.weight"),
    "w_down": ("mlp.down_proj.weight", "block_sparse_moe.experts.{e}.w2.weight"),
}


def _kind_tensor_names(cfg: ModelConfig):
    """For a stack of block kinds: (kind, our key, index in the kind's
    stack, expert or None, hf name, how stored) of every layer tensor."""
    from areal_tpu.models.transformer import _kind_leaves

    if cfg.family in ("laguna", "zaya", "mellum", "sdar_moe"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no checkpoint layout here: its "
            "tensor names are in no public source this repo was written "
            "from (the catalog gives the config.json only); initialise from "
            "scratch"
        )
    if cfg.family == "jamba":
        raise NotImplementedError(
            "family 'jamba' has no checkpoint layout here: the published "
            "tensors' names (mamba.in_proj, mamba.x_proj, mamba.dt_layernorm, "
            "feed_forward ...) are in no source this repo was written from "
            "(the catalog gives the config.json only) and no weights are in "
            "the repository to hold a mapping to (ROADMAP B.15); initialise "
            "from scratch"
        )
    if cfg.family == "qwen3_next":
        raise NotImplementedError(
            "family 'qwen3_next' has no checkpoint layout here yet: the "
            "published tensors interleave q, k, v and z a key head inside "
            "in_proj_qkvz (and b, a inside in_proj_ba) where this tree lays "
            "them side by side, and no weights are in the repository to "
            "hold a mapping to (ROADMAP B.15); initialise from scratch"
        )
    if cfg.family == "joyai_llm_flash":
        raise NotImplementedError(
            "family 'joyai_llm_flash' has no checkpoint layout here yet: "
            "the published rope columns of q_b_proj and kv_a_proj_with_mqa "
            "are interleaved (rope_interleave) where this tree turns them "
            "rotate-half, a permutation a loader owes, the draft module's "
            "tensors (the layer after the last) have no place among a "
            "stack's names, and no weights are in the repository to hold a "
            "mapping to (ROADMAP B.15); initialise from scratch"
        )
    if cfg.experts_held:
        raise NotImplementedError(
            f"family {cfg.family!r} holding {cfg.experts_held} of "
            f"{cfg.num_experts} experts has no checkpoint layout (a share "
            "of the experts is no file of its own): initialise from scratch"
        )
    seen: Dict[str, int] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        sparse = kind.endswith("+moe")
        for key in _kind_leaves(cfg, kind):
            prefix = f"model.layers.{i}."
            if key in _KIMI_LINEAR_FFN:
                dense, expert = _KIMI_LINEAR_FFN[key]
                if sparse:
                    for e in range(cfg.num_experts):
                        yield (kind, key, at, e,
                               prefix + expert.format(e=e), "T")
                else:
                    yield kind, key, at, None, prefix + dense, "T"
            else:
                suffix, how = _KIMI_LINEAR_MAP[key]
                yield kind, key, at, None, prefix + suffix, how


def _load_kinds(cfg: ModelConfig, g, dtype) -> Dict[str, Dict]:
    from areal_tpu.models.transformer import _kind_leaves, block_kinds

    groups = {
        kind: {
            key: np.zeros((cfg.kind_layers(kind),) + leaf[0], np.float32)
            for key, leaf in _kind_leaves(cfg, kind).items()
        }
        for kind in block_kinds(cfg)
    }
    for kind, key, at, e, name, how in _kind_tensor_names(cfg):
        w = np.asarray(g(name), np.float32)
        w = w.T if how == "T" else w[:, 0, :] if how == "conv" else w
        where = (at,) if e is None else (at, e)
        groups[kind][key][where] = w.reshape(groups[kind][key][where].shape)
    return {
        kind: {k: jnp.asarray(v, dtype=dtype) for k, v in group.items()}
        for kind, group in groups.items()
    }


def _open_shards(path: str) -> Dict[str, str]:
    """tensor name -> shard file path."""
    index_file = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_file):
        with open(index_file) as f:
            index = json.load(f)
        return {
            k: os.path.join(path, v) for k, v in index["weight_map"].items()
        }
    single = os.path.join(path, "model.safetensors")
    names = {}
    with safe_open(single, framework="numpy") as f:
        for k in f.keys():
            names[k] = single
    return names


class _ShardReader:
    def __init__(self, name_to_file: Dict[str, str]):
        self.name_to_file = name_to_file
        self._handles: Dict[str, object] = {}

    def __contains__(self, name: str) -> bool:
        return name in self.name_to_file

    def get(self, name: str) -> np.ndarray:
        file = self.name_to_file[name]
        if file not in self._handles:
            self._handles[file] = safe_open(file, framework="numpy")
        return self._handles[file].get_tensor(name)


def _refuse_indexer(cfg: ModelConfig):
    """A family with an indexer trains from seeded weights only: the names
    its checkpoints give the indexer's projections (and whether a share of
    the experts is a file of its own) are not known here."""
    if cfg.has_indexer:
        raise NotImplementedError(
            f"no checkpoint layout for family {cfg.family!r} (its indexer's "
            "weight names are unknown): initialise from scratch"
        )


def load_params(
    path: str, cfg: Optional[ModelConfig] = None, dtype=jnp.bfloat16
) -> Params:
    """Load an HF checkpoint directory into the stacked-layer pytree."""
    if cfg is None:
        cfg = load_hf_config(path)
    _refuse_indexer(cfg)
    reader = _ShardReader(_open_shards(path))

    def g(name: str) -> np.ndarray:
        arr = reader.get(name)
        if arr.dtype == np.dtype("V2"):  # raw bf16 from safetensors/numpy
            import ml_dtypes

            arr = arr.view(ml_dtypes.bfloat16)
        return arr

    layers: Dict[str, np.ndarray] = {}
    moe_map = _MOE_MAPS.get(cfg.family) if cfg.is_moe else None
    if cfg.layer_kinds:  # one stack a block kind, its own names
        layers, moe_map = _load_kinds(cfg, g, dtype), None
    for our_key, (suffix, transpose) in _LAYER_MAP.items():
        if cfg.layer_kinds:
            break
        if moe_map and our_key in moe_map:
            continue  # expert-shaped in MoE families (handled below)
        name0 = f"model.layers.0.{suffix}"
        if name0 not in reader:
            continue
        per_layer = []
        for i in range(cfg.num_layers):
            w = g(f"model.layers.{i}.{suffix}")
            per_layer.append(w.T if transpose else w)
        layers[our_key] = jnp.asarray(np.stack(per_layer), dtype=dtype)
    if moe_map:
        for our_key, (tmpl, transpose) in moe_map.items():
            per_layer = []
            for i in range(cfg.num_layers):
                if "{e}" in tmpl:
                    per_exp = []
                    for ei in range(cfg.num_experts):
                        w = g(
                            f"model.layers.{i}.{tmpl.format(e=ei)}"
                        )
                        per_exp.append(w.T if transpose else w)
                    per_layer.append(np.stack(per_exp))
                else:
                    w = g(f"model.layers.{i}.{tmpl}")
                    per_layer.append(w.T if transpose else w)
            layers[our_key] = jnp.asarray(np.stack(per_layer), dtype=dtype)
    params: Params = {
        "embedding": jnp.asarray(g("model.embed_tokens.weight"), dtype=dtype),
        "layers": layers,
        "final_norm": jnp.asarray(g("model.norm.weight"), dtype=dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(g("lm_head.weight").T, dtype=dtype)
    if cfg.vision is not None:
        vc = cfg.vision
        blocks: Dict[str, np.ndarray] = {}
        for our_key, (suffix, transpose) in _VISION_BLOCK_MAP.items():
            if f"visual.blocks.0.{suffix}" not in reader:
                continue
            per = [g(f"visual.blocks.{i}.{suffix}") for i in range(vc.depth)]
            blocks[our_key] = jnp.asarray(
                np.stack([w.T if transpose else w for w in per]), dtype=dtype
            )
        vis: Params = {"blocks": blocks}
        # conv3d patch embed [H, C, T, P, P] -> flattened linear [Dp, H]
        pw = g("visual.patch_embed.proj.weight")
        vis["patch_embed"] = jnp.asarray(
            pw.reshape(pw.shape[0], -1).T, dtype=dtype
        )
        for our_key, (name, transpose) in _VISION_TOP_MAP.items():
            if f"visual.{name}" not in reader:
                continue
            w = g(f"visual.{name}")
            vis[our_key] = jnp.asarray(w.T if transpose else w, dtype=dtype)
        params["vision"] = vis
    return params


def save_params(
    params: Params,
    cfg: ModelConfig,
    path: str,
    hf_config_dict: Optional[dict] = None,
) -> None:
    """Write the pytree back out as a single-file HF safetensors checkpoint
    (reference: fsdp_engine HF save path; used by disk weight updates)."""
    _refuse_indexer(cfg)
    os.makedirs(path, exist_ok=True)
    tensors: Dict[str, np.ndarray] = {}

    # store in fp32 for portability (loader re-casts); safetensors/numpy
    # cannot serialize ml_dtypes.bfloat16 directly
    def as_np32(x) -> np.ndarray:
        return np.asarray(x, dtype=np.float32)

    tensors["model.embed_tokens.weight"] = as_np32(params["embedding"])
    tensors["model.norm.weight"] = as_np32(params["final_norm"])
    if not cfg.tie_word_embeddings:
        tensors["lm_head.weight"] = as_np32(params["lm_head"]).T.copy()
    moe_map = _MOE_MAPS.get(cfg.family) if cfg.is_moe else None
    if cfg.layer_kinds:
        moe_map = None
        host = {
            kind: {k: as_np32(v) for k, v in group.items()}
            for kind, group in params["layers"].items()
        }
        for kind, key, at, e, name, how in _kind_tensor_names(cfg):
            w = host[kind][key][at] if e is None else host[kind][key][at, e]
            tensors[name] = (
                w.T if how == "T" else w[:, None, :] if how == "conv" else w
            ).copy()
    for our_key, (suffix, transpose) in _LAYER_MAP.items():
        if our_key not in params["layers"]:
            continue
        if moe_map and our_key in moe_map:
            continue
        stacked = as_np32(params["layers"][our_key])
        for i in range(cfg.num_layers):
            w = stacked[i]
            tensors[f"model.layers.{i}.{suffix}"] = (
                w.T.copy() if transpose else w.copy()
            )
    if moe_map:
        for our_key, (tmpl, transpose) in moe_map.items():
            if our_key not in params["layers"]:
                continue
            stacked = as_np32(params["layers"][our_key])
            for i in range(cfg.num_layers):
                if "{e}" in tmpl:
                    for ei in range(cfg.num_experts):
                        w = stacked[i, ei]
                        tensors[f"model.layers.{i}.{tmpl.format(e=ei)}"] = (
                            w.T.copy() if transpose else w.copy()
                        )
                else:
                    w = stacked[i]
                    tensors[f"model.layers.{i}.{tmpl}"] = (
                        w.T.copy() if transpose else w.copy()
                    )
    if cfg.vision is not None and "vision" in params:
        vc = cfg.vision
        vis = params["vision"]
        pw = as_np32(vis["patch_embed"]).T  # [H, Dp]
        tensors["visual.patch_embed.proj.weight"] = np.ascontiguousarray(
            pw.reshape(
                vc.hidden_size, vc.in_channels, vc.temporal_patch_size,
                vc.patch_size, vc.patch_size,
            )
        )
        for our_key, (suffix, transpose) in _VISION_BLOCK_MAP.items():
            if our_key not in vis["blocks"]:
                continue
            stacked = as_np32(vis["blocks"][our_key])
            for i in range(vc.depth):
                w = stacked[i]
                tensors[f"visual.blocks.{i}.{suffix}"] = (
                    w.T.copy() if transpose else w.copy()
                )
        for our_key, (name, transpose) in _VISION_TOP_MAP.items():
            if our_key not in vis:
                continue
            w = as_np32(vis[our_key])
            tensors[f"visual.{name}"] = w.T.copy() if transpose else w.copy()
    save_file(tensors, os.path.join(path, "model.safetensors"))
    if hf_config_dict is None:
        hf_config_dict = default_hf_config_dict(cfg)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config_dict, f, indent=2)


def _kimi_linear_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._kimi_linear_from_hf`` reads."""
    at = {m: [i + 1 for i, k in enumerate(cfg.layer_kinds)
              if k.startswith(m)] for m in ("kda", "mla")}
    return {
        "model_type": cfg.family,
        "architectures": ["KimiLinearForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "model_max_length": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "torch_dtype": "float32",
        "linear_attn_config": {
            "kda_layers": at["kda"], "full_attn_layers": at["mla"],
            "num_heads": cfg.kda.num_heads, "head_dim": cfg.kda.head_dim,
            "short_conv_kernel_size": cfg.kda.conv_size,
        },
        "kv_lora_rank": cfg.mla.kv_lora_rank,
        "q_lora_rank": cfg.mla.q_lora_rank or None,
        "qk_nope_head_dim": cfg.mla.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.mla.qk_rope_head_dim,
        "v_head_dim": cfg.mla.v_head_dim,
        "mla_use_nope": not cfg.mla.rotated,
        "first_k_dense_replace": sum(
            k.endswith("+dense") for k in cfg.layer_kinds
        ),
        "num_experts": cfg.num_experts,
        "num_local_experts": cfg.num_held_experts,
        "num_experts_per_token": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_shared_experts": cfg.shared_expert_size
        // max(cfg.moe_intermediate_size, 1),
        "moe_renormalize": cfg.norm_topk_prob,
        "moe_router_activation_func": cfg.router_act,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
    }


def _laguna_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._laguna_from_hf`` reads, and
    ``_mellum_from_hf`` (which reads fewer: no gate, no shared expert, one
    number of heads)."""
    mixers = {"full": "full_attention", "swa": "sliding_attention"}
    kinds = [k.split("+") for k in cfg.layer_kinds]

    def rope(rc):
        out = {
            "rope_type": "yarn" if rc.yarn_factor > 1.0 else "default",
            "rope_theta": rc.rope_theta,
            "partial_rotary_factor": (
                (rc.rotary_dim or cfg.head_dim) / cfg.head_dim
            ),
        }
        if rc.yarn_factor > 1.0:
            out.update({
                "factor": rc.yarn_factor,
                "original_max_position_embeddings": rc.yarn_original_len,
                "beta_fast": rc.yarn_beta_fast,
                "beta_slow": rc.yarn_beta_slow,
                "attention_factor": rc.attention_factor,
            })
        return out

    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "max_position_embeddings": cfg.max_position_embeddings,
        "attention_bias": False,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "torch_dtype": "float32",
        "num_experts": cfg.num_experts,
        "num_local_experts": cfg.num_held_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size": cfg.shared_expert_size,
        "norm_topk_prob": cfg.norm_topk_prob,
        "moe_routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_apply_router_weight_on_input": False,
        "router_aux_loss_coef": cfg.router_aux_loss_coef,
        "gating": any(
            getattr(cfg, m).head_gate for m in mixers if getattr(cfg, m)
        ),
        "sliding_window": cfg.swa.window if cfg.swa else 0,
        "layer_types": [mixers[m] for m, _ in kinds],
        "mlp_layer_types": [
            "dense" if f == "dense" else "sparse" for _, f in kinds
        ],
        "num_attention_heads_per_layer": [
            getattr(cfg, m).num_heads for m, _ in kinds
        ],
        "rope_parameters": {
            mixers[m]: rope(getattr(cfg, m))
            for m in mixers if getattr(cfg, m) is not None
        },
    }


def _zaya_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._zaya_from_hf`` reads."""
    rotary = (cfg.cca.rotary_dim or cfg.head_dim) / cfg.head_dim
    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": ["hybrid"] * cfg.num_layers,
        "num_attention_heads": cfg.cca.num_heads,
        "num_key_value_heads": cfg.cca.num_kv_heads,
        "head_dim": cfg.head_dim,
        "cca_time0": cfg.cca.conv_taps[0],
        "cca_time1": cfg.cca.conv_taps[1],
        "partial_rotary_factor": rotary,
        "rope_parameters": {"hybrid": {
            "rope_type": "default", "rope_theta": cfg.cca.rope_theta,
            "partial_rotary_factor": rotary,
        }},
        "max_position_embeddings": cfg.max_position_embeddings,
        "attention_bias": False,
        "lm_head_bias": False,
        "hidden_act": "silu",
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "torch_dtype": "float32",
        "num_experts": cfg.num_experts,
        "num_local_experts": cfg.num_held_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "router_hidden_size": cfg.router.hidden_size,
        "router_aux_loss_coef": cfg.router_aux_loss_coef,
        "sliding_window": None,
    }


def _qwen3_next_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._qwen3_next_from_hf`` reads."""
    full_at = [i for i, k in enumerate(cfg.layer_kinds) if k[:4] == "full"]
    gc, rc = cfg.gdn, cfg.full
    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None,
        "partial_rotary_factor": (
            (rc.rotary_dim or cfg.head_dim) / cfg.head_dim
        ),
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "hidden_act": "silu",
        "torch_dtype": "float32",
        "full_attention_interval": full_at[0] + 1 if full_at else 0,
        "linear_num_key_heads": gc.num_k_heads,
        "linear_num_value_heads": gc.num_v_heads,
        "linear_key_head_dim": gc.head_k_dim,
        "linear_value_head_dim": gc.head_v_dim,
        "linear_conv_kernel_dim": gc.conv_size,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [],
        "use_sliding_window": False,
        "num_experts": cfg.num_experts,
        "num_local_experts": cfg.num_held_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size": cfg.shared_expert_size,
        "norm_topk_prob": cfg.norm_topk_prob,
        "router_aux_loss_coef": cfg.router_aux_loss_coef,
    }


def _jamba_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._jamba_from_hf`` reads."""
    full_at = [i for i, k in enumerate(cfg.layer_kinds) if k[:4] == "full"]
    every = (
        full_at[1] - full_at[0] if len(full_at) > 1 else cfg.num_layers
    )
    sc = cfg.ssm
    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "hidden_act": "silu",
        "torch_dtype": "float32",
        "attn_layer_period": every,
        "attn_layer_offset": full_at[0] if full_at else every,
        "mamba_expand": sc.d_inner // cfg.hidden_size,
        "mamba_d_state": sc.d_state,
        "mamba_dt_rank": sc.dt_rank,
        "mamba_d_conv": sc.conv_size,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "num_experts": 1,
        "num_experts_per_tok": 1,
        "sliding_window": None,
    }


def _deepseek_v3_config_dict(cfg: ModelConfig) -> dict:
    """The keys ``models/config._deepseek_v3_from_hf`` reads."""
    m = cfg.mla
    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": m.rope_theta,
        "rope_scaling": None,
        "rope_interleave": True,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "hidden_act": "silu",
        "attention_bias": False,
        "torch_dtype": "float32",
        "kv_lora_rank": m.kv_lora_rank,
        "q_lora_rank": m.q_lora_rank or None,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "qk_head_dim": m.qk_head_dim,
        "v_head_dim": m.v_head_dim,
        "first_k_dense_replace": sum(
            k.endswith("+dense") for k in cfg.layer_kinds
        ),
        "moe_layer_freq": 1,
        "n_routed_experts": cfg.num_experts,
        "num_local_experts": cfg.num_held_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "n_shared_experts": cfg.shared_expert_size
        // max(cfg.moe_intermediate_size, 1),
        "norm_topk_prob": cfg.norm_topk_prob,
        "scoring_func": cfg.router_act,
        "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "router_aux_loss_coef": cfg.router_aux_loss_coef,
        "num_nextn_predict_layers": cfg.mtp_layers,
        "mtp_loss_coef": cfg.mtp_loss_coef,
    }


def default_hf_config_dict(cfg: ModelConfig) -> dict:
    if cfg.family == "kimi_linear":
        return _kimi_linear_config_dict(cfg)
    if cfg.family == "joyai_llm_flash":
        return _deepseek_v3_config_dict(cfg)
    if cfg.family == "qwen3_next":
        return _qwen3_next_config_dict(cfg)
    if cfg.family in ("laguna", "mellum"):
        return _laguna_config_dict(cfg)
    if cfg.family == "zaya":
        return _zaya_config_dict(cfg)
    if cfg.family == "jamba":
        return _jamba_config_dict(cfg)
    return {
        "model_type": cfg.family,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "attention_bias": cfg.attention_bias,
        "torch_dtype": "float32",
        "architectures": {
            "llama": ["LlamaForCausalLM"],
            "qwen2": ["Qwen2ForCausalLM"],
            "qwen3": ["Qwen3ForCausalLM"],
            "mistral": ["MistralForCausalLM"],
            "qwen3_moe": ["Qwen3MoeForCausalLM"],
            "qwen2_moe": ["Qwen2MoeForCausalLM"],
            "mixtral": ["MixtralForCausalLM"],
            "qwen2_vl": ["Qwen2VLForConditionalGeneration"],
            "gemma": ["GemmaForCausalLM"],
            "olmoe": ["OlmoeForCausalLM"],
        }.get(cfg.family, ["LlamaForCausalLM"]),
        **(
            {"hidden_act": "gelu_pytorch_tanh",
             "hidden_activation": "gelu_pytorch_tanh"}
            if cfg.hidden_act == "gelu_tanh"
            else {}
        ),
        **(
            {
                "vision_config": {
                    "embed_dim": cfg.vision.hidden_size,
                    "hidden_size": cfg.vision.out_hidden_size,
                    "depth": cfg.vision.depth,
                    "num_heads": cfg.vision.num_heads,
                    "intermediate_size": cfg.vision.intermediate_size,
                    "patch_size": cfg.vision.patch_size,
                    "temporal_patch_size": cfg.vision.temporal_patch_size,
                    "spatial_merge_size": cfg.vision.spatial_merge_size,
                    "in_chans": cfg.vision.in_channels,
                },
                "rope_scaling": {
                    "type": "mrope",
                    "mrope_section": list(cfg.mrope_sections or ()),
                },
                "image_token_id": cfg.image_token_id,
            }
            if cfg.vision is not None
            else {}
        ),
        **(
            {
                "num_experts": cfg.num_experts,
                "num_local_experts": cfg.num_experts,
                "num_experts_per_tok": cfg.num_experts_per_tok,
                # olmoe and mixtral have no such key: an expert's width is
                # their intermediate_size
                **(
                    {"moe_intermediate_size": cfg.moe_intermediate_size}
                    if cfg.moe_intermediate_size else {}
                ),
                "norm_topk_prob": cfg.norm_topk_prob,
                "router_aux_loss_coef": cfg.router_aux_loss_coef,
                **(
                    {
                        "shared_expert_intermediate_size":
                            cfg.shared_expert_size,
                        "decoder_sparse_step": 1,
                        "mlp_only_layers": [],
                    }
                    if cfg.shared_expert_size
                    else {}
                ),
            }
            if cfg.is_moe
            else {}
        ),
    }
