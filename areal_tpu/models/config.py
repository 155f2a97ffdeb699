"""Model architecture config for the decoder-only families the trainer runs.

Role of reference realhf/api/core/model_api.py `ReaLModelConfig` + the HF
config conversion in realhf/api/from_hf/: one dataclass describes every
supported decoder-only family. A model whose layers are all one block
(llama, qwen2/3, gemma, the sparse-expert families, the one with a learned
indexer) differs from its siblings in fields (QKV bias, tied embeddings,
head_dim override, q/k norm, experts). A model whose layers are of more
than one kind names them: ``layer_kinds`` holds a block kind a layer
(``"<mixer>+<ffn>"``: the SIX mixers ``kda`` (gated delta-rule linear
attention whose decay is a number a channel, ``KDAConfig``), ``gdn`` (the
same rule with ONE decay a head and token, more value heads than key heads
and a gate as wide as its output, ``GDNConfig``), ``mla`` (latent attention,
``MLAConfig``: its query whole or compressed, its shared key part plain or
rotated), the two rotary GQA ones, ``full`` and ``swa`` (all of a
sequence's earlier keys, or a window of them: a ``RotaryConfig`` each) and
``cca`` (attention inside a compressed latent that two causal convolutions
mix, ``CCAConfig``); the
FFNs ``dense`` and ``moe``, the latter routed by ``h @ w_router`` or, where
the model has a ``RouterConfig``, by a small MLP whose input passes from
layer to layer), and the block kinds, not further per-family scalars, are
the unit ``models/transformer.py`` builds its stacks from. The ONE thing a
model may carry beside its stack is a draft module (``mtp_layers``: the
model's own multi-token-prediction block, trained beside the loss at
``mtp_loss_coef``; ``models/transformer.draft_forward``).
"""

import dataclasses
import json
import os
from typing import Optional, Tuple

from areal_tpu.models.vision import VisionConfig


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """The gated delta-rule mixer's sizes (``ops/kda.py``): ``num_heads``
    heads of ``head_dim`` (key and value alike), a depthwise causal
    convolution of ``conv_size`` taps on q, k and v; the decay and the
    output gate are low rank through ``head_dim``."""

    num_heads: int
    head_dim: int
    conv_size: int = 4


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """The scalar-decay gated delta-rule mixer's sizes
    (``models/transformer._gdn_mixer``; ``ops/kda.py`` has the rule):
    ``num_k_heads`` key (and query) heads of ``head_k_dim``, each serving
    ``num_v_heads / num_k_heads`` consecutive value heads of ``head_v_dim``;
    one depthwise causal convolution of ``conv_size`` taps over q, k and v's
    channels together; ONE log-decay and one beta a value head and token;
    the output normed a head and gated element by element."""

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_size: int = 4

    @property
    def key_dim(self) -> int:
        return self.num_k_heads * self.head_k_dim

    @property
    def value_dim(self) -> int:
        return self.num_v_heads * self.head_v_dim


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """The latent-attention mixer's sizes: keys and values come from one
    ``kv_lora_rank``-wide compression of the token, a head's query and key
    are ``qk_nope_head_dim`` wide plus ``qk_rope_head_dim`` that all heads'
    keys share, its value ``v_head_dim``. ``q_lora_rank`` > 0: the query
    too passes a compression of that rank and a norm (0: ``h @ wq``).
    ``rotated``: the shared key part and the last ``qk_rope_head_dim``
    channels of every query head turn at the token's position, plain rope
    at ``rope_theta`` (false: NoPE, the 64-wide part a plain shared key)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: int = 0
    rotated: bool = False
    rope_theta: float = 10000.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class RotaryConfig:
    """One rotary GQA mixer of a stack of block kinds: ``num_heads`` query
    heads over the model's ``num_kv_heads`` of ``head_dim``; a query sees the
    ``window`` latest keys of its sequence, its own among them (0 = all of
    them); the first ``rotary_dim`` channels of a head are rotated (0 = the
    whole head) at ``rope_theta``, by yarn where ``yarn_factor`` > 1
    (``ops/basic.rope_inv_freq``), cos and sin times ``attention_factor``;
    ``head_gate``: each head's output times ``sigmoid(h Wg)`` of the layer's
    normed input, one number a head and token; ``output_gate``: the core's
    output times the sigmoid of a gate as wide as itself, which the query's
    projection makes beside the query (a head's ``head_dim`` of query, then
    its ``head_dim`` of gate)."""

    num_heads: int
    window: int = 0
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    yarn_factor: float = 1.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0
    head_gate: bool = False
    output_gate: bool = False
    # no rotation and no position of any kind (the ``jamba`` family's
    # attention layers): the same mixer without the turn
    nope: bool = False


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The state-space mixer's sizes (``models/transformer._ssm_mixer``;
    ``ops/ssm_scan.py`` has the scan; Mamba-1, S6): ``d_inner`` channels,
    each a state of ``d_state`` numbers; the token's step ``dt`` comes through
    a rank of ``dt_rank``; a depthwise causal convolution of ``conv_size``
    taps with a bias before it. The ``jamba`` family norms ``dt``'s input,
    ``B`` and ``C`` each by a weight (its three inner norms)."""

    d_inner: int
    d_state: int
    dt_rank: int
    conv_size: int = 4


@dataclasses.dataclass(frozen=True)
class CCAConfig:
    """The compressed-convolutional-attention mixer's sizes
    (``models/transformer._cca_mixer``): ``num_heads`` query heads over
    ``num_kv_heads`` key heads of the model's ``head_dim``, narrower
    together than the residual stream; q and k pass two causal convolutions
    over a sequence's own tokens, ``conv_taps[0]`` taps a channel and then
    ``conv_taps[1]`` taps of a [head_dim, head_dim] matrix a head; the
    first ``rotary_dim`` channels of a head are rotated (0 = the whole
    head) at ``rope_theta``. The values are two heads always: the token's
    own and the previous token's."""

    num_heads: int
    num_kv_heads: int
    conv_taps: Tuple[int, int] = (2, 2)
    rotary_dim: int = 0
    rope_theta: float = 10000.0


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """A router whose scores are an MLP's (``ops/moe.router_mlp``): the
    token's normed input projected to ``hidden_size``, normed, two GELU
    layers of that width, one score an expert. A layer's projected input
    takes the previous layer's (after ITS addition) times a learned scalar,
    so the stack's scan carries it beside ``x``."""

    hidden_size: int


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """A model that generates by diffusion over blocks (the ``sdar_moe``
    family; BD3-LM's training layout, arXiv:2503.09573): a token at position
    p of its sequence is in block ``p // block_length``, the prompt counted
    from position 0; a masked slot holds ``mask_token_id`` and predicts its
    OWN token (no shift). What a row holds and what a query sees is
    ``utils/data.pack_bd_rows`` and ``ops/bd_attention.py``."""

    block_length: int
    mask_token_id: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # qwen2: QKV bias, no O bias
    # RMSNorm on q and k before the rotation: None; "head" (qwen3: each
    # head normed by itself, weight [head_dim]); "projection" (olmoe: the
    # whole q or k projection normed at once, before the split into heads,
    # weight [q_dim] / [kv_dim])
    qk_norm: Optional[str] = None
    family: str = "llama"
    # --- MoE (0 experts = dense; reference realhf/impl/model/modules/moe) ---
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0  # 0 → intermediate_size
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    # qwen2_moe-style shared expert: a dense SiLU-gated FFN of this size
    # runs on EVERY token alongside the routed experts, scaled by a
    # sigmoid gate (0 = no shared expert)
    shared_expert_size: int = 0
    # --- gemma-family knobs (GeLU MLP, (1+w) RMSNorm, sqrt(d) embedding
    # scaling); defaults are the llama/qwen conventions ---
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    norm_add_unit_offset: bool = False
    scale_embeddings: bool = False
    # --- VLM (vision tower + mrope; reference VLM path via HF Qwen2-VL,
    # areal/engine/base_hf_engine.py pixel plumbing) ---
    vision: Optional[VisionConfig] = None
    mrope_sections: Optional[Tuple[int, ...]] = None
    image_token_id: int = -1
    # --- learned sparse attention (DeepSeek-Sparse-Attention indexer, the
    # KeyeVL2 family's ``sa_config``; ops/sparse_attention.py): each query
    # attends to the ``dsa_topk`` keys of its own sequence that a small
    # indexer scores highest (0 = full causal attention, no indexer) ---
    dsa_topk: int = 0
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 0
    # --- the experts this process holds of ``num_experts`` (0 = all): the
    # chip's share of an expert-parallel deployment. The router keeps its
    # published width; the layer computes the part of the result that
    # experts [0, experts_held) give (ops/moe.py) ---
    experts_held: int = 0
    # --- a stack of more than one block kind: the kind of every layer,
    # ``"<mixer>+<ffn>"`` (() = every layer the one block the fields above
    # describe), and the sizes of the mixers that are not rotary GQA ---
    layer_kinds: Tuple[str, ...] = ()
    kda: Optional[KDAConfig] = None
    gdn: Optional[GDNConfig] = None
    mla: Optional[MLAConfig] = None
    # the rotary GQA mixers of such a stack, ``full`` and ``swa``: each its
    # own heads, rotation and (``swa``) window; ``num_kv_heads`` and
    # ``head_dim`` above are theirs alike
    full: Optional[RotaryConfig] = None
    swa: Optional[RotaryConfig] = None
    # the router's two forms (ops/moe.router_topk): "softmax", or "sigmoid"
    # (scores chosen with a correction bias, weighted without it, then
    # times ``routed_scaling_factor``)
    router_act: str = "softmax"
    routed_scaling_factor: float = 1.0
    # the shared expert's output times a per-token sigmoid gate (qwen2_moe)
    # or added as it is
    shared_expert_gate: bool = True
    # the ``cca`` mixer of such a stack, and the sparse layers' router where
    # it is an MLP (None: ``h @ w_router``)
    cca: Optional[CCAConfig] = None
    router: Optional[RouterConfig] = None
    # the state-space mixer of such a stack (kind ``ssm``)
    ssm: Optional[SSMConfig] = None
    # --- the draft module beside the stack (DeepSeek-V3's multi-token
    # prediction at depth 1; ``models/transformer.draft_forward``): one block
    # of the stack's LAST kind with its own weights over [norm(Emb(next
    # token)) ; norm(the stack's normed output)], the trunk's embedding and
    # head. 0 = the model has none. ``grad_step`` trains it beside the loss,
    # ``loss + mtp_loss_coef * L_mtp``, where the coefficient is > 0; the
    # trunk, the embedding and the head enter it under ``stop_gradient`` ---
    mtp_layers: int = 0
    mtp_loss_coef: float = 0.0
    # --- generation by diffusion over blocks: the trainer's rows then hold
    # a clean stream and its masked copies under a mask that is causal
    # between blocks and two-way inside one (None: a causal model) ---
    block_diffusion: Optional[BlockDiffusionConfig] = None

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def expert_ffn_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def num_held_experts(self) -> int:
        """How many experts' weights the model stores."""
        return self.experts_held or self.num_experts

    @property
    def has_indexer(self) -> bool:
        return self.dsa_topk > 0

    @property
    def trains_draft(self) -> bool:
        """Whether ``grad_step`` runs the draft module and adds its loss."""
        return self.mtp_layers > 0 and self.mtp_loss_coef > 0

    @property
    def attn_head_dims(self) -> Tuple[int, int]:
        """(query/key, value) head width of the softmax attention core."""
        if self.mla is not None:
            return self.mla.qk_head_dim, self.mla.v_head_dim
        return self.head_dim, self.head_dim

    @property
    def num_kda_layers(self) -> int:
        """The layers whose mixer is the chunked delta rule, either decay."""
        return sum(
            kind.startswith(("kda+", "gdn+")) for kind in self.layer_kinds
        )

    @property
    def layer_window(self) -> int:
        """The window of the model's window-attention layers (0 = it has
        none)."""
        has = self.swa is not None and any(
            kind.startswith("swa+") for kind in self.layer_kinds
        )
        return self.swa.window if has else 0

    @property
    def num_ssm_layers(self) -> int:
        """The layers whose mixer is the selective scan."""
        return sum(kind.startswith("ssm+") for kind in self.layer_kinds)

    def kind_layers(self, kind: str) -> int:
        """How many layers are of block ``kind``."""
        return sum(k == kind for k in self.layer_kinds)


# Supported HF `model_type`s. The llama-style decoder block (RMSNorm +
# SiLU-gated MLP + rotary GQA attention) is the baseline; gemma layers on
# GeLU(tanh), (1+w) norms and sqrt(d) embedding scaling via config knobs.
# qwen3_moe/mixtral/olmoe are expert-only sparse (olmoe: 64 experts of
# which 8 a token, weights not renormalised, q/k normed over the whole
# projection); qwen2_moe adds the shared expert + sigmoid gate.
# KeyeVL2 (the language model of Keye-VL-2.0) is a qwen3_moe block whose
# attention reads only the ``sa_config.topk`` keys a learned indexer picks
# (ops/sparse_attention.py); its vision tower is not built.
# kimi_linear is a stack of block kinds (``_kimi_linear_from_hf``): gated
# delta-rule layers and latent-attention layers (NoPE as published; a rotated
# key part and a compressed query read to ``MLAConfig``), a leading dense FFN
# and sigmoid-routed experts with an ungated shared one after it.
# laguna is a stack of block kinds too (``_laguna_from_hf``): rotary GQA
# layers of two kinds, ``full`` (every earlier key; yarn over the first half
# of a head) and ``swa`` (a window of ``sliding_window`` keys; plain rope),
# each with its own number of query heads and a per-head output gate, over a
# leading dense FFN and softmax-routed experts with an ungated shared one.
# The trainer runs it; the generation engine (window layers want pages that
# stop growing, ROADMAP B.3), ``hf_io`` (no published checkpoint names) and
# sequence parallelism refuse it by name.
# zaya is a stack of ONE block kind, ``cca+moe`` (``_zaya_from_hf``):
# attention inside a compressed latent (8 query and 2 key heads on half the
# residual width, two causal convolutions and a q-k mean on the way in, half
# the values a token late) over experts as wide as the residual stream of
# which a token takes ONE, chosen by a small MLP whose input passes from
# layer to layer; both sublayers scale their residual and their output. The
# trainer runs it; the generation engine (a decode step needs the last
# tokens' pre-convolution q, k and the last token's second value beside the
# pages: recurrent state beside KV pages, ROADMAP B.4), ``hf_io`` (no
# published checkpoint names) and sequence parallelism (a convolution and a
# shift across a ring's borders are not built) refuse it by name, as
# ``_zaya_from_hf`` does the 74B sibling's window layers.
# mellum is laguna's two rotary kinds again (``_mellum_from_hf``) with every
# layer's heads alike, no gate, no shared expert and no leading dense layer,
# the whole head rotated, and q and k normed a head before the rotation
# (``qk_norm`` "head", which a rotary mixer of a stack of kinds reads too);
# the same three refuse it, by what the ``swa`` mechanism declares.
# qwen3_next is a stack of TWO block kinds (``_qwen3_next_from_hf``): ``gdn``,
# the delta rule with one decay a head and token, 32 value heads over 16 key
# heads and an element-wise gate, three layers in four, and ``full``, rotary
# GQA at heads of 256 of which a quarter turns, q and k normed a head, the
# core's output gated element by element from the query's own projection, the
# fourth; every FFN 512 softmax-routed experts of which 10 a token, and a
# shared one behind a sigmoid gate; every norm but the delta rule's output
# norm scales by (1 + w). The trainer runs it; the generation engine (a
# recurrent state beside KV pages), ``hf_io`` (no checkpoint layout of a
# share; the published names wait for ROADMAP B.15) and sequence parallelism
# (the state would pass from device to device) refuse it by name.
# joyai_llm_flash is the DeepSeek-V3 key set (``_deepseek_v3_from_hf``): ONE
# mixer, latent attention whose query passes a compression of its own and
# whose shared key part and query heads' last 64 channels are ROTATED, on
# every layer; a leading dense FFN, then 256 sigmoid-routed experts of which
# 8 a token, chosen with a correction bias, and an ungated shared one; and the
# model's own draft module (multi-token prediction at depth 1) beside the
# stack, which ``grad_step`` trains beside the loss and ``logp_forward`` does
# not run. The trainer runs it; the generation engine (no latent paged cache,
# no absorbed score, no drafting with the module: ROADMAP B.5, B.7), ``hf_io``
# (the published rope columns are interleaved, a loader's permutation: ROADMAP
# B.15) and sequence parallelism (the module's one-token shift across a
# ring's borders is not built) refuse it by name.
# sdar_moe is a stack of ONE block kind, ``full+moe`` (``_sdar_from_hf``):
# the qwen3-moe block (rotary GQA over the whole head, q and k normed a head,
# softmax-routed experts renormalised, no shared one) trained as a
# BLOCK-DIFFUSION policy (``BlockDiffusionConfig``): a trainer's row holds
# each sequence's clean stream and a masked copy a denoising state, and the
# core's mask is causal between blocks of ``block_length`` and two-way inside
# one (``ops/bd_attention.py``). The trainer runs it on batches that carry a
# per-token ``denoise_state``; the generation engine (a step yields other
# than one token a sequence: ROADMAP B), ``hf_io`` (a share of the experts is
# no file of its own) and sequence parallelism refuse it by what the
# ``block_diffusion`` mechanism declares.
# jamba is a stack of TWO block kinds (``_jamba_from_hf``): ``ssm``, a Mamba-1
# selective scan (a decay a channel AND a state, ``ops/ssm_scan.py``) behind a
# biased causal convolution, with the family's three inner norms, and ``full``
# WITHOUT a rotation or a position of any kind (``RotaryConfig.nope``), one
# key head serving the query heads, where ``i % attn_layer_period ==
# attn_layer_offset``; a dense SwiGLU FFN in every layer (``num_experts`` 1:
# a Jamba with experts has a router that is not written, and is refused); the
# head tied to the embedding. The trainer runs it; the generation engine (a
# layer's cache is a recurrent state and a convolution's tail, not pages:
# ROADMAP B.4) and sequence parallelism (the state would pass from device to
# device) refuse it by what the ``ssm`` mechanism declares.
# gemma2/gpt2 remain out (gemma2: soft-capped scores and post-norms beside
# its windows; gpt2: learned positions, a new embedding).
_HF_FAMILIES = (
    "llama", "qwen2", "qwen3", "mistral", "qwen3_moe", "mixtral",
    "qwen2_vl", "qwen2_moe", "gemma", "olmoe", "KeyeVL2", "kimi_linear",
    "laguna", "zaya", "mellum", "qwen3_next", "joyai_llm_flash", "sdar_moe",
    "jamba",
)


_QK_NORM = {
    "qwen3": "head", "qwen3_moe": "head", "olmoe": "projection",
    "KeyeVL2": "head",
}


def _vision_from_hf(d: dict, lm_hidden: int) -> VisionConfig:
    """Parse an HF qwen2_vl / qwen2_5_vl `vision_config` block. qwen2_vl
    names the tower width `embed_dim` (with `hidden_size` = LM hidden);
    qwen2_5_vl names it `hidden_size` (with `out_hidden_size`)."""
    width = d.get("embed_dim") or d["hidden_size"]
    out = d.get("out_hidden_size") or (
        d.get("hidden_size") if d.get("embed_dim") else lm_hidden
    )
    inter = d.get("intermediate_size") or int(
        width * d.get("mlp_ratio", 4)
    )
    return VisionConfig(
        hidden_size=width,
        depth=d.get("depth", 32),
        num_heads=d.get("num_heads", 16),
        intermediate_size=inter,
        out_hidden_size=out,
        patch_size=d.get("patch_size", 14),
        temporal_patch_size=d.get("temporal_patch_size", 2),
        spatial_merge_size=d.get("spatial_merge_size", 2),
        in_channels=d.get("in_chans", d.get("in_channels", 3)),
    )


def _kimi_linear_from_hf(d: dict) -> ModelConfig:
    """``model_type`` kimi_linear: ``linear_attn_config`` lists the layers
    (1-indexed, as published) that are gated delta-rule (``kda_layers``) and
    full attention (``full_attn_layers``, latent attention without
    positions); the first ``first_k_dense_replace`` layers have a dense FFN
    of ``intermediate_size``, the others ``num_experts`` sigmoid-routed
    experts (``num_local_experts`` of them held here, where the file says
    so) and ``num_shared_experts`` shared ones of the same width."""
    la = d["linear_attn_config"]
    n_layers = d["num_hidden_layers"]
    kda_at, full_at = set(la["kda_layers"]), set(la["full_attn_layers"])
    if (kda_at | full_at) != set(range(1, n_layers + 1)) or kda_at & full_at:
        raise ValueError(
            "kimi_linear: kda_layers and full_attn_layers must name every "
            f"layer 1..{n_layers} once (got {sorted(kda_at)}, "
            f"{sorted(full_at)})"
        )
    rotated = not d.get("mla_use_nope")
    refused = {
        # a rotated key part and a compressed query are ``MLAConfig``'s
        # ``rotated`` and ``q_lora_rank`` (below); yarn on them is not built
        "rope_scaling on a rotated latent mixer": (
            rotated and d.get("rope_scaling")
        ),
        "num_expert_group > 1 (grouped top-k)": (
            d.get("num_expert_group", 1) != 1 or d.get("topk_group", 1) != 1
        ),
        "moe_layer_freq != 1": d.get("moe_layer_freq", 1) != 1,
        "a router that is not sigmoid": (
            d.get("moe_router_activation_func", "sigmoid") != "sigmoid"
        ),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"kimi_linear with {what} is not supported")
    dense_first = d.get("first_k_dense_replace", 0)
    kinds = tuple(
        ("kda" if i in kda_at else "mla")
        + ("+dense" if i <= dense_first else "+moe")
        for i in range(1, n_layers + 1)
    )
    num_experts = d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=n_layers,
        num_heads=d["num_attention_heads"],
        num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
        # the file's own ``head_dim`` (72) is read by neither mixer
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        max_position_embeddings=d.get("model_max_length", 32768),
        rope_theta=d.get("rope_theta", 10000.0),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        family="kimi_linear",
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_token"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("moe_renormalize", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        shared_expert_size=(
            d.get("num_shared_experts", 0) * d["moe_intermediate_size"]
        ),
        shared_expert_gate=False,
        router_act="sigmoid",
        routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=kinds,
        kda=KDAConfig(
            num_heads=la["num_heads"], head_dim=la["head_dim"],
            conv_size=la.get("short_conv_kernel_size", 4),
        ),
        mla=MLAConfig(
            kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            q_lora_rank=d.get("q_lora_rank") or 0,
            rotated=rotated,
            rope_theta=float(d.get("rope_theta", 10000.0)),
        ),
    )


def _rotary_from_hf(
    d: dict, rope: dict, heads: int, window: int, head_gate: bool = True
):
    """One rotary mixer's ``RotaryConfig`` from its ``rope_parameters``
    group: ``rope_type`` default or yarn, ``partial_rotary_factor`` of the
    head rotated."""
    kind = rope.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(
            f"{d['model_type']}: rope_type {kind!r} is not supported"
        )
    rotary = int(d["head_dim"] * rope.get("partial_rotary_factor", 1.0))
    yarn = kind == "yarn"
    return RotaryConfig(
        num_heads=heads, window=window,
        rotary_dim=0 if rotary == d["head_dim"] else rotary,
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        yarn_factor=float(rope["factor"]) if yarn else 1.0,
        yarn_original_len=(
            rope["original_max_position_embeddings"] if yarn else 0
        ),
        yarn_beta_fast=float(rope.get("beta_fast", 32.0)),
        yarn_beta_slow=float(rope.get("beta_slow", 1.0)),
        attention_factor=(
            float(rope.get("attention_factor", 1.0)) if yarn else 1.0
        ),
        head_gate=head_gate,
    )


def _rotary_layer_kinds(d: dict, *per_layer: str) -> Tuple[str, ...]:
    """The block kinds of a family whose ``layer_types`` says which layers
    attend to the whole sequence and which to a window, and whose
    ``mlp_layer_types`` which FFNs are dense and which sparse: one entry a
    layer in both (and in every further key of ``per_layer``), refused by
    name otherwise."""
    family, n_layers = d["model_type"], d["num_hidden_layers"]
    mixers = {"full_attention": "full", "sliding_attention": "swa"}
    ffns = {"dense": "dense", "sparse": "moe"}
    keys = ("layer_types", "mlp_layer_types") + per_layer
    sizes = [len(d[k]) for k in keys]
    if sizes != [n_layers] * len(keys):
        raise ValueError(
            f"{family}: {', '.join(keys)} must have one entry a layer "
            f"({', '.join(map(str, sizes))} for {n_layers})"
        )
    types, mlps = d["layer_types"], d["mlp_layer_types"]
    unknown = (set(types) - set(mixers)) | (set(mlps) - set(ffns))
    if unknown:
        raise ValueError(f"{family}: unknown layer types {sorted(unknown)}")
    return tuple(f"{mixers[t]}+{ffns[m]}" for t, m in zip(types, mlps))


def _laguna_from_hf(d: dict) -> ModelConfig:
    """``model_type`` laguna: ``layer_types`` says which layers attend to
    the whole sequence and which to ``sliding_window`` keys,
    ``num_attention_heads_per_layer`` how many query heads each has (one
    number a kind), ``rope_parameters`` each kind's rotation,
    ``mlp_layer_types`` which FFNs are dense (``intermediate_size``) and
    which ``num_experts`` softmax-routed experts (``num_local_experts`` of
    them held here, where the file says so) with a shared one."""
    kinds = _rotary_layer_kinds(d, "num_attention_heads_per_layer")
    types, heads_at = d["layer_types"], d["num_attention_heads_per_layer"]
    refused = {
        f"gating {d.get('gating')!r} (only true / 'per-head': one gate a "
        "head)": d.get("gating") not in (True, "per-head"),
        "gating_types other than per_head": any(
            g != "per_head" for g in d.get("gating_types", ())
        ),
        "moe_apply_router_weight_on_input true": d.get(
            "moe_apply_router_weight_on_input", False
        ),
        "a soft-capped router (moe_router_logit_softcapping)": d.get(
            "moe_router_logit_softcapping", 0
        ),
        "attention_bias": d.get("attention_bias", False),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"laguna with {what} is not supported")
    heads = {}
    for kind, n in zip(types, heads_at):
        if heads.setdefault(kind, n) != n:
            raise ValueError(
                f"laguna: {kind} layers of {heads[kind]} and of {n} heads "
                "(one stack a kind holds one shape)"
            )
    rope = d["rope_parameters"]
    num_experts = d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"],
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        family="laguna",
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("norm_topk_prob", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        shared_expert_size=d.get("shared_expert_intermediate_size", 0),
        shared_expert_gate=False,
        routed_scaling_factor=d.get("moe_routed_scaling_factor", 1.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=kinds,
        full=_rotary_from_hf(
            d, rope["full_attention"], heads["full_attention"], 0
        ) if "full_attention" in heads else None,
        swa=_rotary_from_hf(
            d, rope["sliding_attention"], heads["sliding_attention"],
            d["sliding_window"],
        ) if "sliding_attention" in heads else None,
    )


def _mellum_from_hf(d: dict) -> ModelConfig:
    """``model_type`` mellum: ``layer_types`` says which layers attend to
    the whole sequence and which to ``sliding_window`` keys (all of
    ``num_attention_heads`` each, no gate), ``rope_parameters`` each kind's
    rotation over the whole head, ``mlp_layer_types`` which FFNs are dense
    (``intermediate_size``) and which ``num_experts`` softmax-routed experts
    (``num_local_experts`` of them held here, where the file says so), no
    shared one; q and k are normed a head before the rotation, as in the
    qwen3-moe block whose keys the file has."""
    kinds, heads = _rotary_layer_kinds(d), d["num_attention_heads"]
    types = d["layer_types"]
    if d.get("attention_bias", False):
        raise ValueError("mellum with attention_bias is not supported")
    rope = d["rope_parameters"]
    num_experts = d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"],
        max_position_embeddings=d.get("max_position_embeddings", 131072),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        qk_norm="head",
        family="mellum",
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("norm_topk_prob", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=kinds,
        full=_rotary_from_hf(
            d, rope["full_attention"], heads, 0, head_gate=False
        ) if "full_attention" in types else None,
        swa=_rotary_from_hf(
            d, rope["sliding_attention"], heads, d["sliding_window"],
            head_gate=False,
        ) if "sliding_attention" in types else None,
    )


def _sdar_from_hf(d: dict) -> ModelConfig:
    """``model_type`` sdar_moe: the qwen3-moe key set (every layer rotary GQA
    over the whole head at ``rope_theta``, q and k normed a head, then
    ``num_experts`` softmax-routed experts, ``num_local_experts`` of them
    held here where the file says so) of a model that generates by diffusion
    over blocks. The published config has no key for the block's length or
    the mask token: ``block_length`` (the family's released default, 4) and
    ``mask_token_id`` are read where a file states them."""
    if d.get("mlp_only_layers") or d.get("decoder_sparse_step", 1) != 1:
        raise ValueError(
            "sdar_moe with mlp_only_layers / decoder_sparse_step != 1 is "
            "not supported: every layer is the one sparse block"
        )
    if d.get("attention_bias", False) or d.get("rope_scaling"):
        raise ValueError(
            "sdar_moe with attention_bias or rope_scaling is not supported"
        )
    if d.get("use_sliding_window", False):
        raise ValueError("sdar_moe with a sliding window is not supported")
    if "mask_token_id" not in d:
        raise ValueError(
            "sdar_moe: the configuration must state mask_token_id (the "
            "published config.json has no key for it; the family's <|MASK|> "
            "is an added token)"
        )
    heads, num_experts = d["num_attention_heads"], d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    theta = float(d.get("rope_theta", 1000000.0))
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=d["num_key_value_heads"],
        head_dim=d.get("head_dim") or d["hidden_size"] // heads,
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rope_theta=theta,
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        qk_norm="head",
        family="sdar_moe",
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("norm_topk_prob", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=("full+moe",) * d["num_hidden_layers"],
        full=RotaryConfig(num_heads=heads, rope_theta=theta),
        block_diffusion=BlockDiffusionConfig(
            block_length=int(d.get("block_length", 4)),
            mask_token_id=int(d["mask_token_id"]),
        ),
    )


def _zaya_from_hf(d: dict) -> ModelConfig:
    """``model_type`` zaya: every ``layer_types`` entry ``hybrid`` = one
    ``cca`` attention sublayer (``cca_time0`` / ``cca_time1`` the two
    convolutions' taps, ``rope_parameters.hybrid`` the rotation) and one
    expert sublayer of ``num_experts`` experts (``num_local_experts`` of
    them held here, where the file says so), ONE a token by an MLP router of
    ``router_hidden_size`` whose input is carried from layer to layer."""
    n_layers = d["num_hidden_layers"]
    types = d["layer_types"]
    if len(types) != n_layers:
        raise ValueError(
            f"zaya: layer_types has {len(types)} entries for {n_layers} "
            "layers"
        )
    other = sorted(set(types) - {"hybrid"})
    refused = {
        f"layer_types {other} (only 'hybrid'; the 74B sibling's window "
        "layers, 'hybrid_sliding', are not built)": other,
        "a sliding_window": d.get("sliding_window"),
        f"num_experts_per_tok {d['num_experts_per_tok']} (one a token takes "
        "the unnormalised probability; nobody has stated this router's "
        "renormalisation over more)": d["num_experts_per_tok"] != 1,
        "attention_bias": d.get("attention_bias", False),
        "lm_head_bias": d.get("lm_head_bias", False),
        f"hidden_act {d.get('hidden_act')!r}": (
            d.get("hidden_act", "silu") != "silu"
        ),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"zaya with {what} is not supported")
    rope = d["rope_parameters"]["hybrid"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(
            f"zaya: rope_type {rope['rope_type']!r} is not supported"
        )
    head_dim = d["head_dim"]
    rotary = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    num_experts = d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        # no dense FFN anywhere: the experts' width stands in
        intermediate_size=d["moe_intermediate_size"],
        num_layers=n_layers,
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"],
        head_dim=head_dim,
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", True),
        family="zaya",
        num_experts=num_experts,
        num_experts_per_tok=1,
        moe_intermediate_size=d["moe_intermediate_size"],
        # one a token: the weight is the chosen probability as it is
        norm_topk_prob=False,
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=("cca+moe",) * n_layers,
        cca=CCAConfig(
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            conv_taps=(d["cca_time0"], d["cca_time1"]),
            rotary_dim=0 if rotary == head_dim else rotary,
            rope_theta=float(rope.get("rope_theta", 10000.0)),
        ),
        router=RouterConfig(hidden_size=d["router_hidden_size"]),
    )


def _qwen3_next_from_hf(d: dict) -> ModelConfig:
    """``model_type`` qwen3_next: layer ``i`` is full attention where
    ``(i + 1) % full_attention_interval == 0`` and the scalar-decay delta
    rule elsewhere (``linear_*`` its sizes); every layer's FFN is
    ``num_experts`` softmax-routed experts (``num_local_experts`` of them
    held here, where the file says so) and a shared one of
    ``shared_expert_intermediate_size`` behind a sigmoid gate;
    ``intermediate_size`` is read by nothing."""
    n_layers = d["num_hidden_layers"]
    every = d.get("full_attention_interval", 4)
    refused = {
        "mlp_only_layers (dense layers among the sparse)": d.get(
            "mlp_only_layers"
        ),
        "decoder_sparse_step != 1": d.get("decoder_sparse_step", 1) != 1,
        "rope_scaling": d.get("rope_scaling"),
        "use_sliding_window": d.get("use_sliding_window", False),
        "attention_bias": d.get("attention_bias", False),
        f"hidden_act {d.get('hidden_act')!r}": (
            d.get("hidden_act", "silu") != "silu"
        ),
        "value heads that are no multiple of the key heads": (
            d["linear_num_value_heads"] % d["linear_num_key_heads"]
        ),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"qwen3_next with {what} is not supported")
    head_dim = d["head_dim"]
    rotary = int(head_dim * d.get("partial_rotary_factor", 1.0))
    kinds = tuple(
        ("full" if (i + 1) % every == 0 else "gdn") + "+moe"
        for i in range(n_layers)
    )
    num_experts = d["num_experts"]
    held = d.get("num_local_experts", num_experts)
    shared = d.get("shared_expert_intermediate_size", 0)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=n_layers,
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"],
        head_dim=head_dim,
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        qk_norm="head",
        family="qwen3_next",
        norm_add_unit_offset=True,
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("norm_topk_prob", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        shared_expert_size=shared,
        shared_expert_gate=bool(shared),
        experts_held=0 if held == num_experts else held,
        layer_kinds=kinds,
        gdn=GDNConfig(
            num_k_heads=d["linear_num_key_heads"],
            num_v_heads=d["linear_num_value_heads"],
            head_k_dim=d["linear_key_head_dim"],
            head_v_dim=d["linear_value_head_dim"],
            conv_size=d.get("linear_conv_kernel_dim", 4),
        ),
        full=RotaryConfig(
            num_heads=d["num_attention_heads"],
            rotary_dim=0 if rotary == head_dim else rotary,
            rope_theta=float(d.get("rope_theta", 10000.0)),
            output_gate=True,
        ),
    )


def _deepseek_v3_from_hf(d: dict) -> ModelConfig:
    """The DeepSeek-V3 key set (``model_type`` joyai_llm_flash): every
    layer's mixer is latent attention whose query passes ``q_lora_rank``
    (null: whole) and whose ``qk_rope_head_dim``-wide parts turn at
    ``rope_theta``; the first ``first_k_dense_replace`` layers have a dense
    FFN of ``intermediate_size``, the others ``n_routed_experts``
    sigmoid-routed experts (``num_local_experts`` of them held here, where
    the file says so), ``num_experts_per_tok`` a token chosen with a
    correction bias (``topk_method`` noaux_tc), and ``n_shared_experts``
    shared ones of the same width; ``num_nextn_predict_layers`` 1: the draft
    module beside the stack, trained at ``mtp_loss_coef`` (the file's, else
    0.1: the DeepSeek-V3 report's later-phase weight; the published key set
    has none). ``rope_interleave`` names the published PAIRING of the
    rotated channels; the program turns them rotate-half, the same function
    of freshly drawn weights under a permutation of the rope columns of
    ``wq_b`` and ``w_kv_a`` that a checkpoint loader owes (ROADMAP B.15)."""
    family = d["model_type"]
    heads = d["num_attention_heads"]
    refused = {
        "rope_scaling (yarn on a latent mixer)": d.get("rope_scaling"),
        "n_group > 1 (a group-limited top-k)": (
            d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1
        ),
        "moe_layer_freq != 1": d.get("moe_layer_freq", 1) != 1,
        f"scoring_func {d.get('scoring_func')!r} (only 'sigmoid')": (
            d.get("scoring_func", "sigmoid") != "sigmoid"
        ),
        f"topk_method {d.get('topk_method')!r} (only 'noaux_tc')": (
            d.get("topk_method", "noaux_tc") != "noaux_tc"
        ),
        "attention_bias": d.get("attention_bias", False),
        f"hidden_act {d.get('hidden_act')!r}": (
            d.get("hidden_act", "silu") != "silu"
        ),
        "key heads other than the query heads (the latent mixer has one "
        "key a head)": d.get("num_key_value_heads", heads) != heads,
        f"num_nextn_predict_layers {d.get('num_nextn_predict_layers')} "
        "(a draft module of depth 1 is built, no chain of them)": (
            d.get("num_nextn_predict_layers", 0) > 1
        ),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"{family} with {what} is not supported")
    n_layers = d["num_hidden_layers"]
    dense_first = d.get("first_k_dense_replace", 0)
    num_experts = d["n_routed_experts"]
    held = d.get("num_local_experts", num_experts)
    mtp_layers = d.get("num_nextn_predict_layers", 0)
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=n_layers,
        num_heads=heads,
        num_kv_heads=heads,
        # the file's own ``head_dim`` (64) is the rotated part's width
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        family=family,
        num_experts=num_experts,
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        norm_topk_prob=d.get("norm_topk_prob", True),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.0),
        shared_expert_size=(
            d.get("n_shared_experts", 0) * d["moe_intermediate_size"]
        ),
        shared_expert_gate=False,
        router_act="sigmoid",
        routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
        experts_held=0 if held == num_experts else held,
        layer_kinds=tuple(
            "mla+dense" if i < dense_first else "mla+moe"
            for i in range(n_layers)
        ),
        mla=MLAConfig(
            kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            q_lora_rank=d.get("q_lora_rank") or 0,
            rotated=True,
            rope_theta=float(d.get("rope_theta", 10000.0)),
        ),
        mtp_layers=mtp_layers,
        mtp_loss_coef=(
            float(d.get("mtp_loss_coef", 0.1)) if mtp_layers else 0.0
        ),
    )


def _jamba_from_hf(d: dict) -> ModelConfig:
    """``model_type`` jamba: layer ``i`` is attention where ``i %
    attn_layer_period == attn_layer_offset`` (the family's code's rule) and
    the selective scan elsewhere (``mamba_*`` its sizes, ``d_inner`` =
    ``mamba_expand`` x ``hidden_size``); the attention has no rotation and no
    position; every layer's FFN is dense (``num_experts`` 1, so
    ``expert_layer_*`` are read by nothing); ``head_dim`` is the file's or
    ``hidden_size / num_attention_heads``."""
    refused = {
        f"num_experts {d.get('num_experts')} (the family's router over "
        "experts is not written; this reader takes the dense FFN of "
        "num_experts 1)": d.get("num_experts", 1) > 1,
        "sliding_window": d.get("sliding_window"),
        "mamba_proj_bias": d.get("mamba_proj_bias", False),
        "a convolution without its bias (mamba_conv_bias false)": (
            not d.get("mamba_conv_bias", True)
        ),
        f"hidden_act {d.get('hidden_act')!r}": (
            d.get("hidden_act", "silu") != "silu"
        ),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"jamba with {what} is not supported")
    heads, hidden = d["num_attention_heads"], d["hidden_size"]
    every, at = d.get("attn_layer_period", 8), d.get("attn_layer_offset", 4)
    rank = d.get("mamba_dt_rank", "auto")
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=hidden,
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=d.get("num_key_value_heads", heads),
        head_dim=d.get("head_dim") or hidden // heads,
        max_position_embeddings=d.get("max_position_embeddings", 262144),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        family="jamba",
        layer_kinds=tuple(
            ("full" if i % every == at else "ssm") + "+dense"
            for i in range(d["num_hidden_layers"])
        ),
        full=RotaryConfig(num_heads=heads, nope=True),
        ssm=SSMConfig(
            d_inner=d.get("mamba_expand", 2) * hidden,
            d_state=d.get("mamba_d_state", 16),
            dt_rank=-(-hidden // 16) if rank == "auto" else rank,
            conv_size=d.get("mamba_d_conv", 4),
        ),
    )


def from_hf_config(d: dict) -> ModelConfig:
    """Build from a parsed HF config.json dict (families mirror the
    reference's from_hf registry: realhf/api/from_hf/)."""
    model_type = d.get("model_type", "llama")
    if model_type not in _HF_FAMILIES:
        raise ValueError(f"unsupported model family {model_type!r}")
    if model_type == "kimi_linear":
        return _kimi_linear_from_hf(d)
    if model_type == "laguna":
        return _laguna_from_hf(d)
    if model_type == "zaya":
        return _zaya_from_hf(d)
    if model_type == "mellum":
        return _mellum_from_hf(d)
    if model_type == "qwen3_next":
        return _qwen3_next_from_hf(d)
    if model_type == "joyai_llm_flash":
        return _deepseek_v3_from_hf(d)
    if model_type == "sdar_moe":
        return _sdar_from_hf(d)
    if model_type == "jamba":
        return _jamba_from_hf(d)
    num_heads = d["num_attention_heads"]
    hidden = d["hidden_size"]
    head_dim = d.get("head_dim") or hidden // num_heads
    num_experts = d.get("num_experts") or d.get("num_local_experts") or 0
    if model_type == "qwen2_moe":
        # scanned layers need uniform structure: every layer sparse
        if d.get("mlp_only_layers") or d.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                "qwen2_moe with mlp_only_layers / decoder_sparse_step != 1 "
                "is unsupported (non-uniform layers break the scanned stack)"
            )
    vision = None
    mrope_sections = None
    image_token_id = -1
    if model_type == "qwen2_vl":
        vision = _vision_from_hf(d["vision_config"], hidden)
        rs = d.get("rope_scaling") or {}
        if rs.get("mrope_section"):
            mrope_sections = tuple(rs["mrope_section"])
        else:
            # fallback must partition head_dim//2 EXACTLY; the HF default
            # ratio is 1:1.5:1.5 ((16,24,24) for head_dim 128)
            half = head_dim // 2
            s = (half * 3) // 8
            mrope_sections = (half - 2 * s, s, s)
        image_token_id = d.get("image_token_id", 151655)
    sa = {}
    experts_held = 0
    if model_type == "KeyeVL2":
        sa = d["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError(
                "KeyeVL2: the indexer has one key head "
                f"(indexer_num_kv_heads={sa['indexer_num_kv_heads']})"
            )
        mrope_sections = tuple(d["rope_scaling"]["mrope_section"])
        # `num_experts` is the router's width, `num_local_experts` what
        # is held here (the published file gives both as 128)
        held = d.get("num_local_experts", num_experts)
        experts_held = 0 if held == d["num_experts"] else held
        num_experts = d["num_experts"]
    return ModelConfig(
        vocab_size=d["vocab_size"],
        hidden_size=hidden,
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=d.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        max_position_embeddings=d.get("max_position_embeddings", 32768),
        rope_theta=d.get("rope_theta", 10000.0),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get(
            "tie_word_embeddings", model_type == "gemma"
        ),
        attention_bias=d.get(
            "attention_bias",
            model_type in ("qwen2", "qwen2_vl", "qwen2_moe"),
        ),
        qk_norm=_QK_NORM.get(model_type),
        family=model_type,
        vision=vision,
        mrope_sections=mrope_sections,
        image_token_id=image_token_id,
        # gemma: GeLU(tanh) MLP, (1+w) norms, sqrt(d)-scaled embeddings
        hidden_act=(
            "gelu_tanh"
            if model_type == "gemma"
            or d.get("hidden_act", d.get("hidden_activation", "silu"))
            in ("gelu", "gelu_pytorch_tanh")
            else "silu"
        ),
        norm_add_unit_offset=(model_type == "gemma"),
        scale_embeddings=(model_type == "gemma"),
        num_experts=num_experts,
        num_experts_per_tok=d.get(
            "num_experts_per_tok", d.get("top_k", 2)
        ),
        # olmoe's config has no key of its own for an expert's width: its
        # intermediate_size IS that width (there is no dense MLP), which
        # expert_ffn_size falls back to
        moe_intermediate_size=d.get("moe_intermediate_size", 0),
        # HF Mixtral renormalizes top-k routing weights unconditionally
        # and qwen3_moe's config ships norm_topk_prob=true; qwen2_moe
        # ships FALSE (unnormalized top-k + shared expert)
        norm_topk_prob=d.get(
            "norm_topk_prob", model_type not in ("qwen2_moe", "olmoe")
        ),
        # HF Qwen2MoeConfig defaults the shared expert to 5632 and always
        # builds it — a missing key must not silently drop the expert
        shared_expert_size=(
            d.get("shared_expert_intermediate_size", 5632)
            if model_type == "qwen2_moe"
            else 0
        ),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.001),
        dsa_topk=sa.get("topk", 0),
        dsa_index_heads=sa.get("indexer_num_heads", 0),
        dsa_index_head_dim=sa.get("indexer_head_dim", 0),
        experts_held=experts_held,
    )


def load_hf_config(path: str) -> ModelConfig:
    with open(os.path.join(path, "config.json")) as f:
        return from_hf_config(json.load(f))


def tiny_vlm_config(vocab_size: int = 128) -> ModelConfig:
    """Small qwen2_vl-shaped config for tests: 2-layer LM (head_dim 16,
    mrope sections 4/2/2) over a 2-block vision tower (4px patches)."""
    return dataclasses.replace(
        tiny_config("qwen2", vocab_size=vocab_size),
        family="qwen2_vl",
        vision=VisionConfig(
            hidden_size=32,
            depth=2,
            num_heads=2,
            intermediate_size=64,
            out_hidden_size=64,
            patch_size=4,
            temporal_patch_size=2,
            spatial_merge_size=2,
        ),
        mrope_sections=(4, 2, 2),
        image_token_id=vocab_size - 2,
    )


def tiny_config(family: str = "qwen2", vocab_size: int = 128) -> ModelConfig:
    """Small config for tests."""
    moe = family in ("qwen3_moe", "mixtral", "qwen2_moe", "olmoe")
    if family == "joyai_llm_flash":
        # the leading dense layer and two sparse ones, every mixer latent
        # attention of two heads (8 + 4 | 8) over a rank of 16, the query
        # through a rank of 12, the 4-wide parts rotated; 4 experts held of
        # 16 sigmoid-routed ones, two a token, and a shared one; the draft
        # module beside the stack
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            num_layers=3, num_heads=2, num_kv_heads=2, head_dim=12,
            max_position_embeddings=512, rope_theta=32000000.0,
            rms_norm_eps=1e-6, family=family, num_experts=16,
            num_experts_per_tok=2, moe_intermediate_size=16,
            norm_topk_prob=True, router_aux_loss_coef=0.0, experts_held=4,
            shared_expert_size=16, shared_expert_gate=False,
            router_act="sigmoid", routed_scaling_factor=2.5,
            layer_kinds=("mla+dense", "mla+moe", "mla+moe"),
            mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                          qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=12,
                          rotated=True, rope_theta=32000000.0),
            mtp_layers=1, mtp_loss_coef=0.1,
        )
    if family == "jamba":
        # one period: three selective-scan layers (128 channels of state 16,
        # the step through a rank of 4) and, second, one attention layer of 4
        # query heads over ONE key head of 16, no rotation; dense FFNs, tied
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=4, num_heads=4, num_kv_heads=1, head_dim=16,
            max_position_embeddings=512, rms_norm_eps=1e-6,
            tie_word_embeddings=True, family=family,
            layer_kinds=("ssm+dense", "full+dense", "ssm+dense", "ssm+dense"),
            full=RotaryConfig(num_heads=4, nope=True),
            ssm=SSMConfig(d_inner=128, d_state=16, dt_rank=4),
        )
    if family == "zaya":
        # three ``cca+moe`` layers: 4 query and 2 key heads of 16 on the
        # residual's 64 (the latent is as wide as the stream here; half of
        # each head rotated), a carried router of width 8 over 8 experts of
        # which 4 are held, one a token
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=32,
            num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rope_theta=5000000.0,
            rms_norm_eps=1e-5, tie_word_embeddings=True, family=family,
            num_experts=8, num_experts_per_tok=1, moe_intermediate_size=32,
            norm_topk_prob=False, router_aux_loss_coef=0.0, experts_held=4,
            layer_kinds=("cca+moe",) * 3,
            cca=CCAConfig(num_heads=4, num_kv_heads=2, rotary_dim=8,
                          rope_theta=5000000.0),
            router=RouterConfig(hidden_size=8),
        )
    if family == "qwen3_next":
        # one period: three scalar-decay delta-rule layers (2 key heads of
        # 16 serving 4 value heads of 16) and one full layer of 4 query over
        # 2 key heads of 16, a quarter of each rotated, gated element by
        # element; 4 experts held of 16, four a token, a gated shared one;
        # every norm but the rule's output norm (1 + w)
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rope_theta=10000000.0,
            rms_norm_eps=1e-6, qk_norm="head", family=family,
            norm_add_unit_offset=True, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=16,
            norm_topk_prob=True, router_aux_loss_coef=0.0, experts_held=4,
            shared_expert_size=16, shared_expert_gate=True,
            layer_kinds=("gdn+moe", "gdn+moe", "gdn+moe", "full+moe"),
            gdn=GDNConfig(num_k_heads=2, num_v_heads=4, head_k_dim=16,
                          head_v_dim=16),
            full=RotaryConfig(
                num_heads=4, rotary_dim=4, rope_theta=10000000.0,
                output_gate=True,
            ),
        )
    if family == "sdar_moe":
        # two layers of the one block: 4 query heads of 16 over 2 key heads,
        # q and k normed a head, 8 experts of which 4 held, two a token;
        # blocks of 4, the mask token the vocabulary's last id
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=64,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rope_theta=1000000.0,
            rms_norm_eps=1e-6, qk_norm="head", family=family, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            norm_topk_prob=True, router_aux_loss_coef=0.0, experts_held=4,
            layer_kinds=("full+moe", "full+moe"),
            full=RotaryConfig(num_heads=4, rope_theta=1000000.0),
            block_diffusion=BlockDiffusionConfig(
                block_length=4, mask_token_id=vocab_size - 1
            ),
        )
    if family == "mellum":
        # one period: three layers of an 8-key window, one full layer under
        # yarn over the whole head; 4 query heads of 16 over 2 key heads, q
        # and k normed a head; 4 experts held of 16, four a token
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rms_norm_eps=1e-6, qk_norm="head",
            family=family, num_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=16, norm_topk_prob=True,
            router_aux_loss_coef=0.0, experts_held=4,
            layer_kinds=("swa+moe", "swa+moe", "swa+moe", "full+moe"),
            full=RotaryConfig(
                num_heads=4, rope_theta=500000.0, yarn_factor=16.0,
                yarn_original_len=16, yarn_beta_fast=32.0,
                yarn_beta_slow=1.0, attention_factor=1.2772588722239782,
            ),
            swa=RotaryConfig(num_heads=4, window=8, rope_theta=500000.0),
        )
    if family == "laguna":
        # all three block kinds; 6 (full) and 8 (window of 8 keys) query
        # heads of 16 over 2 key heads, a gate a head; the full layers' yarn
        # over the first half of a head; 4 experts held of 16, two a token
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            num_layers=5, num_heads=6, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rms_norm_eps=1e-6, family=family,
            num_experts=16, num_experts_per_tok=2, moe_intermediate_size=16,
            norm_topk_prob=True, router_aux_loss_coef=0.0, experts_held=4,
            shared_expert_size=16, shared_expert_gate=False,
            routed_scaling_factor=2.5,
            layer_kinds=("full+dense", "swa+moe", "swa+moe", "swa+moe",
                         "full+moe"),
            full=RotaryConfig(
                num_heads=6, rotary_dim=8, rope_theta=500000.0,
                yarn_factor=64.0, yarn_original_len=64, yarn_beta_fast=8.0,
                yarn_beta_slow=1.0, attention_factor=1.4158883083359672,
                head_gate=True,
            ),
            swa=RotaryConfig(num_heads=8, window=8, head_gate=True),
        )
    if family == "kimi_linear":
        # all three block kinds; two KDA heads of 16; latent attention of
        # two heads (8 + 4 | 8) over a rank of 16; 8 experts held of 16
        # sigmoid-routed ones, two a token, and a shared one
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            num_layers=4, num_heads=2, num_kv_heads=2, head_dim=12,
            max_position_embeddings=512, rms_norm_eps=1e-5, family=family,
            num_experts=16, num_experts_per_tok=2, moe_intermediate_size=16,
            norm_topk_prob=True, router_aux_loss_coef=0.0, experts_held=8,
            shared_expert_size=16, shared_expert_gate=False,
            router_act="sigmoid", routed_scaling_factor=2.446,
            layer_kinds=("kda+dense", "kda+moe", "mla+moe", "kda+moe"),
            kda=KDAConfig(num_heads=2, head_dim=16),
            mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                          qk_rope_head_dim=4, v_head_dim=8),
        )
    if family == "KeyeVL2":
        # a qwen3_moe block over the 8 keys an indexer of two heads picks;
        # 8 experts held of 16 routed over, two a token
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512, rope_theta=10000.0,
            rms_norm_eps=1e-6, qk_norm="head", family=family,
            num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
            norm_topk_prob=True, experts_held=8, mrope_sections=(4, 2, 2),
            dsa_topk=8, dsa_index_heads=2, dsa_index_head_dim=8,
        )
    if family == "olmoe":  # 8 experts of which 2, MHA, as the family is
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=32,
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
            max_position_embeddings=512, rope_theta=10000.0,
            rms_norm_eps=1e-5, qk_norm="projection", family=family,
            num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
            router_aux_loss_coef=0.01,
        )
    return ModelConfig(
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_position_embeddings=512,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        attention_bias=(family in ("qwen2", "qwen2_moe")),
        qk_norm=_QK_NORM.get(family),
        family=family,
        hidden_act="gelu_tanh" if family == "gemma" else "silu",
        norm_add_unit_offset=(family == "gemma"),
        scale_embeddings=(family == "gemma"),
        num_experts=4 if moe else 0,
        num_experts_per_tok=2,
        moe_intermediate_size=32 if moe else 0,
        norm_topk_prob=(family != "qwen2_moe"),
        shared_expert_size=48 if family == "qwen2_moe" else 0,
    )
