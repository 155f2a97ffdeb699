"""Shared packed-arrays forward entry for train / eval / logp passes.

One function turns the engine's packed device arrays into a model call,
composing the vision tower when multimodal arrays are present (reference:
areal/engine/base_hf_engine.py builds HF VLM inputs — pixel_values,
image_grid_thw, mrope position ids — before every forward; here the
bookkeeping was already done on host at pack time and this helper only
wires static-shaped gathers together, inside the same jit as the LM so
gradients flow through the tower).
"""

from typing import Any, Optional

import jax.numpy as jnp

from areal_tpu.models import transformer
from areal_tpu.models.config import ModelConfig

# packed vision segment ids are made row-unique as seg + slot * stride;
# bounds images per sequence
IMG_SLOT_STRIDE = 512


def packed_forward(
    params,
    cfg: ModelConfig,
    arrays: dict,
    remat: bool = True,
    attend_fn: Optional[Any] = None,
    moe_fn: Optional[Any] = None,
    return_stats: bool = False,
    return_hidden: bool = False,
    kda_fn: Optional[Any] = None,
    act_sharding: Optional[Any] = None,
    ssm_fn: Optional[Any] = None,
):
    """``transformer.apply`` over engine-packed arrays (tokens /
    segment_ids / positions / t_* / s_*), with the vision tower spliced in
    when the batch carries pixels."""
    kwargs = {}
    positions = arrays["positions"]
    if cfg.vision is not None and "s_pixel_values" in arrays:
        from areal_tpu.models import vision as vision_lib

        pix = arrays["s_pixel_values"]  # [R, S, P, patch_dim]
        r, s_, p, dp = pix.shape
        seg = arrays["s_vis_seg"].astype(jnp.int32)
        slot = jnp.arange(s_, dtype=jnp.int32)[None, :, None]
        seg_u = jnp.where(seg > 0, seg + slot * IMG_SLOT_STRIDE, 0)
        embeds = vision_lib.vision_apply(
            params["vision"],
            cfg.vision,
            pix.reshape(r, s_ * p, dp),
            seg_u.reshape(r, s_ * p),
            arrays["s_vis_pos_h"].astype(jnp.int32).reshape(r, s_ * p),
            arrays["s_vis_pos_w"].astype(jnp.int32).reshape(r, s_ * p),
            remat=remat,
        )  # [R, S*Pm, D]
        pm = p // cfg.vision.merge_factor
        # per-token ordinal within its own sequence -> index into the
        # row-flattened merged embeds: slot * Pm + ordinal
        ordinal = arrays["t_mm_index"].astype(jnp.int32)
        slot_of_tok = arrays["segment_ids"].astype(jnp.int32) - 1
        kwargs["mm_embeds"] = embeds
        kwargs["mm_index"] = jnp.where(
            ordinal >= 0, slot_of_tok * pm + ordinal, -1
        )
        if "t_mrope_pos" in arrays:
            positions = arrays["t_mrope_pos"].astype(jnp.int32)
    if "t_bd_stream" in arrays:  # a block-diffusion policy's rows
        kwargs["bd"] = (arrays["t_bd_stream"], arrays["t_bd_scored"])
    if ssm_fn is not None:
        kwargs["ssm_fn"] = ssm_fn
    return transformer.apply(
        params,
        cfg,
        arrays["tokens"],
        arrays["segment_ids"],
        positions,
        remat=remat,
        attend_fn=attend_fn,
        moe_fn=moe_fn,
        kda_fn=kda_fn,
        return_stats=return_stats,
        return_hidden=return_hidden,
        act_sharding=act_sharding,
        **kwargs,
    )
