"""The train engine's device programs, built in one place from values.

``grad_step``, ``logp_forward``, ``eval_step`` and ``apply_step`` are the
programs ``SPMDTrainEngine`` dispatches (a profile names them by these
function names). Each builder takes what its program is made of as plain
values and returns the jitted program, donation and output shardings
included, so whoever lowers one (the engine on its arrays,
``parallel/feasibility.py`` on abstract shapes over a virtual mesh) lowers
the same computation. The three forward programs share one inner function
(cast the parameters to the compute dtype, run the model over the packed
arrays) and differ only in what follows it.

What a builder does NOT decide: which attention runs (the caller hands in
``attend_fn``, from ``ops.ring_attention.attention_fn`` for a resolved
name), and how a microbatch is split, packed and fetched (the engine's host
path).
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.models.config import ModelConfig
from areal_tpu.models.forward import packed_forward
from areal_tpu.models.transformer import draft_forward, param_logical_axes
from areal_tpu.ops import functional
from areal_tpu.ops import kda as kda_ops
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import ssm_scan as ssm_ops
from areal_tpu.parallel import sharding as sharding_lib
from areal_tpu.utils import tracing


def _forward_fn(
    model_config: ModelConfig, mesh: Mesh, compute_dtype,
    attend_fn: Optional[Callable], remat: bool, stats: bool = False,
):
    """``forward(params, arrays)``: the model over one packed microbatch,
    giving the lazy ``ChunkedLogits`` view (a critic's value head: real
    values); when ``stats`` (the gradient pass), ``(logits, the stack's
    LayerStats, draft)``, ``draft`` what the model's draft module gives on
    the same rows where it trains one (``transformer.draft_forward`` over
    the stack's normed output, which the lazy view holds), else None."""
    mc = model_config
    moe = None
    # one layer's sparse leaves as the mesh's rules table shards them (the
    # scanned layer axis gone): what a chip of the mesh form holds. The
    # sparse kinds of a stack of block kinds hold the same leaves
    axes = param_logical_axes(mc)["layers"]
    if mc.layer_kinds:
        axes = next((v for k, v in axes.items() if k.endswith("+moe")), None)
    if mc.is_moe and axes is not None:
        moe = moe_ops.make_sharded_moe_ffn(mc, mesh, {
            k: sharding_lib.logical_to_spec(axes[k][1:], mesh=mesh)
            for k in moe_ops.sparse_leaves(mc)
        })
    kda = kda_ops.make_kda_scan(mesh) if mc.kda or mc.gdn else None
    ssm = ssm_ops.make_ssm_scan(mesh) if mc.ssm else None
    # [B, T, D] activation constraint: rows over (data, fsdp), tokens over
    # seq. Pinning this stops GSPMD from propagating the embedding table's
    # column sharding onto the batch, which replicates every layer
    # activation across fsdp (a 7B/16-device lowering then allocated 81 GB
    # a device of layer temps)
    act_sh = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))

    def forward(params, arrays):
        with tracing.device_scope("param_cast"):
            cparams = jax.tree_util.tree_map(
                lambda p: p.astype(compute_dtype), params
            )
        out = packed_forward(
            cparams, mc, arrays, remat=remat, attend_fn=attend_fn,
            moe_fn=moe, kda_fn=kda, return_stats=stats, return_hidden=True,
            act_sharding=act_sh, ssm_fn=ssm,
        )
        if not stats:
            return out
        logits, layer_stats = out
        draft = None
        if mc.trains_draft:
            draft = draft_forward(
                cparams, mc, logits.hidden, arrays["tokens"],
                arrays["segment_ids"], arrays["positions"], remat=remat,
                attend_fn=attend_fn, moe_fn=moe, kda_fn=kda,
                act_sharding=act_sh,
            )
        return logits, layer_stats, draft

    return forward


def build_grad_step(
    model_config: ModelConfig, mesh: Mesh, compute_dtype,
    attend_fn: Optional[Callable], remat: bool,
    loss_fn: Callable, loss_weight_fn: Callable,
):
    """``grad_step(params, grad_accum, arrays) -> (grad_accum', loss, stats,
    weight)``: one microbatch's weighted loss gradient added to the float32
    accumulator, which is donated. A model that trains its draft module
    (``ModelConfig.trains_draft``) runs it here and only here, and the loss
    gains ``mtp_loss_coef`` times the module's own."""
    mc = model_config
    forward = _forward_fn(
        mc, mesh, compute_dtype, attend_fn, remat, stats=True
    )

    def fwd_loss(params, arrays):
        logits, layer_stats, draft = forward(params, arrays)
        with tracing.device_scope("loss"):
            loss, stats = loss_fn(logits, arrays)
            # what the model's mechanisms count (``transformer.MECHANISMS``):
            # the host sums them, where every other stat is a weighted mean
            stats = dict(stats, **layer_stats.counts)
            if mc.is_moe:
                if mc.router_aux_loss_coef:
                    loss = loss + (
                        mc.router_aux_loss_coef * layer_stats.aux_loss
                    )
                stats["router_aux_loss"] = layer_stats.aux_loss
            if draft is not None:
                draft_logits, counts = draft
                mtp_loss, stats["mtp_tokens"] = functional.draft_loss(
                    draft_logits, arrays
                )
                loss = loss + mc.mtp_loss_coef * mtp_loss
                stats["mtp_loss"] = mtp_loss
                # the module's block is one more sparse layer of the step
                if "moe_assignments" in counts:
                    stats["mtp_assignments"] = counts["moe_assignments"]
                for k, v in counts.items():
                    stats[k] = stats[k] + v
            w = loss_weight_fn(arrays).astype(jnp.float32)
            return loss * w, (loss, stats, w)

    def grad_step(params, grad_accum, arrays):
        grads, (loss, stats, w) = jax.grad(fwd_loss, has_aux=True)(
            params, arrays
        )
        with tracing.device_scope("grad_add"):
            new_accum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grad_accum, grads
            )
        return new_accum, loss, stats, w

    return jax.jit(grad_step, donate_argnums=(1,))


def build_logp_forward(
    model_config: ModelConfig, mesh: Mesh, compute_dtype,
    attend_fn: Optional[Callable], hook: Callable,
):
    """``logp_forward(params, arrays) -> hook(logits, arrays)``, a [R, T]
    per-token array (the recomputed logprobs by default)."""
    forward = _forward_fn(
        model_config, mesh, compute_dtype, attend_fn, remat=False
    )

    def logp_forward(params, arrays):
        logits = forward(params, arrays)
        with tracing.device_scope("loss"):
            return hook(logits, arrays)

    # replicated output: under multi-process the per-token result must be
    # fully addressable for the host np.asarray fetch
    return jax.jit(
        logp_forward, out_shardings=sharding_lib.replicated(mesh)
    )


def build_eval_step(
    model_config: ModelConfig, mesh: Mesh, compute_dtype,
    attend_fn: Optional[Callable],
    loss_fn: Callable, loss_weight_fn: Callable,
):
    """``eval_step(params, arrays) -> (loss, stats, weight)``."""
    forward = _forward_fn(
        model_config, mesh, compute_dtype, attend_fn, remat=False
    )

    def eval_step(params, arrays):
        logits = forward(params, arrays)
        loss, stats = loss_fn(logits, arrays)
        return loss, stats, loss_weight_fn(arrays).astype(jnp.float32)

    return jax.jit(eval_step)


def build_apply_step(optimizer: optax.GradientTransformation, accum_shardings):
    """``apply_step(params, opt_state, grad_accum, total_weight) ->
    (params', opt_state', grad_accum', grad_norm, ok)``: the accumulated
    gradient over the step's total weight through the optimizer; the first
    three are donated. A non-finite gradient norm leaves params and state as
    they were (``ok`` false). ``grad_accum'`` is the accumulator zeroed, in
    the donated one's buffers: the next minibatch's ``grad_step`` takes it,
    so a step holds ONE accumulator through its minibatches and no program
    runs between two of them to make one. ``accum_shardings`` is its layout
    (the parameters'), stated: zeros depend on no input, so the compiler
    would have nothing to lay them out by, and an output laid out otherwise
    than the donated input cannot take its buffers."""

    def apply_step(params, opt_state, grad_accum, total_weight):
        with tracing.device_scope("optimizer"):
            grads = jax.tree_util.tree_map(
                lambda g: g / total_weight, grad_accum
            )
            grad_norm = optax.global_norm(grads)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            # keep the declared param dtype: f32 updates would silently
            # promote bf16 params (breaking donation every step)
            new_params = jax.tree_util.tree_map(
                lambda n, o: n.astype(o.dtype), new_params, params
            )
            # skip non-finite updates (reference base_hf_engine.py:474)
            ok = jnp.isfinite(grad_norm)
            new_params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new_params, params
            )
            new_opt = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new_opt, opt_state
            )
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, grad_accum)
            return new_params, new_opt, zeroed, grad_norm, ok

    return jax.jit(
        apply_step, donate_argnums=(0, 1, 2),
        out_shardings=(None, None, accum_shardings, None, None),
    )
