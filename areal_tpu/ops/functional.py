"""RL algorithm math: logprobs, PPO losses, GAE, normalization.

Role of reference areal/utils/functional.py + realhf/impl/model/utils/
ppo_functional.py, re-expressed in jnp with static shapes. All functions are
pure and jit-safe; masks replace the reference's dynamic filtering. The GAE
reverse scan replaces the CUDA `cugae` kernel (csrc/cugae/gae.cu) with a
`lax.scan` formulation that handles packed multi-sequence streams via
segment-boundary gating.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def gather_logprobs(
    logits,  # [..., T, V] array (fp32 recommended) or ChunkedLogits
    labels: jnp.ndarray,  # [..., T] int
    temperature: float = 1.0,
) -> jnp.ndarray:
    """Log p(labels) under temperature-scaled logits (reference
    utils/functional.py:29 `gather_logprobs`). A lazy ``ChunkedLogits``
    view dispatches to the memory-bounded chunked kernel — [T, V] is
    never materialized."""
    from areal_tpu.ops.chunked_head import ChunkedLogits, chunked_gather_logprobs

    if isinstance(logits, ChunkedLogits):
        return chunked_gather_logprobs(
            logits.hidden, logits.head, labels, temperature=temperature,
            vocab_axis=logits.vocab_axis,
        )
    if temperature != 1.0:
        logits = logits / temperature
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    ).squeeze(-1)
    return label_logits - logz


def gather_logprobs_entropy(
    logits,
    labels: jnp.ndarray,
    temperature: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(logprobs, entropy) in one pass (reference utils/functional.py:54)."""
    from areal_tpu.ops.chunked_head import ChunkedLogits, chunked_gather_logprobs

    if isinstance(logits, ChunkedLogits):
        return chunked_gather_logprobs(
            logits.hidden, logits.head, labels, temperature=temperature,
            with_entropy=True, vocab_axis=logits.vocab_axis,
        )
    if temperature != 1.0:
        logits = logits / temperature
    logp_full = jax.nn.log_softmax(logits, axis=-1)
    probs = jnp.exp(logp_full)
    entropy = -jnp.sum(probs * logp_full, axis=-1)
    logp = jnp.take_along_axis(logp_full, labels[..., None], axis=-1).squeeze(
        -1
    )
    return logp, entropy


def target_aligned_logprobs(
    logits, arrays: Dict, temperature: float = 1.0
) -> jnp.ndarray:
    """Logprobs aligned to the TARGET token: out[t] = log p(token_t | <t),
    0 at each sequence's first token and on padding. This matches the
    per-generated-token logprobs the rollout engine reports, so behavior /
    proximal / new logprobs line up index-for-index (reference
    ppo/actor.py compute_logp + utils/functional.py:29). ``arrays`` is a
    train engine's packed microbatch (``tokens``, ``segment_ids``)."""
    tokens = arrays["tokens"]
    seg = arrays["segment_ids"]
    logp_shift = gather_logprobs(
        logits[:, :-1], tokens[:, 1:], temperature=temperature
    )
    out = jnp.concatenate(
        [jnp.zeros_like(logp_shift[:, :1]), logp_shift], axis=1
    )
    prev_same = jnp.concatenate(
        [jnp.zeros_like(seg[:, :1], bool), seg[:, 1:] == seg[:, :-1]], axis=1
    ) & (seg > 0)
    return jnp.where(prev_same, out, 0.0)


def slot_aligned_logprobs(
    logits, arrays: Dict, temperature: float = 1.0
) -> jnp.ndarray:
    """Logprobs aligned to the SLOT, for a block-diffusion policy's rows
    (``utils/data.pack_batch_rows(bd=)``): out[i] = log p(the token slot i
    stands for | what slot i's stream shows), NO shift (a masked slot
    predicts its own token), at the slots where a token is scored (the
    stream of its own denoising state) and 0 elsewhere; the lazy view's head
    is run at those slots only. The host reads them
    back into per-token order (``utils/data.unpack_rows_per_token``), where
    they line up with the target-aligned arrays index for index."""
    from areal_tpu.ops.chunked_head import ChunkedLogits

    labels, scored = arrays["t_bd_labels"], arrays["t_bd_scored"] > 0
    if not isinstance(logits, ChunkedLogits):
        logp = gather_logprobs(logits, labels, temperature=temperature)
        return jnp.where(scored, logp, 0.0)
    # the head runs over the scored slots alone: a sequence's completion is
    # scored once and held at least twice (its clean stream, a noisy stream a
    # state), so they are at most half a row's slots: a static bound
    rows, t = labels.shape
    at = jax.vmap(
        lambda m: jnp.nonzero(m, size=t // 2, fill_value=0)[0]
    )(scored)
    on = jnp.arange(t // 2)[None, :] < scored.sum(-1, keepdims=True)
    logp = gather_logprobs(
        ChunkedLogits(
            jnp.take_along_axis(logits.hidden, at[..., None], axis=1),
            logits.head, logits.vocab_axis,
        ),
        jnp.take_along_axis(labels, at, axis=1), temperature=temperature,
    )
    return jnp.zeros(labels.shape, logp.dtype).at[
        jnp.arange(rows)[:, None], at
    ].add(jnp.where(on, logp, 0.0))


def token_logprobs(
    logits, arrays: Dict, temperature: float = 1.0
) -> jnp.ndarray:
    """The logprob of each of a packed microbatch's tokens where the row
    holds it to be scored: slot-aligned on a block-diffusion policy's rows
    (they carry ``t_bd_labels``), target-aligned on every other. Which is
    decided as the program is traced: a causal model's program is
    ``target_aligned_logprobs`` and nothing else."""
    if "t_bd_labels" in arrays:
        return slot_aligned_logprobs(logits, arrays, temperature)
    return target_aligned_logprobs(logits, arrays, temperature)


def _two_on(a: jnp.ndarray) -> jnp.ndarray:
    """``a`` [B, T] two slots early: position i holds a[i + 2], zeros at a
    row's last two."""
    return jnp.pad(a[:, 2:], ((0, 0), (0, 2)))


def draft_aligned_logprobs(logits, arrays: Dict):
    """The draft module's logprobs aligned to the SLOT: out[i] = log
    p_draft(token_{i+2} | tokens <= i + 1), and whether slot i has such a
    target, a token two on in its OWN sequence (a sequence's last two slots
    and padding have none and read 0). ``logits``: the module's
    (``models/transformer.draft_forward``); ``arrays`` a train engine's
    packed microbatch."""
    tokens, seg = arrays["tokens"], arrays["segment_ids"]
    has_target = (_two_on(seg) == seg) & (seg > 0)
    logp = gather_logprobs(logits, _two_on(tokens))
    return jnp.where(has_target, logp, 0.0), has_target


def draft_loss(logits, arrays: Dict):
    """(the mean cross-entropy of the draft module's ``logits`` against the
    token two slots on, over the slots whose target is of the slot's own
    sequence and in the loss mask ``t_loss_mask``, target-aligned as the
    trainer's: a response token is a target once; how many slots that was,
    int32)."""
    logp, on = draft_aligned_logprobs(logits, arrays)
    if "t_loss_mask" in arrays:
        on = on & (_two_on(arrays["t_loss_mask"]) > 0)
    n = on.sum()
    loss = -jnp.where(on, logp, 0.0).sum() / jnp.maximum(n, 1)
    return loss, n.astype(jnp.int32)


def masked_normalization(
    x: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    dim=None,
    unbiased: bool = False,
    eps: float = 1e-5,
    high_precision: bool = True,
    all_reduce: bool = True,  # kept for signature parity; pjit handles it
) -> jnp.ndarray:
    """Whiten x over masked entries (reference utils/functional.py:84).

    Under pjit the mean/std reductions become global automatically when x is
    sharded — no explicit dist.all_reduce as in the reference.
    """
    dtype = jnp.float64 if high_precision and jax.config.jax_enable_x64 else jnp.float32
    x = x.astype(dtype)
    if mask is None:
        factor = jnp.array(x.size, dtype)
        mask = jnp.ones_like(x)
    else:
        mask = mask.astype(dtype)
        factor = jnp.maximum(mask.sum(dim, keepdims=dim is not None), 1.0)
    x = x * mask
    mean = x.sum(dim, keepdims=dim is not None) / factor
    meansq = jnp.square(x).sum(dim, keepdims=dim is not None) / factor
    var = meansq - jnp.square(mean)
    if unbiased:
        var = var * factor / jnp.maximum(factor - 1, 1.0)
    return ((x - mean) * mask * jax.lax.rsqrt(var + eps)).astype(jnp.float32)


def ppo_actor_loss_fn(
    logprobs: jnp.ndarray,  # π_θ logprobs [T]
    old_logprobs: jnp.ndarray,  # behavior policy logprobs [T]
    advantages: jnp.ndarray,  # [T]
    eps_clip: float,
    loss_mask: jnp.ndarray,  # [T] bool/float
    c_clip: Optional[float] = None,
    proximal_logprobs: Optional[jnp.ndarray] = None,  # π_prox (decoupled PPO)
    behav_imp_weight_cap: Optional[float] = None,
    eps_clip_higher: Optional[float] = None,
) -> Tuple[jnp.ndarray, dict]:
    """Decoupled PPO-clip objective (reference utils/functional.py:124-188).

    With `proximal_logprobs` (the logprobs recomputed at the current weight
    version before the update), the ratio is taken against π_prox and the
    whole term is importance-weighted by exp(π_prox − π_behav), optionally
    capped (staleness control for async RL).
    """
    denorm_logprobs = (
        proximal_logprobs if proximal_logprobs is not None else old_logprobs
    )
    loss_mask = loss_mask.astype(jnp.float32)
    loss_mask_count = jnp.maximum(loss_mask.sum(), 1.0)
    ratio = jnp.exp(logprobs - denorm_logprobs)
    clipped_ratio = jnp.clip(
        ratio,
        1.0 - eps_clip,
        1.0 + (eps_clip_higher if eps_clip_higher is not None else eps_clip),
    )
    pg_loss1 = -advantages * ratio
    pg_loss2 = -advantages * clipped_ratio
    clip_mask = pg_loss1 < pg_loss2
    pg_loss = jnp.maximum(pg_loss1, pg_loss2)
    if c_clip is not None:
        assert c_clip > 1.0, c_clip
        pg_loss3 = jnp.sign(advantages) * c_clip * advantages
        # mask marks tokens where the min() actually replaced the value
        dual_clip_mask = (pg_loss3 < pg_loss) & (advantages < 0)
        pg_loss = jnp.minimum(pg_loss, pg_loss3) * (advantages < 0) + pg_loss * (
            advantages >= 0
        )
    else:
        dual_clip_mask = jnp.zeros_like(clip_mask)
    if proximal_logprobs is not None:
        behav_kl = proximal_logprobs - old_logprobs
        behav_imp_weight = jnp.exp(behav_kl)
        if behav_imp_weight_cap is not None:
            behav_mask = (behav_imp_weight <= behav_imp_weight_cap) & (
                loss_mask > 0
            )
        else:
            behav_mask = loss_mask > 0
        behav_kl = jnp.where(behav_mask, behav_kl, 0.0)
        behav_imp_weight = jnp.where(behav_mask, behav_imp_weight, 0.0)
        pg_loss = pg_loss * behav_imp_weight
        loss_mask = loss_mask * behav_mask
        loss_mask_count = jnp.maximum(loss_mask.sum(), 1.0)
    else:
        behav_kl = jnp.zeros_like(pg_loss)
        behav_imp_weight = loss_mask
    loss = jnp.sum(pg_loss * loss_mask) / loss_mask_count
    stats = dict(
        loss=loss,
        importance_weight=jnp.sum(ratio * loss_mask) / loss_mask_count,
        approx_kl=jnp.sum((denorm_logprobs - logprobs) * loss_mask)
        / loss_mask_count,
        clip_ratio=jnp.sum(clip_mask * loss_mask) / loss_mask_count,
        dual_clip_ratio=jnp.sum(dual_clip_mask * loss_mask) / loss_mask_count,
        behave_imp_weight=jnp.sum(behav_imp_weight * loss_mask)
        / loss_mask_count,
        behave_approx_kl=jnp.sum(behav_kl * loss_mask) / loss_mask_count,
    )
    return loss, stats


def gae_packed(
    rewards: jnp.ndarray,  # [T] per-token rewards (terminal reward at seq end)
    values: jnp.ndarray,  # [T] value estimates (zeros for GRPO)
    segment_ids: jnp.ndarray,  # [T] 1-based, 0 = padding
    gamma: float,
    lam: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GAE over a packed multi-sequence stream; returns (advantages, returns).

    TPU-native replacement for the reference CUDA kernel
    (csrc/cugae/gae.cu `gae_kernel_1d_nolp_misalign`, dispatched at
    realhf/impl/model/utils/ppo_functional.py:326-393): a reverse
    `lax.scan` with the carry zeroed at segment boundaries. Bootstrap value
    is 0 at each sequence end (RL episodes terminate).
    """
    t = rewards.shape[0]
    seg = segment_ids
    # next-token same-sequence indicator (False at last token of each seq)
    nxt = jnp.concatenate([seg[1:] == seg[:-1], jnp.array([False])]) & (seg > 0)
    next_values = jnp.concatenate([values[1:], jnp.zeros((1,), values.dtype)])
    next_values = jnp.where(nxt, next_values, 0.0)
    deltas = rewards + gamma * next_values - values

    def body(carry, xs):
        delta, cont = xs
        adv = delta + gamma * lam * cont * carry
        return adv, adv

    # scan in reverse over time
    _, advs_rev = jax.lax.scan(
        body,
        jnp.array(0.0, jnp.float32),
        (deltas[::-1].astype(jnp.float32), nxt[::-1].astype(jnp.float32)),
    )
    advantages = advs_rev[::-1]
    returns = advantages + values
    valid = seg > 0
    return (
        jnp.where(valid, advantages, 0.0),
        jnp.where(valid, returns, 0.0),
    )


def gae_padded(
    rewards: jnp.ndarray,  # [B, L] dense per-token rewards
    values: jnp.ndarray,  # [B, L]
    attention_mask: jnp.ndarray,  # [B, L] valid-token mask
    gamma: float,
    lam: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized GAE over padded [B, L] via a reverse time scan.

    The recursion runs over ALL valid tokens (attention_mask) so a terminal
    reward propagates across loss-masked gaps (multi-turn rollouts where
    tool/user tokens are excluded from the loss but are part of the episode);
    loss masking is the loss function's job, not GAE's.
    """
    b, L = rewards.shape
    valid = attention_mask > 0
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
    )
    nxt_valid = jnp.concatenate(
        [valid[:, 1:], jnp.zeros_like(valid[:, :1])], axis=1
    )
    deltas = rewards + gamma * next_values * nxt_valid - values

    def body(carry, xs):
        delta, cont = xs
        adv = delta + gamma * lam * cont * carry
        return adv, adv

    _, advs_rev = jax.lax.scan(
        body,
        jnp.zeros((b,), jnp.float32),
        (deltas.T[::-1].astype(jnp.float32), nxt_valid.T[::-1].astype(jnp.float32)),
    )
    adv = advs_rev[::-1].T
    returns = adv + values
    return adv * valid, returns * valid


def grpo_group_norm_rewards(
    rewards: jnp.ndarray,  # [B] scalar episode rewards
    group_size: int,
    eps: float = 1e-9,
    norm_std: bool = True,
) -> jnp.ndarray:
    """GRPO group-mean(/std) reward normalization (reference
    ppo/actor.py:94-98). rewards is ordered group-major: [n_groups*G]."""
    g = rewards.reshape(-1, group_size)
    mean = g.mean(axis=1, keepdims=True)
    out = g - mean
    if norm_std:
        std = g.std(axis=1, keepdims=True)
        out = out / (std + eps)
    return out.reshape(-1)


def dynamic_sampling_mask(
    rewards: jnp.ndarray, group_size: int, eps: float = 1e-6
) -> jnp.ndarray:
    """DAPO dynamic sampling (reference utils/functional.py:191): mask out
    groups whose rewards are all identical (no learning signal). Returns a
    [B] bool keep-mask (the reference drops rows; we mask — static shapes)."""
    g = rewards.reshape(-1, group_size)
    spread = g.max(axis=1) - g.min(axis=1)
    keep = spread > eps
    return jnp.repeat(keep, group_size)


def reward_overlong_penalty(
    seq_lens: jnp.ndarray,  # [B] generated lengths
    rewards: jnp.ndarray,  # [B]
    overlong_tokens: int,
    overlong_penalty_factor: float,
    max_new_tokens: int,
) -> jnp.ndarray:
    """DAPO overlong penalty (reference utils/functional.py:237): linearly
    penalize completions in the last `overlong_tokens` before the cap."""
    expected_len = max_new_tokens - overlong_tokens
    exceed = seq_lens - expected_len
    penalty = jnp.clip(
        exceed / max(overlong_tokens, 1), 0.0, 1.0
    ) * overlong_penalty_factor
    return rewards - penalty


# ---------------------------------------------------------------------------
# KL controllers (reference realhf/impl/model/utils/ppo_functional.py:14-49)
# ---------------------------------------------------------------------------
class FixedKLController:
    """Constant KL coefficient."""

    def __init__(self, kl_coef: float):
        self.value = float(kl_coef)

    def update(self, current_kl: float, n_steps: int) -> None:
        pass


class AdaptiveKLController:
    """Adaptive KL coefficient (Ziegler et al.): the coefficient drifts so
    the observed per-token KL tracks ``target`` over ``horizon`` tokens."""

    def __init__(self, init_kl_coef: float, target: float, horizon: float):
        self.value = float(init_kl_coef)
        self.target = float(target)
        self.horizon = float(horizon)

    def update(self, current_kl: float, n_steps: int) -> None:
        error = min(max(current_kl / self.target - 1.0, -0.2), 0.2)
        # floor the multiplier so a large n_steps (e.g. a caller passing
        # token counts) can never flip the coefficient's sign
        mult = max(1.0 + error * n_steps / self.horizon, 0.1)
        self.value *= mult
