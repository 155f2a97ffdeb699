"""PPO/GRPO actor: the algorithm layer over any TrainEngine.

Role of reference areal/engine/ppo/actor.py (`PPOActor`, `FSDPPPOActor`,
`grpo_loss_fn`): reward shaping → advantage estimation → minibatched
decoupled-PPO updates. Device math (GAE, whitening, the loss) is jnp and
jit-traced inside the engine; host-side orchestration (minibatch splitting,
dynamic sampling) is numpy on padded batches.

Data layout (padded Batch, all aligned to TARGET token position t =
"token t given prefix < t"):
- input_ids [B, L], attention_mask [B, L]
- loss_mask [B, L]: 1 on completion tokens (positions to train)
- logprobs  [B, L]: behavior-policy logprobs of token t (0 on prompt)
- versions  [B, L]: weight version that generated token t (-1 prompt)
- rewards   [B]: scalar episode rewards
"""

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.cli_args import PPOActorConfig
from areal_tpu.api.engine_api import TrainEngine
from areal_tpu.ops import functional as F
from areal_tpu.utils import stats_tracker, tracing
from areal_tpu.utils.data import Batch, batch_select, batch_size


class PPOActor:
    """Algorithm wrapper (reference ppo/actor.py:24)."""

    def __init__(self, config: PPOActorConfig, engine: TrainEngine):
        self.config = config
        self.engine = engine
        self.reward_bias = config.reward_bias
        self.reward_scaling = config.reward_scaling
        self.reward_clip = config.reward_clip
        self.group_size = config.group_size
        # KL coefficient: fixed, or adapted toward kl_target (reference
        # ppo_functional.py:14-49 controllers)
        if getattr(config, "kl_adaptive", False):
            self.kl_controller = F.AdaptiveKLController(
                config.kl_ctl, config.kl_target, config.kl_horizon
            )
        else:
            self.kl_controller = F.FixedKLController(config.kl_ctl)

    # ------------------------------------------------------------------
    def compute_logp(self, data: Batch, temperature: Optional[float] = None) -> np.ndarray:
        """Recompute logprobs of the batch tokens under current weights
        (reference ppo/actor.py:48 `compute_logp`)."""
        return self.engine.forward(data, post_hook=self._logp_hook(temperature))

    def _logp_hook(self, temperature: Optional[float] = None):
        temp = temperature if temperature is not None else self.config.temperature
        # cache the hook per temperature: the engine keys its jitted program
        # on hook identity, so a fresh closure per call would recompile
        if not hasattr(self, "_logp_hooks"):
            self._logp_hooks = {}
        if temp not in self._logp_hooks:

            def hook(logits, arrays, _temp=temp):
                # slot-aligned on a block-diffusion policy's rows; the
                # engine reads scored slots back into per-token order
                return F.token_logprobs(logits, arrays, temperature=_temp)

            self._logp_hooks[temp] = hook
        return self._logp_hooks[temp]

    # ------------------------------------------------------------------
    def compute_advantages(self, data: Batch) -> Batch:
        """Reward shaping + GAE + advantage normalization (reference
        ppo/actor.py:67-159). Returns `data` with added keys: advantages,
        kl_rewards, tot_rewards (all [B, L] target-aligned)."""
        with tracing.span(
            "train.compute_advantages", step=self._engine_step()
        ):
            return self._compute_advantages(data)

    def _engine_step(self) -> int:
        # the engine's optimizer-step count stamps the trainer's spans;
        # an engine without one (a remote stub) reads 0
        return int(getattr(self.engine, "step_count", 0))

    def _compute_advantages(self, data: Batch) -> Batch:
        cfg = self.config
        mask = np.asarray(data["attention_mask"]).astype(bool)
        loss_mask = np.asarray(data["loss_mask"]).astype(np.float32)
        bsz, L = mask.shape
        reward_score = np.asarray(data["rewards"]).astype(np.float32)
        reward_score = (reward_score + self.reward_bias) * self.reward_scaling
        reward_score = np.clip(
            reward_score, -self.reward_clip, self.reward_clip
        )
        if cfg.group_reward_norm and self.group_size > 1:
            reward_score = np.asarray(
                F.grpo_group_norm_rewards(
                    jnp.asarray(reward_score), self.group_size
                )
            )
        if cfg.overlong_reward_penalty:
            gen_lens = loss_mask.sum(1)
            reward_score = np.asarray(
                F.reward_overlong_penalty(
                    jnp.asarray(gen_lens), jnp.asarray(reward_score),
                    cfg.overlong_tokens, cfg.overlong_penalty_factor,
                    cfg.max_new_tokens,
                )
            )

        logprobs = np.asarray(
            data.get("prox_logp", data["logprobs"])
        ).astype(np.float32)
        ref_logp = data.get("ref_logp")
        # dense KL reward on completion positions
        kl_ctl = self.kl_controller.value
        if ref_logp is not None and kl_ctl != 0.0:
            kl_est = (
                logprobs - np.asarray(ref_logp, np.float32)
            ) * loss_mask
            kl_rewards = -kl_ctl * kl_est
            n_tok = max(1.0, float(loss_mask.sum()))
            # n_steps is the SEQUENCE count (reference
            # realhf/impl/model/interface/ppo_interface.py:176), not the
            # token count — with kl_horizon ~1e4 a token count would swing
            # the adaptive coefficient by 5x+ per update
            self.kl_controller.update(float(kl_est.sum() / n_tok), bsz)
        else:
            kl_rewards = np.zeros_like(loss_mask)
        tok_rewards = kl_rewards.copy()
        # terminal scalar reward at the last completion token
        lens = mask.sum(1).astype(np.int64)
        last_idx = np.maximum(lens - 1, 0)
        tok_rewards[np.arange(bsz), last_idx] += reward_score

        values = np.asarray(
            data.get("values", np.zeros_like(loss_mask))
        ).astype(np.float32)
        adv, returns = _gae_jit(
            jnp.asarray(tok_rewards), jnp.asarray(values),
            jnp.asarray(mask.astype(np.float32)), cfg.gamma, cfg.lam,
        )
        adv = np.asarray(adv)
        # returns feed the critic's clipped value loss (PPOCritic)
        data["returns"] = np.asarray(returns)
        data["values"] = values
        an = cfg.adv_norm
        if an is not None and (an.mean_level != "none" or an.std_level != "none"):
            adv = _adv_normalize(adv, loss_mask, an, self.group_size)
        data["advantages"] = adv
        data["kl_rewards"] = kl_rewards
        data["tot_rewards"] = tok_rewards
        stats_tracker.scalar(
            task_reward=float(reward_score.mean()),
            kl_reward=float(kl_rewards.sum(1).mean()),
            advantage=float((adv * loss_mask).sum() / max(loss_mask.sum(), 1)),
        )
        self._record_staleness(data, loss_mask)
        return data

    def _record_staleness(self, data: Batch, loss_mask: np.ndarray):
        """Consumed-batch staleness histogram: how many weight versions
        behind the trainer each token being trained on was generated
        (the paper's η in practice — the distribution async rollout
        actually delivered, not just the configured bound). Exported via
        stats_tracker so StatsLogger.commit persists it per step."""
        if "versions" not in data:
            return
        versions = np.asarray(data["versions"])
        on = (loss_mask > 0) & (versions >= 0)
        if not on.any():
            return
        lag = self.engine.get_version() - versions[on]
        hist = {
            f"staleness/lag{b}_frac": float((lag == b).mean())
            for b in range(4)
        }
        hist["staleness/lag_ge4_frac"] = float((lag >= 4).mean())
        stats_tracker.scalar(
            **hist,
            **{
                "staleness/lag_mean": float(lag.mean()),
                "staleness/lag_max": float(lag.max()),
                "staleness/lag_min": float(lag.min()),
                "staleness/n_tokens": float(lag.size),
            },
        )

    # ------------------------------------------------------------------
    def ppo_update(self, data: Batch) -> List[Dict[str, float]]:
        """Minibatched decoupled-PPO update (reference ppo/actor.py:161).

        The proximal policy is the weights the update starts from. Where the
        caller hands no ``prox_logp`` in and ``recompute_logprob`` is on, the
        update finds its logprobs itself: a forward pass over the sequences
        of minibatches 2..n, before the first optimizer step. The FIRST
        minibatch needs none: no step lies between that pass and its own
        ``grad_step``, so its proximal logprobs are what its own forward
        forms (same weights, same tokens), and it takes them from there
        (``stop_gradient(newlogp)``; its ratio is 1 to the bit and not to
        the rounding of two differently packed forwards). A quarter of the
        recipe's pass is not run; ``ppo_n_minibatches: 1`` runs none. A
        caller's own ``prox_logp`` is used as it is, for every minibatch.
        The span and the stats plane (``spmd/prox_own_seqs``,
        ``prox_own_tokens``, ``prox_recomputed_seqs``,
        ``prox_recomputed_tokens``) say how many sequences and loss tokens
        went each way: 1 : 3 in sequences on a step of four minibatches."""
        with tracing.span("train.ppo_update", step=self._engine_step()) as sp:
            return self._ppo_update(data, sp)

    def _ppo_update(self, data: Batch, sp) -> List[Dict[str, float]]:
        """Dynamic sampling and the minibatch split first (the one
        ``np.random.permutation`` draw), then the proximal logprobs of
        minibatches 2..n, then the minibatches: what the first one needs of
        the proximal policy its own forward gives it."""
        cfg = self.config
        recompute = cfg.recompute_logprob and "prox_logp" not in data
        if cfg.dynamic_sampling and self.group_size > 1:
            keep = np.asarray(
                F.dynamic_sampling_mask(
                    jnp.asarray(np.asarray(data["rewards"], np.float32)),
                    self.group_size,
                )
            )
            if keep.any() and not keep.all():
                data = batch_select(data, np.nonzero(keep)[0])
        bsz = batch_size(data)
        n_mbs = min(cfg.ppo_n_minibatches, max(bsz, 1))
        perm = np.random.permutation(bsz)
        groups = [g for g in np.array_split(perm, n_mbs) if len(g)]
        if recompute and groups:
            data = self._with_prox_logp(data, groups, sp)
        # a generator: the engine draws minibatch k + 1 (its selection too)
        # while the device still works on minibatch k, and returns when the
        # last one's stats are on the host
        return self.engine.train_batches(
            (batch_select(data, g) for g in groups),
            self._loss_fn, _ppo_loss_weight_fn,
        )

    def _with_prox_logp(self, data: Batch, groups, sp) -> Batch:
        """``data`` with the proximal logprobs this update finds itself:
        ``prox_logp`` recomputed under the current weights for the sequences
        of ``groups[1:]`` and 0 for the first minibatch's, and ``prox_own``,
        true on the first minibatch's tokens, which tells the loss to take
        its own forward's there. Every minibatch carries both arrays, so the
        choice is data and one ``grad_step`` program a row length serves the
        whole step. The pass keeps the whole batch's row length and packing
        window (``forward(rows=)``): three quarters of a batch do not ask
        for a ``logp_forward`` the whole batch would not have."""
        loss_mask = np.asarray(data["loss_mask"])
        own = np.zeros(batch_size(data), bool)
        own[groups[0]] = True
        if own.all():
            prox_logp = np.zeros(loss_mask.shape, np.float32)
        else:
            prox_logp = self.engine.forward(
                data, post_hook=self._logp_hook(), rows=np.nonzero(~own)[0]
            ) * loss_mask.astype(np.float32)
        tokens = (loss_mask > 0).sum(1)
        counts = {
            "prox_own_seqs": int(own.sum()),
            "prox_own_tokens": int(tokens[own].sum()),
            "prox_recomputed_seqs": int((~own).sum()),
            "prox_recomputed_tokens": int(tokens[~own].sum()),
        }
        sp.set(**counts)
        stats_tracker.counter(**{f"spmd/{k}": n for k, n in counts.items()})
        return {
            **data, "prox_logp": prox_logp,
            "prox_own": np.broadcast_to(own[:, None], loss_mask.shape),
        }

    @property
    def _loss_fn(self):
        if not hasattr(self, "_cached_loss_fn"):
            cfg = self.config

            def grpo_loss_fn(logits, arrays):
                """reference ppo/actor.py:292 `grpo_loss_fn`."""
                newlogp = F.token_logprobs(
                    logits, arrays, temperature=cfg.temperature
                )
                old_logp = arrays["t_logprobs"].astype(jnp.float32)
                prox_logp = (
                    arrays["t_prox_logp"].astype(jnp.float32)
                    if "t_prox_logp" in arrays
                    else None
                )
                if "t_prox_own" in arrays:
                    # a step's first minibatch: no update lies between the
                    # proximal policy and this forward
                    prox_logp = jnp.where(
                        arrays["t_prox_own"], jax.lax.stop_gradient(newlogp),
                        prox_logp,
                    )
                if not cfg.use_decoupled_loss and prox_logp is not None:
                    # plain PPO against recomputed logp
                    old_logp, prox_logp = prox_logp, None
                loss_mask = arrays["t_loss_mask"] > 0
                loss, stats = F.ppo_actor_loss_fn(
                    logprobs=newlogp,
                    old_logprobs=old_logp,
                    advantages=arrays["t_advantages"].astype(jnp.float32),
                    eps_clip=cfg.eps_clip,
                    loss_mask=loss_mask,
                    c_clip=cfg.c_clip,
                    proximal_logprobs=prox_logp,
                    behav_imp_weight_cap=cfg.behav_imp_weight_cap,
                    eps_clip_higher=cfg.eps_clip_higher,
                )
                return loss, stats

            self._cached_loss_fn = grpo_loss_fn
        return self._cached_loss_fn


def _ppo_loss_weight_fn(arrays) -> jnp.ndarray:
    return jnp.maximum(
        (arrays["t_loss_mask"] > 0).astype(jnp.float32).sum(), 1.0
    )


_gae_jit = jax.jit(F.gae_padded, static_argnums=(3, 4))


def _adv_normalize(adv, loss_mask, an, group_size: int) -> np.ndarray:
    """Batch/group-level advantage whitening (reference ppo/actor.py:370
    `AdvNorm`)."""
    m = loss_mask.astype(np.float64)
    x = adv.astype(np.float64)

    def _mean(vals, msk, axis=None):
        return (vals * msk).sum(axis) / np.maximum(msk.sum(axis), 1.0)

    if an.mean_level == "batch":
        mean = _mean(x, m)
    elif an.mean_level == "group":
        g = group_size
        xm = _mean(
            x.reshape(-1, g, x.shape[1]), m.reshape(-1, g, x.shape[1]),
            axis=(1, 2),
        )[:, None, None]
        mean = np.broadcast_to(xm, (x.shape[0] // g, g, x.shape[1])).reshape(x.shape)
    else:
        mean = 0.0
    centered = x - mean
    if an.std_level == "batch":
        std = np.sqrt(_mean(centered**2, m)) + 1e-5
    elif an.std_level == "group":
        g = group_size
        sm = np.sqrt(
            _mean(
                (centered**2).reshape(-1, g, x.shape[1]),
                m.reshape(-1, g, x.shape[1]), axis=(1, 2),
            )
        )[:, None, None] + 1e-5
        std = np.broadcast_to(sm, (x.shape[0] // g, g, x.shape[1])).reshape(x.shape)
    else:
        std = 1.0
    return ((centered / std) * m).astype(np.float32)
