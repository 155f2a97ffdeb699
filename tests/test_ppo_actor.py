"""PPO actor end-to-end on the tiny model: advantages + update mechanics.

Mirrors reference ppo actor behavior: GRPO (no critic) advantage layout,
decoupled-loss update improving the objective, dynamic sampling filtering.
"""

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    AdvNormConfig,
    MicroBatchSpec,
    OptimizerConfig,
    ParallelismConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.ppo.actor import PPOActor
from areal_tpu.engine.spmd_engine import SPMDTrainEngine
from areal_tpu.models.config import tiny_config
from areal_tpu.utils import data as data_utils
from areal_tpu.utils import stats_tracker


@pytest.fixture(autouse=True)
def rows_of_128_tokens(monkeypatch):
    """The shortest rung at 128 tokens, for batches of at most 104: every
    case's rows are what they are on the ladder's own 2048 (one row a
    minibatch and a pass, all of the shortest rung), and no case compiles
    and runs 2048-token programs of its own (424 s of the suite's clock for
    this file: ROADMAP C.17). The two cases of the ladder itself set it
    back."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 128)


def _actor(group_size=2, ppo_n_minibatches=2, max_tokens_per_mb=32768, **kw):
    cfg = PPOActorConfig(
        dtype="float32",
        param_dtype="float32",
        gradient_checkpointing=False,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb),
        optimizer=kw.pop("optimizer", None) or OptimizerConfig(
            lr=1e-3, weight_decay=0.0, warmup_steps_proportion=0.0,
            gradient_clipping=10.0,
        ),
        parallel=ParallelismConfig(),
        group_size=group_size,
        ppo_n_minibatches=ppo_n_minibatches,
        group_reward_norm=True,
        adv_norm=AdvNormConfig(mean_level="batch", std_level="batch"),
        **kw,
    )
    eng = SPMDTrainEngine(cfg)
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 8),
                   model_config=tiny_config("qwen2"), seed=0)
    return PPOActor(cfg, eng)


def _rollout_batch(n=8, vocab=128, seed=0, prompt_len=3):
    """Fake rollout: prompts + completions with behavior logprobs."""
    rng = np.random.default_rng(seed)
    seqs, loss_masks = [], []
    for _ in range(n):
        total = int(rng.integers(6, 14))
        seqs.append(rng.integers(0, vocab, size=total))
        lm = np.zeros(total, np.int32)
        lm[prompt_len:] = 1
        loss_masks.append(lm)
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm_batch = data_utils.pad_sequences_to_tensors(loss_masks)
    batch["loss_mask"] = lm_batch["input_ids"].astype(np.int32)
    mask = batch["attention_mask"]
    batch["logprobs"] = (
        rng.standard_normal(mask.shape).astype(np.float32) * 0.1 - 1.0
    ) * batch["loss_mask"]
    batch["versions"] = np.where(batch["loss_mask"] > 0, 0, -1).astype(np.int32)
    batch["rewards"] = rng.integers(0, 2, size=n).astype(np.float32)
    return batch


def test_compute_advantages_grpo_layout():
    actor = _actor()
    batch = _rollout_batch()
    out = actor.compute_advantages(dict(batch))
    adv = out["advantages"]
    lm = batch["loss_mask"].astype(bool)
    assert adv.shape == batch["input_ids"].shape
    assert (adv[~lm] == 0).all()
    m = adv[lm]
    np.testing.assert_allclose(m.mean(), 0.0, atol=1e-4)  # batch-whitened


def test_ppo_update_runs_and_improves_objective():
    actor = _actor()
    batch = _rollout_batch()
    # proximal logprobs = current-policy recompute (decoupled loss path)
    batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    out = actor.compute_advantages(dict(batch))
    stats = actor.ppo_update(out)
    assert len(stats) == 2  # two minibatches
    for s in stats:
        assert s["update_successful"] == 1.0
        assert np.isfinite(s["grad_norm"])
    assert actor.engine.step_count == 2


def test_dynamic_sampling_filters_uniform_groups():
    actor = _actor(dynamic_sampling=True)
    batch = _rollout_batch()
    # make group 0 uniform (both rewards 1) and group 1 mixed
    batch["rewards"] = np.asarray([1, 1, 0, 1, 1, 1, 0, 1], np.float32)
    batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    out = actor.compute_advantages(dict(batch))
    stats = actor.ppo_update(out)
    assert len(stats) >= 1


# --------------------------------------------------------------------------
# The proximal logprobs an update finds itself (PR 43): the first minibatch
# takes them from its own forward, the recompute pass runs over the others
# --------------------------------------------------------------------------
def _watched(actor):
    """``actor`` with its engine's ``forward`` and ``train_batches`` wrapped:
    what the recompute pass was asked for and which minibatches trained."""
    eng = actor.engine
    seen = {"forward": [], "minibatches": []}
    forward, train_batches = eng.forward, eng.train_batches

    def watching_forward(input_, post_hook=None, rows=None):
        seen["forward"].append(
            (data_utils.batch_size(input_),
             None if rows is None else sorted(int(r) for r in rows))
        )
        return forward(input_, post_hook=post_hook, rows=rows)

    def watching_train_batches(inputs, loss_fn, loss_weight_fn):
        def watched():
            for mb in inputs:
                seen["minibatches"].append(mb)
                yield mb

        return train_batches(watched(), loss_fn, loss_weight_fn)

    eng.forward, eng.train_batches = watching_forward, watching_train_batches
    return seen


def _prox_counts():
    """The stats plane's ``spmd/prox_*`` since the last export: the
    sequences and loss tokens that took their proximal logprobs from the
    update's own forward, and those the recompute pass ran over."""
    step = stats_tracker.export_all()
    return {
        k[len("spmd/"):]: int(v) for k, v in step.items()
        if k.startswith("spmd/prox_")
    }


def _advantaged(actor, **batch_kw):
    batch = _rollout_batch(**batch_kw)
    batch["uid"] = np.arange(len(batch["rewards"]), dtype=np.int32)
    return actor.compute_advantages(batch)


def _params(actor):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(actor.engine.params)]


# the equivalence's tolerance, float32 on the CPU under SGD: the parameters'
# CHANGE over the step agrees to 1e-4 of its largest entry (measured: 1e-5;
# the rounding of one forward against another, differently packed)
_EQUIV_RTOL = 1e-4


@pytest.mark.parametrize("behav_imp_weight_cap", [None, 1.2])
@pytest.mark.parametrize("use_decoupled_loss", [True, False])
def test_own_forward_prox_equals_recomputed_prox_handed_in(
    use_decoupled_loss, behav_imp_weight_cap
):
    """The step a batch WITHOUT ``prox_logp`` takes is the step the same
    batch and split take with ``compute_logp(batch)`` handed in."""
    kw = dict(
        ppo_n_minibatches=4, use_decoupled_loss=use_decoupled_loss,
        behav_imp_weight_cap=behav_imp_weight_cap,
        optimizer=OptimizerConfig(
            type="sgd", lr=1e-1, weight_decay=0.0,
            warmup_steps_proportion=0.0, gradient_clipping=10.0,
        ),
    )
    own, given = _actor(**kw), _actor(**kw)
    before = _params(own)
    batch = _advantaged(own)
    # behaviour logprobs near the policy's, so the cap masks some tokens
    # and keeps others
    near = given.compute_logp(batch) * batch["loss_mask"]
    batch["logprobs"] = (near + 0.3 * np.random.default_rng(5).standard_normal(
        near.shape).astype(np.float32)) * batch["loss_mask"]

    np.random.seed(7)
    stats_own = own.ppo_update(dict(batch))
    handed = dict(batch)
    handed["prox_logp"] = given.compute_logp(batch) * batch["loss_mask"]
    np.random.seed(7)
    stats_given = given.ppo_update(handed)

    assert len(stats_own) == len(stats_given) == 4
    if behav_imp_weight_cap is not None and use_decoupled_loss:
        assert 0.0 < stats_given[0]["behave_imp_weight"]
    moved = 0.0
    for p0, a, b in zip(before, _params(own), _params(given)):
        scale = np.abs(b - p0).max()
        moved = max(moved, scale)
        np.testing.assert_allclose(
            a - p0, b - p0, rtol=0, atol=_EQUIV_RTOL * max(scale, 1e-6)
        )
    assert moved > 1e-4  # the step moved something to compare
    for s_own, s_given in zip(stats_own, stats_given):
        for k in ("loss", "grad_norm", "behave_imp_weight", "clip_ratio"):
            np.testing.assert_allclose(
                s_own[k], s_given[k], rtol=1e-4, atol=1e-6, err_msg=k
            )
    # the first minibatch against its own forward: 1 to the bit
    assert stats_own[0]["importance_weight"] == 1.0
    assert stats_own[0]["approx_kl"] == 0.0


@pytest.mark.parametrize("n_minibatches", [4, 2, 1])
def test_recompute_pass_covers_minibatches_after_the_first(n_minibatches):
    actor = _actor(ppo_n_minibatches=n_minibatches)
    seen = _watched(actor)
    batch = _advantaged(actor)
    np.random.seed(3)
    groups = np.array_split(np.random.permutation(8), n_minibatches)
    np.random.seed(3)
    stats_tracker.export_all()
    stats = actor.ppo_update(batch)
    assert len(stats) == n_minibatches
    rest = sorted(int(i) for g in groups[1:] for i in g)
    # one pass, over the whole batch's other minibatches; none for one
    assert seen["forward"] == ([(8, rest)] if rest else [])
    tokens = (batch["loss_mask"] > 0).sum(1)
    assert _prox_counts() == {
        "prox_own_seqs": len(groups[0]),
        "prox_own_tokens": int(tokens[groups[0]].sum()),
        "prox_recomputed_seqs": len(rest),
        "prox_recomputed_tokens": int(tokens[rest].sum()),
    }
    for k, mb in enumerate(seen["minibatches"]):
        # every minibatch carries both arrays; the flag is the first one's
        assert mb["prox_own"].all() == (k == 0)
        assert mb["prox_own"].any() == (k == 0)
        assert (mb["prox_logp"] != 0).any() == (k > 0)
    assert "prox_logp" not in batch  # the caller's batch is left as it was


def test_a_supplied_prox_logp_runs_the_parents_path():
    actor = _actor(ppo_n_minibatches=4)
    batch = _advantaged(actor)
    batch["prox_logp"] = actor.compute_logp(batch) * batch["loss_mask"]
    seen = _watched(actor)
    stats_tracker.export_all()
    actor.ppo_update(batch)
    assert seen["forward"] == []  # no pass of the update's own
    assert len(seen["minibatches"]) == 4
    assert all("prox_own" not in mb for mb in seen["minibatches"])
    assert _prox_counts() == {}


def test_prox_counter_reads_one_to_three_on_four_minibatches(tmp_path):
    """The stats plane and the ``train.ppo_update`` span's attributes in a
    profile say the same: 2 sequences from the update's own forward, 6
    recomputed."""
    from tests.test_tracing import _profile_events

    actor = _actor(ppo_n_minibatches=4)
    batch = _advantaged(actor)
    stats_tracker.export_all()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        actor.ppo_update(batch)
    finally:
        jax.profiler.stop_trace()
    counts = _prox_counts()
    assert counts["prox_own_seqs"] == 2 and counts["prox_recomputed_seqs"] == 6
    assert counts["prox_own_tokens"] + counts["prox_recomputed_tokens"] == int(
        (batch["loss_mask"] > 0).sum()
    )
    (span,) = _profile_events(str(tmp_path))["areal:train.ppo_update"]
    assert {k: span[k] for k in counts} == counts
    # the stats plane sums the updates between two exports
    actor.ppo_update(dict(batch))
    actor.ppo_update(dict(batch))
    assert _prox_counts()["prox_own_seqs"] == 4


def _programs(actor, kind):
    return [f for k, f in actor.engine._jit_cache.items()
            if isinstance(k, tuple) and k[0] == kind]


def test_one_grad_step_program_a_row_length_over_a_whole_step():
    """Every minibatch of the step is one row of the shortest rung: the
    first one runs the program the other three run, so the step builds as
    many as a step whose ``prox_logp`` was handed in (the parent's)."""
    own, given = _actor(ppo_n_minibatches=4), _actor(ppo_n_minibatches=4)
    batch = _advantaged(own)
    own.ppo_update(dict(batch))
    batch["prox_logp"] = given.compute_logp(batch) * batch["loss_mask"]
    given.ppo_update(batch)
    (grad_own,), (grad_given,) = _programs(own, "grad"), _programs(given, "grad")
    # (two a length on either side: the call's first accumulator is
    # ``zero_grads``', the later ones ``apply_step``'s)
    assert grad_own._cache_size() == grad_given._cache_size() == 2
    assert _programs(own, "fwd")[0]._cache_size() == 1
    assert len(_programs(own, "grad")) == len(_programs(own, "fwd")) == 1


@pytest.mark.parametrize("seed", [0, 11])
def test_the_split_is_the_one_numpys_seed_draws(seed):
    """One ``np.random.permutation(bsz)`` and nothing before it: the split
    the parent drew under the same seed."""
    actor = _actor(ppo_n_minibatches=4)
    seen = _watched(actor)
    batch = _advantaged(actor)
    np.random.seed(seed)
    groups = np.array_split(np.random.permutation(8), 4)
    after = np.random.random()
    np.random.seed(seed)
    actor.ppo_update(batch)
    assert np.random.random() == after  # and nothing after it
    assert [mb["uid"].tolist() for mb in seen["minibatches"]] == [
        g.tolist() for g in groups
    ]


def test_dropped_groups_are_neither_recomputed_nor_trained():
    actor = _actor(dynamic_sampling=True, ppo_n_minibatches=2)
    seen = _watched(actor)
    batch = _rollout_batch()
    # groups of two: 0 and 2 uniform (dropped), 1 and 3 mixed (kept)
    batch["rewards"] = np.asarray([1, 1, 0, 1, 1, 1, 0, 1], np.float32)
    batch["uid"] = np.arange(8, dtype=np.int32)
    out = actor.compute_advantages(batch)
    stats_tracker.export_all()
    actor.ppo_update(out)
    kept = {2, 3, 6, 7}
    trained = [set(mb["uid"].tolist()) for mb in seen["minibatches"]]
    assert len(trained) == 2 and trained[0] | trained[1] == kept
    # the pass saw the kept sequences only, and of them the second minibatch
    ((n_seen, rows),) = seen["forward"]
    assert n_seen == 4
    assert {int(out["uid"][sorted(kept)][r]) for r in rows} == trained[1]
    counts = _prox_counts()
    assert counts["prox_own_seqs"] == counts["prox_recomputed_seqs"] == 2


def test_a_subset_pass_keeps_the_whole_batchs_row_length_and_window(
    monkeypatch
):
    """``forward(rows=)``: the others read 0, the rows read what a pass over
    all of them reads, and the microbatches run at the WHOLE input's row
    length."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 2048)  # the ladder's own
    actor = _actor(ppo_n_minibatches=4, max_tokens_per_mb=2048)
    eng = actor.engine
    rng = np.random.default_rng(0)
    # one long sequence (a 4096 row) among short ones
    lens = [2100, 40, 30, 20, 10, 12]
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, size=n) for n in lens]
    )
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    whole = actor.compute_logp(batch)
    rows = [1, 3, 4]
    mbs = eng._split(batch, backward=False, rows=rows)
    assert sorted(i for g in mbs.groups for i in g) == rows
    assert set(mbs.row_lens) == {4096}  # not the subset's own 2048
    assert eng._split(
        data_utils.batch_select(batch, rows), backward=False
    ).row_lens == [2048]
    part = eng.forward(batch, post_hook=actor._logp_hook(), rows=rows)
    assert part.shape == whole.shape
    others = [i for i in range(len(lens)) if i not in rows]
    assert (part[others] == 0).all()
    np.testing.assert_allclose(part[rows], whole[rows], atol=1e-5)
    (fwd,) = _programs(actor, "fwd")
    assert fwd._cache_size() == 1


def test_a_subset_that_needs_a_longer_row_than_the_whole_is_refused(
    monkeypatch,
):
    """First-fit is not monotone, so a subset could in principle need a
    longer rung than the whole input: the pass refuses it and does not
    build a ``logp_forward`` the whole batch never had."""
    monkeypatch.setattr(data_utils, "ROW_QUANTUM", 2048)  # the ladder's own
    eng = _actor().engine
    rng = np.random.default_rng(0)
    lens = [2100, 40, 30, 20]
    batch = data_utils.pad_sequences_to_tensors(
        [rng.integers(0, 128, size=n) for n in lens]
    )
    split_lens = data_utils.split_lens

    def short_whole(seq_lens, *a, **kw):
        groups, row_lens = split_lens(seq_lens, *a, **kw)
        if len(seq_lens) == len(lens):  # the whole input's
            row_lens = [1024] * len(row_lens)
        return groups, row_lens

    monkeypatch.setattr(data_utils, "split_lens", short_whole)
    with pytest.raises(ValueError, match="whole input's longest is 1024"):
        eng._split(batch, backward=False, rows=[0, 2])
