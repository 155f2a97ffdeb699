"""The length generator: the same multiset for every seed, every clip
honoured, the stationary start's mix."""

import numpy as np
import pytest

from benchmark import lengths
from benchmark.kinds import closed_rollout, train_steps

REASON = {"law": "lognormal", "median": 600, "sigma": 0.9, "lo": 32, "hi": 3584}
CHAT_IN = {"law": "lognormal", "median": 256, "sigma": 1.0, "lo": 16, "hi": 2048}
PROMPT = {"law": "uniform", "lo": 128, "hi": 512}


@pytest.mark.parametrize("law", [REASON, CHAT_IN, PROMPT],
                         ids=["reason", "chat_in", "prompt"])
def test_pool_honours_clips_and_is_one_multiset_for_every_seed(law):
    pool = lengths.pool(law, 512)
    assert pool.min() >= law["lo"] and pool.max() <= law["hi"]
    a = lengths.ordered(law, 512, np.random.default_rng(1))
    b = lengths.ordered(law, 512, np.random.default_rng(2 ** 31 + 5))
    assert sorted(a) == sorted(b) == sorted(pool)
    assert list(a) != list(b)
    again = lengths.ordered(law, 512, np.random.default_rng(1))
    assert list(a) == list(again)


def test_reason_law_has_the_stated_median_and_mean():
    pool = lengths.pool(REASON, 4096)
    assert abs(np.median(pool) - 600) <= 2
    assert 820 <= pool.mean() <= 880  # the issue's "mean about 850"
    assert (pool == 3584).mean() == pytest.approx(0.0235, abs=0.003)


def test_length_biased_mix_matches_the_law():
    pool = lengths.pool(REASON, 512)
    biased = lengths.length_biased(pool, 128)
    want = (pool.astype(float) ** 2).sum() / pool.sum()  # E[L^2] / E[L]
    assert biased.mean() == pytest.approx(want, rel=0.03)
    assert biased.mean() > 1.5 * pool.mean()
    assert set(biased) <= set(pool)


def _rollout_params():
    return {"output_len": REASON, "prompt_len": PROMPT, "pool": 512,
            "group_size": 16}


def test_closed_stream_is_deterministic_in_seed_and_groups_prompts():
    a = closed_rollout.Stream(_rollout_params(), 1000, 2 ** 31 + 3)
    b = closed_rollout.Stream(_rollout_params(), 1000, 2 ** 31 + 3)
    ra = [a.next() for _ in range(40)]
    rb = [b.next() for _ in range(40)]
    assert ra == rb
    assert all(ra[i][0] == ra[0][0] for i in range(16))
    assert ra[16][0] != ra[0][0]
    c = closed_rollout.Stream(_rollout_params(), 1000, 4)
    assert [c.next() for _ in range(40)] != ra
    outs = sorted(r[1] for r in (a.next() for _ in range(512)))
    assert len(outs) == 512 and outs[0] >= 32 and outs[-1] <= 3584


FILL = {"count": 120, "long_count": 24, "long_context": 2048,
        "anchor_to": 4095}


def test_stationary_start_is_in_progress_and_inside_the_context():
    s = closed_rollout.Stream(_rollout_params(), 1000, 9)
    long, groups = s.in_progress(FILL)
    assert len(long) == 24 and sum(len(g) for g in groups) == 96
    # the long ones are found 2048 tokens in, each with its own context;
    # the anchor runs to the end of the context
    assert all(len(p) == 2048 for p, _ in long)
    assert len({tuple(p[:8]) for p, _ in long}) == 24
    assert long[0][1] == 4095 - 2048
    assert all(1 <= n and 2048 + n <= 4096 for _, n in long)
    # the rest have produced nothing and share prompts like the stream
    assert [len(g) for g in groups] == [16] * 6
    for g in groups:
        assert all(p == g[0][0] for p, _ in g) and 128 <= len(g[0][0]) <= 512
    assert len({tuple(g[0][0]) for g in groups}) == 6
    # what is in flight is the length-biased law: far longer than a draw
    total = [2048 - 320 + n for _, n in long] + [n for g in groups for _, n in g]
    assert np.mean(total) > 1.5 * 850
    again = closed_rollout.Stream(_rollout_params(), 1000, 9).in_progress(FILL)
    assert [n for _, n in again[0]] == [n for _, n in long]


def test_train_batches_same_shapes_every_seed_other_tokens():
    p = {"batch_seqs": 8, "group_size": 4, "n_batches": 2, "layout_seed": 5,
         "prompt_len": {"law": "uniform", "lo": 4, "hi": 12},
         "output_len": {"law": "lognormal", "median": 12, "sigma": 0.9,
                        "lo": 2, "hi": 48}}
    a = train_steps.make_batches(p, 100, 1)
    b = train_steps.make_batches(p, 100, 2 ** 31 + 2)
    for x, y in zip(a, b):
        assert list(x["_seq_lens"]) == list(y["_seq_lens"])
        assert x["input_ids"].shape == y["input_ids"].shape
        assert not np.array_equal(x["input_ids"], y["input_ids"])
        # siblings share their prompt, and disagree on the reward
        first_completion = int(np.argmax(x["loss_mask"][0]))
        assert np.array_equal(x["input_ids"][0, :first_completion],
                              x["input_ids"][1, :first_completion])
        assert set(x["rewards"][:4]) == {0.0, 1.0}


@pytest.mark.parametrize("seed", [0, 401, 2 ** 31 + 354])
def test_every_pass_holds_every_batch_once_in_the_seeds_order(seed):
    passes = [train_steps.pass_order(4, seed, k) for k in range(6)]
    assert all(sorted(p) == [0, 1, 2, 3] for p in passes)
    assert passes == [train_steps.pass_order(4, seed, k) for k in range(6)]
    assert len({tuple(p) for p in passes}) > 1  # a pass is drawn anew
    other = [train_steps.pass_order(4, seed + 1, k) for k in range(6)]
    assert other != passes
