"""Packing/micro-batching invariants (mirrors reference
areal/tests/test_packed_vs_padded_consistency.py at the data layer)."""

import numpy as np
import pytest

from areal_tpu.utils import data as du
from areal_tpu.utils import datapack


def _ragged_batch(lens, seed=0):
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(1, 1000, size=L).astype(np.int32) for L in lens]
    batch = du.pad_sequences_to_tensors(seqs)
    batch["loss_mask"] = batch["attention_mask"].astype(np.int32)
    batch["rewards"] = rng.normal(size=len(lens)).astype(np.float32)
    return batch, seqs


def test_pad_sequences():
    batch, seqs = _ragged_batch([3, 5, 2])
    assert batch["input_ids"].shape == (3, 5)
    assert batch["attention_mask"].sum() == 10
    np.testing.assert_array_equal(batch["input_ids"][1], seqs[1])


def test_pack_unpack_roundtrip():
    batch, _ = _ragged_batch([7, 3, 11, 1])
    packed = du.pack_batch(batch)
    assert packed.total_tokens == 22
    assert packed.tokens.shape[0] == du.next_bucket_size(22)
    # segment ids are 1-based contiguous, padding is 0
    assert packed.segment_ids.max() == 4
    assert (packed.segment_ids[packed.total_tokens:] == 0).all()
    restored = du.unpack_batch(packed)
    restored = du.trim_batch(restored)
    np.testing.assert_array_equal(restored["input_ids"], du.trim_batch(batch)["input_ids"])
    np.testing.assert_array_equal(restored["loss_mask"], batch["loss_mask"])
    np.testing.assert_array_equal(restored["rewards"], batch["rewards"])


def test_pack_static_bucket():
    batch, _ = _ragged_batch([5, 5])
    p = du.pack_batch(batch, pad_to=512, pad_seqs_to=8)
    assert p.tokens.shape == (512,)
    assert p.seq_lens.shape == (8,)
    assert p.n_seqs == 2


def test_concat_padded():
    b1, _ = _ragged_batch([3, 4], seed=1)
    b2, _ = _ragged_batch([6], seed=2)
    out = du.concat_padded_tensors([b1, b2])
    assert out["input_ids"].shape == (3, 6)
    assert out["attention_mask"].sum() == 13
    assert out["rewards"].shape == (3,)


def test_mb_split_respects_budget():
    lens = [100, 200, 300, 50, 250, 120, 80]
    batch, _ = _ragged_batch(lens)
    mbl = du.split_padded_batch_into_mb_list(batch, max_tokens_per_mb=400)
    assert sum(int(np.asarray(m["attention_mask"]).sum()) for m in mbl.mbs) == sum(lens)
    for mb in mbl.mbs:
        assert int(np.asarray(mb["attention_mask"]).sum()) <= 400
    # every index appears exactly once
    assert sorted(mbl.forward_indices) == list(range(len(lens)))


def test_reorder_back():
    vals = np.array([10.0, 20.0, 30.0, 40.0])
    fwd = [2, 0, 3, 1]
    # vals are in forward (mb) order; reorder to original
    out = du.reorder_back(vals, fwd)
    np.testing.assert_array_equal(out, [20.0, 40.0, 10.0, 30.0])


def test_ffd_allocate():
    sizes = [5, 9, 3, 7, 2, 8]
    groups = datapack.ffd_allocate(sizes, capacity=10)
    seen = sorted(x for g in groups for x in g)
    assert seen == list(range(6))
    for g in groups:
        assert sum(sizes[i] for i in g) <= 10


def test_ffd_oversize_item():
    groups = datapack.ffd_allocate([100, 2, 3], capacity=10)
    seen = sorted(x for g in groups for x in g)
    assert seen == [0, 1, 2]


def test_ffd_min_groups():
    groups = datapack.ffd_allocate([1, 1, 1, 1], capacity=100, min_groups=2)
    assert len(groups) >= 2


def test_partition_balanced():
    sizes = [10, 1, 1, 1, 9, 8]
    groups = datapack.partition_balanced(sizes, k=3)
    assert len(groups) == 3
    loads = [sum(sizes[i] for i in g) for g in groups]
    assert max(loads) <= 12


def test_bucket_sizes():
    assert du.next_bucket_size(1) == 256
    assert du.next_bucket_size(256) == 256
    assert du.next_bucket_size(257) == 512
    assert du.next_bucket_size(9000) == 16384


def test_pack_unpack_zero_length_rows():
    # zero-length sequences must keep per-seq alignment (regression)
    batch = du.pad_sequences_to_tensors(
        [np.array([1, 2, 3], np.int32), np.array([], np.int32), np.array([7, 8], np.int32)]
    )
    batch["rewards"] = np.array([10.0, 20.0, 30.0], np.float32)
    p = du.pack_batch(batch)
    assert p.n_seqs == 3
    restored = du.unpack_batch(p)
    np.testing.assert_array_equal(restored["rewards"], [10.0, 20.0, 30.0])
    assert restored["attention_mask"].sum(1).tolist() == [3, 0, 2]


# ---------------------------------------------------------------------------
# Rows first (PR 30): a batch becomes microbatches of rows, each microbatch at
# its own row length on the ladder of multiples of the quantum
# ---------------------------------------------------------------------------
def _law_lens(rng, n):
    """Sequence lengths of the reasoning length law the benchmark's traffic
    has: prompts uniform 128-512 shared by 4, outputs log-normal to 3584."""
    outs = np.clip(
        np.round(600 * np.exp(0.9 * rng.standard_normal(n))), 32, 3584
    ).astype(np.int64)
    return outs + np.repeat(rng.integers(128, 513, size=-(-n // 4)), 4)[:n]


def _lens_batch(lens):
    """A batch that holds its lengths and, as token ids, each sequence's own
    index: enough for the split and the pack, and cheap at 4096 wide."""
    lens = np.asarray(lens)
    mask = np.arange(max(int(lens.max(initial=0)), 1))[None, :] < lens[:, None]
    ids = np.where(mask, np.arange(len(lens))[:, None] + 1, 0)
    return dict(input_ids=ids.astype(np.int32), attention_mask=mask)


def _split_and_pack(lens, cap, n_rows, quantum, min_n_mbs=1):
    """[(row length, the lengths' indices in each row)] of every microbatch,
    as the engine dispatches it: split, then pack at the split's length."""
    batch = _lens_batch(lens)
    mbl = du.split_padded_batch_into_mb_list(
        batch, cap, min_n_mbs=min_n_mbs, n_rows=n_rows, quantum=quantum
    )
    assert len(mbl.mbs) == len(mbl.groups) == len(mbl.row_lens)
    out = []
    for mb, group, row_len in zip(mbl.mbs, mbl.groups, mbl.row_lens):
        packed = du.pack_batch_rows(
            mb, n_rows=n_rows, quantum=quantum, pad_to=row_len
        )
        assert packed.tokens.shape == (n_rows, row_len)
        # the pack finds the same rows at the same length on its own (it
        # does not know a budget that closes the ladder between two rungs)
        if row_len // quantum & (row_len // quantum - 1) == 0:
            alone = du.pack_batch_rows(mb, n_rows=n_rows, quantum=quantum)
            assert alone.row_seqs == packed.row_seqs
            assert alone.bucket == row_len
        for r, row in enumerate(packed.row_seqs):
            # whole, in order, nothing else in the row but padding
            want = np.concatenate(
                [np.full(lens[group[b]], group[b] + 1) for b in row]
                + [np.zeros(0, np.int64)]
            )
            np.testing.assert_array_equal(packed.tokens[r, :len(want)], want)
            assert not packed.segment_ids[r, len(want):].any()
        out.append((row_len, [[group[b] for b in row]
                              for row in packed.row_seqs]))
    return out


ROWS_AND_SEQ = [(1, 1), (4, 1), (8, 1), (1, 2), (4, 2), (8, 2)]


@pytest.mark.parametrize("n_rows,seq_parallel", ROWS_AND_SEQ)
def test_rows_first_every_sequence_once_and_whole(n_rows, seq_parallel):
    rng = np.random.default_rng(n_rows * 10 + seq_parallel)
    quantum = 1024 * seq_parallel
    for n in (1, 3, 16, 64):
        lens = _law_lens(rng, n)
        mbs = _split_and_pack(lens, 4096 * n_rows, n_rows, quantum)
        seen = sorted(b for _, rows in mbs for row in rows for b in row)
        assert seen == list(range(n))


@pytest.mark.parametrize("n_rows,seq_parallel", ROWS_AND_SEQ)
def test_rows_first_no_row_exceeds_its_length_nor_a_microbatch_the_cap(
    n_rows, seq_parallel
):
    rng = np.random.default_rng(100 + n_rows * 10 + seq_parallel)
    cap, quantum = 4096 * n_rows, 1024 * seq_parallel
    for _ in range(20):
        lens = _law_lens(rng, int(rng.integers(1, 70)))
        for row_len, rows in _split_and_pack(lens, cap, n_rows, quantum):
            assert len(rows) == n_rows
            assert max(int(lens[row].sum()) for row in rows) <= row_len
            assert sum(int(lens[row].sum()) for row in rows) <= cap


@pytest.mark.parametrize("n_rows,seq_parallel", ROWS_AND_SEQ)
def test_rows_first_lengths_stay_on_a_ladder_of_few_rungs(
    n_rows, seq_parallel
):
    """Over 200 random batches of the length law: every row length the
    quantum times a power of two, none above a row's share of the budget, so
    at most log2(budget / rows / quantum) + 1 distinct ones whatever the
    batches draw."""
    rng = np.random.default_rng(200 + n_rows * 10 + seq_parallel)
    cap, quantum = 4096 * n_rows, 1024 * seq_parallel
    seen = set()
    for _ in range(200):
        lens = _law_lens(rng, int(rng.integers(1, 70)))
        mbl = du.split_padded_batch_into_mb_list(
            _lens_batch(lens), cap, n_rows=n_rows, quantum=quantum
        )
        seen.update(mbl.row_lens)
    assert seen <= {quantum << k for k in range(3)} and max(seen) == 4096
    assert len(seen) == (4096 // quantum).bit_length()


@pytest.mark.parametrize("n_rows", [1, 4, 8])
def test_rows_first_a_sequence_longer_than_a_row_still_packs(n_rows):
    rng = np.random.default_rng(300 + n_rows)
    lens = np.concatenate([_law_lens(rng, 16), [5000]])
    mbs = _split_and_pack(lens, 4096 * n_rows, n_rows, 1024)
    seen = sorted(b for _, rows in mbs for row in rows for b in row)
    assert seen == list(range(17))
    # the long sequence's own length, rounded up to the quantum, closes
    # this call's ladder between 4096 and 8192
    assert max(t for t, _ in mbs) == 5120
    assert all(
        max(int(lens[row].sum()) for row in rows) <= t for t, rows in mbs
    )


@pytest.mark.parametrize("n_rows", [1, 4, 8])
@pytest.mark.parametrize("n_mbs", [2, 4])
def test_rows_first_n_mbs_is_a_lower_bound(n_rows, n_mbs):
    lens = np.asarray([100, 200, 300, 50, 250, 120, 80])
    mbs = _split_and_pack(lens, 4096 * n_rows, n_rows, 1024, min_n_mbs=n_mbs)
    assert len(mbs) == n_mbs  # they would all fit one microbatch
    assert sorted(b for _, rows in mbs for row in rows for b in row) == list(
        range(7)
    )
    # never more microbatches than sequences
    assert len(_split_and_pack(lens[:1], 4096, 1, 1024, min_n_mbs=n_mbs)) == 1
    # and a split the budget already forces is left alone
    big = _split_and_pack(
        np.full(12, 3000), 4096 * n_rows, n_rows, 1024, min_n_mbs=n_mbs
    )
    assert len(big) == max(n_mbs, -(-12 // n_rows))


@pytest.mark.parametrize("n_rows,seq_parallel", ROWS_AND_SEQ)
def test_rows_first_split_is_a_pure_function_of_the_lengths(
    n_rows, seq_parallel
):
    """Two processes of a multi-host run hold the same lengths (other
    arrays, other tokens): they must derive the same microbatches, rows and
    row lengths, and numpy's global generator must not enter."""
    rng = np.random.default_rng(400 + n_rows * 10 + seq_parallel)
    lens = _law_lens(rng, 48)
    quantum = 1024 * seq_parallel
    np.random.seed(1)
    a = _split_and_pack(lens, 4096 * n_rows, n_rows, quantum)
    np.random.seed(2)
    b = _split_and_pack(lens.copy(), 4096 * n_rows, n_rows, quantum)
    assert a == b


def test_rows_first_the_last_microbatch_runs_at_its_own_length():
    """ISSUE 30's two examples: the dense cell's remnant and the sparse
    cell's, which paid the longest microbatch's row (4096, and four of
    them)."""
    dense = [4081 - 2000, 2000, 1231, 3922, 4039, 1963, 1448, 4077]
    mbl = du.split_padded_batch_into_mb_list(_lens_batch(dense), 4096)
    assert sorted(mbl.row_lens) == [2048, 4096, 4096, 4096, 4096, 4096]
    sparse = [4090, 4080, 4070, 4087, 361]
    mbl = du.split_padded_batch_into_mb_list(
        _lens_batch(sparse), 16384, n_rows=4
    )
    assert mbl.row_lens == [4096, du.ROW_QUANTUM] == [4096, 2048]
    assert mbl.groups == [[0, 1, 2, 3], [4]]
    # a finer ladder is the caller's to ask for
    assert du.split_padded_batch_into_mb_list(
        _lens_batch(sparse), 16384, n_rows=4, quantum=1024
    ).row_lens == [4096, 1024]


def test_rows_on_ladder_refuses_a_length_that_does_not_fit():
    assert du.rows_on_ladder(np.asarray([600, 500, 400]), 2, 1024) == (
        1024, [[0, 2], [1]]
    )
    assert du.rows_on_ladder(np.asarray([600, 500, 400]), 1, 1024)[0] == 2048
    with pytest.raises(ValueError):
        du.rows_on_ladder(np.asarray([600, 500, 400]), 1, 1024, pad_to=1024)
    with pytest.raises(ValueError):
        du.rows_on_ladder(np.asarray([1500]), 4, 1024, pad_to=1024)
    # nothing to pack is still a shape
    assert du.rows_on_ladder(np.zeros(0, np.int64), 2, 1024) == (
        1024, [[], []]
    )


# ---------------------------------------------------------------------------
# The two shipped traffic layouts, replayed through the split off the chip
# ---------------------------------------------------------------------------
def _replay_a_pass(traffic, cap, n_rows):
    """One pass over a traffic file's batches as the trainer dispatches it:
    the recompute over a whole batch (one row length a call, the longest's:
    ``SPMDTrainEngine._split``), then ``ppo_n_minibatches`` = 4 minibatches
    split as ``PPOActor._ppo_update`` splits them under the benchmark's
    seeding. {(program, row length): [runs, real, dispatched]}."""
    import json
    import os

    from benchmark import lengths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "traffic", traffic)) as f:
        p = json.load(f)
    n_seq, g, nb = p["batch_seqs"], p["group_size"], p["n_batches"]
    layout = np.random.default_rng(p["layout_seed"])
    outs = lengths.ordered(p["output_len"], nb * n_seq, layout)
    prompts = lengths.ordered(p["prompt_len"], nb * n_seq // g, layout)
    runs = {}
    state = np.random.get_state()
    try:
        for b in range(nb):
            at = np.arange(b * n_seq, (b + 1) * n_seq)
            lens = prompts[at // g] + outs[at]
            np.random.seed(p["layout_seed"] + b)
            minibatches = np.array_split(np.random.permutation(n_seq), 4)
            calls = [("logp_forward", np.arange(n_seq))] + [
                ("grad_step", m) for m in minibatches
            ]
            for program, members in calls:
                mbl = du.split_padded_batch_into_mb_list(
                    _lens_batch(lens[members]), cap, n_rows=n_rows
                )
                row_lens = mbl.row_lens
                if program == "logp_forward":
                    row_lens = [max(row_lens)] * len(row_lens)
                for group, row_len in zip(mbl.groups, row_lens):
                    run = runs.setdefault((program, row_len), [0, 0, 0])
                    run[0] += 1
                    run[1] += int(lens[members][group].sum())
                    run[2] += n_rows * row_len
    finally:
        np.random.set_state(state)
    return runs


@pytest.mark.parametrize(
    "traffic,cap,n_rows,n_runs,real_tokens,pad_limit",
    [
        # the tree read 14.32% and 31.05% before PR 30; ISSUE 30 asked for 7%
        # and 12.5% on a 1024-token ladder of seven shapes, which replays to
        # 6.43% and 9.03% and cost the dense cell 15% of set-up against a
        # bound of 10%: PERF.md, PR 30
        ("grpo_steps32.json", 4096, 1, 86, 301_804, 0.105),
        ("grpo_steps64_fsdp4.json", 16384, 4, 50, 603_660, 0.175),
    ],
)
def test_shipped_layouts_dispatch_little_padding_in_few_shapes(
    traffic, cap, n_rows, n_runs, real_tokens, pad_limit
):
    """What keeps a later change from giving the padding back, or from
    buying it with more shapes than set-up can pay for: the two cells' length
    layouts through the split, numpy only."""
    runs = _replay_a_pass(traffic, cap, n_rows)
    assert sum(r[0] for r in runs.values()) == n_runs
    assert sum(r[1] for r in runs.values()) == real_tokens  # the same work
    dispatched = sum(r[2] for r in runs.values())
    assert 1 - real_tokens / dispatched <= pad_limit, (dispatched, runs)
    # (program, row length) pairs: one more than before PR 30 in the dense
    # cell (2), eight fewer in the sparse one (11)
    assert sorted(runs) == [
        ("grad_step", 2048), ("grad_step", 4096), ("logp_forward", 4096)
    ]
