"""Batch containers and padded↔packed conversion.

Role of reference areal/utils/data.py: RL data is ragged (prompt+completion
lengths vary); the trainer wants it packed (one flat token stream with
sequence boundaries) and micro-batched under a token budget. The reference
uses TensorDict + flash-attn varlen cu_seqlens with fully dynamic shapes.

TPU redesign: a *batch* is a plain ``dict[str, np.ndarray]`` in padded layout
(`[B, L]` per-token keys + ``attention_mask``; `[B]` per-sequence keys). For
the device we convert to a *packed* layout — flat `[T_pad]` token stream with
``segment_ids`` (1-based; 0 marks padding) and ``positions`` — padded up to a
static bucket size so XLA compiles one kernel per bucket instead of one per
shape. Attention uses segment-id masking, the TPU analog of cu_seqlens varlen
attention (reference areal/utils/data.py:245-300).
"""

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from areal_tpu.utils import datapack

Batch = Dict[str, np.ndarray]

# Default bucket ladder: multiples of 256 up to 8k then powers of two. Static
# shapes are what lets XLA tile the MXU without recompiling per batch.
_BUCKET_QUANTUM = 256
# The trainer's ladder of row lengths: this times 1, 2, 4, ... (times the
# sequence-parallel degree). Every rung a run meets is one more grad program
# to compile (20-35 s on a v5e) and, from the cache, to trace and load
# (1.2 s each, measured: PERF.md, PR 30), so the ladder is coarse: two rungs
# to a 4096-token row.
ROW_QUANTUM = 2048


def next_bucket_size(n: int, quantum: int = _BUCKET_QUANTUM) -> int:
    """Smallest bucket >= n: quantized to `quantum` below 8192, else pow2."""
    n = max(int(n), 1)
    if n <= 8192:
        return ((n + quantum - 1) // quantum) * quantum
    out = 8192
    while out < n:
        out *= 2
    return out


def pad_sequences_to_tensors(
    sequences: List[np.ndarray], pad_value: float = 0.0
) -> Dict[str, np.ndarray]:
    """Stack ragged 1-D arrays into [B, L_max] + attention_mask."""
    if not sequences:
        return dict(input_ids=np.zeros((0, 0), np.int32), attention_mask=np.zeros((0, 0), np.bool_))
    max_len = max(len(s) for s in sequences)
    out = np.full((len(sequences), max_len), pad_value, dtype=np.asarray(sequences[0]).dtype)
    mask = np.zeros((len(sequences), max_len), dtype=np.bool_)
    for i, s in enumerate(sequences):
        out[i, : len(s)] = s
        mask[i, : len(s)] = True
    return dict(input_ids=out, attention_mask=mask)


# Per-key pad values for keys where the generic pad_value would be a *valid*
# data value: 'versions' uses -1 as the "padding / not generated" sentinel —
# padding with 0 would masquerade as weight-version-0 tokens under any
# staleness filter.
_KEY_PAD_VALUES = {"versions": -1, "mm_index": -1, "denoise_state": -1}
# per-sequence multimodal payloads: axis 1 is patches, not tokens
_PER_SEQ_PAYLOAD_KEYS = {
    "pixel_values", "image_grid_thw", "vis_seg", "vis_pos_h", "vis_pos_w",
}


def concat_padded_tensors(
    batches: List[Batch], pad_value: float = 0.0
) -> Batch:
    """Concatenate padded batches along B, re-padding to the common max length
    (reference areal/utils/data.py:120)."""
    batches = [b for b in batches if b]
    if not batches:
        return {}
    keys = set(batches[0].keys())
    for b in batches[1:]:
        if set(b.keys()) != keys:
            raise ValueError(f"key mismatch: {keys} vs {set(b.keys())}")
    # per-token keys track the padded token axis; known per-sequence
    # payload keys (VLM pixel tensors — possibly ragged across batches)
    # pad their own axis-1 to the common max instead. Explicit
    # classification: a payload whose axis-1 happens to equal the token
    # width must not be token-padded.
    per_token_keys = {
        k
        for k in keys
        if k not in _PER_SEQ_PAYLOAD_KEYS
        and np.asarray(batches[0][k]).ndim >= 2
        and np.asarray(batches[0][k]).shape[1]
        == np.asarray(batches[0]["attention_mask"]).shape[1]
    }
    max_len = max(np.asarray(b["attention_mask"]).shape[1] for b in batches)
    out: Batch = {}
    for k in keys:
        parts = []
        if k in _PER_SEQ_PAYLOAD_KEYS:
            dim1 = max(np.asarray(b[k]).shape[1] for b in batches)
        for b in batches:
            v = np.asarray(b[k])
            if k in per_token_keys and v.shape[1] < max_len:
                pad_width = [(0, 0), (0, max_len - v.shape[1])] + [(0, 0)] * (v.ndim - 2)
                fill = _KEY_PAD_VALUES.get(
                    k, False if v.dtype == np.bool_ else pad_value
                )
                v = np.pad(v, pad_width, constant_values=fill)
            elif k in _PER_SEQ_PAYLOAD_KEYS and v.shape[1] < dim1:
                pad_width = [(0, 0), (0, dim1 - v.shape[1])] + [(0, 0)] * (
                    v.ndim - 2
                )
                v = np.pad(v, pad_width, constant_values=0)
            parts.append(v)
        out[k] = np.concatenate(parts, axis=0)
    return out


def sample_uid(item: Any) -> str:
    """Stable id of one dataset item for used-data tracking (reference
    realhf/base/recover.py hashes consumed samples so a resumed run never
    trains one twice). Prefers an explicit id field; otherwise hashes a
    canonical JSON view of the item (arrays → bytes)."""
    import hashlib
    import json as _json

    if isinstance(item, dict):
        for k in ("qid", "uid", "id", "task_id", "query_id"):
            if item.get(k) is not None:
                return f"{k}:{item[k]}"

    def norm(v):
        if isinstance(v, np.ndarray):
            return ["<nd>", v.shape, str(v.dtype),
                    hashlib.blake2b(v.tobytes(), digest_size=8).hexdigest()]
        if isinstance(v, dict):
            return {str(k): norm(x) for k, x in sorted(v.items())}
        if isinstance(v, (list, tuple)):
            return [norm(x) for x in v]
        if isinstance(v, (str, int, float, bool)) or v is None:
            return v
        return repr(v)

    blob = _json.dumps(norm(item), sort_keys=True).encode()
    return hashlib.blake2b(blob, digest_size=16).hexdigest()


def batch_select(batch: Batch, indices: Sequence[int]) -> Batch:
    idx = np.asarray(indices, dtype=np.int64)
    return {k: np.asarray(v)[idx] for k, v in batch.items()}


def batch_size(batch: Batch) -> int:
    return int(np.asarray(next(iter(batch.values()))).shape[0])


def trim_batch(batch: Batch) -> Batch:
    """Drop fully-padded tail columns (keeps padded layout minimal)."""
    mask = np.asarray(batch["attention_mask"])
    if mask.size == 0:
        return batch
    lens = mask.sum(1)
    max_len = int(lens.max()) if len(lens) else 0
    out = {}
    for k, v in batch.items():
        v = np.asarray(v)
        out[k] = (
            v[:, :max_len]
            if k not in _PER_SEQ_PAYLOAD_KEYS
            and v.ndim >= 2
            and v.shape[1] >= max_len
            else v
        )
    return out


@dataclasses.dataclass
class PackedBatch:
    """Flat packed device layout. All per-token arrays have shape [T_pad].

    ``segment_ids`` is 1-based per sequence with 0 on padding; ``positions``
    restart at 0 per sequence. ``seq_lens`` has shape [B_pad]; rows past
    ``num_seqs`` are padding (a row within ``num_seqs`` may legitimately have
    length 0). Extra per-token keys (loss_mask, logprobs, ...) live in
    ``per_token``; per-sequence keys (rewards, ...) in ``per_seq``.
    """

    tokens: np.ndarray
    segment_ids: np.ndarray
    positions: np.ndarray
    seq_lens: np.ndarray
    num_seqs: int
    per_token: Dict[str, np.ndarray]
    per_seq: Dict[str, np.ndarray]

    @property
    def total_tokens(self) -> int:
        return int((self.segment_ids > 0).sum())

    @property
    def n_seqs(self) -> int:
        return self.num_seqs


def pack_batch(
    batch: Batch,
    pad_to: Optional[int] = None,
    pad_seqs_to: Optional[int] = None,
) -> PackedBatch:
    """Padded [B, L] batch → packed flat layout (reference data.py:245
    `pack_tensor_dict`, re-shaped for static TPU buckets)."""
    mask = np.asarray(batch["attention_mask"]).astype(bool)
    bsz, _ = mask.shape
    lens = mask.sum(1).astype(np.int32)
    total = int(lens.sum())
    t_pad = pad_to if pad_to is not None else next_bucket_size(total)
    if t_pad < total:
        raise ValueError(f"pad_to={t_pad} < total tokens {total}")
    b_pad = pad_seqs_to if pad_seqs_to is not None else bsz
    flat_idx = np.nonzero(mask.reshape(-1))[0]

    def _pack_tok(v: np.ndarray, fill=0) -> np.ndarray:
        flat = v.reshape((-1,) + v.shape[2:])[flat_idx]
        out_shape = (t_pad,) + flat.shape[1:]
        out = np.full(out_shape, fill, dtype=flat.dtype)
        out[:total] = flat
        return out

    tokens = _pack_tok(np.asarray(batch["input_ids"]))
    seg = np.zeros(t_pad, dtype=np.int32)
    pos = np.zeros(t_pad, dtype=np.int32)
    off = 0
    for i, L in enumerate(lens):
        seg[off : off + L] = i + 1
        pos[off : off + L] = np.arange(L)
        off += int(L)
    seq_lens = np.zeros(b_pad, dtype=np.int32)
    seq_lens[:bsz] = lens
    per_token, per_seq = {}, {}
    for k, v in batch.items():
        if k in ("input_ids", "attention_mask"):
            continue
        v = np.asarray(v)
        if v.ndim >= 2 and v.shape[:2] == mask.shape:
            per_token[k] = _pack_tok(v, fill=_KEY_PAD_VALUES.get(k, 0))
        else:
            padded = np.zeros((b_pad,) + v.shape[1:], dtype=v.dtype)
            padded[:bsz] = v
            per_seq[k] = padded
    return PackedBatch(
        tokens=tokens, segment_ids=seg, positions=pos, seq_lens=seq_lens,
        num_seqs=bsz, per_token=per_token, per_seq=per_seq,
    )


def unpack_batch(packed: PackedBatch) -> Batch:
    """Packed → padded (inverse of `pack_batch`, dropping only the padding
    rows past num_seqs — genuine zero-length rows are preserved so per-seq
    values stay aligned)."""
    bsz = packed.num_seqs
    lens = packed.seq_lens[:bsz]
    max_len = int(lens.max()) if bsz else 0
    out_mask = np.zeros((bsz, max_len), np.bool_)
    cu = np.concatenate([[0], np.cumsum(lens)])

    def _unpack(flat: np.ndarray) -> np.ndarray:
        out = np.zeros((bsz, max_len) + flat.shape[1:], dtype=flat.dtype)
        for i, L in enumerate(lens):
            out[i, :L] = flat[cu[i] : cu[i + 1]]
        return out

    batch: Batch = dict(input_ids=_unpack(packed.tokens))
    for i, L in enumerate(lens):
        out_mask[i, :L] = True
    batch["attention_mask"] = out_mask
    for k, v in packed.per_token.items():
        batch[k] = _unpack(v)
    for k, v in packed.per_seq.items():
        batch[k] = v[: bsz]
    return batch


@dataclasses.dataclass
class MicroBatchList:
    """Result of splitting a batch under a token budget (reference
    data.py:339): padded micro-batches plus the index groups, so results can
    be scattered back into original order."""

    mbs: List[Batch]
    groups: List[List[int]]
    forward_indices: List[int]
    row_lens: List[int]  # the row length each microbatch is dispatched at

    def __len__(self):
        return len(self.mbs)


def _ladder(quantum: int, top: Optional[int]):
    """The row lengths there are, ascending: the quantum times 1, 2, 4, ...
    and ``top`` (a row's share of the token budget, which need not be one of
    them) where it falls between two."""
    t = quantum
    while True:
        if top is not None and t // 2 < top < t:
            yield top
        yield t
        t *= 2


def rows_on_ladder(
    lens: np.ndarray, n_rows: int, quantum: int,
    pad_to: Optional[int] = None, top: Optional[int] = None,
) -> Tuple[int, List[List[int]]]:
    """Sequences into at most ``n_rows`` rows, whole, first-fit-decreasing:
    ``(row length, the indices in each row)``. The length is the shortest
    rung of the ladder at which they fit (``pad_to``: that length and no
    other; ``ValueError`` if they do not fit it). A pure function of its
    arguments: the split sizes a microbatch with it, the pack lays the same
    rows out at the length the split found, and every process of a
    multi-host run derives the same."""
    lens = np.asarray(lens, np.int64)
    longest = int(lens.max(initial=0))
    least = max(longest, -(-int(lens.sum()) // n_rows))
    for t in [pad_to] if pad_to else _ladder(quantum, top):
        if t < least:
            continue
        rows = datapack.ffd_allocate(lens, t)
        if len(rows) <= n_rows:
            rows = [sorted(r) for r in rows]
            return t, rows + [[] for _ in range(n_rows - len(rows))]
    raise ValueError(
        f"pad_to={pad_to}: {len(lens)} sequences, longest {longest}, do not"
        f" fit {n_rows} rows of it"
    )


# ------------------------------------------------------------------
# A block-diffusion policy's rows (``ModelConfig.block_diffusion``)
# ------------------------------------------------------------------
# The batch carries a per-token ``denoise_state``: -1 on the prompt (and on
# padding), s in [0, K) on a completion token that the decoding trace
# revealed in its block's state s (TraceRL's aggregated steps,
# arXiv:2509.06949). A sequence then takes MORE slots of a row than it has
# tokens (BD3-LM's training layout, arXiv:2503.09573): its clean stream, the
# sequence itself, and a NOISY stream a state, a copy of the sequence from
# the first block that holds a completion token to its end, at the same
# positions, with every token not revealed BEFORE that state replaced by the
# mask token. Token i is scored once: in the stream of its own state, at its
# own slot. K is a sequence's own (its largest state + 1).
def _bd_streams(states: np.ndarray, block: int) -> Tuple[int, int]:
    """(the position a noisy stream starts at, K) of one sequence from its
    tokens' ``states`` [L]: the first block that holds a completion token,
    and its largest state + 1; (L, 0) where nothing is to denoise."""
    at = np.nonzero(states >= 0)[0]
    if not len(at):
        return len(states), 0
    return int(at[0]) // block * block, int(states.max()) + 1


def bd_sequence_slots(states: np.ndarray, block: int):
    """One sequence's slots from its tokens' ``states`` [L]: ``(src, stream,
    masked, scored)``, [slots] each: the token a slot holds (its position
    too), its stream (0 clean, s + 1 the noisy stream of state s), whether
    it holds the mask token, whether its token is scored there."""
    states = np.asarray(states, np.int64)
    n = len(states)
    start, k = _bd_streams(states, block)
    tail = np.arange(start, n)
    src = np.concatenate([np.arange(n)] + [tail] * k)
    stream = np.concatenate(
        [np.zeros(n, np.int64)]
        + [np.full(len(tail), s + 1, np.int64) for s in range(k)]
    )
    state = states[src]
    noisy = stream > 0
    return (
        src, stream, noisy & (state >= stream - 1),
        noisy & (state == stream - 1),
    )


def row_lens_of(batch: Batch, bd_block: int = 0) -> np.ndarray:
    """What each sequence of ``batch`` takes of a row: its tokens, or where
    ``bd_block`` is a block-diffusion policy's block length (and the batch
    carries ``denoise_state``) its slots, P + R + K x (R + the border
    block's prompt tokens)."""
    mask = np.asarray(batch["attention_mask"]).astype(bool)
    lens = mask.sum(1).astype(np.int64)
    if not bd_block or "denoise_state" not in batch:
        return lens
    states = np.asarray(batch["denoise_state"])
    out = lens.copy()
    for b, n in enumerate(lens):
        start, k = _bd_streams(states[b, :n], bd_block)
        out[b] += k * (n - start)
    return out


def split_padded_batch_into_mb_list(
    batch: Batch, max_tokens_per_mb: int, min_n_mbs: int = 1,
    n_rows: int = 1, quantum: int = ROW_QUANTUM, bd_block: int = 0,
) -> MicroBatchList:
    """Rows first: first-fit-decreasing of the sequences into rows of a
    microbatch's share of the token budget (``max_tokens_per_mb // n_rows``;
    the longest sequence's length where that is longer, so a sequence is
    never cut), then every ``n_rows`` consecutive rows are one microbatch and
    the rows left over the last. Each microbatch has its OWN row length
    (``row_lens``: ``rows_on_ladder``; log2(budget / quantum) + 1 distinct
    ones at most, whatever a run draws), so the short last one need not pay
    the longest one's row. Rows stay in the order first-fit opened them: the
    last ones hold the short sequences, which spread over ``n_rows`` rows at
    a lower length than any other choice of leftover (rows by fill: 23.6%
    padding against 20.7% over random 16-sequence minibatches of the
    reasoning length law in four rows, 28.6 against 21.3 in eight; PERF.md,
    PR 30). At least ``min_n_mbs`` microbatches (balanced by tokens where the
    rows make fewer). With one row this is the reference's split of sequences
    under a token budget (data.py:401
    `split_padded_tensor_dict_into_mb_list`). ``bd_block``: a block-diffusion
    policy's block length; a sequence then counts its SLOTS against the
    budget (``row_lens_of``), and no row holds more of them than it."""
    lens = row_lens_of(batch, bd_block)
    groups, row_lens = split_lens(
        lens, max_tokens_per_mb, min_n_mbs, n_rows, quantum
    )
    mbs = [trim_batch(batch_select(batch, g)) for g in groups]
    forward_indices = datapack.flat2d(groups)
    return MicroBatchList(
        mbs=mbs, groups=groups, forward_indices=forward_indices,
        row_lens=row_lens,
    )


def split_lens(
    lens: np.ndarray, max_tokens_per_mb: int, min_n_mbs: int = 1,
    n_rows: int = 1, quantum: int = ROW_QUANTUM,
) -> Tuple[List[List[int]], List[int]]:
    """``split_padded_batch_into_mb_list``'s arithmetic, which needs the
    sequences' lengths alone: ``(the indices in each microbatch, each
    microbatch's row length)``."""
    lens = np.asarray(lens, np.int64)
    capacity = max(max_tokens_per_mb // n_rows, int(lens.max(initial=0)))
    rows = datapack.ffd_allocate(lens, capacity)
    groups = [
        datapack.flat2d(rows[i : i + n_rows])
        for i in range(0, len(rows), n_rows)
    ]
    if len(groups) < min(min_n_mbs, len(lens)):
        groups = datapack.partition_balanced(lens, min(min_n_mbs, len(lens)))
    groups = [sorted(g) for g in groups]
    top = -(-capacity // quantum) * quantum
    row_lens = [
        rows_on_ladder(lens[g], n_rows, quantum, top=top)[0] for g in groups
    ]
    return groups, row_lens


@dataclasses.dataclass
class PackedRows:
    """Mesh-ready packed layout: R independent packed streams.

    Rows are sharded over the (data, fsdp) mesh axes and the token dim over
    seq; each row is one packed multi-sequence stream. `row_seqs[r]` lists
    the original batch indices of the sequences packed into row r, in packing
    order (segment id = slot index + 1).
    """

    tokens: np.ndarray  # [R, T] int32
    segment_ids: np.ndarray  # [R, T] int32 (1-based per row; 0 = padding)
    positions: np.ndarray  # [R, T] int32
    per_token: Dict[str, np.ndarray]  # each [R, T, ...]
    per_seq: Dict[str, np.ndarray]  # each [R, S, ...]
    seq_lens: np.ndarray  # [R, S] int32 (0 on empty slots)
    row_seqs: List[List[int]]  # original indices per row
    # a block-diffusion policy's rows only: original index -> the row slot
    # at which each of the sequence's tokens is SCORED, [L] (-1: nowhere, a
    # prompt token); the per-token arrays are laid out at those slots
    token_slots: Optional[Dict[int, np.ndarray]] = None

    @property
    def n_rows(self) -> int:
        return self.tokens.shape[0]

    @property
    def bucket(self) -> int:
        return self.tokens.shape[1]

    @property
    def total_tokens(self) -> int:
        return int((self.segment_ids > 0).sum())


def pack_batch_rows(
    batch: Batch,
    n_rows: int,
    pad_to: Optional[int] = None,
    pad_seqs_to: Optional[int] = None,
    quantum: int = ROW_QUANTUM,
    bd: Optional[Tuple[int, int]] = None,
) -> PackedRows:
    """Pack a padded [B, L] batch into R packed streams.

    The device-facing layout for SPMD training: rows shard over data
    parallelism, tokens over sequence parallelism, every shape static. The
    rows and their length are ``rows_on_ladder``'s: no row exceeds the
    length, which is ``pad_to`` or the shortest rung of `quantum` x 1, 2, 4,
    ... (callers pass ``ROW_QUANTUM`` x seq_parallel so the token axis splits
    evenly across the seq mesh axis) at which the sequences fit.

    ``bd`` = (block length, mask token id) of a block-diffusion policy: a
    sequence is laid out as its SLOTS (``bd_sequence_slots``: the clean
    stream, then a noisy stream a state). ``tokens`` are the masked inputs,
    ``positions`` the token's own in its sequence in every stream, and
    ``per_token`` gains ``bd_stream`` (0 clean, 1..K), ``bd_labels`` (the
    token a slot stands for) and ``bd_scored`` (the slots whose token is
    scored there); every other per-token array holds a token's value at the
    slot where the token is scored and its padding value elsewhere, so a
    loss over the row's slots is the loss over the sequences' tokens.
    """
    mask = np.asarray(batch["attention_mask"]).astype(bool)
    lens = mask.sum(1).astype(np.int32)
    skip = ("input_ids", "attention_mask")
    slots = None
    if bd is not None:
        from areal_tpu.ops import bd_attention

        if "denoise_state" not in batch:
            raise ValueError(
                "a block-diffusion policy's batch carries a per-token "
                "denoise_state"
            )
        block, mask_id = bd
        states = np.asarray(batch["denoise_state"])
        slots = [
            bd_sequence_slots(states[b, :n], block)
            for b, n in enumerate(lens)
        ]
        skip += ("denoise_state",)
    row_lens = lens if slots is None else np.asarray(
        [len(s[0]) for s in slots], np.int64
    )
    t_pad, row_groups = rows_on_ladder(row_lens, n_rows, quantum, pad_to)
    # bucketed (multiples of 8) so the per-seq dim doesn't force a fresh
    # compile for every distinct sequence count
    s_pad = pad_seqs_to if pad_seqs_to is not None else next_bucket_size(
        max(1, max(len(g) for g in row_groups)), 8
    )
    if slots is not None and (
        max(len(g) for g in row_groups) >= bd_attention.MAX_SEQS
        or int(lens.max(initial=0)) > block * bd_attention.MAX_BLOCKS
        or int(states.max(initial=-1)) + 1 >= bd_attention.MAX_STREAMS
    ):
        raise ValueError(
            "a block-diffusion row holds fewer than "
            f"{bd_attention.MAX_SEQS} sequences of at most "
            f"{bd_attention.MAX_BLOCKS} blocks and fewer than "
            f"{bd_attention.MAX_STREAMS} states (ops/bd_attention.py)"
        )

    per_token_keys = [
        k
        for k, v in batch.items()
        if k not in skip
        and k not in _PER_SEQ_PAYLOAD_KEYS
        and np.asarray(v).ndim >= 2
        and np.asarray(v).shape[:2] == mask.shape
    ]
    per_seq_keys = [
        k for k in batch if k not in skip and k not in per_token_keys
    ]

    ids = np.asarray(batch["input_ids"])
    tokens = np.zeros((n_rows, t_pad), np.int32)
    seg = np.zeros((n_rows, t_pad), np.int32)
    pos = np.zeros((n_rows, t_pad), np.int32)
    seq_lens = np.zeros((n_rows, s_pad), np.int32)
    per_token = {
        k: np.full(
            (n_rows, t_pad) + np.asarray(batch[k]).shape[2:],
            _KEY_PAD_VALUES.get(k, 0),
            np.asarray(batch[k]).dtype,
        )
        for k in per_token_keys
    }
    per_seq = {
        k: np.zeros(
            (n_rows, s_pad) + np.asarray(batch[k]).shape[1:],
            np.asarray(batch[k]).dtype,
        )
        for k in per_seq_keys
    }
    token_slots: Optional[Dict[int, np.ndarray]] = None
    if slots is not None:
        token_slots = {}
        for k in ("bd_stream", "bd_labels", "bd_scored"):
            per_token[k] = np.zeros((n_rows, t_pad), np.int32)
    for r, group in enumerate(row_groups):
        off = 0
        for slot, b in enumerate(group):
            L = int(lens[b])
            # a slot's token and where a token's own values go: every slot of
            # a causal model's row, the scored slots of a block-diffusion one
            src, own = slice(0, L), slice(None)
            if slots is not None:
                src, stream, masked, own = slots[b]
            row = slice(off, off + int(row_lens[b]))
            tokens[r, row] = ids[b, src]
            seg[r, row] = slot + 1
            pos[r, row] = np.arange(L)[src]
            seq_lens[r, slot] = L
            for k in per_token_keys:
                per_token[k][r, row][own] = np.asarray(batch[k])[b, src][own]
            for k in per_seq_keys:
                per_seq[k][r, slot] = np.asarray(batch[k])[b]
            if slots is not None:
                per_token["bd_labels"][r, row] = tokens[r, row]
                tokens[r, row][masked] = mask_id
                per_token["bd_stream"][r, row] = stream
                per_token["bd_scored"][r, row] = own
                where = np.full(L, -1, np.int64)
                where[src[own]] = off + np.nonzero(own)[0]
                token_slots[b] = where
            off = row.stop
    return PackedRows(
        tokens=tokens, segment_ids=seg, positions=pos,
        per_token=per_token, per_seq=per_seq, seq_lens=seq_lens,
        row_seqs=row_groups, token_slots=token_slots,
    )


def unpack_rows_per_token(
    packed: PackedRows, values: np.ndarray, pad_value: float = 0.0
) -> np.ndarray:
    """[R, T, ...] per-token device output → padded [B, L, ...] in original
    batch order. A block-diffusion policy's rows hold a value a SLOT: a
    token reads the slot at which it is scored (``PackedRows.token_slots``),
    a token scored nowhere ``pad_value``."""
    if packed.token_slots is not None:
        where = packed.token_slots
        out = np.full(
            (len(where), max((len(w) for w in where.values()), default=0))
            + values.shape[2:], pad_value, dtype=values.dtype,
        )
        for r, group in enumerate(packed.row_seqs):
            for b in group:
                at = where[b]
                out[b, np.nonzero(at >= 0)[0]] = values[r, at[at >= 0]]
        return out
    lens_flat: Dict[int, int] = {}
    for r, group in enumerate(packed.row_seqs):
        for slot, b in enumerate(group):
            lens_flat[b] = int(packed.seq_lens[r, slot])
    bsz = len(lens_flat)
    max_len = max(lens_flat.values()) if bsz else 0
    out = np.full(
        (bsz, max_len) + values.shape[2:], pad_value, dtype=values.dtype
    )
    for r, group in enumerate(packed.row_seqs):
        off = 0
        for slot, b in enumerate(group):
            L = int(packed.seq_lens[r, slot])
            out[b, :L] = values[r, off : off + L]
            off += L
    return out


def reorder_back(values: np.ndarray, forward_indices: List[int]) -> np.ndarray:
    """Scatter per-sequence results of concatenated micro-batches back into
    the original batch order."""
    out = np.empty_like(values)
    out[np.asarray(forward_indices)] = values
    return out
