"""Host-side packing of variable-length sequences into static [R, T] rows.

The bridge between `SequenceSample` (packed 1D, fully dynamic) and what XLA
wants (static shapes): sequences are FFD-packed into R rows of T tokens
with segment ids, T bucketed (multiple of `row_len_multiple`, default 128 —
the TPU lane width) so the number of distinct compiled shapes stays small.
The trainer engine picks (R, T) itself (`base.datapack.ladder_shape`: as
few rows as hold the tokens) and hands both to `pack_sequences`.

Counterpart of the reference's packed varlen layout + cu_seqlens handling
(realhf/api/core/data_api.py SequenceSample + flash-attn varlen); on TPU
the row layout replaces cu_seqlens and the segment ids replace the varlen
kernel's sequence boundaries.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from areal_tpu.base import datapack


@dataclasses.dataclass
class SeqSpan:
    """Where sequence `seq_index` of the original flat list landed."""

    seq_index: int
    row: int
    start: int
    length: int


@dataclasses.dataclass
class PackedBatch:
    input_ids: np.ndarray  # [R, T] int32
    segment_ids: np.ndarray  # [R, T] int32; 0 = pad, sequences numbered 1.. per row
    positions: np.ndarray  # [R, T] int32 within-sequence positions
    spans: List[SeqSpan]
    seq_lens: List[int]

    @property
    def n_rows(self) -> int:
        return self.input_ids.shape[0]

    @property
    def row_len(self) -> int:
        return self.input_ids.shape[1]

    @property
    def total_tokens(self) -> int:
        return int(sum(self.seq_lens))

    @property
    def density(self) -> float:
        """Tokens per padded token: real tokens / the [R, T] cells this
        pack ships to the device (the realized packing efficiency; the
        estimator counterpart is `base.datapack.ladder_density`)."""
        return self.total_tokens / float(self.n_rows * self.row_len)

    def scatter_per_token(self, values: Sequence[np.ndarray]) -> np.ndarray:
        """Place per-sequence 1D arrays (flat-list order) into [R, T] rows."""
        first = np.asarray(values[0])
        out = np.zeros(
            (self.n_rows, self.row_len) + first.shape[1:], dtype=first.dtype
        )
        for span in self.spans:
            v = np.asarray(values[span.seq_index])
            assert v.shape[0] == span.length, (v.shape, span)
            out[span.row, span.start : span.start + span.length] = v
        return out

    def gather_per_token(self, rows: np.ndarray) -> List[np.ndarray]:
        """Inverse of scatter: [R, T, ...] -> per-sequence arrays in order."""
        out: List[Optional[np.ndarray]] = [None] * len(self.seq_lens)
        for span in self.spans:
            out[span.seq_index] = np.asarray(
                rows[span.row, span.start : span.start + span.length]
            )
        return out  # type: ignore[return-value]

    def gather_flat(self, rows: np.ndarray) -> np.ndarray:
        """[R, T, ...] -> packed 1D concatenation in original sequence order."""
        return np.concatenate(self.gather_per_token(rows), axis=0)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pack_sequences(
    seqs: Sequence[np.ndarray],
    row_len: Optional[int] = None,
    row_len_multiple: int = 128,
    n_rows_multiple: int = 1,
    max_row_len: Optional[int] = None,
    n_rows: Optional[int] = None,
) -> PackedBatch:
    """FFD-pack sequences into rows.

    row_len: fixed row length; default = longest sequence rounded up to
    `row_len_multiple` (bucketing keeps recompiles bounded).
    n_rows_multiple: pad the row count (empty rows) so R divides evenly
    across data-parallel shards.
    n_rows: exactly this many rows, the sequences balanced over them
    (FFD's emptiest row with room); with `row_len`, the shape
    `base.datapack.ladder_shape` chose, which is how the trainer engine
    packs. Raises if the rows cannot hold the sequences.
    """
    lens = [int(len(s)) for s in seqs]
    if not lens:
        raise ValueError("cannot pack zero sequences")
    longest = max(lens)
    if row_len is None:
        row_len = _round_up(max(longest, row_len_multiple), row_len_multiple)
        if max_row_len is not None:
            row_len = min(row_len, _round_up(max_row_len, row_len_multiple))
    if longest > row_len:
        raise ValueError(f"sequence of length {longest} exceeds row_len {row_len}")

    groups = datapack.ffd_allocate(lens, capacity=row_len, min_groups=n_rows or 1)
    if n_rows is None:
        n_rows = _round_up(len(groups), n_rows_multiple)
    elif len(groups) > n_rows:
        raise ValueError(
            f"{n_rows} rows of {row_len} cannot hold sequences of lengths {lens}")

    input_ids = np.zeros((n_rows, row_len), dtype=np.int32)
    segment_ids = np.zeros((n_rows, row_len), dtype=np.int32)
    positions = np.zeros((n_rows, row_len), dtype=np.int32)
    spans: List[SeqSpan] = []
    for row, group in enumerate(groups):
        cursor = 0
        for seg_num, seq_idx in enumerate(group, start=1):
            l = lens[seq_idx]
            sl = slice(cursor, cursor + l)
            input_ids[row, sl] = np.asarray(seqs[seq_idx], dtype=np.int32)
            segment_ids[row, sl] = seg_num
            positions[row, sl] = np.arange(l, dtype=np.int32)
            spans.append(SeqSpan(seq_index=seq_idx, row=row, start=cursor, length=l))
            cursor += l
        assert cursor <= row_len
    return PackedBatch(
        input_ids=input_ids,
        segment_ids=segment_ids,
        positions=positions,
        spans=spans,
        seq_lens=lens,
    )
