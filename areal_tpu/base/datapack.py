"""Sequence packing / partitioning algorithms.

Counterpart of the reference's datapack utilities (realhf/base/datapack.py):
first-fit-decreasing bin packing for token-budget micro-batch splitting and
balanced contiguous partitioning for data-parallel dispatch. Pure numpy —
these run on the host in the control plane, never inside jit.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np


def flat2d(lists: Sequence[Sequence]) -> List:
    return [x for sub in lists for x in sub]


def ffd_allocate(
    lengths: Sequence[int],
    capacity: int,
    min_groups: int = 1,
) -> List[List[int]]:
    """First-fit-decreasing bin packing (dispatches to the native C++
    implementation in csrc/host_ops.cpp when available; this Python body is
    the fallback and the parity reference).

    Partition items with the given `lengths` into bins of at most `capacity`
    total length (a single item longer than capacity gets its own bin),
    producing at least `min_groups` bins. Returns a list of index groups.
    """
    if len(lengths) > 64:  # native pays off only past trivial sizes
        from areal_tpu.ops import host_ops

        # wait=False: never stall the dispatch hot path on a g++ compile —
        # the first calls use the Python body while the .so builds.
        if host_ops.native_available(wait=False):
            return host_ops.ffd_allocate_native(lengths, capacity, min_groups)
    return ffd_allocate_py(lengths, capacity, min_groups)


def ffd_allocate_py(
    lengths: Sequence[int],
    capacity: int,
    min_groups: int = 1,
) -> List[List[int]]:
    """Pure-Python FFD; parity reference for the native path."""
    lengths = np.asarray(lengths)
    order = np.argsort(-lengths, kind="stable")
    groups: List[List[int]] = [[] for _ in range(min_groups)]
    sums = [0] * min_groups
    for idx in order:
        idx = int(idx)
        l = int(lengths[idx])
        # Least-loaded bin with room (keeps the min_groups bins balanced);
        # empty bins always accept, so oversized items get their own bin.
        candidates = [g for g in range(len(groups)) if sums[g] + l <= capacity or not groups[g]]
        if candidates:
            g = min(candidates, key=lambda g: sums[g])
            groups[g].append(idx)
            sums[g] += l
        else:
            groups.append([idx])
            sums.append(l)
    # Drop empty bins (possible when min_groups > n items).
    out = [g for g in groups if g]
    return out


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pack_shape(
    lengths: Sequence[int],
    row_len_multiple: int = 128,
    n_rows_multiple: int = 1,
    max_row_len: int = None,
) -> tuple:
    """(n_rows, row_len) that `models.packing.pack_sequences` will
    allocate for these sequence lengths — the padded [R, T] footprint,
    computable without materializing the pack (mirrors its row_len
    bucketing + FFD row grouping). One divergence: where pack_sequences
    RAISES (a sequence longer than max_row_len), the estimator widens
    the rows to fit it — it is used on inputs the caller may not control
    (telemetry fallback), and must always return a footprint the data
    actually fits, never a >1.0 density."""
    lengths = [int(l) for l in lengths]
    if not lengths:
        raise ValueError("cannot compute pack shape of zero sequences")
    longest = max(lengths)
    row_len = _round_up(max(longest, row_len_multiple), row_len_multiple)
    if max_row_len is not None:
        row_len = min(row_len, _round_up(max_row_len, row_len_multiple))
        row_len = max(row_len, _round_up(longest, row_len_multiple))
    groups = ffd_allocate(lengths, capacity=row_len, min_groups=1)
    n_rows = _round_up(len(groups), n_rows_multiple)
    return n_rows, row_len


def packing_density(
    lengths: Sequence[int],
    row_len_multiple: int = 128,
    n_rows_multiple: int = 1,
    max_row_len: int = None,
) -> float:
    """Tokens per padded token of the FFD pack of `lengths` into rows as
    long as the longest sequence (`pack_shape`): real tokens divided by
    the [R, T] cells. What the trainer engine ships is `ladder_density`."""
    n_rows, row_len = pack_shape(
        lengths, row_len_multiple, n_rows_multiple, max_row_len
    )
    return float(sum(int(l) for l in lengths)) / float(n_rows * row_len)


def ladder_rung(n_tokens: int, row_len_multiple: int = 128) -> int:
    """The shortest row length of the engine's ladder that holds
    `n_tokens`. The rungs are multiples of `row_len_multiple` whose step
    doubles with the length, `row_len_multiple` x 2^(floor(log2(n /
    row_len_multiple)) - 3) and never under `row_len_multiple` (at 128:
    steps of 128 to 2048, 256 to 4096, 512 to 8192, 1024 to 16,384), so
    a rung wastes under an eighth of the row, or under one multiple, and
    a doubling of the length adds eight compiled shapes."""
    n_tokens = max(int(n_tokens), 1)
    return _round_up(n_tokens, _step_at(n_tokens, row_len_multiple))


def _step_at(n_tokens: int, row_len_multiple: int) -> int:
    """The ladder's step at a length of `n_tokens`."""
    return row_len_multiple << max((n_tokens // row_len_multiple).bit_length() - 4, 0)


def ladder_step(row_len: int, row_len_multiple: int = 128) -> int:
    """The ladder's step up to the rung `row_len`: the fullest row of a
    micro-batch packed that long holds more than `row_len - step` tokens
    (`ladder_rung` would have taken a shorter rung otherwise), so its
    padding is under one step. At a multiple of 128 a row of 16,384 has
    a step of 1,024; at a multiple of 16,384 the step is the row."""
    return _step_at(max(int(row_len), 1) - 1, row_len_multiple)


def ladder_shape(
    lengths: Sequence[int],
    row_len_multiple: int = 128,
    n_rows_multiple: int = 1,
    max_row_len: int = None,
) -> tuple:
    """(n_rows, row_len) the trainer engine packs a micro-batch of these
    sequence lengths into: as few rows as hold its tokens, at a row
    length from a short ladder.

    n_rows is `n_rows_multiple` (1 on one chip; the data x fsdp shards of
    a mesh), and more, in multiples of it, only where `max_row_len`
    (rounded up to `row_len_multiple`) forbids rows that long; the
    sequences are balanced over the rows by FFD. row_len is the
    shortest rung (`ladder_rung`) that holds the fullest row, or the
    cap. A function of the lengths and the three settings alone: hand
    its result to `models.packing.pack_sequences(row_len=, n_rows=)`,
    which lays the sequences out by the same FFD. Raises, as that does,
    for a sequence longer than the cap."""
    lengths = [int(l) for l in lengths]
    if not lengths:
        raise ValueError("cannot compute pack shape of zero sequences")
    cap = None
    if max_row_len is not None:
        cap = _round_up(max_row_len, row_len_multiple)
        if max(lengths) > cap:
            raise ValueError(
                f"sequence of length {max(lengths)} exceeds row_len {cap}")
    n_rows = n_rows_multiple
    capacity = sum(lengths) if cap is None else cap
    while True:
        # min_groups: FFD hands each sequence to the emptiest row that
        # has room, so the rows come out balanced.
        groups = ffd_allocate(lengths, capacity=capacity, min_groups=n_rows)
        if len(groups) <= n_rows:
            break
        n_rows = _round_up(len(groups), n_rows_multiple)
    fullest = max(sum(lengths[i] for i in g) for g in groups)
    row_len = ladder_rung(fullest, row_len_multiple)
    return n_rows, row_len if cap is None else min(row_len, cap)


def ladder_density(
    lengths: Sequence[int],
    row_len_multiple: int = 128,
    n_rows_multiple: int = 1,
    max_row_len: int = None,
) -> float:
    """Tokens per padded token of `ladder_shape`'s pack: real tokens
    divided by the [R, T] cells the engine ships to the device. 1.0 = no
    pad waste; the projections, MLP and norms spend a (1 - density)
    share of their time on padding. The estimate behind the
    `packing_efficiency` series of the master's perf history where the
    engine recorded none: it is used on inputs the caller may not
    control, so where the engine would raise (a sequence longer than
    `max_row_len`) it widens the cap to fit and never returns a density
    above 1.0."""
    lengths = [int(l) for l in lengths]
    if max_row_len is not None and lengths:
        max_row_len = max(max_row_len, max(lengths))
    n_rows, row_len = ladder_shape(
        lengths, row_len_multiple, n_rows_multiple, max_row_len)
    return float(sum(lengths)) / float(n_rows * row_len)


def min_abs_diff_partition(nums: Sequence[int], k: int) -> List[List[int]]:
    """Split `nums` into k *contiguous* groups with balanced sums.

    Returns index groups. Used for data-parallel dispatch where sample order
    must be preserved. Greedy prefix walking against the ideal per-group sum;
    guarantees each group is non-empty when len(nums) >= k.
    """
    n = len(nums)
    if k <= 0:
        raise ValueError("k must be positive")
    if n < k:
        raise ValueError(f"cannot partition {n} items into {k} non-empty groups")
    cum = np.cumsum(np.asarray(nums, dtype=np.float64))
    total = cum[-1]
    bounds = [0]
    for g in range(1, k):
        ideal = total * g / k
        j = int(np.searchsorted(cum, ideal))
        # Pick the neighbor closest to the ideal prefix sum, then clamp so
        # every remaining group stays non-empty.
        if j + 1 <= n - (k - g) and j >= 1:
            if abs(cum[j] - ideal) < abs(cum[j - 1] - ideal):
                j = j + 1
        j = max(bounds[-1] + 1, min(j, n - (k - g)))
        bounds.append(j)
    bounds.append(n)
    groups = [list(range(bounds[i], bounds[i + 1])) for i in range(k)]
    assert len(groups) == k and all(groups), [len(g) for g in groups]
    return groups


def balanced_partition(nums: Sequence[int], k: int) -> List[List[int]]:
    """Split into k groups balanced by sum, order-free (greedy LPT)."""
    order = np.argsort(-np.asarray(nums), kind="stable")
    groups: List[List[int]] = [[] for _ in range(k)]
    sums = np.zeros(k)
    for idx in order:
        g = int(np.argmin(sums))
        groups[g].append(int(idx))
        sums[g] += nums[int(idx)]
    return [sorted(g) for g in groups]
