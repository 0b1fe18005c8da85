import json
import os

import numpy as np
import pytest

from areal_tpu.base import datapack
from areal_tpu.models.packing import pack_sequences


def test_pack_roundtrip():
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, 100, size=l) for l in [5, 300, 17, 128, 64, 9]]
    b = pack_sequences(seqs, row_len_multiple=128)
    assert b.row_len % 128 == 0
    rec = b.gather_per_token(b.input_ids)
    for s, r in zip(seqs, rec):
        np.testing.assert_array_equal(s, r)
    # Segment ids: 0 only on padding; positions restart per sequence.
    for span in b.spans:
        seg = b.segment_ids[span.row, span.start : span.start + span.length]
        assert (seg == seg[0]).all() and seg[0] > 0
        pos = b.positions[span.row, span.start : span.start + span.length]
        np.testing.assert_array_equal(pos, np.arange(span.length))


def test_pack_rows_multiple():
    seqs = [np.arange(5)]
    b = pack_sequences(seqs, n_rows_multiple=4)
    assert b.n_rows == 4
    assert (b.segment_ids[1:] == 0).all()


def test_scatter_gather_per_token():
    seqs = [np.arange(4), np.arange(6)]
    b = pack_sequences(seqs, row_len=16)
    vals = [np.full(4, 1.5), np.full(6, 2.5)]
    rows = b.scatter_per_token(vals)
    back = b.gather_per_token(rows)
    np.testing.assert_array_equal(back[0], vals[0])
    np.testing.assert_array_equal(back[1], vals[1])
    flat = b.gather_flat(rows)
    assert flat.shape == (10,)


def test_oversized_raises():
    with pytest.raises(ValueError):
        pack_sequences([np.arange(100)], row_len=64)


# ----------------------------------------------------------------------
# The engine's rule (base/datapack.ladder_shape): as few rows as hold the
# tokens, at a row length from a short ladder.
# ----------------------------------------------------------------------

MULTIPLES = [32, 128, 384, 4096, 16384]


def _seqs(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 100, size=l) for l in lens]


def _lens(n, lo, hi, seed):
    return np.random.RandomState(seed).randint(lo, hi, size=n).tolist()


@pytest.mark.parametrize("multiple", MULTIPLES)
def test_a_rung_holds_the_count_and_wastes_at_most_an_eighth(multiple):
    counts = sorted({1, multiple - 1, multiple, multiple + 1,
                     *np.random.RandomState(multiple).randint(1, 40 * multiple, 400),
                     *(multiple * 2 ** k + d for k in range(6) for d in (-1, 0, 1))})
    rungs = [datapack.ladder_rung(n, multiple) for n in counts]
    for n, rung in zip(counts, rungs):
        assert rung >= n and rung % multiple == 0
        # under an eighth of the row, or under one multiple where the
        # row is shorter than eight of them
        assert rung - n < max(multiple, n / 8)
    assert rungs == sorted(rungs)  # monotone
    # a short ladder: eight rungs a doubling of the length
    assert len(set(rungs)) <= 8 * 6 + 16


def test_the_ladder_at_the_launchers_multiple_is_the_documented_one():
    rung = lambda n: datapack.ladder_rung(n, 128)
    assert [rung(n) for n in (1, 128, 129, 1100, 2048)] == [128, 128, 256, 1152, 2048]
    assert [rung(n) for n in (2049, 4097, 8193, 15600, 16384, 16385)] == [
        2304, 4608, 9216, 16384, 16384, 18432]


@pytest.mark.parametrize("lens", [[5], [300, 17, 128, 64, 9], _lens(14, 700, 1500, 1),
                                  _lens(40, 16, 7168, 2)], ids=["one", "few", "short", "mixed"])
def test_one_row_where_one_chip_has_no_cap(lens):
    n_rows, row_len = datapack.ladder_shape(lens, row_len_multiple=128)
    assert n_rows == 1 and row_len == datapack.ladder_rung(sum(lens), 128)
    b = pack_sequences(_seqs(lens), row_len=row_len, n_rows=n_rows)
    assert b.input_ids.shape == (1, row_len)
    # sequences are numbered 1, 2, .. in row order, padding is 0
    seg = b.segment_ids[0]
    assert list(seg[np.r_[True, seg[1:] != seg[:-1]]]) == [
        *range(1, len(lens) + 1), *([0] if sum(lens) < row_len else [])]
    assert b.density == datapack.ladder_density(lens, row_len_multiple=128)


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_a_meshs_multiple_of_rows_balanced(shards):
    lens = _lens(37, 40, 900, shards)
    n_rows, row_len = datapack.ladder_shape(
        lens, row_len_multiple=32, n_rows_multiple=shards)
    assert n_rows == shards
    b = pack_sequences(_seqs(lens), row_len=row_len, n_rows=n_rows)
    fill = (b.segment_ids > 0).sum(axis=1)
    assert fill.sum() == sum(lens) and fill.min() > 0
    assert fill.max() - fill.min() <= max(lens)  # FFD over the emptiest row
    assert row_len == datapack.ladder_rung(fill.max(), 32)
    # fewer sequences than shards: the empty rows are there
    n_rows, row_len = datapack.ladder_shape([50, 20], 32, n_rows_multiple=shards)
    b = pack_sequences(_seqs([50, 20]), row_len=row_len, n_rows=n_rows)
    assert b.input_ids.shape == (shards, 64)
    assert ((b.segment_ids > 0).sum(axis=1) > 0).sum() == 2


@pytest.mark.parametrize("cap,shards", [(1000, 1), (1024, 1), (2048, 2), (4000, 4), (900, 1)])
def test_rows_no_longer_than_the_operators_cap(cap, shards):
    lens = _lens(30, 100, 900, cap)
    n_rows, row_len = datapack.ladder_shape(
        lens, row_len_multiple=128, n_rows_multiple=shards, max_row_len=cap)
    rounded = -(-cap // 128) * 128
    assert row_len <= rounded and row_len % 128 == 0 and n_rows % shards == 0
    # as few rows as the packer's FFD needs at the cap, in the mesh's multiples
    ffd = len(datapack.ffd_allocate(lens, capacity=rounded, min_groups=shards))
    assert -(-sum(lens) // rounded) <= n_rows == -(-ffd // shards) * shards
    b = pack_sequences(_seqs(lens), row_len=row_len, n_rows=n_rows)
    assert (b.segment_ids > 0).sum() == sum(lens)


@pytest.mark.parametrize("cap", [64, 100])
def test_a_sequence_longer_than_the_cap_still_raises(cap):
    with pytest.raises(ValueError, match="exceeds row_len"):
        datapack.ladder_shape([30, 200], row_len_multiple=32, max_row_len=cap)
    # the estimate behind the telemetry widens instead, and stays a density
    assert 0 < datapack.ladder_density([30, 200], 32, max_row_len=cap) <= 1.0
    with pytest.raises(ValueError, match="exceeds row_len"):
        pack_sequences(_seqs([30, 200]), row_len=128, n_rows=1)
    with pytest.raises(ValueError, match="cannot hold"):
        pack_sequences(_seqs([100, 100]), row_len=128, n_rows=1)


@pytest.mark.parametrize("multiple,shards,cap", [
    (128, 1, None), (32, 1, None), (32, 4, None), (128, 2, 1024), (16384, 1, None)])
def test_every_sequence_comes_back_from_the_rows(multiple, shards, cap):
    lens = _lens(23, 3, 800, multiple + shards)
    seqs = _seqs(lens, seed=7)
    n_rows, row_len = datapack.ladder_shape(lens, multiple, shards, cap)
    b = pack_sequences(seqs, row_len=row_len, n_rows=n_rows)
    for s, r in zip(seqs, b.gather_per_token(b.input_ids)):
        np.testing.assert_array_equal(s, r)
    values = [np.full(l, i + 0.5) for i, l in enumerate(lens)]
    for v, r in zip(values, b.gather_per_token(b.scatter_per_token(values))):
        np.testing.assert_array_equal(v, r)
    for span in b.spans:
        pos = b.positions[span.row, span.start: span.start + span.length]
        np.testing.assert_array_equal(pos, np.arange(span.length))


def test_without_a_row_length_the_packer_is_what_it_was():
    """`pack_sequences` / `pack_shape` with no row length: rows as long
    as the longest sequence, which the benchmark's accepted tests and
    other callers count on."""
    lens = [600, 590, 300]
    b = pack_sequences(_seqs(lens), row_len_multiple=128)
    assert b.input_ids.shape == (3, 640) == datapack.pack_shape(lens, 128)
    assert datapack.packing_density(lens, 128) == b.density
    assert datapack.ladder_shape(lens, 128) == (1, 1536)


# ----------------------------------------------------------------------
# The rule on the benchmark's pools (counts, exact on a CPU).
# ----------------------------------------------------------------------


def pool_micro_batches(traffic_name, pool_batches=None):
    """The sequence lengths of every micro-batch the benchmark's train
    runner makes of a traffic file's pool: (those of the train steps, a
    batch split into the interface's minibatches and each at the token
    budget; those of the forward pass, the batch split at the budget)."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from benchmark import manifest, traffic

    with open(os.path.join(manifest.BENCH_DIR, "traffic", f"{traffic_name}.json")) as f:
        p = traffic.effective(json.load(f), rehearsal=False)
    if pool_batches is not None:
        p["pool_batches"] = pool_batches
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=int(p["ppo"]["max_tokens_per_mb"]))
    train, fwd = [], []
    for i, seqs in enumerate(traffic.ppo_batch_lengths(p)):
        lens = [s["prompt_len"] + s["resp_len"] for s in seqs]
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(lens))], seqlens=lens,
            data={"packed_input_ids": np.zeros(sum(lens), np.int32)})
        fwd += [mb.seqlens_of() for mb in batch.split(budget)[0]]
        for mini in batch.split(MicroBatchSpec(n_mbs=int(p["ppo"]["n_minibatches"])))[0]:
            train += [mb.seqlens_of() for mb in mini.split(budget)[0]]
    return train, fwd


def _cells(shapes):
    return sum(r * t for r, t in shapes)


@pytest.mark.parametrize("traffic_name", ["ppo-packed-long", "ppo-packed-long-2b"])
def test_at_a_multiple_of_16384_the_long_pools_are_one_row_of_16384(traffic_name):
    train, fwd = pool_micro_batches(traffic_name)
    assert {datapack.ladder_shape(l, row_len_multiple=16384) for l in train + fwd} == {
        (1, 16384)} == {datapack.pack_shape(l, row_len_multiple=16384) for l in train + fwd}


@pytest.mark.parametrize("traffic_name,was,fb_most,fw_most", [
    ("ppo-packed", (87.58, 15, 12), 8, 5), ("ppo-packed-short", (82.61, 10, 12), 6, 5)])
def test_the_qwen_pools_pack_densely_into_few_shapes(traffic_name, was, fb_most, fw_most):
    train, fwd = pool_micro_batches(traffic_name)
    tokens = sum(map(sum, train))
    old = [[datapack.pack_shape(l, 128) for l in mbs] for mbs in (train, fwd)]
    new = [[datapack.ladder_shape(l, 128) for l in mbs] for mbs in (train, fwd)]
    assert (round(100 * tokens / _cells(old[0]), 2), len(set(old[0])), len(set(old[1]))) == was
    assert 100 * tokens / _cells(new[0]) >= 96.0
    assert len(set(new[0])) <= fb_most and len(set(new[1])) <= fw_most
    assert all(r == 1 for r, _ in new[0] + new[1])
    # the largest micro-batch is no larger than it was
    assert max(r * t for r, t in new[0]) == 16384 <= max(r * t for r, t in old[0])


def test_shapes_over_a_thousand_batches_of_the_ppo_distribution():
    """ROADMAP S9's count: the distinct (rows, row length) a deployment
    compiles a program for, over a thousand batches drawn as
    `ppo-packed` draws its four."""
    train, fwd = pool_micro_batches("ppo-packed", pool_batches=1000)
    old = {datapack.pack_shape(l, 128) for l in train + fwd}
    new = {datapack.ladder_shape(l, 128) for l in train + fwd}
    tokens = sum(map(sum, train))
    density = lambda rule: 100 * tokens / _cells([rule(l, 128) for l in train])
    print(f"a thousand batches: {len(train)} train micro-batches; shapes old "
          f"{len(old)} new {len(new)}; density old {density(datapack.pack_shape):.2f} "
          f"new {density(datapack.ladder_shape):.2f}")
    assert len(old) > 200 and len(new) <= 40  # the ladder has 40 rungs to 16,384
    assert density(datapack.ladder_shape) >= 96.0 > density(datapack.pack_shape)


@pytest.mark.parametrize("multiple", [16, 128, 8192, 16384])
def test_a_rungs_step_bounds_what_its_row_pads(multiple):
    """`ladder_step(rung)` is the step `ladder_rung` took up to that rung:
    every count of tokens that lands on a rung is within one step of it,
    and some count is a whole step short but one. What the engine reads a
    row's chance of an empty band from (`JaxTrainEngine._dead_bands`)."""
    from areal_tpu.base.datapack import ladder_rung, ladder_step

    worst = {}
    for n in range(1, 40000, 7):
        rung = ladder_rung(n, multiple)
        assert rung - ladder_step(rung, multiple) < n <= rung
        worst[rung] = max(worst.get(rung, 0), rung - n)
    assert ladder_step(16384, 128) == 1024 and ladder_step(16384, 16384) == 16384
    assert ladder_step(8192, 8192) == 8192 and ladder_step(1280, 128) == 128
    full = [r for r in worst if r > 8 * multiple and r < 30000]
    assert all(worst[r] > ladder_step(r, multiple) - 8 for r in full)
