"""Traffic is a pure function of the traffic file and the seed: every
seed gets the same set of sizes, with the stated clips and medians, in
another order."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, traffic


def params(name):
    with open(os.path.join(manifest.BENCH_DIR, "traffic", f"{name}.json")) as f:
        return traffic.effective(json.load(f), rehearsal=False)


PPO = params("ppo-packed")
BIG = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


@pytest.mark.parametrize("name", manifest.list_names("traffic"))
def test_traffic_file_names_a_kind_a_runner_and_a_reason(name):
    p = params(name)
    assert p["kind"] == "ppo_batches"
    assert manifest.NAME_RE.match(p["runner"]) and p["why"]
    assert isinstance(p["lengths_seed"], int)


def test_ppo_batches_same_seed_same_inputs():
    a, b = (traffic.ppo_batches(PPO, BIG, 151936) for _ in range(2))
    for x, y in zip(a, b):
        assert x["ids"] == y["ids"]
        for k in ("packed_input_ids", "rewards", "noise_behav", "noise_ref"):
            assert np.array_equal(x[k], y[k])


def test_ppo_batches_other_seed_same_batches_other_tokens():
    """A seed changes what the lengths carry, never a length: each batch
    keeps its sequences in their drawn order, so it packs into the same
    micro-batch shapes, and the compiled programs of one seed serve all."""
    drawn = [[s["prompt_len"] + s["resp_len"] for s in b]
             for b in traffic.ppo_batch_lengths(PPO)]
    a = {x["batch"]: x for x in traffic.ppo_batches(PPO, 1, 151936)}
    b = {x["batch"]: x for x in traffic.ppo_batches(PPO, BIG, 151936)}
    assert sorted(a) == sorted(b) == list(range(PPO["pool_batches"]))
    for i, lens in enumerate(drawn):
        assert a[i]["seqlens"] == b[i]["seqlens"] == lens
        assert not np.array_equal(a[i]["packed_input_ids"], b[i]["packed_input_ids"])


def test_ppo_batches_arrive_in_the_seeds_order():
    orders = {tuple(x["batch"] for x in traffic.ppo_batches(PPO, s, 1000))
              for s in range(2**31, 2**31 + 12)}
    assert len(orders) > 4  # the seed, not the file, orders the batches
    assert all(sorted(o) == list(range(PPO["pool_batches"])) for o in orders)


def test_ppo_lengths_have_the_stated_clips_and_median():
    pool = traffic.ppo_batch_lengths(dict(PPO, pool_batches=64))
    resp = np.array([s["resp_len"] for b in pool for s in b])
    prompt = np.array([s["prompt_len"] for b in pool for s in b])
    lo, hi = PPO["response_len_clip"]
    assert resp.min() >= lo and resp.max() <= hi
    assert prompt.min() >= 128 and prompt.max() <= 1024
    assert 850 <= np.median(resp) <= 1200  # lognormal, median 1024
    assert (prompt + resp).max() <= 8192
    clipped = [s for b in pool for s in b if s["clipped"]]
    assert clipped and all(s["resp_len"] == hi for s in clipped)


def test_ppo_batch_holds_the_step_budget_and_groups_share_a_prompt():
    for b in traffic.ppo_batches(PPO, 3, 1000):
        assert PPO["tokens_per_step"] <= b["n_tokens"] < PPO["tokens_per_step"] + 8192
        assert b["packed_input_ids"].shape == b["prompt_mask"].shape == (b["n_tokens"],)
        assert len(b["rewards"]) == len(b["seqlens"]) == len(b["seq_no_eos_mask"])
        assert set(np.abs(b["rewards"])) == {PPO["reward_abs"]}
        offs = np.concatenate([[0], np.cumsum(b["seqlens"])])
        by_group = {}
        for i, sid in enumerate(b["ids"]):
            head = tuple(b["packed_input_ids"][offs[i]: offs[i] + b["prompt_lens"][i]])
            assert by_group.setdefault(sid.split("/")[0], head) == head


def test_rehearsal_overrides_replace_only_what_they_name():
    with open(os.path.join(manifest.BENCH_DIR, "traffic", "ppo-packed.json")) as f:
        raw = json.load(f)
    toy = traffic.effective(raw, rehearsal=True)
    assert toy["tokens_per_step"] < PPO["tokens_per_step"]
    assert toy["reward_abs"] == PPO["reward_abs"] and "rehearsal" not in toy


@pytest.mark.parametrize("batch", range(PPO["pool_batches"]))
def test_ppo_batch_packs_densely_under_the_launchers_rows(batch):
    """Rows as long as the longest sequence rounded up to 128 (the
    launcher's default) hold a 16k-token half of a batch with at most a
    third of padding; a sequence never passes a row of 8192."""
    from areal_tpu.base import datapack

    lens = [s["prompt_len"] + s["resp_len"]
            for s in traffic.ppo_batch_lengths(PPO)[batch]]
    half, total = [], 0
    for l in lens:  # the first minibatch: sequences up to half the tokens
        if total + l > sum(lens) / 2 and half:
            break
        half.append(l)
        total += l
    rows, row_len = datapack.pack_shape(half, row_len_multiple=128)
    assert row_len % 128 == 0 and max(half) <= row_len <= 8192
    assert sum(half) / (rows * row_len) >= 2 / 3
