"""The yardstick's arithmetic: model FLOPs against a hand count, and each
reader on evidence with a known answer."""

import json
import os

import pytest

from benchmark import flops, manifest
from benchmark.readers import (flops_rate, memory_stats, span_max, span_sum,
                               trace_category_share, trace_idle)


def hf(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
        return manifest.hf_config(json.load(f), rehearsal=False)


def test_model_flops_against_a_hand_count_for_the_d12():
    # Per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536, three MLP
    # matrices 1536x8960. Head 1536x151936 once; the embedding lookup is free.
    per_layer = 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960
    assert per_layer == 46_792_704
    dense = 12 * per_layer + 1536 * 151936
    assert dense == 794_886_144
    m = flops.matmul_params(hf("qwen2.5-1.5b-d12"))
    assert (m["per_layer"], m["head"], m["q_dim"], m["layers"]) == (
        per_layer, 1536 * 151936, 1536, 12)
    lens = [1000, 3000]
    want = sum(6 * dense * l + 6 * 12 * 1536 * l * l for l in lens)
    assert flops.train_flops(hf("qwen2.5-1.5b-d12"), lens) == pytest.approx(want, rel=1e-12)
    assert flops.train_flops_from_sums(
        hf("qwen2.5-1.5b-d12"), 4000, 1000**2 + 3000**2) == pytest.approx(want, rel=1e-12)


def test_parameter_counts_of_the_whole_model():
    """All 28 layers of Qwen2.5-1.5B: 1.31 B in the layers + 0.233 B head."""
    m = flops.matmul_params(dict(hf("qwen2.5-1.5b-d12"), num_hidden_layers=28))
    assert m["per_layer"] == 1536 * (1536 + 2 * 256) + 1536 * 1536 + 3 * 1536 * 8960
    assert 1.5e9 < m["layers"] * m["per_layer"] + m["head"] < 1.6e9


def test_flops_rate_is_a_share_of_the_published_peak():
    ev = dict(work=dict(tokens=1e4, sum_len_sq=0.0, elapsed_s=1.0), chips=1,
              peaks={"bf16_flops_per_s": 197e12}, hf_config=hf("qwen2.5-1.5b-d12"))
    assert flops_rate.read(ev) == pytest.approx(100 * 6 * 794_886_144 * 1e4 / 197e12)
    assert flops_rate.read(dict(ev, work=None)) is None
    assert flops_rate.read(dict(ev, chips=4)) == pytest.approx(flops_rate.read(ev) / 4)


def test_span_readers():
    spans = [dict(name="train_step", start=0.0, end=3.0),
             dict(name="train_batch", start=0.5, end=1.5),
             dict(name="train_batch", start=1.75, end=2.75),
             dict(name="train_step", start=3.0, end=7.0),
             dict(name="train_batch", start=3.5, end=6.5)]
    ev = dict(spans=spans)
    assert span_max.read(ev, span="train_step") == 4.0
    assert span_sum.read(ev, span="train_step", minus=["train_batch"], scale=1000.0) == 1000.0
    assert span_sum.read(ev, span="train_batch") == pytest.approx(5.0 / 3)
    assert span_max.read(dict(spans=[]), span="train_step") is None
    assert span_sum.read({}, span="train_step") is None


def test_trace_and_memory_readers():
    tr = dict(busy_s=6.0, window_s=8.0, category_share={"attention": 0.25})
    assert trace_idle.read(dict(trace=tr)) == 25.0
    assert trace_category_share.read(dict(trace=tr), category="attention") == 25.0
    assert trace_category_share.read(dict(trace=tr), category="collective") == 0.0
    assert trace_idle.read({}) is None and trace_category_share.read({}, category="x") is None
    assert memory_stats.read(dict(memory={"peak_bytes_in_use": 12e9})) == 12.0
    assert memory_stats.read(dict(memory={})) is None


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    got = manifest.read_layer_metrics("q15d12-train-ppo", dict(
        spans=[dict(name="train_step", start=0.0, end=2.0)], trace=None, memory={}))
    assert set(got) == {"train_step_max_s", "ppo_prep_ms"}
    assert got["train_step_max_s"] == {"value": 2.0, "unit": "s"}


@pytest.mark.parametrize("shift,spike,ok", [
    (0.005, 0.05, True),    # bf16-sized noise: passes both limits
    (0.005, 0.5, False),    # one position far off: a mask or position fault
    (0.03, 0.05, False),    # every position a little off: lost precision
])
def test_reference_comparison_holds_a_max_and_a_mean_limit(monkeypatch, shift, spike, ok):
    import sys
    import types

    import numpy as np

    from benchmark import model

    want = np.linspace(-3.0, -1.0, 100).astype(np.float32)
    fake = types.ModuleType("benchmark.reference.fake")
    fake.next_token_logprobs = lambda params, hf, ids, pad_to=None: want
    monkeypatch.setitem(sys.modules, "benchmark.reference.fake", fake)
    got = want + shift
    got[7] += spike
    res = model.compare_with_reference(
        None, {}, "fake", [dict(name="s", token_ids=list(range(101)), first=0, got=got)],
        {"max": 0.1, "mean": 0.015}, pad_to=128)
    assert res["ok"] is ok
    assert res["worst"] == pytest.approx(shift + spike, rel=1e-3)
