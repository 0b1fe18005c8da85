"""The metrics that split a step's `device.starved` time by what the host
was doing (PR 52): the reader `program_span_overlap_ms` on a hand-made
span list (clipping to the step's root, the same-thread rule, the rest,
None without a `within` span, the parts summing to the whole), the five
metric files against their `BENCHMARK.json` entries, and the spans of
real PPO steps on the CPU through `manifest.read_layer_metrics`."""

import json
import os

import pytest

from benchmark import manifest
from benchmark.readers import program_span_overlap_ms

METRICS = ["train_starved_ms", "train_starved_input_ms", "train_starved_enqueue_ms",
           "train_starved_host_ms", "train_starved_unspanned_ms"]
PARTS = METRICS[1:]
MS = 1_000_000


def metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def span(name, trace, start_ms, end_ms, tid=1):
    return dict(kind="span", name=name, trace=trace, span=f"{name}@{start_ms}", parent=None,
                start_ns=int(start_ms * MS), end_ns=int(end_ms * MS), tid=tid)


# Two steps on thread 1, the prefetcher on thread 2.
# Step t1 (0-100 ms), a session's first: a stretch after the prep's read,
#   10-30: gather 10-12, advantages 12-15, 1 ms under no leaf, begin 16-20,
#   wait_input 20-27, 1 ms under no leaf, dispatch 28-31 of which 2 ms
#   before the enqueue returned; the stage's pack and h2d on thread 2
#   cover 18-27 and count for nothing.
# Its last fetch ends at 90; the stretch from there (stats 92-99) ends in
# step t2 (200-300 ms) at 206: prep.pack 200-202, prep.h2d 202-205,
# prep.dispatch 205-207. It is recorded in t2's trace; each step takes the
# part inside its own root.
HAND_MADE = dict(spans=[
    span("ppo.train_step", "t1", 0, 100),
    span("ppo.prep.gather", "t1", 10, 12), span("ppo.advantages", "t1", 12, 15),
    span("train.begin", "t1", 16, 20), span("train.wait_input", "t1", 20, 27),
    span("train.pack", "t1", 18, 21, tid=2), span("train.h2d", "t1", 21, 27, tid=2),
    span("train.dispatch", "t1", 28, 31), span("device.starved", "t1", 10, 30),
    span("train.fetch_stats", "t1", 40, 90), span("ppo.stats", "t1", 92, 99),
    span("ppo.train_step", "t2", 200, 300),
    span("ppo.prep.pack", "t2", 200, 202), span("ppo.prep.h2d", "t2", 202, 205),
    span("ppo.prep.dispatch", "t2", 205, 207), span("device.starved", "t2", 90, 206),
    # another thread's stretch belongs to no step of this thread
    span("device.starved", "t2", 210, 290, tid=3),
    # not a step
    span("device.starved", "other", 400, 500), span("train.dispatch", "other", 400, 500),
], counters={}, dropped=0, profile_dir=None, clock_anchor=None)
# a step: t1 20 + 10 of the tail, t2 6 -> 36 ms over two steps
WANT = {"train_starved_ms": 18.0,
        "train_starved_input_ms": (7 + 5) / 2,       # wait_input; prep.pack + prep.h2d
        "train_starved_enqueue_ms": (2 + 1) / 2,     # the parts before the enqueues returned
        "train_starved_host_ms": (2 + 3 + 4 + 7) / 2,  # gather, advantages, begin; stats
        "train_starved_unspanned_ms": (1 + 1 + 2 + 1) / 2}  # 15-16, 27-28; 90-92, 99-100


@pytest.mark.parametrize("name", METRICS)
def test_the_split_on_a_hand_made_program(name):
    m = metric(name)
    assert m["reader"] == "program_span_overlap_ms"
    read = manifest.load_reader(m["reader"]).read
    assert read({"program": HAND_MADE}, **m["args"]) == pytest.approx(WANT[name])
    # nothing to read: no program, no step, or a program without the span
    # (the parent commit's): the metric is left out of the line
    no_span = dict(HAND_MADE, spans=[s for s in HAND_MADE["spans"]
                                     if s["name"] != "device.starved"])
    no_step = dict(HAND_MADE, spans=[s for s in HAND_MADE["spans"]
                                     if s["name"] != "ppo.train_step"])
    for ev in ({}, {"program": None}, {"program": {"spans": [], "counters": {}}},
               {"program": no_span}, {"program": no_step}):
        assert read(ev, **m["args"]) is None


def test_the_four_parts_sum_to_the_whole_and_name_no_span_twice():
    assert sum(WANT[n] for n in PARTS) == pytest.approx(WANT["train_starved_ms"])
    lists = [metric(n)["args"]["spans"] for n in PARTS[:3]]
    named = [s for l in lists for s in l]
    assert len(named) == len(set(named))
    rest = metric("train_starved_unspanned_ms")["args"]
    assert rest["rest"] is True and sorted(rest["spans"]) == sorted(named)
    assert "spans" not in metric("train_starved_ms")["args"]


def test_a_stretch_is_clipped_to_the_root_and_overlapping_leaves_count_once():
    read = program_span_overlap_ms.read
    prog = dict(spans=[span("ppo.train_step", "t", 10, 20),
                       span("device.starved", "t", 0, 30),
                       span("a", "t", 5, 14), span("a", "t", 12, 16), span("b", "t", 18, 40)])
    assert read({"program": prog}, "device.starved") == pytest.approx(10.0)
    assert read({"program": prog}, "device.starved", spans=["a"]) == pytest.approx(6.0)
    assert read({"program": prog}, "device.starved", spans=["a", "b"]) == pytest.approx(8.0)
    assert read({"program": prog}, "device.starved", spans=["a", "b"], rest=True) == \
        pytest.approx(2.0)
    assert read({"program": prog}, "device.starved", spans=["no.such"]) == 0.0
    assert read({"program": prog}, "device.starved", root="no.such.root") is None


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_file_agrees_with_its_manifest_entry(name):
    m = metric(name)
    man = manifest.load_manifest()
    entry = next(e for e in man["per_layer"] if e["name"] == name)
    assert {k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
        {k: v for k, v in entry.items() if k != "workloads"}
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "ms", "lower", "program_span", "trainer engine", "train_tokens_per_s")
    train_cells = [w["name"] for w in man["workloads"] if "-train-" in w["name"]]
    assert entry["workloads"] == train_cells and m["cells"] == ["*-train-*"]
    assert m in manifest.layer_metrics_for(train_cells[0])


def test_the_five_entries_are_the_manifests_last_in_the_tables_order():
    assert [e["name"] for e in manifest.load_manifest()["per_layer"][-5:]] == METRICS


@pytest.fixture(scope="module")
def real_steps():
    """Three PPO steps of the span tests' toy model on the CPU, traced."""
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.base import tracing
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from tests.interfaces.test_ppo_spans import N_MINIBATCHES, _model, _sample

    tracing.reconfigure()
    model, itf = _model(False), PPOActorInterface(n_minibatches=N_MINIBATCHES)
    mb_spec = MicroBatchSpec(max_tokens_per_mb=48)
    itf.train_step(model, _sample(), mb_spec)
    tracing.start()
    try:
        for seed in (1, 2, 3):
            itf.train_step(model, _sample(seed=seed), mb_spec)
    finally:
        got = tracing.stop()
    tracing.reconfigure()
    return got


def test_real_steps_give_all_five_and_the_parts_sum_to_the_whole(real_steps):
    got = manifest.read_layer_metrics("q15d12-train-ppo", {"program": real_steps})
    assert set(METRICS) <= set(got)
    v = {n: got[n]["value"] for n in METRICS}
    assert all(got[n]["unit"] == "ms" for n in METRICS)
    assert v["train_starved_ms"] > 0
    assert sum(v[n] for n in PARTS) == pytest.approx(v["train_starved_ms"], rel=1e-9)
    # each kind of leaf was inside a stretch (on the CPU too: the toy
    # step's first pack is never hidden, its enqueues and numpy are real)
    assert all(v[n] > 0 for n in PARTS[:3])
    # the leaves cover the stretches but for the Python between them: at
    # toy sizes that is tens of microseconds beside leaves of a
    # millisecond (a share; the chip's is in PERF.md)
    assert v["train_starved_unspanned_ms"] < 0.5 * v["train_starved_ms"]
    # the accepted metrics read what they read before
    assert got["ppo_prep_inner_ms"]["value"] > 0 and got["train_input_wait_ms"]["value"] > 0
    # what the parent commit's program records: none of the five
    old = dict(real_steps, spans=[s for s in real_steps["spans"]
                                  if s["name"] != "device.starved"])
    assert not set(METRICS) & set(manifest.read_layer_metrics(
        "q15d12-train-ppo", {"program": old}))
