"""The readers of the program's own tracing (PR 26): spans and counters
from `tracing.stop()`'s dict, on hand-made evidence and on the program's
record of one moment of the traced pass on the v5e
(`data/v5e_train_scopes_program.json`); each returns None without its
evidence. Beside that record, the device trace of the same moment
(`data/v5e_train_scopes_slice.json`, one 35 ms micro-batch program, the
device ops' name stacks filled in from the file's event metadata): the
program's scope names must move no category of the accepted reduction."""

import json
import os
import re

import pytest

from benchmark import manifest, trace_reduce
from benchmark.readers import program_counter_ratio, program_span_ms

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = ["ppo_prep_inner_ms", "train_input_wait_ms", "train_dispatch_ms",
               "train_stats_wait_ms", "train_pack_density_pct"]


@pytest.fixture(scope="module")
def planes():
    return json.load(open(os.path.join(DATA, "v5e_train_scopes_slice.json")))


@pytest.fixture(scope="module")
def recorded_program():
    return json.load(open(os.path.join(DATA, "v5e_train_scopes_program.json")))


def metric(name):
    return json.load(open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{name}.json")))


def span(name, trace, start, end, **attrs):
    return dict(kind="span", name=name, trace=trace, span=f"{name}{start}", parent=None,
                start_ns=start, end_ns=end, tid=1, attrs=attrs)


HAND_MADE = dict(
    spans=[
        # step t1: prep 4 ms; waits 1 + 2 ms; dispatches 3 + 1 ms, apply 2 ms; fetch 100 ms
        span("ppo.train_step", "t1", 0, 200_000_000), span("ppo.prep", "t1", 0, 4_000_000),
        span("train.wait_input", "t1", 5_000_000, 6_000_000),
        span("train.wait_input", "t1", 9_000_000, 11_000_000),
        span("train.dispatch", "t1", 6_000_000, 9_000_000, kind="first", rows=1, row_len=512),
        span("train.dispatch", "t1", 11_000_000, 12_000_000, kind="next", rows=1, row_len=512),
        span("train.apply", "t1", 12_000_000, 14_000_000),
        span("train.fetch_stats", "t1", 14_000_000, 114_000_000),
        # step t2: prep 6 ms, one wait of 5 ms, fetch 300 ms
        span("ppo.train_step", "t2", 300_000_000, 700_000_000),
        span("ppo.prep", "t2", 300_000_000, 306_000_000),
        span("train.wait_input", "t2", 306_000_000, 311_000_000),
        span("train.fetch_stats", "t2", 320_000_000, 620_000_000),
        # not a step: no ppo.train_step in its trace
        span("train.fetch_stats", "other", 0, 999_000_000), span("clock_anchor", "a", 0, 1),
    ],
    counters={"train.tokens": 900, "train.cells": 1200, "train.batches": 2},
    dropped=0, profile_dir=None, clock_anchor=None)


@pytest.mark.parametrize("name,want", [
    ("ppo_prep_inner_ms", 5.0), ("train_input_wait_ms", 4.0),
    ("train_dispatch_ms", 3.0), ("train_stats_wait_ms", 200.0),
    ("train_pack_density_pct", 75.0),
])
def test_program_metrics_on_a_hand_made_program(name, want):
    m = metric(name)
    read = manifest.load_reader(m["reader"]).read
    assert read({"program": HAND_MADE}, **m["args"]) == pytest.approx(want)
    # nothing to read: a program without the control, or a run that traced nothing
    for ev in ({}, {"program": None}, {"program": {"spans": [], "counters": {}}}):
        assert read(ev, **m["args"]) is None


def test_program_metrics_on_the_recorded_program(recorded_program):
    ev = {"program": recorded_program}
    # the traced pass's own counters: 134,750 tokens in 153,856 cells
    assert program_counter_ratio.read(ev, "train.tokens", "train.cells") == \
        pytest.approx(100.0 * 134750 / 153856)
    assert program_span_ms.read(ev, ["train.fetch_stats"]) > 0.0
    assert program_span_ms.read(ev, ["no.such.span"]) == 0.0
    assert program_span_ms.read(ev, ["train.fetch_stats"], root="no.such.root") is None


def device_ops(planes):
    return [e for p in planes["planes"] if p["name"].startswith("/device:")
            for l in p["lines"] if l["name"] == "XLA Ops" for e in l["events"]]


def test_the_recorded_scoped_trace_reduces_as_any_trace_does(planes):
    """The accepted reduction on the recorded slice (whose device ops carry
    the name stacks the profiler kept in their event metadata): it finds
    the device, its ops and the window."""
    red = trace_reduce.reduce_trace(planes)
    assert red["busy_s"] > 0.03 and red["window_s"] >= red["busy_s"]
    assert red["category_s"]["attention"] > 0 and red["device_ops"]


def test_scope_names_in_a_recorded_name_stack_move_no_category(planes):
    """On the recorded ops: the category with the program's scope names in
    the name stack is the category with each of them struck out."""
    ops = device_ops(planes)
    stacks = [e[3] for e in ops if e[3]]
    assert len(stacks) > 0.9 * len(ops)
    assert any("rematted_computation/mlp/dot_general:" in s for s in stacks)
    scopes = program_scope_names()
    seen = set()
    for name, _t, _d, stack in ops:
        parts = stack.split("/")
        seen |= scopes & set(parts)
        struck = "/".join(p for p in parts if p not in scopes)
        assert trace_reduce.categorize(name, stack) == trace_reduce.categorize(name, struck)
    assert {"attn_qkv", "attn_kernel", "attn_out", "mlp", "optimizer_apply"} <= seen


def program_scope_names():
    root = os.path.join(manifest.REPO, "areal_tpu")
    found = set()
    for d, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                found |= set(re.findall(r'named_scope\(\s*"([^"]+)"', open(os.path.join(d, f)).read()))
    return found


def test_no_scope_name_of_the_program_can_move_an_op_category():
    """`trace_reduce.categorize` also reads an op's name stack where the
    profiler gives one: a scope name that held one of its keys would move
    `train_attn_share_pct` without any op changing."""
    names = program_scope_names()
    assert {"embed", "attn_qkv", "attn_kernel", "attn_out", "mlp", "final_norm", "xent",
            "grad_accum", "optimizer_apply", "ppo_prep", "gae"} <= names
    for n in names:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", n), n  # no shape, no layer number
        assert not re.search(r"\d", n), n
        for cat, keys in trace_reduce.CATEGORY_KEYS:
            assert not [k for k in keys if k in n], (n, cat)
        assert trace_reduce.categorize("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                                       f"jit(f)/{n}/mul:") == "fusion"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_agree_with_the_manifest(name):
    m = metric(name)
    entry = next(e for e in manifest.load_manifest()["per_layer"] if e["name"] == name)
    assert {k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
        {k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"] == ["q15d12-train-ppo"] and m["cells"] == ["*-train-*"]
    assert m["source"] in ("program_span", "program_counter")
