"""The five per-layer metrics under `setup_s` (PR 36): the reader
`program_builds` on hand-made evidence (the program's build records from
`tracing.stop()["builds"]`, the run's own `train_step` spans), None
where the program returns no records, each metric's file against its
`BENCHMARK.json` entry, and on records a real jitted call left."""

import json
import os

import pytest

from benchmark import manifest
from benchmark.readers import program_builds

METRICS = ["setup_build_s", "setup_trace_lower_s", "setup_backend_s",
           "setup_programs_built", "setup_cache_hit_pct"]
TRAIN_CELLS = ["q15d12-train-ppo", "trinity-d5e16-train-ppo-long", "q15d12-train-short",
               "nemotron3n-d9e8-train-ppo-long", "phi4flash-d8-train-ppo-8k"]
S = 1_000_000_000


def metric(name):
    return json.load(open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{name}.json")))


def build(phase, start_s, end_s, cache_hit=None, program=None):
    return dict(kind="build", phase=phase, fun="jit(f)", program=program, rows=None,
                row_len=None, start_ns=int(start_s * S), end_ns=int(end_s * S), tid=1,
                cache_hit=cache_hit)


# The window's first step starts at 100 s. Before it: the weights' program
# (a miss, compiled), a forward program and a step's (both loaded), an
# eager op below the cache's notice; after it a program built inside the
# window and one in the traced pass, which are not set-up's.
BUILDS = [
    build("trace", 10.0, 10.5), build("lower", 10.5, 11.0),
    build("compile", 11.0, 19.0, cache_hit=False),
    build("trace", 30.0, 32.0, program="forward"), build("lower", 32.0, 33.0, program="forward"),
    build("cache_load", 33.0, 33.25, cache_hit=True, program="forward"),
    build("trace", 50.0, 54.0, program="accum_first"),
    build("lower", 54.0, 55.5, program="accum_first"),
    build("cache_load", 55.5, 56.0, cache_hit=True, program="accum_first"),
    build("trace", 60.0, 60.01), build("lower", 60.01, 60.02), build("compile", 60.02, 60.05),
    build("trace", 120.0, 125.0, program="accum_next"),
    build("compile", 126.0, 150.0, cache_hit=False, program="accum_next"),
    build("cache_load", 200.0, 201.0, cache_hit=True),
]
SPANS = [dict(name="train_batch", start=99.0, end=99.5),
         dict(name="train_step", start=104.0, end=108.0),
         dict(name="train_step", start=100.0, end=104.0)]
EVIDENCE = dict(spans=SPANS, program=dict(spans=[], counters={}, builds=BUILDS))
WANT = {"setup_build_s": 18.30, "setup_trace_lower_s": 9.52, "setup_backend_s": 8.78,
        "setup_programs_built": 4, "setup_cache_hit_pct": 100.0 * 2 / 3}


def read(name, evidence):
    m = metric(name)
    return manifest.load_reader(m["reader"]).read(evidence, **m["args"])


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_reads_set_ups_records_alone(name):
    assert read(name, EVIDENCE) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_is_left_out_where_there_is_nothing_to_read(name):
    # the parent's program returns no `builds`; a run that made no step
    # has no end of set-up; a program that traced nothing, no program
    for ev in ({}, dict(spans=SPANS), dict(spans=SPANS, program=None),
               dict(spans=SPANS, program=dict(spans=[], counters={})),
               dict(spans=[], program=dict(builds=BUILDS)),
               dict(program=dict(builds=BUILDS))):
        assert read(name, ev) is None
    # no record yet: nothing built is 0 seconds and 0 programs, and no share
    empty = dict(spans=SPANS, program=dict(builds=[]))
    assert read(name, empty) == (None if name == "setup_cache_hit_pct" else 0)


def test_trace_and_lower_plus_backend_are_the_whole():
    parts = read("setup_trace_lower_s", EVIDENCE) + read("setup_backend_s", EVIDENCE)
    assert parts == pytest.approx(read("setup_build_s", EVIDENCE), rel=1e-12)
    assert read("setup_build_s", EVIDENCE) < min(s["start"] for s in SPANS
                                                  if s["name"] == "train_step")


def test_a_warm_run_reads_100_and_an_empty_cache_0():
    def ev(hit):
        return dict(spans=SPANS, program=dict(builds=[
            build("cache_load" if hit else "compile", 1.0, 2.0, cache_hit=hit),
            build("compile", 3.0, 3.1)]))  # below the cache's notice either way

    assert read("setup_cache_hit_pct", ev(True)) == 100.0
    assert read("setup_cache_hit_pct", ev(False)) == 0.0


def test_the_reader_refuses_what_it_cannot_read():
    with pytest.raises(ValueError):
        program_builds.read(EVIDENCE, "minutes")


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_file_agrees_with_its_manifest_entry(name):
    m = metric(name)
    [entry] = [e for e in manifest.load_manifest()["per_layer"] if e["name"] == name]
    assert {k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
        {k: v for k, v in entry.items() if k != "workloads"}
    assert (m["layer"], m["moves"], m["cells"]) == ("trainer engine", "setup_s", ["*-train-*"])
    assert m["reader"] == "program_builds" and m["better"] in ("lower", "higher")
    assert manifest.UNIT_RE.match(m["unit"]) and manifest.NAME_RE.match(name)
    assert entry["workloads"] == TRAIN_CELLS
    # the file's glob reads it in exactly the cells the entry lists
    assert [c for c in manifest.list_names("cells")
            if name in [x["name"] for x in manifest.layer_metrics_for(c)]] == sorted(TRAIN_CELLS)


def test_the_entries_are_appended_and_setup_s_is_theirs_alone():
    man = manifest.load_manifest()
    assert [e["name"] for e in man["per_layer"]][-5:] == METRICS
    assert [e["name"] for e in man["per_layer"] if e["moves"] == "setup_s"] == METRICS
    assert "setup_s" in [e["name"] for e in man["end_to_end"]]


def test_the_records_a_jitted_call_leaves_are_read():
    """The program's own records, not hand-made ones: a function built
    before the 'window' is set-up's, one built after it is not."""
    import time

    import jax
    import jax.numpy as jnp

    from areal_tpu.base import tracing

    tracing.watch_builds()
    n = len(tracing.builds())

    def f(x):
        return jnp.sum(x * 3.0)

    f.__name__ = f.__qualname__ = "build_metrics_probe"
    jax.jit(f)(jnp.ones((5, 7)))
    t0 = time.monotonic()
    jax.jit(f)(jnp.ones((7, 5)))
    mine = [b for b in tracing.stop()["builds"][n:] if "build_metrics_probe" in b["fun"]]
    assert len(mine) == 6
    ev = dict(spans=[dict(name="train_step", start=t0, end=t0 + 1)],
              program=dict(builds=mine))
    assert read("setup_programs_built", ev) == 1
    assert read("setup_build_s", ev) == pytest.approx(
        sum(b["end_ns"] - b["start_ns"] for b in mine[:3]) / 1e9)
    assert read("setup_trace_lower_s", ev) + read("setup_backend_s", ev) == \
        pytest.approx(read("setup_build_s", ev))
