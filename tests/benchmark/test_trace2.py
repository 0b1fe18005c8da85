"""`--trace 2`: a `--trace 0` run that traces a pass after its measured
window has closed. On the CPU (the explicit rehearsal) it must print one
last line that would report both kinds of metric, do the same steps as
`--trace 0`, and record no program span before the window's end; the
lines of `--trace 0` and `--trace 1` keep the keys they had."""

import json

import pytest

from tests.benchmark.test_run_rehearsal import last_line, run_py

CELL = "q15d12-train-ppo"
SEED = 2**31 + 1234
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "problems",
             "compile_s", "rehearsal", "counts", "would_report"}
END_TO_END = {"setup_s", "train_tokens_per_s"}
HOST_CLOCK_LAYER = {"train_step_max_s", "ppo_prep_ms"}
PROGRAM_LAYER = {"ppo_prep_inner_ms", "train_input_wait_ms", "train_dispatch_ms",
                 "train_stats_wait_ms", "train_pack_density_pct"}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same seed and seconds under each mode, one compile cache. A
    window of 0.01 s is exactly one pass over the toy pool in every mode."""
    root = tmp_path_factory.mktemp("trace2")
    out = {}
    for trace in (0, 1, 2):
        r = run_py(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.01",
                    "--trace", str(trace), "--rehearse-on-cpu",
                    "--out", str(root / f"out{trace}")],
                   env_extra={"JAX_COMPILATION_CACHE_DIR": str(root / "cache")})
        out[trace] = (last_line(r), r.stdout, root / f"out{trace}")
    return out


@pytest.mark.parametrize("trace,want", [
    (0, END_TO_END),
    (1, HOST_CLOCK_LAYER | PROGRAM_LAYER),
    (2, END_TO_END | HOST_CLOCK_LAYER | PROGRAM_LAYER),
])
def test_each_mode_prints_one_line_with_its_kinds_of_metric(runs, trace, want):
    line, stdout, _ = runs[trace]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["problems"] == []
    # counts only, as a rehearsal must: no value under any metric's name
    assert line["metrics"] == {} and "breakdown" not in line
    assert want <= set(line["would_report"])
    if trace != 2:
        others = (END_TO_END if trace == 1 else HOST_CLOCK_LAYER | PROGRAM_LAYER)
        assert not others & set(line["would_report"])
    assert stdout.strip().splitlines()[-1].startswith('{"correct"')


def test_trace_2_measures_what_trace_0_measures(runs):
    (l0, _, out0), (l2, _, out2) = runs[0], runs[2]
    assert l2["counts"]["steps"] == l0["counts"]["steps"] == l2["attempted"]
    assert l2["counts"]["tokens"] == l0["counts"]["tokens"]
    assert l2["counts"]["compiles_in_window"] == 0
    s0 = [json.loads(l) for l in open(out0 / "steps.jsonl")]
    s2 = [json.loads(l) for l in open(out2 / "steps.jsonl")]
    assert [(s["step"], s["batch"], s["tokens"]) for s in s0] == \
        [(s["step"], s["batch"], s["tokens"]) for s in s2]
    # the traced pass is kept apart: the pool once more, after the window
    traced = [json.loads(l) for l in open(out2 / "traced_steps.jsonl")]
    assert [t["batch"] for t in traced] == [s["batch"] for s in s2][:len(traced)]
    assert [t["step"] for t in traced] == list(range(len(s2), len(s2) + len(traced)))
    assert not (out0 / "traced_steps.jsonl").exists()
    assert not (out0 / "program.json").exists()


def test_every_program_span_starts_after_the_measured_window_has_closed(runs):
    _, stdout, out2 = runs[2]
    steps = [json.loads(l) for l in open(out2 / "steps.jsonl")]
    window_end_ns = max(s["end"] for s in steps) * 1e9  # time.monotonic, as the spans
    program = json.load(open(out2 / "program.json"))
    names = {s["name"] for s in program["spans"]}
    assert {"clock_anchor", "ppo.train_step", "train.batch", "train.dispatch"} <= names
    assert all(s["start_ns"] >= window_end_ns for s in program["spans"])
    assert program["clock_anchor"]["monotonic_ns"] >= window_end_ns
    traced = [json.loads(l) for l in open(out2 / "traced_steps.jsonl")]
    assert sum(s["name"] == "ppo.train_step" for s in program["spans"]) == len(traced)
    assert program["counters"]["train.tokens"] == sum(t["tokens"] for t in traced)
    assert "traced pass: " in stdout
    # the trace files are gone once reduced
    assert not (out2 / "trace" / "plugins").exists()


def test_a_traced_pass_that_fails_leaves_the_windows_numbers_standing(tmp_path):
    """The profiler cannot write where a file stands in its directory's
    place: the line still goes out with the closed window's `correct` and
    counts, and the failure is listed beside them."""
    out = tmp_path / "out"
    out.mkdir()
    (out / "trace").write_text("in the way")
    r = run_py(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.01",
                "--trace", "2", "--rehearse-on-cpu", "--out", str(out)],
               env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    line = last_line(r)
    assert line["correct"] is True and line["failed"] == 0
    assert line["counts"]["steps"] == line["attempted"] >= 2
    assert len(line["problems"]) == 1 and line["problems"][0].startswith("the traced pass failed")
    assert END_TO_END | HOST_CLOCK_LAYER <= set(line["would_report"])
    assert not PROGRAM_LAYER & set(line["would_report"])
    assert r.stdout.index("measured window closed: ") < r.stdout.index("the traced pass failed")
    assert len(open(out / "steps.jsonl").readlines()) == line["attempted"]
