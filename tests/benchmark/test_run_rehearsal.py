"""The one command, end to end at toy widths on the CPU through its
explicit rehearsal flag: the runner prints the contract's last line, a
cell dropped in as files is found by name, and without a chip (and
without the flag) the command refuses to measure."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

REPO = manifest.REPO
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_py(args, cwd=REPO, env_extra=None, script="benchmark/run.py", timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(r):
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def rehearse(cell, tmp_path, trace, cwd=REPO, env_extra=None):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    env.update(env_extra or {})
    return run_py(["--workload", cell, "--seed", str(2**31 + 77), "--seconds", "2",
                   "--trace", str(trace), "--rehearse-on-cpu",
                   "--out", str(tmp_path / "out")], cwd=cwd, env_extra=env)


def check_contract_line(line):
    assert CONTRACT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    # a CPU run puts no value under any metric's name
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("trace", [0, 1])
def test_train_runner_rehearsal_prints_the_contract_line(tmp_path, trace):
    r = rehearse("q15d12-train-ppo", tmp_path, trace)
    line = last_line(r)
    check_contract_line(line)
    assert "proves nothing about the chip" in r.stdout
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    want = {"setup_s", "train_tokens_per_s"} if trace == 0 else {"train_step_max_s", "ppo_prep_ms"}
    assert want <= set(line["would_report"])
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert len(steps) == line["counts"]["steps"] and all(s["ok"] for s in steps)
    assert "drawn sequence lengths" in r.stdout
    # float32 at toy widths: the engine and the plain reference agree closely
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    # the window is whole passes over the pool in the seed's order; the
    # warm steps go in the drawn order, so every seed builds the same programs
    order = [b["batch"] for b in json.loads(next(
        l for l in r.stdout.splitlines() if "seed's order: " in l)
        .split("seed's order: ", 1)[1])]
    assert [s["batch"] for s in steps] == order * (len(steps) // len(order))
    warm = [l.split("warm step on batch ")[1].split(":")[0]
            for l in r.stdout.splitlines() if "warm step on batch " in l]
    assert warm == sorted(warm) and len(warm) == len(order)


def test_without_a_chip_and_without_the_flag_it_refuses(tmp_path):
    r = run_py(["--workload", "q15d12-train-ppo", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--out", str(tmp_path / "out")],
               env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout and "not a TPU" in r.stderr


def copy_benchmark(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", "out"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)


def test_alone_with_the_manifest_it_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the paths has no
    program to measure: non-zero exit, no result."""
    copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "q15d12-train-ppo",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse-on-cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"correct"' not in r.stdout


def test_a_cell_added_as_files_is_found_and_run_by_name(tmp_path):
    """A later PR's whole addition: a configuration, a traffic mix, a cell
    and a layer metric over an existing reader, as new files in a copy of
    benchmark/ — no file that was there is edited, not even BENCHMARK.json."""
    copy_benchmark(tmp_path)
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "qwen2.5-1.5b-d12.json"))
    cfg["num_hidden_layers"] = 6
    json.dump(cfg, open(b / "configs" / "later-d6.json", "w"))
    tr = json.load(open(b / "traffic" / "ppo-packed.json"))
    tr["rehearsal"]["response_len_lognormal"] = {"median": 8, "sigma": 0.5}
    tr["rehearsal"]["response_len_clip"] = [4, 32]
    json.dump(tr, open(b / "traffic" / "ppo-short.json", "w"))
    cell = json.load(open(b / "cells" / "q15d12-train-ppo.json"))
    cell.update(name="later-train-short", config="later-d6", traffic="ppo-short")
    json.dump(cell, open(b / "cells" / "later-train-short.json", "w"))
    json.dump(dict(name="train_batch_ms.later", unit="ms", better="lower",
                   source="host_clock", layer="trainer engine",
                   moves="train_tokens_per_s", cells=["later-*"],
                   reader="span_sum", args={"span": "train_batch", "scale": 1000.0}),
              open(b / "layer_metrics" / "train_batch_ms.later.json", "w"))
    r = rehearse("later-train-short", tmp_path, 1, cwd=tmp_path,
                 env_extra={"PYTHONPATH": REPO})
    line = last_line(r)
    check_contract_line(line)
    assert "cell later-train-short: config later-d6, traffic ppo-short" in r.stdout
    # its own metric and the ones whose globs match `*-train-*`
    assert {"train_batch_ms.later", "train_step_max_s"} <= set(line["would_report"])
