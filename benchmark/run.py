#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

Resolves the cell by name (benchmark/cells/<cell>.json -> configs/,
traffic/, runners/, layer_metrics/ -> readers/), runs it on the chips this
machine holds, and prints one JSON object as the last line of stdout:
the cell's end-to-end metrics with `--trace 0`, its per-layer metrics
with `--trace 1`, and both with `--trace 2`: a `--trace 0` run that, once
its measured window has closed and its numbers are taken, traces a few
more seconds of the same traffic for the per-layer metrics. Everything
before that line is the run's log.

Without a TPU (or with fewer chips than the cell asks) it exits non-zero
and prints no result. `--rehearse-on-cpu` walks the same path at toy
widths on the CPU and prints no value under any metric's name: it proves
the plumbing, nothing about the chip.
"""

import time

T_START = time.monotonic()

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)


def log(*a):
    print(f"[bench {time.monotonic() - T_START:8.2f}s]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--out", default=None, help="directory for this run's files")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args(argv)

    # One compile cache at a fixed place inside the checkout (the path is
    # part of the cache key), unless the machine names one.
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        BENCH_DIR, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark import common, manifest

    man = manifest.load_manifest()
    entry = next((w for w in man["workloads"] if w["name"] == args.workload), None)
    cell = manifest.load_cell(args.workload)
    if entry is not None and any(entry[k] != cell[k] for k in ("config", "traffic", "chips")):
        raise SystemExit(f"BENCHMARK.json and cells/{args.workload}.json disagree")
    seconds = args.seconds if args.seconds is not None else man["run_seconds"]
    rehearsal = bool(args.rehearse_on_cpu)

    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        device = common.device_info(int(cell["chips"]), rehearsal)
        peaks = None if rehearsal else manifest.device_peaks(device["kind"])
    except (common.NoChip, KeyError) as e:
        print(f"benchmark: cannot measure here: {e}", file=sys.stderr)
        return 3
    log(f"cell {cell['name']}: config {cell['config']}, traffic {cell['traffic']}, "
        f"seed {args.seed}, {seconds}s, trace {args.trace}, device {device}"
        + (" -- REHEARSAL at toy widths on the CPU: proves nothing about the chip"
           if rehearsal else ""))

    # Whatever the program writes beside its checkout (name records, its
    # file root) goes under TMPDIR, into a directory this run removes.
    work_dir = tempfile.mkdtemp(prefix="benchmark-run-")
    os.environ.setdefault("AREAL_FILEROOT", os.path.join(work_dir, "fileroot"))
    out_dir = args.out or os.path.join(
        BENCH_DIR, "out", f"{cell['name']}.seed{args.seed}.trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    from benchmark import traffic

    traffic_params = traffic.effective(cell["traffic_file"], rehearsal)
    hf = manifest.hf_config(cell["config_file"], rehearsal)
    ctx = dict(
        cell=cell, hf=hf, traffic=traffic_params, seed=args.seed, seconds=seconds,
        trace=args.trace == 1, trace_after=args.trace == 2,
        rehearsal=rehearsal, out_dir=out_dir,
        t_start=T_START, log=log, chips=int(cell["chips"]),
        compiles=common.CompileCounter(), work_dir=work_dir,
    )
    try:
        res = manifest.load_runner(cell["traffic_file"]["runner"]).run(ctx)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    ev = res["evidence"]
    ev.update(hf_config=hf, peaks=peaks, chips=ctx["chips"])
    problems = list(res["problems"])
    metrics = {}
    if args.trace != 1:
        wanted = [m for m in man["end_to_end"]
                  if "workloads" not in m or cell["name"] in m["workloads"]]
        for m in wanted:
            if m["name"] in res["end_to_end"]:
                metrics[m["name"]] = {"value": float(res["end_to_end"][m["name"]]),
                                      "unit": m["unit"]}
            elif entry is not None:
                problems.append(f"no value for {m['name']}")
    if args.trace:
        metrics.update(manifest.read_layer_metrics(cell["name"], ev))
    mem = ev.get("memory") or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    tr = ev.get("trace")
    line = dict(correct=not problems, attempted=res["attempted"],
                failed=res["failed"], metrics=metrics, device=device)
    if args.trace and tr:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = dict(device_ops=tr["device_ops"], idle_gaps=tr["idle_gaps"])
        log("device time by category: " + json.dumps(tr["category_s"]))
    elif args.trace and not rehearsal:  # the CPU's trace has no device plane
        problems.append("the traced window saw no operation on a device")
        # --trace 2 takes `correct` from the window that closed before the
        # tracing began; the problem is listed all the same.
        line["correct"] = line["correct"] and args.trace == 2
    if rehearsal:
        # Counts only: no time, rate or share from a CPU run under any name.
        line.update(metrics={}, rehearsal=True, counts=res["counts"],
                    would_report=sorted(metrics))
        line.pop("breakdown", None)
        device.pop("busy_s", None), device.pop("window_s", None)
    line["problems"] = problems + res.get("trace_problems", [])
    line["compile_s"] = ctx["compiles"].compile_seconds()
    if not rehearsal:
        log(f"all end-to-end values: {json.dumps(res['end_to_end'])}")
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(dict(line, end_to_end=res["end_to_end"], counts=res["counts"],
                       cell={k: v for k, v in cell.items()
                             if k not in ("config_file", "traffic_file")},
                       traffic=traffic_params), f, indent=1)
    sys.stdout.flush()
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # Daemon threads of the program (the engine's prefetcher) must not
    # hold the process: everything that matters has been joined or stopped.
    os._exit(rc)
