#!/usr/bin/env python3
"""Which steps of a benchmark run were long, and what the host did in them.

    python scripts/stall_hunt.py <out dir> --workload <cell> --seed <n> --seconds 330 --trace 0

Runs `benchmark/run.py` with the arguments after `<out dir>` in this process,
with the program's spans on (`AREAL_RL_TRACE`; the shard is flushed before
`run.py` leaves by `os._exit`, which would lose its last second of spans), and two
clocks that do nothing but sleep 20 ms and note when they woke late:

- a thread of this process: it is late when the interpreter's lock was held
  or the process did not run;
- a process of its own that never touches jax (`pauses.jsonl`): it is late
  when the machine did not run it, whatever this process did.

Then, one line a step longer than its batch's median by 0.1 s: the seconds
over, the step's spans by name against their medians, and the pauses either
clock saw inside it. A pause both clocks saw
is the machine's: every process stood still, the device ran out what was
queued (at most one minibatch) and idled for the rest (PERF.md section 6,
PR 50, third session: three pauses of 0.4-3.6 s in a window of 333 s; and one
of 2.6-7.7 s in set-up, 8-10 s after every process began, as jax's backend
opens the device).
"""

import collections
import glob
import json
import os
import runpy
import statistics
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TICK, LATE, OVER = 0.02, 0.3, 0.1
# `device.starved` beside `train.fetch_stats`: a pause inside the blocking
# read grows the second alone, one between a read and the next enqueue the first
SPANS = ("ppo.prep", "train.wait_input", "train.dispatch", "train.apply",
         "train.fetch_stats", "device.starved")


def _clock(note):
    prev = time.monotonic()
    while True:
        time.sleep(TICK)
        now = time.monotonic()
        if now - prev > LATE:
            note(prev, now - prev)
        prev = now


def _own_process(path):
    with open(path, "w") as f:
        def note(at, s):
            f.write(json.dumps([at, s]) + "\n")
            f.flush()
        _clock(note)


def report(out, steps_file):
    """The lines of the report: the long steps, then the window."""
    rows = [json.loads(l) for l in open(steps_file)]
    spans = [d for p in glob.glob(os.path.join(out, "rl_trace", "*.jsonl"))
             for d in map(json.loads, open(p)) if "start_ns" in d and d["name"] in SPANS]
    seen = {"thread": json.load(open(os.path.join(out, "thread.json"))),
            "machine": [json.loads(l) for l in open(os.path.join(out, "pauses.jsonl"))]}
    for r in rows:
        r["s"] = r["end"] - r["start"]
        r["spans"] = collections.Counter()
        for d in spans:
            if r["start"] <= d["start_ns"] / 1e9 and d["end_ns"] / 1e9 <= r["end"]:
                r["spans"][d["name"]] += (d["end_ns"] - d["start_ns"]) / 1e9
    by_batch = collections.defaultdict(list)
    for r in rows:
        by_batch[r["batch"]].append(r)
    lines = []
    for r in rows:
        same = by_batch[r["batch"]]
        extra = r["s"] - statistics.median(x["s"] for x in same)
        if extra > OVER:
            lines.append(dict(
                step=r["step"], batch=r["batch"], seconds=r["s"], over_median=extra,
                spans={n: [r["spans"][n], statistics.median(x["spans"][n] for x in same)]
                       for n in SPANS},
                **{k: [[at - r["start"], s] for at, s in v
                       if r["start"] <= at <= r["end"]] for k, v in seen.items()}))
    t0, t1 = rows[0]["start"], rows[-1]["end"]
    lines.append(dict(
        steps=len(rows), window_s=t1 - t0,
        machine_pauses_in_window=[[at - t0, s] for at, s in seen["machine"] if t0 <= at <= t1]))
    return lines


def main():
    if sys.argv[1] == "--own-process":
        return _own_process(sys.argv[2])
    out, args = os.path.abspath(sys.argv[1]), sys.argv[2:]
    os.makedirs(out, exist_ok=True)
    os.environ["AREAL_RL_TRACE"] = "1"
    os.environ["AREAL_RL_TRACE_DIR"] = os.path.join(out, "rl_trace")
    other = subprocess.Popen([sys.executable, os.path.abspath(__file__), "--own-process",
                              os.path.join(out, "pauses.jsonl")])
    late = []
    threading.Thread(target=_clock, args=(lambda at, s: late.append((at, s)),),
                     daemon=True).start()
    leave = os._exit

    def flush_and_leave(rc):
        from areal_tpu.base import tracing

        tracing.flush()
        other.kill()
        other.wait()
        with open(os.path.join(out, "thread.json"), "w") as f:
            json.dump(late, f)
        steps = glob.glob(os.path.join(ROOT, "benchmark", "out", "*", "steps.jsonl"))
        for line in report(out, max(steps, key=os.path.getmtime)):
            print(json.dumps(line))
        leave(rc)

    os._exit = flush_and_leave
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    sys.argv = ["benchmark/run.py"] + args
    runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"), run_name="__main__")


if __name__ == "__main__":
    main()
