"""Benchmark CLI: thin front-end over areal_tpu/bench/ (the old benchmark).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., ...}

Every phase is a CPU proxy over the control plane (`--list-phases`);
none of them times the chip. Speed on the chip is `benchmark/run.py`'s.

Modes:
  python bench.py                 run every unbanked default phase
                                  (compile pass, then measure), each in
                                  its own deadline-guarded subprocess;
                                  assemble + print the report
  python bench.py --phases a,b    restrict to named phases
  python bench.py --fresh         drop banked records first (new round)

This process NEVER touches jax itself (a chip belongs to one process):
the device probe and the phases run in subprocesses, so a wedged phase
is killed at its deadline without taking the bench with it. Every phase
result is flushed atomically to the bank the moment it exists — a crash
mid-run loses at most the phase in flight, and the next invocation
resumes from banked phases.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from areal_tpu.bench import bank, phases, report, runner  # noqa: E402
from areal_tpu.bench.devices import probe_devices  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def bench_json_path() -> str:
    return os.environ.get(
        "AREAL_BENCH_JSON",
        os.path.join(tempfile.gettempdir(), "areal_bench_result.json"),
    )


def flush_report(bank_path: str) -> dict:
    """Rebuild the report from the bank and persist it — called after
    EVERY phase so a mid-run crash still leaves the newest full
    artifact on disk."""
    rep = report.build_report(bank_path)
    report.write_report(rep, bench_json_path())
    return rep


def emit_and_exit(bank_path: str, code: int, error: str = None):
    rep = flush_report(bank_path)
    line = report.result_line(rep)
    if error:
        line["error"] = (line.get("error", "") + "; " + error).strip("; ")
        line["partial"] = True
    print(json.dumps(line), flush=True)
    # os._exit: the deadline path fires on a timer thread while the main
    # thread may be blocked on a wedged subprocess wait.
    os._exit(code) if code == 3 else sys.exit(code)


def _arm_deadline(bank_path: str, seconds: float):
    """Emit an honest JSON (with whatever phases DID bank) and hard-exit
    if the run overstays its welcome. The bank already holds every
    completed phase, so this handler just reads disk — no mirrored
    module state (the old bench kept a _PARTIAL global in sync by hand;
    the atomic per-phase bank made that hack unnecessary)."""
    import threading

    def fire():
        log(f"bench: deadline {seconds:.0f}s exceeded")
        emit_and_exit(bank_path, 3,
                      error=f"bench deadline {seconds:.0f}s exceeded")

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def run_oneshot(phase_list, bank_path: str, platform: str) -> bool:
    """Compile-then-measure every unbanked phase, priority order. Returns
    True if every phase banked an ok measure record."""
    ok = True
    for spec in phase_list:
        plat = "cpu" if spec.proxy else platform
        if bank.is_banked(bank_path, spec.name, "measure", plat):
            log(f"bench: {spec.name} already banked; skipping")
            continue
        if spec.est_compile_s > 0 and not bank.is_banked(
                bank_path, spec.name, "compile", plat):
            rec = runner.run_phase(spec.name, "compile", bank_path)
            flush_report(bank_path)
            if rec["status"] != "ok":
                ok = False
                continue  # no point measuring what cannot compile
        rec = runner.run_phase(spec.name, "measure", bank_path)
        flush_report(bank_path)
        ok = ok and rec["status"] == "ok"
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--phases", default=None,
                        help="comma-separated phase names (default: the "
                             "registry's default set)")
    parser.add_argument("--bank", default=None, help="bank directory")
    parser.add_argument("--fresh", action="store_true",
                        help="clear banked records first (new round)")
    parser.add_argument("--list-phases", action="store_true")
    args = parser.parse_args(argv)

    if args.list_phases:
        for s in phases.all_phases():
            print(f"{s.priority:3d} {s.name:18s} compile~{s.est_compile_s:.0f}s "
                  f"measure~{s.est_measure_s:.0f}s "
                  f"{'proxy ' if s.proxy else ''}"
                  f"{'headline ' if s.headline else ''}- {s.description}")
        return 0

    bank_path = bank.bank_dir(args.bank)
    if args.fresh:
        bank.clear_bank(bank_path)
    if args.phases:
        phase_list = [phases.get(n.strip())
                      for n in args.phases.split(",") if n.strip()]
    else:
        phase_list = phases.default_phases()

    deadline = _arm_deadline(
        bank_path, float(os.environ.get("AREAL_BENCH_DEADLINE_S", 2700))
    )
    try:
        p = probe_devices()
    except RuntimeError as e:
        log(f"bench: {e}")
        emit_and_exit(bank_path, 2, error=str(e))
    platform = p["platform"]
    log(f"bench: platform={platform} n_devices={p['count']}")
    complete = run_oneshot(phase_list, bank_path, platform)
    deadline.cancel()
    rep = flush_report(bank_path)
    print(json.dumps(report.result_line(rep)), flush=True)
    if complete and not args.phases:
        # The report file is the artifact; the bank is resume state for
        # THIS round only — a completed round must not leak into the
        # next. A --phases-restricted run keeps its records: a later
        # full run resumes from them.
        bank.clear_bank(bank_path)
    return 0 if complete else 1


if __name__ == "__main__":
    sys.exit(main())
