"""Isolated phase runner: one phase pass per subprocess, hard deadline.

The parent (:func:`run_phase`) spawns ``python -m areal_tpu.bench.runner``
for a single (phase, pass) and enforces a wall-clock deadline with
SIGKILL — a wedged XLA compile or a PJRT crash kills that one phase and
the bank still ends the day valid:

- child finishes OK       -> child banks the ok record itself (atomic
                             tmp+rename from inside the subprocess, so
                             even a parent crash right after cannot
                             lose it)
- child raises            -> child banks a failed record with the
                             traceback, exits 1
- child dies / is killed  -> parent banks a failed/timeout record with
                             the captured output tail

Chaos hooks (``base/fault_injection.py``): ``bench.runner.phase`` fires
inside the child right before the phase body — arm it with ``die`` to
simulate a PJRT crash or ``hang`` to simulate a wedged compile; the
``AREAL_FAULTS`` env spec crosses the process boundary on its own.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import traceback
from typing import Dict, Optional

from areal_tpu.base import env_registry
from areal_tpu.bench import bank, phases
from areal_tpu.bench._util import log, repo_root

TAIL_BYTES = 4000


def _kill_group(proc: subprocess.Popen) -> None:
    """SIGKILL the child's whole process group (fall back to the child
    alone if the group is already gone)."""
    import signal

    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        proc.kill()


def _read_tail(path: str) -> Optional[str]:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - TAIL_BYTES))
            return f.read().decode("utf-8", errors="replace")
    except OSError:
        return None


def run_phase(
    phase: str,
    pass_: str,
    bank_path: Optional[str] = None,
    deadline_s: Optional[float] = None,
    env_extra: Optional[Dict[str, str]] = None,
    python: str = sys.executable,
) -> Dict:
    """Execute one (phase, pass) in a subprocess; always returns a valid
    banked record (ok, failed, or timeout)."""
    spec = phases.get(phase)
    if deadline_s is None:
        deadline_s = spec.deadline_s(pass_)
    b = bank.bank_dir(bank_path)
    os.makedirs(b, exist_ok=True)

    repo = repo_root()
    env = dict(os.environ)
    env["AREAL_BENCH_BANK"] = b
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if spec.proxy:
        # Proxy phases are CPU evidence by construction — never let one
        # accidentally attest a TPU platform.
        env["JAX_PLATFORMS"] = "cpu"
    for k, v in (spec.env or {}).items():
        if k == "XLA_FLAGS":
            # Append: the phase asks for extra flags (e.g. a fake
            # multi-device CPU mesh) on top of whatever the host set.
            env[k] = (env.get(k, "") + " " + v).strip()
        else:
            env.setdefault(k, v)
    if env_extra:
        env.update(env_extra)

    out_fd, out_path = tempfile.mkstemp(prefix=f"bench_{phase}_", suffix=".log")
    started = time.time()
    status, error = "ok", None
    try:
        with os.fdopen(out_fd, "wb") as out_f:
            # start_new_session: the child leads its own process group, so
            # the deadline kill below reaps anything the phase spawned
            # (e.g. serving_http's GenerationServer grandchild) — an
            # orphaned jax process would hold the exclusive TPU client
            # and poison every later phase with 'device busy'.
            proc = subprocess.Popen(
                [python, "-m", "areal_tpu.bench.runner",
                 "--phase", phase, "--pass", pass_, "--bank", b],
                env=env, cwd=repo, stdout=out_f, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=deadline_s)
            except subprocess.TimeoutExpired:
                _kill_group(proc)
                proc.wait()
                status, error = "timeout", (
                    f"phase {phase!r} ({pass_}) exceeded its {deadline_s:.0f}s "
                    f"deadline; subprocess killed"
                )
            else:
                if rc != 0:
                    status, error = "failed", (
                        f"phase {phase!r} ({pass_}) subprocess exited {rc}"
                    )
        tail = _read_tail(out_path)
    finally:
        try:
            os.remove(out_path)
        except OSError:
            pass

    rec = bank.load_latest(b, phase, pass_)
    fresh = rec is not None and rec["started_at"] >= started - 1.0
    if fresh and rec["status"] == "ok":
        # The child banked a completed pass. Even if the parent then saw
        # a nonzero exit or a timeout (e.g. interpreter teardown wedged
        # AFTER the atomic bank write), the
        # measurement exists — never clobber it with a failure record.
        return rec
    if status == "ok":
        # Exited 0 without banking: treat as a failure, never as silence.
        status, error = "failed", (
            f"phase {phase!r} ({pass_}) exited 0 without banking a record"
        )
    elif fresh:
        # The child banked its own failure (with the real traceback) —
        # richer than what the parent can reconstruct.
        return rec
    # probe=False: the parent must never touch jax.devices() — it is
    # the one process responsible for enforcing deadlines, and a chip
    # belongs to one process.
    rec = bank.make_record(
        phase, pass_, status, error=error, tail=tail,
        started_at=started, finished_at=time.time(), probe=False,
    )
    bank.write_record(rec, b)
    log(f"bench: {phase}/{pass_} -> {status}"
        + (f" ({error})" if error else ""))
    return rec


# ----------------------------------------------------------------------
# Child entry: python -m areal_tpu.bench.runner --phase X --pass Y
# ----------------------------------------------------------------------


def _child_main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--phase", required=True)
    parser.add_argument("--pass", dest="pass_", required=True,
                        choices=list(bank.PASSES))
    parser.add_argument("--bank", default=None)
    args = parser.parse_args(argv)

    enable_compilation_cache()

    from areal_tpu.base.fault_injection import faults

    # Scope = "bench/<phase>": an AREAL_FAULTS spec can wedge or kill ONE
    # phase's subprocess out of a multi-phase run.
    faults.set_scope(f"bench/{args.phase}")
    phases.load_extra_modules()
    spec = phases.get(args.phase)
    started = time.time()
    try:
        faults.maybe_fail("bench.runner.phase")
        fn = spec.resolve()
        value = fn(args.pass_)
        if not isinstance(value, dict):
            raise TypeError(
                f"phase {spec.name!r} returned {type(value).__name__}, "
                "expected dict"
            )
        rec = bank.make_record(
            spec.name, args.pass_, "ok", value=value,
            started_at=started, finished_at=time.time(),
        )
        path = bank.write_record(rec, args.bank)
        print(json.dumps({"banked": path, "status": "ok"}), flush=True)
        return 0
    except BaseException as e:  # bank the failure, then re-signal it
        err = f"{type(e).__name__}: {e}"
        log(f"bench: phase {spec.name!r} ({args.pass_}) failed: {err}")
        try:
            # probe=False: attesting the failure must not call
            # jax.devices() — if the failure IS the backend, that probe
            # can wedge this child past its deadline and downgrade the
            # rich traceback record below to a parent-side 'timeout'.
            rec = bank.make_record(
                spec.name, args.pass_, "failed", error=err,
                tail=traceback.format_exc()[-TAIL_BYTES:],
                started_at=started, finished_at=time.time(), probe=False,
            )
            bank.write_record(rec, args.bank)
        except Exception:
            pass  # the parent will bank from the captured output tail
        if isinstance(e, KeyboardInterrupt):
            raise
        return 1


def enable_compilation_cache() -> None:
    """Point JAX's persistent compilation cache at a stable directory
    (min-compile-time floors dropped so every bench program caches).
    This is what makes the compile/measure split real: the compile pass
    subprocess dies, the cache entries survive."""
    import jax

    cache_dir = env_registry.get_str("AREAL_XLA_CACHE_DIR") or (
        os.path.join(tempfile.gettempdir(), "areal_xla_cache")
    )
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"bench: persistent compilation cache at {cache_dir}")


if __name__ == "__main__":
    sys.exit(_child_main())
