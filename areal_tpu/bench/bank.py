"""Atomic result bank: per-phase JSON records with attestation.

Every phase pass (compile or measure) lands as ONE file in the bank
directory, written tmp+rename so a crash mid-write can never leave a
half record. Each record carries an attestation block — device kind,
topology, jax/jaxlib/libtpu versions, git sha, and a ``driver_verified``
bool — so a report assembled later can prove which numbers came from a
real accelerator driver and which are CPU/virtual-mesh proxies.

Record layout (``areal-bench-record/v1``)::

    {
      "schema": "areal-bench-record/v1",
      "phase": "train_tflops",
      "pass": "compile" | "measure",
      "status": "ok" | "failed" | "timeout",
      "value": {...} | null,          # phase metrics (ok only)
      "error": str | null,
      "tail": str | null,             # captured child stderr/stdout tail
      "started_at": float, "finished_at": float,
      "attestation": {
        "platform": "tpu" | "cpu" | ...,
        "device_kind": str | null, "n_devices": int | null,
        "topology": str | null,
        "jax_version": str | null, "jaxlib_version": str | null,
        "libtpu_version": str | null,
        "git_sha": str | null, "hostname": str,
        "python": "3.12.x",
        "driver_verified": bool,      # platform == "tpu", period.
      }
    }

The bank is resumable state *and* evidence: loading filters by platform
and age (a stale record from an old round must not be re-reported), and
``validate_record`` is the same checker ``scripts/validate_bench.py``
runs, so malformed evidence fails loudly in CI rather than silently in
a report.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, Optional, Tuple

from areal_tpu.base import env_registry
from areal_tpu.base.wire_schemas import (
    BENCH_RECORD_V1 as RECORD_SCHEMA,
    BENCH_REPORT_V1 as REPORT_SCHEMA,
)
from areal_tpu.bench._util import repo_root

PASSES = ("compile", "measure")
STATUSES = ("ok", "failed", "timeout")

ATTESTATION_KEYS = (
    "platform", "device_kind", "n_devices", "topology",
    "jax_version", "jaxlib_version", "libtpu_version",
    "git_sha", "hostname", "python", "driver_verified",
)


def bank_dir(override: Optional[str] = None) -> str:
    return override or env_registry.get_str("AREAL_BENCH_BANK") or (
        os.path.join(tempfile.gettempdir(), "areal_bench_bank")
    )


def record_path(bank: str, phase: str, pass_: str,
                platform: Optional[str]) -> str:
    """One file per (phase, pass, platform): a CPU dev run sharing the
    bank dir must never overwrite a driver-verified TPU record banked
    mid-round — losing chip evidence to a smoke run is exactly the
    conflation this subsystem exists to prevent."""
    return os.path.join(bank, f"{phase}.{pass_}.{platform or 'unknown'}.json")


# ----------------------------------------------------------------------
# Attestation
# ----------------------------------------------------------------------


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo_root(), timeout=10,
            capture_output=True, text=True,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def attestation(devices=None, probe: bool = True) -> Dict:
    """Collect the attestation block for the CURRENT process.

    `devices` may be a pre-fetched jax device list; None probes lazily
    and degrades to nulls (a failed phase still attests versions + host,
    with driver_verified False). `probe=False` skips `jax.devices()`
    entirely — the runner PARENT uses it when banking a crash/timeout:
    it must stay off jax (a chip belongs to one process)."""
    att = {k: None for k in ATTESTATION_KEYS}
    att["hostname"] = socket.gethostname()
    att["python"] = ".".join(map(str, sys.version_info[:3]))
    att["git_sha"] = _git_sha()
    att["driver_verified"] = False
    try:
        import jax  # safe without probe: no backend init on import

        att["jax_version"] = jax.__version__
        try:
            import jaxlib

            att["jaxlib_version"] = getattr(jaxlib, "__version__", None)
        except Exception:
            pass
        import importlib.metadata as _md

        for pkg in ("libtpu", "libtpu-nightly"):
            try:
                att["libtpu_version"] = _md.version(pkg)
                break
            except Exception:
                continue
        if devices is None:
            devices = jax.devices() if probe else []
        if devices:
            d0 = devices[0]
            att["platform"] = d0.platform
            att["device_kind"] = getattr(d0, "device_kind", None)
            att["n_devices"] = len(devices)
            coords = getattr(d0, "coords", None)
            att["topology"] = (
                f"{len(devices)}x{att['device_kind']}"
                + (f" coords0={tuple(coords)}" if coords is not None else "")
            )
            att["driver_verified"] = d0.platform == "tpu"
    except Exception:
        pass  # no usable backend: nulls + driver_verified False stand
    return att


# ----------------------------------------------------------------------
# Records
# ----------------------------------------------------------------------


def make_record(
    phase: str,
    pass_: str,
    status: str,
    value: Optional[Dict] = None,
    error: Optional[str] = None,
    tail: Optional[str] = None,
    started_at: Optional[float] = None,
    finished_at: Optional[float] = None,
    att: Optional[Dict] = None,
    probe: bool = True,
) -> Dict:
    now = time.time()
    return {
        "schema": RECORD_SCHEMA,
        "phase": phase,
        "pass": pass_,
        "status": status,
        "value": value if status == "ok" else None,
        "error": error,
        "tail": tail,
        "started_at": started_at if started_at is not None else now,
        "finished_at": finished_at if finished_at is not None else now,
        "attestation": att if att is not None else attestation(probe=probe),
    }


def validate_record(rec: Dict) -> None:
    """Raise ValueError naming every problem with `rec`."""
    problems = []
    if not isinstance(rec, dict):
        raise ValueError("record is not an object")
    if rec.get("schema") != RECORD_SCHEMA:
        problems.append(f"schema != {RECORD_SCHEMA!r}: {rec.get('schema')!r}")
    if not rec.get("phase") or not isinstance(rec.get("phase"), str):
        problems.append("missing/invalid 'phase'")
    if rec.get("pass") not in PASSES:
        problems.append(f"'pass' not in {PASSES}: {rec.get('pass')!r}")
    if rec.get("status") not in STATUSES:
        problems.append(f"'status' not in {STATUSES}: {rec.get('status')!r}")
    if rec.get("status") == "ok" and not isinstance(rec.get("value"), dict):
        problems.append("ok record must carry an object 'value'")
    att = rec.get("attestation")
    if not isinstance(att, dict):
        problems.append("missing attestation block")
    else:
        for k in ATTESTATION_KEYS:
            if k not in att:
                problems.append(f"attestation missing {k!r}")
        dv = att.get("driver_verified")
        if not isinstance(dv, bool):
            problems.append("attestation.driver_verified must be a bool")
        elif dv and att.get("platform") != "tpu":
            problems.append(
                "attestation claims driver_verified on platform "
                f"{att.get('platform')!r}"
            )
    for k in ("started_at", "finished_at"):
        if not isinstance(rec.get(k), (int, float)):
            problems.append(f"missing/invalid {k!r}")
    if problems:
        raise ValueError("; ".join(problems))


def write_record(rec: Dict, bank: Optional[str] = None) -> str:
    """Validate then flush `rec` atomically; returns the record path."""
    validate_record(rec)
    b = bank_dir(bank)
    os.makedirs(b, exist_ok=True)
    path = record_path(b, rec["phase"], rec["pass"],
                       rec["attestation"].get("platform"))
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _iter_records(bank: str):
    try:
        names = sorted(os.listdir(bank))
    except OSError:
        return
    for name in names:
        if not name.endswith(".json") or name.endswith(".tmp"):
            continue
        try:
            with open(os.path.join(bank, name)) as f:
                rec = json.load(f)
            validate_record(rec)
        except (OSError, ValueError):
            continue  # malformed files must never poison a report
        yield rec


def _preference(rec: Dict) -> Tuple:
    """Evidence order: driver-verified ok > any ok > newest anything."""
    return (
        rec["status"] == "ok" and bool(rec["attestation"].get("driver_verified")),
        rec["status"] == "ok",
        rec["finished_at"],
    )


def load_record(bank: str, phase: str, pass_: str,
                platform: Optional[str] = None) -> Optional[Dict]:
    """The record for (phase, pass) — exact platform file when given,
    otherwise the best evidence across platforms (see _preference)."""
    if platform is not None:
        try:
            with open(record_path(bank, phase, pass_, platform)) as f:
                rec = json.load(f)
            validate_record(rec)
            return rec
        except (OSError, ValueError):
            return None
    cands = [r for r in _iter_records(bank)
             if r["phase"] == phase and r["pass"] == pass_]
    return max(cands, key=_preference) if cands else None


def load_latest(bank: str, phase: str, pass_: str) -> Optional[Dict]:
    """Most recently finished record for (phase, pass), any platform —
    the runner parent uses this to see what THIS run's child banked."""
    cands = [r for r in _iter_records(bank)
             if r["phase"] == phase and r["pass"] == pass_]
    return max(cands, key=lambda r: r["finished_at"]) if cands else None


def load_bank(
    bank: Optional[str] = None, max_age_s: Optional[float] = None,
) -> Dict[Tuple[str, str], Dict]:
    """Best-evidence record per (phase, pass) (see _preference). The
    age filter applies BEFORE preference: a stale driver-verified record
    must not shadow (and thereby discard) fresh evidence from another
    platform."""
    out: Dict[Tuple[str, str], Dict] = {}
    now = time.time()
    for rec in _iter_records(bank_dir(bank)):
        if max_age_s is not None and now - float(rec["finished_at"]) > max_age_s:
            continue
        key = (rec["phase"], rec["pass"])
        if key not in out or _preference(rec) > _preference(out[key]):
            out[key] = rec
    return out


def is_banked(
    bank: Optional[str],
    phase: str,
    pass_: str,
    platform: Optional[str] = None,
    max_age_s: Optional[float] = None,
) -> bool:
    """True if an OK record for (phase, pass) exists, is fresh, and was
    measured on `platform` (stale or cross-platform records must not
    short-circuit a re-run)."""
    if max_age_s is None:
        max_age_s = env_registry.get_float("AREAL_BENCH_STATE_TTL_S")
    rec = load_record(bank_dir(bank), phase, pass_, platform)
    if rec is None or rec["status"] != "ok":
        return False
    if platform is not None and rec["attestation"].get("platform") != platform:
        return False
    if time.time() - float(rec["finished_at"]) > max_age_s:
        return False
    return True


def clear_bank(bank: Optional[str] = None) -> None:
    b = bank_dir(bank)
    try:
        names = os.listdir(b)
    except OSError:
        return
    for name in names:
        if name.endswith(".json") or name.endswith(".tmp"):
            try:
                os.remove(os.path.join(b, name))
            except OSError:
                pass
