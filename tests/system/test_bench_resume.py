"""Bank flap tolerance: atomic per-phase records (a run killed
mid-phase resumes finished phases instead of losing the round), with
platform + freshness gates so stale or cross-platform evidence never
short-circuits a re-run."""

import json
import os

import pytest

from areal_tpu.bench import bank


@pytest.fixture(autouse=True)
def bank_env(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_BENCH_BANK", str(tmp_path / "bank"))
    yield str(tmp_path / "bank")


def _ok_record(phase, platform="cpu", **value):
    att = bank.attestation()
    att.update(platform=platform, driver_verified=platform == "tpu",
               n_devices=1, device_kind=platform)
    return bank.make_record(phase, "measure", "ok",
                            value=value or {"m": 1.0}, att=att)


def test_write_then_load_roundtrip(bank_env):
    bank.write_record(_ok_record("train_tflops", train_tflops=12.5))
    bank.write_record(_ok_record("gen_tps", gen_tps=340.0))
    loaded = bank.load_bank()
    assert loaded[("train_tflops", "measure")]["value"]["train_tflops"] == 12.5
    assert loaded[("gen_tps", "measure")]["value"]["gen_tps"] == 340.0
    assert bank.is_banked(None, "train_tflops", "measure", "cpu")


def test_platform_mismatch_not_banked(bank_env):
    bank.write_record(_ok_record("train_tflops", platform="tpu"))
    assert not bank.is_banked(None, "train_tflops", "measure", "cpu")
    assert bank.is_banked(None, "train_tflops", "measure", "tpu")


def test_stale_record_not_banked(bank_env):
    bank.write_record(_ok_record("train_tflops"))
    assert not bank.is_banked(None, "train_tflops", "measure", "cpu",
                              max_age_s=0.0)
    assert bank.is_banked(None, "train_tflops", "measure", "cpu",
                          max_age_s=3600.0)


def test_failed_record_not_banked(bank_env):
    bank.write_record(bank.make_record("gen_tps", "measure", "failed",
                                       error="backend lost"))
    assert not bank.is_banked(None, "gen_tps", "measure", "cpu")
    # ...but it IS loadable evidence of the failure.
    rec = bank.load_record(bank.bank_dir(None), "gen_tps", "measure")
    assert rec["error"] == "backend lost"


def test_cpu_record_never_clobbers_tpu_evidence(bank_env):
    """Records are platform-scoped files: a CPU dev/smoke run sharing
    the bank dir must not overwrite a driver-verified record banked
    mid-round, and reports must prefer the driver-verified evidence."""
    bank.write_record(_ok_record("train_tflops", platform="tpu",
                                 train_tflops=59.0))
    bank.write_record(_ok_record("train_tflops", platform="cpu",
                                 train_tflops=0.01))
    assert bank.is_banked(None, "train_tflops", "measure", "tpu")
    assert bank.is_banked(None, "train_tflops", "measure", "cpu")
    best = bank.load_bank()[("train_tflops", "measure")]
    assert best["attestation"]["platform"] == "tpu"
    assert best["value"]["train_tflops"] == 59.0
    # load_latest (the runner parent's this-run check) sees the newest.
    latest = bank.load_latest(bank.bank_dir(None), "train_tflops", "measure")
    assert latest["attestation"]["platform"] == "cpu"


def test_clear_bank(bank_env):
    bank.write_record(_ok_record("train_tflops"))
    bank.clear_bank()
    assert bank.load_bank() == {}
    bank.clear_bank()  # idempotent


def test_corrupt_record_skipped(bank_env):
    bank.write_record(_ok_record("train_tflops"))
    os.makedirs(bank_env, exist_ok=True)
    with open(os.path.join(bank_env, "gen_tps.measure.json"), "w") as f:
        f.write("{not json")
    loaded = bank.load_bank()
    assert ("train_tflops", "measure") in loaded
    assert ("gen_tps", "measure") not in loaded
    assert not bank.is_banked(None, "gen_tps", "measure", "cpu")


def test_tmp_files_never_load(bank_env):
    """A crash mid-write leaves only a .tmp — invisible to the bank."""
    bank.write_record(_ok_record("train_tflops"))
    rec = _ok_record("gen_tps")
    os.makedirs(bank_env, exist_ok=True)
    with open(os.path.join(bank_env, "gen_tps.measure.json.123.tmp"),
              "w") as f:
        json.dump(rec, f)
    assert set(bank.load_bank()) == {("train_tflops", "measure")}


def test_report_folds_rl_trace_summary(bank_env, monkeypatch):
    """AREAL_RL_TRACE runs keep their rl_* passthrough in the report and
    the one-line driver JSON (the PR 3 contract, docs/observability.md)."""
    from areal_tpu.base import tracing
    from areal_tpu.bench import report
    from areal_tpu.utils import rl_trace

    bank.write_record(_ok_record("train_tflops", train_tflops=10.0))
    monkeypatch.setattr(tracing, "enabled", lambda: True)
    monkeypatch.setattr(tracing, "trace_dir", lambda: "/nonexistent")
    monkeypatch.setattr(rl_trace, "summarize", lambda d: {
        "overlap_score": 0.5, "rollout_e2e_p50_ms": 12.0,
        "staleness_hist": {"0": 3},
    })
    rep = report.build_report(bank.bank_dir(None))
    assert rep["rl_trace"]["overlap_score"] == 0.5
    line = report.result_line(rep)
    assert line["rl_overlap_score"] == 0.5
    assert line["rl_rollout_e2e_p50_ms"] == 12.0
    assert line["rl_staleness_hist"] == {"0": 3}


def test_validate_rejects_driver_verified_lie():
    rec = _ok_record("train_tflops", platform="cpu")
    rec["attestation"]["driver_verified"] = True
    with pytest.raises(ValueError, match="driver_verified"):
        bank.validate_record(rec)


def test_write_rejects_malformed():
    rec = bank.make_record("x", "measure", "ok", value={"m": 1})
    rec.pop("attestation")
    with pytest.raises(ValueError):
        bank.write_record(rec)
