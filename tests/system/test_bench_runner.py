"""Bench runner under chaos: a wedged or killed phase subprocess costs
one phase, never the bank. Covers the acceptance flow — simulated flap
mid-phase leaves a valid bank, a restart completes only the unbanked
phases, and the report the bank yields validates clean."""

import json
import os
import subprocess
import sys

import pytest

from areal_tpu.bench import bank, phases, report, runner
from tests.fixtures import scale_timeout
from tests.system.bench_phases import read_counter

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def bench_env(tmp_path, monkeypatch):
    scratch = tmp_path / "scratch"
    scratch.mkdir()
    b = str(tmp_path / "bank")
    monkeypatch.setenv("AREAL_BENCH_BANK", b)
    monkeypatch.setenv("AREAL_BENCH_TEST_SCRATCH", str(scratch))
    monkeypatch.setenv("AREAL_BENCH_PHASE_MODULES", "tests.system.bench_phases")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # Subprocess jax imports are pure overhead here: keep them 1-device.
    monkeypatch.setenv("XLA_FLAGS", "")
    yield b, str(scratch)


def test_ok_phase_banks_attested_record(bench_env):
    b, scratch = bench_env
    rec = runner.run_phase("t_alpha", "measure", b,
                           deadline_s=scale_timeout(120))
    assert rec["status"] == "ok"
    assert rec["value"] == {"alpha_metric": 42.0}
    assert rec["attestation"]["platform"] == "cpu"
    assert rec["attestation"]["driver_verified"] is False
    assert read_counter(scratch, "t_alpha.measure") == 1
    # The banked file is the same validated record.
    on_disk = bank.load_record(b, "t_alpha", "measure")
    assert on_disk["value"] == rec["value"]


def test_phase_exception_banked_as_failure(bench_env):
    b, _ = bench_env
    rec = runner.run_phase("t_broken", "measure", b,
                           deadline_s=scale_timeout(120))
    assert rec["status"] == "failed"
    assert "always fails" in (rec["error"] or "") + (rec["tail"] or "")
    assert rec["value"] is None
    bank.validate_record(rec)  # a failure is still well-formed evidence
    assert not bank.is_banked(b, "t_broken", "measure", "cpu")


def test_crashed_subprocess_banked_by_parent(bench_env, monkeypatch):
    """A hard kill (PJRT-crash stand-in: fault action 'die' = os._exit)
    leaves no child-written record; the parent banks the failure with
    the captured output tail."""
    b, scratch = bench_env
    monkeypatch.setenv("AREAL_FAULTS", "bench.runner.phase@bench/t_alpha=die")
    rec = runner.run_phase("t_alpha", "measure", b,
                           deadline_s=scale_timeout(120))
    assert rec["status"] == "failed"
    assert "exited" in rec["error"]
    # The fault fired before the phase body ran.
    assert read_counter(scratch, "t_alpha.measure") == 0
    bank.validate_record(bank.load_record(b, "t_alpha", "measure"))


def test_parent_failure_never_clobbers_child_ok_record(bench_env,
                                                       monkeypatch):
    """A child that atomically banks its ok record and THEN wedges/dies
    (teardown hung) must not have the completed
    measurement overwritten by the parent's failure bookkeeping."""
    b, _ = bench_env
    # Stand-in for "child banked ok, then died": the record exists and is
    # fresh when the parent observes a crashed child.
    bank.write_record(bank.make_record(
        "t_alpha", "measure", "ok", value={"alpha_metric": 42.0}), b)
    monkeypatch.setenv("AREAL_FAULTS", "bench.runner.phase@bench/t_alpha=die")
    rec = runner.run_phase("t_alpha", "measure", b,
                           deadline_s=scale_timeout(120))
    assert rec["status"] == "ok"
    assert rec["value"] == {"alpha_metric": 42.0}
    assert bank.load_record(b, "t_alpha", "measure")["status"] == "ok"


def test_wedged_subprocess_killed_at_deadline(bench_env, monkeypatch):
    """A hang (wedged-XLA-compile stand-in) is killed at the phase
    deadline and banked as a timeout — it costs at most one phase."""
    b, _ = bench_env
    monkeypatch.setenv("AREAL_FAULTS", "bench.runner.phase@bench/t_slow=hang")
    rec = runner.run_phase("t_slow", "measure", b,
                           deadline_s=scale_timeout(15))
    assert rec["status"] == "timeout"
    assert "deadline" in rec["error"]
    bank.validate_record(bank.load_record(b, "t_slow", "measure"))


def test_flap_then_restart_completes_only_unbanked(bench_env, monkeypatch):
    """Acceptance flow: kill one phase mid-run (chaos hook), assert the
    bank survived, then re-run and assert only the unbanked phase
    executed; the report built from the bank validates clean, every
    record carrying an attestation block."""
    import bench

    b, scratch = bench_env
    specs = [phases.get("t_alpha"), phases.get("t_beta")]

    # Run 1: t_beta's subprocess is killed mid-phase (simulated flap).
    monkeypatch.setenv("AREAL_FAULTS", "bench.runner.phase@bench/t_beta=die")
    monkeypatch.setenv("AREAL_BENCH_PHASE_DEADLINE_S", str(scale_timeout(120)))
    assert bench.run_oneshot(specs, b, "cpu") is False
    assert bank.is_banked(b, "t_alpha", "measure", "cpu")
    assert not bank.is_banked(b, "t_beta", "measure", "cpu")
    assert read_counter(scratch, "t_alpha.measure") == 1

    # Run 2: no faults; only t_beta may execute.
    monkeypatch.delenv("AREAL_FAULTS")
    assert bench.run_oneshot(specs, b, "cpu") is True
    assert read_counter(scratch, "t_alpha.compile") == 1
    assert read_counter(scratch, "t_alpha.measure") == 1
    assert read_counter(scratch, "t_beta.compile") == 1
    assert read_counter(scratch, "t_beta.measure") == 1

    # Every banked record (incl. run 1's failure overwritten by run 2's
    # ok) is schema-valid with an attestation block.
    records = bank.load_bank(b)
    assert {("t_alpha", "measure"), ("t_beta", "measure"),
            ("t_alpha", "compile"), ("t_beta", "compile")} <= set(records)
    for rec in records.values():
        bank.validate_record(rec)
        assert rec["attestation"]["driver_verified"] is False

    # Report + driver-line + validator (the scripts/ entry points).
    out = str(os.path.join(scratch, "BENCH_test.json"))
    proc = subprocess.run(
        [sys.executable, "scripts/bench_report.py", "--bank", b,
         "--out", out, "--round", "rtest"],
        cwd=REPO, capture_output=True, text=True,
        timeout=scale_timeout(120),
    )
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(open(out).read())
    assert rep["schema"] == bank.REPORT_SCHEMA
    assert rep["round"] == "rtest"
    assert rep["driver_verified"] is False
    proc = subprocess.run(
        [sys.executable, "scripts/validate_bench.py", out],
        cwd=REPO, capture_output=True, text=True,
        timeout=scale_timeout(60),
    )
    assert proc.returncode == 0, proc.stderr


def _load_validator():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "validate_bench", os.path.join(REPO, "scripts", "validate_bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_validator_rejects_tampered_evidence(bench_env):
    """Failures on malformed records and on headline numbers that lack
    driver_verified: true without the proxy label (the conflation the
    attestation block exists to prevent)."""
    b, _ = bench_env
    runner.run_phase("t_alpha", "measure", b, deadline_s=scale_timeout(120))
    rep = report.build_report(b)
    validator = _load_validator()

    assert validator.validate_report(rep) == []

    # Inject an unlabeled CPU headline number: conflation, must fail.
    bad = json.loads(json.dumps(rep))
    bad["headline"]["alpha_metric"] = {"value": 42.0,
                                       "driver_verified": False}
    assert any("conflate" in p for p in validator.validate_report(bad))

    # Strip an attestation block: malformed record, must fail.
    bad = json.loads(json.dumps(rep))
    del bad["phases"]["t_alpha"]["attestation"]
    assert validator.validate_report(bad)

    # A report claiming driver_verified its records don't back: fail.
    bad = json.loads(json.dumps(rep))
    bad["driver_verified"] = True
    assert validator.validate_report(bad)

    # --require-driver-verified gates CPU evidence out of a chip round.
    ok_proxy = json.loads(json.dumps(rep))
    ok_proxy["headline"]["x"] = {
        "value": 1.0, "driver_verified": False, "evidence": "proxy",
    }
    assert validator.validate_report(ok_proxy, require_driver=False) == []
    assert validator.validate_report(ok_proxy, require_driver=True)


def test_weight_update_phase_survives_peer_chaos_and_validates(
    bench_env, monkeypatch
):
    """ISSUE 5 CI satellite: the weight_update phase, run through the
    subprocess runner with AREAL_FAULTS killing a mid-transfer peer
    (the middle holder of the chain dies serving its child), must
    re-fanout from the SURVIVING peer, bank an ok record that still
    carries the O(1)-origin-egress invariant, and leave a bank + report
    that validate clean."""
    import pytest as _pytest

    b, _ = bench_env
    # The phase moves a 16 MiB payload in 1 MiB chunks along a 3-holder
    # chain; waves are strictly ordered, so /weights/chunk hits 33-48
    # are h1 serving h2. Fire all 3 retry attempts of h2's chunk 7:
    # h1 "dies" mid-serve and h2 must re-fanout from h0, not the origin.
    monkeypatch.setenv(
        "AREAL_FAULTS", "weight_plane.serve_chunk=raise:k=40:n=3"
    )
    rec = runner.run_phase(
        "weight_update", "measure", b, deadline_s=scale_timeout(300)
    )
    monkeypatch.delenv("AREAL_FAULTS")
    assert rec["status"] == "ok", rec
    val = rec["value"]
    # Re-fanout went peer-to-peer: the origin still egressed exactly
    # one payload, and the transfer/cutover split is intact.
    assert val["origin_full_payloads"] == _pytest.approx(1.0)
    assert val["weight_transfer_ms"] > 0.0
    assert val["weight_cutover_ms"] > 0.0
    assert val["weight_update_ms"] >= val["weight_transfer_ms"]

    validator = _load_validator()
    assert validator.validate_bank_dir(b) == []
    rep = report.build_report(b)
    assert validator.validate_report(rep) == []

    # The validator's schema coverage has teeth: strip a required key /
    # degrade the invariant and the same record must now fail.
    tampered = json.loads(json.dumps(rec))
    del tampered["value"]["weight_cutover_ms"]
    assert any(
        "weight_cutover_ms" in p
        for p in validator.validate_phase_value("weight_update", tampered)
    )
    degraded = json.loads(json.dumps(rec))
    degraded["value"]["origin_full_payloads"] = 3.0
    assert any(
        "broadcast" in p
        for p in validator.validate_phase_value("weight_update", degraded)
    )
