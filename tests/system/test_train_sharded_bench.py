"""ISSUE 9 acceptance (bench leg): the `train_sharded` phase banks an
attested CPU-proxy record with loss-trajectory parity (single-device vs
FSDP2 vs TP2 fake-device meshes), the per-mesh step-time breakdown, and
the shard-local dump's host high-water reduced ~1/mesh_size with a
byte-identical weight-plane round trip — and `validate_bench.py`
refuses records lacking the parity / scaling / high-water fields.

Loss parity and sha256 byte accounting are exact and machine
independent, which is why a CPU-proxy record is real evidence here.

The phase runs through the REAL bench runner (its own subprocess +
PhaseSpec.env 2-fake-device mesh + child-banked attested record) — the
production path. (On jax 0.9.0 the phase also runs in-process, with a
cold or a warm persistent cache; the subprocess is the runner's
contract, no longer a workaround.)

Time budget: ~45 s (child imports + compiles);
tier-1 headroom is tracked per PR 7's discipline."""

import importlib.util
import json
import os

import pytest

from areal_tpu.bench import bank, runner
from tests.fixtures import scale_timeout

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

pytestmark = pytest.mark.serial


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_bench", os.path.join(REPO, "scripts", "validate_bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.timeout(420)
def test_train_sharded_record_banks_and_validates(tmp_path, monkeypatch):
    b = str(tmp_path / "bank")
    monkeypatch.setenv("AREAL_BENCH_BANK", b)
    # The child gets exactly the phase's requested device topology (the
    # runner APPENDS PhaseSpec.env XLA_FLAGS to inherited ones; the
    # suite's 8-device conftest flag would otherwise ride along).
    monkeypatch.setenv("XLA_FLAGS", "")
    rec = runner.run_phase(
        "train_sharded", "measure", b, deadline_s=scale_timeout(360)
    )
    assert rec["status"] == "ok", rec
    bank.validate_record(rec)
    assert rec["attestation"]["platform"] == "cpu"
    assert rec["attestation"]["driver_verified"] is False

    validator = _load_validator()
    assert validator.validate_phase_value("train_sharded", rec) == []
    assert validator.validate_bank_dir(b) == []

    v = rec["value"]
    # THE acceptance numbers: mesh trajectories match the single-device
    # engine, and the shard-local dump halves the host high-water.
    assert v["fsdp2_parity_ok"] == 1.0 and v["tp2_parity_ok"] == 1.0
    assert v["loss_parity_max_rel_err"] < 5e-4
    assert v["dump_highwater_frac"] <= 0.6
    assert v["dump_roundtrip_ok"] == 1.0
    for k in ("single_step_s", "fsdp2_step_s", "tp2_step_s"):
        assert v[k] > 0  # the step-time breakdown banked

    # Validator teeth: records that lost the parity...
    bad = json.loads(json.dumps(rec))
    bad["value"]["tp2_parity_ok"] = 0.0
    assert any(
        "diverged" in p
        for p in validator.validate_phase_value("train_sharded", bad)
    )
    # ...whose dump did not shrink the high-water...
    bad = json.loads(json.dumps(rec))
    bad["value"]["dump_highwater_frac"] = 1.0
    assert any(
        "high-water" in p
        for p in validator.validate_phase_value("train_sharded", bad)
    )
    # ...or that lack the round-trip field entirely are refused.
    bad = json.loads(json.dumps(rec))
    del bad["value"]["dump_roundtrip_ok"]
    assert validator.validate_phase_value("train_sharded", bad)
