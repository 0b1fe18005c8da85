"""ISSUE 17 acceptance (bench leg): the `moe_scaling` phase banks an
attested CPU-proxy record — dense vs MoE per-token step time at matched
active FLOPs, dropless EP1 vs EP2 loss-trajectory parity, the
capacity-vs-dropless dispatch A/B with its drop-rate sweep, and the
expert-sliced stream's ~1/EP per-rank ingress over a live origin — and
`validate_bench.py` refuses the three failure classes: parity-missing
records, dropless arms that realized drops, and EP streams whose
ingress did not shrink.

Loss parity, realized drop rates, and sha256 byte accounting are exact
and machine-independent, which is why a CPU-proxy record is real
evidence here; absolute step times only mean anything on-chip.

The phase runs through the REAL bench runner (own subprocess +
PhaseSpec.env 2-fake-device mesh + child-banked attested record) — the
production path.

Time budget: ~40 s (child imports + compiles)."""

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
def test_moe_scaling_record_banks_and_validates(tmp_path, monkeypatch):
    b = str(tmp_path / "bank")
    monkeypatch.setenv("AREAL_BENCH_BANK", b)
    # The child gets exactly the phase's requested device topology (the
    # runner APPENDS PhaseSpec.env XLA_FLAGS to inherited ones; the
    # suite's 8-device conftest flag would otherwise ride along).
    monkeypatch.setenv("XLA_FLAGS", "")
    rec = runner.run_phase(
        "moe_scaling", "measure", b, deadline_s=scale_timeout(360)
    )
    assert rec["status"] == "ok", rec
    bank.validate_record(rec)
    assert rec["attestation"]["platform"] == "cpu"
    assert rec["attestation"]["driver_verified"] is False

    validator = _load_validator()
    assert validator.validate_phase_value("moe_scaling", rec) == []
    assert validator.validate_bank_dir(b) == []

    v = rec["value"]
    # THE acceptance numbers: dropless EP2 and the no-drop capacity arm
    # track dropless EP1, nothing dropped, per-rank ingress ~1/EP at
    # ~one origin payload, and the sweep shows drops vanishing.
    assert v["ep_parity_ok"] == 1.0 and v["capacity_parity_ok"] == 1.0
    assert v["ep_loss_max_rel_err"] < 1e-5
    assert v["dropless_drop_rate"] == 0.0 and v["ep2_drop_rate"] == 0.0
    assert v["ep_ingress_frac_max"] <= 1.0 / v["ep_degree"] + 0.25
    assert v["origin_full_payloads"] <= 1.05
    assert v["capacity_sweep"][0]["drop_rate"] > 0.0
    assert v["capacity_sweep"][-1]["drop_rate"] == 0.0
    for k in ("dense_step_s", "moe_ep1_step_s", "moe_ep2_step_s",
              "capacity_step_s"):
        assert v[k] > 0  # the A/B step-time breakdown banked

    # Validator teeth, refusal class 1: parity-missing records.
    bad = json.loads(json.dumps(rec))
    del bad["value"]["ep_parity_ok"]
    assert validator.validate_phase_value("moe_scaling", bad)
    bad = json.loads(json.dumps(rec))
    bad["value"]["ep_parity_ok"] = 0.0
    assert any(
        "diverged" in p
        for p in validator.validate_phase_value("moe_scaling", bad)
    )
    # Refusal class 2: a "dropless" arm that realized drops.
    bad = json.loads(json.dumps(rec))
    bad["value"]["dropless_drop_rate"] = 0.02
    assert any(
        "broken dispatcher" in p
        for p in validator.validate_phase_value("moe_scaling", bad)
    )
    # Refusal class 3: an EP stream whose ingress did not shrink.
    bad = json.loads(json.dumps(rec))
    bad["value"]["ep_ingress_frac_max"] = 1.0
    assert any(
        "shrink" in p
        for p in validator.validate_phase_value("moe_scaling", bad)
    )
    # And the sweep is structural evidence: absent or non-monotone
    # drop-rate curves are refused too.
    bad = json.loads(json.dumps(rec))
    bad["value"]["capacity_sweep"] = []
    assert any(
        "capacity_sweep" in p
        for p in validator.validate_phase_value("moe_scaling", bad)
    )
    bad = json.loads(json.dumps(rec))
    bad["value"]["capacity_sweep"][-1]["drop_rate"] = 0.9
    assert any(
        "non-increasing" in p
        for p in validator.validate_phase_value("moe_scaling", bad)
    )


def test_moe_scaling_registered_as_default_proxy_phase():
    """The daemon picks moe_scaling up by default; CPU rounds self-label
    proxy evidence. Budget: <1 s (no phase body runs)."""
    from areal_tpu.bench import phases

    spec = phases.get("moe_scaling")
    assert spec.default and spec.proxy
    assert spec in phases.default_phases()
    assert "host_platform_device_count=2" in spec.env["XLA_FLAGS"]
