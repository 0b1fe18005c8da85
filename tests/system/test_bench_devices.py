"""The one plain probe child (bench/devices.py): a jax-free parent asks
a throwaway process what `jax.devices()` says; a device that is not
there is a failure, not something to wait for."""

import pytest

from areal_tpu.bench import devices


def test_probe_reports_what_jax_reports():
    p = devices.probe_devices(timeout_s=120)
    assert p["platform"] == "cpu" and p["kind"] == "cpu" and p["count"] >= 1


def test_probe_failure_raises_with_the_childs_output(monkeypatch):
    monkeypatch.setattr(
        devices, "_PROBE", "import sys; sys.exit('Unable to initialize backend')"
    )
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        devices.probe_devices(timeout_s=60)


def test_probe_does_not_wait_past_its_timeout(monkeypatch):
    monkeypatch.setattr(devices, "_PROBE", "import time; time.sleep(60)")
    with pytest.raises(RuntimeError, match="exceeded 1s"):
        devices.probe_devices(timeout_s=1)
