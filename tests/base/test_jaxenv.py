"""utils/jaxenv: what a chip-holding process says about the environment
it ran in (`areal-ran {json}` log lines) and how they are read back."""

import logging

import jax
import pytest

from areal_tpu.utils import jaxenv


@pytest.fixture
def ran_log(monkeypatch):
    """Capture the `ran` logger's lines; start from a clean once-set."""
    lines = []

    class H(logging.Handler):
        def emit(self, record):
            lines.append((record.levelno, record.getMessage()))

    h = H()
    jaxenv.logger.addHandler(h)
    monkeypatch.setattr(jaxenv, "_said", set())
    yield lines
    jaxenv.logger.removeHandler(h)


def _facts(lines):
    return jaxenv.parse_ran("\n".join(f"ts ran INFO: {m}" for _, m in lines))


def test_say_once_per_content_and_parse_roundtrip(ran_log):
    jaxenv.say("k", a=1, b=[1, 2])
    jaxenv.say("k", a=1, b=[1, 2])  # same fact: said once
    jaxenv.say("k", a=2, b=[1, 2])
    assert _facts(ran_log) == [
        {"kind": "k", "a": 1, "b": [1, 2]}, {"kind": "k", "a": 2, "b": [1, 2]}
    ]
    # Lines without the tag are not facts.
    assert jaxenv.parse_ran("nothing here\nareal-ranX {}\n") == []


@pytest.mark.parametrize("backend,requested,ran,warns", [
    ("tpu", "auto", "reference", True),   # the device hidden: a warning
    ("tpu", "auto", "xla", True),
    ("tpu", "auto", "splash", False),
    ("tpu", "reference", "reference", False),  # asked for: no warning
    ("cpu", "auto", "reference", False),  # nothing to hide off the chip
])
def test_say_dispatch_warns_only_when_a_tpu_is_left_idle(
    ran_log, monkeypatch, backend, requested, ran, warns
):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    jaxenv.say_dispatch("attn_impl", requested, ran, "because", t=64)
    assert _facts(ran_log) == [{"kind": "attn_impl", "requested": requested,
                                "ran": ran, "why": "because", "t": 64}]
    assert any(lvl == logging.WARNING for lvl, _ in ran_log) is warns


def test_report_devices_and_usage_on_the_cpu(ran_log):
    jaxenv.report_devices("model_worker/7")
    jaxenv.report_usage("model_worker/7")
    dev, use = _facts(ran_log)
    assert dev["kind"] == "devices" and dev["worker"] == "model_worker/7"
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.local_devices())
    assert dev["ids"] == [d.id for d in jax.local_devices()]
    assert dev["native_host_ops"] is True  # g++ is in this container
    assert use["kind"] == "usage" and len(use["peak_hbm_bytes"]) == dev["count"]
    assert use["compile_s"] >= 0


def test_compile_seconds_are_the_build_records(ran_log, monkeypatch):
    """`compile_s` is read off `tracing.builds()` (the program's one
    jax.monitoring listener): every stage's seconds, a cache load's too,
    and a trace inside a trace once."""
    from jax import monitoring

    from areal_tpu.base import tracing

    tracing.watch_builds()
    monkeypatch.setattr(tracing, "_BUILDS", [])
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    monitoring.record_scalar(trace, 0.0, fun_name="outer")
    monitoring.record_scalar(trace, 0.0, fun_name="inner")
    monitoring.record_event_duration_secs(trace, 0.125, fun_name="inner")
    monitoring.record_event_duration_secs(trace, 0.25, fun_name="outer")
    monitoring.record_scalar(backend, 0.0, fun_name="jit(outer)")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 9.0)
    monitoring.record_event_duration_secs(backend, 1.5, fun_name="jit(outer)")
    assert [b["fun"] for b in tracing.builds()] == ["outer", "jit(outer)"]
    jaxenv.report_usage("w")
    [use] = _facts(ran_log)
    assert use["compile_s"] == 1.8  # 1.75 to a tenth, as the line prints it
