"""Test configuration: force an 8-device virtual CPU platform.

Mirrors the reference's CPU-only multi-process test strategy (SURVEY.md §4)
the TPU way: a single process with 8 virtual CPU devices so every sharding
path (data/fsdp/tensor/seq mesh axes) exercises real XLA collectives
without TPU hardware.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# Sandboxed python-answer programs get generous wall time under CI load
# (interpreter spawn alone can take seconds on a busy machine); the
# runaway-program test passes its own tight timeout explicitly.
os.environ.setdefault("AREAL_PYEXEC_TIMEOUT", "30")
# Same discipline for the math grader's sympy-equivalence subprocess:
# under full-suite load the forked child's cold sympy import can eat
# the whole 3s production budget and misjudge legit equivalences
# (test_sympy_equivalence flaked exactly this way). The adversarial
# hang test still bounds total wall clock at 30s.
os.environ.setdefault("AREAL_SYMPY_TIMEOUT_S", "10")

if not os.environ.get("AREAL_ONCHIP_TESTS"):
    # AREAL_ONCHIP_TESTS=1 keeps the real platform so the compiled-kernel
    # parity gates (e.g. test_splash_compiled_matches_reference_on_tpu)
    # can run on hardware; everything else pins the virtual CPU mesh —
    # in the environment, so every process a test spawns inherits it.
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax

if not os.environ.get("AREAL_TEST_NO_XLA_CACHE"):
    # Persistent XLA compilation cache for the suite (same discipline as
    # bench.py): the tier-1 run is compile-dominated on a loaded CPU
    # machine, and repeated runs re-trace identical tiny programs.
    # Correctness-neutral — the cache is keyed by computation hash.
    # AREAL_TEST_NO_XLA_CACHE=1 opts out (e.g. compile-time measurements).
    import tempfile

    _cache_dir = os.environ.get(
        "AREAL_XLA_CACHE_DIR",
        os.path.join(tempfile.gettempdir(), "areal_xla_cache"),
    )
    os.makedirs(_cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import uuid

import pytest

# One env knob scales every CPU-contention-sensitive timeout in the
# suite (tests opt in via tests.fixtures.scale_timeout); the sandboxed
# python-exec budget above participates too.
from tests.fixtures import scale_timeout as _scale_timeout

if not os.environ.get("_AREAL_PYEXEC_TIMEOUT_SCALED"):
    # Sentinel: xdist workers inherit the parent's env, so scaling must
    # apply exactly once, not compound per worker.
    os.environ["AREAL_PYEXEC_TIMEOUT"] = str(
        _scale_timeout(float(os.environ.get("AREAL_PYEXEC_TIMEOUT", "30")))
    )
    os.environ["_AREAL_PYEXEC_TIMEOUT_SCALED"] = "1"


def pytest_collection_modifyitems(config, items):
    """Under pytest-xdist, pin every `serial`-marked test onto ONE
    worker (xdist_group + --dist loadgroup) so the heavyweight e2e runs
    never stack on top of each other; without xdist the marker is
    purely documentary."""
    if not config.pluginmanager.hasplugin("xdist"):
        return
    for item in items:
        if "serial" in item.keywords:
            item.add_marker(pytest.mark.xdist_group("serial-e2e"))


@pytest.fixture
def tmp_name_resolve(tmp_path):
    """Fresh NFS-backend name_resolve rooted in a tmp dir."""
    from areal_tpu.base import name_resolve

    repo = name_resolve.reconfigure("nfs", record_root=str(tmp_path / "name_resolve"))
    yield repo
    repo.reset()


@pytest.fixture
def experiment_context():
    from areal_tpu.base import constants

    exp, trial = f"test-exp-{uuid.uuid4().hex[:6]}", "trial0"
    constants.set_experiment_trial_names(exp, trial)
    yield exp, trial
