"""The learning rate a train step asks for is a host number
(`engine/optimizer.host_lr_schedule`): numpy in float32, operation for
operation the optax schedule called eagerly with a Python int. Equal to
`float(make_lr_schedule(...)(pos))` to the last bit for `constant` and
`linear`; for `cosine` to one ulp of float32 (numpy's cosine against
XLA's; on this CPU they agree to the bit too, which the test does not
require). And it runs no jax computation."""

import numpy as np
import pytest

from areal_tpu.base import tracing
from areal_tpu.engine import optimizer
from areal_tpu.engine.optimizer import OptimizerConfig, host_lr_schedule, make_lr_schedule

TOTAL = 40
ULPS = {"constant": 0, "linear": 0, "cosine": 1}


def _positions(warmup):
    """0, around the join of warm-up and decay, mid-way, the end, past it."""
    return sorted({0, 1, max(warmup - 1, 0), warmup, warmup + 1, TOTAL // 2,
                   TOTAL - 1, TOTAL, TOTAL + 7})


def _ulps_apart(a: float, b: float) -> int:
    fa, fb = np.float32(a), np.float32(b)
    if float(fa) != a or float(fb) != b:  # a Python number no float32 holds
        return 0 if a == b else 1 << 30
    return abs(int(fa.view(np.int32)) - int(fb.view(np.int32)))


@pytest.mark.parametrize("min_lr_ratio", [0.0, 0.1])
@pytest.mark.parametrize("warmup_proportion", [0.0, 0.001, 0.25])
@pytest.mark.parametrize("kind", sorted(ULPS))
def test_the_host_value_is_the_optax_schedules(kind, warmup_proportion, min_lr_ratio):
    cfg = OptimizerConfig(lr=3.3e-5, lr_scheduler_type=kind,
                          warmup_steps_proportion=warmup_proportion,
                          min_lr_ratio=min_lr_ratio)
    warmup = int(warmup_proportion * TOTAL)
    assert warmup == {0.0: 0, 0.001: 0, 0.25: 10}[warmup_proportion]
    want_of, got_of = make_lr_schedule(cfg, TOTAL), host_lr_schedule(cfg, TOTAL)
    for pos in _positions(warmup):
        want, got = float(want_of(pos)), got_of(pos)
        assert type(got) is float
        assert _ulps_apart(want, got) <= ULPS[kind], (pos, want, got)
    # the ends, by value: the ramp starts at lr / warmup, the decay ends at the floor
    assert got_of(0) == pytest.approx(cfg.lr / max(warmup, 1), rel=1e-6)
    if kind != "constant":
        assert got_of(TOTAL + 7) == pytest.approx(cfg.lr * min_lr_ratio, rel=1e-6, abs=1e-12)


@pytest.mark.parametrize("kind", sorted(ULPS))
def test_the_cells_schedule_one_warm_up_step_of_a_thousand(kind):
    """The benchmark's cells and the launchers' default: warm-up 0.001 of
    1000 steps is one step, so even `constant` went through optax's join."""
    cfg = OptimizerConfig(lr=1e-4, lr_scheduler_type=kind, min_lr_ratio=0.1)
    want_of, got_of = make_lr_schedule(cfg, 1000), host_lr_schedule(cfg, 1000)
    for pos in (0, 1, 2, 500, 999, 1000, 1001):
        assert _ulps_apart(float(want_of(pos)), got_of(pos)) <= ULPS[kind], pos


@pytest.mark.parametrize("kind", sorted(ULPS))
def test_it_runs_no_jax_computation(kind, monkeypatch):
    """No program is traced, lowered, compiled or looked up while the host
    schedule runs (an eager optax schedule builds one an operation), and
    the optax schedule is never made."""
    import jax.numpy as jnp

    cfg = OptimizerConfig(lr=1e-4, lr_scheduler_type=kind, warmup_steps_proportion=0.1,
                          min_lr_ratio=0.1)
    tracing.watch_builds()
    float(make_lr_schedule(cfg, TOTAL)(jnp.int32(3)))  # the watch is live: this one builds
    n = len(tracing.builds())
    assert n > 0
    monkeypatch.setattr(optimizer, "make_lr_schedule",
                        lambda *a, **k: pytest.fail("the optax schedule was asked for"))
    monkeypatch.setattr(optimizer.optax, "join_schedules",
                        lambda *a, **k: pytest.fail("optax was asked for a schedule"))
    sched = host_lr_schedule(cfg, TOTAL)
    values = [sched(pos) for pos in range(TOTAL + 3)]
    assert len(tracing.builds()) == n
    assert all(type(v) is float and v > 0 for v in values)


def test_an_unknown_kind_is_refused_when_the_schedule_is_made():
    with pytest.raises(ValueError, match="unknown lr_scheduler_type"):
        host_lr_schedule(OptimizerConfig(lr_scheduler_type="step"), TOTAL)


def test_the_engine_asks_the_host_schedule():
    """`train_batch` reports the host value as `<loss>/lr` and hands the
    step the same number: nothing else of the engine knows a schedule."""
    import jax

    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.models.transformer import init_params
    from tests.engine.test_prefetch import loss_weight, make_batch, packed_loss, small_cfg

    cfg = OptimizerConfig(lr=1e-3, lr_scheduler_type="cosine", warmup_steps_proportion=0.2,
                          min_lr_ratio=0.1)
    eng = JaxTrainEngine(small_cfg(), init_params(small_cfg(), jax.random.PRNGKey(0)),
                         optimizer_config=cfg, total_train_steps=10, row_len_multiple=32)
    want_of = make_lr_schedule(cfg, 10)
    for pos in (0, 1, 2, 6, 12):
        st = eng.train_batch(make_batch(n=4, seed=pos), MicroBatchSpec(n_mbs=1), packed_loss,
                             loss_weight, version_steps=pos, loss_name="t")
        assert _ulps_apart(st["t/lr"], float(want_of(pos))) <= 1
