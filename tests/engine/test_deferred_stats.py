"""`train_batch` returns stats that are read when first looked at
(`engine/jax_engine.TrainStats`): a step of four minibatches read at its
end is, to the bit, the step read minibatch by minibatch, and between its
first enqueue and its last the host reads nothing from the device. Both
input paths, two losses; a serial-dispatch engine reads before it
returns; `stats_fetch_interval`'s stale values keep their host fields
exact; the mapping behaves as one."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import TrainStats
from areal_tpu.models.transformer import init_params
from areal_tpu.ops.loss import sft_loss_from_logprobs

from tests.engine.test_prefetch import (
    loss_weight, make_batch, mk_engine, packed_loss, small_cfg,
)

N_MINIBATCHES, N_MBS = 4, 2
PATHS = {"overlapped": 2, "fused": 0}  # path -> prefetch depth


def kinds_loss(lp, rows):
    """A loss whose aux holds every kind of entry the read tells apart:
    a plain sum (divided by the tokens), `mean:`, `sum:`, `num:` / `den:`."""
    mask = rows["loss_mask"]
    total, n = sft_loss_from_logprobs(lp, mask)
    return 2.0 * total, {
        "n_valid_tokens": n,
        "mean:clipped": jnp.mean((lp < -3.0) * mask),
        "sum:seen": n,
        "num:lp": jnp.sum(lp * mask),
        "den:lp": n,
    }


LOSSES = {"sft": packed_loss, "kinds": kinds_loss}


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def _params_of(eng):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(jax.device_get(eng.params))]


@pytest.fixture(scope="module", params=[(p, l) for p in sorted(PATHS) for l in sorted(LOSSES)],
                ids=lambda pl: "-".join(pl))
def steps(request):
    """Two steps of four minibatches on two engines from the same
    parameters: `eager` reads each minibatch's stats before the next is
    enqueued, `late` appends the mappings and reads them at the step's
    end, as the PPO loops do. The second step is traced on both."""
    path, loss = request.param
    tracing.reconfigure()
    params = init_params(small_cfg(), jax.random.PRNGKey(21))
    spec = MicroBatchSpec(n_mbs=N_MBS)
    out = {"path": path}
    for how in ("eager", "late"):
        eng = mk_engine(params, depth=PATHS[path])
        assert not eng._serial_dispatch
        got = None
        for step in range(2):
            if step == 1:
                tracing.start()
            try:
                sts = []
                for i in range(N_MINIBATCHES):
                    st = eng.train_batch(make_batch(n=8, seed=30 + i), spec, LOSSES[loss],
                                         loss_weight, version_steps=step, loss_name="t")
                    assert isinstance(st, TrainStats)
                    if how == "eager":
                        st = dict(st)
                    else:
                        assert not st.resolved
                    sts.append(st)
                stats = [dict(st) for st in sts]  # oldest first, as `ppo.stats` reads
            finally:
                if step == 1:
                    got = tracing.stop()
        out[how] = dict(stats=stats, params=_params_of(eng), got=got)
    return out


def test_parameters_are_bit_equal_and_stats_value_equal(steps):
    eager, late = steps["eager"], steps["late"]
    assert len(eager["params"]) == len(late["params"]) > 0
    for a, b in zip(eager["params"], late["params"]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert late["stats"] == eager["stats"]  # every key, every float
    assert all(np.isfinite(v) for st in late["stats"] for v in st.values())
    assert len({st["t/loss"] for st in late["stats"]}) == N_MINIBATCHES  # four batches


def _main(got):
    return sorted(got["spans"], key=lambda s: s["start_ns"])


def test_no_read_lies_between_the_steps_first_enqueue_and_its_last(steps):
    spans = _main(steps["late"]["got"])
    first = min(s["start_ns"] for s in spans if s["name"] == "train.dispatch")
    last_name = "train.apply" if steps["path"] == "overlapped" else "train.dispatch"
    last = max(s["end_ns"] for s in spans if s["name"] == last_name)
    fetches = [s for s in spans if s["name"] == "train.fetch_stats"]
    assert len(fetches) == N_MINIBATCHES
    assert all(s["attrs"]["stale"] is False and s["start_ns"] >= last for s in fetches)
    assert first < last
    # the eager reader's lie between them, one after each minibatch
    eager = [s for s in _main(steps["eager"]["got"]) if s["name"] == "train.fetch_stats"]
    e_last = max(s["end_ns"] for s in steps["eager"]["got"]["spans"] if s["name"] == last_name)
    assert sum(s["end_ns"] <= e_last for s in eager) == N_MINIBATCHES - 1


def test_behind_counts_the_calls_enqueued_after_the_one_read(steps):
    late = [s["attrs"]["behind"] for s in _main(steps["late"]["got"])
            if s["name"] == "train.fetch_stats"]
    assert late == [3, 2, 1, 0]
    assert steps["late"]["got"]["counters"]["train.stats_deferred"] == 3
    eager = [s["attrs"]["behind"] for s in _main(steps["eager"]["got"])
             if s["name"] == "train.fetch_stats"]
    assert eager == [0, 0, 0, 0]
    assert "train.stats_deferred" not in steps["eager"]["got"]["counters"]


def test_only_the_newest_programs_read_marks_the_device_drained(steps):
    """Read at the end: three reads with programs still queued behind
    them and one, the last, that empties the queue; its mark finds no
    enqueue in the session. Read at once: each read empties the queue and
    the next minibatch's first enqueue ends the stretch."""
    assert [s for s in steps["late"]["got"]["spans"] if s["name"] == "device.starved"] == []
    starved = [s for s in _main(steps["eager"]["got"]) if s["name"] == "device.starved"]
    until = "accum_step" if steps["path"] == "overlapped" else "fused_step"
    assert [s["attrs"] for s in starved] == [
        {"after": "train.fetch_stats", "until": until}] * (N_MINIBATCHES - 1)


def test_sum_entries_reach_the_recorders_counters_once_a_read(steps):
    for how in ("eager", "late"):
        counters, stats = steps[how]["got"]["counters"], steps[how]["stats"]
        if "t/seen" in stats[0]:
            assert counters["train.seen"] == sum(st["t/seen"] for st in stats)
        else:
            assert "train.seen" not in counters


def test_a_serial_dispatch_engine_reads_inside_train_batch():
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh

    params = init_params(small_cfg(), jax.random.PRNGKey(22))
    eng = mk_engine(params, depth=2, mesh=make_mesh(MeshSpec.parse("f2"), jax.devices()[:2]))
    assert eng._serial_dispatch
    tracing.start()
    try:
        sts = [eng.train_batch(make_batch(n=8, seed=40 + i), MicroBatchSpec(n_mbs=N_MBS),
                               packed_loss, loss_weight, loss_name="t") for i in range(2)]
        assert all(st.resolved for st in sts)
    finally:
        got = tracing.stop()
    by_id = {s["span"]: s for s in got["spans"]}
    fetches = [s for s in got["spans"] if s["name"] == "train.fetch_stats"]
    assert len(fetches) == 2
    for s in fetches:
        assert by_id[s["parent"]]["name"] == "train.batch" and s["attrs"]["behind"] == 0
    assert "train.stats_deferred" not in got["counters"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_stale_interval_still_serves_the_last_values_with_exact_host_fields(path):
    params = init_params(small_cfg(), jax.random.PRNGKey(23))
    eng = mk_engine(params, depth=PATHS[path], stats_fetch_interval=2)
    ref = mk_engine(params, depth=PATHS[path])
    spec = MicroBatchSpec(n_mbs=N_MBS)
    got, want = [], []
    for i in range(4):
        args = (make_batch(n=6 + i, seed=50 + i), spec, packed_loss, loss_weight)
        st = eng.train_batch(*args, version_steps=i, loss_name="t")
        stale = i == 2  # calls 1 and 2 read (nothing to serve, then the interval's), 3 stale
        assert st.resolved == stale  # a stale mapping is born read
        got.append(dict(st))
        want.append(dict(ref.train_batch(*args, version_steps=i, loss_name="t")))
    assert [st["t/stats_stale"] for st in got] == [0.0, 0.0, 1.0, 0.0]
    for i, (g, w) in enumerate(zip(got, want)):
        # host-side fields: exact whether or not the values are stale
        for k in ("t/n_tokens", "t/n_mbs", "t/lr"):
            assert g[k] == w[k], (i, k)
        device_side = ("t/loss", "t/grad_norm", "t/update_norm", "t/n_valid_tokens")
        src = got[1] if i == 2 else w  # the stale call serves the second call's values
        assert all(g[k] == src[k] for k in device_side), i
    for a, b in zip(_params_of(eng), _params_of(ref)):
        assert np.array_equal(a, b)


def test_the_mapping_survives_dict_items_and_json_and_reads_once(monkeypatch):
    from areal_tpu.engine.jax_engine import JaxTrainEngine

    reads = []
    inner = JaxTrainEngine._read_train_stats
    monkeypatch.setattr(JaxTrainEngine, "_read_train_stats",
                        lambda self, *a, **k: reads.append(1) or inner(self, *a, **k))
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(24)), depth=2)
    st = eng.train_batch(make_batch(n=8, seed=60), MicroBatchSpec(n_mbs=N_MBS),
                         kinds_loss, loss_weight, loss_name="t")
    assert reads == [] and not st.resolved and "on the device" in repr(st)
    assert not isinstance(st, dict)
    d = dict(st)
    assert reads == [1] and st.resolved
    assert set(d) == {"t/loss", "t/grad_norm", "t/update_norm", "t/n_tokens", "t/n_mbs",
                      "t/lr", "t/n_valid_tokens", "t/clipped", "t/seen", "t/lp"}
    assert dict(st.items()) == d == {k: st[k] for k in st} and len(st) == len(d)
    assert list(st.keys()) == list(d) and list(st.values()) == list(d.values())
    assert json.loads(json.dumps(dict(st))) == d
    assert "t/loss" in st and "t/nothing" not in st and st.get("t/nothing") is None
    assert (lambda **kw: kw)(**st) == d  # what `stats_tracker.scalar(**stats)` sees
    assert st == d and all(isinstance(v, float) for v in d.values())
    assert reads == [1]
    # the read's arithmetic by kind of entry
    assert d["t/lp"] == pytest.approx(-d["t/loss"] / 2.0, rel=1e-5)  # num / den
    assert d["t/seen"] == d["t/n_tokens"] and d["t/n_valid_tokens"] == 1.0
    assert 0.0 <= d["t/clipped"] <= 1.0
