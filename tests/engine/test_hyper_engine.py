"""The trainer engine over a stack of several residual streams (the
`xing4_0` family at toy widths): its logprobs are the plain reference's,
a train step moves every parameter of the hyper-connections, counts the
cells the stream steps ran by the device's rule, reports what Sinkhorn
left undone, and `train.dispatch` names the kinds."""

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models import moe as moe_lib
from areal_tpu.ops import band_loop
from areal_tpu.ops.loss import response_positions
from benchmark.reference import xing4_0 as ref

from tests.engine.test_latent_engine import n_response, ppo_like_batch, response_loss
from tests.model.test_hyper_stack import HF, _cfg, _flat, _params

N_MBS = 3


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth=2, row_len_multiple=32, **over):
    cfg = _cfg(HF, **over)
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=row_len_multiple, prefetch_depth=depth,
        attn_impl="reference", hf_family="xing4_0")
    return cfg, eng


def test_the_engines_logprobs_are_the_plain_references():
    cfg, eng = engine()
    rng = np.random.default_rng(3)
    lens = [40, 23, 31]
    ids = rng.integers(0, 64, sum(lens)).astype(np.int32)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=lens,
        data={"packed_input_ids": ids})
    got = np.asarray(eng.forward(sample, MicroBatchSpec()).data["logprobs"], np.float32)
    o = 0
    for l in lens:
        want = ref.next_token_logprobs(eng.params, HF, ids[o:o + l], pad_to=256)
        np.testing.assert_allclose(got[o:o + l - 1], want, atol=5e-5)
        o += l


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_moves_the_hyper_connections_and_counts_what_it_ran(depth):
    cfg, eng = engine(depth)
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    lens = [30, 1, 44, 2, 25, 3, 38, 17, 51]
    prompts = [10, 1, 20, 1, 24, 1, 5, 8, 30]
    batch = ppo_like_batch(lens, prompts)
    tracing.start()
    try:
        stats = eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS), response_loss,
                                n_response, loss_name="t", scored_fn=response_positions)
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    moved = _flat(jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), after, before))
    assert all((v > 0) == ("expert_bias" not in k) for k, v in moved.items()), moved
    assert sum("hc1" in k or "hc2" in k for k in moved) == 2 * 2 * 3  # stacks x sublayers x leaves
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0
    # twenty iterations leave a row or column sum within 1e-4 of 1
    assert 0 < stats["t/mhc_res_err"] < 1e-4

    c = got["counters"]
    # toy rows are under two bands: every sublayer of every layer runs the row
    assert c["train.mhc_cells"] == 2 * 3 * c["train.cells"] > 0 == c["train.mhc_loop_cells"]
    assert c["train.band_cells"] == c["train.cells"]
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    for d in dispatch:
        assert d["kinds"] == "hc4.dense.latent.full.rope,hc4.moe.latent.full.rope x2"


def test_one_sinkhorn_iteration_shows_in_the_steps_stat():
    """What an operator reads where Sinkhorn has not converged: the same
    step with one iteration reports a distance a hundred times as large."""
    cfg, eng = engine(0, hc_sinkhorn_iters=1)
    batch = ppo_like_batch([30, 44, 25], [10, 20, 24])
    stats = eng.train_batch(batch, MicroBatchSpec(n_mbs=1), response_loss,
                            n_response, loss_name="t", scored_fn=response_positions)
    assert stats["t/mhc_res_err"] > 1e-2


def test_the_host_counts_the_stream_steps_cells_by_the_devices_rule(monkeypatch):
    """Where the scanned layers loop (one row alone of two bands or more
    that the packer may leave half empty), their four stream steps run
    the live bands and the dense layer's, a layer alone among several
    streams (`transformer._lone_layer_loops`), the whole row; one stream
    counts nothing, and its leading dense layer walks bands too."""
    monkeypatch.setattr(band_loop, "_BAND", 16)
    cfg, eng = engine(0, row_len_multiple=128)
    seg = np.zeros((1, 128), np.int32)
    seg[0, :40] = 1
    said = lambda eng, seg: eng.counts.of({"segment_ids": seg}, 0)[0]
    mhc = lambda eng, seg: tuple(
        said(eng, seg)[n] for n in ("train.mhc_cells", "train.mhc_loop_cells"))
    assert eng._dead_bands(128) and band_loop.band_cells_run(seg) == 48
    assert said(eng, seg)["train.band_cells"] == (2 * 48 + 128) // 3
    # all of them, and those inside the two layers that walk bands (whose
    # backward loop makes a band's forward once more)
    assert mhc(eng, seg) == (2 * (2 * 48 + 128), 2 * 2 * 48)
    assert mhc(eng, np.stack([seg, seg])) == (4 * (2 * 48 + 128), 4 * 2 * 48)
    _, plain = engine(0, hc_mult=1)
    assert said(plain, seg)["train.band_cells"] == 48
    assert not any("mhc" in name for name in said(plain, seg))
    # a row the packer fills to the last band runs whole in every layer
    eng.row_len_multiple = eng.counts.row_len_multiple = 16
    assert not eng._dead_bands(128) and mhc(eng, seg) == (2 * 3 * 128, 0)
    assert kinds_label(plain.model_cfg) == "dense.latent.full.rope,moe.latent.full.rope x2"


def test_the_ppo_interface_reports_what_sinkhorn_left_undone():
    from areal_tpu.api.config import ModelName
    from areal_tpu.api.model_api import Model
    from areal_tpu.interfaces.ppo import PPOActorInterface

    cfg, eng = engine(2)
    lens, prompts = [30, 44, 25, 38], [10, 20, 24, 5]
    total = sum(lens)
    batch = ppo_like_batch(lens, prompts)
    rng = np.random.default_rng(0)
    mask = np.concatenate([np.r_[np.zeros(p - 1), np.ones(l - p), 0.0]
                           for l, p in zip(lens, prompts)]).astype(np.float32)
    lp = np.asarray(eng.forward(batch, MicroBatchSpec()).data["logprobs"], np.float32)
    lp = np.r_[lp, 0.0][:total] if len(lp) < total else lp
    batch.update_(SequenceSample.from_default(
        ids=batch.ids, seqlens=lens,
        data={"packed_logprobs": (lp * mask).astype(np.float32),
              "ref_logprobs": (lp * mask).astype(np.float32),
              "rewards": rng.normal(size=len(lens)).astype(np.float32),
              "seq_no_eos_mask": np.zeros(len(lens), np.float32)}))
    stats = PPOActorInterface(n_minibatches=1).train_step(
        Model(name=ModelName("actor"), module=eng, tokenizer=None), batch, MicroBatchSpec())
    assert 0 < stats["ppo_actor/mhc_res_err"] < 1e-4
