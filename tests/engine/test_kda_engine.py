"""The trainer engine over a stack of delta-rule mixers and latent
attention (the `kimi_linear` family at toy widths): its logprobs are the
plain reference's, a train step moves every parameter of both mixers,
counts the chunks the rule ran by the device's rule, `train.dispatch`
names the kinds, and the family runs through `PPOActorInterface`."""

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models import moe as moe_lib
from areal_tpu.ops import kda
from areal_tpu.ops.loss import response_positions
from benchmark.reference import kimi_linear as ref

from tests.engine.test_latent_engine import n_response, ppo_like_batch, response_loss
from tests.model.test_hyper_stack import _flat
from tests.model.test_kda_stack import HF, _cfg, _params

N_MBS = 3
KINDS = "dense.kda.c64,moe.kda.c64,moe.latent.full.nope,moe.kda.c64"


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def _said(eng, seg):
    """What the engine's counts say of rows of these segment ids."""
    return eng.counts.of({"segment_ids": seg}, 0)[0]


def engine(depth=2, row_len_multiple=32):
    cfg = _cfg(HF)
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=row_len_multiple, prefetch_depth=depth,
        attn_impl="reference", hf_family="kimi_linear")
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
def test_a_train_step_moves_both_mixers_and_counts_what_the_rule_ran(depth):
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
    assert sum("'kda'" in k for k in moved) == 2 * 15  # two stacks of the mixer's leaves
    assert sum("'attn'" in k for k in moved) == 5  # wq, wkv_a, kv_a_norm, wkv_b, wo
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0

    c = got["counters"]
    # toy rows are one group of chunks: the rule runs every chunk of a row
    # (96 cells: two of 64) in each of the three delta-rule layers
    assert c["train.kda_cells"] == 3 * c["train.cells"] // 96 * 128 > 0
    assert c["train.kda_chunks"] * 64 == c["train.kda_cells"]
    # the CPU takes the plain form, forward and backward
    assert c["train.kda_fwd_kernel_cells"] == c["train.kda_bwd_kernel_cells"] == 0
    # the convolutions of three layers were asked for every cell, and none of
    # them went through the taps' kernels
    assert c["train.kda_taps_cells"] == 3 * c["train.cells"]
    assert c["train.kda_taps_kernel_cells"] == 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"]
    assert c["train.kda_resets"] == 3 * len(lens)
    assert c["train.attn_cells"] == c["train.cells"]  # the one latent layer's alone
    assert "train.ssm_chunks" not in c
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    assert all(d["kinds"] == KINDS for d in dispatch)


def test_the_host_counts_the_rules_chunks_by_the_devices_rule(monkeypatch):
    """A row of 256 cells with 70 tokens at chunks of 64: with the row one
    group the rule runs its four chunks, with groups of a chunk the two up
    to its last token; three layers of it."""
    cfg, eng = engine(0, row_len_multiple=256)
    seg = np.zeros((1, 256), np.int32)
    seg[0, :40], seg[0, 40:70] = 1, 2
    names = ("train.kda_cells", "train.kda_chunks", "train.kda_chunks_live", "train.kda_resets")
    rule = lambda seg: tuple(_said(eng, seg)[n] for n in names)
    assert rule(seg) == (3 * 256, 3 * 4, 3 * 2, 3 * 2)
    monkeypatch.setattr(kda, "GROUP_CELLS", 64)
    assert rule(seg) == (3 * 128, 3 * 2, 3 * 2, 3 * 2)
    assert rule(np.stack([seg, seg])) == (6 * 128, 6 * 2, 6 * 2, 6 * 2)
    assert kinds_label(cfg) == KINDS


def test_the_family_runs_through_the_ppo_interface():
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
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    stats = PPOActorInterface(n_minibatches=1).train_step(
        Model(name=ModelName("actor"), module=eng, tokenizer=None), batch, MicroBatchSpec())
    assert stats["ppo_actor/n_tokens"] == total
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                                   eng.params, before)
    assert moved["stacks"]["kda+moe"]["kda"]["A_log"] > 0


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_the_kernels_cells_are_the_rules_where_the_kernels_run(kernel, monkeypatch):
    """`train.kda_fwd_kernel_cells` and `train.kda_bwd_kernel_cells`: every
    position the rule walked where `ops/kda.use_kernel` takes the kernels
    (one chip, heads of whole lane tiles), none where the plain form runs;
    `train.kda_cells` either way."""
    _, eng = engine(0, row_len_multiple=256)
    seen = []
    monkeypatch.setattr(kda, "use_kernel", lambda cfg, mesh: seen.append((cfg, mesh)) or kernel)
    seg = np.zeros((1, 256), np.int32)
    seg[0, :70] = 1
    tracing.start()
    try:
        eng._count_batch("fused", 1, 1, 70, 256, _said(eng, seg))
    finally:
        c = tracing.stop()["counters"]
    assert c["train.kda_cells"] == 768 and c["train.kda_chunks"] == 12
    assert c["train.kda_fwd_kernel_cells"] == (768 if kernel else 0)
    assert c["train.kda_bwd_kernel_cells"] == (768 if kernel else 0)
    assert seen == [(eng.model_cfg.kda, eng.mesh)]


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("head_dim", [16, 128], ids=["toy_heads", "lane_tiles"])
def test_the_taps_kernels_cells_are_the_convolutions_where_the_kernels_run(
        kernel, head_dim, monkeypatch):
    """`train.kda_taps_cells`: rows x row length x delta-rule layers, several
    micro-batches summed; `train.kda_taps_kernel_cells`: all of them where
    `ops/kda.use_kernel` holds and the shapes fit the taps' kernels (heads of
    128: widths of whole strips; a row of whole blocks), 0 where the plain
    form runs (the CPU; widths or a row's length that do not fit)."""
    import dataclasses

    _, eng = engine(0, row_len_multiple=256)
    eng.counts = dataclasses.replace(eng.counts, cfg=dataclasses.replace(
        eng.model_cfg, kda=dataclasses.replace(eng.model_cfg.kda, head_dim=head_dim)))
    monkeypatch.setattr(kda, "use_kernel", lambda cfg, mesh: kernel)
    seg = np.zeros((1, 256), np.int32)
    seg[0, :70] = 1
    took = kernel and head_dim == 128
    taps = lambda seg: tuple(
        _said(eng, seg)[n] for n in ("train.kda_taps_cells", "train.kda_taps_kernel_cells"))
    assert taps(seg) == (3 * 256, 3 * 256 if took else 0)
    assert taps(np.stack([seg, seg])) == (6 * 256, 6 * 256 if took else 0)
    assert taps(seg[:, :200]) == (3 * 200, 0)  # no whole blocks
    tracing.start()
    try:
        eng._count_batch("fused", 1, 1, 70, 256, _said(eng, seg))
    finally:
        c = tracing.stop()["counters"]
    assert c["train.kda_taps_cells"] == 768
    assert c["train.kda_taps_kernel_cells"] == (768 if took else 0)
