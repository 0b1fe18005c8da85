"""The trainer engine over a stack of one-part layers (the `nemotron_h`
family at toy widths): the state-space mixer's vectors take no weight
decay, the selection bias is a buffer, the scan's chunks are counted on
the host by the device's rule, attention's counters count attention
layers only, and `train.dispatch` names the kinds."""

import dataclasses

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import BUFFER_LEAVES, JaxTrainEngine, trainable
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.engine.optimizer import NO_DECAY_LEAVES, OptimizerConfig, _decay_mask
from areal_tpu.models.transformer import init_params
from areal_tpu.ops.ssm import chunk_counts

from tests.engine.test_prefetch import loss_weight, make_batch, packed_loss
from tests.model.test_hybrid_stack import HF, _cfg

N_MBS = 3


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth, weight_decay=0.05):
    cfg = _cfg()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(2))
    bias = params["stacks"]["moe"]["mlp"]["expert_bias"]
    params["stacks"]["moe"]["mlp"]["expert_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(3), bias.shape)
    eng = JaxTrainEngine(
        cfg, params,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                         weight_decay=weight_decay),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference")
    return cfg, eng


@pytest.mark.parametrize("leaf", ["A_log", "D", "dt_bias", "conv_b"])
def test_a_state_space_mixers_vectors_take_no_weight_decay(leaf):
    """Stacked on a layer axis they have two dimensions, so the rule of
    dimensions alone would decay them: they are named."""
    cfg = _cfg()
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    mask = _decay_mask(trainable(params))
    ssm = params["stacks"]["ssm"]["ssm"]
    assert leaf in NO_DECAY_LEAVES and ssm[leaf].ndim == 2
    assert mask["stacks"]["ssm"]["ssm"][leaf] is False
    # the matrices beside them are decayed as every matrix is
    for name in ("in_proj", "out_proj", "conv_w"):
        assert mask["stacks"]["ssm"]["ssm"][name] is True
    assert mask["stacks"]["moe"]["mlp"]["w_in"] is True
    assert mask["final_norm"]["weight"] is False


def test_the_published_selection_bias_is_the_buffer():
    """`e_score_correction_bias` of the checkpoint is `expert_bias` here,
    one of BUFFER_LEAVES: no gradient, no update, no Adam state."""
    from areal_tpu.models.hf import get_family

    cfg = _cfg()
    params = jax.tree_util.tree_map(
        np.asarray, jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(0)))
    mlp = params["stacks"]["moe"]["mlp"]
    mlp["expert_bias"] = mlp["expert_bias"] + np.arange(4, dtype=np.float32)[:, None]
    sd = get_family("nemotron_h").params_to_hf(params, cfg)
    np.testing.assert_array_equal(
        sd["backbone.layers.3.mixer.gate.e_score_correction_bias"],
        params["stacks"]["moe"]["mlp"]["expert_bias"][1])
    assert "expert_bias" in BUFFER_LEAVES
    assert "expert_bias" not in trainable(params)["stacks"]["moe"]["mlp"]


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_updates_the_weights_and_counts_the_chunks(depth):
    cfg, eng = engine(depth)
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    n_weights = len(jax.tree_util.tree_leaves(trainable(eng.params)))
    assert len(jax.tree_util.tree_leaves(eng.params)) == n_weights + 1
    moments = [l for l in jax.tree_util.tree_leaves(eng.opt_state) if l.ndim > 0]
    assert len(moments) == 2 * n_weights

    batch = make_batch(n=9, seed=5)
    tracing.start()
    try:
        stats = dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS),
                                     packed_loss, loss_weight, loss_name="t"))
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    bias = lambda p: p["stacks"]["moe"]["mlp"]["expert_bias"]
    np.testing.assert_array_equal(bias(after), bias(before))
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()),
                                   trainable(after), trainable(before))
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0

    c = got["counters"]
    # the chunks, by the device's rule from the rows the engine packed
    mbs, _, _ = batch.split(MicroBatchSpec(n_mbs=N_MBS))
    want = np.sum([chunk_counts(eng._build_rows(mb)[1]["segment_ids"], cfg.ssm.chunk_size)
                   for mb in mbs], axis=0) * cfg.n_ssm_layers
    assert cfg.n_ssm_layers == 4 and cfg.ssm.chunk_size == 16
    assert [c[f"train.{k}"] for k in ("ssm_chunks", "ssm_chunks_live",
                                      "ssm_chunks_mixed", "ssm_resets")] == list(want)
    assert c["train.ssm_chunks"] * cfg.ssm.chunk_size == c["train.cells"] * 4
    assert 0 < c["train.ssm_chunks_mixed"] < c["train.ssm_chunks_live"] <= c["train.ssm_chunks"]
    assert c["train.ssm_resets"] == 9 * 4  # every sequence starts once a layer
    # one attention layer of nine: the reference runs every cell of a row
    assert c["train.attn_cells"] == c["train.cells"]
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0
    assert c["train.moe_pairs"] == cfg.moe.top_k * c["train.tokens"] * 4
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    for d in dispatch:
        assert d["window"] is None
        assert d["kinds"] == "ssm,moe,ssm,moe,ssm,attn.full.nope,moe,ssm,moe"

    # attention's cells are those of the attention layers: two count twice
    seg = eng._build_rows(mbs[0])[1]["segment_ids"]
    one = eng.counts.of({"segment_ids": seg}, 0)[0]
    twice = dataclasses.replace(
        eng.counts, cfg=_cfg(dict(HF, hybrid_override_pattern="M*MEM*EME")))
    two = twice.of({"segment_ids": seg}, 0)[0]
    summed = [k for k in one if k.startswith("train.attn_")
              and k not in ("train.attn_cells", "train.attn_cells_in_place")]
    assert len(summed) == 7 and all(two[k] == 2 * one[k] for k in summed)
    assert one["train.attn_causal_cells"] > 0


def test_the_kinds_label_folds_runs_and_names_one_part_layers():
    from tests.model.test_layer_kinds import _cfg as afmoe_cfg

    assert kinds_label(afmoe_cfg()) == (
        "dense.w8.rope,moe.w8.rope x2,moe.full.nope,moe.w8.rope")
    hf = dict(HF, num_hidden_layers=6, hybrid_override_pattern="MM-*EE")
    assert kinds_label(_cfg(hf)) == "ssm x2,dense,attn.full.nope,moe x2"
