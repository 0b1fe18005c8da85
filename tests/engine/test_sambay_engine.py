"""The trainer engine over a SambaY stack (the `phi4flash` family at toy
widths): its logprobs are the plain reference's, the new per-channel and
lambda leaves take no weight decay, the scan's blocks of time and its
cells are counted on the host by the device's rule, attention's counters
run over the four attention layers, and `train.dispatch` names the kinds."""

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine, trainable
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.engine.optimizer import NO_DECAY_LEAVES, OptimizerConfig, _decay_mask
from areal_tpu.models.transformer import init_params
from areal_tpu.ops.ssm import chunk_counts
from areal_tpu.parallel.sharding import param_partition_spec
from benchmark.reference import phi4flash as ref

from tests.engine.test_prefetch import loss_weight, make_batch, packed_loss
from tests.model.test_sambay_stack import HF, _cfg, _params

N_MBS = 3


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth, n_layers=8, weight_decay=0.05):
    cfg = _cfg(num_hidden_layers=n_layers)
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                         weight_decay=weight_decay),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference", hf_family="phi4flash")
    return cfg, eng


@pytest.mark.parametrize("stack,group,leaf", [
    ("ssm+dense", "ssm", "A_log"), ("ssm+dense", "ssm", "D"), ("ssm+dense", "ssm", "dt_bias"),
    ("ssm+dense^", "ssm", "conv_b"), ("diffattention+dense", "attn", "lambda_q1"),
    ("diffattention+dense^", "attn", "lambda_k2"), ("xdiffattention+dense", "attn", "sub_norm"),
])
def test_the_per_channel_and_lambda_leaves_take_no_weight_decay(stack, group, leaf):
    cfg = _cfg()
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    mask = _decay_mask(trainable(params))
    assert leaf in NO_DECAY_LEAVES and params["stacks"][stack][group][leaf].ndim >= 2
    assert mask["stacks"][stack][group][leaf] is False
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj", "conv_w"):
        assert mask["stacks"]["ssm+dense"]["ssm"][name] is True
    assert mask["stacks"]["gmu+dense"]["gmu"]["w_in"] is True
    assert mask["stacks"]["xdiffattention+dense"]["attn"]["wq"] is True


def test_the_new_leaves_shard_by_the_rules_that_are_there():
    from jax.sharding import PartitionSpec as P

    spec = lambda path, ndim: param_partition_spec(path, ndim)
    assert spec("stacks/ssm+dense/ssm/in_proj", 3) == P(None, "fsdp", None)
    assert spec("stacks/ssm+dense/ssm/out_proj", 3) == P(None, None, "fsdp")
    assert spec("stacks/gmu+dense/gmu/w_in", 3) == P(None, "fsdp", "tensor")
    assert spec("stacks/gmu+dense/gmu/w_out", 3) == P(None, "tensor", "fsdp")
    for leaf, ndim in (("x_proj", 3), ("dt_proj", 3), ("A_log", 3), ("dt_bias", 2)):
        assert spec(f"stacks/ssm+dense/ssm/{leaf}", ndim) == P(*[None] * ndim)
    for leaf in ("lambda_q1", "sub_norm"):
        assert spec(f"stacks/xdiffattention+dense/attn/{leaf}", 2) == P(None, None)


@pytest.mark.parametrize("n_layers", [8, 16])
def test_the_engines_logprobs_are_the_plain_references(n_layers):
    cfg, eng = engine(2, n_layers)
    rng = np.random.default_rng(3)
    lens = [40, 23, 31]
    ids = rng.integers(0, 64, sum(lens)).astype(np.int32)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=lens,
        data={"packed_input_ids": ids})
    got = np.asarray(eng.forward(sample, MicroBatchSpec()).data["logprobs"], np.float32)
    hf = dict(HF, num_hidden_layers=n_layers)
    o = 0
    for l in lens:
        want = ref.next_token_logprobs(eng.params, hf, ids[o:o + l], pad_to=256)
        np.testing.assert_allclose(got[o:o + l - 1], want, atol=5e-5)
        o += l


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_updates_every_weight_and_counts_the_scan(depth):
    cfg, eng = engine(depth)
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    batch = make_batch(n=9, seed=5)
    tracing.start()
    try:
        stats = eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS),
                                packed_loss, loss_weight, loss_name="t")
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), after, before)
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))  # no buffer in this stack
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0

    c = got["counters"]
    mbs, _, _ = batch.split(MicroBatchSpec(n_mbs=N_MBS))
    want = np.sum([chunk_counts(eng._build_rows(mb)[1]["segment_ids"], cfg.ssm.chunk_size)
                   for mb in mbs], axis=0) * cfg.n_ssm_layers
    assert cfg.n_ssm_layers == 3 and cfg.ssm.chunk_size == 16
    assert [c[f"train.{k}"] for k in ("ssm_chunks", "ssm_chunks_live",
                                      "ssm_chunks_mixed", "ssm_resets")] == list(want)
    # positions the scan walks: rows of a multiple of the chunk, three layers
    assert c["train.sscan_cells"] == c["train.ssm_chunks"] * 16 == 3 * c["train.cells"]
    assert c["train.ssm_resets"] == 9 * 3
    # four attention layers (two window, one full, one cross): the
    # reference runs every cell of a row whatever the mask
    assert c["train.attn_cells"] == c["train.cells"]
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0
    seg = eng._build_rows(mbs[0])[1]["segment_ids"]
    r, t = seg.shape
    assert eng.counts.of({"segment_ids": seg}, 0)[0]["train.attn_active_cells"] == 4 * r * t * t
    assert "train.moe_pairs" not in c
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    for d in dispatch:
        assert d["window"] == 8
        assert d["kinds"] == ("ssm+dense,dense.diff.w8.nope,ssm+dense,dense.diff.w8.nope,"
                              "ssm+dense^,dense.diff.full.nope^,gmu+dense<4,"
                              "dense.diff.full.nope<5")


def test_the_kinds_label_folds_the_cross_decoders_units_apart():
    assert kinds_label(_cfg(num_hidden_layers=12)).endswith(
        "ssm+dense^,dense.diff.full.nope^,gmu+dense<6,dense.diff.full.nope<7,"
        "gmu+dense<6,dense.diff.full.nope<7")


def test_a_mesh_that_splits_rows_runs_the_plain_scan():
    """A `pallas_call` is opaque to GSPMD: under a mesh of more than one
    device the scan is the plain form, whatever the backend."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.ops.selective_scan import resolve_scan_impl
    from areal_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    assert resolve_scan_impl("auto", 5120, 16, 128, mesh) == "plain"
    cfg = _cfg()
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2), mesh=mesh,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=0, attn_impl="reference")
    stats = eng.train_batch(make_batch(n=8, seed=5), MicroBatchSpec(n_mbs=2),
                            packed_loss, loss_weight, loss_name="t")
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0
