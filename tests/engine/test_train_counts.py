"""What a train step says of itself (`engine/train_counts.py`), a family
the engine tests build: the fused and the overlapped input path emit the
same `train.*` counters for the same batch, and their names are exactly
the table below, written down from the tree before the module existed
(PR 57). A counter renamed or dropped fails here, before a reader under
`benchmark/layer_metrics/` reads a zero on the chip."""

import dataclasses
import importlib

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine.train_counts import TrainCounts
from areal_tpu.models import moe as moe_lib
from areal_tpu.models.transformer import init_params
from areal_tpu.ops.loss import response_positions

from tests.engine.test_latent_engine import n_response, ppo_like_batch, response_loss

# micro-batches of 65, 64 and 64 tokens: one row of 96 and two of 64, so
# the fused path scans two stacks
LENS = [40, 25, 1, 44, 19, 1, 38, 24, 1]
PROMPTS = [10, 5, 1, 20, 4, 1, 5, 8, 1]
N_MBS = 3

BATCH = {"batches", "micro_batches", "one_row_batches", "tokens", "cells"}
BANDS = {"band_cells"}
ATTN = {"attn_cells", "attn_active_cells", "attn_window_cells", "attn_full_cells",
        "attn_causal_cells", "attn_grid_steps", "attn_live_steps", "attn_bwd_steps",
        "attn_cells_in_place"}
HEAD = {"scored_cells", "head_cells"}
MTP = {"mtp_targets", "mtp_head_cells"}
# `moe_pairs` by the host; the rest the step's own statistics, fetched
MOE = {"moe_pairs", "moe_pairs_held", "moe_rows", "moe_chunks"}
SSM = {"ssm_chunks", "ssm_chunks_live", "ssm_chunks_mixed", "ssm_resets"}
SSCAN = {"sscan_cells"}
KDA = {"kda_cells", "kda_fwd_kernel_cells", "kda_bwd_kernel_cells", "kda_chunks",
       "kda_chunks_live", "kda_resets", "kda_taps_cells", "kda_taps_kernel_cells"}
INDEX = {"index_cells", "index_selected", "index_queries_choosing"}
MHC = {"mhc_cells", "mhc_loop_cells"}
ANY = BATCH | BANDS | ATTN
ACTOR = ANY | HEAD


def _plain(depth, **over):
    from tests.engine.test_prefetch import small_cfg

    cfg = dataclasses.replace(small_cfg(), **over)
    return JaxTrainEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(5)),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth)


def _mellum(depth):
    from tests.model.test_mellum_stack import _cfg, _params

    cfg = _cfg()
    return JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference", hf_family="mellum")


def _of(test):
    """The `engine(depth)` of one of the families' engine tests."""
    return lambda depth: importlib.import_module(f"tests.engine.{test}").engine(depth)[1]


# family -> (its engine at a prefetch depth, the `train.*` names a step counts)
FAMILIES = {
    "plain": (_plain, ACTOR),
    "critic": (lambda depth: _plain(depth, is_critic=True), ANY),
    "afmoe": (_of("test_layer_kinds_engine"), ACTOR | MOE),
    "hybrid_ssm": (_of("test_hybrid_stack_engine"), ACTOR | MOE | SSM),
    "sambay": (_of("test_sambay_engine"), ACTOR | SSM | SSCAN),
    "latent": (_of("test_latent_engine"), ACTOR | MOE | MTP),
    "indexed": (_of("test_indexed_engine"), ACTOR | MOE | INDEX),
    "hyper": (_of("test_hyper_engine"), ACTOR | MOE | MHC),
    "kda": (_of("test_kda_engine"), ACTOR | MOE | KDA),
    "gdn": (_of("test_gdn_engine"), ACTOR | MOE | KDA),
    "mellum": (_mellum, ACTOR | MOE),
}


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def _traced_step(eng, scored_fn):
    """(the `train.*` counters, the dispatches' attributes) of one step."""
    batch = ppo_like_batch(LENS, PROMPTS)
    tracing.start()
    try:
        # read inside the session: the `sum:` stats are counted by the read
        dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS), response_loss, n_response,
                             loss_name="t", scored_fn=scored_fn))
    finally:
        got = tracing.stop()
    return ({k: v for k, v in got["counters"].items() if k.startswith("train.")},
            [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_both_paths_count_the_same_and_the_names_are_the_tables(family):
    build, names = FAMILIES[family]
    scored_fn = None if family == "critic" else response_positions
    fused, [one] = _traced_step(build(0), scored_fn)
    overlapped, each = _traced_step(build(2), scored_fn)
    assert set(fused) == {f"train.{n}" for n in names}
    # the step's own statistics are floats off the device; the host's are exact
    fetched = {"train.moe_pairs_held", "train.moe_rows", "train.moe_chunks"}
    for name in fused:
        if name in fetched:
            np.testing.assert_allclose(fused[name], overlapped[name], rtol=1e-6)
        else:
            assert fused[name] == overlapped[name] and isinstance(fused[name], int), name
    assert set(overlapped) == set(fused)
    assert fused["train.micro_batches"] == N_MBS and fused["train.tokens"] == sum(LENS)
    assert fused["train.cells"] == 96 + 2 * 64
    # the fused step's span says the shape of its largest micro-batch
    assert len(each) == N_MBS and (one["rows"], one["row_len"]) == (1, 96)
    assert {(d["row_len"], d["attn_row_len"]) for d in each} == {(96, 96), (64, 64)}
    assert one["attn_row_len"] == 96 and {d["width"] for d in each} == {one["width"]}


def test_the_counts_of_micro_batches_add_up_to_their_stacks():
    """Every counter is linear in a micro-batch: a stack [n, R, T] says the
    sum of what its micro-batches say, which is what lets the two paths
    add mappings in any grouping; the attributes are not sums."""
    counts = _of("test_kda_engine")(0).counts
    assert isinstance(counts, TrainCounts)
    rng = np.random.default_rng(0)
    seg = np.zeros((2, 1, 128), np.int32)
    seg[0, 0, :40], seg[0, 0, 40:70], seg[1, 0, :100] = 1, 2, 1
    rows = {"segment_ids": seg, "prompt_mask": (rng.random(seg.shape) < 0.3).astype(np.int32)}
    whole, attrs = counts.of(rows, 170, response_positions)
    parts = [counts.of({k: v[i] for k, v in rows.items()}, n, response_positions)
             for i, n in enumerate((70, 100))]
    assert whole == {k: parts[0][0][k] + parts[1][0][k] for k in whole}
    assert whole["train.moe_pairs"] > 0 < whole["train.scored_cells"]
    assert attrs == parts[0][1] == parts[1][1] == dict(attn_row_len=128, width=0, in_place=0)
