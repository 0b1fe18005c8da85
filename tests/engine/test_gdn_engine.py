"""The trainer engine over a stack of Gated DeltaNet mixers and gated
attention (the `qwen3_next` family at toy widths): its logprobs are the
plain reference's, a train step moves every parameter of both mixers and
of the gated shared expert, counts the chunks the rule ran by the device's
rule (value heads: the same counts as the other rule's), `train.dispatch`
tells the two rules apart, and the family runs through
`PPOActorInterface`."""

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
from benchmark.reference import qwen3_next as ref

from tests.engine.test_latent_engine import n_response, ppo_like_batch, response_loss
from tests.model.test_gdn_stack import HF, _cfg, _params
from tests.model.test_hyper_stack import _flat

N_MBS = 3
KINDS = "moe.kda.head.k2.c64 x3,moe.full.rope"


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth=2, row_len_multiple=32):
    cfg = _cfg(HF)
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=row_len_multiple, prefetch_depth=depth,
        attn_impl="reference", hf_family="qwen3_next")
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
        stats = dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS), response_loss,
                                     n_response, loss_name="t", scored_fn=response_positions))
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    moved = _flat(jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), after, before))
    assert all(v > 0 for v in moved.values()), moved
    # the mixer's leaves: q, k, v, the gate, beta's and the decay's columns,
    # three convolutions, A_log, dt_bias, the head norm, the output
    assert sum("'kda'" in k for k in moved) == 13
    assert sum("'attn'" in k for k in moved) == 7  # wq, wk, wv, wo, wg, q_norm, k_norm
    assert sum("'w_s'" in k for k in moved) == 2  # the shared expert's gate, both stacks
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0

    c = got["counters"]
    # toy rows are one group of chunks: the rule runs every chunk of a row
    # (96 cells: two of 64) in each of the three delta-rule layers
    assert c["train.kda_cells"] == 3 * c["train.cells"] // 96 * 128 > 0
    assert c["train.kda_chunks"] * 64 == c["train.kda_cells"]
    # the CPU takes the plain form, forward and backward
    assert c["train.kda_fwd_kernel_cells"] == c["train.kda_bwd_kernel_cells"] == 0
    assert c["train.kda_taps_cells"] == 3 * c["train.cells"]  # the convolutions' cells
    assert c["train.kda_taps_kernel_cells"] == 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"]
    assert c["train.kda_resets"] == 3 * len(lens)
    assert c["train.attn_cells"] == c["train.cells"]  # the one attention layer's alone
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * cfg.moe.top_k
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.moe_rows"] >= c["train.moe_pairs_held"]
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    assert all(d["kinds"] == KINDS for d in dispatch)


def test_the_host_counts_this_rules_chunks_as_it_counts_the_others(monkeypatch):
    cfg, eng = engine(0, row_len_multiple=256)
    seg = np.zeros((1, 256), np.int32)
    seg[0, :40], seg[0, 40:70] = 1, 2
    names = ("train.kda_cells", "train.kda_chunks", "train.kda_chunks_live", "train.kda_resets")
    rule = lambda seg: tuple(eng.counts.of({"segment_ids": seg}, 0)[0][n] for n in names)
    assert rule(seg) == (3 * 256, 3 * 4, 3 * 2, 3 * 2)
    monkeypatch.setattr(kda, "GROUP_CELLS", 64)
    assert rule(seg) == (3 * 128, 3 * 2, 3 * 2, 3 * 2)
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
    assert moved["stacks"]["attention+moe"]["attn"]["wg"] > 0
