"""The trainer engine over Keye-VL-2.0's language model at toy widths
(the `KeyeVL2` family): its logprobs are the plain reference's, a
micro-batch's loss and gradients with the indexers' KL on are the
reference's, the KL moves nothing of the policy and nothing else moves
the indexers, a step divides each by its own count, the host counts what
the indexers score and keep by the device's rule, the stats and
`train.dispatch` say what is new."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models import moe as moe_lib
from areal_tpu.ops.loss import response_positions
from benchmark.reference import keye_vl2 as ref

from tests.model.test_indexed_stack import HF, TOPK, _cfg, _params


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth=2, weight=None, clip=1.0, decay=0.05, **over):
    cfg = _cfg(**over)
    params = _params(cfg, seed=2)
    if weight is not None:
        cfg = dataclasses.replace(
            cfg, indexer=dataclasses.replace(cfg.indexer, loss_weight=weight))
    eng = JaxTrainEngine(
        cfg, params,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                         gradient_clipping=clip, weight_decay=decay),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference", hf_family="KeyeVL2")
    return cfg, eng


def ppo_like_batch(lens, prompts, seed=5):
    rng = np.random.default_rng(seed)
    prompt_mask = np.concatenate(
        [np.r_[np.ones(p, np.int32), np.zeros(l - p, np.int32)] for l, p in zip(lens, prompts)])
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=list(lens),
        data={"packed_input_ids": rng.integers(0, 64, sum(lens)).astype(np.int32),
              "prompt_mask": prompt_mask})


def response_loss(lp, rows):
    """Minus the logprob of every response token."""
    mask = response_positions(rows)
    return -jnp.sum(lp * mask), {"n_valid_tokens": jnp.sum(mask)}


def n_response(mb):
    lens = [s[0] for s in mb.seqlens["packed_input_ids"]]
    return float(sum(lens) - np.sum(mb.data["prompt_mask"]))


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


LENS, PROMPTS = [256, 256], [100, 37]


def _one_row(eng):
    """Both sequences in one packed row of 512, as the engine packs them."""
    batch = ppo_like_batch(LENS, PROMPTS)
    _, rows = eng._build_rows(batch)
    assert rows["input_ids"].shape == (1, 512)
    return batch, {k: jnp.asarray(v) for k, v in rows.items()}


def _reference_loss(params, batch, weight):
    """The reference's scalar, a sequence at a time, joined as the step
    joins them: the caller's loss over the response tokens of both, the
    KL over every token of both."""
    ids = np.asarray(batch.data["packed_input_ids"])
    n = sum(l - p for l, p in zip(LENS, PROMPTS))
    ppo, kl, o = 0.0, 0.0, 0
    for l, p in zip(LENS, PROMPTS):
        caller = ref.loss(params, HF, ids[o:o + l], p, 0.0)
        ppo = ppo + caller * (l - p)
        kl = kl + (ref.loss(params, HF, ids[o:o + l], p, 1.0) - caller) * l
        o += l
    return ppo / n + weight * kl / sum(LENS)


def _is_indexer(path):
    return any(getattr(k, "key", None) == "indexer" for k in path)


@pytest.mark.parametrize("weight", [1.0, 0.0], ids=["kl_on", "kl_off"])
def test_a_micro_batchs_loss_and_gradients_are_the_plain_references(weight):
    """The engine's own loss function over one packed row (`_mb_loss_fn`)
    and the division a step makes (`_optimizer_apply`'s: the caller's
    loss by its count of scored tokens, the indexers' KL by the real
    tokens) against the reference's scalar loss: the value and every leaf
    of the gradient; with the weight at 0 no gradient reaches an
    indexer."""
    cfg, eng = engine(weight=weight)
    batch, rows = _one_row(eng)
    n, n_tok = sum(l - p for l, p in zip(LENS, PROMPTS)), sum(LENS)
    fn = eng._mb_loss_fn(response_loss, response_positions)
    (got, aux), g_got = jax.value_and_grad(fn, has_aux=True)(eng.params, rows)
    want, g_want = jax.value_and_grad(_reference_loss)(eng.params, batch, weight)
    kl_sum = float(aux["num:indexer_kl"])
    np.testing.assert_allclose((float(got) - weight * kl_sum) / n + weight * kl_sum / n_tok,
                               want, rtol=2e-5)
    assert float(aux["den:indexer_kl"]) == n_tok
    assert (kl_sum > 0) == (weight > 0)
    assert float(aux["den:indexer_selected"]) == 2 * 2 * 256 * 257 / 2
    inv = eng._inv_denom(float(n), n_tok)
    assert inv.shape == ((2,) if weight else ())
    got_leaves = jax.tree_util.tree_flatten_with_path(g_got)[0]
    for (path, a), b in zip(got_leaves, jax.tree_util.tree_leaves(g_want)):
        a = a * (1.0 / n_tok if _is_indexer(path) else 1.0 / n)
        np.testing.assert_allclose(a, b, atol=3e-5 * max(1.0, float(jnp.abs(b).max())),
                                   err_msg=jax.tree_util.keystr(path))
        if _is_indexer(path):
            assert (float(jnp.abs(a).max()) > 0) == (weight > 0)


@pytest.mark.parametrize("clip", [0.0, 1.0], ids=["no_clipping", "clipping"])
def test_the_kl_moves_no_policy_parameter_and_nothing_else_moves_an_indexer(clip):
    """One optimizer step with the weight at 1 and at 0 from the same
    state: every parameter outside the indexers lands on the same value
    (the mask is a constant, the indexer reads its input and the
    attention probabilities under stop_gradient), and at 0 the indexers
    stay where they were (no weight decay here: AdamW's is all that
    touches a parameter without a gradient). Under global-norm clipping
    the two sets share the one norm, which Adam's update forgets but for
    its epsilon."""
    batch = ppo_like_batch([120, 70, 90, 60], [30, 20, 40, 10])
    after = {}
    for weight in (1.0, 0.0):
        cfg, eng = engine(weight=weight, clip=clip, decay=0.0)
        before = jax.tree_util.tree_map(np.asarray, eng.params)
        stats = eng.train_batch(batch, MicroBatchSpec(n_mbs=2), response_loss, n_response,
                                scored_fn=response_positions)
        after[weight] = (jax.tree_util.tree_map(np.asarray, eng.params), stats)
    (on, s_on), (off, s_off) = after[1.0], after[0.0]
    for (path, a), b, c in zip(jax.tree_util.tree_flatten_with_path(on)[0],
                               jax.tree_util.tree_leaves(off),
                               jax.tree_util.tree_leaves(before)):
        if _is_indexer(path):
            np.testing.assert_array_equal(b, c)  # weight 0: untouched
            assert np.abs(a - c).max() > 0  # weight 1: moved
        else:
            np.testing.assert_allclose(a, b, atol=5e-6 if clip else 1e-7)
    assert s_on["loss/indexer_kl"] > 0 and "loss/indexer_kl" in s_off
    np.testing.assert_allclose(s_on["loss/loss"], s_off["loss/loss"] + s_on["loss/indexer_kl"],
                               rtol=1e-5)
    assert 0 < s_on["loss/indexer_selected"] < 1
    assert s_on["loss/grad_norm"] > s_off["loss/grad_norm"]


def test_the_kl_falls_step_by_step_where_the_policy_stands_still():
    """The indexer trains by its own KL: with a caller's loss that has no
    gradient (so the policy stands still and the attention probabilities
    with it), a few steps over one batch bring the KL down every step."""
    batch = ppo_like_batch([120, 70, 90, 60], [30, 20, 40, 10])
    cfg, eng = engine(decay=0.0)
    still = lambda lp, rows: (0.0 * jnp.sum(lp), {})
    kls = [eng.train_batch(batch, MicroBatchSpec(n_mbs=2), still, n_response,
                           scored_fn=response_positions)["loss/indexer_kl"]
           for _ in range(5)]
    assert all(b < a for a, b in zip(kls, kls[1:])), kls
    assert kls[-1] < 0.985 * kls[0]


@pytest.mark.parametrize("depth", [2, 0], ids=["overlapped", "fused"])
def test_both_step_paths_take_the_kls_mean_over_the_minibatchs_tokens(depth):
    """Micro-batches of unequal size: the KL a step reports and descends
    is the sum over all of them over all their real tokens, on the
    pipelined path and the fused one alike."""
    lens, prompts = [200, 60, 90, 50], [30, 20, 40, 10]
    batch = ppo_like_batch(lens, prompts)
    cfg, eng = engine(depth=depth)
    before = eng.params
    ids = np.asarray(batch.data["packed_input_ids"])
    want, o = 0.0, 0
    for l in lens:
        want += ref.indexer_kl(before, HF, ids[o:o + l], pad_to=256).mean(axis=0).sum()
        o += l
    stats = eng.train_batch(batch, MicroBatchSpec(n_mbs=2), response_loss, n_response,
                            scored_fn=response_positions)
    np.testing.assert_allclose(stats["loss/indexer_kl"], want / sum(lens), rtol=5e-5)


def _brute_counts(lens, top_k):
    cells = sum(l * (l + 1) // 2 for l in lens)
    kept = sum(min(t + 1, top_k) for l in lens for t in range(l))
    choosing = sum(t + 1 > top_k for l in lens for t in range(l))
    return cells, kept, choosing


def test_the_host_counts_what_the_indexers_score_and_keep(monkeypatch, tmp_path):
    """`train.index_cells`, `train.index_selected`,
    `train.index_queries_choosing` against a brute-force count over the
    sequences, summed over both layers; `train.dispatch` names the kinds;
    the device's own count of chosen cells is the host's or, with ties,
    above it."""
    monkeypatch.setenv("AREAL_RL_TRACE", "1")
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", str(tmp_path))
    tracing.reconfigure()
    lens, prompts = [120, 7, 90, 60, 12], [30, 2, 40, 10, 5]
    cfg, eng = engine()
    assert kinds_label(cfg) == "moe.indexed.full.rope x2"
    tracing.start()
    stats = eng.train_batch(ppo_like_batch(lens, prompts), MicroBatchSpec(n_mbs=2),
                            response_loss, n_response, scored_fn=response_positions)
    got = tracing.stop()
    cells, kept, choosing = _brute_counts(lens, TOPK)
    c = got["counters"]
    assert c["train.index_cells"] == 2 * cells
    assert c["train.index_selected"] == 2 * kept
    assert c["train.index_queries_choosing"] == 2 * choosing
    assert stats["loss/indexer_selected"] >= kept / cells
    assert stats["loss/indexer_selected"] < 1.2 * kept / cells
    spans = [s for s in got["spans"] if s["name"] == "train.dispatch"]
    assert spans and all(s["attrs"]["kinds"] == "moe.indexed.full.rope x2" for s in spans)
