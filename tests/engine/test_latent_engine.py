"""The trainer engine over a stack of DeepSeek-V3's shape (the
`joyai_llm_flash` family at toy widths): its logprobs are the plain
reference's, a micro-batch's loss and gradients with the prediction
module on are the reference's, the module moves nothing of the policy,
the host counts the module's run of the head and its layer by the
device's rule, and `train.dispatch` names the kinds."""

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
from areal_tpu.ops.loss import head_cells_run, response_positions, two_on
from areal_tpu.parallel.sharding import param_partition_spec
from benchmark.reference import joyai_llm_flash as ref

from tests.model.test_latent_stack import DENSE, HF, _cfg, _flat, _params

N_MBS = 3


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth=2, hf=HF, mtp_weight=None, **over):
    cfg = _cfg(hf, **over)
    params = _params(cfg, seed=2)
    if mtp_weight is not None:
        cfg = dataclasses.replace(
            cfg, mtp=dataclasses.replace(cfg.mtp, loss_weight=mtp_weight))
    eng = JaxTrainEngine(
        cfg, params,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference", hf_family="joyai_llm_flash")
    return cfg, eng


def ppo_like_batch(lens, prompts, seed=5):
    rng = np.random.default_rng(seed)
    total = sum(lens)
    prompt_mask = np.concatenate(
        [np.r_[np.ones(p, np.int32), np.zeros(l - p, np.int32)] for l, p in zip(lens, prompts)])
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=list(lens),
        data={"packed_input_ids": rng.integers(0, 64, total).astype(np.int32),
              "prompt_mask": prompt_mask})


def response_loss(lp, rows):
    """Minus the logprob of every response token."""
    mask = response_positions(rows)
    return -jnp.sum(lp * mask), {"n_valid_tokens": jnp.sum(mask)}


def n_response(mb):
    return float(sum(l - p for l, p in zip(
        (s[0] for s in mb.seqlens["packed_input_ids"]),
        np.add.reduceat(mb.data["prompt_mask"],
                        np.r_[0, np.cumsum([s[0] for s in mb.seqlens["packed_input_ids"]])[:-1]]))))


def test_the_new_leaves_shard_by_the_rules_that_are_there():
    from jax.sharding import PartitionSpec as P

    spec = lambda leaf, ndim: param_partition_spec(f"layers/attn/{leaf}", ndim)
    assert spec("wq_a", 3) == spec("wkv_a", 3) == P(None, "fsdp", None)
    assert spec("wq_b", 3) == spec("wkv_b", 3) == P(None, "fsdp", "tensor")
    assert spec("wo", 3) == P(None, "tensor", "fsdp")
    assert spec("q_a_norm", 2) == spec("kv_a_norm", 2) == P(None, None)
    assert param_partition_spec("mtp/block/attn/wq_b", 3) == P(None, "fsdp", "tensor")
    assert param_partition_spec("mtp/eh_proj/weight", 2) == P(None, None)


@pytest.mark.parametrize("hf", [HF, DENSE], ids=["whole", "latent_alone"])
def test_the_engines_logprobs_are_the_plain_references(hf):
    cfg, eng = engine(hf=hf)
    rng = np.random.default_rng(3)
    lens = [40, 23, 31]
    ids = rng.integers(0, 64, sum(lens)).astype(np.int32)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=lens,
        data={"packed_input_ids": ids})
    got = np.asarray(eng.forward(sample, MicroBatchSpec()).data["logprobs"], np.float32)
    o = 0
    for l in lens:
        want = ref.next_token_logprobs(eng.params, hf, ids[o:o + l], pad_to=256)
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
    ids = np.asarray(batch.data["packed_input_ids"])
    n = sum(l - p for l, p in zip(LENS, PROMPTS))
    total, o = 0.0, 0
    for l, p in zip(LENS, PROMPTS):
        one = ref.loss(params, HF, ids[o:o + l], p, weight) * (l - p)
        total, o = total + one, o + l
    return total / n


@pytest.mark.parametrize("weight", [0.1, 0.0], ids=["module_on", "module_off"])
def test_a_micro_batchs_loss_and_gradients_are_the_plain_references(weight):
    """The engine's own loss function over one packed row (`_mb_loss_fn`:
    forward, the masked head, the caller's loss, the module's pass and
    the second run of the head) against the reference's scalar loss, a
    sequence at a time: the value, and every leaf of the gradient."""
    cfg, eng = engine(mtp_weight=weight)
    batch, rows = _one_row(eng)
    n = sum(l - p for l, p in zip(LENS, PROMPTS))
    fn = eng._mb_loss_fn(response_loss, response_positions)
    (got, aux), g_got = jax.value_and_grad(fn, has_aux=True)(eng.params, rows)
    want, g_want = jax.value_and_grad(_reference_loss)(eng.params, batch, weight)
    np.testing.assert_allclose(got / n, want, rtol=2e-5)
    assert ("mtp_loss" in aux) == (weight > 0)
    g_got, g_want = _flat(g_got), _flat(g_want)
    assert g_got.keys() == g_want.keys()
    for name in g_want:
        scale = float(jnp.abs(g_want[name]).max())
        assert (scale > 0) == ("expert_bias" not in name and (weight > 0 or "mtp" not in name)), name
        np.testing.assert_allclose(g_got[name] / n, g_want[name], atol=2e-4 * scale + 1e-7,
                                   err_msg=name)


def test_the_module_moves_nothing_of_the_policy():
    """The gradient of every parameter outside the module is the same
    with the module's loss weighted 0.1 and with 0 (which skips its
    pass): its inputs and the head are constants in its branch."""
    grads = {}
    for weight in (0.1, 0.0):
        cfg, eng = engine(mtp_weight=weight)
        _, rows = _one_row(eng)
        fn = eng._mb_loss_fn(response_loss, response_positions)
        grads[weight] = jax.grad(lambda p: fn(p, rows)[0])(eng.params)
    on, off = _flat(grads[0.1]), _flat(grads[0.0])
    for name in on:
        if name.startswith("['mtp']"):
            assert float(jnp.abs(off[name]).max()) == 0
            assert float(jnp.abs(on[name]).max()) > 0 or "expert_bias" in name
        else:
            np.testing.assert_allclose(on[name], off[name], rtol=0, atol=1e-6 * max(
                1.0, float(jnp.abs(off[name]).max())), err_msg=name)


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_trains_the_module_and_counts_what_it_ran(depth):
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
    assert all((v > 0) == ("expert_bias" not in k) for k, v in moved.items()), moved
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0
    assert np.isfinite(stats["t/mtp_loss"]) and stats["t/mtp_loss"] > 1.0  # about log(64)
    assert 0 <= stats["t/mtp_accept"] <= 1

    c = got["counters"]
    mbs, _, _ = batch.split(MicroBatchSpec(n_mbs=N_MBS))
    rows = [eng._build_rows(mb)[1] for mb in mbs]
    want = np.sum([head_cells_run(r["segment_ids"], two_on(response_positions(r)), 64, shift=2)
                   for r in rows], axis=0)
    # a position reads the token two on where that is a response token of its own sequence
    assert want[0] == sum(max(l - 2 - max(p - 2, 0), 0) for l, p in zip(lens, prompts))
    assert [c["train.mtp_targets"], c["train.mtp_head_cells"]] == list(want)
    assert c["train.scored_cells"] == sum(l - p for l, p in zip(lens, prompts))
    assert 0 < c["train.mtp_head_cells"] <= c["train.head_cells"] <= c["train.cells"]
    # three layers of the stack and the module's: the reference runs every cell
    seg = rows[0]["segment_ids"]
    r, t = seg.shape
    assert eng.counts.of({"segment_ids": seg}, 0)[0]["train.attn_active_cells"] == 4 * r * t * t
    # two expert layers of the stack and the module's
    assert c["train.moe_pairs"] == 4 * sum(lens) * 3
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    for d in dispatch:
        assert d["window"] is None
        assert d["kinds"] == "dense.latent.full.rope,moe.latent.full.rope x2+mtp"


def test_a_module_weighted_zero_is_skipped_and_counted_nowhere():
    cfg, eng = engine(0, mtp_weight=0.0)
    batch = ppo_like_batch([30, 44, 25], [10, 20, 24])
    tracing.start()
    try:
        stats = dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=1), response_loss,
                                     n_response, loss_name="t", scored_fn=response_positions))
    finally:
        c = tracing.stop()["counters"]
    assert "t/mtp_loss" not in stats and "train.mtp_targets" not in c
    assert c["train.moe_pairs"] == 4 * 99 * 2
    seg = eng._build_rows(batch)[1]["segment_ids"]
    assert (eng.counts.of({"segment_ids": seg}, 0)[0]["train.attn_active_cells"]
            == 3 * seg.shape[0] * seg.shape[1] ** 2)
    assert kinds_label(_cfg(DENSE)) == "dense.latent.full.rope x2"


def test_the_ppo_interface_reports_the_modules_loss_and_acceptance():
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
    assert np.isfinite(stats["ppo_actor/mtp_loss"]) and 0 <= stats["ppo_actor/mtp_accept"] <= 1


@pytest.mark.parametrize("stack", ["leading_dense", "module", "afmoe_leading_dense"])
def test_a_layer_that_runs_once_walks_its_live_bands_as_the_scanned_ones_do(stack, monkeypatch):
    """One row alone, 40 tokens in 128 cells at bands of 16, as the engine
    packs and runs it: a leading dense layer outside the scan (the latent
    stack's with its module off, the stack of blocks') and the prediction
    module's block run their two stretches over the three live bands as
    the scanned layers do; the loss and every gradient are the whole
    row's program's, and the host counts what `live_bands` runs."""
    from areal_tpu.models.transformer import looping_layers
    from areal_tpu.ops import band_loop

    from tests.model.test_layer_kinds import small_bands

    ran = small_bands(monkeypatch)
    if stack == "afmoe_leading_dense":
        from tests.engine.test_layer_kinds_engine import engine as afmoe_engine

        cfg, eng = afmoe_engine(0)
    else:
        cfg, eng = engine(0, mtp_weight=0.1 if stack == "module" else 0.0)
    eng.row_len_multiple = eng.counts.row_len_multiple = 128
    _, rows = eng._build_rows(ppo_like_batch([24, 16], [10, 5]))
    assert rows["input_ids"].shape == (1, 128) and eng._dead_bands(128)
    seg = np.asarray(rows["segment_ids"])
    live = int(band_loop.live_bands(jnp.asarray(seg)))
    # every layer a step runs is of a kind that loops, and all of them do
    layers = cfg.n_layers + (stack == "module")
    assert [s.repeats for s in cfg.segments()][0] == 1  # the leading layer: no scan
    assert looping_layers(cfg, 1, 128, mtp=stack == "module") == layers
    # every layer walks the live bands, none runs the row whole
    assert live == 3 and eng.counts.of({"segment_ids": seg}, 0)[0]["train.band_cells"] == 16 * live
    rows = {k: jnp.asarray(v) for k, v in rows.items()}
    step = lambda: jax.jit(jax.value_and_grad(
        eng._mb_loss_fn(response_loss, response_positions), has_aux=True))(eng.params, rows)
    (got, _), g_got = step()
    # the leading layer, the scan's one body (and the module's block), two stretches each
    assert set(ran) == {"_before_mixer", "_after_mixer"} and len(ran) >= 2 * (2 + (stack == "module"))
    del ran[:]
    monkeypatch.setattr(eng, "_dead_bands", lambda row_len: False)  # the whole row's program
    (want, _), g_want = step()
    assert not ran
    np.testing.assert_allclose(got, want, rtol=2e-5)
    g_got, g_want = _flat(g_got), _flat(g_want)
    assert g_got.keys() == g_want.keys()
    for name in g_want:
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], atol=2e-4 * scale + 1e-6,
                                   err_msg=name)
