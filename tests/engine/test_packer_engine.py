"""The engine's one packing rule (`datapack.ladder_shape`, through
`_build_rows`) leaves a train step what it was: a micro-batch packed as
one ladder row trains as the same micro-batch in rows as long as its
longest sequence does, for each kind of stack; the fused step takes
micro-batches of unequal row length unpadded; the telemetry says what
was shipped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import datapack, stats_tracker, tracing
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine.jax_engine import JaxTrainEngine, trainable
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models.transformer import init_params
from areal_tpu.parallel.mesh import make_mesh

from tests.engine.test_jax_engine import dp_scaled_sft_loss
from tests.engine.test_prefetch import loss_weight, make_batch, packed_loss, small_cfg
from tests.model.test_hybrid_stack import _cfg as hybrid_cfg
from tests.model.test_layer_kinds import _cfg as afmoe_cfg

STACKS = {"dense": small_cfg, "afmoe": afmoe_cfg, "hybrid": hybrid_cfg}
LONGEST = 29  # make_batch draws lengths of 5-29: a cap of 32 is a row a sequence or two


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def _engine(cfg, params, depth=2, **kw):
    return JaxTrainEngine(
        cfg, jax.tree_util.tree_map(jnp.copy, params),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference", **kw)


def _same(a, b, what):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5 * max(np.abs(b).max(), 1e-2),
                               err_msg=str(what))


def _grads(eng, mb):
    """Every gradient leaf of one micro-batch, as the engine packs it."""
    _, rows = eng._build_rows(mb)
    loss = eng._mb_loss_fn(packed_loss, None)
    (total, _), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        eng.params, eng._device_rows(rows))
    return rows["input_ids"].shape, float(total), trainable(g)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_one_ladder_row_trains_as_rows_as_long_as_the_longest_sequence(stack):
    """Loss, `t/*` statistics, every gradient leaf and, through two
    optimizer steps, every parameter: one row of 160 holding nine
    sequences (sequence-start resets of a state-space layer in the
    middle of its chunks; a window and a full-attention layer; the held
    experts' passes over a buffer sized from the row) against the rows
    of 32 the operator's cap makes of the same micro-batch."""
    cfg = STACKS[stack]()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(2))
    one, rows = _engine(cfg, params), _engine(cfg, params, max_row_len=LONGEST)
    batch = make_batch(n=9, seed=5)
    (shape_one, loss_one, g_one), (shape_rows, loss_rows, g_rows) = (
        _grads(one, batch), _grads(rows, batch))
    assert shape_one == (1, datapack.ladder_rung(batch.total_seqlen(), 32))
    assert shape_rows[0] > 4 and shape_rows[1] == 32
    _same(loss_one, loss_rows, "loss")
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(leaves(g_one), leaves(g_rows)):
        _same(a, b, jax.tree_util.keystr(path))
    for step in range(2):
        sa, sb = (e.train_batch(batch, MicroBatchSpec(n_mbs=1), packed_loss, loss_weight,
                                version_steps=step, loss_name="t") for e in (one, rows))
        assert set(sa) == set(sb)
        for k in sa:
            if not k.startswith("t/moe_"):  # buffer rows and overflow follow the layout
                _same(sa[k], sb[k], (step, k))
    for (path, a), (_, b) in zip(leaves(jax.device_get(one.params)),
                                 leaves(jax.device_get(rows.params))):
        _same(a, b, jax.tree_util.keystr(path))


def _unequal_batch(seqlens=(30, 12, 30, 28, 30, 27, 30, 26, 30, 25), seed=3):
    """Sequences that split, at 160 tokens a micro-batch, into
    micro-batches of unequal fill (the five of 30, then the rest): on
    one device a row of 160 and one of 128."""
    rng = np.random.RandomState(seed)
    total = sum(seqlens)
    return SequenceSample.from_default(
        ids=[f"u{i}" for i in range(len(seqlens))], seqlens=list(seqlens),
        data={"packed_input_ids": rng.randint(0, 64, size=total),
              "loss_mask": np.ones(total, np.float32)})


BUDGET = MicroBatchSpec(max_tokens_per_mb=160)


def test_the_fused_step_takes_micro_batches_of_unequal_row_length_unpadded():
    cfg = small_cfg()
    params = init_params(cfg, jax.random.PRNGKey(4))
    batch = _unequal_batch()
    fused, piped = _engine(cfg, params, depth=0), _engine(cfg, params, depth=2)
    shapes = [fused._build_rows(mb)[0].input_ids.shape for mb in batch.split(BUDGET)[0]]
    assert shapes == [(1, 160), (1, 128)]
    counters = {}
    for name, eng in (("fused", fused), ("piped", piped)):
        tracing.start()
        try:
            stats = [eng.train_batch(batch, BUDGET, packed_loss, loss_weight,
                                     version_steps=s, loss_name="t") for s in range(2)]
        finally:
            got = tracing.stop()
        counters[name] = (stats, got["counters"],
                          [s["attrs"]["path"] for s in got["spans"]
                           if s["name"] == "train.batch"])
    (sf, cf, pf), (sp, cp, pp) = counters["fused"], counters["piped"]
    assert pf == ["fused"] * 2 and pp == ["overlapped"] * 2
    # no micro-batch is padded to another's shape: both paths ship the same cells
    built = len(fused._jit_cache), len(piped._jit_cache)
    # what jax built for them (`jit.*`) is each path's own
    cf, cp = ({k: v for k, v in c.items() if not k.startswith("jit.")}
              for c in (cf, cp))
    assert cf == cp and cf["train.cells"] == 2 * sum(r * t for r, t in shapes)
    assert cf["train.one_row_batches"] == cf["train.micro_batches"] == 2 * len(shapes)
    assert built[0] == 1  # one program, a scan a shape
    for a, b in zip(sf, sp):
        for k in a:
            _same(a[k], b[k], k)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(fused.params)),
                    jax.tree_util.tree_leaves(jax.device_get(piped.params))):
        _same(a, b, "params")


def test_dp_normalisation_over_micro_batches_of_unequal_row_length():
    """`token_normalize_scope='dp'` on two row shards, several
    micro-batches of unequal shape: the loss is the mean over shards of
    (the shard's loss sum over every micro-batch / its token count)."""
    cfg = small_cfg()
    params = init_params(cfg, jax.random.PRNGKey(6))
    batch = _unequal_batch((30, 12, 30, 28, 30, 30, 30), seed=8)
    lp = np.asarray(_engine(cfg, params).forward(
        batch, MicroBatchSpec(n_mbs=1)).data["logprobs"])
    lens = batch.seqlens_of()
    nll = dict(zip(batch.ids, (-x.sum() for x in np.split(lp, np.cumsum(lens)[:-1]))))
    eng = _engine(cfg, params, mesh=make_mesh(MeshSpec.parse("d2"), jax.devices()[:2]))
    assert eng._n_row_multiple == 2
    mbs = batch.split(BUDGET)[0]
    shard_nll, shard_tokens = np.zeros(2), np.zeros(2)
    shapes = set()
    for mb in mbs:
        packed, _ = eng._build_rows(mb)
        shapes.add(packed.input_ids.shape)
        assert packed.n_rows == 2
        for span in packed.spans:
            shard_nll[span.row] += nll[mb.ids[span.seq_index]]
            shard_tokens[span.row] += span.length
    assert shapes == {(2, 96), (2, 32)}
    # every token carries weight 1 (`loss_mask`); a sequence's last has logprob 0
    want = float(np.mean(shard_nll / shard_tokens))
    got = eng.train_batch(batch, BUDGET, dp_scaled_sft_loss, loss_weight,
                          token_normalize_scope="dp", loss_name="sft")
    np.testing.assert_allclose(got["sft/loss"], want, rtol=1e-4)
    assert abs(want - float(sum(nll.values()) / sum(lens))) > 1e-6  # not the global mean


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_packing_efficiency_is_tokens_over_cells_of_the_same_batch(depth):
    stats_tracker.export()
    cfg = small_cfg()
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(9)), depth=depth)
    batch = _unequal_batch(seed=9)
    tracing.start()
    try:
        eng.train_batch(batch, BUDGET, packed_loss, loss_weight, loss_name="t")
    finally:
        c = tracing.stop()["counters"]
    assert c["train.tokens"] == batch.total_seqlen()
    assert eng.last_overlap["packing_efficiency"] == c["train.tokens"] / c["train.cells"]
    assert stats_tracker.export()["perf/packing_efficiency"] == pytest.approx(
        c["train.tokens"] / c["train.cells"])
    # and the model worker's estimate, where the engine recorded none, is
    # the same rule's: here each micro-batch is one row from the ladder
    assert c["train.cells"] == sum(
        datapack.ladder_rung(sum(mb.seqlens_of()), 32) for mb in batch.split(BUDGET)[0])


def test_a_sequence_longer_than_the_cap_still_raises_in_the_engine():
    cfg = small_cfg()
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(1)), max_row_len=32)
    with pytest.raises(ValueError, match="exceeds row_len"):
        eng._build_rows(_unequal_batch((40, 10)))
    assert eng._build_rows(_unequal_batch((32, 10)))[0].input_ids.shape == (2, 32)
