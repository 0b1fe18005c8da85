"""The loss head over the positions the loss reads, through the engine:
`train_batch(scored_fn=...)` as `PPOActorInterface.train_step` and
`SFTInterface.train_step` call it gives the step the unmasked head
gives, on both step paths and with rows sharded over a mesh; the
counters `train.scored_cells` / `train.head_cells` say what the device
ran; no program is built that the unmasked step would not build."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import ModelName
from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.api.model_api import Model
from areal_tpu.base import tracing
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.interfaces.sft import SFTInterface, sft_row_loss
from areal_tpu.models.transformer import init_params
from areal_tpu.ops import loss as L
from areal_tpu.parallel.mesh import make_mesh

from tests.interfaces.test_ppo_interface import small_cfg
from tests.interfaces.test_ppo_spans import _sample

# 8 sequences of 24 (prompt 8), 2 minibatches of 4, at most 48 tokens a
# micro-batch: two micro-batches of 2 rows x 32 a minibatch.
MB_SPEC = MicroBatchSpec(max_tokens_per_mb=48)
PATHS = {"fused": 0, "overlapped": 2}  # path -> prefetch depth
CE_CHUNK = 16  # 4 chunks a micro-batch of 64 cells: some run, some do not


@pytest.fixture(autouse=True)
def _small_chunks_and_tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_CE_CHUNK", str(CE_CHUNK))
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()
    L._CE_CHUNK_SNAP = None


def _actor(depth, mesh=None, masked=True):
    cfg = small_cfg()
    eng = JaxTrainEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(1)), mesh=mesh,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=100, row_len_multiple=32, prefetch_depth=depth)
    if not masked:
        # the step as it was before the interfaces named their positions
        inner = eng.train_batch
        eng.train_batch = lambda *a, scored_fn=None, **k: inner(*a, **k)
    return Model(name=ModelName("actor"), module=eng, tokenizer=None)


def _step(kind, model, seed):
    if kind == "ppo":
        return PPOActorInterface(n_minibatches=2).train_step(
            model, _sample(seed=seed), MB_SPEC)
    return SFTInterface().train_step(model, _sample(seed=seed), MB_SPEC)


def _assert_same_steps(kind, a, b, rtol=2e-5):
    name = "ppo_actor" if kind == "ppo" else "sft"
    for seed in (0, 1):
        sa, sb = _step(kind, a, seed), _step(kind, b, seed)
        for k in ("loss", "grad_norm", "update_norm"):
            np.testing.assert_allclose(sa[f"{name}/{k}"], sb[f"{name}/{k}"],
                                       rtol=rtol, atol=1e-7, err_msg=k)
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a.module.params)),
                    jax.tree_util.tree_leaves(jax.device_get(b.module.params))):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=1e-6)


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("kind", ["ppo", "sft"])
def test_train_step_is_the_unmasked_steps(kind, path):
    masked, plain = _actor(PATHS[path]), _actor(PATHS[path], masked=False)
    _assert_same_steps(kind, masked, plain)
    # and the masked program really is the other one
    keys = {k for k in masked.module._jit_cache if k[0] in ("train", "accum")}
    assert keys and all(k[-1] is True for k in keys)
    assert all(k[-1] is False for k in plain.module._jit_cache
               if k[0] in ("train", "accum"))


@pytest.mark.parametrize("spec", ["d2", "f2"])
def test_train_step_with_rows_sharded_is_the_single_devices(spec):
    mesh = make_mesh(MeshSpec.parse(spec), jax.devices()[:2])
    sharded = _actor(2, mesh=mesh)
    assert sharded.module._n_row_multiple == 2
    _assert_same_steps("ppo", sharded, _actor(0, masked=False))


def _all_gathers(text):
    """Shapes of the float arrays a compiled program all-gathers."""
    return re.findall(r"= ((?:f32|bf16)\[[0-9,]*\])\S* all-gather\(", text)


def test_rows_sharded_gather_no_more_hidden_states_than_the_unmasked_step():
    """On a mesh that splits the rows the positions move to the front
    of their own shard's rows: the compiled step all-gathers hidden
    states (arrays whose last axis is the model's width) no more often
    than the unmasked step, whose scan over chunks already hands every
    device every chunk."""
    mesh = make_mesh(MeshSpec.parse("d2"), jax.devices()[:2])
    batch = _sample(seed=3)
    width = small_cfg().hidden_dim
    texts = {}
    for masked in (False, True):
        eng = _actor(2, mesh=mesh).module
        _, rows = eng._build_rows(batch)
        assert np.prod(rows["input_ids"].shape) // CE_CHUNK > 2
        rows_dev = {k: jax.device_put(np.asarray(v), eng._batch_sharding)
                    for k, v in rows.items()}
        step = eng._train_step_fn("sft", sft_row_loss, tuple(sorted(rows)), 1,
                                  L.response_positions if masked else None)
        texts[masked] = step.lower(
            eng.params, eng.opt_state, rows_dev, jnp.float32(1.0),
            jnp.float32(1e-3)).compile().as_text()

    def hidden(text):
        return [s for s in _all_gathers(text)
                if s.count(",") == 2 and s.endswith(f",{width}]")]

    assert hidden(texts[False]), "the unmasked step gathers its chunks"
    assert len(hidden(texts[True])) <= len(hidden(texts[False]))
    assert texts[True] != texts[False]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_counters_say_what_the_head_ran(path, monkeypatch):
    model = _actor(PATHS[path])
    eng = model.module
    seen = []
    inner = eng.counts.of
    monkeypatch.setattr(
        eng.counts, "of", lambda rows, tok, fn: seen.append(rows) or inner(rows, tok, fn))
    itf = PPOActorInterface(n_minibatches=2)
    itf.train_step(model, _sample(seed=0), MB_SPEC)  # warm
    del seen[:]
    tracing.start()
    try:
        itf.train_step(model, _sample(seed=1), MB_SPEC)
    finally:
        c = tracing.stop()["counters"]
    # by hand: a sequence of 24 with a prompt of 8 is read at the 16
    # positions before a response token; a micro-batch is 2 such rows of
    # 32 cells, its 32 read positions fill 2 chunks of 16
    assert c["train.scored_cells"] == 8 * 16
    assert c["train.cells"] == 8 * 32
    assert c["train.head_cells"] == 4 * 2 * CE_CHUNK
    # and what the device laid out from the same rows
    ran = 0
    for rows in seen:
        for i in range(int(np.prod(rows["input_ids"].shape[:-2]))):
            r = {k: jnp.asarray(v.reshape((-1,) + v.shape[-2:])[i]) for k, v in rows.items()}
            _, valid = L._next_token_targets(r["input_ids"], r["segment_ids"])
            keep = valid & (L.response_positions(r) > 0)
            assert L.head_chunk_len(keep.size, 64) == CE_CHUNK
            ran += int(L._scored_layout(keep.reshape(1, -1), CE_CHUNK)[-1]) * CE_CHUNK
    assert ran == c["train.head_cells"]


def test_no_mask_counts_every_chunk_and_a_critic_counts_no_head():
    from tests.engine.test_prefetch import loss_weight, make_batch, mk_engine, packed_loss
    from tests.engine.test_prefetch import small_cfg as lm_cfg

    eng = mk_engine(init_params(lm_cfg(), jax.random.PRNGKey(2)), depth=0)
    tracing.start()
    try:
        eng.train_batch(make_batch(n=6, seed=2), MicroBatchSpec(n_mbs=2), packed_loss,
                        loss_weight, loss_name="t")
    finally:
        c = tracing.stop()["counters"]
    assert c["train.head_cells"] == c["train.cells"]
    assert 0 < c["train.scored_cells"] < c["train.tokens"]  # a sequence's last token
    critic = JaxTrainEngine(
        small_cfg(is_critic=True), init_params(small_cfg(is_critic=True), jax.random.PRNGKey(3)),
        optimizer_config=OptimizerConfig(lr=1e-3), total_train_steps=10, row_len_multiple=32)
    said, _ = critic.counts.of({"segment_ids": np.ones((2, 32), np.int32)}, 64)
    assert "train.attn_cells" in said and not any("head" in n or "scored" in n for n in said)


@pytest.mark.parametrize("cell", ["q15d12-train-ppo", "q15d12-train-short",
                                  "trinity-d5e16-train-ppo-long"])
def test_a_cells_traffic_builds_the_programs_the_unmasked_step_builds(cell, monkeypatch):
    """A pass over the cell's pool at the rehearsal's toy widths, with
    the mask and without: the engine's jit cache holds as many entries, the
    mask adds no compiled shape."""
    from benchmark import manifest, model, traffic
    from benchmark.runners.train import _sample as pool_sample
    from benchmark.runners.train import _scoring_mask

    monkeypatch.delenv("AREAL_CE_CHUNK")
    c = manifest.load_cell(cell)
    hf = manifest.hf_config(c["config_file"], True)
    cfg = model.transformer_config(hf, "float32")
    p = traffic.effective(c["traffic_file"], True)
    eng = manifest.section(c, "engine", True)
    pool = traffic.ppo_batches(p, 2**31 + 5, cfg.vocab_size)
    built = {}
    for masked in (True, False):
        engine = JaxTrainEngine(
            cfg, init_params(cfg, jax.random.PRNGKey(4)),
            optimizer_config=OptimizerConfig(**manifest.section(c, "optimizer", True)),
            total_train_steps=100, attn_impl=eng.get("attn_impl", "auto"),
            remat=eng.get("remat", "full"), row_len_multiple=int(eng["row_len_multiple"]),
            max_row_len=eng.get("max_row_len"),
            prefetch_depth=int(eng.get("prefetch_depth", 2)))
        if not masked:
            inner = engine.train_batch
            engine.train_batch = lambda *a, scored_fn=None, inner=inner, **k: inner(*a, **k)
        actor = Model(name=ModelName("actor"), module=engine, tokenizer=None)
        itf = PPOActorInterface(n_minibatches=int(p["ppo"]["n_minibatches"]))
        mb_spec = MicroBatchSpec(max_tokens_per_mb=int(p["ppo"]["max_tokens_per_mb"]))
        tracing.start()
        try:
            for b in pool:
                lp = (-np.random.RandomState(0).rand(b["n_tokens"]) * _scoring_mask(b))
                extra = dict(packed_logprobs=lp.astype(np.float32),
                             ref_logprobs=lp.astype(np.float32))
                stats = itf.train_step(actor, pool_sample(b, extra), mb_spec)
                assert np.isfinite(stats["ppo_actor/loss"])
        finally:
            got = tracing.stop()["counters"]
        built[masked] = len(engine._jit_cache)
        # (at toy widths a micro-batch is one chunk, and it runs)
        assert 0 < got["train.scored_cells"] < got["train.head_cells"] <= got["train.cells"]
        assert masked or got["train.head_cells"] == got["train.cells"]
    assert built[True] == built[False] > 0
