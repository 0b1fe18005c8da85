"""The trainer engine over a stack of layer kinds with a share of the
experts (the `afmoe` family at toy widths): buffers take no gradient, no
update and no Adam state; the new counters and the attributes of
`train.dispatch`; both input paths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import (
    BUFFER_LEAVES, JaxTrainEngine, trainable, with_buffers,
)
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models.transformer import init_params

from tests.engine.test_prefetch import loss_weight, make_batch, packed_loss
from tests.model.test_layer_kinds import HF, _cfg

N_MBS = 3


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth):
    cfg = _cfg()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(2))
    bias = params["layers"]["mlp"]["expert_bias"]
    params["layers"]["mlp"]["expert_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(3), bias.shape)
    eng = JaxTrainEngine(
        cfg, params,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth,
        attn_impl="reference")
    return cfg, eng


def test_trainable_takes_the_buffers_out_and_with_buffers_puts_them_back():
    tree = {"a": {"w": 1, "expert_bias": 2}, "b": 3}
    assert BUFFER_LEAVES == ("expert_bias",)
    assert trainable(tree) == {"a": {"w": 1}, "b": 3}
    assert with_buffers({"a": {"w": 10}, "b": 30}, tree) == {
        "a": {"w": 10, "expert_bias": 2}, "b": 30}
    plain = {"a": {"w": 1}, "b": 3}
    assert jax.tree_util.tree_structure(trainable(plain)) == jax.tree_util.tree_structure(plain)


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_updates_the_weights_and_leaves_the_buffer(depth):
    cfg, eng = engine(depth)
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    # Adam keeps moments for the weights only
    n_weights = len(jax.tree_util.tree_leaves(trainable(eng.params)))
    n_all = len(jax.tree_util.tree_leaves(eng.params))
    assert n_all == n_weights + 1
    moments = [l for l in jax.tree_util.tree_leaves(eng.opt_state) if l.ndim > 0]
    assert len(moments) == 2 * n_weights
    assert not any(l.shape == before["layers"]["mlp"]["expert_bias"].shape
                   for l in moments)

    tracing.start()
    try:
        stats = dict(eng.train_batch(make_batch(n=9, seed=5), MicroBatchSpec(n_mbs=N_MBS),
                                     packed_loss, loss_weight, loss_name="t"))
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    np.testing.assert_array_equal(after["layers"]["mlp"]["expert_bias"],
                                  before["layers"]["mlp"]["expert_bias"])
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()),
                                   trainable(after), trainable(before))
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0

    c = got["counters"]
    k, expert_layers = cfg.moe.top_k, cfg.n_moe_layers
    assert (k, expert_layers) == (4, 4)
    assert c["train.moe_pairs"] == k * c["train.tokens"] * expert_layers
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.moe_pairs_held"] == stats["t/moe_pairs_held"]
    assert c["train.moe_rows"] == stats["t/moe_rows"] >= c["train.moe_pairs_held"]
    # a layer's held pairs of a toy micro-batch fit one chunk, and each holds some
    assert c["train.moe_chunks"] == stats["t/moe_chunks"] == expert_layers * N_MBS
    assert stats["t/moe_drop_rate"] == 0.0
    # the einsum reference runs every cell of a row whatever the mask
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0
    # four window layers to one full layer: the split of the cells run
    assert c["train.attn_window_cells"] == 4 * c["train.attn_full_cells"] > 0
    assert c["train.attn_window_cells"] + c["train.attn_full_cells"] == c["train.attn_active_cells"]
    assert c["train.attn_cells"] == c["train.cells"]
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert len(dispatch) == (N_MBS if depth else 1)
    for d in dispatch:
        assert d["window"] == HF["sliding_window"]
        assert d["kinds"] == "dense.w8.rope,moe.w8.rope x2,moe.full.nope,moe.w8.rope"


def test_experts_held_across_chips_is_refused_by_name():
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.models.transformer import forward
    from areal_tpu.parallel.mesh import make_mesh

    cfg = _cfg()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    ids = jnp.zeros((2, 32), jnp.int32)
    with pytest.raises(NotImplementedError, match="exchange"):
        forward(params, cfg, ids, jnp.ones_like(ids), jnp.tile(jnp.arange(32), (2, 1)),
                attn_impl="reference", mesh=mesh)


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_bf16_weights_build_each_optimizer_program_once(depth):
    """Adam's moments are float32 before the first update as after it, so
    the programs that take the optimizer state are traced once: a second
    build would fall inside a benchmark's window, when the first
    micro-batch shape of the run comes round again."""
    from tests.engine.test_prefetch import small_cfg

    cfg = dataclasses.replace(small_cfg(), param_dtype="bfloat16")
    eng = JaxTrainEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=depth)
    dtypes = lambda: {str(l.dtype) for l in jax.tree_util.tree_leaves(eng.opt_state)
                      if l.ndim > 0}
    assert dtypes() == {"float32"}
    built = []  # every lowering of a program, as the benchmark counts them

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            built.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    for _ in range(3):
        eng.train_batch(make_batch(n=9, seed=5), MicroBatchSpec(n_mbs=N_MBS if depth else 1),
                        packed_loss, loss_weight, loss_name="t")
        assert dtypes() == {"float32"}
    name = "jit(apply)" if depth else "jit(step)"
    assert built.count(name) == 1, built
