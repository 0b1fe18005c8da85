"""The pipelined accumulation path builds the model once a micro-batch
shape: a minibatch's first micro-batch and every later one run one
program (`accum_step`), which adds a micro-batch's gradient into the fp32
sums it is handed and takes them for zeros when `first`, an argument of
the run, says so (by a `lax.cond` for the leaves a scan stacks, by a
select for every other: `transformer.scan_stacked`). The two programs
beside it see no row (`accum_zeros`, `accum_stats`) and are built once an
engine whatever shapes arrive. The step itself is the one the two model
programs it replaced (`first`, `nxt`) made."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models.transformer import init_params, scan_stacked

from tests.engine.test_prefetch import (
    loss_weight, make_batch, mk_engine, packed_loss, small_cfg,
)
from tests.engine.test_train_spans import _long_batch

N_MBS = 3
NO_ROW = ("accum_zeros", "accum_stats")
# the toy stack as one scan (its layers' leaves under the branch, the
# embedding's and the norm's under the select) and layer by layer (every
# leaf under the select)
STACKS = pytest.mark.parametrize("scan_min_repeats", [2, 99],
                                 ids=["scanned", "one_by_one"])


def _cfg(scan_min_repeats):
    cfg = dataclasses.replace(small_cfg(), scan_min_repeats=scan_min_repeats)
    stacked = jax.tree_util.tree_leaves(scan_stacked(
        cfg, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))))
    assert any(stacked) == (scan_min_repeats == 2) and not all(stacked)
    return cfg


def _engine(cfg, params):
    return JaxTrainEngine(
        cfg, jax.tree_util.tree_map(jnp.copy, params),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=32, prefetch_depth=2)


def _records(eng, batches):
    """The build records of `batches` through `eng`, one list a batch."""
    out = []
    for batch in batches:
        n = len(tracing.builds())
        eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS), packed_loss,
                        loss_weight, loss_name="t")
        out.append(tracing.builds()[n:])
    return out


def _count(records, program, phase):
    """Build records of `program` in `phase`; of traces, those that
    traced: jax also reports a `trace` of microseconds when it looks a
    trace up again (an argument that was one program's output is now
    another's), which builds nothing."""
    return sum(b["program"] == program and b["phase"] == phase
               and (phase != "trace" or b["end_ns"] - b["start_ns"] > 0.5e6)
               for b in records)


@pytest.mark.parametrize("multiple,n_shapes", [(128, 1), (32, 2)],
                         ids=["one_shape", "two_shapes"])
def test_the_model_is_built_once_a_shape_and_the_rest_once_an_engine(
        multiple, n_shapes):
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(21)), depth=2)
    eng.row_len_multiple = multiple
    batches = [make_batch(n=9, seed=21), _long_batch(21)]
    shapes = [{eng._build_rows(mb)[0].input_ids.shape
               for mb in b.split(MicroBatchSpec(n_mbs=N_MBS))[0]} for b in batches]
    # at a multiple of 128 every micro-batch of both batches is one row of
    # 128; at 32 a batch's micro-batches share a shape and the batches differ
    assert [len(s) for s in shapes] == [1, 1] and len(shapes[0] | shapes[1]) == n_shapes
    first, second = _records(eng, batches)
    for phase in ("trace", "lower"):
        # three micro-batches of one shape: the model once, not once for
        # the first and once for the rest
        assert _count(first, "accum_step", phase) == 1
        for p in NO_ROW:
            assert _count(first, p, phase) == 1
    # a second batch builds the model again only at a new shape ...
    for phase in ("trace", "lower"):
        assert _count(second, "accum_step", phase) == n_shapes - 1
    # ... and the programs that see no row never, whatever shape arrived:
    # the sums' buffers are the last minibatch's
    assert not [b for b in second if b["program"] in NO_ROW and b["phase"] != "trace"]
    assert not any(_count(second, p, "trace") for p in NO_ROW)
    [step_shape] = {(b["rows"], b["row_len"]) for b in first if b["program"] == "accum_step"}
    assert step_shape == next(iter(shapes[0]))
    assert all(b["rows"] is None and b["row_len"] is None
               for b in first + second if b["program"] in NO_ROW)
    # the engine's entries: the accumulate program, the pair beside it, the apply
    assert len(eng._jit_cache) == 3


@STACKS
@pytest.mark.parametrize("dtype,rtol", [(jnp.bfloat16, 0.0), (jnp.float32, 2e-5)],
                         ids=["bf16", "f32"])
def test_the_step_is_the_one_two_model_programs_made(dtype, rtol, scan_min_repeats):
    """`first` and `nxt` as the engine held them until PR 49, a program
    that starts the fp32 sums and one that adds into them, against the
    engine's one program over the same micro-batches: the same sums in
    the same order, so the same stats and the same parameters, whatever
    the sums' buffers held before (the second step's hold the first's).
    Bit for bit in bfloat16, what every launcher trains in; with float32
    parameters XLA's CPU backend may fuse a product and its add in
    another order in one program than in the other, so the last bit may
    differ, as it does between either and the fused step
    (test_prefetch.py)."""
    cfg = _cfg(scan_min_repeats)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(dtype), init_params(cfg, jax.random.PRNGKey(22)))
    eng, ref = _engine(cfg, params), _engine(cfg, params)
    batch = make_batch(n=9, seed=22)
    spec = MicroBatchSpec(n_mbs=N_MBS)
    mb_loss = ref._mb_loss_fn(packed_loss, None)

    def to_f32(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    @jax.jit
    def first(p, rows):
        (loss, aux), g = jax.value_and_grad(mb_loss, has_aux=True)(p, rows)
        return to_f32(g), loss.astype(jnp.float32), to_f32(aux)

    @jax.jit
    def nxt(p, carry, rows):
        (loss, aux), g = jax.value_and_grad(mb_loss, has_aux=True)(p, rows)
        return jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), carry, (g, loss, aux))

    for step in range(2):
        got = eng.train_batch(batch, spec, packed_loss, loss_weight,
                              version_steps=step, loss_name="t")
        # the sums outlive the minibatch: the next one's buffers
        assert eng._grad_sums["embedding"]["weight"].dtype == jnp.float32
        carry, denom, n_tok = None, 0.0, 0
        for mb in batch.split(spec)[0]:
            built, rows = ref._build_rows(mb)
            rows = {k: jnp.asarray(v) for k, v in rows.items()}
            carry = first(ref.params, rows) if carry is None else nxt(ref.params, carry, rows)
            denom += loss_weight(mb)
            n_tok += built.total_tokens
        ref.params, ref.opt_state, packed, _ = ref._apply_step_fn("t")(
            ref.params, ref.opt_state, carry, ref._inv_denom(denom, n_tok),
            jnp.asarray(ref._lr_schedule(step), jnp.float32))
        np.testing.assert_allclose(got["t/loss"], float(packed[0]) / denom, rtol=rtol)
        np.testing.assert_allclose(got["t/grad_norm"], float(packed[1]), rtol=rtol)
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(ref.params)):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=rtol, atol=rtol / 20)


@STACKS
def test_sums_that_hold_no_number_are_discarded_by_the_first_micro_batch(scan_min_repeats):
    """The sums' buffers a minibatch starts from are the last one's, and
    `first` discards them by a branch or a select, not a product: a step
    after one whose gradient overflowed is the step it would have been.
    The engine holds them only from one minibatch to the next: asked for
    anything else, it lets them go."""
    cfg = _cfg(scan_min_repeats)
    params = init_params(cfg, jax.random.PRNGKey(23))
    eng, clean = _engine(cfg, params), _engine(cfg, params)
    batch = make_batch(n=9, seed=23)
    args = (batch, MicroBatchSpec(n_mbs=N_MBS), packed_loss, loss_weight)
    eng._grad_sums = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.nan, jnp.float32), eng.params)
    got = eng.train_batch(*args, loss_name="t")
    want = clean.train_batch(*args, loss_name="t")
    assert got["t/grad_norm"] == want["t/grad_norm"] and np.isfinite(got["t/grad_norm"])
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert eng._grad_sums is not None
    eng.forward(batch, MicroBatchSpec(n_mbs=N_MBS))
    assert eng._grad_sums is None
    # an offloaded engine gives the buffers back with the rest
    eng.train_batch(*args, loss_name="t")
    eng.offload()
    assert eng._grad_sums is None


@pytest.mark.parametrize("config,branch", [
    ("qwen2.5-1.5b-d12", {"layers"}),
    ("keye-vl-2.0-d6-e16", {"layers"}),
    # a leading dense layer (and joyai's prediction module) run once
    ("trinity-mini-d5-e16", {"layers"}),
    ("joyai-llm-flash-d6-e16", {"layers"}),
    ("xing4.0-d5-e8", {"layers"}),
    # (M E) x 2 is scanned, but each kind's stack also holds layers that
    # run one by one: no leaf is a scan's alone
    ("nemotron-3-nano-d9-e8", set()),
    ("phi-4-mini-flash-d8", set()),
])
def test_which_leaves_of_the_benchmarks_stacks_a_scan_stacks(config, branch):
    """`scan_stacked` over the stacks the cells train: the leaves under
    the accumulate program's branch are those of the kinds whose every
    layer a scan runs, and a leaf's flag is its whole stack's."""
    import json
    import os

    from benchmark.model import transformer_config

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                        "configs", config + ".json")
    with open(path) as f:
        hf = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = transformer_config(hf, "bfloat16")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    flags = scan_stacked(cfg, params)
    assert jax.tree_util.tree_structure(flags) == jax.tree_util.tree_structure(params)
    got = set()
    for keys, flag in jax.tree_util.tree_leaves_with_path(flags):
        top = keys[0].key
        if flag:
            got.add(top if top != "stacks" else "stacks/" + keys[1].key)
        else:
            assert top not in branch
    assert got == branch
