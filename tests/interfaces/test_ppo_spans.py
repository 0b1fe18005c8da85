"""A PPO step as the program's own tracing records it: one
`ppo.train_step` root a step, `ppo.prep` (its host parts as children),
`ppo.advantages`, one `ppo.minibatch` a minibatch and `ppo.stats` under
it, each minibatch's `train.batch` tree under that, the prefetcher's
`train.stage` under the step's trace id, every minibatch's stats read
under `ppo.stats` (no read between a step's first enqueue and its last),
and a `device.starved` span for every stretch between a blocking read of
the newest thing enqueued and the next enqueue: two a step. Actor and
critic."""

import threading

import jax
import numpy as np
import pytest

from areal_tpu.api.config import ModelName
from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import Model
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.interfaces.ppo import PPOActorInterface, PPOCriticInterface
from areal_tpu.models.transformer import init_params

from tests.interfaces.test_ppo_interface import small_cfg

N_MINIBATCHES, MBS_PER_MINIBATCH = 2, 2
# span kind -> (count a step, parent's name)
TREE = {
    "ppo.train_step": (1, None),
    "ppo.prep": (1, "ppo.train_step"),
    "ppo.prep.pack": (1, "ppo.prep"),
    "ppo.prep.h2d": (1, "ppo.prep"),
    "ppo.prep.dispatch": (1, "ppo.prep"),
    "ppo.prep.gather": (1, "ppo.prep"),
    "ppo.advantages": (1, "ppo.train_step"),
    "ppo.stats": (1, "ppo.train_step"),
    "ppo.minibatch": (N_MINIBATCHES, "ppo.train_step"),
    "train.batch": (N_MINIBATCHES, "ppo.minibatch"),
    "train.begin": (N_MINIBATCHES, "train.batch"),
    "train.stage": (N_MINIBATCHES * MBS_PER_MINIBATCH, "train.batch"),
    "train.pack": (N_MINIBATCHES * MBS_PER_MINIBATCH, "train.stage"),
    "train.h2d": (N_MINIBATCHES * MBS_PER_MINIBATCH, "train.stage"),
    "train.wait_input": (N_MINIBATCHES * (MBS_PER_MINIBATCH + 1), "train.batch"),
    "train.dispatch": (N_MINIBATCHES * MBS_PER_MINIBATCH, "train.batch"),
    "train.apply": (N_MINIBATCHES, "train.batch"),
    # read at the step's end, the minibatches all enqueued
    "train.fetch_stats": (N_MINIBATCHES, "ppo.stats"),
    # a session's first step: after the prep's read alone, ended by the
    # first minibatch's first dispatch (no minibatch's fetch comes before
    # the next one's enqueue)
    "device.starved": (1, "train.dispatch"),
}


def _sample(n=8, seed=0, values=False):
    """n sequences of 24 tokens (prompt 8), one sample each."""
    rng = np.random.RandomState(seed)
    lens = [24] * n
    total = sum(lens)
    pm = np.concatenate([np.r_[np.ones(8), np.zeros(l - 8)] for l in lens]).astype(np.int64)
    data = dict(
        packed_input_ids=rng.randint(1, 64, size=total),
        prompt_mask=pm,
        packed_logprobs=(-rng.rand(total) * (1 - pm)).astype(np.float32),
        rewards=rng.randn(n).astype(np.float32),
        seq_no_eos_mask=np.zeros(n, np.float32),
    )
    if values:
        data["values"] = rng.randn(total).astype(np.float32)
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n)], seqlens=lens, data=data,
        metadata={"version_start": [0] * n, "version_end": [0] * n})


def _model(critic):
    cfg = small_cfg(is_critic=True) if critic else small_cfg()
    eng = JaxTrainEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(1)),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=100, row_len_multiple=32)
    return Model(name=ModelName("critic" if critic else "actor"), module=eng,
                 tokenizer=None)


@pytest.fixture(scope="module", params=["actor", "critic"])
def step_spans(request):
    """Two steps of one interface: the first untraced (warm, and proof
    that off records nothing), the second between start() and stop()."""
    critic = request.param == "critic"
    tracing.reconfigure()
    model = _model(critic)
    itf = (PPOCriticInterface if critic else PPOActorInterface)(
        n_minibatches=N_MINIBATCHES)
    # 4 sequences of 24 a minibatch, at most 48 tokens a micro-batch: two
    mb_spec = MicroBatchSpec(max_tokens_per_mb=48)
    itf.train_step(model, _sample(values=critic), mb_spec)
    assert tracing.recorder() is None
    version = model.version
    tracing.start()
    try:
        itf.train_step(model, _sample(seed=1, values=critic), mb_spec)
    finally:
        got = tracing.stop()
    return got, version, threading.get_ident() & 0xFFFF


@pytest.mark.parametrize("kind", sorted(TREE))
def test_span_kind_has_the_right_count_and_parent(step_spans, kind):
    got, *_ = step_spans
    by_id = {s["span"]: s for s in got["spans"]}
    mine = [s for s in got["spans"] if s["name"] == kind]
    count, parent = TREE[kind]
    assert len(mine) == count
    for s in mine:
        assert (by_id[s["parent"]]["name"] if s["parent"] else None) == parent


def test_one_trace_a_step_with_the_stage_on_the_prefetchers_thread(step_spans):
    got, version, main_tid = step_spans
    spans = got["spans"]
    assert {s["name"] for s in spans} == set(TREE)
    [root] = [s for s in spans if s["name"] == "ppo.train_step"]
    assert {s["trace"] for s in spans} == {root["trace"]}
    for s in spans:
        on_worker = s["name"] in ("train.stage", "train.pack", "train.h2d")
        assert (s["tid"] != main_tid) == on_worker, s
        assert root["start_ns"] <= s["start_ns"] <= s["end_ns"] <= root["end_ns"]
    assert root["attrs"] == {"version": version, "tokens": 8 * 24, "sequences": 8}


def test_attributes_count_what_the_step_did(step_spans):
    got, *_ = step_spans
    spans = got["spans"]
    [prep] = [s for s in spans if s["name"] == "ppo.prep"]
    assert prep["attrs"]["rows"] * prep["attrs"]["row_len"] >= 8 * 24
    mbs = sorted((s["attrs"]["index"], s["attrs"]["tokens"])
                 for s in spans if s["name"] == "ppo.minibatch")
    assert mbs == [(0, 4 * 24), (1, 4 * 24)]
    for s in spans:
        if s["name"] == "train.batch":
            a = s["attrs"]
            assert a["path"] == "overlapped" and a["n_mbs"] == MBS_PER_MINIBATCH
            assert a["tokens"] == 4 * 24 <= a["cells"]
    c = got["counters"]
    assert c["train.batches"] == N_MINIBATCHES
    assert c["train.micro_batches"] == N_MINIBATCHES * MBS_PER_MINIBATCH
    assert c["train.tokens"] == 8 * 24 <= c["train.cells"]


@pytest.mark.parametrize("critic", [False, True], ids=["actor", "critic"])
def test_a_first_step_builds_its_prep_under_ppo_prep_by_name_and_shape(critic):
    tracing.reconfigure()
    model = _model(critic)
    itf = (PPOCriticInterface if critic else PPOActorInterface)(
        n_minibatches=N_MINIBATCHES)
    tracing.start()
    try:
        itf.train_step(model, _sample(values=critic), MicroBatchSpec(max_tokens_per_mb=48))
    finally:
        got = tracing.stop()
    [prep] = [s for s in got["spans"] if s["name"] == "ppo.prep"]
    [enqueue] = [s for s in got["spans"] if s["name"] == "ppo.prep.dispatch"]
    # the child that enqueues pays the build
    named = [s for s in got["spans"] if s["parent"] == enqueue["span"]
             and s["attrs"].get("program") == "ppo_prep"]
    assert [s["name"] for s in named][:2] == ["jit.trace", "jit.lower"]
    assert named[2]["name"] in ("jit.compile", "jit.cache_load") and len(named) == 3
    a = prep["attrs"]
    assert all((s["attrs"]["rows"], s["attrs"]["row_len"]) == (a["rows"], a["row_len"])
               for s in named)
    assert enqueue["attrs"]["built"] >= 1  # the prep, and whatever eager op was new beside it
    programs = {b["program"] for b in got["builds"]}
    assert {"ppo_prep", "accum_step", "accum_zeros", "accum_stats", "apply"} <= programs


@pytest.mark.parametrize("critic", [False, True], ids=["actor", "critic"])
def test_every_stretch_after_a_blocking_read_is_one_starved_span_of_the_step_that_fed(critic):
    """Three steps of one session: the first records a stretch after the
    prep's read; the read of its last minibatch's stats, the only one with
    nothing enqueued behind it, leaves a mark that waits for the next
    step's prep, so every later step records two, whatever the number of
    minibatches (n + 1 while each minibatch's stats were read before the
    next was enqueued)."""
    tracing.reconfigure()
    model = _model(critic)
    itf = (PPOCriticInterface if critic else PPOActorInterface)(
        n_minibatches=N_MINIBATCHES)
    mb_spec = MicroBatchSpec(max_tokens_per_mb=48)
    itf.train_step(model, _sample(values=critic), mb_spec)  # warm, and marks nothing
    tracing.start()
    try:
        for seed in (1, 2, 3):
            itf.train_step(model, _sample(seed=seed, values=critic), mb_spec)
    finally:
        got = tracing.stop()
    spans = sorted(got["spans"], key=lambda s: s["start_ns"])
    by_id = {s["span"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "ppo.train_step"]
    main = [s for s in spans if s["tid"] == roots[0]["tid"] and s["name"] != "device.starved"]
    assert len(roots) == 3
    for i, root in enumerate(roots):
        mine = [s for s in spans if s["name"] == "device.starved"
                and s["trace"] == root["trace"]]
        untils = [s["attrs"]["until"] for s in mine]
        afters = [s["attrs"]["after"] for s in mine]
        tail = [] if i == 0 else [("train.fetch_stats", "ppo_prep")]
        assert list(zip(afters, untils)) == tail + [("ppo.prep", "accum_step")]
        for s in mine:
            # it ends as an enqueue returns, inside the span that enqueued
            parent = by_id[s["parent"]]
            assert parent["name"] == ("ppo.prep.dispatch" if s["attrs"]["until"] == "ppo_prep"
                                      else "train.dispatch")
            assert parent["start_ns"] <= s["end_ns"] <= parent["end_ns"]
            # and starts where the blocking read returned: after the span
            # that blocked (the prep's read: between its enqueue and its
            # gather) with no other span of the thread begun in between
            before = [m for m in main if m["end_ns"] <= s["start_ns"]]
            last = max(before, key=lambda m: m["end_ns"])
            assert last["name"] == ("ppo.prep.dispatch" if s["attrs"]["after"] == "ppo.prep"
                                    else "train.fetch_stats")
            assert not [m for m in main if last["end_ns"] < m["start_ns"] < s["start_ns"]]
        if tail:  # the waiting mark was set in the step before, after its last fetch
            assert roots[i - 1]["start_ns"] < mine[0]["start_ns"] < roots[i - 1]["end_ns"]
        # the step's reads: under `ppo.stats`, oldest first, none before
        # the last minibatch's apply was enqueued
        fetches = [s for s in spans if s["name"] == "train.fetch_stats"
                   and s["trace"] == root["trace"]]
        assert [s["attrs"]["behind"] for s in fetches] == list(range(N_MINIBATCHES))[::-1]
        last_apply = max(s["end_ns"] for s in spans if s["name"] == "train.apply"
                         and s["trace"] == root["trace"])
        assert all(s["start_ns"] >= last_apply for s in fetches)
    assert got["counters"]["train.stats_deferred"] == 3 * (N_MINIBATCHES - 1)
    assert got["dropped"] == 0
