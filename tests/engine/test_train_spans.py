"""The trainer engine's own spans and counters (`train.*`, recorded
through base/tracing.py where the work happens), on both input paths:
one `train.batch` a call, one `train.dispatch` a micro-batch (the fused
step: one for all of them), the stage on the prefetcher's thread under
the batch's trace, tokens <= cells, and `perf/*` telemetry the same
whether tracing is on or off."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import stats_tracker, tracing
from areal_tpu.models.transformer import init_params

from tests.engine.test_prefetch import (
    loss_weight, make_batch, mk_engine, packed_loss, small_cfg,
)

N_MBS = 3
N_LAYERS = small_cfg().n_layers
# path -> prefetch depth, and per train_batch the count of each span kind
PATHS = {
    "overlapped": dict(depth=2, counts={
        "train.batch": 1, "train.begin": 1, "train.stage": N_MBS, "train.pack": N_MBS,
        "train.h2d": N_MBS, "train.wait_input": N_MBS + 1,
        "train.dispatch": N_MBS, "train.apply": 1, "train.fetch_stats": 1}),
    "fused": dict(depth=0, counts={
        "train.batch": 1, "train.begin": 1, "train.pack": 1, "train.h2d": 1,
        "train.dispatch": 1, "train.fetch_stats": 1}),
}


@pytest.fixture(autouse=True)
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


@pytest.fixture(scope="module", params=sorted(PATHS))
def recorded(request):
    """One traced train_batch on each path (after an untraced warm one),
    its stats read at once, as a caller with one update a step reads them:
    (path, what stop() returned, the engine's telemetry, the main thread)."""
    path = request.param
    tracing.reconfigure()
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(5)),
                    depth=PATHS[path]["depth"])
    batch = make_batch(n=9, seed=5)
    args = (batch, MicroBatchSpec(n_mbs=N_MBS), packed_loss, loss_weight)
    # warm, untraced; twice: the second call hands the programs updated
    # parameters, and jax looks its cached trace up again (a `jit.trace`
    # of microseconds), the third and every later one build nothing
    eng.train_batch(*args, loss_name="t")
    eng.train_batch(*args, loss_name="t")
    assert tracing.recorder() is None
    off = dict(eng.last_overlap)
    tracing.start()
    try:
        dict(eng.train_batch(*args, loss_name="t"))
    finally:
        got = tracing.stop()
    # the (rows, row length) the engine's packer gives each micro-batch
    got["shapes"] = [eng._build_rows(mb)[0].input_ids.shape
                     for mb in batch.split(MicroBatchSpec(n_mbs=N_MBS))[0]]
    return path, got, off, dict(eng.last_overlap), threading.get_ident() & 0xFFFF


@pytest.mark.parametrize("kind", sorted({k for p in PATHS.values() for k in p["counts"]}))
def test_each_span_kind_is_recorded_the_right_number_of_times(recorded, kind):
    path, got, *_ = recorded
    n = sum(s["name"] == kind for s in got["spans"])
    assert n == PATHS[path]["counts"].get(kind, 0), (path, kind, n)


def test_the_tree_hangs_under_one_train_batch(recorded):
    path, got, _, _, main_tid = recorded
    spans = got["spans"]
    by_id = {s["span"]: s for s in spans}
    [batch] = [s for s in spans if s["name"] == "train.batch"]
    # the read is the caller's: after the batch has ended, under whatever
    # span the caller is in (here none)
    [fetch] = [s for s in spans if s["name"] == "train.fetch_stats"]
    assert fetch["parent"] is None and fetch["start_ns"] >= batch["end_ns"]
    assert {s["trace"] for s in spans if s is not fetch} == {batch["trace"]}
    parent_of = {s["name"]: by_id[s["parent"]]["name"] for s in spans if s["parent"]}
    want = {"train.dispatch": "train.batch", "train.begin": "train.batch"}
    if path == "overlapped":
        want.update({"train.stage": "train.batch", "train.pack": "train.stage",
                     "train.h2d": "train.stage", "train.wait_input": "train.batch",
                     "train.apply": "train.batch"})
    else:
        want.update({"train.pack": "train.batch", "train.h2d": "train.batch"})
    assert parent_of == want
    # `train.begin`: the host's work before the path's input begins
    [begin] = [s for s in spans if s["name"] == "train.begin"]
    first_input = min(s["start_ns"] for s in spans if s["name"] == (
        "train.wait_input" if path == "overlapped" else "train.pack"))
    assert batch["start_ns"] <= begin["start_ns"] < begin["end_ns"] <= first_input
    # the stage runs on the prefetcher's thread, everything else on ours
    for s in spans:
        on_worker = path == "overlapped" and s["name"] in (
            "train.stage", "train.pack", "train.h2d")
        assert (s["tid"] != main_tid) == on_worker, s


def test_attributes_and_counters_count_tokens_and_cells(recorded):
    path, got, *_ = recorded
    spans = got["spans"]
    [batch] = [s for s in spans if s["name"] == "train.batch"]
    a = batch["attrs"]
    assert a["path"] == path and a["n_mbs"] == N_MBS
    assert 0 < a["tokens"] <= a["cells"]
    dispatches = [s["attrs"] for s in spans if s["name"] == "train.dispatch"]
    shapes = got["shapes"]
    # one program a micro-batch, or one for all of them that says the
    # shape of the largest
    assert [(d["rows"], d["row_len"]) for d in dispatches] == (
        shapes if path == "overlapped" else [max(shapes, key=np.prod)])
    # the reference runs on the CPU, at the rows' own length and over
    # every cell of a row, in each layer, whatever the mask
    all_cells = sum(r * t ** 2 for r, t in shapes) * N_LAYERS
    assert a["cells"] == sum(r * t for r, t in shapes)
    assert got["counters"] == {
        "train.batches": 1, "train.micro_batches": N_MBS,
        "train.one_row_batches": sum(r == 1 for r, _ in shapes),
        "train.tokens": a["tokens"], "train.cells": a["cells"],
        "train.attn_cells": a["cells"],
        # the einsum reference reads no operand where a projection left it
        "train.attn_cells_in_place": 0,
        "train.attn_active_cells": all_cells,
        "train.attn_causal_cells": all_cells,
        # no layer has a window: the split says none and the whole
        "train.attn_window_cells": 0, "train.attn_full_cells": all_cells,
        # the einsum reference has no grid to walk
        "train.attn_grid_steps": 0, "train.attn_live_steps": 0,
        "train.attn_bwd_steps": 0,
        # no `scored_fn`: the head reads every token but a sequence's
        # last (9 sequences) and runs over every cell
        "train.scored_cells": a["tokens"] - 9, "train.head_cells": a["cells"],
        # rows under two bands: the layers' token-wise stretches run whole
        "train.band_cells": a["cells"]}
    assert all("window" not in d and "kinds" not in d for d in dispatches)
    kinds = [s["attrs"]["kind"] for s in spans if s["name"] == "train.dispatch"]
    assert kinds == (["first"] + ["next"] * (N_MBS - 1) if path == "overlapped"
                     else ["fused"])
    for s in spans:
        if s["name"] == "train.dispatch":
            assert s["attrs"]["rows"] >= 1 and s["attrs"]["row_len"] % 32 == 0
            assert s["attrs"]["attn_row_len"] == s["attrs"]["row_len"]
            assert s["attrs"]["width"] == 0
        if s["name"] == "train.stage":
            assert 0 < s["attrs"]["tokens"] <= s["attrs"]["cells"]
        if s["name"] == "train.fetch_stats":
            assert s["attrs"] == {"stale": False, "behind": 0}
    if path == "overlapped":
        stages = [s["attrs"] for s in spans if s["name"] == "train.stage"]
        assert sum(x["tokens"] for x in stages) == a["tokens"]
        assert sum(x["cells"] for x in stages) == a["cells"]


def test_perf_telemetry_is_the_same_with_tracing_on_and_off(recorded):
    _, _, off, on, _ = recorded
    assert set(on) == set(off) == {
        "packing_efficiency", "h2d_wait_ms", "dispatch_gap_ms", "overlap_events"}
    assert on["packing_efficiency"] == off["packing_efficiency"]
    assert on["h2d_wait_ms"] >= 0.0 and on["dispatch_gap_ms"] >= 0.0


def test_the_jit_cache_holds_three_entries_and_a_stale_fetch_is_marked_and_drains_nothing():
    stats_tracker.export()
    tracing.start()
    try:
        eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(6)),
                        depth=2, stats_fetch_interval=2)
        batch = make_batch(n=9, seed=6)
        for _ in range(4):  # each read at once
            dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=N_MBS), packed_loss,
                                 loss_weight, loss_name="t"))
    finally:
        got = tracing.stop()
    # one entry for the accumulate program, one for the two beside it
    # that see no row, one for the apply; built once, run four times
    assert len(eng._jit_cache) == 3
    assert got["counters"]["train.batches"] == 4
    fetches = [s for s in got["spans"] if s["name"] == "train.fetch_stats"]
    assert [s["attrs"]["stale"] for s in fetches] == [False, False, True, False]
    # a fetch that blocked leaves a mark for the next batch's first
    # enqueue; the stale one did not block, and the fourth batch starts
    # with the third's programs still queued: no stretch
    batches = [s for s in got["spans"] if s["name"] == "train.batch"]
    starved = [s for s in got["spans"] if s["name"] == "device.starved"]
    assert [s["trace"] for s in starved] == [b["trace"] for b in batches[1:3]]
    for s, fetch in zip(starved, fetches):
        assert s["attrs"] == {"after": "train.fetch_stats", "until": "accum_step"}
        assert fetch["end_ns"] <= s["start_ns"] < s["end_ns"]
    out = stats_tracker.export()
    assert {"perf/packing_efficiency", "perf/h2d_wait_ms",
            "perf/dispatch_gap_ms", "perf/overlap_events"} <= set(out)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_only_a_read_of_the_newest_enqueued_programs_stats_marks_the_device_drained(path):
    """Reading an older call's stats returns while the calls made since
    are still queued: the device is not dry and no `device.starved` span
    may follow. The newest call's read does empty the queue. So `n`
    calls read at the end of each round leave one stretch a round (the
    PPO step's two, with its prep's: tests/interfaces/test_ppo_spans.py)
    where reading each at once leaves one a call."""
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(12)),
                    depth=PATHS[path]["depth"])
    args = (make_batch(n=9, seed=12), MicroBatchSpec(n_mbs=N_MBS), packed_loss, loss_weight)
    dict(eng.train_batch(*args, loss_name="t"))
    dict(eng.train_batch(*args, loss_name="t"))  # warm, untraced
    until = "accum_step" if path == "overlapped" else "fused_step"
    tracing.start()
    try:
        old = eng.train_batch(*args, loss_name="t")
        new = eng.train_batch(*args, loss_name="t")
        dict(old)  # one call behind it: marks nothing
        eng.train_batch(*args, loss_name="t")  # so this enqueue ends no stretch
        mid = tracing.stop()
        tracing.start()
        dict(new)  # still one behind (the third call)
        newest = eng.train_batch(*args, loss_name="t")
        dict(newest)  # nothing behind: the queue is empty when this returns
        eng.train_batch(*args, loss_name="t")  # and this enqueue ends the stretch
    finally:
        got = tracing.stop()
    assert [s for s in mid["spans"] if s["name"] == "device.starved"] == []
    assert [s["attrs"]["behind"] for s in mid["spans"]
            if s["name"] == "train.fetch_stats"] == [1]
    fetches = sorted((s for s in got["spans"] if s["name"] == "train.fetch_stats"),
                     key=lambda s: s["start_ns"])
    assert [s["attrs"]["behind"] for s in fetches] == [1, 0]
    [starved] = [s for s in got["spans"] if s["name"] == "device.starved"]
    assert starved["attrs"] == {"after": "train.fetch_stats", "until": until}
    assert fetches[1]["end_ns"] <= starved["start_ns"] < starved["end_ns"]
    assert mid["counters"]["train.stats_deferred"] == got["counters"]["train.stats_deferred"] == 1


@pytest.mark.parametrize("n", [2, 4])
def test_n_calls_read_at_their_end_leave_one_stretch_where_reading_at_once_leaves_n(n):
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(13)), depth=2)
    args = (make_batch(n=9, seed=13), MicroBatchSpec(n_mbs=N_MBS), packed_loss, loss_weight)
    dict(eng.train_batch(*args, loss_name="t"))
    dict(eng.train_batch(*args, loss_name="t"))  # warm, untraced

    def rounds(at_once):
        tracing.start()
        try:
            for _ in range(3):
                sts = []
                for _ in range(n):
                    st = eng.train_batch(*args, loss_name="t")
                    sts.append(dict(st) if at_once else st)
                [dict(st) for st in sts]
        finally:
            got = tracing.stop()
        return sum(s["name"] == "device.starved" for s in got["spans"])

    # a session's first enqueue follows no mark, its last read feeds none
    assert rounds(at_once=True) == 3 * n - 1
    assert rounds(at_once=False) == 3 - 1


@pytest.mark.parametrize("impl", ["splash", "reference"])
def test_attn_cells_count_the_length_the_kernel_runs_at(impl):
    """A row of 640 (5 blocks of 128): splash pads it to a length whose
    blocks are large and `train.attn_cells` counts rows x that length;
    the reference runs it as it is."""
    from areal_tpu.ops.attention import splash_run_shape

    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(7)), depth=0,
                    attn_impl=impl)
    eng.row_len_multiple = 128
    rng = np.random.RandomState(7)
    seqlens = [300, 200, 100]
    total = sum(seqlens)
    batch = SequenceSample.from_default(
        ids=[f"a{i}" for i in range(3)], seqlens=seqlens,
        data={"packed_input_ids": rng.randint(0, 64, size=total),
              "loss_mask": np.ones(total, np.float32)})
    tracing.start()
    try:
        eng.train_batch(batch, MicroBatchSpec(n_mbs=1), packed_loss, loss_weight,
                        loss_name="t")
    finally:
        got = tracing.stop()
    [d] = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert d["row_len"] == 640
    want = splash_run_shape(640)[0] if impl == "splash" else 640
    assert (want > 640) == (impl == "splash")
    assert d["attn_row_len"] == want
    c = got["counters"]
    assert c["train.cells"] == d["rows"] * 640
    assert c["train.attn_cells"] == d["rows"] * want
    # the grids the kernels walk, by q head and layer: a short row keeps
    # the static ones (forward nq x widest, the fused backward nq x nkv);
    # the reference has none
    if impl == "splash":
        from areal_tpu.ops.attention import attn_grid_steps

        cfg = eng.model_cfg
        seg = np.zeros((d["rows"], 640), np.int32)
        steps, live, width, backward = attn_grid_steps(
            "splash", seg, cfg.n_q_heads, cfg.n_kv_heads)
        assert 0 < live < steps and d["width"] == width > 0 < backward < steps
        assert c["train.attn_grid_steps"] == steps * cfg.n_q_heads * cfg.n_layers
        assert c["train.attn_live_steps"] == live * cfg.n_q_heads * cfg.n_layers
        assert c["train.attn_bwd_steps"] == backward * cfg.n_q_heads * cfg.n_layers
    else:
        assert c["train.attn_grid_steps"] == c["train.attn_live_steps"] == d["width"] == 0
        assert c["train.attn_bwd_steps"] == 0


def test_band_cells_are_the_cells_the_devices_loops_run(monkeypatch):
    """A row of 640 at bands of 128 holding 300 tokens: the host counts
    three bands of five (`train.band_cells` 384 beside `train.cells` 640)
    by the rule the device's trip count is made from, and two rows
    together count every cell."""
    from areal_tpu.ops import band_loop

    monkeypatch.setattr(band_loop, "_BAND", 128)
    jax.clear_caches()
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(7)), depth=0,
                    attn_impl="reference")
    eng.row_len_multiple = eng.counts.row_len_multiple = 640
    rng = np.random.RandomState(7)

    def counters(seqlens):
        total = sum(seqlens)
        batch = SequenceSample.from_default(
            ids=[f"b{i}" for i in range(len(seqlens))], seqlens=seqlens,
            data={"packed_input_ids": rng.randint(0, 64, size=total),
                  "loss_mask": np.ones(total, np.float32)})
        tracing.start()
        try:
            eng.train_batch(batch, MicroBatchSpec(n_mbs=1), packed_loss, loss_weight,
                            loss_name="t")
        finally:
            got = tracing.stop()
        [d] = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
        return d, got["counters"]

    d, c = counters([200, 100])
    assert (d["rows"], d["row_len"]) == (1, 640)
    seg = np.zeros((1, 640), np.int32)
    seg[0, :300] = 1
    assert c["train.band_cells"] == 128 * int(band_loop.live_bands(jnp.asarray(seg))) == 384
    assert c["train.cells"] == 640 and c["train.tokens"] == 300
    # a ladder that steps by a band or less fills a row's every band: at a
    # multiple of 16 a row of 512 pads under 32 cells, and runs whole
    eng.row_len_multiple = eng.counts.row_len_multiple = 16
    d, c = counters([300, 190])
    assert (d["rows"], d["row_len"]) == (1, 512) and band_loop.loops(1, 512)
    assert not eng._dead_bands(512) and c["train.band_cells"] == c["train.cells"] == 512
    eng.row_len_multiple = eng.counts.row_len_multiple = 640
    # rows together, a row under two bands and micro-batches stacked: by shape
    bands = lambda seg: eng.counts.of({"segment_ids": seg}, 0)[0]["train.band_cells"]
    assert bands(np.tile(seg, (2, 1))) == 1280
    assert bands(seg[:, :128]) == 128
    assert bands(np.stack([seg, np.roll(seg, 200, axis=1)])) == 384 + 512


# ---------------------------------------------------------------------------
# What the engine builds (tracing.builds, jit.*), the forward-only path's
# spans, and what tracing costs when it is off
# ---------------------------------------------------------------------------


def _long_batch(seed, n=6, lo=40, hi=60):
    """Sequences long enough that a micro-batch's row is a rung above
    `make_batch`'s."""
    rng = np.random.RandomState(seed)
    seqlens = rng.randint(lo, hi, size=n).tolist()
    total = sum(seqlens)
    return SequenceSample.from_default(
        ids=[f"long{seed}-{i}" for i in range(n)], seqlens=seqlens,
        data={"packed_input_ids": rng.randint(0, 64, size=total),
              "loss_mask": np.ones(total, np.float32)})


def _children(spans, parent):
    return [s for s in spans if s["parent"] == parent["span"]]


def test_a_new_shape_through_an_old_jit_entry_is_built_under_its_dispatch():
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(8)), depth=2)
    spec = MicroBatchSpec(n_mbs=N_MBS)
    tracing.start()
    try:
        for _ in range(2):  # the second call looks its traces up again
            eng.train_batch(make_batch(n=9, seed=8), spec, packed_loss,
                            loss_weight, loss_name="t")
        warm = tracing.stop()
        n_builds, n_entries = len(warm["builds"]), len(eng._jit_cache)
        tracing.start()
        eng.train_batch(_long_batch(8), spec, packed_loss, loss_weight,
                        loss_name="t")
    finally:
        got = tracing.stop()
    # jit-cache entries: the accumulate program, the two beside it that
    # see no row and the apply, made by the first call; a new shape adds none
    assert len(eng._jit_cache) == n_entries == 3
    dispatches = [s for s in got["spans"] if s["name"] == "train.dispatch"]
    shapes = {(s["attrs"]["rows"], s["attrs"]["row_len"]) for s in dispatches}
    old = {(s["attrs"]["rows"], s["attrs"]["row_len"])
           for s in warm["spans"] if s["name"] == "train.dispatch"}
    assert shapes and not shapes & old  # every micro-batch a new shape
    paid = [s for s in dispatches if "built" in s["attrs"]]
    assert paid and got["counters"]["jit.programs_compiled"] == sum(
        s["attrs"]["built"] for s in paid)
    assert got["counters"]["jit.build_s"] > 0
    for s in paid:
        a = s["attrs"]
        kids = _children(got["spans"], s)
        assert [k["name"] for k in kids][:2] == ["jit.trace", "jit.lower"]
        assert kids[2]["name"] in ("jit.compile", "jit.cache_load")
        # a new shape builds one program, whether the micro-batch is a
        # minibatch's first or a later one
        assert len(kids) == 3 and a["kind"] in ("first", "next")
        for k in kids:
            assert k["attrs"]["program"] == "accum_step"
            assert (k["attrs"]["rows"], k["attrs"]["row_len"]) == (a["rows"], a["row_len"])
            assert s["start_ns"] <= k["start_ns"] + 1_000_000 and k["end_ns"] <= s["end_ns"]
    # the records say the same without a session: which step recompiled
    new = [b for b in got["builds"][n_builds:] if b["program"] is not None]
    assert {b["program"] for b in new} <= {"accum_step", "apply"}
    assert {(b["rows"], b["row_len"]) for b in new if b["rows"]} == shapes
    assert all(b["fun"] in ("mb_accum", "jit(mb_accum)", "apply", "jit(apply)")
               for b in new)


def test_the_fused_step_and_the_apply_are_named_too():
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(9)), depth=0)
    n = len(tracing.builds())
    eng.train_batch(make_batch(n=9, seed=9), MicroBatchSpec(n_mbs=N_MBS),
                    packed_loss, loss_weight, loss_name="t")
    assert tracing.recorder() is None  # records without spans
    named = [b for b in tracing.builds()[n:] if b["program"]]
    assert {b["program"] for b in named} == {"fused_step"}
    assert {b["fun"] for b in named} == {"step", "jit(step)"}
    assert all(b["rows"] >= 1 and b["row_len"] % 32 == 0 for b in named)
    eng2 = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(9)), depth=2)
    n = len(tracing.builds())
    eng2.train_batch(make_batch(n=9, seed=9), MicroBatchSpec(n_mbs=N_MBS),
                     packed_loss, loss_weight, loss_name="t")
    by_program = {}
    for b in tracing.builds()[n:]:
        by_program.setdefault(b["program"], []).append(b)
    assert set(by_program) >= {"accum_step", "accum_zeros", "accum_stats", "apply"}
    assert all(b["rows"] is None for p in ("accum_zeros", "accum_stats", "apply")
               for b in by_program[p])


@pytest.mark.parametrize("depth", [2, 0])
def test_one_forward_records_the_fwd_spans(depth):
    eng = mk_engine(init_params(small_cfg(), jax.random.PRNGKey(10)), depth=depth)
    batch = make_batch(n=9, seed=10)
    spec = MicroBatchSpec(n_mbs=N_MBS)
    want = eng.forward(batch, spec).data["logprobs"]  # untraced, and builds
    tracing.start()
    try:
        out = eng.forward(batch, spec).data["logprobs"]
        eng.forward(_long_batch(10), spec)
    finally:
        got = tracing.stop()
    np.testing.assert_array_equal(out, want)
    roots = [s for s in got["spans"] if s["name"] == "fwd.batch"]
    assert len(roots) == 2 and all(s["parent"] is None for s in roots)
    kids = _children(got["spans"], roots[0])
    names = [k["name"] for k in kids]
    assert names.count("fwd.dispatch") == N_MBS
    # one drain of every output, or one fetch a micro-batch without the prefetcher
    assert names.count("fwd.fetch") == (1 if depth else N_MBS)
    assert names.count("fwd.wait_input") == (N_MBS + 1 if depth else 0)
    assert set(names) <= {"fwd.dispatch", "fwd.fetch", "fwd.wait_input"}
    a = roots[0]["attrs"]
    shapes = [(k["attrs"]["rows"], k["attrs"]["row_len"])
              for k in kids if k["name"] == "fwd.dispatch"]
    assert a["n_mbs"] == N_MBS and a["cells"] == sum(r * t for r, t in shapes)
    assert 0 < a["tokens"] == sum(sum(sl) for sl in batch.seqlens["packed_input_ids"])
    assert a["tokens"] <= a["cells"]
    assert all("built" not in k.get("attrs", {}) for k in kids)  # warm
    # the second forward's shapes are new: built under their dispatches
    paid = [k for k in _children(got["spans"], roots[1])
            if k["name"] == "fwd.dispatch" and "built" in k["attrs"]]
    assert paid
    for s in paid:
        for k in _children(got["spans"], s):
            if k["name"] == "device.starved":
                continue
            assert k["name"].startswith("jit.") and k["attrs"]["program"] == "forward"
            assert k["attrs"]["row_len"] == s["attrs"]["row_len"]
    # every fetch blocks and the dispatch after it ends the stretch: one
    # drain a forward with the prefetcher, one a micro-batch without; the
    # session's first dispatch follows no drain, its last fetch feeds none
    starved = [s for s in got["spans"] if s["name"] == "device.starved"]
    by_id = {s["span"]: s for s in got["spans"]}
    assert len(starved) == (1 if depth else 2 * N_MBS - 1)
    for s in starved:
        assert s["attrs"] == {"after": "fwd.fetch", "until": "forward"}
        assert by_id[s["parent"]]["name"] == "fwd.dispatch"
        assert s["end_ns"] <= by_id[s["parent"]]["end_ns"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_tracing_off_runs_no_host_count_and_the_stats_are_bit_equal(path, monkeypatch):
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.engine.train_counts import TrainCounts

    params = init_params(small_cfg(), jax.random.PRNGKey(11))
    batch = make_batch(n=9, seed=11)
    args = (batch, MicroBatchSpec(n_mbs=N_MBS), packed_loss, loss_weight)
    on_eng = mk_engine(params, depth=PATHS[path]["depth"])
    tracing.start()
    try:
        on = [on_eng.train_batch(*args, loss_name="t") for _ in range(2)]
    finally:
        counters = tracing.stop()["counters"]
    assert counters["train.attn_cells"] > 0 and counters["train.head_cells"] > 0

    called = []
    monkeypatch.setattr(TrainCounts, "of", lambda self, *a, **k: called.append("of") or ({}, {}))
    monkeypatch.setattr(JaxTrainEngine, "_count_batch",
                        lambda *a, **k: called.append("_count_batch"))
    off_eng = mk_engine(params, depth=PATHS[path]["depth"])
    off = [off_eng.train_batch(*args, loss_name="t") for _ in range(2)]
    assert called == [] and not tracing.enabled()
    assert on == off  # every float, to the bit
    assert off_eng.last_overlap["packing_efficiency"] == \
        on_eng.last_overlap["packing_efficiency"] > 0
