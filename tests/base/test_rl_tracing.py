"""RL-trace recorder unit tests (ISSUE 3 tentpole).

Pins the two hard contracts:

- DISABLED is a true no-op: span calls cost one branch, no recorder is
  ever allocated, no shard files appear (the acceptance criterion),
  unless the environment or `start()` says so.
- ENABLED records parent-linked spans into per-worker JSONL shards that
  the aggregator merges with intact flow links, and the trace context
  survives both transports' metadata (request_reply_stream Payload,
  push/pull JSON).
"""

import json
import os

import pytest

from areal_tpu.base import tracing
from areal_tpu.system import push_pull_stream as pps
from areal_tpu.system import request_reply_stream as rrs
from areal_tpu.utils import rl_trace


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Tracing ON into a fresh shard dir; restored + reset afterwards."""
    d = str(tmp_path / "rl_trace")
    monkeypatch.setenv("AREAL_RL_TRACE", "1")
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", d)
    tracing.reconfigure()
    tracing.configure_worker("test_worker/0")
    yield d
    tracing.reconfigure()


@pytest.fixture
def untraced(tmp_path, monkeypatch):
    d = str(tmp_path / "rl_trace_off")
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", d)
    tracing.reconfigure()
    yield d
    tracing.reconfigure()


def _load_spans(trace_dir):
    spans = []
    for name in os.listdir(trace_dir):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(trace_dir, name)) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "span":
                    spans.append(rec)
    return spans


# ---------------------------------------------------------------------------
# No-op fast path
# ---------------------------------------------------------------------------


def test_disabled_is_true_noop(untraced):
    with tracing.span("a", attr=1) as ctx:
        assert ctx is None
        tracing.event("b")
        tracing.record_span("c", tracing.now_ns())
        assert tracing.start_span("d") is None
        assert tracing.inject() is None
        assert tracing.current() is None
        tracing.set_attrs(late=1)
        tracing.count("n")
        tracing.drained("train.fetch_stats")
        tracing.fed("accum_step")
    tracing.flush()
    assert tracing._DRAINED is None  # off: not even a mark
    # The acceptance pin: no recorder allocation, no shard files, as long
    # as neither the environment nor start() has switched tracing on.
    assert tracing.recorder() is None
    assert not os.path.exists(untraced) or not os.listdir(untraced)


def test_disabled_inject_into_returns_same_dict(untraced):
    d = {"x": 1}
    assert tracing.inject_into(d) is d
    assert tracing.extract_from({"x": 1}) is None


# ---------------------------------------------------------------------------
# Build records: what jax traces, lowers, compiles or loads from its cache
# ---------------------------------------------------------------------------


def _jitted(name):
    """A jitted function of that name that calls a jitted function (and
    `jnp.where`, jitted too): jax traces both inside the outer trace."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def helper(x):
        return jnp.where(x > 0, x, 0.0) * 2

    def f(x):
        return jnp.sum(helper(x))

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _since(n, fun=None):
    return [b for b in tracing.builds()[n:]
            if fun is None or b["fun"] in (fun, f"jit({fun})")]


def _phases(recs):
    """The records' phases; the suite runs under a persistent cache
    (tests/conftest.py), so a program is compiled (a miss that writes the
    entry) the first time a machine sees it and loaded ever after."""
    for b in recs:
        if b["phase"] in ("compile", "cache_load"):
            assert b["cache_hit"] is (b["phase"] == "cache_load")
        else:
            assert b["cache_hit"] is None
    return [{"cache_load": "compile"}.get(b["phase"], b["phase"]) for b in recs]


def test_a_first_call_leaves_trace_lower_and_compile_and_a_second_none(untraced):
    import jax.numpy as jnp

    tracing.watch_builds()
    tracing.watch_builds()  # idempotent: one listener, one record an event
    f, x = _jitted("builds_probe_a"), jnp.ones((4, 8))
    n, t0 = len(tracing.builds()), tracing.now_ns()
    f(x)
    t1 = tracing.now_ns()
    recs = _since(n, "builds_probe_a")
    assert _phases(recs) == ["trace", "lower", "compile"]
    # the functions traced inside the outer trace are no records of their
    # own: the outer's seconds hold theirs
    assert {b["fun"] for b in _since(n)} == {"builds_probe_a", "jit(builds_probe_a)"}
    for b in recs:
        assert b["kind"] == "build" and t0 <= b["start_ns"] <= b["end_ns"] <= t1
        assert b["program"] is None and b["rows"] is None  # nobody named a site
    assert [a["end_ns"] <= b["start_ns"] + 1_000_000 for a, b in zip(recs, recs[1:])]
    n = len(tracing.builds())
    f(x)
    assert _since(n) == []
    f(jnp.ones((16, 8)))  # a new shape is a new program
    assert _phases(_since(n, "builds_probe_a")) == ["trace", "lower", "compile"]
    # all of it with spans off: no recorder
    assert tracing.recorder() is None


def test_a_record_takes_the_site_only_of_the_function_it_names(untraced):
    import jax.numpy as jnp

    tracing.watch_builds()
    f, g = _jitted("builds_probe_b"), _jitted("builds_probe_c")
    n = len(tracing.builds())
    tracing.build_site("accum_first", f, 2, 128)
    f(jnp.ones((2, 128)))
    g(jnp.ones((2, 128)))  # built after the site was named, by another function
    mine, other = _since(n, "builds_probe_b"), _since(n, "builds_probe_c")
    assert len(mine) == len(other) == 3
    assert all((b["program"], b["rows"], b["row_len"]) == ("accum_first", 2, 128)
               for b in mine)
    assert all(b["program"] is None and b["row_len"] is None for b in other)


def test_the_list_of_builds_is_bounded_and_counts_what_it_drops(untraced, monkeypatch):
    import jax.numpy as jnp

    tracing.watch_builds()
    monkeypatch.setattr(tracing, "_BUILDS_CAP", 8)
    # whatever this process built before (other tests of the worker) is
    # not this test's to drop four at a time
    monkeypatch.setattr(tracing, "_BUILDS", [])
    dropped = tracing.builds_dropped()
    f = _jitted("builds_probe_d")
    for i in range(1, 8):
        f(jnp.ones((i, 3)))
    assert len(tracing.builds()) <= 8
    assert tracing.builds_dropped() > dropped
    assert tracing.builds()[-1]["fun"] == "jit(builds_probe_d)"  # the oldest went
    assert tracing.stop()["builds_dropped"] == tracing.builds_dropped()


def test_stop_returns_builds_made_before_start_and_spans_for_those_inside(live):
    import jax.numpy as jnp

    tracing.watch_builds()
    f, x = _jitted("builds_probe_e"), jnp.ones((3, 2))
    tracing.build_site("apply", print)  # this thread's last dispatch was another's
    n = len(tracing.builds())
    f(jnp.ones((2, 2)))  # set-up: before any session
    assert tracing.recorder() is None
    tracing.start()
    tracing.build_site("forward", f, 3, 2)
    with tracing.span("fwd.dispatch") as ctx:
        f(x)
    with tracing.span("fwd.dispatch"):
        f(x)  # builds nothing
    got = tracing.stop()
    recs = [b for b in got["builds"][n:] if "builds_probe_e" in b["fun"]]
    assert _phases(recs) == ["trace", "lower", "compile"] * 2
    assert [b["rows"] for b in recs] == [None] * 3 + [3] * 3
    first, second = [s for s in got["spans"] if s["name"] == "fwd.dispatch"]
    assert first["attrs"] == {"built": 1} and "attrs" not in second
    jit = [s for s in got["spans"] if s["name"].startswith("jit.")]
    assert [s["name"] for s in jit] == ["jit." + b["phase"] for b in recs[3:]]
    for s, b in zip(jit, recs[3:]):
        assert s["parent"] == ctx.span_id and s["trace"] == ctx.trace_id
        assert (s["start_ns"], s["end_ns"]) == (b["start_ns"], b["end_ns"])
        assert s["attrs"] == {
            "fun": b["fun"], "program": "forward", "rows": 3, "row_len": 2,
            **({} if b["cache_hit"] is None else {"cache_hit": b["cache_hit"]})}
    c = got["counters"]
    assert c["jit.programs_compiled"] == 1
    assert c.get("jit.cache_hits", 0) + c.get("jit.cache_misses", 0) == 1
    assert c["jit.build_s"] == pytest.approx(
        sum(b["end_ns"] - b["start_ns"] for b in recs[3:]) / 1e9, rel=1e-3)


def test_a_program_no_cache_has_seen_is_a_miss_and_its_twin_a_load(untraced):
    import jax
    import jax.numpy as jnp

    tracing.watch_builds()
    c = float(int.from_bytes(os.urandom(4), "big"))  # in no cache yet

    def twin():
        def f(x):
            return jnp.sum(x * c)

        f.__name__ = f.__qualname__ = "builds_probe_f"
        return jax.jit(f)

    n = len(tracing.builds())
    twin()(jnp.ones((3, 3)))
    twin()(jnp.ones((3, 3)))  # traced anew, and the same program
    got = [(b["phase"], b["cache_hit"]) for b in _since(n, "builds_probe_f")]
    assert got == [("trace", None), ("lower", None), ("compile", False),
                   ("trace", None), ("lower", None), ("cache_load", True)]


def test_jaxs_events_in_its_order_on_a_hit_a_miss_and_without_a_cache(untraced):
    """jax's own events, fired by hand in its order on a hit (compiler.py:
    `cache_hits`, `cache_retrieval_time_sec`, then the
    `backend_compile_duration` that wraps the look-up) and on a miss."""
    from jax import monitoring

    tracing.watch_builds()
    compile_ev = "/jax/core/compile/backend_compile_duration"

    def backend_compile(hit):
        monitoring.record_scalar(compile_ev, 0.0, fun_name="jit(p)")
        if hit:
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        else:
            monitoring.record_event("/jax/compilation_cache/cache_misses")
        monitoring.record_event_duration_secs(compile_ev, 0.5, fun_name="jit(p)")

    n = len(tracing.builds())
    backend_compile(hit=True)
    backend_compile(hit=False)
    monitoring.record_scalar(compile_ev, 0.0, fun_name="jit(p)")
    monitoring.record_event_duration_secs(compile_ev, 0.5, fun_name="jit(p)")
    got = [(b["phase"], b["cache_hit"]) for b in tracing.builds()[n:]]
    assert got == [("cache_load", True), ("compile", False), ("compile", None)]
    assert all(b["end_ns"] - b["start_ns"] == 500_000_000
               for b in tracing.builds()[n:])


# ---------------------------------------------------------------------------
# The runtime control: start() / stop() in a live process
# ---------------------------------------------------------------------------


@pytest.fixture(params=["no_dir", "dir"])
def live(request, tmp_path, monkeypatch):
    """A process whose environment says off; with and without a shard dir."""
    d = str(tmp_path / "rl_trace_live")
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    if request.param == "dir":
        monkeypatch.setenv("AREAL_RL_TRACE_DIR", d)
    else:
        monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield d if request.param == "dir" else None
    tracing.reconfigure()


def test_start_stop_switch_a_live_process(live):
    with tracing.span("before") as ctx:
        assert ctx is None
    assert tracing.recorder() is None
    assert tracing.start() is True
    assert tracing.enabled() and tracing.recorder() is not None
    with tracing.span("outer", k=1) as outer:
        tracing.set_attrs(late=2)
        tracing.count("things", 3)
        tracing.count("things")
        with tracing.span("inner"):
            tracing.set_attrs(only_inner=True)
        t0 = tracing.now_ns()
        tracing.record_span("explicit", t0, t0 + 5)
    got = tracing.stop()
    # back to what the environment says, and nothing lost to a file
    assert not tracing.enabled()
    with tracing.span("after") as ctx:
        assert ctx is None
    spans = {s["name"]: s for s in got["spans"]}
    assert set(spans) == {"outer", "inner", "explicit"}
    assert spans["inner"]["parent"] == spans["outer"]["span"] == outer.span_id
    assert spans["outer"]["attrs"] == {"k": 1, "late": 2}
    assert spans["inner"]["attrs"] == {"only_inner": True}
    assert got["counters"] == {"things": 4} and got["dropped"] == 0
    assert got["profile_dir"] is None and got["clock_anchor"] is None
    # the shard is written only where AREAL_RL_TRACE_DIR is in use
    if live is None:
        assert tracing.recorder().path is None
    else:
        assert {s["name"] for s in _load_spans(live)} == set(spans)


def test_start_and_stop_are_idempotent_and_sessions_do_not_leak(live):
    assert tracing.stop()["spans"] == []  # no session: the same dict, empty
    assert tracing.start() is True
    assert tracing.start() is False  # a second start changes nothing
    tracing.event("first")
    tracing.count("c")
    assert [s["name"] for s in tracing.stop()["spans"]] == ["first"]
    # empty but for the process's build records, which outlive sessions
    assert tracing.stop() == {"spans": [], "counters": {}, "dropped": 0,
                              "profile_dir": None, "clock_anchor": None,
                              "builds": tracing.builds(),
                              "builds_dropped": tracing.builds_dropped()}
    assert tracing.start() is True
    tracing.event("second")
    got = tracing.stop()
    assert [s["name"] for s in got["spans"]] == ["second"]
    assert got["counters"] == {}


def test_a_starved_stretch_runs_from_the_first_mark_to_the_next_enqueue(live):
    tracing.start()
    with tracing.span("step") as step:
        tracing.fed("accum_step")  # nothing drained yet: nothing to end
        t0 = tracing.now_ns()
        tracing.drained("train.fetch_stats")
        t1 = tracing.now_ns()
        tracing.drained("ppo.prep")  # the device has been empty since the first
        with tracing.span("train.dispatch") as enqueue:
            tracing.fed("accum_step")
            t2 = tracing.now_ns()
            tracing.fed("accum_step")  # a later enqueue ends nothing
    got = tracing.stop()
    [s] = [x for x in got["spans"] if x["name"] == "device.starved"]
    assert t0 <= s["start_ns"] <= t1 <= s["end_ns"] <= t2
    assert s["attrs"] == {"after": "train.fetch_stats", "until": "accum_step"}
    # under the span that enqueued, in its trace
    assert (s["trace"], s["parent"]) == (step.trace_id, enqueue.span_id)
    assert tracing._DRAINED is None


def test_a_mark_from_before_start_or_left_at_stop_ends_in_no_span(traced):
    tracing.drained("train.fetch_stats")  # the environment's recording is on
    assert tracing._DRAINED is not None
    tracing.start()  # a session records no stretch that began before it
    tracing.fed("ppo_prep")
    tracing.drained("ppo.prep")
    got = tracing.stop()  # nor leaves one that would end after it
    tracing.fed("accum_step")
    tracing.flush()
    assert got["spans"] == [] and tracing._DRAINED is None
    assert "device.starved" not in {s["name"] for s in _load_spans(traced)}


def test_control_works_from_any_thread(live):
    import threading

    t = threading.Thread(target=tracing.start)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and tracing.enabled()
    with tracing.span("main_thread"):
        pass
    box = {}
    t = threading.Thread(target=lambda: box.update(got=tracing.stop()))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert [s["name"] for s in box["got"]["spans"]] == ["main_thread"]


def test_environment_on_stays_on_after_stop(traced):
    assert tracing.start() is True
    tracing.event("in_session")
    assert [s["name"] for s in tracing.stop()["spans"]] == ["in_session"]
    assert tracing.enabled()  # "on from the first call" still holds
    tracing.event("after_session")
    tracing.flush()
    assert {s["name"] for s in _load_spans(traced)} == {"in_session", "after_session"}


def test_session_memory_is_bounded_like_the_ring(monkeypatch, live):
    monkeypatch.setenv("AREAL_RL_TRACE_RING", "8")
    tracing.reconfigure()
    tracing.start()
    for i in range(20):
        tracing.event(f"e{i}")
    got = tracing.stop()
    assert len(got["spans"]) <= 8 and got["dropped"] > 0
    assert got["spans"][-1]["name"] == "e19"  # the oldest went


def test_sessions_toggled_under_concurrent_spans_lose_nothing_they_own(live):
    """More threads than cores record spans and counts while the control
    is toggled: every span a session returns is whole, counters never
    exceed what was counted, and the threads end."""
    import sys
    import threading

    stop_flag, errors, emitted = threading.Event(), [], [0] * 16

    def work(i):
        try:
            while not stop_flag.is_set():
                with tracing.span("w", i=i):
                    tracing.count("c")
                    tracing.set_attrs(done=True)
                emitted[i] += 1
        except Exception as e:  # pragma: no cover - the failure this test is for
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=work, args=(i,)) for i in range(16)]
    try:
        for t in threads:
            t.start()
        got = []
        for _ in range(20):
            assert tracing.start() is True
            got.append(tracing.stop())
    finally:
        stop_flag.set()
        for t in threads:
            t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    spans = [s for g in got for s in g["spans"]]
    assert all(s["name"] == "w" and s["end_ns"] >= s["start_ns"] for s in spans)
    assert all(s["attrs"] == {"i": s["attrs"]["i"], "done": True} for s in spans)
    assert len(spans) <= sum(emitted) and sum(g["counters"].get("c", 0) for g in got) <= sum(emitted) + 16
    assert not tracing.enabled()


class _FakeProfiler:
    """Stands in for jax.profiler: records what the control asks of it."""

    def __init__(self):
        self.calls = []
        fake = self

        class TraceAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                fake.calls.append(("enter", self.name))

            def __exit__(self, *a):
                fake.calls.append(("exit", self.name))

        self.TraceAnnotation = TraceAnnotation

    def start_trace(self, log_dir, profiler_options=None):
        self.calls.append(("start_trace", log_dir,
                           profiler_options.python_tracer_level,
                           profiler_options.host_tracer_level))

    def stop_trace(self):
        self.calls.append(("stop_trace",))


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax

    fake = _FakeProfiler()
    for name in ("start_trace", "stop_trace", "TraceAnnotation"):
        monkeypatch.setattr(jax.profiler, name, getattr(fake, name))
    return fake


def test_profile_dir_starts_the_profiler_anchors_the_clocks_and_mirrors_spans(
    live, fake_profiler, tmp_path
):
    d = str(tmp_path / "prof")
    before = tracing.now_ns()
    assert tracing.start(profile_dir=d) is True
    # the options the benchmark's traced window has always used, then the anchor
    assert fake_profiler.calls[:3] == [
        ("start_trace", d, 0, 2),
        ("enter", "areal/clock_anchor"), ("exit", "areal/clock_anchor")]
    with tracing.span("train.dispatch"):
        t0 = tracing.now_ns()
        tracing.record_span("train.wait_input", t0, t0 + 1)  # not mirrored
        ms = tracing.start_span("manual")  # not mirrored
        ms.end()
    got = tracing.stop()
    assert fake_profiler.calls[3:] == [
        ("enter", "areal/train.dispatch"), ("exit", "areal/train.dispatch"),
        ("stop_trace",)]
    assert got["profile_dir"] == d
    anchor = got["clock_anchor"]
    assert anchor["name"] == "areal/clock_anchor"
    assert before <= anchor["monotonic_ns"] <= tracing.now_ns()
    rec = next(s for s in got["spans"] if s["name"] == "clock_anchor")
    assert rec["attrs"]["monotonic_ns"] == rec["start_ns"] == anchor["monotonic_ns"]
    assert {s["name"] for s in got["spans"]} == {
        "clock_anchor", "train.dispatch", "train.wait_input", "manual"}
    # with the profiler off again a span is not mirrored
    tracing.start()
    with tracing.span("plain"):
        pass
    tracing.stop()
    assert fake_profiler.calls[-1] == ("stop_trace",)


def test_a_profiler_that_fails_to_start_leaves_tracing_off(live, monkeypatch):
    import jax

    def boom(*a, **k):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="profiler busy"):
        tracing.start(profile_dir="/nonexistent")
    assert not tracing.enabled()
    assert tracing.start() is True  # and the control is still usable
    assert tracing.stop()["spans"] == []


# ---------------------------------------------------------------------------
# Recording + shard format
# ---------------------------------------------------------------------------


def test_nested_spans_share_trace_and_parent_link(traced):
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
    tracing.flush()
    spans = {s["name"]: s for s in _load_spans(traced)}
    assert spans["inner"]["trace"] == spans["outer"]["trace"]
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["start_ns"] >= spans["outer"]["start_ns"]


def test_manual_span_and_explicit_record(traced):
    ms = tracing.start_span("episode", qid="q0")
    t0 = tracing.now_ns()
    tracing.record_span("residency", t0, t0 + 1000, ctx=ms.ctx, version_start=3)
    ms.end(accepted=True)
    ms.end(accepted=False)  # idempotent: second end is a no-op
    tracing.flush()
    spans = {s["name"]: s for s in _load_spans(traced)}
    assert spans["episode"]["attrs"]["accepted"] is True
    assert spans["residency"]["parent"] == spans["episode"]["span"]
    assert spans["residency"]["attrs"]["version_start"] == 3
    header = [
        json.loads(line)
        for line in open(
            os.path.join(traced, os.listdir(traced)[0])
        )
    ][0]
    assert header["kind"] == "header"
    assert header["worker"] == "test_worker/0"
    assert header["anchor_wall_ns"] > 0 and header["anchor_mono_ns"] > 0


def test_inject_extract_roundtrip(traced):
    with tracing.span("root") as ctx:
        d = tracing.inject_into({"payload": 1})
        assert d["payload"] == 1
        got = tracing.extract_from(d)
        assert got == ctx
        assert "__rl_trace__" not in d  # extract_from pops the key


def test_ring_buffer_overflow_drops_oldest(tmp_path, monkeypatch):
    d = str(tmp_path / "ring")
    monkeypatch.setenv("AREAL_RL_TRACE", "1")
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", d)
    monkeypatch.setenv("AREAL_RL_TRACE_RING", "8")
    tracing.reconfigure()
    try:
        # Below the flush batch size but above the ring capacity: the
        # ring must drop oldest instead of growing.
        for i in range(20):
            tracing.event(f"e{i}")
        rec = tracing.recorder()
        assert rec is not None
        tracing.flush()
        shard = rl_trace.load_shard(
            os.path.join(d, os.listdir(d)[0])
        )
        assert shard.n_dropped > 0
        assert len(shard.spans) <= 8
    finally:
        tracing.reconfigure()


def _writer_threads():
    import threading

    return [t for t in threading.enumerate() if t.name == "rl-trace-writer"]


def test_the_thread_that_appends_the_512th_span_writes_nothing(traced, monkeypatch):
    import threading
    import time

    tracing.event("first")
    rec = tracing.recorder()
    real, wrote = rec._write_lines, []

    def slow(lines):
        wrote.append((threading.get_ident(), len(lines)))
        time.sleep(0.4)
        real(lines)

    monkeypatch.setattr(rec, "_write_lines", slow)
    t0 = time.monotonic()
    for i in range(600):  # past the writer's batch of 512
        tracing.event(f"e{i}")
    took = time.monotonic() - t0
    # the 512th span woke the writer's thread, which is inside its slow
    # write (or on its way there) while we are already done
    assert took < 0.3, took
    deadline = time.monotonic() + 5.0
    while not wrote and time.monotonic() < deadline:
        time.sleep(0.01)
    assert wrote and wrote[0][0] != threading.get_ident() and wrote[0][1] >= 512
    tracing.flush()  # waits for that write, then writes the rest itself
    names = [s["name"] for s in _load_spans(traced)]
    assert names == ["first"] + [f"e{i}" for i in range(600)]


def test_flush_leaves_every_span_on_disk_in_whole_lines_under_two_appenders(traced):
    import sys
    import threading

    n = 3000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(tag):
            for i in range(n):
                tracing.event(f"{tag}{i}", payload="x" * 200)

        threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            tracing.flush()  # a third writer beside the recorder's own
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    tracing.flush()
    [shard] = os.listdir(traced)
    with open(os.path.join(traced, shard)) as f:
        recs = [json.loads(line) for line in f]  # a torn line would not parse
    assert recs[0]["kind"] == "header"
    assert sum(r["kind"] == "header" for r in recs) == 1
    for tag in "ab":  # nothing lost, each thread's spans in its order
        assert [r["name"] for r in recs[1:] if r["name"][0] == tag] == [
            f"{tag}{i}" for i in range(n)]


def test_reconfigure_ends_the_writer_and_a_session_without_a_shard_has_none(
        tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    assert not _writer_threads()
    tracing.start()
    tracing.event("in memory")
    assert tracing.recorder().path is None and not _writer_threads()
    tracing.stop()
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", str(tmp_path / "w"))
    tracing.reconfigure()
    tracing.start()
    tracing.event("to a shard")
    assert len(_writer_threads()) == 1
    tracing.stop()
    tracing.reconfigure()
    assert not _writer_threads()


def test_a_process_that_leaves_by_os_exit_loses_at_most_its_last_second(tmp_path):
    import subprocess
    import sys

    d = str(tmp_path / "exit")
    code = (
        "import os, time\n"
        "from areal_tpu.base import tracing\n"
        "for i in range(20): tracing.event(f'early{i}')\n"
        "time.sleep(1.6)\n"
        "tracing.event('late')\n"
        "os._exit(0)\n"
    )
    env = dict(os.environ, AREAL_RL_TRACE="1", AREAL_RL_TRACE_DIR=d)
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=60,
                   cwd=os.path.dirname(os.path.dirname(os.path.dirname(__file__))))
    names = [s["name"] for s in _load_spans(d)]
    # no atexit hook ran; what was older than a second is there
    assert names == [f"early{i}" for i in range(20)]


# ---------------------------------------------------------------------------
# Transport metadata propagation
# ---------------------------------------------------------------------------


def test_request_reply_stream_propagates_ctx(
    traced, tmp_name_resolve, experiment_context
):
    exp, trial = experiment_context
    master = rrs.make_master_stream(exp, trial)
    worker = rrs.make_worker_stream(exp, trial, "model_worker/0")
    try:
        with tracing.span("master.step") as ctx:
            [rid] = master.request(["model_worker/0"], "mfc", [{"x": 1}])
        req = worker.poll(block=True, timeout_ms=5000)
        got = tracing.extract(req.trace_ctx)
        assert got is not None
        assert got.trace_id == ctx.trace_id
        assert got.span_id == ctx.span_id
        worker.reply_to(req, data=None)
        master.poll(rid, block=True, timeout=10)
    finally:
        master.close()
        worker.close()


def test_push_pull_stream_propagates_and_strips_ctx(traced):
    puller = pps.ZMQJsonPuller(host="127.0.0.1")
    pusher = pps.ZMQJsonPusher("127.0.0.1", puller.port)
    try:
        with tracing.span("episode") as ctx:
            pusher.push({"ids": ["a"], "v": 2})
        got = puller.pull(timeout_ms=5000)
        # Payload intact, reserved key stripped, ctx surfaced.
        assert got == {"ids": ["a"], "v": 2}
        assert puller.last_trace_ctx is not None
        assert puller.last_trace_ctx.trace_id == ctx.trace_id
    finally:
        pusher.close()
        puller.close()


def test_push_pull_disabled_has_no_ctx(untraced):
    puller = pps.ZMQJsonPuller(host="127.0.0.1")
    pusher = pps.ZMQJsonPusher("127.0.0.1", puller.port)
    try:
        pusher.push({"k": 1})
        got = puller.pull(timeout_ms=5000)
        assert got == {"k": 1}
        assert puller.last_trace_ctx is None
    finally:
        pusher.close()
        puller.close()


# ---------------------------------------------------------------------------
# Aggregation + validation
# ---------------------------------------------------------------------------


def test_validate_catches_dangling_parent(tmp_path):
    shard_path = tmp_path / "w0.1.jsonl"
    shard_path.write_text(
        "\n".join(
            [
                json.dumps(
                    {
                        "kind": "header", "worker": "w0", "pid": 1,
                        "anchor_wall_ns": 10**18, "anchor_mono_ns": 10**9,
                    }
                ),
                json.dumps(
                    {
                        "kind": "span", "name": "orphan", "trace": "t1",
                        "span": "s1", "parent": "NO_SUCH_SPAN",
                        "start_ns": 10**9, "end_ns": 10**9 + 100,
                    }
                ),
            ]
        )
        + "\n"
    )
    shards = rl_trace.load_shards(str(tmp_path))
    problems = rl_trace.validate(shards)
    assert any("dangling parent" in p for p in problems)


def test_dangling_parent_waived_when_ring_overflowed(tmp_path):
    """A shard that RECORDED ring-buffer drops may legitimately have
    dangling parents (the oldest spans were dropped by design): validate
    marks them waived and the merge script exits 0."""
    import subprocess
    import sys

    shard_path = tmp_path / "w0.1.jsonl"
    shard_path.write_text(
        "\n".join(
            [
                json.dumps(
                    {
                        "kind": "header", "worker": "w0", "pid": 1,
                        "anchor_wall_ns": 10**18, "anchor_mono_ns": 10**9,
                    }
                ),
                json.dumps({"kind": "dropped", "count": 5}),
                json.dumps(
                    {
                        "kind": "span", "name": "orphan", "trace": "t1",
                        "span": "s1", "parent": "DROPPED_SPAN",
                        "start_ns": 10**9, "end_ns": 10**9 + 100,
                    }
                ),
            ]
        )
        + "\n"
    )
    shards = rl_trace.load_shards(str(tmp_path))
    problems = rl_trace.validate(shards)
    assert problems and all(
        p.startswith(rl_trace.WAIVED_PREFIX) for p in problems
    )
    r = subprocess.run(
        [sys.executable, "scripts/merge_rl_trace.py", str(tmp_path)],
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr


def test_merge_script_exits_nonzero_on_dangling_ref(tmp_path):
    import subprocess
    import sys

    shard_path = tmp_path / "w0.1.jsonl"
    shard_path.write_text(
        json.dumps(
            {
                "kind": "span", "name": "x", "trace": "t", "span": "s",
                "parent": "missing", "start_ns": 1, "end_ns": 2,
            }
        )
        + "\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/merge_rl_trace.py", str(tmp_path)],
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 1
    assert "dangling parent" in r.stderr


def test_merge_and_reports_end_to_end(traced):
    # A miniature rollout timeline recorded in-process: episode ->
    # chunk -> buffer residency -> train step consuming the trace.
    ep = tracing.start_span("rollout.episode", qid="q0")
    with tracing.use_ctx(ep.ctx):
        with tracing.span("gen.chunk", server="s0", reprefill_tokens=12):
            pass
        tracing.event("gen.interrupted", qid="q0")
    t0 = tracing.now_ns()
    tracing.record_span(
        "buffer.wait", t0, t0 + 5_000_000, ctx=ep.ctx,
        version_start=1, version_end=2, train_step=3, rpc="actor_train",
    )
    ep.end(accepted=True)
    with tracing.span(
        "master.mfc.actor_train", itype="train_step",
        consumed_traces=[ep.ctx.trace_id],
    ):
        pass
    tracing.flush()

    shards = rl_trace.load_shards(traced)
    assert rl_trace.validate(shards) == []
    merged = rl_trace.merge_to_chrome(shards)
    events = merged["traceEvents"]
    slices = [e for e in events if e.get("ph") == "X"]
    flows = [e for e in events if e.get("ph") in ("s", "t", "f")]
    assert {e["name"] for e in slices} >= {
        "rollout.episode", "gen.chunk", "buffer.wait", "master.mfc.actor_train",
    }
    assert flows, "expected flow events stitching the rollout trace"
    # Derived reports.
    hist = rl_trace.staleness_histogram(shards)
    assert hist == {2: 1}  # train_step 3 - version_start 1
    phases = rl_trace.phase_latency(shards)
    assert phases["interrupted_reprefill"]["tokens"] == 12
    assert phases["buffer_wait"]["count"] == 1
    summary = rl_trace.summarize(traced)
    assert "overlap_score" in summary
    report = rl_trace.format_report(shards)
    assert "staleness histogram" in report
    assert "overlap score" in report
