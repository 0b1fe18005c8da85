"""Request-scoped distributed tracing for the RL system plane, and the
one runtime control over it and the device profiler.

AReaL's headline claims (rollout/train overlap, staleness-gated
admission, cheap interruption resumption) are timeline claims. This
module records *RL-level* spans — one rollout's life across the rollout
worker, gserver manager, generation server, reward verifier, buffer, and
trainer, and inside the trainer one PPO step down to each dispatch —
into per-worker JSONL shards that `areal_tpu/utils/rl_trace.py` merges
into one Chrome-trace/Perfetto timeline with flow links per rollout.

Two switches turn it on: the environment (`AREAL_RL_TRACE`, on from the
process's first call) and `start()` / `stop()`, callable in a running
process from any thread. `start(profile_dir)` is also the program's one
place that starts `jax.profiler` (`utils/profiling.py::maybe_profile`
and the benchmark's traced window both come through it); while the
profiler runs, every `span()` is mirrored into the device trace as a
`TraceAnnotation("areal/<name>")`, so host spans and device ops share
the trace's clock. `stop()` returns what was recorded since `start()`
from memory.

Apart from the spans, and whether or not they are on, the module keeps
the process's build records (`watch_builds()`, `builds()`): every
program jax traced, lowered, compiled or loaded from its persistent
cache, by name and shape, on the spans' clock.

Design constraints:

- Hard no-op by default: every public call starts with one cached
  boolean branch; the recorder object is never allocated unless
  AREAL_RL_TRACE is truthy or `start()` was called (pinned by
  tests/base/test_rl_tracing.py). The build records need no recorder:
  jax calls their listener only when it builds a program, which a
  steady step never does.
- A span never synchronises with the device: it reads the host clock
  twice and nothing else. Device time comes from the profiler.
- Thread-safe: spans are appended to a bounded ring buffer under a lock
  (overflow drops the OLDEST spans and counts them — tracing must never
  block or OOM the hot path). The thread that appends never writes: a
  writer thread of the recorder's own takes the buffer at 512 spans and
  at least once a second, so a process that leaves by `os._exit` loses
  its last second and no more; `flush()` and `stop()` return with
  everything on disk.
- Clock model: span timestamps are `time.monotonic_ns()` (immune to NTP
  steps within a process); the shard header carries one
  (wall_ns, monotonic_ns) anchor pair so the merger maps every shard
  onto the shared wall clock. Cross-process skew is therefore bounded by
  host clock sync, which is fine for millisecond-scale RL phases.
- Context propagation: a `SpanContext` (trace_id, span_id) travels in a
  contextvar within a process (asyncio tasks inherit it) and as a small
  dict (`inject()`/`extract()`) inside existing transport metadata — the
  request_reply_stream Payload, push/pull JSON, and the HTTP JSON bodies
  of the gserver manager and generation servers.

Environment knobs:

- AREAL_RL_TRACE=1          enable (anything not in {"", "0", "false"})
- AREAL_RL_TRACE_DIR=<dir>  shard root (default /tmp/areal_tpu/rl_trace);
                            a `start()`ed recorder writes a shard only
                            when this is set or AREAL_RL_TRACE is on
- AREAL_RL_TRACE_RING=<n>   ring-buffer capacity (default 65536 spans)

See docs/observability.md for the span model and how to read the merged
timeline.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import dataclasses
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional

from areal_tpu.base import env_registry

_ENV_ENABLE = "AREAL_RL_TRACE"
_ENV_DIR = "AREAL_RL_TRACE_DIR"
_ENV_RING = "AREAL_RL_TRACE_RING"
_DEFAULT_DIR = "/tmp/areal_tpu/rl_trace"
# The shard's writer takes the buffer at this many spans, and at least this often.
_FLUSH_EVERY = 512
_WRITE_EVERY_S = 1.0

# Cached enablement: None = not yet read from the environment. The hot
# path pays exactly one branch once this is a bool.
_ENABLED: Optional[bool] = None
# The recorder is allocated lazily and ONLY when enabled.
_REC: Optional["_Recorder"] = None
_REC_LOCK = threading.Lock()
# Worker label stamped on every span this process records (set from
# Worker.configure; falls back to "proc<pid>").
_WORKER: Optional[str] = None
# Experiment/trial scope for the DEFAULT shard dir: without it, reruns
# against the fixed default path would silently mix shards from earlier
# runs into every summary. An explicit AREAL_RL_TRACE_DIR wins — callers
# setting it own its freshness.
_SCOPE: Optional[str] = None

# The runtime control (start()/stop()). _SESSION is None outside a
# session, else what stop() will add to the recorder's part of its answer.
# _MIRROR is the profiler's TraceAnnotation class while this control has
# the profiler running, else None: span() tests it once to decide whether
# to mirror itself into the device trace.
_CTL_LOCK = threading.Lock()
_SESSION: Optional[Dict[str, Any]] = None
_MIRROR: Optional[type] = None
_MIRROR_PREFIX = "areal/"

# `drained()`'s pending mark: (monotonic_ns, the blocking span's name) from
# the moment this process last emptied the device until `fed()` ends the
# stretch as a `device.starved` span. One for the process: the device is.
_DRAINED: Optional[tuple] = None
_DRAINED_LOCK = threading.Lock()

_CTX_KEY = "__rl_trace__"

_current: contextvars.ContextVar[Optional["SpanContext"]] = (
    contextvars.ContextVar("areal_rl_trace_ctx", default=None)
)
# The attrs dict of the innermost open span(), for set_attrs().
_live_attrs: contextvars.ContextVar[Optional[Dict[str, Any]]] = (
    contextvars.ContextVar("areal_rl_trace_attrs", default=None)
)


@dataclasses.dataclass(frozen=True)
class SpanContext:
    """What crosses process/task boundaries: which trace, which parent."""

    trace_id: str
    span_id: str

    def to_dict(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}


def enabled() -> bool:
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = env_registry.get_bool(_ENV_ENABLE)
    return _ENABLED


def trace_dir() -> str:
    d = env_registry.get_str(_ENV_DIR)
    if d:
        return d
    if _SCOPE:
        return os.path.join(_DEFAULT_DIR, _SCOPE)
    return _DEFAULT_DIR


def configure_worker(
    name: str, experiment: str = "", trial: str = ""
) -> None:
    """Label this process's shard with the worker name (e.g.
    'rollout_worker/0') and scope the default shard dir by
    experiment/trial. Safe to call when tracing is disabled."""
    global _WORKER, _SCOPE
    if name:
        _WORKER = name
    if experiment and trial:
        _SCOPE = f"{experiment}__{trial}".replace("/", "_").replace(
            os.sep, "_"
        )


def reconfigure() -> None:
    """Re-read the environment (tests flip AREAL_RL_TRACE in-process;
    production workers inherit it at spawn and never need this). Ends a
    `start()`ed session, flushes and drops any live recorder."""
    global _ENABLED, _REC
    stop()
    with _REC_LOCK:
        if _REC is not None:
            _REC.close()
            # Drop the exit hook with the recorder: repeated reconfigure
            # cycles (tests) must not accumulate callbacks that try to
            # flush into deleted tmp dirs at interpreter exit.
            atexit.unregister(_REC.flush)
        _REC = None
        _ENABLED = None


def now_ns() -> int:
    return time.monotonic_ns()


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class _Recorder:
    """Bounded ring buffer of span dicts + the JSONL shard's writer, the
    counters, and between `start()` and `stop()` the session: the same
    spans kept in memory (bounded like the ring) for `stop()` to return.
    Without a shard (`to_file` false: started at run time with no
    AREAL_RL_TRACE_DIR) the session is all there is, and no thread.

    With a shard, `append` only appends: a daemon thread takes the buffer
    when it holds `_FLUSH_EVERY` spans and at least every `_WRITE_EVERY_S`
    seconds, and serialises and writes it outside the buffer's lock."""

    def __init__(self, worker: str, to_file: bool = True):
        self.worker = worker
        self.capacity = env_registry.get_int(_ENV_RING)
        self._buf: List[Dict] = []
        self._lock = threading.Lock()
        self.n_dropped = 0
        self.counters: Dict[str, float] = {}
        self._session: Optional[List[Dict]] = None
        self._session_dropped = 0
        self.anchor_wall_ns = time.time_ns()
        self.anchor_mono_ns = time.monotonic_ns()
        self.path: Optional[str] = None
        self._header_written = False
        # The writer's alone: whoever writes the shard (the writer thread,
        # a caller of `flush()`) holds it from the buffer's swap to the
        # write's end, so batches reach the file whole and in order.
        self._write_lock = threading.Lock()
        self._wake = threading.Event()
        self._closed = False
        self._writer: Optional[threading.Thread] = None
        if to_file:
            d = trace_dir()
            os.makedirs(d, exist_ok=True)
            safe = worker.replace("/", "_").replace(os.sep, "_")
            self.path = os.path.join(d, f"{safe}.{os.getpid()}.jsonl")
            self._writer = threading.Thread(
                target=self._write_loop, name="rl-trace-writer", daemon=True)
            self._writer.start()

    def begin_session(self) -> None:
        with self._lock:
            self._session, self._session_dropped = [], 0
            self.counters = {}

    def end_session(self) -> Dict[str, Any]:
        with self._lock:
            spans, self._session = self._session or [], None
            return {"spans": spans, "counters": dict(self.counters),
                    "dropped": self._session_dropped}

    def count(self, name: str, n: float) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def append(self, rec: Dict) -> None:
        with self._lock:
            if self._session is not None:
                if len(self._session) >= self.capacity:
                    drop = self.capacity // 2
                    del self._session[:drop]
                    self._session_dropped += drop
                self._session.append(rec)
            if self.path is None:
                return
            if len(self._buf) >= self.capacity:
                # Overflow: drop the oldest half rather than blocking the
                # hot path or growing without bound.
                drop = self.capacity // 2
                del self._buf[:drop]
                self.n_dropped += drop
            self._buf.append(rec)
            wake = len(self._buf) % _FLUSH_EVERY == 0
        if wake:
            self._wake.set()

    def _write_loop(self) -> None:
        while not self._closed:
            self._wake.wait(_WRITE_EVERY_S)
            self._wake.clear()
            self.flush()

    def close(self) -> None:
        """Write what is held and end the writer thread."""
        self._closed = True
        self._wake.set()
        if self._writer is not None:
            self._writer.join(timeout=5.0)
        self.flush()

    def flush(self) -> None:
        """Everything appended so far is on disk when this returns."""
        if self.path is None:
            return
        with self._write_lock:
            with self._lock:
                batch, self._buf = self._buf, []
                dropped, self.n_dropped = self.n_dropped, 0
            header = None
            if not self._header_written:
                header = {
                    "kind": "header",
                    "worker": self.worker,
                    "pid": os.getpid(),
                    "anchor_wall_ns": self.anchor_wall_ns,
                    "anchor_mono_ns": self.anchor_mono_ns,
                }
            if header is None and not batch and not dropped:
                return
            lines = []
            if header is not None:
                lines.append(json.dumps(header, separators=(",", ":")))
            if dropped:
                lines.append(
                    json.dumps(
                        {"kind": "dropped", "count": dropped},
                        separators=(",", ":"),
                    )
                )
            for rec in batch:
                lines.append(
                    json.dumps(rec, separators=(",", ":"), default=str)
                )
            try:
                self._write_lines(lines)
                self._header_written = True
            except OSError:
                # Tracing must never take down the hot path: a full or
                # vanished /tmp loses this batch (counted as dropped); a
                # header that was in it goes out with the next batch that
                # is written, so the shard stays parseable.
                with self._lock:
                    self.n_dropped += dropped + len(batch)

    def _write_lines(self, lines: List[str]) -> None:
        with open(self.path, "a") as f:
            f.write("\n".join(lines) + "\n")


def _rec() -> _Recorder:
    global _REC
    if _REC is None:
        with _REC_LOCK:
            if _REC is None:
                to_file = env_registry.get_bool(_ENV_ENABLE) or bool(
                    env_registry.get_str(_ENV_DIR)
                )
                _REC = _Recorder(_WORKER or f"proc{os.getpid()}", to_file)
                atexit.register(_REC.flush)
    return _REC


def recorder() -> Optional[_Recorder]:
    """The live recorder, or None when tracing never recorded (the
    disabled-mode test pins exactly this)."""
    return _REC


# ---------------------------------------------------------------------------
# The runtime control
# ---------------------------------------------------------------------------


def start(profile_dir: Optional[str] = None) -> bool:
    """Turn span recording on in this running process, from any thread;
    with `profile_dir` also start `jax.profiler` there (host tracer level
    2, no Python tracer) and mirror every `span()` into its trace. The
    first thing written to that trace is the marker annotation
    `areal/clock_anchor`, whose span record carries the `monotonic_ns`
    read inside it: the marker's time in the `.xplane.pb` minus that
    value is the offset between the trace's clock and the spans'.

    Idempotent: a second `start()` before `stop()` changes nothing and
    returns False; True says this call started the session (and is the
    one that should stop it)."""
    global _ENABLED, _SESSION, _MIRROR, _DRAINED
    with _CTL_LOCK:
        if _SESSION is not None:
            return False
        _ENABLED = True
        _DRAINED = None  # a stretch that began before the session is not its
        session: Dict[str, Any] = {"profile_dir": None, "clock_anchor": None}
        try:
            _rec().begin_session()
            if profile_dir is not None:
                import jax

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # host spans are annotations
                opts.host_tracer_level = 2
                jax.profiler.start_trace(profile_dir, profiler_options=opts)
                session["profile_dir"] = profile_dir
                _MIRROR = jax.profiler.TraceAnnotation
        except BaseException:
            _ENABLED = env_registry.get_bool(_ENV_ENABLE)
            if _REC is not None:
                _REC.end_session()
            raise
        _SESSION = session
        if _MIRROR is not None:
            name = _MIRROR_PREFIX + "clock_anchor"
            with _MIRROR(name):
                mono = time.monotonic_ns()
            session["clock_anchor"] = {"name": name, "monotonic_ns": mono}
            record_span("clock_anchor", mono, monotonic_ns=mono)
        return True


def stop() -> Dict[str, Any]:
    """End the session `start()` began: recording goes back to what the
    environment says, the profiler stops if this control started it, and
    a pending `drained()` mark is dropped (the stretch would end in no
    session), and what was recorded since `start()` comes back from memory:
    `{"spans": [...], "counters": {...}, "dropped": n, "profile_dir": ...,
    "clock_anchor": {"name", "monotonic_ns"} | None, "builds": [...],
    "builds_dropped": n}`. The JSONL shard, where there is one, is
    flushed too. `builds` is `builds()`: every build record of the
    process so far, those from before `start()` too (set-up's; a reader
    tells them from the session's by the clock). Without a session: the
    same dict, empty but for the builds."""
    global _ENABLED, _SESSION, _MIRROR, _DRAINED
    with _CTL_LOCK:
        _DRAINED = None
        if _SESSION is None:
            return {"spans": [], "counters": {}, "dropped": 0,
                    "profile_dir": None, "clock_anchor": None,
                    "builds": builds(), "builds_dropped": _BUILDS_DROPPED}
        session, _SESSION = _SESSION, None
        _ENABLED = env_registry.get_bool(_ENV_ENABLE)
        try:
            if _MIRROR is not None:
                _MIRROR = None
                import jax

                jax.profiler.stop_trace()
        finally:
            rec = _rec()
            out = rec.end_session()
            rec.flush()
        return {**out, **session, "builds": builds(),
                "builds_dropped": _BUILDS_DROPPED}


def count(name: str, n: float = 1) -> None:
    """Add to a counter kept in the recorder (`stop()` returns them)."""
    if not enabled():
        return
    _rec().count(name, n)


def set_attrs(**attrs: Any) -> None:
    """Add attributes to the innermost open `span()` of this thread or
    task: for what is only known once the work is under way. Decided by
    the span, not by the switch: a span opened while recording was on
    keeps its attributes when `stop()` falls in its middle (it may be
    recorded by the next session)."""
    live = _live_attrs.get()
    if live is not None:
        live.update(attrs)


def flush() -> None:
    if _REC is not None:
        _REC.flush()


# ---------------------------------------------------------------------------
# Context propagation
# ---------------------------------------------------------------------------


def current() -> Optional[SpanContext]:
    if not enabled():
        return None
    return _current.get()


def inject() -> Optional[Dict[str, str]]:
    """Current context as a transport-safe dict (None when disabled or
    outside any span)."""
    if not enabled():
        return None
    ctx = _current.get()
    return ctx.to_dict() if ctx is not None else None


def extract(d: Any) -> Optional[SpanContext]:
    """Rebuild a SpanContext from `inject()` output (tolerates None /
    junk — transport metadata is best-effort)."""
    if not enabled() or not isinstance(d, dict):
        return None
    tid, sid = d.get("trace_id"), d.get("span_id")
    if not tid or not sid:
        return None
    return SpanContext(trace_id=str(tid), span_id=str(sid))


def inject_into(meta: Dict[str, Any]) -> Dict[str, Any]:
    """Return a copy of a transport dict carrying the current context
    under a reserved key (the input dict is never mutated; returned
    unchanged when disabled or outside any span)."""
    if not enabled():
        return meta
    ctx = inject()
    if ctx is not None:
        meta = {**meta, _CTX_KEY: ctx}
    return meta


def inject_ctx_into(
    meta: Dict[str, Any], ctx: Optional[SpanContext]
) -> Dict[str, Any]:
    """Explicit-context variant of `inject_into` for callers holding a
    ManualSpan's context instead of relying on the contextvar."""
    if not enabled() or ctx is None:
        return meta
    return {**meta, _CTX_KEY: ctx.to_dict()}


def extract_from(meta: Any) -> Optional[SpanContext]:
    """Pop and rebuild a context placed by `inject_into` (pops even when
    present-but-disabled so payloads stay clean)."""
    if not isinstance(meta, dict):
        return None
    d = meta.pop(_CTX_KEY, None)
    return extract(d)


def set_current(ctx: Optional[SpanContext]) -> None:
    """Set the current context without scoping — ONLY for code that owns
    its execution context outright (an asyncio Task's body: the Task's
    context copy dies with it, so there is nothing to restore)."""
    if not enabled() or ctx is None:
        return
    _current.set(ctx)


@contextlib.contextmanager
def use_ctx(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Run a block with `ctx` as the current context (no-op on None)."""
    if not enabled() or ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


# ---------------------------------------------------------------------------
# Recording
# ---------------------------------------------------------------------------


def _record(
    name: str,
    start_ns: int,
    end_ns: int,
    trace_id: str,
    span_id: str,
    parent_id: Optional[str],
    attrs: Dict[str, Any],
) -> None:
    rec = {
        "kind": "span",
        "name": name,
        "trace": trace_id,
        "span": span_id,
        "parent": parent_id,
        "start_ns": start_ns,
        "end_ns": end_ns,
        "tid": threading.get_ident() & 0xFFFF,
    }
    if attrs:
        rec["attrs"] = attrs
    _rec().append(rec)


@contextlib.contextmanager
def span(
    name: str, ctx: Optional[SpanContext] = None, **attrs: Any
) -> Iterator[Optional[SpanContext]]:
    """Record a span around the block; the block runs with the new span
    as the current context (children nest automatically).

    `ctx` overrides the parent (e.g. a context extracted from transport
    metadata). Without a parent, the span starts a NEW trace. Yields the
    span's own context (None when disabled) so callers can stash it.

    While `start(profile_dir)` has the profiler running, the span is also
    a `TraceAnnotation("areal/<name>")` on its thread's line of the device
    trace. `record_span` and `ManualSpan` (ended later, perhaps on another
    thread) are not mirrored: an annotation must open and close on one
    thread.
    """
    if not enabled():
        yield None
        return
    mirror = _MIRROR
    ann = mirror(_MIRROR_PREFIX + name) if mirror is not None else None
    parent = ctx if ctx is not None else _current.get()
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        trace_id, parent_id = _new_id(), None
    me = SpanContext(trace_id=trace_id, span_id=_new_id())
    token = _current.set(me)
    attrs_token = _live_attrs.set(attrs)
    if ann is not None:
        ann.__enter__()
    t0 = time.monotonic_ns()
    try:
        yield me
    finally:
        t1 = time.monotonic_ns()
        if ann is not None:
            ann.__exit__(None, None, None)
        _live_attrs.reset(attrs_token)
        _current.reset(token)
        _record(name, t0, t1, trace_id, me.span_id, parent_id, attrs)


class ManualSpan:
    """A span opened now and ended later (possibly from another task/
    thread) — for lifetimes that don't nest in one call frame, like a
    rollout episode or an HTTP request handled across callbacks. `ctx`
    is the span's OWN context: hand it to children / inject it."""

    __slots__ = ("name", "ctx", "parent_id", "start_ns", "attrs", "_done")

    def __init__(self, name: str, parent: Optional[SpanContext], attrs: Dict):
        if parent is not None:
            trace_id, self.parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, self.parent_id = _new_id(), None
        self.name = name
        self.ctx = SpanContext(trace_id=trace_id, span_id=_new_id())
        self.start_ns = time.monotonic_ns()
        self.attrs = dict(attrs)
        self._done = False

    def end(self, **attrs: Any) -> None:
        if self._done:
            return
        self._done = True
        self.attrs.update(attrs)
        _record(
            self.name, self.start_ns, time.monotonic_ns(),
            self.ctx.trace_id, self.ctx.span_id, self.parent_id, self.attrs,
        )


def start_span(
    name: str, ctx: Optional[SpanContext] = None, **attrs: Any
) -> Optional[ManualSpan]:
    """Open a ManualSpan under `ctx` (or the current context, or a new
    trace). Returns None when tracing is disabled — callers guard with
    `if ms is not None: ms.end()` or just `ms and ms.end()`."""
    if not enabled():
        return None
    parent = ctx if ctx is not None else _current.get()
    return ManualSpan(name, parent, attrs)


def record_span(
    name: str,
    start_ns: int,
    end_ns: Optional[int] = None,
    ctx: Optional[SpanContext] = None,
    **attrs: Any,
) -> None:
    """Record a span with explicit timestamps — for lifetimes that do not
    nest in one call frame (buffer residency: enqueue → consume). `ctx`
    is the PARENT (the recorded span gets a fresh span id under it);
    without one the span starts its own trace."""
    if not enabled():
        return
    parent = ctx if ctx is not None else _current.get()
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        trace_id, parent_id = _new_id(), None
    _record(
        name,
        int(start_ns),
        int(end_ns if end_ns is not None else time.monotonic_ns()),
        trace_id,
        _new_id(),
        parent_id,
        attrs,
    )


def event(name: str, ctx: Optional[SpanContext] = None, **attrs: Any) -> None:
    """Zero-duration marker (retries, evictions, drops)."""
    if not enabled():
        return
    t = time.monotonic_ns()
    record_span(name, t, t, ctx=ctx, **attrs)


def drained(after: str) -> None:
    """The caller has just returned from a blocking read of the newest
    thing it enqueued, so nothing of its own is on the device: note the
    clock and `after`, the name of the span that blocked
    (`train.fetch_stats`, `ppo.prep`, `fwd.fetch`). A second call before
    a `fed()` keeps the first mark: the device has been empty since then.
    """
    global _DRAINED
    if not enabled():
        return
    if _DRAINED is None:
        _DRAINED = (time.monotonic_ns(), after)


def fed(program: str) -> None:
    """An enqueue has just returned. If a `drained()` mark is pending,
    record the span `device.starved` from the mark to now under the
    current context (so it lands in the trace of the step that fed the
    device), attrs `after` (the mark's) and `until` = `program`, the
    engine's name for what was enqueued (`build_site`'s names), and clear
    the mark. Without a mark: nothing, after two branches.

    What the span is: the stretch in which this process had nothing
    queued on the device, as the host saw it. It starts a device-to-host
    copy and a thread's wake-up after the device truly ran dry, and ends
    at most one enqueue after it truly started again; neither call
    touches the device. A pause of the machine inside the blocking read
    is the device's idle time and no part of the span (nothing told the
    host the device was dry); an enqueue that returns late is the span's
    and not the device's."""
    global _DRAINED
    if not enabled() or _DRAINED is None:
        return
    with _DRAINED_LOCK:
        mark, _DRAINED = _DRAINED, None
    if mark is not None:
        record_span("device.starved", mark[0], after=mark[1], until=program)


# ---------------------------------------------------------------------------
# Builds: the programs jax traces, lowers, compiles or loads from its cache
# ---------------------------------------------------------------------------

# jax.monitoring's names (jax 0.9.0). Each of the three is a scalar event
# when its stage starts and a duration event when it ends;
# `backend_compile_duration` wraps the look-up in the persistent cache, so
# a hit ends as that event too, after `cache_hits` (and after
# `/jax/compilation_cache/cache_retrieval_time_sec`, the read and
# deserialisation alone, which lies inside it and makes no record).
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# The records, for the life of the process; overflow drops the oldest
# half and counts them, like the ring.
_BUILDS_CAP = 8192
_BUILDS: List[Dict[str, Any]] = []
_BUILDS_DROPPED = 0
_BUILDS_LOCK = threading.Lock()
_WATCHING = False


class _BuildThread(threading.local):
    """What the listeners keep between two events of one thread."""

    # Stages of a build open on this thread: jax traces the jitted
    # functions a function calls (every `jnp.where`) inside its trace,
    # an event each, and only the outermost is a record: its seconds
    # hold theirs.
    depth = 0
    # What the engine said its next dispatch runs (`build_site`).
    site: Optional[tuple] = None
    # The persistent cache's answer since the last backend compile:
    # True after a hit, False after a miss that wrote the entry.
    cache_hit: Optional[bool] = None


_BT = _BuildThread()


def watch_builds() -> None:
    """Start keeping build records (idempotent). Imports jax: call it
    where jax is in use anyway (the engine's module, `report_devices`)."""
    global _WATCHING
    with _BUILDS_LOCK:
        if _WATCHING:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_build_start)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_duration_secs_listener(_on_build_end)
        _WATCHING = True


def build_site(program: str, fun: Any, rows: Optional[int] = None,
               row_len: Optional[int] = None) -> None:
    """Say what this thread dispatches next: the engine's name for the
    program, the jitted function (a record takes the site only if jax
    reports that function's name) and the micro-batch's shape. One
    store; nothing reads it unless jax builds."""
    _BT.site = (program, fun.__name__, rows, row_len)


def builds() -> List[Dict[str, Any]]:
    """Every build record of the process so far, oldest first: `{"kind":
    "build", "phase": "trace" | "lower" | "compile" | "cache_load", "fun",
    "program", "rows", "row_len", "start_ns", "end_ns", "tid",
    "cache_hit"}` on `time.monotonic_ns`. Which step recompiled: the
    records whose clock falls inside it."""
    with _BUILDS_LOCK:
        return list(_BUILDS)


def builds_dropped() -> int:
    return _BUILDS_DROPPED


def _on_build_start(event: str, value: float, **_: Any) -> None:
    if event in _BUILD_PHASES:
        _BT.depth += 1


def _on_cache_event(event: str, **_: Any) -> None:
    if event == _CACHE_HIT_EVENT:
        _BT.cache_hit = True
    elif event == _CACHE_MISS_EVENT:
        _BT.cache_hit = False


def _on_build_end(event: str, duration_secs: float, **kw: Any) -> None:
    global _BUILDS_DROPPED
    end_ns = time.monotonic_ns()
    bt = _BT
    phase = _BUILD_PHASES.get(event)
    if phase is None:
        return
    bt.depth = max(bt.depth - 1, 0)
    fun, cache_hit = kw.get("fun_name"), None
    if phase == "compile":
        cache_hit, bt.cache_hit = bt.cache_hit, None
        if cache_hit:
            phase = "cache_load"
    if bt.depth:  # inside another stage of this thread, whose seconds hold these
        return
    program = rows = row_len = None
    site = bt.site
    if site is not None and fun in (site[1], f"jit({site[1]})"):
        program, _, rows, row_len = site
    rec = {
        "kind": "build", "phase": phase, "fun": fun, "program": program,
        "rows": rows, "row_len": row_len,
        "start_ns": end_ns - int(duration_secs * 1e9), "end_ns": end_ns,
        "tid": threading.get_ident() & 0xFFFF, "cache_hit": cache_hit,
    }
    with _BUILDS_LOCK:
        if len(_BUILDS) >= _BUILDS_CAP:
            drop = _BUILDS_CAP // 2
            del _BUILDS[:drop]
            _BUILDS_DROPPED += drop
        _BUILDS.append(rec)
    if not enabled():
        return
    # While recording is on the record is also a span under the span that
    # paid for it (`train.dispatch`, `fwd.dispatch`, ...), which says how
    # many programs it built.
    record_span(
        "jit." + phase, rec["start_ns"], end_ns,
        **{k: rec[k] for k in ("fun", "program", "rows", "row_len", "cache_hit")
           if rec[k] is not None},
    )
    count("jit.build_s", duration_secs)
    if phase in ("compile", "cache_load"):
        live = _live_attrs.get()
        if live is not None:
            live["built"] = live.get("built", 0) + 1
        count("jit.programs_compiled")
        if cache_hit is not None:
            count("jit.cache_hits" if cache_hit else "jit.cache_misses")
