"""Bounded background prefetch pipeline for the train/forward hot path.

The step loop's host side — FFD-pack rows, pad to the shape bucket,
`jax.device_put` — runs serially before every dispatch in the eager
path, so the device idles for exactly that long each step. AReaL's
design hides one plane's latency behind another's compute (async rollout
behind training); `HostPrefetcher` applies the same overlap one level
down: a single worker thread stages micro-batch i+1 (pack + H2D) while
the device runs step i, bounded by a depth-limited queue so host memory
and in-flight transfers can never run away.

Why one thread and not a pool: results must arrive in submission order
(gradient accumulation and `reorder_output` both assume it), and the
stage is dominated by numpy packing + the H2D call, which release the
GIL — one thread already achieves full overlap against device compute.

Telemetry contract (consumed by `JaxTrainEngine` and surfaced as
`perf/h2d_wait_ms` / `perf/dispatch_gap_ms`):
- `wait_ms`: total time the consumer blocked on an empty queue — the
  pack+transfer latency NOT hidden behind compute. Eager pipelines
  pay the full stage cost here; a healthy prefetched loop shows ~0.
- `stage_ms`: total time inside `stage_fn` (the work being hidden).
- `spans`: per-item (stage_start, stage_end, consumed_at) timestamps in
  seconds, so tests can assert overlap structurally (stage i+1
  started before item i was consumed) instead of racing wall clocks.

All of it is on the span recorder's clock (`time.monotonic_ns`), and
with `wait_span` set the two reads around the queue wait that feed
`wait_ms` are also recorded as a span of that name (base/tracing.py):
one measurement, two readers.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional, Tuple

from areal_tpu.base import tracing


class _Done:
    """Queue sentinel: the item stream is exhausted."""


class HostPrefetcher:
    """Run `stage_fn(item)` for each element of `items` on one background
    thread, delivering results in submission order through a bounded
    queue of `depth` slots (backpressure: the stage blocks once `depth`
    results are staged but unconsumed).

    Exceptions raised by `stage_fn` (or by the `items` iterator) are
    delivered to the consumer at the position they occurred and terminate
    the pipeline; remaining items are never staged.

    Use as an iterator, or call `get()` directly. Always `close()` (or
    exhaust) — exiting a consumer loop early without closing would leave
    the worker blocked on a full queue. Iteration closes on exhaustion
    and on exception; `close()` is idempotent.
    """

    def __init__(
        self,
        items: Iterable[Any],
        stage_fn: Callable[[Any], Any],
        depth: int = 2,
        name: str = "prefetch",
        wait_span: Optional[str] = None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = depth
        self._items = iter(items)
        self._stage = stage_fn
        self._wait_span = wait_span
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.wait_ms = 0.0
        self.stage_ms = 0.0
        self.n_staged = 0
        self.n_consumed = 0
        # (stage_start, stage_end, consumed_at) per item, consumption
        # order. consumed_at is filled by get().
        self.spans: List[Tuple[float, float, Optional[float]]] = []
        self._thread = threading.Thread(
            target=self._work, name=f"{name}-worker", daemon=True
        )
        self._thread.start()

    # -- worker side ---------------------------------------------------

    def _put(self, payload) -> bool:
        """Bounded put that aborts when the consumer closed early."""
        while not self._stop.is_set():
            try:
                self._q.put(payload, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _work(self):
        while not self._stop.is_set():
            try:
                item = next(self._items)
            except StopIteration:
                self._put(_Done)
                return
            except BaseException as e:  # iterator itself failed
                self._put((None, e, 0.0, 0.0))
                return
            t0 = time.monotonic_ns()
            try:
                res = self._stage(item)
            except BaseException as e:
                self._put((None, e, t0 / 1e9, time.monotonic_ns() / 1e9))
                return
            t1 = time.monotonic_ns()
            self.stage_ms += (t1 - t0) / 1e6
            self.n_staged += 1
            if not self._put((res, None, t0 / 1e9, t1 / 1e9)):
                return

    # -- consumer side -------------------------------------------------

    def get(self):
        """Next staged result in order; raises StopIteration when the
        stream is exhausted, or the original exception when the stage
        (or source iterator) failed at this position."""
        t0 = time.monotonic_ns()
        payload = self._q.get()
        now = time.monotonic_ns()
        self.wait_ms += (now - t0) / 1e6
        if self._wait_span is not None:
            tracing.record_span(self._wait_span, t0, now)
        if payload is _Done:
            self.close()
            raise StopIteration
        res, exc, s0, s1 = payload
        if exc is not None:
            self.close()
            raise exc
        self.spans.append((s0, s1, now / 1e9))
        self.n_consumed += 1
        return res

    def __iter__(self):
        while True:
            try:
                yield self.get()
            except StopIteration:
                return

    def close(self):
        """Stop the worker and release its queue slot; idempotent."""
        self._stop.set()
        # Drain so a worker blocked on put() observes the stop quickly.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    # -- telemetry -----------------------------------------------------

    def overlap_count(self) -> int:
        """Number of items whose staging started before the PREVIOUS
        item was consumed — the structural evidence that pack/H2D of
        micro-batch i+1 overlapped step i (no wall-clock ratios, so the
        check is stable under CI load)."""
        n = 0
        for i in range(1, len(self.spans)):
            prev_consumed = self.spans[i - 1][2]
            if prev_consumed is not None and self.spans[i][0] < prev_consumed:
                n += 1
        return n
