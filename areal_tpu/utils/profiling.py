"""Per-MFC profiling: jax.profiler trace capture (started through
`base/tracing.py::start`, the program's one control over the profiler)
+ wall-time breakdown.

TPU counterpart of the reference's env-gated per-MFC torch profiler
(realhf/system/model_worker.py:136-139, __maybe_profile_rpc:828-909) and
its chrome-trace post-processing (realhf/base/monitor.py:404-610): on TPU
the trace IS the XLA/TensorBoard profile produced by `jax.profiler`, so
there is no kernel-classification re-parser — point TensorBoard (or
xprof) at the dump directory instead.

Environment knobs (mirroring the reference's `REAL_DUMP_TRACE`):
- AREAL_DUMP_TRACE=1       enable jax.profiler trace capture per MFC
- AREAL_TRACE_DIR=<dir>    dump root (default /tmp/areal_tpu/traces)
- AREAL_TRACE_STEPS=a,b,c  only capture these global steps (default: all)
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, Iterator, List, Optional

from areal_tpu.base import env_registry, tracing
from areal_tpu.base import logging as areal_logging

logger = areal_logging.getLogger("profiling")


def trace_enabled() -> bool:
    return env_registry.get_bool("AREAL_DUMP_TRACE")


def _trace_dir() -> str:
    # NOT AREAL_RL_TRACE_DIR: this is the jax-profiler dump root; the
    # RL span recorder has its own tree (see env_registry docs).
    return env_registry.get_str("AREAL_TRACE_DIR")


def _step_selected(step: Optional[int]) -> bool:
    sel = env_registry.get_str("AREAL_TRACE_STEPS")
    if not sel or step is None:
        return True
    try:
        return step in {int(s) for s in sel.split(",") if s}
    except ValueError:
        return True


@contextlib.contextmanager
def maybe_profile(name: str, step: Optional[int] = None) -> Iterator[None]:
    """Capture a jax.profiler trace around the block when enabled.

    The dump lands in `<AREAL_TRACE_DIR>/<name>/step<step>/` in the
    TensorBoard profile format (open with `tensorboard --logdir` or
    xprof), with the block's `tracing.span`s mirrored into it as
    `areal/<name>` annotations. No-op unless AREAL_DUMP_TRACE is set.
    """
    if not trace_enabled() or not _step_selected(step):
        yield
        return
    sub = name if step is None else os.path.join(name, f"step{step}")
    path = os.path.join(_trace_dir(), sub)
    os.makedirs(path, exist_ok=True)
    logger.info(f"capturing jax.profiler trace for {name!r} -> {path}")
    # Through the program's one control over the profiler, so the MFC's
    # spans are in the dump too. A session someone else started (an
    # operator's, an enclosing MFC's) is left to them.
    started = tracing.start(profile_dir=path)
    try:
        yield
    finally:
        if started:
            tracing.stop()


class TimeMarks:
    """Wall-time breakdown recorder (reference time-mark parsing,
    realhf/base/monitor.py): label spans of work, export totals.

    Used by the model worker to ship a per-hook/per-MFC wall-time
    breakdown back to the master in the reply stats
    (reference model_function_call.py:460-472).
    """

    def __init__(self) -> None:
        self._totals: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def record(self, label: str) -> Iterator[None]:
        t0 = time.monotonic()
        try:
            yield
        finally:
            dt = time.monotonic() - t0
            self._totals[label] = self._totals.get(label, 0.0) + dt
            self._counts[label] = self._counts.get(label, 0) + 1

    def export(self, prefix: str = "timeperf", reset: bool = True) -> Dict[str, float]:
        out = {f"{prefix}/{k}": v for k, v in self._totals.items()}
        if reset:
            self._totals.clear()
            self._counts.clear()
        return out
