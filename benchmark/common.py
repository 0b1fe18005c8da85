"""What every runner needs from the process: the device, the compile
counter, host-clock spans mirrored into the profiler's trace, the traced
window, and peak memory."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from benchmark import trace_reduce


class NoChip(RuntimeError):
    """The machine cannot run this cell as a measurement."""


def device_info(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The devices as jax reports them; fails unless this is the chip the
    cell asks for (or the run is the explicit CPU walk-through)."""
    import jax

    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=min(len(devs), chips))
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise NoChip(f"jax reports platform {info['platform']!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chip(s), jax sees {len(devs)}")
    return info


class CompileCounter:
    """Programs jax had to build in this process: every lowering of a new
    program (whether XLA then compiled it or the persistent cache had it)
    with the function's name, and the seconds of backend compiles. Inside
    the measured window there must be no lowering."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowerings: List[tuple] = []  # (time, function name)
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration_secs: float, **kw):
        if event == self.LOWER:
            self.lowerings.append((time.monotonic(), str(kw.get("fun_name", "?"))))
        elif event == self.COMPILE:
            self.compile_s += duration_secs

    def between(self, t0: float, t1: float) -> List[str]:
        return [name for t, name in self.lowerings if t0 <= t <= t1]

    def compile_seconds(self) -> float:
        return self.compile_s


class Spans:
    """Host-clock spans kept in memory; each is also written into the
    profiler's trace as `bench/<name>` so idle gaps can be laid to it."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax

        row = dict(name=name, start=time.monotonic(), end=None, **attrs)
        with jax.profiler.TraceAnnotation(trace_reduce.ANNOTATION_PREFIX + name):
            try:
                yield row
            finally:
                row["end"] = time.monotonic()
                self.rows.append(row)


class TracedWindow:
    """The profiler and the program's own spans over a stretch of work:
    part of the measured window (`--trace 1`) or a pass after it
    (`--trace 2`). Both are switched by the program's one control,
    `areal_tpu.base.tracing.start` / `stop`, which nothing here touches
    unless `enabled`. `program` is what `stop()` returned (spans,
    counters, the clock anchor)."""

    def __init__(self, out_dir: str, enabled: bool):
        self.dir = os.path.join(out_dir, "trace")
        self.enabled = enabled
        self.active = False
        self._ann = None
        self.t0 = self.t1 = None
        self.program: Optional[Dict[str, Any]] = None

    def start(self):
        if not self.enabled or self.active:
            return
        import jax

        from areal_tpu.base import tracing

        tracing.start(profile_dir=self.dir)
        self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        self.active = True

    def stop(self):
        if not self.active:
            return
        from areal_tpu.base import tracing

        self.t1 = time.monotonic()
        self._ann.__exit__(None, None, None)
        self.program = tracing.stop()
        self.active = False

    def reduce(self) -> Optional[Dict[str, Any]]:
        if not self.enabled or self.t1 is None:
            return None
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))
        # Keep an outline (what planes and lines there were, and a few
        # events of each) and drop the raw trace: it is large.
        summary = dict(planes=[
            dict(name=p["name"], lines=[
                dict(name=l["name"], n=len(l["events"]),
                     first=[[str(e[0])[:160], e[1], e[2], e[3]]
                            for e in l["events"][:40]])
                for l in p["lines"]])
            for p in trace["planes"]])
        with open(os.path.join(self.dir, "trace_outline.json"), "w") as f:
            json.dump(summary, f, indent=1)
        shutil.rmtree(os.path.join(self.dir, "plugins"), ignore_errors=True)
        return trace_reduce.reduce_trace(trace)


def peak_memory(chips: int) -> Dict[str, Any]:
    """memory_stats() of the fullest of the chips used."""
    import jax

    best: Dict[str, Any] = {}
    for d in jax.devices()[:chips]:
        m = d.memory_stats() or {}
        if m.get("peak_bytes_in_use", 0) >= best.get("peak_bytes_in_use", -1):
            best = dict(m)
    return best
