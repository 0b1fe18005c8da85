"""What a process says about the jax environment it actually ran in.

A chip belongs to one process and an `auto` dispatch picks its
implementation at trace time, so neither the launcher nor a reader of
the config can know which devices a worker owned, which attention kernel
it compiled, or how much HBM it peaked at. Each chip-holding worker says
so itself, once per fact, as a log line `areal-ran {json}` that
`parse_ran` reads back (chip_smoke.py, tests).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List

from areal_tpu.base import logging, tracing

logger = logging.getLogger("ran")

RAN_TAG = "areal-ran"

_lock = threading.Lock()
_said: set = set()


def say(kind: str, **fields: Any) -> None:
    """Log one fact about what ran, once per distinct content."""
    line = json.dumps({"kind": kind, **fields}, sort_keys=True)
    with _lock:
        if line in _said:
            return
        _said.add(line)
    logger.info(f"{RAN_TAG} {line}")


def say_dispatch(kind: str, requested: str, ran: str, why: str, **shape) -> None:
    """Say what a trace-time dispatch resolved to, and warn when it left
    the device's kernels on a TPU: `reference` is an O(T^2) einsum and
    `xla` a gather, which nobody would otherwise notice."""
    import jax

    say(kind, requested=requested, ran=ran, why=why, **shape)
    if (
        ran != requested
        and ran in ("reference", "xla")
        and jax.default_backend() == "tpu"
    ):
        logger.warning(f"{kind} {requested!r} runs {ran!r} on a TPU for {shape}: {why}")


def parse_ran(text: str) -> List[Dict[str, Any]]:
    """The facts `say` wrote into a captured log, in order."""
    out = []
    for line in text.splitlines():
        _, tag, payload = line.partition(RAN_TAG + " ")
        if tag:
            out.append(json.loads(payload))
    return out


def report_devices(worker: str) -> None:
    """Say which devices this process owns (its whole local view: the
    launcher shows a worker only its own chips), and start keeping the
    records of what jax traces, lowers and compiles (`tracing.builds`).
    On a TPU the native host ops must be in use, not their Python
    fallbacks."""
    import jax

    tracing.watch_builds()
    from areal_tpu.ops import host_ops

    devs = jax.local_devices()
    if devs[0].platform == "tpu":
        native = host_ops.require_native()
    else:
        native = host_ops.native_available()
    say(
        "devices",
        worker=worker,
        pid=os.getpid(),
        platform=devs[0].platform,
        device_kind=devs[0].device_kind,
        count=len(devs),
        ids=[d.id for d in devs],
        coords=[list(getattr(d, "coords", ())) for d in devs],
        visible_chips=os.environ.get("TPU_VISIBLE_CHIPS"),
        native_host_ops=native,
    )


def report_usage(worker: str) -> None:
    """Say the peak HBM of every local device and the seconds jax spent
    building programs so far: tracing, lowering, compiling and loading
    from its cache (call after work that should be accounted)."""
    from areal_tpu.base import monitor

    build_ns = sum(b["end_ns"] - b["start_ns"] for b in tracing.builds())
    say(
        "usage",
        worker=worker,
        peak_hbm_bytes=monitor.device_peak_bytes(),
        compile_s=round(build_ns / 1e9, 1),
    )
