"""From a profiler trace to numbers: device busy and idle time, device
time by op category, the heaviest ops, and idle gaps by what the host did.

The yardstick's copy of the sound arithmetic in
`areal_tpu/utils/trace_analysis.py` (interval union, idle = window minus
union, ops classed by HLO name), reading the profiler's `.xplane.pb`
with `jax.profiler.ProfileData` instead of the Chrome dump, and counting
an op's *self* time (its duration minus its children's) so that a
`while` does not count its body twice.

A trace here is plain data, so that a small recorded one can be kept
with the tests:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, long_name], ...]}]}]}
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

# First match wins (a fusion whose name mentions attention is attention).
CATEGORY_KEYS: List[Tuple[str, Tuple[str, ...]]] = [
    ("attention", ("flash_attention", "splash", "attention", "mha",
                   "paged_attn", "paged_decode", "flash_attn")),
    ("collective", ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute", "collective-broadcast", "psum",
                    "ppermute", "send", "recv")),
    ("gemm", ("dot", "conv", "matmul", "einsum", "megacore_fusion")),
    ("memory", ("copy", "transpose", "dynamic-update-slice", "dynamic-slice",
                "broadcast", "concatenate", "reshape", "pad", "slice",
                "gather", "scatter", "convert", "bitcast", "memset",
                "infeed", "outfeed", "tuple", "iota")),
    ("fusion", ("fusion", "custom-call", "custom_call", "loop", "while")),
]
CATEGORIES = [c for c, _ in CATEGORY_KEYS] + ["misc"]
ANNOTATION_PREFIX = "bench/"
WINDOW_ANNOTATION = "bench/trace_window"
# Device-plane lines that restate the op line at another grain.
SHORT_GAP_NS = 20e3  # gaps under 20 us are summed, not labelled one by one
_NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "Launch Stats")


_OPCODE_RE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9_\-]*)\(")
_SUFFIX_RE = re.compile(r"(\.\d+)+$")


def op_label(name: str) -> Tuple[str, str]:
    """(instruction name, opcode) of a device event. On a TPU the event's
    name is the whole HLO instruction, `%name.7 = type opcode(operands)`;
    elsewhere it is just the name."""
    if " = " not in name:
        return name.lstrip("%"), ""
    left, right = name.split(" = ", 1)
    m = _OPCODE_RE.search(right)
    return left.strip().lstrip("%"), (m.group(1) if m else "")


def base_name(instr: str) -> str:
    """`fusion.12` and `fusion.13` are one kind of op in a top-ten."""
    return _SUFFIX_RE.sub("", instr)


def categorize(name: str, long_name: str = "") -> str:
    """By the instruction's own name and opcode (never its operands: every
    fusion takes a bitcast or a copy-done as input) and what the profiler
    says of it."""
    instr, opcode = op_label(name)
    s = f"{instr} {opcode} {long_name}".lower()
    for cat, keys in CATEGORY_KEYS:
        if any(k in s for k in keys):
            return cat
    return "misc"


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: List[Tuple[str, float, float, str]]) -> List[Tuple[str, float, str]]:
    """(name, self_ns, long_name) per event of one line: duration minus
    the time its direct children cover. Events of a line nest properly."""
    out = []
    stack: List[List[Any]] = []  # [name, end, self, long_name]
    for name, start, dur, long_name in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][1]:
            n, _, s, ln = stack.pop()
            out.append((n, max(0.0, s), ln))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur, long_name])
    while stack:
        n, _, s, ln = stack.pop()
        out.append((n, max(0.0, s), ln))
    return out


def find_xplane(trace_dir: str) -> str:
    c = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    if not c:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return c[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    """The profiler's file as the plain structure above."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                long_name = ""
                if pl.name.startswith("/device:"):
                    try:
                        long_name = " ".join(
                            str(v) for k, v in e.stats
                            if k in ("hlo_category", "tf_op"))[:200]
                    except Exception:
                        long_name = ""
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            long_name])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def _device_op_events(plane) -> List[Tuple[str, float, float, str]]:
    lines = [l for l in plane["lines"] if l["name"] == "XLA Ops"]
    if not lines:
        lines = [l for l in plane["lines"] if l["name"] not in _NOT_OP_LINES]
    return [tuple(e) for l in lines for e in l["events"] if e[2] > 0]


def reduce_trace(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """busy_s and window_s as the contract's `device` wants them (busy is
    the union of device op intervals, averaged over the devices that ran
    anything; the window is the `bench/trace_window` annotation, else the
    span of all device events), self time by category, the heaviest ops,
    and idle seconds by what the host was doing. None when no operation
    ran on a device."""
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    host_events = [
        (e[0], e[1], e[1] + e[2], ln["name"])
        for p in trace["planes"] if p["name"].startswith("/host:")
        for ln in p["lines"] for e in ln["events"] if e[2] > 0
    ]
    win = [h for h in host_events if h[0] == WINDOW_ANNOTATION]
    per_device = []
    cat_ns = {c: 0.0 for c in CATEGORIES}
    op_ns: Dict[str, float] = {}
    gaps_by_label: Dict[str, float] = {}
    for plane in devices:
        evs = _device_op_events(plane)
        if not evs:
            continue
        if win:
            w0, w1 = win[0][1], win[0][2]
        else:
            w0 = min(e[1] for e in evs)
            w1 = max(e[1] + e[2] for e in evs)
        iv = [(max(e[1], w0), min(e[1] + e[2], w1)) for e in evs
              if e[1] < w1 and e[1] + e[2] > w0]
        busy_iv = merged(iv)
        busy = sum(e - s for s, e in busy_iv)
        per_device.append(dict(device=plane["name"], busy_s=busy / 1e9,
                               window_s=(w1 - w0) / 1e9, n_ops=len(evs)))
        for name, self_ns, long_name in self_times(evs):
            cat_ns[categorize(name, long_name)] += self_ns
            key = base_name(op_label(name)[0])[:80]
            op_ns[key] = op_ns.get(key, 0.0) + self_ns
        if len(per_device) == 1:  # gaps of the first device that ran
            index = _HostIndex(host_events, w0, w1)
            edges = [w0] + [x for s, e in busy_iv for x in (s, e)] + [w1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 - g0 >= SHORT_GAP_NS:
                    label = index.label(g0, g1)
                elif g1 - g0 > 0:
                    label = f"gaps under {SHORT_GAP_NS / 1e3:.0f} us"
                else:
                    continue
                gaps_by_label[label] = gaps_by_label.get(label, 0.0) + (g1 - g0)
    if not per_device:
        return None
    n = len(per_device)
    total_self = sum(cat_ns.values()) or 1.0
    return dict(
        busy_s=sum(d["busy_s"] for d in per_device) / n,
        window_s=sum(d["window_s"] for d in per_device) / n,
        per_device=per_device,
        category_share={c: v / total_self for c, v in cat_ns.items()},
        category_s={c: v / 1e9 / n for c, v in cat_ns.items()},
        device_ops=[[k, v / 1e9] for k, v in
                    sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / 1e9] for k, v in
                   sorted(gaps_by_label.items(), key=lambda kv: -kv[1])[:top]],
    )


class _HostIndex:
    """Host events binned in time, to label many gaps quickly."""

    BIN_NS = 5e6

    def __init__(self, host_events, w0, w1):
        self.w0 = w0
        self.bins: Dict[int, List[Tuple[str, float, float]]] = {}
        for name, s, e, _line in host_events:
            if e <= w0 or s >= w1 or name == WINDOW_ANNOTATION:
                continue
            for b in range(self._bin(max(s, w0)), self._bin(min(e, w1)) + 1):
                self.bins.setdefault(b, []).append((name, s, e))

    def _bin(self, t):
        return int((t - self.w0) // self.BIN_NS)

    def label(self, g0: float, g1: float) -> str:
        """`<innermost bench span at the gap's middle>|<the other host
        event that overlaps the gap most>`."""
        mid = (g0 + g1) / 2
        bench, bench_dur = "-", float("inf")
        other, other_ov = "-", 0.0
        seen = set()
        for b in range(self._bin(g0), self._bin(g1) + 1):
            for ev in self.bins.get(b, ()):
                if ev in seen:
                    continue
                seen.add(ev)
                name, s, e = ev
                if e <= g0 or s >= g1:
                    continue
                if name.startswith(ANNOTATION_PREFIX):
                    if s <= mid < e and e - s < bench_dur:
                        bench, bench_dur = name[len(ANNOTATION_PREFIX):], e - s
                else:
                    ov = min(e, g1) - max(s, g0)
                    if ov > other_ov:
                        other, other_ov = name, ov
        return f"{bench}|{other[:60]}"
