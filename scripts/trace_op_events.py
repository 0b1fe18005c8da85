#!/usr/bin/env python3
"""A device op's calls in a profiler trace, one line an HLO instruction:
how often it ran, for how long, and the bytes of its operands and results
by where the compiled program keeps them.

    python scripts/trace_op_events.py <dir with an .xplane.pb> --op mhc_mix mhc_coef_grad

`--op splash_pairs` lists a row alone's attention kernels, an instruction a
call site: `splash_pairs_fwd.N` and, since PR 51, the one backward kernel
`splash_pairs_bwd.N` (of whose results the float32 `[Hq, hd, T]` is the sums
of dq in passing, the other three dq, dk and dv; `splash_pairs_dq` and
`splash_pairs_dkv` in a trace from before).

On a TPU a device event's name is the whole HLO instruction, types and
layouts included, so the trace itself says which operands of a call live
in VMEM (`S(1)` in the layout) and which in HBM. A line:

    {"instr": "mhc_mix.112", "calls": 580, "total_s": 0.0131, "median_us": 22.4,
     "bytes": 66109440, "hbm_bytes": 0, "gb_per_s": 2950.1, "hbm_gb_per_s": 0.0}

`bytes` is what one call takes in and gives out, `hbm_bytes` the part whose
layout names no other memory space; `gb_per_s` and `hbm_gb_per_s` are those
over the median call. A call that moves more `bytes` a second than the
chip's HBM does is not moving them through HBM: that is how
`xing4-d5e8-train-ppo-8k`'s `mhc_mix` read over 100 % of an HBM roofline
counted from bytes in and out (PERF.md section 6, PR 47). The last line is
the sum over the instructions.

The benchmark drops the raw trace once it has reduced it
(`benchmark/common.TracedWindow.reduce`); a run of one's own under
`areal_tpu.base.tracing.start(profile_dir=...)` keeps it.
"""

import argparse
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce

_TYPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]\{([^}]*)\}")
_BYTES = {"pred": 1, "bf16": 2}


def _results_and_operands(text: str) -> str:
    """`%name = results opcode(operands), attributes` -> `results
    (operands)`: an attribute may list the operands' types once more
    (`operand_layout_constraints` of a custom call), which are no bytes."""
    left, sep, right = text.partition(" = ")
    if not sep:
        return text
    m = trace_reduce._OPCODE_RE.search(right)
    if not m:
        return right
    depth, end = 0, len(right)
    for i in range(m.end() - 1, len(right)):
        depth += (right[i] == "(") - (right[i] == ")")
        if depth == 0:
            end = i + 1
            break
    return right[:end]


def type_bytes(text: str):
    """(bytes of the results and operands of the instruction `text`,
    bytes of those whose layout names no memory space but the default,
    HBM)."""
    total = hbm = 0
    for dtype, dims, layout in _TYPE_RE.findall(_results_and_operands(text)):
        size = _BYTES.get(dtype) or int(dtype[1:]) // 8
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
        hbm += 0 if re.search(r"S\(\d+\)", layout) else size
    return total, hbm


def op_rows(trace, prefixes):
    by_instr = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        for name, _start, dur_ns, _long in trace_reduce._device_op_events(plane):
            instr, _ = trace_reduce.op_label(name)
            if not any(trace_reduce.base_name(instr).startswith(p) for p in prefixes):
                continue
            row = by_instr.setdefault(instr, dict(instr=instr, text=name, durs=[]))
            row["durs"].append(dur_ns)
    rows = []
    for row in sorted(by_instr.values(), key=lambda r: -sum(r["durs"])):
        total, hbm = type_bytes(row["text"])
        median_s = statistics.median(row["durs"]) / 1e9
        rows.append(dict(
            instr=row["instr"], calls=len(row["durs"]), total_s=sum(row["durs"]) / 1e9,
            median_us=median_s * 1e6, bytes=total, hbm_bytes=hbm,
            gb_per_s=total / median_s / 1e9, hbm_gb_per_s=hbm / median_s / 1e9))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--op", nargs="+", required=True,
                    help="the start of an HLO base name: mhc_mix, splash_pairs, fusion")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(args.trace_dir))
    rows = op_rows(trace, args.op)
    rows.append(dict(
        instr="all", calls=sum(r["calls"] for r in rows), total_s=sum(r["total_s"] for r in rows),
        bytes=sum(r["bytes"] * r["calls"] for r in rows),
        hbm_bytes=sum(r["hbm_bytes"] * r["calls"] for r in rows)))
    for r in rows:
        print(json.dumps(r), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
