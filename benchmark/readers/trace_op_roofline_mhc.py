"""A stream kernel's share of its roofline in percent, from the traced
pass: the least time the chip could take for the kernel's work (the
larger of its operations over the published bf16 peak and its bytes over
the published HBM bandwidth; a function of `benchmark/flops_mhc.py` named
by `work`, from the configuration's shapes and the program's own counters
`train.mhc_cells` and `train.mhc_loop_cells`) over the device seconds of
the ops named in `needs` (by the start of their HLO base name). Both
kernels are bound by their bytes, **and the bytes are those in and out of
the calls, which is more than crosses HBM where the compiler keeps a
band's operands in VMEM** (`benchmark/flops_mhc.py`): the share can read
over 100, so no metric of this reader is listed in `BENCHMARK.json`
until its work counts what crosses HBM.

As `trace_op_roofline_dsa`: it sees `trace["device_ops"]`, the ten
heaviest ops of the traced pass; None unless every op of `needs` is among
them, and None where the configuration has one stream, the program
counted nothing (a program without hyper-connections, as this PR's
parent) or the device has no published peaks."""

from benchmark import flops_mhc


def read(evidence, needs, work):
    ops = (evidence.get("trace") or {}).get("device_ops") or []
    hf = evidence.get("hf_config") or {}
    peaks = evidence.get("peaks") or {}
    c = (evidence.get("program") or {}).get("counters") or {}
    found = [[s for name, s in ops if str(name).startswith(prefix)] for prefix in needs]
    if (not all(found) or hf.get("hc_mult", 1) < 2 or not c.get("train.mhc_cells")
            or not peaks.get("bf16_flops_per_s") or not peaks.get("hbm_bytes_per_s")):
        return None
    need = getattr(flops_mhc, work)(hf, c)
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(map(sum, found))
