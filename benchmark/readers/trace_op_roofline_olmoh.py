"""A delta-rule kernel's share of its roofline in percent, the rule at keys
and values of two widths (Olmo-Hybrid), from the traced pass: the least time
the chip could take for the kernel's own work (the larger of its operations
over the published bf16 peak and its bytes over the published HBM bandwidth:
`benchmark/flops_olmo_hybrid`'s `rule_work` or, with `work="taps"`,
`taps_work`, from the configuration's shapes and the program's counter
`cells`: `train.kda_cells` for the rule, `train.kda_taps_kernel_cells` for
the taps) over the device seconds of the ops named in `needs` (by the start of
their HLO base name). `calls`: how many times a train step runs that pass (2
forward under full remat); `backward`: the backward pass's work.

As `trace_op_roofline_gdn`: it sees `trace["device_ops"]`, the ten heaviest
ops of the traced pass; None unless an op of every name in `needs` is among
them, and None where the configuration is no `olmo_hybrid`, the program
counted nothing (a program without this rule, as this PR's parent) or the
device has no published peaks."""

from benchmark import flops_olmo_hybrid

_WORK = {"rule": flops_olmo_hybrid.rule_work, "taps": flops_olmo_hybrid.taps_work}


def read(evidence, needs, cells="train.kda_cells", work="rule", calls=1, backward=False):
    ops = (evidence.get("trace") or {}).get("device_ops") or []
    hf = evidence.get("hf_config") or {}
    peaks = evidence.get("peaks") or {}
    c = (evidence.get("program") or {}).get("counters") or {}
    found = [[s for name, s in ops if str(name).startswith(prefix)] for prefix in needs]
    if (not all(found) or hf.get("model_type") != "olmo_hybrid" or not c.get(cells)
            or not peaks.get("bf16_flops_per_s") or not peaks.get("hbm_bytes_per_s")):
        return None
    need = _WORK[work](hf, c[cells], calls, backward)
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(map(sum, found))
