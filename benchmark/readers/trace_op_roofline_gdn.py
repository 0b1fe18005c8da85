"""The delta rule's share of its roofline in percent, one decay a head,
from the traced pass: the least time the chip could take for the rule's
own work (the larger of its operations over the published bf16 peak and
its bytes over the published HBM bandwidth: `benchmark/flops_gdn.gdn_work`,
from the configuration's shapes and the program's `train.kda_cells`, the
same whatever implements the rule) over the device seconds of the ops
named in `needs` (by the start of their HLO base name). `calls`: how many
times a train step runs that pass (2 forward under full remat);
`backward`: the backward pass's work.

As `trace_op_roofline_kda`: it sees `trace["device_ops"]`, the ten
heaviest ops of the traced pass; None unless an op of every name in
`needs` is among them, and None where the configuration has no
`linear_num_value_heads`, the program counted nothing (a program without
this rule, as this PR's parent) or the device has no published peaks."""

from benchmark import flops_gdn


def read(evidence, needs, calls=1, backward=False):
    ops = (evidence.get("trace") or {}).get("device_ops") or []
    hf = evidence.get("hf_config") or {}
    peaks = evidence.get("peaks") or {}
    c = (evidence.get("program") or {}).get("counters") or {}
    found = [[s for name, s in ops if str(name).startswith(prefix)] for prefix in needs]
    if (not all(found) or "linear_num_value_heads" not in hf or not c.get("train.kda_cells")
            or not peaks.get("bf16_flops_per_s") or not peaks.get("hbm_bytes_per_s")):
        return None
    need = flops_gdn.gdn_work(hf, c["train.kda_cells"], calls, backward)
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(map(sum, found))
