"""A kernel's share of its roofline in percent, from the traced pass:
the least time the chip could take for the kernel's work (the larger of
its operations over the published bf16 peak and its bytes over the
published HBM bandwidth; a function of `benchmark/flops_dsa.py` named by
`work`, from the configuration's shapes and the program's own counters
`train.tokens`, `train.index_cells` and `train.index_selected` alone, so
that it reads the same work whatever implements it) over the device
seconds of the ops named in `needs` (by the start of their HLO base
name). `calls`: how often a layer of a train step runs the kernel (2
where full remat runs its forward pass again).

As `trace_op_roofline`: it sees `trace["device_ops"]`, the ten heaviest
ops of the traced pass; None unless every op of `needs` is among them,
and None where the configuration has no indexer, the program counted
nothing (a program without one, as this PR's parent) or the device has
no published peaks."""

from benchmark import flops_dsa


def read(evidence, needs, work, calls=1):
    ops = (evidence.get("trace") or {}).get("device_ops") or []
    hf = evidence.get("hf_config") or {}
    peaks = evidence.get("peaks") or {}
    c = (evidence.get("program") or {}).get("counters") or {}
    found = [[s for name, s in ops if str(name).startswith(prefix)] for prefix in needs]
    if (not all(found) or "sa_config" not in hf or not c.get("train.tokens")
            or not c.get("train.index_cells") or not c.get("train.index_selected")
            or not peaks.get("bf16_flops_per_s") or not peaks.get("hbm_bytes_per_s")):
        return None
    need = getattr(flops_dsa, work)(hf, c["train.tokens"], c["train.index_cells"],
                                    c["train.index_selected"], calls)
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(map(sum, found))
