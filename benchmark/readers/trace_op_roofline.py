"""A kernel's share of its HBM roofline in percent, from the traced
pass: the bytes the kernel's work must move (a function of
`benchmark/flops_sambay.py` named by `bytes`, from the configuration's
shapes and the program's own `train.tokens` alone, so that it reads the
same work whatever implements it) over the chip's published HBM
bandwidth, over the device seconds of the ops named in `needs` (by the
start of their HLO base name: a kernel's forward and its backward).

It sees `trace["device_ops"]`, the ten heaviest ops of the traced pass
(what `trace_reduce` keeps): None unless every op of `needs` is among
them (the share of half the time would read too high), and None where
the configuration has no such kernel, the program counted no tokens or
the device has no published peak."""

from benchmark import flops_sambay


def read(evidence, needs, bytes):
    ops = (evidence.get("trace") or {}).get("device_ops") or []
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("hbm_bytes_per_s")
    tokens = ((evidence.get("program") or {}).get("counters") or {}).get("train.tokens")
    found = [[s for name, s in ops if str(name).startswith(prefix)] for prefix in needs]
    if not all(found) or not peak or not tokens or hf.get("model_type") != "phi4flash":
        return None
    need = getattr(flops_sambay, bytes)(hf, tokens)
    return 100.0 * need / peak / sum(map(sum, found))
