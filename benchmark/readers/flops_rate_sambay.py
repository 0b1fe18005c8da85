"""Model FLOP/s utilisation in percent of a SambaY stack (`model_type`
phi4flash): the operations the window's training work requires
(`benchmark/flops_sambay.py`, by part, from shapes alone) over the
window's time, the chips used and the chip's published bf16 peak.

As `flops_rate_moe` does: the window is whole passes over a traffic
file's pool, found by its squared lengths a token. None where the
configuration is no such stack or the run has no window."""

from benchmark import flops_sambay
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    if (not w or not peak or not w.get("elapsed_s")
            or hf.get("model_type") != "phi4flash"):
        return None
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    passes = w["tokens"] / float(sum(lens))
    need = passes * flops_sambay.train_flops(hf, lens)["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
