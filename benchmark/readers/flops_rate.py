"""Model FLOP/s utilisation in percent: the operations the window's
training work requires (`benchmark/flops.py`, from shapes; recompute not
counted) over the window's time, the chips used and the chip's published
bf16 peak (`benchmark/peaks.json`)."""

from benchmark import flops


def read(evidence):
    w = evidence.get("work")
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    if not w or not peak or not w.get("elapsed_s"):
        return None
    need = flops.train_flops_from_sums(
        evidence["hf_config"], w["tokens"], w["sum_len_sq"])
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
