"""Model FLOP/s utilisation in percent of a hybrid stack (state-space,
attention and expert layers, one part a layer) with a share of the
experts: the operations the window's training work requires
(`benchmark/flops_hybrid.py`, by part; the held pairs from the program's
own counter) over the window's time, the chips used and the chip's
published bf16 peak.

As `flops_rate_moe` does: the window is whole passes over a traffic
file's pool, found by its squared lengths a token; the pairs held come
from the traced pass (`train.moe_pairs_held` over `train.tokens`), scaled
to the pool's tokens. None where the configuration is no such stack, the
program has no such counter, or the run has no window."""

from benchmark import flops_hybrid
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or "hybrid_override_pattern" not in hf):
        return None
    pairs_a_token = 0.0
    if "E" in hf["hybrid_override_pattern"]:
        if "train.moe_pairs_held" not in c:
            return None
        pairs_a_token = c["train.moe_pairs_held"] / c["train.tokens"]
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    passes = w["tokens"] / float(sum(lens))
    need = passes * flops_hybrid.train_flops(
        hf, lens, pairs_a_token * float(sum(lens)))["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
