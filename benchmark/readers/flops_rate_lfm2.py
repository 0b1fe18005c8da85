"""Model FLOP/s utilisation in percent of one chip's share of LFM2-8B-A1B
(gated short convolutions and attention layers over a share of sigmoid-routed
experts): the operations the window's training work requires
(`benchmark/flops_lfm2.py`, by part) over the window's time, the chips used
and the chip's published bf16 peak.

As `flops_rate_mellum` does: the window is whole passes over a traffic
file's pool, found by its squared lengths a token (attention's cells need
the lengths, and the evidence carries their sums alone); the pairs held and
the cells the loss head ran come from the traced pass
(`train.moe_pairs_held`, `train.head_cells` over `train.tokens`), scaled to
the pool's tokens. None where the configuration is no `lfm2_moe`, the
program has no such counters (a program without the family, as this PR's
parent, does not run the cell at all), or the run has no window."""

from benchmark import flops_lfm2
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or hf.get("model_type") != "lfm2_moe" or "train.conv_cells" not in c
            or "train.moe_pairs_held" not in c or "train.head_cells" not in c):
        return None
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    pool = float(sum(lens))
    a_token = lambda name: c[name] / c["train.tokens"] * pool
    need = w["tokens"] / pool * flops_lfm2.train_flops(
        hf, lens, a_token("train.moe_pairs_held"), a_token("train.head_cells"))["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
