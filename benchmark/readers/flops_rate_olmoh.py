"""Model FLOP/s utilisation in percent of a stack of Gated DeltaNet mixers
(keys and values of two widths) and plain attention layers under dense MLPs
(Olmo-Hybrid): the operations the window's training work requires
(`benchmark/flops_olmo_hybrid.py`, by part) over the window's time, the chips
used and the chip's published bf16 peak.

As `flops_rate_gdn` does: the window is whole passes over a traffic file's
pool, found by its squared lengths a token; the cells the loss head ran come
from the traced pass (`train.head_cells` over `train.tokens`), scaled to the
pool's tokens. None where the configuration is no `olmo_hybrid`, the program
has no such counters (a program without the family, as this PR's parent, does
not run the cell at all), or the run has no window."""

from benchmark import flops_olmo_hybrid
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or hf.get("model_type") != "olmo_hybrid" or "train.kda_cells" not in c
            or "train.head_cells" not in c):
        return None
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    pool = float(sum(lens))
    head_cells = c["train.head_cells"] / c["train.tokens"] * pool
    need = w["tokens"] / pool * flops_olmo_hybrid.train_flops(hf, lens, head_cells)["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
