"""Model FLOP/s utilisation in percent of a sparse-attention expert
stack with a share of the experts: the operations the window's training
work requires (`benchmark/flops_dsa.py`, by part) over the window's time,
the chips used and the chip's published bf16 peak.

As `flops_rate_mla` does: the window is whole passes over a traffic
file's pool, found by its squared lengths a token; the cells the
indexers scored and an exact choice keeps, the pairs held and the cells
the loss head ran come from the traced pass (`train.index_cells`,
`train.index_selected`, `train.moe_pairs_held`, `train.head_cells` over
`train.tokens`), scaled to the window's tokens. None where the
configuration has no indexer, the program has no such counters (a
program without one, as this PR's parent), or the run has no window."""

from benchmark import flops_dsa
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    names = ("train.index_cells", "train.index_selected", "train.moe_pairs_held",
             "train.head_cells")
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or "sa_config" not in hf or any(n not in c for n in names)):
        return None
    if window_pool_lengths(w) is None:
        return None
    a_token = lambda name: c[name] / c["train.tokens"] * w["tokens"]
    need = flops_dsa.train_flops(hf, w["tokens"], *(a_token(n) for n in names))["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
