"""Model FLOP/s utilisation in percent of a stack of Gated DeltaNet mixers
and gated attention over a share of the experts: the operations the
window's training work requires (`benchmark/flops_gdn.py`, by part) over
the window's time, the chips used and the chip's published bf16 peak.

As `flops_rate_kda` does: the window is whole passes over a traffic
file's pool, found by its squared lengths a token; the pairs held and the
cells the loss head ran come from the traced pass (`train.moe_pairs_held`,
`train.head_cells` over `train.tokens`), scaled to the pool's tokens. None
where the configuration has no `linear_num_value_heads`, the program has
no such counters (a program without this rule, as this PR's parent), or
the run has no window."""

from benchmark import flops_gdn
from benchmark.readers.flops_rate_moe import window_pool_lengths


def read(evidence):
    w = evidence.get("work")
    hf = evidence.get("hf_config") or {}
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or "linear_num_value_heads" not in hf or "train.kda_cells" not in c
            or "train.moe_pairs_held" not in c or "train.head_cells" not in c):
        return None
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    pool = float(sum(lens))
    a_token = lambda name: c[name] / c["train.tokens"] * pool
    need = w["tokens"] / pool * flops_gdn.train_flops(
        hf, lens, a_token("train.moe_pairs_held"), a_token("train.head_cells"))["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
