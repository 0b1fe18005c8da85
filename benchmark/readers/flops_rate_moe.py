"""Model FLOP/s utilisation in percent of a stack of layer kinds with a
share of the experts: the operations the window's training work requires
(`benchmark/flops_moe.py`: window-limited attention per layer kind, the
held pairs from the program's own counter) over the window's time, the
chips used and the chip's published bf16 peak.

Window-limited attention needs the window's sequence lengths, and the
evidence carries only their sums. The window is whole passes over a
traffic file's pool, whose lengths come from the file's `lengths_seed`
(not from the run's seed): the pool is the one among `benchmark/traffic/`
whose squared lengths per token are the window's, and a window that
matches none reads nothing. The pairs held come from the traced pass
(`train.moe_pairs_held` over `train.tokens`), scaled to the window's
tokens. None where the program has no such counter (a program without
the expert share) or the run has no window."""

import json
import os

from benchmark import flops_moe, manifest, traffic as traffic_lib


def window_pool_lengths(work):
    """Sequence lengths of the pool this window's passes went over."""
    for name in manifest.list_names("traffic"):
        with open(os.path.join(manifest.BENCH_DIR, "traffic", f"{name}.json")) as f:
            params = json.load(f)
        if params.get("kind") != "ppo_batches":
            continue
        for rehearsal in (False, True):
            pool = traffic_lib.ppo_batch_lengths(traffic_lib.effective(params, rehearsal))
            lens = [s["prompt_len"] + s["resp_len"] for b in pool for s in b]
            per_token = sum(l * l for l in lens) / float(sum(lens))
            if abs(per_token * work["tokens"] - work["sum_len_sq"]) <= 1e-9 * work["sum_len_sq"]:
                return lens
    return None


def read(evidence):
    w = evidence.get("work")
    peak = (evidence.get("peaks") or {}).get("bf16_flops_per_s")
    c = (evidence.get("program") or {}).get("counters") or {}
    if (not w or not peak or not w.get("elapsed_s") or not c.get("train.tokens")
            or "train.moe_pairs_held" not in c):
        return None
    lens = window_pool_lengths(w)
    if lens is None:
        return None
    passes = w["tokens"] / float(sum(lens))
    pairs = c["train.moe_pairs_held"] / c["train.tokens"] * float(sum(lens))
    need = passes * flops_moe.train_flops(evidence["hf_config"], lens, pairs)["total"]
    return 100.0 * need / w["elapsed_s"] / (evidence["chips"] * peak)
