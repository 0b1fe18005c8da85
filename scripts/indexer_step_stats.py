#!/usr/bin/env python3
"""A benchmark cell run exactly as `benchmark/run.py` runs it, with the
indexers' stats of every train step printed beside the run's log: the
runner keeps a step's loss, gradient norm and update norm, and
`indexer_kl` (the KL's mean over layers and real tokens) and
`indexer_selected` (the share of scored cells chosen, the device's own
count; a mean over the step's minibatches of each one's share) are not
among them; nor `moe_pairs_held` and `moe_rows` (a minibatch's (token,
expert) pairs whose expert is held here and the rows the tiles that held
them ran, summed over layers; a mean over the step's minibatches), which
say what a step's expert layers cost.

    python scripts/indexer_step_stats.py --workload keye-d6e16-train-ppo-long --seed 7 --trace 2

Takes `benchmark/run.py`'s arguments and prints its last line. Before it,
a line a `PPOActorInterface.train_step` (`indexer step <n>: {...}`: warm
steps first, then the window's, then the traced pass's), and on stderr
one line `indexer passes: ...` with the mean `indexer_kl` of each pass
over the pool. The wrapper reads what the step returns and changes
nothing of it (`scripts/mtp_step_stats.py`'s wrapper, with other names)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mtp_step_stats  # first: its import of `benchmark.run` starts the run's clock

KEEP = ("loss", "indexer_kl", "indexer_selected", "grad_norm", "moe_pairs_held", "moe_rows")

if __name__ == "__main__":
    rc = mtp_step_stats.main(KEEP, "indexer", "indexer_kl")
    sys.stdout.flush()
    os._exit(rc)
