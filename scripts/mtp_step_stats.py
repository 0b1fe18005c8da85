#!/usr/bin/env python3
"""A benchmark cell run exactly as `benchmark/run.py` runs it, with the
prediction module's stats of every train step printed beside the run's
log: the runner keeps a step's loss, gradient norm and update norm, and
the module's `mtp_loss` and `mtp_accept` are not among them.

    python scripts/mtp_step_stats.py --workload joyai-d6e16-train-ppo-long --seed 7 --trace 2

Takes `benchmark/run.py`'s arguments and prints its last line. Before it,
a line a `PPOActorInterface.train_step` (`mtp step <n>: {...}`: warm
steps first, then the window's, then the traced pass's), and on stderr
one line `mtp passes: ...` with the mean `mtp_loss` of each pass over the
pool.
The wrapper reads what the step returns and changes nothing of it."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # first: its import starts the run's clock

from areal_tpu.interfaces import ppo

KEEP = ("loss", "mtp_loss", "mtp_accept", "grad_norm")
SEEN = []


def main(keep=KEEP, label="mtp", a_pass="mtp_loss") -> int:
    """Run the cell, printing `<label> step <n>: {<the stats in keep>}` a
    train step and, on stderr, the mean of `a_pass` a pass over the pool
    (`scripts/indexer_step_stats.py` calls it with the indexers' names)."""
    inner = ppo.PPOActorInterface.train_step

    def train_step(self, *a, **k):
        stats = inner(self, *a, **k)
        row = {key.split("/")[-1]: float(v) for key, v in stats.items()
               if key.split("/")[-1] in keep}
        SEEN.append(row)
        print(f"{label} step {len(SEEN) - 1}: {json.dumps(row)}", flush=True)
        return stats

    ppo.PPOActorInterface.train_step = train_step
    rc = run.main()
    if SEEN and a_pass in SEEN[0]:
        from benchmark import manifest

        cell = sys.argv[sys.argv.index("--workload") + 1]
        pool = int(manifest.load_cell(cell)["traffic_file"]["pool_batches"])
        loss = [r[a_pass] for r in SEEN]
        # on stderr: the contract's line stays the last of stdout
        print(f"{label} passes (mean {a_pass}, warm pass first): " + json.dumps(
            [sum(loss[i:i + pool]) / len(loss[i:i + pool]) for i in range(0, len(loss), pool)]),
            file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
