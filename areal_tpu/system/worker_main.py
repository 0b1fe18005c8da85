"""Standalone worker entry point for cluster launches.

Counterpart of the reference's `python -m realhf.apps.remote worker`
(realhf/apps/remote.py — what SLURM srun lines execute on every node).
The ClusterController (system/controller.py) writes each worker's config
as a pickle into the run's spool directory (shared filesystem on real
clusters) and submits this module through the scheduler client; discovery
then happens via name_resolve (typically the 'kv' TCP service, which
needs no shared FS).

    python -m areal_tpu.system.worker_main \
        --worker-type model_worker --config /spool/model_worker_0.pkl \
        --name-resolve '{"backend": "kv", "address": "10.0.0.2:2379"}'
"""

from __future__ import annotations

import argparse
import json
import os
import pickle


def main(argv=None):
    ap = argparse.ArgumentParser(description="areal_tpu worker process")
    ap.add_argument("--worker-type", required=True)
    ap.add_argument("--config", required=True, help="pickled worker config path")
    ap.add_argument("--name-resolve", required=True,
                    help="JSON kwargs for name_resolve.reconfigure")
    args = ap.parse_args(argv)

    from areal_tpu.base import name_resolve

    name_resolve.reconfigure(**json.loads(args.name_resolve))

    with open(args.config, "rb") as f:
        config = pickle.load(f)

    from areal_tpu.system import load_worker

    cls = load_worker(args.worker_type)
    w = cls()
    w.configure(
        config,
        experiment_name=config.experiment_name,
        trial_name=config.trial_name,
        worker_name=config.worker_name,
    )
    w.run()


if __name__ == "__main__":
    main()
