"""Multi-host SPMD training: the pod-scale launch path.

On TPU pods the right architecture is NOT the single-host master/worker
dance scaled up — it is one identical SPMD process per host over a global
mesh: `jax.distributed` forms the world (coordinator elected through the
name_resolve rendezvous, areal_tpu/parallel/distributed.py), every host
builds the same global mesh, iterates the same deterministic dataloader,
and dispatches the same jitted train step; GSPMD inserts every cross-host
collective over ICI/DCN.

Reference counterpart: realhf/training/utils.py:62-226 +
realhf/scheduler/slurm/utils.py (816 LoC of srun/NCCL group wiring). The
reference must explicitly construct NCCL subgroups per parallelism
dimension; on TPU the runtime owns the fabric, so multi-host launch
reduces to (1) rendezvous, (2) same program everywhere — which is what
this module does.

`launch_multihost` starts one process per host through the scheduler
client: LocalSchedulerClient simulates a pod on one machine (each "host"
gets its own process with a slice of CPU devices — the test topology);
a cluster scheduler registered under `make_scheduler` submits the same
per-host commands to real pods.

Usage (single-machine simulation of 2 hosts):
    python -m training.multihost n_hosts=2 mesh_spec=d2f2 \
        experiment_name=mh trial_name=t0 dataset.path=/data/sft.jsonl \
        model.config='{"n_layers":2,...}' steps=4 out=/tmp/mh.json
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.cli_args import SFTExpConfig, apply_overrides
from areal_tpu.base import logging, name_resolve

logger = logging.getLogger("multihost")

_HOST_ENV = "AREAL_TPU_HOST_RANK"


def host_main(
    cfg: SFTExpConfig,
    host_rank: int,
    n_hosts: int,
    mesh_spec: str,
    steps: int,
    out_path: Optional[str] = None,
) -> Dict:
    """The per-host SPMD program: rendezvous, global mesh, lockstep SFT.

    Every host runs this exact function with only `host_rank` differing;
    determinism of the dataloader (same seed, same files) keeps the hosts
    dispatching identical programs, which is the SPMD contract.
    """
    from areal_tpu.parallel.distributed import setup_host_group

    if cfg.name_resolve_root:
        name_resolve.reconfigure("nfs", record_root=cfg.name_resolve_root)
    else:
        name_resolve.reconfigure("nfs")
    group = setup_host_group(
        cfg.experiment_name, cfg.trial_name, "trainer", host_rank, n_hosts
    )

    import jax
    import numpy as np

    from areal_tpu.api import data_api
    from areal_tpu.api.data_api import DatasetUtility, MicroBatchSpec
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.models.hf import load_hf_model
    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import init_params
    from areal_tpu.parallel.mesh import make_mesh
    import areal_tpu.datasets  # noqa: F401  (registry)
    from areal_tpu.experiments import common as C

    mesh = make_mesh(MeshSpec.parse(mesh_spec), jax.devices())
    logger.info(
        f"host {host_rank}/{n_hosts}: world={jax.process_count()} procs, "
        f"{jax.device_count()} devices, mesh={dict(mesh.shape)}"
    )

    m = cfg.model
    if m.path is not None:
        model_cfg, params = load_hf_model(m.path)
        tokenizer_path = cfg.tokenizer_path or m.path
    else:
        model_cfg = TransformerConfig(**(m.config or {}))
        params = init_params(model_cfg, jax.random.PRNGKey(cfg.seed))
        tokenizer_path = cfg.tokenizer_path
    tokenizer = (
        data_api.load_hf_tokenizer(tokenizer_path) if tokenizer_path else None
    )

    # Same dataset + same shuffle seed on every host => lockstep batches.
    ds = data_api.make_dataset(
        C.dataset_abstraction(cfg.dataset),
        DatasetUtility(seed=cfg.seed, dp_rank=0, world_size=1,
                       tokenizer=tokenizer),
    )
    loader = data_api.PackedDataLoader(
        ds, batch_size=cfg.train_batch_size, shuffle=True, seed=cfg.seed
    )

    eng = JaxTrainEngine(
        model_cfg, params, mesh=mesh,
        optimizer_config=m.optimizer,
        total_train_steps=max(steps, 1),
        remat=m.remat,
        row_len_multiple=m.row_len_multiple,
        max_row_len=m.max_row_len,
    )

    from areal_tpu.interfaces.sft import sft_loss_weight, sft_row_loss

    losses: List[float] = []
    for step in range(steps):
        batch, _ = loader.next_batch()
        st = eng.train_batch(
            batch, MicroBatchSpec(n_mbs=cfg.mb_spec_n_mbs), sft_row_loss,
            sft_loss_weight, version_steps=step, loss_name="sft",
        )
        losses.append(st["sft/loss"])
        logger.info(f"host {host_rank} step {step}: loss={st['sft/loss']:.4f}")

    result = {
        "host_rank": host_rank,
        "n_processes": jax.process_count(),
        "n_devices": jax.device_count(),
        "mesh": dict(mesh.shape),
        "losses": losses,
    }
    if out_path and jax.process_index() == 0:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f)
    return result


def launch_multihost(
    n_hosts: int,
    overrides: List[str],
    mesh_spec: str,
    steps: int,
    out_path: str,
    host_env: Optional[Dict[str, str]] = None,
    scheduler_mode: str = "local",
    timeout: float = 900.0,
):
    """Spawn one `training.multihost` process per host and wait.

    With scheduler_mode="local", hosts are subprocesses of this machine
    (pod simulation / tests); cluster schedulers registered under
    make_scheduler receive identical per-host submissions."""
    from areal_tpu.scheduler.client import make_scheduler

    sched = make_scheduler(scheduler_mode)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = []
    for rank in range(n_hosts):
        env = dict(host_env or {})
        env[_HOST_ENV] = str(rank)
        cmd = [
            sys.executable, "-m", "training.multihost",
            f"n_hosts={n_hosts}", f"mesh_spec={mesh_spec}",
            f"steps={steps}", f"out={out_path}",
        ] + list(overrides)
        names.append(sched.submit(f"host{rank}", cmd, env=env, cwd=repo_root))
    try:
        sched.wait(names, timeout=timeout)
    finally:
        sched.stop_all()
    if not out_path:
        return None  # hosts ran fine; nothing was asked to be collected
    with open(out_path) as f:
        return json.load(f)


def _parse_argv(argv: List[str]):
    meta = {"n_hosts": 1, "mesh_spec": "d1", "steps": 2, "out": ""}
    overrides = []
    for arg in argv:
        k, _, v = arg.partition("=")
        if k in ("n_hosts", "steps"):
            meta[k] = int(v)
        elif k in ("mesh_spec", "out"):
            meta[k] = v
        else:
            overrides.append(arg)
    cfg = SFTExpConfig()
    apply_overrides(cfg, overrides)
    return meta, cfg, overrides


if __name__ == "__main__":
    meta, cfg, overrides = _parse_argv(sys.argv[1:])
    rank_env = os.environ.get(_HOST_ENV)
    if rank_env is None:
        # Launcher role: fan out one process per host.
        launch_multihost(
            meta["n_hosts"], overrides, meta["mesh_spec"], meta["steps"],
            meta["out"],
        )
    else:
        host_main(
            cfg, int(rank_env), meta["n_hosts"], meta["mesh_spec"],
            meta["steps"], meta["out"],
        )
