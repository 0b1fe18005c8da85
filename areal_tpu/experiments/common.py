"""Shared experiment-building helpers (reference experiments/common/)."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from areal_tpu.api.cli_args import (
    BaseExperimentConfig,
    DatasetConfig,
    ModelTrainEvalConfig,
)
from areal_tpu.api.config import (
    DatasetAbstraction,
    ModelAbstraction,
    ModelBackendAbstraction,
    ModelInterfaceAbstraction,
    ModelName,
    ModelShardID,
)
from areal_tpu.api.data_api import MicroBatchSpec
from areal_tpu.api.system_api import (
    MasterWorkerConfig,
    ModelShardSpec,
    ModelWorkerConfig,
)
from areal_tpu.base import logging
from areal_tpu.parallel.mesh import AllocationMode

logger = logging.getLogger("experiments")


def model_abstraction(m: ModelTrainEvalConfig, tokenizer_path: Optional[str],
                      is_critic: bool = False,
                      mesh_spec: Optional[str] = None,
                      device_ids: Optional[List[int]] = None,
                      ) -> ModelAbstraction:
    """``mesh_spec``/``device_ids`` (usually from ``train_mesh_for_worker``)
    place the model on its slice of the allocation; an explicit per-model
    ``m.mesh_spec`` always wins (the pre-PR-9 worker-local knob)."""
    args: Dict = dict(
        tokenizer_path=tokenizer_path or m.path,
        is_critic=is_critic or m.is_critic,
        dtype=m.dtype,
        mesh_spec=m.mesh_spec or mesh_spec,
    )
    if m.mesh_spec is None and device_ids is not None:
        args["device_ids"] = list(device_ids)
    if m.path and not m.init_from_scratch:
        args["model_path"] = m.path
    else:
        assert m.config is not None, "need model config for scratch init"
        args["config"] = _apply_moe_overrides(m, dict(m.config))
    return ModelAbstraction("tpu_transformer", args=args)


def _apply_moe_overrides(m: ModelTrainEvalConfig, config: Dict) -> Dict:
    """Overlay the flat moe_* CLI knobs onto the nested config['moe']
    block (TransformerConfig.__post_init__ coerces the dict to an
    MoEConfig). Setting a knob on a dense model (no 'moe' block) is a
    silently-ignored sweep bug — refuse it."""
    overrides = {
        "dispatch": m.moe_dispatch,
        "capacity_factor": m.moe_capacity_factor,
        "aux_loss_coef": m.moe_aux_loss_coef,
    }
    overrides = {k: v for k, v in overrides.items() if v is not None}
    if not overrides:
        return config
    if not config.get("moe"):
        raise ValueError(
            f"moe_* overrides {sorted(overrides)} set but the model "
            f"config has no 'moe' block — they would be silently ignored"
        )
    moe = dict(config["moe"]) if isinstance(config["moe"], dict) else (
        dataclasses.asdict(config["moe"])
    )
    moe.update(overrides)
    config["moe"] = moe
    return config


def train_mesh_for_worker(
    cfg: BaseExperimentConfig, worker_index: int, n_workers: int
) -> Tuple[Optional[str], Optional[List[int]]]:
    """(mesh_spec, device_ids) for one model worker's slice of the
    allocation's TRAIN partition — the system-layer wiring that makes
    `allocation_mode` actually drive sharded training.

    ``device_ids`` index the worker's OWN devices: the launcher gives
    each worker process only its chips (``worker_chips`` below), so the
    slice always starts at local device 0.

    - Single-host (train_n_hosts == 1): the train data axis splits
      across workers (each worker is one DP rank of the MFC layer);
      worker i gets a LOCAL (data/n_workers, fsdp, seq, tensor) mesh
      over its first ``size`` devices.
    - Multi-host (train_n_hosts > 1): every worker-host builds the
      GLOBAL train mesh over the jax.distributed world's devices
      (device_ids None = all); DP happens inside the mesh.
    - Returns (None, None) for single-device allocations or when the
      data axis doesn't divide the worker count (single-device mesh per
      worker).
    """
    try:
        alloc = AllocationMode.parse(cfg.allocation_mode)
    except (ValueError, AttributeError):
        return None, None
    ts = alloc.train_spec
    if ts.size <= 1:
        return None, None
    n_hosts = int(getattr(cfg, "train_n_hosts", 1) or 1)
    if n_hosts > 1:
        # One worker per host; the global mesh spans the distributed
        # world's devices, so no per-worker device slice applies.
        return str(ts), None
    if ts.data % max(1, n_workers) != 0:
        return None, None
    local = dataclasses.replace(ts, data=ts.data // max(1, n_workers))
    return str(local), list(range(local.size))


def worker_chips(cfg: BaseExperimentConfig, n_workers: int) -> Dict[str, List[int]]:
    """Chips of this host owned by each model worker and generation
    server, by worker name (``AllocationMode.worker_chips``). Empty when
    the allocation does not describe this launch: multi-host training
    (each worker owns its whole host) or worker counts the allocation's
    axes do not divide — on a TPU host the controller then refuses to
    start more than one chip-holding process."""
    if int(getattr(cfg, "train_n_hosts", 1) or 1) > 1:
        return {}
    try:
        return AllocationMode.parse(cfg.allocation_mode).worker_chips(
            int(getattr(cfg, "n_generation_servers", 0)), n_workers
        )
    except (ValueError, AttributeError) as e:
        logger.warning(
            f"allocation_mode={cfg.allocation_mode!r} gives no per-worker "
            f"chip assignment: {e}"
        )
        return {}


def backend_abstraction(m: ModelTrainEvalConfig, train: bool = True) -> ModelBackendAbstraction:
    if m.backend.startswith("mock"):
        return ModelBackendAbstraction(m.backend)
    name = "jax_train" if train else "jax_inference"
    args = dict(
        remat=m.remat,
        attn_impl=m.attn_impl,
        row_len_multiple=m.row_len_multiple,
        max_row_len=m.max_row_len,
        prefetch_depth=m.prefetch_depth,
        stats_fetch_interval=m.stats_fetch_interval,
    )
    if train:
        args["optimizer"] = dataclasses.asdict(m.optimizer)
    return ModelBackendAbstraction(name, args=args)


def dataset_abstraction(d: DatasetConfig) -> DatasetAbstraction:
    args = dict(d.args)
    if d.path is not None:
        args.setdefault("dataset_path", d.path)
    if d.max_length is not None and d.type_ in ("prompt_answer", "prompt", "rw_pair"):
        args.setdefault("max_length", d.max_length)
    return DatasetAbstraction(d.type_, args=args)


def mb_spec(cfg: BaseExperimentConfig, mfc=None) -> MicroBatchSpec:
    """Global micro-batch spec, optionally overridden per MFC
    (reference: each MFCConfig carries its own MicroBatchSpec)."""
    n_mbs = cfg.mb_spec_n_mbs
    max_tokens = cfg.mb_spec_max_tokens
    if mfc is not None:
        if mfc.n_mbs is not None:
            n_mbs = mfc.n_mbs
        if mfc.max_tokens_per_mb is not None:
            max_tokens = mfc.max_tokens_per_mb
    return MicroBatchSpec(n_mbs=n_mbs, max_tokens_per_mb=max_tokens)


def worker_names(n: int) -> List[str]:
    return [f"model_worker/{i}" for i in range(n)]


def resolve_n_workers(cfg: BaseExperimentConfig) -> int:
    """The local single-host launcher maps the allocation's train data axis
    onto model workers when n_model_workers is left at default. With
    train_n_hosts > 1 there is exactly one worker per host of the shared
    jax.distributed train mesh."""
    if int(getattr(cfg, "train_n_hosts", 1) or 1) > 1:
        return int(cfg.train_n_hosts)
    if cfg.n_model_workers > 1:
        return cfg.n_model_workers
    try:
        alloc = AllocationMode.parse(cfg.allocation_mode)
        return max(1, alloc.train_spec.data)
    except Exception:
        return cfg.n_model_workers


def base_model_worker(
    cfg: BaseExperimentConfig,
    index: int,
    n_workers: int,
    shards: List[ModelShardSpec],
    with_dataset: bool = True,
    stream_dataset: bool = False,
) -> ModelWorkerConfig:
    # Multi-host SPMD training: every worker-host iterates the SAME
    # dataset shard (dp_rank 0 of 1) so the hosts dispatch identical
    # global programs in lockstep — DP happens inside the shared mesh,
    # not across workers (training/multihost.py's contract, now at the
    # system layer).
    multihost = int(getattr(cfg, "train_n_hosts", 1) or 1) > 1
    return ModelWorkerConfig(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        worker_index=index,
        chips=worker_chips(cfg, n_workers).get(f"model_worker/{index}"),
        shards=shards,
        datasets=[dataset_abstraction(cfg.dataset)] if with_dataset else [],
        tokenizer_path=cfg.tokenizer_path,
        dataset_dp_rank=0 if multihost else index,
        dataset_dp_size=1 if multihost else n_workers,
        train_n_hosts=int(getattr(cfg, "train_n_hosts", 1) or 1),
        train_host_rank=index if multihost else 0,
        train_batch_size=cfg.train_batch_size,
        total_train_epochs=resolved_total_train_epochs(cfg),
        seed=cfg.seed,
        stream_dataset=stream_dataset,
        n_pullers=n_workers if stream_dataset else 1,
        weight_plane=bool(getattr(cfg, "gen_weight_plane", False)),
        weight_chunk_bytes=int(getattr(cfg, "gen_weight_chunk_mb", 8)) << 20,
        weight_wire_dtype=getattr(cfg, "gen_weight_wire_dtype", None),
    )


def dataset_line_count(dataset_cfg) -> int:
    """Number of usable samples in a jsonl prompt dataset (0 if unknown);
    used by async experiments to size epochs master-side. math_code_prompt
    datasets are counted through their own validator (invalid rows are
    dropped at load, so a raw line count would overstate the epoch)."""
    path = getattr(dataset_cfg, "path", None)
    if not path:
        return 0
    try:
        if getattr(dataset_cfg, "type_", None) == "math_code_prompt":
            from areal_tpu.datasets.math_code_prompt import load_metadata

            id2info, _ = load_metadata(path)
            return len(id2info)
        with open(path, "rb") as f:
            return sum(1 for line in f if line.strip())
    except (OSError, AssertionError):
        return 0


def resolved_total_train_epochs(cfg: BaseExperimentConfig) -> int:
    """One source of truth for the epoch budget. `cfg.total_train_epochs`
    is the documented knob (it already drives the LR schedule via
    FinetuneSpec); `exp_ctrl.total_train_epochs` defaults to None =
    inherit, and wins when set explicitly (including an explicit 1).
    Previously the master stopped on the exp_ctrl copy (default 1)
    regardless of the top-level field, so `total_train_epochs=3` trained
    one epoch with a 3-epoch LR schedule (ADVICE r1 finding a)."""
    if cfg.exp_ctrl.total_train_epochs is not None:
        return cfg.exp_ctrl.total_train_epochs
    return cfg.total_train_epochs


def base_master(cfg: BaseExperimentConfig, rpcs, model_topos, n_workers: int) -> MasterWorkerConfig:
    import dataclasses as _dc

    exp_ctrl = _dc.replace(
        cfg.exp_ctrl, total_train_epochs=resolved_total_train_epochs(cfg)
    )
    return MasterWorkerConfig(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        exp_ctrl=exp_ctrl,
        rpcs=rpcs,
        model_topos=model_topos,
        data_hosts=worker_names(n_workers),
        n_model_workers=n_workers,
        train_batch_size=cfg.train_batch_size,
        recover_mode=cfg.recover_mode,
    )
