"""Experiment runner with a fault-tolerant relaunch loop.

Counterpart of the reference's launcher (realhf/apps/main.py:77-289 +
training/utils.py): run the experiment via the LocalController; on
worker/master failure, relaunch with recover_mode=auto up to
`recover_retries` times, resuming from the last recover checkpoint.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Optional, Type

from areal_tpu.api.cli_args import apply_overrides
from areal_tpu.base import constants, logging, name_resolve
from areal_tpu.experiments import make_experiment
from areal_tpu.system.controller import LocalController

logger = logging.getLogger("launcher")


def parse_args(cfg_cls: Type, argv=None):
    parser = argparse.ArgumentParser(
        description=f"areal_tpu launcher ({cfg_cls.__name__}). "
        "Overrides: dotted key=value pairs, e.g. actor.path=/ckpt lr=1e-5",
    )
    parser.add_argument("overrides", nargs="*", help="a.b.c=value overrides")
    parser.add_argument(
        "--help-config",
        action="store_true",
        help="list every dotted override path with type/default/help "
        "(the Hydra --help surface of the reference)",
    )
    args = parser.parse_args(argv)
    cfg = cfg_cls()
    if args.help_config:
        from areal_tpu.api.cli_args import format_options

        print(format_options(cfg))
        sys.exit(0)
    apply_overrides(cfg, args.overrides)
    return cfg


def run_experiment(experiment_type: str, cfg, worker_env: Optional[dict] = None) -> dict:
    """Build + run, relaunching with recovery on failure
    (reference apps/main.py:236-289)."""
    name_resolve_cfg = {"backend": cfg.name_resolve_backend}
    if cfg.name_resolve_root:
        name_resolve_cfg["record_root"] = cfg.name_resolve_root
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)

    evaluator_stop = _start_auto_evaluator(cfg)
    result = None
    try:
        attempt = 0
        while True:
            exp_cfg = make_experiment(experiment_type, cfg)
            ctl = LocalController(
                exp_cfg, name_resolve_cfg=name_resolve_cfg,
                worker_env=worker_env,
                # Inner fault domain: individual serving-plane workers
                # restart in place; only escalations reach the relaunch
                # loop below.
                max_worker_restarts=getattr(cfg, "worker_restarts", 2),
            )
            try:
                result = ctl.run()
                break
            except Exception:
                attempt += 1
                if (
                    cfg.recover_mode == "disabled"
                    or attempt > cfg.recover_retries
                ):
                    raise
                logger.exception(
                    f"experiment failed; relaunching with recovery "
                    f"(attempt {attempt}/{cfg.recover_retries})"
                )
                cfg.recover_mode = "auto"
                time.sleep(2)
    finally:
        # Evaluator teardown runs OUTSIDE the recovery try: a drain
        # failure must never relaunch a finished run, and a permanently
        # failed run must not orphan in-flight eval jobs.
        if evaluator_stop is not None:
            try:
                evaluator_stop(drain=result is not None)
            except Exception:
                logger.warning("auto-eval teardown failed", exc_info=True)
    return result


def _start_auto_evaluator(cfg):
    """When cfg.auto_eval is set, watch the save dir from a daemon thread
    and evaluate each new checkpoint through the scheduler client
    (reference: master worker starts AutomaticEvaluator under auto_eval,
    realhf/system/master_worker.py + scheduler/evaluator.py:160-348).

    Returns a stop() callable that drains pending evals, or None."""
    if not getattr(cfg, "auto_eval", False):
        return None
    if not cfg.auto_eval_data_path:
        raise ValueError("auto_eval=True requires auto_eval_data_path")
    import os
    import threading

    from areal_tpu.scheduler.evaluator import AutomaticEvaluator

    save_root = os.path.join(
        constants.get_save_path(cfg.experiment_name, cfg.trial_name),
        cfg.auto_eval_model_role,
    )
    output_root = os.path.join(
        constants.get_log_path(cfg.experiment_name, cfg.trial_name), "eval"
    )
    evaluator = AutomaticEvaluator(
        save_root=save_root,
        data_path=cfg.auto_eval_data_path,
        output_root=output_root,
        task=cfg.auto_eval_task,
        max_concurrent_jobs=cfg.auto_eval_max_concurrent_jobs,
        eval_args={"max_new_tokens": cfg.auto_eval_max_new_tokens},
        # Keep eval jobs off the accelerator the workers hold.
        job_env={"JAX_PLATFORMS": cfg.auto_eval_device},
    )
    stop_event = threading.Event()

    def _tick():
        while not stop_event.wait(2.0):
            try:
                evaluator.step()
            except Exception:
                logger.warning("auto-eval step failed", exc_info=True)

    tick_thread = threading.Thread(target=_tick, daemon=True)
    tick_thread.start()

    def stop(drain_timeout: float = 600.0, drain: bool = True):
        stop_event.set()
        # The evaluator is not thread-safe: an in-flight tick must finish
        # before the drain touches evaluator state from this thread.
        tick_thread.join(timeout=60)
        if tick_thread.is_alive():
            logger.warning(
                "auto-eval tick thread still busy after 60s; skipping the "
                "final drain to avoid racing it"
            )
            drain = False
        try:
            if drain:
                # One final discovery pass + drain so the last checkpoint
                # (saved right before exit) still gets scored.
                evaluator.run_until_idle(timeout=drain_timeout)
        except TimeoutError:
            logger.warning("auto-eval drain timed out; results incomplete")
        finally:
            evaluator.scheduler.stop_all()
        if evaluator.results():
            logger.info(f"auto-eval accuracies by step: {evaluator.results()}")

    return stop


def main(experiment_type: str, cfg_cls: Type, argv=None):
    cfg = parse_args(cfg_cls, argv)
    result = run_experiment(experiment_type, cfg)
    logger.info(f"experiment finished: {result}")
    return result
