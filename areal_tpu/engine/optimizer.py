"""Optimizer construction: AdamW + LR schedules + global-norm clipping.

Counterpart of the reference's Megatron DistributedOptimizer + LR scheduler
wiring (realhf/impl/model/backend/megatron.py:561-700). ZeRO sharding of
optimizer state is not code here — it falls out of giving Adam's mu/nu the
same NamedShardings as their parameters (fsdp/tensor axes), see
jax_engine.opt_state_shardings.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import optax


@dataclasses.dataclass
class OptimizerConfig:
    """Mirrors the reference's OptimizerConfig dataclass (api/cli_args.py)."""

    type: str = "adamw"
    lr: float = 1e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-5
    min_lr_ratio: float = 0.0
    lr_scheduler_type: str = "constant"  # constant | linear | cosine
    warmup_steps_proportion: float = 0.001
    gradient_clipping: float = 1.0


def make_lr_schedule(cfg: OptimizerConfig, total_train_steps: int):
    warmup = int(cfg.warmup_steps_proportion * total_train_steps)
    decay_steps = max(1, total_train_steps - warmup)
    end = cfg.lr * cfg.min_lr_ratio
    if cfg.lr_scheduler_type == "constant":
        after = optax.constant_schedule(cfg.lr)
    elif cfg.lr_scheduler_type == "linear":
        after = optax.linear_schedule(cfg.lr, end, decay_steps)
    elif cfg.lr_scheduler_type == "cosine":
        after = optax.cosine_decay_schedule(cfg.lr, decay_steps, alpha=cfg.min_lr_ratio)
    else:
        raise ValueError(f"unknown lr_scheduler_type {cfg.lr_scheduler_type!r}")
    if warmup == 0:
        return after
    # Ramp starts at lr/warmup (not 0) so the very first step trains.
    return optax.join_schedules(
        [optax.linear_schedule(cfg.lr / warmup, cfg.lr, warmup), after], [warmup]
    )


def host_lr_schedule(cfg: OptimizerConfig, total_train_steps: int):
    """`make_lr_schedule`'s value at a whole position as a host number:
    `f(pos) -> float`, numpy in float32, operation for operation what the
    optax schedule does when it is called eagerly with a Python int. No
    jax computation runs, so a train step that asks for its learning rate
    neither puts anything on the device's queue nor waits for what is
    there (an eager schedule is a dozen one-op programs and a fetch).
    Equal to `float(make_lr_schedule(...)(pos))` to the last bit for
    `constant` and `linear`, and to one ulp of float32 for `cosine`
    (numpy's cosine against XLA's): tests/engine/test_lr_on_host.py."""
    f32 = np.float32
    warmup = int(cfg.warmup_steps_proportion * total_train_steps)
    decay_steps = max(1, total_train_steps - warmup)
    kind = cfg.lr_scheduler_type
    if kind not in ("constant", "linear", "cosine"):
        raise ValueError(f"unknown lr_scheduler_type {kind!r}")

    def line(init, end, steps, pos):  # optax.linear_schedule(init, end, steps)(pos)
        frac = f32(1) - f32(min(max(pos, 0), steps)) / f32(steps)
        return f32(init - end) * frac + f32(end)

    def after(pos):
        if kind == "constant":
            return cfg.lr  # optax hands the Python number back as it is
        if kind == "linear":
            return line(cfg.lr, cfg.lr * cfg.min_lr_ratio, decay_steps, pos)
        turn = f32(np.pi) * f32(min(pos, decay_steps)) / f32(decay_steps)
        cosine_decay = f32(0.5) * (f32(1) + np.cos(turn, dtype=f32))
        return f32(cfg.lr) * (
            f32(1 - cfg.min_lr_ratio) * cosine_decay + f32(cfg.min_lr_ratio))

    def schedule(pos: int) -> float:
        pos = int(pos)
        if warmup == 0:
            return float(after(pos))
        if pos < warmup:
            return float(line(cfg.lr / warmup, cfg.lr, warmup, pos))
        # `jnp.where` makes the constant schedule's Python number a float32
        return float(f32(after(pos - warmup)))

    return schedule


# A state-space mixer's per-head and per-channel parameters (ops/ssm.py,
# ops/selective_scan.py: there `A_log` is [channels, states], rates all
# the same) and differential attention's lambda vectors and inner norm:
# stacked on a layer axis they have two dimensions and are no matrices.
NO_DECAY_LEAVES = ("A_log", "D", "dt_bias", "conv_b", "lambda_q1", "lambda_k1",
                   "lambda_q2", "lambda_k2", "sub_norm")


def _decay_mask(params):
    """No weight decay on 1D params (norms, biases) — standard practice —
    nor, by leaf name, on a state-space mixer's decay rates, skip
    weights, step biases and convolution bias, nor on differential
    attention's lambda vectors and inner norm."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, p: p.ndim > 1 and getattr(path[-1], "key", None) not in NO_DECAY_LEAVES,
        params)


def make_optimizer(
    cfg: OptimizerConfig, total_train_steps: int, params_example=None,
    external_lr: bool = False,
) -> optax.GradientTransformation:
    """With ``external_lr=True`` the transformation applies a UNIT
    learning rate (as a constant schedule, so the optimizer-state
    structure — including the schedule's count leaf — stays identical to
    the internal-schedule build and old checkpoints keep loading); the
    caller scales the returned updates by the schedule value it wants.
    This is how `JaxTrainEngine.train_batch` honors `version_steps` as
    the LR-schedule position (reference semantics: several PPO minibatch
    updates share one schedule step) while Adam's bias correction keeps
    counting actual updates."""
    if cfg.type != "adamw":
        raise NotImplementedError(f"optimizer type {cfg.type!r}")
    schedule = (
        optax.constant_schedule(1.0)
        if external_lr
        else make_lr_schedule(cfg, total_train_steps)
    )
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.gradient_clipping)
        if cfg.gradient_clipping
        else optax.identity(),
        optax.adamw(
            learning_rate=schedule,
            b1=cfg.beta1,
            b2=cfg.beta2,
            eps=cfg.eps,
            weight_decay=cfg.weight_decay,
            mask=_decay_mask if cfg.weight_decay else None,
        ),
    )
    return tx
