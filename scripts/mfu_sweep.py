"""On-chip MFU sweep over the train-step tuning levers (VERDICT r4 #3).

Runs the bench.py flagship train step (R1-Distill-Qwen-1.5B shape,
remat=save_attn) under a grid of the three unmeasured levers:

  - CE chunk size (AREAL_CE_CHUNK, ops/loss.fused_next_token_logprobs)
  - splash block-size targets (AREAL_SPLASH_BQ/BKV/BKVC,
    ops/attention._splash_kernel — ~25%% of step time at the 12q/2kv
    hd=128 shape per scripts/analyze_trace.py)
  - micro-batching (n_mbs: grad-accum scan slice cost vs one fused step)

Each configuration gets a FRESH engine (fresh jit trace — the env
overrides are read at trace time). Prints one JSON line per config to
stdout and a human table to stderr; best config last. Run on the real
chip; on CPU it only validates the harness (AREAL_SWEEP_TINY=1).

Usage:  python scripts/mfu_sweep.py [ce|blocks|mbs|all]
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from bench import (  # shared shape + formula: rows stay comparable
    flagship_cfg,
    train_step_flops,
)
from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import count_params, init_params
from areal_tpu.ops.loss import sft_loss_from_logprobs


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(**kw):
    print(json.dumps(kw), flush=True)


TINY = bool(os.environ.get("AREAL_SWEEP_TINY"))


def cfg_and_shape():
    if TINY:
        cfg = TransformerConfig(
            n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2,
            head_dim=16, intermediate_dim=128, vocab_size=256,
            compute_dtype="float32",
        )
        return cfg, 128, 4, 1, 2
    return flagship_cfg(), 2048, 16, 2, 4


def measure(env: dict, n_mbs: int = 1, seqlen: int = 0) -> float:
    """TFLOP/s for one config. Fresh engine per call: the env overrides
    are trace-time, so a new jit (new engine) picks them up. seqlen > 0
    overrides the row length, holding total tokens constant."""
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    try:
        cfg, d_seqlen, d_n_seqs, n_warm, n_steps = cfg_and_shape()
        if seqlen:
            total_tokens = d_seqlen * d_n_seqs
            n_seqs = max(1, total_tokens // seqlen)
        else:
            seqlen, n_seqs = d_seqlen, d_n_seqs
        params = init_params(cfg, jax.random.PRNGKey(0))
        n_params = count_params(params)
        eng = JaxTrainEngine(
            cfg, params,
            optimizer_config=OptimizerConfig(lr=1e-4,
                                             warmup_steps_proportion=0.0),
            total_train_steps=1000, row_len_multiple=seqlen,
            max_row_len=seqlen,
            remat="full" if TINY else "save_attn",
        )
        rng = np.random.RandomState(0)
        seqlens = [seqlen] * n_seqs
        total = sum(seqlens)
        batch = SequenceSample.from_default(
            ids=[f"b{i}" for i in range(n_seqs)],
            seqlens=seqlens,
            data={
                "packed_input_ids": rng.randint(0, cfg.vocab_size,
                                                size=total),
                "loss_mask": np.ones(total, np.float32),
            },
        )

        def packed_loss(lp, rows):
            tot, _ = sft_loss_from_logprobs(lp, rows["loss_mask"])
            return tot, {}

        def weight(mb):
            return float(np.sum(mb.data["loss_mask"]))

        def one(i):
            return eng.train_batch(batch, MicroBatchSpec(n_mbs=n_mbs),
                                   packed_loss, weight, version_steps=i,
                                   loss_name="sweep")

        for i in range(n_warm):
            t = time.perf_counter()
            one(i)
            log(f"  warmup {i}: {time.perf_counter() - t:.2f}s")
        t0 = time.perf_counter()
        for i in range(n_steps):
            one(n_warm + i)
        jax.block_until_ready(eng.params)
        dt = (time.perf_counter() - t0) / n_steps
        tflops = train_step_flops(cfg, n_params, seqlens) / dt / 1e12
        del eng, params
        gc.collect()
        return tflops
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def sweep(name, configs):
    """configs: list of (label, env, n_mbs[, seqlen]). Emits one JSON row
    each and a winner row at the end."""
    best = None
    for label, env, n_mbs, *rest in configs:
        log(f"sweep {name}: {label} ...")
        try:
            tflops = measure(env, n_mbs=n_mbs,
                             seqlen=rest[0] if rest else 0)
        except Exception as e:  # OOM on one config must not kill the rest
            log(f"sweep {name}: {label} FAILED {type(e).__name__}: {e}")
            emit(sweep=name, config=label,
                 error=f"{type(e).__name__}: {e}"[:200])
            gc.collect()
            continue
        emit(sweep=name, config=label, tflops=round(tflops, 2))
        log(f"sweep {name}: {label:32s} {tflops:7.2f} TFLOP/s")
        if best is None or tflops > best[1]:
            best = (label, tflops)
    if best:
        emit(sweep=name, best=best[0], tflops=round(best[1], 2))
        log(f"sweep {name}: BEST {best[0]} @ {best[1]:.2f} TFLOP/s")


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which not in ("all", "ce", "blocks", "mbs", "seqlen"):
        sys.exit(
            f"unknown sweep {which!r}: expected all|ce|blocks|mbs|seqlen"
        )
    platform = jax.devices()[0].platform
    log(f"mfu_sweep: platform={platform} which={which}")
    if platform != "tpu" and not TINY:
        log("WARNING: not on TPU; numbers are not meaningful")

    if which in ("all", "ce"):
        # Default (byte-budget @32k vocab) resolves to 4096.
        sweep("ce_chunk", [
            (f"ce={c}", {"AREAL_CE_CHUNK": c}, 1)
            for c in ((64,) if TINY else (1024, 2048, 4096, 8192, 16384))
        ])
    if which in ("all", "blocks"):
        grid = ((128, 128, 128),) if TINY else (
            (512, 1024, 512),   # current default
            (256, 1024, 512),
            (512, 512, 512),
            (1024, 1024, 512),
            (512, 2048, 512),
            (512, 1024, 1024),
            (256, 512, 512),
        )
        sweep("splash_blocks", [
            (f"bq={bq},bkv={bkv},bkvc={bkvc}",
             {"AREAL_SPLASH_BQ": bq, "AREAL_SPLASH_BKV": bkv,
              "AREAL_SPLASH_BKVC": bkvc}, 1)
            for bq, bkv, bkvc in grid
        ])
    if which in ("all", "mbs"):
        sweep("n_mbs", [
            (f"n_mbs={m}", {}, m) for m in ((1, 2) if TINY else (1, 2, 4))
        ])
    if which in ("all", "seqlen"):
        # Row length at constant total tokens: longer rows raise the
        # attention-FLOPs fraction (higher arithmetic intensity in the
        # splash kernel) but deepen remat recompute; measure, don't guess.
        sweep("seqlen", [
            (f"seqlen={s}", {}, 1, s)
            for s in ((64, 128) if TINY else (1024, 2048, 4096, 8192))
        ])


if __name__ == "__main__":
    main()
