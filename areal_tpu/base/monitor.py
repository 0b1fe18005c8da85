"""Throughput accounting: analytic FLOP formulas and rollout statistics.

Counterpart of the reference's monitor module (realhf/base/monitor.py),
minus CUDA-specific kernel-trace parsing (the TPU analogue is
`jax.profiler` traces, handled in `areal_tpu.utils.profiling`). The FLOP
formulas are the standard dense-transformer counts used to report
TFLOP/s-per-chip.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass
class RolloutStat:
    """Counters the generation manager logs per interval."""

    submitted: int = 0
    accepted: int = 0
    running: int = 0
    gen_tokens: int = 0


def calculate_llama_forward_flops(
    batch_size: int,
    seqlens: Sequence[int],
    hidden_size: int,
    intermediate_size: int,
    vocab_size: int,
    n_layers: int,
    num_heads: int,
    num_kv_heads: int,
) -> int:
    """Forward FLOPs of a llama-family model over packed sequences.

    Matmul-only accounting (2*m*n*k per matmul), including the quadratic
    attention term computed per-sequence from `seqlens`.
    """
    total_tokens = int(sum(seqlens))
    head_dim = hidden_size // num_heads
    kv_size = head_dim * num_kv_heads
    # Projections: q (h->h), k/v (h->kv), o (h->h)
    attn_proj = 2 * total_tokens * hidden_size * (2 * hidden_size + 2 * kv_size)
    # Attention scores + values: 2 * sum(len^2) * h per each of QK^T and PV
    attn_quad = 4 * sum(int(l) ** 2 for l in seqlens) * hidden_size
    # Gated MLP: gate+up (h->i each), down (i->h)
    mlp = 2 * total_tokens * hidden_size * intermediate_size * 3
    # LM head
    head = 2 * total_tokens * hidden_size * vocab_size
    return n_layers * (attn_proj + attn_quad + mlp) + head


def calculate_llama_train_flops(*args, **kwargs) -> int:
    """Training = forward + backward ~= 3x forward."""
    return 3 * calculate_llama_forward_flops(*args, **kwargs)


def transformer_forward_flops(cfg, seqlens: Sequence[int]) -> int:
    """Forward FLOPs from an areal_tpu TransformerConfig over packed
    sequences (matmul-only, MoE-aware: only the top-k routed experts'
    FLOPs count per token).

    Unlike the llama formula above (API parity with the reference's
    hidden_size/num_heads signature, realhf/base/monitor.py:307), this
    uses the config's true q/kv/head dims, so GQA and decoupled head_dim
    models are counted exactly.
    """
    total_tokens = int(sum(seqlens))
    D = cfg.hidden_dim
    q_dim = cfg.n_q_heads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    attn_proj = 2 * total_tokens * D * (2 * q_dim + 2 * kv_dim)
    attn_quad = 4 * sum(int(l) ** 2 for l in seqlens) * q_dim
    if cfg.moe is not None:
        e_dim = cfg.moe.expert_intermediate_dim or cfg.intermediate_dim
        mlp = 2 * total_tokens * D * (cfg.moe.top_k * e_dim) * 3
        mlp += 2 * total_tokens * D * cfg.moe.num_experts  # router
    else:
        n_in = 2 if cfg.mlp_type == "gated" else 1
        mlp = 2 * total_tokens * D * cfg.intermediate_dim * (n_in + 1)
    head = 2 * total_tokens * D * cfg.vocab_size
    return cfg.n_layers * (attn_proj + attn_quad + mlp) + head


def mfc_flops(
    cfg,
    interface_type: str,
    input_seqlens: Sequence[int],
    output_seqlens: Optional[Sequence[int]] = None,
) -> int:
    """Analytic FLOPs of one model function call, from the model's
    TransformerConfig and the packed batch shape (counterpart of the
    reference's FlopsCounter, realhf/system/flops_counter.py — computed
    worker-side here because the worker knows the true config+shapes).

    - train_step: 3x forward (fwd + bwd)
    - inference:  1x forward
    - generate:   prefill over prompts + per-token decode; approximated
      as one forward over the FULL (prompt+generated) sequences, which
      counts each decode step's matmuls once and the attention context
      quadratically — the same accounting the reference's gen formula
      reaches in closed form.
    """
    if interface_type == "train_step":
        return 3 * transformer_forward_flops(cfg, input_seqlens)
    if interface_type == "inference":
        return transformer_forward_flops(cfg, input_seqlens)
    if interface_type == "generate":
        full = output_seqlens if output_seqlens else input_seqlens
        return transformer_forward_flops(cfg, full)
    return 0


def calculate_llama_gen_flops(
    batch_size: int,
    prompt_lens: Sequence[int],
    gen_len: int,
    hidden_size: int,
    intermediate_size: int,
    vocab_size: int,
    n_layers: int,
    num_heads: int,
    num_kv_heads: int,
) -> int:
    """Generation FLOPs: one prefill over prompts plus `gen_len` decode steps."""
    flops = calculate_llama_forward_flops(
        batch_size,
        prompt_lens,
        hidden_size,
        intermediate_size,
        vocab_size,
        n_layers,
        num_heads,
        num_kv_heads,
    )
    head_dim = hidden_size // num_heads
    kv_size = head_dim * num_kv_heads
    # Closed form of sum_i sum_j (prompt_j + i) over decode steps i:
    # gen_len * sum(prompt) + B * gen_len*(gen_len-1)/2.
    total_ctx = gen_len * sum(int(l) for l in prompt_lens) + batch_size * (
        gen_len * (gen_len - 1) // 2
    )
    attn_proj = 2 * batch_size * hidden_size * (2 * hidden_size + 2 * kv_size)
    mlp = 2 * batch_size * hidden_size * intermediate_size * 3
    head = 2 * batch_size * hidden_size * vocab_size
    flops += gen_len * (n_layers * (attn_proj + mlp) + head)
    flops += n_layers * 4 * total_ctx * hidden_size
    return flops


# ---------------------------------------------------------------------------
# Device memory telemetry + OOM guard
# ---------------------------------------------------------------------------

# Fraction of HBM beyond which the worker self-terminates so the relaunch
# loop can recover it (reference REAL_GPU_MEMORY_KILL_THRESHOLD,
# realhf/system/model_worker.py:1507-1610).
MEMORY_KILL_THRESHOLD_ENV = "AREAL_TPU_MEMORY_KILL_THRESHOLD"


class DeviceOOMGuardError(RuntimeError):
    """Raised when device memory use crosses the kill threshold."""


def _memory_stats(device) -> dict:
    """`Device.memory_stats()`, or {} where the backend keeps none (the
    CPU). A TPU always keeps them: absent or failing stats there would
    blind the OOM guard, so they raise."""
    if device.platform != "tpu":
        try:
            return device.memory_stats() or {}
        except Exception:
            return {}
    stats = device.memory_stats()
    if not stats:
        raise RuntimeError(f"{device} reports no memory_stats()")
    return stats


def device_peak_bytes(devices=None) -> list:
    """`peak_bytes_in_use` of each local device (0 where the backend
    keeps no stats)."""
    import jax

    devices = devices if devices is not None else jax.local_devices()
    return [int(_memory_stats(d).get("peak_bytes_in_use", 0)) for d in devices]


def device_memory_stats(devices=None) -> dict:
    """Aggregate HBM usage over the local devices.

    Uses `Device.memory_stats()` (populated on TPU/GPU backends; None on
    CPU) — absent stats off-TPU yield zeros so callers can log
    unconditionally; on a TPU they raise (`_memory_stats`)."""
    import jax

    devices = devices if devices is not None else jax.local_devices()
    in_use = limit = peak = 0
    n_reporting = 0
    for d in devices:
        stats = _memory_stats(d)
        if not stats:
            continue
        n_reporting += 1
        in_use += int(stats.get("bytes_in_use", 0))
        limit += int(stats.get("bytes_limit", 0) or stats.get("bytes_reservable_limit", 0))
        peak += int(stats.get("peak_bytes_in_use", 0))
    frac = (in_use / limit) if limit else 0.0
    return {
        "mem_bytes_in_use": float(in_use),
        "mem_bytes_limit": float(limit),
        "mem_peak_bytes_in_use": float(peak),
        "mem_frac_in_use": float(frac),
        "mem_devices_reporting": float(n_reporting),
    }


def check_memory_kill_threshold(stats: Optional[dict] = None, devices=None):
    """Raise DeviceOOMGuardError when usage exceeds the env threshold.

    No-op when the env var is unset or the backend reports no stats."""
    from areal_tpu.base import env_registry

    threshold = env_registry.get_float(MEMORY_KILL_THRESHOLD_ENV)
    if threshold is None:
        return
    stats = stats if stats is not None else device_memory_stats(devices)
    if stats["mem_bytes_limit"] and stats["mem_frac_in_use"] > threshold:
        raise DeviceOOMGuardError(
            f"device memory {stats['mem_frac_in_use']:.3f} of HBM exceeds "
            f"kill threshold {threshold} "
            f"({stats['mem_bytes_in_use']:.0f}/{stats['mem_bytes_limit']:.0f} "
            f"bytes); terminating for relaunch-recovery"
        )
