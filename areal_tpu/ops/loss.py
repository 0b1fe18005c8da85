"""Token-level loss/logprob primitives over packed rows.

Replaces the reference's vocab-parallel cross entropy and packed logprob
gathering (realhf/impl/model/parallelism/tensor_parallel/modules.py:1180,
realhf/impl/model/utils/functional.py): under GSPMD the vocab dimension is
just a sharded axis, so a plain log_softmax + gather compiles to the same
collectives the hand-written vocab-parallel CE performs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from areal_tpu.base import env_registry
import jax.numpy as jnp


def gather_logprobs(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """log P(labels) under logits along the last axis. fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return picked - lse


def next_token_logprobs(
    logits: jnp.ndarray,  # [R, T, V] fp32
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T], 0 = pad
) -> jnp.ndarray:
    """logprob[t] = log P(token[t+1] | prefix) when t+1 continues the same
    segment; 0 elsewhere (sequence-final tokens, padding). Shape [R, T].

    Matches the reference convention where packed logprobs are shifted so
    position t scores the token emitted *at* t+1.
    """
    next_ids, valid = _next_token_targets(input_ids, segment_ids)
    logp = gather_logprobs(logits, next_ids)
    return jnp.where(valid, logp, 0.0)


def next_token_entropy(
    logits: jnp.ndarray, segment_ids: jnp.ndarray
) -> jnp.ndarray:
    """Per-position predictive entropy, masked like next_token_logprobs."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return jnp.where(segment_ids > 0, ent, 0.0)


def _next_token_targets(input_ids: jnp.ndarray, segment_ids: jnp.ndarray):
    """(next_ids, valid) in the shifted frame shared by all logprob ops."""
    next_ids = jnp.concatenate(
        [input_ids[:, 1:], jnp.zeros_like(input_ids[:, :1])], axis=1
    )
    next_seg = jnp.concatenate(
        [segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1
    )
    valid = (segment_ids > 0) & (next_seg == segment_ids)
    return next_ids, valid


def _pick_chunk(n_tokens: int, target: int = 4096) -> int:
    """Largest divisor of n_tokens that is <= target (>=1)."""
    c = min(target, n_tokens)
    while n_tokens % c:
        c -= 1
    return c


# AREAL_CE_CHUNK snapshot: (value,) once taken, None before. The tuple
# wrapper distinguishes "snapshotted as unset" from "never snapshotted".
_CE_CHUNK_SNAP: Optional[Tuple[Optional[int]]] = None


def snapshot_ce_chunk() -> Optional[int]:
    """Parse + validate AREAL_CE_CHUNK and pin it for subsequent traces.

    Called at engine construction (engine/jax_engine.py): a mid-run
    retrace then reuses the pinned value instead of silently picking up
    a mutated environment, and an unparseable value fails HERE — at
    init — rather than deep inside a jit trace. A fresh engine re-pins."""
    global _CE_CHUNK_SNAP
    # ValueError (unparseable value) surfaces at snapshot time.
    val: Optional[int] = env_registry.get_int("AREAL_CE_CHUNK")
    if val is not None and val <= 0:
        raise ValueError(f"AREAL_CE_CHUNK={val}: must be positive")
    _CE_CHUNK_SNAP = (val,)
    return val


def _ce_chunk_setting() -> Optional[int]:
    if _CE_CHUNK_SNAP is None:
        # Direct ops use without an engine: snapshot lazily on first use.
        return snapshot_ce_chunk()
    return _CE_CHUNK_SNAP[0]


@jax.named_scope("xent")
def fused_next_token_logprobs(
    hidden: jnp.ndarray,  # [R, T, D] compute dtype
    head_w: jnp.ndarray,  # [D, V]
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T]
    chunk_size: Optional[int] = None,
) -> jnp.ndarray:
    """next_token_logprobs computed straight from hidden states without
    ever materializing the [R, T, V] logits tensor.

    The token axis is flattened and scanned in chunks; each chunk computes
    its [C, V] logits tile, reduces to (picked - logsumexp), and discards
    the tile. `jax.checkpoint` on the chunk body makes the backward pass
    recompute the tile instead of storing softmax residuals, so peak
    memory is O(C * V) rather than O(R * T * V) in both directions —
    the TPU-shaped equivalent of the reference's vocab-parallel fused
    cross entropy (realhf/impl/model/parallelism/tensor_parallel/
    modules.py:1180), which shards V to avoid the same materialization.

    Returns [R, T] fp32, zeros at invalid (sequence-final / pad) slots.
    """
    R, T, D = hidden.shape
    V = head_w.shape[-1]
    if chunk_size is None:
        # AREAL_CE_CHUNK override, validated + pinned at engine
        # construction (snapshot_ce_chunk) so retraces can't mix
        # settings mid-run.
        chunk_size = _ce_chunk_setting()
        if chunk_size is None:
            # Byte-budgeted: keep the per-chunk fp32 logits tile ~512 MB
            # regardless of vocab size (C*V elements), floor 256 tokens.
            chunk_size = max(256, (1 << 27) // V)
    next_ids, valid = _next_token_targets(input_ids, segment_ids)
    n = R * T
    c = _pick_chunk(n, chunk_size)
    flat_h = hidden.reshape(n // c, c, D)
    flat_y = next_ids.reshape(n // c, c)

    def chunk(carry, hy):
        h_c, y_c = hy
        logits = (h_c @ head_w.astype(h_c.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        return carry, picked - lse

    _, logp = jax.lax.scan(jax.checkpoint(chunk), None, (flat_h, flat_y))
    return jnp.where(valid, logp.reshape(R, T), 0.0)


def sft_loss_from_logprobs(
    logp: jnp.ndarray,  # [R, T] next-token logprobs (zeros at invalid)
    loss_mask: jnp.ndarray,  # [R, T]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked next-token NLL from precomputed logprobs."""
    mask = loss_mask.astype(jnp.float32)
    return -jnp.sum(logp * mask), jnp.sum(mask)


def sft_loss(
    logits: jnp.ndarray,  # [R, T, V]
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T]
    loss_mask: jnp.ndarray,  # [R, T] 1.0 where the *target* token (t+1) counts
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token cross entropy over masked positions.

    loss_mask is given per-position in the shifted frame: mask[t] = 1 means
    the prediction made at t (of token t+1) contributes. Returns
    (sum_loss, n_tokens); callers normalize globally so DP shards with
    different token counts average correctly.
    """
    logp = next_token_logprobs(logits, input_ids, segment_ids)
    mask = loss_mask.astype(jnp.float32)
    return -jnp.sum(logp * mask), jnp.sum(mask)


def masked_normalization(
    x: jnp.ndarray,
    mask: jnp.ndarray,
    eps: float = 1e-5,
    unbiased: bool = True,
) -> jnp.ndarray:
    """Whiten x over masked elements (advantage normalization).

    Under pjit the batch is global, so the mean/std are global without any
    explicit collective (reference: realhf/impl/model/utils/functional.py
    masked_normalization with its dist.all_reduce).
    """
    mask = mask.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    mean = jnp.sum(x32 * mask) / n
    var = jnp.sum(((x32 - mean) ** 2) * mask) / jnp.maximum(
        n - (1.0 if unbiased else 0.0), 1.0
    )
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return jnp.where(mask > 0, out, 0.0).astype(x.dtype)
