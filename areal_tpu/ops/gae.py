"""Generalized Advantage Estimation over packed rows.

Replaces the reference's cugae CUDA kernels (csrc/cugae/gae.cu:
gae_1d_nolp_misalign et al.) and their Python fallback
(realhf/impl/model/utils/ppo_functional.py:292-324) with three
TPU-native implementations over one shared formulation:

- ``gae_rows`` — reverse ``lax.scan`` over the time axis, vectorized
  across rows: O(T) *sequential* steps. The original implementation
  and the numerical oracle every other impl is pinned against.
- ``gae_rows_assoc`` — segment-aware ``jax.lax.associative_scan``:
  the GAE recursion A_t = delta_t + (gamma*lam)*[same-seg]*A_{t+1} is a
  first-order linear recurrence, i.e. a reverse scan of affine maps
  f_t(x) = a_t*x + b_t under composition — associative, so XLA runs it
  in O(log T) depth instead of T serial dispatches (the serial scan
  is T tiny dependent ops). Not timed alone on the chip: the whole
  PPO prep is 8.6 ms of a 2.0 s step (`ppo_prep_ms`; ledger, PR 29).
- ``gae_rows_pallas`` — the same affine scan as a blocked Pallas kernel
  (ops/pallas/gae_scan.py): ONE HBM read of (a, b) + one write of the
  result vs associative_scan's log T full-array passes. Shape-gated
  (``gae_pallas_ok``); interpret-mode on non-TPU backends, so it is
  parity-testable everywhere but only *fast* on device.

``packed_gae`` dispatches (``impl='auto'|'scan'|'assoc'|'pallas'``,
mirroring ops/attention.resolve_attn_impl): 'auto' resolves to the
associative scan everywhere — Pallas stays opt-in: no chip run has
timed one against the other (docs/perf_notes.md "Round 15").

Inputs are [R, T] row-packed (multiple sequences per row, segment ids,
0 = padding). Bootstrapping for truncated (no-EOS) sequences is expressed
by placing V(s_T) in `bootstrap` at each sequence's final token.

Parity: the three impls reassociate float32 sums differently, so they
agree to ~1e-6 relative on realistic magnitudes (pinned in
tests/ops/test_gae.py); at lam = 0 nothing accumulates and they agree
to one ulp (XLA FMA fusion still moves the last bit).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def gae_rows(
    rewards: jnp.ndarray,  # [R, T] per-token rewards
    values: jnp.ndarray,  # [R, T] V(s_t)
    segment_ids: jnp.ndarray,  # [R, T]
    bootstrap: jnp.ndarray,  # [R, T] V(s_{T+1}) at final tokens of truncated seqs, else 0
    gamma: float = 1.0,
    lam: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (advantages, returns), both [R, T], zero outside segments.

    delta_t = r_t + gamma * V(s_{t+1}) - V(s_t), with V(s_{t+1}) = the next
    token's value within the same segment, the bootstrap value at segment
    ends, 0 otherwise. A_t = delta_t + gamma*lam*A_{t+1} (same-segment).
    """
    R, T = rewards.shape
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    bootstrap = bootstrap.astype(jnp.float32)

    def step(carry, xs):
        adv_next, v_next, seg_next = carry
        r_t, v_t, seg_t, boot_t = xs  # each [R]
        valid = seg_t > 0
        same = (seg_t == seg_next) & valid
        v_tp1 = jnp.where(same, v_next, boot_t)
        delta = r_t + gamma * v_tp1 - v_t
        adv = delta + gamma * lam * jnp.where(same, adv_next, 0.0)
        adv = jnp.where(valid, adv, 0.0)
        return (adv, v_t, seg_t), adv

    xs = (rewards.T, values.T, segment_ids.T, bootstrap.T)  # scan over T
    init = (
        jnp.zeros((R,), jnp.float32),
        jnp.zeros((R,), jnp.float32),
        jnp.zeros((R,), jnp.int32),
    )
    _, advs = jax.lax.scan(step, init, xs, reverse=True)
    advantages = advs.T
    returns = advantages + values
    valid = segment_ids > 0
    return (
        jnp.where(valid, advantages, 0.0),
        jnp.where(valid, returns, 0.0),
    )


def _gae_affine_elems(rewards, values, segment_ids, bootstrap, gamma, lam):
    """(a, b, valid, values32): the per-token affine scan elements.

    The GAE recursion is x_t = a_t * x_{t+1} + b_t with
    a_t = gamma*lam*[seg_t == seg_{t+1}, both valid] and b_t = delta_t.
    Computed in one vectorized pass (no neighbor access inside the scan):
    V(s_{t+1}) is the left-shifted values where the NEXT token shares the
    segment, the bootstrap at segment ends — exactly the serial scan's
    carry semantics, including its t = T-1 edge (carry seg 0 => same is
    False there, matching the shifted pad of 0 segment ids)."""
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    bootstrap = bootstrap.astype(jnp.float32)
    valid = segment_ids > 0
    seg_next = jnp.concatenate(
        [segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1
    )
    v_next = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
    )
    same = (segment_ids == seg_next) & valid
    v_tp1 = jnp.where(same, v_next, bootstrap)
    delta = rewards + gamma * v_tp1 - values
    a = jnp.where(same, jnp.float32(gamma * lam), 0.0)
    # Masking b here makes invalid positions exact zeros (a is already 0
    # there, so they also never leak into neighbors) — the serial scan's
    # post-hoc where(valid, ., 0) built into the elements.
    b = jnp.where(valid, delta, 0.0)
    return a, b, valid, values


def _finish_gae(adv, values32, valid):
    adv = jnp.where(valid, adv, 0.0)
    return adv, jnp.where(valid, adv + values32, 0.0)


def gae_rows_assoc(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    bootstrap: jnp.ndarray,
    gamma: float = 1.0,
    lam: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``gae_rows`` semantics in O(log T) scan depth
    (``jax.lax.associative_scan`` over the affine elements)."""
    a, b, valid, values32 = _gae_affine_elems(
        rewards, values, segment_ids, bootstrap, gamma, lam
    )

    def combine(l, r):
        # reverse=True flips the array before a forward tree scan, so the
        # LEFT operand holds the LATER timesteps — the inner composition,
        # applied first: (f_outer . f_inner)(x) = a_o*(a_i*x + b_i) + b_o.
        a_inner, b_inner = l
        a_outer, b_outer = r
        return a_outer * a_inner, b_outer + a_outer * b_inner

    _, adv = jax.lax.associative_scan(combine, (a, b), reverse=True, axis=1)
    return _finish_gae(adv, values32, valid)


def gae_rows_pallas(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    bootstrap: jnp.ndarray,
    gamma: float = 1.0,
    lam: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``gae_rows`` semantics as a blocked Pallas scan kernel.

    Shapes must pass ``gae_pallas_ok`` (T lane-aligned, R sublane-
    aligned); callers going through ``packed_gae(impl='auto')`` never
    reach this without the gate. Runs interpreted off-TPU."""
    from areal_tpu.ops.pallas.gae_scan import (
        gae_pallas_ok,
        segment_scan_reverse,
    )

    R, T = rewards.shape
    if not gae_pallas_ok(R, T):
        raise ValueError(
            f"gae impl='pallas' needs lane/sublane-aligned rows "
            f"(T % 128 == 0, R % 8 == 0), got [R={R}, T={T}]; use "
            f"impl='assoc'"
        )
    a, b, valid, values32 = _gae_affine_elems(
        rewards, values, segment_ids, bootstrap, gamma, lam
    )
    interpret = jax.default_backend() != "tpu"
    adv = segment_scan_reverse(a, b, interpret=interpret)
    return _finish_gae(adv, values32, valid)


def resolve_gae_impl(impl: str, r: int, t: int) -> str:
    """Resolve 'auto' to a concrete impl for the given packed shape
    (trace-time static decision, mirroring ops/attention.
    resolve_attn_impl). Explicit values pass through untouched.

    'auto' is the associative scan everywhere: O(log T) depth where the
    serial scan is T dependent dispatches. The Pallas kernel stays
    opt-in (impl='pallas'): no chip run has timed it against the
    associative scan, and a default is not flipped on an unmeasured
    kernel."""
    if impl != "auto":
        return impl
    return "assoc"


@jax.named_scope("gae")
def packed_gae(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    bootstrap: jnp.ndarray,
    gamma: float = 1.0,
    lam: float = 1.0,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch between GAE implementations (static, trace-time):
    'scan' (the serial oracle), 'assoc', 'pallas', or 'auto'
    (resolve_gae_impl). The PPO interface calls this with the
    AREAL_GAE_IMPL knob's value."""
    impl = resolve_gae_impl(impl, rewards.shape[0], rewards.shape[1])
    if impl == "scan":
        return gae_rows(rewards, values, segment_ids, bootstrap,
                        gamma=gamma, lam=lam)
    if impl == "assoc":
        return gae_rows_assoc(rewards, values, segment_ids, bootstrap,
                              gamma=gamma, lam=lam)
    if impl == "pallas":
        return gae_rows_pallas(rewards, values, segment_ids, bootstrap,
                               gamma=gamma, lam=lam)
    raise ValueError(
        f"unknown gae impl {impl!r}; expected 'auto', 'scan', 'assoc', "
        f"or 'pallas'"
    )
