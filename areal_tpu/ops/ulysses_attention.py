"""Ulysses attention: all-to-all sequence parallelism.

The second context-parallel scheme next to ring attention
(ops/ring_attention.py), after DeepSpeed-Ulysses: instead of rotating KV
chunks S times around the `seq` axis, ONE all-to-all swaps the sharded
dimension from sequence to heads — each device then holds ALL tokens for
Hq/S of the heads, runs ordinary packed attention locally, and a second
all-to-all swaps back. Trade-offs vs ring:

- comm: 4 all-to-alls (q, k, v, out) + 2 tiny metadata all-gathers per
  layer, each moving O(T·hd/S) per device, vs ring's S ppermute steps
  pipelined behind compute — Ulysses usually wins at moderate T, ring
  at very long T where O(T/S) attention memory matters;
- memory: local attention sees the FULL sequence (O(T) KV per device,
  like megatron-SP; the splash local kernel keeps scores tiled) — ring
  keeps O(T/S);
- constraint: head counts must divide seq*tensor (ring only needs
  tensor).

Packed-varlen semantics are inherited from the local attention oracle
(same segment AND causal masking); GQA stays consistent because a
contiguous head split assigns each shard matching q/kv head runs
(q head j maps to kv head j // G, and Hq/S q-heads align with Hkv/S
kv-heads when Hkv % S == 0).

Differentiable end-to-end: all_to_all's transpose is the reverse
all-to-all, so autodiff derives the standard Ulysses backward.

Reference counterpart: none — the reference has no sequence/context
parallelism (megatron.py:94 TODO); both schemes exceed it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from areal_tpu.ops.attention import (
    cp_axes,
    reference_packed_attention,
    splash_packed_attention,
)


def ulysses_packed_attention(
    q: jnp.ndarray,  # [R, T, Hq, hd] (T sharded on `seq`)
    k: jnp.ndarray,  # [R, T, Hkv, hd]
    v: jnp.ndarray,  # [R, T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [R, T]
    positions: jnp.ndarray,  # [R, T]
    mesh,
    softmax_scale: Optional[float] = None,
    local_impl: str = "auto",
) -> jnp.ndarray:
    """Packed GQA attention with the seq shard swapped onto heads via
    all-to-all. Callers must check `ulysses_ok` first.

    `local_impl` selects the per-shard attention: 'splash' (the tiled
    TPU flash kernel — without it the dense oracle materializes [T, T]
    scores over the FULL gathered sequence, defeating CP exactly at the
    context lengths it exists for), 'reference', or 'auto' (splash on
    TPU when shapes allow)."""
    from jax.sharding import PartitionSpec as P

    rows = ("data", "fsdp")
    T = q.shape[1]
    _, S, tensor = cp_axes(mesh)
    hq_l = q.shape[2] // tensor // S  # local heads after the swap
    hkv_l = k.shape[2] // tensor // S
    if local_impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        splash_shapes = (
            T >= 128 and T % 128 == 0 and hq_l % max(hkv_l, 1) == 0
        )
        local_impl = "splash" if (on_tpu and splash_shapes) else "reference"

    def one_row(q1, k1, v1, s1, p1):
        if local_impl == "splash":
            return splash_packed_attention(
                q1, k1, v1, s1, p1, softmax_scale=softmax_scale
            )
        return reference_packed_attention(
            q1, k1, v1, s1, p1, softmax_scale=softmax_scale
        )

    def local(q, k, v, seg, pos):
        # per shard: q [R_l, C, Hq_t, hd] with C = T/S, Hq_t = Hq/tensor.
        # seq -> heads swap: [R_l, T, Hq_t/S, hd]
        q = jax.lax.all_to_all(q, "seq", split_axis=2, concat_axis=1, tiled=True)
        k = jax.lax.all_to_all(k, "seq", split_axis=2, concat_axis=1, tiled=True)
        v = jax.lax.all_to_all(v, "seq", split_axis=2, concat_axis=1, tiled=True)
        # mask metadata is tiny ([R_l, T] int32): gather it whole.
        seg_f = jax.lax.all_gather(seg, "seq", axis=1, tiled=True)
        pos_f = jax.lax.all_gather(pos, "seq", axis=1, tiled=True)
        out = jax.vmap(one_row)(q, k, v, seg_f, pos_f)
        # heads -> seq swap back: [R_l, C, Hq_t, hd]
        return jax.lax.all_to_all(
            out, "seq", split_axis=1, concat_axis=2, tiled=True
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(rows, "seq", "tensor", None),
            P(rows, "seq", "tensor", None),
            P(rows, "seq", "tensor", None),
            P(rows, "seq"),
            P(rows, "seq"),
        ),
        out_specs=P(rows, "seq", "tensor", None),
        check_vma=False,
    )(q, k, v, segment_ids, positions)


def ulysses_ok(mesh, r: int, t: int, hq: int, hkv: int) -> bool:
    """Shape/mesh divisibility for ulysses_packed_attention: the per-
    tensor-shard head counts must further divide the seq axis."""
    rows, seq, tensor = cp_axes(mesh)
    if seq <= 1 or r % rows or t % seq:
        return False
    if hq % tensor or hkv % tensor:
        return False
    hq_t, hkv_t = hq // tensor, hkv // tensor
    return (
        hq_t % seq == 0
        and hkv_t % seq == 0
        and (hq_t // seq) % (hkv_t // seq) == 0
    )
