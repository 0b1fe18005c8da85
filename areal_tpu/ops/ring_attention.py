"""Ring attention: sequence/context parallelism with O(T/S) memory.

The megatron-SP path (ops/attention.sharded_splash_attention) shards
activations on the `seq` axis but all-gathers the FULL key/value stream
into every shard before the kernel — per-device attention memory stays
O(T). Ring attention (Liu et al., 2023; the TPU-native long-context
recipe) keeps KV sharded too: each seq shard holds one KV chunk, and
chunks rotate around the `seq` axis with `lax.ppermute` while each
device folds them into an online-softmax accumulator — per-device memory is
O(T/S), which is what makes 32k+ packed contexts trainable.

Packed-varlen semantics match reference_packed_attention exactly: the
(same segment) AND (causal by position) mask travels with the KV chunk
(segment ids + positions rotate alongside), so packing is preserved
across shard boundaries. Fully-padding rows produce finite garbage
masked by downstream losses — the same convention as every other impl.

Differentiable end-to-end: the ring is a `lax.scan` over S steps and
`ppermute`'s transpose is the reverse rotation, so the backward pass is
the standard ring-attention backward (gradients counter-rotate) derived
by autodiff — no custom VJP to maintain.

Reference counterpart: the flash-attn varlen path under megatron CP
(realhf/impl/model/modules/attn.py:272-289) — the reference shards
sequences only across DP (no CP); this is a capability the TPU design
adds for its long-context mandate.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from areal_tpu.ops.attention import NEG_INF


def _ring_chunk_attention(q, k, v, seg_q, pos_q, seg_kv, pos_kv, scale,
                          m, l, acc):
    """Fold one KV chunk into the online-softmax state.

    q: [R, Cq, Hkv, G, hd] f32 (pre-grouped); k/v: [R, Ck, Hkv, hd];
    m/l: [R, Hkv, G, Cq]; acc: [R, Hkv, G, Cq, hd]."""
    scores = jnp.einsum("rqhgd,rkhd->rhgqk", q, k.astype(jnp.float32)) * scale
    same = seg_q[:, :, None] == seg_kv[:, None, :]
    causal = pos_q[:, :, None] >= pos_kv[:, None, :]
    valid = (seg_q[:, :, None] > 0) & (seg_kv[:, None, :] > 0)
    mask = (same & causal & valid)[:, None, None]  # [R,1,1,Cq,Ck]
    scores = jnp.where(mask, scores, NEG_INF)

    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "rhgqk,rkhd->rhgqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def ring_packed_attention(
    q: jnp.ndarray,  # [R, T, Hq, hd] (T sharded on `seq`)
    k: jnp.ndarray,  # [R, T, Hkv, hd]
    v: jnp.ndarray,  # [R, T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [R, T]
    positions: jnp.ndarray,  # [R, T]
    mesh,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Packed GQA attention with the KV stream ring-rotated over the
    mesh's `seq` axis. Callers must check `ring_ok` first."""
    from jax.sharding import PartitionSpec as P

    hd = q.shape[-1]
    scale = float(softmax_scale) if softmax_scale is not None else hd**-0.5
    S = mesh.shape["seq"]
    rows = ("data", "fsdp")

    def local(q, k, v, seg, pos):
        R, C, Hq, _ = q.shape
        Hkv = k.shape[2]
        G = Hq // Hkv
        qg = (
            q.reshape(R, C, Hkv, G, hd).astype(jnp.float32)
        )
        m = jnp.full((R, Hkv, G, C), NEG_INF, jnp.float32)
        l = jnp.zeros((R, Hkv, G, C), jnp.float32)
        acc = jnp.zeros((R, Hkv, G, C, hd), jnp.float32)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def step(carry, _):
            k_c, v_c, seg_c, pos_c, m, l, acc = carry
            m, l, acc = _ring_chunk_attention(
                qg, k_c, v_c, seg, pos, seg_c, pos_c, scale, m, l, acc
            )
            # Rotate the KV chunk (with its mask metadata) to the next
            # shard; after S steps every shard has folded every chunk.
            k_c = jax.lax.ppermute(k_c, "seq", perm)
            v_c = jax.lax.ppermute(v_c, "seq", perm)
            seg_c = jax.lax.ppermute(seg_c, "seq", perm)
            pos_c = jax.lax.ppermute(pos_c, "seq", perm)
            return (k_c, v_c, seg_c, pos_c, m, l, acc), None

        (k_c, v_c, seg_c, pos_c, m, l, acc), _ = jax.lax.scan(
            step, (k, v, seg, pos, m, l, acc), None, length=S
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [R,Hkv,G,C,hd]
        return out.transpose(0, 3, 1, 2, 4).reshape(R, C, Hq, hd).astype(q.dtype)

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


def ring_ok(mesh, r: int, t: int, hq: int, hkv: int) -> bool:
    """Shape/mesh divisibility for ring_packed_attention."""
    from areal_tpu.ops.attention import cp_axes

    rows, seq, tensor = cp_axes(mesh)
    return (
        seq > 1
        and r % rows == 0
        and t % seq == 0
        and hq % tensor == 0
        and hkv % tensor == 0
        and (hq // tensor) % (hkv // tensor) == 0
    )
