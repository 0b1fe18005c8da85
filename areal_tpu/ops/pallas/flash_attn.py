"""Segment-aware blocked flash attention for packed varlen batches (Pallas/TPU).

TPU-native replacement for the reference's flash-attn varlen kernels
(realhf/impl/model/modules/attn.py:272-289): instead of cu_seqlens, a packed
token stream carries *segment ids* (0 = padding) and within-sequence
positions. The kernel computes online-softmax attention over (block_q,
block_k) tiles with two kinds of tile skipping:

- causal skip: tile (i, j) is skipped when every kv index in j exceeds every
  q index in i (valid because sequences are packed contiguously with
  ascending positions, so position-causality implies stream-causality);
- masking inside live tiles uses (same segment) & (q_pos >= kv_pos).

GQA is handled by gridding over q heads and indexing the shared kv head
(h // group) in the BlockSpec index map; the dkv backward grids over kv
heads and accumulates the whole group in scratch so dk/dv HBM traffic is
[Hkv, T, d], not [Hq, T, d]. head_dim is zero-padded to a lane multiple (128).

Forward saves the logsumexp rows; backward recomputes probabilities per
tile (standard flash backward) with two kernels: dq (grid over q tiles,
inner loop kv) and dkv (grid over kv tiles, inner loop q).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _block_size(t: int, preferred: int = 512) -> int:
    b = preferred
    while b >= LANES:
        if t % b == 0:
            return b
        b //= 2
    raise ValueError(f"sequence length {t} is not a multiple of {LANES}")


def _pad_head_dim(x: jnp.ndarray) -> jnp.ndarray:
    d = x.shape[-1]
    dp = ((d + LANES - 1) // LANES) * LANES
    if dp == d:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, dp - d)]
    return jnp.pad(x, pad)


def _tile_mask(qseg, kseg, qpos, kpos):
    """[bq, bk] boolean validity mask from (1, b)-shaped ref reads."""
    qs = qseg.reshape(-1, 1)
    ks = kseg.reshape(1, -1)
    qp = qpos.reshape(-1, 1)
    kp = kpos.reshape(1, -1)
    return (qs == ks) & (qp >= kp) & (qs > 0)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    qseg_ref, kseg_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
    out_ref, lse_ref, m_s, l_s, acc_s, *, scale, bq, bk,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    j_last = ((i + 1) * bq - 1) // bk

    @pl.when(j <= j_last)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(qseg_ref[:], kseg_ref[:], qpos_ref[:], kpos_ref[:])
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[:, :1]  # [bq, 1]
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [bq, bk] f32
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == j_last)
    def _finalize():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_s[:] / safe_l).astype(out_ref.dtype)
        lse_ref[0, 0] = (m_s[:, :1] + jnp.log(safe_l))[:, 0]


def _fwd(scale, interpret, group, q, k, v, seg, pos):
    """q: [Hq, T, dp], k/v: [Hkv, T, dp], seg/pos: [1, T] -> (out, lse)."""
    hq, t, dp = q.shape
    bq = _block_size(t)
    bk = _block_size(t)
    grid = (hq, t // bq, t // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, bk), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, bq), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, bk), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, bq, dp), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dp), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hq, t, dp), q.dtype),
            jax.ShapeDtypeStruct((hq, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seg, seg, pos, pos, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    qseg_ref, kseg_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
    dout_ref, lse_ref, delta_ref, dq_ref, dq_s, *, scale, bq, bk,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    j_last = ((i + 1) * bq - 1) // bk

    @pl.when(j <= j_last)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(qseg_ref[:], kseg_ref[:], qpos_ref[:], kpos_ref[:])
        lse = lse_ref[0].reshape(-1, 1)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dout = dout_ref[0]
        dp = jax.lax.dot_general(
            dout, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0].reshape(-1, 1)
        ds = p * (dp - delta) * scale  # [bq, bk] f32
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == j_last)
    def _finalize():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(
    qseg_ref, kseg_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
    dout_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s,
    *, scale, bq, bk, nq,
):
    # Grid: (Hkv, kv tiles, group * q tiles). The inner dimension walks
    # (g, i) pairs so dk/dv accumulate over the whole GQA group in scratch
    # and are written once per kv head — [Hkv, T, dp] HBM traffic, not
    # [Hq, T, dp].
    j = pl.program_id(1)  # kv tile
    c = pl.program_id(2)  # g * nq + i
    nc = pl.num_programs(2)
    i = c % nq

    i_first = (j * bk) // bq

    @pl.when(c == i_first)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(i >= i_first)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(qseg_ref[:], kseg_ref[:], qpos_ref[:], kpos_ref[:])
        lse = lse_ref[0].reshape(-1, 1)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dout = dout_ref[0]
        # dv += p^T @ dout
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(dout.dtype), dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dout, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0].reshape(-1, 1)
        ds = p * (dp - delta) * scale  # [bq, bk]
        # dk += ds^T @ q
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(c == nc - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(scale, interpret, group, q, k, v, seg, pos, out, lse, dout):
    hq, t, dp = q.shape
    bq = _block_size(t)
    bk = _block_size(t)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk),
        grid=(hq, t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, bk), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, bq), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, bk), lambda h, i, j: (0, j)),
            pl.BlockSpec((1, bq, dp), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dp), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bq, dp), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, t, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seg, seg, pos, pos, q, k, v, dout, lse, delta)

    # dk/dv accumulated over the GQA group inside the kernel (grid walks
    # (g, i) pairs in its inner dimension); outputs are [Hkv, T, dp].
    nq = t // bq
    hkv = hq // group
    qh = lambda hk, c: hk * group + c // nq
    qi = lambda c: c % nq
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, nq=nq),
        grid=(hkv, t // bk, group * nq),
        in_specs=[
            pl.BlockSpec((1, bq), lambda hk, j, c: (0, qi(c))),
            pl.BlockSpec((1, bk), lambda hk, j, c: (0, j)),
            pl.BlockSpec((1, bq), lambda hk, j, c: (0, qi(c))),
            pl.BlockSpec((1, bk), lambda hk, j, c: (0, j)),
            pl.BlockSpec((1, bq, dp), lambda hk, j, c: (qh(hk, c), qi(c), 0)),
            pl.BlockSpec((1, bk, dp), lambda hk, j, c: (hk, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda hk, j, c: (hk, j, 0)),
            pl.BlockSpec((1, bq, dp), lambda hk, j, c: (qh(hk, c), qi(c), 0)),
            pl.BlockSpec((1, 1, bq), lambda hk, j, c: (qh(hk, c), 0, qi(c))),
            pl.BlockSpec((1, 1, bq), lambda hk, j, c: (qh(hk, c), 0, qi(c))),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dp), lambda hk, j, c: (hk, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda hk, j, c: (hk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hkv, t, dp), q.dtype),
            jax.ShapeDtypeStruct((hkv, t, dp), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dp), jnp.float32),
            pltpu.VMEM((bk, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seg, seg, pos, pos, q, k, v, dout, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash_core(scale, interpret, group, q, k, v, seg, pos):
    out, _ = _fwd(scale, interpret, group, q, k, v, seg, pos)
    return out


def _flash_core_fwd(scale, interpret, group, q, k, v, seg, pos):
    out, lse = _fwd(scale, interpret, group, q, k, v, seg, pos)
    return out, (q, k, v, seg, pos, out, lse)


def _flash_core_bwd(scale, interpret, group, res, dout):
    q, k, v, seg, pos, out, lse = res
    dq, dk, dv = _bwd(scale, interpret, group, q, k, v, seg, pos, out, lse, dout)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_packed_attention(
    q: jnp.ndarray,  # [T, Hq, hd]
    k: jnp.ndarray,  # [T, Hkv, hd]
    v: jnp.ndarray,  # [T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [T] int32, 0 = padding
    positions: jnp.ndarray,  # [T] int32
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    t, hq, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = float(softmax_scale) if softmax_scale is not None else hd**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qt = _pad_head_dim(q.transpose(1, 0, 2))
    kt = _pad_head_dim(k.transpose(1, 0, 2))
    vt = _pad_head_dim(v.transpose(1, 0, 2))
    seg = segment_ids.reshape(1, t).astype(jnp.int32)
    pos = positions.reshape(1, t).astype(jnp.int32)

    out = _flash_core(scale, bool(interpret), group, qt, kt, vt, seg, pos)
    return out[..., :hd].transpose(1, 0, 2)
