"""int8 paged-attention decode kernel (Pallas, TPU).

The stock jax paged-attention kernel handles quantized pools by
broadcasting the per-token scales to full head_dim in f32 BEFORE
pallas_call (jax .../paged_attention_kernel.py:421-431) — materializing
2x the bf16 pool's bytes in HBM per call and streaming 4 B/elem of
scales, which inverts the bandwidth win int8 exists for. This kernel
streams the pool AS STORED:

  data   [Hkv, N, pg, hd] int8
  scales [Hkv, N, pg]     f32   (squeezed; pg is the lane axis)

and applies the scales in VMEM, so HBM traffic per (kv head, page) is
pg*(hd + 4) bytes vs 2*pg*hd for a bf16 pool — ~1.94x less at hd=128.

Design (counterpart of the stock kernel's role, not its structure —
engine/paged.py docstring maps this to SGLang/vLLM paged attention in
the reference, realhf/impl/model/backend/sglang.py):

- Grid (B, Hkv, P) with P minor: flash-style online softmax
  (running max / sum / weighted accumulator in VMEM scratch) across a
  sequence's pages; the output block is written once, on the last page.
- Page blocks are selected straight out of the global pool by
  scalar-prefetched page_indices driving the BlockSpec index_map — no
  gather materialization, and Pallas double-buffers the page DMAs
  against compute automatically.
- GQA runs as one MQA problem per kv head: the q block is that head's
  contiguous group of q heads (same convention as the engine's
  reshape(B, Hkv, group, hd) and ops/attention's splash adoption).
- Pages at or past a sequence's length are skipped via pl.when (their
  DMA still runs; bounding that needs manual copies, deliberately
  avoided for simplicity) and partially-filled pages mask per-token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Dequant convention shared with engine/paged.quantize_kv (and the stock
# kernel's quantization_utils): x ~= int8 * scale / 127.5. Re-exported
# from the one dependency-free source of truth (ops/quant_const) —
# structural identity pinned in tests/engine/test_kv_int8.py.
from areal_tpu.ops.quant_const import KV_INT8_MAX  # noqa: F401  (re-export)

_NEG_INF = -1e30  # finite: keeps exp() clean for fully-masked positions
_LANES = 128


def int8_paged_kernel_ok(page_size: int, head_dim: int) -> bool:
    """Shape gate: hd rides the lane axis of the data blocks and pg the
    lane axis of the scales blocks, so both must be 128-aligned (the
    engine defaults — page_size=128, head_dim=128 — qualify)."""
    return head_dim % _LANES == 0 and page_size % _LANES == 0


def _kernel(lengths_ref, pi_ref, q_ref, kd_ref, ks_ref, vd_ref, vs_ref,
            o_ref, m_sc, l_sc, acc_sc):
    b = pl.program_id(0)
    p = pl.program_id(2)
    pg = kd_ref.shape[1]

    @pl.when(p == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, m_sc.dtype)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = lengths_ref[b]

    @pl.when(p * pg < length)
    def _compute():
        # The per-token scales sit with pg on the lane axis ([1, pg]
        # blocks), which is also the lane axis of the [g, pg] scores and
        # probabilities — so they are applied THERE, as a sublane
        # broadcast, instead of being turned into a [pg, 1] column to
        # dequantize the [pg, hd] tiles (a lane->sublane relayout):
        # q.(k*ks)^T == (q.k^T)*ks and p.(v*vs) == (p*vs).v exactly.
        q = q_ref[...].astype(jnp.float32)  # [g, hd], pre-scaled
        s = jax.lax.dot_general(
            q, kd_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (ks_ref[...] * (1.0 / KV_INT8_MAX))  # [g, pg]
        pos = p * pg + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)

        m_prev = m_sc[...][:, :1]  # [g, 1]
        l_prev = l_sc[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # [g, 1]
        p_ij = jnp.exp(s - m_new)  # [g, pg]
        l_new = l_prev * alpha + jnp.sum(p_ij, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p_ij * (vs_ref[...] * (1.0 / KV_INT8_MAX)),
            vd_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(p == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_sc[...][:, :1], 1e-30)
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_paged_decode_attention(
    qs,  # [B, Hq, hd] float, already multiplied by the softmax scale
    k_pool,  # (data [Hkv, N, pg, hd] int8, scales [Hkv, N, pg] f32)
    v_pool,
    lengths,  # [B] int32, INCLUDING the token written this step
    page_indices,  # [B, P] int32
    interpret: bool = False,
):
    kd, ks = k_pool
    vd, vs = v_pool
    B, Hq, hd = qs.shape
    Hkv, _, pg, _ = kd.shape
    P = page_indices.shape[1]
    g = Hq // Hkv

    # Mosaic wants the last two dims of every block to be (8, 128)
    # multiples or the array's own: q/out go in as [B, Hkv, g, hd] (the
    # group is a whole dim, g is rarely a multiple of 8) and the scales
    # as [Hkv, N, 1, pg] — both free reshapes of the stored layouts.
    def page_map(b, h, p, lr, pr):
        # Block index: h-th kv head, pool page for (b, p).
        return (h, pr[b, p], 0, 0)

    def head_map(b, h, p, lr, pr):
        return (b, h, 0, 0)

    data_spec = pl.BlockSpec((None, 1, pg, hd), page_map)
    scale_spec = pl.BlockSpec((None, None, 1, pg), page_map)
    head_spec = pl.BlockSpec((None, None, g, hd), head_map)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, P),
            in_specs=[head_spec, data_spec, scale_spec, data_spec,
                      scale_spec],
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((g, _LANES), jnp.float32),  # running max
                pltpu.VMEM((g, _LANES), jnp.float32),  # running sum
                pltpu.VMEM((g, hd), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), qs.dtype),
        interpret=interpret,
    )(lengths, page_indices, qs.reshape(B, Hkv, g, hd),
      kd, ks[:, :, None], vd, vs[:, :, None])
    return out.reshape(B, Hq, hd)
