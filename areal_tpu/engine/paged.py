"""Paged KV-cache machinery for the serving engine.

TPU-native counterpart of SGLang/vLLM's paged attention memory manager
(the reference serves through patched SGLang — realhf/impl/model/backend/
sglang.py:192-500 — whose RadixAttention allocates KV in fixed-size pages
from a token pool). Here:

- KV lives in a global page pool `[L, Hkv, n_pages, page_size, hd]`
  shared by every slot; a host-side `PageAllocator` hands out pages and a
  per-slot page table `[B, pages_per_seq]` maps sequence position ->
  pool page. Memory scales with *tokens in flight*, not
  `batch * max_seq_len`, which is what makes 31k-token generation
  (benchmark/verl_v0_3_0_post1_76084d3/README.md:38-44) servable.
- Decode attention dispatches to jax's TPU Pallas paged-attention kernel
  (jax.experimental.pallas.ops.tpu.paged_attention) on TPU backends and
  to a gather + masked-softmax XLA fallback elsewhere (the CPU oracle).
- Page 0 is a reserved trash page: writes for inactive slots and
  prompt-padding overflow are routed there so a freed-and-reused page can
  never be corrupted by a stale slot.

Everything here is shape-static: the pool, the page table width, and the
decode block are compiled once per engine lifetime.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from areal_tpu.base import env_registry
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import _mlp, _norm
from areal_tpu.ops.wquant import qmat
from areal_tpu.ops.norms import rms_norm
from areal_tpu.ops.rotary import apply_rotary, rotary_cos_sin, rotary_inv_freq
from areal_tpu.ops.sampling import NEG_INF

TRASH_PAGE = 0  # reserved sink page, never allocated
# top-k requests at or below this threshold sample through lax.top_k
# instead of a full-vocab sort (warp_sample tier 1).
TOPK_FAST_MAX = 128


def pages_needed(n_tokens: int, page_size: int) -> int:
    return max(1, -(-n_tokens // page_size))


# ----------------------------------------------------------------------
# int8 KV pools
# ----------------------------------------------------------------------
#
# With kv_cache_dtype="int8" a pool is a (data, scales) pair instead of a
# bare array: data [L, Hkv, N, pg, hd] int8, scales [L, Hkv, N, pg] f32 —
# per-token-per-head absmax over the head dim, stored WITHOUT a trailing
# size-1 dim (TPU tiled layouts pad the minor dim to 128 lanes, so a
# [.., pg, 1] f32 array can physically occupy 128x its logical bytes;
# squeezed, pg=128 IS the lane dim). Decode is HBM-bandwidth-bound
# streaming KV pages, so int8 halves the pool's resident bytes — double
# the tokens-in-flight a pool budget holds (fewer preempt/resubmit
# cycles at 16-32k contexts) — and halves the gathered bytes on the XLA
# attention path. NOTE the stock Pallas kernel is NOT the fast path for
# int8: it broadcasts the scales to full head_dim in f32 before
# pallas_call (paged_attention_kernel.py:421-431), materializing 2x the
# bf16 pool per call, so 'auto' keeps quantized pools on the XLA path
# (see paged_decode_attention). A from-scratch kernel streaming
# [.., pg, 1] scales is the follow-up. The reference's serving backend
# has no KV quantization (realhf/impl/model/backend/sglang.py). Pools
# stay plain arrays when not quantized; every helper accepts both.

# Dequant convention: x ~= int8 * scale / 127.5. ONE source of truth
# (ops/quant_const — dependency-free, so importing this module still
# doesn't pull the Pallas stack; all kernel imports here stay lazy, at
# the branches that dispatch to them). The structural identity of this
# re-export with the kernel's is pinned in tests/engine/test_kv_int8.py.
from areal_tpu.ops.quant_const import KV_INT8_MAX  # noqa: F401  (re-export)


def kv_pool_data(pool) -> jnp.ndarray:
    """The data leaf of a pool (bare array, or (data, scales) pair)."""
    return pool[0] if isinstance(pool, tuple) else pool


def quantize_kv(x: jnp.ndarray):
    """[..., hd] float -> (int8 [..., hd], f32 scales [..., 1]).

    Matches the kernel's from_int8 dequant (w * s / 127.5). The exact-max
    element clips to 127 (~0.4% error on that single element) instead of
    wrapping at rint(127.5) = 128."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-6)
    w = jnp.clip(jnp.rint(x32 * (KV_INT8_MAX / s)), -127, 127)
    return w.astype(jnp.int8), s


def dequantize_kv(w: jnp.ndarray, s: jnp.ndarray, dtype) -> jnp.ndarray:
    return (w.astype(jnp.float32) * (s / KV_INT8_MAX)).astype(dtype)


def gather_kv_tokens(pool, page_ids, n_tokens: int):
    """Gather one sequence's KV out of the pool in token-major order
    (the disaggregated-serving handoff export, engine/kv_handoff.py).

    ``page_ids`` are the sequence's pages in order; tokens beyond
    ``n_tokens`` (final-page padding) are dropped. Plain pools return
    ``[L, Hkv, n_tokens, hd]``; int8 pools return the
    ``(data, scales [L, Hkv, n_tokens])`` pair. Dispatch-only — the
    caller device_gets the (small) result off the serve loop."""
    idx = jnp.asarray(page_ids, jnp.int32)

    def g(arr, has_hd: bool):
        x = arr[:, :, idx]  # [L, Hkv, P, pg, (hd)]
        L, H = x.shape[0], x.shape[1]
        if has_hd:
            return x.reshape(L, H, -1, x.shape[-1])[:, :, :n_tokens]
        return x.reshape(L, H, -1)[:, :, :n_tokens]

    if isinstance(pool, tuple):
        return g(pool[0], True), g(pool[1], False)
    return g(pool, True)


class PageAllocator:
    """Host-side free-list allocator over the pool's page indices.

    Page 0 (TRASH_PAGE) is reserved. Same role as SGLang's
    TokenToKVPool allocator; transparently simple because the device
    side only ever sees the page-table indices."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (one is the trash page)")
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, 0, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages, or None (and no state change) if unavailable."""
        if n > len(self._free):
            return None
        got = self._free[-n:][::-1]
        del self._free[len(self._free) - n:]
        return got

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("freeing the trash page")
            self._free.append(p)


# ----------------------------------------------------------------------
# Paged decode attention
# ----------------------------------------------------------------------


def paged_attention_kernel_ok(page_size: int, head_dim: int, pages_per_seq: int) -> bool:
    """Shape gate for jax's TPU paged-attention Pallas kernel: the kernel
    tiles (page, hd) blocks into VMEM, so lanes (hd) must be 128-aligned
    and sublanes (page) 8-aligned."""
    return head_dim % 128 == 0 and page_size % 8 == 0 and pages_per_seq >= 1


def _pages_per_compute_block(pages_per_seq: int, cap: int = 8) -> int:
    d = min(cap, pages_per_seq)
    while pages_per_seq % d:
        d -= 1
    return d


def _paged_attention_xla(q, k_pages, v_pages, lengths, page_indices, scale):
    """Gather + masked softmax oracle/fallback.

    q: [B, Hq, hd]; k/v_pages: [Hkv, N, pg, hd] (or int8 (data, scales)
    pairs — gathered quantized, dequantized after the gather so the bytes
    moved stay halved); lengths: [B] valid tokens (INCLUDING the one
    written this step); page_indices: [B, P]."""
    B, Hq, hd = q.shape
    Hkv, _, pg, _ = kv_pool_data(k_pages).shape
    P = page_indices.shape[1]
    group = Hq // Hkv

    def gather(pool):
        # [Hkv, B, P, pg, hd] -> [B, P*pg, Hkv, hd]
        if isinstance(pool, tuple):
            d, s = pool  # s: [Hkv, N, pg] squeezed
            g = dequantize_kv(d[:, page_indices],
                              s[:, page_indices][..., None], jnp.float32)
        else:
            g = pool[:, page_indices]
        return g.transpose(1, 2, 3, 0, 4).reshape(B, P * pg, Hkv, hd)

    with jax.named_scope("kv_read"):
        k = gather(k_pages)
        v = gather(v_pages)
    qg = q.reshape(B, Hkv, group, hd).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(P * pg)[None, :]
    mask = pos < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


def _choose_paged_decode_impl(
    quantized: bool, page_size: int, head_dim: int, pages_per_seq: int,
    tp_ok: bool,
):
    """(implementation 'auto' runs, why)."""
    if jax.default_backend() != "tpu":
        return "xla", f"backend is {jax.default_backend()}, not tpu"
    if not tp_ok:
        return "xla", "head counts do not divide the mesh's tensor axis"
    if quantized:
        # Import inside the TPU arm: keeps the Pallas stack off CPU-only
        # import paths.
        from areal_tpu.ops.pallas.paged_decode_int8 import int8_paged_kernel_ok

        if int8_paged_kernel_ok(page_size, head_dim):
            return "int8_kernel", "tpu backend, int8 pool, 128-aligned"
        return "xla", "int8 kernel needs page_size and head_dim % 128 == 0"
    if paged_attention_kernel_ok(page_size, head_dim, pages_per_seq):
        return "kernel", "tpu backend, lane/sublane-aligned pages"
    return "xla", "kernel needs head_dim % 128 == 0 and page_size % 8 == 0"


def resolve_paged_decode_impl(
    impl: str,
    quantized: bool,
    page_size: int,
    head_dim: int,
    pages_per_seq: int,
    tp_ok: bool = True,
) -> str:
    """The paged-decode implementation that runs (trace-time static
    decision, mirroring ops/attention.resolve_attn_impl).
    Explicit impls pass through untouched.

    int8 pools use OUR kernel (ops/pallas/paged_decode_int8) on TPU:
    the stock kernel broadcasts the scales to full head_dim in f32
    before pallas_call (jax .../paged_attention_kernel.py:421-431),
    materializing 2x the bf16 pool per call. impl='kernel' stays
    available for an explicit A/B. Off-TPU (and whenever shapes or the
    TP head split disqualify a kernel) everything resolves to the XLA
    gather path. Every decision is said once, and an 'auto' that lands
    on the gather path ON a TPU is a warning (utils/jaxenv.say_dispatch)."""
    from areal_tpu.utils.jaxenv import say_dispatch

    ran, why = impl, "requested"
    if impl == "auto":
        ran, why = _choose_paged_decode_impl(
            quantized, page_size, head_dim, pages_per_seq, tp_ok
        )
    say_dispatch(
        "paged_decode_impl", impl, ran, why, quantized=quantized,
        page_size=page_size, head_dim=head_dim, pages_per_seq=pages_per_seq,
    )
    return ran


def paged_decode_attention(
    q,  # [B, Hq, hd]
    k_pages,  # [Hkv, N, pg, hd]
    v_pages,
    lengths,  # [B] int32, incl. the token written this step
    page_indices,  # [B, P] int32
    softmax_scale: Optional[float] = None,
    mesh=None,
    impl: str = "auto",
):
    """Single-step decode attention over the paged pool.

    impl: 'kernel' (Pallas), 'xla', or 'auto' (kernel on TPU when shapes
    allow). With a mesh whose `tensor` axis is >1, the Pallas kernel runs
    under shard_map with heads sharded on `tensor` (pallas_call is opaque
    to the SPMD partitioner — same treatment as sharded_splash_attention,
    ops/attention.py). int8 (data, scales) pools flow to the kernel as
    QuantizedTensor (fused dequant in VMEM) and to the XLA path as a
    gather-then-dequantize."""
    B, Hq, hd = q.shape
    quantized = isinstance(k_pages, tuple)
    Hkv, _, pg, _ = kv_pool_data(k_pages).shape
    P = page_indices.shape[1]
    scale = float(softmax_scale) if softmax_scale is not None else hd**-0.5
    tensor_size = mesh.shape.get("tensor", 1) if mesh is not None else 1
    # Under tensor parallelism the kernel runs per shard with heads split
    # on `tensor` — impossible when the head counts don't divide (the
    # pool then replicates, ServingEngine._ensure_pool); the GSPMD-
    # partitionable einsum path handles that layout instead.
    tp_ok = Hkv % tensor_size == 0 and Hq % tensor_size == 0
    if impl in ("kernel", "int8_kernel") and not tp_ok:
        raise ValueError(
            f"paged-attention kernel under tensor={tensor_size} needs head "
            f"counts divisible by it (Hq={Hq}, Hkv={Hkv}); use impl='xla'"
        )
    impl = resolve_paged_decode_impl(impl, quantized, pg, hd, P, tp_ok)
    if impl == "xla":
        return _paged_attention_xla(q, k_pages, v_pages, lengths, page_indices, scale)
    if impl == "int8_kernel":
        if not quantized:
            raise ValueError("impl='int8_kernel' needs an int8 (data, "
                             "scales) pool; got a plain array")
        from areal_tpu.ops.pallas.paged_decode_int8 import (
            int8_paged_decode_attention,
        )

        qs = q * jnp.asarray(scale, q.dtype)
        interp = jax.default_backend() != "tpu"
        if tensor_size > 1:
            from jax.sharding import PartitionSpec as Pt

            pool_spec = (Pt("tensor", None, None, None),
                         Pt("tensor", None, None))
            out = jax.shard_map(
                functools.partial(int8_paged_decode_attention,
                                  interpret=interp),
                mesh=mesh,
                in_specs=(Pt(None, "tensor", None), pool_spec, pool_spec,
                          Pt(None), Pt(None, None)),
                out_specs=Pt(None, "tensor", None),
                check_vma=False,
            )(qs, k_pages, v_pages, lengths, page_indices)
        else:
            out = int8_paged_decode_attention(
                qs, k_pages, v_pages, lengths, page_indices,
                interpret=interp,
            )
        return out.astype(q.dtype)

    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention_kernel as pak,
        quantization_utils as pqu,
    )

    ppcb = _pages_per_compute_block(P)
    # int8 pools: q stays in its float dtype (the kernel dequantizes KV
    # to bf16 in VMEM); otherwise match the pool dtype as before.
    qs = q * jnp.asarray(scale, q.dtype)
    if not quantized:
        qs = qs.astype(k_pages.dtype)

    def kernel(qq, kk, vv, ll, pi):
        if isinstance(kk, tuple):
            # Stock kernel wants [.., pg, 1] scales; ours are squeezed.
            kk = pqu.QuantizedTensor(kk[0], kk[1][..., None])
            vv = pqu.QuantizedTensor(vv[0], vv[1][..., None])
        return pak.paged_attention(
            qq, kk, vv, ll, pi, pages_per_compute_block=ppcb
        )

    tensor = mesh.shape.get("tensor", 1) if mesh is not None else 1
    if tensor > 1:
        from jax.sharding import PartitionSpec as Pt

        pool_spec = Pt("tensor", None, None, None)
        if quantized:  # spec subtree mirrors (data 4-D, scales 3-D)
            pool_spec = (pool_spec, Pt("tensor", None, None))
        out = jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(
                Pt(None, "tensor", None),
                pool_spec,
                pool_spec,
                Pt(None),
                Pt(None, None),
            ),
            out_specs=Pt(None, "tensor", None),
            check_vma=False,
        )(qs, k_pages, v_pages, lengths, page_indices)
    else:
        out = kernel(qs, k_pages, v_pages, lengths, page_indices)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# Paged decode step (one token per slot through all layers)
# ----------------------------------------------------------------------


def _paged_decode_layer(
    x, lp, cfg, cos, sin, kp_l, vp_l, w_pidx, w_off, page_indices, lengths,
    cdt, mesh, attn_impl,
):
    """One layer for one new token per slot against the paged pool.

    x: [B, D]; kp_l/vp_l: [Hkv, N, pg, hd]; w_pidx/w_off: [B] write page +
    offset (already trash-routed for inactive slots); lengths: [B] fill
    count BEFORE this token. Mirrors models/generation._decode_layer."""
    B, _ = x.shape
    h = _norm(x, lp["ln1"], cfg)
    a = lp["attn"]
    q = qmat(h, a["wq"], cdt)
    k = qmat(h, a["wk"], cdt)
    v = qmat(h, a["wv"], cdt)
    if "bq" in a:
        q = q + a["bq"].astype(cdt)
        k = k + a["bk"].astype(cdt)
        v = v + a["bv"].astype(cdt)
    q = q.reshape(B, cfg.n_q_heads, cfg.head_dim)
    k = k.reshape(B, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
    if cos is not None:
        q = apply_rotary(q, cos, sin, cfg.rotary_interleaved)
        k = apply_rotary(k, cos, sin, cfg.rotary_interleaved)
    # Scatter the new token's K/V into its page. [Hkv, B, hd] values at
    # (page w_pidx[b], offset w_off[b]) per slot; allocator guarantees
    # active slots' pages are distinct, trash collisions are harmless.
    def scatter(pool, val_t):  # val_t: [Hkv, B, hd]
        if isinstance(pool, tuple):
            w, s = quantize_kv(val_t)
            return (pool[0].at[:, w_pidx, w_off].set(w),
                    pool[1].at[:, w_pidx, w_off].set(s[..., 0]))
        return pool.at[:, w_pidx, w_off].set(val_t.astype(pool.dtype))

    with jax.named_scope("kv_write"):
        kp_l = scatter(kp_l, k.transpose(1, 0, 2))
        vp_l = scatter(vp_l, v.transpose(1, 0, 2))
    with jax.named_scope("decode_kernel"):
        out = paged_decode_attention(
            q, kp_l, vp_l, lengths + 1, page_indices, mesh=mesh,
            impl=attn_impl,
        )
    attn_out = qmat(out.reshape(B, cfg.q_dim), a["wo"], cdt)
    if "bo" in a:
        attn_out = attn_out + a["bo"].astype(cdt)
    x = x + attn_out
    h = _norm(x, lp["ln2"], cfg)
    if cfg.moe is not None:
        from areal_tpu.models.moe import decode_moe_overrides, moe_mlp

        # Decode-time dispatch/capacity differ from training: the
        # capacity formula quantizes badly at decode row counts (C=1
        # drops on any router skew), so decode defaults to dropless —
        # see decode_moe_overrides.
        d_dispatch, d_cap = decode_moe_overrides(cfg)
        m, moe_aux = moe_mlp(
            h, lp["mlp"], cfg, cdt,
            capacity_factor=d_cap, dispatch=d_dispatch,
        )
        aux = {
            "moe_drop_rate": moe_aux["drop_rate"].astype(jnp.float32),
            "moe_router_entropy":
                moe_aux["router_entropy"].astype(jnp.float32),
        }
    else:
        m = _mlp(h, lp["mlp"], cfg, cdt)
        aux = {}
    x = x + m
    return x, kp_l, vp_l, aux


def paged_decode_step(
    params, cfg: TransformerConfig, tokens, k_pages, v_pages, page_indices,
    lengths, active, mesh=None, attn_impl: str = "auto",
    return_moe_stats: bool = False,
):
    """One decode step for all slots. tokens: [B] just-sampled inputs;
    lengths: [B] fill BEFORE this token; active: [B] bool (inactive slots'
    writes are routed to the trash page). Returns (logits, pools); with
    return_moe_stats, also a dict of layer-mean router scalars
    (moe_drop_rate / moe_router_entropy; empty for dense models)."""
    cfg.require_plain_stack("engine/paged.py paged_decode_step")
    cdt = jnp.dtype(cfg.compute_dtype)
    pg = kv_pool_data(k_pages).shape[3]
    B = tokens.shape[0]
    w_pidx = jnp.where(
        active,
        page_indices[jnp.arange(B), lengths // pg],
        TRASH_PAGE,
    ).astype(jnp.int32)
    w_off = jnp.where(active, lengths % pg, 0).astype(jnp.int32)

    x = params["embedding"]["weight"][tokens].astype(cdt)
    if cfg.embedding_multiplier:
        x = x * jnp.asarray(cfg.embedding_multiplier, cdt)
    if cfg.pos_emb == "learned":
        x = x + params["pos_embedding"]["weight"][lengths].astype(cdt)
        cos = sin = None
    else:
        inv_freq = jnp.asarray(
            rotary_inv_freq(
                cfg.head_dim, cfg.rotary_base, cfg.rotary_scaling,
                cfg.rotary_scaling_type, cfg.rotary_scaling_params,
            )
        )
        cos, sin = rotary_cos_sin(lengths, inv_freq)

    def body(x, layer):
        lp, kp, vp = layer
        x, kp, vp, aux = _paged_decode_layer(
            x, lp, cfg, cos, sin, kp, vp, w_pidx, w_off, page_indices,
            lengths, cdt, mesh, attn_impl,
        )
        return x, (kp, vp, aux)

    x, (k_pages, v_pages, aux) = jax.lax.scan(
        body, x, (params["layers"], k_pages, v_pages)
    )
    moe_stats = {k: v.mean() for k, v in aux.items()}  # mean over layers
    x = _norm(x, params["final_norm"], cfg)
    if "head_q" in params:  # int8 decode weights (ops/wquant.py)
        logits = qmat(x, params["head_q"], cdt).astype(jnp.float32)
    else:
        head_w = (
            params["embedding"]["weight"].T
            if cfg.tied_embeddings
            else params["head"]["weight"]
        )
        logits = (x @ head_w.astype(cdt)).astype(jnp.float32)
    if return_moe_stats:
        return logits, k_pages, v_pages, moe_stats
    return logits, k_pages, v_pages


# ----------------------------------------------------------------------
# Chunked prefill (long prompts)
# ----------------------------------------------------------------------


@jax.named_scope("prefill_chunk")
def _chunk_prefill_body(
    params,
    cfg: TransformerConfig,
    tokens,  # [C] chunk token ids, right-padded to the chunk size
    k_pages,
    v_pages,
    page_row,  # [P] the request's page-table row
    start,  # scalar int32: absolute position of tokens[0]
    valid_len,  # scalar int32: valid tokens in this chunk
    attn_impl: str = "auto",
    mesh=None,
):
    """One chunk of ONE long prompt through the paged pool.

    A chunk of C tokens at positions start..start+C-1 is exactly C decode
    rows of the same request with staggered lengths sharing one
    page-table row: every row's K/V scatters into its (page, offset)
    first, then row i's attention masks gathered keys at flat positions
    < start+i+1 — full prefix (earlier chunks, already in the pool) plus
    intra-chunk causal. So this reuses paged_decode_step verbatim, which
    keeps ONE compiled program for any prompt length (the batched
    prefill path compiles per length bucket — ruinous for 16-32k prompts
    with varied lengths; the reference's serving backend chunk-prefills
    long prompts for the same reason).

    Returns (last_logits [V] — the final valid row's, for first-token
    sampling; meaningful only on the prompt's last chunk — k_pages,
    v_pages).

    The C rows run through paged_decode_step in sub-chunks: the TPU
    paged-attention kernel prefetches its [rows, P] page_indices operand
    into SMEM (~1 MB), so rows*P*4 bytes must stay well under that — at
    C=2048 and a 16k-context pool (P~138) a single call is a guaranteed
    compile-time SMEM overflow (measured on v5e: 1,130,496 B > 1,048,576).
    Sub-chunks also keep logits at [sub, V] instead of [C, V] (268 MB at
    C=2048, V=32k): only the selected last-valid row's logits leave the
    scan."""
    C = tokens.shape[0]
    P = page_row.shape[0]
    # Half the 1 MB SMEM for the page-index operand; the rest holds the
    # kernel's other prefetched scalars. AREAL_CHUNK_SMEM_BUDGET overrides
    # for tests (forcing n_sub > 1 on CPU pools too small to need it);
    # read at trace time, so set it before the first call in a process.
    smem_budget = env_registry.get_int("AREAL_CHUNK_SMEM_BUDGET")
    rows_cap = max(8, smem_budget // (P * 4))
    # Balanced ceil-division with a padded tail, NOT a divisor search:
    # any chunk size (prime included) splits into n_sub equal sub-chunks;
    # pad rows sit past valid_len, so `active` masks them like any ragged
    # tail. Balancing (n_sub first, then sub) minimizes the padding —
    # sub=min(C,rows_cap) at C=2048/cap=949 would pad 799 wasted rows.
    n_sub = -(-C // min(C, rows_cap))
    sub = -(-C // n_sub)
    pad = n_sub * sub - C
    tokens = jnp.pad(tokens, (0, pad)) if pad else tokens
    target = jnp.maximum(valid_len - 1, 0)

    def body(carry, xs):
        k_pages, v_pages, acc = carry
        toks_s, base = xs
        rows = base + jnp.arange(sub, dtype=jnp.int32)
        lengths = start + rows
        active = rows < valid_len
        page_indices = jnp.broadcast_to(page_row, (sub, P))
        logits, k_pages, v_pages = paged_decode_step(
            params, cfg, toks_s, k_pages, v_pages, page_indices, lengths,
            active, mesh=mesh, attn_impl=attn_impl,
        )
        sel = (rows == target).astype(logits.dtype)
        acc = acc + jnp.einsum("r,rv->v", sel, logits)
        return (k_pages, v_pages, acc), None

    acc0 = jnp.zeros((cfg.vocab_size,), jnp.float32)
    bases = (jnp.arange(n_sub, dtype=jnp.int32) * sub)
    (k_pages, v_pages, last), _ = jax.lax.scan(
        body, (k_pages, v_pages, acc0), (tokens.reshape(n_sub, sub), bases)
    )
    return last, k_pages, v_pages


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "mesh"),
    donate_argnames=("k_pages", "v_pages"),
)
def paged_chunk_prefill(
    params,
    cfg: TransformerConfig,
    tokens,
    k_pages,
    v_pages,
    page_row,
    start,
    valid_len,
    attn_impl: str = "auto",
    mesh=None,
):
    """Legacy 3-transfer entry point (tokens + start + valid_len staged
    separately): see ``_chunk_prefill_body`` for the semantics. Kept as
    the AREAL_DECODE_RESIDENT=0 arm of the decode-state A/B."""
    return _chunk_prefill_body(
        params, cfg, tokens, k_pages, v_pages, page_row, start, valid_len,
        attn_impl=attn_impl, mesh=mesh,
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "mesh"),
    donate_argnames=("k_pages", "v_pages"),
)
def paged_chunk_prefill_packed(
    params,
    cfg: TransformerConfig,
    ctl,  # [C + 2] int32: tokens[0:C] | start | valid_len
    k_pages,
    v_pages,
    page_row,
    attn_impl: str = "auto",
    mesh=None,
):
    """``_chunk_prefill_body`` with the per-chunk control — token ids,
    absolute start position, valid length — packed into ONE staged int32
    array. The legacy entry point pays three H2D transfers per chunk
    (tokens + two scalars); each transfer is a separate dispatch, so a
    16k prompt at C=512 paid ~96 stagings where this pays ~32. Scalars are sliced out
    on device — trace-identical math, pinned by the decode-state parity
    tests."""
    C = ctl.shape[0] - 2
    return _chunk_prefill_body(
        params, cfg, ctl[:C], k_pages, v_pages, page_row, ctl[C],
        ctl[C + 1], attn_impl=attn_impl, mesh=mesh,
    )


# ----------------------------------------------------------------------
# Prefill scatter
# ----------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnames=("k_pages", "v_pages"))
def scatter_prefill(k_pages, v_pages, k_pref, v_pref, flat_page_ids):
    """Write batched-prefill KV into the pool.

    k_pref/v_pref: [L, n, pad, Hkv, hd] from the packed forward;
    flat_page_ids: [n * pad//pg] pool pages in row-major (row, chunk)
    order, TRASH_PAGE for chunks past a row's allocation. int8 pools
    quantize each token's head vector before the scatter."""
    L, n, pad, Hkv, hd = k_pref.shape
    pg = kv_pool_data(k_pages).shape[3]
    n_chunks = pad // pg

    def to_chunks(pref):
        # [L, n, pad, Hkv, x] -> [L, Hkv, n*chunks, pg, x]
        x = pref.shape[-1]
        out = pref.transpose(0, 3, 1, 2, 4).reshape(
            L, Hkv, n, n_chunks, pg, x
        )
        return out.reshape(L, Hkv, n * n_chunks, pg, x)

    def write(pool, pref):
        if isinstance(pool, tuple):
            w, s = quantize_kv(pref)
            return (pool[0].at[:, :, flat_page_ids].set(to_chunks(w)),
                    pool[1].at[:, :, flat_page_ids].set(
                        to_chunks(s)[..., 0]))
        return pool.at[:, :, flat_page_ids].set(
            to_chunks(pref).astype(pool.dtype)
        )

    return write(k_pages, k_pref), write(v_pages, v_pref)


@functools.partial(jax.jit, donate_argnames=("k_pages", "v_pages"))
def scatter_prefill_int8(k_pages, v_pages, k_data, k_scales, v_data,
                         v_scales, page_ids):
    """Write an int8-wire KV prefix straight into an int8 pool — the
    tier-restore fast path (ISSUE 11 satellite): the wire's (data,
    scales) pairs ARE the pool encoding, so a spill + restore round
    trip is bit-exact and never pays dequantize→re-quantize (nor the
    4x float staging bytes).

    k_data/v_data: [L, Hkv, pad, hd] int8 token-major (padded to whole
    pages); k_scales/v_scales: [L, Hkv, pad] f32; page_ids: [pad//pg]
    pool pages in order. Pools must be (data, scales) pairs."""
    L, Hkv, pad, hd = k_data.shape
    pg = k_pages[0].shape[3]
    n_chunks = pad // pg

    def write(pool, data, scales):
        d = data.reshape(L, Hkv, n_chunks, pg, hd)
        s = scales.reshape(L, Hkv, n_chunks, pg)
        return (pool[0].at[:, :, page_ids].set(d),
                pool[1].at[:, :, page_ids].set(s))

    return (write(k_pages, k_data, k_scales),
            write(v_pages, v_data, v_scales))


# ----------------------------------------------------------------------
# Per-slot sampling (shared by the decode block and batched prefill)
# ----------------------------------------------------------------------


def warp_logits(logits, temps, top_ps, top_ks, forbid_rows, eos_mask,
                active_rows=None):
    """The warping half of warp_sample: per-row temperature / top-k /
    top-p / EOS-forbid applied to [B, V] logits. Returns (warped [B, V],
    base_logp [B, V] — log-softmax of the UNWARPED, forbid-masked
    logits, the distribution PPO logprobs are reported under). Shared by
    the decode block's sampling and speculative verification (which
    needs the whole warped distribution, not just a sample).

    Three tiers, picked at runtime by the active rows' settings:
    temperature-only skips warping entirely; top-k-only (all active k <=
    TOPK_FAST_MAX, no top-p) thresholds via `lax.top_k` — far cheaper
    than sorting 32k+ vocab; any top-p (or huge k) pays the full [B, V]
    descending sort (one sort serves both warps). The tiers produce
    identical warped logits for the rows they share, so the sampled
    token for a given rng is tier-invariant.
    """
    logits = logits.astype(jnp.float32)
    em = eos_mask if eos_mask.ndim == 2 else eos_mask[None, :]
    forbid = forbid_rows[:, None] & em
    logits = jnp.where(forbid, NEG_INF, logits)
    base_logp = jax.nn.log_softmax(logits, axis=-1)
    warped = logits / jnp.maximum(temps[:, None], 1e-6)

    def with_cutoffs(warped):
        V = warped.shape[-1]
        # ONE descending sort serves both warps (top-k threshold + top-p
        # nucleus cutoff); two sorts would double the per-step cost.
        sorted_desc = jnp.sort(warped, axis=-1)[:, ::-1]
        k_eff = jnp.where(top_ks <= 0, V, jnp.minimum(top_ks, V))
        kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_ps[:, None]
        cutoff_idx = jnp.sum(keep_sorted, axis=-1, keepdims=True) - 1
        p_cut = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
        return jnp.where(warped < jnp.maximum(kth, p_cut), NEG_INF, warped)

    kmax = min(TOPK_FAST_MAX, logits.shape[-1])

    def with_topk_only(warped):
        # k-th largest via lax.top_k: same threshold the sort path
        # gathers at sorted[k-1], without ordering the other V-k logits.
        vals = jax.lax.top_k(warped, kmax)[0]  # [B, kmax] desc
        k_eff = jnp.clip(top_ks, 1, kmax)
        kth = jnp.take_along_axis(vals, (k_eff - 1)[:, None], axis=-1)
        kth = jnp.where((top_ks > 0)[:, None], kth, NEG_INF)
        return jnp.where(warped < kth, NEG_INF, warped)

    # Only ACTIVE rows count: finished slots keep their stale top-k/top-p
    # until the next admission overwrites them, and must not re-enable
    # the sort for temperature-only batches.
    row_topk = top_ks > 0
    row_topp = top_ps < 1.0 - 1e-6
    if active_rows is not None:
        row_topk = row_topk & active_rows
        row_topp = row_topp & active_rows
    any_warp = jnp.any(row_topk | row_topp)
    need_sort = jnp.any(row_topp) | jnp.any(
        jnp.where(row_topk, top_ks, 0) > kmax
    )
    warped = jax.lax.cond(
        any_warp,
        lambda w: jax.lax.cond(need_sort, with_cutoffs, with_topk_only, w),
        lambda w: w,
        warped,
    )
    return warped, base_logp


def warp_sample(logits, rng, temps, top_ps, top_ks, greedy_mask, forbid_rows,
                eos_mask, active_rows=None):
    """Per-row warped sampling: temperature, top-k, top-p, greedy rows,
    and EOS-forbid rows — all as [B] arrays so one compiled program serves
    every mix of per-request params. Returns (tokens [B], logprobs [B] of
    the unwarped distribution, PPO convention — ops/sampling.sample_token).
    Warping tiers documented on warp_logits."""
    warped, base_logp = warp_logits(
        logits, temps, top_ps, top_ks, forbid_rows, eos_mask,
        active_rows=active_rows,
    )
    sampled = jax.random.categorical(rng, warped, axis=-1)
    argmax = jnp.argmax(base_logp, axis=-1)
    tokens = jnp.where(greedy_mask, argmax, sampled).astype(jnp.int32)
    logprobs = jnp.take_along_axis(base_logp, tokens[:, None], axis=-1)[:, 0]
    return tokens, logprobs


# ----------------------------------------------------------------------
# The decode block
# ----------------------------------------------------------------------


@functools.partial(
    jax.jit,
    donate_argnames=("state",),
    static_argnames=("n_slots",),
)
def apply_admits(
    state,  # tuple of [B] control arrays (see ServingEngine._dstate order)
    slots,  # [m] int32 slot indices (admitted)
    valid,  # [m] bool — False rows are bucket padding, must not write
    plens,  # [m] int32
    toks,  # [m] int32 first sampled tokens
    budgets,  # [m] int32 remaining budget after the first token
    minrs,  # [m] int32 min_remaining
    temps_new,  # [m] f32
    tps_new,  # [m] f32
    tks_new,  # [m] int32
    greedy_new,  # [m] bool
    n_slots: int,
):
    """One fused device update activating admitted slots.

    Keeps ALL per-slot control state device-resident between decode
    blocks — per-slot host writes would each be a host->device round
    trip. Invalid (padding) rows are routed to a scratch row beyond the
    real slots."""
    (lengths, next_input, active, remaining, min_remaining,
     temps, top_ps, top_ks, greedy) = state
    # Route padding rows to index B (one past the end): scatter drops
    # out-of-bounds indices on TPU/XLA's clip semantics would corrupt slot
    # B-1, so extend by one scratch row and slice back.
    idx = jnp.where(valid, slots, n_slots).astype(jnp.int32)

    def upd(arr, new):
        ext = jnp.concatenate([arr, arr[:1]], axis=0)
        ext = ext.at[idx].set(new.astype(arr.dtype))
        return ext[:n_slots]

    lengths = upd(lengths, plens)
    next_input = upd(next_input, toks)
    active = upd(active, jnp.ones_like(slots, bool))
    remaining = upd(remaining, budgets)
    min_remaining = upd(min_remaining, minrs)
    temps = upd(temps, temps_new)
    top_ps = upd(top_ps, tps_new)
    top_ks = upd(top_ks, tks_new)
    greedy = upd(greedy, greedy_new)
    return (lengths, next_input, active, remaining, min_remaining,
            temps, top_ps, top_ks, greedy)


@functools.partial(jax.jit, donate_argnames=("active",))
def apply_deactivations(active, deact_mask):
    """Host-initiated stops (extra stop-token trims, preemptions) must
    land on the device active mask BEFORE the next block, or the dead
    slot would keep writing KV into pages the allocator already freed."""
    return active & ~deact_mask


@functools.partial(
    jax.jit, donate_argnames=("pt_dev",), static_argnames=("n_slots",)
)
def update_page_rows(
    pt_dev,  # [B, P] int32 device page table (donated)
    packed_rows,  # [m, P + 1] int32: col 0 = slot index (< 0 padding),
    #               cols 1: = that slot's replacement page row
    n_slots: int,
):
    """Scatter only the CHANGED page-table rows into the device table.

    The device-resident half of the decode-state contract
    (AREAL_DECODE_RESIDENT): the legacy path re-staged the whole
    [B, max_pages] host mirror every time any slot's row changed — at
    B=64 slots x a 16k-context table that is ~35 KB of H2D per admit/
    finish/page-growth lap for a one-row edit. Here only the dirty rows
    cross the host boundary, fused with their slot indices into ONE
    staged array (each transfer is its own dispatch, so splitting
    control into slots/valid/rows arrays would triple the count the A/B
    measures); the table itself stays device-resident (donated, like
    apply_admits). Padding rows (slot < 0) route to the scratch row
    past the real slots — same clip-semantics guard as apply_admits."""
    slots = packed_rows[:, 0]
    rows = packed_rows[:, 1:]
    idx = jnp.where(slots >= 0, slots, n_slots).astype(jnp.int32)
    ext = jnp.concatenate([pt_dev, pt_dev[:1]], axis=0)
    ext = ext.at[idx].set(rows.astype(pt_dev.dtype))
    return ext[:n_slots]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "attn_impl", "mesh"),
    donate_argnames=(
        "k_pages", "v_pages", "lengths", "next_input", "active",
        "remaining", "min_remaining", "rng",
    ),
)
def paged_decode_block(
    params,
    cfg: TransformerConfig,
    k_pages,
    v_pages,
    page_indices,  # [B, P]
    lengths,  # [B] cache fill per slot (excl. the pending next_input token)
    next_input,  # [B] last sampled token, to feed
    active,  # [B] bool
    remaining,  # [B] int32 budget left
    min_remaining,  # [B] int32 forbid-EOS countdown
    temps,
    top_ps,
    top_ks,
    greedy_mask,
    eos_mask,  # [V] bool
    rng,
    n_steps: int,
    attn_impl: str = "auto",
    mesh=None,
):
    """Run up to n_steps decode steps for every active slot over the paged
    pool. The host guarantees each active slot has pages allocated for
    lengths + n_steps tokens before calling.

    Returns (packed, k_pages, v_pages, lengths, next_input, active,
    remaining, min_remaining, rng) where `packed` is ONE [B, 2n+4] f32
    array — [tokens | logprobs | n_emitted, hit_eos, active, lengths] —
    so the host needs exactly one device fetch per block (per-array
    fetches are serial round trips).
    Emission is prefix-contiguous per slot (active only ever falls within
    a block), so tokens[:n_emitted] is the emitted sequence.

    MoE models get TWO extra packed columns — [B, 2n+6] instead of
    [B, 2n+4] — broadcasting the block-mean decode router stats
    (moe_drop_rate, moe_router_entropy) so the serving /metrics surface
    sees them without a second device fetch."""
    B = lengths.shape[0]
    is_moe = cfg.moe is not None

    def body(i, carry):
        (kp, vp, lengths, next_input, active, remaining, min_remaining,
         rng, out_t, out_lp, out_m, hit_eos, moe_acc) = carry
        logits, kp, vp, moe_stats = paged_decode_step(
            params, cfg, next_input, kp, vp, page_indices, lengths, active,
            mesh=mesh, attn_impl=attn_impl, return_moe_stats=True,
        )
        if is_moe:
            moe_acc = (
                moe_acc[0] + moe_stats["moe_drop_rate"],
                moe_acc[1] + moe_stats["moe_router_entropy"],
            )
        rng, sub = jax.random.split(rng)
        tokens, logprobs = warp_sample(
            logits, sub, temps, top_ps, top_ks, greedy_mask,
            min_remaining > 0, eos_mask, active_rows=active,
        )
        emit = active
        tokens = jnp.where(emit, tokens, 0)
        logprobs = jnp.where(emit, logprobs, 0.0)
        out_t = out_t.at[:, i].set(tokens)
        out_lp = out_lp.at[:, i].set(logprobs)
        out_m = out_m.at[:, i].set(emit)

        is_eos = eos_mask[tokens] & emit
        remaining = remaining - emit.astype(jnp.int32)
        min_remaining = jnp.maximum(min_remaining - emit.astype(jnp.int32), 0)
        exhausted = (remaining <= 0) & emit
        hit_eos = hit_eos | is_eos
        active = active & ~is_eos & ~exhausted
        lengths = lengths + emit.astype(lengths.dtype)
        next_input = tokens
        return (kp, vp, lengths, next_input, active, remaining, min_remaining,
                rng, out_t, out_lp, out_m, hit_eos, moe_acc)

    out_t = jnp.zeros((B, n_steps), jnp.int32)
    out_lp = jnp.zeros((B, n_steps), jnp.float32)
    out_m = jnp.zeros((B, n_steps), bool)
    hit_eos = jnp.zeros((B,), bool)
    moe_acc = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    carry = (k_pages, v_pages, lengths, next_input, active, remaining,
             min_remaining, rng, out_t, out_lp, out_m, hit_eos, moe_acc)
    carry = jax.lax.fori_loop(0, n_steps, body, carry)
    (k_pages, v_pages, lengths, next_input, active, remaining, min_remaining,
     rng, out_t, out_lp, out_m, hit_eos, moe_acc) = carry
    cols = [
        out_t.astype(jnp.float32),
        out_lp,
        jnp.sum(out_m, axis=1, keepdims=True).astype(jnp.float32),
        hit_eos[:, None].astype(jnp.float32),
        active[:, None].astype(jnp.float32),
        lengths[:, None].astype(jnp.float32),
    ]
    if is_moe:
        inv = 1.0 / float(n_steps)
        cols.append(jnp.broadcast_to(moe_acc[0] * inv, (B,))[:, None])
        cols.append(jnp.broadcast_to(moe_acc[1] * inv, (B,))[:, None])
    packed = jnp.concatenate(cols, axis=1)
    return (packed, k_pages, v_pages, lengths, next_input, active,
            remaining, min_remaining, rng)
