"""Splash attention over a list of block pairs whose length is a value of
the run (Pallas, TPU): the kernels of a long packed row alone in its call.

jax's splash kernels walk a grid of `q blocks x W kv blocks`, W static;
a step whose pair holds nothing is skipped but still walked (0.3-0.4 us
on a v5e). These walk a flat list instead: `ops/attention._pair_lists`
names the row's live (q block, kv block) pairs, q-major for the forward
and dq kernels and kv-major for dkv, with `n`, how many there are, and
the grid is `(q heads, n)`, `n` a dynamic grid dimension: no step without
a pair. A step's flags say whether it is the first or the last of its q
block (kv block in dkv): scratch is initialised on the first, the output
block written on the last.

Inside a step the arithmetic is splash's
(jax.experimental.pallas.ops.tpu.splash_attention.splash_attention_kernel:
`flash_attention_kernel`, `_flash_attention_dq_kernel`,
`_flash_attention_dkv_kernel` without the fused dq): online softmax over
`bkvc` sub-blocks, float32 sums in scratch, seven products a pair in the
backward, the mask by place in the row and segment id. Operands are
head-first: q `[Hq, T, hd]`, k `[Hkv, T, hd]`, v `[Hkv, T, hd_v]`; the kv
head of a q head is in the index maps (`h // group`), and dkv's grid is
`(kv heads, n, group)`: a kv block's q blocks for every q head of the
group before dk and dv leave scratch.

A mask operand (`pair_attention_chosen`) is a choice of keys a query
that is a value of the run, the same for every q head of the row: int8,
non-zero = the query may read the key, ANDed with the mask by place and
segment a cell at a time. Forward and dq read it as `[T / bkvc, T, bkvc]`
(kv sub-block c of a pair's tile is `[c]` of its block), dkv as its
transpose by q blocks, `[T / bq, T, bq]`. The walk stays the row's live
pairs; a query must keep a key in its q block's pairs (its own place:
the indexer always chooses a query's best-scored key, and a query alone
in its prefix has itself). A row without such a choice passes no mask
and its kernels read none: the operand is not there.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# splash's DEFAULT_MASK_VALUE
_MASK_VALUE = -0.7 * float(np.finfo(np.dtype("float32")).max)
_NN = (((1,), (0,)), ((), ()))  # standard matmul
_NT = (((1,), (1,)), ((), ()))  # right-hand side transposed

# A step's flags (`PairList.flags`).
FIRST, LAST = 1, 2


class PairList(NamedTuple):
    """Block pairs in the order a kernel walks them, int32 `[capacity]`
    each; past `PairLists.n` the last pair again. `flags`: FIRST / LAST
    of the run of steps that share the list's major block."""
    q: jax.Array
    kv: jax.Array
    flags: jax.Array


class PairLists(NamedTuple):
    q_major: PairList  # forward and dq: a q block's kv blocks in a run
    kv_major: PairList  # dkv: a kv block's q blocks in a run
    n: jax.Array  # int32 scalar: the pairs that are there


class Blocks(NamedTuple):
    bq: int
    bkv: int
    bkvc: int


def _keep(q_at, k_at, shape, q_ids, kv_ids, window, k_in_lanes, chosen=None):
    """bool `shape`: the cells of a (q rows, kv columns) tile (or its
    transpose) that splash's causal or local mask and the segment ids
    leave: kv place <= q place, within `window`, one segment; and, with
    `chosen` (the tile of a mask operand: int8 `shape`, non-zero = the
    query reads the key), that the run's own choice leaves."""
    q_dim, k_dim = (0, 1) if k_in_lanes else (1, 0)
    q_seq = q_at + lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_seq = k_at + lax.broadcasted_iota(jnp.int32, shape, k_dim)
    keep = q_seq >= k_seq
    if window is not None:
        keep &= q_seq - k_seq < window
    keep &= q_ids == kv_ids
    if chosen is not None:
        keep &= chosen.astype(jnp.int32) != 0
    return keep


def _fwd_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, qseg_ref,
                kvseg_ref, *rest, blocks, window, masked=False):
    mask_ref, (o_ref, *rest) = (rest[0], rest[1:]) if masked else (None, rest)
    # The logsumexp is an output only where the backward will want it.
    lse_ref, (m_sc, l_sc, o_sc) = (rest[0], rest[1:]) if len(rest) == 4 else (None, rest)
    bq, bkv, bkvc = blocks
    s = pl.program_id(1)
    flags = flags_ref[s]
    v_repeats = pl.cdiv(o_sc.shape[-1], _LANES)

    @pl.when(flags & FIRST != 0)
    def init():
        o_sc[...] = jnp.zeros_like(o_sc)
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)

    q_at, k_at = qi_ref[s] * bq, ki_ref[s] * bkv

    def sub_block(c, _):
        cols = pl.ds(c * bkvc, bkvc)
        m_prev, l_prev = m_sc[...], l_sc[...]
        qk = lax.dot_general(q_ref[...], k_ref[cols, :], _NT,
                             preferred_element_type=jnp.float32)
        keep = _keep(q_at, k_at + c * bkvc, qk.shape,
                     jnp.tile(qseg_ref[...], (1, bkvc // _LANES)),
                     kvseg_ref[:1, cols], window, True,
                     None if mask_ref is None else mask_ref[c])
        qk = jnp.where(keep, qk, _MASK_VALUE)
        m_curr = qk.max(axis=-1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)
        s_curr = jnp.exp(qk - jnp.tile(m_next, (1, bkvc // _LANES)))
        l_curr = lax.broadcast_in_dim(s_curr.sum(axis=-1), l_prev.shape, (0,))
        alpha = jnp.exp(m_prev - m_next)
        m_sc[...], l_sc[...] = m_next, l_curr + alpha * l_prev
        o_curr = lax.dot_general(s_curr, v_ref[cols, :].astype(jnp.float32), _NN)
        alpha_o = jnp.tile(alpha, (1, v_repeats))[..., :o_sc.shape[-1]]
        o_sc[...] = alpha_o * o_sc[...] + o_curr

    lax.fori_loop(0, bkv // bkvc, sub_block, None, unroll=True)

    @pl.when(flags & LAST != 0)
    def end():
        l = l_sc[...]
        l_inv = jnp.tile(1.0 / l, (1, v_repeats))[..., :o_sc.shape[-1]]
        o_ref[...] = (o_sc[...] * l_inv).astype(o_ref.dtype)
        if lse_ref is not None:
            # One row of bq, not splash's bq x 128 of equal lanes (a 128th
            # of the bytes, and no relayout around the call to slice it).
            lse_ref[...] = (jnp.log(l) + m_sc[...]).T[:1]


def _dq_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, qseg_ref,
               kvseg_ref, *rest, blocks, window, masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    lse_ref, do_ref, di_ref, dq_ref, dq_sc = rest
    bq, bkv, _ = blocks
    s = pl.program_id(1)
    flags = flags_ref[s]

    @pl.when(flags & FIRST != 0)
    def init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    k, v = k_ref[...], v_ref[...]
    qk = lax.dot_general(q_ref[...], k, _NT, preferred_element_type=jnp.float32)
    chosen = None if mask_ref is None else jnp.concatenate(
        [mask_ref[c] for c in range(mask_ref.shape[0])], axis=1)
    keep = _keep(qi_ref[s] * bq, ki_ref[s] * bkv, qk.shape,
                 jnp.tile(qseg_ref[...], (1, bkv // _LANES)), kvseg_ref[:1, :],
                 window, True, chosen)
    p = jnp.exp(jnp.where(keep, qk, _MASK_VALUE) - jnp.expand_dims(lse_ref[0], -1))
    dp = lax.dot_general(do_ref[...].astype(v.dtype), v, _NT,
                         preferred_element_type=jnp.float32)
    ds = (dp - jnp.expand_dims(di_ref[0], -1)) * p
    dq_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                  preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def end():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, qseg_ref,
                kvseg_ref, *rest, blocks, window, masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    lse_ref, do_ref, di_ref, dk_ref, dv_ref, dk_sc, dv_sc = rest
    bq, bkv, bkvc = blocks
    s, g = pl.program_id(1), pl.program_id(2)
    flags = flags_ref[s]

    @pl.when((flags & FIRST != 0) & (g == 0))
    def init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    q_at, k_at = qi_ref[s] * bq, ki_ref[s] * bkv

    def sub_block(c, _):
        rows = pl.ds(c * bkvc, bkvc)
        q, k, v, do = q_ref[...], k_ref[rows, :], v_ref[rows, :], do_ref[...]
        qk = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        keep = _keep(q_at, k_at + c * bkvc, qk.shape, qseg_ref[:1, :],
                     jnp.tile(kvseg_ref[rows, :], (1, bq // _LANES)), window, False,
                     None if mask_ref is None else mask_ref[rows, :])
        p = jnp.exp(jnp.where(keep, qk, _MASK_VALUE) - lse_ref[:1, :])
        dv = lax.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dv_sc[rows, :] = dv + dv_sc[rows, :]
        dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (dp - di_ref[:1, :]) * p
        dk = lax.dot_general(ds.astype(do.dtype), q, _NN,
                             preferred_element_type=jnp.float32)
        dk_sc[rows, :] = dk + dk_sc[rows, :]

    lax.fori_loop(0, bkv // bkvc, sub_block, None, unroll=True)

    @pl.when((flags & LAST != 0) & (g == pl.num_programs(2) - 1))
    def end():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _call(kernel, name, grid, lists, in_specs, out_specs, out_shape, scratch,
          semantics, interpret, operands):
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
            name=name, interpret=interpret,
        )(*lists, *operands)


def _segment_operands(segment_ids, q_in_lanes):
    """(q ids, kv ids) as splash lays them out: the operand whose
    blocks lie along the tile's rows broadcast over lanes, the other
    over sublanes."""
    t = segment_ids.shape[0]
    wide = lax.broadcast_in_dim(segment_ids, (t, _LANES), (0,))
    flat = lax.broadcast_in_dim(segment_ids, (_SUBLANES, t), (1,))
    return (flat, wide) if q_in_lanes else (wide, flat)


def _q_major(q, k, v, blocks, mask=None):
    """(index map of a q head's blocks, in_specs of q, k, v, the segment
    ids and, where there is one, the mask operand `[T / bkvc, T, bkvc]`)
    for the kernels that walk the q-major list."""
    hq, _, hd = q.shape
    hkv, hd_v = k.shape[0], v.shape[-1]
    bq, bkv, bkvc = blocks
    on_q = lambda h, s, qi, ki, fl: (h, qi[s], 0)
    on_kv = lambda h, s, qi, ki, fl: (h // (hq // hkv), ki[s], 0)
    specs = [
        pl.BlockSpec((None, bq, hd), on_q),
        pl.BlockSpec((None, bkv, hd), on_kv),
        pl.BlockSpec((None, bkv, hd_v), on_kv),
        pl.BlockSpec((bq, _LANES), lambda h, s, qi, ki, fl: (qi[s], 0)),
        pl.BlockSpec((_SUBLANES, bkv), lambda h, s, qi, ki, fl: (0, ki[s])),
    ]
    if mask is not None:
        specs.append(pl.BlockSpec((bkv // bkvc, bq, bkvc),
                                  lambda h, s, qi, ki, fl: (ki[s], qi[s], 0)))
    return on_q, specs


def _masked(*operands):
    """The operands that are there: a kernel's mask is its last input
    before the backward's, and absent for a row without one."""
    return tuple(x for x in operands if x is not None)


def _forward(q, k, v, segment_ids, lists, blocks, window, interpret, residuals,
             mask=None):
    hq, t, _ = q.shape
    bq, hd_v = blocks.bq, v.shape[-1]
    on_q, in_specs = _q_major(q, k, v, blocks, mask)
    out_shape = [jax.ShapeDtypeStruct((hq, t, hd_v), q.dtype)]
    out_specs = [pl.BlockSpec((None, bq, hd_v), on_q)]
    if residuals:
        out_shape.append(jax.ShapeDtypeStruct((hq, 1, t), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, 1, bq), lambda h, s, qi, ki, fl: (h, 0, qi[s])))
    out = _call(
        functools.partial(_fwd_kernel, blocks=blocks, window=window,
                          masked=mask is not None),
        "splash_pairs_fwd", (hq, lists.n), lists.q_major, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch=[pltpu.VMEM((bq, _LANES), jnp.float32),
                 pltpu.VMEM((bq, _LANES), jnp.float32),
                 pltpu.VMEM((bq, hd_v), jnp.float32)],
        semantics=("parallel", "arbitrary"), interpret=interpret,
        operands=_masked(q, k, v, *_segment_operands(segment_ids, q_in_lanes=False),
                         mask))
    return out[0], (out[1][:, 0] if residuals else None)


def _backward_dq(q, k, v, segment_ids, lists, lse, do, di, blocks, window,
                 interpret, mask=None):
    hq, _, hd = q.shape
    bq, hd_v = blocks.bq, v.shape[-1]
    on_q, in_specs = _q_major(q, k, v, blocks, mask)
    q_row = pl.BlockSpec((None, 1, bq), lambda h, s, qi, ki, fl: (h, 0, qi[s]))
    return _call(
        functools.partial(_dq_kernel, blocks=blocks, window=window,
                          masked=mask is not None),
        "splash_pairs_dq", (hq, lists.n), lists.q_major,
        in_specs=[*in_specs, q_row, pl.BlockSpec((None, bq, hd_v), on_q), q_row],
        out_specs=pl.BlockSpec((None, bq, hd), on_q),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch=[pltpu.VMEM((bq, hd), jnp.float32)],
        semantics=("parallel", "arbitrary"), interpret=interpret,
        operands=_masked(q, k, v, *_segment_operands(segment_ids, q_in_lanes=False),
                         mask, lse[:, None, :], do, di[:, None, :]))


def _backward_dkv(q, k, v, segment_ids, lists, lse, do, di, blocks, window,
                  interpret, mask_t=None):
    hq, t, hd = q.shape
    hkv, hd_v = k.shape[0], v.shape[-1]
    group = hq // hkv
    bq, bkv, _ = blocks
    on_q = lambda h, s, g, qi, ki, fl: (h * group + g, qi[s], 0)
    on_kv = lambda h, s, g, qi, ki, fl: (h, ki[s], 0)
    # Sublane-broadcast, as splash does it: Mosaic has no retiling of a
    # single row yet.
    q_rows = pl.BlockSpec((None, _SUBLANES, bq),
                          lambda h, s, g, qi, ki, fl: (h * group + g, 0, qi[s]))
    rows = lambda x: jnp.broadcast_to(x[:, None, :], (hq, _SUBLANES, t))
    # The mask transposed by q blocks, `[T / bq, T, bq]`: a pair's tile
    # with its kv rows along sublanes, as this kernel computes.
    mask_spec = () if mask_t is None else (pl.BlockSpec(
        (None, bkv, bq), lambda h, s, g, qi, ki, fl: (qi[s], ki[s], 0)),)
    return _call(
        functools.partial(_dkv_kernel, blocks=blocks, window=window,
                          masked=mask_t is not None),
        "splash_pairs_dkv", (hkv, lists.n, group), lists.kv_major,
        in_specs=[
            pl.BlockSpec((None, bq, hd), on_q),
            pl.BlockSpec((None, bkv, hd), on_kv),
            pl.BlockSpec((None, bkv, hd_v), on_kv),
            pl.BlockSpec((_SUBLANES, bq), lambda h, s, g, qi, ki, fl: (0, qi[s])),
            pl.BlockSpec((bkv, _LANES), lambda h, s, g, qi, ki, fl: (ki[s], 0)),
            *mask_spec,
            q_rows,
            pl.BlockSpec((None, bq, hd_v), on_q),
            q_rows,
        ],
        out_specs=[pl.BlockSpec((None, bkv, hd), on_kv),
                   pl.BlockSpec((None, bkv, hd_v), on_kv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch=[pltpu.VMEM((bkv, hd), jnp.float32),
                 pltpu.VMEM((bkv, hd_v), jnp.float32)],
        semantics=("parallel", "arbitrary", "arbitrary"), interpret=interpret,
        operands=_masked(q, k, v, *_segment_operands(segment_ids, q_in_lanes=True),
                         mask_t, rows(lse), do, rows(di)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def pair_attention(q, k, v, segment_ids, lists: PairLists, blocks: Blocks,
                   window: Optional[int], residual_name: str, interpret: bool):
    """Attention of one packed row over the block pairs `lists` names:
    q `[Hq, T, hd]` (already scaled), k `[Hkv, T, hd]`, v `[Hkv, T,
    hd_v]`, `segment_ids` `[T]` -> `[Hq, T, hd_v]`. A q block must have
    a pair, or its output block is never written. The output and the
    logsumexp the backward keeps are `checkpoint_name`d `residual_name`,
    so a remat policy can keep them and the backward not run the forward
    kernel again."""
    out, _ = _forward(q, k, v, segment_ids, lists, blocks, window, interpret,
                      residuals=False)
    return checkpoint_name(out, residual_name)


def _pair_attention_fwd(q, k, v, segment_ids, lists, blocks, window,
                        residual_name, interpret):
    out, lse = _forward(q, k, v, segment_ids, lists, blocks, window, interpret,
                        residuals=True)
    out, lse = (checkpoint_name(x, residual_name) for x in (out, lse))
    return out, (q, k, v, segment_ids, lists, out, lse)


def _pair_attention_bwd(blocks, window, residual_name, interpret, res, do):
    del residual_name
    q, k, v, segment_ids, lists, out, lse = res
    di = jnp.einsum("hsd,hsd->hs", out.astype(jnp.float32), do.astype(jnp.float32))
    args = (q, k, v, segment_ids, lists, lse, do, di, blocks, window, interpret)
    dk, dv = _backward_dkv(*args)
    return _backward_dq(*args), dk, dv, None, None


pair_attention.defvjp(_pair_attention_fwd, _pair_attention_bwd)


def transpose_mask(mask, bq: int):
    """`[T / bkvc, T, bkvc]` (forward and dq's layout of a mask operand)
    -> `[T / bq, T, bq]` (dkv's): `out[i, s, r] = mask[s // bkvc, i bq +
    r, s % bkvc]`."""
    n, t, bkvc = mask.shape
    return mask.reshape(n, t // bq, bq, bkvc).transpose(1, 0, 3, 2).reshape(
        t // bq, t, bq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def pair_attention_chosen(q, k, v, segment_ids, lists: PairLists, mask, mask_t,
                          blocks: Blocks, residual_name: str, interpret: bool):
    """`pair_attention` under a mask operand (the module's docstring):
    `mask` int8 `[T / bkvc, T, bkvc]` and `mask_t` = `transpose_mask(mask,
    bq)`, constants of the backward pass. Also returns the logsumexp
    `[Hq, T]`, which carries no gradient: what reads it (the indexer's
    loss) holds the attention probabilities fixed."""
    out, lse = _forward(q, k, v, segment_ids, lists, blocks, None, interpret,
                        residuals=True, mask=mask)
    return checkpoint_name(out, residual_name), lse


def _pair_attention_chosen_fwd(q, k, v, segment_ids, lists, mask, mask_t, blocks,
                               residual_name, interpret):
    out, lse = _forward(q, k, v, segment_ids, lists, blocks, None, interpret,
                        residuals=True, mask=mask)
    out, lse = (checkpoint_name(x, residual_name) for x in (out, lse))
    return (out, lse), (q, k, v, segment_ids, lists, mask, mask_t, out, lse)


def _pair_attention_chosen_bwd(blocks, residual_name, interpret, res, cts):
    del residual_name
    q, k, v, segment_ids, lists, mask, mask_t, out, lse = res
    do, _ = cts
    di = jnp.einsum("hsd,hsd->hs", out.astype(jnp.float32), do.astype(jnp.float32))
    args = (q, k, v, segment_ids, lists, lse, do, di, blocks, None, interpret)
    dk, dv = _backward_dkv(*args, mask_t=mask_t)
    return _backward_dq(*args, mask=mask), dk, dv, None, None, None, None


pair_attention_chosen.defvjp(_pair_attention_chosen_fwd, _pair_attention_chosen_bwd)
