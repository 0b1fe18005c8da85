"""Splash attention over a list of block pairs whose length is a value of
the run (Pallas, TPU): the kernels of a long packed row alone in its call.

jax's splash kernels walk a grid of `q blocks x W kv blocks`, W static;
a step whose pair holds nothing is skipped but still walked (0.3-0.4 us
on a v5e). These walk a flat list instead: `ops/attention._pair_lists`
names the row's live (q block, kv block) pairs, q-major for the forward
kernel and kv-major for the backward, with `n`, how many there are, and
the grid is `(q heads, n)`, `n` a dynamic grid dimension: no step without
a pair. A step's flags say whether it is the first or the last of its q
block (kv block in the backward): scratch is initialised on the first,
the output block written on the last.

Inside a step the arithmetic is splash's
(jax.experimental.pallas.ops.tpu.splash_attention.splash_attention_kernel:
`flash_attention_kernel`, `_flash_attention_dkv_kernel` with the fused
dq): online softmax over `bkvc` sub-blocks, float32 sums, the mask by
place in the row and segment id. Operands are head-first: q `[Hq, T,
hd]`, k `[Hkv, T, hd]`, v `[Hkv, T, hd_v]`; the kv head of a q head is in
the index maps (`h // group`). Or, `seq_minor`, sequence-minor: q, k `[H,
hd, T]`, v `[H, hd_v, T]`, and so the output, `do`, dq, dk and dv, which
is how XLA's products write latent attention's q, k and v (the sequence
in lanes) and read its gradients, so that nothing relays a row between a
projection and a kernel (`ops/attention._rows_in_place` says when; PERF.md
section 6, PR 62). The walk, the lists, the flags and a step's arithmetic
are the same, each product the same shape with its operands read the other
way round (NN for NT and NT for NN); what is turned is a block that stays
for a run of steps, once a run: in the forward the q block on its way in
and the output block on its way out, in the backward k and v on their way
in (k's transpose for dq is then the block as it lies) and dk and dv on
their way out; dq leaves as the block of its float32 sum stands. On a v5e
that costs the forward 2.3-3.3 % and the backward -0.1 to +1.1 % (the
probe, PERF.md section 6, PR 62).

The backward is one kernel, five products a pair (`s = k q^T`, `dp = v
do^T`, `dv = p^T do`, `dk = ds^T q`, `dq^T = k^T ds`), where a dq kernel
beside a dkv kernel made `s`, `dp` and the exponential twice. Its grid is
`(kv heads, n, group)`: a kv block's q blocks for every q head of the
group before dk and dv leave scratch. dq has no such run: a q block is
visited once a kv block it pairs with, in steps that are not consecutive,
so its float32 sum lives in HBM between visits, in an output of the
kernel's own, `[Hq, hd, T]` (transposed: `k^T ds` needs the transpose of
the kv block's k, made once a run of steps, where `ds^T k` would need
that of every step's `ds`; and 192 rows of sublanes pad nothing where 192
lanes pad to 256). A step reads the block's sum, adds its own and writes
it back by copies of its own making, not the pipeline's: splash's fused
backward avoids the read by writing partials `[kv blocks, heads, T, hd]`
and summing them outside (805 MB a layer at 16,384), and an output block
the pipeline writes back may still be on its way when the block's next
visit, as little as one step later, is fetched. The order is kept by the
grid running in order on one core (every axis "arbitrary": a chip with
two cores must not split the heads either, since a step's write is
waited for two steps on, whichever head that is) and by the copies'
semaphores. The sums pass through the two halves of one buffer in turn:
step j, at its start, waits for step j - 2's write out of its half (the
whole of step j - 1 has hidden it), then starts the read of its block
into that half; at its end it waits for the read, adds, and starts its
write, which step j + 1 hides. So the only write that can still be on
its way when a read starts is step j - 1's, and that is never to the
same block: where the walk visits a block in two steps running (a group
of 1: a kv block's last q block is the next's first; NEXT in the first
step's flags) the first step writes nothing and the second takes the
sum from the other half. A q block's first visit in the walk (NEW)
writes and does not read, so nothing is set to zero first and nothing
uninitialised is read; its last (DONE) writes no sum but the block of dq
itself: transposed back to `[bq, hd]` and rounded to q's dtype, once,
when the sum is complete (a minor dimension that is no multiple of the
lanes, 192, goes out in 256 and is sliced outside). Every q block has a
pair (its diagonal), so every block of dq is written. What the steps
must know of each other (a write nobody has waited for, a sum kept for
the next step) is four scalars in SMEM. On a v5e a step a head costs
4.4 us at heads of 128 and 6.4 at 192 / 128 where a dq and a dkv kernel
took 5.5 and 8.4; the products alone would take 3.8 and 5.7: the read of
the sum costs half a microsecond wherever it is started and waited for,
the writes nothing (PERF.md section 6, PR 51).

A mask operand (`pair_attention_chosen`) is a choice of keys a query
that is a value of the run, the same for every q head of the row: int8,
non-zero = the query may read the key, ANDed with the mask by place and
segment a cell at a time. The forward reads it as `[T / bkvc, T, bkvc]`
(kv sub-block c of a pair's tile is `[c]` of its block), the backward as
its transpose by q blocks, `[T / bq, T, bq]`. The walk stays the row's
live pairs; a query must keep a key in its q block's pairs (its own
place: the indexer always chooses a query's best-scored key, and a query
alone in its prefix has itself). A row without such a choice passes no
mask and its kernels read none: the operand is not there.
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

# A step's flags (`PairList.flags`): FIRST and LAST of its major block's
# run of steps; NEW at its minor block's first step of the whole walk and
# DONE at its last, NEXT where the walk's next step is at the same minor
# block.
FIRST, LAST, NEW, NEXT, DONE = 1, 2, 4, 8, 16

# What the backward kernel's steps tell each other, int32 in SMEM: [0] and
# [1] a write out of that half of `dq_io` that nobody has waited for, then
# a block out of `dq_out` likewise, and that the step before kept its sum
# in its half for this one.
_OUT, _KEPT = 2, 3


class PairList(NamedTuple):
    """Block pairs in the order a kernel walks them, int32 `[capacity]`
    each; past `PairLists.n` the last pair again. `flags`: FIRST / LAST
    of the run of steps that share the list's major block, NEW where
    the walk comes to the minor block for the first time, DONE for the
    last, NEXT where its next step is at the same minor block."""
    q: jax.Array
    kv: jax.Array
    flags: jax.Array


class PairLists(NamedTuple):
    q_major: PairList  # forward: a q block's kv blocks in a run
    kv_major: PairList  # backward: a kv block's q blocks in a run
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
                kvseg_ref, *rest, blocks, window, masked=False, seq_minor=False):
    mask_ref, (o_ref, *rest) = (rest[0], rest[1:]) if masked else (None, rest)
    # Sequence-minor blocks lie `[hd, bq]`, `[hd, bkv]`, `[hd_v, bkv]`: the
    # q block is turned once a run of steps, the output block once at its end,
    # and a step's two products are today's with k and v read as they lie.
    q_sc, rest = (rest[-1], rest[:-1]) if seq_minor else (None, rest)
    # The logsumexp is an output only where the backward will want it.
    lse_ref, (m_sc, l_sc, o_sc) = (rest[0], rest[1:]) if len(rest) == 4 else (None, rest)
    bq, bkv, bkvc = blocks
    over_hd, over_kv = (_NN, _NT) if seq_minor else (_NT, _NN)
    s = pl.program_id(1)
    flags = flags_ref[s]
    v_repeats = pl.cdiv(o_sc.shape[-1], _LANES)

    @pl.when(flags & FIRST != 0)
    def init():
        o_sc[...] = jnp.zeros_like(o_sc)
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        if seq_minor:
            q_sc[...] = q_ref[...].T

    q_at, k_at = qi_ref[s] * bq, ki_ref[s] * bkv
    kv_cols = (lambda ref, cols: ref[:, cols]) if seq_minor else (
        lambda ref, cols: ref[cols, :])

    def sub_block(c, _):
        cols = pl.ds(c * bkvc, bkvc)
        m_prev, l_prev = m_sc[...], l_sc[...]
        qk = lax.dot_general((q_sc if seq_minor else q_ref)[...], kv_cols(k_ref, cols),
                             over_hd, preferred_element_type=jnp.float32)
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
        o_curr = lax.dot_general(s_curr, kv_cols(v_ref, cols).astype(jnp.float32), over_kv)
        alpha_o = jnp.tile(alpha, (1, v_repeats))[..., :o_sc.shape[-1]]
        o_sc[...] = alpha_o * o_sc[...] + o_curr

    lax.fori_loop(0, bkv // bkvc, sub_block, None, unroll=True)

    @pl.when(flags & LAST != 0)
    def end():
        l = l_sc[...]
        l_inv = jnp.tile(1.0 / l, (1, v_repeats))[..., :o_sc.shape[-1]]
        o = o_sc[...] * l_inv
        o_ref[...] = (o.T if seq_minor else o).astype(o_ref.dtype)
        if lse_ref is not None:
            # One row of bq, not splash's bq x 128 of equal lanes (a 128th
            # of the bytes, and no relayout around the call to slice it).
            lse_ref[...] = (jnp.log(l) + m_sc[...]).T[:1]


def _bwd_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, qseg_ref,
                kvseg_ref, *rest, blocks, window, group, masked=False, seq_minor=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    # `k_sc`: the kv block's k the other way round than it lies; `v_sc`
    # (sequence-minor operands alone): v likewise.
    (lse_ref, do_ref, di_ref, dq_ref, sum_ref, dk_ref, dv_ref,
     dk_sc, dv_sc, k_sc, *v_sc, dq_sc, dq_io, dq_out, sems, on_its_way) = rest
    bq, bkv, bkvc = blocks
    # A product with a q block's operand (q, do) runs over the head size
    # or over the block's cells: which axis that is follows the layout.
    over_hd, over_q = (_NN, _NT) if seq_minor else (_NT, _NN)
    h, s, g = (pl.program_id(i) for i in range(3))
    kv_heads, n = pl.num_programs(0), pl.num_programs(1)
    flags = flags_ref[s]
    step = (h * n + s) * group + g

    @pl.when(step == 0)
    def nothing_yet():
        for i in range(4):
            on_its_way[i] = 0

    @pl.when((flags & FIRST != 0) & (g == 0))
    def init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)
        # dq is summed transposed, `[hd, bq]` = k^T ds: the transpose is
        # of the kv block's k, once a run of steps, not of a step's ds.
        # Sequence-minor blocks are that transpose as they lie, and what
        # is turned once a run is what the other products read by rows.
        k_sc[...] = k_ref[...].T
        if seq_minor:
            v_sc[0][...] = v_ref[...].T

    # The q block's sum lives in HBM between its visits (`sum_ref`, the
    # whole `[Hq, hd, T]`, float32): in at the step's start, added to at
    # its end, and out behind the next step, through the two halves of
    # `dq_io` in turn; its last visit writes the block of dq itself.
    q_at, k_at = qi_ref[s] * bq, ki_ref[s] * bkv
    half = lax.rem(step, 2)
    mine, other = dq_io.at[half], dq_io.at[1 - half]
    rows_of_q = pl.ds(pl.multiple_of(q_at, bq), bq)
    sum_at = sum_ref.at[h * group + g, :, rows_of_q]
    read = pltpu.make_async_copy(sum_at, mine, sems.at[2])
    write = pltpu.make_async_copy(mine, sum_at, sems.at[half])
    dq_at = (dq_ref.at[h * group + g, :, rows_of_q] if seq_minor else
             dq_ref.at[h * group + g, rows_of_q])
    out = pltpu.make_async_copy(dq_out, dq_at, sems.at[3])
    handed = on_its_way[_KEPT] != 0
    summed = flags & NEW == 0  # an earlier step left this block a sum
    fetched = summed & jnp.logical_not(handed)
    last_visit = flags & DONE != 0
    # A group of 1 visits a block in two steps running where a kv block's
    # last q block is the next's first: the sum stays in VMEM.
    keeps = (flags & NEXT != 0) if group == 1 else jnp.bool_(False)

    @pl.when(on_its_way[half] != 0)
    def half_free():  # step j - 2's write: step j - 1 has hidden it
        write.wait()
        on_its_way[half] = 0

    @pl.when(fetched)
    def sum_in():
        read.start()

    k_rows, k_cols, v_rows = (k_sc, k_ref, *v_sc) if seq_minor else (k_ref, k_sc, v_ref)
    for c in range(bkv // bkvc):
        rows = pl.ds(c * bkvc, bkvc)
        q, k, v, do = q_ref[...], k_rows[rows, :], v_rows[rows, :], do_ref[...]
        qk = lax.dot_general(k, q, over_hd, preferred_element_type=jnp.float32)
        keep = _keep(q_at, k_at + c * bkvc, qk.shape, qseg_ref[:1, :],
                     jnp.tile(kvseg_ref[rows, :], (1, bq // _LANES)), window, False,
                     None if mask_ref is None else mask_ref[rows, :])
        p = jnp.exp(jnp.where(keep, qk, _MASK_VALUE) - lse_ref[:1, :])
        dv = lax.dot_general(p.astype(do.dtype), do, over_q,
                             preferred_element_type=jnp.float32)
        dv_sc[rows, :] = dv + dv_sc[rows, :]
        dp = lax.dot_general(v, do, over_hd, preferred_element_type=jnp.float32)
        ds = ((dp - di_ref[:1, :]) * p).astype(do.dtype)
        dk = lax.dot_general(ds, q, over_q, preferred_element_type=jnp.float32)
        dk_sc[rows, :] = dk + dk_sc[rows, :]
        dq = lax.dot(k_cols[:, rows], ds, preferred_element_type=jnp.float32)
        dq_sc[...] = dq if c == 0 else dq + dq_sc[...]

    @pl.when(fetched)
    def add():
        read.wait()
        mine[...] += dq_sc[...]

    @pl.when(handed)
    def take_over():
        mine[...] = other[...] + dq_sc[...]

    @pl.when(jnp.logical_not(summed))
    def begin():
        mine[...] = dq_sc[...]

    on_its_way[_KEPT] = keeps.astype(jnp.int32)

    @pl.when(last_visit)
    def block_out():  # the one rounding, and dq as the model has it
        @pl.when(on_its_way[_OUT] != 0)
        def stage_free():
            out.wait()

        if seq_minor:  # `[hd, bq]`, the sum as it stands
            dq_out[...] = mine[...].astype(dq_out.dtype)
        else:  # `[bq, hd]`
            dq_out[:, :mine.shape[0]] = mine[...].T.astype(dq_out.dtype)
        out.start()
        on_its_way[_OUT] = 1

    @pl.when(jnp.logical_not(last_visit | keeps))
    def sum_out():
        write.start()
        on_its_way[half] = 1

    @pl.when((flags & LAST != 0) & (g == group - 1))
    def end():
        for ref, sc in ((dk_ref, dk_sc), (dv_ref, dv_sc)):
            ref[...] = (sc[...].T if seq_minor else sc[...]).astype(ref.dtype)

    @pl.when((h == kv_heads - 1) & (s == n - 1) & (g == group - 1))
    def drain():
        for i in (0, 1):
            @pl.when(on_its_way[i] != 0)
            def landed():
                pltpu.make_async_copy(dq_io.at[i], sum_at, sems.at[i]).wait()

        out.wait()  # the walk's last step is its block's last visit


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


def _block(rows, hd, at, seq_minor):
    """The block of `rows` cells that `at` names, as (head, block), of a
    head-first operand `[H, T, hd]` or of a sequence-minor one `[H, hd,
    T]`."""

    def index(*grid):
        h, i = at(*grid)
        return (h, 0, i) if seq_minor else (h, i, 0)

    return pl.BlockSpec((None, hd, rows) if seq_minor else (None, rows, hd), index)


def _q_major(q, k, v, blocks, mask=None, seq_minor=False):
    """((head, block) of a q head's blocks, in_specs of q, k, v, the
    segment ids and, where there is one, the mask operand `[T / bkvc, T,
    bkvc]`) for the kernels that walk the q-major list."""
    hq, hkv = q.shape[0], k.shape[0]
    hd, hd_v = (q.shape[1], v.shape[1]) if seq_minor else (q.shape[2], v.shape[2])
    bq, bkv, bkvc = blocks
    on_q = lambda h, s, qi, ki, fl: (h, qi[s])
    on_kv = lambda h, s, qi, ki, fl: (h // (hq // hkv), ki[s])
    specs = [
        _block(bq, hd, on_q, seq_minor),
        _block(bkv, hd, on_kv, seq_minor),
        _block(bkv, hd_v, on_kv, seq_minor),
        pl.BlockSpec((bq, _LANES), lambda h, s, qi, ki, fl: (qi[s], 0)),
        pl.BlockSpec((_SUBLANES, bkv), lambda h, s, qi, ki, fl: (0, ki[s])),
    ]
    if mask is not None:
        specs.append(pl.BlockSpec((bkv // bkvc, bq, bkvc),
                                  lambda h, s, qi, ki, fl: (ki[s], qi[s], 0)))
    return on_q, specs


def _masked(*operands):
    """The operands that are there: a kernel's mask is its last input
    before the backward's own, and absent for a row without one."""
    return tuple(x for x in operands if x is not None)


def _forward(q, k, v, segment_ids, lists, blocks, window, interpret, residuals,
             mask=None, seq_minor=False):
    assert mask is None or not seq_minor
    hq, bq = q.shape[0], blocks.bq
    (hd, t), hd_v = (q.shape[1:], v.shape[1]) if seq_minor else (q.shape[:0:-1], v.shape[2])
    on_q, in_specs = _q_major(q, k, v, blocks, mask, seq_minor)
    out_shape = [jax.ShapeDtypeStruct((hq, hd_v, t) if seq_minor else (hq, t, hd_v), q.dtype)]
    out_specs = [_block(bq, hd_v, on_q, seq_minor)]
    if residuals:
        out_shape.append(jax.ShapeDtypeStruct((hq, 1, t), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, 1, bq), lambda h, s, qi, ki, fl: (h, 0, qi[s])))
    out = _call(
        functools.partial(_fwd_kernel, blocks=blocks, window=window,
                          masked=mask is not None, seq_minor=seq_minor),
        "splash_pairs_fwd", (hq, lists.n), lists.q_major, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch=[pltpu.VMEM((bq, _LANES), jnp.float32),
                 pltpu.VMEM((bq, _LANES), jnp.float32),
                 pltpu.VMEM((bq, hd_v), jnp.float32),
                 *([pltpu.VMEM((bq, hd), q.dtype)] if seq_minor else [])],
        semantics=("parallel", "arbitrary"), interpret=interpret,
        operands=_masked(q, k, v, *_segment_operands(segment_ids, q_in_lanes=False),
                         mask))
    return out[0], (out[1][:, 0] if residuals else None)


def _backward(q, k, v, segment_ids, lists, lse, do, di, blocks, window,
              interpret, mask_t=None, seq_minor=False):
    """(dq, dk, dv) from one kernel over the kv-major list, laid as q, k
    and v are. A head-first dq's minor dimension is a multiple of the
    lanes in the kernel's own copies (192 in 256): sliced here. A
    sequence-minor dq is the block of the sum as it stands."""
    assert mask_t is None or not seq_minor
    hq, hkv = q.shape[0], k.shape[0]
    (hd, t), hd_v = (q.shape[1:], v.shape[1]) if seq_minor else (q.shape[:0:-1], v.shape[2])
    group = hq // hkv
    bq, bkv, _ = blocks
    hd_out = hd if seq_minor else -(-hd // _LANES) * _LANES
    on_q = lambda h, s, g, qi, ki, fl: (h * group + g, qi[s])
    on_kv = lambda h, s, g, qi, ki, fl: (h, ki[s])
    # Sublane-broadcast, as splash does it: Mosaic has no retiling of a
    # single row yet.
    q_rows = pl.BlockSpec((None, _SUBLANES, bq),
                          lambda h, s, g, qi, ki, fl: (h * group + g, 0, qi[s]))
    rows = lambda x: jnp.broadcast_to(x[:, None, :], (hq, _SUBLANES, t))
    # The mask transposed by q blocks, `[T / bq, T, bq]`: a pair's tile
    # with its kv rows along sublanes, as this kernel computes.
    mask_spec = () if mask_t is None else (pl.BlockSpec(
        (None, bkv, bq), lambda h, s, g, qi, ki, fl: (qi[s], ki[s], 0)),)
    dq, _, dk, dv = _call(
        functools.partial(_bwd_kernel, blocks=blocks, window=window, group=group,
                          masked=mask_t is not None, seq_minor=seq_minor),
        "splash_pairs_bwd", (hkv, lists.n, group), lists.kv_major,
        in_specs=[
            _block(bq, hd, on_q, seq_minor),
            _block(bkv, hd, on_kv, seq_minor),
            _block(bkv, hd_v, on_kv, seq_minor),
            pl.BlockSpec((_SUBLANES, bq), lambda h, s, g, qi, ki, fl: (0, qi[s])),
            pl.BlockSpec((bkv, _LANES), lambda h, s, g, qi, ki, fl: (ki[s], 0)),
            *mask_spec,
            q_rows,
            _block(bq, hd_v, on_q, seq_minor),
            q_rows,
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   _block(bkv, hd, on_kv, seq_minor),
                   _block(bkv, hd_v, on_kv, seq_minor)],
        out_shape=[jax.ShapeDtypeStruct((hq, hd, t) if seq_minor else (hq, t, hd_out), q.dtype),
                   jax.ShapeDtypeStruct((hq, hd, t), jnp.float32),  # the sums, in passing
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch=[pltpu.VMEM((bkv, hd), jnp.float32),
                 pltpu.VMEM((bkv, hd_v), jnp.float32),
                 *([pltpu.VMEM((bkv, hd), k.dtype), pltpu.VMEM((bkv, hd_v), v.dtype)]
                   if seq_minor else [pltpu.VMEM((hd, bkv), k.dtype)]),
                 pltpu.VMEM((hd, bq), jnp.float32),
                 pltpu.VMEM((2, hd, bq), jnp.float32),
                 pltpu.VMEM((hd, bq) if seq_minor else (bq, hd_out), q.dtype),
                 pltpu.SemaphoreType.DMA((4,)), pltpu.SMEM((4,), jnp.int32)],
        semantics=("arbitrary", "arbitrary", "arbitrary"), interpret=interpret,
        operands=_masked(q, k, v, *_segment_operands(segment_ids, q_in_lanes=True),
                         mask_t, rows(lse), do, rows(di)))
    return (dq if seq_minor else dq[..., :hd]), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def pair_attention(q, k, v, segment_ids, lists: PairLists, blocks: Blocks,
                   window: Optional[int], residual_name: str, interpret: bool,
                   seq_minor: bool = False):
    """Attention of one packed row over the block pairs `lists` names:
    q `[Hq, T, hd]` (already scaled), k `[Hkv, T, hd]`, v `[Hkv, T,
    hd_v]`, `segment_ids` `[T]` -> `[Hq, T, hd_v]`; `seq_minor`: every
    one of them, and each gradient, `[H, hd, T]`. A q block must have
    a pair, or its output block is never written. The output and the
    logsumexp the backward keeps are `checkpoint_name`d `residual_name`,
    so a remat policy can keep them and the backward not run the forward
    kernel again."""
    out, _ = _forward(q, k, v, segment_ids, lists, blocks, window, interpret,
                      residuals=False, seq_minor=seq_minor)
    return checkpoint_name(out, residual_name)


def _pair_attention_fwd(q, k, v, segment_ids, lists, blocks, window,
                        residual_name, interpret, seq_minor=False):
    out, lse = _forward(q, k, v, segment_ids, lists, blocks, window, interpret,
                        residuals=True, seq_minor=seq_minor)
    out, lse = (checkpoint_name(x, residual_name) for x in (out, lse))
    return out, (q, k, v, segment_ids, lists, out, lse)


def _pair_attention_bwd(blocks, window, residual_name, interpret, seq_minor, res, do):
    del residual_name
    q, k, v, segment_ids, lists, out, lse = res
    di = jnp.einsum("hdt,hdt->ht" if seq_minor else "hsd,hsd->hs",
                    out.astype(jnp.float32), do.astype(jnp.float32))
    dq, dk, dv = _backward(q, k, v, segment_ids, lists, lse, do, di, blocks, window,
                           interpret, seq_minor=seq_minor)
    return dq, dk, dv, None, None


pair_attention.defvjp(_pair_attention_fwd, _pair_attention_bwd)


def transpose_mask(mask, bq: int):
    """`[T / bkvc, T, bkvc]` (the forward's layout of a mask operand) ->
    `[T / bq, T, bq]` (the backward's): `out[i, s, r] = mask[s // bkvc,
    i bq + r, s % bkvc]`."""
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
    return (out, lse), (q, k, v, segment_ids, lists, mask_t, out, lse)


def _pair_attention_chosen_bwd(blocks, residual_name, interpret, res, cts):
    del residual_name
    q, k, v, segment_ids, lists, mask_t, out, lse = res
    do, _ = cts
    di = jnp.einsum("hsd,hsd->hs", out.astype(jnp.float32), do.astype(jnp.float32))
    dq, dk, dv = _backward(q, k, v, segment_ids, lists, lse, do, di, blocks, None,
                           interpret, mask_t=mask_t)
    return dq, dk, dv, None, None, None, None


pair_attention_chosen.defvjp(_pair_attention_chosen_fwd, _pair_attention_chosen_bwd)
