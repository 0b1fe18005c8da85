"""The indexer's two kernels (Pallas, TPU) for a long packed row alone
in its call (`ops/indexer.py` has the equations and the plain forms).

`index_select` walks the row in q blocks of `rows` queries. For each it
computes the indexer's scores over the block's causal prefix (the kv
chunks `lo .. hi` its sequences reach, from the row's segment ids), keeps
them in VMEM as the order-preserving int32 image of a float32, finds
every query's `top_k`-th largest exactly by 32 halvings of that image
(a count of the keys at or above a candidate, a bit at a time from the
sign down), and writes the choice as int8 `[T / chunk, T, chunk]`: chunk
j holds the columns `j chunk ..` of every query's row of the mask, so
that the attention kernels' block of it is the (q block, kv block) tile
in kv sub-blocks. A query with `top_k` keys or fewer finds the threshold
below every score and keeps them all; ties at the threshold are all
kept. Beside the mask a query's threshold, the log-sum-exp of its chosen
scores and how many it chose.

`index_kl` walks the row's live block pairs (`ops/attention._pair_lists`,
q-major) with the q heads innermost: a pair's attention probabilities
are summed over the heads into one tile of scratch, never `[T, T]`, and
meet the softmax of the indexer's scores over the chosen keys there. It
gives the row's sum of KL over real tokens, or (a second variant, for
the backward pass) the gradient of that sum in the indexer's q, k and
head weights: `dI = sigma - pbar` on the chosen cells, through the relu
and the three products. The key's gradient is one `[T, d]` block that
stays in VMEM for the whole walk.

Device op names hold no key of `benchmark/trace_reduce`'s `attention`
category: the attention kernels' share stays theirs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.splash_pairs import (
    _LANES, _MASK_VALUE, _NN, _NT, _SUBLANES, FIRST, LAST, _keep,
    _segment_operands,
)

_TN = (((0,), (0,)), ((), ()))  # left-hand side transposed
_INT_MIN = int(np.iinfo(np.int32).min)
_FLIP = 0x7FFFFFFF

# q rows of one step of `index_select`: the scores of `rows` queries over
# a whole row of 16,384 are 8 MB of VMEM at 128.
SELECT_ROWS = 128
# (q block, kv block) of `index_kl`'s walk; the kv block is one chunk of
# the mask.
KL_BQ = 256


def _ordered(x):
    """float32 -> int32, order-preserving under signed comparison."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b >= 0, b, b ^ _FLIP)


def _unordered(key):
    return lax.bitcast_convert_type(
        jnp.where(key >= 0, key, key ^ _FLIP), jnp.float32)


def _lane_tiles(x, op):
    """[r, n 128] -> [r, 128]: `op` over the n lane tiles, elementwise."""
    out = x[:, :_LANES]
    for c in range(1, x.shape[1] // _LANES):
        out = op(out, x[:, c * _LANES:(c + 1) * _LANES])
    return out


def _tile_scores(iq_ref, ik, w):
    """The indexer's scores of a tile: iq_ref `[H, bq, d]`, ik `[bk, d]`,
    w `[bq, H]` float32 (the head weights times both scales) ->
    `[bq, bk]` float32, `sum_h w[:, h] relu(iq[h] ik^T)`; an exact zero
    is +0 (a negative weight times the relu's zero is -0, which the
    int32 image would order below +0)."""
    acc = None
    for h in range(iq_ref.shape[0]):
        s = lax.dot_general(iq_ref[h], ik, _NT, preferred_element_type=jnp.float32)
        term = w[:, h:h + 1] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return jnp.where(acc == 0.0, 0.0, acc)


def _select_kernel(lo_ref, hi_ref, iq_ref, ik_ref, w_ref, qseg_ref, kvseg_ref,
                   mask_ref, stats_ref, keys_sc, *, top_k):
    n_chunks, rows, chunk = keys_sc.shape
    i = pl.program_id(0)
    lo, hi = lo_ref[i], hi_ref[i] + 1
    w = w_ref[...]
    qseg = jnp.tile(qseg_ref[...], (1, chunk // _LANES))

    def score(j, _):
        scores = _tile_scores(iq_ref, ik_ref[j], w)
        valid = _keep(i * rows, j * chunk, scores.shape, qseg,
                      kvseg_ref[j, :1, :], None, True)
        keys_sc[j] = jnp.where(valid, _ordered(scores), _INT_MIN)
        return _

    lax.fori_loop(lo, hi, score, None)

    def count_at_least(v):
        def one(j, acc):
            return acc + _lane_tiles((keys_sc[j] >= v).astype(jnp.int32), jnp.add)

        acc = lax.fori_loop(lo, hi, one, jnp.zeros((rows, _LANES), jnp.int32))
        return acc.sum(axis=1, keepdims=True)

    def halve(it, v):
        # From the sign bit down: the candidate is the threshold so far
        # with this bit set (the sign bit cleared: INT_MIN ^ INT_MIN = 0).
        cand = v ^ jnp.left_shift(jnp.int32(1), 31 - it)
        return jnp.where(count_at_least(cand) >= top_k, cand, v)

    # The largest v with `top_k` keys at or above it: the top_k-th largest
    # key, or INT_MIN (below every score) where the prefix holds fewer.
    v = lax.fori_loop(0, 32, halve, jnp.full((rows, 1), _INT_MIN, jnp.int32))

    top = lax.fori_loop(
        lo, hi, lambda j, acc: jnp.maximum(acc, _lane_tiles(keys_sc[j], jnp.maximum)),
        jnp.full((rows, _LANES), _INT_MIN, jnp.int32))
    m = _unordered(top.max(axis=1, keepdims=True))  # a query's best score is chosen

    def choose(j, carry):
        l, n = carry
        key = keys_sc[j]
        chosen = (key >= v) & (key != _INT_MIN)
        mask_ref[j] = chosen.astype(jnp.int32).astype(jnp.int8)
        e = jnp.where(chosen, jnp.exp(_unordered(key) - m), 0.0)
        return (l + _lane_tiles(e, jnp.add),
                n + _lane_tiles(chosen.astype(jnp.int32), jnp.add))

    l, n = lax.fori_loop(lo, hi, choose, (jnp.zeros((rows, _LANES), jnp.float32),
                                          jnp.zeros((rows, _LANES), jnp.int32)))

    def blank(j, _):
        mask_ref[j] = jnp.zeros((rows, chunk), jnp.int8)
        return _

    lax.fori_loop(0, lo, blank, None)
    lax.fori_loop(hi, n_chunks, blank, None)

    tau = jnp.where(v == _INT_MIN, -jnp.inf, _unordered(v))
    lse = m + jnp.log(l.sum(axis=1, keepdims=True))
    count = n.sum(axis=1, keepdims=True).astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    stats_ref[...] = jnp.where(lane == 0, tau, jnp.where(
        lane == 1, lse, jnp.where(lane == 2, count, 0.0)))


# Lanes of `index_select`'s statistics: a query's threshold, the
# log-sum-exp of its chosen scores, how many keys it chose.
TAU, LSE, COUNT = 0, 1, 2


def index_select(iq, ik, w, segment_ids, lo, hi, *, top_k, chunk, interpret,
                 rows=SELECT_ROWS):
    """iq `[H, T, d]`, ik `[T, d]`, w `[T, H]` float32 (scaled),
    `segment_ids` `[T]`, `lo` / `hi` int32 `[T / rows]` (a q block's
    first and last kv chunk) -> (mask int8 `[T / chunk, T, chunk]`,
    statistics float32 `[T, 128]`: lanes TAU, LSE, COUNT)."""
    n_heads, t, d = iq.shape
    n_chunks = t // chunk
    qseg, kvseg = _segment_operands(segment_ids, q_in_lanes=False)
    kvseg = kvseg.reshape(_SUBLANES, n_chunks, chunk).transpose(1, 0, 2)
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, lo, hi: (0,) * len(shape))
    with jax.named_scope("index_select"):
        return pl.pallas_call(
            functools.partial(_select_kernel, top_k=top_k),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(t // rows,),
                in_specs=[
                    pl.BlockSpec((n_heads, rows, d), lambda i, lo, hi: (0, i, 0)),
                    whole(n_chunks, chunk, d),
                    pl.BlockSpec((rows, n_heads), lambda i, lo, hi: (i, 0)),
                    pl.BlockSpec((rows, _LANES), lambda i, lo, hi: (i, 0)),
                    whole(n_chunks, _SUBLANES, chunk),
                ],
                out_specs=[
                    pl.BlockSpec((n_chunks, rows, chunk), lambda i, lo, hi: (0, i, 0)),
                    pl.BlockSpec((rows, _LANES), lambda i, lo, hi: (i, 0)),
                ],
                scratch_shapes=[pltpu.VMEM((n_chunks, rows, chunk), jnp.int32)]),
            out_shape=[jax.ShapeDtypeStruct((n_chunks, t, chunk), jnp.int8),
                       jax.ShapeDtypeStruct((t, _LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024),
            name="index_select", interpret=interpret,
        )(lo, hi, iq, ik.reshape(n_chunks, chunk, d), w, qseg, kvseg)


def _kl_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, lse_ref, iq_ref, ik_ref,
               w_ref, stats_ref, qseg_ref, kvseg_ref, mask_ref, *rest, grads):
    if grads:
        diq_ref, dw_ref, dik_ref, pbar_sc, diq_sc, dw_sc = rest
    else:
        kl_ref, pbar_sc, kl_sc = rest
    hq, bq, _ = q_ref.shape
    hkv, bkv, _ = k_ref.shape
    group = hq // hkv
    s = pl.program_id(0)
    flags = flags_ref[s]

    if grads:
        @pl.when(s == 0)
        def start():
            dik_ref[...] = jnp.zeros_like(dik_ref)

    @pl.when(flags & FIRST != 0)
    def init():
        if grads:
            diq_sc[...] = jnp.zeros_like(diq_sc)
            dw_sc[...] = jnp.zeros_like(dw_sc)
        else:
            kl_sc[...] = jnp.zeros_like(kl_sc)

    keep = _keep(qi_ref[s] * bq, ki_ref[s] * bkv, (bq, bkv),
                 jnp.tile(qseg_ref[...], (1, bkv // _LANES)), kvseg_ref[:1, :],
                 None, True, mask_ref[0])

    # The q heads' probabilities over this pair, summed a head at a time.
    pbar_sc[...] = jnp.zeros_like(pbar_sc)

    def kv_head(g, _):
        k, lse = k_ref[g], lse_ref[g]
        for j in range(group):
            qk = lax.dot_general(q_ref[g * group + j], k, _NT,
                                 preferred_element_type=jnp.float32)
            pbar_sc[...] += jnp.exp(
                jnp.where(keep, qk, _MASK_VALUE) - lse[:, j:j + 1])
        return _

    lax.fori_loop(0, hkv, kv_head, None)
    pbar = pbar_sc[...] * (1.0 / hq)

    w, ik = w_ref[...], ik_ref[...]
    log_sigma = _tile_scores(iq_ref, ik, w) - stats_ref[:, LSE:LSE + 1]
    real = (qseg_ref[:, :1] > 0).astype(jnp.float32)
    if not grads:
        held = pbar > 0.0
        kl = jnp.where(held, pbar * (jnp.log(jnp.where(held, pbar, 1.0)) - log_sigma), 0.0)
        kl_sc[...] += _lane_tiles(kl * real, jnp.add)

        @pl.when(flags & LAST != 0)
        def end():
            kl_ref[...] = kl_sc[...]

        return

    d_scores = (jnp.where(keep, jnp.exp(log_sigma), 0.0) - pbar) * real
    rows = pl.ds(pl.multiple_of(ki_ref[s] * bkv, bkv), bkv)
    for h in range(iq_ref.shape[0]):
        iq = iq_ref[h]
        sh = lax.dot_general(iq, ik, _NT, preferred_element_type=jnp.float32)
        dw_sc[:, h:h + 1] += (d_scores * jnp.maximum(sh, 0.0)).sum(axis=1, keepdims=True)
        g = jnp.where(sh > 0.0, d_scores * w[:, h:h + 1], 0.0).astype(ik.dtype)
        diq_sc[h] += lax.dot_general(g, ik, _NN, preferred_element_type=jnp.float32)
        dik_ref[rows, :] += lax.dot_general(g, iq, _TN,
                                            preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def end():
        diq_ref[...] = diq_sc[...]
        dw_ref[...] = dw_sc[...]


def index_kl(iq, ik, w, q, k, lse, mask, stats, segment_ids, lists, *, grads,
             interpret):
    """The row's sum over real tokens of the KL from the q heads' mean
    attention probability to the softmax of the indexer's scores over the
    chosen keys (`grads` False), or that sum's gradient in (iq, ik, w)
    (`grads` True: float32, their shapes). iq `[H, T, d]`, ik `[T, d]`,
    w `[T, H]` float32 (scaled); q `[Hq, T, hd]` (scaled), k `[Hkv, T,
    hd]`, lse `[Hq, T]` attention's own; `mask`, `stats` as
    `index_select` made them; `lists` the row's live pairs at blocks of
    `(KL_BQ, chunk)`, of which the q-major list is walked."""
    n_heads, t, d = iq.shape
    hq, _, hd = q.shape
    hkv = k.shape[0]
    bq, bkv = KL_BQ, mask.shape[-1]
    qseg, kvseg = _segment_operands(segment_ids, q_in_lanes=False)
    on_q = lambda *dims: pl.BlockSpec(
        dims, lambda s, qi, ki, fl: (0,) * (len(dims) - 2) + (qi[s], 0))
    on_kv = lambda *dims: pl.BlockSpec(
        dims, lambda s, qi, ki, fl: (0,) * (len(dims) - 2) + (ki[s], 0))
    in_specs = [
        on_q(hq, bq, hd), on_kv(hkv, bkv, hd), on_q(hkv, bq, hq // hkv),
        on_q(n_heads, bq, d), on_kv(bkv, d), on_q(bq, n_heads), on_q(bq, _LANES),
        on_q(bq, _LANES),
        pl.BlockSpec((_SUBLANES, bkv), lambda s, qi, ki, fl: (0, ki[s])),
        pl.BlockSpec((1, bq, bkv), lambda s, qi, ki, fl: (ki[s], qi[s], 0)),
    ]
    tile = pltpu.VMEM((bq, bkv), jnp.float32)
    if grads:
        out_specs = [on_q(n_heads, bq, d), on_q(bq, n_heads),
                     pl.BlockSpec((t, d), lambda s, qi, ki, fl: (0, 0))]
        out_shape = [jax.ShapeDtypeStruct(iq.shape, jnp.float32),
                     jax.ShapeDtypeStruct(w.shape, jnp.float32),
                     jax.ShapeDtypeStruct(ik.shape, jnp.float32)]
        scratch = [tile, pltpu.VMEM((n_heads, bq, d), jnp.float32),
                   pltpu.VMEM((bq, n_heads), jnp.float32)]
    else:
        out_specs = [on_q(bq, _LANES)]
        out_shape = [jax.ShapeDtypeStruct((t, _LANES), jnp.float32)]
        scratch = [tile, pltpu.VMEM((bq, _LANES), jnp.float32)]
    name = "index_kl_bwd" if grads else "index_kl_fwd"
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_kl_kernel, grads=grads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(lists.n,), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024),
            name=name, interpret=interpret,
        )(*lists.q_major, q, k,
          lse.reshape(hkv, hq // hkv, t).transpose(0, 2, 1), iq, ik, w, stats,
          qseg, kvseg, mask)
    if grads:
        diq, dw, dik = out
        return diq, dik, dw
    return out[0].sum()
