"""Attention over keys that a learned indexer chooses, a query at a time
(`models/config.IndexerConfig` has the equations).

With `iq` `[T, H, d]`, `ik` `[T, d]` and `iw` `[T, H]` the indexer's
projections of a packed row (`models/transformer._index_proj`), the score
of key s for query t is `I[t, s] = sum_j iw[t, j] relu(iq[t, j] . ik[s])`
in float32 (both scales folded into `iw`). A query's keys `A_t` are the
places `s <= t` of its own sequence; with more than `top_k` of them it
keeps `S_t = {s in A_t : I[t, s] >= tau_t}`, `tau_t` its `top_k`-th
largest score (ties at `tau_t` all kept: a `top_k` that breaks ties by
index would keep exactly `top_k`), otherwise all of `A_t`. Attention is
dense attention under the mask `s in S_t`, the same for every q head;
the mask is a constant of the backward pass. The indexer's loss of a
row is `sum_t KL(pbar[t, .] || softmax_{S_t} I[t, .])` over real tokens,
`pbar` the q heads' mean attention probability under `stop_gradient`.

Two forms share `indexed_attention`'s signature:

- the plain form (`_plain_row`): `[T, T]` scores, the threshold by a
  sort, einsum attention under the mask, the KL by autodiff. What the
  einsum reference runs (the CPU, tests) and, on the chip, rows that are
  not alone in their call or shorter than 2,048: the static splash
  kernels (`ops/attention._splash_kernel`) take a mask that is a
  function of place alone, not a value of the run.
- the kernels of a long row alone (`ops/attention._rows_skip`):
  `ops/pallas/index_kernels.index_select` (scores, the exact threshold,
  the choice as an int8 operand), `ops/pallas/splash_pairs.
  pair_attention_chosen` (the row's live block pairs under that operand)
  and `index_kernels.index_kl` (the KL and the indexer's backward over
  the same pairs). Scopes `index_scores` / `index_select` are one kernel
  there; `index_kl` the other.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops.attention import (
    NEG_INF, SPLASH_RESIDUAL_NAME, _pair_lists, _rows_skip, _static_block_pairs,
    live_block_pairs, segment_causal_mask, splash_run_shape,
)


# The sums an indexed layer adds to `forward`'s aux sums.
INDEX_SUMS = ("index_kl", "index_chosen", "index_cells")


def index_scores(iq, ik, iw):
    """`I` `[T, T]` float32 of one row: iq `[T, H, d]`, ik `[T, d]`, iw
    `[T, H]` (scaled). An exact zero is +0."""
    with jax.named_scope("index_scores"):
        s = jnp.einsum("thd,sd->hts", iq, ik, preferred_element_type=jnp.float32)
        scores = jnp.einsum("th,hts->ts", iw.astype(jnp.float32), jax.nn.relu(s))
        return jnp.where(scores == 0.0, 0.0, scores)


def choose(scores, valid, top_k: int):
    """(choice bool `[T, T]`, tau `[T]`): of a query's `valid` keys those
    scored at least its `top_k`-th largest, tau, which keeps all of them
    where it has exactly `top_k`; all of them where it has fewer (tau
    -inf)."""
    with jax.named_scope("index_select"):
        t = scores.shape[-1]
        if t <= top_k:
            return valid, jnp.full(scores.shape[:-1], -jnp.inf, jnp.float32)
        held = jnp.where(valid, scores, -jnp.inf)
        kth = -jnp.sort(-held, axis=-1)[..., top_k - 1]
        tau = jnp.where(valid.sum(axis=-1) >= top_k, kth, -jnp.inf)
        return valid & (scores >= tau[..., None]), tau


def index_counts(positions, segment_ids, top_k: int):
    """(cells the indexer scores, cells an exact choice without ties
    keeps, queries that have a choice) of packed rows, from each real
    token's place in its sequence: `sum L (L + 1) / 2`, `sum min(t + 1,
    top_k)`, `#{t + 1 > top_k}`. numpy or jax arrays alike (the host's
    counters and the device's statistic)."""
    xp = jnp if isinstance(positions, jax.Array) else np
    real = segment_ids > 0
    keys = xp.where(real, positions + 1, 0)
    return (keys.sum(), xp.minimum(keys, top_k).sum(), (keys > top_k).sum())


def _plain_row(q, k, v, iq, ik, iw, segment_ids, positions, *, top_k, want_kl):
    """One row, the plain form: (out `[T, Hq, hd]`, KL summed over real
    tokens, cells chosen by real tokens, the choice bool `[T, T]`, tau
    `[T]`)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    valid = segment_causal_mask(segment_ids, segment_ids, positions, positions)
    scores = index_scores(iq, ik, iw)
    choice, tau = choose(jax.lax.stop_gradient(scores), valid, top_k)
    with jax.named_scope("attn_kernel"):
        qg = q.reshape(t, hkv, hq // hkv, hd).astype(jnp.float32)
        qk = jnp.einsum("qhgd,khd->hgqk", qg, k.astype(jnp.float32)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(choice[None, None], qk, NEG_INF), axis=-1)
        probs = jnp.where(choice.any(axis=-1)[None, None, :, None], probs, 0.0)
        out = jnp.einsum("hgqk,khd->qhgd", probs, v.astype(jnp.float32))
        out = out.reshape(t, hq, v.shape[-1]).astype(q.dtype)
    real = segment_ids > 0
    n_chosen = jnp.sum(choice & real[:, None], dtype=jnp.float32)
    kl = jnp.zeros((), jnp.float32)
    if want_kl:
        with jax.named_scope("index_kl"):
            pbar = jax.lax.stop_gradient(probs.mean(axis=(0, 1)))
            # (a padded position chooses nothing: its row stays finite)
            empty = jnp.where(choice.any(axis=-1, keepdims=True), -jnp.inf, 0.0)
            log_sigma = jax.nn.log_softmax(jnp.where(choice, scores, empty), axis=-1)
            held = pbar > 0.0
            cell = jnp.where(
                held, pbar * (jnp.log(jnp.where(held, pbar, 1.0))
                              - jnp.where(held, log_sigma, 0.0)), 0.0)
            kl = jnp.sum(cell.sum(axis=-1) * real)
    return out, kl, n_chosen, choice, tau


def _select_range(segment_ids, rows: int, chunk: int):
    """A q block's first and last kv chunk: the row's live pairs at
    blocks of `(rows, chunk)` (`live_block_pairs` and the causal mask),
    which for sequences numbered in order are one run a q block; its own
    diagonal is always among them."""
    t = segment_ids.shape[0]
    live = live_block_pairs(segment_ids, rows, chunk) & _static_block_pairs(t, rows, chunk)
    n = live.shape[1]
    lo = jnp.argmax(live, axis=1)
    hi = n - 1 - jnp.argmax(live[:, ::-1], axis=1)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def _kl_sum(iq, ik, w, q, k, lse, mask, stats, segment_ids, lists, interpret):
    from areal_tpu.ops.pallas.index_kernels import index_kl

    return index_kl(iq, ik, w, q, k, lse, mask, stats, segment_ids, lists,
                    grads=False, interpret=interpret)


def _kl_sum_fwd(iq, ik, w, q, k, lse, mask, stats, segment_ids, lists, interpret):
    from areal_tpu.ops.pallas.index_kernels import index_kl

    # Two calls, so that each pass of a step under remat runs one: the
    # forward pass reads the sum alone (nothing reads the residuals
    # there), the backward pass the gradients alone.
    args = (iq, ik, w, q, k, lse, mask, stats, segment_ids, lists)
    kl = index_kl(*args, grads=False, interpret=interpret)
    diq, dik, dw = index_kl(*args, grads=True, interpret=interpret)
    return kl, (diq.astype(iq.dtype), dik.astype(ik.dtype), dw.astype(w.dtype))


def _kl_sum_bwd(interpret, res, ct):
    return tuple(ct.astype(g.dtype) * g for g in res) + (None,) * 7


_kl_sum.defvjp(_kl_sum_fwd, _kl_sum_bwd)


def _kernel_row(q, k, v, iq, ik, iw, segment_ids, positions, *, top_k, want_kl,
                run_shape, interpret):
    """One long row alone, the kernels: as `_plain_row` (the choice as
    the kernels' mask operand read back, real queries' and keys')."""
    from areal_tpu.ops.pallas import index_kernels as ik_
    from areal_tpu.ops.pallas.splash_pairs import (
        Blocks, pair_attention_chosen, transpose_mask,
    )

    t, hq, hd = q.shape
    t_run, bq, bkv, bkvc = run_shape
    q = q * jnp.asarray(hd ** -0.5, q.dtype)
    if t_run > t:
        pad = lambda a: jnp.pad(a, ((0, t_run - t),) + ((0, 0),) * (a.ndim - 1))
        q, k, v, iq, ik, iw, segment_ids = map(pad, (q, k, v, iq, ik, iw, segment_ids))
    heads_first = lambda a: a.transpose(1, 0, 2)
    qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
    iqh = heads_first(jax.lax.stop_gradient(iq))
    ik0, w0 = jax.lax.stop_gradient(ik), jax.lax.stop_gradient(iw).astype(jnp.float32)
    lo, hi = _select_range(segment_ids, ik_.SELECT_ROWS, bkvc)
    with jax.named_scope("index_scores"):  # one kernel scores and chooses
        mask, stats = ik_.index_select(
            iqh, ik0, w0, segment_ids, lo, hi, top_k=top_k, chunk=bkvc,
            interpret=interpret)
    with jax.named_scope("attn_kernel"):
        out, lse = pair_attention_chosen(
            qh, kh, vh, segment_ids, _pair_lists(segment_ids, bq, bkv, None), mask,
            transpose_mask(mask, bq), Blocks(bq, bkv, bkvc), SPLASH_RESIDUAL_NAME,
            interpret)
    real = segment_ids > 0
    n_chosen = jnp.sum(jnp.where(real, stats[:, ik_.COUNT], 0.0))
    kl = jnp.zeros((), jnp.float32)
    if want_kl:
        with jax.named_scope("index_kl"):
            kl = _kl_sum(
                heads_first(iq), ik, iw.astype(jnp.float32),
                *(jax.lax.stop_gradient(x) for x in (qh, kh, lse)), mask, stats,
                segment_ids, _pair_lists(segment_ids, ik_.KL_BQ, bkvc, None), interpret)
    choice = (mask.transpose(1, 0, 2).reshape(t_run, t_run)[:t, :t] != 0
              ) & segment_causal_mask(segment_ids[:t], segment_ids[:t], positions, positions)
    return heads_first(out)[:t].astype(q.dtype), kl, n_chosen, choice, stats[:t, ik_.TAU]


def indexed_attention(
    q: jnp.ndarray,  # [R, T, Hq, hd]
    k: jnp.ndarray,  # [R, T, Hkv, hd]
    v: jnp.ndarray,  # [R, T, Hkv, hd]
    iq: jnp.ndarray,  # [R, T, H, d]
    ik: jnp.ndarray,  # [R, T, d]
    iw: jnp.ndarray,  # [R, T, H], both scales folded in
    segment_ids: jnp.ndarray,  # [R, T]
    positions: jnp.ndarray,  # [R, T]
    top_k: int,
    impl: str,
    want_kl: bool,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Packed rows -> (attention's output `[R, T, Hq, hd]`, the layer's
    sums: `index_kl` (KL over real tokens; 0 without `want_kl`),
    `index_chosen` (cells real tokens chose, the device's own count:
    ties lift it above `index_counts`'), `index_cells` (cells scored);
    and, not sums, `choice` bool `[R, T, T]` and `tau` `[R, T]`, for
    whoever compares the choice itself).
    `impl` is the attention implementation that runs the row
    (`ops/attention.resolve_attn_impl`): under "splash" a long row alone
    runs the kernels, every other row the plain form."""
    r, t = segment_ids.shape
    run_shape = splash_run_shape(t) if impl == "splash" else None
    if run_shape is not None and _rows_skip(r, run_shape[0]):
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        out, kl, n, choice, tau = (a[None] for a in _kernel_row(
            q[0], k[0], v[0], iq[0], ik[0], iw[0], segment_ids[0], positions[0],
            top_k=top_k, want_kl=want_kl, run_shape=run_shape,
            interpret=bool(interpret)))
    else:
        out, kl, n, choice, tau = jax.vmap(functools.partial(
            _plain_row, top_k=top_k, want_kl=want_kl))(
                q, k, v, iq, ik, iw, segment_ids, positions)
    cells = index_counts(positions, segment_ids, top_k)[0].astype(jnp.float32)
    return out, dict(index_kl=kl.sum(), index_chosen=n.sum(), index_cells=cells,
                     choice=choice, tau=tau)
