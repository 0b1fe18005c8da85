"""Packed variable-length causal attention with GQA.

Replaces the reference's flash-attn varlen path
(realhf/impl/model/modules/attn.py:272-289) the TPU way: batches are packed
token streams with *segment ids* (0 = padding, sequences numbered from 1)
and per-token positions; attention is masked to (same segment) AND
(causal by position). Two implementations share one signature:

- `reference_packed_attention`: dense jnp einsum + mask. O(T^2) memory;
  used on CPU tests and as the numerical oracle.
- `splash_packed_attention`: jax's Pallas TPU kernel under a static
  block mask, or this repo's pair kernels over a row's live block pairs
  (areal_tpu.ops.pallas.splash_pairs); what a training layer runs on the
  chip.

`packed_attention` dispatches on platform/size.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


NEG_INF = -2.0**30
LANES = 128  # TPU lane width; splash blocks must be lane-aligned

# checkpoint_name tag for splash-attention residuals (see _splash_kernel).
SPLASH_RESIDUAL_NAME = "splash_attn_residuals"


def segment_causal_mask(
    q_seg: jnp.ndarray, kv_seg: jnp.ndarray, q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Boolean [Tq, Tk]: token i may attend to token j. With a `window`,
    only to the `window` positions that end at its own."""
    same = q_seg[:, None] == kv_seg[None, :]
    causal = q_pos[:, None] >= kv_pos[None, :]
    valid = (q_seg[:, None] > 0) & (kv_seg[None, :] > 0)
    mask = same & causal & valid
    if window is not None:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    return mask


def reference_packed_attention(
    q: jnp.ndarray,  # [T, Hq, hd]
    k: jnp.ndarray,  # [T, Hkv, hd]
    v: jnp.ndarray,  # [T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [T] int32, 0 = pad
    positions: jnp.ndarray,  # [T] int32 within-sequence positions
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,
    chosen: Optional[jnp.ndarray] = None,  # [T, T] bool: a choice of keys a query
) -> jnp.ndarray:
    T, Hq, hd = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd**-0.5
    qg = q.reshape(T, Hkv, group, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # scores: [Hkv, group, Tq, Tk]
    scores = jnp.einsum("qhgd,khd->hgqk", qg, kf) * scale
    mask = segment_causal_mask(
        segment_ids, segment_ids, positions, positions, window=window
    )
    if chosen is not None:
        mask &= chosen
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # Fully-masked (padding) rows: zero out.
    probs = jnp.where(mask.any(axis=-1)[None, None, :, None], probs, 0.0)
    out = jnp.einsum("hgqk,khd->qhgd", probs, vf)
    return out.reshape(T, Hq, v.shape[-1]).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # [B, Hq, hd] — one new token per sequence
    k_cache: jnp.ndarray,  # [B, S, Hkv, hd]
    v_cache: jnp.ndarray,  # [B, S, Hkv, hd]
    cache_lens: jnp.ndarray,  # [B] valid lengths INCLUDING the new token
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-step decode attention against a padded KV cache."""
    B, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else hd**-0.5
    qg = q.reshape(B, Hkv, group, hd).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(S)[None, :]  # [1, S]
    mask = pos < cache_lens[:, None]  # [B, S]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


_SPLASH_MASK_CACHE = {}

# Upper targets for splash's blocks (bq, bkv, bkvc): the largest a run
# shape may take (`splash_run_shape`).
SPLASH_BLOCK_TARGETS = (512, 1024, 512)


def _blocks_dividing(n: int, cap: int) -> list:
    """Multiples of 128 that divide n and are <= cap, largest first
    (splash requires lane-aligned blocks that divide the sequence)."""
    if n % LANES:
        raise ValueError(
            f"splash attention needs seq len a multiple of {LANES}, got {n}"
        )
    top = min(cap, n) // LANES
    return [d * LANES for d in range(top, 0, -1) if n % (d * LANES) == 0]


# How far a row is padded at most to find large blocks: the next
# multiple of this always has q and kv blocks of 512.
_SPLASH_PAD_TO = 512

# splash_cost's constants, ns per q head of one row over the three
# kernel passes a layer runs under full remat (forward, re-forward with
# residuals, fused backward), one per term of _splash_cost_terms: least
# squares over 229 timed run shapes of 19 row lengths on one v5e, 12 / 2
# heads of 128 (scripts/splash_shape_sweep.py; docs/perf_notes.md, "How
# the row length picks the splash blocks"). Median error 6 %; the shape
# picked is within 3 % of the fastest measured one for every length of
# 896 and above but 3840, which stays as it is (_SPLASH_MIN_GAIN).
# Fitted on the fused backward over static grids: a row alone runs the
# repo's own forward and backward kernels over its lists of live pairs
# (ops/pallas/splash_pairs.py), at blocks these constants still pick (a
# refit is its own change).
_SPLASH_NS = (980.0, 0.0115, 1.13, 1.25)

# The estimate's own median error: a smaller estimated gain is no reason
# to leave the row as it is at the largest blocks that divide it.
_SPLASH_MIN_GAIN = 0.05


def _static_block_pairs(t: int, bq: int, bkv: int,
                        window: Optional[int] = None):
    """bool [nq, nkv]: the (q block, kv block) pairs of a row of length
    `t` that the row's own mask leaves any work in, whatever the row
    holds. Causal: kv block j for q block i when j * bkv <= (i + 1) * bq
    - 1; with a window also when block j ends at or after the first
    column that q block i's first row sees, i * bq - (window - 1)."""
    i = np.arange(t // bq)[:, None]
    j = np.arange(t // bkv)[None, :]
    pairs = j * bkv <= (i + 1) * bq - 1
    if window is not None:
        pairs &= (j + 1) * bkv - 1 >= i * bq - (window - 1)
    return pairs


def _active_block_pairs(t: int, bq: int, bkv: int,
                        window: Optional[int] = None) -> tuple:
    """(active pairs, widest q row) of `_static_block_pairs`. The
    static forward kernel's grid is nq x the widest row: splash shrinks
    the kv axis to the most active blocks any q block has, and skips the
    rest (the fused backward kernel keeps its whole grid and skips the
    work; a row alone walks a list of its live pairs instead:
    `_pair_lists`)."""
    pairs = _static_block_pairs(t, bq, bkv, window)
    return int(pairs.sum()), int(pairs.sum(axis=1).max())


def _diagonal_block_pairs(t: int, bq: int, bkv: int):
    """bool [nq, nkv]: the pairs whose q block and kv block share a place
    in the row. Every token sees itself there."""
    i = np.arange(t // bq)[:, None]
    j = np.arange(t // bkv)[None, :]
    return (j * bkv < (i + 1) * bq) & (i * bq < (j + 1) * bkv)


def live_block_pairs(segment_ids, bq: int, bkv: int):
    """bool [..., nq, nkv]: the (q block, kv block) pairs of packed rows
    `segment_ids` [..., T] that may hold a q token and a kv token of one
    sequence. A block's sequences span [lowest, highest non-zero id];
    a pair is live when both blocks hold a real token and the two spans
    meet. A span covers every id in its block, so no pair with two
    tokens of one sequence is ever dropped; where the packer numbers a
    row's sequences 1, 2, .. in order, each contiguous, none is kept in
    vain either. The pairs on a q block's own diagonal are always live:
    a q block with no kv block at all would divide by a softmax
    denominator of 0, and the NaN it leaves at padded positions reaches
    the weights' gradients as 0 x NaN.

    One rule for the device (traced ids: `_pair_lists`) and the host
    (numpy ids: `attn_block_cells`)."""
    xp = jnp if isinstance(segment_ids, jax.Array) else np
    t = segment_ids.shape[-1]

    def spans(b):
        ids = segment_ids.reshape(*segment_ids.shape[:-1], t // b, b)
        lo = xp.where(ids > 0, ids, np.iinfo(np.int32).max).min(axis=-1)
        return lo, ids.max(axis=-1)

    # A block with no real token spans [largest int, 0]: it meets nothing.
    (lo_q, hi_q), (lo_k, hi_k) = spans(bq), spans(bkv)
    meet = ((lo_q[..., :, None] <= hi_k[..., None, :])
            & (lo_k[..., None, :] <= hi_q[..., :, None]))
    return meet | _diagonal_block_pairs(t, bq, bkv)


def _splash_cost_terms(t: int, bq: int, bkv: int, bkvc: int,
                       window: Optional[int] = None) -> tuple:
    """What splash_cost prices, a q head of one row: grid steps (each a
    fixed overhead whether or not the mask leaves it any work: at
    128 x 128 blocks nearly all of the time; a walked step whose pair
    does not run costs 0.29 us in the forward kernel, 0.31 in dq, 0.41
    in dkv and 0.85 in the fused backward, which also writes a block of
    zeros into its partial of dq: one v5e, 512 x 1024 blocks, PERF.md
    section 6, PR 29 and PR 35), and over the block pairs
    the mask leaves active their bq x bkv cells, their bq rows of
    softmax bookkeeping once per compute sub-block, and the bq + bkv
    rows of q and k/v they load. `window` counts a window layer's pairs
    (for a fit of a `scripts/splash_shape_sweep.py --window` sweep: the
    constants in the tree are fitted on causal masks, and `splash_cost`
    prices those alone). The steps are the static forward grid's: a row
    alone walks its live pairs and no step besides (`_pair_lists`),
    which is not priced."""
    active, widest = _active_block_pairs(t, bq, bkv, window)
    return ((t // bq) * widest, active * bq * bkv,
            active * (bkv // bkvc) * bq, active * (bq + bkv))


def splash_cost(t: int, bq: int, bkv: int, bkvc: int) -> float:
    """Estimated kernel time of a causal row of length `t` at the given
    blocks, in ns per q head: forward, remat's forward and the fused
    backward over the static grids, which is what the constants were
    fitted on. A row alone runs less (its live pairs' steps and no
    others, forward and backward); the blocks picked are the same."""
    terms = _splash_cost_terms(t, bq, bkv, bkvc)
    return sum(ns * x for ns, x in zip(_SPLASH_NS, terms))


def _run_lengths(t: int) -> range:
    """`t, t+128, ..` up to the next multiple of _SPLASH_PAD_TO."""
    return range(t, -(-t // _SPLASH_PAD_TO) * _SPLASH_PAD_TO + 1, LANES)


def _plain_run_shape(t: int, tq: int, tkv: int, tkvc: int) -> tuple:
    """The row as it is, at the largest blocks that divide it."""
    bkv = _blocks_dividing(t, tkv)[0]
    return (t, _blocks_dividing(t, tq)[0], bkv, _blocks_dividing(bkv, tkvc)[0])


@functools.lru_cache(maxsize=None)
def _cheapest_run_shape(t: int, tq: int, tkv: int, tkvc: int) -> tuple:
    plain = _plain_run_shape(t, tq, tkv, tkvc)
    shapes = [
        (t_run, bq, bkv, bkvc)
        for t_run in _run_lengths(t)
        for bq in _blocks_dividing(t_run, tq)
        for bkv in _blocks_dividing(t_run, tkv)
        for bkvc in _blocks_dividing(bkv, tkvc)
    ]
    # min keeps the first of equals: the shorter run, the larger blocks.
    best = min(shapes, key=lambda s: splash_cost(*s))
    if splash_cost(*best) > (1.0 - _SPLASH_MIN_GAIN) * splash_cost(*plain):
        return plain
    return best


def _row_window(t: int, window: Optional[int]) -> Optional[int]:
    """A window that covers the whole row masks nothing: the same kernel
    as no window."""
    return None if window is None or window >= t else int(window)


def splash_run_shape(t: int):
    """(t', bq, bkv, bkvc): the length the splash kernel runs a row of
    length `t` at, and its blocks. A pure function of `t` (and
    `SPLASH_BLOCK_TARGETS`), decided at trace time: a window layer
    runs its rows at the same shape as a causal one. Its mask leaves
    fewer block pairs active, but on a v5e the shape picked here is
    also a window layer's fastest at four of five row lengths timed and
    12 % behind at the fifth, while the causal constants over the
    window's pairs would pick a shape 16 % slower at one
    (tests/model/data/splash_window_sweep_v5e.jsonl).

    Blocks must divide the length, so a row whose count of 128-blocks
    is prime (packed rows are multiples of 128: 3712 = 29 x 128) would
    run 128 x 128 tiles, where per-grid-step overhead, not arithmetic,
    sets the time. Among `t, t+128, ..` up to the next multiple of 512
    and the blocks that divide each, this takes the cheapest by
    `splash_cost`, if that is cheaper than the row as it is at its
    largest dividing blocks by more than the estimate's error; else the
    row stays as it is. So a padded length is never priced above `t`."""
    return _cheapest_run_shape(t, *SPLASH_BLOCK_TARGETS)


def _splash_kernel(t: int, bq: int, bkv: int, bkvc: int, group: int,
                   interpret: bool = False, window: Optional[int] = None):
    """Build the static splash-attention kernel for rows of `t` at the
    given blocks: what short rows and rows together run (the mask object
    is cached; the kernel itself is rebuilt per trace).

    jax's splash attention (jax.experimental.pallas.ops.tpu.splash_attention,
    the production TPU flash kernel — same role as the flash-attn package
    the reference installs, realhf Dockerfile) is used as an MQA problem
    per kv head: q carries the GQA group as its head axis. Global causal
    mask + segment ids equals our (same segment) & (position causal) mask
    because packed segments are contiguous with ascending positions; a
    `window` is splash's LocalMask (window - 1 to the left, none to the
    right) for the same reason, and the kernels skip the block pairs
    wholly behind it. Length and blocks come from `splash_run_shape`; the
    backward is the fused dq/dkv kernel at the same blocks.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    # Only the mask object is cached: the built kernel holds per-trace
    # mask-info buffers, and reusing it across jit traces leaks tracers
    # (UnexpectedTracerError). Rebuilding per trace is cheap — tracing
    # happens once per compiled program, not per step.
    key = (t, group, window)
    mask = _SPLASH_MASK_CACHE.get(key)
    if mask is None:
        one = (sm.CausalMask((t, t)) if window is None else
               sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
        mask = sm.MultiHeadMask([one for _ in range(group)])
        _SPLASH_MASK_CACHE[key] = mask

    bs = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkvc,
        use_fused_bwd_kernel=True,
    )
    # Residuals are checkpoint-named so the "save_attn" remat policy
    # (models/transformer.py) can pin them: backward then runs the
    # flash bwd kernel without re-running the fwd kernel.
    return sk.make_splash_mqa_single_device(
        mask=mask, block_sizes=bs,
        residual_checkpoint_name=SPLASH_RESIDUAL_NAME,
        interpret=interpret,
    )


def _pair_list(pairs, capacity: int):
    """(`pairs` [a, b] in row-major order as (major, minor, flags), int32
    [capacity] each, and how many there are): a major block's pairs in a
    run, its first flagged FIRST and its last LAST; NEW the pair at which
    the walk first comes to its minor block and DONE that at which it
    leaves it (in the kv-major list: where the backward kernel writes a
    q block's sum and does not read it, and where it writes the block of
    dq), NEXT a pair whose minor block is the next pair's too (there the
    kernel keeps the sum for the next step); past the count, the last
    pair again (a step never walked: the grid is as long as the count)."""
    from areal_tpu.ops.pallas.splash_pairs import DONE, FIRST, LAST, NEW, NEXT

    n = pairs.sum(dtype=jnp.int32)
    at = jnp.nonzero(pairs.reshape(-1), size=capacity, fill_value=0)[0]
    at = jnp.where(jnp.arange(capacity) < n, at, at[n - 1]).astype(jnp.int32)
    major, minor = at // pairs.shape[1], at % pairs.shape[1]
    edge = major[1:] != major[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), edge])
    last = jnp.concatenate([edge, jnp.ones(1, bool)]) | (jnp.arange(capacity) == n - 1)
    new = major == jnp.argmax(pairs, axis=0)[minor]
    done = major == (pairs.shape[0] - 1 - jnp.argmax(pairs[::-1], axis=0))[minor]
    again = jnp.concatenate([minor[1:] == minor[:-1], jnp.zeros(1, bool)])
    again &= jnp.arange(capacity) < n - 1
    flags = FIRST * first + LAST * last + NEW * new + NEXT * again + DONE * done
    return major, minor, flags.astype(jnp.int32), n


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pair_lists(segment_ids, bq, bkv, window):
    """The block pairs the kernels of one packed row `segment_ids` [T]
    walk (`ops/pallas/splash_pairs.PairLists`): of the pairs its static
    mask leaves (`_static_block_pairs`) those that `live_block_pairs`
    finds in the row, each once, q-major for the forward kernel and
    kv-major for the backward, in lists as long as the static mask has pairs
    with `n`, the row's own count, beside them. Every q block's diagonal
    pair is live, so `n` is never under the number of q blocks and every
    output block is written. Jitted: a program's call sites (each kind of
    layer, the forward pass and remat's) share one traced copy."""
    from areal_tpu.ops.pallas.splash_pairs import PairList, PairLists

    t = segment_ids.shape[-1]
    static = _static_block_pairs(t, bq, bkv, window)
    live = live_block_pairs(segment_ids, bq, bkv) & static
    capacity = int(static.sum())
    qi, ki, q_flags, n = _pair_list(live, capacity)
    kj, qj, kv_flags, _ = _pair_list(live.T, capacity)
    return PairLists(PairList(qi, ki, q_flags), PairList(qj, kj, kv_flags), n)


# A row shorter than this (at the length it runs at) keeps the static
# kernel: its kernels take tens of microseconds, and its static kernels
# with the fused backward are 8-25 % faster there (PERF.md section 6,
# PR 35).
_SKIP_MIN_LEN = 2048


def _rows_skip(rows: int, t_run: int) -> bool:
    """Whether the kernels of `rows` packed rows in one call walk the
    rows' own live block pairs (`_pair_lists`: a list of pairs whose
    length is a value of the run, forward and backward each one of the
    repo's own kernels): a long row alone. Several rows keep the static kernels,
    the fused backward among them, and share one grid: a list a row
    needs a loop over the rows,
    which under `vmap` (pallas's own, around the kernel calls alone) is
    slower than the static kernel and under `lax.map` gained 3-4 % of
    `q15d12-train-ppo`'s tokens/s for 19 % of its `setup_s`, each program
    0.35 s longer to trace and lower (PERF.md section 6, PR 29)."""
    return rows == 1 and t_run >= _SKIP_MIN_LEN


def _rows_in_place(skip: bool, hd: int) -> bool:
    """Whether the pair kernels of a row that walks its own pairs
    (`skip`: `_rows_skip`) read q, k and v, and write the output and the
    three gradients, sequence-minor, `[H, hd, T]`: where q's and k's head
    size is more than a lane tile and no whole number of them (192: latent
    attention). XLA's products write such operands with the sequence in
    lanes, and a head-first kernel has them relaid first, a real transpose
    of q, k, v and the output a layer forward, and of `do`, dq, dk and dv
    backward (PERF.md section 6, PR 62); the kernels' step is the same
    products either way (`ops/pallas/splash_pairs.py`). A head of whole
    lane tiles keeps the head-first kernels, and so does one under a
    tile (64: not priced)."""
    return skip and hd > LANES and hd % LANES != 0


def splash_packed_attention(
    q: jnp.ndarray,  # [T, Hq, hd], or packed rows: [R, T, Hq, hd]
    k: jnp.ndarray,  # [T, Hkv, hd]
    v: jnp.ndarray,  # [T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [T] int32, 0 = pad
    positions: jnp.ndarray,  # [T] int32 (unused: causality via stream order)
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    _run_shape: Optional[tuple] = None,
    window: Optional[int] = None,
    _in_place: Optional[bool] = None,
) -> jnp.ndarray:
    """Packed GQA attention on jax's splash kernel (one MQA call per kv
    head, GQA group as the q-head axis). Pad tokens (segment 0) attend
    only among themselves, so outputs there are finite garbage — masked
    by downstream losses exactly like the other impls.

    The kernel runs at `splash_run_shape(T)`: the row is padded with
    zeros in segment 0 up to a length whose blocks are large, and the
    first T positions come back. `_run_shape` overrides that choice
    (tests, scripts/splash_shape_sweep.py), `_in_place` that of
    `_rows_in_place` for a row that walks its own pairs.

    Of the block pairs the row's causal or window mask leaves, the
    kernels of a long row alone (`_rows_skip`) walk those that
    `live_block_pairs` finds in the row's segment ids, and no others:
    not the pairs between two sequences, nor those between a sequence
    and the padding, and their grids are as long as the row's live pairs
    (`_pair_lists`, `ops/pallas/splash_pairs.py`: no step without a
    pair). What comes back at a real position is the same to the bit.

    Packed rows go in whole, every array with a leading axis, so that
    the wrapper sees how many share the call; a row given alone (which
    may be under a caller's `vmap`) keeps the static kernel."""
    del positions
    t, hd = q.shape[-3], q.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    run_shape = _run_shape or splash_run_shape(t)
    skip = q.ndim == 4 and _rows_skip(q.shape[0], run_shape[0])
    one = functools.partial(
        _splash_row, run_shape=run_shape, interpret=bool(interpret),
        window=_row_window(t, window), skip=skip,
        in_place=_rows_in_place(skip, hd) if _in_place is None else skip and _in_place,
        scale=float(softmax_scale) if softmax_scale is not None else hd ** -0.5)
    rows = (q, k, v, segment_ids)
    if q.ndim == 3:
        return one(*rows)
    if q.shape[0] == 1:
        return one(*(a[0] for a in rows))[None]
    return jax.vmap(one)(*rows)


def _splash_row(q, k, v, segment_ids, *, run_shape, interpret, window, skip,
                in_place, scale):
    """One packed row of `splash_packed_attention`, its choices made."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    t, hq, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    t_run, bq, bkv, bkvc = run_shape
    kernel = None if skip else _splash_kernel(
        t_run, bq, bkv, bkvc, group, interpret=interpret, window=window)

    q = q * jnp.asarray(scale, q.dtype)
    if t_run > t:
        # Segment 0 is already the padding segment: real tokens never see
        # the new positions, which attend among themselves.
        pad = ((0, t_run - t), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        segment_ids = jnp.pad(segment_ids, (0, t_run - t))
    if in_place:
        from areal_tpu.ops.pallas.splash_pairs import Blocks, pair_attention

        # [T', H, hd] -> [H, hd, T'], which is how XLA's products leave
        # them: these transposes, and the output's, move nothing
        out = pair_attention(
            *(a.transpose(1, 2, 0) for a in (q, k, v)), segment_ids,
            _pair_lists(segment_ids, bq, bkv, window), Blocks(bq, bkv, bkvc), window,
            SPLASH_RESIDUAL_NAME, interpret, True)
        return out.transpose(2, 0, 1)[:t].astype(q.dtype)
    # [T', H, hd] -> [H, T', hd]; the static kernel takes q as
    # [Hkv, group, T', hd], an MQA problem a kv head
    qh = q.transpose(1, 0, 2)
    if not skip:
        qh = qh.reshape(hkv, group, t_run, hd)
    kh = k.transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)
    if skip:
        from areal_tpu.ops.pallas.splash_pairs import Blocks, pair_attention

        out = pair_attention(
            qh, kh, vh, segment_ids, _pair_lists(segment_ids, bq, bkv, window),
            Blocks(bq, bkv, bkvc), window, SPLASH_RESIDUAL_NAME, interpret)
    else:
        ids = sk.SegmentIds(q=segment_ids, kv=segment_ids)
        out = jax.vmap(lambda qq, kk, vv: kernel(qq, kk, vv, ids))(qh, kh, vh)
    # [Hq, T', hd of v] -> [T, Hq, hd of v]
    out = out.reshape(hq, t_run, v.shape[-1]).transpose(1, 0, 2)
    return out[:t].astype(q.dtype)


def sharded_splash_attention(
    q: jnp.ndarray,  # [R, T, Hq, hd]
    k: jnp.ndarray,  # [R, T, Hkv, hd]
    v: jnp.ndarray,  # [R, T, Hkv, hd]
    segment_ids: jnp.ndarray,  # [R, T]
    positions: jnp.ndarray,  # [R, T]
    mesh,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """splash attention under `shard_map` for GSPMD programs.

    pallas_call is opaque to the SPMD partitioner — inside a sharded jit
    it would replicate or fail (reference's analogue runs flash-attn under
    megatron TP, realhf/impl/model/modules/attn.py:272-289). Here the
    kernel runs per shard with an explicit layout:

    - rows on (data, fsdp) — fully data-parallel,
    - q heads on `tensor` (column-parallel qkv makes them local already),
      kv heads likewise (requires tensor | Hkv),
    - sequence gathered: in_specs leave T unsharded, so jit all-gathers
      seq-sharded activations into each shard before the kernel — the
      same collective GSPMD inserts for the einsum path's [T, T] scores.

    Callers must check `sharded_splash_ok` first.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def local_attn(q, k, v, seg, pos):
        return splash_packed_attention(
            q, k, v, seg, pos, softmax_scale=softmax_scale,
            interpret=interpret, window=window,
        )

    rows = ("data", "fsdp")
    return jax.shard_map(
        local_attn,
        mesh=mesh,
        in_specs=(
            P(rows, None, "tensor", None),
            P(rows, None, "tensor", None),
            P(rows, None, "tensor", None),
            P(rows, None),
            P(rows, None),
        ),
        out_specs=P(rows, None, "tensor", None),
        check_vma=False,
    )(q, k, v, segment_ids, positions)


def cp_axes(mesh) -> tuple:
    """(rows, seq, tensor) sizes of the canonical activation mesh axes —
    the shared prologue of every sharded-attention shape checker."""
    names = mesh.shape
    rows = names.get("data", 1) * names.get("fsdp", 1)
    return rows, names.get("seq", 1), names.get("tensor", 1)


def sharded_splash_ok(mesh, r: int, t: int, hq: int, hkv: int) -> bool:
    """Shapes/mesh divisibility for sharded_splash_attention."""
    rows, _, tensor = cp_axes(mesh)
    return (
        t >= 128
        and t % 128 == 0
        and r % rows == 0
        and hq % tensor == 0
        and hkv % tensor == 0
        and (hq // tensor) % (hkv // tensor) == 0
    )


def resolve_cp_impl(mesh, r: int, t: int, hq: int, hkv: int) -> Optional[str]:
    """Default context-parallel scheme for an 'auto' impl on a seq>1
    mesh (trace-time static decision).

    Policy (analytic default, not measured on the chip's interconnect —
    see docs/perf_notes.md "ring vs Ulysses"):
    prefer Ulysses when the head counts divide the seq axis — its
    per-layer communication is 4 all-to-alls + 2 small gathers
    regardless of the seq size, each moving 1/seq of the activations,
    while ring pays seq pipelined ppermute steps whose overlap with the
    per-chunk kernel is hard to sustain at small chunk sizes. Fall back
    to ring when heads don't divide (GQA with few KV heads on a wide
    seq axis) — ring only needs t % seq == 0. Returns None when neither
    scheme fits (caller keeps its non-CP path)."""
    from areal_tpu.ops.ring_attention import ring_ok
    from areal_tpu.ops.ulysses_attention import ulysses_ok

    if ulysses_ok(mesh, r, t, hq, hkv):
        return "ulysses"
    if ring_ok(mesh, r, t, hq, hkv):
        return "ring"
    return None


def _choose_attn_impl(impl, t, hq, hkv, mesh, r):
    """(implementation that will run, why)."""
    known = ("auto", "splash", "reference", "ring", "ulysses")
    if impl not in known:
        raise ValueError(f"attn_impl={impl!r}: expected one of {', '.join(known)}")
    sharded = mesh is not None and mesh.size > 1
    why = "requested"
    if impl == "auto":
        if sharded and r is not None and mesh.shape.get("seq", 1) > 1:
            cp = resolve_cp_impl(mesh, r, t, hq, hkv)
            if cp is not None:
                return cp, "context parallel over the mesh's seq axis"
        if jax.default_backend() != "tpu":
            return "reference", f"backend is {jax.default_backend()}, not tpu"
        if not (t >= LANES and t % LANES == 0 and hq % hkv == 0):
            return "reference", (
                f"splash needs rows a multiple of {LANES} and hkv | hq"
            )
        impl, why = "splash", f"tpu backend, rows a multiple of {LANES}"
    if sharded and impl == "splash" and (
            r is None or not sharded_splash_ok(mesh, r, t, hq, hkv)):
        # Never run a bare pallas_call inside a sharded jit — GSPMD
        # cannot partition it (it replicates or fails). Where splash's
        # shard_map wrapping does not fit the mesh, the einsum reference
        # runs, which partitions cleanly.
        return "reference", f"splash has no shard_map layout for mesh {dict(mesh.shape)}"
    return impl, why


def resolve_attn_impl(
    impl: str, t: int, hq: int, hkv: int, mesh=None, r: Optional[int] = None,
) -> str:
    """The attention implementation that runs for the given shape
    (trace-time static decision). 'auto' picks a context-parallel scheme
    first on a seq>1 mesh (and r given; resolve_cp_impl), otherwise
    splash on a TPU backend when shapes allow, else the einsum
    reference. On a sharded mesh a Pallas kernel without a shard_map
    layout (explicit or chosen) becomes the reference. Every decision is
    said once, and one that lands on the reference ON a TPU is a
    warning (utils/jaxenv.say_dispatch)."""
    from areal_tpu.utils.jaxenv import say_dispatch

    ran, why = _choose_attn_impl(impl, t, hq, hkv, mesh, r)
    say_dispatch("attn_impl", impl, ran, why, rows=r, t=t, hq=hq, hkv=hkv)
    return ran


def attn_run_len(
    impl: str, t: int, hq: int, hkv: int, mesh=None, r: Optional[int] = None
) -> int:
    """Length the attention kernel runs rows of `t` at: splash's padded
    `t'` (splash_run_shape) where splash is what runs, else `t`. For
    host-side counters; says nothing (resolve_attn_impl does, in the
    trace)."""
    ran, _ = _choose_attn_impl(impl, t, hq, hkv, mesh, r)
    return splash_run_shape(t)[0] if ran == "splash" else t


def _host_rows_skip(r: int, t_run: int, mesh) -> bool:
    """`_rows_skip` of the `r` rows of one micro-batch as the host sees
    them: a sharded mesh runs each shard's rows in a call of their own."""
    return _rows_skip(r // (cp_axes(mesh)[0] if mesh is not None else 1), t_run)


def _host_block_pairs(impl, segment_ids, hq, hkv, mesh, window):
    """What the host's counters count of the packed rows `segment_ids`
    [R, T] of one micro-batch: None where an implementation without
    blocks runs them, else (run shape, static pairs [nq, nkv], and
    where the kernels walk the rows' own pairs (`_rows_skip`) those
    pairs, [R, nq, nkv])."""
    r, t = segment_ids.shape
    ran, _ = _choose_attn_impl(impl, t, hq, hkv, mesh, r)
    if ran != "splash":
        return None
    shape = splash_run_shape(t)
    t_run, bq, bkv, _ = shape
    static = _static_block_pairs(t_run, bq, bkv, _row_window(t, window))
    if not _host_rows_skip(r, t_run, mesh):
        return shape, static, None
    live = live_block_pairs(
        np.pad(segment_ids, ((0, 0), (0, t_run - t))), bq, bkv)
    return shape, static, live & static


def attn_in_place(
    impl: str, r: int, t: int, hq: int, hkv: int, hd: int, mesh=None,
) -> bool:
    """Whether the attention kernels of `r` packed rows of `t` in one
    micro-batch, q and k at a head size of `hd`, read their operands
    where the projections left them (`_rows_in_place`: the pair kernels
    of a long row alone, sequence-minor). For host-side counters."""
    ran, _ = _choose_attn_impl(impl, t, hq, hkv, mesh, r)
    return _rows_in_place(
        ran == "splash" and _host_rows_skip(r, splash_run_shape(t)[0], mesh), hd)


def attn_block_cells(
    impl: str, segment_ids: np.ndarray, hq: int, hkv: int, mesh=None,
    window: Optional[int] = None,
) -> tuple:
    """(cells the attention kernels run for the packed rows
    `segment_ids` [R, T] of one micro-batch, cells they would run under
    the causal mask alone), per q head: those of the block pairs splash
    runs at the shape it runs the rows at, which are the pairs its
    static mask leaves AND, where the kernels skip by the rows' own ids
    (`_rows_skip`), `live_block_pairs` of each row; an implementation
    without blocks (the einsum reference) runs all t x t cells whatever
    the mask. For host-side counters."""
    r, t = segment_ids.shape
    found = _host_block_pairs(impl, segment_ids, hq, hkv, mesh, window)
    if found is None:
        return r * t * t, r * t * t
    (t_run, bq, bkv, _), static, live = found
    causal = r * int(_static_block_pairs(t_run, bq, bkv).sum())
    pairs = r * int(static.sum()) if live is None else int(live.sum())
    return pairs * bq * bkv, causal * bq * bkv


def attn_grid_steps(
    impl: str, segment_ids: np.ndarray, hq: int, hkv: int, mesh=None,
    window: Optional[int] = None,
) -> tuple:
    """(grid steps the attention kernels walk for the packed rows
    `segment_ids` [R, T] of one micro-batch, those whose block pair
    runs, the widest forward grid: kv steps a q block, and the steps of
    the two that the backward walks), per q head, the forward kernel
    once. A row alone (`_rows_skip`) walks its list of live pairs
    (`_pair_lists`: the device's rule) once in the forward kernel and
    once in the one backward kernel, and no step besides; its widest
    grid is its fullest q block's pairs. Other rows walk the static
    grids: nq x the mask's widest row forward, and the fused backward's
    whole nq x nkv. An implementation without blocks has no grid: zeros.
    For host-side counters."""
    found = _host_block_pairs(impl, segment_ids, hq, hkv, mesh, window)
    if found is None:
        return 0, 0, 0, 0
    _, static, live = found
    if live is None:
        nq, nkv = static.shape
        r, widest = len(segment_ids), int(static.sum(axis=1).max())
        return (r * (nq * widest + nq * nkv), r * 2 * int(static.sum()), widest,
                r * nq * nkv)
    n = int(live.sum())
    return 2 * n, 2 * n, int(live.sum(axis=-1).max()), n


def packed_attention(q, k, v, segment_ids, positions, softmax_scale=None,
                     impl="auto", window=None):
    """Dispatch between implementations. Static decision (trace-time): `impl`
    is 'reference', 'splash' (jax's tuned TPU kernel), or 'auto' (see
    resolve_attn_impl). `window` limits a token to the `window` positions
    that end at its own."""
    impl = resolve_attn_impl(impl, q.shape[0], q.shape[1], k.shape[1])
    if impl == "splash":
        return splash_packed_attention(
            q, k, v, segment_ids, positions, softmax_scale=softmax_scale,
            window=window,
        )
    return reference_packed_attention(
        q, k, v, segment_ids, positions, softmax_scale=softmax_scale,
        window=window,
    )
