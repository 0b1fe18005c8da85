"""Token-level loss/logprob primitives over packed rows.

Replaces the reference's vocab-parallel cross entropy and packed logprob
gathering (realhf/impl/model/parallelism/tensor_parallel/modules.py:1180,
realhf/impl/model/utils/functional.py): under GSPMD the vocab dimension is
just a sharded axis, so a plain log_softmax + gather compiles to the same
collectives the hand-written vocab-parallel CE performs.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.base import env_registry


def gather_logprobs(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """log P(labels) under logits along the last axis. fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return picked - lse


def next_token_logprobs(
    logits: jnp.ndarray,  # [R, T, V] fp32
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T], 0 = pad
) -> jnp.ndarray:
    """logprob[t] = log P(token[t+1] | prefix) when t+1 continues the same
    segment; 0 elsewhere (sequence-final tokens, padding). Shape [R, T].

    Matches the reference convention where packed logprobs are shifted so
    position t scores the token emitted *at* t+1.
    """
    next_ids, valid = _next_token_targets(input_ids, segment_ids)
    logp = gather_logprobs(logits, next_ids)
    return jnp.where(valid, logp, 0.0)


def next_token_entropy(
    logits: jnp.ndarray, segment_ids: jnp.ndarray
) -> jnp.ndarray:
    """Per-position predictive entropy, masked like next_token_logprobs."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return jnp.where(segment_ids > 0, ent, 0.0)


def _next_token_targets(input_ids, segment_ids, shift: int = 1):
    """(next_ids, valid) in the shifted frame shared by all logprob ops;
    on device rows, or on numpy rows for the host's counts. `shift`:
    how many tokens on the target lies, inside the position's own
    sequence (a prediction module's is 2)."""
    xp = np if isinstance(segment_ids, np.ndarray) else jnp
    next_ids = xp.concatenate(
        [input_ids[:, shift:], xp.zeros_like(input_ids[:, :shift])], axis=1
    )
    next_seg = xp.concatenate(
        [segment_ids[:, shift:], xp.zeros_like(segment_ids[:, :shift])], axis=1
    )
    valid = (segment_ids > 0) & (next_seg == segment_ids)
    return next_ids, valid


def _pick_chunk(n_tokens: int, target: int = 4096) -> int:
    """Largest divisor of n_tokens that is <= target (>=1)."""
    c = min(target, n_tokens)
    while n_tokens % c:
        c -= 1
    return c


# AREAL_CE_CHUNK snapshot: (value,) once taken, None before. The tuple
# wrapper distinguishes "snapshotted as unset" from "never snapshotted".
_CE_CHUNK_SNAP: Optional[Tuple[Optional[int]]] = None


def snapshot_ce_chunk() -> Optional[int]:
    """Parse + validate AREAL_CE_CHUNK and pin it for subsequent traces.

    Called at engine construction (engine/jax_engine.py): a mid-run
    retrace then reuses the pinned value instead of silently picking up
    a mutated environment, and an unparseable value fails HERE — at
    init — rather than deep inside a jit trace. A fresh engine re-pins."""
    global _CE_CHUNK_SNAP
    # ValueError (unparseable value) surfaces at snapshot time.
    val: Optional[int] = env_registry.get_int("AREAL_CE_CHUNK")
    if val is not None and val <= 0:
        raise ValueError(f"AREAL_CE_CHUNK={val}: must be positive")
    _CE_CHUNK_SNAP = (val,)
    return val


def _ce_chunk_setting() -> Optional[int]:
    if _CE_CHUNK_SNAP is None:
        # Direct ops use without an engine: snapshot lazily on first use.
        return snapshot_ce_chunk()
    return _CE_CHUNK_SNAP[0]


def _chunk_logprobs(h_c, y_c, head_w, top: bool = False):
    """[C] fp32: log P(y_c) from one [C, V] logits tile; with `top` a
    pair, the second 1.0 where y_c is the tile's largest logit."""
    logits = (h_c @ head_w.astype(h_c.dtype)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
    if top:
        return picked - lse, (picked >= jnp.max(logits, axis=-1)).astype(jnp.float32)
    return picked - lse


def head_chunk_len(n_cells: int, vocab: int,
                   chunk_size: Optional[int] = None) -> int:
    """Cells the head runs through one logits tile, of `n_cells` in a
    micro-batch: the AREAL_CE_CHUNK override (validated + pinned at
    engine construction, snapshot_ce_chunk, so retraces can't mix
    settings mid-run), else byte-budgeted — the per-chunk fp32 tile
    stays ~512 MB whatever the vocabulary (C*V elements), floor 256 —
    then the largest divisor of `n_cells` not above it."""
    if chunk_size is None:
        chunk_size = _ce_chunk_setting()
        if chunk_size is None:
            chunk_size = max(256, (1 << 27) // vocab)
    return _pick_chunk(n_cells, chunk_size)


def response_scoring_mask(segment_ids, prompt_mask):
    """[..., T] 1.0 where position t scores a response token (t+1): the
    positions the PPO and SFT losses read. On numpy rows (the engine's
    host-side counts) or on device rows (inside the step)."""
    xp = np if isinstance(segment_ids, np.ndarray) else jnp
    seg, pm = segment_ids, prompt_mask
    next_seg = xp.concatenate(
        [seg[..., 1:], xp.zeros_like(seg[..., :1])], axis=-1)
    next_pm = xp.concatenate(
        [pm[..., 1:], xp.ones_like(pm[..., :1])], axis=-1)
    return ((next_seg == seg) & (seg > 0) & (next_pm == 0)).astype(xp.float32)


def response_positions(rows):
    """`train_batch`'s `scored_fn` of the losses that weigh every
    position by `response_scoring_mask`."""
    return response_scoring_mask(rows["segment_ids"], rows["prompt_mask"])


def two_on(scored):
    """What a prediction module's loss reads, of a next-token loss's
    `scored` [..., T] (nonzero at t = the loss reads token t + 1): nonzero
    at t where it reads token t + 2, the token that position t + 1
    scores. `_next_token_targets(shift=2)` keeps those whose t + 2 lies
    in t's own sequence. On numpy or device rows."""
    xp = np if isinstance(scored, np.ndarray) else jnp
    return xp.concatenate([scored[..., 1:], xp.zeros_like(scored[..., :1])], axis=-1)


def _kept_first(keep):
    """[G, m] int32: slot j of a group names its j-th kept entry (of
    `keep` [G, m] bool; slots past the kept count name the last entry),
    ascending. Slot j's entry has before it every entry whose running
    count of kept ones is j or less: a histogram of the running count
    (a scatter-add that is told its indices are sorted) and its running
    sum. No sort, and few operations to trace: a train step is traced
    for every micro-batch shape."""
    g, m = keep.shape
    csum = jax.lax.cumsum(keep.astype(jnp.int32), axis=1)
    hist = jax.lax.scatter_add(
        jnp.zeros((g, m + 1), jnp.int32), csum[..., None],
        jnp.ones((g, m), jnp.int32),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(1,),
            scatter_dims_to_operand_dims=(1,), operand_batching_dims=(0,),
            scatter_indices_batching_dims=(0,)),
        indices_are_sorted=True, mode="promise_in_bounds")
    return jnp.minimum(jax.lax.cumsum(hist[:, :m], axis=1), m - 1), csum


def _take(x, idx, mask, sharding=None):
    """x[g, idx[g, j]] where mask[g, j], zero elsewhere, for `x`
    [G, m] or [G, m, D]; `idx` ascends within a group and the gather is
    told so, and the result stays where `sharding` puts its groups."""
    wide = x.ndim == 3
    got = jax.lax.gather(
        x, idx[..., None],
        jax.lax.GatherDimensionNumbers(
            offset_dims=(2,) if wide else (), collapsed_slice_dims=(1,),
            start_index_map=(1,), operand_batching_dims=(0,),
            start_indices_batching_dims=(0,)),
        slice_sizes=(1, 1) + x.shape[2:], indices_are_sorted=True,
        mode="promise_in_bounds")
    out = jnp.where(mask[..., None] if wide else mask, got, 0)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out


def _scored_layout(keep, c: int):
    """How the head lays out the positions `keep` [G, m] leaves it, a
    group of rows at a time (one group on one chip; a shard's rows
    where a mesh's data axes split them, so that no hidden state
    crosses a shard on its way): (`src`, the position each slot of a
    group takes — its kept positions first, in order; `rank`, the slot
    a kept position goes to; `live`, the slots that hold one; the ids
    of the chunks of `c` slots that hold one, ascending; their count)."""
    src, csum = _kept_first(keep)
    live = jnp.arange(keep.shape[1], dtype=jnp.int32)[None] < csum[:, -1:]
    runs = jnp.any(live.reshape(-1, c), axis=1)
    chunk_ids, n_run = _kept_first(runs[None])
    return src, jnp.maximum(csum - 1, 0), live, chunk_ids[0], n_run[0, -1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scored_logprobs(hidden, next_ids, head_w, keep, c: int, mesh, top: bool = False):
    """[G, m] fp32: log P(next_ids) at the positions `keep` [G, m], zero
    at the others, from `hidden` [G, m, D] through chunks of `c`
    positions: the kept positions' rows move to the front of their
    group and only chunks that hold one run.

    Both passes are loops whose trip count is the run-time count of
    such chunks; the backward one recomputes each tile it needs (as
    `jax.checkpoint` on the chunk body does where every chunk runs) and
    adds each chunk's product into the head's gradient in place. A
    chunk that does not run costs nothing, where a `lax.cond` inside a
    scan of static length hands the scan's backward a [D, V] block of
    zeros to add for every chunk it skips and takes the fusion of that
    add from the chunks that run (the head alone on a v5e: PERF.md
    section 6, PR 31). Moving the rows there and back is a gather each
    way in either pass, each the other's transpose, so neither leaves
    the compiler a scatter whose indices it would sort (models/moe.py).
    With `top` a pair: beside the logprobs, 1.0 at the kept positions
    whose label is the largest logit (nothing flows back through it)."""
    return _scored_logprobs_fwd(hidden, next_ids, head_w, keep, c, mesh, top)[0]


def _group_shardings(mesh):
    """(groups over the data axes, everything everywhere) on `mesh`."""
    if mesh is None:
        return None, None
    spec = jax.sharding.PartitionSpec
    return (jax.sharding.NamedSharding(mesh, spec(("data", "fsdp"))),
            jax.sharding.NamedSharding(mesh, spec()))


def _scored_logprobs_fwd(hidden, next_ids, head_w, keep, c, mesh, top=False):
    g, m, d = hidden.shape
    by_group, everywhere = _group_shardings(mesh)
    src, rank, live, chunk_ids, n_run = _scored_layout(keep, c)
    # Every device runs every chunk, as the unmasked scan does on a
    # mesh: say so, or the partitioner finds it out late.
    h = _take(hidden, src, live, by_group).reshape(-1, c, d)
    if mesh is not None:
        h = jax.lax.with_sharding_constraint(h, everywhere)
    y = _take(next_ids, src, live).reshape(-1, c)

    def body(k, out):
        i = chunk_ids[k]
        return jax.tree_util.tree_map(
            lambda o, new: o.at[i].set(new), out,
            _chunk_logprobs(h[i], y[i], head_w, top))

    zeros = jnp.zeros(y.shape, jnp.float32)
    out = jax.lax.fori_loop(0, n_run, body, (zeros, zeros) if top else zeros)
    out = jax.tree_util.tree_map(
        lambda a: _take(a.reshape(g, m), rank, keep, by_group), out)
    return out, (h, y, head_w, src, rank, live, keep, chunk_ids, n_run)


def _scored_logprobs_bwd(c, mesh, top, res, d_out):
    h, y, head_w, src, rank, live, keep, chunk_ids, n_run = res
    by_group, _ = _group_shardings(mesh)
    d_logp = _take(d_out[0] if top else d_out, src, live).reshape(-1, c)

    def body(k, grads):
        dh, dw = grads
        i = chunk_ids[k]
        _, vjp = jax.vjp(
            lambda h_c, w: _chunk_logprobs(h_c, y[i], w), h[i], head_w)
        dh_c, dw_c = vjp(d_logp[i])
        return dh.at[i].set(dh_c), dw + dw_c

    dh, dw = jax.lax.fori_loop(
        0, n_run, body, (jnp.zeros_like(h), jnp.zeros_like(head_w)))
    dh = _take(dh.reshape(keep.shape + h.shape[-1:]), rank, keep, by_group)
    return dh, None, dw, None


_scored_logprobs.defvjp(_scored_logprobs_fwd, _scored_logprobs_bwd)


def head_cells_run(segment_ids: np.ndarray, scored: Optional[np.ndarray],
                   vocab: int, row_groups: int = 1, shift: int = 1) -> Tuple[int, int]:
    """(positions whose logprob is read, cells the head runs its logits
    tile over) for one micro-batch's packed rows [R, T], counted on the
    host by the rule `fused_next_token_logprobs` runs by on the device.
    `scored` None: every valid position is read and every chunk runs."""
    seg = np.asarray(segment_ids)
    _, keep = _next_token_targets(seg, seg, shift)
    if scored is None:
        return int(keep.sum()), seg.size
    keep = (keep & (np.asarray(scored) > 0)).reshape(row_groups, -1)
    c = head_chunk_len(seg.size, vocab)
    live = np.arange(keep.shape[1])[None] < keep.sum(axis=1)[:, None]
    return int(keep.sum()), int(live.reshape(-1, c).any(axis=1).sum()) * c


@jax.named_scope("xent")
def fused_next_token_logprobs(
    hidden: jnp.ndarray,  # [R, T, D] compute dtype
    head_w: jnp.ndarray,  # [D, V]
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T]
    chunk_size: Optional[int] = None,
    scored: Optional[jnp.ndarray] = None,  # [R, T], nonzero = read
    mesh=None,
    shift: int = 1,
    top: bool = False,
) -> jnp.ndarray:
    """next_token_logprobs computed straight from hidden states without
    ever materializing the [R, T, V] logits tensor.

    The token axis is flattened and scanned in chunks; each chunk computes
    its [C, V] logits tile, reduces to (picked - logsumexp), and discards
    the tile. `jax.checkpoint` on the chunk body makes the backward pass
    recompute the tile instead of storing softmax residuals, so peak
    memory is O(C * V) rather than O(R * T * V) in both directions —
    the TPU-shaped equivalent of the reference's vocab-parallel fused
    cross entropy (realhf/impl/model/parallelism/tensor_parallel/
    modules.py:1180), which shards V to avoid the same materialization.

    `scored` is the caller's word on which positions' logprobs it reads
    (the engine hands on what `train_batch`'s `scored_fn` says of the
    rows; the PPO and SFT losses name `response_scoring_mask`). Given,
    the head runs over those positions alone: the valid scored
    positions' hidden rows and labels move to the front of the
    flattened axis (on a `mesh` whose data axes split the rows, of each
    shard's rows), the same chunks of the same length are laid over it,
    and only chunks that hold such a position run — a count known at
    run time, so the program's shapes are those of the unmasked call.
    Every other position reads 0, as invalid slots do. Absent, every
    valid position is computed: the forward-only path and any caller
    that says nothing.

    `shift` is how many tokens on the label lies (1: the next token; a
    prediction module's hidden states are read with 2), always inside
    the position's own sequence of a packed row. `top` (with `scored`)
    makes the result a pair: the logprobs, and 1.0 where the label is
    the head's argmax.

    Returns [R, T] fp32, zeros at invalid (sequence-final / pad) slots.
    """
    R, T, D = hidden.shape
    next_ids, valid = _next_token_targets(input_ids, segment_ids, shift)
    n = R * T
    c = head_chunk_len(n, head_w.shape[-1], chunk_size)
    if scored is not None:
        # one group of rows a shard of the mesh's data axes
        g = 1 if mesh is None else mesh.shape["data"] * mesh.shape["fsdp"]
        out = _scored_logprobs(
            hidden.reshape(g, n // g, D), next_ids.reshape(g, n // g), head_w,
            (valid & (scored > 0)).reshape(g, n // g), c, mesh, top)
        return jax.tree_util.tree_map(lambda a: a.reshape(R, T), out)
    if top:
        raise NotImplementedError("top is the masked head's: pass `scored`")
    flat_h = hidden.reshape(n // c, c, D)
    flat_y = next_ids.reshape(n // c, c)

    def chunk(carry, hy):
        return carry, _chunk_logprobs(*hy, head_w)

    _, logp = jax.lax.scan(jax.checkpoint(chunk), None, (flat_h, flat_y))
    return jnp.where(valid, logp.reshape(R, T), 0.0)


def sft_loss_from_logprobs(
    logp: jnp.ndarray,  # [R, T] next-token logprobs (zeros at invalid)
    loss_mask: jnp.ndarray,  # [R, T]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked next-token NLL from precomputed logprobs."""
    mask = loss_mask.astype(jnp.float32)
    return -jnp.sum(logp * mask), jnp.sum(mask)


def sft_loss(
    logits: jnp.ndarray,  # [R, T, V]
    input_ids: jnp.ndarray,  # [R, T]
    segment_ids: jnp.ndarray,  # [R, T]
    loss_mask: jnp.ndarray,  # [R, T] 1.0 where the *target* token (t+1) counts
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token cross entropy over masked positions.

    loss_mask is given per-position in the shifted frame: mask[t] = 1 means
    the prediction made at t (of token t+1) contributes. Returns
    (sum_loss, n_tokens); callers normalize globally so DP shards with
    different token counts average correctly.
    """
    logp = next_token_logprobs(logits, input_ids, segment_ids)
    mask = loss_mask.astype(jnp.float32)
    return -jnp.sum(logp * mask), jnp.sum(mask)


def masked_normalization(
    x: jnp.ndarray,
    mask: jnp.ndarray,
    eps: float = 1e-5,
    unbiased: bool = True,
) -> jnp.ndarray:
    """Whiten x over masked elements (advantage normalization).

    Under pjit the batch is global, so the mean/std are global without any
    explicit collective (reference: realhf/impl/model/utils/functional.py
    masked_normalization with its dist.all_reduce).
    """
    mask = mask.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    mean = jnp.sum(x32 * mask) / n
    var = jnp.sum(((x32 - mean) ** 2) * mask) / jnp.maximum(
        n - (1.0 if unbiased else 0.0), 1.0
    )
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return jnp.where(mask > 0, out, 0.0).astype(x.dtype)
