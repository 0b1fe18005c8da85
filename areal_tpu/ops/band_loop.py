"""A token-wise stretch of a layer over the bands of a row that hold a token.

A packed row's tokens are a prefix (`models/packing.pack_sequences` fills
a row from cell 0 and leaves its padding at the tail), and what a layer
does to every cell alone (a norm, a projection, an MLP, a router, a
residual: row `i` of the result depends on row `i` of the inputs and on
the weights) does the same work for a padding cell as for a token.
`stretch` runs such a function over *bands* of `_BAND` consecutive
cells, the first `live_bands` of them and no others: one `fori_loop`
whose trip count is a value of the run, as the loss head
(`ops/loss._scored_logprobs`), the held experts (`models/moe._run_tiles`)
and attention (`ops/pallas/splash_pairs.py`) already walk what they run.
A band past them costs nothing and reads zero on the way out: what
crosses tokens afterwards (a scan, a convolution's taps) finds zeros
there, not what memory held.

The backward pass is a second loop of the same count, written by hand
(`jax.custom_vjp`). The forward rule keeps the stretch's inputs and
nothing of a band; the backward loop makes a band's `jax.vjp` again from
its cells, pulls the band's cotangents back and adds the band's weight
gradients into float32 sums (the compiler fuses the add into the product
that makes them), cast once where the loop ends. (A forward rule that kept
each band's products a band a slot, for the backward loop to start from,
bought 0.25-0.46 % of `train_tokens_per_s` in the three cells that loop,
every one of them under `remat` full, for a hundred lines that walked the
band's jaxpr: PERF.md section 6, PR 45, after review.)

`carried` is the same pair of loops for a function that is token-wise but
for what one band hands the next (a state-space layer: the state, the
convolution's last cells): the carry is threaded through the forward loop,
each band's carry-in kept a band a slot, and the backward loop walks the
bands last to first, handing the carry's cotangent backwards. It has its
own bodies: `stretch`'s traced program is what eight cells' tests hold.

One function, jitted at module level with the stretch's function and
its static description as static arguments: the second layer of a kind,
and the second and third program of a process (a train step is traced
for the first micro-batch, the next, and a forward pass beside them),
find the stretch traced, differentiated and transposed in jax's own
caches.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.base import datapack

# Cells a band. A shorter band runs fewer empty cells in the last band a
# row's tokens reach (8.6k tokens of 16,384 are 9 bands of 1,024 = 9.2k
# cells, 5 of 2,048 = 10.2k) and reads the stretch's weights, and adds
# into their float32 gradient sums, once more a band. Measured on the
# chip (`scripts/band_loop_probe.py`; PERF.md section 6, PR 45), one layer
# at 8,600 tokens of 16,384, forward + backward ms at bands of 512 / 1,024
# / 2,048: trinity's expert layer 60.6 / 61.6 / 64.4, its dense layer 58.1
# / 55.9 / 59.2; Qwen's layer 30.7 at 1,024 against 32.6 at 2,048; a full
# row reads the same at all three. One constant for every stack.
_BAND = 1024


def dead_bands(row_len: int, row_len_multiple: int) -> bool:
    """Whether a row of `row_len` cells, packed at a ladder of
    `row_len_multiple` (`base/datapack.ladder_shape`), may hold a band no
    token is in (what `forward(bands=)` is told, and what the host counts
    by): the ladder's step up to that rung is longer than a band. At the
    launcher's `row_len_multiple` of 128 a row of 16,384 pads under 1,024
    cells and every band of it is live; at a multiple that is the row,
    half of it may be empty."""
    return datapack.ladder_step(row_len, row_len_multiple) > _BAND


def loops(n_rows: int, row_len: int) -> bool:
    """Whether a call of `n_rows` rows of `row_len` cells walks its live
    bands: one row (several rows' tokens are no prefix of the call's
    cells) of two bands or more, as `ops/attention._rows_skip` says for
    the pair kernels."""
    return n_rows == 1 and row_len >= 2 * _BAND and row_len % _BAND == 0


def live_bands(segment_ids) -> jnp.ndarray:
    """Bands of the one row `segment_ids` `[1, T]` up to its last token
    (segment id > 0), int32: `ceil(tokens / _BAND)` for a packed row."""
    T = segment_ids.shape[-1]
    last = jnp.max(jnp.where(segment_ids.reshape(-1) > 0,
                             jnp.arange(1, T + 1, dtype=jnp.int32), 0))
    return (last + _BAND - 1) // _BAND


def band_cells_run(segment_ids: np.ndarray) -> int:
    """Cells the stretches of one micro-batch `[R, T]` run, on the host by
    the device's rule: live bands x `_BAND` where the call loops, every
    cell where it runs whole."""
    seg = np.asarray(segment_ids)
    if seg.ndim != 2 or not loops(*seg.shape):
        return int(seg.size)
    (live,) = np.nonzero(seg[0] > 0)
    return int(-(-(live[-1] + 1) // _BAND) * _BAND) if live.size else 0


def _axes(arrays, minor=()):
    """The axis each array's bands lie along: 1 of a `[1, T, ...]` array,
    the last of one of `minor` (indices into `arrays`), `[1, ..., T]`."""
    return tuple(a.ndim - 1 if j in minor else 1 for j, a in enumerate(arrays))


def _cut(arrays, i, minor=()):
    """Band i of each `[1, T, ...]` array (of `minor`'s, `[1, ..., T]`)."""
    return tuple(jax.lax.dynamic_slice_in_dim(a, i * _BAND, _BAND, axis=axis)
                 for a, axis in zip(arrays, _axes(arrays, minor)))


def _put(bufs, bands, i, minor=()):
    """Each `[1, T, ...]` buffer (of `minor`'s, `[1, ..., T]`) with its band
    written as band i."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(b, a, i * _BAND, axis=axis)
                 for b, a, axis in zip(bufs, bands, _axes(bufs, minor)))


def _row_zeros(avals, T):
    """`[1, T, ...]` zeros for bands of `avals`: what a band that does not
    run reads. (Zeros written into the dead bands alone, band by band
    after the loop, cost the chip as much as zeros over the whole row and
    a second loop a stretch to trace, lower and compile.)"""
    return tuple(jnp.zeros((1, T) + a.shape[2:], a.dtype) for a in avals)


def _cotangents(avals, ds):
    """A cotangent a leaf of `avals`: the next of `ds` for a floating leaf,
    float0 zeros for an integer one (no one's to hand in)."""
    ds = iter(ds)
    return jax.tree_util.tree_map(
        lambda a: next(ds) if jnp.issubdtype(a.dtype, jnp.inexact)
        else np.zeros(a.shape, jax.dtypes.float0), avals)


def _floating(tree):
    """The floating leaves of `tree`, in order."""
    return [a for a in jax.tree_util.tree_leaves(tree) if jnp.issubdtype(a.dtype, jnp.inexact)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _stretch(fn, static, minor, weights, xs, side, n_live):
    band = lambda i: tuple(fn(static, weights, _cut(xs, i, minor), _cut(side, i)))
    return jax.lax.fori_loop(
        0, n_live, lambda i, outs: _put(outs, band(i), i),
        _row_zeros(jax.eval_shape(band, 0), xs[0].shape[_axes(xs, minor)[0]]))


def _stretch_fwd(fn, static, minor, weights, xs, side, n_live):
    return (_stretch(fn, static, minor, weights, xs, side, n_live),
            (weights, xs, side, n_live))


def _stretch_bwd(fn, static, minor, res, d_outs):
    weights, xs, side, n_live = res
    # an integer output's cotangent is float0: no one's to hand in
    d_outs = tuple(d for d in d_outs if d.dtype != jax.dtypes.float0)

    def body(i, carry):
        dws, dxs = carry
        s = _cut(side, i)
        outs, vjp = jax.vjp(lambda w, *x: tuple(fn(static, w, x, s)), weights,
                            *_cut(xs, i, minor))
        dw, *dx = vjp(_cotangents(outs, _cut(d_outs, i)))
        dws = jax.tree_util.tree_map(lambda a, g: a + g.astype(a.dtype), dws, dw)
        return dws, _put(dxs, dx, i, minor)

    dws, dxs = jax.lax.fori_loop(0, n_live, body, (
        jax.tree_util.tree_map(lambda w: jnp.zeros(w.shape, jnp.float32), weights),
        tuple(jnp.zeros_like(x) for x in xs)))
    # Cast here and now, before anything reads the cells' cotangents: left
    # to the compiler the cast joins whatever reads the gradient last (a
    # layer that runs outside a scan hands it to the step's end), and a
    # stretch's float32 sums stay until then.
    dws, dxs = jax.lax.optimization_barrier(
        (jax.tree_util.tree_map(lambda a, w: a.astype(w.dtype), dws, weights), dxs))
    return dws, dxs, None, None


_stretch.defvjp(_stretch_fwd, _stretch_bwd)

_stretch_jit = jax.jit(_stretch, static_argnums=(0, 1, 2))


def _carried_loop(fn, static, weights, xs, side, carry, n_live, keep: bool):
    """The forward loop of a carried stretch: (the results `[1, T, ...]`,
    each band's carry-in a band a slot where `keep`, else None)."""
    band = lambda i, c: fn(static, weights, _cut(xs, i), _cut(side, i), c)
    T = xs[0].shape[1]
    kept = jax.tree_util.tree_map(
        lambda c: jnp.zeros((T // _BAND,) + c.shape, c.dtype), carry) if keep else None

    def body(i, state):
        outs, c, kept = state
        if keep:
            kept = jax.tree_util.tree_map(
                lambda k, a: jax.lax.dynamic_update_index_in_dim(k, a, i, 0), kept, c)
        band_outs, c = band(i, c)
        return _put(outs, tuple(band_outs), i), c, kept

    outs, _, kept = jax.lax.fori_loop(0, n_live, body, (
        _row_zeros(jax.eval_shape(lambda: tuple(band(0, carry)[0])), T), carry, kept))
    return outs, kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _carried(fn, static, weights, xs, side, carry, n_live):
    return _carried_loop(fn, static, weights, xs, side, carry, n_live, False)[0]


def _carried_fwd(fn, static, weights, xs, side, carry, n_live):
    outs, kept = _carried_loop(fn, static, weights, xs, side, carry, n_live, True)
    return outs, (weights, xs, side, kept, n_live)


def _carried_bwd(fn, static, res, d_outs):
    weights, xs, side, kept, n_live = res
    d_outs = tuple(d for d in d_outs if d.dtype != jax.dtypes.float0)

    def body(j, state):
        dws, dxs, dc = state
        i = n_live - 1 - j  # the last live band first: its carry's cotangent is zero
        s = _cut(side, i)
        c_in = jax.tree_util.tree_map(
            lambda k: jax.lax.dynamic_index_in_dim(k, i, 0, keepdims=False), kept)

        def band(w, c, *x):
            outs, c = fn(static, w, x, s, c)
            return tuple(outs), c

        (outs, c_out), vjp = jax.vjp(band, weights, c_in, *_cut(xs, i))
        dw, dc, *dx = vjp((_cotangents(outs, _cut(d_outs, i)), _cotangents(c_out, dc)))
        dws = jax.tree_util.tree_map(lambda a, g: a + g.astype(a.dtype), dws, dw)
        return dws, _put(dxs, dx, i), _floating(dc)

    dws, dxs, _ = jax.lax.fori_loop(0, n_live, body, (
        jax.tree_util.tree_map(lambda w: jnp.zeros(w.shape, jnp.float32), weights),
        tuple(jnp.zeros_like(x) for x in xs),
        [jnp.zeros(k.shape[1:], k.dtype) for k in _floating(kept)]))
    dws, dxs = jax.lax.optimization_barrier(  # as `_stretch_bwd`: cast here and now
        (jax.tree_util.tree_map(lambda a, w: a.astype(w.dtype), dws, weights), dxs))
    return dws, dxs, None, None, None


_carried.defvjp(_carried_fwd, _carried_bwd)

_carried_jit = jax.jit(_carried, static_argnums=(0, 1))


def stretch(fn: Callable, static: Any, weights: Any, xs: Sequence[jnp.ndarray],
            side: Sequence[jnp.ndarray], n_live,
            minor: Tuple[int, ...] = ()) -> Tuple[jnp.ndarray, ...]:
    """`fn(static, weights, xs, side)` over the first `n_live` bands of the
    one row: `xs` and `side` are tuples of `[1, T, ...]` arrays of which
    `fn` sees a band `[1, _BAND, ...]` each, and returns a tuple of such
    arrays; the result is those as `[1, T, ...]`, zero past the live
    bands. The `xs` that `minor` names by index lie sequence-minor, `[1,
    ..., T]`, and `fn` sees `[1, ..., _BAND]` of them (an attention
    kernel's output as the kernel wrote it: the loop's buffers, this one
    and its cotangent's, are then the kernel's own layout, and nothing
    relays the row on its way in or out). `fn` and `static` are hashable (a module-level function and a
    tuple of what it does not trace): they key the one trace. Gradients
    flow to `weights` (a pytree of floating arrays, the same for every
    band; summed in float32 across bands) and to `xs`; `side` gets none
    (positions' tables)."""
    return _stretch_jit(fn, static, tuple(minor), weights, tuple(xs), tuple(side), n_live)


def carried(fn: Callable, static: Any, weights: Any, xs: Sequence[jnp.ndarray],
            side: Sequence[jnp.ndarray], carry: Any, n_live) -> Tuple[jnp.ndarray, ...]:
    """`stretch` for a function that is token-wise but for what one band
    hands the next: `fn(static, weights, xs, side, carry) -> (outs, carry)`
    over the first `n_live` bands in order, `carry` (a pytree; integer leaves
    ride along) what the first band receives. The result is `outs` as
    `[1, T, ...]`, zero past the live bands; the last band's carry is no
    one's. The forward rule keeps each band's carry-in a band a slot
    (`[T / _BAND, ...]` a leaf) and nothing else of a band; the backward loop
    walks the same bands last to first, makes a band's `jax.vjp` again from
    its cells and its kept carry-in and hands the carry's cotangent
    backwards. Gradients flow to `weights` (float32 sums across bands) and
    `xs`; `side` and the first `carry` (a constant: zeros where a row
    starts) get none."""
    return _carried_jit(fn, static, weights, tuple(xs), tuple(side), carry, n_live)
