"""The gated short convolution as a kernel pair (Pallas, TPU): LFM2's mixer
between its two projections, `ops/ssm.gated_conv` over a whole row,
`conv_gate_fwd` and `conv_gate_bwd` under a `jax.custom_vjp`.

`gated_conv`'s meaning: bcx `[R, T, 3 D]` = `[B | C | x]`, w `[K, D]`,
segment_ids `[R, T]` ->

    y_t = C_t * sum_l w[K-1-l] (B * x)_{t-l},  l = 0 .. K-1,

over the taps whose position lies in t's own sequence, 0 at padding cells;
what a padding cell of bcx holds reaches neither a result nor a gradient
(every mask is a `select`). XLA runs the plain form's backward (the
transposes of a `pad`, K misaligned slices, the `split` and three sums for
the taps) as nine fusions that cross HBM with 1.14 GB for a row of 8,192 x
6,144 where the operands and results are 0.23 GB: 1.42 ms, a fifth of the
memory's rate (`scripts/conv_probe.py`, PERF.md section 6, PR 63); here bcx
and the output's cotangent are read once and bcx's cotangent written once.

`ops/pallas/kda_taps.py`'s plan and helpers (the delta-rule mixers' taps:
the same convolution under a silu): which taps of a cell count is worked out
before the call from the segment ids (`kda_taps._codes`: bit 0 the cell
holds a token, bit l the cell l before is of its sequence, bit K-1+l the cell
l after is), the same number in all 128 lanes; a grid step is `ROWS` cells
of a row by all `3 D` columns, walked a strip of 128 lanes at a time and a
chunk of `CHUNK` cells inside a strip, float32 in the registers; the K-1
cells before the block come from a second block spec on the same array;
the backward walks a row's blocks from the last, keeps the first cells'
`dacc` of the block after in VMEM and the taps' sums in an output block
that stays in VMEM over the row. A block past a row's last live cell
fetches nothing new and is written as zeros.

*Forward*: `bx = B x`, the taps by sublane rolls, the gate, the mask.
*Backward*: a chunk makes `bx` and `acc` again, `dC = dy acc`, `dacc = dy
C` at live cells, `dw[K-1-l] += sum_t dacc (bx)_{t-l}`, `d(bx)_t = sum_l
w[K-1-l] dacc_{t+l}`, `dB = d(bx) x`, `dx = d(bx) B`.

No bias and nothing handed in from cells before the row (`fits`): a band of
a longer row (`ops/band_loop.carried`) and a convolution with a bias take
the plain form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.kda_taps import (
    COLS, HALO, _chunks, _codes, _live, _live_blocks, _masks, _weights)

ROWS = 128  # cells a grid step: a block of bcx is 1.5 MB at 6,144 columns
CHUNK = 32  # cells of a strip in the registers at a time: four arrays of them


def fits(T: int, D: int, K: int) -> bool:
    """Whether the kernels take a call of these shapes."""
    return T % ROWS == 0 and D % COLS == 0 and 2 <= K <= 7


def _third(ref, rows, k: int, off, D: int):
    """Strip `off` of third k (0 B, 1 C, 2 x) of a `[.., 3 D]` block."""
    return ref[rows, pl.ds(pl.multiple_of(k * D + off, COLS), COLS)]


def _strips(D: int, body):
    """`body(off)` for every strip of `COLS` lanes of a third."""

    def step(j, _):
        body(pl.multiple_of(j * COLS, COLS))
        return _

    lax.fori_loop(0, D // COLS, step, None)


def _acc(bcx_ref, prev_ref, m_ref, wb, off, cells, K: int, D: int):
    """The strip's chunk `cells`: B, x, the masked, shifted gated inputs in
    float32 (tap l's at place l) and `sum_l w[K-1-l] (B x)_{t-l}`."""
    f32 = jnp.float32
    lo = cells.start
    B = _third(bcx_ref, cells, 0, off, D).astype(f32)
    x = _third(bcx_ref, cells, 2, off, D).astype(f32)
    bx = B * x
    src, rows = (prev_ref, slice(None)) if lo == 0 else (bcx_ref, slice(lo - HALO, lo))
    before = (_third(src, rows, 0, off, D).astype(f32) * _third(src, rows, 2, off, D).astype(f32))
    xe = jnp.concatenate([before[HALO - 8:], bx], axis=0)  # cell t at row t + 8
    taps = [jnp.where(_live(m_ref, 0, cells), bx, 0.0)] + [
        jnp.where(_live(m_ref, lag, cells), pltpu.roll(xe, lag, 0)[8:], 0.0)
        for lag in range(1, K)]
    acc = taps[0] * wb[K - 1:K]
    for lag in range(1, K):
        acc = acc + taps[lag] * wb[K - 1 - lag:K - lag]
    return B, x, taps, acc


def _fwd_kernel(n_live_ref, bcx_ref, prev_ref, code_ref, wb_ref, y_ref, m_ref, *, K, D):
    r, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t < n_live_ref[r])
    def _():
        _masks(code_ref, m_ref, K)

        def strip(off):
            at = pl.ds(off, COLS)
            wb = wb_ref[:, at]
            for cells in _chunks(bcx_ref.shape[0], CHUNK):
                _, _, _, acc = _acc(bcx_ref, prev_ref, m_ref, wb, off, cells, K, D)
                C = _third(bcx_ref, cells, 1, off, D).astype(jnp.float32)
                y_ref[cells, at] = jnp.where(
                    _live(m_ref, 0, cells), C * acc, 0.0).astype(y_ref.dtype)

        _strips(D, strip)

    @pl.when(t >= n_live_ref[r])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _bwd_kernel(n_live_ref, bcx_ref, prev_ref, code_ref, wb_ref, dy_ref, dbcx_ref, dwb_ref,
                after_ref, m_ref, *, K, D):
    r, p = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    t = pl.num_programs(1) - 1 - p  # the row's blocks from the last
    rows = bcx_ref.shape[0]

    @pl.when(p == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(t < n_live_ref[r])
    def _():
        _masks(code_ref, m_ref, 2 * K - 1)
        # eight rows' sums in a vreg: the sublanes are added when a strip is done
        folded = lambda a: sum(a[i:i + 8] for i in range(0, a.shape[0], 8))

        def strip(off):
            at = pl.ds(off, COLS)
            wb = wb_ref[:, at]
            after = after_ref[:, at]  # the 8 cells after the chunk: their dacc
            sums = [jnp.zeros((8, COLS), f32)] * K
            for cells in reversed(_chunks(rows, CHUNK)):  # the chunks from the last
                n = cells.stop - cells.start
                live = _live(m_ref, 0, cells)
                B, x, taps, acc = _acc(bcx_ref, prev_ref, m_ref, wb, off, cells, K, D)
                C = _third(bcx_ref, cells, 1, off, D).astype(f32)
                dy = dy_ref[cells, at].astype(f32)
                dacc = jnp.where(live, dy * C, 0.0)
                sums = [a + folded(dacc * tap) for a, tap in zip(sums, taps)]
                de = jnp.concatenate([dacc, after], axis=0)
                dbx = dacc * wb[K - 1:K]
                for lag in range(1, K):
                    dbx = dbx + jnp.where(_live(m_ref, K - 1 + lag, cells),
                                          pltpu.roll(de, n + 8 - lag, 0)[:n], 0.0) \
                        * wb[K - 1 - lag:K - lag]
                for k, d in enumerate((dbx * x, dy * acc, dbx * B)):  # dB, dC, dx
                    dbcx_ref[cells, pl.ds(pl.multiple_of(k * D + off, COLS), COLS)] = (
                        jnp.where(live, d, 0.0).astype(dbcx_ref.dtype))
                after = dacc[:8]
            after_ref[:, at] = after
            for lag in range(K):
                dwb_ref[K - 1 - lag:K - lag, at] += jnp.sum(sums[lag], axis=0, keepdims=True)

        _strips(D, strip)

    @pl.when(t >= n_live_ref[r])
    def _():
        dbcx_ref[...] = jnp.zeros_like(dbcx_ref)


def _in_specs(D, block_of):
    """The blocks of bcx, of the cells before, of the cells' numbers and of
    the weights, for the block `block_of(step)` of a row."""
    at = lambda r, s, n: jnp.minimum(block_of(s), jnp.maximum(n[r] - 1, 0))
    return [pl.BlockSpec((None, ROWS, 3 * D), lambda r, s, n: (r, at(r, s, n), 0)),
            pl.BlockSpec((None, HALO, 3 * D), lambda r, s, n: (
                r, jnp.maximum(at(r, s, n) * (ROWS // HALO) - 1, 0), 0)),
            pl.BlockSpec((None, ROWS, COLS), lambda r, s, n: (r, at(r, s, n), 0)),
            pl.BlockSpec((8, D), lambda r, s, n: (0, 0))]


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_gate_fwd(bcx, w, segment_ids, interpret: bool = False):
    """The forward: bcx `[R, T, 3 D]`, w `[K, D]`, segment_ids `[R, T]` -> y
    `[R, T, D]` in bcx's dtype. Device op `conv_gate_fwd`. Jitted here: the
    layers of a stack, their forward and remat's trace the body once a shape."""
    R, T, D3 = bcx.shape
    K, D = w.shape
    with jax.named_scope("conv_gate_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, K=K, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R, T // ROWS),
                in_specs=_in_specs(D, lambda t: t),
                out_specs=pl.BlockSpec((None, ROWS, D), lambda r, t, n: (r, t, 0)),
                scratch_shapes=[pltpu.VMEM((K, ROWS, COLS), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((R, T, D), bcx.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="conv_gate_fwd", interpret=interpret,
        )(_live_blocks(segment_ids, ROWS), bcx, bcx, _codes(segment_ids, K), _weights(w, None))


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_gate_bwd(bcx, w, segment_ids, dy, interpret: bool = False):
    """The transpose from the forward's operands and `dy` `[R, T, D]`: the
    cotangents of bcx (its dtype) and of w (its own, summed in float32).
    Device op `conv_gate_bwd`. Jitted here, as the forward."""
    R, T, D3 = bcx.shape
    K, D = w.shape
    N = T // ROWS
    specs = _in_specs(D, lambda p: N - 1 - p)
    with jax.named_scope("conv_gate_bwd"):
        dbcx, dwb = pl.pallas_call(
            functools.partial(_bwd_kernel, K=K, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R, N),
                in_specs=specs + [pl.BlockSpec(
                    (None, ROWS, D), specs[0].index_map)],
                out_specs=[pl.BlockSpec((None, ROWS, D3), lambda r, p, n: (r, N - 1 - p, 0)),
                           pl.BlockSpec((None, 8, D), lambda r, p, n: (r, 0, 0))],
                scratch_shapes=[pltpu.VMEM((8, D), jnp.float32),
                                pltpu.VMEM((2 * K - 1, ROWS, COLS), jnp.int32)]),
            out_shape=[jax.ShapeDtypeStruct((R, T, D3), bcx.dtype),
                       jax.ShapeDtypeStruct((R, 8, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="conv_gate_bwd", interpret=interpret,
        )(_live_blocks(segment_ids, ROWS), bcx, bcx, _codes(segment_ids, K), _weights(w, None),
          dy.astype(bcx.dtype))
    return dbcx, dwb.sum(0)[:K].astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv_gate(bcx, w, segment_ids, interpret: bool = False):
    """`ops/ssm.gated_conv(bcx, w, None, segment_ids)[0]` as the kernels
    above; the shapes must fit (`fits`)."""
    return conv_gate_fwd(bcx, w, segment_ids, interpret=interpret)


def _vjp_fwd(bcx, w, segment_ids, interpret):
    return conv_gate_fwd(bcx, w, segment_ids, interpret=interpret), (bcx, w, segment_ids)


def _vjp_bwd(interpret, res, dy):
    return conv_gate_bwd(*res, dy, interpret=interpret) + (None,)


conv_gate.defvjp(_vjp_fwd, _vjp_bwd)
