"""The delta-rule mixers' taps as a kernel pair (Pallas, TPU): the short
causal convolution of `ops/ssm.causal_conv` with the mixer's mask of its
input inside it, `kda_taps_fwd` and `kda_taps_bwd` under a `jax.custom_vjp`.

`causal_conv`'s meaning exactly: x `[R, T, C]`, w `[K, C]`, b `[C]` or None,
segment_ids `[R, T]` ->

    y_t = silu(b + sum_l w[K-1-l] x_{t-l}),  l = 0 .. K-1,

over the taps whose position lies in t's own sequence, 0 at padding cells;
and a cell whose segment id is 0 is read as 0: whatever a padding cell of x
holds (NaN and inf among it: the residual stream carries them along) reaches
neither a result nor a gradient. XLA runs the plain form (a `where` of the
operand, a `pad`, K misaligned slices with a `where` a tap, silu, a last
`where`; in the backward the transposes of all of them and a separate pass of
sums for the weights) as a dozen passes over the whole array; here an array
is read once and written once forward, and backward x and the output's
cotangent are read once and x's cotangent written once.

Which taps of a cell count is worked out before the call, from the segment
ids alone (`_codes`, `[R, T]` integers: bit 0 the cell holds a token, bit l
the cell l places before is of its sequence, bit K-1+l the cell l places
after is); the kernels see that number a cell, the same in all 128 lanes
(`[R, T, 128]`: a block of it is the masks' vregs as they stand, no move
from lanes to sublanes), and no segment id. Every mask is a `select`, never
a product: a NaN on the side not taken does not pass.

A grid step is `ROWS` cells of a row by all C columns (T on sublanes, C on
lanes as the projections leave them, so the reshape to `[R, T, h, K]` after
the call stays free; a block is whole rows of the array, one stretch of
HBM). The step's masks are made once (`_masks`: a bit a mask into VMEM
scratch), then the block is walked a strip of `COLS` lanes at a time and,
inside a strip, a chunk of `CHUNK` cells at a time: eight vregs an array, so
a chunk's taps, sums and silu stay in the registers (the whole strip at once
was a third slower: 0.73 / 2.61 ms forward / forward + backward a full row
of 16,384 x 4,096 against 0.58 / 1.90, PERF.md section 6, PR 57). The K-1
cells before the block come from a second block spec on the same array, the
sublane tile of 16 that ends where the block starts; those before a later
chunk are the block's own. Sums, silu and the masks are float32 in VMEM
whatever the operands' dtype; silu's sigmoid is the vector unit's tanh.

*Forward*: masks, taps by sublane rolls, bias, silu, the last mask.

*Backward*: the row's blocks from the last to the first, a block's chunks
from the last too. A chunk makes `acc` again from x (nothing but x, w and b
is kept from the forward), `dacc = dy silu'(acc)` at live cells, adds
`sum_t dacc x_{t-l}` and `sum_t dacc` to the strip's sums (eight rows'
partial sums a vreg, the sublanes added when the strip is done, into an
output block `[8, C]` float32 that stays in VMEM over the row: rows 0..K-1
dw, row K db), and writes `dx_t = sum_l w[K-1-l] dacc_{t+l}`: the K-1 cells
after the chunk are the first rows of the `dacc` made just before (the
chunk after it; across blocks kept in VMEM scratch), so neither x nor the
cotangent is read past the block.

A block past a row's last live cell (`n_live`, a scalar the index maps
read) fetches nothing new and is written as zeros.

By the probe (`scripts/kda_probe.py --taps`, PERF.md section 6, PR 57), an
operand `[1, 16384, 4096]` bf16 under four taps, ms forward / forward +
backward with the loss's own pass: 0.37 / 1.28 at 53 % fill and 0.48 / 1.67
full, against the plain form's 1.43 / 5.02 and 1.04 / 4.32; with the taps,
the masks and silu all left out the forward is 0.47 full: it stands at the
memory's rate.

`fits` says what the kernels need of a call's shapes (T whole blocks, C whole
strips, K - 1 cells within one sublane tile and K + 1 rows within the `[8,
C]` block); `ops/kda.kda_mixer` takes the plain form where it says no.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 256  # cells a grid step
CHUNK = 64  # cells of a strip in the registers at a time
COLS = 128  # lanes a strip inside a step: the column block
HALO = 16  # rows of the block spec that holds the cells before: bf16's sublane tile


def fits(T: int, C: int, K: int) -> bool:
    """Whether the kernels take a call of these shapes."""
    return T % ROWS == 0 and C % COLS == 0 and 2 <= K <= 7


# What a step's blocks take of VMEM grows with the width: the backward's (x,
# the cotangent and x's cotangent, each twice, 256 cells by all columns) are
# 18.5 MB at 5,760 columns, past the 16 MiB a kernel gets unasked; up to 4,096
# columns (12.6 MB) nothing is asked, and the programs there are as they were.
WIDE_COLS = 4096
WIDE_VMEM_BYTES = 32 * 2 ** 20


def _vmem(C: int):
    return None if C <= WIDE_COLS else WIDE_VMEM_BYTES


def _codes(seg, K: int):
    """[R, T] -> [R, T, COLS] int32, a cell's number in every lane of a strip: bit 0 it
    holds a token; bit l (1..K-1) the cell l before it is of its sequence;
    bit K-1+l the cell l after it is."""
    T = seg.shape[1]
    valid = seg > 0
    code = valid.astype(jnp.int32)
    for lag in range(1, K):
        before = jnp.pad(seg, ((0, 0), (lag, 0)))[:, :T]
        after = jnp.pad(seg, ((0, 0), (0, lag)))[:, lag:]
        code = code | ((valid & (before == seg)).astype(jnp.int32) << lag) \
            | ((valid & (after == seg)).astype(jnp.int32) << (K - 1 + lag))
    return jnp.broadcast_to(code[..., None], code.shape + (COLS,))


def _live_blocks(seg, rows: int):
    """[R] the blocks of each row up to its last token's (0 = an empty row):
    `ops/kda._live_chunks` without its `select`."""
    T = seg.shape[1]
    last = jnp.max((seg > 0) * jnp.arange(1, T + 1, dtype=jnp.int32), axis=1)
    return lax.div(last + rows - 1, jnp.int32(rows))


def _weights(w, b):
    """w `[K, C]` and b `[C]` or None as one `[8, C]` float32 block: rows
    0..K-1 the taps, row K the bias (the backward writes its sums so)."""
    K, C = w.shape
    rows = [w.astype(jnp.float32),
            (jnp.zeros((C,), jnp.float32) if b is None else b.astype(jnp.float32))[None],
            jnp.zeros((7 - K, C), jnp.float32)]
    return jnp.concatenate(rows, axis=0)


def _sigmoid(x):
    """By the vector unit's own tanh: one transcendental and two products a
    cell where `1 / (1 + exp(-x))` is two and a division."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _masks(code_ref, m_ref, n):
    """The step's masks out of its cells' numbers, once for all its strips:
    `m_ref[i]` is bit i, 0 or 1."""
    code = code_ref[...]
    for i in range(n):
        m_ref[i] = (code >> i) & 1


def _strips(C, body):
    """`body(at)` for every strip of `COLS` lanes of the step's blocks."""

    def step(j, _):
        body(pl.ds(pl.multiple_of(j * COLS, COLS), COLS))
        return _

    lax.fori_loop(0, C // COLS, step, None)


def _chunks(rows, chunk):
    """A step's cells in chunks (a block shorter than a chunk: in one)."""
    ch = min(chunk, rows)
    return [slice(lo, lo + ch) for lo in range(0, rows, ch)]


def _live(m_ref, i, cells):
    return m_ref[i, cells, :] != 0


def _acc(x_ref, prev_ref, m_ref, wb, at, cells, K):
    """The taps of the strip's chunk `cells` (a slice): the masked, shifted
    operands in float32 (tap l's at place l) and `b + sum_l w[K-1-l] x_{t-l}`;
    `wb` the strip's `[8, COLS]` of the weights."""
    f32 = jnp.float32
    lo = cells.start
    x = x_ref[cells, at].astype(f32)
    before = prev_ref[:, at] if lo == 0 else x_ref[lo - HALO:lo, at]
    xe = jnp.concatenate([before.astype(f32)[HALO - 8:], x], axis=0)  # cell t at row t + 8
    taps = [jnp.where(_live(m_ref, 0, cells), x, 0.0)] + [
        jnp.where(_live(m_ref, lag, cells), pltpu.roll(xe, lag, 0)[8:], 0.0)
        for lag in range(1, K)]
    acc = wb[K:K + 1]
    for lag, tap in enumerate(taps):
        acc = acc + tap * wb[K - 1 - lag:K - lag]
    return taps, acc


def _fwd_kernel(n_live_ref, x_ref, prev_ref, code_ref, wb_ref, o_ref, m_ref, *, K, chunk):
    r, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t < n_live_ref[r])
    def _():
        _masks(code_ref, m_ref, K)

        def strip(at):
            wb = wb_ref[:, at]
            for cells in _chunks(x_ref.shape[0], chunk):
                _, acc = _acc(x_ref, prev_ref, m_ref, wb, at, cells, K)
                y = jnp.where(_live(m_ref, 0, cells), acc * _sigmoid(acc), 0.0)
                o_ref[cells, at] = y.astype(o_ref.dtype)

        _strips(x_ref.shape[1], strip)

    @pl.when(t >= n_live_ref[r])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _bwd_kernel(n_live_ref, x_ref, prev_ref, code_ref, wb_ref, dy_ref, dx_ref, dwb_ref,
                after_ref, m_ref, *, K, chunk):
    r, p = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    t = pl.num_programs(1) - 1 - p  # the row's blocks from the last
    rows = x_ref.shape[0]

    @pl.when(p == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(t < n_live_ref[r])
    def _():
        _masks(code_ref, m_ref, 2 * K - 1)
        # eight rows' sums in a vreg: the sublanes are added when a strip is done
        folded = lambda a: sum(a[i:i + 8] for i in range(0, a.shape[0], 8))

        def strip(at):
            wb = wb_ref[:, at]
            after = after_ref[:, at]  # the 8 cells after the chunk: their dacc
            sums = [jnp.zeros((8, COLS), f32)] * (K + 1)
            for cells in reversed(_chunks(rows, chunk)):  # the chunks from the last
                n = cells.stop - cells.start
                taps, acc = _acc(x_ref, prev_ref, m_ref, wb, at, cells, K)
                s = _sigmoid(acc)
                dacc = dy_ref[cells, at].astype(f32) * (s * (1.0 + acc * (1.0 - s)))
                dacc = jnp.where(_live(m_ref, 0, cells), dacc, 0.0)
                sums = [a + folded(dacc * tap) for a, tap in zip(sums, taps)] + [
                    sums[K] + folded(dacc)]
                de = jnp.concatenate([dacc, after], axis=0)
                dx = dacc * wb[K - 1:K]
                for lag in range(1, K):
                    dx = dx + jnp.where(_live(m_ref, K - 1 + lag, cells),
                                        pltpu.roll(de, n + 8 - lag, 0)[:n], 0.0) \
                        * wb[K - 1 - lag:K - lag]
                dx_ref[cells, at] = dx.astype(dx_ref.dtype)
                after = dacc[:8]
            after_ref[:, at] = after
            for lag in range(K):
                dwb_ref[K - 1 - lag:K - lag, at] += jnp.sum(sums[lag], axis=0, keepdims=True)
            dwb_ref[K:K + 1, at] += jnp.sum(sums[K], axis=0, keepdims=True)

        _strips(x_ref.shape[1], strip)

    @pl.when(t >= n_live_ref[r])
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)


def _in_specs(rows, C, block_of):
    """The blocks of x, of the cells before, of the cells' numbers and of the
    weights, for the block `block_of(step)` of a row."""
    at = lambda r, s, n: jnp.minimum(block_of(s), jnp.maximum(n[r] - 1, 0))
    return [pl.BlockSpec((None, rows, C), lambda r, s, n: (r, at(r, s, n), 0)),
            pl.BlockSpec((None, HALO, C), lambda r, s, n: (
                r, jnp.maximum(at(r, s, n) * (rows // HALO) - 1, 0), 0)),
            pl.BlockSpec((None, rows, COLS), lambda r, s, n: (r, at(r, s, n), 0)),
            pl.BlockSpec((8, C), lambda r, s, n: (0, 0))]


@functools.partial(jax.jit, static_argnames=("interpret", "rows", "chunk"))
def taps_fwd(x, w, b, segment_ids, interpret: bool = False, rows: int = ROWS,
             chunk: int = CHUNK):
    """The forward: x `[R, T, C]`, w `[K, C]`, b `[C]` or None, segment_ids
    `[R, T]` -> y `[R, T, C]` in x's dtype. Device op `kda_taps_fwd`. Jitted
    here: q's, k's and v's calls, the layers of a stack, their forward and
    remat's trace the kernel's body once a shape."""
    R, T, C = x.shape
    K = w.shape[0]
    with jax.named_scope("kda_taps_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, K=K, chunk=chunk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R, T // rows),
                in_specs=_in_specs(rows, C, lambda t: t),
                out_specs=pl.BlockSpec((None, rows, C), lambda r, t, n: (r, t, 0)),
                scratch_shapes=[pltpu.VMEM((K, rows, COLS), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((R, T, C), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_vmem(C)),
            name="kda_taps_fwd", interpret=interpret,
        )(_live_blocks(segment_ids, rows), x, x, _codes(segment_ids, K), _weights(w, b))


@functools.partial(jax.jit, static_argnames=("interpret", "rows", "chunk"))
def taps_bwd(x, w, b, segment_ids, dy, interpret: bool = False, rows: int = ROWS,
             chunk: int = CHUNK):
    """The transpose from the forward's operands and `dy` `[R, T, C]`: the
    cotangents of x (its dtype), w and b (theirs, summed in float32; b's
    None where b is). Device op `kda_taps_bwd`. Jitted here, as the forward."""
    R, T, C = x.shape
    K, N = w.shape[0], T // rows
    specs = _in_specs(rows, C, lambda p: N - 1 - p)
    with jax.named_scope("kda_taps_bwd"):
        dx, dwb = pl.pallas_call(
            functools.partial(_bwd_kernel, K=K, chunk=chunk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R, N),
                in_specs=specs + [specs[0]],
                out_specs=[pl.BlockSpec((None, rows, C), lambda r, p, n: (r, N - 1 - p, 0)),
                           pl.BlockSpec((None, 8, C), lambda r, p, n: (r, 0, 0))],
                scratch_shapes=[pltpu.VMEM((8, C), jnp.float32),
                                pltpu.VMEM((2 * K - 1, rows, COLS), jnp.int32)]),
            out_shape=[jax.ShapeDtypeStruct((R, T, C), x.dtype),
                       jax.ShapeDtypeStruct((R, 8, C), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_vmem(C)),
            name="kda_taps_bwd", interpret=interpret,
        )(_live_blocks(segment_ids, rows), x, x, _codes(segment_ids, K), _weights(w, b),
          dy.astype(x.dtype))
    dwb = dwb.sum(0)
    return dx, dwb[:K].astype(w.dtype), None if b is None else dwb[K].astype(b.dtype)


def taps(x, w, b, segment_ids, interpret: bool = False):
    """`ops/ssm.causal_conv(where(segment_ids > 0, x, 0), w, b, segment_ids)`
    as the kernels above; the shapes must fit (`fits`)."""
    return _taps(x, w, b, segment_ids, interpret, ROWS, CHUNK)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _taps(x, w, b, segment_ids, interpret, rows, chunk):
    return taps_fwd(x, w, b, segment_ids, interpret=interpret, rows=rows, chunk=chunk)


def _taps_fwd(x, w, b, segment_ids, interpret, rows, chunk):
    y = taps_fwd(x, w, b, segment_ids, interpret=interpret, rows=rows, chunk=chunk)
    return y, (x, w, b, segment_ids)


def _taps_bwd(interpret, rows, chunk, res, dy):
    return taps_bwd(*res, dy, interpret=interpret, rows=rows, chunk=chunk) + (None,)


_taps.defvjp(_taps_fwd, _taps_bwd)
