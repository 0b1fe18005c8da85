"""Rows added to their tokens as a segment-sum over token bands (Pallas,
TPU): what `models/moe._add_rows` runs on the chip in place of a
scatter-add, which there reads and writes the whole `[T, D]` it adds into
and walks the rows one at a time.

`y [T, D]` float32 is cut into bands of `BAND` tokens, the rows, already
in token order, into blocks of `BLOCK`. Bands and blocks both rise along
the rows, so the (band, block) pairs that hold a row are one staircase of
at most `T / BAND + B / BLOCK` steps (`_walk`), and the grid is that
list, its length a value of the run (as `ops/pallas/splash_pairs.py`
walks a row's live block pairs). A step builds `onehot[BAND, BLOCK] = (band's token == row's token)` on the VPU
and adds `onehot @ rows` to the band on the MXU, float32 sums; the band
stays in VMEM for its run of steps and `y` is aliased in and out, so a
band no row touches is neither read nor written and keeps its bits. A
row whose token lies outside the band is a zero column of the one-hot: no
unaligned slice, no branch.

The one-hot is exact (0 and 1), and a bf16 row times 1 summed in float32
is the row, so a token's sum differs from the scatter-add's only in the
order float32 adds its rows. Float32 rows (an engine run in float32) take
the product at `Precision.HIGHEST`, which does not round them to bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.splash_pairs import FIRST

_LANES = 128
_SUBLANES = 8
BAND = 128  # tokens of y a step adds into
BLOCK = 256  # rows a step adds


def kernel_ok(n_tok: int, d: int, n_rows: int) -> bool:
    """Shapes the kernel takes: whole bands of tokens, whole blocks of
    rows, `d` in whole lane tiles."""
    return n_tok % BAND == 0 and n_rows % BLOCK == 0 and d % _LANES == 0


def _walk(tok, n_rows, n_bands: int):
    """The (band, block) pairs that hold one of the first `n_rows` rows
    of `tok` [B] (int32, ascending over those rows), in the order the
    kernel walks them: `(band, block, flags)`, int32 `[n_bands + B /
    BLOCK]` each, and how many there are. A pair starts at the row that
    is the first of its block or of its band, so the steps are those rows
    in order; `flags` is FIRST on a band's first step. Past the count,
    the last pair again (never walked: the grid is as long as the
    count)."""
    b = tok.shape[0]
    row = jnp.arange(b, dtype=jnp.int32)
    band_of = tok // BAND
    starts = ((row % BLOCK == 0) | (band_of != jnp.roll(band_of, 1))) & (row < n_rows)
    seen = jnp.cumsum(starts, dtype=jnp.int32)  # pairs begun up to and with a row
    n = seen[-1]
    s = jnp.minimum(jnp.arange(n_bands + b // BLOCK, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    at = jnp.minimum(jnp.sum(seen[None, :] <= s[:, None], axis=1, dtype=jnp.int32), b - 1)
    band = jnp.minimum(band_of[at], n_bands - 1)  # no block index past y, whatever tok holds
    new = jnp.concatenate([jnp.ones(1, bool), band[1:] != band[:-1]])
    return band, at // BLOCK, (FIRST * new).astype(jnp.int32), n


def _kernel(band_ref, block_ref, flags_ref, n_ref, tok_ref, rows_ref, y_ref, out_ref, *,
            precision):
    s = pl.program_id(0)

    @pl.when(flags_ref[s] & FIRST != 0)
    def first():
        out_ref[...] = y_ref[...]

    at = band_ref[s] * BAND + lax.broadcasted_iota(jnp.int32, (BAND, BLOCK), 0)
    onehot = (at == tok_ref[:1, :]).astype(rows_ref.dtype)
    # what a row past n_rows holds need not be finite, and 0 x it is not 0
    row = block_ref[s] * BLOCK + lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
    rows = jnp.where(row < n_ref[0], rows_ref[...], 0)
    out_ref[...] += lax.dot(onehot, rows, precision=precision,
                            preferred_element_type=jnp.float32)


def add_sorted_rows(y, rows, tok, n_rows, interpret: bool = False):
    """`y [T, D]` float32 with row r of `rows [B, D]` added to token
    `tok[r]` for r under `n_rows`; `tok` int32, ascending over those
    rows (what lies past them, row or token, is not read), and
    `kernel_ok(T, D, B)`. Device op `moe_rows_add`."""
    (n_tok, d), b = y.shape, rows.shape[0]
    assert kernel_ok(n_tok, d, b) and y.dtype == jnp.float32, (y.shape, y.dtype, rows.shape)
    band, block, flags, n = _walk(tok, n_rows, n_tok // BAND)
    on_band = lambda s, band, block, flags, n_rows: (band[s], 0)
    precision = lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    with jax.named_scope("moe_rows_add"):
        return pl.pallas_call(
            functools.partial(_kernel, precision=precision),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n,),
                in_specs=[
                    # Sublane-broadcast: Mosaic has no retiling of a single row.
                    pl.BlockSpec((_SUBLANES, BLOCK),
                                 lambda s, band, block, flags, n_rows: (0, block[s])),
                    pl.BlockSpec((BLOCK, d), lambda s, band, block, flags, n_rows: (block[s], 0)),
                    pl.BlockSpec((BAND, d), on_band),
                ],
                out_specs=pl.BlockSpec((BAND, d), on_band)),
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            input_output_aliases={6: 0},
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            name="moe_rows_add", interpret=interpret,
        )(band, block, flags, jnp.asarray(n_rows, jnp.int32).reshape(1),
          lax.broadcast_in_dim(tok, (_SUBLANES, b), (1,)), rows, y)
