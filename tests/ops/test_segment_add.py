"""`ops/pallas/segment_add.py` in interpret mode against the scatter-add
it replaces on the chip: `models/moe._add_rows` run both ways on the same
rows, tokens and `n_rows` (off the chip `_add_rows` is the scatter-add;
`_kernel_add` steers it to the kernel as the chip would)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.ops.pallas import segment_add

T = 512  # four bands of 128 tokens
K = 8  # rows a token takes at most: a layer's top_k


def _kernel_add(monkeypatch, y, rows, tok, n_rows):
    """`_add_rows` as the chip runs it, the kernel in interpret mode."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        m.setattr(segment_add, "add_sorted_rows",
                  functools.partial(segment_add.add_sorted_rows, interpret=True))
        add = lambda *args: moe_lib._add_rows(*args)  # jax keeps a function's trace
        traced = str(jax.make_jaxpr(add)(y, rows, tok, n_rows))
        assert "pallas_call" in traced and "scatter" not in traced
        return jax.jit(add)(y, rows, tok, n_rows)


def _case(name, b, rng):
    """(tokens [b] in the order tiles hand them over: not sorted, n_rows,
    tokens that must keep their old values)."""
    tok = np.full(b, T - 1, np.int64)
    if name == "hits-1-2-k":  # a token hit by 1, 2 and k rows, in three bands
        n_rows = 1 + 2 + K
        tok[:n_rows] = rng.permutation([5] + [140] * 2 + [300] * K)
        return tok, n_rows, np.r_[0:5, 6:140, 141:300, 301:T]
    if name == "n_rows-0":
        tok[:] = rng.integers(0, T, b)
        return tok, 0, np.arange(T)
    if name == "all-to-one":
        tok[:] = 200
        return tok, b, np.r_[0:200, 201:T]
    if name == "band-untouched":  # bands 0, 1 and 3 take rows, band 2 none
        n_rows = b - 40
        tok[:n_rows] = rng.choice(np.r_[0:256, 384:T], n_rows)
        return tok, n_rows, np.arange(256, 384)
    if name == "straddle":  # one block's rows on both sides of a band's edge
        n_rows = 200
        tok[:n_rows] = rng.permutation(np.r_[120:128, 128:136].repeat(13)[:n_rows])
        return tok, n_rows, np.r_[0:120, 136:T]
    if name == "random":  # every block and every band
        n_rows = b - 3
        tok[:n_rows] = rng.integers(0, T, n_rows)
        return tok, n_rows, np.setdiff1d(np.arange(T), tok[:n_rows])
    raise ValueError(name)


@pytest.mark.parametrize("case", ["hits-1-2-k", "n_rows-0", "all-to-one", "band-untouched",
                                  "straddle", "random"])
@pytest.mark.parametrize("b", [256, 768], ids=["one-block", "three-blocks"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [256, 384], ids=["D256", "D384"])
def test_the_kernel_adds_what_the_scatter_adds(d, dtype, b, case, monkeypatch):
    """D of an even and an odd count of lane tiles (as 2,048 and 2,688
    are), bf16 and float32 rows, a chunk of one row block and of three.
    Rows past `n_rows` hold NaN and must not land; a token no row goes to
    keeps its old value to the bit, in a band that is walked and in one
    that is not."""
    rng = np.random.default_rng(sum(map(ord, case)) + b + d)
    tok, n_rows, kept = _case(case, b, rng)
    y = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    rows = np.asarray(rng.standard_normal((b, d)), np.float32)
    rows[n_rows:] = np.nan
    rows, tok = jnp.asarray(rows, dtype), jnp.asarray(tok, jnp.int32)
    want = jax.jit(moe_lib._add_rows)(y, rows, tok, n_rows)
    got = _kernel_add(monkeypatch, y, rows, tok, n_rows)
    assert np.isfinite(np.asarray(got)).all()
    # a token's at most k rows summed in another order: a few units in
    # float32's last place; hundreds of rows to one token, of a sum of tens
    atol = 1e-4 if case == "all-to-one" else 4e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=atol)
    np.testing.assert_array_equal(np.asarray(got)[kept], np.asarray(y)[kept])
    if n_rows:
        assert not np.array_equal(np.asarray(got), np.asarray(y))


def test_float32_rows_are_not_rounded_to_bf16():
    """One row to one token of zeros: the sum is the row, every bit of it."""
    rows = jnp.zeros((256, 128), jnp.float32).at[0].set(jnp.float32(1) / 3)
    tok = jnp.zeros((256,), jnp.int32).at[0].set(7)
    got = segment_add.add_sorted_rows(jnp.zeros((128, 128), jnp.float32), rows,
                                      jnp.sort(tok), 1, interpret=True)
    want = np.zeros((128, 128), np.float32)
    want[0] = np.float32(1) / 3  # tok sorted: the row's token is 0
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n_rows,bands,blocks", [
    (0, [], []), (1, [0], [0]), (256, [0, 1], [0, 0]), (300, [0, 1, 1], [0, 0, 1]),
    (450, [0, 1, 1, 3], [0, 0, 1, 1]), (512, [0, 1, 1, 3], [0, 0, 1, 1])])
def test_the_walk_is_one_step_a_band_a_block_holds(n_rows, bands, blocks):
    """Rows 0-199 in band 0, 200-399 in band 1, 400-511 in band 3: a
    block is walked in the bands of its first `n_rows` rows alone, band 2
    never, and FIRST marks a band's first step."""
    tok = jnp.asarray(np.r_[np.full(200, 100), np.full(200, 130), np.full(112, 500)], jnp.int32)
    band, block, flags, n = segment_add._walk(tok, n_rows, 4)
    assert band.shape == (4 + 2,) and int(n) == len(bands)
    assert np.asarray(band)[:int(n)].tolist() == bands
    assert np.asarray(block)[:int(n)].tolist() == blocks
    first = [i == 0 or bands[i] != bands[i - 1] for i in range(len(bands))]
    assert (np.asarray(flags)[:int(n)] == segment_add.FIRST * np.asarray(first)).all()


def test_toy_tiles_fall_to_the_scatter_add_by_shape(monkeypatch):
    """The model tests patch `_HELD_ROW_TILE` / `_HELD_CHUNK_ROWS` to toy
    sizes at toy widths: shapes the kernel does not take, so `_add_rows`
    is the scatter-add there on the chip too (off the chip it always is)."""
    assert not segment_add.kernel_ok(40, 16, 16) and not segment_add.kernel_ok(256, 32, 64)
    assert segment_add.kernel_ok(16384, 2048, 8192) and segment_add.kernel_ok(16384, 2688, 256)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    traced = str(jax.make_jaxpr(lambda *args: moe_lib._add_rows(*args))(
        jnp.zeros((40, 16)), jnp.zeros((16, 16)), jnp.zeros((16,), jnp.int32), 8))
    assert "scatter" in traced and "pallas_call" not in traced
