"""The gated short convolution (`ops/ssm.gated_conv`, `gated_conv_mixer`:
LFM2's mixer, `C * conv(B * x)` over three taps without an activation)
against a loop over positions, values and every gradient; a packed row
against each of its sequences alone; a band with its `tail` against the
whole row; and `causal_conv`'s older callers, which pass no `act`, to the
bit. CPU, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import ConvConfig
from areal_tpu.ops import band_loop
from areal_tpu.ops.ssm import (
    ConvCarry,
    causal_conv,
    conv_start_carry,
    gated_conv,
    gated_conv_mixer,
    init_conv_params,
)

D = 8


def _seg(lens, T):
    seg = np.zeros((1, T), np.int32)
    o = 0
    for j, n in enumerate(lens):
        seg[0, o:o + n] = j + 1
        o += n
    return seg


def _loop(bcx, w, b, seg):
    """`C * (b + sum_j w[K-1-j] (B * x)_{t-j})`, a position and a lag at a
    time, float64; a lag reaches no position of another sequence; 0 at
    padding cells."""
    bcx, w = np.asarray(bcx, np.float64), np.asarray(w, np.float64)
    K, d = w.shape
    out = np.zeros(bcx.shape[:2] + (d,))
    for r in range(bcx.shape[0]):
        B, C, x = bcx[r, :, :d], bcx[r, :, d:2 * d], bcx[r, :, 2 * d:]
        for t in range(bcx.shape[1]):
            if seg[r, t] == 0:
                continue
            z = np.zeros(d) if b is None else np.asarray(b, np.float64).copy()
            for j in range(K):
                if t - j >= 0 and seg[r, t - j] == seg[r, t]:
                    z += w[K - 1 - j] * B[t - j] * x[t - j]
            out[r, t] = C[t] * z
    return out


def _draw(T, K, bias, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k1, (1, T, 3 * D)), jax.random.normal(k2, (K, D)) / np.sqrt(K),
            0.3 * jax.random.normal(k3, (D,)) if bias else None,
            jax.random.normal(k4, (1, T, D)))


CASES = {
    "one_sequence": ([24], 24),
    "three_sequences": ([9, 1, 14], 24),
    "padding_after": ([7, 6], 24),
    "two_cells": ([2, 1, 2], 8),
    "all_padding": ([], 8),
}


@pytest.mark.parametrize("K,bias", [(3, False), (4, True), (2, False)], ids=["k3", "k4_bias", "k2"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gated_convolution_is_a_loop_over_positions(case, K, bias):
    """Values against the loop, and the gradients of a weighted sum to
    `[B | C | x]`, the taps and the bias against the loop's closed form:
    `dC = dy z`, `dz = dy C`, `d(Bx)_s = sum_j w[K-1-j] dz_{s+j}` within a
    sequence, `dB = d(Bx) x`, `dx = d(Bx) B`, `dw[K-1-j] = sum_t dz_t
    (Bx)_{t-j}`."""
    lens, T = CASES[case]
    seg = _seg(lens, T)
    bcx, w, b, dy = _draw(T, K, bias)
    fn = lambda bcx, w, b: gated_conv(bcx, w, b, jnp.asarray(seg))[0]
    got = fn(bcx, w, b)
    np.testing.assert_allclose(np.asarray(got), _loop(bcx, w, b, seg), atol=1e-5)
    grads = jax.grad(lambda *a: (fn(*a) * dy).sum(), (0, 1, 2) if bias else (0, 1))(bcx, w, b)
    # the closed form, position by position
    x64, w64, dy64 = (np.asarray(a, np.float64) for a in (bcx, w, dy))
    B, C, x = x64[0, :, :D], x64[0, :, D:2 * D], x64[0, :, 2 * D:]
    bx = B * x
    real = seg[0] > 0
    z = np.where(real[:, None], _loop(
        np.concatenate([B, np.ones_like(C), x], -1)[None], w, b, seg)[0], 0.0)
    dz = np.where(real[:, None], dy64[0] * C, 0.0)
    dbx, dw = np.zeros_like(bx), np.zeros_like(w64)
    for t in range(T):
        for j in range(K):
            if real[t] and t - j >= 0 and seg[0, t - j] == seg[0, t]:
                dbx[t - j] += w64[K - 1 - j] * dz[t]
                dw[K - 1 - j] += dz[t] * bx[t - j]
    want = np.concatenate([dbx * x, np.where(real[:, None], dy64[0] * z, 0.0), dbx * B], -1)
    np.testing.assert_allclose(np.asarray(grads[0][0]), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads[1]), dw, atol=1e-4)
    if bias:
        np.testing.assert_allclose(np.asarray(grads[2]), dz.sum(0), atol=1e-4)


def _mixer_params(seed=0, kernel=3, bias=False):
    dense = lambda key, shape, scale=None: jax.random.normal(key, shape) * (
        scale if scale is not None else shape[-2] ** -0.5)
    cp = init_conv_params(ConvConfig(kernel, bias), D, dense, jax.random.PRNGKey(seed), 1,
                          jnp.float32)
    cp = jax.tree_util.tree_map(lambda a: a[0], cp)
    if bias:
        cp["conv_b"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (D,))
    return cp


def test_the_mixers_parameters_are_two_projections_and_the_taps():
    cp = _mixer_params(bias=True)
    assert {k: v.shape for k, v in cp.items()} == {
        "in_proj": (D, 3 * D), "conv_w": (3, D), "out_proj": (D, D), "conv_b": (D,)}
    assert set(_mixer_params()) == {"in_proj", "conv_w", "out_proj"}
    with pytest.raises(ValueError, match="taps"):
        ConvConfig(kernel=1)


@pytest.mark.parametrize("lens", [(11, 9), (5, 1, 13, 2)], ids=["two", "four"])
def test_a_packed_row_is_each_of_its_sequences_alone(lens):
    """The mixer over sequences packed in one row (padding after them)
    against each in a row of its own: values, and the gradients to the input
    and to every parameter."""
    T = sum(lens) + 4
    cp = _mixer_params(2)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, T, D))
    dy = jax.random.normal(jax.random.PRNGKey(4), (1, T, D))
    seg = jnp.asarray(_seg(lens, T))
    packed = lambda u, cp: gated_conv_mixer(None, u, cp, seg, jnp.float32)[0]

    def alone(u, cp):
        out, o = [], 0
        for n in lens:
            one = jnp.ones((1, n), jnp.int32)
            out.append(gated_conv_mixer(None, u[:, o:o + n], cp, one, jnp.float32)[0])
            o += n
        return jnp.concatenate(out + [jnp.zeros((1, T - o, D))], axis=1)

    np.testing.assert_allclose(np.asarray(packed(u, cp)), np.asarray(alone(u, cp)), atol=1e-5)
    assert not np.asarray(packed(u, cp))[0, sum(lens):].any()  # 0 at padding cells
    g = [jax.grad(lambda u, cp: (f(u, cp) * dy).sum(), (0, 1))(u, cp) for f in (packed, alone)]
    for a, b in zip(jax.tree_util.tree_leaves(g[0]), jax.tree_util.tree_leaves(g[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("band,lens", [
    (8, (13, 11)),  # a sequence across a boundary, one that starts on a band's second cell
    (8, (8, 8, 7)),  # sequences that start on a band's first cell
    (4, (5, 1, 9)),  # a band of a sequence's last cell and a whole one-cell sequence
    (16, (30,)),  # padding inside the last band
], ids=["across", "on_a_boundary", "short_bands", "padded_band"])
def test_a_band_with_its_tail_is_the_whole_row(band, lens):
    """Band after band from `conv_start_carry`, each handed the last two
    gated inputs and their segment ids of the one before, against the mixer
    over the whole row: values and every gradient (the carry's cotangent
    flows back through the bands)."""
    T = -(-sum(lens) // band) * band
    cp = _mixer_params(5)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, T, D))
    dy = jax.random.normal(jax.random.PRNGKey(7), (1, T, D))
    seg = jnp.asarray(_seg(lens, T))
    whole = lambda u, cp: gated_conv_mixer(None, u, cp, seg, jnp.float32)[0]

    def banded(u, cp):
        carry, out = conv_start_carry(ConvConfig(), 1, D, jnp.float32), []
        for i in range(T // band):
            cut = slice(i * band, (i + 1) * band)
            y, carry = gated_conv_mixer(carry, u[:, cut], cp, seg[:, cut], jnp.float32)
            assert isinstance(carry, ConvCarry) and carry.bx.shape == (1, 2, D)
            out.append(y)
        return jnp.concatenate(out, axis=1)

    np.testing.assert_allclose(np.asarray(banded(u, cp)), np.asarray(whole(u, cp)), atol=1e-6)
    g = [jax.grad(lambda u, cp: (f(u, cp) * dy).sum(), (0, 1))(u, cp) for f in (banded, whole)]
    for a, b in zip(jax.tree_util.tree_leaves(g[0]), jax.tree_util.tree_leaves(g[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_the_carried_loop_hands_the_tail_from_band_to_band(monkeypatch):
    """The same through `ops/band_loop.carried`, as the stack runs it: the
    live bands alone, zeros past them, and the gradients of the whole row."""
    monkeypatch.setattr(band_loop, "_BAND", 8)
    jax.clear_caches()
    T, lens = 40, (13, 11)  # three live bands of five
    cp = _mixer_params(8)
    u = jax.random.normal(jax.random.PRNGKey(9), (1, T, D))
    dy = jax.random.normal(jax.random.PRNGKey(10), (1, T, D))
    seg = jnp.asarray(_seg(lens, T))
    assert int(band_loop.live_bands(seg)) == 3

    def step(static, w, xs, side, carry):
        y, carry = gated_conv_mixer(carry, xs[0], w, side[0], jnp.float32)
        return (y,), carry

    looped = lambda u, cp: band_loop.carried(
        step, None, cp, (u,), (seg,), conv_start_carry(ConvConfig(), 1, D, jnp.float32),
        band_loop.live_bands(seg))[0]
    whole = lambda u, cp: gated_conv_mixer(None, u, cp, seg, jnp.float32)[0]
    np.testing.assert_allclose(np.asarray(looped(u, cp)), np.asarray(whole(u, cp)), atol=1e-6)
    g = [jax.grad(lambda u, cp: (f(u, cp) * dy).sum(), (0, 1))(u, cp) for f in (looped, whole)]
    for a, b in zip(jax.tree_util.tree_leaves(g[0]), jax.tree_util.tree_leaves(g[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    jax.clear_caches()


def test_what_padding_cells_hold_reaches_no_token():
    """NaN in the padding cells of the input: every token's result and the
    gradient at every token's cell are finite and what zeros there give."""
    lens, T = (9, 6), 24
    cp = _mixer_params(11)
    seg = jnp.asarray(_seg(lens, T))
    u = jax.random.normal(jax.random.PRNGKey(12), (1, T, D))
    dirty = jnp.where((seg > 0)[..., None], u, jnp.nan)
    f = lambda u: gated_conv_mixer(None, u, cp, seg, jnp.float32)[0]
    np.testing.assert_array_equal(np.asarray(f(dirty))[0, :15], np.asarray(f(u))[0, :15])
    assert not np.asarray(f(dirty))[0, 15:].any()
    g = lambda u: jax.grad(lambda u: f(u)[0, :15].sum())(u)
    np.testing.assert_array_equal(np.asarray(g(dirty))[0, :15], np.asarray(g(u))[0, :15])


def _parents_causal_conv(xbc, w, b, segment_ids, tail=None):
    """`causal_conv` as the parent commit had it: the silu unconditional."""
    K, T = w.shape[0], xbc.shape[1]
    if tail is None:
        before = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        seg_before = jnp.pad(segment_ids, ((0, 0), (K - 1, 0)))
    else:
        before = jnp.concatenate([tail[0], xbc], axis=1)
        seg_before = jnp.concatenate([tail[1], segment_ids], axis=1)
    acc = xbc * w[K - 1]
    for lag in range(1, K):
        lo = K - 1 - lag
        same = seg_before[:, lo: lo + T] == segment_ids
        acc = acc + jnp.where(same[..., None], before[:, lo: lo + T], 0) * w[lo]
    if b is not None:
        acc = acc + b
    return jnp.where((segment_ids > 0)[..., None], jax.nn.silu(acc), 0)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("tail", [True, False], ids=["tail", "whole"])
def test_causal_convs_older_callers_get_the_parents_program_to_the_bit(bias, tail):
    """A caller that passes no `act` (the Mamba-2 mixer, the delta rules'
    taps) traces what the parent's function traced, forward and backward, and
    so reads its results to the bit."""
    T, K, C = 24, 4, 12
    k = jax.random.split(jax.random.PRNGKey(13), 4)
    x, w = jax.random.normal(k[0], (2, T, C)), jax.random.normal(k[1], (K, C))
    b = jax.random.normal(k[2], (C,)) if bias else None
    seg = jnp.asarray(np.concatenate([_seg((9, 11), T), _seg((24,), T)]))
    before = (jax.random.normal(k[3], (2, K - 1, C)),
              jnp.asarray([[3, 1, 1], [0, 1, 1]], jnp.int32)) if tail else None
    loss = lambda fn: lambda x, w: (fn(x, w, b, seg, before) ** 2).sum()
    new, old = (jax.make_jaxpr(jax.value_and_grad(loss(fn), (0, 1)))(x, w)
                for fn in (causal_conv, _parents_causal_conv))
    assert str(new) == str(old)
    np.testing.assert_array_equal(np.asarray(causal_conv(x, w, b, seg, before)),
                                  np.asarray(_parents_causal_conv(x, w, b, seg, before)))
    bare = causal_conv(x, w, b, seg, before, act=None)  # the sum itself
    np.testing.assert_allclose(
        np.asarray(jax.nn.silu(bare) * (seg > 0)[..., None]),
        np.asarray(causal_conv(x, w, b, seg, before)), atol=1e-6)


KERNEL_CASES = {
    "three_sequences": (256, 128, 3, (100, 27, 60)),  # padding after, a dead last block
    "starts_on_blocks": (384, 256, 3, (128, 129, 127)),
    "one_sequence_four_taps": (256, 128, 4, (256,)),
    "all_padding": (256, 128, 3, ()),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_pair_is_the_plain_form(case):
    """`ops/pallas/conv_gate.py` in interpret mode against the plain form over
    a whole row: values, the gradients to `[B | C | x]` and to the taps, and
    NaN in the padding cells reaching neither."""
    from areal_tpu.ops.pallas import conv_gate
    from areal_tpu.ops.ssm import conv_in_kernel

    T, d, K, lens = KERNEL_CASES[case]
    k = jax.random.split(jax.random.PRNGKey(14), 3)
    bcx, w = jax.random.normal(k[0], (1, T, 3 * d)), jax.random.normal(k[1], (K, d))
    dy = jax.random.normal(k[2], (1, T, d))
    seg = jnp.asarray(_seg(lens, T))
    assert conv_in_kernel(T, d, K, False, False, True) and conv_gate.fits(T, d, K)
    plain = lambda bcx, w: gated_conv(bcx, w, None, seg, kernel=False)[0]
    kern = lambda bcx, w: gated_conv(bcx, w, None, seg, kernel=True)[0]
    np.testing.assert_allclose(np.asarray(kern(bcx, w)), np.asarray(plain(bcx, w)), atol=1e-5)
    g = [jax.grad(lambda bcx, w: (f(bcx, w) * dy).sum(), (0, 1))(bcx, w) for f in (kern, plain)]
    np.testing.assert_allclose(np.asarray(g[0][0]), np.asarray(g[1][0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g[0][1]), np.asarray(g[1][1]), rtol=1e-5, atol=1e-4)
    dirty = jnp.where((seg > 0)[..., None], bcx, jnp.nan)
    np.testing.assert_array_equal(np.asarray(kern(dirty, w)), np.asarray(kern(bcx, w)))
    got = jax.grad(lambda bcx: (kern(bcx, w) * dy).sum())(dirty)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(g[0][0]))
    # the last cells' gated input, what a band after would be handed, is the plain form's
    np.testing.assert_array_equal(
        np.asarray(gated_conv(bcx, w, None, seg, kernel=True)[1]),
        np.asarray(gated_conv(bcx, w, None, seg, kernel=False)[1]))


def test_where_the_kernels_are_not_taken():
    """Off the chip, on a mesh, under a bias, for a band with its tail, at a
    row no block divides or a width no lane tile does: the plain form."""
    from areal_tpu.ops.ssm import conv_in_kernel

    assert not conv_in_kernel(8192, 2048, 3, False, False)  # the CPU
    assert conv_in_kernel(8192, 2048, 3, False, False, True)
    assert not conv_in_kernel(8192, 2048, 3, False, False, False)
    assert not conv_in_kernel(8192, 2048, 3, True, False, True)
    assert not conv_in_kernel(1024, 2048, 3, False, True, True)
    assert not conv_in_kernel(8192 + 64, 2048, 3, False, False, True)
    assert not conv_in_kernel(8192, 2048 + 64, 3, False, False, True)
