"""The indexer's threshold and kernels (`ops/indexer.py`,
`ops/pallas/index_kernels.py`, the mask operand of
`ops/pallas/splash_pairs.py`): the threshold against a sort on
adversarial rows, in the plain form and in `index_select`; the pair
kernels under a mask operand against the einsum reference under the same
mask; a long row alone through all the kernels against the plain form,
values and gradients. Interpret mode, float32, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import indexer as ix
from areal_tpu.ops.attention import (
    _pair_lists, reference_packed_attention, segment_causal_mask,
)

from tests.model.test_indexed_stack import _row


# ---------------------------------------------------------------------------
# The threshold, against a sort
# ---------------------------------------------------------------------------


def _brute_choice(scores, seg, pos, top_k):
    """numpy: the topk-th largest by a sort, ties at it all kept."""
    scores, seg, pos = (np.asarray(a) for a in (scores, seg, pos))
    t = len(seg)
    out = np.zeros((t, t), bool)
    for i in range(t):
        keys = np.flatnonzero((seg == seg[i]) & (pos <= pos[i]) & (np.arange(t) <= i))
        if len(keys) <= top_k:
            out[i, keys] = True
            continue
        tau = np.sort(scores[i, keys])[::-1][top_k - 1]
        out[i, keys[scores[i, keys] >= tau]] = True
    return out


def _adversarial(case, t, heads, d, rng):
    """(iq [t, H, d], ik [t, d], iw [t, H]) whose scores stress the
    threshold."""
    iq = rng.normal(size=(t, heads, d)).astype(np.float32)
    ik = rng.normal(size=(t, d)).astype(np.float32)
    iw = rng.normal(size=(t, heads)).astype(np.float32)
    if case == "equal":  # one key for all: every score of a query is the same
        ik[:] = ik[0]
    elif case == "negative":  # every score <= 0, many exact zeros
        iw = -np.abs(iw)
    elif case == "zeros":  # the relu leaves most cells an exact 0, of either sign's weight
        iq, ik = np.abs(iq), -np.abs(ik)
        ik[::7] *= -1
    elif case == "coarse":  # few distinct values: ties at the threshold
        iq, ik, iw = np.round(iq), np.round(ik), np.round(iw)
    return tuple(jnp.asarray(a) for a in (iq, ik, iw))


@pytest.mark.parametrize("case", ["random", "equal", "negative", "zeros", "coarse"])
def test_the_threshold_is_a_sorts_on_adversarial_rows(case):
    """`choose` (the plain form) and `index_select` (the kernel's 32
    halvings over the int32 image, interpret mode) against a numpy sort:
    equal scores, all-negative scores, exact zeros from the relu (+0 and
    -0 alike), ties, sequences of exactly topk keys, of one key, padding."""
    from areal_tpu.ops.pallas import index_kernels as ik_

    top_k, t = 16, 256
    lens = [16, 1, 120, 17, 60]  # exactly topk; one; long; topk + 1
    _, seg, pos = _row(lens, t)
    seg, pos = seg[0], pos[0]
    iq, ik, iw = _adversarial(case, t, 2, 8, np.random.default_rng(3))
    scores = ix.index_scores(iq, ik, iw)
    assert not np.signbit(np.asarray(scores)[np.asarray(scores) == 0]).any()
    valid = segment_causal_mask(seg, seg, pos, pos)
    want = _brute_choice(scores, seg, pos, top_k) & np.asarray(valid)
    got, tau = ix.choose(scores, valid, top_k)
    np.testing.assert_array_equal(np.asarray(got), want)
    if case in ("equal", "coarse", "zeros"):
        assert (want.sum(-1) > top_k).any()  # ties lift a query's count above topk
    lo, hi = ix._select_range(seg, 128, 128)
    mask, stats = ik_.index_select(
        iq.transpose(1, 0, 2), ik, iw, seg, lo, hi, top_k=top_k, chunk=128,
        interpret=True, rows=128)
    chosen = np.asarray(mask).transpose(1, 0, 2).reshape(t, t) != 0
    real = np.asarray(seg) > 0
    np.testing.assert_array_equal(chosen[real], want[real])
    np.testing.assert_array_equal(np.asarray(stats[:, ik_.COUNT])[real], want[real].sum(-1))
    # (the kernel sums a score's heads in another order: a rounding apart)
    np.testing.assert_allclose(np.asarray(stats[:, ik_.TAU])[real], np.asarray(tau)[real],
                               rtol=1e-4, atol=1e-6)
    held = np.where(want, np.asarray(scores), -np.inf)
    np.testing.assert_allclose(np.asarray(stats[:, ik_.LSE])[real],
                               jax.scipy.special.logsumexp(held, axis=-1)[real], rtol=1e-5)


# ---------------------------------------------------------------------------
# The kernels, interpret mode
# ---------------------------------------------------------------------------


def test_the_pair_kernels_under_a_mask_are_the_einsum_reference_under_it():
    """`pair_attention_chosen` forward, dq and dkv with a mask operand
    against `reference_packed_attention(chosen=)`: output, logsumexp and
    gradients, a group of 2, several sequences and padding."""
    from areal_tpu.ops.pallas.splash_pairs import (
        Blocks, pair_attention_chosen, transpose_mask,
    )

    t, hq, hkv, hd, blocks = 512, 4, 2, 64, Blocks(128, 256, 128)
    _, seg, pos = _row([200, 130, 100], t)
    seg, pos = seg[0], pos[0]
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(kk, (t, h, hd)) for kk, h in zip(ks, (hq, hkv, hkv)))
    valid = segment_causal_mask(seg, seg, pos, pos)
    # a random choice that always keeps a query's own place
    chosen = valid & ((jax.random.uniform(ks[3], (t, t)) < 0.3) | jnp.eye(t, dtype=bool))
    mask = chosen.astype(jnp.int8).reshape(t, t // 128, 128).transpose(1, 0, 2)
    w = jax.random.normal(ks[4], (t, hq, hd))
    real = (seg > 0)[:, None, None]

    def kernels(q, k, v):
        out, lse = pair_attention_chosen(
            (q * hd ** -0.5).transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            seg, _pair_lists(seg, blocks.bq, blocks.bkv, None), mask,
            transpose_mask(mask, blocks.bq), blocks, "x", True)
        return out.transpose(1, 0, 2), lse

    def plain(q, k, v):
        return reference_packed_attention(q, k, v, seg, pos, chosen=chosen)

    out, lse = kernels(q, k, v)
    np.testing.assert_allclose(np.where(real, out, 0), np.where(real, plain(q, k, v), 0),
                               atol=2e-5)
    qk = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, axis=1)) * hd ** -0.5
    want_lse = jax.scipy.special.logsumexp(jnp.where(chosen[None], qk, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse)[:, np.asarray(seg) > 0],
                               np.asarray(want_lse)[:, np.asarray(seg) > 0], rtol=1e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.where(real, fn(*a) * w, 0.0))
    got = jax.grad(loss(lambda *a: kernels(*a)[0]), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_a_long_row_alones_kernels_are_the_plain_form():
    """`indexed_attention` under "splash" for one row of 2,048 (the
    indexer's two kernels and the pair kernels under its mask, interpret
    mode) against the plain form: output, the three sums and every
    gradient, the indexer's through the KL."""
    t, hq, hkv, hd, hi, d, top_k = 2048, 4, 2, 64, 2, 16, 96
    _, seg, pos = _row([1024, 549, 100], t)
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    n = lambda kk, *s, scale=1.0: jax.random.normal(kk, s) * scale
    x = (n(ks[0], 1, t, hq, hd), n(ks[1], 1, t, hkv, hd), n(ks[2], 1, t, hkv, hd),
         n(ks[3], 1, t, hi, d), n(ks[4], 1, t, d), n(ks[5], 1, t, hi, scale=0.2))
    w = n(ks[6], 1, t, hq, hd)
    real = (seg > 0)[..., None, None]

    def loss(impl, *x):
        out, sums = ix.indexed_attention(*x, seg, pos, top_k, impl, True, interpret=True)
        return jnp.sum(jnp.where(real, out * w, 0.0)) + sums["index_kl"], (out, sums)

    (_, (out_p, sums_p)), g_p = jax.value_and_grad(
        lambda *x: loss("reference", *x), argnums=range(6), has_aux=True)(*x)
    (_, (out_k, sums_k)), g_k = jax.value_and_grad(
        lambda *x: loss("splash", *x), argnums=range(6), has_aux=True)(*x)
    np.testing.assert_allclose(np.where(real, out_k, 0), np.where(real, out_p, 0), atol=2e-5)
    assert float(sums_k["index_chosen"]) == float(sums_p["index_chosen"])
    assert float(sums_k["index_cells"]) == float(sums_p["index_cells"])
    np.testing.assert_allclose(sums_k["index_kl"], sums_p["index_kl"], rtol=2e-5)
    keep = np.asarray(seg[0] > 0)
    for a, b in zip(g_k, g_p):
        a, b = np.asarray(a)[0][keep], np.asarray(b)[0][keep]
        np.testing.assert_allclose(a, b, atol=3e-5 * np.abs(b).max())
