"""Pallas flash attention vs the dense reference oracle (forward + grads).

Runs the kernel in interpreter mode on the CPU test platform; the same
code path compiles on TPU (dispatched by areal_tpu/ops/attention.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops.attention import reference_packed_attention
from areal_tpu.ops.pallas.flash_attn import flash_packed_attention


def make_packed(T, n_seqs, hq, hkv, hd, seed=0):
    rng = np.random.RandomState(seed)
    # Random cut points -> n_seqs contiguous segments + tail padding.
    cuts = np.sort(rng.choice(np.arange(1, T - 1), size=n_seqs - 1, replace=False))
    bounds = [0, *cuts.tolist(), T - rng.randint(0, T // 8)]
    seg = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    for s in range(n_seqs):
        lo, hi = bounds[s], bounds[s + 1]
        seg[lo:hi] = s + 1
        pos[lo:hi] = np.arange(hi - lo)
    q = rng.randn(T, hq, hd).astype(np.float32)
    k = rng.randn(T, hkv, hd).astype(np.float32)
    v = rng.randn(T, hkv, hd).astype(np.float32)
    return q, k, v, seg, pos


@pytest.mark.parametrize("hq,hkv,hd", [(4, 4, 64), (4, 2, 64), (8, 2, 32)])
def test_flash_forward_matches_reference(hq, hkv, hd):
    T = 256
    q, k, v, seg, pos = make_packed(T, n_seqs=3, hq=hq, hkv=hkv, hd=hd)
    ref = reference_packed_attention(q, k, v, seg, pos)
    got = flash_packed_attention(q, k, v, seg, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_padding_rows_zero():
    T = 128
    q, k, v, seg, pos = make_packed(T, n_seqs=2, hq=4, hkv=2, hd=32, seed=3)
    seg[100:] = 0  # force a padded tail
    got = np.asarray(flash_packed_attention(q, k, v, seg, pos, interpret=True))
    np.testing.assert_allclose(got[100:], 0.0, atol=1e-6)


def test_flash_grads_match_reference():
    T = 256
    q, k, v, seg, pos = make_packed(T, n_seqs=3, hq=4, hkv=2, hd=32, seed=7)
    dout = np.random.RandomState(9).randn(T, 4, 32).astype(np.float32)

    def loss_ref(q, k, v):
        return jnp.vdot(reference_packed_attention(q, k, v, seg, pos), dout)

    def loss_flash(q, k, v):
        return jnp.vdot(
            flash_packed_attention(q, k, v, seg, pos, interpret=True), dout
        )

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4, err_msg=name
        )


def test_flash_vmap_rows():
    # The model vmaps attention over packed rows; exercise the batching rule.
    R, T = 2, 128
    packs = [make_packed(T, 2, 4, 2, 32, seed=10 + r) for r in range(R)]
    q = np.stack([p[0] for p in packs])
    k = np.stack([p[1] for p in packs])
    v = np.stack([p[2] for p in packs])
    seg = np.stack([p[3] for p in packs])
    pos = np.stack([p[4] for p in packs])
    got = jax.vmap(
        lambda q1, k1, v1, s1, p1: flash_packed_attention(
            q1, k1, v1, s1, p1, interpret=True
        )
    )(q, k, v, seg, pos)
    for r in range(R):
        ref = reference_packed_attention(q[r], k[r], v[r], seg[r], pos[r])
        np.testing.assert_allclose(
            np.asarray(got[r]), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


# ---------------------------------------------------------------------------
# splash attention (jax's TPU kernel, auto-dispatched on TPU backends)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv,hd", [(4, 4, 64), (4, 2, 64), (8, 2, 32)])
def test_splash_forward_matches_reference(hq, hkv, hd):
    from areal_tpu.ops.attention import splash_packed_attention

    T = 256
    q, k, v, seg, pos = make_packed(T, 3, hq, hkv, hd, seed=11)
    ref = reference_packed_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(seg), jnp.asarray(pos),
    )
    got = splash_packed_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(seg), jnp.asarray(pos), interpret=True,
    )
    valid = seg > 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(ref)[valid], atol=2e-2, rtol=2e-2
    )


def test_splash_grads_match_reference():
    from areal_tpu.ops.attention import splash_packed_attention

    T, hq, hkv, hd = 256, 4, 2, 32
    q, k, v, seg, pos = make_packed(T, 2, hq, hkv, hd, seed=12)
    qj, kj, vj = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    segj, posj = jnp.asarray(seg), jnp.asarray(pos)
    rng = np.random.RandomState(0)
    dout = jnp.asarray(rng.randn(T, hq, hd).astype(np.float32))
    dout = dout * jnp.asarray((seg > 0)[:, None, None], jnp.float32)

    def loss_splash(q, k, v):
        return jnp.sum(
            splash_packed_attention(q, k, v, segj, posj, interpret=True) * dout
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_packed_attention(q, k, v, segj, posj) * dout)

    g1 = jax.grad(loss_splash, argnums=(0, 1, 2))(qj, kj, vj)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(qj, kj, vj)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2
        )


def test_splash_block_sizes_divide_odd_row_lengths():
    """Packed rows are padded to multiples of 128 (e.g. T=640, 1536);
    block-size selection must produce dividing blocks for all of them."""
    from areal_tpu.ops.attention import splash_packed_attention

    for T in (128, 384, 640, 896):
        q, k, v, seg, pos = make_packed(T, 2, 4, 2, 32, seed=13)
        out = splash_packed_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(seg), jnp.asarray(pos), interpret=True,
        )
        assert out.shape == (T, 4, 32)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="real-TPU compiled-kernel parity (CPU runs interpret mode above)",
)
def test_splash_compiled_matches_reference_on_tpu():
    from areal_tpu.ops.attention import splash_packed_attention

    T, hq, hkv, hd = 512, 4, 2, 64
    q, k, v, seg, pos = make_packed(T, 3, hq, hkv, hd, seed=21)
    qb = jnp.asarray(q, jnp.bfloat16)
    kb = jnp.asarray(k, jnp.bfloat16)
    vb = jnp.asarray(v, jnp.bfloat16)
    ref = reference_packed_attention(
        qb, kb, vb, jnp.asarray(seg), jnp.asarray(pos)
    )
    got = splash_packed_attention(
        qb, kb, vb, jnp.asarray(seg), jnp.asarray(pos), interpret=False
    )
    valid = seg > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[valid],
        np.asarray(ref, np.float32)[valid],
        atol=5e-2, rtol=5e-2,
    )
