"""Pallas flash attention vs the dense reference oracle (forward + grads).

Runs the kernel in interpreter mode on the CPU test platform; the same
code path compiles on TPU (dispatched by areal_tpu/ops/attention.py).
"""

import importlib.util
import json
import os

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


# ---------------------------------------------------------------------------
# the length and blocks splash runs a row at (splash_run_shape), and the pad
# ---------------------------------------------------------------------------

# Row lengths of the benchmark's pool (training and forward-only), every
# length up to 1024, and a few beyond the pool.
RUN_SHAPE_LENGTHS = [
    128, 256, 384, 512, 640, 768, 896, 1024, 1152, 1280, 1920, 2048, 2432,
    3072, 3200, 3584, 3712, 3840, 4480, 5504, 5760, 6144, 7296, 8192, 16384,
]


@pytest.mark.parametrize("t", RUN_SHAPE_LENGTHS)
def test_splash_run_shape_is_lane_aligned_divisible_and_never_dearer(t):
    from areal_tpu.ops import attention as A

    tq, tkv, tkvc = A._splash_block_targets()
    t_run, bq, bkv, bkvc = A.splash_run_shape(t)
    assert t <= t_run <= -(-t // 512) * 512 and t_run % 128 == 0
    for b, cap in ((bq, tq), (bkv, tkv), (bkvc, tkvc)):
        assert b % 128 == 0 and 128 <= b <= cap
    assert t_run % bq == 0 and t_run % bkv == 0 and bkv % bkvc == 0
    # Never priced above the row as it is at its largest dividing blocks,
    # and moved away from that only for more than the estimate's error.
    plain = A._plain_run_shape(t, tq, tkv, tkvc)
    got, was = A.splash_cost(t_run, bq, bkv, bkvc), A.splash_cost(*plain)
    assert got <= was
    assert (t_run, bq, bkv, bkvc) == plain or got <= 0.95 * was
    assert A.splash_run_shape(t) == (t_run, bq, bkv, bkvc)  # pure


@pytest.mark.parametrize("t", [128, 256, 384, 512, 768, 2048, 3072, 3840, 6144, 8192])
def test_splash_run_shape_keeps_lengths_whose_blocks_are_large(t):
    """The program is the parent's: the row as it is, largest dividing
    blocks. (Of the pool's lengths with large blocks, 5760 does move, to
    6144: its kv block of 640 leaves a compute block of 128, and the
    kernel alone measured 29 % faster at 6144; PERF.md section 6, PR 27.)"""
    from areal_tpu.ops import attention as A

    assert A.splash_run_shape(t) == A._plain_run_shape(
        t, *A._splash_block_targets())


@pytest.mark.parametrize("t", [5504, 3712, 4480, 3200])
def test_splash_run_shape_gives_the_pools_slow_lengths_large_blocks(t):
    """43, 29, 35 and 25 blocks of 128: no divisor above 128 for q."""
    from areal_tpu.ops.attention import splash_run_shape

    t_run, bq, bkv, _ = splash_run_shape(t)
    assert t_run > t and bq >= 384 and bkv >= 512


def _padded_rows(R, T, hq, hkv, hd, seed):
    """R packed rows; row 0's last sequence ends in old padding, row 1's
    runs to the row's end."""
    packs = [make_packed(T, 2, hq, hkv, hd, seed=seed + r) for r in range(R)]
    seg = np.stack([p[3] for p in packs])
    pos = np.stack([p[4] for p in packs])
    seg[0, T - 40:] = 0
    start = np.argmax(seg[1] == seg[1].max())
    seg[1, start:] = seg[1].max()
    pos[1, start:] = np.arange(T - start)
    return ([jnp.asarray(np.stack([p[i] for p in packs])) for i in range(3)]
            + [jnp.asarray(seg), jnp.asarray(pos)])


@pytest.mark.parametrize("run_shape", [
    (384, 384, 384, 384),  # as it is
    (512, 512, 512, 512),  # padded to one block
    (512, 256, 512, 256),  # padded, several blocks
    (768, 384, 768, 384),  # padded by a whole row's worth
])
def test_splash_padded_run_matches_reference_forward_and_grads(run_shape):
    """384 run at 512 and 768 under vmap over rows: the first T outputs
    and the gradients of q, k, v (exactly T long) are the reference's."""
    from areal_tpu.ops.attention import splash_packed_attention

    R, T, hq, hkv, hd = 2, 384, 4, 2, 32
    q, k, v, seg, pos = _padded_rows(R, T, hq, hkv, hd, seed=31)
    valid = jnp.asarray(np.asarray(seg) > 0, jnp.float32)[..., None, None]
    dout = jnp.asarray(
        np.random.RandomState(1).randn(R, T, hq, hd).astype(np.float32)) * valid

    def run(fn):
        def loss(q, k, v):
            out = jax.vmap(fn)(q, k, v, seg, pos)
            return jnp.sum(out * dout), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    got, g_got = run(lambda *a: splash_packed_attention(
        *a, interpret=True, _run_shape=run_shape))
    ref, g_ref = run(reference_packed_attention)
    assert got.shape == (R, T, hq, hd) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got * valid), np.asarray(ref * valid),
                               atol=2e-2, rtol=2e-2)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        assert a.shape == b.shape and a.shape[1] == T, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


@pytest.mark.parametrize("impl,t,want", [
    ("splash", 3712, "padded"), ("splash", 6144, 6144), ("splash", 384, 384),
    ("reference", 3712, 3712), ("auto", 3712, 3712),  # auto off the TPU: reference
])
def test_attn_run_len_follows_the_implementation_that_runs(impl, t, want):
    from areal_tpu.ops.attention import attn_run_len, splash_run_shape

    if want == "padded":
        want = splash_run_shape(t)[0]
        assert want > t
    assert attn_run_len(impl, t, 12, 2) == want


# The kernel alone on one v5e, 12 / 2 heads of 128, twelve chained layers:
# 229 run shapes of the benchmark pool's 19 row lengths
# (scripts/splash_shape_sweep.py, PR 27).
SWEEP = os.path.join(os.path.dirname(__file__), "data", "splash_sweep_v5e.jsonl")


def _sweep_module():
    spec = importlib.util.spec_from_file_location(
        "splash_shape_sweep",
        os.path.join(os.path.dirname(__file__), "..", "..", "scripts",
                     "splash_shape_sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_splash_cost_constants_are_what_the_recorded_sweep_fits():
    from areal_tpu.ops import attention as A

    coef, err, rows = _sweep_module().fit(SWEEP)
    assert len(rows) == 229
    np.testing.assert_allclose(A._SPLASH_NS, coef, rtol=0.02)
    assert np.median(np.abs(err)) < 0.07


def test_splash_run_shape_is_near_the_fastest_measured_shape():
    """On the recorded sweep: for every row length of 896 and above the
    shape picked is within 3 % of the fastest one measured, or is the
    row as it was (3840: the parent's program, 9.5 % off the fastest);
    never slower than the row as it was."""
    from areal_tpu.ops import attention as A

    mod = _sweep_module()
    ms = {}
    for r in map(json.loads, open(SWEEP)):
        ms.setdefault((r["rows"], r["t"]), {})[
            (r["t_run"], r["bq"], r["bkv"], r["bkvc"])] = r["fwd_ms"] + r["grad_ms"]
    assert len(ms) == 19
    for (rows, t), by_shape in ms.items():
        pick, was = A.splash_run_shape(t), mod.today(t)
        if t < 896:  # microseconds a layer: the timing's own noise is 10 %
            assert by_shape[pick] <= 1.10 * by_shape[was], (t, pick)
            continue
        assert by_shape[pick] <= by_shape[was], (t, pick)
        assert pick == was or by_shape[pick] <= 1.03 * min(by_shape.values()), (t, pick)
