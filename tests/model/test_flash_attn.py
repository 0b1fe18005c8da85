"""The training attention kernels (splash, the pair kernels) vs the dense
reference oracle (forward + grads).

Runs the kernels in interpreter mode on the CPU test platform; the same
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
@pytest.mark.parametrize("window", [None, 700], ids=["causal", "window"])
def test_splash_compiled_matches_reference_on_tpu(window):
    """Three sequences and a padded tail in a row of several block pairs:
    the block tables made from the segment ids at run time, compiled."""
    from areal_tpu.ops.attention import splash_packed_attention

    T, hq, hkv, hd = 2048, 4, 2, 64
    q, k, v, seg, pos = make_packed(T, 3, hq, hkv, hd, seed=21)
    seg[T - 600:] = 0
    qb = jnp.asarray(q, jnp.bfloat16)
    kb = jnp.asarray(k, jnp.bfloat16)
    vb = jnp.asarray(v, jnp.bfloat16)
    ref = reference_packed_attention(
        qb, kb, vb, jnp.asarray(seg), jnp.asarray(pos), window=window
    )
    got = splash_packed_attention(
        qb[None], kb[None], vb[None], jnp.asarray(seg)[None], jnp.asarray(pos)[None],
        interpret=False, window=window,
    )[0]
    assert np.isfinite(np.asarray(got, np.float32)).all()
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

    tq, tkv, tkvc = A.SPLASH_BLOCK_TARGETS
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
        t, *A.SPLASH_BLOCK_TARGETS)


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


# ---------------------------------------------------------------------------
# the block pairs the kernels run: the row's mask AND the row's segment ids
# ---------------------------------------------------------------------------


def _row(t, lens):
    """Sequences of `lens` one after another from the row's start,
    numbered from 1 as the packer does; the rest is padding."""
    seg, at = np.zeros(t, np.int32), 0
    for i, n in enumerate(lens):
        seg[at:at + n] = i + 1
        at += n
    return seg


LAYOUTS = {
    "three_and_a_padded_tail": _row(1024, [300, 150, 200]),
    "all_one_sequence": _row(1024, [1024]),
    "last_block_part_padding": _row(1024, [500, 470]),
    "many_short": _row(1024, [60, 70, 130, 10, 200, 128, 128, 40]),
    "all_padding": _row(1024, []),
    "one_token": _row(1024, [1]),
}


@pytest.mark.parametrize("window", [None, 200, 513], ids=["causal", "w200", "w513"])
@pytest.mark.parametrize("bq,bkv", [(128, 128), (128, 256), (256, 128), (512, 256)])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_live_block_pairs_keep_every_pair_with_an_unmasked_cell(layout, bq, bkv, window):
    """Against the dense mask, cell by cell: no block pair that holds an
    unmasked cell is dropped, every q block keeps a pair (its diagonal),
    and under a causal mask nothing else is kept; numpy ids (the host's
    count) and jax ids (the device's tables) give one answer."""
    from areal_tpu.ops import attention as A

    seg = LAYOUTS[layout]
    t = len(seg)
    at = np.arange(t)  # splash masks by place in the row, not by position
    dense = np.asarray(A.segment_causal_mask(seg, seg, at, at, window=window))
    dense &= (seg > 0)[:, None]
    holds = dense.reshape(t // bq, bq, t // bkv, bkv).any(axis=(1, 3))
    live = A.live_block_pairs(seg, bq, bkv)
    assert live.dtype == bool and live.shape == holds.shape
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda s: A.live_block_pairs(s, bq, bkv))(jnp.asarray(seg))), live)
    runs = live & A._static_block_pairs(t, bq, bkv, window)
    assert not (holds & ~runs).any()
    assert runs.any(axis=1).all()
    i, j = np.arange(t // bq)[:, None], np.arange(t // bkv)[None, :]
    diagonal = (j * bkv < (i + 1) * bq) & (i * bq < (j + 1) * bkv)
    if window is None:
        np.testing.assert_array_equal(runs, holds | diagonal)
    if layout == "all_one_sequence":  # the static mask, back from the rule itself
        np.testing.assert_array_equal(runs, A._static_block_pairs(t, bq, bkv, window))
    # rows at once, as the host counts them
    both = A.live_block_pairs(np.stack([seg, seg[::-1].copy()]), bq, bkv)
    np.testing.assert_array_equal(both[0], live)
    np.testing.assert_array_equal(both[1], A.live_block_pairs(seg[::-1].copy(), bq, bkv))


def _layout_rows(T, hq, hkv, hd):
    """Three packed rows of different layouts: three sequences and a tail
    of padding several blocks long; all one sequence; two sequences, the
    last block part padding."""
    segs = [_row(T, [T // 3 - 20, T // 8, T // 6 + 5]), _row(T, [T]),
            _row(T, [T // 2 - 7, T // 2 - 30])]
    rng = np.random.RandomState(41)
    q, k, v = (jnp.asarray(rng.randn(len(segs), T, h, hd).astype(np.float32))
               for h in (hq, hkv, hkv))
    seg = np.stack(segs)
    pos = np.zeros_like(seg)
    for r, row in enumerate(seg):
        for s in range(1, row.max() + 1):
            pos[r, row == s] = np.arange((row == s).sum())
    return q, k, v, jnp.asarray(seg), jnp.asarray(pos)


def _static_unfused(t, bq, bkv, bkvc, group, window):
    """jax's own splash kernel over its static tables with the dq and
    dkv kernels (not the fused backward): what a step of the run-time
    kernels computes, pair for pair."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    one = (sm.CausalMask((t, t)) if window is None else
           sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    return sk.make_splash_mqa_single_device(
        mask=sm.MultiHeadMask([one] * group), interpret=True,
        block_sizes=sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkvc, block_q_dkv=bq,
            block_kv_dkv=bkv, block_kv_dkv_compute=bkvc, block_q_dq=bq,
            block_kv_dq=bkv, use_fused_bwd_kernel=False))


def _static_unfused_attention(q, k, v, seg, run_shape, window):
    """One row [T, H, hd] through `_static_unfused`, as `_splash_row`
    lays it out."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk

    t, hq, hd = q.shape
    hkv = k.shape[1]
    kernel = _static_unfused(*run_shape, hq // hkv, window)
    assert run_shape[0] == t
    q = q * jnp.asarray(hd ** -0.5, q.dtype)
    qh = q.transpose(1, 0, 2).reshape(hkv, hq // hkv, t, hd)
    ids = sk.SegmentIds(q=seg, kv=seg)
    out = jax.vmap(lambda qq, kk, vv: kernel(qq, kk, vv, ids))(
        qh, k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    return out.reshape(hq, t, v.shape[-1]).transpose(1, 0, 2)


def _watch_pair_lists(A, monkeypatch):
    """The calls of `_pair_lists`, as they are made."""
    calls, keep = [], A._pair_lists
    monkeypatch.setattr(A, "_pair_lists", lambda *a: calls.append(a) or keep(*a))
    return calls


@pytest.mark.parametrize("rows", ["one_by_one", "vmap"])
@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window"])
@pytest.mark.parametrize("run_shape", [
    (768, 128, 128, 128), (768, 128, 256, 128), (768, 384, 128, 128)])
def test_splash_run_time_block_mask_is_the_static_kernel_at_real_positions(
        run_shape, window, rows, monkeypatch):
    """Rows of different layouts, each given alone as `[1, T, ..]` (one
    after another, or under a caller's `vmap`: a list a row, pallas's
    own loop over the kernel calls), walking the live pairs of their
    lists and no others: outputs at real positions equal to those of
    splash's static kernels (forward, dq, dkv), not to a tolerance, dq,
    dk and dv to float32's rounding (a kv block's q heads are summed
    pair by pair, not head by head, and dq is summed a sub-block at a
    time as `k^T ds`, where splash's dq kernel sums `ds k` over a whole
    kv block in one product); finite where the row is padding; and the
    reference's."""
    from areal_tpu.ops import attention as A

    R, T, hq, hkv, hd = 3, 768, 4, 2, 32
    q, k, v, seg, pos = _layout_rows(T, hq, hkv, hd)
    real = np.asarray(seg) > 0
    dout = jnp.asarray(np.random.RandomState(2).randn(R, T, hq, hd).astype(np.float32)
                       * real[..., None, None])

    def run(fn, rows="vmap"):
        def loss(q, k, v):
            if rows == "vmap":
                out = jax.vmap(fn)(q, k, v, seg, pos)
            else:
                out = jnp.stack([fn(q[r], k[r], v[r], seg[r], pos[r]) for r in range(R)])
            return jnp.sum(out * dout), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return np.asarray(out), [np.asarray(g) for g in grads]

    splash = lambda *a: A.splash_packed_attention(
        *(x[None] for x in a), interpret=True, _run_shape=run_shape, window=window)[0]
    monkeypatch.setattr(A, "_SKIP_MIN_LEN", 0)  # a row this short would not skip
    walked = _watch_pair_lists(A, monkeypatch)
    got, g_got = run(splash, rows)
    assert walked  # the run-time lists are what ran
    static, g_static = run(lambda q, k, v, seg, pos: _static_unfused_attention(
        q, k, v, seg, run_shape, window))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[real], static[real])
    for a, b, name in zip(g_got, g_static, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6, err_msg=name)
    ref, g_ref = run(lambda *a: A.reference_packed_attention(*a, window=window))
    np.testing.assert_allclose(got[real], ref[real], atol=2e-5, rtol=2e-5)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)


def test_only_a_long_row_alone_skips_by_its_segment_ids(monkeypatch):
    """Several rows in one call, a row given without its leading axis (it
    may be one of many under a caller's `vmap`) and a short row keep the
    static kernel: the parent's program."""
    from areal_tpu.ops import attention as A

    assert A._rows_skip(1, 16384) and A._rows_skip(1, 2048)
    assert not A._rows_skip(1, 1536) and not A._rows_skip(3, 6144)
    called = _watch_pair_lists(A, monkeypatch)
    q, k, v, seg, pos = _layout_rows(768, 4, 2, 32)
    run = lambda *a: A.splash_packed_attention(*a, interpret=True)
    short = run(q[:1], k[:1], v[:1], seg[:1], pos[:1])
    assert not called
    monkeypatch.setattr(A, "_SKIP_MIN_LEN", 512)
    rows = run(q, k, v, seg, pos)
    alone = run(q[0], k[0], v[0], seg[0], pos[0])
    assert not called
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(short[0]))
    one = run(q[:1], k[:1], v[:1], seg[:1], pos[:1])
    assert len(called) == 1
    real = np.asarray(seg[0]) > 0
    np.testing.assert_array_equal(np.asarray(one[0])[real], np.asarray(rows[0])[real])


def _check_pair_list(lst, n, pairs, major):
    """`lst` walks just `pairs` [nq, nkv], each once, `major`-major in
    ascending order, its flags right (NEW at a minor block's first step
    of the walk and DONE at its last, NEXT where the next step's minor
    block is the same), and past `n` nothing new."""
    from areal_tpu.ops.pallas.splash_pairs import DONE, FIRST, LAST, NEW, NEXT

    qi, ki, flags = (np.asarray(a) for a in lst)
    assert qi.dtype == ki.dtype == flags.dtype == np.int32
    assert n == pairs.sum() and qi.shape == ki.shape == flags.shape and n <= len(qi)
    want = np.argwhere(pairs if major == "q" else pairs.T)  # row-major: ascending
    want = want if major == "q" else want[:, ::-1]
    np.testing.assert_array_equal(np.stack([qi[:n], ki[:n]], 1), want)
    assert (qi[n:] == qi[n - 1]).all() and (ki[n:] == ki[n - 1]).all()  # in bounds
    run = (qi if major == "q" else ki)[:n]
    edge = np.flatnonzero(np.diff(run)) + 1
    first = np.zeros(n, bool)
    first[np.r_[0, edge]] = True
    last = np.zeros(n, bool)
    last[np.r_[edge - 1, n - 1]] = True
    minor = (ki if major == "q" else qi)[:n]
    new = np.zeros(n, bool)
    new[np.unique(minor, return_index=True)[1]] = True
    done = np.zeros(n, bool)
    done[n - 1 - np.unique(minor[::-1], return_index=True)[1]] = True
    again = np.r_[minor[1:] == minor[:-1], False]
    np.testing.assert_array_equal(
        flags[:n], FIRST * first + LAST * last + NEW * new + NEXT * again + DONE * done)


@pytest.mark.parametrize("window", [None, 2048], ids=["causal", "window"])
@pytest.mark.parametrize("t,lens", [
    (2048, [700, 500, 300]), (3712, [1500, 900, 1000]), (8192, [3000, 900]),
    (8192, [8192]), (16384, [9000]), (16384, [1100] * 14), (16384, [3000, 5000, 4000])])
def test_host_counts_are_the_device_block_tables(t, lens, window):
    """`attn_block_cells` and `attn_grid_steps` (the engine's
    `train.attn_active_cells`, `train.attn_grid_steps`,
    `train.attn_live_steps` and the span's `width`) count on the host
    what the kernels walk on the device: the lists `_pair_lists` makes,
    `n` steps in the forward kernel and `n` in the one backward kernel
    (`train.attn_bwd_steps`), every one a pair that runs; and the lists name just the pairs the static mask
    and the row's sequences leave, each once, in order, their capacity
    the static mask's pairs."""
    from areal_tpu.ops import attention as A

    seg = _row(t, lens)
    t_run, bq, bkv, bkvc = A.splash_run_shape(t)
    nq, nkv = t_run // bq, t_run // bkv
    assert A._rows_skip(1, t_run)
    padded = np.pad(seg, (0, t_run - t))
    win = A._row_window(t, window)
    lists = A._pair_lists(jnp.asarray(padded), bq, bkv, win)
    n = int(lists.n)
    pairs = A.live_block_pairs(padded, bq, bkv) & A._static_block_pairs(t_run, bq, bkv, win)
    active, widest = A._active_block_pairs(t_run, bq, bkv, win)
    assert nq <= n <= active == len(lists.q_major.q) == len(lists.kv_major.kv)
    _check_pair_list(lists.q_major, n, pairs, "q")
    _check_pair_list(lists.kv_major, n, pairs, "kv")

    ran, causal = A.attn_block_cells("splash", seg[None], 4, 2, window=window)
    assert ran == n * bq * bkv
    assert causal == A._active_block_pairs(t_run, bq, bkv)[0] * bq * bkv
    skips = len(lens) > 1 or lens[0] < t or win is not None
    assert (ran < causal) if skips else (ran == causal)
    steps, live, width, backward = A.attn_grid_steps(
        "splash", seg[None], 4, 2, window=window)
    assert steps == live == 2 * n  # forward and backward: no step without a pair
    assert width == pairs.sum(axis=1).max() and backward == n
    # several rows in one call keep the static kernels; so does a short row
    assert A.attn_block_cells("splash", np.stack([seg, seg]), 4, 2, window=window) == (
        2 * active * bq * bkv, 2 * causal)
    assert A.attn_grid_steps("splash", np.stack([seg, seg]), 4, 2, window=window) == (
        2 * (nq * widest + nq * nkv), 2 * 2 * active, widest,
        2 * nq * nkv)  # forward, fused backward
    assert A.attn_block_cells("splash", _row(1024, [10])[None], 4, 2) == (
        A._active_block_pairs(1024, 512, 512)[0] * 512 * 512,) * 2
    assert A.attn_grid_steps("splash", _row(1024, [10])[None], 4, 2) == (
        2 * 2 + 2 * 2, 6, 2, 2 * 2)
    assert A.attn_grid_steps("reference", seg[None], 4, 2, window=window) == (0, 0, 0, 0)


@pytest.mark.parametrize("window", [None, 200, 513], ids=["causal", "w200", "w513"])
@pytest.mark.parametrize("bq,bkv", [(128, 256), (256, 128)])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_pair_lists_walk_every_live_pair_once_in_order(layout, bq, bkv, window):
    """Over every layout of the rule's own test: the lists against
    `live_block_pairs` AND the static mask; `n` at least the q blocks
    (an all-padding row: just its diagonal)."""
    from areal_tpu.ops import attention as A

    seg = LAYOUTS[layout]
    t = len(seg)
    lists = A._pair_lists(jnp.asarray(seg), bq, bkv, window)
    pairs = A.live_block_pairs(seg, bq, bkv) & A._static_block_pairs(t, bq, bkv, window)
    n = int(lists.n)
    assert n >= t // bq and (layout != "all_padding" or n == A._diagonal_block_pairs(
        t, bq, bkv).sum())
    _check_pair_list(lists.q_major, n, pairs, "q")
    _check_pair_list(lists.kv_major, n, pairs, "kv")


@pytest.mark.parametrize("window", [None, 700], ids=["causal", "window"])
def test_pair_lists_of_a_whole_row_are_the_static_tables(window):
    """One sequence from end to end leaves every pair of the static
    mask: the lists are full (`n` their capacity) and name the steps
    splash's own shrunk static tables run and the blocks they load,
    forward, dq and dkv."""
    from areal_tpu.ops import attention as A

    t, bq, bkv = 4096, 256, 512
    static = _static_unfused(t, bq, bkv, bkv, 1, window)
    lists = A._pair_lists(jnp.asarray(_row(t, [t])), bq, bkv, window)
    assert int(lists.n) == len(lists.q_major.q)
    for info, lst, axes in ((static.fwd_mask_info, lists.q_major, (0, 1)),
                            (static.dq_mask_info, lists.q_major, (0, 1)),
                            (static.dkv_mask_info, lists.kv_major, (1, 0))):
        run = np.asarray(info.block_mask)[0] > 0
        named = np.asarray(info.data_next)[0][run]  # the kv block; in dkv the q block
        at = np.nonzero(run)[axes[0]]  # the q block the step belongs to; in dkv the kv block
        mine = (lst.q, lst.kv) if axes == (0, 1) else (lst.kv, lst.q)
        assert sorted(zip(at, named)) == sorted(zip(*(np.asarray(a) for a in mine)))


# lens, window, (hq, hkv, head size of q and k, of v)
PAIRS = {
    "one_sequence": ([2048], None, (4, 2, 32, 32)),
    "128_short": ([16] * 128, None, (4, 2, 32, 32)),
    "all_padding": ([], None, (4, 2, 32, 32)),
    "padded_tail": ([500, 400], None, (4, 2, 32, 32)),
    "three_sequences": ([700, 300, 600], None, (4, 2, 32, 32)),
    "one_token": ([1], None, (2, 2, 32, 32)),
    "window": ([900, 700], 300, (4, 2, 32, 32)),
    "window_one_sequence": ([2048], 300, (4, 2, 32, 32)),
    "window_128_short": ([16] * 128, 300, (4, 2, 32, 32)),
    "window_all_padding": ([], 300, (2, 1, 32, 32)),
    "heads_of_128": ([700, 300, 600], None, (2, 1, 128, 128)),
    "heads_of_64_against_128": ([700, 300, 600], None, (4, 2, 64, 128)),
    "heads_of_192_against_128": ([700, 300, 600], None, (2, 2, 192, 128)),
    "window_heads_of_192_against_128": ([900, 700], 300, (2, 2, 192, 128)),
    "gqa_group_of_16": ([100] * 18, None, (16, 1, 32, 32)),
    "gqa_group_of_8": ([100] * 18, None, (8, 1, 32, 32)),
    "gqa_group_of_6": ([700, 300, 600], None, (12, 2, 32, 32)),
    "gqa_group_of_1": ([100] * 18, None, (3, 3, 32, 32)),
}


@pytest.mark.parametrize("case", PAIRS)
def test_a_row_alone_walks_its_live_pairs(case, monkeypatch):
    """A row of 2,048 alone in its call through the repo's own forward
    and backward kernels over the row's lists of pairs, the grid as long
    as the row's live pairs: the output at real positions is the static
    fused-backward kernel's to the bit, and the output and the q, k, v
    gradients are the einsum reference's to 2e-5 in float32; finite
    everywhere, padding too (every q block has its diagonal pair)."""
    from areal_tpu.ops import attention as A

    lens, window, (hq, hkv, hd, hd_v) = PAIRS[case]
    t, run_shape = 2048, (2048, 128, 256, 128)
    seg = _row(t, lens)
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, t, h, d).astype(np.float32))
               for h, d in ((hq, hd), (hkv, hd), (hkv, hd_v)))
    real = seg > 0
    dout = jnp.asarray(rng.randn(1, t, hq, hd_v).astype(np.float32) * real[None, :, None, None])
    ids, at = jnp.asarray(seg)[None], jnp.arange(t)[None]

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out * dout), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return np.asarray(out)[0], [np.asarray(g) for g in grads]

    splash = lambda q, k, v: A.splash_packed_attention(
        q, k, v, ids, at, interpret=True, _run_shape=run_shape, window=window)
    walked = _watch_pair_lists(A, monkeypatch)
    got, g_got = run(splash)
    assert walked and got.shape == (t, hq, hd_v) and np.isfinite(got).all()
    assert all(np.isfinite(g).all() for g in g_got)
    ref, g_ref = run(lambda q, k, v: A.reference_packed_attention(
        q[0], k[0], v[0], ids[0], at[0], window=window)[None])
    np.testing.assert_allclose(got[real], ref[real], atol=2e-5, rtol=2e-5)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)
    monkeypatch.setattr(A, "_rows_skip", lambda rows, t_run: False)
    static, _ = run(splash)
    np.testing.assert_array_equal(got[real], static[real])


# lens, (bq, bkv, bkvc), window, (hq, hkv, head size of q and k, of v), a
# mask operand, the fewest grid steps between two visits of one q block's
# sum (0: no q block has two) and the most steps running at one q block
BACKWARD = {
    "group_8_at_128": ([400, 300, 200], (128, 256, 128), None, (8, 1, 128, 128), False, 16, 1),
    "group_1_at_192_128": ([400, 300, 200], (128, 256, 128), None, (2, 2, 192, 128), False, 2, 1),
    "window": ([900], (128, 256, 128), 300, (4, 2, 32, 32), False, 4, 1),
    "mask_operand": ([400, 300, 200], (128, 256, 128), None, (4, 2, 32, 32), True, 4, 1),
    # kv block 0 ends at q block 1, where kv block 1 begins; and kv block
    # 1 (of 256) ends at q block 4, where kv block 2 begins
    "revisit_one_step_later": ([200, 400, 300], (128, 128, 128), None, (1, 1, 32, 32), False, 1, 2),
    "revisit_one_step_later_at_256": ([640], (128, 256, 128), None, (2, 2, 32, 32), False, 1, 2),
    # the same under a group of 2: the two visits are not two steps running
    "revisit_one_pair_later_in_a_group": ([640], (128, 256, 128), None, (2, 1, 32, 32), False, 2, 2),
    # (kv 3, q 2), (kv 4, q 2), (kv 5, q 2): kv block 4 has one pair
    "revisit_twice_running": ([384, 316, 324], (256, 128, 128), None, (1, 1, 32, 32), False, 1, 3),
    # (kv 0, q 2), (kv 0, q 3), (kv 1, q 2): a sequence that ends with a kv block
    "revisit_two_steps_later": ([512, 512], (128, 256, 128), None, (1, 1, 32, 32), False, 2, 1),
    # all padding: a q block's diagonal pair and no other, a column one pair
    "a_single_pair": ([], (128, 128, 128), None, (1, 1, 32, 32), False, 0, 1),
}


@pytest.mark.parametrize("case", BACKWARD)
def test_the_one_backward_kernel_sums_dq_where_it_lives(case):
    """`splash_pairs_bwd` alone over the kv-major list (interpret mode):
    dq, whose block's float32 sum lives in HBM between the block's
    visits and is read, added to and written back a step (or, where two
    visits are two steps running, kept in VMEM for the second), dk and
    dv against the einsum reference's to the tolerance of
    `test_a_row_alone_walks_its_live_pairs`; over a group of 8 at heads
    of 128, a group of 1 at 192 / 128, a window, a mask operand, and the
    layouts that bring a q block's visits closest."""
    from areal_tpu.ops import attention as A
    from areal_tpu.ops.pallas.splash_pairs import (
        NEW, Blocks, pair_attention, pair_attention_chosen, transpose_mask,
    )

    lens, blocks, window, (hq, hkv, hd, hd_v), masked, gap, running = BACKWARD[case]
    t, blocks = 1024, Blocks(*blocks)
    seg = jnp.asarray(_row(t, lens))
    pos = jnp.arange(t)
    lists = A._pair_lists(seg, blocks.bq, blocks.bkv, window)
    n, walk = int(lists.n), np.asarray(lists.kv_major.q)
    visits = [np.flatnonzero(walk[:n] == b) for b in range(t // blocks.bq)]
    assert all(len(v) for v in visits)  # every block of dq is written
    gaps = [int(np.diff(v).min()) * (hq // hkv) for v in visits if len(v) > 1]
    assert min(gaps, default=0) == gap
    edges = np.flatnonzero(np.diff(walk[:n])) + 1
    assert np.diff(np.r_[0, edges, n]).max() == running
    firsts = np.flatnonzero(np.asarray(lists.kv_major.flags)[:n] & NEW)
    assert sorted(firsts) == sorted(v[0] for v in visits)

    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(t, h, d).astype(np.float32))
               for h, d in ((hq, hd), (hkv, hd), (hkv, hd_v)))
    real = np.asarray(seg) > 0
    dout = jnp.asarray(rng.randn(t, hq, hd_v).astype(np.float32) * real[:, None, None])
    chosen = mask = None
    if masked:  # a random choice that always keeps a query's own place
        chosen = A.segment_causal_mask(seg, seg, pos, pos) & jnp.asarray(
            (rng.rand(t, t) < 0.3) | np.eye(t, dtype=bool))
        mask = chosen.astype(jnp.int8).reshape(t, t // blocks.bkvc, blocks.bkvc).transpose(1, 0, 2)

    def kernels(q, k, v):
        heads_first = ((q * hd ** -0.5).transpose(1, 0, 2), k.transpose(1, 0, 2),
                       v.transpose(1, 0, 2), seg, lists)
        if masked:
            out, _ = pair_attention_chosen(
                *heads_first, mask, transpose_mask(mask, blocks.bq), blocks, "x", True)
        else:
            out = pair_attention(*heads_first, blocks, window, "x", True)
        return out.transpose(1, 0, 2)

    def plain(q, k, v):
        return A.reference_packed_attention(q, k, v, seg, pos, window=window, chosen=chosen)

    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * dout)
    got = jax.grad(loss(kernels), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "w200"])
@pytest.mark.parametrize("bq,bkv", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_minor_blocks_first_visit_is_flagged_once(layout, bq, bkv, window):
    """`_pair_list`'s NEW marks, of the steps up to `n`, just one a minor
    block that has a pair, and that step is the block's first in the
    walk: in the kv-major list the step of the backward kernel that
    writes a q block's dq and does not read it; every q block has one."""
    from areal_tpu.ops import attention as A
    from areal_tpu.ops.pallas.splash_pairs import NEW

    seg = LAYOUTS[layout]
    lists = A._pair_lists(jnp.asarray(seg), bq, bkv, window)
    n = int(lists.n)
    for lst, minor, blocks in ((lists.kv_major, lists.kv_major.q, len(seg) // bq),
                               (lists.q_major, lists.q_major.kv, len(seg) // bkv)):
        minor, new = np.asarray(minor)[:n], (np.asarray(lst.flags)[:n] & NEW) != 0
        for b in range(blocks):
            at = np.flatnonzero(minor == b)
            assert new[at].sum() == (len(at) > 0) and (not len(at) or new[at[0]])
        assert lst is lists.q_major or new.sum() == blocks


def test_a_shard_with_one_long_row_walks_its_live_pairs(monkeypatch):
    """`sharded_splash_attention` over a mesh of two: each shard's call
    holds one row of 2,048, so each walks its own list of pairs (the
    two rows' layouts differ), forward and backward, inside `shard_map`:
    the einsum reference's output and gradients."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.ops import attention as A
    from areal_tpu.parallel.mesh import make_mesh

    t, hq, hkv, hd = 2048, 4, 2, 32
    seg = np.stack([_row(t, [700, 300, 600]), _row(t, [2048])])
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(2, t, h, hd).astype(np.float32)) for h in (hq, hkv, hkv))
    real = seg > 0
    dout = jnp.asarray(rng.randn(2, t, hq, hd).astype(np.float32) * real[..., None, None])
    ids, at = jnp.asarray(seg), jnp.broadcast_to(jnp.arange(t), (2, t))
    mesh = make_mesh(MeshSpec(data=2), jax.devices()[:2])
    walked = _watch_pair_lists(A, monkeypatch)

    def run(fn):
        loss = lambda q, k, v: (lambda out: (jnp.sum(out * dout), out))(fn(q, k, v))
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return np.asarray(out), [np.asarray(g) for g in grads]

    got, g_got = run(lambda q, k, v: A.sharded_splash_attention(
        q, k, v, ids, at, mesh, interpret=True))
    assert walked
    ref, g_ref = run(lambda q, k, v: jax.vmap(A.reference_packed_attention)(q, k, v, ids, at))
    np.testing.assert_allclose(got[real], ref[real], atol=2e-5, rtol=2e-5)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)
    assert A.attn_grid_steps("splash", seg, hq, hkv, mesh=mesh)[:2] == (
        A.attn_grid_steps("splash", seg[:1], hq, hkv)[0]
        + A.attn_grid_steps("splash", seg[1:], hq, hkv)[0],) * 2


# lens, (bq, bkv, bkvc), window: rows of 1,024 over which the sequence-minor
# kernels are held to the head-first ones
SEQ_MINOR = {
    "three_sequences_and_padding": ([400, 300, 200], (128, 256, 128), None),
    "ends_mid_block": ([333, 479], (128, 256, 128), None),
    "one_sequence_to_the_end": ([1024], (256, 256, 128), None),
    "window": ([900], (128, 256, 128), 300),
    "window_over_three": ([300, 400, 250], (128, 128, 128), 200),
    "twelve_short": ([80] * 12, (128, 256, 128), None),
    # a q block visited in two steps running: its sum stays in VMEM
    "revisit_one_step_later": ([200, 400, 300], (128, 128, 128), None),
    "all_padding": ([], (128, 128, 128), None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", SEQ_MINOR)
def test_sequence_minor_kernels_are_the_head_first_kernels_to_the_bit(case, dtype):
    """`pair_attention(seq_minor=True)`, q, k, v and the cotangent `[H, hd,
    T]` as XLA's products leave latent attention's, at 192 / 128 and a
    group of 1: the output `[H, hd_v, T]` and dq, dk, dv are the head-first
    kernels' transposed, **to the bit** in interpret mode, float32 and
    bf16 (the same products over the same values, an operand read the other
    way round; on the chip `scripts/pair_backward_check.py` says what the
    MXU's order of summing leaves: PERF.md section 6, PR 62), over several
    sequences, padding, a row that ends inside a block, a window, and a q
    block's sum kept in VMEM."""
    from areal_tpu.ops import attention as A
    from areal_tpu.ops.pallas.splash_pairs import Blocks, pair_attention

    lens, blocks, window = SEQ_MINOR[case]
    t, h, hd, hd_v, blocks = 1024, 2, 192, 128, Blocks(*blocks)
    seg = jnp.asarray(_row(t, lens))
    lists = A._pair_lists(seg, blocks.bq, blocks.bkv, window)
    rng = np.random.RandomState(11)
    q, k, v, dout = (jnp.asarray(rng.randn(h, t, d), dtype) for d in (hd, hd, hd_v, hd_v))
    q = q * jnp.asarray(hd ** -0.5, q.dtype)

    def run(seq_minor):
        turn = (lambda a: a.transpose(0, 2, 1)) if seq_minor else (lambda a: a)
        out, vjp = jax.vjp(
            lambda q, k, v: pair_attention(q, k, v, seg, lists, blocks, window, "x", True,
                                           seq_minor), *map(turn, (q, k, v)))
        return [np.asarray(turn(a), np.float32) for a in (out, *vjp(turn(dout)))]

    for a, b, name in zip(run(True), run(False), ("out", "dq", "dk", "dv")):
        assert a.shape == b.shape and np.isfinite(a).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("skip,hd,want", [
    (True, 192, True), (True, 128, False), (True, 256, False), (True, 64, False),
    (True, 320, True), (False, 192, False)])
def test_only_a_row_alone_at_a_head_of_no_whole_lane_tiles_reads_in_place(skip, hd, want):
    """The one rule (`ops/attention._rows_in_place`), and what the call
    traces by it: at 192 the pair kernels' operands are `[H, 192, T]`, at
    any other head size here and for rows together they are as they were."""
    from areal_tpu.ops import attention as A

    assert A._rows_in_place(skip, hd) == want
    rows, t, h = (1 if skip else 3), 2048, 2
    qk = jax.ShapeDtypeStruct((rows, t, h, hd), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((rows, t, h, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((rows, t), jnp.int32)

    def loss(q, k, v, seg, pos):
        return A.splash_packed_attention(q, k, v, seg, pos, interpret=True).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(qk, qk, v, ids, ids))
    assert (f"bf16[{h},{hd},{t}]" in text) == want
    assert ("splash_pairs_bwd" in text) == skip
    assert A.attn_in_place("splash", rows, t, h, h, hd) == want
    assert not A.attn_in_place("reference", rows, t, h, h, hd)


# sha256 of str(jaxpr) of the backward pass of one call of a long row
# alone, taken at the commit before the sequence-minor kernels (PR 61):
# (t, hq, hkv, head size of q and k, of v, window)
ROW_ALONE_JAXPR = {
    (2048, 4, 2, 128, 128, None): "e2b81492edd2f9a81ea08b8fcf9b09a9962e7a1da4e13bdfb275d2c3fa227f71",
    (2048, 2, 2, 128, 128, 512): "331d84a3ba1ad9a0c839e9b2294e38cb5bb919c628a1819ebb03c94ceddfe0ef",
    (4096, 4, 1, 256, 256, None): "ffd4693b10c8fa31f18ac3ebc6544366e7aa62a338184366ab8fd52583b36808",
    (2048, 4, 2, 64, 128, None): "8ac1f4a47a7608a2076ce69d7b4d61d43935ad084b9bae731d7d46f0bec941e5",
}


@pytest.mark.parametrize("t,hq,hkv,hd,hd_v,window", sorted(ROW_ALONE_JAXPR, key=str))
def test_a_row_alone_at_whole_lane_tiles_traces_the_program_it_did(t, hq, hkv, hd, hd_v, window):
    """A long row alone whose heads are whole lane tiles (128 in a group
    of 2, under a window, 256 in a group of 4) or under one (64 against
    128) keeps the head-first pair kernels: the jaxpr of its backward
    pass, the kernels' bodies in it, is the parent commit's to the
    character."""
    import hashlib

    from areal_tpu.ops.attention import splash_packed_attention

    q = jax.ShapeDtypeStruct((1, t, hq, hd), jnp.float32)
    k = jax.ShapeDtypeStruct((1, t, hkv, hd), jnp.float32)
    v = jax.ShapeDtypeStruct((1, t, hkv, hd_v), jnp.float32)
    ids = jax.ShapeDtypeStruct((1, t), jnp.int32)

    def loss(q, k, v, seg, pos):
        return splash_packed_attention(q, k, v, seg, pos, interpret=True, window=window).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v, ids, ids))
    assert hashlib.sha256(text.encode()).hexdigest() == ROW_ALONE_JAXPR[t, hq, hkv, hd, hd_v, window]
    assert "splash_pairs_bwd" in text and "splash_mqa" not in text


# sha256 of str(jaxpr) of the backward pass of one call, taken at the
# commit before the compacted tables (PR 34): (rows, t, window)
STATIC_JAXPR = {
    (1, 1024, None): "7fc849574ba150c4f3b9f5f8f0311417d7949c09d52ba1c9a1292bdc0d302d3d",
    (3, 2048, None): "68f17527c86c1846ef97da91460e93444b55e03d9e5256b090757715a9f55702",
    (3, 2048, 512): "174e14466af1ee5d5bec27f916b4d5188efb24f087e0fe1320fb0030aad29956",
}


@pytest.mark.parametrize("rows,t,window", sorted(STATIC_JAXPR, key=str))
def test_short_rows_and_rows_together_trace_the_program_they_did(rows, t, window):
    """A row under 2,048 and three rows in one call keep the static
    kernels and the fused backward: the jaxpr of the backward pass is
    the parent commit's to the character."""
    import hashlib

    from areal_tpu.ops.attention import splash_packed_attention

    q = jax.ShapeDtypeStruct((rows, t, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((rows, t, 2, 32), jnp.float32)
    ids = jax.ShapeDtypeStruct((rows, t), jnp.int32)

    def loss(q, k, v, seg, pos):
        return splash_packed_attention(q, k, v, seg, pos, interpret=True, window=window).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv, ids, ids))
    assert hashlib.sha256(text.encode()).hexdigest() == STATIC_JAXPR[rows, t, window]
    assert "splash_mqa_dkv" in text and "splash_mqa_dq" not in text


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
