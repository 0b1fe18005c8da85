"""GAE implementation parity + edge cases (ISSUE 15 tentpole/satellite).

The serial ``gae_rows`` scan is the oracle; the associative scan and
the blocked Pallas kernel (interpret mode on CPU) must match it on the
case families the reference ships three CUDA variants for: packed
multi-segment rows, misaligned starts, zero-length (all-padding) rows,
truncation bootstraps at segment boundaries, and the lam in {0, 1}
closed forms.

Parity tolerance: the impls reassociate float32 sums, so comparisons
are NORMALIZED by the advantage scale (<= 1e-6 relative — absolute
1e-6 at O(20) magnitudes would be below float32 eps, unattainable by
any reassociated sum). lam = 0 accumulates nothing and is one-ulp
tight (XLA's FMA fusion still moves the last bit vs numpy).

Time budget: pure CPU jit of tiny shapes — the whole module runs in
well under 30 s warm (each case is a [R<=8, T<=256] program).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops.gae import (
    gae_rows,
    gae_rows_assoc,
    gae_rows_pallas,
    packed_gae,
    resolve_gae_impl,
)

IMPLS = {
    "assoc": gae_rows_assoc,
    "pallas": gae_rows_pallas,
}


def _pack(R, T, seed=0, max_len=40, gap=True):
    """Misaligned packed rows: segments start at random offsets, padding
    gaps between them, bootstrap at every segment's final token."""
    rng = np.random.RandomState(seed)
    seg = np.zeros((R, T), np.int32)
    boot = np.zeros((R, T), np.float32)
    for r in range(R):
        t = int(rng.randint(0, 5))
        s = 1
        while t < T - 4:
            length = int(rng.randint(3, max_len))
            end = min(t + length, T)
            seg[r, t:end] = s
            boot[r, end - 1] = rng.randn()
            s += 1
            t = end + (int(rng.randint(0, 3)) if gap else 0)
    rew = (rng.randn(R, T) * (seg > 0)).astype(np.float32)
    val = (rng.randn(R, T) * (seg > 0)).astype(np.float32)
    return tuple(
        jnp.asarray(x) for x in (rew, val, seg, boot)
    ), (rew, val, seg, boot)


def _assert_close(got, want, rel=1e-6):
    g, w = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(1.0, float(np.max(np.abs(w))))
    np.testing.assert_allclose(g, w, atol=rel * scale, rtol=0)


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("gamma,lam", [(1.0, 1.0), (0.97, 0.95)])
def test_impl_parity_packed_misaligned(impl, gamma, lam):
    args, _ = _pack(8, 256, seed=1)
    adv0, ret0 = gae_rows(*args, gamma=gamma, lam=lam)
    adv1, ret1 = IMPLS[impl](*args, gamma=gamma, lam=lam)
    _assert_close(adv1, adv0)
    _assert_close(ret1, ret0)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_zero_length_rows(impl):
    """All-padding rows (and an empty batch half) must come back exact
    zeros — padding never leaks into the recursion."""
    args, (rew, val, seg, boot) = _pack(8, 128, seed=2)
    seg2 = seg.copy()
    seg2[1] = 0  # row 1 entirely padding
    seg2[3] = 0
    args2 = (jnp.asarray(rew), jnp.asarray(val), jnp.asarray(seg2),
             jnp.asarray(boot))
    adv0, ret0 = gae_rows(*args2, gamma=0.97, lam=0.95)
    adv1, ret1 = IMPLS[impl](*args2, gamma=0.97, lam=0.95)
    assert np.all(np.asarray(adv1)[1] == 0.0)
    assert np.all(np.asarray(ret1)[3] == 0.0)
    _assert_close(adv1, adv0)
    _assert_close(ret1, ret0)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_truncation_bootstrap_at_segment_boundary(impl):
    """A truncated (no-EOS) segment bootstraps V(s_{T+1}) at its final
    token; its right NEIGHBOR segment must not see that value. A
    hand-checkable segment pair, tiled to 8 rows for the Pallas
    sublane gate."""
    T = 128
    seg = np.zeros((8, T), np.int32)
    seg[:, 2:6] = 1  # segment 1: positions 2..5
    seg[:, 6:9] = 2  # segment 2 abuts it immediately (misaligned pair)
    rew = np.zeros((8, T), np.float32)
    val = np.zeros((8, T), np.float32)
    boot = np.zeros((8, T), np.float32)
    rew[:, 2:9] = 1.0
    boot[:, 5] = 10.0  # segment 1 truncated, V(s_T+1) = 10
    gamma, lam = 0.9, 0.8
    args = tuple(jnp.asarray(x) for x in (rew, val, seg, boot))
    adv, _ = IMPLS[impl](*args, gamma=gamma, lam=lam)
    adv = np.asarray(adv)
    # Last token of segment 1: delta = r + gamma * boot = 1 + 9 = 10.
    np.testing.assert_allclose(adv[0, 5], 1.0 + gamma * 10.0, rtol=1e-6)
    # Last token of segment 2: NO bootstrap (boot=0 there) — the
    # neighbor's bootstrap must not cross the boundary.
    np.testing.assert_allclose(adv[0, 8], 1.0, rtol=1e-6)
    # And the whole thing matches the serial oracle.
    adv0, _ = gae_rows(*args, gamma=gamma, lam=lam)
    _assert_close(adv, adv0)


@pytest.mark.parametrize("impl", ["scan"] + sorted(IMPLS))
def test_lam_zero_closed_form(impl):
    """lam = 0: A_t = delta_t (one-step TD error), nothing accumulates.
    Checked per element against the numpy closed form at one-ulp
    tightness (1e-7 relative: XLA fuses r + g*v - v into FMA forms
    numpy does not, so the LAST BIT can legitimately differ — anything
    beyond that is a real leak across tokens). Padding is exact zero."""
    args, (rew, val, seg, boot) = _pack(8, 128, seed=3)
    fn = gae_rows if impl == "scan" else IMPLS[impl]
    adv, ret = fn(*args, gamma=0.9, lam=0.0)
    # Closed form, vectorized: delta_t = r + gamma*V(s_{t+1}) - V(s_t).
    seg_next = np.concatenate([seg[:, 1:], np.zeros_like(seg[:, :1])], 1)
    v_next = np.concatenate([val[:, 1:], np.zeros_like(val[:, :1])], 1)
    same = (seg == seg_next) & (seg > 0)
    v_tp1 = np.where(same, v_next, boot).astype(np.float32)
    delta = np.where(
        seg > 0, rew + np.float32(0.9) * v_tp1 - val, np.float32(0.0)
    )
    _assert_close(adv, delta, rel=1e-7)
    _assert_close(ret, np.where(seg > 0, delta + val, np.float32(0.0)),
                  rel=1e-7)
    assert np.all(np.asarray(adv)[seg == 0] == 0.0)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_lam_one_closed_form(impl):
    """lam = 1: A_t = sum_k gamma^(k-t) delta_k over the remaining
    segment (pure discounted delta sum) — checked against a float64
    numpy suffix sum per segment."""
    gamma = 0.95
    args, (rew, val, seg, boot) = _pack(8, 128, seed=4, max_len=20)
    adv, _ = IMPLS[impl](*args, gamma=gamma, lam=1.0)
    adv = np.asarray(adv, np.float64)
    for r in range(seg.shape[0]):
        for s in np.unique(seg[r])[1:] if seg[r].any() else []:
            idx = np.where(seg[r] == s)[0]
            v_n = np.append(val[r, idx[1:]], boot[r, idx[-1]])
            delta = rew[r, idx] + gamma * v_n - val[r, idx]
            want = np.zeros(len(idx))
            acc = 0.0
            for j in range(len(idx) - 1, -1, -1):
                acc = delta[j] + gamma * acc
                want[j] = acc
            scale = max(1.0, np.max(np.abs(want)))
            np.testing.assert_allclose(
                adv[r, idx], want, atol=2e-6 * scale, rtol=0
            )


def test_pallas_shape_gate():
    """Unaligned shapes must be refused loudly, not miscomputed."""
    args, _ = _pack(3, 100, seed=5)  # 3 rows, T=100: both misaligned
    with pytest.raises(ValueError, match="pallas"):
        gae_rows_pallas(*args)


def test_dispatcher_resolution_and_knob_default():
    """'auto' resolves to the associative scan, explicit impls pass
    through, unknown ones are refused, and the registered knob default
    is 'auto' so the PPO interface dispatches without env plumbing."""
    from areal_tpu.base import env_registry

    assert resolve_gae_impl("auto", 8, 256) == "assoc"
    assert resolve_gae_impl("scan", 8, 256) == "scan"
    assert resolve_gae_impl("pallas", 8, 256) == "pallas"
    assert env_registry.REGISTRY["AREAL_GAE_IMPL"].default == "auto"

    args, _ = _pack(8, 128, seed=6)
    a_auto, _ = packed_gae(*args, gamma=0.97, lam=0.95)
    a_assoc, _ = gae_rows_assoc(*args, gamma=0.97, lam=0.95)
    np.testing.assert_array_equal(np.asarray(a_auto), np.asarray(a_assoc))
    with pytest.raises(ValueError, match="unknown gae impl"):
        packed_gae(*args, impl="cuda")
