"""Parity tests for the native host ops (csrc/host_ops.cpp) against the
pure-Python fallbacks and the in-jit GAE scan.

Mirrors the reference's tests/cpp_extensions/test_interval_ops.py and
test_cugae.py (CUDA-vs-Python parity), but the native side is the C++
host library and the accelerator side is the lax.scan GAE.
"""

import os

import numpy as np
import pytest

from areal_tpu.base.datapack import ffd_allocate_py as py_ffd
from areal_tpu.ops import host_ops


def test_native_builds():
    # The library should compile in this environment; if not, every other
    # test still passes on fallbacks, but flag it loudly here.
    assert host_ops.native_available(), "native host_ops failed to build"


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("capacity,min_groups", [(100, 1), (64, 4), (10, 1), (1000, 2)])
def test_ffd_parity(seed, capacity, min_groups):
    rng = np.random.RandomState(seed)
    lengths = rng.randint(1, 80, size=rng.randint(1, 200)).astype(np.int64)
    expect = py_ffd(lengths, capacity, min_groups)
    got = host_ops.ffd_allocate_native(lengths, capacity, min_groups)
    assert got == expect


def test_ffd_oversized_items_and_empty():
    assert host_ops.ffd_allocate_native([50, 50], 10, 1) == py_ffd([50, 50], 10, 1)
    assert host_ops.ffd_allocate_native([5], 10, 4) == py_ffd([5], 10, 4)


def test_merge_intervals():
    iv = np.array([[0, 3], [3, 5], [7, 9], [8, 12], [20, 21]], dtype=np.int64)
    out = host_ops.merge_intervals(iv)
    assert out.tolist() == [[0, 5], [7, 12], [20, 21]]
    assert host_ops.merge_intervals(np.zeros((0, 2), np.int64)).shape == (0, 2)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32, np.uint8])
def test_slice_set_roundtrip(dtype):
    rng = np.random.RandomState(0)
    src = (rng.rand(1000) * 100).astype(dtype)
    iv = np.array([[0, 10], [50, 51], [100, 300], [999, 1000]], dtype=np.int64)
    sl = host_ops.slice_intervals(src, iv)
    expect = np.concatenate([src[s:e] for s, e in iv])
    np.testing.assert_array_equal(sl, expect)

    dst = np.zeros_like(src)
    host_ops.set_intervals(sl, dst, iv)
    for s, e in iv:
        np.testing.assert_array_equal(dst[s:e], src[s:e])
    mask = np.ones(1000, bool)
    for s, e in iv:
        mask[s:e] = False
    assert not dst[mask].any()


def test_interval_bounds_rejected():
    src = np.arange(10, dtype=np.float32)
    dst = np.zeros(10, np.float32)
    for bad in ([[5, 12]], [[-1, 3]], [[4, 2]]):
        iv = np.array(bad, np.int64)
        with pytest.raises(ValueError):
            host_ops.slice_intervals(src, iv)
        with pytest.raises(ValueError):
            host_ops.set_intervals(src[:1], dst, iv)


def test_native_available_nonblocking_converges():
    # wait=False must never raise and must eventually report the built lib.
    import time

    for _ in range(100):
        if host_ops.native_available(wait=False):
            break
        time.sleep(0.05)
    assert host_ops.native_available(wait=False)


def _py_gae_reference(rewards, values, cu, trunc, gamma, lam):
    """Direct transcription of the misaligned-values recurrence."""
    adv = np.zeros_like(rewards)
    ret = np.zeros_like(rewards)
    n_seqs = len(cu) - 1
    for s in range(n_seqs):
        r0, r1 = int(cu[s]), int(cu[s + 1])
        v0 = r0 + s
        nxt_adv, v_next = 0.0, (float(values[v0 + (r1 - r0)]) if trunc[s] else 0.0)
        for t in range(r1 - r0 - 1, -1, -1):
            delta = rewards[r0 + t] + gamma * v_next - values[v0 + t]
            nxt_adv = delta + gamma * lam * nxt_adv
            adv[r0 + t] = nxt_adv
            ret[r0 + t] = nxt_adv + values[v0 + t]
            v_next = float(values[v0 + t])
    return adv, ret


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("gamma,lam", [(1.0, 1.0), (0.99, 0.95)])
def test_gae_native_vs_python(seed, gamma, lam):
    rng = np.random.RandomState(seed)
    seqlens = rng.randint(1, 30, size=8)
    cu = np.concatenate([[0], np.cumsum(seqlens)]).astype(np.int64)
    total = int(cu[-1])
    rewards = rng.randn(total).astype(np.float32)
    values = rng.randn(total + len(seqlens)).astype(np.float32)
    trunc = rng.randint(0, 2, size=len(seqlens)).astype(np.uint8)
    adv, ret = host_ops.gae_1d_packed(rewards, values, cu, trunc, gamma, lam)
    eadv, eret = _py_gae_reference(rewards, values, cu, trunc, gamma, lam)
    np.testing.assert_allclose(adv, eadv, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ret, eret, rtol=1e-5, atol=1e-5)


def test_gae_host_matches_jit_scan():
    """Host packed GAE == in-jit row-packed lax.scan GAE (areal_tpu.ops.gae)."""
    import jax.numpy as jnp

    from areal_tpu.ops.gae import gae_rows

    rng = np.random.RandomState(1)
    seqlens = [5, 9, 3]
    cu = np.concatenate([[0], np.cumsum(seqlens)]).astype(np.int64)
    total = int(cu[-1])
    rewards = rng.randn(total).astype(np.float32)
    values = rng.randn(total + len(seqlens)).astype(np.float32)
    trunc = np.array([1, 0, 1], dtype=np.uint8)
    gamma, lam = 0.99, 0.95
    adv, ret = host_ops.gae_1d_packed(rewards, values, cu, trunc, gamma, lam)

    # Pack into one [1, T] row for gae_rows.
    T = total
    seg = np.zeros(T, np.int32)
    vrow = np.zeros(T, np.float32)
    boot = np.zeros(T, np.float32)
    for s in range(len(seqlens)):
        r0, r1 = int(cu[s]), int(cu[s + 1])
        seg[r0:r1] = s + 1
        vrow[r0:r1] = values[r0 + s : r1 + s]
        if trunc[s]:
            boot[r1 - 1] = values[r1 + s]
    jadv, jret = gae_rows(
        jnp.asarray(rewards)[None], jnp.asarray(vrow)[None], jnp.asarray(seg)[None],
        jnp.asarray(boot)[None], gamma=gamma, lam=lam,
    )
    np.testing.assert_allclose(adv, np.asarray(jadv)[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ret, np.asarray(jret)[0], rtol=1e-4, atol=1e-4)


def test_library_is_keyed_by_the_sources_content(tmp_path, monkeypatch):
    """Built from what git would commit: the library's name carries the
    source's hash, so an artifact left in the git-ignored build dir by
    another source is never the one that loads."""
    import hashlib

    with open(host_ops._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    path = host_ops._lib_path()
    assert os.path.dirname(path) == host_ops._LIB_DIR
    assert os.path.basename(path) == f"libareal_host_ops.{digest}.so"
    assert host_ops.require_native() is True
    assert os.path.exists(path)

    # Another source maps to another file; a stale unkeyed artifact of
    # the old scheme sits beside it untouched and unloaded.
    other = tmp_path / "host_ops.cpp"
    other.write_bytes(open(host_ops._SRC, "rb").read() + b"\n// changed\n")
    monkeypatch.setattr(host_ops, "_SRC", str(other))
    host_ops._lib_path.cache_clear()
    try:
        assert host_ops._lib_path() != path
    finally:
        monkeypatch.undo()
        host_ops._lib_path.cache_clear()
    assert host_ops._lib_path() == path


def test_require_native_raises_with_the_reason(monkeypatch):
    """On the chip path a failed build is an error (utils/jaxenv.
    report_devices calls this on a TPU), with the compiler's output."""
    monkeypatch.setattr(host_ops, "_lib", None)
    monkeypatch.setattr(host_ops, "_load_failed", True)
    monkeypatch.setattr(host_ops, "_failure", "g++: fatal error: boom")
    assert host_ops.native_available() is False
    with pytest.raises(RuntimeError, match="g\\+\\+: fatal error: boom"):
        host_ops.require_native()
