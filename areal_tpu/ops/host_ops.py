"""ctypes bindings for the native host-ops library (csrc/host_ops.cpp).

Counterpart of the reference's csrc/ extension loading
(realhf/impl/model/nn/flatten_param.py:31,113,162 and
realhf/impl/model/utils/ppo_functional.py:358-394): native fast path with
pure-Python/numpy fallbacks, selected at import time. The library is
compiled on first use with g++ (no pybind11 in the toolchain; plain C ABI)
into csrc/build/ under a name keyed by the source's content.

Public API (all accept/return numpy arrays):
  - ffd_allocate_native(lengths, capacity, min_groups) -> List[List[int]]
  - merge_intervals(intervals[N,2]) -> intervals[M,2]
  - slice_intervals(src, intervals) -> 1d array
  - set_intervals(src, dst, intervals) -> None (in-place on dst)
  - gae_1d_packed(rewards, values, cu_seqlens, truncate, gamma, lam)
        -> (advantages, returns)
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from areal_tpu.base import logging as areal_logging

logger = areal_logging.getLogger("host_ops")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "csrc", "host_ops.cpp")
_LIB_DIR = os.path.join(_REPO_ROOT, "csrc", "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
# Why the native library is unavailable (compiler output), for
# require_native's error.
_failure = ""

_i64p = ctypes.POINTER(ctypes.c_int64)
_f32p = ctypes.POINTER(ctypes.c_float)
_u8p = ctypes.POINTER(ctypes.c_uint8)
_charp = ctypes.c_char_p


@functools.cache
def _lib_path() -> str:
    """Where the library built from THIS source lives: the file name
    carries the source's content hash, so a library left behind by an
    older source (csrc/build/ is git-ignored and survives checkouts) is
    never loaded in its place."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_LIB_DIR, f"libareal_host_ops.{digest}.so")


def _build(lib_path: str) -> bool:
    global _failure
    # Compile to a process-unique temp path and rename into place: os.rename
    # is atomic, so a concurrent worker either sees no library or the
    # complete one, never a half-written ELF.
    os.makedirs(_LIB_DIR, exist_ok=True)
    tmp = f"{lib_path}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.rename(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:  # pragma: no cover
        _failure = f"{e!r}: {getattr(e, 'stderr', b'') or b''!r}"
        logger.warning(
            f"host_ops native build failed ({_failure}); using Python "
            f"fallbacks"
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed, _failure
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            lib_path = _lib_path()
        except OSError as e:
            _failure = f"no source to build from: {e!r}"
            _load_failed = True
            return None
        if not os.path.exists(lib_path) and not _build(lib_path):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:  # pragma: no cover
            _failure = f"load failed: {e!r}"
            logger.warning(f"host_ops {_failure}")
            _load_failed = True
            return None
        lib.ffd_allocate.restype = ctypes.c_int64
        lib.ffd_allocate.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, _i64p]
        lib.merge_intervals.restype = ctypes.c_int64
        lib.merge_intervals.argtypes = [_i64p, _i64p, ctypes.c_int64]
        lib.slice_intervals.restype = None
        lib.slice_intervals.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, _i64p, _i64p, ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.set_intervals.restype = None
        lib.set_intervals.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, _i64p, _i64p, ctypes.c_int64,
        ]
        lib.gae_1d_packed.restype = None
        lib.gae_1d_packed.argtypes = [
            _f32p, _f32p, _i64p, _u8p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, _f32p, _f32p,
        ]
        _lib = lib
        return _lib


_bg_build: Optional[threading.Thread] = None


def native_available(wait: bool = True) -> bool:
    """Whether the native library is usable. With wait=False, never blocks
    on a compile: kicks off a background build on first call and reports
    False until it finishes (hot paths fall back to Python meanwhile)."""
    global _bg_build
    if _lib is not None:
        return True
    if _load_failed:
        return False
    if wait or not os.path.exists(_SRC) or os.path.exists(_lib_path()):
        return _load() is not None
    if _bg_build is None or not _bg_build.is_alive():
        _bg_build = threading.Thread(target=_load, daemon=True, name="host_ops_build")
        _bg_build.start()
    return False


def require_native() -> bool:
    """Build/load the native library or raise with the compiler's
    output. Workers call this on a TPU backend (utils/jaxenv.
    report_devices): there a failed build is an error, not a warning and
    a slower loop."""
    if _load() is None:
        raise RuntimeError(f"native host ops unavailable: {_failure}")
    return True


def _as_i64(x) -> np.ndarray:
    return np.ascontiguousarray(x, dtype=np.int64)


# ---------------------------------------------------------------- ffd


def ffd_allocate_native(lengths, capacity: int, min_groups: int = 1) -> List[List[int]]:
    """Native first-fit-decreasing packing; same contract as
    areal_tpu.base.datapack.ffd_allocate."""
    lib = _load()
    lengths = _as_i64(lengths)
    n = len(lengths)
    if lib is None or n == 0:
        from areal_tpu.base.datapack import ffd_allocate_py

        return ffd_allocate_py(lengths, capacity, min_groups)
    gids = np.empty(n, dtype=np.int64)
    n_groups = lib.ffd_allocate(
        lengths.ctypes.data_as(_i64p), n, int(capacity), int(min_groups),
        gids.ctypes.data_as(_i64p),
    )
    groups: List[List[int]] = [[] for _ in range(n_groups)]
    # Preserve FFD insertion order within each bin (descending length,
    # stable), matching the Python implementation exactly.
    order = np.argsort(-lengths, kind="stable")
    for idx in order:
        groups[int(gids[idx])].append(int(idx))
    return groups


# ----------------------------------------------------------- intervals


def merge_intervals(intervals: np.ndarray) -> np.ndarray:
    """Merge overlapping/adjacent [start, end) rows of an [N, 2] array
    (sorted by start). Mirrors reference csrc/interval_op/interval_op.cpp:27."""
    intervals = _as_i64(intervals).reshape(-1, 2)
    n = len(intervals)
    if n == 0:
        return intervals
    lib = _load()
    starts = np.ascontiguousarray(intervals[:, 0])
    ends = np.ascontiguousarray(intervals[:, 1])
    if lib is not None:
        m = lib.merge_intervals(starts.ctypes.data_as(_i64p), ends.ctypes.data_as(_i64p), n)
        return np.stack([starts[:m], ends[:m]], axis=1)
    out = [[int(starts[0]), int(ends[0])]]
    for s, e in zip(starts[1:], ends[1:]):
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], int(e))
        else:
            out.append([int(s), int(e)])
    return np.asarray(out, dtype=np.int64)


def _interval_args(intervals: np.ndarray, limit: int):
    intervals = _as_i64(intervals).reshape(-1, 2)
    starts = np.ascontiguousarray(intervals[:, 0])
    ends = np.ascontiguousarray(intervals[:, 1])
    # Validate before anything reaches memcpy: a bad interval on the native
    # path would silently corrupt the heap instead of raising.
    if len(starts) and (
        (starts < 0).any() or (ends < starts).any() or (ends > limit).any()
    ):
        raise ValueError(f"intervals out of bounds for array of length {limit}")
    total = int((ends - starts).sum())
    return starts, ends, total


def slice_intervals(src: np.ndarray, intervals: np.ndarray) -> np.ndarray:
    """Gather [start, end) element ranges of a flat array contiguously.
    Mirrors reference csrc/interval_op/interval_op.cu slice path."""
    src = np.ascontiguousarray(src)
    starts, ends, total = _interval_args(intervals, len(src))
    lib = _load()
    if lib is None:
        return np.concatenate([src[s:e] for s, e in zip(starts, ends)]) if total else src[:0].copy()
    out = np.empty(total, dtype=src.dtype)
    lib.slice_intervals(
        src.ctypes.data, src.dtype.itemsize,
        starts.ctypes.data_as(_i64p), ends.ctypes.data_as(_i64p), len(starts),
        out.ctypes.data,
    )
    return out


def set_intervals(src: np.ndarray, dst: np.ndarray, intervals: np.ndarray) -> None:
    """Scatter a contiguous flat `src` into [start, end) ranges of `dst`
    in place. Mirrors reference csrc/interval_op/interval_op.cu set path."""
    src = np.ascontiguousarray(src)
    assert dst.flags["C_CONTIGUOUS"] and dst.dtype == src.dtype
    starts, ends, total = _interval_args(intervals, len(dst))
    assert total == len(src), (total, len(src))
    lib = _load()
    if lib is None:
        off = 0
        for s, e in zip(starts, ends):
            dst[s:e] = src[off : off + (e - s)]
            off += e - s
        return
    lib.set_intervals(
        src.ctypes.data, dst.ctypes.data, src.dtype.itemsize,
        starts.ctypes.data_as(_i64p), ends.ctypes.data_as(_i64p), len(starts),
    )


# ----------------------------------------------------------------- gae


def gae_1d_packed(
    rewards: np.ndarray,
    values: np.ndarray,
    cu_seqlens: np.ndarray,
    truncate: np.ndarray,
    gamma: float,
    lam: float,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host GAE over packed sequences, misaligned-values layout
    (reference csrc/cugae/gae.cu:10 gae_1d_nolp_misalign): `rewards` has
    sum(seqlens) entries, `values` one extra bootstrap slot per sequence,
    `truncate[i]` keeps sequence i's bootstrap (no terminal state reached).

    The in-jit TPU path is areal_tpu.ops.gae.gae_rows; this is the host
    path for CPU-side post-processing and parity testing.
    """
    rewards = np.ascontiguousarray(rewards, dtype=np.float32)
    values = np.ascontiguousarray(values, dtype=np.float32)
    cu = _as_i64(cu_seqlens)
    n_seqs = len(cu) - 1
    trunc = np.ascontiguousarray(truncate, dtype=np.uint8)
    assert len(values) == len(rewards) + n_seqs, (len(values), len(rewards), n_seqs)
    adv = np.zeros_like(rewards)
    ret = np.zeros_like(rewards)
    lib = _load()
    if lib is not None:
        lib.gae_1d_packed(
            rewards.ctypes.data_as(_f32p), values.ctypes.data_as(_f32p),
            cu.ctypes.data_as(_i64p), trunc.ctypes.data_as(_u8p), n_seqs,
            float(gamma), float(lam),
            adv.ctypes.data_as(_f32p), ret.ctypes.data_as(_f32p),
        )
        return adv, ret
    for s in range(n_seqs):
        r0, r1 = int(cu[s]), int(cu[s + 1])
        v0 = r0 + s
        length = r1 - r0
        next_adv = 0.0
        v_next = float(values[v0 + length]) if trunc[s] else 0.0
        for t in range(length - 1, -1, -1):
            delta = rewards[r0 + t] + gamma * v_next - values[v0 + t]
            next_adv = delta + gamma * lam * next_adv
            adv[r0 + t] = next_adv
            ret[r0 + t] = next_adv + values[v0 + t]
            v_next = float(values[v0 + t])
    return adv, ret
