"""Trainer -> generation-server weight transfer with a same-host fast path.

Counterpart of the reference's param-realloc transfer stack
(realhf/system/model_worker.py:1046-1148 — disk-mediated by default, with
NCCL/GDRDMA fast paths keeping it under the <3 s bar of
blog/AReaL_v0_2.md:52-54). The TPU single-host equivalent of the CUDA-IPC
path is raw parameter bytes in tmpfs (/dev/shm) read back with mmap: no
pickle serialize/deserialize copies, no disk IO, and `jax.device_put`
streams straight from the mapped pages. The pickle-on-NFS dump
(engine/checkpoint.py) remains the cross-host fallback.

Format (per dump directory):
- ``params-v{N}.bin``  — every leaf's contiguous bytes, concatenated.
- ``params.json``      — manifest: schema version, dump version N, bin
  filename, and per-leaf (path, dtype, shape, offset). Written via
  tmp+rename AFTER the bin, so a reader that sees a manifest always sees
  its complete bin. Older bins are garbage-collected down to the last 2;
  a reader racing the GC gets FileNotFoundError and falls back.

The tree is assumed to be nested dicts of arrays (what
models/transformer.init_params builds); list/tuple nodes are rejected at
dump time rather than silently mis-rebuilt.
"""

from __future__ import annotations

import bisect
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from areal_tpu.base import env_registry, logging
from areal_tpu.base.chunking import (
    DEFAULT_CHUNK_BYTES,
    StreamChunker,
    slice_byte_ranges,
)

logger = logging.getLogger("weight_transfer")

_MANIFEST = "params.json"
_SCHEMA = 1

from areal_tpu.base.wire_schemas import (  # noqa: E402 (module constants)
    WEIGHT_LAYOUT_V1 as LAYOUT_SCHEMA,
    WEIGHT_SLABS_V1 as SLAB_SCHEMA,
)

# Telemetry of the most recent dump on this process: host high-water
# (largest single host materialization — the whole-model gather the
# sharded dump exists to avoid), total bytes, wall seconds, and whether
# the dump was shard-local. Read by model_worker logs and the
# `train_sharded` bench phase; single-writer by the dp-rank-0 dump rule.
LAST_DUMP_STATS: Dict[str, Any] = {}

# Quantized-wire convention (mirrors ops/wquant.py): symmetric int8 with
# per-output-channel scales reduced over axis -2, w ~= q * s. Slicing any
# dimension commutes with the dequant (s broadcasts along -2 only), so a
# shard of the quantized bin dequantizes to exactly the shard of the
# dequantized full bin — the property the weight plane's dequant-parity
# check asserts.
_WIRE_Q = 127.0
_WIRE_QAXIS = -2

# Leaf NAMES the int8 wire quantizes: the matmul weights + embedding/LM
# head — the bulk of the payload. Kept in sync with ops/wquant._QUANT_KEYS
# (weight_transfer stays jax-free, so no import); norms, biases, router
# tables, and integer leaves ship raw — the small +epsilon of a dump.
WIRE_QUANT_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out",
    "weight", "w",
})


class WeightVersionMismatch(RuntimeError):
    """load_for_serving found weights, but not the requested version.

    Serving them anyway would pin a stale (or unverifiable, version -1
    pickle/HF) dump under the new version label — the exact accounting
    hole the staleness gate can't see. Callers fail the update instead;
    the manager's eviction/readmission path re-syncs the server."""


def shm_transfer_dir(experiment_name: str, trial_name: str, role: str) -> Optional[str]:
    """tmpfs dump directory for the same-host fast path, or None when
    /dev/shm is unavailable (then only the disk path is used)."""
    base = "/dev/shm"
    if not os.path.isdir(base) or not os.access(base, os.W_OK):
        return None
    return os.path.join(base, "areal_tpu", experiment_name, trial_name, role)


def _flatten(params: Any, prefix: Tuple[str, ...] = ()) -> list:
    out = []
    if isinstance(params, dict):
        for k in sorted(params.keys()):
            out.extend(_flatten(params[k], prefix + (str(k),)))
        return out
    if isinstance(params, (list, tuple)):
        raise TypeError(
            f"weight_transfer supports dict-of-array trees only; found "
            f"{type(params).__name__} at {'/'.join(prefix)}"
        )
    return [("/".join(prefix), params)]


def chunk_sidecar_name(bin_name: str) -> str:
    """Chunk-index sidecar for a bin (``params-v{N}.chunks.json``)."""
    return bin_name[: -len(".bin")] + ".chunks.json"


def layout_sidecar_name(bin_name: str) -> str:
    """Per-leaf layout sidecar for a bin (``params-v{N}.layout.json``):
    path -> dtype/shape -> byte extent. Makes each bin self-describing
    (params.json only describes the NEWEST dump, but GC keeps two bins)
    and is what the weight plane's shard manifests slice against."""
    return bin_name[: -len(".bin")] + ".layout.json"


def wire_bin_name(version: int, wire_dtype: str) -> str:
    """The quantized-wire companion bin (``params-v{N}.int8.bin``)."""
    return f"params-v{version}.{wire_dtype}.bin"


def slab_bin_name(version: int, slab: int) -> str:
    """One process's shard-local slab of a sharded dump
    (``params-v{N}.slab{K}.bin``)."""
    return f"params-v{version}.slab{slab}.bin"


def slab_sidecar_name(bin_name: str) -> str:
    """The slab's entry list (``params-v{N}.slab{K}.slabs.json``):
    which (path, slices) live at which slab offsets, in write order."""
    return bin_name[: -len(".bin")] + ".slabs.json"


def _gc_old_versions(dump_dir: str, keep: int = 2) -> None:
    """Remove every artifact (bin, wire companion, sidecars, slabs) of
    all but the newest ``keep`` dump versions. Prefix-based so new
    artifact kinds never need their own victim list."""
    versions = set()
    for b in os.listdir(dump_dir):
        if b.startswith("params-v"):
            v = b[len("params-v"):].split(".", 1)[0]
            if v.isdigit():
                versions.add(int(v))
    for v in sorted(versions)[:-keep]:
        prefix = f"params-v{v}."
        for b in os.listdir(dump_dir):
            if b.startswith(prefix):
                try:
                    os.unlink(os.path.join(dump_dir, b))
                except OSError:
                    pass


def _wire_quantizable(path: str, arr: np.ndarray) -> bool:
    """Leaves the int8 wire quantizes: float matrices (ndim >= 2) whose
    leaf name marks a matmul weight / embedding (WIRE_QUANT_KEYS).
    Everything else ships raw — the scale convention needs an input dim
    and norm/bias precision is not worth trading for their few bytes."""
    return (
        arr.ndim >= 2
        and path.split("/")[-1] in WIRE_QUANT_KEYS
        and (
            np.issubdtype(arr.dtype, np.floating)
            or arr.dtype.name == "bfloat16"
        )
    )


def quantize_wire_leaf(arr: np.ndarray):
    """(int8 data, float32 scales) for one leaf under the wire
    convention (see _WIRE_Q/_WIRE_QAXIS). Host-side numpy mirror of
    ops/wquant.quantize_weight, bit-equal in convention so W8A16
    serving could adopt wire-quantized leaves without requantizing."""
    w32 = np.asarray(arr, dtype=np.float32)
    s = np.maximum(np.max(np.abs(w32), axis=_WIRE_QAXIS), 1e-8) / _WIRE_Q
    q = np.clip(
        np.rint(w32 / np.expand_dims(s, _WIRE_QAXIS)), -_WIRE_Q, _WIRE_Q
    ).astype(np.int8)
    return q, s.astype(np.float32)


def dequantize_wire_leaf(q: np.ndarray, s: np.ndarray, dtype) -> np.ndarray:
    """Inverse of quantize_wire_leaf, cast back to the logical dtype."""
    return (
        q.astype(np.float32) * np.expand_dims(s, _WIRE_QAXIS)
    ).astype(dtype)


def _write_json_atomic(dump_dir: str, name: str, payload: Dict) -> None:
    tmp = os.path.join(dump_dir, name + f".tmp.{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(dump_dir, name))


def _dump_wire_bin(
    dump_dir: str, version: int, wire_dtype: str,
    leaves, chunk_bytes: int,
) -> Dict[str, Any]:
    """Write the quantized-wire companion bin + its chunk/layout
    sidecars; returns the layout dict. Per leaf the int8 data slab is
    immediately followed by its float32 scale slab, so a shard manifest
    slices them as adjacent segments of one stream."""
    if wire_dtype != "int8":
        raise ValueError(f"unsupported weight_wire_dtype {wire_dtype!r}")
    bin_name = wire_bin_name(version, wire_dtype)
    layout: Dict[str, Any] = {
        "schema": LAYOUT_SCHEMA, "version": int(version), "bin": bin_name,
        "wire": wire_dtype, "leaves": [],
    }
    offset = 0
    chunker = StreamChunker(chunk_bytes)
    tmp_bin = os.path.join(dump_dir, bin_name + f".tmp.{os.getpid()}")
    with open(tmp_bin, "wb") as f:

        def put(data: bytes):
            nonlocal offset
            f.write(data)
            chunker.update(data)
            offset += len(data)

        for path, leaf in leaves:
            arr = np.ascontiguousarray(np.asarray(leaf))
            entry: Dict[str, Any] = {
                "path": path, "dtype": arr.dtype.name,
                "shape": list(arr.shape), "offset": offset,
            }
            if _wire_quantizable(path, arr):
                q, s = quantize_wire_leaf(arr)
                entry.update(
                    wire="int8", nbytes=q.nbytes,
                    scale_offset=offset + q.nbytes, scale_nbytes=s.nbytes,
                    scale_shape=list(s.shape), scale_dtype="float32",
                )
                put(q.tobytes())
                put(s.tobytes())
            else:
                entry.update(wire="raw", nbytes=arr.nbytes)
                put(arr.tobytes())
            layout["leaves"].append(entry)
        f.flush()
        os.fsync(f.fileno())
    layout["total_bytes"] = offset
    os.replace(tmp_bin, os.path.join(dump_dir, bin_name))
    _write_json_atomic(dump_dir, chunk_sidecar_name(bin_name), chunker.finish())
    _write_json_atomic(dump_dir, layout_sidecar_name(bin_name), layout)
    return layout


def dump_raw_params(
    params: Any, dump_dir: str, version: int,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    wire_dtype: Optional[str] = None,
) -> float:
    """Write the raw dump; returns seconds spent. Safe against concurrent
    readers (see module docstring); single writer assumed (the dp-rank-0
    dump rule, system/model_worker._param_realloc).

    Also publishes per-bin sidecars the weight-distribution plane serves
    from without re-reading the multi-GB bin:

    - ``params-v{N}.chunks.json`` — content hashes of the bin's
      fixed-size chunks, computed while the bytes stream through this
      loop anyway (``chunk_bytes`` should match the plane's
      ``weight_chunk_bytes`` knob; a mismatched sidecar is ignored).
    - ``params-v{N}.layout.json`` — per-leaf path/dtype/shape/byte
      extent, making the bin self-describing (params.json only describes
      the newest dump while GC keeps two) and sliceable into per-shard
      manifests.
    - with ``wire_dtype="int8"``: ``params-v{N}.int8.bin`` + its own
      sidecars — each float matrix leaf quantized to int8 data +
      float32 per-output-channel scales (ops/wquant.py convention),
      roughly halving bytes on the wire per version again; servers
      dequantize at assembly.
    """
    t0 = time.monotonic()
    os.makedirs(dump_dir, exist_ok=True)
    leaves = _flatten(params)
    bin_name = f"params-v{version}.bin"
    manifest: Dict[str, Any] = {
        "schema": _SCHEMA, "version": int(version), "bin": bin_name,
        "leaves": [],
    }
    offset = 0
    high_water = 0
    chunker = StreamChunker(chunk_bytes)
    tmp_bin = os.path.join(dump_dir, bin_name + f".tmp.{os.getpid()}")
    with open(tmp_bin, "wb") as f:
        for path, leaf in leaves:
            arr = np.ascontiguousarray(np.asarray(leaf))
            high_water = max(high_water, arr.nbytes)
            data = arr.tobytes()
            f.write(data)
            chunker.update(data)
            # dtype.name (not .str): ml_dtypes types like bfloat16 have
            # .str '<V2' which round-trips to a raw void type.
            manifest["leaves"].append(
                {"path": path, "dtype": arr.dtype.name,
                 "shape": list(arr.shape), "offset": offset,
                 "nbytes": arr.nbytes}
            )
            offset += arr.nbytes
        # fsync BEFORE the rename pair below: rename ordering alone is
        # only crash-safe within one file. Without it a host crash can
        # persist the (later-written) manifest but not the bin's data
        # blocks — a manifest pointing at unsynced bytes that would pass
        # the size check and serve garbage weights.
        f.flush()
        os.fsync(f.fileno())
    manifest["total_bytes"] = offset
    os.replace(tmp_bin, os.path.join(dump_dir, bin_name))
    _write_json_atomic(dump_dir, chunk_sidecar_name(bin_name), chunker.finish())
    _write_json_atomic(
        dump_dir, layout_sidecar_name(bin_name),
        {"schema": LAYOUT_SCHEMA, "version": int(version), "bin": bin_name,
         "wire": "raw", "total_bytes": offset,
         "leaves": [dict(e, wire="raw") for e in manifest["leaves"]]},
    )
    if wire_dtype not in (None, "model", "raw"):
        # Quantize during the dump pass (before the manifest lands), so
        # a reader that sees params.json advertise the wire can rely on
        # the wire bin existing for that version.
        wire_layout = _dump_wire_bin(
            dump_dir, version, wire_dtype, leaves, chunk_bytes
        )
        manifest["wire_dtypes"] = [wire_dtype]
        manifest["wire_total_bytes"] = {
            wire_dtype: wire_layout["total_bytes"]
        }
    _write_json_atomic(dump_dir, _MANIFEST, manifest)
    # GC old versions (bins + every sidecar/wire companion/slab; keep
    # the newest 2 so an in-flight reader can finish).
    _gc_old_versions(dump_dir)
    dt = time.monotonic() - t0
    LAST_DUMP_STATS.clear()
    LAST_DUMP_STATS.update(
        sharded=False, high_water_bytes=int(high_water),
        total_bytes=int(offset), seconds=dt, n_slabs=0,
    )
    return dt


def chunk_index_from_reader(
    reader: "DumpStreamReader", total_bytes: int, chunk_bytes: int
) -> Dict[str, Any]:
    """Chunk index of a dump's (possibly slab-backed) byte stream, one
    4 MiB-stride pass through ``reader`` — shared by the dump-time
    sidecar write below and the weight-plane origin's lazy indexing, so
    the two can never diverge on chunking semantics."""
    chunker = StreamChunker(chunk_bytes)
    pos = 0
    while pos < total_bytes:
        n = min(4 << 20, total_bytes - pos)
        chunker.update(reader.read_at(pos, n))
        pos += n
    return chunker.finish()


def _full_layout_leaves(leaves) -> Tuple[List[Dict[str, Any]], int]:
    """The canonical full-stream layout of a flattened param list:
    sorted-path order, row-major full leaves, cumulative offsets —
    exactly the byte stream ``dump_raw_params`` writes contiguously.
    Shape/dtype come off the (possibly jax, possibly sharded) leaves
    WITHOUT materializing any data."""
    out: List[Dict[str, Any]] = []
    offset = 0
    for path, leaf in leaves:
        dt = np.dtype(leaf.dtype.name if hasattr(leaf.dtype, "name")
                      else leaf.dtype)
        shape = list(getattr(leaf, "shape", ()))
        nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize \
            if shape else dt.itemsize
        out.append({
            "path": path, "dtype": dt.name, "shape": shape,
            "offset": offset, "nbytes": nbytes,
        })
        offset += nbytes
    return out, offset


def _norm_slices(index, shape) -> List[Tuple[int, int]]:
    """A jax ``Shard.index`` (tuple of slices, possibly open-ended) as
    concrete per-dim ``(start, stop)`` pairs."""
    out = []
    for sl, dim in zip(index, shape):
        a = 0 if sl.start is None else int(sl.start)
        b = int(dim) if sl.stop is None else int(sl.stop)
        out.append((a, b))
    return out


def _owned_shards(leaf, process_index: int):
    """This process's OWNED shards of one leaf: ``(slices, data)`` pairs
    in deterministic (start-tuple) order. For jax arrays, ownership is
    ``replica_id == 0`` (each distinct shard has exactly one owner
    globally, so a replicated leaf is written once fleet-wide); plain
    host arrays are owned by process 0. ``data`` stays lazy — the caller
    materializes one shard at a time, which IS the high-water win."""
    shards = getattr(leaf, "addressable_shards", None)
    if shards is None:
        if process_index != 0:
            return []
        shape = getattr(leaf, "shape", ())
        return [([(0, int(d)) for d in shape], leaf)]
    owned = [
        (_norm_slices(s.index, leaf.shape), s.data)
        for s in shards
        if getattr(s, "replica_id", 0) == 0
    ]
    owned.sort(key=lambda e: tuple(a for a, _ in e[0]))
    return owned


def dump_raw_params_sharded(
    params: Any, dump_dir: str, version: int,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    process_index: int = 0, n_processes: int = 1,
    wire_dtype: Optional[str] = None,
) -> float:
    """Shard-local raw dump: each process writes ONLY its addressable
    shard slabs; no host ever materializes more than one shard at a
    time. Returns seconds spent (this process).

    The dump's logical payload is the SAME byte stream ``dump_raw_params``
    writes (sorted-path, row-major full leaves) — but stored as one
    ``params-v{N}.slab{K}.bin`` per process plus a ``.slabs.json``
    sidecar mapping each slab extent back to (path, slices). Readers and
    the weight-plane origin reassemble the stream through
    :class:`DumpStreamReader`, so chunk hashes, shard manifests and the
    whole PR 5/8 distribution contract are byte-identical to a
    contiguous dump of the same values. ``params.json`` (process 0 only)
    carries ``storage: "sharded"`` + the full virtual layout; a reader
    that sees the manifest before every slab landed treats the dump as
    absent and retries — the same torn-write discipline as the
    contiguous format.

    The quantized wire companion is NOT published for sharded dumps:
    its per-output-channel scales reduce over axis -2, which FSDP
    shards — per-shard absmax would silently diverge from the global
    convention. The plane serves the raw wire; ``weight_wire_dtype``
    on a sharded trainer mesh logs a warning and ships raw.
    """
    t0 = time.monotonic()
    os.makedirs(dump_dir, exist_ok=True)
    if wire_dtype not in (None, "model", "raw"):
        logger.warning(
            f"weight_wire_dtype={wire_dtype!r} ignored for the sharded "
            f"dump: wire scales reduce an axis FSDP shards (see "
            f"dump_raw_params_sharded docstring); serving the raw wire"
        )
    leaves = _flatten(params)
    full_leaves, total_bytes = _full_layout_leaves(leaves)
    bin_name = f"params-v{version}.bin"  # virtual stream name
    slab_name = slab_bin_name(version, process_index)
    slab: Dict[str, Any] = {
        "schema": SLAB_SCHEMA, "version": int(version), "bin": slab_name,
        "slab": int(process_index), "n_slabs": int(n_processes),
        "entries": [],
    }
    offset = 0
    high_water = 0
    tmp_bin = os.path.join(dump_dir, slab_name + f".tmp.{os.getpid()}")
    with open(tmp_bin, "wb") as f:
        for path, leaf in leaves:
            for slices, data in _owned_shards(leaf, process_index):
                arr = np.ascontiguousarray(np.asarray(data))
                high_water = max(high_water, arr.nbytes)
                f.write(arr.tobytes())
                slab["entries"].append({
                    "path": path,
                    "slices": [list(s) for s in slices],
                    "offset": offset, "nbytes": int(arr.nbytes),
                })
                offset += arr.nbytes
                del arr
        f.flush()
        os.fsync(f.fileno())
    slab["total_bytes"] = offset
    os.replace(tmp_bin, os.path.join(dump_dir, slab_name))
    _write_json_atomic(dump_dir, slab_sidecar_name(slab_name), slab)
    if process_index == 0:
        manifest: Dict[str, Any] = {
            "schema": _SCHEMA, "version": int(version), "bin": bin_name,
            "storage": "sharded", "n_slabs": int(n_processes),
            "leaves": full_leaves, "total_bytes": int(total_bytes),
        }
        _write_json_atomic(
            dump_dir, layout_sidecar_name(bin_name),
            {"schema": LAYOUT_SCHEMA, "version": int(version),
             "bin": bin_name, "wire": "raw", "storage": "sharded",
             "n_slabs": int(n_processes), "total_bytes": int(total_bytes),
             "leaves": [dict(e, wire="raw") for e in full_leaves]},
        )
        if n_processes == 1:
            # Single process: every slab is already on disk, so publish
            # the full-stream chunk index now (one page-cache-hot read
            # pass) — the weight-plane origin then never re-hashes.
            # Multi-process dumps skip it (process 0 cannot see sibling
            # slabs yet); the origin hashes lazily on first manifest.
            with DumpStreamReader(dump_dir, manifest) as reader:
                idx = chunk_index_from_reader(
                    reader, total_bytes, chunk_bytes
                )
            _write_json_atomic(dump_dir, chunk_sidecar_name(bin_name), idx)
        _write_json_atomic(dump_dir, _MANIFEST, manifest)
        _gc_old_versions(dump_dir)
    dt = time.monotonic() - t0
    LAST_DUMP_STATS.clear()
    LAST_DUMP_STATS.update(
        sharded=True, high_water_bytes=int(high_water),
        total_bytes=int(total_bytes), slab_bytes=int(offset),
        seconds=dt, n_slabs=int(n_processes),
    )
    return dt


def mirror_dump_version(src_dir: str, dst_dir: str, version: int) -> float:
    """File-level copy of one dump version's artifacts into another dump
    dir (the tmpfs fast-path mirror for shard-local dumps): slabs and
    sidecars first, ``params.json`` LAST via tmp+rename, so a reader of
    the mirror never sees a manifest ahead of its data — the same
    ordering discipline the dump itself follows. Costs file I/O off the
    page cache instead of a second device->host materialization of
    every shard. Returns seconds spent."""
    t0 = time.monotonic()
    os.makedirs(dst_dir, exist_ok=True)
    prefix = f"params-v{version}."

    def copy_atomic(name: str) -> None:
        tmp = os.path.join(dst_dir, name + f".tmp.{os.getpid()}")
        with open(os.path.join(src_dir, name), "rb") as s, open(tmp, "wb") as d:
            while True:
                piece = s.read(4 << 20)
                if not piece:
                    break
                d.write(piece)
            d.flush()
            os.fsync(d.fileno())
        os.replace(tmp, os.path.join(dst_dir, name))

    for name in sorted(os.listdir(src_dir)):
        if name.startswith(prefix) and ".tmp." not in name:
            copy_atomic(name)
    copy_atomic(_MANIFEST)
    _gc_old_versions(dst_dir)
    return time.monotonic() - t0


class DumpStreamReader:
    """Positioned reads over one dump version's FULL byte stream.

    Contiguous dumps pread the bin directly. Sharded dumps gather
    through an interval map from stream offsets to (slab fd, slab
    offset), built from the manifest's full layout plus every slab
    sidecar — each slab entry's covering stream ranges
    (``slice_byte_ranges``, row-major order) correspond 1:1 to its
    contiguous slab bytes, because the dump wrote the shard row-major.
    ``os.pread`` throughout, so one reader serves concurrent origin
    requests without locking; an open reader also survives the dump GC
    (the fds pin the unlinked files).

    Raises ``FileNotFoundError`` when a bin/slab is missing (GC race or
    slabs still landing — callers treat the dump as absent and retry)
    and ``ValueError`` when the slabs do not tile the stream exactly.
    """

    def __init__(self, dump_dir: str, manifest: Dict[str, Any]):
        self._fds: List[int] = []
        self.total_bytes = int(manifest["total_bytes"])
        try:
            if manifest.get("storage") != "sharded":
                fd = os.open(
                    os.path.join(dump_dir, manifest["bin"]), os.O_RDONLY
                )
                self._fds.append(fd)
                self._segments = [(0, self.total_bytes, 0, 0)]
            else:
                self._segments = self._build_sharded(dump_dir, manifest)
        except Exception:
            self.close()
            raise
        self._starts = [s[0] for s in self._segments]

    def _build_sharded(self, dump_dir: str, manifest: Dict[str, Any]):
        by_path = {e["path"]: e for e in manifest["leaves"]}
        segments: List[Tuple[int, int, int, int]] = []
        for k in range(int(manifest.get("n_slabs", 1))):
            name = slab_bin_name(int(manifest["version"]), k)
            with open(os.path.join(dump_dir, slab_sidecar_name(name))) as f:
                slab = json.load(f)
            if slab.get("schema") != SLAB_SCHEMA:
                raise ValueError(f"bad slab schema in {name}")
            fd = os.open(os.path.join(dump_dir, name), os.O_RDONLY)
            self._fds.append(fd)
            if os.fstat(fd).st_size != int(slab["total_bytes"]):
                raise ValueError(f"torn slab {name}")
            fd_idx = len(self._fds) - 1
            for e in slab["entries"]:
                leaf = by_path.get(e["path"])
                if leaf is None:
                    raise ValueError(f"slab entry for unknown {e['path']}")
                shape = list(leaf["shape"])
                n_items = int(np.prod(shape, dtype=np.int64)) if shape else 1
                itemsize = int(leaf["nbytes"]) // n_items
                slab_off = int(e["offset"])
                for off, ln in slice_byte_ranges(
                    int(leaf["offset"]), shape, itemsize, e["slices"]
                ):
                    segments.append((off, ln, fd_idx, slab_off))
                    slab_off += ln
                if slab_off - int(e["offset"]) != int(e["nbytes"]):
                    raise ValueError(f"slab entry size mismatch: {e}")
        segments.sort(key=lambda s: s[0])
        pos = 0
        for off, ln, _, _ in segments:
            if off != pos:
                raise ValueError(
                    f"slabs do not tile the stream: gap/overlap at "
                    f"{pos} (next segment starts {off}) — slab still "
                    f"landing or replica dedup bug"
                )
            pos += ln
        if pos != self.total_bytes:
            raise ValueError(
                f"slabs cover {pos} of {self.total_bytes} stream bytes"
            )
        return segments

    def read_at(self, offset: int, length: int) -> bytes:
        """``[offset, offset+length)`` of the stream; OSError on short
        reads (matches the origin's pread contract)."""
        if not (0 <= offset and offset + length <= self.total_bytes):
            raise ValueError(
                f"read [{offset}, {offset + length}) outside stream of "
                f"{self.total_bytes}"
            )
        out = []
        i = max(0, bisect.bisect_right(self._starts, offset) - 1)
        need = length
        pos = offset
        while need > 0:
            seg_off, seg_len, fd_idx, slab_off = self._segments[i]
            lo = pos - seg_off
            take = min(seg_len - lo, need)
            data = os.pread(self._fds[fd_idx], take, slab_off + lo)
            if len(data) != take:
                raise OSError(
                    f"short stream read: wanted {take}, got {len(data)}"
                )
            out.append(data)
            need -= take
            pos += take
            i += 1
        return b"".join(out)

    def close(self):
        for fd in self._fds:
            try:
                os.close(fd)
            except OSError:
                pass
        self._fds = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def unflatten_leaves(leaves: Dict[str, np.ndarray]) -> Any:
    """path->array mapping back into the nested-dict pytree (shared with
    the weight plane's host-buffer assembly, engine/weight_client.py)."""
    root: Dict[str, Any] = {}
    for path, arr in leaves.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return root


def read_layout_sidecar(
    dump_dir: str, bin_name: str
) -> Optional[Dict[str, Any]]:
    """The bin's layout sidecar, or None when absent/malformed (callers
    synthesize a raw layout from params.json for pre-sidecar dumps)."""
    try:
        with open(os.path.join(dump_dir, layout_sidecar_name(bin_name))) as f:
            layout = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError):
        return None
    if layout.get("schema") != LAYOUT_SCHEMA:
        return None
    return layout


def _read_manifest(dump_dir: str) -> Optional[Dict[str, Any]]:
    try:
        with open(os.path.join(dump_dir, _MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError):
        return None
    if manifest.get("schema") != _SCHEMA:
        return None
    return manifest


def has_raw_dump(dump_dir: str) -> bool:
    """Whether a raw dump (single bin or shard-local slabs) has been
    published in ``dump_dir``."""
    return _read_manifest(dump_dir) is not None


def load_raw_params(dump_dir: str) -> Optional[Tuple[Any, int]]:
    """mmap the latest raw dump: (params pytree of memory-mapped arrays,
    dump version), or None if absent/torn (caller falls back).

    A reader can race the dump GC: the manifest it read names a bin the
    writer just unlinked (GC keeps only the newest 2). That race means a
    NEWER dump exists — re-read the manifest once and retry against it
    rather than silently falling through to a stale pickle."""
    import ml_dtypes  # noqa: F401  registers bfloat16 et al. by name

    for _attempt in range(2):
        manifest = _read_manifest(dump_dir)
        if manifest is None:
            return None
        if manifest.get("storage") == "sharded":
            # Shard-local dump: assemble full leaves through the virtual
            # stream (no single bin exists to mmap). A missing slab
            # means the dump is still landing on another process (or the
            # GC race) — treat as absent like a missing bin.
            try:
                reader = DumpStreamReader(dump_dir, manifest)
            except FileNotFoundError:
                continue
            except (OSError, ValueError, KeyError):
                return None
            try:
                leaves = {}
                for e in manifest["leaves"]:
                    dt = np.dtype(e["dtype"])
                    buf = reader.read_at(e["offset"], int(e["nbytes"]))
                    leaves[e["path"]] = np.frombuffer(buf, dt).reshape(
                        e["shape"]
                    )
                return unflatten_leaves(leaves), int(manifest["version"])
            except (OSError, ValueError, KeyError):
                return None
            finally:
                reader.close()
        try:
            mm = np.memmap(
                os.path.join(dump_dir, manifest["bin"]), mode="r",
                dtype=np.uint8,
            )
        except FileNotFoundError:
            continue  # GC race: refreshed manifest names the new bin
        except (OSError, ValueError, KeyError):
            return None  # malformed manifest: caller falls back
        try:
            if mm.size != manifest["total_bytes"]:
                return None  # torn write
            leaves = {}
            for e in manifest["leaves"]:
                dt = np.dtype(e["dtype"])
                n = int(np.prod(e["shape"])) * dt.itemsize
                leaves[e["path"]] = (
                    mm[e["offset"]: e["offset"] + n].view(dt).reshape(e["shape"])
                )
            return unflatten_leaves(leaves), int(manifest["version"])
        except (ValueError, KeyError):
            return None
    return None


def _load_once(
    model_path: str,
    shm_dir: Optional[str],
    t0: float,
    want_version: Optional[int] = None,
    raw_seen: Optional[Dict[str, int]] = None,
):
    """One pass down the fallback chain. With ``want_version`` pinned, a
    raw dump holding the WRONG version falls through to the next source
    instead of shadowing it — e.g. a tmpfs dump lagging one version
    behind the NFS dump (writer crashed between the two dumps) must not
    hide the matching disk copy. Mismatched raw versions are recorded in
    ``raw_seen`` for the caller's error message."""
    if shm_dir is not None:
        got = load_raw_params(shm_dir)
        if got is not None:
            params, v = got
            if want_version is None or v == want_version:
                return params, {"source": "shm_raw", "version": v,
                                "load_s": time.monotonic() - t0}
            if raw_seen is not None:
                raw_seen["shm_raw"] = v
    got = load_raw_params(model_path)
    if got is not None:
        params, v = got
        if want_version is not None and v != want_version and raw_seen is not None:
            raw_seen["disk_raw"] = v
        # A mismatched disk raw still ends the chain: pickle/HF below
        # are version -1 (strictly less informative), and its intact
        # version lets the caller's retry loop wait for the right dump
        # and report exactly what it saw.
        return params, {"source": "disk_raw", "version": v,
                        "load_s": time.monotonic() - t0}
    if want_version is not None:
        # pickle/HF always report version -1: they can NEVER satisfy a
        # pinned version, so skip their multi-GB deserialization instead
        # of paying it once per retry while waiting for the raw dump.
        return None, {"source": "no_raw_dump", "version": -1,
                      "load_s": time.monotonic() - t0}
    state_file = os.path.join(model_path, "engine_state.pkl")
    if os.path.exists(state_file):
        import pickle

        with open(state_file, "rb") as f:
            params = pickle.load(f)["params"]
        return params, {"source": "pickle", "version": -1,
                        "load_s": time.monotonic() - t0}
    from areal_tpu.models.hf import load_hf_model

    _, params = load_hf_model(model_path)
    return params, {"source": "hf", "version": -1,
                    "load_s": time.monotonic() - t0}


def load_for_serving(
    model_path: str,
    shm_dir: Optional[str] = None,
    want_version: Optional[int] = None,
    retries: Optional[int] = None,
    retry_s: Optional[float] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Load params for a generation server's weight update, fastest source
    first. Returns (params, info) where info records the source and load
    seconds for the /metrics surface:

    1. ``shm_dir`` raw dump      — same-host tmpfs fast path
    2. ``model_path`` raw dump   — mmap from page cache / NFS
    3. ``model_path`` pickle     — engine_state.pkl (checkpoint fallback)
    4. ``model_path`` HF dir     — cold start from an HF checkpoint

    With ``want_version`` set, the loaded dump's version must MATCH it.
    The pickle/HF fallbacks report version -1 and a raw dump can lag the
    publisher; accepting either would pin stale weights under the new
    version label, silently corrupting routing and the staleness gate.
    The chain itself is version-aware: a raw dump holding the wrong
    version falls through to the next source (a stale tmpfs copy must
    not shadow the matching NFS dump). A miss is retried (the dump may
    still be landing — cross-host NFS attribute caching can lag the
    publisher by seconds, and a pinned retry is just a manifest read
    since it skips the pickle/HF deserialization), then raised as
    :class:`WeightVersionMismatch` so the caller fails the update and
    eviction/readmission re-syncs the server instead. The default
    budget (``AREAL_WEIGHT_LOAD_RETRIES`` x ``AREAL_WEIGHT_LOAD_RETRY_S``,
    40 x 0.25 s = 10 s) matches the plane path's manifest-retry scale.
    """
    t0 = time.monotonic()
    if retries is None:
        retries = env_registry.get_int("AREAL_WEIGHT_LOAD_RETRIES")
    if retry_s is None:
        retry_s = env_registry.get_float("AREAL_WEIGHT_LOAD_RETRY_S")
    attempts = max(1, retries)
    last_info = None
    raw_seen: Dict[str, int] = {}

    class _VersionLag(OSError):
        """Dump not (yet) at the pinned version — retryable while the
        publisher's write lands across NFS attribute caching."""

    def attempt(_timeout: float):
        nonlocal last_info
        params, info = _load_once(
            model_path, shm_dir, t0,
            want_version=want_version, raw_seen=raw_seen,
        )
        if want_version is None or info["version"] == want_version:
            return params, info
        last_info = info
        raise _VersionLag(f"dump at {info['version']} != {want_version}")

    # Fixed-interval local wait (the historical 40 x 0.25 s cadence,
    # no jitter): an NFS write landing, not a congested peer —
    # deliberately NOT routed through rpc.retry_sync, whose
    # process-global areal:rpc_* counters must only ever describe
    # network calls (a routine weight swap would otherwise read as a
    # phantom RPC retry storm on every dashboard).
    for att in range(attempts):
        try:
            return attempt(3600.0)
        except _VersionLag:
            if att + 1 < attempts:
                time.sleep(retry_s)
    raise WeightVersionMismatch(
        f"requested weight version {want_version} but "
        + (
            "no raw dump was available"
            if last_info["source"] == "no_raw_dump"
            else f"{last_info['source']} dump holds version "
            f"{last_info['version']}"
        )
        + f" after {attempts} attempt(s) (model_path={model_path}"
        + (f", mismatched raw dumps seen: {raw_seen}" if raw_seen else "")
        + ")"
    )
