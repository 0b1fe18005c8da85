"""Shard-local trainer dump (PR 9 tentpole leg): each process writes
only its addressable shard slabs (no whole-model host gather), and the
virtual full byte stream the slabs encode is BYTE-IDENTICAL to a
contiguous `dump_raw_params` of the same values — so every downstream
consumer (mmap fallback loader, weight-plane origin, TP-sliced shard
manifests) sees exactly the PR 5/8 contract.

All host-side + loopback HTTP on the conftest fake-device CPU mesh.
Time budget: ~10 s total (tiny trees; tier-1 headroom note per PR 7's
discipline)."""

import json
import os

import jax
import ml_dtypes
import numpy as np
import pytest

from areal_tpu.base.topology import MeshSpec
from areal_tpu.parallel.mesh import make_mesh
from areal_tpu.parallel.sharding import shard_params
from areal_tpu.system import weight_transfer as wt

CB = 1 << 12  # 4 KiB chunks: multi-chunk streams on tiny payloads


def make_tree(seed=0):
    """Leaf names chosen so parallel/sharding.py specs engage: wq
    column-parallel, wo row-parallel, embedding/head vocab-parallel,
    norm scale replicated (the per-rank dedup case)."""
    rng = np.random.RandomState(seed)
    L, D, V = 2, 16, 64
    return {
        "embedding": {
            "weight": rng.standard_normal((V, D)).astype(ml_dtypes.bfloat16)
        },
        "head": {
            "weight": rng.standard_normal((D, V)).astype(ml_dtypes.bfloat16)
        },
        "layers": {
            "attn": {
                "wq": rng.standard_normal((L, D, D)).astype(np.float32),
                "wo": rng.standard_normal((L, D, D)).astype(np.float32),
            },
            "norm": {
                "scale": rng.standard_normal((L, D)).astype(np.float32)
            },
        },
    }


def flat_leaves(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from flat_leaves(tree[k], prefix + (k,))
    else:
        yield "/".join(prefix), tree


def assert_trees_bitwise_equal(a, b):
    for (pa, la), (pb, lb) in zip(flat_leaves(a), flat_leaves(b)):
        assert pa == pb
        np.testing.assert_array_equal(
            np.asarray(la).view(np.uint8), np.asarray(lb).view(np.uint8),
            err_msg=pa,
        )


def f2_sharded(tree):
    mesh = make_mesh(MeshSpec.parse("f2"), jax.devices()[:2])
    return shard_params(tree, mesh)


def test_sharded_dump_roundtrips_and_matches_contiguous_stream(tmp_path):
    tree = make_tree()
    da, db = str(tmp_path / "full"), str(tmp_path / "shard")
    wt.dump_raw_params(tree, da, version=1, chunk_bytes=CB)
    full_stats = dict(wt.LAST_DUMP_STATS)
    wt.dump_raw_params_sharded(
        f2_sharded(tree), db, version=1, chunk_bytes=CB
    )
    shard_stats = dict(wt.LAST_DUMP_STATS)

    # Manifest advertises the storage; loader reassembles bit-for-bit.
    man = json.load(open(os.path.join(db, "params.json")))
    assert man["storage"] == "sharded" and man["n_slabs"] == 1
    got, v = wt.load_raw_params(db)
    assert v == 1
    assert_trees_bitwise_equal(tree, got)

    # The dump-time chunk sidecar (single-process sharded dumps publish
    # it) hashes the SAME byte stream the contiguous dump wrote.
    ca = json.load(open(os.path.join(da, "params-v1.chunks.json")))
    cb_ = json.load(open(os.path.join(db, "params-v1.chunks.json")))
    assert ca["hashes"] == cb_["hashes"]
    assert ca["total_bytes"] == cb_["total_bytes"]

    # THE high-water claim: the sharded dump never materialized a full
    # leaf (largest leaves halve on the 2-way fsdp mesh).
    assert shard_stats["sharded"] and not full_stats["sharded"]
    assert (
        shard_stats["high_water_bytes"]
        <= 0.6 * full_stats["high_water_bytes"]
    )


def test_sharded_dump_serves_through_weight_plane(tmp_path):
    """Origin over a slab-backed dump: full stream and TP2-sliced shard
    streams are hash-identical to a contiguous dump's, and a ChunkStore
    fetch assembles the exact tree — the PR 5/8 distribution contract
    holds with no host ever holding the whole model."""
    from areal_tpu.engine.weight_client import (
        ChunkStore, assemble_params, fetch_manifest,
    )
    from areal_tpu.system.weight_plane import WeightPlaneSource

    tree = make_tree(seed=3)
    da, db = str(tmp_path / "full"), str(tmp_path / "shard")
    wt.dump_raw_params(tree, da, version=1, chunk_bytes=CB)
    wt.dump_raw_params_sharded(
        f2_sharded(tree), db, version=1, chunk_bytes=CB
    )
    src_a = src_b = None
    try:
        src_a = WeightPlaneSource(da, chunk_bytes=CB).start()
        src_b = WeightPlaneSource(db, chunk_bytes=CB).start()
        man_a = fetch_manifest(src_a.address, version=1)
        man_b = fetch_manifest(src_b.address, version=1)
        assert man_a["hashes"] == man_b["hashes"]
        st = ChunkStore(man_b)
        st.fetch([src_b.address], origin=src_b.address)
        assembled, v = assemble_params(st)
        assert v == 1
        assert_trees_bitwise_equal(tree, assembled)
        # TP-sliced serving streams built over the slabs == over the bin
        # (what a sharded gserver fleet actually fetches).
        for rank in range(2):
            sa = fetch_manifest(
                src_a.address, version=1, tp_degree=2, tp_rank=rank
            )
            sb = fetch_manifest(
                src_b.address, version=1, tp_degree=2, tp_rank=rank
            )
            assert sa["hashes"] == sb["hashes"], f"rank {rank}"
            assert sa["total_bytes"] == sb["total_bytes"]
    finally:
        for s in (src_a, src_b):
            if s is not None:
                s.close()


def test_sharded_dump_gc_removes_slab_artifacts(tmp_path):
    d = str(tmp_path / "dumps")
    sharded = f2_sharded(make_tree())
    for v in (1, 2, 3):
        wt.dump_raw_params_sharded(sharded, d, version=v, chunk_bytes=CB)
    names = os.listdir(d)
    assert not any(n.startswith("params-v1.") for n in names), names
    for v in (2, 3):
        assert wt.slab_bin_name(v, 0) in names
    got, v = wt.load_raw_params(d)
    assert v == 3


def test_sharded_dump_skips_quantized_wire(tmp_path):
    """The int8 wire's per-output-channel scales reduce axis -2, which
    FSDP shards — a per-shard absmax would silently diverge from the
    global convention, so sharded dumps refuse to publish the companion
    (warned, raw wire served) rather than publish wrong scales."""
    d = str(tmp_path / "dumps")
    wt.dump_raw_params_sharded(
        f2_sharded(make_tree()), d, version=1, chunk_bytes=CB,
        wire_dtype="int8",
    )
    names = os.listdir(d)
    assert wt.wire_bin_name(1, "int8") not in names
    man = json.load(open(os.path.join(d, "params.json")))
    assert "wire_dtypes" not in man
    # And the plane 404s an int8-wire manifest request instead of
    # serving garbage scales.
    from areal_tpu.system.weight_plane import chunk_manifest_for_dump

    assert chunk_manifest_for_dump(d, CB, wire="int8") is None
    assert chunk_manifest_for_dump(d, CB) is not None


def test_sharded_dump_missing_slab_reads_as_absent(tmp_path):
    """Multi-process discipline: a manifest that lands before every slab
    (process 0 cannot see sibling hosts' writes) must read as ABSENT —
    retried by load_for_serving / 404'd by the origin — never as a torn
    tree."""
    d = str(tmp_path / "dumps")
    wt.dump_raw_params_sharded(
        f2_sharded(make_tree()), d, version=1, chunk_bytes=CB,
        process_index=0, n_processes=2,
    )
    # Slab 1 (the "other host") never landed: reader refuses.
    assert wt.load_raw_params(d) is None
    from areal_tpu.system.weight_plane import chunk_manifest_for_dump

    assert chunk_manifest_for_dump(d, CB) is None


def test_mirror_dump_version_copies_sharded_artifacts(tmp_path):
    """model_worker's tmpfs fast path mirrors a finished sharded dump at
    the FILE level (a second dump call would re-materialize every shard
    off the device): the mirror must be a complete, readable dump —
    bit-identical leaves — with its own GC applied."""
    tree = make_tree(seed=5)
    d, shm = str(tmp_path / "disk"), str(tmp_path / "shm")
    sharded = f2_sharded(tree)
    for v in (1, 2, 3):
        wt.dump_raw_params_sharded(sharded, d, version=v, chunk_bytes=CB)
        wt.mirror_dump_version(d, shm, v)
    got, v = wt.load_raw_params(shm)
    assert v == 3
    assert_trees_bitwise_equal(tree, got)
    names = os.listdir(shm)
    assert not any(n.startswith("params-v1.") for n in names), names
    assert not any(".tmp." in n for n in names), names


def test_manager_manifest_falls_back_to_raw_wire(tmp_path, monkeypatch):
    """gserver manager + sharded trainer dump + weight_wire_dtype=int8:
    the quantized companion does not exist (sharded dumps never publish
    it), so _fetch_plane_manifest must FALL BACK to the raw wire instead
    of failing every fleet weight update. Budget: ~6 s (the fallback
    spends a capped slice of its retry budget on the configured wire
    first)."""
    from types import SimpleNamespace

    from areal_tpu.system.gserver_manager import GserverManager
    from areal_tpu.system.weight_plane import WeightPlaneSource

    d = str(tmp_path / "dumps")
    wt.dump_raw_params_sharded(
        f2_sharded(make_tree()), d, version=1, chunk_bytes=CB,
        wire_dtype="int8",
    )
    src = WeightPlaneSource(d, chunk_bytes=CB).start()
    try:
        mgr = GserverManager.__new__(GserverManager)
        mgr.cfg = SimpleNamespace(weight_wire_dtype="int8")
        man = mgr._fetch_plane_manifest(src.address, version=1)
        assert man["wire"] == "raw"
        assert man["version"] == 1
    finally:
        src.close()


def test_param_realloc_dst_falls_back_to_raw_dump(tmp_path):
    """model_worker's dst branch: a sharded source writes no
    engine_state.pkl — the destination assembles the raw dump instead
    (weight_transfer.load_raw_params handles sharded storage)."""
    d = str(tmp_path / "dumps")
    tree = make_tree(seed=9)
    wt.dump_raw_params_sharded(
        f2_sharded(tree), d, version=4, chunk_bytes=CB
    )
    assert not os.path.exists(os.path.join(d, "engine_state.pkl"))
    got, v = wt.load_raw_params(d)
    assert v == 4
    assert_trees_bitwise_equal(tree, got)


def test_manager_sees_a_sharded_trainers_dump_on_the_disk_path(
    tmp_path, monkeypatch
):
    """A sharded trainer (mesh > 1) publishes only the shard-local raw
    dump — no engine_state.pkl. The manager's disk path used to wait for
    the pickle, so with an fsdp trainer and no weight plane no update
    ever reached a server and the async loop stalled once the staleness
    budget was spent (PR 21, found by chip_smoke's rehearsal)."""
    from types import SimpleNamespace

    from areal_tpu.base import constants
    from areal_tpu.system.gserver_manager import GserverManager

    monkeypatch.setattr(
        constants, "get_param_realloc_path", lambda exp, trial: str(tmp_path)
    )
    mgr = GserverManager.__new__(GserverManager)
    mgr.cfg = SimpleNamespace(
        experiment_name="e", trial_name="t", model_name="actor"
    )
    d = tmp_path / "actor"
    d.mkdir()
    assert not wt.has_raw_dump(str(d))
    assert mgr._current_param_path() is None  # nothing published yet
    wt.dump_raw_params_sharded(
        f2_sharded(make_tree()), str(d), version=1, chunk_bytes=CB
    )
    assert not (d / "engine_state.pkl").exists()
    assert wt.has_raw_dump(str(d))
    assert mgr._current_param_path() == str(d)
    # ... and what it points the servers at loads at that version.
    _, info = wt.load_for_serving(str(d), want_version=1, retries=1)
    assert info["version"] == 1 and info["source"] == "disk_raw"
