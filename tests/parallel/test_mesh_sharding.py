"""Mesh/sharding tests on the 8-device virtual CPU platform."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from areal_tpu.base.topology import MeshSpec
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.packing import pack_sequences
from areal_tpu.models.transformer import forward, init_params
from areal_tpu.parallel.mesh import AllocationMode, make_mesh
from areal_tpu.parallel.realloc import (
    gc_param_versions,
    latest_param_version,
    load_param_version,
    reshard_params,
    save_param_version,
)
from areal_tpu.parallel.sharding import (
    batch_sharding,
    param_partition_spec,
    param_shardings,
    shard_params,
)


def small_cfg():
    return TransformerConfig(
        n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2, head_dim=8,
        intermediate_dim=64, vocab_size=64, compute_dtype="float32",
    )


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_partition_specs():
    assert param_partition_spec("embedding/weight", 2) == P("tensor", "fsdp")
    assert param_partition_spec("layers/attn/wq", 3) == P(None, "fsdp", "tensor")
    assert param_partition_spec("layers/attn/wo", 3) == P(None, "tensor", "fsdp")
    assert param_partition_spec("layers/mlp/w_down", 3) == P(None, "tensor", "fsdp")
    assert param_partition_spec("layers/ln1/weight", 2) == P(None, None)
    assert param_partition_spec("head/weight", 2) == P("fsdp", "tensor")


@pytest.mark.parametrize("spec_str", ["d2t4", "d2f2t2", "d8", "t8", "d2f2s2t1"])
def test_sharded_forward_matches_single_device(spec_str):
    cfg = small_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, 64, size=l) for l in [12, 20, 9, 17]]
    batch = pack_sequences(seqs, row_len=32, n_rows_multiple=8)

    ref = forward(params, cfg, batch.input_ids, batch.segment_ids, batch.positions,
                  attn_impl="reference")

    mesh = make_mesh(MeshSpec.parse(spec_str))
    sharded = shard_params(params, mesh)
    bsh = batch_sharding(mesh)
    args = [jax.device_put(x, bsh) for x in
            (batch.input_ids, batch.segment_ids, batch.positions)]

    @jax.jit
    def f(p, i, s, pos):
        return forward(p, cfg, i, s, pos, attn_impl="reference")

    with jax.sharding.set_mesh(mesh):
        out = f(sharded, *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_reshard_between_meshes():
    cfg = small_cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    mesh_a = make_mesh(MeshSpec.parse("d4t2"))
    mesh_b = make_mesh(MeshSpec.parse("t8"))
    pa = shard_params(params, mesh_a)
    pb = reshard_params(pa, mesh_b)
    for x, y in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_disjoint_cover(chips, n):
    owned = sorted(c for cs in chips.values() for c in cs)
    assert owned == list(range(n)), chips  # disjoint AND covering


def test_allocation_mode_worker_chips():
    """allocation_mode -> one chip set per chip-holding worker process:
    gen slices first (server i = i-th slice), then train slices."""
    chips = AllocationMode.parse("d1").worker_chips(0, 1)
    assert chips == {"model_worker/0": [0]}

    chips = AllocationMode.parse("gen.d1+d1").worker_chips(1, 1)
    assert chips == {"generation_server/0": [0], "model_worker/0": [1]}
    _assert_disjoint_cover(chips, 2)

    # Two one-chip servers and an fsdp-2 trainer on a 2x2 host.
    am = AllocationMode.parse("gen.d2t1+d1f2")
    assert am.decoupled
    chips = am.worker_chips(2, 1)
    assert chips == {
        "generation_server/0": [0],
        "generation_server/1": [1],
        "model_worker/0": [2, 3],
    }
    _assert_disjoint_cover(chips, 4)

    # One TP-2 server, the train data axis spread over two workers.
    chips = AllocationMode.parse("gen.d1t2+d2").worker_chips(1, 2)
    assert chips == {
        "generation_server/0": [0, 1],
        "model_worker/0": [2],
        "model_worker/1": [3],
    }
    _assert_disjoint_cover(chips, 4)

    # Colocated: generation servers have no partition of their own.
    assert AllocationMode.parse("d4t2").worker_chips(2, 1) == {
        "model_worker/0": list(range(8))
    }
    # Not divisible among the processes: refused. (Larger than the host:
    # refused where the host is known, tests/system/test_chip_assignment.py.)
    with pytest.raises(ValueError, match="do not split evenly"):
        AllocationMode.parse("gen.d3+d1").worker_chips(2, 1)


def test_param_version_roundtrip(tmp_path):
    cfg = small_cfg()
    params = init_params(cfg, jax.random.PRNGKey(2))
    root = str(tmp_path / "realloc")
    save_param_version(params, root, 0)
    save_param_version(params, root, 1, meta={"step": 10})
    assert latest_param_version(root) == 1
    loaded = load_param_version(root, 1)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    save_param_version(params, root, 2)
    gc_param_versions(root, keep_latest=1)
    assert latest_param_version(root) == 2
    assert load_param_version(root, 2) is not None
    with pytest.raises(FileNotFoundError):
        load_param_version(root, 0)


def test_critic_head_fits_tensor_mesh():
    # [D, 1] head cannot shard its size-1 dim over tensor; spec must degrade.
    from areal_tpu.parallel.sharding import fit_spec_to_shape
    mesh = make_mesh(MeshSpec.parse("d2t4"))
    fitted = fit_spec_to_shape(P("fsdp", "tensor"), (32, 1), mesh)
    assert fitted == P("fsdp", None)
    cfg = small_cfg()
    cfg.is_critic = True
    params = init_params(cfg, jax.random.PRNGKey(5))
    sharded = shard_params(params, mesh)  # must not raise
    assert sharded["head"]["weight"].shape == (32, 1)


def test_moe_fsdp_fallback_specs():
    """When num_experts doesn't divide fsdp, expert weights must fall
    back to hidden-dim ZeRO sharding, never silent replication (the
    expert leaves are the bulk of model memory)."""
    from areal_tpu.parallel.sharding import fitted_param_spec

    mesh = make_mesh(MeshSpec.parse("f2t2"), jax.devices()[:4])
    # E=4 divides fsdp=2: the expert dim shards.
    assert fitted_param_spec(
        "layers/mlp/w_gate", (2, 4, 32, 64), mesh
    ) == P(None, "fsdp", None, "tensor")
    # E=3 does not: hidden dim takes the fsdp shard instead.
    assert fitted_param_spec(
        "layers/mlp/w_gate", (2, 3, 32, 64), mesh
    ) == P(None, None, "fsdp", "tensor")
    assert fitted_param_spec(
        "layers/mlp/w_up", (2, 3, 32, 64), mesh
    ) == P(None, None, "fsdp", "tensor")
    assert fitted_param_spec(
        "layers/mlp/w_down", (2, 3, 64, 32), mesh
    ) == P(None, None, "tensor", "fsdp")


def test_fitted_param_spec_matches_devices_indices_map():
    """spec_slices (the weight plane's byte slicer) and
    NamedSharding.devices_indices_map (what the engine actually places)
    must agree per device for every MoE leaf shape — including the
    indivisible-E ZeRO fallback."""
    from jax.sharding import NamedSharding

    from areal_tpu.parallel.sharding import fitted_param_spec, spec_slices

    mesh = make_mesh(MeshSpec.parse("f2t2"), jax.devices()[:4])
    cases = [
        ("layers/mlp/w_gate", (2, 4, 32, 64)),   # EP-shardable
        ("layers/mlp/w_gate", (2, 3, 32, 64)),   # ZeRO fallback
        ("layers/mlp/w_down", (2, 3, 64, 32)),   # fallback, F/D swapped
        ("layers/mlp/router", (2, 32, 4)),       # non-expert leaf
        ("layers/attn/wq", (2, 32, 32)),
    ]
    sizes = dict(mesh.shape)
    for path, shape in cases:
        spec = fitted_param_spec(path, shape, mesh)
        idx_map = NamedSharding(mesh, spec).devices_indices_map(shape)
        for idx, dev in np.ndenumerate(mesh.devices):
            coords = dict(zip(mesh.axis_names, map(int, idx)))
            want = [
                (sl.start or 0, sl.stop if sl.stop is not None else d)
                for sl, d in zip(idx_map[dev], shape)
            ]
            got = spec_slices(spec, shape, sizes, coords)
            assert got == want, (path, shape, dev)
