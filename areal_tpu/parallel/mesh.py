"""Device mesh construction and device-partition allocation.

TPU-native replacement for the reference's process-topology + NCCL-group
machinery (realhf/base/topology.py grids, realhf/impl/model/comm/
global_comm.py): parallelism is expressed as a `jax.sharding.Mesh` with
axes (data, fsdp, seq, tensor) and GSPMD inserts the collectives. Device
*partitions* (disjoint sets of chips for generation vs training, the
reference's `sglang.dXpYmZ+dApBmC` decoupled allocation) are contiguous
slices of the host's chips, cut per worker process by
`AllocationMode.worker_chips`: a chip has one owner.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.base.topology import MeshSpec

MESH_AXES = ("data", "fsdp", "seq", "tensor")


def make_mesh(spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (data, fsdp, seq, tensor) mesh from a MeshSpec.

    Axis order puts `tensor` innermost so tensor-parallel collectives ride
    the fastest ICI links, matching megatron convention.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if spec.size != len(devices):
        raise ValueError(
            f"mesh spec {spec} needs {spec.size} devices, got {len(devices)}"
        )
    arr = np.array(devices).reshape(spec.data, spec.fsdp, spec.seq, spec.tensor)
    return Mesh(arr, MESH_AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    d = device or jax.devices()[0]
    return Mesh(np.array([d]).reshape(1, 1, 1, 1), MESH_AXES)


@dataclasses.dataclass
class AllocationMode:
    """Parsed allocation DSL (counterpart of the reference's
    `sglang.d4m1+d2m2`-style strings, realhf/experiments/common/utils.py:289).

    Forms:
    - "d2t4"             : one shared partition for everything (sync/global hybrid)
    - "gen.d4t1+d2t2"    : decoupled: first 4 devices generation, next 4 training
    """

    gen_spec: Optional[MeshSpec]
    train_spec: MeshSpec
    decoupled: bool

    @classmethod
    def parse(cls, s: str) -> "AllocationMode":
        s = s.strip()
        if "+" in s:
            gen_part, train_part = s.split("+", 1)
            if "." in gen_part:
                prefix, gen_part = gen_part.split(".", 1)
                if prefix not in ("gen", "sglang", "jax"):
                    raise ValueError(f"unknown allocation prefix {prefix!r} in {s!r}")
            return cls(
                gen_spec=MeshSpec.parse(gen_part),
                train_spec=MeshSpec.parse(train_part),
                decoupled=True,
            )
        return cls(gen_spec=None, train_spec=MeshSpec.parse(s), decoupled=False)

    def worker_chips(
        self, n_gen_servers: int, n_train_workers: int
    ) -> Dict[str, List[int]]:
        """Host chip indices owned by each chip-holding worker process,
        keyed by worker name.

        A chip belongs to one process, so the allocation is cut into
        per-process sets here, in the launcher, and never by indexing a
        shared ``jax.devices()``: the gen partition comes first and
        generation server i takes its i-th equal slice; the train
        partition follows and model worker j takes its j-th equal slice.
        The sets are disjoint and cover the allocation by construction
        (whether they fit the host is the controller's check, where the
        host is known). Inside a worker, ``device_ids`` index its own
        (local) devices.

        A colocated allocation ("d1f2") gives generation servers no
        entry: they have no partition of their own.
        """
        gen_size = self.gen_spec.size if self.decoupled else 0
        out: Dict[str, List[int]] = {}
        if self.decoupled and n_gen_servers:
            _slice_evenly(out, "generation_server", 0, gen_size, n_gen_servers)
        _slice_evenly(
            out, "model_worker", gen_size, self.train_spec.size, n_train_workers
        )
        return out


def _slice_evenly(out, role: str, start: int, size: int, n: int) -> None:
    if n < 1 or size % n:
        raise ValueError(
            f"{size} chips do not split evenly among {n} {role} processes"
        )
    per = size // n
    for i in range(n):
        out[f"{role}/{i}"] = list(range(start + i * per, start + (i + 1) * per))
