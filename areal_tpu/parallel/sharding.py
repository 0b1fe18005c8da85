"""GSPMD partition rules: megatron-equivalent shardings by annotation.

Replaces the reference's hand-written tensor/sequence-parallel modules
(realhf/impl/model/parallelism/tensor_parallel/modules.py — Column/Row
parallel linears, parallel embedding, vocab-parallel CE) with
`PartitionSpec`s over the (data, fsdp, seq, tensor) mesh:

- attention qkv projections: column-parallel  -> output dim on `tensor`
- attention output proj:     row-parallel     -> input dim on `tensor`
- MLP gate/up:               column-parallel; down: row-parallel
- embedding + LM head:       vocab on `tensor` (vocab-parallel CE falls out
  of the sharded logits + psum XLA inserts for logsumexp)
- every weight's other big dim on `fsdp` (ZeRO-3-style param sharding);
  optimizer state inherits these specs (ZeRO-1/2)
- activations: rows on (data, fsdp), sequence dim on `seq` (context
  parallelism; megatron-SP's activation sharding falls out here too)

The reference's parameter-flattening + interval scatter/gather machinery
(flatten_param.py, csrc/interval_op) has no TPU counterpart by design:
resharding is `jax.device_put` between NamedShardings (see realloc.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Dict[str, Any]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_partition_spec(path: str, ndim: int) -> P:
    """PartitionSpec for one parameter, by pytree path.

    Layer-stacked params have a leading L axis (never sharded). Biases and
    norms are small: replicated.
    """
    name = path.split("/")[-1]
    if "embedding" in path:
        return P("tensor", "fsdp")  # [V, D]
    if path.startswith("head") or "/head/" in path or path == "head/weight":
        return P("fsdp", "tensor")  # [D, V] or [D, 1]
    # latent attention's up-projections [L, rank, heads x size] go with
    # the column-parallel matrices; its down-projections, whose outputs
    # an RMSNorm reads whole, are ZeRO-sharded on the hidden dim alone
    if name in ("wq_a", "wkv_a"):
        return P(None, "fsdp", None)
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_in", "wq_b", "wkv_b"):
        if ndim == 4:
            # MoE stacked experts [L, E, D, F]: expert parallelism —
            # E shards over the ZeRO/fsdp axis (the einsum dispatch
            # "tec,td->ecd" with tokens on (data,fsdp) and experts on
            # fsdp makes XLA emit the token all-to-all; DeepSeek-style
            # EP-over-DP without custom collectives), F stays
            # column-parallel on tensor.
            return P(None, "fsdp", None, "tensor")
        return P(None, "fsdp", "tensor")  # [L, D, out]: column parallel
    if name in ("wo", "w_down", "w_out"):
        if ndim == 4:
            return P(None, "fsdp", "tensor", None)  # [L, E, F, D]
        return P(None, "tensor", "fsdp")  # [L, in, D]: row parallel
    if name in ("bq", "bk", "bv", "b_gate", "b_up", "b_in"):
        return P(None, "tensor")  # [L, out]
    # A state-space mixer (ops/ssm.py): its two projections ZeRO-sharded
    # on the hidden dim; [z | xBC | dt] and the heads stay whole on every
    # device (the scan over a row's chunks has no tensor-parallel form
    # here), so `tensor` shards nothing of it.
    if name == "in_proj":
        return P(None, "fsdp", None)  # [L, D, z + xBC + dt]
    if name == "out_proj":
        return P(None, None, "fsdp")  # [L, d_inner, D]
    # conv_w, conv_b, A_log, D, dt_bias, the gated norm; the selective
    # scan's x_proj [L, d_inner, rank + 2 N] and dt_proj [L, rank, d_inner]
    # (small, and the scan has every channel on every device); differential
    # attention's lambda vectors and sub_norm; a gated memory unit's two
    # matrices are `w_in` / `w_out` above;
    # norms, small biases (b_down/b_out [L, D]), router [L, D, E],
    # q_norm/k_norm: replicated.
    return P(*([None] * ndim))


def _moe_fsdp_fallback(name: str, ndim: int) -> Optional[P]:
    """When num_experts doesn't divide the fsdp axis, EP is impossible —
    but the expert weights are the bulk of model memory, so ZeRO-3 must
    not silently degrade to full replication: shard the hidden dim on
    fsdp instead."""
    if ndim != 4:
        return None
    if name in ("w_gate", "w_up"):
        return P(None, None, "fsdp", "tensor")  # [L, E, D, F]
    if name == "w_down":
        return P(None, None, "tensor", "fsdp")  # [L, E, F, D]
    return None


def _axis_size(mesh, entry) -> int:
    """Mesh-axis product for one PartitionSpec entry. ``mesh`` is a
    jax Mesh OR a plain ``{axis: size}`` mapping — the latter keeps the
    slice-resolution path (weight-plane shard manifests) usable without
    constructing devices."""
    if entry is None:
        return 1
    sizes = getattr(mesh, "shape", mesh)
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= sizes[n]
    return size


def fit_spec_to_shape(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharded axes a dimension cannot honor (not divisible by the
    mesh-axis size — e.g. the critic head's [D, 1] output dim)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    fitted = []
    for dim, entry in zip(shape, entries):
        fitted.append(entry if dim % _axis_size(mesh, entry) == 0 else None)
    return P(*fitted)


def fitted_param_spec(path: str, shape, mesh) -> P:
    """The PartitionSpec a parameter actually gets on this mesh: the
    megatron-style rule spec, fitted to the shape (indivisible axes
    dropped), with the MoE ZeRO fallback applied. ``mesh`` may be a jax
    Mesh or an ``{axis: size}`` mapping (see ``_axis_size``) — the
    SINGLE source of truth shared by ``param_shardings`` (device
    placement) and the weight plane's shard manifests (byte slicing),
    so what a shard manifest ships is exactly what the engine's
    NamedSharding will place."""
    spec = param_partition_spec(path, len(shape))
    fitted = fit_spec_to_shape(spec, shape, mesh)
    if len(spec) > 1 and spec[1] == "fsdp" and fitted[1] is None:
        # Expert dim indivisible by fsdp: fall back to hidden-dim
        # ZeRO sharding rather than replicating the expert weights.
        alt = _moe_fsdp_fallback(path.split("/")[-1], len(shape))
        if alt is not None:
            fitted = fit_spec_to_shape(alt, shape, mesh)
    return fitted


def param_shardings(params: Params, mesh: Mesh) -> Params:
    """Pytree of NamedShardings matching `params`' structure."""

    def one(path, leaf):
        return NamedSharding(
            mesh, fitted_param_spec(_path_str(path), leaf.shape, mesh)
        )

    return jax.tree_util.tree_map_with_path(one, params)


def spec_slices(spec: P, shape, axis_sizes, coords):
    """Per-dimension ``(start, stop)`` of one mesh coordinate's shard of
    a row-major array under ``spec`` — pure integer math, mirroring
    ``NamedSharding.devices_indices_map`` (tuple entries shard over the
    product with the FIRST named axis varying slowest).

    ``axis_sizes``: {axis: size}; ``coords``: {axis: coordinate}. The
    caller passes a spec already fitted to the shape
    (``fitted_param_spec``): every sharded dim must divide evenly."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        size = _axis_size(axis_sizes, entry)
        if size == 1:
            out.append((0, int(dim)))
            continue
        if dim % size != 0:
            raise ValueError(
                f"dim {dim} not divisible by mesh extent {size} "
                f"for entry {entry!r} (spec not fitted?)"
            )
        names = entry if isinstance(entry, tuple) else (entry,)
        c = 0
        for n in names:
            c = c * axis_sizes[n] + coords[n]
        shard = dim // size
        out.append((c * shard, (c + 1) * shard))
    return out


def leaf_shard_slices(path: str, shape, axis_sizes, coords):
    """(start, stop) per dim of this mesh coordinate's shard of one
    parameter, by pytree path — fitted spec + slice math in one step."""
    return spec_slices(
        fitted_param_spec(path, shape, axis_sizes), shape, axis_sizes, coords
    )


def tensor_shard_slices(path: str, shape, degree: int, rank: int):
    """Shard slices for rank ``rank`` of a ``degree``-way TENSOR-parallel
    group (the serving-mesh case: every other axis is 1). Replicated
    leaves come back as full-extent slices — each rank fetches its own
    copy of norms/biases, the small +ε on top of payload/TP."""
    if degree < 1 or not (0 <= rank < degree):
        raise ValueError(f"bad tensor shard rank {rank}/{degree}")
    sizes = {"data": 1, "fsdp": 1, "seq": 1, "tensor": degree}
    coords = {"data": 0, "fsdp": 0, "seq": 0, "tensor": rank}
    return leaf_shard_slices(path, shape, sizes, coords)


def expert_shard_slices(path: str, shape, degree: int, rank: int):
    """Shard slices for rank ``rank`` of a ``degree``-way EXPERT-parallel
    group: stacked expert leaves ([L, E, ...] MoE weights, the bulk of
    an expert-dominated checkpoint) slice E ``degree`` ways; every other
    leaf comes back full-extent — each gserver fetches all attention /
    norm / router weights but only its OWN experts (ROADMAP item 5).
    An expert dim indivisible by ``degree`` degrades that leaf to
    full-extent (replicated) rather than slicing something else: the
    stream stays byte-correct, just without the 1/EP saving."""
    if degree < 1 or not (0 <= rank < degree):
        raise ValueError(f"bad expert shard rank {rank}/{degree}")
    spec = param_partition_spec(path, len(shape))
    if (
        len(shape) == 4 and len(spec) > 1 and spec[1] == "fsdp"
        and shape[1] % degree == 0
    ):
        sizes = {"data": 1, "fsdp": degree, "seq": 1, "tensor": 1}
        coords = {"data": 0, "fsdp": rank, "seq": 0, "tensor": 0}
        return spec_slices(P(None, "fsdp"), shape, sizes, coords)
    return [(0, int(d)) for d in shape]


def compose_shard_slices(a, b, shape):
    """Intersect two shard-slice lists that slice DISJOINT dims (e.g. a
    TP slice of F and an EP slice of E on the same [L, E, D, F] leaf).
    Per dim, at most one of the two may be a proper sub-slice."""
    out = []
    for (a0, a1), (b0, b1), dim in zip(a, b, shape):
        if (a0, a1) == (0, int(dim)):
            out.append((b0, b1))
        elif (b0, b1) == (0, int(dim)):
            out.append((a0, a1))
        else:
            raise ValueError(
                f"both shardings slice the same dim of {tuple(shape)}: "
                f"{(a0, a1)} vs {(b0, b1)}"
            )
    return out


def shard_params(params: Params, mesh: Mesh) -> Params:
    """Place a host pytree onto the mesh with megatron-equivalent sharding."""
    return jax.device_put(params, param_shardings(params, mesh))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """[R, T] token rows: rows over (data, fsdp), sequence over seq."""
    return NamedSharding(mesh, P(("data", "fsdp"), "seq"))


def activation_constraint(x, mesh: Mesh):
    """Constrain [R, T, D] activations inside jit."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    )


def logits_constraint(x, mesh: Mesh):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(("data", "fsdp"), "seq", "tensor"))
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
