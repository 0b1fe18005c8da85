"""Mixture-of-experts layer: top-k router + capacity/dropless dispatch.

Counterpart of the reference's MoE modules (realhf/impl/model/modules/moe/
router.py:242, token_dispatcher.py, experts.py) rebuilt TPU-first: instead
of the reference's permute/unpermute token dispatcher + grouped GEMM, the
classic GShard/Switch einsum formulation — dispatch/combine tensors of
shape [T, E, C] contracted against stacked expert weights [E, D, F] — so
the whole layer is three large einsums that XLA tiles onto the MXU, and
expert parallelism falls out of sharding E over the `fsdp` mesh axis
(parallel/sharding.py: dispatch contracts token-sharded activations
against expert-sharded weights, so GSPMD inserts the token all-to-all —
the reference has no EP at all).

Load-balance aux loss and router z-loss follow the Switch/ST-MoE
formulas (reference router.py aux_loss/z_loss). Tokens beyond an
expert's capacity are dropped (contribute zero), standard for the
einsum formulation; capacity_factor controls the drop rate, and the
realized drop rate is returned in the aux dict (surfaced in train stats
as moe_drop_rate).

The alternative `dispatch="dropless"` path matches the reference
dispatcher's zero-drop guarantee (token_dispatcher.py) the TPU way:
tokens sort by expert id and the expert FFN runs as `lax.ragged_dot`
grouped matmuls with per-expert group sizes — static shapes, no
capacity buffer, exact at any router skew. On an expert-parallel mesh
(fsdp > 1 with num_experts divisible) the dropless path now runs under
`shard_map` over the fsdp axis (`_moe_mlp_ep`): each shard holds only
its E/ep experts, the (token, choice) streams are exchanged with an
all-gather + psum_scatter pair (the static-shape stand-in for a ragged
all-to-all), and the per-shard grouped matmul runs
local experts only — so the zero-drop guarantee and 1/ep expert HBM
coexist. Tradeoff: the gather-side grouped matmul touches every
exchanged row (dummy zero-weight groups absorb non-local rows), so
dropless-EP spends up to ep x the expert-FFN FLOPs of capacity
dispatch for its zero drops and 1/ep weight memory — measured, not
assumed, by the `moe_scaling` bench phase (docs/perf_notes.md Round
17), with capacity dispatch kept as the FLOPs-optimal EP baseline.

`MoEConfig.experts_held` makes the layer one share of an expert-parallel
layer on a chip of its own (`_held_experts`): it routes over all
experts, computes the experts it holds and adds nothing for the rest,
with no exchange and nothing that stands in for the absent chips. The
(token, choice) pairs of held experts are compacted to the front of one
sort and walked in row tiles of one expert each by a loop whose trip
count is the number of tiles the run holds, forward and backward, each
tile's products dense: no pair is dropped at any skew and the work
follows the pairs held, not all k x T. The router's published sigmoid form
(`score_func`), the selection bias and the shared expert live here too.
Experts, routed and shared, are gated (`w_gate`, `w_up`, `w_down`) or
plain (`w_in`, `w_out`) by `cfg.mlp_type`, under `cfg.activation`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.base import env_registry
from areal_tpu.models.config import TransformerConfig


def activation_fn(name: str):
    """silu, gelu, or relu2: the squared ReLU."""
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def expert_mats(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The matrices of one expert MLP (routed, shared or dense), by
    `mlp_type`: gated `act(x w_gate) * (x w_up) w_down`, plain
    `act(x w_in) w_out`."""
    return ("w_gate", "w_up", "w_down") if cfg.mlp_type == "gated" else ("w_in", "w_out")


def _grouped_ffn(xs, weights, gs, act):
    """The experts' MLP over rows sorted by expert, `gs` rows a group:
    `lax.ragged_dot` against the two (plain) or three (gated) stacks."""
    if len(weights) == 3:
        wg, wu, wd = weights
        h = act(jax.lax.ragged_dot(xs, wg, gs)) * jax.lax.ragged_dot(xs, wu, gs)
        return jax.lax.ragged_dot(h, wd, gs)
    w_in, w_out = weights
    return jax.lax.ragged_dot(act(jax.lax.ragged_dot(xs, w_in, gs)), w_out, gs)


def moe_ep_degree(cfg: TransformerConfig, mesh, x_shape=None) -> int:
    """Expert-parallel degree the dropless path can use on this mesh.

    The fsdp extent when it divides num_experts (the sharding.py EP
    layout: stacked expert weights put E on fsdp) AND the activation
    shape divides the mesh's token tiling; else 1 — no shard_map, the
    indivisible case falls through to GSPMD with sharding.py's
    hidden-dim ZeRO fallback (ragged_dot contracts an UNsharded expert
    axis there, which is legal)."""
    if cfg.moe is None or mesh is None:
        return 1
    sizes = getattr(mesh, "shape", {})
    ep = int(sizes.get("fsdp", 1))
    if ep <= 1 or cfg.moe.num_experts % ep != 0:
        return 1
    if x_shape is not None:
        if len(x_shape) != 3:
            return 1
        rows = int(sizes.get("data", 1)) * ep
        seq = int(sizes.get("seq", 1))
        if x_shape[0] % rows != 0 or x_shape[1] % seq != 0:
            return 1
    return ep


def decode_moe_overrides(cfg: TransformerConfig) -> Tuple[str, Optional[float]]:
    """(dispatch, capacity_factor) for DECODE-time MoE calls.

    At decode T is a handful of tokens, so the training capacity formula
    `C = max(1, capacity_factor*T*k/E)` quantizes badly — C=1 drops at
    the slightest router skew while larger T wastes HBM. Default routes
    decode through the dropless grouped matmul (exact at any skew, and
    trivially cheap at decode row counts). AREAL_MOE_DECODE_DISPATCH
    ('model' = follow cfg.moe.dispatch) and AREAL_MOE_DECODE_CAPACITY
    (capacity_factor override when the resolved dispatch is 'capacity')
    are trace-time A/B hooks."""
    dispatch = env_registry.get_str("AREAL_MOE_DECODE_DISPATCH") or "dropless"
    if dispatch == "model":
        dispatch = cfg.moe.dispatch
    if dispatch not in ("capacity", "dropless"):
        raise ValueError(
            f"AREAL_MOE_DECODE_DISPATCH={dispatch!r}: expected "
            f"'dropless', 'capacity', or 'model'"
        )
    cap = env_registry.get_float("AREAL_MOE_DECODE_CAPACITY")
    return dispatch, cap


def _router(xt, router_w, moe, expert_bias=None):
    """fp32 router: probs, top-k gates, expert choices. "softmax":
    probabilities over all experts, the k largest, renormalised.
    "sigmoid": scores s = sigmoid(logits); the k experts with the largest
    s + expert_bias (a buffer: no gradient reaches it), weighted by the
    bare s over their sum (`route_norm`) times the scaling factor."""
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
    if moe.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores
        if expert_bias is not None:
            select = scores + jax.lax.stop_gradient(
                expert_bias.astype(jnp.float32))
        _, top_e = jax.lax.top_k(select, moe.top_k)  # [T, k]
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        if moe.route_norm:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + moe.route_norm_eps)
        return logits, scores, top_p * moe.routed_scaling_factor, top_e
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)  # [T, k]
    if moe.routed_scaling_factor != 1.0:
        top_p = top_p * moe.routed_scaling_factor
    # renormalize the selected gates (mixtral convention)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return logits, probs, top_p, top_e


def _router_stats(logits, probs, top_e, E, token_mask=None):
    """(f_e, P_e, z, entropy) over this shard's tokens: the real ones
    where `token_mask` says which they are (a padding cell's routing, or
    the zeros a band no token is in reads, `ops/band_loop.stretch`, is no
    one's to balance), every cell without.

    f_e is the per-expert fraction of (token, choice) routings — the
    expert-load histogram surfaced in telemetry; the Switch loss is
    E * sum_e f_e * P_e."""
    if token_mask is None:
        mean = lambda a: jnp.mean(a, axis=tuple(range(a.ndim - 1)) if a.ndim > 1 else None)
    else:
        w = token_mask.reshape(-1).astype(jnp.float32)
        w = w / jnp.maximum(jnp.sum(w), 1.0)
        mean = lambda a: w @ (a if a.ndim < 3 else jnp.mean(a, axis=1))
    f_e = mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32))
    P_e = mean(probs)
    z = mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    entropy = mean(-jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))
    return f_e, P_e, z, entropy


def _moe_mlp_ep(
    x: jnp.ndarray,  # [R, T, D]
    mp: Dict[str, Any],
    cfg: TransformerConfig,
    cdt,
    mesh,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel dropless dispatch under shard_map over `fsdp`.

    Each shard routes its LOCAL tokens, the (token, choice) streams are
    all-gathered across the fsdp axis (within each (data, seq) group),
    the shard's grouped matmul runs ONLY its E/ep experts — rows routed
    to other shards' experts fall into dummy zero-weight groups and
    contribute exact zeros — and psum_scatter returns each token's
    combined output to its home shard. Zero drops at any skew, expert
    weights never all-gathered. The F dim stays column-parallel on
    `tensor` when divisible (psum over tensor closes the row-parallel
    w_down)."""
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    E, k = moe.num_experts, moe.top_k
    R, T, D = x.shape
    ep = mesh.shape["fsdp"]
    eloc = E // ep
    F = mp["w_gate"].shape[-1]
    tp = mesh.shape.get("tensor", 1)
    tp_shards = tp if (tp > 1 and F % tp == 0) else 1
    rows = ("data", "fsdp")
    n_local = (R // (mesh.shape.get("data", 1) * ep)) * (
        T // mesh.shape.get("seq", 1)
    )
    # Per-device exchange bytes this layer (telemetry, trace-time
    # constant): all-gather receives (ep-1) peers' activation rows and
    # (choice, gate, token) streams; the reduce-scatter combine sends
    # the same activation volume back.
    a2a_bytes = float(
        (ep - 1) * n_local * (2 * D * jnp.dtype(cdt).itemsize + k * 12)
    )
    act = activation_fn(cfg.activation)
    f_spec = "tensor" if tp_shards > 1 else None
    red = ("data", "fsdp", "seq")  # equal-count shards: pmean is exact

    def body(xb, router_w, wg, wu, wd):
        # xb [r, t, D] local block; wg/wu [eloc, D, Floc]; wd [eloc, Floc, D]
        xt = xb.reshape(-1, D)
        n = xt.shape[0]
        logits, probs, top_p, top_e = _router(xt, router_w, moe)
        choice_e = top_e.T.reshape(-1)  # [kn] choice-major
        gate = top_p.T.reshape(-1)
        tok = jnp.tile(jnp.arange(n), k)

        # Exchange: every EP peer of this (data, seq) group sees the full
        # token set; token slots are offset by source shard so the
        # combine can scatter straight back.
        me = jax.lax.axis_index("fsdp")
        xg = jax.lax.all_gather(xt.astype(cdt), "fsdp", axis=0, tiled=True)
        ceg = jax.lax.all_gather(choice_e, "fsdp", axis=0, tiled=True)
        gg = jax.lax.all_gather(gate, "fsdp", axis=0, tiled=True)
        tokg = jax.lax.all_gather(
            tok + me * n, "fsdp", axis=0, tiled=True
        )

        order = jnp.argsort(ceg)  # stable: keeps (shard, choice) priority
        sizes = jnp.bincount(ceg, length=E)
        xs = xg[tokg[order]]  # [ep*kn, D] sorted by expert id

        # Grouped matmul over LOCAL experts only: rows of experts before/
        # after this shard's block land in dummy zero-weight prefix/
        # suffix groups — their outputs are exact zeros, so the combine
        # needs no mask and psum_scatter sums shards' disjoint
        # contributions.
        e0 = me * eloc
        prefix = jnp.sum(jnp.where(jnp.arange(E) < e0, sizes, 0))
        local_sizes = jax.lax.dynamic_slice(sizes, (e0,), (eloc,))
        suffix = xs.shape[0] - prefix - jnp.sum(local_sizes)
        gsizes = jnp.concatenate(
            [prefix[None], local_sizes, suffix[None]]
        ).astype(jnp.int32)
        zgu = jnp.zeros((1,) + wg.shape[1:], cdt)
        zd = jnp.zeros((1,) + wd.shape[1:], cdt)
        wgp = jnp.concatenate([zgu, wg.astype(cdt), zgu], 0)
        wup = jnp.concatenate([zgu, wu.astype(cdt), zgu], 0)
        wdp = jnp.concatenate([zd, wd.astype(cdt), zd], 0)
        h = act(jax.lax.ragged_dot(xs, wgp, gsizes))
        h = h * jax.lax.ragged_dot(xs, wup, gsizes)
        ys = jax.lax.ragged_dot(h, wdp, gsizes)  # [ep*kn, D]

        yg = (
            jnp.zeros((xg.shape[0], D), cdt)
            .at[tokg[order]]
            .add(gg[order].astype(cdt)[:, None] * ys)
        )
        y = jax.lax.psum_scatter(
            yg, "fsdp", scatter_dimension=0, tiled=True
        )  # [n, D]: this shard's tokens, summed over expert shards
        if tp_shards > 1:
            y = jax.lax.psum(y, "tensor")

        f_e, P_e, z, entropy = _router_stats(logits, probs, top_e, E)
        f_e = jax.lax.pmean(f_e, red)
        P_e = jax.lax.pmean(P_e, red)
        aux = {
            "load_balance_loss": E * jnp.sum(f_e * P_e),
            "z_loss": jax.lax.pmean(z, red),
            "drop_rate": jnp.zeros((), jnp.float32),
            "router_entropy": jax.lax.pmean(entropy, red),
            "expert_load": f_e,
            "a2a_bytes": jnp.asarray(a2a_bytes, jnp.float32),
        }
        return y.reshape(xb.shape), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(rows, "seq", None),
            P(None, None),
            P("fsdp", None, f_spec),
            P("fsdp", None, f_spec),
            P("fsdp", f_spec, None),
        ),
        out_specs=(
            P(rows, "seq", None),
            {k_: P() for k_ in AUX_KEYS},
        ),
        check_vma=False,
    )(x, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"])
    return y, aux


AUX_KEYS = ("load_balance_loss", "z_loss", "drop_rate", "router_entropy",
            "expert_load", "a2a_bytes")
# What a share of the experts adds (`experts_held`): the real (token,
# choice) pairs routed to experts held here, the rows of the tiles that
# ran over them, and the chunks of tiles whose rows went to their tokens
# together (`_add_rows` ran once a chunk forward and once backward).
HELD_AUX_KEYS = ("pairs_held", "rows_run", "chunks_run")


def moe_aux_zeros(cfg: TransformerConfig) -> Dict[str, jnp.ndarray]:
    """The sums `forward` carries through its layers, at zero: a dense
    model carries them too (one entry of `expert_load`)."""
    moe = cfg.moe
    aux = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    aux["expert_load"] = jnp.zeros((moe.num_experts if moe else 1,), jnp.float32)
    if moe is not None and moe.experts_held is not None:
        aux.update({k: jnp.zeros((), jnp.float32) for k in HELD_AUX_KEYS})
    return aux


# Rows of one tile of the held pairs, and rows of one chunk of tiles. A
# tile belongs to one expert, so its products are dense and every tile
# re-reads that expert's matrices and adds into its float32 gradients:
# the smaller the tile, the fewer rows an expert's short last tile runs
# empty and the more often the matrices are read. Measured (PERF.md
# section 6, PR 37): the held part alone under mildly skewed routing is
# fastest at tiles of 512, by 6-12 % over 256; in the trinity cell, whose
# routers skew more, 256 and 384 come before 512 (15.90, 15.85 and 16.00
# s of device time a pass) and 256 runs the fewest empty rows (137 % of
# the pairs held, against 158 and 179). A chunk's rows go to their tokens
# together (`_add_rows`): a tile at a time that pass cost ten times the
# tile's products. Each call sorts and gathers a whole chunk, rows held
# or not (0.05 ms at 8,192 rows of 2,048, 0.07 at 12,288, 0.11 at
# 16,384), and reads and writes every band of tokens a row lands in (0.25
# ms for the 8.5k real tokens of a row of 16,384, few rows or many), so a
# layer's rows should fit one chunk and the chunk be no larger than that
# takes. Measured with the kernel (PERF.md section 6, PR 43: the held
# part alone, forward and backward, ms at chunks of 8,192 / 12,288 /
# 16,384): 12.44 / 11.96 / 11.94 at 8.7k rows, 12.23 / 11.68 / 11.88 at
# 10.8k, 8.55 / 8.72 / 8.82 at 6.4k, 9.52 / 9.46 / 9.66 at 3.8k rows of
# 2,688.
_HELD_ROW_TILE = 256
_HELD_CHUNK_ROWS = 12288


def _expert_ffn(xs, ws, act):
    """One expert's MLP over its rows: gated `act(x w_gate) * (x w_up)
    w_down` (three matrices) or plain `act(x w_in) w_out` (two)."""
    if len(ws) == 3:
        wg, wu, wd = ws
        return (act(xs @ wg) * (xs @ wu)) @ wd
    w_in, w_out = ws
    return act(xs @ w_in) @ w_out


def _held_tiles(sizes, n_rows: int):
    """The held pairs, sorted by expert, cut into tiles of
    `_HELD_ROW_TILE` rows that hold one expert's pairs each (an expert's
    last tile is short). `sizes`: pairs an expert. Returns the number of
    tiles, a value of the run, and for every tile up to the static bound
    (`n_rows` pairs in all) its expert, its first row and the pairs it
    holds (0 past the tiles there are)."""
    R, n_held = _HELD_ROW_TILE, sizes.shape[0]
    ends = jnp.cumsum(sizes)
    per = -(-sizes // R)  # tiles an expert
    tile_ends = jnp.cumsum(per)
    i = jnp.arange(-(-n_rows // R) + n_held, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(tile_ends[None, :] <= i[:, None], axis=1), n_held - 1)
    lo = (ends - sizes)[e] + (i - (tile_ends - per)[e]) * R
    return tile_ends[-1], (e.astype(jnp.int32), lo.astype(jnp.int32),
                           jnp.clip(ends[e] - lo, 0, R).astype(jnp.int32))


def _tile_rows(i, tiles, pairs, gate, n_tok: int):
    """Tile i: its expert, its rows' pairs, tokens and weights, and which
    rows hold a pair of its own. A row past them (another expert's pair,
    or one not held) is sent to the last token: what it adds there is a
    zero."""
    R = _HELD_ROW_TILE
    e, lo, count = (a[i] for a in tiles)
    valid = jnp.arange(R, dtype=jnp.int32) < count
    pair = jax.lax.dynamic_slice(pairs, (lo,), (R,))
    return e, pair, jnp.where(valid, pair % n_tok, n_tok - 1), gate[pair], valid


def _tile_out(act, xs, ws, w, valid):
    """A tile's weighted results, [R, D]. Masked on the way in and on the
    way out, and before weighing: what a row past the tile's pairs holds
    need not be finite, and 0 x it (the weight's gradient, were the mask
    applied after) would not be 0."""
    with jax.named_scope("moe_experts"):
        ys = _expert_ffn(jnp.where(valid[:, None], xs, 0), ws, act)
    with jax.named_scope("moe_combine"):
        return w.astype(ys.dtype)[:, None] * jnp.where(valid[:, None], ys, 0)


def _expert_of(weights, e):
    """Expert e's matrices, cut from the stacks."""
    return tuple(jax.lax.dynamic_index_in_dim(m, e, keepdims=False) for m in weights)


def _add_rows(y, rows, tok, n_rows):
    """y [T, D] with row r of `rows` added to token `tok[r]`, for the
    first `n_rows` rows (the rest hold what an earlier chunk left). By
    token, then by row: one sort of distinct keys (token x rows + row)
    and the rows taken in that order, nothing past `n_rows`. On the chip
    those go to a kernel that sums them band of tokens by band
    (`ops/pallas/segment_add.py`: only the bands a row lands in are read
    and written); elsewhere, and at shapes the kernel does not take (the
    tests' toy tiles), to a scatter-add that is told its indices are
    sorted, which on the chip reads and writes all of `y` and walks the
    rows one at a time (1.55 ms for 8,192 rows into [16384, 2048]). Left
    to itself the chip's compiler sorts the indices of a scatter with the
    rows as a second operand, and takes 8 s over that sort at 20k rows
    (1.8 s over this scatter, 1.5 over this sort)."""
    from areal_tpu.ops.pallas import segment_add

    b = rows.shape[0]
    assert y.shape[0] * b < 2**31
    row = jnp.arange(b, dtype=jnp.int32)
    keys = jax.lax.sort(jnp.where(row < n_rows, tok, y.shape[0] - 1) * b + row, is_stable=False)
    row = keys % b  # the first n_rows rows stay the first n_rows
    tok, rows = keys // b, rows[row]
    if jax.default_backend() == "tpu" and segment_add.kernel_ok(*y.shape, b):
        return segment_add.add_sorted_rows(y, rows, tok, n_rows)
    return y.at[tok].add(
        jnp.where((row < n_rows)[:, None], rows, 0).astype(y.dtype), indices_are_sorted=True)


def _chunks(n_tiles):
    """(tiles a chunk, chunks that hold one of `n_tiles` tiles)."""
    G = max(1, _HELD_CHUNK_ROWS // _HELD_ROW_TILE)
    return G, -(-n_tiles // G)


def _chunk_loop(n_tiles, tile, carry, dtype):
    """Walk the tiles a chunk at a time: `tile(i, carry) -> (carry, rows,
    tok)` runs tile i and hands back the [R, D] rows of `dtype` it has
    for tokens `tok`; a chunk's rows are added to `carry[0]`, [T, D],
    together. Both loops' trip counts are values of the run: the chunks
    that hold a tile, and the tiles a chunk holds."""
    R = _HELD_ROW_TILE
    G, n_chunks = _chunks(n_tiles)

    def chunk(c, state):
        def one(j, state):
            carry, buf, toks = state
            carry, rows, tok = tile(c * G + j, carry)
            return (carry, jax.lax.dynamic_update_slice(buf, rows, (j * R, 0)),
                    jax.lax.dynamic_update_slice(toks, tok, (j * R,)))

        held = jnp.minimum(G, n_tiles - c * G)
        carry, buf, toks = jax.lax.fori_loop(0, held, one, state)
        with jax.named_scope("moe_combine"):
            return (_add_rows(carry[0], buf, toks, held * R),) + carry[1:], buf, toks

    # a chunk's rows and their tokens: made once, written a tile at a time
    return jax.lax.fori_loop(0, n_chunks, chunk, (
        carry, jnp.zeros((G * R, carry[0].shape[1]), dtype),
        jnp.zeros((G * R,), jnp.int32)))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _run_tiles(act, xc, weights, gate, pairs, n_tiles, tiles):
    """[T, D] float32: every tile's weighted results added to their
    tokens. The gradient is a second walk over the tiles that computes a
    tile again (`_run_tiles_bwd`): what is kept for it is the inputs,
    nothing a tile made."""
    T = xc.shape[0]

    def tile(i, carry):
        with jax.named_scope("moe_dispatch"):
            e, _, tok, w, valid = _tile_rows(i, tiles, pairs, gate, T)
            xs = xc[tok]
        return carry, _tile_out(act, xs, _expert_of(weights, e), w, valid), tok

    return _chunk_loop(n_tiles, tile, (jnp.zeros(xc.shape, jnp.float32),), xc.dtype)[0]


def _run_tiles_fwd(act, xc, weights, gate, pairs, n_tiles, tiles):
    y = _run_tiles(act, xc, weights, gate, pairs, n_tiles, tiles)
    return y, (xc, weights, gate, pairs, n_tiles, tiles)


def _run_tiles_bwd(act, res, dy):
    xc, weights, gate, pairs, n_tiles, tiles = res
    T = xc.shape[0]
    dyc = dy.astype(xc.dtype)

    def tile(i, carry):
        dx, dws, dg = carry
        with jax.named_scope("moe_dispatch"):
            e, pair, tok, w, valid = _tile_rows(i, tiles, pairs, gate, T)
            xs, d = xc[tok], dyc[tok]
        _, vjp = jax.vjp(lambda xs, ws, w: _tile_out(act, xs, ws, w, valid),
                         xs, _expert_of(weights, e), w)
        dxs, dw_e, dw = vjp(d)
        with jax.named_scope("moe_dispatch"):
            dg = dg.at[pair].add(dw.astype(dg.dtype))
        with jax.named_scope("moe_experts"):
            dws = tuple(a.at[e].add(g.astype(a.dtype)) for a, g in zip(dws, dw_e))
        return (dx, dws, dg), dxs, tok

    # float32 sums: a token's rows come from up to k tiles, an expert's
    # weight gradient from every tile it has
    dx, dws, dg = _chunk_loop(n_tiles, tile, (
        jnp.zeros(xc.shape, jnp.float32),
        tuple(jnp.zeros(m.shape, jnp.float32) for m in weights),
        jnp.zeros(gate.shape, jnp.float32)), xc.dtype)
    return (dx.astype(xc.dtype), tuple(a.astype(m.dtype) for a, m in zip(dws, weights)),
            dg.astype(gate.dtype), None, None, None)


_run_tiles.defvjp(_run_tiles_fwd, _run_tiles_bwd)


def _held_experts(xt, mp, moe, act, cdt, choice_e, gate, token_mask,
                  mats=("w_gate", "w_up", "w_down")):
    """The held experts' part of sum_e w_e Expert_e(x): [T, D], and
    (pairs held, rows run, chunks run). `choice_e`, `gate`: the k x T (token, choice)
    pairs' expert and weight, choice-major (pair c T + t is token t's
    choice c).

    Of the k x T pairs only those of real tokens whose expert is held
    here take part. One sort puts them first, by expert; each expert's
    pairs are cut into tiles of `_HELD_ROW_TILE` rows (`_held_tiles`),
    and a loop whose trip count is the number of tiles the run has
    (`_run_tiles`, `_chunk_loop`) gathers a tile's tokens, runs its
    expert's MLP (`mats`) on them as dense products and adds the weighted
    rows to their tokens, a chunk of tiles at a time; the backward pass
    is a loop of the same count. So no pair is dropped whatever the
    imbalance, and the work follows the pairs held: nothing runs, and
    nothing is zeroed, for the pairs of absent experts or for tiles there
    are not."""
    T, k, R = xt.shape[0], moe.top_k, _HELD_ROW_TILE
    first, n_held = moe.experts_held
    with jax.named_scope("moe_dispatch"):
        here = (choice_e >= first) & (choice_e < first + n_held)
        if token_mask is not None:
            here &= jnp.tile(token_mask.reshape(-1), k)
        local_e = jnp.where(here, choice_e - first, n_held).astype(jnp.int32)
        # Held first, by expert, then by pair: the order of a stable
        # argsort, as one sort of distinct keys (expert x pairs + pair).
        # The chip's compiler takes 12 s over a stable or two-operand
        # sort of this length and 1.5 s over this one.
        n = k * T
        assert (n_held + 1) * n < 2**31
        keys = local_e * n + jnp.arange(n, dtype=jnp.int32)
        # a tile's slice of R rows stays inside the array
        pairs = jnp.pad(jax.lax.sort(keys, is_stable=False) % n, (0, R))
        sizes = jnp.sum(local_e[None, :] == jnp.arange(n_held, dtype=jnp.int32)[:, None],
                        axis=1, dtype=jnp.int32)
        n_tiles, tiles = _held_tiles(sizes, n)
    y = _run_tiles(act, xt.astype(cdt), tuple(mp[m].astype(cdt) for m in mats),
                   gate, pairs, n_tiles, tiles)
    return (y.astype(cdt), jnp.sum(sizes).astype(jnp.float32),
            (n_tiles * R).astype(jnp.float32), _chunks(n_tiles)[1].astype(jnp.float32))


def _shared_expert(xt, sp, act, cdt):
    """The MLP every token passes through, gated or plain; times
    `sigmoid(x w_s)` where it has a gate of its own (`w_s`, scope
    `moe_shared_gate`)."""
    with jax.named_scope("moe_shared"):
        mats = ("w_in", "w_out") if "w_in" in sp else ("w_gate", "w_up", "w_down")
        y = _expert_ffn(xt.astype(cdt), tuple(sp[m].astype(cdt) for m in mats), act)
        if "w_s" in sp:
            with jax.named_scope("moe_shared_gate"):
                y = y * jax.nn.sigmoid(xt.astype(cdt) @ sp["w_s"].astype(cdt))
        return y


def moe_mlp(
    x: jnp.ndarray,  # [..., D]
    mp: Dict[str, Any],  # router [D, E], w_gate/w_up [E, D, F], w_down [E, F, D]
    cfg: TransformerConfig,
    cdt,
    capacity_factor: float = None,
    token_mask: jnp.ndarray = None,  # [...] bool, True = real token
    mesh=None,
    dispatch: Optional[str] = None,
    routed=None,
    shared=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (y with x's shape, aux dict: load_balance_loss, z_loss,
    drop_rate, router_entropy, expert_load [E], a2a_bytes).

    token_mask marks real (non-padding) tokens: the reported drop_rate
    then counts only real routings — padding rows route too (static
    shapes) and would otherwise dilute the rate. `mesh` enables the
    expert-parallel dropless path (`_moe_mlp_ep`) when the fsdp axis
    divides num_experts; `dispatch` overrides cfg.moe.dispatch (the
    decode path passes decode_moe_overrides). `routed`, `shared`: the
    router's four (`_router`) and the shared expert's product, each of
    x's leading shape, where the caller has them (a stretch of the layer
    made them a band at a time, `models/transformer._mlp_part`: one chip,
    no mesh's path); made here otherwise."""
    moe = cfg.moe
    if capacity_factor is None:
        capacity_factor = moe.capacity_factor
    if dispatch is None:
        dispatch = moe.dispatch
    if moe.experts_held is not None and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "experts_held is one chip's share of an expert-parallel layer: "
            "across chips the pairs must be exchanged (models/moe.py has "
            "_moe_mlp_ep for a whole layer on an fsdp mesh, and no exchange "
            "for a share)"
        )
    if (dispatch == "dropless" and moe.experts_held is None
            and moe_ep_degree(cfg, mesh, x.shape) > 1):
        if moe.score_func != "softmax" or "shared" in mp or cfg.mlp_type != "gated":
            raise NotImplementedError(
                "_moe_mlp_ep routes with the softmax router, has no shared "
                "expert and runs gated experts (w_gate, w_up, w_down) only: "
                "plain experts (w_in, w_out) have no grouped matmul in its "
                "shard_map body"
            )
        return _moe_mlp_ep(x, mp, cfg, cdt, mesh)

    lead_shape = x.shape[:-1]
    D = x.shape[-1]
    xt = x.reshape(-1, D)
    if routed is None:
        with jax.named_scope("moe_router"):
            routed = _router(xt, mp["router"], moe, mp.get("expert_bias"))
    else:
        flat = lambda a: a.reshape((-1,) + a.shape[len(lead_shape):])
        routed, shared = tuple(map(flat, routed)), None if shared is None else flat(shared)
    y, aux = after_router(xt, mp, cfg, cdt, routed, token_mask, mesh, dispatch,
                          capacity_factor, shared)
    return y.reshape(*lead_shape, D), aux


def after_router(xt, mp, cfg, cdt, routed, token_mask=None, mesh=None,
                 dispatch=None, capacity_factor=None, shared=None):
    """The layer from the router's `routed` (`_router`'s four) on, for the
    tokens xt `[T, D]`: the routed experts' weighted sum plus the shared
    expert's result, `[T, D]`, and the aux dict. `shared`: that result
    where `moe_mlp`'s caller had it; made here otherwise. A cell
    `token_mask` calls padding takes no part in what crosses tokens: no
    expert's capacity, no held expert's tile, none of the router's
    statistics; under dropless dispatch it is routed like a token (static
    shapes) and read by no one."""
    moe = cfg.moe
    capacity_factor = moe.capacity_factor if capacity_factor is None else capacity_factor
    dispatch = dispatch or moe.dispatch
    E, k = moe.num_experts, moe.top_k
    T, D = xt.shape
    _, _, top_p, top_e = routed
    choice_e = top_e.T.reshape(-1)  # [k*T] expert ids, choice-major
    gate = top_p.T.reshape(-1)  # [kT], aligned with choice_e
    tok_idx = jnp.tile(jnp.arange(T), k)
    act, mats = activation_fn(cfg.activation), expert_mats(cfg)
    a2a_bytes = jnp.zeros((), jnp.float32)
    held_aux = {}

    if moe.experts_held is not None:
        y, *counts = _held_experts(
            xt, mp, moe, act, cdt, choice_e, gate, token_mask, mats)
        held_aux = dict(zip(HELD_AUX_KEYS, counts))
        drop_rate = jnp.zeros((), jnp.float32)
    elif dispatch == "dropless":
        # Sort (token, choice) pairs by expert; the expert FFN becomes
        # ragged grouped matmuls with per-expert group sizes. Static
        # shapes (kT rows regardless of skew), zero drops.
        order = jnp.argsort(choice_e)  # stable: keeps priority order
        group_sizes = jnp.bincount(choice_e, length=E)
        xs = xt[tok_idx[order]].astype(cdt)  # [kT, D] sorted by expert
        ys = _grouped_ffn(xs, tuple(mp[n].astype(cdt) for n in mats),
                          group_sizes, act)  # [kT, D]
        y = (
            jnp.zeros((T, D), cdt)
            .at[tok_idx[order]]
            .add(gate[order].astype(cdt)[:, None] * ys)
        )
        drop_rate = jnp.zeros((), jnp.float32)
    else:
        C = max(1, int(capacity_factor * T * k / E))
        # Position of each (token, choice) within its expert's capacity
        # buffer: one-hot over experts -> exclusive cumsum over the
        # flattened (k, T) priority order (choice 0 of every token
        # first).
        onehot = jax.nn.one_hot(choice_e, E, dtype=jnp.int32)  # [kT, E]
        if token_mask is not None:  # padding takes no expert's capacity
            mask_k = jnp.tile(token_mask.reshape(-1), k)  # aligns choice_e
            onehot = onehot * mask_k[:, None].astype(jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive
        pos = jnp.sum(pos_in_e * onehot, axis=-1)  # [kT]
        over = pos >= C
        keep = ~over if token_mask is None else ~over & mask_k

        # dispatch [T, E, C] / combine [T, E, C]
        disp = jnp.zeros((T, E, C), bool)
        disp = disp.at[tok_idx, choice_e, jnp.minimum(pos, C - 1)].max(keep)
        comb = jnp.zeros((T, E, C), jnp.float32)
        comb = comb.at[tok_idx, choice_e, jnp.minimum(pos, C - 1)].add(
            jnp.where(keep, gate, 0.0)
        )

        xe = jnp.einsum("tec,td->ecd", disp.astype(cdt), xt.astype(cdt))  # [E, C, D]
        h = act(jnp.einsum("ecd,edf->ecf", xe, mp[mats[0]].astype(cdt)))
        if len(mats) == 3:
            h = h * jnp.einsum("ecd,edf->ecf", xe, mp["w_up"].astype(cdt))
        ye = jnp.einsum("ecf,efd->ecd", h, mp[mats[-1]].astype(cdt))  # [E, C, D]
        y = jnp.einsum("tec,ecd->td", comb.astype(cdt), ye)  # [T, D]
        # Realized drop rate: fraction of REAL (token, choice) routings
        # that exceeded their expert's capacity this step. The quality
        # risk of the einsum formulation under router skew — surfaced in
        # train stats so it is measured, not assumed.
        if token_mask is not None:
            real = jnp.sum(mask_k.astype(jnp.float32))
            dropped = jnp.sum(over.astype(jnp.float32))  # padding is never over
            drop_rate = dropped / jnp.maximum(real, 1.0)
        else:
            # Clamp: XLA's mean (sum * approx-reciprocal) can round an
            # exact 1.0 to 1.0000000419, making this ~-4e-8.
            drop_rate = jnp.maximum(
                1.0 - jnp.mean(keep.astype(jnp.float32)), 0.0
            )
        ep = moe_ep_degree(cfg, mesh)
        if ep > 1:
            # GSPMD inserts the token all-to-all for the [E, C, D]
            # dispatch/combine contractions on an EP mesh; estimate the
            # per-device bytes so capacity vs dropless-EP exchange
            # volume is comparable in telemetry.
            a2a_bytes = jnp.asarray(
                2.0 * (ep - 1) / ep * E * C * D * jnp.dtype(cdt).itemsize,
                jnp.float32,
            )

    if shared is None and "shared" in mp:
        shared = _shared_expert(xt, mp["shared"], act, cdt)
    if shared is not None:
        y = y + shared
    f_e, P_e, z, entropy = _router_stats(*routed[:2], top_e, E, token_mask)
    return y, {
        "load_balance_loss": E * jnp.sum(f_e * P_e),
        "z_loss": z,
        "drop_rate": drop_rate,
        "router_entropy": entropy,
        "expert_load": f_e,
        "a2a_bytes": a2a_bytes,
        **held_aux,
    }


# The standard deviation of a seeded selection bias (`init_moe_params`'
# `bias_key`): a sigmoid router's scores over seeded weights have one near
# 0.2, and its k-th largest of E stands where their density is thin, so a
# bias of this size moves an expert's load by about half and every second
# token's choice: a check of logprobs sees whether the experts were chosen on
# score + bias or on the score.
_SEEDED_BIAS_STD = 0.05


def init_moe_params(cfg: TransformerConfig, dense_fn, keys, n_layers: int,
                    shared_key=None, bias_key=None) -> Dict[str, Any]:
    """Stacked per-layer MoE params (L leading dim, matching the scan):
    the router over all experts, the weights of the experts held here,
    and where the config has them the selection bias and the shared expert.
    The bias is zeros, or with `bias_key` a seeded draw at
    `_SEEDED_BIAS_STD`, the same draw for the second half of the experts as
    for the first: the two halves of an expert-parallel layer then see one
    load between them whatever the seed, as under a trained bias, whose
    purpose that is (a free draw moved a half's pairs, and a step's seconds,
    by several percent from seed to seed)."""
    moe = cfg.moe
    L, D, E, H = n_layers, cfg.hidden_dim, moe.num_experts, moe.n_held
    F = moe.expert_intermediate_dim or cfg.intermediate_dim
    mats = expert_mats(cfg)
    # (gate, up, down) or (in, out): the last maps back to the hidden size
    mp = {"router": dense_fn(keys[0], (L, D, E))}
    for name, key in zip(mats[:-1], keys[1:]):
        mp[name] = dense_fn(key, (L, H, D, F))
    mp[mats[-1]] = dense_fn(keys[3], (L, H, F, D))
    if moe.router_bias and bias_key is not None and E % 2 == 0:
        half = jax.random.normal(bias_key, (L, E // 2), jnp.float32) * _SEEDED_BIAS_STD
        mp["expert_bias"] = jnp.concatenate([half, half], axis=-1)
    elif moe.router_bias:
        mp["expert_bias"] = jnp.zeros((L, E), jnp.float32)
    if moe.n_shared_experts:
        Fs = moe.shared_intermediate_dim or F * moe.n_shared_experts
        ks = jax.random.split(shared_key, 3)
        mp["shared"] = {name: dense_fn(key, (L, D, Fs))
                        for name, key in zip(mats[:-1], ks)}
        mp["shared"][mats[-1]] = dense_fn(ks[2], (L, Fs, D))
        if moe.shared_gate:
            mp["shared"]["w_s"] = dense_fn(jax.random.fold_in(shared_key, 3), (L, D, 1))
    return mp
