"""The selective scan (Mamba-1 form) over packed rows: one function, two forms.

For x [R, T, Dn] (a layer's channels after its convolution), a step size
dt [R, T, Dn] > 0, decay rates A [Dn, N] < 0, and B, C [R, T, N] shared
by all channels:

    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t     S [Dn, N]
    y_t = S_t C_t

with S = 0 before a sequence's first token (segment ids, 0 = padding;
the sequences of a row are contiguous). There is a decay for every
channel and state, so the recurrence has no matrix form over a chunk
(`ops/ssm.py` has one because Mamba-2's decay is a scalar a head): it is
walked token by token. A padding cell has dt = 0 and x = 0, so it adds
nothing to the state and (its C being 0, the caller's convolution yields
0 there) reads 0. Decays and the state are float32 in both forms.

- `plain_scan`: `jax.numpy`. A `lax.scan` over chunks of `chunk`
  positions carries the state; inside a chunk an associative scan over
  (decay, input) pairs. Differentiated by jax; each chunk is
  checkpointed, so the backward pass holds one chunk's [Q, Dn, N] at a
  time. Runs on the CPU, under a mesh, and is the kernel's check.
- `kernel_scan`: a Pallas TPU kernel, forward (`sscan_fwd`) and backward
  (`sscan_bwd`). Grid (rows, blocks of channels, chunks of time), the
  time axis walked in order; the state [N, block] stays in VMEM, states
  on sublanes and channels on lanes. The forward also writes the state
  at every chunk's start ([R, T / chunk, N, Dn] float32); the backward
  walks the chunks in reverse, recomputes a chunk's states from its
  start into VMEM, and runs the adjoint recurrence over them. [T, Dn, N]
  never exists in HBM.

B, C and the restart flags go into the kernels as one array
[R, T / 16, 3 N, 16] (N rows each; the flag repeated down its N, for
Mosaic broadcasts along lanes or sublanes, not both): sixteen positions
on the minor axis, so that a position's B (a column) is a static lane
slice that broadcasts along the channels, whatever the position's tile,
which is a leading index.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

TILE = 16  # positions a loop step of the kernels walks: one bf16 sublane tile
LANES = 128
_BLOCKS = (512, 256, 128)  # channel blocks tried, largest first


def sequence_keeps(segment_ids):
    """float32 [R, T]: 0 where a position is its sequence's first (the
    state is dropped before it), 1 elsewhere; the rule `ops/ssm.chunk_counts`
    counts resets by."""
    before = jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :-1]
    return 1.0 - ((segment_ids != before) & (segment_ids > 0)).astype(jnp.float32)


def _to_chunks(arrays, chunk: int):
    """[R, T, ..] arrays padded with zeros along T to a multiple of `chunk`."""
    pad = -arrays[0].shape[1] % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in arrays)


def plain_scan(x, dt, A, B, C, segment_ids, chunk: int):
    """x [R, T, Dn] (0 at padding), dt [R, T, Dn] float32 (0 at padding),
    A [Dn, N] float32, B and C [R, T, N], segment_ids [R, T] ->
    y [R, T, Dn] float32."""
    R, T, Dn = x.shape
    N = A.shape[1]
    f32 = jnp.float32
    x, dt, B, C, keep = _to_chunks((x, dt, B, C, sequence_keeps(segment_ids)), chunk)
    nc = x.shape[1] // chunk
    by_chunk = lambda a: jnp.moveaxis(
        a.reshape((R, nc, chunk) + a.shape[2:]), 1, 0)  # [nc, R, Q, ..]

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    @jax.checkpoint
    def step(S, xs):
        xq, dtq, Bq, Cq, kq = xs
        a = jnp.exp(dtq[..., None] * A) * kq[..., None, None]  # [R, Q, Dn, N]
        b = (dtq * xq.astype(f32))[..., None] * Bq.astype(f32)[:, :, None, :]
        a_to, b_to = jax.lax.associative_scan(combine, (a, b), axis=1)
        states = a_to * S[:, None] + b_to
        y = jnp.einsum("rqdn,rqn->rqd", states, Cq.astype(f32))
        return states[:, -1], y

    _, y = jax.lax.scan(step, jnp.zeros((R, Dn, N), f32),
                        tuple(by_chunk(a) for a in (x, dt, B, C, keep)))
    return jnp.moveaxis(y, 0, 1).reshape(R, nc * chunk, Dn)[:, :T]


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------


def _pack_bc(B, C, keep):
    """B, C [R, T, N], keep [R, T] -> [R, T / 16, 3 N, 16] float32:
    rows 0..N-1 B, N..2N-1 C, 2N..3N-1 the restart flag, sixteen
    positions on the minor axis."""
    R, T, N = B.shape
    rows = jnp.concatenate(
        [B.astype(jnp.float32), C.astype(jnp.float32),
         jnp.broadcast_to(keep[..., None], (R, T, N))], axis=-1)  # [R, T, 3N]
    return rows.reshape(R, T // TILE, TILE, 3 * N).swapaxes(2, 3)


def _unpack_dbc(dbc, N: int):
    """[R, blocks, T / 16, 2 N, 16] (a block's share of dB and dC) ->
    dB, dC [R, T, N] float32."""
    d = dbc.sum(axis=1).swapaxes(2, 3)  # [R, T / 16, 16, 2N]
    d = d.reshape(d.shape[0], -1, 2 * N)
    return d[..., :N], d[..., N:]


def _fwd_kernel(x_ref, dt_ref, bc_ref, a_ref, y_ref, s0_ref, s_scr, *, n, tiles):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]
    A = a_ref[...]  # [N, block]

    def tile(i, S):
        o = pl.multiple_of(i * TILE, TILE)
        dtt = dt_ref[pl.ds(o, TILE), :]
        ut = dtt * x_ref[pl.ds(o, TILE), :].astype(jnp.float32)
        bc = bc_ref[i]  # [3N, 16]
        rows = []
        for j in range(TILE):
            a = jnp.exp(dtt[j:j + 1] * A) * bc[2 * n:3 * n, j:j + 1]
            S = a * S + ut[j:j + 1] * bc[0:n, j:j + 1]
            rows.append(jnp.sum(S * bc[n:2 * n, j:j + 1], axis=0, keepdims=True))
        y_ref[pl.ds(o, TILE), :] = jnp.concatenate(rows, axis=0).astype(y_ref.dtype)
        return S

    s_scr[...] = jax.lax.fori_loop(0, tiles, tile, s_scr[...])


def _bwd_kernel(x_ref, dt_ref, bc_ref, a_ref, s0_ref, dy_ref,
                dx_ref, ddt_ref, dbc_ref, da_ref, g_scr, states, *, n, tiles):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    A = a_ref[...]  # [N, block]
    # states[t + 1] = the state after the chunk's position t; [0] = before it
    states[0] = s0_ref[...]

    def recompute(i, S):
        o = pl.multiple_of(i * TILE, TILE)
        dtt = dt_ref[pl.ds(o, TILE), :]
        ut = dtt * x_ref[pl.ds(o, TILE), :].astype(jnp.float32)
        bc = bc_ref[i]
        for j in range(TILE):
            a = jnp.exp(dtt[j:j + 1] * A) * bc[2 * n:3 * n, j:j + 1]
            S = a * S + ut[j:j + 1] * bc[0:n, j:j + 1]
            states[o + j + 1] = S
        return S

    jax.lax.fori_loop(0, tiles, recompute, s0_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, TILE), 1)

    def tile(k, carry):
        G, dA = carry
        i = tiles - 1 - k
        o = pl.multiple_of(i * TILE, TILE)
        dtt = dt_ref[pl.ds(o, TILE), :]
        xt = x_ref[pl.ds(o, TILE), :].astype(jnp.float32)
        dyt = dy_ref[pl.ds(o, TILE), :].astype(jnp.float32)
        ut = dtt * xt
        bc = bc_ref[i]
        dx_rows, ddt_rows = [None] * TILE, [None] * TILE
        dB = jnp.zeros((n, TILE), jnp.float32)
        dC = jnp.zeros((n, TILE), jnp.float32)
        for j in reversed(range(TILE)):
            dt_j = dtt[j:j + 1]
            G = G + dyt[j:j + 1] * bc[n:2 * n, j:j + 1]
            dC = jnp.where(lane == j, jnp.sum(
                dyt[j:j + 1] * states[o + j + 1], axis=1, keepdims=True), dC)
            dB = jnp.where(lane == j, jnp.sum(
                G * ut[j:j + 1], axis=1, keepdims=True), dB)
            du = jnp.sum(G * bc[0:n, j:j + 1], axis=0, keepdims=True)  # [1, block]
            a = jnp.exp(dt_j * A) * bc[2 * n:3 * n, j:j + 1]
            ga = G * states[o + j] * a
            ddt_rows[j] = jnp.sum(ga * A, axis=0, keepdims=True) + du * xt[j:j + 1]
            dx_rows[j] = du * dt_j
            dA = dA + ga * dt_j
            G = a * G
        dx_ref[pl.ds(o, TILE), :] = jnp.concatenate(dx_rows, axis=0).astype(dx_ref.dtype)
        ddt_ref[pl.ds(o, TILE), :] = jnp.concatenate(ddt_rows, axis=0)
        dbc_ref[i, 0:n, :] = dB
        dbc_ref[i, n:2 * n, :] = dC
        return G, dA

    G, dA = jax.lax.fori_loop(0, tiles, tile, (g_scr[...], da_ref[...]))
    g_scr[...] = G
    da_ref[...] = dA


def _block_of(dn: int) -> int:
    return next(b for b in _BLOCKS if dn % b == 0)


def _calls(R, T, Dn, N, chunk, dtype, interpret):
    """(forward, backward) `pallas_call`s for rows of T (a multiple of
    `chunk`) at the largest channel block that divides Dn."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk, nc, tiles = _block_of(Dn), T // chunk, chunk // TILE
    nb = Dn // blk
    f32 = jnp.float32
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)

    def specs(at):
        """Block specs with chunk `at(c)` at grid step c."""
        cell = pl.BlockSpec((None, chunk, blk), lambda r, b, c: (r, at(c), b))
        bc = pl.BlockSpec((None, tiles, 3 * N, TILE),
                          lambda r, b, c: (r, at(c), 0, 0))
        rates = pl.BlockSpec((N, blk), lambda r, b, c: (0, b))
        s0 = pl.BlockSpec((None, None, N, blk), lambda r, b, c: (r, at(c), 0, b))
        return cell, bc, rates, s0

    cell, bc, rates, s0 = specs(lambda c: c)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, n=N, tiles=tiles),
        grid=(R, nb, nc),
        in_specs=[cell, cell, bc, rates],
        out_specs=[cell, s0],
        out_shape=[jax.ShapeDtypeStruct((R, T, Dn), dtype),
                   jax.ShapeDtypeStruct((R, nc, N, Dn), f32)],
        scratch_shapes=[pltpu.VMEM((N, blk), f32)],
        compiler_params=params, interpret=interpret, name="sscan_fwd")

    cell, bc, rates, s0 = specs(lambda c: nc - 1 - c)
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, n=N, tiles=tiles),
        grid=(R, nb, nc),
        in_specs=[cell, cell, bc, rates, s0, cell],
        out_specs=[
            cell, cell,
            pl.BlockSpec((None, None, tiles, 2 * N, TILE),
                         lambda r, b, c: (r, b, nc - 1 - c, 0, 0)),
            pl.BlockSpec((None, N, blk), lambda r, b, c: (r, 0, b)),
        ],
        out_shape=[jax.ShapeDtypeStruct((R, T, Dn), dtype),
                   jax.ShapeDtypeStruct((R, T, Dn), f32),
                   jax.ShapeDtypeStruct((R, nb, T // TILE, 2 * N, TILE), f32),
                   jax.ShapeDtypeStruct((R, N, Dn), f32)],
        scratch_shapes=[pltpu.VMEM((N, blk), f32),
                        pltpu.VMEM((chunk + 1, N, blk), f32)],
        compiler_params=params, interpret=interpret, name="sscan_bwd")
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_scan(x, dt, At, B, C, keep, chunk, interpret):
    return _kernel_fwd(x, dt, At, B, C, keep, chunk, interpret)[0]


def _kernel_fwd(x, dt, At, B, C, keep, chunk, interpret):
    R, T, Dn = x.shape
    fwd, _ = _calls(R, T, Dn, At.shape[0], chunk, x.dtype, interpret)
    bc = _pack_bc(B, C, keep)
    y, s0 = fwd(x, dt, bc, At)
    return y, (x, dt, At, bc, s0, jnp.zeros((0,), B.dtype))


def _kernel_bwd(chunk, interpret, res, dy):
    x, dt, At, bc, s0, like_b = res
    R, T, Dn = x.shape
    N = At.shape[0]
    _, bwd = _calls(R, T, Dn, N, chunk, x.dtype, interpret)
    dx, ddt, dbc, dA = bwd(x, dt, bc, At, s0, dy.astype(x.dtype))
    dB, dC = _unpack_dbc(dbc, N)
    return (dx, ddt, dA.sum(axis=0), dB.astype(like_b.dtype), dC.astype(like_b.dtype),
            jnp.zeros((R, T), jnp.float32))


_kernel_scan.defvjp(_kernel_fwd, _kernel_bwd)


def kernel_ok(dn: int, n: int, chunk: int) -> bool:
    """Shapes the kernels take: channels in lane-wide blocks, states in
    whole sublane tiles, chunks of whole position tiles."""
    return dn % LANES == 0 and n % 8 == 0 and chunk % TILE == 0


def kernel_scan(x, dt, A, B, C, segment_ids, chunk: int,
                interpret: Optional[bool] = None):
    """`plain_scan`'s arguments and result (in x's dtype), on the Pallas
    kernels; differentiable in x, dt, A, B and C."""
    R, T, Dn = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    x, dt, B, C, keep = _to_chunks((x, dt, B, C, sequence_keeps(segment_ids)), chunk)
    y = _kernel_scan(x, dt.astype(jnp.float32), A.astype(jnp.float32).T, B, C,
                     keep, int(chunk), bool(interpret))
    return y[:, :T]


def resolve_scan_impl(impl: str, dn: int, n: int, chunk: int, mesh=None) -> str:
    """'kernel' or 'plain' for the given shapes: 'auto' is the kernel on
    a TPU backend, on one device (a `pallas_call` is opaque to GSPMD),
    where `kernel_ok`."""
    if impl != "auto":
        return impl
    sharded = mesh is not None and mesh.size > 1
    on_tpu = jax.default_backend() == "tpu"
    return "kernel" if on_tpu and not sharded and kernel_ok(dn, n, chunk) else "plain"


def selective_scan(x, dt, A, B, C, segment_ids, chunk: int, impl: str = "auto",
                   mesh=None):
    """The one entry point: y [R, T, Dn] in x's dtype."""
    impl = resolve_scan_impl(impl, x.shape[-1], A.shape[1], chunk, mesh)
    if impl == "kernel":
        return kernel_scan(x, dt, A, B, C, segment_ids, chunk)
    return plain_scan(x, dt, A, B, C, segment_ids, chunk).astype(x.dtype)


# ---------------------------------------------------------------------------
# The mixer around it
# ---------------------------------------------------------------------------


def init_sscan_params(ssm, hidden_dim: int, dense_fn, key, n_layers: int,
                      pdt) -> Dict[str, Any]:
    """`n_layers` Mamba-1 mixers stacked on a leading axis. `A_log` =
    log 1..N along the state axis, `dt_bias` the inverse softplus of a
    log-uniform step in [dt_min, dt_max] floored at dt_floor, `D` = 1: a
    channel's states forget at exp(-dt n) a token, between 0.999 (dt
    0.001, n 1) and 0.20 (dt 0.1, n 16)."""
    L, Dn, N, rank = n_layers, ssm.channels, ssm.state_dim, ssm.dt_rank
    k_in, k_conv, k_x, k_dtw, k_dt, k_out = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k_dt, (L, Dn), jnp.float32)
                 * (math.log(ssm.dt_max) - math.log(ssm.dt_min))
                 + math.log(ssm.dt_min))
    dt = jnp.maximum(dt, ssm.dt_floor)
    sp: Dict[str, Any] = {
        "in_proj": dense_fn(k_in, (L, hidden_dim, 2 * Dn)),  # [x | z]
        # [K, channels]: tap K-1 multiplies the position itself
        "conv_w": dense_fn(k_conv, (L, ssm.conv_kernel, Dn),
                           1.0 / math.sqrt(ssm.conv_kernel)),
        "x_proj": dense_fn(k_x, (L, Dn, rank + 2 * N)),  # [r | B | C]
        "dt_proj": dense_fn(k_dtw, (L, rank, Dn)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (L, Dn, N)).astype(pdt),
        "D": jnp.ones((L, Dn), pdt),
        "out_proj": dense_fn(k_out, (L, Dn, hidden_dim)),
    }
    if ssm.conv_bias:
        sp["conv_b"] = jnp.zeros((L, Dn), pdt)
    return sp


def sscan_mixer(h, sp, ssm, segment_ids, cdt, mesh=None, impl: str = "auto"):
    """h [R, T, D] (the layer's input after its norm) -> (the mixer's
    output [R, T, D], the scan's output before the gate [R, T, Dn]: what
    a layer that `keeps` hands on); `sp` one layer's parameters
    (`init_sscan_params` without the leading axis). The input is masked
    at padding cells on the way in, as `ops/ssm.ssm_mixer` does."""
    from areal_tpu.ops.ssm import causal_conv

    N, rank = ssm.state_dim, ssm.dt_rank
    valid = (segment_ids > 0)[..., None]
    f32 = jnp.float32
    with jax.named_scope("sscan_in_proj"):
        h = jnp.where(valid, h, 0).astype(cdt)
        x, z = jnp.split(h @ sp["in_proj"].astype(cdt), 2, axis=-1)
    with jax.named_scope("sscan_taps"):
        x = causal_conv(
            x, sp["conv_w"].astype(cdt),
            sp["conv_b"].astype(cdt) if "conv_b" in sp else None, segment_ids)
    with jax.named_scope("sscan_xdt"):
        r, B, C = jnp.split(x @ sp["x_proj"].astype(cdt), [rank, rank + N], axis=-1)
        dt = jnp.matmul(r, sp["dt_proj"].astype(cdt), preferred_element_type=f32)
        dt = jnp.where(valid, jax.nn.softplus(dt + sp["dt_bias"].astype(f32)), 0.0)
        A = -jnp.exp(sp["A_log"].astype(f32))
    with jax.named_scope("sscan_kernel"):
        y = selective_scan(x, dt, A, B, C, segment_ids, ssm.chunk_size, impl, mesh)
    with jax.named_scope("sscan_gate"):
        y = (y.astype(f32) + sp["D"].astype(f32) * x.astype(f32)).astype(cdt)
        gated = y * jax.nn.silu(z)
    with jax.named_scope("sscan_out_proj"):
        return gated @ sp["out_proj"].astype(cdt), y
