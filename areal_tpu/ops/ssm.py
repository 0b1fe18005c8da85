"""A state-space mixer (Mamba-2 form) over packed rows.

For a layer's normalised input `h` [R, T, D], with H heads of P channels,
a state of N a channel, B and C shared by the H / G heads of a group:

    [z | xBC | dt] = h W_in
    xBC_t <- silu(b_c + sum_l w_c[K-1-l] * xBC_{t-l}),  l = 0 .. K-1, depthwise,
             a term dropped unless position t-l lies in t's own sequence
    xBC -> x [H, P], B [G, N], C [G, N];  dt_t = softplus(dt_t + dt_bias) [H]
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S = 0 before a sequence's
          first token;  A = -exp(A_log) [H]
    y_t = S_t C_t + D x_t;  y <- RMSNorm_groups(y * silu(z)) * w;  out = y W_out

A packed row holds several sequences (segment ids, 0 = padding): the
state and the convolution start afresh at every sequence start, and a
padding cell has dt = 0 and x = 0, so it adds nothing to any state and
its own result is 0. The input is masked at padding cells on the way in:
whatever they hold (the residual stream carries them along) reaches
neither a result nor a gradient.

Computed in chunks of `chunk_size` positions (the SSD form), in einsums
and one scan. With `a_t = dt_t A` and `cum` its running sum inside a
chunk: within a chunk `y_i = sum_{j<=i} L_ij (C_i . B_j) dt_j x_j`, where
`L_ij = exp(cum_i - cum_j)` if i and j are of one sequence, else 0
(`ssm_intra`); between chunks a scan over the chunks' states
(`ssm_states`): a chunk hands on the state of the sequence its last cell
belongs to, `sum_j exp(cum_last - cum_j) dt_j x_j (x) B_j` over that
sequence's cells, plus `exp(cum_last)` times the state it received if
that same sequence crossed the whole chunk; the state a chunk receives
reaches, decayed by `exp(cum_i)`, only the cells of the sequence that
crossed into it. Decays, softplus and running sums are float32; the
matrix products run in the compute dtype and accumulate in float32.

A row may also be run a band of whole chunks at a time (`band_mixer`
under `ops/band_loop.carried`; `models/transformer._ssm_layer`): a band
receives the state at the end of the cell before it, that cell's segment
id, and the convolution's last `conv_kernel - 1` inputs with theirs
(`MixerCarry`), and hands the same on. The lines are `ssm_mixer`'s own, so
a band's first chunk receives exactly what the whole row's scan would
have handed it; bands no token is in are not run.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import ConvConfig, SSMConfig


def init_ssm_params(ssm: SSMConfig, hidden_dim: int, dense_fn, key, n_layers: int,
                    pdt) -> Dict[str, Any]:
    """`n_layers` mixers stacked on a leading axis. `A_log` = log of a
    uniform draw from 1..16, `dt_bias` the inverse softplus of a
    log-uniform step in [dt_min, dt_max] floored at dt_floor, `D` = 1:
    a head then forgets at a rate of exp(-dt A) a token, between 0.9990
    (dt 0.001, A 1) and 0.20 (dt 0.1, A 16)."""
    L, H = n_layers, ssm.n_heads
    k_in, k_conv, k_out, k_a, k_dt = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(k_dt, (L, H), jnp.float32)
                 * (math.log(ssm.dt_max) - math.log(ssm.dt_min))
                 + math.log(ssm.dt_min))
    dt = jnp.maximum(dt, ssm.dt_floor)
    sp: Dict[str, Any] = {
        "in_proj": dense_fn(k_in, (L, hidden_dim, ssm.in_proj_dim)),
        # [K, channels]: tap K-1 multiplies the position itself
        "conv_w": dense_fn(k_conv, (L, ssm.conv_kernel, ssm.conv_dim),
                           1.0 / math.sqrt(ssm.conv_kernel)),
        "A_log": jnp.log(jax.random.uniform(
            k_a, (L, H), jnp.float32, 1.0, 16.0)).astype(pdt),
        "D": jnp.ones((L, H), pdt),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "norm": jnp.ones((L, ssm.d_inner), pdt),
        "out_proj": dense_fn(k_out, (L, ssm.d_inner, hidden_dim)),
    }
    if ssm.conv_bias:
        sp["conv_b"] = jnp.zeros((L, ssm.conv_dim), pdt)
    return sp


def causal_conv(xbc, w, b, segment_ids, tail=None, act=jax.nn.silu):
    """xbc [R, T, C], w [K, C], b [C] or None, segment_ids [R, T] ->
    act(b + sum_l w[K-1-l] xbc[t-l]) over the taps whose position lies
    in t's own sequence (`act` None: the sum as it is); 0 at padding cells.
    `tail`: the K - 1 cells
    before the first, (xbc [R, K-1, C], segment ids [R, K-1]), where xbc is
    a band of a longer row; without it nothing stands before cell 0."""
    K, T = w.shape[0], xbc.shape[1]
    if tail is None:
        before = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # position t at t + K - 1
        seg_before = jnp.pad(segment_ids, ((0, 0), (K - 1, 0)))
    else:
        before = jnp.concatenate([tail[0], xbc], axis=1)
        seg_before = jnp.concatenate([tail[1], segment_ids], axis=1)
    acc = xbc * w[K - 1]
    for lag in range(1, K):
        lo = K - 1 - lag
        same = seg_before[:, lo: lo + T] == segment_ids
        acc = acc + jnp.where(same[..., None], before[:, lo: lo + T], 0) * w[lo]
    if b is not None:
        acc = acc + b
    return jnp.where((segment_ids > 0)[..., None], acc if act is None else act(acc), 0)


def chunked_scan(x, dt, A, B, C, segment_ids, chunk: int):
    """The recurrence over packed rows, in chunks. x [R, T, H, P] (0 at
    padding), dt [R, T, H] float32 (0 at padding), A [H] float32 (< 0),
    B and C [R, T, G, N], segment_ids [R, T] -> y [R, T, H, P] float32,
    without the `D x` term."""
    return scan_from(None, x, dt, A, B, C, segment_ids, chunk)[0]


def scan_from(received, x, dt, A, B, C, segment_ids, chunk: int):
    """`chunked_scan` over cells that follow others: `received` is (the
    state at the end of the cell before the first, [R, H, P, N] float32,
    that cell's segment id [R]), or None where nothing stands before cell 0
    (zeros and 0). -> (y, the state at the end of the last chunk, which for
    T a multiple of `chunk` is the last cell's: what the next cells
    receive beside `segment_ids[:, -1]`)."""
    R, T, H, P = x.shape
    G, N = B.shape[2:]
    Q, K = chunk, H // G
    pad = -T % Q
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C, segment_ids = (grow(a) for a in (x, dt, B, C, segment_ids))
    nc = (T + pad) // Q
    cdt = x.dtype
    f32 = jnp.float32
    # [R, c, Q, ...]; heads as (group, head of the group). The decays
    # are held [R, c, G, K, Q]: the chunk's positions on the minor axis.
    x = x.reshape(R, nc, Q, G, K, P)
    dt = dt.reshape(R, nc, Q, G, K)
    B, C = B.reshape(R, nc, Q, G, N), C.reshape(R, nc, Q, G, N)
    seg = segment_ids.reshape(R, nc, Q)
    by_head = lambda a: jnp.moveaxis(a, 2, -1)  # [R, c, Q, G, K] -> [R, c, G, K, Q]
    by_cell = lambda a: jnp.moveaxis(a, -1, 2)[..., None]  # -> [R, c, Q, G, K, 1]
    cum = jnp.cumsum(by_head(dt * A.reshape(G, K)), axis=-1)  # <= 0
    dtx = dt[..., None] * x.astype(f32)  # [R, c, Q, G, K, P]

    with jax.named_scope("ssm_intra"):
        # L_ij = exp(cum_i - cum_j) for j <= i of i's sequence, else 0
        # (masked before the exp: above the diagonal the difference is
        # positive and may overflow).
        seen = (seg[:, :, :, None] == seg[:, :, None, :]) & jnp.tril(
            jnp.ones((Q, Q), bool))  # [R, c, i, j]
        diff = cum[..., :, None] - cum[..., None, :]  # [R, c, G, K, i, j]
        decay = jnp.exp(jnp.where(seen[:, :, None, None], diff, -jnp.inf))
        cb = jnp.einsum("rcign,rcjgn->rcgij", C, B, preferred_element_type=f32)
        scores = (cb[:, :, :, None] * decay).astype(cdt)
        y = jnp.einsum("rcgkij,rcjgkp->rcigkp", scores, dtx.astype(cdt),
                       preferred_element_type=f32)

    with jax.named_scope("ssm_states"):
        last = seg[:, :, -1]  # [R, c] the sequence a chunk hands on
        # what the chunk's own cells add to the state at its end
        to_end = jnp.exp(jnp.where(
            (seg == last[..., None])[:, :, None, None],
            cum[..., -1:] - cum, -jnp.inf))  # [R, c, G, K, Q]
        local = jnp.einsum(
            "rcjgn,rcjgkp->rcgkpn", B, (by_cell(to_end) * dtx).astype(cdt),
            preferred_element_type=f32)  # [R, c, G, K, P, N]
        # the state received is handed on, decayed over the whole chunk,
        # if the sequence that crossed in is the one handed on
        seg0 = jnp.zeros((R, 1), last.dtype) if received is None else received[1][:, None]
        before = jnp.concatenate([seg0, last[:, :-1]], axis=1)  # [R, c]
        carry_on = jnp.where(((last == before) & (last > 0))[..., None, None],
                             jnp.exp(cum[..., -1]), 0.0)  # [R, c, G, K]

        def step(state, inp):
            keep, add = inp
            return keep[..., None, None] * state + add, state

        state0 = (jnp.zeros((R, G, K, P, N), f32) if received is None
                  else received[0].reshape(R, G, K, P, N))
        state, received = jax.lax.scan(
            step, state0, (jnp.moveaxis(carry_on, 1, 0), jnp.moveaxis(local, 1, 0)))
        received = jnp.moveaxis(received, 0, 1)  # [R, c, G, K, P, N]
        # ... and reaches the cells of the sequence that crossed in
        from_start = jnp.where(
            ((seg == before[..., None]) & (seg > 0))[:, :, None, None],
            jnp.exp(cum), 0.0)  # [R, c, G, K, Q]
        y = y + by_cell(from_start) * jnp.einsum(
            "rcign,rcgkpn->rcigkp", C, received.astype(cdt),
            preferred_element_type=f32)
    return y.reshape(R, nc * Q, H, P)[:, :T], state.reshape(R, H, P, N)


def ssm_mixer(h, sp, ssm: SSMConfig, segment_ids, cdt, eps: float,
              scan=chunked_scan):
    """h [R, T, D] (the layer's input after its norm) -> the mixer's
    output [R, T, D]; `sp` one layer's parameters (`init_ssm_params`
    without the leading axis)."""
    return _mixer(None, h, sp, ssm, segment_ids, cdt, eps,
                  lambda received, *a: (scan(*a), None))[0]


class MixerCarry(NamedTuple):
    """What the cells of a row up to a band's first hand the band
    (`band_mixer`): the state at the end of the last of them `[R, H, P, N]`
    float32, and the convolution's reach back, the last `conv_kernel - 1`
    cells' `xBC` before the taps `[R, K-1, conv_dim]` and segment ids
    `[R, K-1]` (the last of which is the cell the state is of)."""
    state: Any
    xbc: Any
    seg: Any


def start_carry(ssm: SSMConfig, n_rows: int, cdt) -> MixerCarry:
    """What a row's first band receives: no state, no cell before it."""
    k = ssm.conv_kernel - 1
    return MixerCarry(
        jnp.zeros((n_rows, ssm.n_heads, ssm.head_dim, ssm.state_dim), jnp.float32),
        jnp.zeros((n_rows, k, ssm.conv_dim), cdt), jnp.zeros((n_rows, k), jnp.int32))


def band_mixer(carry: MixerCarry, h, sp, ssm: SSMConfig, segment_ids, cdt, eps: float):
    """`ssm_mixer` over a band of a row, whole chunks of it: h `[R, band, D]`
    and what the cells before it handed on -> (the mixer's output for the
    band, what it hands on). `ssm_mixer`'s own lines in its dtypes: band
    after band from `start_carry` is the whole row's arithmetic in the whole
    row's order (`ops/band_loop.carried` walks a row so)."""
    assert h.shape[1] % ssm.chunk_size == 0 and h.shape[1] >= ssm.conv_kernel - 1
    return _mixer(carry, h, sp, ssm, segment_ids, cdt, eps, scan_from)


def _mixer(carry, h, sp, ssm: SSMConfig, segment_ids, cdt, eps: float, scan):
    """The mixer over h [R, T, D] after `carry` (None: from a row's first
    cell) -> (its output, the carry after h's last cell)."""
    R, T, _ = h.shape
    H, P, G, N = ssm.n_heads, ssm.head_dim, ssm.n_groups, ssm.state_dim
    d_in = ssm.d_inner
    valid = segment_ids > 0
    f32 = jnp.float32
    with jax.named_scope("ssm_in_proj"):
        h = jnp.where(valid[..., None], h, 0).astype(cdt)
        zxbcdt = h @ sp["in_proj"].astype(cdt)
        z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + ssm.conv_dim], axis=-1)
    with jax.named_scope("ssm_taps"):
        k = ssm.conv_kernel - 1
        handed = (xbc[:, T - k:], segment_ids[:, T - k:])
        xbc = causal_conv(
            xbc, sp["conv_w"].astype(cdt),
            sp["conv_b"].astype(cdt) if "conv_b" in sp else None, segment_ids,
            None if carry is None else (carry.xbc, carry.seg))
        x, B, C = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
        x = x.reshape(R, T, H, P)
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(f32) + sp["dt_bias"].astype(f32))
        dt = jnp.where(valid[..., None], dt, 0.0)
        A = -jnp.exp(sp["A_log"].astype(f32))
        y, state = scan(
            None if carry is None else (carry.state, carry.seg[:, -1]),
            x, dt, A, B.reshape(R, T, G, N), C.reshape(R, T, G, N),
            segment_ids, ssm.chunk_size)
        y = y + sp["D"].astype(f32)[:, None] * x.astype(f32)
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(R, T, d_in) * jax.nn.silu(z.astype(f32))
        # RMSNorm over each group's channels, after the gate
        yg = y.reshape(R, T, G, d_in // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
        y = (yg.reshape(R, T, d_in) * sp["norm"].astype(f32)).astype(cdt)
    with jax.named_scope("ssm_out_proj"):
        return y @ sp["out_proj"].astype(cdt), MixerCarry(state, *handed)


def chunk_counts(segment_ids: np.ndarray, chunk: int, band: Optional[int] = None):
    """What `chunked_scan` does with packed rows, counted on the host by
    its own rule; `segment_ids` [..., T]: (chunks it runs, those that
    hold a token, those that hold a sequence start after their first
    cell, sequence starts). `band`: the mixer walks each row's live bands
    of that many cells (`band_mixer` under `ops/band_loop.carried`), and
    runs the chunks of the bands up to the row's last token, no others."""
    seg = np.asarray(segment_ids)
    seg = seg.reshape(-1, seg.shape[-1])
    pad = -seg.shape[1] % chunk
    seg = np.pad(seg, ((0, 0), (0, pad)))
    start = (seg != np.pad(seg, ((0, 0), (1, 0)))[:, :-1]) & (seg > 0)
    chunks = seg.reshape(seg.shape[0], -1, chunk)
    starts = start.reshape(chunks.shape)
    run = chunks.shape[0] * chunks.shape[1]
    if band is not None:
        tokens = np.where(seg > 0, np.arange(1, seg.shape[1] + 1), 0).max(-1)  # to the last
        run = (-(-tokens // band) * (band // chunk)).sum()
    return (int(run),
            int((chunks > 0).any(-1).sum()),
            int(starts[:, :, 1:].any(-1).sum()),
            int(start.sum()))


# ---------------------------------------------------------------------------
# A gated short convolution as a layer's whole mixer (LFM2)
# ---------------------------------------------------------------------------


def init_conv_params(conv: ConvConfig, hidden_dim: int, dense_fn, key, n_layers: int,
                     pdt) -> Dict[str, Any]:
    """`n_layers` gated short convolutions stacked on a leading axis:
    `in_proj` to `[B | C | x]`, the taps `[K, D]` (tap K-1 multiplies the
    position itself, as `init_ssm_params`'), `out_proj`."""
    L, D = n_layers, hidden_dim
    k_in, k_conv, k_out = jax.random.split(key, 3)
    cp: Dict[str, Any] = {
        "in_proj": dense_fn(k_in, (L, D, 3 * D)),
        "conv_w": dense_fn(k_conv, (L, conv.kernel, D), 1.0 / math.sqrt(conv.kernel)),
        "out_proj": dense_fn(k_out, (L, D, D)),
    }
    if conv.bias:
        cp["conv_b"] = jnp.zeros((L, D), pdt)
    return cp


class ConvCarry(NamedTuple):
    """What the cells of a row up to a band's first hand the band
    (`gated_conv_mixer`): the last `kernel - 1` cells' gated inputs `B * x`
    `[R, K-1, D]` and their segment ids `[R, K-1]`."""
    bx: Any
    seg: Any


def conv_start_carry(conv: ConvConfig, n_rows: int, hidden_dim: int, cdt) -> ConvCarry:
    """What a row's first band receives: no cell before it."""
    k = conv.kernel - 1
    return ConvCarry(jnp.zeros((n_rows, k, hidden_dim), cdt),
                     jnp.zeros((n_rows, k), jnp.int32))


def conv_in_kernel(T: int, D: int, K: int, bias: bool, tail: bool,
                   kernel: Optional[bool] = None) -> bool:
    """Whether `gated_conv` takes its kernels (`ops/pallas/conv_gate.py`) for a
    call of these shapes: on the chip (`kernel` None; True: anywhere, in
    interpret mode off the chip; False: nowhere, a mesh of several devices),
    a whole row of whole blocks and lane tiles, no bias, nothing handed in."""
    from areal_tpu.ops.pallas import conv_gate

    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return bool(kernel) and not bias and not tail and conv_gate.fits(T, D, K)


def gated_conv(bcx, w, b, segment_ids, tail=None, kernel: Optional[bool] = None):
    """bcx `[R, T, 3 D]` = `[B | C | x]` -> (`C * conv(B * x)` `[R, T, D]`,
    the last `K - 1` cells' gated input `B * x`, what the cells after would
    be handed): `causal_conv` without an activation over the product, under
    the second gate. Over a whole row on the chip a kernel pair
    (`conv_in_kernel`); otherwise plain `jax.numpy`, whose forward the
    compiler fuses into one pass at three quarters of the memory's rate and
    whose backward it does not (a fifth: `scripts/conv_probe.py`, PERF.md
    section 6, PR 63)."""
    K, D = w.shape
    B, C, x = jnp.split(bcx, 3, axis=-1)
    last = slice(-(K - 1), None)
    if conv_in_kernel(bcx.shape[1], D, K, b is not None, tail is not None, kernel):
        from areal_tpu.ops.pallas import conv_gate

        return (conv_gate.conv_gate(bcx, w, segment_ids, jax.default_backend() != "tpu"),
                B[:, last] * x[:, last])
    bx = B * x
    z = causal_conv(bx, w, b, segment_ids, tail, act=None)
    # (masked once more after the gate: what a padding cell's C holds is no one's)
    return jnp.where((segment_ids > 0)[..., None], C * z, 0), bx[:, last]


def gated_conv_mixer(carry: Optional[ConvCarry], u, cp, segment_ids, cdt,
                     kernel: Optional[bool] = None):
    """u `[R, T, D]` (the layer's input after its norm) -> (the mixer's
    output `[R, T, D]`, what its last cells hand on); `cp` one layer's
    parameters. `carry`: what the cells before u's first handed on where u
    is a band of a longer row (`ops/band_loop.carried`), None where nothing
    stands before cell 0. A padding cell's result is 0 and no tap reaches
    out of a sequence, so what padding cells hold reaches no token.
    `kernel`: `conv_in_kernel`'s."""
    k = cp["conv_w"].shape[0] - 1
    with jax.named_scope("conv_in_proj"):
        bcx = u.astype(cdt) @ cp["in_proj"].astype(cdt)
    with jax.named_scope("conv_taps"):
        y, last = gated_conv(
            bcx, cp["conv_w"].astype(cdt),
            cp["conv_b"].astype(cdt) if "conv_b" in cp else None, segment_ids,
            None if carry is None else tuple(carry), kernel)
        handed = ConvCarry(last, segment_ids[:, -k:])
    with jax.named_scope("conv_out_proj"):
        return y @ cp["out_proj"].astype(cdt), handed
