"""The delta rule's backward as one kernel (Pallas, TPU): `ops/kda.delta_rule`'s
transpose over a whole row, `kda_bwd_rule`.

The grid walks a row's groups of chunks from the last to the first (the
groups whose received states the forward kernel wrote, `bounds`), and every
group twice: forwards from the state it received, which puts the state
every chunk received into VMEM scratch in the operands' dtype (the forward
kernel's chunk without q's half: no `P`, no `Qg`, no O), then backwards, a
chunk of `kda_fwd.HEADS` heads a grid step, with the state's cotangent `dS` `[V,
K]` float32 a head in VMEM scratch too. q, k, v, f, b and `dO` are read
cells-major as the projections leave them and dq, dk, dv, df, db written
cells-major; nothing else of a chunk reaches HBM: no part `[N, H, C,
...]`, no cotangent of one, no chunk's state, no heads-first copy. A
backward step

    makes the chunk's `intra` again (`kda_fwd._intra`: the forward kernel's
        own formulas and dtypes, the pairs `[P, C, 2 C]` side by side)
    Vn = U - Wm S                                    (the walk's step again)
    dVn = Pm^T dO + Kd dS'^T;  dWm = -dVn S;  dQg = dO S;  dKd = Vn dS'
    dPm = dO Vn^T;  ddec = colsum(dS' * S);  dU = dVn
    dS  = dO^T Qg + Diag(dec) dS' - dVn^T Wm         (carried to the chunk before)
    dT = dW (K e^G)^T + dU V^T;  d(K e^G) = T^T dW;  dv = T^T dU
    dY = dT Diag(b);  dA = -Y^T dY Y^T               (the inverse's own rule: two
        float32 products at the highest precision, a pair's two heads in
        each, where the forward's squarings are ten)
    M = d kk = strict-lower(dA) Diag(b) rows;  N = dP = tril(dPm), both of one sequence
    db = rowsum(dA (.) kk) + colsum(dT (.) Y)

and pulls M and N back through the pairs `sum_d x_i[d] k_j[d] exp(G_i[d] -
G_j[d])` (x = k under M, x = q under N). A decay a channel: by sub-blocks of
16 as the forward made them, an off-diagonal one three products relative to
the later sub-block's first cell, a diagonal one cell by cell (the one
exponential `exp(min(G_i - G_c, 0))` under dq's, dk's row's and dk's
column's term: never the exponential of a positive number). A decay a head:
`M (.) D` and `N (.) D` against K and Q, four products a side. The running
sum's cotangent needs no exponential of its own: for every such term it is
`x (.) dx - k (.) dk` over that term's dx and dk, and W, Qg, Kd give their
`(K e^G) (.) d(K e^G)` the same way (a decay a head: the pairs' share is
`D`'s cotangent times `D`, a row's sum less a column's, float32 throughout
as the plain form has it). A pair's term enters that cotangent at its row
and leaves it at its column, and the sum up the rows must hold nothing of
it before the column: so both ends are one number. An off-diagonal
sub-block's x and k stand there as its products took them, rounded to the
compute dtype (with one end's factor rounded and the other's not, the
4e-3 of every pair that is left adds up over a chunk's earlier cells: by
the probe's rehearsal in bf16, d dt_bias 2.4 times as far from the float32
rule as the plain form's, and level with it so); and what has no decay
stays out, where each end would hold it at order 1 to cancel (a cell
against itself in P, the chunk's last cell in Kd). `dg` is the running sum's transpose
(shifted adds up the rows), `df = dg A softplus'`, and `dA`, `d dt_bias`
are sums over cells kept in an output block that stays in VMEM over a
row's chunks. The decays, running sums, A, P, the inverse and their
cotangents are float32; the other products take operands in the compute
dtype and accumulate in float32; the parts' cotangents never leave VMEM
and are float32 there (the plain form rounds them to the compute dtype on
their way through HBM).

Key heads under value heads: dq and dk of a key head are summed over its
value heads inside the step, before `unit`'s pullback. A chunk past a
row's last live one fetches nothing new, computes nothing and writes
zeros; a number a cell a head (db; df where the decay is a head's) is
written with the cells along lanes and turned outside.

By the probe (`scripts/kda_probe.py`, PERF.md section 6, PR 55), a row of
16,384 at 53 % fill, 32 value heads of 128, bf16, ms a call: the backward 13.8
the channel form (45 as a loop of XLA's over groups with the walk's two
kernels in it) and 8.0 the head form at 16 key heads (15.8); about 3.9 and
2.8 of them the chunks' states again; four heads a step 10-25 % slower.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.kda import L2_EPS, SUB, RuleForm
from areal_tpu.ops.pallas.kda_fwd import (
    _BNN, _BNT, _BTN, _at, _chunk, _in_specs, _intra, _mm, _operands, _pairs,
    _running_sum, _sides, _under, step_heads)

# A decay a channel: what a step holds at 8 heads of 128 x 128 in chunks of 64
# (the blocks in and out twice, a group's states, the sub-blocks' scratch, the
# compiler's spills) is past the 16 MiB a kernel gets unasked, of a core's 128;
# a decay a head fits them. (The program's other arrays lose what a kernel is
# promised: by the compiles of `tests/model/test_tpu_compile.py` the step's
# temporaries are lowest so.)
CHANNEL_VMEM_BYTES = 48 * 2 ** 20


def _mm32(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _channel_pairs_bwd(made, scratch, dkc_s, M, N, cdt):
    """`kda_fwd._channel_pairs`'s transpose: M, N `[P, C, 2 C]` float32 the
    cotangents of `kk` and `P`, masked, N without its diagonal -> for each
    side dq (of P's rows), dk (of kk's rows and of both's columns) and the
    running sum's cotangent `x (.) dx - k (.) dk`, each `[P, C, K]` float32.
    `dkc_s` `[2, P, C, K]` float32 scratch: a diagonal cell's column term is
    a row of it.

    An off-diagonal sub-block's share of the running sum's cotangent takes
    its x and k as the products took them, rounded to `cdt`: a pair's term
    then enters at its row and leaves at its column as one number, and the
    sum up the rows holds nothing of it before the column (with one side's
    factor rounded and the other's not, what is left of every pair adds up
    over a chunk's earlier cells)."""
    f32 = jnp.float32
    G_s, k_s, q_s = scratch
    P, C, K = made[0][0].shape
    n = C // SUB
    lanes = lax.broadcasted_iota(jnp.int32, (1, SUB, 2 * C), 2)
    earlier = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    dq, dk, dG, off, off_G = ([], []), ([], []), ([], []), [0.0, 0.0], [0.0, 0.0]
    for i in range(n):
        at = slice(i * SUB, (i + 1) * SUB)
        Mi, Ni = M[:, at], N[:, at]
        Mc, Nc = Mi.astype(cdt), Ni.astype(cdt)
        for h, (G, _, kf) in enumerate(made):
            Gs, ks, qs = G_s[h, :, at, :], k_s[h, :, at, :], q_s[h, :, at, :]
            dq_i = dk_i = dG_i = own_q = own_k = jnp.zeros((P, SUB, K), f32)
            if i:  # the sub-blocks before: relative to this one's first cell
                Gr = G_s[h, :, i * SUB:i * SUB + 1, :]
                reach = jnp.exp(Gs - Gr)
                cols = jnp.where(earlier < i * SUB, jnp.exp(jnp.minimum(Gr - G, 0.0)), 0.0)
                kc = (kf * cols).astype(cdt)
                k_cols = _under(h, kc)
                kr, qr = (ks * reach).astype(cdt), (qs * reach).astype(cdt)
                over_q, over_k = _mm(Nc, k_cols, _BNN), _mm(Mc, k_cols, _BNN)
                dq_i, dk_i = reach * over_q, reach * over_k
                dG_i = qr.astype(f32) * over_q + kr.astype(f32) * over_k
                back = (_mm(Mc, kr, _BTN) + _mm(Nc, qr, _BTN))[:, h * C:(h + 1) * C]  # [P, C, K]
                off[h] = off[h] + cols * back
                off_G[h] = off_G[h] + kc.astype(f32) * back
            for c in range(i * SUB, (i + 1) * SUB):  # its own cells, one at a time
                E = jnp.exp(jnp.minimum(Gs - G_s[h, :, c:c + 1, :], 0.0))
                ke = k_s[h, :, c:c + 1, :] * E
                here = lanes == h * C + c
                mc = jnp.sum(jnp.where(here, Mi, 0.0), axis=2, keepdims=True)  # [P, SUB, 1]
                nc = jnp.sum(jnp.where(here, Ni, 0.0), axis=2, keepdims=True)
                own_q = own_q + nc * ke
                own_k = own_k + mc * ke
                dkc_s[h, :, c:c + 1, :] = jnp.sum((mc * ks + nc * qs) * E, axis=1, keepdims=True)
            dq[h].append(dq_i + own_q)
            dk[h].append(dk_i + own_k)
            dG[h].append(dG_i + qs * own_q + ks * own_k)
    outs = []
    for h, (_, _, kf) in enumerate(made):
        rows = lambda x: jnp.concatenate(x[h], axis=1)
        own = dkc_s[h]  # the diagonal sub-blocks' column terms
        outs.append((rows(dq), rows(dk) + own + off[h], rows(dG) - off_G[h] - kf * own))
    return outs


def _chunk_bwd(sides, S, dO, dS, scratch, dkc_s, seg_row, before, last, cdt, scalar=False,
               shared_key=False, form=RuleForm()):
    """A chunk of a grid step's heads backwards. `sides`, `scratch`, the
    segment ids and the two sequences as `kda_fwd._intra` takes them; for
    each side S `[P, V, K]` in `cdt` (the state the chunk received), dO
    `[P, C, V]` in `cdt`, dS `[P, V, K]` float32 (the cotangent of the state
    it hands on) -> for each side the cotangents of unit q scaled and of
    unit k `[P, C, K]`, of v `[P, C, V]`, of `f + dt_bias` and of the
    decay's softplus times A's share (`[P, C, K]`, or `[P, C, 1]` a decay a
    head: their sums over cells are d dt_bias and dA), of b `[P, C, 1]`,
    all float32, and of the state the chunk received."""
    f32 = jnp.float32
    m = _intra(sides, scratch, seg_row, before, last, cdt, scalar, shared_key, form=form)
    P, C, K = sides[0][1].shape
    half = lambda h, x: x[:, h * C:(h + 1) * C]  # side h's rows of a `[P, 2 C, .]` product

    walked, dPm, dT = [], 0.0, 0.0
    for h in range(2):  # the walk's step again, and its transpose
        s = m.side(h)
        sc, do, dS_h = S[h], dO[h], dS[h]
        dsc = dS_h.astype(cdt)
        vc = (s.U.astype(f32) - _mm(s.wm, sc, _BNT)).astype(cdt)
        dvn = half(h, _mm(m.Pc, do, _BTN)) + _mm(s.kd, dsc, _BNT)
        dvc = dvn.astype(cdt)
        dWc = jnp.where(m.cross, -_mm(dvc, sc, _BNN), 0.0).astype(cdt)
        dQg = jnp.where(m.cross, _mm(do, sc, _BNN), 0.0)
        dKd = jnp.where(m.to_end, _mm(vc, dsc, _BNN), 0.0)
        dPm = dPm + _mm(do, _under(h, vc), _BNT)
        dT = dT + _mm(dWc, _under(h, s.KeG), _BNT) + _mm(dvc, _under(h, sides[h][2]), _BNT)
        walked.append(dict(
            s=s, dQg=dQg, dKd=dKd, dKeG=half(h, _mm(m.Tc, dWc, _BTN)),
            dv=half(h, _mm(m.Tc, dvc, _BTN)),
            ddec=jnp.sum(dS_h * sc.astype(f32), axis=1, keepdims=True),
            dS=_mm(do, s.qg, _BTN) + s.dec * dS_h - _mm(dvc, s.wm, _BTN)))

    # T = Y Diag(b), Y = (I + A)^-1, A = strict-lower(kk) by b down the rows
    Z = _mm32(dT * m.b_row, m.blocks(m.inv), _BNT)  # dY Y^T, a pair
    X = _mm32(m.inv, Z, _BTN)  # `[P, 2 C, 2 C]`: Y^T dY Y^T on its diagonal blocks
    dA = -jnp.where(m.second, X[:, C:], X[:, :C])
    M = jnp.where(m.seen & ~m.eye, dA * m.b_col, 0.0)
    N = jnp.where(m.seen, dPm, 0.0)
    # b down A's rows and along T's columns (a row of sums stood up by `eye`)
    db = dA * jnp.where(m.eye, 0.0, m.kk) + jnp.where(
        m.eye, jnp.sum(dT * m.inv, axis=1, keepdims=True), 0.0)

    if scalar:
        MD, ND = M * m.D, N * m.D
        # the decay's own share of the pairs, a number a cell: D's cotangent
        # `M (.) K K^T + N (.) Q K^T` times D, a row's sum less a column's (a
        # row of sums stood up by `eye`), in float32 as the plain form has it.
        # A cell against itself has no decay (`D[i, i]` = 1): left out, where
        # a row's sum and a column's would each hold it, at order 1, to cancel
        Z = jnp.where(m.eye, 0.0, MD * m.KK + ND * m.QK)
        Z = Z - jnp.where(m.eye, jnp.sum(Z, axis=1, keepdims=True), 0.0)
        MD, ND = MD.astype(cdt), ND.astype(cdt)
        pairs = []
        for h, (_, qf, kf) in enumerate(m.made):
            kc = kf.astype(cdt)
            pairs.append((_mm(ND, _under(h, kc), _BNN), _mm(MD, _under(h, kc), _BNN) + half(
                h, _mm(MD, kc, _BTN) + _mm(ND, qf.astype(cdt), _BTN)), None))
        own = None
    else:
        # a cell against itself has no decay: its term of P (q_i k_i N[i, i];
        # M's diagonal is masked) goes to dq and dk apart, and not to the
        # running sum's cotangent, where it would stand twice at order 1 to cancel
        pairs = _channel_pairs_bwd(m.made, scratch, dkc_s, M, jnp.where(m.eye, 0.0, N), cdt)
        own = [jnp.sum(jnp.where(m.eye & (m.second if h else ~m.second), N, 0.0), axis=2,
                       keepdims=True) for h in range(2)]  # N's diagonal, `[P, C, 1]` a side

    valid = m.seg_col > 0
    bottom = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    outs = []
    for h, ((_, qf, kf), (dq_p, dk_p, dG_p), w) in enumerate(zip(m.made, pairs, walked)):
        s = w["s"]
        dQe, dKe = w["dQg"] * s.eG, w["dKeG"] * s.eG  # of q and k where they stand under e^G
        to_end = w["dKd"] * s.eE
        # where it stands in Kd: against the chunk's last cell (which stands
        # there against itself, with no decay)
        ended = jnp.where(bottom, 0.0, kf * to_end)
        # the running sum's cotangent: x (.) dx - k (.) dk a term, and the
        # chunk's last cell's from Kd and dec
        if scalar:  # the pairs' share a number a cell, spread evenly over the lanes
            dG_p = jnp.sum(jnp.where(m.second if h else ~m.second, Z, 0.0), axis=2,
                           keepdims=True) * (1.0 / K)
        else:
            dq_p, dk_p = dq_p + own[h] * kf, dk_p + own[h] * qf
        dG = dG_p + qf * dQe + kf * dKe - ended
        dG = dG + jnp.where(bottom, jnp.sum(ended, axis=1, keepdims=True) + w["ddec"] * s.dec, 0.0)
        dg = _running_sum(dG, reverse=True)
        if scalar:
            dg = jnp.sum(dg, axis=2, keepdims=True)
        dg = jnp.where(valid, dg, 0.0)
        x, softplus = m.x[h]
        e = jnp.exp(-jnp.abs(x))
        dx = dg * sides[h][5] * (jnp.where(x >= 0, 1.0, e) / (1.0 + e))
        outs.append(dict(
            dqf=dq_p + dQe, dkf=dk_p + dKe + to_end, dv=w["dv"], dx=dx, dA=dg * softplus,
            db=jnp.sum(jnp.where(m.second if h else ~m.second, db, 0.0), axis=2, keepdims=True),
            dS=w["dS"]))
    return outs, m.made


def _unit_bwd(x, xf, dxf, scale):
    """`xf = unit(x) * scale`: x `[n, C, K]` as read, xf and its cotangent
    float32 -> x's cotangent."""
    x = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)
    xh = xf * (1.0 / scale)
    return (r * scale) * (dxf - xh * jnp.sum(xh * dxf, axis=-1, keepdims=True))


def _kernel(n_live_ref, ends_ref, q_ref, k_ref, v_ref, f_ref, b_ref, seg_ref, a_ref,
            bias_ref, bounds_ref, do_ref, dq_ref, dk_ref, dv_ref, df_ref, db_ref, sums_ref,
            st, sts, dst, G_s, k_s, q_s, dkc_s, *, gs, scalar, form):
    """A group of `gs` chunks a step of the third grid axis, from the row's
    last group to its first; the fourth axis walks the group twice: `gs`
    steps forwards from the state the group received (`bounds_ref`), which
    put the state every chunk received into `sts` (the forward kernel's
    chunk without q's half), then `gs` steps backwards."""
    r, hg, s = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    g = pl.num_programs(2) - 1 - pl.program_id(2)
    N = pl.num_programs(2) * gs
    hb, V, K = bounds_ref.shape
    rep = hb * K // q_ref.shape[1]
    cdt = k_ref.dtype
    pairs = _pairs(hb)
    sweep = s < gs
    at = jnp.where(sweep, s, 2 * gs - 1 - s)  # the chunk's place in its group
    c = g * gs + at
    live = c < n_live_ref[r]
    scratch = (G_s, k_s, q_s)

    def chunk_inputs():
        before = jnp.where(c > 0, ends_ref[r * N + jnp.maximum(c - 1, 0)], 0)
        sides, shared_key = _sides(q_ref, k_ref, v_ref, f_ref, b_ref, a_ref, bias_ref, hg, hb,
                                   K, V, scalar)
        return sides, shared_key, before, ends_ref[r * N + c]

    @pl.when((pl.program_id(2) == 0) & (s == 0))
    def _():
        dst[...] = jnp.zeros_like(dst)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(s == 0)
    def _():
        for j in range(hb):
            st[j % 2, j // 2] = bounds_ref[j]

    @pl.when(sweep & live)
    def _():
        for j in range(hb):
            sts[s, j] = st[j % 2, j // 2].astype(cdt)

    @pl.when(sweep & (s < gs - 1) & (c + 1 < n_live_ref[r]))  # the next chunk reads it
    def _():
        sides, shared_key, before, last = chunk_inputs()
        outs = _chunk(sides, [st[0], st[1]], scratch, seg_ref[...], before, last, cdt, scalar,
                      shared_key, with_o=False, form=form)
        for h, (_, s_t) in enumerate(outs):
            st[h] = s_t

    @pl.when(jnp.logical_not(sweep) & live)
    def _():
        sides, shared_key, before, last = chunk_inputs()
        of = lambda h, get: jnp.stack([get(pair[h]) for pair in pairs])
        outs, made = _chunk_bwd(
            sides, [of(h, lambda j: sts[at, j]) for h in range(2)],
            [of(h, lambda j: do_ref[:, j * V:(j + 1) * V]) for h in range(2)],
            [dst[0], dst[1]], scratch, dkc_s, seg_ref[...], before, last, cdt, scalar,
            shared_key, form)
        C = seg_ref.shape[-1] // 2
        lying = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
                 == lax.broadcasted_iota(jnp.int32, (C, C), 1))
        which = lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
        # a head's column `[C, 1]` laid along the lanes of its row of `[hb, C]`
        into = lambda j, col: jnp.where(
            which == j, jnp.sum(jnp.where(lying, col, 0.0), axis=0, keepdims=True), 0.0)
        narrow = lax.broadcasted_iota(jnp.int32, (1, hb), 1)
        db = df = jnp.zeros((hb, C), jnp.float32)
        sums = jnp.zeros((2, hb), jnp.float32)
        by_key = {}
        for h, o in enumerate(outs):
            dst[h] = o["dS"]
            for p, pair in enumerate(pairs):
                j = pair[h]
                if h and j == pair[0]:  # a last odd head beside itself: once
                    continue
                dv_ref[:, j * V:(j + 1) * V] = o["dv"][p].astype(dv_ref.dtype)
                db = db + into(j, o["db"][p])
                cell_sums = jnp.concatenate(
                    [jnp.sum(o[n][p], axis=0, keepdims=True) for n in ("dx", "dA")], axis=0)
                if scalar:
                    df = df + into(j, o["dx"][p])
                    sums = sums + jnp.where(narrow == j, cell_sums, 0.0)
                else:
                    df_ref[:, j * K:(j + 1) * K] = o["dx"][p].astype(df_ref.dtype)
                    sums_ref[:, j * K:(j + 1) * K] += cell_sums
                got = by_key.setdefault(j // rep, [made[h][1][p], made[h][2][p], 0.0, 0.0])
                got[2], got[3] = got[2] + o["dqf"][p], got[3] + o["dkf"][p]
        db_ref[...] = db
        if scalar:
            df_ref[...] = df
            sums_ref[...] += sums
        keys = sorted(by_key)
        stack = lambda i: jnp.stack([by_key[kh][i] for kh in keys])
        cut = lambda ref: jnp.stack([ref[:, kh * K:(kh + 1) * K] for kh in keys])
        dq = _unit_bwd(cut(q_ref), stack(0), stack(2), form.q_scale(K))
        dk = _unit_bwd(cut(k_ref), stack(1), stack(3), 1.0)
        for i, kh in enumerate(keys):
            dq_ref[:, kh * K:(kh + 1) * K] = dq[i].astype(dq_ref.dtype)
            dk_ref[:, kh * K:(kh + 1) * K] = dk[i].astype(dk_ref.dtype)

    @pl.when(jnp.logical_not(sweep | live))
    def _():
        for ref in (dq_ref, dk_ref, dv_ref, df_ref, db_ref):
            ref[...] = jnp.zeros_like(ref)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "form"))
def rule_bwd(q, k, v, f, b, A, dt_bias, segment_ids, n_live, bounds, do, chunk: int,
             interpret: bool = False, form: RuleForm = RuleForm()):
    """`ops/kda.delta_rule`'s transpose from its operands (as
    `kda_fwd.rule_fwd` takes them), the state every group of chunks
    received (`bounds` `[N // group, R, H, V, K]` float32, as `rule_fwd`
    wrote it) and `do` `[R, T, H, V]`: the cotangents of q, k, v, f (in
    their dtypes), b, A and dt_bias (float32); `form` as `rule_fwd` takes it.
    Device op `kda_bwd_rule`.
    Jitted here, as the forward is."""
    R, T, Hk, K = q.shape
    H, V, C, N = v.shape[2], v.shape[-1], chunk, T // chunk
    rep, scalar = H // Hk, f.ndim == 3
    G = bounds.shape[0]
    gs = N // G
    f32 = jnp.float32
    hb, _ = step_heads(H, Hk, K, V)  # value heads a step, as the forward's

    first = lambda gi: (G - 1 - gi) * gs  # groups from the row's last to its first
    reads = lambda r, gi, s, n, e: _at(
        r, first(gi) + jnp.where(s < gs, s, 2 * gs - 1 - s), n)
    # the sweep's steps hold the blocks the group's last chunk will write
    writes = lambda gi, s: first(gi) + jnp.where(s < gs, gs - 1, 2 * gs - 1 - s)
    cells = lambda w: pl.BlockSpec((None, C, w), lambda r, h, gi, s, n, e: (r, writes(gi, s), h))
    # a number a head a cell (db; df where the decay is a head's): cells along lanes
    narrow = pl.BlockSpec((None, None, None, hb, C),
                          lambda r, h, gi, s, n, e: (r, h, writes(gi, s), 0, 0))
    a_narrow = jax.ShapeDtypeStruct((R, H // hb, N, hb, C), f32)
    if scalar:
        df_spec, df_shape = narrow, a_narrow
        sums_spec = pl.BlockSpec((None, None, 2, hb), lambda r, h, gi, s, n, e: (r, h, 0, 0))
        sums_shape = jax.ShapeDtypeStruct((R, H // hb, 2, hb), f32)
    else:
        df_spec, df_shape = cells(hb * K), jax.ShapeDtypeStruct((R, T, H * K), f.dtype)
        sums_spec = pl.BlockSpec((None, 2, hb * K), lambda r, h, gi, s, n, e: (r, 0, h))
        sums_shape = jax.ShapeDtypeStruct((R, 2, H * K), f32)
    half = -(-hb // 2)
    with jax.named_scope("kda_bwd_rule"):
        dq, dk, dv, df, db, sums = pl.pallas_call(
            functools.partial(_kernel, gs=gs, scalar=scalar, form=form),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(R, H // hb, G, 2 * gs),
                in_specs=_in_specs(C, H, hb, rep, K, V, scalar, reads) + [
                    pl.BlockSpec((None, None, hb, V, K),
                                 lambda r, h, gi, s, n, e: (G - 1 - gi, r, h, 0, 0)),
                    pl.BlockSpec((None, C, hb * V),
                                 lambda r, h, gi, s, n, e: (r, reads(r, gi, s, n, e), h))],
                out_specs=[cells(hb // rep * K), cells(hb // rep * K), cells(hb * V), df_spec,
                           narrow, sums_spec],
                scratch_shapes=[pltpu.VMEM((2, half, V, K), f32),
                                pltpu.VMEM((gs, hb, V, K), q.dtype),
                                pltpu.VMEM((2, half, V, K), f32)]
                + [pltpu.VMEM((2, half, C, K), f32)] * 4),
            out_shape=[jax.ShapeDtypeStruct((R, T, Hk * K), q.dtype),
                       jax.ShapeDtypeStruct((R, T, Hk * K), k.dtype),
                       jax.ShapeDtypeStruct((R, T, H * V), v.dtype), df_shape, a_narrow,
                       sums_shape],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=None if scalar else CHANNEL_VMEM_BYTES),
            # dq, dk, dv (and df, a decay a channel) may stand where q, k, dO (and
            # f) stood: a step has read its chunk's blocks before it writes them
            input_output_aliases={11: 2},
            name="kda_bwd_rule", interpret=interpret,
        )(*_operands(q, k, v, f, b, A, dt_bias, segment_ids, n_live, C), bounds,
          do.astype(q.dtype).reshape(R, T, H * V))
    wide = lambda a: a.transpose(0, 2, 4, 1, 3).reshape(R, T, H)  # `[R, ., N, hb, C]` to cells
    if scalar:
        df, (d_bias, dA) = wide(df).astype(f.dtype), jnp.moveaxis(sums, 1, 2).reshape(R, 2, H).sum(0)
    else:
        d_bias, dA = sums.sum(0).reshape(2, H, K)
        df, dA = df.reshape(R, T, H, K), dA.sum(-1)
    return (dq.reshape(R, T, Hk, K), dk.reshape(R, T, Hk, K), dv.reshape(R, T, H, V), df,
            wide(db).astype(b.dtype), dA.astype(A.dtype), d_bias.astype(dt_bias.dtype))
