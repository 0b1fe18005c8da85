"""The delta rule's forward as one kernel (Pallas, TPU): `ops/kda.decay`,
`ops/kda.intra` and the walk over a row's chunks in one call, `kda_fwd_rule`.

A grid step is one chunk of C cells of `HEADS` heads; the chunk axis is
sequential, the state `[V, K]` float32 a head in VMEM scratch (transposed:
the decay then scales lanes). q, k, v and f are read cells-major as
the projections leave them (`[R, T, H K]`: a block `[C, HEADS K]` is
lane-dense as it stands), `O` is written cells-major, and nothing of a
chunk but `O` reaches HBM: no heads-first copy and no `[N, H, C, ...]`
part. A step makes, by `intra`'s own formulas and in its dtypes:

    g = A softplus(f + dt_bias), 0 at padding;  G = cumsum g   (log2 C
        shifted adds down the rows, float32)
    unit q K^-0.5, unit k
    off-diagonal sub-blocks of 16: matrix products relative to the later
        sub-block's first cell, operands in the compute dtype
    diagonal sub-blocks cell by cell: exp(min(G_i - G_j, 0)) a cell j of
        the sub-block, float32 (never the exponential of a positive
        number; what stands above the diagonal is masked after)
    (I + A)^-1 by squarings, float32;  W, U;  the masks of the sequence
        that crossed in and of the one handed on
    Vn = U - Wm S;  O = Qg S + Pm Vn;  S' = Diag(dec) S + Kd^T Vn

Two heads' `[C, C]` matrices (A, P, the inverse) stand side by side in one
`[C, 2 C]` array, full lanes at C = 64: a float32 product of the squarings
(six passes of the MXU) then serves both heads, against the two blocks on a
diagonal of `[2 C, 2 C]`. Every array carries the step's pairs on a leading
axis (`[P, C, ...]`, the products batched): the pairs go through each stage
together, so that one pair's chain of products fills the units while
another's waits, and an operation is traced and lowered once for all of
them (the body is host Python that no compile cache saves: unrolled over
eight heads it cost 4-7 s of a warm set-up). By the probe (`PERF.md`
section 6, PR 53), ms a call at 53 % fill: 10.5 a head at a time, 6.8 in
pairs, 5.3 with four pairs staged, 4.85 with the pairs in front.

Both decays (`ops/kda.py`): a decay a channel as above; one decay a head
(f `[R, T, H]`, read a head's column a cell as beta is) is spread over the K
lanes in VMEM for the running sum, and the sub-block work gives way to one
`[C, 2 C]` exponential a pair (`exp(min(G_i - G_j, 0))`, rows against the
diagonal's row) under one `K K^T` and one `Q K^T`, which the two value heads
of a pair share where they read one key head. Key heads under value heads:
the q and k blocks hold `heads / rep` key heads and a value head cuts its
key head's columns; nothing is repeated in HBM. By the probe (PERF.md
section 6, PR 54): 4.82 ms a call the channel form, 3.45 the head form at 16
key heads.

A chunk past a row's last live one (`n_live`, a scalar the index maps
read) fetches nothing new, computes nothing and writes zeros. Beside `O`
the kernel writes the state every group of `group` chunks received
(`bounds`, the one residual `ops/kda._rule_bwd` keeps beside its inputs;
zeros for a group the row does not reach).

The backward is `ops/pallas/kda_bwd.py`'s kernel, `kda_bwd_rule`. It makes a
chunk again by this module's own functions: `_intra` (everything of a
chunk that does not depend on the state it receives: what `_chunk` walks),
`_chunk` without q's half for the chunks' states, `_channel_pairs`,
`_running_sum` (and its transpose), the blocks' specs and `_sides`.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.kda import LANES, SUB, RuleForm, unit

HEADS = 8  # heads a grid step: four pairs whose chains of products are independent


def _heads(H: int, most: int) -> int:
    """The largest divisor of H that is at most `most`."""
    hb = min(H, most)
    while H % hb:
        hb -= 1
    return hb


def step_heads(H: int, Hk: int, K: int, V: int):
    """(value heads a grid step, whether its blocks are whole lane tiles):
    whole key heads, at most `HEADS` value heads, the most whose blocks of q
    and k (`heads / rep` keys of K) and of v and O (`heads` values of V) are
    whole tiles of `LANES`; where no count's are (toy heads, which interpret
    mode alone runs), the most. Heads of 128 x 128: 8, as ever. 30 heads of
    128 (keys of 96 widened: `ops/kda.key_lanes`) x 192: 6, a block of v
    nine tiles (5 would be seven and a half)."""
    rep = H // Hk
    most = _heads(Hk, max(1, HEADS // rep))
    for hk in range(most, 0, -1):
        if Hk % hk == 0 and hk * K % LANES == 0 and rep * hk * V % LANES == 0:
            return rep * hk, True
    return rep * most, False


def _mm(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


_BNN = (((2,), (1,)), ((0,), (0,)))  # a pair at a time: a @ b
_BNT = (((2,), (2,)), ((0,), (0,)))  # a @ b^T
_BTN = (((1,), (1,)), ((0,), (0,)))  # a^T @ b


def _mm32(a, b):
    return lax.dot_general(a, b, _BNN, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _running_sum(g, reverse=False):
    """Down the C rows of each of g `[P, C, K]` float32, in log2 C shifted
    adds; `reverse`: up them, a row the sum of itself and those after it
    (the running sum's transpose)."""
    P, C, K = g.shape
    g = g.reshape(P * C, K)
    row = lax.broadcasted_iota(jnp.int32, g.shape, 0) % C
    s = 1
    while s < C:
        if reverse:
            g = g + jnp.where(row < C - s, pltpu.roll(g, P * C - s, 0), 0.0)
        else:
            g = g + jnp.where(row >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g.reshape(P, C, K)


def _under(h, x):
    """A side's `[P, C, x]` operand under the pairs' `[P, C, 2 C]` matrices:
    zeros where the other side's rows would stand."""
    return jnp.concatenate((jnp.zeros_like(x), x) if h else (x, jnp.zeros_like(x)), axis=1)


def _channel_pairs(made, scratch, seen, cdt, with_q=True):
    """A decay a channel: `kk` `[P, C, 2 C]` float32 and `P` in `cdt`, masked
    by `seen`: sub-blocks of 16, an off-diagonal one a product relative to
    the later sub-block's first cell, a diagonal one cell by cell. (`P` is
    None without `with_q`: the sweep that makes states alone reads no q.)"""
    f32 = jnp.float32
    G_s, k_s, q_s = scratch
    P, C, K = made[0][0].shape
    n = C // SUB
    lanes = lax.broadcasted_iota(jnp.int32, (1, SUB, 2 * C), 2)
    earlier = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    kk_rows, p_rows = [], []
    for i in range(n):
        at = slice(i * SUB, (i + 1) * SUB)
        acc_k = acc_q = jnp.zeros((P, SUB, 2 * C), f32)
        for h, (G, _, kf) in enumerate(made):
            Gs, ks = G_s[h, :, at, :], k_s[h, :, at, :]
            qs = q_s[h, :, at, :] if with_q else None
            if i:  # the sub-blocks before: relative to this one's first cell
                Gr = G_s[h, :, i * SUB:i * SUB + 1, :]
                reach = jnp.exp(Gs - Gr)
                k_cols = _under(h, (kf * jnp.where(earlier < i * SUB, jnp.exp(
                    jnp.minimum(Gr - G, 0.0)), 0.0)).astype(cdt))
                acc_k = acc_k + _mm((ks * reach).astype(cdt), k_cols, _BNT)
                if with_q:
                    acc_q = acc_q + _mm((qs * reach).astype(cdt), k_cols, _BNT)
            for c in range(i * SUB, (i + 1) * SUB):  # its own cells, one at a time
                ke = k_s[h, :, c:c + 1, :] * jnp.exp(
                    jnp.minimum(Gs - G_s[h, :, c:c + 1, :], 0.0))
                here = lanes == h * C + c
                acc_k = jnp.where(here, jnp.sum(ks * ke, axis=2, keepdims=True), acc_k)
                if with_q:
                    acc_q = jnp.where(here, jnp.sum(qs * ke, axis=2, keepdims=True), acc_q)
        kk_rows.append(acc_k)
        p_rows.append(acc_q)
    kk = jnp.where(seen, jnp.concatenate(kk_rows, axis=1), 0.0)  # [P, C, 2 C]
    if not with_q:
        return kk, None
    return kk, jnp.where(seen, jnp.concatenate(p_rows, axis=1), 0.0).astype(cdt)


def _intra(sides, scratch, seg_row, before, last, cdt, scalar=False, shared_key=False,
           with_q=True, form=RuleForm()):
    """What of a chunk does not depend on the state it receives
    (`ops/kda.intra`), for a grid step's heads as P pairs: a pair's `[C, C]`
    matrices stand side by side in `[C, 2 C]` (full lanes at C = 64; a
    float32 product of the inverse then takes both heads at once, against
    the two blocks on a diagonal), and every array has the pairs in front
    (`[P, ...]`: an operation is traced once for all of them, and the
    pairs' chains of products, which are independent, fill the units while
    one another's wait). `sides`: the pairs' first heads and their second
    heads, each q, k, v, f `[P, C, K]` in `cdt`, b_col `[P, C, 1]`, A and
    dt_bias `[P, 1, K]`; `scratch` three `[2, P, C, K]` float32 refs (the
    running sum, unit k and q: rows and sub-blocks of them are read back
    from there); seg_row `[1, 2 C]` (the chunk's segment ids, twice), the
    sequences the chunk before handed on and this one hands on (scalars).
    -> the masks, `made` (a side's running sum, unit q scaled and unit k),
    `kk` and the inverse `inv` `[P, C, 2 C]` float32, `Pc` and `Tc` in
    `cdt`, b down the rows and along them, and a side's parts (`side`: W,
    U, Wm, Qg, Kd, dec and the exponentials under them). The forward walks
    them (`_chunk`); the backward kernel makes them again and pulls the
    parts' cotangents back through them (`ops/pallas/kda_bwd.py`).

    A decay a head (`ops/kda._intra_head`): a side's f is `[P, C, 1]` and
    its A and dt_bias `[P, 1, 1]`; the running sum is spread over the K
    lanes here, in VMEM, and `exp(G_i - G_j)` is one `[C, 2 C]` matrix a
    pair under one product `K K^T` and one `Q K^T` a side (one for both
    where the pair's heads read one key head, `shared_key`): no
    sub-blocks. Without `with_q` nothing of q is made (`Pc`, `Qg`: the
    sweep that makes the chunks' states for the backward reads neither).

    `form` (`ops/kda.RuleForm`): unit q is scaled by `K^-0.5` of the keys'
    own width (keys widened to whole lane tiles with zeros, `ops/kda.key_lanes`,
    keep their own: a zero lane moves no norm, no product and no state), and
    under `doubling` the inverse is made by doubling blocks,
    `ops/kda._inverse_unit_lower`'s other form: as many products and no power
    of A (a beta that reaches 2). v and the state's other side are V wide,
    whatever K is: nothing here takes the state for a square."""
    f32 = jnp.float32
    P, C, K = sides[0][1].shape
    scale = form.q_scale(K)
    G_s, k_s, q_s = scratch
    m = types.SimpleNamespace()
    row = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    lane = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    m.second = second = lane >= C  # the second head's half
    col = jnp.where(second, lane - C, lane)
    m.eye = eye = row == col
    m.seg_col = seg_col = jnp.sum(
        jnp.where(eye & ~second, seg_row, 0), axis=1, keepdims=True)  # [C, 1]
    m.seen = seen = (seg_col == seg_row) & (row >= col)
    m.cross = (seg_col == before) & (seg_col > 0)
    m.to_end = seg_col == last
    m.carry = (last == before) & (last > 0)

    m.made = made = []
    m.x = []  # a side's `f + dt_bias`, float32: the backward's softplus reads it again
    for h, (q, k, _, f, _, A, dt_bias) in enumerate(sides):
        x = f.astype(f32) + dt_bias
        softplus = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))
        g = jnp.where(seg_col > 0, A * softplus, 0.0)
        if scalar:  # one number a cell: over the lanes, in VMEM alone
            g = jnp.broadcast_to(g, (P, C, K))
        G_s[h] = G = _running_sum(g)  # <= 0, falling
        qf = unit(q) * scale if with_q else None
        kf = unit(k)
        if not scalar:  # the sub-blocks read rows of them back
            k_s[h] = kf
            if with_q:
                q_s[h] = qf
        made.append((G, qf, kf))
        m.x.append((x, softplus))

    if scalar:
        wide = lambda x: jnp.broadcast_to(x[:, :, :1], (P, C, 2 * C))
        G_col = jnp.where(second, wide(made[1][0]), wide(made[0][0]))
        G_row = jnp.sum(jnp.where(eye, G_col, 0.0), axis=1, keepdims=True)  # [P, 1, 2 C]
        m.D = D = jnp.exp(jnp.minimum(G_col - G_row, 0.0))
        over = lambda x, kc: _mm(x.astype(cdt), kc, _BNT)  # [P, C, C]
        kcs = [kf.astype(cdt) for _, _, kf in made]
        if shared_key:
            kk0 = over(made[0][2], kcs[0])
            kk_pair = (kk0, kk0)
        else:
            kk_pair = tuple(over(kf, kc) for (_, _, kf), kc in zip(made, kcs))
        m.KK = jnp.concatenate(kk_pair, axis=2)  # [P, C, 2 C], before the decay
        kk = jnp.where(seen, m.KK * D, 0.0)
        Pc = None
        if with_q:
            if shared_key:
                qk0 = over(made[0][1], kcs[0])
                qk_pair = (qk0, qk0)
            else:
                qk_pair = tuple(over(qf, kc) for (_, qf, _), kc in zip(made, kcs))
            m.QK = jnp.concatenate(qk_pair, axis=2)
            Pc = jnp.where(seen, m.QK * D, 0.0).astype(cdt)
    else:
        kk, Pc = _channel_pairs(made, scratch, seen, cdt, with_q)
    m.kk, m.Pc = kk, Pc
    m.b_col = b_col = jnp.where(second, sides[1][4], sides[0][4])  # [P, C, 2 C]
    m.b_row = b_row = jnp.sum(jnp.where(eye, b_col, 0.0), axis=1, keepdims=True)  # [P, 1, 2 C]

    # the two heads' blocks on a diagonal of `[2 C, 2 C]`
    m.blocks = blocks = lambda x: jnp.concatenate(
        [jnp.where(second, 0.0, x), jnp.where(second, x, 0.0)], axis=1)
    ident = eye.astype(f32)
    power = jnp.where(eye, 0.0, kk) * b_col
    if form.doubling:  # exact on blocks of 2; X - X off X is the inverse over blocks twice as long
        same = lambda s: (row // s) == (col // s)
        inv, s = ident - jnp.where(same(2), power, 0.0), 2
        while s < C:
            off = jnp.where(same(2 * s) & ~same(s), power, 0.0)
            inv = inv - _mm32(_mm32(inv, blocks(off)), blocks(inv))
            s *= 2
    else:
        inv, n = ident - power, 2
        while n < C:  # (I + a)^-1 = (I - a)(I + a^2)(I + a^4)...
            power = _mm32(power, blocks(power))
            inv = _mm32(inv, blocks(ident + power))
            n *= 2
    m.inv = inv
    m.Tc = Tc = (inv * b_row).astype(cdt)

    def side(h):
        """Side h's parts, masks folded in."""
        G, qf, kf = made[h]
        s = types.SimpleNamespace()
        s.eG = eG = jnp.exp(G)
        s.KeG = (kf * eG).astype(cdt)
        s.W = _mm(Tc, _under(h, s.KeG), _BNN)
        s.U = _mm(Tc, _under(h, sides[h][2]), _BNN).astype(cdt)
        G_end = G_s[h, :, C - 1:C, :]
        s.wm = jnp.where(m.cross, s.W, 0.0).astype(cdt)
        s.qg = jnp.where(m.cross, qf * eG, 0.0).astype(cdt) if with_q else None
        s.eE = jnp.exp(G_end - G)
        s.kd = jnp.where(m.to_end, kf * s.eE, 0.0).astype(cdt)
        s.dec = jnp.where(m.carry, jnp.exp(G_end), 0.0)
        return s

    m.side = side
    return m


def _chunk(sides, states, scratch, seg_row, before, last, cdt, scalar=False,
           shared_key=False, with_o=True, form=RuleForm()):
    """A chunk of a grid step's heads: `_intra`, then the walk's step from
    `states`, the sides' states `[P, V, K]` float32 -> for each side O
    `[P, C, V]` float32 (None without `with_o`) and the state handed on."""
    f32 = jnp.float32
    m = _intra(sides, scratch, seg_row, before, last, cdt, scalar, shared_key, with_o, form)
    outs = []
    for h, s_t in enumerate(states):
        s = m.side(h)
        sc = s_t.astype(cdt)
        vc = (s.U.astype(f32) - _mm(s.wm, sc, _BNT)).astype(cdt)
        o = _mm(s.qg, sc, _BNT) + _mm(m.Pc, _under(h, vc), _BNN) if with_o else None
        outs.append((o, s.dec * s_t + _mm(vc, s.kd, _BTN)))
    return outs


def _pairs(hb):
    """A step's heads two by two; a last odd one stands beside itself."""
    return [(j, min(j + 1, hb - 1)) for j in range(0, hb, 2)]


def _sides(q_ref, k_ref, v_ref, f_ref, b_ref, a_ref, bias_ref, hg, hb, K, V, scalar):
    """The two sides of a grid step's `hb` heads (`_intra`'s `sides`) cut
    from its blocks, and whether every pair's heads read one key head."""
    rep = hb * K // q_ref.shape[1]  # value heads a key head
    pairs = _pairs(hb)
    b = b_ref[...]
    which = lax.broadcasted_iota(jnp.int32, b.shape, 1)
    # a head's column of a `[., H]` block (b; a head's decay: f, A, dt_bias)
    column = lambda x, j: jnp.sum(
        jnp.where(which[:x.shape[0]] == hg * hb + j, x, 0.0), axis=1, keepdims=True)

    def side(h):
        js = [pair[h] for pair in pairs]
        cut = lambda ref, w, at=lambda j: j: jnp.stack(
            [ref[:, at(j) * w:(at(j) + 1) * w] for j in js])
        key = lambda j: j // rep  # the key head a value head reads
        b_col = jnp.stack([column(b, j) for j in js])
        if scalar:
            decay = tuple(jnp.stack([column(ref[...].astype(jnp.float32), j) for j in js])
                          for ref in (f_ref, a_ref, bias_ref))
        else:
            decay = (cut(f_ref, K), cut(a_ref, K), cut(bias_ref, K))
        return (cut(q_ref, K, key), cut(k_ref, K, key), cut(v_ref, V), decay[0], b_col,
                decay[1], decay[2])

    return [side(0), side(1)], all(a // rep == b // rep for a, b in pairs)


def _kernel(n_live_ref, ends_ref, q_ref, k_ref, v_ref, f_ref, b_ref, seg_ref, a_ref,
            bias_ref, o_ref, bounds_ref, st, *scratch, group, scalar, form):
    r, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    N = pl.num_programs(2)
    hb, V, K = bounds_ref.shape
    pairs = _pairs(hb)
    live = c < n_live_ref[r]

    @pl.when(c == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    @pl.when(c % group == 0)
    def _():
        for j in range(hb):
            bounds_ref[j] = jnp.where(live, st[j % 2, j // 2], 0.0)

    @pl.when(live)
    def _():
        before = jnp.where(c > 0, ends_ref[r * N + jnp.maximum(c - 1, 0)], 0)
        last = ends_ref[r * N + c]
        sides, shared_key = _sides(q_ref, k_ref, v_ref, f_ref, b_ref, a_ref, bias_ref, hg, hb,
                                   K, V, scalar)
        outs = _chunk(sides, [st[0], st[1]], scratch, seg_ref[...], before, last,
                      q_ref.dtype, scalar, shared_key, form=form)
        for h, (o, s_t) in enumerate(outs):
            st[h] = s_t
            for p, pair in enumerate(pairs):
                o_ref[:, pair[h] * V:(pair[h] + 1) * V] = o[p].astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _at(r, c, n):
    """A chunk past the last live one fetches the last live one's blocks again."""
    return jnp.minimum(c, jnp.maximum(n[r] - 1, 0))


def _in_specs(C, H, hb, rep, K, V, scalar, chunk_of):
    """The blocks of q, k, v, f, b, the segment ids (twice), A and dt_bias
    for `hb` value heads of the chunk `chunk_of(r, *at)`, `at` what an index
    map gets after the row and the heads' step."""
    cells = lambda w: pl.BlockSpec((None, C, w), lambda r, h, *at: (r, chunk_of(r, *at), h))
    a_cell = pl.BlockSpec((None, C, H), lambda r, h, *at: (r, chunk_of(r, *at), 0))
    if scalar:  # a head's decay: a column of `[., H]` blocks, as beta's
        a_head = pl.BlockSpec((1, H), lambda r, h, *at: (0, 0))
        f_spec = a_cell
    else:
        a_head = pl.BlockSpec((1, hb * K), lambda r, h, *at: (0, h))
        f_spec = cells(hb * K)
    seg = pl.BlockSpec((None, None, 1, 2 * C), lambda r, h, *at: (r, chunk_of(r, *at), 0, 0))
    return [cells(hb // rep * K), cells(hb // rep * K), cells(hb * V), f_spec, a_cell, seg,
            a_head, a_head]


def _operands(q, k, v, f, b, A, dt_bias, segment_ids, n_live, C):
    """The two scalar tables and the arrays as `_in_specs` reads them."""
    R, T, Hk, K = q.shape
    H, V, N = v.shape[2], v.shape[-1], T // C
    f32 = jnp.float32
    seg = segment_ids.reshape(R, N, C)
    if f.ndim == 3:
        f_in, consts = f, (A.astype(f32)[None], dt_bias.astype(f32)[None])
    else:
        f_in = f.reshape(R, T, H * K)
        consts = (jnp.repeat(A.astype(f32), K)[None], dt_bias.astype(f32).reshape(1, H * K))
    return (n_live.astype(jnp.int32), seg[:, :, -1].reshape(R * N).astype(jnp.int32),
            q.reshape(R, T, Hk * K), k.reshape(R, T, Hk * K), v.reshape(R, T, H * V),
            f_in, b, jnp.tile(seg[:, :, None, :], (1, 1, 1, 2)), *consts)


@functools.partial(jax.jit, static_argnames=("chunk", "group", "interpret", "form"))
def rule_fwd(q, k, v, f, b, A, dt_bias, segment_ids, n_live, chunk: int, group: int,
             interpret: bool = False, form: RuleForm = RuleForm()):
    """`ops/kda.delta_rule`'s forward: q, k `[R, T, Hk, K]` (Hk key heads;
    value head j reads key head `j // (H / Hk)` through the blocks' index:
    nothing is repeated), v `[R, T, H, V]`, f `[R, T, H, K]` with dt_bias
    `[H, K]` (a decay a channel) or f `[R, T, H]` with dt_bias `[H]` (a
    decay a head: read a head a cell and spread over lanes in VMEM), b `[R,
    T, H]` float32, A `[H]`, segment_ids `[R, T]`, T a multiple of `chunk`,
    n_live `[R]` the chunks of a row up to its last token's -> o `[R, T, H,
    V]` in q's dtype and the state every `group` chunks received, `[N //
    group, R, H, V, K]` float32. `form`: `ops/kda.RuleForm` (the keys' own
    width where q and k come widened with zero lanes, and how `I + A` is
    inverted). Device op `kda_fwd_rule`. Jitted here: the
    layers of a stack, their forward and remat's, trace the kernel's body
    once a shape and lower it once a program."""
    R, T, Hk, K = q.shape
    H, V, C, N = v.shape[2], v.shape[-1], chunk, T // chunk
    rep, scalar = H // Hk, f.ndim == 3
    hb, _ = step_heads(H, Hk, K, V)  # value heads a step: whole key heads
    with jax.named_scope("kda_fwd_rule"):
        o, bounds = pl.pallas_call(
            functools.partial(_kernel, group=group, scalar=scalar, form=form),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(R, H // hb, N),
                in_specs=_in_specs(C, H, hb, rep, K, V, scalar,
                                   lambda r, c, n, e: _at(r, c, n)),
                out_specs=[pl.BlockSpec((None, C, hb * V), lambda r, h, c, n, e: (r, c, h)),
                           pl.BlockSpec((None, None, hb, V, K),
                                        lambda r, h, c, n, e: (c // group, r, h, 0, 0))],
                scratch_shapes=[pltpu.VMEM((2, -(-hb // 2), V, K), jnp.float32)]
                + [pltpu.VMEM((2, -(-hb // 2), C, K), jnp.float32)] * 3),
            out_shape=[jax.ShapeDtypeStruct((R, T, H * V), q.dtype),
                       jax.ShapeDtypeStruct((N // group, R, H, V, K), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="kda_fwd_rule", interpret=interpret,
        )(*_operands(q, k, v, f, b, A, dt_bias, segment_ids, n_live, C))
    return o.reshape(R, T, H, V), bounds
