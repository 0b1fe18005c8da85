"""A delta-rule mixer over packed rows, in the three published forms: a
decay for every channel (Kimi Delta Attention, arXiv:2510.26692), one
decay a head under fewer key heads than value heads (Gated DeltaNet,
arXiv:2412.06464, as Qwen3-Next runs it), and one decay a head with values
twice as wide as keys and a doubled beta (Gated DeltaNet at its authors'
`expand_v` 2 with arXiv:2411.12537's negative eigenvalues, as Olmo-Hybrid
runs it: the second column below with V != K and `b = 2 sigmoid`).
`KDAConfig` says which.

For a layer's input `h` [R, T, D] (normalised, where the block norms on the
way in), with H value heads and Hk key
heads (Hk = H a decay a channel; Hk divides H a decay a head, value head j
reading key head `j // (H / Hk)`) whose keys are K = `head_dim` wide and
whose values are V = `value_dim` wide (V = K a decay a channel; any V a
decay a head: the state a head is a rectangle `[K, V]`), side by side:

    a decay a channel (`decay="channel"`)        | a decay a head (`decay="head"`)
    q, k, v = silu(conv(h W_q)), .. W_k, .. W_v  | the same; q, k [T, Hk, K], v [T, H, K]
      conv: causal, depthwise, `conv_kernel` taps, no bias; a tap is dropped
      unless its position lies in the token's own sequence
    q, k = q * rsqrt(sum q^2 + 1e-6), k likewise, a head;  q <- q * K^-0.5
    g = -exp(A_log)[H] softplus((h W_fa) W_fb    | g = -exp(A_log)[H] softplus(h W_a
        + dt_bias[H, K])    [T, H, K] float32    |     + dt_bias[H])        [T, H] float32
    b = sigmoid(h W_b), doubled under `neg_eigval` (a decay a head)     [T, H]
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t))     | S_t = exp(g_t) (I - b_t k_t k_t^T) S_{t-1}
          S_{t-1} + b_t k_t v_t^T                |       + b_t k_t v_t^T
      [K, V] a value head, float32, S = 0 before a sequence's first token
    o_t = S_t^T q_t
    out = (RMSNorm_head(o) * sigmoid((h W_ga)    | out = (RMSNorm_head(o) * silu(h W_g)) W_o
          W_gb)) W_o                             |

(The released code of the second decays the state first, `S <- exp(g_t) S`,
then corrects: `d = b_t (v_t - S^T k_t)`, `S <- S + k_t d^T`. With a scalar
decay that is the line above; with a decay a channel the first's order,
`(I - b k k^T) Diag(a) S`, is the same statement.)

What is token-wise (the projections before, the head norm, gate and `W_o`
after) is `models/transformer.py`'s (`_before_mixer`, `_after_mixer`);
here is what crosses tokens (`kda_mixer`): the convolutions, the decay,
and the rule (`delta_rule`, which makes q and k unit a head on its way in).

A packed row holds several sequences (segment ids, 0 = padding): state and
convolution start afresh at every sequence start; a padding cell has
b = 0, g = 0 and q = k = v = 0, so it adds nothing to any state and its own
result is 0.

**The rule in chunks** of `chunk_size` C positions (`delta_rule`; one
`custom_vjp`, one `_Groups`, one walk for both decays: the branch is on the
decay's rank, known when the program is traced). With `G_i` the running sum
of `g` inside a chunk (restarting nowhere: the masks do the restarting), a
chunk that receives the state `S_0`, a decay a channel:

    A[i, j] = b_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])   j < i of one sequence, else 0
    T = (I + A)^-1 Diag(b);   W = T (K * exp(G));   U = T V
    O = (Q * exp(G)) S_0' + tril(P) (U - W S_0'),
        P[i, j] = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d]),  j <= i of one sequence
    S_C = Diag(exp(G_C)) S_0' + (K * exp(G_C - G))^T (U - W S_0')

A decay a head (`_intra_head`): `G` is one number a position a value head
and `D[i, j] = exp(G_i - G_j)` for `j <= i` a `[C, C]` matrix, at most 1, so

    A = b (.) tril(K K^T, -1) (.) D;   T = (I + A)^-1 Diag(b)
    W = T (K * exp(G));   U = T V
    O = exp(G) * (Q S_0') + (tril(Q K^T) (.) D) (U - W S_0')
    S_C = exp(G_C) S_0' + (K * exp(G_C - G))^T (U - W S_0')

one product `K K^T` and one `Q K^T` a key head and one exponential a value
head, every exponent at most 0 and no sub-blocks. **The decay a head is
never laid out a channel in HBM**: `f`, `g` and their gradients are `[T,
H]`; a kernel spreads them over lanes in VMEM, and of the parts only a
chunk's `dec` holds a head's number K times (a chunk's, not a cell's: the
walk then serves both). q and k stay a key head's: the forward kernel reads
them through its blocks' index, the plain form spreads the `[C, C]` products
over a key head's value heads, and their gradients sum over those.

`S_0'` is `S_0` for the cells of the sequence that crossed into the chunk
and 0 for the rest; a chunk hands on the state of the sequence its last
cell belongs to (as `ops/ssm.chunked_scan`). Two steps: `intra` makes, for
every chunk of a group at once, what does not depend on the state (W, U,
Q exp(G), K exp(G_C - G), tril(P), exp(G_C), each with its mask folded
in); the walk carries `S` over the group's chunks (`states_scan`, a
`lax.scan`). `delta_rule` takes a row a group of chunks at a time, up to
the group of its last token.

**Which form runs where** (`use_kernel`: a TPU backend, one device, heads
whose blocks are whole lane tiles; decided from what the code sees, no
argument). **Keys that are no whole tile** (`key_lanes`: 96 of 128) go into
the kernels widened to one with zero lanes, q's, k's and the convolutions'
weights alike, in `kda_mixer` on the way into the taps: a zero lane moves no
norm, no product and no state (the state stands `[V, 128]` there with 32
columns of zeros), q's scale stays the keys' own `K^-0.5` (`RuleForm.key_dim`), and the
zeros' transpose is a slice. They cross HBM: a third more of q's and k's
bytes, a tenth of the rule's (counted in its roofline's bytes). By the
compiler and the probe (`scripts/kda_layout_probe.py`; PERF.md section 6,
PR 60): blocks of 96-wide heads as they stand are no whole tiles at any
count of heads that divides 30 but all of them; the whole width a step (15
pairs, keys at 96) compiles given 110 MB of VMEM and gives the same numbers
to the bit, at 3.15 / 15.7 ms a call forward / forward + backward (a row of
8,192, 70 % full, 30 heads of 96 x 192) and 13.6 / 57.1 s to build, where
the widened keys, 6 heads a step, take 2.90 / 9.35 ms and 3.0 / 22.2 s; the
plain form 6.37 / 23.3 ms. Values of 192 are a tile and a half: a step takes
an even count of heads (`kda_fwd.step_heads`: 6 of 30).

- forward, on the chip, both decays: one kernel over the whole row,
  `kda_fwd_rule` (`ops/pallas/kda_fwd.py`): the decay, `intra`'s formulas
  in `intra`'s dtypes (the channel form's sub-blocks, or the head form's
  one `[C, 2 C]` exponential a pair of heads) and the walk, a chunk a grid
  step with the state in VMEM; q, k, v, f read and O written cells-major,
  nothing else of a chunk in HBM. Under full remat both forward runs of a
  step take it.
- backward, on the chip, both decays: one kernel over the whole row,
  `kda_bwd_rule` (`ops/pallas/kda_bwd.py`), a group of chunks at a time
  from the row's last: the chunks' states again from the state the group
  received (the forward kernel's chunk without q's half, the states in
  VMEM), then the chunks backwards: `intra` again, the walk's transpose
  with the state's cotangent in VMEM, and `intra`'s pullback by hand (the
  inverse's rule in two float32 products, the pairs' cotangents under the
  forward's own exponentials, the running sum's as `x (.) dx - k (.) dk`);
  q, k, v, f, b and dO read and dq, dk, dv, df, db written cells-major,
  dA and d dt_bias summed on the way. No loop of XLA's, no part of a chunk
  and no cotangent of one in HBM.
- the mixer's way into the rule, on the chip, both decays: q's, k's and v's
  mask and short convolution are a kernel pair, `kda_taps_fwd` /
  `kda_taps_bwd` (`ops/pallas/kda_taps.py`: `ops/ssm.causal_conv`'s meaning
  with the mask inside, an array read once and written once, the weights'
  sums inside the backward), where the rule takes its kernels and a row is
  whole blocks and the widths (the keys' as widened) whole lane tiles
  (`taps_in_kernel`); `causal_conv` after a `where` everywhere else.
- the CPU, a mesh of several devices, toy heads: `intra` + `states_scan`
  a group at a time forward, `states_scan` and `states_scan_bwd` under
  `jax.vjp` of `intra` backward: the plain form, and the tests' reference.

By the probe (`scripts/kda_probe.py`; PERF.md section 6, PR 55), a row of
16,384 at 53 % fill, 32 value heads of 128, bf16, ms forward / forward +
backward: the channel form 4.84 / 18.64 (4.85 / 49.8 with the backward a
loop of XLA's over groups, 14.01 / 59.0 the plain form), the head form under
16 key heads 3.45 / 11.40 (3.45 / 19.3; 8.94 / 25.2).

**No exponential of a positive number.** A decay a channel: `exp(G_i - G_j)` is never split
into `exp(G_i) exp(-G_j)` across a chunk (a decay of 0.2 a token over 64
positions is 1e-45): a chunk is sub-blocks of 16; an off-diagonal
sub-block is taken relative to the later sub-block's first position r
(`exp(G_i - G_r)` and `exp(G_r - G_j)`, both at most 1) and is a matrix
product; a diagonal sub-block is taken cell by cell. `A` is strictly
lower triangular, so `(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...`, `log2 C`
squarings in float32 (backwards the inverse's own rule, two products); under
a beta that reaches 2 (`RuleForm.doubling`) the same count of products
doubles blocks on the diagonal and forms no power of A
(`_inverse_unit_lower`). Decays, running sums, A, P and the inverse are
float32; the other matrix products take operands in the compute dtype and
accumulate in float32.

The backward pass (`delta_rule`'s `custom_vjp`) keeps the rule's inputs
and the state each group received; group by group from the last, it makes
`intra` and the chunks' states again, walks the chunks backwards for the
state's part, and differentiates `intra` for the rest: the plain form by
`jax.vjp` in a loop over groups, the kernel by hand inside its grid.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import KDAConfig
from areal_tpu.ops.ssm import causal_conv

SUB = 16  # a chunk's sub-blocks: decays inside one are taken cell by cell
LANES = 128  # a lane tile: what a kernel's blocks are whole multiples of
L2_EPS = 1e-6
# The rule takes a call's rows this many cells at a time, `intra` and the
# walk, forward and backward: what it holds at once is a group's, not a row's.
GROUP_CELLS = 1024


_mm32 = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


class RuleForm(NamedTuple):
    """What `delta_rule` is told of its operands beyond their shapes (static:
    a program a form). `key_dim`: the keys' own width where q and k come
    widened with zero lanes (`key_lanes`; the kernels' alone: q's scale is its
    `^-0.5`), None: K as it stands. `doubling`: `(I + A)^-1` by doubling
    blocks (`_inverse_unit_lower`), for a beta that reaches 2."""
    key_dim: Optional[int] = None
    doubling: bool = False

    def q_scale(self, K: int) -> float:
        """What unit q is scaled by, of keys that stand K wide."""
        return (self.key_dim or K) ** -0.5


def init_kda_params(kda: KDAConfig, hidden_dim: int, dense_fn, key, n_layers: int,
                    pdt) -> Dict[str, Any]:
    """`n_layers` mixers stacked on a leading axis. `A_log` (a head) and
    `dt_bias` (a channel, or a head where the decay is one) as
    `ops/ssm.init_ssm_params` draws them: log of a uniform draw from
    1..16, the inverse softplus of a log-uniform step in [dt_min,
    dt_max]: before its input moves it, a channel (a head) forgets at
    0.999 to 0.2 a token. The decay's input and the gate by the form:
    the low-rank pairs `w_fa`, `w_fb` and `w_ga`, `w_gb`, or a column a
    head `w_a` and a full-rank `w_g`."""
    L, D, d_in, d_key, r = n_layers, hidden_dim, kda.d_inner, kda.d_key, kda.gate_rank
    ks = jax.random.split(key, 13)
    dt = jnp.exp(jax.random.uniform(ks[0], (L, kda.d_decay), jnp.float32)
                 * (math.log(kda.dt_max) - math.log(kda.dt_min))
                 + math.log(kda.dt_min))
    dt = jnp.maximum(dt, kda.dt_floor)
    taps = lambda k, width: dense_fn(k, (L, kda.conv_kernel, width),
                                     1.0 / math.sqrt(kda.conv_kernel))
    if kda.decay_input == "lowrank":
        decay_in = {"w_fa": dense_fn(ks[7], (L, D, r)), "w_fb": dense_fn(ks[8], (L, r, d_in))}
    else:
        decay_in = {"w_a": dense_fn(ks[7], (L, D, kda.n_heads))}
    if r is None:
        gate = {"w_g": dense_fn(ks[10], (L, D, d_in))}
    else:
        gate = {"w_ga": dense_fn(ks[10], (L, D, r)), "w_gb": dense_fn(ks[11], (L, r, d_in))}
    return {
        "wq": dense_fn(ks[1], (L, D, d_key)),
        "wk": dense_fn(ks[2], (L, D, d_key)),
        "wv": dense_fn(ks[3], (L, D, d_in)),
        # [taps, channels]: the last tap multiplies the position itself
        "conv_q": taps(ks[4], d_key), "conv_k": taps(ks[5], d_key),
        "conv_v": taps(ks[6], d_in),
        **decay_in,
        "w_b": dense_fn(ks[9], (L, D, kda.n_heads)),
        **gate,
        "A_log": jnp.log(jax.random.uniform(
            jax.random.fold_in(ks[0], 1), (L, kda.n_heads), jnp.float32, 1.0, 16.0)
        ).astype(pdt),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "o_norm": jnp.ones((L, kda.value_dim), pdt),
        "wo": dense_fn(ks[12], (L, d_in, D)),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse_unit_lower(a, doubling=False):
    """(I + a)^-1 for strictly lower triangular `a` [..., C, C] float32:
    (I - a)(I + a^2)(I + a^4)..., exact once the power reaches C. Its
    backward rule is the inverse's own, `da = -Y^T dY Y^T`: two products
    where the squarings' transposes are twenty.

    `doubling`: by blocks on the diagonal that double instead, as many
    products (2 log2 C - 2): `X = I - a` is exact on blocks of 2, and with X
    the inverse over blocks of s and `off` what `a` holds inside blocks of 2 s
    and outside those of s, `X - X off X` is the inverse over blocks of 2 s
    (`(off X)^2 = 0`: `off` maps a block's first half to its second). No power
    of `a` is ever formed. The squarings hold A^32, whose entries reach 1e10
    where a chunk's keys lie close together and beta nears 2 (they cancel in
    the product, and float32 cannot follow: an error of 1e-5 on independent
    keys, of 1e+3 on keys half alike, where doubling reads 2e-7 on both;
    PERF.md section 6, PR 60); at beta <= 1 on a seeded model's keys they
    hold, and the two forms that run so keep them (their programs as built)."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    if doubling:
        at = jnp.arange(C)
        same = lambda s: (at[:, None] // s) == (at[None, :] // s)
        inv, s = eye - jnp.where(same(2), a, 0.0), 2
        while s < C:
            off = jnp.where(same(2 * s) & ~same(s), a, 0.0)
            inv = inv - _mm32(_mm32(inv, off), inv)
            s *= 2
        return inv
    inv, power, n = eye - a, a, 2
    while n < C:
        power = _mm32(power, power)
        inv = _mm32(inv, eye + power)
        n *= 2
    return inv


def _inverse_fwd(a, doubling):
    y = _inverse_unit_lower(a, doubling)
    return y, y


def _inverse_bwd(doubling, y, dy):
    yt = jnp.swapaxes(y, -1, -2)
    return (-_mm32(_mm32(yt, dy), yt),)


_inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


@jax.checkpoint
def _diagonal_blocks(q, k, G, same):
    """The diagonal sub-blocks cell by cell: q, k, G [..., n, SUB, K]
    float32, same [..., n, SUB, SUB] (i, j of one sequence, j <= i) ->
    sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d]) [..., n, SUB, SUB] for x = k
    and for x = q, the one exponential under both. (Under a checkpoint:
    its backward recomputes the [SUB, SUB, K] terms, which are the largest
    tensor of the rule by K / 4, and keeps none.)"""
    diff = G[..., :, None, :] - G[..., None, :, :]
    ke = k[..., None, :, :] * jnp.exp(jnp.where(same[..., None], diff, -jnp.inf))
    return (jnp.sum(k[..., :, None, :] * ke, axis=-1),
            jnp.sum(q[..., :, None, :] * ke, axis=-1))


def unit(x):
    """x a head over its norm, float32: `x * rsqrt(sum x^2 + L2_EPS)`."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def decay(f, A, dt_bias, seg):
    """The log-decay: f [N, C, H, K] (a channel: the low-rank product) or
    [N, C, H] (a head: the projection's column), A [H] float32
    (`-exp(A_log)`), dt_bias [H, K] or [H] float32, seg [N, C] ->
    `g = A softplus(f + dt_bias)` float32 <= 0 in f's shape, 0 at padding."""
    lift = (...,) + (None,) * (f.ndim - 3)  # a head's number over its channels, if any
    g = A[lift] * jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
    return jnp.where((seg > 0)[lift + (None,)], g, 0.0)


def intra(q, k, v, g, b, seg, before, cdt, doubling=False):
    """What of a chunk does not depend on the state it receives, by the
    decay's rank: `g` [N, C, H, K] a channel (`_intra_channel`) or [N, C,
    H] a head (`_intra_head`). Both return, heads first and masks folded
    in: Wm [N, H, C, K], U [N, H, C, V], Qg [N, H, C, K], Kd [N, H, C, K],
    Pm [N, H, C, C] in `cdt`, dec [N, H, K] float32 (a head's decay over
    the chunk stands K times there, a chunk's and not a cell's: the walk
    then serves both). `doubling`: how a decay a head inverts `I + A`
    (`_inverse_unit_lower`)."""
    if g.ndim == 3:
        return _intra_head(q, k, v, g, b, seg, before, cdt, doubling)
    return _intra_channel(q, k, v, g, b, seg, before, cdt)


def _intra_head(q, k, v, g, b, seg, before, cdt, doubling=False):
    """One decay a value head: q, k [N, C, Hk, K] (Hk key heads, value head
    j reads key head `j // (H / Hk)`: never repeated, the products a key
    head's and the `[C, C]` matrices spread over its value heads), v [N, C,
    H, V], g, b [N, C, H] float32. `D[i, j] = exp(G_i - G_j)` is one number
    a pair of positions, at most 1 for j <= i, so `A = b (.) tril(K K^T,
    -1) (.) D` and `P = tril(Q K^T) (.) D` are one product a key head and
    one exponential a value head: no sub-blocks."""
    f32 = jnp.float32
    N, C, Hk, K = q.shape
    H = v.shape[2]
    heads_first = lambda a: jnp.moveaxis(a, 2, 1)  # [N, C, H, ..] -> [N, H, C, ..]
    q, k, v, g, b = (heads_first(a) for a in (q, k, v, g, b))
    qf, kf = unit(q) * K ** -0.5, unit(k)
    # a key head's array under each of its value heads: a broadcast, whose
    # transpose sums the value heads' cotangents
    per_v = lambda a: jnp.broadcast_to(
        a[:, :, None], (N, Hk, H // Hk) + a.shape[2:]).reshape((N, H) + a.shape[2:])
    G = jnp.cumsum(g, axis=2)  # [N, H, C] <= 0, falling

    same = seg[:, :, None] == seg[:, None, :]  # [N, i, j]
    seen = (same & jnp.tril(jnp.ones((C, C), bool)))[:, None]  # [N, 1, i, j]
    D = jnp.exp(jnp.where(seen, G[..., :, None] - G[..., None, :], -jnp.inf))  # [N, H, i, j]
    kc = kf.astype(cdt)
    pairs = lambda x: per_v(jnp.einsum("ngik,ngjk->ngij", x.astype(cdt), kc,
                                       preferred_element_type=f32)) * D
    P = pairs(qf)
    A = jnp.where(jnp.eye(C, dtype=bool), 0.0, pairs(kf)) * b[..., None]  # row i by b_i
    T = _inverse_unit_lower(A, doubling) * b[:, :, None, :]  # (I + A)^-1 Diag(b)
    Tc = T.astype(cdt)
    eG = jnp.exp(G)[..., None]  # [N, H, C, 1]
    kv_, qv_ = per_v(kf), per_v(qf)
    W = jnp.einsum("nhij,nhjk->nhik", Tc, (kv_ * eG).astype(cdt), preferred_element_type=f32)
    U = jnp.einsum("nhij,nhjv->nhiv", Tc, v.astype(cdt), preferred_element_type=f32)

    last = seg[:, -1]
    cross = ((seg == before[:, None]) & (seg > 0))[:, None, :, None]  # [N, 1, C, 1]
    to_end = (seg == last[:, None])[:, None, :, None]
    carry = ((last == before) & (last > 0))[:, None, None]  # [N, 1, 1]
    G_end = G[:, :, -1:]  # [N, H, 1]
    Wm = jnp.where(cross, W, 0.0).astype(cdt)
    Qg = jnp.where(cross, qv_ * eG, 0.0).astype(cdt)
    Kd = jnp.where(to_end, kv_ * jnp.exp(G_end - G)[..., None], 0.0).astype(cdt)
    dec = jnp.broadcast_to(jnp.where(carry, jnp.exp(G_end), 0.0), (N, H, K))
    return Wm, U.astype(cdt), Qg, Kd, P.astype(cdt), dec


def _intra_channel(q, k, v, g, b, seg, before, cdt):
    """A decay a channel. q, k
    [N, C, H, K] (as their convolutions left them: made unit a head, and q
    scaled by K^-0.5, here, in float32, a group of chunks at a time and
    not a row) and v [N, C, H, V] (N chunks of C cells), g [N, C, H, K]
    float32, b [N, C, H] float32, seg [N, C], before [N] (the sequence
    the chunk before handed on, 0 = none) -> heads first, masks folded in:
    Wm [N, H, C, K], U [N, H, C, V], Qg [N, H, C, K], Kd [N, H, C, K],
    Pm [N, H, C, C] in `cdt`, dec [N, H, K] float32."""
    f32 = jnp.float32
    N, C, H, K = q.shape
    n = C // SUB
    heads_first = lambda a: jnp.moveaxis(a, 2, 1)  # [N, C, H, ..] -> [N, H, C, ..]
    q, k, v, g = (heads_first(a) for a in (q, k, v, g))
    qf, kf = unit(q) * K ** -0.5, unit(k)
    b = jnp.moveaxis(b, 2, 1)  # [N, H, C]
    G = jnp.cumsum(g, axis=2)  # [N, H, C, K] <= 0, falling

    same = seg[:, :, None] == seg[:, None, :]  # [N, i, j]
    causal = jnp.tril(jnp.ones((C, C), bool))
    # off-diagonal sub-blocks: relative to the later sub-block's first cell
    blocks = lambda a: a.reshape(a.shape[:2] + (n, SUB) + a.shape[3:])
    Gb = blocks(G)  # [N, H, n, SUB, K]
    Gr = Gb[:, :, :, :1]  # [N, H, n, 1, K] the running sum at a sub-block's first cell
    rows = jnp.exp(Gb - Gr)  # <= 1: a cell against its own sub-block's first
    # every cell before sub-block I against I's first cell, 0 from there on
    earlier = (jnp.arange(C)[None, :] < (jnp.arange(n) * SUB)[:, None])  # [n, C]
    cols = jnp.where(earlier[..., None], jnp.exp(jnp.minimum(
        Gr - G[:, :, None], 0.0)), 0.0)  # [N, H, n, C, K]
    k_cols = (kf[:, :, None] * cols).astype(cdt)
    off = lambda x: jnp.einsum(
        "nhbik,nhbjk->nhbij", (blocks(x) * rows).astype(cdt), k_cols,
        preferred_element_type=f32).reshape(N, H, C, C)
    # diagonal sub-blocks: cell by cell
    seen = same & causal  # [N, i, j]
    same_b = jnp.moveaxis(jnp.diagonal(
        seen.reshape(N, n, SUB, n, SUB), axis1=1, axis2=3), -1, 1)[:, None]
    on_k, on_q = (jnp.einsum("nhbij,bc->nhbicj", d, jnp.eye(n, dtype=f32)).reshape(N, H, C, C)
                  for d in _diagonal_blocks(blocks(qf), blocks(kf), Gb, same_b))

    seen = seen[:, None]  # [N, 1, i, j]
    kk = jnp.where(seen, off(kf) + on_k, 0.0)
    P = jnp.where(seen, off(qf) + on_q, 0.0)
    A = jnp.where(jnp.eye(C, dtype=bool), 0.0, kk) * b[..., None]  # row i by b_i
    T = _inverse_unit_lower(A) * b[:, :, None, :]  # (I + A)^-1 Diag(b)
    Tc = T.astype(cdt)
    eG = jnp.exp(G)
    W = jnp.einsum("nhij,nhjk->nhik", Tc, (kf * eG).astype(cdt),
                   preferred_element_type=f32)
    U = jnp.einsum("nhij,nhjv->nhiv", Tc, v.astype(cdt), preferred_element_type=f32)

    last = seg[:, -1]
    cross = ((seg == before[:, None]) & (seg > 0))[:, None, :, None]  # [N, 1, C, 1]
    to_end = (seg == last[:, None])[:, None, :, None]
    carry = ((last == before) & (last > 0))[:, None, None]  # [N, 1, 1]
    G_end = G[:, :, -1:]  # [N, H, 1, K]
    Wm = jnp.where(cross, W, 0.0).astype(cdt)
    Qg = jnp.where(cross, qf * eG, 0.0).astype(cdt)
    Kd = jnp.where(to_end, kf * jnp.exp(G_end - G), 0.0).astype(cdt)
    dec = jnp.where(carry, jnp.exp(G_end[:, :, 0]), 0.0)
    return Wm, U.astype(cdt), Qg, Kd, P.astype(cdt), dec


def states_scan(Wm, U, Qg, Kd, Pm, dec, S_in):
    """The walk over a row's chunks, plain: each [R, N, H, ...] as `intra`
    makes them, `S_in` [R, H, V, K] float32 the state the first of them
    receives (held transposed, as the kernels hold it) -> O [R, N, H, C,
    V] and the state every chunk received [R, N, H, V, K] in the operands'
    dtype, and the state the last hands on, float32."""
    f32 = jnp.float32
    cdt = Wm.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    def step(S, x):
        wm, u, qg, kd, pm, de = x
        Sc = S.astype(cdt)
        vc = (u.astype(f32) - mm("rhck,rhvk->rhcv", wm, Sc)).astype(cdt)
        o = mm("rhck,rhvk->rhcv", qg, Sc) + mm("rhij,rhjv->rhiv", pm, vc)
        return de[..., None, :] * S + mm("rhcv,rhck->rhvk", vc, kd), (o.astype(cdt), Sc)

    by_chunk = lambda a: jnp.moveaxis(a, 1, 0)
    S_out, (O, S_all) = jax.lax.scan(
        step, S_in, tuple(by_chunk(a) for a in (Wm, U, Qg, Kd, Pm, dec)))
    return jnp.moveaxis(O, 0, 1), jnp.moveaxis(S_all, 0, 1), S_out


def states_scan_bwd(Wm, U, Qg, Kd, Pm, dec, S_all, dO, dS_in):
    """`states_scan`'s transpose, the chunks walked backwards with the
    state's cotangent carried from `dS_in` (that of the state the last
    chunk hands on): -> the cotangents of Wm, U, Qg, Kd, Pm (in their
    dtype) and dec, and that of the state the first chunk received."""
    f32 = jnp.float32
    cdt = Wm.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    def step(dS, x):
        wm, u, qg, kd, pm, de, Sc, doc = x
        S, dSc = Sc.astype(f32), dS.astype(cdt)
        vn = (u.astype(f32) - mm("rhck,rhvk->rhcv", wm, Sc)).astype(cdt)
        dvn = mm("rhij,rhiv->rhjv", pm, doc) + mm("rhck,rhvk->rhcv", kd, dSc)
        dvc = dvn.astype(cdt)
        outs = (-mm("rhcv,rhvk->rhck", dvc, Sc), dvn, mm("rhcv,rhvk->rhck", doc, Sc),
                mm("rhcv,rhvk->rhck", vn, dSc), mm("rhiv,rhjv->rhij", doc, vn))
        dS_new = (mm("rhcv,rhck->rhvk", doc, qg) + de[..., None, :] * dS
                  - mm("rhcv,rhck->rhvk", dvc, wm))
        return dS_new, tuple(a.astype(cdt) for a in outs) + (jnp.sum(dS * S, axis=-2),)

    by_chunk = lambda a: jnp.moveaxis(a, 1, 0)
    dS_out, outs = jax.lax.scan(
        step, dS_in, tuple(by_chunk(a) for a in (Wm, U, Qg, Kd, Pm, dec, S_all, dO)),
        reverse=True)
    return tuple(jnp.moveaxis(a, 0, 1) for a in outs) + (dS_out,)


def _group(R: int, N: int, C: int, cells: int) -> int:
    """Chunks of every row the rule takes at a time: the largest divisor
    of N whose cells, over the R rows, are at most `cells`."""
    g = max(1, min(N, cells // (R * C)))
    while N % g:
        g -= 1
    return g


def _live_chunks(seg, C: int):
    """[R] the chunks of each row up to its last token's (0 = an empty row)."""
    T = seg.shape[1]
    last = jnp.max(jnp.where(seg > 0, jnp.arange(1, T + 1), 0), axis=1)
    return (last + C - 1) // C


class _Groups:
    """A call's rows cut into groups of chunks: `args` [R, N, ...] a group
    `i` of every row at a time (`take`), and a result put back (`put`)."""

    def __init__(self, segment_ids, C: int, cells: int):
        R, T = segment_ids.shape
        self.R, self.N, self.C = R, T // C, C
        self.gs = _group(R, self.N, C, cells)
        self.seg = segment_ids.reshape(R, self.N, C)
        # the sequence the chunk before each handed on (0 for a row's first)
        self.before = jnp.pad(self.seg[:, :, -1], ((0, 0), (1, 0)))[:, :-1]
        self.n_live = _live_chunks(segment_ids, C)
        # groups up to the one that holds the fullest row's last token
        self.live = (jnp.max(self.n_live) + self.gs - 1) // self.gs

    def chunked(self, a):
        return a.reshape((self.R, self.N, self.C) + a.shape[2:])

    def take(self, a, i):
        return jax.lax.dynamic_slice_in_dim(a, i * self.gs, self.gs, axis=1)

    def put(self, buf, a, i):
        return jax.lax.dynamic_update_slice_in_dim(buf, a.astype(buf.dtype), i * self.gs, axis=1)

    def intra(self, cdt, i, doubling=False):
        """`decay` and `intra` of group i's chunks, as a function of (q, k,
        v, f, b) `[R, gs, C, ...]`, A and dt_bias -> parts `[R, gs, H,
        ...]`: the float32 decays are a group's, never a row's."""
        R, gs = self.R, self.gs
        flat = lambda a: a.reshape((R * gs,) + a.shape[2:])
        seg, before = flat(self.take(self.seg, i)), flat(self.take(self.before, i))

        def fn(q, k, v, f, b, A, dt_bias):
            g = decay(flat(f), A, dt_bias, seg)
            parts = intra(flat(q), flat(k), flat(v), g, flat(b), seg, before, cdt, doubling)
            return tuple(a.reshape((R, gs) + a.shape[1:]) for a in parts)

        return fn


def delta_rule(q, k, v, f, b, A, dt_bias, segment_ids, chunk: int, kernel,
               form: RuleForm = RuleForm()):
    """The recurrence over packed rows, in chunks, of `unit(q) K^-0.5` and
    `unit(k)` under the decay `exp(A softplus(f + dt_bias))`: q, k [R, T,
    Hk, K] (Hk key heads that divide the H value heads), v [R, T, H, V], f
    [R, T, H, K] with dt_bias [H, K] (a decay a channel; Hk = H) or f [R,
    T, H] with dt_bias [H] (a decay a head: its rank is what tells the two
    rules apart, known when the program is traced), all 0 at padding; b
    [R, T, H] float32, 0 at padding; A [H] float32; segment_ids [R, T]; T
    a multiple of `chunk` -> o [R, T, H, V] in q's dtype. `kernel`: the
    forward by `ops/pallas/kda_fwd.py`'s one kernel and the backward by
    `ops/pallas/kda_bwd.py`'s (True; "interpret": in interpret mode, a
    test's), or the plain form (False). `form`: `RuleForm`.

    Plain: a group of every row's chunks at a time (`_Groups`), up to the
    group of the fullest row's last token (a loop whose trip count is a
    value of the run): `intra` of the group, then the walk over its chunks
    from the state the group before handed on. What stands in memory at
    once is a group's; the forward rule keeps its inputs and the state
    each group received (the kernel writes the same), and the backward
    makes a group's `intra` and its chunks' states again before it walks
    them backwards (the kernel inside its grid, the plain form in a loop
    like the forward's). One function
    jitted at module level (as `ops/band_loop.stretch`): the layers of a
    stack that call it at one shape share a trace and a lowering of each
    loop."""
    if form.key_dim is not None and not kernel:
        raise ValueError("delta_rule: keys widened with zero lanes are the kernels' alone")
    return _rule_jit(q, k, v, f, b, A, dt_bias, segment_ids, chunk, kernel, GROUP_CELLS, form)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _rule(q, k, v, f, b, A, dt_bias, segment_ids, chunk, kernel, cells, form=RuleForm()):
    return _rule_fwd(q, k, v, f, b, A, dt_bias, segment_ids, chunk, kernel, cells, form)[0]


def _rule_fwd(q, k, v, f, b, A, dt_bias, segment_ids, chunk, kernel, cells, form):
    if not kernel:
        return _rule_fwd_groups(q, k, v, f, b, A, dt_bias, segment_ids, chunk, cells,
                                form.doubling)
    from areal_tpu.ops.pallas import kda_fwd

    R, T = segment_ids.shape
    res = (q, k, v, f, b, A, dt_bias, segment_ids)
    o, bounds = kda_fwd.rule_fwd(*res, _live_chunks(segment_ids, chunk), chunk,
                                 _group(R, T // chunk, chunk, cells),
                                 interpret=kernel == "interpret", form=form)
    return o, res + (bounds,)


def _rule_fwd_groups(q, k, v, f, b, A, dt_bias, segment_ids, chunk, cells, doubling=False):
    """The plain form's forward, a group of chunks at a time: `intra`, then
    `states_scan` over the group's chunks."""
    R, T, _, K = q.shape
    H, V, cdt = v.shape[2], v.shape[-1], q.dtype
    res = (q, k, v, f, b, A, dt_bias, segment_ids)
    gr = _Groups(segment_ids, chunk, cells)
    args = tuple(gr.chunked(a) for a in (q, k, v, f, b))

    def body(i, carry):
        S, O, bounds = carry
        with jax.named_scope("kda_intra"):
            parts = gr.intra(cdt, i, doubling)(*(gr.take(a, i) for a in args), A, dt_bias)
        with jax.named_scope("kda_states"):
            O_g, _, S_out = states_scan(*parts, S)
        return (S_out, gr.put(O, O_g, i),
                jax.lax.dynamic_update_slice_in_dim(bounds, S[None], i, axis=0))

    _, O, bounds = jax.lax.fori_loop(0, gr.live, body, (
        jnp.zeros((R, H, V, K), jnp.float32), jnp.zeros((R, gr.N, H, chunk, V), cdt),
        jnp.zeros((gr.N // gr.gs, R, H, V, K), jnp.float32)))
    o = jnp.moveaxis(O, 2, 3).reshape(R, T, H, V)  # [R, N, H, C, V] -> cells
    return o, res + (bounds,)


def _rule_bwd(chunk, kernel, cells, form, res, do):
    if kernel:
        from areal_tpu.ops.pallas import kda_bwd

        segment_ids, bounds = res[-2:]
        return kda_bwd.rule_bwd(*res[:-1], _live_chunks(segment_ids, chunk), bounds, do,
                                chunk, interpret=kernel == "interpret", form=form) + (None,)
    q, k, v, f, b, A, dt_bias, segment_ids, bounds = res
    R, T, _, K = q.shape
    H, V, cdt = v.shape[2], v.shape[-1], q.dtype
    gr = _Groups(segment_ids, chunk, cells)
    args = tuple(gr.chunked(a) for a in (q, k, v, f, b))
    dO = jnp.moveaxis(gr.chunked(do.astype(cdt)), 3, 2)  # [R, N, H, C, V]

    def body(j, carry):
        dS, grads, consts = carry
        i = gr.live - 1 - j
        with jax.named_scope("kda_intra"):
            parts, pull = jax.vjp(gr.intra(cdt, i, form.doubling),
                                  *(gr.take(a, i) for a in args), A, dt_bias)
        with jax.named_scope("kda_states"):
            S_in = jax.lax.dynamic_index_in_dim(bounds, i, 0, keepdims=False)
            _, S_all, _ = states_scan(*parts, S_in)
            *d_parts, dS = states_scan_bwd(*parts, S_all, gr.take(dO, i), dS)
        with jax.named_scope("kda_intra"):
            *got, dA, d_bias = pull(tuple(d.astype(p.dtype) for d, p in zip(d_parts, parts)))
        return (dS, tuple(gr.put(buf, a, i) for buf, a in zip(grads, got)),
                (consts[0] + dA, consts[1] + d_bias))

    _, grads, consts = jax.lax.fori_loop(0, gr.live, body, (
        jnp.zeros((R, H, V, K), jnp.float32), tuple(jnp.zeros_like(a) for a in args),
        (jnp.zeros_like(A), jnp.zeros_like(dt_bias))))
    return tuple(a.reshape((R, T) + a.shape[3:]) for a in grads) + consts + (None,)


_rule.defvjp(_rule_fwd, _rule_bwd)
_rule_jit = jax.jit(_rule, static_argnums=(8, 9, 10, 11))


def key_lanes(K: int) -> int:
    """The width a key head of K stands at in the kernels: K where it is whole
    lane tiles, the next whole tile where zeros up to it are at most a third
    more (96 -> 128), else K as it is (a toy head: no kernel on the chip)."""
    full = -(-K // LANES) * LANES
    return full if 4 * K >= 3 * full else K


def use_kernel(kda: KDAConfig, mesh) -> bool:
    """The kernels (the forward's one, the backward's one) on the
    chip, one device's rows, heads whose blocks a grid step are whole lane
    tiles (`kda_fwd.step_heads`, the keys at `key_lanes`); the plain form
    elsewhere (the CPU, a toy head, a mesh of several devices: a kernel is
    opaque to the partitioner)."""
    from areal_tpu.ops.pallas import kda_fwd

    return (jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1)
            and kda_fwd.step_heads(kda.n_heads, kda.key_heads, key_lanes(kda.head_dim),
                                   kda.value_dim)[1])


def taps_in_kernel(kda: KDAConfig, T: int, kernel) -> bool:
    """Whether a row of T cells takes the taps' kernels (`ops/pallas/
    kda_taps.py`) on its way into the rule: where the rule takes its own
    (`kernel`, as `delta_rule` reads it) and q's, k's and v's shapes fit, the
    keys at the width the kernels take them (`key_lanes`)."""
    from areal_tpu.ops.pallas import kda_taps

    return bool(kernel) and all(kda_taps.fits(T, w, kda.conv_kernel) for w in (
        kda.key_heads * key_lanes(kda.head_dim), kda.d_inner))


def kda_mixer(q, k, v, f, b, kp, kda: KDAConfig, segment_ids, cdt, mesh=None,
              kernel=None):
    """What of the mixer crosses tokens. q, k [R, T, Hk K], v [R, T, H V]
    (the three projections), f the decay's input ([R, T, H K] the low-rank
    product, or [R, T, H] the projection's column where the decay is a
    head's), b [R, T, H] (beta's projection), `kp` the layer's `conv_*`,
    `A_log`, `dt_bias` -> o [R, T, H, V] in `cdt`, before the head norm.
    `kernel` as `delta_rule` takes it (None: `use_kernel`); the taps take
    their kernels with the rule's, where the shapes fit (`taps_in_kernel`).
    Keys that are no whole lane tile go into the kernels widened with zero
    lanes (`key_lanes`; scope `kda_widen`), before the taps where those are
    kernels too (their weights widened alike), after them where not."""
    R, T, _ = q.shape
    H, Hk, K, V, C = kda.n_heads, kda.key_heads, kda.head_dim, kda.value_dim, kda.chunk_size
    f32 = jnp.float32
    valid = segment_ids > 0
    if kernel is None:
        kernel = use_kernel(kda, mesh)
    Kw = key_lanes(K) if kernel else K

    def widen(a):
        """Zero lanes after each key head's K of a's last axis, `Hk K` wide."""
        if Kw == K:
            return a
        with jax.named_scope("kda_widen"):
            heads = a.reshape(a.shape[:-1] + (Hk, K))
            return jnp.pad(heads, ((0, 0),) * (heads.ndim - 1) + ((0, Kw - K),)).reshape(
                a.shape[:-1] + (Hk * Kw,))
    # masked on the way in: whatever padding cells hold (the residual
    # stream carries them along) reaches neither a result nor a gradient
    masked = lambda *xs: tuple(jnp.where(valid[..., None], a, 0) for a in xs)
    f, b = masked(f, b)
    with jax.named_scope("kda_taps"):
        if taps_in_kernel(kda, T, kernel):  # q's, k's and v's mask is the kernels' own
            from areal_tpu.ops.pallas import kda_taps

            conv = lambda x, w, wide: kda_taps.taps(
                wide(x.astype(cdt)), wide(w.astype(cdt)), None, segment_ids,
                kernel == "interpret")
        else:
            q, k, v = masked(q, k, v)
            conv = lambda x, w, wide: wide(
                causal_conv(x.astype(cdt), w.astype(cdt), None, segment_ids))
        q, k, v = (conv(x, kp[n], wide).reshape(R, T, h, w) for x, n, h, w, wide in (
            (q, "conv_q", Hk, Kw, widen), (k, "conv_k", Hk, Kw, widen),
            (v, "conv_v", H, V, lambda a: a)))
    with jax.named_scope("kda_gate"):
        A = -jnp.exp(kp["A_log"].astype(f32))  # [H]
        dt_bias = kp["dt_bias"].astype(f32)
        f = f.astype(cdt)
        if kda.decay == "channel":
            dt_bias, f = dt_bias.reshape(H, K), f.reshape(R, T, H, K)
        beta = jax.nn.sigmoid(b.astype(f32))
        if kda.neg_eigval:  # in (0, 2): `I - beta k k^T` reflects past 1
            beta = kda.beta_scale * beta
        beta = jnp.where(valid[..., None], beta, 0.0)
    with jax.named_scope("kda_chunk"):
        pad = -T % C
        if pad:
            grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            q, k, v, f, beta, segment_ids = (
                grow(a) for a in (q, k, v, f, beta, segment_ids))
        o = delta_rule(q, k, v, f, beta, A, dt_bias, segment_ids, C, kernel,
                       RuleForm(K if Kw != K else None, kda.neg_eigval))
    return o[:, :T]


def chunk_counts(segment_ids: np.ndarray, chunk: int):
    """What `delta_rule` does with packed rows, counted on the host by its
    own rule; `segment_ids` [R, T] of one call or [n, R, T] of several:
    (the positions it walks: the chunks it runs times their length; the
    chunks it runs: every row's, a group at a time up to the group of the
    fullest row's last token, `_Groups`; those that hold a token; sequence
    starts)."""
    seg = np.asarray(segment_ids)
    seg = seg.reshape((-1,) + seg.shape[-2:])
    pad = -seg.shape[-1] % chunk
    seg = np.pad(seg, ((0, 0), (0, 0), (0, pad)))
    start = (seg != np.pad(seg, ((0, 0), (0, 0), (1, 0)))[..., :-1]) & (seg > 0)
    n_calls, R, T = seg.shape
    N = T // chunk
    live = (seg.reshape(n_calls, R, N, chunk) > 0).any(-1)  # [n, R, N]
    last = np.where(live.any(-1), N - np.argmax(live[..., ::-1], axis=-1), 0).max(-1)
    gs = _group(R, N, chunk, GROUP_CELLS)
    run = R * int((-(-last // gs) * gs).sum())
    return run * chunk, run, int(live.sum()), int(start.sum())
