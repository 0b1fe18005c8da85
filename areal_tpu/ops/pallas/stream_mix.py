"""A token's residual streams mixed by a small matrix of its own (Pallas,
TPU): the two passes over `[T, n D]` that hyper-connections add to a
sublayer (`models/config.HyperConnConfig`, `ops/hyper_conn.py`).

Both ways a sublayer touches the streams are one operation,

    out[t, i, :] = sum_k A[t, i, k] in[t, k, :]          (`mhc_mix`)

the read with `A = H_pre` (1 x n over the streams), the write with `A =
[H_res | H_post^T]` (n x (n + 1) over the streams and the sublayer's
output), and each one's backward to its input the same under `A^T`; and
its gradient to the coefficients,

    dA[t, i, k] = sum_d dout[t, i, d] in[t, k, d]         (`mhc_coef_grad`)

A token's streams lie one after the other in a row of `n D` (`vec(X)`),
so a stream of a tile of tokens is a lane-aligned slab `[tokens, D]`,
tokens on sublanes: a coefficient is a column that multiplies a slab.
`in` and `out` may each be several arrays whose streams follow one
another (`[X; y]` is never joined in memory). A kernel walks tiles of
`TOKENS` tokens, forms its float32 products in VMEM and moves every
input and output once through HBM; no product meets the MXU (the
matrices differ by token), and the work is bound by those bytes.

Off the chip, and for shapes the kernels do not take, the plain forms:
an einsum over the streams in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128
TOKENS = 64  # tokens a step: 9 slabs of [64, 3584] bf16 in and out are 4.1 MB, twice in VMEM


def _tile(dtype) -> int:
    """Tokens a step: `TOKENS` for 2-byte streams, half for float32 (an
    engine run in float32: the same bytes a step)."""
    return TOKENS * 2 // max(jnp.dtype(dtype).itemsize, 2)


def kernel_ok(n_tok: int, d: int, dtype=jnp.bfloat16) -> bool:
    """Shapes the kernels take: whole tiles of tokens, streams of whole
    lane tiles."""
    return n_tok % _tile(dtype) == 0 and d % _LANES == 0


def _use_kernel(kernel: Optional[bool], n_tok: int, d: int, dtype) -> bool:
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return bool(kernel) and kernel_ok(n_tok, d, dtype)


def _streams(arrays: Sequence[jnp.ndarray], d: int) -> jnp.ndarray:
    """`[.., k_j d]` arrays -> their streams as one float32 `[.., K, d]`."""
    lead = arrays[0].shape[:-1]
    return jnp.concatenate(
        [a.reshape(lead + (-1, d)) for a in arrays], axis=-2).astype(jnp.float32)


def _slabs(refs, d: int):
    """The `[tokens, d]` float32 slab of every stream of the refs, in order."""
    return [r[:, s * d:(s + 1) * d].astype(jnp.float32)
            for r in refs for s in range(r.shape[-1] // d)]


def _column(a, j: int):
    """Column j of the tile's coefficients `a` `[tokens, I K]` as `[tokens,
    1]`: a select and a lane sum, which Mosaic has for any j."""
    at = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.sum(jnp.where(at == j, a, 0.0), axis=-1, keepdims=True)


def _mix_kernel(a_ref, *refs, n_in: int, d: int):
    ins, outs = _slabs(refs[:n_in], d), refs[n_in:]
    a, k_all, i = a_ref[...], len(ins), 0
    for o_ref in outs:
        for s in range(o_ref.shape[-1] // d):
            acc = _column(a, i * k_all) * ins[0]
            for k in range(1, k_all):
                acc = acc + _column(a, i * k_all + k) * ins[k]
            o_ref[:, s * d:(s + 1) * d] = acc.astype(o_ref.dtype)
            i += 1


def _coef_kernel(*refs, n_out: int, d: int):
    douts, ins, o_ref = _slabs(refs[:n_out], d), _slabs(refs[n_out:-1], d), refs[-1]
    at = lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for i, dout in enumerate(douts):
        for k, x in enumerate(ins):
            acc = jnp.where(at == i * len(ins) + k,
                            jnp.sum(dout * x, axis=-1, keepdims=True), acc)
    o_ref[...] = acc


def _tiles(arrays, tokens: int):
    return [pl.BlockSpec((tokens, a.shape[-1]), lambda t: (t, 0)) for a in arrays]


def _mix(a, ins: Tuple[jnp.ndarray, ...], out_sizes: Tuple[int, ...],
         kernel: Optional[bool], interpret: bool = False) -> Tuple[jnp.ndarray, ...]:
    """`a` `[.., I, K]` float32 over the streams of `ins` (`[.., k_j D]`
    each, `sum k_j = K`) -> arrays of `out_sizes` streams (`sum = I`),
    `[.., i_j D]` in the inputs' dtype. Device op `mhc_mix`."""
    lead, (n_i, n_k) = a.shape[:-2], a.shape[-2:]
    d = sum(x.shape[-1] for x in ins) // n_k
    n_tok = math.prod(lead)
    if not (interpret or _use_kernel(kernel, n_tok, d, ins[0].dtype)):
        out = jnp.einsum("...ik,...kd->...id", a, _streams(ins, d)).astype(ins[0].dtype)
        cuts, at = [], 0
        for n in out_sizes:
            cuts.append(out[..., at:at + n, :].reshape(lead + (n * d,)))
            at += n
        return tuple(cuts)
    flat = [x.reshape(n_tok, x.shape[-1]) for x in (a.reshape(lead + (n_i * n_k,)),) + tuple(ins)]
    tokens = _tile(ins[0].dtype)
    with jax.named_scope("mhc_mix"):
        outs = pl.pallas_call(
            functools.partial(_mix_kernel, n_in=len(ins), d=d),
            grid=(n_tok // tokens,),
            in_specs=_tiles(flat, tokens),
            out_specs=[pl.BlockSpec((tokens, n * d), lambda t: (t, 0)) for n in out_sizes],
            out_shape=[jax.ShapeDtypeStruct((n_tok, n * d), ins[0].dtype) for n in out_sizes],
            name="mhc_mix", interpret=interpret,
        )(*flat)
    return tuple(o.reshape(lead + o.shape[-1:]) for o in outs)


def mhc_coef_grad(douts: Sequence[jnp.ndarray], ins: Sequence[jnp.ndarray], d: int,
                  kernel: Optional[bool] = None, interpret: bool = False) -> jnp.ndarray:
    """`[.., I, K]` float32: stream i of `douts` (`[.., i_j d]` each)
    against stream k of `ins` (`[.., k_j d]` each), summed over the `d`
    features of a token. Device op `mhc_coef_grad`."""
    lead = ins[0].shape[:-1]
    n_i = sum(x.shape[-1] for x in douts) // d
    n_k = sum(x.shape[-1] for x in ins) // d
    n_tok = math.prod(lead)
    if not (interpret or _use_kernel(kernel, n_tok, d, ins[0].dtype)):
        return jnp.einsum("...id,...kd->...ik", _streams(douts, d), _streams(ins, d))
    flat = [x.reshape(n_tok, x.shape[-1]) for x in tuple(douts) + tuple(ins)]
    tokens = _tile(ins[0].dtype)
    with jax.named_scope("mhc_coef_grad"):
        out = pl.pallas_call(
            functools.partial(_coef_kernel, n_out=len(douts), d=d),
            grid=(n_tok // tokens,),
            in_specs=_tiles(flat, tokens),
            out_specs=pl.BlockSpec((tokens, n_i * n_k), lambda t: (t, 0)),
            out_shape=jax.ShapeDtypeStruct((n_tok, n_i * n_k), jnp.float32),
            name="mhc_coef_grad", interpret=interpret,
        )(*flat)
    return out.reshape(lead + (n_i, n_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def mhc_mix(a, ins: Tuple[jnp.ndarray, ...], kernel: Optional[bool] = None,
            interpret: bool = False) -> jnp.ndarray:
    """`out[.., i, :] = sum_k a[.., i, k] in[.., k, :]`: `a` `[.., I, K]`
    float32, `ins` a tuple of `[.., k_j D]` arrays whose streams follow
    one another (`sum k_j = K`); returns `[.., I D]` in their dtype, the
    products and sums in float32. `kernel`: None = the kernel on the chip
    where the shapes allow, False = the plain form (a mesh: a kernel is
    opaque to the partitioner). Differentiable in `a` and `ins`."""
    return _mix(a, tuple(ins), (a.shape[-2],), kernel, interpret)[0]


def _mhc_mix_fwd(a, ins, kernel, interpret):
    return mhc_mix(a, ins, kernel, interpret), (a, tuple(ins))


def _mhc_mix_bwd(kernel, interpret, res, dout):
    a, ins = res
    d = dout.shape[-1] // a.shape[-2]
    d_ins = _mix(jnp.swapaxes(a, -1, -2), (dout,), tuple(x.shape[-1] // d for x in ins),
                 kernel, interpret)
    return mhc_coef_grad((dout,), ins, d, kernel, interpret), d_ins


mhc_mix.defvjp(_mhc_mix_fwd, _mhc_mix_bwd)
