"""The Sinkhorn iterations of hyper-connections as one kernel each way
(Pallas, TPU): `ops/hyper_conn.sinkhorn` on the chip.

Twenty iterations of a row and a column normalisation over a token's
`n x n` matrix are forty reductions over an axis of `n` and forty
divisions. As `jnp` under autodiff each is a small program of its own,
eighty a call forward and about two hundred backward, in every band of
every sublayer: at `xing4-d5e8-train-ppo-8k`'s shapes more than half of
a traced pass's 917,000 device events and a sixth of a stretch's ops
(PERF.md section 6, PR 47). Here a call is one kernel: the matrices lie
`[n, n, tokens]`, tokens on the lanes, a tile of `TOKENS` of them a step;
a row sum is a sum over the sublanes of a slab, a column sum adds the
slabs. The backward kernel makes the forty steps again from the input,
keeps each step's result and denominator in VMEM, and walks them back:
for `y = x / d`, `d = sum_a x + eps`, the cotangent is `(g - sum_a(g y))
/ d`. Float32 throughout; the same divisions in the same order as the
plain form, which the CPU, toy shapes and a mesh run.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TOKENS = 512  # lanes a step: forty kept steps of [n, n, 512] float32 are 5 MB of VMEM


def kernel_ok(n_tok: int) -> bool:
    return n_tok % TOKENS == 0


def _steps(x, iters: int, eps: float, keep: bool = False):
    """The `2 iters` normalisations of `x` `[n, n, tokens]` (rows: over
    axis 1; columns: over axis 0): the result, and with `keep` every
    step's (axis, result, denominator)."""
    kept = []
    for _ in range(iters):
        for axis in (1, 0):
            d = jnp.sum(x, axis=axis, keepdims=True) + eps
            x = x / d
            if keep:
                kept.append((axis, x, d))
    return x, kept


def _fwd_kernel(x_ref, o_ref, *, iters, eps):
    o_ref[...] = _steps(x_ref[...], iters, eps)[0]


def _bwd_kernel(x_ref, g_ref, o_ref, *, iters, eps):
    g = g_ref[...]
    for axis, y, d in reversed(_steps(x_ref[...], iters, eps, keep=True)[1]):
        g = (g - jnp.sum(g * y, axis=axis, keepdims=True)) / d
    o_ref[...] = g


def _call(kernel, name, iters, eps, interpret, *arrays):
    n, _, n_tok = arrays[0].shape
    tile = pl.BlockSpec((n, n, TOKENS), lambda t: (0, 0, t))
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(kernel, iters=iters, eps=eps),
            grid=(n_tok // TOKENS,), in_specs=[tile] * len(arrays), out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(arrays[0].shape, jnp.float32),
            name=name, interpret=interpret,
        )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def sinkhorn_tokens_last(x, iters: int, eps: float, interpret: bool = False):
    """`x` `[n, n, tokens]` float32 positive -> rows then columns
    normalised `iters` times. Device op `mhc_sinkhorn`; its backward
    `mhc_sinkhorn_bwd`."""
    return _call(_fwd_kernel, "mhc_sinkhorn", iters, eps, interpret, x)


def _fwd(x, iters, eps, interpret):
    return sinkhorn_tokens_last(x, iters, eps, interpret), x


def _bwd(iters, eps, interpret, x, g):
    return (_call(_bwd_kernel, "mhc_sinkhorn_bwd", iters, eps, interpret, x, g),)


sinkhorn_tokens_last.defvjp(_fwd, _bwd)


def sinkhorn(m, iters: int, eps: float, interpret: bool = False):
    """`m` `[.., n, n]` float32 with `kernel_ok(tokens)` -> the same, its
    matrices laid tokens-last for the kernels and back."""
    n = m.shape[-1]
    x = jnp.moveaxis(m.reshape(-1, n, n), 0, -1)
    y = sinkhorn_tokens_last(x, int(iters), float(eps), interpret)
    return jnp.moveaxis(y, -1, 0).reshape(m.shape)
