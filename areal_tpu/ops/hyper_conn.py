"""The coefficients of manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606): what a
sublayer reads its input from a token's `n` residual streams by, and
writes its output back by (`models/config.HyperConnConfig` has the
equations; `models/transformer._hc_read` / `_hc_write` the two mixes,
`ops/pallas/stream_mix.py` their kernels).

All of it is token-wise and small: one statistic and one `[n D] x [n D,
n^2 + 2 n]` product over the streams, then `n^2 + 2 n` floats a token,
in float32. The Sinkhorn iterations are plain `jnp` under autodiff off
the chip and one kernel each way on it (`ops/pallas/sinkhorn.py`: forty
reductions and forty divisions are as many small programs a call as
`jnp`, in every band of every sublayer).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import HyperConnConfig
from areal_tpu.ops.pallas import sinkhorn as sinkhorn_kernel


def sinkhorn(m: jnp.ndarray, iters: int, eps: float,
             kernel: Optional[bool] = None) -> jnp.ndarray:
    """`m` `[.., n, n]` positive -> rows then columns normalised, `iters`
    times, `eps` in every denominator: doubly stochastic in the limit.
    `kernel`: None = the kernels on the chip where the shape allows,
    False = this plain form."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel and m.dtype == jnp.float32 and sinkhorn_kernel.kernel_ok(math.prod(m.shape[:-2])):
        return sinkhorn_kernel.sinkhorn(m, iters, eps)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(hp: Dict[str, jnp.ndarray], x: jnp.ndarray, hy: HyperConnConfig,
                 norm_eps: float, kernel: Optional[bool] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A sublayer's (H_pre `[.., n]`, H_post `[.., n]`, H_res `[.., n,
    n]`), float32, of the streams `x` `[.., n D]` (a token's `vec(X)`, a
    stream after the other) under its parameters `hp`: `phi` `[n D, n^2 +
    2 n]`, `b` `[n^2 + 2 n]`, `a` `[3]` (pre, post, res). The RMSNorm of
    `x~` has no weight of its own (it folds into `phi`), so the norm is a
    scalar a token and multiplies the product, not its operand."""
    f32, n = jnp.float32, hy.n
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(f32)), axis=-1, keepdims=True) + norm_eps)
    m = r * jnp.dot(x, hp["phi"].astype(x.dtype), preferred_element_type=f32)
    a, b = hp["a"].astype(f32), hp["b"].astype(f32)
    h_pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
    m_res = jnp.exp(jnp.clip(a[2] * m[..., 2 * n:] + b[2 * n:], *hy.clamp))
    h_res = sinkhorn(m_res.reshape(m_res.shape[:-1] + (n, n)), hy.sinkhorn_iters, hy.eps,
                     kernel)
    return h_pre, h_post, h_res


def res_err(h_res: jnp.ndarray) -> jnp.ndarray:
    """`[..]`: the largest distance of a row or column sum of `h_res`
    `[.., n, n]` from 1: 0 for a doubly stochastic matrix, what Sinkhorn
    left undone otherwise."""
    rows = jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)
    cols = jnp.abs(jnp.sum(h_res, axis=-2) - 1.0)
    return jnp.max(jnp.maximum(rows, cols), axis=-1)
