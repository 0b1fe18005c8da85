"""The delta rule's walk over a row's chunks (Pallas, TPU): the part of
`ops/kda.delta_rule` that carries a state from chunk to chunk, as the
backward loop runs it: a group's `intra` under `jax.vjp`, then
`kda_fwd_states` for the states its chunks received and `kda_bwd_states`
backwards. (The forward is one kernel that holds `intra` and this walk,
`ops/pallas/kda_fwd.py`; it shares `_heads` and the products here.)

`ops/kda.intra` makes, for every chunk of a group at once, what does not
depend on the state a chunk receives, masks folded in: `Wm`, `Qg`, `Kd`
`[C, K]`, `U` `[C, V]`, `Pm` `[C, C]` and `dec` `[K]` a head. With `S`
`[K, V]` the state a chunk receives, a chunk is

    Vn = U - Wm S;   O = Qg S + Pm Vn;   S' = Diag(dec) S + Kd^T Vn

`kda_fwd_states` walks the chunks of `HEADS` heads a grid step from the
state the group received, their states in VMEM (float32, held transposed,
`[V, K]`: the decay then scales lanes); it writes `O`, the state every
chunk received and the state the last hands on. `kda_bwd_states` walks
them backwards with the state's cotangent in VMEM and writes the cotangent
of every operand and of the state the group received:

    dVn = Pm^T dO + Kd dS';  dQg = dO S^T;  dPm = dO Vn^T;  dKd = Vn dS'^T
    ddec = rowsum(dS' * S);  dU = dVn;  dWm = -dVn S^T
    dS  = Qg^T dO + Diag(dec) dS' - Wm^T dVn

Both stop at the row's last live chunk (`n_live`, a value of the run, a
scalar the index maps read: a chunk past it fetches nothing new, computes
nothing and writes zeros). Products take operands in the arrays' dtype and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS = 4  # heads a grid step: independent chains that share a step's overhead


def _heads(H: int, most: int = 0) -> int:
    hb = min(H, most or HEADS)
    while H % hb:
        hb -= 1
    return hb


_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _mm(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(n_live_ref, wm_ref, u_ref, qg_ref, kd_ref, pm_ref, dec_ref, s_in_ref,
                o_ref, s_ref, s_out_ref, st):
    r, c = pl.program_id(0), pl.program_id(2)
    cdt = wm_ref.dtype

    @pl.when(c == 0)
    def _():
        st[...] = s_in_ref[...]

    @pl.when(c < n_live_ref[r])
    def _():
        for j in range(st.shape[0]):
            s_t = st[j]  # [V, K]: the state, transposed
            sc = s_t.astype(cdt)
            s_ref[j] = sc
            vc = (u_ref[j].astype(jnp.float32) - _mm(wm_ref[j], sc, _NT)).astype(cdt)
            o_ref[j] = (_mm(qg_ref[j], sc, _NT) + _mm(pm_ref[j], vc, _NN)).astype(o_ref.dtype)
            st[j] = dec_ref[j] * s_t + _mm(vc, kd_ref[j], _TN)

    @pl.when(c >= n_live_ref[r])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = st[...]


def _bwd_kernel(n_live_ref, wm_ref, u_ref, qg_ref, kd_ref, pm_ref, dec_ref, s_ref,
                do_ref, ds_in_ref, dwm_ref, du_ref, dqg_ref, dkd_ref, dpm_ref, ddec_ref,
                ds_out_ref, dst):
    r, c = pl.program_id(0), pl.num_programs(2) - 1 - pl.program_id(2)
    cdt = wm_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst[...] = ds_in_ref[...]

    @pl.when(c < n_live_ref[r])
    def _():
        for j in range(dst.shape[0]):
            sc, ds_t = s_ref[j], dst[j]  # [V, K]
            s_t, dsc, do = sc.astype(jnp.float32), ds_t.astype(cdt), do_ref[j]
            wm, qg, kd, pm = wm_ref[j], qg_ref[j], kd_ref[j], pm_ref[j]
            vn = (u_ref[j].astype(jnp.float32) - _mm(wm, sc, _NT)).astype(cdt)
            dvn = _mm(pm, do, _TN) + _mm(kd, dsc, _NT)
            dvc = dvn.astype(cdt)
            du_ref[j] = dvc
            dqg_ref[j] = _mm(do, sc, _NN).astype(cdt)
            dpm_ref[j] = _mm(do, vn, _NT).astype(cdt)
            dkd_ref[j] = _mm(vn, dsc, _NN).astype(cdt)
            dwm_ref[j] = (-_mm(dvc, sc, _NN)).astype(cdt)
            ddec_ref[j] = jnp.sum(ds_t * s_t, axis=0, keepdims=True)
            dst[j] = _mm(do, qg, _TN) + dec_ref[j] * ds_t - _mm(dvc, wm, _TN)

    @pl.when(c >= n_live_ref[r])
    def _():
        for ref in (dwm_ref, du_ref, dqg_ref, dkd_ref, dpm_ref, ddec_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(c == 0)
    def _():
        ds_out_ref[...] = dst[...]


def _specs(arrays, hb, at):
    """A block of `hb` heads of each array: of one chunk of a `[R, N, H,
    a, b]` array, the chunk `at(r, c, n_live)`; of a state `[R, H, V, K]`,
    the same block at every chunk."""
    by_chunk = lambda a: pl.BlockSpec(
        (None, None, hb) + a.shape[3:], lambda r, h, c, n: (r, at(r, c, n), h, 0, 0))
    state = lambda a: pl.BlockSpec((None, hb) + a.shape[2:], lambda r, h, c, n: (r, h, 0, 0))
    return [by_chunk(a) if len(a.shape) == 5 else state(a) for a in arrays]


def _call(kernel, name, ins, outs, n_live, backwards, interpret):
    R, N, H = ins[0].shape[:3]
    hb = _heads(H)
    chunk = (lambda c: N - 1 - c) if backwards else (lambda c: c)
    # a chunk past the last live one fetches the last live one's blocks again
    live = lambda r, c, n: jnp.minimum(chunk(c), jnp.maximum(n[r] - 1, 0))
    true = lambda r, c, n: chunk(c)
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R, H // hb, N),
                in_specs=_specs(ins, hb, live), out_specs=_specs(outs, hb, true),
                scratch_shapes=[pltpu.VMEM((hb,) + ins[-1].shape[2:], jnp.float32)]),
            out_shape=outs,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name=name, interpret=interpret,
        )(n_live.astype(jnp.int32), *ins)


def states_fwd(Wm, U, Qg, Kd, Pm, dec, S_in, n_live, interpret: bool = False):
    """Each `[R, N, H, ...]` as `ops/kda.intra` makes them, `S_in` `[R, H,
    V, K]` float32 the state the first chunk receives (transposed),
    `n_live` [R] the chunks of a row up to its last token's -> O `[R, N, H,
    C, V]` and the state every chunk received `[R, N, H, V, K]` (both in
    the operands' dtype: the products read the state in that dtype), and
    the state the last chunk hands on, float32. Device op
    `kda_fwd_states`."""
    R, N, H, C, K = Wm.shape
    V = U.shape[-1]
    outs = [jax.ShapeDtypeStruct((R, N, H, C, V), Wm.dtype),
            jax.ShapeDtypeStruct((R, N, H, V, K), Wm.dtype),
            jax.ShapeDtypeStruct((R, H, V, K), jnp.float32)]
    return tuple(_call(_fwd_kernel, "kda_fwd_states",
                       [Wm, U, Qg, Kd, Pm, dec[:, :, :, None, :], S_in], outs, n_live,
                       False, interpret))


def states_bwd(Wm, U, Qg, Kd, Pm, dec, S_all, dO, dS_in, n_live, interpret: bool = False):
    """`states_fwd`'s transpose, the chunks walked backwards from `dS_in`
    (the cotangent of the state the last chunk hands on): the cotangents
    of Wm, U, Qg, Kd, Pm (in their dtype), of dec (float32) and of the
    state the first chunk received. Device op `kda_bwd_states`."""
    R, N, H, C, K = Wm.shape
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    outs = [like(Wm), like(U), like(Qg), like(Kd), like(Pm),
            jax.ShapeDtypeStruct((R, N, H, 1, K), jnp.float32), like(dS_in)]
    *d, ddec, dS = _call(_bwd_kernel, "kda_bwd_states",
                         [Wm, U, Qg, Kd, Pm, dec[:, :, :, None, :], S_all, dO, dS_in], outs,
                         n_live, True, interpret)
    return (*d, ddec[:, :, :, 0, :], dS)
