"""The TPU-native parallel transformer (counterpart of ReaLModel).

Reference equivalent: realhf/impl/model/nn/real_llm_api.py (ReaLModel) and
real_llm_base.py (blocks) — redesigned for XLA rather than translated:

- **Stacked layer parameters + `lax.scan`**: all transformer layers live in
  one pytree with a leading layer axis, and the forward pass scans over it.
  One layer gets traced/compiled regardless of depth, and XLA pipelines
  HBM weight streaming across layers.
- **Packed rows**: a batch is [R, T] token streams; each row packs several
  variable-length sequences tagged by segment ids (0 = padding). No pad
  waste beyond the row tail, matching the reference's packed varlen
  flash-attn layout, but with static shapes for jit.
- **Sharding by annotation**: there are no TP/SP modules. Params carry
  `PartitionSpec`s (areal_tpu/parallel/sharding.py) and GSPMD inserts the
  megatron-equivalent collectives.
- Mixed precision: params in fp32 (or bf16), compute in bf16, logits and
  softmax in fp32.

The KV-cache decode path lives in areal_tpu/models/generation.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.ops.attention import packed_attention, reference_packed_attention
from areal_tpu.ops.norms import layer_norm, rms_norm
from areal_tpu.ops.rotary import apply_rotary, rotary_cos_sin, rotary_inv_freq
# qmat == `h @ w.astype(cdt)` for plain weights; the serving decode path
# may pass (int8, scale) pairs instead (ops/wquant.py W8A16).
from areal_tpu.ops.wquant import qmat

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Params:
    """Random-init parameter pytree with stacked layers."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, F, V, L = cfg.hidden_dim, cfg.intermediate_dim, cfg.vocab_size, cfg.n_layers
    keys = jax.random.split(rng, 16)

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else (1.0 / math.sqrt(shape[-2]))
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(pdt)

    attn: Dict[str, Any] = {
        "wq": dense(keys[0], (L, D, cfg.q_dim)),
        "wk": dense(keys[1], (L, D, cfg.kv_dim)),
        "wv": dense(keys[2], (L, D, cfg.kv_dim)),
        "wo": dense(keys[3], (L, cfg.q_dim, D)),
    }
    if cfg.attn_bias:
        attn["bq"] = jnp.zeros((L, cfg.q_dim), pdt)
        attn["bk"] = jnp.zeros((L, cfg.kv_dim), pdt)
        attn["bv"] = jnp.zeros((L, cfg.kv_dim), pdt)
    if cfg.attn_out_bias:
        attn["bo"] = jnp.zeros((L, D), pdt)
    if cfg.qk_norm:
        attn["q_norm"] = jnp.ones((L, cfg.head_dim), pdt)
        attn["k_norm"] = jnp.ones((L, cfg.head_dim), pdt)

    if cfg.moe is not None:
        from areal_tpu.models.moe import init_moe_params

        if cfg.moe.first_k_dense:
            raise NotImplementedError(
                "first_k_dense breaks the homogeneous layer scan; "
                "interleaved dense layers are not supported yet"
            )
        mlp = init_moe_params(cfg, dense, jax.random.split(keys[4], 4))
    elif cfg.mlp_type == "gated":
        mlp = {
            "w_gate": dense(keys[4], (L, D, F)),
            "w_up": dense(keys[5], (L, D, F)),
            "w_down": dense(keys[6], (L, F, D)),
        }
    else:
        mlp = {
            "w_in": dense(keys[4], (L, D, F)),
            "w_out": dense(keys[6], (L, F, D)),
        }
    if cfg.mlp_bias and cfg.moe is None:
        if cfg.mlp_type == "gated":
            mlp["b_gate"] = jnp.zeros((L, F), pdt)
            mlp["b_up"] = jnp.zeros((L, F), pdt)
            mlp["b_down"] = jnp.zeros((L, D), pdt)
        else:
            mlp["b_in"] = jnp.zeros((L, F), pdt)
            mlp["b_out"] = jnp.zeros((L, D), pdt)

    layers = {
        "ln1": {"weight": jnp.ones((L, D), pdt)},
        "ln2": {"weight": jnp.ones((L, D), pdt)},
        "attn": attn,
        "mlp": mlp,
    }
    if cfg.norm_type == "layer":
        layers["ln1"]["bias"] = jnp.zeros((L, D), pdt)
        layers["ln2"]["bias"] = jnp.zeros((L, D), pdt)

    params: Params = {
        "embedding": {"weight": dense(keys[7], (V, D), scale=0.02)},
        "layers": layers,
        "final_norm": {"weight": jnp.ones((D,), pdt)},
    }
    if cfg.pos_emb == "learned":
        params["pos_embedding"] = {
            "weight": dense(keys[9], (cfg.max_position_embeddings, D), scale=0.02)
        }
    if cfg.norm_type == "layer":
        params["final_norm"]["bias"] = jnp.zeros((D,), pdt)
    if cfg.is_critic:
        params["head"] = {"weight": dense(keys[8], (D, 1), scale=0.02)}
    elif not cfg.tied_embeddings:
        params["head"] = {"weight": dense(keys[8], (D, V), scale=0.02)}
    return params


def count_params(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _norm(x, p, cfg):
    if cfg.norm_type == "rms":
        return rms_norm(x, p["weight"], cfg.norm_eps)
    return layer_norm(x, p["weight"], p.get("bias"), cfg.norm_eps)


def _mlp(h, lp, cfg, cdt):
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    if cfg.mlp_type == "gated":
        g = qmat(h, lp["w_gate"], cdt)
        u = qmat(h, lp["w_up"], cdt)
        if "b_gate" in lp:
            g = g + lp["b_gate"].astype(cdt)
            u = u + lp["b_up"].astype(cdt)
        out = qmat(act(g) * u, lp["w_down"], cdt)
        if "b_down" in lp:
            out = out + lp["b_down"].astype(cdt)
    else:
        u = qmat(h, lp["w_in"], cdt)
        if "b_in" in lp:
            u = u + lp["b_in"].astype(cdt)
        out = qmat(act(u), lp["w_out"], cdt)
        if "b_out" in lp:
            out = out + lp["b_out"].astype(cdt)
    return out


def _attention_block(
    x, lp, cfg, cos, sin, segment_ids, positions, attn_impl, cdt, mesh=None
):
    """x: [R, T, D] -> attention output [R, T, D]. Named scopes say in
    the device trace which part an op belongs to: `attn_qkv`
    (projections, rotary; the caller's input norm too), `attn_kernel`
    (the attention call), `attn_out`."""
    from areal_tpu.ops.attention import (
        resolve_attn_impl,
        sharded_splash_attention,
    )

    R, T, D = x.shape
    with jax.named_scope("attn_qkv"):
        q = x @ lp["wq"].astype(cdt)
        k = x @ lp["wk"].astype(cdt)
        v = x @ lp["wv"].astype(cdt)
        if "bq" in lp:
            q = q + lp["bq"].astype(cdt)
            k = k + lp["bk"].astype(cdt)
            v = v + lp["bv"].astype(cdt)
        q = q.reshape(R, T, cfg.n_q_heads, cfg.head_dim)
        k = k.reshape(R, T, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(R, T, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cos is not None:  # rotary position encoding (None = learned pos emb)
            q = apply_rotary(q, cos, sin, cfg.rotary_interleaved)
            k = apply_rotary(k, cos, sin, cfg.rotary_interleaved)

    # Resolution is mesh-aware: a seq>1 mesh picks a CP scheme for
    # 'auto' (Ulysses when heads divide the seq axis, ring otherwise)
    # before the local-kernel choice, and a kernel with no shard_map
    # layout for this mesh becomes the reference.
    impl = resolve_attn_impl(
        attn_impl, T, cfg.n_q_heads, cfg.n_kv_heads, mesh=mesh, r=R
    )
    sharded = mesh is not None and mesh.size > 1
    with jax.named_scope("attn_kernel"):
        if impl == "ring":
            # Context parallelism: KV chunks ring-rotate over the seq axis
            # (O(T/seq) per-device attention memory — the long-context path).
            from areal_tpu.ops.ring_attention import ring_ok, ring_packed_attention

            if not (sharded and ring_ok(mesh, R, T, cfg.n_q_heads, cfg.n_kv_heads)):
                raise ValueError(
                    "attn_impl='ring' needs a mesh with seq > 1 and divisible "
                    f"shapes (R={R}, T={T}, Hq={cfg.n_q_heads}, "
                    f"Hkv={cfg.n_kv_heads}, mesh={dict(mesh.shape) if mesh else None})"
                )
            out = ring_packed_attention(q, k, v, segment_ids, positions, mesh)
        elif impl == "ulysses":
            # Context parallelism via all-to-alls (seq shard swaps onto
            # heads; 4 a2a + 2 small gathers per layer vs ring's S ppermute
            # steps) with a splash local kernel on TPU; pick ring vs ulysses
            # by measurement per context length (ops/ulysses_attention.py).
            from areal_tpu.ops.ulysses_attention import (
                ulysses_ok,
                ulysses_packed_attention,
            )

            if not (
                sharded and ulysses_ok(mesh, R, T, cfg.n_q_heads, cfg.n_kv_heads)
            ):
                raise ValueError(
                    "attn_impl='ulysses' needs a mesh with seq > 1 and head "
                    f"counts divisible by seq*tensor (R={R}, T={T}, "
                    f"Hq={cfg.n_q_heads}, Hkv={cfg.n_kv_heads}, "
                    f"mesh={dict(mesh.shape) if mesh else None})"
                )
            out = ulysses_packed_attention(q, k, v, segment_ids, positions, mesh)
        elif sharded and impl == "splash":
            # pallas_call is opaque to GSPMD: run the kernel per shard under
            # shard_map with the megatron-equivalent layout.
            out = sharded_splash_attention(
                q, k, v, segment_ids, positions, mesh
            )  # [R, T, Hq, hd]
        else:
            attn_fn = lambda q1, k1, v1, s1, p1: packed_attention(
                q1, k1, v1, s1, p1, impl=impl
            )
            out = jax.vmap(attn_fn)(q, k, v, segment_ids, positions)
    with jax.named_scope("attn_out"):
        out = out.reshape(R, T, cfg.q_dim) @ lp["wo"].astype(cdt)
        if "bo" in lp:
            out = out + lp["bo"].astype(cdt)
    return out, (k, v)


def forward(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jnp.ndarray,  # [R, T] int32
    segment_ids: jnp.ndarray,  # [R, T] int32, 0 = padding
    positions: jnp.ndarray,  # [R, T] int32
    attn_impl: str = "auto",
    output: str = "logits",  # logits | hidden
    return_kv: bool = False,
    return_aux: bool = False,  # also return MoE aux losses (zeros if dense)
    remat: Any = False,  # False/"none" | True/"full" | "save_attn" | "mlp"
    mesh=None,  # jax.sharding.Mesh: anchor activation/logits shardings
) -> Any:
    """Packed-rows forward pass.

    Returns logits [R, T, V] (fp32), critic values [R, T] when
    cfg.is_critic, or hidden states; optionally also per-layer (k, v)
    stacked as [L, R, T, Hkv, hd] for generation prefill.

    When `mesh` is given, activations are pinned to
    P((data, fsdp), seq, None) and logits to P((data, fsdp), seq, tensor)
    between layers (the megatron-SP/CP activation layout,
    areal_tpu/parallel/sharding.py) so GSPMD keeps a consistent layout
    instead of re-deriving one per op.
    """
    if mesh is not None:
        from areal_tpu.parallel.sharding import (
            activation_constraint,
            logits_constraint,
        )

        act_c = lambda h: activation_constraint(h, mesh)
        log_c = lambda h: logits_constraint(h, mesh)
    else:
        act_c = log_c = lambda h: h

    cdt = jnp.dtype(cfg.compute_dtype)
    emb = params["embedding"]["weight"]
    if mesh is not None:
        # ZeRO-style gather-before-use: the table is stored (vocab ->
        # tensor, D -> fsdp)-sharded, but a token gather from a sharded
        # table cannot transition to the (data,fsdp)-row activation layout
        # — the SPMD partitioner falls back to "involuntary full
        # rematerialization" (replicating the gather OUTPUT per step).
        # All-gathering the table first is one clean collective and makes
        # the gather fully local.
        emb = jax.lax.with_sharding_constraint(
            emb, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        )
    with jax.named_scope("embed"):
        x = act_c(emb[input_ids].astype(cdt))
        if cfg.embedding_multiplier:
            x = x * jnp.asarray(cfg.embedding_multiplier, cdt)
        if cfg.pos_emb == "learned":
            x = x + params["pos_embedding"]["weight"][positions].astype(cdt)

    if cfg.pos_emb == "learned":
        cos = sin = None
    else:
        inv_freq = jnp.asarray(
            rotary_inv_freq(
                cfg.head_dim, cfg.rotary_base, cfg.rotary_scaling,
                cfg.rotary_scaling_type, cfg.rotary_scaling_params,
            )
        )
        cos, sin = rotary_cos_sin(positions, inv_freq)  # [R, T, hd/2]

    use_moe = cfg.moe is not None
    # remat policy: "full" recomputes the whole layer in backward (least
    # memory, ~+33% FLOPs); "save_attn" is "full" but pins the attention
    # kernel's residuals (q/k/v/out/lse) so the backward runs the flash
    # bwd kernel without re-running the fwd kernel — the fwd kernel is
    # the most expensive single op in the layer; "mlp" recomputes only
    # the MLP block; "none" saves everything (fastest when HBM allows).
    remat_mode = {True: "full", False: "none"}.get(remat, remat)
    if remat_mode not in ("full", "save_attn", "mlp", "none"):
        raise ValueError(f"unknown remat mode {remat!r}")
    if remat_mode == "save_attn":
        from areal_tpu.ops.attention import resolve_attn_impl

        resolved = resolve_attn_impl(
            attn_impl, input_ids.shape[1], cfg.n_q_heads, cfg.n_kv_heads,
            mesh=mesh, r=input_ids.shape[0],
        )
        if resolved != "splash":
            # Only the splash kernel tags its residuals; with other impls
            # the policy saves nothing and "save_attn" would silently be
            # "full" — make that explicit.
            import warnings

            warnings.warn(
                f"remat='save_attn' requires the splash attention impl "
                f"(resolved {resolved!r}); falling back to remat='full'",
                stacklevel=2,
            )
            remat_mode = "full"
    if use_moe:
        from areal_tpu.models.moe import moe_mlp

        moe_token_mask = segment_ids > 0  # real-token drop accounting
        # mesh enables the expert-parallel dropless path (moe.py
        # _moe_mlp_ep) when the fsdp axis divides num_experts.
        mlp_fn = lambda h, mp: moe_mlp(
            h, mp, cfg, cdt, token_mask=moe_token_mask, mesh=mesh
        )
    else:
        mlp_fn = lambda h, mp: _mlp(h, mp, cfg, cdt)
    if remat_mode == "mlp":
        mlp_fn = jax.checkpoint(mlp_fn)

    def layer_body(carry, lp):
        x, aux_acc = carry
        with jax.named_scope("attn_qkv"):
            h = _norm(x, lp["ln1"], cfg)
        a, kv = _attention_block(
            h, lp["attn"], cfg, cos, sin,
            segment_ids, positions, attn_impl, cdt, mesh=mesh,
        )
        with jax.named_scope("attn_out"):
            x = x + a
        with jax.named_scope("mlp"):
            h = _norm(x, lp["ln2"], cfg)
            if use_moe:
                m, aux = mlp_fn(h, lp["mlp"])
                aux_acc = {k: aux_acc[k] + aux[k] for k in aux_acc}
            else:
                m = mlp_fn(h, lp["mlp"])
            x = act_c(x + m)
        return (x, aux_acc), kv if return_kv else None

    aux0 = {
        "load_balance_loss": jnp.zeros((), jnp.float32),
        "z_loss": jnp.zeros((), jnp.float32),
        "drop_rate": jnp.zeros((), jnp.float32),  # summed; /n_layers = mean
        # Router telemetry (summed over layers like drop_rate):
        # per-expert routing-fraction histogram, router entropy, and
        # EP-exchange bytes per device (0 off expert-parallel meshes).
        "router_entropy": jnp.zeros((), jnp.float32),
        "expert_load": jnp.zeros(
            (cfg.moe.num_experts if use_moe else 1,), jnp.float32
        ),
        "a2a_bytes": jnp.zeros((), jnp.float32),
    }
    if remat_mode == "full":
        body = jax.checkpoint(layer_body)
    elif remat_mode == "save_attn":
        from areal_tpu.ops.attention import SPLASH_RESIDUAL_NAME

        body = jax.checkpoint(
            layer_body,
            policy=jax.checkpoint_policies.save_only_these_names(
                SPLASH_RESIDUAL_NAME
            ),
        )
    else:
        body = layer_body
    (x, moe_aux), kvs = jax.lax.scan(body, (x, aux0), params["layers"])
    with jax.named_scope("final_norm"):
        x = _norm(x, params["final_norm"], cfg)

    if output == "hidden":
        out = x
    else:
        if cfg.is_critic:
            head = params["head"]["weight"].astype(cdt)
            out = (x @ head).astype(jnp.float32)[..., 0]  # [R, T]
        else:
            head_w = (
                params["embedding"]["weight"].T
                if cfg.tied_embeddings
                else params["head"]["weight"]
            )
            out = log_c((x @ head_w.astype(cdt)).astype(jnp.float32))  # [R, T, V]
    if return_kv and return_aux:
        return out, kvs, moe_aux
    if return_kv:
        return out, kvs
    if return_aux:
        return out, moe_aux
    return out
