"""The TPU-native parallel transformer (counterpart of ReaLModel).

Reference equivalent: realhf/impl/model/nn/real_llm_api.py (ReaLModel) and
real_llm_base.py (blocks) — redesigned for XLA rather than translated:

- **Stacked layer parameters + `lax.scan`**: all transformer layers live in
  one pytree with a leading layer axis, and the forward pass scans over it.
  One layer gets traced/compiled regardless of depth, and XLA pipelines
  HBM weight streaming across layers.
- **Layers of several kinds in one stack** (`config.LayerKind`: the
  parts a layer has, each under its own norm with its own residual: a
  mixer, attention with a window or none and rotary or none,
  differential or latent (low-rank q and kv projections,
  `_latent_attention_block`) or neither, a state-space mixer,
  `ops/ssm.py` or `ops/selective_scan.py`, a delta-rule mixer,
  `ops/kda.py`, a gated short convolution (`ops/ssm.gated_conv_mixer`), or
  a gated memory unit; and
  an MLP, dense or expert; all static). A layer may keep a tensor (its
  scan's output, its
  k and v) that later layers read: it travels beside the residual
  stream, an input of each reader's checkpointed body and a constant of
  a scan over readers, so the backward pass holds it once. Layers with
  the same parts share a parameter stack
  (`config.stack_paths`: `layers` and, for leading blocks of another
  kind, `lead_layers`; `stacks/<parts>` for any other pattern), and the
  forward pass walks the pattern in segments (`config.segments_of`): a
  run of a repeated unit of one or more layers is one scan over the
  unit, whose stacks are cut from their kinds' by `lax.split`; a layer
  that repeats nothing runs as it is. Layers of a scan that differ only
  in their attention share one traced body: which (window, rotary table:
  none, the stack's one, or a named set's, `config.RotarySet`) each
  has is an index scanned beside its parameters that switches the
  attention call alone and picks the layer's table. What is traced
  grows with the runs of the
  pattern, not the depth; a stack of one kind is the plain scan.
- **Packed rows**: a batch is [R, T] token streams; each row packs several
  variable-length sequences tagged by segment ids (0 = padding). No pad
  waste beyond the row tail, matching the reference's packed varlen
  flash-attn layout, but with static shapes for jit.
- **Sharding by annotation**: there are no TP/SP modules. Params carry
  `PartitionSpec`s (areal_tpu/parallel/sharding.py) and GSPMD inserts the
  megatron-equivalent collectives.
- Mixed precision: params in fp32 (or bf16), compute in bf16, logits and
  softmax in fp32.

- **An indexer beside attention** (`config.IndexerConfig`, a layer whose
  kind is `indexed`): three projections of the layer's normed input
  under `stop_gradient` (`_index_proj`) score every key of a query's
  causal prefix, and attention reads the `top_k` best alone
  (`ops/indexer.py`); `forward(index_loss=True)` also sums the layers'
  KL terms into the aux sums, as the expert layers' are.

- **A prediction module after the stack** (`config.MTPConfig`):
  `forward(mtp=True)` also hands out the hidden states
  of one more block that reads the stack's output and the next token's
  embedding, for a loss over the token after that.

- **Several residual streams a token** (`config.HyperConnConfig`,
  manifold-constrained hyper-connections): the carry of the stack is a
  token's `n` streams one after the other, `[R, T, n D]` (`vec(X)` a
  row: a stream of a tile of tokens is then a lane-aligned slab, which
  `[.., n, D]` with `n` = 4 on the chip's sublanes is not). Every
  sublayer reads its input from them through `_hc_read` and writes its
  output back through `_hc_write`, both token-wise: they run inside the
  steps a row walks band by band and in the join after the routed
  experts. The stack starts from `n` copies of the embedding and ends in
  the sum of the streams. With `cfg.hyper` None both are the plain `x`
  and `x + y`.

The KV-cache decode path lives in areal_tpu/models/generation.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import LayerKind, TransformerConfig
from areal_tpu.models.moe import activation_fn
from areal_tpu.ops import band_loop, hyper_conn
from areal_tpu.ops.attention import packed_attention, reference_packed_attention
from areal_tpu.ops.norms import layer_norm, rms_norm
from areal_tpu.ops.pallas import stream_mix
from areal_tpu.ops.rotary import apply_rotary, rotary_cos_sin, rotary_inv_freq
# qmat == `h @ w.astype(cdt)` for plain weights; the serving decode path
# may pass (int8, scale) pairs instead (ops/wquant.py W8A16).
from areal_tpu.ops.wquant import qmat

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init_layer_stack(cfg: TransformerConfig, keys, n: int, kind: LayerKind,
                      dense) -> Dict[str, Any]:
    """`n` layers with the parts of `kind`, stacked on a leading axis:
    the mixer (`attn` or `ssm`) under `ln1`, the MLP under `ln2`."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, F, L = cfg.hidden_dim, cfg.intermediate_dim, n
    layers: Dict[str, Any] = {}
    norms = []
    if kind.mixer == "attention" and kind.latent:
        layers["attn"] = _init_latent_attention(cfg, keys, L, dense)
    elif kind.mixer == "attention":
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
        if kind.reads is not None:  # another layer's k and v
            for name in ("wk", "wv", "bk", "bv"):
                attn.pop(name, None)
        if kind.diff:
            for n, name in enumerate(DIFF_LAMBDAS):
                attn[name] = dense(jax.random.fold_in(keys[15], n),
                                   (L, cfg.head_dim), 0.1)
            attn["sub_norm"] = jnp.ones((L, 2 * cfg.head_dim), pdt)
        if cfg.attn_out_bias:
            attn["bo"] = jnp.zeros((L, D), pdt)
        if cfg.qk_norm and cfg.qk_norm_over == "width":
            # `_LATENT_Q_GAIN`'s reason: a softmax peaked as a trained model's
            attn["q_norm"] = jnp.ones((L, cfg.q_dim), pdt) * _LATENT_Q_GAIN
            attn["k_norm"] = jnp.ones((L, cfg.kv_dim), pdt)
        elif cfg.qk_norm:
            attn["q_norm"] = jnp.ones((L, cfg.head_dim), pdt)
            attn["k_norm"] = jnp.ones((L, cfg.head_dim), pdt)
        if cfg.attn_gate:
            attn["wg"] = dense(keys[10], (L, D, cfg.q_dim))
        if kind.indexed:
            ix = cfg.indexer
            k_iq, k_ik, k_iw = jax.random.split(jax.random.fold_in(keys[15], 32), 3)
            attn["indexer"] = {
                "iq_proj": dense(k_iq, (L, D, ix.n_heads * ix.head_dim)),
                "ik_proj": dense(k_ik, (L, D, ix.head_dim)),
                "iw_proj": dense(k_iw, (L, D, ix.n_heads)),
                "ik_norm": {"weight": jnp.ones((L, ix.head_dim), pdt),
                            "bias": jnp.zeros((L, ix.head_dim), pdt)},
            }
            if cfg.qk_norm:
                attn["q_norm"] = attn["q_norm"] * _INDEXED_Q_GAIN
        if cfg.qk_norm and (cfg.rotary_fraction != 1.0 or kind.rotary_set is not None
                            or cfg.n_conv_layers):
            # `_LATENT_Q_GAIN`'s reason: under unit scores a softmax over
            # thousands of keys is flat, and no check of logprobs would see
            # which of a head's columns were turned, nor by which table (nor,
            # among convolution mixers, the one attention layer of four at all)
            attn["q_norm"] = attn["q_norm"] * _LATENT_Q_GAIN
        layers["attn"] = attn
    elif kind.mixer == "ssm" and cfg.ssm.form == "mamba1":
        from areal_tpu.ops.selective_scan import init_sscan_params

        layers["ssm"] = init_sscan_params(cfg.ssm, D, dense, keys[13], L, pdt)
    elif kind.mixer == "ssm":
        from areal_tpu.ops.ssm import init_ssm_params

        layers["ssm"] = init_ssm_params(cfg.ssm, D, dense, keys[13], L, pdt)
    elif kind.mixer == "kda":
        from areal_tpu.ops.kda import init_kda_params

        layers["kda"] = init_kda_params(cfg.kda, D, dense, keys[13], L, pdt)
    elif kind.mixer == "conv":
        from areal_tpu.ops.ssm import init_conv_params

        layers["conv"] = init_conv_params(cfg.conv, D, dense, keys[13], L, pdt)
    elif kind.mixer == "gmu":
        k_in, k_out = jax.random.split(jax.random.fold_in(keys[15], 8))
        layers["gmu"] = {"w_in": dense(k_in, (L, D, cfg.ssm.d_inner)),
                         "w_out": dense(k_out, (L, cfg.ssm.d_inner, D))}
    if kind.mixer is not None:
        norms += ["ln1"] * cfg.pre_norms + ["ln1_post"] * cfg.post_norms

    if kind.mlp == "moe":
        from areal_tpu.models.moe import init_moe_params

        mlp = init_moe_params(
            cfg, dense, jax.random.split(keys[4], 4), L, shared_key=keys[11],
            bias_key=jax.random.fold_in(keys[15], 128) if cfg.n_conv_layers else None,
        )
    elif kind.mlp == "dense" and cfg.mlp_type == "gated":
        mlp = {
            "w_gate": dense(keys[4], (L, D, F)),
            "w_up": dense(keys[5], (L, D, F)),
            "w_down": dense(keys[6], (L, F, D)),
        }
    elif kind.mlp == "dense":
        mlp = {
            "w_in": dense(keys[4], (L, D, F)),
            "w_out": dense(keys[6], (L, F, D)),
        }
    if cfg.mlp_bias and kind.mlp == "dense":
        if cfg.mlp_type == "gated":
            mlp["b_gate"] = jnp.zeros((L, F), pdt)
            mlp["b_up"] = jnp.zeros((L, F), pdt)
            mlp["b_down"] = jnp.zeros((L, D), pdt)
        else:
            mlp["b_in"] = jnp.zeros((L, F), pdt)
            mlp["b_out"] = jnp.zeros((L, D), pdt)
    if kind.mlp is not None:
        layers["mlp"] = mlp
        norms += ["ln2"] * cfg.pre_norms + ["ln2_post"] * cfg.post_norms

    hy = cfg.hyper
    if hy is not None:  # a sublayer's hyper-connections: the mixer's, the MLP's
        for j, name in enumerate(("hc1", "hc2")):
            if (kind.mixer, kind.mlp)[j] is None:
                continue
            k_phi, k_b = jax.random.split(jax.random.fold_in(keys[15], 64 + j))
            layers[name] = {
                "phi": dense(k_phi, (L, hy.n * D, hy.n_coef)),
                "b": dense(k_b, (L, hy.n_coef), _HC_BIAS_SCALE),
                "a": jnp.full((L, 3), _HC_GATE, pdt),  # pre, post, res
            }

    for name in norms:
        layers[name] = {"weight": jnp.ones((L, D), pdt)}
        if cfg.norm_type == "layer":
            layers[name]["bias"] = jnp.zeros((L, D), pdt)
    return layers


# What the seeded draw of an indexed attention layer departs by from
# ones in its q norm (benchmark/configs/keye-vl-2.0-*.json `assumed`): a
# head's scores then have this standard deviation and its softmax is
# peaked, as a trained model's; at 1 a head is a near-uniform average
# over thousands of keys, and no check of logprobs would see which of
# them the indexer chose.
_INDEXED_Q_GAIN = 3.0
# And the embedding of a stack with such layers is drawn at this scale,
# not 0.02: a position's state is then its own token's first, as a
# trained model's. At 0.02 the residual stream is what attention wrote
# there, an average over the sequence's values that every later layer
# averages again: by the sixth layer half of a normed state's energy is
# its sequence's mean, a router sends a whole sequence the same way, and
# the (token, expert) pairs a share of the experts holds swing by a
# third from one sequence, seed or step to the next (PERF.md section 6,
# PR 42), a step's seconds with them. (Where the head is the embedding
# too, the usual draw stays: logits of that scale would be no model's.)
_INDEXED_EMBED_SCALE = 2.0

# The seeded draw of a sublayer's hyper-connections
# (benchmark/configs/xing4.0-*.json `assumed`): the paper starts the three
# gates at 0.01, which makes every coefficient a constant of the layer
# (sigmoid(b), Sinkhorn of exp(b)), and a check of logprobs would then
# see neither `phi` nor the token-dependent Sinkhorn. Here the gates
# start at 1, `phi` is drawn at 1 / sqrt(n D) (`dense`'s: `m` has unit
# variance a column) and `b` from N(0, 1): coefficients differ by token
# and H_res is a generic doubly stochastic matrix.
_HC_GATE = 1.0
_HC_BIAS_SCALE = 1.0
# A stack of several streams draws its embedding at `_INDEXED_EMBED_SCALE`
# too, for that constant's reason: the streams start as `n` copies of the
# embedding, and under unit scores (the YaRN draw below) attention writes
# a sequence's running mean over them. At 0.02 the routers then follow the
# sequence, not the token: the pairs that 8 of 64 experts hold swing from
# 8.7 to 12.3 % by seed and fall through a run as the router trains, and
# a step's seconds with them (PERF.md section 6, PR 47, third hand-in).

# What the seeded draw of a latent attention layer departs by from
# `dense`'s 1 / sqrt(fan-in), so that a check against a reference sees
# every part of the layer (benchmark/configs/joyai-llm-flash-*.json
# `assumed`): q is drawn `_LATENT_Q_GAIN` times as large, so a head's
# scores have that standard deviation and its softmax is peaked, as a
# trained model's, not the near-uniform average over a sequence that
# unit scores give (whose output is a hundredth of the MLP's beside it);
# the two down-projections' latent columns are drawn `_LATENT_DOWN_GAIN`
# times as large, so the RMSNorms inside the projections rescale what
# they are given instead of passing on a vector that is unit already.
# Where the rope part's table is scaled (YaRN: the softmax scale carries
# `mscale^2`, `MLAConfig.softmax_scale_factor`) q keeps `dense`'s own
# scale, over that factor, so that a head's scores have unit standard
# deviation: dividing the low frequencies by 64 moves the scores enough
# for a check to see the table without a peaked softmax (the unscaled
# table moves a sequence's mean logprob by 0.56-0.65), and bf16's rounding
# of peaked scores is most of what separates the program from its float32
# reference: 0.15 on that mean at a gain of 3, 0.027 at 1, where the
# subtlest control (one Sinkhorn iteration for twenty) reads 0.23 and 0.09
# (PERF.md section 6, PR 47).
_LATENT_Q_GAIN = 3.0
_LATENT_DOWN_GAIN = 0.25


def _init_latent_attention(cfg: TransformerConfig, keys, L: int, dense) -> Dict[str, Any]:
    """`L` latent attention layers (`config.MLAConfig`): the two
    down-projections (the kv one with the shared rope key beside its
    latent), the norms inside them, the two up-projections to heads, and
    the output projection from heads of `v_dim`; with `q_rank` None, q's
    one projection `wq` in place of its three leaves."""
    pdt = jnp.dtype(cfg.param_dtype)
    m, D, H = cfg.mla, cfg.hidden_dim, cfg.n_q_heads
    k_kvb, k_rope = jax.random.split(jax.random.fold_in(keys[15], 16))
    down = _LATENT_DOWN_GAIN / math.sqrt(D)
    q_gain = _LATENT_Q_GAIN if m.softmax_scale_factor == 1.0 else 1.0 / m.softmax_scale_factor
    if m.q_rank is None:  # a full-rank q: one product, no norm
        q = {"wq": dense(keys[1], (L, D, H * m.qk_dim), q_gain / math.sqrt(D))}
    else:
        q = {"wq_a": dense(keys[0], (L, D, m.q_rank), down),
             "q_a_norm": jnp.ones((L, m.q_rank), pdt),
             "wq_b": dense(keys[1], (L, m.q_rank, H * m.qk_dim),
                           q_gain / math.sqrt(m.q_rank))}
    return {
        **q,
        "wkv_a": jnp.concatenate(
            [dense(keys[2], (L, D, m.kv_rank), down),
             dense(k_rope, (L, D, m.rope_dim))], axis=-1),
        "kv_a_norm": jnp.ones((L, m.kv_rank), pdt),
        "wkv_b": dense(k_kvb, (L, m.kv_rank, H * (m.nope_dim + m.v_dim))),
        "wo": dense(keys[3], (L, H * m.v_dim, D)),
    }


# A differential attention layer's four vectors of head_dim:
# lambda = exp(q1 . k1) - exp(q2 . k2) + lambda_init(depth).
DIFF_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def diff_lambda_init(layer_idx):
    """0.8 - 0.6 exp(-0.3 depth): 0.2 at the first layer, towards 0.8."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Params:
    """Random-init parameter pytree with stacked layers, one stack a
    kind of layer (`cfg.stack_paths`): `layers`, and before it
    `lead_layers` where the first blocks have another MLP than the rest
    (leading dense layers of an expert model); `stacks/<parts>` for a
    pattern of kinds that is not that."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, V = cfg.hidden_dim, cfg.vocab_size
    keys = jax.random.split(rng, 16)

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else (1.0 / math.sqrt(shape[-2]))
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(pdt)

    kinds = cfg.kinds()
    params: Params = {
        "embedding": {"weight": dense(keys[7], (V, D), scale=(
            _INDEXED_EMBED_SCALE
            if (cfg.hyper is not None or cfg.rotary_sets is not None
                or any(k.indexed or k.mixer == "kda" for k in kinds))
            and not cfg.tied_embeddings else 0.02))},
        "final_norm": {"weight": jnp.ones((D,), pdt)},
    }
    for i, (path, idx) in enumerate(cfg.stack_paths().values()):
        if path == ("layers",):
            stack_keys = keys
        elif path == ("lead_layers",):
            stack_keys = jax.random.split(keys[12], 16)
        else:
            stack_keys = jax.random.split(jax.random.fold_in(keys[14], i), 16)
        _stack_at(params, path, _init_layer_stack(
            cfg, stack_keys, len(idx), kinds[idx[0]], dense))
    if cfg.mtp is not None:
        k_eh, k_block = jax.random.split(jax.random.fold_in(keys[14], 1 << 16))
        params["mtp"] = {
            "enorm": {"weight": jnp.ones((D,), pdt)},
            "hnorm": {"weight": jnp.ones((D,), pdt)},
            "eh_proj": {"weight": dense(k_eh, (2 * D, D))},
            # one layer of the stack's last kind, on a leading axis of 1
            "block": _init_layer_stack(
                cfg, jax.random.split(k_block, 16), 1, kinds[-1], dense),
            "norm": {"weight": jnp.ones((D,), pdt)},
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


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _norm(x, p, cfg):
    if p is None:  # a layer without this norm (`pre_norms` off): x as it is
        return x
    if cfg.norm_type == "rms":
        return rms_norm(x, p["weight"], cfg.norm_eps)
    return layer_norm(x, p["weight"], p.get("bias"), cfg.norm_eps)


def _stack_at(params, path, new=None):
    """The stack at `path` of the parameter tree (`cfg.stack_paths`);
    with `new`, put it there."""
    node = params
    for key in path[:-1]:
        node = node.setdefault(key, {}) if new is not None else node[key]
    if new is not None:
        node[path[-1]] = new
    return node[path[-1]]


def _mlp(h, lp, cfg, cdt):
    act = activation_fn(cfg.activation)
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


def _attention_kernel(q, k, v, segment_ids, positions, impl, cfg, mesh, window,
                      softmax_scale=None):
    """The attention call itself, by the resolved implementation:
    q [R, T, Hq, hd], k and v [R, T, Hkv, hd] -> [R, T, Hq, hd].
    `softmax_scale`: None = the head size's `hd^-0.5`."""
    from areal_tpu.ops.attention import (
        sharded_splash_attention,
        splash_packed_attention,
    )

    R, T = q.shape[:2]
    sharded = mesh is not None and mesh.size > 1
    if (window is not None or softmax_scale is not None) and impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attn_impl={impl!r} (context parallelism over the mesh's seq "
            "axis) has no window in its mask and no softmax scale but the head "
            "size's: ops/ring_attention.py and ops/ulysses_attention.py build a "
            "causal mask only"
        )
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
            q, k, v, segment_ids, positions, mesh, window=window,
            softmax_scale=softmax_scale,
        )  # [R, T, Hq, hd]
    elif impl == "splash":
        # Rows go in whole: the wrapper sees how many share the call.
        out = splash_packed_attention(
            q, k, v, segment_ids, positions, window=window, softmax_scale=softmax_scale)
    else:
        attn_fn = lambda q1, k1, v1, s1, p1: packed_attention(
            q1, k1, v1, s1, p1, softmax_scale=softmax_scale, impl=impl, window=window
        )
        out = jax.vmap(attn_fn)(q, k, v, segment_ids, positions)
    return out


def _diff_split(q, k, v):
    """Differential attention's heads, for one kernel call. The heads
    come in consecutive pairs: q1, q2 the even and odd q heads, k1, k2
    likewise, v the pairs joined to heads of twice the size. Returns q
    as [q1 heads | q2 heads], k as [k1 heads | k2 heads] and v twice:
    under the kernel's own grouping (q head h on kv head h // group) q1's
    pair p then meets k1 of kv pair p // group, and q2's the k2 of it."""
    R, T, hq, hd = q.shape
    hkv = k.shape[2]
    q = q.reshape(R, T, hq // 2, 2, hd)
    k = k.reshape(R, T, hkv // 2, 2, hd)
    v = v.reshape(R, T, hkv // 2, 2 * hd)
    return (jnp.concatenate([q[:, :, :, 0], q[:, :, :, 1]], axis=2),
            jnp.concatenate([k[:, :, :, 0], k[:, :, :, 1]], axis=2),
            jnp.concatenate([v, v], axis=2))


def _diff_combine(out, lp, l0, eps):
    """out [R, T, Hq, 2 hd], the q1 heads' softmax(q1 k1) v then the q2
    heads' softmax(q2 k2) v -> RMSNorm_2hd(A1 - lambda A2) * (1 - l0),
    [R, T, Hq / 2, 2 hd]; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0."""
    f32 = jnp.float32
    lq1, lk1, lq2, lk2 = (lp[name].astype(f32) for name in DIFF_LAMBDAS)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + l0
    half = out.shape[2] // 2
    a = out[:, :, :half].astype(f32) - lam * out[:, :, half:].astype(f32)
    a = rms_norm(a, lp["sub_norm"], eps) * (1.0 - l0)
    return a.astype(out.dtype)


class _Index(NamedTuple):
    """What `forward` hands an indexed layer: the indexer's rotary tables
    (`cos`, `sin` of its head size / 2), whether the KL is wanted, and
    where to leave each layer's (choice, tau) for a caller that asked
    (None: nowhere)."""
    cos: jnp.ndarray
    sin: jnp.ndarray
    want_kl: bool
    choices: Optional[list]


def _index_proj(x, ip, cfg, cos, sin, cdt):
    """The indexer's projections of the layer's normed input x `[R, T,
    D]`, which they do not move (`stop_gradient`): (iq `[R, T, H, d]`, ik
    `[R, T, d]` under its LayerNorm, iw `[R, T, H]` float32 with both
    scales folded in), rotary (`cos`, `sin` of `d / 2`) over the whole
    of iq's heads and of the one ik."""
    ix = cfg.indexer
    R, T, _ = x.shape
    with jax.named_scope("index_proj"):
        x = jax.lax.stop_gradient(x)
        iq = (x @ ip["iq_proj"].astype(cdt)).reshape(R, T, ix.n_heads, ix.head_dim)
        ik = layer_norm(x @ ip["ik_proj"].astype(cdt), ip["ik_norm"]["weight"],
                        ip["ik_norm"]["bias"], ix.norm_eps)
        iw = (x @ ip["iw_proj"].astype(cdt)).astype(jnp.float32) * ix.scale
        iq = apply_rotary(iq, cos, sin, cfg.rotary_interleaved)
        ik = apply_rotary(ik[:, :, None, :], cos, sin, cfg.rotary_interleaved)[:, :, 0]
    return iq, ik, iw


def _attn_in(x, lp, cfg, cdt, kv=None):
    """An attention layer's projections of its normed input x `[R, T, D]`
    (scope `attn_qkv`): q `[R, T, Hq, hd]`, k and v `[R, T, Hkv, hd]` (or
    `kv`, another layer's), q and k under their norms where the layer
    has them, the gate's projection (scope `attn_gate`; None without),
    and (k, v) as they were before the norm."""
    R, T, D = x.shape
    gate = None
    with jax.named_scope("attn_qkv"):
        q = x @ lp["wq"].astype(cdt)
        if kv is None:
            k = x @ lp["wk"].astype(cdt)
            v = x @ lp["wv"].astype(cdt)
        if "bq" in lp:
            q = q + lp["bq"].astype(cdt)
            if kv is None:
                k = k + lp["bk"].astype(cdt)
                v = v + lp["bv"].astype(cdt)
        over_width = cfg.qk_norm and cfg.qk_norm_over == "width"
        if over_width:  # one norm over the projected width, before the heads
            with jax.named_scope("attn_qk_wide_norm"):
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = q.reshape(R, T, cfg.n_q_heads, cfg.head_dim)
        if kv is None:
            k = k.reshape(R, T, cfg.n_kv_heads, cfg.head_dim)
            v = v.reshape(R, T, cfg.n_kv_heads, cfg.head_dim)
        else:
            k, v = kv
        own_kv = (k, v)
        if cfg.qk_norm and not over_width:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if "wg" in lp:
            with jax.named_scope("attn_gate"):
                gate = x @ lp["wg"].astype(cdt)
    return q, k, v, gate, own_kv


def _attn_out(out, gate, lp, cfg, cdt, l0=None):
    """The attention call's output `[R, T, Hq, hd]` -> the layer's `[R,
    T, D]`: differential attention's combine (`l0`), the gate, the output
    projection (scopes `attn_diff`, `attn_out` > `attn_gate`)."""
    if l0 is not None:
        with jax.named_scope("attn_diff"):
            out = _diff_combine(out, lp, l0, cfg.norm_eps)
    with jax.named_scope("attn_out"):
        out = out.reshape(out.shape[:2] + (cfg.q_dim,))
        if gate is not None:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(gate)
        out = out @ lp["wo"].astype(cdt)
        if "bo" in lp:
            out = out + lp["bo"].astype(cdt)
    return out


def _attn_core(q, k, v, cfg, cos, sin, segment_ids, positions, attn_impl, mesh,
               variants, variant_index, diff, index, ix, rotated=False):
    """What of an attention layer crosses tokens: rotary (unless q and k
    come `rotated`; `cos`, `sin`: the layer's own table, `layer_body`
    picks it) and the attention call of the (window, table)
    variant that runs, differential attention's split before it, the
    indexer's choice (`ix`: its three projections) inside it. Returns
    the call's output `[R, T, Hq, hd]`, k as attended, the layer's sums."""
    from areal_tpu.ops.attention import resolve_attn_impl

    R, T = q.shape[:2]
    # Resolution is mesh-aware: a seq>1 mesh picks a CP scheme for
    # 'auto' (Ulysses when heads divide the seq axis, ring otherwise)
    # before the local-kernel choice, and a kernel with no shard_map
    # layout for this mesh becomes the reference.
    impl = resolve_attn_impl(
        attn_impl, T, cfg.n_q_heads, cfg.n_kv_heads, mesh=mesh, r=R
    )

    sums = {}
    if index is not None:
        if impl in ("ring", "ulysses"):
            raise NotImplementedError(
                f"attn_impl={impl!r} (context parallelism over the mesh's seq "
                "axis) with an indexer: ops/ring_attention.py and "
                "ops/ulysses_attention.py hold a shard of a query's keys each "
                "and have no threshold over all of them, nor a mask operand")
        if mesh is not None and mesh.size > 1 and impl == "splash":
            impl = "reference"  # the plain form partitions; the kernels are one chip's
        iq, ik, iw = ix

    def attend(window, rotary):
        """q, k, v -> (attention output [R, T, Hq, hd], k as attended)."""

        def run(q, k, v):
            if rotary and cos is not None and not rotated:  # None = learned pos emb
                with jax.named_scope("attn_qkv"):
                    q = apply_rotary(q, cos, sin, cfg.rotary_interleaved)
                    k = apply_rotary(k, cos, sin, cfg.rotary_interleaved)
            if index is not None:
                from areal_tpu.ops.indexer import indexed_attention

                out, got = indexed_attention(
                    q, k, v, iq, ik, iw, segment_ids, positions,
                    cfg.indexer.top_k, impl, index.want_kl)
                choice = (got.pop("choice"), got.pop("tau"))
                if index.choices is not None:
                    index.choices.append(choice)
                sums.update(got)
                return out, k
            with jax.named_scope("attn_kernel"):
                return _attention_kernel(
                    q, k, v, segment_ids, positions, impl, cfg, mesh, window), k

        return run

    if diff:
        with jax.named_scope("attn_qkv"):
            q, k, v = _diff_split(q, k, v)
    if len(variants) == 1:
        out, k = attend(*variants[0])(q, k, v)
    else:
        out, k = jax.lax.switch(
            variant_index, [attend(*vt) for vt in variants], q, k, v)
    return out, k, sums


def _latent_in(x, lp, cfg, cos, sin, cdt, rotary=True):
    """Latent attention's q and k `[R, T, H, nope + rope]` and v `[R, T,
    H, v_dim]` of the layer's normed input x (scope `attn_qkv`). q through
    its low-rank pair, or through the one `wq` of a full-rank q
    (`MLAConfig.q_rank` None); the rope parts turned unless the kind has
    no `rotary`."""
    R, T, _ = x.shape
    m, H = cfg.mla, cfg.n_q_heads
    with jax.named_scope("attn_qkv"):
        with jax.named_scope("mla_q_proj"):
            if "wq" in lp:
                c_q, wq = x, lp["wq"]
            else:
                c_q, wq = rms_norm(x @ lp["wq_a"].astype(cdt), lp["q_a_norm"],
                                   cfg.norm_eps), lp["wq_b"]
            q = (c_q @ wq.astype(cdt)).reshape(R, T, H, m.qk_dim)
        with jax.named_scope("mla_kv_proj"):
            c_kv, k_r = jnp.split(x @ lp["wkv_a"].astype(cdt), [m.kv_rank], axis=-1)
            c_kv = rms_norm(c_kv, lp["kv_a_norm"], cfg.norm_eps)
            kv = (c_kv @ lp["wkv_b"].astype(cdt)).reshape(
                R, T, H, m.nope_dim + m.v_dim)
        q_nope, q_r = jnp.split(q, [m.nope_dim], axis=-1)
        k_nope, v = jnp.split(kv, [m.nope_dim], axis=-1)
        if rotary:
            q_r = apply_rotary(q_r, cos, sin, cfg.rotary_interleaved)
            k_r = apply_rotary(k_r[:, :, None, :], cos, sin, cfg.rotary_interleaved)
        else:  # its columns as they are: that many more of the one shared key
            k_r = k_r[:, :, None, :]
        q = jnp.concatenate([q_nope, q_r], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (R, T, H, m.rope_dim))], axis=-1)
    return q, k, v


def _latent_out(out, lp, cdt):
    """The attention call's output, sequence-minor as `_latent_core` hands
    it over, `[R, H, v_dim, T]`, through latent attention's output
    projection (scope `attn_out`) -> `[R, T, D]`."""
    _, H, V, _ = out.shape
    with jax.named_scope("attn_out"):
        return jnp.einsum("rhvt,hvd->rtd", out, lp["wo"].astype(cdt).reshape(H, V, -1))


def _latent_core(q, k, v, cfg, segment_ids, positions, attn_impl, mesh):
    """Latent attention's kernel call (scope `attn_kernel`): every head
    has its own k and v; the softmax scale is `MLAConfig.softmax_scale`
    (the head size's times YaRN's `mscale^2` where the table is scaled).
    Returns the output sequence-minor, `[R, H, v_dim, T]`: where the pair
    kernels read and write in place (`ops/attention._rows_in_place`: a
    long row alone) that is the kernel's own output and this
    transposition undoes the call's, so that the output reaches the
    output projection's stretch, and its cotangent the kernel, as they
    lie."""
    from areal_tpu.ops.attention import resolve_attn_impl

    H = cfg.n_q_heads
    impl = resolve_attn_impl(attn_impl, q.shape[1], H, H, mesh=mesh, r=q.shape[0])
    with jax.named_scope("attn_kernel"):
        return _attention_kernel(q, k, v, segment_ids, positions, impl, cfg, mesh, None,
                                 cfg.mla.softmax_scale).transpose(0, 2, 3, 1)


class _Stretch(NamedTuple):
    """What a layer's token-wise steps do not trace, hashable (it keys
    the trace of a stretch, `ops/band_loop.stretch`): the configuration
    (by identity), the layer's kind, the compute dtype, and where the row
    walks its bands, which ops stand inside a step that the whole row has
    elsewhere."""
    cfg: TransformerConfig
    kind: LayerKind
    cdt: Any
    # Rotary turns q and k inside the first step, where every layer that
    # shares the body has it: in the attention call's switch (the whole row's
    # place, `_attn_core`) it would turn every cell of the row.
    rot_in: bool = False
    # The router and the shared expert run inside the second step, a band
    # at a time. The whole row leaves both to `moe.moe_mlp`, which picks
    # a mesh's expert-parallel path before the router and makes the
    # shared expert's product after the experts'.
    route_in: bool = False
    # remat "mlp": the dense MLP under a checkpoint of its own. (No loop
    # has it: a stretch's backward rule keeps nothing of a band.)
    mlp_ckpt: bool = False
    # The stream steps' kernels (`ops/pallas/stream_mix.py`, `sinkhorn.py`) and
    # a gated short convolution's (`ops/pallas/conv_gate.py`):
    # None = on the chip where the shapes allow; False = the plain forms,
    # on a mesh of several devices (a kernel is opaque to the partitioner).
    hc_kernel: Optional[bool] = None


# Of a plain attention layer's parameters, those its first step reads;
# its second reads the rest (latent attention: all but `wo`, and `wo`).
_ATTN_IN = ("wq", "wk", "wv", "bq", "bk", "bv", "q_norm", "k_norm", "wg", "indexer")
# Of a delta-rule mixer's, those its first step reads (the projections)
# and its second (the head norm, the output projection); the rest are
# what crosses tokens reads (`ops/kda.kda_mixer`).
# The decay's input and the gate by the rule's form (`KDAConfig`): the
# low-rank pairs, or a column a head `w_a` and a full-rank `w_g`.
_KDA_IN = ("wq", "wk", "wv", "w_fa", "w_fb", "w_a", "w_b", "w_ga", "w_gb", "w_g")
_KDA_OUT = ("o_norm", "wo")


def _mixer_weights(st: _Stretch, mp, first: bool):
    """The part of a mixer's parameters `mp` that its first step reads, or
    its second."""
    if st.kind.mixer == "kda":
        return {n: mp[n] for n in (_KDA_IN if first else _KDA_OUT) if n in mp}
    first_names = set(mp) - {"wo"} if st.kind.latent else _ATTN_IN
    return {n: w for n, w in mp.items() if (n in first_names) == first}


def _hc_read(st: _Stretch, hp, x):
    """A sublayer's input from the streams x `[R, T, n D]` under its
    hyper-connections `hp` (`ops/hyper_conn.coefficients`, scope
    `mhc_coef`): `h = H_pre X` `[R, T, D]` (scope `mhc_read`) and the
    (H_post `[R, T, n]`, H_res `[R, T, n, n]`), float32, that
    `_hc_write` puts its output back by. One stream (`cfg.hyper` None):
    x itself, and nothing."""
    hy = st.cfg.hyper
    if hy is None:
        return x, ()
    with jax.named_scope("mhc_coef"):
        h_pre, h_post, h_res = hyper_conn.coefficients(
            hp, x, hy, st.cfg.norm_eps, st.hc_kernel)
    with jax.named_scope("mhc_read"):
        h = stream_mix.mhc_mix(h_pre[..., None, :], (x,), st.hc_kernel)
    return h, (h_post, h_res)


def _hc_write(st: _Stretch, x, y, h_post=None, h_res=None):
    """The streams after a sublayer's output y `[R, T, D]`: `X' = H_res X
    + H_post^T y` (scope `mhc_write`); one stream: `x + y`."""
    if h_res is None:
        return x + y
    with jax.named_scope("mhc_write"):
        a = jnp.concatenate([h_res, h_post[..., None]], axis=-1)  # [R, T, n, n + 1]
        return stream_mix.mhc_mix(a, (x, y), st.hc_kernel)


def _kda_in(h, mp, cdt):
    """A delta-rule mixer's projections of its input h `[R, T, D]` (normed,
    where the block norms on the way in)
    (scope `kda_proj`; the decay's and the gate's under `kda_gate`): q, k
    `[R, T, Hk K]` and v `[R, T, H V]` before their convolutions, the
    decay's input (`(h W_fa) W_fb` `[R, T, H K]`, or a column a head `h
    W_a` `[R, T, H]`), beta's `h W_b` `[R, T, H]`, the output gate's
    (`(h W_ga) W_gb`, or the full-rank `h W_g`) `[R, T, H V]`."""
    with jax.named_scope("kda_proj"):
        q, k, v, b = (h @ mp[n].astype(cdt) for n in ("wq", "wk", "wv", "w_b"))
        with jax.named_scope("kda_gate"):
            if "w_a" in mp:
                f = h @ mp["w_a"].astype(cdt)
            else:
                f = (h @ mp["w_fa"].astype(cdt)) @ mp["w_fb"].astype(cdt)
            if "w_g" in mp:
                gate = h @ mp["w_g"].astype(cdt)
            else:
                gate = (h @ mp["w_ga"].astype(cdt)) @ mp["w_gb"].astype(cdt)
    return q, k, v, f, b, gate


def _kda_out(o, gate, mp, cfg, cdt):
    """The rule's output o `[R, T, H, V]` -> the mixer's `[R, T, D]`: an
    RMSNorm a head (V wide), the gate (`KDAConfig.gate_act`: a sigmoid, or silu),
    the output projection (scope `kda_out`)."""
    with jax.named_scope("kda_out"):
        o = rms_norm(o, mp["o_norm"], cfg.norm_eps).reshape(gate.shape)
        act = jax.nn.silu if cfg.kda.gate_act == "silu" else jax.nn.sigmoid
        return (o * act(gate)) @ mp["wo"].astype(cdt)


def _before_mixer(st: _Stretch, w, xs, side):
    """A delta-rule mixer's layer up to what crosses tokens: `ln1` and
    `_kda_in`. An attention layer up to what crosses tokens: its input x (then
    the k and v of the layer it reads, where it reads one) under `ln1`
    and attention's projections of that (scope `attn_qkv`: `_attn_in`,
    `_latent_in`), with what is token-wise after them (q/k norm, the
    gate's and the indexer's projections, rotary where `st.rot_in`).
    `side`: the rotary tables, then the indexer's. Returns q, k, v, (the
    gate's projection), (the indexer's three), (differential attention:
    k and v as projected, which a reader of this layer takes), (several
    streams: H_post and H_res, `_hc_read`'s, for `_after_mixer`)."""
    cfg, kind, cdt = st.cfg, st.kind, st.cdt
    (x, *kept), mp = xs, w["mixer"]
    if kind.mixer == "kda":
        with jax.named_scope("kda_proj"):
            h = _norm(x, w.get("ln1"), cfg)
        return _kda_in(h, mp, cdt)
    h, coefs = _hc_read(st, w.get("hc"), x)
    with jax.named_scope("attn_qkv"):
        h = _norm(h, w.get("ln1"), cfg)
    if kind.latent:
        return _latent_in(h, mp, cfg, *side[:2], cdt, kind.rotary) + coefs
    q, k, v, gate, own_kv = _attn_in(h, mp, cfg, cdt, tuple(kept) or None)
    if st.rot_in:
        with jax.named_scope("attn_qkv"):
            q = apply_rotary(q, side[0], side[1], cfg.rotary_interleaved)
            k = apply_rotary(k, side[0], side[1], cfg.rotary_interleaved)
    out = (q, k, v) if gate is None else (q, k, v, gate)
    if kind.indexed:
        out += _index_proj(h, mp["indexer"], cfg, side[-2], side[-1], cdt)
    return (out + own_kv if kind.diff else out) + coefs


def _mlp_joins(st: _Stretch, x, m, w, coefs=()):
    """The stream x plus the MLP's product m under the layer's
    `ln2_post`; several streams: m written to them by `coefs`."""
    if "ln2_post" in w:
        with jax.named_scope("mlp_out_norm"):
            m = _norm(m, w["ln2_post"], st.cfg)
    return _hc_write(st, x, m, *coefs)


def _mlp_part(st: _Stretch, w, xs, side=()):
    """What of a layer's MLP is token-wise, from the stream x (scope
    `mlp`): `ln2` and the dense MLP with its residual, returned alone; or
    for an expert layer the stream and the experts' input, with the
    router's four and the shared expert's product where `st.route_in`. A
    layer of a mixer alone hands x back. Several streams: x is they, the
    MLP reads them through `_hc_read`, and the result ends in H_res
    (the dense MLP's, its write done) or H_post and H_res (the experts',
    for the join after them)."""
    cfg, kind, cdt = st.cfg, st.kind, st.cdt
    (x,) = xs
    if kind.mlp is None:
        return (x,)
    h, coefs = _hc_read(st, w.get("hc"), x)
    with jax.named_scope("mlp"):
        h = _norm(h, w.get("ln2"), cfg)
        if kind.mlp == "dense":
            mlp = jax.checkpoint(_mlp, static_argnums=(2, 3)) if st.mlp_ckpt else _mlp
            m = mlp(h, w["mlp"], cfg, cdt)
            return (_mlp_joins(st, x, m, w, coefs),) + coefs[1:]
        if not st.route_in:
            return (x, h) + coefs
        from areal_tpu.models.moe import _router, _shared_expert

        ht = h.reshape(-1, h.shape[-1])
        with jax.named_scope("moe_router"):
            routed = _router(ht, w["mlp"]["router"], cfg.moe, w["mlp"].get("expert_bias"))
        out = (x, h) + tuple(a.reshape(h.shape[:2] + a.shape[1:]) for a in routed)
        if "shared" in w["mlp"]:
            out += (_shared_expert(ht, w["mlp"]["shared"], activation_fn(cfg.activation),
                                   cdt).reshape(h.shape),)
        return out + coefs


def _after_mixer(st: _Stretch, w, xs, side):
    """A delta-rule mixer's layer from the rule's output and the gate's
    product (`_kda_out`), an attention layer from the attention call's output (then the
    gate's projection; several streams: `_before_mixer`'s H_post and
    H_res; the layer's input last) to the MLP's product or
    the routed experts' doorstep: the output projection under the gate
    (`_attn_out`, with differential attention's combine under `w["l0"]`;
    `_latent_out`), `ln1_post` and the residual (scope `attn_out`), then
    `_mlp_part`: two steps composed, because a third loop would cost
    set-up more than it saves the chip."""
    cfg, kind, cdt = st.cfg, st.kind, st.cdt
    *got, x = xs
    coefs = ()
    if cfg.hyper is not None:  # `_before_mixer`'s H_post and H_res
        *got, h_post, h_res = got
        coefs = (h_post, h_res)
    if kind.mixer == "kda":
        a = _kda_out(got[0], got[1], w["mixer"], cfg, cdt)
    elif kind.latent:
        a = _latent_out(got[0], w["mixer"], cdt)
    else:
        a = _attn_out(got[0], got[1] if len(got) > 1 else None, w["mixer"], cfg, cdt,
                      w.get("l0"))
    with jax.named_scope("kda_out" if kind.mixer == "kda" else "attn_out"):
        if "ln1_post" in w:
            with jax.named_scope("mixer_out_norm"):
                a = _norm(a, w["ln1_post"], cfg)
        x = _hc_write(st, x, a, *coefs)
    return _mlp_part(st, w, (x,))


def _ssm_layer(st: _Stretch, w, xs, side, carry):
    """A Mamba-2 layer alone, one band of it (`ops/band_loop.carried`): the
    input norm, the mixer after what the cells before the band handed on
    (`ops/ssm.band_mixer`: the state, the convolution's last cells),
    `ln1_post` where the stack has one, the residual. `side`: the segment
    ids. Returns the stream and what the band hands on."""
    from areal_tpu.ops.ssm import band_mixer

    cfg = st.cfg
    (x,), (segment_ids,) = xs, side
    with jax.named_scope("ssm_in_proj"):
        h = _norm(x, w.get("ln1"), cfg)
    a, carry = band_mixer(carry, h, w["mixer"], cfg.ssm, segment_ids, st.cdt, cfg.norm_eps)
    with jax.named_scope("ssm_out_proj"):
        if "ln1_post" in w:
            a = _norm(a, w["ln1_post"], cfg)
        return (x + a,), carry


def _conv_layer(st: _Stretch, w, xs, side, carry):
    """A gated short-convolution layer up to its MLP's product or the routed
    experts' doorstep, the whole row (`carry` None) or one band of it
    (`ops/band_loop.carried`): the input norm, the mixer after what the
    cells before the band handed on (`ops/ssm.gated_conv_mixer`: the last
    gated inputs `B * x` and their segment ids), `ln1_post` where the stack
    has one, the residual, then `_mlp_part`. `side`: the segment ids.
    Returns `_mlp_part`'s and what the band hands on."""
    from areal_tpu.ops.ssm import gated_conv_mixer

    cfg = st.cfg
    (x,), (segment_ids,) = xs, side
    with jax.named_scope("conv_in_proj"):
        h = _norm(x, w.get("ln1"), cfg)
    a, carry = gated_conv_mixer(carry, h, w["mixer"], segment_ids, st.cdt, st.hc_kernel)
    with jax.named_scope("conv_out_proj"):
        if "ln1_post" in w:
            a = _norm(a, w["ln1_post"], cfg)
        x = x + a
    return _mlp_part(st, w, (x,)), carry


def _kind_loops(cfg: TransformerConfig, kind: LayerKind) -> bool:
    """Whether a layer of `kind` runs over live bands where the call's
    shape allows. As two stretches (`ops/band_loop.stretch`): a plain,
    latent or indexed attention mixer, or a delta-rule mixer, with an MLP
    beside it (the delta-rule kinds by memory first: a whole row of their
    stretches' float32 and MLP temporaries is 1.8 GB of a 16,384-token
    step's 7.4, PERF.md section 6, PR 50). By the probe
    (`scripts/band_loop_probe.py`; PERF.md section 6, PR 45) such a layer
    takes 21-32 % off a half-empty row and loses 0-3.5 % of a full one. As
    one carried loop (`ops/band_loop.carried` around `_ssm_layer`; PR 61):
    a Mamba-2 mixer alone in its layer, whose bands are whole chunks: the
    layer's whole body runs a band at a time, so nothing `[T, in_proj_dim]`
    is made, cut or put, and what crosses bands is the state, the taps'
    last cells and a segment id (the probe's figures for it: PERF.md
    section 6, PR 61; with a stretch before the mixer and one after, PR
    45's form, the wide products still crossed HBM and the kind lost 7-14 %
    of a full row for 9-22 % off a half-empty one). As one carried loop too
    (around `_conv_layer`; PR 63): a gated short convolution under a dense MLP,
    whose bands hand on the taps' last two gated inputs; under experts the same
    mixer keeps the whole row: its token-wise part is two light projections and
    a router, and by the probe the loop's float32 sums and copies cost 2-9 %
    more than the dead cells save (PERF.md section 6, PR 63). Experts or attention
    alone in a layer, a selective scan or memory unit with its MLP, and
    differential attention's three kinds (each walked outside a scan, the
    most to trace in the stack with the least set-up to spare) keep the
    whole row. Where such a layer stands does not matter
    (`looping_layers`): a leading dense layer and a prediction module's
    block, which run once outside a scan, loop as the scanned layers do
    since PR 48, but in a stack of several streams (`_lone_layer_loops`;
    the set-up they cost is PERF.md section 6's, PR 48). The figures of the
    kinds still ruled out were taken with looping bodies that went with
    PR 45's rule: the probe in the tree re-measures the kinds that loop."""
    if kind.mixer == "ssm":
        return (kind.mlp is None and cfg.ssm.form == "mamba2" and kind.reads is None
                and not kind.keeps and band_loop._BAND % cfg.ssm.chunk_size == 0)
    if kind.mixer == "conv":
        return kind.mlp == "dense"
    return (kind.mixer in ("attention", "kda") and kind.mlp is not None
            and not kind.diff and kind.reads is None)


def _scanned_layers(cfg: TransformerConfig) -> set:
    """The layers `forward` runs inside a `lax.scan` (`cfg.segments`)."""
    return {i for seg in cfg.segments() if seg.repeats > 1
            for i in range(seg.start, seg.start + len(seg.unit) * seg.repeats)}


def looping_layers(cfg: TransformerConfig, n_rows: int, row_len: int,
                   sharded: bool = False, mtp: bool = False,
                   mixer: Optional[str] = None) -> int:
    """How many layers walk their live bands in a call of `n_rows` rows of
    `row_len` cells: none on a mesh that splits rows or the sequence
    (`sharded`), none for rows together or a row under two bands
    (`ops/band_loop.loops`), else every layer whose kind takes the loop
    (`_kind_loops`), in a scan or alone (a leading dense layer), and with
    `mtp` the prediction module's block, a layer of the stack's last kind
    alone. One exception (`_lone_layer_loops`): a layer alone in a stack
    of several residual streams keeps the whole row. `mixer`: count the
    layers of that mixer alone ("ssm": those whose scan walks bands)."""
    if sharded or not band_loop.loops(n_rows, row_len):
        return 0
    kinds = cfg.kinds()
    scanned = _scanned_layers(cfg)
    return sum(_kind_loops(cfg, k) and (i in scanned or _lone_layer_loops(cfg))
               and mixer in (None, k.mixer)
               for i, k in enumerate(kinds + kinds[-1:] * mtp))


def scan_stacked(cfg: TransformerConfig, params: Params) -> Any:
    """A bool a leaf of `params`: whether every layer that holds it runs
    inside a `lax.scan` of `forward` (`cfg.segments`). Such a leaf's
    gradient is a scan's stacked output: it stands in memory, whole and
    in the leaf's own layout, before anything can consume it. No other
    leaf's does: a layer run once hands its weights' gradients over as
    products that a consumer can fuse with, a kind's stack with a layer
    outside a scan is joined from pieces, and the embedding's, the
    head's and the prediction module's come in whatever layout their
    last product chose (`engine/jax_engine._accum_step_fn` asks)."""
    scanned = _scanned_layers(cfg)
    out = jax.tree_util.tree_map(lambda _: False, params)
    for path, idx in cfg.stack_paths().values():
        if scanned.issuperset(idx):
            _stack_at(out, path, jax.tree_util.tree_map(
                lambda _: True, _stack_at(params, path)))
    return out


def _lone_layer_loops(cfg: TransformerConfig) -> bool:
    """Whether a layer that runs once outside a scan (a leading dense
    layer, a prediction module's block) walks its live bands where its
    kind does: yes, but for a stack of several residual streams
    (`cfg.hyper`). Such a layer's stretches hold the stream steps' kernels
    and their hand-written backward rules, the most of any kind to trace
    and lower once more outside the scan's one body, for rows 69 % full:
    in `xing4-d5e8-train-ppo-8k` it bought +1.7 to +1.9 % of
    `train_tokens_per_s` for +6.7 to +8.3 s of warm `setup_s` (a bound of
    5.9 s), where one stream's lone layers cost +1.0 to +2.6 s (trinity)
    and +2.3 to +5.9 s (joyai, two of them) for +5.8 to +6.4 % (PERF.md
    section 6, PR 48)."""
    return cfg.hyper is None


def _segment_stacks(params, cfg: TransformerConfig):
    """The stack's segments (`cfg.segments`), each with the parameters of
    its unit's positions: one layer's pytree a position where the
    segment runs once, a stack of `repeats` layers a position where it
    is scanned. Each kind's stack is cut along its leading axis into
    what the segments take of it, in order, by one `lax.split` a leaf
    (its transpose is one concatenation; indexing's would be a
    zero-padded copy of the stack for every piece). A stack that one
    scan takes whole is handed over as it is."""
    segments = cfg.segments()
    # per kind: (segment, how many layers a repeat) in the stack's order
    takes: Dict[str, list] = {}
    for si, seg in enumerate(segments):
        for parts in dict.fromkeys(seg.unit):
            takes.setdefault(parts, []).append((si, seg.unit.count(parts)))
    pieces: Dict[Tuple[int, str], list] = {}
    for parts, (path, _) in cfg.stack_paths().items():
        stack = _stack_at(params, path)
        leaves, treedef = jax.tree_util.tree_flatten(stack)
        (s0, c0), whole = takes[parts][0], len(takes[parts]) == 1
        if whole and c0 == 1 and segments[s0].repeats > 1:
            pieces[s0, parts] = [stack]
            continue
        sizes = tuple(segments[si].repeats * c for si, c in takes[parts])

        def cut(a):
            """One leaf -> per segment, the arrays of its unit's positions."""
            out = []
            for piece, (si, c) in zip(jax.lax.split(a, sizes, axis=0), takes[parts]):
                r = segments[si].repeats
                if c > 1:  # c layers of this kind a repeat: [r c, ..] -> c of [r, ..]
                    piece = piece.reshape((r, c) + a.shape[1:])
                    out.append([b.reshape((r,) + a.shape[1:])
                                for b in jax.lax.split(piece, (1,) * c, axis=1)])
                else:
                    out.append([piece])
                if r == 1:  # run as they are: no leading axis
                    out[-1] = [b.reshape(a.shape[1:]) for b in out[-1]]
            return out

        per_leaf = [cut(a) for a in leaves]
        for n, (si, c) in enumerate(takes[parts]):
            pieces[si, parts] = [treedef.unflatten([l[n][i] for l in per_leaf])
                                 for i in range(c)]
    out = []
    for si, seg in enumerate(segments):
        nth = {parts: iter(pieces[si, parts]) for parts in dict.fromkeys(seg.unit)}
        out.append((seg, [next(nth[parts]) for parts in seg.unit]))
    return out


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
    mtp: bool = False,  # also run the prediction module (cfg.mtp)
    index_loss: bool = False,  # also sum the indexers' KL terms (cfg.indexer)
    index_choice: bool = False,  # also return what the indexers chose
    bands: bool = False,  # True: the caller's packer may leave a band of a row empty
) -> Any:
    """Packed-rows forward pass.

    Returns logits [R, T, V] (fp32), critic values [R, T] when
    cfg.is_critic, or hidden states; optionally also per-layer (k, v)
    stacked as [L, R, T, Hkv, hd] for generation prefill. With `mtp`
    the result is a pair: what it would be without, and the prediction
    module's hidden states [R, T, D] after its norm (its expert layer's
    sums are in the aux losses), which the model's head
    turns into a prediction of the token two on. With `index_loss` the
    indexed layers' KL terms (`config.IndexerConfig`) are summed into the
    aux sums under `index_kl`; without it those layers score and choose
    all the same and the sum stays 0. With `index_choice` (a check's,
    not a step's: the layers then run one by one, without remat) the
    last result is (choice bool `[L, R, T, T]`, tau `[L, R, T]`) of the
    indexed layers in order.

    With `bands` one row alone of two bands or more (`ops/band_loop.loops`)
    runs the token-wise stretches of every layer whose kind takes the loop
    (`looping_layers`: the stack's, in a scan or not, and the prediction
    module's block; a layer alone among several residual streams keeps the
    whole row) over the bands its tokens reach, and a Mamba-2 layer alone
    whole, band after band, the state handed on (`_ssm_layer`). For the caller to
    say, who knows its packer: one whose ladder steps by a band or less at
    this row length
    (`base/datapack.ladder_step`) fills every band of such a row, and the
    loop would only cost (0 to 6 % of a full row).

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
        if cfg.hyper is not None:  # every stream starts as the embedding
            x = act_c(jnp.concatenate([x] * cfg.hyper.n, axis=-1))

    # One table a rotary set, built once: `LayerKind.table` -> (cos, sin).
    tables: Dict[Any, Any] = {}
    if cfg.pos_emb == "learned":
        cos = sin = None
    elif cfg.rotary_sets is None:
        inv_freq = jnp.asarray(
            rotary_inv_freq(
                cfg.rotary_dim, cfg.rotary_base, cfg.rotary_scaling,
                cfg.rotary_scaling_type, cfg.rotary_scaling_params,
            )
        )
        cos, sin = rotary_cos_sin(positions, inv_freq)  # [R, T, rotary_dim/2]
    else:
        for name, rs in cfg.rotary_sets.items():
            with jax.named_scope(f"rotary_table.{name}"):
                tables[name] = rotary_cos_sin(positions, jnp.asarray(rotary_inv_freq(
                    cfg.rotary_dim, rs.base, rs.scaling, rs.scaling_type,
                    rs.scaling_params)), rs.attention_factor)
        cos, sin = next(iter(tables.values()))
    index = None
    if cfg.indexer is not None:
        # the indexer's own tables: the same base over its head size
        index = _Index(*rotary_cos_sin(positions, jnp.asarray(rotary_inv_freq(
            cfg.indexer.head_dim, cfg.rotary_base, cfg.rotary_scaling,
            cfg.rotary_scaling_type, cfg.rotary_scaling_params))), bool(index_loss),
            [] if index_choice else None)
    if index_choice:
        if index is None or remat not in (False, "none"):
            raise ValueError("index_choice=True needs cfg.indexer, and no remat")
        # one by one: a scan's body would hold the choices as tracers
        cfg = dataclasses.replace(cfg, scan_min_repeats=cfg.n_layers + 1)

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
    kinds = cfg.kinds()
    if return_kv and (cfg.hyper is not None or not all(
            k == kinds[0] and k.block and not k.latent and not k.indexed
            for k in kinds)):
        raise NotImplementedError(
            "return_kv with layers of different kinds, latent attention, an "
            "indexer or several residual streams: the KV cache "
            "(models/generation.py) holds one kind of "
            "layer, plain attention and an MLP in each over one stream, has no "
            "recurrent state "
            "for a state-space layer, no latent row for latent attention and no "
            "indexer keys beside k and v"
        )
    if mtp and (cfg.mtp is None or return_kv):
        raise ValueError("mtp=True needs cfg.mtp, and hands out no KV cache")
    if cfg.n_kda_layers and mesh is not None and mesh.shape.get("seq", 1) > 1:
        raise NotImplementedError(
            "a delta-rule layer on a mesh that splits the sequence (ring / "
            "ulysses context parallelism): ops/kda.py walks a row's chunks on one "
            "device and has no hand-over of the state and of the convolutions' "
            "last inputs from one sequence shard to the next"
        )
    if cfg.n_ssm_layers and mesh is not None and mesh.shape.get("seq", 1) > 1:
        raise NotImplementedError(
            "a state-space layer on a mesh that splits the sequence (ring / "
            "ulysses context parallelism): ops/ssm.py scans a row's chunks on "
            "one device and has no hand-over of the state and of the "
            "convolution's last inputs from one sequence shard to the next"
        )
    if use_moe:
        from areal_tpu.models.moe import moe_mlp

        moe_token_mask = segment_ids > 0  # real-token drop accounting
        # mesh enables the expert-parallel dropless path (moe.py
        # _moe_mlp_ep) when the fsdp axis divides num_experts.
        moe_fn = lambda h, mp, routed=None, shared=None: moe_mlp(
            h, mp, cfg, cdt, token_mask=moe_token_mask, mesh=mesh,
            routed=routed, shared=shared)
        if remat_mode == "mlp":
            moe_fn = jax.checkpoint(moe_fn)
    # One row of two bands or more, alone on its chip, walks the bands its
    # tokens reach in every token-wise stretch of its layers.
    n_live = None
    if bands and not return_kv and looping_layers(
            cfg, *input_ids.shape, sharded=mesh is not None and mesh.size > 1):
        n_live = band_loop.live_bands(segment_ids)

    def layer_body(kind, variants, scanned=False):
        """carry, (one layer's parameters, which of `variants` it is),
        the tensor it reads -> carry, its (k, v) (for a layer that keeps:
        what it keeps): a layer with the parts of `kind` whose
        attention is one of the (window, table) `variants`
        (`LayerKind.table`), under the remat mode. Layers that differ
        only in their attention share the
        one traced body: the switch is around the attention call alone
        (`_attn_core`), so what the backward pass keeps of a layer is one
        layer's, whatever its kind.

        A layer is steps, token-wise (`_before_mixer`, `_after_mixer`,
        `_mlp_part`) or across tokens (the attention call, a scan, the
        routed experts), and `run` is how a token-wise step runs: over the
        row's live bands (`ops/band_loop.stretch`) for a layer whose kind
        takes the loop (`_kind_loops`), in a scan or outside one
        (`_lone_layer_loops`), where the call walks bands at all
        (`n_live`), a plain call otherwise. (A Mamba-2 mixer alone in its
        layer, where `banded`, is no step of this kind: the whole layer is
        one loop that hands a carry from band to band, `_ssm_layer`.)"""
        banded = (n_live is not None and _kind_loops(cfg, kind)
                  and (scanned or _lone_layer_loops(cfg)))
        st = _Stretch(
            cfg, kind, cdt, route_in=banded, mlp_ckpt=remat_mode == "mlp" and not banded,
            rot_in=banded and not kind.latent and cos is not None
            and all(table for _, table in variants),
            hc_kernel=False if mesh is not None and mesh.size > 1 else None)
        hyper = cfg.hyper is not None
        # The variants' tables (a stack of rotary sets): one, the body's own;
        # several, stacked a variant, and a layer takes its own by its variant
        # index before its first step, so the band loop's rotation and the
        # attention call's alike read the one pair they are handed.
        own = [tables.get(t, (cos, sin)) for _, t in variants]
        several = len({t for _, t in variants if t in tables}) > 1
        if several:
            stacked = tuple(jnp.stack([pair[j] for pair in own]) for j in (0, 1))

        def res_err(h_res):
            """What Sinkhorn left undone of H_res, summed over real tokens."""
            return jnp.sum(jnp.where(segment_ids > 0, hyper_conn.res_err(h_res), 0.0))

        if banded:
            # `_before_mixer` reads nothing of the layer's MLP, and its key
            # says so: a leading dense layer's first stretch is then the
            # trace the expert layers' first stretch made, where the two
            # have the same mixer (PERF.md section 6, PR 48: 1.5 s of the
            # joyai stack's tracing on the chip's host).
            st_in = st._replace(kind=dataclasses.replace(kind, mlp=None))
            run = lambda fn, w, xs, side=(), minor=(): band_loop.stretch(
                fn, st_in if fn is _before_mixer else st, w, xs, side, n_live, minor)
        else:
            run = lambda fn, w, xs, side=(), minor=(): fn(st, w, xs, side)

        def body(carry, xs, kept=None):
            lp, variant_index = xs
            x, aux_acc = carry
            kv, terms, step, w = None, {}, _mlp_part, {}
            ln1 = {n: lp[n] for n in ("ln1",) if n in lp}  # none: output norms only
            if kind.mixer == "attention":
                mp = lp["attn"]
                cos, sin = own[0]
                if several:
                    cos, sin = (jax.lax.dynamic_index_in_dim(a, variant_index, keepdims=False)
                                for a in stacked)
                side = (() if cos is None else (cos, sin)) + (
                    (index.cos, index.sin) if kind.indexed else ())
                q, k, v, *mid = run(
                    _before_mixer,
                    {**ln1, "mixer": _mixer_weights(st, mp, True),
                     **({"hc": lp["hc1"]} if hyper else {})},
                    (x,) + (kept or ()), side)
                step, w = _after_mixer, {"mixer": _mixer_weights(st, mp, False)}
                coefs = ()
                if hyper:  # H_post and H_res of the mixer's read, for its write
                    *mid, h_post, h_res = mid
                    coefs, terms = (h_post, h_res), {"mhc_res_err": res_err(h_res)}
                if kind.latent:
                    got = (_latent_core(q, k, v, cfg, segment_ids, positions, attn_impl, mesh),)
                else:
                    if kind.diff:  # beside the variant, the layer's lambda_init
                        variant_index, w["l0"] = variant_index["variant"], variant_index["l0"]
                        *mid, k0, v0 = mid
                    gate = (mid.pop(0),) if "wg" in mp else ()
                    out, k, sums = _attn_core(
                        q, k, v, cfg, cos, sin, segment_ids, positions, attn_impl, mesh,
                        variants, variant_index, kind.diff, index if kind.indexed else None,
                        tuple(mid), rotated=st.rot_in)
                    got, terms = (out,) + gate, {**terms, **sums}
                    if kind.diff:  # a reader takes k and v as projected
                        k, v = k0, v0
                kv, got = (k, v), got + coefs + (x,)
                if "ln1_post" in lp:
                    w["ln1_post"] = lp["ln1_post"]
            elif kind.mixer == "kda":
                from areal_tpu.ops.kda import kda_mixer

                mp = lp["kda"]
                *qkvfb, gate = run(
                    _before_mixer,
                    {**ln1, "mixer": _mixer_weights(st, mp, True)}, (x,))
                o = kda_mixer(*qkvfb, mp, cfg.kda, segment_ids, cdt, mesh=mesh)
                step, w = _after_mixer, {"mixer": _mixer_weights(st, mp, False)}
                got = (o, gate, x)
                if "ln1_post" in lp:
                    w["ln1_post"] = lp["ln1_post"]
            elif kind.mixer == "ssm" and cfg.ssm.form == "mamba1":
                from areal_tpu.ops.selective_scan import sscan_mixer

                with jax.named_scope("sscan_in_proj"):
                    h = _norm(x, lp.get("ln1"), cfg)
                a, kv = sscan_mixer(h, lp["ssm"], cfg.ssm, segment_ids, cdt, mesh=mesh)
                with jax.named_scope("sscan_out_proj"):
                    got = (x + a,)
            elif kind.mixer == "gmu":
                with jax.named_scope("gmu"):
                    h = _norm(x, lp.get("ln1"), cfg)
                    g = jax.nn.silu(h @ lp["gmu"]["w_in"].astype(cdt))
                    got = (x + (g * kept) @ lp["gmu"]["w_out"].astype(cdt),)
            elif kind.mixer == "conv":
                # one step from the stream to the MLP's product (the experts'
                # doorstep); banded, one loop that hands the taps' reach back on
                step, got = _conv_layer, (x,)
                w = {**ln1, "mixer": lp["conv"],
                     **{n: lp[n] for n in ("ln1_post",) if n in lp}}
            elif kind.mixer == "ssm" and banded:
                # the whole layer a band at a time: one loop, and the state and
                # the convolution's last cells handed from band to band
                from areal_tpu.ops.ssm import start_carry

                got = band_loop.carried(
                    _ssm_layer, st,
                    {**ln1, "mixer": lp["ssm"],
                     **{n: lp[n] for n in ("ln1_post",) if n in lp}},
                    (x,), (segment_ids,), start_carry(cfg.ssm, x.shape[0], cdt), n_live)
            elif kind.mixer == "ssm":
                from areal_tpu.ops.ssm import ssm_mixer

                with jax.named_scope("ssm_in_proj"):
                    h = _norm(x, lp.get("ln1"), cfg)
                a = ssm_mixer(h, lp["ssm"], cfg.ssm, segment_ids, cdt, cfg.norm_eps)
                with jax.named_scope("ssm_out_proj"):
                    if "ln1_post" in lp:
                        a = _norm(a, lp["ln1_post"], cfg)
                    got = (x + a,)
            else:  # an MLP alone
                got = (x,)
            if kind.mlp == "dense":
                w.update({n: lp[n] for n in ("ln2", "ln2_post", "mlp") if n in lp})
            elif kind.mlp == "moe":  # the experts' own weights stay out of a stretch
                w.update({n: lp[n] for n in ("ln2",) if n in lp}, mlp={n: lp["mlp"][n] for n in (
                    "router", "expert_bias", "shared") if n in lp["mlp"]})
            if hyper and kind.mlp is not None:
                w["hc"] = lp["hc2"]
            # (a mixer alone in its layer has no step left: no loop over nothing)
            # (latent attention's output comes sequence-minor: `_latent_core`)
            if step is _conv_layer:  # a band hands the next its last cells
                from areal_tpu.ops.ssm import conv_start_carry

                x, *rest = band_loop.carried(
                    step, st, w, got, (segment_ids,),
                    conv_start_carry(cfg.conv, x.shape[0], cfg.hidden_dim, cdt), n_live,
                ) if banded else step(st, w, got, (segment_ids,), None)[0]
            else:
                x, *rest = got if kind.mlp is None and step is _mlp_part else run(
                    step, w, got, minor=(0,) if kind.latent else ())
            coefs = ()
            if hyper and kind.mlp is not None:  # the MLP's read: H_res last, H_post before
                *rest, h_res = rest
                terms = {**terms, "mhc_res_err": terms["mhc_res_err"] + res_err(h_res)}
                if kind.mlp == "moe":
                    *rest, h_post = rest
                    coefs = (h_post, h_res)
            if kind.mlp == "moe":
                # the shared expert's product, where a stretch made it,
                # joins the experts' over the whole row: a sum, of zeros
                # where no token is (no pair, and the stream and that
                # product are zeros there)
                with jax.named_scope("mlp"):
                    m, aux = moe_fn(rest[0], lp["mlp"], tuple(rest[1:5]) or None,
                                    rest[5] if len(rest) > 5 else None)
                terms = {**terms, **aux}
            aux_acc = {n: aux_acc[n] + terms[n] if n in terms else aux_acc[n]
                       for n in aux_acc}
            if kind.mlp == "moe":  # its post-norm follows the experts' sum
                with jax.named_scope("mlp"):
                    x = _mlp_joins(st, x, m, lp, coefs)
            return (act_c(x), aux_acc), kv if return_kv or kind.keeps else None

        if remat_mode == "full":
            return jax.checkpoint(body)
        if remat_mode == "save_attn":
            from areal_tpu.ops.attention import SPLASH_RESIDUAL_NAME

            return jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    SPLASH_RESIDUAL_NAME
                ),
            )
        return body

    from areal_tpu.models.moe import moe_aux_zeros

    sums0 = moe_aux_zeros(cfg)
    if cfg.indexer is not None:
        from areal_tpu.ops.indexer import INDEX_SUMS

        sums0.update({k: jnp.zeros((), jnp.float32) for k in INDEX_SUMS})
    if cfg.hyper is not None:
        # over sublayers and real tokens: what Sinkhorn left undone of H_res
        sums0["mhc_res_err"] = jnp.zeros((), jnp.float32)
    carry, kvs = (x, sums0), None
    kept: Dict[int, Any] = {}  # keeping layer -> its tensor, for its readers
    for seg, stacks in _segment_stacks(params, cfg):
        # One body a position of the unit: its layers, one a repeat,
        # differ at most in their attention, and which each has is
        # scanned beside its parameters (nothing, where all have the same).
        p, bodies, which, reads = len(seg.unit), [], [], []
        for j in range(p):
            idx = range(seg.start + j, seg.start + p * seg.repeats, p)
            of_j = [kinds[i] for i in idx]
            rest = [(k.window, k.table) for k in of_j]
            variants = tuple(sorted(set(rest), key=rest.index))
            bodies.append(layer_body(of_j[0], variants, seg.repeats > 1))
            w = None if len(variants) == 1 else jnp.asarray(
                [variants.index(v) for v in rest], jnp.int32)
            if of_j[0].diff:
                l0 = jnp.asarray([diff_lambda_init(i) for i in idx], jnp.float32)
                w = dict(variant=w, l0=l0 if seg.repeats > 1 else l0[0])
            which.append(w)
            if len({k.reads for k in of_j}) > 1 or (
                    seg.repeats > 1 and any(k.keeps for k in of_j)):
                raise NotImplementedError(
                    "layers of one scan that read different layers' tensors, or "
                    "one that keeps its own: a kept tensor is a constant of the "
                    "scan over its readers, and a keeping layer runs on its own")
            reads.append(None if of_j[0].reads is None else kept[of_j[0].reads])
        if seg.repeats == 1:  # as they are, one by one
            for j, (body, lp) in enumerate(zip(bodies, stacks)):
                carry, out = body(carry, (lp, which[j]), reads[j])
                if kinds[seg.start + j].keeps:
                    kept[seg.start + j] = out
        elif p > 1:
            def unit(c, xs, bodies=bodies, reads=reads):
                for body, layer, read in zip(bodies, xs, reads):
                    c, _ = body(c, layer, read)
                return c, None

            carry, _ = jax.lax.scan(unit, carry, tuple(zip(stacks, which)))
        elif which[0] is None:
            body, read = bodies[0], reads[0]
            carry, kvs = jax.lax.scan(
                lambda c, lp: body(c, (lp, None), read), carry, stacks[0])
        else:
            body, read = bodies[0], reads[0]
            carry, kvs = jax.lax.scan(
                lambda c, xs: body(c, xs, read), carry, (stacks[0], which[0]))
    x, moe_aux = carry
    if cfg.hyper is not None:  # the stack's output: the sum of the streams
        d = cfg.hidden_dim
        x = sum(x[..., k * d:(k + 1) * d].astype(jnp.float32)
                for k in range(cfg.hyper.n)).astype(cdt)
    with jax.named_scope("final_norm"):
        x = _norm(x, params["final_norm"], cfg)
    if mtp:
        # The module reads the stack's output and the embedding table and
        # moves neither: the caller's loss alone trains the model.
        mp = params["mtp"]
        with jax.named_scope("mtp_in"):
            # (a row's last position wraps round: it has no target to read)
            e = jax.lax.stop_gradient(emb)[jnp.roll(input_ids, -1, axis=1)].astype(cdt)
            if cfg.embedding_multiplier:
                e = e * jnp.asarray(cfg.embedding_multiplier, cdt)
            u = jnp.concatenate(
                [_norm(e, mp["enorm"], cfg),
                 _norm(jax.lax.stop_gradient(x), mp["hnorm"], cfg)], axis=-1)
            u = act_c(u @ mp["eh_proj"]["weight"].astype(cdt))
        with jax.named_scope("mtp_block"):
            block = jax.tree_util.tree_map(lambda a: a[0], mp["block"])
            (u, moe_aux), _ = layer_body(kinds[-1], ((None, True),))(
                (u, moe_aux), (block, None))
            x_mtp = _norm(u, mp["norm"], cfg)

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
    if mtp:
        out = (out, x_mtp)
    if index_choice:
        chosen = tuple(jnp.stack(a) for a in zip(*index.choices))
        return (out, moe_aux, chosen) if return_aux else (out, chosen)
    if return_kv and return_aux:
        return out, kvs, moe_aux
    if return_kv:
        return out, kvs
    if return_aux:
        return out, moe_aux
    return out
