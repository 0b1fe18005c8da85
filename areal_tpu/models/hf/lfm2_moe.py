"""LFM2-8B-A1B / LFM2-24B-A2B (Liquid AI), HF conversion: `model_type: lfm2_moe`.

A stack whose layers mix tokens in one of two ways, all of it from the
published config's keys: `layer_types` says which layers are a **gated
short convolution** ("conv": `[B | C | x] = u W_in`, a depthwise causal
convolution of `conv_L_cache` taps over `B * x` with no activation and,
`conv_bias` false, no bias, `(C * z) W_out`: `config.ConvConfig`,
`ops/ssm.gated_conv_mixer`) and which GQA attention ("full_attention":
an RMSNorm over each q and k head before the rotary embedding at
`rope_theta`, or `rope_parameters.rope_theta` where the file has that
group, over the whole head). The first `num_dense_layers` layers end in a
dense SwiGLU of `intermediate_size`; every later one in `num_experts`
experts of `moe_intermediate_size`: scores `sigmoid(f W_r)`,
`num_experts_per_tok` chosen on score + `expert_bias` (`use_expert_bias`;
a buffer that no RL step updates, `engine/jax_engine.BUFFER_LEAVES`),
weighted by the bare scores over their sum + 1e-6 (`norm_topk_prob`;
`MoEConfig.route_norm_eps`) times `routed_scaling_factor`; no shared
expert. Every norm an RMSNorm at `norm_eps`; no bias anywhere; the head
is the embedding (`tie_word_embeddings`, true where the file does not
say: the family's convention).

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `num_experts` counts only
the experts whose weights are here) and `experts_held_first`.

The checkpoint's tensor names are written from memory of the
`transformers` release (`operator_norm`, `ffn_norm`, `conv.in_proj` /
`conv.conv` / `conv.out_proj`, `self_attn.q_proj` .. `out_proj` with
`q_layernorm` / `k_layernorm`, `feed_forward.w1` / `w3` / `w2` for gate /
up / down, `feed_forward.gate`, `feed_forward.expert_bias`,
`feed_forward.experts.{e}.*`, `model.embedding_norm`): the catalog gives
the config only. `in_proj`'s rows are `[B | C | x]` in that order, and the
convolution's weight `[channels, 1, taps]` has its last tap on the
position itself (torch's cross-correlation over a left-padded row), which
is `ops/ssm.causal_conv`'s `[taps, channels]` transposed.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import ConvConfig, LayerKind, MoEConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "lfm2_moe"
CONV, FULL = "conv", "full_attention"
ROUTE_NORM_EPS = 1e-6  # the modelling code's, no config key


def _refuse(what: str) -> NotImplementedError:
    return NotImplementedError(f"{MODEL_TYPE}: {what}")


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    n = int(hf["num_hidden_layers"])
    types = list(hf["layer_types"])
    if len(types) != n or set(types) - {CONV, FULL}:
        raise ValueError(f"{MODEL_TYPE}: layer_types must name {n} layers as "
                         f"{CONV!r} or {FULL!r}, got {types}")
    rope = dict(hf.get("rope_parameters") or {})
    theta = rope.pop("rope_theta", hf.get("rope_theta"))
    if theta is None or rope.get("rope_type", "default") != "default":
        raise _refuse(f"rope_theta {theta} under rope_parameters "
                      f"{hf.get('rope_parameters')}: the published files turn q and k "
                      "by a plain table")
    if not hf.get("use_expert_bias", True) or not hf.get("norm_topk_prob", True):
        raise _refuse("use_expert_bias or norm_topk_prob false: no published file "
                      "of this family routes without the bias or the renormalisation")
    n_dense = int(hf.get("num_dense_layers", 0))
    if not 0 <= n_dense <= n:
        raise ValueError(f"{MODEL_TYPE}: num_dense_layers {n_dense} of {n} layers")
    held = int(hf["num_experts"])
    routed = int(hf.get("num_experts_routed", held))
    first = int(hf.get("experts_held_first", 0))
    moe = MoEConfig(
        num_experts=routed,
        top_k=int(hf["num_experts_per_tok"]),
        dispatch="dropless",
        aux_loss_coef=0.0,
        expert_intermediate_dim=int(hf["moe_intermediate_size"]),
        score_func="sigmoid",
        route_norm=True, route_norm_eps=ROUTE_NORM_EPS,
        router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        n_shared_experts=0,
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    ) if n_dense < n else None
    D, Hq = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    return TransformerConfig(
        n_layers=n,
        hidden_dim=D,
        n_q_heads=Hq,
        n_kv_heads=int(hf.get("num_key_value_heads") or Hq),
        head_dim=int(hf.get("head_dim") or D // Hq),
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation="silu", mlp_type="gated",
        norm_eps=float(hf.get("norm_eps", 1e-5)),
        rotary_base=float(theta),
        qk_norm=True,
        tied_embeddings=bool(hf.get("tie_word_embeddings", True)),
        is_critic=is_critic,
        moe=moe,
        conv=ConvConfig(kernel=int(hf["conv_L_cache"]), bias=bool(hf.get("conv_bias", False))),
        layer_kinds=tuple(
            LayerKind(mlp="dense" if i < n_dense else "moe",
                      **(dict(mixer="conv") if t == CONV else {}))
            for i, t in enumerate(types)),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, kinds = cfg.moe, cfg.kinds()
    hf: Dict[str, Any] = dict(
        architectures=["Lfm2MoeForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base,
        conv_L_cache=cfg.conv.kernel, conv_bias=cfg.conv.bias,
        layer_types=[CONV if k.mixer == "conv" else FULL for k in kinds],
        num_dense_layers=sum(k.mlp == "dense" for k in kinds),
        tie_word_embeddings=cfg.tied_embeddings,
        torch_dtype="bfloat16",
    )
    if moe is not None:
        hf.update(num_experts=moe.n_held, num_experts_per_tok=moe.top_k,
                  moe_intermediate_size=moe.expert_intermediate_dim,
                  norm_topk_prob=moe.route_norm, use_expert_bias=moe.router_bias,
                  routed_scaling_factor=moe.routed_scaling_factor)
        if moe.experts_held is not None:
            hf.update(num_experts_routed=moe.num_experts,
                      experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`
# (from memory: the module's docstring); matrices are stored [out, in] there
# and [in, out] here.
_NORMS = {"ln1": "operator_norm", "ln2": "ffn_norm"}
_ATTN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "out_proj"}
_ATTN_VECS = {"q_norm": "q_layernorm", "k_norm": "k_layernorm"}
_CONV_MATS = {"in_proj": "in_proj", "out_proj": "out_proj"}
_MLP_MATS = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}


def _layer_from_hf(sd, i: int, kind: LayerKind, moe) -> Dict:
    base = f"model.layers.{i}"
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer: Dict[str, Any] = {ours: {"weight": w(f"{base}.{theirs}.weight")}
                             for ours, theirs in _NORMS.items()}
    if kind.mixer == "conv":
        at = f"{base}.conv"
        layer["conv"] = {
            **{ours: t(f"{at}.{theirs}.weight") for ours, theirs in _CONV_MATS.items()},
            # [channels, 1, taps] -> [taps, channels]
            "conv_w": np.ascontiguousarray(w(f"{at}.conv.weight")[:, 0, :].T),
        }
        if f"{at}.conv.bias" in sd:
            layer["conv"]["conv_b"] = w(f"{at}.conv.bias")
    else:
        at = f"{base}.self_attn"
        layer["attn"] = {
            **{ours: t(f"{at}.{theirs}.weight") for ours, theirs in _ATTN_MATS.items()},
            **{ours: w(f"{at}.{theirs}.weight") for ours, theirs in _ATTN_VECS.items()},
        }
    ff = f"{base}.feed_forward"
    if kind.mlp == "dense":
        layer["mlp"] = {ours: t(f"{ff}.{theirs}.weight") for ours, theirs in _MLP_MATS.items()}
        return layer
    first, held = moe.experts_held or (0, moe.num_experts)
    experts = [{ours: t(f"{ff}.experts.{e}.{theirs}.weight")
                for ours, theirs in _MLP_MATS.items()}
               for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    layer["mlp"]["router"] = t(f"{ff}.gate.weight")
    layer["mlp"]["expert_bias"] = w(f"{ff}.expert_bias")
    return layer


def _layer_to_hf(sd, i: int, lp: Dict, first: int) -> None:
    base = f"model.layers.{i}"
    put = lambda at, tree, names: sd.update(
        {f"{at}.{theirs}.weight": np.asarray(tree[ours]).T for ours, theirs in names.items()})
    for ours, theirs in _NORMS.items():
        sd[f"{base}.{theirs}.weight"] = np.asarray(lp[ours]["weight"])
    if "conv" in lp:
        at, cp = f"{base}.conv", lp["conv"]
        put(at, cp, _CONV_MATS)
        sd[f"{at}.conv.weight"] = np.asarray(cp["conv_w"]).T[:, None, :]
        if "conv_b" in cp:
            sd[f"{at}.conv.bias"] = np.asarray(cp["conv_b"])
    else:
        at, ap = f"{base}.self_attn", lp["attn"]
        put(at, ap, _ATTN_MATS)
        for ours, theirs in _ATTN_VECS.items():
            sd[f"{at}.{theirs}.weight"] = np.asarray(ap[ours])
    ff, mlp = f"{base}.feed_forward", lp["mlp"]
    if "router" not in mlp:
        put(ff, mlp, _MLP_MATS)
        return
    sd[f"{ff}.gate.weight"] = np.asarray(mlp["router"]).T
    sd[f"{ff}.expert_bias"] = np.asarray(mlp["expert_bias"])
    for ours, theirs in _MLP_MATS.items():
        for e in range(mlp[ours].shape[0]):
            sd[f"{ff}.experts.{first + e}.{theirs}.weight"] = np.asarray(mlp[ours][e]).T


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    layers = [_layer_from_hf(sd, i, k, cfg.moe) for i, k in enumerate(cfg.kinds())]
    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "final_norm": {"weight": sd["model.embedding_norm.weight"].astype(np.float32)},
    }
    for path, idx in cfg.stack_paths().values():
        _stack_at(params, path, stack_layers([layers[i] for i in idx]))
    if cfg.is_critic:
        params["head"] = {"weight": np.ascontiguousarray(
            sd["score.weight"].astype(np.float32).T) if "score.weight" in sd
            else np.zeros((cfg.hidden_dim, 1), np.float32)}
    elif not cfg.tied_embeddings:
        params["head"] = {"weight": np.ascontiguousarray(
            sd["lm_head.weight"].astype(np.float32).T)}
    return params


def _params_to_hf(params: Dict, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    from areal_tpu.models.hf import unstack_layers
    from areal_tpu.models.transformer import _stack_at

    sd = {"model.embed_tokens.weight": np.asarray(params["embedding"]["weight"]),
          "model.embedding_norm.weight": np.asarray(params["final_norm"]["weight"])}
    first = cfg.moe.experts_held[0] if cfg.moe and cfg.moe.experts_held else 0
    for path, idx in cfg.stack_paths().values():
        for i, lp in zip(idx, unstack_layers(_stack_at(params, path), len(idx))):
            _layer_to_hf(sd, i, lp, first)
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["head"]["weight"]).T
    elif not cfg.tied_embeddings:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    return sd


register_hf_family(
    MODEL_TYPE,
    HFFamily(
        name=MODEL_TYPE,
        hf_model_type=MODEL_TYPE,
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    ),
)
