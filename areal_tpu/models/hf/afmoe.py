"""AFMoE (Arcee Trinity) HF conversion: `model_type: afmoe`.

A stack whose layers differ in kind, all of it from the published
config's keys: `layer_types` says which layers attend through a window
of `sliding_window` positions with rotary q/k ("sliding_attention") and
which attend to their whole sequence with no position encoding at all
("full_attention"); the first `num_dense_layers` have a dense SwiGLU of
width `intermediate_size`, the rest a sigmoid-routed expert layer
(`num_experts` experts of width `moe_intermediate_size`, `num_experts_per_tok`
a token chosen on score + `expert_bias`, weighted by the bare scores
normalised (`route_norm`) and scaled (`route_scale`), plus
`num_shared_experts` shared). Every layer has four RMSNorms, per-head
RMSNorm on q and k, and a sigmoid output gate on the attention; the
embedding is scaled by sqrt(hidden) under `mup_enabled`.

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `num_experts` counts only
the experts whose weights are here) and `experts_held_first` (the first
of them). Without them the layer holds all `num_experts`.

`load_balance_coeff` is a pre-training term: `aux_loss_coef` is 0 here,
and `expert_bias` is a buffer that no RL step updates
(engine/jax_engine.py BUFFER_LEAVES). The checkpoint's tensor names below
are written from memory of the published modelling code (the catalog
gives the config only): `self_attn.gate_proj`, `self_attn.{q,k}_norm`,
`{input,post_attention,pre_mlp,post_mlp}_layernorm`, `mlp.router.gate`,
`mlp.expert_bias`, `mlp.shared_experts.*`, `mlp.experts.{e}.*`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import LayerKind, MoEConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily
from areal_tpu.models.hf.llama import (
    _config_from_hf as llama_config_from_hf,
    _config_to_hf as llama_config_to_hf,
)

SLIDING, FULL = "sliding_attention", "full_attention"


def _layer_types(hf: Dict[str, Any]) -> List[str]:
    n = hf["num_hidden_layers"]
    types = hf.get("layer_types")
    if types is None:
        every = hf.get("global_attn_every_n_layers") or 0
        types = [FULL if every and (i + 1) % every == 0 else SLIDING
                 for i in range(n)]
    if len(types) != n or any(t not in (SLIDING, FULL) for t in types):
        raise ValueError(
            f"afmoe: layer_types must name {n} layers as {SLIDING!r} or "
            f"{FULL!r}, got {types}"
        )
    return list(types)


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if hf.get(key, 1) not in (None, 1):
            raise NotImplementedError(
                f"afmoe: {key}={hf[key]}: group-limited routing is not in "
                "models/moe.py's router"
            )
    n_dense = int(hf.get("num_dense_layers", 0))
    window = int(hf["sliding_window"])
    kinds = tuple(
        LayerKind(mlp="dense" if i < n_dense else "moe",
                  window=window if t == SLIDING else None, rotary=t == SLIDING)
        for i, t in enumerate(_layer_types(hf))
    )
    held = int(hf["num_experts"])
    routed = int(hf.get("num_experts_routed", held))
    first = int(hf.get("experts_held_first", 0))
    moe = MoEConfig(
        num_experts=routed,
        top_k=int(hf["num_experts_per_tok"]),
        dispatch="dropless",
        routed_scaling_factor=float(hf.get("route_scale", 1.0)),
        aux_loss_coef=0.0,
        expert_intermediate_dim=int(hf["moe_intermediate_size"]),
        first_k_dense=n_dense,
        score_func=hf.get("score_func", "sigmoid"),
        route_norm=bool(hf.get("route_norm", True)),
        router_bias=True,
        n_shared_experts=int(hf.get("num_shared_experts", 0)),
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    )
    return dataclasses.replace(
        llama_config_from_hf(hf, is_critic),
        attn_bias=False, qk_norm=True, attn_gate=True, post_norms=True,
        embedding_multiplier=(
            math.sqrt(hf["hidden_size"]) if hf.get("mup_enabled") else None),
        layer_kinds=kinds, moe=moe,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    hf = llama_config_to_hf(cfg)
    moe, kinds = cfg.moe, cfg.kinds()
    windows = {k.window for k in kinds if k.window is not None}
    hf.update(
        architectures=["AfmoeForCausalLM"],
        model_type="afmoe",
        layer_types=[FULL if k.window is None else SLIDING for k in kinds],
        sliding_window=windows.pop() if windows else cfg.max_position_embeddings,
        num_dense_layers=sum(k.mlp == "dense" for k in kinds),
        num_experts=moe.n_held,
        num_experts_per_tok=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        num_shared_experts=moe.n_shared_experts,
        score_func=moe.score_func,
        route_norm=moe.route_norm,
        route_scale=moe.routed_scaling_factor,
        mup_enabled=bool(cfg.embedding_multiplier),
        n_group=1, topk_group=1,
    )
    hf.pop("attention_bias", None)
    if moe.experts_held is not None:
        hf.update(num_experts_routed=moe.num_experts,
                  experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`;
# matrices are stored [out, in] there and [in, out] here.
_NORMS = {"ln1": "input_layernorm", "ln1_post": "post_attention_layernorm",
          "ln2": "pre_mlp_layernorm", "ln2_post": "post_mlp_layernorm"}
_ATTN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
              "wg": "gate_proj"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _layer_from_hf(sd, i: int, kind: LayerKind, moe: MoEConfig) -> Dict:
    base = f"model.layers.{i}"
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight")}
             for ours, theirs in _NORMS.items()}
    layer["attn"] = {ours: t(f"{base}.self_attn.{theirs}.weight")
                     for ours, theirs in _ATTN_MATS.items()}
    layer["attn"]["q_norm"] = w(f"{base}.self_attn.q_norm.weight")
    layer["attn"]["k_norm"] = w(f"{base}.self_attn.k_norm.weight")
    mats = lambda prefix: {ours: t(f"{prefix}.{theirs}.weight")
                           for ours, theirs in _MLP_MATS.items()}
    if kind.mlp == "dense":
        layer["mlp"] = mats(f"{base}.mlp")
        return layer
    first, held = moe.experts_held or (0, moe.num_experts)
    experts = [mats(f"{base}.mlp.experts.{e}") for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    layer["mlp"]["router"] = t(f"{base}.mlp.router.gate.weight")
    layer["mlp"]["expert_bias"] = w(f"{base}.mlp.expert_bias")
    if moe.n_shared_experts:
        layer["mlp"]["shared"] = mats(f"{base}.mlp.shared_experts")
    return layer


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers

    kinds, n_lead = cfg.kinds(), cfg.n_lead_layers
    layers = [_layer_from_hf(sd, i, k, cfg.moe) for i, k in enumerate(kinds)]
    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "layers": stack_layers(layers[n_lead:]),
        "final_norm": {"weight": sd["model.norm.weight"].astype(np.float32)},
    }
    if n_lead:
        params["lead_layers"] = stack_layers(layers[:n_lead])
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

    n_lead = cfg.n_lead_layers
    layers = (unstack_layers(params["lead_layers"], n_lead) if n_lead else []) + \
        unstack_layers(params["layers"], cfg.n_layers - n_lead)
    sd = {"model.embed_tokens.weight": np.asarray(params["embedding"]["weight"]),
          "model.norm.weight": np.asarray(params["final_norm"]["weight"])}
    first = cfg.moe.experts_held[0] if cfg.moe.experts_held else 0
    for i, lp in enumerate(layers):
        base = f"model.layers.{i}"
        for ours, theirs in _NORMS.items():
            sd[f"{base}.{theirs}.weight"] = lp[ours]["weight"]
        for ours, theirs in _ATTN_MATS.items():
            sd[f"{base}.self_attn.{theirs}.weight"] = lp["attn"][ours].T
        sd[f"{base}.self_attn.q_norm.weight"] = lp["attn"]["q_norm"]
        sd[f"{base}.self_attn.k_norm.weight"] = lp["attn"]["k_norm"]
        mlp = lp["mlp"]
        if "router" not in mlp:
            for ours, theirs in _MLP_MATS.items():
                sd[f"{base}.mlp.{theirs}.weight"] = mlp[ours].T
            continue
        sd[f"{base}.mlp.router.gate.weight"] = mlp["router"].T
        sd[f"{base}.mlp.expert_bias"] = mlp["expert_bias"]
        for ours, theirs in _MLP_MATS.items():
            for e in range(mlp[ours].shape[0]):
                sd[f"{base}.mlp.experts.{first + e}.{theirs}.weight"] = mlp[ours][e].T
            if "shared" in mlp:
                sd[f"{base}.mlp.shared_experts.{theirs}.weight"] = mlp["shared"][ours].T
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["head"]["weight"]).T
    elif not cfg.tied_embeddings:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    return sd


register_hf_family(
    "afmoe",
    HFFamily(
        name="afmoe",
        hf_model_type="afmoe",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    ),
)
