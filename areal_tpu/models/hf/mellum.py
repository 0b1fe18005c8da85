"""Mellum 2 (JetBrains), HF conversion: `model_type: mellum`.

A stack whose layers differ in their attention alone, all of it from the
published config's keys: `layer_types` says which layers attend through a
window of `sliding_window` positions ("sliding_attention": a token and
the `sliding_window - 1` before it) and which to their whole sequence
("full_attention"); `max_window_layers` and `use_sliding_window` are not
consulted. **`rope_parameters` is keyed by layer type**: each kind of
layer turns q and k by a table of its own (`models/config.RotarySet`, one
a key, under the key's name): `rope_type` "default" at `rope_theta`, or
"yarn" (`factor` over `original_max_position_embeddings`, `beta_fast`,
`beta_slow`, `truncate`) whose `cos` and `sin` carry the
`attention_factor` (stated, or HF's `_compute_yarn_parameters`' default:
`ops/rotary.yarn_attention_factor`). Every layer: GQA attention with the
qwen3 family's RMSNorm over each q and k head before the rotation, no
bias; then an expert layer (`mlp_layer_types` all "sparse":
`intermediate_size` is used by no layer): `num_experts` experts of
`moe_intermediate_size`, softmax over all of them in float32,
`num_experts_per_tok` chosen, renormalised (`norm_topk_prob`), no shared
expert, no scaling factor. No prediction module: the config has no key
for one.

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `num_experts` counts only
the experts whose weights are here) and `experts_held_first`.

The checkpoint's tensor names are the qwen3-moe layout's (`self_attn.
q_proj` .. `o_proj`, `q_norm`, `k_norm`, `mlp.gate`, `mlp.experts.{e}.*`),
written from memory of that release: the catalog gives the config only.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import LayerKind, MoEConfig, RotarySet, TransformerConfig
from areal_tpu.models.hf import HFFamily
from areal_tpu.ops.rotary import yarn_attention_factor

MODEL_TYPE = "mellum"
SLIDING, FULL = "sliding_attention", "full_attention"
_YARN_KEYS = ("original_max_position_embeddings", "beta_fast", "beta_slow", "truncate",
              "mscale", "mscale_all_dim")


def _refuse(what: str) -> NotImplementedError:
    return NotImplementedError(f"{MODEL_TYPE}: {what}")


def _rotary_set(name: str, rp: Dict[str, Any]) -> RotarySet:
    kind = rp.get("rope_type", rp.get("type", "default"))
    base = float(rp["rope_theta"])
    if kind == "default":
        return RotarySet(base=base)
    if kind != "yarn":
        raise _refuse(f"rope_parameters[{name!r}] rope_type {kind!r}: the tables here "
                      "are 'default' and 'yarn'")
    factor = float(rp["factor"])
    params = {k: rp[k] for k in _YARN_KEYS if rp.get(k) is not None}
    return RotarySet(base=base, scaling=factor, scaling_type="yarn", scaling_params=params,
                     attention_factor=yarn_attention_factor(factor, rp))


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    n = int(hf["num_hidden_layers"])
    types = list(hf["layer_types"])
    if len(types) != n or any(t not in (SLIDING, FULL) for t in types):
        raise ValueError(f"{MODEL_TYPE}: layer_types must name {n} layers as "
                         f"{SLIDING!r} or {FULL!r}, got {types}")
    mlps = list(hf.get("mlp_layer_types") or ["sparse"] * n)
    if len(mlps) != n or set(mlps) != {"sparse"}:
        raise _refuse(f"mlp_layer_types {sorted(set(mlps))} over {len(mlps)} layers: an "
                      f"expert layer in each of the {n} layers (the published model "
                      "has no dense layer, and intermediate_size says nothing of how "
                      "one would be gated)")
    rope = hf.get("rope_parameters") or {}
    if not set(types) <= set(rope):
        raise _refuse(f"rope_parameters {sorted(rope)} must hold a set for every layer "
                      f"type in use, {sorted(set(types))}")
    if float(hf.get("partial_rotary_factor", 1.0)) != 1.0:
        raise _refuse("partial_rotary_factor: the tables here turn the whole head")
    window = int(hf["sliding_window"])
    sets = {t: _rotary_set(t, rope[t]) for t in (SLIDING, FULL) if t in set(types)}
    held = int(hf["num_experts"])
    routed = int(hf.get("num_experts_routed", held))
    first = int(hf.get("experts_held_first", 0))
    moe = MoEConfig(
        num_experts=routed,
        top_k=int(hf["num_experts_per_tok"]),
        dispatch="dropless",
        aux_loss_coef=0.0,
        expert_intermediate_dim=int(hf["moe_intermediate_size"]),
        score_func="softmax",
        route_norm=bool(hf.get("norm_topk_prob", True)),
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    )
    if not moe.route_norm:
        raise _refuse("norm_topk_prob false: models/moe.py's softmax router "
                      "renormalises the chosen gates")
    return TransformerConfig(
        n_layers=n,
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation="silu", mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rotary_base=float(rope[types[0]]["rope_theta"]),
        rotary_sets=sets,
        attn_bias=bool(hf.get("attention_bias", False)),
        qk_norm=True,
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe,
        layer_kinds=tuple(
            LayerKind(mlp="moe", window=window if t == SLIDING else None, rotary_set=t)
            for t in types),
    )


def _rope_parameters(rs: RotarySet) -> Dict[str, Any]:
    if rs.scaling_type is None:
        return dict(rope_type="default", rope_theta=rs.base)
    return dict(rope_type=rs.scaling_type, rope_theta=rs.base, factor=rs.scaling,
                **(rs.scaling_params or {}), attention_factor=rs.attention_factor)


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, kinds = cfg.moe, cfg.kinds()
    windows = {k.window for k in kinds if k.window is not None}
    hf: Dict[str, Any] = dict(
        architectures=["MellumForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act="silu",
        rms_norm_eps=cfg.norm_eps,
        attention_bias=cfg.attn_bias,
        tie_word_embeddings=cfg.tied_embeddings,
        layer_types=[k.rotary_set for k in kinds],
        mlp_layer_types=["sparse"] * cfg.n_layers,
        rope_parameters={name: _rope_parameters(rs) for name, rs in cfg.rotary_sets.items()},
        sliding_window=windows.pop() if windows else cfg.max_position_embeddings,
        use_sliding_window=True, max_window_layers=0,
        num_experts=moe.n_held,
        num_experts_per_tok=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        norm_topk_prob=moe.route_norm,
        torch_dtype="bfloat16",
    )
    if moe.experts_held is not None:
        hf.update(num_experts_routed=moe.num_experts,
                  experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`;
# matrices are stored [out, in] there and [in, out] here.
_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}
_ATTN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj"}
_ATTN_NORMS = {"q_norm": "q_norm", "k_norm": "k_norm"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _layer_from_hf(sd, base: str, moe: MoEConfig) -> Dict:
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight")}
             for ours, theirs in _NORMS.items()}
    layer["attn"] = {
        **{ours: t(f"{base}.self_attn.{theirs}.weight") for ours, theirs in _ATTN_MATS.items()},
        **{ours: w(f"{base}.self_attn.{theirs}.weight") for ours, theirs in _ATTN_NORMS.items()},
    }
    first, held = moe.experts_held or (0, moe.num_experts)
    experts = [{ours: t(f"{base}.mlp.experts.{e}.{theirs}.weight")
                for ours, theirs in _MLP_MATS.items()}
               for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    layer["mlp"]["router"] = t(f"{base}.mlp.gate.weight")
    return layer


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers

    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "layers": stack_layers([_layer_from_hf(sd, f"model.layers.{i}", cfg.moe)
                                for i in range(cfg.n_layers)]),
        "final_norm": {"weight": sd["model.norm.weight"].astype(np.float32)},
    }
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

    sd = {"model.embed_tokens.weight": np.asarray(params["embedding"]["weight"]),
          "model.norm.weight": np.asarray(params["final_norm"]["weight"])}
    first = cfg.moe.experts_held[0] if cfg.moe.experts_held else 0
    for i, lp in enumerate(unstack_layers(params["layers"], cfg.n_layers)):
        base = f"model.layers.{i}"
        for ours, theirs in _NORMS.items():
            sd[f"{base}.{theirs}.weight"] = lp[ours]["weight"]
        for ours, theirs in _ATTN_MATS.items():
            sd[f"{base}.self_attn.{theirs}.weight"] = lp["attn"][ours].T
        for ours, theirs in _ATTN_NORMS.items():
            sd[f"{base}.self_attn.{theirs}.weight"] = lp["attn"][ours]
        sd[f"{base}.mlp.gate.weight"] = lp["mlp"]["router"].T
        for ours, theirs in _MLP_MATS.items():
            for e in range(lp["mlp"][ours].shape[0]):
                sd[f"{base}.mlp.experts.{first + e}.{theirs}.weight"] = lp["mlp"][ours][e].T
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
