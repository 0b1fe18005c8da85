"""Nemotron-H HF conversion: `model_type: nemotron_h`.

A stack whose layers have one part each, under one RMSNorm with a
residual around it, all of it from the published config's keys:
`hybrid_override_pattern` names each layer by a letter, `M` a Mamba-2
state-space mixer (`mamba_num_heads` heads of `mamba_head_dim`, state
`ssm_state_size`, `n_groups` groups, a convolution of `conv_kernel` taps,
chunks of `chunk_size`), `*` attention (`num_attention_heads` /
`num_key_value_heads` heads of `head_dim`, no position encoding), `E` an
expert layer (`n_routed_experts` experts of `moe_intermediate_size`,
`num_experts_per_tok` a token chosen on sigmoid score +
`e_score_correction_bias`, weighted by the bare scores normalised
(`norm_topk_prob`) and scaled (`routed_scaling_factor`), plus a shared
expert of `moe_shared_expert_intermediate_size`) and `-` a dense MLP of
`intermediate_size`. Every MLP, routed, shared or dense, is plain:
`act(x W_up) W_down` with `mlp_hidden_act` (relu2, the squared ReLU).

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `n_routed_experts` counts
only the experts whose weights are here) and `experts_held_first` (the
first of them). Without them the layer holds all `n_routed_experts`.

`e_score_correction_bias` is a buffer that pre-training balances and no
RL step updates (`expert_bias` of engine/jax_engine.py BUFFER_LEAVES).
The checkpoint's tensor names below are written from memory of the
published modelling code (the catalog gives the config only):
`backbone.embeddings`, `backbone.layers.{i}.norm`, `.mixer.{in_proj,
conv1d, dt_bias, A_log, D, norm, out_proj}`, `.mixer.{q,k,v,o}_proj`,
`.mixer.{up,down}_proj`, `.mixer.gate.{weight,e_score_correction_bias}`,
`.mixer.experts.{e}.*`, `.mixer.shared_experts.*`, `backbone.norm_f`,
`lm_head`. Its `conv1d.weight` is [channels, 1, taps]; ours is
[taps, channels].
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import LayerKind, MoEConfig, SSMConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily

KINDS = {
    "M": LayerKind(mlp=None, mixer="ssm"),
    "*": LayerKind(mlp=None, mixer="attention", rotary=False),
    "E": LayerKind(mlp="moe", mixer=None),
    "-": LayerKind(mlp="dense", mixer=None),
}
LETTERS = {k.parts: letter for letter, k in KINDS.items()}


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set(KINDS):
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern must name "
            f"{hf['num_hidden_layers']} layers by {sorted(KINDS)}, got {pattern!r}")
    for key in ("n_group", "topk_group"):
        if hf.get(key, 1) not in (None, 1):
            raise NotImplementedError(
                f"nemotron_h: {key}={hf[key]}: group-limited routing is not in "
                "models/moe.py's router")
    if hf.get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"nemotron_h: mamba_hidden_act={hf['mamba_hidden_act']!r}: "
            "ops/ssm.py gates and convolves under silu")
    if hf.get("mamba_proj_bias"):
        raise NotImplementedError(
            "nemotron_h: mamba_proj_bias: ops/ssm.py's two projections have no bias")
    if hf.get("mlp_hidden_act", "relu2") not in ("relu2", "silu", "gelu"):
        raise NotImplementedError(
            f"nemotron_h: mlp_hidden_act={hf['mlp_hidden_act']!r}")
    moe = ssm = None
    if "E" in pattern:
        held = int(hf["n_routed_experts"])
        routed = int(hf.get("num_experts_routed", held))
        first = int(hf.get("experts_held_first", 0))
        width = int(hf["moe_intermediate_size"])
        moe = MoEConfig(
            num_experts=routed,
            top_k=int(hf["num_experts_per_tok"]),
            dispatch="dropless",
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            aux_loss_coef=0.0,
            expert_intermediate_dim=width,
            score_func="sigmoid",
            route_norm=bool(hf.get("norm_topk_prob", True)),
            router_bias=True,
            n_shared_experts=int(hf.get("n_shared_experts", 0)),
            shared_intermediate_dim=int(hf.get(
                "moe_shared_expert_intermediate_size",
                width * int(hf.get("n_shared_experts", 0)))) or None,
            experts_held=(first, held) if (first, held) != (0, routed) else None,
        )
    if "M" in pattern:
        ssm = SSMConfig(
            n_heads=int(hf["mamba_num_heads"]),
            head_dim=int(hf["mamba_head_dim"]),
            n_groups=int(hf["n_groups"]),
            state_dim=int(hf["ssm_state_size"]),
            conv_kernel=int(hf["conv_kernel"]),
            chunk_size=int(hf["chunk_size"]),
            conv_bias=bool(hf.get("use_conv_bias", True)),
            dt_min=float(hf.get("time_step_min", 0.001)),
            dt_max=float(hf.get("time_step_max", 0.1)),
            dt_floor=float(hf.get("time_step_floor", 1e-4)),
        )
    return TransformerConfig(
        n_layers=int(hf["num_hidden_layers"]),
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation=hf.get("mlp_hidden_act", "relu2"),
        mlp_type="plain",
        norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
        rotary_base=float(hf.get("rope_theta", 10000.0)),  # no layer rotates
        attn_bias=bool(hf.get("attention_bias", False)),
        mlp_bias=bool(hf.get("mlp_bias", False)),
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe, ssm=ssm,
        layer_kinds=tuple(KINDS[c] for c in pattern),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, ssm = cfg.moe, cfg.ssm
    hf: Dict[str, Any] = dict(
        architectures=["NemotronHForCausalLM"],
        model_type="nemotron_h",
        num_hidden_layers=cfg.n_layers,
        hybrid_override_pattern="".join(LETTERS[k.parts] for k in cfg.kinds()),
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        mlp_hidden_act=cfg.activation,
        layer_norm_epsilon=cfg.norm_eps, norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base,
        attention_bias=cfg.attn_bias, mlp_bias=cfg.mlp_bias,
        tie_word_embeddings=cfg.tied_embeddings,
    )
    if moe is not None:
        hf.update(
            n_routed_experts=moe.n_held,
            num_experts_per_tok=moe.top_k,
            moe_intermediate_size=moe.expert_intermediate_dim,
            n_shared_experts=moe.n_shared_experts,
            routed_scaling_factor=moe.routed_scaling_factor,
            norm_topk_prob=moe.route_norm,
            n_group=1, topk_group=1,
        )
        if moe.n_shared_experts:
            hf["moe_shared_expert_intermediate_size"] = (
                moe.shared_intermediate_dim
                or moe.expert_intermediate_dim * moe.n_shared_experts)
        if moe.experts_held is not None:
            hf.update(num_experts_routed=moe.num_experts,
                      experts_held_first=moe.experts_held[0])
    if ssm is not None:
        hf.update(
            mamba_num_heads=ssm.n_heads, mamba_head_dim=ssm.head_dim,
            n_groups=ssm.n_groups, ssm_state_size=ssm.state_dim,
            conv_kernel=ssm.conv_kernel, chunk_size=ssm.chunk_size,
            use_conv_bias=ssm.conv_bias, mamba_proj_bias=False,
            mamba_hidden_act="silu", time_step_min=ssm.dt_min,
            time_step_max=ssm.dt_max, time_step_floor=ssm.dt_floor,
        )
    return hf


# our leaf -> the checkpoint's name under `backbone.layers.{i}.mixer.`;
# matrices are stored [out, in] there and [in, out] here.
_ATTN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj"}
_MLP_MATS = {"w_in": "up_proj", "w_out": "down_proj"}
_SSM_MATS = {"in_proj": "in_proj", "out_proj": "out_proj"}
_SSM_VECS = {"A_log": "A_log", "D": "D", "dt_bias": "dt_bias",
             "norm": "norm.weight", "conv_b": "conv1d.bias"}


def _layer_from_hf(sd, i: int, kind: LayerKind, cfg: TransformerConfig) -> Dict:
    base = f"backbone.layers.{i}"
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    mats = lambda prefix, names: {
        ours: t(f"{prefix}.{theirs}.weight") for ours, theirs in names.items()}
    norm = {"weight": w(f"{base}.norm.weight")}
    if kind.mixer == "ssm":
        ssm = mats(f"{base}.mixer", _SSM_MATS)
        ssm.update({ours: w(f"{base}.mixer.{theirs}")
                    for ours, theirs in _SSM_VECS.items()
                    if ours != "conv_b" or cfg.ssm.conv_bias})
        ssm["conv_w"] = t(f"{base}.mixer.conv1d.weight")[:, 0, :]  # [K, 1, C] -> [K, C]
        return {"ln1": norm, "ssm": ssm}
    if kind.mixer == "attention":
        return {"ln1": norm, "attn": mats(f"{base}.mixer", _ATTN_MATS)}
    if kind.mlp == "dense":
        return {"ln2": norm, "mlp": mats(f"{base}.mixer", _MLP_MATS)}
    moe = cfg.moe
    first, held = moe.experts_held or (0, moe.num_experts)
    experts = [mats(f"{base}.mixer.experts.{e}", _MLP_MATS)
               for e in range(first, first + held)]
    mlp = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    mlp["router"] = t(f"{base}.mixer.gate.weight")
    mlp["expert_bias"] = w(f"{base}.mixer.gate.e_score_correction_bias")
    if moe.n_shared_experts:
        mlp["shared"] = mats(f"{base}.mixer.shared_experts", _MLP_MATS)
    return {"ln2": norm, "mlp": mlp}


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    kinds = cfg.kinds()
    layers = [_layer_from_hf(sd, i, k, cfg) for i, k in enumerate(kinds)]
    params = {
        "embedding": {"weight": sd["backbone.embeddings.weight"].astype(np.float32)},
        "final_norm": {"weight": sd["backbone.norm_f.weight"].astype(np.float32)},
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

    sd = {"backbone.embeddings.weight": np.asarray(params["embedding"]["weight"]),
          "backbone.norm_f.weight": np.asarray(params["final_norm"]["weight"])}
    first = cfg.moe.experts_held[0] if cfg.moe and cfg.moe.experts_held else 0
    for path, idx in cfg.stack_paths().values():
        for i, lp in zip(idx, unstack_layers(_stack_at(params, path), len(idx))):
            base = f"backbone.layers.{i}"
            sd[f"{base}.norm.weight"] = (lp.get("ln1") or lp["ln2"])["weight"]
            put = lambda prefix, tree, names: sd.update(
                {f"{prefix}.{theirs}.weight": tree[ours].T
                 for ours, theirs in names.items()})
            if "ssm" in lp:
                put(f"{base}.mixer", lp["ssm"], _SSM_MATS)
                for ours, theirs in _SSM_VECS.items():
                    if ours in lp["ssm"]:
                        sd[f"{base}.mixer.{theirs}"] = lp["ssm"][ours]
                sd[f"{base}.mixer.conv1d.weight"] = lp["ssm"]["conv_w"].T[:, None, :]
            elif "attn" in lp:
                put(f"{base}.mixer", lp["attn"], _ATTN_MATS)
            elif "router" not in lp["mlp"]:
                put(f"{base}.mixer", lp["mlp"], _MLP_MATS)
            else:
                mlp = lp["mlp"]
                sd[f"{base}.mixer.gate.weight"] = mlp["router"].T
                sd[f"{base}.mixer.gate.e_score_correction_bias"] = mlp["expert_bias"]
                for e in range(mlp["w_in"].shape[0]):
                    put(f"{base}.mixer.experts.{first + e}",
                        {k: mlp[k][e] for k in _MLP_MATS}, _MLP_MATS)
                if "shared" in mlp:
                    put(f"{base}.mixer.shared_experts", mlp["shared"], _MLP_MATS)
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["head"]["weight"]).T
    elif not cfg.tied_embeddings:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    return sd


register_hf_family(
    "nemotron_h",
    HFFamily(
        name="nemotron_h",
        hf_model_type="nemotron_h",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    ),
)
