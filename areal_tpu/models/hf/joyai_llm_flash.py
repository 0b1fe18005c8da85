"""JoyAI-LLM-Flash HF conversion: `model_type: joyai_llm_flash`, a stack
of DeepSeek-V3's shape.

Every layer attends through latent attention (`models/config.MLAConfig`:
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`; the config's `head_dim` is the rope part again), rotary
interleaved over the rope part alone (`rope_interleave`), its table
plain (`rope_scaling` null, the published file's) or YaRN's
(`_rope_scaling`: DeepSeek-V3's, the softmax scale times `mscale^2`). The
first `first_k_dense_replace` layers have a dense SwiGLU of
`intermediate_size`, the rest (`moe_layer_freq` 1) a sigmoid-routed
expert layer: `n_routed_experts` experts of `moe_intermediate_size`,
`num_experts_per_tok` a token chosen on score + `e_score_correction_bias`
(`topk_method` noaux_tc, one group), weighted by the bare scores
normalised (`norm_topk_prob`) times `routed_scaling_factor`, plus
`n_shared_experts` shared. After the stack, `num_nextn_predict_layers`
multi-token-prediction modules (`models/config.MTPConfig`; one).

Three keys are this repo's, not the published file's: `num_experts_routed`
and `experts_held_first` for one chip's share of an expert-parallel layer
(models/moe.py `experts_held`: `n_routed_experts` then counts the experts
whose weights are here), and `mtp_loss_weight`, what a training step gives
the prediction module's loss (0.1 where absent, DeepSeek-V3's later
value; 0 = the step skips the module).

The checkpoint's tensor names are DeepSeek-V3's, written from memory of
that release (the catalog gives the config only): `self_attn.q_a_proj`,
`q_a_layernorm`, `q_b_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`,
`kv_b_proj`, `o_proj`; `mlp.gate.weight`, `mlp.gate.e_score_correction_bias`,
`mlp.experts.{e}.*`, `mlp.shared_experts.*`; the prediction module is
`model.layers.{num_hidden_layers}`: a block's names and `enorm`, `hnorm`,
`eh_proj`, `shared_head.norm`, and copies of the model's `embed_tokens` and
`shared_head.head`, which are written out and not read back (the module
shares both with the model).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import MLAConfig, MoEConfig, MTPConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "joyai_llm_flash"


def _rope_scaling(hf: Dict[str, Any], family: str):
    """`rope_scaling` of a DeepSeek-V3-shaped config -> (TransformerConfig's
    three rotary-scaling fields, the softmax scale's factor): none and 1
    for a plain table; YaRN (`ops/rotary.py`) over the rope part with the
    scale times `mscale(factor, mscale_all_dim)^2`. cos and sin carry
    `mscale(factor, mscale) / mscale(factor, mscale_all_dim)`, which is 1
    in every published config of this shape; another ratio is refused."""
    from areal_tpu.ops.rotary import yarn_mscale

    rs = hf.get("rope_scaling")
    if not rs:
        return {}, 1.0
    kind = rs.get("type", rs.get("rope_type"))
    if kind != "yarn":
        raise NotImplementedError(
            f"{family}: rope_scaling type {kind!r}: latent attention's rope "
            "part has a plain table or YaRN's (ops/rotary.py), no other")
    if rs.get("mscale", 1.0) != rs.get("mscale_all_dim", 0.0):
        raise NotImplementedError(
            f"{family}: rope_scaling mscale {rs.get('mscale')} != mscale_all_dim "
            f"{rs.get('mscale_all_dim')}: cos and sin would carry their ratio, which "
            "ops/rotary.py's tables do not")
    factor = float(rs["factor"])
    params = {k: v for k, v in rs.items() if k not in ("type", "rope_type", "factor")}
    return (dict(rotary_scaling=factor, rotary_scaling_type="yarn",
                 rotary_scaling_params=params),
            yarn_mscale(factor, float(rs["mscale_all_dim"])) ** 2)


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False,
                    family: str = MODEL_TYPE) -> TransformerConfig:
    for key in ("n_group", "topk_group"):
        if hf.get(key, 1) not in (None, 1):
            raise NotImplementedError(
                f"{family}: {key}={hf[key]}: group-limited routing is not "
                "in models/moe.py's router")
    scaling, scale_factor = _rope_scaling(hf, family)
    if hf.get("moe_layer_freq", 1) != 1 or hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(
            f"{family}: an expert layer every layer after the dense ones, "
            "sigmoid scores: got moe_layer_freq "
            f"{hf.get('moe_layer_freq')}, scoring_func {hf.get('scoring_func')!r}")
    if not hf.get("q_lora_rank"):
        raise NotImplementedError(
            f"{family}: q_lora_rank is absent: models/transformer.py's latent "
            "block takes a full-rank q (MLAConfig.q_rank None, as the kimi_linear "
            "family has it), but this family's checkpoint names and its round trip "
            "are written for the low-rank pair every published config of it has")
    mla = MLAConfig(
        q_rank=int(hf["q_lora_rank"]), kv_rank=int(hf["kv_lora_rank"]),
        nope_dim=int(hf["qk_nope_head_dim"]), rope_dim=int(hf["qk_rope_head_dim"]),
        v_dim=int(hf["v_head_dim"]), softmax_scale_factor=scale_factor)
    heads = int(hf["num_attention_heads"])
    if int(hf.get("num_key_value_heads", heads)) != heads:
        raise ValueError(f"{family}: latent attention has k and v a head")
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
        first_k_dense=int(hf.get("first_k_dense_replace", 0)),
        score_func="sigmoid",
        route_norm=bool(hf.get("norm_topk_prob", True)),
        router_bias=True,
        n_shared_experts=int(hf.get("n_shared_experts", 0)),
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    )
    n_mtp = int(hf.get("num_nextn_predict_layers", 0))
    return TransformerConfig(
        n_layers=int(hf["num_hidden_layers"]),
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=heads, n_kv_heads=heads,
        head_dim=mla.qk_dim,
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation="silu", mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rotary_base=float(hf.get("rope_theta", 10000.0)),
        rotary_interleaved=bool(hf.get("rope_interleave", True)),
        **scaling,
        attn_bias=bool(hf.get("attention_bias", False)),
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe, mla=mla,
        mtp=None if is_critic or not n_mtp else MTPConfig(
            n_modules=n_mtp, loss_weight=float(hf.get("mtp_loss_weight", 0.1))),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, mla = cfg.moe, cfg.mla
    hf: Dict[str, Any] = dict(
        architectures=["JoyAILLMFlashForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=mla.rope_dim,
        q_lora_rank=mla.q_rank, kv_lora_rank=mla.kv_rank,
        qk_nope_head_dim=mla.nope_dim, qk_rope_head_dim=mla.rope_dim,
        qk_head_dim=mla.qk_dim, v_head_dim=mla.v_dim,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act="silu",
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base, rope_interleave=cfg.rotary_interleaved,
        rope_scaling=None if cfg.rotary_scaling_type != "yarn" else dict(
            cfg.rotary_scaling_params, factor=cfg.rotary_scaling, type="yarn"),
        attention_bias=cfg.attn_bias,
        tie_word_embeddings=cfg.tied_embeddings,
        first_k_dense_replace=moe.first_k_dense, moe_layer_freq=1,
        n_routed_experts=moe.n_held,
        num_experts_per_tok=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        n_shared_experts=moe.n_shared_experts,
        scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
        norm_topk_prob=moe.route_norm,
        routed_scaling_factor=moe.routed_scaling_factor,
        num_nextn_predict_layers=cfg.mtp.n_modules if cfg.mtp else 0,
        torch_dtype="bfloat16",
    )
    if cfg.mtp is not None:
        hf["mtp_loss_weight"] = cfg.mtp.loss_weight
    if moe.experts_held is not None:
        hf.update(num_experts_routed=moe.num_experts,
                  experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`;
# matrices are stored [out, in] there and [in, out] here.
_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}
_ATTN_MATS = {"wq_a": "q_a_proj", "wq_b": "q_b_proj", "wkv_a": "kv_a_proj_with_mqa",
              "wkv_b": "kv_b_proj", "wo": "o_proj"}
_ATTN_NORMS = {"q_a_norm": "q_a_layernorm", "kv_a_norm": "kv_a_layernorm"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
# the prediction module's own leaves -> names under its `model.layers.{n}.`
_MTP = {"enorm": "enorm", "hnorm": "hnorm", "norm": "shared_head.norm"}


def _layer_from_hf(sd, base: str, expert_layer: bool, moe: MoEConfig) -> Dict:
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight")}
             for ours, theirs in _NORMS.items()}
    layer["attn"] = {ours: t(f"{base}.self_attn.{theirs}.weight")
                     for ours, theirs in _ATTN_MATS.items()}
    layer["attn"].update({ours: w(f"{base}.self_attn.{theirs}.weight")
                          for ours, theirs in _ATTN_NORMS.items()})
    mats = lambda prefix: {ours: t(f"{prefix}.{theirs}.weight")
                           for ours, theirs in _MLP_MATS.items()}
    if not expert_layer:
        layer["mlp"] = mats(f"{base}.mlp")
        return layer
    first, held = moe.experts_held or (0, moe.num_experts)
    experts = [mats(f"{base}.mlp.experts.{e}") for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    layer["mlp"]["router"] = t(f"{base}.mlp.gate.weight")
    layer["mlp"]["expert_bias"] = w(f"{base}.mlp.gate.e_score_correction_bias")
    if moe.n_shared_experts:
        layer["mlp"]["shared"] = mats(f"{base}.mlp.shared_experts")
    return layer


def _layer_to_hf(sd, base: str, lp: Dict, first: int) -> None:
    for ours, theirs in _NORMS.items():
        sd[f"{base}.{theirs}.weight"] = lp[ours]["weight"]
    for ours, theirs in _ATTN_MATS.items():
        sd[f"{base}.self_attn.{theirs}.weight"] = lp["attn"][ours].T
    for ours, theirs in _ATTN_NORMS.items():
        sd[f"{base}.self_attn.{theirs}.weight"] = lp["attn"][ours]
    mlp = lp["mlp"]
    if "router" not in mlp:
        for ours, theirs in _MLP_MATS.items():
            sd[f"{base}.mlp.{theirs}.weight"] = mlp[ours].T
        return
    sd[f"{base}.mlp.gate.weight"] = mlp["router"].T
    sd[f"{base}.mlp.gate.e_score_correction_bias"] = mlp["expert_bias"]
    for ours, theirs in _MLP_MATS.items():
        for e in range(mlp[ours].shape[0]):
            sd[f"{base}.mlp.experts.{first + e}.{theirs}.weight"] = mlp[ours][e].T
        if "shared" in mlp:
            sd[f"{base}.mlp.shared_experts.{theirs}.weight"] = mlp["shared"][ours].T


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers

    kinds, n_lead = cfg.kinds(), cfg.n_lead_layers
    layers = [_layer_from_hf(sd, f"model.layers.{i}", k.mlp == "moe", cfg.moe)
              for i, k in enumerate(kinds)]
    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "layers": stack_layers(layers[n_lead:]),
        "final_norm": {"weight": sd["model.norm.weight"].astype(np.float32)},
    }
    if n_lead:
        params["lead_layers"] = stack_layers(layers[:n_lead])
    if cfg.mtp is not None:
        base = f"model.layers.{cfg.n_layers}"
        params["mtp"] = {
            ours: {"weight": sd[f"{base}.{theirs}.weight"].astype(np.float32)}
            for ours, theirs in _MTP.items()}
        params["mtp"]["eh_proj"] = {"weight": np.ascontiguousarray(
            sd[f"{base}.eh_proj.weight"].astype(np.float32).T)}
        params["mtp"]["block"] = stack_layers(
            [_layer_from_hf(sd, base, kinds[-1].mlp == "moe", cfg.moe)])
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
        _layer_to_hf(sd, f"model.layers.{i}", lp, first)
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["head"]["weight"]).T
    elif not cfg.tied_embeddings:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    if cfg.mtp is not None and "mtp" in params:
        base, mtp = f"model.layers.{cfg.n_layers}", params["mtp"]
        _layer_to_hf(sd, base, unstack_layers(mtp["block"], 1)[0], first)
        for ours, theirs in _MTP.items():
            sd[f"{base}.{theirs}.weight"] = np.asarray(mtp[ours]["weight"])
        sd[f"{base}.eh_proj.weight"] = np.asarray(mtp["eh_proj"]["weight"]).T
        # the release's checkpoints repeat what the module shares
        sd[f"{base}.embed_tokens.weight"] = sd["model.embed_tokens.weight"]
        head = "model.embed_tokens.weight" if cfg.tied_embeddings else "lm_head.weight"
        sd[f"{base}.shared_head.head.weight"] = sd[head]
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
