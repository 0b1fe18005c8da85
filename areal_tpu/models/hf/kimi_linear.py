"""Kimi-Linear HF conversion: `model_type: kimi_linear` (arXiv:2510.26692).

Every layer is a mixer and an MLP, each under its RMSNorm with a
residual. The mixers by `linear_attn_config` (layers counted from 1):
`kda_layers` are Kimi Delta Attention (`models/config.KDAConfig`,
`ops/kda.py`: `num_heads` heads of `head_dim`, convolutions of
`short_conv_kernel_size` taps; three to one in the published file),
`full_attn_layers` latent attention (`models/config.MLAConfig`:
`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`; a
full-rank q, `q_lora_rank` null; **no position encoding anywhere**,
`mla_use_nope`: the `qk_rope_head_dim` columns are that many more columns
of q and of the one key every head shares). The first
`first_k_dense_replace` layers have a dense SwiGLU of `intermediate_size`,
the rest (`moe_layer_freq` 1) `num_experts` experts of
`moe_intermediate_size`, `num_experts_per_token` a token chosen on sigmoid
score (`moe_router_activation_func`) + `e_score_correction_bias`, weighted
by the bare scores normalised (`moe_renormalize`) times
`routed_scaling_factor`, plus `num_shared_experts` shared.

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `num_experts` counts only
the experts whose weights are here) and `experts_held_first`.

What the config does not give is set by the family's convention
(benchmark/configs/kimi-linear-*.json `assumed`): the rank of the decay's
and the output gate's low-rank products is `linear_attn_config.head_dim`;
the rule runs in chunks of `CHUNK`. Refused by name: `num_expert_group` /
`topk_group` over 1, `num_nextn_predict_layers` over 0, `rope_scaling`,
`mla_use_nope` false (a rotary part in a stack whose other mixers carry
the order: not the published model), a router that is no sigmoid, an
expert layer that is not every layer after the dense ones, and a
`linear_attn_config` whose two lists do not cover every layer once.
Nothing of the delta rule runs on a mesh that splits a row
(`models/transformer.forward` says so).

The checkpoint's tensor names are written from memory of the released
modelling code (the catalog gives the config only): `self_attn.{q,k,v}_proj`,
`{q,k,v}_conv1d.weight` [channels, 1, taps], `A_log` [1, 1, heads, 1],
`dt_bias`, `f_a_proj`, `f_b_proj`, `b_proj`, `g_a_proj`, `g_b_proj`,
`o_norm`, `o_proj`; latent layers `self_attn.q_proj`, `kv_a_proj_with_mqa`,
`kv_a_layernorm`, `kv_b_proj`, `o_proj`; `block_sparse_moe.gate.{weight,
e_score_correction_bias}`, `block_sparse_moe.experts.{e}.{w1,w3,w2}` (gate,
up, down), `block_sparse_moe.shared_experts.*`, `mlp.*` in a dense layer.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import (
    KDAConfig, LayerKind, MLAConfig, MoEConfig, TransformerConfig)
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "kimi_linear"
CHUNK = 64  # positions the delta rule takes at a time (ops/kda.py)


def _kinds(hf: Dict[str, Any]):
    n, lin = int(hf["num_hidden_layers"]), hf["linear_attn_config"]
    kda, full = list(lin["kda_layers"]), list(lin["full_attn_layers"])
    if sorted(kda + full) != list(range(1, n + 1)):
        raise ValueError(
            f"{MODEL_TYPE}: linear_attn_config's kda_layers and full_attn_layers "
            f"must name each of the layers 1..{n} once, got {kda} and {full}")
    dense = int(hf.get("first_k_dense_replace", 0))
    return tuple(
        LayerKind(mlp="dense" if i <= dense else "moe",
                  **(dict(mixer="kda") if i in kda else dict(latent=True, rotary=False)))
        for i in range(1, n + 1))


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    for key in ("num_expert_group", "topk_group"):
        if hf.get(key, 1) not in (None, 1):
            raise NotImplementedError(
                f"{MODEL_TYPE}: {key}={hf[key]}: group-limited routing is not in "
                "models/moe.py's router")
    if hf.get("num_nextn_predict_layers", 0):
        raise NotImplementedError(
            f"{MODEL_TYPE}: num_nextn_predict_layers="
            f"{hf['num_nextn_predict_layers']}: a prediction module after a stack "
            "that ends in a delta-rule layer is in no published config; "
            "models/transformer.py's module is a transformer block")
    if hf.get("rope_scaling"):
        raise NotImplementedError(
            f"{MODEL_TYPE}: rope_scaling={hf['rope_scaling']}: no layer of this "
            "family turns anything (mla_use_nope), so there is no table to scale")
    if not hf.get("mla_use_nope", False):
        raise NotImplementedError(
            f"{MODEL_TYPE}: mla_use_nope false: latent layers with a rotary part "
            "between delta-rule layers are not the published model, and which "
            "table they would turn by (rope_theta over qk_rope_head_dim, "
            "interleaved or not) is in no released file")
    if (hf.get("moe_layer_freq", 1) != 1
            or hf.get("moe_router_activation_func", "sigmoid") != "sigmoid"):
        raise NotImplementedError(
            f"{MODEL_TYPE}: an expert layer every layer after the dense ones, "
            f"sigmoid scores: got moe_layer_freq {hf.get('moe_layer_freq')}, "
            f"moe_router_activation_func {hf.get('moe_router_activation_func')!r}")
    if hf.get("q_lora_rank"):
        raise NotImplementedError(
            f"{MODEL_TYPE}: q_lora_rank={hf['q_lora_rank']}: the published model's "
            "q is full-rank; whether a low-rank q here would carry a norm is in "
            "no released file")
    heads = int(hf["num_attention_heads"])
    if int(hf.get("num_key_value_heads", heads)) != heads:
        raise ValueError(f"{MODEL_TYPE}: latent attention has k and v a head")
    lin = hf["linear_attn_config"]
    mla = MLAConfig(
        q_rank=None, kv_rank=int(hf["kv_lora_rank"]),
        nope_dim=int(hf["qk_nope_head_dim"]), rope_dim=int(hf["qk_rope_head_dim"]),
        v_dim=int(hf["v_head_dim"]))
    kda = KDAConfig(
        n_heads=int(lin["num_heads"]), head_dim=int(lin["head_dim"]),
        conv_kernel=int(lin["short_conv_kernel_size"]),
        gate_rank=int(lin["head_dim"]), chunk_size=CHUNK)
    held = int(hf["num_experts"])
    routed = int(hf.get("num_experts_routed", held))
    first = int(hf.get("experts_held_first", 0))
    moe = MoEConfig(
        num_experts=routed,
        top_k=int(hf["num_experts_per_token"]),
        dispatch="dropless",
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        aux_loss_coef=0.0,
        expert_intermediate_dim=int(hf["moe_intermediate_size"]),
        first_k_dense=int(hf.get("first_k_dense_replace", 0)),
        score_func="sigmoid",
        route_norm=bool(hf.get("moe_renormalize", True)),
        router_bias=True,
        n_shared_experts=int(hf.get("num_shared_experts", 0)),
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    )
    return TransformerConfig(
        n_layers=int(hf["num_hidden_layers"]),
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=heads, n_kv_heads=heads,
        head_dim=mla.qk_dim,
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("model_max_length", 4096)),
        activation=hf.get("hidden_act", "silu"), mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        rotary_base=float(hf.get("rope_theta", 10000.0)),  # no layer rotates
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe, mla=mla, kda=kda,
        layer_kinds=_kinds(hf),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, mla, kda = cfg.moe, cfg.mla, cfg.kda
    kinds = cfg.kinds()
    hf: Dict[str, Any] = dict(
        architectures=["KimiLinearForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.hidden_dim // cfg.n_q_heads,
        q_lora_rank=None, kv_lora_rank=mla.kv_rank,
        qk_nope_head_dim=mla.nope_dim, qk_rope_head_dim=mla.rope_dim,
        v_head_dim=mla.v_dim, mla_use_nope=True,
        linear_attn_config=dict(
            kda_layers=[i + 1 for i, k in enumerate(kinds) if k.mixer == "kda"],
            full_attn_layers=[i + 1 for i, k in enumerate(kinds) if k.mixer != "kda"],
            num_heads=kda.n_heads, head_dim=kda.head_dim,
            short_conv_kernel_size=kda.conv_kernel),
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        model_max_length=cfg.max_position_embeddings,
        hidden_act=cfg.activation,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base, rope_scaling=None,
        tie_word_embeddings=cfg.tied_embeddings,
        first_k_dense_replace=moe.first_k_dense, moe_layer_freq=1,
        num_experts=moe.n_held,
        num_experts_per_token=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        num_shared_experts=moe.n_shared_experts,
        moe_router_activation_func="sigmoid", moe_renormalize=moe.route_norm,
        use_grouped_topk=True, num_expert_group=1, topk_group=1,
        routed_scaling_factor=moe.routed_scaling_factor,
        num_nextn_predict_layers=0,
        torch_dtype="bfloat16",
    )
    if moe.experts_held is not None:
        hf.update(num_experts_routed=moe.num_experts,
                  experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`;
# matrices are stored [out, in] there and [in, out] here.
_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}
_KDA_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "w_fa": "f_a_proj",
             "w_fb": "f_b_proj", "w_b": "b_proj", "w_ga": "g_a_proj",
             "w_gb": "g_b_proj", "wo": "o_proj"}
_KDA_CONVS = {"conv_q": "q_conv1d", "conv_k": "k_conv1d", "conv_v": "v_conv1d"}
_MLA_MATS = {"wq": "q_proj", "wkv_a": "kv_a_proj_with_mqa", "wkv_b": "kv_b_proj",
             "wo": "o_proj"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
_EXPERT_MATS = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}


def _layer_from_hf(sd, i: int, kind: LayerKind, moe: MoEConfig) -> Dict:
    base = f"model.layers.{i}"
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    mats = lambda prefix, names: {
        ours: t(f"{prefix}.{theirs}.weight") for ours, theirs in names.items()}
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight")}
             for ours, theirs in _NORMS.items()}
    at = f"{base}.self_attn"
    if kind.mixer == "kda":
        kp = mats(at, _KDA_MATS)
        kp.update({ours: t(f"{at}.{theirs}.weight")[:, 0, :]  # [K, 1, C] -> [K, C]
                   for ours, theirs in _KDA_CONVS.items()})
        kp["A_log"] = w(f"{at}.A_log").reshape(-1)
        kp["dt_bias"] = w(f"{at}.dt_bias")
        kp["o_norm"] = w(f"{at}.o_norm.weight")
        layer["kda"] = kp
    else:
        layer["attn"] = mats(at, _MLA_MATS)
        layer["attn"]["kv_a_norm"] = w(f"{at}.kv_a_layernorm.weight")
    if kind.mlp == "dense":
        layer["mlp"] = mats(f"{base}.mlp", _MLP_MATS)
        return layer
    first, held = moe.experts_held or (0, moe.num_experts)
    sp = f"{base}.block_sparse_moe"
    experts = [mats(f"{sp}.experts.{e}", _EXPERT_MATS) for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _EXPERT_MATS}
    layer["mlp"]["router"] = t(f"{sp}.gate.weight")
    layer["mlp"]["expert_bias"] = w(f"{sp}.gate.e_score_correction_bias")
    if moe.n_shared_experts:
        layer["mlp"]["shared"] = mats(f"{sp}.shared_experts", _MLP_MATS)
    return layer


def _layer_to_hf(sd, i: int, lp: Dict, first: int) -> None:
    base = f"model.layers.{i}"
    put = lambda prefix, tree, names: sd.update(
        {f"{prefix}.{theirs}.weight": tree[ours].T for ours, theirs in names.items()})
    for ours, theirs in _NORMS.items():
        sd[f"{base}.{theirs}.weight"] = lp[ours]["weight"]
    at = f"{base}.self_attn"
    if "kda" in lp:
        kp = lp["kda"]
        put(at, kp, _KDA_MATS)
        for ours, theirs in _KDA_CONVS.items():
            sd[f"{at}.{theirs}.weight"] = kp[ours].T[:, None, :]
        sd[f"{at}.A_log"] = kp["A_log"].reshape(1, 1, -1, 1)
        sd[f"{at}.dt_bias"] = kp["dt_bias"]
        sd[f"{at}.o_norm.weight"] = kp["o_norm"]
    else:
        put(at, lp["attn"], _MLA_MATS)
        sd[f"{at}.kv_a_layernorm.weight"] = lp["attn"]["kv_a_norm"]
    mlp = lp["mlp"]
    if "router" not in mlp:
        put(f"{base}.mlp", mlp, _MLP_MATS)
        return
    sp = f"{base}.block_sparse_moe"
    sd[f"{sp}.gate.weight"] = mlp["router"].T
    sd[f"{sp}.gate.e_score_correction_bias"] = mlp["expert_bias"]
    for e in range(mlp["w_gate"].shape[0]):
        put(f"{sp}.experts.{first + e}", {k: mlp[k][e] for k in _EXPERT_MATS},
            _EXPERT_MATS)
    if "shared" in mlp:
        put(f"{sp}.shared_experts", mlp["shared"], _MLP_MATS)


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    layers = [_layer_from_hf(sd, i, k, cfg.moe) for i, k in enumerate(cfg.kinds())]
    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "final_norm": {"weight": sd["model.norm.weight"].astype(np.float32)},
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
          "model.norm.weight": np.asarray(params["final_norm"]["weight"])}
    first = cfg.moe.experts_held[0] if cfg.moe.experts_held else 0
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
