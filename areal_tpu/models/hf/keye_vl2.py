"""Keye-VL-2.0's language model, HF conversion: `model_type: KeyeVL2`.

Text only: the published model has a vision tower whose widths its
`config.json` does not give, and none is here. Under text tokens the
three components of an `mrope_section` position are equal and the rotary
is the one-dimensional one, over the whole head at `rope_theta`.

Every layer: GQA attention with the qwen3 family's RMSNorm over each q
and k head, no bias, and beside it an indexer (`sa_config`:
`indexer_num_heads` heads of `indexer_head_dim`, one key head, `topk`
keys a query; `models/config.IndexerConfig`), then an expert layer
(`decoder_sparse_step` 1, `mlp_only_layers` []: `intermediate_size` is
used by no layer): `num_experts` experts of `moe_intermediate_size`,
softmax over all of them, `num_experts_per_tok` chosen, renormalised
(`norm_topk_prob`), no shared expert. `q_chunk_size` / `kv_chunk_size`
are the published implementation's tiles and choose nothing here.

Three keys are this repo's, not the published file's: `num_experts_routed`
and `experts_held_first` for one chip's share of an expert-parallel layer
(models/moe.py `experts_held`: `num_experts` then counts the experts
whose weights are here), and `indexer_loss_weight`, what a training step
gives the indexers' KL loss (1.0 where absent; 0 = the step skips it).

The checkpoint's tensor names are the qwen3-moe layout's (`self_attn.
q_proj` .. `o_proj`, `q_norm`, `k_norm`, `mlp.gate`, `mlp.experts.{e}.*`)
and, for the indexer, DeepSeek-V3.2's under `self_attn.indexer` (`wq`
where that release has `wq_b` behind a low-rank q, `wk`, `k_norm` with a
bias, `weights_proj`): written from memory of those releases, the catalog
gives the config only.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import IndexerConfig, MoEConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "KeyeVL2"


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
        raise NotImplementedError(
            f"{MODEL_TYPE}: an expert layer in every layer: got decoder_sparse_step "
            f"{hf.get('decoder_sparse_step')}, mlp_only_layers {hf.get('mlp_only_layers')}")
    if hf.get("use_sliding_window") or hf.get("sliding_window"):
        raise NotImplementedError(
            f"{MODEL_TYPE}: sliding_window={hf.get('sliding_window')}: an indexer "
            "chooses among the keys of full causal attention")
    scaling = hf.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise NotImplementedError(
            f"{MODEL_TYPE}: rope_scaling={scaling}: text positions under the "
            "default rotary (mrope_section's three components equal) alone")
    sa = hf.get("sa_config")
    if not sa:
        raise NotImplementedError(f"{MODEL_TYPE}: sa_config (the indexer) is absent")
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise NotImplementedError(
            f"{MODEL_TYPE}: indexer_num_kv_heads={sa['indexer_num_kv_heads']}: the "
            "indexer here has one key head for all its q heads")
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
        raise NotImplementedError(
            f"{MODEL_TYPE}: norm_topk_prob false: models/moe.py's softmax router "
            "renormalises the chosen gates")
    return TransformerConfig(
        n_layers=int(hf["num_hidden_layers"]),
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation="silu", mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rotary_base=float(hf.get("rope_theta", 10000.0)),
        attn_bias=bool(hf.get("attention_bias", False)),
        qk_norm=True,
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe,
        indexer=IndexerConfig(
            n_heads=int(sa["indexer_num_heads"]), head_dim=int(sa["indexer_head_dim"]),
            top_k=int(sa["topk"]),
            loss_weight=float(hf.get("indexer_loss_weight", 1.0))),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, ix = cfg.moe, cfg.indexer
    hf: Dict[str, Any] = dict(
        architectures=["KeyeVL2ForConditionalGeneration"],
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
        rope_theta=cfg.rotary_base,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        attention_bias=cfg.attn_bias,
        tie_word_embeddings=cfg.tied_embeddings,
        decoder_sparse_step=1, mlp_only_layers=[],
        num_experts=moe.n_held, num_local_experts=moe.n_held,
        num_experts_per_tok=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        norm_topk_prob=moe.route_norm,
        sa_config=dict(indexer_head_dim=ix.head_dim, indexer_num_heads=ix.n_heads,
                       indexer_num_kv_heads=1, topk=ix.top_k,
                       q_chunk_size=512, kv_chunk_size=512),
        sliding_window=None, use_sliding_window=False,
        indexer_loss_weight=ix.loss_weight,
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
_INDEX_MATS = {"iq_proj": "wq", "ik_proj": "wk", "iw_proj": "weights_proj"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _layer_from_hf(sd, base: str, moe: MoEConfig) -> Dict:
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight")}
             for ours, theirs in _NORMS.items()}
    attn = {ours: t(f"{base}.self_attn.{theirs}.weight")
            for ours, theirs in _ATTN_MATS.items()}
    attn.update({ours: w(f"{base}.self_attn.{theirs}.weight")
                 for ours, theirs in _ATTN_NORMS.items()})
    ix = f"{base}.self_attn.indexer"
    attn["indexer"] = {ours: t(f"{ix}.{theirs}.weight")
                       for ours, theirs in _INDEX_MATS.items()}
    attn["indexer"]["ik_norm"] = {"weight": w(f"{ix}.k_norm.weight"),
                                  "bias": w(f"{ix}.k_norm.bias")}
    layer["attn"] = attn
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
        ix = lp["attn"]["indexer"]
        for ours, theirs in _INDEX_MATS.items():
            sd[f"{base}.self_attn.indexer.{theirs}.weight"] = ix[ours].T
        sd[f"{base}.self_attn.indexer.k_norm.weight"] = ix["ik_norm"]["weight"]
        sd[f"{base}.self_attn.indexer.k_norm.bias"] = ix["ik_norm"]["bias"]
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
