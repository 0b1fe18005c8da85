"""Mistral HF conversion: llama layout, silu, GQA.
Reference parity: realhf/api/from_hf/mistral.py.

A published `sliding_window` becomes the window of every layer
(`TransformerConfig.layer_kinds`): the trainer's attention masks and
skips what lies behind it (ops/attention.py), and the KV-cache paths,
which hold no window yet, refuse the configuration
(`TransformerConfig.require_plain_stack`).
"""

from __future__ import annotations

from typing import Any, Dict

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import LayerKind, TransformerConfig
from areal_tpu.models.hf import HFFamily
from areal_tpu.models.hf.llama import (
    _config_from_hf as llama_config_from_hf,
    _config_to_hf as llama_config_to_hf,
    params_from_hf_llama_style,
    params_to_hf_llama_style,
)


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    cfg = llama_config_from_hf(hf, is_critic)
    window = hf.get("sliding_window")
    if window is not None and window < cfg.max_position_embeddings:
        cfg.layer_kinds = (LayerKind(window=int(window)),) * cfg.n_layers
    return cfg


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    hf = llama_config_to_hf(cfg)
    hf["architectures"] = ["MistralForCausalLM"]
    hf["model_type"] = "mistral"
    hf["sliding_window"] = cfg.kinds()[0].window
    hf.pop("attention_bias", None)
    return hf


register_hf_family(
    "mistral",
    HFFamily(
        name="mistral",
        hf_model_type="mistral",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=lambda sd, cfg: params_from_hf_llama_style(sd, cfg),
        params_to_hf=lambda p, cfg: params_to_hf_llama_style(p, cfg),
    ),
)
