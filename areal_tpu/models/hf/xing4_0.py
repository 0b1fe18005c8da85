"""Xing4.0 HF conversion: `model_type: xing4_0`, a stack of DeepSeek-V3's
shape (`models/hf/joyai_llm_flash.py`: latent attention, leading dense
layers, sigmoid-routed experts chosen on score + bias with a shared
expert, a prediction module) whose residual path is `hc_mult` streams a
token under manifold-constrained hyper-connections
(`models/config.HyperConnConfig`: `hc_mult`, `hc_sinkhorn_iters`,
`hc_eps`, `mhc_h_res_clamp_min` / `_max`) and whose rope part turns by a
YaRN table (`rope_scaling` type `yarn`; the softmax scale times
`mscale^2`).

It refuses, by name: `n_group` / `topk_group` over 1, `moe_layer_freq`
not 1, a `rope_scaling` type other than `yarn` (and `mscale` !=
`mscale_all_dim`), and `num_nextn_predict_layers` > 0 beside `hc_mult` >
1: how a prediction module reads several streams and hands them on is in
the model's modeling file, which no published config or paper states.

Three keys are this repo's, not the published file's, as
`joyai_llm_flash` has them: `num_experts_routed` and `experts_held_first`
(one chip's share of an expert-parallel layer) and `mtp_loss_weight`.

The checkpoint's tensor names are DeepSeek-V3's (written from memory of
that release, as `joyai_llm_flash`'s). **The hyper-connections' names are
this repo's own**, no release's: `model.layers.{i}.hc_attn.{phi,b,a}`
for the mixer's sublayer and `hc_mlp.{phi,b,a}` for the MLP's: `phi`
`[hc_mult hidden, hc_mult^2 + 2 hc_mult]` stored as it multiplies (`x~
phi`, columns H_pre, H_post, then H_res row by row), `b` alike, `a` the
three gates (pre, post, res).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import HyperConnConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily
from areal_tpu.models.hf import joyai_llm_flash as base

MODEL_TYPE = "xing4_0"

# our stack's leaf -> the checkpoint's name under `model.layers.{i}.`
_HC = {"hc1": "hc_attn", "hc2": "hc_mlp"}
_HC_LEAVES = ("phi", "b", "a")


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    n = int(hf.get("hc_mult", 1))
    if n > 1 and int(hf.get("num_nextn_predict_layers", 0)):
        raise NotImplementedError(
            f"{MODEL_TYPE}: num_nextn_predict_layers={hf['num_nextn_predict_layers']} "
            f"beside hc_mult={n}: how a prediction module reads {n} residual streams "
            "and hands them on is in no published config or paper")
    cfg = base._config_from_hf(hf, is_critic, MODEL_TYPE)
    if n == 1:
        return cfg
    return dataclasses.replace(cfg, hyper=HyperConnConfig(
        n=n, sinkhorn_iters=int(hf["hc_sinkhorn_iters"]), eps=float(hf["hc_eps"]),
        clamp=(hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"])))


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    hf = base._config_to_hf(cfg)
    hf.update(architectures=["Xing4ForCausalLM"], model_type=MODEL_TYPE)
    hy = cfg.hyper or HyperConnConfig()  # one stream: the other keys at their defaults
    hf.update(hc_mult=cfg.hyper.n if cfg.hyper else 1, hc_sinkhorn_iters=hy.sinkhorn_iters,
              hc_eps=hy.eps, mhc_h_res_clamp_min=hy.clamp[0], mhc_h_res_clamp_max=hy.clamp[1])
    return hf


def _stack_ranges(cfg: TransformerConfig):
    """(stack, its layers) of the parameter tree's stacks that hold a layer."""
    n_lead = cfg.n_lead_layers
    stacks = (("lead_layers", range(n_lead)), ("layers", range(n_lead, cfg.n_layers)))
    return [(stack, idx) for stack, idx in stacks if len(idx)]


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    params = base._params_from_hf(sd, cfg)
    if cfg.hyper is not None:
        for stack, idx in _stack_ranges(cfg):
            for ours, theirs in _HC.items():
                params[stack][ours] = {leaf: np.stack([
                    sd[f"model.layers.{i}.{theirs}.{leaf}"].astype(np.float32)
                    for i in idx]) for leaf in _HC_LEAVES}
    return params


def _params_to_hf(params: Dict, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    sd = base._params_to_hf(params, cfg)
    if cfg.hyper is not None:
        for stack, idx in _stack_ranges(cfg):
            for ours, theirs in _HC.items():
                for leaf in _HC_LEAVES:
                    for j, i in enumerate(idx):
                        sd[f"model.layers.{i}.{theirs}.{leaf}"] = np.asarray(
                            params[stack][ours][leaf])[j]
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
