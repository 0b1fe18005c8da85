"""HF checkpoint conversion registry.

Counterpart of the reference's HF registry + per-family converters
(realhf/impl/model/conversion/hf_registry.py, realhf/api/from_hf/*). Each
family module registers an `HFFamily` with config and state-dict mappers;
`load_hf_model` / `save_hf_model` go through safetensors on disk so
checkpoints interoperate with the HF ecosystem (and with vLLM/SGLang-style
servers if ever needed).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Optional

import numpy as np

from areal_tpu.api.model_api import HF_FAMILY_REGISTRY, register_hf_family
from areal_tpu.models.config import TransformerConfig


@dataclasses.dataclass
class HFFamily:
    name: str
    hf_model_type: str
    config_from_hf: Callable[[Dict[str, Any], bool], TransformerConfig]
    config_to_hf: Callable[[TransformerConfig], Dict[str, Any]]
    params_from_hf: Callable[[Dict[str, np.ndarray], TransformerConfig], Dict]
    params_to_hf: Callable[[Dict, TransformerConfig], Dict[str, np.ndarray]]


def get_family(name: str) -> HFFamily:
    if name not in HF_FAMILY_REGISTRY:
        raise KeyError(
            f"unknown HF family {name!r}; registered: {sorted(HF_FAMILY_REGISTRY)}"
        )
    return HF_FAMILY_REGISTRY[name]


def family_from_hf_config(hf_config: Dict[str, Any]) -> HFFamily:
    mt = hf_config.get("model_type")
    for fam in HF_FAMILY_REGISTRY.values():
        if fam.hf_model_type == mt:
            return fam
    raise KeyError(f"no registered family for HF model_type {mt!r}")


# ---------------------------------------------------------------------------
# Disk IO (safetensors sharded or single, else torch .bin)
# ---------------------------------------------------------------------------


def load_hf_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Read all tensors of an HF checkpoint directory into numpy."""
    import safetensors.numpy

    out: Dict[str, np.ndarray] = {}
    st_files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if st_files:
        for f in st_files:
            out.update(safetensors.numpy.load_file(os.path.join(path, f)))
        return out
    bin_files = sorted(f for f in os.listdir(path) if f.endswith(".bin"))
    if bin_files:
        import torch

        for f in bin_files:
            sd = torch.load(os.path.join(path, f), map_location="cpu", weights_only=True)
            out.update({k: v.float().numpy() if v.dtype == torch.bfloat16 else v.numpy()
                        for k, v in sd.items()})
        return out
    raise FileNotFoundError(f"no safetensors/bin weights under {path}")


def torch_state_dict_to_numpy(sd) -> Dict[str, np.ndarray]:
    import torch

    out = {}
    for k, v in sd.items():
        v = v.detach().cpu()
        if v.dtype == torch.bfloat16:
            v = v.float()
        out[k] = v.numpy()
    return out


def load_hf_config(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, "config.json")) as f:
        return json.load(f)


def load_hf_model(
    path: str, is_critic: bool = False, family: Optional[str] = None
):
    """(TransformerConfig, params) from an HF checkpoint directory."""
    hf_cfg = load_hf_config(path)
    fam = get_family(family) if family else family_from_hf_config(hf_cfg)
    cfg = fam.config_from_hf(hf_cfg, is_critic)
    sd = load_hf_state_dict(path)
    params = fam.params_from_hf(sd, cfg)
    return cfg, params


def save_hf_model(
    save_dir: str,
    cfg: TransformerConfig,
    params: Dict,
    family: str,
    tokenizer=None,
):
    """Write an HF-format checkpoint (config.json + model.safetensors)."""
    import safetensors.numpy

    fam = get_family(family)
    os.makedirs(save_dir, exist_ok=True)
    sd = fam.params_to_hf(params, cfg)
    sd = {k: np.ascontiguousarray(v) for k, v in sd.items()}
    safetensors.numpy.save_file(sd, os.path.join(save_dir, "model.safetensors"))
    with open(os.path.join(save_dir, "config.json"), "w") as f:
        json.dump(fam.config_to_hf(cfg), f, indent=2)
    if tokenizer is not None:
        tokenizer.save_pretrained(save_dir)


# ---------------------------------------------------------------------------
# Shared stacking helpers for llama-style families
# ---------------------------------------------------------------------------


def stack_layers(per_layer: list) -> Dict:
    """List of per-layer pytrees -> one pytree with stacked leading axis."""
    import jax

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=0), *per_layer)


def unstack_layers(stacked: Dict, n_layers: int) -> list:
    import jax

    return [
        jax.tree_util.tree_map(lambda x: np.asarray(x)[i], stacked)
        for i in range(n_layers)
    ]


# Register families on import.
from areal_tpu.models.hf import llama as _llama  # noqa: E402,F401
from areal_tpu.models.hf import qwen2 as _qwen2  # noqa: E402,F401
from areal_tpu.models.hf import qwen3 as _qwen3  # noqa: E402,F401
from areal_tpu.models.hf import mistral as _mistral  # noqa: E402,F401
from areal_tpu.models.hf import mixtral as _mixtral  # noqa: E402,F401
from areal_tpu.models.hf import gemma as _gemma  # noqa: E402,F401
from areal_tpu.models.hf import gpt2 as _gpt2  # noqa: E402,F401
from areal_tpu.models.hf import afmoe as _afmoe  # noqa: E402,F401
from areal_tpu.models.hf import nemotron_h as _nemotron_h  # noqa: E402,F401
from areal_tpu.models.hf import phi4flash as _phi4flash  # noqa: E402,F401
from areal_tpu.models.hf import joyai_llm_flash as _joyai_llm_flash  # noqa: E402,F401
from areal_tpu.models.hf import keye_vl2 as _keye_vl2  # noqa: E402,F401
from areal_tpu.models.hf import xing4_0 as _xing4_0  # noqa: E402,F401
from areal_tpu.models.hf import kimi_linear as _kimi_linear  # noqa: E402,F401
from areal_tpu.models.hf import qwen3_next as _qwen3_next  # noqa: E402,F401
from areal_tpu.models.hf import mellum as _mellum  # noqa: E402,F401
from areal_tpu.models.hf import olmo_hybrid as _olmo_hybrid  # noqa: E402,F401
from areal_tpu.models.hf import lfm2_moe as _lfm2_moe  # noqa: E402,F401
