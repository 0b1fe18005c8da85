"""Phi-4-mini-flash HF conversion: `model_type: phi4flash` (SambaY, a
decoder-hybrid-decoder with differential attention).

Every layer is a mixer and a gated MLP, each under a LayerNorm (weight
and bias) with a residual around it; no position encoding anywhere. The
mixers follow from `num_hidden_layers` = L (a multiple of 4),
`mb_per_layer` (2: every second layer a state-space one) and
`sliding_window` by the published rule:

- even layers up to L/2: a Mamba-1 selective scan (`mamba_expand` x hidden
  channels, state `mamba_d_state`, a convolution of `mamba_d_conv` taps,
  step size through rank `mamba_dt_rank`); layer L/2 also keeps its scan
  output before the gate;
- odd layers under L/2: differential attention over a window of
  `sliding_window`; layer L/2 + 1: differential attention over all of
  the sequence, whose k and v are kept;
- from L/2 + 2 on, even layers are gated memory units reading layer
  L/2's kept scan output, odd layers differential cross-attention over
  layer L/2 + 1's k and v (q and the output projection only).

Differential attention: `num_attention_heads` / `num_key_value_heads`
heads of hidden / heads, paired (`models/transformer._diff_split`), its
lambda_init from the layer's index.

The catalog gives the config only. The keys it is silent on
(`mamba_d_state` 16, `mamba_d_conv` 4, `mamba_expand` 2, `mamba_dt_rank`
ceil(hidden / 16), a convolution bias, no projection bias) take
Mamba-1's defaults, and the checkpoint's tensor names below are written
from memory of the published modelling code: `model.embed_tokens`,
`model.layers.{i}.{input,post_attention}_layernorm`, `.attn.{in_proj,
conv1d, x_proj, dt_proj, A_log, D, out_proj}` (a state-space layer; a
gated memory unit has `in_proj` and `out_proj` only), `.attn.{Wqkv,
out_proj}` and `.attn.inner_cross_attn.{lambda_q1, lambda_k1, lambda_q2,
lambda_k2, subln.weight}` (attention; a cross layer's `Wqkv` holds q
alone), `.mlp.{fc1, fc2}` (`fc1` = [gate | up]), `model.final_layernorm`.
`conv1d.weight` is [channels, 1, taps] there and [taps, channels] here.
`scan_chunk_size` is this repository's key (the scan kernel's block of
time), not the published file's.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import LayerKind, SSMConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily
from areal_tpu.models.transformer import DIFF_LAMBDAS


def layer_kinds(n_layers: int, window) -> tuple:
    """The published rule, layer by layer."""
    half = n_layers // 2
    attn = dict(rotary=False, diff=True)
    kinds = []
    for i in range(n_layers):
        if i % 2 == 0 and i <= half:
            kinds.append(LayerKind(mixer="ssm", keeps=i == half))
        elif i % 2 == 0:
            kinds.append(LayerKind(mixer="gmu", reads=half))
        elif i < half:
            kinds.append(LayerKind(window=window, **attn))
        elif i == half + 1:
            kinds.append(LayerKind(keeps=True, **attn))
        else:
            kinds.append(LayerKind(reads=half + 1, **attn))
    return tuple(kinds)


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    L, D = int(hf["num_hidden_layers"]), int(hf["hidden_size"])
    if L % 4 or L < 8:
        raise ValueError(
            f"phi4flash: num_hidden_layers must be a multiple of 4 and at least 8 "
            f"(a self-decoder of (scan, window attention) pairs, then a scan, a "
            f"full attention and (memory unit, cross-attention) pairs), got {L}")
    if int(hf.get("mb_per_layer", 2)) != 2:
        raise NotImplementedError(
            f"phi4flash: mb_per_layer={hf['mb_per_layer']}: the layer rule here "
            "is the published one, a state-space layer every second layer")
    if hf.get("lm_head_bias"):
        raise NotImplementedError("phi4flash: lm_head_bias: the loss head has no bias")
    if hf.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"phi4flash: hidden_act={hf['hidden_act']!r}")
    heads = int(hf["num_attention_heads"])
    window = hf.get("sliding_window")
    return TransformerConfig(
        n_layers=L,
        hidden_dim=D,
        n_q_heads=heads,
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=D // heads,
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation="silu",
        mlp_type="gated",
        norm_type="layer",
        norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
        attn_bias=True,
        attn_out_bias=True,
        mlp_bias=bool(hf.get("mlp_bias", False)),
        tied_embeddings=bool(hf.get("tie_word_embeddings", True)),
        is_critic=is_critic,
        ssm=SSMConfig(
            form="mamba1",
            channels=int(hf.get("mamba_expand", 2)) * D,
            dt_rank=int(hf.get("mamba_dt_rank") or -(-D // 16)),
            state_dim=int(hf.get("mamba_d_state", 16)),
            conv_kernel=int(hf.get("mamba_d_conv", 4)),
            chunk_size=int(hf.get("scan_chunk_size", 128)),
            conv_bias=True,
        ),
        layer_kinds=layer_kinds(L, None if window is None else int(window)),
        # At these widths two repeats of (scan, attention) are 436 M
        # parameters of gradients held to the end of the backward pass.
        scan_min_repeats=3,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    ssm = cfg.ssm
    windows = {k.window for k in cfg.kinds() if k.window is not None}
    return dict(
        architectures=["Phi4FlashForCausalLM"],
        model_type="phi4flash",
        num_hidden_layers=cfg.n_layers,
        mb_per_layer=2,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act="silu",
        layer_norm_eps=cfg.norm_eps,
        sliding_window=min(windows) if windows else None,
        mlp_bias=cfg.mlp_bias,
        lm_head_bias=False,
        tie_word_embeddings=cfg.tied_embeddings,
        mamba_expand=ssm.channels // cfg.hidden_dim,
        mamba_d_state=ssm.state_dim,
        mamba_d_conv=ssm.conv_kernel,
        mamba_dt_rank=ssm.dt_rank,
        scan_chunk_size=ssm.chunk_size,
    )


# our leaf -> the checkpoint's name under `model.layers.{i}.attn.`;
# matrices are stored [out, in] there and [in, out] here.
_SCAN_MATS = {"in_proj": "in_proj", "x_proj": "x_proj", "dt_proj": "dt_proj",
              "out_proj": "out_proj"}
_SCAN_VECS = {"A_log": "A_log", "D": "D", "dt_bias": "dt_proj.bias",
              "conv_b": "conv1d.bias"}
_GMU_MATS = {"w_in": "in_proj", "w_out": "out_proj"}


def _layer_from_hf(sd, i: int, kind: LayerKind, cfg: TransformerConfig) -> Dict:
    base = f"model.layers.{i}"
    w = lambda name: sd[name].astype(np.float32)
    t = lambda name: np.ascontiguousarray(w(name).T)
    norm = lambda name: {"weight": w(f"{base}.{name}.weight"),
                         "bias": w(f"{base}.{name}.bias")}
    fc1 = t(f"{base}.mlp.fc1.weight")  # [D, gate | up]
    F = cfg.intermediate_dim
    lp = {"ln1": norm("input_layernorm"), "ln2": norm("post_attention_layernorm"),
          "mlp": {"w_gate": fc1[:, :F], "w_up": fc1[:, F:],
                  "w_down": t(f"{base}.mlp.fc2.weight")}}
    at = f"{base}.attn"
    if kind.mixer == "ssm":
        ssm = {ours: t(f"{at}.{theirs}.weight") for ours, theirs in _SCAN_MATS.items()}
        ssm.update({ours: w(f"{at}.{theirs}") for ours, theirs in _SCAN_VECS.items()})
        ssm["conv_w"] = t(f"{at}.conv1d.weight")[:, 0, :]  # [K, 1, C] -> [K, C]
        lp["ssm"] = ssm
    elif kind.mixer == "gmu":
        lp["gmu"] = {ours: t(f"{at}.{theirs}.weight")
                     for ours, theirs in _GMU_MATS.items()}
    else:
        qkv, b = t(f"{at}.Wqkv.weight"), w(f"{at}.Wqkv.bias")
        q, kv = cfg.q_dim, cfg.kv_dim
        attn = {"wq": qkv[:, :q], "bq": b[:q],
                "wo": t(f"{at}.out_proj.weight"), "bo": w(f"{at}.out_proj.bias"),
                "sub_norm": w(f"{at}.inner_cross_attn.subln.weight")}
        if kind.reads is None:
            attn.update(wk=qkv[:, q:q + kv], wv=qkv[:, q + kv:],
                        bk=b[q:q + kv], bv=b[q + kv:])
        attn.update({name: w(f"{at}.inner_cross_attn.{name}") for name in DIFF_LAMBDAS})
        lp["attn"] = attn
    return lp


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    kinds = cfg.kinds()
    layers = [_layer_from_hf(sd, i, k, cfg) for i, k in enumerate(kinds)]
    f32 = lambda name: sd[name].astype(np.float32)
    params = {
        "embedding": {"weight": f32("model.embed_tokens.weight")},
        "final_norm": {"weight": f32("model.final_layernorm.weight"),
                       "bias": f32("model.final_layernorm.bias")},
    }
    for path, idx in cfg.stack_paths().values():
        _stack_at(params, path, stack_layers([layers[i] for i in idx]))
    if cfg.is_critic:
        params["head"] = {"weight": np.ascontiguousarray(f32("score.weight").T)
                          if "score.weight" in sd
                          else np.zeros((cfg.hidden_dim, 1), np.float32)}
    elif not cfg.tied_embeddings:
        params["head"] = {"weight": np.ascontiguousarray(f32("lm_head.weight").T)}
    return params


def _params_to_hf(params: Dict, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    from areal_tpu.models.hf import unstack_layers
    from areal_tpu.models.transformer import _stack_at

    sd = {"model.embed_tokens.weight": np.asarray(params["embedding"]["weight"]),
          "model.final_layernorm.weight": np.asarray(params["final_norm"]["weight"]),
          "model.final_layernorm.bias": np.asarray(params["final_norm"]["bias"])}
    for path, idx in cfg.stack_paths().values():
        for i, lp in zip(idx, unstack_layers(_stack_at(params, path), len(idx))):
            base, at = f"model.layers.{i}", f"model.layers.{i}.attn"
            for ours, theirs in (("ln1", "input_layernorm"),
                                 ("ln2", "post_attention_layernorm")):
                sd[f"{base}.{theirs}.weight"] = lp[ours]["weight"]
                sd[f"{base}.{theirs}.bias"] = lp[ours]["bias"]
            mlp = lp["mlp"]
            sd[f"{base}.mlp.fc1.weight"] = np.concatenate(
                [mlp["w_gate"], mlp["w_up"]], axis=1).T
            sd[f"{base}.mlp.fc2.weight"] = mlp["w_down"].T
            if "ssm" in lp:
                for ours, theirs in _SCAN_MATS.items():
                    sd[f"{at}.{theirs}.weight"] = lp["ssm"][ours].T
                for ours, theirs in _SCAN_VECS.items():
                    sd[f"{at}.{theirs}"] = lp["ssm"][ours]
                sd[f"{at}.conv1d.weight"] = lp["ssm"]["conv_w"].T[:, None, :]
            elif "gmu" in lp:
                for ours, theirs in _GMU_MATS.items():
                    sd[f"{at}.{theirs}.weight"] = lp["gmu"][ours].T
            else:
                a = lp["attn"]
                own = "wk" in a
                sd[f"{at}.Wqkv.weight"] = np.concatenate(
                    [a["wq"]] + ([a["wk"], a["wv"]] if own else []), axis=1).T
                sd[f"{at}.Wqkv.bias"] = np.concatenate(
                    [a["bq"]] + ([a["bk"], a["bv"]] if own else []))
                sd[f"{at}.out_proj.weight"] = a["wo"].T
                sd[f"{at}.out_proj.bias"] = a["bo"]
                sd[f"{at}.inner_cross_attn.subln.weight"] = a["sub_norm"]
                for name in DIFF_LAMBDAS:
                    sd[f"{at}.inner_cross_attn.{name}"] = a[name]
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["head"]["weight"]).T
    elif not cfg.tied_embeddings:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    return sd


register_hf_family(
    "phi4flash",
    HFFamily(
        name="phi4flash",
        hf_model_type="phi4flash",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    ),
)
