"""Olmo-Hybrid HF conversion: `model_type: olmo_hybrid` (Gated DeltaNet layers,
arXiv:2412.06464, at its authors' `expand_v` 2 sizing with the doubled beta of
arXiv:2411.12537, beside plain softmax attention without rotary, every layer
under a dense SwiGLU, in the Olmo 2 / Olmo 3 block, arXiv:2501.00656).

`layer_types` gives the kind of every layer (`linear_attention` or
`full_attention`; the published pattern is three to one, eight times):

- **block, both kinds** (the family's convention; the config has no key for
  it): output norms only, `h = x + norm(mixer(x))`, `y = h + norm(mlp(h))`,
  no norm on the way in (`TransformerConfig(pre_norms=False,
  post_norms=True)`), a final norm before an untied head.
- **linear_attention** (`models/config.KDAConfig` with `decay="head"`,
  `decay_input="column"`, `gate_rank=None`, `gate_act="silu"`,
  `value_head_dim`, `neg_eigval`; `ops/kda.py`): `linear_num_value_heads`
  heads over as many or fewer `linear_num_key_heads`, keys of
  `linear_key_head_dim` and values of `linear_value_head_dim` (a state of 96
  x 192 a head in the published file), a convolution of
  `linear_conv_kernel_dim` taps on q, k and v, one decay a head, beta `2
  sigmoid` under `linear_allow_neg_eigval`, a full-rank silu gate on the
  head-normed output.
- **full_attention**: `num_attention_heads` / `num_key_value_heads` heads of
  `hidden_size / num_attention_heads`, q and k under one RMSNorm over their
  whole projected width before the split into heads
  (`qk_norm_over="width"`: Olmo 2's), no rotary where
  `rope_parameters.rope_theta` is null (the published file), plain rotary at
  that theta where it is a number.
- **MLP**: a dense SwiGLU of `intermediate_size` in every layer.

No key here is this repo's own (`vocab_size` cut to a slice of the rows is a
smaller embedding and head: ids and softmax over the slice).

Refused by name: `attention_bias` true, a `rope_parameters` with any key but
`rope_theta` (a scaled table without a published layer to check it against),
`sliding_window`, a `layer_types` entry that is neither kind, value heads
that key heads do not divide. Nothing of the delta rule runs on a mesh that
splits a row (`models/transformer.forward` says so), and the cache paths
refuse the family (`TransformerConfig.require_plain_stack`: no slot for a
recurrent state of any width).

The checkpoint's layout is **written from memory** of the released modelling
code and of flash-linear-attention's `GatedDeltaNet` (the catalog gives the
config only; no test here reads a released file): under `model.layers.{i}.`
`linear_attn.{q,k,v}_proj.weight` `[Hk K | Hk K | Hv V, hidden]`,
`linear_attn.{a,b}_proj.weight` `[Hv, hidden]`, `linear_attn.g_proj.weight`
`[Hv V, hidden]`, `linear_attn.{q,k,v}_conv1d.weight` `[channels, 1, taps]`,
`linear_attn.{A_log, dt_bias}` `[Hv]`, `linear_attn.o_norm.weight` `[V]`,
`linear_attn.o_proj.weight`; `self_attn.{q,k,v,o}_proj.weight`,
`self_attn.{q,k}_norm.weight` `[heads hd]`; `post_attention_layernorm`,
`post_feedforward_layernorm`; `mlp.{gate,up,down}_proj.weight`;
`model.embed_tokens.weight`, `model.norm.weight`, `lm_head.weight`.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import KDAConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "olmo_hybrid"
CHUNK = 64  # positions the delta rule takes at a time (ops/kda.py)
_KINDS = {"linear_attention": "kda", "full_attention": "attention"}


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    refuse = lambda what: NotImplementedError(f"{MODEL_TYPE}: {what}")
    if hf.get("attention_bias"):
        raise refuse("attention_bias true: the published model has no bias anywhere")
    if hf.get("sliding_window"):
        raise refuse(f"sliding_window={hf['sliding_window']}: the attention layers of "
                     "the published model see their whole sequence")
    rope = dict(hf.get("rope_parameters") or {})
    theta = rope.pop("rope_theta", hf.get("rope_theta"))
    if {k: v for k, v in rope.items() if v is not None and k != "rope_type"} or (
            rope.get("rope_type") not in (None, "default")):
        raise refuse(f"rope_parameters={hf['rope_parameters']}: a scaled rotary table "
                     "is in no published file of this family")
    types = list(hf["layer_types"])
    n_layers = int(hf["num_hidden_layers"])
    if len(types) != n_layers or set(types) - set(_KINDS):
        raise refuse(f"layer_types has {len(types)} entries {sorted(set(types))} for "
                     f"{n_layers} layers of kinds {sorted(_KINDS)}")
    Hk, Hv = int(hf["linear_num_key_heads"]), int(hf["linear_num_value_heads"])
    if Hv % Hk:
        raise refuse(f"linear_num_key_heads {Hk} do not divide linear_num_value_heads {Hv}")
    kda = KDAConfig(
        n_heads=Hv, n_key_heads=Hk, head_dim=int(hf["linear_key_head_dim"]),
        value_head_dim=int(hf["linear_value_head_dim"]),
        neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
        conv_kernel=int(hf["linear_conv_kernel_dim"]), gate_rank=None, chunk_size=CHUNK,
        decay="head", decay_input="column", gate_act="silu")
    D, Hq = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    rotary = theta is not None
    return TransformerConfig(
        n_layers=n_layers,
        hidden_dim=D,
        n_q_heads=Hq,
        n_kv_heads=int(hf.get("num_key_value_heads") or Hq),
        head_dim=int(hf.get("head_dim") or D // Hq),
        intermediate_dim=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation=hf.get("hidden_act", "silu"), mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rotary_base=float(theta) if rotary else 10000.0,
        qk_norm=True, qk_norm_over="width",
        pre_norms=False, post_norms=True,
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        kda=kda,
        layer_kinds=tuple(
            LayerKind(mlp="dense", mixer="kda") if t == "linear_attention"
            else LayerKind(mlp="dense", rotary=rotary) for t in types),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    kda = cfg.kda
    kinds = cfg.kinds()
    names = {v: k for k, v in _KINDS.items()}
    rotary = any(k.rotary for k in kinds if k.mixer == "attention")
    return dict(
        architectures=["OlmoHybridForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        layer_types=[names[k.mixer] for k in kinds],
        linear_num_key_heads=kda.key_heads, linear_num_value_heads=kda.n_heads,
        linear_key_head_dim=kda.head_dim, linear_value_head_dim=kda.value_dim,
        linear_conv_kernel_dim=kda.conv_kernel,
        linear_allow_neg_eigval=kda.neg_eigval,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act=cfg.activation,
        rms_norm_eps=cfg.norm_eps,
        rope_parameters={"rope_theta": cfg.rotary_base if rotary else None},
        attention_bias=False,
        tie_word_embeddings=cfg.tied_embeddings,
        torch_dtype="bfloat16",
    )


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`
# (from memory: the module's docstring); matrices are stored [out, in] there
# and [in, out] here.
_NORMS = {"ln1_post": "post_attention_layernorm", "ln2_post": "post_feedforward_layernorm"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
_GDN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "w_a": "a_proj",
             "w_b": "b_proj", "w_g": "g_proj", "wo": "o_proj"}
_GDN_CONVS = {"conv_q": "q_conv1d", "conv_k": "k_conv1d", "conv_v": "v_conv1d"}
_GDN_VECS = {"A_log": "A_log", "dt_bias": "dt_bias", "o_norm": "o_norm.weight"}
_ATTN_MATS = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj"}
_ATTN_VECS = {"q_norm": "q_norm", "k_norm": "k_norm"}


def _layer_from_hf(sd, i: int, kind: LayerKind) -> Dict:
    base = f"model.layers.{i}"
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    layer: Dict[str, Any] = {ours: {"weight": w(f"{base}.{theirs}.weight")}
                             for ours, theirs in _NORMS.items()}
    if kind.mixer == "kda":
        at = f"{base}.linear_attn"
        layer["kda"] = {
            **{ours: t(f"{at}.{theirs}.weight") for ours, theirs in _GDN_MATS.items()},
            # [channels, 1, taps] -> [taps, channels]
            **{ours: np.ascontiguousarray(w(f"{at}.{theirs}.weight")[:, 0, :].T)
               for ours, theirs in _GDN_CONVS.items()},
            **{ours: w(f"{at}.{theirs}").reshape(-1) for ours, theirs in _GDN_VECS.items()},
        }
    else:
        at = f"{base}.self_attn"
        layer["attn"] = {
            **{ours: t(f"{at}.{theirs}.weight") for ours, theirs in _ATTN_MATS.items()},
            **{ours: w(f"{at}.{theirs}.weight") for ours, theirs in _ATTN_VECS.items()},
        }
    layer["mlp"] = {ours: t(f"{base}.mlp.{theirs}.weight")
                    for ours, theirs in _MLP_MATS.items()}
    return layer


def _layer_to_hf(sd, i: int, lp: Dict) -> None:
    base = f"model.layers.{i}"
    put = lambda at, tree, names: sd.update(
        {f"{at}.{theirs}.weight": np.asarray(tree[ours]).T for ours, theirs in names.items()})
    for ours, theirs in _NORMS.items():
        sd[f"{base}.{theirs}.weight"] = np.asarray(lp[ours]["weight"])
    if "kda" in lp:
        at, kp = f"{base}.linear_attn", lp["kda"]
        put(at, kp, _GDN_MATS)
        for ours, theirs in _GDN_CONVS.items():
            sd[f"{at}.{theirs}.weight"] = np.asarray(kp[ours]).T[:, None, :]
        for ours, theirs in _GDN_VECS.items():
            sd[f"{at}.{theirs}"] = np.asarray(kp[ours])
    else:
        at, ap = f"{base}.self_attn", lp["attn"]
        put(at, ap, _ATTN_MATS)
        for ours, theirs in _ATTN_VECS.items():
            sd[f"{at}.{theirs}.weight"] = np.asarray(ap[ours])
    put(f"{base}.mlp", lp["mlp"], _MLP_MATS)


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    layers = [_layer_from_hf(sd, i, k) for i, k in enumerate(cfg.kinds())]
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
    for path, idx in cfg.stack_paths().values():
        for i, lp in zip(idx, unstack_layers(_stack_at(params, path), len(idx))):
            _layer_to_hf(sd, i, lp)
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
