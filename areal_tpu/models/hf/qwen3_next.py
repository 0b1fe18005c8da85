"""Qwen3-Next HF conversion: `model_type: qwen3_next` (Gated Delta Networks,
arXiv:2412.06464, beside gated softmax attention).

Every layer is a mixer and an expert MLP, each under the family's norm
(`x * rsqrt(mean x^2 + eps) * (1 + w)`: the 1 is folded into the weights
on the way in and taken out on the way back, as `models/hf/gemma.py`
does) with a residual. Layer `i` (from 0) is gated attention when
`(i + 1) % full_attention_interval == 0`, else a Gated DeltaNet:

- **Gated DeltaNet** (`models/config.KDAConfig` with `decay="head"`,
  `decay_input="column"`, `gate_rank=None`, `gate_act="silu"`;
  `ops/kda.py`): `linear_num_key_heads` key heads under
  `linear_num_value_heads` value heads of `linear_key_head_dim` =
  `linear_value_head_dim`, a convolution of `linear_conv_kernel_dim` taps,
  one decay a value head, a full-rank silu gate on the normed output (that
  one norm scales by `w`, not `1 + w`).
- **gated attention**: `num_attention_heads` / `num_key_value_heads` heads
  of `head_dim`, a norm on q and k a head (`qk_norm`, as `1 + w`), a
  sigmoid gate on the output from q's own projection (`attn_gate`), rotary
  over the first `partial_rotary_factor` of a head (`rotary_fraction`).
- **experts**: softmax over `num_experts` (here: `num_experts_routed`),
  `num_experts_per_tok` a token, renormalised (`norm_topk_prob`), each a
  SwiGLU of `moe_intermediate_size`, plus one shared SwiGLU of
  `shared_expert_intermediate_size` under `sigmoid(x w_s)`
  (`MoEConfig.shared_gate`).

Two keys are this repo's, not the published file's, for one chip's share
of an expert-parallel layer (models/moe.py `experts_held`):
`num_experts_routed` (the router's width, when `num_experts` counts only
the experts whose weights are here) and `experts_held_first`.

Refused by name: `mlp_only_layers` not empty, `decoder_sparse_step` over
1 (a dense layer between expert layers: no published file of this family
has one), `rope_scaling`, `use_sliding_window` true, key and value head
sizes that differ, value heads that key heads do not divide,
`norm_topk_prob` false, a prediction module (`num_nextn_predict_layers` /
`mtp_num_hidden_layers` over 0: its block is in no config key). Nothing of
the delta rule runs on a mesh that splits a row
(`models/transformer.forward` says so), and the cache paths refuse the
family (`TransformerConfig.require_plain_stack`).

The checkpoint's layout is written from memory of the released modelling
code (the catalog gives the config only): `linear_attn.in_proj_qkvz` `[Hk
(2 K + 2 r V), hidden]`, a key head's rows `[q K | k K | v r V | z r V]`
(r value heads a key head); `linear_attn.in_proj_ba` `[Hk 2 r, hidden]`, a
key head's rows `[b r | a r]`; `linear_attn.conv1d.weight` `[2 Hk K + Hv V,
1, taps]` over `[q | k | v]`; `linear_attn.{A_log, dt_bias}` `[Hv]`,
`linear_attn.norm.weight` `[V]`, `linear_attn.out_proj`; `self_attn.q_proj`
`[Hq 2 hd, hidden]`, a head's rows `[q hd | gate hd]`; `self_attn.{k,v,o}_proj`,
`self_attn.{q,k}_norm`; `mlp.gate`, `mlp.experts.{e}.{gate,up,down}_proj`,
`mlp.shared_expert.*`, `mlp.shared_expert_gate` `[1, hidden]`.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from areal_tpu.api.model_api import register_hf_family
from areal_tpu.models.config import KDAConfig, LayerKind, MoEConfig, TransformerConfig
from areal_tpu.models.hf import HFFamily

MODEL_TYPE = "qwen3_next"
CHUNK = 64  # positions the delta rule takes at a time (ops/kda.py)


def _kinds(n_layers: int, interval: int):
    return tuple(
        LayerKind(mlp="moe") if (i + 1) % interval == 0 else LayerKind(mlp="moe", mixer="kda")
        for i in range(n_layers))


def _config_from_hf(hf: Dict[str, Any], is_critic: bool = False) -> TransformerConfig:
    refuse = lambda what: NotImplementedError(f"{MODEL_TYPE}: {what}")
    if hf.get("mlp_only_layers"):
        raise refuse(f"mlp_only_layers={hf['mlp_only_layers']}: a dense layer among the "
                     "expert layers is in no published file of this family")
    if int(hf.get("decoder_sparse_step", 1)) != 1:
        raise refuse(f"decoder_sparse_step={hf['decoder_sparse_step']}: every layer of "
                     "the published model is an expert layer")
    if hf.get("rope_scaling"):
        raise refuse(f"rope_scaling={hf['rope_scaling']}: how a scaled table meets a "
                     "partial rotation is in no released file")
    if hf.get("use_sliding_window"):
        raise refuse("use_sliding_window true: the attention layers of the published "
                     "model see their whole sequence")
    for key in ("num_nextn_predict_layers", "mtp_num_hidden_layers"):
        if hf.get(key, 0):
            raise refuse(f"{key}={hf[key]}: the prediction module's block is in no "
                         "config key; models/transformer.py's module is a transformer "
                         "block of the stack's last kind and would be a guess here")
    K, V = int(hf["linear_key_head_dim"]), int(hf["linear_value_head_dim"])
    Hk, Hv = int(hf["linear_num_key_heads"]), int(hf["linear_num_value_heads"])
    if K != V:
        raise refuse(f"linear_key_head_dim {K} != linear_value_head_dim {V}: ops/kda.py "
                     "keeps a square state a head")
    if Hv % Hk:
        raise refuse(f"linear_num_key_heads {Hk} do not divide linear_num_value_heads {Hv}")
    if not hf.get("norm_topk_prob", True):
        raise refuse("norm_topk_prob false: models/moe.py's softmax router renormalises "
                     "the chosen gates")
    kda = KDAConfig(
        n_heads=Hv, n_key_heads=Hk, head_dim=K,
        conv_kernel=int(hf["linear_conv_kernel_dim"]), gate_rank=None, chunk_size=CHUNK,
        decay="head", decay_input="column", gate_act="silu")
    held = int(hf["num_experts"])
    routed = int(hf.get("num_experts_routed", held))
    first = int(hf.get("experts_held_first", 0))
    moe = MoEConfig(
        num_experts=routed,
        top_k=int(hf["num_experts_per_tok"]),
        dispatch="dropless",
        aux_loss_coef=0.0,
        expert_intermediate_dim=int(hf["moe_intermediate_size"]),
        score_func="softmax", route_norm=True,
        n_shared_experts=1,
        shared_intermediate_dim=int(hf["shared_expert_intermediate_size"]),
        shared_gate=True,
        experts_held=(first, held) if (first, held) != (0, routed) else None,
    )
    n_layers = int(hf["num_hidden_layers"])
    return TransformerConfig(
        n_layers=n_layers,
        hidden_dim=int(hf["hidden_size"]),
        n_q_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        intermediate_dim=int(hf["intermediate_size"]),  # no layer uses it
        vocab_size=int(hf["vocab_size"]),
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        activation=hf.get("hidden_act", "silu"), mlp_type="gated",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rotary_base=float(hf.get("rope_theta", 10000.0)),
        rotary_fraction=float(hf.get("partial_rotary_factor", 1.0)),
        qk_norm=True, attn_gate=True,
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        is_critic=is_critic,
        moe=moe, kda=kda,
        layer_kinds=_kinds(n_layers, int(hf["full_attention_interval"])),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, kda = cfg.moe, cfg.kda
    full = [i for i, k in enumerate(cfg.kinds()) if k.mixer == "attention"]
    hf: Dict[str, Any] = dict(
        architectures=["Qwen3NextForCausalLM"],
        model_type=MODEL_TYPE,
        num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.hidden_dim,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        full_attention_interval=full[0] + 1 if full else cfg.n_layers + 1,
        linear_num_key_heads=kda.key_heads, linear_num_value_heads=kda.n_heads,
        linear_key_head_dim=kda.head_dim, linear_value_head_dim=kda.head_dim,
        linear_conv_kernel_dim=kda.conv_kernel,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act=cfg.activation,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base, rope_scaling=None,
        partial_rotary_factor=cfg.rotary_fraction,
        tie_word_embeddings=cfg.tied_embeddings,
        decoder_sparse_step=1, mlp_only_layers=[],
        num_experts=moe.n_held,
        num_experts_per_tok=moe.top_k,
        moe_intermediate_size=moe.expert_intermediate_dim,
        shared_expert_intermediate_size=moe.shared_intermediate_dim,
        norm_topk_prob=moe.route_norm,
        use_sliding_window=False,
        torch_dtype="bfloat16",
    )
    if moe.experts_held is not None:
        hf.update(num_experts_routed=moe.num_experts,
                  experts_held_first=moe.experts_held[0])
    return hf


# our leaf under a layer -> the checkpoint's name under `model.layers.{i}.`;
# matrices are stored [out, in] there and [in, out] here. A norm of the
# family holds w there and 1 + w here (`_ONE`).
_ONE = np.float32(1.0)
_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}
_MLP_MATS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _gdn_from_hf(sd, at: str, kda: KDAConfig) -> Dict:
    Hk, K, r = kda.key_heads, kda.head_dim, kda.n_heads // kda.key_heads
    w = lambda name: sd[f"{at}.{name}"].astype(np.float32)
    D = w("in_proj_qkvz.weight").shape[1]
    qkvz = w("in_proj_qkvz.weight").reshape(Hk, 2 * K + 2 * r * K, D)  # a key head's rows
    q, k, v, z = np.split(qkvz, [K, 2 * K, 2 * K + r * K], axis=1)
    ba = w("in_proj_ba.weight").reshape(Hk, 2 * r, D)
    # [Hk, rows, D] -> [D, Hk rows]: heads in order, a head's rows together
    mat = lambda a: np.ascontiguousarray(a.reshape(-1, D).T)
    conv = w("conv1d.weight")[:, 0, :].T  # [channels, 1, taps] -> [taps, channels]
    conv_q, conv_k, conv_v = np.split(conv, [Hk * K, 2 * Hk * K], axis=1)
    return {
        "wq": mat(q), "wk": mat(k), "wv": mat(v), "w_g": mat(z),
        "w_b": mat(ba[:, :r]), "w_a": mat(ba[:, r:]),
        "conv_q": np.ascontiguousarray(conv_q), "conv_k": np.ascontiguousarray(conv_k),
        "conv_v": np.ascontiguousarray(conv_v),
        "A_log": w("A_log").reshape(-1), "dt_bias": w("dt_bias").reshape(-1),
        "o_norm": w("norm.weight"),
        "wo": np.ascontiguousarray(w("out_proj.weight").T),
    }


def _gdn_to_hf(sd, at: str, kp: Dict, kda: KDAConfig) -> None:
    Hk, K, r = kda.key_heads, kda.head_dim, kda.n_heads // kda.key_heads
    heads = lambda a: np.asarray(a).T.reshape(Hk, -1, np.asarray(a).shape[0])  # [Hk, rows, D]
    sd[f"{at}.in_proj_qkvz.weight"] = np.concatenate(
        [heads(kp[n]) for n in ("wq", "wk", "wv", "w_g")], axis=1).reshape(
            Hk * (2 * K + 2 * r * K), -1)
    sd[f"{at}.in_proj_ba.weight"] = np.concatenate(
        [heads(kp["w_b"]), heads(kp["w_a"])], axis=1).reshape(Hk * 2 * r, -1)
    sd[f"{at}.conv1d.weight"] = np.concatenate(
        [np.asarray(kp[n]) for n in ("conv_q", "conv_k", "conv_v")], axis=1).T[:, None, :]
    sd[f"{at}.A_log"], sd[f"{at}.dt_bias"] = np.asarray(kp["A_log"]), np.asarray(kp["dt_bias"])
    sd[f"{at}.norm.weight"] = np.asarray(kp["o_norm"])
    sd[f"{at}.out_proj.weight"] = np.asarray(kp["wo"]).T


def _layer_from_hf(sd, i: int, kind: LayerKind, cfg: TransformerConfig) -> Dict:
    base, moe = f"model.layers.{i}", cfg.moe
    t = lambda name: np.ascontiguousarray(sd[name].astype(np.float32).T)
    w = lambda name: sd[name].astype(np.float32)
    mats = lambda prefix: {ours: t(f"{prefix}.{theirs}.weight")
                           for ours, theirs in _MLP_MATS.items()}
    layer = {ours: {"weight": w(f"{base}.{theirs}.weight") + _ONE}
             for ours, theirs in _NORMS.items()}
    if kind.mixer == "kda":
        layer["kda"] = _gdn_from_hf(sd, f"{base}.linear_attn", cfg.kda)
    else:
        at, hd, D = f"{base}.self_attn", cfg.head_dim, cfg.hidden_dim
        qg = w(f"{at}.q_proj.weight").reshape(cfg.n_q_heads, 2 * hd, D)  # a head: q | gate
        layer["attn"] = {
            "wq": np.ascontiguousarray(qg[:, :hd].reshape(-1, D).T),
            "wg": np.ascontiguousarray(qg[:, hd:].reshape(-1, D).T),
            "wk": t(f"{at}.k_proj.weight"), "wv": t(f"{at}.v_proj.weight"),
            "wo": t(f"{at}.o_proj.weight"),
            "q_norm": w(f"{at}.q_norm.weight") + _ONE,
            "k_norm": w(f"{at}.k_norm.weight") + _ONE,
        }
    first, held = moe.experts_held or (0, moe.num_experts)
    sp = f"{base}.mlp"
    experts = [mats(f"{sp}.experts.{e}") for e in range(first, first + held)]
    layer["mlp"] = {k: np.stack([x[k] for x in experts]) for k in _MLP_MATS}
    layer["mlp"]["router"] = t(f"{sp}.gate.weight")
    layer["mlp"]["shared"] = {**mats(f"{sp}.shared_expert"),
                              "w_s": t(f"{sp}.shared_expert_gate.weight")}
    return layer


def _layer_to_hf(sd, i: int, lp: Dict, cfg: TransformerConfig) -> None:
    base = f"model.layers.{i}"
    put = lambda prefix, tree: sd.update(
        {f"{prefix}.{theirs}.weight": np.asarray(tree[ours]).T
         for ours, theirs in _MLP_MATS.items()})
    for ours, theirs in _NORMS.items():
        sd[f"{base}.{theirs}.weight"] = np.asarray(lp[ours]["weight"]) - _ONE
    if "kda" in lp:
        _gdn_to_hf(sd, f"{base}.linear_attn", lp["kda"], cfg.kda)
    else:
        at, ap, hd = f"{base}.self_attn", lp["attn"], cfg.head_dim
        heads = lambda a: np.asarray(a).T.reshape(cfg.n_q_heads, hd, -1)
        sd[f"{at}.q_proj.weight"] = np.concatenate(
            [heads(ap["wq"]), heads(ap["wg"])], axis=1).reshape(cfg.n_q_heads * 2 * hd, -1)
        for ours, theirs in (("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            sd[f"{at}.{theirs}.weight"] = np.asarray(ap[ours]).T
        sd[f"{at}.q_norm.weight"] = np.asarray(ap["q_norm"]) - _ONE
        sd[f"{at}.k_norm.weight"] = np.asarray(ap["k_norm"]) - _ONE
    mlp, sp = lp["mlp"], f"{base}.mlp"
    first = cfg.moe.experts_held[0] if cfg.moe.experts_held else 0
    sd[f"{sp}.gate.weight"] = np.asarray(mlp["router"]).T
    for e in range(mlp["w_gate"].shape[0]):
        put(f"{sp}.experts.{first + e}", {k: mlp[k][e] for k in _MLP_MATS})
    put(f"{sp}.shared_expert", mlp["shared"])
    sd[f"{sp}.shared_expert_gate.weight"] = np.asarray(mlp["shared"]["w_s"]).T


def _params_from_hf(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    from areal_tpu.models.hf import stack_layers
    from areal_tpu.models.transformer import _stack_at

    layers = [_layer_from_hf(sd, i, k, cfg) for i, k in enumerate(cfg.kinds())]
    params = {
        "embedding": {"weight": sd["model.embed_tokens.weight"].astype(np.float32)},
        "final_norm": {"weight": sd["model.norm.weight"].astype(np.float32) + _ONE},
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
          "model.norm.weight": np.asarray(params["final_norm"]["weight"]) - _ONE}
    for path, idx in cfg.stack_paths().values():
        for i, lp in zip(idx, unstack_layers(_stack_at(params, path), len(idx))):
            _layer_to_hf(sd, i, lp, cfg)
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
