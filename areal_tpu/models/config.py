"""Transformer architecture configuration.

Counterpart of the reference's ReaLModelConfig (realhf/api/core/model_api.py:340),
covering the same architecture space: GQA attention, rotary variants,
RMS/LayerNorm, gated MLPs, optional MoE, actor (LM head) or critic (scalar
head) outputs, tied embeddings, and qk-norm (qwen3); and, beyond it, a
kind per layer (`LayerKind`: MLP, attention window, rotary), an attention
output gate, post-norms, a sigmoid router with a selection bias, shared
experts and a share of the experts held here (afmoe).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # Expert capacity = capacity_factor * T * top_k / num_experts; tokens
    # beyond it are dropped (standard einsum-MoE training approximation;
    # >= num_experts / top_k guarantees no drops).
    capacity_factor: float = 1.25
    routed_scaling_factor: float = 1.0
    aux_loss_coef: float = 1e-3
    z_loss_coef: float = 0.0
    # Size of each expert's hidden dim; defaults to intermediate_dim.
    expert_intermediate_dim: Optional[int] = None
    # "capacity": GShard einsum dispatch, [T,E,C] tensors — three large
    #   MXU einsums, shards cleanly for expert parallelism, DROPS tokens
    #   beyond capacity (drop rate surfaced in train stats as
    #   moe_drop_rate). "dropless": sort-by-expert + lax.ragged_dot
    #   grouped matmuls — zero drops at any router skew (the reference
    #   dispatcher's guarantee, token_dispatcher.py), static shapes;
    #   when the mesh's fsdp extent divides num_experts it runs
    #   expert-parallel via shard_map (models/moe.py _moe_mlp_ep: each
    #   shard computes only its own experts' ragged grouped matmuls and
    #   results combine with psum_scatter), otherwise it falls back to
    #   the single-program GSPMD path. Tradeoff documented in
    #   docs/perf_notes.md (Round 17).
    dispatch: str = "capacity"
    # Leading dense layers before the expert layers (they run outside
    # the layer scan: models/transformer.py).
    first_k_dense: int = 0
    # Router form. "softmax": probabilities over all experts, top-k,
    # renormalised (mixtral). "sigmoid": scores s = sigmoid(logits);
    # the k experts are chosen on s + expert_bias (a buffer the RL step
    # never updates) and weighted by the bare s, divided by their sum
    # when `route_norm`, times `routed_scaling_factor`.
    score_func: str = "softmax"
    route_norm: bool = True
    router_bias: bool = False
    # Shared experts: one gated MLP of width n_shared_experts *
    # expert width that every token passes through, added to the routed
    # result.
    n_shared_experts: int = 0
    # (first, count): the contiguous range of experts whose weights this
    # chip holds, as one share of an expert-parallel layer. The router
    # keeps all `num_experts` outputs and its top-k; the layer computes
    # the held experts' part of the result and adds nothing for the
    # rest. None = all of them. Needs dispatch="dropless".
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"MoEConfig.score_func must be 'softmax' or 'sigmoid', "
                f"got {self.score_func!r}"
            )
        if self.experts_held is not None:
            first, count = (int(v) for v in self.experts_held)
            self.experts_held = (first, count)
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} is not a range of the "
                    f"{self.num_experts} experts"
                )
            if self.dispatch != "dropless":
                raise ValueError(
                    "experts_held needs dispatch='dropless': the capacity "
                    "einsum has no form that computes a share of the experts"
                )
        if self.dispatch not in ("capacity", "dropless"):
            # A typo here would silently fall through to capacity
            # dispatch — the exact drop risk "dropless" exists to remove.
            raise ValueError(
                f"MoEConfig.dispatch must be 'capacity' or 'dropless', "
                f"got {self.dispatch!r}"
            )


    @property
    def n_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer of the stack is, known when the program is traced:
    its MLP ("dense" or "moe": different parameter shapes), its attention
    mask (`window` = how many positions back a token sees, itself
    included; None = all of its sequence) and whether q and k get the
    rotary embedding (False = no position encoding in this layer)."""

    mlp: str = "dense"
    window: Optional[int] = None
    rotary: bool = True

    def __post_init__(self):
        if self.mlp not in ("dense", "moe"):
            raise ValueError(f"LayerKind.mlp must be 'dense' or 'moe', got {self.mlp!r}")
        if self.window is not None and self.window < 1:
            raise ValueError(f"LayerKind.window must be >= 1, got {self.window}")


@dataclasses.dataclass(eq=False)  # eq=False keeps it hashable (by id) for jit static args
class TransformerConfig:
    n_layers: int = 2
    hidden_dim: int = 64
    n_q_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    intermediate_dim: int = 128
    vocab_size: int = 128
    max_position_embeddings: int = 2048

    activation: str = "silu"  # silu | gelu
    mlp_type: str = "gated"  # gated | plain
    norm_type: str = "rms"  # rms | layer
    norm_eps: float = 1e-6

    # Position encoding: "rotary" (default) or "learned" absolute
    # embeddings (gpt2).
    pos_emb: str = "rotary"
    rotary_base: float = 10000.0
    rotary_scaling: Optional[float] = None
    rotary_scaling_type: Optional[str] = None  # linear | llama3 | None
    # Extra factors for llama3-style scaling (low/high_freq_factor,
    # original_max_position_embeddings), carried from the HF config.
    rotary_scaling_params: Optional[dict] = None
    rotary_interleaved: bool = False

    attn_bias: bool = False  # qwen2 uses qkv bias
    attn_out_bias: bool = False  # gpt2 also biases the output projection
    mlp_bias: bool = False
    qk_norm: bool = False  # qwen3 per-head RMSNorm on q/k
    # a = (softmax(qk)v) * sigmoid(h Wg) before the output projection.
    attn_gate: bool = False
    # Four norms a layer: the attention and MLP outputs are normalised
    # (ln1_post, ln2_post) before they join the residual stream.
    post_norms: bool = False
    tied_embeddings: bool = False
    embedding_multiplier: Optional[float] = None  # gemma normalizer

    is_critic: bool = False
    moe: Optional[MoEConfig] = None
    # One LayerKind a layer, filled by the family from the published
    # config; None = every layer the same (moe or dense by `moe`, full
    # causal attention, rotary by `pos_emb`).
    layer_kinds: Optional[Tuple[LayerKind, ...]] = None

    # Numerics: params kept in param_dtype, compute in compute_dtype.
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_q_heads % self.n_kv_heads != 0:
            raise ValueError("n_q_heads must be a multiple of n_kv_heads")
        if isinstance(self.moe, dict):
            # Experiment configs arrive as plain kwargs dicts
            # (cli_args ModelTrainEvalConfig.config -> factories.py
            # TransformerConfig(**config)); coerce the nested MoE block
            # so `model.config.moe.num_experts=8` works end-to-end.
            self.moe = MoEConfig(**self.moe)
        if self.layer_kinds is not None:
            self.layer_kinds = tuple(
                LayerKind(**k) if isinstance(k, dict) else k
                for k in self.layer_kinds
            )
            if len(self.layer_kinds) != self.n_layers:
                raise ValueError(
                    f"layer_kinds has {len(self.layer_kinds)} entries for "
                    f"{self.n_layers} layers"
                )
        kinds = self.kinds()
        if any(k.mlp == "moe" for k in kinds) and self.moe is None:
            raise ValueError("a layer of kind 'moe' needs TransformerConfig.moe")
        rest = kinds[self.n_lead_layers:]
        if any(k.mlp != rest[0].mlp for k in rest):
            raise NotImplementedError(
                "MLP kinds that alternate after the leading layers need a "
                "parameter stack per position of the period "
                "(models/transformer.py scans one stack): "
                f"{[k.mlp for k in kinds]}"
            )

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def kinds(self) -> Tuple[LayerKind, ...]:
        """The kind of every layer, in order."""
        if self.layer_kinds is not None:
            return self.layer_kinds
        rotary = self.pos_emb == "rotary"
        dense_first = self.moe.first_k_dense if self.moe is not None else self.n_layers
        return tuple(
            LayerKind(mlp="dense" if i < dense_first else "moe", rotary=rotary)
            for i in range(self.n_layers)
        )

    @property
    def n_lead_layers(self) -> int:
        """Leading layers whose MLP differs from the last layer's: their
        parameters are a stack of their own (`params["lead_layers"]`) and
        they run before the scan over `params["layers"]`."""
        kinds = self.kinds()
        n = 0
        while n < len(kinds) and kinds[n].mlp != kinds[-1].mlp:
            n += 1
        return n

    @property
    def n_moe_layers(self) -> int:
        return sum(k.mlp == "moe" for k in self.kinds())

    @property
    def one_kind(self) -> bool:
        """Every layer the same, full causal attention, rotary as
        `pos_emb` says: what the KV-cache paths (generation, serving,
        paged) can run."""
        kinds = self.kinds()
        plain = LayerKind(mlp=kinds[0].mlp, rotary=self.pos_emb == "rotary")
        return all(k == plain for k in kinds)

    def require_plain_stack(self, where: str) -> None:
        """The KV-cache paths (models/generation.py, engine/paged.py,
        engine/serving.py) hold one kind of layer and run the plain
        block: refuse what they would otherwise compute wrongly, naming
        what is missing."""
        missing = []
        if not self.one_kind:
            missing.append(
                "a cache manager with a kind per layer (window layers keep "
                "the last `window` positions, full layers all; rotary or "
                "none per layer): the layers here are "
                f"{sorted({(k.mlp, k.window or 0, k.rotary) for k in self.kinds()})}"
            )
        if self.attn_gate or self.post_norms:
            missing.append(
                "the attention output gate and the post-attention / post-MLP "
                "norms in the decode layer"
            )
        if self.moe is not None and (
            self.moe.experts_held is not None or self.moe.score_func != "softmax"
            or self.moe.n_shared_experts
        ):
            missing.append(
                "the sigmoid router, shared expert and held-experts share in "
                "the decode layer's expert MLP"
            )
        if missing:
            raise NotImplementedError(
                f"{where} cannot run this configuration; it lacks "
                + "; and ".join(missing)
            )

    def layer_uses_moe(self, layer_idx: int) -> bool:
        return self.kinds()[layer_idx].mlp == "moe"
