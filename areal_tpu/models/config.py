"""Transformer architecture configuration.

Counterpart of the reference's ReaLModelConfig (realhf/api/core/model_api.py:340),
covering the same architecture space: GQA attention, rotary variants,
RMS/LayerNorm, gated MLPs, optional MoE, actor (LM head) or critic (scalar
head) outputs, tied embeddings, and qk-norm (qwen3); and, beyond it, a
kind per layer (`LayerKind`: the parts a layer has: a mixer, attention
with its window and rotary (the stack's one table or a named set of its
own, `RotarySet`), differential or latent or neither, a
state-space mixer in one of two forms, a delta-rule mixer (`KDAConfig`),
a gated short convolution (`ConvConfig`) or a gated memory unit, and an MLP,
dense or expert, either of which may be absent; a layer may keep a tensor
that later layers read), an attention output gate,
post-norms, a sigmoid router with a selection bias, shared experts, a
share of the experts held here, a multi-token-prediction module
after the stack (`MTPConfig`), an indexer beside attention that
chooses each query's keys (`IndexerConfig`), and a residual path of
several streams a token (`HyperConnConfig`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


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
    # What the sigmoid router adds to the chosen scores' sum before it
    # divides by it (`route_norm`): LFM2's modelling code says 1e-6.
    route_norm_eps: float = 1e-20
    router_bias: bool = False
    # Shared experts: one gated MLP of width n_shared_experts *
    # expert width that every token passes through, added to the routed
    # result.
    n_shared_experts: int = 0
    # The shared MLP's width where the config states it; None = the
    # expert width times n_shared_experts.
    shared_intermediate_dim: Optional[int] = None
    # The shared MLP's product times `sigmoid(x w_s)`, `w_s` hidden -> 1
    # without a bias (Qwen's `shared_expert_gate`).
    shared_gate: bool = False
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


@dataclasses.dataclass
class SSMConfig:
    """A state-space mixer, in one of two forms. "mamba2" (ops/ssm.py):
    `n_heads` heads of `head_dim` channels, each with a state of
    `state_dim`, whose B and C are shared by the heads of one of
    `n_groups` groups, and a scalar decay a head, so a chunk is matrix
    products. "mamba1" (ops/selective_scan.py): `channels` channels with
    a state of `state_dim` each and a decay for every channel and state,
    B and C shared by all channels, the step size through a projection
    of rank `dt_rank`; walked token by token. Both: a causal depthwise
    convolution of `conv_kernel` taps before it; computed in chunks of
    `chunk_size` positions (mamba1: the kernel's block of time)."""

    form: str = "mamba2"
    channels: Optional[int] = None  # mamba1
    dt_rank: Optional[int] = None  # mamba1
    n_heads: int = 8
    head_dim: int = 16
    n_groups: int = 1
    state_dim: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    conv_bias: bool = True
    # The step size's initial range: dt_bias is the inverse softplus of a
    # log-uniform draw from [dt_min, dt_max], floored at dt_floor.
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    def __post_init__(self):
        if self.form not in ("mamba2", "mamba1"):
            raise ValueError(
                f"SSMConfig.form must be 'mamba2' or 'mamba1', got {self.form!r}")
        if self.form == "mamba1" and not (self.channels and self.dt_rank):
            raise ValueError("SSMConfig.form 'mamba1' needs channels and dt_rank")
        if self.n_heads % self.n_groups != 0:
            raise ValueError("SSMConfig.n_heads must be a multiple of n_groups")

    @property
    def d_inner(self) -> int:
        if self.form == "mamba1":
            return self.channels
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.state_dim

    @property
    def in_proj_dim(self) -> int:
        """[z | xBC | dt]."""
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass
class ConvConfig:
    """A gated short convolution as a layer's mixer (LFM2's, `ops/ssm.py`
    `gated_conv_mixer`): `[B | C | x] = u W_in`, three parts of the hidden
    size each; `z_t = sum_j w_j (B * x)_{t-j}`, depthwise over `kernel`
    taps within a sequence, no activation; `(C * z) W_out`."""

    kernel: int = 3
    bias: bool = False

    def __post_init__(self):
        if self.kernel < 2:
            raise ValueError(f"ConvConfig.kernel must be >= 2 taps, got {self.kernel}")


@dataclasses.dataclass
class KDAConfig:
    """A delta-rule mixer (`ops/kda.py` has the equations), in the three
    published forms, side by side:

    ====================  =========================  ==========================  ==========================
    .                     Kimi Delta Attention       Gated DeltaNet              Gated DeltaNet, expand_v 2
                          (arXiv:2510.26692;         (arXiv:2412.06464,          (its authors' sizing with
                          defaults)                  Qwen3-Next)                 arXiv:2411.12537's beta;
                                                                                 Olmo-Hybrid)
    ====================  =========================  ==========================  ==========================
    `decay`               "channel": g `[T, H, K]`   "head": g `[T, H]`          "head"
    `decay_input`         "lowrank": `(h W_fa) W_fb` "column": `h W_a`           "column"
    `n_key_heads`         None: as many as `n_heads` divides `n_heads`: value    None
                                                     head j reads key head
                                                     `j // (H / Hk)`
    `value_head_dim`      None: V = K = `head_dim`   None                        V = 2 K: a state `[K, V]`
    `neg_eigval`          False: beta = sigmoid      False                       True: beta = 2 sigmoid
    `gate_rank`           an int: `(h W_ga) W_gb`    None: a full-rank `h W_g`   None
    `gate_act`            "sigmoid"                  "silu"                      "silu"
    state a value head    `Diag(exp(g_t))` S         `exp(g_t)` S                `exp(g_t)` S
    ====================  =========================  ==========================  ==========================

    All: `n_heads` (value) heads whose keys are `head_dim` wide (K) and
    whose values are `value_head_dim` wide (V; None: as the keys), a state
    of K x V a value head, q, k and v each
    through a causal depthwise convolution of `conv_kernel` taps and silu,
    q and k made unit a head, `g = -exp(A_log) softplus(x + dt_bias)`,
    beta a sigmoid a value head (doubled under `neg_eigval`: `I - beta k
    k^T` then has an eigenvalue in (-1, 1) along k), the recurrence
    computed in chunks of
    `chunk_size` positions, an RMSNorm a head (V wide) on the output under
    the gate. `dt_min`, `dt_max`, `dt_floor`: the seeded draw of `dt_bias`, as
    `SSMConfig`'s. A combination that has no code is refused here."""

    n_heads: int = 2
    head_dim: int = 16
    conv_kernel: int = 4
    gate_rank: Optional[int] = 16
    chunk_size: int = 64
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    n_key_heads: Optional[int] = None
    decay: str = "channel"  # channel | head
    decay_input: str = "lowrank"  # lowrank | column
    gate_act: str = "sigmoid"  # sigmoid | silu
    value_head_dim: Optional[int] = None  # None: as wide as the keys
    neg_eigval: bool = False  # beta = 2 sigmoid: in (0, 2)

    def __post_init__(self):
        if self.chunk_size % 16:
            raise ValueError(
                f"KDAConfig.chunk_size must be a multiple of 16 (the sub-blocks "
                f"inside which decays are taken cell by cell), got {self.chunk_size}")
        if self.decay not in ("channel", "head") or self.gate_act not in ("sigmoid", "silu"):
            raise ValueError(
                f"KDAConfig: decay is 'channel' or 'head' and gate_act 'sigmoid' or "
                f"'silu', got {self.decay!r} and {self.gate_act!r}")
        if self.decay_input != {"channel": "lowrank", "head": "column"}[self.decay]:
            raise NotImplementedError(
                f"KDAConfig: a decay a {self.decay} from decay_input "
                f"{self.decay_input!r}: models/transformer._kda_in makes a decay a "
                "channel from the low-rank pair ('lowrank') and a decay a head from "
                "a column of its own ('column'), and nothing else")
        if self.n_heads % self.key_heads:
            raise ValueError(
                f"KDAConfig: {self.key_heads} key heads do not divide "
                f"{self.n_heads} value heads")
        if self.key_heads != self.n_heads and self.decay == "channel":
            raise NotImplementedError(
                "KDAConfig: fewer key heads than value heads under a decay a "
                "channel: ops/kda.py's channel form scales a key by its own head's "
                "decays before every product and has no shared key to read")
        if self.gate_rank is None and self.decay_input == "lowrank":
            raise NotImplementedError(
                "KDAConfig: a full-rank output gate beside a low-rank decay is in no "
                "published model; models/transformer._kda_in has no such pair")
        if self.decay == "channel" and (self.value_dim != self.head_dim or self.neg_eigval):
            raise NotImplementedError(
                "KDAConfig: values wider than keys, or a doubled beta, under a decay a "
                "channel: the decay's low-rank product is d_inner wide and scales a "
                "key's channels (models/transformer._kda_in, ops/kda._intra_channel), "
                "so it has one width for both, and no published model doubles its beta")

    @property
    def key_heads(self) -> int:
        return self.n_heads if self.n_key_heads is None else self.n_key_heads

    @property
    def value_dim(self) -> int:
        """A value head's width V: the state a head is `[head_dim, V]`."""
        return self.head_dim if self.value_head_dim is None else self.value_head_dim

    @property
    def beta_scale(self) -> float:
        return 2.0 if self.neg_eigval else 1.0

    @property
    def d_inner(self) -> int:
        """v's, the gate's and the output's width."""
        return self.n_heads * self.value_dim

    @property
    def d_key(self) -> int:
        """q's and k's width."""
        return self.key_heads * self.head_dim

    @property
    def d_decay(self) -> int:
        """The decay's width: a channel of every value head, or a value head."""
        return self.d_inner if self.decay == "channel" else self.n_heads


@dataclasses.dataclass
class MLAConfig:
    """Latent attention: q and k, v come through low-rank projections
    with an RMSNorm inside each. With x the layer's normed input,
    `c_q = RMSNorm(x W_qa)` [q_rank], `q = c_q W_qb`: a head
    `[nope_dim | rope_dim]` (`q_rank` None: a full-rank `q = x W_q`, no
    norm); `[c_kv | k_r] = x W_kva` [kv_rank |
    rope_dim], `c_kv = RMSNorm(c_kv)`, `c_kv W_kvb`: a head `[k_nope
    nope_dim | v v_dim]`. Rotary goes over the rope part alone (a kind
    with `rotary=False`: over nothing, the rope part is then `rope_dim`
    more columns of q and of the shared key), and the
    one `k_r` a token is every head's. A head's q and k are `nope_dim +
    rope_dim` wide (`TransformerConfig.head_dim`, the softmax scale's),
    its v and output `v_dim`."""

    q_rank: Optional[int] = 24
    kv_rank: int = 16
    nope_dim: int = 8
    rope_dim: int = 8
    v_dim: int = 8
    # What the softmax scale `qk_dim^-0.5` is multiplied by: YaRN's
    # `mscale(factor, mscale_all_dim)^2` where the rope part's table is
    # scaled (DeepSeek-V3's `softmax_scale`), 1 otherwise.
    softmax_scale_factor: float = 1.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def softmax_scale(self) -> Optional[float]:
        """The attention call's scale, or None for its own `qk_dim^-0.5`."""
        if self.softmax_scale_factor == 1.0:
            return None
        return self.qk_dim ** -0.5 * self.softmax_scale_factor


@dataclasses.dataclass
class MTPConfig:
    """A multi-token-prediction module after the stack (DeepSeek-V3's):
    with h the stack's output after `final_norm` and e the embedding
    table, position i of a sequence gives `u_i = W_eh [RMSNorm_e(e[t_{i+1}])
    ; RMSNorm_h(h_i)]`, one more layer of the stack's last kind, an
    RMSNorm, and the model's own head: a prediction of `t_{i+2}`.
    `loss_weight` is what the training step gives its loss beside the
    caller's (0 = the step skips the module's pass). In that step the
    module's inputs h and e and the head are under `stop_gradient`: the
    caller's loss alone moves the model, and the module follows it."""

    n_modules: int = 1
    loss_weight: float = 0.1

    def __post_init__(self):
        if self.n_modules != 1:
            raise NotImplementedError(
                f"MTPConfig.n_modules={self.n_modules}: models/transformer.py "
                "runs one prediction module (what the published models have), "
                "not a chain of them")


@dataclasses.dataclass
class IndexerConfig:
    """A learned indexer beside attention (DeepSeek sparse attention): it
    scores every key of a query's causal prefix and attention reads the
    `top_k` best alone. With x the layer's normed input under
    `stop_gradient`: `qI = x W_Iq` (`n_heads` heads of `head_dim`),
    `kI = LayerNorm(x W_Ik)` (one head for all), `wI = x W_Iw`
    (`n_heads`), rotary over the whole `head_dim` of qI and kI, and
    `I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]) head_dim^-0.5
    n_heads^-0.5` in float32. A query with more than `top_k` keys in its
    sequence keeps those whose score is at least its `top_k`-th largest
    (ties at the threshold all kept); one with fewer keeps them all.
    Attention is dense attention under that mask, which is a constant of
    the backward pass. `loss_weight` is what a training step gives the
    indexer's own loss beside the caller's: the KL from the attention
    probabilities averaged over the q heads (under `stop_gradient`) to
    the softmax of `I` over the chosen keys, a mean over real tokens and
    over layers; 0 = the step skips that pass and no gradient reaches
    the indexer (an optimizer's weight decay still does). So the
    caller's loss alone moves the model and the KL alone moves the
    indexer; under global-norm clipping the two share the one norm."""

    n_heads: int = 4
    head_dim: int = 16
    top_k: int = 16
    loss_weight: float = 1.0
    norm_eps: float = 1e-6

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5 * self.n_heads ** -0.5


@dataclasses.dataclass
class HyperConnConfig:
    """Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
    Hyper-Connections, arXiv:2409.19606): a token has `n` residual
    streams `X` `[n, D]`, and each sublayer `F` (a layer's mixer, then its
    MLP) has `Phi` `[n D, n^2 + 2 n]`, `b` `[n^2 + 2 n]` and three scalars
    `a` = (pre, post, res). In float32, with `x~ = vec(X)`:

        m      = rsqrt(mean(x~^2) + norm_eps) * (x~ Phi)
        H_pre  = sigmoid(a_pre m[:n] + b[:n])                    [1, n]
        H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])             [1, n]
        M_0    = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), clamp))  [n, n]
        M_k    = cols(rows(M_{k-1})), rows(M) = M / (sum_j M + eps), cols
                 alike; H_res = M_{sinkhorn_iters}: doubly stochastic
        h = H_pre X;  y = F(norm(h));  X' = H_res X + H_post^T y

    The stack starts from `n` copies of the embedding and ends in the sum
    of the streams (`models/transformer._hc_read`, `_hc_write`)."""

    n: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)

    def __post_init__(self):
        self.clamp = tuple(float(c) for c in self.clamp)
        if self.n < 2 or self.sinkhorn_iters < 1 or len(self.clamp) != 2:
            raise ValueError(
                f"HyperConnConfig: n >= 2 streams, sinkhorn_iters >= 1 and a clamp "
                f"of (min, max), got {self}")

    @property
    def n_coef(self) -> int:
        """Columns of `Phi`: H_pre's n, H_post's n, H_res's n^2."""
        return self.n * (self.n + 2)


@dataclasses.dataclass(frozen=True)
class RotarySet:
    """One rotary table's parameters, for a stack whose layers do not all
    turn q and k by the same one (`TransformerConfig.rotary_sets`; a
    `LayerKind` names its own): the base, the scaling as
    `ops/rotary.rotary_inv_freq` takes it (`scaling_params` a dict), and
    `attention_factor`: what the table's `cos` and `sin` are multiplied
    by (HF's `attention_scaling` of a scaled table on a plain head; the
    logits carry its square)."""

    base: float = 10000.0
    scaling: Optional[float] = None
    scaling_type: Optional[str] = None
    scaling_params: Optional[dict] = None
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer of the stack is, known when the program is traced:
    the parts it has, each under its own norm with its own residual. A
    mixer (`mixer`: "attention", "ssm", "kda", "conv", "gmu" or None) and an MLP (`mlp`:
    "dense", "moe" or None); a transformer block has both, a layer may
    have one. For attention: its mask (`window` = how many positions
    back a token sees, itself included; None = all of its sequence),
    whether q and k get the rotary embedding (False = no position
    encoding in this layer) and by which table (`rotary_set`: a name in
    `TransformerConfig.rotary_sets`; None = the stack's one table), and
    `diff`: differential attention, two
    softmaxes a pair of heads, the second subtracted from the first
    (`transformer._diff_combine`), and `latent`: q and k, v through
    low-rank projections (`MLAConfig`, `transformer._latent_in`),
    and `indexed`: an indexer of its own parameters chooses the keys
    each query reads (`IndexerConfig`, `ops/indexer.py`). (The residual
    streams a layer's parts read and write are the stack's, not a layer's:
    `TransformerConfig.hyper`.)

    Two relations between layers. `keeps`: the layer hands a tensor on
    to later layers: an "ssm" mixer its scan's output before the gate,
    attention its k and v. `reads`: the index of the keeping layer whose
    tensor this layer reads: attention then has no k and v of its own
    (cross-attention over that layer's), and a "gmu" mixer (a gated
    memory unit: `(silu(u W_1) * m) W_2`, no recurrence of its own)
    always reads a scan's output m."""

    mlp: Optional[str] = "dense"
    window: Optional[int] = None
    rotary: bool = True
    mixer: Optional[str] = "attention"
    diff: bool = False
    keeps: bool = False
    reads: Optional[int] = None
    latent: bool = False
    indexed: bool = False
    rotary_set: Optional[str] = None

    def __post_init__(self):
        if self.mlp not in ("dense", "moe", None):
            raise ValueError(
                f"LayerKind.mlp must be 'dense', 'moe' or None, got {self.mlp!r}")
        if self.mixer not in ("attention", "ssm", "kda", "conv", "gmu", None):
            raise ValueError(
                "LayerKind.mixer must be 'attention', 'ssm', 'kda', 'conv', 'gmu' or "
                f"None, got {self.mixer!r}")
        if self.mixer is None and self.mlp is None:
            raise ValueError("a LayerKind needs a mixer or an MLP")
        if self.window is not None and self.window < 1:
            raise ValueError(f"LayerKind.window must be >= 1, got {self.window}")
        if self.mixer != "attention" and (
                self.window is not None or not self.rotary or self.diff
                or self.latent or self.indexed or self.rotary_set is not None):
            # one spelling a kind: layers without attention compare equal
            raise ValueError(
                "window, rotary, rotary_set, diff, latent and indexed describe an "
                "attention mixer")
        if self.rotary_set is not None and (
                not self.rotary or self.diff or self.latent or self.indexed
                or self.reads is not None):
            raise NotImplementedError(
                "a rotary set of a layer's own is a plain head's table: the layer "
                "rotates, and is no differential, latent or indexed attention, "
                "nor a reader of another layer's k and v (which come rotated by "
                "that layer's table)")
        if self.indexed and (self.window is not None or self.diff or self.latent
                             or self.keeps or self.reads is not None):
            raise NotImplementedError(
                "an indexer chooses among the keys of plain causal attention: "
                "no window, no differential pairing, no latent projections, "
                "and its k and v are no other layer's")
        if self.latent and (self.window is not None or self.diff
                            or self.keeps or self.reads is not None):
            raise NotImplementedError(
                "latent attention is causal over the whole sequence, its rope "
                "part rotated or (rotary=False) left as it is: no window, no "
                "differential pairing, and its k and v are no other layer's")
        if self.keeps and (self.mixer not in ("attention", "ssm")
                           or self.reads is not None):
            raise ValueError(
                "keeps: an 'ssm' mixer keeps its scan's output, attention with "
                "k and v of its own keeps them; nothing else has what to keep")
        if self.mixer != "attention" and (self.reads is not None) != (self.mixer == "gmu"):
            raise ValueError(
                "reads: a 'gmu' mixer always reads a kept scan output, attention "
                "may read a kept k and v, no other mixer reads")

    @property
    def parts(self) -> str:
        """The layer's parts, which decide its parameters' structure and
        the stack they live in: layers with the same parts share a
        stack. "attention+moe", "ssm", "moe", "kda+moe", "conv+moe", "diffattention+dense",
        "xdiffattention+dense" (x: q and the output projection only),
        "latentattention+moe", "indexedattention+moe" (the indexer's
        parameters beside attention's), "gmu+dense", ...; a layer that
        `keeps` adds
        "^" ("ssm+dense^"):
        it runs on its own, never as a repeat of a scan, so its
        parameters are a stack of their own, which `forward` takes
        whole (a stack cut between segments is copied, and so are the
        gradient's pieces joined)."""
        mixer = self.mixer
        if mixer == "attention":
            mixer = ("x" if self.reads is not None else "") + (
                "diff" if self.diff else "") + (
                "latent" if self.latent else "") + (
                "indexed" if self.indexed else "") + "attention"
        return "+".join(p for p in (mixer, self.mlp) if p) + ("^" if self.keeps else "")

    @property
    def block(self) -> bool:
        """A transformer block: attention, then an MLP."""
        return self.mixer == "attention" and self.mlp is not None

    @property
    def table(self):
        """Which rotary table turns this layer's q and k: False = none,
        True = the stack's one, a name = that set of
        `TransformerConfig.rotary_sets`. With the window, what tells one
        attention variant of a traced body from another."""
        return self.rotary and (self.rotary_set or True)


@dataclasses.dataclass(frozen=True)
class Segment:
    """A stretch of the stack that `forward` runs as one scan: `repeats`
    times a unit of `len(unit)` layers (their `LayerKind.parts`),
    starting at layer `start`. One repeat is run as it is, layer by
    layer."""

    start: int
    unit: Tuple[str, ...]
    repeats: int


def segments_of(parts: Tuple[str, ...], min_repeats: int = 2) -> Tuple[Segment, ...]:
    """The pattern of a stack cut into segments, greedily from the front:
    at each layer the unit whose immediate repeats (`min_repeats` of them
    or more) cover most layers (the shortest such), or the layer alone
    when nothing repeats that often. `M E M E M *
    E M E` is (M E) x 2, then M, *, E, M, E one by one; twelve equal
    layers are one unit x 12; a different first layer stands alone. What
    is traced grows with the runs of the pattern, not with the depth."""
    out, i, n = [], 0, len(parts)
    while i < n:
        best_p, best_r = 1, 1
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while parts[i + r * p: i + (r + 1) * p] == parts[i: i + p]:
                r += 1
            if r >= max(min_repeats, 2) and r * p > best_r * best_p:
                best_p, best_r = p, r
        out.append(Segment(i, tuple(parts[i: i + best_p]), best_r))
        i += best_p * best_r
    return tuple(out)


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

    activation: str = "silu"  # silu | gelu | relu2 (squared ReLU)
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
    # The share of a head's columns the rotary embedding turns, from the
    # first (`partial_rotary_factor`): the rest are left as they are.
    rotary_fraction: float = 1.0
    # More than the one table above: name -> `RotarySet`, each layer's
    # `LayerKind.rotary_set` naming its own (every rotating layer names
    # one, and `rotary_base` .. `rotary_scaling_params` are then unused).
    rotary_sets: Optional[Dict[str, RotarySet]] = None

    attn_bias: bool = False  # qwen2 uses qkv bias
    attn_out_bias: bool = False  # gpt2 also biases the output projection
    mlp_bias: bool = False
    qk_norm: bool = False  # qwen3 per-head RMSNorm on q/k
    # With `qk_norm`: "head" (one RMSNorm a head, weights `[head_dim]`) or
    # "width" (Olmo 2's: one RMSNorm over the whole projected width of q and
    # of k, before the split into heads, weights `[q_dim]` and `[kv_dim]`).
    qk_norm_over: str = "head"
    # a = (softmax(qk)v) * sigmoid(h Wg) before the output projection.
    attn_gate: bool = False
    # Four norms a layer: the attention and MLP outputs are normalised
    # (ln1_post, ln2_post) before they join the residual stream.
    post_norms: bool = False
    # The norms on the way into the mixer and the MLP (ln1, ln2). Without
    # them and with `post_norms` a layer has output norms only (Olmo 2's
    # block: `x + norm(mixer(x))`, `h + norm(mlp(h))`); with both, four.
    pre_norms: bool = True
    tied_embeddings: bool = False
    embedding_multiplier: Optional[float] = None  # gemma normalizer

    is_critic: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # The delta-rule mixer's sizes, for layers whose mixer is "kda".
    kda: Optional[KDAConfig] = None
    # The gated short convolution's taps, for layers whose mixer is "conv".
    conv: Optional[ConvConfig] = None
    # Latent attention's sizes; with them and no `layer_kinds`, every
    # layer's attention is latent. `head_dim` is then a head's q and k.
    mla: Optional[MLAConfig] = None
    # The prediction module after the stack, or None.
    mtp: Optional[MTPConfig] = None
    # The indexer's sizes; with them and no `layer_kinds`, every layer's
    # attention reads the keys its indexer chooses.
    indexer: Optional[IndexerConfig] = None
    # The residual path: `n` streams a token under hyper-connections, or
    # None for the one plain stream; every layer's parts read and write
    # them, and carry a sublayer's `phi`, `b`, `a` beside their own.
    hyper: Optional[HyperConnConfig] = None
    # One LayerKind a layer, filled by the family from the published
    # config; None = every layer the same (moe or dense by `moe`, full
    # causal attention, rotary by `pos_emb`).
    layer_kinds: Optional[Tuple[LayerKind, ...]] = None
    # A unit of layers that repeats fewer times than this runs layer by
    # layer, not as a scan. A scan's weight gradients are whole stacks
    # that live until its backward pass ends; a layer's own are added to
    # the gradient sums at once. Two repeats of wide layers save little
    # tracing and may cost memory a chip does not have.
    scan_min_repeats: int = 2

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
        if isinstance(self.ssm, dict):
            self.ssm = SSMConfig(**self.ssm)
        if isinstance(self.kda, dict):
            self.kda = KDAConfig(**self.kda)
        if isinstance(self.conv, dict):
            self.conv = ConvConfig(**self.conv)
        if isinstance(self.mla, dict):
            self.mla = MLAConfig(**self.mla)
        if isinstance(self.mtp, dict):
            self.mtp = MTPConfig(**self.mtp)
        if isinstance(self.indexer, dict):
            self.indexer = IndexerConfig(**self.indexer)
        if isinstance(self.hyper, dict):
            self.hyper = HyperConnConfig(**self.hyper)
        if self.rotary_sets is not None:
            self.rotary_sets = {
                name: RotarySet(**rs) if isinstance(rs, dict) else rs
                for name, rs in self.rotary_sets.items()}
        if not (self.pre_norms or self.post_norms):
            raise NotImplementedError(
                "a layer with no norm at all (pre_norms and post_norms both off) is "
                "in no published model; a layer norms on the way in, on the way "
                "out, or both")
        if self.qk_norm_over not in ("head", "width"):
            raise ValueError(
                f"qk_norm_over is 'head' or 'width', got {self.qk_norm_over!r}")
        if self.activation not in ("silu", "gelu", "relu2"):
            raise ValueError(
                f"activation must be 'silu', 'gelu' or 'relu2', got {self.activation!r}")
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
        if any(k.mixer in ("ssm", "gmu") for k in kinds) and self.ssm is None:
            raise ValueError(
                "a layer with an 'ssm' or 'gmu' mixer needs TransformerConfig.ssm")
        if any(k.mixer == "kda" for k in kinds) and self.kda is None:
            raise ValueError("a layer with a 'kda' mixer needs TransformerConfig.kda")
        if any(k.mixer == "conv" for k in kinds) and self.conv is None:
            raise ValueError("a layer with a 'conv' mixer needs TransformerConfig.conv")
        if any(k.latent for k in kinds):
            if self.mla is None:
                raise ValueError("a latent attention layer needs TransformerConfig.mla")
            if self.head_dim != self.mla.qk_dim or self.n_q_heads != self.n_kv_heads:
                raise ValueError(
                    "latent attention: head_dim is a head's q and k, nope_dim + "
                    f"rope_dim = {self.mla.qk_dim}, and k and v are a head each "
                    f"(got head_dim {self.head_dim}, {self.n_q_heads} / "
                    f"{self.n_kv_heads} heads)")
        if any(k.indexed for k in kinds) and self.indexer is None:
            raise ValueError("an indexed attention layer needs TransformerConfig.indexer")
        if self.hyper is not None:
            if self.mtp is not None:
                raise NotImplementedError(
                    "a prediction module after a stack of several residual streams: "
                    "how the module reads the streams and hands them on is in no "
                    "published config or paper; models/transformer.py runs the "
                    "module over one stream")
            if self.is_critic or self.norm_type != "rms" or not all(
                    k.block and not k.diff and k.reads is None and not k.keeps
                    for k in kinds):
                raise NotImplementedError(
                    "hyper-connections are computed around an attention mixer and "
                    "an MLP under RMSNorms in every layer of an actor: no "
                    "state-space or memory mixer, no differential pairing, no kept "
                    "tensor, no LayerNorm, no critic head")
        if self.mtp is not None and (self.is_critic or not kinds[-1].block):
            raise ValueError(
                "the prediction module is one more transformer block of the "
                "stack's last kind under an actor's head")
        for i, k in enumerate(kinds):
            if k.reads is None:
                continue
            kept = kinds[k.reads] if 0 <= k.reads < i else None
            want = "ssm" if k.mixer == "gmu" else "attention"
            if kept is None or not kept.keeps or kept.mixer != want:
                raise ValueError(
                    f"layer {i} reads layer {k.reads}, which is no earlier "
                    f"{want!r} layer that keeps its tensor")
            if k.diff != kept.diff and want == "attention":
                raise ValueError(
                    f"layer {i} reads the k and v of layer {k.reads}: both "
                    "differential or neither")
        if any(k.diff for k in kinds) and (self.n_q_heads % 2 or self.n_kv_heads % 2):
            raise ValueError("differential attention pairs the heads: even counts")
        named = {k.rotary_set for k in kinds if k.mixer == "attention" and k.rotary}
        if named != {None} and named:
            if None in named or not named <= set(self.rotary_sets or {}):
                raise ValueError(
                    f"the layers' rotary sets {sorted(map(str, named))} are not all "
                    f"among rotary_sets {sorted(self.rotary_sets or {})}: where a "
                    "stack has sets, every rotating layer names one of them")
            if (self.pos_emb != "rotary" or self.mla is not None
                    or self.indexer is not None or self.mtp is not None):
                raise NotImplementedError(
                    "rotary sets beside learned positions, latent attention, an "
                    "indexer or a prediction module: models/transformer.py builds "
                    "the sets' tables for plain heads' q and k and nothing else")
        elif self.rotary_sets:
            raise ValueError(
                f"rotary_sets {sorted(self.rotary_sets)} that no layer names")
        if self.qk_norm and self.qk_norm_over == "width" and (
                self.mla is not None or self.indexer is not None
                or any(k.diff or k.reads is not None for k in kinds)):
            raise NotImplementedError(
                "q and k normed over their whole width beside latent attention, an "
                "indexer, differential attention or a layer that reads another's k "
                "and v: models/transformer._attn_in norms a plain layer's own "
                "projections before the split into heads and nothing else")
        if self.rotary_fraction != 1.0:
            turned = self.head_dim * self.rotary_fraction
            if (not 0.0 < self.rotary_fraction < 1.0 or turned != int(turned)
                    or int(turned) % 2):
                raise ValueError(
                    f"rotary_fraction {self.rotary_fraction} of a head of "
                    f"{self.head_dim} is no even number of columns")
            if self.mla is not None or self.indexer is not None or any(
                    k.diff for k in kinds):
                raise NotImplementedError(
                    "a partial rotation beside latent attention (its rope part is "
                    "its own), an indexer or differential attention: "
                    "models/transformer.py turns the first columns of a plain "
                    "head's q and k and nothing else")

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def rotary_dim(self) -> int:
        """The width the rotary embedding turns: a whole head (its first
        `rotary_fraction`), or latent attention's rope part."""
        if self.mla is not None:
            return self.mla.rope_dim
        if self.rotary_fraction == 1.0:
            return self.head_dim
        return int(self.head_dim * self.rotary_fraction)

    def kinds(self) -> Tuple[LayerKind, ...]:
        """The kind of every layer, in order."""
        if self.layer_kinds is not None:
            return self.layer_kinds
        rotary = self.pos_emb == "rotary"
        dense_first = self.moe.first_k_dense if self.moe is not None else self.n_layers
        return tuple(
            LayerKind(mlp="dense" if i < dense_first else "moe", rotary=rotary,
                      latent=self.mla is not None,
                      indexed=self.indexer is not None)
            for i in range(self.n_layers)
        )

    @property
    def n_lead_layers(self) -> int:
        """Leading layers whose parts differ from the last layer's: in a
        stack of transformer blocks their parameters are a stack of
        their own (`params["lead_layers"]`)."""
        kinds = self.kinds()
        n = 0
        while n < len(kinds) and kinds[n].parts != kinds[-1].parts:
            n += 1
        return n

    def stack_paths(self) -> Dict[str, Tuple[Tuple[str, ...], Tuple[int, ...]]]:
        """Where the parameters of each kind of layer live: `parts` ->
        (path into the parameter tree, the layers it holds in order).
        Layers with the same parts share one stack, on a leading axis. A
        stack of transformer blocks that is leading layers of one kind
        and then layers of another keeps its names, `lead_layers` and
        `layers`; any other pattern has `stacks/<parts>`."""
        kinds = self.kinds()
        by_parts: Dict[str, list] = {}
        for i, k in enumerate(kinds):
            by_parts.setdefault(k.parts, []).append(i)
        n_lead = self.n_lead_layers
        lead, rest = kinds[:n_lead], kinds[n_lead:]
        if (all(k.block for k in kinds)
                and all(k.parts == rest[0].parts for k in rest)
                and all(k.parts == lead[0].parts for k in lead)):
            out = {rest[0].parts: (("layers",), tuple(range(n_lead, len(kinds))))}
            if lead:
                out[lead[0].parts] = (("lead_layers",), tuple(range(n_lead)))
            return out
        return {parts: (("stacks", parts), tuple(idx))
                for parts, idx in by_parts.items()}

    def segments(self) -> Tuple[Segment, ...]:
        return segments_of(tuple(k.parts for k in self.kinds()), self.scan_min_repeats)

    @property
    def n_moe_layers(self) -> int:
        return sum(k.mlp == "moe" for k in self.kinds())

    @property
    def n_indexed_layers(self) -> int:
        return sum(k.indexed for k in self.kinds())

    @property
    def n_ssm_layers(self) -> int:
        return sum(k.mixer == "ssm" for k in self.kinds())

    @property
    def n_kda_layers(self) -> int:
        return sum(k.mixer == "kda" for k in self.kinds())

    @property
    def n_conv_layers(self) -> int:
        return sum(k.mixer == "conv" for k in self.kinds())

    @property
    def one_kind(self) -> bool:
        """Every layer the same transformer block over one residual
        stream, full causal attention, rotary as `pos_emb` says: what the
        KV-cache paths (generation, serving, paged) can run."""
        kinds = self.kinds()
        plain = LayerKind(mlp=kinds[0].mlp, rotary=self.pos_emb == "rotary")
        return self.hyper is None and all(k == plain for k in kinds)

    def require_plain_stack(self, where: str) -> None:
        """The KV-cache paths (models/generation.py, engine/paged.py,
        engine/serving.py) hold one kind of layer and run the plain
        block: refuse what they would otherwise compute wrongly, naming
        what is missing."""
        missing = []
        kinds = self.kinds()
        sets = {k.rotary_set: self.rotary_sets[k.rotary_set]
                for k in kinds if k.rotary_set is not None}
        if any(k.latent for k in kinds):
            missing.append(
                "a latent cache: latent attention keeps, a token, one row of "
                f"kv_rank + rope_dim = {self.mla.kv_rank + self.mla.rope_dim} "
                "values that every head reads (its projections absorbed into q "
                "and the output in decode), where the KV pages hold k and v a "
                "head; models/transformer.py runs the materialised form, which "
                "a decode step has no use for")
        if any(k.indexed for k in kinds):
            missing.append(
                "the indexer: a cache of indexer keys (one row of "
                f"{self.indexer.head_dim} values a token a layer) beside the KV "
                "pages, the scores of a new token against it and the choice of "
                f"{self.indexer.top_k} pages' rows inside paged decode; "
                "models/transformer.py scores and chooses over a whole packed "
                "row, as a training or prefill pass does")
        if self.hyper is not None:
            missing.append(
                f"{self.hyper.n} residual streams a token: the decode layer carries "
                "one stream and has no read, write or Sinkhorn step of "
                "hyper-connections (models/transformer._hc_read, _hc_write)")
        if self.mtp is not None:
            missing.append(
                "the multi-token-prediction module: the cache paths have no "
                "pages for its layer, no draft from it (engine/spec_decode.py "
                "drafts by n-gram) and a weight update that does not carry it")
        if any(k.mixer == "ssm" for k in kinds):
            state = ("[channels, state_dim], a decay for every channel and state"
                     if self.ssm.form == "mamba1" else "[heads, head_dim, state_dim]")
            missing.append(
                "a recurrent state beside the KV pages: a state-space layer "
                f"keeps, a sequence, its state {state} and "
                "the last conv_kernel - 1 inputs of its convolution, which the "
                "cache manager has no slot for, no snapshot of for an "
                "interrupted rollout to resume from, and no decode step"
            )
        if any(k.mixer == "kda" for k in kinds):
            missing.append(
                "a delta-rule state beside the KV pages: such a layer keeps, a "
                f"sequence, its state [{self.kda.n_heads}, {self.kda.head_dim}, "
                f"{self.kda.value_dim}] and the last conv_kernel - 1 inputs of its "
                "three convolutions, which the cache manager has no slot for, no "
                "snapshot of for an interrupted rollout to resume from, and no "
                "decode step"
            )
        if any(k.mixer == "conv" for k in kinds):
            missing.append(
                "a convolution's reach back beside the KV pages: a gated "
                f"short-convolution layer keeps, a sequence, the last "
                f"{self.conv.kernel - 1} gated inputs B * x of {self.hidden_dim} "
                "values each and no KV page, which the cache manager has no slot "
                "for, no snapshot of for an interrupted rollout to resume from, and "
                "no decode step"
            )
        if any(k.reads is not None or k.keeps for k in kinds):
            missing.append(
                "one layer's tensors shared by many: the layers "
                f"{[i for i, k in enumerate(kinds) if k.reads is not None]} read "
                "the k and v, or the scan output, that layers "
                f"{sorted({k.reads for k in kinds if k.reads is not None})} keep; "
                "the cache paths give every layer KV pages of its own and have "
                "no slot for a kept scan output a position"
            )
        if any(k.diff for k in kinds):
            missing.append(
                "the differential combine in the decode layer: two softmaxes a "
                "pair of heads over q, k of one size and v of twice it, their "
                "difference under a norm of its own"
            )
        if not all(k.block for k in kinds):
            missing.append(
                "a decode layer per kind of layer: the layers here have the "
                f"parts {sorted({k.parts for k in kinds})}, the cache paths run "
                "attention and an MLP in every layer"
            )
        elif not any(k.latent or k.indexed for k in kinds) and (
                len({dataclasses.replace(k, rotary_set=None) for k in kinds}) > 1
                if sets else not self.one_kind):
            missing.append(
                "a cache manager with a kind per layer (window layers keep "
                "the last `window` positions, full layers all; rotary or "
                "none per layer): the layers here are "
                f"{sorted({(k.mlp, k.window or 0, k.rotary) for k in kinds})}"
            )
        if len(sets) > 1:
            missing.append(
                f"a rotary table a kind of layer: the layers here turn q and k by "
                f"{len(sets)} rotary sets {sorted(sets)}, the cache paths build one "
                "table from rotary_base and its scaling and turn every layer's new "
                "q and k by it")
        factors = {n: rs.attention_factor for n, rs in sets.items()
                   if rs.attention_factor != 1.0}
        if factors:
            missing.append(
                f"a scaled table's attention factor on a plain head: rotary sets "
                f"{sorted(factors)} multiply cos and sin by "
                f"{sorted(factors.values())}, the decode layer rotates by a table "
                "of unit amplitude and scores at head_dim^-0.5")
        if self.attn_gate or self.post_norms or self.rotary_fraction != 1.0:
            missing.append(
                "the attention output gate, the post-attention / post-MLP "
                "norms and a rotation of part of a head (rotary_fraction "
                f"{self.rotary_fraction}) in the decode layer"
            )
        if not self.pre_norms or (self.qk_norm and self.qk_norm_over == "width"):
            missing.append(
                "a block with output norms only (no norm on the way into the mixer "
                "or the MLP) and one RMSNorm over the whole width of q and of k: "
                "the decode layer norms its input and norms q and k a head"
            )
        if self.moe is not None and (
            self.moe.experts_held is not None or self.moe.score_func != "softmax"
            or self.moe.n_shared_experts
        ):
            missing.append(
                "the sigmoid router, shared expert and held-experts share in "
                "the decode layer's expert MLP"
            )
        if self.activation == "relu2":
            missing.append("the squared-ReLU activation in the decode layer's MLP")
        if missing:
            raise NotImplementedError(
                f"{where} cannot run this configuration; it lacks "
                + "; and ".join(missing)
            )

    def layer_uses_moe(self, layer_idx: int) -> bool:
        return self.kinds()[layer_idx].mlp == "moe"
