"""User-facing experiment option dataclasses + `key=value` override CLI.

Counterpart of the reference's cli args module (realhf/api/cli_args.py,
1558 LoC of Hydra structured configs). Hydra/OmegaConf are not available
in this environment, so the same pattern is realized with plain
dataclasses plus a dotted-path `key=value` override parser
(`apply_overrides`) — the experiment classes remain *properties over the
dataclass* exactly like the reference.
"""

from __future__ import annotations

import dataclasses
import json
import typing
from typing import Any, Dict, List, Optional, Tuple

from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.api.system_api import ExperimentSaveEvalControl
from areal_tpu.engine.optimizer import OptimizerConfig


@dataclasses.dataclass
class ModelTrainEvalConfig:
    """One model's build + engine options (reference ModelTrainEvalConfig)."""

    path: Optional[str] = None  # HF checkpoint dir; None = random init
    init_from_scratch: bool = False
    config: Optional[Dict[str, Any]] = None  # TransformerConfig kwargs
    is_critic: bool = False
    dtype: str = "bfloat16"
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    backend: str = "jax_train"  # jax_train | jax_inference | mock_train
    attn_impl: str = dataclasses.field(
        default="auto",
        metadata={
            "help": "attention impl: auto | splash | reference | "
            "ring | ulysses (ring/ulysses = context parallelism over the "
            "seq mesh axis)"
        },
    )
    remat: bool = True
    mesh_spec: Optional[str] = None  # worker-local mesh, e.g. "d1f4t2"
    row_len_multiple: int = 128
    max_row_len: Optional[int] = None
    prefetch_depth: int = dataclasses.field(
        default=2,
        metadata={
            "help": "overlapped input pipeline depth: a background "
            "thread packs + device_puts micro-batch i+1 while step i "
            "runs on device, bounded to this many staged micro-batches; "
            "0 = fully eager (engine/prefetch.py)"
        },
    )
    stats_fetch_interval: int = dataclasses.field(
        default=1,
        metadata={
            "help": "fetch the packed train stats from device every Nth "
            "train_batch only (each fetch is a host round trip); "
            "skipped calls return the last values "
            "tagged <loss>/stats_stale=1"
        },
    )
    # MoE overlay knobs: applied on top of config["moe"] by
    # experiments/common.model_abstraction, so sweeps can flip dispatch
    # or capacity without rewriting the whole nested model config.
    moe_dispatch: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "override config['moe']['dispatch'] for this model: "
            "'capacity' (einsum, drops beyond capacity) or 'dropless' "
            "(ragged grouped matmul; expert-parallel when the fsdp "
            "mesh axis divides num_experts)"
        },
    )
    moe_capacity_factor: Optional[float] = dataclasses.field(
        default=None,
        metadata={
            "help": "override config['moe']['capacity_factor'] "
            "(capacity dispatch only; >= num_experts/top_k guarantees "
            "zero drops)"
        },
    )
    moe_aux_loss_coef: Optional[float] = dataclasses.field(
        default=None,
        metadata={
            "help": "override config['moe']['aux_loss_coef'] (the "
            "Switch load-balance loss weight)"
        },
    )


@dataclasses.dataclass
class MFCConfig:
    """Per-MFC micro-batching override (reference MFCConfig,
    api/cli_args.py: each model function call carries its own
    MicroBatchSpec + allocation). None fields inherit the experiment's
    global `mb_spec_n_mbs` / `mb_spec_max_tokens`."""

    n_mbs: Optional[int] = dataclasses.field(
        default=None,
        metadata={"help": "split this MFC's batch into n micro-batches"},
    )
    max_tokens_per_mb: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "cap tokens per micro-batch for this MFC "
            "(balanced-packing split)"
        },
    )


@dataclasses.dataclass
class PPOHyperparameters:
    """Mirrors reference PPOHyperparameters (api/cli_args.py)."""

    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=lambda: GenerationHyperparameters(
            max_new_tokens=512, top_p=1.0, temperature=1.0
        )
    )
    group_size: int = 1
    # Best-of-k: sample this many responses per prompt, verify, train on
    # the top `group_size` (None disables; reference
    # ppo_interface.py:376-408).
    generation_size: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "sample-then-select: candidates per prompt before "
            "keeping the best group_size"
        },
    )
    ppo_n_minibatches: int = 4
    eps_clip: float = 0.2
    c_clip: Optional[float] = None
    value_eps_clip: float = 0.2
    disable_value: bool = True  # group-reward baseline by default (GRPO-style)
    reward_output_scaling: float = 1.0
    reward_output_bias: float = 0.0
    max_reward_clip: float = 20.0
    mask_no_eos_with_zero: bool = False
    discount: float = 1.0
    gae_lambda: float = 1.0
    adv_norm: bool = True
    group_adv_norm: bool = False
    kl_ctl: float = 0.1
    use_adaptive_kl_ctl: bool = False
    use_decoupled_loss: bool = False
    behav_imp_weight_cap: Optional[float] = None
    # 'global' | 'dp': gradient token-normalization scope (reference
    # ppo_interface.py:253; see JaxTrainEngine.train_batch).
    token_normalize_scope: str = "global"
    recompute_logprob: bool = True
    fuse_rew_ref: bool = False
    success_rate_lb: float = 0.0
    success_rate_ub: float = 1.0
    # async controls
    max_head_offpolicyness: int = 0
    new_tokens_per_chunk: int = 1 << 30
    max_concurrent_rollouts: int = 32


@dataclasses.dataclass
class DatasetConfig:
    path: Optional[str] = None
    max_length: Optional[int] = 1024
    type_: str = "math_code_prompt"
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class BaseExperimentConfig:
    """Fields shared by every experiment (reference CommonExperimentConfig,
    experiments/common/common.py:72)."""

    experiment_name: str = "exp"
    trial_name: str = "trial"
    seed: int = 1
    total_train_epochs: int = 1
    train_batch_size: int = 8
    tokenizer_path: Optional[str] = None
    dataset: DatasetConfig = dataclasses.field(default_factory=DatasetConfig)
    exp_ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    # "d2t4" or decoupled "gen.d1t1+d1t1"; data axis -> #model workers for
    # the single-host local launcher.
    allocation_mode: str = "d1"
    n_model_workers: int = 1
    train_n_hosts: int = dataclasses.field(
        default=1,
        metadata={
            "help": "host processes sharing ONE train mesh via "
            "jax.distributed: each model worker becomes one host of the "
            "train partition (coordinator elected through name_resolve, "
            "parallel/distributed.setup_host_group), builds the GLOBAL "
            "allocation_mode train mesh, and iterates the dataset in "
            "lockstep (dp handled inside the mesh, not across workers). "
            "1 = single-host (worker-local meshes, the default)"
        },
    )
    recover_mode: str = "disabled"  # disabled | auto | resume
    recover_retries: int = 1
    # Per-worker fault domain: serving-plane workers (generation server /
    # rollout worker / gserver manager) that die or hang are restarted in
    # place this many times each before the failure escalates to the
    # whole-experiment relaunch above.
    worker_restarts: int = 2
    name_resolve_backend: str = "nfs"
    name_resolve_root: Optional[str] = None
    mb_spec_n_mbs: int = 1
    mb_spec_max_tokens: Optional[int] = None
    # Automatic per-checkpoint offline evaluation (reference
    # scheduler/evaluator.py AutomaticEvaluator, enabled via auto_eval):
    # watches the save dir while training runs and submits one eval job
    # per new checkpoint through the scheduler client.
    auto_eval: bool = False
    auto_eval_data_path: Optional[str] = None  # benchmark jsonl
    auto_eval_task: str = "math"  # math | code
    auto_eval_model_role: str = "default"  # "actor" for PPO experiments
    auto_eval_max_new_tokens: int = 512
    auto_eval_max_concurrent_jobs: int = 1
    # JAX platform for eval jobs: "cpu" (default) keeps them off the
    # accelerator the training workers exclusively hold.
    auto_eval_device: str = "cpu"


@dataclasses.dataclass
class SFTExpConfig(BaseExperimentConfig):
    model: ModelTrainEvalConfig = dataclasses.field(
        default_factory=ModelTrainEvalConfig
    )

    def __post_init__(self):
        if self.dataset.type_ == "math_code_prompt":
            self.dataset.type_ = "prompt_answer"


@dataclasses.dataclass
class PPOMATHExpConfig(BaseExperimentConfig):
    """Sync PPO on math/code prompts (reference PPOMATHConfig)."""

    actor: ModelTrainEvalConfig = dataclasses.field(
        default_factory=ModelTrainEvalConfig
    )
    ref: Optional[ModelTrainEvalConfig] = None  # default: copy of actor path
    critic: Optional[ModelTrainEvalConfig] = None  # None when disable_value
    ppo: PPOHyperparameters = dataclasses.field(default_factory=PPOHyperparameters)
    group_size: int = 1
    # Per-MFC micro-batch overrides (reference PPOMATHConfig exposes one
    # MFCConfig per function call; e.g. `actor_train.n_mbs=8
    # actor_gen.max_tokens_per_mb=65536`).
    actor_gen: MFCConfig = dataclasses.field(default_factory=MFCConfig)
    actor_train: MFCConfig = dataclasses.field(default_factory=MFCConfig)
    rew_inf: MFCConfig = dataclasses.field(default_factory=MFCConfig)
    ref_inf: MFCConfig = dataclasses.field(default_factory=MFCConfig)
    critic_inf: MFCConfig = dataclasses.field(default_factory=MFCConfig)
    critic_train: MFCConfig = dataclasses.field(default_factory=MFCConfig)

    def __post_init__(self):
        if self.group_size > 1:
            self.ppo.group_size = self.group_size


@dataclasses.dataclass
class AsyncPPOMATHExpConfig(PPOMATHExpConfig):
    """Async PPO: decoupled generation + streaming rollouts
    (reference AsyncPPOMATHConfig)."""

    n_rollout_workers: int = 1
    n_generation_servers: int = 1
    gen_max_concurrent_requests: int = 32
    gen_max_seq_len: int = 4096
    gen_decode_block_steps: int = 16
    gen_kv_page_size: int = 128
    # Paged KV pool capacity in tokens (None = B * max_seq_len); sizing it
    # below that serves long contexts in bounded HBM with
    # preempt-and-resubmit under pressure (engine/serving.py).
    gen_kv_pool_tokens: Optional[int] = None
    # Shard each generation server over this many devices (GSPMD TP).
    gen_tensor_parallel: int = 1
    # Prefill shape buckets: prompts are padded up to a multiple of this,
    # bounding the number of compiled prefill programs.
    gen_prompt_bucket: int = 64
    # Max prompts admitted into one batched prefill.
    gen_prefill_max_batch: int = 8
    # Chunked prefill threshold/size for long prompts (None disables).
    gen_prefill_chunk: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "prompts longer than this prefill in fixed-size "
            "chunks through one compiled program (16-32k contexts)"
        },
    )
    # Cap on chunked/cache-hit prefills admitted per serve-loop lap
    # (they run sequentially and stall decode for running slots).
    gen_chunked_prefill_per_lap: int = dataclasses.field(
        default=2,
        metadata={
            "help": "max one-at-a-time chunked prefills admitted per "
            "serve-loop lap; bounds decode-latency jitter"
        },
    )
    # Prefix KV reuse budget for partial-rollout resubmissions.
    gen_prefix_cache_tokens: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "token budget for qid-keyed prefix KV reuse; "
            "resubmissions prefill only the delta (None disables)"
        },
    )
    # KV pool precision on the generation servers.
    gen_kv_cache_dtype: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "KV pool precision: None/'model' stores the compute "
            "dtype; 'int8' stores quantized pages (half the decode HBM "
            "traffic, double the tokens per pool budget)"
        },
    )
    # N-gram (prompt-lookup) speculative decoding on the gen servers.
    gen_speculative_draft_len: int = dataclasses.field(
        default=0,
        metadata={
            "help": "tokens drafted per decode step via n-gram prompt "
            "lookup; verified prefix kept (lossless). 0 disables"
        },
    )
    gen_speculative_ngram: int = dataclasses.field(
        default=2,
        metadata={"help": "n-gram length for the draft lookup match"},
    )
    gen_speculative_window: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "backward search window (tokens) for the n-gram "
            "draft lookup: only the most recent W candidate positions "
            "are matched, so draft cost stops scaling with max_seq_len "
            "at 16-32k contexts. None = engine default (1024); 0 = "
            "unbounded full-history scan"
        },
    )
    gen_decode_weight_dtype: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "decode-path weight precision: 'int8' halves the "
            "per-step weight stream (prefill stays bf16); None disables"
        },
    )
    # Streaming weight-distribution plane (system/weight_plane.py).
    gen_weight_plane: bool = dataclasses.field(
        default=False,
        metadata={
            "help": "distribute weight updates over a peer-fanout tree "
            "(origin uploads each byte once; servers serve chunks to "
            "siblings) instead of every generation server re-reading "
            "the checkpoint from NFS; transfer overlaps serving, the "
            "interrupt+swap cutover is measured separately"
        },
    )
    gen_weight_chunk_mb: int = dataclasses.field(
        default=8,
        metadata={
            "help": "weight-plane chunk size (MiB): per-chunk content "
            "hashes + HTTP Range resume, so a torn transfer re-pays at "
            "most one chunk"
        },
    )
    gen_weight_fanout: int = dataclasses.field(
        default=2,
        metadata={
            "help": "children per node in the weight-plane fanout tree; "
            "origin egress is bounded by fanout * payload"
        },
    )
    gen_weight_cutover_budget_s: float = dataclasses.field(
        default=3.0,
        metadata={
            "help": "target bound for the serve-interrupting weight "
            "cutover window (the reference's <3s weight-update bar); "
            "overruns are surfaced in /status + logs, not fatal"
        },
    )
    gen_weight_wire_dtype: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "'int8' ships weight updates over the plane as "
            "quantized data+scale streams (~half the bytes per "
            "version; servers dequantize at assembly). The trainer "
            "dump publishes the companion bin; None ships raw bytes"
        },
    )
    gen_weight_shards: str = dataclasses.field(
        default="",
        metadata={
            "help": "comma-separated 'rank/degree' weight-shard spec "
            "per generation server index (e.g. '0/2,1/2' for a 2-way "
            "fleet TP group): each server fetches only its slice of "
            "every weight version and same-shard peers fan chunks to "
            "each other. Empty entries = unsharded (full payload)"
        },
    )

    # Disaggregated prefill/decode serving (docs/serving.md).
    gen_server_roles: str = dataclasses.field(
        default="",
        metadata={
            "help": "comma-separated pool role per generation server "
            "index (prefill|decode|unified); empty/short lists pad "
            "with 'unified'. E.g. 'prefill,decode,unified' splits a "
            "3-server fleet with one elastic spare"
        },
    )
    gen_kv_handoff_compress: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "'int8' quantizes exported KV-handoff blobs "
            "(halves the prefill->decode hop; importer dequantizes). "
            "None ships the pool's own precision"
        },
    )
    # Tiered KV plane (docs/serving.md "KV tiering + global prefix
    # index").
    gen_kv_tier_mb: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "host-RAM KV tier capacity (MiB) per generation "
            "server: prefix-cache evictions spill there (handoff wire "
            "format) instead of being freed, and returning sessions "
            "restore instead of re-prefilling. None = "
            "AREAL_KV_TIER_BYTES (default off)"
        },
    )
    gen_kv_tier_disk_dir: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "optional local-disk second KV tier directory "
            "(host-LRU evictions demote there, hash-verified on "
            "read-back). None = AREAL_KV_TIER_DISK_DIR"
        },
    )
    gen_kv_spill_dtype: Optional[str] = dataclasses.field(
        default=None,
        metadata={
            "help": "'int8' quantizes FLOAT KV pools' prefixes on the "
            "spill wire (halves tier bytes; int8 pools always spill "
            "their data+scales form). None = AREAL_KV_SPILL_DTYPE"
        },
    )
    gen_kv_index_size: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "LRU cap on the manager's global prefix index "
            "(qid -> holder + tier; lets ANY server serve a returning "
            "session by pulling its prefix from the holder). None = "
            "AREAL_KV_INDEX_SIZE; 0 disables index-aware routing"
        },
    )
    gen_elastic_pools: bool = dataclasses.field(
        default=False,
        metadata={
            "help": "let the manager re-role 'unified'-configured "
            "servers between the prefill and decode pools from "
            "queue-depth/free-page watermarks (drain + flip, weights "
            "stay resident)"
        },
    )
    gen_prefill_queue_high_tokens: int = dataclasses.field(
        default=4096,
        metadata={
            "help": "queued-prompt-token watermark over the prefill "
            "pool at which an elastic decode-side server flips to "
            "prefill"
        },
    )
    gen_prefill_queue_low_tokens: int = dataclasses.field(
        default=0,
        metadata={
            "help": "queued-prompt-token floor at or below which a "
            "manager-flipped prefill server returns to its original "
            "pool"
        },
    )
    gen_decode_free_page_min_frac: float = dataclasses.field(
        default=0.1,
        metadata={
            "help": "decode-pool free-KV-page floor (fraction): below "
            "it an elastic prefill-side server flips to decode"
        },
    )
    gen_elastic_fleet: bool = dataclasses.field(
        default=True,
        metadata={
            "help": "elastic fleet control plane: adopt runtime "
            "joiners (peer weight bootstrap before routing), forget "
            "graceful drain departures, persist the manager HA lease "
            "(system/fleet_controller.py). False = fixed fleet"
        },
    )
    gen_autoscale: bool = dataclasses.field(
        default=False,
        metadata={
            "help": "watermark autoscaling of the generation fleet: "
            "scale-out/in from the queued-token / free-page signals "
            "(requires a launcher attached to the manager)"
        },
    )
    gen_scale_out_queued_tokens: int = dataclasses.field(
        default=4096,
        metadata={
            "help": "fleet-average queued prompt tokens per routable "
            "server at/above which the autoscaler launches a server"
        },
    )
    gen_scale_in_queued_tokens: int = dataclasses.field(
        default=64,
        metadata={
            "help": "fleet-average queued prompt tokens at/below "
            "which the autoscaler drains the least-loaded server"
        },
    )
    gen_pool_min_servers: int = dataclasses.field(
        default=1,
        metadata={"help": "autoscaler floor on fleet size"},
    )
    gen_pool_max_servers: int = dataclasses.field(
        default=8,
        metadata={"help": "autoscaler ceiling on fleet size"},
    )
    schedule_policy: str = "round_robin"
    # rollout agent: "math-single-step" | "math-multi-turn" | "tool-use"
    agent_type: str = "math-single-step"
    agent_num_turns: int = 4
    agent_turn_discount: float = 1.0
    # tool-use agent only: deterministic tool turns before the model is
    # trusted to emit its own <tool:...> calls (0 = fully model-driven).
    agent_scripted_tool_turns: int = 0

    def __post_init__(self):
        super().__post_init__()
        # Config-parse-time validation: bad serving precisions and
        # malformed weight-shard specs must fail HERE, not at engine
        # construction deep inside server startup.
        if self.gen_decode_weight_dtype not in (None, "model", "int8"):
            raise ValueError(
                f"gen_decode_weight_dtype="
                f"{self.gen_decode_weight_dtype!r}: expected None, "
                f"'model', or 'int8'"
            )
        if self.gen_weight_wire_dtype not in (None, "int8"):
            raise ValueError(
                f"gen_weight_wire_dtype={self.gen_weight_wire_dtype!r}: "
                f"expected None or 'int8'"
            )
        for i, spec in enumerate(parse_weight_shards(
            self.gen_weight_shards, self.n_generation_servers
        )):
            # The engine can only place a sliced cutover when its mesh
            # tensor extent matches the fleet shard degree — catch the
            # mismatch here, not after a full fleet transfer.
            if spec is not None and spec[1] != self.gen_tensor_parallel:
                raise ValueError(
                    f"gen_weight_shards[{i}] degree {spec[1]} != "
                    f"gen_tensor_parallel {self.gen_tensor_parallel}"
                )


def parse_weight_shards(
    spec: str, n_servers: int
) -> List[Optional[Tuple[int, int]]]:
    """'0/2,1/2' -> [(0, 2), (1, 2), ...] padded with None (unsharded)
    per generation-server index; raises ValueError on malformed or
    out-of-range entries."""
    entries = (spec or "").split(",")
    if spec and len(entries) > n_servers:
        raise ValueError(
            f"gen_weight_shards lists {len(entries)} entries for "
            f"{n_servers} generation server(s)"
        )
    out: List[Optional[Tuple[int, int]]] = []
    for i, ent in enumerate(entries):
        ent = ent.strip()
        if not ent:
            out.append(None)
            continue
        try:
            rank_s, degree_s = ent.split("/")
            rank, degree = int(rank_s), int(degree_s)
        except ValueError:
            raise ValueError(
                f"gen_weight_shards[{i}]={ent!r}: expected 'rank/degree'"
            )
        if degree < 1 or not (0 <= rank < degree):
            raise ValueError(
                f"gen_weight_shards[{i}]={ent!r}: rank out of range"
            )
        out.append((rank, degree))
    out += [None] * (n_servers - len(out))
    return out[:n_servers]


# ---------------------------------------------------------------------------
# Option discovery (`--help-config`)
# ---------------------------------------------------------------------------


def describe_options(cfg: Any, prefix: str = "") -> List[Dict[str, Any]]:
    """Walk a (possibly nested) config dataclass and return one row per
    reachable dotted override path: {path, type, default, help}. This is
    the counterpart of the reference's Hydra `--help` surface — every row
    is directly usable as a `key=value` CLI override."""
    rows: List[Dict[str, Any]] = []
    cls = type(cfg) if not isinstance(cfg, type) else cfg
    obj = cfg if not isinstance(cfg, type) else None
    hints = typing.get_type_hints(cls)
    for f in dataclasses.fields(cls):
        path = f"{prefix}{f.name}"
        val = getattr(obj, f.name) if obj is not None else (
            f.default
            if f.default is not dataclasses.MISSING
            else (
                f.default_factory()
                if f.default_factory is not dataclasses.MISSING
                else None
            )
        )
        typ = hints.get(f.name, f.type)
        nested = val if dataclasses.is_dataclass(val) else None
        if nested is None:
            # Optional[dataclass] fields defaulting to None still expose
            # their subtree (apply_overrides instantiates on demand).
            for cand in typing.get_args(typ) or ():
                if dataclasses.is_dataclass(cand):
                    nested = cand()
                    break
        if nested is not None:
            rows.extend(describe_options(nested, prefix=f"{path}."))
            continue
        rows.append(
            {
                "path": path,
                "type": getattr(typ, "__name__", str(typ)),
                "default": val,
                "help": f.metadata.get("help", ""),
            }
        )
    return rows


def format_options(cfg: Any) -> str:
    rows = describe_options(cfg)
    width = max(len(r["path"]) for r in rows) + 2
    lines = [
        f"{type(cfg).__name__ if not isinstance(cfg, type) else cfg.__name__}"
        f" options (override with dotted key=value):"
    ]
    for r in rows:
        help_txt = f"  # {r['help']}" if r["help"] else ""
        lines.append(
            f"  {r['path']:<{width}}= {r['default']!r}{help_txt}"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# key=value override parsing
# ---------------------------------------------------------------------------


def _coerce(value: str, typ) -> Any:
    origin = typing.get_origin(typ)
    if origin is typing.Union:
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if value.lower() in ("none", "null"):
            return None
        return _coerce(value, args[0]) if args else value
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ in (dict, Dict, Any) or origin in (dict, list) or typ is list:
        return json.loads(value)
    return value


def apply_overrides(cfg: Any, overrides: List[str]) -> Any:
    """Apply `a.b.c=value` overrides in place onto nested dataclasses."""
    hints_cache: Dict[type, Dict[str, Any]] = {}
    for ov in overrides:
        if "=" not in ov:
            raise ValueError(f"override {ov!r} is not key=value")
        path, value = ov.split("=", 1)
        obj = cfg
        parts = path.split(".")
        for p in parts[:-1]:
            if not hasattr(obj, p):
                raise AttributeError(f"no field {p!r} on {type(obj).__name__}")
            nxt = getattr(obj, p)
            if nxt is None and dataclasses.is_dataclass(obj):
                # Instantiate Optional nested dataclasses on demand so
                # e.g. `critic.path=/ckpt` works when critic defaults None.
                cls = type(obj)
                if cls not in hints_cache:
                    hints_cache[cls] = typing.get_type_hints(cls)
                typ = hints_cache[cls].get(p)
                inner = None
                for cand in typing.get_args(typ) or (typ,):
                    if dataclasses.is_dataclass(cand):
                        inner = cand
                        break
                if inner is None:
                    raise AttributeError(
                        f"field {p!r} is None and not a dataclass "
                        f"(declared type: {typ})"
                    )
                nxt = inner()
                setattr(obj, p, nxt)
            obj = nxt
        leaf = parts[-1]
        if dataclasses.is_dataclass(obj):
            cls = type(obj)
            if cls not in hints_cache:
                hints_cache[cls] = typing.get_type_hints(cls)
            if leaf not in hints_cache[cls]:
                raise AttributeError(f"no field {leaf!r} on {cls.__name__}")
            setattr(obj, leaf, _coerce(value, hints_cache[cls][leaf]))
        elif isinstance(obj, dict):
            obj[leaf] = json.loads(value) if value[:1] in "[{" else value
        else:
            raise AttributeError(f"cannot set {leaf!r} on {type(obj)}")
    return cfg
