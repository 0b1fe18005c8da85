"""Single registry of every ``AREAL_*`` environment knob.

Before this module existed the tree held ~60 ad-hoc ``os.environ``
reads with per-call-site defaults — the drift class that forced PR 1 to
bolt construction-time snapshotting onto ``AREAL_CE_CHUNK`` /
``AREAL_SPLASH_*`` after two call sites disagreed about a default.
Every knob is now declared ONCE here (name, type, default, doc,
snapshot-at-construction flag) and read through the typed accessors
below; the ``env-knob`` checker in ``areal_tpu/lint`` flags any raw
``os.environ``/``getenv`` read of an undeclared ``AREAL_*`` name, any
raw read of a *declared* name outside this module (use an accessor),
and any registry entry nothing reads (dead knob).

``docs/env_vars.md`` is GENERATED from this registry
(``python scripts/areal_lint.py --emit-env-docs docs/env_vars.md``) and
drift-gated in tier-1, so the doc can't fork from the code.

Accessor semantics (uniform, unlike the historical call sites):

- unset **or empty-string** values fall back to the declared default
  (historically ``os.environ.get(k, d)`` sites crashed on ``k=""``
  while ``os.environ.get(k) or d`` sites silently defaulted);
- booleans: ``"" / "0" / "false" / "no" / "off"`` (case-insensitive)
  are False, anything else set is True (historically
  ``AREAL_WEIGHT_PLANE=0`` meant *enabled* because the site tested
  plain string truthiness);
- a knob whose declared default is ``None`` returns ``None`` when
  unset (the "optional override" pattern).

This module must stay stdlib-only: it is imported by
``areal_tpu/base/logging.py`` and by the no-jax lint gate.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

_FALSEY = ("", "0", "false", "no", "off")


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str  # "str" | "int" | "float" | "bool"
    default: Any  # typed default, or None for "optional override" knobs
    doc: str
    # True: read once at construction/init and pinned for the object's
    # lifetime — mid-run env edits must NOT change behavior (a retrace
    # or retry re-reading a changed value was the PR 1 drift bug).
    snapshot: bool = False


def _k(name: str, kind: str, default: Any, doc: str, *,
       snapshot: bool = False) -> Knob:
    return Knob(name=name, kind=kind, default=default, doc=doc,
                snapshot=snapshot)


_KNOBS: List[Knob] = [
    # -- engine / serving ------------------------------------------------
    _k("AREAL_KV_CACHE_DTYPE", "str", None,
       "KV pool precision default when the engine ctor passes None: "
       "'model' or 'int8' (paged.py int8 KV pools). A/B hook so bench "
       "runs need no plumbing.", snapshot=True),
    _k("AREAL_SPEC_DRAFT", "int", 0,
       "N-gram speculative-decoding draft length default when the "
       "engine ctor passes 0 (engine/spec_decode.py). 0 disables.",
       snapshot=True),
    _k("AREAL_SPEC_WINDOW", "int", None,
       "Backward search window (tokens) for the speculative n-gram "
       "lookup; unset = 1024, 0 = unbounded full-history scan.",
       snapshot=True),
    _k("AREAL_DECODE_WEIGHT_DTYPE", "str", None,
       "Decode-weight precision default when the engine ctor passes "
       "None: 'model' or 'int8' (W8A16, ops/wquant.py).", snapshot=True),
    _k("AREAL_CHUNK_SMEM_BUDGET", "int", 512 * 1024,
       "SMEM byte budget the chunked-prefill kernel sizes its blocks "
       "against (engine/paged.py).", snapshot=True),
    # -- tiered KV plane (engine/kv_tier.py, docs/serving.md) ------------
    _k("AREAL_KV_TIER_BYTES", "int", 0,
       "Host-RAM KV tier capacity in bytes when the engine ctor passes "
       "None: prefix-cache evictions SPILL here (handoff wire format) "
       "instead of being freed, and a returning session restores the "
       "prefix instead of re-prefilling. 0 disables the tier.",
       snapshot=True),
    _k("AREAL_KV_TIER_DISK_DIR", "str", None,
       "Optional local-disk second KV tier: host-tier LRU evictions "
       "demote into this directory instead of being dropped (read back "
       "with per-chunk hash verification). Unset = no disk tier.",
       snapshot=True),
    _k("AREAL_KV_TIER_DISK_BYTES", "int", 1 << 30,
       "Capacity of the local-disk KV tier (AREAL_KV_TIER_DISK_DIR); "
       "LRU entries beyond it are dropped for good.", snapshot=True),
    _k("AREAL_KV_SPILL_DTYPE", "str", None,
       "KV spill wire precision when the engine ctor passes None: "
       "'int8' quantizes a FLOAT pool's prefixes on the spill wire "
       "(quantize_kv — halves tier bytes); 'fp8' uses the e4m3 wire "
       "(kv_handoff.quantize_kv_fp8 — same 1-byte footprint, floating "
       "mantissa so small-magnitude KV keeps relative precision); "
       "int8 pools always spill their (data, scales) form unchanged. "
       "None/'model' ships the pool's own precision.", snapshot=True),
    _k("AREAL_KV_INDEX_SIZE", "int", 65536,
       "LRU capacity of the gserver manager's global prefix index "
       "(qid -> holder + tier, fed from each server's /kv/index) when "
       "GserverManagerConfig.kv_index_size is unset."),
    _k("AREAL_CKPT_BACKEND", "str", "pickle",
       "Checkpoint storage backend when the API caller passes none: "
       "'pickle' or 'orbax' (engine/checkpoint.py)."),
    _k("AREAL_CKPT_ASYNC", "bool", False,
       "Route pickle-backend engine checkpoints through the background "
       "writer (engine/checkpoint.py): the step loop pays only a "
       "reference-snapshot stall while device->host fetch + fsync + "
       "rename run off-thread. Orbax saves stay synchronous "
       "(collectives are not thread-safe off the main loop)."),
    _k("AREAL_WAL", "bool", True,
       "Arm the rollout-buffer write-ahead log + exactly-once sample "
       "ledger (system/wal.py, system/stream_dataset.py, "
       "system/push_pull_stream.py): accepted samples journal to disk "
       "before acking the pusher, restarts replay unconsumed entries. "
       "False restores the fire-and-forget pre-WAL wire."),
    _k("AREAL_WAL_FSYNC_MS", "float", 50.0,
       "Max milliseconds an appended WAL record may sit before the "
       "batched fsync (and its deferred pusher ack) flushes it "
       "(system/wal.py). 0 = fsync every append."),
    _k("AREAL_WAL_ACK_TIMEOUT_S", "float", 5.0,
       "Seconds a pushed sample may sit unacked before the pusher "
       "redelivers it (system/push_pull_stream.py); the puller-side "
       "ledger makes redelivery idempotent."),
    _k("AREAL_WAL_REDELIVER_MAX", "int", 0,
       "Redelivery attempts per unacked sample before the pusher drops "
       "it and counts areal:train_samples_lost_total "
       "(system/push_pull_stream.py); 0 = retry forever (exactly-once "
       "mode: nothing is ever dropped)."),
    _k("AREAL_PREFETCH_DEPTH", "int", None,
       "Host-prefetcher queue depth override for the train engine "
       "(engine/jax_engine.py); unset = config/ctor default.",
       snapshot=True),
    _k("AREAL_DECODE_RESIDENT", "bool", True,
       "Device-resident decode dispatch (engine/serving.py): page-table "
       "edits land as donated per-slot row scatters and chunk-prefill "
       "control crosses as ONE fused array, so only admission/eviction "
       "DELTAS pay H2D between decode blocks. False restores the "
       "legacy full-table restage + per-scalar staging (greedy-token "
       "parity between the modes is pinned in "
       "tests/engine/test_decode_resident.py).", snapshot=True),
    # -- base ------------------------------------------------------------
    _k("AREAL_FILEROOT", "str", None,
       "Filesystem root for logs/checkpoints/realloc params; unset = "
       "/tmp/areal_tpu/$USER. Resolved at call time, not import time "
       "(base/constants.py: workers import before the controller env "
       "lands)."),
    _k("AREAL_LOG_LEVEL", "str", "INFO",
       "Root log level for areal_tpu loggers (base/logging.py)."),
    _k("AREAL_FAULTS", "str", "",
       "Deterministic chaos-injection spec, e.g. "
       "'gserver.weight_fetch@0.5:seed=7' (base/fault_injection.py); "
       "empty = no faults."),
    _k("AREAL_HEALTH_TTL", "float", 10.0,
       "Default lease TTL seconds for the health registry "
       "(base/health.py); per-role overrides via worker config."),
    _k("AREAL_FLEET_LEASE_TTL", "float", None,
       "Gserver-manager HA lease TTL seconds "
       "(system/fleet_controller.py): a successor takes over once the "
       "record is stale by 3x this. Unset = AREAL_HEALTH_TTL, so one "
       "knob tunes both failure-detection horizons."),
    _k("AREAL_NAME_RESOLVE_ROOT", "str", "/tmp/areal_tpu/name_resolve",
       "Root directory for the filesystem name-resolve backend "
       "(base/name_resolve.py)."),
    _k("AREAL_TPU_MEMORY_KILL_THRESHOLD", "float", None,
       "Host-memory fraction above which the monitor kills the worker "
       "(base/monitor.py); unset = disabled."),
    # -- tracing: TWO distinct trace trees (near-collision, kept) --------
    _k("AREAL_DUMP_TRACE", "bool", False,
       "Arm jax.profiler XLA/device trace dumps "
       "(utils/profiling.py). Distinct from AREAL_RL_TRACE, which "
       "records request-scoped RL spans."),
    _k("AREAL_TRACE_DIR", "str", "/tmp/areal_tpu/traces",
       "Output root for AREAL_DUMP_TRACE jax-profiler dumps. NOT the "
       "RL span dir — that is AREAL_RL_TRACE_DIR. The names nearly "
       "collide; both are load-bearing and documented here on purpose "
       "(lint env-knob checker would flag a third variant)."),
    _k("AREAL_TRACE_STEPS", "str", "",
       "Comma/range list of train steps to profile under "
       "AREAL_DUMP_TRACE (utils/profiling.py); empty = all."),
    _k("AREAL_RL_TRACE", "bool", False,
       "Arm the request-scoped RL span recorder (base/tracing.py; "
       "merge tool: scripts/merge_rl_trace.py)."),
    _k("AREAL_RL_TRACE_DIR", "str", None,
       "Output dir for RL span shards; unset = "
       "/tmp/areal_tpu/rl_trace[/<scope>]. See AREAL_TRACE_DIR note."),
    _k("AREAL_RL_TRACE_RING", "int", 65536,
       "Span ring-buffer capacity per worker before drops "
       "(base/tracing.py).", snapshot=True),
    # -- ops -------------------------------------------------------------
    _k("AREAL_CE_CHUNK", "int", None,
       "Cross-entropy vocab-chunk size override (ops/loss.py); unset = "
       "heuristic. Snapshotted at first use per jit trace.",
       snapshot=True),
    _k("AREAL_GAE_IMPL", "str", "auto",
       "Trainer GAE implementation (ops/gae.packed_gae): 'auto' "
       "(associative scan), 'scan' (the serial lax.scan oracle), "
       "'assoc', or 'pallas' (blocked Pallas scan kernel, shape-gated; "
       "opt-in: not timed on the chip against the associative scan). "
       "Pinned when the PPO prep program is first traced.",
       snapshot=True),
    # -- MoE dispatch (models/moe.py, engine/jax_engine.py) --------------
    _k("AREAL_MOE_DISPATCH", "str", None,
       "Training-time MoE dispatch override ('capacity' or 'dropless'); "
       "unset = the model config's moe.dispatch. Applied at engine "
       "construction (engine/jax_engine.py), so it participates in the "
       "jit cache key via the model config.", snapshot=True),
    _k("AREAL_MOE_DECODE_DISPATCH", "str", "dropless",
       "Decode-time MoE dispatch (engine/paged.py): 'dropless' (default "
       "— decode token counts are tiny, so capacity buckets quantize "
       "badly), 'capacity', or 'model' to follow the model config.",
       snapshot=True),
    _k("AREAL_MOE_DECODE_CAPACITY", "float", None,
       "Decode-time capacity_factor override used when the decode "
       "dispatch resolves to 'capacity'; unset = the model config's "
       "moe.capacity_factor.", snapshot=True),
    # -- functioncall ----------------------------------------------------
    _k("AREAL_SYMPY_TIMEOUT_S", "float", 3.0,
       "Per-expression sympy equivalence-check timeout "
       "(functioncall/math_grader.py)."),
    _k("AREAL_PYEXEC_TIMEOUT", "float", 6.0,
       "Sandboxed python-answer execution timeout seconds "
       "(functioncall/python_answer.py)."),
    # -- pooled reward executor (system/reward_executor.py, docs/agentic.md)
    _k("AREAL_REXEC_WORKERS", "int", 2,
       "Warm sandbox worker subprocesses per reward-executor service. "
       "Workers are REUSED across jobs (no per-case fork); a job that "
       "times out or crashes costs one respawn, not the pool."),
    _k("AREAL_REXEC_QUEUE_MAX", "int", 64,
       "Bounded pending-job queue per executor service; submits beyond "
       "it shed 429 + Retry-After (deliberate backpressure, clients "
       "fail over / retry elsewhere)."),
    _k("AREAL_REXEC_MEM_MB", "int", 1024,
       "RLIMIT_AS ceiling (MiB) applied inside each warm sandbox "
       "worker at spawn (the code_verify guard, paid once per worker "
       "instead of once per case)."),
    _k("AREAL_REXEC_TIMEOUT_S", "float", 6.0,
       "Default per-job wall timeout on the executor pool; an overrun "
       "kills + respawns the one worker running the job."),
    _k("AREAL_REXEC_MAX_REUSE", "int", 0,
       "Jobs served per warm worker before a preventive recycle "
       "(leak hygiene for long campaigns); 0 = unlimited reuse."),
    # -- multi-tenant gateway (system/gateway.py, docs/serving.md) -------
    _k("AREAL_GW_TENANTS", "str", None,
       "Tenant table for the multi-tenant gateway: comma list of "
       "'name:api_key:weight:tokens_per_s:burst:max_streams' entries "
       "(e.g. 'acme:sk-acme:4:200:400:8'). Weight drives the "
       "fair-share quantum, tokens_per_s/burst the per-tenant token "
       "bucket, max_streams the concurrent-stream cap. The reserved "
       "'trainer' tenant (internal rollout traffic, infinite weight, "
       "never shed) always exists and may not be redeclared. Unset = "
       "no external tenants (every /v1 request answers 401)."),
    _k("AREAL_GW_FAIR_SHARE", "bool", True,
       "Weighted deficit-round-robin fair-share scheduling across "
       "tenant queues on the gateway. False = naive FIFO admission "
       "(the tenant_fairness bench's unfair A/B arm: documents the "
       "noisy-neighbor collapse)."),
    _k("AREAL_GW_CHUNK_TOKENS", "int", 32,
       "New-token budget per gateway->server /generate hop; between "
       "chunks the request re-schedules through the manager, so "
       "weight cutovers and reroutes interpose at chunk granularity "
       "(same contract as partial_rollout's trainer chunking)."),
    _k("AREAL_GW_MAX_INFLIGHT", "int", 8,
       "Upstream streams the gateway runs concurrently across ALL "
       "tenants; admitted requests beyond it wait in their tenant's "
       "fair-share queue (this cap is what makes the DRR order "
       "matter)."),
    _k("AREAL_GW_RETRY_AFTER_FLOOR_S", "float", 0.05,
       "Floor on the Retry-After seconds a gateway 429 carries; the "
       "advertised value is max(floor, the TENANT'S OWN bucket refill "
       "time for the request's cost) — never derived from fleet "
       "load."),
    _k("AREAL_GW_REQUEST_TIMEOUT_S", "float", 120.0,
       "Gateway->fleet HTTP session timeout and the default deadline "
       "budget minted for a /v1 request that arrives without "
       "X-Areal-Deadline."),
    _k("AREAL_GW_INTERNAL_TOKEN", "str", None,
       "Shared secret gating the gateway's INTERNAL surfaces: the "
       "/schedule_request trainer proxy and the /v1/usage + /metrics "
       "operator endpoints (presented as X-Areal-Gateway-Token or a "
       "Bearer token). Unset = each gateway instance mints a random "
       "token at startup. Either way the active token is published to "
       "name_resolve (names.gateway_internal_token) where rollout "
       "workers — but no external tenant — can read it; a caller "
       "without it gets 401, so tenant auth/quotas/metering can never "
       "be bypassed by POSTing the proxy directly."),
    _k("AREAL_GW_USAGE_COMPACT_EVERY", "int", 4096,
       "Usage-WAL compaction cadence: after this many journaled "
       "billing records the gateway folds the journal into one "
       "aggregated per-tenant row set (RolloutWAL.compact) and ages "
       "request ids out of the dedup set down to a bounded recent "
       "window — disk, replay time, and dedup memory stay O(cadence) "
       "instead of growing with lifetime traffic. 0 disables "
       "compaction (tests pinning raw-record replay)."),
    _k("AREAL_GW_MODELS", "str", None,
       "Model ids the fleet serves, comma list; the FIRST entry is "
       "the default a request without a meaningful OpenAI 'model' "
       "field maps to. Set -> the gateway resolves the request field "
       "against this list (unknown model 404, unentitled 403 via the "
       "tenant spec's optional 7th 'a|b' entitlement field), tags the "
       "scheduling meta with the resolved id so the manager routes "
       "to that model's pool only, and meters usage per (tenant, "
       "model). Unset = single-model legacy mode."),
    _k("AREAL_GW_TLS_CERT", "str", None,
       "PEM certificate chain for TLS termination on the gateway's "
       "tenant-facing listener; must be set together with "
       "AREAL_GW_TLS_KEY (exactly one set is a startup error, never "
       "a silent plaintext listener). The published discovery URL "
       "becomes https://. Production fleets normally terminate mTLS "
       "at the load balancer instead (docs/serving.md)."),
    _k("AREAL_GW_TLS_KEY", "str", None,
       "PEM private key paired with AREAL_GW_TLS_CERT (the in-process "
       "TLS terminator for single-box deployments and the selftest's "
       "self-signed arm)."),
    _k("AREAL_GW_TRAINER_VIA_GATEWAY", "bool", False,
       "Route rollout workers' partial-rollout SCHEDULING hops "
       "through the gateway's /schedule_request trainer-tenant proxy "
       "instead of straight at the manager (system/rollout_worker.py) "
       "— the fairness-accounting regression arm; allocate/finish "
       "stay on the manager either way."),
    # -- per-task staleness (system/buffer.py, docs/agentic.md) ----------
    _k("AREAL_TASK_STALENESS_WINDOWS", "str", "math:2,agentic:8",
       "Per-task buffer-admission version windows, 'task:window' comma "
       "list: a sample whose metadata carries a matching `task` tag is "
       "DROPPED at put_batch when current_train_step - version_end "
       "exceeds its window (math tight, agentic loose). Samples with "
       "no/unlisted task tag keep the global gserver-manager gate "
       "only."),
    # -- RPC substrate (base/rpc.py, docs/fault_tolerance.md) ------------
    _k("AREAL_RPC_ATTEMPTS", "int", 4,
       "Default attempts per cross-process RPC (base/rpc.py "
       "default_policy) — replaces the per-call-site magic numbers "
       "(e.g. generation_server's old hardcoded 4-attempt KV pull)."),
    _k("AREAL_RPC_BACKOFF_S", "float", 0.05,
       "Base of the jittered exponential backoff between RPC "
       "attempts; a server's Retry-After floors the computed wait."),
    _k("AREAL_RPC_BACKOFF_MAX_S", "float", 2.0,
       "Backoff ceiling for the default RPC policy."),
    _k("AREAL_RPC_TIMEOUT_S", "float", 30.0,
       "Per-attempt timeout CAP; the effective timeout is "
       "min(cap, remaining deadline budget) so a call with 2s left "
       "never waits 30s on one attempt."),
    _k("AREAL_RPC_HEDGE", "bool", True,
       "Enable hedged reads for idempotent hash-verified GETs "
       "(weight /weights/chunk, KV /kv/chunk) when multiple holders "
       "exist. The rpc_resilience bench A/B flips this."),
    _k("AREAL_RPC_HEDGE_DELAY_S", "float", 0.25,
       "Silence window after which a hedge request launches against "
       "the next holder; first success wins, losers are cancelled."),
    _k("AREAL_RPC_BREAKER_FAILS", "int", 5,
       "Consecutive failures that open a per-peer circuit breaker "
       "(closed -> open); sheds (429) never count."),
    _k("AREAL_RPC_BREAKER_COOLDOWN_S", "float", 2.0,
       "Open-breaker cooldown before ONE half-open probe is allowed "
       "through; probe success closes the circuit, failure re-opens."),
    _k("AREAL_RPC_REDISCOVERY_ATTEMPTS", "int", 64,
       "Manager-blip budget shared by partial_rollout and the rollout "
       "worker (base/rpc.py rediscovery_policy): control-plane "
       "restarts cost seconds and hit every client at once, so this "
       "is deliberately generous and separate from per-sample "
       "failure budgets."),
    _k("AREAL_RPC_REDISCOVERY_BACKOFF_MAX_S", "float", 5.0,
       "Backoff ceiling while rediscovering a restarted manager "
       "(jittered so thousands of workers don't hammer the successor "
       "the instant it registers)."),
    _k("AREAL_CHAOS_HTTP", "bool", False,
       "Arm the generation server's /configure chaos-control surface "
       "(runtime AREAL_FAULTS arming + hit introspection) so the "
       "all-points chaos campaign can sweep one long-lived subprocess "
       "fleet. OFF in production: with it off, /configure refuses "
       "fault specs with 403."),
    # -- system ----------------------------------------------------------
    _k("AREAL_WEIGHT_PLANE", "bool", False,
       "Arm the streaming weight-distribution plane without config "
       "plumbing (system/model_worker.py; GserverManagerConfig."
       "weight_plane is the first-class switch)."),
    _k("AREAL_WEIGHT_LOAD_RETRIES", "int", 40,
       "NFS weight-load retry attempts while a dump lands "
       "(system/weight_transfer.py)."),
    _k("AREAL_WEIGHT_LOAD_RETRY_S", "float", 0.25,
       "Sleep seconds between weight-load retries."),
    # -- bench -----------------------------------------------------------
    _k("AREAL_BENCH_BANK", "str", None,
       "Bench evidence-bank directory; unset = "
       "$TMPDIR/areal_bench_bank (bench/bank.py)."),
    _k("AREAL_BENCH_STATE_TTL_S", "float", 6 * 3600.0,
       "Age beyond which banked device state is stale for reporting "
       "(bench/bank.py, bench/report.py)."),
    _k("AREAL_BENCH_PHASE_DEADLINE_S", "float", None,
       "Hard wall-clock deadline override for one phase subprocess "
       "(bench/phases.py); unset = per-phase default."),
    _k("AREAL_BENCH_PHASE_MODULES", "str", "",
       "Comma list of extra modules to import for phase registration "
       "(bench/phases.py)."),
    _k("AREAL_XLA_CACHE_DIR", "str", None,
       "Persistent XLA compilation-cache dir; unset = "
       "$TMPDIR/areal_xla_cache (bench/runner.py)."),
    _k("AREAL_TTFT_SLO_MS", "float", None,
       "p99-TTFT SLO stamped onto open-loop bench records and gated "
       "by the report validator (bench/workloads.py); unset = no SLO."),
    _k("AREAL_OPENLOOP_SERVERS", "int", 2,
       "Open-loop bench: generation-server process count."),
    _k("AREAL_OPENLOOP_POINT_S", "float", 3.0,
       "Open-loop bench: seconds per arrival-rate sweep point."),
    _k("AREAL_OPENLOOP_RATES", "str", "0.25,1.0,3.0",
       "Open-loop bench: comma list of arrival-rate multipliers."),
    _k("AREAL_OPENLOOP_WATERMARK", "int", 8,
       "Open-loop bench: admission watermark (queued prompt kilotokens "
       "per server)."),
    _k("AREAL_OPENLOOP_MAX_RPS", "float", 12.0,
       "Open-loop bench: arrival-rate ceiling."),
    _k("AREAL_DISAGG_LONG_PLEN", "int", 768,
       "Disagg A/B bench: long-prefill prompt length."),
    _k("AREAL_DISAGG_SHORT_PLEN", "int", 16,
       "Disagg A/B bench: short (decode-stream) prompt length."),
    _k("AREAL_DISAGG_STREAMS", "int", 3,
       "Disagg A/B bench: concurrent decode streams."),
    _k("AREAL_DISAGG_STREAM_TOKENS", "int", 260,
       "Disagg A/B bench: max new tokens per decode stream."),
    _k("AREAL_DISAGG_N_LONG", "int", 5,
       "Disagg A/B bench: number of long prefills injected."),
    _k("AREAL_DISAGG_LONG_GAP_S", "float", 0.7,
       "Disagg A/B bench: gap between long-prefill injections."),
    _k("AREAL_DISAGG_LONG_MAX_NEW", "int", 8,
       "Disagg A/B bench: max new tokens per long prefill."),
]

REGISTRY: Dict[str, Knob] = {k.name: k for k in _KNOBS}
assert len(REGISTRY) == len(_KNOBS), "duplicate knob declaration"

# Accessor names areal_tpu/lint's env-knob checker recognizes as
# registry-routed reads (keep in sync with the functions below).
ACCESSOR_NAMES = (
    "get_raw", "get_str", "get_int", "get_float", "get_bool", "is_set",
)


class UndeclaredKnobError(KeyError):
    pass


def _knob(name: str) -> Knob:
    try:
        return REGISTRY[name]
    except KeyError:
        raise UndeclaredKnobError(
            f"{name} is not declared in areal_tpu.base.env_registry; "
            f"add a Knob entry (the env-knob lint checker enforces this)"
        ) from None


def get_raw(name: str) -> Optional[str]:
    """Raw string value, or None when unset/empty. For call sites with
    bespoke parsing; still validates the knob is declared."""
    _knob(name)
    v = os.environ.get(name)
    return v if v else None


def is_set(name: str) -> bool:
    _knob(name)
    return bool(os.environ.get(name))


def get_str(name: str) -> Optional[str]:
    k = _knob(name)
    v = os.environ.get(name)
    return v if v else k.default


def get_int(name: str) -> Optional[int]:
    k = _knob(name)
    v = os.environ.get(name)
    if not v:
        return k.default
    try:
        return int(v)
    except ValueError as e:
        raise ValueError(f"{name}={v!r}: expected an integer") from e


def get_float(name: str) -> Optional[float]:
    k = _knob(name)
    v = os.environ.get(name)
    if not v:
        return k.default
    try:
        return float(v)
    except ValueError as e:
        raise ValueError(f"{name}={v!r}: expected a float") from e


def get_bool(name: str) -> bool:
    k = _knob(name)
    v = os.environ.get(name)
    if not v:
        # unset OR empty falls back to the default, like every other
        # getter (the module contract) — not straight to False.
        return bool(k.default)
    return v.strip().lower() not in _FALSEY


def render_docs() -> str:
    """Markdown for docs/env_vars.md — generated, drift-gated; never
    hand-edit the output file."""
    lines = [
        "# `AREAL_*` environment knobs",
        "",
        "<!-- GENERATED FILE — do not edit. Source of truth: "
        "areal_tpu/base/env_registry.py. Regenerate with: "
        "python scripts/areal_lint.py --emit-env-docs docs/env_vars.md "
        "-->",
        "",
        "Every knob the system reads, generated from the registry the "
        "`env-knob` lint checker enforces. *Snapshot* knobs are read "
        "once at construction and pinned; editing them mid-run has no "
        "effect by design. Unset or empty values fall back to the "
        "default; `-` means the default is dynamic or None (see "
        "description).",
        "",
        "| Knob | Type | Default | Snapshot | Description |",
        "|---|---|---|---|---|",
    ]
    for k in sorted(REGISTRY.values(), key=lambda k: k.name):
        default = "-" if k.default is None else repr(k.default)
        snap = "yes" if k.snapshot else ""
        doc = k.doc.replace("|", "\\|")
        lines.append(
            f"| `{k.name}` | {k.kind} | {default} | {snap} | {doc} |"
        )
    lines.append("")
    return "\n".join(lines)
