"""Single registry of every cross-process metric name.

Two namespaces, both string-matched across process boundaries and both
previously undeclared anywhere:

- ``areal:*`` — the /metrics text surface every generation server
  emits (``generation_server._h_metrics``) and four independent
  consumers regex/startswith-parse: the gserver manager's poll loop,
  ``fleet_controller.rebuild_fleet_state`` (manager-HA takeover),
  the bench fleet harness, and the system tests. A renamed line used
  to turn a consumer into a silent zero (the PR 7 "different random
  weights per server" class: contract drift found the hard way).
- ``perf/*`` — stats_tracker scalar keys shipped worker -> master in
  MFC stats payloads and read back by ``master_worker`` (perf history,
  tflops headline) and the bench workloads. ``perf/overlap_events``
  was parsed by the prefetch-overlap bench but never emitted — the
  checker class this registry exists for.

Every name is declared ONCE here (name, kind, emitter, doc); the
``metrics-registry`` checker in ``areal_tpu/lint`` flags any
``areal:*``/``perf/*`` literal not declared here, any f-string-built
name (unverifiable), any ``startswith`` parse whose prefix is
ambiguous against the registry, and any dead entry nothing references.

Parse call sites reference the generated CONSTANTS (e.g.
``metrics_registry.NUM_USED_TOKENS``) instead of raw literals — same
pattern as the PR 10 env-knob migration. ``docs/metrics.md`` is
GENERATED from this registry
(``python scripts/areal_lint.py --emit-metrics-docs docs/metrics.md``)
and drift-gated in tier-1.

This module must stay stdlib-only: it is imported by the no-jax lint
gate.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

AREAL_PREFIX = "areal:"
PERF_PREFIX = "perf/"

# Deliberate family probes: a startswith() on exactly one of these
# matches a whole name family by design (filtering, iteration) and is
# not an ambiguous single-line parse. Any other prefix probe matching
# two or more declared names fails the metrics-registry lint gate.
FAMILY_PREFIXES = (AREAL_PREFIX, PERF_PREFIX, "perf/mem_")

# kind vocabulary:
#   counter — monotonically increasing since process start (consumers
#             must diff, never reset: /metrics counters never reset)
#   gauge   — point-in-time value
#   hist    — sparse latency bucket counts (base/latency.py encoding;
#             '-' when empty); fleet aggregation merges raw counts
#   string  — non-numeric surface (role, wire tag, 'r/d' shard)
#   scalar  — stats_tracker scalar (perf/*); ``reduce`` says how DP
#             workers merge (avg/sum/max) or 'derived' for keys
#             computed at aggregation time


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str  # full wire name: "areal:x" or "perf/x"
    kind: str  # counter | gauge | hist | string | scalar
    emitter: str  # repo-rel module (under areal_tpu/) that emits it
    doc: str
    reduce: str = ""  # perf/* only: avg | sum | max | derived


def _m(name: str, kind: str, emitter: str, doc: str, *,
       reduce: str = "") -> Metric:
    return Metric(name=name, kind=kind, emitter=emitter, doc=doc,
                  reduce=reduce)


_GS = "system/generation_server.py"

_METRICS: List[Metric] = [
    # -- serving load (admission, routing) -------------------------------
    _m("areal:num_running_reqs", "gauge", _GS,
       "In-flight requests on the engine loop; manager load estimate."),
    _m("areal:num_used_tokens", "gauge", _GS,
       "KV tokens resident in the paged pool; the manager's "
       "least_token_usage routing signal (poll + in-flight fold)."),
    _m("areal:queue_depth", "gauge", _GS,
       "Requests queued behind admission on this server."),
    _m("areal:queued_prompt_tokens", "gauge", _GS,
       "Prompt tokens queued behind admission; the 429 watermark and "
       "re-role sizer input."),
    _m("areal:load_shed_total", "counter", _GS,
       "Requests shed with 429 + Retry-After. Deliberate backpressure, "
       "NOT failures — the manager must never count these toward "
       "eviction."),
    _m("areal:total_requests", "counter", _GS,
       "All /generate requests admitted; fleet hit-rate denominator "
       "(manager aggregates ratio of sums, never averages rates)."),
    _m("areal:total_generated_tokens", "counter", _GS,
       "Tokens generated since start; fleet throughput numerator."),
    _m("areal:num_interrupted_reqs", "counter", _GS,
       "Generations interrupted by weight cutover (resubmitted by "
       "partial_rollout with the accumulated prefix)."),
    _m("areal:num_preempted_reqs", "counter", _GS,
       "Requests preempted by the scheduler for page pressure."),
    # -- MoE decode router telemetry -------------------------------------
    _m("areal:moe_drop_rate", "gauge", _GS,
       "Decode-time realized MoE token-drop rate, layer-mean over the "
       "last decode block (0 for dense models and dropless dispatch)."),
    _m("areal:moe_router_entropy", "gauge", _GS,
       "Decode-time MoE router entropy (nats), layer-mean over the "
       "last decode block; collapse detector for serving-side drift."),
    # -- latency SLOs ----------------------------------------------------
    _m("areal:ttft_p50_ms", "gauge", _GS,
       "Per-server TTFT p50 (humans; fleet math uses the hist)."),
    _m("areal:ttft_p99_ms", "gauge", _GS,
       "Per-server TTFT p99 (humans; SLO gate uses the hist)."),
    _m("areal:itl_p50_ms", "gauge", _GS,
       "Per-server inter-token latency p50."),
    _m("areal:itl_p99_ms", "gauge", _GS,
       "Per-server inter-token latency p99."),
    _m("areal:ttft_hist", "hist", _GS,
       "Raw TTFT bucket counts (base/latency.py edges, sparse "
       "i:count) — percentiles cannot be averaged, so the manager and "
       "bench merge counts."),
    _m("areal:itl_hist", "hist", _GS,
       "Raw ITL bucket counts; fleet ratio-of-sums aggregation."),
    # -- weights ---------------------------------------------------------
    _m("areal:weight_version", "gauge", _GS,
       "Engine weight version; staleness control + HA rebuild input."),
    _m("areal:last_weight_swap_s", "gauge", _GS,
       "Seconds the last on-device weight swap took."),
    _m("areal:last_weight_stage_s", "gauge", _GS,
       "Seconds the last host-side weight staging took."),
    _m("areal:last_weight_load_s", "gauge", _GS,
       "Seconds the last full weight load took (disk or plane)."),
    _m("areal:weight_load_fast_path", "gauge", _GS,
       "1.0 when the last load came from the shm_raw fast path."),
    _m("areal:weight_transfer_ms", "gauge", _GS,
       "Weight-plane network transfer ms (overlaps serving — "
       "deliberately separate from cutover)."),
    _m("areal:weight_cutover_ms", "gauge", _GS,
       "Weight cutover interrupt+swap window ms (budget-bounded)."),
    _m("areal:weight_verify_ms", "gauge", _GS,
       "Per-chunk hash verification ms for the last plane transfer."),
    _m("areal:weight_bytes_from_origin", "counter", _GS,
       "Plane bytes fetched from the origin; the peer-fanout benches "
       "pin this (zero origin bytes per peer join)."),
    _m("areal:weight_bytes_from_peers", "counter", _GS,
       "Plane bytes fetched from peer servers."),
    _m("areal:weight_chunks_served", "counter", _GS,
       "Plane chunks this server served to peers."),
    _m("areal:weight_bytes_served", "counter", _GS,
       "Plane bytes this server served to peers."),
    _m("areal:weight_expected_bytes", "gauge", _GS,
       "THIS server's chunk-stream size (shard slice and/or quantized "
       "wire) — ingress/expected reads 1.0 for a complete sliced "
       "fetch, never 'incomplete' against the full payload."),
    _m("areal:weight_ingress_payload_equivalents", "gauge", _GS,
       "Ingress bytes / expected bytes for the last transfer "
       "(attested 1.0 -> 0.50 -> 0.25 across TP1/TP2/TP2+int8)."),
    _m("areal:weight_wire", "string", _GS,
       "Wire encoding of the last plane transfer (float/int8)."),
    _m("areal:weight_shard", "string", _GS,
       "'rank/degree' TP shard this server holds, '-' unsharded; "
       "second source besides the heartbeat so a fanout racing a "
       "server's first beat never plans it into the unsharded group."),
    # -- disaggregated serving / roles -----------------------------------
    _m("areal:role", "string", _GS,
       "Live pool role (prefill/decode/unified) as the server sees "
       "it; the sizer's view wins until this surface catches up."),
    _m("areal:model_id", "string", _GS,
       "Registered model family this server hosts (multi-model "
       "serving plane, system/model_registry.py); second source "
       "besides the heartbeat so a manager-HA rebuild pools the "
       "fleet per model without waiting a beat."),
    _m("areal:elastic", "gauge", _GS,
       "1.0 when the CONFIGURED role is unified (re-role pool "
       "eligibility), independent of the live role."),
    _m("areal:kv_pages_free", "gauge", _GS,
       "Free paged-pool pages; autoscaler low-watermark input."),
    _m("areal:kv_pages_total", "gauge", _GS,
       "Total paged-pool pages."),
    # -- KV handoff (prefill -> decode wire) -----------------------------
    _m("areal:kv_export_total", "counter", _GS,
       "KV handoffs exported (prefill side)."),
    _m("areal:kv_export_bytes", "counter", _GS,
       "KV handoff bytes exported."),
    _m("areal:last_kv_export_ms", "gauge", _GS,
       "Duration of the last KV export."),
    _m("areal:kv_import_total", "counter", _GS,
       "KV handoffs imported (decode side)."),
    _m("areal:kv_import_bytes", "counter", _GS,
       "KV handoff bytes imported."),
    _m("areal:last_kv_import_ms", "gauge", _GS,
       "Duration of the last KV import."),
    _m("areal:last_kv_transfer_ms", "gauge", _GS,
       "End-to-end duration of the last KV handoff transfer."),
    _m("areal:kv_handoff_ok", "counter", _GS,
       "Handoffs completed on the disagg wire."),
    _m("areal:kv_handoff_failed", "counter", _GS,
       "Handoffs that failed outright (after retries)."),
    _m("areal:kv_handoff_fallback", "counter", _GS,
       "Handoffs that fell back to local-serve (the A/B bench pins "
       "this to zero on the disagg arm)."),
    # -- tiered KV plane (spill/restore, docs/serving.md) ----------------
    _m("areal:kv_spill_total", "counter", _GS,
       "Prefix evictions spilled to the host tier instead of freed."),
    _m("areal:kv_spill_bytes", "counter", _GS,
       "Bytes spilled to the KV tier (int8 wire ~0.31x float)."),
    _m("areal:kv_spill_tokens", "counter", _GS,
       "Tokens covered by spilled prefixes."),
    _m("areal:kv_restore_total", "counter", _GS,
       "Prefix restores from any tier (delta prefill instead of full "
       "re-prefill)."),
    _m("areal:kv_restore_host", "counter", _GS,
       "Restores served from the host-RAM tier."),
    _m("areal:kv_restore_disk", "counter", _GS,
       "Restores served from the disk tier."),
    _m("areal:kv_restore_tokens", "counter", _GS,
       "Tokens restored from tiers (re-prefill work avoided)."),
    _m("areal:last_kv_restore_ms", "gauge", _GS,
       "Duration of the last tier restore."),
    _m("areal:kv_prefix_lost_total", "counter", _GS,
       "Prefixes the tier FAILED to preserve — the residual true-loss "
       "count the tier exists to zero (chaos bench asserts 0)."),
    _m("areal:kv_tier_host_bytes", "gauge", _GS,
       "Bytes resident in the host-RAM tier."),
    _m("areal:kv_tier_disk_bytes", "gauge", _GS,
       "Bytes resident in the disk tier."),
    _m("areal:kv_tier_host_entries", "gauge", _GS,
       "Entries resident in the host-RAM tier."),
    _m("areal:kv_tier_disk_entries", "gauge", _GS,
       "Entries resident in the disk tier."),
    _m("areal:kv_tier_misses", "counter", _GS,
       "Tier lookups that found nothing (full re-prefill)."),
    _m("areal:kv_tier_corrupt_dropped", "counter", _GS,
       "Tier entries dropped on hash-verify failure at read-back."),
    _m("areal:kv_tier_peer_hits", "counter", _GS,
       "Restores served from a PEER's tier via the global prefix "
       "index (kv_source routing hint)."),
    _m("areal:kv_tier_peer_bytes", "counter", _GS,
       "Bytes fetched from peer tiers."),
    _m("areal:kv_tier_peer_failed", "counter", _GS,
       "Peer-tier fetches that failed (fell back to re-prefill)."),
    # -- elastic fleet (drain-then-leave, docs/fault_tolerance.md) -------
    _m("areal:draining", "gauge", _GS,
       "1.0 while drain-then-leave is quiescing this server."),
    _m("areal:kv_migrated_out", "counter", _GS,
       "Parked prefixes migrated to survivors during drain."),
    _m("areal:kv_drain_lost", "counter", _GS,
       "Prefixes lost during drain — the drain analogue of "
       "kv_prefix_lost_total; the elastic e2e pins it to 0."),
    _m("areal:kv_accepted", "counter", _GS,
       "Migrated prefixes this server accepted from a drainer."),
    _m("areal:kv_accept_bytes", "counter", _GS,
       "Bytes accepted from draining peers."),
    _m("areal:kv_manifests_served", "counter", _GS,
       "KV tier manifests served to peers (/kv/manifest)."),
    _m("areal:kv_chunks_served", "counter", _GS,
       "KV tier chunks served to peers (/kv/chunk)."),
    # -- prefix cache ----------------------------------------------------
    _m("areal:prefix_cache_hits", "counter", _GS,
       "Prefix-cache hits; affinity-routing numerator (fleet "
       "ratio-of-sums with total_requests)."),
    _m("areal:prefix_tokens_reused", "counter", _GS,
       "Prompt tokens served from cached prefixes."),
    _m("areal:prefix_cached_tokens", "counter", _GS,
       "Tokens currently parked in cached prefixes."),
    # -- speculative decoding --------------------------------------------
    _m("areal:spec_tokens_per_step", "gauge", _GS,
       "Mean emitted tokens per spec-decode step (per-server ratio; "
       "humans — fleet math uses the raw sums below)."),
    _m("areal:spec_emitted_tokens", "counter", _GS,
       "Raw spec-decode emitted-token sum (fleet yield numerator)."),
    _m("areal:spec_active_steps", "counter", _GS,
       "Raw spec-decode active-step sum (fleet yield denominator)."),
    # -- RPC substrate (base/rpc.py, docs/fault_tolerance.md) ------------
    _m("areal:rpc_attempts", "counter", _GS,
       "Outbound RPC attempts this process made through base/rpc.py "
       "(retries included)."),
    _m("areal:rpc_retries", "counter", _GS,
       "Attempts that were retries of a failed/shed predecessor."),
    _m("areal:rpc_failures", "counter", _GS,
       "Calls that exhausted their retry budget (includes each "
       "exhausted hedge LEG; see rpc_hedge_failures for whole races "
       "lost)."),
    _m("areal:rpc_hedges", "counter", _GS,
       "Secondary (hedge) requests launched after the primary went "
       "AREAL_RPC_HEDGE_DELAY_S without answering."),
    _m("areal:rpc_hedge_wins", "counter", _GS,
       "Races a hedge won — the rpc_resilience bench's proof that "
       "hedging, not luck, cut the tail."),
    _m("areal:rpc_hedge_cancelled", "counter", _GS,
       "Losing hedge legs cancelled/abandoned; their bytes are "
       "dropped, never double-counted into ingress/egress."),
    _m("areal:rpc_hedge_failures", "counter", _GS,
       "Whole hedged races lost (every leg failed), counted once per "
       "race — a transient leg failure inside a race the hedge won "
       "does NOT land here."),
    _m("areal:rpc_deadline_expired", "counter", _GS,
       "Calls short-circuited because the propagated X-Areal-Deadline "
       "budget was already spent (includes refusals before attempt "
       "1)."),
    _m("areal:rpc_breaker_rejections", "counter", _GS,
       "Attempts refused locally by an OPEN per-peer circuit "
       "breaker — budget saved, not failures."),
    _m("areal:rpc_breaker_opens", "counter", _GS,
       "closed->open (and failed-probe re-open) breaker transitions."),
    # -- pooled reward executor (system/reward_executor.py) --------------
    _m("areal:rexec_jobs_total", "counter",
       "system/reward_executor.py",
       "Sandboxed jobs completed (ok or failed) by this executor's "
       "warm worker pool; saturation-sweep throughput numerator."),
    _m("areal:rexec_job_failures", "counter",
       "system/reward_executor.py",
       "Jobs that returned failed (guarded exec raised, nonzero "
       "exit, rlimit kill) — episode-level failures, distinct from "
       "sheds."),
    _m("areal:rexec_timeouts", "counter",
       "system/reward_executor.py",
       "Jobs killed at their wall timeout (the one worker running "
       "the job is killed + respawned; the pool survives)."),
    _m("areal:rexec_shed_total", "counter",
       "system/reward_executor.py",
       "Submits shed with 429 + Retry-After past the bounded queue "
       "watermark. Deliberate backpressure, NOT failures — clients "
       "fail over or back off."),
    _m("areal:rexec_queue_depth", "gauge",
       "system/reward_executor.py",
       "Jobs pending or running on the pool right now; the "
       "saturation sweep's load signal."),
    _m("areal:rexec_workers_alive", "gauge",
       "system/reward_executor.py",
       "Warm sandbox workers currently alive in the pool."),
    _m("areal:rexec_worker_respawns", "counter",
       "system/reward_executor.py",
       "Worker respawns (timeout kill, crash, preventive recycle) "
       "since start; the warm-reuse test pins this at 0 under clean "
       "load."),
    _m("areal:rexec_warm_hits", "counter",
       "system/reward_executor.py",
       "Jobs served by an already-warm worker (no spawn on the job's "
       "critical path) — the pooled service's whole point; the bench "
       "asserts warm_hits/jobs ~ 1 after warmup."),
    # -- multi-tenant gateway (system/gateway.py, docs/serving.md) -------
    _m("areal:gw_requests_total", "counter", "system/gateway.py",
       "/v1 requests ADMITTED through auth + bucket + fair-share "
       "(completed or failed upstream); the tenant_fairness bench's "
       "throughput denominator."),
    _m("areal:gw_auth_failures_total", "counter", "system/gateway.py",
       "Requests refused 401 (missing/unknown API key, or the gw.auth "
       "chaos point firing in the key lookup)."),
    _m("areal:gw_shed_total", "counter", "system/gateway.py",
       "Requests shed 429 by a tenant's OWN token bucket or stream "
       "cap, Retry-After from that bucket. Deliberate per-tenant "
       "backpressure, NOT failures — the fleet never sees these."),
    _m("areal:gw_prompt_tokens_total", "counter", "system/gateway.py",
       "Prompt tokens metered across tenants (ledger grand total; "
       "/v1/usage carries the per-tenant split)."),
    _m("areal:gw_completion_tokens_total", "counter",
       "system/gateway.py",
       "Completion tokens metered across tenants, billed as emitted "
       "— a mid-stream failover resumes from the billed prefix, so "
       "retried chunks never double-count."),
    _m("areal:gw_active_streams", "gauge", "system/gateway.py",
       "Upstream SSE streams running right now (bounded by "
       "AREAL_GW_MAX_INFLIGHT)."),
    _m("areal:gw_queue_depth", "gauge", "system/gateway.py",
       "Admitted requests waiting in tenant fair-share queues."),
    _m("areal:gw_fairshare_picks_total", "counter",
       "system/gateway.py",
       "DRR dispatch decisions taken while 2+ tenant queues were "
       "nonempty — proof the fair-share queue actually arbitrated "
       "(validate_bench refuses tenant_fairness records where this "
       "never moved)."),
    _m("areal:gw_ttft_hist", "hist", "system/gateway.py",
       "Gateway-observed TTFT bucket counts across tenants "
       "(base/latency.py edges; per-tenant hists ride /v1/usage)."),
    _m("areal:gw_itl_hist", "hist", "system/gateway.py",
       "Gateway-observed inter-token latency bucket counts across "
       "tenants."),
    _m("areal:gw_upstream_failovers_total", "counter",
       "system/gateway.py",
       "Mid-stream server deaths survived by rerouting through the "
       "manager with the accumulated prefix (PR 14 discipline on the "
       "gateway->server hop)."),
    _m("areal:gw_usage_replayed_total", "counter",
       "system/gateway.py",
       "Usage-WAL records replayed into the ledger at gateway "
       "restart."),
    _m("areal:gw_usage_dup_dropped_total", "counter",
       "system/gateway.py",
       "Usage records dropped at replay/append because their request "
       "id was already accounted — the exactly-once ledger doing its "
       "job across restarts."),
    _m("areal:gw_model_rejections_total", "counter",
       "system/gateway.py",
       "Requests refused at model resolution: 404 (model unknown to "
       "the registry) or 403 (tenant not entitled to it). Neither "
       "reaches the fleet; distinct from auth failures and sheds."),
    _m("areal:gw_usage_compactions_total", "counter",
       "system/gateway.py",
       "Usage-WAL compactions: every AREAL_GW_USAGE_COMPACT_EVERY "
       "billing records the journal folds into one aggregated "
       "per-tenant record, bounding disk, replay time, and the "
       "request-id dedup set for long-lived gateways."),
    # ====================================================================
    # perf/* — stats_tracker scalar keys (worker -> master MFC stats
    # payloads; master_worker perf history + bench workloads).
    # ====================================================================
    _m("perf/sec", "scalar", "system/model_worker.py",
       "Wall seconds of the MFC on this worker.", reduce="max"),
    _m("perf/elapsed", "scalar", "system/model_function_call.py",
       "Aggregated MFC wall seconds (slowest worker) — becomes "
       "timeperf/<mfc> in the master's history.", reduce="max"),
    _m("perf/flops", "scalar", "system/model_worker.py",
       "Analytic FLOP count of the MFC (monitor.mfc_flops).",
       reduce="sum"),
    _m("perf/tflops", "scalar", "system/model_function_call.py",
       "flops/elapsed/1e12, computed at aggregation.",
       reduce="derived"),
    _m("perf/gen_tokens", "scalar", "system/model_worker.py",
       "New tokens generated by a generate MFC (group-sampling "
       "replicas subtracted).", reduce="sum"),
    _m("perf/gen_tokens_per_sec", "scalar",
       "system/model_function_call.py",
       "gen_tokens/elapsed, computed at aggregation.",
       reduce="derived"),
    _m("perf/packing_efficiency", "scalar", "engine/jax_engine.py",
       "Realized token/cell density of what shipped to HBM (the "
       "packer's own estimate, datapack.ladder_density, for non-packed "
       "paths).", reduce="avg"),
    _m("perf/h2d_wait_ms", "scalar", "engine/jax_engine.py",
       "Host-to-device staging wait per step; MAX across DP workers "
       "— the step blocks on the slowest, averaging understates.",
       reduce="max"),
    _m("perf/dispatch_gap_ms", "scalar", "engine/jax_engine.py",
       "Gap between microbatch dispatches (prefetch pipeline bubble).",
       reduce="max"),
    _m("perf/overlap_events", "scalar", "engine/jax_engine.py",
       "Microbatches staged during a previous step's compute (the "
       "prefetch-overlap bench's engagement proof).", reduce="sum"),
    # MoE router telemetry (engine/jax_engine._record_moe_stats; per-MFC
    # fold in master_worker perf_summary, bench JSON passthrough).
    _m("perf/moe_drop_rate", "scalar", "engine/jax_engine.py",
       "Realized fraction of routed (token, expert) assignments dropped "
       "by capacity buckets this step; exactly 0 on dropless arms.",
       reduce="avg"),
    _m("perf/moe_router_entropy", "scalar", "engine/jax_engine.py",
       "Mean per-token router-softmax entropy (nats). Collapse toward "
       "0 means the router funnels everything to few experts.",
       reduce="avg"),
    _m("perf/moe_expert_overload", "scalar", "engine/jax_engine.py",
       "max_e(f_e) * E — hottest expert's token share relative to the "
       "uniform ideal (1.0 = perfectly balanced). MAX across DP "
       "workers: the hottest shard bounds the step.", reduce="max"),
    _m("perf/moe_a2a_bytes", "scalar", "engine/jax_engine.py",
       "Trace-time estimate of bytes exchanged by the expert-parallel "
       "dispatch per step (0 at EP1); SUM accumulates the window "
       "total.", reduce="sum"),
    _m("perf/rollout_e2e_p50_ms", "scalar",
       "system/model_function_call.py",
       "Rollout end-to-end p50 from RL spans.", reduce="max"),
    _m("perf/rollout_e2e_p95_ms", "scalar",
       "system/model_function_call.py",
       "Rollout end-to-end p95 from RL spans.", reduce="max"),
    _m("perf/reprefill_tokens", "scalar",
       "system/model_function_call.py",
       "Tokens re-prefilled after interrupts this MFC.", reduce="sum"),
    # Multi-turn episode telemetry (trajectory metadata stamped by the
    # agents, folded at MFC aggregation like rollout_e2e above).
    _m("perf/episode_turns", "scalar",
       "system/model_function_call.py",
       "Agent turns across the episodes consumed by this train MFC.",
       reduce="sum"),
    _m("perf/episode_tool_calls", "scalar",
       "system/model_function_call.py",
       "Tool invocations (executor-pool python exec, calculator, "
       "search) across the consumed episodes.", reduce="sum"),
    _m("perf/task_staleness_math", "scalar",
       "system/model_function_call.py",
       "Mean version lag (train step - version_end) of consumed "
       "samples tagged task=math — the tight per-task window.",
       reduce="max"),
    _m("perf/task_staleness_agentic", "scalar",
       "system/model_function_call.py",
       "Mean version lag of consumed samples tagged task=agentic — "
       "the loose window (multi-turn episodes live longer).",
       reduce="max"),
    _m("perf/task_stale_dropped_math", "scalar",
       "system/model_function_call.py",
       "Samples tagged task=math dropped at buffer admission by the "
       "math staleness window since the last train step — the "
       "per-task split of areal:train_stale_dropped_total.",
       reduce="sum"),
    _m("perf/task_stale_dropped_agentic", "scalar",
       "system/model_function_call.py",
       "Samples tagged task=agentic dropped at buffer admission by "
       "the agentic staleness window since the last train step.",
       reduce="sum"),
    # HBM telemetry (monitor.device_memory_stats, shipped per MFC by
    # model_worker through perf_mem_stats below).
    _m("perf/mem_bytes_in_use", "scalar", "base/monitor.py",
       "Device bytes in use, summed over local devices.",
       reduce="max"),
    _m("perf/mem_bytes_limit", "scalar", "base/monitor.py",
       "Device byte limit, summed over local devices.", reduce="max"),
    _m("perf/mem_peak_bytes_in_use", "scalar", "base/monitor.py",
       "Peak device bytes in use.", reduce="max"),
    _m("perf/mem_frac_in_use", "scalar", "base/monitor.py",
       "in_use/limit fraction (the OOM-guard input).", reduce="max"),
    _m("perf/mem_devices_reporting", "scalar", "base/monitor.py",
       "Local devices that reported memory stats.", reduce="max"),
    # Durable training plane (rollout WAL + exactly-once ledger +
    # async checkpoint). The two headline invariant counters are
    # expected to read 0 — the kill-anywhere e2e asserts exactly that.
    _m("areal:train_samples_lost_total", "counter",
       "system/push_pull_stream.py",
       "Pushed samples dropped after exhausting the redelivery budget "
       "(AREAL_WAL_REDELIVER_MAX). 0 under the default unbounded "
       "budget — the exactly-once invariant."),
    _m("areal:train_samples_duplicated_total", "counter",
       "system/buffer.py",
       "Samples DETECTED entering training twice (a sequence id "
       "consumed again after the ledger marked it). A defensive "
       "invariant detector, not a dedup count: redeliveries/replays "
       "the ledger filters at admission are counted separately "
       "(areal:train_wal_dup_dropped_total). Expected 0."),
    _m("areal:train_wal_replayed_total", "counter",
       "system/stream_dataset.py",
       "WAL records replayed into the stream dataset at restart "
       "(in-flight rollouts that survived a trainer kill)."),
    _m("areal:train_wal_dup_dropped_total", "counter",
       "system/stream_dataset.py",
       "Redelivered/replayed samples dropped at admission because "
       "their sequence id was already journaled or consumed — the "
       "ledger doing its job (each drop is a prevented duplicate)."),
    _m("areal:train_stale_dropped_total", "counter",
       "system/buffer.py",
       "Samples dropped at buffer admission because their task's "
       "staleness window (AREAL_TASK_STALENESS_WINDOWS) was exceeded "
       "— per-task admission on top of the gserver manager's global "
       "allocation gate."),
    _m("areal:train_ckpt_stall_ms", "gauge", "engine/checkpoint.py",
       "Step-loop stall of the most recent engine checkpoint: full "
       "save duration when synchronous, reference-snapshot handoff "
       "only when AREAL_CKPT_ASYNC routes the write off-thread (the "
       "recovery_slo bench A/Bs the two)."),
]

REGISTRY: Dict[str, Metric] = {m.name: m for m in _METRICS}
assert len(REGISTRY) == len(_METRICS), "duplicate metric declaration"


def const_name(name: str) -> str:
    """Deterministic constant identifier for a metric name:
    ``areal:num_used_tokens`` -> ``NUM_USED_TOKENS``,
    ``perf/h2d_wait_ms`` -> ``PERF_H2D_WAIT_MS``."""
    if name.startswith(AREAL_PREFIX):
        return name[len(AREAL_PREFIX):].upper()
    if name.startswith(PERF_PREFIX):
        return "PERF_" + name[len(PERF_PREFIX):].upper()
    raise ValueError(f"metric {name!r} outside both namespaces")


# Bind one module constant per entry (NUM_USED_TOKENS = "areal:...").
# Parse sites reference these instead of literals; the metrics-registry
# checker verifies `metrics_registry.X` attributes resolve here.
CONSTANTS: Dict[str, str] = {}
for _metric in _METRICS:
    _c = const_name(_metric.name)
    assert _c not in CONSTANTS, f"constant collision: {_c}"
    CONSTANTS[_c] = _metric.name
    globals()[_c] = _metric.name
del _metric, _c


def parse_line(line: str) -> Optional[Tuple[str, str]]:
    """Split one ``/metrics`` text line into (declared name, value
    text). Returns None for blank/unknown lines. Exact name match —
    immune to the startswith prefix-ambiguity class the lint checker
    flags."""
    name, _, value = line.strip().partition(" ")
    if name in REGISTRY:
        return name, value
    return None


def perf_mem_stats(mem: Dict[str, float]) -> Dict[str, float]:
    """Prefix monitor.device_memory_stats() keys into declared
    ``perf/mem_*`` scalars. The one legal dynamic build of a perf key
    — anywhere else the metrics-registry checker flags f-string-built
    names; here every output key is validated against the registry."""
    out = {}
    for k, v in mem.items():
        name = f"{PERF_PREFIX}{k}"
        if name not in REGISTRY:
            raise KeyError(
                f"{name} is not declared in "
                f"areal_tpu.base.metrics_registry; declare it (name, "
                f"kind, emitter, doc) — the metrics-registry lint "
                f"checker enforces this"
            )
        out[name] = v
    return out


def render_docs() -> str:
    """Markdown for docs/metrics.md — generated, drift-gated; never
    hand-edit the output file."""
    lines = [
        "# Cross-process metric names",
        "",
        "<!-- GENERATED FILE — do not edit. Source of truth: "
        "areal_tpu/base/metrics_registry.py. Regenerate with: "
        "python scripts/areal_lint.py --emit-metrics-docs "
        "docs/metrics.md -->",
        "",
        "Every `areal:*` /metrics line and every `perf/*` "
        "stats_tracker scalar key that crosses a process boundary, "
        "generated from the registry the `metrics-registry` lint "
        "checker enforces. Counters are monotonic since process start "
        "(consumers diff; they never reset). `hist` lines carry "
        "sparse `i:count` buckets over base/latency.py edges — fleet "
        "aggregation merges counts, never averages percentiles.",
        "",
        "## `areal:*` — generation-server /metrics lines",
        "",
        "| Name | Kind | Description |",
        "|---|---|---|",
    ]
    areal = [m for m in _METRICS if m.name.startswith(AREAL_PREFIX)]
    perf = [m for m in _METRICS if m.name.startswith(PERF_PREFIX)]
    for m in sorted(areal, key=lambda m: m.name):
        doc = m.doc.replace("|", "\\|")
        lines.append(f"| `{m.name}` | {m.kind} | {doc} |")
    lines += [
        "",
        "## `perf/*` — stats_tracker scalar keys (worker → master)",
        "",
        "| Name | Reduce | Emitter | Description |",
        "|---|---|---|---|",
    ]
    for m in sorted(perf, key=lambda m: m.name):
        doc = m.doc.replace("|", "\\|")
        lines.append(
            f"| `{m.name}` | {m.reduce} | `{m.emitter}` | {doc} |"
        )
    lines.append("")
    return "\n".join(lines)
