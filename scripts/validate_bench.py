#!/usr/bin/env python
"""Schema + attestation validator for bench evidence.

    python scripts/validate_bench.py BENCH_r06.json
    python scripts/validate_bench.py --bank /tmp/areal_bench_bank
    python scripts/validate_bench.py --require-driver-verified BENCH_r06.json

Nonzero exit when:
- any record is malformed (schema tag, pass/status enums, missing or
  inconsistent attestation block — e.g. ``driver_verified: true`` on a
  non-TPU platform);
- a headline number is presented WITHOUT ``driver_verified: true`` and
  without the explicit ``"evidence": "proxy"`` label (the round-6
  mandate: chip numbers and CPU smoke numbers must never be conflated);
- the report claims top-level ``driver_verified: true`` that its own
  records do not back;
- with ``--require-driver-verified``: any headline entry is not
  driver-verified at all (the gate for publishing a BENCH round as chip
  evidence).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.bench import bank  # noqa: E402

# Per-phase value schemas: an ok MEASURE record for these phases must
# carry every listed numeric key. Catches a phase body drifting away
# from what the report/readers consume without anything failing loudly.
PHASE_VALUE_KEYS: Dict[str, tuple] = {
    # Sharded-training evidence without its parity/high-water/roundtrip
    # fields is not evidence: a record could bank mesh step times off a
    # run whose sharded math silently diverged.
    "train_sharded": (
        "fsdp2_parity_ok", "tp2_parity_ok", "loss_parity_max_rel_err",
        "dump_highwater_frac", "dump_roundtrip_ok", "n_devices",
    ),
    "weight_update": (
        "weight_update_ms", "weight_transfer_ms", "weight_cutover_ms",
        "origin_full_payloads",
    ),
    # The hedging A/B is only evidence as a PAIR with its win/cancel
    # accounting: a low hedged p99 without hedge_wins could just mean
    # the injected tail never landed.
    "rpc_resilience": (
        "n_chunks", "injected_delay_ms", "hedge_delay_ms",
        "unhedged_p50_ms", "unhedged_p99_ms",
        "hedged_p50_ms", "hedged_p99_ms",
        "hedge_wins", "hedge_cancelled", "hedge_failures",
    ),
    # Durable-plane evidence is only evidence when nothing was lost OR
    # double-trained along the way and the async arm actually bought
    # its stall reduction: a fast MTTR next to a nonzero loss counter
    # is a broken plane with a good-looking timing.
    "recovery_slo": (
        "state_mb", "n_ckpt_saves",
        "sync_stall_ms_mean", "async_stall_ms_mean",
        "async_stall_saved_frac", "mttr_ms",
        "wal_records", "wal_replayed", "redelivered",
        "samples_lost", "samples_duplicated",
    ),
    # Quantized-wire evidence without its dequant-parity check field is
    # not evidence: a record could bank a great ingress number off a
    # stream that assembles to garbage weights.
    "weight_plane_sharded": (
        "full_payload_bytes", "tp1_ingress_frac", "tp2_ingress_frac",
        "tp2_int8_ingress_frac", "origin_full_payloads",
        "replica_bytes_from_origin",
        "dequant_parity_ok", "dequant_max_abs_err",
    ),
    "serving_openloop": (
        "capacity_rps",
        "overload_offered_rps",
        "overload_admission_p99_ttft_ms",
        "overload_admission_goodput_rps",
        "overload_baseline_p99_ttft_ms",
        "overload_baseline_goodput_rps",
    ),
    # The tiered-KV probe is only evidence as a PAIR (tier vs full-
    # re-prefill baseline) WITH its per-tier hit accounting and loss
    # counter: a fast TTFT number without those could just mean the
    # sweep never exceeded HBM.
    "sessions_resident": (
        "n_resident_max",
        "tier_ttft_p99_ms",
        "baseline_ttft_p99_ms",
        "hit_rate_hbm",
        "hit_rate_host",
        "hit_rate_peer",
        "miss_rate",
        "kv_spill_total",
        "kv_prefix_lost",
        "int8_spill_bytes_ratio",
    ),
    # Elastic-fleet evidence is only evidence when nothing was lost
    # along the way: a record with ANY failed rollout, a "peer" join
    # that actually read origin bytes, or drained prefixes that did not
    # migrate is a broken control plane with good-looking timings.
    "fleet_elastic": (
        "join_peer_ms",
        "join_origin_ms",
        "join_peer_origin_bytes",
        "killover_recovery_ms",
        "killover_epoch",
        "failed_rollouts",
        "drain_migrated",
        "drain_lost",
        "kv_prefix_lost",
        "n_servers_max",
        "autoscale_out_actions",
        "autoscale_launched",
        "autoscale_n_after",
        "autoscale_load_failed",
    ),
    # Multi-model evidence is only evidence with its isolation and
    # independence accounting next to the latency pair: a clean B-side
    # p99 with a contaminated parity row, a cross-model route/KV hit,
    # or a steady pool whose version (or outputs) moved during the
    # other model's cutover is the exact failure the phase refuses.
    "multi_model_serving": (
        "n_models", "families_distinct",
        "parity_mismatches", "cross_model_routes", "cross_model_kv_hits",
        "unknown_model_rejected", "unknown_model_routed",
        "cutover_version_before", "cutover_version_after",
        "steady_version_after", "steady_outputs_stable",
        "cutover_outputs_changed",
        "b_completed", "b_failed",
        "b_p99_ttft_base_ms", "b_p99_ttft_cutover_ms",
        "kv_prefix_lost",
    ),
    # Gateway fairness evidence is only evidence as the full A/B/C
    # triple with its shed and queue accounting: a good-looking fair-arm
    # p99 without aggressor sheds (the flood never saturated), without
    # DRR picks (the queue never arbitrated), or without the FIFO arm's
    # collapse next to it proves nothing about fair share.
    "tenant_fairness": (
        "solo_p99_ttft_ms", "fair_p99_ttft_ms", "unfair_p99_ttft_ms",
        "fair_over_solo", "unfair_over_fair",
        "aggressor_sheds", "fairshare_picks", "victim_failed",
    ),
    # MoE fast-path evidence is only evidence with its parity, drop, and
    # ingress accounting: a fast EP2 step time next to a diverged loss
    # trajectory, a "dropless" arm that realized drops, or an
    # expert-sliced stream that did not shrink ingress is the exact
    # failure the phase exists to catch.
    "moe_scaling": (
        "n_devices", "dense_step_s", "moe_ep1_step_s", "moe_ep2_step_s",
        "capacity_step_s", "ep_parity_ok", "capacity_parity_ok",
        "ep_loss_max_rel_err", "dropless_drop_rate", "ep_degree",
        "ep_ingress_frac_max", "origin_full_payloads",
    ),
    # Agentic-rollout evidence is only evidence when every episode
    # finished, the continuation path measurably beat the session-blind
    # baseline, the affinity/prefix path actually engaged, and the
    # executor sweep shed under load WITHOUT starving a single job.
    "agentic_rollout": (
        "episodes", "failed_episodes", "episodes_per_s",
        "turn_ttft_p50_ms", "baseline_turn_ttft_p50_ms",
        "tool_calls", "tool_failures", "tool_call_ms_p50",
        "reprefill_tokens", "full_prefill_tokens", "reprefill_ratio",
        "affinity_prefix_hits",
        "exec_jobs_total", "exec_warm_hits", "exec_workers_alive",
        "sat_peak_jobs_per_s", "sat_failed", "sat_shed_total",
    ),
    # The disaggregation A/B is only evidence as a PAIR: a record
    # carrying one arm's tail latency without the other cannot show the
    # interference delta the phase exists to measure.
    "serving_disagg": (
        "offered_rate_rps",
        "unified_itl_p99_ms",
        "unified_ttft_p99_ms",
        "disagg_itl_p99_ms",
        "disagg_ttft_p99_ms",
        "kv_handoffs",
        "kv_handoff_bytes",
    ),
}

# Phases whose records may carry a p99-TTFT SLO stamp; key = the value
# field holding the headline p99 the stamp judges.
SLO_HEADLINE_KEYS = {
    "serving_openloop": "headline_ttft_p99_ms",
    "serving_disagg": "disagg_ttft_p99_ms",
}


def _validate_ttft_slo(name: str, val: Dict) -> List[str]:
    """A record carrying an SLO limit must stamp itself honestly: p99
    over the limit without ttft_slo_violated=true is exactly the silent
    headline-eligibility the satellite forbids."""
    slo = val.get("ttft_slo_ms")
    if not isinstance(slo, (int, float)) or isinstance(slo, bool):
        return []
    headline_key = SLO_HEADLINE_KEYS.get(name)
    p99 = val.get(headline_key) if headline_key else None
    problems: List[str] = []
    if not isinstance(p99, (int, float)) or isinstance(p99, bool):
        problems.append(
            f"{name}: carries ttft_slo_ms but no numeric "
            f"{headline_key!r} to judge it against"
        )
        return problems
    violated = bool(val.get("ttft_slo_violated"))
    if p99 > float(slo) and not violated:
        problems.append(
            f"{name}: p99 TTFT {p99:.0f}ms exceeds the {slo:.0f}ms SLO "
            f"but the record is not stamped ttft_slo_violated — "
            f"refusing silent headline eligibility"
        )
    if p99 <= float(slo) and violated:
        problems.append(
            f"{name}: stamped ttft_slo_violated but p99 {p99:.0f}ms is "
            f"within the {slo:.0f}ms SLO"
        )
    return problems

# Numeric keys every serving_openloop arrival-rate sweep point must
# carry: a record without the sweep (or with points missing p99 TTFT)
# is not tail-latency evidence.
OPENLOOP_POINT_KEYS = (
    "offered_rps", "goodput_rps", "p50_ttft_ms", "p99_ttft_ms",
)


def _validate_openloop_sweep(val: Dict) -> List[str]:
    problems: List[str] = []
    sweep = val.get("sweep")
    if not isinstance(sweep, list) or len(sweep) < 2:
        return [
            "serving_openloop: measure value must carry an arrival-rate "
            "'sweep' list with >= 2 points"
        ]
    for i, pt in enumerate(sweep):
        if not isinstance(pt, dict):
            problems.append(f"serving_openloop: sweep[{i}] is not an object")
            continue
        for k in OPENLOOP_POINT_KEYS:
            if not isinstance(pt.get(k), (int, float)) or isinstance(
                pt.get(k), bool
            ):
                problems.append(
                    f"serving_openloop: sweep[{i}] missing numeric {k!r}"
                )
        off, good = pt.get("offered_rps"), pt.get("goodput_rps")
        if (
            isinstance(off, (int, float))
            and isinstance(good, (int, float))
            and good > off * 1.001
        ):
            # Physically impossible: completions can't outrun arrivals.
            problems.append(
                f"serving_openloop: sweep[{i}] goodput {good:.2f} rps "
                f"exceeds offered load {off:.2f} rps"
            )
    return problems


def _num(val: Dict, key: str):
    v = val.get(key)
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) else None


def _validate_sharded_plane(val: Dict) -> List[str]:
    """The sharded-plane phase exists to show ingress SHRINKING: with
    the TP degree (each server fetches only its slice) and again with
    the quantized wire. A record where it doesn't — or whose quantized
    stream failed the dequant-parity check, or whose same-shard replica
    leaned on the origin — is refused, not published."""
    problems: List[str] = []
    tp1, tp2 = _num(val, "tp1_ingress_frac"), _num(val, "tp2_ingress_frac")
    tpq = _num(val, "tp2_int8_ingress_frac")
    if tp1 is not None and tp2 is not None and tp2 >= tp1 * 0.75:
        problems.append(
            f"weight_plane_sharded: per-server ingress does not shrink "
            f"with TP degree (tp1 {tp1:.3f} -> tp2 {tp2:.3f})"
        )
    if tp2 is not None and tpq is not None and tpq >= tp2 * 0.75:
        problems.append(
            f"weight_plane_sharded: quantized wire does not shrink "
            f"ingress (tp2 {tp2:.3f} -> int8 {tpq:.3f})"
        )
    if _num(val, "dequant_parity_ok") != 1:
        problems.append(
            "weight_plane_sharded: quantized-wire record failed (or "
            "lacks) the dequant-parity check"
        )
    rep = _num(val, "replica_bytes_from_origin")
    if rep is not None and rep > 0:
        problems.append(
            f"weight_plane_sharded: same-shard replica pulled "
            f"{rep:.0f} bytes from the origin — peer serving degraded"
        )
    if (
        _num(val, "decode_parity_checked") == 1
        and _num(val, "decode_parity_ok") != 1
    ):
        problems.append(
            "weight_plane_sharded: sharded-cutover greedy decode "
            "diverged from the unsharded baseline"
        )
    return problems


def _validate_train_sharded(val: Dict) -> List[str]:
    """The sharded-training phase exists to show the mesh paths
    MATCHING the single-device trajectory and the shard-local dump
    actually shrinking the host high-water while round-tripping
    byte-identically — a record failing any of those is refused."""
    problems: List[str] = []
    for k in ("fsdp2_parity_ok", "tp2_parity_ok"):
        if _num(val, k) is not None and _num(val, k) != 1:
            problems.append(
                f"train_sharded: {k.split('_')[0]} loss trajectory "
                f"diverged from the single-device engine"
            )
    if _num(val, "dump_roundtrip_ok") != 1:
        problems.append(
            "train_sharded: shard-local dump did not round-trip "
            "byte-identically through the weight plane"
        )
    frac = _num(val, "dump_highwater_frac")
    if frac is not None and not (0.0 < frac <= 0.75):
        problems.append(
            f"train_sharded: dump host high-water frac {frac:.3f} does "
            f"not show the ~1/mesh_size reduction (expected <= 0.75 on "
            f"a 2-device mesh)"
        )
    return problems


def _validate_sessions_resident(val: Dict) -> List[str]:
    """The tiered-KV phase exists to show a returning session's TTFT
    measurably below the full re-prefill baseline once residency
    exceeds HBM, with the tier actually engaged (spills happened, host
    restores happened, nothing truly lost) and the int8 spill wire at
    least halving tier bytes. Records not showing that are refused."""
    problems: List[str] = []
    tier = _num(val, "tier_ttft_p99_ms")
    base = _num(val, "baseline_ttft_p99_ms")
    if tier is not None and base is not None and tier > 0.75 * base:
        problems.append(
            f"sessions_resident: tier-hit returning p99 TTFT "
            f"{tier:.0f}ms is not measurably below the full-re-prefill "
            f"baseline {base:.0f}ms"
        )
    lost = _num(val, "kv_prefix_lost")
    if lost is None or lost > 0:
        problems.append(
            f"sessions_resident: {lost} true prefix losses under "
            f"pressure — spill-not-loss is the phase's contract"
        )
    if (_num(val, "kv_spill_total") or 0) < 1:
        problems.append(
            "sessions_resident: no spills recorded — residency never "
            "exceeded the HBM budget, nothing was measured"
        )
    if (_num(val, "hit_rate_host") or 0) <= 0:
        problems.append(
            "sessions_resident: zero host-tier restores — the tier "
            "never engaged"
        )
    if (_num(val, "hit_rate_peer") or 0) <= 0:
        problems.append(
            "sessions_resident: zero peer pulls — the global prefix "
            "index path never engaged"
        )
    for k in ("hit_rate_hbm", "hit_rate_host", "hit_rate_disk",
              "hit_rate_peer", "miss_rate"):
        v = _num(val, k)
        if v is not None and not (0.0 <= v <= 1.0):
            problems.append(f"sessions_resident: {k} {v} outside [0, 1]")
    ratio = _num(val, "int8_spill_bytes_ratio")
    if ratio is not None and not (0.1 <= ratio <= 0.62):
        problems.append(
            f"sessions_resident: int8 spill wire is {ratio:.2f}x the "
            f"float wire — expected <= 0.62 (halved or better) and a "
            f"sane floor"
        )
    sweep = val.get("sweep")
    if not isinstance(sweep, list) or len(sweep) < 2:
        problems.append(
            "sessions_resident: measure value must carry a residency "
            "'sweep' list with >= 2 points"
        )
    else:
        for i, pt in enumerate(sweep):
            if not isinstance(pt, dict):
                problems.append(
                    f"sessions_resident: sweep[{i}] is not an object"
                )
                continue
            for k in ("n_resident", "ttft_p99_ms", "hit_rate"):
                if not isinstance(pt.get(k), (int, float)) or isinstance(
                    pt.get(k), bool
                ):
                    problems.append(
                        f"sessions_resident: sweep[{i}] missing "
                        f"numeric {k!r}"
                    )
    return problems


def _validate_fleet_elastic(val: Dict) -> List[str]:
    """The elastic control plane's contract: joins bootstrap from
    peers (the 'peer' arm must read ZERO origin bytes — a fallback to
    origin broadcast is the regression the phase exists to catch), the
    manager killover costs zero rollouts, and a drain migrates every
    live prefix instead of losing it."""
    problems: List[str] = []
    failed = _num(val, "failed_rollouts")
    if failed is None or failed > 0:
        problems.append(
            f"fleet_elastic: {failed} failed rollout(s) — the elastic "
            f"control plane's contract is zero across join, killover, "
            f"and drain"
        )
    if val.get("join_peer_source") != "peer":
        problems.append(
            f"fleet_elastic: peer-arm join source is "
            f"{val.get('join_peer_source')!r}, not 'peer' — the join "
            f"fell back to the origin broadcast"
        )
    if (_num(val, "join_peer_origin_bytes") or 0) > 0:
        problems.append(
            "fleet_elastic: the 'peer' join read bytes from the origin "
            "— origin egress is no longer O(1) under joins"
        )
    if (_num(val, "join_peer_peer_bytes") or 0) <= 0:
        problems.append(
            "fleet_elastic: the peer join transferred zero peer bytes "
            "— the bootstrap path never engaged"
        )
    for k in ("drain_lost", "kv_prefix_lost"):
        v = _num(val, k)
        if v is None or v > 0:
            problems.append(
                f"fleet_elastic: {k} = {v} — drained prefixes must "
                f"migrate, never be lost"
            )
    if (_num(val, "drain_migrated") or 0) < 1:
        problems.append(
            "fleet_elastic: zero migrated prefixes — the drain path "
            "never exercised the KV wire"
        )
    if (_num(val, "killover_epoch") or 0) < 2:
        problems.append(
            "fleet_elastic: killover epoch < 2 — no successor manager "
            "ever took the lease"
        )
    if (_num(val, "n_servers_max") or 0) < 3:
        problems.append(
            "fleet_elastic: fleet never grew past its launch size — "
            "no runtime join was measured"
        )
    # The autoscale arm's growth must be AUTOSCALER-driven: the
    # WatermarkAutoscaler issues the launch through its attached
    # launcher. Growth the launcher cannot account for means the
    # harness grew the fleet and the record proves nothing about the
    # control loop.
    if (_num(val, "autoscale_out_actions") or 0) < 1:
        problems.append(
            "fleet_elastic: the autoscaler never issued a scale-out — "
            "the watermark control loop was not exercised"
        )
    if (_num(val, "autoscale_launched") or 0) < 1:
        problems.append(
            "fleet_elastic: the autoscaler's launcher launched nothing "
            "— any growth was harness-driven"
        )
    n_before = _num(val, "autoscale_n_before") or 1
    n_after = _num(val, "autoscale_n_after") or 0
    if n_after <= n_before:
        problems.append(
            f"fleet_elastic: autoscale pool never grew "
            f"({n_before:.0f} -> {n_after:.0f})"
        )
    if n_after - n_before > (_num(val, "autoscale_launched") or 0):
        problems.append(
            "fleet_elastic: autoscale pool grew beyond what the "
            "launcher launched — harness-driven growth is not "
            "autoscaler evidence"
        )
    auto_failed = _num(val, "autoscale_load_failed")
    if auto_failed is None or auto_failed > 0:
        problems.append(
            f"fleet_elastic: {auto_failed} failed request(s) under the "
            f"autoscale arm's pressure load — scale-out must be "
            f"loss-free"
        )
    return problems


def _validate_multi_model_serving(val: Dict) -> List[str]:
    """The multi-model serving plane's contract (ISSUE 20): pools are
    ISOLATED (parity per pool vs single-model baselines, zero
    cross-model routes or KV hits, unknown models refused) and weight
    lifecycles are INDEPENDENT (one family cuts over while the other's
    version, outputs, and tail latency hold, loss-free)."""
    problems: List[str] = []
    if (_num(val, "families_distinct") or 0) != 1:
        problems.append(
            "multi_model_serving: the two families share a config hash "
            "— contamination would be token-invisible"
        )
    for k, what in (
        ("parity_mismatches",
         "pool outputs diverged from the single-model baseline"),
        ("cross_model_routes",
         "a request routed outside its model's pool"),
        ("cross_model_kv_hits",
         "a KV source crossed a model boundary"),
        ("unknown_model_routed",
         "an unregistered model was routed instead of refused"),
    ):
        v = _num(val, k)
        if v is None or v > 0:
            problems.append(f"multi_model_serving: {k} = {v} — {what}")
    if (_num(val, "unknown_model_rejected") or 0) < 1:
        problems.append(
            "multi_model_serving: the unknown-model refusal was never "
            "observed — the negative arm did not run"
        )
    before = _num(val, "cutover_version_before") or 0
    if (_num(val, "cutover_version_after") or 0) <= before:
        problems.append(
            "multi_model_serving: the cutover family's version never "
            "advanced — no independent cutover was measured"
        )
    if (_num(val, "steady_version_after") or 0) != before:
        problems.append(
            f"multi_model_serving: steady_version_after = "
            f"{val.get('steady_version_after')} — the OTHER family's "
            f"cutover moved the steady pool's version"
        )
    if (_num(val, "steady_outputs_stable") or 0) != 1:
        problems.append(
            "multi_model_serving: the steady family's greedy outputs "
            "changed across the other family's cutover — cross-model "
            "weight contamination"
        )
    if (_num(val, "cutover_outputs_changed") or 0) != 1:
        problems.append(
            "multi_model_serving: the cutover family's outputs did not "
            "change at v2 — the 'cutover' never actually swapped "
            "weights"
        )
    b_failed = _num(val, "b_failed")
    if b_failed is None or b_failed > 0:
        problems.append(
            f"multi_model_serving: {b_failed} failed steady-family "
            f"request(s) during the cutover — the independent-"
            f"lifecycle claim requires zero"
        )
    if (_num(val, "b_completed") or 0) < 1:
        problems.append(
            "multi_model_serving: zero steady-family completions "
            "during the cutover window — nothing was measured"
        )
    b_base = _num(val, "b_p99_ttft_base_ms") or 0.0
    b_cut = _num(val, "b_p99_ttft_cutover_ms")
    if b_cut is None or b_cut > 5.0 * b_base + 500.0:
        problems.append(
            f"multi_model_serving: steady-family p99 TTFT went "
            f"{b_base:.0f}ms -> {b_cut}ms across the cutover — the "
            f"other family's fanout stalled this pool"
        )
    lost = _num(val, "kv_prefix_lost")
    if lost is None or lost > 0:
        problems.append(
            f"multi_model_serving: kv_prefix_lost = {lost} — the "
            f"cutover must never cost a prefix"
        )
    return problems


def _validate_agentic_rollout(val: Dict) -> List[str]:
    """The agentic-rollout contract (ISSUE 18 acceptance): episodes are
    loss-free, the session-continuation path measurably beats full
    re-prefill (ratio strictly below 1 AND prefix affinity actually
    engaged — a good ratio with zero prefix hits means the accounting
    lied), tool calls all landed, and the executor sweep proves
    BACKPRESSURE (sheds happened, nothing starved)."""
    problems: List[str] = []
    failed = _num(val, "failed_episodes")
    if failed is None or failed > 0:
        problems.append(
            f"agentic_rollout: {failed} failed episode(s) — multi-turn "
            f"rollout evidence must be loss-free"
        )
    ratio = _num(val, "reprefill_ratio")
    if ratio is None or ratio >= 1.0:
        problems.append(
            f"agentic_rollout: re-prefill ratio {ratio} not below 1.0 "
            f"— continuation turns paid the session-blind full "
            f"re-prefill, the path never engaged"
        )
    if (_num(val, "reprefill_tokens") or 0) <= 0:
        problems.append(
            "agentic_rollout: zero re-prefill tokens — either no "
            "continuation turn ran or the client accounting is dead"
        )
    if (_num(val, "affinity_prefix_hits") or 0) < 1:
        problems.append(
            "agentic_rollout: zero prefix-cache hits during the "
            "continuation arm — sticky-qid affinity never engaged, so "
            "the delta re-prefills hit servers without the parked KV"
        )
    if (_num(val, "tool_failures") or 0) > 0:
        problems.append(
            f"agentic_rollout: {val.get('tool_failures')} failed tool "
            f"call(s) — the pooled executor starved mid-episode"
        )
    if (_num(val, "exec_warm_hits") or 0) < 1:
        problems.append(
            "agentic_rollout: zero warm-worker hits — every job paid a "
            "cold spawn, the pool's whole point"
        )
    if (_num(val, "exec_workers_alive") or 0) < 1:
        problems.append(
            "agentic_rollout: no executor worker alive at the end of "
            "the episode arms"
        )
    if (_num(val, "sat_shed_total") or 0) < 1:
        problems.append(
            "agentic_rollout: saturation sweep never shed — the "
            "bounded queue's 429 backpressure was not exercised"
        )
    if (_num(val, "sat_failed") or 0) > 0:
        problems.append(
            f"agentic_rollout: {val.get('sat_failed')} job(s) failed "
            f"in the saturation sweep — sheds must back clients off, "
            f"never starve them"
        )
    return problems


def _validate_tenant_fairness(val: Dict) -> List[str]:
    """The tenant gateway's fairness contract (ISSUE 19 acceptance):
    under the aggressor flood, weighted fair share must hold the
    victim's p99 TTFT below the FIFO arm's, the aggressor must be shed
    against its OWN limits (a flood that never saturated proves
    nothing), the DRR queue must have actually arbitrated, and not one
    victim request may fail — fairness by starving no one."""
    problems: List[str] = []
    fair = _num(val, "fair_p99_ttft_ms")
    unfair = _num(val, "unfair_p99_ttft_ms")
    if fair is None or unfair is None or fair >= unfair:
        problems.append(
            f"tenant_fairness: fair-share victim p99 {fair}ms is not "
            f"below the FIFO arm's {unfair}ms — the weighted queue "
            f"bought the victim nothing"
        )
    if (_num(val, "solo_p99_ttft_ms") or 0) <= 0:
        problems.append(
            "tenant_fairness: no solo baseline p99 — the record cannot "
            "anchor the flood arms to an idle-fleet floor"
        )
    if (_num(val, "aggressor_sheds") or 0) < 1:
        problems.append(
            "tenant_fairness: zero aggressor sheds — the flood never "
            "exceeded its stream cap, so the arms measured an idle "
            "gateway"
        )
    if (_num(val, "fairshare_picks") or 0) < 1:
        problems.append(
            "tenant_fairness: zero DRR picks in the fair arm — "
            "admitted requests never contended in the gateway queue, "
            "so fair share was never exercised"
        )
    victim_failed = _num(val, "victim_failed")
    if victim_failed is None or victim_failed > 0:
        problems.append(
            f"tenant_fairness: {victim_failed} failed victim "
            f"request(s) — fair share must protect the victim, not "
            f"starve it"
        )
    return problems


def _validate_rpc_resilience(val: Dict) -> List[str]:
    """The hedging contract (ISSUE 14 acceptance): under the injected
    delay tail, the hedged arm's p99 must be MEASURABLY lower than the
    unhedged arm's — sitting below the injected tail, which the
    unhedged arm must actually have eaten (otherwise the A/B measured
    nothing) — and the win/cancel accounting must prove hedges ran,
    won, and cancelled their losers instead of double-counting."""
    problems: List[str] = []
    injected = _num(val, "injected_delay_ms") or 0.0
    unhedged = _num(val, "unhedged_p99_ms")
    hedged = _num(val, "hedged_p99_ms")
    if injected <= 0:
        problems.append(
            "rpc_resilience: no injected delay — the A/B has no tail "
            "to escape"
        )
    if unhedged is None or unhedged < injected:
        problems.append(
            f"rpc_resilience: unhedged p99 {unhedged} ms below the "
            f"injected {injected} ms tail — the slow peer never "
            f"landed, so the hedged number proves nothing"
        )
    if hedged is None or unhedged is None or hedged >= unhedged:
        problems.append(
            f"rpc_resilience: hedged p99 {hedged} ms not below "
            f"unhedged {unhedged} ms — hedging bought nothing"
        )
    if hedged is not None and injected > 0 and hedged >= injected:
        problems.append(
            f"rpc_resilience: hedged p99 {hedged} ms still at/above "
            f"the injected {injected} ms tail — the hedge never "
            f"escaped the slow holder"
        )
    if (_num(val, "hedge_wins") or 0) < 1:
        problems.append(
            "rpc_resilience: zero hedge wins — a low hedged p99 "
            "without wins just means the tail never landed on the "
            "hedged arm"
        )
    if (_num(val, "hedge_cancelled") or 0) < 1:
        problems.append(
            "rpc_resilience: zero cancelled losers — every win must "
            "abandon its loser or bytes get double-counted"
        )
    if (_num(val, "hedge_failures") or 0) > 0:
        problems.append(
            f"rpc_resilience: {val.get('hedge_failures')} hedged pull "
            f"failure(s) — both holders serve the same verified bytes, "
            f"a failure means the substrate dropped a request"
        )
    return problems


def _validate_recovery_slo(val: Dict) -> List[str]:
    """The durable-plane contract (ISSUE 16 acceptance): the recovery
    path must have a measured MTTR, the exactly-once ledger must show
    ZERO lost and ZERO duplicated samples even though redelivery and
    WAL replay were actually exercised, and the async checkpoint arm's
    caller stall must be measurably below the sync arm's — otherwise
    the background writer bought nothing."""
    problems: List[str] = []
    mttr = _num(val, "mttr_ms")
    if mttr is None or mttr <= 0:
        problems.append(
            f"recovery_slo: mttr_ms = {mttr} — no measured recovery "
            f"path, the SLO record is empty"
        )
    for k in ("samples_lost", "samples_duplicated"):
        v = _num(val, k)
        if v is None or v > 0:
            problems.append(
                f"recovery_slo: {k} = {v} — exactly-once means zero, "
                f"a durable plane that loses or double-trains samples "
                f"is broken regardless of its timings"
            )
    if (_num(val, "wal_replayed") or 0) < 1:
        problems.append(
            "recovery_slo: zero WAL records replayed — the MTTR number "
            "never exercised the journal"
        )
    if (_num(val, "redelivered") or 0) < 1:
        problems.append(
            "recovery_slo: zero redeliveries — the exactly-once "
            "counters were never put under stress"
        )
    sync_ms = _num(val, "sync_stall_ms_mean")
    async_ms = _num(val, "async_stall_ms_mean")
    if sync_ms is None or async_ms is None or async_ms >= sync_ms:
        problems.append(
            f"recovery_slo: async stall {async_ms} ms not below sync "
            f"stall {sync_ms} ms — the background writer bought nothing"
        )
    return problems


def _validate_moe_scaling(val: Dict) -> List[str]:
    """The MoE fast-path contract: EP and no-drop-capacity loss
    trajectories must MATCH dropless-EP1 (parity-missing records are
    refused by the key schema), a 'dropless' arm that realized drops is
    a broken dispatcher, and the expert-sliced stream must actually
    shrink per-rank ingress toward 1/EP."""
    problems: List[str] = []
    for k, arm in (("ep_parity_ok", "dropless-EP2"),
                   ("capacity_parity_ok", "no-drop capacity")):
        if _num(val, k) != 1:
            problems.append(
                f"moe_scaling: {arm} loss trajectory diverged from "
                f"dropless-EP1 (or parity missing) — refusing"
            )
    for k in ("dropless_drop_rate", "ep2_drop_rate"):
        dr = _num(val, k)
        if dr is not None and dr > 0:
            problems.append(
                f"moe_scaling: {k} = {dr:.4f} — a dropless dispatch "
                f"that drops tokens is a broken dispatcher"
            )
    ep = _num(val, "ep_degree")
    frac = _num(val, "ep_ingress_frac_max")
    if ep and frac is not None and frac > 1.0 / ep + 0.25:
        problems.append(
            f"moe_scaling: per-rank ingress frac {frac:.3f} does not "
            f"shrink toward 1/{ep:.0f} — the expert-sliced stream is "
            f"not engaged"
        )
    sweep = val.get("capacity_sweep")
    if not isinstance(sweep, list) or not sweep:
        problems.append(
            "moe_scaling: measure value must carry a non-empty "
            "'capacity_sweep'"
        )
    else:
        prev = None
        for i, pt in enumerate(sweep):
            cf = pt.get("capacity_factor") if isinstance(pt, dict) else None
            dr = pt.get("drop_rate") if isinstance(pt, dict) else None
            if not isinstance(cf, (int, float)) or not isinstance(
                dr, (int, float)
            ):
                problems.append(
                    f"moe_scaling: capacity_sweep[{i}] missing numeric "
                    f"capacity_factor/drop_rate"
                )
                continue
            if prev is not None and (cf <= prev[0] or dr > prev[1] + 1e-9):
                problems.append(
                    f"moe_scaling: capacity_sweep[{i}] drop rate must "
                    f"be non-increasing in capacity_factor"
                )
            prev = (cf, dr)
    return problems


def validate_phase_value(name: str, rec: Dict) -> List[str]:
    """Schema problems for one banked record's value dict (measure/ok
    records of phases with a declared schema only)."""
    keys = PHASE_VALUE_KEYS.get(name)
    if not keys or rec.get("status") != "ok" or rec.get("pass") != "measure":
        return []
    problems = []
    val = rec.get("value") or {}
    for k in keys:
        if not isinstance(val.get(k), (int, float)) or isinstance(
            val.get(k), bool
        ):
            problems.append(f"{name}: measure value missing numeric {k!r}")
    ofp = val.get("origin_full_payloads")
    if isinstance(ofp, (int, float)) and ofp > 1.05:
        # The plane's whole point: each byte leaves the origin once.
        problems.append(
            f"{name}: origin served {ofp:.2f} full payloads — peer "
            f"fanout silently degraded to an origin broadcast"
        )
    if name == "train_sharded":
        problems.extend(_validate_train_sharded(val))
    if name == "moe_scaling":
        problems.extend(_validate_moe_scaling(val))
    if name == "weight_plane_sharded":
        problems.extend(_validate_sharded_plane(val))
    if name == "serving_openloop":
        problems.extend(_validate_openloop_sweep(val))
    if name == "sessions_resident":
        problems.extend(_validate_sessions_resident(val))
    if name == "fleet_elastic":
        problems.extend(_validate_fleet_elastic(val))
    if name == "multi_model_serving":
        problems.extend(_validate_multi_model_serving(val))
    if name == "rpc_resilience":
        problems.extend(_validate_rpc_resilience(val))
    if name == "tenant_fairness":
        problems.extend(_validate_tenant_fairness(val))
    if name == "agentic_rollout":
        problems.extend(_validate_agentic_rollout(val))
    if name == "recovery_slo":
        problems.extend(_validate_recovery_slo(val))
    if name == "serving_disagg":
        failed = val.get("disagg_failed")
        if isinstance(failed, (int, float)) and failed > 0:
            problems.append(
                f"{name}: {failed:.0f} failed request(s) in the "
                f"disaggregated arm — handoff evidence must be loss-free"
            )
    problems.extend(_validate_ttft_slo(name, rec.get("value") or {}))
    return problems


def validate_report(rep: Dict, require_driver: bool = False) -> List[str]:
    problems: List[str] = []
    if rep.get("schema") != bank.REPORT_SCHEMA:
        problems.append(
            f"report schema != {bank.REPORT_SCHEMA!r}: {rep.get('schema')!r}"
        )
        return problems

    # Keyed per section: a phase's compile record must never shadow (or
    # be shadowed by) its measure record — the driver_verified backing
    # check below must see the MEASURE evidence, nothing else.
    measures = {}
    for section in ("phases", "compiled", "proxy"):
        for name, rec in (rep.get(section) or {}).items():
            if name == "multichip_dryrun":
                if rec.get("driver_verified") is not False:
                    problems.append(
                        "multichip_dryrun passthrough must be labeled "
                        "driver_verified: false"
                    )
                continue
            try:
                bank.validate_record(rec)
            except ValueError as e:
                problems.append(f"{section}/{name}: {e}")
                continue
            problems.extend(
                f"{section}/{p}" for p in validate_phase_value(name, rec)
            )
            if section == "phases":
                measures[name] = rec
            if section == "proxy" and rec["attestation"].get("driver_verified"):
                problems.append(
                    f"proxy/{name}: proxy evidence cannot be driver_verified"
                )

    # Report-level SLO gating consistency: a record stamped
    # ttft_slo_violated must surface in the report's slo_violations —
    # the stamp exists so a breach is never silently headline-eligible.
    stamped = set()
    for section in ("phases", "proxy"):
        for name, rec in (rep.get(section) or {}).items():
            if ((rec or {}).get("value") or {}).get("ttft_slo_violated"):
                stamped.add(name)
    surfaced = set(rep.get("slo_violations") or {})
    for name in sorted(stamped - surfaced):
        problems.append(
            f"{name}: record is stamped ttft_slo_violated but the "
            f"report's slo_violations does not surface it"
        )

    headline = rep.get("headline") or {}
    any_unverified_headline = False
    for key, entry in headline.items():
        dv = entry.get("driver_verified")
        if not isinstance(dv, bool):
            problems.append(f"headline/{key}: missing driver_verified bool")
            continue
        if not dv:
            any_unverified_headline = True
            if entry.get("evidence") != "proxy":
                problems.append(
                    f"headline/{key}: number lacks driver_verified: true and "
                    f"is not labeled evidence: proxy — refusing to conflate"
                )
        if require_driver and not dv:
            problems.append(
                f"headline/{key}: --require-driver-verified set but the "
                f"number is not driver-verified"
            )

    if rep.get("driver_verified") and any_unverified_headline:
        problems.append(
            "report claims driver_verified: true but carries non-verified "
            "headline numbers"
        )
    if rep.get("driver_verified"):
        tr = measures.get("train_tflops")
        if tr is None or not tr["attestation"].get("driver_verified"):
            problems.append(
                "report claims driver_verified: true but the train_tflops "
                "record does not back it"
            )
    return problems


def validate_bank_dir(path: str) -> List[str]:
    problems: List[str] = []
    seen = 0
    try:
        names = sorted(os.listdir(path))
    except OSError as e:
        return [f"cannot read bank dir {path!r}: {e}"]
    for name in names:
        if not name.endswith(".json"):
            continue
        seen += 1
        full = os.path.join(path, name)
        try:
            with open(full) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{name}: unreadable ({e})")
            continue
        try:
            bank.validate_record(rec)
        except ValueError as e:
            problems.append(f"{name}: {e}")
            continue
        problems.extend(
            f"{name}: {p}"
            for p in validate_phase_value(str(rec.get("phase")), rec)
        )
    if seen == 0:
        problems.append(f"bank dir {path!r} holds no records")
    return problems


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("report", nargs="?", default=None,
                        help="report JSON to validate")
    parser.add_argument("--bank", default=None,
                        help="validate every record in a bank directory")
    parser.add_argument("--require-driver-verified", action="store_true")
    args = parser.parse_args(argv)
    if (args.report is None) == (args.bank is None):
        parser.error("pass exactly one of a report path or --bank")

    if args.bank:
        problems = validate_bank_dir(args.bank)
        target = args.bank
    else:
        try:
            with open(args.report) as f:
                rep = json.load(f)
        except (OSError, ValueError) as e:
            print(f"INVALID {args.report}: unreadable ({e})", file=sys.stderr)
            return 1
        problems = validate_report(
            rep, require_driver=args.require_driver_verified
        )
        target = args.report

    if problems:
        for p in problems:
            print(f"INVALID {target}: {p}", file=sys.stderr)
        return 1
    print(f"OK {target}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
