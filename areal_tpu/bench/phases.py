"""Phase registry: what the bench can measure, what each phase costs.

A phase is the unit of banking. Each one declares:

- ``priority``       lower runs first
- ``est_compile_s``  estimated on-chip cost of the *compile pass*:
                     trace + XLA-compile every program the phase needs,
                     populating the persistent compilation cache. Banked
                     as a ``compile`` record — a later window never
                     re-pays it.
- ``est_measure_s``  estimated on-chip cost of the *measure pass*
                     (warm re-compile from cache + timed steady state)
- ``min_window_s``   the smallest window in which the measure pass can
                     still produce a steady-state number worth banking
- ``headline``       this phase backs a top-level report number and so
                     must be driver-verified to count as evidence
- ``proxy``          CPU/virtual-mesh evidence by construction; the
                     runner pins its subprocess to JAX_PLATFORMS=cpu
                     and the report labels it non-driver-verified
- ``entrypoint``     ``"module:function"``; the function takes the pass
                     name (``"compile"`` | ``"measure"``) and returns
                     the record's value dict

Phase bodies live in :mod:`areal_tpu.bench.workloads`; tests register
their own cheap phases (``AREAL_BENCH_PHASE_MODULES`` makes the runner
subprocess import them too).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, List, Optional

from areal_tpu.base import env_registry

# How far a phase may overrun its estimate before the runner kills it.
DEADLINE_FACTOR = 3.0
MIN_DEADLINE_S = 120.0


@dataclasses.dataclass(frozen=True)
class PhaseSpec:
    name: str
    entrypoint: str
    priority: int = 100
    est_compile_s: float = 60.0
    est_measure_s: float = 60.0
    min_window_s: float = 30.0
    headline: bool = False
    proxy: bool = False
    # Included in a bare `python bench.py` run (non-default phases run
    # only when asked for by name or picked up by the daemon).
    default: bool = True
    # Extra env for the runner subprocess (applied before env_extra;
    # XLA_FLAGS values APPEND to the inherited flags so e.g. a phase
    # can request a fake multi-device CPU mesh without clobbering the
    # host's settings).
    env: Optional[Dict[str, str]] = None
    description: str = ""

    def resolve(self) -> Callable[[str], Dict]:
        mod, _, fn = self.entrypoint.partition(":")
        return getattr(importlib.import_module(mod), fn)

    def cost(self, pass_: str) -> float:
        return self.est_compile_s if pass_ == "compile" else self.est_measure_s

    def deadline_s(self, pass_: str) -> float:
        env = env_registry.get_float("AREAL_BENCH_PHASE_DEADLINE_S")
        if env is not None:
            return env
        return max(self.cost(pass_) * DEADLINE_FACTOR, MIN_DEADLINE_S)


_REGISTRY: Dict[str, PhaseSpec] = {}


def register(spec: PhaseSpec) -> PhaseSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"phase {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> PhaseSpec:
    load_extra_modules()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown phase {name!r}; known: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]


def all_phases() -> List[PhaseSpec]:
    """Every registered phase, priority order (ties by name)."""
    load_extra_modules()
    return sorted(_REGISTRY.values(), key=lambda s: (s.priority, s.name))


def default_phases() -> List[PhaseSpec]:
    return [s for s in all_phases() if s.default]


_EXTRA_LOADED: Optional[str] = None


def load_extra_modules(spec: Optional[str] = None) -> None:
    """Import extra phase modules (comma-separated module names from
    AREAL_BENCH_PHASE_MODULES). The runner child calls this too, so a
    phase registered by a test exists in the subprocess that executes
    it."""
    global _EXTRA_LOADED
    if spec is None:
        spec = env_registry.get_str("AREAL_BENCH_PHASE_MODULES")
    if spec == _EXTRA_LOADED:
        return
    _EXTRA_LOADED = spec
    for mod in filter(None, (m.strip() for m in spec.split(","))):
        importlib.import_module(mod)


# ----------------------------------------------------------------------
# Built-in phases. The compile/measure estimates are planning hints
# from early rounds, not measurements of the present machine.
# ----------------------------------------------------------------------

register(PhaseSpec(
    name="serving_openloop",
    entrypoint="areal_tpu.bench.workloads:serving_openloop_phase",
    priority=4,
    est_compile_s=90.0,
    est_measure_s=180.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Open-loop (Poisson) fleet serving against REAL server "
                "processes behind the manager: arrival-rate sweep -> "
                "p50/p99 TTFT + goodput, server-side 429 admission vs "
                "no-backpressure A/B at deliberate overload "
                "(scheduling-policy evidence; CPU-proxy)",
))

register(PhaseSpec(
    name="serving_disagg",
    entrypoint="areal_tpu.bench.workloads:serving_disagg_phase",
    priority=5,
    est_compile_s=90.0,
    est_measure_s=180.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Disaggregated prefill/decode A/B: unified vs 1P+1D "
                "real-process fleets under a mixed long-prefill/"
                "short-decode open-loop load -> decode ITL p99 + TTFT "
                "p99 for both arms + KV-handoff counters (CPU-proxy)",
))

register(PhaseSpec(
    name="sessions_resident",
    entrypoint="areal_tpu.bench.workloads:sessions_resident_phase",
    priority=6,
    est_compile_s=90.0,
    est_measure_s=240.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Tiered-KV plane: resident-session sweep past the HBM "
                "prefix budget on real server processes — returning-"
                "session TTFT with the host tier vs the full-re-prefill "
                "baseline, hit rate by tier (hbm/host/peer/miss), zero "
                "true prefix loss under pressure, and the int8-vs-float "
                "spill-wire byte ratio (CPU-proxy)",
))

register(PhaseSpec(
    name="fleet_elastic",
    entrypoint="areal_tpu.bench.workloads:fleet_elastic_phase",
    priority=7,
    est_compile_s=90.0,
    est_measure_s=300.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Elastic fleet control plane: one real-process fleet "
                "lives through runtime join (peer-bootstrap vs origin "
                "A/B on join-to-first-routed-token + origin bytes), a "
                "manager SIGKILL + lease-takeover successor, and a "
                "drain-then-leave KV migration — under sustained "
                "PartialRolloutManager load with zero failed rollouts "
                "(CPU-proxy)",
))

register(PhaseSpec(
    name="multi_model_serving",
    entrypoint="areal_tpu.bench.workloads:multi_model_serving_phase",
    priority=7,
    est_compile_s=90.0,
    est_measure_s=300.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Multi-model serving plane: two model families on one "
                "real-process fleet behind a multi-model manager — "
                "per-model routing with greedy parity vs single-model "
                "baseline fleets (zero cross-model contamination), "
                "unknown-model refusal, cross-model KV isolation, and "
                "an independent weight cutover of one family under the "
                "other family's sustained load (p99 TTFT holds, zero "
                "failures, zero prefix loss) (CPU-proxy)",
))

register(PhaseSpec(
    name="tenant_fairness",
    entrypoint="areal_tpu.bench.workloads:tenant_fairness_phase",
    priority=7,
    est_compile_s=90.0,
    est_measure_s=240.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Tenant gateway fairness A/B: a real gateway subprocess "
                "in front of a real-process fleet, noisy-aggressor flood "
                "vs an interactive victim — victim p99 TTFT (admission-"
                "to-first-token) solo vs fair-share ON vs FIFO, with the "
                "aggressor shed against its own stream cap and the DRR "
                "queue demonstrably engaged (CPU-proxy)",
))

register(PhaseSpec(
    name="rpc_resilience",
    entrypoint="areal_tpu.bench.workloads:rpc_resilience_phase",
    priority=12,
    est_compile_s=0.0,  # host + loopback HTTP only: no compile pass
    est_measure_s=30.0,
    min_window_s=0.0,
    proxy=True,
    description="RPC substrate tail-latency A/B: hedged vs unhedged "
                "hash-verified chunk pulls from two loopback holders "
                "under the injected-delay chaos action — hedged p99 "
                "must sit near the hedge delay, unhedged near the "
                "injected tail, with win/cancel accounting "
                "(host-side; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="recovery_slo",
    entrypoint="areal_tpu.bench.workloads:recovery_slo_phase",
    priority=12,
    est_compile_s=0.0,  # host + loopback ZMQ only: no compile pass
    est_measure_s=30.0,
    min_window_s=0.0,
    proxy=True,
    description="Durable-training-plane SLOs: async-vs-sync checkpoint "
                "stall A/B on synthetic engine state, cold-recovery "
                "MTTR (manifest + state + WAL replay against the "
                "checkpointed ledger cut), and exactly-once accounting "
                "under a forced redelivery storm — lost and duplicated "
                "must both be zero (host-side; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="weight_update",
    entrypoint="areal_tpu.bench.workloads:weight_update_phase",
    priority=12,
    est_compile_s=0.0,  # host + loopback HTTP only: no compile pass
    est_measure_s=30.0,
    min_window_s=0.0,
    proxy=True,
    description="Weight-distribution plane: origin + 3-holder peer "
                "fanout over loopback HTTP — weight_update_ms with the "
                "transfer/cutover split and the O(1)-origin-egress "
                "invariant (host-side; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="weight_plane_sharded",
    entrypoint="areal_tpu.bench.workloads:weight_plane_sharded_phase",
    priority=13,
    est_compile_s=0.0,  # host + loopback HTTP + tiny CPU-mesh engines
    est_measure_s=180.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    description="Shard-aware + quantized weight plane: per-server "
                "ingress bytes/version vs TP degree (1 vs 2) and wire "
                "dtype (raw vs int8) over a live origin, same-shard "
                "peer replica at zero origin cost, O(1)-origin "
                "invariant, dequant-parity, and greedy-decode parity "
                "of a 2-way-TP engine cut over from sliced shard "
                "streams (byte accounting is exact and "
                "machine-independent; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="train_sharded",
    entrypoint="areal_tpu.bench.workloads:train_sharded_phase",
    priority=14,
    est_compile_s=0.0,  # tiny CPU-mesh programs; the measure pass pays
    est_measure_s=120.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    description="Sharded training end-to-end on a 2-fake-device mesh: "
                "loss-trajectory parity single-device vs FSDP2 vs TP2, "
                "per-mesh step-time breakdown, and the shard-local "
                "trainer dump's host high-water reduction with a "
                "byte-identical round trip through the weight-plane "
                "origin (parity + byte accounting are exact and "
                "machine-independent; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="moe_scaling",
    entrypoint="areal_tpu.bench.workloads:moe_scaling_phase",
    priority=15,
    est_compile_s=0.0,  # tiny CPU-mesh programs; the measure pass pays
    est_measure_s=150.0,
    min_window_s=0.0,
    proxy=True,
    # Default: the daemon banks the MoE evidence unattended; CPU rounds
    # self-label proxy, on-chip rounds make the step times meaningful.
    env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    description="Expert-parallel MoE fast path: dense vs MoE per-token "
                "step time at matched active FLOPs, dropless EP1 vs EP2 "
                "loss-trajectory parity + step times, capacity-vs-"
                "dropless dispatch A/B with a capacity-factor drop-rate "
                "sweep, and the expert-sliced weight stream's ~1/EP "
                "per-rank ingress over a live origin (parity, drop "
                "rates, and byte accounting are exact and machine-"
                "independent; CPU-proxy evidence)",
))

register(PhaseSpec(
    name="agentic_rollout",
    entrypoint="areal_tpu.bench.workloads:agentic_rollout_phase",
    priority=16,
    est_compile_s=90.0,
    est_measure_s=240.0,
    min_window_s=0.0,
    proxy=True,
    default=False,
    description="Multi-turn tool-use rollouts over real server "
                "processes + the pooled reward executor: session-"
                "continuation vs session-blind A/B (re-prefill ratio + "
                "per-turn TTFT), real sandboxed tool-call latency, zero "
                "failed episodes, and an executor saturation sweep that "
                "must shed (429 backpressure) without starving any job "
                "(CPU-proxy)",
))

