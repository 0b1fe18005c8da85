"""Single registry of every named chaos-injection point.

``base/fault_injection.py`` gives production code free no-op points
(``faults.maybe_fail("gserver.drain")``) that chaos tests arm by BARE
STRING — in-process (``faults.arm``) or across process boundaries via
the ``AREAL_FAULTS`` env spec. That name was never checked anywhere:
rename an injection point and every chaos test that armed it becomes a
silent no-op that still passes — the worst kind of rot, a fault-
tolerance suite that tests nothing.

Every point is declared ONCE here (name, modules, sync/async, what
failure it simulates); the ``chaos-registry`` checker in
``areal_tpu/lint`` flags ``maybe_fail``/``maybe_fail_async`` calls and
``arm``/``hits`` references naming undeclared points, ``AREAL_FAULTS``
spec strings naming unknown points, non-literal point names, and dead
registry entries no production site fires.

Names under ``test.`` are reserved for the injector's own unit suite
(synthetic points that exercise the arming machinery, not a production
contract) and are exempt from declaration.

``docs/fault_points.md`` is GENERATED from this registry
(``python scripts/areal_lint.py --emit-fault-docs
docs/fault_points.md``) and drift-gated in tier-1.

This module must stay stdlib-only: it is imported by the no-jax lint
gate.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

# Reserved namespace for fault_injection's own unit tests.
TEST_PREFIX = "test."


@dataclasses.dataclass(frozen=True)
class FaultPoint:
    name: str
    modules: Tuple[str, ...]  # repo-rel modules with maybe_fail sites
    kind: str  # "sync" | "async" | "both"
    doc: str  # the real-world failure this point simulates


def _p(name: str, modules: Tuple[str, ...], kind: str,
       doc: str) -> FaultPoint:
    return FaultPoint(name=name, modules=modules, kind=kind, doc=doc)


_GS = ("areal_tpu/system/generation_server.py",)

_POINTS: List[FaultPoint] = [
    _p("engine.kv_spill", ("areal_tpu/engine/serving.py",), "sync",
       "KV tier spill write fails (host allocation/disk error) — the "
       "eviction must fall back to a clean free, counted as "
       "kv_prefix_lost, never a wedge."),
    _p("gserver.generate", _GS, "async",
       "Generation request dies or stalls server-side (engine crash, "
       "wedged decode lap)."),
    _p("gserver.kv_export", _GS, "async",
       "Prefill side dies mid KV handoff export."),
    _p("gserver.kv_restore", _GS, "async",
       "Tier restore fails mid delta-prefill — session must fall "
       "back to full re-prefill, spill-not-loss."),
    _p("gserver.kv_import", _GS, "async",
       "Decode side dies mid KV handoff import (the disagg e2e kills "
       "a prefill server mid-handoff through this)."),
    _p("gserver.drain", _GS, "async",
       "Drain-then-leave dies at the start of the drain (server "
       "killed right as it begins quiescing)."),
    _p("gserver.kv_accept", _GS, "async",
       "Migration target fails while accepting a parked prefix from "
       "a draining peer."),
    _p("gserver.update_weights", _GS, "async",
       "Weight load from the shared dump dies mid-update."),
    _p("gserver.distribute_weights", _GS, "async",
       "Plane fanout transfer dies on this server (mid-fetch peer "
       "kill in the weight-plane e2e)."),
    _p("gserver.weight_fetch", _GS, "sync",
       "One chunk fetch inside the plane transfer fails (transient "
       "peer error; the stream must retry/re-source)."),
    _p("gserver.cutover_weights", _GS, "async",
       "Cutover window dies between interrupt and swap."),
    _p("weight_plane.serve_chunk",
       ("areal_tpu/system/weight_plane.py",
        "areal_tpu/system/generation_server.py"), "async",
       "A serving peer/origin fails mid-chunk (the bench kills a "
       "mid-transfer peer via serve_chunk=raise:k=40:n=3)."),
    _p("weight_plane.chunk_bytes",
       ("areal_tpu/system/weight_plane.py",), "sync",
       "Weight chunk payload corrupted on the wire AFTER its hash was "
       "stamped (bit-rot, torn proxy) — the puller's sha256 verify "
       "must reject and re-fetch; corrupt weights never cut over. "
       "Fires for every /weights/chunk byte path (origin, peer "
       "holders, gserver peer hop) via chunk_response."),
    _p("gserver.kv_chunk_bytes", _GS, "async",
       "KV chunk/blob payload corrupted after its chunk index was "
       "minted (tier chunk, handoff blob) — the puller's per-chunk "
       "sha256 verify must reject and re-fetch, never scatter corrupt "
       "KV into the paged pool."),
    _p("worker.poll",
       ("areal_tpu/system/worker_base.py",), "both",
       "A worker's poll loop dies or hangs — THE generic worker "
       "kill: the elastic e2e SIGKILLs the manager via "
       "worker.poll@gserver_manager=die."),
    _p("rollout.episode",
       ("areal_tpu/system/rollout_worker.py",), "sync",
       "One rollout episode dies mid-flight (agent/env crash)."),
    _p("master.step",
       ("areal_tpu/system/master_worker.py",), "sync",
       "The master dies mid training step (controller-restart "
       "recovery path)."),
    _p("manager.plane_fanout",
       ("areal_tpu/system/gserver_manager.py",), "sync",
       "The manager dies inside the weight-plane fanout push."),
    _p("manager.fanout",
       ("areal_tpu/system/gserver_manager.py",), "async",
       "The manager dies inside the update-weights fanout wave."),
    _p("bench.runner.phase",
       ("areal_tpu/bench/runner.py",), "sync",
       "A bench phase subprocess dies or wedges (the runner banks "
       "the failure; a re-run resumes from the bank)."),
    _p("train.checkpoint",
       ("areal_tpu/engine/checkpoint.py",), "sync",
       "The trainer dies at the engine-checkpoint commit point, after "
       "artifacts landed but around the manifest rename — recovery "
       "must resume from the previous complete checkpoint, never a "
       "torn one."),
    _p("buffer.wal_append",
       ("areal_tpu/system/wal.py",), "sync",
       "The trainer dies inside a rollout-WAL append (possibly leaving "
       "a torn final record) — replay must drop the torn tail and the "
       "unacked sample must be redelivered by the pusher."),
    _p("buffer.consume",
       ("areal_tpu/system/buffer.py",), "sync",
       "The trainer dies handing a batch to training, after buffer "
       "admission but before the consumed-seq watermark persists — "
       "the ledger must re-admit exactly once on resume."),
    _p("rexec.case",
       ("areal_tpu/system/reward_executor.py",), "sync",
       "One sandboxed reward job fails inside a warm executor worker "
       "(guarded exec raises / worker OOM-kill) — the case must come "
       "back as a failed result, never take the pool or the caller "
       "down."),
    _p("rexec.die",
       ("areal_tpu/system/reward_executor.py",), "sync",
       "A whole reward-executor service dies mid-flight (container "
       "kill) — its heartbeat goes stale and clients must fail over "
       "to a surviving executor with zero failed episodes."),
    _p("manager.model_registry",
       ("areal_tpu/system/gserver_manager.py",), "sync",
       "The model-registry read flakes during the manager's "
       "multi-model refresh — the accepted-model set must stay at "
       "its last good value (live pools keep routing, unregistered "
       "joiners stay quarantined), never a poll-thread crash or a "
       "mass quarantine of registered models."),
    _p("gw.auth",
       ("areal_tpu/system/gateway.py",), "sync",
       "The gateway's API-key lookup dies mid-auth (key store "
       "flake) — the request must come back as a clean 401-class "
       "refusal the client can retry, never a hung stream or a "
       "half-admitted tenant slot."),
    _p("gw.shed",
       ("areal_tpu/system/gateway.py",), "sync",
       "The gateway dies inside the admission/shed decision (right "
       "as a 429 is being minted) — the tenant's bucket charge must "
       "not leak and the usage ledger must not double-count the shed "
       "after restart replay."),
]

REGISTRY: Dict[str, FaultPoint] = {p.name: p for p in _POINTS}
assert len(REGISTRY) == len(_POINTS), "duplicate fault-point declaration"


def render_docs() -> str:
    """Markdown for docs/fault_points.md — generated, drift-gated;
    never hand-edit the output file."""
    lines = [
        "# Chaos injection points",
        "",
        "<!-- GENERATED FILE — do not edit. Source of truth: "
        "areal_tpu/base/fault_points.py. Regenerate with: "
        "python scripts/areal_lint.py --emit-fault-docs "
        "docs/fault_points.md -->",
        "",
        "Every named `faults.maybe_fail(...)` injection point "
        "(base/fault_injection.py), generated from the registry the "
        "`chaos-registry` lint checker enforces. Arm one in-process "
        "with `faults.arm(point, action, ...)` or across process "
        "boundaries with the `AREAL_FAULTS` env spec "
        "(`<point>[@scope]=<action>[:k=N][:n=N][:delay=S]`). Names "
        "under `test.` are reserved for the injector's own unit "
        "suite.",
        "",
        "| Point | Kind | Module(s) | Simulates |",
        "|---|---|---|---|",
    ]
    for p in sorted(_POINTS, key=lambda p: p.name):
        mods = ", ".join(f"`{m}`" for m in p.modules)
        doc = p.doc.replace("|", "\\|")
        lines.append(f"| `{p.name}` | {p.kind} | {mods} | {doc} |")
    lines.append("")
    return "\n".join(lines)
