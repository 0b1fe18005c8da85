"""Generation-server manager: router + staleness controller + weight updater.

Counterpart of the reference's GserverManager
(realhf/system/gserver_manager.py:32-496). Singleton worker that:

- routes generation requests across servers (/schedule_request) with
  round_robin / least_requests / least_token_usage policies
- gates new rollouts by capacity and staleness (/allocate_rollout):
  a rollout may start only if (expected model version when it trains) -
  (current weight version) <= max_head_offpolicyness
- watches the trainer's published model version and fans out weight
  updates (interrupting running requests) to servers — either the
  legacy /update_weights_from_disk broadcast (every server re-reads the
  checkpoint from NFS) or, with ``weight_plane`` enabled, a peer-fanout
  tree over the streaming distribution plane (system/weight_plane.py):
  the origin uploads each byte once, holders serve chunks to siblings,
  and the serve-interrupting cutover is dispatched (and measured)
  separately from the overlapped transfer
- GCs old param-realloc dumps

Fault-domain isolation: servers are tracked through the health registry
(base/health.py) and a healthy/evicted split. Unhealthy servers — dead
heartbeats, client-reported request failures, or failed weight updates —
are evicted from every routing policy; the weight-update fanout is
quorum-based (>= 1 healthy server suffices, so one dead server degrades
throughput instead of aborting the step); an evicted server whose
heartbeat returns is first re-synced to the current weight version and
only then readmitted to rotation, so `is_staled` accounting stays
correct across the outage.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import os
import shutil
import threading
import time
from typing import Dict, List, Optional, Tuple

import aiohttp
from aiohttp import web

from areal_tpu.api.system_api import GserverManagerConfig
from areal_tpu.base import constants, env_registry, health, logging, name_resolve, names, network, rpc, tracing
from areal_tpu.base import metrics_registry as mreg
from areal_tpu.base.fault_injection import faults
from areal_tpu.system.worker_base import PollResult, Worker

logger = logging.getLogger("gserver_manager")


class RolloutStat:
    def __init__(self):
        self.submitted = 0
        self.running = 0
        self.accepted = 0

    def as_dict(self):
        return dict(
            submitted=self.submitted, running=self.running, accepted=self.accepted
        )


class GserverManager(Worker):
    @property
    def breakers(self) -> rpc.BreakerBoard:
        """Per-peer circuit breakers (base/rpc.py): fed by the
        manager's OWN calls (metrics poll, fanout/cutover posts) and by
        client-reported request failures. An OPEN breaker makes the
        peer unroutable exactly like an active shed window — never
        evicted for it (eviction stays the health registry's call) —
        so a flapping server stops eating every caller's budget
        between heartbeat-driven evictions. Surfaced on /status.
        Lazily built so harness-built partial managers (tests construct
        via ``__new__``) get a board without running _configure."""
        b = self.__dict__.get("_breaker_board")
        if b is None:
            b = rpc.BreakerBoard()
            self.__dict__["_breaker_board"] = b
        return b

    @property
    def gateway_registry(self) -> Optional[health.HealthRegistry]:
        """Health-registry view over tenant-gateway heartbeats
        (system/gateway.py): each gateway's heartbeat payload carries
        its per-tenant usage brief, which /status folds into
        ``gateway_tenants`` rows — no extra wire route needed. Lazily
        built like ``breakers``; returns None for harness-built
        partial managers with no trial identity."""
        r = self.__dict__.get("_gateway_registry")
        if r is None:
            try:
                r = health.HealthRegistry(
                    self.cfg.experiment_name, self.cfg.trial_name,
                    prefix="gateway",
                )
            except Exception:
                return None
            self.__dict__["_gateway_registry"] = r
        return r

    def gateway_tenants(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant usage rows summed across live gateways. Blocking
        (name_resolve reads) — call via run_in_executor from async."""
        reg = self.gateway_registry
        if reg is None:
            return {}
        try:
            snap = reg.snapshot()
        except Exception:
            return {}
        out: Dict[str, Dict[str, int]] = {}
        for rec in snap.values():
            for tenant, row in (rec.get("tenants") or {}).items():
                agg = out.setdefault(tenant, {
                    "requests": 0, "sheds": 0,
                    "prompt_tokens": 0, "completion_tokens": 0,
                })
                for k in agg:
                    agg[k] += int(row.get(k, 0) or 0)
        return out

    def _configure(self, config: GserverManagerConfig):
        from areal_tpu.system import fleet_controller

        self.cfg = config
        constants.set_experiment_trial_names(
            config.experiment_name, config.trial_name
        )
        # Health registry first: both the first-boot wait and the HA
        # takeover's membership rebuild read it.
        self._registry = health.HealthRegistry(
            config.experiment_name, config.trial_name,
            prefix="generation_server",
        )
        # Manager HA (system/fleet_controller.py): the lease is the ONLY
        # state a manager persists — epoch (generation fence) + weight
        # version. A record from a previous incarnation means this is a
        # restart/standby takeover: membership, roles, shards, and shed
        # totals are rebuilt from heartbeats + /metrics below; the
        # affinity map is best-effort lost (the global prefix index
        # re-feeds from the next /kv/index poll).
        self._lease = (
            fleet_controller.ManagerLease(
                config.experiment_name, config.trial_name
            )
            if config.elastic_fleet else None
        )
        prior = self._lease.read() if self._lease is not None else None
        rebuilt = None
        if prior is not None:
            # wait_expired can return None (the record vanished while
            # we parked — trial teardown, cleared subtree): proceed as
            # a takeover with nothing to inherit rather than crash.
            prior = self._lease.wait_expired(
                timeout=1e9 if config.standby else 300.0
            )
            snap = self._registry.snapshot()
            # Concurrent /metrics sweep with a short timeout: takeover
            # often happens exactly when some members died with the
            # predecessor, and N sequential 5s timeouts would turn the
            # "manager death costs seconds" path into N*5s.
            from concurrent.futures import ThreadPoolExecutor

            m_urls = sorted(
                {r["url"] for r in snap.values() if r.get("url")}
            )
            with ThreadPoolExecutor(max_workers=8) as ex:
                metrics = dict(zip(m_urls, ex.map(
                    lambda u: fleet_controller.fetch_metrics(
                        u, timeout=2.0
                    ),
                    m_urls,
                )))
            rebuilt = fleet_controller.rebuild_fleet_state(snap, metrics)
            urls = rebuilt.urls
            logger.info(
                f"manager takeover: lease epoch "
                f"{prior.epoch if prior else 0} expired; rebuilt "
                f"{len(urls)} member(s) from heartbeats (weight_version="
                f"{prior.weight_version if prior else 0})"
            )
        else:
            # First boot: wait for the launch-time fleet to register.
            key = names.gen_servers(config.experiment_name, config.trial_name)
            deadline = time.monotonic() + 300
            while True:
                urls = name_resolve.get_subtree(key)
                if len(urls) >= config.n_servers:
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"only {len(urls)}/{config.n_servers} "
                        f"generation servers up"
                    )
                time.sleep(0.2)
        self.server_urls: List[str] = sorted(urls)
        self._rr = 0
        self._server_reqs = {u: 0 for u in self.server_urls}  # in-flight est.
        self._server_tokens = {u: 0.0 for u in self.server_urls}
        self.weight_version = 0
        self.last_weight_sync_s = 0.0
        self.rollout_stat = RolloutStat()
        self._lock = threading.Lock()
        self._last_metrics_poll = 0.0
        # Training-samples counter snapshot, refreshed on the worker
        # poll thread (_poll): the staleness gate reads THIS, never
        # name_resolve directly — that read is file I/O (NFS in
        # production) and is_staled() runs inside /allocate_rollout on
        # the HTTP event loop, under _lock (areal-lint blocking-async).
        self._training_samples_cache = 0
        self._server_gen_totals = {u: 0.0 for u in self.server_urls}
        self._server_prefix_hits = {u: 0.0 for u in self.server_urls}
        self._server_prefix_reused = {u: 0.0 for u in self.server_urls}
        # Per-server request counts for the fleet hit-rate denominator
        # (ratio of SUMS, like spec_tokens_per_step: averaging per-server
        # hit rates would overweight idle servers).
        self._server_gen_reqs = {u: 0.0 for u in self.server_urls}
        # Fleet speculation yield as a ratio of SUMS: per-server emitted
        # tokens and active decode steps, not per-server ratios (an
        # unweighted mean of ratios overweights idle servers).
        self._server_spec_emitted = {u: 0.0 for u in self.server_urls}
        self._server_spec_steps = {u: 0.0 for u in self.server_urls}
        # Prefix-/session-affinity routing + load-shed awareness:
        # qid -> url LRU (a session's next chunk/turn goes to the server
        # holding its KV prefix); servers that shed a client with 429
        # are routed around until their Retry-After elapses (deliberate
        # backpressure, never eviction); tokens scheduled since the last
        # /metrics poll fold into least_token_usage so a burst between
        # polls doesn't pile onto one server.
        self._affinity: "collections.OrderedDict[str, str]" = (
            collections.OrderedDict()
        )
        # Global prefix index (tiered KV plane, docs/serving.md):
        # qid -> {url, tier, n_tokens, version}, LRU-bounded, fed from
        # each server's /kv/index on the metrics poll. Affinity is the
        # FAST PATH (route the session back to its holder); the index
        # is what makes it only that — a session routed anywhere else
        # gets a ``kv_source`` hint and the target pulls the prefix
        # over /kv/{manifest,chunk} instead of re-prefilling.
        idx_size = config.kv_index_size
        if idx_size is None:
            idx_size = env_registry.get_int("AREAL_KV_INDEX_SIZE")
        self._kv_index_size = int(idx_size or 0)
        self._prefix_index: "collections.OrderedDict[str, Dict]" = (
            collections.OrderedDict()
        )
        # url -> qids last advertised by that server (for pruning
        # entries the holder no longer has, and evictee migration).
        self._server_kv_index: Dict[str, set] = {}
        # Disaggregated prefill/decode pools: live role per server
        # (reported via heartbeat payload + /metrics, updated directly
        # when the elastic sizer re-roles), elastic eligibility
        # (configured role "unified"), and the poll-fed load signals the
        # pool routing keys on — queued prompt tokens for the prefill
        # pool, free KV pages for the decode pool.
        self._server_roles: Dict[str, str] = {
            u: "unified" for u in self.server_urls
        }
        # Shard-aware weight plane: url -> (tp_rank, tp_degree) from the
        # heartbeat payload (None = unsharded). Fanout trees are planned
        # per shard group — only same-shard peers hold the same stream.
        self._server_shards: Dict[str, Optional[Tuple[int, int]]] = {}
        # Multi-model serving plane (system/model_registry.py): which
        # registered family each server hosts (heartbeat-learned;
        # launch-time servers default to the manager's model_name), the
        # registered-id set adoption checks heartbeats against, the
        # registry records (pool-policy floors/ceilings for the
        # model-scoped autoscaler), per-model weight versions
        # (weight_version stays the DEFAULT model's — the training
        # plane's staleness gate keys off it), and the quarantine
        # ledger for beats naming an unregistered model_id.
        self._server_models: Dict[str, str] = {
            u: config.model_name for u in self.server_urls
        }
        self._model_set: set = {config.model_name}
        self._model_records: Dict = {}
        self._model_versions: Dict[str, int] = {}
        self._new_model: str = config.model_name
        self._quarantined: Dict[str, str] = {}
        self._autoscalers: Dict[str, object] = {}
        if getattr(config, "multi_model", False):
            self._refresh_model_set()
        self._server_elastic: Dict[str, bool] = {}
        self._server_queued_toks = {u: 0.0 for u in self.server_urls}
        self._server_free_pages: Dict[str, float] = {}
        self._server_total_pages: Dict[str, float] = {}
        self._server_kv: Dict[str, Dict[str, float]] = {}
        # Elastic sizer bookkeeping: what we flipped (url -> the role it
        # held before OUR flip, for the flip-back path) + an audit log.
        self._rerole_orig: Dict[str, str] = {}
        self._rerole_log: List[Dict] = []
        self._last_rerole = 0.0
        self._server_shed_until = {u: 0.0 for u in self.server_urls}
        self._server_tokens_pending = {u: 0.0 for u in self.server_urls}
        self._server_shed_total = {u: 0.0 for u in self.server_urls}
        # Raw TTFT/ITL bucket counts per server (base/latency.py edges):
        # fleet percentiles come from SUMMED buckets, the histogram
        # analogue of the ratio-of-sums rule above.
        self._server_ttft_hist: Dict[str, List[int]] = {}
        self._server_itl_hist: Dict[str, List[int]] = {}
        self._last_gen_total = 0.0
        self._last_throughput_log = time.monotonic()
        self._throughput_log_interval = 10.0

        # Fault-domain state. Servers start healthy; the health registry
        # (+ client failure reports + fanout failures) evicts, heartbeat
        # return + weight re-sync readmits. A server that never
        # heartbeats (legacy topologies, harness-built tests) is simply
        # never evicted by the registry path.
        self._healthy = set(self.server_urls)
        self._evicted: Dict[str, str] = {}  # url -> reason
        self._server_versions = {u: 0 for u in self.server_urls}
        self._member_urls: Dict[str, str] = {}  # health member -> url

        # Elastic fleet control plane (system/fleet_controller.py,
        # docs/fault_tolerance.md): draining servers keep serving
        # in-flight work and KV pulls but take no new routing; joiners
        # start evicted ("joining") until their peer weight bootstrap
        # lands; the autoscaler turns the re-role sizer's watermarks
        # into launch/drain actions through an attached launcher.
        self._draining: set = set()
        self._drain_deadline: Dict[str, float] = {}
        self._join_t0: Dict[str, float] = {}
        self._join_info: Dict[str, Dict] = {}
        self._join_log: List[Dict] = []
        self._drain_log: List[Dict] = []
        self._scale_log: List[Dict] = []
        # Launch markers the autoscaler is still waiting on:
        # {"t": monotonic, "model": model_id or None}. Model-scoped so a
        # multi-model fleet counts pending capacity per pool.
        self._pending_launches: List[Dict] = []
        self._launched_indices: set = set()
        self._known_indices: set = set()
        self._launcher = None
        self._autoscaler = (
            fleet_controller.WatermarkAutoscaler(
                fleet_controller.AutoscalePolicy(
                    scale_out_queued_tokens=config.scale_out_queued_tokens,
                    scale_in_queued_tokens=config.scale_in_queued_tokens,
                    scale_free_page_min_frac=config.scale_free_page_min_frac,
                    pool_min_servers=config.pool_min_servers,
                    pool_max_servers=config.pool_max_servers,
                    cooldown_s=config.scale_cooldown_s,
                    sustain_polls=config.scale_sustain_polls,
                )
            )
            if config.autoscale else None
        )

        if rebuilt is not None:
            # Apply the takeover rebuild: heartbeat payloads are
            # authoritative for identity, /metrics for live surfaces.
            self._member_urls = dict(rebuilt.member_urls)
            self._server_roles.update(rebuilt.roles)
            # Per-model pools must survive the takeover too: a successor
            # that forgot which model each url hosts could make its
            # first routing decisions across model boundaries.
            for _u, _mid in rebuilt.model_ids.items():
                if _mid:
                    self._server_models[_u] = _mid
            self._server_shards.update(rebuilt.shards)
            self._server_elastic.update(rebuilt.elastic)
            self._server_shed_total.update(rebuilt.shed_totals)
            self._server_versions.update(rebuilt.versions)
            self._draining = set(rebuilt.draining)
            # Inherited drains restart their timeout clock here: the
            # predecessor's deadlines died with it, and a drain with
            # no deadline could wedge in limbo forever.
            self._drain_deadline = {
                u: time.monotonic() + config.drain_timeout_s
                for u in self._draining
            }
            self._known_indices = set(rebuilt.server_indices.values())
            # Corroborate the inherited version before trusting it: a
            # re-run reusing experiment/trial names on a dirty
            # name_resolve root would otherwise inherit a DEAD run's
            # lease and suppress every fanout of the new run
            # (check_new_params ignores v <= weight_version). In a
            # genuine restart the trainer's published model_version is
            # always >= the lease version (the manager only ever
            # learned it from that key), so this never lowers a
            # legitimate inheritance.
            inherited = prior.weight_version if prior else 0
            try:
                published = int(name_resolve.get(names.model_version(
                    config.experiment_name, config.trial_name,
                    config.model_name,
                )))
            except (name_resolve.NameEntryNotFoundError, ValueError):
                published = 0
            fleet_max = max(
                [int(v) for v in rebuilt.versions.values()], default=0
            )
            if inherited > max(published, fleet_max):
                logger.warning(
                    f"manager takeover: lease weight_version "
                    f"{inherited} corroborated by neither the "
                    f"published model_version ({published}) nor any "
                    f"live server ({fleet_max}) — stale lease from a "
                    f"previous run? inheriting "
                    f"{max(published, fleet_max)} instead"
                )
                inherited = max(published, fleet_max)
            self.weight_version = max(inherited, fleet_max)
            # Servers behind the inherited version start evicted; the
            # normal readmission path re-syncs them (peer bootstrap
            # under the weight plane) before they route again.
            for u in self.server_urls:
                if rebuilt.versions.get(u, 0) < self.weight_version:
                    self._healthy.discard(u)
                    self._evicted[u] = "version behind at takeover"
        # Rollout-worker quota reconciliation: outstanding slots per
        # worker, reclaimed when that worker's heartbeat dies — a killed
        # worker's episodes can never call /finish_rollout, and without
        # reclamation the capacity gate would wedge shut forever.
        self._worker_slots: Dict[str, int] = {}
        self._rollout_registry = health.HealthRegistry(
            config.experiment_name, config.trial_name,
            prefix="rollout_worker",
        )
        self._rollout_seen: set = set()
        self._last_health_poll = 0.0

        # Weight-distribution plane: manager-hosted origin fallbacks
        # (only started when weight_plane is on and no trainer-side
        # source is registered), one per model — each model's checkpoint
        # tree gets its own chunk stream so two models publish versions
        # without touching each other's pools — + the last fanout's
        # per-server stats for /status.
        self._own_sources: Dict[str, object] = {}
        self._wp_last: Dict = {}

        self._http_loop = asyncio.new_event_loop()
        # Prime the staleness-gate snapshot BEFORE the HTTP server can
        # field /allocate_rollout: a restarted manager starts with
        # rollout_stat.submitted == 0, so without this read it would
        # admit over-stale rollouts until the first poll lap refreshes
        # the cache (the durable KV counter is the only restart-
        # surviving input to is_staled).
        self._refresh_training_samples()
        self._http_ready = threading.Event()
        self._http_thread = threading.Thread(target=self._serve_http, daemon=True)
        self._http_thread.start()
        if not self._http_ready.wait(30):
            raise RuntimeError("gserver manager HTTP failed to start")
        if self._lease is not None:
            # Fence the generation BEFORE advertising the address: a
            # zombie predecessor that wakes up sees the higher epoch on
            # its next renew and stands down instead of dueling us.
            self._lease.take(
                self.address, self.weight_version, prior=prior
            )
        name_resolve.add(
            names.gen_server_manager(config.experiment_name, config.trial_name),
            self.address,
            keepalive_ttl=60,
            replace=True,
        )
        logger.info(
            f"gserver manager at {self.address} "
            f"(epoch {self._lease.epoch if self._lease else 0}), "
            f"servers={self.server_urls}"
        )

    def _heartbeat_ttl(self) -> float:
        # The fanout blocks this worker's poll loop (no beats) for up to
        # flush_request_timeout; the lease must outlive a healthy fanout
        # or the controller would hang-kill the manager mid-update.
        return max(health.default_ttl(), self.cfg.flush_request_timeout / 2)

    def _await_fut(self, fut, timeout_s: float):
        """Block on a cross-loop future while keeping BOTH leases fresh
        — the worker heartbeat AND the HA lease. A bootstrap or fanout
        can legally block for minutes (flush_request_timeout); without
        renewals in that window a warm standby would see the lease
        expire and fence a LIVE manager mid-operation (and the
        supervisor would hang-kill it). Stand-down on supersession
        stays in _poll — this only keeps a healthy manager's claim
        alive."""
        import concurrent.futures as _cf

        deadline = time.monotonic() + timeout_s
        while True:
            try:
                return fut.result(
                    timeout=min(
                        5.0, max(0.1, deadline - time.monotonic())
                    )
                )
            except _cf.TimeoutError:
                self._beat()
                if self._lease is not None:
                    self._lease.renew(self.weight_version)
                if time.monotonic() > deadline:
                    raise

    # ------------------------------------------------------------------
    # Scheduling / staleness
    # ------------------------------------------------------------------

    def _healthy_urls(self, model: Optional[str] = None) -> List[str]:
        """Routable servers: healthy AND not draining. A draining
        server finishes in-flight work and serves KV pulls, but takes
        no new routing, no weight fanouts, no re-roles. With ``model``
        set, only that model's pool — routing, fanout, drain migration
        and the autoscaler all pass it in a multi-model fleet, so a
        model_id mismatch is a routing error, never a silent
        cross-model KV or weight hit."""
        urls = [
            u for u in self.server_urls
            if u in self._healthy and u not in self._draining
        ]
        if model is not None:
            urls = [u for u in urls if self._model_of(u) == model]
        return urls

    def _model_of(self, url: str) -> str:
        """Which registered family ``url`` hosts (heartbeat-learned;
        defaults to the manager's own model_name for legacy servers
        that never declared one). getattr default: harness-built
        instances predating the multi-model plane lack the map."""
        return getattr(self, "_server_models", {}).get(
            url, self.cfg.model_name
        )

    def _model_version(self, model: str) -> int:
        """Current weight version of one model's pool. The default
        model reads the legacy scalar (the training plane's staleness
        gate and lease fencing key off it)."""
        if model == self.cfg.model_name:
            return self.weight_version
        return getattr(self, "_model_versions", {}).get(model, 0)

    def _set_model_version(self, model: str, version: int) -> None:
        """Record a completed cutover (call under _lock)."""
        self._model_versions[model] = int(version)
        if model == self.cfg.model_name:
            self.weight_version = int(version)

    def _target_version(self, url: str) -> int:
        """The version a (re)joining server must reach before it
        routes: its OWN model's current version, not the default
        model's — resyncing a model-B server to model A's version
        would be a cross-model weight hit."""
        return self._model_version(self._model_of(url))

    def _model_watch_list(self) -> List[str]:
        """Models whose published weight versions this manager watches
        (check_new_params). Single-model fleets watch only their own
        model_name — byte-identical legacy behavior."""
        if not getattr(self.cfg, "multi_model", False):
            return [self.cfg.model_name]
        return sorted(self._model_set)

    def _refresh_model_set(self):
        """Configure-time / poll-thread only (file I/O): fold the
        registry's ids into the accepted-model set. Ids are only ever
        ADDED — a registry record disappearing must not orphan a live
        pool mid-flight."""
        from areal_tpu.system import model_registry

        try:
            faults.maybe_fail("manager.model_registry")
            records = model_registry.list_models(
                self.cfg.experiment_name, self.cfg.trial_name
            )
        except Exception:
            # A registry-store flake keeps the last good model set:
            # live pools keep routing, unknown joiners stay
            # quarantined — never a poll crash or a mass quarantine.
            return
        for rec in records.values():
            self._model_set.add(rec.model_id)
            self._model_records[rec.model_id] = rec

    def _live_urls(self) -> List[str]:
        """Healthy servers INCLUDING draining ones — the metrics /
        kv-index poll set (a draining server still reports its drain
        progress and advertises prefixes peers may pull)."""
        return [u for u in self.server_urls if u in self._healthy]

    def _load_key(self, u: str) -> Tuple[int, float]:
        """Least-loaded order: in-flight request estimate first, then
        token usage with the since-last-poll in-flight estimate folded
        in (a burst between polls must not pile onto one server)."""
        return (
            self._server_reqs.get(u, 0),
            self._server_tokens.get(u, 0.0)
            + self._server_tokens_pending.get(u, 0.0),
        )

    def _role(self, u: str) -> str:
        return self._server_roles.get(u, "unified")

    def _disagg_split(self, candidates: List[str]) -> bool:
        """True when the healthy fleet holds at least one dedicated
        prefill or decode server — pool routing engages only then; an
        all-unified fleet keeps the PR 6 single-pool behavior."""
        return any(self._role(u) != "unified" for u in candidates)

    def _index_holder(self, qid: str,
                      candidates: List[str]) -> Optional[str]:
        """Healthy holder of qid's prefix per the global index (call
        under self._lock). None when indexing is off or nobody holds."""
        if not qid or not self._kv_index_size:
            return None
        ent = self._prefix_index.get(qid)
        if ent is None:
            return None
        url = ent.get("url")
        return url if url in candidates else None

    def _choose_server(
        self, meta: Dict
    ) -> Tuple[Optional[str], str, Optional[str], Optional[str]]:
        """Pick a healthy server; returns (url, policy, decode_url,
        kv_source) where policy names the routing decision (recorded in
        the request trace): 'affinity' (session's prefix-holding server,
        from the affinity map), 'kv-index' (same, recovered from the
        global prefix index after the affinity map forgot), 'spill'
        (holder saturated/shedding -> least-loaded, with kv_source
        pointing back at the holder so the target PULLS the prefix),
        'sticky' (legacy previous-server hint), 'disagg' (prefill/decode
        pair — decode_url is set and the client forwards it into
        /generate), or the configured base policy. kv_source, when set,
        names a server holding the session's KV prefix that is NOT the
        routed server — the client forwards it and the target restores
        over /kv/{manifest,chunk} instead of re-prefilling.
        (None, 'none', None, None) when the whole fleet is unhealthy.

        Multi-model fleets filter candidates to the requested model's
        pool FIRST — affinity, index, spill, sticky and the base
        policies all operate inside it, so a session can never land on
        (or pull KV from) another model's server. An unknown/poolless
        model routes nowhere: (None, 'no-model-pool', None, None)."""
        candidates = self._healthy_urls()
        if getattr(self.cfg, "multi_model", False):
            model = str(meta.get("model") or "") or self.cfg.model_name
            candidates = [
                u for u in candidates if self._model_of(u) == model
            ]
            if not candidates:
                return None, "no-model-pool", None, None
        if not candidates:
            return None, "none", None, None
        now = time.monotonic()
        tripped = set(self.breakers.open_peers())
        open_ = [
            u for u in candidates
            if self._server_shed_until.get(u, 0.0) <= now
            and u not in tripped
        ]
        # Whole fleet inside a shed window / breaker-open: route anyway
        # (the client backs off on the 429 itself, and a half-open
        # probe needs SOME traffic); shed hints and breakers are
        # advisory, never a second eviction mechanism.
        pool = open_ or candidates
        qid = str(meta.get("qid") or "")
        if self._disagg_split(candidates):
            return self._choose_disagg(meta, candidates, pool, qid, now)
        holder = self._index_holder(qid, candidates)
        if self.cfg.session_affinity and qid:
            aff = self._affinity.get(qid)
            policy_hit = "affinity"
            if aff is None or aff not in candidates:
                # Affinity map forgot (LRU cap, manager restart) but the
                # global index still knows a holder: same fast path.
                aff, policy_hit = holder, "kv-index"
            if aff is not None and aff in candidates:
                sat = self.cfg.affinity_saturation_requests
                shedding = self._server_shed_until.get(aff, 0.0) > now
                saturated = (
                    sat is not None and self._server_reqs.get(aff, 0) >= sat
                )
                if not shedding and not saturated:
                    # KV-prefix reuse survives weight-version bumps: the
                    # engine flushes stale KV on swap, so the worst case
                    # is the same re-prefill any server would pay.
                    return aff, policy_hit, None, None
                spill_pool = [u for u in pool if u != aff] or pool
                spilled = min(spill_pool, key=self._load_key)
                # The spilled-to server can pull the prefix from the
                # saturated holder — spill costs a transfer, not a
                # re-prefill.
                src = aff if spilled != aff else None
                return spilled, "spill", None, src
        prev = meta.get("previous_server_url") or ""
        prev_version = int(meta.get("previous_version", -1))
        # Legacy sticky hint (clients predating the affinity map, or a
        # restarted manager with an empty map). Unlike affinity it has
        # no saturation/shed spill, so keep the pre-affinity guard:
        # sticky only while the weight version is unchanged — version
        # bumps are the periodic rebalancing trigger.
        if prev in pool and prev_version == self._model_version(
            self._model_of(prev)
        ):
            return (
                prev, "sticky", None,
                holder if holder and holder != prev else None,
            )
        policy = self.cfg.schedule_policy
        if policy == "least_requests":
            url = min(pool, key=lambda u: self._server_reqs[u])
        elif policy == "least_token_usage":
            url = min(
                pool,
                key=lambda u: self._server_tokens[u]
                + self._server_tokens_pending.get(u, 0.0),
            )
        else:
            policy = "round_robin"
            url = pool[self._rr % len(pool)]
            self._rr += 1
        # Affinity off (or fresh session under load-balance policies):
        # the index still pays — whoever we route to pulls the prefix.
        return url, policy, None, (
            holder if holder and holder != url else None
        )

    def _choose_disagg(self, meta, candidates, pool, qid, now):
        """Pool routing for a split fleet: continuations follow their
        decode-side KV (session affinity), fresh work pairs the least
        prompt-loaded prefill server with the most page-free decode
        server — each pool batches and scales on its own signal."""
        prefill_pool = [u for u in pool if self._role(u) != "decode"]
        decode_pool = [u for u in pool if self._role(u) != "prefill"]
        # A failure retry re-pairs through the pools instead of riding
        # affinity: the affinity entry was recorded at PAIRING time, so
        # after a prefill server died mid-handoff it may point at a
        # decode server that never received the session's KV — the
        # retry must land on a surviving prefill server, not turn the
        # decode server into an accidental unified one.
        retry = bool(meta.get("failed_server_url"))
        holder = None if retry else self._index_holder(qid, candidates)
        if self.cfg.session_affinity and qid and not retry:
            aff = self._affinity.get(qid)
            policy_hit = "affinity"
            if aff is None or aff not in candidates:
                aff, policy_hit = holder, "kv-index"
            if aff is not None and aff in candidates:
                # The session's KV parked on its decode server; a direct
                # /generate there prefills only the delta. Honored even
                # if the sizer has since re-roled that server prefill-
                # ward — any role serves plain /generate, and the
                # parked delta is far cheaper than the full re-prefill
                # a KV-less decode server would pay. Spill like the
                # unified path when it sheds/saturates — with a
                # kv_source hint so the spill target pulls the prefix.
                sat = self.cfg.affinity_saturation_requests
                shedding = self._server_shed_until.get(aff, 0.0) > now
                saturated = (
                    sat is not None and self._server_reqs.get(aff, 0) >= sat
                )
                if not shedding and not saturated:
                    return aff, policy_hit, None, None
                if decode_pool:
                    spill = [u for u in decode_pool if u != aff] or decode_pool
                    spilled = min(spill, key=self._load_key)
                    return (
                        spilled, "spill", None,
                        aff if spilled != aff else None,
                    )
        if not prefill_pool or not decode_pool:
            # Degenerate split (one pool empty): serve unified on
            # whatever remains rather than stalling.
            rest = prefill_pool or decode_pool or pool
            url = min(rest, key=self._load_key)
            return url, "disagg-degenerate", None, (
                holder if holder and holder != url else None
            )
        # Prefill by queued-prompt-token load (the signal that actually
        # queues there), decode by free-page/slot headroom.
        purl = min(
            prefill_pool,
            key=lambda u: (
                self._server_queued_toks.get(u, 0.0)
                + self._server_tokens_pending.get(u, 0.0),
                self._server_reqs.get(u, 0),
            ),
        )
        durl = min(
            decode_pool,
            key=lambda u: (
                self._server_reqs.get(u, 0),
                -self._server_free_pages.get(u, 0.0),
            ),
        )
        if purl == durl:
            # Same (unified) server won both pools: plain local serve.
            return purl, "disagg-local", None, (
                holder if holder and holder != purl else None
            )
        # The prefill server does the (delta) prefill, so it is the one
        # that profits from pulling the session's prefix.
        return purl, "disagg", durl, (
            holder if holder and holder != purl else None
        )

    def _route(
        self, meta: Dict
    ) -> Tuple[Optional[str], str, Optional[str], Optional[str]]:
        """Choose a server AND do the routing-side bookkeeping: bump the
        in-flight request estimate, fold the scheduled tokens into the
        load estimate until the next /metrics poll refreshes the
        snapshot (a burst between polls must not pile onto one server),
        and record the session's affinity. For a disaggregated pair the
        prompt tokens land on the prefill server's estimate, the decode
        budget on the decode server's — and the session's affinity
        points at the DECODE server, where its KV will live."""
        qid = str(meta.get("qid") or "")
        with self._lock:
            url, policy, decode_url, kv_source = self._choose_server(meta)
            if url is not None:
                self._server_reqs[url] += 1
                self._server_tokens_pending[url] = (
                    self._server_tokens_pending.get(url, 0.0)
                    + float(meta.get("prompt_len") or 0)
                    + (0.0 if decode_url
                       else float(meta.get("new_token_budget") or 0))
                )
                if decode_url is not None:
                    self._server_reqs[decode_url] = (
                        self._server_reqs.get(decode_url, 0) + 1
                    )
                    self._server_tokens_pending[decode_url] = (
                        self._server_tokens_pending.get(decode_url, 0.0)
                        + float(meta.get("prompt_len") or 0)
                        + float(meta.get("new_token_budget") or 0)
                    )
                self._record_affinity(qid, decode_url or url)
        return url, policy, decode_url, kv_source

    def _record_affinity(self, qid: str, url: str):
        """LRU-bounded qid -> url map (call under self._lock)."""
        if not qid or not self.cfg.session_affinity:
            return
        self._affinity.pop(qid, None)
        self._affinity[qid] = url
        while len(self._affinity) > max(1, self.cfg.affinity_map_size):
            self._affinity.popitem(last=False)

    # ------------------------------------------------------------------
    # Fault-domain isolation: eviction + readmission
    # ------------------------------------------------------------------

    def _drop_index_for(self, url: str):
        """Evictee migration for the global prefix index (call under
        self._lock): a dead/replaced server's process RAM — and so its
        whole KV tier — is gone; entries pointing at it would route
        returning sessions into guaranteed pull failures."""
        qids = self._server_kv_index.pop(url, None) or set()
        for q in qids:
            ent = self._prefix_index.get(q)
            if ent is not None and ent.get("url") == url:
                self._prefix_index.pop(q, None)

    # Keep in sync with _add_server_row: every dict here gets a zeroed
    # row there.
    _PER_SERVER_FLOAT_MAPS = (
        "_server_tokens", "_server_gen_totals", "_server_prefix_hits",
        "_server_prefix_reused", "_server_gen_reqs",
        "_server_spec_emitted", "_server_spec_steps",
        "_server_tokens_pending", "_server_shed_until",
        "_server_shed_total", "_server_queued_toks",
    )
    _PER_SERVER_SPARSE_MAPS = (
        "_server_free_pages", "_server_total_pages", "_server_kv",
        "_server_elastic", "_server_shards", "_rerole_orig",
        "_server_ttft_hist", "_server_itl_hist", "_server_models",
    )

    def _forget_server(self, url: str, remove: bool = False):
        """Drop every routing-side trace of ``url`` in ONE place (call
        under self._lock). Shared by eviction, URL replacement, and the
        drain/leave path — these used to prune the maps ad hoc in three
        places and drifted (ISSUE 12 satellite).

        remove=False (eviction): the url stays a fleet member — the
        readmission path may bring it back — but its in-flight load
        estimates, shed window, affinity entries, prefix-index entries,
        and shard row are gone; its process state (and so its KV)
        cannot be trusted, and shard/role re-learn from the next
        heartbeat before readmission. remove=True (clean departure /
        dead-address replacement) additionally drops the whole row:
        table membership, role/latency bookkeeping, version, health
        split, and the member mapping."""
        self._server_reqs[url] = 0
        self._server_tokens[url] = 0.0
        self._server_tokens_pending[url] = 0.0
        self._server_shed_until[url] = 0.0
        for qid in [q for q, u in self._affinity.items() if u == url]:
            self._affinity.pop(qid, None)
        self._drop_index_for(url)
        self._server_shards.pop(url, None)
        self._draining.discard(url)
        self._drain_deadline.pop(url, None)
        self._join_t0.pop(url, None)
        self._join_info.pop(url, None)
        if not remove:
            return
        # The departed incarnation's cumulative tokens leave the fleet
        # sum; shift the throughput baseline down with them or the next
        # tokens/s log goes negative.
        self._last_gen_total = max(
            0.0,
            self._last_gen_total - self._server_gen_totals.get(url, 0.0),
        )
        self.server_urls = [u for u in self.server_urls if u != url]
        for attr in self._PER_SERVER_FLOAT_MAPS + self._PER_SERVER_SPARSE_MAPS:
            getattr(self, attr).pop(url, None)
        self._server_reqs.pop(url, None)
        self._server_roles.pop(url, None)
        self._server_versions.pop(url, None)
        self.breakers.drop(url)
        for member in [m for m, u in self._member_urls.items() if u == url]:
            self._member_urls.pop(member, None)
        self._healthy.discard(url)
        self._evicted.pop(url, None)

    def _add_server_row(self, url: str):
        """Zeroed routing-table row for a url entering the table (join
        adoption or dead-address replacement); call under self._lock.
        Role/shard refresh from the incarnation's first heartbeat."""
        self.server_urls = sorted(set(self.server_urls) | {url})
        for attr in self._PER_SERVER_FLOAT_MAPS:
            getattr(self, attr)[url] = 0.0
        self._server_reqs[url] = 0
        self._server_roles[url] = "unified"
        self._server_models.setdefault(url, self.cfg.model_name)
        self._server_versions[url] = 0

    def _admit_server(self, url: str, member: str, record: Dict):
        """Adopt a runtime joiner into the routing table (call under
        self._lock). It starts EVICTED ('joining') so the normal
        readmission path weight-bootstraps it — from peers over the
        weight plane when armed — before it takes traffic."""
        self._add_server_row(url)
        self._member_urls[member] = url
        role = record.get("role")
        if role:
            self._server_roles[url] = str(role)
        mid = record.get("model_id")
        if mid:
            self._server_models[url] = str(mid)
        shard = record.get("weight_shard")
        if shard and len(shard) == 2:
            self._server_shards[url] = (int(shard[0]), int(shard[1]))
        idx = record.get("server_index")
        if idx is not None:
            self._known_indices.add(int(idx))
        self._healthy.discard(url)
        self._evicted[url] = "joining: weight bootstrap pending"
        self._join_t0[url] = time.monotonic()
        # A registered AUTOSCALER launch stops being pending (it now
        # counts as 'joining'): leaving the timestamp behind would
        # double-count it against the ceiling and block scale-in for
        # the whole 180s horizon. Only launches the autoscaler itself
        # issued qualify — an operator join popping someone else's
        # marker would un-gate the ceiling while that launch is still
        # genuinely in flight.
        if (
            idx is not None
            and int(idx) in self._launched_indices
            and self._pending_launches
        ):
            self._launched_indices.discard(int(idx))
            # Pop the joiner's OWN model's marker (a model-B join must
            # not un-gate a still-in-flight model-A launch).
            joined = self._server_models.get(url, self.cfg.model_name)
            for i, ent in enumerate(self._pending_launches):
                if ent.get("model") in (None, joined):
                    self._pending_launches.pop(i)
                    break
            else:
                self._pending_launches.pop(0)

    def _mark_unhealthy(self, url: str, reason: str):
        if url not in self.server_urls:
            return
        with self._lock:
            if url not in self._healthy:
                return
            self._healthy.discard(url)
            self._evicted[url] = reason
            # In-flight estimates for a dead server are meaningless; a
            # readmitted server starts from a clean routing slate.
            self._forget_server(url)
        logger.warning(
            f"evicted generation server {url}: {reason} "
            f"({len(self._healthy_urls())}/{len(self.server_urls)} healthy)"
        )

    def _readmit(self, url: str):
        with self._lock:
            self._evicted.pop(url, None)
            self._healthy.add(url)
            t0 = self._join_t0.pop(url, None)
            if t0 is not None:
                # A runtime joiner just entered routing: record the
                # join (admit -> routable) with its bootstrap breakdown
                # for /status and the fleet_elastic bench.
                entry = {
                    "t": time.time(), "url": url,
                    "join_s": time.monotonic() - t0,
                    "version": self.weight_version,
                }
                entry.update(self._join_info.pop(url, {}))
                self._join_log.append(entry)
                del self._join_log[:-32]
                tracing.event("manager.join", server=url,
                              join_s=entry["join_s"],
                              source=entry.get("source", ""))
        logger.info(
            f"readmitted generation server {url} at weight version "
            f"{self._server_versions.get(url, 0)} "
            f"({len(self._healthy_urls())}/{len(self.server_urls)} healthy)"
        )

    def _current_param_path(
        self, model: Optional[str] = None
    ) -> Optional[str]:
        path = os.path.join(
            constants.get_param_realloc_path(
                self.cfg.experiment_name, self.cfg.trial_name
            ),
            model or self.cfg.model_name,
        )
        # A sharded trainer (mesh > 1) writes only the shard-local raw
        # dump — no engine_state.pkl — which the servers assemble through
        # weight_transfer.load_for_serving; gating on the pickle alone
        # left that update forever pending.
        from areal_tpu.system.weight_transfer import has_raw_dump

        if has_raw_dump(path) or os.path.exists(
            os.path.join(path, "engine_state.pkl")
        ):
            return path
        return None

    def _resync_server(self, url: str) -> bool:
        """Push the current weight version to a returning server before
        it re-enters rotation (server-side is_stale_update makes this a
        cheap no-op when it already has the version). Targets the
        server's OWN model's version and checkpoint tree."""
        target_v = self._target_version(url)
        if target_v <= 0:
            return True
        path = self._current_param_path(self._model_of(url))
        if path is None:
            # Dump GC'd / not yet written: can't prove the server is
            # current, keep it out of rotation until the next fanout.
            return False

        async def _push():
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.cfg.flush_request_timeout)
            ) as sess:
                async with sess.post(
                    f"{url}/update_weights_from_disk",
                    json={"model_path": path, "allow_interrupt": True,
                          "version": target_v},
                ) as r:
                    body = await r.json()
                    return bool(body.get("success"))

        try:
            fut = asyncio.run_coroutine_threadsafe(_push(), self._http_loop)
            ok = self._await_fut(
                fut, self.cfg.flush_request_timeout + 10
            )
        except Exception:
            logger.warning(f"re-sync of {url} failed; staying evicted",
                           exc_info=True)
            return False
        if ok:
            with self._lock:
                self._server_versions[url] = target_v
        return ok

    def _bootstrap_server(self, url: str) -> bool:
        """Bring a joining/returning server to the current weight
        version before it enters rotation. With the weight plane armed
        this fetches from PEERS over /weights/{manifest,chunk} with the
        origin as last resort — a joiner never touches NFS; without the
        plane it falls back to the legacy /update_weights_from_disk
        re-sync. Returns False (stay evicted, retry next health poll)
        on any failure."""
        if self._target_version(url) <= 0:
            return True
        if getattr(self.cfg, "weight_plane", False):
            try:
                return self._plane_bootstrap(url)
            except Exception:
                logger.warning(
                    f"plane bootstrap of {url} failed; staying evicted",
                    exc_info=True,
                )
                return False
        return self._resync_server(url)

    def _plane_bootstrap(self, url: str) -> bool:
        """One-server weight bootstrap over the distribution plane:
        manifest + chunks from same-shard peers that hold the current
        version (their ChunkStores outlive cutover for exactly this),
        origin last resort, then a normal cutover. Runs on the worker
        poll thread (blocking manifest fetch is fine there)."""
        from areal_tpu.engine.weight_client import fetch_manifest

        model = self._model_of(url)
        version = self._model_version(model)
        t0 = time.monotonic()
        with self._lock:
            shard = self._server_shards.get(url)
            # Same-MODEL same-shard peers only: a model-B holder at the
            # right integer version still streams the wrong weights.
            holders = [
                u for u in self._healthy_urls(model)
                if u != url
                and self._server_shards.get(u) == shard
                and self._server_versions.get(u, 0) == version
            ]
        degree = shard[1] if shard else 1
        rank = shard[0] if shard else 0
        wire = getattr(self.cfg, "weight_wire_dtype", None)
        origin = self._weight_plane_origin(
            self._current_param_path(model), model
        )
        man = None
        if self.cfg.join_bootstrap != "origin":
            for h in holders:
                try:
                    man = fetch_manifest(
                        h, version=version, timeout=5.0, wire=wire,
                        tp_degree=degree if degree > 1 else None,
                        tp_rank=rank if degree > 1 else None,
                    )
                    break
                except Exception:
                    continue
        if man is None:
            if origin is None:
                logger.warning(
                    f"bootstrap of {url}: no peer holds v{version} and "
                    f"no plane origin is reachable; retrying next poll"
                )
                return False
            man = self._fetch_plane_manifest(
                origin, version,
                tp_degree=degree if degree > 1 else None,
                tp_rank=rank if degree > 1 else None,
            )
        if self.cfg.join_bootstrap == "origin":
            upstreams = [origin] if origin else []
        else:
            upstreams = holders[:3]
        payload = {
            "version": version, "manifest": man,
            "upstreams": upstreams, "origin": origin,
            "deadline_s": self.cfg.flush_request_timeout,
        }
        cut_total = max(
            self.cfg.flush_request_timeout, 120.0,
            self.cfg.weight_cutover_budget_s * 10.0,
        ) + 10

        async def _push():
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(
                    total=self.cfg.flush_request_timeout + cut_total
                )
            ) as sess:
                _u, ok, body = await self._post_distribute(
                    sess, url,
                    upstreams[0] if upstreams else (origin or ""),
                    payload, None,
                )
                if not ok:
                    return False, body
                _u, ok2, body2 = await self._post_cutover(
                    sess, url, version, None
                )
                body = dict(body)
                body.update(body2)
                return ok2, body

        fut = asyncio.run_coroutine_threadsafe(_push(), self._http_loop)
        ok, body = self._await_fut(
            fut, self.cfg.flush_request_timeout + cut_total + 10
        )
        if not ok:
            if body.get("weight_shard"):
                # Shard-spec 409: OUR map was stale (bootstrap racing
                # the first heartbeat). Learn and retry next poll.
                ws = body["weight_shard"]
                spec = (int(ws[0]), int(ws[1]))
                with self._lock:
                    self._server_shards[url] = (
                        None if spec == (0, 1) else spec
                    )
            logger.warning(f"plane bootstrap of {url} rejected: {body}")
            return False
        from_peers = float(body.get("bytes_from_peers") or 0.0)
        from_origin = float(body.get("bytes_from_origin") or 0.0)
        if body.get("already_held") or body.get("joined"):
            source = "held"
        elif from_origin > 0.0:
            source = "origin"
        else:
            source = "peer"
        with self._lock:
            self._server_versions[url] = version
            self._join_info[url] = {
                "source": source,
                "bytes_from_peers": from_peers,
                "bytes_from_origin": from_origin,
                "transfer_ms": float(body.get("transfer_ms") or 0.0),
                "cutover_ms": float(body.get("cutover_ms") or 0.0),
                "bootstrap_ms": (time.monotonic() - t0) * 1000.0,
            }
        logger.info(
            f"plane bootstrap of {url} to v{version}: {source} "
            f"(peers {from_peers:.0f}B, origin {from_origin:.0f}B) in "
            f"{(time.monotonic() - t0) * 1000.0:.0f}ms"
        )
        return True

    def attach_launcher(self, launcher):
        """Arm scale-out actuation (fleet_controller.Launcher). Config
        carries the watermark policy; the launcher is process-local
        wiring (subprocess locally, a scheduler client in production)."""
        self._launcher = launcher

    def _next_server_index(self) -> int:
        return (
            max(self._known_indices) + 1
            if self._known_indices else len(self.server_urls)
        )

    def _pick_drain_victim(
        self, model: Optional[str] = None
    ) -> Optional[str]:
        """Least-loaded routable server, never the last one (never the
        last of ITS MODEL's pool when model-scoped); skip when a
        disaggregated split would fall below its pool floors."""
        with self._lock:
            cands = self._healthy_urls(model)
            if len(cands) <= 1:
                return None
            if self._disagg_split(cands):
                roles = {u: self._role(u) for u in cands}
                n_prefill = sum(1 for u in cands if roles[u] != "decode")
                n_decode = sum(1 for u in cands if roles[u] != "prefill")
                cands = [
                    u for u in cands
                    if (roles[u] == "decode"
                        or n_prefill - 1 >= self.cfg.pool_min_prefill)
                    and (roles[u] == "prefill"
                         or n_decode - 1 >= self.cfg.pool_min_decode)
                ]
                if not cands:
                    return None
            return min(cands, key=self._load_key)

    def _model_autoscaler(self, model: Optional[str]):
        """The watermark instance for one pool. The default model (and
        the single-model fleet, model=None) uses the configured
        instance; other models get their own lazily — each pool needs
        its own sustain/cooldown debounce — with floors/ceilings
        overridden by the model's registry pool policy when set."""
        if model is None or model == self.cfg.model_name:
            return self._autoscaler
        autoscaler = self._autoscalers.get(model)
        if autoscaler is None:
            pol = dataclasses.replace(self._autoscaler.policy)
            rec = self._model_records.get(model)
            if rec is not None:
                if rec.min_servers > 0:
                    pol.pool_min_servers = int(rec.min_servers)
                if rec.max_servers > 0:
                    pol.pool_max_servers = int(rec.max_servers)
            autoscaler = fleet_controller.WatermarkAutoscaler(pol)
            self._autoscalers[model] = autoscaler
        return autoscaler

    def _maybe_autoscale(self):
        """Watermark autoscaling over the fresh metrics snapshot (rides
        the same poll cadence as the re-role sizer). Scale-out launches
        through the attached launcher; scale-in drains the least-loaded
        server, which migrates its KV and departs cleanly. Multi-model
        fleets run one decision per model POOL — model B saturating
        must grow B's pool, not read A's idle headroom as spare."""
        if self._autoscaler is None:
            return
        if self._launcher is not None:
            self._launcher.reap()
        if getattr(self.cfg, "multi_model", False):
            for model in sorted(self._model_set):
                self._autoscale_pool(model)
            return
        self._autoscale_pool(None)

    def _autoscale_pool(self, model: Optional[str]):
        """One pool's watermark decision (model=None: the whole fleet —
        the single-model behavior, byte-identical signals)."""
        autoscaler = self._model_autoscaler(model)
        if autoscaler is None:
            return
        now = time.monotonic()
        with self._lock:
            routable = self._healthy_urls(model)
            queued = sum(
                self._server_queued_toks.get(u, 0.0) for u in routable
            )
            free = sum(
                self._server_free_pages.get(u, 0.0) for u in routable
            )
            total = sum(
                self._server_total_pages.get(u, 0.0) for u in routable
            )
            joining = [
                u for u in self._evicted
                if u in self._join_t0
                and (model is None or self._model_of(u) == model)
            ]
            # Launches that never registered stop counting as pending
            # after the spawn horizon, or one lost child wedges
            # scale-out forever.
            self._pending_launches = [
                e for e in self._pending_launches if now - e["t"] < 180.0
            ]
            n_pending = len(joining) + sum(
                1 for e in self._pending_launches
                if model is None or e.get("model") in (None, model)
            )
        action = autoscaler.observe(
            len(routable), n_pending, queued,
            free / total if total > 0 else 1.0,
        )
        if action == "out":
            if self._launcher is None:
                logger.warning(
                    "autoscale: scale-out wanted but no launcher attached"
                )
                return
            idx = self._next_server_index()
            self._known_indices.add(idx)
            try:
                self._launch_indexed(idx, model)
            except Exception:
                logger.warning("autoscale launch failed", exc_info=True)
                return
            self._launched_indices.add(idx)
            with self._lock:
                self._pending_launches.append({"t": now, "model": model})
                self._scale_log.append({
                    "t": time.time(), "action": "out",
                    "server_index": idx, "queued_tokens": queued,
                    "n_routable": len(routable),
                    "model": model or self.cfg.model_name,
                })
                del self._scale_log[:-32]
            tracing.event("manager.scale_out", server_index=idx,
                          queued_tokens=queued)
        elif action == "in":
            victim = self._pick_drain_victim(model)
            if victim is None:
                return
            if self._drain_server_sync(
                victim, reason="autoscale: under low watermark"
            ):
                with self._lock:
                    self._scale_log.append({
                        "t": time.time(), "action": "in", "url": victim,
                        "queued_tokens": queued,
                        "n_routable": len(routable),
                        "model": model or self.cfg.model_name,
                    })
                    del self._scale_log[:-32]
                tracing.event("manager.scale_in", server=victim,
                              queued_tokens=queued)

    def _launch_indexed(self, idx: int, model: Optional[str]):
        """Launch through the attached launcher, passing the target
        model when the launcher's spawn path understands it (the
        subprocess harness and legacy launchers take only the index)."""
        if model is not None and model != self.cfg.model_name:
            import inspect

            try:
                params = inspect.signature(
                    self._launcher.launch
                ).parameters
            except (TypeError, ValueError):
                params = {}
            if "model_id" in params:
                self._launcher.launch(idx, model_id=model)
                return
        self._launcher.launch(idx)

    def _drain_server_sync(self, url: str, reason: str) -> bool:
        """Poll-thread entry to the drain orchestration (the HTTP POST
        itself runs on the event loop)."""
        fut = asyncio.run_coroutine_threadsafe(
            self._initiate_drain(url, reason), self._http_loop
        )
        try:
            return bool(fut.result(timeout=30).get("success"))
        except Exception:
            logger.warning(f"drain initiation for {url} failed",
                           exc_info=True)
            return False

    async def _initiate_drain(self, url: str, reason: str) -> Dict:
        """Drain-then-leave, manager side: stop routing to the server
        NOW (in-flight work finishes; its KV stays pullable), then ask
        it to quiesce, migrate its parked prefixes to the surviving
        peers over the /kv wire, and depart with a graceful heartbeat
        stop — which the health fold turns into a clean
        _forget_server. A drain that never completes is rolled back by
        the deadline sweep in _poll."""
        with self._lock:
            if url not in self.server_urls or url not in self._healthy:
                return {"success": False, "error": f"{url} is not healthy"}
            if url in self._draining:
                return {"success": False,
                        "error": f"{url} is already draining"}
            # Migration targets come from the drainee's OWN model pool:
            # parking model-A prefixes on a model-B server would hand
            # returning sessions cross-model KV.
            migrate = [
                u for u in self._healthy_urls(
                    self._model_of(url)
                    if getattr(self.cfg, "multi_model", False) else None
                )
                if u != url
            ]
            if not migrate:
                return {"success": False,
                        "error": "cannot drain the last routable server"}
            self._draining.add(url)
            self._drain_deadline[url] = (
                time.monotonic() + self.cfg.drain_timeout_s
            )
        try:
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=15)
            ) as sess:
                async with sess.post(
                    f"{url}/drain",
                    json={"migrate_to": migrate, "exit": True,
                          "reason": reason},
                ) as r:
                    body = await r.json()
            ok = bool(body.get("success"))
        except Exception as e:
            ok, body = False, {"error": repr(e)}
        if not ok:
            with self._lock:
                self._draining.discard(url)
                self._drain_deadline.pop(url, None)
            return {"success": False,
                    "error": f"drain request failed: {body}"}
        with self._lock:
            self._drain_log.append({
                "t": time.time(), "url": url, "reason": reason,
                "status": "draining",
            })
            del self._drain_log[:-32]
        tracing.event("manager.drain", server=url, reason=reason)
        logger.info(
            f"draining {url}: {reason} "
            f"(migrating KV to {len(migrate)} peer(s))"
        )
        return {"success": True, "migrate_to": migrate}

    def _replace_server_url(self, old: str, new: str):
        """A restarted generation server re-registers the SAME health
        member at a NEW address: forget the dead incarnation's whole
        routing footprint (affinity, prefix-index, shard — the new
        process holds no KV and re-reports its spec on the first
        heartbeat) and add a zeroed row for the new address. The new
        incarnation starts evicted at version 0, so the normal
        readmission path re-syncs it before it serves."""
        with self._lock:
            self._forget_server(old, remove=True)
            self._add_server_row(new)
            self._evicted[new] = "restarted at new address"
        logger.info(f"generation server moved {old} -> {new}")

    def _poll_health(self):
        """Fold the health registry into the healthy/evicted split:
        heartbeat loss evicts, heartbeat return (after a weight re-sync)
        readmits; a member returning at a new address migrates the
        routing table first."""
        # One subtree walk serves both the live set and the graceful-
        # departure fold below (each record read is file I/O).
        snapshot, stopped_snap = self._registry.classified()
        alive_urls = set()
        unknown = []
        for member, record in sorted(snapshot.items()):
            url = record.get("url")
            if not url:
                continue
            old = self._member_urls.get(member)
            if old is not None and old != url and old in self.server_urls:
                self._replace_server_url(old, url)
            elif url not in self.server_urls:
                if old is None:
                    unknown.append((member, url))
                continue
            self._member_urls[member] = url
            alive_urls.add(url)
            # Pool role from the heartbeat payload (fresher than the
            # metrics poll) — but never clobber a role OUR sizer set:
            # the server's heartbeat may predate the /set_role landing.
            role = record.get("role")
            if role and url not in self._rerole_orig:
                self._server_roles[url] = str(role)
            mid = record.get("model_id")
            if mid:
                self._server_models[url] = str(mid)
            shard = record.get("weight_shard")
            if shard and len(shard) == 2:
                self._server_shards[url] = (int(shard[0]), int(shard[1]))
            if record.get("server_index") is not None:
                self._known_indices.add(int(record["server_index"]))
            # Drain advertised through the heartbeat: survives a manager
            # restart (the successor rebuild reads the same flag).
            # Under the lock: /status iterates this set on the HTTP
            # loop (sorted() over a set mutating mid-iteration raises).
            # A heartbeat-learned drain gets a deadline too — the
            # timeout eviction sweep must cover drains we did not
            # initiate (takeover inheritance, operator drains), or a
            # wedged migration keeps the server in limbo forever.
            if record.get("draining") and url not in self._draining:
                with self._lock:
                    self._draining.add(url)
                    self._drain_deadline.setdefault(
                        url,
                        time.monotonic() + self.cfg.drain_timeout_s,
                    )
        # Adoption: a member we have NEVER seen, beating at an address
        # outside the table. If its previous incarnation died before we
        # observed it, it is the restarted owner of some evicted url no
        # live member claims — replace the (deterministically first)
        # such dead-weight entry. Otherwise it is a runtime JOINER
        # (autoscaler launch, operator scale-out): adopt it into the
        # table; it bootstraps weights before routing.
        for member, url in unknown:
            # Multi-model gate FIRST — before dead-weight replacement
            # and before elastic adoption: a beat naming a model_id the
            # registry has never heard of is QUARANTINED, never adopted.
            # Re-read the registry once on a miss (the record may have
            # just landed); routing an unregistered model's server
            # would risk silent cross-model weight/KV hits.
            if getattr(self.cfg, "multi_model", False):
                mid = str(snapshot[member].get("model_id") or "")
                if mid and mid not in self._model_set:
                    self._refresh_model_set()
                if mid and mid not in self._model_set:
                    if self._quarantined.get(member) != mid:
                        logger.warning(
                            f"quarantined joiner {url} ({member}): "
                            f"heartbeat names unregistered model_id "
                            f"{mid!r}"
                        )
                    self._quarantined[member] = mid
                    continue
                self._quarantined.pop(member, None)
            claimed = set(self._member_urls.values())
            dead_weight = sorted(
                u for u in self.server_urls
                if u in self._evicted and u not in claimed
            )
            if dead_weight:
                self._replace_server_url(dead_weight[0], url)
                self._member_urls[member] = url
                alive_urls.add(url)
                continue
            if not self.cfg.elastic_fleet:
                continue  # fixed fleet: ignore strangers
            with self._lock:
                self._admit_server(url, member, snapshot[member])
            alive_urls.add(url)
            logger.info(
                f"fleet join: adopted {url} ({member}); weight bootstrap "
                f"pending ({len(self.server_urls)} members)"
            )
        # A quarantined member that stopped beating leaves the ledger
        # (it can re-earn a row by beating again post-registration).
        if self._quarantined:
            self._quarantined = {
                m: v for m, v in self._quarantined.items() if m in snapshot
            }
        # Graceful departures (drain-then-leave): a member that announced
        # a clean stop is REMOVED, not evicted — no failure handling, no
        # readmission. Must run before death detection: a stopped member
        # also vanishes from the snapshot.
        if self.cfg.elastic_fleet:
            for member, record in stopped_snap.items():
                url = record.get("url") or self._member_urls.get(member)
                if not url or url not in self.server_urls:
                    continue
                with self._lock:
                    self._forget_server(url, remove=True)
                    self._drain_log.append({
                        "t": time.time(), "url": url, "status": "departed",
                        "migrated": int(record.get("drain_migrated") or 0),
                        "lost": int(record.get("drain_lost") or 0),
                    })
                    del self._drain_log[:-32]
                # The stopped record has served its purpose (the
                # controller only consults it for a LIVE process's
                # hang check; death handling keys off exit codes):
                # delete it, or every future health poll re-reads a
                # departed member's record forever.
                try:
                    name_resolve.delete(names.health(
                        self.cfg.experiment_name, self.cfg.trial_name,
                        member,
                    ))
                except Exception:
                    pass
                logger.info(
                    f"fleet leave: {url} departed cleanly ({member}); "
                    f"{len(self.server_urls)} member(s) remain"
                )
        # Death: a server we have seen heartbeat before is now stale.
        for member, url in list(self._member_urls.items()):
            if member not in snapshot and url in self._healthy:
                self._mark_unhealthy(url, f"missed heartbeats ({member})")
        # Readmission: evicted servers whose heartbeat is back (and
        # joiners whose first heartbeat brought them in above). Never
        # a DRAINING server: it is alive but shedding everything and
        # on its way out — only its departure (or death) ends that.
        for url in [
            u for u in list(self._evicted)
            if u in alive_urls and u not in self._draining
        ]:
            # Each re-sync can block up to the flush timeout; renew this
            # worker's own lease between them so recovering several
            # servers can't make the supervisor hang-kill the manager.
            self._beat()
            if (
                self._server_versions.get(url, 0)
                >= self._target_version(url)
                or self._bootstrap_server(url)
            ):
                self._readmit(url)
        # Rollout-worker quota reconciliation: a worker whose heartbeat
        # died (or gracefully departed) can never finish its episodes —
        # give its outstanding slots (and their staleness budget) back.
        rollout_alive = self._rollout_registry.snapshot()
        self._rollout_seen |= set(rollout_alive)
        for member in [m for m in self._rollout_seen if m not in rollout_alive]:
            self._rollout_seen.discard(member)
            with self._lock:
                n = self._worker_slots.pop(member, 0)
                if n:
                    self.rollout_stat.running = max(
                        0, self.rollout_stat.running - n
                    )
                    self.rollout_stat.submitted = max(
                        0, self.rollout_stat.submitted - n
                    )
            if n:
                logger.warning(
                    f"reclaimed {n} quota slot(s) from dead/departed "
                    f"rollout worker {member}"
                )

    def _training_samples(self) -> int:
        """Cached global-sample counter for the staleness gate.

        Regression note (areal-lint blocking-async): this used to read
        name_resolve inline — file I/O, NFS-backed in production — and
        is_staled() calls it from the /allocate_rollout handler ON the
        HTTP event loop while holding self._lock, so one slow NFS stat
        stalled every concurrent admission/schedule request. The poll
        thread now refreshes the snapshot (_refresh_training_samples);
        one poll lap of staleness is harmless — the counter only grows,
        and rollout_stat.submitted (the other max() arm) is live."""
        return self._training_samples_cache

    def _refresh_training_samples(self) -> None:
        """Poll-thread-only: fetch the published counter (file I/O)."""
        try:
            self._training_samples_cache = int(
                name_resolve.get(
                    names.training_samples(
                        self.cfg.experiment_name, self.cfg.trial_name
                    )
                )
            )
        except (name_resolve.NameEntryNotFoundError, ValueError):
            pass

    def prefix_cache_fleet(self) -> Dict[str, float]:
        """Fleet prefix-cache effectiveness as ratios of SUMS (the
        spec_tokens_per_step fix shape): per-server counters summed
        first, divided once — an unweighted mean of per-server rates
        would overweight idle servers."""
        hits = sum(self._server_prefix_hits.values())
        reused = sum(self._server_prefix_reused.values())
        reqs = sum(self._server_gen_reqs.values())
        return {
            "prefix_cache_hits": hits,
            "prefix_tokens_reused": reused,
            "total_requests": reqs,
            "prefix_cache_hit_rate": hits / reqs if reqs > 0 else 0.0,
            "prefix_tokens_reused_per_hit": reused / hits if hits > 0 else 0.0,
        }

    def serving_latency_fleet(self) -> Dict[str, float]:
        """Fleet TTFT/ITL percentiles from SUMMED per-server bucket
        counts (the histogram form of the ratio-of-sums rule): merging
        raw buckets yields the true fleet distribution, which averaged
        per-server percentiles do not."""
        from areal_tpu.base.latency import (
            merge_counts, percentile_from_counts,
        )

        ttft = merge_counts(self._server_ttft_hist.values())
        itl = merge_counts(self._server_itl_hist.values())
        return {
            "ttft_p50_ms": percentile_from_counts(ttft, 50.0),
            "ttft_p99_ms": percentile_from_counts(ttft, 99.0),
            "itl_p50_ms": percentile_from_counts(itl, 50.0),
            "itl_p99_ms": percentile_from_counts(itl, 99.0),
            "ttft_count": float(sum(ttft)),
            "itl_count": float(sum(itl)),
            "load_shed_total": sum(self._server_shed_total.values()),
        }

    def is_staled(self) -> bool:
        """Staleness gate (reference gserver_manager.py:351-366): if this
        rollout trained at the version implied by samples already produced,
        would it be more than max_head_offpolicyness behind?"""
        global_samples = max(
            self._training_samples(),
            self.rollout_stat.submitted,
        )
        expected_version = global_samples // self.cfg.train_batch_size
        return (
            expected_version - self.weight_version
            > self.cfg.max_head_offpolicyness
        )

    # ------------------------------------------------------------------
    # HTTP endpoints
    # ------------------------------------------------------------------

    def _serve_http(self):
        asyncio.set_event_loop(self._http_loop)
        app = web.Application()
        app.router.add_post("/schedule_request", self._h_schedule)
        app.router.add_post("/allocate_rollout", self._h_allocate)
        app.router.add_post("/finish_rollout", self._h_finish)
        app.router.add_post("/drain_server", self._h_drain_server)
        app.router.add_get("/status", self._h_status)
        runner = web.AppRunner(app)
        self._http_loop.run_until_complete(runner.setup())
        host = network.gethostip()
        port = network.find_free_port()
        self._http_loop.run_until_complete(web.TCPSite(runner, host, port).start())
        self.address = f"http://{host}:{port}"
        self._http_ready.set()
        self._http_loop.run_forever()

    async def _h_schedule(self, request: web.Request) -> web.Response:
        meta = await request.json()
        trace_ctx = tracing.extract_from(meta)
        # Clients report the server a request just failed on; that server
        # leaves rotation immediately (the health registry readmits it
        # once its heartbeat proves it alive and it re-syncs weights).
        failed = meta.get("failed_server_url")
        if failed:
            # Breaker first: eviction clears routing state, but the
            # breaker REMEMBERS — a flapping server that heartbeats its
            # way back keeps failing its way to open and stays
            # unroutable through the cooldown instead of re-entering
            # rotation on every readmission.
            self.breakers.record(failed, ok=False)
            self._mark_unhealthy(failed, "client-reported request failure")
        # A 429 is DELIBERATE load-shedding, never a failure: route
        # around the server for its Retry-After window (sessions with
        # affinity there spill to the least-loaded server) and keep it
        # healthy.
        shed = meta.get("shed_server_url")
        if shed and shed in self.server_urls:
            ra = float(meta.get("shed_retry_after") or 1.0)
            with self._lock:
                self._server_shed_until[shed] = time.monotonic() + ra
                self._server_shed_total[shed] = (
                    self._server_shed_total.get(shed, 0.0) + 1.0
                )
        qid = str(meta.get("qid") or "")
        url, policy, decode_url, kv_source = self._route(meta)
        tracing.event(
            "manager.schedule", ctx=trace_ctx,
            server=url or "", routed=url is not None, policy=policy,
            qid=qid, kv_source=kv_source or "",
        )
        if url is None:
            err = "no healthy generation servers"
            if policy == "no-model-pool":
                err = (
                    f"no healthy generation servers for model "
                    f"{str(meta.get('model') or self.cfg.model_name)!r}"
                )
            return web.json_response(
                {"error": err, "retry_after": 0.5},
                status=503,
            )
        # The version the client staleness-tracks against is the ROUTED
        # server's model's version — in a multi-model fleet the default
        # model's scalar would be the wrong clock for every other pool.
        resp = {
            "url": url,
            "version": self._model_version(self._model_of(url)),
            "policy": policy,
        }
        if kv_source is not None:
            # Global-prefix-index hint: a DIFFERENT server holds this
            # session's KV — the client forwards kv_source into
            # /generate and the routed server pulls the prefix over
            # /kv/{manifest,chunk} instead of re-prefilling.
            resp["kv_source"] = kv_source
        if decode_url is not None:
            # The prefill->decode pairing decision, recorded for the
            # merged timeline (who prefilled, who decoded, why).
            tracing.event(
                "manager.pair", ctx=trace_ctx, qid=qid,
                prefill=url, decode=decode_url,
                prefill_queued_tokens=self._server_queued_toks.get(url, 0.0),
                decode_free_pages=self._server_free_pages.get(
                    decode_url, 0.0),
            )
            resp["decode_url"] = decode_url
        return web.json_response(resp)

    async def _h_allocate(self, request: web.Request) -> web.Response:
        d = await request.json()
        trace_ctx = tracing.extract_from(d)
        worker = str(d.get("worker", "?"))
        reason = None
        with self._lock:
            cap = self.cfg.max_concurrent_rollouts or (1 << 30)
            if self.rollout_stat.running >= cap:
                reason = "capacity"
            elif self.is_staled():
                reason = "staled"
            else:
                self.rollout_stat.submitted += 1
                self.rollout_stat.running += 1
                self._worker_slots[worker] = (
                    self._worker_slots.get(worker, 0) + 1
                )
        tracing.event(
            "manager.allocate", ctx=trace_ctx,
            admitted=reason is None, reason=reason or "",
            version=self.weight_version,
        )
        if reason is not None:
            resp = {"success": False, "reason": reason}
            if reason == "staled":
                resp["version"] = self.weight_version
            return web.json_response(resp)
        return web.json_response({"success": True, "version": self.weight_version})

    async def _h_finish(self, request: web.Request) -> web.Response:
        d = await request.json()
        worker = str(d.get("worker", "?"))
        with self._lock:
            # max(0, ...): a restarted manager starts the counters at
            # zero while pre-restart episodes still report their
            # finishes; going negative would over-admit past capacity
            # and corrupt the staleness gate.
            self.rollout_stat.running = max(0, self.rollout_stat.running - 1)
            n = self._worker_slots.get(worker, 0)
            if n > 1:
                self._worker_slots[worker] = n - 1
            else:
                self._worker_slots.pop(worker, None)
            if d.get("accepted", True):
                self.rollout_stat.accepted += 1
            else:
                # Rejected rollouts give their staleness budget back.
                self.rollout_stat.submitted = max(
                    0, self.rollout_stat.submitted - 1
                )
        return web.json_response({"success": True})

    async def _h_drain_server(self, request: web.Request) -> web.Response:
        """Operator/test hook for drain-then-leave: POST {"url": ...}.
        The autoscaler's scale-in path goes through the same
        _initiate_drain orchestration."""
        d = await request.json()
        res = await self._initiate_drain(
            str(d.get("url") or ""), str(d.get("reason") or "requested")
        )
        return web.json_response(
            res, status=200 if res.get("success") else 409
        )

    async def _h_status(self, request: web.Request) -> web.Response:
        loop = asyncio.get_event_loop()
        gw_tenants = await loop.run_in_executor(None, self.gateway_tenants)
        with self._lock:
            healthy = self._healthy_urls()
            evicted = dict(self._evicted)
            versions = dict(self._server_versions)
            wp_last = dict(self._wp_last)
            roles = {
                u: self._server_roles.get(u, "unified")
                for u in self.server_urls
            }
            pools = {
                "roles": roles,
                # Shard map (None -> unsharded), part of what a
                # successor manager must rebuild bit-for-bit.
                "weight_shards": {
                    u: (
                        f"{s[0]}/{s[1]}"
                        if (s := self._server_shards.get(u)) else None
                    )
                    for u in self.server_urls
                },
                "prefill": sorted(
                    u for u in healthy if roles[u] != "decode"
                ),
                "decode": sorted(
                    u for u in healthy if roles[u] != "prefill"
                ),
                "elastic": sorted(
                    u for u in healthy
                    if self._server_elastic.get(u, False)
                ),
                # Per-pool load signals the routing keys on.
                "queued_prompt_tokens": {
                    u: self._server_queued_toks.get(u, 0.0) for u in healthy
                },
                "kv_pages_free": {
                    u: self._server_free_pages.get(u, 0.0) for u in healthy
                },
                # Fleet KV-handoff totals (ratio-of-sums rule: raw sums).
                "kv_handoff": {
                    "exports": sum(
                        s.get("exports", 0.0)
                        for s in self._server_kv.values()
                    ),
                    "imports": sum(
                        s.get("imports", 0.0)
                        for s in self._server_kv.values()
                    ),
                    "export_bytes": sum(
                        s.get("export_bytes", 0.0)
                        for s in self._server_kv.values()
                    ),
                    "import_bytes": sum(
                        s.get("import_bytes", 0.0)
                        for s in self._server_kv.values()
                    ),
                },
                "reroles": list(self._rerole_log),
            }
            # Tiered KV plane: global prefix index size (by tier) +
            # fleet spill/restore/lost sums (ratio-of-sums rule).
            by_tier: Dict[str, int] = {}
            for ent in self._prefix_index.values():
                t = ent.get("tier", "host")
                by_tier[t] = by_tier.get(t, 0) + 1
            kv_tier = {
                "index_entries": len(self._prefix_index),
                "index_by_tier": by_tier,
                "spills": sum(
                    s.get("spills", 0.0) for s in self._server_kv.values()
                ),
                "restores": sum(
                    s.get("restores", 0.0)
                    for s in self._server_kv.values()
                ),
                "peer_hits": sum(
                    s.get("peer_hits", 0.0)
                    for s in self._server_kv.values()
                ),
                "prefix_lost": sum(
                    s.get("lost", 0.0) for s in self._server_kv.values()
                ),
            }
            # Elastic fleet control plane: membership dynamics + the
            # HA epoch (fleet_controller.py). Everything here is also
            # what the satellite-3 rebuild test diffs across a manager
            # restart (joins/drains/scale logs excepted — history dies
            # with the incarnation by design).
            fleet = {
                "epoch": self._lease.epoch if self._lease else 0,
                "elastic": bool(self.cfg.elastic_fleet),
                "n_members": len(self.server_urls),
                "draining": sorted(self._draining),
                "joining": sorted(
                    u for u in self._evicted if u in self._join_t0
                ),
                "joins": list(self._join_log),
                "drains": list(self._drain_log),
                "autoscale": list(self._scale_log),
            }
            # Multi-model serving plane: per-model pool membership +
            # each pool's OWN weight version (two models cut over
            # independently; the top-level weight_version stays the
            # default model's for legacy readers), and the quarantine
            # ledger (member -> the unregistered model_id it beat with).
            model_pools: Dict[str, Dict] = {}
            for u in self.server_urls:
                mid = self._model_of(u)
                row = model_pools.setdefault(mid, {
                    "servers": [],
                    "healthy": [],
                    "version": self._model_version(mid),
                })
                row["servers"].append(u)
                if u in healthy:
                    row["healthy"].append(u)
            quarantined = dict(self._quarantined)
        return web.json_response(
            {
                "pools": pools,
                "models": model_pools,
                "quarantined": quarantined,
                "kv_tier": kv_tier,
                "fleet": fleet,
                "weight_version": self.weight_version,
                "rollout_stat": self.rollout_stat.as_dict(),
                "servers": self.server_urls,
                "healthy_servers": healthy,
                "evicted_servers": evicted,
                "server_versions": versions,
                "prefix_cache": self.prefix_cache_fleet(),
                # Fleet latency SLOs (merged engine histograms) + the
                # admission-control counters, next to prefix_cache.
                "serving_latency": self.serving_latency_fleet(),
                "load_shed": {
                    "total": sum(self._server_shed_total.values()),
                    "per_server": dict(self._server_shed_total),
                },
                "affinity_entries": len(self._affinity),
                # RPC substrate health (base/rpc.py): this process's
                # areal:rpc_* counters plus the per-peer breaker board
                # the routing pool consults — an "open" entry here IS
                # why a healthy-looking server takes no traffic.
                "rpc": {
                    "stats": rpc.stats.snapshot(),
                    "breakers": self.breakers.snapshot(),
                    "open": self.breakers.open_peers(),
                },
                # Last tree fanout: per-server transfer vs cutover ms
                # (separate by design), the planned tree, and any
                # evictions it caused. Empty when the plane is off.
                "weight_plane": wp_last,
                # Per-tenant gateway usage rows (system/gateway.py),
                # folded from gateway heartbeat payloads. Empty when no
                # gateway is deployed.
                "gateway_tenants": gw_tenants,
            }
        )

    # ------------------------------------------------------------------
    # Elastic pool sizing (disaggregated serving, docs/serving.md)
    # ------------------------------------------------------------------

    def _post_set_role(self, url: str, role: str) -> bool:
        async def _push():
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=15)
            ) as sess:
                async with sess.post(
                    f"{url}/set_role", json={"role": role}
                ) as r:
                    body = await r.json()
                    return bool(body.get("success"))

        try:
            fut = asyncio.run_coroutine_threadsafe(_push(), self._http_loop)
            return fut.result(timeout=20)
        except Exception:
            logger.warning(f"set_role({role}) failed for {url}",
                           exc_info=True)
            return False

    def _rerole(self, url: str, to_role: str, reason: str) -> bool:
        """Flip one elastic server's pool. Routing flips FIRST (under
        the lock) so no new work of the old kind lands during the drain;
        in-flight requests finish under the old behavior — the flip
        itself is just a label, weights stay resident."""
        with self._lock:
            from_role = self._server_roles.get(url, "unified")
            if from_role == to_role:
                return False
            self._rerole_orig.setdefault(url, from_role)
            self._server_roles[url] = to_role
        if not self._post_set_role(url, to_role):
            with self._lock:  # server unreachable: roll the map back
                self._server_roles[url] = from_role
                if self._rerole_orig.get(url) == from_role:
                    self._rerole_orig.pop(url, None)
            return False
        if to_role == self._rerole_orig.get(url):
            # Back to its pre-flip pool: the flip-back completed.
            self._rerole_orig.pop(url, None)
        entry = {
            "t": time.time(), "url": url,
            "from": from_role, "to": to_role, "reason": reason,
        }
        with self._lock:
            self._rerole_log.append(entry)
            del self._rerole_log[:-32]
        self._last_rerole = time.monotonic()
        tracing.event("manager.rerole", server=url,
                      from_role=from_role, to_role=to_role, reason=reason)
        logger.info(f"re-roled {url}: {from_role} -> {to_role} ({reason})")
        return True

    def _maybe_rerole(self):
        """Watermark-driven pool sizing over the elastic (configured-
        unified) servers: prefill queue pressure pulls a server out of
        the decode pool; a drained prefill queue (or a decode free-page
        floor breach) sends it back."""
        cfg = self.cfg
        if not cfg.elastic_pools:
            return
        if time.monotonic() - self._last_rerole < cfg.rerole_cooldown_s:
            return
        with self._lock:
            healthy = self._healthy_urls()
            roles = {u: self._server_roles.get(u, "unified") for u in healthy}
            elastic = {
                u for u in healthy if self._server_elastic.get(u, False)
            }
            queued = dict(self._server_queued_toks)
            free = dict(self._server_free_pages)
            total = dict(self._server_total_pages)
            flipped = {
                u: orig for u, orig in self._rerole_orig.items()
                if u in healthy
            }
        if not healthy:
            return
        prefill_pool = [u for u in healthy if roles[u] != "decode"]
        decode_pool = [u for u in healthy if roles[u] != "prefill"]
        prefill_queue = sum(queued.get(u, 0.0) for u in prefill_pool)
        dec_free = sum(free.get(u, 0.0) for u in decode_pool)
        dec_total = sum(total.get(u, 0.0) for u in decode_pool)
        dec_free_frac = dec_free / dec_total if dec_total > 0 else 1.0

        if (
            prefill_queue >= cfg.prefill_queue_high_tokens
            and dec_free_frac >= cfg.decode_free_page_min_frac
        ):
            # Prompts are queueing: grow the prefill pool from elastic
            # decode-side servers (most free pages = cheapest to take),
            # keeping the decode pool at its floor.
            cands = [
                u for u in decode_pool
                if u in elastic and roles[u] != "prefill"
                and len(decode_pool) - 1 >= cfg.pool_min_decode
            ]
            if cands:
                u = max(cands, key=lambda c: free.get(c, 0.0))
                self._rerole(
                    u, "prefill",
                    f"prefill queue {prefill_queue:.0f} tokens >= "
                    f"{cfg.prefill_queue_high_tokens}",
                )
            return
        if dec_free_frac < cfg.decode_free_page_min_frac:
            # Decode pool starving for pages: pull an elastic prefill
            # server back in.
            cands = [
                u for u in prefill_pool
                if u in elastic and roles[u] != "decode"
                and len(prefill_pool) - 1 >= cfg.pool_min_prefill
            ]
            if cands:
                u = min(cands, key=lambda c: queued.get(c, 0.0))
                self._rerole(
                    u, "decode",
                    f"decode free pages {dec_free_frac:.2f} < "
                    f"{cfg.decode_free_page_min_frac}",
                )
            return
        if prefill_queue <= cfg.prefill_queue_low_tokens and flipped:
            # Pressure gone: return the server we flipped prefill-ward
            # to its original pool (and vice versa).
            for u, orig in sorted(flipped.items()):
                if roles.get(u) != orig:
                    if self._rerole(
                        u, orig,
                        f"prefill queue {prefill_queue:.0f} tokens <= "
                        f"{cfg.prefill_queue_low_tokens}",
                    ):
                        return

    # ------------------------------------------------------------------
    # Weight-update fanout (runs on the worker poll loop)
    # ------------------------------------------------------------------

    def check_new_params(self) -> Optional[str]:
        """Scan the watched models' published version pointers for one
        that moved. Single-model fleets watch only their own
        model_name; multi-model fleets watch every registered id —
        each model's version lives under its OWN names.model_version
        key, so two models publish (and the manager cuts over)
        independently. Sets ``_new_version`` AND ``_new_model`` so the
        fanout targets the right pool."""
        for model in self._model_watch_list():
            try:
                v = int(
                    name_resolve.get(
                        names.model_version(
                            self.cfg.experiment_name,
                            self.cfg.trial_name,
                            model,
                        )
                    )
                )
            except (name_resolve.NameEntryNotFoundError, ValueError):
                continue
            if v <= self._model_version(model):
                continue
            if (
                model != self.cfg.model_name
                and not self._healthy_urls(model)
            ):
                # A non-default model with no routable pool (yet): skip
                # it rather than let its fanout fail-and-retry wedge
                # the scan ahead of models with live pools. The default
                # model keeps the legacy behavior (fanout into an
                # unhealthy fleet raises and retries — that IS the
                # signal the trainer waits on).
                continue
            path = self._current_param_path(model)
            if path is None:
                continue
            self._new_version = v
            self._new_model = model
            return path
        return None

    # ------------------------------------------------------------------
    # Weight-distribution plane (system/weight_plane.py)
    # ------------------------------------------------------------------

    def _weight_plane_origin(
        self, path: str, model: Optional[str] = None
    ) -> Optional[str]:
        """The plane's origin URL for ``model`` (default: the manager's
        own model_name), or None when the plane is disabled. Prefers a
        trainer-side source registered in name_resolve (the dump rank
        serving its own tmpfs/disk bytes); falls back to a
        manager-hosted source over the NFS dump dir — still O(1) NFS
        reads per version (one streaming read here) vs the legacy
        O(n_servers) full re-reads. Sources are PER MODEL: each model's
        checkpoint tree gets its own chunk stream, so one model's
        publish never serves bytes into another's pool."""
        if not getattr(self.cfg, "weight_plane", False):
            return None
        model = model or self.cfg.model_name
        try:
            return name_resolve.get(
                names.weight_plane_source(
                    self.cfg.experiment_name, self.cfg.trial_name,
                    model,
                )
            )
        except name_resolve.NameEntryNotFoundError:
            pass
        if self._own_sources.get(model) is None:
            if path is None:
                # No trainer-side source registered and no dump on disk
                # to self-host one over (e.g. a bootstrap while the
                # trainer is between dumps): no origin, peers only.
                return None
            from areal_tpu.base import network
            from areal_tpu.system.weight_plane import WeightPlaneSource

            # Bind the routable interface, not the 127.0.0.1 default:
            # this URL is handed to generation servers on OTHER hosts.
            self._own_sources[model] = WeightPlaneSource(
                path, chunk_bytes=self.cfg.weight_chunk_bytes,
                host=network.gethostip(),
            ).start()
            logger.info(
                f"weight plane: no trainer-side source registered for "
                f"{model!r}; manager-hosted origin at "
                f"{self._own_sources[model].address} over {path}"
            )
        return self._own_sources[model].address

    def _fetch_plane_manifest(
        self, origin: str, version: int,
        tp_degree: Optional[int] = None, tp_rank: Optional[int] = None,
    ) -> Dict:
        """Pinned-version manifest from the origin, with a short retry:
        model_version publication can race the dump landing on disk.
        ``tp_degree``/``tp_rank`` request one shard group's sliced
        stream; the configured ``weight_wire_dtype`` picks the
        quantized companion stream when armed.

        When the quantized companion is unavailable for this version —
        shard-local trainer dumps never publish it (the wire's scales
        reduce an axis FSDP shards, weight_transfer.py), and legacy
        dumps predate it — the fetch FALLS BACK to the raw wire rather
        than failing every weight update: the client assembles whatever
        wire the manifest declares, so raw is always safe, just more
        bytes on the fanout."""
        import urllib.error

        from areal_tpu.engine.weight_client import fetch_manifest

        wire = getattr(self.cfg, "weight_wire_dtype", None)
        deadline = time.monotonic() + 15.0
        while True:
            try:
                return fetch_manifest(
                    origin, version=version, timeout=5.0,
                    wire=wire, tp_degree=tp_degree, tp_rank=tp_rank,
                )
            except Exception as e:
                # Only a definitive MISS (origin answered 404) justifies
                # probing raw: the dump writes the wire companion BEFORE
                # the manifest, so a 404'd wire plus a fetchable RAW
                # stream for this version proves the wire will never
                # exist (sharded trainer dumps / legacy dumps) — fall
                # back instead of burning the retry budget. Transient
                # failures (timeouts, dropped connections) keep retrying
                # the configured wire: downgrading on those would ship
                # ~2x the bytes over the fanout for no reason.
                wire_missing = (
                    wire is not None
                    and isinstance(e, urllib.error.HTTPError)
                    and e.code == 404
                )
                if wire_missing:
                    try:
                        man = fetch_manifest(
                            origin, version=version, timeout=5.0,
                            tp_degree=tp_degree, tp_rank=tp_rank,
                        )
                        logger.warning(
                            f"weight plane: no {wire!r}-wire stream for "
                            f"v{version} (sharded trainer dumps publish "
                            f"raw only); falling back to the raw wire"
                        )
                        return man
                    except Exception:
                        pass  # dump still landing: retry the wire
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)

    async def _post_distribute(self, sess, url, parent, payload, span):
        edge_span = tracing.start_span(
            "manager.weight_update.fetch",
            ctx=span.ctx if span else None,
            server=url, parent=parent,
        )
        try:
            # Fanout hop under the wire deadline rule (base/rpc.py):
            # the transfer inherits the wave's remaining flush budget,
            # so a wedged edge fails inside the wave instead of
            # outliving it.
            dl = rpc.Deadline.after(self.cfg.flush_request_timeout)
            async with sess.post(
                f"{url}/distribute_weights",
                headers=dl.headers(),
                json=tracing.inject_ctx_into(
                    dict(payload),
                    edge_span.ctx if edge_span
                    else (span.ctx if span else None),
                ),
            ) as r:
                body = await r.json()
            ok = bool(body.get("success"))
        except Exception as e:
            ok, body = False, {"error": repr(e)}
        self.breakers.record(url, ok=ok)
        if edge_span is not None:
            edge_span.end(
                ok=ok,
                transfer_ms=float(body.get("transfer_ms") or 0.0),
                verify_ms=float(body.get("verify_ms") or 0.0),
            )
        return url, ok, body

    async def _post_cutover(self, sess, url, version, span):
        cut_span = tracing.start_span(
            "manager.weight_update.cutover",
            ctx=span.ctx if span else None, server=url,
        )
        try:
            dl = rpc.Deadline.after(self.cfg.flush_request_timeout)
            async with sess.post(
                f"{url}/cutover_weights",
                headers=dl.headers(),
                json=tracing.inject_ctx_into(
                    {"version": version, "allow_interrupt": True,
                     "budget_s": self.cfg.weight_cutover_budget_s},
                    cut_span.ctx if cut_span
                    else (span.ctx if span else None),
                ),
            ) as r:
                body = await r.json()
            ok = bool(body.get("success"))
        except Exception as e:
            ok, body = False, {"error": repr(e)}
        self.breakers.record(url, ok=ok)
        if cut_span is not None:
            cut_span.end(
                ok=ok, cutover_ms=float(body.get("cutover_ms") or 0.0),
                within_budget=bool(body.get("within_budget", True)),
            )
        return url, ok, body

    def _plane_update_weights(self, origin: str):
        """Tree fanout over the distribution plane, wave by wave.

        Re-fanout on failure: an edge whose planned parent failed (or
        died mid-transfer, PR 1 health) is re-parented onto a surviving
        holder — the origin only as last resort — so one dead peer
        costs its own subtree a hop, not a full origin re-upload. After
        the transfer completes fleet-wide, every holder cuts over
        concurrently: one short interrupt window per server, measured
        separately from transfer."""
        faults.maybe_fail("manager.plane_fanout")
        from areal_tpu.system.weight_plane import group_by_shard, plan_fanout

        t_start = time.monotonic()
        version = self._new_version
        model = self._new_model
        # Fanout targets are the publishing model's OWN pool: model A's
        # cutover must never interrupt (or restream into) model B.
        targets = self._healthy_urls(
            model if getattr(self.cfg, "multi_model", False) else None
        )
        if not targets:
            raise RuntimeError(
                f"weight-plane fanout: no healthy generation servers "
                f"for model {model!r}"
            )
        fanout_span = tracing.start_span(
            "manager.weight_update", version=version,
            n_targets=len(targets), plane=True,
        )
        successes: List[str] = []
        failures: Dict[str, str] = {}
        transfer_ms: Dict[str, float] = {}
        cutover_ms: Dict[str, float] = {}
        ready: List[str] = []
        try:
            # Shard-aware fanout: servers holding the same (degree,
            # rank) slice form a peer group with its OWN sliced chunk
            # stream, fanout tree, and re-parent pool — a rank-0 holder
            # can never feed a rank-1 fetcher. Unsharded fleets collapse
            # to one (1, 0) group, byte-identical to the PR 5 behavior.
            # Σ over groups of shard bytes ≈ one full payload, so the
            # O(1)-origin invariant is preserved per version.
            groups = group_by_shard(
                targets, {u: self._server_shards.get(u) for u in targets}
            )
            plans = {}  # key -> {"man", "waves", "ready": [urls]}
            merged_waves: List[List[Tuple[str, str]]] = []
            for key in sorted(groups):
                degree, rank = key
                man = self._fetch_plane_manifest(
                    origin, version,
                    tp_degree=degree if degree > 1 else None,
                    tp_rank=rank if degree > 1 else None,
                )
                g_waves = plan_fanout(
                    origin, groups[key], self.cfg.weight_fanout_degree
                )
                plans[key] = {"man": man, "waves": g_waves, "ready": []}
                for i, w in enumerate(g_waves):
                    while len(merged_waves) <= i:
                        merged_waves.append([])
                    merged_waves[i].extend((u, p, key) for u, p in w)
            waves = merged_waves

            async def _run_wave(wave):
                async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(
                        # Headroom over the server-side fetch deadline
                        # (deadline_s below): a transfer that finishes
                        # just inside its deadline must not be timed out
                        # client-side — that would mark a READY server
                        # 'prefetch failed' and evict it healthy.
                        total=self.cfg.flush_request_timeout + 10
                    )
                ) as sess:
                    tasks = []
                    for url, parent, key in wave:
                        # Re-parent onto a surviving SAME-SHARD holder
                        # when the planned parent never reached READY.
                        g_ready = plans[key]["ready"]
                        eff = parent
                        if eff != origin and eff not in g_ready:
                            eff = g_ready[0] if g_ready else origin
                        upstreams = (
                            [eff]
                            + [u for u in g_ready if u != eff][:2]
                            + ([origin] if eff != origin else [])
                        )
                        tasks.append(self._post_distribute(
                            sess, url, eff,
                            {"version": version,
                             "manifest": plans[key]["man"],
                             "upstreams": upstreams, "origin": origin,
                             "deadline_s": self.cfg.flush_request_timeout},
                            fanout_span,
                        ))
                    return await asyncio.gather(*tasks)

            url_group = {
                u: key for key, urls in groups.items() for u in urls
            }
            for wave in waves:
                # Each wave can take a full transfer; keep our lease.
                self._beat()
                fut = asyncio.run_coroutine_threadsafe(
                    _run_wave(wave), self._http_loop
                )
                for url, ok, body in self._await_fut(
                    fut, self.cfg.flush_request_timeout + 20
                ):
                    if ok:
                        ready.append(url)
                        plans[url_group[url]]["ready"].append(url)
                        transfer_ms[url] = float(
                            body.get("transfer_ms") or 0.0
                        )
                    elif body.get("weight_shard"):
                        # Shard-spec mismatch 409: OUR map was stale
                        # (fanout raced the server's first heartbeat),
                        # not a sick server. Learn the spec it reported
                        # and leave it healthy — the next fanout plans
                        # it into the right group.
                        ws = body["weight_shard"]
                        spec = (int(ws[0]), int(ws[1]))
                        self._server_shards[url] = (
                            None if spec == (0, 1) else spec
                        )
                        logger.warning(
                            f"weight plane v{version}: {url} holds "
                            f"shard {spec[0]}/{spec[1]}, not "
                            f"{url_group[url]}; corrected for the "
                            f"next fanout"
                        )
                    else:
                        failures[url] = f"prefetch failed: {body}"
            if not ready:
                raise RuntimeError(
                    f"weight plane v{version}: no server prefetched: "
                    f"{failures}"
                )

            # Out-wait the server-side engine cutover timeout
            # (generation_server: max(120, budget*10)) with headroom —
            # a client timeout below it would evict a server whose
            # slow-but-successful cutover is already serving the new
            # version (the hazard _run_wave's own headroom guards).
            cut_total = max(
                self.cfg.flush_request_timeout, 120.0,
                self.cfg.weight_cutover_budget_s * 10.0,
            ) + 10

            async def _run_cutovers():
                async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=cut_total)
                ) as sess:
                    return await asyncio.gather(*[
                        self._post_cutover(sess, u, version, fanout_span)
                        for u in ready
                    ])

            self._beat()
            fut = asyncio.run_coroutine_threadsafe(
                _run_cutovers(), self._http_loop
            )
            for url, ok, body in self._await_fut(fut, cut_total + 10):
                if ok:
                    successes.append(url)
                    cutover_ms[url] = float(body.get("cutover_ms") or 0.0)
                else:
                    failures[url] = f"cutover failed: {body}"
            if not successes:
                raise RuntimeError(
                    f"weight plane v{version}: no server cut over: "
                    f"{failures}"
                )
        finally:
            if fanout_span is not None:
                fanout_span.end(
                    n_success=len(successes), n_failed=len(failures)
                )
        for u, reason in failures.items():
            self._mark_unhealthy(u, f"weight plane: {reason}")
        with self._lock:
            self._set_model_version(model, version)
            for u in successes:
                self._server_versions[u] = version
            self.last_weight_sync_s = time.monotonic() - t_start
            any_man = next(iter(plans.values()))["man"]
            self._wp_last = {
                "version": version,
                "model": model,
                "origin": origin,
                "tree": [[[u, p] for u, p, _ in w] for w in waves],
                # Sum-of-streams view so the pair stays coherent:
                # total_bytes / n_chunks describe what the origin serves
                # per version across ALL groups (for an unsharded fleet
                # that IS the full manifest, byte-identical to PR 5).
                "total_bytes": sum(
                    int(g["man"]["total_bytes"]) for g in plans.values()
                ),
                "n_chunks": sum(
                    int(g["man"]["n_chunks"]) for g in plans.values()
                ),
                "wire": any_man.get("wire", "raw"),
                "groups": {
                    f"{key[1]}/{key[0]}": {
                        "servers": list(urls),
                        "shard_bytes": int(plans[key]["man"]["total_bytes"]),
                        "n_chunks": int(plans[key]["man"]["n_chunks"]),
                    }
                    for key, urls in groups.items()
                },
                "transfer_ms": dict(transfer_ms),
                "cutover_ms": dict(cutover_ms),
                "failures": dict(failures),
                "sync_s": self.last_weight_sync_s,
            }
        lvl = logger.warning if failures else logger.info
        lvl(
            f"weight plane v{version}: {len(successes)}/{len(targets)} "
            f"servers in {self.last_weight_sync_s:.3f}s "
            f"(transfer max {max(transfer_ms.values(), default=0):.1f}ms, "
            f"cutover max {max(cutover_ms.values(), default=0):.1f}ms"
            + (f"; evicted {sorted(failures)}" if failures else "")
            + ")"
        )

    def flush_requests_and_update_weights(self, path: str):
        """Quorum-based fanout: push the new version to every HEALTHY
        server; the step proceeds when at least one succeeds. Failed
        servers are evicted (they re-sync on readmission), so a single
        dead server degrades throughput instead of aborting training.

        With the weight plane enabled this dispatches to the streaming
        tree fanout instead; the legacy NFS broadcast below stays both
        as the default and as the re-sync path's mechanism. In a
        multi-model fleet both paths target only the publishing model's
        pool (check_new_params recorded it in ``_new_model``)."""
        model = self._new_model
        origin = self._weight_plane_origin(path, model)
        if origin is not None:
            return self._plane_update_weights(origin)
        t_start = time.monotonic()
        targets = self._healthy_urls(
            model if getattr(self.cfg, "multi_model", False) else None
        )
        if not targets:
            raise RuntimeError(
                f"weight-update fanout: no healthy generation servers "
                f"for model {model!r}"
            )
        load_stats: list = []
        successes: List[str] = []
        failures: Dict[str, str] = {}
        fanout_span = tracing.start_span(
            "manager.weight_update", version=self._new_version,
            n_targets=len(targets),
        )

        async def _update():
            await faults.maybe_fail_async("manager.fanout")
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.cfg.flush_request_timeout)
            ) as sess:
                tasks = [
                    sess.post(
                        f"{u}/update_weights_from_disk",
                        json=tracing.inject_ctx_into(
                            {
                                "model_path": path,
                                "allow_interrupt": True,
                                # Pin the engines to the trainer's
                                # published version so routing/staleness
                                # accounting agree.
                                "version": self._new_version,
                            },
                            fanout_span.ctx if fanout_span else None,
                        ),
                    )
                    for u in targets
                ]
                resps = await asyncio.gather(*tasks, return_exceptions=True)
                for u, r in zip(targets, resps):
                    if isinstance(r, Exception):
                        failures[u] = repr(r)
                        continue
                    body = await r.json()
                    if not body.get("success"):
                        failures[u] = f"rejected: {body}"
                        continue
                    successes.append(u)
                    load_stats.append(
                        (body.get("source", "?"), float(body.get("load_s", 0.0)))
                    )

        try:
            fut = asyncio.run_coroutine_threadsafe(_update(), self._http_loop)
            self._await_fut(fut, self.cfg.flush_request_timeout + 10)
        finally:
            if fanout_span is not None:
                fanout_span.end(
                    n_success=len(successes), n_failed=len(failures)
                )
        if not successes:
            # No quorum: weight_version stays put so the next poll
            # retries the (idempotent, version-pinned) fanout.
            raise RuntimeError(
                f"weight update v{self._new_version} reached no server: "
                f"{failures}"
            )
        for u, reason in failures.items():
            self._mark_unhealthy(u, f"weight update failed: {reason}")
        with self._lock:
            self._set_model_version(model, self._new_version)
            for u in successes:
                self._server_versions[u] = self._new_version
            self.last_weight_sync_s = time.monotonic() - t_start
        # Sync latency is the async-RL staleness floor (reference bar:
        # <3 s/transfer, blog/AReaL_v0_2.md:52-54) — always logged.
        if failures:
            logger.warning(
                f"degraded weight-update fanout to v{self._new_version}: "
                f"{len(successes)}/{len(targets)} servers in "
                f"{self.last_weight_sync_s:.3f}s; evicted {sorted(failures)}"
            )
        else:
            logger.info(
                f"all servers updated to weight version {self._new_version} "
                f"in {self.last_weight_sync_s:.3f}s "
                f"(loads: {', '.join(f'{s} {t:.3f}s' for s, t in load_stats)})"
            )

    async def _poll_metrics(self):
        async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=5)
        ) as sess:
            # Evicted servers are skipped: polling a dead endpoint costs a
            # 5s timeout per tick and the health registry already owns
            # their lifecycle. Draining servers ARE polled — their kv
            # index stays pullable until they depart.
            from areal_tpu.base.latency import decode_counts

            for u in self._live_urls():
                try:
                    async with sess.get(f"{u}/metrics") as r:
                        text = await r.text()
                    # Regression note: this chain used to startswith-
                    # match raw literals, the prefix-ambiguity class
                    # the metrics-registry checker now flags
                    # ("areal:role" needed a hand-added trailing space
                    # to dodge it). parse_line splits on the declared
                    # EXACT name, and every branch references the
                    # registry constant, so a renamed /metrics line is
                    # a lint failure here instead of a silent zero.
                    for line in text.splitlines():
                        parsed = mreg.parse_line(line)
                        if parsed is None:
                            continue
                        name, val = parsed
                        if name == mreg.NUM_USED_TOKENS:
                            self._server_tokens[u] = float(val)
                            # Fresh snapshot: the since-last-poll
                            # in-flight fold restarts from zero.
                            self._server_tokens_pending[u] = 0.0
                        elif name == mreg.NUM_RUNNING_REQS:
                            self._server_reqs[u] = int(float(val))
                        elif name == mreg.LOAD_SHED_TOTAL:
                            self._server_shed_total[u] = float(val)
                        elif name == mreg.TTFT_HIST:
                            self._server_ttft_hist[u] = decode_counts(val)
                        elif name == mreg.ITL_HIST:
                            self._server_itl_hist[u] = decode_counts(val)
                        elif name == mreg.TOTAL_GENERATED_TOKENS:
                            self._server_gen_totals[u] = float(val)
                        elif name == mreg.PREFIX_CACHE_HITS:
                            self._server_prefix_hits[u] = float(val)
                        elif name == mreg.PREFIX_TOKENS_REUSED:
                            self._server_prefix_reused[u] = float(val)
                        elif name == mreg.TOTAL_REQUESTS:
                            self._server_gen_reqs[u] = float(val)
                        elif name == mreg.SPEC_EMITTED_TOKENS:
                            self._server_spec_emitted[u] = float(val)
                        elif name == mreg.SPEC_ACTIVE_STEPS:
                            self._server_spec_steps[u] = float(val)
                        elif name == mreg.QUEUED_PROMPT_TOKENS:
                            self._server_queued_toks[u] = float(val)
                        elif name == mreg.KV_PAGES_FREE:
                            self._server_free_pages[u] = float(val)
                        elif name == mreg.KV_PAGES_TOTAL:
                            self._server_total_pages[u] = float(val)
                        elif name == mreg.ROLE:
                            role = val
                            # The sizer's view wins for servers it
                            # re-roled until the server's own surface
                            # catches up (it does, on the next beat).
                            if u not in self._rerole_orig or (
                                role == self._server_roles.get(u)
                            ):
                                self._server_roles[u] = role
                        elif name == mreg.ELASTIC:
                            self._server_elastic[u] = float(val) > 0.5
                        elif name == mreg.WEIGHT_SHARD:
                            # Second source besides the heartbeat: a
                            # fanout racing a server's first beat must
                            # not plan it into the unsharded group.
                            if "/" in val:
                                r_s, d_s = val.split("/", 1)
                                self._server_shards[u] = (
                                    int(r_s), int(d_s)
                                )
                        elif name == mreg.KV_EXPORT_TOTAL:
                            self._server_kv.setdefault(u, {})["exports"] = (
                                float(val)
                            )
                        elif name == mreg.KV_EXPORT_BYTES:
                            self._server_kv.setdefault(u, {})[
                                "export_bytes"] = float(val)
                        elif name == mreg.KV_IMPORT_TOTAL:
                            self._server_kv.setdefault(u, {})["imports"] = (
                                float(val)
                            )
                        elif name == mreg.KV_IMPORT_BYTES:
                            self._server_kv.setdefault(u, {})[
                                "import_bytes"] = float(val)
                        elif name == mreg.LAST_KV_TRANSFER_MS:
                            self._server_kv.setdefault(u, {})[
                                "last_transfer_ms"] = float(val)
                        elif name == mreg.KV_SPILL_TOTAL:
                            self._server_kv.setdefault(u, {})["spills"] = (
                                float(val)
                            )
                        elif name == mreg.KV_RESTORE_TOTAL:
                            self._server_kv.setdefault(u, {})["restores"] = (
                                float(val)
                            )
                        elif name == mreg.KV_PREFIX_LOST_TOTAL:
                            self._server_kv.setdefault(u, {})["lost"] = (
                                float(val)
                            )
                        elif name == mreg.KV_TIER_PEER_HITS:
                            self._server_kv.setdefault(u, {})[
                                "peer_hits"] = float(val)
                    if self._kv_index_size:
                        await self._poll_kv_index(sess, u)
                    # A served /metrics clears stray strikes on a
                    # HEALTHY breaker only. It must never close a
                    # tripped one: a wedged engine whose HTTP loop
                    # still answers /metrics would otherwise re-enter
                    # rotation every poll interval — closing a tripped
                    # breaker takes a DATA-PLANE success (fanout/
                    # cutover record) or the peer's removal.
                    br = self.breakers.breaker(u)
                    if br.state() == rpc.STATE_CLOSED:
                        br.record_success()
                except Exception:
                    self.breakers.record(u, ok=False)
                    logger.warning(f"metrics poll failed for {u}")

    async def _poll_kv_index(self, sess, u: str):
        """Fold one server's /kv/index advertisement into the global
        prefix index: entries it newly holds point at it; entries it
        stopped advertising (consumed, aged out) are dropped if they
        still pointed at it; the map stays LRU-bounded."""
        try:
            async with sess.get(f"{u}/kv/index") as r:
                if r.status != 200:
                    return
                body = await r.json()
        except Exception:
            return
        held = body.get("held") or []
        with self._lock:
            prev = self._server_kv_index.get(u) or set()
            now_qids = set()
            for e in held:
                qid = str(e.get("qid") or "")
                if not qid:
                    continue
                now_qids.add(qid)
                self._prefix_index.pop(qid, None)
                self._prefix_index[qid] = {
                    "url": u,
                    "tier": str(e.get("tier") or "host"),
                    "n_tokens": int(e.get("n_tokens") or 0),
                    "version": int(e.get("version", -1)),
                }
            for qid in prev - now_qids:
                ent = self._prefix_index.get(qid)
                if ent is not None and ent.get("url") == u:
                    self._prefix_index.pop(qid, None)
            self._server_kv_index[u] = now_qids
            while len(self._prefix_index) > self._kv_index_size:
                old_qid, old_ent = self._prefix_index.popitem(last=False)
                s = self._server_kv_index.get(old_ent.get("url"))
                if s is not None:
                    s.discard(old_qid)

    def _poll(self) -> Optional[PollResult]:
        try:
            status = name_resolve.get(
                names.experiment_status(
                    self.cfg.experiment_name, self.cfg.trial_name
                )
            )
            if status in ("COMPLETE", "ABORT"):
                return None
        except name_resolve.NameEntryNotFoundError:
            pass

        # Staleness-gate input, fetched HERE (poll thread) so the HTTP
        # loop's is_staled() never does file I/O.
        self._refresh_training_samples()

        # HA lease renewal (rate-limited): a False return means a
        # successor fenced us with a higher epoch — stand down instead
        # of dueling its routing state.
        if self._lease is not None and not self._lease.renew(
            self.weight_version
        ):
            return None

        # Drains that outlive their deadline are EVICTED, not returned
        # to routing: a drain cannot be cancelled server-side — the
        # server keeps shedding 429 and will exit when its migration
        # finishes — so "rolling back" would hand traffic to a server
        # that refuses all of it. It stays in _draining so readmission
        # cannot resurrect it; the graceful-stop marker (or death) is
        # the terminal transition either way.
        now = time.monotonic()
        with self._lock:
            expired_drains = [
                u for u, d in self._drain_deadline.items()
                if now > d and u in self.server_urls
            ]
            for u in expired_drains:
                self._healthy.discard(u)
                self._evicted[u] = "drain timed out; awaiting departure"
                # Same ONE cleanup as every other eviction (affinity,
                # prefix index, load rows) — then re-assert draining,
                # which _forget_server cleared: readmission must keep
                # skipping this server until it departs or dies.
                self._forget_server(u)
                self._draining.add(u)
        for u in expired_drains:
            logger.warning(
                f"drain of {u} exceeded drain_timeout_s; evicted while "
                f"it finishes quiescing (it cannot take traffic again)"
            )

        # Health registry: evict dead servers, readmit returning ones.
        if time.monotonic() - self._last_health_poll > self.cfg.health_check_interval:
            if getattr(self.cfg, "multi_model", False):
                # Registry re-read on the same cadence (one subtree
                # walk): models registered after boot enter the watch
                # list and the adoption gate without a restart.
                self._refresh_model_set()
            try:
                self._poll_health()
            except Exception:
                logger.warning("health poll failed", exc_info=True)
            self._last_health_poll = time.monotonic()

        path = self.check_new_params()
        if path is not None:
            try:
                self.flush_requests_and_update_weights(path)
                # Persist the new version immediately: a successor
                # inheriting the lease must not re-fanout a landed
                # version (the fanout IS idempotent, but re-syncing a
                # whole healthy fleet is a multi-second routing stall).
                if self._lease is not None:
                    self._lease.renew(self.weight_version, force=True)
            except Exception:
                # Transient server failure: weight_version stays put, so the
                # next poll retries the (idempotent, version-pinned) fanout.
                logger.warning("weight-update fanout failed; will retry",
                               exc_info=True)
                time.sleep(1.0)
            return PollResult(batch_count=1)
        if time.monotonic() - self._last_metrics_poll > 2.0:
            fut = asyncio.run_coroutine_threadsafe(
                self._poll_metrics(), self._http_loop
            )
            try:
                fut.result(timeout=10)
            except Exception:
                pass
            self._last_metrics_poll = time.monotonic()
            # Elastic pool sizing rides the fresh load snapshot; the
            # autoscaler one level up turns the same watermarks into
            # launch/drain actions.
            try:
                self._maybe_rerole()
            except Exception:
                logger.warning("elastic rerole pass failed", exc_info=True)
            try:
                self._maybe_autoscale()
            except Exception:
                logger.warning("autoscale pass failed", exc_info=True)
        # Periodic generation-throughput log (reference
        # gserver_manager.py:279-285): interval tokens/s over all servers
        # plus the rollout counters.
        now = time.monotonic()
        if now - self._last_throughput_log > self._throughput_log_interval:
            total_gen = sum(self._server_gen_totals.values())
            dt = now - self._last_throughput_log
            # Clamped: a server restarting in place (counters reset to 0
            # at the same url) can briefly shrink the fleet sum.
            tps = max(0.0, total_gen - self._last_gen_total) / dt
            with self._lock:
                rs = self.rollout_stat.as_dict()
            pc = self.prefix_cache_fleet()
            logger.info(
                f"generation throughput: {tps:.0f} tokens/s "
                f"(total {total_gen:.0f}) rollouts={rs} "
                f"weight_version={self.weight_version} "
                f"prefix_cache_hits={pc['prefix_cache_hits']:.0f} "
                f"prefix_tokens_reused={pc['prefix_tokens_reused']:.0f} "
                f"prefix_cache_hit_rate={pc['prefix_cache_hit_rate']:.3f} "
                f"prefix_tokens_reused_per_hit="
                f"{pc['prefix_tokens_reused_per_hit']:.1f}"
                + (
                    # Realized fleet speculation yield: ratio of SUMS
                    # (total emitted tokens / total active decode steps),
                    # so busy servers weigh in proportionally; absent
                    # when speculation is off fleet-wide.
                    f" spec_tokens_per_step="
                    f"{sum(self._server_spec_emitted.values()) / steps:.2f}"
                    if (steps := sum(self._server_spec_steps.values())) > 0
                    else ""
                )
            )
            self._last_gen_total = total_gen
            self._last_throughput_log = now
        time.sleep(0.05)
        return PollResult(batch_count=0)

    def _exit_hook(self):
        try:
            for src in self._own_sources.values():
                if src is not None:
                    src.close()
            self._http_loop.call_soon_threadsafe(self._http_loop.stop)
            self._http_thread.join(timeout=5)
        except Exception:
            pass
