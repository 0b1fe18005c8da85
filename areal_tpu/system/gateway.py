"""Multi-tenant streaming inference gateway (docs/serving.md).

The fleet's public front door: an OpenAI-compatible HTTP surface
(``POST /v1/completions``, ``POST /v1/chat/completions``, SSE token
streaming) that sits in front of the gserver manager and makes the
serving plane safely shareable by untrusted tenants:

- **auth**: Bearer API keys map to tenants (AREAL_GW_TENANTS); an
  unknown key is a clean 401, never a routed request;
- **model routing** (multi-model fleets): when AREAL_GW_MODELS names
  the fleet's model ids, the OpenAI ``"model"`` request field is
  resolved against them — an unknown model is a 404, a model the
  tenant is not entitled to (the optional 7th ``models=a|b`` tenant
  field) is a 403, and a resolved model rides the scheduling meta so
  the manager routes to that model's server pool only. Usage rows are
  metered per (tenant, model);
- **quotas**: each tenant owns a token bucket (tokens/s + burst) and a
  concurrent-stream cap. A request costing more than the tenant can
  afford is shed with 429 whose Retry-After is derived from the
  tenant's OWN bucket refill — never from fleet state, so one tenant's
  backoff schedule leaks nothing about another's traffic;
- **weighted fair share**: admitted requests queue per tenant and are
  dispatched by deficit round-robin weighted by tenant weight × the
  engine priority class (session continuations cost less, mirroring
  the engine's class-0 admission), so a noisy tenant saturating its
  quota cannot move a well-behaved tenant's p99 TTFT;
- **usage ledger**: per-tenant prompt/completion tokens, TTFT/ITL
  histograms (base/latency.py buckets) and sheds are journaled through
  an append-only usage WAL (system/wal.py, ``areal-gw-usage-wal/v1``)
  with per-request id dedup, so accounting is exactly-once across
  gateway SIGKILL + restart. Surfaced as ``areal:gw_*`` /metrics
  lines, the ``GET /v1/usage`` operator endpoint, and per-tenant rows
  in the manager's /status (via the gateway heartbeat payload);
- **house discipline**: the gateway→manager→server hop speaks the
  PR 14 contract — ``X-Areal-Deadline`` propagation, declared retry /
  breaker-report / shed-backoff policies (base/rpc.py), session
  affinity + ``kv_source`` hints preserved — and the whole request is
  a ``gateway.request`` trace span. Chaos points ``gw.auth`` and
  ``gw.shed`` arm via AREAL_FAULTS.

Internal trainer traffic is NOT a tenant like the others: rollout
workers opting in via AREAL_GW_TRAINER_VIA_GATEWAY route their
``/schedule_request`` hops through this gateway's trainer proxy, which
tags metas with the reserved ``trainer`` tenant, bypasses buckets and
queues entirely (weight ∞, never shed) and forwards to the manager
with the caller's deadline intact. The proxy — and the ``/v1/usage`` +
``/metrics`` operator surfaces — share the tenant-facing listener, so
they are gated by an INTERNAL TOKEN (AREAL_GW_INTERNAL_TOKEN, or a
random one minted at startup) published only through name_resolve
(``names.gateway_internal_token``): rollout workers and operators can
read it, external tenants cannot, and a caller without it gets a 401
instead of a free ride past auth, quotas, and metering. A tenant API
key on ``/v1/usage`` sees exactly its own row.

Prompts arrive as text (byte-level codec, exact for the vocab-256
harness models — api/public.py) or raw token-id lists; production
deployments inject a real tokenizer pair via the ``tokenizer`` hook.

TLS: AREAL_GW_TLS_CERT + AREAL_GW_TLS_KEY terminate TLS on the
tenant-facing listener (the published gateway URL becomes https://).
The production stance is mTLS at the load balancer with the gateway
behind it on a private network; the in-process terminator exists for
single-box deployments and the selftest's self-signed arm.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import hmac
import json
import math
import os
import ssl
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import aiohttp

from areal_tpu.api import public
from areal_tpu.base import (
    env_registry,
    latency,
    logging,
    name_resolve,
    names,
    network,
    rpc,
    tracing,
)
from areal_tpu.base.fault_injection import faults
from areal_tpu.base.health import Heartbeat
from areal_tpu.base.wire_schemas import GATEWAY_V1, GW_USAGE_WAL_V1
from areal_tpu.system import model_registry
from areal_tpu.system.wal import RolloutWAL

logger = logging.getLogger("gateway")

# Reserved internal tenant: the training plane's own rollout traffic.
# Never declared in AREAL_GW_TENANTS, never shed, never queued — the
# trainer proxy tags scheduling metas with it so manager-side
# accounting and /status can attribute load, nothing more.
TRAINER_TENANT = "trainer"

# Header internal callers present on the trainer proxy and operator
# surfaces; the value is the gateway's internal token.
INTERNAL_TOKEN_HEADER = "X-Areal-Gateway-Token"


def resolve_gateway_once(
    experiment_name: str, trial_name: str
) -> Optional[Tuple[str, str]]:
    """One non-blocking discovery pass over the per-instance gateway
    records: returns (url, internal_token) of the lowest-id registered
    instance, or None while no gateway is up. Both records are written
    by the same instance at start(), so the pair is consistent."""
    try:
        keys = name_resolve.find_subtree(
            names.gateway_url_root(experiment_name, trial_name))
    except Exception:
        return None
    for key in sorted(keys):
        gid = key.rsplit("/", 1)[-1]
        try:
            url = name_resolve.get(
                names.gateway_url(experiment_name, trial_name, gid))
            token = name_resolve.get(
                names.gateway_internal_token(
                    experiment_name, trial_name, gid))
        except Exception:
            continue
        if url and token:
            return url, token
    return None


def discover_gateway(
    experiment_name: str, trial_name: str, timeout: float = 300.0
) -> Tuple[str, str]:
    """Block until some gateway instance registers; returns
    (url, internal_token). The trainer-via-gateway rollout path's
    counterpart of name_resolve.wait on the manager key."""
    deadline = time.monotonic() + timeout
    while True:
        got = resolve_gateway_once(experiment_name, trial_name)
        if got is not None:
            return got
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"no gateway registered for "
                f"{experiment_name}/{trial_name} within {timeout:.0f}s"
            )
        time.sleep(0.5)


class Tenant:
    """One paying tenant: identity + quota state.

    Bucket/stream state is only ever touched from the gateway's single
    HTTP event loop, so no locking. ``level`` refills continuously at
    ``tokens_per_s`` up to ``burst``; a request charges
    prompt_len + max_tokens units up front (the worst case it may
    consume — billing afterwards is by actual emission, the bucket is
    purely admission control)."""

    def __init__(self, name: str, api_key: str, weight: float,
                 tokens_per_s: float, burst: float, max_streams: int,
                 models: Optional[frozenset] = None):
        self.name = name
        self.api_key = api_key
        self.weight = float(weight)
        self.tokens_per_s = float(tokens_per_s)
        self.burst = float(burst)
        self.max_streams = int(max_streams)
        # Model entitlements: None = every model the fleet serves;
        # a frozenset restricts the tenant to exactly those model ids
        # (anything else answers 403, never a cross-model route).
        self.models = models
        self.level = float(burst)
        self.stamp = time.monotonic()
        self.active_streams = 0

    def _refill(self, now: float):
        self.level = min(
            self.burst,
            self.level + max(0.0, now - self.stamp) * self.tokens_per_s,
        )
        self.stamp = now

    def time_to_afford(self, cost: float, now: float) -> float:
        """Seconds until THIS tenant's bucket can pay ``cost`` (0.0 if
        it already can). The 429 Retry-After source."""
        self._refill(now)
        if self.level >= cost:
            return 0.0
        if self.tokens_per_s <= 0:
            return 3600.0
        return (cost - self.level) / self.tokens_per_s

    def try_charge(self, cost: float, now: float) -> Optional[float]:
        """Charge the bucket; None on success, else the tenant's own
        seconds-until-affordable (the Retry-After)."""
        wait = self.time_to_afford(cost, now)
        if wait <= 0.0:
            self.level -= cost
            return None
        return wait


def parse_tenant_spec(
    spec: Optional[str],
    known_models: Optional[set] = None,
) -> Dict[str, Tenant]:
    """Parse AREAL_GW_TENANTS: comma list of
    ``name:api_key:weight:tokens_per_s:burst:max_streams`` entries,
    optionally followed by a 7th ``model|model`` entitlement field
    (absent = entitled to every model the fleet serves).
    Raises ValueError on malformed entries, duplicate names, duplicate
    API keys (a shared key would silently bill whichever tenant parses
    last), non-positive quotas, an attempt to redeclare the reserved
    trainer tenant, or — when ``known_models`` is given — an
    entitlement naming a model the fleet does not serve (a typo here
    would silently lock the tenant out or grant nothing; fail at parse
    time instead)."""
    tenants: Dict[str, Tenant] = {}
    keys_seen: Dict[str, str] = {}
    if not spec:
        return tenants
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (6, 7):
            raise ValueError(
                f"bad tenant entry {entry!r}: want "
                f"name:api_key:weight:tokens_per_s:burst:max_streams"
                f"[:model|model...]"
            )
        name, api_key, weight, rate, burst, streams = parts[:6]
        entitled: Optional[frozenset] = None
        if len(parts) == 7 and parts[6].strip():
            models = [m.strip() for m in parts[6].split("|")
                      if m.strip()]
            for m in models:
                model_registry.validate_model_id(m)
                if known_models is not None and m not in known_models:
                    raise ValueError(
                        f"tenant {name!r} entitlement names unknown "
                        f"model {m!r} (fleet serves "
                        f"{sorted(known_models)})"
                    )
            entitled = frozenset(models)
        if not name or not api_key:
            raise ValueError(f"tenant entry {entry!r}: empty name or key")
        if name == TRAINER_TENANT:
            raise ValueError(
                f"tenant name {TRAINER_TENANT!r} is reserved for the "
                f"training plane and may not be declared"
            )
        if name in tenants:
            raise ValueError(f"duplicate tenant {name!r}")
        if api_key in keys_seen:
            raise ValueError(
                f"tenant {name!r} reuses the API key of tenant "
                f"{keys_seen[api_key]!r}: keys must be unique or "
                f"auth cannot attribute traffic"
            )
        keys_seen[api_key] = name
        t = Tenant(name, api_key, float(weight), float(rate),
                   float(burst), int(streams), models=entitled)
        if t.weight <= 0 or t.tokens_per_s <= 0 or t.burst <= 0 \
                or t.max_streams < 1:
            raise ValueError(
                f"tenant {name!r}: weight/tokens_per_s/burst must be "
                f"> 0 and max_streams >= 1"
            )
        tenants[name] = t
    return tenants


class UsageLedger:
    """Exactly-once per-tenant usage accounting over a usage WAL.

    Every billable event (a served request's token counts + latency
    histograms, or a shed) is journaled with a unique request id BEFORE
    it lands in the in-memory rows; restart replays the journal through
    the same ``_apply`` with rid dedup, so a record is counted exactly
    once no matter how many times the gateway dies and replays.
    Thread-safe: the HTTP loop journals through run_in_executor while
    the supervisor thread reads briefs.

    A long-lived gateway must not grow without bound: every
    AREAL_GW_USAGE_COMPACT_EVERY journaled records the WAL is folded
    into ONE aggregated per-tenant ``agg`` record (RolloutWAL.compact —
    the totals the individual records sum to, so a restart replays the
    aggregate plus whatever landed after it into identical rows), and
    the request-id dedup set is aged down to a bounded recent window.
    Disk, replay time, and dedup memory are all O(cadence), not
    O(lifetime traffic)."""

    # Request ids kept for duplicate defence across a compaction; only
    # in-flight retries can legitimately re-present a rid, so a short
    # recency window is enough.
    SEEN_WINDOW = 1024

    def __init__(self, path: str, compact_every: Optional[int] = None):
        self._lock = threading.Lock()
        self._wal = RolloutWAL(path, schema=GW_USAGE_WAL_V1)
        self._seen: set = set()
        self._recent: Deque[str] = collections.deque(
            maxlen=self.SEEN_WINDOW)
        if compact_every is None:
            compact_every = env_registry.get_int(
                "AREAL_GW_USAGE_COMPACT_EVERY")
        self._compact_every = max(0, int(compact_every))
        self._records = 0  # journal records since the last compaction
        # Rows are keyed (tenant, model); model "" is single-model
        # traffic (and every pre-multi-model WAL record replays there).
        self._rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.replayed = 0
        self.dup_dropped = 0
        self.compactions = 0
        for rec in self._wal.replay():
            if self._apply(rec):
                self.replayed += 1
                self._records += 1
            else:
                self.dup_dropped += 1
        # A journal that replayed past the cadence (e.g. a crash loop)
        # compacts immediately instead of carrying the backlog forward.
        self._maybe_compact_locked()

    def _row(self, tenant: str, model: str = "") -> Dict[str, Any]:
        row = self._rows.get((tenant, model))
        if row is None:
            row = {
                "requests": 0,
                "sheds": 0,
                "prompt_tokens": 0,
                "completion_tokens": 0,
                "ttft_counts": [0] * latency.N_BUCKETS,
                "itl_counts": [0] * latency.N_BUCKETS,
            }
            self._rows[(tenant, model)] = row
        return row

    def _apply(self, rec: Dict[str, Any]) -> bool:
        rid = rec.get("rid")
        if not rid or rid in self._seen:
            return False
        self._seen.add(rid)
        self._recent.append(rid)
        if rec.get("kind") == "agg":
            # A compaction record: the summed totals of every
            # individual record it replaced, added wholesale. Keys are
            # "tenant" or "tenant\tmodel" (pre-multi-model aggregates
            # have no tab and land on the "" model row).
            for rkey, agg in (rec.get("rows") or {}).items():
                tenant, _, model = str(rkey).partition("\t")
                row = self._row(tenant, model)
                for k in ("requests", "sheds", "prompt_tokens",
                          "completion_tokens"):
                    row[k] += int(agg.get(k) or 0)
                for key in ("ttft_counts", "itl_counts"):
                    for i, n in enumerate(
                        latency.decode_counts(agg.get(key) or "")
                    ):
                        row[key][i] += n
            return True
        row = self._row(str(rec.get("tenant") or "unknown"),
                        str(rec.get("model") or ""))
        if rec.get("kind") == "shed":
            row["sheds"] += 1
            return True
        row["requests"] += 1
        row["prompt_tokens"] += int(rec.get("prompt_tokens") or 0)
        row["completion_tokens"] += int(rec.get("completion_tokens") or 0)
        if rec.get("ttft_ms") is not None:
            row["ttft_counts"][
                latency.bucket_index(float(rec["ttft_ms"]))
            ] += 1
        itl = latency.decode_counts(rec.get("itl_counts") or "")
        for i, n in enumerate(itl):
            row["itl_counts"][i] += n
        return True

    def _maybe_compact_locked(self):
        """Compact once the cadence is reached; caller holds the lock
        (or is the single-threaded constructor)."""
        if self._compact_every <= 0 \
                or self._records < self._compact_every:
            return
        agg_rid = "agg-" + uuid.uuid4().hex
        rec = {
            "rid": agg_rid,
            "kind": "agg",
            "ts": time.time(),
            "rows": {
                (name if not model else f"{name}\t{model}"): {
                    "requests": r["requests"],
                    "sheds": r["sheds"],
                    "prompt_tokens": r["prompt_tokens"],
                    "completion_tokens": r["completion_tokens"],
                    "ttft_counts": latency.encode_counts(
                        r["ttft_counts"]),
                    "itl_counts": latency.encode_counts(
                        r["itl_counts"]),
                }
                for (name, model), r in self._rows.items()
            },
        }
        # The aggregate IS the current in-memory rows (every applied
        # record was journaled first), so it is NOT re-applied here —
        # it exists purely for the next replay. Append, fsync, then
        # drop everything else: the journal becomes [agg].
        self._wal.append(rec)
        self._wal.sync()
        dropped = self._wal.compact(
            lambda r: r.get("rid") == agg_rid)
        # Age the dedup set down to the recent window (+ the aggregate
        # itself): only in-flight duplicates need defending against.
        self._seen = set(self._recent)
        self._seen.add(agg_rid)
        self._records = 1  # the agg record itself
        self.compactions += 1
        logger.info(
            f"usage WAL compacted: {dropped} records folded into one "
            f"aggregate ({len(rec['rows'])} tenants)"
        )

    def record_usage(self, rid: str, tenant: str, prompt_tokens: int,
                     completion_tokens: int, ttft_ms: Optional[float],
                     itl_counts: Optional[List[int]],
                     model: str = "") -> bool:
        """Journal + count one served request. fsyncs before counting:
        a record is billed iff it is durable (SIGKILL right after the
        response leaves at most the terminal frame unbilled, never a
        double-count)."""
        rec = {
            "rid": rid,
            "kind": "usage",
            "tenant": tenant,
            "model": model,
            "prompt_tokens": int(prompt_tokens),
            "completion_tokens": int(completion_tokens),
            "ttft_ms": None if ttft_ms is None else float(ttft_ms),
            "itl_counts": latency.encode_counts(itl_counts or []),
            "ts": time.time(),
        }
        with self._lock:
            if rid in self._seen:
                self.dup_dropped += 1
                return False
            self._wal.append(rec)
            self._wal.sync()
            applied = self._apply(rec)
            self._records += 1
            self._maybe_compact_locked()
            return applied

    def record_shed(self, rid: str, tenant: str,
                    model: str = "") -> bool:
        rec = {"rid": rid, "kind": "shed", "tenant": tenant,
               "model": model, "ts": time.time()}
        with self._lock:
            if rid in self._seen:
                self.dup_dropped += 1
                return False
            self._wal.append(rec)
            self._wal.sync()
            applied = self._apply(rec)
            self._records += 1
            self._maybe_compact_locked()
            return applied

    def totals(self) -> Tuple[int, int, List[int], List[int]]:
        """(prompt_tokens, completion_tokens, merged ttft counts,
        merged itl counts) across all tenants — the /metrics source."""
        with self._lock:
            rows = list(self._rows.values())
        pt = sum(r["prompt_tokens"] for r in rows)
        ct = sum(r["completion_tokens"] for r in rows)
        ttft = latency.merge_counts([r["ttft_counts"] for r in rows])
        itl = latency.merge_counts([r["itl_counts"] for r in rows])
        return pt, ct, ttft, itl

    @staticmethod
    def _present(requests: int, sheds: int, pt: int, ct: int,
                 ttft: List[int], itl: List[int]) -> Dict[str, Any]:
        return {
            "requests": requests,
            "sheds": sheds,
            "prompt_tokens": pt,
            "completion_tokens": ct,
            "total_tokens": pt + ct,
            "ttft_p50_ms": latency.percentile_from_counts(ttft, 50.0),
            "ttft_p99_ms": latency.percentile_from_counts(ttft, 99.0),
            "itl_p50_ms": latency.percentile_from_counts(itl, 50.0),
            "itl_p99_ms": latency.percentile_from_counts(itl, 99.0),
        }

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant rows with computed percentiles (GET /v1/usage).
        The top-level tenant row aggregates across models (ratio-of-
        sums over merged histogram counts); per-model sub-rows sit
        under ``"models"`` keyed by model id. Single-model traffic
        (model "") contributes only to the aggregate."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            by_tenant: Dict[str, List[Tuple[str, Dict[str, Any]]]] = {}
            for (name, model), r in self._rows.items():
                by_tenant.setdefault(name, []).append((model, r))
            for name, rows in by_tenant.items():
                agg = self._present(
                    sum(r["requests"] for _, r in rows),
                    sum(r["sheds"] for _, r in rows),
                    sum(r["prompt_tokens"] for _, r in rows),
                    sum(r["completion_tokens"] for _, r in rows),
                    latency.merge_counts(
                        [r["ttft_counts"] for _, r in rows]),
                    latency.merge_counts(
                        [r["itl_counts"] for _, r in rows]),
                )
                models = {
                    model: self._present(
                        r["requests"], r["sheds"],
                        r["prompt_tokens"], r["completion_tokens"],
                        r["ttft_counts"], r["itl_counts"])
                    for model, r in rows if model
                }
                if models:
                    agg["models"] = models
                out[name] = agg
        return out

    def brief(self) -> Dict[str, Dict[str, int]]:
        """Compact totals for the heartbeat payload (manager /status).
        Aggregated across models — the wire shape predates the
        multi-model plane and /status consumers sum rows anyway."""
        with self._lock:
            out: Dict[str, Dict[str, int]] = {}
            for (n, _model), r in self._rows.items():
                b = out.setdefault(n, {
                    "requests": 0, "sheds": 0,
                    "prompt_tokens": 0, "completion_tokens": 0,
                })
                for k in b:
                    b[k] += r[k]
            return out

    def close(self):
        with self._lock:
            self._wal.close()


class _QueueItem:
    """One admitted request waiting for a fair-share dispatch slot."""

    __slots__ = ("tenant", "cost", "fut")

    def __init__(self, tenant: str, cost: float, fut: asyncio.Future):
        self.tenant = tenant
        self.cost = cost
        self.fut = fut


class _ServerFailure(RuntimeError):
    def __init__(self, url: str, detail: str):
        super().__init__(f"generate failed on {url}: {detail}")
        self.url = url


class GatewayService:
    """The gateway process: HTTP front + DRR dispatcher + usage ledger
    + health lease (lifecycle mirrors system/reward_executor.py)."""

    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        gateway_id: int = 0,
        port: int = 0,
        manager_addr: Optional[str] = None,
        tenant_spec: Optional[str] = None,
        usage_wal_path: Optional[str] = None,
        fair_share: Optional[bool] = None,
        tokenizer: Optional[Tuple[Callable, Callable]] = None,
        internal_token: Optional[str] = None,
        model_spec: Optional[str] = None,
    ):
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.gateway_id = int(gateway_id)
        self.member = f"gateway/{self.gateway_id}"
        self.manager_addr = manager_addr
        self.request_timeout = env_registry.get_float(
            "AREAL_GW_REQUEST_TIMEOUT_S")
        self.chunk_tokens = max(1, env_registry.get_int(
            "AREAL_GW_CHUNK_TOKENS"))
        self.max_inflight = max(1, env_registry.get_int(
            "AREAL_GW_MAX_INFLIGHT"))
        self.retry_after_floor = env_registry.get_float(
            "AREAL_GW_RETRY_AFTER_FLOOR_S")
        self.fair_share = (
            fair_share if fair_share is not None
            else env_registry.get_bool("AREAL_GW_FAIR_SHARE")
        )
        # Multi-model serving: AREAL_GW_MODELS (or the explicit arg)
        # names the model ids the fleet serves; the first entry is the
        # default a request without a meaningful "model" field maps
        # to. Empty = single-model legacy mode (no model resolution,
        # no model tag on the scheduling meta).
        mspec = (model_spec if model_spec is not None
                 else env_registry.get_str("AREAL_GW_MODELS"))
        self.models: List[str] = []
        for m in (mspec or "").split(","):
            m = m.strip()
            if m and m not in self.models:
                model_registry.validate_model_id(m)
                self.models.append(m)
        self._known_models = set(self.models)
        self.default_model = self.models[0] if self.models else None
        spec = (tenant_spec if tenant_spec is not None
                else env_registry.get_str("AREAL_GW_TENANTS"))
        self.tenants = parse_tenant_spec(
            spec,
            known_models=self._known_models or None,
        )
        self._by_key = {t.api_key: t for t in self.tenants.values()}
        # Internal-surface shared secret (trainer proxy + operator
        # endpoints): explicit arg > env knob > random mint. Published
        # to name_resolve at start() — reachable by rollout workers
        # and operators, never by external tenants.
        if internal_token is None:
            internal_token = env_registry.get_str(
                "AREAL_GW_INTERNAL_TOKEN")
        self.internal_token = internal_token or uuid.uuid4().hex
        # Optional (encode(text)->ids, decode(ids)->text) pair; absent,
        # api/public.py's byte codec applies.
        self.tokenizer = tokenizer
        # TLS termination (AREAL_GW_TLS_CERT/KEY): both knobs set ->
        # the tenant listener serves https and the published discovery
        # URL says so. Production fleets usually terminate mTLS at the
        # load balancer instead (docs/serving.md); exactly one knob
        # set is a config error, not a silent plaintext listener.
        self._tls_cert = env_registry.get_str("AREAL_GW_TLS_CERT")
        self._tls_key = env_registry.get_str("AREAL_GW_TLS_KEY")
        if bool(self._tls_cert) != bool(self._tls_key):
            raise ValueError(
                "AREAL_GW_TLS_CERT and AREAL_GW_TLS_KEY must be set "
                "together (got exactly one)"
            )
        if usage_wal_path is None:
            usage_wal_path = os.path.join(
                tempfile.gettempdir(),
                f"areal_gw_usage_{experiment_name}_{trial_name}"
                f"_{self.gateway_id}.jsonl",
            )
        self.ledger = UsageLedger(usage_wal_path)
        # Declared retry disciplines (base/rpc.py): per-request server
        # failover budget, plus the fleet-wide manager-rediscovery one.
        self._policy = rpc.default_policy(
            attempt_timeout_s=self.request_timeout)
        self._mgr_policy = rpc.rediscovery_policy()
        self.counters: Dict[str, int] = {
            "requests_total": 0,
            "auth_failures_total": 0,
            "shed_total": 0,
            "fairshare_picks_total": 0,
            "upstream_failovers_total": 0,
            "model_rejections_total": 0,
        }
        self._trainer_sched = 0
        # DRR state (event-loop confined).
        self.quantum = 64.0
        self._queues: Dict[str, Deque[_QueueItem]] = {}
        self._fifo: Deque[_QueueItem] = collections.deque()
        self._rr: List[str] = []
        self._deficit: Dict[str, float] = {}
        self._inflight = 0
        self._queue_event: Optional[asyncio.Event] = None
        self._dispatch_task: Optional[asyncio.Task] = None
        self._session: Optional[aiohttp.ClientSession] = None
        self._port = port
        self.address: Optional[str] = None
        self._heartbeat: Optional[Heartbeat] = None
        self._http_loop: Optional[asyncio.AbstractEventLoop] = None
        self._http_ready = threading.Event()
        self._http_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._sup_thread: Optional[threading.Thread] = None
        faults.set_scope(self.member)

    # -- manager discovery ---------------------------------------------

    def _refresh_manager_addr(self):
        """Blocking name_resolve lookup — call via run_in_executor from
        async paths."""
        try:
            addr = name_resolve.get(
                names.gen_server_manager(
                    self.experiment_name, self.trial_name)
            )
        except Exception:
            return
        if addr and addr != self.manager_addr:
            logger.warning(
                f"gserver manager moved {self.manager_addr} -> {addr}"
            )
            self.manager_addr = addr

    async def _sess(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.request_timeout)
            )
        return self._session

    # -- fair-share dispatcher -----------------------------------------

    def _enqueue(self, item: _QueueItem):
        if self.fair_share:
            q = self._queues.get(item.tenant)
            if q is None:
                q = self._queues[item.tenant] = collections.deque()
                if item.tenant not in self._rr:
                    self._rr.append(item.tenant)
            q.append(item)
        else:
            self._fifo.append(item)
        if self._queue_event is not None:
            self._queue_event.set()

    def _release_slot(self):
        self._inflight = max(0, self._inflight - 1)
        if self._queue_event is not None:
            self._queue_event.set()

    def _queue_depth(self) -> int:
        return len(self._fifo) + sum(
            len(q) for q in self._queues.values())

    def _dispatch_one(self) -> bool:
        """Pick and release one queued request. Exact weighted DRR: all
        nonempty queues advance their deficit by the minimum number of
        rounds that makes some head affordable, then that head is
        served — O(tenants) per dispatch, no credit-spin loop."""
        if self._inflight >= self.max_inflight:
            return False
        if not self.fair_share:
            while self._fifo and self._fifo[0].fut.cancelled():
                self._fifo.popleft()
            if not self._fifo:
                return False
            item = self._fifo.popleft()
            self._inflight += 1
            item.fut.set_result(True)
            return True
        nonempty: List[str] = []
        for name in list(self._rr):
            q = self._queues.get(name)
            while q and q[0].fut.cancelled():
                q.popleft()
            if q:
                nonempty.append(name)
            else:
                # Classic DRR: an emptied queue forfeits its credit.
                self._deficit[name] = 0.0
        if not nonempty:
            return False
        if len(nonempty) > 1:
            # Proof the queue actually arbitrated between tenants (the
            # tenant_fairness bench validator keys on this moving).
            self.counters["fairshare_picks_total"] += 1
        best: Optional[str] = None
        best_rounds = 0
        for name in nonempty:
            t = self.tenants.get(name)
            weight = t.weight if t is not None else 1.0
            credit = self.quantum * max(1e-6, weight)
            need = (self._queues[name][0].cost
                    - self._deficit.get(name, 0.0))
            rounds = 0 if need <= 0 else int(math.ceil(need / credit))
            if best is None or rounds < best_rounds:
                best, best_rounds = name, rounds
        if best_rounds > 0:
            for name in nonempty:
                t = self.tenants.get(name)
                weight = t.weight if t is not None else 1.0
                self._deficit[name] = (
                    self._deficit.get(name, 0.0)
                    + best_rounds * self.quantum * max(1e-6, weight)
                )
        item = self._queues[best].popleft()
        self._deficit[best] = max(
            0.0, self._deficit.get(best, 0.0) - item.cost)
        # Served tenant rotates to the back (round-robin tie order).
        try:
            self._rr.remove(best)
            self._rr.append(best)
        except ValueError:
            pass
        self._inflight += 1
        item.fut.set_result(True)
        return True

    async def _dispatch_loop(self):
        while True:
            await self._queue_event.wait()
            self._queue_event.clear()
            while self._dispatch_one():
                pass

    # -- upstream generation -------------------------------------------

    def _model_tag(self, parsed: public.ParsedRequest) -> str:
        """Ledger/meta model id: the resolved model in multi-model
        mode, "" in single-model legacy mode (where parsed.model is
        whatever placeholder the client sent and must not be routed
        or billed as a pool name)."""
        return parsed.model if self._known_models else ""

    async def _schedule(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        sess = await self._sess()
        dl = rpc.Deadline.after(self.request_timeout)
        async with sess.post(
            f"{self.manager_addr}/schedule_request", json=meta,
            headers=dl.headers(),
        ) as r:
            return await r.json()

    async def _generate_chunks(
        self,
        parsed: public.ParsedRequest,
        tenant: Tenant,
        qid: str,
        deadline: rpc.Deadline,
        t_start: float,
        emit,
    ) -> Tuple[List[int], str, Optional[float], List[int], Optional[str]]:
        """Drive one request through the manager chunk by chunk
        (partial_rollout discipline: failover via failed_server_url,
        shed hints, manager rediscovery, session affinity on qid).
        Calls ``await emit(token_ids)`` per successful chunk. Returns
        (output_ids, finish_reason, ttft_ms, itl_counts, error_detail)
        — never raises for upstream exhaustion, so the caller can bill
        what was actually emitted."""
        sess = await self._sess()
        loop = asyncio.get_event_loop()
        acc: List[int] = []
        prev_url, prev_version = "", -1
        failed_url: Optional[str] = None
        shed_url: Optional[str] = None
        shed_ra_hint = 0.0
        retries = 0
        consec_shed = 0
        n_shed = 0
        shed_budget = max(32, self._policy.attempts * 8)
        mgr_fails = 0
        consec_mgr = 0
        ttft_ms: Optional[float] = None
        itl_counts = [0] * latency.N_BUCKETS
        t_last = t_start
        finish = "length"
        error: Optional[str] = None
        budget = parsed.max_tokens
        while budget > 0:
            if deadline.expired():
                break
            meta = tracing.inject_into(dict(
                qid=qid,
                prompt_len=len(parsed.prompt_ids) + len(acc),
                group_size=1,
                new_token_budget=budget,
                previous_server_url=prev_url,
                previous_version=prev_version,
                failed_server_url=failed_url,
                shed_server_url=shed_url,
                shed_retry_after=shed_ra_hint,
                tenant=tenant.name,
                model=self._model_tag(parsed),
            ))
            try:
                sched = await self._schedule(meta)
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                mgr_fails += 1
                consec_mgr += 1
                if mgr_fails > self._mgr_policy.attempts:
                    error = (f"gserver manager unreachable after "
                             f"{mgr_fails} attempts: {e!r}")
                    finish = "error"
                    break
                await loop.run_in_executor(
                    None, self._refresh_manager_addr)
                await asyncio.sleep(self._mgr_policy.backoff(consec_mgr))
                continue
            consec_mgr = 0
            failed_url = None
            shed_url, shed_ra_hint = None, 0.0
            if "url" not in sched:
                retries += 1
                if retries > self._policy.attempts:
                    error = f"no healthy generation servers: {sched}"
                    finish = "error"
                    break
                await asyncio.sleep(self._policy.backoff(
                    retries,
                    retry_after=float(sched.get("retry_after", 0.0)),
                ))
                continue
            url = sched["url"]
            chunk = min(budget, self.chunk_tokens)
            payload = tracing.inject_into(dict(
                qid=qid,
                decode_url=sched.get("decode_url"),
                kv_source=sched.get("kv_source"),
                input_ids=list(parsed.prompt_ids) + acc,
                # Continuations (accumulated prefix or an explicit
                # session) ride engine priority class 0 — same rule as
                # the trainer's partial-rollout client.
                priority=0 if (acc or parsed.session) else 1,
                gconfig=dict(
                    max_new_tokens=chunk,
                    min_new_tokens=0,
                    greedy=parsed.greedy,
                    temperature=parsed.temperature,
                    top_p=parsed.top_p,
                    top_k=-1,
                    stop_token_ids=[],
                ),
            ))
            shed_ra: Optional[float] = None
            try:
                chunk_dl = rpc.Deadline.after(min(
                    self.request_timeout,
                    max(rpc.MIN_ATTEMPT_S, deadline.remaining()),
                ))
                async with sess.post(
                    f"{url}/generate", json=payload,
                    headers=chunk_dl.headers(),
                ) as r:
                    if r.status == 429:
                        try:
                            body = await r.json()
                        except Exception:
                            body = {}
                        shed_ra = float(
                            body.get("retry_after")
                            or r.headers.get("Retry-After")
                            or 1.0
                        )
                    elif r.status != 200:
                        raise _ServerFailure(
                            url, f"{r.status} {await r.text()}")
                    else:
                        out = await r.json()
            except (_ServerFailure, aiohttp.ClientError,
                    asyncio.TimeoutError) as e:
                # Server died mid-chunk: tokens already emitted to the
                # client are safe in acc — the retry resubmits the full
                # prefix through the manager, which routes around the
                # failure. No token is ever emitted (or billed) twice.
                retries += 1
                self.counters["upstream_failovers_total"] += 1
                if retries > self._policy.attempts:
                    error = f"upstream exhausted: {e!r}"
                    finish = "error"
                    break
                failed_url = url
                prev_url, prev_version = "", -1
                logger.warning(
                    f"{qid}: generate failed on {url} ({e!r}); "
                    f"retry {retries}/{self._policy.attempts}"
                )
                await asyncio.sleep(self._policy.backoff(retries))
                continue
            if shed_ra is not None:
                n_shed += 1
                consec_shed += 1
                if n_shed > shed_budget:
                    error = (f"load-shed {n_shed} times; fleet "
                             f"persistently overloaded")
                    finish = "error"
                    break
                shed_url, shed_ra_hint = url, shed_ra
                await asyncio.sleep(
                    rpc.shed_backoff(consec_shed, shed_ra))
                continue
            consec_shed = 0
            toks = [int(t) for t in out.get("output_ids") or []]
            now = time.monotonic()
            if toks:
                if ttft_ms is None:
                    ttft_ms = (now - t_start) * 1000.0
                else:
                    per_tok = (now - t_last) * 1000.0 / len(toks)
                    itl_counts[latency.bucket_index(per_tok)] += len(toks)
            t_last = now
            made_progress = bool(toks)
            acc.extend(toks)
            budget = parsed.max_tokens - len(acc)
            prev_url = url
            prev_version = int(out.get("version_end", -1))
            if toks:
                await emit(toks)
            if not out.get("no_eos", True):
                finish = "stop"
                break
            if not made_progress and not out.get("interrupted", False):
                break
            if budget <= 0:
                break
        return acc, finish, ttft_ms, itl_counts, error

    # -- public handlers -----------------------------------------------

    async def _h_completions(self, request):
        return await self._serve_public(request, "completion")

    async def _h_chat(self, request):
        return await self._serve_public(request, "chat")

    async def _serve_public(self, request, kind: str):
        from aiohttp import web

        self.counters["requests_total"] += 1
        # Auth: a key-store flake (chaos gw.auth) must surface as a
        # clean 401-class refusal, never a routed request or a 500.
        tenant: Optional[Tenant] = None
        try:
            faults.maybe_fail("gw.auth")
            auth = request.headers.get("Authorization", "")
            key = auth[7:] if auth.startswith("Bearer ") else auth
            tenant = self._by_key.get(key)
        except Exception as e:
            logger.warning(f"auth path failed: {e!r}")
            tenant = None
        if tenant is None:
            self.counters["auth_failures_total"] += 1
            return web.json_response(
                public.error_body(401, "invalid or missing API key"),
                status=401,
            )
        try:
            body = await request.json()
            parsed = (public.parse_completion_request(body)
                      if kind == "completion"
                      else public.parse_chat_request(body))
        except public.PublicApiError as e:
            return web.json_response(
                public.error_body(e.status, e.message), status=e.status)
        except Exception:
            return web.json_response(
                public.error_body(400, "malformed JSON body"),
                status=400,
            )
        if self._known_models:
            # Multi-model resolution: the OpenAI "model" field picks
            # the pool. "areal" is api/public.py's absent-field
            # placeholder, so it (like "") maps to the default model;
            # anything else must name a served model (404) the tenant
            # is entitled to (403). The resolved id rides the
            # scheduling meta — a wrong-pool route is the manager's
            # error to refuse, never a silent cross-model hit.
            requested = parsed.model
            if requested in ("", "areal"):
                requested = self.default_model
            if requested not in self._known_models:
                self.counters["model_rejections_total"] += 1
                return web.json_response(
                    public.error_body(
                        404, f"unknown model {requested!r}"),
                    status=404,
                )
            if tenant.models is not None \
                    and requested not in tenant.models:
                self.counters["model_rejections_total"] += 1
                return web.json_response(
                    public.error_body(
                        403,
                        f"tenant {tenant.name} is not entitled to "
                        f"model {requested!r}",
                    ),
                    status=403,
                )
            parsed.model = requested
        inbound = rpc.Deadline.from_headers(request.headers)
        if inbound is not None and inbound.expired():
            return web.json_response(
                public.error_body(429, "deadline expired",
                                  retry_after=0.0),
                status=429, headers={"Retry-After": "0"},
            )
        deadline = rpc.ensure_deadline(inbound, self.request_timeout)
        prefix = "cmpl-" if kind == "completion" else "chatcmpl-"
        rid = prefix + uuid.uuid4().hex
        # Admission cost: the worst case this request may consume.
        # Session continuations discount to the engine's class-0 rate —
        # their prefix KV is already paid for.
        cost = (len(parsed.prompt_ids) + parsed.max_tokens) * (
            0.5 if parsed.session else 1.0)
        # Chaos gw.shed fires BEFORE the bucket charge: a crash inside
        # the shed decision must never leak a charge or a ledger row.
        faults.maybe_fail("gw.shed")
        now = time.monotonic()
        loop = asyncio.get_event_loop()
        if tenant.active_streams >= tenant.max_streams:
            retry_after: Optional[float] = tenant.time_to_afford(
                cost, now)
        else:
            retry_after = tenant.try_charge(cost, now)
        if retry_after is not None:
            self.counters["shed_total"] += 1
            ra = max(self.retry_after_floor, retry_after)

            def _journal_shed():
                self.ledger.record_shed(
                    rid, tenant.name, model=self._model_tag(parsed))

            await loop.run_in_executor(None, _journal_shed)
            return web.json_response(
                public.error_body(
                    429,
                    f"tenant {tenant.name} over quota "
                    f"(streams {tenant.active_streams}/"
                    f"{tenant.max_streams})",
                    retry_after=ra,
                ),
                status=429, headers={"Retry-After": f"{ra:.3f}"},
            )
        item = _QueueItem(tenant.name, cost, loop.create_future())
        self._enqueue(item)
        tenant.active_streams += 1
        try:
            await item.fut
            with tracing.span(
                "gateway.request", rid=rid, tenant=tenant.name,
                kind=kind, prompt_len=len(parsed.prompt_ids),
            ):
                # TTFT is admission-to-first-token: `now` predates the
                # fair-share queue wait, so the per-tenant histograms
                # actually witness queueing unfairness (a queue-blind
                # clock would make the fairness evidence vacuous).
                return await self._reply(request, parsed, tenant, rid,
                                         deadline, t_start=now)
        finally:
            tenant.active_streams -= 1
            if item.fut.done() and not item.fut.cancelled():
                self._release_slot()

    async def _reply(self, request, parsed: public.ParsedRequest,
                     tenant: Tenant, rid: str, deadline: rpc.Deadline,
                     t_start: Optional[float] = None):
        from aiohttp import web

        qid = f"gw/{tenant.name}/{parsed.session or rid}"
        if t_start is None:
            t_start = time.monotonic()
        loop = asyncio.get_event_loop()
        resp: Optional[web.StreamResponse] = None
        first_box = [True]

        async def emit(toks: List[int]):
            nonlocal resp
            if not parsed.stream:
                return
            if resp is None:
                resp = web.StreamResponse()
                resp.headers["Content-Type"] = "text/event-stream"
                resp.headers["Cache-Control"] = "no-cache"
                await resp.prepare(request)
            chunk = (
                public.completion_chunk(rid, parsed.model, toks)
                if parsed.kind == "completion"
                else public.chat_chunk(rid, parsed.model, toks,
                                       first=first_box[0])
            )
            first_box[0] = False
            await resp.write(public.sse_event(chunk))

        acc, finish, ttft_ms, itl_counts, error = \
            await self._generate_chunks(
                parsed, tenant, qid, deadline, t_start, emit)
        billable = bool(acc) or error is None
        if billable:
            # Journal BEFORE the terminal frame: billed-as-emitted.
            # A mid-stream failover already resumed from the emitted
            # prefix, so len(acc) is exactly what the client received.
            def _journal():
                self.ledger.record_usage(
                    rid, tenant.name, len(parsed.prompt_ids), len(acc),
                    ttft_ms, itl_counts,
                    model=self._model_tag(parsed),
                )

            await loop.run_in_executor(None, _journal)
        if parsed.stream:
            if resp is None:
                if error is not None:
                    return web.json_response(
                        public.error_body(503, error), status=503)
                resp = web.StreamResponse()
                resp.headers["Content-Type"] = "text/event-stream"
                resp.headers["Cache-Control"] = "no-cache"
                await resp.prepare(request)
            if error is not None:
                await resp.write(public.sse_event(
                    public.error_body(503, error)))
            final = (
                public.completion_chunk(rid, parsed.model, [],
                                        finish_reason=finish)
                if parsed.kind == "completion"
                else public.chat_chunk(rid, parsed.model, [],
                                       finish_reason=finish)
            )
            final["usage"] = public.usage_fields(
                len(parsed.prompt_ids), len(acc))
            await resp.write(public.sse_event(final))
            await resp.write(public.SSE_DONE)
            await resp.write_eof()
            return resp
        if error is not None and not acc:
            return web.json_response(
                public.error_body(503, error), status=503)
        body = (
            public.completion_body(rid, parsed.model, acc,
                                   len(parsed.prompt_ids), finish)
            if parsed.kind == "completion"
            else public.chat_body(rid, parsed.model, acc,
                                  len(parsed.prompt_ids), finish)
        )
        return web.json_response(body)

    # -- internal-surface auth ------------------------------------------

    def _internal_ok(self, request) -> bool:
        """True iff the caller presented the internal shared secret
        (X-Areal-Gateway-Token, or a Bearer token) — the gate on the
        trainer proxy and the operator surfaces, which share the
        tenant-facing listener."""
        tok = request.headers.get("X-Areal-Gateway-Token", "")
        if not tok:
            auth = request.headers.get("Authorization", "")
            tok = auth[7:] if auth.startswith("Bearer ") else ""
        return bool(tok) and hmac.compare_digest(
            tok, self.internal_token)

    # -- trainer proxy --------------------------------------------------

    async def _h_schedule_proxy(self, request):
        """Reserved-tenant pass-through for the training plane: tags
        the meta as the trainer tenant (never shed, never queued) and
        forwards to the manager with the caller's deadline intact.
        Internal-token gated: an unauthenticated caller would otherwise
        ride the never-shed trainer lane past every tenant quota."""
        from aiohttp import web

        if not self._internal_ok(request):
            self.counters["auth_failures_total"] += 1
            return web.json_response(
                public.error_body(
                    401, "trainer proxy requires the internal token"),
                status=401,
            )
        try:
            meta = await request.json()
        except Exception:
            meta = {}
        if not isinstance(meta, dict):
            meta = {}
        # Overwrite, never setdefault: the proxy's whole meaning is
        # "this IS trainer traffic" — an internal caller must not be
        # able to spoof some other tenant's attribution either.
        meta["tenant"] = TRAINER_TENANT
        self._trainer_sched += 1
        dl = rpc.ensure_deadline(
            rpc.Deadline.from_headers(request.headers),
            self.request_timeout,
        )
        sess = await self._sess()
        try:
            async with sess.post(
                f"{self.manager_addr}/schedule_request", json=meta,
                headers=dl.headers(),
            ) as r:
                body = await r.json()
                code = r.status
        except (aiohttp.ClientError, asyncio.TimeoutError):
            loop = asyncio.get_event_loop()
            await loop.run_in_executor(None, self._refresh_manager_addr)
            return web.json_response(
                {"error": "gserver manager unreachable",
                 "retry_after": 0.5},
                status=503,
            )
        return web.json_response(body, status=code)

    # -- operator surfaces ----------------------------------------------

    async def _h_usage(self, request):
        """Operator token -> every tenant's row; a tenant API key ->
        exactly that tenant's row; anyone else -> 401. Usage rows are
        per-tenant confidential (the Retry-After design already
        refuses to leak cross-tenant traffic — the report must not
        hand it out for free)."""
        from aiohttp import web

        operator = self._internal_ok(request)
        tenant: Optional[Tenant] = None
        if not operator:
            auth = request.headers.get("Authorization", "")
            key = auth[7:] if auth.startswith("Bearer ") else auth
            tenant = self._by_key.get(key)
            if tenant is None:
                self.counters["auth_failures_total"] += 1
                return web.json_response(
                    public.error_body(
                        401,
                        "usage requires the internal token or a "
                        "tenant API key",
                    ),
                    status=401,
                )
        snap = self.ledger.snapshot()
        if operator:
            trainer = snap.setdefault(TRAINER_TENANT, {
                "requests": 0, "sheds": 0, "prompt_tokens": 0,
                "completion_tokens": 0, "total_tokens": 0,
            })
            trainer["sched_requests"] = self._trainer_sched
        else:
            snap = {tenant.name: snap.get(tenant.name, {
                "requests": 0, "sheds": 0, "prompt_tokens": 0,
                "completion_tokens": 0, "total_tokens": 0,
            })}
        return web.json_response({
            "schema": GATEWAY_V1,
            "gateway": self.member,
            "fair_share": self.fair_share,
            "models": self.models,
            "usage_replayed": self.ledger.replayed,
            "usage_dup_dropped": self.ledger.dup_dropped,
            "usage_compactions": self.ledger.compactions,
            "tenants": snap,
        })

    async def _h_metrics(self, request):
        from aiohttp import web

        if not self._internal_ok(request):
            self.counters["auth_failures_total"] += 1
            return web.json_response(
                public.error_body(
                    401, "metrics requires the internal token"),
                status=401,
            )
        c = self.counters
        pt, ct, ttft, itl = self.ledger.totals()
        active = sum(t.active_streams for t in self.tenants.values())
        lines = [
            f"areal:gw_requests_total {c['requests_total']}",
            f"areal:gw_auth_failures_total {c['auth_failures_total']}",
            f"areal:gw_shed_total {c['shed_total']}",
            f"areal:gw_prompt_tokens_total {pt}",
            f"areal:gw_completion_tokens_total {ct}",
            f"areal:gw_active_streams {active}",
            f"areal:gw_queue_depth {self._queue_depth()}",
            f"areal:gw_fairshare_picks_total {c['fairshare_picks_total']}",
            f"areal:gw_ttft_hist {latency.encode_counts(ttft) or '-'}",
            f"areal:gw_itl_hist {latency.encode_counts(itl) or '-'}",
            f"areal:gw_upstream_failovers_total "
            f"{c['upstream_failovers_total']}",
            f"areal:gw_model_rejections_total "
            f"{c['model_rejections_total']}",
            f"areal:gw_usage_replayed_total {self.ledger.replayed}",
            f"areal:gw_usage_dup_dropped_total "
            f"{self.ledger.dup_dropped}",
            f"areal:gw_usage_compactions_total "
            f"{self.ledger.compactions}",
        ]
        return web.Response(text="\n".join(lines) + "\n")

    async def _h_health(self, request):
        from aiohttp import web

        return web.json_response({
            "status": "ok",
            "tenants": len(self.tenants),
            "manager_addr": self.manager_addr,
            "fair_share": self.fair_share,
        })

    # -- lifecycle -------------------------------------------------------

    def _run_http(self):
        from aiohttp import web

        self._http_loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._http_loop)
        self._queue_event = asyncio.Event()
        app = web.Application(client_max_size=64 << 20)
        app.router.add_post("/v1/completions", self._h_completions)
        app.router.add_post("/v1/chat/completions", self._h_chat)
        app.router.add_post("/schedule_request", self._h_schedule_proxy)
        app.router.add_get("/v1/usage", self._h_usage)
        app.router.add_get("/metrics", self._h_metrics)
        app.router.add_get("/health", self._h_health)
        runner = web.AppRunner(app)
        self._http_loop.run_until_complete(runner.setup())
        host = network.gethostip()
        port = self._port or network.find_free_port()
        ssl_ctx: Optional[ssl.SSLContext] = None
        if self._tls_cert and self._tls_key:
            ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_ctx.load_cert_chain(self._tls_cert, self._tls_key)
        site = web.TCPSite(runner, host, port, ssl_context=ssl_ctx)
        self._http_loop.run_until_complete(site.start())
        scheme = "https" if ssl_ctx is not None else "http"
        self.address = f"{scheme}://{host}:{port}"
        self._dispatch_task = self._http_loop.create_task(
            self._dispatch_loop())
        self._http_ready.set()
        self._http_loop.run_forever()

    def _supervise(self):
        ttl = self._heartbeat.ttl if self._heartbeat else 10.0
        while not self._stop.wait(max(0.05, ttl / 3)):
            if self._heartbeat is not None:
                # Per-tenant usage rides the heartbeat payload so the
                # manager's /status can surface tenant rows without a
                # new wire route.
                self._heartbeat.update_payload(
                    tenants=self.ledger.brief(),
                    sheds=self.counters["shed_total"],
                )

    def start(self, timeout: float = 30.0) -> str:
        if self.manager_addr is None:
            self._refresh_manager_addr()
        self._http_thread = threading.Thread(
            target=self._run_http, daemon=True, name="gw-http"
        )
        self._http_thread.start()
        if not self._http_ready.wait(timeout):
            raise TimeoutError("gateway HTTP front did not start")
        # Per-instance records (keyed by gateway_id, like every other
        # fleet member): concurrent gateways never overwrite — or on
        # stop() delete — each other's discovery state.
        name_resolve.add(
            names.gateway_url(
                self.experiment_name, self.trial_name, self.gateway_id),
            self.address,
            delete_on_exit=True,
            replace=True,
        )
        name_resolve.add(
            names.gateway_internal_token(
                self.experiment_name, self.trial_name, self.gateway_id),
            self.internal_token,
            delete_on_exit=True,
            replace=True,
        )
        self._heartbeat = Heartbeat(
            self.experiment_name,
            self.trial_name,
            self.member,
            payload={"url": self.address, "tenants": {}},
        )
        self._sup_thread = threading.Thread(
            target=self._supervise, daemon=True, name="gw-supervise"
        )
        self._sup_thread.start()
        logger.info(
            f"gateway {self.member} serving at {self.address} "
            f"({len(self.tenants)} tenants, fair_share="
            f"{self.fair_share}, manager={self.manager_addr})"
        )
        return self.address

    def stop(self):
        self._stop.set()
        if self._heartbeat is not None:
            self._heartbeat.stop()
        for key in (
            names.gateway_url(
                self.experiment_name, self.trial_name, self.gateway_id),
            names.gateway_internal_token(
                self.experiment_name, self.trial_name, self.gateway_id),
        ):
            try:
                name_resolve.delete(key)
            except Exception:
                pass
        if self._http_loop is not None:
            if self._session is not None and not self._session.closed:
                try:
                    asyncio.run_coroutine_threadsafe(
                        self._session.close(), self._http_loop
                    ).result(timeout=5)
                except Exception:
                    pass
            if self._dispatch_task is not None:
                task = self._dispatch_task

                async def _stop_dispatch():
                    task.cancel()
                    try:
                        await task
                    except asyncio.CancelledError:
                        pass

                try:
                    asyncio.run_coroutine_threadsafe(
                        _stop_dispatch(), self._http_loop
                    ).result(timeout=5)
                except Exception:
                    pass
            self._http_loop.call_soon_threadsafe(self._http_loop.stop)
        if self._http_thread is not None:
            self._http_thread.join(timeout=5)
        self.ledger.close()


# -- selftest ------------------------------------------------------------

class _StubUpstream:
    """In-process manager+server stand-in for ``--selftest``: answers
    /schedule_request with its own URL and /generate with two canned
    tokens then EOS, so the preflight exercises the full public path
    without a fleet."""

    def __init__(self):
        self._ready = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self.address: Optional[str] = None

    async def _h_sched(self, request):
        from aiohttp import web

        await request.json()
        return web.json_response({"url": self.address, "version": 0})

    async def _h_gen(self, request):
        from aiohttp import web

        await request.json()
        toks = list(b"ok")
        return web.json_response({
            "output_ids": toks,
            "output_logprobs": [0.0] * len(toks),
            "no_eos": False,
            "version_start": 0,
            "version_end": 0,
        })

    def _run(self):
        from aiohttp import web

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        app = web.Application()
        app.router.add_post("/schedule_request", self._h_sched)
        app.router.add_post("/generate", self._h_gen)
        runner = web.AppRunner(app)
        self._loop.run_until_complete(runner.setup())
        host = network.gethostip()
        port = network.find_free_port()
        site = web.TCPSite(runner, host, port)
        self._loop.run_until_complete(site.start())
        self.address = f"http://{host}:{port}"
        self._ready.set()
        self._loop.run_forever()

    def start(self, timeout: float = 10.0):
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="gw-selftest-stub")
        self._thread.start()
        if not self._ready.wait(timeout):
            raise TimeoutError("selftest stub did not start")

    def stop(self):
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)


def _selftest_tls(stub: _StubUpstream, policy) -> None:
    """Self-signed-cert arm: mint a throwaway cert pair (openssl
    binary), serve a second gateway over https with a two-model
    fleet spec, and drive one completion + the 404/403 model
    refusals through the TLS listener. Raises on any failure; a box
    without the openssl binary skips the arm (the production stance
    is mTLS at the LB anyway — docs/serving.md)."""
    import shutil
    import subprocess
    import urllib.error
    import urllib.request

    if not shutil.which("openssl"):
        print("gateway selftest: openssl missing, TLS arm skipped")
        return
    tls_dir = tempfile.mkdtemp(prefix="gw_selftest_tls_")
    cert = os.path.join(tls_dir, "cert.pem")
    keyf = os.path.join(tls_dir, "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", keyf, "-out", cert, "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    wal2 = os.path.join(
        tempfile.gettempdir(), f"gw_selftest_tls_{os.getpid()}.jsonl")
    try:
        os.remove(wal2)
    except OSError:
        pass
    os.environ["AREAL_GW_TLS_CERT"] = cert
    os.environ["AREAL_GW_TLS_KEY"] = keyf
    svc = None
    try:
        svc = GatewayService(
            "gw_selftest_tls", "local",
            manager_addr=stub.address,
            tenant_spec="selftest:sk-selftest:1:100000:200000:4:alpha",
            usage_wal_path=wal2,
            model_spec="alpha,beta",
        )
        url = svc.start()
        assert url.startswith("https://"), url
        unverified = ssl._create_unverified_context()
        hdrs = {"Authorization": "Bearer sk-selftest",
                "Content-Type": "application/json"}

        def _post(model):
            data = json.dumps({"prompt": "hi", "max_tokens": 2,
                               "stream": False,
                               "model": model}).encode()
            req = urllib.request.Request(
                f"{url}/v1/completions", data=data, headers=hdrs)
            probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
            with urllib.request.urlopen(
                req, timeout=policy.attempt_timeout(probe_dl),
                context=unverified,
            ) as r:
                return json.loads(r.read().decode())

        body = _post("alpha")
        assert body["model"] == "alpha", body
        for model, want in (("nope", 404), ("beta", 403)):
            try:
                _post(model)
                raise AssertionError(
                    f"model {model!r} was not refused")
            except urllib.error.HTTPError as e:
                assert e.code == want, (model, e.code)
        snap = svc.ledger.snapshot()["selftest"]
        assert snap["models"]["alpha"]["requests"] == 1, snap
        print(f"gateway selftest TLS arm ok: {url}")
    finally:
        os.environ.pop("AREAL_GW_TLS_CERT", None)
        os.environ.pop("AREAL_GW_TLS_KEY", None)
        if svc is not None:
            svc.stop()
        shutil.rmtree(tls_dir, ignore_errors=True)
        try:
            os.remove(wal2)
        except OSError:
            pass


def _selftest() -> int:
    import urllib.error
    import urllib.request

    stub = _StubUpstream()
    stub.start()
    wal_path = os.path.join(
        tempfile.gettempdir(), f"gw_selftest_{os.getpid()}.jsonl")
    try:
        os.remove(wal_path)
    except OSError:
        pass
    svc = GatewayService(
        "gw_selftest", "local",
        manager_addr=stub.address,
        tenant_spec="selftest:sk-selftest:1:100000:200000:4",
        usage_wal_path=wal_path,
    )
    url = svc.start()
    policy = rpc.default_policy()
    try:
        data = json.dumps(
            {"prompt": "hi", "max_tokens": 4, "stream": True}).encode()
        req = urllib.request.Request(
            f"{url}/v1/completions", data=data,
            headers={"Authorization": "Bearer sk-selftest",
                     "Content-Type": "application/json"},
        )
        probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
        with urllib.request.urlopen(
            req, timeout=policy.attempt_timeout(probe_dl)
        ) as r:
            text = r.read().decode()
        assert "[DONE]" in text, text
        assert '"finish_reason":"stop"' in text, text
        data = json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "stream": False,
        }).encode()
        req = urllib.request.Request(
            f"{url}/v1/chat/completions", data=data,
            headers={"Authorization": "Bearer sk-selftest",
                     "Content-Type": "application/json"},
        )
        probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
        with urllib.request.urlopen(
            req, timeout=policy.attempt_timeout(probe_dl)
        ) as r:
            chat = json.loads(r.read().decode())
        assert chat["usage"]["completion_tokens"] >= 1, chat
        # Operator surfaces and the trainer proxy are internal-token
        # gated: no token -> 401, the minted token -> full view.
        op_hdr = {INTERNAL_TOKEN_HEADER: svc.internal_token}
        try:
            probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
            urllib.request.urlopen(
                urllib.request.Request(f"{url}/v1/usage"),
                timeout=policy.attempt_timeout(probe_dl),
            )
            raise AssertionError("tokenless /v1/usage was not refused")
        except urllib.error.HTTPError as e:
            assert e.code == 401, e.code
        probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
        with urllib.request.urlopen(
            urllib.request.Request(f"{url}/v1/usage", headers=op_hdr),
            timeout=policy.attempt_timeout(probe_dl),
        ) as r:
            usage = json.loads(r.read().decode())
        row = usage["tenants"]["selftest"]
        assert row["requests"] == 2, usage
        assert row["completion_tokens"] >= 2, usage
        probe_dl = rpc.Deadline.after(policy.attempt_timeout_s)
        with urllib.request.urlopen(
            urllib.request.Request(f"{url}/metrics", headers=op_hdr),
            timeout=policy.attempt_timeout(probe_dl),
        ) as r:
            mtext = r.read().decode()
        assert "areal:gw_requests_total 2" in mtext, mtext
        _selftest_tls(stub, policy)
        print(f"gateway selftest ok: {url}")
        return 0
    except Exception as e:
        print(f"gateway selftest FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        svc.stop()
        stub.stop()
        try:
            os.remove(wal_path)
        except OSError:
            pass


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="multi-tenant inference gateway")
    p.add_argument("--experiment", default="gateway")
    p.add_argument("--trial", default="local")
    p.add_argument("--index", type=int, default=0)
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--manager-addr", default=None)
    p.add_argument("--tenants", default=None,
                   help="overrides AREAL_GW_TENANTS")
    p.add_argument("--models", default=None,
                   help="comma list of served model ids (first is the "
                   "default); overrides AREAL_GW_MODELS")
    p.add_argument("--usage-wal", default=None)
    p.add_argument("--name-resolve-root", default=None)
    p.add_argument(
        "--selftest",
        action="store_true",
        help="serve against an in-process stub fleet, run one "
        "completion + one chat completion through the full tenant "
        "path, check the ledger; exit 0 iff healthy",
    )
    args = p.parse_args(argv)
    if args.name_resolve_root:
        name_resolve.reconfigure("nfs", record_root=args.name_resolve_root)
    else:
        name_resolve.reconfigure("memory")
    if args.selftest:
        return _selftest()
    svc = GatewayService(
        args.experiment, args.trial, gateway_id=args.index,
        port=args.port, manager_addr=args.manager_addr,
        tenant_spec=args.tenants, usage_wal_path=args.usage_wal,
        model_spec=args.models,
    )
    url = svc.start()
    print(url, flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        svc.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
