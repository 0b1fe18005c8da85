"""JAX generation server worker: the ServingEngine behind HTTP.

Counterpart of the reference's GenerationServer + patched SGLang
(realhf/system/generation_server.py:121, realhf/api/cli_args.py:323-391):
instead of launching an SGLang subprocess, the engine runs in-process on
this worker's TPU devices. The HTTP surface mirrors what the rest of the
stack expects (SURVEY §8 "SGLang server contract"):

- POST /generate {qid, input_ids, gconfig...} -> token-in/token-out with
  logprobs and version stamps
- POST /update_weights_from_disk {model_path, allow_interrupt}
- GET  /metrics  (areal:num_used_tokens / areal:num_running_reqs)
- GET  /health

Disaggregated prefill/decode serving (docs/serving.md): the server has
a live ``role`` (prefill / decode / unified, starting from the config,
flipped at runtime by the manager's elastic sizer via POST /set_role).
When the manager pairs a decode server into a request (``decode_url``
in the /generate body), this server runs the prompt to its FIRST
sampled token only, exports the filled KV pages as a hash-indexed blob
(engine/kv_handoff.py), and POSTs /kv_handoff to the decode server —
which pulls the payload back over chunked HTTP (per-chunk sha256 +
Range resume, the weight-plane transfer discipline), imports it, and
runs the decode stream as a priority-0 continuation. Any handoff
failure falls back to serving the remainder locally, so disaggregation
can only add throughput, never lose a rollout.

Plus the streaming weight-distribution plane (system/weight_plane.py):

- POST /distribute_weights  prefetch version-N chunks into host memory
  from an ordered upstream list (fanout-tree parent, surviving peers,
  origin) WHILE still serving version N-1
- POST /cutover_weights     short interrupt + device-swap to the
  prefetched version; duration measured separately from transfer
- GET  /weights/manifest, /weights/chunk   serve held chunks to sibling
  servers (the peer hop that keeps trainer egress O(1))
"""

from __future__ import annotations

import asyncio
import collections
import os
import threading
import time
from typing import Any, Dict, Optional

from aiohttp import web

from areal_tpu.api import data_api
from areal_tpu.api.system_api import GenerationServerConfig
from areal_tpu.base import constants, logging, name_resolve, names, network, rpc, seeding, tracing
from areal_tpu.base.fault_injection import faults
from areal_tpu.engine.serving import GenRequest, ServingEngine
from areal_tpu.engine.weight_client import ChunkStore, assemble_params
from areal_tpu.utils import jaxenv
from areal_tpu.system.weight_plane import (
    serve_store_chunk,
    serve_store_manifest,
)
from areal_tpu.system.worker_base import PollResult, Worker

logger = logging.getLogger("generation_server")


class GenerationServer(Worker):
    def _configure(self, config: GenerationServerConfig):
        self.cfg = config
        constants.set_experiment_trial_names(
            config.experiment_name, config.trial_name
        )
        seeding.set_random_seed(config.seed, config.worker_name)
        import areal_tpu.engine.factories  # noqa: F401  (registry)
        from areal_tpu.api.model_api import make_model

        # One shared model name across the fleet: the init rng folds the
        # name in, so a per-index name would give every random-init
        # server DIFFERENT weights — fatal for disaggregation, where KV
        # prefilled on one server is decoded on another (checkpoint
        # loads were never affected; random init is the test/bench
        # path).
        kwargs: Dict[str, Any] = {"name": "gserver"}
        if config.model_path is not None:
            kwargs["model_path"] = config.model_path
        if config.tokenizer_path is not None:
            kwargs["tokenizer_path"] = config.tokenizer_path
        model = make_model(config.model, **kwargs)
        raw = model._raw
        self.tokenizer = model.tokenizer
        eos = self.tokenizer.eos_token_id if self.tokenizer else None
        from areal_tpu.engine.serving import serving_mesh

        mesh = (
            serving_mesh(config.tensor_parallel)
            if config.tensor_parallel > 1
            else None
        )
        self.engine = ServingEngine(
            cfg=raw["cfg"],
            params=raw["params"],
            max_batch_size=config.max_concurrent_requests,
            max_seq_len=config.max_seq_len,
            decode_block_steps=config.decode_block_steps,
            eos_token_id=eos,
            seed=config.seed + config.server_index,
            page_size=config.kv_page_size,
            kv_pool_tokens=config.kv_pool_tokens,
            prompt_bucket=config.prompt_bucket,
            prefill_max_batch=config.prefill_max_batch,
            prefill_chunk=config.prefill_chunk,
            chunked_prefill_per_lap=config.chunked_prefill_per_lap,
            prefix_cache_tokens=config.prefix_cache_tokens,
            kv_cache_dtype=config.kv_cache_dtype,
            speculative_draft_len=config.speculative_draft_len,
            speculative_ngram=config.speculative_ngram,
            speculative_window=config.speculative_window,
            decode_weight_dtype=config.decode_weight_dtype,
            prefill_token_budget=config.prefill_token_budget,
            decode_blocks_per_admit=config.decode_blocks_per_admit,
            kv_tier_bytes=config.kv_tier_bytes,
            kv_tier_disk_dir=config.kv_tier_disk_dir,
            kv_tier_disk_bytes=config.kv_tier_disk_bytes,
            kv_spill_dtype=config.kv_spill_dtype,
            mesh=mesh,
        )
        self.engine.start()
        jaxenv.report_devices(config.worker_name)
        if config.warm_on_start:
            # Compile the serving programs before taking traffic (and
            # before discovery registration below): one bucket's worth
            # of prompt + the decode block covers the hot path.
            self.engine.warm([config.prompt_bucket])
            jaxenv.report_usage(config.worker_name)
        self._n_interrupted = 0
        self._n_shed = 0
        self._last_load_info = None

        # Disaggregated serving: live pool role (the manager's elastic
        # sizer re-roles "unified"-configured servers at runtime) + the
        # export stash the decode side pulls handoff payloads from.
        if config.role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be unified/prefill/decode, got {config.role!r}"
            )
        self.role = config.role
        self._role_lock = threading.Lock()
        # Drain-then-leave (docs/fault_tolerance.md): once draining,
        # admission sheds every new /generate with 429 (the manager
        # already stopped routing here), in-flight work finishes, the
        # parked prefixes migrate to peers over the /kv wire, and the
        # worker departs with a graceful heartbeat stop. _draining is a
        # plain bool flipped on the HTTP loop and read by the poll
        # thread (GIL-atomic); _drain_state is mutated only by the
        # drain task on the HTTP loop.
        self._draining = False
        self._drain_state: Dict[str, Any] = {
            "draining": False, "done": False, "held": 0, "migrated": 0,
            "lost": 0, "stale_dropped": 0, "drain_ms": 0.0, "reason": "",
        }
        # Drain-migration ingest counters (/kv/accept).
        self._kv_accepted = 0
        self._kv_accept_bytes = 0
        self._handoff_store: "collections.OrderedDict[str, tuple]" = (
            collections.OrderedDict()
        )
        self._handoff_ok = 0
        self._handoff_failed = 0
        self._handoff_fallback = 0
        self._last_handoff_ms = 0.0
        self._last_kv_transfer_ms = 0.0
        self._handoff_session = None  # lazy aiohttp session (HTTP loop)
        # Tiered KV plane (docs/serving.md): peer-pull counters — a
        # returning session routed here without its prefix pulls it
        # from whichever peer the manager's global index names.
        self._kv_peer_hits = 0
        self._kv_peer_bytes = 0
        self._kv_peer_failed = 0
        self._last_kv_restore_ms = 0.0
        self._kv_manifests_served = 0
        self._kv_chunks_served = 0
        self._kv_chunk_bytes_served = 0

        # Shard-aware weight plane: this server's coordinates in a
        # fleet-level tensor-parallel group (None = fetch full
        # payloads). The manager groups fanout trees by this spec —
        # only same-shard peers hold the same chunk stream.
        rank, degree = config.weight_shard_rank, config.weight_shard_degree
        if (rank is None) != (degree is None):
            raise ValueError(
                "weight_shard_rank and weight_shard_degree must be set "
                f"together (got {rank!r}/{degree!r})"
            )
        if degree is not None and not (degree >= 1 and 0 <= rank < degree):
            raise ValueError(f"bad weight shard {rank}/{degree}")
        self._weight_shard = (
            (int(rank), int(degree)) if degree is not None else None
        )
        if self._weight_shard is not None and degree > 1:
            # Fail at STARTUP, not after a full fleet transfer: a sliced
            # cutover can only land when this process hosts exactly the
            # mesh slice for its rank. A single-process mesh owns every
            # tensor coordinate, so sliced fetch needs a multi-host
            # (jax.distributed) deployment.
            t_size = (
                self.engine.mesh.shape.get("tensor", 1)
                if self.engine.mesh is not None else 1
            )
            if t_size != degree:
                raise ValueError(
                    f"weight_shard {rank}/{degree} requires a tensor "
                    f"mesh of extent {degree} (engine has {t_size}); "
                    f"set tensor_parallel={degree}"
                )
            coords = set(
                self.engine._addressable_tensor_coords().values()
            )
            if coords != {int(rank)}:
                raise ValueError(
                    f"weight_shard {rank}/{degree} requires this "
                    f"process to host exactly tensor coordinate {rank} "
                    f"of the mesh, but it hosts {sorted(coords)} — "
                    f"sliced weight fetch needs a multi-host "
                    f"(jax.distributed) mesh, one rank per server "
                    f"process"
                )

        # Weight-plane prefetch state machine: idle -> fetching -> ready
        # (-> failed). The store outlives its own cutover so this server
        # keeps serving chunks to later-wave siblings and to chaos
        # re-fanouts; a new /distribute_weights replaces it.
        self._wp_lock = threading.Lock()
        self._wp_store: Any = None
        self._wp_state = "idle"
        self._wp_transfer_ms = 0.0
        self._wp_verify_ms = 0.0
        self._wp_cutover_ms = 0.0
        self._wp_bytes_from_origin = 0
        self._wp_bytes_from_peers = 0
        self._wp_chunks_served = 0
        self._wp_bytes_served = 0
        # Shard-aware expectations for /metrics: a sliced fetch is
        # complete at its SHARD bytes — dashboards must divide ingress
        # by this, not the full payload, or every sliced fetch reads as
        # a torn transfer.
        self._wp_expected_bytes = 0
        self._wp_ingress_eq = 0.0
        self._wp_wire = "raw"

        # HTTP server on its own thread + loop.
        self._http_loop = asyncio.new_event_loop()
        self._http_ready = threading.Event()
        self._http_thread = threading.Thread(target=self._serve_http, daemon=True)
        self._http_thread.start()
        if not self._http_ready.wait(30):
            raise RuntimeError("generation server HTTP failed to start")

        # Register for discovery.
        name_resolve.add_subentry(
            names.gen_servers(config.experiment_name, config.trial_name),
            self.address,
        )
        name_resolve.add(
            names.gen_server_url(
                config.experiment_name, config.trial_name, str(config.server_index)
            ),
            self.address,
            keepalive_ttl=60,
            replace=True,
        )
        logger.info(f"generation server {config.server_index} at {self.address}")

    def _heartbeat_payload(self):
        # The gserver manager maps health members -> routing-table URLs
        # through this field (eviction on missed beats, readmission +
        # weight re-sync on return).
        payload = super()._heartbeat_payload()
        payload["url"] = self.address
        payload["server_index"] = self.cfg.server_index
        payload["role"] = self.role
        if self.cfg.model_id:
            # Multi-model fleets pool servers by this field; the
            # manager QUARANTINES a beat naming an unregistered id
            # rather than adopt it (system/model_registry.py).
            payload["model_id"] = self.cfg.model_id
        # The drain flag rides the heartbeat so even a RESTARTED
        # manager learns in-progress drains without asking.
        payload["draining"] = bool(self._draining)
        if self._weight_shard is not None:
            # (rank, degree): the manager plans per-shard fanout groups
            # from this.
            payload["weight_shard"] = list(self._weight_shard)
        return payload

    # ------------------------------------------------------------------
    # HTTP
    # ------------------------------------------------------------------

    def _serve_http(self):
        asyncio.set_event_loop(self._http_loop)
        app = web.Application()
        app.router.add_post("/generate", self._h_generate)
        app.router.add_post("/kv_handoff", self._h_kv_handoff)
        app.router.add_get("/kv_handoff/blob", self._h_kv_blob)
        app.router.add_get("/kv/manifest", self._h_kv_manifest)
        app.router.add_get("/kv/chunk", self._h_kv_chunk)
        app.router.add_get("/kv/index", self._h_kv_index)
        app.router.add_post("/kv/accept", self._h_kv_accept)
        app.router.add_post("/drain", self._h_drain)
        app.router.add_get("/drain", self._h_drain_status)
        app.router.add_post("/set_role", self._h_set_role)
        app.router.add_post("/configure", self._h_configure)
        app.router.add_post("/update_weights_from_disk", self._h_update_weights)
        app.router.add_post("/distribute_weights", self._h_distribute_weights)
        app.router.add_post("/cutover_weights", self._h_cutover_weights)
        app.router.add_get("/weights/manifest", self._h_weights_manifest)
        app.router.add_get("/weights/chunk", self._h_weights_chunk)
        app.router.add_get("/metrics", self._h_metrics)
        app.router.add_get("/health", self._h_health)
        runner = web.AppRunner(app)
        self._http_loop.run_until_complete(runner.setup())
        host = network.gethostip()
        port = network.find_free_port()
        site = web.TCPSite(runner, host, port)
        self._http_loop.run_until_complete(site.start())
        self.address = f"http://{host}:{port}"
        self._http_ready.set()
        self._http_loop.run_forever()

    def _admission_overloaded(self) -> Optional[float]:
        """Backpressure watermark check: returns the Retry-After seconds
        when /generate must shed, None when the request may queue. Reads
        only host counters the engine maintains — no device sync."""
        cfg = self.cfg
        if self._draining:
            # Quiesce: the manager stopped routing here; stragglers
            # (in-flight schedule decisions, stale affinity) get the
            # normal shed treatment and retry elsewhere.
            return cfg.shed_retry_after_s
        depth_wm = cfg.max_queue_depth
        token_wm = cfg.max_queued_tokens
        if depth_wm is None and token_wm is None:
            return None
        over = (
            depth_wm is not None and self.engine.queue_depth >= depth_wm
        ) or (
            token_wm is not None
            and self.engine.queued_prompt_tokens >= token_wm
        )
        return cfg.shed_retry_after_s if over else None

    async def _h_generate(self, request: web.Request) -> web.Response:
        # Chaos injection point: tests arm this to kill/fail/stall THIS
        # server mid-rollout and prove clients fail over.
        await faults.maybe_fail_async("gserver.generate")
        d = await request.json()
        # Propagated deadline (base/rpc.py wire rule): a request whose
        # budget already expired is refused CHEAPLY — prefilling tokens
        # the caller will never consume just steals budget from live
        # requests. 429 + Retry-After 0: the client re-mints a budget
        # on its next attempt.
        deadline = rpc.Deadline.from_headers(request.headers)
        if deadline is not None and deadline.expired():
            rpc.stats.incr("deadline_expired")
            return web.json_response(
                {"qid": str(d.get("qid", "")), "error": "deadline expired",
                 "retry_after": 0.0},
                status=429, headers={"Retry-After": "0"},
            )
        # Admission control BEFORE the engine sees the request: beyond
        # the queue-depth/token watermark the server load-sheds with 429
        # so open-loop tail latency stays bounded (clients back off with
        # jitter and the manager spills the session to another server).
        retry_after = self._admission_overloaded()
        if retry_after is not None:
            self._n_shed += 1
            tracing.event(
                "server.load_shed", ctx=tracing.extract_from(d),
                qid=str(d.get("qid", "")),
                queue_depth=self.engine.queue_depth,
            )
            return web.json_response(
                {
                    "qid": str(d.get("qid", "")),
                    "error": "overloaded",
                    "retry_after": retry_after,
                    "queue_depth": self.engine.queue_depth,
                },
                status=429,
                headers={"Retry-After": str(max(1, int(-(-retry_after // 1))))},
            )
        # Request-scoped tracing: the client's chunk span is this span's
        # parent, so the merged timeline shows queue+compute time on the
        # server track inside the client's chunk.
        gen_span = tracing.start_span(
            "server.generate",
            ctx=tracing.extract_from(d),
            qid=str(d.get("qid", "")),
            prompt_len=len(d.get("input_ids") or []),
        )
        # Tiered-KV restore (docs/serving.md): a returning session
        # routed here without its parked prefix restores it from the
        # local host/disk tier — or pulls it from the peer the
        # manager's global prefix index named (``kv_source``) — BEFORE
        # submission, so admission sees a parked prefix and prefills
        # only the delta. Any failure degrades to the full re-prefill
        # this path exists to avoid; it can never fail the request.
        await self._maybe_restore_prefix(d, deadline=deadline)
        g = d.get("gconfig", {})
        # Disaggregated path: the manager paired a decode server into
        # this request — prefill to the first token here, hand the KV
        # off, let the decode server run the stream. Single-token
        # budgets and self-pairings serve locally.
        decode_url = d.get("decode_url") or None
        if (
            decode_url
            and decode_url != self.address
            and int(g.get("max_new_tokens", 256)) > 1
        ):
            return await self._h_generate_disagg(
                d, g, decode_url, gen_span, deadline=deadline
            )
        req = self._gen_request_from(d, g)
        try:
            res = await self._submit_and_wait(req)
        except RuntimeError as e:
            # Fail-fast path: the serve loop already died; keep the same
            # JSON error contract as the in-flight res.error branch below.
            if gen_span is not None:
                gen_span.end(error=str(e))
            return web.json_response(
                {"qid": req.qid, "error": str(e)}, status=500
            )
        if gen_span is not None:
            gen_span.end(
                n_tokens=len(res.output_ids),
                interrupted=res.interrupted,
                version_start=res.version_start,
                version_end=res.version_end,
                error=res.error or "",
            )
        if res.error is not None:
            # Serve-loop death: surface as a 500 so clients retry against
            # another server instead of treating it as an empty completion.
            return web.json_response(
                {"qid": res.qid, "error": res.error}, status=500
            )
        if res.interrupted:
            self._n_interrupted += 1
        return web.json_response(self._gen_response(res))

    def _gen_request_from(self, d: Dict, g: Dict) -> GenRequest:
        return GenRequest(
            qid=str(d["qid"]),
            input_ids=[int(t) for t in d["input_ids"]],
            max_new_tokens=int(g.get("max_new_tokens", 256)),
            min_new_tokens=int(g.get("min_new_tokens", 0)),
            greedy=bool(g.get("greedy", False)),
            temperature=float(g.get("temperature", 1.0)),
            top_p=float(g.get("top_p", 1.0)),
            top_k=int(g.get("top_k", -1)),
            stop_token_ids=tuple(g.get("stop_token_ids", [])),
            priority=int(d.get("priority", 1)),
        )

    async def _submit_and_wait(self, req: GenRequest):
        """Submit to the engine, await the result on this event loop.
        Raises RuntimeError when the serve loop is already dead."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def done_cb(res):
            loop.call_soon_threadsafe(
                lambda: fut.set_result(res) if not fut.done() else None
            )

        req.done_cb = done_cb
        self.engine.submit(req)
        return await fut

    @staticmethod
    def _gen_response(res, **extra) -> Dict:
        out = {
            "qid": res.qid,
            "output_ids": res.output_ids,
            "output_logprobs": res.output_logprobs,
            "no_eos": res.no_eos,
            "interrupted": res.interrupted,
            "version_start": res.version_start,
            "version_end": res.version_end,
            "latency": res.latency,
        }
        out.update(extra)
        return out

    # ------------------------------------------------------------------
    # Disaggregated prefill/decode (docs/serving.md)
    # ------------------------------------------------------------------

    async def _handoff_sess(self) -> "aiohttp.ClientSession":
        import aiohttp

        if self._handoff_session is None or self._handoff_session.closed:
            self._handoff_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=600)
            )
        return self._handoff_session

    def _stash_handoff(self, qid: str, meta: Dict, payload: bytes):
        """Park an exported blob for the decode server's chunked pull.
        An entry lives until its /kv_handoff POST returns — which spans
        the decode server's WHOLE decode stream, not just the pull — so
        the cap must cover the server's full admission concurrency or
        normal load evicts in-flight blobs (404 on the pull -> handoff
        counted failed -> local fallback, silently un-disaggregating
        the fleet). TTL pruning handles decode servers that died
        mid-pull."""
        now = time.monotonic()
        self._handoff_store[qid] = (meta, payload, now)
        for k in [
            k for k, (_, _, t) in self._handoff_store.items()
            if now - t > 600.0
        ]:
            self._handoff_store.pop(k, None)
        cap = max(32, 4 * self.cfg.max_concurrent_requests)
        while len(self._handoff_store) > cap:
            self._handoff_store.popitem(last=False)

    async def _h_generate_disagg(self, d, g, decode_url, gen_span,
                                 deadline=None):
        from areal_tpu.engine.kv_handoff import KVHandoffError

        qid = str(d["qid"])
        budget = int(g.get("max_new_tokens", 256))
        min_new = int(g.get("min_new_tokens", 0))
        # Prefill leg: run to the first sampled token only. The finish
        # parks the prompt's KV pages under this qid (prefix cache).
        first_req = self._gen_request_from(d, g)
        first_req.max_new_tokens = 1
        first_req.min_new_tokens = min(1, min_new)
        try:
            res = await self._submit_and_wait(first_req)
        except RuntimeError as e:
            if gen_span is not None:
                gen_span.end(error=str(e))
            return web.json_response({"qid": qid, "error": str(e)}, status=500)
        if res.error is not None:
            if gen_span is not None:
                gen_span.end(error=res.error)
            return web.json_response(
                {"qid": qid, "error": res.error}, status=500
            )
        if res.interrupted or not res.output_ids or not res.no_eos:
            # Interrupted (client resubmits), zero-budget degenerate, or
            # the first token already hit EOS: nothing to hand off.
            if res.interrupted:
                self._n_interrupted += 1
            if gen_span is not None:
                gen_span.end(
                    n_tokens=len(res.output_ids),
                    interrupted=res.interrupted, disagg="short-circuit",
                )
            return web.json_response(self._gen_response(res))
        first = int(res.output_ids[0])
        t_handoff0 = time.monotonic()

        # Export the KV blob (engine-thread gather via the loop door).
        exp_span = tracing.start_span(
            "server.kv_export", ctx=tracing.extract_from(d),
            qid=qid, decode_url=decode_url,
        )
        meta = payload = None
        try:
            meta, payload = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: self.engine.export_kv_handoff(
                    qid, compress=self.cfg.kv_handoff_compress
                ),
            )
        except (KeyError, KVHandoffError, RuntimeError, TimeoutError) as e:
            # Short prompt (< one page), pool pressure evicted the park,
            # or the loop door timed out: serve the remainder locally.
            logger.warning(f"{qid}: kv export unavailable ({e!r}); "
                           f"serving remainder locally")
            if exp_span is not None:
                exp_span.end(error=repr(e))
            return await self._disagg_local_remainder(
                d, g, res, first, gen_span, reason=f"export: {e!r}"
            )
        if exp_span is not None:
            exp_span.end(
                n_tokens=meta["n_tokens"], bytes=len(payload),
                export_ms=self.engine.last_kv_export_ms,
            )
        # Mid-handoff chaos point: a prefill server dying HERE leaves
        # the client's /generate hanging on a dead socket — the failover
        # path (failed_server_url -> eviction -> reroute) must absorb it.
        await faults.maybe_fail_async("gserver.kv_export")
        self._stash_handoff(qid, meta, payload)
        try:
            sess = await self._handoff_sess()
            # The decode hop inherits the rollout's REMAINING budget
            # (base/rpc.py wire rule), so its blob pull-back can never
            # out-wait the client that asked for it.
            hop_headers = (
                deadline.headers() if deadline is not None else {}
            )
            async with sess.post(
                f"{decode_url}/kv_handoff",
                headers=hop_headers,
                json=tracing.inject_ctx_into(
                    {
                        "qid": qid,
                        "meta": meta,
                        "source": self.address,
                        "first_token": first,
                        "gconfig": {
                            "max_new_tokens": budget - 1,
                            "min_new_tokens": max(0, min_new - 1),
                            "greedy": bool(g.get("greedy", False)),
                            "temperature": float(g.get("temperature", 1.0)),
                            "top_p": float(g.get("top_p", 1.0)),
                            "top_k": int(g.get("top_k", -1)),
                            "stop_token_ids": list(g.get("stop_token_ids", [])),
                        },
                    },
                    gen_span.ctx if gen_span is not None else None,
                ),
            ) as r:
                body = await r.json()
                ok = r.status == 200 and "output_ids" in body
        except Exception as e:
            ok, body = False, {"error": repr(e)}
        finally:
            self._handoff_store.pop(qid, None)
        if not ok:
            self._handoff_failed += 1
            logger.warning(
                f"{qid}: kv handoff to {decode_url} failed "
                f"({str(body.get('error'))[:200]}); serving remainder locally"
            )
            return await self._disagg_local_remainder(
                d, g, res, first, gen_span,
                reason=f"decode: {str(body.get('error'))[:120]}",
            )
        self._handoff_ok += 1
        self._last_handoff_ms = (time.monotonic() - t_handoff0) * 1000.0
        if gen_span is not None:
            gen_span.end(
                n_tokens=1 + len(body["output_ids"]),
                disagg="handoff", decode_url=decode_url,
                handoff_ms=self._last_handoff_ms,
            )
        return web.json_response({
            "qid": qid,
            "output_ids": [first] + [int(t) for t in body["output_ids"]],
            "output_logprobs": (
                res.output_logprobs
                + [float(x) for x in body["output_logprobs"]]
            ),
            "no_eos": bool(body["no_eos"]),
            "interrupted": bool(body["interrupted"]),
            "version_start": res.version_start,
            "version_end": int(body["version_end"]),
            "latency": time.monotonic() - (t_handoff0 - res.latency),
            "disagg": {
                "decode_url": decode_url,
                "handoff_bytes": len(payload),
                "handoff_ms": self._last_handoff_ms,
            },
        })

    async def _disagg_local_remainder(self, d, g, first_res, first,
                                      gen_span, reason: str):
        """Handoff fallback: finish the request on THIS engine (it holds
        or recomputes the prefix) so disaggregation failures degrade to
        unified serving instead of losing the rollout."""
        self._handoff_fallback += 1
        cont = self._gen_request_from(d, g)
        cont.input_ids = [int(t) for t in d["input_ids"]] + [first]
        cont.max_new_tokens = int(g.get("max_new_tokens", 256)) - 1
        cont.min_new_tokens = max(0, int(g.get("min_new_tokens", 0)) - 1)
        cont.priority = 0
        try:
            res2 = await self._submit_and_wait(cont)
        except RuntimeError as e:
            if gen_span is not None:
                gen_span.end(error=str(e))
            return web.json_response(
                {"qid": cont.qid, "error": str(e)}, status=500
            )
        if res2.error is not None:
            if gen_span is not None:
                gen_span.end(error=res2.error)
            return web.json_response(
                {"qid": res2.qid, "error": res2.error}, status=500
            )
        if res2.interrupted:
            self._n_interrupted += 1
        if gen_span is not None:
            gen_span.end(
                n_tokens=1 + len(res2.output_ids),
                disagg="local-fallback", fallback_reason=reason,
            )
        merged = self._gen_response(
            res2, disagg={"fallback": reason},
        )
        merged["output_ids"] = [first] + list(res2.output_ids)
        merged["output_logprobs"] = (
            list(first_res.output_logprobs) + list(res2.output_logprobs)
        )
        merged["version_start"] = first_res.version_start
        merged["latency"] = first_res.latency + res2.latency
        return web.json_response(merged)

    # ------------------------------------------------------------------
    # Tiered KV plane: restore + peer pull + /kv endpoints
    # (docs/serving.md "KV tiering + global prefix index")
    # ------------------------------------------------------------------

    async def _maybe_restore_prefix(
        self, d: Dict, deadline: Optional[rpc.Deadline] = None,
    ) -> Optional[str]:
        """Best-effort prefix restore for a returning session; returns
        the tier it hit ('host'/'disk'/'peer') or None. Never raises —
        every failure path is a plain re-prefill."""
        try:
            return await self._restore_prefix_impl(d, deadline=deadline)
        except Exception:
            logger.warning(
                f"kv restore for {d.get('qid')!r} failed; "
                f"falling back to re-prefill", exc_info=True,
            )
            return None

    async def _restore_prefix_impl(
        self, d: Dict, deadline: Optional[rpc.Deadline] = None,
    ) -> Optional[str]:
        qid = str(d.get("qid") or "")
        input_ids = [int(t) for t in (d.get("input_ids") or [])]
        eng = self.engine
        if (
            not qid
            or len(input_ids) <= self.cfg.kv_page_size
            or eng.has_parked(qid)
        ):
            return None
        kv_source = str(d.get("kv_source") or "")
        if eng.kv_tier is None and (
            not kv_source or kv_source == self.address
        ):
            return None
        # Chaos point: tests arm this to break restores and prove the
        # continuation still completes via re-prefill.
        await faults.maybe_fail_async("gserver.kv_restore")
        loop = asyncio.get_running_loop()
        t0 = time.monotonic()
        span_t0 = tracing.now_ns() if tracing.enabled() else 0
        # 1) Local tier (restore_from_tier blocks on the engine loop
        #    door + device staging: executor, never the event loop).
        if eng.kv_tier is not None:
            n = await loop.run_in_executor(
                None, eng.restore_from_tier, qid, input_ids
            )
            if n:
                self._last_kv_restore_ms = (time.monotonic() - t0) * 1000.0
                if tracing.enabled():
                    tracing.record_span(
                        "server.kv_restore", span_t0,
                        ctx=tracing.extract_from(d), qid=qid,
                        tier="local", n_tokens=n,
                    )
                return "local"
        # 2) Peer pull over /kv/{manifest,chunk} — the weight-plane hop
        #    applied to KV: hash-verified chunks, Range resume.
        if not kv_source or kv_source == self.address:
            return None
        sess = await self._handoff_sess()
        async with sess.get(
            f"{kv_source}/kv/manifest", params={"qid": qid}
        ) as r:
            if r.status != 200:
                self._kv_peer_failed += 1
                return None
            man = await r.json()
        hmeta = man.get("meta") or {}
        toks = [int(t) for t in (hmeta.get("tokens") or [])]
        use = min(len(toks), len(input_ids) - 1)
        if (
            use < self.cfg.kv_page_size
            or toks[:use] != input_ids[:use]
            or int(hmeta.get("version", -1)) != eng.version
        ):
            # Wrong content or stale version: don't pay the transfer.
            return None
        payload = await self._fetch_handoff_payload(
            kv_source, qid, hmeta, path="/kv/chunk", deadline=deadline
        )
        await loop.run_in_executor(
            None, eng.import_kv_handoff, hmeta, payload
        )
        self._kv_peer_hits += 1
        self._kv_peer_bytes += len(payload)
        self._last_kv_restore_ms = (time.monotonic() - t0) * 1000.0
        if tracing.enabled():
            tracing.record_span(
                "server.kv_restore", span_t0,
                ctx=tracing.extract_from(d), qid=qid, tier="peer",
                source=kv_source, n_tokens=len(toks),
                bytes=len(payload),
            )
        return "peer"

    async def _h_kv_manifest(self, request: web.Request) -> web.Response:
        """Peer-pull hop 1: the handoff meta for a prefix this server
        holds (tier entry served as-is; an HBM park is exported into
        the tier first so /kv/chunk can stream its bytes)."""
        from areal_tpu.base.wire_schemas import KV_TIER_V1

        qid = request.query.get("qid", "")
        try:
            # stage_peer_export can block on the engine loop door (HBM
            # export path): executor, never the event loop.
            meta = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.stage_peer_export, qid
            )
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        except Exception as e:
            return web.json_response({"error": repr(e)}, status=503)
        self._kv_manifests_served += 1
        return web.json_response({
            "schema": KV_TIER_V1, "qid": qid,
            "holder": self.address, "meta": meta,
        })

    @staticmethod
    async def _serve_ranged(
        payload: bytes, request: web.Request
    ) -> web.Response:
        """Range-aware byte serving shared by the handoff blob and the
        tier chunk endpoints. The ``gserver.kv_chunk_bytes`` chaos
        point (corrupt action) fires on the bytes ACTUALLY SERVED —
        the Range slice, like weight_plane.chunk_bytes — so an armed
        corruption is guaranteed to reach the puller's sha256 verify
        instead of possibly flipping bytes outside the requested
        window (a silent no-op drill); async because a delay/hang arm
        must wedge this one request, not the event loop."""
        rng = request.headers.get("Range")
        if rng and rng.startswith("bytes="):
            try:
                a, _, b = rng[len("bytes="):].partition("-")
                start = int(a)
                end = int(b) if b else len(payload) - 1
            except ValueError:
                return web.Response(status=416)
            if start >= len(payload):
                return web.Response(status=416)
            end = min(end, len(payload) - 1)
            body = await faults.maybe_corrupt_async(
                "gserver.kv_chunk_bytes", payload[start: end + 1]
            )
            return web.Response(
                body=body, status=206,
                headers={"Content-Range":
                         f"bytes {start}-{end}/{len(payload)}"},
            )
        body = await faults.maybe_corrupt_async(
            "gserver.kv_chunk_bytes", payload
        )
        return web.Response(body=body)

    async def _h_kv_chunk(self, request: web.Request) -> web.Response:
        """Peer-pull hop 2: serve a held prefix's payload bytes (the
        puller verifies per-chunk hashes — the hash, not this server,
        is the authority)."""
        qid = request.query.get("qid", "")
        # peer_payload may read (and hash-verify) a disk-tier entry:
        # executor, never the event loop.
        got = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.peer_payload, qid
        )
        if got is None:
            return web.json_response(
                {"error": f"no tiered prefix for {qid!r}"}, status=404
            )
        resp = await self._serve_ranged(got[1], request)
        self._kv_chunks_served += 1
        # Bytes actually on the wire (the Range slice), not the whole
        # payload per chunk request — a 10-chunk pull must read as one
        # payload, not ten.
        self._kv_chunk_bytes_served += len(resp.body or b"")
        return resp

    async def _h_kv_index(self, request: web.Request) -> web.Response:
        """Holdings advertisement for the manager's global prefix
        index: HBM parks (loop-refreshed snapshot) + tier entries."""
        from areal_tpu.base.wire_schemas import KV_TIER_V1

        eng = self.engine
        held = eng.parked_index()
        if eng.kv_tier is not None:
            held += await asyncio.get_running_loop().run_in_executor(
                None, eng.kv_tier.held
            )
        return web.json_response({
            "schema": KV_TIER_V1, "url": self.address, "held": held,
        })

    async def _h_kv_handoff(self, request: web.Request) -> web.Response:
        """Decode side: pull the blob from the prefill server (chunked,
        hash-verified, Range-resumable), import it into the engine, and
        run the decode stream as a priority-0 continuation."""
        await faults.maybe_fail_async("gserver.kv_import")
        d = await request.json()
        from areal_tpu.engine.kv_handoff import (
            KVHandoffError, KVHandoffVersionMismatch,
        )

        qid = str(d["qid"])
        meta = d["meta"]
        source = d["source"]
        imp_span = tracing.start_span(
            "server.kv_import", ctx=tracing.extract_from(d),
            qid=qid, source=source,
            n_tokens=int(meta.get("n_tokens", 0)),
        )
        t0 = time.monotonic()
        try:
            payload = await self._fetch_handoff_payload(
                source, qid, meta,
                deadline=rpc.Deadline.from_headers(request.headers),
            )
        except Exception as e:
            if imp_span is not None:
                imp_span.end(error=repr(e))
            return web.json_response(
                {"qid": qid, "error": f"transfer failed: {e!r}"}, status=502
            )
        self._last_kv_transfer_ms = (time.monotonic() - t0) * 1000.0
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.import_kv_handoff, meta, payload
            )
        except KVHandoffVersionMismatch as e:
            if imp_span is not None:
                imp_span.end(error=repr(e))
            return web.json_response(
                {"qid": qid, "error": str(e),
                 "version": self.engine.version},
                status=409,
            )
        except (KVHandoffError, RuntimeError, TimeoutError) as e:
            if imp_span is not None:
                imp_span.end(error=repr(e))
            return web.json_response(
                {"qid": qid, "error": str(e)}, status=503
            )
        g = d.get("gconfig", {})
        cont = self._gen_request_from(
            {"qid": qid,
             "input_ids": list(meta["tokens"]) + [int(d["first_token"])],
             "priority": 0},
            g,
        )
        try:
            res = await self._submit_and_wait(cont)
        except RuntimeError as e:
            if imp_span is not None:
                imp_span.end(error=str(e))
            return web.json_response({"qid": qid, "error": str(e)}, status=500)
        if res.error is not None:
            if imp_span is not None:
                imp_span.end(error=res.error)
            return web.json_response(
                {"qid": qid, "error": res.error}, status=500
            )
        if res.interrupted:
            self._n_interrupted += 1
        if imp_span is not None:
            imp_span.end(
                bytes=len(payload),
                transfer_ms=self._last_kv_transfer_ms,
                import_ms=self.engine.last_kv_import_ms,
                n_tokens_out=len(res.output_ids),
            )
        return web.json_response(self._gen_response(
            res,
            transfer_ms=self._last_kv_transfer_ms,
            import_ms=self.engine.last_kv_import_ms,
        ))

    async def _fetch_handoff_payload(
        self, source: str, qid: str, meta: Dict,
        path: str = "/kv_handoff/blob",
        deadline: Optional[rpc.Deadline] = None,
    ) -> bytes:
        """Chunked pull of a KV blob (the disagg export stash, or a
        peer's KV tier via ``path="/kv/chunk"``): per-chunk sha256
        verify, mid-chunk Range resume on torn reads — the weight-plane
        transfer discipline applied to the KV hop. Per-chunk attempts,
        timeouts and backoff come from the unified RPC policy
        (AREAL_RPC_* knobs, base/rpc.py) instead of the old hardcoded
        4-attempt/0.05s loop, and the caller's propagated deadline caps
        every attempt — a rollout with 2s of budget left never waits a
        full blob timeout here.

        Regression note (areal-lint blocking-async): verify_chunk used
        to run inline here — sha256 over a multi-MB KV chunk is ~10ms+
        of CPU per chunk on the 2-core host, paid ON the event loop
        while this decode server is streaming other requests' tokens
        (the PR 7 ITL-stall class). It now runs in the default
        executor, like the weight plane's ChunkStore.fetch."""
        from areal_tpu.base.chunking import chunk_spans, verify_chunk

        index = meta["chunks"]
        total = int(index["total_bytes"])
        buf = bytearray(total)
        sess = await self._handoff_sess()
        policy = rpc.default_policy()
        for i, (off, length) in enumerate(
            chunk_spans(total, int(index["chunk_bytes"]))
        ):
            state = {"got": 0}

            async def attempt(attempt_timeout: float) -> None:
                import aiohttp

                start = off + state["got"]
                dl = (deadline or rpc.Deadline.after(attempt_timeout))
                try:
                    async with sess.get(
                        f"{source}{path}",
                        params={"qid": qid},
                        headers=dl.headers(
                            {"Range": f"bytes={start}-{off + length - 1}"}
                        ),
                        timeout=aiohttp.ClientTimeout(total=attempt_timeout),
                    ) as r:
                        if r.status not in (200, 206):
                            raise OSError(
                                f"blob fetch {r.status}: "
                                f"{(await r.text())[:200]}"
                            )
                        data = await r.read()
                        if r.status == 200:
                            # Range-less server: slice the full payload.
                            data = data[start: off + length]
                except aiohttp.ClientError as e:
                    raise OSError(f"blob fetch failed: {e!r}") from e
                take = min(len(data), length - state["got"])
                buf[start: start + take] = data[:take]
                state["got"] += take
                if state["got"] < length:
                    raise OSError(
                        f"short read {state['got']}/{length}"
                    )  # Range resume continues from the new offset
                ok = await asyncio.get_running_loop().run_in_executor(
                    None, verify_chunk,
                    bytes(buf[off: off + length]), index["hashes"][i],
                )
                if not ok:
                    state["got"] = 0  # corrupt chunk: refetch whole
                    raise ValueError(f"chunk {i} content-hash mismatch")

            try:
                await rpc.retry_async(
                    attempt, policy=policy, deadline=deadline,
                    retryable=rpc.RETRYABLE_DEFAULT,
                    what=f"kv chunk {i} <- {source}{path}",
                )
            except rpc.RpcError as e:
                raise RuntimeError(
                    f"chunk {i} unrecoverable after retries: {e}"
                ) from e
        return bytes(buf)

    async def _h_kv_blob(self, request: web.Request) -> web.Response:
        qid = request.query.get("qid", "")
        ent = self._handoff_store.get(qid)
        if ent is None:
            return web.json_response(
                {"error": f"no handoff blob for {qid!r}"}, status=404
            )
        return await self._serve_ranged(ent[1], request)

    # ------------------------------------------------------------------
    # Drain-then-leave + KV migration (docs/fault_tolerance.md
    # "Fleet elasticity + manager HA")
    # ------------------------------------------------------------------

    async def _h_drain(self, request: web.Request) -> web.Response:
        """Drain-then-leave, server side: quiesce admission NOW (every
        new /generate sheds 429), let in-flight work finish, migrate
        parked prefixes to the given peers over the /kv wire, then
        deregister and exit with a graceful heartbeat-stop marker the
        manager folds into a clean removal. Returns immediately; GET
        /drain reports progress."""
        await faults.maybe_fail_async("gserver.drain")
        d = await request.json()
        if self._draining:
            return web.json_response(
                {"success": True, "already": True, **self._drain_state}
            )
        self._draining = True
        migrate = [
            u for u in (d.get("migrate_to") or [])
            if u and u != self.address
        ]
        self._drain_state.update(
            draining=True, reason=str(d.get("reason") or "")
        )
        hb = getattr(self, "_heartbeat", None)
        if hb is not None:
            # Advertise the drain through the heartbeat (name_resolve
            # file I/O: executor, never the event loop).
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: hb.update_payload(draining=True)
            )
        # Keep a strong reference: the loop holds tasks weakly, and a
        # GC'd drain task would leave the server shedding 429 forever
        # without ever migrating or exiting.
        self._drain_task_handle = asyncio.get_running_loop().create_task(
            self._drain_task(migrate, bool(d.get("exit", True)))
        )
        tracing.event(
            "server.drain", ctx=tracing.extract_from(d),
            n_targets=len(migrate), reason=str(d.get("reason") or ""),
        )
        logger.info(
            f"drain started ({d.get('reason')!r}): migrating KV to "
            f"{len(migrate)} peer(s), {self.engine.n_running} in flight"
        )
        return web.json_response({"success": True, "draining": True})

    async def _h_drain_status(self, request: web.Request) -> web.Response:
        return web.json_response({
            "address": self.address, **self._drain_state,
            "n_running": self.engine.n_running,
            "queue_depth": self.engine.queue_depth,
        })

    async def _drain_task(self, migrate_to, exit_after: bool):
        t0 = time.monotonic()
        loop = asyncio.get_running_loop()
        # Function-scope counters: the failure path below must report
        # honest numbers (whatever was NOT migrated when the task died
        # is lost with the process — never a clean 0/0 departure).
        held: Dict[str, int] = {}
        migrated = lost = stale = 0
        # Lower bound for the failure path: if the authoritative
        # loop-door enumeration below never completes (wedged engine),
        # the snapshot count keeps the loss report honest instead of a
        # clean 0/0 departure. Not used for migration itself — the
        # snapshot can contain already-consumed parks.
        snap_count = len(self.engine.parked_index())
        try:
            # 1) Quiesce: admission already sheds; wait out in-flight
            #    requests (bounded — a wedged slot must not block the
            #    departure forever).
            deadline = t0 + self.cfg.drain_wait_s
            while time.monotonic() < deadline:
                if (
                    self.engine.n_running == 0
                    and self.engine.queue_depth == 0
                ):
                    break
                await asyncio.sleep(0.1)
            # 2) Migrate parked prefixes (HBM parks + tier entries)
            #    over the hash-verified /kv wire: peers pull chunks
            #    from our /kv/chunk and park them in THEIR tier, so
            #    returning sessions restore there instead of paying a
            #    full re-prefill. Version-stale entries are dropped
            #    (unrestorable under the current weights — not a loss).
            # Authoritative loop-door read, NOT the ~0.2s-stale
            # snapshot: a prefix parked moments before the drain must
            # not be silently left behind (parked entries carry the
            # live engine version).
            parked = await loop.run_in_executor(
                None, self.engine.parked_qids_now
            )
            for qid in parked:
                held[qid] = int(self.engine.version)
            if self.engine.kv_tier is not None:
                for e in await loop.run_in_executor(
                    None, self.engine.kv_tier.held
                ):
                    held.setdefault(e["qid"], int(e.get("version", -1)))
            self._drain_state["held"] = len(held)
            sess = (
                await self._handoff_sess() if migrate_to and held else None
            )
            for i, (qid, ver) in enumerate(sorted(held.items())):
                if ver >= 0 and ver != self.engine.version:
                    stale += 1
                    continue
                ok = False
                peer_409 = False
                if sess is not None:
                    try:
                        # stage_peer_export blocks on the engine loop
                        # door for HBM parks: executor.
                        meta = await loop.run_in_executor(
                            None, self.engine.stage_peer_export, qid
                        )
                    except Exception:
                        logger.warning(
                            f"drain: staging {qid!r} failed",
                            exc_info=True,
                        )
                        meta = None
                    # Rotate through EVERY peer starting at this
                    # prefix's round-robin home: one tierless or
                    # blipping peer must not turn its share of the
                    # prefixes into losses the others would accept.
                    k = i % len(migrate_to)
                    targets = migrate_to[k:] + migrate_to[:k]
                    for target in targets if meta is not None else []:
                        try:
                            async with sess.post(
                                f"{target}/kv/accept",
                                json={"qid": qid, "meta": meta,
                                      "source": self.address},
                            ) as r:
                                body = await r.json()
                                ok = r.status == 200 and bool(
                                    body.get("success")
                                )
                                peer_409 = r.status == 409
                        except Exception:
                            logger.warning(
                                f"drain: migrating {qid!r} to "
                                f"{target} failed", exc_info=True,
                            )
                        if ok or peer_409:
                            # 409 = version skew; every peer sits at
                            # the same fleet version — no point asking
                            # the rest.
                            break
                if ok:
                    migrated += 1
                elif peer_409:
                    # The PEER rejected on version skew: the fleet cut
                    # over to a new version while we drained (draining
                    # servers are excluded from fanouts, so OUR engine
                    # version froze and the local check above cannot
                    # see it). The prefix is unrestorable under the
                    # fleet's current weights — stale, not lost.
                    stale += 1
                else:
                    lost += 1
            self._drain_state.update(
                migrated=migrated, lost=lost, stale_dropped=stale,
                drain_ms=(time.monotonic() - t0) * 1000.0, done=True,
            )
            # 3) Deregister the per-index discovery record (the
            #    heartbeat-stop in the worker exit path is the
            #    authoritative departed marker); carry the drain
            #    results on that final record for the manager's log.
            def _dereg():
                try:
                    name_resolve.delete(names.gen_server_url(
                        self.cfg.experiment_name, self.cfg.trial_name,
                        str(self.cfg.server_index),
                    ))
                except Exception:
                    pass

            await loop.run_in_executor(None, _dereg)
            hb = getattr(self, "_heartbeat", None)
            if hb is not None:
                await loop.run_in_executor(
                    None,
                    lambda: hb.update_payload(
                        drain_migrated=migrated, drain_lost=lost
                    ),
                )
            logger.info(
                f"drain complete in "
                f"{self._drain_state['drain_ms']:.0f}ms: migrated "
                f"{migrated}, lost {lost}, stale {stale} of "
                f"{len(held)} held prefix(es)"
            )
        except Exception:
            # Honest accounting: everything held and not yet migrated
            # (or proven stale) dies with this process — report it as
            # lost on the final heartbeat instead of a clean 0/0. The
            # snapshot lower bound covers failures BEFORE the
            # authoritative enumeration populated `held`.
            lost = max(
                lost,
                len(held) - migrated - stale,
                snap_count - migrated - stale,
            )
            self._drain_state.update(
                migrated=migrated, lost=lost, stale_dropped=stale,
                done=True, failed=True,
                drain_ms=(time.monotonic() - t0) * 1000.0,
            )
            hb = getattr(self, "_heartbeat", None)
            if hb is not None:
                try:
                    await loop.run_in_executor(
                        None,
                        lambda: hb.update_payload(
                            drain_migrated=migrated, drain_lost=lost
                        ),
                    )
                except Exception:
                    pass
            logger.exception("drain task failed")
        finally:
            if exit_after:
                # Poll loop exits; Worker.run()'s finally stops the
                # heartbeat with the graceful marker and runs
                # _exit_hook.
                self.exit()

    async def _h_kv_accept(self, request: web.Request) -> web.Response:
        """Drain-migration ingest: pull a departing peer's prefix blob
        over the hash-verified /kv/chunk wire and park it in the LOCAL
        tier (no HBM import — the session may return to any server;
        the entry is advertised via /kv/index, so the manager's global
        prefix index re-routes returning sessions here)."""
        await faults.maybe_fail_async("gserver.kv_accept")
        d = await request.json()
        qid = str(d.get("qid") or "")
        meta = d.get("meta") or {}
        source = str(d.get("source") or "")
        if self.engine.kv_tier is None:
            return web.json_response(
                {"success": False, "error": "no kv tier"}, status=503
            )
        if not qid or not source or not meta:
            return web.json_response(
                {"success": False, "error": "qid/meta/source required"},
                status=400,
            )
        if int(meta.get("version", -1)) != self.engine.version:
            return web.json_response(
                {"success": False,
                 "error": f"version {meta.get('version')} != "
                          f"{self.engine.version}"},
                status=409,
            )
        try:
            payload = await self._fetch_handoff_payload(
                source, qid, meta, path="/kv/chunk",
                deadline=rpc.Deadline.from_headers(request.headers),
            )
        except Exception as e:
            return web.json_response(
                {"success": False, "error": f"transfer failed: {e!r}"},
                status=502,
            )
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.kv_tier.put, qid, meta, payload
        )
        self._kv_accepted += 1
        self._kv_accept_bytes += len(payload)
        tracing.event(
            "server.kv_accept", ctx=tracing.extract_from(d),
            qid=qid, source=source, bytes=len(payload),
        )
        return web.json_response({"success": True, "bytes": len(payload)})

    async def _h_set_role(self, request: web.Request) -> web.Response:
        """Elastic re-role (manager sizer): flip the live pool role.
        Drain + flip — in-flight requests finish under the old behavior
        (the engine is identical either way); the manager already
        stopped routing the old kind of work here. Weights stay
        resident."""
        d = await request.json()
        role = str(d.get("role", ""))
        if role not in ("unified", "prefill", "decode"):
            return web.json_response(
                {"success": False, "error": f"bad role {role!r}"}, status=400
            )
        with self._role_lock:
            prev, self.role = self.role, role
        tracing.event(
            "server.set_role", ctx=tracing.extract_from(d),
            role=role, previous=prev, n_running=self.engine.n_running,
        )
        logger.info(f"re-roled {prev} -> {role} "
                    f"({self.engine.n_running} in flight)")
        return web.json_response({
            "success": True, "role": role, "previous": prev,
            "n_running": self.engine.n_running,
            "queue_depth": self.engine.queue_depth,
        })

    async def _h_configure(self, request: web.Request) -> web.Response:
        """Runtime admission-watermark overrides (bench A/B arms flip
        backpressure off and back without restarting the fleet), plus —
        ONLY when the AREAL_CHAOS_HTTP knob armed it at boot — runtime
        fault-injection control: ``{"faults": "<AREAL_FAULTS spec>"}``
        arms points in THIS process, ``{"faults_reset": true}`` clears
        them, and the response carries per-point hit counts. The chaos
        campaign (tests/system/test_chaos_campaign.py) sweeps every
        declared fault point against one long-lived subprocess fleet
        through this; a production fleet (knob off) refuses with 403."""
        d = await request.json()
        chaos_keys = (
            "faults" in d or d.get("faults_reset") or "faults_hits" in d
        )
        # Refusals FIRST, before anything mutates: a request the server
        # answers 403/400 must leave zero trace — no half-applied
        # watermarks, no arms left standing behind an error response.
        if chaos_keys:
            from areal_tpu.base import env_registry

            if not env_registry.get_bool("AREAL_CHAOS_HTTP"):
                return web.json_response(
                    {"success": False,
                     "error": "chaos control disabled "
                              "(set AREAL_CHAOS_HTTP=1 at server boot)"},
                    status=403,
                )
            try:
                # Registry-verified: a typo'd point in a remote hits
                # query must 400, not silently report 0 hits — and a
                # typo'd point in an arming spec must 400, not arm a
                # silent no-op behind success:True.
                for p in d.get("faults_hits", []):
                    faults.check_declared(str(p))
                for entry in str(d.get("faults") or "").split(";"):
                    entry = entry.strip()
                    if entry:
                        faults.check_declared(
                            entry.partition("=")[0].partition("@")[0].strip()
                        )
            except ValueError as e:
                return web.json_response(
                    {"success": False, "error": str(e)}, status=400,
                )
        changed = {}
        for key, cast in (("max_queue_depth", int),
                          ("max_queued_tokens", int),
                          ("shed_retry_after_s", float)):
            if key in d:
                val = d[key]
                setattr(self.cfg, key, None if val is None else cast(val))
                changed[key] = val
        resp = {"success": True, "changed": changed}
        if chaos_keys:
            if d.get("faults_reset"):
                faults.reset()
                changed["faults_reset"] = True
            spec = d.get("faults")
            if spec:
                faults.load_env(str(spec))
                changed["faults"] = spec
            resp["faults_armed"] = faults.armed_points()
            resp["faults_hits"] = {
                p: faults.hits_declared(str(p))
                for p in d.get("faults_hits", [])
            }
        return web.json_response(resp)

    async def _h_update_weights(self, request: web.Request) -> web.Response:
        await faults.maybe_fail_async("gserver.update_weights")
        d = await request.json()
        upd_span = tracing.start_span(
            "server.weight_update",
            ctx=tracing.extract_from(d),
            version=d.get("version"),
            n_running=self.engine.n_running,
        )
        model_path = d["model_path"]
        allow_interrupt = bool(d.get("allow_interrupt", True))
        version = d.get("version")
        # is_stale_update takes the engine's stage lock, which an
        # in-flight update_params holds for the whole multi-second
        # staging — run it in the executor like everything else that can
        # block, or every in-flight HTTP response stalls behind it.
        stale = await asyncio.get_running_loop().run_in_executor(
            None,
            self.engine.is_stale_update,
            None if version is None else int(version),
        )
        if stale:
            # Retry of a version that already staged/landed (manager
            # flush timeout): skip the multi-GB reload entirely, but
            # still honor the interrupt escalation — the retry may be
            # asking a drain-blocked staging to stop waiting.
            if allow_interrupt:
                self.engine.escalate_pending_interrupt()
            logger.info(f"skipping stale weight update v{version}")
            if upd_span is not None:
                upd_span.end(stale=True)
            return web.json_response(
                {"success": True, "stale": True,
                 "num_paused_requests": self.engine.n_running}
            )
        try:
            params, info = await asyncio.get_running_loop().run_in_executor(
                None, self._load_params, model_path,
                None if version is None else int(version),
            )
        except Exception as e:
            logger.exception("weight update load failed")
            if upd_span is not None:
                upd_span.end(error=repr(e))
            return web.json_response({"success": False, "error": repr(e)}, status=500)
        self._last_load_info = info
        n_running = self.engine.n_running
        # update_params stages the full host->device transfer on the
        # calling thread — keep it off the event loop like the load, or
        # every in-flight HTTP response stalls behind it.
        await asyncio.get_running_loop().run_in_executor(
            None,
            lambda: self.engine.update_params(
                params,
                allow_interrupt=allow_interrupt,
                version=None if version is None else int(version),
            ),
        )
        logger.info(
            f"weight update: source={info['source']} "
            f"load={info['load_s']:.3f}s dump_version={info['version']}"
        )
        if upd_span is not None:
            upd_span.end(
                source=info["source"], load_s=info["load_s"],
                n_paused=n_running,
            )
        return web.json_response(
            {
                "success": True,
                "num_paused_requests": n_running,
                "load_s": info["load_s"],
                "source": info["source"],
            }
        )

    def _load_params(self, model_path: str, want_version=None):
        """Fastest source first: tmpfs raw -> disk raw -> pickle -> HF
        (system/weight_transfer.load_for_serving). With a pinned
        want_version, a dump that doesn't hold exactly that version
        raises WeightVersionMismatch after brief retries — the manager
        pins the engine to its published version, so silently loading a
        stale raw dump (or a version:-1 pickle/HF fallback) would serve
        old weights under a new version label."""
        from areal_tpu.system.weight_transfer import (
            load_for_serving, shm_transfer_dir,
        )

        # The realloc dump dir is .../param_realloc/<role>; the tmpfs
        # fast-path dump (model_worker._param_realloc) is keyed by the
        # same role name.
        role = os.path.basename(model_path.rstrip("/"))
        shm = shm_transfer_dir(
            self.cfg.experiment_name, self.cfg.trial_name, role
        )
        return load_for_serving(
            model_path, shm_dir=shm, want_version=want_version
        )

    # ------------------------------------------------------------------
    # Weight-distribution plane (system/weight_plane.py)
    # ------------------------------------------------------------------

    async def _h_distribute_weights(self, request: web.Request) -> web.Response:
        """Prefetch version-N chunks into host memory while version N-1
        keeps serving. Returns when the payload is complete+verified, so
        the manager can use this server as a parent in the next wave."""
        await faults.maybe_fail_async("gserver.distribute_weights")
        d = await request.json()
        version = int(d["version"])
        upstreams = [u for u in (d.get("upstreams") or []) if u]
        origin = d.get("origin")
        # A sharded server accepts exactly ITS shard's stream: fetching
        # another rank's slice would waste a full shard of ingress and
        # the cutover below could never place it.
        man_shard = (d.get("manifest") or {}).get("shard") or {}
        man_key = (
            int(man_shard.get("tp_rank") or 0),
            int(man_shard.get("tp_degree") or 1),
        )
        want_key = getattr(self, "_weight_shard", None) or (0, 1)
        if man_key != want_key:
            # Teach the caller our real spec: a manager whose shard map
            # hasn't caught up yet (fanout racing the first heartbeat)
            # corrects itself from this instead of evicting us.
            return web.json_response(
                {"success": False,
                 "error": f"manifest shard {man_key} != server shard "
                          f"{want_key}",
                 "weight_shard": list(want_key)},
                status=409,
            )
        fetch_span = tracing.start_span(
            "server.weight_fetch",
            ctx=tracing.extract_from(d),
            version=version, n_upstreams=len(upstreams),
        )
        with self._wp_lock:
            held = self._wp_store
            joining = False
            if held is not None and held.version > version:
                # A stale edge (manager retry from an older fanout):
                # reject before paying the model-sized staging
                # allocation below.
                if fetch_span is not None:
                    fetch_span.end(error="superseded")
                return web.json_response(
                    {"success": False,
                     "error": f"superseded by v{held.version}"},
                    status=409,
                )
            if held is not None and held.version == version:
                if self._wp_state == "ready":
                    # Manager retry / duplicate edge: already holding it.
                    if fetch_span is not None:
                        fetch_span.end(already_held=True)
                    return web.json_response(
                        {"success": True, "already_held": True,
                         "transfer_ms": self._wp_transfer_ms,
                         "verify_ms": self._wp_verify_ms}
                    )
                if self._wp_state == "fetching":
                    # A duplicate for an IN-FLIGHT fetch (manager retry
                    # after a wave timeout) joins it instead of
                    # replacing the store: restarting from byte 0 would
                    # discard every verified chunk, and a transfer
                    # slower than the manager's timeout could then
                    # never complete at all.
                    store, joining = held, True
        if not joining:
            # The store's host-memory staging buffer is model-sized and
            # zero-filled at construction: allocate on an executor
            # thread so the event loop keeps streaming in-flight
            # /generate responses (the whole point of the overlap).
            try:
                store = await asyncio.get_running_loop().run_in_executor(
                    None, ChunkStore, d["manifest"]
                )
            except Exception as e:
                if fetch_span is not None:
                    fetch_span.end(error=repr(e))
                return web.json_response(
                    {"success": False, "error": repr(e)}, status=400
                )
            with self._wp_lock:
                held = self._wp_store
                if held is not None and held.version > version:
                    # A newer version landed while we allocated; this
                    # edge is stale — publishing ours would roll the
                    # holder back.
                    if fetch_span is not None:
                        fetch_span.end(error="superseded")
                    return web.json_response(
                        {"success": False,
                         "error": f"superseded by v{held.version}"},
                        status=409,
                    )
                if held is not None and held.version == version:
                    if self._wp_state == "ready":
                        if fetch_span is not None:
                            fetch_span.end(already_held=True)
                        return web.json_response(
                            {"success": True, "already_held": True,
                             "transfer_ms": self._wp_transfer_ms,
                             "verify_ms": self._wp_verify_ms}
                        )
                    if self._wp_state == "fetching":
                        # A concurrent duplicate won the publish while
                        # we allocated: join its in-flight fetch.
                        store, joining = held, True
                if not joining:
                    self._wp_store = store
                    self._wp_state = "fetching"

        if joining:
            deadline = time.monotonic() + float(d.get("deadline_s") or 600.0)

            def _await_inflight():
                while time.monotonic() < deadline:
                    with self._wp_lock:
                        if self._wp_store is not store:
                            return "superseded"
                        if self._wp_state != "fetching":
                            return self._wp_state
                    time.sleep(0.05)
                return "timeout"

            state = await asyncio.get_running_loop().run_in_executor(
                None, _await_inflight
            )
            with self._wp_lock:
                body = {"success": state == "ready", "joined": True,
                        "transfer_ms": self._wp_transfer_ms,
                        "verify_ms": self._wp_verify_ms}
            if state != "ready":
                body["error"] = f"in-flight fetch ended: {state}"
            if fetch_span is not None:
                fetch_span.end(joined=True, state=state)
            return web.json_response(
                body, status=200 if state == "ready" else 500
            )

        def _fetch():
            faults.maybe_fail("gserver.weight_fetch")
            return store.fetch(
                upstreams,
                origin=origin,
                timeout=float(d.get("chunk_timeout") or 30.0),
                deadline_s=float(d.get("deadline_s") or 600.0),
            )

        try:
            stats = await asyncio.get_running_loop().run_in_executor(None, _fetch)
        except Exception as e:
            with self._wp_lock:
                if self._wp_store is store:
                    self._wp_state = "failed"
            logger.exception("weight-plane prefetch failed")
            if fetch_span is not None:
                fetch_span.end(error=repr(e))
            return web.json_response(
                {"success": False, "error": repr(e)}, status=500
            )
        with self._wp_lock:
            # Both the state flip AND the telemetry are guarded: a fetch
            # superseded by a newer /distribute_weights must not clobber
            # the live version's transfer numbers on /metrics.
            if self._wp_store is store:
                self._wp_state = "ready"
                self._wp_transfer_ms = stats["fetch_s"] * 1000.0
                self._wp_verify_ms = stats["verify_s"] * 1000.0
                self._wp_bytes_from_origin = stats["bytes_from_origin"]
                self._wp_bytes_from_peers = stats["bytes_from_peers"]
                self._wp_expected_bytes = stats["expected_bytes"]
                self._wp_ingress_eq = stats["ingress_payload_equivalents"]
                self._wp_wire = stats.get("wire") or "raw"
        logger.info(
            f"weight-plane prefetch v{version}: "
            f"{stats['total_bytes']} bytes in {stats['fetch_s']:.3f}s "
            f"(origin {stats['bytes_from_origin']}, "
            f"peers {stats['bytes_from_peers']}); still serving "
            f"v{self.engine.version}"
        )
        if fetch_span is not None:
            fetch_span.end(
                fetch_s=stats["fetch_s"], verify_s=stats["verify_s"],
                bytes_from_origin=stats["bytes_from_origin"],
                bytes_from_peers=stats["bytes_from_peers"],
            )
        return web.json_response(
            {"success": True,
             "transfer_ms": self._wp_transfer_ms,
             "verify_ms": self._wp_verify_ms,
             "bytes_from_origin": stats["bytes_from_origin"],
             "bytes_from_peers": stats["bytes_from_peers"],
             "n_chunks": stats["n_chunks"],
             "resumed_chunks": stats["resumed_chunks"]}
        )

    async def _h_cutover_weights(self, request: web.Request) -> web.Response:
        """Swap to the prefetched version: interrupt in-flight requests
        (partial results return for client re-prefill), device-put the
        host buffer, flip. Measured end-to-end, separately from the
        transfer, and compared against the cutover budget."""
        await faults.maybe_fail_async("gserver.cutover_weights")
        d = await request.json()
        version = int(d["version"])
        budget_s = float(d.get("budget_s") or 0.0)
        cut_span = tracing.start_span(
            "server.weight_cutover",
            ctx=tracing.extract_from(d),
            version=version, n_running=self.engine.n_running,
        )
        with self._wp_lock:
            store = self._wp_store
            if (
                store is None or store.version != version
                or self._wp_state != "ready"
            ):
                if cut_span is not None:
                    cut_span.end(error="not holding")
                return web.json_response(
                    {"success": False,
                     "error": f"not holding v{version} "
                              f"(state={self._wp_state})"},
                    status=409,
                )
        n_running = self.engine.n_running

        def _cut():
            shard = store.manifest.get("shard") or {}
            degree = int(shard.get("tp_degree") or 1)
            if degree > 1:
                # Sliced manifest: the leaves ARE this rank's local
                # shards — device_put them straight under the engine's
                # NamedSharding (make_array path), no model-sized host
                # assembly. Requires the engine's addressable mesh slice
                # to be exactly this rank (multi-host TP); anything else
                # fails loudly and the manager evicts/re-syncs.
                from areal_tpu.engine.weight_client import assemble_leaves

                rank = int(shard.get("tp_rank") or 0)
                leaves = assemble_leaves(store)
                gshapes = {
                    e["path"]: tuple(e["global_shape"])
                    for e in store.manifest["leaves"]
                    if "global_shape" in e
                }
                return self.engine.cutover_shard_leaves(
                    {rank: leaves}, degree, version=store.version,
                    allow_interrupt=bool(d.get("allow_interrupt", True)),
                    timeout_s=max(120.0, budget_s * 10.0),
                    global_shapes=gshapes,
                )
            params, v = assemble_params(store)
            return self.engine.cutover_params(
                params, version=v,
                allow_interrupt=bool(d.get("allow_interrupt", True)),
                timeout_s=max(120.0, budget_s * 10.0),
            )

        try:
            cut_s = await asyncio.get_running_loop().run_in_executor(None, _cut)
        except Exception as e:
            logger.exception("weight-plane cutover failed")
            if cut_span is not None:
                cut_span.end(error=repr(e))
            return web.json_response(
                {"success": False, "error": repr(e)}, status=500
            )
        with self._wp_lock:
            self._wp_cutover_ms = cut_s * 1000.0
        self._last_load_info = {
            "source": "weight_plane", "version": version,
            "load_s": self._wp_transfer_ms / 1000.0,
        }
        within = budget_s <= 0.0 or cut_s <= budget_s
        if not within:
            logger.warning(
                f"weight cutover v{version} took {cut_s:.3f}s, over the "
                f"{budget_s:.3f}s budget"
            )
        logger.info(
            f"weight-plane cutover to v{version}: {cut_s * 1000:.1f}ms "
            f"({n_running} request(s) interrupted)"
        )
        if cut_span is not None:
            cut_span.end(
                cutover_s=cut_s, within_budget=within, n_paused=n_running
            )
        return web.json_response(
            {"success": True,
             "cutover_ms": cut_s * 1000.0,
             "transfer_ms": self._wp_transfer_ms,
             "within_budget": within,
             "num_paused_requests": n_running}
        )

    async def _h_weights_manifest(self, request: web.Request) -> web.Response:
        with self._wp_lock:
            store = self._wp_store
        return serve_store_manifest(store, request)

    async def _h_weights_chunk(self, request: web.Request) -> web.Response:
        """Peer hop: serve a verified chunk to a sibling. Valid during
        an in-flight prefetch too (ChunkStore marks chunks servable the
        moment they verify), so deeper tree levels can pipeline."""
        await faults.maybe_fail_async("weight_plane.serve_chunk")
        with self._wp_lock:
            store = self._wp_store
        # The chunk copy (up to weight_chunk_bytes) goes off the event
        # loop: this loop also serves /generate, and a fanout wave means
        # one request per chunk per child — blocking it would defeat the
        # transfer-overlaps-serving design.
        resp, served = await asyncio.get_running_loop().run_in_executor(
            None, serve_store_chunk, store, request
        )
        if served:
            with self._wp_lock:
                self._wp_chunks_served += 1
                self._wp_bytes_served += served
        return resp

    async def _h_metrics(self, request: web.Request) -> web.Response:
        from areal_tpu.base.latency import encode_counts

        m = self.engine.metrics()
        snap = self.engine.latency_snapshot()
        rpc_snap = rpc.stats.snapshot()
        lines = [
            f"areal:num_running_reqs {m['num_running_reqs']}",
            f"areal:num_used_tokens {m['num_used_tokens']}",
            f"areal:total_generated_tokens {m['total_generated']}",
            f"areal:queue_depth {m['queue_depth']}",
            f"areal:queued_prompt_tokens {m['queued_prompt_tokens']}",
            # Admission control: requests shed with 429 (deliberate
            # load-shedding, NOT failures — the manager must never count
            # these toward eviction).
            f"areal:load_shed_total {float(self._n_shed)}",
            # Per-request latency SLOs from the engine loop. Percentiles
            # for humans; raw bucket counts (base/latency.py edges,
            # sparse i:count) for the manager's ratio-of-sums fleet
            # aggregation — percentiles cannot be averaged.
            f"areal:ttft_p50_ms {snap['ttft_p50_ms']}",
            f"areal:ttft_p99_ms {snap['ttft_p99_ms']}",
            f"areal:itl_p50_ms {snap['itl_p50_ms']}",
            f"areal:itl_p99_ms {snap['itl_p99_ms']}",
            f"areal:ttft_hist {encode_counts(snap['ttft_counts']) or '-'}",
            f"areal:itl_hist {encode_counts(snap['itl_counts']) or '-'}",
            f"areal:num_interrupted_reqs {float(self._n_interrupted)}",
            f"areal:weight_version {float(self.engine.version)}",
            f"areal:kv_pages_free {m['kv_pages_free']}",
            f"areal:kv_pages_total {m['kv_pages_total']}",
            # Decode-time MoE router telemetry (zeros for dense models):
            # last-block layer-mean realized drop rate and router
            # entropy, from the packed decode-block columns.
            f"areal:moe_drop_rate {m.get('moe_drop_rate', 0.0)}",
            f"areal:moe_router_entropy {m.get('moe_router_entropy', 0.0)}",
            # Disaggregated serving: live pool role (string surface, like
            # the histogram lines), elastic eligibility (configured role
            # is the re-role pool), and the KV-handoff counters.
            f"areal:role {self.role}",
            f"areal:model_id {self.cfg.model_id or '-'}",
            f"areal:elastic {1.0 if self.cfg.role == 'unified' else 0.0}",
            f"areal:kv_export_total {m['kv_export_total']}",
            f"areal:kv_export_bytes {m['kv_export_bytes']}",
            f"areal:last_kv_export_ms {m['last_kv_export_ms']}",
            f"areal:kv_import_total {m['kv_import_total']}",
            f"areal:kv_import_bytes {m['kv_import_bytes']}",
            f"areal:last_kv_import_ms {m['last_kv_import_ms']}",
            f"areal:last_kv_transfer_ms {self._last_kv_transfer_ms}",
            f"areal:kv_handoff_ok {float(self._handoff_ok)}",
            f"areal:kv_handoff_failed {float(self._handoff_failed)}",
            f"areal:kv_handoff_fallback {float(self._handoff_fallback)}",
            # Tiered KV plane: spill/restore counters + per-tier
            # hit/miss/bytes (docs/serving.md). kv_prefix_lost_total is
            # the residual TRUE-loss count the tier exists to zero out
            # (chaos bench asserts 0 under pressure).
            f"areal:kv_spill_total {m['kv_spill_total']}",
            f"areal:kv_spill_bytes {m['kv_spill_bytes']}",
            f"areal:kv_spill_tokens {m['kv_spill_tokens']}",
            f"areal:kv_restore_total {m['kv_restore_total']}",
            f"areal:kv_restore_host {m['kv_restore_host']}",
            f"areal:kv_restore_disk {m['kv_restore_disk']}",
            f"areal:kv_restore_tokens {m['kv_restore_tokens']}",
            f"areal:kv_prefix_lost_total {m['kv_prefix_lost_total']}",
            f"areal:kv_tier_host_bytes {m.get('kv_tier_host_bytes', 0.0)}",
            f"areal:kv_tier_disk_bytes {m.get('kv_tier_disk_bytes', 0.0)}",
            f"areal:kv_tier_host_entries "
            f"{m.get('kv_tier_host_entries', 0.0)}",
            f"areal:kv_tier_disk_entries "
            f"{m.get('kv_tier_disk_entries', 0.0)}",
            f"areal:kv_tier_misses {m.get('kv_tier_misses', 0.0)}",
            f"areal:kv_tier_corrupt_dropped "
            f"{m.get('kv_tier_dropped_corrupt', 0.0)}",
            f"areal:kv_tier_peer_hits {float(self._kv_peer_hits)}",
            f"areal:kv_tier_peer_bytes {float(self._kv_peer_bytes)}",
            f"areal:kv_tier_peer_failed {float(self._kv_peer_failed)}",
            # Elastic fleet: drain state + KV migration counters
            # (docs/fault_tolerance.md). kv_drain_lost is the drain
            # analogue of kv_prefix_lost_total — the e2e pins it to 0.
            f"areal:draining {1.0 if self._draining else 0.0}",
            f"areal:kv_migrated_out "
            f"{float(self._drain_state.get('migrated', 0))}",
            f"areal:kv_drain_lost {float(self._drain_state.get('lost', 0))}",
            f"areal:kv_accepted {float(self._kv_accepted)}",
            f"areal:kv_accept_bytes {float(self._kv_accept_bytes)}",
            f"areal:last_kv_restore_ms {self._last_kv_restore_ms}",
            f"areal:kv_manifests_served {float(self._kv_manifests_served)}",
            f"areal:kv_chunks_served {float(self._kv_chunks_served)}",
            f"areal:num_preempted_reqs {m['num_preempted_reqs']}",
            f"areal:prefix_cache_hits {m['prefix_cache_hits']}",
            f"areal:prefix_tokens_reused {m['prefix_tokens_reused']}",
            f"areal:prefix_cached_tokens {m['prefix_cached_tokens']}",
            # Fleet hit-rate denominator (manager aggregates ratio of
            # sums across servers, not an average of per-server rates).
            f"areal:total_requests {m['total_requests']}",
            f"areal:spec_tokens_per_step {m['spec_tokens_per_step']}",
            # Raw sums behind the ratio, so the manager can aggregate the
            # fleet yield as sum(emitted)/sum(steps) instead of averaging
            # per-server ratios.
            f"areal:spec_emitted_tokens {m['spec_emitted_tokens']}",
            f"areal:spec_active_steps {m['spec_active_steps']}",
            # RPC substrate counters (base/rpc.py process-global stats):
            # this server's OWN outbound calls — KV/weight chunk pulls,
            # handoff hops — under the unified retry/hedge/breaker
            # discipline (docs/fault_tolerance.md).
            f"areal:rpc_attempts {float(rpc_snap['attempts'])}",
            f"areal:rpc_retries {float(rpc_snap['retries'])}",
            f"areal:rpc_failures {float(rpc_snap['failures'])}",
            f"areal:rpc_hedges {float(rpc_snap['hedges'])}",
            f"areal:rpc_hedge_wins {float(rpc_snap['hedge_wins'])}",
            f"areal:rpc_hedge_cancelled {float(rpc_snap['hedge_cancelled'])}",
            f"areal:rpc_hedge_failures {float(rpc_snap['hedge_failures'])}",
            f"areal:rpc_deadline_expired {float(rpc_snap['deadline_expired'])}",
            f"areal:rpc_breaker_rejections "
            f"{float(rpc_snap['breaker_rejections'])}",
            f"areal:rpc_breaker_opens {float(rpc_snap['breaker_opens'])}",
            f"areal:last_weight_swap_s {m['last_weight_swap_s']}",
            f"areal:last_weight_stage_s {m['last_weight_stage_s']}",
            f"areal:last_weight_load_s "
            f"{self._last_load_info['load_s'] if self._last_load_info else 0.0}",
            f"areal:weight_load_fast_path "
            f"{1.0 if (self._last_load_info or {}).get('source') == 'shm_raw' else 0.0}",
            # Weight-distribution plane: network transfer vs cutover are
            # separate numbers by design — transfer overlaps serving,
            # cutover is the short interrupt+swap window the budget
            # knob bounds.
            f"areal:weight_transfer_ms {self._wp_transfer_ms}",
            f"areal:weight_cutover_ms {self._wp_cutover_ms}",
            f"areal:weight_verify_ms {self._wp_verify_ms}",
            f"areal:weight_bytes_from_origin {float(self._wp_bytes_from_origin)}",
            f"areal:weight_bytes_from_peers {float(self._wp_bytes_from_peers)}",
            f"areal:weight_chunks_served {float(self._wp_chunks_served)}",
            f"areal:weight_bytes_served {float(self._wp_bytes_served)}",
            # Shard-aware expectations: expected_bytes is THIS server's
            # chunk stream size (shard slice and/or quantized wire), so
            # ingress/expected reads 1.0 for a complete sliced fetch —
            # never "incomplete" against the full payload.
            f"areal:weight_expected_bytes {float(self._wp_expected_bytes)}",
            f"areal:weight_ingress_payload_equivalents {self._wp_ingress_eq}",
            f"areal:weight_wire {self._wp_wire}",
            "areal:weight_shard "
            + (
                f"{self._weight_shard[0]}/{self._weight_shard[1]}"
                if self._weight_shard else "-"
            ),
        ]
        return web.Response(text="\n".join(lines) + "\n")

    async def _h_health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "ok", "version": self.engine.version,
             "role": self.role}
        )

    # ------------------------------------------------------------------

    def _poll(self) -> Optional[PollResult]:
        # Exit when the experiment completes (reference
        # generation_server.py:209-222 watches experiment status).
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
        time.sleep(0.2)
        return PollResult(batch_count=0)

    def _exit_hook(self):
        try:
            jaxenv.report_usage(self.cfg.worker_name)
            self.engine.stop()
            if self._handoff_session is not None:
                asyncio.run_coroutine_threadsafe(
                    self._handoff_session.close(), self._http_loop
                ).result(timeout=5)
            self._http_loop.call_soon_threadsafe(self._http_loop.stop)
            self._http_thread.join(timeout=5)
        except Exception:
            pass
