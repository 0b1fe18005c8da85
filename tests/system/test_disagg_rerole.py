"""Disaggregated serving in-process: prefill/decode pairing + KV
handoff through real GenerationServer workers behind a real
GserverManager, and the elastic re-role state machine (ISSUE 7).

Covered:
- the manager pairs a prefill and a decode server for a fresh request
  (policy=disagg, decode_url in the schedule response), the prefill
  server hands the KV off over HTTP (hash-verified chunk pull), and the
  client receives the combined stream — identical tokens to a unified
  greedy run;
- the session's affinity lands on the DECODE server (where its KV
  parked), so the follow-up chunk routes there directly;
- `manager.pair` / `server.kv_export` / `server.kv_import` spans land
  in the PR 3 trace;
- elastic sizing: watermark pressure flips a unified server
  prefill-ward and back, visible in /status pools.reroles, with zero
  failed rollouts.

Time budget: ~35 s (two in-process CPU servers, shared tiny-model
compiled programs with the affinity suite).
"""

import asyncio
import json
import threading
import time
import urllib.request
import uuid

import pytest

from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.api.system_api import (
    GenerationServerConfig,
    GserverManagerConfig,
)
from tests import fixtures

pytestmark = pytest.mark.serial

MODEL_CFG = dict(
    n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
    intermediate_dim=128, vocab_size=256, max_position_embeddings=512,
    compute_dtype="float32",
)
PROMPT = list(range(20, 40))  # 20 tokens >= one 16-token page


def _get_json(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _metrics(url):
    text = urllib.request.urlopen(url + "/metrics", timeout=30).read().decode()
    out = {}
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                out[parts[0]] = parts[1]
    return out


def _wait_until(cond, timeout, msg):
    deadline = time.monotonic() + fixtures.scale_timeout(timeout)
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def _mk_server(exp, trial, idx, role, **extra):
    from areal_tpu.system.generation_server import GenerationServer

    kw = dict(
        experiment_name=exp, trial_name=trial, server_index=idx,
        model=ModelAbstraction(
            "tpu_transformer", args=dict(config=dict(MODEL_CFG))
        ),
        max_concurrent_requests=4, max_seq_len=256,
        kv_page_size=16, decode_block_steps=4, prompt_bucket=16,
        prefix_cache_tokens=2048, role=role, seed=idx,
    )
    kw.update(extra)
    cfg = GenerationServerConfig(**kw)
    w = GenerationServer()
    w.configure(cfg, experiment_name=exp, trial_name=trial,
                worker_name=cfg.worker_name)
    return w


def _mk_manager(exp, trial, n, **extra):
    from areal_tpu.system.gserver_manager import GserverManager

    mgr = GserverManager()
    mgr.configure(
        GserverManagerConfig(
            experiment_name=exp, trial_name=trial, model_name="actor",
            n_servers=n, schedule_policy="least_requests",
            train_batch_size=4, max_head_offpolicyness=1000,
            health_check_interval=0.5, **extra,
        ),
        experiment_name=exp, trial_name=trial,
        worker_name="gserver_manager",
    )
    t = threading.Thread(target=mgr.run, daemon=True)
    t.start()
    return mgr, t


@pytest.mark.timeout(600)
def test_disagg_pairing_handoff_and_trace(tmp_path, monkeypatch):
    from areal_tpu.base import name_resolve, names, tracing
    from areal_tpu.system.partial_rollout import PartialRolloutManager
    from areal_tpu.utils import rl_trace

    exp, trial = f"disagg-{uuid.uuid4().hex[:6]}", "t0"
    trace_dir = str(tmp_path / "rl_trace")
    monkeypatch.setenv("AREAL_HEALTH_TTL", "120")
    monkeypatch.setenv("AREAL_RL_TRACE", "1")
    monkeypatch.setenv("AREAL_RL_TRACE_DIR", trace_dir)
    tracing.reconfigure()
    name_resolve.reconfigure("nfs", record_root=str(tmp_path / "nr"))

    servers, mgr, mgr_thread, prm = [], None, None, None
    loop = asyncio.new_event_loop()
    try:
        servers.append(_mk_server(exp, trial, 0, "prefill"))
        servers.append(_mk_server(exp, trial, 1, "decode"))
        by_role = {w.role: w for w in servers}
        mgr, mgr_thread = _mk_manager(exp, trial, 2)
        _wait_until(lambda: len(mgr._healthy_urls()) == 2, 60,
                    "manager sees both servers")
        # Roles flow in via /metrics polling (no heartbeats in-process).
        _wait_until(
            lambda: set(mgr._server_roles.values()) == {"prefill", "decode"},
            30, "manager learned the pool roles",
        )

        prm = PartialRolloutManager(
            mgr.address, request_timeout=fixtures.scale_timeout(120)
        )
        g = GenerationHyperparameters(max_new_tokens=8, greedy=True)
        out = loop.run_until_complete(prm._generate_one("d/0", PROMPT, g))
        assert len(out.output_ids) == 8

        pre, dec = by_role["prefill"], by_role["decode"]
        # The KV crossed the wire: export on the prefill engine, a
        # hash-verified import + priority-0 continuation on the decode
        # engine (delta prefill via its parked prefix).
        assert pre.engine.kv_exports == 1
        assert dec.engine.kv_imports == 1
        assert dec.engine.prefix_cache_hits == 1
        assert dec.engine.prefix_tokens_reused == len(PROMPT)
        assert pre._handoff_ok == 1 and pre._handoff_failed == 0
        m_pre, m_dec = _metrics(pre.address), _metrics(dec.address)
        assert m_pre["areal:role"] == "prefill"
        assert m_pre["areal:kv_export_total"] == 1.0
        assert m_pre["areal:kv_export_bytes"] > 0
        assert m_dec["areal:kv_import_total"] == 1.0
        assert m_dec["areal:last_kv_transfer_ms"] >= 0.0

        # Affinity re-homed onto the decode server; the follow-up chunk
        # routes there directly (no second handoff).
        assert mgr._affinity.get("d/0") == dec.address
        follow = loop.run_until_complete(prm._generate_one(
            "d/0", PROMPT + out.output_ids,
            GenerationHyperparameters(max_new_tokens=4, greedy=True),
        ))
        assert len(follow.output_ids) == 4
        assert pre.engine.kv_exports == 1  # no new handoff
        assert dec.engine.prefix_cache_hits >= 2

        # Greedy parity: the handed-off stream must match a direct
        # single-engine run of the same prompt token for token.
        from areal_tpu.engine.serving import GenRequest

        got = {}
        done = threading.Event()

        def cb(res):
            got["res"] = res
            done.set()

        dec.engine.submit(GenRequest(
            qid="ref", input_ids=list(PROMPT), max_new_tokens=8,
            greedy=True, done_cb=cb,
        ))
        assert done.wait(fixtures.scale_timeout(60))
        assert out.output_ids == got["res"].output_ids
        # A second fresh session pairs (and hands off) again.
        uni = loop.run_until_complete(
            prm._generate_one("u/0", list(PROMPT), g)
        )
        assert uni.output_ids == got["res"].output_ids
        assert pre.engine.kv_exports == 2
        assert dec.engine.kv_imports == 2

        # Manager /status: pools surface with roles, pool membership,
        # and the fleet handoff totals (after a metrics poll of BOTH
        # servers: imports come from the decode server's /metrics,
        # export bytes from the prefill server's).
        def _totals_in():
            kv = _get_json(mgr.address + "/status")["pools"]["kv_handoff"]
            return kv["imports"] >= 1 and kv["export_bytes"] > 0

        _wait_until(_totals_in, 30, "kv handoff totals on /status")
        st = _get_json(mgr.address + "/status")
        assert st["pools"]["roles"][pre.address] == "prefill"
        assert st["pools"]["roles"][dec.address] == "decode"
        assert st["pools"]["prefill"] == [pre.address]
        assert st["pools"]["decode"] == [dec.address]
        assert st["pools"]["kv_handoff"]["export_bytes"] > 0

        # PR 3 trace: pairing + export/import spans, linked.
        tracing.flush()
        shards = rl_trace.load_shards(trace_dir)
        spans = [sp for s in shards for sp in s.spans]
        names_seen = {sp["name"] for sp in spans}
        assert {"manager.pair", "server.kv_export",
                "server.kv_import"} <= names_seen, names_seen
        pair = next(sp for sp in spans if sp["name"] == "manager.pair")
        assert pair["attrs"]["prefill"] == pre.address
        assert pair["attrs"]["decode"] == dec.address
    finally:
        try:
            name_resolve.add(
                names.experiment_status(exp, trial), "COMPLETE",
                replace=True,
            )
        except Exception:
            pass
        if mgr_thread is not None:
            mgr_thread.join(timeout=15)
        for w in servers:
            w._exit_hook()
        if prm is not None:
            loop.run_until_complete(prm.close())
        loop.run_until_complete(asyncio.sleep(0))
        loop.close()
        tracing.reconfigure()


@pytest.mark.timeout(600)
def test_elastic_rerole_flips_and_returns_under_watermark_pressure(
    tmp_path, monkeypatch
):
    """A unified server flips prefill-ward when the prefill queue
    crosses the high watermark, then flips back once it drains — zero
    failed rollouts, both transitions in /status pools.reroles."""
    from areal_tpu.base import name_resolve, names
    from areal_tpu.engine.serving import GenRequest
    from areal_tpu.system.partial_rollout import PartialRolloutManager

    exp, trial = f"rerole-{uuid.uuid4().hex[:6]}", "t0"
    monkeypatch.setenv("AREAL_HEALTH_TTL", "120")
    name_resolve.reconfigure("nfs", record_root=str(tmp_path / "nr"))

    servers, mgr, mgr_thread, prm = [], None, None, None
    loop = asyncio.new_event_loop()
    try:
        # Both unified (elastic); one will be pulled prefill-ward. A
        # deep max_seq_len lets the blocker requests below hold their
        # slots for the whole pressure phase.
        servers.append(_mk_server(exp, trial, 0, "unified",
                                  max_seq_len=2048))
        servers.append(_mk_server(exp, trial, 1, "unified",
                                  max_seq_len=2048))
        mgr, mgr_thread = _mk_manager(
            exp, trial, 2,
            elastic_pools=True,
            rerole_cooldown_s=0.0,
            prefill_queue_high_tokens=100,
            prefill_queue_low_tokens=10,
            # Isolate the queue-watermark path: parked prefix-cache
            # pages read as used, so the free-page floor would also
            # fire here and interleave decode-ward flips.
            decode_free_page_min_frac=0.0,
            pool_min_decode=1, pool_min_prefill=0,
        )
        _wait_until(lambda: len(mgr._healthy_urls()) == 2, 60,
                    "manager sees both servers")
        _wait_until(
            lambda: len(mgr._server_elastic) == 2, 30,
            "manager learned elastic eligibility",
        )

        # Watermark pressure, SUSTAINED: four blocker requests occupy
        # every slot for ~2000 decode tokens, so the 10 queued prompts
        # behind them (400 tokens >= the 100-token watermark) cannot
        # admit until we deliberately interrupt — a fast engine
        # draining the queue between two manager metrics polls
        # (measured: 600 tokens gone in <10 s) must not be able to
        # hide the pressure from the sizer.
        victim = servers[0]
        for i in range(4):
            victim.engine.submit(GenRequest(
                qid=f"blk{i}", input_ids=[5, 6, 7],
                max_new_tokens=2000, greedy=True, done_cb=lambda r: None,
            ))
        for i in range(10):
            victim.engine.submit(GenRequest(
                qid=f"p{i}", input_ids=list(range(1, 41)),
                max_new_tokens=60, greedy=True, done_cb=lambda r: None,
            ))
        _wait_until(
            lambda: victim.engine.queued_prompt_tokens >= 100, 30,
            "queued-token watermark pressure",
        )
        # The signal must actually REACH the sizer (manager-side view).
        _wait_until(
            lambda: mgr._server_queued_toks.get(victim.address, 0) >= 100,
            60, "manager observed the queue pressure",
        )
        # The sizer flips the most page-free elastic decode-side server
        # prefill-ward (cheapest to take from the decode pool) — not
        # necessarily the pressured one.
        _wait_until(
            lambda: "prefill" in mgr._server_roles.values(), 90,
            "elastic flip to prefill",
        )
        flipped = next(
            w for w in servers
            if mgr._server_roles.get(w.address) == "prefill"
        )
        _wait_until(lambda: flipped.role == "prefill", 10,
                    "server-side role flip")
        # The decode pool floor holds: no second flip drains it.
        assert sum(
            1 for r in mgr._server_roles.values() if r != "prefill"
        ) >= 1

        # Release the pressure: interrupt the blockers (the weight-swap
        # path — partial results return, the queued prompts admit and
        # drain), then the sizer returns the server to its original
        # pool.
        victim.engine.update_params(
            victim.engine.params, allow_interrupt=True
        )

        # Traffic through the re-roled fleet still completes (drain +
        # flip loses nothing). After the release, so a decode pairing
        # onto the (formerly fully-blocked) victim can't stall behind
        # the blockers' whole token budget.
        prm = PartialRolloutManager(
            mgr.address, request_timeout=fixtures.scale_timeout(120)
        )
        out = loop.run_until_complete(prm._generate_one(
            "live/0", PROMPT,
            GenerationHyperparameters(max_new_tokens=6, greedy=True),
        ))
        assert len(out.output_ids) == 6
        _wait_until(
            lambda: sum(
                w.engine.queued_prompt_tokens for w in servers
            ) <= 10, 240,
            "pressure drained",
        )
        _wait_until(
            lambda: mgr._server_roles.get(flipped.address) == "unified", 120,
            "elastic flip back",
        )
        _wait_until(lambda: flipped.role == "unified", 20,
                    "server-side flip back")

        st = _get_json(mgr.address + "/status")
        transitions = [(e["from"], e["to"]) for e in st["pools"]["reroles"]]
        assert ("unified", "prefill") in transitions, transitions
        assert ("prefill", "unified") in transitions, transitions
        assert all(
            e["url"] == flipped.address for e in st["pools"]["reroles"]
        )
    finally:
        try:
            name_resolve.add(
                names.experiment_status(exp, trial), "COMPLETE",
                replace=True,
            )
        except Exception:
            pass
        if mgr_thread is not None:
            mgr_thread.join(timeout=15)
        for w in servers:
            w._exit_hook()
        if prm is not None:
            loop.run_until_complete(prm.close())
        loop.run_until_complete(asyncio.sleep(0))
        loop.close()
