"""Phase bodies: the old benchmark's workloads.

Every function here is a phase entrypoint ``fn(pass_) -> value dict``
run inside its own runner subprocess (see :mod:`areal_tpu.bench.runner`):

- ``pass_ == "compile"``: build the workload and compile every program
  it needs, so the persistent XLA cache holds them. Returns compile
  timings.
- ``pass_ == "measure"``: warm briefly (cache hits), then run the
  workload and return what it counted.

Every phase is a CPU proxy over the control plane: the runner pins its
subprocess to ``JAX_PLATFORMS=cpu``, and none of them times the chip.
Speed on the chip is ``benchmark/run.py``'s to measure.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from areal_tpu.base import env_registry
from areal_tpu.base import metrics_registry as mreg
from areal_tpu.bench._util import log

BASELINE_TFLOPS = 198.0


# ----------------------------------------------------------------------
# serving_openloop: open-loop (Poisson-arrival) tail-latency benchmark
# over a REAL multi-process fleet (bench/fleet.py): GenerationServer
# worker subprocesses behind a real GserverManager, load routed through
# /schedule_request — the path production rollout workers take (the
# ROADMAP item-2 "not in-process engines" gap). A closed loop cannot
# see overload behavior — an open-loop generator keeps submitting at
# the offered rate regardless of completions. Sweeps arrival
# rates against measured capacity and A/Bs server-side admission
# control (429 watermark shedding) against a no-backpressure baseline
# at deliberate overload: with admission, p99 TTFT stays bounded by the
# watermark; without it, the queue (and therefore TTFT) grows with the
# length of the run. Scheduling-policy effects are visible on CPU;
# banked as CPU-proxy evidence until a device window returns.
# ----------------------------------------------------------------------

# Geometry matches the engine test harness (tests/engine/
# test_prefix_cache.py) so tier-1 runs reuse compiled programs via the
# persistent XLA cache instead of paying fresh compiles per child.
_OPENLOOP_MODEL = dict(
    n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
    intermediate_dim=128, vocab_size=256, max_position_embeddings=512,
    compute_dtype="float32",
)
_OPENLOOP_SRV = dict(
    max_concurrent_requests=4, max_seq_len=256, kv_page_size=16,
    decode_block_steps=4, prompt_bucket=16, prefill_token_budget=64,
    warm_on_start=True,
)


def _ttft_slo_fields(headline_p99: float) -> dict:
    """Optional p99-TTFT SLO stamp (satellite 2): with AREAL_TTFT_SLO_MS
    set, the banked record carries the configured limit and whether its
    headline p99 violated it — the report/validator refuse to leave a
    violating record silently headline-eligible."""
    slo = env_registry.get_float("AREAL_TTFT_SLO_MS")
    if not slo:
        return {}
    return {
        "ttft_slo_ms": float(slo),
        "ttft_slo_violated": bool(headline_p99 > float(slo)),
    }


def serving_openloop_phase(pass_: str) -> dict:
    from areal_tpu.bench.fleet import (
        ProcessFleet, closed_loop_capacity, open_loop_point,
        warm_admit_shapes,
    )

    n_servers = env_registry.get_int("AREAL_OPENLOOP_SERVERS")
    point_s = env_registry.get_float("AREAL_OPENLOOP_POINT_S")
    # Multiples of the CLOSED-LOOP capacity (batched admission, the
    # engine's peak). Open-loop sustainable throughput is lower — a
    # trickle arrival admits in singletons and loses prefill batching —
    # so ~1.0 is already past saturation and the top multiple is deep
    # overload.
    rate_mults = [
        float(x)
        for x in env_registry.get_str("AREAL_OPENLOOP_RATES")
        .split(",")
        if x
    ]
    watermark = env_registry.get_int("AREAL_OPENLOOP_WATERMARK")
    plen, max_new, vocab = 16, 16, _OPENLOOP_MODEL["vocab_size"]
    t_start = time.monotonic()
    rng = np.random.RandomState(5)

    if pass_ == "compile":
        # One server, one request: compiles land in the persistent XLA
        # cache, which every measure-pass child then hits warm.
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL, [dict(_OPENLOOP_SRV)], tag="olc"
        ) as fleet:
            out = fleet.generate_routed(
                "c0", list(range(1, plen + 1)), max_new)
            assert "output_ids" in out, out
        dt = time.perf_counter() - t0
        log(f"bench: serving_openloop compile pass {dt:.1f}s")
        return {"compile_s": dt}

    servers = [
        dict(_OPENLOOP_SRV, max_queue_depth=watermark,
             shed_retry_after_s=0.5)
        for _ in range(n_servers)
    ]
    with ProcessFleet(_OPENLOOP_MODEL, servers, tag="openloop") as fleet:
        def prompt(i):
            return rng.randint(1, vocab, size=plen).tolist()

        # Capacity probe runs closed-loop direct to the servers — lift
        # the watermark for it (a burst of 4B requests would shed).
        fleet.configure_servers({"max_queue_depth": None})
        B = _OPENLOOP_SRV["max_concurrent_requests"]
        # Every pow2 admit-batch shape on every server, or a cold shape
        # compiles inside a sweep point and reads as queueing delay.
        warm_admit_shapes(fleet, plen, max_new, vocab, rng)
        closed_loop_capacity(fleet, 4 * B * n_servers, plen, max_new,
                             "w", vocab, rng)
        capacity = closed_loop_capacity(
            fleet, 4 * B * n_servers, plen, max_new, "c", vocab, rng)
        fleet.configure_servers({"max_queue_depth": watermark})
        # Closed-loop capacity (batched admission) runs far above what a
        # thread-per-arrival generator can cleanly OFFER on a small CPU
        # host — sweeping multiples of it just measures client-side
        # thread-storm chaos. The sweep base is capped so the generator
        # stays honest; the measured capacity is still banked.
        sweep_base = min(
            capacity,
            env_registry.get_float("AREAL_OPENLOOP_MAX_RPS"),
        )
        log(f"bench: serving_openloop capacity ~{capacity:.1f} req/s, "
            f"sweep base {sweep_base:.1f} req/s "
            f"({n_servers} real server processes)")

        sweep = []
        for mult in rate_mults:
            pt = open_loop_point(
                fleet, mult * sweep_base, point_s, prompt, max_new,
                f"s{mult}-", rng=rng,
            )
            pt["rate_multiple"] = float(mult)
            sweep.append(pt)

        # Deliberate overload A/B. Overload must hold by CONSTRUCTION,
        # not by trusting a noisy capacity probe: the A/B arms use
        # heavy requests (8x the decode tokens, so per-request service
        # time is ~8x and true capacity ~capacity/8) at 3x that derated
        # capacity, with a tight queue watermark. Admission (429) vs no
        # backpressure at the same offered rate: with admission the
        # queue — and so p99 TTFT — is bounded by the watermark; without
        # it both grow with the length of the run.
        heavy_new = 8 * max_new
        overload_wm = 2

        def heavy(i):
            return rng.randint(1, vocab, size=plen).tolist()

        # Probe the HEAVY workload's own closed-loop capacity (an
        # analytic max_new derating of the short-request capacity was
        # off by the batch-parallelism factor, run to run): 3x that is
        # overload by measurement, not by model.
        fleet.configure_servers({"max_queue_depth": None})
        heavy_cap = closed_loop_capacity(
            fleet, 4 * n_servers, plen, heavy_new, "hc", vocab, rng)
        overload_rps = 3.0 * max(1.0, heavy_cap)
        fleet.configure_servers({"max_queue_depth": overload_wm})
        adm = open_loop_point(
            fleet, overload_rps, point_s, heavy, heavy_new, "oa-", rng=rng,
        )
        fleet.configure_servers(
            {"max_queue_depth": None, "max_queued_tokens": None})
        base = open_loop_point(
            fleet, overload_rps, point_s, heavy, heavy_new, "b-", rng=rng,
        )
        fleet.configure_servers({"max_queue_depth": watermark})
        # Headline p99 for the SLO gate: the operating point nearest
        # (at or below) saturation, not the deliberate-overload arm.
        at_or_below = [p for p in sweep if p["rate_multiple"] <= 1.0]
        headline = (at_or_below or sweep)[-1]["p99_ttft_ms"]
        return {
            # Closed-loop peak (admission batches full prefill rounds);
            # open-loop goodput saturates below this by design.
            "capacity_rps": capacity,
            "sweep_base_rps": sweep_base,
            "n_servers": float(n_servers),
            "watermark": float(watermark),
            "fleet": "process",
            "sweep": sweep,
            "headline_ttft_p99_ms": headline,
            "overload_offered_rps": adm["offered_rps"],
            "overload_admission_p99_ttft_ms": adm["p99_ttft_ms"],
            "overload_admission_goodput_rps": adm["goodput_rps"],
            "overload_admission_shed": adm["n_shed"],
            "overload_baseline_p99_ttft_ms": base["p99_ttft_ms"],
            "overload_baseline_goodput_rps": base["goodput_rps"],
            "wall_s": time.monotonic() - t_start,
            **_ttft_slo_fields(headline),
        }


# ----------------------------------------------------------------------
# serving_disagg: unified vs 1-prefill+1-decode A/B under a mixed
# long-prefill/short-decode open-loop workload, on the same real-process
# harness. The unified arm admits long chunked prefills on the serve
# loop between decode blocks — running slots' inter-token latency eats
# the whole prefill stall. The disaggregated arm's decode server only
# ever admits one-token handoff deltas, so its ITL distribution stays
# tight while prefill-pool throughput absorbs the long prompts. Banked:
# decode ITL p99 + TTFT p99 for BOTH arms (validate_bench.py requires
# the pair), plus the KV-handoff counters proving the hop really ran.
# ----------------------------------------------------------------------

# Pool sized WELL above B*max_seq residency: decode-side page
# pressure would otherwise evict parked handoff imports between import
# and admission, turning the decode loop into a re-prefill storm that
# drowns the interference signal under test (measured: disagg ITL p99
# 1024ms from eviction thrash at kv_pool_tokens=B*S, 32ms at 2x).
_DISAGG_SRV = dict(
    max_concurrent_requests=4, max_seq_len=1024, kv_page_size=16,
    kv_pool_tokens=8192, decode_block_steps=4, prompt_bucket=16,
    prefill_chunk=16, prefix_cache_tokens=4096, warm_on_start=True,
)


def serving_disagg_phase(pass_: str) -> dict:
    from areal_tpu.bench.fleet import ProcessFleet, interference_point

    # Long prompts must be LONG relative to a decode block for the
    # interference to be measurable: 768 tokens = 48 serve-loop chunk
    # forwards (~0.4-0.7 s on the 2-core CPU proxy shape) stalling every
    # running decode stream in the unified arm; the per-token base ITL
    # is ~4-16 ms, so one collision pushes a slot's samples several
    # log2 buckets up.
    long_plen = env_registry.get_int("AREAL_DISAGG_LONG_PLEN")
    short_plen = env_registry.get_int("AREAL_DISAGG_SHORT_PLEN")
    n_streams = env_registry.get_int("AREAL_DISAGG_STREAMS")
    # Streams must OUTLIVE the last long injection (gap * n_long plus
    # the prefill time itself), or tail injections land on an idle
    # fleet and measure nothing.
    stream_max_new = env_registry.get_int("AREAL_DISAGG_STREAM_TOKENS")
    n_long = env_registry.get_int("AREAL_DISAGG_N_LONG")
    long_gap_s = env_registry.get_float("AREAL_DISAGG_LONG_GAP_S")
    long_max_new = env_registry.get_int("AREAL_DISAGG_LONG_MAX_NEW")
    t_start = time.monotonic()

    if pass_ == "compile":
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL,
            [dict(_DISAGG_SRV, role="prefill"),
             dict(_DISAGG_SRV, role="decode")],
            tag="dsc",
        ) as fleet:
            fleet.wait_roles(["prefill", "decode"])
            # One long handoff covers chunk prefill + export + import +
            # decode-block programs on both children.
            out = fleet.generate_routed(
                "c0", list(range(1, long_plen + 1)), long_max_new)
            assert "output_ids" in out, out
        dt = time.perf_counter() - t0
        log(f"bench: serving_disagg compile pass {dt:.1f}s")
        return {"compile_s": dt}

    # The A/B is a deterministic interference probe, not a Poisson
    # sweep: n_streams decode streams run for the whole window while
    # n_long long prompts arrive at fixed gaps — every long admission
    # lands while streams decode (a sampled arrival process at this
    # scale only collides by luck, which made the A/B noisy). Both arms
    # replay the same script.
    def arm(servers, tag, ttft_urls_idx=None, itl_urls_idx=None, roles=None):
        with ProcessFleet(_OPENLOOP_MODEL, servers, tag=tag) as fleet:
            if roles:
                fleet.wait_roles(roles)
            wrng = np.random.RandomState(7)
            # Warm BOTH prompt shapes through the arm's real admission
            # path before measuring: a chunked-prefill or handoff-
            # scatter compile landing inside the window would
            # masquerade as scheduler-induced latency.
            for n in (long_plen, short_plen):
                out = fleet.generate_routed(
                    f"w{tag}{n}", wrng.randint(1, 200, size=n).tolist(),
                    long_max_new)
                assert "output_ids" in out, out
            if roles is None:
                # Unified arm: warm the second server directly too
                # (routing may have sent both warms to one).
                for i, u in enumerate(fleet.urls):
                    for n in (long_plen, short_plen):
                        out = fleet.generate_direct(
                            u, f"w{tag}{i}-{n}",
                            wrng.randint(1, 200, size=n).tolist(),
                            long_max_new,
                        )
                        assert "output_ids" in out, out
            kw = {}
            if ttft_urls_idx is not None:
                kw["ttft_urls"] = [fleet.urls[i] for i in ttft_urls_idx]
            if itl_urls_idx is not None:
                kw["itl_urls"] = [fleet.urls[i] for i in itl_urls_idx]
            pt = interference_point(
                fleet, n_streams, short_plen, stream_max_new,
                n_long, long_plen, long_gap_s, long_max_new,
                tag, rng=np.random.RandomState(11), **kw,
            )
            m_by_url = {u: fleet.metrics(u) for u in fleet.urls}
            return pt, m_by_url

    uni, _ = arm([dict(_DISAGG_SRV), dict(_DISAGG_SRV)], "dsu")
    dis, m_dis = arm(
        [dict(_DISAGG_SRV, role="prefill"), dict(_DISAGG_SRV, role="decode")],
        "dsd",
        # TTFT is measured where prompts land (the prefill pool);
        # decode ITL where the streams run (the decode pool).
        ttft_urls_idx=[0], itl_urls_idx=[1],
        roles=["prefill", "decode"],
    )
    m_pre = next(m for m in m_dis.values() if m.get(mreg.ROLE) == "prefill")
    m_dec = next(m for m in m_dis.values() if m.get(mreg.ROLE) == "decode")
    handoffs = m_dec.get(mreg.KV_IMPORT_TOTAL, 0.0)
    handoff_bytes = m_dec.get(mreg.KV_IMPORT_BYTES, 0.0)
    fallbacks = m_pre.get(mreg.KV_HANDOFF_FALLBACK, 0.0)

    log(f"bench: serving_disagg A/B: unified itl p99 "
        f"{uni['itl_p99_ms']:.1f}ms ttft p99 {uni['p99_ttft_ms']:.1f}ms | "
        f"disagg itl p99 {dis['itl_p99_ms']:.1f}ms ttft p99 "
        f"{dis['p99_ttft_ms']:.1f}ms ({handoffs:.0f} handoffs, "
        f"{fallbacks:.0f} fallbacks)")
    return {
        "offered_rate_rps": uni["offered_rps"],
        "point_s": uni["duration_s"],
        "long_plen": float(long_plen),
        "long_frac": n_long / float(n_long + n_streams),
        "n_streams": float(n_streams),
        "n_long": float(n_long),
        "unified_offered_rps": uni["offered_rps"],
        "disagg_offered_rps": dis["offered_rps"],
        "unified_itl_p99_ms": uni["itl_p99_ms"],
        "unified_itl_p50_ms": uni["itl_p50_ms"],
        "unified_ttft_p99_ms": uni["p99_ttft_ms"],
        "unified_goodput_rps": uni["goodput_rps"],
        "unified_failed": uni["n_failed"],
        "disagg_itl_p99_ms": dis["itl_p99_ms"],
        "disagg_itl_p50_ms": dis["itl_p50_ms"],
        "disagg_ttft_p99_ms": dis["p99_ttft_ms"],
        "disagg_goodput_rps": dis["goodput_rps"],
        "disagg_failed": dis["n_failed"],
        "kv_handoffs": handoffs,
        "kv_handoff_bytes": handoff_bytes,
        "kv_handoff_fallbacks": fallbacks,
        "wall_s": time.monotonic() - t_start,
        **_ttft_slo_fields(dis["p99_ttft_ms"]),
    }


# ----------------------------------------------------------------------
# sessions_resident: the tiered-KV plane's headline probe (ISSUE 11).
# Resident-session count sweeps PAST the HBM prefix budget; returning
# sessions either hit HBM, restore from the host tier (spill survived
# eviction), pull from a peer via the manager's global prefix index, or
# miss and pay the full re-prefill the tier exists to avoid. Banked:
# returning-session TTFT with the tier vs the no-tier full-re-prefill
# baseline, hit rate by tier (hbm/host/peer/miss), zero true prefix
# loss under pressure, and the int8-vs-float spill-wire byte ratio.
# ----------------------------------------------------------------------

# ~199 parked tokens per session (192-token prompt + 7 landed outputs)
# against an 800-token HBM prefix budget: ~4 sessions fit, the rest
# spill. The pool itself is ample — the pressure under test is the
# prefix budget, not decode pages. Sessions are deliberately LONG
# relative to the 16-token prefill chunk: a full re-prefill costs 12+
# sequential chunk forwards on the serve loop while a restore is a
# host->device copy + one scatter, so the TTFT gap is structural, not
# 2-core scheduling luck (a 64-token variant measured p99s within one
# log2 bucket of each other, run to run).
_SRES_SRV = dict(
    max_concurrent_requests=4, max_seq_len=256, kv_page_size=16,
    kv_pool_tokens=8192, decode_block_steps=4, prompt_bucket=16,
    prefill_chunk=16, prefix_cache_tokens=800, warm_on_start=True,
)
_SRES_PLEN = 192
_SRES_TURN1_NEW = 8
_SRES_TURN2_NEW = 4


def _sres_prompt(i: int):
    rng = np.random.RandomState(1000 + i)
    return rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                       size=_SRES_PLEN).tolist()


def _sres_wait(cond, timeout_s: float, msg: str):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise RuntimeError(f"sessions_resident: timed out waiting for {msg}")


def _sres_point(fleet, n_resident: int, tag: str) -> dict:
    """Park n_resident sessions (turn 1), wait for spills to settle,
    then run every session's turn 2 and read TTFT + hit tiers from the
    server-side histogram/counter diffs."""
    from areal_tpu.base.latency import percentile_from_counts

    turn1 = {}
    for i in range(n_resident):
        qid = f"{tag}{i}"
        out = fleet.generate_routed(
            qid, _sres_prompt(i), _SRES_TURN1_NEW, timeout=300)
        assert "output_ids" in out, out
        turn1[qid] = [int(t) for t in out["output_ids"]]

    def m_sum(key):
        return sum(fleet.metrics(u).get(key, 0.0) for u in fleet.urls)

    # Spills are asynchronous: wait for the spill counter to go quiet
    # (two identical reads 0.5s apart) before snapshotting baselines.
    last = [-1.0]

    def settled():
        cur = m_sum(mreg.KV_SPILL_TOTAL)
        ok = cur == last[0]
        last[0] = cur
        return ok

    time.sleep(0.3)
    _sres_wait(settled, 30.0, "spills to settle")

    base_hits = m_sum(mreg.PREFIX_CACHE_HITS)
    base_rest_h = m_sum(mreg.KV_RESTORE_HOST)
    base_rest_d = m_sum(mreg.KV_RESTORE_DISK)
    base_peer = m_sum(mreg.KV_TIER_PEER_HITS)
    base_t = fleet.hist_counts(fleet.urls)["ttft"]
    for i in range(n_resident):
        qid = f"{tag}{i}"
        p2 = _sres_prompt(i) + turn1[qid] + [5]
        out = fleet.generate_routed(qid, p2, _SRES_TURN2_NEW, timeout=300)
        assert "output_ids" in out, out
    after_t = fleet.hist_counts(fleet.urls)["ttft"]
    dt = [max(0, a - b) for a, b in zip(after_t, base_t)]
    hits = m_sum(mreg.PREFIX_CACHE_HITS) - base_hits
    rest_h = m_sum(mreg.KV_RESTORE_HOST) - base_rest_h
    rest_d = m_sum(mreg.KV_RESTORE_DISK) - base_rest_d
    peer = m_sum(mreg.KV_TIER_PEER_HITS) - base_peer
    # Every restore (host/disk/peer) re-parks the prefix and is then
    # consumed as an admission hit; HBM-only hits are the remainder.
    hbm = max(0.0, hits - rest_h - rest_d - peer)
    pt = {
        "n_resident": float(n_resident),
        "ttft_p50_ms": percentile_from_counts(dt, 50.0),
        "ttft_p99_ms": percentile_from_counts(dt, 99.0),
        "hits_hbm": hbm,
        "hits_host": rest_h,
        "hits_disk": rest_d,
        "hits_peer": peer,
        "misses": float(n_resident) - hits,
        "hit_rate": hits / n_resident,
    }
    log(f"bench: sessions_resident point {tag}: {pt}")
    return pt


def sessions_resident_phase(pass_: str) -> dict:
    from areal_tpu.bench.fleet import ProcessFleet

    t_start = time.monotonic()
    tier_env = {"AREAL_KV_TIER_BYTES": str(64 << 20)}

    if pass_ == "compile":
        # One spill + restore + both prompt shapes covers the chunked
        # prefill, the decode block, the import scatter, and the
        # restore path's programs. A 16-token prefix budget forces the
        # single session to spill immediately.
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL,
            [dict(_SRES_SRV, prefix_cache_tokens=16, env=tier_env)],
            tag="srsc",
        ) as fleet:
            _sres_point(fleet, 1, "c")
        dt = time.perf_counter() - t0
        log(f"bench: sessions_resident compile pass {dt:.1f}s")
        return {"compile_s": dt}

    n_max = 16
    sweep_ns = (2, 8, n_max)

    # --- Tier arm: host tier armed, resident count swept past the
    # HBM budget. The top point is the headline.
    sweep = []
    with ProcessFleet(
        _OPENLOOP_MODEL, [dict(_SRES_SRV, env=tier_env)], tag="srst"
    ) as fleet:
        for n in sweep_ns:
            sweep.append(_sres_point(fleet, n, f"t{n}-"))
        m = fleet.metrics(fleet.urls[0])
        tier_lost = m.get(mreg.KV_PREFIX_LOST_TOTAL, 0.0)
        tier_spills = m.get(mreg.KV_SPILL_TOTAL, 0.0)
        f_bytes = m.get(mreg.KV_SPILL_BYTES, 0.0)
        f_tokens = m.get(mreg.KV_SPILL_TOKENS, 0.0)
    top = sweep[-1]

    # --- Baseline arm: tier DISABLED — evicted sessions pay the full
    # re-prefill. Same top-point script, so the TTFT delta is the
    # tier's value.
    with ProcessFleet(
        _OPENLOOP_MODEL,
        [dict(_SRES_SRV, env={"AREAL_KV_TIER_BYTES": "0"})],
        tag="srsb",
    ) as fleet:
        base_top = _sres_point(fleet, n_max, "b-")

    # --- int8 spill arm: same pressure, quantized spill wire; the
    # bytes-per-token ratio vs the float arm is the halving claim
    # (float32 CPU-proxy pools give ~0.28; bf16 device pools ~0.53 —
    # either way the tier traffic at least halves).
    with ProcessFleet(
        _OPENLOOP_MODEL,
        [dict(_SRES_SRV,
              env=dict(tier_env, AREAL_KV_SPILL_DTYPE="int8"))],
        tag="srsq",
    ) as fleet:
        _sres_point(fleet, 8, "q-")
        m = fleet.metrics(fleet.urls[0])
        q_bytes = m.get(mreg.KV_SPILL_BYTES, 0.0)
        q_tokens = m.get(mreg.KV_SPILL_TOKENS, 0.0)
    f_bpt = f_bytes / max(1.0, f_tokens)
    q_bpt = q_bytes / max(1.0, q_tokens)

    # --- Peer arm: 2 servers, session affinity OFF — returning
    # sessions land wherever round robin says and pull their prefix
    # from the holder the global index names.
    n_peer = 6
    with ProcessFleet(
        _OPENLOOP_MODEL,
        [dict(_SRES_SRV, env=tier_env) for _ in range(2)],
        manager_kw=dict(session_affinity=False,
                        schedule_policy="round_robin"),
        tag="srsp",
    ) as fleet:
        turn1 = {}
        for i in range(n_peer):
            qid = f"p{i}"
            out = fleet.generate_routed(
                qid, _sres_prompt(i), _SRES_TURN1_NEW, timeout=300)
            assert "output_ids" in out, out
            turn1[qid] = [int(t) for t in out["output_ids"]]
        # The index is poll-fed (~2s cadence): wait until the manager
        # knows EVERY holder before resuming — a session scheduled
        # before its index entry lands gets no kv_source and silently
        # re-prefills (measured as 4/6 peer pulls on a lax wait).
        _sres_wait(
            lambda: len(fleet.manager._prefix_index) >= n_peer,
            30.0, "global prefix index fill",
        )
        # Shift round-robin parity by one: an even turn-1 count would
        # otherwise route every turn-2 straight back to its holder and
        # the peer-pull path would never engage (sessions must RESUME
        # ON THE OTHER SERVER — the point of this arm).
        fleet.schedule({"qid": "rr-shift", "prompt_len": 1,
                        "new_token_budget": 1})
        for i in range(n_peer):
            qid = f"p{i}"
            p2 = _sres_prompt(i) + turn1[qid] + [5]
            out = fleet.generate_routed(qid, p2, _SRES_TURN2_NEW,
                                        timeout=300)
            assert "output_ids" in out, out
        peer_hits = sum(
            fleet.metrics(u).get(mreg.KV_TIER_PEER_HITS, 0.0)
            for u in fleet.urls
        )
        peer_lost = sum(
            fleet.metrics(u).get(mreg.KV_PREFIX_LOST_TOTAL, 0.0)
            for u in fleet.urls
        )

    log(
        f"bench: sessions_resident: tier p99 {top['ttft_p99_ms']:.0f}ms "
        f"vs full-re-prefill {base_top['ttft_p99_ms']:.0f}ms at "
        f"{n_max} resident; spill bytes/token float {f_bpt:.0f} vs "
        f"int8 {q_bpt:.0f} ({q_bpt / max(1e-9, f_bpt):.2f}x); "
        f"peer pulls {peer_hits:.0f}/{n_peer}; lost {tier_lost:.0f}"
    )
    return {
        "n_resident_max": float(n_max),
        "hbm_prefix_budget_tokens": float(_SRES_SRV["prefix_cache_tokens"]),
        "session_tokens": float(_SRES_PLEN + _SRES_TURN1_NEW - 1),
        "sweep": sweep,
        "tier_ttft_p50_ms": top["ttft_p50_ms"],
        "tier_ttft_p99_ms": top["ttft_p99_ms"],
        "baseline_ttft_p50_ms": base_top["ttft_p50_ms"],
        "baseline_ttft_p99_ms": base_top["ttft_p99_ms"],
        "hit_rate_hbm": top["hits_hbm"] / n_max,
        "hit_rate_host": top["hits_host"] / n_max,
        "hit_rate_disk": top["hits_disk"] / n_max,
        "hit_rate_peer": peer_hits / n_peer,
        "miss_rate": max(0.0, top["misses"]) / n_max,
        "kv_spill_total": tier_spills,
        "kv_prefix_lost": tier_lost + peer_lost,
        "float_spill_bytes_per_token": f_bpt,
        "int8_spill_bytes_per_token": q_bpt,
        "int8_spill_bytes_ratio": q_bpt / max(1e-9, f_bpt),
        "peer_sessions": float(n_peer),
        "peer_hits": peer_hits,
        "fleet": "process",
        "wall_s": time.monotonic() - t_start,
    }


def weight_plane_sharded_phase(pass_: str) -> dict:
    """Shard-aware, quantized weight plane (ISSUE 8 acceptance): bank
    per-server ingress bytes/version against TP degree and wire dtype
    over a LIVE origin serving sliced chunk streams.

    Byte accounting is exact and machine-independent (sha256-verified
    chunk streams over loopback HTTP), so CPU-proxy records are real
    evidence here. Arms, one dump version each so the origin's
    full_payload_equivalents stays per-version honest:

    - v1, TP=1 raw:   one server fetches the full payload (frac 1.0)
    - v2, TP=2 raw:   each rank fetches its slice (frac ~0.5 + the
                      replicated-leaf epsilon); a same-shard REPLICA
                      then fetches rank 0's stream entirely from the
                      first holder — zero extra origin egress
    - v3, TP=2 int8:  sliced QUANTIZED streams (~half of v2 again);
                      dequantized shard leaves must equal the sliced
                      dequantized full payload exactly (slicing
                      commutes with the per-output-channel dequant)

    Plus the assemble-side proof on a fake-device CPU mesh: a 2-way-TP
    ServingEngine cut over from the two sliced streams must match the
    float unsharded baseline's greedy decode token-for-token."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # tiny CPU-mesh programs; measure pays
    import shutil
    import tempfile

    import jax
    import ml_dtypes

    from areal_tpu.engine.weight_client import (
        ChunkStore, assemble_leaves, fetch_manifest,
    )
    from areal_tpu.parallel.sharding import tensor_shard_slices
    from areal_tpu.system.weight_plane import (
        PeerStoreServer, WeightPlaneSource,
    )
    from areal_tpu.system.weight_transfer import (
        dump_raw_params, dequantize_wire_leaf, quantize_wire_leaf,
    )

    rng = np.random.RandomState(0)
    L, D, F, V = 4, 256, 512, 2048
    cb = 256 << 10

    def mat(*shape):
        return rng.standard_normal(shape).astype(ml_dtypes.bfloat16)

    # Leaf names chosen so parallel/sharding.py specs engage: wq/wk/wv/
    # w_gate/w_up column-parallel, wo/w_down row-parallel, embedding/head
    # vocab-parallel, norm scales replicated (the per-rank epsilon).
    tree = {
        "embedding": {"weight": mat(V, D)},
        "head": {"weight": mat(D, V)},
        "layers": {
            "attn": {"wq": mat(L, D, D), "wk": mat(L, D, D),
                     "wv": mat(L, D, D), "wo": mat(L, D, D)},
            "mlp": {"w_gate": mat(L, D, F), "w_up": mat(L, D, F),
                    "w_down": mat(L, F, D)},
            "norm": {"scale": rng.standard_normal((L, D)).astype(np.float32)},
        },
    }
    flat = {
        "embedding/weight": tree["embedding"]["weight"],
        "head/weight": tree["head"]["weight"],
        **{f"layers/attn/{k}": v for k, v in tree["layers"]["attn"].items()},
        **{f"layers/mlp/{k}": v for k, v in tree["layers"]["mlp"].items()},
    }
    tmp = tempfile.mkdtemp(prefix="areal_wps_bench_")
    src, holder0 = None, None
    out: dict = {}
    try:
        # ---- v1: TP=1 raw (the baseline denominator) ------------------
        dump_raw_params(tree, tmp, version=1, chunk_bytes=cb,
                        wire_dtype="int8")
        src = WeightPlaneSource(tmp, chunk_bytes=cb).start()
        man1 = fetch_manifest(src.address, version=1)
        full_bytes = man1["total_bytes"]
        t0 = time.perf_counter()
        st1 = ChunkStore(man1)
        s1 = st1.fetch([src.address], origin=src.address)
        tp1_ms = (time.perf_counter() - t0) * 1000.0
        tp1_frac = sum(s1["bytes_from"].values()) / full_bytes

        # ---- v2: TP=2 raw sliced + same-shard peer replica ------------
        dump_raw_params(tree, tmp, version=2, chunk_bytes=cb,
                        wire_dtype="int8")
        fracs = []
        t0 = time.perf_counter()
        for rank in range(2):
            man = fetch_manifest(
                src.address, version=2, tp_degree=2, tp_rank=rank
            )
            st = ChunkStore(man)
            stats = st.fetch([src.address], origin=src.address)
            fracs.append(sum(stats["bytes_from"].values()) / full_bytes)
            if rank == 0:
                holder0 = PeerStoreServer().start()
                holder0.store = st
        tp2_ms = (time.perf_counter() - t0) * 1000.0
        # Same-shard replica: served entirely by the rank-0 holder.
        man0 = fetch_manifest(
            holder0.address, version=2, tp_degree=2, tp_rank=0
        )
        st_rep = ChunkStore(man0)
        rep = st_rep.fetch([holder0.address, src.address], origin=src.address)

        # ---- v3: TP=2 int8 sliced + dequant parity --------------------
        dump_raw_params(tree, tmp, version=3, chunk_bytes=cb,
                        wire_dtype="int8")
        q_fracs, dequant_err, dequant_ok = [], 0.0, True
        t0 = time.perf_counter()
        for rank in range(2):
            man = fetch_manifest(
                src.address, version=3, wire="int8", tp_degree=2,
                tp_rank=rank,
            )
            st = ChunkStore(man)
            stats = st.fetch([src.address], origin=src.address)
            q_fracs.append(sum(stats["bytes_from"].values()) / full_bytes)
            leaves = assemble_leaves(st)
            for path, orig in flat.items():
                # Slicing must commute with dequant: the assembled shard
                # equals the sliced dequantized FULL payload bit-for-bit.
                ref = dequantize_wire_leaf(
                    *quantize_wire_leaf(np.asarray(orig)), orig.dtype
                )
                sl = tuple(
                    slice(a, b) for a, b in
                    tensor_shard_slices(path, orig.shape, 2, rank)
                )
                got = np.asarray(leaves[path])
                if not np.array_equal(
                    got.view(np.uint8), np.ascontiguousarray(ref[sl]).view(np.uint8)
                ):
                    dequant_ok = False
                dequant_err = max(
                    dequant_err,
                    float(np.max(np.abs(
                        np.asarray(got, np.float32)
                        - np.asarray(orig[sl], np.float32)
                    ))),
                )
        tp2_int8_ms = (time.perf_counter() - t0) * 1000.0
        fpe = src.stats()["full_payload_equivalents"]

        # ---- assemble-side greedy-decode parity on a 2-dev CPU mesh ---
        parity_checked, parity_ok = 0.0, 0.0
        if len(jax.devices()) >= 2:
            parity_checked = 1.0
            parity_ok = 1.0 if _sharded_decode_parity(cb=1 << 12) else 0.0

        out = {
            "full_payload_bytes": float(full_bytes),
            "int8_payload_bytes": float(
                fetch_manifest(src.address, version=3, wire="int8")
                ["total_bytes"]
            ),
            "tp1_ingress_frac": tp1_frac,
            "tp2_ingress_frac": max(fracs),
            "tp2_int8_ingress_frac": max(q_fracs),
            "tp1_transfer_ms": tp1_ms,
            "tp2_transfer_ms": tp2_ms,
            "tp2_int8_transfer_ms": tp2_int8_ms,
            # Replica ingress came from the same-shard peer, not the
            # origin — sharded fleets keep the O(1)-origin property.
            "replica_bytes_from_origin": float(rep["bytes_from_origin"]),
            "replica_ingress_payload_equivalents": rep[
                "ingress_payload_equivalents"
            ],
            "origin_full_payloads": max(fpe.values()),
            "dequant_parity_ok": 1.0 if dequant_ok else 0.0,
            "dequant_max_abs_err": dequant_err,
            "decode_parity_checked": parity_checked,
            "decode_parity_ok": parity_ok,
        }
        log(f"bench: weight_plane_sharded {out}")
        return out
    finally:
        if holder0 is not None:
            holder0.close()
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _sharded_decode_parity(cb: int) -> bool:
    """Greedy-decode parity proof: a TP=2 ServingEngine (fake-device CPU
    mesh) cut over from two SLICED weight-plane streams must emit the
    same greedy tokens as an unsharded float engine holding the dumped
    params directly."""
    import queue as _queue
    import shutil
    import tempfile

    import jax

    from areal_tpu.engine.serving import (
        GenRequest, ServingEngine, serving_mesh,
    )
    from areal_tpu.engine.weight_client import (
        ChunkStore, assemble_leaves, fetch_manifest,
    )
    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import init_params
    from areal_tpu.system.weight_plane import WeightPlaneSource
    from areal_tpu.system.weight_transfer import dump_raw_params

    cfg = TransformerConfig(
        n_layers=2, hidden_dim=32, n_q_heads=2, n_kv_heads=2, head_dim=16,
        intermediate_dim=64, vocab_size=64, compute_dtype="float32",
        param_dtype="float32",
    )
    p_serve = jax.tree_util.tree_map(
        np.asarray, init_params(cfg, jax.random.PRNGKey(9))
    )
    p_boot = jax.tree_util.tree_map(
        np.asarray, init_params(cfg, jax.random.PRNGKey(0))
    )

    def greedy(eng, ids, n=8):
        q: "_queue.Queue" = _queue.Queue()
        eng.submit(GenRequest(
            qid="q", input_ids=list(ids), max_new_tokens=n, greedy=True,
            done_cb=q.put,
        ))
        r = q.get(timeout=300)
        if r.error is not None:
            raise RuntimeError(r.error)
        return r.output_ids

    tmp = tempfile.mkdtemp(prefix="areal_wps_parity_")
    src = None
    engines = []
    try:
        dump_raw_params(p_serve, tmp, version=1, chunk_bytes=cb)
        src = WeightPlaneSource(tmp, chunk_bytes=cb).start()
        leaves_by_rank, gshapes = {}, {}
        for rank in range(2):
            man = fetch_manifest(
                src.address, version=1, tp_degree=2, tp_rank=rank
            )
            st = ChunkStore(man)
            st.fetch([src.address], origin=src.address)
            leaves_by_rank[rank] = assemble_leaves(st)
            gshapes.update({
                e["path"]: tuple(e["global_shape"])
                for e in man["leaves"]
            })
        base = ServingEngine(
            cfg, p_serve, max_batch_size=2, max_seq_len=128,
            decode_block_steps=4, page_size=8, seed=0,
        )
        base.start()
        engines.append(base)
        want = greedy(base, [5, 6, 7])
        tp = ServingEngine(
            cfg, p_boot, max_batch_size=2, max_seq_len=128,
            decode_block_steps=4, page_size=8, seed=0,
            mesh=serving_mesh(2),
        )
        tp.start()
        engines.append(tp)
        tp.cutover_shard_leaves(
            leaves_by_rank, 2, version=1, global_shapes=gshapes
        )
        got = greedy(tp, [5, 6, 7])
        log(f"bench: sharded decode parity base={want} tp={got}")
        return got == want
    finally:
        for e in engines:
            try:
                e.stop()
            except Exception:
                pass
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _sharded_train_cfg():
    """Tiny deterministic float32 shape whose big dims all divide 2, so
    FSDP2/TP2 fake-device meshes shard every matmul leaf evenly."""
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(
        n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2, head_dim=8,
        intermediate_dim=64, vocab_size=64, compute_dtype="float32",
        param_dtype="float32",
    )


def train_sharded_phase(pass_: str) -> dict:
    """Sharded training end-to-end on a 2-fake-device CPU mesh (ISSUE 9
    acceptance): loss-trajectory parity of the single-device engine vs
    FSDP2 and TP2 meshes (same init, same batch, same LR — GSPMD mesh
    placement must be a scheduling change, not a numeric one), the
    step-time breakdown per mesh, and the shard-local dump's host
    high-water reduction (~1/mesh_size) with a byte-identical round
    trip through the live weight-plane origin (full stream AND a
    TP2-sliced stream hash-equal to a contiguous dump of the same
    values). Loss parity and byte accounting are machine-independent,
    which is why a CPU-proxy record is real evidence here; absolute
    step times only mean anything on-chip."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # tiny CPU-mesh programs; measure pays
    return _train_sharded_measure()


def _train_sharded_measure() -> dict:
    import shutil
    import tempfile

    import jax

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.weight_client import (
        ChunkStore, assemble_params, fetch_manifest,
    )
    from areal_tpu.models.transformer import init_params
    from areal_tpu.ops.loss import sft_loss_from_logprobs
    from areal_tpu.parallel.mesh import make_mesh, single_device_mesh
    from areal_tpu.system import weight_transfer as wt
    from areal_tpu.system.weight_plane import WeightPlaneSource

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "train_sharded needs >= 2 devices (the phase env requests "
            "--xla_force_host_platform_device_count=2)"
        )
    cfg = _sharded_train_cfg()
    seqlen, n_seqs, n_steps = 32, 8, 3
    params0 = jax.tree_util.tree_map(
        np.asarray, init_params(cfg, jax.random.PRNGKey(5))
    )
    rng = np.random.RandomState(5)
    total = seqlen * n_seqs
    batch = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n_seqs)],
        seqlens=[seqlen] * n_seqs,
        data={
            "packed_input_ids": rng.randint(0, cfg.vocab_size, size=total),
            "loss_mask": np.ones(total, np.float32),
        },
    )

    def packed_loss(lp, rows):
        tot, _ = sft_loss_from_logprobs(lp, rows["loss_mask"])
        return tot, {}

    def weight(mb):
        return float(np.sum(mb.data["loss_mask"]))

    t_start = time.monotonic()
    meshes = {
        "single": single_device_mesh(),
        "fsdp2": make_mesh(MeshSpec.parse("f2"), jax.devices()[:2]),
        "tp2": make_mesh(MeshSpec.parse("t2"), jax.devices()[:2]),
    }
    losses: dict = {}
    step_s: dict = {}
    engines: dict = {}
    for name, mesh in meshes.items():
        eng = JaxTrainEngine(
            cfg, jax.tree_util.tree_map(np.copy, params0), mesh=mesh,
            optimizer_config=OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0
            ),
            total_train_steps=100, row_len_multiple=seqlen,
            max_row_len=seqlen,
        )
        traj, times = [], []
        for i in range(n_steps):
            t0 = time.perf_counter()
            st = eng.train_batch(
                batch, MicroBatchSpec(n_mbs=2), packed_loss, weight,
                version_steps=i, loss_name="bench",
            )
            jax.block_until_ready(eng.params)
            times.append(time.perf_counter() - t0)
            traj.append(st["bench/loss"])
        losses[name] = traj
        step_s[name] = float(np.mean(times[1:]) if len(times) > 1
                             else times[0])
        engines[name] = eng
        log(f"bench: train_sharded {name} losses={traj} "
            f"step_s={step_s[name]:.3f}")

    # Loss-trajectory parity: the mesh paths must track the
    # single-device trajectory (CPU collectives reorder float sums, so
    # tolerance, not bitwise).
    ref = np.asarray(losses["single"])
    parity = {}
    max_rel = 0.0
    for name in ("fsdp2", "tp2"):
        rel = float(np.max(np.abs(np.asarray(losses[name]) - ref)
                           / np.maximum(np.abs(ref), 1e-8)))
        max_rel = max(max_rel, rel)
        parity[name] = rel < 5e-4
        log(f"bench: train_sharded parity {name}: max rel err {rel:.2e}")

    # Shard-local dump: high-water ~1/2 of the full-gather dump, byte
    # stream identical, round-trips through the live origin.
    tmp_full = tempfile.mkdtemp(prefix="areal_ts_full_")
    tmp_shard = tempfile.mkdtemp(prefix="areal_ts_shard_")
    src = src_full = None
    cb = 64 << 10
    try:
        post = engines["fsdp2"].params  # trained, fsdp2-sharded tree
        post_host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), post
        )
        dump_full_s = wt.dump_raw_params(
            post_host, tmp_full, version=1, chunk_bytes=cb
        )
        full_hw = wt.LAST_DUMP_STATS["high_water_bytes"]
        dump_shard_s = wt.dump_raw_params_sharded(
            post, tmp_shard, version=1, chunk_bytes=cb
        )
        shard_hw = wt.LAST_DUMP_STATS["high_water_bytes"]

        src = WeightPlaneSource(tmp_shard, chunk_bytes=cb).start()
        src_full = WeightPlaneSource(tmp_full, chunk_bytes=cb).start()
        man = fetch_manifest(src.address, version=1)
        man_ref = fetch_manifest(src_full.address, version=1)
        stream_equal = (
            man["hashes"] == man_ref["hashes"]
            and man["total_bytes"] == man_ref["total_bytes"]
        )
        # TP2-sliced streams over the slab-backed origin must equal the
        # contiguous dump's slices too (serving fleets fetch these).
        for rank in range(2):
            a = fetch_manifest(src.address, version=1,
                               tp_degree=2, tp_rank=rank)
            b = fetch_manifest(src_full.address, version=1,
                               tp_degree=2, tp_rank=rank)
            stream_equal = stream_equal and a["hashes"] == b["hashes"]
        st = ChunkStore(man)
        st.fetch([src.address], origin=src.address)
        assembled, _v = assemble_params(st)
        roundtrip = all(
            np.array_equal(
                np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8)
            )
            for x, y in zip(
                jax.tree_util.tree_leaves(post_host),
                jax.tree_util.tree_leaves(assembled),
            )
        )
    finally:
        for s in (src, src_full):
            if s is not None:
                s.close()
        shutil.rmtree(tmp_full, ignore_errors=True)
        shutil.rmtree(tmp_shard, ignore_errors=True)

    out = {
        "n_devices": 2.0,
        "n_steps": float(n_steps),
        "fsdp2_parity_ok": 1.0 if parity["fsdp2"] else 0.0,
        "tp2_parity_ok": 1.0 if parity["tp2"] else 0.0,
        "loss_parity_max_rel_err": max_rel,
        "single_step_s": step_s["single"],
        "fsdp2_step_s": step_s["fsdp2"],
        "tp2_step_s": step_s["tp2"],
        "dump_full_s": dump_full_s,
        "dump_sharded_s": dump_shard_s,
        "dump_full_highwater_bytes": float(full_hw),
        "dump_shard_highwater_bytes": float(shard_hw),
        "dump_highwater_frac": shard_hw / max(full_hw, 1),
        "dump_roundtrip_ok": 1.0 if (roundtrip and stream_equal) else 0.0,
        "wall_s": time.monotonic() - t_start,
    }
    log(f"bench: train_sharded {out}")
    return out


def _moe_bench_cfg(dispatch="dropless", capacity_factor=8.0):
    """Expert-dominated MoE bench shape: E=4 experts of F=512 with
    top_k=2, so per-token ACTIVE expert FLOPs equal a dense FFN of
    intermediate_dim 1024 (`_dense_matched_cfg`), expert weights are
    ~97% of total bytes (the regime where the EP stream's replicated
    non-expert leaves cost the origin only ~3% extra egress), and every
    sharded dim divides the 2-fake-device mesh. capacity_factor=8 >=
    E/k guarantees zero drops, so the capacity arm is loss-comparable
    to dropless."""
    from areal_tpu.models.config import MoEConfig, TransformerConfig

    return TransformerConfig(
        n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2, head_dim=8,
        intermediate_dim=32, vocab_size=64, compute_dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, dispatch=dispatch,
                      capacity_factor=capacity_factor,
                      expert_intermediate_dim=512, aux_loss_coef=1e-2),
    )


def _dense_matched_cfg():
    """Dense control with the same ACTIVE per-token matmul FLOPs as
    `_moe_bench_cfg` (intermediate_dim = top_k * expert_intermediate_dim
    = 1024; the router matmul D*E is the only extra)."""
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(
        n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2, head_dim=8,
        intermediate_dim=1024, vocab_size=64, compute_dtype="float32",
        param_dtype="float32",
    )


def moe_scaling_phase(pass_: str) -> dict:
    """MoE fast-path evidence (ISSUE 17): dense vs MoE per-token step
    time at matched active FLOPs, expert-parallel dropless EP1 vs EP2
    with loss-trajectory parity, the capacity-vs-dropless dispatch A/B
    (with a capacity-factor drop-rate sweep), and the expert-sliced
    weight stream's per-rank ingress ~1/EP over a live origin. Loss
    parity, realized drop rates, and byte accounting are exact and
    machine-independent — CPU-proxy rounds are real evidence for them;
    absolute step times only mean anything on-chip."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # tiny CPU-mesh programs; measure pays
    return _moe_scaling_measure()


def _moe_scaling_measure() -> dict:
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.weight_client import ChunkStore, fetch_manifest
    from areal_tpu.models.moe import moe_mlp
    from areal_tpu.models.transformer import init_params
    from areal_tpu.ops.loss import sft_loss_from_logprobs
    from areal_tpu.parallel.mesh import make_mesh, single_device_mesh
    from areal_tpu.system import weight_transfer as wt
    from areal_tpu.system.weight_plane import WeightPlaneSource

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "moe_scaling needs >= 2 devices (the phase env requests "
            "--xla_force_host_platform_device_count=2)"
        )
    t_start = time.monotonic()
    seqlen, n_seqs, n_steps = 32, 4, 3
    total = seqlen * n_seqs
    rng = np.random.RandomState(7)
    batch = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n_seqs)],
        seqlens=[seqlen] * n_seqs,
        data={
            "packed_input_ids": rng.randint(0, 64, size=total),
            "loss_mask": np.ones(total, np.float32),
        },
    )

    def packed_loss(lp, rows):
        tot, _ = sft_loss_from_logprobs(lp, rows["loss_mask"])
        return tot, {}

    def weight(mb):
        return float(np.sum(mb.data["loss_mask"]))

    def run_arm(cfg, mesh, params0):
        eng = JaxTrainEngine(
            cfg, jax.tree_util.tree_map(np.copy, params0), mesh=mesh,
            optimizer_config=OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0
            ),
            total_train_steps=100, row_len_multiple=seqlen,
            max_row_len=seqlen,
        )
        traj, times, last = [], [], {}
        for i in range(n_steps):
            t0 = time.perf_counter()
            last = eng.train_batch(
                batch, MicroBatchSpec(n_mbs=2), packed_loss, weight,
                version_steps=i, loss_name="bench",
            )
            jax.block_until_ready(eng.params)
            times.append(time.perf_counter() - t0)
            traj.append(last["bench/loss"])
        step_s = float(np.mean(times[1:]) if len(times) > 1 else times[0])
        return traj, step_s, last

    moe_cfg = _moe_bench_cfg()
    params0 = jax.tree_util.tree_map(
        np.asarray, init_params(moe_cfg, jax.random.PRNGKey(11))
    )
    dense_params0 = jax.tree_util.tree_map(
        np.asarray, init_params(_dense_matched_cfg(), jax.random.PRNGKey(11))
    )

    dense_traj, dense_step_s, _ = run_arm(
        _dense_matched_cfg(), single_device_mesh(), dense_params0
    )
    ep1_traj, ep1_step_s, ep1_stats = run_arm(
        _moe_bench_cfg(), single_device_mesh(), params0
    )
    ep2_traj, ep2_step_s, ep2_stats = run_arm(
        _moe_bench_cfg(),
        make_mesh(MeshSpec.parse("f2"), jax.devices()[:2]), params0,
    )
    cap_traj, cap_step_s, cap_stats = run_arm(
        _moe_bench_cfg(dispatch="capacity"), single_device_mesh(), params0
    )
    log(f"bench: moe_scaling dense={dense_traj} ep1={ep1_traj} "
        f"ep2={ep2_traj} cap={cap_traj}")

    def rel(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-8)))

    # Dropless EP2 must TRACK dropless EP1 (the shard_map exchange is a
    # scheduling change, not a numeric one); the no-drop capacity arm
    # tracks both within collective-reorder tolerance.
    ep_rel = rel(ep2_traj, ep1_traj)
    cap_rel = rel(cap_traj, ep1_traj)
    ep_parity = ep_rel < 1e-5
    cap_parity = cap_rel < 5e-4
    log(f"bench: moe_scaling parity ep2-vs-ep1 {ep_rel:.2e} "
        f"capacity-vs-dropless {cap_rel:.2e}")

    # Capacity-factor drop-rate sweep (layer-level, one expert layer):
    # drops must fall monotonically as capacity grows and vanish by
    # capacity_factor >= E/top_k; dropless realizes zero by construction.
    mp0 = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a)[0]),
        params0["layers"]["mlp"],
    )
    xs = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    sweep = []
    for cf in (0.25, 0.5, 1.0, 2.0):
        swept = _moe_bench_cfg(dispatch="capacity", capacity_factor=cf)
        _, aux = moe_mlp(xs, mp0, swept, jnp.float32)
        sweep.append({
            "capacity_factor": float(cf),
            "drop_rate": float(aux["drop_rate"]),
        })
    log(f"bench: moe_scaling capacity sweep {sweep}")

    # Expert-sliced weight streams over a live origin: each EP rank's
    # manifest carries ~1/EP of the bytes (expert-dominated model), and
    # both ranks together cost the origin ~ONE full payload.
    tmp = tempfile.mkdtemp(prefix="areal_moe_scaling_")
    src = None
    try:
        wt.dump_raw_params(params0, tmp, version=1, chunk_bytes=64 << 10)
        src = WeightPlaneSource(tmp, chunk_bytes=64 << 10).start()
        ingress = []
        for rank_i in range(2):
            man = fetch_manifest(
                src.address, version=1, ep_degree=2, ep_rank=rank_i
            )
            st = ChunkStore(man)
            st.fetch([src.address], origin=src.address)
            ingress.append(man["total_bytes"] / man["model_total_bytes"])
        origin_payloads = float(src.stats()["full_payload_equivalents"][1])
    finally:
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)
    log(f"bench: moe_scaling ep ingress {ingress} "
        f"origin payloads {origin_payloads:.3f}")

    tokens = float(total)
    out = {
        "n_devices": 2.0,
        "n_steps": float(n_steps),
        "dense_step_s": dense_step_s,
        "moe_ep1_step_s": ep1_step_s,
        "moe_ep2_step_s": ep2_step_s,
        "capacity_step_s": cap_step_s,
        "dense_step_per_token_us": dense_step_s / tokens * 1e6,
        "moe_step_per_token_us": ep1_step_s / tokens * 1e6,
        "moe_vs_dense_step_ratio": ep1_step_s / max(dense_step_s, 1e-9),
        "ep2_vs_ep1_step_ratio": ep2_step_s / max(ep1_step_s, 1e-9),
        "dispatch_ab_ratio": cap_step_s / max(ep1_step_s, 1e-9),
        "ep_parity_ok": 1.0 if ep_parity else 0.0,
        "capacity_parity_ok": 1.0 if cap_parity else 0.0,
        "ep_loss_max_rel_err": ep_rel,
        "capacity_loss_max_rel_err": cap_rel,
        "dropless_drop_rate": float(ep1_stats["bench/moe_drop_rate"]),
        "ep2_drop_rate": float(ep2_stats["bench/moe_drop_rate"]),
        "capacity_drop_rate": float(cap_stats["bench/moe_drop_rate"]),
        "router_entropy": float(ep1_stats["bench/moe_router_entropy"]),
        "ep2_a2a_bytes": float(ep2_stats["bench/moe_a2a_bytes"]),
        "capacity_sweep": sweep,
        "ep_degree": 2.0,
        "ep_ingress_frac_max": float(max(ingress)),
        "origin_full_payloads": origin_payloads,
        "wall_s": time.monotonic() - t_start,
    }
    log(f"bench: moe_scaling {out}")
    return out


def rpc_resilience_phase(pass_: str) -> dict:
    """Hedged vs unhedged chunk-pull tail latency under injected delay
    (ISSUE 14 acceptance): two peer holders serve the same hash-verified
    /weights/chunk stream over loopback HTTP; the chaos ``delay``
    action makes every ODD serve slow (alternating at_hit windows), so
    an unhedged client eats the injected tail on half its pulls while a
    hedged client (base/rpc.py hedged_sync, first verified chunk wins,
    losers abandoned) escapes it for the price of the hedge delay.
    Proxy evidence by construction (loopback, injected tail): what it
    banks is the SUBSTRATE's behavior — hedged p99 must sit near the
    hedge delay, unhedged p99 near the injected delay — plus the
    win/cancel accounting the no-double-count tests pin."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # host + loopback only
    import shutil
    import tempfile

    from areal_tpu.base import rpc
    from areal_tpu.base.chunking import verify_chunk
    from areal_tpu.base.fault_injection import faults
    from areal_tpu.engine.weight_client import ChunkStore, fetch_manifest
    from areal_tpu.system.weight_plane import (
        PeerStoreServer, WeightPlaneSource,
    )
    from areal_tpu.system.weight_transfer import dump_raw_params

    delay_s = 0.35       # injected tail (the slow-peer stand-in)
    hedge_delay_s = 0.05  # silence window before the hedge launches
    rng = np.random.RandomState(3)
    params = {
        "layers": {
            f"l{i:02d}": {
                "w": rng.standard_normal((128, 128)).astype(np.float32)
            }
            for i in range(16)
        }
    }
    tmp = tempfile.mkdtemp(prefix="areal_rpc_bench_")
    src = None
    peers = []
    faults.reset()
    try:
        dump_raw_params(params, tmp, version=1, chunk_bytes=1 << 15)
        src = WeightPlaneSource(tmp, chunk_bytes=1 << 15).start()
        man = fetch_manifest(src.address, version=1)
        n_chunks = int(man["n_chunks"])
        for _ in range(2):
            peer = PeerStoreServer().start()
            peer.store = ChunkStore(man)
            peer.store.fetch([src.address], origin=src.address)
            peers.append(peer)

        def pull(peer_url, idx):
            def fetch():
                data = rpc.get_bytes_sync(
                    f"{peer_url}/weights/chunk?version=1&idx={idx}",
                    policy=rpc.default_policy(attempts=2),
                    what="bench chunk",
                )
                if not verify_chunk(data, man["hashes"][idx]):
                    raise ValueError(f"chunk {idx} hash mismatch")
                return data
            return fetch

        def arm_odd_hits_slow():
            """Every odd serve_chunk hit sleeps ``delay_s``: the
            unhedged arm's every-other-pull tail, and the hedged arm's
            every-primary tail (primary odd, hedge even)."""
            faults.reset()
            for i in range(2 * n_chunks + 4):
                faults.arm(
                    "weight_plane.serve_chunk", action="delay",
                    delay_s=delay_s, at_hit=2 * i + 1, times=1,
                )

        def p(q, xs):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(q * (len(xs) - 1)))]

        # -- arm A: unhedged (one holder, no race) ----------------------
        arm_odd_hits_slow()
        unhedged_ms = []
        for i in range(n_chunks):
            t0 = time.perf_counter()
            pull(peers[0].address, i)()
            unhedged_ms.append((time.perf_counter() - t0) * 1000.0)

        # -- arm B: hedged (two holders, loser cancelled) ---------------
        arm_odd_hits_slow()
        before = rpc.stats.snapshot()
        hedged_ms = []
        for i in range(n_chunks):
            t0 = time.perf_counter()
            rpc.hedged_sync(
                [pull(peers[0].address, i), pull(peers[1].address, i)],
                hedge_delay=hedge_delay_s,
            )
            hedged_ms.append((time.perf_counter() - t0) * 1000.0)
        after = rpc.stats.snapshot()

        out = {
            "n_chunks": float(n_chunks),
            "injected_delay_ms": delay_s * 1000.0,
            "hedge_delay_ms": hedge_delay_s * 1000.0,
            "unhedged_p50_ms": p(0.5, unhedged_ms),
            "unhedged_p99_ms": p(0.99, unhedged_ms),
            "hedged_p50_ms": p(0.5, hedged_ms),
            "hedged_p99_ms": p(0.99, hedged_ms),
            "hedge_wins": float(
                after["hedge_wins"] - before["hedge_wins"]
            ),
            "hedge_cancelled": float(
                after["hedge_cancelled"] - before["hedge_cancelled"]
            ),
            # The dedicated whole-race counter, NOT "failures": a
            # transient single-leg blip inside a race the hedge WON
            # would otherwise fail the validator's zero-failures tooth.
            "hedge_failures": float(
                after["hedge_failures"] - before["hedge_failures"]
            ),
        }
        log(f"bench: rpc_resilience {out}")
        return out
    finally:
        faults.reset()
        for peer in peers:
            peer.close()
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)


def weight_update_phase(pass_: str) -> dict:
    """Weight-distribution plane end-to-end on loopback HTTP: dump a
    raw-bin payload, serve it from a WeightPlaneSource origin, fan it
    out to 3 holders along a degree-1 chain (the maximum-peer-hop
    shape), then host-assemble each holder's buffer as the cutover
    proxy. Proxy evidence by construction (no device swap, no real
    serving engine): what it banks is the plane's software overhead —
    chunk/hash/HTTP cost per MB — and the O(1)-origin-egress invariant
    (``origin_full_payloads`` must stay ~1.0; the validator refuses
    records where peer fanout silently degraded to origin broadcast)."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # host + loopback only
    import shutil
    import tempfile

    from areal_tpu.engine.weight_client import assemble_params
    from areal_tpu.system.weight_plane import (
        WeightPlaneSource, distribute_to_stores,
    )
    from areal_tpu.system.weight_transfer import dump_raw_params

    rng = np.random.RandomState(0)
    # ~16 MiB payload: big enough that per-chunk overhead is amortized
    # like production, small enough for a sub-30s proxy phase.
    params = {
        "layers": {
            f"l{i:02d}": {
                "w": rng.standard_normal((512, 256)).astype(np.float32)
            }
            for i in range(32)
        }
    }
    n_holders, version = 3, 1
    tmp = tempfile.mkdtemp(prefix="areal_wp_bench_")
    holders, src = [], None
    try:
        dump_raw_params(params, tmp, version=version, chunk_bytes=1 << 20)
        src = WeightPlaneSource(tmp, chunk_bytes=1 << 20).start()
        t0 = time.perf_counter()
        holders, stats = distribute_to_stores(
            src.address, n_holders, degree=1, version=version
        )
        cutover_ms = []
        for h in holders:
            t1 = time.perf_counter()
            assemble_params(h.store)
            cutover_ms.append((time.perf_counter() - t1) * 1000.0)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        origin_eq = src.stats()["full_payload_equivalents"].get(version, 0.0)
        out = {
            "weight_update_ms": wall_ms,
            "weight_transfer_ms": max(
                s["fetch_s"] for s in stats["per_holder"].values()
            ) * 1000.0,
            "weight_cutover_ms": max(cutover_ms),
            "origin_full_payloads": origin_eq,
            "n_holders": float(n_holders),
            "payload_mb": stats["total_bytes"] / float(1 << 20),
            "n_chunks": float(stats["n_chunks"]),
        }
        log(f"bench: weight_update {out}")
        return out
    finally:
        for h in holders:
            h.close()
        if src is not None:
            src.close()
        shutil.rmtree(tmp, ignore_errors=True)


# ----------------------------------------------------------------------
# fleet_elastic: the elastic fleet control plane's headline probe
# (ISSUE 12). One real-process fleet lives through the whole elastic
# story under sustained PartialRolloutManager load: a runtime JOIN
# bootstrapped from peers (zero origin bytes), a manager SIGKILL +
# successor takeover (lease epoch bump, zero failed rollouts), a second
# join forced through the origin (the baseline arm of the
# peer-vs-origin A/B), and a drain-then-leave that migrates every
# parked prefix to the survivors over the /kv wire.
# ----------------------------------------------------------------------

_FLEET_SRV = dict(
    max_concurrent_requests=4, max_seq_len=256, kv_page_size=16,
    decode_block_steps=4, prompt_bucket=16, prefill_chunk=16,
    prefix_cache_tokens=512, warm_on_start=True,
)
_FLEET_CHUNK = 1 << 15
_FLEET_PLEN = 48
_FLEET_TURN_NEW = 6


class _FleetLoad:
    """Sustained 2-turn-session load through the real
    PartialRolloutManager client on a dedicated asyncio thread — the
    production retry/rediscovery path, so a manager death mid-run is
    ridden out instead of failing rollouts."""

    def __init__(self, fleet, n_streams: int):
        import asyncio
        import threading

        from areal_tpu.api.model_api import GenerationHyperparameters
        from areal_tpu.base import name_resolve, names
        from areal_tpu.system.partial_rollout import PartialRolloutManager

        self.completed = 0
        self.failed = 0
        self._stop = threading.Event()

        def resolver():
            return name_resolve.get(
                names.gen_server_manager(fleet.exp, fleet.trial)
            )

        async def session(prm, i, k):
            rng = np.random.RandomState(9000 + i * 131 + k)
            prompt = rng.randint(
                1, _OPENLOOP_MODEL["vocab_size"], size=_FLEET_PLEN
            ).tolist()
            g = GenerationHyperparameters(
                max_new_tokens=_FLEET_TURN_NEW, greedy=True
            )
            out1 = await prm._generate_one(f"ld{i}-{k}", prompt, g)
            out2 = await prm._generate_one(
                f"ld{i}-{k}", prompt + list(out1.output_ids) + [3], g
            )
            if len(out2.output_ids) != _FLEET_TURN_NEW:
                raise RuntimeError(f"short turn 2: {out2}")

        async def stream(prm, i):
            k = 0
            while not self._stop.is_set():
                try:
                    await session(prm, i, k)
                    self.completed += 1
                except Exception as e:
                    self.failed += 1
                    log(f"bench: fleet_elastic load failure: {e!r}")
                k += 1

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            prm = PartialRolloutManager(
                fleet.manager_addr(), request_timeout=120.0,
                max_retries=8, retry_backoff_s=0.1,
                addr_resolver=resolver,
            )
            try:
                loop.run_until_complete(asyncio.gather(
                    *[stream(prm, i) for i in range(n_streams)]
                ))
                loop.run_until_complete(prm.close())
            finally:
                loop.close()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 120.0) -> dict:
        self._stop.set()
        self._thread.join(timeout=timeout)
        return {"completed": self.completed, "failed": self.failed}


def _fleet_wait(cond, timeout_s: float, msg: str):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.2)
    raise RuntimeError(f"fleet_elastic: timed out waiting for {msg}")


def _fleet_first_routed_token_ms(fleet, url: str, t0: float,
                                 tag: str) -> float:
    """Route requests through the manager until one lands on `url`
    (its total_requests counter moves); returns ms since t0 — the
    join-to-first-routed-token clock."""
    base = fleet.metrics(url).get(mreg.TOTAL_REQUESTS, 0.0)
    i = 0
    while fleet.metrics(url).get(mreg.TOTAL_REQUESTS, 0.0) <= base:
        rng = np.random.RandomState(7000 + i)
        fleet.generate_routed(
            f"{tag}{i}",
            rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                        size=8).tolist(),
            2, timeout=120,
        )
        i += 1
        if i > 200:
            raise RuntimeError(
                f"fleet_elastic: {url} never served a routed token"
            )
    return (time.monotonic() - t0) * 1000.0


def _fleet_autoscale_arm(tier_env: dict) -> dict:
    """AUTOSCALER-driven growth (ISSUE 20 satellite): the manager's
    WatermarkAutoscaler — not the harness — must issue the scale-out.
    A one-server fleet with a SubprocessLauncher attached sits under
    sustained queue pressure until the queued-token watermark trips
    and the manager launches server 2 itself; the harness never calls
    spawn_server. validate_bench refuses records whose growth is not
    fully attributable to launcher actions."""
    import threading

    from areal_tpu.bench.fleet import ProcessFleet
    from areal_tpu.system.fleet_controller import SubprocessLauncher

    fleet = ProcessFleet(
        _OPENLOOP_MODEL, [dict(_FLEET_SRV, env=tier_env)],
        manager_kw=dict(
            autoscale=True, scale_out_queued_tokens=32,
            # avg_q is never negative, so -1 disables scale-in: the
            # arm measures growth attribution, not shrink.
            scale_in_queued_tokens=-1, pool_max_servers=2,
            scale_cooldown_s=2.0, scale_sustain_polls=2,
        ),
        tag="flas",
    )
    stop = threading.Event()
    failures = [0]

    def pressure(i: int):
        k = 0
        while not stop.is_set():
            rng = np.random.RandomState(6000 + i * 257 + k)
            out = fleet.generate_routed(
                f"as{i}-{k}",
                rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                            size=_FLEET_PLEN).tolist(),
                16, timeout=120,
            )
            if "error" in out:
                failures[0] += 1
            k += 1

    try:
        launcher = SubprocessLauncher(
            lambda idx: fleet._spawn_server_child(
                idx, dict(_FLEET_SRV, env=tier_env)
            )
        )
        fleet.manager.attach_launcher(launcher)
        t0 = time.monotonic()
        threads = [
            threading.Thread(target=pressure, args=(i,), daemon=True)
            for i in range(8)
        ]
        for th in threads:
            th.start()
        _fleet_wait(
            lambda: len(fleet.status()["healthy_servers"]) >= 2,
            240.0, "autoscaler-driven scale-out",
        )
        grow_ms = (time.monotonic() - t0) * 1000.0
        st = fleet.status()
        outs = [
            e for e in st["fleet"]["autoscale"] if e["action"] == "out"
        ]
        n_after = len(st["healthy_servers"])
        out = {
            "autoscale_n_before": 1.0,
            "autoscale_n_after": float(n_after),
            "autoscale_out_actions": float(len(outs)),
            "autoscale_launched": float(len(launcher.procs)),
            "autoscale_grow_ms": grow_ms,
            "autoscale_load_failed": float(failures[0]),
        }
        log(f"bench: fleet_elastic autoscale arm: {out}")
        return out
    finally:
        stop.set()
        fleet.close()


def fleet_elastic_phase(pass_: str) -> dict:
    import tempfile

    import jax

    from areal_tpu.base import constants, name_resolve, names
    from areal_tpu.bench.fleet import ProcessFleet
    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import init_params
    from areal_tpu.system.weight_plane import WeightPlaneSource
    from areal_tpu.system.weight_transfer import dump_raw_params

    t_start = time.monotonic()
    tier_env = {"AREAL_KV_TIER_BYTES": str(64 << 20)}

    if pass_ == "compile":
        # One fleet, one 2-turn session: compiles the chunked prefill,
        # decode block, and restore-path programs into the persistent
        # cache so the measure pass's six server spawns all hit warm.
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL, [dict(_FLEET_SRV, env=tier_env)],
            tag="flec",
        ) as fleet:
            rng = np.random.RandomState(1)
            p = rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                            size=_FLEET_PLEN).tolist()
            out = fleet.generate_routed("c0", p, _FLEET_TURN_NEW,
                                        timeout=600)
            assert "output_ids" in out, out
            fleet.generate_routed(
                "c0", p + [int(t) for t in out["output_ids"]] + [3],
                _FLEET_TURN_NEW, timeout=600,
            )
        dt = time.perf_counter() - t0
        log(f"bench: fleet_elastic compile pass {dt:.1f}s")
        return {"compile_s": dt}

    # ---- Arm 0: autoscaler-driven growth on its own tiny fleet (no
    # weight plane needed — the arm is about WHO issues the launch).
    auto = _fleet_autoscale_arm(tier_env)

    # Children and this process must agree on the param-realloc path
    # (the weight-plane origin serves the dump dir): pin AREAL_FILEROOT
    # before the fleet copies the environment — and restore/clean it in
    # the finally below so a later phase in the same process doesn't
    # inherit this phase's scratch root.
    prev_fileroot = env_registry.get_raw("AREAL_FILEROOT")
    fileroot = tempfile.mkdtemp(prefix="areal_flel_")
    os.environ["AREAL_FILEROOT"] = fileroot
    mgr_kw = dict(
        weight_plane=True, weight_chunk_bytes=_FLEET_CHUNK,
        weight_fanout_degree=2, flush_request_timeout=120.0,
        drain_timeout_s=240.0, join_bootstrap="peers",
    )
    src = None
    load = None
    fleet = None
    try:
        # Inside the try: a child dying at spawn must still restore
        # AREAL_FILEROOT and remove the scratch root in the finally.
        fleet = ProcessFleet(
            _OPENLOOP_MODEL, [dict(_FLEET_SRV, env=tier_env)] * 2,
            manager_kw=mgr_kw, manager_subprocess=True,
            manager_env={"AREAL_FLEET_LEASE_TTL": "2"}, tag="flee",
        )
        # ---- Trainer-side dump v1 + plane source + version publish:
        # the substrate every join bootstraps from.
        role_dir = os.path.join(
            constants.get_param_realloc_path(fleet.exp, fleet.trial),
            "actor",
        )
        os.makedirs(role_dir, exist_ok=True)
        with open(os.path.join(role_dir, "engine_state.pkl"), "wb") as f:
            f.write(b"gate")  # existence gate for check_new_params
        cfg = TransformerConfig(**_OPENLOOP_MODEL)
        p1 = jax.tree_util.tree_map(
            lambda x: np.asarray(x), init_params(cfg, jax.random.PRNGKey(7))
        )
        dump_raw_params(p1, role_dir, version=1, chunk_bytes=_FLEET_CHUNK)
        src = WeightPlaneSource(role_dir, chunk_bytes=_FLEET_CHUNK).start()
        src.register(fleet.exp, fleet.trial, "actor")
        name_resolve.add(
            names.model_version(fleet.exp, fleet.trial, "actor"), "1",
            replace=True,
        )
        _fleet_wait(
            lambda: fleet.status()["weight_version"] == 1, 120.0,
            "v1 plane fanout",
        )

        load = _FleetLoad(fleet, n_streams=2)
        _fleet_wait(lambda: load.completed >= 2, 180.0,
                    "load warm-up sessions")

        # ---- Arm A: runtime JOIN, bootstrapped from PEERS.
        t0 = time.monotonic()
        url2 = fleet.spawn_server(dict(_FLEET_SRV, env=tier_env))
        st = fleet.wait_healthy(3, timeout_s=300)
        join_peer_ms = _fleet_first_routed_token_ms(
            fleet, url2, t0, "ja")
        joins = fleet.status()["fleet"]["joins"]
        jp = [e for e in joins if e["url"] == url2][-1]
        log(f"bench: fleet_elastic peer join: {jp} "
            f"first-token {join_peer_ms:.0f}ms")

        # ---- Manager killover: SIGKILL the live manager mid-load,
        # spawn a successor that takes the lease (epoch 2) and
        # rebuilds; the load's rediscovery path must ride it out.
        epoch0 = st["fleet"]["epoch"]
        fleet.mgr_procs[-1].kill()
        t0 = time.monotonic()
        fleet._manager_kw["join_bootstrap"] = "origin"
        fleet.spawn_manager()
        st = fleet.wait_healthy(3, timeout_s=300, epoch=epoch0 + 1)
        killover_ms = (time.monotonic() - t0) * 1000.0
        log(f"bench: fleet_elastic killover: epoch {st['fleet']['epoch']} "
            f"in {killover_ms:.0f}ms")

        # ---- Arm B: a second join forced through the ORIGIN (the
        # baseline the peer arm beats on origin egress).
        t0 = time.monotonic()
        url3 = fleet.spawn_server(dict(_FLEET_SRV, env=tier_env))
        fleet.wait_healthy(4, timeout_s=300)
        join_origin_ms = _fleet_first_routed_token_ms(
            fleet, url3, t0, "jb")
        joins = fleet.status()["fleet"]["joins"]
        jo = [e for e in joins if e["url"] == url3][-1]
        log(f"bench: fleet_elastic origin join: {jo} "
            f"first-token {join_origin_ms:.0f}ms")

        # ---- Drain-then-leave: park prefixes on the victim, then
        # drain it; the parked KV must MIGRATE to survivors over the
        # /kv wire (no loss) and the departure must be clean.
        rng = np.random.RandomState(55)
        parked = {}
        for i in range(3):
            p = rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                            size=_FLEET_PLEN).tolist()
            out = fleet.generate_direct(url2, f"park{i}", p,
                                        _FLEET_TURN_NEW)
            parked[f"park{i}"] = (p, [int(t) for t in out["output_ids"]])
        res = fleet.drain_server(url2, reason="bench scale-in")
        assert res.get("success"), res
        _fleet_wait(
            lambda: any(
                e["url"] == url2 and e["status"] == "departed"
                for e in fleet.status()["fleet"]["drains"]
            ),
            300.0, "drain departure",
        )
        drain = [
            e for e in fleet.status()["fleet"]["drains"]
            if e["url"] == url2 and e["status"] == "departed"
        ][-1]
        st = fleet.wait_healthy(3, timeout_s=60)
        # The parked sessions RESUME elsewhere via the migrated tier
        # entries (manager index re-fed by the survivors' /kv/index).
        resumed = 0
        for qid, (p, out1) in parked.items():
            out = fleet.generate_routed(qid, p + out1 + [3],
                                        _FLEET_TURN_NEW, timeout=120)
            if "output_ids" in out:
                resumed += 1

        stats = load.stop()
        load = None
        survivors = [u for u in fleet.urls if u and u != url2]
        lost = accepted = 0.0
        for u in survivors:
            try:
                m = fleet.metrics(u)
                lost += m.get(mreg.KV_PREFIX_LOST_TOTAL, 0.0)
                accepted += m.get(mreg.KV_ACCEPTED, 0.0)
            except Exception:
                pass
        out = {
            "n_servers_start": 2.0,
            "n_servers_max": 4.0,
            "n_servers_end": float(len(st["healthy_servers"])),
            "join_peer_ms": join_peer_ms,
            "join_peer_bootstrap_ms": float(jp.get("bootstrap_ms", 0.0)),
            "join_peer_source": jp.get("source", ""),
            "join_peer_origin_bytes": float(
                jp.get("bytes_from_origin", 0.0)),
            "join_peer_peer_bytes": float(jp.get("bytes_from_peers", 0.0)),
            "join_origin_ms": join_origin_ms,
            "join_origin_source": jo.get("source", ""),
            "join_origin_bytes": float(jo.get("bytes_from_origin", 0.0)),
            "killover_recovery_ms": killover_ms,
            "killover_epoch": float(st["fleet"]["epoch"]),
            "failed_rollouts": float(stats["failed"]),
            "completed_rollouts": float(stats["completed"]),
            "drain_held": float(drain.get("migrated", 0)
                                + drain.get("lost", 0)),
            "drain_migrated": float(drain.get("migrated", 0)),
            "drain_lost": float(drain.get("lost", 0)),
            "drain_resumed_sessions": float(resumed),
            "kv_accepted": accepted,
            "kv_prefix_lost": lost,
            "fleet": "process",
            "wall_s": time.monotonic() - t_start,
            **auto,
        }
        log(f"bench: fleet_elastic {out}")
        return out
    finally:
        if load is not None:
            load.stop(timeout=30)
        if src is not None:
            src.close()
        if fleet is not None:
            fleet.close()
        if prev_fileroot is None:
            os.environ.pop("AREAL_FILEROOT", None)
        else:
            os.environ["AREAL_FILEROOT"] = prev_fileroot
        import shutil

        shutil.rmtree(fileroot, ignore_errors=True)


# ----------------------------------------------------------------------
# multi_model_serving: the multi-model serving plane's claims, banked
# (ISSUE 20 tentpole). Two model FAMILIES (different configs, provably
# different hashes) share one real-process fleet behind one multi-model
# manager: per-model routing must hit only the requested model's pool
# with greedy parity against single-model baseline fleets (zero
# cross-model contamination), an unknown model must be refused rather
# than routed, and model A must cut its weights over while model B's
# sustained traffic holds its p99 TTFT with zero failures and zero
# prefix loss — the independent-lifecycle claim.
# ----------------------------------------------------------------------

# Family B: a genuinely different config (extra layer) so its registry
# hash, its weights, and its greedy outputs all differ from family A —
# contamination is then token-visible, not just a counter.
_MM_MODEL_B = dict(_OPENLOOP_MODEL, n_layers=3)
_MM_STEADY = "actor"    # family A's pool: sustained traffic ("model B" of the A/B)
_MM_CUTOVER = "scout"   # family B's pool: cut over under that load


def _mm_prompts(n: int = 3):
    return [
        np.random.RandomState(4200 + i).randint(
            1, _OPENLOOP_MODEL["vocab_size"], size=_FLEET_PLEN
        ).tolist()
        for i in range(n)
    ]


def _mm_baseline(model_cfg: dict, tag: str, tier_env: dict):
    """Greedy outputs from a SINGLE-model fleet of one family — the
    contamination reference: the multi-model fleet must reproduce these
    token for token per pool."""
    from areal_tpu.bench.fleet import ProcessFleet

    with ProcessFleet(
        model_cfg, [dict(_FLEET_SRV, env=tier_env)], tag=tag
    ) as f:
        outs = []
        for i, p in enumerate(_mm_prompts()):
            r = f.generate_routed(f"bl{i}", p, _FLEET_TURN_NEW,
                                  timeout=600)
            assert "output_ids" in r, r
            outs.append([int(t) for t in r["output_ids"]])
        return outs


def multi_model_serving_phase(pass_: str) -> dict:
    import tempfile
    import threading
    import urllib.error

    import jax

    from areal_tpu.base import constants, name_resolve, names
    from areal_tpu.bench.fleet import ProcessFleet, open_loop_point
    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import init_params
    from areal_tpu.system import model_registry
    from areal_tpu.system.weight_plane import WeightPlaneSource
    from areal_tpu.system.weight_transfer import dump_raw_params

    t_start = time.monotonic()
    tier_env = {"AREAL_KV_TIER_BYTES": str(64 << 20)}
    vocab = _OPENLOOP_MODEL["vocab_size"]

    if pass_ == "compile":
        # Warm BOTH families' serving programs (family B's extra layer
        # is a distinct compile) so the measure pass's six server
        # spawns all hit the persistent cache.
        t0 = time.perf_counter()
        for cfg, tag in ((_OPENLOOP_MODEL, "mmca"), (_MM_MODEL_B, "mmcb")):
            with ProcessFleet(
                cfg, [dict(_FLEET_SRV, env=tier_env)], tag=tag
            ) as f:
                p = _mm_prompts(1)[0]
                out = f.generate_routed("c0", p, _FLEET_TURN_NEW,
                                        timeout=600)
                assert "output_ids" in out, out
        dt = time.perf_counter() - t0
        log(f"bench: multi_model_serving compile pass {dt:.1f}s")
        return {"compile_s": dt}

    cfgs = {_MM_STEADY: _OPENLOOP_MODEL, _MM_CUTOVER: _MM_MODEL_B}
    hash_a = model_registry.config_hash(_OPENLOOP_MODEL)
    hash_b = model_registry.config_hash(_MM_MODEL_B)

    # Same AREAL_FILEROOT discipline as fleet_elastic: children and the
    # weight-plane sources must agree on the param-realloc root.
    prev_fileroot = env_registry.get_raw("AREAL_FILEROOT")
    fileroot = tempfile.mkdtemp(prefix="areal_mms_")
    os.environ["AREAL_FILEROOT"] = fileroot
    srcs = []
    fleet = None
    try:
        # ---- Single-model baseline fleets first: version-0 weights,
        # the parity references.
        base = {
            _MM_STEADY: _mm_baseline(_OPENLOOP_MODEL, "mmba", tier_env),
            _MM_CUTOVER: _mm_baseline(_MM_MODEL_B, "mmbb", tier_env),
        }

        # ---- The multi-model fleet: 2 family-A servers + 1 family-B
        # server, both families registered BEFORE anything spawns.
        fleet = ProcessFleet(
            _OPENLOOP_MODEL,
            [
                dict(_FLEET_SRV, model_id=_MM_STEADY, env=tier_env),
                dict(_FLEET_SRV, model_id=_MM_STEADY, env=tier_env),
                dict(_FLEET_SRV, model_id=_MM_CUTOVER,
                     model_cfg=_MM_MODEL_B, env=tier_env),
            ],
            manager_kw=dict(
                multi_model=True, weight_plane=True,
                weight_chunk_bytes=_FLEET_CHUNK, weight_fanout_degree=2,
                flush_request_timeout=120.0,
            ),
            models=[
                dict(model_id=_MM_STEADY, family="tpu_transformer",
                     config_hash=hash_a),
                dict(model_id=_MM_CUTOVER, family="tpu_transformer",
                     config_hash=hash_b),
            ],
            tag="mms",
        )
        _fleet_wait(
            lambda: {
                m: len(r["healthy"])
                for m, r in fleet.status()["models"].items()
            } == {_MM_STEADY: 2, _MM_CUTOVER: 1},
            120.0, "per-model pool map",
        )
        pools = {
            m: set(r["servers"])
            for m, r in fleet.status()["models"].items()
        }

        # ---- Arm 1: routing + greedy parity per pool vs the
        # single-model baselines (weights still at version 0 = the
        # baselines' init).
        cross_routes = 0
        parity_mismatch = 0
        for model in (_MM_STEADY, _MM_CUTOVER):
            for i, p in enumerate(_mm_prompts()):
                sched = fleet.schedule({
                    "qid": f"par-{model}-{i}", "prompt_len": len(p),
                    "new_token_budget": _FLEET_TURN_NEW, "model": model,
                })
                url = sched.get("url")
                if url not in pools[model]:
                    cross_routes += 1
                    continue
                r = fleet.generate_direct(
                    url, f"par-{model}-{i}", p, _FLEET_TURN_NEW
                )
                got = [int(t) for t in r.get("output_ids", [])]
                if got != base[model][i]:
                    parity_mismatch += 1
        log(f"bench: multi_model_serving parity: "
            f"mismatches={parity_mismatch} cross_routes={cross_routes}")

        # ---- Arm 2: cross-model KV isolation. A session served on the
        # cutover pool, re-requested under the steady model, must route
        # inside the steady pool and NEVER be offered the other pool's
        # server as a KV source — a model_id mismatch is a routing
        # error, not a prefix hit.
        p0 = _mm_prompts(1)[0]
        r = fleet.generate_routed("xm0", p0, _FLEET_TURN_NEW,
                                  model=_MM_CUTOVER, timeout=300)
        assert "output_ids" in r, r
        cross_kv = 0
        sched = fleet.schedule({
            "qid": "xm0", "prompt_len": len(p0),
            "new_token_budget": _FLEET_TURN_NEW, "model": _MM_STEADY,
        })
        if sched.get("url") not in pools[_MM_STEADY]:
            cross_kv += 1
        if sched.get("kv_source") in pools[_MM_CUTOVER]:
            cross_kv += 1

        # ---- Arm 3: an unregistered model must be refused (503
        # no-model-pool), never routed to some pool.
        unknown_rejected = 0
        unknown_routed = 0
        try:
            s = fleet.schedule({
                "qid": "gh0", "prompt_len": 8, "new_token_budget": 2,
                "model": "ghost",
            })
            if s.get("url"):
                unknown_routed += 1
        except urllib.error.HTTPError as e:
            if e.code == 503:
                unknown_rejected += 1

        # ---- Arm 4: independent weight lifecycles. Publish v1 for
        # BOTH families (each through its own per-model plane source),
        # then cut the cutover family to v2 while the steady family
        # carries sustained open-loop traffic.
        for m in (_MM_STEADY, _MM_CUTOVER):
            d = os.path.join(
                constants.get_param_realloc_path(fleet.exp, fleet.trial),
                m,
            )
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "engine_state.pkl"), "wb") as f:
                f.write(b"gate")  # existence gate for check_new_params
            cfg = TransformerConfig(**cfgs[m])
            p1 = jax.tree_util.tree_map(
                lambda x: np.asarray(x),
                init_params(cfg, jax.random.PRNGKey(
                    7 if m == _MM_STEADY else 8)),
            )
            dump_raw_params(p1, d, version=1, chunk_bytes=_FLEET_CHUNK)
            s = WeightPlaneSource(d, chunk_bytes=_FLEET_CHUNK).start()
            s.register(fleet.exp, fleet.trial, m)
            srcs.append(s)
            name_resolve.add(
                names.model_version(fleet.exp, fleet.trial, m), "1",
                replace=True,
            )
        _fleet_wait(
            lambda: all(
                r["version"] == 1
                for r in fleet.status()["models"].values()
            ),
            240.0, "v1 fanout to both pools",
        )

        # Steady family's post-v1 outputs: the fixed point the cutover
        # must not move. Cutover family's post-v1 outputs: the thing v2
        # must visibly change.
        ps = _mm_prompts(1)[0]
        steady_pre = fleet.generate_routed(
            "stp0", ps, _FLEET_TURN_NEW, model=_MM_STEADY, timeout=300
        )["output_ids"]
        cut_pre = fleet.generate_routed(
            "ctp0", ps, _FLEET_TURN_NEW, model=_MM_CUTOVER, timeout=300
        )["output_ids"]

        steady_urls = sorted(pools[_MM_STEADY])

        def prompt_fn(i):
            return np.random.RandomState(5000 + i).randint(
                1, vocab, size=_FLEET_PLEN
            ).tolist()

        pt_base = open_loop_point(
            fleet, 2.0, 6.0, prompt_fn, _FLEET_TURN_NEW, "mmb",
            ttft_urls=steady_urls, itl_urls=steady_urls,
            rng=np.random.RandomState(11), model=_MM_STEADY,
        )

        cut_dir = os.path.join(
            constants.get_param_realloc_path(fleet.exp, fleet.trial),
            _MM_CUTOVER,
        )
        p2 = jax.tree_util.tree_map(
            lambda x: np.asarray(x),
            init_params(TransformerConfig(**_MM_MODEL_B),
                        jax.random.PRNGKey(9)),
        )

        def bump():
            time.sleep(1.5)
            dump_raw_params(p2, cut_dir, version=2,
                            chunk_bytes=_FLEET_CHUNK)
            name_resolve.add(
                names.model_version(
                    fleet.exp, fleet.trial, _MM_CUTOVER
                ),
                "2", replace=True,
            )

        bt = threading.Thread(target=bump, daemon=True)
        bt.start()
        pt_cut = open_loop_point(
            fleet, 2.0, 8.0, prompt_fn, _FLEET_TURN_NEW, "mmc",
            ttft_urls=steady_urls, itl_urls=steady_urls,
            rng=np.random.RandomState(13), model=_MM_STEADY,
        )
        bt.join(timeout=60)
        _fleet_wait(
            lambda: fleet.status()["models"][_MM_CUTOVER]["version"] == 2,
            240.0, "cutover family v2 fanout",
        )
        st = fleet.status()
        steady_v_after = st["models"][_MM_STEADY]["version"]
        cut_v_after = st["models"][_MM_CUTOVER]["version"]

        steady_post = fleet.generate_routed(
            "stp1", ps, _FLEET_TURN_NEW, model=_MM_STEADY, timeout=300
        )["output_ids"]
        cut_post = fleet.generate_routed(
            "ctp1", ps, _FLEET_TURN_NEW, model=_MM_CUTOVER, timeout=300
        )["output_ids"]

        lost = 0.0
        for u in fleet.urls:
            try:
                lost += fleet.metrics(u).get(
                    mreg.KV_PREFIX_LOST_TOTAL, 0.0
                )
            except Exception:
                pass

        out = {
            "n_models": 2.0,
            "steady_pool_servers": float(len(pools[_MM_STEADY])),
            "cutover_pool_servers": float(len(pools[_MM_CUTOVER])),
            "families_distinct": float(hash_a != hash_b),
            "parity_mismatches": float(parity_mismatch),
            "cross_model_routes": float(cross_routes),
            "cross_model_kv_hits": float(cross_kv),
            "unknown_model_rejected": float(unknown_rejected),
            "unknown_model_routed": float(unknown_routed),
            "cutover_version_before": 1.0,
            "cutover_version_after": float(cut_v_after),
            "steady_version_after": float(steady_v_after),
            "steady_outputs_stable": float(
                list(steady_pre) == list(steady_post)
            ),
            "cutover_outputs_changed": float(
                list(cut_pre) != list(cut_post)
            ),
            "b_completed": pt_cut["n_completed"],
            "b_failed": pt_cut["n_failed"],
            "b_p99_ttft_base_ms": pt_base["p99_ttft_ms"],
            "b_p99_ttft_cutover_ms": pt_cut["p99_ttft_ms"],
            "kv_prefix_lost": lost,
            "fleet": "process",
            "wall_s": time.monotonic() - t_start,
        }
        log(f"bench: multi_model_serving {out}")
        return out
    finally:
        for s in srcs:
            try:
                s.close()
            except Exception:
                pass
        if fleet is not None:
            fleet.close()
        if prev_fileroot is None:
            os.environ.pop("AREAL_FILEROOT", None)
        else:
            os.environ["AREAL_FILEROOT"] = prev_fileroot
        import shutil

        shutil.rmtree(fileroot, ignore_errors=True)


# ----------------------------------------------------------------------
# tenant_fairness: the gateway's weighted-fair-share claim, as a banked
# A/B (ISSUE 19). A noisy aggressor tenant floods past its stream cap
# through a REAL gateway subprocess in front of a real-process fleet
# while an interactive victim issues sequential completions; the arm
# with fair share ON must hold the victim's p99 TTFT (admission-to-
# first-token, so queue wait counts) below the FIFO arm, with the
# aggressor shed against its OWN limits and the DRR queue demonstrably
# engaged. The OFF arm documents the collapse it prevents.
# ----------------------------------------------------------------------

# Aggressor: weight 1 with a stream cap ABOVE the gateway's inflight
# cap — admitted flood requests form a standing queue (the thing DRR
# vs FIFO decide about) while the overflow beyond 8 streams is shed.
# Victim: weight 4. Buckets are generous on purpose — sheds must come
# from the stream cap and victim latency from QUEUEING, not token
# exhaustion.
_GWF_TENANTS = ("agg:sk-gwf-agg:1:1000000:2000000:8,"
                "victim:sk-gwf-vic:4:1000000:2000000:8")
_GWF_FLOOD_THREADS = 12
_GWF_VICTIM_REQS = 10
_GWF_MAX_NEW = 6


def _gwf_req(url, path, payload=None, key=None, timeout=120.0,
             op_token=None):
    """(status, parsed-json) against the gateway; 4xx/5xx returned.
    ``op_token`` is the gateway's internal token (operator surfaces +
    trainer proxy are gated on it)."""
    import json as _json
    import urllib.error
    import urllib.request

    h = {"Content-Type": "application/json"}
    if key:
        h["Authorization"] = f"Bearer {key}"
    if op_token:
        h["X-Areal-Gateway-Token"] = op_token
    data = _json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url + path, data, h)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, _json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, _json.loads(body or b"{}")
        except Exception:
            return e.code, {"raw": body.decode(errors="replace")}


def _gwf_spawn(fleet, wal_path: str, fair: bool, not_url=None):
    """Spawn a gateway subprocess in front of `fleet`; returns
    (Popen, url, internal_token) once /health answers — the token
    gates the operator surfaces the arms read. AREAL_GW_MAX_INFLIGHT
    is pinned low so admitted requests contend in the gateway's queue
    — the spot where DRR (or FIFO, fair off) decides who goes next."""
    import subprocess

    from areal_tpu.base import name_resolve, names

    env = dict(fleet._env)
    env["AREAL_GW_FAIR_SHARE"] = "1" if fair else "0"
    env["AREAL_GW_MAX_INFLIGHT"] = "2"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "areal_tpu.system.gateway",
            "--experiment", fleet.exp, "--trial", fleet.trial,
            "--manager-addr", fleet.manager_addr(),
            "--tenants", _GWF_TENANTS,
            "--usage-wal", wal_path,
            "--name-resolve-root", fleet._nr,
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.monotonic() + 60.0
    key = names.gateway_url(fleet.exp, fleet.trial, 0)
    token_key = names.gateway_internal_token(fleet.exp, fleet.trial, 0)
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"tenant_fairness: gateway died rc={proc.returncode}"
            )
        try:
            url = name_resolve.get(key)
            token = name_resolve.get(token_key)
        except Exception:
            url, token = None, None
        if url and token and url != not_url:
            try:
                st, _ = _gwf_req(url, "/health", timeout=5.0)
                if st == 200:
                    return proc, url, token
            except Exception:
                pass
        time.sleep(0.2)
    proc.kill()
    raise RuntimeError("tenant_fairness: gateway never became healthy")


def _gwf_completion(url: str, key: str, seed: int):
    rng = np.random.RandomState(seed)
    return _gwf_req(
        url, "/v1/completions",
        payload={
            "prompt": rng.randint(
                1, _OPENLOOP_MODEL["vocab_size"], size=_FLEET_PLEN
            ).tolist(),
            "max_tokens": _GWF_MAX_NEW,
            "temperature": 0.0,
            "stream": False,
        },
        key=key,
    )


def _gwf_metric(url: str, name: str, op_token: str) -> float:
    """Read one counter off the gateway's text /metrics endpoint
    (internal-token gated)."""
    import urllib.request

    req = urllib.request.Request(
        url + "/metrics",
        headers={"X-Areal-Gateway-Token": op_token},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        text = r.read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def _gwf_victim_arm(url: str, flood: bool, op_token: str = ""):
    """One measurement arm: optionally saturate the gateway with
    aggressor threads for the WHOLE victim window, issue the victim's
    sequential completions, return (victim_failed, usage-json). The
    usage read rides the operator token: it needs EVERY tenant's row
    (victim latency + aggressor sheds), which a tenant key no longer
    sees."""
    import threading as _threading

    stop = _threading.Event()
    threads = []
    if flood:
        def _flood(tid):
            i = 0
            while not stop.is_set():
                try:
                    _gwf_completion(url, "sk-gwf-agg", 9000 + tid * 997 + i)
                except Exception:
                    pass
                i += 1

        threads = [
            _threading.Thread(target=_flood, args=(t,), daemon=True)
            for t in range(_GWF_FLOOD_THREADS)
        ]
        for t in threads:
            t.start()
        time.sleep(1.0)  # let the flood build a standing queue first
    failed = 0
    try:
        for i in range(_GWF_VICTIM_REQS):
            st, body = _gwf_completion(url, "sk-gwf-vic", 100 + i)
            if st != 200:
                failed += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    st, usage = _gwf_req(url, "/v1/usage", op_token=op_token)
    assert st == 200, usage
    return failed, usage


def _gwf_row(usage: dict, tenant: str) -> dict:
    row = usage["tenants"].get(tenant)
    assert row is not None, usage
    return row


def tenant_fairness_phase(pass_: str) -> dict:
    import tempfile

    from areal_tpu.bench.fleet import ProcessFleet

    t_start = time.monotonic()

    if pass_ == "compile":
        # One server + one gateway + one completion: compiles the
        # serving programs AND proves the gateway wiring end-to-end so
        # the measure pass never debugs plumbing inside its window.
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL, [dict(_FLEET_SRV)], tag="gwfc",
        ) as fleet:
            wal = os.path.join(tempfile.mkdtemp(prefix="areal_gwf_"),
                               "usage.jsonl")
            proc, url, _tok = _gwf_spawn(fleet, wal, fair=True)
            try:
                st, body = _gwf_completion(url, "sk-gwf-vic", 1)
                assert st == 200, body
            finally:
                proc.kill()
                proc.wait(timeout=10)
        dt = time.perf_counter() - t0
        log(f"bench: tenant_fairness compile pass {dt:.1f}s")
        return {"compile_s": dt}

    fleet = None
    gw = None
    tmp = tempfile.mkdtemp(prefix="areal_gwf_")
    try:
        fleet = ProcessFleet(
            _OPENLOOP_MODEL, [dict(_FLEET_SRV)] * 2, tag="gwf",
        )

        # ---- Solo baseline: the victim alone, fair share on (it has
        # no one to arbitrate against — this is the latency floor).
        # Warm the serving path on the AGGRESSOR's key first so cold-
        # start cost never lands in the victim's baseline histogram.
        gw, url, tok = _gwf_spawn(fleet, os.path.join(tmp, "solo.jsonl"),
                                  fair=True)
        for i in range(4):
            st, body = _gwf_completion(url, "sk-gwf-agg", 500 + i)
            assert st == 200, body
        failed_solo, usage = _gwf_victim_arm(url, flood=False, op_token=tok)
        solo_p99 = float(_gwf_row(usage, "victim")["ttft_p99_ms"])
        gw.kill()
        gw.wait(timeout=10)

        # ---- Fair ON under flood: victim p99 must stay livable while
        # the aggressor saturates its stream cap and gets shed.
        gw, url2, tok2 = _gwf_spawn(fleet, os.path.join(tmp, "fair.jsonl"),
                                    fair=True, not_url=url)
        failed_fair, usage = _gwf_victim_arm(url2, flood=True, op_token=tok2)
        fair_p99 = float(_gwf_row(usage, "victim")["ttft_p99_ms"])
        agg_sheds = float(_gwf_row(usage, "agg")["sheds"])
        picks = _gwf_metric(url2, "areal:gw_fairshare_picks_total", tok2)
        gw.kill()
        gw.wait(timeout=10)

        # ---- Fair OFF (FIFO) under the same flood: documents the
        # collapse weighted fair share prevents.
        gw, url3, tok3 = _gwf_spawn(fleet, os.path.join(tmp, "unfair.jsonl"),
                                    fair=False, not_url=url2)
        failed_unfair, usage = _gwf_victim_arm(url3, flood=True,
                                               op_token=tok3)
        unfair_p99 = float(_gwf_row(usage, "victim")["ttft_p99_ms"])
        gw.kill()
        gw.wait(timeout=10)
        gw = None

        out = {
            "solo_p99_ttft_ms": solo_p99,
            "fair_p99_ttft_ms": fair_p99,
            "unfair_p99_ttft_ms": unfair_p99,
            "fair_over_solo": fair_p99 / max(solo_p99, 1e-9),
            "unfair_over_fair": unfair_p99 / max(fair_p99, 1e-9),
            "aggressor_sheds": agg_sheds,
            "fairshare_picks": picks,
            "victim_failed": float(
                failed_solo + failed_fair + failed_unfair
            ),
            "fleet": "process",
            "wall_s": time.monotonic() - t_start,
        }
        log(f"bench: tenant_fairness {out}")
        return out
    finally:
        if gw is not None:
            gw.kill()
        if fleet is not None:
            fleet.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def recovery_slo_phase(pass_: str) -> dict:
    """Durable-training-plane SLOs (ISSUE 16 acceptance), host-side
    CPU-proxy evidence in three measurements. (1) Checkpoint-stall A/B:
    mean caller-thread stall of `save_engine_state` with the async
    writer off vs on over the same synthetic state — the async arm pays
    a snapshot handoff, not the pickle+fsync, so its stall must be
    measurably lower. (2) MTTR: the full cold-recovery critical path —
    load the committed manifest, restore engine state, replay the WAL
    and filter it against the checkpointed ledger cut. (3) Exactly-once
    under a redelivery storm: an acked loopback push/pull stream with a
    forced redeliver mid-drain; the ledger must absorb every duplicate
    (samples_duplicated is the DETECTOR, not the prevention counter)
    and nothing may be lost."""
    if pass_ == "compile":
        return {"compile_s": 0.0}  # host-only: nothing to compile
    import shutil
    import tempfile

    from areal_tpu.engine import checkpoint
    from areal_tpu.system import push_pull_stream as pps
    from areal_tpu.system.wal import RolloutWAL, SeqLedger

    rng = np.random.RandomState(5)

    class _Eng:
        """Checkpointable stand-in: ~16 MiB of numpy state, replaced
        (never mutated) like the real engines, so async snapshots by
        reference are crash-consistent."""

        def __init__(self):
            self.params = {
                f"l{i:02d}": rng.standard_normal((512, 256)).astype(
                    np.float32
                )
                for i in range(32)
            }
            self.opt_state = None
            self.version = 0

        def set_params(self, params):
            self.params = params

    n_saves = 8
    state_mb = 32 * 512 * 256 * 4 / 2**20
    tmp = tempfile.mkdtemp(prefix="areal_recovery_bench_")
    saved_env = {
        k: os.environ.get(k)
        for k in ("AREAL_CKPT_ASYNC", "AREAL_CKPT_BACKEND")
    }
    pusher = puller = None
    try:
        os.environ["AREAL_CKPT_BACKEND"] = "pickle"
        eng = _Eng()

        # -- arm A: synchronous saves (the stall IS the full write) ----
        os.environ["AREAL_CKPT_ASYNC"] = "0"
        sync_ms = []
        for v in range(1, n_saves + 1):
            eng.version = v
            t0 = time.perf_counter()
            checkpoint.save_engine_state(eng, os.path.join(tmp, "sync"))
            sync_ms.append((time.perf_counter() - t0) * 1000.0)

        # -- arm B: async saves (the stall is the snapshot handoff) ----
        os.environ["AREAL_CKPT_ASYNC"] = "1"
        async_ms = []
        for v in range(1, n_saves + 1):
            eng.version = v
            t0 = time.perf_counter()
            checkpoint.save_engine_state(eng, os.path.join(tmp, "async"))
            async_ms.append((time.perf_counter() - t0) * 1000.0)
        checkpoint.wait_pending_writes(timeout=120)
        os.environ["AREAL_CKPT_ASYNC"] = "0"

        # -- MTTR: commit a barrier cut, then time cold recovery -------
        n_wal, n_consumed = 256, 128
        ledger = SeqLedger()
        for i in range(n_consumed):
            ledger.mark(f"b0/{i}")
        ckpt_dir = os.path.join(tmp, "mttr")
        checkpoint.save_engine_state(
            eng, ckpt_dir,
            dataset_cursors={"consumed_seqs": ledger.to_dict()},
        )
        wal_path = os.path.join(tmp, "wal", "puller0.wal")
        wal = RolloutWAL(wal_path, fsync_ms=0)
        payload = {"traj": list(range(64))}
        for i in range(n_wal):
            wal.append({"seq": f"b0/{i}", "data": payload})
        wal.close()

        t0 = time.perf_counter()
        man = checkpoint.load_manifest(ckpt_dir)
        eng2 = _Eng()
        checkpoint.load_engine_state(eng2, ckpt_dir)
        cut = SeqLedger.from_dict(
            (man.get("dataset_cursors") or {}).get("consumed_seqs")
        )
        replayed = sum(
            1 for rec in RolloutWAL(wal_path, fsync_ms=0).replay()
            if rec["seq"] not in cut
        )
        mttr_ms = (time.perf_counter() - t0) * 1000.0
        if eng2.version != eng.version or replayed != n_wal - n_consumed:
            raise RuntimeError(
                f"recovery_slo: recovered state wrong (version "
                f"{eng2.version}/{eng.version}, replayed {replayed})"
            )

        # -- exactly-once under a forced redelivery storm --------------
        n_msgs = 64
        puller = pps.ZMQJsonPuller(host="127.0.0.1")
        pusher = pps.ZMQJsonPusher("127.0.0.1", puller.port, ack=True)
        for i in range(n_msgs):
            pusher.push({"i": i}, seq=f"s0/{i}")
        consumed, duplicated, redelivered = SeqLedger(), 0, 0
        trained = 0
        deadline = time.monotonic() + 60
        while trained < n_msgs and time.monotonic() < deadline:
            try:
                puller.pull(timeout_ms=200)
            except TimeoutError:
                redelivered += pusher.redeliver(timeout_s=0.0)
                continue
            seq = puller.last_seq
            if seq in consumed:
                duplicated += 1  # would have trained twice
            else:
                consumed.mark(seq)
                trained += 1
            puller.ack(seq, puller.last_ack_addr)
            pusher.drain_acks()
            if trained == n_msgs // 2:
                # Mid-drain storm: re-send everything still unacked.
                redelivered += pusher.redeliver(timeout_s=0.0)
        ack_deadline = time.monotonic() + 10
        while pusher.unacked() and time.monotonic() < ack_deadline:
            pusher.drain_acks()
            time.sleep(0.01)

        def mean(xs):
            return sum(xs) / len(xs)

        out = {
            "state_mb": state_mb,
            "n_ckpt_saves": float(n_saves),
            "sync_stall_ms_mean": mean(sync_ms),
            "async_stall_ms_mean": mean(async_ms),
            "async_stall_saved_frac": (
                1.0 - mean(async_ms) / mean(sync_ms) if mean(sync_ms) else 0.0
            ),
            "mttr_ms": mttr_ms,
            "wal_records": float(n_wal),
            "wal_replayed": float(replayed),
            "redelivered": float(redelivered),
            "samples_lost": float(n_msgs - trained),
            "samples_duplicated": float(duplicated),
        }
        log(f"bench: recovery_slo {out}")
        return out
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if pusher is not None:
            pusher.close()
        if puller is not None:
            puller.close()
        shutil.rmtree(tmp, ignore_errors=True)

# ----------------------------------------------------------------------
# agentic_rollout: multi-turn tool-use episodes over real server
# processes + the pooled reward executor (system/reward_executor.py).
# Continuation turns ride the session-prefix path (delta re-prefill +
# sticky-qid affinity); the baseline arm resubmits every turn session-
# blind, so the re-prefill ratio is the continuation path's value.
# ----------------------------------------------------------------------

_AGENTIC_SRV = dict(
    max_concurrent_requests=4, max_seq_len=256, kv_page_size=16,
    decode_block_steps=4, prompt_bucket=16, prefill_chunk=16,
    prefix_cache_tokens=2048, warm_on_start=True,
)
_AGENTIC_PLEN = 96
_AGENTIC_TURN_NEW = 6
_AGENTIC_TURNS = 3
_AGENTIC_EPISODES = 4
# Fixed "tool output" token frame appended between turns (vocab 256);
# the bench drives the PRM + executor wire directly — the tokenizer-level
# tool grammar lives in agents/tool_use.py and its own e2e.
_AGENTIC_TOOL_TOKENS = [7, 11, 13, 5]
_AGENTIC_TOOL_JOB = {"kind": "python", "code": "print(sum(range(100)))"}
# Saturation-sweep job: holds a warm worker ~50ms so the bounded
# queue actually fills at the top offered level and 429s happen.
_AGENTIC_SAT_JOB = {
    "kind": "python",
    "code": "import time; time.sleep(0.05); print(1)",
}


def _agentic_prompt(i: int):
    rng = np.random.RandomState(4200 + i)
    return rng.randint(1, _OPENLOOP_MODEL["vocab_size"],
                       size=_AGENTIC_PLEN).tolist()


def _agentic_episodes(fleet, pool_client, n_episodes, n_turns, tag,
                      continuation):
    """Run n_episodes concurrent n_turn episodes through a fresh
    PartialRolloutManager. Continuation arm: one sticky session qid per
    episode, turns 2+ submitted as continuations. Baseline arm: a fresh
    qid per TURN, so every turn pays the session-blind full prefill.
    Returns per-arm accounting incl. the PRM's prefill counters."""
    import asyncio

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.system.partial_rollout import PartialRolloutManager

    tool_ms: list = []
    failed = [0]
    tool_failures = [0]

    async def episode(prm, i):
        prompt = _agentic_prompt(i)
        g = GenerationHyperparameters(
            max_new_tokens=_AGENTIC_TURN_NEW, greedy=True
        )
        ids = list(prompt)
        for turn in range(n_turns):
            qid = (f"{tag}{i}" if continuation
                   else f"{tag}{i}-t{turn}")
            out = await prm._generate_one(
                qid, list(ids), g,
                continuation=continuation and turn > 0,
            )
            if len(out.output_ids) < 1:
                raise RuntimeError(f"empty turn {turn} on {qid}")
            ids += [int(t) for t in out.output_ids]
            if turn < n_turns - 1:
                # One real sandboxed tool call between turns, off the
                # episode's event loop like the production envs.
                t0 = time.perf_counter()
                res = (await asyncio.get_event_loop().run_in_executor(
                    None, pool_client.submit, [dict(_AGENTIC_TOOL_JOB)]
                ))[0]
                tool_ms.append((time.perf_counter() - t0) * 1e3)
                if not res.get("ok"):
                    tool_failures[0] += 1
                ids += _AGENTIC_TOOL_TOKENS

    async def run_all():
        prm = PartialRolloutManager(
            fleet.manager_addr(), request_timeout=120.0,
            max_retries=8, retry_backoff_s=0.1,
        )
        try:
            results = await asyncio.gather(
                *[episode(prm, i) for i in range(n_episodes)],
                return_exceptions=True,
            )
            for r in results:
                if isinstance(r, BaseException):
                    failed[0] += 1
                    log(f"bench: agentic episode failed: {r!r}")
            return (prm.reprefill_tokens_total,
                    prm.full_prefill_tokens_total)
        finally:
            await prm.close()

    base_ttft = fleet.hist_counts(fleet.urls)["ttft"]
    t0 = time.monotonic()
    reprefill, full = asyncio.run(run_all())
    wall = time.monotonic() - t0
    after_ttft = fleet.hist_counts(fleet.urls)["ttft"]
    dt = [max(0, a - b) for a, b in zip(after_ttft, base_ttft)]
    from areal_tpu.base.latency import percentile_from_counts

    return {
        "episodes": n_episodes,
        "failed": failed[0],
        "wall_s": wall,
        "ttft_p50_ms": percentile_from_counts(dt, 50.0),
        "ttft_p99_ms": percentile_from_counts(dt, 99.0),
        "tool_ms": tool_ms,
        "tool_failures": tool_failures[0],
        "reprefill_tokens": float(reprefill),
        "full_prefill_tokens": float(full),
    }


def _agentic_saturation_sweep(url: str, levels=(2, 8, 24)) -> dict:
    """Offered-concurrency sweep straight at ONE executor's bounded
    queue: `level` submitter threads, each posting small batches until
    its share of jobs is done. Sheds (429 + Retry-After) are expected at
    the top level — the client-side retry loop must absorb every one of
    them (backpressure, not starvation)."""
    import concurrent.futures as cf

    from areal_tpu.base import rpc
    from areal_tpu.functioncall.remote import _post_json_sync

    policy = rpc.RetryPolicy(
        attempts=12, backoff_base_s=0.1, backoff_max_s=1.0,
        attempt_timeout_s=30.0,
    )
    points = []
    for level in levels:
        jobs_per_thread = 2
        batch = 2

        def submit_one(_i):
            def attempt(timeout):
                out = _post_json_sync(
                    url + "/rexec/submit",
                    {"jobs": [dict(_AGENTIC_SAT_JOB)] * batch,
                     "timeout_s": 10.0},
                    timeout,
                )
                return out["results"]

            results = rpc.retry_sync(
                attempt, policy=policy, what="rexec saturation",
            )
            return sum(1 for r in results if r.get("ok"))

        t0 = time.perf_counter()
        n_jobs = level * jobs_per_thread * batch
        ok = 0
        fails = 0
        with cf.ThreadPoolExecutor(level) as ex:
            futs = [ex.submit(submit_one, i)
                    for i in range(level * jobs_per_thread)]
            for f in futs:
                try:
                    ok += f.result()
                except Exception as e:
                    fails += batch
                    log(f"bench: saturation submit failed: {e!r}")
        dt = time.perf_counter() - t0
        points.append({
            "offered_threads": float(level),
            "jobs": float(n_jobs),
            "jobs_ok": float(ok),
            "jobs_failed": float(n_jobs - ok),
            "jobs_per_s": n_jobs / max(1e-9, dt),
        })
        log(f"bench: agentic saturation point {points[-1]}")
    return {
        "points": points,
        "peak_jobs_per_s": max(p["jobs_per_s"] for p in points),
        "failed": sum(p["jobs_failed"] for p in points),
    }


def _rexec_metrics(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2:
            out[parts[0]] = float(parts[1])
    return out


def agentic_rollout_phase(pass_: str) -> dict:
    from areal_tpu.base import rpc
    from areal_tpu.bench.fleet import ProcessFleet
    from areal_tpu.functioncall.remote import ExecutorPoolClient
    from areal_tpu.system.reward_executor import RewardExecutorService

    t_start = time.monotonic()

    if pass_ == "compile":
        # One server, one 2-turn continuation episode + one sandboxed
        # job: compiles the chunked prefill and decode-block programs
        # into the persistent cache; the executor pool has nothing to
        # compile (warm subprocess workers).
        t0 = time.perf_counter()
        with ProcessFleet(
            _OPENLOOP_MODEL, [dict(_AGENTIC_SRV)], tag="agrc",
        ) as fleet:
            svc = RewardExecutorService(
                fleet.exp, fleet.trial, executor_id=0, n_workers=1,
            )
            svc.start()
            try:
                client = ExecutorPoolClient(fleet.exp, fleet.trial)
                arm = _agentic_episodes(
                    fleet, client, 1, 2, "c", continuation=True
                )
                assert arm["failed"] == 0, arm
            finally:
                svc.stop()
        dt = time.perf_counter() - t0
        log(f"bench: agentic_rollout compile pass {dt:.1f}s")
        return {"compile_s": dt}

    svc = None
    sat_svc = None
    with ProcessFleet(
        _OPENLOOP_MODEL, [dict(_AGENTIC_SRV)] * 2, tag="agrm",
    ) as fleet:
        try:
            svc = RewardExecutorService(
                fleet.exp, fleet.trial, executor_id=0, n_workers=2,
            )
            svc.start()
            client = ExecutorPoolClient(
                fleet.exp, fleet.trial,
                policy=rpc.RetryPolicy(
                    attempts=8, backoff_base_s=0.1, backoff_max_s=1.0,
                    attempt_timeout_s=60.0,
                ),
            )

            # --- Arm A: session-blind baseline — fresh qid per turn,
            # every turn re-prefills its whole conversation.
            base = _agentic_episodes(
                fleet, client, _AGENTIC_EPISODES, _AGENTIC_TURNS, "b",
                continuation=False,
            )

            # --- Arm B: continuation — sticky session qid, turns 2+
            # re-prefill only the turn delta past the parked prefix.
            hits0 = sum(
                fleet.metrics(u).get(mreg.PREFIX_CACHE_HITS, 0.0)
                for u in fleet.urls
            )
            cont = _agentic_episodes(
                fleet, client, _AGENTIC_EPISODES, _AGENTIC_TURNS, "s",
                continuation=True,
            )
            affinity_hits = sum(
                fleet.metrics(u).get(mreg.PREFIX_CACHE_HITS, 0.0)
                for u in fleet.urls
            ) - hits0
            em = _rexec_metrics(svc.address)

            # --- Executor saturation sweep against a dedicated
            # small-queue service (the episode service keeps its big
            # queue; backpressure evidence needs a tight watermark).
            svc.stop()
            svc = None
            sat_svc = RewardExecutorService(
                fleet.exp, fleet.trial, executor_id=1, n_workers=2,
                queue_max=6,
            )
            sat_svc.start()
            sat = _agentic_saturation_sweep(sat_svc.address)
            sat_m = _rexec_metrics(sat_svc.address)
        finally:
            for s in (svc, sat_svc):
                if s is not None:
                    s.stop()

    n_turns_total = _AGENTIC_EPISODES * _AGENTIC_TURNS
    tool_all = base["tool_ms"] + cont["tool_ms"]
    tool_sorted = sorted(tool_all) or [0.0]
    full = max(1.0, cont["full_prefill_tokens"])
    out = {
        "episodes": float(_AGENTIC_EPISODES * 2),
        "turns_per_episode": float(_AGENTIC_TURNS),
        "failed_episodes": float(base["failed"] + cont["failed"]),
        "episodes_per_s": _AGENTIC_EPISODES / max(1e-9, cont["wall_s"]),
        "turn_ttft_p50_ms": cont["ttft_p50_ms"],
        "turn_ttft_p99_ms": cont["ttft_p99_ms"],
        "baseline_turn_ttft_p50_ms": base["ttft_p50_ms"],
        "baseline_turn_ttft_p99_ms": base["ttft_p99_ms"],
        "tool_calls": float(len(tool_all)),
        "tool_failures": float(
            base["tool_failures"] + cont["tool_failures"]
        ),
        "tool_call_ms_p50": tool_sorted[len(tool_sorted) // 2],
        "tool_call_ms_p99": tool_sorted[-1],
        "reprefill_tokens": cont["reprefill_tokens"],
        "full_prefill_tokens": cont["full_prefill_tokens"],
        "reprefill_ratio": cont["reprefill_tokens"] / full,
        "affinity_prefix_hits": float(affinity_hits),
        "exec_jobs_total": em.get(mreg.REXEC_JOBS_TOTAL, 0.0),
        "exec_warm_hits": em.get(mreg.REXEC_WARM_HITS, 0.0),
        "exec_worker_respawns": em.get(mreg.REXEC_WORKER_RESPAWNS, 0.0),
        "exec_workers_alive": em.get(mreg.REXEC_WORKERS_ALIVE, 0.0),
        "sat_points": sat["points"],
        "sat_peak_jobs_per_s": sat["peak_jobs_per_s"],
        "sat_failed": sat["failed"],
        "sat_shed_total": sat_m.get(mreg.REXEC_SHED_TOTAL, 0.0),
        "n_turns_total": float(n_turns_total * 2),
        "fleet": "process",
        "wall_s": time.monotonic() - t_start,
    }
    log(
        f"bench: agentic_rollout: {out['episodes']:.0f} episodes "
        f"({out['failed_episodes']:.0f} failed), re-prefill ratio "
        f"{out['reprefill_ratio']:.3f} vs session-blind 1.0, turn TTFT "
        f"p50 {out['turn_ttft_p50_ms']:.0f}ms vs baseline "
        f"{out['baseline_turn_ttft_p50_ms']:.0f}ms, tool p50 "
        f"{out['tool_call_ms_p50']:.0f}ms, sheds "
        f"{out['sat_shed_total']:.0f}"
    )
    return out
