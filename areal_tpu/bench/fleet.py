"""Real-process serving-fleet harness for bench phases.

The ROADMAP item-2 gap: `serving_openloop` measured in-process engines,
so scheduler results never crossed a process or HTTP boundary. This
module spawns REAL `GenerationServer` worker processes (CPU jax in the
bench's proxy mode, TPU when a window is live) behind a REAL in-thread
`GserverManager`, and drives open-loop load through the manager's
routing — the same path production rollout workers take. Both
`serving_openloop` and `serving_disagg` build on it.

Latency is read server-side: each point diffs the fleet's /metrics
TTFT/ITL histogram counters (base/latency.py sparse encoding) before
and after, then merges per-server buckets — the ratio-of-sums rule, no
client-side clock skew.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import uuid
from typing import Callable, Dict, List, Optional

import numpy as np

from areal_tpu.base import metrics_registry as mreg
from areal_tpu.bench._util import log, repo_root

_CHILD = '''
import os, sys
sys.path.insert(0, %(repo)r)
from areal_tpu.base import name_resolve
name_resolve.reconfigure("nfs", record_root=%(nr)r)
from areal_tpu.api.system_api import GenerationServerConfig
from areal_tpu.api.config import ModelAbstraction
from areal_tpu.system.generation_server import GenerationServer
import areal_tpu.engine.factories  # registry
cfg = GenerationServerConfig(
    experiment_name=%(exp)r, trial_name=%(trial)r, server_index=%(idx)d,
    model=ModelAbstraction("tpu_transformer", args=dict(config=%(model_cfg)r)),
    seed=0, **%(srv)r)
w = GenerationServer()
w.configure(cfg, experiment_name=cfg.experiment_name,
            trial_name=cfg.trial_name, worker_name=cfg.worker_name)
w.run()
'''

_MGR_CHILD = '''
import os, sys
sys.path.insert(0, %(repo)r)
from areal_tpu.base import name_resolve
name_resolve.reconfigure("nfs", record_root=%(nr)r)
from areal_tpu.api.system_api import GserverManagerConfig
from areal_tpu.system.gserver_manager import GserverManager
cfg = GserverManagerConfig(
    experiment_name=%(exp)r, trial_name=%(trial)r, model_name="actor",
    n_servers=%(n)d, train_batch_size=4, max_head_offpolicyness=1 << 20,
    health_check_interval=0.5, **%(mgr)r)
m = GserverManager()
m.configure(cfg, experiment_name=cfg.experiment_name,
            trial_name=cfg.trial_name, worker_name=cfg.worker_name)
m.run()
'''


def _post(url: str, path: str, payload: Dict, timeout: float = 300.0) -> Dict:
    req = urllib.request.Request(
        url + path, json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class ProcessFleet:
    """N real GenerationServer subprocesses + a real GserverManager
    (in a thread). Context manager; `close()` tears everything down and
    restores name_resolve."""

    def __init__(
        self,
        model_cfg: Dict,
        servers: List[Dict],
        manager_kw: Optional[Dict] = None,
        tmp_dir: Optional[str] = None,
        tag: str = "fleet",
        spawn_timeout_s: float = 600.0,
        manager_subprocess: bool = False,
        manager_env: Optional[Dict] = None,
        models: Optional[List[Dict]] = None,
    ):
        import tempfile

        from areal_tpu.base import name_resolve, names

        self._names = names
        self._name_resolve = name_resolve
        self.tmp = tmp_dir or tempfile.mkdtemp(prefix=f"areal_{tag}_")
        self.exp = f"bench-{tag}-{uuid.uuid4().hex[:6]}"
        self.trial = "t0"
        self._model_cfg = dict(model_cfg)
        self._nr = os.path.join(self.tmp, "nr")
        self._repo_handle = name_resolve.reconfigure(
            "nfs", record_root=self._nr
        )
        # Multi-model fleets: register every served family in the
        # discovery-plane registry BEFORE anything spawns — the manager
        # builds its pool map from list_models at configure time, and a
        # heartbeat naming an unregistered model_id is quarantined, not
        # adopted. Each entry is ModelRecord kwargs.
        if models:
            from areal_tpu.system import model_registry

            for rec in models:
                model_registry.register_model(
                    self.exp, self.trial,
                    model_registry.ModelRecord(**rec),
                )
        repo = repo_root()
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("AREAL_HEALTH_TTL", "60")
        self._env = env
        self._repo = repo
        self.procs: List[subprocess.Popen] = []
        self.logs: List[str] = []
        self._log_files = []
        self.urls: List[Optional[str]] = []
        for idx, srv in enumerate(servers):
            self._spawn_server_child(idx, dict(srv))
        self._await_discovery(
            range(len(servers)), spawn_timeout_s=spawn_timeout_s
        )
        # Manager: in-thread (legacy, cheap) or a REAL subprocess —
        # required by the fleet_elastic killover arm (you cannot
        # SIGKILL a thread) and by manager-HA e2es.
        self.manager = None
        self._mthread = None
        self.mgr_procs: List[subprocess.Popen] = []
        self._manager_kw = dict(manager_kw or {})
        self._manager_env = dict(manager_env or {})
        self._n_servers0 = len(servers)
        if manager_subprocess:
            self.spawn_manager()
        else:
            from areal_tpu.api.system_api import GserverManagerConfig
            from areal_tpu.system.gserver_manager import GserverManager

            self.manager = GserverManager()
            self.manager.configure(GserverManagerConfig(
                experiment_name=self.exp, trial_name=self.trial,
                model_name="actor", n_servers=len(servers),
                train_batch_size=4, max_head_offpolicyness=1 << 20,
                health_check_interval=0.5,
                **self._manager_kw,
            ))
            self._mthread = threading.Thread(
                target=self.manager.run, daemon=True
            )
            self._mthread.start()
        self.wait_healthy(len(servers))

    # ------------------------------------------------------------------
    # Elastic-fleet harness surface (ISSUE 12)
    # ------------------------------------------------------------------

    def _spawn_server_child(self, idx: int, srv: Dict) -> subprocess.Popen:
        child_env = dict(self._env)
        for k, v in (srv.pop("env", None) or {}).items():
            child_env[k] = v
        # A multi-model fleet serves genuinely different weights per
        # pool: a server dict may override the fleet-level model config
        # (and carries its model_id through the remaining srv kwargs).
        model_cfg = srv.pop("model_cfg", None) or self._model_cfg
        log_path = os.path.join(self.tmp, f"server{idx}.log")
        self.logs.append(log_path)
        log_f = open(log_path, "w")
        self._log_files.append(log_f)
        p = subprocess.Popen(
            [sys.executable, "-c", _CHILD % dict(
                repo=self._repo, nr=self._nr, exp=self.exp,
                trial=self.trial, idx=idx, model_cfg=dict(model_cfg),
                srv=srv,
            )],
            env=child_env, cwd=self._repo, stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        self.procs.append(p)
        while len(self.urls) <= idx:
            self.urls.append(None)
        return p

    def _await_discovery(self, indices, spawn_timeout_s: float = 600.0):
        deadline = time.monotonic() + spawn_timeout_s
        pending = [i for i in indices if self.urls[i] is None]
        while pending:
            for i in list(pending):
                if self.procs[i].poll() is not None:
                    with open(self.logs[i]) as f:
                        tail = f.read()[-3000:]
                    raise RuntimeError(f"fleet server {i} died:\n{tail}")
                try:
                    self.urls[i] = self._name_resolve.get(
                        self._names.gen_server_url(
                            self.exp, self.trial, str(i)
                        )
                    )
                    pending.remove(i)
                except Exception:
                    pass
            if time.monotonic() > deadline:
                raise TimeoutError("fleet servers never registered")
            time.sleep(0.2)

    def spawn_server(self, srv: Optional[Dict] = None,
                     spawn_timeout_s: float = 600.0) -> str:
        """Runtime JOIN: spawn one more GenerationServer child (next
        index) and wait for its discovery registration; the manager
        adopts it from its first heartbeat. Returns its url."""
        idx = len(self.procs)
        self._spawn_server_child(idx, dict(srv or {}))
        self._await_discovery([idx], spawn_timeout_s=spawn_timeout_s)
        return self.urls[idx]

    def spawn_manager(self, env: Optional[Dict] = None) -> subprocess.Popen:
        """Spawn a gserver-manager subprocess (successors take over the
        HA lease from a dead predecessor). ``env`` overrides the
        construction-time manager_env — a successor must not re-inherit
        a predecessor's chaos arm."""
        if env is not None:
            self._manager_env = dict(env)
        i = len(self.mgr_procs)
        log_path = os.path.join(self.tmp, f"manager{i}.log")
        log_f = open(log_path, "w")
        self._log_files.append(log_f)
        p = subprocess.Popen(
            [sys.executable, "-c", _MGR_CHILD % dict(
                repo=self._repo, nr=self._nr, exp=self.exp,
                trial=self.trial, n=self._n_servers0,
                mgr=self._manager_kw,
            )],
            env={**self._env, **self._manager_env},
            cwd=self._repo, stdout=log_f, stderr=subprocess.STDOUT,
        )
        self.mgr_procs.append(p)
        return p

    def manager_addr(self) -> str:
        """The CURRENT manager address: in-thread manager's directly, a
        subprocess manager's via its name_resolve registration (which a
        successor overwrites on takeover)."""
        if self.manager is not None:
            return self.manager.address
        return self._name_resolve.get(
            self._names.gen_server_manager(self.exp, self.trial)
        )

    def status(self) -> Dict:
        with urllib.request.urlopen(
            self.manager_addr() + "/status", timeout=30
        ) as r:
            return json.loads(r.read())

    def wait_healthy(self, n: int, timeout_s: float = 120.0,
                     epoch: Optional[int] = None):
        """Block until /status shows n healthy servers (and, when
        given, the manager epoch — takeover convergence)."""
        deadline = time.monotonic() + timeout_s
        last = None
        while time.monotonic() < deadline:
            try:
                st = self.status()
                last = (len(st["healthy_servers"]),
                        st.get("fleet", {}).get("epoch"))
                if len(st["healthy_servers"]) == n and (
                    epoch is None or last[1] == epoch
                ):
                    return st
            except Exception:
                pass
            time.sleep(0.2)
        raise TimeoutError(
            f"manager never reached {n} healthy servers"
            + (f" at epoch {epoch}" if epoch is not None else "")
            + f" (last seen: {last})"
        )

    def drain_server(self, url: str, reason: str = "harness") -> Dict:
        return _post(self.manager_addr(), "/drain_server",
                     {"url": url, "reason": reason}, timeout=30)

    # ------------------------------------------------------------------

    def wait_roles(self, roles: List[str], timeout_s: float = 60.0):
        """Block until the manager's /metrics poll learned every
        server's role (pool routing engages only then)."""
        want = {self.urls[i]: r for i, r in enumerate(roles)}
        deadline = time.monotonic() + timeout_s
        got = None
        while time.monotonic() < deadline:
            try:
                st_roles = self.status()["pools"]["roles"]
                got = {u: st_roles.get(u) for u in want}
                if got == want:
                    return
            except Exception:
                pass
            time.sleep(0.2)
        raise TimeoutError(f"manager never learned roles {want} ({got})")

    def metrics(self, url: str) -> Dict:
        from areal_tpu.system.fleet_controller import parse_metrics

        text = urllib.request.urlopen(
            url + "/metrics", timeout=30).read().decode()
        return parse_metrics(text)

    def hist_counts(self, urls: List[str]) -> Dict[str, List[int]]:
        """Fleet-merged raw TTFT/ITL bucket counts over `urls`."""
        from areal_tpu.base.latency import decode_counts, merge_counts

        ttft, itl = [], []
        for u in urls:
            m = self.metrics(u)
            ttft.append(decode_counts(str(m.get(mreg.TTFT_HIST) or "")))
            itl.append(decode_counts(str(m.get(mreg.ITL_HIST) or "")))
        return {"ttft": merge_counts(ttft), "itl": merge_counts(itl)}

    def configure_servers(self, payload: Dict, urls: Optional[List[str]] = None):
        for u in urls or self.urls:
            _post(u, "/configure", payload, timeout=30)

    def schedule(self, meta: Dict) -> Dict:
        return _post(self.manager_addr(), "/schedule_request", meta,
                     timeout=30)

    def generate_direct(self, url: str, qid: str, input_ids: List[int],
                        max_new: int, timeout: float = 600.0) -> Dict:
        """One greedy request straight at a server (no manager routing)
        — the single place the bench builds a raw /generate body."""
        return _post(url, "/generate", {
            "qid": qid, "input_ids": list(input_ids),
            "gconfig": {"max_new_tokens": int(max_new), "greedy": True},
        }, timeout=timeout)

    def generate_routed(self, qid: str, input_ids: List[int],
                        max_new: int, timeout: float = 300.0,
                        model: Optional[str] = None) -> Dict:
        """One request through the manager's routing (pairing included),
        like a rollout worker. ``model`` pins the request to that
        model's pool on a multi-model fleet (the manager refuses to
        route it anywhere else). Returns the /generate body; a dict
        with 'shed'/'error' on 429/failure."""
        meta = {
            "qid": qid, "prompt_len": len(input_ids),
            "new_token_budget": max_new,
        }
        if model:
            meta["model"] = model
        try:
            sched = self.schedule(meta)
        except urllib.error.HTTPError as e:
            return {"error": f"schedule {e.code}: {e.read()[:200]}"}
        if "url" not in sched:
            return {"error": f"unroutable: {sched}"}
        payload = {
            "qid": qid, "input_ids": input_ids,
            "gconfig": {"max_new_tokens": max_new, "greedy": True},
        }
        if sched.get("decode_url"):
            payload["decode_url"] = sched["decode_url"]
        if sched.get("kv_source"):
            payload["kv_source"] = sched["kv_source"]
        try:
            return _post(sched["url"], "/generate", payload, timeout=timeout)
        except urllib.error.HTTPError as e:
            if e.code == 429:
                return {"shed": True}
            return {"error": f"{e.code}: {e.read()[:200]}"}
        except Exception as e:  # noqa: BLE001 — counted, not raised
            return {"error": repr(e)}

    def close(self):
        try:
            self._name_resolve.add(
                self._names.experiment_status(self.exp, self.trial),
                "COMPLETE", replace=True,
            )
        except Exception:
            pass
        for p in self.procs + self.mgr_procs:
            try:
                p.terminate()
            except Exception:
                pass
        for p in self.procs + self.mgr_procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
            except Exception:
                pass
        try:
            if self._mthread is not None:
                self._mthread.join(timeout=10)
        except Exception:
            pass
        for f in self._log_files:
            try:
                f.close()
            except Exception:
                pass
        try:
            self._repo_handle.reset()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def open_loop_point(
    fleet: ProcessFleet,
    rate: float,
    duration_s: float,
    prompt_fn: Callable[[int], List[int]],
    max_new: int,
    tag: str,
    ttft_urls: Optional[List[str]] = None,
    itl_urls: Optional[List[str]] = None,
    rng: Optional[np.random.RandomState] = None,
    drain_timeout_s: float = 120.0,
    model: Optional[str] = None,
) -> Dict:
    """One Poisson-arrival sweep point against the real fleet, routed
    through the manager (``model`` pins every request to one model's
    pool on a multi-model fleet). Fixed arrival COUNT
    (ceil(rate * duration)) so the overload A/B is deterministic;
    p50/p99 come from the per-server histogram DIFF over the point
    (the /metrics counters never reset)."""
    from areal_tpu.base.latency import merge_counts, percentile_from_counts

    rng = rng or np.random.RandomState(0)
    ttft_urls = ttft_urls or list(fleet.urls)
    itl_urls = itl_urls or list(fleet.urls)
    base_t = fleet.hist_counts(ttft_urls)["ttft"]
    base_i = fleet.hist_counts(itl_urls)["itl"]
    n_target = max(2, int(-(-rate * duration_s // 1)))
    results = {"completed": 0, "shed": 0, "failed": 0}
    rlock = threading.Lock()
    threads: List[threading.Thread] = []

    def fire(i: int):
        out = fleet.generate_routed(
            f"{tag}{i}", prompt_fn(i), max_new,
            timeout=max(60.0, drain_timeout_s), model=model,
        )
        with rlock:
            if out.get("shed"):
                results["shed"] += 1
            elif "error" in out:
                results["failed"] += 1
            else:
                results["completed"] += 1

    t0 = time.monotonic()
    t_next = t0
    for i in range(n_target):
        now = time.monotonic()
        if now < t_next:
            time.sleep(t_next - now)
        th = threading.Thread(target=fire, args=(i,), daemon=True)
        th.start()
        threads.append(th)
        t_next += rng.exponential(1.0 / rate)
    arrival_window = time.monotonic() - t0
    deadline = time.monotonic() + drain_timeout_s
    for th in threads:
        th.join(timeout=max(0.1, deadline - time.monotonic()))
    elapsed = time.monotonic() - t0
    after_t = fleet.hist_counts(ttft_urls)["ttft"]
    after_i = fleet.hist_counts(itl_urls)["itl"]
    dt = [max(0, a - b) for a, b in zip(after_t, base_t)]
    di = [max(0, a - b) for a, b in zip(after_i, base_i)]
    pt = {
        "nominal_rate_rps": float(rate),
        "offered_rps": n_target / arrival_window,
        "duration_s": arrival_window,
        "n_arrivals": float(n_target),
        "n_admitted": float(n_target - results["shed"]),
        "n_shed": float(results["shed"]),
        "n_failed": float(results["failed"]),
        "n_completed": float(results["completed"]),
        "goodput_rps": results["completed"] / elapsed,
        "p50_ttft_ms": percentile_from_counts(dt, 50.0),
        "p99_ttft_ms": percentile_from_counts(dt, 99.0),
        "itl_p50_ms": percentile_from_counts(di, 50.0),
        "itl_p99_ms": percentile_from_counts(di, 99.0),
    }
    log(f"bench: {tag} point: {pt}")
    return pt


def interference_point(
    fleet: ProcessFleet,
    n_streams: int,
    stream_plen: int,
    stream_max_new: int,
    n_long: int,
    long_plen: int,
    long_gap_s: float,
    long_max_new: int,
    tag: str,
    ttft_urls: Optional[List[str]] = None,
    itl_urls: Optional[List[str]] = None,
    rng: Optional[np.random.RandomState] = None,
    timeout_s: float = 300.0,
) -> Dict:
    """Deterministic prefill/decode interference probe: `n_streams`
    long-decode sessions run for the whole window while `n_long` long
    prompts arrive at fixed gaps — every long admission is GUARANTEED
    to land while decode streams are running (a Poisson point at this
    scale only collides by luck, which made the A/B noisy). The ITL
    histogram diff over `itl_urls` is then a direct read of how much
    decode latency the long prefills steal."""
    from areal_tpu.base.latency import percentile_from_counts

    rng = rng or np.random.RandomState(0)
    ttft_urls = ttft_urls or list(fleet.urls)
    itl_urls = itl_urls or list(fleet.urls)
    vocab = 200
    results = {"completed": 0, "failed": 0}
    rlock = threading.Lock()

    def fire(qid, ids, max_new):
        out = fleet.generate_routed(qid, ids, max_new, timeout=timeout_s)
        with rlock:
            if "output_ids" in out:
                results["completed"] += 1
            else:
                results["failed"] += 1

    # Start the decode streams and wait until every one has sampled its
    # first token ON the decode pool. The predicate is the MONOTONIC
    # TTFT sample count, not an instantaneous num_running read: under
    # heavy CPU contention a polling loop can miss the running peak
    # entirely and burn its whole deadline while the streams complete —
    # leaving the baseline snapshot AFTER the window it was meant to
    # open (measured as a 21-sample, 62 s degenerate point).
    base_ttft_n = sum(fleet.hist_counts(itl_urls)["ttft"])
    threads = [
        threading.Thread(
            target=fire,
            args=(f"{tag}st{i}",
                  rng.randint(1, vocab, size=stream_plen).tolist(),
                  stream_max_new),
            daemon=True,
        )
        for i in range(n_streams)
    ]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(fleet.hist_counts(itl_urls)["ttft"]) >= base_ttft_n + n_streams:
            break
        time.sleep(0.1)
    # Hist baseline AFTER the streams admitted: the diff then holds the
    # streams' steady decode cadence + whatever the long prompts steal.
    base_t = fleet.hist_counts(ttft_urls)["ttft"]
    base_i = fleet.hist_counts(itl_urls)["itl"]
    for i in range(n_long):
        time.sleep(long_gap_s)
        th = threading.Thread(
            target=fire,
            args=(f"{tag}lg{i}",
                  rng.randint(1, vocab, size=long_plen).tolist(),
                  long_max_new),
            daemon=True,
        )
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=max(0.1, timeout_s - (time.monotonic() - t0)))
    elapsed = time.monotonic() - t0
    after_t = fleet.hist_counts(ttft_urls)["ttft"]
    after_i = fleet.hist_counts(itl_urls)["itl"]
    dt = [max(0, a - b) for a, b in zip(after_t, base_t)]
    di = [max(0, a - b) for a, b in zip(after_i, base_i)]
    pt = {
        "n_streams": float(n_streams),
        "n_long": float(n_long),
        "offered_rps": (n_streams + n_long) / elapsed,
        "duration_s": elapsed,
        "n_failed": float(results["failed"]),
        "n_completed": float(results["completed"]),
        "goodput_rps": results["completed"] / elapsed,
        "p50_ttft_ms": percentile_from_counts(dt, 50.0),
        "p99_ttft_ms": percentile_from_counts(dt, 99.0),
        "itl_p50_ms": percentile_from_counts(di, 50.0),
        "itl_p99_ms": percentile_from_counts(di, 99.0),
        "itl_samples": float(sum(di)),
    }
    log(f"bench: {tag} interference point: {pt}")
    return pt


def warm_admit_shapes(
    fleet: ProcessFleet, plen: int, max_new: int, vocab: int,
    rng: np.random.RandomState, max_batch: int = 8, rounds: int = 2,
):
    """Compile every pow2 admit-batch shape on every server BEFORE
    measuring: the engine pads batched prefill to pow2 row counts, so a
    burst size never seen warm compiles INSIDE a sweep point and
    masquerades as multi-second queueing delay (measured: an unwarmed
    pad-4 batch put p99 TTFT at 4096 ms in whichever A/B arm ran
    first). Bursts go DIRECT to each server; a burst may split across
    admission laps, so run a couple of rounds."""
    for _ in range(rounds):
        for u in fleet.urls:
            for k in (1, 2, 3, 4, 6, max_batch):
                threads = []

                def fire(i):
                    try:
                        fleet.generate_direct(
                            u, f"warm{k}-{i}",
                            rng.randint(1, vocab, size=plen).tolist(),
                            max_new,
                        )
                    except Exception:
                        pass

                for i in range(k):
                    th = threading.Thread(target=fire, args=(i,),
                                          daemon=True)
                    th.start()
                    threads.append(th)
                for th in threads:
                    th.join(timeout=600)


def closed_loop_capacity(
    fleet: ProcessFleet, n: int, plen: int, max_new: int, tag: str,
    vocab: int, rng: np.random.RandomState,
) -> float:
    """Closed-loop peak: n concurrent requests direct to the servers
    (round-robin), completions per second."""
    threads = []
    done = []

    def fire(i):
        url = fleet.urls[i % len(fleet.urls)]
        try:
            out = fleet.generate_direct(
                url, f"{tag}{i}",
                rng.randint(1, vocab, size=plen).tolist(), max_new,
            )
            if "output_ids" in out:
                done.append(1)
        except Exception:
            pass

    t0 = time.monotonic()
    for i in range(n):
        th = threading.Thread(target=fire, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=600)
    dt = time.monotonic() - t0
    if not done:
        raise RuntimeError(f"capacity probe: no completions ({tag})")
    return len(done) / dt
