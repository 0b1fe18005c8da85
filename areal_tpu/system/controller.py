"""Experiment controller: spawn workers, run the master, reap results.

Counterpart of the reference's controller (realhf/system/controller.py:
98-689) in its "local" form: every worker is a separate OS process
(multiprocessing spawn so each gets a clean JAX runtime), the master runs
inline in the controller process, and worker health is watched while the
master drives the experiment. This is also the in-process e2e test
harness (reference tests/experiments/utils.py:22-52).
"""

from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Set

from areal_tpu.api.system_api import ExperimentConfig
from areal_tpu.base import constants, health, logging, name_resolve, names

logger = logging.getLogger("controller")

# Worker roles the supervisor restarts in place on death/hang. The
# trainer plane (model workers, master) holds in-flight step state the
# request/reply stream can't rebuild mid-step, so those still escalate
# to the whole-experiment relaunch in training/utils.run_experiment;
# the serving plane is designed to re-register, re-sync weights, and
# re-enter rotation.
RESTARTABLE_ROLES = frozenset(
    {"generation_server", "rollout_worker", "gserver_manager"}
)

# TPU_CHIPS_PER_PROCESS_BOUNDS for the chip-set shapes libtpu 0.0.34
# accepted on a v5e 2x2 host (PR 21 chip run): one chip, or an aligned
# pair (0,1) / (2,3). TPU_VISIBLE_CHIPS alone is refused: the second
# process aborts on libtpu's multi-process lockfile.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
_MESH_CONTROLLER_PORT = 8476


def host_tpu_chips(env: Dict[str, str]) -> int:
    """TPU chips attached to this host, counted the way jax counts them
    (PCI scan; touches no backend). 0 when the launch is held to the CPU
    platform: virtual devices have no owner to assign."""
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return 0
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def chip_env(chips: List[int], n_host_chips: int) -> Dict[str, str]:
    """The libtpu environment under which a process sees only `chips`
    of this host (as local devices 0..n-1). Must be in the environment
    before the interpreter starts: libtpu reads it when jax loads."""
    chips = list(chips)
    if not chips or chips[-1] >= n_host_chips:
        raise ValueError(
            f"chip set {chips} does not fit a host with {n_host_chips} chips"
        )
    if chips == list(range(n_host_chips)):
        return {}  # the whole host: nothing to hide
    n = len(chips)
    if (
        n not in _CHIP_BOUNDS
        or chips != list(range(chips[0], chips[0] + n))
        or chips[0] % n
    ):
        raise ValueError(
            f"cannot give one process chips {chips}: supported sets are "
            f"one chip, an aligned pair (0,1)/(2,3), or the whole host"
        )
    port = _MESH_CONTROLLER_PORT + chips[0]
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[n],
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port),
    }


def plan_worker_envs(
    exp_cfg: ExperimentConfig, worker_env: Dict[str, str], n_host_chips: int
) -> Dict[str, Dict[str, str]]:
    """Environment of every worker process, by worker name.

    A chip belongs to one process. Model workers and generation servers
    compute on an accelerator: each gets the shared `worker_env` plus
    the libtpu variables for its own chips (its config's `chips`, cut
    from allocation_mode by experiments/common.worker_chips). Every
    other role is pinned to the CPU platform, so that a stray jnp call
    in a rollout worker cannot take a chip from its owner. With
    `n_host_chips` > 0 (a TPU host) the assignment is checked —
    disjoint, inside the host, and no two processes left to fight over
    unassigned chips; with 0 (held to the CPU) there is nothing to own."""
    chip_cfgs = list(exp_cfg.model_workers) + list(exp_cfg.generation_servers)
    envs: Dict[str, Dict[str, str]] = {}
    owner: Dict[int, str] = {}
    for cfg in chip_cfgs:
        env = dict(worker_env)
        if n_host_chips > 0:
            if cfg.chips is None:
                if len(chip_cfgs) > 1:
                    raise ValueError(
                        f"{cfg.worker_name} has no chips assigned, and "
                        f"{len(chip_cfgs)} processes need an accelerator on "
                        f"this {n_host_chips}-chip host: a chip has one "
                        f"owner, so allocation_mode must name a set for each"
                    )
            else:
                for c in cfg.chips:
                    if c in owner:
                        raise ValueError(
                            f"chip {c} assigned to both {owner[c]} and "
                            f"{cfg.worker_name}"
                        )
                    owner[c] = cfg.worker_name
                env.update(chip_env(cfg.chips, n_host_chips))
        envs[cfg.worker_name] = env
    others = list(exp_cfg.rollout_workers)
    if exp_cfg.gserver_manager is not None:
        others.append(exp_cfg.gserver_manager)
    for cfg in others:
        envs[cfg.worker_name] = {**worker_env, "JAX_PLATFORMS": "cpu"}
    return envs


# Serializes the window in which a child's environment is staged in
# os.environ around Process.start() (spawn inherits the parent's
# environment at exec, the only way to reach the child's interpreter
# start through multiprocessing).
_spawn_env_lock = threading.Lock()


def _run_worker_proc(
    worker_type: str,
    config: Any,
    name_resolve_cfg: Dict,
    error_queue,
):
    """Subprocess entry: reconfigure name_resolve, build + run the worker."""
    worker_name = getattr(config, "worker_name", worker_type)
    try:
        name_resolve.reconfigure(**name_resolve_cfg)
        from areal_tpu.system import load_worker

        cls = load_worker(worker_type)
        w = cls()
        w.configure(
            config,
            experiment_name=config.experiment_name,
            trial_name=config.trial_name,
            worker_name=worker_name,
        )
        w.run()
    except Exception:
        error_queue.put(f"{worker_name}: " + traceback.format_exc())
        raise


@dataclasses.dataclass
class _WorkerRecord:
    worker_type: str
    config: Any
    proc: mp.Process
    restarts: int = 0
    last_restart: float = 0.0
    last_seen_alive: float = 0.0  # last fresh heartbeat (0 = never beat)


class LocalController:
    """Run one trial on this host: subprocess workers + inline master."""

    def __init__(
        self,
        exp_cfg: ExperimentConfig,
        name_resolve_cfg: Optional[Dict] = None,
        worker_env: Optional[Dict[str, str]] = None,
        max_worker_restarts: int = 2,
        restartable_roles: Optional[Set[str]] = None,
    ):
        self.exp_cfg = exp_cfg
        self.name_resolve_cfg = name_resolve_cfg or {"backend": "nfs"}
        self.worker_env = worker_env or {}
        # Per-worker fault domain: how many times one worker role may be
        # restarted in place before the failure escalates to the
        # whole-experiment relaunch loop.
        self.max_worker_restarts = max_worker_restarts
        self.restartable_roles = (
            RESTARTABLE_ROLES if restartable_roles is None
            else frozenset(restartable_roles)
        )
        self._workers: Dict[str, _WorkerRecord] = {}
        # Guarded by _err_lock: appended by the supervisor thread while
        # the main thread drains/raises in run()'s teardown.
        self._pending_errors: List[str] = []
        self._err_lock = threading.Lock()
        # Worker name -> process environment, planned in start_workers.
        self._envs: Dict[str, Dict[str, str]] = {}
        self._ctx = mp.get_context("spawn")
        self._errors = self._ctx.Queue()

    @property
    def _procs(self) -> List[mp.Process]:
        return [r.proc for r in self._workers.values()]

    def _spawn(self, worker_type: str, config) -> mp.Process:
        # Spawned children must be able to import areal_tpu before the
        # target function runs (unpickling imports this module), so the
        # repo root has to be on PYTHONPATH at process start.
        import areal_tpu

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(areal_tpu.__file__)))
        name = getattr(config, "worker_name", worker_type)
        env = dict(self._envs.get(name, self.worker_env))
        existing = env.get("PYTHONPATH", os.environ.get("PYTHONPATH", ""))
        if repo_root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = (
                repo_root + (os.pathsep + existing if existing else "")
            )
        p = self._ctx.Process(
            target=_run_worker_proc,
            args=(worker_type, config, self.name_resolve_cfg, self._errors),
            daemon=True,
        )
        # The child's environment must be complete BEFORE its
        # interpreter starts: unpickling the target imports jax, and
        # libtpu reads its chip assignment when it loads.
        with _spawn_env_lock:
            saved = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                p.start()
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        rec = self._workers.get(name)
        if rec is None:
            self._workers[name] = _WorkerRecord(worker_type, config, p)
        else:  # restart: keep the record's history
            rec.proc = p
        return p

    def start_workers(self):
        from areal_tpu.system import _WORKER_CLASSES

        async_types = ["generation_server", "gserver_manager", "rollout_worker"]
        wants_async = bool(
            self.exp_cfg.generation_servers
            or self.exp_cfg.gserver_manager
            or self.exp_cfg.rollout_workers
        )
        missing = [t for t in async_types if t not in _WORKER_CLASSES]
        if wants_async and missing:
            raise NotImplementedError(
                f"async worker roles not available yet: {missing}"
            )
        n_chips = host_tpu_chips({**os.environ, **self.worker_env})
        if n_chips > 0:
            from jax._src import xla_bridge

            if xla_bridge.backends_are_initialized():
                raise RuntimeError(
                    "the launcher process has initialised a jax backend and "
                    "so holds the chips its workers need; it must stay off jax"
                )
        self._envs = plan_worker_envs(self.exp_cfg, self.worker_env, n_chips)
        for cfg in self.exp_cfg.model_workers:
            self._spawn("model_worker", cfg)
        for cfg in self.exp_cfg.generation_servers:
            self._spawn("generation_server", cfg)
        if self.exp_cfg.gserver_manager is not None:
            self._spawn("gserver_manager", self.exp_cfg.gserver_manager)
        for cfg in self.exp_cfg.rollout_workers:
            self._spawn("rollout_worker", cfg)

    def _drain_errors(self):
        while True:
            try:
                err = self._errors.get_nowait()
            except Exception:
                return
            with self._err_lock:
                self._pending_errors.append(err)

    def _discard_errors_for(self, worker_name: str):
        """Drop queued tracebacks attributed to a worker the supervisor
        is restarting — a handled failure must not fail the run later."""
        with self._err_lock:
            kept, dropped = [], []
            for err in self._pending_errors:
                (dropped if err.startswith(f"{worker_name}: ")
                 else kept).append(err)
            self._pending_errors = kept
        for err in dropped:
            logger.warning(
                f"restarting {worker_name}; absorbed its failure:\n{err}"
            )
        return len(dropped)

    def check_worker_errors(self):
        self._drain_errors()
        with self._err_lock:
            if self._pending_errors:
                raise RuntimeError(
                    f"worker failed:\n{self._pending_errors[0]}"
                )

    # ------------------------------------------------------------------
    # Supervision: per-worker restart, heartbeat hang detection,
    # escalation to the whole-experiment relaunch
    # ------------------------------------------------------------------

    def _escalate(self, why: str):
        import _thread

        logger.error(f"{why}; interrupting master")
        self._watchdog_fired = True
        _thread.interrupt_main()

    def _restart_worker(self, name: str, rec: _WorkerRecord, why: str) -> bool:
        """Restart one worker role in place. Returns False when the
        failure must escalate instead (role not restartable / budget
        spent)."""
        if (
            rec.worker_type not in self.restartable_roles
            or rec.restarts >= self.max_worker_restarts
        ):
            return False
        if rec.proc.is_alive():
            # Hung, not dead: kill the wedged process first.
            rec.proc.kill()
            rec.proc.join(timeout=10)
        rec.restarts += 1
        rec.last_restart = time.monotonic()
        self._discard_errors_for(name)
        logger.warning(
            f"restarting {name} ({why}; "
            f"attempt {rec.restarts}/{self.max_worker_restarts})"
        )
        self._spawn(rec.worker_type, rec.config)
        return True

    def supervise_once(self, registry: Optional[health.HealthRegistry] = None) -> bool:
        """One supervision pass. Returns False once a failure escalated
        (supervision should stop); True to keep supervising."""
        self._drain_errors()
        alive_members = registry.snapshot() if registry is not None else {}
        stopped = registry.stopped_members() if registry is not None else {}
        now = time.monotonic()
        for name, rec in list(self._workers.items()):
            # Only THIS incarnation's beats count: a dead worker's record
            # stays fresh for up to 3*TTL, and crediting it to the
            # replacement would end its startup grace before its first
            # beat (and hang-kill it mid model load).
            if (
                name in alive_members
                and alive_members[name].get("pid") == rec.proc.pid
            ):
                rec.last_seen_alive = now
            dead = (not rec.proc.is_alive()) and rec.proc.exitcode not in (0, None)
            # Hang: the process is up but its poll loop stopped beating
            # AFTER this incarnation was last seen healthy (never-beat
            # workers get startup grace; freshly restarted ones too), and
            # it did not announce a graceful shutdown. Only judged for
            # restartable (serving-plane) roles: trainer-plane poll loops
            # legitimately block for minutes inside jit compiles.
            hung = (
                rec.worker_type in self.restartable_roles
                and rec.proc.is_alive()
                and rec.last_seen_alive > rec.last_restart
                and name not in alive_members
                and name not in stopped
            )
            if not dead and not hung:
                continue
            why = "process died" if dead else "heartbeat went stale"
            if not self._restart_worker(name, rec, why):
                self._escalate(f"{name} failed ({why})")
                return False
        # Queued tracebacks. A traceback whose process is still alive is
        # either in-flight death (handled as a proc exit on a later pass)
        # or a leftover from an incarnation we already replaced.
        with self._err_lock:
            pending_snapshot = list(self._pending_errors)
        for err in pending_snapshot:
            name = err.split(": ", 1)[0]
            rec = self._workers.get(name)
            if rec is not None and rec.proc.is_alive():
                if rec.restarts > 0:
                    self._discard_errors_for(name)
                continue
            if rec is not None and self._restart_worker(name, rec, "raised"):
                continue
            self._escalate(f"worker failure: {name}")
            return False
        return True

    def _watchdog(self, stop_event):
        """Supervise workers while the inline master runs: restart failed
        serving-plane workers in place; interrupt the master (so its
        relaunch-recovery path runs) for anything non-recoverable."""
        registry = health.HealthRegistry(
            self.exp_cfg.experiment_name, self.exp_cfg.trial_name
        )
        while not stop_event.wait(0.5):
            try:
                keep_going = self.supervise_once(registry)
            except Exception:
                logger.warning("supervision pass failed", exc_info=True)
                continue
            if not keep_going:
                return

    def run(self, timeout: Optional[float] = None) -> Dict:
        """Blocking: start workers, run master inline, join everything."""
        import threading

        name_resolve.reconfigure(**self.name_resolve_cfg)
        self.start_workers()
        self._watchdog_fired = False
        user_interrupt = False
        stop_watchdog = threading.Event()
        watchdog = threading.Thread(
            target=self._watchdog, args=(stop_watchdog,), daemon=True
        )
        watchdog.start()

        from areal_tpu.system.master_worker import MasterWorker

        master = MasterWorker()
        try:
            master.configure(
                self.exp_cfg.master,
                experiment_name=self.exp_cfg.experiment_name,
                trial_name=self.exp_cfg.trial_name,
                worker_name="master",
            )
            master.run()
        except KeyboardInterrupt:
            # Distinguish the two interrupt sources by WHO fired: only
            # the watchdog's interrupt means a worker died (traceback or
            # not) and must become RuntimeError for relaunch-recovery. A
            # genuine Ctrl-C propagates as-is — the terminal delivers
            # SIGINT to the whole process group, so workers also die
            # nonzero, and exit codes alone can't tell the cases apart.
            if self._watchdog_fired:
                self.check_worker_errors()
                dead = [
                    p.pid for p in self._procs
                    if (not p.is_alive()) and p.exitcode not in (0, None)
                ]
                raise RuntimeError(
                    f"worker process(es) died without a traceback "
                    f"(killed/native crash): pids={dead}"
                )
            user_interrupt = True
            raise
        finally:
            stop_watchdog.set()
            if not user_interrupt:
                # Surface worker failures the watchdog hadn't polled yet
                # (died in its 0.5s window as the master finished). Only
                # a genuine Ctrl-C suppresses this — teardown noise from
                # interrupted workers must not override the user's stop.
                self.check_worker_errors()
            self.join(timeout=30)
        return {"global_step": master.step_info.global_step,
                "perf_summary": dict(master.perf_summary)}

    def join(self, timeout: float = 30):
        deadline = time.monotonic() + timeout
        for p in self._procs:
            p.join(timeout=max(0.1, deadline - time.monotonic()))
        for p in self._procs:
            if p.is_alive():
                logger.warning(f"terminating straggler worker pid={p.pid}")
                p.terminate()
        self._workers.clear()


class ClusterController:
    """Scheduler-submitted workers + inline master: the multi-host control
    plane (reference counterpart: realhf/apps/main.py submitting
    `apps.remote worker` lines through the SLURM scheduler,
    scheduler/slurm/utils.py).

    Differences from LocalController: workers are launched through a
    `SchedulerClient` (local subprocesses for one machine; a registered
    cluster scheduler for pods) with their configs spooled as pickles to
    `spool_dir` (a shared filesystem on real clusters), and discovery
    runs over any name_resolve backend — typically the 'kv' TCP service
    (base/name_resolve_kv.py), which needs no shared FS at all. When
    `kv_address` is omitted a KvStoreServer is started in-process next to
    the master (the usual topology: control plane on the launch host).
    """

    def __init__(
        self,
        exp_cfg: ExperimentConfig,
        spool_dir: str,
        scheduler_mode: str = "local",
        kv_address: Optional[str] = None,
        worker_env: Optional[Dict[str, str]] = None,
        scheduler_kwargs: Optional[Dict] = None,
    ):
        self.exp_cfg = exp_cfg
        self.spool_dir = spool_dir
        self.scheduler_mode = scheduler_mode
        self.worker_env = worker_env or {}
        self._kv_server = None
        if kv_address is None:
            from areal_tpu.base.name_resolve_kv import KvStoreServer
            from areal_tpu.base import network

            self._kv_server = KvStoreServer(network.gethostip(), 0).start()
            kv_address = self._kv_server.address
        self.kv_address = kv_address
        self.name_resolve_cfg = {"backend": "kv", "address": kv_address}
        # Importing the client initializes the scheduler package, whose
        # __init__ registers the cluster backends (gke).
        from areal_tpu.scheduler.client import make_scheduler

        kwargs = dict(scheduler_kwargs or {})
        if scheduler_mode != "local":
            # Cluster job names must be scoped per trial: two experiments
            # sharing a namespace would otherwise collide on worker names
            # (and submit()'s stale-job cleanup would delete the other
            # trial's live workers).
            kwargs.setdefault(
                "name_prefix",
                f"{exp_cfg.experiment_name}-{exp_cfg.trial_name}",
            )
        self._envs: Dict[str, Dict[str, str]] = {}
        self._sched = make_scheduler(
            scheduler_mode,
            log_dir=os.path.join(spool_dir, "logs"),
            **kwargs,
        )
        self._job_names: List[str] = []

    def _submit(self, worker_type: str, config) -> str:
        import json as _json
        import pickle

        os.makedirs(self.spool_dir, exist_ok=True)
        cfg_path = os.path.join(
            self.spool_dir, f"{config.worker_name.replace('/', '_')}.pkl"
        )
        with open(cfg_path, "wb") as f:
            pickle.dump(config, f)
        import areal_tpu

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(areal_tpu.__file__))
        )
        env = dict(self._envs.get(config.worker_name, self.worker_env))
        env["PYTHONPATH"] = (
            repo_root + os.pathsep + env.get(
                "PYTHONPATH", os.environ.get("PYTHONPATH", "")
            )
        ).rstrip(os.pathsep)
        name = self._sched.submit(
            config.worker_name,
            [
                sys.executable, "-m", "areal_tpu.system.worker_main",
                "--worker-type", worker_type,
                "--config", cfg_path,
                "--name-resolve", _json.dumps(self.name_resolve_cfg),
            ],
            env=env,
            cwd=repo_root,
        )
        self._job_names.append(name)
        return name

    def start_workers(self):
        # Only local-mode workers share this host's chips; a cluster
        # scheduler places each worker on a host of its own.
        n_chips = (
            host_tpu_chips({**os.environ, **self.worker_env})
            if self.scheduler_mode == "local" else 0
        )
        self._envs = plan_worker_envs(self.exp_cfg, self.worker_env, n_chips)
        for cfg in self.exp_cfg.model_workers:
            self._submit("model_worker", cfg)
        for cfg in self.exp_cfg.generation_servers:
            self._submit("generation_server", cfg)
        if self.exp_cfg.gserver_manager is not None:
            self._submit("gserver_manager", self.exp_cfg.gserver_manager)
        for cfg in self.exp_cfg.rollout_workers:
            self._submit("rollout_worker", cfg)

    def check_worker_errors(self):
        from areal_tpu.scheduler.client import JobState

        for n in self._job_names:
            info = self._sched.find(n)
            if info.state in (JobState.FAILED, JobState.CANCELLED):
                log = os.path.join(
                    self.spool_dir, "logs", n.replace("/", "_") + ".log"
                )
                tail = ""
                try:
                    with open(log) as f:
                        tail = f.read()[-3000:]
                except OSError:
                    pass
                raise RuntimeError(f"worker {n} -> {info.state}:\n{tail}")

    def _watchdog(self, stop_event):
        import _thread

        from areal_tpu.scheduler.client import JobState

        while not stop_event.wait(0.5):
            for n in self._job_names:
                if self._sched.find(n).state in (
                    JobState.FAILED, JobState.CANCELLED
                ):
                    logger.error(
                        f"worker {n} failed; interrupting master"
                    )
                    self._watchdog_fired = True
                    _thread.interrupt_main()
                    return

    def run(self) -> Dict:
        """Blocking: start workers via the scheduler, run master inline."""
        import threading

        name_resolve.reconfigure(**self.name_resolve_cfg)
        self.start_workers()
        self._watchdog_fired = False
        user_interrupt = False
        stop_watchdog = threading.Event()
        watchdog = threading.Thread(
            target=self._watchdog, args=(stop_watchdog,), daemon=True
        )
        watchdog.start()

        from areal_tpu.system.master_worker import MasterWorker

        master = MasterWorker()
        try:
            master.configure(
                self.exp_cfg.master,
                experiment_name=self.exp_cfg.experiment_name,
                trial_name=self.exp_cfg.trial_name,
                worker_name="master",
            )
            master.run()
        except KeyboardInterrupt:
            # See LocalController.run: only the watchdog's interrupt is a
            # worker failure; genuine Ctrl-C re-raises untouched.
            if self._watchdog_fired:
                self.check_worker_errors()
                raise RuntimeError(
                    "a worker job failed (state captured by scheduler)"
                )
            user_interrupt = True
            raise
        finally:
            stop_watchdog.set()
            try:
                if not user_interrupt:
                    self.check_worker_errors()
            finally:
                # Always tear down: leaking scheduler jobs + the KV
                # server would collide with a recovery relaunch.
                self.stop()
        return {"global_step": master.step_info.global_step,
                "perf_summary": dict(master.perf_summary)}

    def stop(self):
        self._sched.stop_all()
        if self._kv_server is not None:
            self._kv_server.stop()
            self._kv_server = None
