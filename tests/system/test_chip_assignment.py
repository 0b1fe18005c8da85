"""One owner per chip: the controller turns each worker config's `chips`
(cut from allocation_mode by experiments/common.worker_chips) into a
libtpu environment that is in place BEFORE the child's interpreter
starts, and pins every chip-less role to the CPU platform."""

import json
import os

import pytest

from areal_tpu.api.system_api import (
    ExperimentConfig,
    GenerationServerConfig,
    GserverManagerConfig,
    ModelWorkerConfig,
    RolloutWorkerConfig,
)
from areal_tpu.system import controller as C


def _exp(gen_chips, train_chips):
    return ExperimentConfig(
        experiment_name="chips", trial_name="t0",
        model_workers=[
            ModelWorkerConfig(worker_index=i, chips=c)
            for i, c in enumerate(train_chips)
        ],
        generation_servers=[
            GenerationServerConfig(server_index=i, chips=c)
            for i, c in enumerate(gen_chips)
        ],
        gserver_manager=GserverManagerConfig(),
        rollout_workers=[RolloutWorkerConfig(worker_index=0)],
    )


def test_chip_env_shapes():
    assert C.chip_env([0], 1) == {}  # the whole host: nothing to hide
    assert C.chip_env([0, 1, 2, 3], 4) == {}
    env = C.chip_env([1], 4)
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    pair = C.chip_env([2, 3], 4)
    assert pair["TPU_VISIBLE_CHIPS"] == "2,3"
    assert pair["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    # Every process talks to its own mesh controller.
    assert pair["TPU_MESH_CONTROLLER_PORT"] != env["TPU_MESH_CONTROLLER_PORT"]
    assert pair["TPU_MESH_CONTROLLER_ADDRESS"].endswith(
        pair["TPU_MESH_CONTROLLER_PORT"]
    )
    for bad in ([1, 2], [0, 2], [0, 1, 2], [4], []):
        with pytest.raises(ValueError):
            C.chip_env(bad, 4)


def test_plan_worker_envs_on_a_tpu_host():
    envs = C.plan_worker_envs(
        _exp([[0], [1]], [[2, 3]]), {"AREAL_FILEROOT": "/x"}, n_host_chips=4
    )
    assert envs["generation_server/0"]["TPU_VISIBLE_CHIPS"] == "0"
    assert envs["generation_server/1"]["TPU_VISIBLE_CHIPS"] == "1"
    assert envs["model_worker/0"]["TPU_VISIBLE_CHIPS"] == "2,3"
    ports = {e["TPU_MESH_CONTROLLER_PORT"] for n, e in envs.items()
             if "TPU_MESH_CONTROLLER_PORT" in e}
    assert len(ports) == 3
    # Roles without a chip never get near one; the shared env reaches all.
    for name in ("rollout_worker/0", "gserver_manager"):
        assert envs[name]["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_CHIPS" not in envs[name]
    assert all(e["AREAL_FILEROOT"] == "/x" for e in envs.values())

    with pytest.raises(ValueError, match="assigned to both"):
        C.plan_worker_envs(_exp([[0]], [[0]]), {}, 4)
    with pytest.raises(ValueError, match="does not fit a host with 1"):
        C.plan_worker_envs(_exp([[0]], [[1]]), {}, 1)
    # Two chip-holding processes and no assignment: they would fight
    # over the host's chips, so the launch is refused up front.
    with pytest.raises(ValueError, match="has no chips assigned"):
        C.plan_worker_envs(_exp([None], [None]), {}, 4)
    # A single chip-holding process may own the host implicitly.
    assert "TPU_VISIBLE_CHIPS" not in C.plan_worker_envs(
        _exp([], [None]), {}, 4)["model_worker/0"]


def test_plan_worker_envs_cpu_path_keeps_its_shape():
    """Held to the CPU there are no chips to own: virtual devices, local
    indices, no TPU variables — only the CPU pin for chip-less roles."""
    assert C.host_tpu_chips({"JAX_PLATFORMS": "cpu"}) == 0
    envs = C.plan_worker_envs(_exp([None, None], [None]), {"K": "v"}, 0)
    assert envs["model_worker/0"] == {"K": "v"}
    assert envs["generation_server/1"] == {"K": "v"}
    assert envs["rollout_worker/0"] == {"K": "v", "JAX_PLATFORMS": "cpu"}


def _dump_start_env(worker_type, config, name_resolve_cfg, error_queue):
    """Stand-in worker entry: /proc/self/environ is the environment the
    process was exec'd with — later os.environ edits never show there."""
    with open("/proc/self/environ", "rb") as f:
        start = dict(
            kv.split("=", 1) for kv in f.read().decode().split("\0") if "=" in kv
        )
    with open(config.out_path, "w") as f:
        json.dump({k: v for k, v in start.items()
                   if k.startswith("TPU_") or k == "JAX_PLATFORMS"}, f)


def test_child_environment_carries_assignment_at_interpreter_start(
    tmp_path, monkeypatch
):
    monkeypatch.setattr(C, "_run_worker_proc", _dump_start_env)
    cfg = ModelWorkerConfig(worker_index=0, chips=[2, 3])
    cfg.out_path = str(tmp_path / "mw.json")
    roll = RolloutWorkerConfig(worker_index=0)
    roll.out_path = str(tmp_path / "rw.json")
    exp = ExperimentConfig(model_workers=[cfg], rollout_workers=[roll])
    ctl = C.LocalController(exp, worker_env={})
    ctl._envs = C.plan_worker_envs(exp, {}, n_host_chips=4)
    before = dict(os.environ)
    procs = [ctl._spawn("model_worker", cfg), ctl._spawn("rollout_worker", roll)]
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0
    assert dict(os.environ) == before  # staging leaves the parent as it was
    mw = json.load(open(cfg.out_path))
    assert mw["TPU_VISIBLE_CHIPS"] == "2,3"
    assert mw["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert mw["TPU_MESH_CONTROLLER_PORT"] == "8478"
    rw = json.load(open(roll.out_path))
    assert rw["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_CHIPS" not in rw
