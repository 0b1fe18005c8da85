"""Experiment builders + cli overrides + entry-point e2e (mirrors the
reference tests/experiments category at the config level)."""

import json
import subprocess
import sys
import uuid

import pytest

from areal_tpu.api.cli_args import (
    AsyncPPOMATHExpConfig,
    PPOMATHExpConfig,
    SFTExpConfig,
    apply_overrides,
)
from areal_tpu.api.dfg import build_graph
from areal_tpu.experiments import make_experiment
from tests import fixtures
from tests.system.test_e2e_experiments import TINY_CFG


def test_apply_overrides_types():
    cfg = SFTExpConfig()
    apply_overrides(
        cfg,
        [
            "experiment_name=abc",
            "train_batch_size=32",
            "model.optimizer.lr=0.001",
            "model.remat=false",
            "exp_ctrl.benchmark_steps=5",
            f"model.config={json.dumps(TINY_CFG)}",
            "dataset.max_length=none",
        ],
    )
    assert cfg.experiment_name == "abc"
    assert cfg.train_batch_size == 32
    assert cfg.model.optimizer.lr == 0.001
    assert cfg.model.remat is False
    assert cfg.exp_ctrl.benchmark_steps == 5
    assert cfg.model.config["hidden_dim"] == 32
    assert cfg.dataset.max_length is None
    with pytest.raises(AttributeError):
        apply_overrides(cfg, ["nonexistent_field=1"])


def _sft_cfg(tmp_path):
    rows = fixtures.make_sft_rows(16, seed=3)
    texts = [r["prompt"] + " " + r["answer"] for r in rows]
    tok = fixtures.train_tiny_tokenizer(texts, tmp_path)
    tok_dir = str(tmp_path / "tok")
    tok.save_pretrained(tok_dir)
    data = fixtures.write_jsonl(rows, tmp_path / "sft.jsonl")
    cfg = SFTExpConfig()
    apply_overrides(
        cfg,
        [
            f"experiment_name=sft-{uuid.uuid4().hex[:6]}",
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            "dataset.max_length=64",
            "train_batch_size=4",
            "model.backend=mock_train",
            f"model.config={json.dumps(TINY_CFG)}",
            "exp_ctrl.benchmark_steps=3",
            f"name_resolve_root={tmp_path / 'nr'}",
        ],
    )
    return cfg, tok_dir, data


def test_build_sft_and_ppo_experiments(tmp_path):
    cfg, tok_dir, data = _sft_cfg(tmp_path)
    exp = make_experiment("sft", cfg)
    assert len(exp.model_workers) == 1
    assert exp.master.rpcs[0].name == "trainDefault"
    build_graph(exp.master.rpcs)

    pcfg = PPOMATHExpConfig()
    apply_overrides(
        pcfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
            "group_size=2",
        ],
    )
    exp = make_experiment("ppo-math", pcfg)
    g = build_graph(exp.master.rpcs)
    names = set(g.rpcs)
    assert {"actor_gen", "rew_inf", "actor_train"} <= names
    # scratch init without a path: no ref model
    assert "ref_inf" not in names
    # group size propagated into the generate interface
    gen = g.rpcs["actor_gen"]
    actor_shard = exp.model_workers[0].shards[0]
    assert actor_shard.interface.args["gconfig"]["n"] == 2

    acfg = AsyncPPOMATHExpConfig()
    apply_overrides(
        acfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
            "n_rollout_workers=2",
            "ppo.max_head_offpolicyness=4",
        ],
    )
    exp = make_experiment("async-ppo-math", acfg)
    assert len(exp.rollout_workers) == 2
    assert exp.gserver_manager.max_head_offpolicyness == 4
    assert exp.generation_servers[0].tokenizer_path == tok_dir
    assert exp.model_workers[0].stream_dataset
    build_graph(exp.master.rpcs)
    # Colocated default: servers have no chips of their own to be given.
    assert exp.generation_servers[0].chips is None
    assert exp.model_workers[0].chips == [0]

    # Decoupled: server i owns the i-th slice of the gen partition, the
    # trainer the chips after it; the slice must match the server's TP.
    acfg.allocation_mode = "gen.d2t1+d1f2"
    acfg.n_generation_servers = 2
    exp = make_experiment("async-ppo-math", acfg)
    assert [g.chips for g in exp.generation_servers] == [[0], [1]]
    assert exp.model_workers[0].chips == [2, 3]
    assert exp.model_workers[0].shards[0].model.args["device_ids"] == [0, 1]
    acfg.gen_tensor_parallel = 2
    with pytest.raises(ValueError, match="gen_tensor_parallel=2"):
        make_experiment("async-ppo-math", acfg)


def test_allocation_mode_drives_train_mesh(tmp_path):
    """PR 9 wiring pin: the allocation DSL's fsdp/tensor axes reach the
    trainer (previously only the data axis was consumed, as the worker
    count). Worker-local meshes slice the train partition; the
    decoupled form offsets past the gen partition; multi-host builds
    the GLOBAL mesh with lockstep datasets. Budget: <2 s (config-level
    only, no engines built)."""
    from areal_tpu.experiments import common as C

    cfg, tok_dir, data = _sft_cfg(tmp_path)
    # Single-device allocation: unchanged legacy behavior.
    assert C.train_mesh_for_worker(cfg, 0, 1) == (None, None)

    cfg.allocation_mode = "d2f2t2"
    n = C.resolve_n_workers(cfg)
    assert n == 2
    spec, devs = C.train_mesh_for_worker(cfg, 1, n)
    assert spec == "d1f2s1t2"
    # device_ids are LOCAL: the launcher shows worker 1 only its chips
    # (4..7 of the host), which it sees as devices 0..3.
    assert devs == [0, 1, 2, 3]
    exp = make_experiment("sft", cfg)
    m = exp.model_workers[1].shards[0].model
    assert m.args["mesh_spec"] == "d1f2s1t2"
    assert m.args["device_ids"] == [0, 1, 2, 3]
    assert [w.chips for w in exp.model_workers] == [[0, 1, 2, 3], [4, 5, 6, 7]]

    # Decoupled: the train partition's chips start after the gen partition.
    cfg.allocation_mode = "gen.d2t1+d1f2"
    spec, devs = C.train_mesh_for_worker(cfg, 0, 1)
    assert spec == "d1f2s1t1"
    assert devs == [0, 1]
    assert C.worker_chips(cfg, 1)["model_worker/0"] == [2, 3]

    # Multi-host: one worker per host, GLOBAL mesh, lockstep dataset.
    cfg.allocation_mode = "d2f2"
    cfg.train_n_hosts = 2
    assert C.resolve_n_workers(cfg) == 2
    spec, devs = C.train_mesh_for_worker(cfg, 1, 2)
    assert spec == "d2f2s1t1" and devs is None
    exp = make_experiment("sft", cfg)
    for i, w in enumerate(exp.model_workers):
        assert w.chips is None  # each worker owns its whole host
        assert (w.train_n_hosts, w.train_host_rank) == (2, i)
        assert (w.dataset_dp_rank, w.dataset_dp_size) == (0, 1)

    # An explicit per-model mesh_spec still wins over the derivation.
    cfg.train_n_hosts = 1
    cfg.model.mesh_spec = "d1"
    exp = make_experiment("sft", cfg)
    assert exp.model_workers[0].shards[0].model.args["mesh_spec"] == "d1"


@pytest.mark.slow
def test_main_sft_entrypoint(tmp_path):
    """Run the real CLI entry point in a subprocess (mock engine)."""
    cfg, tok_dir, data = _sft_cfg(tmp_path)
    cmd = [
        sys.executable,
        "training/main_sft.py",
        f"experiment_name={cfg.experiment_name}",
        f"tokenizer_path={tok_dir}",
        f"dataset.path={data}",
        "dataset.max_length=64",
        "train_batch_size=4",
        "model.backend=mock_train",
        f"model.config={json.dumps(TINY_CFG)}",
        "exp_ctrl.benchmark_steps=3",
        f"name_resolve_root={tmp_path / 'nr2'}",
    ]
    import os

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AREAL_FILEROOT=str(tmp_path / "fileroot"),
    )
    out = subprocess.run(
        cmd, cwd="/root/repo", env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "experiment finished" in (out.stderr + out.stdout)


def test_optional_nested_dataclass_override():
    cfg = PPOMATHExpConfig()
    assert cfg.critic is None
    apply_overrides(cfg, ["critic.path=/some/ckpt", "critic.is_critic=true",
                          "ppo.disable_value=false"])
    assert cfg.critic is not None
    assert cfg.critic.path == "/some/ckpt"
    assert cfg.ppo.disable_value is False


def test_total_train_epochs_single_source_of_truth(tmp_path):
    """ADVICE r1 (a): the top-level total_train_epochs must drive BOTH the
    master's stop condition (exp_ctrl) and the LR schedule (FinetuneSpec),
    not just the latter."""
    cfg, *_ = _sft_cfg(tmp_path)
    apply_overrides(cfg, ["total_train_epochs=3"])
    exp = make_experiment("sft", cfg)
    assert exp.master.exp_ctrl.total_train_epochs == 3
    assert exp.model_workers[0].total_train_epochs == 3

    # an explicitly-set exp_ctrl value wins (backward compat)
    cfg2, *_ = _sft_cfg(tmp_path)
    apply_overrides(
        cfg2, ["total_train_epochs=3", "exp_ctrl.total_train_epochs=5"]
    )
    exp2 = make_experiment("sft", cfg2)
    assert exp2.master.exp_ctrl.total_train_epochs == 5


def test_async_master_gets_prompt_dataset_size(tmp_path):
    """ADVICE r1 (b): async experiments must give the master the prompt
    dataset size so it can derive epoch boundaries (the stream dataset
    never reports epoch_done)."""
    rows = fixtures.make_math_code_rows(16, seed=3)
    texts = [r["prompt"] for r in rows]
    tok = fixtures.train_tiny_tokenizer(texts, tmp_path)
    tok_dir = str(tmp_path / "tok")
    tok.save_pretrained(tok_dir)
    data = fixtures.write_jsonl(rows, tmp_path / "prompts.jsonl")
    acfg = AsyncPPOMATHExpConfig()
    apply_overrides(
        acfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
        ],
    )
    exp = make_experiment("async-ppo-math", acfg)
    assert exp.master.dataset_size == 16


def test_async_multi_turn_agent_selection(tmp_path):
    rows = fixtures.make_sft_rows(8, seed=4)
    texts = [r["prompt"] + " " + r["answer"] for r in rows]
    tok = fixtures.train_tiny_tokenizer(texts, tmp_path)
    tok_dir = str(tmp_path / "tok2")
    tok.save_pretrained(tok_dir)
    data = fixtures.write_jsonl(rows, tmp_path / "p2.jsonl")
    acfg = AsyncPPOMATHExpConfig()
    apply_overrides(
        acfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
            "agent_type=math-multi-turn",
            "agent_num_turns=3",
            "agent_turn_discount=0.9",
        ],
    )
    exp = make_experiment("async-ppo-math", acfg)
    agent = exp.rollout_workers[0].agent
    assert agent.type_ == "math-multi-turn"
    assert agent.args["num_turns"] == 3
    assert agent.args["turn_level_discount"] == 0.9


def test_auto_evaluator_wiring(tmp_path, monkeypatch):
    """run_experiment starts/drains the AutomaticEvaluator when
    cfg.auto_eval is set (reference master starts it under auto_eval)."""
    import threading

    import training.utils as TU
    from areal_tpu.api.cli_args import SFTExpConfig

    calls = {"init": None, "steps": 0, "drained": False}

    class StubEvaluator:
        def __init__(self, **kw):
            calls["init"] = kw
            self.scheduler = type(
                "S", (), {"stop_all": staticmethod(lambda: None)}
            )()

        def step(self):
            calls["steps"] += 1

        def run_until_idle(self, timeout):
            calls["drained"] = True

        def results(self):
            return {2: 0.5}

    monkeypatch.setattr(
        "areal_tpu.scheduler.evaluator.AutomaticEvaluator", StubEvaluator
    )
    cfg = SFTExpConfig(
        experiment_name="ae", trial_name="t0",
        auto_eval=True, auto_eval_data_path="/data/bench.jsonl",
        auto_eval_task="code", auto_eval_model_role="actor",
    )
    stop = TU._start_auto_evaluator(cfg)
    assert stop is not None
    assert calls["init"]["task"] == "code"
    assert calls["init"]["save_root"].endswith("/actor")
    assert calls["init"]["data_path"] == "/data/bench.jsonl"
    deadline = threading.Event()
    deadline.wait(2.5)  # let the tick thread run at least once
    stop(drain_timeout=5)
    assert calls["drained"]

    # auto_eval without a data path is a config error.
    import pytest as _pytest

    with _pytest.raises(ValueError, match="auto_eval_data_path"):
        TU._start_auto_evaluator(
            SFTExpConfig(experiment_name="ae2", trial_name="t0", auto_eval=True)
        )

    # Disabled -> no evaluator.
    assert TU._start_auto_evaluator(SFTExpConfig()) is None


def test_per_mfc_microbatch_overrides(tmp_path):
    """Per-MFC MicroBatchSpec reachable as dotted overrides (reference:
    one MFCConfig per function call in PPOMATHConfig)."""
    _, tok_dir, data = _sft_cfg(tmp_path)
    pcfg = PPOMATHExpConfig()
    apply_overrides(
        pcfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
            "mb_spec_n_mbs=2",
            "actor_train.n_mbs=8",
            "actor_gen.max_tokens_per_mb=4096",
        ],
    )
    exp = make_experiment("ppo-math", pcfg)
    by_name = {r.name: r for r in exp.master.rpcs}
    assert by_name["actor_train"].mb_spec.n_mbs == 8  # per-MFC override
    assert by_name["actor_gen"].mb_spec.max_tokens_per_mb == 4096
    assert by_name["actor_gen"].mb_spec.n_mbs == 2  # inherits global
    assert by_name["rew_inf"].mb_spec.n_mbs == 2


def test_serving_engine_knobs_reachable(tmp_path):
    _, tok_dir, data = _sft_cfg(tmp_path)
    acfg = AsyncPPOMATHExpConfig()
    apply_overrides(
        acfg,
        [
            f"tokenizer_path={tok_dir}",
            f"dataset.path={data}",
            f"actor.config={json.dumps(TINY_CFG)}",
            "actor.init_from_scratch=true",
            "gen_prompt_bucket=128",
            "gen_prefill_max_batch=4",
            "gen_kv_pool_tokens=65536",
            "exp_ctrl.save_freq_steps=50",
            "exp_ctrl.eval_freq_epochs=1",
        ],
    )
    exp = make_experiment("async-ppo-math", acfg)
    gs = exp.generation_servers[0]
    assert gs.prompt_bucket == 128
    assert gs.prefill_max_batch == 4
    assert gs.kv_pool_tokens == 65536
    assert exp.master.exp_ctrl.save_freq_steps == 50
    assert exp.master.exp_ctrl.eval_freq_epochs == 1


def test_describe_options_surface():
    """Every dotted override path is discoverable with type/default/help
    (the reference's Hydra --help surface)."""
    from areal_tpu.api.cli_args import describe_options, format_options

    rows = describe_options(AsyncPPOMATHExpConfig())
    paths = {r["path"] for r in rows}
    # nested dataclasses expand ...
    assert "ppo.gconfig.max_new_tokens" in paths
    assert "actor.optimizer.lr" in paths
    assert "actor_train.n_mbs" in paths
    assert "exp_ctrl.save_freq_steps" in paths
    assert "gen_prompt_bucket" in paths
    # ... including Optional[dataclass] fields defaulting to None
    assert "critic.optimizer.lr" in paths
    # help metadata rides along
    per_mfc = next(r for r in rows if r["path"] == "actor_train.n_mbs")
    assert "micro-batches" in per_mfc["help"]
    txt = format_options(AsyncPPOMATHExpConfig())
    assert "ppo.gconfig.max_new_tokens" in txt


def test_help_config_flag(tmp_path):
    """`training/main_*.py --help-config` prints the full option surface."""
    repo = fixtures.REPO_ROOT if hasattr(fixtures, "REPO_ROOT") else None
    import os

    repo = repo or os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    r = subprocess.run(
        [sys.executable, "training/main_sync_ppo.py", "--help-config"],
        capture_output=True,
        text=True,
        cwd=repo,
        env={**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"},
        timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "actor.optimizer.lr" in r.stdout
    assert "exp_ctrl.save_freq_steps" in r.stdout
