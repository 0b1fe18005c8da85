"""Async PPO math experiment (reference experiments/async_exp/
async_ppo_math_exp.py): decoupled generation servers + rollout workers
stream trajectories to stream-dataset trainers; the train-side DFG is
{ref_inf?} -> actor_train with a post-hook param-realloc dump that the
gserver manager fans out to the servers."""

from __future__ import annotations

import dataclasses

from areal_tpu.api.cli_args import AsyncPPOMATHExpConfig
from areal_tpu.api.config import (
    AgentAbstraction,
    EnvServiceAbstraction,
    ModelInterfaceAbstraction,
    ModelName,
    ModelShardID,
)
from areal_tpu.api.dfg import MFCDef, ModelInterfaceType, ParamReallocHook
from areal_tpu.api.system_api import (
    ExperimentConfig,
    GenerationServerConfig,
    GserverManagerConfig,
    ModelShardSpec,
    RolloutWorkerConfig,
)
from areal_tpu.experiments import register_experiment
from areal_tpu.experiments import common as C
from areal_tpu.experiments.ppo_math_exp import actor_interface_args


def _agent_abstraction(cfg: AsyncPPOMATHExpConfig) -> AgentAbstraction:
    """Rollout agent from config: `agent_type` picks "math-single-step"
    (default; one group per prompt), "math-multi-turn" (feedback loop,
    reference math_multi_turn_agent.py), or "tool-use" (multi-turn tool
    calls through the pooled reward executor, agents/tool_use.py)."""
    if cfg.agent_type == "tool-use":
        return AgentAbstraction(
            "tool-use",
            args=dict(
                gconfig=dataclasses.asdict(cfg.ppo.gconfig.new(n=1)),
                num_turns=cfg.agent_num_turns,
                turn_level_discount=cfg.agent_turn_discount,
                reward_scaling=cfg.ppo.reward_output_scaling,
                reward_bias=cfg.ppo.reward_output_bias,
                scripted_tool_turns=cfg.agent_scripted_tool_turns,
            ),
        )
    if cfg.agent_type == "math-multi-turn":
        return AgentAbstraction(
            "math-multi-turn",
            args=dict(
                gconfig=dataclasses.asdict(cfg.ppo.gconfig.new(n=1)),
                num_turns=cfg.agent_num_turns,
                turn_level_discount=cfg.agent_turn_discount,
                reward_scaling=cfg.ppo.reward_output_scaling,
                reward_bias=cfg.ppo.reward_output_bias,
            ),
        )
    return AgentAbstraction(
        "math-single-step",
        args=dict(
            gconfig=dataclasses.asdict(
                cfg.ppo.gconfig.new(n=cfg.ppo.group_size)
            ),
            success_rate_lb=cfg.ppo.success_rate_lb,
            success_rate_ub=cfg.ppo.success_rate_ub,
            reward_scaling=cfg.ppo.reward_output_scaling,
            reward_bias=cfg.ppo.reward_output_bias,
        ),
    )


def build_async_ppo_math_experiment(cfg: AsyncPPOMATHExpConfig) -> ExperimentConfig:
    n_workers = C.resolve_n_workers(cfg)
    actor = ModelName("actor", 0)
    ref = ModelName("ref", 0)
    use_ref = cfg.ref is not None or (
        cfg.actor.path is not None and cfg.ppo.kl_ctl != 0.0
    )
    n_seqs = cfg.train_batch_size
    iface_args = actor_interface_args(cfg)

    train_input_keys = [
        "packed_input_ids", "prompt_mask", "packed_logprobs",
        "rewards", "seq_no_eos_mask",
    ]
    rpcs = []
    if use_ref:
        rpcs.append(
            MFCDef(
                name="ref_inf",
                model_name=ref,
                interface_type=ModelInterfaceType.INFERENCE,
                interface_impl=ModelInterfaceAbstraction("ppo_actor"),
                n_seqs=n_seqs,
                input_keys=("packed_input_ids", "prompt_mask"),
                output_keys=("logprobs",),
                output_key_remap={"logprobs": "ref_logprobs"},
                mb_spec=C.mb_spec(cfg, cfg.ref_inf),
            )
        )
        train_input_keys.append("ref_logprobs")
    rpcs.append(
        MFCDef(
            name="actor_train",
            model_name=actor,
            interface_type=ModelInterfaceType.TRAIN_STEP,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            n_seqs=n_seqs,
            input_keys=tuple(train_input_keys),
            mb_spec=C.mb_spec(cfg, cfg.actor_train),
            post_hooks=[ParamReallocHook(source=str(actor))],
        )
    )

    workers = []
    for i in range(n_workers):
        # The decoupled allocation's TRAIN partition drives the trainer
        # mesh: fsdp/tensor axes from allocation_mode reach the engine.
        t_mesh, t_devs = C.train_mesh_for_worker(cfg, i, n_workers)
        shards = [
            ModelShardSpec(
                id=ModelShardID(actor, host_rank=i, n_hosts=n_workers),
                model=C.model_abstraction(
                    cfg.actor, cfg.tokenizer_path,
                    mesh_spec=t_mesh, device_ids=t_devs,
                ),
                backend=C.backend_abstraction(cfg.actor, train=True),
                interface=ModelInterfaceAbstraction("ppo_actor", args=iface_args),
            )
        ]
        if use_ref:
            ref_cfg = cfg.ref or cfg.actor
            shards.append(
                ModelShardSpec(
                    id=ModelShardID(ref, host_rank=i, n_hosts=n_workers),
                    model=C.model_abstraction(
                        ref_cfg, cfg.tokenizer_path,
                        mesh_spec=t_mesh, device_ids=t_devs,
                    ),
                    backend=C.backend_abstraction(ref_cfg, train=False),
                    interface=ModelInterfaceAbstraction("ppo_actor", args=iface_args),
                )
            )
        workers.append(
            C.base_model_worker(
                cfg, i, n_workers, shards, with_dataset=False, stream_dataset=True
            )
        )

    names_ = C.worker_names(n_workers)
    model_topos = {str(actor): names_}
    if use_ref:
        model_topos[str(ref)] = names_
    master = C.base_master(cfg, rpcs, model_topos, n_workers)
    # The prompt dataset lives in the rollout workers, so the master's
    # stream dataset never reports epoch boundaries; give it the prompt
    # count so it can derive steps-per-epoch (and terminate on
    # total_train_epochs without benchmark_steps).
    master.dataset_size = C.dataset_line_count(cfg.dataset)

    # Disaggregated prefill/decode: per-index roles from the
    # comma-separated knob, padded with "unified" (the elastic pool).
    roles = [
        r.strip() or "unified"
        for r in (cfg.gen_server_roles or "").split(",")
    ]
    roles += ["unified"] * (cfg.n_generation_servers - len(roles))
    # Shard-aware weight plane: per-server (rank, degree) fleet-TP
    # coordinates (validated at config parse).
    from areal_tpu.api.cli_args import parse_weight_shards

    shards = parse_weight_shards(
        cfg.gen_weight_shards, cfg.n_generation_servers
    )
    # One owner per chip: generation server i holds the i-th slice of
    # the allocation's gen partition (the launcher hands it over in the
    # process environment), and shards over exactly those chips.
    chips = C.worker_chips(cfg, n_workers)
    own = chips.get("generation_server/0")  # slices are equal
    if own is not None and len(own) != cfg.gen_tensor_parallel:
        raise ValueError(
            f"allocation_mode={cfg.allocation_mode!r} gives each of "
            f"{cfg.n_generation_servers} generation servers {len(own)} "
            f"chip(s), but gen_tensor_parallel={cfg.gen_tensor_parallel}"
        )
    gen_servers = [
        GenerationServerConfig(
            experiment_name=cfg.experiment_name,
            trial_name=cfg.trial_name,
            server_index=i,
            chips=chips.get(f"generation_server/{i}"),
            model=C.model_abstraction(cfg.actor, cfg.tokenizer_path),
            tokenizer_path=cfg.tokenizer_path or cfg.actor.path,
            max_concurrent_requests=cfg.gen_max_concurrent_requests,
            max_seq_len=cfg.gen_max_seq_len,
            decode_block_steps=cfg.gen_decode_block_steps,
            kv_page_size=cfg.gen_kv_page_size,
            kv_pool_tokens=cfg.gen_kv_pool_tokens,
            prompt_bucket=cfg.gen_prompt_bucket,
            prefill_max_batch=cfg.gen_prefill_max_batch,
            prefill_chunk=cfg.gen_prefill_chunk,
            chunked_prefill_per_lap=cfg.gen_chunked_prefill_per_lap,
            prefix_cache_tokens=cfg.gen_prefix_cache_tokens,
            kv_cache_dtype=cfg.gen_kv_cache_dtype,
            speculative_draft_len=cfg.gen_speculative_draft_len,
            speculative_ngram=cfg.gen_speculative_ngram,
            speculative_window=cfg.gen_speculative_window,
            decode_weight_dtype=cfg.gen_decode_weight_dtype,
            tensor_parallel=cfg.gen_tensor_parallel,
            role=roles[i],
            kv_handoff_compress=cfg.gen_kv_handoff_compress,
            kv_tier_bytes=(
                cfg.gen_kv_tier_mb << 20
                if cfg.gen_kv_tier_mb is not None else None
            ),
            kv_tier_disk_dir=cfg.gen_kv_tier_disk_dir,
            kv_spill_dtype=cfg.gen_kv_spill_dtype,
            weight_shard_rank=shards[i][0] if shards[i] else None,
            weight_shard_degree=shards[i][1] if shards[i] else None,
            seed=cfg.seed,
        )
        for i in range(cfg.n_generation_servers)
    ]
    manager = GserverManagerConfig(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        model_name=actor.role,
        n_servers=cfg.n_generation_servers,
        schedule_policy=cfg.schedule_policy,
        max_head_offpolicyness=cfg.ppo.max_head_offpolicyness,
        train_batch_size=cfg.train_batch_size,
        max_concurrent_rollouts=cfg.ppo.max_concurrent_rollouts,
        weight_plane=cfg.gen_weight_plane,
        weight_chunk_bytes=cfg.gen_weight_chunk_mb << 20,
        weight_fanout_degree=cfg.gen_weight_fanout,
        weight_cutover_budget_s=cfg.gen_weight_cutover_budget_s,
        weight_wire_dtype=cfg.gen_weight_wire_dtype,
        kv_index_size=cfg.gen_kv_index_size,
        elastic_pools=cfg.gen_elastic_pools,
        prefill_queue_high_tokens=cfg.gen_prefill_queue_high_tokens,
        prefill_queue_low_tokens=cfg.gen_prefill_queue_low_tokens,
        decode_free_page_min_frac=cfg.gen_decode_free_page_min_frac,
        elastic_fleet=cfg.gen_elastic_fleet,
        autoscale=cfg.gen_autoscale,
        scale_out_queued_tokens=cfg.gen_scale_out_queued_tokens,
        scale_in_queued_tokens=cfg.gen_scale_in_queued_tokens,
        pool_min_servers=cfg.gen_pool_min_servers,
        pool_max_servers=cfg.gen_pool_max_servers,
    )
    rollouts = [
        RolloutWorkerConfig(
            experiment_name=cfg.experiment_name,
            trial_name=cfg.trial_name,
            worker_index=i,
            n_rollout_workers=cfg.n_rollout_workers,
            n_pullers=n_workers,
            model_name=actor.role,
            agent=_agent_abstraction(cfg),
            env=EnvServiceAbstraction(
                "tool-use" if cfg.agent_type == "tool-use"
                else "math-code-single-step"
            ),
            datasets=[C.dataset_abstraction(cfg.dataset)],
            tokenizer_path=cfg.tokenizer_path or cfg.actor.path,
            new_tokens_per_chunk=cfg.ppo.new_tokens_per_chunk,
            max_concurrent_rollouts=max(
                1, cfg.ppo.max_concurrent_rollouts // cfg.n_rollout_workers
            ),
            seed=cfg.seed,
        )
        for i in range(cfg.n_rollout_workers)
    ]
    return ExperimentConfig(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        master=master,
        model_workers=workers,
        rollout_workers=rollouts,
        gserver_manager=manager,
        generation_servers=gen_servers,
    )


register_experiment("async-ppo-math", build_async_ppo_math_experiment)
