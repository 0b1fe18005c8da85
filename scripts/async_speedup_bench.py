"""Async-vs-sync PPO speedup benchmark — the reference's headline metric.

AReaL's pitch is asynchronous RL beating synchronous PPO by >2.5x on
effective-token throughput at equal quality (reference README.md:23,
blog/AReaL_v0_3.md:107-119; methodology: effective trained tokens /
end-to-end seconds, benchmark/verl_v0_3_0_post1_76084d3/README.md:26-36).
This script runs the SAME math workload through BOTH experiment shapes
and reports the ratio:

  sync:  in-mesh generate -> reward -> train, generation blocking every
         step (the ppo_math_exp DFG).
  async: generation server(s) + gserver manager + rollout workers
         (math agent + verifier env) feeding a stream-dataset trainer
         (the async_ppo_math_exp topology) — generation and verification
         overlap training.

Modes:
  --mode tiny (default): self-contained CPU run — synthetic math prompts,
    a freshly-trained WordPiece tokenizer, a 2-layer model. Proves the
    harness end-to-end and is pinned in CI
    (tests/system/test_async_speedup_bench.py). The printed ratio on CPU
    miniatures is a harness artifact, not the headline number.
  --mode chip: flagship-shaped config staged for real TPU hardware
    (R1-Distill-Qwen-1.5B shape, real tokenizer/dataset paths required).

Output: ONE JSON line
  {"sync_tokens_per_s": ..., "async_tokens_per_s": ..., "speedup": ...,
   "target": 2.5, ...}
plus optional --out file. Warmup steps (XLA compiles) are dropped from
the rate via the master's per-step history.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import uuid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TINY_CFG = dict(
    vocab_size=128,
    hidden_dim=32,
    n_layers=2,
    n_q_heads=2,
    n_kv_heads=1,
    head_dim=16,
    intermediate_dim=64,
    max_position_embeddings=256,
    compute_dtype="float32",
)

# The flagship bench shape: what the reference's own headline benchmark
# trains, sized for one v5e.
FLAGSHIP_CFG = dict(
    vocab_size=32768,
    hidden_dim=1536,
    n_layers=16,
    n_q_heads=12,
    n_kv_heads=2,
    head_dim=128,
    intermediate_dim=8960,
    max_position_embeddings=32768,
    compute_dtype="bfloat16",
)


def _make_synthetic_workload(root: str, n_rows: int = 64, seed: int = 17):
    """Tiny tokenizer + \\boxed math prompts, self-contained (no tests/
    import): the same workload shape the e2e suites drive."""
    import random

    from tokenizers import Tokenizer
    from tokenizers.models import WordPiece
    from tokenizers.pre_tokenizers import Whitespace
    from tokenizers.trainers import WordPieceTrainer
    from transformers import PreTrainedTokenizerFast

    rng = random.Random(seed)
    words = [
        "prove", "that", "the", "sum", "of", "two", "odd", "numbers",
        "is", "even", "find", "x", "such", "integral", "matrix", "prime",
        "graph", "vertex", "angle", "triangle", "circle", "radius",
    ]
    rows = []
    texts = []
    for _ in range(n_rows):
        prompt = " ".join(rng.choice(words) for _ in range(rng.randint(6, 14)))
        rows.append(
            dict(
                query_id=str(uuid.uuid4()),
                task="math",
                prompt=prompt,
                solutions=["\\boxed{42}"],
            )
        )
        texts.append(prompt)

    tok = Tokenizer(WordPiece(unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    trainer = WordPieceTrainer(
        vocab_size=TINY_CFG["vocab_size"] - 2,
        min_frequency=0,
        special_tokens=["[UNK]", "[EOS]"],
    )
    tok.train_from_iterator(texts, trainer)
    tok_file = os.path.join(root, "tokenizer.json")
    tok.save(tok_file)
    tok_dir = os.path.join(root, "tokenizer")
    PreTrainedTokenizerFast(
        tokenizer_file=tok_file, eos_token="[EOS]", pad_token="[EOS]",
        unk_token="[UNK]",
    ).save_pretrained(tok_dir)

    data_path = os.path.join(root, "math.jsonl")
    with open(data_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return tok_dir, data_path


def build_sync_cfg(*, exp, trial, model_cfg, tok_dir, data_path, n_seqs,
                   steps, gconfig, remat):
    """Sync PPO DFG: actor_gen -> rew_inf -> actor_train on one worker
    (areal_tpu/experiments/ppo_math_exp.py shape). Generation runs
    in-mesh and blocks every step — the baseline being beaten."""
    from areal_tpu.api.config import (
        DatasetAbstraction, ModelAbstraction, ModelBackendAbstraction,
        ModelInterfaceAbstraction, ModelName, ModelShardID,
    )
    from areal_tpu.api.dfg import MFCDef, ModelInterfaceType
    from areal_tpu.api.system_api import (
        ExperimentConfig, ExperimentSaveEvalControl, MasterWorkerConfig,
        ModelShardSpec, ModelWorkerConfig,
    )

    actor = ModelName("actor", 0)
    rew = ModelName("reward", 0)
    rpcs = [
        MFCDef(
            name="actor_gen",
            model_name=actor,
            interface_type=ModelInterfaceType.GENERATE,
            interface_impl=None,
            n_seqs=n_seqs,
            input_keys=("packed_prompts",),
            output_keys=(
                "packed_input_ids", "prompt_mask", "packed_logprobs",
                "seq_no_eos_mask",
            ),
        ),
        MFCDef(
            name="rew_inf",
            model_name=rew,
            interface_type=ModelInterfaceType.INFERENCE,
            interface_impl=None,
            n_seqs=n_seqs,
            input_keys=("packed_input_ids", "prompt_mask"),
            output_keys=("rewards",),
        ),
        MFCDef(
            name="actor_train",
            model_name=actor,
            interface_type=ModelInterfaceType.TRAIN_STEP,
            interface_impl=None,
            n_seqs=n_seqs,
            input_keys=(
                "packed_input_ids", "prompt_mask", "packed_logprobs",
                "rewards", "seq_no_eos_mask",
            ),
        ),
    ]
    model_args = dict(config=model_cfg, tokenizer_path=tok_dir,
                      dtype=model_cfg.get("compute_dtype", "float32"))
    shards = [
        ModelShardSpec(
            id=ModelShardID(actor),
            model=ModelAbstraction("tpu_transformer", args=model_args),
            backend=ModelBackendAbstraction(
                "jax_train",
                args=dict(optimizer=dict(lr=1e-5), remat=remat,
                          row_len_multiple=8),
            ),
            interface=ModelInterfaceAbstraction(
                "ppo_actor", args=dict(gconfig=gconfig, kl_ctl=0.0)
            ),
        ),
        ModelShardSpec(
            id=ModelShardID(rew),
            model=ModelAbstraction("tpu_transformer", args=model_args),
            backend=ModelBackendAbstraction("mock_inference"),
            interface=ModelInterfaceAbstraction("rw-math-code"),
        ),
    ]
    mw = ModelWorkerConfig(
        experiment_name=exp,
        trial_name=trial,
        worker_index=0,
        chips=[0],
        shards=shards,
        datasets=[
            DatasetAbstraction("math_code_prompt",
                               args=dict(dataset_path=data_path))
        ],
        tokenizer_path=tok_dir,
        train_batch_size=n_seqs,
        total_train_epochs=1000,
    )
    master = MasterWorkerConfig(
        experiment_name=exp,
        trial_name=trial,
        exp_ctrl=ExperimentSaveEvalControl(
            total_train_epochs=1000, benchmark_steps=steps
        ),
        rpcs=rpcs,
        model_topos={str(actor): ["model_worker/0"],
                     str(rew): ["model_worker/0"]},
        data_hosts=["model_worker/0"],
        n_model_workers=1,
        train_batch_size=n_seqs,
    )
    return ExperimentConfig(
        experiment_name=exp, trial_name=trial, master=master,
        model_workers=[mw],
    )


def build_async_cfg(*, exp, trial, model_cfg, tok_dir, data_path, n_seqs,
                    steps, gconfig, remat, max_seq_len,
                    max_concurrent_rollouts, offpolicyness):
    """Async PPO topology: generation server + manager + rollout worker
    (math agent + verifier env) + stream-dataset trainer
    (areal_tpu/experiments/async_ppo_math_exp.py shape)."""
    from areal_tpu.api.config import (
        AgentAbstraction, DatasetAbstraction, EnvServiceAbstraction,
        ModelAbstraction, ModelBackendAbstraction,
        ModelInterfaceAbstraction, ModelName, ModelShardID,
    )
    from areal_tpu.api.dfg import (
        MFCDef, ModelInterfaceType, ParamReallocHook,
    )
    from areal_tpu.api.system_api import (
        ExperimentConfig, ExperimentSaveEvalControl,
        GenerationServerConfig, GserverManagerConfig, MasterWorkerConfig,
        ModelShardSpec, ModelWorkerConfig, RolloutWorkerConfig,
    )

    actor = ModelName("actor", 0)
    train = MFCDef(
        name="actor_train",
        model_name=actor,
        interface_type=ModelInterfaceType.TRAIN_STEP,
        interface_impl=None,
        n_seqs=n_seqs,
        input_keys=(
            "packed_input_ids", "prompt_mask", "packed_logprobs",
            "rewards", "seq_no_eos_mask",
        ),
        post_hooks=[ParamReallocHook(source=str(actor))],
    )
    model_args = dict(config=model_cfg, tokenizer_path=tok_dir,
                      dtype=model_cfg.get("compute_dtype", "float32"))
    mw = ModelWorkerConfig(
        experiment_name=exp,
        trial_name=trial,
        worker_index=0,
        chips=[1],  # the generation server owns chip 0
        shards=[
            ModelShardSpec(
                id=ModelShardID(actor),
                model=ModelAbstraction("tpu_transformer", args=model_args),
                backend=ModelBackendAbstraction(
                    "jax_train",
                    args=dict(optimizer=dict(lr=1e-5), remat=remat,
                              row_len_multiple=8),
                ),
                interface=ModelInterfaceAbstraction(
                    "ppo_actor", args=dict(kl_ctl=0.0)
                ),
            )
        ],
        tokenizer_path=tok_dir,
        train_batch_size=n_seqs,
        total_train_epochs=1000,
        stream_dataset=True,
        n_pullers=1,
    )
    master = MasterWorkerConfig(
        experiment_name=exp,
        trial_name=trial,
        exp_ctrl=ExperimentSaveEvalControl(
            total_train_epochs=1000, benchmark_steps=steps
        ),
        rpcs=[train],
        model_topos={str(actor): ["model_worker/0"]},
        data_hosts=["model_worker/0"],
        n_model_workers=1,
        train_batch_size=n_seqs,
    )
    gen_server = GenerationServerConfig(
        experiment_name=exp,
        trial_name=trial,
        server_index=0,
        chips=[0],
        model=ModelAbstraction("tpu_transformer", args=model_args),
        tokenizer_path=tok_dir,
        max_concurrent_requests=max_concurrent_rollouts,
        max_seq_len=max_seq_len,
        decode_block_steps=4,
    )
    gserver_mgr = GserverManagerConfig(
        experiment_name=exp,
        trial_name=trial,
        model_name="actor",
        n_servers=1,
        train_batch_size=n_seqs,
        max_head_offpolicyness=offpolicyness,
    )
    rollout = RolloutWorkerConfig(
        experiment_name=exp,
        trial_name=trial,
        worker_index=0,
        n_rollout_workers=1,
        n_pullers=1,
        agent=AgentAbstraction(
            "math-single-step", args=dict(gconfig=gconfig)
        ),
        env=EnvServiceAbstraction("math-code-single-step"),
        datasets=[
            DatasetAbstraction("math_code_prompt",
                               args=dict(dataset_path=data_path))
        ],
        tokenizer_path=tok_dir,
        max_concurrent_rollouts=max_concurrent_rollouts,
    )
    return ExperimentConfig(
        experiment_name=exp,
        trial_name=trial,
        master=master,
        model_workers=[mw],
        rollout_workers=[rollout],
        gserver_manager=gserver_mgr,
        generation_servers=[gen_server],
    )


def _rate(perf_summary: dict, warmup: int):
    """Effective tokens/s over post-warmup steps (reference methodology:
    tokens / e2e seconds; warmup steps carry the XLA compiles). Returns
    (rate, tokens, secs, warmup_dropped): when the run is too short to
    drop warmup the FULL history is used and warmup_dropped is False —
    the report flags that the rate is compile-contaminated."""
    hist = perf_summary.get("history") or []
    dropped = len(hist) > warmup
    eff = hist[warmup:] if dropped else hist
    secs = sum(h[0] for h in eff)
    toks = sum(h[1] for h in eff)
    return (toks / secs if secs > 0 else 0.0), toks, secs, dropped


def run_one(cfg, *, workdir: str, warmup: int, worker_env: dict):
    from areal_tpu.system.controller import LocalController

    env = dict(worker_env)
    env["AREAL_FILEROOT"] = os.path.join(workdir, "fileroot")
    ctl = LocalController(
        cfg,
        name_resolve_cfg={
            "backend": "nfs",
            "record_root": os.path.join(workdir, "name_resolve"),
        },
        worker_env=env,
    )
    result = ctl.run()
    rate, toks, secs, warmup_dropped = _rate(result["perf_summary"], warmup)
    return dict(
        global_step=result["global_step"],
        tokens_per_s=rate,
        measured_tokens=toks,
        measured_secs=secs,
        warmup_dropped=warmup_dropped,
        perf_summary=result["perf_summary"],
    )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["tiny", "chip"], default="tiny")
    ap.add_argument("--steps", type=int, default=4,
                    help="train steps per experiment (incl. warmup)")
    ap.add_argument("--warmup-steps", type=int, default=1,
                    help="leading steps dropped from the rate (compiles)")
    ap.add_argument("--n-seqs", type=int, default=4,
                    help="train batch size in sequences")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--group-size", type=int, default=2,
                    help="samples per prompt (gconfig.n)")
    ap.add_argument("--offpolicyness", type=int, default=4,
                    help="async max_head_offpolicyness staleness gate")
    ap.add_argument("--tokenizer", default=None,
                    help="tokenizer dir (chip mode; tiny synthesizes one)")
    ap.add_argument("--dataset", default=None,
                    help="math jsonl path (chip mode; tiny synthesizes one)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)

    import tempfile

    workdir = args.workdir or tempfile.mkdtemp(prefix="async_speedup_")
    os.makedirs(workdir, exist_ok=True)

    # The master runs inline in THIS process and is control-plane only —
    # pin it to CPU so it can never take a chip from the workers (a chip
    # belongs to one process). Workers get their platform via worker_env.
    import jax

    jax.config.update("jax_platforms", "cpu")

    if args.mode == "tiny":
        model_cfg = TINY_CFG
        remat = False
        max_seq_len = 256
        tok_dir, data_path = _make_synthetic_workload(workdir)
        worker_env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": os.environ.get(
                "XLA_FLAGS", "--xla_force_host_platform_device_count=2"
            ),
        }
    else:
        if not (args.tokenizer and args.dataset):
            ap.error("--mode chip requires --tokenizer and --dataset")
        model_cfg = FLAGSHIP_CFG
        remat = "save_attn"
        max_seq_len = 4096
        tok_dir, data_path = args.tokenizer, args.dataset
        # Workers use the real device platform, each on its own chips
        # (the `chips` of its config, placed by the controller).
        worker_env = {"JAX_PLATFORMS": "tpu"}

    gconfig = dict(
        n=args.group_size, max_new_tokens=args.max_new_tokens,
        greedy=False, temperature=1.0,
    )
    shared = dict(
        model_cfg=model_cfg, tok_dir=tok_dir, data_path=data_path,
        n_seqs=args.n_seqs, steps=args.steps, gconfig=gconfig, remat=remat,
    )
    run_id = uuid.uuid4().hex[:6]

    sync_cfg = build_sync_cfg(
        exp=f"spdup-sync-{run_id}", trial="t0", **shared
    )
    sync = run_one(sync_cfg, workdir=os.path.join(workdir, "sync"),
                   warmup=args.warmup_steps, worker_env=worker_env)

    async_cfg = build_async_cfg(
        exp=f"spdup-async-{run_id}", trial="t0", **shared,
        max_seq_len=max_seq_len,
        max_concurrent_rollouts=max(8, 2 * args.n_seqs),
        offpolicyness=args.offpolicyness,
    )
    asy = run_one(async_cfg, workdir=os.path.join(workdir, "async"),
                  warmup=args.warmup_steps, worker_env=worker_env)

    speedup = (
        asy["tokens_per_s"] / sync["tokens_per_s"]
        if sync["tokens_per_s"] > 0 else 0.0
    )
    report = {
        "metric": "async_over_sync_speedup",
        "mode": args.mode,
        "sync_tokens_per_s": round(sync["tokens_per_s"], 2),
        "async_tokens_per_s": round(asy["tokens_per_s"], 2),
        "speedup": round(speedup, 3),
        "target": 2.5,
        "steps": args.steps,
        "warmup_steps": args.warmup_steps,
        # False = runs were too short to drop warmup; the rates include
        # XLA compile time and the ratio is not citable.
        "warmup_dropped": bool(
            sync["warmup_dropped"] and asy["warmup_dropped"]
        ),
        "n_seqs": args.n_seqs,
        "max_new_tokens": args.max_new_tokens,
        "sync_steps_done": sync["global_step"],
        "async_steps_done": asy["global_step"],
    }
    line = json.dumps(report)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return report


if __name__ == "__main__":
    main()
