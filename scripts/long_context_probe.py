"""Long-context on-chip probe (VERDICT r3 missing #4 + #6).

Measures, on the real TPU chip, with the bench flagship shape
(R1-Distill-Qwen-1.5B layers, bench.py):

  A. packed train step at 16k and 32k tokens (remat=save_attn) -> TFLOP/s
     (the reference's headline workload trains 27-32k packed tokens,
     benchmark/verl_v0_3_0_post1_76084d3/README.md:38-44)
  B. >=16k-token generation through the paged engine with chunked
     prefill: prefill seconds + sustained decode tok/s, and the
     prefix-cache resubmission delta (chunk boundary cost with/without
     KV reuse)
  C. decode sampling sort-skip A/B: block time with all-greedy requests
     (sort skipped) vs top-k/top-p active (full-vocab sort) — replaces
     the "expected ~15%" estimate in docs/perf_notes.md with a measured
     number.

Prints one JSON line per measurement to stdout; human detail on stderr.
Timing fetches a result per step, so it waits for execution.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import count_params, init_params


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(**kw):
    print(json.dumps(kw), flush=True)


def flagship_cfg(max_pos=40960):
    if os.environ.get("AREAL_PROBE_TINY"):
        # Harness-validation shape (CI / virtual CPU mesh): same head
        # divisibility structure as the flagship (hq/hkv divide seq*tp
        # meshes the same way), tiny everything else.
        return TransformerConfig(
            n_layers=2, hidden_dim=128, n_q_heads=12, n_kv_heads=2,
            head_dim=16, intermediate_dim=256, vocab_size=512,
            compute_dtype="float32", param_dtype="float32",
            max_position_embeddings=max_pos,
        )
    from bench import flagship_cfg as bench_flagship

    return bench_flagship(max_pos=max_pos)


from bench import train_step_flops  # shared formula with bench.py  # noqa: E402


def probe_train(seq_tokens: int, remat: str = "save_attn"):
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.ops.loss import sft_loss_from_logprobs

    cfg = flagship_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = count_params(params)
    eng = JaxTrainEngine(
        cfg, params,
        optimizer_config=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        total_train_steps=1000,
        row_len_multiple=seq_tokens, max_row_len=seq_tokens,
        remat=remat,
    )
    rng = np.random.RandomState(0)
    batch = SequenceSample.from_default(
        ids=["b0"],
        seqlens=[seq_tokens],
        data={
            "packed_input_ids": rng.randint(0, cfg.vocab_size, size=seq_tokens),
            "loss_mask": np.ones(seq_tokens, np.float32),
        },
    )

    def packed_loss(lp, rows):
        tot, _ = sft_loss_from_logprobs(lp, rows["loss_mask"])
        return tot, {}

    def weight(mb):
        return float(np.sum(mb.data["loss_mask"]))

    def one(i):
        st = eng.train_batch(batch, MicroBatchSpec(n_mbs=1), packed_loss,
                             weight, version_steps=i, loss_name="lc")
        return st

    for i in range(2):
        t = time.perf_counter()
        one(i)
        log(f"train {seq_tokens}: warmup {i} {time.perf_counter()-t:.2f}s")
    n = 3
    t0 = time.perf_counter()
    for i in range(n):
        one(2 + i)
    # engine stats fetch inside train_batch forces the sync
    dt = (time.perf_counter() - t0) / n
    tflops = train_step_flops(cfg, n_params, [seq_tokens]) / dt / 1e12
    emit(metric=f"train_{seq_tokens//1024}k_tflops_per_chip",
         value=round(tflops, 2), unit="TFLOP/s",
         step_s=round(dt, 3), remat=remat)
    log(f"train {seq_tokens}: {dt:.3f}s/step {tflops:.1f} TFLOP/s")
    del eng
    import gc

    gc.collect()


def probe_gen(plen=16384, max_new=512):
    import threading

    from areal_tpu.engine.serving import GenRequest, ServingEngine

    cfg = flagship_cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = ServingEngine(
        cfg, params,
        max_batch_size=4,
        max_seq_len=plen + 2 * max_new + 256,
        decode_block_steps=32,
        prompt_bucket=256,
        eos_token_id=None,
        page_size=128,
        kv_pool_tokens=2 * (plen + 2 * max_new + 256),
        prefill_chunk=2048,
        prefix_cache_tokens=2 * (plen + max_new),
    )
    eng.start()
    rng = np.random.RandomState(1)

    def run_one(qid, ids, new):
        done = threading.Event()
        holder = {}

        def cb(res):
            holder["r"] = res
            done.set()

        t0 = time.perf_counter()
        # AREAL_PROBE_GREEDY=1: greedy decode — the regime where the
        # speculative A/B (AREAL_SPEC_DRAFT) is meaningful; sampled-at-
        # temp-1 acceptance of point-mass drafts is ~p(t) per token.
        eng.submit(GenRequest(qid=qid, input_ids=list(ids),
                              max_new_tokens=new, done_cb=cb,
                              greedy=os.environ.get(
                                  "AREAL_PROBE_GREEDY", "0"
                              ) not in ("", "0", "false")))
        assert done.wait(1800)
        res = holder["r"]
        if res.error is not None:
            # Engine crash delivered via _fail_all: surface it as a
            # phase failure, never as a 0.0 tok/s "measurement".
            raise RuntimeError(f"gen engine died: {res.error}")
        return res, time.perf_counter() - t0

    prompt = rng.randint(0, cfg.vocab_size, size=plen).tolist()
    # warmup compiles (chunk prefill + decode block)
    run_one("w", prompt[:4096], 2 * 32)
    r1, dt1 = run_one("lc/0", prompt, max_new)
    tps = len(r1.output_ids) / dt1
    emit(metric="gen_16k_tokens_per_sec", value=round(tps, 1),
         unit="tok/s", total_s=round(dt1, 2), new_tokens=len(r1.output_ids))
    log(f"gen 16k: {dt1:.2f}s for {len(r1.output_ids)} tokens -> {tps:.1f} tok/s")

    # prefix-cache resubmission (partial-rollout chunk boundary): delta
    # prefill only vs the cold full-prefix cost above.
    r2, dt2 = run_one("lc/0", prompt + r1.output_ids, max_new)
    emit(metric="gen_16k_resubmit_s", value=round(dt2, 2), unit="s",
         cold_s=round(dt1, 2),
         prefix_cache_hits=eng.prefix_cache_hits,
         prefix_tokens_reused=eng.prefix_tokens_reused)
    log(f"gen 16k resubmit: {dt2:.2f}s (cold {dt1:.2f}s), "
        f"hits={eng.prefix_cache_hits} reused={eng.prefix_tokens_reused}")
    if eng.spec_draft_len > 0:
        # The decision signal for AREAL_SPEC_DRAFT: realized tokens per
        # active decode step (1.0 = speculation added nothing).
        y = eng.metrics()["spec_tokens_per_step"]
        emit(metric="gen_spec_tokens_per_step", value=round(y, 3),
             draft_len=eng.spec_draft_len)
        log(f"spec yield: {y:.3f} tokens/step (draft {eng.spec_draft_len})")
    eng.stop()


def probe_dense_gen(B=32, plen=512, new=512):
    """Dense-decode anchor (VERDICT r4 weak #5): the in-mesh batch
    generator (models/generation.generate_tokens — dense [B, S] cache,
    whole batch in lockstep, the sync-PPO path) on the SAME shape as
    bench.py's short gen phase, so the paged engine's banked tok/s has
    an on-chip dense comparison instead of standing alone."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.models.generation import generate_tokens

    cfg = flagship_cfg(max_pos=4096)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, size=plen).tolist()
               for _ in range(B)]
    g = GenerationHyperparameters(
        max_new_tokens=new, greedy=False, temperature=1.0,
    )
    # Full-shape warmup: _prefill_jit/_decode_loop are shape-specialized,
    # so anything smaller leaves the real compiles inside the timed pass
    # (same trap probe_sort_skip documents).
    generate_tokens(params, cfg, prompts, g, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    outs = generate_tokens(params, cfg, prompts, g, jax.random.PRNGKey(1))
    toks = sum(len(o["output_ids"]) for o in outs)
    dt = time.perf_counter() - t0
    emit(metric="dense_gen_tokens_per_sec", value=round(toks / dt, 1),
         unit="tok/s", B=B, plen=plen, new=new, total_s=round(dt, 2))
    log(f"dense gen: {toks} tokens in {dt:.2f}s -> {toks/dt:.0f} tok/s "
        f"(paged-engine comparison: bench.py gen phase, same shape)")


def probe_sort_skip(B=32, plen=512, new=256):
    """Decode block throughput: greedy-only (sampling sort skipped) vs
    top-k/top-p active (full-vocab sort per step)."""
    import threading

    from areal_tpu.engine.serving import GenRequest, ServingEngine

    cfg = flagship_cfg(max_pos=4096)
    params = init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(2)

    def run(label, **sample_kw):
        eng = ServingEngine(
            cfg, params,
            max_batch_size=B,
            max_seq_len=plen + new + 128,
            decode_block_steps=32,
            prompt_bucket=128,
            eos_token_id=None,
            page_size=128,
            kv_pool_tokens=B * (plen + new + 128),
        )
        eng.start()

        def one_pass(tag):
            ev = threading.Event()
            results = []

            def cb(res):
                results.append(res)
                if len(results) == B:
                    ev.set()

            t0 = time.perf_counter()
            for i in range(B):
                eng.submit(GenRequest(
                    qid=f"{tag}{i}",
                    input_ids=rng.randint(
                        0, cfg.vocab_size, size=plen).tolist(),
                    max_new_tokens=new,
                    done_cb=cb, **sample_kw))
            assert ev.wait(1800)
            errs = [r.error for r in results if r.error is not None]
            if errs:
                raise RuntimeError(f"gen engine died: {errs[0]}")
            dt = time.perf_counter() - t0
            return sum(len(r.output_ids) for r in results), dt

        # Full-shape warmup pass: the FIRST engine in the process pays
        # every batched-prefill/admit compile the second gets from the
        # in-process jit cache — a single-request warmup left ~18 s of
        # compile inside the first timed pass (measured: greedy "0.16x").
        one_pass("w")
        toks, dt = one_pass(label)
        eng.stop()
        return toks / dt

    tps_greedy = run("g", greedy=True)
    tps_sorted = run("s", top_k=50, top_p=0.95, temperature=1.0)
    emit(metric="decode_sort_skip_ab",
         greedy_tok_s=round(tps_greedy, 1),
         topk_topp_tok_s=round(tps_sorted, 1),
         speedup=round(tps_greedy / tps_sorted, 3))
    log(f"sort-skip A/B: greedy {tps_greedy:.0f} tok/s vs "
        f"top-k/p {tps_sorted:.0f} tok/s "
        f"({tps_greedy / tps_sorted:.2f}x)")


def probe_cp(seq_tokens: int, mesh_spec: str):
    """Ring vs Ulysses vs seq-sharded-reference A/B at one context length
    (VERDICT r4 next-round #4): the SAME packed forward+backward on the
    SAME seq>1 mesh under each attn_impl, timed per step. Needs more
    than one device (real ICI for meaningful numbers; runs on the
    virtual CPU mesh too, but only to validate the harness). The winner
    should be wired as the 'auto' default in ops/attention.py
    resolve_cp_impl — today's default (Ulysses when heads divide) is
    analytic, pending this measurement."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh
    from areal_tpu.parallel.sharding import batch_sharding, shard_params

    spec = MeshSpec.parse(mesh_spec)
    if spec.size > len(jax.devices()):
        log(f"cp {mesh_spec}: needs {spec.size} devices, "
            f"have {len(jax.devices())} — skipping")
        emit(metric=f"cp_ab_{seq_tokens//1024}k", mesh=mesh_spec,
             step_seconds={"error": "not enough devices"})
        return
    mesh = make_mesh(spec, devices=jax.devices()[: spec.size])
    cfg = flagship_cfg()
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), mesh)
    n_params = count_params(params)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(1, seq_tokens)).astype(np.int32)
    seg = np.ones((1, seq_tokens), np.int32)
    pos = np.arange(seq_tokens, dtype=np.int32)[None, :]
    sh = batch_sharding(mesh)
    ids, seg, pos = (jax.device_put(a, sh) for a in (ids, seg, pos))

    from areal_tpu.models.transformer import forward as model_forward

    results = {}
    for impl in ("reference", "ring", "ulysses"):
        def loss(p):
            h = model_forward(
                p, cfg, ids, seg, pos, attn_impl=impl, remat="full",
                output="hidden", mesh=mesh,
            )
            return jnp.sum(h.astype(jnp.float32) ** 2)

        try:
            step = jax.jit(jax.value_and_grad(loss))
            t = time.perf_counter()
            v, g = step(params)
            float(v)  # wait for the result
            compile_s = time.perf_counter() - t
            n, t0 = 3, time.perf_counter()
            for _ in range(n):
                v, g = step(params)
                float(v)
            dt = (time.perf_counter() - t0) / n
            tflops = train_step_flops(cfg, n_params, [seq_tokens]) / dt / 1e12
            results[impl] = round(dt, 3)
            log(f"cp {impl} @{seq_tokens}: {dt:.3f}s/fwdbwd "
                f"{tflops:.1f} TFLOP/s (compile {compile_s:.1f}s)")
        except Exception as e:  # shape/mesh mismatch: record and move on
            results[impl] = f"error: {type(e).__name__}"
            log(f"cp {impl} @{seq_tokens}: {e}")
    emit(metric=f"cp_ab_{seq_tokens//1024}k", mesh=mesh_spec,
         step_seconds=results)


def main():
    platform = jax.devices()[0].platform
    log(f"platform={platform} n_devices={len(jax.devices())}")
    if platform != "tpu":
        log("WARNING: not on TPU; numbers are not meaningful")
    which = sys.argv[1] if len(sys.argv) > 1 else "all"

    def guarded(name, fn, *a, **kw):
        """One phase OOMing (32k on a 16 GB v5e) must not cost the rest
        of the run its banked numbers."""
        try:
            fn(*a, **kw)
        except Exception as e:
            log(f"{name}: FAILED {type(e).__name__}: {e}")
            emit(metric=name, error=f"{type(e).__name__}: {e}"[:200])
            # The failed phase's engine/optimizer buffers sit in
            # reference cycles; reclaim their HBM before the next phase
            # compiles, or the OOM cascades into it.
            import gc

            gc.collect()

    if which in ("all", "train16k"):
        guarded("train16k", probe_train, 16384)
    if which in ("all", "train32k"):
        # save_attn at 32k does not fit one v5e (16 GB) next to fp32 Adam
        # state; full remat trades ~30% step time for the activation HBM.
        remat = sys.argv[2] if which == "train32k" and len(sys.argv) > 2 \
            else "full"
        guarded("train32k", probe_train, 32768, remat=remat)
    if (which.startswith("train")
            and which not in ("train16k", "train32k")
            and which[len("train"):].isdigit()):
        # e.g. `train24576 full` — largest-context search on one chip.
        toks = int(which[len("train"):])
        remat = sys.argv[2] if len(sys.argv) > 2 else "full"
        guarded(which, probe_train, toks, remat=remat)
    if which in ("all", "gen"):
        guarded("gen16k", probe_gen)
    if which in ("all", "sortskip"):
        guarded("sortskip", probe_sort_skip)
    if which in ("all", "densegen"):
        guarded("densegen", probe_dense_gen)
    if which == "cp":
        # Needs a multi-device allotment: run e.g.
        #   python scripts/long_context_probe.py cp d1f1s2t1,d1f1s4t1 16384
        # The default sweeps BOTH a seq=2 and a seq=4 mesh: the flagship's
        # 2 KV heads divide only seq=2, so the Ulysses arm exists only
        # there — a single s4 run would silently yield ring-vs-reference.
        # (CPU harness check: AREAL_PROBE_TINY=1
        #  XLA_FLAGS=--xla_force_host_platform_device_count=4
        #  JAX_PLATFORMS=cpu python scripts/long_context_probe.py cp
        #  d1f1s2t1 512)
        mesh_specs = (
            sys.argv[2] if len(sys.argv) > 2 else "d1f1s2t1,d1f1s4t1"
        ).split(",")
        seq_tokens = int(sys.argv[3]) if len(sys.argv) > 3 else 16384
        for spec in mesh_specs:
            probe_cp(seq_tokens, spec)


if __name__ == "__main__":
    main()
