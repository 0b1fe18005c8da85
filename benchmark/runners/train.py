"""Runner `train`: the PPO actor step, in this process.

`PPOActorInterface.train_step` on a `JaxTrainEngine`, built as
`tests/interfaces/test_ppo_interface.py::make_actor` builds it, fed the
`SequenceSample`s `benchmark/traffic.py` generates. Weights are made on
the device from the seed in one jitted call. Set-up computes the
behaviour logprobs with the engine's own forward, compares a few
sequences with the plain reference, and runs one step over every batch
of the pool (each shape the window uses is then compiled). The window
runs whole passes over the pool in the seed's order, back to back, each
step ending in `block_until_ready(params)`. With `--trace 2` the window
runs exactly as with `--trace 0`; once it has closed and every number is
taken, one more pass over the pool is traced (`_traced_pass`).
"""

from __future__ import annotations

import json
import math
import os
import time
import traceback
from typing import Any, Dict, List

import numpy as np

from benchmark import common, manifest, model, traffic


def _mesh(spec):
    if not spec:
        return None
    import jax

    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh

    ms = MeshSpec(**{k: int(v) for k, v in spec.items()})
    return make_mesh(ms, jax.devices()[: ms.size])


def _sample(b: Dict[str, Any], extra: Dict[str, np.ndarray]):
    from areal_tpu.api.data_api import SequenceSample

    n = len(b["ids"])
    data = dict(packed_input_ids=b["packed_input_ids"],
                prompt_mask=b["prompt_mask"], rewards=b["rewards"],
                seq_no_eos_mask=b["seq_no_eos_mask"], **extra)
    return SequenceSample.from_default(
        ids=b["ids"], seqlens=b["seqlens"], data=data,
        metadata={"version_start": [0] * n, "version_end": [0] * n})


def _scoring_mask(b) -> np.ndarray:
    """1 where position t scores a response token (t+1): per sequence the
    positions prompt_len-1 .. len-2, the frame `interfaces/ppo.py` uses."""
    m = np.zeros(b["n_tokens"], np.float32)
    off = 0
    for l, pl in zip(b["seqlens"], b["prompt_lens"]):
        m[off + pl - 1: off + l - 1] = 1.0
        off += l
    return m


def _traced_pass(ctx, step, pool, extras, first_step: int):
    """`--trace 2`, after the measured window has closed: trace one pass
    over the pool in the seed's order (the pass `--trace 1` traces inside
    the window). Its rows go to `traced_steps.jsonl` and enter no
    end-to-end value. No throw-away profiler session comes first: on the
    chip a process's first start costs what its second does, and both
    fall before the traced window opens (PERF.md section 6, PR 26)."""
    log = ctx["log"]
    tracer = common.TracedWindow(ctx["out_dir"], True)
    began = time.monotonic()
    tracer.start()
    rows = [step(first_step + i, b, extra)
            for i, (b, extra) in enumerate(zip(pool, extras))]
    tracer.stop()
    ended = time.monotonic()
    built = ctx["compiles"].between(began, ended)
    log(f"traced pass: {len(rows)} steps in {rows[-1]['end'] - rows[0]['start']:.3f}s"
        f" ({ended - began:.3f}s with the profiler's start and stop), "
        f"{len(built)} program(s) built {sorted(set(built))}")
    with open(os.path.join(ctx["out_dir"], "traced_steps.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    with open(os.path.join(ctx["out_dir"], "program.json"), "w") as f:
        json.dump(tracer.program, f, default=str)
    trace = tracer.reduce()
    log(f"trace reduced {time.monotonic() - ended:.1f}s after the traced pass")
    return tracer.program, trace, common.peak_memory(ctx["chips"])  # of the whole run


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from areal_tpu.api.config import ModelName
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.api.model_api import Model
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from areal_tpu.models.transformer import init_params

    log, cell, hf, p = ctx["log"], ctx["cell"], ctx["hf"], ctx["traffic"]
    rehearsal, seed = ctx["rehearsal"], ctx["seed"]
    eng = manifest.section(cell, "engine", rehearsal)
    dtype = "float32" if rehearsal else cell["config_file"]["benchmark"]["dtype"]
    cfg = model.transformer_config(hf, dtype)
    spans, compiles = common.Spans(), ctx["compiles"]
    problems: List[str] = []

    # Weights on the device, from the seed, in one jitted call.
    key = jax.random.PRNGKey(traffic.fold_seed(seed))
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    engine = JaxTrainEngine(
        cfg, params, mesh=_mesh(eng.get("mesh")),
        optimizer_config=OptimizerConfig(**manifest.section(cell, "optimizer", rehearsal)),
        total_train_steps=int(eng.get("total_train_steps", 1000)),
        attn_impl=eng.get("attn_impl", "auto"), remat=eng.get("remat", "full"),
        row_len_multiple=int(eng["row_len_multiple"]),
        max_row_len=eng.get("max_row_len"),
        prefetch_depth=int(eng.get("prefetch_depth", 2)),
        stats_fetch_interval=int(eng.get("stats_fetch_interval", 1)),
        hf_family=hf["model_type"],
    )
    del params
    actor = Model(name=ModelName("actor"), module=engine, tokenizer=None)
    ppo = p["ppo"]
    itf = PPOActorInterface(n_minibatches=int(ppo["n_minibatches"]))
    mb_spec = MicroBatchSpec(max_tokens_per_mb=int(ppo["max_tokens_per_mb"]))
    log(f"engine: {cfg.n_layers} layers, settings {json.dumps(eng)}, "
        f"ppo {json.dumps(ppo)}")

    # The engine's method, timed from outside: no edit to the program.
    inner = engine.train_batch

    def timed_train_batch(*a, **k):
        with spans.span("train_batch"):
            return inner(*a, **k)

    engine.train_batch = timed_train_batch

    # The pool: behaviour and reference logprobs are the engine's own at
    # its initial weights, plus the traffic's noise.
    pool = traffic.ppo_batches(p, seed, cfg.vocab_size)
    lens_all = [l for b in pool for l in b["seqlens"]]
    log("drawn sequence lengths: " + json.dumps(traffic.length_histogram(lens_all)))
    log("batches in this seed's order: " + json.dumps(
        [dict(batch=b["batch"], sequences=len(b["ids"]), tokens=b["n_tokens"])
         for b in pool]))
    extras, raw0 = [], None
    for i, b in enumerate(pool):
        lp = np.asarray(engine.forward(_sample(b, {}), mb_spec).data["logprobs"],
                        np.float32)
        if i == 0:
            raw0 = lp
        mask = _scoring_mask(b)
        extras.append(dict(
            packed_logprobs=((lp + b["noise_behav"]) * mask).astype(np.float32),
            ref_logprobs=((lp + b["noise_ref"]) * mask).astype(np.float32)))

    # correct (1): the engine's logprobs against the plain reference,
    # outside the window: sequences of the first batch spread evenly from
    # its shortest to its longest, each up to `max_positions`.
    chk = p["check"]
    b0 = pool[0]
    offs = np.concatenate([[0], np.cumsum(b0["seqlens"])])
    by_len = np.argsort(b0["seqlens"], kind="stable")
    picks = np.linspace(0, len(by_len) - 1, int(chk["sequences"])).round().astype(int)
    samples = []
    for j in by_len[np.unique(picks)]:
        n = min(int(b0["seqlens"][j]), int(chk["max_positions"]))
        o = int(offs[j])
        samples.append(dict(name=b0["ids"][j], first=0,
                            token_ids=b0["packed_input_ids"][o: o + n],
                            got=raw0[o: o + n - 1]))
    ref = model.compare_with_reference(
        engine.params, hf, cell["config_file"]["benchmark"]["reference"],
        samples, cell["logprob_tolerance"], int(chk["max_positions"]))
    log("reference check: " + json.dumps(ref))
    if not ref["ok"]:
        problems.append(f"logprobs differ from the reference by {ref['worst']:.4f} "
                        f"at worst, {ref['worst_mean']:.4f} on a sequence's mean")

    def step(i: int, b, extra) -> Dict[str, Any]:
        with spans.span("train_step", step=i, batch=b["batch"],
                        tokens=b["n_tokens"]) as row:
            stats = itf.train_step(actor, _sample(b, extra), mb_spec)
            jax.block_until_ready(engine.params)
        row["stats"] = {k.split("/")[-1]: float(v) for k, v in stats.items()
                        if k.endswith(("/loss", "/grad_norm", "/update_norm"))}
        s = row["stats"]
        row["ok"] = bool(all(math.isfinite(v) for v in s.values())
                         and s.get("update_norm", 0.0) > 0.0)
        return row

    # Warm every shape the window uses: one step over each batch, in the
    # drawn order whatever the seed. The program annotates its parameters'
    # shardings differently before and after their first update, so a
    # batch's programs differ by whether it came first: a fixed warm-up
    # order makes every seed ask the compile cache for the same programs.
    for i in sorted(range(len(pool)), key=lambda i: pool[i]["batch"]):
        row = step(-1 - i, pool[i], extras[i])
        log(f"warm step on batch {pool[i]['batch']}: "
            f"{row['end'] - row['start']:.3f}s {json.dumps(row['stats'])}")
    spans.rows.clear()

    tracer = common.TracedWindow(ctx["out_dir"], ctx["trace"])
    tracer.start()
    t0 = time.monotonic()
    setup_s = t0 - ctx["t_start"]
    # Whole passes over the pool until `seconds` have gone by: every pass
    # is the same work whatever the order, so the rate does not hang on
    # which batches a cut-off pass would have held. `--trace 1` traces the
    # first pass.
    rows = []
    while not rows or time.monotonic() - t0 < ctx["seconds"]:
        for b, extra in zip(pool, extras):
            rows.append(step(len(rows), b, extra))
        tracer.stop()
    t1 = time.monotonic()

    in_window = compiles.between(t0, t1)
    if in_window:
        problems.append(f"{len(in_window)} program(s) built inside the "
                        f"window: {sorted(set(in_window))}")
    bad = [r["step"] for r in rows if not r["ok"]]
    if bad:
        problems.append(f"steps {bad} had a non-finite loss or gradient norm, "
                        "or an update of norm 0")
    with open(os.path.join(ctx["out_dir"], "steps.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
            log(f"step {r['step']} (batch {r['batch']}): {r['end'] - r['start']:.4f}s "
                f"{r['tokens']} tokens {json.dumps(r['stats'])}")

    elapsed = rows[-1]["end"] - rows[0]["start"]
    tokens = float(sum(r["tokens"] for r in rows))
    sum_sq = float(len(rows) // len(pool)
                   * sum(l * l for b in pool for l in b["seqlens"]))
    memory = common.peak_memory(ctx["chips"])
    window_spans = list(spans.rows)
    trace = tracer.reduce()
    end_to_end = dict(setup_s=setup_s, train_tokens_per_s=tokens / elapsed)
    program = tracer.program
    # Everything above is the measured window's; nothing of the profiler
    # or the program's tracing existed until here in modes 0 and 2. What
    # follows can only add per-layer metrics: if it fails, the window's
    # numbers and its `correct` still go out, the failure beside them.
    trace_problems: List[str] = []
    if ctx.get("trace_after"):
        log(f"measured window closed: {json.dumps(end_to_end)}")
        try:
            program, trace, memory = _traced_pass(ctx, step, pool, extras, len(rows))
        except Exception as e:
            log("the traced pass failed:\n" + traceback.format_exc())
            trace_problems.append(f"the traced pass failed: {type(e).__name__}: {e}")
    return dict(
        problems=problems, trace_problems=trace_problems,
        attempted=len(rows), failed=len(bad),
        end_to_end=end_to_end,
        evidence=dict(
            spans=window_spans, trace=trace, memory=memory,
            work=dict(tokens=tokens, sum_len_sq=sum_sq, elapsed_s=elapsed),
            program=program,
        ),
        counts=dict(steps=len(rows), tokens=tokens, compiles_in_window=len(in_window)),
    )
