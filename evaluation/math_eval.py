"""Offline math evaluation harness.

Counterpart of the reference's evaluation/math_eval.py: load a saved
checkpoint, greedy/sampled generation over a benchmark jsonl
(prompt + solutions rows), grade with the math verifier, write
results.json with pass@1-style accuracy. Invoked standalone or by the
AutomaticEvaluator per saved checkpoint.

Usage:
    python evaluation/math_eval.py ckpt=/save/actor/step10/dp0 \
        data=/data/aime24.jsonl benchmark=aime24 output=/tmp/results.json
    # benchmark= selects a preset (aime24/aime25/amc23/math500/gsm8k:
    # field mapping + prompt template + few-shot demos + sampling
    # defaults, evaluation/presets.py); prompt_type=/num_shots=/
    # n_samples=/max_new_tokens= override it. Without benchmark=, rows
    # are the repo's prompt/solutions schema taken verbatim.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def evaluate_checkpoint(
    ckpt: str,
    data: str,
    output: str = "",
    benchmark: str = "",
    prompt_type: str = "",
    num_shots: int = -1,
    max_new_tokens: int = 0,
    greedy: bool = True,
    # None = take the preset's (or 1.0); 0.0 is a VALID explicit value
    # (temperature-0 sampling), not a sentinel.
    temperature: Optional[float] = None,
    n_samples: int = 0,
    max_prompts: int = 0,
    seed: int = 1,
    answer_mode: str = "text",
) -> dict:
    """benchmark= selects a preset (aime24/aime25/amc23/math500/gsm8k,
    see evaluation/presets.py) carrying the field mapping, prompt
    template, few-shot count, and sampling defaults; prompt_type=,
    num_shots=, max_new_tokens=, n_samples= override it. Without
    benchmark=, rows use the repo's prompt/solutions schema with the
    prompt taken verbatim (the pre-round-5 behavior).

    answer_mode='text' extracts the answer from the generated text
    (boxed / "answer is" / last number); answer_mode='python' executes
    the generated program in a sandboxed subprocess and grades its
    output (PAL style; pairs with prompt_type='pal')."""
    import jax

    from areal_tpu.api import data_api
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.functioncall.math_grader import (
        extract_answer,
        grade_answer,
        normalize_answer,
    )
    from areal_tpu.models.generation import generate_tokens
    from areal_tpu.models.hf import load_hf_model

    from evaluation.presets import (
        BENCHMARKS, PROMPT_TEMPLATES, build_prompt, load_benchmark,
    )

    # Validate EVERYTHING and build the prompt rows BEFORE the (multi-GB)
    # checkpoint load: a typo'd benchmark/prompt_type or an over-asked
    # num_shots should fail instantly, not after minutes of loading.
    if benchmark and benchmark not in BENCHMARKS:
        raise ValueError(
            f"unknown benchmark {benchmark!r}; available: "
            f"{sorted(BENCHMARKS)}"
        )
    if answer_mode not in ("text", "python"):
        raise ValueError(
            f"answer_mode must be 'text' or 'python', got {answer_mode!r}"
        )
    preset = BENCHMARKS[benchmark] if benchmark else None
    if preset is not None:
        # Explicit args override the preset's defaults.
        prompt_type = prompt_type or preset.prompt_type
        num_shots = preset.num_shots if num_shots < 0 else num_shots
        max_new_tokens = max_new_tokens or preset.max_new_tokens
        n_samples = n_samples or preset.n_samples
        if temperature is None:
            temperature = preset.temperature
        if n_samples > 1:
            greedy = False  # pass@k/maj@k need sample diversity
        if prompt_type not in PROMPT_TEMPLATES:
            raise ValueError(
                f"unknown prompt_type {prompt_type!r}; available: "
                f"{sorted(PROMPT_TEMPLATES)}"
            )
        # (num_shots bounds are enforced by build_prompt below, which
        # also runs before the checkpoint load.)
        bench_rows = load_benchmark(data, preset)
        if max_prompts:
            bench_rows = bench_rows[:max_prompts]
        rows = [
            # gt may already be a list (e.g. a 'solutions' field):
            # wrapping it again would make grade_answer compare against
            # the list's repr and score everything wrong.
            {"query_id": r["query_id"],
             "solutions": (r["gt"] if isinstance(r["gt"], (list, tuple))
                           else [r["gt"]]),
             "prompt": build_prompt(r["question"], prompt_type, num_shots)}
            for r in bench_rows
        ]
    else:
        # No preset = prompts taken verbatim; prompt args would be
        # silently ignored, so refuse them rather than record a
        # methodology that never ran.
        if prompt_type or num_shots >= 0:
            raise ValueError(
                "prompt_type=/num_shots= require benchmark=<preset>; "
                "without one, prompts are used verbatim (the 'generic' "
                "preset wraps prompt/solutions rows in the boxed "
                "template)"
            )
        max_new_tokens = max_new_tokens or 512
        n_samples = n_samples or 1
        if temperature is None:
            temperature = 1.0
        with open(data) as f:
            rows = [json.loads(l) for l in f if l.strip()]
        if max_prompts:
            rows = rows[:max_prompts]

    cfg, params = load_hf_model(ckpt)
    tokenizer = data_api.load_hf_tokenizer(ckpt)

    g = GenerationHyperparameters(
        max_new_tokens=max_new_tokens, greedy=greedy, temperature=temperature
    )
    prompts = [tokenizer(r["prompt"])["input_ids"] for r in rows]

    n_correct, per_prompt = 0, []
    # Per-prompt sample records for multi-sample metrics (pass@k +
    # majority vote, reference evaluation/rm_maj_eval.py).
    by_prompt: dict = {}
    batch = 8
    for s in range(n_samples):
        rng = jax.random.PRNGKey(seed + s)
        for i in range(0, len(prompts), batch):
            chunk = prompts[i : i + batch]
            outs = generate_tokens(
                params, cfg, chunk, g, jax.random.fold_in(rng, i),
                eos_token_id=tokenizer.eos_token_id,
            )
            texts = [tokenizer.decode(o["output_ids"]) for o in outs]
            if answer_mode == "python":
                # PAL: run each generated program ONCE in its sandbox
                # subprocess; the executed output is graded AND is the
                # vote for maj@k. Candidates run concurrently — each
                # non-terminating program burns its full timeout, and
                # serializing those would dominate eval wall-clock.
                from concurrent.futures import ThreadPoolExecutor

                from areal_tpu.functioncall.python_answer import (
                    compare_python_answer,
                    execute_python_answer,
                )

                with ThreadPoolExecutor(max_workers=len(texts)) as pool:
                    answers = list(pool.map(execute_python_answer, texts))
            else:
                answers = [None] * len(texts)
            for j, text in enumerate(texts):
                row = rows[i + j]
                refs = row.get("solutions") or row.get("answers")
                if answer_mode == "python":
                    ans = answers[j]
                    ok = compare_python_answer(ans, refs)
                else:
                    ok = grade_answer(text, refs)
                    ans = extract_answer(text)
                n_correct += bool(ok)
                qid = str(row.get("query_id", i + j))
                per_prompt.append({"query_id": qid, "correct": bool(ok)})
                by_prompt.setdefault(qid, []).append(
                    (normalize_answer(ans) if ans else None, bool(ok))
                )

    total = len(prompts) * n_samples
    result = {
        "ckpt": ckpt,
        "data": data,
        "benchmark": benchmark or "none",
        "prompt_type": prompt_type or "verbatim",
        "answer_mode": answer_mode,
        "num_shots": max(0, num_shots),
        "n_prompts": len(prompts),
        "n_samples": n_samples,
        "accuracy": n_correct / max(1, total),
        "details": per_prompt,
    }
    if n_samples > 1:
        # pass@k: any sample correct; maj@k: the most common extracted
        # answer is correct (unextractable answers never win the vote).
        from collections import Counter

        pass_k = maj_k = 0
        for samples in by_prompt.values():
            pass_k += any(ok for _, ok in samples)
            counts = Counter(a for a, _ in samples if a is not None)
            if counts:
                top_ans, _ = counts.most_common(1)[0]
                maj_k += any(ok for a, ok in samples if a == top_ans)
        result["pass_at_k"] = pass_k / max(1, len(by_prompt))
        result["maj_at_k"] = maj_k / max(1, len(by_prompt))
    if output:
        os.makedirs(os.path.dirname(output) or ".", exist_ok=True)
        with open(output, "w") as f:
            json.dump(result, f)
    print(json.dumps({k: v for k, v in result.items() if k != "details"}))
    return result


if __name__ == "__main__":
    kwargs = {}
    for arg in sys.argv[1:]:
        k, v = arg.split("=", 1)
        if k in ("max_new_tokens", "n_samples", "max_prompts", "seed",
                 "num_shots"):
            v = int(v)
        elif k in ("greedy",):
            v = v.lower() in ("1", "true")
        elif k in ("temperature",):
            v = float(v)
        kwargs[k] = v
    evaluate_checkpoint(**kwargs)
