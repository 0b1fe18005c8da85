"""Offline code evaluation harness.

Counterpart of the reference's evaluation/code_eval.py (548 LoC around a
vLLM generate + code_verifier.local_verify pipeline): load a saved
checkpoint, generate solutions over a benchmark jsonl of coding problems,
extract the final code block, run it against the per-problem test cases in
the sandboxed verifier (areal_tpu/functioncall/code_verify.py), and write
results.json with pass@1-style accuracy.

jsonl rows: {"prompt", "query_id", "input_output": {"inputs", "outputs",
"fn_name"?}} — the math_code_prompt dataset's code-task schema.

Usage:
    python evaluation/code_eval.py ckpt=/save/actor/step10/dp0 \
        data=/data/lcb.jsonl output=/tmp/results.json max_new_tokens=1024
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def evaluate_checkpoint(
    ckpt: str,
    data: str,
    output: str = "",
    max_new_tokens: int = 1024,
    greedy: bool = True,
    temperature: float = 1.0,
    n_samples: int = 1,
    max_prompts: int = 0,
    case_timeout: float = 6.0,
    max_cases: int = 0,
    seed: int = 1,
) -> dict:
    import jax

    from areal_tpu.api import data_api
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.functioncall.code_verify import code_verify
    from areal_tpu.models.generation import generate_tokens
    from areal_tpu.models.hf import load_hf_model

    cfg, params = load_hf_model(ckpt)
    tokenizer = data_api.load_hf_tokenizer(ckpt)

    with open(data) as f:
        rows = [json.loads(l) for l in f if l.strip()]
    if max_prompts:
        rows = rows[:max_prompts]

    g = GenerationHyperparameters(
        max_new_tokens=max_new_tokens, greedy=greedy, temperature=temperature
    )
    prompts = [tokenizer(r["prompt"])["input_ids"] for r in rows]

    n_correct, per_prompt = 0, []
    batch = 8
    for s in range(n_samples):
        rng = jax.random.PRNGKey(seed + s)
        for i in range(0, len(prompts), batch):
            chunk = prompts[i : i + batch]
            outs = generate_tokens(
                params, cfg, chunk, g, jax.random.fold_in(rng, i),
                eos_token_id=tokenizer.eos_token_id,
            )
            for j, o in enumerate(outs):
                row = rows[i + j]
                text = tokenizer.decode(o["output_ids"])
                io = row["input_output"]
                if isinstance(io, str):
                    io = json.loads(io)
                ok = code_verify(
                    text, io, timeout=case_timeout,
                    max_cases=max_cases or None,
                )
                n_correct += bool(ok)
                per_prompt.append(
                    {"query_id": str(row.get("query_id", i + j)), "correct": bool(ok)}
                )

    total = len(prompts) * n_samples
    result = {
        "ckpt": ckpt,
        "data": data,
        "task": "code",
        "n_prompts": len(prompts),
        "n_samples": n_samples,
        "accuracy": n_correct / max(1, total),
        "details": per_prompt,
    }
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
        if k in ("max_new_tokens", "n_samples", "max_prompts", "max_cases", "seed"):
            v = int(v)
        elif k in ("greedy",):
            v = v.lower() in ("1", "true")
        elif k in ("temperature", "case_timeout"):
            v = float(v)
        kwargs[k] = v
    evaluate_checkpoint(**kwargs)
