#!/usr/bin/env python3
"""The stream kernels beside their plain forms on the chip
(`areal_tpu/ops/pallas/stream_mix.py`): milliseconds a call and the
bytes a second each moves, at the shapes `xing4-d5e8-train-ppo-8k` runs
them (a band of 1,024 tokens and a row of 8,192, four streams of 3,584,
bf16), by use: the read and the write forward, each one's backward to its
input, and the coefficients' gradient of each.

    python scripts/stream_mix_probe.py [--out chiprun_out/x.jsonl]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from areal_tpu.ops.pallas import stream_mix


def timed(fn, args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--tokens", type=int, nargs="+", default=[1024, 8192])
    args = ap.parse_args()
    n, d, rows = 4, 3584, []
    key = jax.random.PRNGKey(0)
    for t in args.tokens:
        x = jax.random.normal(key, (1, t, n * d), jnp.bfloat16)
        y = jax.random.normal(key, (1, t, d), jnp.bfloat16)
        uses = {
            "read": (jax.random.uniform(key, (1, t, 1, n)), (x,)),
            "write": (jax.random.uniform(key, (1, t, n, n + 1)), (x, y)),
        }
        for use, (a, ins) in uses.items():
            n_i, n_k = a.shape[-2:]
            dout = jax.random.normal(key, (1, t, n_i * d), jnp.bfloat16)
            sizes = tuple(i.shape[-1] // d for i in ins)
            calls = {
                "forward": (lambda k: jax.jit(lambda a, ins: stream_mix._mix(a, ins, (n_i,), k)),
                            (a, ins), (n_k + n_i) * d * 2 * t),
                "backward": (lambda k: jax.jit(lambda a, g: stream_mix._mix(
                    jnp.swapaxes(a, -1, -2), (g,), sizes, k)), (a, dout), (n_k + n_i) * d * 2 * t),
                "coef_grad": (lambda k: jax.jit(lambda g, ins: stream_mix.mhc_coef_grad(
                    (g,), ins, d, k)), (dout, ins), (n_k + n_i) * d * 2 * t),
            }
            for call, (make, operands, nbytes) in calls.items():
                row = dict(tokens=t, use=use, call=call, bytes=nbytes)
                for name, kernel in (("kernel", True), ("plain", False)):
                    s = timed(make(kernel), operands)
                    row[f"{name}_ms"] = s * 1e3
                    row[f"{name}_gb_s"] = nbytes / s / 1e9
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
