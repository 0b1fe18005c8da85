#!/usr/bin/env python3
"""The selective scan's kernels alone, on the chip, at the widths of
`phi4flash-d8-train-ppo-8k`: one packed row of 8,192, 5,120 channels,
16 states, bf16. A line a shape (the kernel's block of time x its block
of channels): forward and forward + backward milliseconds (the median
of `--reps` calls, each ended by `block_until_ready`), and the kernel
against `plain_scan` on the same inputs (values and the five gradients,
largest absolute difference over the largest absolute value).

    python scripts/sscan_probe.py [--chunks 128] [--blocks 512] [--out chiprun_out/x.jsonl]

`--toy` walks it on the CPU in interpret mode at a small size: the
plumbing, no time.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import selective_scan as ss


def inputs(seed, R, T, Dn, N, dtype, lens):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    seg = np.zeros((R, T), np.int32)
    o = 0
    for j, l in enumerate(lens):
        seg[:, o:o + l] = j + 1
        o += l
    seg = jnp.asarray(seg)
    valid = (seg > 0)[..., None]
    x = jnp.where(valid, jax.random.normal(k[0], (R, T, Dn)), 0).astype(dtype)
    dt = jnp.where(valid, jnp.exp(jax.random.uniform(
        k[1], (R, T, Dn), minval=np.log(1e-3), maxval=np.log(0.1))), 0.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (Dn, N))
    B = jnp.where(valid, jax.random.normal(k[2], (R, T, N)), 0).astype(dtype)
    C = jnp.where(valid, jax.random.normal(k[3], (R, T, N)), 0).astype(dtype)
    w = jax.random.normal(k[4], (R, T, Dn)).astype(dtype)
    return (x, dt, A, B, C), seg, w


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, nargs="+", default=[128])
    ap.add_argument("--blocks", type=int, nargs="+", default=[512])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    if args.toy:
        R, T, Dn, N, dtype, lens = 1, 192, 256, 16, jnp.float32, [70, 60, 40]
        args.chunks, args.blocks, args.reps = [32], [128], 1
    else:
        R, T, Dn, N, dtype, lens = 1, 8192, 5120, 16, jnp.bfloat16, [3000, 2600, 1500, 700]
    a, seg, w = inputs(args.seed, R, T, Dn, N, dtype, lens)
    rows = []
    for chunk in args.chunks:
        plain = lambda *a: ss.plain_scan(*a, seg, chunk).astype(dtype)
        loss = lambda f: (lambda *a: (f(*a).astype(jnp.float32) * w.astype(jnp.float32)).sum())
        want = jax.jit(plain)(*a)
        want_g = jax.jit(jax.grad(loss(plain), (0, 1, 2, 3, 4)))(*a)
        for blk in args.blocks:
            ss._BLOCKS = (blk,)
            kern = lambda *a: ss.kernel_scan(*a, seg, chunk)
            fwd = jax.jit(kern)
            both = jax.jit(jax.grad(loss(kern), (0, 1, 2, 3, 4)))
            rel = lambda g, h: float(jnp.abs(g.astype(jnp.float32) - h.astype(jnp.float32)).max()
                                     / jnp.abs(h.astype(jnp.float32)).max())
            row = dict(chunk=chunk, block=blk, device=jax.devices()[0].device_kind,
                       fwd_ms=timed(fwd, a, args.reps), fwd_bwd_ms=timed(both, a, args.reps),
                       y=rel(fwd(*a), want),
                       **{f"d{n}": rel(g, h) for n, g, h in zip("x dt A B C".split(), both(*a), want_g)})
            if args.toy:
                row.pop("fwd_ms"), row.pop("fwd_bwd_ms")
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
