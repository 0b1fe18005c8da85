#!/usr/bin/env python3
"""The gated short convolution alone (`ops/ssm.gated_conv`: `C * conv(B * x)`,
three taps, no activation; PR 63, PERF.md section 6), on the chip at
`lfm2-d5e16-train-ppo-8k`'s shape: `[B | C | x]` `[1, 8192, 6144]` bf16 in,
`[1, 8192, 2048]` out, the row 70 % full of four sequences; ms a call forward
and forward + backward in plain `jax.numpy` as the compiler fuses it, beside the
call's HBM bound (`benchmark/flops_lfm2.conv_work`: the forward reads 3 x 2048
and writes 2048 bf16 values a cell, the backward reads them and the product's
cotangent and writes 3 x 2048) at the chip's published bandwidth, and the share
of that bound each arm reaches: `plain` as the compiler fuses `jax.numpy`,
`kernel` the pair of `ops/pallas/conv_gate.py` (with its worst difference from
the plain arm's result and gradients). Under half, the convolution wants a
kernel of its own; over it, the plain form stays. Also one band of 1,024 cells
with its tail (what a layer that walks its bands calls: the plain form's, and a
host's dispatch more than a device's time).

    python scripts/conv_probe.py [--out chiprun_out/conv_probe63.jsonl]

`--toy` walks it on the CPU at a toy shape: the plumbing, no time."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops.ssm import gated_conv
from benchmark import flops_lfm2, manifest


def bench(fn, args, reps):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return first, (time.perf_counter() - t0) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--toy", action="store_true")
    a = ap.parse_args()
    if not a.toy and jax.default_backend() != "tpu":
        sys.exit("conv_probe: no TPU here (--toy walks it on the CPU)")
    D, K = (128, 3) if a.toy else (2048, 3)
    dt = jnp.float32 if a.toy else jnp.bfloat16
    rows = []
    for T, fill, tail in ((256, 0.7, False), (64, 1.0, True)) if a.toy else (
            (8192, 0.7, False), (8192, 1.0, False), (1024, 1.0, True)):
        rng = np.random.default_rng(0)
        n, seg = int(T * fill), np.zeros((1, T), np.int32)
        cuts = [0, int(n * 0.37), int(n * 0.66), int(n * 0.88), n]
        for s, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
            seg[0, lo:hi] = s + 1
        seg = jnp.asarray(seg)
        bcx = jnp.asarray(rng.normal(size=(1, T, 3 * D)), dt)
        w = jnp.asarray(rng.normal(size=(K, D)) / np.sqrt(K), dt)
        dy = jnp.asarray(rng.normal(size=(1, T, D)), dt)
        before = (jnp.asarray(rng.normal(size=(1, K - 1, D)), dt),
                  jnp.ones((1, K - 1), jnp.int32)) if tail else None
        for arm in ("plain",) if tail else ("plain", "kernel"):
            kernel = arm == "kernel"
            fn = lambda bcx, w: gated_conv(bcx, w, None, seg, before, kernel)[0]
            fwd = jax.jit(fn)
            both = jax.jit(jax.grad(lambda bcx, w: (
                fn(bcx, w).astype(jnp.float32) * dy.astype(jnp.float32)).sum(), (0, 1)))
            c1, ms_f = bench(fwd, (bcx, w), a.reps)
            c2, ms_fb = bench(both, (bcx, w), a.reps)
            row = dict(arm=arm, device=jax.devices()[0].device_kind, shape=[1, T, 3 * D],
                       fill=fill, band_with_tail=tail, first_call_s=[round(c1, 2), round(c2, 2)],
                       fwd_ms=round(ms_f, 4), fwd_bwd_ms=round(ms_fb, 4), rehearsal=a.toy)
            if kernel:  # against the plain arm's results
                row["y_max_diff"] = float(jnp.abs(
                    fwd(bcx, w).astype(jnp.float32) - plain[0].astype(jnp.float32)).max())
                row["grad_rel_diff"] = [
                    float(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)).max()
                          / (jnp.abs(r.astype(jnp.float32)).max() + 1e-9))
                    for g, r in zip(both(bcx, w), plain[1])]
            else:
                plain = (fwd(bcx, w), both(bcx, w))
            if not a.toy:
                bw = manifest.device_peaks(row["device"])["hbm_bytes_per_s"]
                work = flops_lfm2.conv_work(D, K, T)
                row["fwd_bound_ms"] = round(work["fwd_bytes"] / bw * 1e3, 4)
                row["fwd_bwd_bound_ms"] = round(work["bwd_bytes"] / bw * 1e3, 4)
                row["fwd_hbm_share_pct"] = round(100 * row["fwd_bound_ms"] / ms_f, 1)
                row["fwd_bwd_hbm_share_pct"] = round(100 * row["fwd_bwd_bound_ms"] / ms_fb, 1)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))


if __name__ == "__main__":
    main()
