#!/usr/bin/env python3
"""The delta rule on the chip (`areal_tpu/ops/kda.py`): milliseconds a call
of `delta_rule`, forward and forward + backward, at the shapes
`kimilinear-d5e8-train-ppo-long` runs it (a row of 16,384 cells, 32 heads
of 128, bf16, q, k, v and f cells-major `[1, T, H K]` as the projections
leave them; `--decay channel`) and at those `qwen3next-d4e32-train-ppo-long`
does (`--decay head --key-heads 16`: f `[1, T, H]`, one decay a value head,
q and k `[1, T, Hk K]`; with `--key-heads 32` the same rule beside the
channel form fed one decay K times) with a given share of the row holding
tokens, in three arms:
`plain` (`intra` and the `lax.scan` walk a group at a time), `walk` (the
same loop with the walk by `kda_fwd_states`: the chip's forward before
PR 53) and `fused` (the forward one kernel, `kda_fwd_rule`); the last two
share the backward loop. At each chunk size, group of chunks and heads a
grid step asked for; `fused` beside its worst difference from `plain`.

    python scripts/kda_probe.py [--out chiprun_out/x.jsonl] [--chunks 64 128]
        [--decay channel head] [--key-heads 32 16] [--arms plain fused]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import kda


def timed(fn, args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def inputs(T, H, K, tokens, seed=0, decay="channel", Hk=None):
    rng = np.random.default_rng(seed)
    seg = np.zeros((1, T), np.int32)
    at, i = 0, 1
    while at < tokens:  # sequences of 1-6k tokens
        n = min(int(rng.integers(1000, 6000)), tokens - at)
        seg[0, at:at + n] = i
        at, i = at + n, i + 1
    valid = seg > 0
    q, k = (rng.normal(size=(1, T, Hk or H, K)) for _ in range(2))
    v = rng.normal(size=(1, T, H, K))
    # with A of 1-16: a decay of 0.2-0.999 a token; the channel form fed a
    # head's one number K times, so that both forms compute one thing
    f = rng.normal(size=(1, T, H)) - 4.0
    if decay == "channel":
        f = np.broadcast_to(f[..., None], (1, T, H, K))
    b = rng.uniform(0.1, 0.95, size=(1, T, H))
    bf = lambda a: jnp.asarray(
        np.where(valid.reshape(valid.shape + (1,) * (a.ndim - 2)), a, 0), jnp.bfloat16)
    return (bf(q), bf(k), bf(v), bf(f),
            jnp.asarray(np.where(valid[..., None], b, 0), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32),
            jnp.zeros((H, K) if decay == "channel" else (H,), jnp.float32), jnp.asarray(seg))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--chunks", type=int, nargs="+", default=[64])
    ap.add_argument("--fill", type=float, nargs="+", default=[0.53, 1.0])
    ap.add_argument("--heads", type=int, nargs="+", default=[4, 8])
    ap.add_argument("--groups", type=int, nargs="+", default=[1024])
    ap.add_argument("--decay", nargs="+", default=["channel"], choices=["channel", "head"])
    ap.add_argument("--key-heads", type=int, nargs="+", default=[32],
                    help="under --decay head (the channel form has a key a value head)")
    ap.add_argument("--arms", nargs="+", default=["plain", "walk", "fused"])
    args = ap.parse_args()
    T, H, K = 16384, 32, 128
    rows = []
    from areal_tpu.ops.pallas import kda_chunk, kda_fwd

    # the group loop with the kernels' walk in the forward too
    walk = jax.custom_vjp(
        lambda *a: kda._rule_fwd_groups(*a)[0], nondiff_argnums=(8, 9, 10))
    walk.defvjp(kda._rule_fwd_groups, kda._rule_bwd)
    forms = [(d, hk) for d in args.decay for hk in (args.key_heads if d == "head" else [H])]
    for fill, (decay, Hk) in ((fl, fm) for fl in args.fill for fm in forms):
        *xs, seg = inputs(T, H, K, int(T * fill), decay=decay, Hk=Hk)
        shapes = [a.shape for a in xs[:4]]
        xs = [a.reshape(1, T, -1) if a.ndim == 4 else a for a in xs]
        w = jnp.asarray(np.random.default_rng(1).normal(size=(1, T, H, K)), jnp.float32)
        for chunk in args.chunks:
            for group in args.groups:
                want = None
                for name, heads in [("plain", 0)] * ("plain" in args.arms) + [
                        (n, h) for h in args.heads for n in ("walk", "fused") if n in args.arms]:
                    if heads:
                        kda_chunk.HEADS = kda_fwd.HEADS = heads

                    def rule(q, k, v, f, *rest, name=name):
                        q, k, v, f = (a.reshape(sh) for a, sh in zip((q, k, v, f), shapes))
                        if name == "walk":
                            return walk(q, k, v, f, *rest, seg, chunk, True, group)
                        return kda._rule(q, k, v, f, *rest, seg, chunk, name == "fused", group)

                    fwd = jax.jit(rule)
                    both = jax.jit(jax.grad(
                        lambda *a: jnp.sum(rule(*a) * w), tuple(range(7))))
                    row = dict(fill=fill, decay=decay, key_heads=Hk, chunk=chunk, group=group,
                               arm=name, heads=heads,
                               fwd_ms=timed(fwd, xs) * 1e3,
                               fwd_bwd_ms=timed(both, xs) * 1e3)
                    o = fwd(*xs).astype(jnp.float32)
                    if name == "plain":
                        want = o
                    elif name == "fused" and want is not None:
                        row["max_diff"] = float(jnp.abs(o - want).max())
                        row["max_abs"] = float(jnp.abs(want).max())
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    jax.clear_caches()
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
