#!/usr/bin/env python3
"""The delta rule on the chip (`areal_tpu/ops/kda.py`): milliseconds a call
of `delta_rule`, forward and forward + backward, at the shapes
`kimilinear-d5e8-train-ppo-long` runs it (a row of 16,384 cells, 32 heads
of 128, bf16, q, k, v and f cells-major `[1, T, H K]` as the projections
leave them; `--decay channel`) and at those `qwen3next-d4e32-train-ppo-long`
does (`--decay head --key-heads 16`: f `[1, T, H]`, one decay a value head,
q and k `[1, T, Hk K]`; with `--key-heads 32` the same rule beside the
channel form fed one decay K times) with a given share of the row holding
tokens, in two arms: `plain` (`intra` and the `lax.scan` walk a group at a
time, forward and backward: the CPU's and a mesh's form) and `fused` (the
kernels: `kda_fwd_rule` forward, `kda_bwd_rule` backward). Forward +
backward is the loss `sum(o * w)` with its seven gradients (the loss itself
is returned too: the forward runs in both arms). At each chunk size and
heads a grid step asked for; `fused` beside its worst
differences from `plain`: of O (`max_diff` at values up to `max_abs`) and
of each of the seven gradients (`grad_diff` at `grad_abs`); with `--exact`
each arm's gradients also against the plain form's in float32 at the
highest precision (`grad_err` the worst difference, `grad_err_rms` the root
of the mean square: which of two bf16 computations that differ is the
nearer). Every row says where it was made: the device, the shape, the dtype
and whether the kernels ran in interpret mode (a rehearsal off the chip,
whose milliseconds mean nothing).

`--taps`: the mixers' way into the rule instead (`ops/pallas/kda_taps.py`):
milliseconds a call of one operand's masked convolution, `[1, T, C]` bf16 at
the widths the two cells have (`--widths`, 4,096 and 2,048 columns) under K
taps, forward and forward + backward (the loss `sum(y * g)` with the
gradients of x and w), in two arms: `plain` (`ops/ssm.causal_conv` after the
`where` of the operand, XLA's) and `kernel` (`kda_taps_fwd`, `kda_taps_bwd`),
the kernel's rows beside their worst differences from the plain arm's, at each
block height (`--rows`) and chunk (`--chunk-cells`) asked for; with `--exact`
each arm also against the plain form in float32 over the same operands (`err`
the worst difference, `err_rms` the root of the mean square).

    python scripts/kda_probe.py [--out chiprun_out/x.jsonl] [--chunks 64 128]
        [--decay channel head] [--key-heads 32 16] [--arms plain fused] [--exact]
    python scripts/kda_probe.py --taps [--out ...] [--widths 4096 2048] [--taps-k 4]
        [--rows 256 128] [--chunk-cells 64 128] [--exact]
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


def taps_rows(args):
    """`--taps`: a row a width, a fill, a block height and an arm."""
    from areal_tpu.ops.pallas import kda_taps
    from areal_tpu.ops.ssm import causal_conv

    T, K = args.shape[0], args.taps_k
    rng = np.random.default_rng(0)
    rows = []
    names = ("y", "dx", "dw")
    for C, fill in ((c, fl) for c in args.widths for fl in args.fill):
        seg = inputs(T, 1, 1, int(T * fill))[-1]
        x, g = (jnp.asarray(rng.normal(size=(1, T, C)), jnp.bfloat16) for _ in range(2))
        w = jnp.asarray(rng.normal(size=(K, C)) / 2, jnp.bfloat16)
        plain = lambda x, w: causal_conv(jnp.where((seg > 0)[..., None], x, 0), w, None, seg)
        kernel = lambda x, w: kda_taps.taps(x, w, None, seg, args.interpret)
        three = lambda fn: jax.jit(jax.value_and_grad(
            lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32) * g), (0, 1)))
        want = exact = None
        if args.exact:  # the plain form in float32 over the same operands: what both arms round
            f32 = lambda a: a.astype(jnp.float32)
            exact = [jax.jit(plain)(f32(x), f32(w))] + list(three(plain)(f32(x), f32(w))[1])
        for name, fn, height, chunk in [("plain", plain, 0, 0)] + [
                ("kernel", kernel, h, c) for h in args.rows for c in args.chunk_cells]:
            if height:
                kda_taps.ROWS = height
            if chunk:
                kda_taps.CHUNK = chunk
            fwd, both = jax.jit(fn), three(fn)
            row = dict(device=jax.devices()[0].device_kind, interpret=args.interpret,
                       shape=[1, T, C], dtype=str(x.dtype), taps=K, fill=fill, arm=name,
                       rows=height, chunk=chunk, fwd_ms=timed(fwd, (x, w), 20) * 1e3,
                       fwd_bwd_ms=timed(both, (x, w), 20) * 1e3)
            got = [a.astype(jnp.float32) for a in (fwd(x, w),) + both(x, w)[1]]
            if exact is not None:
                row["err"] = {n: float(jnp.abs(a - t).max()) for n, a, t in zip(names, got, exact)}
                row["err_rms"] = {n: float(jnp.sqrt(jnp.mean(jnp.square(a - t))))
                                  for n, a, t in zip(names, got, exact)}
            if want is None:
                want = got
            else:
                row["max_diff"] = {n: float(jnp.abs(a - t).max())
                                   for n, a, t in zip(names, got, want)}
                row["max_abs"] = {n: float(jnp.abs(t).max()) for n, t in zip(names, want)}
                row["finite"] = bool(all(jnp.isfinite(a).all() for a in got))
            rows.append(row)
            print(json.dumps(row), flush=True)
            jax.clear_caches()
    return rows


def write(out, rows):
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--chunks", type=int, nargs="+", default=[64])
    ap.add_argument("--fill", type=float, nargs="+", default=[0.53, 1.0])
    ap.add_argument("--heads", type=int, nargs="+", default=[8],
                    help="heads a grid step, the forward's and the backward's kernels")
    ap.add_argument("--decay", nargs="+", default=["channel"], choices=["channel", "head"])
    ap.add_argument("--key-heads", type=int, nargs="+", default=[32],
                    help="under --decay head (the channel form has a key a value head)")
    ap.add_argument("--arms", nargs="+", default=["plain", "fused"])
    ap.add_argument("--shape", type=int, nargs=3, default=[16384, 32, 128],
                    help="T H K: a small one with --interpret rehearses the script off the chip")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--exact", action="store_true")
    ap.add_argument("--taps", action="store_true",
                    help="time the mixers' masked convolution instead of the rule")
    ap.add_argument("--widths", type=int, nargs="+", default=[4096, 2048])
    ap.add_argument("--taps-k", type=int, default=4)
    ap.add_argument("--rows", type=int, nargs="+", default=[256],
                    help="under --taps: cells a grid step of the kernels")
    ap.add_argument("--chunk-cells", type=int, nargs="+", default=[64],
                    help="under --taps: cells of a strip the kernels hold in registers")
    args = ap.parse_args()
    T, H, K = args.shape
    kernel = "interpret" if args.interpret else True
    if args.taps:
        return write(args.out, taps_rows(args))
    rows = []
    from areal_tpu.ops.pallas import kda_fwd

    names = ("q", "k", "v", "f", "b", "A", "dt_bias")
    forms = [(d, hk) for d in args.decay for hk in (args.key_heads if d == "head" else [H])]
    for fill, (decay, Hk) in ((fl, fm) for fl in args.fill for fm in forms):
        *xs, seg = inputs(T, H, K, int(T * fill), decay=decay, Hk=Hk)
        shapes = [a.shape for a in xs[:4]]
        xs = [a.reshape(1, T, -1) if a.ndim == 4 else a for a in xs]
        w = jnp.asarray(np.random.default_rng(1).normal(size=(1, T, H, K)), jnp.float32)
        for chunk in args.chunks:
            want = want_grads = exact = None
            if args.exact:  # the plain form in float32: what both arms round
                with jax.default_matmul_precision("highest"):
                    exact = jax.jit(jax.grad(lambda *a: jnp.sum(kda._rule(
                        *(x.astype(jnp.float32).reshape(sh) for x, sh in zip(a[:4], shapes)),
                        *a[4:], seg, chunk, False, kda.GROUP_CELLS) * w), tuple(range(7))))(*xs)
                jax.clear_caches()
            for name, heads in [("plain", 0)] * ("plain" in args.arms) + [
                    ("fused", h) for h in args.heads if "fused" in args.arms]:
                if heads:
                    kda_fwd.HEADS = heads
                cut = lambda q, k, v, f: tuple(
                    a.reshape(sh) for a, sh in zip((q, k, v, f), shapes))

                def rule(q, k, v, f, *rest, name=name):
                    return kda._rule(*cut(q, k, v, f), *rest, seg, chunk,
                                     name == "fused" and kernel, kda.GROUP_CELLS)

                fwd = jax.jit(rule)
                both = jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(rule(*a) * w), tuple(range(7))))
                row = dict(device=jax.devices()[0].device_kind, interpret=args.interpret,
                           shape=[T, H, K], dtype=str(xs[0].dtype), fill=fill, decay=decay,
                           key_heads=Hk, chunk=chunk, arm=name, heads=heads,
                           fwd_ms=timed(fwd, xs) * 1e3, fwd_bwd_ms=timed(both, xs) * 1e3)
                o = fwd(*xs).astype(jnp.float32)
                grads = [g.astype(jnp.float32) for g in both(*xs)[1]]
                if exact is not None:
                    row["grad_err"] = {n: float(jnp.abs(g - t).max())
                                       for n, g, t in zip(names, grads, exact)}
                    row["grad_err_rms"] = {n: float(jnp.sqrt(jnp.mean(jnp.square(g - t))))
                                           for n, g, t in zip(names, grads, exact)}
                if name == "plain":
                    want, want_grads = o, grads
                else:
                    if want is not None:
                        row["max_diff"] = float(jnp.abs(o - want).max())
                        row["max_abs"] = float(jnp.abs(want).max())
                        row["grad_diff"] = {n: float(jnp.abs(g - t).max())
                                            for n, g, t in zip(names, grads, want_grads)}
                        row["grad_abs"] = {n: float(jnp.abs(t).max())
                                           for n, t in zip(names, want_grads)}
                    row["finite"] = bool(all(jnp.isfinite(g).all() for g in grads))
                rows.append(row)
                print(json.dumps(row), flush=True)
                jax.clear_caches()
    write(args.out, rows)


if __name__ == "__main__":
    main()
