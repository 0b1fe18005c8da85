#!/usr/bin/env python3
"""Time the splash kernel alone, per row shape and per candidate run
shape, and fit `ops/attention.splash_cost` to what the chip says.

    python scripts/splash_shape_sweep.py --out chiprun_out/splash_sweep.jsonl
    python scripts/splash_shape_sweep.py --fit chiprun_out/splash_sweep.jsonl
    python scripts/splash_shape_sweep.py --chosen --out chiprun_out/splash_chosen.jsonl

The first form needs a TPU: for every `rows x t` of `--shapes` (default:
the sixteen training micro-batches and the forward-only lengths of the
benchmark's `ppo-packed` pool) it runs `splash_packed_attention` over
the rows at each candidate `(t', bq, bkv, bkvc)` — `t, t+128, ..`
up to the next multiple of 512, a few dividing blocks each — through
`--layers` chained calls in one program, forward alone and forward plus
the backward, and writes one JSON line a candidate. `--chosen`
times only what `splash_run_shape` picks beside today's `t' = t` at the
largest dividing blocks. `--fit` needs no device: least squares of
`splash_cost`'s constants on fwd + (fwd + bwd), the kernels one layer
runs under full remat.

`--check` times nothing: the call's output and gradients against the
einsum reference's. The backward is what the call runs: splash's dq and
dkv kernels over compacted tables for a row of 2048 or more alone in its
call, the fused kernel over the static grid otherwise. `--unfused` gives every call the
dq and dkv kernels, `--fused` the fused one (a row alone then keeps its
static tables: the two cannot be told apart otherwise), `--static` a row
alone its static tables, `--width W,Wq` one width for its compacted
tables in place of the branch `_table_widths` offers (the row must fit:
`--seq-len` packs it with sequences that short), so that two widths
price a walked dead step: forward by `fwd_ms`, dq by `grad_ms` less
that at two `W`, dkv at two `Wq`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POOL = ("3x6144,1x896,3x5504,6x3200,2x768,6x3072,2x640,3x5760,2x384,5x3840,"
        "1x512,4x4480,1x1152,5x3712,1x1280,9x2432,9x2048,10x1920,3x1024")


def candidates(t):
    """Run shapes to time for a row of `t`: each length up to the next
    multiple of 512 at its two largest q blocks x three largest kv
    blocks with the largest compute block, and the top pair once more
    at the next compute block down."""
    from areal_tpu.ops import attention as A

    tq, tkv, tkvc = A._splash_block_targets()
    out = []
    for t_run in A._run_lengths(t):
        bqs = A._blocks_dividing(t_run, tq)[:2]
        bkvs = A._blocks_dividing(t_run, tkv)[:3]
        out += [(t_run, bq, bkv, A._blocks_dividing(bkv, tkvc)[0])
                for bq in bqs for bkv in bkvs]
        out += [(t_run, bqs[0], bkvs[0], c)
                for c in A._blocks_dividing(bkvs[0], tkvc)[1:2]]
    return out


def today(t):
    """What the parent ran: t' = t at the largest dividing blocks."""
    from areal_tpu.ops import attention as A

    return A._plain_run_shape(t, *A._splash_block_targets())


def variant(args):
    """Steer `ops/attention` to the kernels `--unfused`, `--fused`,
    `--static` and `--width` name."""
    from areal_tpu.ops import attention as A

    if args.unfused or args.fused:
        build = A._splash_kernel
        A._splash_kernel = lambda *a, fused_bwd=True, **kw: build(
            *a, fused_bwd=not args.unfused, **kw)
    if args.fused:
        A._rows_skip = lambda rows, t_run: False
    if args.static:
        A._with_tables = lambda kernel, tables: kernel
    if args.width:
        A._table_widths = lambda *a: (tuple(args.width),)


def packed_rows(rows, t, seq_len=None):
    """(segment ids, positions), [rows, t] each: three sequences and a
    padded tail a row, as the packer leaves them, or sequences of
    `seq_len` as far as they go."""
    import numpy as np

    seg = np.zeros((rows, t), np.int32)
    pos = np.zeros((rows, t), np.int32)
    for r in range(rows):
        cuts = [0, t // 5, t // 2, t - 1 - (r * 37) % 100]
        if seq_len:
            cuts = list(range(0, t - (r * 37) % 100, seq_len))
        for i in range(len(cuts) - 1):
            seg[r, cuts[i]: cuts[i + 1]] = i + 1
            pos[r, cuts[i]: cuts[i + 1]] = np.arange(cuts[i + 1] - cuts[i])
    return seg, pos


def time_shape(rows, t, run_shape, hq, hkv, hd, layers, window=None,
               seq_len=None):
    """(fwd ms, fwd+bwd ms) of `layers` chained attention calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.attention import splash_packed_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(rows, t, hq, hd), jnp.bfloat16)
    k = jnp.asarray(rng.randn(rows, t, hkv, hd), jnp.bfloat16)
    v = jnp.asarray(rng.randn(rows, t, hkv, hd), jnp.bfloat16)
    seg, pos = (jnp.asarray(a) for a in packed_rows(rows, t, seq_len))

    def chain(q, k, v):
        def body(x, _):
            # rows whole, as the model gives them
            out = splash_packed_attention(x, k, v, seg, pos,
                                          _run_shape=run_shape, window=window)
            return x + out * jnp.asarray(1e-3, x.dtype), None

        x, _ = jax.lax.scan(body, q, None, length=layers)
        return jnp.sum(x.astype(jnp.float32))

    def clock(fn):
        jax.block_until_ready(fn(q, k, v))  # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v))
        reps = max(1, int(0.05 / (time.perf_counter() - t0)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e3

    return clock(jax.jit(chain)), clock(jax.jit(jax.grad(chain, (0, 1, 2))))


def check_shape(rows, t, run_shape, hq, hkv, hd, window=None, seq_len=None):
    """Largest error of the call's output and of its q, k, v gradients
    against `reference_packed_attention`'s (float32 from the same bf16
    inputs), each as a share of the reference's largest value."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.attention import (
        reference_packed_attention,
        splash_packed_attention,
    )

    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(rows, t, h, hd), jnp.bfloat16)
               for h in (hq, hkv, hkv))
    seg, pos = packed_rows(rows, t, seq_len)
    real = jnp.asarray(seg > 0)[..., None, None]
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    dout = jnp.asarray(rng.randn(rows, t, hq, hd), jnp.float32) * real

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32) * real
            return jnp.sum(out * dout), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    got = run(lambda q, k, v: splash_packed_attention(
        q, k, v, seg, pos, _run_shape=run_shape, window=window))
    want = run(lambda q, k, v: jax.vmap(
        lambda q, k, v, s, p: reference_packed_attention(
            q, k, v, s, p, window=window))(q, k, v, seg, pos))
    return {name: float(np.abs(a - b).max() / np.abs(b).max())
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}


def sweep(args):
    import jax

    if jax.default_backend() != "tpu":
        sys.exit("splash_shape_sweep times the compiled kernel: needs a TPU")
    from areal_tpu.ops.attention import splash_run_shape

    shapes = [tuple(int(x) for x in s.split("x")) for s in args.shapes.split(",")]
    plan, seen = [], set()
    for rows, t in shapes:
        cands = [today(t)]
        cands += [splash_run_shape(t)] if args.chosen else candidates(t)
        for c in cands:
            if (rows, t, c) not in seen:
                seen.add((rows, t, c))
                plan.append((rows, t, c))
    # Today's shapes first, so a run cut short still has the baseline.
    plan.sort(key=lambda p: p[2] != today(p[1]))
    variant(args)
    began = time.monotonic()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a" if args.append else "w") as f:
        for i, (rows, t, c) in enumerate(plan):
            if time.monotonic() - began > args.max_seconds:
                print(f"stopped at {i} of {len(plan)}: --max-seconds", flush=True)
                break
            row = dict(rows=rows, t=t, t_run=c[0], bq=c[1], bkv=c[2], bkvc=c[3],
                       hq=args.hq, hkv=args.hkv, hd=args.hd, layers=args.layers,
                       window=args.window, device=jax.devices()[0].device_kind,
                       unfused=args.unfused, fused=args.fused,
                       static=args.static, width=args.width,
                       seq_len=args.seq_len)
            try:
                if args.check:
                    row["rel_err"] = check_shape(
                        rows, t, c, args.hq, args.hkv, args.hd, args.window,
                        args.seq_len)
                else:
                    row["fwd_ms"], row["grad_ms"] = time_shape(
                        rows, t, c, args.hq, args.hkv, args.hd, args.layers,
                        args.window, args.seq_len)
            except Exception as e:  # a block the compiler refuses is a result
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)


def fit(path):
    """Least squares of splash_cost's constants (`_SPLASH_NS`) on a
    sweep's file: (constants, the model's relative error a run, the runs)."""
    import numpy as np

    from areal_tpu.ops.attention import _splash_cost_terms

    rows = [json.loads(l) for l in open(path)]
    rows = [r for r in rows if "error" not in r]
    x = np.asarray([_splash_cost_terms(r["t_run"], r["bq"], r["bkv"], r["bkvc"],
                                       r.get("window"))
                    for r in rows], float)
    y = np.asarray([(r["fwd_ms"] + r["grad_ms"]) * 1e6
                    / (r["rows"] * r["hq"] * r["layers"]) for r in rows])
    # Relative error matters (the choice compares costs of one row), so
    # weigh each run by 1 / measured.
    coef, *_ = np.linalg.lstsq(x / y[:, None], np.ones_like(y), rcond=None)
    return tuple(map(float, coef)), x @ coef / y - 1.0, rows


def print_fit(path):
    import numpy as np

    coef, err, rows = fit(path)
    print(json.dumps(dict(runs=len(rows), _SPLASH_NS=coef,
                          rel_err_median=float(np.median(np.abs(err))),
                          rel_err_max=float(np.max(np.abs(err))))))
    for r, e in sorted(zip(rows, err), key=lambda z: -abs(z[1]))[:12]:
        print(f"  {r['rows']}x{r['t']} at {r['t_run']} {r['bq']}/{r['bkv']}/"
              f"{r['bkvc']}: fwd {r['fwd_ms']:.2f} + grad {r['grad_ms']:.2f} ms,"
              f" model {e:+.1%}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=POOL, help="rows x t, comma-separated")
    ap.add_argument("--out", default="chiprun_out/splash_sweep.jsonl")
    ap.add_argument("--fit", metavar="JSONL", help="fit splash_cost to a sweep")
    ap.add_argument("--chosen", action="store_true",
                    help="time only today's shape and splash_run_shape's")
    ap.add_argument("--hq", type=int, default=12)
    ap.add_argument("--hkv", type=int, default=2)
    ap.add_argument("--hd", type=int, default=128)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--window", type=int, default=None,
                    help="time a window layer (LocalMask), not a causal one")
    ap.add_argument("--unfused", action="store_true",
                    help="splash's dq and dkv kernels in every call")
    ap.add_argument("--fused", action="store_true",
                    help="the fused backward (static tables) in every call")
    ap.add_argument("--static", action="store_true",
                    help="a row alone keeps its static tables")
    ap.add_argument("--width", type=lambda s: [int(x) for x in s.split(",")],
                    help="W,Wq: one width for a row's compacted tables")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="pack the rows with sequences this long")
    ap.add_argument("--check", action="store_true",
                    help="no timing: output and gradients against the "
                         "einsum reference")
    ap.add_argument("--append", action="store_true", help="add to --out")
    ap.add_argument("--max-seconds", type=float, default=3000.0)
    args = ap.parse_args()
    if args.fit:
        print_fit(args.fit)
    else:
        sweep(args)


if __name__ == "__main__":
    main()
