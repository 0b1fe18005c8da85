#!/usr/bin/env python3
"""The indexer's kernels alone, on the chip, at the keye cell's shapes:
one packed row of 16,384, 32 / 4 heads of 128, an indexer of 16 heads of
64 choosing 2,048 keys a query, bf16, seeded random inputs.

A line a row layout (`--rows`: sequence lengths joined by `+`, rows by
`,`): milliseconds (the median of `--reps` calls, each ended by
`block_until_ready`) of `index_select`, of `indexed_attention` forward
without and with the KL, and of forward + backward with the KL; the
share of scored cells chosen; and with `--ops` the heaviest device ops of
a traced forward + backward, by HLO base name. `--check N` compares the
kernels with the plain form (`ops/indexer._plain_row`) at a row of N
first: output, KL and every gradient.

    python scripts/index_kernels_probe.py [--rows 14900,8000,3000+2500+2000+1100] [--ops] [--out chiprun_out/x.jsonl]

`--toy` walks it on the CPU (interpret mode) at a small size: the
plumbing, no time.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import indexer as ix


def row(lens, t):
    seg, pos, o = np.zeros(t, np.int32), np.zeros(t, np.int32), 0
    for i, l in enumerate(lens):
        seg[o:o + l], pos[o:o + l] = i + 1, np.arange(l)
        o += l
    pos[o:] = np.arange(t - o)
    return jnp.asarray(seg)[None], jnp.asarray(pos)[None]


def inputs(seed, t, hq, hkv, hd, hi, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *s, scale=1.0: (jax.random.normal(k, s, jnp.float32) * scale).astype(dtype)
    return (n(ks[0], 1, t, hq, hd, scale=3.0), n(ks[1], 1, t, hkv, hd), n(ks[2], 1, t, hkv, hd),
            n(ks[3], 1, t, hi, d), n(ks[4], 1, t, d),
            n(ks[5], 1, t, hi, scale=(hi * d) ** -0.5).astype(jnp.float32),
            n(ks[6], 1, t, hq, hd))


def timed(fn, *args, reps):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="14900,8000,3000+2500+2000+1100")
    ap.add_argument("--t", type=int, default=16384)
    ap.add_argument("--top-k", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--check", type=int, default=0)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args()
    shape = dict(hq=32, hkv=4, hd=128, hi=16, d=64, dtype=jnp.bfloat16)
    if a.toy:
        a.t, a.top_k, a.reps, a.rows = 2048, 96, 1, "1200+500"
        shape = dict(hq=4, hkv=2, hd=64, hi=2, d=16, dtype=jnp.float32)
        a.check = a.check and 2048
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: this script times the chip (--toy walks it on the CPU)")
    lines = []

    def loss(impl, want_kl, seg, pos, w_out, q, k, v, iq, ik, iw):
        out, s = ix.indexed_attention(q, k, v, iq, ik, iw, seg, pos, a.top_k, impl, want_kl)
        real = (seg > 0)[..., None, None]
        return jnp.sum(jnp.where(real, out.astype(jnp.float32) * w_out, 0.0)) + s["index_kl"], s

    if a.check:
        t = a.check
        *x, w_out = inputs(1, t, **dict(shape, dtype=jnp.float32))
        seg, pos = row([t // 2, t // 4 + 37, 100], t)
        got = {}
        for impl in ("reference", "splash"):
            got[impl] = jax.jit(jax.value_and_grad(
                lambda *xs, impl=impl: loss(impl, True, seg, pos, w_out.astype(jnp.float32), *xs),
                argnums=range(6), has_aux=True))(*x)
        (lp, sp), gp = got["reference"]
        (lk, sk), gk = got["splash"]
        real = np.asarray(seg[0] > 0)
        line = dict(check=t, loss=[float(lp), float(lk)],
                    sums={k: [float(sp[k]), float(sk[k])] for k in sp if jnp.ndim(sp[k]) == 0},
                    grad_err={n: [float(np.abs(np.asarray(u - w)[0][real]).max()),
                                  float(np.abs(np.asarray(u)[0][real]).max())]
                              for n, u, w in zip("q k v iq ik iw".split(), gp, gk)})
        print(json.dumps(line), flush=True)
        lines.append(line)

    for spec in a.rows.split(","):
        lens = [int(l) for l in spec.split("+")]
        seg, pos = row(lens, a.t)
        *x, w_out = inputs(2, a.t, **shape)
        w_out = w_out.astype(jnp.float32)
        fwd = jax.jit(lambda *xs: loss("splash", False, seg, pos, w_out, *xs))
        fwd_kl = jax.jit(lambda *xs: loss("splash", True, seg, pos, w_out, *xs))
        both = jax.jit(jax.value_and_grad(
            lambda *xs: loss("splash", True, seg, pos, w_out, *xs), argnums=range(6),
            has_aux=True))
        no_kl = jax.jit(jax.value_and_grad(
            lambda *xs: loss("splash", False, seg, pos, w_out, *xs), argnums=range(3),
            has_aux=True))
        _, s = fwd_kl(*x)
        line = dict(rows=spec, t=a.t,
                    chosen_pct=100.0 * float(s["index_chosen"]) / float(s["index_cells"]),
                    kl=float(s["index_kl"]),
                    fwd_ms=timed(fwd, *x, reps=a.reps),
                    fwd_kl_ms=timed(fwd_kl, *x, reps=a.reps),
                    fwd_bwd_no_kl_ms=timed(no_kl, *x, reps=a.reps),
                    fwd_bwd_ms=timed(both, *x, reps=a.reps))
        if a.ops and not a.toy:
            from benchmark import trace_reduce

            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(3):
                    jax.block_until_ready(both(*x))
                jax.profiler.stop_trace()
                red = trace_reduce.reduce_trace(
                    trace_reduce.load_xplane(trace_reduce.find_xplane(tmp)), 14)
            line["ops_ms_a_call"] = {n: 1e3 * sec / 3 for n, sec in red["device_ops"]}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
