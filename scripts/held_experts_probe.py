#!/usr/bin/env python3
"""The held experts' part of an expert layer alone, on the chip, at the
shapes of the four cells that run it: one row of 16,384 tokens of which
about half are real, bf16,

    trinity   D 2048, 16 of 128 gated silu experts of 1024, top-8, about 6,750 pairs held
    nemotron  D 2688,  8 of 128 plain squared-ReLU experts of 1856, top-6, about 2,600 pairs held
    joyai     D 2048, 16 of 256 gated silu experts of 768, top-8, about 4,300 pairs held
    keye      D 2048, 16 of 128 gated silu experts of 768, top-8, about 8,600 pairs held

with seeded random routing skewed to those counts. A line a (cell, row
tile x chunk rows): forward and forward + backward milliseconds (the
median of `--reps` calls, each ended by `block_until_ready`), the pairs
held, the tiles and the rows they run. `--parent DIR` times `_held_experts` of the
checkout at DIR on the same inputs and compares values and gradients;
`--ops` adds the heaviest device ops of a traced call, by HLO base name.
`--combine` adds a line a (cell, chunk rows) for the combine alone
(`_add_rows`: sort, gather, add) over the first chunk's rows and tokens
as the tiles hand them over: milliseconds a call (a scan over sixteen
calls whose rows are rotated tile by tile, so that nothing is hoisted)
for the scatter-add beside the kernel (`ops/pallas/segment_add.py`, at
each `--kernel-blocks` band x block), the largest difference between
the two, and with `--ops` each form's device ops.

    python scripts/held_experts_probe.py [--tiles 128x4096,256x4096,512x4096] [--parent _parent] [--combine] [--out chiprun_out/x.jsonl]

`--toy` walks it on the CPU at a small size: the plumbing, no time.
"""

import argparse
import importlib.util
import inspect
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

from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import MoEConfig
from areal_tpu.ops.pallas import segment_add

_GATED = ("w_gate", "w_up", "w_down")
CELLS = {
    "trinity": dict(D=2048, F=1024, held=16, k=8, act="silu", scale=2.826,
                    mats=_GATED, real=0.521, pairs=6750),
    "nemotron": dict(D=2688, F=1856, held=8, k=6, act="relu2", scale=2.5,
                     mats=("w_in", "w_out"), real=0.526, pairs=2600),
    "joyai": dict(D=2048, F=768, held=16, k=8, act="silu", scale=2.5,
                  mats=_GATED, real=0.526, pairs=4300, experts=256),
    "keye": dict(D=2048, F=768, held=16, k=8, act="silu", scale=1.0,
                 mats=_GATED, real=0.526, pairs=8600),
}


def routing(seed, T, k, held, real, pairs, experts=128):
    """k distinct experts a token, drawn with the held ones weighted so
    that about `pairs` pairs of real tokens fall to them, each held
    expert's share a little different (a seeded router's skew)."""
    rng = np.random.default_rng(seed)
    n_real = int(real * T)
    skew = np.exp(0.35 * rng.standard_normal(experts))
    lo, hi = 0.01, 10.0
    for _ in range(30):  # the held experts' weight, by bisection on the expected count
        a = (lo * hi) ** 0.5
        w = skew.copy()
        w[:held] *= a
        g = np.log(w)[None, :] + rng.gumbel(size=(n_real, experts))
        got = (np.argsort(-g, axis=1)[:, :k] < held).sum()
        lo, hi = (a, hi) if got < pairs else (lo, a)
    choice = np.zeros((T, k), np.int64) + held  # padding: experts not held
    choice[:n_real] = np.argsort(-g, axis=1)[:, :k]
    mask = np.arange(T) < n_real
    return (jnp.asarray(choice.T.reshape(-1), jnp.int32), jnp.asarray(mask),
            int(((choice < held) & mask[:, None]).sum()))


def held_call(lib, moe, act, mats, choice, mask, T, k):
    """`lib._held_experts` as a function of (x, mp, gate): this tree's, or
    the parent's, which also takes the pairs' tokens."""
    takes_tokens = "tok_idx" in inspect.signature(lib._held_experts).parameters
    tok = (jnp.tile(jnp.arange(T, dtype=jnp.int32), k),) if takes_tokens else ()

    def call(x, mp, gate):
        return lib._held_experts(x, mp, moe, act, x.dtype, choice, gate, *tok, mask, mats)

    return call


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def first_chunk(choice, mask, held, k, T):
    """The tokens of the first chunk's rows as `_run_tiles` hands them to
    `_add_rows` (a row past its tile's pairs is sent to the last token),
    and the rows the chunk holds."""
    R, G = moe_lib._HELD_ROW_TILE, moe_lib._chunks(0)[0]
    here = (choice < held) & jnp.tile(mask, k)
    local_e = jnp.where(here, choice, held).astype(jnp.int32)
    n = k * T
    pairs = jnp.pad(jax.lax.sort(local_e * n + jnp.arange(n, dtype=jnp.int32)) % n, (0, R))
    sizes = jnp.sum(local_e[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None], axis=1,
                    dtype=jnp.int32)
    n_tiles, tiles = moe_lib._held_tiles(sizes, n)
    in_chunk = int(min(G, int(n_tiles)))
    toks = [moe_lib._tile_rows(i, tiles, pairs, jnp.zeros((n + R,)), T)[2] for i in range(in_chunk)]
    tok = jnp.concatenate(toks + [jnp.zeros(((G - in_chunk) * R,), jnp.int32)])
    return tok, in_chunk * R


def combine_lines(name, c, choice, mask, T, dtype, a):
    """The combine alone: `_add_rows` as a scatter-add and as the kernel."""
    R = moe_lib._HELD_ROW_TILE
    tok, n_rows = first_chunk(choice, mask, c["held"], c["k"], T)
    B, reps = tok.shape[0], 16
    key = jax.random.PRNGKey(a.seed + 1)
    rows = jax.random.normal(key, (B, c["D"])).astype(dtype)
    y0 = jax.random.normal(jax.random.fold_in(key, 1), (T, c["D"]), jnp.float32)
    # call i sees the chunk's first n_rows rows rotated by i tiles
    at = (jnp.arange(n_rows)[None, :] + R * jnp.arange(reps)[:, None]) % max(n_rows, 1)
    toks = jnp.concatenate([tok[:n_rows][at], jnp.broadcast_to(tok[n_rows:], (reps, B - n_rows))], 1)

    def program(blocks):
        """The sixteen calls compiled as the scatter-add (no `blocks`) or
        as the kernel at that band x block: traced here, under the form
        asked for."""
        def calls(y, rows, toks):
            return jax.lax.scan(
                lambda y, t: (moe_lib._add_rows(y, rows, t, n_rows), None), y, toks)[0]

        was = segment_add.kernel_ok, segment_add.BAND, segment_add.BLOCK
        if blocks:
            segment_add.BAND, segment_add.BLOCK = blocks
        else:
            segment_add.kernel_ok = lambda *_: False
        try:
            return jax.jit(calls).lower(y0, rows, toks).compile()
        finally:
            segment_add.kernel_ok, segment_add.BAND, segment_add.BLOCK = was

    lines, want = [], None
    forms = [("scatter", None)] + [("kernel", tuple(map(int, b.split("x"))))
                                   for b in a.kernel_blocks.split(",")]
    for form, blocks in forms:
        fn = program(blocks)
        got = fn(y0, rows, toks)
        line = dict(cell=name, combine=form, blocks=blocks, T=T, D=c["D"], chunk_rows=B,
                    n_rows=n_rows, ms_a_call=None if a.toy else timed(fn, (y0, rows, toks), a.reps) / reps)
        if want is None:
            want = got
        else:
            line["vs_scatter"] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        if a.ops and not a.toy:
            line["device_ops_ms_a_call"] = [[n_, round(ms / reps, 4)] for n_, ms in
                                            device_ops(fn, (y0, rows, toks))]
        lines.append(line)
        print(json.dumps(line), flush=True)
    if not a.toy:  # a chunk without a row: a grid of no step, y as it was to the bit
        same = jax.jit(lambda y, rows, tok: moe_lib._add_rows(y, rows, tok, 0))(y0, rows, tok)
        print(json.dumps(dict(cell=name, combine="kernel", n_rows=0,
                              y_kept=bool((same == y0).all()))), flush=True)
    return lines


def device_ops(fn, args, top=12):
    from benchmark import trace_reduce

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        got = trace_reduce.reduce_trace(trace_reduce.load_xplane(trace_reduce.find_xplane(d)), top)
    return [[name, round(s / 3 * 1e3, 3)] for name, s in got["device_ops"]] if got else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tiles", default="128x4096,256x4096,512x4096",
                    help="row tile x chunk rows, comma-separated")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--combine", action="store_true")
    ap.add_argument("--kernel-blocks", default=f"{segment_add.BAND}x{segment_add.BLOCK}",
                    help="with --combine: the kernel's band x block, comma-separated")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    if not a.toy and jax.default_backend() != "tpu":
        sys.exit("no chip: a time comes from the chip alone (--toy walks the plumbing)")
    parent = None
    if a.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_moe", os.path.join(a.parent, "areal_tpu", "models", "moe.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    T, dtype = (256, jnp.float32) if a.toy else (16384, jnp.bfloat16)
    lines = []
    for name in a.cells.split(","):
        c = dict(CELLS[name])
        if a.toy:
            c.update(D=32, F=48, pairs=c["pairs"] * T // 16384)
        k, held, mats = c["k"], c["held"], c["mats"]
        experts = c.get("experts", 128)
        moe = MoEConfig(num_experts=experts, top_k=k, dispatch="dropless", score_func="sigmoid",
                        routed_scaling_factor=c["scale"], experts_held=(0, held))
        act = moe_lib.activation_fn(c["act"])
        choice, mask, pairs = routing(a.seed, T, k, held, c["real"], c["pairs"], experts)
        ks = jax.random.split(jax.random.PRNGKey(a.seed), 6)
        x = jax.random.normal(ks[0], (T, c["D"])).astype(dtype)
        r = jax.random.normal(ks[1], (T, c["D"])).astype(dtype)
        mp = {m: (0.02 * jax.random.normal(key, (held, c["F"], c["D"]) if m == mats[-1]
                                           else (held, c["D"], c["F"]))).astype(dtype)
              for m, key in zip(mats, ks[2:])}
        gate = jax.random.uniform(ks[5], (k * T,), minval=0.05, maxval=0.6) * c["scale"]

        def programs(lib):
            call = held_call(lib, moe, act, mats, choice, mask, T, k)

            def loss(x, mp, gate, r):
                y, n_pairs, rows, *_ = call(x, mp, gate)
                return (y.astype(jnp.float32) * r).sum(), (y, n_pairs, rows)

            return jax.jit(call), jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

        want = None
        runs = [("parent", parent, None)] if parent else []
        runs += [(f"tile{t}", moe_lib, tuple(map(int, t.split("x")))) for t in a.tiles.split(",")]
        for label, lib, tile in runs:
            if tile:
                moe_lib._HELD_ROW_TILE, moe_lib._HELD_CHUNK_ROWS = tile
                tile = tile[0]
            fwd, both = programs(lib)
            (_, (y, n_pairs, rows)), grads = both(x, mp, gate, r)
            line = dict(cell=name, form=label, T=T, pairs=int(n_pairs), rows_run=int(rows),
                        tiles=int(rows) // tile if tile else None,
                        fwd_ms=None if a.toy else timed(fwd, (x, mp, gate), a.reps),
                        fwd_bwd_ms=None if a.toy else timed(both, (x, mp, gate, r), a.reps))
            assert int(n_pairs) == pairs
            got = [y] + jax.tree_util.tree_leaves(grads)
            if want is None:
                want = got
            else:  # against the first form run: largest difference over largest value
                line["vs_first"] = [float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max()
                                          / (jnp.abs(w.astype(jnp.float32)).max() + 1e-30))
                                    for g, w in zip(got, want)]
            if a.ops and not a.toy:
                line["device_ops_ms"] = device_ops(both, (x, mp, gate, r))
            lines.append(line)
            print(json.dumps(line), flush=True)
        if a.combine:
            seen = set()
            for t in a.tiles.split(","):
                moe_lib._HELD_ROW_TILE, moe_lib._HELD_CHUNK_ROWS = map(int, t.split("x"))
                if moe_lib._HELD_CHUNK_ROWS not in seen:
                    seen.add(moe_lib._HELD_CHUNK_ROWS)
                    lines += combine_lines(name, c, choice, mask, T, dtype, a)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
