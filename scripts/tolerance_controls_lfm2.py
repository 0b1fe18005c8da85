#!/usr/bin/env python3
"""Controls for `lfm2-d5e16-train-ppo-8k`'s `logprob_tolerance`, on the chip:
what each limit must fail, measured on the cell's own configuration with
seeded bf16 weights and random token ids at the check's lengths.

    python scripts/tolerance_controls_lfm2.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst position, a
sequence's mean) and whether the cell's limits pass it (`ok`). The plain
reference against itself with one thing changed
(`benchmark/reference/lfm2_moe.py` `control`):

- `taps_reversed`, `no_B`, `no_C` (a gate left out), `conv_silu` (an
  activation the model does not have), `no_conv` (the tap on the position
  itself alone);
- `select_no_bias` (the experts chosen on the score), `top_2` for top-4,
  `no_renorm`, `norm_eps_1e-20` (the router's constant: nothing can separate
  1e-6 beside a sum of four scores; listed so that the reading is on record);
- `no_qk_norm`, `no_rope`;
- `float8`: every matrix rounded to float8 e4m3 (the nearest precision below
  bf16);
- `router_bf16`: nothing changed but the router's input rounded to bf16:
  what routing flips alone cost (no limit must fail it: it bounds `max`
  from below).

And `engine`: the program (bf16, its kernels, the band loop) against the
reference, as the cell's check does; `engine_f32`: the program computing in
float32 at the highest matmul precision on the same bf16 weights: what is
left between the two when rounding is taken away. (The convolution's stop at
a sequence's start has no control here: the reference takes one sequence a
call; `tests/model/test_lfm2_stack.py` holds it.) Exits 1 if `engine` fails
the cell's limits or a control that must fail them passes.
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.transformer import forward, init_params
from benchmark import manifest, model
from benchmark.reference import lfm2_moe as ref

CONFIG, CELL = "lfm2-8b-a1b-d5-e16", "lfm2-d5e16-train-ppo-8k"
CONTROLS = ("taps_reversed", "no_B", "no_C", "conv_silu", "no_conv", "select_no_bias", "top_2",
            "no_renorm", "no_qk_norm", "no_rope", "norm_eps_1e-20")
MAY_PASS = ("norm_eps_1e-20", "router_bf16", "engine", "engine_f32")

_JITTED = {}


def reference(params, hf, ids, pad_to, control=None, **patch):
    """The reference's logprobs of one sequence (padded so that a control
    compiles once), `control` its one departure; `patch`: module attributes
    of the reference replaced while it is traced."""
    n = len(ids)
    padded = -(-max(n, pad_to) // ref.ROWS) * ref.ROWS
    full = jnp.asarray(np.concatenate([ids, np.zeros(padded - n, np.int32)]))
    name = control or "+".join(patch) or "plain"
    saved = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        if name not in _JITTED:
            small = ref._small(hf)
            _JITTED[name] = jax.jit(lambda p, i: ref._forward(p, i, small, control))
        return np.asarray(_JITTED[name](params, full))[: n - 1]
    finally:
        for k, v in saved.items():
            setattr(ref, k, v)


def to_float8(params):
    def one(path, a):
        if a.ndim >= 2 and "norm" not in jax.tree_util.keystr(path):
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


def router_in_bf16(f, mlp, hf, control=None, _plain=ref.router_gates):
    """`ref.router_gates`, fed its input rounded to bf16: the gates of that
    choice, the experts on the float32 input."""
    return _plain(jax.lax.reduce_precision(f, 8, 7), mlp, hf, control)


def program_row(params, cfg, ids, seg, pos, attn):
    logits = jax.jit(lambda p: forward(p, cfg, ids[None], seg[None], pos[None],
                                       attn_impl=attn, bands=True))(params)[0]
    lp = jax.nn.log_softmax(logits, -1)
    return np.asarray(jnp.take_along_axis(lp[:-1], ids[1:, None], -1)[:, 0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--lengths", type=int, nargs="+", default=[2240, 6144])
    ap.add_argument("--out", default=None)
    ap.add_argument("--toy", action="store_true",
                    help="toy widths, float32, the einsum attention: the plumbing, on a CPU")
    args = ap.parse_args()
    load = lambda kind, name: json.load(open(os.path.join(
        manifest.BENCH_DIR, kind, f"{name}.json")))
    hf = manifest.hf_config(load("configs", CONFIG), args.toy)
    tol = load("cells", CELL)["logprob_tolerance"]
    cfg = model.transformer_config(hf, "float32" if args.toy else "bfloat16")
    attn = "reference" if args.toy else "splash"
    pad_to = max(args.lengths)
    rows = []

    def emit(control, seed, n, got, want):
        d = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
        row = dict(control=control, seed=seed, positions=n, max=float(d.max()),
                   mean=float(d.mean()))
        row["ok"] = bool(row["max"] <= tol["max"] and row["mean"] <= tol["mean"])
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in args.seeds:
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        for n in args.lengths:
            ids = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            want = reference(params, hf, ids, pad_to)
            for name in CONTROLS:
                emit(name, seed, n, reference(params, hf, ids, pad_to, name), want)
            emit("float8", seed, n, reference(to_float8(params), hf, ids, pad_to), want)
            emit("router_bf16", seed, n,
                 reference(params, hf, ids, pad_to, router_gates=router_in_bf16), want)
            t = -(-n // 1024) * 1024  # a row of whole bands
            seg = (np.arange(t) < n).astype(np.int32)
            row = (jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                   jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit("engine", seed, n, program_row(params, cfg, *row, attn)[: n - 1], want)
            exact = dataclasses.replace(cfg, compute_dtype="float32")
            with jax.default_matmul_precision("highest"):
                emit("engine_f32", seed, n, program_row(params, exact, *row, attn)[: n - 1], want)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    # `engine` must pass, every control outside `MAY_PASS` must fail
    wrong = [r for r in rows if (r["control"] == "engine" and not r["ok"])
             or (r["control"] not in MAY_PASS and r["ok"])]
    if wrong and not args.toy:
        print("limits " + json.dumps(tol) + " do not separate: "
              + json.dumps([(r["control"], r["seed"], r["positions"]) for r in wrong]))
        sys.exit(1)


if __name__ == "__main__":
    main()
