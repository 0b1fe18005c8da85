#!/usr/bin/env python3
"""Controls for `olmohybrid-d4-train-ppo-8k`'s `logprob_tolerance`, on the
chip: what each limit must fail, measured on the cell's own configuration
with seeded bf16 weights and random token ids at the check's lengths.

    python scripts/tolerance_controls_olmo_hybrid.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst position,
a sequence's mean), each with `passes`: whether the cell's limits would let
it through. The plain reference against itself with one thing changed
(`benchmark/reference/olmo_hybrid.py` `control`):

- `beta_sigmoid`: beta = sigmoid, not doubled;
- `no_decay`: g = 0 (the rule without its gate: DeltaNet);
- `no_correction`: `S_t = exp(g) S + beta k v^T` (the rule without its
  read-to-correct term: gated linear attention);
- `no_k_scale`: q's `K^-0.5` left out; `no_conv`: the convolutions left out;
- `z_sigmoid`: the output gate a sigmoid;
- `norm_in`: an RMSNorm on the way into every mixer and MLP, added;
  `no_out_norms`: the output norms left out;
- `qk_head_norm`: q and k normed a head, not over the width;
- `rotary`: a table at theta 10,000 applied to the attention layer's q and k;
- `v_halves`: the values' second 96 columns read as the first;
- `float8`: every matrix rounded to float8 e4m3 (a precision below bf16).

And `engine`: the program (bf16, its kernels) against the reference, as
the cell's check does; `engine_squarings`: the same with `(I + A)^-1` by
squarings (`ops/kda.RuleForm.doubling` off), what the doubling form is there
for. Exits 1 if the engine fails the cell's limits or the float8 or
beta_sigmoid control passes them.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.transformer import forward, init_params
from benchmark import manifest, model
from benchmark.reference import olmo_hybrid as ref

CONFIG = "olmo-hybrid-d4"
CELL = "olmohybrid-d4-train-ppo-8k"
CONTROLS = ("beta_sigmoid", "no_decay", "no_correction", "no_k_scale", "no_conv", "z_sigmoid",
            "norm_in", "no_out_norms", "qk_head_norm", "rotary", "v_halves")
MUST_FAIL = ("float8", "beta_sigmoid")
_JITTED = {}


def reference(params, hf, ids, pad_to, control=None, **patch):
    """The reference's logprobs of one sequence (padded so that a control
    compiles once), `control` its one departure; `patch`: module attributes
    of the reference replaced while it is traced."""
    n = len(ids)
    padded = -(-max(n, pad_to) // ref.ROWS) * ref.ROWS
    full = jnp.asarray(np.concatenate([ids, np.zeros(padded - n, np.int32)]))
    key = control or ",".join(patch) or "plain"
    if key not in _JITTED:
        small = ref._small(hf)
        _JITTED[key] = jax.jit(lambda p, i: ref._forward(p, i, small, control))
    saved = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        return np.asarray(_JITTED[key](params, full))[: n - 1]
    finally:
        for k, v in saved.items():
            setattr(ref, k, v)


def to_float8(params):
    def one(path, a):
        if a.ndim >= 2 and "norm" not in jax.tree_util.keystr(path):
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


ATTN = "splash"


def program_row(params, cfg, ids, seg, pos):
    logits = jax.jit(lambda p: forward(p, cfg, ids[None], seg[None], pos[None],
                                       attn_impl=ATTN))(params)[0]
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
    hf = manifest.hf_config(json.load(open(os.path.join(
        manifest.BENCH_DIR, "configs", f"{CONFIG}.json"))), args.toy)
    cfg = model.transformer_config(hf, "float32" if args.toy else "bfloat16")
    if args.toy:
        global ATTN
        ATTN = "reference"
    pad_to = max(args.lengths)
    tol = json.load(open(os.path.join(
        manifest.BENCH_DIR, "cells", f"{CELL}.json")))["logprob_tolerance"]
    rows = []

    def emit(**row):
        row["passes"] = row["max"] <= tol["max"] and row["mean"] <= tol["mean"]
        rows.append(row)
        print(json.dumps(row), flush=True)

    def stats(a, b):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        return dict(max=float(d.max()), mean=float(d.mean()))

    for seed in args.seeds:
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        for n in args.lengths:
            ids = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            want = reference(params, hf, ids, pad_to)
            for control in CONTROLS:
                emit(control=control, seed=seed, positions=n,
                     **stats(reference(params, hf, ids, pad_to, control), want))
            emit(control="float8", seed=seed, positions=n,
                 **stats(reference(to_float8(params), hf, ids, pad_to), want))
            t = -(-n // 1024) * 1024  # a row of whole bands (and of the taps' blocks)
            seg = (np.arange(t) < n).astype(np.int32)
            got = program_row(params, cfg, jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                              jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit(control="engine", seed=seed, positions=n, **stats(got[: n - 1], want))
            if not args.toy:
                from areal_tpu.ops import kda

                form = kda.RuleForm
                kda.RuleForm = lambda key_dim=None, doubling=False: form(key_dim, False)
                jax.clear_caches()
                try:
                    got = program_row(params, cfg, jnp.asarray(np.pad(ids, (0, t - n))),
                                      jnp.asarray(seg), jnp.asarray(
                                          np.arange(t, dtype=np.int32) * seg))
                finally:
                    kda.RuleForm = form
                    jax.clear_caches()
                emit(control="engine_squarings", seed=seed, positions=n,
                     **stats(got[: n - 1], want))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    bad = [r for r in rows if r["passes"] == (r["control"] in MUST_FAIL)
           and r["control"] in MUST_FAIL + ("engine",)]
    if bad and not args.toy:
        print("the limits do not separate:", json.dumps(bad))
        sys.exit(1)


if __name__ == "__main__":
    main()
