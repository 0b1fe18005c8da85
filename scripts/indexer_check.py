#!/usr/bin/env python3
"""What `keye-d6e16-train-ppo-long`'s check of logprobs cannot say alone:
the engine path's choice of keys against the reference's, its KL against
the reference's, and the controls its `logprob_tolerance` must fail;
measured on the cell's own configuration with seeded bf16 weights and
random token ids, a sequence a packed row alone (the kernels' path).

    python scripts/indexer_check.py [--seeds 1 2] [--lengths 2240 6144] [--out chiprun_out/x.jsonl]

A line a control, absolute logprob differences (worst position, a
sequence's mean):

- `engine`: the program (bf16: `index_select`, the pair kernels under its
  mask) against the plain reference, next-token logprobs as the cell's
  check compares them; `engine_f32`: the program computing in float32 at
  the highest matmul precision on the same weights: what is left when
  precision is taken out.
- `choice` / `choice_f32`: of the cells either side scores (a query's
  causal prefix, all layers), the share on which the engine's choice and
  `indexer_choice` agree, over all layers and layer by layer (a layer's
  input is the stack's output so far: what earlier layers' rounding and
  choices moved reaches its scores); of the rest, how far the
  reference's score lies from the reference's threshold, in units of
  bf16's rounding of the threshold (2^-8 |tau|) and in units of the
  spread of the query's own scores (their standard deviation over its
  prefix): the worst and the 99th percentile; and the engine's KL (a
  mean over layers and tokens) against `indexer_kl`'s.
- `float8`: the reference against itself with every matrix rounded to
  float8 e4m3 (a precision below bf16).
- `no_choice`: the reference attending over the whole prefix;
  `last_keys`: over the last topk keys instead of the indexer's;
  `no_index_rope`: with the indexer's rotary left out. Each must fail a
  limit.

`--reference-only` leaves the program's lines out (the controls are the
reference against itself in float32, which a CPU computes as the chip
does); `--toy` walks it at toy widths on the CPU.
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
from areal_tpu.ops.loss import fused_next_token_logprobs
from benchmark import manifest, model
from benchmark.reference import keye_vl2 as ref

CONFIG = "keye-vl-2.0-d6-e16"


def to_float8(params):
    def one(path, a):
        if a.ndim >= 2 and jax.tree_util.keystr(path).count("norm") == 0:
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


def program_row(params, cfg, ids, seg, pos, attn):
    """(next-token logprobs [T], the layers' choice [L, T, T], the KL's
    mean over layers and real tokens) of one packed row, through the
    engine's path: `forward` to the hidden states, then the fused head."""
    def run(p):
        hidden, sums, (choice, _) = forward(
            p, cfg, ids[None], seg[None], pos[None], attn_impl=attn, output="hidden",
            return_aux=True, index_loss=True, index_choice=True)
        lp = fused_next_token_logprobs(hidden, p["head"]["weight"], ids[None], seg[None])[0]
        return lp, choice[:, 0], sums["index_kl"] / (cfg.n_layers * jnp.sum(seg > 0))
    return [np.asarray(a) for a in jax.jit(run)(params)]


def choice_line(got, kl, params, hf, ids, pad_to):
    n = len(ids)
    want = ref.indexer_choice(params, hf, ids, pad_to)
    scores = ref.indexer_scores(params, hf, ids, pad_to)
    got = got[:, :n, :n]
    seen = np.tril(np.ones((n, n), bool))[None]
    differ = (got != want) & seen
    tau = np.where(want, scores, np.inf).min(axis=-1)  # the least chosen score
    layer, q, _ = np.nonzero(differ)
    off = np.abs(scores[differ] - tau[layer, q])
    gap = off / (2.0 ** -8 * np.abs(tau[layer, q]))
    held = np.where(seen, scores, np.nan)
    spread = off / np.nanstd(held, axis=-1)[layer, q]
    top = lambda a, pct: float(np.percentile(a, pct)) if len(a) else 0.0
    want_kl = float(ref.indexer_kl(params, hf, ids, pad_to).mean())
    return dict(cells=int(seen.sum() * len(got)), agree_pct=100.0 * (1.0 - differ.sum() / (
        seen.sum() * len(got))), differ=int(differ.sum()),
        agree_pct_by_layer=[100.0 * (1.0 - d.sum() / seen.sum()) for d in differ],
        chosen=[int(got.sum()), int(want.sum())],
        gap_bf16_roundings_max=top(gap, 100), gap_bf16_roundings_p99=top(gap, 99),
        gap_score_spreads_max=top(spread, 100), gap_score_spreads_p99=top(spread, 99),
        gap_score_spreads_p50=top(spread, 50),
        kl=[float(kl), want_kl], kl_rel_err=abs(float(kl) - want_kl) / want_kl)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--lengths", type=int, nargs="+", default=[2240, 6144])
    ap.add_argument("--out", default=None)
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--engine-only", action="store_true",
                    help="leave the controls and the float32 program out")
    ap.add_argument("--toy", action="store_true",
                    help="toy widths, float32, the einsum attention: the plumbing, on a CPU")
    args = ap.parse_args()
    hf = manifest.hf_config(json.load(open(os.path.join(
        manifest.BENCH_DIR, "configs", f"{CONFIG}.json"))), args.toy)
    cfg = model.transformer_config(hf, "float32" if args.toy else "bfloat16")
    cfg32 = model.transformer_config(hf, "float32")
    attn = "reference" if args.toy else "splash"
    if args.toy:
        args.lengths = [70, 200]
    pad_to = max(args.lengths)
    rows = []

    def emit(**row):
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
            want = ref.next_token_logprobs(params, hf, ids, pad_to)
            controls = () if args.engine_only else (
                ("no_choice", dict(mode="all")), ("last_keys", dict(mode="last")),
                ("no_index_rope", dict(index_rotary=False)))
            if not args.engine_only:
                emit(control="float8", seed=seed, positions=n, **stats(
                    ref.next_token_logprobs(to_float8(params), hf, ids, pad_to), want))
            for name, control in controls:
                emit(control=name, seed=seed, positions=n,
                     **stats(ref.next_token_logprobs(params, hf, ids, pad_to, **control), want))
            if args.reference_only:
                continue
            t = -(-n // 128) * 128  # a row as the engine packs it: a multiple of 128
            seg = (np.arange(t) < n).astype(np.int32)
            row = (jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                   jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            got, choice, kl = program_row(params, cfg, *row, attn)
            emit(control="engine", seed=seed, positions=n, **stats(got[: n - 1], want))
            emit(control="choice", seed=seed, positions=n,
                 **choice_line(choice, kl, params, hf, ids, pad_to))
            if args.engine_only:
                continue
            with jax.default_matmul_precision("highest"):
                wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
                got, choice, kl = program_row(wide, cfg32, *row, attn)
            emit(control="engine_f32", seed=seed, positions=n, **stats(got[: n - 1], want))
            emit(control="choice_f32", seed=seed, positions=n,
                 **choice_line(choice, kl, params, hf, ids, pad_to))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
