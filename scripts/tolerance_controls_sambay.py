#!/usr/bin/env python3
"""Controls for `phi4flash-d8-train-ppo-8k`'s `logprob_tolerance`, on the
chip: what each limit must fail, measured on the cell's own configuration
with seeded bf16 weights and random token ids.

    python scripts/tolerance_controls_sambay.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst
position, a sequence's mean):

- `engine`: the program (bf16, splash, the scan kernel) against the
  plain reference, as the cell's check does; `engine_f32`: the program
  computing in float32 at the highest matmul precision on the same
  weights: what is left when precision is taken out.
- `float8`: the reference against itself with every matrix rounded to
  float8 e4m3 (a precision below bf16): `mean` must fail.
- `window_256`, `window_none`: the reference against itself with the
  window layers' window halved, or gone.
- `one_softmax`: the reference against itself with the second softmax
  left out (lambda A2 = 0).
- `state_runs_on`, `taps_run_on`, `both_run_on`: the program on a packed
  row of sequences against itself with the selective scan, the
  convolution, or both running on across every sequence start; compared
  on all sequences but the row's first.
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
from areal_tpu.ops import selective_scan as scan_lib
from areal_tpu.ops import ssm as ssm_lib
from benchmark import manifest, model
from benchmark.reference import phi4flash as ref

CONFIG = "phi-4-mini-flash-d8"
ATTN = "splash"


_JITTED = {}


def reference(params, hf, ids, pad_to, control="plain", **patch):
    """The reference's logprobs of one sequence (padded to `pad_to`, so
    that a control compiles once), with module attributes of the
    reference replaced while it is traced (a control)."""
    n = len(ids)
    padded = -(-max(n, pad_to) // ref.ROWS) * ref.ROWS
    full = jnp.asarray(np.concatenate([ids, np.zeros(padded - n, np.int32)]))
    small = {k: hf[k] for k in ref._KEYS if k in hf}
    if control not in _JITTED:
        _JITTED[control] = jax.jit(lambda p, i: ref._forward(p, i, small))
    saved = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        return np.asarray(_JITTED[control](params, full))[: n - 1]
    finally:
        for k, v in saved.items():
            setattr(ref, k, v)


def to_float8(params):
    def one(path, a):
        if a.ndim >= 2 and jax.tree_util.keystr(path).count("norm") == 0:
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


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
    cfg32 = model.transformer_config(hf, "float32")
    if args.toy:
        global ATTN
        ATTN = "reference"
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
            want = reference(params, hf, ids, pad_to)
            emit(control="float8", seed=seed, positions=n,
                 **stats(reference(to_float8(params), hf, ids, pad_to), want))
            half = hf["sliding_window"] // 2
            emit(control=f"window_{half}", seed=seed, positions=n,
                 **stats(reference(params, dict(hf, sliding_window=half), ids, pad_to, "half"),
                         want))
            emit(control="window_none", seed=seed, positions=n,
                 **stats(reference(params, dict(hf, sliding_window=None), ids, pad_to, "none"),
                         want))
            emit(control="one_softmax", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, pad_to, "one",
                                   second_softmax_weight=lambda at, l0: 0.0), want))
            t = -(-n // 128) * 128  # a row as the engine packs it: a multiple of 128
            seg = (np.arange(t) < n).astype(np.int32)
            row = (jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                   jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit(control="engine", seed=seed, positions=n,
                 **stats(program_row(params, cfg, *row)[: n - 1], want))
            with jax.default_matmul_precision("highest"):
                wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
                emit(control="engine_f32", seed=seed, positions=n,
                     **stats(program_row(wide, cfg32, *row)[: n - 1], want))
        # the boundary: a row of 8,192 holding sequences of 3000, 2500, 1500, 1000
        T, lens = (256, [80, 60, 50, 30]) if args.toy else (8192, [3000, 2500, 1500, 1000])
        ids = rng.integers(0, cfg.vocab_size, T).astype(np.int32)
        seg, pos, o = np.zeros(T, np.int32), np.zeros(T, np.int32), 0
        for j, l in enumerate(lens):
            seg[o:o + l], pos[o:o + l] = j + 1, np.arange(l)
            o += l
        later = (seg[:-1] > 1) & (seg[1:] == seg[:-1])  # scored, not in the first sequence
        row = [jnp.asarray(a) for a in (ids, seg, pos)]
        base = program_row(params, cfg, *row)
        keeps0, conv0 = scan_lib.sequence_keeps, ssm_lib.causal_conv
        whole = lambda s: jnp.where(s > 0, 1, 0)
        runs_on = dict(
            state=lambda s: jnp.ones(s.shape, jnp.float32),
            taps=lambda x, w, b, s: jnp.where((s > 0)[..., None], conv0(x, w, b, whole(s)), 0))
        for name, broken in (("state_runs_on", ("state",)), ("taps_run_on", ("taps",)),
                             ("both_run_on", ("state", "taps"))):
            scan_lib.sequence_keeps = runs_on["state"] if "state" in broken else keeps0
            ssm_lib.causal_conv = runs_on["taps"] if "taps" in broken else conv0
            try:
                got = program_row(params, cfg, *row)
            finally:
                scan_lib.sequence_keeps, ssm_lib.causal_conv = keeps0, conv0
            emit(control=name, seed=seed, positions=int(later.sum()),
                 **stats(got[later], base[later]))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
