#!/usr/bin/env python3
"""Controls for `joyai-d6e16-train-ppo-long`'s `logprob_tolerance`, and the
prediction module's logprobs against the reference's: what each limit
must fail, measured on the cell's own configuration with seeded bf16
weights and random token ids.

    python scripts/tolerance_controls_latent.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute logprob differences (worst position, a
sequence's mean):

- `engine`, `engine_mtp`: the program (bf16, splash at q/k 192 against v
  128) against the plain reference: next-token logprobs as the cell's
  check compares them, and the prediction module's logprobs of the token
  two on against `mtp_logprobs` (the runner does not read those);
  `engine_f32`, `engine_mtp_f32`: the program computing in float32 at the
  highest matmul precision on the same weights: what is left when
  precision is taken out.
- `float8`: the reference against itself with every matrix rounded to
  float8 e4m3 (a precision below bf16): `mean` must fail.
- `no_rope`: the reference against itself with the rotary part left
  unturned; `no_kv_norm`: with the RMSNorm inside the kv projection left
  out. Each must fail a limit.

`--reference-only` leaves the program's lines out: the controls are the
reference against itself in float32, which a CPU computes as the chip
does.
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
from benchmark.reference import joyai_llm_flash as ref

CONFIG = "joyai-llm-flash-d6-e16"
ATTN = "splash"

_JITTED = {}


def reference(params, hf, ids, pad_to, control="plain", mtp=False, **patch):
    """The reference's logprobs of one sequence (padded to `pad_to`, so
    that a control compiles once), with module attributes of the
    reference replaced while it is traced (a control)."""
    n = len(ids)
    small = {k: hf[k] for k in ref._KEYS if k in hf}
    key = (control, mtp)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda p, i: ref._forward(p, i, small, mtp=mtp))
    saved = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        return np.asarray(_JITTED[key](params, ref._padded(ids, pad_to)))[: n - 1 - mtp]
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
    """(next-token logprobs [T], the module's of the token two on [T]) of
    one packed row, through the engine's path: `forward` to the hidden
    states, then the fused head."""
    def run(p):
        hidden, x_mtp = forward(p, cfg, ids[None], seg[None], pos[None],
                                attn_impl=ATTN, output="hidden", mtp=True)
        head = p["head"]["weight"]
        return (fused_next_token_logprobs(hidden, head, ids[None], seg[None])[0],
                fused_next_token_logprobs(x_mtp, head, ids[None], seg[None], shift=2)[0])
    return [np.asarray(a) for a in jax.jit(run)(params)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--lengths", type=int, nargs="+", default=[2240, 6144])
    ap.add_argument("--out", default=None)
    ap.add_argument("--reference-only", action="store_true")
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
            emit(control="no_rope", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, pad_to, "no_rope",
                                   _rope=lambda x, pos, theta: x), want))
            emit(control="no_kv_norm", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, pad_to, "no_kv_norm",
                                   _kv_latent=lambda c_kv, at, eps: c_kv), want))
            if args.reference_only:
                continue
            want2 = reference(params, hf, ids, pad_to, mtp=True)
            t = -(-n // 128) * 128  # a row as the engine packs it: a multiple of 128
            seg = (np.arange(t) < n).astype(np.int32)
            row = (jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                   jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            got, got2 = program_row(params, cfg, *row)
            emit(control="engine", seed=seed, positions=n, **stats(got[: n - 1], want))
            emit(control="engine_mtp", seed=seed, positions=n, **stats(got2[: n - 2], want2))
            with jax.default_matmul_precision("highest"):
                wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
                got, got2 = program_row(wide, cfg32, *row)
            emit(control="engine_f32", seed=seed, positions=n, **stats(got[: n - 1], want))
            emit(control="engine_mtp_f32", seed=seed, positions=n,
                 **stats(got2[: n - 2], want2))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
