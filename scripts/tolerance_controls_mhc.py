#!/usr/bin/env python3
"""Controls for `xing4-d5e8-train-ppo-8k`'s `logprob_tolerance`: what each
limit must fail, measured on the cell's own configuration with seeded
bf16 weights and random token ids.

    python scripts/tolerance_controls_mhc.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control: the absolute logprob differences (worst position, a
sequence's mean) and `ok`, the verdict of the benchmark's own comparison
(`benchmark/model.compare_with_reference` under the cell's
`logprob_tolerance`, the control's logprobs handed in as the system's):
true for `engine` and `engine_f32`, false for every control, or the
script exits 1.

- `engine`: the program (bf16, the pair kernels at q/k 192 against v 128,
  the stream kernels) against the plain reference, next-token logprobs as
  the cell's check compares them; `engine_f32`: the program computing in
  float32 at the highest matmul precision on the same weights: what is
  left when precision is taken out.
- `float8`: the reference against itself with every matrix rounded to
  float8 e4m3 (a precision below bf16): `mean` must fail.
- the reference against itself with a part of the mathematics changed,
  each of which must fail a limit: `h_res_identity` (H_res = I: the
  streams never mix), `sinkhorn_1` (one Sinkhorn iteration for twenty),
  `h_post_one` (H_post = 1: every stream takes a sublayer's output
  whole), `plain_rope` (the YaRN table left unscaled), `no_mscale` (the
  softmax scale without mscale^2).

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
from benchmark.reference import xing4_0 as ref

CONFIG, CELL = "xing4.0-d5-e8", "xing4-d5e8-train-ppo-8k"

_JITTED = {}
_PLAIN = {k: getattr(ref, k) for k in (
    "sinkhorn", "hyper_coefficients", "yarn_inv_freq", "softmax_scale")}


def _h_post_one(X, hp, hf):
    h_pre, h_post, h_res = _PLAIN["hyper_coefficients"](X, hp, hf)
    return h_pre, jnp.ones_like(h_post), h_res


# control -> the reference's module attributes replaced while it is traced
CONTROLS = {
    "h_res_identity": dict(sinkhorn=lambda m, iters, eps: jnp.broadcast_to(
        jnp.eye(m.shape[-1], dtype=m.dtype), m.shape)),
    "sinkhorn_1": dict(sinkhorn=lambda m, iters, eps: _PLAIN["sinkhorn"](m, 1, eps)),
    "h_post_one": dict(hyper_coefficients=_h_post_one),
    "plain_rope": dict(yarn_inv_freq=lambda d, theta, rs: _PLAIN["yarn_inv_freq"](d, theta, None)),
    "no_mscale": dict(softmax_scale=lambda hf: _PLAIN["softmax_scale"](
        dict(hf, rope_scaling=None))),
}


def reference(params, hf, ids, pad_to, control="plain"):
    """The reference's logprobs of one sequence (padded to `pad_to`, so
    that a control compiles once), under a control's patch."""
    n = len(ids)
    small = {k: hf[k] for k in ref._KEYS if k in hf}
    if control not in _JITTED:
        _JITTED[control] = jax.jit(lambda p, i: ref._forward(p, i, small))
    patch = CONTROLS.get(control, {})
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        return np.asarray(_JITTED[control](params, ref._padded(ids, pad_to)))[: n - 1]
    finally:
        for k in patch:
            setattr(ref, k, _PLAIN[k])


def to_float8(params):
    """Every matrix (the hyper-connections' `phi` among them; not the
    norms, the gates `a`, the biases `b` nor the selection bias) rounded
    to float8 e4m3."""
    def one(path, a):
        name = jax.tree_util.keystr(path)
        if a.ndim >= 2 and "norm" not in name and not name.endswith(
                ("['a']", "['b']", "['expert_bias']")) and "ln" not in name:
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


def program_row(params, cfg, ids, seg, pos, attn):
    """Next-token logprobs [T] of one packed row, through the engine's
    path: `forward` to the hidden states, then the fused head."""
    def run(p):
        hidden = forward(p, cfg, ids[None], seg[None], pos[None], attn_impl=attn,
                         output="hidden", bands=True)
        return fused_next_token_logprobs(hidden, p["head"]["weight"], ids[None], seg[None])[0]
    return np.asarray(jax.jit(run)(params))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--lengths", type=int, nargs="+", default=[2240, 6144])
    ap.add_argument("--out", default=None)
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--toy", action="store_true",
                    help="toy widths, float32, the einsum attention: the plumbing, on a CPU")
    args = ap.parse_args()
    # the reference is traced anew in every comparison: keep what it compiles to
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR") or os.path.join(manifest.BENCH_DIR, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    load = lambda kind, name: json.load(open(os.path.join(
        manifest.BENCH_DIR, kind, f"{name}.json")))
    hf = manifest.hf_config(load("configs", CONFIG), args.toy)
    tol = load("cells", CELL)["logprob_tolerance"]
    cfg = model.transformer_config(hf, "float32" if args.toy else "bfloat16")
    cfg32 = model.transformer_config(hf, "float32")
    attn = "reference" if args.toy else "splash"
    if args.toy:
        args.lengths = [70, 200]
    pad_to = max(args.lengths)
    rows = []

    def emit(control, seed, params, ids, got):
        """`got` against the plain reference on `params`, by the
        benchmark's own comparison under the cell's limits."""
        res = model.compare_with_reference(
            params, hf, "xing4_0", [dict(name=control, token_ids=ids, first=0, got=got)],
            tol, pad_to)
        rows.append(dict(control=control, seed=seed, positions=len(ids), max=res["worst"],
                         mean=res["worst_mean"], ok=res["ok"]))
        print(json.dumps(rows[-1]), flush=True)

    for seed in args.seeds:
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        for n in args.lengths:
            ids = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            emit("float8", seed, params, ids, reference(to_float8(params), hf, ids, pad_to))
            for control in CONTROLS:
                emit(control, seed, params, ids, reference(params, hf, ids, pad_to, control))
            if args.reference_only:
                continue
            t = -(-n // 128) * 128  # a row as the engine packs it: a multiple of 128
            seg = (np.arange(t) < n).astype(np.int32)
            row = (jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                   jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit("engine", seed, params, ids, program_row(params, cfg, *row, attn)[: n - 1])
            with jax.default_matmul_precision("highest"):
                wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
                emit("engine_f32", seed, params, ids,
                     program_row(wide, cfg32, *row, attn)[: n - 1])
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    wrong = [r for r in rows if r["ok"] != r["control"].startswith("engine")]
    if wrong and not args.toy:  # toy widths in float32 say nothing of the cell's limits
        sys.exit(f"{len(wrong)} line(s) on the wrong side of {tol}: "
                 f"{sorted({r['control'] for r in wrong})}")


if __name__ == "__main__":
    main()
