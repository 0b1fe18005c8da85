#!/usr/bin/env python3
"""Controls for `mellum2-d4e16-train-ppo-long`'s `logprob_tolerance`, on the
chip: what each limit must fail, measured on the cell's own configuration
with seeded bf16 weights and random token ids at the check's lengths.

    python scripts/tolerance_controls_mellum.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst position,
a sequence's mean). The plain reference against itself with one thing
changed (`benchmark/reference/mellum.py` `control`):

- `plain_on_full`: the full layer turned by the window layers' plain table
  (and so without the attention factor); `yarn_on_window`: the window
  layers turned by the full layer's YaRN table; `no_factor`: YaRN's
  frequencies with `cos` and `sin` of unit amplitude;
- `half_window` (512), `no_window`, `window_off_by_one` (1,023);
- `no_qk_norm`: q and k as projected; `top4`: the 4 largest experts for
  the 8; `no_renorm`: the chosen gates not divided by their sum;
- `float8`: every matrix rounded to float8 e4m3 (a precision below bf16);
- `router_bf16`: nothing changed but the router's input rounded to bf16:
  what routing flips alone cost (no limit must fail it: it bounds `max`
  from below).

And `engine`: the program (bf16, its kernels) against the reference, as
the cell's check does.
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
from benchmark.reference import mellum as ref

CONFIG = "mellum2-d4-e16"
S, F = "sliding_attention", "full_attention"


def controls(hf):
    w = hf["sliding_window"]
    return dict(
        plain_on_full=dict(tables={F: S}), yarn_on_window=dict(tables={S: F}),
        no_factor=dict(attention_factor=False), half_window=dict(window=w // 2),
        no_window=dict(window=None), window_off_by_one=dict(window=w - 1),
        no_qk_norm=dict(qk_norm=False), top4=dict(top_k=hf["num_experts_per_tok"] // 2),
        no_renorm=dict(renorm=False))


_JITTED = {}


def reference(params, hf, ids, pad_to, name="plain", control=None, **patch):
    """The reference's logprobs of one sequence (padded so that a control
    compiles once), `control` its one departure; `patch`: module attributes
    of the reference replaced while it is traced."""
    n = len(ids)
    padded = -(-max(n, pad_to) // ref.ROWS) * ref.ROWS
    full = jnp.asarray(np.concatenate([ids, np.zeros(padded - n, np.int32)]))
    if name not in _JITTED:
        small = ref._small(hf)
        _JITTED[name] = jax.jit(lambda p, i: ref._forward(p, i, small, control))
    saved = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
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


def router_in_bf16(h2, router, hf, top_k=None, renorm=True, _plain=ref.router_gates):
    """`ref.router_gates`, fed its input rounded to bf16: the gates of that
    choice, the experts on the float32 input."""
    return _plain(jax.lax.reduce_precision(h2, 8, 7), router, hf, top_k, renorm)


ATTN = "splash"


def program_row(params, cfg, ids, seg, pos):
    logits = jax.jit(lambda p: forward(p, cfg, ids[None], seg[None], pos[None],
                                       attn_impl=ATTN, bands=True))(params)[0]
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
            for name, control in controls(hf).items():
                emit(control=name, seed=seed, positions=n,
                     **stats(reference(params, hf, ids, pad_to, name, control), want))
            emit(control="float8", seed=seed, positions=n,
                 **stats(reference(to_float8(params), hf, ids, pad_to), want))
            emit(control="router_bf16", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, pad_to, "router_bf16",
                                   router_gates=router_in_bf16), want))
            t = -(-n // 1024) * 1024  # a row of whole bands
            seg = (np.arange(t) < n).astype(np.int32)
            got = program_row(params, cfg, jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                              jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit(control="engine", seed=seed, positions=n, **stats(got[: n - 1], want))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
