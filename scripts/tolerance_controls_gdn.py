#!/usr/bin/env python3
"""Controls for `qwen3next-d4e32-train-ppo-long`'s `logprob_tolerance`, on
the chip: what each limit must fail, measured on the cell's own
configuration with seeded bf16 weights and random token ids at the check's
lengths.

    python scripts/tolerance_controls_gdn.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst position,
a sequence's mean). The plain reference against itself with one thing
changed (`benchmark/reference/qwen3_next.py` `control`):

- `beta_one`: beta = 1 (the delta rule without its learning rate);
- `no_decay`: g = 0 (the rule without its gate: DeltaNet);
- `no_correction`: `S_t = exp(g) S + beta k v^T` (the rule without its
  read-to-correct term: gated linear attention);
- `no_conv`, `no_z`, `no_l2`: the convolution, `silu(z)`, the norms of q
  and k left out; `z_sigmoid`: `sigmoid(z)` for `silu(z)` (the other
  family's gate);
- `pair_mod`: value head j reads key head `j % 16` in place of `j // 2`;
- `rotary_whole`: the whole head turned (the table over 256) in place of
  its first 64 columns; `no_rotary`;
- `no_attn_gate`, `no_shared_gate`: the attention's and the shared
  expert's sigmoid gates left out;
- `w_for_1pw`: every `1 + w` norm scaling by w;
- `top8`: the 8 largest experts for the 10;
- `decay_bf16`: g and exp(g) rounded to bf16 (the program keeps them
  float32);
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
from benchmark.reference import qwen3_next as ref

CONFIG = "qwen3-next-d4-e32"
CONTROLS = ("beta_one", "no_decay", "no_correction", "no_conv", "no_z", "z_sigmoid", "no_l2",
            "pair_mod", "rotary_whole", "no_rotary", "no_attn_gate", "w_for_1pw",
            "no_shared_gate", "top8", "decay_bf16")
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


def router_in_bf16(h2, mlp, hf, control=None, _plain=ref.router_weights):
    """`ref.router_weights`, fed its input rounded to bf16: the routed
    weights of that choice, the experts on the float32 input."""
    return _plain(jax.lax.reduce_precision(h2, 8, 7), mlp, hf, control)


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
            for control in CONTROLS:
                emit(control=control, seed=seed, positions=n,
                     **stats(reference(params, hf, ids, pad_to, control), want))
            emit(control="float8", seed=seed, positions=n,
                 **stats(reference(to_float8(params), hf, ids, pad_to), want))
            emit(control="router_bf16", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, pad_to, router_weights=router_in_bf16), want))
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
