#!/usr/bin/env python3
"""Controls for `nemotron3n-d9e8-train-ppo-long`'s `logprob_tolerance`, on
the chip: what each limit must fail, measured on the cell's own
configuration with seeded bf16 weights and random token ids.

    python scripts/tolerance_controls_hybrid.py [--seeds 1 2] [--out chiprun_out/x.jsonl]

A line a control, absolute next-token logprob differences (worst
position, a sequence's mean):

- `float8`: the plain reference against itself with every matrix rounded
  to float8 e4m3 (a precision below bf16): `mean` must fail.
- `decay_bf16`: the reference against itself with the recurrence's dt A
  and decay exp(dt A) rounded to bf16 (the program keeps them float32).
- `router_bf16`: the reference against itself with nothing changed but
  the router's input rounded to bf16: what routing flips alone cost.
- `state_runs_on`, `taps_run_on`, `both_run_on`: the program (bf16,
  splash) on a packed row of sequences against itself with the
  state-space scan, the convolution, or both given one segment for the
  whole row, so that they run on across every sequence start; compared
  on all sequences but the row's first: `max` must fail.
- `engine`: the program against the reference, as the cell's check does.
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
from areal_tpu.ops import ssm as ssm_lib
from benchmark import manifest, model
from benchmark.reference import nemotron_h as ref

CONFIG = "nemotron-3-nano-d9-e8"
_JITTED = {}


def reference(params, hf, ids, control="plain", decay_dtype=None, **patch):
    """The reference's logprobs of one sequence (padded to 6,144 so that
    a control compiles once), with module attributes of the reference
    replaced while it is traced (a control)."""
    n = len(ids)
    padded = -(-max(n, 6144) // ref.ROWS) * ref.ROWS
    full = jnp.asarray(np.concatenate([ids, np.zeros(padded - n, np.int32)]))
    if control not in _JITTED:
        _JITTED[control] = jax.jit(lambda p, i: ref._forward(p, i, hf, decay_dtype))
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


def router_in_bf16(h, mlp, hf):
    """`ref.expert_layer`, the router alone fed its input rounded to bf16."""
    s = jax.nn.sigmoid(jax.lax.reduce_precision(h, 8, 7) @ mlp["router"])
    routed = hf.get("num_experts_routed", hf["n_routed_experts"])
    first, held = hf.get("experts_held_first", 0), hf["n_routed_experts"]
    _, chosen = jax.lax.top_k(s + mlp["expert_bias"], hf["num_experts_per_tok"])
    sc = jnp.take_along_axis(s, chosen, axis=-1)
    w = sc / (jnp.sum(sc, axis=-1, keepdims=True) + 1e-20) * hf["routed_scaling_factor"]
    weights = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32) * w[..., None], axis=1)
    m = ref._relu2_mlp(h, mlp["shared"])
    for e in range(held):
        m = m + weights[:, first + e, None] * ref._relu2_mlp(
            h, {k: mlp[k][e] for k in ("w_in", "w_out")})
    return m


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
            want = reference(params, hf, ids)
            emit(control="float8", seed=seed, positions=n,
                 **stats(reference(to_float8(params), hf, ids), want))
            emit(control="decay_bf16", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, "decay", jnp.bfloat16), want))
            emit(control="router_bf16", seed=seed, positions=n,
                 **stats(reference(params, hf, ids, "router", expert_layer=router_in_bf16), want))
            t = -(-n // 128) * 128  # a row as the engine packs it: a multiple of 128
            seg = (np.arange(t) < n).astype(np.int32)
            got = program_row(params, cfg, jnp.asarray(np.pad(ids, (0, t - n))), jnp.asarray(seg),
                              jnp.asarray(np.arange(t, dtype=np.int32) * seg))
            emit(control="engine", seed=seed, positions=n, **stats(got[: n - 1], want))
        # the boundary: a row of 16,384 holding sequences of 5000, 3000, 2500, 1100
        T, lens = (256, [80, 60, 50, 30]) if args.toy else (16384, [5000, 3000, 2500, 1100])
        ids = rng.integers(0, cfg.vocab_size, T).astype(np.int32)
        seg, pos, o = np.zeros(T, np.int32), np.zeros(T, np.int32), 0
        for j, l in enumerate(lens):
            seg[o:o + l], pos[o:o + l] = j + 1, np.arange(l)
            o += l
        later = (seg[:-1] > 1) & (seg[1:] == seg[:-1])  # scored, not in the first sequence
        row = [jnp.asarray(a) for a in (ids, seg, pos)]
        base = program_row(params, cfg, *row)
        whole = lambda s: jnp.where(s > 0, 1, 0)
        scan0, conv0, mixer0 = ssm_lib.chunked_scan, ssm_lib.causal_conv, ssm_lib.ssm_mixer
        runs_on = dict(
            state=lambda *a: scan0(*a[:5], whole(a[5]), a[6]),
            taps=lambda x, w, b, s: jnp.where((s > 0)[..., None], conv0(x, w, b, whole(s)), 0))
        for name, broken in (("state_runs_on", ("state",)), ("taps_run_on", ("taps",)),
                             ("both_run_on", ("state", "taps"))):
            scan = runs_on["state"] if "state" in broken else scan0
            ssm_lib.causal_conv = runs_on["taps"] if "taps" in broken else conv0
            ssm_lib.ssm_mixer = lambda *a, **k: mixer0(*a, scan=scan, **k)
            try:
                got = program_row(params, cfg, *row)
            finally:
                ssm_lib.causal_conv, ssm_lib.ssm_mixer = conv0, mixer0
            emit(control=name, seed=seed, positions=int(later.sum()),
                 **stats(got[later], base[later]))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
