#!/usr/bin/env python3
"""dq, dk and dv of this tree's pair kernels against those of another
checkout on the same inputs, on the chip, at one row of 16,384.

    python scripts/pair_backward_check.py [--parent _parent] [--out chiprun_out/x.jsonl]
        [--shapes group_1_at_192_128 ..]

`--parent DIR` is a `git archive` of the commit to compare with. A line a
shape x layout: the largest difference of each gradient as a share of its
largest value, how many elements differ, and how many differ between two
calls of this tree's own. The backward kernel
(`ops/pallas/splash_pairs.py`) keeps a q block's sum of dq in HBM between
the block's visits and reads it back by copies of its own making: a read
that overtook the write before it would show as a difference far above one
rounding of bf16, or between the two calls. Interpret mode cannot show
it: its copies are immediate; `--toy` walks the script there at a small
size, for its plumbing.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

# (q heads, kv heads, head size of q and k, of v, window)
SHAPES = {
    "group_8_at_128": (32, 4, 128, 128, None),
    "group_1_at_192_128": (32, 32, 192, 128, None),
    "group_16_at_64_128": (16, 1, 64, 128, None),
}
# sequence lengths from the row's start; the rest is padding
LAYOUTS = {
    "one_sequence": [16384],
    "three": [3000, 5000, 4000],
    "fourteen_of_1100": [1100] * 14,
    "ends_in_the_next_kv_blocks_first_q_block": [1500, 1700, 2500, 3300],
    "fifty_of_300": [300] * 50,
    "all_padding": [],
}


def row(t, lens):
    import jax.numpy as jnp
    import numpy as np

    seg, pos, at = np.zeros((1, t), np.int32), np.zeros((1, t), np.int32), 0
    for i, n in enumerate(lens):
        seg[0, at:at + n], pos[0, at:at + n] = i + 1, np.arange(n)
        at += n
    return jnp.asarray(seg), jnp.asarray(pos)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=os.path.join(ROOT, "_parent"))
    ap.add_argument("--out", default=None)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--shapes", nargs="+", choices=sorted(SHAPES), default=sorted(SHAPES))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import splash_shape_sweep as sweep

    t, shapes = 16384, {name: SHAPES[name] for name in args.shapes}
    if args.toy:
        t, shapes = 2048, {"toy": (4, 2, 32, 32, None)}
    elif jax.default_backend() != "tpu":
        sys.exit("pair_backward_check compares compiled kernels: needs a TPU (or --toy)")
    trees = [("here", *sweep.attention_module()),
             ("parent", *sweep.attention_module(args.parent))]
    rows = [(name, row(t, [n * t // 16384 for n in lens])) for name, lens in LAYOUTS.items()]
    if not args.toy:
        pool = sweep.pool_rows("ppo-packed-long-2b", t)
        rows += [(f"pool_{i}", (jnp.asarray(pool[0][i]), jnp.asarray(pool[1][i])))
                 for i in (0, 5, 11)]
    lines = []
    for shape, (hq, hkv, hd, hd_v, window) in shapes.items():
        qkv = sweep.inputs(1, t, hq, hkv, hd, hd_v, seed=3)
        dout = jnp.asarray(np.random.RandomState(5).randn(1, t, hq, hd_v), jnp.float32)
        grads = {}
        for tree, A, pairs in trees:
            def loss(q, k, v, seg, pos, A=A):
                out = A.splash_packed_attention(q, k, v, seg, pos, window=window)
                return jnp.sum(out.astype(jnp.float32) * dout * (seg > 0)[..., None, None])

            grads[tree] = (pairs, jax.jit(jax.grad(loss, (0, 1, 2))))

        def run(tree, ids):
            pairs, fn = grads[tree]
            sweep.use_tree(pairs)  # what the first call traces
            return [np.asarray(g, np.float32) for g in fn(*qkv, *ids)]

        for layout, ids in rows:
            here, parent, again = run("here", ids), run("parent", ids), run("here", ids)
            line = dict(shape=shape, layout=layout)
            for name, a, b, c in zip(("dq", "dk", "dv"), here, parent, again):
                line[name] = dict(
                    rel=float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)),
                    differ=int((a != b).sum()), differ_again=int((a != c).sum()),
                    finite=bool(np.isfinite(a).all()))
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
