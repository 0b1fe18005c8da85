#!/usr/bin/env python3
"""Time the splash kernel alone, per row shape and per candidate run
shape, and fit `ops/attention.splash_cost` to what the chip says.

    python scripts/splash_shape_sweep.py --out chiprun_out/splash_sweep.jsonl
    python scripts/splash_shape_sweep.py --fit chiprun_out/splash_sweep.jsonl
    python scripts/splash_shape_sweep.py --chosen --out chiprun_out/splash_chosen.jsonl

The first form needs a TPU: for every `rows x t` of `--shapes` (default:
the sixteen training micro-batches and the forward-only lengths of the
benchmark's `ppo-packed` pool) it runs `splash_packed_attention` over
the rows at each candidate `(t', bq, bkv, bkvc)` — `t, t+128, ..`
up to the next multiple of 512, a few dividing blocks each — through
`--layers` chained calls in one program, forward alone and forward plus
the backward, and writes one JSON line a candidate. `--chosen`
times only what `splash_run_shape` picks beside today's `t' = t` at the
largest dividing blocks. `--fit` needs no device: least squares of
`splash_cost`'s constants on fwd + (fwd + bwd), the kernels one layer
runs under full remat.

`--check` times nothing: the call's output and gradients against the
einsum reference's. The kernels are what the call runs: for a row of
2048 or more alone in its call the repo's own over the row's list of
live block pairs (`ops/pallas/splash_pairs.py`), splash's static ones
and the fused backward otherwise. `--static` gives a row alone the
static kernels too, `--seq-len`
packs the rows with sequences that short and `--rows-from TRAFFIC` with
the micro-batches of one of the benchmark's pools, a line each.
`--group` and `--v-dim` set the kv heads (hq // group) and v's head
size (192 / 128: `--hd 192 --v-dim 128`). `--ops` times by a trace, not
the host's clock: from a traced call of forward + backward, the
milliseconds a layer of each attention kernel by name (`ops_ms`: a row
alone `splash_pairs_fwd` and `splash_pairs_bwd`), the backward's kernels
summed (`backward_ms`: the parent's dq + dkv beside the one kernel's),
the device's busy ms a layer (`busy_ms`: the kernels and what XLA runs
around them) and the heaviest other ops, with the grid steps a q head
walks and those whose pair runs;
`--parent DIR` runs every line for the checkout at DIR as well (`git
archive` of the commit to compare with), on the same inputs.
`--layout` says how a row alone hands its kernels q, k and v:
`head-first` (`[H, T, hd]`), `seq-minor` (`[H, hd, T]`, as XLA's
products leave them: `ops/attention._rows_in_place`), `both` (a line
each) or, the default, what the call picks for its head size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POOL = ("3x6144,1x896,3x5504,6x3200,2x768,6x3072,2x640,3x5760,2x384,5x3840,"
        "1x512,4x4480,1x1152,5x3712,1x1280,9x2432,9x2048,10x1920,3x1024")


def candidates(t):
    """Run shapes to time for a row of `t`: each length up to the next
    multiple of 512 at its two largest q blocks x three largest kv
    blocks with the largest compute block, and the top pair once more
    at the next compute block down."""
    from areal_tpu.ops import attention as A

    tq, tkv, tkvc = A.SPLASH_BLOCK_TARGETS
    out = []
    for t_run in A._run_lengths(t):
        bqs = A._blocks_dividing(t_run, tq)[:2]
        bkvs = A._blocks_dividing(t_run, tkv)[:3]
        out += [(t_run, bq, bkv, A._blocks_dividing(bkv, tkvc)[0])
                for bq in bqs for bkv in bkvs]
        out += [(t_run, bqs[0], bkvs[0], c)
                for c in A._blocks_dividing(bkvs[0], tkvc)[1:2]]
    return out


def today(t):
    """What the parent ran: t' = t at the largest dividing blocks."""
    from areal_tpu.ops import attention as A

    return A._plain_run_shape(t, *A.SPLASH_BLOCK_TARGETS)


_PAIRS = "areal_tpu.ops.pallas.splash_pairs"


def attention_module(parent=None):
    """(`ops/attention`, `ops/pallas/splash_pairs`) of this tree, or of
    the checkout at `parent`, loaded beside them. `ops/attention` imports
    the pair kernels by name where it calls them: `use_tree` puts a
    tree's own under that name."""
    if parent is None:
        from areal_tpu.ops import attention
        from areal_tpu.ops.pallas import splash_pairs

        return attention, splash_pairs
    import importlib.util

    def load(name, *path):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(parent, "areal_tpu", "ops", *path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    return (load("parent_attention", "attention.py"),
            load("parent_splash_pairs", "pallas", "splash_pairs.py"))


def use_tree(pairs):
    """What a tree's `ops/attention` traces from here on are its own
    pair kernels."""
    sys.modules[_PAIRS] = pairs


def variant(args, A):
    """Steer `A` to the kernels `--static` names."""
    if args.static:
        A._rows_skip = lambda rows, t_run: False


def pool_rows(traffic_name, t):
    """(segment ids, positions), [n, 1, t] each: the train micro-batches
    of the benchmark's pool `traffic_name`, each packed into one row of
    `t` in the order the batch has them."""
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from benchmark import manifest, traffic

    with open(os.path.join(manifest.BENCH_DIR, "traffic", f"{traffic_name}.json")) as f:
        p = traffic.effective(json.load(f), rehearsal=False)
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=int(p["ppo"]["max_tokens_per_mb"]))
    rows = []
    for i, seqs in enumerate(traffic.ppo_batch_lengths(p)):
        lens = [s["prompt_len"] + s["resp_len"] for s in seqs]
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(lens))], seqlens=lens,
            data={"packed_input_ids": np.zeros(sum(lens), np.int32)})
        for mini in batch.split(MicroBatchSpec(n_mbs=int(p["ppo"]["n_minibatches"])))[0]:
            rows += [mb.seqlens_of() for mb in mini.split(budget)[0]]
    seg = np.zeros((len(rows), 1, t), np.int32)
    pos = np.zeros((len(rows), 1, t), np.int32)
    for r, lens in enumerate(rows):
        at = 0
        for i, n in enumerate(lens):
            seg[r, 0, at:at + n] = i + 1
            pos[r, 0, at:at + n] = np.arange(n)
            at += n
    return seg, pos


def packed_rows(rows, t, seq_len=None):
    """(segment ids, positions), [rows, t] each: three sequences and a
    padded tail a row, as the packer leaves them, or sequences of
    `seq_len` as far as they go."""
    import numpy as np

    seg = np.zeros((rows, t), np.int32)
    pos = np.zeros((rows, t), np.int32)
    for r in range(rows):
        cuts = [0, t // 5, t // 2, t - 1 - (r * 37) % 100]
        if seq_len:
            cuts = list(range(0, t - (r * 37) % 100 + 1, seq_len))
        for i in range(len(cuts) - 1):
            seg[r, cuts[i]: cuts[i + 1]] = i + 1
            pos[r, cuts[i]: cuts[i + 1]] = np.arange(cuts[i + 1] - cuts[i])
    return seg, pos


def inputs(rows, t, hq, hkv, hd, v_dim, seed):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(rows, t, h, d), jnp.bfloat16)
                 for h, d in ((hq, hd), (hkv, hd), (hkv, v_dim)))


# `_in_place` of `splash_packed_attention` by its name here, and what
# `--layout` runs
LAYOUT_NAMES = {None: "auto", False: "head-first", True: "seq-minor"}
LAYOUTS = {**{name: [value] for value, name in LAYOUT_NAMES.items()},
           "both": [False, True]}


def chained(A, run_shape, layers, window, in_place=None):
    """(q, k, v, seg, pos) -> a scalar through `layers` chained calls of
    `A.splash_packed_attention`, rows whole as the model gives them;
    `in_place`: the kernels' layout (None: the call's own choice)."""
    import jax
    import jax.numpy as jnp

    layout = {} if in_place is None else {"_in_place": in_place}

    def chain(q, k, v, seg, pos):
        def body(x, _):
            out = A.splash_packed_attention(x, k, v, seg, pos, _run_shape=run_shape,
                                            window=window, **layout)
            # v's head size may differ from q's
            d = x.shape[-1] - out.shape[-1]
            out = jnp.pad(out, ((0, 0),) * 3 + ((0, d),)) if d > 0 else out[..., :x.shape[-1]]
            return x + out * jnp.asarray(1e-3, x.dtype), None

        x, _ = jax.lax.scan(body, q, None, length=layers)
        return jnp.sum(x.astype(jnp.float32))

    return chain


def time_shape(A, qkv, ids, run_shape, layers, window=None, in_place=None):
    """(fwd ms, fwd+bwd ms) of `layers` chained attention calls."""
    import jax

    chain = chained(A, run_shape, layers, window, in_place)

    def clock(fn):
        jax.block_until_ready(fn(*qkv, *ids))  # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*qkv, *ids))
        reps = max(1, int(0.05 / (time.perf_counter() - t0)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(*qkv, *ids)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e3

    return clock(jax.jit(chain)), clock(jax.jit(jax.grad(chain, (0, 1, 2))))


def backward_ms(ops_ms):
    """The backward kernels' ms of `kernel_ops`' names: the one kernel
    over the kv-major list, the parent's dq + dkv, or the static fused
    backward."""
    return sum(ms for name, ms in ops_ms.items() if not name.endswith("fwd"))


def kernel_ops(A, qkv, run_shape, layers, window=None, reps=3, in_place=None):
    """ids -> ({attention kernel's name: ms a layer}, the device's busy
    ms a layer: the kernels and what XLA runs around them, {the six
    heaviest other ops: ms a layer}) from a trace of `reps` calls of
    forward + backward through `layers` chained calls; one program for
    every (segment ids, positions)."""
    import tempfile

    import jax

    from benchmark import trace_reduce

    fn = jax.jit(jax.grad(chained(A, run_shape, layers, window, in_place), (0, 1, 2)))

    def traced(ids):
        jax.block_until_ready(fn(*qkv, *ids))
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                for _ in range(reps):
                    jax.block_until_ready(fn(*qkv, *ids))
            got = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(trace_reduce.find_xplane(d)), top=40)
        ms = {name: 1e3 * s / (reps * layers) for name, s in got["device_ops"]}
        mine = {name: x for name, x in ms.items()
                if trace_reduce.categorize(name) == "attention"}
        rest = [(name, x) for name, x in ms.items() if name not in mine]
        return mine, 1e3 * got["busy_s"] / (reps * layers), dict(rest[:6])

    return traced


def walked(A, ids, hq, hkv, window):
    """(grid steps, live steps) a q head, the forward kernel once, by
    the tree's own host rule."""
    import numpy as np

    steps, live = A.attn_grid_steps("splash", np.asarray(ids[0]), hq, hkv,
                                    window=window)[:2]
    return int(steps), int(live)


def check_shape(A, qkv, ids, run_shape, window=None, in_place=None):
    """Largest error of the call's output and of its q, k, v gradients
    against `reference_packed_attention`'s (float32 from the same bf16
    inputs), each as a share of the reference's largest value."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seg, pos = ids
    real = (seg > 0)[..., None, None]
    dout = jnp.asarray(np.random.RandomState(1).randn(
        *qkv[0].shape[:-1], qkv[2].shape[-1]), jnp.float32) * real

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32) * real
            return jnp.sum(out * dout), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(*qkv)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    layout = {} if in_place is None else {"_in_place": in_place}
    got = run(lambda q, k, v: A.splash_packed_attention(
        q, k, v, seg, pos, _run_shape=run_shape, window=window, **layout))
    want = run(lambda q, k, v: jax.vmap(
        lambda q, k, v, s, p: A.reference_packed_attention(
            q, k, v, s, p, window=window))(q, k, v, seg, pos))
    return {name: float(np.abs(a - b).max() / np.abs(b).max())
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}


def sweep(args):
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        sys.exit("splash_shape_sweep times the compiled kernel: needs a TPU")
    from areal_tpu.ops.attention import splash_run_shape

    hkv = args.hq // args.group if args.group else args.hkv
    v_dim = args.v_dim or args.hd
    shapes = [tuple(int(x) for x in s.split("x")) for s in args.shapes.split(",")]
    plan, seen = [], set()
    for rows, t in shapes:
        cands = [today(t)]
        cands += [splash_run_shape(t)] if args.chosen else candidates(t)
        for c in cands:
            if (rows, t, c) not in seen:
                seen.add((rows, t, c))
                plan.append((rows, t, c))
    # Today's shapes first, so a run cut short still has the baseline.
    plan.sort(key=lambda p: p[2] != today(p[1]))
    trees = [("here", *attention_module())]
    if args.parent:
        trees.append(("parent", *attention_module(args.parent)))
    for _, A, _ in trees:
        variant(args, A)
    began = time.monotonic()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a" if args.append else "w") as f:
        for i, (rows, t, c) in enumerate(plan):
            if time.monotonic() - began > args.max_seconds:
                print(f"stopped at {i} of {len(plan)}: --max-seconds", flush=True)
                break
            qkv = inputs(rows, t, args.hq, hkv, args.hd, v_dim, seed=0)
            if args.rows_from:
                assert rows == 1, "--rows-from packs one row a micro-batch"
                pool = [(a, b) for a, b in zip(*pool_rows(args.rows_from, t))]
            else:
                pool = [packed_rows(rows, t, args.seq_len)]
            pool = [tuple(jnp.asarray(a) for a in ids) for ids in pool]
            # the parent has one layout: its own
            for tree, A, pairs, in_place in [
                    (*tr, lay) for tr in trees
                    for lay in (LAYOUTS[args.layout] if tr[0] == "here" else [None])]:
                use_tree(pairs)
                ops = (kernel_ops(A, qkv, c, args.layers, args.window, in_place=in_place)
                       if args.ops else None)
                for at, ids in enumerate(pool):
                    row = dict(rows=rows, t=t, t_run=c[0], bq=c[1], bkv=c[2], bkvc=c[3],
                               hq=args.hq, hkv=hkv, hd=args.hd, v_dim=v_dim,
                               layers=args.layers, window=args.window,
                               device=jax.devices()[0].device_kind, tree=tree,
                               static=args.static,
                               layout=LAYOUT_NAMES[in_place],
                               seq_len=args.seq_len, rows_from=args.rows_from, row=at)
                    try:
                        if args.check:
                            row["rel_err"] = check_shape(A, qkv, ids, c, args.window, in_place)
                        elif args.ops:
                            row["steps"], row["live"] = walked(
                                A, ids, args.hq, hkv, args.window)
                            row["ops_ms"], row["busy_ms"], row["other_ms"] = ops(ids)
                            row["backward_ms"] = backward_ms(row["ops_ms"])
                        else:
                            row["fwd_ms"], row["grad_ms"] = time_shape(
                                A, qkv, ids, c, args.layers, args.window, in_place)
                    except Exception as e:  # a block the compiler refuses is a result
                        row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    print(json.dumps(row), flush=True)


def fit(path):
    """Least squares of splash_cost's constants (`_SPLASH_NS`) on a
    sweep's file: (constants, the model's relative error a run, the runs)."""
    import numpy as np

    from areal_tpu.ops.attention import _splash_cost_terms

    rows = [json.loads(l) for l in open(path)]
    rows = [r for r in rows if "error" not in r]
    x = np.asarray([_splash_cost_terms(r["t_run"], r["bq"], r["bkv"], r["bkvc"],
                                       r.get("window"))
                    for r in rows], float)
    y = np.asarray([(r["fwd_ms"] + r["grad_ms"]) * 1e6
                    / (r["rows"] * r["hq"] * r["layers"]) for r in rows])
    # Relative error matters (the choice compares costs of one row), so
    # weigh each run by 1 / measured.
    coef, *_ = np.linalg.lstsq(x / y[:, None], np.ones_like(y), rcond=None)
    return tuple(map(float, coef)), x @ coef / y - 1.0, rows


def print_fit(path):
    import numpy as np

    coef, err, rows = fit(path)
    print(json.dumps(dict(runs=len(rows), _SPLASH_NS=coef,
                          rel_err_median=float(np.median(np.abs(err))),
                          rel_err_max=float(np.max(np.abs(err))))))
    for r, e in sorted(zip(rows, err), key=lambda z: -abs(z[1]))[:12]:
        print(f"  {r['rows']}x{r['t']} at {r['t_run']} {r['bq']}/{r['bkv']}/"
              f"{r['bkvc']}: fwd {r['fwd_ms']:.2f} + grad {r['grad_ms']:.2f} ms,"
              f" model {e:+.1%}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=POOL, help="rows x t, comma-separated")
    ap.add_argument("--out", default="chiprun_out/splash_sweep.jsonl")
    ap.add_argument("--fit", metavar="JSONL", help="fit splash_cost to a sweep")
    ap.add_argument("--chosen", action="store_true",
                    help="time only today's shape and splash_run_shape's")
    ap.add_argument("--hq", type=int, default=12)
    ap.add_argument("--hkv", type=int, default=2)
    ap.add_argument("--hd", type=int, default=128)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--window", type=int, default=None,
                    help="time a window layer (LocalMask), not a causal one")
    ap.add_argument("--group", type=int, default=None,
                    help="q heads a kv head (sets the kv heads: hq // group)")
    ap.add_argument("--v-dim", type=int, default=None,
                    help="v's head size, where it is not q's and k's")
    ap.add_argument("--static", action="store_true",
                    help="a row alone keeps splash's static kernels")
    ap.add_argument("--rows-from", metavar="TRAFFIC", default=None,
                    help="pack the rows as a benchmark pool's micro-batches")
    ap.add_argument("--ops", action="store_true",
                    help="a traced call's attention kernels, ms a layer each")
    ap.add_argument("--parent", metavar="DIR", default=None,
                    help="time the checkout at DIR as well")
    ap.add_argument("--layout", choices=sorted(LAYOUTS), default="auto",
                    help="how a row alone hands its kernels q, k and v")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="pack the rows with sequences this long")
    ap.add_argument("--check", action="store_true",
                    help="no timing: output and gradients against the "
                         "einsum reference")
    ap.add_argument("--append", action="store_true", help="add to --out")
    ap.add_argument("--max-seconds", type=float, default=3000.0)
    args = ap.parse_args()
    if args.fit:
        print_fit(args.fit)
    else:
        sweep(args)


if __name__ == "__main__":
    main()
