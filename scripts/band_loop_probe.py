#!/usr/bin/env python3
"""Each kind of layer a configuration's stack holds, as the stack runs it
(two of a kind in a scan where the kind's first layer stands in a
repeating segment; a layer alone where it runs once: a leading dense
layer, a prediction module's block), its token-wise
stretches over the whole row against over the row's live bands
(`areal_tpu/ops/band_loop.py`), on the chip at the cell's widths: one
packed row of `--row-len` cells of which `--tokens` hold a token
(sequences of about 2k from cell 0, padding after, as the packer leaves
it), bf16, seeded random weights, `remat="full"` as the cells run it, the
hidden states of the real tokens summed for a loss (no head: the head has
its own walk). Run by no cell.

A kind is a layer with the parts of one `LayerKind` of the stack (window
and rotary aside); a layer that keeps a tensor is timed alone, and one
that reads another's with the layer it reads before it. A kind that keeps
the whole row (`models/transformer._kind_loops`) reads the same in both
modes. **Which rows of PERF.md section 6's tables it regenerates**: PR 45's
for the kinds that loop (trinity's attention + dense and attention +
experts, joyai's latent + dense and latent + experts, keye's indexed +
experts, Qwen's attention + dense; since PR 48 a leading dense layer and a
prediction module's block are probed alone, as they run, where PR 45
probed every kind alone) and PR 48's own (the leading dense layers of the
trinity and joyai stacks and joyai's module block, with the seconds to
trace and lower each; the xing4 stack's row was taken before
`_lone_layer_loops` ruled that layer out, and reads the same in both modes
now), and **PR 61's for nemotron's `M`** (a Mamba-2 mixer alone in its
layer: `whole` against the carried loop, `ops/band_loop.carried` around
`transformer._ssm_layer`, the layer's whole body a band at a time with the
state and the taps' last cells handed on; PR 45's row for this kind was a
stretch before and one after the mixer, a body that went with that PR's
rule). **It cannot regenerate PR 45's rows for
the other kinds that were ruled out** (nemotron's `E` and `*`, phi4flash's
scan, memory unit and differential layers): those were taken one layer a
kind by the code of that PR's calls 1 and 2, which had a looping body for
every kind; the bodies of the kinds ruled out went with the rule, and a
re-run needs them written again (a stretch before and after the mixer in
`ops/selective_scan.py`, and for a layer of one
part). A line a (config, kind, mode, tokens): forward
and forward + backward milliseconds (the median of `--reps` calls, each
ended by `block_until_ready`), the seconds jax spent tracing and lowering
the forward + backward program, the cells the stretches ran, and with
`--ops` the heaviest device ops of a traced call by HLO base name. Modes:
`whole`, and `loop@N` for bands of N cells.

    python scripts/band_loop_probe.py --config trinity-mini-d5-e16 \\
        --row-len 16384 --tokens 8600,16384 --modes whole,loop@512,loop@1024,loop@2048 \\
        [--kinds 0,1] [--ops] [--out chiprun_out/x.jsonl]
    python scripts/band_loop_probe.py --config nemotron-3-nano-d9-e8 --kinds 0 \\
        --tokens 8600,16384 --modes whole,loop@1024 --ops   # the `M` kind, PR 61

`--toy` walks it on the CPU at the configuration's rehearsal widths: the
plumbing, no time.
"""

import argparse
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models import transformer as tf
from areal_tpu.ops import band_loop
from benchmark import manifest, model

def steer(mode: str) -> bool:
    """The band's length as `mode` says (`loop@N`; the program's own
    without), and whether `forward` is to walk the live bands at all."""
    which, _, n = mode.partition("@")
    band_loop._BAND = int(n) if n else _BAND
    jax.clear_caches()  # a stretch traced at another band length is no one's to find
    return which != "whole"


_BAND = band_loop._BAND  # the program's own


def unit_stacks(cfg):
    """[(name, a configuration of a kind of `cfg`'s stack as the stack
    runs its first layer of that kind: two of it, a scan, where that layer
    stands in a repeating segment, the layer alone where it runs once (a
    leading dense layer, a layer that keeps a tensor), the layer it reads
    and that layer where it reads one)], a kind once; and where the stack
    has a prediction module, its block: a layer of the last kind alone."""
    kinds, seen, out = cfg.kinds(), set(), []
    scanned = {i for seg in cfg.segments() if seg.repeats > 1
               for i in range(seg.start, seg.start + len(seg.unit) * seg.repeats)}
    units = []
    for i, kind in enumerate(kinds):
        key = (kind.mixer, kind.mlp, kind.diff, kind.latent, kind.indexed,
               kind.reads is not None)
        if key in seen:
            continue
        seen.add(key)
        units.append(((kind,) * (1 + (i in scanned)) if kind.reads is None else
                      (kinds[kind.reads], dataclasses.replace(kind, reads=0)), ""))
    if cfg.mtp is not None:
        units.append(((kinds[-1],), "@module"))
    for unit, suffix in units:
        name = "+".join(
            f"{k.mixer or '-'}{'.latent' if k.latent else ''}{'.indexed' if k.indexed else ''}"
            f"{'.diff' if k.diff else ''}{'<' if k.reads is not None else ''}/{k.mlp or '-'}"
            for k in unit) + suffix
        moe = cfg.moe
        if moe is not None and not any(k.mlp == "moe" for k in unit):
            moe = None
        out.append((name, dataclasses.replace(
            cfg, n_layers=len(unit), layer_kinds=unit, mtp=None,
            moe=moe if moe is None else dataclasses.replace(moe, first_k_dense=0))))
    return out


def packed_row(row_len: int, tokens: int, seq: int = 2048):
    """Segment ids and positions of one row: sequences of `seq` (the last
    shorter) from cell 0, padding after `tokens`."""
    cell = np.arange(row_len)
    seg = np.where(cell < tokens, cell // seq + 1, 0).astype(np.int32)
    pos = np.where(cell < tokens, cell % seq, 0).astype(np.int32)
    return seg[None], pos[None]


def device_ops(fn, args, top: int = 14):
    """Device time of one call: (busy ms, its heaviest ops by HLO base
    name as [name, ms])."""
    from benchmark import trace_reduce

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        got = trace_reduce.reduce_trace(trace_reduce.load_xplane(trace_reduce.find_xplane(d)), top)
    if not got:
        return None, None
    return (round(got["busy_s"] * 1e3, 3),
            [[name, round(s * 1e3, 3)] for name, s in got["device_ops"]])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="trinity-mini-d5-e16")
    ap.add_argument("--row-len", type=int, default=16384)
    ap.add_argument("--tokens", default="8600,16384")
    ap.add_argument("--modes", default="whole,loop@1024")
    ap.add_argument("--kinds", default=None, help="which of the stack's kinds, by index")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()

    if not a.toy and jax.default_backend() != "tpu":
        sys.exit("band_loop_probe: no TPU here (--toy walks it on the CPU)")
    config_file = json.load(open(os.path.join(ROOT, "benchmark", "configs", a.config + ".json")))
    cfg = model.transformer_config(
        manifest.hf_config(config_file, a.toy), "float32" if a.toy else "bfloat16")
    row_len = 512 if a.toy else a.row_len
    tokens = [260, 512] if a.toy else [int(t) for t in a.tokens.split(",")]
    units = unit_stacks(cfg)
    if a.kinds:
        units = [units[int(i)] for i in a.kinds.split(",")]
    out = open(a.out, "a") if a.out else None

    def say(line):
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for name, unit in units:
        params = jax.jit(lambda k: tf.init_params(unit, k))(jax.random.PRNGKey(a.seed))
        params = {k: v for k, v in params.items() if k not in ("embedding", "head")}
        x = jax.random.normal(jax.random.PRNGKey(a.seed + 1), (1, row_len, unit.hidden_dim),
                              jnp.dtype(unit.compute_dtype))
        # the stack alone: the embedding's rows come in as they are
        ids = jnp.arange(row_len, dtype=jnp.int32)[None]
        for mode in a.modes.split(","):
            if a.toy and "@" in mode:
                mode = mode.split("@")[0] + "@128"
            bands = steer(mode)

            def loss(p, x, seg, pos):
                h = tf.forward({**p, "embedding": {"weight": x[0]}}, unit, ids, seg, pos,
                               output="hidden", remat="full", bands=bands,
                               return_aux=unit.moe is not None or unit.indexer is not None)
                h = h[0] if isinstance(h, tuple) else h
                return jnp.sum(jnp.where((seg > 0)[..., None], h, 0).astype(jnp.float32))

            # fresh functions a mode: jax keeps a function's trace
            fns = {"fwd": jax.jit(lambda *z: loss(*z)),
                   "fwd_bwd": jax.jit(jax.grad(lambda *z: loss(*z), (0, 1)))}
            for n_tok in tokens:
                seg, pos = (jnp.asarray(z) for z in packed_row(row_len, n_tok))
                line = dict(config=a.config, kind=name, mode=mode, row_len=row_len, tokens=n_tok,
                            band_cells=band_loop.band_cells_run(np.asarray(seg))
                            if bands and tf.looping_layers(unit, 1, row_len) else row_len)
                args = (params, x, seg, pos)
                if n_tok == tokens[0]:
                    t0 = time.perf_counter()
                    traced = fns["fwd_bwd"].trace(*args)
                    t1 = time.perf_counter()
                    traced.lower()
                    line["trace_s"], line["lower_s"] = (
                        round(t1 - t0, 3), round(time.perf_counter() - t1, 3))
                for fn_name, fn in fns.items():
                    jax.block_until_ready(fn(*args))
                    times = []
                    for _ in range(a.reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(*args))
                        times.append((time.perf_counter() - t0) * 1e3)
                    line[fn_name + "_ms"] = round(statistics.median(times), 3)
                    if a.ops and not a.toy and fn_name == "fwd_bwd":
                        line["fwd_bwd_device_ms"], line["fwd_bwd_ops"] = device_ops(fn, args)
                say(line)


if __name__ == "__main__":
    main()
