#!/usr/bin/env python3
"""How 96-wide key heads meet 128 lanes (PR 60; PERF.md section 6), on the chip:
the delta rule alone at `olmohybrid-d4-train-ppo-8k`'s shape `[1, 8192, 30 heads,
K 96, V 192]`, bf16, one decay a head, beta in (0, 2), the row 70 % full, ms a
call forward and forward + backward, the first call's seconds (the build) and
each arm's difference from the first arm's results.

    python scripts/kda_layout_probe.py [widened whole_width plain]

- `widened`: q and k padded to 128 lanes a head in HBM, 6 heads a grid step
  (`ops/kda.key_lanes`, `kda_fwd.step_heads`: the tree's choice);
- `whole_width`: keys as they stand at 96, all 30 heads a step (the only count
  whose blocks of 96-wide heads are whole lane tiles), 110 MB of VMEM allowed:
  by patching `kda_fwd.HEADS` and the compiler's parameters here, nothing the
  program offers;
- `plain`: `intra` + `states_scan`, what `ops/kda.use_kernel` fell to at
  K % 128 != 0 before PR 60.

Writes `chiprun_out/kda_layout_probe60.jsonl`. No CPU mode: a time from here
says nothing."""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from areal_tpu.ops import kda
from areal_tpu.ops.pallas import kda_fwd
from jax.experimental.pallas import tpu as pltpu

T, H, K, V, C = 8192, 30, 96, 192, 64
rng = np.random.default_rng(0)
n = int(T * 0.7)
seg = np.zeros((1, T), np.int32)
o, s = 0, 1
for l in (2100, 1700, 1234, n - 5034):
    seg[0, o:o + l] = s; o += l; s += 1
valid = jnp.asarray(seg > 0)
mk = lambda *sh: jnp.where(valid.reshape((1, T) + (1,) * (len(sh) - 2)), jnp.asarray(rng.normal(size=sh), jnp.bfloat16), 0)
q, k, v = mk(1, T, H, K), mk(1, T, H, K), mk(1, T, H, V)
f = mk(1, T, H)
b = jnp.where(valid[..., None], jnp.asarray(rng.uniform(0.05, 1.95, size=(1, T, H)), jnp.float32), 0)
A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
bias = jnp.asarray(rng.normal(size=(H,)) - 4.0, jnp.float32)
seg = jnp.asarray(seg)
w = mk(1, T, H, V)
pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, 32),))

def arm(name):
    if name == "widened":
        fn = lambda q, k, v, f, b, A, bias: kda.delta_rule(pad(q), pad(k), v, f, b, A, bias, seg, C, True, kda.RuleForm(K, True))
    elif name == "whole_width":
        fn = lambda q, k, v, f, b, A, bias: kda.delta_rule(q, k, v, f, b, A, bias, seg, C, True, kda.RuleForm(None, True))
    else:
        fn = lambda q, k, v, f, b, A, bias: kda.delta_rule(q, k, v, f, b, A, bias, seg, C, False, kda.RuleForm(None, True))
    fwd = jax.jit(fn)
    both = jax.jit(jax.value_and_grad(lambda *a: (fn(*a).astype(jnp.float32) * w).sum(), argnums=tuple(range(7))))
    return fwd, both

def bench(fn, args, reps=10):
    t0 = time.time(); out = jax.block_until_ready(fn(*args)); first = time.time() - t0
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return first, (time.time() - t0) / reps * 1e3, out

rows, ref = [], None
args = (q, k, v, f, b, A, bias)
for name in sys.argv[1:] or ["widened", "whole_width", "plain"]:
    if name == "whole_width":
        kda_fwd.HEADS = 30
        orig = pltpu.CompilerParams
        pltpu.CompilerParams = lambda **kw: orig(**{**kw, "vmem_limit_bytes": 110 * 2 ** 20})
        jax.clear_caches()
    try:
        fwd, both = arm(name)
        c1, ms_f, o = bench(fwd, args)
        c2, ms_fb, (val, grads) = bench(both, args)
        row = dict(arm=name, device=jax.devices()[0].device_kind, shape=[1, T, H, K, V], fill=0.7,
                   first_call_s=[round(c1, 1), round(c2, 1)], fwd_ms=round(ms_f, 3), fwd_bwd_ms=round(ms_fb, 3))
        o32 = np.asarray(o, np.float32)
        if ref is None:
            ref = (o32, [np.asarray(g, np.float32) for g in grads])
        else:
            row["o_max_diff_vs_first_arm"] = float(np.abs(o32 - ref[0]).max())
            row["grad_rel_diff_vs_first_arm"] = [float(np.abs(np.asarray(g, np.float32) - r).max() / (np.abs(r).max() + 1e-9)) for g, r in zip(grads, ref[1])]
    except Exception as e:
        row = dict(arm=name, error=str(e)[:400])
    if name == "whole_width":
        kda_fwd.HEADS = 8; pltpu.CompilerParams = orig; jax.clear_caches()
    rows.append(row); print(json.dumps(row), flush=True)
os.makedirs("chiprun_out", exist_ok=True)
open("chiprun_out/kda_layout_probe60.jsonl", "w").write("".join(json.dumps(r) + "\n" for r in rows))
