"""Decode-path perf probe: time the paged decode step vs sampling warp on
the real chip (diagnosing the gen tok/s bottleneck before optimizing)."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import init_params
from areal_tpu.engine import paged

def log(*a): print(*a, file=sys.stderr, flush=True)

def timeit(fn, *args, n=20, warmup=3):
    for _ in range(warmup): jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))  # per-call block: time
        # execution, not dispatch
    return (time.perf_counter() - t0) / n

cfg = TransformerConfig(
    n_layers=16, hidden_dim=1536, n_q_heads=12, n_kv_heads=2,
    head_dim=128, intermediate_dim=8960, vocab_size=32768,
    attn_bias=True, compute_dtype="bfloat16", param_dtype="bfloat16",
)
params = init_params(cfg, jax.random.PRNGKey(0))
B, pg, P = 32, 128, 9   # ~1152 tokens per slot
N = B * P + 1
kp = jnp.zeros((cfg.n_layers, cfg.n_kv_heads, N, pg, cfg.head_dim), jnp.bfloat16)
vp = jnp.zeros_like(kp)
pt = jnp.asarray(np.arange(1, B*P+1, dtype=np.int32).reshape(B, P))
lengths = jnp.full((B,), 600, jnp.int32)
active = jnp.ones((B,), bool)
tokens = jnp.ones((B,), jnp.int32)

step = jax.jit(lambda p, t, k, v, pi, l, a: paged.paged_decode_step(p, cfg, t, k, v, pi, l, a)[0], static_argnames=())
t_step = timeit(step, params, tokens, kp, vp, pt, lengths, active)
log(f"decode_step (B={B}): {t_step*1e3:.2f} ms")

logits = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.vocab_size), jnp.float32)
temps = jnp.ones((B,), jnp.float32); tps = jnp.ones((B,), jnp.float32)
tks = jnp.full((B,), -1, jnp.int32); gm = jnp.zeros((B,), bool)
fr = jnp.zeros((B,), bool); em = jnp.zeros((cfg.vocab_size,), bool)
ws = jax.jit(paged.warp_sample)
t_ws = timeit(ws, logits, jax.random.PRNGKey(2), temps, tps, tks, gm, fr, em)
log(f"warp_sample (B={B}, V=32768): {t_ws*1e3:.2f} ms")

# plain categorical for comparison
cat = jax.jit(lambda l, r: jax.random.categorical(r, l, axis=-1))
t_cat = timeit(cat, logits, jax.random.PRNGKey(3))
log(f"plain categorical: {t_cat*1e3:.2f} ms")

# attention-only: paged attention at this shape
q = jax.random.normal(jax.random.PRNGKey(4), (B, cfg.n_q_heads, cfg.head_dim), jnp.bfloat16)
pa = jax.jit(lambda q, k, v, l, pi: paged.paged_decode_attention(q, k, v, l, pi))
t_pa = timeit(pa, q, kp[0], vp[0], lengths, pt)
log(f"paged attention single layer: {t_pa*1e3:.3f} ms  (x{cfg.n_layers} = {t_pa*cfg.n_layers*1e3:.2f} ms)")
