"""Plain reference forward of Mellum 2 (`model_type: mellum`), for
`correct`: GQA attention, three layers through a window to one over the
whole sequence, each kind of layer under a rotary table of its own;
softmax-routed experts in every layer.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, one sequence at a time. Layer by layer,
`x` being `[T, hidden]`, t the layer's type (`layer_types`):

    h  = RMS_in(x)
    q, k, v = h Wq, h Wk, h Wv                32 / 4 / 4 heads of 128
    q, k = RMS_q(q), RMS_k(k)                 per head, over the head size
    q, k = rope_t(q, k)                       half-split pairs, the whole head
      rope_parameters[t] "default": inv_j = theta^(-2j/d), j = 0..d/2-1
      "yarn" (HF's `_compute_yarn_parameters`): with
        c(b) = d ln(orig / (2 pi b)) / (2 ln theta)
        low, high = floor c(beta_fast), ceil c(beta_slow)   (`truncate`)
        low, high = max(low, 0), min(high, d - 1)
        ramp_j = clip((j - low) / (high - low), 0, 1)
        inv'_j = inv_j (1 - ramp_j) + inv_j / factor ramp_j
      and cos, sin both times `attention_factor` (stated; absent: 0.1 ln
      factor + 1): the logits of such a layer carry its square.
    p  = softmax(q k^T / sqrt(d)) over j <= i, and on sliding layers
         i - j < sliding_window (a token and the sliding_window - 1 before it)
    x  = x + concat_h(p v) Wo
    h2 = RMS_post(x);  s = softmax(h2 Wr) over all routed experts, float32
    C  = top-k of s;  g = s[C] / sum s[C]      (`norm_topk_prob`)
    x  = x + sum_{e in C, e held here} g_e SwiGLU_e(h2)
    RMS_final, then the untied head.

Departures from the published model, each because the configuration is
one chip's share of a deployment (the config file's `deployment`), or
because the config does not say (its `assumed`):

- **the experts held here only**: `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held; router, top-k and the
  normalisation are over all routed experts, the sum over the chosen
  experts that are held, and nothing is added for the rest.
- **the vocabulary slice**: embedding and head have `vocab_size` rows.
- **the q/k norm** is the qwen3 family's (the config's keys are that
  family's mixture-of-experts layout); the config has no key for it.
- **no prediction module**: the catalog's description names one, the
  config has no key for one.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each expert is applied to every token
  and weighted by 0 where it was not chosen.

Independent of the code under test: it reads the program's parameter
tree (`layers`, stacked on a leading axis in layer order, weights stored
[in, out], the held experts stacked [held, in, out]) and the config's
keys, and nothing else. The weights are the served ones (bf16), upcast.

`control` (the tolerance's controls, `scripts/tolerance_controls_mellum.py`)
changes one thing: `tables` (layer type -> the layer type whose table it
takes), `attention_factor` (False: left out), `window` (another width;
None: none), `qk_norm` (False), `top_k` (another k), `renorm` (False).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
SLIDING = "sliding_attention"


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_table(d: int, rp: dict):
    """(inv_freq [d/2] float64, the factor on cos and sin) of one entry of
    `rope_parameters`."""
    theta = float(rp["rope_theta"])
    j = np.arange(d // 2, dtype=np.float64)
    inv = theta ** (-2.0 * j / d)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]
    c = lambda b: d * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = c(rp.get("beta_fast") or 32), c(rp.get("beta_slow") or 1)
    if rp.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    amp = rp.get("attention_factor")
    if amp is None:
        amp = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv * (1.0 - ramp) + inv / factor * ramp, float(amp)


def _rope(x, pos, inv, amp):
    """x: [T, H, d]; pairs are (x[:d/2], x[d/2:])."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * amp, jnp.sin(ang)[:, None, :] * amp
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def router_gates(h2, router, hf, top_k=None, renorm=True):
    """[T, hidden] -> [T, routed]: a token's gate on each routed expert, 0
    where it was not chosen."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    s = jax.nn.softmax(h2 @ router, axis=-1)
    g, chosen = jax.lax.top_k(s, top_k or hf["num_experts_per_tok"])
    if renorm:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32) * g[..., None], axis=1)


def expert_layer(h2, mlp, hf, top_k=None, renorm=True):
    """[T, hidden] -> the held experts' part of the routed sum."""
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    gates = router_gates(h2, mlp["router"], hf, top_k, renorm)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + gates[:, first + e, None] * _swiglu(h2, one), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(h2), jnp.arange(held))
    return m


def _attention(q, k, v, window):
    """q [T, H, d], k and v [T, H, d] (kv heads repeated) -> [T, H, d],
    ROWS query rows at a time."""
    T, _, d = q.shape
    cols = jnp.arange(T)

    def block(qr):
        qb, rows = qr  # [ROWS, H, d], [ROWS]
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(d)
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, *q.shape[1:]),
                              cols.reshape(T // ROWS, ROWS)))
    return out.reshape(q.shape)


def _layer(x, lp, hf, kind, control):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    T = x.shape[0]
    H, Hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps, at = hf["rms_norm_eps"], lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q, k = (h @ at["wq"]).reshape(T, H, d), (h @ at["wk"]).reshape(T, Hkv, d)
    v = (h @ at["wv"]).reshape(T, Hkv, d)
    if control.get("qk_norm", True):
        q, k = _rms(q, at["q_norm"], eps), _rms(k, at["k_norm"], eps)
    inv, amp = rope_table(d, hf["rope_parameters"][control.get("tables", {}).get(kind, kind)])
    if not control.get("attention_factor", True):
        amp = 1.0
    pos = jnp.arange(T)
    q, k = _rope(q, pos, inv, amp), _rope(k, pos, inv, amp)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    window = control.get("window", hf["sliding_window"]) if kind == SLIDING else None
    x = x + _attention(q, k, v, window).reshape(T, H * d) @ at["wo"]
    h2 = _rms(x, lp["ln2"]["weight"], eps)
    return x + expert_layer(h2, lp["mlp"], hf, control.get("top_k"),
                            control.get("renorm", True))


def _layers_in_order(params):
    stack = params["layers"]
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i], stack) for i in range(n)]


def _stack(params, ids, hf, control):
    """-> hidden states after the final norm [T, hidden]."""
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    layers = _layers_in_order(params)
    if len(layers) != len(hf["layer_types"]):
        raise ValueError("the parameter tree and layer_types disagree on depth")
    for lp, kind in zip(layers, hf["layer_types"]):
        x = _layer(x, lp, hf, kind, control)
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32), hf["rms_norm_eps"])


def _head_logprobs(x, head, labels):
    def rows(xn):  # a block of positions: log-softmax over the slice
        logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
        return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

    blocks = (x.reshape(-1, ROWS, x.shape[-1]), labels.reshape(-1, ROWS))
    return jax.lax.map(rows, blocks).reshape(-1)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
         "rms_norm_eps", "rope_parameters", "sliding_window", "layer_types",
         "num_experts", "num_experts_routed", "experts_held_first", "num_experts_per_tok")


def _small(hf):
    return {k: hf[k] for k in _KEYS if k in hf}


def _padded(token_ids, pad_to):
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // ROWS) * ROWS
    return np.concatenate([ids, np.zeros(padded - n, np.int32)]), n


def _forward(params, ids, hf, control=None):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller); `hf` the
    keys `_small` keeps."""
    with jax.default_matmul_precision("highest"):
        x = _stack(params, ids, hf, control or {})
        return _head_logprobs(x, params["head"]["weight"].astype(jnp.float32),
                              jnp.roll(ids, -1))


def next_token_logprobs(params, hf, token_ids, pad_to=None, **control) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, and every token is routed on its own) so that every
    call shares one compiled program. `control`: the module's docstring."""
    ids, n = _padded(token_ids, pad_to)
    small = _small(hf)
    fn = jax.jit(lambda p, i: _forward(p, i, small, control))
    return np.asarray(fn(params, jnp.asarray(ids)), np.float32)[: n - 1]

