"""Plain reference forward of the AFMoE decoder (Arcee Trinity), for `correct`.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no sorting of tokens by expert, one
sequence at a time. It follows the published architecture
(`AfmoeForCausalLM`), layer by layer, `x` being `[T, hidden]`:

    x0 = E[ids] * sqrt(hidden)                                (mup_enabled)
    h  = RMS_in(x); q, k, v = h Wq, h Wk, h Wv; g = h Wg
    q, k = RMS_q(q), RMS_k(k)        per head, over the head size
    sliding layers: q, k = rope(q, k; rope_theta, half-split pairs)
    full layers:    no position encoding at all
    p  = softmax(q k^T / sqrt(head)) over j <= i, and on sliding layers
         i - j < sliding_window
    a  = (p v) * sigmoid(g);  x = x + RMS_post_attn(a Wo)
    h2 = RMS_pre_mlp(x)
    dense layer (the first num_dense_layers): m = SwiGLU(h2), width intermediate_size
    expert layer: s = sigmoid(h2 Wr); C = top-k of (s + expert_bias)
                  w = s[C] / (sum s[C] + 1e-20) * route_scale
                  m = Shared(h2) + sum_{e in C} w_e Expert_e(h2)
    x  = x + RMS_post_mlp(m);  RMS_final, then the untied head.

Departures from the published model, each because the configuration is
one chip's share of a deployment (the config file's `deployment`):

- **the experts held here only.** `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held. The router, its top-k and the
  weights `w` are over all routed experts; the sum runs over the chosen
  experts that are held, and nothing is added for the rest. The shared
  expert is whole. That partial `m` goes on to the next layer.
- **the vocabulary slice.** Embedding and head have `vocab_size` rows:
  the logits and their softmax are over the slice.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time (32 heads x 6144^2 scores and 6144 x the
  vocabulary do not fit otherwise); each expert is applied to every
  token and weighted by 0 where it was not chosen.

Independent of the code under test: it reads the program's parameter
tree (`lead_layers` and `layers`, stacked on a leading axis in layer
order, weights stored [in, out], the held experts stacked [held, in,
out]) and the config's keys, and nothing else. The weights are the
served ones (bf16), upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x: [T, H, hd]; pairs are (x[:hd/2], x[hd/2:])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def expert_layer(h2, mlp, hf):
    """[T, hidden] -> the expert layer's `m`: the shared expert plus the
    held experts' part of the routed sum."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    s = jax.nn.sigmoid(h2 @ mlp["router"])  # [T, routed]
    _, chosen = jax.lax.top_k(s + mlp["expert_bias"], hf["num_experts_per_tok"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("route_norm", True):
        s_chosen = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-20)
    w = s_chosen * hf.get("route_scale", 1.0)
    # [T, routed]: a token's weight on each expert, 0 where not chosen
    weights = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32)
                      * w[..., None], axis=1)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + weights[:, first + e, None] * _swiglu(h2, one), None

    m = jnp.zeros_like(h2)
    if "shared" in mlp:
        m = _swiglu(h2, mlp["shared"])
    m, _ = jax.lax.scan(add_expert, m, jnp.arange(held))
    return m


def _attention(q, k, v, window):
    """q [T, H, hd], k and v [T, H, hd] (kv heads repeated) -> [T, H, hd],
    ROWS query rows at a time."""
    T, _, hd = q.shape
    cols = jnp.arange(T)

    def block(qr):
        qb, rows = qr  # [ROWS, H, hd], [ROWS]
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, *q.shape[1:]),
                              cols.reshape(T // ROWS, ROWS)))
    return out.reshape(q.shape)


def layer_kinds(hf):
    """Per layer (window or None, rotary or not), from `layer_types`: a
    sliding layer sees `sliding_window` positions and rotates q and k, a
    full layer sees its whole sequence and has no position encoding."""
    return [(hf["sliding_window"], True) if t == "sliding_attention" else (None, False)
            for t in hf["layer_types"]]


def _layer(x, lp, hf, window, rotary: bool):
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    lp = f32(lp)
    T = x.shape[0]
    H, Hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    at = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = _rms((h @ at["wq"]).reshape(T, H, hd), at["q_norm"], eps)
    k = _rms((h @ at["wk"]).reshape(T, Hkv, hd), at["k_norm"], eps)
    v = (h @ at["wv"]).reshape(T, Hkv, hd)
    if rotary:
        pos = jnp.arange(T)
        q, k = _rope(q, pos, hf["rope_theta"]), _rope(k, pos, hf["rope_theta"])
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    a = _attention(q, k, v, window)
    a = a.reshape(T, H * hd) * jax.nn.sigmoid(h @ at["wg"])
    x = x + _rms(a @ at["wo"], lp["ln1_post"]["weight"], eps)
    h2 = _rms(x, lp["ln2"]["weight"], eps)
    m = expert_layer(h2, lp["mlp"], hf) if "router" in lp["mlp"] else _swiglu(h2, lp["mlp"])
    return x + _rms(m, lp["ln2_post"]["weight"], eps)


def _layers_in_order(params):
    """Each layer's slice of the program's stacks, first layer first."""
    out = []
    for name in ("lead_layers", "layers"):
        stack = params.get(name)
        if stack is not None:
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            out += [jax.tree_util.tree_map(lambda a: a[i], stack) for i in range(n)]
    return out


def _forward(params, ids, hf, kinds=None):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller). `kinds`
    (tests) overrides what `layer_types` says of each layer."""
    T = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][ids].astype(jnp.float32)
        if hf.get("mup_enabled"):
            x = x * np.sqrt(hf["hidden_size"]).astype(np.float32)
        layers = _layers_in_order(params)
        kinds = layer_kinds(hf) if kinds is None else kinds
        if len(layers) != len(kinds):
            raise ValueError("the parameter tree and layer_types disagree on depth")
        for lp, (window, rotary) in zip(layers, kinds):
            x = _layer(x, lp, hf, window, rotary)
        x = _rms(x, params["final_norm"]["weight"].astype(jnp.float32),
                 hf["rms_norm_eps"])
        head = params["head"]["weight"].astype(jnp.float32)
        nxt = jnp.roll(ids, -1)

        def rows(xn):  # a block of positions: log-softmax over the slice
            logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
            return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

        blocks = (x.reshape(-1, ROWS, x.shape[-1]), nxt.reshape(-1, ROWS))
        return jax.lax.map(rows, blocks).reshape(T)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
         "rms_norm_eps", "rope_theta", "sliding_window", "layer_types",
         "num_experts", "num_experts_routed", "experts_held_first",
         "num_experts_per_tok", "route_norm", "route_scale", "mup_enabled")


def next_token_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, and every token is routed on its own) so that every
    call shares one compiled program."""
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // ROWS) * ROWS
    ids = np.concatenate([ids, np.zeros(padded - n, np.int32)])
    small = {k: hf[k] for k in _KEYS if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small))
    return np.asarray(fn(params, jnp.asarray(ids)), np.float32)[: n - 1]
