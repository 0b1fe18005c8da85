"""Plain reference forward of LFM2-8B-A1B (`model_type: lfm2_moe`), for
`correct`: gated short convolutions and GQA attention layers, a dense MLP
in the leading layers and sigmoid-routed experts chosen on score + bias in
the rest.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, one sequence at a time. Layer by layer, `h`
being `[T, hidden]`; every norm an RMSNorm at `norm_eps` with a learned
weight; no projection has a bias:

    u  = RMS_op(h)
    "conv" layer:
      [B | C | x] = u W_in                      three parts of `hidden`, in that order
      z_t = sum_{j=0..K-1} w_j (B * x)_{t-j}    depthwise, K = conv_L_cache, nothing
                                                before the sequence's first position,
                                                no activation, no bias (conv_bias false)
      m  = (C * z) W_out
      (the parameter tree holds the taps `[K, hidden]` with tap K-1 on the
      position itself, torch's layout transposed: `w_j = conv_w[K-1-j]`)
    "full_attention" layer:
      q, k, v = u W_q, u W_k, u W_v             32 / 8 / 8 heads of 64
      q, k = RMS_q(q), RMS_k(k)                 per head, over the head size
      q, k = rope(q, k)                         rope_theta, half-split pairs, the whole head
      p  = softmax(q k^T / sqrt(64)) over j <= i
      m  = concat_h(p v) W_o
    h1 = h + m;  f = RMS_ffn(h1)
    layer < num_dense_layers:  mlp = (silu(f W_1) * f W_3) W_2
    else:  s = sigmoid(f W_r)                   over all routed experts
           E = top-k of (s + expert_bias)       (`use_expert_bias`)
           g = s[E] / (sum s[E] + 1e-6)         (`norm_topk_prob`) x routed_scaling_factor
           mlp = sum_{e in E, e held here} g_e (silu(f W_1e) * f W_3e) W_2e
    h2 = h1 + mlp
    RMS_final (`embedding_norm`), then the head, which is the embedding.

Departures from the published modelling code, each because the
configuration is one chip's share of a deployment (the config file's
`deployment`), or because the config does not say (its `assumed`):

- **the experts held here only**: `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held; router, bias, top-k and the
  normalisation are over all routed experts, the sum over the chosen
  experts that are held, and nothing is added for the rest.
- **the vocabulary slice**: the embedding, which is the head too, has
  `vocab_size` rows; logits, softmax and loss are over them.
- the order `B, C, x` of `in_proj`'s thirds, no activation in the
  convolution, the per-head q/k norm before the rotary embedding, the
  half-split rotary over the whole head and the router's `1e-6` are the
  `transformers` modelling code's as remembered: the config has no key
  for any of them.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each held expert is applied to every
  token and weighted by 0 where it was not chosen.

Independent of the code under test: it reads the program's parameter tree
(`stacks/<parts>`, each kind of layer stacked on a leading axis in layer
order, matrices stored [in, out], the taps [K, channels], the held experts
stacked [held, in, out]) and the config's keys, and imports nothing from
`areal_tpu/ops` or `areal_tpu/models`. The weights are the served ones
(bf16), upcast.

`control` (the tolerance's controls, `scripts/tolerance_controls_lfm2.py`)
changes one thing: "taps_reversed", "no_B", "no_C", "conv_silu" (an
activation the model does not have), "no_conv" (the tap on the position
itself alone), "select_no_bias" (selection on s), "top_2", "no_renorm",
"no_qk_norm", "no_rope", "norm_eps_1e-20" (the router's constant).
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
ROUTE_NORM_EPS = 1e-6
CONV = "conv"


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def conv_mixer(u, cp, control=None, starts=None):
    """[T, D] -> the gated short convolution's output. `starts` `[T]` bool
    (the tests'): positions at which a sequence starts inside u; a tap
    reaches no position before its own sequence's first."""
    T = u.shape[0]
    B, C, x = jnp.split(u @ cp["in_proj"], 3, axis=-1)
    bx = x if control == "no_B" else B * x
    w = cp["conv_w"]  # [K, D]: tap K-1 multiplies the position itself
    K = w.shape[0]
    if control == "taps_reversed":
        w = w[::-1]
    # which sequence a position is of, for the taps' reach
    seq = jnp.zeros((T,), jnp.int32) if starts is None else jnp.cumsum(starts.astype(jnp.int32))
    z = jnp.zeros_like(bx)
    for j in range(1 if control == "no_conv" else K):  # the lag
        shifted = jnp.pad(bx, ((j, 0), (0, 0)))[:T]  # (B * x)_{t-j}, zeros before the row
        same = jnp.pad(seq, (j, 0), constant_values=-1)[:T] == seq
        z = z + jnp.where(same[:, None], shifted, 0.0) * w[K - 1 - j]
    if "conv_b" in cp:
        z = z + cp["conv_b"]
    if control == "conv_silu":
        z = jax.nn.silu(z)
    return (z if control == "no_C" else C * z) @ cp["out_proj"]


def _rope(a, theta):
    """a [T, H, d] turned whole, pairs (a[i], a[i + d/2]), positions 0..T-1."""
    d = a.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., : d // 2], a[..., d // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], axis=-1)


def _theta(hf):
    return (hf.get("rope_parameters") or {}).get("rope_theta", hf.get("rope_theta"))


def attention_mixer(u, at, hf, control=None):
    """[T, D] -> the attention mixer's output; T a multiple of ROWS."""
    T = u.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    q = (u @ at["wq"]).reshape(T, Hq, hd)
    k = (u @ at["wk"]).reshape(T, Hkv, hd)
    v = (u @ at["wv"]).reshape(T, Hkv, hd)
    if control != "no_qk_norm":
        q, k = _rms(q, at["q_norm"], hf["norm_eps"]), _rms(k, at["k_norm"], hf["norm_eps"])
    if control != "no_rope":
        q, k = _rope(q, _theta(hf)), _rope(k, _theta(hf))
    k, v = jnp.repeat(k, Hq // Hkv, axis=1), jnp.repeat(v, Hq // Hkv, axis=1)
    cols = jnp.arange(T)

    def block(qr):  # ROWS query rows at a time
        qb, rows = qr
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        s = jnp.where((rows[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, Hq, hd),
                              cols.reshape(T // ROWS, ROWS))).reshape(T, Hq * hd)
    return out @ at["wo"]


def router_gates(f, mlp, hf, control=None):
    """[T, hidden] -> [T, routed]: a token's gate on each routed expert, 0
    where it was not chosen."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    s = jax.nn.sigmoid(f @ mlp["router"])
    select = s if control == "select_no_bias" else s + mlp["expert_bias"]
    _, chosen = jax.lax.top_k(select, 2 if control == "top_2" else hf["num_experts_per_tok"])
    g = jnp.take_along_axis(s, chosen, axis=-1)
    if control != "no_renorm":
        g = g / (jnp.sum(g, axis=-1, keepdims=True)
                 + (1e-20 if control == "norm_eps_1e-20" else ROUTE_NORM_EPS))
    g = g * hf.get("routed_scaling_factor", 1.0)
    return jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32) * g[..., None], axis=1)


def expert_layer(f, mlp, hf, control=None):
    """[T, hidden] -> the held experts' part of the routed sum."""
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    gates = router_gates(f, mlp, hf, control)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + gates[:, first + e, None] * _swiglu(f, one), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(f), jnp.arange(held))
    return m


def block(h, lp, conv: bool, dense: bool, hf, control=None):
    """One layer, [T, D] -> [T, D]."""
    eps = hf["norm_eps"]
    u = _rms(h, lp["ln1"]["weight"], eps)
    m = conv_mixer(u, lp["conv"], control) if conv else attention_mixer(
        u, lp["attn"], hf, control)
    h1 = h + m
    f = _rms(h1, lp["ln2"]["weight"], eps)
    return h1 + (_swiglu(f, lp["mlp"]) if dense else expert_layer(f, lp["mlp"], hf, control))


def _runs(params, hf):
    """(is its mixer a convolution, is its MLP dense, the slice of its
    kind's stack that a run of consecutive layers of that kind takes,
    float32), first layer first: a kind's stack is
    `stacks/<conv | attention>+<dense | moe>`."""
    n = hf["num_hidden_layers"]
    kinds = [(t == CONV, i < hf.get("num_dense_layers", 0))
             for i, t in enumerate(hf["layer_types"][:n])]
    taken, out = {}, []
    for kind, run in itertools.groupby(kinds):
        count = len(list(run))
        parts = ("conv" if kind[0] else "attention") + "+" + ("dense" if kind[1] else "moe")
        at = taken.get(parts, 0)
        taken[parts] = at + count
        out.append(kind + (jax.tree_util.tree_map(
            lambda a: a[at: at + count].astype(jnp.float32), params["stacks"][parts]),))
    return out


def _stack(params, ids, hf, control=None):
    """The stack's output after the final norm, [T, D]."""
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    for conv, dense, stack in _runs(params, hf):
        x, _ = jax.lax.scan(
            lambda c, lp: (block(c, lp, conv, dense, hf, control), None), x, stack)
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32), hf["norm_eps"])


def _head_logprobs(x, head, labels):
    """log softmax(x head)[labels], a block of positions at a time."""
    def rows(xn):
        logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
        return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

    blocks = (x.reshape(-1, ROWS, x.shape[-1]), labels.reshape(-1, ROWS))
    return jax.lax.map(rows, blocks).reshape(x.shape[0])


def _forward(params, ids, hf, control=None):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the last
    position scores ids[0] and is dropped by the caller)."""
    with jax.default_matmul_precision("highest"):
        x = _stack(params, ids, hf, control)
        head = params["embedding"]["weight"].astype(jnp.float32).T  # tied
        return _head_logprobs(x, head, jnp.roll(ids, -1))


_KEYS = ("num_hidden_layers", "layer_types", "num_dense_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "hidden_size", "norm_eps", "rope_parameters",
         "rope_theta", "num_experts", "num_experts_routed", "experts_held_first",
         "num_experts_per_tok", "routed_scaling_factor")


def _small(hf):
    return {k: hf[k] for k in _KEYS if k in hf}


def next_token_logprobs(params, hf, token_ids, pad_to=None, control=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, and every token is routed on its own) so that every
    call shares one compiled program."""
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // ROWS) * ROWS
    ids = np.concatenate([ids, np.zeros(padded - n, np.int32)])
    small = _small(hf)
    fn = jax.jit(lambda p, i: _forward(p, i, small, control))
    return np.asarray(fn(params, jnp.asarray(ids)), np.float32)[: n - 1]


def loss(params, hf, token_ids, prompt_len):
    """The scalar a training step minimises over one sequence with minus
    the logprob as the caller's loss: the mean over the response tokens
    token[prompt_len..] of -log p(token). Differentiable in `params`; T
    must be a multiple of ROWS."""
    ids = jnp.asarray(token_ids, jnp.int32)
    T = ids.shape[0]
    t = jnp.arange(T)
    logp = _forward(params, ids, _small(hf))
    scored = (t >= prompt_len - 1) & (t < T - 1)
    return -jnp.sum(jnp.where(scored, logp, 0.0)) / jnp.sum(scored)
