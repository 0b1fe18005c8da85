"""Plain reference of Qwen3-Next (`model_type` `qwen3_next`; Gated Delta
Networks, arXiv:2412.06464), for `correct`: the forward pass and a scalar
training loss whose gradients the CPU tests read.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no chunks, no inverse, no sorting of tokens
by expert, one sequence at a time. Layer by layer, `x` being `[T, hidden]`:

    x = x + Mixer(N(x));   x = x + MoE(N(x))

Layer `i` (from 0) is full attention when `(i + 1) % full_attention_interval
== 0`, else Gated DeltaNet: L L L F. `N` is the family's norm everywhere
but inside the DeltaNet's output: `N(x) = x * rsqrt(mean x^2 + rms_norm_eps)
* (1 + w)`, in float32. **The parameter tree read here holds `1 + w`** (the
family folds the 1 in when it loads a checkpoint, as `models/hf/gemma.py`
does, and the seeded draw is of `1 + w`), so `_rms` multiplies by the leaf
as it stands; the control `w_for_1pw` takes the 1 out again.

*Gated DeltaNet*, Hk = `linear_num_key_heads` key heads, Hv =
`linear_num_value_heads` value heads, K = `linear_key_head_dim` = V =
`linear_value_head_dim`, h the normed input:

    [q | k | v | z] = h W_qkvz      hidden -> Hk K | Hk K | Hv V | Hv V
    [b | a]         = h W_ba        hidden -> Hv | Hv
    [q | k | v]     = silu(conv([q | k | v]))   causal, depthwise over the channels,
                      `linear_conv_kernel_dim` taps, no bias, zeros before the sequence
    q, k  = q * rsqrt(sum q^2 + 1e-6), k likewise, a key head;  q <- q * K^-0.5
    value head j reads key head j // (Hv / Hk)      (q and k repeated, each in a row)
    beta  = sigmoid(b)                                                  [T, Hv]
    g     = -exp(A_log)[Hv] * softplus(a + dt_bias[Hv])                 [T, Hv], <= 0
    **token by token, in the released code's order**, S [K, V] a value head, S = 0 first:
        S <- exp(g_t) S;   d = beta_t (v_t - S^T k_t);   S <- S + k_t d^T;   o_t = S^T q_t
    out   = (o * rsqrt(mean_V o^2 + eps) * w_o[V] * silu(z)) W_out      Hv V -> hidden
            (this one norm scales by w, not 1 + w)

(The tree keeps W_qkvz as `wq`, `wk`, `wv`, `w_g` and W_ba as `w_b`, `w_a`,
and the convolution's taps as `conv_q`, `conv_k`, `conv_v`: a depthwise
convolution over [q | k | v] is one over each.)

*Gated attention*, `num_attention_heads` query and `num_key_value_heads`
key-value heads of `head_dim`:

    [q | gate] = h W_q   (tree: `wq`, `wg`);   k = h W_k, v = h W_v
    q, k = N_head(q), N_head(k)        over a head's values, weights [head_dim], as 1 + w
    rotary on the first `partial_rotary_factor * head_dim` columns of a head (halves
    paired, non-interleaved), theta `rope_theta`; the other columns left as they are
    o = causal softmax(q k^T * head_dim^-0.5) v;   out = (o * sigmoid(gate)) W_o

*Experts* (every layer): `p = softmax(h W_r)` over the routed experts in
float32, the `num_experts_per_tok` largest, divided by their sum
(`norm_topk_prob`); an expert is `(silu(x W_g) * (x W_u)) W_d` at
`moe_intermediate_size`; plus `sigmoid(x w_s) * Shared(x)`, `Shared` one such
MLP at `shared_expert_intermediate_size`. No selection bias, no scaling
factor, no groups. Then `N_final` and the untied head.

Departures from the published model, each because the configuration is one
chip's share of a deployment (the config file's `deployment`):

- **the experts held here only.** `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held. Router, top-k and weights are over
  all routed experts; the sum runs over the chosen experts that are held.
  The shared expert and its gate are whole. That partial result goes on.
- **the vocabulary slice.** Embedding and head have `vocab_size` rows.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each held expert is applied to every token
  and weighted by 0 where it was not chosen.

`control` (the tolerance's controls, `scripts/tolerance_controls_gdn.py`)
names one departure: `beta_one`, `no_decay` (g = 0), `no_correction` (`S_t =
exp(g) S + beta k v^T`), `no_conv`, `no_z` (silu(z) left out), `z_sigmoid`
(sigmoid(z) for silu(z)), `no_l2`, `pair_mod` (value head j reads key head
`j % Hk`), `rotary_whole` (the whole head turned, the table over head_dim),
`no_rotary`, `no_attn_gate`, `w_for_1pw` (every `1 + w` norm scales by w),
`no_shared_gate`, `top8` (two fewer experts a token than the config's),
`decay_bf16` (g and exp(g) rounded to bf16).

Independent of the code under test: it reads the program's parameter tree
(`stacks/<parts>`, each kind of layer stacked on a leading axis in layer
order, matrices stored [in, out], a convolution [taps, channels], the held
experts stacked [held, in, out]) and the config's keys, and nothing else.
The weights are the served ones (bf16), upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
L2_EPS = 1e-6


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def delta_rule(q, k, v, g, b, control=None):
    """q, k [T, H, K], v [T, H, V], g [T, H] (<= 0), b [T, H] -> o [T, H,
    V], token by token from S = 0 in the released code's order: S <- exp(g_t)
    S; d = b_t (v_t - S^T k_t); S <- S + k_t d^T; o_t = S^T q_t."""
    H, K = q.shape[1:]
    if control == "decay_bf16":
        # `reduce_precision`, not a pair of casts: the compiler may drop those
        info = jnp.finfo(jnp.bfloat16)
        rounded = lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)
    else:
        rounded = lambda a: a

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = rounded(jnp.exp(rounded(gt)))[:, None, None] * S
        read = 0.0 if control == "no_correction" else jnp.einsum("hkv,hk->hv", S, kt)
        d = bt[:, None] * (vt - read)
        S = S + kt[..., None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    return jax.lax.scan(step, jnp.zeros((H, K, v.shape[-1]), jnp.float32),
                        (q, k, v, g, b))[1]


def gdn_layer(h, kp, hf, control=None):
    """[T, hidden] -> the Gated DeltaNet mixer's output."""
    T = h.shape[0]
    Hk, Hv, K = hf["linear_num_key_heads"], hf["linear_num_value_heads"], hf["linear_key_head_dim"]
    taps = hf["linear_conv_kernel_dim"]

    def conv(x, w):  # w [taps, channels]: the last tap multiplies the position itself
        if control == "no_conv":
            return jax.nn.silu(x)
        shifted = jnp.pad(x, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
        return jax.nn.silu(sum(shifted[j: j + T] * w[j] for j in range(taps)))

    q, k, v = (conv(h @ kp[w], kp[c]).reshape(T, n, K)
               for w, c, n in (("wq", "conv_q", Hk), ("wk", "conv_k", Hk), ("wv", "conv_v", Hv)))
    if control != "no_l2":
        unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
        q, k = unit(q), unit(k)
    q = q * K ** -0.5
    if control == "pair_mod":
        q, k = jnp.tile(q, (1, Hv // Hk, 1)), jnp.tile(k, (1, Hv // Hk, 1))
    else:
        q, k = jnp.repeat(q, Hv // Hk, axis=1), jnp.repeat(k, Hv // Hk, axis=1)
    g = -jnp.exp(kp["A_log"]) * jax.nn.softplus(h @ kp["w_a"] + kp["dt_bias"])  # [T, Hv]
    if control == "no_decay":
        g = jnp.zeros_like(g)
    b = jax.nn.sigmoid(h @ kp["w_b"])
    if control == "beta_one":
        b = jnp.ones_like(b)
    o = delta_rule(q, k, v, g, b, control)
    o = _rms(o, kp["o_norm"], hf["rms_norm_eps"]).reshape(T, Hv * K)
    z = h @ kp["w_g"]
    if control == "z_sigmoid":
        o = o * jax.nn.sigmoid(z)
    elif control != "no_z":
        o = o * jax.nn.silu(z)
    return o @ kp["wo"]


def _rope(x, pos, theta, width):
    """x: [T, H, d]: the first `width` columns turned, pairs (x[i], x[i +
    width/2]) among them; the rest as they are."""
    inv = 1.0 / (theta ** (np.arange(0, width, 2, dtype=np.float32) / width))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : width // 2], x[..., width // 2: width], x[..., width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gated_attention(h, at, hf, control=None):
    T = h.shape[0]
    Hq, Hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    one = 1.0 if control == "w_for_1pw" else 0.0
    q = _rms((h @ at["wq"]).reshape(T, Hq, hd), at["q_norm"] - one, eps)
    k = _rms((h @ at["wk"]).reshape(T, Hkv, hd), at["k_norm"] - one, eps)
    v = (h @ at["wv"]).reshape(T, Hkv, hd)
    width = hd if control == "rotary_whole" else int(hd * hf["partial_rotary_factor"])
    if control != "no_rotary":
        pos = jnp.arange(T)
        q, k = _rope(q, pos, hf["rope_theta"], width), _rope(k, pos, hf["rope_theta"], width)
    k, v = jnp.repeat(k, Hq // Hkv, axis=1), jnp.repeat(v, Hq // Hkv, axis=1)
    cols = jnp.arange(T)

    def block(qr):  # ROWS query rows at a time
        qb, rows = qr
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        s = jnp.where((rows[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, Hq, hd),
                              cols.reshape(T // ROWS, ROWS))).reshape(T, Hq * hd)
    if control != "no_attn_gate":
        out = out * jax.nn.sigmoid(h @ at["wg"])
    return out @ at["wo"]


def router_weights(h2, mlp, hf, control=None):
    """[T, routed]: a token's weight on each routed expert, 0 where not
    chosen: softmax over all of them, the top-k, divided by their sum."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    p = jax.nn.softmax(h2 @ mlp["router"], axis=-1)
    top = hf["num_experts_per_tok"] - (2 if control == "top8" else 0)
    w, chosen = jax.lax.top_k(p, top)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32) * w[..., None], axis=1)


def shared_expert(h2, sp, control=None):
    m = _swiglu(h2, sp)
    if control != "no_shared_gate":
        m = m * jax.nn.sigmoid(h2 @ sp["w_s"])
    return m


def expert_layer(h2, mlp, hf, control=None):
    """[T, hidden] -> the expert layer's `m`: the gated shared expert plus
    the held experts' part of the routed sum."""
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    weights = router_weights(h2, mlp, hf, control)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + jax.lax.dynamic_slice_in_dim(weights, first + e, 1, 1) * _swiglu(h2, one), None

    m, _ = jax.lax.scan(add_expert, shared_expert(h2, mlp["shared"], control), jnp.arange(held))
    return m


def is_full_attention(i: int, hf) -> bool:
    return (i + 1) % hf["full_attention_interval"] == 0


def _layers_in_order(params, hf):
    """(is it a full-attention layer, the layer's slice of its kind's
    stack), first layer first: a kind's stack is `stacks/<mixer>+moe` (a
    stack of attention layers alone: `layers`)."""
    seen, out = {}, []
    for i in range(hf["num_hidden_layers"]):
        full = is_full_attention(i, hf)
        parts = ("attention" if full else "kda") + "+moe"
        stack = params["stacks"][parts] if "stacks" in params else params["layers"]
        n = seen.get(parts, 0)
        seen[parts] = n + 1
        out.append((full, jax.tree_util.tree_map(lambda a: a[n].astype(jnp.float32), stack)))
    return out


def _stack(params, ids, hf, control=None):
    """The stack's output after the final norm, [T, hidden]."""
    eps = hf["rms_norm_eps"]
    one = 1.0 if control == "w_for_1pw" else 0.0
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    for full, lp in _layers_in_order(params, hf):
        h = _rms(x, lp["ln1"]["weight"] - one, eps)
        x = x + (gated_attention(h, lp["attn"], hf, control) if full
                 else gdn_layer(h, lp["kda"], hf, control))
        h2 = _rms(x, lp["ln2"]["weight"] - one, eps)
        x = x + expert_layer(h2, lp["mlp"], hf, control)
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32) - one, eps)


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
        return _head_logprobs(x, params["head"]["weight"].astype(jnp.float32),
                              jnp.roll(ids, -1))


_KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
         "hidden_size", "rms_norm_eps", "rope_theta", "partial_rotary_factor",
         "full_attention_interval", "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
         "num_experts", "num_experts_routed", "experts_held_first", "num_experts_per_tok",
         "norm_topk_prob")


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
