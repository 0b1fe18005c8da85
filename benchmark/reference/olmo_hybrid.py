"""Plain reference of Olmo-Hybrid (`model_type` `olmo_hybrid`: Gated DeltaNet,
arXiv:2412.06464, at `expand_v` 2 with the doubled beta of arXiv:2411.12537,
beside plain attention, in the Olmo 2 / Olmo 3 block, arXiv:2501.00656), for
`correct`: the forward pass and a scalar training loss whose gradients the
CPU tests read.

Straight `jax.numpy` in float32 at the highest matmul precision: no kernels,
no cache, no packing, no chunks, no inverse, no remat, one sequence at a
time. Layer by layer, `x` being `[T, D]`, D = `hidden_size`, N an RMSNorm
`x * rsqrt(mean x^2 + rms_norm_eps) * w` in float32, no bias anywhere:

    h = x + N_D(Mixer(x));   y = h + N_D(MLP(h))             output norms only:
    MLP(h) = (silu(h W_gate) * (h W_up)) W_down               no norm on the way in

then a final `N_D` and the head, its own matrix. `layer_types[i]` says which
mixer layer i has.

*linear_attention*, H = `linear_num_value_heads` (= `linear_num_key_heads`
in the published file; fewer key heads are repeated, each in a row), K =
`linear_key_head_dim`, V = `linear_value_head_dim`:

    q = silu(conv(x W_q)) [T, H, K];  k likewise;  v = silu(conv(x W_v)) [T, H, V]
        conv: causal, depthwise, `linear_conv_kernel_dim` taps, no bias, zeros
        before the sequence, **as a sum of shifted products**
    q <- q rsqrt(sum q^2 + 1e-6) K^-0.5,  k <- k rsqrt(sum k^2 + 1e-6),   a head
    g = -exp(A_log) softplus(x W_a + dt_bias)   [T, H] float32, <= 0
    beta = 2 sigmoid(x W_b)                     [T, H]   (`linear_allow_neg_eigval`)
    **token by token**, S [K, V] a head, S = 0 before the first token:
        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
    Mixer(x) = (N_V(o) * silu(x W_g)) W_o       W_g D -> H V,  W_o H V -> D

*full_attention*, `num_attention_heads` query and `num_key_value_heads`
key-value heads of `hidden_size / num_attention_heads`:

    q, k, v = x W_q, x W_k, x W_v;   q <- N_width(q),  k <- N_width(k)
        (one norm over the whole projected width, before the split into heads)
    no rotary (`rope_parameters.rope_theta` null; a number: plain rotary at it)
    a = causal softmax(q k^T head_dim^-0.5) v;   Mixer(x) = a W_o

Departures from the published description, each listed in the configuration
file's `assumed` too:

- **the vocabulary slice.** Embedding and head have `vocab_size` rows (one of
  eight vocabulary-parallel slices): ids, logits, softmax and loss over them.
- **what the config has no key for** is the Olmo 2 / Olmo 3 convention: where
  the norms stand (outputs only), the norm of q and k (over the width), the
  rule's order and its eps (the released Gated DeltaNet code's), no bias.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time (so that 6,144 positions fit); consecutive
  layers of one kind run under one `lax.scan` (one traced body: the
  compile's seconds; the arithmetic is a layer at a time either way).

`control` (the tolerance's controls, `scripts/tolerance_controls_olmo_hybrid.py`)
names one departure: `beta_sigmoid` (beta not doubled), `no_decay` (g = 0),
`no_correction` (`S_t = exp(g) S + beta k v^T`), `no_k_scale` (`K^-0.5` left
out), `no_conv`, `z_sigmoid` (the gate a sigmoid), `norm_in` (an unweighted
RMSNorm on the way into every mixer and MLP, added), `no_out_norms`,
`qk_head_norm` (q and k normed a head, each head under its own slice of the
width's weights), `rotary` (a table at theta 10,000 applied to q and k),
`v_halves` (the values' second `V / 2` columns read as the first).

Independent of the code under test: it reads the program's parameter tree
(`stacks/<parts>`, each kind of layer stacked on a leading axis in layer
order, matrices stored [in, out], a convolution [taps, channels]) and the
config's keys, and imports nothing from `areal_tpu/ops` or
`areal_tpu/models`. The weights are the served ones (bf16), upcast.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
L2_EPS = 1e-6
_PARTS = {"linear_attention": "kda+dense", "full_attention": "attention+dense"}


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return y if w is None else y * w


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def delta_rule(q, k, v, g, b, control=None):
    """q, k [T, H, K], v [T, H, V], g [T, H] (<= 0), b [T, H] -> o [T, H,
    V], token by token from S = 0: S <- exp(g_t) S; d = b_t (v_t - S^T k_t);
    S <- S + k_t d^T; o_t = S^T q_t."""
    H, K = q.shape[1:]

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = jnp.exp(gt)[:, None, None] * S
        read = 0.0 if control == "no_correction" else jnp.einsum("hkv,hk->hv", S, kt)
        d = bt[:, None] * (vt - read)
        S = S + kt[..., None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    return jax.lax.scan(step, jnp.zeros((H, K, v.shape[-1]), jnp.float32),
                        (q, k, v, g, b))[1]


def gdn_mixer(x, kp, hf, control=None):
    """[T, D] -> the Gated DeltaNet mixer's output, before the block's norm."""
    T = x.shape[0]
    Hk, H = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    taps = hf["linear_conv_kernel_dim"]

    def conv(a, w):  # w [taps, channels]: the last tap multiplies the position itself
        if control == "no_conv":
            return jax.nn.silu(a)
        shifted = jnp.pad(a, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
        return jax.nn.silu(sum(shifted[j: j + T] * w[j] for j in range(taps)))

    q = conv(x @ kp["wq"], kp["conv_q"]).reshape(T, Hk, K)
    k = conv(x @ kp["wk"], kp["conv_k"]).reshape(T, Hk, K)
    v = conv(x @ kp["wv"], kp["conv_v"]).reshape(T, H, V)
    if control == "v_halves":
        v = jnp.concatenate([v[..., : V // 2]] * 2, axis=-1)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q), unit(k)
    if control != "no_k_scale":
        q = q * K ** -0.5
    q, k = jnp.repeat(q, H // Hk, axis=1), jnp.repeat(k, H // Hk, axis=1)
    g = -jnp.exp(kp["A_log"]) * jax.nn.softplus(x @ kp["w_a"] + kp["dt_bias"])  # [T, H]
    if control == "no_decay":
        g = jnp.zeros_like(g)
    b = jax.nn.sigmoid(x @ kp["w_b"])
    if hf.get("linear_allow_neg_eigval", False) and control != "beta_sigmoid":
        b = 2.0 * b
    o = delta_rule(q, k, v, g, b, control)
    o = _rms(o, kp["o_norm"], hf["rms_norm_eps"]).reshape(T, H * V)
    z = x @ kp["w_g"]
    return (o * (jax.nn.sigmoid(z) if control == "z_sigmoid" else jax.nn.silu(z))) @ kp["wo"]


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


def attention_mixer(x, at, hf, control=None):
    """[T, D] -> the attention mixer's output, before the block's norm."""
    T = x.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    eps = hf["rms_norm_eps"]
    q, k, v = x @ at["wq"], x @ at["wk"], x @ at["wv"]
    if control == "qk_head_norm":
        q = _rms(q.reshape(T, Hq, hd), at["q_norm"].reshape(Hq, hd), eps)
        k = _rms(k.reshape(T, Hkv, hd), at["k_norm"].reshape(Hkv, hd), eps)
    else:
        q = _rms(q, at["q_norm"], eps).reshape(T, Hq, hd)
        k = _rms(k, at["k_norm"], eps).reshape(T, Hkv, hd)
    v = v.reshape(T, Hkv, hd)
    theta = 10000.0 if control == "rotary" else _theta(hf)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
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


def block(x, lp, linear: bool, hf, control=None):
    """One layer, [T, D] -> [T, D]: output norms only."""
    eps = hf["rms_norm_eps"]
    pre = (lambda a: _rms(a, None, eps)) if control == "norm_in" else (lambda a: a)
    post = ((lambda a, w: a) if control == "no_out_norms"
            else (lambda a, w: _rms(a, w["weight"], eps)))
    mixer = gdn_mixer(pre(x), lp["kda"], hf, control) if linear else attention_mixer(
        pre(x), lp["attn"], hf, control)
    h = x + post(mixer, lp["ln1_post"])
    return h + post(_swiglu(pre(h), lp["mlp"]), lp["ln2_post"])


def _runs(params, hf):
    """(is it linear attention, the slice of its kind's stack that a run of
    consecutive layers of that kind takes, float32), first layer first: a
    kind's stack is `stacks/<mixer>+dense` (a stack of one kind alone:
    `layers`)."""
    taken, out = {}, []
    for kind, run in itertools.groupby(hf["layer_types"][: hf["num_hidden_layers"]]):
        n, parts = len(list(run)), _PARTS[kind]
        at = taken.get(parts, 0)
        taken[parts] = at + n
        stack = params["stacks"][parts] if "stacks" in params else params["layers"]
        out.append((kind == "linear_attention", jax.tree_util.tree_map(
            lambda a: a[at: at + n].astype(jnp.float32), stack)))
    return out


def _stack(params, ids, hf, control=None):
    """The stack's output after the final norm, [T, D]."""
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    for linear, stack in _runs(params, hf):
        x, _ = jax.lax.scan(
            lambda c, lp: (block(c, lp, linear, hf, control), None), x, stack)
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32), hf["rms_norm_eps"])


def _head_logprobs(x, head, labels):
    """log softmax(x head)[labels], a block of positions at a time."""
    def rows(xn):
        logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
        return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

    blocks = (x.reshape(-1, ROWS, x.shape[-1]), labels.reshape(-1, ROWS))
    return jax.lax.map(rows, blocks).reshape(x.shape[0])


def logits(params, hf, token_ids):
    """[T, vocab_size] float32: the logits over the slice, every position."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        return _stack(params, ids, _small(hf)) @ params["head"]["weight"].astype(jnp.float32)


def _forward(params, ids, hf, control=None):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the last
    position scores ids[0] and is dropped by the caller)."""
    with jax.default_matmul_precision("highest"):
        x = _stack(params, ids, hf, control)
        return _head_logprobs(x, params["head"]["weight"].astype(jnp.float32),
                              jnp.roll(ids, -1))


_KEYS = ("num_hidden_layers", "layer_types", "num_attention_heads", "num_key_value_heads",
         "head_dim", "hidden_size", "rms_norm_eps", "rope_parameters", "rope_theta",
         "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval")


def _small(hf):
    return {k: hf[k] for k in _KEYS if k in hf}


def next_token_logprobs(params, hf, token_ids, pad_to=None, control=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding) so that every call shares one compiled program."""
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
