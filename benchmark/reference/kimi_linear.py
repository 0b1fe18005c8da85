"""Plain reference of Kimi-Linear (arXiv:2510.26692; `model_type`
`kimi_linear`), for `correct`: the forward pass and a scalar training loss
whose gradients the CPU tests read.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no chunks, no inverse, no sorting of tokens
by expert, one sequence at a time. Layer by layer, `x` being `[T, hidden]`:

    x = x + Mixer(RMS_in(x));   x = x + MLP(RMS_post(x))

The mixers by `linear_attn_config` (layers counted from 1): `kda_layers`
Kimi Delta Attention, `full_attn_layers` latent attention.

*Kimi Delta Attention*, H = `num_heads` heads, K = V = `head_dim`, h the
normed input:

    q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))   [T, H, K] each
              conv: causal, depthwise, `short_conv_kernel_size` taps, no bias,
              zeros before the sequence
    q, k    = q * rsqrt(sum q^2 + 1e-6), k likewise, a head;  q <- q * K^-0.5
    g       = -exp(A_log)[H] * softplus((h W_fa) W_fb + dt_bias)   [T, H, K], <= 0
    a       = exp(g)                                                 the decay, a channel
    b       = sigmoid(h W_b)                                         [T, H]
    S_t     = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T  [K, V] a head,
              S_{-1} = 0                                             **token by token**
    o_t     = S_t^T q_t
    out     = (RMS_head(o) * sigmoid((h W_ga) W_gb)) W_o             RMS_head: weight [K],
                                                                     eps `rms_norm_eps`

*Latent attention*: `q = h W_q` (heads of `qk_nope_head_dim +
qk_rope_head_dim`, no rank, no norm); `[c_kv | k_r] = h W_kva`
(`kv_lora_rank` | `qk_rope_head_dim`), `c_kv = RMS(c_kv)`, `c_kv W_kvb`: a
head `[k_nope | v]`; a head's key is `[k_nope | k_r]`, the one `k_r` a
token every head's; **no rotary on anything** (`mla_use_nope`); causal
softmax at the scale `(nope + rope)^-0.5`; output `H v_head_dim x hidden`.

*MLP*: the first `first_k_dense_replace` layers a SwiGLU of
`intermediate_size`; the rest `s = sigmoid(h W_r)`, C = top-k of
`s + e_score_correction_bias`, `w = s[C] / (sum s[C] + 1e-20) *
routed_scaling_factor` (`moe_renormalize`), `m = Shared(h) + sum_{e in C}
w_e Expert_e(h)`, every expert a SwiGLU of `moe_intermediate_size`. Then
`RMS_final` and the untied head.

Departures from the published model, each because the configuration is one
chip's share of a deployment (the config file's `deployment`):

- **the experts held here only.** `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held. Router, top-k and weights are over
  all routed experts; the sum runs over the chosen experts that are held.
  The shared expert is whole. That partial result goes on.
- **the vocabulary slice.** Embedding and head have `vocab_size` rows.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each expert is applied to every token and
  weighted by 0 where it was not chosen.

What the config's keys do not give is the configuration file's `assumed`:
the gates' rank, `A_log` a head and `dt_bias` a channel, the convolution
without bias under silu, the L2 norm's eps inside the root, the output
gate a sigmoid, the 64 `qk_rope_head_dim` columns kept unrotated.

`control` (the tolerance's controls, `scripts/tolerance_controls_kda.py`)
names one departure: `beta_one` (b = 1), `scalar_decay` (a head's decay
its mean over channels), `no_correction` (`S_t = Diag(a) S + b k v^T`),
`no_conv`, `no_gate` (the output gate left out), `no_l2` (q and k not
normalised), `rotary` (rope_theta's table over the latent layers' 64
columns), `decay_bf16` (g and exp(g) rounded to bf16).

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


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def delta_rule(q, k, v, g, b, control=None):
    """q, k [T, H, K], v [T, H, V], g [T, H, K] (<= 0), b [T, H] -> o [T,
    H, V]: S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    from S = 0, o_t = S_t^T q_t, token by token."""
    H, K = q.shape[1:]
    if control == "decay_bf16":
        # `reduce_precision`, not a pair of casts: the compiler may drop those
        info = jnp.finfo(jnp.bfloat16)
        rounded = lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)
    else:
        rounded = lambda a: a

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = rounded(jnp.exp(rounded(gt)))[..., None] * S  # Diag(a) S
        kv = bt[:, None, None] * kt[..., None] * vt[:, None, :]  # b k v^T
        if control != "no_correction":
            kv = kv - bt[:, None, None] * kt[..., None] * jnp.einsum(
                "hk,hkv->hv", kt, S)[:, None, :]  # b k (k^T Diag(a) S)
        S = S + kv
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    return jax.lax.scan(step, jnp.zeros((H, K, v.shape[-1]), jnp.float32),
                        (q, k, v, g, b))[1]


def kda_layer(h, kp, hf, control=None):
    """[T, hidden] -> the Kimi Delta Attention mixer's output."""
    T = h.shape[0]
    lin = hf["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]

    def conv(x, w):  # w [taps, channels]: the last tap multiplies the position itself
        if control == "no_conv":
            return jax.nn.silu(x)
        shifted = jnp.pad(x, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
        return jax.nn.silu(sum(shifted[j: j + T] * w[j] for j in range(taps)))

    q, k, v = (conv(h @ kp[w], kp[c]).reshape(T, H, K)
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    if control != "no_l2":
        unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q), unit(k)
    q = q * K ** -0.5
    g = -jnp.exp(kp["A_log"])[:, None] * jax.nn.softplus(
        (h @ kp["w_fa"]) @ kp["w_fb"] + kp["dt_bias"]).reshape(T, H, K)
    if control == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    b = jax.nn.sigmoid(h @ kp["w_b"])
    if control == "beta_one":
        b = jnp.ones_like(b)
    o = delta_rule(q, k, v, g, b, control)
    o = _rms(o, kp["o_norm"], hf["rms_norm_eps"]).reshape(T, H * K)
    if control != "no_gate":
        o = o * jax.nn.sigmoid((h @ kp["w_ga"]) @ kp["w_gb"])
    return o @ kp["wo"]


def _rope(x, pos, theta):
    """x: [T, H, d]; pairs are (x[i], x[i + d/2]). A control's only."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_attention(h, at, hf, control=None):
    T = h.shape[0]
    H, nope, rope, vd = (hf["num_attention_heads"], hf["qk_nope_head_dim"],
                         hf["qk_rope_head_dim"], hf["v_head_dim"])
    q = (h @ at["wq"]).reshape(T, H, nope + rope)
    c_kv, k_r = jnp.split(h @ at["wkv_a"], [hf["kv_lora_rank"]], axis=-1)
    kv = (_rms(c_kv, at["kv_a_norm"], hf["rms_norm_eps"]) @ at["wkv_b"]).reshape(
        T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_r = k_r[:, None, :]
    if control == "rotary":
        pos = jnp.arange(T)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], pos, hf["rope_theta"])], axis=-1)
        k_r = _rope(k_r, pos, hf["rope_theta"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (T, H, rope))], axis=-1)
    cols = jnp.arange(T)

    def block(qr):  # ROWS query rows at a time
        qb, rows = qr
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(nope + rope)
        s = jnp.where((rows[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, H, nope + rope),
                              cols.reshape(T // ROWS, ROWS)))
    return out.reshape(T, H * vd) @ at["wo"]


def expert_layer(h2, mlp, hf):
    """[T, hidden] -> the expert layer's `m`: the shared expert plus the
    held experts' part of the routed sum."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    s = jax.nn.sigmoid(h2 @ mlp["router"])  # [T, routed]
    _, chosen = jax.lax.top_k(s + mlp["expert_bias"], hf["num_experts_per_token"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("moe_renormalize", True):
        s_chosen = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-20)
    w = s_chosen * hf.get("routed_scaling_factor", 1.0)
    # [T, routed]: a token's weight on each expert, 0 where not chosen
    weights = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32)
                      * w[..., None], axis=1)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + weights[:, first + e, None] * _swiglu(h2, one), None

    m = _swiglu(h2, mlp["shared"]) if "shared" in mlp else jnp.zeros_like(h2)
    m, _ = jax.lax.scan(add_expert, m, jnp.arange(held))
    return m


def _layers_in_order(params, hf):
    """(is it a KDA layer, the layer's slice of its kind's stack), first
    layer first: a kind's stack is `stacks/<mixer>+<mlp>` (a stack of
    latent layers alone: `lead_layers` for the dense ones, then `layers`)."""
    kda = set(hf["linear_attn_config"]["kda_layers"])
    dense = hf.get("first_k_dense_replace", 0)
    n_layers = hf["num_hidden_layers"]
    seen, out = {}, []
    for i in range(1, n_layers + 1):
        parts = ("kda" if i in kda else "latentattention") + (
            "+dense" if i <= dense else "+moe")
        if "stacks" in params:
            stack = params["stacks"][parts]
        else:
            parts = "lead_layers" if i <= dense < n_layers else "layers"
            stack = params[parts]
        n = seen.get(parts, 0)
        seen[parts] = n + 1
        out.append((i in kda, jax.tree_util.tree_map(
            lambda a: a[n].astype(jnp.float32), stack)))
    return out


def _stack(params, ids, hf, control=None):
    """The stack's output after the final norm, [T, hidden]."""
    eps = hf["rms_norm_eps"]
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    for is_kda, lp in _layers_in_order(params, hf):
        h = _rms(x, lp["ln1"]["weight"], eps)
        x = x + (kda_layer(h, lp["kda"], hf, control) if is_kda
                 else latent_attention(h, lp["attn"], hf, control))
        h2 = _rms(x, lp["ln2"]["weight"], eps)
        x = x + (expert_layer(h2, lp["mlp"], hf) if "router" in lp["mlp"]
                 else _swiglu(h2, lp["mlp"]))
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32), eps)


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


_KEYS = ("num_hidden_layers", "num_attention_heads", "hidden_size", "rms_norm_eps",
         "rope_theta", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "first_k_dense_replace", "linear_attn_config", "num_experts",
         "num_experts_routed", "experts_held_first", "num_experts_per_token",
         "moe_renormalize", "routed_scaling_factor")


def _small(hf):
    small = {k: hf[k] for k in _KEYS if k in hf}
    small["linear_attn_config"] = {
        k: (tuple(v) if isinstance(v, list) else v)
        for k, v in hf["linear_attn_config"].items()}
    return small


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
