"""Plain reference of Xing4.0-29B-A4B (`xing4_0`), for `correct`: the
forward pass and a scalar training loss whose gradients the CPU tests
read.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no loop over bands, no cache, no packing, no sorting of tokens
by expert, one sequence at a time, token by token (the layers of a stack
of the program's parameters under one `lax.scan`, Sinkhorn's iterations
one `lax.fori_loop`: the same plain bodies, compiled once instead of once a
layer and once an iteration). A token has `n =
hc_mult` residual streams `X` in `R^{n x D}` (manifold-constrained
hyper-connections, arXiv:2512.24880, on Hyper-Connections,
arXiv:2409.19606). For each sublayer `F` (latent attention; then the
dense MLP or the expert layer with its shared expert), with parameters
`Phi` in `R^{nD x (n^2 + 2n)}`, `b` in `R^{n^2 + 2n}` and scalars `a_pre,
a_post, a_res`:

    x~ = vec(X);  r = rsqrt(mean(x~^2) + rms_norm_eps);  m = r * (x~ Phi)
    H_pre  = sigmoid(a_pre  * m[0:n]    + b[0:n])                   in R^{1 x n}
    H_post = 2 sigmoid(a_post * m[n:2n] + b[n:2n])                  in R^{1 x n}
    M_0    = exp(clip(a_res * mat(m[2n:]) + mat(b[2n:]),
                      mhc_h_res_clamp_min, mhc_h_res_clamp_max))    in R^{n x n}
    M_k    = cols(rows(M_{k-1})),  rows(M) = M / (sum_j M + hc_eps), cols
             alike;  H_res = M_{hc_sinkhorn_iters}
    h = H_pre X;   y = F(RMSNorm(h));   X' = H_res X + H_post^T y

The stack starts from `n` copies of the embedding and ends in the sum of
the streams, then the final norm and the untied head (Hyper-Connections,
section 2). `F`, attention (DeepSeek-V3's latent attention, `h` being a
token's normed input):

    c_q = RMS_qa(h W_qa)                       [q_lora_rank]
    q   = c_q W_qb                             heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva                     [kv_lora_rank | qk_rope_head_dim]
    c_kv W_kvb, c_kv = RMS_kva(c_kv)           heads of [k_nope | v]
    q_h = [q_nope_h | rope(q_rope_h)],  k_h = [k_nope_h | rope(k_r)]
    p   = softmax(q_h k_h^T * scale), j <= i;  concat_h(p v_h) W_o

with YaRN over the rope part: for `d = qk_rope_head_dim`, `inv_i =
rope_theta^(-2i/d)`, `low, high = floor, ceil of d ln(orig / (2 pi
beta)) / (2 ln rope_theta)` at `beta_fast` and `beta_slow`, clipped to
`[0, d/2 - 1]`, `ramp_i = clip((i - low) / (high - low), 0, 1)`,
`inv_freq = inv / factor * ramp + inv * (1 - ramp)`; cos and sin times
`(0.1 mscale ln factor + 1) / (0.1 mscale_all_dim ln factor + 1)` (= 1 as
published); `scale = (qk_nope + qk_rope)^-0.5 * (0.1 mscale_all_dim ln
factor + 1)^2`. `F`, the MLP: SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers; after them `s = sigmoid(h W_r)`, `C` =
top-k of `s + e_score_correction_bias`, `w = s[C] / (sum s[C] + 1e-20) *
routed_scaling_factor`, `Shared(h) + sum_{e in C} w_e Expert_e(h)`.

Departures from the published model, each a choice this file states (the
configuration's file lists them under `assumed` with their sources):

- **the experts held here only**, and **the vocabulary slice**: the
  configuration is one chip's share of a deployment. `n_routed_experts`
  counts the experts whose weights this chip holds, `num_experts_routed`
  the router's outputs, `experts_held_first` the first held; router,
  top-k and weights are over all routed experts, the sum over the chosen
  experts that are held. The shared expert is whole.
- the RMSNorm of `x~` inside a sublayer's hyper-connections has no weight
  of its own (it folds into `Phi`); `hc_eps` stands in Sinkhorn's
  denominators, rows before columns; the clamp is before the
  exponential.
- rotary turns the pairs (2i, 2i + 1) of the rope part where they are
  (the released DeepSeek-V3 code moves them first; q and k alike, so every
  score is the same number).
- no prediction module (`num_nextn_predict_layers` 0 here).
- `loss` is this system's RL step with minus the logprob as the caller's
  loss, not the pretraining objective.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each expert is applied to every token and
  weighted by 0 where it was not chosen.

Independent of the code under test: it reads the program's parameter tree
(`lead_layers` and `layers`, stacked on a leading axis in layer order,
weights stored [in, out], the held experts stacked [held, in, out], a
sublayer's hyper-connections under `hc1` (attention) and `hc2` (MLP):
`phi`, `b`, `a` = (pre, post, res)) and the config's keys, and nothing
else. The weights are the served ones (bf16), upcast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(d, theta, rs):
    """The rope part's frequencies `[d / 2]` under `rope_scaling` `rs`
    (None: the plain table), and the factor of cos and sin."""
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if not rs:
        return inv, 1.0
    turns = lambda beta: d * math.log(rs["original_max_position_embeddings"]
                                      / (beta * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns(rs["beta_fast"])), 0)
    high = min(math.ceil(turns(rs["beta_slow"])), d // 2 - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv / rs["factor"] * ramp + inv * (1.0 - ramp)
    return inv, _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"], rs["mscale_all_dim"])


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, pos, hf):
    """x: [T, H, d]; pairs are (x[2i], x[2i + 1])."""
    inv, amp = yarn_inv_freq(x.shape[-1], hf["rope_theta"], hf.get("rope_scaling"))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def softmax_scale(hf):
    rs = hf.get("rope_scaling")
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    return scale * (_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 if rs else 1.0)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def expert_layer(h2, mlp, hf):
    """[T, hidden] -> the expert layer's output: the shared expert plus
    the held experts' part of the routed sum."""
    routed = hf.get("num_experts_routed", hf["n_routed_experts"])
    first, held = hf.get("experts_held_first", 0), hf["n_routed_experts"]
    s = jax.nn.sigmoid(h2 @ mlp["router"])  # [T, routed]
    _, chosen = jax.lax.top_k(s + mlp["expert_bias"], hf["num_experts_per_tok"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        s_chosen = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-20)
    w = s_chosen * hf.get("routed_scaling_factor", 1.0)
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


def _attention(q, k, v, scale):
    """q, k [T, H, dqk], v [T, H, dv] -> [T, H, dv], causal, ROWS query
    rows at a time."""
    T = q.shape[0]
    cols = jnp.arange(T)

    def block(qr):
        qb, rows = qr  # [ROWS, H, dqk], [ROWS]
        s = jnp.einsum("thd,shd->hts", qb, k) * scale
        s = jnp.where((rows[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, *q.shape[1:]),
                              cols.reshape(T // ROWS, ROWS)))
    return out.reshape(T, *v.shape[1:])


def latent_attention(h, at, hf):
    """[T, hidden] (normed) -> [T, hidden]: the materialised form, k and v
    a head."""
    T = h.shape[0]
    H, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    nope, rope, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    pos = jnp.arange(T)
    q = (_rms(h @ at["wq_a"], at["q_a_norm"], eps) @ at["wq_b"]).reshape(T, H, nope + rope)
    down = h @ at["wkv_a"]
    c_kv, k_r = down[:, : hf["kv_lora_rank"]], down[:, hf["kv_lora_rank"]:]
    kv = (_rms(c_kv, at["kv_a_norm"], eps) @ at["wkv_b"]).reshape(T, H, nope + dv)
    q_r = _rope(q[..., nope:], pos, hf)
    k_r = _rope(k_r[:, None, :], pos, hf)  # one head
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (T, H, rope))], axis=-1)
    return _attention(q, k, kv[..., nope:], softmax_scale(hf)).reshape(T, H * dv) @ at["wo"]


def sinkhorn(m, iters, eps):
    """[T, n, n] positive -> rows then columns normalised, `iters` times
    (one loop of `iters` steps, not `iters` copies of a step: the same
    arithmetic, a twentieth of it to compile)."""
    def rows_then_columns(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, rows_then_columns, m)


def hyper_coefficients(X, hp, hf):
    """X [T, n, D] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]."""
    T, n, _ = X.shape
    xt = X.reshape(T, -1)
    r = jax.lax.rsqrt(jnp.mean(xt * xt, axis=-1, keepdims=True) + hf["rms_norm_eps"])
    m = r * (xt @ hp["phi"])
    a_pre, a_post, a_res = hp["a"]
    b = hp["b"]
    h_pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    m0 = jnp.exp(jnp.clip(a_res * m[:, 2 * n:] + b[2 * n:],
                          hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(m0.reshape(T, n, n), hf["hc_sinkhorn_iters"], hf["hc_eps"])


def hyper_sublayer(X, hp, norm_w, f, hf):
    """X' = H_res X + H_post^T F(RMSNorm(H_pre X)), X [T, n, D]."""
    h_pre, h_post, h_res = hyper_coefficients(X, hp, hf)
    h = jnp.einsum("tk,tkd->td", h_pre, X)
    y = f(_rms(h, norm_w, hf["rms_norm_eps"]))
    return jnp.einsum("tik,tkd->tid", h_res, X) + h_post[:, :, None] * y[:, None, :]


def mlp_of(lp, hf):
    """The layer's second sublayer `F`: [T, hidden] -> [T, hidden]."""
    if "router" in lp["mlp"]:
        return lambda h: expert_layer(h, lp["mlp"], hf)
    return lambda h: _swiglu(h, lp["mlp"])


def _layer(X, lp, hf):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    X = hyper_sublayer(X, lp["hc1"], lp["ln1"]["weight"],
                       lambda h: latent_attention(h, lp["attn"], hf), hf)
    return hyper_sublayer(X, lp["hc2"], lp["ln2"]["weight"], mlp_of(lp, hf), hf)


def _stacks_in_order(params):
    """The program's stacks of layers (each stacked on a leading axis in
    layer order), the leading dense layers' first."""
    return [params[name] for name in ("lead_layers", "layers") if params.get(name) is not None]


def _head_logprobs(x, head, labels):
    """log softmax(x head)[labels], a block of positions at a time."""
    def rows(xn):
        logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
        return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

    blocks = (x.reshape(-1, ROWS, x.shape[-1]), labels.reshape(-1, ROWS))
    return jax.lax.map(rows, blocks).reshape(x.shape[0])


def _stack(params, ids, hf):
    """The stack's output after the final norm, [T, hidden]."""
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    X = jnp.repeat(x[:, None, :], hf["hc_mult"], axis=1)  # n copies of the embedding
    stacks = _stacks_in_order(params)
    if sum(jax.tree_util.tree_leaves(s)[0].shape[0] for s in stacks) != hf["num_hidden_layers"]:
        raise ValueError("the parameter tree and num_hidden_layers disagree on depth")
    for stack in stacks:  # a stack's layers one after the other: one layer to compile
        X, _ = jax.lax.scan(lambda X, lp: (_layer(X, lp, hf), None), X, stack)
    return _rms(jnp.sum(X, axis=1), params["final_norm"]["weight"].astype(jnp.float32),
                hf["rms_norm_eps"])


def _forward(params, ids, hf):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller)."""
    with jax.default_matmul_precision("highest"):
        h = _stack(params, ids, hf)
        return _head_logprobs(h, params["head"]["weight"].astype(jnp.float32),
                              jnp.roll(ids, -1))


_KEYS = ("num_hidden_layers", "num_attention_heads", "hidden_size", "rms_norm_eps",
         "rope_theta", "rope_scaling", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_routed",
         "experts_held_first", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
         "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def _padded(token_ids, pad_to):
    ids = np.asarray(token_ids, np.int32)
    padded = -(-max(len(ids), pad_to or 0) // ROWS) * ROWS
    return jnp.asarray(np.concatenate([ids, np.zeros(padded - len(ids), np.int32)]))


def next_token_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, and every token is routed and mixed on its own) so
    that every call shares one compiled program."""
    small = {k: hf[k] for k in _KEYS if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small))
    return np.asarray(fn(params, _padded(token_ids, pad_to)), np.float32)[: len(token_ids) - 1]


def loss(params, hf, token_ids, prompt_len):
    """The scalar a training step of this system minimises over one
    sequence, with minus the logprob as the caller's loss: the mean over
    the response tokens token[prompt_len..] of -log p(token).
    Differentiable in `params`; T must be a multiple of ROWS."""
    ids = jnp.asarray(token_ids, jnp.int32)
    t = jnp.arange(ids.shape[0])
    logp = _forward(params, ids, hf)
    scored = (t >= prompt_len - 1) & (t < ids.shape[0] - 1)
    return -jnp.sum(jnp.where(scored, logp, 0.0)) / jnp.sum(scored)
