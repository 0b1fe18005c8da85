"""Plain reference of JoyAI-LLM-Flash (a stack of DeepSeek-V3's shape),
for `correct`: the forward pass, the prediction module, and a scalar
training loss whose gradients the CPU tests read.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no sorting of tokens by expert, one
sequence at a time. Layer by layer, `x` being `[T, hidden]`:

    h   = RMS_in(x)
    c_q = RMS_qa(h W_qa)                       [q_lora_rank]
    q   = c_q W_qb                             heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva                     [kv_lora_rank | qk_rope_head_dim]
    c_kv = RMS_kva(c_kv)
    c_kv W_kvb                                 heads of [k_nope | v]
    q_h = [q_nope_h | rope(q_rope_h)],  k_h = [k_nope_h | rope(k_r)]
          (the one k_r a token for every head; rope over qk_rope_head_dim
           alone, pairs (2i, 2i + 1): rope_interleave)
    p   = softmax(q_h k_h^T / sqrt(qk_nope_head_dim + qk_rope_head_dim)), j <= i
    x   = x + concat_h(p v_h) W_o
    h2  = RMS_post(x)
    dense layer (the first first_k_dense_replace): m = SwiGLU(h2), intermediate_size
    expert layer: s = sigmoid(h2 W_r); C = top-k of (s + e_score_correction_bias)
                  w = s[C] / (sum s[C] + 1e-20) * routed_scaling_factor
                  m = Shared(h2) + sum_{e in C} w_e Expert_e(h2)
    x   = x + m;  RMS_final, then the untied head.

The prediction module (`num_nextn_predict_layers` 1), with h the stack's
output after RMS_final and E the embedding table, at position i:

    u_i = W_eh [RMS_e(E[t_{i+1}]) ; RMS_h(h_i)]      (embedding half first)
    one more expert-layer block over u (positions as i's), RMS_mtp, the
    model's own head: p_mtp(. | i) predicts t_{i+2}.

Departures from the published model, each a choice this file states:

- **the experts held here only**, and **the vocabulary slice**: the
  configuration is one chip's share of a deployment (its file's
  `deployment`). `n_routed_experts` counts the experts whose weights this
  chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held; router, top-k and weights are over
  all routed experts, the sum over the chosen experts that are held. The
  shared expert is whole. Embedding and head have `vocab_size` rows.
- the released code applies the rotary embedding after moving each pair
  (2i, 2i + 1) to (i, i + d/2); here the pairs are turned where they
  are. q and k are permuted alike, so every q.k is the same number.
- **`loss` is this system's RL step, not the pretraining objective**: the
  caller's loss over the scored positions plus `mtp_weight` times the
  module's, and in the module's branch h, E and the head are under
  `stop_gradient` (the policy's gradient is the caller's loss's; the
  module follows the policy). Both sums are divided by the count of
  scored positions.
- `u`'s order (embedding half first), h taken after RMS_final, and the
  constant 1e-20 follow DeepSeek-V3's released code, from memory.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each expert is applied to every token and
  weighted by 0 where it was not chosen.

Independent of the code under test: it reads the program's parameter tree
(`lead_layers`, `layers` and `mtp.block`, stacked on a leading axis in
layer order, weights stored [in, out], the held experts stacked [held,
in, out]) and the config's keys, and nothing else. The weights are the
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
    """x: [T, H, d]; pairs are (x[2i], x[2i + 1])."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _kv_latent(c_kv, at, eps):
    """The norm inside the kv projection."""
    return _rms(c_kv, at["kv_a_norm"], eps)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def expert_layer(h2, mlp, hf):
    """[T, hidden] -> the expert layer's `m`: the shared expert plus the
    held experts' part of the routed sum."""
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


def _attention(q, k, v):
    """q, k [T, H, dqk], v [T, H, dv] -> [T, H, dv], causal, ROWS query
    rows at a time."""
    T, _, dqk = q.shape
    cols = jnp.arange(T)

    def block(qr):
        qb, rows = qr  # [ROWS, H, dqk], [ROWS]
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(dqk)
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
    kv = (_kv_latent(c_kv, at, eps) @ at["wkv_b"]).reshape(T, H, nope + dv)
    q_r = _rope(q[..., nope:], pos, hf["rope_theta"])
    k_r = _rope(k_r[:, None, :], pos, hf["rope_theta"])  # one head
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (T, H, rope))], axis=-1)
    return _attention(q, k, kv[..., nope:]).reshape(T, H * dv) @ at["wo"]


def _layer(x, lp, hf):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    eps = hf["rms_norm_eps"]
    x = x + latent_attention(_rms(x, lp["ln1"]["weight"], eps), lp["attn"], hf)
    h2 = _rms(x, lp["ln2"]["weight"], eps)
    m = expert_layer(h2, lp["mlp"], hf) if "router" in lp["mlp"] else _swiglu(h2, lp["mlp"])
    return x + m


def _layers_in_order(params):
    """Each layer's slice of the program's stacks, first layer first."""
    out = []
    for name in ("lead_layers", "layers"):
        stack = params.get(name)
        if stack is not None:
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            out += [jax.tree_util.tree_map(lambda a: a[i], stack) for i in range(n)]
    return out


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
    layers = _layers_in_order(params)
    if len(layers) != hf["num_hidden_layers"]:
        raise ValueError("the parameter tree and num_hidden_layers disagree on depth")
    for lp in layers:
        x = _layer(x, lp, hf)
    return _rms(x, params["final_norm"]["weight"].astype(jnp.float32), hf["rms_norm_eps"])


def _mtp(params, ids, h, head, hf):
    """[T] log p_mtp(ids[i + 2] | ids[..i + 1]) from the stack's output
    `h` (positions past T - 3 read wrapped-round tokens and are dropped by
    the caller; no position before them sees them)."""
    f32 = lambda a: a.astype(jnp.float32)
    mp, eps = params["mtp"], hf["rms_norm_eps"]
    e = f32(params["embedding"]["weight"])[jnp.roll(ids, -1)]
    u = jnp.concatenate([_rms(e, f32(mp["enorm"]["weight"]), eps),
                         _rms(h, f32(mp["hnorm"]["weight"]), eps)], axis=-1)
    u = u @ f32(mp["eh_proj"]["weight"])
    u = _layer(u, jax.tree_util.tree_map(lambda a: a[0], mp["block"]), hf)
    return _head_logprobs(_rms(u, f32(mp["norm"]["weight"]), eps), head, jnp.roll(ids, -2))


def _forward(params, ids, hf, mtp=False):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller); with `mtp`
    the prediction module's log p_mtp(ids[t+2] | ids[..t+1]) instead."""
    with jax.default_matmul_precision("highest"):
        h = _stack(params, ids, hf)
        head = params["head"]["weight"].astype(jnp.float32)
        if mtp:
            return _mtp(params, ids, h, head, hf)
        return _head_logprobs(h, head, jnp.roll(ids, -1))


_KEYS = ("num_hidden_layers", "num_attention_heads", "hidden_size", "rms_norm_eps",
         "rope_theta", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_routed",
         "experts_held_first", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor")


def _padded(token_ids, pad_to):
    ids = np.asarray(token_ids, np.int32)
    padded = -(-max(len(ids), pad_to or 0) // ROWS) * ROWS
    return jnp.asarray(np.concatenate([ids, np.zeros(padded - len(ids), np.int32)]))


def next_token_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, and every token is routed on its own) so that every
    call shares one compiled program."""
    small = {k: hf[k] for k in _KEYS if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small))
    return np.asarray(fn(params, _padded(token_ids, pad_to)), np.float32)[: len(token_ids) - 1]


def mtp_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p_mtp(token[i+2] | token[..i+1]) for i = 0..T-3, float32 [T-2]:
    the prediction module's, through the model's own head."""
    small = {k: hf[k] for k in _KEYS if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small, mtp=True))
    return np.asarray(fn(params, _padded(token_ids, pad_to)), np.float32)[: len(token_ids) - 2]


def loss(params, hf, token_ids, prompt_len, mtp_weight=0.0):
    """The scalar a training step of this system minimises over one
    sequence, with minus the logprob as the caller's loss: the mean over
    the response tokens token[prompt_len..] of -log p(token), plus
    `mtp_weight` times the sum over the positions i whose token[i+2] is a
    response token of -log p_mtp(token[i+2]), over the same count. In the
    module's branch the stack's output, the embedding table and the head
    are constants (`stop_gradient`). Differentiable in `params`; T must
    be a multiple of ROWS."""
    ids = jnp.asarray(token_ids, jnp.int32)
    T = ids.shape[0]
    t = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        h = _stack(params, ids, hf)
        head = params["head"]["weight"].astype(jnp.float32)
        logp = _head_logprobs(h, head, jnp.roll(ids, -1))
        scored = (t >= prompt_len - 1) & (t < T - 1)
        total = -jnp.sum(jnp.where(scored, logp, 0.0))
        if mtp_weight:
            still = jax.lax.stop_gradient
            frozen = dict(params, embedding=still(params["embedding"]))
            logp2 = _mtp(frozen, ids, still(h), still(head), hf)
            reads = (t >= prompt_len - 2) & (t < T - 2)
            total = total - mtp_weight * jnp.sum(jnp.where(reads, logp2, 0.0))
        return total / jnp.sum(scored)
