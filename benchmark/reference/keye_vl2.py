"""Plain reference forward of Keye-VL-2.0's language model (`KeyeVL2`,
text tokens), for `correct`: GQA attention over the keys a learned
indexer chooses (DeepSeek sparse attention), softmax-routed experts.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, one sequence at a time. Layer by layer,
`x` being `[T, hidden]`, `sg` = `stop_gradient`:

    h  = RMS_in(x)
    q, k, v = h Wq, h Wk, h Wv                32 / 4 / 4 heads of 128
    q, k = RMS_q(q), RMS_k(k)                 per head, over the head size
    q, k = rope(q, k; rope_theta, half-split pairs, the whole head)
    indexer:  qI = sg(h) W_Iq                 16 heads of 64
              kI = LayerNorm(sg(h) W_Ik)      one head of 64 for all 16
              wI = sg(h) W_Iw                 16
              qI, kI = rope(qI, kI; the same theta, over the whole 64)
              I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]) 64^-0.5 16^-0.5
    choice:   A_t = {s <= t};  S_t = A_t where |A_t| <= topk, else
              {s in A_t : I[t, s] >= tau_t}, tau_t the topk-th largest of I[t, A_t]
    p_h = softmax_{s in S_t}(q_h k_g(h)^T / sqrt(128));  a = concat_h(p_h v_g(h)) Wo
    x  = x + a
    h2 = RMS_post(x);  p = softmax(h2 Wr) over all routed experts, float32
    C  = top-8 of p;  w = p[C] / sum p[C]
    x  = x + sum_{e in C, e held here} w_e SwiGLU_e(h2)
    RMS_final, then the untied head.

    the indexer's loss of a layer (DeepSeek-V3.2's sparse stage):
    pbar[t, s] = sg(mean_h p_h[t, s]);  sigma[t, .] = softmax_{S_t}(I[t, .])
    KL_t = sum_{s in S_t} pbar log(pbar / sigma)
    loss = L_caller + indexer_loss_weight x mean over layers of mean over tokens of KL_t

Departures from the published description, each noted where it applies:

- **text only**: no vision tower (its widths are not in the published
  config); under text tokens an mrope position's three components are
  equal, so the rotary is the one-dimensional one.
- **ties at tau_t are all kept** (the program alike): a `top_k` that
  breaks ties by index would keep exactly topk. With float32 scores of
  seeded weights a tie beyond an exact zero of the relu does not occur.
- **the choice is a token's**: `q_chunk_size` / `kv_chunk_size` (512) are
  read as the published implementation's tiles, not a choice by blocks.
- **the experts held here only**: `num_experts` counts the experts whose
  weights this chip holds, `num_experts_routed` the router's outputs,
  `experts_held_first` the first held; router, top-k and weights are
  over all routed experts, and nothing is added for the rest.
- **the vocabulary slice**: embedding and head have `vocab_size` rows.
- the mask is a constant of the backward pass and pbar is under sg, so
  the policy's gradient is the caller's loss's alone and the indexer's
  the KL's alone: the published separation (DeepSeek-V3.2's sparse
  stage), assumed to be how this model is trained too.
- attention and the indexer's scores are computed a block of query rows
  at a time, the logits a block of positions at a time.

Independent of the code under test: it reads the program's parameter
tree (`layers`, stacked on a leading axis, weights stored [in, out], the
held experts stacked [held, in, out], the indexer under `attn/indexer`)
and the config's keys, and nothing else. The weights are the served
ones (bf16), upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_norm(x, w, b, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


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
    """[T, hidden] -> the held experts' part of the routed sum."""
    routed = hf.get("num_experts_routed", hf["num_experts"])
    first, held = hf.get("experts_held_first", 0), hf["num_experts"]
    p = jax.nn.softmax(h2 @ mlp["router"], axis=-1)  # [T, routed]
    p_chosen, chosen = jax.lax.top_k(p, hf["num_experts_per_tok"])
    w = p_chosen / jnp.sum(p_chosen, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32)
                      * w[..., None], axis=1)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
        return m + weights[:, first + e, None] * _swiglu(h2, one), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(h2), jnp.arange(held))
    return m


def index_scores(h, ix, hf, rotary=True):
    """The indexer's scores `I` [T, T] float32 of one layer, from its
    normed input h (which they do not move)."""
    sa = hf["sa_config"]
    T = h.shape[0]
    heads, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h = jax.lax.stop_gradient(h)
    qi = (h @ ix["iq_proj"]).reshape(T, heads, d)
    ki = _layer_norm(h @ ix["ik_proj"], ix["ik_norm"]["weight"], ix["ik_norm"]["bias"])
    wi = h @ ix["iw_proj"]
    if rotary:
        pos = jnp.arange(T)
        qi = _rope(qi, pos, hf["rope_theta"])
        ki = _rope(ki[:, None, :], pos, hf["rope_theta"])[:, 0]

    def block(qw):
        qb, wb = qw  # [ROWS, heads, d], [ROWS, heads]
        s = jax.nn.relu(jnp.einsum("thd,sd->ths", qb, ki))
        return jnp.einsum("th,ths->ts", wb, s) * (d ** -0.5 * heads ** -0.5)

    scores = jax.lax.map(block, (qi.reshape(T // ROWS, ROWS, heads, d),
                                 wi.reshape(T // ROWS, ROWS, heads)))
    scores = scores.reshape(T, T)
    return jnp.where(scores == 0.0, 0.0, scores)  # -0 (a negative weight's zero) is 0


def choose(scores, topk, mode="indexer"):
    """bool [T, T]: the keys each query reads. `mode` (the tolerance's
    controls): "all" leaves the choice out, "last" takes the last topk
    keys instead of the indexer's."""
    T = scores.shape[0]
    t = jnp.arange(T)
    seen = t[:, None] >= t[None, :]
    if mode == "all" or T <= topk:
        return seen
    if mode == "last":
        return seen & (t[:, None] - t[None, :] < topk)
    held = jnp.where(seen, jax.lax.stop_gradient(scores), -jnp.inf)
    kth = -jnp.sort(-held, axis=-1)[:, topk - 1]
    tau = jnp.where(t + 1 > topk, kth, -jnp.inf)
    return seen & (held >= tau[:, None])


def _attention(q, k, v, choice):
    """q [T, H, hd], k and v [T, H, hd] (kv heads repeated), choice bool
    [T, T] -> (out [T, H, hd], the heads' mean probability [T, T]), ROWS
    query rows at a time."""
    T, _, hd = q.shape

    def block(qc):
        qb, cb = qc  # [ROWS, H, hd], [ROWS, T]
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(cb[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v), p.mean(axis=0)

    out, pbar = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, *q.shape[1:]),
                                    choice.reshape(T // ROWS, ROWS, T)))
    return out.reshape(q.shape), pbar.reshape(T, T)


def _kl(pbar, scores, choice):
    """KL_t [T]: from the heads' mean probability (a constant) to the
    softmax of the indexer's scores over the chosen keys."""
    pbar = jax.lax.stop_gradient(pbar)
    log_sigma = jax.nn.log_softmax(jnp.where(choice, scores, -jnp.inf), axis=-1)
    held = pbar > 0.0
    return jnp.sum(jnp.where(held, pbar * (jnp.log(jnp.where(held, pbar, 1.0))
                                           - jnp.where(held, log_sigma, 0.0)), 0.0), axis=-1)


def _layer(x, lp, hf, mode="indexer", index_rotary=True):
    """-> (x after the layer, the layer's choice [T, T], its KL_t [T], its
    scores I [T, T])."""
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    T = x.shape[0]
    H, Hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    at = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = _rms((h @ at["wq"]).reshape(T, H, hd), at["q_norm"], eps)
    k = _rms((h @ at["wk"]).reshape(T, Hkv, hd), at["k_norm"], eps)
    v = (h @ at["wv"]).reshape(T, Hkv, hd)
    pos = jnp.arange(T)
    q, k = _rope(q, pos, hf["rope_theta"]), _rope(k, pos, hf["rope_theta"])
    scores = index_scores(h, at["indexer"], hf, rotary=index_rotary)
    choice = choose(scores, hf["sa_config"]["topk"], mode)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    a, pbar = _attention(q, k, v, choice)
    x = x + a.reshape(T, H * hd) @ at["wo"]
    h2 = _rms(x, lp["ln2"]["weight"], eps)
    return x + expert_layer(h2, lp["mlp"], hf), choice, _kl(pbar, scores, choice), scores


def _layers_in_order(params):
    stack = params["layers"]
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i], stack) for i in range(n)]


def _stack(params, ids, hf, **control):
    """-> (hidden states after the final norm [T, hidden], the layers'
    choices [L, T, T], their KL_t [L, T], their scores [L, T, T])."""
    x = params["embedding"]["weight"][ids].astype(jnp.float32)
    per_layer = []
    for lp in _layers_in_order(params):
        x, *rest = _layer(x, lp, hf, **control)
        per_layer.append(rest)
    x = _rms(x, params["final_norm"]["weight"].astype(jnp.float32), hf["rms_norm_eps"])
    return (x, *(jnp.stack(a) for a in zip(*per_layer)))


def _head_logprobs(x, head, labels):
    def rows(xn):  # a block of positions: log-softmax over the slice
        logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
        return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

    blocks = (x.reshape(-1, ROWS, x.shape[-1]), labels.reshape(-1, ROWS))
    return jax.lax.map(rows, blocks).reshape(-1)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
         "rms_norm_eps", "rope_theta", "sa_config", "num_experts",
         "num_experts_routed", "experts_held_first", "num_experts_per_tok")


def _padded(token_ids, pad_to):
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // ROWS) * ROWS
    return np.concatenate([ids, np.zeros(padded - n, np.int32)]), n


def _small(hf):
    small = {k: hf[k] for k in _KEYS if k in hf}
    small["sa_config"] = dict(small["sa_config"])
    return small


def next_token_logprobs(params, hf, token_ids, pad_to=None, **control) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding, nor choose it, and every token is routed on its own)
    so that every call shares one compiled program. `control`: `mode`
    ("all" | "last") and `index_rotary` (False), the tolerance's controls."""
    ids, n = _padded(token_ids, pad_to)
    small = _small(hf)

    def fwd(p, i):
        with jax.default_matmul_precision("highest"):
            x = _stack(p, i, small, **control)[0]
            return _head_logprobs(x, p["head"]["weight"].astype(jnp.float32),
                                  jnp.roll(i, -1))

    return np.asarray(jax.jit(fwd)(params, jnp.asarray(ids)), np.float32)[: n - 1]


def _of_stack(params, hf, token_ids, pad_to, which):
    ids, n = _padded(token_ids, pad_to)
    small = _small(hf)

    def fwd(p, i):
        with jax.default_matmul_precision("highest"):
            return _stack(p, i, small)[which]

    return np.asarray(jax.jit(fwd)(params, jnp.asarray(ids))), n


def indexer_choice(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """bool [layers, T, T]: the keys each query of each layer reads."""
    got, n = _of_stack(params, hf, token_ids, pad_to, 1)
    return got[:, :n, :n]


def indexer_scores(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """float32 [layers, T, T]: each layer's scores I (a query's threshold
    is the least of its chosen cells')."""
    got, n = _of_stack(params, hf, token_ids, pad_to, 3)
    return got[:, :n, :n]


def indexer_kl(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """float32 [layers, T]: KL_t of each layer's indexer."""
    got, n = _of_stack(params, hf, token_ids, pad_to, 2)
    return got[:, :n]


def loss(params, hf, token_ids, prompt_len, indexer_weight=1.0):
    """The scalar a training step of this system minimises over one
    sequence, with minus the logprob as the caller's loss: the mean over
    the response tokens token[prompt_len..] of -log p(token), plus
    `indexer_weight` times the mean over layers of the mean over all the
    sequence's tokens of KL_t. Differentiable in `params`; T must be a
    multiple of ROWS."""
    ids = jnp.asarray(token_ids, jnp.int32)
    T = ids.shape[0]
    t = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        h, _, kl, _ = _stack(params, ids, hf)
        logp = _head_logprobs(h, params["head"]["weight"].astype(jnp.float32),
                              jnp.roll(ids, -1))
        scored = (t >= prompt_len - 1) & (t < T - 1)
        return (-jnp.sum(jnp.where(scored, logp, 0.0)) / jnp.sum(scored)
                + indexer_weight * jnp.mean(kl))
