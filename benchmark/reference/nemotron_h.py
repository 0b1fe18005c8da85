"""Plain reference forward of the Nemotron-H decoder, for `correct`.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no chunks, no sorting of tokens by
expert, one sequence at a time. It follows the published architecture
(`NemotronHForCausalLM`), layer by layer, `x` being `[T, hidden]`; every
layer is `x + part(RMS(x))`, the part named by the layer's letter in
`hybrid_override_pattern`:

    M  [z | xBC | dt] = h W_in          (d_in = mamba_num_heads x mamba_head_dim)
       xBC_t = silu(b_c + sum_{j=0..K-1} w_c[:, j] xBC_{t-K+1+j})   depthwise, zeros before t = 0
       xBC -> x [H, P], B [G, N], C [G, N];  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t,  S_{-1} = 0     **token by token**
       y_t = S_t C_t + D_h x_t  (head h reads group h // (H / G))
       y = RMS_groups(y * silu(z)) * w  (over each of the G groups of d_in / G channels)
       part = y W_out
    *  q, k, v = h Wq, h Wk, h Wv;  p = softmax(q k^T / sqrt(head)) over j <= i,
       no position encoding;  part = (p v) Wo
    E  s = sigmoid(h Wr); C = top-k of (s + e_score_correction_bias)
       w = s[C] / (sum s[C] + 1e-20) * routed_scaling_factor
       part = Shared(h) + sum_{e in C} w_e Expert_e(h),  every MLP relu(x W_up)^2 W_down
    -  part = relu(h W_up)^2 W_down  (width intermediate_size)

then `norm_f` and the untied head.

Departures from the published model, each because the configuration is
one chip's share of a deployment (the config file's `deployment`):

- **the experts held here only.** `n_routed_experts` counts the experts
  whose weights this chip holds, `num_experts_routed` the router's
  outputs, `experts_held_first` the first held. The router, its top-k and
  the weights `w` are over all routed experts; the sum runs over the
  chosen experts that are held, and nothing is added for the rest. The
  shared expert is whole. That partial result goes on to the next layer.
- **the vocabulary slice.** Embedding and head have `vocab_size` rows:
  the logits and their softmax are over the slice.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time; each expert is applied to every token
  and weighted by 0 where it was not chosen.

Independent of the code under test: the state-space layer is the
recurrence itself, a `lax.scan` over positions, where the program
computes chunks; it reads the program's parameter tree (`stacks/<parts>`,
each kind of layer stacked on a leading axis in layer order, matrices
stored [in, out], the convolution [taps, channels], the held experts
stacked [held, in, out]) and the config's keys, and nothing else. The
weights are the served ones (bf16), upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
PARTS = {"M": "ssm", "*": "attention", "E": "moe", "-": "dense"}


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _relu2_mlp(h, m):
    return jnp.square(jax.nn.relu(h @ m["w_in"])) @ m["w_out"]


def recurrence(x, dt, A, B, C, decay_dtype=None):
    """x [T, H, P], dt [T, H], A [H], B and C [T, H, N] -> y [T, H, P]:
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t from S = 0; y_t = S_t C_t.
    `decay_dtype` (a control for the tolerance) rounds dt A and the
    decay exp(dt A) to that dtype."""
    H, P = x.shape[1:]
    # `reduce_precision`, not a pair of casts: the compiler may drop those
    info = jnp.finfo(decay_dtype or jnp.float32)
    rounded = lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        decay = rounded(jnp.exp(rounded(dtt * A)))
        S = decay[:, None, None] * S + (dtt[:, None] * xt)[..., None] * Bt[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, Ct)

    return jax.lax.scan(step, jnp.zeros((H, P, B.shape[-1]), jnp.float32), (x, dt, B, C))[1]


def state_space_layer(h, sp, hf, decay_dtype=None):
    """[T, hidden] -> the M layer's part."""
    T = h.shape[0]
    H, P, G, N = (hf["mamba_num_heads"], hf["mamba_head_dim"], hf["n_groups"],
                  hf["ssm_state_size"])
    K, d_in = hf["conv_kernel"], H * P
    z, xbc, dt = jnp.split(h @ sp["in_proj"], [d_in, 2 * d_in + 2 * G * N], axis=-1)
    shifted = jnp.pad(xbc, ((K - 1, 0), (0, 0)))  # zeros before the sequence
    xbc = sum(shifted[j: j + T] * sp["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(xbc + sp["conv_b"]) if "conv_b" in sp else jax.nn.silu(xbc)
    x, B, C = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    x = x.reshape(T, H, P)
    to_heads = lambda a: jnp.repeat(a.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + sp["dt_bias"])
    A = -jnp.exp(sp["A_log"])
    y = recurrence(x, dt, A, to_heads(B), to_heads(C), decay_dtype)
    y = (y + sp["D"][:, None] * x).reshape(T, d_in) * jax.nn.silu(z)
    y = _rms(y.reshape(T, G, d_in // G), 1.0, hf["layer_norm_epsilon"])
    return (y.reshape(T, d_in) * sp["norm"]) @ sp["out_proj"]


def attention_layer(h, at, hf):
    T = h.shape[0]
    H, Hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    q = (h @ at["wq"]).reshape(T, H, hd)
    k = jnp.repeat((h @ at["wk"]).reshape(T, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((h @ at["wv"]).reshape(T, Hkv, hd), H // Hkv, axis=1)
    cols = jnp.arange(T)

    def block(qr):  # ROWS query rows at a time
        qb, rows = qr
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        s = jnp.where((rows[:, None] >= cols[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, H, hd),
                              cols.reshape(T // ROWS, ROWS)))
    return out.reshape(T, H * hd) @ at["wo"]


def expert_layer(h, mlp, hf):
    """[T, hidden] -> the E layer's part: the shared expert plus the held
    experts' part of the routed sum."""
    routed = hf.get("num_experts_routed", hf["n_routed_experts"])
    first, held = hf.get("experts_held_first", 0), hf["n_routed_experts"]
    s = jax.nn.sigmoid(h @ mlp["router"])  # [T, routed]
    _, chosen = jax.lax.top_k(s + mlp["expert_bias"], hf["num_experts_per_tok"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        s_chosen = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-20)
    w = s_chosen * hf.get("routed_scaling_factor", 1.0)
    # [T, routed]: a token's weight on each expert, 0 where not chosen
    weights = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=jnp.float32)
                      * w[..., None], axis=1)

    def add_expert(m, e):
        one = {k: mlp[k][e] for k in ("w_in", "w_out")}
        return m + weights[:, first + e, None] * _relu2_mlp(h, one), None

    m = _relu2_mlp(h, mlp["shared"]) if "shared" in mlp else jnp.zeros_like(h)
    m, _ = jax.lax.scan(add_expert, m, jnp.arange(held))
    return m


def _layers_in_order(params, pattern):
    """(letter, the layer's slice of its kind's stack), first layer first."""
    seen = {letter: 0 for letter in PARTS}
    out = []
    for letter in pattern:
        i, stack = seen[letter], params["stacks"][PARTS[letter]]
        out.append((letter, jax.tree_util.tree_map(
            lambda a: a[i].astype(jnp.float32), stack)))
        seen[letter] += 1
    return out


def _forward(params, ids, hf, decay_dtype=None):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller)."""
    T = ids.shape[0]
    eps = hf["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][ids].astype(jnp.float32)
        for letter, lp in _layers_in_order(params, hf["hybrid_override_pattern"]):
            if letter == "M":
                x = x + state_space_layer(
                    _rms(x, lp["ln1"]["weight"], eps), lp["ssm"], hf, decay_dtype)
            elif letter == "*":
                x = x + attention_layer(_rms(x, lp["ln1"]["weight"], eps), lp["attn"], hf)
            elif letter == "E":
                x = x + expert_layer(_rms(x, lp["ln2"]["weight"], eps), lp["mlp"], hf)
            else:
                x = x + _relu2_mlp(_rms(x, lp["ln2"]["weight"], eps), lp["mlp"])
        x = _rms(x, params["final_norm"]["weight"].astype(jnp.float32), eps)
        head = params["head"]["weight"].astype(jnp.float32)
        nxt = jnp.roll(ids, -1)

        def rows(xn):  # a block of positions: log-softmax over the slice
            logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
            return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

        blocks = (x.reshape(-1, ROWS, x.shape[-1]), nxt.reshape(-1, ROWS))
        return jax.lax.map(rows, blocks).reshape(T)


_KEYS = ("hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
         "head_dim", "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
         "n_groups", "ssm_state_size", "conv_kernel", "n_routed_experts",
         "num_experts_routed", "experts_held_first", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor")


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
