"""Plain reference forward of the Phi-4-mini-flash decoder (SambaY with
differential attention), for `correct`.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, no chunks, one sequence at a time. Written
from the layer equations, `x` being `[T, hidden]`; for layer i of L:

    u = LN1(x);  h = x + Mixer_i(u);  x = h + W2 (silu(g) * y),  [g | y] = W1 LN2(h)

LN a LayerNorm with weight and bias; no position encoding anywhere; a
final LayerNorm; the head is the embedding. The mixers:

    M  (i even, i <= L/2)   [x | z] = u W_in
       x_t = silu(b_c + sum_{l=0..K-1} w_c[l] x_{t-l})      depthwise, zeros before t = 0
       [r | B | C] = x W_x;  dt = softplus(r W_dt + b_dt);  A = -exp(A_log) [d_in, N]
       S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t,  S_{-1} = 0   **token by token**
       y_t = S_t C_t + D * x_t;  part = (y * silu(z)) W_out;  layer L/2 keeps m = y
    A  (i odd)   [q | k | v] = u W_qkv + b;  heads in consecutive pairs: q1, q2 the even and
       odd q heads, k1, k2 likewise, v the pairs joined to heads of twice the size
       A1 = softmax(q1 k1^T / sqrt(hd) + mask) v,  A2 = softmax(q2 k2^T / sqrt(hd) + mask) v
       (q pair p on kv pair p // (q pairs / kv pairs));  mask: j <= i, and for i < L/2 also
       i - j < sliding_window
       lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 i)
       part = (RMSNorm_2hd(A1 - lambda A2) * w * (1 - l0)) W_o + b_o
       layer L/2 + 1 keeps its k and v; a layer i >= L/2 + 3 has q only and reads them
    G  (i even, i >= L/2 + 2)   part = (silu(u W_1) * m) W_2

Departures from the published model, each because the configuration is
one chip's share of a deployment (the config file's `deployment`):

- **the vocabulary slice.** The embedding has `vocab_size` rows: the
  logits and their softmax are over the slice.
- attention is computed a block of query rows at a time and the logits a
  block of positions at a time.

Independent of the code under test: the state-space layer is the
recurrence itself, a `lax.scan` over positions, where the program runs a
kernel over blocks of time; attention is two dense softmaxes under an
explicit mask, where the program makes one kernel call over rearranged
heads. It reads the program's parameter tree (`stacks/<parts>`, each kind
of layer stacked on a leading axis in layer order, the two layers that
keep a tensor in stacks of their own, matrices stored
[in, out]; the convolution [taps, channels] with the last tap on the
position itself, as the checkpoint's conv1d has it, so w_c[l] above is
row K - 1 - l) and the config's keys, and nothing else. The weights are
the served ones (bf16), upcast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256  # query rows / positions whose scores / logits are held at once
STACKS = {"M": "ssm+dense", "m": "ssm+dense^", "A": "diffattention+dense",
          "a": "diffattention+dense^", "G": "gmu+dense", "X": "xdiffattention+dense"}


def letters(n_layers: int) -> str:
    """The published rule: M a selective scan, A differential attention
    with k and v of its own, G a gated memory unit, X cross-attention;
    m and a the scan and the attention whose tensors later layers read
    (the program keeps their parameters in stacks of their own)."""
    half = n_layers // 2
    one = lambda i: (("m" if i == half else "M" if i < half else "G") if i % 2 == 0 else
                     ("a" if i == half + 1 else "A" if i < half else "X"))
    return "".join(one(i) for i in range(n_layers))


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def recurrence(x, dt, A, B, C):
    """x, dt [T, d_in], A [d_in, N], B and C [T, N] -> y [T, d_in]:
    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t from S = 0;
    y_t = S_t C_t."""

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = jnp.exp(dtt[:, None] * A) * S + (dtt * xt)[:, None] * Bt[None, :]
        return S, S @ Ct

    return jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32), (x, dt, B, C))[1]


def scan_layer(u, sp, hf):
    """[T, hidden] -> (the M layer's part, its scan output before the gate)."""
    T = u.shape[0]
    K, N = sp["conv_w"].shape[0], sp["A_log"].shape[1]
    rank = sp["dt_proj"].shape[0]
    x, z = jnp.split(u @ sp["in_proj"], 2, axis=-1)
    shifted = jnp.pad(x, ((K - 1, 0), (0, 0)))  # zeros before the sequence
    x = jax.nn.silu(sum(shifted[K - 1 - l: K - 1 - l + T] * sp["conv_w"][K - 1 - l]
                        for l in range(K)) + sp["conv_b"])
    r, B, C = jnp.split(x @ sp["x_proj"], [rank, rank + N], axis=-1)
    dt = jax.nn.softplus(r @ sp["dt_proj"] + sp["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(sp["A_log"]), B, C) + sp["D"] * x
    return (y * jax.nn.silu(z)) @ sp["out_proj"], y


def second_softmax_weight(at, l0):
    """lambda. (A control for the tolerance replaces this.)"""
    return (jnp.exp(jnp.sum(at["lambda_q1"] * at["lambda_k1"]))
            - jnp.exp(jnp.sum(at["lambda_q2"] * at["lambda_k2"])) + l0)


def attention_layer(u, at, hf, i, window=None, kv=None):
    """[T, hidden] -> (the layer's part, the k and v it attended over)."""
    T = u.shape[0]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["hidden_size"] // H
    q = (u @ at["wq"] + at["bq"]).reshape(T, H // 2, 2, hd)
    if kv is None:
        kv = ((u @ at["wk"] + at["bk"]).reshape(T, Hkv // 2, 2, hd),
              (u @ at["wv"] + at["bv"]).reshape(T, Hkv // 2, 2 * hd))
    k, v = kv
    per = (H // 2) // (Hkv // 2)  # q pairs a kv pair
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    cols = jnp.arange(T)

    def block(qr):  # ROWS query rows at a time
        qb, rows = qr
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window

        def attend(which):
            s = jnp.einsum("thd,shd->hts", qb[:, :, which], k[:, :, which]) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shd->thd", p, v)

        return attend(0), attend(1)

    a1, a2 = jax.lax.map(block, (q.reshape(T // ROWS, ROWS, H // 2, 2, hd),
                                 cols.reshape(T // ROWS, ROWS)))
    l0 = 0.8 - 0.6 * math.exp(-0.3 * i)
    a = (a1 - second_softmax_weight(at, l0) * a2).reshape(T, H // 2, 2 * hd)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + hf["layer_norm_eps"])
    a = a * at["sub_norm"] * (1.0 - l0)
    return a.reshape(T, H * hd) @ at["wo"] + at["bo"], kv


def _layers_in_order(params, pattern):
    """(letter, the layer's slice of its kind's stack), first layer first."""
    seen = {letter: 0 for letter in STACKS}
    out = []
    for letter in pattern:
        n, stack = seen[letter], params["stacks"][STACKS[letter]]
        out.append((letter, jax.tree_util.tree_map(
            lambda a: a[n].astype(jnp.float32), stack)))
        seen[letter] += 1
    return out


def _forward(params, ids, hf):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller)."""
    T = ids.shape[0]
    L, eps = hf["num_hidden_layers"], hf["layer_norm_eps"]
    m = kv = None
    with jax.default_matmul_precision("highest"):
        emb = params["embedding"]["weight"].astype(jnp.float32)
        x = emb[ids]
        for i, (letter, lp) in enumerate(_layers_in_order(params, letters(L))):
            u = _ln(x, lp["ln1"], eps)
            if letter in "Mm":
                part, y = scan_layer(u, lp["ssm"], hf)
                m = y if i == L // 2 else m
            elif letter == "G":
                part = (jax.nn.silu(u @ lp["gmu"]["w_in"]) * m) @ lp["gmu"]["w_out"]
            elif letter in "Aa":
                part, own = attention_layer(
                    u, lp["attn"], hf, i,
                    window=hf.get("sliding_window") if i < L // 2 else None)
                kv = own if i == L // 2 + 1 else kv
            else:
                part, _ = attention_layer(u, lp["attn"], hf, i, kv=kv)
            h = x + part
            w = lp["mlp"]
            v = _ln(h, lp["ln2"], eps)
            x = h + (jax.nn.silu(v @ w["w_gate"]) * (v @ w["w_up"])) @ w["w_down"]
        x = _ln(x, jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params["final_norm"]), eps)
        nxt = jnp.roll(ids, -1)

        def rows(xn):  # a block of positions: log-softmax over the slice
            logp = jax.nn.log_softmax(xn[0] @ emb.T, axis=-1)
            return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

        blocks = (x.reshape(-1, ROWS, x.shape[-1]), nxt.reshape(-1, ROWS))
        return jax.lax.map(rows, blocks).reshape(T)


_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "layer_norm_eps", "sliding_window")


def next_token_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding) so that every call shares one compiled program."""
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // ROWS) * ROWS
    ids = np.concatenate([ids, np.zeros(padded - n, np.int32)])
    small = {k: hf[k] for k in _KEYS if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small))
    return np.asarray(fn(params, jnp.asarray(ids)), np.float32)[: n - 1]
