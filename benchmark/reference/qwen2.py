"""Plain reference forward of the Qwen2 / Qwen2.5 decoder, for `correct`.

Straight `jax.numpy` in float32 at the highest matmul precision: no
kernels, no cache, no packing, one sequence at a time. It follows the
published architecture (Qwen2ForCausalLM): token embedding; per layer
RMSNorm -> q/k/v projections with bias -> rotary embedding in the
half-split ("neox") layout with base `rope_theta` -> causal softmax
attention with grouped KV heads, scaled by 1/sqrt(head size) -> output
projection without bias, residual; RMSNorm -> SwiGLU MLP
(down(silu(gate(x)) * up(x))), residual; final RMSNorm; logits through
the tied embedding (or a separate head).

Independent of the code under test: it reads the program's parameter
tree (layers stacked on a leading axis, weights stored [in, out]) and
nothing else of it. The weights are the served ones (bf16), upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


LOGIT_ROWS = 256  # positions whose [rows, vocabulary] logits are held at once


def _forward(params, ids, hf):
    """[T] float32: log p(ids[t+1] | ids[..t]) at each position t (the
    last position scores ids[0] and is dropped by the caller)."""
    f32 = lambda a: a.astype(jnp.float32)
    T = ids.shape[0]
    H = hf["num_attention_heads"]
    Hkv = hf.get("num_key_value_heads", H)
    hd = hf.get("head_dim") or hf["hidden_size"] // H
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    emb = params["embedding"]["weight"]

    def layer(x, lp):  # one decoder layer; lp is this layer's slice
        at = {k: f32(v) for k, v in lp["attn"].items()}
        ml = {k: f32(v) for k, v in lp["mlp"].items()}
        h = _rms(x, f32(lp["ln1"]["weight"]), eps)
        q = (h @ at["wq"] + at["bq"]).reshape(T, H, hd)
        k = (h @ at["wk"] + at["bk"]).reshape(T, Hkv, hd)
        v = (h @ at["wv"] + at["bv"]).reshape(T, Hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(T, H * hd) @ at["wo"]
        h = _rms(x, f32(lp["ln2"]["weight"]), eps)
        x = x + (jax.nn.silu(h @ ml["w_gate"]) * (h @ ml["w_up"])) @ ml["w_down"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = f32(emb[ids])
        # The program stacks its layers on a leading axis; walk them in order.
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x, f32(params["final_norm"]["weight"]), eps)
        head = f32(emb).T if hf.get("tie_word_embeddings") else f32(params["head"]["weight"])
        nxt = jnp.roll(ids, -1)

        def rows(xn):  # a block of positions: full-vocabulary log-softmax
            logp = jax.nn.log_softmax(xn[0] @ head, axis=-1)
            return jnp.take_along_axis(logp, xn[1][:, None], axis=-1)[:, 0]

        blocks = (x.reshape(-1, LOGIT_ROWS, x.shape[-1]), nxt.reshape(-1, LOGIT_ROWS))
        return jax.lax.map(rows, blocks).reshape(T)


def next_token_logprobs(params, hf, token_ids, pad_to=None) -> np.ndarray:
    """log p(token[t+1] | token[..t]) for t = 0..T-2, float32 [T-1].
    `pad_to` pads the sequence (a causal model's earlier positions do not
    see the padding) so that every call shares one compiled program."""
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    padded = -(-max(n, pad_to or 0) // LOGIT_ROWS) * LOGIT_ROWS
    ids = np.concatenate([ids, np.zeros(padded - n, np.int32)])
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    small = {k: hf[k] for k in keys if k in hf}
    fn = jax.jit(lambda p, i: _forward(p, i, small))
    return np.asarray(fn(params, jnp.asarray(ids)), np.float32)[: n - 1]
