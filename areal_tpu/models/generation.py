"""In-framework generation: jitted prefill + KV-cache decode loop.

Counterpart of the reference's generation engine
(realhf/impl/model/nn/real_llm_generate.py): token-by-token decode with a
preallocated KV cache and on-device sampling. The reference needs CUDA
graph capture (`maybe_capture_cudagraph:218`) to make tiny decode kernels
fast; on TPU the whole decode step is one jitted XLA program with donated
cache buffers, so no capture machinery exists at all.

Cache layout: k/v as [L, B, S, Hkv, hd] matching the scan-over-layers
parameter stacking. Batch entries are independent sequences (generation is
not packed; packing happens on training inputs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import forward as packed_forward
from areal_tpu.models.transformer import _norm, _mlp
from areal_tpu.ops.attention import decode_attention
from areal_tpu.ops.rotary import apply_rotary, rotary_cos_sin, rotary_inv_freq
from areal_tpu.ops.norms import rms_norm
from areal_tpu.ops.sampling import sample_token


@dataclasses.dataclass
class GenState:
    """Decode-loop carry (a pytree)."""

    rng: jax.Array
    k_cache: jnp.ndarray  # [L, B, S, Hkv, hd]
    v_cache: jnp.ndarray
    lengths: jnp.ndarray  # [B] tokens currently in cache (incl. prompt)
    logits: jnp.ndarray  # [B, V] for the next sampling step
    out_tokens: jnp.ndarray  # [B, max_new]
    out_logprobs: jnp.ndarray  # [B, max_new]
    done: jnp.ndarray  # [B] bool
    step: jnp.ndarray  # scalar int32


jax.tree_util.register_dataclass(
    GenState,
    data_fields=[
        "rng", "k_cache", "v_cache", "lengths", "logits",
        "out_tokens", "out_logprobs", "done", "step",
    ],
    meta_fields=[],
)


def _decode_layer(x, lp, cfg, cos, sin, k_cache_l, v_cache_l, lengths, cdt):
    """One transformer layer for a single new token per sequence.

    x: [B, D]; k/v_cache_l: [B, S, Hkv, hd]; lengths: [B] count *before*
    this token. Returns (x, new_k_cache_l, new_v_cache_l).
    """
    B, D = x.shape
    h = _norm(x, lp["ln1"], cfg)
    a = lp["attn"]
    q = h @ a["wq"].astype(cdt)
    k = h @ a["wk"].astype(cdt)
    v = h @ a["wv"].astype(cdt)
    if "bq" in a:
        q = q + a["bq"].astype(cdt)
        k = k + a["bk"].astype(cdt)
        v = v + a["bv"].astype(cdt)
    q = q.reshape(B, cfg.n_q_heads, cfg.head_dim)
    k = k.reshape(B, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
    if cos is not None:
        # cos/sin: [B, hd/2] at the current position of each row.
        q = apply_rotary(q, cos, sin, cfg.rotary_interleaved)
        k = apply_rotary(k, cos, sin, cfg.rotary_interleaved)
    b_idx = jnp.arange(B)
    k_cache_l = k_cache_l.at[b_idx, lengths].set(k)
    v_cache_l = v_cache_l.at[b_idx, lengths].set(v)
    out = decode_attention(q, k_cache_l, v_cache_l, lengths + 1)
    attn_out = out.reshape(B, cfg.q_dim) @ a["wo"].astype(cdt)
    if "bo" in a:
        attn_out = attn_out + a["bo"].astype(cdt)
    x = x + attn_out
    h = _norm(x, lp["ln2"], cfg)
    if cfg.moe is not None:
        from areal_tpu.models.moe import decode_moe_overrides, moe_mlp

        # Same decode-time dispatch/capacity as engine/paged.py, so the
        # batch generator and the paged server produce identical greedy
        # streams for MoE models.
        d_dispatch, d_cap = decode_moe_overrides(cfg)
        m, _ = moe_mlp(
            h, lp["mlp"], cfg, cdt,
            capacity_factor=d_cap, dispatch=d_dispatch,
        )
    else:
        m = _mlp(h, lp["mlp"], cfg, cdt)
    x = x + m
    return x, k_cache_l, v_cache_l


def decode_step(params, cfg: TransformerConfig, tokens, k_cache, v_cache, lengths):
    """One decode step for all sequences.

    tokens: [B] the tokens just sampled (to be fed in); lengths: [B] cache
    fill BEFORE this token. Returns (logits [B, V], k_cache, v_cache).
    """
    cfg.require_plain_stack("models/generation.py decode_step")
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embedding"]["weight"][tokens].astype(cdt)  # [B, D]
    if cfg.embedding_multiplier:
        x = x * jnp.asarray(cfg.embedding_multiplier, cdt)
    if cfg.pos_emb == "learned":
        x = x + params["pos_embedding"]["weight"][lengths].astype(cdt)
        cos = sin = None
    else:
        inv_freq = jnp.asarray(
            rotary_inv_freq(
                cfg.head_dim, cfg.rotary_base, cfg.rotary_scaling,
                cfg.rotary_scaling_type, cfg.rotary_scaling_params,
            )
        )
        cos, sin = rotary_cos_sin(lengths, inv_freq)  # [B, hd/2]

    def body(x, layer):
        lp, kc, vc = layer
        x, kc, vc = _decode_layer(x, lp, cfg, cos, sin, kc, vc, lengths, cdt)
        return x, (kc, vc)

    x, (k_cache, v_cache) = jax.lax.scan(body, x, (params["layers"], k_cache, v_cache))
    x = _norm(x, params["final_norm"], cfg)
    head_w = (
        params["embedding"]["weight"].T
        if cfg.tied_embeddings
        else params["head"]["weight"]
    )
    logits = (x @ head_w.astype(cdt)).astype(jnp.float32)
    return logits, k_cache, v_cache


def prefill(params, cfg: TransformerConfig, input_ids, prompt_lens, cache_len: int):
    """Run the prompt forward, build the KV cache.

    input_ids: [B, P] right-padded prompts; prompt_lens: [B].
    Returns (last_logits [B, V], k_cache, v_cache) with caches sized
    [L, B, cache_len, Hkv, hd].
    """
    cfg.require_plain_stack("models/generation.py prefill")
    B, P = input_ids.shape
    pos = jnp.arange(P)[None, :]
    seg = (pos < prompt_lens[:, None]).astype(jnp.int32)
    positions = jnp.where(seg > 0, pos, 0).astype(jnp.int32)
    logits, kvs = packed_forward(
        params, cfg, input_ids, seg, positions, return_kv=True
    )
    # kvs: (k, v) each [L, B, P, Hkv, hd]
    k_pref, v_pref = kvs
    L = k_pref.shape[0]
    Hkv, hd = k_pref.shape[-2], k_pref.shape[-1]
    cdt = k_pref.dtype
    k_cache = jnp.zeros((L, B, cache_len, Hkv, hd), cdt)
    v_cache = jnp.zeros((L, B, cache_len, Hkv, hd), cdt)
    k_cache = k_cache.at[:, :, :P].set(k_pref)
    v_cache = v_cache.at[:, :, :P].set(v_pref)
    last_idx = jnp.maximum(prompt_lens - 1, 0)
    last_logits = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1
    )[:, 0]  # [B, V]
    return last_logits, k_cache, v_cache


# Module-level jit so the compile cache survives across generate calls.
_prefill_jit = jax.jit(prefill, static_argnames=("cfg", "cache_len"))


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "max_new_tokens", "min_new_tokens", "greedy",
        "top_k", "stop_tokens",
    ),
    donate_argnames=("k_cache", "v_cache"),
)
def _decode_loop(
    params,
    cfg: TransformerConfig,
    first_logits,
    k_cache,
    v_cache,
    prompt_lens,
    rng,
    max_new_tokens: int,
    min_new_tokens: int,
    greedy: bool,
    top_k: int,
    top_p,
    temperature,
    stop_tokens: Tuple[int, ...],
):
    B = first_logits.shape[0]
    stop_arr = jnp.asarray(stop_tokens, jnp.int32) if stop_tokens else None
    state = GenState(
        rng=rng,
        k_cache=k_cache,
        v_cache=v_cache,
        lengths=prompt_lens,
        logits=first_logits,
        out_tokens=jnp.zeros((B, max_new_tokens), jnp.int32),
        out_logprobs=jnp.zeros((B, max_new_tokens), jnp.float32),
        done=jnp.zeros((B,), bool),
        step=jnp.asarray(0, jnp.int32),
    )

    def cond(s: GenState):
        return (s.step < max_new_tokens) & ~jnp.all(s.done)

    def body(s: GenState):
        rng, sub = jax.random.split(s.rng)
        forbid_mask = (
            jnp.full((B,), s.step < min_new_tokens) if min_new_tokens > 0 else None
        )
        tokens, logprobs = sample_token(
            s.logits, sub, greedy=greedy, temperature=temperature,
            top_k=top_k, top_p=top_p,
            forbid_token_ids=stop_arr if min_new_tokens > 0 else None,
            forbid_mask=forbid_mask,
        )
        hit_stop = (
            jnp.isin(tokens, stop_arr) if stop_arr is not None
            else jnp.zeros((B,), bool)
        )
        # Rows already done keep emitting pad (token 0) that we mask out.
        emit = jnp.where(s.done, 0, tokens).astype(jnp.int32)
        out_tokens = s.out_tokens.at[:, s.step].set(emit)
        out_logprobs = s.out_logprobs.at[:, s.step].set(
            jnp.where(s.done, 0.0, logprobs)
        )
        logits, kc, vc = decode_step(
            params, cfg, emit, s.k_cache, s.v_cache, s.lengths
        )
        return GenState(
            rng=rng,
            k_cache=kc,
            v_cache=vc,
            lengths=s.lengths + jnp.where(s.done, 0, 1).astype(s.lengths.dtype),
            logits=logits,
            out_tokens=out_tokens,
            out_logprobs=out_logprobs,
            done=s.done | hit_stop,
            step=s.step + 1,
        )

    final = jax.lax.while_loop(cond, body, state)
    return final.out_tokens, final.out_logprobs, final.lengths, final.done


def generate_tokens(
    params,
    cfg: TransformerConfig,
    prompts: List[List[int]],
    gconfig,
    rng: jax.Array,
    eos_token_id: Optional[int] = None,
    prompt_pad_multiple: int = 128,
) -> List[Dict[str, Any]]:
    """Host-facing generation over a batch of prompts.

    Prompts pad to a multiple of the TPU lane width, so the prefill's
    rows qualify for the splash kernel (a shorter or unaligned row runs
    the O(T^2) reference, ops/attention.resolve_attn_impl).

    Returns per-prompt dicts: output_ids, output_logprobs, no_eos.
    """
    B = len(prompts)
    plens = np.array([len(p) for p in prompts], np.int32)
    P = int(
        -(-max(int(plens.max()), 1) // prompt_pad_multiple) * prompt_pad_multiple
    )
    input_ids = np.zeros((B, P), np.int32)
    for i, p in enumerate(prompts):
        input_ids[i, : len(p)] = p
    cache_len = P + gconfig.max_new_tokens

    stop = tuple(gconfig.stop_token_ids)
    if eos_token_id is not None and eos_token_id not in stop:
        stop = stop + (eos_token_id,)

    first_logits, k_cache, v_cache = _prefill_jit(
        params, cfg, jnp.asarray(input_ids), jnp.asarray(plens), cache_len=cache_len
    )
    out_tokens, out_logprobs, lengths, done = _decode_loop(
        params, cfg, first_logits, k_cache, v_cache, jnp.asarray(plens), rng,
        max_new_tokens=gconfig.max_new_tokens,
        min_new_tokens=gconfig.min_new_tokens,
        greedy=gconfig.greedy,
        top_k=gconfig.top_k,
        top_p=jnp.asarray(gconfig.top_p, jnp.float32),
        temperature=jnp.asarray(gconfig.temperature, jnp.float32),
        stop_tokens=stop,
    )
    out_tokens = np.asarray(out_tokens)
    out_logprobs = np.asarray(out_logprobs)
    gen_lens = np.asarray(lengths) - plens
    done = np.asarray(done)
    results = []
    for i in range(B):
        # `lengths` advances on the step that emits the stop token, so
        # gen_lens already counts it (reference convention: EOS terminates
        # the sequence and is part of the output).
        n = int(gen_lens[i])
        results.append(
            {
                "output_ids": out_tokens[i, :n].tolist(),
                "output_logprobs": out_logprobs[i, :n].tolist(),
                "no_eos": not bool(done[i]),
            }
        )
    return results
