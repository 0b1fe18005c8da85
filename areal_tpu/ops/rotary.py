"""Rotary position embeddings with scaling variants.

Replaces the reference's torch rotary module
(realhf/impl/model/modules/rotary.py) with position-indexed jnp: because
batches are packed, every token carries an explicit position id and the
embedding is gathered per token rather than sliced per sequence.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


SUPPORTED_ROPE_TYPES = (None, "default", "linear", "llama3", "yarn")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: `0.1 mscale ln(factor) + 1` for a
    factor over 1, else 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_attention_factor(factor: float, params: Optional[dict] = None) -> float:
    """What a plain head's YaRN table multiplies `cos` and `sin` by, as
    HF's `_compute_yarn_parameters` says it: `attention_factor` where the
    config states one, else the quotient of the two temperatures where it
    states both `mscale` and `mscale_all_dim`, else `yarn_mscale(factor)`.
    (Latent attention puts its temperature on the softmax scale instead:
    `MLAConfig.softmax_scale_factor`.)"""
    p = params or {}
    if p.get("attention_factor") is not None:
        return float(p["attention_factor"])
    if p.get("mscale") and p.get("mscale_all_dim"):
        return yarn_mscale(factor, p["mscale"]) / yarn_mscale(factor, p["mscale_all_dim"])
    return yarn_mscale(factor)


def rotary_inv_freq(
    head_dim: int,
    base: float = 10000.0,
    scaling: Optional[float] = None,
    scaling_type: Optional[str] = None,
    scaling_params: Optional[dict] = None,
) -> np.ndarray:
    if scaling_type not in SUPPORTED_ROPE_TYPES:
        raise NotImplementedError(
            f"rope scaling type {scaling_type!r} not supported "
            f"(supported: {SUPPORTED_ROPE_TYPES})"
        )
    inv_freq = 1.0 / (base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if scaling_type == "linear" and scaling:
        inv_freq = inv_freq / scaling
    elif scaling_type == "llama3" and scaling:
        # llama3-style NTK frequency interpolation: low frequencies scaled,
        # high frequencies kept, smooth ramp between. Factors come from the
        # checkpoint's rope_scaling config.
        p = scaling_params or {}
        low_freq_factor = p.get("low_freq_factor", 1.0)
        high_freq_factor = p.get("high_freq_factor", 4.0)
        orig_ctx = p.get("original_max_position_embeddings", 8192)
        wavelen = 2 * np.pi / inv_freq
        low_wl = orig_ctx / low_freq_factor
        high_wl = orig_ctx / high_freq_factor
        scaled = inv_freq / scaling
        smooth = (orig_ctx / wavelen - low_freq_factor) / (
            high_freq_factor - low_freq_factor
        )
        smoothed = (1 - smooth) * scaled + smooth * inv_freq
        inv_freq = np.where(
            wavelen < high_wl, inv_freq, np.where(wavelen > low_wl, scaled, smoothed)
        )
    elif scaling_type == "yarn" and scaling:
        # YaRN (arXiv:2309.00071) as DeepSeek-V3 and HF's
        # `_compute_yarn_parameters` run it: the dimensions
        # that turn more than `beta_fast` times over the original context
        # keep their frequency, those that turn fewer than `beta_slow`
        # times are divided by the factor, a linear ramp between whose
        # ends are whole dimensions unless `truncate` is false.
        p = scaling_params or {}
        orig_ctx = p.get("original_max_position_embeddings", 4096)
        turns = lambda beta: head_dim * math.log(orig_ctx / (beta * 2 * math.pi)) / (
            2 * math.log(base))
        low, high = turns(p.get("beta_fast") or 32), turns(p.get("beta_slow") or 1)
        if p.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, head_dim - 1)
        ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / scaling * ramp + inv_freq * (1.0 - ramp)
    return inv_freq.astype(np.float32)


def rotary_cos_sin(positions: jnp.ndarray, inv_freq: jnp.ndarray,
                   attention_factor: float = 1.0):
    """cos/sin of shape (*positions.shape, head_dim/2), fp32, both times
    `attention_factor` (a scaled table's temperature, HF's
    `attention_scaling`: q and k each carry it, so the logits its square)."""
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq[None, :]
    if attention_factor == 1.0:
        return jnp.cos(freqs), jnp.sin(freqs)
    return jnp.cos(freqs) * attention_factor, jnp.sin(freqs) * attention_factor


def apply_rotary(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    interleaved: bool = False,
) -> jnp.ndarray:
    """Apply rotary embedding.

    x: (..., n_heads, head_dim); cos/sin: (..., head_dim/2) broadcast over heads.
    Non-interleaved (HF neox style): pairs are (x[:d/2], x[d/2:]).
    Tables narrower than half a head (a partial rotation,
    `TransformerConfig.rotary_fraction`): the head's first `2 * width`
    columns are turned, paired among themselves, and the rest left as
    they are (scope `attn_rotary`).
    """
    turned = 2 * cos.shape[-1]
    if turned < x.shape[-1]:
        with jax.named_scope("attn_rotary"):
            return jnp.concatenate(
                [apply_rotary(x[..., :turned], cos, sin, interleaved), x[..., turned:]],
                axis=-1)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    d2 = x.shape[-1] // 2
    if interleaved:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    else:
        x1 = x[..., :d2]
        x2 = x[..., d2:]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.concatenate([o1, o2], axis=-1)
    return out.astype(dtype)
