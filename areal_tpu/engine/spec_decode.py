"""N-gram (prompt-lookup) speculative decoding for the paged engine.

Decode at small batch is weight-streaming-bound: every step reads the
full parameter set from HBM to emit ONE token per slot. Speculative
decoding amortizes that read across several tokens — draft k candidate
continuations, feed them all in one multi-row step (extra rows are
nearly free while weights dominate the bytes), and keep the verified
prefix. The reference's serving stack has no speculative decoding
(realhf/impl/model/backend/sglang.py) — this is a TPU-side extension,
opt-in via ServingEngine(speculative_draft_len=...).

Drafts come from prompt-lookup (n-gram matching): the last `g` tokens
of a slot's history are matched against earlier history; the tokens
that followed the most recent earlier occurrence become the draft.
Math-RL generations repeat prompt fragments, numbers, and derivation
spans constantly, so acceptance is high exactly where the async design
needs throughput. Everything is device-resident (history buffer,
matching, verification) — no host round trips inside the block.

Verification is lossless:
- greedy rows accept a draft token iff it IS the argmax — the emitted
  stream is bit-identical to plain greedy decode;
- sampled rows use standard speculative sampling with a point-mass
  draft distribution: accept draft t with prob p(t); on rejection,
  resample from p with t removed and renormalized. The emitted stream
  is distributed EXACTLY as plain sampling (Leviathan et al.'s
  correctness argument with q = delta_t).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from areal_tpu.engine.paged import (
    NEG_INF,
    paged_decode_step,
    warp_logits,
)
from areal_tpu.models.config import TransformerConfig


def propose_ngram_drafts(
    history: jnp.ndarray,  # [B, S+1] int32; col S is a scratch column
    lengths: jnp.ndarray,  # [B] int32: position of the PENDING token
    ngram: int,
    draft_len: int,
    window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Match the n-gram ending at the pending token against earlier
    history; return (draft [B, draft_len] int32, eff [B] int32 — number
    of proposed tokens, 0 when no match / not enough history).

    history[b, 0..lengths[b]] are known tokens (prompt + emitted, the
    last one pending, its KV not yet written). The draft is the
    continuation after the MOST RECENT earlier occurrence of the
    window; continuation tokens must themselves be known history.

    `window > 0` bounds the backward search to each slot's last `window`
    candidate match positions instead of the full max_seq_len: the
    [B, S, g] sliding-window compare is the one spec-decode term that
    scales with the CONFIGURED S rather than the live lengths, so at
    16-32k contexts an unbounded scan dominates draft cost. A bounded
    window only ever drops matches older than `window` tokens — the
    most-recent-match-within-window semantics are otherwise identical
    (verification is unchanged, so the output is still lossless)."""
    B, S1 = history.shape
    S = S1 - 1
    g, d = ngram, draft_len
    last_idx = jnp.clip(
        lengths[:, None] - (g - 1) + jnp.arange(g)[None, :], 0, S - 1
    )
    lastgram = jnp.take_along_axis(history, last_idx, axis=1)  # [B, g]
    if window and window < S:
        # Candidate match starts: the last W positions whose n-gram can
        # end strictly before the pending token (latest legal start is
        # lengths - g). Per-slot absolute positions, gathered instead of
        # scanned, so the compare is [B, W, g] independent of S.
        W = int(window)
        base = jnp.maximum(lengths[:, None] - g - W + 1, 0)  # [B, 1]
        s_pos = base + jnp.arange(W)[None, :]  # [B, W] absolute starts
        win_idx = jnp.minimum(
            s_pos[:, :, None] + jnp.arange(g)[None, None, :], S - 1
        )  # [B, W, g]
        windows = jnp.take_along_axis(
            history, win_idx.reshape(B, W * g), axis=1
        ).reshape(B, W, g)
    else:
        # Unbounded: sliding windows [B, S, g] (clip keeps the tail
        # in-bounds; those positions are excluded by the validity mask).
        win_idx = jnp.minimum(
            jnp.arange(S)[:, None] + jnp.arange(g)[None, :], S - 1
        )
        windows = history[:, win_idx]  # [B, S, g]
        s_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    eq = jnp.all(windows == lastgram[:, None, :], axis=2)  # [B, S or W]
    # The earlier occurrence must end strictly before the pending
    # position, and there must be at least g tokens of history.
    valid = eq & (s_pos + g - 1 < lengths[:, None]) & (lengths[:, None] + 1 >= g)
    best = jnp.max(jnp.where(valid, s_pos, -1), axis=1)  # [B]
    start = best + g  # continuation start (a known position <= lengths)
    cont_idx = jnp.clip(
        start[:, None] + jnp.arange(d)[None, :], 0, S - 1
    )
    draft = jnp.take_along_axis(history, cont_idx, axis=1).astype(jnp.int32)
    eff = jnp.where(
        best >= 0,
        jnp.clip(lengths - start + 1, 0, d),
        0,
    ).astype(jnp.int32)
    return draft, eff


def spec_verify(
    logits: jnp.ndarray,  # [B, d+1, V] fp32, row j = dist after feeding
    #                       token j (0 = pending input, j>0 = draft[j-1])
    draft: jnp.ndarray,  # [B, d] int32
    eff: jnp.ndarray,  # [B] int32 proposed tokens (<= d)
    rng,
    temps, top_ps, top_ks, greedy_mask, forbid_rows, eos_mask,
    active_rows=None,
):
    """Vectorized accept/resample. Returns (emitted [B, d+1] int32,
    n_emit [B] int32 in 1..d+1, logprobs [B, d+1] under the base
    distribution). Row semantics per slot:
      a = length of the accepted draft prefix (greedy: argmax matches;
          sampled: u_j < p_j(draft_j)), capped at eff
      emitted = draft[:a] + one final token from position a's
          distribution (argmax for greedy; for sampled: the rejected
          token removed + renormalized when a < eff, plain sample when
          a == eff)
    Slots with eff = 0 reduce exactly to one plain warp_sample step."""
    B, d1, V = logits.shape
    d = d1 - 1
    flat = logits.reshape(B * d1, V)

    def rep(x):
        return jnp.repeat(x, d1, axis=0)

    warped_f, base_f = warp_logits(
        flat, rep(temps), rep(top_ps), rep(top_ks), rep(forbid_rows),
        eos_mask,
        active_rows=rep(active_rows) if active_rows is not None else None,
    )
    warped = warped_f.reshape(B, d1, V)
    base_logp = base_f.reshape(B, d1, V)
    probs = jax.nn.softmax(warped, axis=-1)

    rng_u, rng_cat = jax.random.split(rng)
    u = jax.random.uniform(rng_u, (B, d))
    p_draft = jnp.take_along_axis(
        probs[:, :d], draft[:, :, None], axis=2
    )[:, :, 0]  # [B, d]: p_j(draft_j)
    # Greedy acceptance is judged on the BASE distribution — the same
    # argmax the plain decode path emits (paged.warp_sample) — so greedy
    # speculative decoding is bit-identical to plain greedy by
    # construction, not merely when warping preserves the argmax.
    argmax_d = jnp.argmax(base_logp[:, :d], axis=2)  # [B, d]
    ok_greedy = argmax_d == draft
    ok_sample = u < p_draft
    ok = jnp.where(greedy_mask[:, None], ok_greedy, ok_sample)
    ok = ok & (jnp.arange(d)[None, :] < eff[:, None])
    # a = length of the accepted prefix
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    a = jnp.sum(acc, axis=1)  # [B] in 0..eff

    # Final token from position a's distribution.
    w_a = jnp.take_along_axis(
        warped, a[:, None, None], axis=1
    )[:, 0]  # [B, V]
    # On rejection (a < eff) remove the rejected draft token and let
    # categorical renormalize; argmax rows are unaffected by removal
    # semantics (the rejected token was not the argmax).
    rej_tok = jnp.take_along_axis(
        draft, jnp.minimum(a, d - 1)[:, None], axis=1
    )[:, 0] if d > 0 else jnp.zeros((B,), jnp.int32)
    remove = (a < eff)
    remove_mask = remove[:, None] & (
        jnp.arange(V)[None, :] == rej_tok[:, None]
    )
    w_final = jnp.where(remove_mask, NEG_INF, w_a)
    sampled = jax.random.categorical(rng_cat, w_final, axis=-1)
    # Greedy final token from the BASE distribution (matching
    # warp_sample's greedy path); the rejected-token mask is a no-op for
    # greedy rows (a rejected draft is never the base argmax) but keeps
    # the row semantics uniform.
    b_a = jnp.take_along_axis(
        base_logp, a[:, None, None], axis=1
    )[:, 0]  # [B, V]
    greedy_tok = jnp.argmax(jnp.where(remove_mask, NEG_INF, b_a), axis=-1)
    final = jnp.where(greedy_mask, greedy_tok, sampled).astype(jnp.int32)

    # emitted[j] = draft[j] for j < a, final at j == a, zeros after.
    emitted = jnp.where(
        jnp.arange(d1)[None, :] < a[:, None],
        jnp.pad(draft, ((0, 0), (0, 1))),
        0,
    )
    emitted = emitted.at[jnp.arange(B), a].set(final).astype(jnp.int32)
    n_emit = a + 1
    logprobs = jnp.take_along_axis(
        base_logp, emitted[:, :, None], axis=2
    )[:, :, 0]
    logprobs = jnp.where(jnp.arange(d1)[None, :] < n_emit[:, None],
                         logprobs, 0.0)
    return emitted, n_emit, logprobs


@functools.partial(jax.jit, donate_argnames=("history",))
def set_history(history, slots, valid, rows):
    """Write admitted requests' token history (prompt + first sampled
    token) into their slots' rows. rows: [m, S+1] int32; invalid
    (padding) entries route to a scratch row, same trick as
    apply_admits."""
    B = history.shape[0]
    idx = jnp.where(valid, slots, B).astype(jnp.int32)
    ext = jnp.concatenate([history, history[:1]], axis=0)
    ext = ext.at[idx].set(rows)
    return ext[:B]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "draft_len", "ngram", "ngram_window",
                     "attn_impl", "mesh"),
    donate_argnames=(
        "k_pages", "v_pages", "lengths", "next_input", "active",
        "remaining", "min_remaining", "rng", "history",
    ),
)
def paged_spec_decode_block(
    params,
    cfg: TransformerConfig,
    k_pages,
    v_pages,
    page_indices,  # [B, P]
    lengths,
    next_input,
    active,
    remaining,
    min_remaining,
    temps,
    top_ps,
    top_ks,
    greedy_mask,
    eos_mask,  # [V] bool
    rng,
    history,  # [B, S+1] int32 (see set_history)
    n_steps: int,
    draft_len: int,
    ngram: int = 2,
    ngram_window: int = 0,
    attn_impl: str = "auto",
    mesh=None,
):
    """paged_decode_block with n-gram speculative decoding: each step
    feeds 1 + draft_len rows per slot (pending token + drafts, staggered
    lengths sharing the slot's page-table row — the same trick as
    chunked prefill) and emits the verified prefix + one token. Output
    layout matches paged_decode_block with n_out = n_steps*(draft_len+1)
    token/logprob columns. The host must reserve pages for
    lengths + n_steps*(draft_len+1) tokens per active slot: rejected
    rows still write (stale) KV, overwritten by later steps and never
    attended (position >= the slot's length)."""
    B = lengths.shape[0]
    d1 = draft_len + 1
    n_out = n_steps * d1
    S1 = history.shape[1]

    def body(i, carry):
        del i
        (kp, vp, lengths, next_input, active, remaining, min_remaining,
         rng, history, total, steps_act, out_t, out_lp, out_m,
         hit_eos) = carry
        steps_act = steps_act + active.astype(jnp.int32)
        # Drafting is disabled while the EOS-forbid floor is live (the
        # per-position forbid interaction isn't worth the complexity)
        # and for inactive slots.
        draft, eff = propose_ngram_drafts(history, lengths, ngram,
                                          draft_len, window=ngram_window)
        eff = jnp.where(active & (min_remaining <= 0), eff, 0)
        # Also never propose past the remaining budget: tokens beyond it
        # would be dropped anyway; skipping them keeps n_emit <= budget.
        eff = jnp.minimum(eff, jnp.maximum(remaining - 1, 0))

        # [B, d1] rows: j=0 feeds the pending token, j>0 the drafts.
        toks = jnp.concatenate([next_input[:, None], draft], axis=1)
        j_idx = jnp.arange(d1)[None, :]
        row_lengths = (lengths[:, None] + j_idx).reshape(-1)
        row_active = (active[:, None] & (j_idx <= eff[:, None])).reshape(-1)
        row_pages = jnp.repeat(page_indices, d1, axis=0)
        logits, kp, vp = paged_decode_step(
            params, cfg, toks.reshape(-1), kp, vp, row_pages, row_lengths,
            row_active, mesh=mesh, attn_impl=attn_impl,
        )
        rng, sub = jax.random.split(rng)
        emitted, n_emit, logprobs = spec_verify(
            logits.reshape(B, d1, -1), draft, eff, sub,
            temps, top_ps, top_ks, greedy_mask, min_remaining > 0,
            eos_mask, active_rows=active,
        )

        # Truncate the emitted group at the first EOS, then at budget.
        pos_mask = j_idx < n_emit[:, None]
        is_eos = eos_mask[emitted] & pos_mask
        any_eos = jnp.any(is_eos, axis=1)
        first_eos = jnp.argmax(is_eos, axis=1)
        n_emit = jnp.where(any_eos, first_eos + 1, n_emit)
        n_emit = jnp.minimum(n_emit, jnp.maximum(remaining, 0))
        n_emit = jnp.where(active, n_emit, 0)
        emit_mask = j_idx < n_emit[:, None]
        emitted = jnp.where(emit_mask, emitted, 0)
        logprobs = jnp.where(emit_mask, logprobs, 0.0)

        # State advance (mirrors the plain block, in units of n_emit).
        got_eos = any_eos & (first_eos < n_emit) & active
        remaining = remaining - n_emit
        min_remaining = jnp.maximum(min_remaining - n_emit, 0)
        exhausted = (remaining <= 0) & active & (n_emit > 0)
        hit_eos = hit_eos | got_eos
        new_active = active & ~got_eos & ~exhausted

        # next_input = last emitted token (only meaningful where
        # n_emit > 0; inactive slots keep their stale value).
        last_tok = jnp.take_along_axis(
            emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
        )[:, 0]
        next_input = jnp.where(n_emit > 0, last_tok, next_input)

        # History append: emitted[i] lands at position lengths + 1 + i;
        # masked writes route to the scratch column S.
        brow = jnp.broadcast_to(jnp.arange(B)[:, None], (B, d1))
        wpos = jnp.where(
            emit_mask, jnp.minimum(lengths[:, None] + 1 + j_idx, S1 - 1),
            S1 - 1,
        )
        history = history.at[brow, wpos].set(emitted)
        lengths = lengths + n_emit

        # Emission buffers, compacted per slot: the host consumes the
        # FIRST n_emitted columns, so each step's group scatters at the
        # slot's running offset (masked entries route to the scratch
        # column n_out).
        wcol = jnp.where(emit_mask, total[:, None] + j_idx, n_out)
        out_t = out_t.at[brow, wcol].set(emitted)
        out_lp = out_lp.at[brow, wcol].set(logprobs)
        out_m = out_m.at[brow, wcol].set(emit_mask)
        total = total + n_emit
        return (kp, vp, lengths, next_input, new_active, remaining,
                min_remaining, rng, history, total, steps_act, out_t,
                out_lp, out_m, hit_eos)

    # One scratch column (n_out) absorbs masked scatter writes.
    out_t = jnp.zeros((B, n_out + 1), jnp.int32)
    out_lp = jnp.zeros((B, n_out + 1), jnp.float32)
    out_m = jnp.zeros((B, n_out + 1), bool)
    hit_eos = jnp.zeros((B,), bool)
    total0 = jnp.zeros((B,), jnp.int32)
    steps0 = jnp.zeros((B,), jnp.int32)
    carry = (k_pages, v_pages, lengths, next_input, active, remaining,
             min_remaining, rng, history, total0, steps0, out_t, out_lp,
             out_m, hit_eos)
    carry = jax.lax.fori_loop(0, n_steps, body, carry)
    (k_pages, v_pages, lengths, next_input, active, remaining, min_remaining,
     rng, history, _total, steps_act, out_t, out_lp, out_m, hit_eos) = carry
    out_t, out_lp, out_m = out_t[:, :n_out], out_lp[:, :n_out], out_m[:, :n_out]
    # One extra column vs the plain block: per-slot steps the slot was
    # ACTIVE for — the exact denominator for the speculation yield
    # (charging full blocks to early-finishing slots would deflate it).
    packed = jnp.concatenate(
        [
            out_t.astype(jnp.float32),
            out_lp,
            jnp.sum(out_m, axis=1, keepdims=True).astype(jnp.float32),
            hit_eos[:, None].astype(jnp.float32),
            active[:, None].astype(jnp.float32),
            lengths[:, None].astype(jnp.float32),
            steps_act[:, None].astype(jnp.float32),
        ],
        axis=1,
    )
    return (packed, k_pages, v_pages, lengths, next_input, active,
            remaining, min_remaining, rng, history)
