"""The one traffic generator: a traffic file's parameters + a seed -> inputs.

A traffic mix is data (`benchmark/traffic/<name>.json`); this module is
the only code that turns one into work. One kind exists so far, chosen
by the file's `kind`: `ppo_batches` (packed PPO training batches).

Every seed gets the same set of sizes, in another order. The lengths
(prompt, response) and which sequences make a batch come from the file's
own `lengths_seed`: the trainer compiles one program per micro-batch
shape, so sizes that moved with `--seed` would make every run compile
and no two runs do the same work. `--seed` owns the order in which the
batches arrive and what the lengths carry: token ids, rewards, logprob
noise (and, in the runner, the weights).

Pure numpy; imports nothing of the program and never jax.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

SEED_MOD = 2**31 - 1  # --seed may exceed 32 signed bits; fold it


def fold_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def _uniform_int(rng, lo: int, hi: int) -> int:
    return int(rng.integers(int(lo), int(hi) + 1))


def _lognormal_int(rng, median: float, sigma: float, lo: int, hi: int):
    """(length, clipped_high): lognormal with the given median, rounded,
    clipped to [lo, hi]."""
    raw = float(rng.lognormal(math.log(float(median)), float(sigma)))
    n = int(round(raw))
    return max(int(lo), min(int(hi), n)), n >= int(hi)


def effective(params: Dict[str, Any], rehearsal: bool) -> Dict[str, Any]:
    """The traffic parameters as run: the file's, or with its `rehearsal`
    overrides on top for the toy-width CPU walk-through."""
    out = {k: v for k, v in params.items() if k != "rehearsal"}
    if rehearsal:
        out.update(params.get("rehearsal", {}))
    return out


# ----------------------------------------------------------------------
# ppo_batches
# ----------------------------------------------------------------------


def ppo_batch_lengths(p: Dict[str, Any]) -> List[List[Dict[str, int]]]:
    """The pool's sizes: per batch a list of sequences
    {group, prompt_len, resp_len, clipped}. Sequences are drawn group by
    group (one prompt length, `group_size` response lengths) until the
    batch holds `tokens_per_step` tokens; the last group may be cut."""
    rng = np.random.default_rng(int(p["lengths_seed"]))
    pool = []
    group = 0
    for _ in range(int(p["pool_batches"])):
        seqs, total = [], 0
        while total < int(p["tokens_per_step"]):
            plen = _uniform_int(rng, *p["prompt_len_uniform"])
            for _i in range(int(p["group_size"])):
                rlen, clipped = _lognormal_int(
                    rng, p["response_len_lognormal"]["median"],
                    p["response_len_lognormal"]["sigma"],
                    *p["response_len_clip"],
                )
                seqs.append(dict(group=group, prompt_len=plen,
                                 resp_len=rlen, clipped=int(clipped)))
                total += plen + rlen
                if total >= int(p["tokens_per_step"]):
                    break
            group += 1
        pool.append(seqs)
    return pool


def ppo_batches(p: Dict[str, Any], seed: int, vocab_size: int) -> List[Dict[str, Any]]:
    """The pool of distinct training batches in the order this seed
    steps through them, as plain arrays: batch (its index in the drawn
    pool), ids, seqlens, packed_input_ids, prompt_mask, rewards,
    seq_no_eos_mask, noise_behav, noise_ref (the N(0, sigma) to add to
    the engine's own logprobs). Sequences keep their drawn order inside a
    batch (a group's samples arrive together), so a batch packs into the
    same micro-batch shapes under every seed."""
    rng = np.random.default_rng(fold_seed(seed))
    pool = ppo_batch_lengths(p)
    arrival = [int(i) for i in rng.permutation(len(pool))]
    sigma = float(p["logprob_noise_sigma"])
    reward = float(p["reward_abs"])
    out = []
    for b in arrival:
        seqs = pool[b]
        prompts: Dict[int, np.ndarray] = {}
        toks, pmask, lens, ids = [], [], [], []
        for j, s in enumerate(seqs):
            if s["group"] not in prompts:  # a group's prompt is one draw
                prompts[s["group"]] = rng.integers(
                    0, vocab_size, size=s["prompt_len"], dtype=np.int64)
            resp = rng.integers(0, vocab_size, size=s["resp_len"], dtype=np.int64)
            toks.append(np.concatenate([prompts[s["group"]], resp]))
            pmask.append(np.concatenate([
                np.ones(s["prompt_len"], np.int32),
                np.zeros(s["resp_len"], np.int32)]))
            lens.append(s["prompt_len"] + s["resp_len"])
            ids.append(f"b{int(b)}g{s['group']}/{j}")
        total = int(sum(lens))
        out.append(dict(
            batch=b,
            ids=ids,
            seqlens=lens,
            prompt_lens=[s["prompt_len"] for s in seqs],
            packed_input_ids=np.concatenate(toks).astype(np.int32),
            prompt_mask=np.concatenate(pmask),
            rewards=rng.choice([reward, -reward], size=len(seqs)).astype(np.float32),
            seq_no_eos_mask=np.asarray(
                [s["clipped"] for s in seqs], np.float32),
            noise_behav=(sigma * rng.standard_normal(total)).astype(np.float32),
            noise_ref=(sigma * rng.standard_normal(total)).astype(np.float32),
            n_tokens=total,
        ))
    return out


def length_histogram(values, edges=(32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)):
    """Counts of `values` at or under each edge (last bin: above all)."""
    counts = [0] * (len(edges) + 1)
    for v in values:
        for i, e in enumerate(edges):
            if v <= e:
                counts[i] += 1
                break
        else:
            counts[-1] += 1
    labels = [f"<={e}" for e in edges] + [f">{edges[-1]}"]
    return dict(zip(labels, counts))
