"""Device-resident decode state (ISSUE 15 tentpole, decode front).

AREAL_DECODE_RESIDENT keeps per-slot decode control device-resident
between blocks: page-table edits land as ONE donated per-slot row
scatter (paged.update_page_rows) and chunked-prefill control crosses as
ONE fused int32 array (paged.paged_chunk_prefill_packed), so only
admission/eviction deltas pay H2D. These tests pin:

- greedy-token parity resident vs legacy (the pre-change engine path,
  kept verbatim behind the knob) across chunked prefill, prefix-cache
  resubmission, and multi-round admission;
- the measured reduction itself: per-decode-block H2D transfer count
  strictly below legacy on a chunked workload;
- unit semantics of the fused row scatter and the packed chunk-prefill
  entry point against their legacy equivalents.

Time budget: tiny 2-layer CPU engines; whole module well under 30 s
warm (the heaviest test runs two engines over 6 short requests).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.engine.serving import GenRequest, ServingEngine
from areal_tpu.models.transformer import init_params

from .serving_utils import TINY_SERVING_CFG, run_requests

CFG = TINY_SERVING_CFG


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, resident: bool, **kw):
    defaults = dict(
        max_batch_size=2,
        max_seq_len=128,
        decode_block_steps=4,
        prompt_bucket=8,
        page_size=8,
        prefill_chunk=16,
        prefix_cache_tokens=256,
        seed=11,
        decode_resident=resident,
    )
    defaults.update(kw)
    eng = ServingEngine(CFG, params, **defaults)
    eng.start()
    return eng


def _prompts(n=6, seed=0):
    rng = np.random.RandomState(seed)
    # Mix: short (bucketed path), long (chunked path), odd lengths
    # (misaligned pages) — and more requests than slots, forcing
    # multi-round admission + finish/admit page-table churn.
    lens = [5, 17, 40, 9, 33, 26][:n]
    return [rng.randint(6, CFG.vocab_size, size=l).tolist() for l in lens]


def _run(eng, prompts, tag="q", max_new=20):
    reqs = [
        GenRequest(qid=f"{tag}{i}", input_ids=p, max_new_tokens=max_new,
                   greedy=True)
        for i, p in enumerate(prompts)
    ]
    res = run_requests(eng, reqs, timeout=240)
    return {q: r.output_ids for q, r in res.items()}


def test_greedy_parity_and_h2d_reduction(tiny_params):
    """The engine must emit IDENTICAL greedy tokens with the knob on and
    off, while the resident arm stages measurably fewer transfers per
    decode block (strict <: the whole point of the pass) and no more
    bytes. Covers chunked prefill (17/33/40-token prompts over chunk
    16), multi-round admission (6 requests over 2 slots), and the
    finish/admit page-table churn between blocks."""
    prompts = _prompts()
    stats = {}
    outs = {}
    for resident in (True, False):
        eng = _engine(tiny_params, resident)
        try:
            outs[resident] = _run(eng, prompts)
            blocks = max(1, eng.decode_blocks)
            stats[resident] = (
                eng.h2d_transfers / blocks,
                eng.h2d_bytes / blocks,
            )
        finally:
            eng.stop()
    assert outs[True] == outs[False], "resident mode changed greedy tokens"
    assert all(len(v) == 20 for v in outs[True].values())
    assert stats[True][0] < stats[False][0], (
        f"resident h2d/block {stats[True][0]:.2f} not below legacy "
        f"{stats[False][0]:.2f}"
    )
    assert stats[True][1] <= stats[False][1] * 1.05


def test_prefix_cache_resubmission_parity(tiny_params):
    """A same-qid resubmission extending its prompt (the partial-rollout
    protocol) admits through the cache-hit delta prefill — the path the
    packed control array changed most. Tokens must match legacy."""
    rng = np.random.RandomState(7)
    base = rng.randint(6, CFG.vocab_size, size=24).tolist()
    outs = {}
    for resident in (True, False):
        eng = _engine(tiny_params, resident)
        try:
            first = _run(eng, [base], tag="s", max_new=12)["s0"]
            # Resubmit prompt + emitted tokens under the SAME qid: the
            # parked prefix serves all but the 1-token delta.
            second = _run(eng, [base + first], tag="s", max_new=8)["s0"]
            outs[resident] = (first, second)
            assert eng.prefix_cache_hits >= 1
        finally:
            eng.stop()
    assert outs[True] == outs[False]


def test_update_page_rows_matches_full_restage():
    """Unit pin: scattering dirty rows into a device-resident table
    yields exactly the table a full restage would build; padding rows
    (slot < 0) must not write anywhere."""
    from areal_tpu.engine.paged import update_page_rows

    rng = np.random.RandomState(0)
    B, P = 8, 6
    host = rng.randint(0, 50, size=(B, P)).astype(np.int32)
    dev = jnp.asarray(host)
    # Mutate three rows + build the packed [m, P+1] control (pow2 pad).
    host[1] = rng.randint(0, 50, size=P)
    host[4] = rng.randint(0, 50, size=P)
    host[6] = rng.randint(0, 50, size=P)
    packed = np.full((4, P + 1), -1, np.int32)
    for i, slot in enumerate((1, 4, 6)):
        packed[i, 0] = slot
        packed[i, 1:] = host[slot]
    packed[3, 1:] = 99  # padding row: must be dropped, not scattered
    got = update_page_rows(dev, jnp.asarray(packed), n_slots=B)
    np.testing.assert_array_equal(np.asarray(got), host)


def test_packed_chunk_prefill_matches_legacy(tiny_params):
    """The fused-control chunk prefill is the SAME traced math as the
    3-transfer legacy entry point — logits and pool contents must agree
    bitwise (both slice the identical scalars; only the staging
    changed)."""
    from areal_tpu.engine.paged import (
        paged_chunk_prefill, paged_chunk_prefill_packed,
    )

    C, P, pg, L = 8, 4, 8, CFG.n_layers
    Hkv, hd = CFG.n_kv_heads, CFG.head_dim
    rng = np.random.RandomState(1)
    toks = rng.randint(0, CFG.vocab_size, size=C).astype(np.int32)
    valid = 5
    start = 0
    page_row = jnp.asarray([1, 2, 3, 0], jnp.int32)

    def pools():
        shape = (L, Hkv, P + 1, pg, hd)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    k1, v1 = pools()
    last1, k1, v1 = paged_chunk_prefill(
        tiny_params, CFG, jnp.asarray(toks), k1, v1, page_row,
        jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32),
    )
    ctl = np.concatenate([toks, [start, valid]]).astype(np.int32)
    k2, v2 = pools()
    last2, k2, v2 = paged_chunk_prefill_packed(
        tiny_params, CFG, jnp.asarray(ctl), k2, v2, page_row,
    )
    np.testing.assert_array_equal(np.asarray(last1), np.asarray(last2))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_metrics_surface_h2d_counters(tiny_params):
    """metrics() must expose the staging telemetry the A/B reads."""
    eng = _engine(tiny_params, True)
    try:
        _run(eng, _prompts(2), max_new=8)
        m = eng.metrics()
        assert m["decode_resident"] == 1.0
        assert m["h2d_transfers_total"] > 0
        assert m["h2d_bytes_total"] > 0
        assert m["decode_blocks_total"] > 0
        assert m["h2d_per_decode_block"] == pytest.approx(
            m["h2d_transfers_total"] / m["decode_blocks_total"]
        )
    finally:
        eng.stop()
