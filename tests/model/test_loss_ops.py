import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.packing import pack_sequences
from areal_tpu.ops.loss import (
    gather_logprobs,
    masked_normalization,
    next_token_logprobs,
    sft_loss,
)


def test_gather_logprobs_matches_log_softmax():
    rng = np.random.RandomState(0)
    logits = rng.randn(4, 10).astype(np.float32)
    labels = rng.randint(0, 10, size=4)
    out = np.asarray(gather_logprobs(jnp.asarray(logits), jnp.asarray(labels)))
    ref = np.log(np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))[
        np.arange(4), labels
    ]
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_next_token_logprobs_segment_boundaries():
    rng = np.random.RandomState(1)
    seqs = [rng.randint(0, 50, size=l) for l in [4, 3]]
    b = pack_sequences(seqs, row_len=16)
    logits = rng.randn(b.n_rows, b.row_len, 50).astype(np.float32)
    lp = np.asarray(
        next_token_logprobs(
            jnp.asarray(logits), jnp.asarray(b.input_ids), jnp.asarray(b.segment_ids)
        )
    )
    # Within a sequence, position t scores token t+1.
    for span in b.spans:
        seq = seqs[span.seq_index]
        for t in range(span.length - 1):
            col = span.start + t
            row_logits = logits[span.row, col]
            expect = row_logits[seq[t + 1]] - np.log(np.exp(row_logits).sum())
            np.testing.assert_allclose(lp[span.row, col], expect, atol=1e-4)
        # Final position of each sequence contributes 0.
        assert lp[span.row, span.start + span.length - 1] == 0.0
    # Padding positions are 0.
    assert (lp[b.segment_ids == 0] == 0).all()


def test_sft_loss_counts_masked_tokens():
    rng = np.random.RandomState(2)
    seqs = [rng.randint(0, 50, size=6)]
    b = pack_sequences(seqs, row_len=8)
    logits = rng.randn(1, 8, 50).astype(np.float32)
    mask = np.zeros((1, 8), np.float32)
    mask[0, 2:5] = 1.0  # predictions at t=2,3,4 count
    total, n = sft_loss(
        jnp.asarray(logits), jnp.asarray(b.input_ids), jnp.asarray(b.segment_ids),
        jnp.asarray(mask),
    )
    assert float(n) == 3.0
    assert float(total) > 0


def test_masked_normalization():
    x = jnp.asarray(np.array([[1.0, 2.0, 3.0, 100.0]]))
    mask = jnp.asarray(np.array([[1.0, 1.0, 1.0, 0.0]]))
    out = np.asarray(masked_normalization(x, mask))
    vals = out[0, :3]
    assert abs(vals.mean()) < 1e-5
    assert out[0, 3] == 0.0
    np.testing.assert_allclose(np.std(vals, ddof=1), 1.0, atol=0.05)


def test_fused_next_token_logprobs_matches_unfused():
    from areal_tpu.ops.loss import fused_next_token_logprobs

    rng = np.random.RandomState(3)
    R, T, D, V = 2, 32, 16, 64
    hidden = rng.randn(R, T, D).astype(np.float32)
    head_w = (rng.randn(D, V) * 0.1).astype(np.float32)
    input_ids = rng.randint(0, V, size=(R, T)).astype(np.int32)
    seg = np.zeros((R, T), np.int32)
    seg[0, :20] = 1
    seg[0, 20:29] = 2
    seg[1, :15] = 1
    logits = hidden @ head_w
    ref = np.asarray(
        next_token_logprobs(jnp.asarray(logits), jnp.asarray(input_ids), jnp.asarray(seg))
    )
    for chunk in (4096, 16, 7):
        out = np.asarray(
            fused_next_token_logprobs(
                jnp.asarray(hidden), jnp.asarray(head_w),
                jnp.asarray(input_ids), jnp.asarray(seg), chunk_size=chunk,
            )
        )
        np.testing.assert_allclose(out, ref, atol=1e-4)


def test_fused_next_token_logprobs_grads_match():
    import jax

    from areal_tpu.ops.loss import fused_next_token_logprobs

    rng = np.random.RandomState(4)
    R, T, D, V = 2, 16, 8, 32
    hidden = jnp.asarray(rng.randn(R, T, D), jnp.float32)
    head_w = jnp.asarray(rng.randn(D, V) * 0.1, jnp.float32)
    input_ids = jnp.asarray(rng.randint(0, V, size=(R, T)), jnp.int32)
    seg = jnp.ones((R, T), jnp.int32)

    def loss_fused(h, w):
        return -jnp.sum(fused_next_token_logprobs(h, w, input_ids, seg, chunk_size=8))

    def loss_ref(h, w):
        logits = (h @ w).astype(jnp.float32)
        return -jnp.sum(next_token_logprobs(logits, input_ids, seg))

    gh1, gw1 = jax.grad(loss_fused, argnums=(0, 1))(hidden, head_w)
    gh2, gw2 = jax.grad(loss_ref, argnums=(0, 1))(hidden, head_w)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2), atol=1e-4)


# ----------------------------------------------------------------------
# The head over the positions the loss reads (`scored=`)
# ----------------------------------------------------------------------


def _parent_fused_next_token_logprobs(hidden, head_w, input_ids, segment_ids,
                                      chunk_size=None):
    """`fused_next_token_logprobs` as it stood before it took `scored`:
    the program a call without a mask must still lower to."""
    import jax

    from areal_tpu.ops.loss import _ce_chunk_setting, _next_token_targets, _pick_chunk

    R, T, D = hidden.shape
    V = head_w.shape[-1]
    if chunk_size is None:
        chunk_size = _ce_chunk_setting()
        if chunk_size is None:
            chunk_size = max(256, (1 << 27) // V)
    next_ids, valid = _next_token_targets(input_ids, segment_ids)
    n = R * T
    c = _pick_chunk(n, chunk_size)
    flat_h = hidden.reshape(n // c, c, D)
    flat_y = next_ids.reshape(n // c, c)

    def chunk(carry, hy):
        h_c, y_c = hy
        logits = (h_c @ head_w.astype(h_c.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        return carry, picked - lse

    _, logp = jax.lax.scan(jax.checkpoint(chunk), None, (flat_h, flat_y))
    return jnp.where(valid, logp.reshape(R, T), 0.0)


def _head_case(rows, seed=5, T=24, D=8, V=32):
    rng = np.random.RandomState(seed)
    hidden = rng.randn(rows, T, D).astype(np.float32)
    emb = (rng.randn(V, D) * 0.3).astype(np.float32)
    ids = rng.randint(0, V, size=(rows, T)).astype(np.int32)
    seg = np.zeros((rows, T), np.int32)
    for r in range(rows):  # two sequences a row and a tail of padding
        a = 5 + 3 * r
        seg[r, :a] = 1
        seg[r, a:T - 2 - r] = 2
    valid = (seg > 0) & (np.concatenate([seg[:, 1:], np.zeros_like(seg[:, :1])], 1) == seg)
    return hidden, emb, ids, seg, valid


def _scored_mask(valid, kept, seed=6):
    """`kept` of the valid positions (or "all"), spread over the rows."""
    where = np.flatnonzero(valid.reshape(-1))
    if kept != "all":
        where = np.random.RandomState(seed).permutation(where)[:kept]
    scored = np.zeros(valid.size, np.float32)
    scored[where] = 1.0
    return scored.reshape(valid.shape)


CHUNK = 8


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("kept", [0, 1, 2 * CHUNK, "all"])
@pytest.mark.parametrize("rows", [1, 3])
def test_scored_head_equals_the_unmasked_head_where_the_loss_reads(rows, kept, tied):
    """Logprobs at the kept positions are the unmasked call's and zero
    elsewhere; the gradients of a loss weighted by the mask, with respect
    to `hidden` and to the head's weight, are the unmasked path's."""
    import jax

    from areal_tpu.ops.loss import fused_next_token_logprobs

    hidden, emb, ids, seg, valid = _head_case(rows)
    scored = _scored_mask(valid, kept)
    weights = scored * np.random.RandomState(7).rand(*scored.shape).astype(np.float32)

    def loss(h, w, masked):
        head_w = w.T if tied else w
        lp = fused_next_token_logprobs(
            h, head_w, ids, seg, chunk_size=CHUNK,
            scored=jnp.asarray(scored) if masked else None)
        return jnp.sum(lp * weights), lp

    w = emb if tied else np.ascontiguousarray(emb.T)
    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True),
                   static_argnums=2)
    (l1, lp1), (gh1, gw1) = grad(hidden, w, True)
    (l0, lp0), (gh0, gw0) = grad(hidden, w, False)
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp0) * scored, atol=1e-5)
    assert (np.asarray(lp1)[scored == 0] == 0).all()
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw0), atol=1e-5)


@pytest.mark.parametrize("kept", [0, 5, "all"])
def test_scored_head_never_touches_a_dropped_position(kept):
    """Hidden rows at positions the mask drops (and at invalid ones) hold
    NaN: loss and gradients stay finite and equal the clean input's, so
    no product ran over them, forward or backward."""
    import jax

    from areal_tpu.ops.loss import fused_next_token_logprobs

    hidden, emb, ids, seg, valid = _head_case(3)
    scored = _scored_mask(valid, kept)
    keep = valid & (scored > 0)
    poisoned = np.where(keep[..., None], hidden, np.nan).astype(np.float32)

    def loss(h, w):
        return jnp.sum(fused_next_token_logprobs(
            h, w.T, ids, seg, chunk_size=CHUNK, scored=jnp.asarray(scored)) * scored)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    l1, (gh1, gw1) = grad(poisoned, emb)
    l0, (gh0, gw0) = grad(hidden, emb)
    assert np.isfinite(float(l1)) and np.isfinite(np.asarray(gh1)).all()
    assert float(l1) == float(l0)
    np.testing.assert_array_equal(np.asarray(gw1), np.asarray(gw0))
    np.testing.assert_array_equal(np.asarray(gh1), np.asarray(gh0))
    assert (np.asarray(gh1)[~keep] == 0).all()


def test_head_without_a_mask_lowers_to_the_parents_program():
    import jax

    from areal_tpu.ops.loss import fused_next_token_logprobs

    hidden, emb, ids, seg, _ = _head_case(3)

    def text(fn, grad):
        def f(h, w):
            return jnp.sum(fn(h, w.T, ids, seg, chunk_size=CHUNK))

        return str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)) if grad else f)(hidden, emb))

    for grad in (False, True):
        assert text(fused_next_token_logprobs, grad) == \
            text(_parent_fused_next_token_logprobs, grad)
    # and with a mask it is another program: a loop of run-time length
    masked = str(jax.make_jaxpr(lambda h, w: fused_next_token_logprobs(
        h, w.T, ids, seg, chunk_size=CHUNK, scored=jnp.ones(seg.shape)))(hidden, emb))
    assert "while" in masked and "while" not in text(fused_next_token_logprobs, False)


@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("kept", [0, 1, 2 * CHUNK, 2 * CHUNK + 1, "all"])
def test_host_count_of_the_heads_chunks_is_the_devices(kept, groups, monkeypatch):
    """`head_cells_run` (numpy, what the engine's counters record) against
    the layout the device makes from the same mask, and against a count
    by hand: every group's kept positions fill the front of the group's
    slots, and a chunk runs if it holds one."""
    import jax

    from areal_tpu.ops import loss as L

    monkeypatch.setattr(L, "_CE_CHUNK_SNAP", (CHUNK,))
    _, _, _, seg, valid = _head_case(3)
    scored = _scored_mask(valid, kept)
    n_scored, cells = L.head_cells_run(seg, scored, vocab=32, row_groups=groups)
    keep = valid & (scored > 0)
    assert n_scored == keep.sum() == (valid.sum() if kept == "all" else kept)
    *_, chunk_ids, n_run = jax.jit(lambda k: L._scored_layout(k, CHUNK))(
        jnp.asarray(keep.reshape(groups, -1)))
    assert cells == int(n_run) * CHUNK
    m = seg.size // groups
    assert m % CHUNK == 0
    by_hand = sum(-(-int(k) // CHUNK) for k in keep.reshape(groups, m).sum(axis=1))
    assert cells == by_hand * CHUNK
    assert list(np.asarray(chunk_ids)[:int(n_run)]) == sorted(
        g * (m // CHUNK) + j for g, k in enumerate(keep.reshape(groups, m).sum(axis=1))
        for j in range(-(-int(k) // CHUNK)))
    # no mask: every valid position is read and every chunk runs
    assert L.head_cells_run(seg, None, vocab=32) == (int(valid.sum()), seg.size)


def test_response_scoring_mask_is_one_rule_for_numpy_and_jax_rows():
    from areal_tpu.ops.loss import response_positions, response_scoring_mask

    seg = np.array([[1, 1, 1, 1, 2, 2, 2, 0]], np.int32)
    pm = np.array([[1, 1, 0, 0, 1, 0, 0, 0]], np.int32)
    want = np.array([[0, 1, 1, 0, 1, 1, 0, 0]], np.float32)
    on_host = response_scoring_mask(seg, pm)
    assert isinstance(on_host, np.ndarray)
    np.testing.assert_array_equal(on_host, want)
    np.testing.assert_array_equal(
        np.asarray(response_scoring_mask(jnp.asarray(seg), jnp.asarray(pm))), want)
    rows = {"segment_ids": np.stack([seg, seg]), "prompt_mask": np.stack([pm, pm])}
    np.testing.assert_array_equal(response_positions(rows), np.stack([want, want]))
