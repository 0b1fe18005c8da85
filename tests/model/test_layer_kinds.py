"""Layers of different kinds in one stack (models/config.py LayerKind),
window and full attention, the sigmoid-routed expert layer that holds a
share of the experts, and the `afmoe` family: the program against the
plain reference `benchmark/reference/afmoe.py` on the CPU, float32,
seeded random weights, toy widths."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import LayerKind, MoEConfig, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params
from areal_tpu.ops import attention as A
from areal_tpu.ops import band_loop
from benchmark.reference import afmoe as ref

WINDOW = 8
HF = dict(
    model_type="afmoe", hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=5, num_dense_layers=1,
    layer_types=["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=64,
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=10000,
    sliding_window=WINDOW, num_experts=4, num_experts_routed=16,
    experts_held_first=4, num_experts_per_tok=4, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.826,
    mup_enabled=True, tie_word_embeddings=False, hidden_act="silu",
)
# rows of packed sequences, each longer than the toy window
ROWS = [[20, 30, 10], [45, 11]]
ROW_LEN = 64


def _cfg(hf=HF, **over):
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32", **over)


def _params(cfg, seed=0, bias_scale=0.0):
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    if bias_scale:  # a selection bias that is not zero, as a trained one
        mlp = params["layers"]["mlp"]
        mlp["expert_bias"] = bias_scale * jax.random.normal(
            jax.random.PRNGKey(seed + 1), mlp["expert_bias"].shape)
    return params


def _packed(seed=0, vocab=64, rows=ROWS, row_len=ROW_LEN):
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(rows), row_len), np.int32)
    seg, pos = np.zeros_like(ids), np.zeros_like(ids)
    seqs = []
    for r, lens in enumerate(rows):
        o = 0
        for j, n in enumerate(lens):
            t = rng.integers(0, vocab, n)
            seqs.append((r, o, t))
            ids[r, o:o + n], seg[r, o:o + n], pos[r, o:o + n] = t, j + 1, np.arange(n)
            o += n
    return jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos), seqs


def _program_logprob_sum(params, cfg, ids, seg, pos, seqs, **kw):
    """Sum over every sequence's next-token logprobs, and each
    sequence's own, from one packed forward pass."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, ids, seg, pos, attn_impl="reference", **kw)
    lp = jax.nn.log_softmax(logits, -1)
    per_seq = [
        jnp.take_along_axis(lp[r, o:o + len(t) - 1], jnp.asarray(t[1:, None]), -1)[:, 0]
        for r, o, t in seqs]
    return sum(x.sum() for x in per_seq), per_seq


def _reference_logprob_sum(params, hf, seqs, kinds=None):
    per_seq = []
    for _, _, t in seqs:
        n = -(-len(t) // ref.ROWS) * ref.ROWS
        ids = jnp.asarray(np.concatenate([t, np.zeros(n - len(t), np.int64)]), jnp.int32)
        per_seq.append(ref._forward(params, ids, hf, kinds)[: len(t) - 1])
    return sum(x.sum() for x in per_seq), per_seq


def _assert_trees_close(got, want, rtol=2e-4):
    flat_g = jax.tree_util.tree_leaves_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        scale = float(jnp.abs(w).max()) + 1e-6
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=rtol * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "nope"])
@pytest.mark.parametrize("mlp", ["dense", "moe"])
@pytest.mark.parametrize("window", [WINDOW, None], ids=["window", "full"])
def test_each_layer_kind_matches_the_reference(window, mlp, rotary, monkeypatch):
    """Two layers of one kind: logprobs and the gradients of their sum,
    on packed rows whose sequences cross the window."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)  # several tiles an expert at toy size
    kind = LayerKind(mlp=mlp, window=window, rotary=rotary)
    hf = dict(HF, num_hidden_layers=2, num_dense_layers=2 if mlp == "dense" else 0,
              layer_types=["sliding_attention"] * 2)
    cfg = _cfg(hf, layer_kinds=(kind, kind))
    params = _params(cfg, bias_scale=0.1 if mlp == "moe" else 0.0)
    ids, seg, pos, seqs = _packed()
    kinds = [(window, rotary)] * 2

    prog = lambda p: _program_logprob_sum(p, cfg, ids, seg, pos, seqs, remat="full")[0]
    want = lambda p: _reference_logprob_sum(p, hf, seqs, kinds)[0]
    _, got_seqs = _program_logprob_sum(params, cfg, ids, seg, pos, seqs)
    _, want_seqs = _reference_logprob_sum(params, hf, seqs, kinds)
    for g, w in zip(got_seqs, want_seqs):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    g_prog, g_ref = jax.grad(prog)(params), jax.grad(want)(params)
    if mlp == "moe":  # a buffer: the program sends it no gradient
        assert not np.asarray(g_prog["layers"]["mlp"]["expert_bias"]).any()
        g_ref["layers"]["mlp"]["expert_bias"] = g_prog["layers"]["mlp"]["expert_bias"]
    _assert_trees_close(g_prog, g_ref)


@pytest.mark.parametrize("remat", ["none", "full", "mlp"])
def test_the_whole_stack_matches_the_reference(remat, monkeypatch):
    """One leading dense layer, then expert layers `s s f s`: a period of
    three in the scan and a remainder of one, under each remat mode."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    assert cfg.n_lead_layers == 1 and [k.window for k in cfg.kinds()] == [8, 8, 8, None, 8]
    params = _params(cfg, bias_scale=0.1)
    ids, seg, pos, seqs = _packed()
    _, got = _program_logprob_sum(params, cfg, ids, seg, pos, seqs, remat=remat)
    _, want = _reference_logprob_sum(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _program_logprob_sum(p, cfg, ids, seg, pos, seqs, remat=remat)[0]
    g_prog = jax.grad(prog)(params)
    g_ref = jax.grad(lambda p: _reference_logprob_sum(p, HF, seqs)[0])(params)
    g_ref["layers"]["mlp"]["expert_bias"] = g_prog["layers"]["mlp"]["expert_bias"]
    _assert_trees_close(g_prog, g_ref)


def small_bands(monkeypatch, band=16):
    """Bands of `band` cells, so that a toy row walks them, and a list
    that grows by one for every stretch a program runs through the
    loop (`ops/band_loop.stretch`, `carried`)."""
    monkeypatch.setattr(band_loop, "_BAND", band)
    jax.clear_caches()  # a trace made at another band length is no one's to find
    ran = []
    for name in ("stretch", "carried"):
        loop = getattr(band_loop, name)
        monkeypatch.setattr(band_loop, name, lambda fn, *a, loop=loop: (
            ran.append(fn.__name__) or loop(fn, *a)))
    return ran


@pytest.mark.parametrize("remat", ["none", "full", "mlp"])
def test_a_half_empty_row_walks_its_live_bands_and_matches_the_reference(remat, monkeypatch):
    """One row alone, 37 tokens in 96 cells: the leading dense layer,
    which runs once outside a scan, and the four scanned expert layers of
    the stack (window and full; one traced body) run their two stretches
    each over three bands of six, and the logprobs and every gradient are
    the plain reference's."""
    from areal_tpu.models.transformer import looping_layers

    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    ran = small_bands(monkeypatch)
    cfg = _cfg()
    params = _params(cfg, bias_scale=0.1)
    ids, seg, pos, seqs = _packed(rows=[[24, 13]], row_len=96)
    assert int(band_loop.live_bands(seg)) == 3 and band_loop.band_cells_run(np.asarray(seg)) == 48
    _, got = _program_logprob_sum(params, cfg, ids, seg, pos, seqs, remat=remat, bands=True)
    assert looping_layers(cfg, 1, 96) == 5 and ran == ["_before_mixer", "_after_mixer"] * 2
    _, want = _reference_logprob_sum(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _program_logprob_sum(
        p, cfg, ids, seg, pos, seqs, remat=remat, bands=True)[0]
    g_prog = jax.grad(prog)(params)
    g_ref = jax.grad(lambda p: _reference_logprob_sum(p, HF, seqs)[0])(params)
    g_ref["layers"]["mlp"]["expert_bias"] = g_prog["layers"]["mlp"]["expert_bias"]
    _assert_trees_close(g_prog, g_ref)


def test_a_dead_bands_cells_leave_the_stack_as_zeros(monkeypatch):
    """What no token is in reads zero after every layer, not what memory
    held: the hidden states past the last live band, and the stream's
    gradient there."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    small_bands(monkeypatch)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed(rows=[[24, 13]], row_len=96)
    hidden = forward(params, cfg, ids, seg, pos, attn_impl="reference", output="hidden",
                     bands=True)
    assert np.asarray(hidden[:, :37]).any() and not np.asarray(hidden[:, 48:]).any()
    whole = forward(params, cfg, jnp.tile(ids, (2, 1)), jnp.tile(seg, (2, 1)),
                    jnp.tile(pos, (2, 1)), attn_impl="reference", output="hidden")
    np.testing.assert_allclose(hidden[0, :37], whole[0, :37], atol=2e-5)


def test_what_keeps_the_whole_row(monkeypatch):
    """Rows together, a row under two bands, a caller that wants the KV
    cache and one that does not ask for bands run no loop: the parent's
    program."""
    ran = small_bands(monkeypatch)
    kind = LayerKind(mlp="dense", window=None, rotary=True)
    hf = dict(HF, num_hidden_layers=2, num_dense_layers=2, layer_types=["full_attention"] * 2)
    cfg = _cfg(hf, layer_kinds=(kind, kind))
    params = _params(cfg)
    ids, seg, pos, _ = _packed()  # two rows
    run = lambda *a, **kw: forward(params, cfg, *a, attn_impl="reference", **kw)
    run(ids, seg, pos, bands=True)
    run(ids[:1, :24], seg[:1, :24], pos[:1, :24], bands=True)
    run(ids[:1], seg[:1], pos[:1], return_kv=True, bands=True)
    run(ids[:1], seg[:1], pos[:1])
    assert not ran
    run(ids[:1], seg[:1], pos[:1], bands=True)
    assert ran == ["_before_mixer", "_after_mixer"]


def test_a_second_program_finds_its_stretches_traced(monkeypatch):
    """What the set-up budget rests on: a cell's later programs
    (`forward` after `accum_step`; until PR 49 a second accumulate
    program too) hand
    `ops/band_loop.stretch` the same functions, static description and
    shapes, so the stretch's Python runs for the first program alone (its
    plain loop, its forward rule and its backward loop) and for no later
    one; and layers that run one by one, outside a scan, loop as well,
    the second and third finding the first's trace: three cost the
    stretches' Python what one does."""
    import collections

    from areal_tpu.models import transformer as tf

    ran = small_bands(monkeypatch)
    calls = []
    for name in ("_before_mixer", "_after_mixer"):
        def counted(st, w, xs, side, fn=getattr(tf, name), name=name):
            calls.append(name)
            return fn(st, w, xs, side)

        monkeypatch.setattr(tf, name, counted)
    kind = LayerKind(mlp="dense", window=None, rotary=True)
    ids, seg, pos, seqs = _packed(rows=[[24, 13]], row_len=96)
    hf = dict(HF, num_hidden_layers=3, num_dense_layers=3, layer_types=["full_attention"] * 3)
    cfg = _cfg(hf, layer_kinds=(kind,) * 3)
    assert [s.repeats for s in cfg.segments()] == [3] and tf.looping_layers(cfg, 1, 96) == 3
    params = _params(cfg)
    total = lambda p: _program_logprob_sum(
        p, cfg, ids, seg, pos, seqs, remat="full", bands=True)[0]
    jax.jit(jax.grad(total))(params)
    first = collections.Counter(calls)
    assert set(first) == {"_before_mixer", "_after_mixer"} and len(ran) == 2
    jax.jit(jax.value_and_grad(lambda p: total(p)))(params)  # a second program
    jax.jit(lambda p: total(p))(params)  # a third, forward only
    assert collections.Counter(calls) == first and len(ran) == 6
    alone = _cfg(hf, layer_kinds=(kind,) * 3, scan_min_repeats=4)  # one by one
    assert [s.repeats for s in alone.segments()] == [1] * 3
    assert tf.looping_layers(alone, 1, 96) == 3
    del ran[:], calls[:]
    _program_logprob_sum(params, alone, ids, seg, pos, seqs, bands=True)
    three = collections.Counter(calls)
    assert len(ran) == 6 and set(three) == {"_before_mixer", "_after_mixer"}
    one = _cfg(dict(hf, num_hidden_layers=1, num_dense_layers=1,
                    layer_types=["full_attention"]), layer_kinds=(kind,))
    del ran[:], calls[:]
    _program_logprob_sum(_params(one), one, ids, seg, pos, seqs, bands=True)
    assert len(ran) == 2 and collections.Counter(calls) == three


def test_next_token_logprobs_pads_and_blocks():
    """The harness's entry point: padded to `pad_to`, attention and
    logits in blocks of rows, the same numbers as the packed program."""
    cfg, ids_seg = _cfg(), _packed()
    params = _params(cfg)
    ids, seg, pos, seqs = ids_seg
    _, got = _program_logprob_sum(params, cfg, ids, seg, pos, seqs)
    for g, (_, _, t) in zip(got, seqs):
        want = ref.next_token_logprobs(params, HF, t, pad_to=2 * ref.ROWS)
        np.testing.assert_allclose(np.asarray(g), want, atol=2e-5)


def _expert_layer_inputs(n_tokens=96, seed=3):
    """A whole expert layer (16 experts, all held) and some tokens."""
    hf = dict(HF, num_experts=16, experts_held_first=0)
    cfg = _cfg(hf)
    assert cfg.moe.experts_held is None
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, seed, 0.1)["layers"]["mlp"])
    h2 = jax.random.normal(jax.random.PRNGKey(seed), (n_tokens, 32))
    return hf, cfg, mlp, h2


def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 8 shares, the
    shared expert counted once, add up to what the reference gives for
    the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf, cfg, mlp, h2 = _expert_layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h2, mlp, hf)
        total = jnp.zeros_like(h2)
        pairs = 0.0
        for share in range(8):
            held = (2 * share, 2)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 2] if k in ("w_gate", "w_up", "w_down") else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h2, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            # the reference, given the same share, gives the same part
            part = ref.expert_layer(h2, mp, dict(hf, num_experts=2, num_experts_routed=16,
                                                 experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h2.shape[0] * cfg.moe.top_k  # every pair is held by one share


@pytest.mark.parametrize("tile", [8, 512], ids=["12-tiles-an-expert", "1-tile-an-expert"])
def test_no_pair_is_dropped_when_every_token_goes_to_the_held_experts(tile, monkeypatch):
    """A skew that sends all k choices of every token to the experts held
    here: 8 times an even share, so the loop runs as many tiles as that
    takes; the answer is the reference's and every pair is counted."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", tile)
    monkeypatch.setattr(moe_lib, "_HELD_CHUNK_ROWS", 4 * tile)  # 12 chunks of 4 tiles, or one
    hf, cfg, mlp, h2 = _expert_layer_inputs()
    held = (4, 4)  # k = 4 of the 4 held
    mlp = dict(mlp, expert_bias=jnp.zeros(16).at[4:8].set(10.0))
    mp = {k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in mlp.items()}
    c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
    mask = jnp.arange(h2.shape[0]) < 90  # the last tokens are padding
    with jax.default_matmul_precision("highest"):
        y, aux = moe_lib.moe_mlp(h2, mp, c, jnp.float32, token_mask=mask)
        want = ref.expert_layer(h2, mp, dict(hf, num_experts=4, num_experts_routed=16,
                                             experts_held_first=4))
        shared = ref._swiglu(h2, mp["shared"])
    k = cfg.moe.top_k
    np.testing.assert_allclose(np.asarray(y[:90]), np.asarray(want[:90]), atol=5e-5)
    # padding is routed nowhere: only the shared expert's part is left
    np.testing.assert_allclose(np.asarray(y[90:]), np.asarray(shared[90:]), atol=5e-5)
    assert float(aux["pairs_held"]) == 90 * k and float(aux["drop_rate"]) == 0.0
    # each of the 4 held experts has 90 pairs: whole tiles of them
    assert float(aux["rows_run"]) == 4 * -(-90 // tile) * tile


def test_rows_past_a_tiles_pairs_never_reach_a_result_or_a_gradient(monkeypatch):
    """An expert's last tile is short: its rows past the pairs hold other
    experts' pairs, masked on the way in. Here the expert's MLP leaves
    NaN in every such row: the layer's result and every gradient (the
    router's too, through the pairs' weights) stay finite and equal to
    the clean run's."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf, cfg, mlp, h2 = _expert_layer_inputs()
    c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=(4, 4)))
    mp = {k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in mlp.items()}

    def run(mp, h2):
        y, aux = moe_lib.moe_mlp(h2, mp, c, jnp.float32)
        return (y * jnp.cos(y)).sum(), (aux["rows_run"], aux["pairs_held"])

    (clean, (rows, pairs)), g_clean = jax.value_and_grad(run, (0, 1), has_aux=True)(mp, h2)
    real = moe_lib._expert_ffn

    def dirty(xs, ws, act):
        if xs.shape[0] != 8:  # the shared expert, over every token
            return real(xs, ws, act)
        masked = (xs == 0).all(-1)  # what `_tile_out` zeroed on the way in
        return jnp.where(masked[:, None], jnp.nan, real(xs, ws, act))

    monkeypatch.setattr(moe_lib, "_expert_ffn", dirty)
    (got, _), g = jax.value_and_grad(run, (0, 1), has_aux=True)(mp, h2)
    assert float(rows) > float(pairs) > 0  # tiles hold rows beyond the pairs held
    np.testing.assert_allclose(float(got), float(clean), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_clean)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


_HELD = (4, 3)  # experts 4, 5, 6 of 16
_TILE = 8


def _held_case(case, T=40, k=2, seed=0):
    """(choice_e, token_mask) for T tokens of k choices, choice-major,
    and the pairs each held expert gets."""
    rng = np.random.default_rng(seed)
    out = np.array([0, 1, 2, 3, 7, 8, 9, 10, 11, 12, 13, 14, 15])  # experts not held
    choice = rng.choice(out, (k, T))
    mask = np.ones(T, bool)
    if case == "one-pair":
        choice[1, 17] = 5
    elif case == "R-pairs":
        choice[0, rng.choice(T, _TILE, replace=False)] = 4
    elif case == "R+1-pairs":
        choice[1, rng.choice(T, _TILE + 1, replace=False)] = 6
    elif case == "all-to-one":  # every token's first choice: 5 tiles of one expert
        choice[0] = 5
    elif case in ("random", "padding"):
        choice = np.stack([rng.permutation(16)[:k] for _ in range(T)], 1)
        choice[:, ::3] = [[4], [6]]  # a skew: a third of the tokens to two held experts
        if case == "padding":
            mask[T - 10:] = False
    else:
        assert case == "no-pair"
    sizes = [int(((choice == e) & mask).sum()) for e in range(_HELD[0], sum(_HELD))]
    return jnp.asarray(choice.reshape(-1), jnp.int32), jnp.asarray(mask), sizes


def _plain_sum_over_pairs(x, ws, act, choice, gate, mask):
    """sum over the held pairs of w_pair Expert(x_token): every expert's
    MLP over every token, the pairs picked by a mask."""
    T = x.shape[0]
    y = jnp.zeros_like(x)
    for h in range(_HELD[1]):
        out = moe_lib._expert_ffn(x, tuple(m[h] for m in ws), act)
        for c in range(choice.shape[0] // T):
            sel = (choice[c * T:(c + 1) * T] == _HELD[0] + h) & mask
            y = y + jnp.where(sel[:, None], gate[c * T:(c + 1) * T, None] * out, 0)
    return y


@pytest.mark.parametrize("case", ["no-pair", "one-pair", "R-pairs", "R+1-pairs",
                                  "all-to-one", "random", "padding"])
@pytest.mark.parametrize("mats", [("w_gate", "w_up", "w_down"), ("w_in", "w_out")],
                         ids=["gated", "plain"])
def test_the_held_part_is_the_plain_sum_over_its_pairs(mats, case, monkeypatch):
    """`_held_experts` against a sum over pairs that knows no tile:
    values and the gradients of x, of the pairs' weights and of every
    expert matrix, float32, for gated silu and plain squared-ReLU
    experts, at chunks of two tiles (the last chunk of `all-to-one` holds
    one); and the rows run are whole tiles of every expert's pairs."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", _TILE)
    monkeypatch.setattr(moe_lib, "_HELD_CHUNK_ROWS", 2 * _TILE)
    T, k, D, F = 40, 2, 16, 24
    moe = MoEConfig(num_experts=16, top_k=k, dispatch="dropless", score_func="sigmoid",
                    experts_held=_HELD)
    act = moe_lib.activation_fn("silu" if len(mats) == 3 else "relu2")
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (T, D))
    mp = {m: 0.3 * jax.random.normal(key, (_HELD[1], F, D) if m == mats[-1] else (_HELD[1], D, F))
          for m, key in zip(mats, ks[1:])}
    gate = jax.random.uniform(ks[4], (k * T,), minval=0.1, maxval=1.0)
    choice, mask, sizes = _held_case(case)

    def tiled(x, mp, gate):
        y, pairs, rows, chunks = moe_lib._held_experts(
            x, mp, moe, act, jnp.float32, choice, gate, mask, mats)
        return (y * jnp.cos(y)).sum(), (y, pairs, rows, chunks)

    def plain(x, mp, gate):
        y = _plain_sum_over_pairs(x, tuple(mp[m] for m in mats), act, choice, gate, mask)
        return (y * jnp.cos(y)).sum(), y

    with jax.default_matmul_precision("highest"):
        (_, (y, pairs, rows, chunks)), g = jax.value_and_grad(
            tiled, (0, 1, 2), has_aux=True)(x, mp, gate)
        (_, want), g_want = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(x, mp, gate)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert float(pairs) == sum(sizes)
    assert float(rows) == sum(-(-n // _TILE) for n in sizes) * _TILE
    assert float(chunks) == -(-float(rows) // (2 * _TILE))  # chunks of two tiles
    if case == "no-pair":
        assert float(rows) == 0 and not np.asarray(y).any()


def test_the_held_part_is_loops_of_a_run_time_count_and_its_row_scatters_are_sorted():
    """What the held part lowers to, forward and backward: a loop over
    chunks around a loop over a chunk's tiles, each way (the sort's own
    aside), no conditional, no grouped matmul; the two scatters of rows
    into tokens, one a chunk, are handed sorted indices, because the
    chip's compiler otherwise sorts them with the rows as a second
    operand and takes 8 s a program over it."""
    T, k, D, F = 64, 2, 16, 24
    moe = MoEConfig(num_experts=16, top_k=k, dispatch="dropless", score_func="sigmoid",
                    experts_held=_HELD)
    mp = {m: jnp.ones((3, F, D) if m == "w_down" else (3, D, F)) for m in
          ("w_gate", "w_up", "w_down")}
    choice, mask, _ = _held_case("random", T=T)

    def loss(x, mp, gate):
        y, pairs, rows, _ = moe_lib._held_experts(
            x, mp, moe, jax.nn.silu, jnp.float32, choice, gate, mask)
        return (y ** 2).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        jnp.ones((T, D)), mp, jnp.ones((k * T,))).as_text()
    assert "ragged_dot" not in text and "stablehlo.case" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.while") == 4
    rows = [op for op in re.findall(r'"stablehlo\.scatter"\([^\n]*', text)
            if "update_window_dims = [1]" in op]  # rows of [D] into a [T, D]
    assert len(rows) == 2  # y in the forward's chunks, dx in the backward's
    assert all("indices_are_sorted = true" in op for op in rows)


def test_the_router_is_the_published_form():
    """sigmoid scores, chosen on score + bias, weighted by the bare
    scores over their sum, times the scale; float32 whatever comes in."""
    moe = MoEConfig(num_experts=8, top_k=2, score_func="sigmoid",
                    routed_scaling_factor=2.5, dispatch="dropless")
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros(8).at[7].set(100.0)  # always chosen, never weighs more
    logits, scores, top_p, top_e = moe_lib._router(x, w, moe, bias)
    assert logits.dtype == scores.dtype == top_p.dtype == jnp.float32
    s = np.asarray(jax.nn.sigmoid(x.astype(jnp.float32) @ w))
    assert (np.asarray(top_e) == 7).any(axis=1).all()
    picked = np.take_along_axis(s, np.asarray(top_e), 1)
    np.testing.assert_allclose(
        np.asarray(top_p), 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    # no gradient reaches the bias
    g = jax.grad(lambda b: moe_lib._router(x, w, moe, b)[2].sum())(bias)
    assert not np.asarray(g).any()


@pytest.mark.parametrize("window", [None, 128, 200, 4096])
def test_a_window_mask_is_the_same_in_every_attention_implementation(window):
    """The einsum reference, the splash kernel (interpreted) and the
    sharded wrapper, on a packed row with padding."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh

    t, hq, hkv, hd = 512, 4, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (t, hq, hd))
    k = jax.random.normal(ks[1], (t, hkv, hd))
    v = jax.random.normal(ks[2], (t, hkv, hd))
    seg = jnp.asarray(np.repeat([1, 2, 0], [300, 150, 62]), jnp.int32)
    pos = jnp.asarray(np.concatenate([np.arange(300), np.arange(150), np.zeros(62)]), jnp.int32)
    real = np.asarray(seg) > 0
    mask = np.asarray(A.segment_causal_mask(seg, seg, pos, pos, window=window))
    i, j = np.nonzero(mask)
    assert (j <= i).all() and (window is None or (i - j < window).all())
    assert mask[299, 0] == (window is None or window >= 300)
    want = np.asarray(A.reference_packed_attention(q, k, v, seg, pos, window=window))
    got = np.asarray(A.splash_packed_attention(q, k, v, seg, pos, window=window,
                                               interpret=True))
    np.testing.assert_allclose(got[real], want[real], atol=2e-5)
    mesh = make_mesh(MeshSpec(data=2), jax.devices()[:2])
    rows = lambda a: jnp.stack([a, a])
    sharded = np.asarray(A.sharded_splash_attention(
        rows(q), rows(k), rows(v), rows(seg), rows(pos), mesh, window=window,
        interpret=True))
    np.testing.assert_allclose(sharded[0][real], want[real], atol=2e-5)


def test_window_layers_skip_block_pairs_behind_the_window():
    """The count of active block pairs, which the counters report (and
    the run-shape rule prices for a causal mask): causal as before, less
    under a window."""
    assert A._active_block_pairs(4096, 512, 512) == (36, 8)
    assert A._active_block_pairs(4096, 512, 512, window=1024) == (1 + 2 + 6 * 3, 3)
    # what splash's own mask processing keeps, and how far it shrinks the grid
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm, splash_attention_mask_info as mi)
    for t, bq, bkv, window in ((2048, 256, 512, 700), (3072, 512, 256, 2048),
                               (1536, 128, 128, 129), (1024, 512, 512, None)):
        one = (sm.CausalMask((t, t)) if window is None else
               sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
        info, _ = mi.process_mask(sm.MultiHeadMask([one]), (bq, bkv))
        kept = np.asarray(info.block_mask)[0]
        assert A._active_block_pairs(t, bq, bkv, window) == (
            int((kept > 0).sum()), kept.shape[1]), (t, bq, bkv, window)
    assert A._splash_cost_terms(3072, 512, 1024, 512) == A._splash_cost_terms(
        3072, 512, 1024, 512, window=None)
    # the counters, for a row that is all one sequence (nothing else to
    # skip): a window layer runs its rows at the causal layers' shape
    # (chosen from the row length alone) and skips pairs there
    splash = dict(impl="splash", hq=32, hkv=4)
    one = lambda t: np.ones((1, t), np.int32)
    ran, causal = A.attn_block_cells(segment_ids=one(16384), window=2048, **splash)
    assert ran < 0.4 * causal == 0.4 * A.attn_block_cells(segment_ids=one(16384), **splash)[0]
    assert A.attn_block_cells(segment_ids=one(1024), window=2048, **splash) == (
        A.attn_block_cells(segment_ids=one(1024), **splash))
    t_run, bq, bkv, _ = A.splash_run_shape(3712)  # padded to 4096
    assert A.attn_block_cells(segment_ids=one(3712), window=2048, **splash) == (
        A._active_block_pairs(t_run, bq, bkv, 2048)[0] * bq * bkv,
        A._active_block_pairs(t_run, bq, bkv)[0] * bq * bkv)
    assert A.attn_block_cells("reference", np.ones((3, 640), np.int32), 32, 4,
                              window=128) == (3 * 640 * 640, 3 * 640 * 640)


# A window layer (LocalMask 2048) alone on one v5e, 32 / 4 heads of 128,
# three chained layers: 47 timed run shapes of five of the long pool's row
# lengths (scripts/splash_shape_sweep.py --window 2048 --hq 32 --hkv 4; PR 28).
WINDOW_SWEEP = os.path.join(os.path.dirname(__file__), "data", "splash_window_sweep_v5e.jsonl")


@pytest.mark.parametrize("t,slack", [(15616, 1.0), (6656, 1.0), (8704, 1.0),
                                     (13312, 1.0), (3840, 1.13)])
def test_the_row_lengths_pick_is_near_a_window_layers_fastest_measured_shape(t, slack):
    """The shape picked from the row length alone is the fastest measured
    one for a window layer too, but for 3840 (which stays at blocks of 384,
    as under a causal mask): 12 % behind 4096 at 512 / 1024 / 512. Pricing
    the window's pairs with the causal fit would have lost 16 % at 13312."""
    rows = [json.loads(l) for l in open(WINDOW_SWEEP)]
    ms = {(r["t_run"], r["bq"], r["bkv"], r["bkvc"]): r["fwd_ms"] + r["grad_ms"]
          for r in rows if r["t"] == t and "error" not in r}
    assert len(ms) >= 5 and all(r["window"] == 2048 for r in rows)
    pick = A.splash_run_shape(t)
    assert ms[pick] <= slack * min(ms.values())
    if t == 13312:
        priced = min(ms, key=lambda c: sum(
            ns * x for ns, x in zip(A._SPLASH_NS, A._splash_cost_terms(*c, window=2048))))
        assert ms[priced] > 1.15 * ms[pick]


def test_afmoe_names_round_trip():
    fam = get_family("afmoe")
    cfg = _cfg()
    hf2 = fam.config_to_hf(cfg)
    for key in ("layer_types", "sliding_window", "num_dense_layers", "num_experts",
                "num_experts_routed", "experts_held_first", "num_experts_per_tok",
                "moe_intermediate_size", "num_shared_experts", "score_func",
                "route_norm", "route_scale", "mup_enabled", "model_type"):
        assert hf2[key] == HF[key], key
    cfg2 = fam.config_from_hf(hf2)
    assert cfg2.kinds() == cfg.kinds() and cfg2.moe == cfg.moe
    params = jax.tree_util.tree_map(np.asarray, _params(cfg, bias_scale=0.1))
    sd = fam.params_to_hf(params, cfg)
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in sd  # held: 4..7
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    assert sd["model.layers.4.mlp.router.gate.weight"].shape == (16, 32)
    for name in ("self_attn.gate_proj", "self_attn.q_norm", "pre_mlp_layernorm",
                 "post_mlp_layernorm", "mlp.shared_experts.up_proj"):
        assert f"model.layers.2.{name}.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_a_one_kind_configuration_traces_the_program_it_always_did():
    """Its parameter tree and the jaxpr of its backward pass, against
    hashes taken at the commit before layer kinds (PR 27)."""
    cfg = TransformerConfig(
        n_layers=3, hidden_dim=32, n_q_heads=4, n_kv_heads=2, head_dim=8,
        intermediate_dim=64, vocab_size=96, attn_bias=True, tied_embeddings=True,
        param_dtype="float32", compute_dtype="float32")
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    assert set(params) == {"embedding", "layers", "final_norm"}
    ids = jnp.zeros((2, 32), jnp.int32)

    def loss(p, ids):
        return forward(p, cfg, ids, jnp.ones_like(ids), jnp.tile(jnp.arange(32), (2, 1)),
                       attn_impl="reference", remat="full").sum()

    sha = lambda x: hashlib.sha256(str(x).encode()).hexdigest()
    tree = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    assert sha(tree) == "5c46ae3782a9e3baa99418786eb9e3930d4aed202f94635a722dc22d275fcdc7"
    assert sha(jax.make_jaxpr(jax.grad(loss))(params, ids)) == (
        "34425ea4b98f96ecb150f4e2c789dcb30055aca1b539e939f899381921cb993c")


def test_kinds_that_the_stack_cannot_hold_are_refused():
    # MLP kinds that alternate were refused until each kind had a stack of
    # its own (tests/model/test_hybrid_stack.py); a kind without a part is not one
    with pytest.raises(ValueError, match="'dense', 'moe' or None"):
        LayerKind(mlp="gated")
    with pytest.raises(ValueError, match="4 layers"):
        TransformerConfig(n_layers=4, layer_kinds=(LayerKind(),))
    with pytest.raises(ValueError, match="dropless"):
        MoEConfig(num_experts=8, experts_held=(0, 2))
    with pytest.raises(ValueError, match="not a range"):
        MoEConfig(num_experts=8, experts_held=(6, 4), dispatch="dropless")
    cfg = _cfg()
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(_params(cfg), cfg, ids, seg, pos, return_kv=True)


@pytest.mark.parametrize("where", ["prefill", "decode_step", "paged_decode_step",
                                   "ServingEngine"])
@pytest.mark.parametrize("what", ["afmoe", "mistral-window"])
def test_the_cache_paths_refuse_a_window_or_layer_kinds(what, where):
    """Generation, the paged pool and the serving engine hold one kind of
    layer: a configuration with a window or with kinds is refused with
    the missing mechanism named, not run with full attention."""
    from areal_tpu.engine import paged, serving
    from areal_tpu.models import generation

    if what == "afmoe":
        cfg = _cfg()
    else:
        cfg = get_family("mistral").config_from_hf(dict(
            hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=64,
            max_position_embeddings=512, sliding_window=16))
        assert cfg.kinds() == (LayerKind(window=16),) * 2
    call = {
        "prefill": lambda: generation.prefill(None, cfg, jnp.zeros((1, 8), jnp.int32),
                                              jnp.asarray([8]), 16),
        "decode_step": lambda: generation.decode_step(None, cfg, None, None, None, None),
        "paged_decode_step": lambda: paged.paged_decode_step(
            None, cfg, None, None, None, None, None, None),
        "ServingEngine": lambda: serving.ServingEngine(cfg, None),
    }[where]
    with pytest.raises(NotImplementedError, match="cache manager with a kind per layer"):
        call()
