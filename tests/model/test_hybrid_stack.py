"""A stack whose layers have one part each (models/config.py LayerKind:
a state-space mixer, attention, an expert layer or a dense MLP), the
state-space mixer over packed rows (ops/ssm.py), plain squared-ReLU
experts, and the `nemotron_h` family: the program against the plain
reference `benchmark/reference/nemotron_h.py` on the CPU, float32, seeded
random weights, toy widths."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import (
    LayerKind, MoEConfig, Segment, SSMConfig, TransformerConfig, segments_of,
)
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params
from areal_tpu.ops import ssm as ssm_lib
from benchmark.reference import nemotron_h as ref

from tests.model.test_layer_kinds import (
    HF as AFMOE_HF, _assert_trees_close, _cfg as _afmoe_cfg, _packed, small_bands,
)

HF = dict(
    model_type="nemotron_h", hidden_size=32, intermediate_size=48,
    num_hidden_layers=9, hybrid_override_pattern="MEMEM*EME",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=64,
    max_position_embeddings=512, layer_norm_epsilon=1e-5, norm_eps=1e-5,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, use_conv_bias=True, mamba_proj_bias=False,
    mamba_hidden_act="silu", mlp_hidden_act="relu2", time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4,
    n_routed_experts=4, num_experts_routed=16, experts_held_first=4,
    num_experts_per_tok=4, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1, topk_group=1,
    attention_bias=False, mlp_bias=False, tie_word_embeddings=False,
)
SSM = SSMConfig(n_heads=4, head_dim=8, n_groups=2, state_dim=16, chunk_size=16)


def _cfg(hf=HF, **over):
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32", **over)


def _params(cfg, seed=0, bias_scale=0.1):
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    moe = params["stacks"].get("moe")
    if moe is not None and bias_scale:  # a selection bias that is not zero
        moe["mlp"]["expert_bias"] = bias_scale * jax.random.normal(
            jax.random.PRNGKey(seed + 1), moe["mlp"]["expert_bias"].shape)
    return params


def _program_logprobs(params, cfg, ids, seg, pos, seqs, **kw):
    """Each sequence's next-token logprobs, from one packed forward pass."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, ids, seg, pos, attn_impl="reference", **kw)
    lp = jax.nn.log_softmax(logits, -1)
    return [jnp.take_along_axis(lp[r, o:o + len(t) - 1], jnp.asarray(t[1:, None]), -1)[:, 0]
            for r, o, t in seqs]


def _reference_logprobs(params, hf, seqs):
    out = []
    for _, _, t in seqs:
        n = -(-len(t) // ref.ROWS) * ref.ROWS
        ids = jnp.asarray(np.concatenate([t, np.zeros(n - len(t), np.int64)]), jnp.int32)
        out.append(ref._forward(params, ids, hf)[: len(t) - 1])
    return out


def _ppo_loss(logprobs, seed=7):
    """A PPO actor step's loss from the sequences' logprobs: the clipped
    surrogate against seeded behaviour logprobs and advantages."""
    rng = np.random.default_rng(seed)
    total, n = 0.0, 0
    for lp in logprobs:
        old = jax.lax.stop_gradient(lp) + jnp.asarray(
            0.2 * rng.standard_normal(lp.shape[0]), jnp.float32)
        adv = jnp.asarray(rng.standard_normal(lp.shape[0]), jnp.float32)
        ratio = jnp.exp(lp - old)
        total = total + jnp.sum(-jnp.minimum(ratio * adv, jnp.clip(ratio, 0.8, 1.2) * adv))
        n += lp.shape[0]
    return total / n


def _no_bias_grad(g_prog, g_ref):
    """`expert_bias` is a buffer: the program sends it no gradient."""
    bias = g_prog["stacks"]["moe"]["mlp"]["expert_bias"]
    assert not np.asarray(bias).any()
    g_ref["stacks"]["moe"]["mlp"]["expert_bias"] = bias
    return g_ref


@pytest.mark.parametrize("remat", ["none", "full"])
def test_the_stack_matches_the_reference_through_a_ppo_step(remat, monkeypatch):
    """`M E M E M * E M E`: a scan over two (M, E) units and five layers
    one by one, three parameter stacks; logprobs, the PPO loss and every
    parameter's gradient."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)  # several tiles an expert at toy size
    cfg = _cfg()
    assert [s.repeats for s in cfg.segments()] == [2, 1, 1, 1, 1, 1]
    params = _params(cfg)
    assert {k: jax.tree_util.tree_leaves(v)[0].shape[0]
            for k, v in params["stacks"].items()} == {"ssm": 4, "moe": 4, "attention": 1}
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat=remat)
    want = _reference_logprobs(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _ppo_loss(_program_logprobs(p, cfg, ids, seg, pos, seqs, remat=remat))
    plain = lambda p: _ppo_loss(_reference_logprobs(p, HF, seqs))
    (l_prog, g_prog), (l_ref, g_ref) = (jax.value_and_grad(f)(params) for f in (prog, plain))
    np.testing.assert_allclose(float(l_prog), float(l_ref), atol=2e-5)
    _assert_trees_close(g_prog, _no_bias_grad(g_prog, g_ref), rtol=1e-4)


@pytest.mark.parametrize("remat", ["none", "full", "mlp"])
@pytest.mark.parametrize("pattern,loops,ran_want", [
    ("MEMEM*EME", 4, ["_ssm_layer"] * 3), ("E*EE*", 0, [])],
    ids=["M_walks_its_bands", "E_and_attention_keep_the_row"])
def test_a_half_empty_row_of_one_part_layers(pattern, loops, ran_want, remat, monkeypatch):
    """One row alone, 37 tokens in 96 cells at bands of 16. A Mamba-2
    mixer alone in its layer walks the three live bands as one loop that
    hands state and taps from band to band (`transformer._ssm_layer`
    under `band_loop.carried`: the scan's one body and the two layers
    that run alone); experts or attention alone in a layer run no loop
    (`transformer._kind_loops`). Either way the logprobs, the PPO loss and
    every gradient are the plain reference's, as two rows' are."""
    from areal_tpu.models.transformer import looping_layers

    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    ran = small_bands(monkeypatch)
    hf = dict(HF, num_hidden_layers=len(pattern), hybrid_override_pattern=pattern)
    cfg = _cfg(hf)
    assert looping_layers(cfg, 1, 96) == loops and looping_layers(_afmoe_cfg(), 1, 96) == 5
    assert looping_layers(cfg, 1, 96, mixer="ssm") == loops
    assert looping_layers(cfg, 2, 96) == looping_layers(cfg, 1, 96, sharded=True) == 0
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=[[24, 13]], row_len=96)
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat=remat, bands=True)
    assert ran == ran_want
    want = _reference_logprobs(params, hf, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _ppo_loss(_program_logprobs(
        p, cfg, ids, seg, pos, seqs, remat=remat, bands=True))
    plain = lambda p: _ppo_loss(_reference_logprobs(p, hf, seqs))
    (l_prog, g_prog), (l_ref, g_ref) = (jax.value_and_grad(f)(params) for f in (prog, plain))
    np.testing.assert_allclose(float(l_prog), float(l_ref), atol=2e-5)
    _assert_trees_close(g_prog, _no_bias_grad(g_prog, g_ref), rtol=1e-4)


def _band_layer_operands(lens, T=64, seed=0):
    """A toy Mamba-2 layer's weights (`ln1` and the mixer, a bias on the
    taps), a packed row of `lens` in `T` cells and a stream to run."""
    cfg = _cfg()
    lp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, seed)["stacks"]["ssm"])
    lp["ssm"]["conv_b"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 5), lp["ssm"]["conv_b"].shape)
    seg = np.zeros((1, T), np.int32)
    o = 0
    for j, n in enumerate(lens):
        seg[0, o:o + n] = j + 1
        o += n
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, T, cfg.hidden_dim))
    return cfg, {"ln1": lp["ln1"], "mixer": lp["ssm"]}, jnp.asarray(seg), x


def _layer_over_bands(cfg, w, x, seg):
    """The layer as `forward` runs it where a row walks its bands."""
    from areal_tpu.models import transformer as tf
    from areal_tpu.ops import band_loop

    st = tf._Stretch(cfg, LayerKind(mixer="ssm", mlp=None), jnp.float32)
    return band_loop.carried(
        tf._ssm_layer, st, w, (x,), (seg,), ssm_lib.start_carry(cfg.ssm, 1, jnp.float32),
        band_loop.live_bands(seg))[0]


def _layer_whole(cfg, w, x, seg):
    from areal_tpu.models.transformer import _norm

    return x + ssm_lib.ssm_mixer(_norm(x, w["ln1"], cfg), w["mixer"], cfg.ssm, seg,
                                 jnp.float32, cfg.norm_eps)


@pytest.mark.parametrize("lens", [
    [20, 17], [16, 20], [14, 2, 20], [30, 10], [40, 24], [3], []], ids=[
    "a_sequence_crosses_a_boundary", "one_starts_on_a_bands_first_cell",
    "one_of_two_cells_before_a_boundary", "a_dead_last_band", "a_full_row",
    "one_band_of_four", "no_token"])
def test_a_mamba2_layer_over_its_live_bands_is_the_whole_rows(lens, monkeypatch):
    """`transformer._ssm_layer` band after band (`band_loop.carried`: the
    state, the taps' last three cells and their segment ids handed on)
    against `ssm_mixer` over the whole row, float32: the result and every
    gradient to 1e-5; a band no token is in reads zeros and sends its
    cells no gradient."""
    small_bands(monkeypatch)
    cfg, w, seg, x = _band_layer_operands(lens)
    assert cfg.ssm.chunk_size == 16 and cfg.ssm.conv_kernel == 4
    cells = -(-sum(lens) // 16) * 16
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    live = (jnp.arange(x.shape[1]) < cells)[None, :, None]  # a dead band is no one's to read
    loss = lambda run: lambda w, x: jnp.sum(jnp.where(live, run(cfg, w, x, seg) * cot, 0))
    with jax.default_matmul_precision("highest"):
        got, want = _layer_over_bands(cfg, w, x, seg), _layer_whole(cfg, w, x, seg)
        g_got = jax.grad(loss(_layer_over_bands), (0, 1))(w, x)
        g_want = jax.grad(loss(_layer_whole), (0, 1))(w, x)
    np.testing.assert_allclose(np.asarray(got[:, :cells]), np.asarray(want[:, :cells]),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[:, cells:]).any(), "a dead band's results are zeros"
    _assert_trees_close(g_got, g_want, rtol=1e-5)
    assert not np.asarray(g_got[1][:, cells:]).any(), "nothing flows into a dead band"


def test_a_packed_row_over_its_bands_is_each_of_its_sequences_alone(monkeypatch):
    """State and taps start afresh at every sequence start wherever it
    falls in a band, and what one band hands the next is its own
    sequence's: each sequence of a row that walks its bands reads what it
    reads alone in a row of its own, from its first cell."""
    small_bands(monkeypatch)
    lens = [14, 2, 20, 9, 3]  # starts at 0, 14, 16, 36 and 45; 48 cells of 64 live
    cfg, w, seg, x = _band_layer_operands(lens, seed=3)
    with jax.default_matmul_precision("highest"):
        packed = _layer_over_bands(cfg, w, x, seg)
        o = 0
        for n in lens:
            alone = _layer_whole(cfg, w, x[:, o:o + n], jnp.ones((1, n), jnp.int32))
            np.testing.assert_allclose(np.asarray(packed[0, o:o + n]), np.asarray(alone[0]),
                                       atol=2e-5)
            o += n
        x_nan = jnp.where((seg > 0)[..., None], x, jnp.nan)  # padding reaches nothing
        np.testing.assert_array_equal(
            np.asarray(_layer_over_bands(cfg, w, x_nan, seg)[0, :o]), np.asarray(packed[0, :o]))


def test_the_host_counts_the_chunks_and_cells_the_devices_loop_runs(monkeypatch):
    """`train.ssm_chunks` and `train.band_cells` (`engine/train_counts.py`)
    against what the device ran, counted where it runs: a callback in the
    scan, a call a band. One row of 128 cells with 40 tokens at bands of
    16 and chunks of 8: the four `M` layers run three bands of two chunks
    each, the other five layers the whole row; rows together, a mesh that
    splits them and a packer that fills every band run every chunk."""
    from areal_tpu.engine.train_counts import TrainCounts

    small_bands(monkeypatch)
    hf = dict(HF, chunk_size=8)
    cfg = _cfg(hf)
    params = _params(cfg)
    ids, seg, pos, _ = _packed(rows=[[24, 16]], row_len=128)
    ran, scan_from = [], ssm_lib.scan_from

    def counted(received, x, *a):
        jax.debug.callback(lambda: ran.append(x.shape[1] // cfg.ssm.chunk_size))
        return scan_from(received, x, *a)

    monkeypatch.setattr(ssm_lib, "scan_from", counted)
    forward(params, cfg, ids, seg, pos, attn_impl="reference", bands=True).block_until_ready()
    jax.effects_barrier()
    assert ran == [2] * 12  # four layers, three live bands each

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    counts = TrainCounts(cfg, mesh, "reference", 128, 1, False, cfg.n_moe_layers)
    said = lambda c, s: c.of({"segment_ids": np.asarray(s)}, 40)[0]
    c = said(counts, seg)
    assert c["train.ssm_chunks"] == sum(ran) == 24
    assert c["train.ssm_chunks_live"] == 4 * 5 and c["train.ssm_resets"] == 4 * 2
    assert c["train.band_cells"] == (sum(ran) * 8 + 5 * 128) // 9
    whole = 4 * 128 // 8
    assert said(counts, np.concatenate([seg, seg]))["train.ssm_chunks"] == 2 * whole
    filled = dataclasses.replace(counts, row_len_multiple=16)
    assert said(filled, seg)["train.ssm_chunks"] == whole
    assert said(filled, seg)["train.band_cells"] == 128
    assert ssm_lib.chunk_counts(np.asarray(seg), 8, band=16)[0] == 6
    assert ssm_lib.chunk_counts(np.zeros((1, 128), np.int32), 8, band=16)[0] == 0


def test_a_dense_mlp_layer_and_a_unit_of_three_run_as_the_reference_does():
    """`M - * M - *`: the `-` layer (a plain squared-ReLU MLP of
    `intermediate_size`), and one scan over a unit of three kinds."""
    hf = dict(HF, num_hidden_layers=6, hybrid_override_pattern="M-*M-*")
    cfg = _cfg(hf)
    assert cfg.moe is None and cfg.segments() == (
        Segment(0, ("ssm", "dense", "attention"), 2),)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(3))
    assert set(params["stacks"]["dense"]["mlp"]) == {"w_in", "w_out"}
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat="full")
    for g, w in zip(got, _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    g_prog = jax.grad(lambda p: _ppo_loss(
        _program_logprobs(p, cfg, ids, seg, pos, seqs, remat="full")))(params)
    g_ref = jax.grad(lambda p: _ppo_loss(_reference_logprobs(p, hf, seqs)))(params)
    _assert_trees_close(g_prog, g_ref, rtol=1e-4)


def test_a_unit_that_holds_a_kind_several_times_takes_its_layers_in_order(monkeypatch):
    """`(M E M E M * E) x 2`: three state-space and three expert layers a
    repeat, cut from their kinds' stacks of six in the pattern's order."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_hidden_layers=14, hybrid_override_pattern="MEMEM*E" * 2)
    cfg = _cfg(hf)
    assert cfg.segments() == (Segment(0, ("ssm", "moe", "ssm", "moe", "ssm", "attention", "moe"), 2),)
    params = _params(cfg, seed=5)
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat="full")
    for g, w in zip(got, _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    g_prog = jax.grad(lambda p: _ppo_loss(
        _program_logprobs(p, cfg, ids, seg, pos, seqs, remat="full")))(params)
    g_ref = jax.grad(lambda p: _ppo_loss(_reference_logprobs(p, hf, seqs)))(params)
    _assert_trees_close(g_prog, _no_bias_grad(g_prog, g_ref), rtol=1e-4)


def test_a_pattern_is_cut_into_runs_of_a_repeated_unit():
    cut = lambda s: [("".join(x.unit), x.repeats) for x in segments_of(tuple(s))]
    assert cut("MEMEM*EME") == [("ME", 2), ("M", 1), ("*", 1), ("E", 1), ("M", 1), ("E", 1)]
    assert cut("DAAAA") == [("D", 1), ("A", 4)] and cut("A" * 12) == [("A", 12)]
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert cut(whole) == [("MEMEM*E", 5), ("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("E", 1)]
    assert sum(len(u) * r for u, r in cut(whole)) == 52  # 14 traced layers of 52
    # blocks that alternate have a stack a kind and one scan over the pair
    cfg = TransformerConfig(n_layers=4, moe=MoEConfig(), layer_kinds=tuple(
        LayerKind(mlp=m) for m in ("dense", "moe", "dense", "moe")))
    assert {p[0] for p in cfg.stack_paths().values()} == {
        ("stacks", "attention+dense"), ("stacks", "attention+moe")}
    assert cfg.segments() == (Segment(0, ("attention+dense", "attention+moe"), 2),)


def recurrent_scan(x, dt, A, B, C, segment_ids, chunk=None):
    """The recurrence of `ops/ssm.chunked_scan` token by token: S_t = exp(dt_t A)
    S_{t-1} + dt_t x_t (x) B_t, S = 0 at a sequence's first token."""
    R, T, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    start = segment_ids != jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :T]
    rep = lambda a: jnp.repeat(a.astype(f32), H // G, axis=2)  # [R, T, H, N]

    def step(S, inp):
        xt, dtt, Bt, Ct, st = inp
        S = jnp.where(st[:, None, None, None], 0.0, S)
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return S, jnp.einsum("rhpn,rhn->rhp", S, Ct)

    t_first = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((R, H, P, N), f32),
        (t_first(x.astype(f32)), t_first(dt), t_first(rep(B)), t_first(rep(C)),
         t_first(start)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(T, seed=0, rows=((20, 25), (7, 43))):
    R, H, P, G, N = len(rows), 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    seg = np.zeros((R, T), np.int32)
    for r, lens in enumerate(rows):
        o = 0
        for j, n in enumerate(lens):
            seg[r, o:o + n] = j + 1
            o += n
    seg = jnp.asarray(seg)
    valid = seg > 0
    x = jax.random.normal(k[0], (R, T, H, P)) * valid[..., None, None]
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, T, H))) * valid[..., None]
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = jax.random.normal(k[3], (R, T, G, N)), jax.random.normal(k[4], (R, T, G, N))
    return (x, dt, A, B, C), seg


@pytest.mark.parametrize("T,chunk", [(64, 16), (50, 16), (50, 64), (64, 8)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    """Row lengths that are and are not multiples of the chunk, sequence
    starts inside chunks and on their edges, padding at the tail: the
    result and the gradients of the token-by-token recurrence."""
    args, seg = _scan_inputs(T)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        f = lambda scan: lambda *a: (scan(*a, seg, chunk) * w).sum()
        got, g_got = jax.value_and_grad(f(ssm_lib.chunked_scan), (0, 1, 2, 3, 4))(*args)
        want, g_want = jax.value_and_grad(f(recurrent_scan), (0, 1, 2, 3, 4))(*args)
        y, y_want = ssm_lib.chunked_scan(*args, seg, chunk), recurrent_scan(*args, seg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), atol=2e-5 * float(jnp.abs(y_want).max()))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    _assert_trees_close(g_got, g_want, rtol=1e-4)


def test_a_packed_row_is_each_of_its_sequences_alone():
    """State and convolution start afresh at every sequence start, and
    cells of padding, filled with NaN, reach neither a result nor a
    gradient."""
    D, T = 32, 64
    sp = jax.tree_util.tree_map(lambda a: a[0], ssm_lib.init_ssm_params(
        SSM, D, lambda k, s, scale=None: jax.random.normal(k, s) * (scale or s[-2] ** -0.5),
        jax.random.PRNGKey(0), 1, jnp.float32))
    sp["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), sp["conv_b"].shape)
    lens = [[20, 30, 10], [45, 11]]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, D))
    seg = np.zeros((2, T), np.int32)
    for r, ls in enumerate(lens):
        o = 0
        for j, n in enumerate(ls):
            seg[r, o:o + n] = j + 1
            o += n
    seg = jnp.asarray(seg)
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, D)) * (seg > 0)[..., None]
    mixer = lambda h, sp, seg: ssm_lib.ssm_mixer(h, sp, SSM, seg, jnp.float32, 1e-5)
    loss = lambda h, sp: (mixer(h, sp, seg) * w).sum()
    with jax.default_matmul_precision("highest"):
        packed = mixer(h, sp, seg)
        for r, ls in enumerate(lens):
            o = 0
            for n in ls:  # the sequence alone in a row of its own, chunks from its start
                alone = mixer(h[r:r + 1, o:o + n], sp, jnp.ones((1, n), jnp.int32))
                np.testing.assert_allclose(np.asarray(packed[r, o:o + n]),
                                           np.asarray(alone[0]), atol=2e-5)
                o += n
            assert not np.asarray(packed[r, o:]).any()  # padding gets nothing
        (g_h, g_sp) = jax.grad(loss, (0, 1))(h, sp)
        h_nan = jnp.where((seg > 0)[..., None], h, jnp.nan)
        np.testing.assert_array_equal(np.asarray(mixer(h_nan, sp, seg)), np.asarray(packed))
        (n_h, n_sp) = jax.grad(loss, (0, 1))(h_nan, sp)
    for a, b in zip(jax.tree_util.tree_leaves((n_h, n_sp)), jax.tree_util.tree_leaves((g_h, g_sp))):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seeded_state_space_parameters_neither_freeze_nor_blow_up():
    """A_log = log U(1, 16), dt = softplus(dt_bias) log-uniform in
    [0.001, 0.1], D = 1: a head's decay a token lies in (0.2, 0.999)."""
    sp = ssm_lib.init_ssm_params(
        SSMConfig(n_heads=64, head_dim=8), 32, lambda k, s, scale=None: jnp.zeros(s),
        jax.random.PRNGKey(0), 4, jnp.float32)
    A, dt = np.exp(np.asarray(sp["A_log"])), np.asarray(jax.nn.softplus(sp["dt_bias"]))
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.std() > 2.0
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6
    decay = np.exp(-dt * A)
    assert 0.2 < decay.min() and decay.max() < 0.9991
    assert (np.asarray(sp["D"]) == 1).all() and sp["conv_w"].shape == (4, 4, 64 * 8 + 32)


def _expert_layer_inputs(n_experts=32, n_tokens=96, seed=3):
    """A whole plain expert layer (all `n_experts` held) and some tokens."""
    hf = dict(HF, num_hidden_layers=1, hybrid_override_pattern="E",
              n_routed_experts=n_experts, num_experts_routed=n_experts,
              experts_held_first=0)
    cfg = _cfg(hf)
    assert cfg.moe.experts_held is None and cfg.mlp_type == "plain"
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, seed)["stacks"]["moe"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(seed), (n_tokens, 32))
    return hf, cfg, mlp, h


def test_the_sixteen_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 16 shares of 2
    experts, the shared expert counted once, add up to what the reference
    gives for the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf, cfg, mlp, h = _expert_layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(16):
            held = (2 * share, 2)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 2] if k in ("w_in", "w_out") else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            part = ref.expert_layer(h, mp, dict(hf, n_routed_experts=2,
                                                experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h.shape[0] * cfg.moe.top_k  # every pair is held by one share


@pytest.mark.parametrize("tile", [4, 8, 512])
def test_plain_experts_run_whole_tiles_and_rows_past_their_pairs_reach_nothing(tile, monkeypatch):
    """A share of 4 plain squared-ReLU experts at tiles of 4, 8 and 512
    rows: the rows run are tiles x the tile, at least the pairs held;
    with NaN left in every row past a tile's pairs (masked on the way in)
    the result and every gradient, the router's too, are the clean
    run's."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", tile)
    hf, cfg, mlp, h = _expert_layer_inputs()
    c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=(8, 4)))
    mp = {k: (v[8:12] if k in ("w_in", "w_out") else v) for k, v in mlp.items()}
    mask = jnp.arange(h.shape[0]) < 80  # the last tokens are padding

    def run(mp, h):
        y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32, token_mask=mask)
        return (y * jnp.cos(y)).sum(), (aux["rows_run"], aux["pairs_held"])

    (clean, (rows, pairs)), g_clean = jax.value_and_grad(run, (0, 1), has_aux=True)(mp, h)
    real = moe_lib._expert_ffn

    def dirty(xs, ws, act):
        if xs.shape[0] != tile:  # the shared expert, over every token
            return real(xs, ws, act)
        return jnp.where((xs == 0).all(-1)[:, None], jnp.nan, real(xs, ws, act))

    monkeypatch.setattr(moe_lib, "_expert_ffn", dirty)
    (got, _), g = jax.value_and_grad(run, (0, 1), has_aux=True)(mp, h)
    assert float(rows) % tile == 0 and float(rows) >= float(pairs) > 0
    assert float(rows) < float(pairs) + 4 * tile  # under a tile an expert is empty
    np.testing.assert_allclose(float(got), float(clean), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_clean)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_plain_squared_relu_experts_are_a_loop_over_experts(dispatch):
    """`relu(x W_in)^2 W_out` for each chosen expert, weighted, plus the
    shared expert of its own width: the sorted grouped matmuls and the
    capacity einsum (no token dropped at this capacity) against a loop."""
    hf, cfg, mlp, h = _expert_layer_inputs(n_experts=8)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, dispatch=dispatch, capacity_factor=8.0))
    assert mlp["shared"]["w_in"].shape == (32, 24) and mlp["w_in"].shape == (8, 32, 16)
    with jax.default_matmul_precision("highest"):
        y, aux = moe_lib.moe_mlp(h, mlp, cfg, jnp.float32)
        s = jax.nn.sigmoid(h @ mlp["router"])
        _, chosen = jax.lax.top_k(s + mlp["expert_bias"], 4)
        want = np.zeros(h.shape, np.float32)
        for t in range(h.shape[0]):
            picked = np.asarray(s[t, chosen[t]])
            for e, w in zip(np.asarray(chosen[t]), picked / (picked.sum() + 1e-20) * 2.5):
                want[t] += w * np.asarray(
                    jnp.square(jax.nn.relu(h[t] @ mlp["w_in"][e])) @ mlp["w_out"][e])
        want += np.asarray(jnp.square(jax.nn.relu(h @ mlp["shared"]["w_in"]))
                           @ mlp["shared"]["w_out"])
    np.testing.assert_allclose(np.asarray(y), want, atol=5e-5)
    assert float(aux["drop_rate"]) == 0.0
    assert moe_lib.expert_mats(cfg) == ("w_in", "w_out")


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("sizes", [
    [20, 30, 10, 5], [32, 0, 32, 32], [0, 0, 0, 7], [33, 20, 10, 5], [0, 96, 0, 0]])
def test_the_grouped_matmuls_are_a_loop_over_the_experts_rows(sizes, gated):
    """`_grouped_ffn`, plain (`relu(x W_in)^2 W_out`) and gated, is a
    loop over each expert's own rows; rows of no group (filled by the
    kernel with whatever it left, zeroed on the way in) reach neither
    the result's own rows nor a gradient."""
    B, D, F = 96, 16, 24
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    w = tuple(jax.random.normal(k[i], (4, D, F)) for i in range(1, 3 if gated else 2)
              ) + (jax.random.normal(k[3], (4, F, D)),)
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(B) < sum(sizes))[:, None]
    xs = jnp.where(valid, jax.random.normal(k[0], (B, D)), 0)
    act = moe_lib.activation_fn("silu" if gated else "relu2")

    def loop(xs, w):
        out, o = [], 0
        for e, n in enumerate(sizes):
            h = act(xs[o:o + n] @ w[0][e])
            if gated:
                h = h * (xs[o:o + n] @ w[1][e])
            out.append(h @ w[-1][e])
            o += n
        return jnp.concatenate(out + [jnp.zeros((B - o, D))])

    with jax.default_matmul_precision("highest"):
        got = jnp.where(valid, moe_lib._grouped_ffn(xs, w, gs, act), 0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(loop(xs, w)), atol=1e-4, rtol=1e-5)
        f = lambda fn: lambda xs, w: (jnp.where(valid, fn(xs, w), 0) ** 2).sum()
        g_got = jax.grad(f(lambda xs, w: moe_lib._grouped_ffn(xs, w, gs, act)), (0, 1))(xs, w)
        g_want = jax.grad(f(loop), (0, 1))(xs, w)
    _assert_trees_close(g_got, g_want, rtol=1e-5)
    assert np.isfinite(np.asarray(g_got[0])).all()


def test_nemotron_h_config_and_names_round_trip():
    fam = get_family("nemotron_h")
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == [
        "ssm", "moe", "ssm", "moe", "ssm", "attention", "moe", "ssm", "moe"]
    assert cfg.kinds()[5] == LayerKind(mlp=None, mixer="attention", rotary=False)
    assert cfg.moe.experts_held == (4, 4) and cfg.moe.num_experts == 16
    assert cfg.moe.shared_intermediate_dim == 24 and cfg.ssm.d_inner == 32
    assert cfg.ssm.in_proj_dim == 32 + (32 + 2 * 2 * 16) + 4
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF if k in back} == {k: HF[k] for k in HF if k in back}
    assert set(HF) - set(back) <= {"model_type", "norm_eps"} | set(back)
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    assert sd["backbone.layers.0.mixer.conv1d.weight"].shape == (32 + 64, 1, 4)
    assert sd["backbone.layers.0.mixer.in_proj.weight"].shape == (cfg.ssm.in_proj_dim, 32)
    assert "backbone.layers.1.mixer.experts.4.up_proj.weight" in sd  # held: 4..7
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in sd
    assert sd["backbone.layers.1.mixer.gate.weight"].shape == (16, 32)
    for name in ("5.mixer.q_proj.weight", "5.norm.weight", "0.mixer.A_log",
                 "0.mixer.dt_bias", "0.mixer.D", "0.mixer.norm.weight",
                 "0.mixer.conv1d.bias", "1.mixer.gate.e_score_correction_bias",
                 "1.mixer.shared_experts.down_proj.weight"):
        assert f"backbone.layers.{name}" in sd
    assert "backbone.norm_f.weight" in sd and "lm_head.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        fam.config_from_hf(dict(HF, hybrid_override_pattern="MEX"))
    with pytest.raises(NotImplementedError, match="group-limited"):
        fam.config_from_hf(dict(HF, n_group=2))


# sha256 of str(jaxpr) of the backward pass, by remat mode: taken at the
# commit before that refactor (PR 31) and held until PR 37, which changed
# what the held experts trace to (loops over tiles each way) and took
# them again with nothing else changed; PR 43 took them again for the
# same reason (a chunk of 12,288 rows, the count of chunks carried beside
# the pairs and rows); PR 45 took them again because the router's
# statistics now count real tokens alone (`moe._router_stats` under the
# `token_mask` `forward` always hands it; with that mask ignored the
# jaxpr is the parent's but for where one scalar product stands)
AFMOE_JAXPR = {
    "full": "512e5f607d254a218606fc832d961554b5afefcbe2f8cfce72c8028812b901fb",
    "none": "c8709a5f20de825ba06f48941c9cf554207bf42038249e0a5aec6526e239a8eb",
    "mlp": "58e5cc75b6607f7fca46feb23cbbfd00f0aafca825bc643ffb9fa524b660f080",
}


@pytest.mark.parametrize("remat", sorted(AFMOE_JAXPR))
def test_a_stack_of_blocks_traces_the_program_it_did_before_segments(remat):
    """A leading dense block, then expert blocks `s s f s` in one scan
    with a switch around the attention call: the parameter tree and the
    jaxpr of the backward pass, against hashes taken at the parent
    commit (the one-kind stack's: test_layer_kinds.py)."""
    cfg = _afmoe_cfg()
    assert list(cfg.stack_paths().values()) == [
        (("layers",), (1, 2, 3, 4)), (("lead_layers",), (0,))]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 32), jnp.int32)

    def loss(p, ids):
        out, aux = forward(p, cfg, ids, jnp.ones_like(ids), jnp.tile(jnp.arange(32), (2, 1)),
                           attn_impl="reference", remat=remat, return_aux=True)
        return out.sum() + aux["pairs_held"]

    sha = lambda x: hashlib.sha256(str(x).encode()).hexdigest()
    tree = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    assert sha(tree) == "c6792ea57d440257f40e03591a32e3fbfcf9aa1335111e0ed8bb0b6200ff9c6c"
    assert sha(jax.make_jaxpr(jax.grad(loss))(params, ids)) == AFMOE_JAXPR[remat]
    assert AFMOE_HF["num_hidden_layers"] == 5


# sha256 of str(jaxpr) of the backward pass of a stack whose scanned layers
# walk their live bands (one row alone of 96 or 256 cells at bands of 16,
# `bands=True`, remat full): taken at the commit before PR 46 (869f2e1), where
# `forward.layer_body` held such a layer a second time (`looped`). "dense"
# is that commit's. The three with experts were taken again at PR 46 for
# ops that stand where the whole row has them and compute what they did:
# the reshape of the experts' sum from `[T, D]` to the row's `[1, T, D]`
# is `moe.moe_mlp`'s, before the layer's aux sums are added and not after
# them (afmoe 8994ddb7..., latent a5937252... at 869f2e1: no other line of
# the jaxpr differs); and the indexed layer's three sums join the carry in
# the layer's one sum with the experts', after the routed experts, not on
# their own after the attention call (indexed aa430367... at 869f2e1: those
# adds and the names after them). "afmoe" and "latent" were taken again at
# PR 48, which drops the rule "only inside a scan": the afmoe stack's
# leading dense layer, and the latent stack's leading dense layer and its
# prediction module's block, now run their two steps as stretches where
# they ran them as plain calls (PR 46: afmoe f23332a1..., latent
# 371185f2..., which this tree still gives with `banded` asking `scanned`
# again: nothing else of the program moved). "indexed" (every layer in one
# scan) and "dense" (one kind, one scan) have no layer outside a scan and
# are kept. "latent" was taken again at PR 62 (405ab41b... before it): the
# attention call's output reaches the `attn_out` stretch sequence-minor,
# `[1, H, v_dim, T]` (a transposition after the call, bands cut along the
# last axis: `band_loop.stretch(minor=(0,))`), and the output projection is
# the einsum `rhvt,hvd->rtd` over it; the other three, which hold every
# other line of `stretch` and `layer_body`, are as they were.
LOOPING_JAXPR = {
    "afmoe": "8579ee6c452e5a5184b9f3ceb217dfc27a19c3775d9e1d51cb2fa2b05899b852",
    "latent": "095fc3edb86906423a0d60e78405f984f4156f0d21f1a5ce18e1ac540e0d93b3",
    "indexed": "f8414a0388ce27ce05d56210d5cb72a45b59e3d2341255a35b89c0772c8d8774",
    "dense": "0154f30c09a2fff80afea6c6d94735dadef447ee9a2428d4a3193e05505657dc",
}


@pytest.mark.parametrize("stack", sorted(LOOPING_JAXPR))
def test_a_stack_that_walks_its_bands_traces_the_program_it_did_with_two_bodies(
        stack, monkeypatch):
    """The looping program of each kind that loops: the stack of blocks
    above (a leading dense layer that runs once, expert layers `s s f s`
    with a shared expert, gate and four norms in one scan), the latent
    stack with its leading dense layer and its prediction module, the
    indexed stack with its KL and a one-kind dense stack."""
    from areal_tpu.models.transformer import looping_layers
    from tests.model import test_indexed_stack, test_latent_stack

    ran = small_bands(monkeypatch)
    kw, T, want, bodies = {}, 256, 2, 1
    if stack == "afmoe":  # the leading layer's body, then the scan's
        cfg, T, want, bodies = _afmoe_cfg(), 96, 5, 2
    elif stack == "latent":  # and the module's block
        cfg, kw, want, bodies = test_latent_stack._cfg(), dict(mtp=True), 4, 3
    elif stack == "indexed":
        cfg, kw = test_indexed_stack._cfg(), dict(index_loss=True)
    else:
        kind = LayerKind(mlp="dense", window=None, rotary=True)
        hf = dict(AFMOE_HF, num_hidden_layers=2, num_dense_layers=2,
                  layer_types=["full_attention"] * 2)
        cfg = _afmoe_cfg(hf, layer_kinds=(kind, kind))
    assert looping_layers(cfg, 1, T, mtp="mtp" in kw) == want
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    ids = jnp.zeros((1, T), jnp.int32)

    def loss(p, ids):
        out, aux = forward(p, cfg, ids, jnp.ones_like(ids), jnp.arange(T)[None],
                           attn_impl="reference", remat="full", return_aux=True,
                           output="hidden", bands=True, **kw)
        return sum(o.sum() for o in jax.tree_util.tree_leaves(out)) + sum(
            a.sum() for a in jax.tree_util.tree_leaves(aux))

    sha = hashlib.sha256(str(jax.make_jaxpr(jax.grad(loss))(params, ids)).encode()).hexdigest()
    assert ran == ["_before_mixer", "_after_mixer"] * bodies
    assert sha == LOOPING_JAXPR[stack], sha


def test_seeded_weights_of_a_stack_of_blocks_are_the_ones_they_were():
    """`init_params` draws an accepted configuration's weights from the
    same keys as before: a checksum taken at the parent commit."""
    cfg = _afmoe_cfg()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(4))
    total = sum(float(jnp.abs(x).sum()) for x in jax.tree_util.tree_leaves(params))
    np.testing.assert_allclose(total, 9556.762916564941, rtol=1e-7)


@pytest.mark.parametrize("where", ["prefill", "decode_step", "paged_decode_step",
                                   "ServingEngine"])
def test_the_cache_paths_name_the_recurrent_state_they_lack(where):
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="recurrent state beside the KV pages"):
        cfg.require_plain_stack(where)
    with pytest.raises(NotImplementedError, match="snapshot.*squared-ReLU"):
        cfg.require_plain_stack(where)


def test_what_a_stack_of_one_part_layers_cannot_run_is_refused_by_mechanism():
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh

    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv.*recurrent state"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    mesh = make_mesh(MeshSpec(seq=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="hand-over of the state"):
        forward(params, cfg, ids, seg, pos, attn_impl="ring", mesh=mesh)
    # the expert-parallel dropless path runs gated experts only
    hf, whole, mlp, h = _expert_layer_inputs(n_experts=8)
    whole = dataclasses.replace(whole, moe=dataclasses.replace(
        whole.moe, score_func="softmax", n_shared_experts=0))
    mesh = make_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="plain experts"):
        moe_lib.moe_mlp(jnp.zeros((2, 16, 32)), {k: v for k, v in mlp.items() if k != "shared"},
                        whole, jnp.float32, mesh=mesh)
    with pytest.raises(ValueError, match="needs TransformerConfig.ssm"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(mlp=None, mixer="ssm"),))
    with pytest.raises(ValueError, match="a mixer or an MLP"):
        LayerKind(mlp=None, mixer=None)
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mlp="moe", mixer=None, window=8)


def test_a_mesh_of_two_runs_the_state_space_and_dense_layers():
    """`parallel/sharding.py` gives the new leaves a spec (the mixer's two
    projections ZeRO-sharded on the hidden dim, the rest replicated), and
    the forward pass on an fsdp mesh of 2 is the single device's."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh
    from areal_tpu.parallel.sharding import fitted_param_spec, shard_params
    from jax.sharding import PartitionSpec as P

    hf = dict(HF, num_hidden_layers=4, hybrid_override_pattern="M-*M")
    cfg = _cfg(hf)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    sizes = dict(mesh.shape)
    spec = lambda name: fitted_param_spec(
        f"stacks/ssm/ssm/{name}", params["stacks"]["ssm"]["ssm"][name].shape, sizes)
    assert spec("in_proj") == P(None, "fsdp", None) and spec("out_proj") == P(None, None, "fsdp")
    for name in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "norm"):
        assert all(e is None for e in spec(name)), name
    ids, seg, pos, _ = _packed()
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, ids, seg, pos, attn_impl="reference")
        got = forward(shard_params(params, mesh), cfg, ids, seg, pos,
                      attn_impl="reference", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
