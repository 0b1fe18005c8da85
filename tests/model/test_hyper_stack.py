"""A stack of several residual streams a token (`model_type` xing4_0 at
toy widths): manifold-constrained hyper-connections around latent
attention under a YaRN table, a leading dense layer and sigmoid-routed
expert layers holding a share. The program against the plain reference
(`benchmark/reference/xing4_0.py`), whole row and through the band loop,
a packed row against its sequences alone, H_res doubly stochastic, the
stream kernels against their plain forms, the YaRN table against its
formula, the shares of the expert layer, the family's round trip and
refusals. Float32 on the CPU."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models import transformer as tf
from areal_tpu.models.config import HyperConnConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from areal_tpu.ops import hyper_conn
from areal_tpu.ops.loss import fused_next_token_logprobs
from areal_tpu.ops.pallas import stream_mix
from areal_tpu.ops.rotary import rotary_inv_freq, yarn_mscale
from benchmark.reference import xing4_0 as ref

YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=32, type="yarn")
HF = dict(model_type="xing4_0", num_hidden_layers=3, hidden_size=64,
          num_attention_heads=4, num_key_value_heads=4,
          q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
          v_head_dim=16, intermediate_size=96, vocab_size=64,
          moe_intermediate_size=16, n_routed_experts=4, num_experts_routed=16,
          experts_held_first=4, num_experts_per_tok=4, n_shared_experts=1,
          first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
          norm_topk_prob=True, routed_scaling_factor=2, scoring_func="sigmoid",
          topk_method="noaux_tc", num_nextn_predict_layers=0, rms_norm_eps=1e-6,
          rope_theta=10000, rope_scaling=YARN, tie_word_embeddings=False,
          max_position_embeddings=2048, attention_bias=False, hidden_act="silu",
          hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
          mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
HC2 = dict(HF, hc_mult=2)
WIDTHS = pytest.mark.parametrize("hf", [HF, HC2], ids=["hc4", "hc2"])


def _cfg(hf=HF, **over):
    hf = dict(hf, **over)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    return cfg


def _params(cfg, seed=0):
    """Seeded weights with the norms, the gates and the selection bias
    moved off their initial values, so that each matters."""
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return treedef.unflatten([a + 0.1 * jax.random.normal(k, a.shape) if a.ndim <= 2
                              else a for a, k in zip(leaves, keys)])


def _row(lens, T, seed=1, vocab=64):
    """One packed row of sequences of `lens`, padded to T."""
    ids = np.zeros(T, np.int32)
    seg, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    rng, o = np.random.default_rng(seed), 0
    for j, l in enumerate(lens):
        ids[o:o + l] = rng.integers(0, vocab, l)
        seg[o:o + l], pos[o:o + l] = j + 1, np.arange(l)
        o += l
    return tuple(jnp.asarray(a)[None] for a in (ids, seg, pos))


def _logprobs(params, cfg, ids, seg, pos, **kw):
    hidden = forward(params, cfg, ids, seg, pos, output="hidden", **kw)
    return fused_next_token_logprobs(hidden, params["head"]["weight"], ids, seg)[0]


def _flat(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# The program against the plain reference
# ---------------------------------------------------------------------------


@WIDTHS
def test_logprobs_are_the_plain_references(hf):
    cfg = _cfg(hf)
    assert [s.repeats for s in cfg.segments()] == [1, 2]
    assert [k.parts for k in cfg.kinds()][1] == "latentattention+moe"
    params = _params(cfg)
    n = 40
    ids, seg, pos = _row([n], 48)
    lp = jax.jit(lambda p: _logprobs(p, cfg, ids, seg, pos, remat="full"))(params)
    want = ref.next_token_logprobs(params, hf, np.asarray(ids[0, :n]), pad_to=256)
    np.testing.assert_allclose(lp[: n - 1], want, atol=3e-5)
    assert float(jnp.abs(lp[n - 1:]).max()) == 0


def _assert_gradients_are_the_references(hf, remat, **kw):
    """The policy's gradient: of the sum of the logprobs, every leaf
    reached (the selection bias, chosen on under `stop_gradient`, but for)."""
    cfg = _cfg(hf)
    params = _params(cfg)
    n, T = 40, 256
    ids, seg, pos = _row([n], T)
    small = {k: hf[k] for k in ref._KEYS}

    def want_fn(p):
        with jax.default_matmul_precision("highest"):
            h = ref._stack(p, ids[0], small)
            return ref._head_logprobs(h, p["head"]["weight"], jnp.roll(ids[0], -1))[: n - 1].sum()

    got_fn = lambda p: _logprobs(p, cfg, ids, seg, pos, remat=remat, **kw).sum()
    want, got = (_flat(jax.jit(jax.grad(fn))(params)) for fn in (want_fn, got_fn))
    assert want.keys() == got.keys()
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        if "expert_bias" in name:
            assert scale == 0 and float(jnp.abs(got[name]).max()) == 0
            continue
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale + 1e-6,
                                   err_msg=name)


def test_gradients_are_the_plain_references():
    """Two streams, the whole row; four, through the band loop, below."""
    _assert_gradients_are_the_references(HC2, "full")


def test_a_half_empty_row_of_streams_walks_its_live_bands(monkeypatch):
    """One row alone, 40 tokens in 256 cells at bands of 16: the two
    scanned expert layers run their two stretches over three bands (the
    streams cut band by band as any input, the coefficients handed from
    the first stretch to the second), thirteen bands stay empty and read
    zeros, the dense layer, alone among several streams
    (`transformer._lone_layer_loops`), runs the whole row; logprobs and
    every gradient are the plain reference's."""
    from tests.model.test_layer_kinds import small_bands

    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    ran = small_bands(monkeypatch)
    cfg = _cfg()
    params = _params(cfg)
    n = 40
    ids, seg, pos = _row([n], 256)
    hidden, aux = jax.jit(lambda p: forward(
        p, cfg, ids, seg, pos, output="hidden", return_aux=True, remat="full", bands=True))(params)
    assert looping_layers(cfg, 1, 256) == 2 and ran == ["_before_mixer", "_after_mixer"]
    lp = fused_next_token_logprobs(hidden, params["head"]["weight"], ids, seg)[0]
    np.testing.assert_allclose(lp[: n - 1], ref.next_token_logprobs(
        params, HF, np.asarray(ids[0, :n]), pad_to=256), atol=3e-5)
    assert 0 < float(aux["mhc_res_err"]) / (6 * n) < 1e-4
    _assert_gradients_are_the_references(HF, "full", bands=True)


LENS, ROW = [21, 1], 32


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_a_packed_row_of_streams_is_each_of_its_sequences_alone(what, monkeypatch):
    """Every stream step is a token's own, so the streams cross no
    sequence boundary and the padding adds nothing: to 2e-5."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row(LENS, ROW)
    offs = np.concatenate([[0], np.cumsum(LENS)])
    scored = np.concatenate([np.arange(offs[j], offs[j] + l - 1) for j, l in enumerate(LENS)])
    w = jax.random.normal(jax.random.PRNGKey(5), (ROW,))

    def packed(p):
        return _logprobs(p, cfg, ids, seg, pos, remat="full")[scored]

    def alone(p):
        return jnp.concatenate([_logprobs(
            p, cfg, ids[:, o:o + l], jnp.ones((1, l), jnp.int32), pos[:, o:o + l])[: l - 1]
            for o, l in zip(offs, LENS)])

    if what == "values":
        np.testing.assert_allclose(jax.jit(packed)(params), jax.jit(alone)(params), atol=2e-5)
        return
    g_packed, g_alone = (jax.jit(jax.grad(lambda p, fn=fn: (fn(p) * w[scored]).sum()))(params)
                         for fn in (packed, alone))
    for a, b in zip(jax.tree_util.tree_leaves(g_packed), jax.tree_util.tree_leaves(g_alone)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))


def test_one_stream_reads_and_writes_as_a_plain_residual():
    """`cfg.hyper` None: `_hc_read` hands x back and no coefficients, and
    `_hc_write` is `x + y`: the four places compute what they computed."""
    cfg = TransformerConfig()
    st = tf._Stretch(cfg, LayerKind(), jnp.float32)
    x, y = jnp.ones((1, 4, 64)), jnp.full((1, 4, 64), 2.0)
    h, coefs = tf._hc_read(st, None, x)
    assert h is x and coefs == ()
    assert str(jax.make_jaxpr(lambda x, y: tf._hc_write(st, x, y, *coefs))(x, y)) == str(
        jax.make_jaxpr(lambda x, y: x + y)(x, y))


def test_the_seeded_embedding_is_a_tokens_own_under_streams():
    """The streams start as copies of the embedding: it is the one-stream
    stack's draw at a scale of 2, key for key (`_INDEXED_EMBED_SCALE`), so
    that a router reads the token before its sequence's mean; the head,
    the router and the experts are drawn as they were."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plain = init_params(dataclasses.replace(cfg, hyper=None), jax.random.PRNGKey(0))
    emb = params["embedding"]["weight"]
    assert 1.8 < float(jnp.std(emb)) < 2.2
    np.testing.assert_allclose(emb, 100.0 * plain["embedding"]["weight"], rtol=1e-5)
    np.testing.assert_array_equal(params["head"]["weight"], plain["head"]["weight"])
    for name in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(params["layers"]["mlp"][name],
                                      plain["layers"]["mlp"][name])


# ---------------------------------------------------------------------------
# The coefficients
# ---------------------------------------------------------------------------


def test_h_res_is_doubly_stochastic_after_twenty_iterations_and_not_after_one():
    hy = HyperConnConfig()
    cfg = _cfg()
    hp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["layers"]["hc1"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 4 * 64))
    sums = lambda h: np.concatenate([np.asarray(h.sum(-1)).ravel(), np.asarray(h.sum(-2)).ravel()])
    _, _, h_res = hyper_conn.coefficients(hp, x, hy, 1e-6)
    assert h_res.shape == (1, 96, 4, 4) and float(h_res.min()) > 0
    assert np.abs(sums(h_res) - 1).max() < 1e-4
    assert float(hyper_conn.res_err(h_res).max()) < 1e-4
    _, _, once = hyper_conn.coefficients(hp, x, dataclasses.replace(hy, sinkhorn_iters=1), 1e-6)
    assert np.abs(sums(once) - 1).max() > 1e-2
    assert float(hyper_conn.res_err(once).mean()) > 1e-2
    # the coefficients differ by token, and are the reference's
    assert float(jnp.std(h_res[0, :, 0, 0])) > 0.05
    want = ref.hyper_coefficients(x[0].reshape(96, 4, 64), hp, HF)
    for got, w in zip(hyper_conn.coefficients(hp, x, hy, 1e-6), want):
        np.testing.assert_allclose(got[0], w, atol=1e-5)
    # an all-zero token (a cell past the live bands): finite, from b alone
    zero = hyper_conn.coefficients(hp, jnp.zeros((1, 2, 256)), hy, 1e-6)
    assert all(bool(jnp.isfinite(a).all()) for a in zero)


def test_the_clamp_holds_before_the_exponential():
    hy = HyperConnConfig(n=2, clamp=(-1.0, 1.0), sinkhorn_iters=1, eps=0.0)
    hp = dict(phi=jnp.zeros((8, 8)), a=jnp.ones(3),
              b=jnp.asarray([0, 0, 0, 0, 50.0, -50.0, 0.0, 0.0]))
    _, _, h_res = hyper_conn.coefficients(hp, jnp.ones((1, 8)), hy, 1e-6)
    m0 = jnp.exp(jnp.asarray([[1.0, -1.0], [0.0, 0.0]]))
    np.testing.assert_allclose(h_res[0], ref.sinkhorn(m0[None], 1, 0.0)[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# The stream kernels against their plain forms (interpret mode)
# ---------------------------------------------------------------------------


def _mix_case(use, seed=0, tokens=128, d=128, n=4, dtype=jnp.float32):
    ka, kx, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (1, tokens, n * d), dtype)
    if use == "read":
        return jax.random.uniform(ka, (1, tokens, 1, n)), (x,)
    return (jax.random.uniform(ka, (1, tokens, n, n + 1)),
            (x, jax.random.normal(ky, (1, tokens, d), dtype)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("use", ["read", "write"])
def test_mhc_mix_is_its_plain_form_forward_and_every_gradient(use, dtype):
    a, ins = _mix_case(use, dtype=dtype)
    assert stream_mix.kernel_ok(128, 128) and not stream_mix.kernel_ok(100, 128)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 128, a.shape[-2] * 128))

    def total(interpret):
        return lambda a, ins: (stream_mix.mhc_mix(a, ins, False, interpret)
                               .astype(jnp.float32) * w).sum()

    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    got, want = (stream_mix.mhc_mix(a, ins, False, i) for i in (True, False))
    assert got.dtype == dtype and got.shape == (1, 128, a.shape[-2] * 128)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=tol)
    # the einsum it stands for
    x = jnp.concatenate([i.reshape(1, 128, -1, 128) for i in ins], axis=2).astype(jnp.float32)
    np.testing.assert_allclose(want.astype(jnp.float32).reshape(1, 128, -1, 128),
                               jnp.einsum("rtik,rtkd->rtid", a, x), atol=tol)
    g_got, g_want = (jax.grad(total(i), (0, 1))(a, ins) for i in (True, False))
    for g, gw in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
        assert g.shape == gw.shape and g.dtype == gw.dtype
        np.testing.assert_allclose(g.astype(jnp.float32), gw.astype(jnp.float32),
                                   atol=tol * max(1.0, float(jnp.abs(gw).max())))


@pytest.mark.parametrize("use", ["read", "write"])
def test_mhc_coef_grad_is_its_plain_form(use):
    a, ins = _mix_case(use, seed=3)
    dout = jax.random.normal(jax.random.PRNGKey(4), (1, 128, a.shape[-2] * 128))
    got, want = (stream_mix.mhc_coef_grad((dout,), ins, 128, False, i) for i in (True, False))
    assert got.shape == a.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    x = jnp.concatenate([i.reshape(1, 128, -1, 128) for i in ins], axis=2)
    np.testing.assert_allclose(
        want, jnp.einsum("rtid,rtkd->rtik", dout.reshape(1, 128, -1, 128), x), atol=1e-4)


def test_the_sinkhorn_kernels_are_the_plain_iterations_forward_and_backward():
    from areal_tpu.ops.pallas import sinkhorn as sk

    m = jnp.exp(2.0 * jax.random.normal(jax.random.PRNGKey(7), (1, 1024, 4, 4)))
    w = jax.random.normal(jax.random.PRNGKey(8), m.shape)
    assert sk.kernel_ok(1024) and not sk.kernel_ok(1000)
    plain = lambda m: hyper_conn.sinkhorn(m, 20, 1e-6, kernel=False)
    kernel = lambda m: sk.sinkhorn(m, 20, 1e-6, interpret=True)
    np.testing.assert_allclose(kernel(m), plain(m), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(plain(m), ref.sinkhorn(m[0], 20, 1e-6)[None], rtol=1e-5)
    g_kernel, g_plain = (jax.grad(lambda m, f=f: (f(m) * w).sum())(m) for f in (kernel, plain))
    np.testing.assert_allclose(g_kernel, g_plain, rtol=2e-4, atol=1e-6)
    # a shape the kernels do not take, and the CPU, run the plain form
    odd = m[:, :1000]
    assert str(jax.make_jaxpr(lambda m: hyper_conn.sinkhorn(m, 20, 1e-6))(odd)) == str(
        jax.make_jaxpr(lambda m: hyper_conn.sinkhorn(m, 20, 1e-6, kernel=False))(odd))


def test_the_references_sinkhorn_loop_is_the_iterations_one_after_the_other():
    """The reference's `lax.fori_loop` (one step to compile, not twenty) is
    the unrolled arithmetic, and differentiates as the CPU tests of the
    policy's gradient need."""
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (5, 4, 4)))
    want = m
    for _ in range(20):
        want = want / (want.sum(-1, keepdims=True) + 1e-6)
        want = want / (want.sum(-2, keepdims=True) + 1e-6)
    np.testing.assert_allclose(ref.sinkhorn(m, 20, 1e-6), want, rtol=1e-5)
    assert float(jnp.abs(ref.sinkhorn(m, 1, 1e-6) - want).max()) > 1e-3
    loss = lambda m, f: (f(m) ** 2).sum()

    def unrolled(m):
        for _ in range(20):
            m = m / (m.sum(-1, keepdims=True) + 1e-6)
            m = m / (m.sum(-2, keepdims=True) + 1e-6)
        return m

    np.testing.assert_allclose(jax.grad(loss)(m, lambda m: ref.sinkhorn(m, 20, 1e-6)),
                               jax.grad(loss)(m, unrolled), rtol=1e-4, atol=1e-7)


@WIDTHS
def test_the_reference_scans_a_stack_as_its_layers_one_by_one(hf):
    """`_stack` runs each of the program's stacks (the leading dense
    layer's, the expert layers') under one `lax.scan`: the same streams
    as `_layer` applied to each layer's slice in order."""
    cfg = _cfg(hf)
    params = _params(cfg)
    small = {k: hf[k] for k in ref._KEYS}
    ids = _row([40], 256)[0][0]
    x = params["embedding"]["weight"][ids]
    X = jnp.repeat(x[:, None, :], hf["hc_mult"], axis=1)
    n_layers = 0
    with jax.default_matmul_precision("highest"):
        for stack in ref._stacks_in_order(params):
            for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
                X = ref._layer(X, jax.tree_util.tree_map(lambda a: a[i], stack), small)
                n_layers += 1
        want = ref._rms(jnp.sum(X, axis=1), params["final_norm"]["weight"], hf["rms_norm_eps"])
        got = ref._stack(params, ids, small)
    assert n_layers == hf["num_hidden_layers"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="depth"):
        ref._stack(params, ids, dict(small, num_hidden_layers=4))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_the_yarn_table_is_the_formula_at_the_published_numbers():
    """d = 64, base 10000, factor 64 over 4,096, beta 32 and 1: the
    frequencies that turn over 32 times in 4,096 positions keep theirs
    (i < 10), those under once are divided by 64 (i >= 23), a linear
    ramp between; the softmax scale's factor is (0.1 ln 64 + 1)^2."""
    rs = dict(beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1,
              original_max_position_embeddings=4096)
    got = rotary_inv_freq(64, 10000.0, 64.0, "yarn", rs)
    plain = rotary_inv_freq(64, 10000.0)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, plain / 64 * ramp + plain * (1 - ramp), rtol=1e-6)
    # spot values: unscaled, mid-ramp, fully scaled
    np.testing.assert_allclose(got[[0, 9, 10]], plain[[0, 9, 10]], rtol=1e-6)
    np.testing.assert_allclose(got[16] / plain[16], 1 - 6 / 13 * (1 - 1 / 64), rtol=1e-6)
    np.testing.assert_allclose(got[16], 10000 ** -0.5 * 0.5456730769, rtol=1e-6)
    np.testing.assert_allclose(got[[23, 31]], plain[[23, 31]] / 64, rtol=1e-6)
    np.testing.assert_allclose(got[31], 10000 ** (-62 / 64) / 64, rtol=1e-6)
    ref_inv, amp = ref.yarn_inv_freq(64, 10000, dict(rs, factor=64))
    np.testing.assert_allclose(got, ref_inv, rtol=1e-6)
    assert amp == 1.0
    assert abs(yarn_mscale(64, 1) ** 2 - 2.0047397) < 1e-6 and yarn_mscale(1.0) == 1.0
    cfg = family_from_hf_config(HF).config_from_hf(dict(
        HF, qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling=dict(rs, factor=64, type="yarn")))
    assert abs(cfg.mla.softmax_scale - 192 ** -0.5 * 2.0047397) < 1e-7
    assert abs(ref.softmax_scale(dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                                      rope_scaling=dict(rs, factor=64))) - cfg.mla.softmax_scale) < 1e-9
    with pytest.raises(NotImplementedError, match="dynamic"):
        rotary_inv_freq(64, 10000.0, 2.0, "dynamic")


def test_the_table_and_the_scale_reach_the_logprobs():
    """Left unscaled, or the softmax scale without mscale^2, the toy
    stack's logprobs move: both are in the program."""
    cfg = _cfg()
    params = _params(cfg)
    n = 100
    ids, seg, pos = _row([n], 128)
    lp = jax.jit(lambda p: _logprobs(p, cfg, ids, seg, pos))(params)
    plain_table = dataclasses.replace(cfg, rotary_scaling=None, rotary_scaling_type=None)
    plain_scale = dataclasses.replace(
        cfg, mla=dataclasses.replace(cfg.mla, softmax_scale_factor=1.0))
    assert plain_scale.mla.softmax_scale is None
    for other in (plain_table, plain_scale):
        moved = jax.jit(lambda p, c=other: _logprobs(p, c, ids, seg, pos))(params)
        assert float(jnp.abs(moved - lp)[: n - 1].max()) > 1e-3


# ---------------------------------------------------------------------------
# The expert layer's shares
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 8 shares of 8
    experts of 64, the shared expert counted once, add up to what the
    reference gives for the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_hidden_layers=2, n_routed_experts=64, num_experts_routed=64,
              experts_held_first=0)
    cfg = _cfg(hf)
    assert cfg.moe.experts_held is None and cfg.moe.num_experts == 64 and cfg.moe.top_k == 4
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, 3)["layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(8):
            held = (8 * share, 8)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 8] if k in ("w_gate", "w_up", "w_down") else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            part = ref.expert_layer(h, mp, dict(hf, n_routed_experts=8,
                                                experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    assert pairs == 96 * 4  # every (token, expert) pair is held by exactly one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)


# ---------------------------------------------------------------------------
# The family
# ---------------------------------------------------------------------------


def test_config_round_trip_and_what_the_family_reads():
    fam = family_from_hf_config(HF)
    assert fam is get_family("xing4_0")
    cfg = fam.config_from_hf(dict(HF))
    assert cfg.hyper == HyperConnConfig(n=4, sinkhorn_iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert cfg.rotary_scaling_type == "yarn" and cfg.rotary_scaling == 64.0
    assert cfg.rotary_interleaved and cfg.rotary_dim == 8 and cfg.mtp is None
    assert cfg.moe.experts_held == (4, 4) and cfg.moe.num_experts == 16
    assert all(k.latent for k in cfg.kinds())
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = fam.config_from_hf(back)
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    # one stream: the family is DeepSeek-V3's shape, and may have its module
    one = fam.config_from_hf(dict(HF, hc_mult=1, num_nextn_predict_layers=1))
    assert one.hyper is None and one.mtp is not None
    assert fam.config_to_hf(one)["hc_mult"] == 1


def test_a_checkpoint_round_trips_under_its_names():
    cfg = _cfg()
    fam = get_family("xing4_0")
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    assert sd["model.layers.0.hc_attn.phi"].shape == (4 * 64, 24)
    assert sd["model.layers.2.hc_mlp.a"].shape == (3,)
    assert sd["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"].shape == (16 + 8, 64)
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in sd  # the first held expert
    assert not any(".experts.0." in k for k in sd)
    back = fam.params_from_hf(sd, cfg)
    want, got = _flat(params), _flat(back)
    assert want.keys() == got.keys()
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("over,named", [
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(rope_scaling=dict(YARN, type="longrope")), "longrope"),
    (dict(rope_scaling=dict(YARN, mscale_all_dim=0.5)), "mscale_all_dim"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_family_refuses_by_name_what_it_does_not_compute(over, named):
    with pytest.raises(NotImplementedError, match=named):
        family_from_hf_config(HF).config_from_hf(dict(HF, **over))


def test_the_sibling_family_takes_a_yarn_table_through_the_same_code():
    from tests.model.test_latent_stack import HF as JOYAI

    cfg = family_from_hf_config(JOYAI).config_from_hf(dict(JOYAI, rope_scaling=YARN))
    assert cfg.rotary_scaling_type == "yarn" and cfg.mla.softmax_scale_factor > 2
    assert get_family("joyai_llm_flash").config_to_hf(cfg)["rope_scaling"] == dict(YARN, factor=64.0)
    assert family_from_hf_config(JOYAI).config_from_hf(dict(JOYAI)).mla.softmax_scale is None


def test_the_config_refuses_streams_where_no_step_reads_them():
    hy = HyperConnConfig(n=2)
    with pytest.raises(NotImplementedError, match="prediction module"):
        _cfg(hc_mult=1, num_nextn_predict_layers=1).__class__(
            **{**dataclasses.asdict(_cfg(hc_mult=1, num_nextn_predict_layers=1)), "hyper": hy,
               "layer_kinds": None})
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        TransformerConfig(hyper=hy, norm_type="layer")
    cfg = TransformerConfig(hyper=hy)
    assert [k.parts for k in cfg.kinds()] == ["attention+dense"] * 2 and not cfg.one_kind
    with pytest.raises(NotImplementedError, match="residual streams"):
        cfg.require_plain_stack("the cache path")
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="residual streams"):
        forward(init_params(cfg, jax.random.PRNGKey(0)), cfg, ids, ids + 1, ids, return_kv=True)
