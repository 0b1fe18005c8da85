"""A SambaY stack (`model_type` phi4flash at toy widths): Mamba-1
selective scans over packed rows, window and full differential
attention, and a cross-decoder whose layers read one layer's k, v and
scan output. The program against the plain reference
(`benchmark/reference/phi4flash.py`: the recurrence token by token, two
dense softmaxes), the scan's two forms against the recurrence and each
other, a packed row against its sequences alone, the kept tensors saved
once, the family's rule and round trip, and what the cache paths lack.
Float32 on the CPU."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import LayerKind, SSMConfig, TransformerConfig, segments_of
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import (
    DIFF_LAMBDAS, _diff_combine, _diff_split, diff_lambda_init, forward, init_params,
)
from areal_tpu.ops import selective_scan as ss
from areal_tpu.ops.attention import reference_packed_attention
from benchmark.reference import phi4flash as ref

HF = dict(model_type="phi4flash", num_hidden_layers=8, hidden_size=32,
          num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
          vocab_size=64, sliding_window=8, layer_norm_eps=1e-5, mb_per_layer=2,
          tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
          hidden_act="silu", max_position_embeddings=512,
          mamba_dt_rank=2, scan_chunk_size=16)


def _cfg(hf=HF, **over):
    hf = dict(hf, **over)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    return cfg


def _params(cfg, seed=0):
    """Seeded weights with the biases, norms and lambda vectors moved off
    their initial values, so that each matters."""
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
    """[T-1]: log p(ids[t+1] | ..) of the row's first T-1 positions."""
    lp = jax.nn.log_softmax(forward(params, cfg, ids, seg, pos, **kw)[0], -1)
    return jnp.take_along_axis(lp[:-1], ids[0, 1:, None], -1)[:, 0]


# ---------------------------------------------------------------------------
# The program against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("remat", ["full", "none"])
@pytest.mark.parametrize("n_layers", [8, 16])
def test_logprobs_are_the_plain_references(n_layers, remat):
    """L = 16 has (scan, window attention) x 4 and (memory unit,
    cross-attention) x 3 as scans; L = 8 runs layer by layer."""
    hf = dict(HF, num_hidden_layers=n_layers)
    cfg = _cfg(hf)
    assert (len(cfg.segments()) == 4) == (n_layers == 16)
    params = _params(cfg)
    n = 40
    ids, seg, pos = _row([n], 48)
    want = ref.next_token_logprobs(params, hf, np.asarray(ids[0, :n]), pad_to=256)
    got = _logprobs(params, cfg, ids, seg, pos, remat=remat)[: n - 1]
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("n_layers", [8, 16])
def test_gradients_are_the_plain_references(n_layers):
    hf = dict(HF, num_hidden_layers=n_layers)
    cfg = _cfg(hf)
    params = _params(cfg)
    n, T = 40, 256
    ids, seg, pos = _row([n], T)
    small = {k: hf[k] for k in ref._KEYS}
    want = jax.grad(lambda p: ref._forward(p, ids[0], small)[: n - 1].sum())(params)
    got = jax.grad(lambda p: _logprobs(p, cfg, ids, seg, pos, remat="full")[: n - 1].sum())(params)
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    want, got = flat(want), flat(got)
    assert want.keys() == got.keys()
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name  # every leaf is reached
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale + 1e-6,
                                   err_msg=name)


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_a_packed_row_is_each_of_its_sequences_alone(what):
    """The state and the convolution start afresh at a sequence start,
    attention stays inside a sequence, and the padding adds nothing: to
    2e-5, values and gradients. Sequence starts fall inside a chunk of
    16 (at 21 and 50) and the window of 8 is shorter than every sequence."""
    cfg = _cfg()
    params = _params(cfg)
    lens, T = [21, 29, 17], 80
    ids, seg, pos = _row(lens, T)
    weights = jax.random.normal(jax.random.PRNGKey(5), (T - 1,))
    offs = np.concatenate([[0], np.cumsum(lens)])

    def packed(p):
        return _logprobs(p, cfg, ids, seg, pos, remat="full")

    def alone(p):
        out = []
        for j, l in enumerate(lens):
            o = offs[j]
            one = (ids[:, o:o + l], jnp.ones((1, l), jnp.int32), pos[:, o:o + l])
            out.append(_logprobs(p, cfg, *one)[: l - 1])
        return out

    scored = np.concatenate([np.arange(offs[j], offs[j] + l - 1) for j, l in enumerate(lens)])
    if what == "values":
        np.testing.assert_allclose(packed(params)[scored], jnp.concatenate(alone(params)),
                                   atol=2e-5)
        return
    w = weights[scored]
    g_packed = jax.grad(lambda p: (packed(p)[scored] * w).sum())(params)
    g_alone = jax.grad(lambda p: (jnp.concatenate(alone(p)) * w).sum())(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_packed), jax.tree_util.tree_leaves(g_alone)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))


def test_what_the_padding_holds_reaches_nothing():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([30], 48)
    other = ids.at[0, 30:].set(7)
    np.testing.assert_array_equal(_logprobs(params, cfg, ids, seg, pos)[:29],
                                  _logprobs(params, cfg, other, seg, pos)[:29])


# ---------------------------------------------------------------------------
# The selective scan: recurrence, plain form, kernel
# ---------------------------------------------------------------------------


def _scan_inputs(R, T, Dn, N, lens, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    seg = np.zeros((R, T), np.int32)
    for r in range(R):
        o = 0
        for j, l in enumerate(lens[r]):
            seg[r, o:o + l] = j + 1
            o += l
    seg = jnp.asarray(seg)
    valid = (seg > 0)[..., None]
    x = jnp.where(valid, jax.random.normal(k[0], (R, T, Dn)), 0)
    dt = jnp.where(valid, jax.nn.softplus(jax.random.normal(k[1], (R, T, Dn)) - 2), 0)
    A = -jnp.exp(jax.random.uniform(k[2], (Dn, N)) * 2.7)
    B = jnp.where(valid, jax.random.normal(k[3], (R, T, N)), 0)
    C = jnp.where(valid, jax.random.normal(k[4], (R, T, N)), 0)
    return (x, dt, A, B, C), seg, jax.random.normal(k[5], (R, T, Dn))


def _recurrence(x, dt, A, B, C, seg):
    """Token by token, a row at a time: the reference's recurrence with
    the state dropped before a sequence's first token."""
    keep = ss.sequence_keeps(seg)

    def row(x, dt, B, C, keep):
        def step(S, inp):
            xt, dtt, Bt, Ct, kt = inp
            S = jnp.exp(dtt[:, None] * A) * kt * S + (dtt * xt)[:, None] * Bt[None]
            return S, S @ Ct
        return jax.lax.scan(step, jnp.zeros(A.shape), (x, dt, B, C, keep))[1]

    return jax.vmap(row)(x, dt, B, C, keep)


def _close(got, want, tol=2e-5):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=tol * max(1.0, float(jnp.abs(w).max())))


@pytest.mark.parametrize("T,chunk", [(96, 32), (96, 16), (90, 64)],
                         ids=["starts_inside_chunks", "short_chunks", "row_no_multiple"])
def test_the_plain_scan_is_the_recurrence(T, chunk):
    a, seg, w = _scan_inputs(2, T, 24, 8, [[37, 33, 20], [50, 40]])
    _close(ss.plain_scan(*a, seg, chunk), _recurrence(*a, seg))
    grad = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(), (0, 1, 2, 3, 4))(*a)
    _close(grad(lambda *a: ss.plain_scan(*a, seg, chunk)),
           grad(lambda *a: _recurrence(*a, seg)))
    # one row's reference recurrence, unpacked: the same numbers
    one = ref.recurrence(a[0][0, :37], a[1][0, :37], a[2], a[3][0, :37], a[4][0, :37])
    _close(ss.plain_scan(*a, seg, chunk)[0, :37], one)


@pytest.mark.parametrize("what", ["forward", "backward"])
@pytest.mark.parametrize("T,chunk,lens", [
    (96, 32, [[37, 33, 20], [50, 46]]),  # starts at 37, 70, 50: inside chunks
    (128, 64, [[100], [30, 30]]),  # a tail of padding; a chunk of padding alone
    (80, 32, [[80], [41, 39]]),  # the row is no multiple of the chunk
], ids=["starts_inside_chunks", "padding", "row_no_multiple"])
def test_the_kernel_in_interpret_mode_is_the_plain_form(T, chunk, lens, what):
    a, seg, w = _scan_inputs(2, T, 256, 16, lens)
    kern = lambda *a: ss.kernel_scan(*a, seg, chunk, interpret=True)
    plain = lambda *a: ss.plain_scan(*a, seg, chunk)
    if what == "forward":
        _close(kern(*a), plain(*a))
        return
    grad = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(), (0, 1, 2, 3, 4))(*a)
    _close(grad(kern), grad(plain))


def test_the_scan_picks_its_form_by_what_it_can_observe():
    assert ss.resolve_scan_impl("auto", 5120, 16, 128) == "plain"  # no TPU here
    assert ss.resolve_scan_impl("kernel", 5120, 16, 128) == "kernel"
    assert ss.kernel_ok(5120, 16, 128) and not ss.kernel_ok(5120, 16, 24)
    assert not ss.kernel_ok(96, 16, 128) and not ss.kernel_ok(5120, 12, 128)
    assert ss._block_of(5120) == 512 and ss._block_of(384) == 128


# ---------------------------------------------------------------------------
# Differential attention
# ---------------------------------------------------------------------------


def _two_softmaxes(q, k, v, lp, l0, window, second=1.0):
    """q [T, Hq, hd], k and v [T, Hkv, hd]: two dense softmaxes a pair of
    heads under an explicit mask, then the combine; [T, Hq / 2, 2 hd]."""
    T, hq, hd = q.shape
    hkv = k.shape[1]
    per = (hq // 2) // (hkv // 2)
    q = q.reshape(T, hq // 2, 2, hd)
    k = jnp.repeat(k.reshape(T, hkv // 2, 2, hd), per, axis=1)
    v = jnp.repeat(v.reshape(T, hkv // 2, 2 * hd), per, axis=1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)

    def attend(which):
        s = jnp.einsum("thd,shd->hts", q[:, :, which], k[:, :, which]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, v)

    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + l0)
    a = attend(0) - second * lam * attend(1)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5)
    return a * lp["sub_norm"] * (1.0 - l0)


def _one_call(q, k, v, lp, l0, window):
    """The program's form: `_diff_split`, one attention call, `_diff_combine`."""
    T = q.shape[0]
    qs, ks, vs = _diff_split(q[None], k[None], v[None])
    ones, pos = jnp.ones(T, jnp.int32), jnp.arange(T)
    out = reference_packed_attention(qs[0], ks[0], vs[0], ones, pos, window=window)
    return _diff_combine(out[None], lp, l0, 1e-5)[0]


@pytest.mark.parametrize("window", [8, None], ids=["window", "full"])
def test_differential_attention_is_two_dense_softmaxes_a_pair_of_heads(window):
    T, hq, hkv, hd = 40, 8, 4, 8  # two q pairs a kv pair
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    q, kk, v = (jax.random.normal(k[i], (T, h, hd)) for i, h in enumerate((hq, hkv, hkv)))
    lp = {name: 0.3 * jax.random.normal(k[3 + i], (hd,)) for i, name in enumerate(DIFF_LAMBDAS)}
    lp["sub_norm"] = 1.0 + 0.1 * jax.random.normal(k[7], (2 * hd,))
    l0 = diff_lambda_init(3)
    assert abs(l0 - (0.8 - 0.6 * math.exp(-0.9))) < 1e-12 and abs(diff_lambda_init(0) - 0.2) < 1e-12
    want = _two_softmaxes(q, kk, v, lp, l0, window)
    got = _one_call(q, kk, v, lp, l0, window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # with lambda A2 dropped it is another function
    dropped = _two_softmaxes(q, kk, v, lp, l0, window, second=0.0)
    assert float(jnp.abs(dropped - want).max()) > 0.05
    # and a window is no full mask
    other = _two_softmaxes(q, kk, v, lp, l0, None if window else 8)
    assert float(jnp.abs(other - want).max()) > 0.05


def test_attention_takes_values_of_another_head_size():
    T = 24
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q, kk = jax.random.normal(k[0], (T, 4, 8)), jax.random.normal(k[1], (T, 2, 8))
    v = jax.random.normal(k[2], (T, 2, 16))
    out = reference_packed_attention(q, kk, v, jnp.ones(T, jnp.int32), jnp.arange(T))
    assert out.shape == (T, 4, 16)
    halves = [reference_packed_attention(q, kk, v[..., s], jnp.ones(T, jnp.int32), jnp.arange(T))
              for s in (slice(0, 8), slice(8, 16))]
    np.testing.assert_allclose(out, jnp.concatenate(halves, -1), atol=1e-6)


# ---------------------------------------------------------------------------
# Layers that share tensors
# ---------------------------------------------------------------------------


def _body_count(jaxpr):
    """Traced layer bodies: the outermost checkpointed computations (a
    layer body under full remat; the plain scan's checkpointed chunk
    step lies inside one), inside a scan or not."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2":
            n += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _body_count(sub)
    return n


def test_the_published_32_layers_are_four_segments_and_six_traced_bodies():
    cfg = _cfg(num_hidden_layers=32)
    segs = cfg.segments()
    assert [(s.start, s.unit, s.repeats) for s in segs] == [
        (0, ("ssm+dense", "diffattention+dense"), 8),
        (16, ("ssm+dense^",), 1), (17, ("diffattention+dense^",), 1),
        (18, ("gmu+dense", "xdiffattention+dense"), 7)]
    assert {p: len(idx) for p, (_, idx) in cfg.stack_paths().items()} == {
        "ssm+dense": 8, "diffattention+dense": 8, "ssm+dense^": 1,
        "diffattention+dense^": 1, "gmu+dense": 7, "xdiffattention+dense": 7}
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    ids, seg, pos = _row([20], 32)
    jaxpr = jax.make_jaxpr(lambda p: forward(p, cfg, ids, seg, pos, remat="full"))(params)
    assert _body_count(jaxpr.jaxpr) == 6  # one a kind, not 32
    # a unit that repeats twice is not worth a scan at these widths
    assert [s.repeats for s in _cfg().segments()] == [1] * 8
    assert [s.repeats for s in segments_of(tuple(k.parts for k in _cfg().kinds()))][0] == 2
    assert [s.repeats for s in _cfg(num_hidden_layers=12).segments()] == [3, 1, 1, 2][:3] + [1, 1, 1, 1]


@pytest.mark.parametrize("shape,what", [((1, 48, 64), "the scan output"),
                                        ((1, 48, 2, 8), "k and v")])
def test_a_kept_tensor_is_saved_once_however_many_layers_read_it(shape, what):
    """What the backward pass keeps of the forward, under full remat:
    the kept scan output [R, T, d_in] and the kept k and v
    [R, T, Hkv, hd] once each, whether one layer reads them (L = 8) or
    three in a scan (L = 16)."""
    from jax._src.ad_checkpoint import saved_residuals

    counts = []
    for n_layers in (8, 16):
        cfg = _cfg(num_hidden_layers=n_layers)
        params = _params(cfg)
        ids, seg, pos = _row([40], 48)
        res = saved_residuals(
            lambda p: forward(p, cfg, ids, seg, pos, remat="full").sum(), params)
        counts.append(sum(1 for aval, _ in res if tuple(aval.shape) == shape))
    assert counts[0] == counts[1] == (1 if what == "the scan output" else 2), counts


def test_layers_that_read_are_tied_to_a_layer_that_keeps():
    ssm = SSMConfig(form="mamba1", channels=16, dt_rank=2)
    with pytest.raises(ValueError, match="no earlier 'ssm' layer that keeps"):
        TransformerConfig(n_layers=2, ssm=ssm, layer_kinds=(
            LayerKind(mixer="ssm"), LayerKind(mixer="gmu", reads=0)))
    with pytest.raises(ValueError, match="no earlier 'attention' layer that keeps"):
        TransformerConfig(n_layers=2, layer_kinds=(LayerKind(), LayerKind(reads=0)))
    with pytest.raises(ValueError, match="'gmu' mixer always reads"):
        LayerKind(mixer="gmu")
    with pytest.raises(ValueError, match="nothing else has what to keep"):
        LayerKind(mixer="gmu", reads=0, keeps=True)
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mixer="ssm", diff=True)
    with pytest.raises(ValueError, match="needs channels and dt_rank"):
        SSMConfig(form="mamba1")
    with pytest.raises(ValueError, match="even counts"):
        TransformerConfig(n_layers=1, n_q_heads=3, n_kv_heads=1,
                          layer_kinds=(LayerKind(diff=True),))
    assert LayerKind(mixer="ssm", keeps=True).parts == "ssm+dense^"
    assert LayerKind(diff=True, reads=3).parts == "xdiffattention+dense"


# ---------------------------------------------------------------------------
# The family
# ---------------------------------------------------------------------------

PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560, intermediate_size=10240,
    layer_norm_eps=1e-05, max_position_embeddings=262144, mb_per_layer=2,
    model_type="phi4flash", num_attention_heads=40, num_hidden_layers=32,
    num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, vocab_size=200064)


@pytest.mark.parametrize("n_layers", [8, 16, 32])
def test_config_from_hf_builds_the_kinds_by_the_published_rule(n_layers):
    hf = dict(PUBLISHED, num_hidden_layers=n_layers)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    kinds, half = cfg.kinds(), n_layers // 2
    assert len(kinds) == n_layers and not any(k.rotary for k in kinds if k.mixer == "attention")
    for i, k in enumerate(kinds):
        assert k.mlp == "dense"
        if i % 2 == 0:
            assert k.mixer == ("ssm" if i <= half else "gmu")
            assert k.keeps == (i == half) and k.reads == (half if i > half else None)
        else:
            assert k.mixer == "attention" and k.diff
            assert k.window == (512 if i < half else None)
            assert k.keeps == (i == half + 1) and k.reads == (half + 1 if i > half + 1 else None)
    if n_layers == 32:
        assert [i for i, k in enumerate(kinds) if k.window] == [1, 3, 5, 7, 9, 11, 13, 15]
        assert sum(k.mixer == "ssm" for k in kinds) == 9 and cfg.n_ssm_layers == 9
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (2560, 40, 20, 64)
    assert (cfg.intermediate_dim, cfg.norm_type, cfg.norm_eps) == (10240, "layer", 1e-5)
    assert cfg.tied_embeddings and cfg.attn_bias and cfg.attn_out_bias and not cfg.mlp_bias
    s = cfg.ssm
    assert (s.form, s.channels, s.dt_rank, s.state_dim, s.conv_kernel, s.chunk_size) == (
        "mamba1", 5120, 160, 16, 4, 128)
    # the program's own count of the parameters, by the issue's arithmetic
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    stacks = {p: sum(math.prod(a.shape[1:]) for a in jax.tree_util.tree_leaves(t))
              for p, t in shapes["stacks"].items()}
    scan = (2560 * 10240 + 5120 * 5 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120
            + 5120 * 2560)
    assert scan == 41_241_600  # the issue's 41.24 M
    assert stacks["ssm+dense"] == stacks["ssm+dense^"] == scan + 3 * 2560 * 10240 + 4 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    assert attn == 19_668_864  # 19.67 M
    assert stacks["diffattention+dense"] == attn + 3 * 2560 * 10240 + 4 * 2560
    assert stacks["xdiffattention+dense"] == (2 * 2560 * 2560 + 2 * 2560 + 4 * 64 + 128
                                              + 3 * 2560 * 10240 + 4 * 2560)
    assert stacks["gmu+dense"] == 2 * 2560 * 5120 + 3 * 2560 * 10240 + 4 * 2560


@pytest.mark.parametrize("bad,err,match", [
    (dict(num_hidden_layers=10), ValueError, "multiple of 4"),
    (dict(num_hidden_layers=4), ValueError, "at least 8"),
    (dict(mb_per_layer=4), NotImplementedError, "mb_per_layer"),
    (dict(lm_head_bias=True), NotImplementedError, "lm_head_bias"),
])
def test_config_from_hf_refuses_what_the_rule_does_not_give(bad, err, match):
    with pytest.raises(err, match=match):
        family_from_hf_config(PUBLISHED).config_from_hf(dict(PUBLISHED, **bad))


@pytest.mark.parametrize("n_layers", [8, 16])
def test_hf_round_trip_on_a_toy_checkpoint(n_layers, tmp_path):
    from areal_tpu.models.hf import load_hf_model, save_hf_model

    cfg = _cfg(num_hidden_layers=n_layers)
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    fam = get_family("phi4flash")
    sd = fam.params_to_hf(params, cfg)
    half = n_layers // 2
    assert sd[f"model.layers.{half}.attn.A_log"].shape == (64, 16)
    assert sd["model.layers.0.attn.conv1d.weight"].shape == (64, 1, 4)
    assert sd["model.layers.1.attn.Wqkv.weight"].shape == (32 + 16 + 16, 32)
    assert sd[f"model.layers.{half + 3}.attn.Wqkv.weight"].shape == (32, 32)  # q alone
    assert sd[f"model.layers.{half + 2}.attn.in_proj.weight"].shape == (64, 32)  # a memory unit
    assert f"model.layers.{half + 2}.attn.x_proj.weight" not in sd
    assert sd["model.layers.0.mlp.fc1.weight"].shape == (96, 32)
    assert f"model.layers.{half + 1}.attn.inner_cross_attn.subln.weight" in sd
    assert "lm_head.weight" not in sd  # tied
    save_hf_model(str(tmp_path), cfg, params, "phi4flash")
    cfg2, back = load_hf_model(str(tmp_path))
    assert cfg2.kinds() == cfg.kinds() and cfg2.ssm == cfg.ssm
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("where", ["prefill", "decode_step", "paged_decode_step",
                                   "ServingEngine"])
def test_the_cache_paths_name_what_they_lack(where):
    cfg = _cfg()
    for what in (r"\[channels, state_dim\], a decay for every channel and state",
                 r"one layer's tensors shared by many: the layers \[6, 7\] read",
                 "the differential combine in the decode layer",
                 "a decode layer per kind of layer"):
        with pytest.raises(NotImplementedError, match=what):
            cfg.require_plain_stack(where)


def test_what_the_stack_cannot_run_is_refused_by_mechanism():
    from areal_tpu.engine.serving import ServingEngine
    from areal_tpu.models.generation import prefill

    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([20], 32)
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    with pytest.raises(NotImplementedError, match="shared by many"):
        prefill(params, cfg, ids, seg, pos)
    with pytest.raises(NotImplementedError, match="shared by many"):
        ServingEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    # layers of one scan must read one layer
    ssm = SSMConfig(form="mamba1", channels=16, dt_rank=2)
    odd = TransformerConfig(n_layers=4, ssm=ssm, hidden_dim=16, layer_kinds=(
        LayerKind(mixer="ssm", keeps=True), LayerKind(mixer="ssm", keeps=True),
        LayerKind(mixer="gmu", reads=0), LayerKind(mixer="gmu", reads=1)))
    shapes = jax.eval_shape(lambda k: init_params(odd, k), jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="read different layers' tensors"):
        jax.eval_shape(lambda p: forward(p, odd, ids, seg, pos), shapes)
