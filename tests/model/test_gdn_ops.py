"""The delta rule with one decay a head and fewer key heads than value
heads (`areal_tpu/ops/kda.py`, Gated DeltaNet's): the chunked form and the
shared backward loop against the recurrence token by token in the released
code's order (`benchmark/reference/qwen3_next.delta_rule`), decays small
enough to underflow a chunk, the scalar form against the channel form fed
the same decay K times, the kernels in interpret mode (the forward's one
kernel reading a key head through its blocks' index, the backward's summing
a key head's gradients over its value heads) against the plain form and its
`jax.vjp`, a row with an empty tail, the mixer under the rule's and the
taps' kernels against the plain mixer, and the host's counts. CPU, float32, toy
widths. (A packed row against each of its sequences alone: `recurrence`
runs a sequence at a time, so every comparison with it is that.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import KDAConfig
from areal_tpu.ops import kda
from areal_tpu.ops.pallas import kda_bwd, kda_fwd, kda_taps
from benchmark.reference import qwen3_next as ref

HK, H, K = 2, 4, 16
ROWS = ((50, 77, 30), (100, 64))  # sequences no chunk of 16 or 64 divides evenly


def _segments(rows, T):
    seg = np.zeros((len(rows), T), np.int32)
    for r, lens in enumerate(rows):
        o = 0
        for j, n in enumerate(lens):
            seg[r, o:o + n] = j + 1
            o += n
    return seg


def _inputs(T=192, rows=ROWS, g_max=0.5, g_min=0.001, seed=0, hk=HK):
    """q, k [R, T, hk, K], v [R, T, H, K], g [R, T, H] in [-g_max, -g_min],
    b in (0.1, 0.95), all 0 at padding, and the rows' segment ids."""
    rng = np.random.default_rng(seed)
    seg = _segments(rows, T)
    R = len(rows)
    q, k = (rng.normal(size=(R, T, hk, K)) for _ in range(2))
    v = rng.normal(size=(R, T, H, K))
    g = -rng.uniform(g_min, g_max, size=(R, T, H))
    b = rng.uniform(0.1, 0.95, size=(R, T, H))
    valid = seg > 0
    arrays = [np.where(valid[..., None, None], a, 0) for a in (q, k, v)] + [
        np.where(valid[..., None], a, 0) for a in (g, b)]
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays) + (jnp.asarray(seg),)


def recurrence(q, k, v, g, b, seg):
    """The reference's token-by-token rule over q and k made unit a key head
    (q scaled) and repeated to the value heads, each in a row; a sequence
    of a packed row at a time, zeros at padding."""
    rep = v.shape[2] // q.shape[2]
    q, k = kda.unit(q) * K ** -0.5, kda.unit(k)
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    out = jnp.zeros(v.shape, jnp.float32)
    seg = np.asarray(seg)
    for r in range(seg.shape[0]):
        for s in np.unique(seg[r][seg[r] > 0]):
            (at,) = np.nonzero(seg[r] == s)
            cut = slice(at[0], at[-1] + 1)
            out = out.at[r, cut].set(ref.delta_rule(
                q[r, cut], k[r, cut], v[r, cut], g[r, cut], b[r, cut]))
    return out


def _f_of(g):
    """The inverse softplus of -g (anything at padding, where g is 0)."""
    return jnp.where(g < 0, jnp.log(jnp.expm1(-jnp.where(g < 0, g, -1.0))), 0.0)


def _rule(q, k, v, g, b, seg, chunk, kernel):
    """`kda.delta_rule` given the log-decays g themselves: A = -1, no bias."""
    h = v.shape[2]
    return kda.delta_rule(q, k, v, _f_of(g), b, -jnp.ones((h,)), jnp.zeros((h,)), seg, chunk,
                          kernel)


def _grads(fn, args, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)


def _assert_close(got, want, tol, g_tol=None):
    """Each gradient to `tol` of its largest value; the decay's to `g_tol`
    where that is given."""
    for name, a, b in zip("qkvgb", got, want):
        assert a.shape == b.shape, name
        scale = float(jnp.abs(b).max()) + 1e-6
        limit = g_tol if g_tol and name == "g" else tol
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=limit * scale, rtol=0,
                                   err_msg=f"d{name}")


def _weights(shape):
    return jnp.asarray(np.random.default_rng(1).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("group_cells", [128, 1 << 20], ids=["groups", "whole"])
def test_the_scalar_chunked_rule_is_the_recurrence_and_so_is_its_backward(
        chunk, group_cells, monkeypatch):
    """Outputs and every gradient (q's and k's are a key head's: the sum
    over the value heads that read it), a group of chunks at a time and
    whole, a row whose sequences no chunk divides."""
    monkeypatch.setattr(kda, "GROUP_CELLS", group_cells)
    *args, seg = _inputs()
    w = _weights(args[2].shape)
    chunked = lambda *a: _rule(*a, seg, chunk, False)
    plain = lambda *a: recurrence(*a, seg)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(chunked(*args)), np.asarray(plain(*args)),
                                   atol=2e-5)
        _assert_close(_grads(chunked, args, w), _grads(plain, args, w), 2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_heads_decays_that_underflow_a_chunk_stay_finite_and_right(chunk):
    """A decay of 0.01 a token (g = -4.6) over whole chunks: `exp(G_i -
    G_j)` is taken for j <= i alone, at most 1, and the result is the
    recurrence's to 1e-4."""
    *args, seg = _inputs(g_max=4.7, g_min=4.5)
    w = _weights(args[2].shape)
    chunked = lambda *a: _rule(*a, seg, chunk, False)
    with jax.default_matmul_precision("highest"):
        got, want = chunked(*args), recurrence(*args, seg)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
        grads = _grads(chunked, args, w)
        assert all(np.isfinite(np.asarray(a)).all() for a in grads)
        _assert_close(grads, _grads(lambda *a: recurrence(*a, seg), args, w), 1e-4)


def test_the_scalar_intra_takes_no_exponential_of_a_positive_number(monkeypatch):
    seen = []
    exp = jnp.exp
    monkeypatch.setattr(kda.jnp, "exp", lambda x: seen.append(float(jnp.max(x))) or exp(x))
    q, k, v, g, b, seg = _inputs(g_max=4.7)
    cut = lambda a: a.reshape((-1, 64) + a.shape[2:])
    with jax.disable_jit():
        parts = kda.intra(cut(q), cut(k), cut(v), cut(g), cut(b), cut(seg),
                          jnp.zeros((seg.size // 64,), jnp.int32), jnp.float32)
    assert len(seen) >= 3 and max(seen) <= 0.0
    assert [p.shape[1] for p in parts] == [H] * 6  # every part a value head's


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_scalar_form_is_the_channel_form_fed_the_same_decay_k_times(chunk):
    """As many key heads as value heads (the channel form has no shared
    key): outputs and gradients, the channel form's decay gradient summed
    over a head's channels."""
    q, k, v, g, b, seg = _inputs(hk=H)
    w = _weights(v.shape)
    wide = lambda g: jnp.broadcast_to(g[..., None], g.shape + (K,))
    scalar = lambda *a: _rule(*a, seg, chunk, False)
    channel = lambda q, k, v, g, b: kda.delta_rule(
        q, k, v, _f_of(wide(g)), b, -jnp.ones((H,)), jnp.zeros((H, K)), seg, chunk, False)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(scalar(q, k, v, g, b)),
                                   np.asarray(channel(q, k, v, g, b)), atol=2e-5)
        _assert_close(_grads(scalar, (q, k, v, g, b), w),
                      _grads(channel, (q, k, v, g, b), w), 2e-5)


# rows of 256 cells for the kernels: sequences that start in the middle of
# a chunk, rows that end before the row's last group (an empty tail),
# decays that underflow a chunk, a row with no token beside a full one
KERNEL_ROWS = {
    "mid_starts": dict(rows=((50, 77, 30, 41), (100, 64, 92))),
    "empty_tail": dict(rows=((50, 40), (150,))),
    "underflow": dict(rows=((50, 77, 30), (100, 64)), g_max=4.7, g_min=4.5),
    "empty_row": dict(rows=((), (100, 64, 92))),
}


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("hk", [HK, H], ids=["grouped", "a_key_a_value"])
@pytest.mark.parametrize("case", list(KERNEL_ROWS))
def test_the_kernels_are_the_plain_form_with_a_decay_a_head(case, hk, chunk, monkeypatch):
    """`kda_fwd_rule` in interpret mode (q and k read a key head through
    the blocks' index; the pair's two value heads under one key head share
    its products) against `decay`, `_intra_head` and `states_scan` a group
    at a time: `O`, the state each group received, dead chunks zero; and
    the whole rule's gradients through the backward's kernel
    (`kda_bwd_rule`) against the plain form's."""
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # groups of 64 cells of both rows
    q, k, v, g, b, seg = _inputs(T=256, hk=hk, **KERNEL_ROWS[case])
    f, A, bias = _f_of(g), -jnp.ones((H,)), jnp.zeros((H,))
    gs = kda._group(2, 256 // chunk, chunk, 128)
    with jax.default_matmul_precision("highest"):
        o, bounds = kda_fwd.rule_fwd(q, k, v, f, b, A, bias, seg, kda._live_chunks(seg, chunk),
                                     chunk, gs, interpret=True)
        want_o, res = kda._rule_fwd_groups(q, k, v, f, b, A, bias, seg, chunk, 128)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(bounds)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=5e-6)
    live = np.asarray(kda._live_chunks(seg, chunk))
    want_b = np.asarray(res[-1])
    assert bounds.shape == want_b.shape == (256 // chunk // gs, 2, H, K, K)
    for r in range(2):
        n = -(-int(live[r]) // gs)  # the groups the row reaches
        np.testing.assert_allclose(np.asarray(bounds)[:n, r], want_b[:n, r], atol=5e-6)
        assert not np.asarray(bounds)[n:, r].any()
        assert not np.asarray(o)[r, int(live[r]) * chunk:].any()
    w = _weights(v.shape)
    kernel = lambda *a: _rule(*a, seg, chunk, "interpret")
    plain = lambda *a: _rule(*a, seg, chunk, False)
    # Where decays underflow a chunk (0.01 a token) the running sum reaches
    # -300 over a chunk of 64, where float32's step is 3e-5, and every
    # `exp(G_i - G_j)` carries that in both arms. Against the recurrence in
    # float64 the decay's gradient reads, as shares of its largest value
    # (0.01), 3.8e-6 to 2.4e-5 from the kernel and 6.7e-6 to 1.9e-5 from the
    # plain form over the four cases; the two stand 6.7e-6 to 3.0e-5 apart
    # (PR 55's readings; q's, k's, v's and b's stay under 4e-7 and keep 2e-6).
    with jax.default_matmul_precision("highest"):
        _assert_close(_grads(kernel, (q, k, v, g, b), w), _grads(plain, (q, k, v, g, b), w),
                      2e-6, g_tol=5e-5 if case == "underflow" else None)


# rows of 256 cells for the backward's kernel, as `tests/model/test_kda_ops.py`
# has them: starts inside a chunk with padding after, starts on a chunk's
# edge, a last live chunk that ends before its group does, a row with no
# token, a decay of 0.2 a token over whole chunks
BWD_ROWS = {
    "mid_starts": dict(rows=((50, 77, 30, 41), (100, 64, 92))),
    "edge_starts": dict(rows=((64, 128, 32), (128, 64))),
    "mid_group_end": dict(rows=((50, 40), (150,))),
    "empty_row": dict(rows=((), (100, 64, 92))),
    "fast_decay": dict(rows=((50, 77, 30), (100, 64)), g_max=1.7, g_min=1.5),
}


@pytest.mark.parametrize("case,chunk,hk", [(c, n, HK) for c in BWD_ROWS for n in (16, 64)] + [
    ("mid_starts", 16, H), ("fast_decay", 64, H)])
def test_the_backwards_kernel_is_the_plain_forms_transpose_with_a_decay_a_head(
        case, chunk, hk, monkeypatch):
    """`kda_bwd_rule` in interpret mode against `jax.vjp` of the plain form,
    one decay a value head: the seven gradients (q's and k's a key head's,
    summed over the value heads that read it inside the kernel's step; f's
    a number a cell a head; dA and d dt_bias a head) under an A and a
    dt_bias that are not trivial, 2 key heads under 4 value heads and a key
    a value head; a dead chunk's gradients zero."""
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # groups of 64 cells of both rows
    q, k, v, g, b, seg = _inputs(T=256, hk=hk, **BWD_ROWS[case])
    A = -jnp.asarray([1.0, 1.7, 0.6, 2.2])
    bias = jnp.asarray(np.random.default_rng(3).normal(size=(H,)) * 0.3, jnp.float32)
    # g = A softplus(f + dt_bias) at the cells that hold a token
    f = jnp.where(g < 0, jnp.log(jnp.expm1(jnp.where(g < 0, g, -1.0) / A)) - bias, 0.0)
    args = (q, k, v, f, b, A, bias)
    w = _weights(v.shape)
    seven = lambda kernel: jax.vjp(
        lambda *a: kda._rule(*a, seg, chunk, kernel, kda.GROUP_CELLS), *args)
    with jax.default_matmul_precision("highest"):
        (o, pull), (want_o, want_pull) = seven("interpret"), seven(False)
        got, want = pull(w), want_pull(w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=5e-6)
    live = np.asarray(kda._live_chunks(seg, chunk)) * chunk
    for name, a, t in zip(("q", "k", "v", "f", "b", "A", "dt_bias"), got, want):
        assert a.shape == t.shape and a.dtype == t.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.abs(t).max()) + 1e-6
        # A's and dt_bias's are float32 sums over every cell, in another order
        np.testing.assert_allclose(np.asarray(a), np.asarray(t), rtol=0, err_msg=f"d{name}",
                                   atol=1e-5 * scale * (4 if a.ndim < 3 else 1))
        if a.ndim >= 3:
            assert not any(np.asarray(a[r, live[r]:]).any() for r in range(2)), name


def test_the_mixer_draws_and_runs_a_decay_a_head_over_grouped_keys():
    """`init_kda_params` by the form (a column a head, a full-rank gate,
    `dt_bias` a head) and `kda_mixer` over a packed row against each
    sequence alone: what holds the resets of the convolutions and of the
    state at a sequence's start."""
    cfg = KDAConfig(n_heads=H, n_key_heads=HK, head_dim=K, gate_rank=None, chunk_size=16,
                    decay="head", decay_input="column", gate_act="silu")
    dense = lambda key, shape, scale=None: jax.random.normal(key, shape) * (
        scale if scale is not None else shape[-2] ** -0.5)
    kp = kda.init_kda_params(cfg, 32, dense, jax.random.PRNGKey(0), 1, jnp.float32)
    assert {n: a.shape[1:] for n, a in kp.items()} == {
        "wq": (32, HK * K), "wk": (32, HK * K), "wv": (32, H * K), "w_g": (32, H * K),
        "w_a": (32, H), "w_b": (32, H), "conv_q": (4, HK * K), "conv_k": (4, HK * K),
        "conv_v": (4, H * K), "A_log": (H,), "dt_bias": (H,), "o_norm": (K,),
        "wo": (H * K, 32)}
    forget = np.exp(-np.exp(np.asarray(kp["A_log"])) * np.logaddexp(0, np.asarray(kp["dt_bias"])))
    assert (forget > 0.15).all() and (forget < 0.9999).all()  # 0.2 to 0.999 a token
    kp = {n: a[0] for n, a in kp.items()}
    rng = np.random.default_rng(0)
    seg = jnp.asarray(_segments(((50, 77, 30),), 192))
    x = [jnp.asarray(rng.normal(size=(1, 192, w)), jnp.float32)
         for w in (HK * K, HK * K, H * K, H, H)]
    with jax.default_matmul_precision("highest"):
        packed = kda.kda_mixer(*x, kp, cfg, seg, jnp.float32)
        assert packed.shape == (1, 192, H, K) and not np.asarray(packed[0, 157:]).any()
        for s, (o, n) in enumerate(((0, 50), (50, 77), (127, 30))):
            alone = kda.kda_mixer(*(a[:, o:o + n] for a in x), kp, cfg,
                                  jnp.ones((1, n), jnp.int32), jnp.float32)
            np.testing.assert_allclose(np.asarray(packed[:, o:o + n]), np.asarray(alone),
                                       atol=2e-5, err_msg=f"sequence {s}")


@pytest.mark.parametrize("hk", [1, 2], ids=["grouped_keys", "a_key_a_value_head"])
def test_the_mixer_under_its_kernels_is_the_plain_mixer(hk, monkeypatch):
    """`kda_mixer(..., kernel="interpret")` at heads of 128 (q and k of `hk`
    key heads' columns, v of two value heads': the taps' kernels take widths
    that differ), a packed row with NaN in its padding cells: the output and
    the gradients of q, k, v, f, b, of `conv_q`, `conv_k`, `conv_v` and of the
    decay's `A_log` and `dt_bias` against `kernel=False`, at the limits the
    rule's kernels are held to."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    cfg = KDAConfig(n_heads=2, n_key_heads=hk, head_dim=128, gate_rank=None, chunk_size=16,
                    decay="head", decay_input="column", gate_act="silu")
    dense = lambda key, shape, scale=None: jax.random.normal(key, shape) * (
        scale if scale is not None else shape[-2] ** -0.5)
    kp = {n: a[0] for n, a in kda.init_kda_params(
        cfg, 32, dense, jax.random.PRNGKey(0), 1, jnp.float32).items()}
    rng = np.random.default_rng(0)
    seg = jnp.asarray(_segments(((20, 31, 9), (45, 11)), 64))
    assert kda.taps_in_kernel(cfg, 64, "interpret")
    ran = []
    taps = kda_taps.taps
    monkeypatch.setattr(kda_taps, "taps", lambda *a: ran.append(a[0].shape[-1]) or taps(*a))
    xs = tuple(jnp.asarray(rng.normal(size=(2, 64, w)), jnp.float32)
               for w in (hk * 128, hk * 128, 256, 2, 2))
    nan = lambda a: jnp.where((seg > 0)[..., None], a, jnp.nan)
    w = jnp.asarray(rng.normal(size=(2, 64, 2, 128)), jnp.float32) * (seg > 0)[..., None, None]
    run = lambda kernel, xs: jax.value_and_grad(lambda xs, kp: (kda.kda_mixer(
        *xs, kp, cfg, seg, jnp.float32, kernel=kernel) * w).sum(), (0, 1))(xs, kp)
    with jax.default_matmul_precision("highest"):
        (got_l, got), (want_l, want) = run("interpret", tuple(nan(a) for a in xs)), run(False, xs)
    assert ran[:3] == [hk * 128, hk * 128, 256]
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    for name, a, t in zip(("q", "k", "v", "f", "b"), got[0], want[0]):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(t), rtol=0, err_msg=f"d{name}",
                                   atol=1e-5 * (float(jnp.abs(t).max()) + 1e-6))
    for n in ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias"):
        np.testing.assert_allclose(np.asarray(got[1][n]), np.asarray(want[1][n]), rtol=0,
                                   atol=4e-5 * (float(jnp.abs(want[1][n]).max()) + 1e-6),
                                   err_msg=n)


@pytest.mark.parametrize("kw,err", [
    (dict(decay="head"), NotImplementedError),  # a head's decay from the low-rank pair
    (dict(decay="channel", decay_input="column"), NotImplementedError),
    (dict(n_heads=4, n_key_heads=2), NotImplementedError),  # shared keys, a decay a channel
    (dict(n_heads=4, n_key_heads=3, decay="head", decay_input="column"), ValueError),
    (dict(gate_rank=None), NotImplementedError),
    (dict(decay="row"), ValueError),
    (dict(gate_act="tanh"), ValueError),
])
def test_a_combination_without_code_is_refused(kw, err):
    with pytest.raises(err):
        KDAConfig(**kw)
