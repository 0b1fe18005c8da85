"""The delta rule at a rectangle (`areal_tpu/ops/kda.py`, Gated DeltaNet at
`expand_v` 2 as Olmo-Hybrid runs it): keys of K against values of V = 2 K, a
beta in (0, 2), `(I + A)^-1` by doubling blocks. The chunked form and its
backward against the recurrence token by token
(`benchmark/reference/olmo_hybrid.delta_rule`), a packed row against each of
its sequences alone, the kernels in interpret mode against the plain form
(also with the keys widened to a lane tile with zeros, as the chip takes 96
of 128), the mixer under the rule's and the taps' kernels against the plain
mixer, the inverse near beta 2 against a triangular solve, and the host's
counts. CPU, float32, toy widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import KDAConfig
from areal_tpu.ops import kda
from areal_tpu.ops.pallas import kda_bwd, kda_fwd, kda_taps
from benchmark.reference import olmo_hybrid as ref

H, K, V = 4, 16, 32
ROWS = ((50, 77, 30), (100, 64))  # sequences no chunk of 16 or 64 divides evenly
DOUBLING = kda.RuleForm(None, True)


def _segments(rows, T):
    seg = np.zeros((len(rows), T), np.int32)
    for r, lens in enumerate(rows):
        o = 0
        for j, n in enumerate(lens):
            seg[r, o:o + n] = j + 1
            o += n
    return seg


def _inputs(T=192, rows=ROWS, g_max=0.5, g_min=0.001, seed=0, alike=0.0, k_dim=K):
    """q, k [R, T, H, K], v [R, T, H, V], g [R, T, H] in [-g_max, -g_min], b
    in (0.1, 1.99), all 0 at padding, and the rows' segment ids. `alike`: the
    share of a key that a row's keys have in common."""
    rng = np.random.default_rng(seed)
    seg = _segments(rows, T)
    R = len(rows)
    q = rng.normal(size=(R, T, H, k_dim))
    k = (1 - alike) * rng.normal(size=(R, T, H, k_dim)) + alike * rng.normal(
        size=(R, 1, H, k_dim))
    v = rng.normal(size=(R, T, H, V))
    g = -rng.uniform(g_min, g_max, size=(R, T, H))
    b = rng.uniform(0.1, 1.99, size=(R, T, H))
    valid = seg > 0
    arrays = [np.where(valid[..., None, None], a, 0) for a in (q, k, v)] + [
        np.where(valid[..., None], a, 0) for a in (g, b)]
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays) + (jnp.asarray(seg),)


def recurrence(q, k, v, g, b, seg):
    """The reference's token-by-token rule over q and k made unit a head (q
    scaled by its own width's `^-0.5`); a sequence of a packed row at a time,
    zeros at padding."""
    q, k = kda.unit(q) * q.shape[-1] ** -0.5, kda.unit(k)
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


def _rule(q, k, v, g, b, seg, chunk, kernel, form=DOUBLING):
    """`kda.delta_rule` given the log-decays g themselves: A = -1, no bias."""
    h = v.shape[2]
    return kda.delta_rule(q, k, v, _f_of(g), b, -jnp.ones((h,)), jnp.zeros((h,)), seg, chunk,
                          kernel, form)


def _grads(fn, args, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)


def _assert_close(got, want, tol):
    """Each gradient to `tol` of its largest value."""
    for name, a, b in zip("qkvgb", got, want):
        assert a.shape == b.shape, name
        scale = float(jnp.abs(b).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol * scale, rtol=0,
                                   err_msg=f"d{name}")


def _weights(shape):
    return jnp.asarray(np.random.default_rng(1).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("group_cells", [128, 1 << 20], ids=["groups", "whole"])
def test_the_chunked_rule_at_a_rectangle_is_the_recurrence_and_so_is_its_backward(
        chunk, group_cells, monkeypatch):
    """K = 16 against V = 32, beta up to 1.99: outputs `[R, T, H, V]` and
    every gradient, a group of chunks at a time and whole, a packed row whose
    sequences no chunk divides against each sequence alone."""
    monkeypatch.setattr(kda, "GROUP_CELLS", group_cells)
    *args, seg = _inputs()
    w = _weights(args[2].shape)
    chunked = lambda *a: _rule(*a, seg, chunk, False)
    plain = lambda *a: recurrence(*a, seg)
    with jax.default_matmul_precision("highest"):
        got = chunked(*args)
        assert got.shape == args[2].shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain(*args)), atol=2e-5)
        _assert_close(_grads(chunked, args, w), _grads(plain, args, w), 2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_keys_alike_under_a_beta_near_two_need_the_doubling_inverse(chunk):
    """A row whose keys share four fifths of themselves, beta up to 1.99: the
    rule by doubling blocks stays the recurrence to 1e-4; by squarings at a
    chunk of 64 it is off by more than 1 (A^32's entries cancel in the
    product and float32 cannot follow), which is why a doubled beta takes the
    other form. At a chunk of 16 both hold."""
    *args, seg = _inputs(alike=0.8)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(recurrence(*args, seg))
        doubling = np.abs(np.asarray(_rule(*args, seg, chunk, False)) - want).max()
        squarings = np.abs(np.asarray(_rule(*args, seg, chunk, False, kda.RuleForm())) - want).max()
    assert doubling < 1e-4, doubling
    assert (squarings < 1e-3) if chunk == 16 else (squarings > 1.0), squarings


@pytest.mark.parametrize("doubling", [False, True], ids=["squarings", "doubling"])
@pytest.mark.parametrize("scale", [1.0, 1.99])
def test_the_inverse_is_a_triangular_solves_at_a_chunk_of_64(scale, doubling):
    """`(I + A)^-1` for `A = beta tril(K K^T, -1) (.) D` of independent unit
    keys of 96, as a seeded model's chunk has them, beta `scale` times a
    sigmoid, in float32 against a float64 solve: both forms to 2e-5; and the
    doubling form's backward rule is the inverse's own."""
    rng = np.random.default_rng(0)
    C, n = 64, 12
    k = jax.nn.silu(jnp.asarray(rng.normal(size=(n, C, 96))))
    k = np.asarray(k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6), np.float64)
    b = scale / (1 + np.exp(-2 * rng.normal(size=(n, C))))
    G = np.cumsum(-np.abs(rng.normal(size=(n, C))) * 0.05, -1)
    A = np.tril(np.einsum("nik,njk->nij", k, k) * np.exp(
        np.tril(G[:, :, None] - G[:, None, :])), -1) * b[..., None]
    want = np.linalg.inv(np.eye(C) + A)
    a32 = jnp.asarray(A, jnp.float32)
    got = kda._inverse_unit_lower(a32, doubling)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=2e-5)
    w = jnp.asarray(rng.normal(size=A.shape), jnp.float32)
    da = jax.grad(lambda a: jnp.sum(kda._inverse_unit_lower(a, doubling) * w))(a32)
    np.testing.assert_allclose(
        np.asarray(da, np.float64),
        -np.einsum("nji,njk,nlk->nil", want, np.asarray(w, np.float64), want), atol=2e-4)


def test_the_doubling_inverse_holds_where_keys_are_the_same():
    """Every key of a chunk the same and beta 1.99: by doubling 1e-4 from the
    solve (whose entries reach 2), by squarings not within 1e+3 of it."""
    C = 64
    A = jnp.asarray(np.tril(np.full((C, C), 1.99), -1), jnp.float32)
    want = np.linalg.inv(np.eye(C) + np.asarray(A, np.float64))
    np.testing.assert_allclose(np.asarray(kda._inverse_unit_lower(A, True)), want, atol=1e-4)
    assert np.abs(np.asarray(kda._inverse_unit_lower(A, False)) - want).max() > 1e3


# rows of 256 cells for the kernels: sequences that start in the middle of a
# chunk, rows that end before the row's last group (an empty tail), a row
# with no token beside a full one
KERNEL_ROWS = {
    "mid_starts": dict(rows=((50, 77, 30, 41), (100, 64, 92))),
    "empty_tail": dict(rows=((50, 40), (150,))),
    "empty_row": dict(rows=((), (100, 64, 92))),
}


def _widened(a, to):
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, to - a.shape[-1]),))


@pytest.mark.parametrize("case,widen,chunk", [
    ("mid_starts", 24, 16), ("mid_starts", 24, 64), ("empty_tail", 24, 64),
    ("empty_row", None, 64), ("mid_starts", None, 16)])
def test_the_kernels_are_the_plain_form_at_a_rectangle(case, widen, chunk, monkeypatch):
    """`kda_fwd_rule` and `kda_bwd_rule` in interpret mode (the mode
    `tests/model/test_gdn_ops.py` runs them in) at K = 16 against V = 32 with
    the doubling inverse, against `decay`, `_intra_head` and `states_scan` a
    group at a time: `O` `[R, T, H, V]`, the state each group received `[., R,
    H, V, K]`, dead chunks zero, and the seven gradients. `keys_widened`: q and
    k handed to the kernels with zero lanes after each head's 16 (to 24: the
    chip's 96 to 128) and `RuleForm.key_dim` 16: the same O, the state's new
    columns zero, and the gradients' new lanes zero."""
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # groups of 64 cells of both rows
    q, k, v, g, b, seg = _inputs(T=256, **KERNEL_ROWS[case])
    f, A, bias = _f_of(g), -jnp.ones((H,)), jnp.zeros((H,))
    gs = kda._group(2, 256 // chunk, chunk, 128)
    form = kda.RuleForm(K if widen else None, True)
    Kw = widen or K
    qw, kw = (_widened(a, Kw) for a in (q, k))
    with jax.default_matmul_precision("highest"):
        o, bounds = kda_fwd.rule_fwd(qw, kw, v, f, b, A, bias, seg, kda._live_chunks(seg, chunk),
                                     chunk, gs, interpret=True, form=form)
        want_o, res = kda._rule_fwd_groups(q, k, v, f, b, A, bias, seg, chunk, 128, True)
    assert o.shape == (2, 256, H, V)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=5e-6)
    live = np.asarray(kda._live_chunks(seg, chunk))
    want_b = np.asarray(res[-1])
    assert want_b.shape == (256 // chunk // gs, 2, H, V, K)
    assert bounds.shape == want_b.shape[:-1] + (Kw,)
    assert not np.asarray(bounds)[..., K:].any()
    for r in range(2):
        n = -(-int(live[r]) // gs)  # the groups the row reaches
        np.testing.assert_allclose(np.asarray(bounds)[:n, r, ..., :K], want_b[:n, r], atol=5e-6)
        assert not np.asarray(bounds)[n:, r].any()
        assert not np.asarray(o)[r, int(live[r]) * chunk:].any()
    w = _weights(v.shape)
    kernel = lambda q, k, *a: _rule(_widened(q, Kw), _widened(k, Kw), *a, seg, chunk,
                                    "interpret", form)
    plain = lambda *a: _rule(*a, seg, chunk, False)
    with jax.default_matmul_precision("highest"):
        _assert_close(_grads(kernel, (q, k, v, g, b), w), _grads(plain, (q, k, v, g, b), w), 2e-6)
        dq = jax.grad(lambda qw: jnp.sum(_rule(qw, kw, v, g, b, seg, chunk, "interpret", form)
                                         * w))(qw)
    assert not np.asarray(dq)[..., K:].any()


def test_keys_widened_are_the_kernels_alone():
    *args, seg = _inputs()
    with pytest.raises(ValueError, match="kernels' alone"):
        _rule(*args, seg, 16, False, kda.RuleForm(K, True))


def test_a_step_takes_heads_whose_blocks_are_whole_lane_tiles():
    """`kda_fwd.step_heads`: 8 of 32 heads of 128 x 128 as ever; 6 of 30 heads
    of 128 (96 widened) x 192 (5 would be a block of seven and a half tiles);
    whole key heads under value heads; toy heads the most, and not whole."""
    assert kda_fwd.step_heads(32, 32, 128, 128) == (8, True)
    assert kda_fwd.step_heads(32, 16, 128, 128) == (8, True)
    assert kda_fwd.step_heads(30, 30, 128, 192) == (6, True)
    assert kda_fwd.step_heads(30, 30, 96, 192) == (6, False)
    assert kda_fwd.step_heads(4, 2, 16, 16) == (4, False)
    assert kda.key_lanes(96) == 128 and kda.key_lanes(128) == 128 and kda.key_lanes(256) == 256
    assert kda.key_lanes(16) == 16 and kda.key_lanes(64) == 64
    wide = KDAConfig(n_heads=30, head_dim=96, value_head_dim=192, neg_eigval=True, decay="head",
                     decay_input="column", gate_rank=None, gate_act="silu")
    assert not kda.use_kernel(wide, None)  # the CPU
    assert kda.taps_in_kernel(wide, 8192, True) and not kda.taps_in_kernel(wide, 8192, False)
    assert not kda.taps_in_kernel(wide, 8200, True)


def _mixer_inputs(lens=((40, 20), (25, 39)), heads=2, head_dim=96, value_dim=128, T=64,
                  neg_eigval=True):
    """A KDAConfig as Olmo-Hybrid's (a decay a head, keys and values of two
    widths, a doubled beta), a layer of `init_kda_params`' weights, the five
    projections `kda_mixer` takes and the rows' segment ids."""
    cfg = KDAConfig(n_heads=heads, head_dim=head_dim, value_head_dim=value_dim,
                    neg_eigval=neg_eigval, gate_rank=None, chunk_size=16, decay="head",
                    decay_input="column", gate_act="silu")
    dense = lambda key, shape, scale=None: jax.random.normal(key, shape) * (
        scale or shape[-2] ** -0.5)
    kp = jax.tree_util.tree_map(lambda a: a[0], kda.init_kda_params(
        cfg, 32, dense, jax.random.PRNGKey(0), 1, jnp.float32))
    seg = jnp.asarray(_segments(lens, T))
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    R = len(lens)
    xs = tuple(jax.random.normal(kk, (R, T, w)) for kk, w in zip(
        keys, (cfg.d_key, cfg.d_key, cfg.d_inner, heads, heads)))
    return cfg, kp, xs, seg


def test_the_mixer_under_its_kernels_is_the_plain_mixer_at_96_by_128(monkeypatch):
    """`kda_mixer(..., kernel="interpret")` at keys of 96 and values of 128, a
    doubled beta: q, k and the convolutions' weights go into the taps' kernels
    widened to 128 lanes a head (three calls, `[2, 64, 256]` each), the rule
    takes `RuleForm(96, True)`, and the output `[R, T, H, 128]` and the
    gradients of q, k, v, f, b, of the three convolutions' weights and of the
    decay's two are the plain mixer's at the width the parameters have."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    cfg, kp, xs, seg = _mixer_inputs()
    assert kp["o_norm"].shape == (128,) and kp["conv_q"].shape == (4, 192)
    assert kp["wv"].shape == (32, 256) and kp["wo"].shape == (256, 32)
    assert kda.taps_in_kernel(cfg, 64, "interpret")
    ran, forms = [], []
    taps, rule = kda_taps.taps, kda.delta_rule
    monkeypatch.setattr(kda_taps, "taps", lambda *a: ran.append(a[0].shape) or taps(*a))
    monkeypatch.setattr(kda, "delta_rule", lambda *a: forms.append((a[0].shape, a[-1])) or rule(*a))
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 2, 128)) * (seg > 0)[..., None, None]
    both = lambda kernel: jax.value_and_grad(lambda xs, kp: (kda.kda_mixer(
        *xs, kp, cfg, seg, jnp.float32, kernel=kernel) * w).sum(), (0, 1))(xs, kp)
    out = lambda kernel: kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32, kernel=kernel)
    with jax.default_matmul_precision("highest"):
        got_o, want_o = out("interpret"), out(False)
        assert ran == [(2, 64, 256)] * 3
        assert forms == [((2, 64, 2, 128), kda.RuleForm(96, True)),
                         ((2, 64, 2, 96), kda.RuleForm(None, True))]
        (_, got), (_, want) = both("interpret"), both(False)
    assert got_o.shape == (2, 64, 2, 128)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-6)
    for a, b in zip(got[0], want[0]):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5 * (float(jnp.abs(b).max()) + 1e-6))
    for n in ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias"):
        assert got[1][n].shape == want[1][n].shape == kp[n].shape
        scale = float(jnp.abs(want[1][n]).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(got[1][n]), np.asarray(want[1][n]), rtol=0,
                                   atol=4e-5 * scale, err_msg=n)


def test_beta_is_doubled_where_the_config_says_so():
    """`neg_eigval`: the mixer's beta is `2 sigmoid`; one in (1, 2) moves the
    output where a sigmoid alone would not reach."""
    cfg, kp, xs, seg = _mixer_inputs(head_dim=16, value_dim=32)
    plain = KDAConfig(**{**cfg.__dict__, "neg_eigval": False})
    seen = []
    rule = kda.delta_rule
    try:
        kda.delta_rule = lambda *a: seen.append(a[4]) or rule(*a)
        kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32, kernel=False)
        kda.kda_mixer(*xs, kp, plain, seg, jnp.float32, kernel=False)
    finally:
        kda.delta_rule = rule
    doubled, single = (np.asarray(a) for a in seen)
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(doubled, 2 * single, rtol=1e-6)
    assert doubled[valid].max() > 1.0 and doubled.max() < 2.0 and not doubled[~valid].any()


def test_the_configs_refusals_name_what_has_no_code():
    with pytest.raises(NotImplementedError, match="values wider than keys"):
        KDAConfig(n_heads=2, head_dim=16, value_head_dim=32)
    with pytest.raises(NotImplementedError, match="doubled beta"):
        KDAConfig(n_heads=2, head_dim=16, neg_eigval=True)
    ok = KDAConfig(n_heads=4, n_key_heads=2, head_dim=16, value_head_dim=32, neg_eigval=True,
                   decay="head", decay_input="column", gate_rank=None, gate_act="silu")
    assert (ok.d_key, ok.d_inner, ok.value_dim, ok.beta_scale) == (32, 128, 32, 2.0)
    assert KDAConfig().value_dim == 16 and KDAConfig().beta_scale == 1.0


def test_the_hosts_counts_do_not_depend_on_the_widths():
    """`chunk_counts` counts cells, chunks, live chunks and starts: a rule at
    96 x 192 walks what a rule at 128 x 128 walks."""
    seg = _segments(((50, 77, 30), (100, 64)), 256)
    assert kda.chunk_counts(seg, 64) == (512, 8, 6, 5)
