"""The delta rule over packed rows (`areal_tpu/ops/kda.py`): the chunked
form and its hand-written backward against the recurrence token by token
(`benchmark/reference/kimi_linear.delta_rule`), decays small enough to
underflow a chunk, the forward's one kernel (`ops/pallas/kda_fwd.py`) and
the backward's (`ops/pallas/kda_bwd.py`) in interpret mode against the
plain form and its `jax.vjp`, a packed row against each of its sequences
alone, the host's counts, and the taps' kernels (`ops/pallas/kda_taps.py`)
in interpret mode against `ops/ssm.causal_conv` after the mixer's `where`.
CPU, float32, toy widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.config import KDAConfig
from areal_tpu.ops import kda
from areal_tpu.ops.pallas import kda_bwd, kda_fwd, kda_taps
from areal_tpu.ops.ssm import causal_conv
from benchmark.reference import kimi_linear as ref

H, K = 2, 16
ROWS = ((50, 77, 30), (100, 64))  # sequences no chunk of 16 or 64 divides evenly


def _segments(rows, T):
    seg = np.zeros((len(rows), T), np.int32)
    for r, lens in enumerate(rows):
        o = 0
        for j, n in enumerate(lens):
            seg[r, o:o + n] = j + 1
            o += n
    return seg


def _inputs(T=192, rows=ROWS, g_max=0.5, g_min=0.001, seed=0):
    """q, k, v, g in [-g_max, -g_min], b in (0.1, 0.95), all 0 at padding,
    and the rows' segment ids."""
    rng = np.random.default_rng(seed)
    seg = _segments(rows, T)
    R = len(rows)
    q, k, v = (rng.normal(size=(R, T, H, K)) for _ in range(3))
    g = -rng.uniform(g_min, g_max, size=(R, T, H, K))
    b = rng.uniform(0.1, 0.95, size=(R, T, H))
    valid = seg > 0
    m = valid[..., None, None]
    arrays = [np.where(m, a, 0) for a in (q, k, v, g)] + [
        np.where(valid[..., None], b, 0)]
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays) + (jnp.asarray(seg),)


def recurrence(q, k, v, g, b, seg):
    """The reference's token-by-token rule over q and k made unit a head
    (q scaled), a sequence of a packed row at a time, zeros at padding."""
    q, k = kda.unit(q) * K ** -0.5, kda.unit(k)
    out = jnp.zeros(v.shape, jnp.float32)
    seg = np.asarray(seg)
    for r in range(seg.shape[0]):
        for s in np.unique(seg[r][seg[r] > 0]):
            (at,) = np.nonzero(seg[r] == s)
            cut = slice(at[0], at[-1] + 1)
            out = out.at[r, cut].set(ref.delta_rule(
                q[r, cut], k[r, cut], v[r, cut], g[r, cut], b[r, cut]))
    return out


def _rule(q, k, v, g, b, seg, chunk, kernel):
    """`kda.delta_rule` given the log-decays g themselves: A = -1, no bias,
    and f the inverse softplus of -g (anything at padding, where g is 0)."""
    f = jnp.where(g < 0, jnp.log(jnp.expm1(-jnp.where(g < 0, g, -1.0))), 0.0)
    H, K = q.shape[2:]
    return kda.delta_rule(q, k, v, f, b, -jnp.ones((H,)), jnp.zeros((H, K)), seg, chunk, kernel)


def _grads(fn, args, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)


def _assert_close(got, want, tol, g_tol=None):
    """Each gradient to `tol` of its largest value; the decay's to `g_tol`
    where that is given."""
    for name, a, b in zip("qkvgb", got, want):
        scale = float(jnp.abs(b).max()) + 1e-6
        limit = g_tol if g_tol and name == "g" else tol
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=limit * scale, rtol=0,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("group_cells", [128, 1 << 20], ids=["groups", "whole"])
def test_the_chunked_rule_is_the_recurrence_and_so_is_its_backward(chunk, group_cells,
                                                                   monkeypatch):
    """Outputs and every gradient, with `intra` run a group of chunks at a
    time (a loop whose trip count is the row's live groups) and whole."""
    monkeypatch.setattr(kda, "GROUP_CELLS", group_cells)
    *args, seg = _inputs()
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    chunked = lambda *a: _rule(*a, seg, chunk, False)
    plain = lambda *a: recurrence(*a, seg)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(chunked(*args)), np.asarray(plain(*args)),
                                   atol=2e-5)
        _assert_close(_grads(chunked, args, w), _grads(plain, args, w), 2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_that_underflow_a_chunk_stay_finite_and_right(chunk):
    """The sub-block rule: a decay of 0.01 a token (g = -4.6) in every
    channel over whole chunks, where `exp(-G_j)` across a chunk of 64
    would be 1e128: no exponential of a positive number is taken, and the
    result is the recurrence's to 1e-4."""
    *args, seg = _inputs(g_max=4.7, g_min=4.5)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    chunked = lambda *a: _rule(*a, seg, chunk, False)
    with jax.default_matmul_precision("highest"):
        got, want = chunked(*args), recurrence(*args, seg)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
        grads = _grads(chunked, args, w)
        assert all(np.isfinite(np.asarray(a)).all() for a in grads)
        _assert_close(grads, _grads(lambda *a: recurrence(*a, seg), args, w), 1e-4)


def test_intra_takes_no_exponential_of_a_positive_number(monkeypatch):
    """Every `exp` of `intra`'s trace gets an argument that is at most 0
    (or -inf where a mask stands)."""
    seen = []
    exp = jnp.exp
    monkeypatch.setattr(kda.jnp, "exp", lambda x: seen.append(float(jnp.max(x))) or exp(x))
    q, k, v, g, b, seg = _inputs(g_max=4.7)
    C = 64
    cut = lambda a: a.reshape((-1, C) + a.shape[2:])
    # the cell-by-cell blocks without their checkpoint, which would trace
    monkeypatch.setattr(kda, "_diagonal_blocks", kda._diagonal_blocks.__wrapped__)
    with jax.disable_jit():
        kda.intra(cut(q), cut(k), cut(v), cut(g), cut(b), cut(seg),
                  jnp.zeros((seg.size // C,), jnp.int32), jnp.float32)
    assert len(seen) >= 5 and max(seen) <= 0.0


# rows of 256 cells for the backward's kernel: (a) sequences that start
# inside a chunk and inside a sub-block of 16, padding after them, (b) every
# sequence starting on a chunk's edge, (c) a row whose last live chunk ends
# before its group of chunks does, beside a longer one, (d) a row with no
# token beside a full one, (e) a decay of 0.2 a token (g = -1.6) in every
# channel over whole chunks: 1e-45 across 64 cells
BWD_ROWS = {
    "mid_starts": dict(rows=((50, 77, 30, 41), (100, 64, 92))),
    "edge_starts": dict(rows=((64, 128, 32), (128, 64))),
    "mid_group_end": dict(rows=((50, 40), (150,))),
    "empty_row": dict(rows=((), (100, 64, 92))),
    "fast_decay": dict(rows=((50, 77, 30), (100, 64)), g_max=1.7, g_min=1.5),
}


def _seven(kernel, args, seg, chunk, w):
    """The rule's result and its seven cotangents under `w`: q's, k's, v's,
    f's, b's, A's and dt_bias's."""
    out, pull = jax.vjp(lambda *a: kda._rule(*a, seg, chunk, kernel, kda.GROUP_CELLS), *args)
    return out, pull(w)


def _assert_seven(got, want, tol):
    for name, a, b in zip(("q", "k", "v", "f", "b", "A", "dt_bias"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.abs(b).max()) + 1e-6
        # A's and dt_bias's are float32 sums over every cell, in another order
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, err_msg=f"d{name}",
                                   atol=tol * scale * (4 if a.ndim < 3 else 1))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("case", list(BWD_ROWS))
def test_the_backwards_kernel_is_the_plain_forms_transpose(case, chunk, monkeypatch):
    """`kda_bwd_rule` in interpret mode (the chunks' states again from the
    state each group received, then the chunks backwards: `intra` made
    again, the walk's transpose, `intra`'s pullback by hand) against
    `jax.vjp` of the plain form: the seven gradients, dA and d dt_bias
    among them, under an A a head and a dt_bias a channel that are not
    trivial; finite at a decay that underflows a chunk; a dead chunk's
    gradients zero."""
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # groups of 64 cells of both rows
    q, k, v, g, b, seg = _inputs(T=256, **BWD_ROWS[case])
    A = -jnp.asarray([1.0, 1.7])
    bias = jnp.asarray(np.random.default_rng(3).normal(size=(H, K)) * 0.3, jnp.float32)
    # g = A softplus(f + dt_bias) at the cells that hold a token
    sp = jnp.where(g < 0, g, -1.0) / A[:, None]
    f = jnp.where(g < 0, jnp.log(jnp.expm1(sp)) - bias, 0.0)
    args = (q, k, v, f, b, A, bias)
    w = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, got = _seven("interpret", args, seg, chunk, w)
        want_o, want = _seven(False, args, seg, chunk, w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-6)
    _assert_seven(got, want, 1e-5)
    live = np.asarray(kda._live_chunks(seg, chunk)) * chunk
    for r in range(2):
        assert not any(np.asarray(a[r, live[r]:]).any() for a in got[:5])


def test_the_backwards_kernel_in_bf16_stands_as_near_the_float32_rule_as_the_plain_form():
    """bf16 operands at heads of 128, a row of 256 cells of eight heads with
    the probe's decays (`scripts/kda_probe.py`), in interpret mode: the
    decay's three gradients (f's, A's, dt_bias's: all three are sums of the
    running sum's cotangent) stand from the plain form in float32 at the
    highest precision no farther, root mean square, than the plain form in
    bf16 does. PR 55's readings, kernel over plain: 0.91, 0.99 and 0.94; with
    an off-diagonal sub-block's pairs taken into that cotangent as `x (.) dx
    - k (.) dk` from one rounded factor and one not, 1.50, 5.86 and 3.30:
    what was left of every pair added up over a chunk's earlier cells."""
    T, Hb, Kb = 256, 8, 128
    rng = np.random.default_rng(0)
    seg = np.zeros((1, T), np.int32)
    seg[0, :70], seg[0, 70:205] = 1, 2
    at = lambda a: np.where((seg > 0).reshape(seg.shape + (1,) * (a.ndim - 2)), a, 0)
    q, k, v = (jnp.asarray(at(rng.normal(size=(1, T, Hb, Kb))), jnp.bfloat16) for _ in range(3))
    f = jnp.asarray(at(np.broadcast_to((rng.normal(size=(1, T, Hb)) - 4.0)[..., None],
                                       (1, T, Hb, Kb))), jnp.bfloat16)
    b = jnp.asarray(at(rng.uniform(0.1, 0.95, size=(1, T, Hb))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(Hb,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, T, Hb, Kb)), jnp.float32)
    seg = jnp.asarray(seg)

    def three(kernel, dtype):
        args = tuple(a.astype(dtype) for a in (q, k, v, f)) + (b, A, jnp.zeros((Hb, Kb)))
        grads = jax.grad(lambda *a: jnp.sum(
            kda._rule(*a, seg, 64, kernel, kda.GROUP_CELLS) * w), (3, 5, 6))(*args)
        return [np.asarray(g, np.float32) for g in grads]

    with jax.default_matmul_precision("highest"):
        exact = three(False, jnp.float32)
    plain, fused = three(False, jnp.bfloat16), three("interpret", jnp.bfloat16)
    rms = lambda a, t: float(np.sqrt(np.mean(np.square(a - t))))
    for name, e, p, g in zip(("f", "A", "dt_bias"), exact, plain, fused):
        assert rms(g, e) <= 1.15 * rms(p, e), (name, rms(g, e), rms(p, e))


def test_the_rule_takes_its_kernels_once_each_and_the_plain_form_none(monkeypatch):
    """`delta_rule` under `jax.grad`: with `kernel` the forward's kernel
    (traced for the rule itself and for its `custom_vjp` forward) and one
    call of the backward's for the whole call's rows; without, neither."""
    ran = []
    for mod, name in ((kda_fwd, "rule_fwd"), (kda_bwd, "rule_bwd")):
        fn = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda *a, _fn=fn, _n=name, **kw: (
            ran.append(_n) or _fn(*a, **kw)))
    jax.clear_caches()  # `delta_rule` is jitted at module level: trace it through the patches
    *args, seg = _inputs(rows=((50, 40), (100, 92)))
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _grads(lambda *a: _rule(*a, seg, 64, "interpret"), args, w)
        assert ran == ["rule_fwd", "rule_fwd", "rule_bwd"]
        n = len(ran)
        want = _grads(lambda *a: _rule(*a, seg, 64, False), args, w)
        assert len(ran) == n
    _assert_close(got, want, 2e-6)
    jax.clear_caches()


def test_the_backwards_kernel_takes_no_exponential_of_a_positive_number(monkeypatch):
    """Every `exp` of a chunk's backward gets an argument that is at most
    0: `intra` again by the forward kernel's formulas, the sub-blocks'
    cotangents under the same `exp(min(G_i - G_c, 0))` and relative to the
    later sub-block's first cell, softplus' derivative from `exp(-|x|)`;
    finite at a decay of 0.01 a token."""
    seen = []
    exp = jnp.exp
    monkeypatch.setattr(kda_fwd.jnp, "exp", lambda x: seen.append(float(jnp.max(x))) or exp(x))
    monkeypatch.setattr(kda_fwd.pltpu, "roll", jnp.roll)  # the kernel's has no eager rule
    q, k, v, g, b, seg = _inputs(g_max=4.7)
    C = 64
    f = jnp.where(g < 0, jnp.log(jnp.expm1(-jnp.where(g < 0, g, -1.0))), 0.0)
    side = lambda h: (q[:1, :C, h], k[:1, :C, h], v[:1, :C, h], f[:1, :C, h], b[:1, :C, h:h + 1],
                      -jnp.ones((1, 1, K)), jnp.zeros((1, 1, K)))
    rng = np.random.default_rng(2)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    scratch = [np.zeros((2, 1, C, K), np.float32) for _ in range(4)]
    with jax.disable_jit():
        outs, _ = kda_bwd._chunk_bwd(
            [side(0), side(1)], [draw(1, K, K)] * 2, [draw(1, C, K)] * 2, [draw(1, K, K)] * 2,
            scratch[:3], scratch[3], jnp.tile(seg[:1, :C], (1, 2)), 0, int(seg[0, C - 1]),
            jnp.float32)
    assert len(seen) >= 4 * (4 * 16 + 5) and max(seen) <= 0.0
    assert all(np.isfinite(np.asarray(a)).all() for o in outs for a in o.values())


# rows of 256 cells for the forward's one kernel: (a) sequences that start
# in the middle of a chunk and of a sub-block of 16, (b) rows that end
# before the row's last group, one before the other, (c) decays of 0.01 a
# token, which underflow a chunk, (d) a row with no token beside a full one
FWD_ROWS = {
    "mid_starts": dict(rows=((50, 77, 30, 41), (100, 64, 92))),
    "dead_groups": dict(rows=((50, 40), (150,))),
    "underflow": dict(rows=((50, 77, 30), (100, 64)), g_max=4.7, g_min=4.5),
    "empty_row": dict(rows=((), (100, 64, 92))),
}


# The decay's own gradient where decays underflow a chunk (0.01 a token): the
# running sum reaches -300 over a chunk of 64, where float32's step is 3e-5,
# and every `exp(G_i - G_j)` carries that, in the kernel (shifted adds) and in
# the plain form (`cumsum`) alike. Against the recurrence in float64 the
# decay's gradient reads, as shares of its largest value (0.005), 5.2e-6
# (chunks of 16) and 1.2e-5 (64) from the kernel and 1.3e-5 and 1.3e-5 from
# the plain form; the two stand 1.3e-5 and 1.6e-5 apart (PR 55's readings;
# q's, k's, v's and b's stay under 3e-7 and keep the limit of every other case).
UNDERFLOW_G_TOL = 5e-5


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("case", list(FWD_ROWS))
def test_the_forwards_one_kernel_is_intra_and_the_plain_walk(case, chunk, monkeypatch):
    """`kda_fwd_rule` in interpret mode against `decay`, `intra` and
    `states_scan` a group at a time: `O`, the state each group received
    (`bounds`, zeros from a row's first dead group on: nothing reads them),
    dead chunks zero; and `jax.grad` of the rule through both kernels is
    the plain rule's."""
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # groups of 64 cells of both rows
    q, k, v, g, b, seg = _inputs(T=256, **FWD_ROWS[case])
    f = jnp.where(g < 0, jnp.log(jnp.expm1(-jnp.where(g < 0, g, -1.0))), 0.0)
    A, bias = -jnp.ones((H,)), jnp.zeros((H, K))
    gs = kda._group(2, 256 // chunk, chunk, 128)
    with jax.default_matmul_precision("highest"):
        o, bounds = kda_fwd.rule_fwd(q, k, v, f, b, A, bias, seg, kda._live_chunks(seg, chunk),
                                     chunk, gs, interpret=True)
        want_o, res = kda._rule_fwd_groups(q, k, v, f, b, A, bias, seg, chunk, 128)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(bounds)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-6)
    live = np.asarray(kda._live_chunks(seg, chunk))  # [R]
    want_b = np.asarray(res[-1])
    assert bounds.shape == want_b.shape == (256 // chunk // gs, 2, H, K, K)
    for r in range(2):
        reached = -(-int(live[r]) // gs)
        np.testing.assert_allclose(np.asarray(bounds[:reached, r]), want_b[:reached, r],
                                   atol=2e-6, rtol=1e-5)
        assert not np.asarray(bounds[reached:, r]).any()
        assert not np.asarray(o[r, int(live[r]) * chunk:]).any()
    args = (q, k, v, g, b)
    w = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        _assert_close(_grads(lambda *a: _rule(*a, seg, chunk, "interpret"), args, w),
                      _grads(lambda *a: _rule(*a, seg, chunk, False), args, w), 2e-6,
                      g_tol=UNDERFLOW_G_TOL if case == "underflow" else None)


def test_the_forwards_one_kernel_takes_no_exponential_of_a_positive_number(monkeypatch):
    """Every `exp` of the kernel's trace gets an argument that is at most 0
    (`test_intra_takes_no_exponential_of_a_positive_number`'s meaning):
    the diagonal sub-blocks cell by cell under `min(G_i - G_j, 0)`, the
    others relative to the later sub-block's first cell."""
    seen = []
    exp = jnp.exp
    monkeypatch.setattr(kda_fwd.jnp, "exp", lambda x: seen.append(float(jnp.max(x))) or exp(x))
    monkeypatch.setattr(kda_fwd.pltpu, "roll", jnp.roll)  # the kernel's has no eager rule
    q, k, v, g, b, seg = _inputs(g_max=4.7)
    C = 64
    f = jnp.where(g < 0, jnp.log(jnp.expm1(-jnp.where(g < 0, g, -1.0))), 0.0)
    side = lambda h: (q[:1, :C, h], k[:1, :C, h], v[:1, :C, h], f[:1, :C, h], b[:1, :C, h:h + 1],
                      -jnp.ones((1, 1, K)), jnp.zeros((1, 1, K)))
    scratch = [np.zeros((2, 1, C, K), np.float32) for _ in range(3)]
    with jax.disable_jit():
        outs = kda_fwd._chunk([side(0), side(1)], [jnp.zeros((1, K, K))] * 2, scratch,
                              jnp.tile(seg[:1, :C], (1, 2)), 0, int(seg[0, C - 1]), jnp.float32)
    assert len(seen) >= 2 * (4 * 16 + 5) and max(seen) <= 0.0
    assert all(np.isfinite(np.asarray(a)).all() for o_s in outs for a in o_s)


def _mixer_inputs(D=32, T=64, lens=((20, 30, 10), (45, 11)), seed=0, heads=H, head_dim=K):
    cfg = KDAConfig(n_heads=heads, head_dim=head_dim, gate_rank=8, chunk_size=16)
    dense = lambda k, s, scale=None: jax.random.normal(k, s) * (scale or s[-2] ** -0.5)
    kp = jax.tree_util.tree_map(lambda a: a[0], kda.init_kda_params(
        cfg, D, dense, jax.random.PRNGKey(seed), 1, jnp.float32))
    proj = lambda key, n: jax.random.normal(jax.random.PRNGKey(key), (len(lens), T, n))
    xs = tuple(proj(i, heads * head_dim) for i in range(4)) + (proj(4, heads),)
    return cfg, kp, xs, jnp.asarray(_segments(lens, T)), lens


def test_a_packed_row_is_each_of_its_sequences_alone():
    """State and the three convolutions start afresh at every sequence
    start, and cells of padding, filled with NaN, reach neither a result
    nor a gradient."""
    cfg, kp, xs, seg, lens = _mixer_inputs()
    T = seg.shape[1]
    mixer = lambda xs, kp, seg: kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (len(lens), T, H, K)) * (seg > 0)[..., None, None]
    loss = lambda xs, kp: (mixer(xs, kp, seg) * w).sum()
    with jax.default_matmul_precision("highest"):
        packed = mixer(xs, kp, seg)
        g_xs, g_kp = jax.grad(loss, (0, 1))(xs, kp)
        for r, ls in enumerate(lens):
            o = 0
            for n in ls:  # the sequence alone in a row of its own, chunks from its start
                cut = lambda a: a[r:r + 1, o:o + n]
                alone_seg = jnp.ones((1, n), jnp.int32)
                alone = mixer(tuple(cut(a) for a in xs), kp, alone_seg)
                np.testing.assert_allclose(np.asarray(packed[r, o:o + n]),
                                           np.asarray(alone[0]), atol=2e-5)
                g_alone = jax.grad(lambda xs: (mixer(xs, kp, alone_seg) * cut(w)).sum())(
                    tuple(cut(a) for a in xs))
                for a, b in zip(g_xs, g_alone):
                    np.testing.assert_allclose(np.asarray(cut(a)), np.asarray(b), atol=5e-5)
                o += n
            assert not np.asarray(packed[r, o:]).any()  # padding gets nothing
        nan = lambda a: jnp.where((seg > 0).reshape(seg.shape + (1,) * (a.ndim - 2)), a, jnp.nan)
        xs_nan = tuple(nan(a) for a in xs)
        np.testing.assert_array_equal(np.asarray(mixer(xs_nan, kp, seg)), np.asarray(packed))
        n_xs, n_kp = jax.grad(loss, (0, 1))(xs_nan, kp)
    for a, b in zip(jax.tree_util.tree_leaves((n_xs, n_kp)),
                    jax.tree_util.tree_leaves((g_xs, g_kp))):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_row_no_chunk_divides_is_padded_and_cut_back():
    cfg, kp, xs, seg, _ = _mixer_inputs(T=50, lens=((20, 25), (7, 43)))
    with jax.default_matmul_precision("highest"):
        got = kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32)
        longer = kda.kda_mixer(*(jnp.pad(a, ((0, 0), (0, 14), (0, 0))) for a in xs), kp, cfg,
                               jnp.pad(seg, ((0, 0), (0, 14))), jnp.float32)
    assert got.shape == (2, 50, H, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(longer[:, :50]), atol=1e-6)


def test_seeded_parameters_neither_freeze_nor_blow_up():
    """`A_log` a head and `dt_bias` a channel as `init_ssm_params` draws
    them: before the low-rank product moves it a channel forgets at 0.999
    to 0.2 a token."""
    cfg = KDAConfig(n_heads=32, head_dim=128, gate_rank=128)
    dense = lambda k, s, scale=None: jax.random.normal(k, s) * (scale or s[-2] ** -0.5)
    kp = kda.init_kda_params(cfg, 64, dense, jax.random.PRNGKey(0), 2, jnp.float32)
    assert kp["A_log"].shape == (2, 32) and kp["dt_bias"].shape == (2, 4096)
    dt = jax.nn.softplus(kp["dt_bias"]).reshape(2, 32, 128)
    decay = np.asarray(jnp.exp(-jnp.exp(kp["A_log"])[..., None] * dt))
    assert 0.19 < decay.min() < 0.5 and 0.99 < decay.max() < 1.0
    assert kp["conv_q"].shape == (2, 4, 4096) and "conv_b" not in kp


def test_the_host_counts_chunks_by_the_devices_rule(monkeypatch):
    """Positions walked, chunks run (a group of every row's chunks at a
    time, up to the fullest row's last token), chunks with a token,
    sequence starts; several micro-batches are summed."""
    seg = _segments(((50, 40), (100, 92)), 192)
    monkeypatch.setattr(kda, "GROUP_CELLS", 1 << 20)
    assert kda.chunk_counts(seg, 64) == (2 * 192, 6, 5, 4)  # one group: every chunk
    monkeypatch.setattr(kda, "GROUP_CELLS", 128)  # a chunk of each row a group
    assert kda.chunk_counts(seg, 64) == (2 * 192, 6, 5, 4)
    half = _segments(((50, 40), (60,)), 192)
    assert kda.chunk_counts(half, 64) == (2 * 128, 4, 3, 3)
    assert kda.chunk_counts(np.stack([seg, half]), 64) == (2 * 192 + 2 * 128, 10, 8, 7)
    one = _segments(((70,),), 256)
    assert kda.chunk_counts(one, 16) == (128, 8, 5, 1)  # groups of 8 chunks
    live = np.asarray(kda._live_chunks(jnp.asarray(half), 64))
    assert live.tolist() == [2, 1]


# The taps' kernels at blocks of 32 cells in chunks of 16, rows of 128: (a) sequences that
# start and end inside blocks, (b) one that starts on a block's first row
# (32), on its second (65) and on its third (row 1: 66), so that the cells
# before come from the other block spec, and two that end on a block's last
# row (31, 95), (c) a row whose last live cell is in the first block beside a
# full one: every other block of it dead, (d) a row with no token
TAPS_ROWS = {
    "mid_starts": ((50, 41, 20), (100, 1, 13)),
    "edge_starts": ((32, 33, 31), (66, 30)),
    "first_block_only": ((20,), (128,)),
    "empty_row": ((), (100,)),
}


def _taps_inputs(rows, T, C, K, dtype, bias, seed=0):
    """x with NaN and inf written into its padding cells, w, b (or None),
    the output's cotangent, the segment ids."""
    rng = np.random.default_rng(seed)
    seg = _segments(rows, T)
    x = rng.normal(size=(len(rows), T, C))
    x = np.where((seg > 0)[..., None], x, np.where(np.arange(C) % 2, np.nan, np.inf))
    w, b, dy = rng.normal(size=(K, C)) / 2, rng.normal(size=(C,)), rng.normal(size=x.shape)
    cast = lambda a: jnp.asarray(a, dtype)
    return cast(x), cast(w), cast(b) if bias else None, cast(dy), jnp.asarray(seg)


def _taps_both(x, w, b, dy, seg, ref_dtype=None):
    """(y, dx, dw[, db]) of the kernels in interpret mode, and of
    `causal_conv` after the `where` (in `ref_dtype` where one is given)."""
    args = (x, w) + (() if b is None else (b,))
    with_b = lambda fn: (lambda x, w, *b: fn(x, w, b[0] if b else None))
    y, pull = jax.vjp(with_b(lambda x, w, b: kda_taps.taps(x, w, b, seg, True)), *args)
    plain = with_b(lambda x, w, b: causal_conv(
        jnp.where((seg > 0)[..., None], x, 0), w, b, seg))
    up = (lambda a: a.astype(ref_dtype)) if ref_dtype else (lambda a: a)
    want, want_pull = jax.vjp(plain, *(up(a) for a in args))
    return (y,) + pull(dy), (want,) + want_pull(up(dy))


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("K", [2, 4])
@pytest.mark.parametrize("case", list(TAPS_ROWS))
def test_the_taps_kernels_are_causal_conv_after_the_where(case, K, bias, monkeypatch):
    """`kda_taps_fwd` and `kda_taps_bwd` in interpret mode against
    `causal_conv(where(valid, x, 0))` and its `jax.vjp`, float32 to 1e-5:
    values, dx, dw and db; NaN and inf in x's padding cells reach none of
    them; a dead block's y and dx are zeros."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    monkeypatch.setattr(kda_taps, "CHUNK", 16)  # two chunks a block
    x, w, b, dy, seg = _taps_inputs(TAPS_ROWS[case], 128, 256, K, jnp.float32, bias)
    assert kda_taps.fits(128, 256, K)
    got, want = _taps_both(x, w, b, dy, seg)
    assert len(got) == 3 + bias
    for name, a, t in zip(("y", "dx", "dw", "db"), got, want):
        assert a.shape == t.shape and a.dtype == t.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(t), rtol=0, err_msg=name,
                                   atol=1e-5 * (float(jnp.abs(t).max()) + 1e-6))
    pad = np.asarray(seg) == 0
    assert not np.asarray(got[0])[pad].any() and not np.asarray(got[1])[pad].any()


def _assert_a_bf16_step_apart(got, want):
    """Each element within one bf16 step of the float32 one (and float32's
    own rounding of a sum of terms of the array's size, where they cancel)."""
    for name, a, t in zip(("y", "dx", "dw", "db"), got, want):
        assert a.dtype == jnp.bfloat16, name
        a, t = np.asarray(a, np.float32), np.asarray(t)
        step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(t), 1e-30))) - 7)
        assert (np.abs(a - t) <= step + 2e-6 * np.abs(t).max()).all(), name


@pytest.mark.parametrize("case", list(TAPS_ROWS))
def test_the_taps_kernels_in_bf16_are_a_step_from_the_float32_result(case, monkeypatch):
    """bf16 operands: sums, silu and the weights' sums over the row are
    float32 inside, so y, dx, dw and db stand within one bf16 step of
    `causal_conv` in float32 over the same (rounded) operands."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    monkeypatch.setattr(kda_taps, "CHUNK", 16)
    got, want = _taps_both(*_taps_inputs(TAPS_ROWS[case], 128, 256, 4, jnp.bfloat16, True),
                           ref_dtype=jnp.float32)
    _assert_a_bf16_step_apart(got, want)


@pytest.mark.parametrize("C", [2048, 384])
def test_the_taps_kernels_at_the_blocks_the_chip_runs(C):
    """Blocks of `ROWS` cells as the module has them, the qwen3-next cell's
    q and k of 2,048 columns (and a width of three strips): bf16, a row of
    two blocks, a sequence across their edge."""
    T = 2 * kda_taps.ROWS
    rows = ((T // 2 - 3, 40), (T // 4,))
    got, want = _taps_both(*_taps_inputs(rows, T, C, 4, jnp.bfloat16, False),
                           ref_dtype=jnp.float32)
    _assert_a_bf16_step_apart(got, want)


def test_the_taps_kernels_take_a_packed_row_as_each_of_its_sequences_alone(monkeypatch):
    """A sequence's y, and x's cotangent under it, are what the kernels give
    for the sequence alone at the head of a row of its own; dw is the sum of
    the sequences' own."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    lens = (50, 41, 20)
    x, w, b, dy, seg = _taps_inputs((lens,), 128, 128, 4, jnp.float32, True)
    fn = lambda x, w, b, seg: kda_taps.taps(x, w, b, seg, True)
    y, pull = jax.vjp(lambda x, w, b: fn(x, w, b, seg), x, w, b)
    dx, dw, db = pull(dy)
    o, dw_sum, db_sum = 0, 0.0, 0.0
    for n in lens:
        own = lambda a: jnp.pad(a[:, o:o + n], ((0, 0), (0, 128 - n), (0, 0)))
        alone = jnp.asarray(_segments(((n,),), 128))
        y1, pull1 = jax.vjp(lambda x, w, b: fn(x, w, b, alone), own(x), w, b)
        dx1, dw1, db1 = pull1(own(dy))
        np.testing.assert_allclose(np.asarray(y[0, o:o + n]), np.asarray(y1[0, :n]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(dx[0, o:o + n]), np.asarray(dx1[0, :n]), atol=1e-6)
        dw_sum, db_sum, o = dw_sum + dw1, db_sum + db1, o + n
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_sum), atol=2e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_sum), atol=2e-5)


def test_the_mixer_under_its_kernels_is_the_plain_mixer(monkeypatch):
    """`kda_mixer(..., kernel="interpret")`: the taps' kernels follow the
    rule's into interpret mode; the output and the gradients of q, k, v, f,
    b, of the three convolutions' weights and of the decay's two against
    `kernel=False`, at the limits the rule's kernels are held to."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    cfg, kp, xs, seg, _ = _mixer_inputs(head_dim=128)  # heads of whole lane tiles
    assert kda.taps_in_kernel(cfg, 64, "interpret") and not kda.taps_in_kernel(cfg, 64, False)
    ran = []
    taps = kda_taps.taps
    monkeypatch.setattr(kda_taps, "taps", lambda *a: ran.append(a[0].shape) or taps(*a))
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 2, 128)) * (seg > 0)[..., None, None]
    both = lambda kernel: jax.value_and_grad(lambda xs, kp: (kda.kda_mixer(
        *xs, kp, cfg, seg, jnp.float32, kernel=kernel) * w).sum(), (0, 1))(xs, kp)
    out = lambda kernel: kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32, kernel=kernel)
    with jax.default_matmul_precision("highest"):
        got_o, want_o = out("interpret"), out(False)
        assert ran == [(2, 64, 256)] * 3
        (_, got), (_, want) = both("interpret"), both(False)
        assert len(ran) == 6  # the plain mixer took none
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-6)
    _assert_seven(got[0], want[0], 1e-5)  # q's, k's, v's, f's and b's
    for n in ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias"):
        scale = float(jnp.abs(want[1][n]).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(got[1][n]), np.asarray(want[1][n]), rtol=0,
                                   atol=4e-5 * scale, err_msg=n)


def test_a_width_no_column_block_divides_takes_the_plain_form(monkeypatch):
    """The taps' kernels engage by the operands' shapes: a row no block of
    cells divides, or a width that is no multiple of the column block (heads
    of 64 here: 192 columns), runs `causal_conv` after the `where`."""
    monkeypatch.setattr(kda_taps, "ROWS", 32)
    monkeypatch.setattr(kda_taps, "taps", lambda *a: pytest.fail("the kernels ran"))
    cfg, kp, xs, seg, _ = _mixer_inputs(lens=((40, 20),), heads=3, head_dim=64)
    assert kda_taps.fits(64, 256, 4) and not kda_taps.fits(64, 192, 4)
    assert not kda_taps.fits(48, 256, 4) and not kda_taps.fits(64, 256, 8)
    assert not kda.taps_in_kernel(cfg, 64, "interpret")
    wide = KDAConfig(n_heads=2, head_dim=128, gate_rank=8, chunk_size=16)
    assert kda.taps_in_kernel(wide, 64, True) and not kda.taps_in_kernel(wide, 80, True)
    o = kda.kda_mixer(*xs, kp, cfg, seg, jnp.float32, kernel=False)
    assert o.shape == (1, 64, 3, 64) and np.isfinite(np.asarray(o)).all()
