"""`ops/band_loop.stretch` against the same function over the whole row,
float32 on the CPU: values and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import band_loop

BAND, T, D, F = 8, 48, 16, 24
CALLS = []


@pytest.fixture(autouse=True)
def small_band(monkeypatch):
    monkeypatch.setattr(band_loop, "_BAND", BAND)
    jax.clear_caches()  # a trace made at another band length is no one's to find


def mlp(static, w, xs, side):
    """A norm, a gated MLP, a residual and a second result (the router's
    kind: float32 scores and an integer choice), rotated by `side`."""
    CALLS.append(static)
    (x,), (c,) = xs, side
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w["ln"]
    m = (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    scores = (h * c) @ w["route"]
    return x + m * static, scores, jnp.argmax(scores, -1).astype(jnp.int32)


def whole(fn, static, weights, xs, side=()):
    """The same stretch over every cell: what `stretch` is held to."""
    return tuple(fn(static, weights, tuple(xs), tuple(side)))


def operands(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    w = {"ln": 1 + 0.1 * jax.random.normal(k[0], (D,)),
         "gate": jax.random.normal(k[1], (D, F)) / 4, "up": jax.random.normal(k[2], (D, F)) / 4,
         "down": jax.random.normal(k[3], (F, D)) / 5, "route": jax.random.normal(k[4], (D, 5))}
    return w, jax.random.normal(k[5], (1, T, D)), jax.random.normal(k[6], (1, T, D))


def loss_of(run, tokens):
    """A scalar of the live cells' results alone (a dead cell is no one's
    to read), and the results."""
    live = (jnp.arange(T) < tokens)[None, :, None]

    def loss(w, x, c):
        y, scores, choice = run(w, x, c)
        return jnp.sum(jnp.where(live, jnp.sin(y), 0)) + jnp.sum(
            jnp.where(live, scores * scores, 0)), (y, scores, choice)

    return loss


@pytest.mark.parametrize("tokens", [0, 5, 8, 9, 20, 40, 47, 48], ids=[
    "no_live_band", "one_band_half_full", "one_band", "a_band_and_a_cell",
    "last_band_half_full", "all_but_one_band", "all_but_one_cell", "every_band"])
def test_stretch_matches_the_whole_row(tokens):
    w, x, c = operands()
    seg = (jnp.arange(T) < tokens).astype(jnp.int32)[None]
    n_live = band_loop.live_bands(seg)
    assert int(n_live) == -(-tokens // BAND)
    cells = int(n_live) * BAND
    looped = loss_of(lambda w, x, c: band_loop.stretch(mlp, 0.5, w, (x,), (c,), n_live), tokens)
    plain = loss_of(lambda w, x, c: whole(mlp, 0.5, w, (x,), (c,)), tokens)
    (l0, got0), g0 = jax.jit(jax.value_and_grad(plain, (0, 1), has_aux=True))(w, x, c)
    (l1, got1), g1 = jax.jit(jax.value_and_grad(looped, (0, 1), has_aux=True))(w, x, c)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    for a, b in zip(got1, got0):
        np.testing.assert_allclose(a[:, :cells], b[:, :cells], rtol=1e-5, atol=1e-6)
        assert not np.any(np.asarray(a[:, cells:])), "a dead band's results are zeros"
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert not np.any(np.asarray(g1[1][:, cells:])), "nothing flows into a dead band"


def heads_out(static, w, xs, side):
    """An attention layer's way out: the kernel's output as the kernel
    wrote it, sequence-minor `[1, H, V, cells]`, through the output
    projection onto the residual `[1, cells, D]`."""
    (a, x), _ = xs, side
    return (x + static * jnp.einsum("rhvt,hvd->rtd", a, w["wo"]),)


@pytest.mark.parametrize("tokens", [0, 5, 9, 40, 48], ids=[
    "no_live_band", "one_band_half_full", "a_band_and_a_cell", "all_but_one_band", "every_band"])
def test_a_sequence_minor_input_is_cut_and_its_cotangent_put_along_its_last_axis(tokens):
    """`stretch(minor=(0,))`: the first input lies `[1, H, V, T]` and the
    function sees `[1, H, V, band]` of it; values, the weight's gradient
    and both inputs' against the whole row, and the minor input's
    cotangent comes back `[1, H, V, T]`, zero past the live bands."""
    H, V = 3, 4
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    w = {"wo": jax.random.normal(k[0], (H, V, D)) / 3}
    a, x = jax.random.normal(k[1], (1, H, V, T)), jax.random.normal(k[2], (1, T, D))
    n_live = band_loop.live_bands((jnp.arange(T) < tokens).astype(jnp.int32)[None])
    cells = int(n_live) * BAND
    live = (jnp.arange(T) < tokens)[None, :, None]
    loss = lambda run: lambda w, a, x: jnp.sum(jnp.where(live, jnp.sin(run(w, a, x)[0]), 0))
    looped = loss(lambda w, a, x: band_loop.stretch(heads_out, 0.5, w, (a, x), (), n_live,
                                                    minor=(0,)))
    plain = loss(lambda w, a, x: whole(heads_out, 0.5, w, (a, x)))
    l0, g0 = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(w, a, x)
    l1, g1 = jax.jit(jax.value_and_grad(looped, (0, 1, 2)))(w, a, x)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert g1[1].shape == (1, H, V, T) and not np.any(np.asarray(g1[1][..., cells:]))
    assert not np.any(np.asarray(g1[2][:, cells:]))


def test_two_rows_with_different_counts_share_one_trace():
    """The count is a value of the run: one compiled program, two rows."""
    w, x, c = operands()
    step = jax.jit(lambda w, x, c, n: jax.grad(
        lambda w: jnp.sum(band_loop.stretch(mlp, 1.0, w, (x,), (c,), n)[0] ** 2))(w))
    plain = jax.jit(lambda w, x, c, cells: jax.grad(lambda w: jnp.sum(jnp.where(
        (jnp.arange(T) < cells)[None, :, None], whole(mlp, 1.0, w, (x,), (c,))[0], 0) ** 2))(w))
    traced = []
    for n in (2, 5):
        before = len(CALLS)
        got = step(w, x, c, jnp.int32(n))
        traced.append(len(CALLS) - before)
        want = plain(w, x, c, n * BAND)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert traced[0] > 0 and traced[1] == 0


def test_a_second_layer_and_a_second_program_find_the_stretch_traced():
    """What the set-up budget rests on: `stretch` is one function jitted at
    module level, so the second layer of a kind, and a second program of
    the process, run none of the stretch's Python again (forward rule,
    backward rule and transposition come from jax's own caches)."""
    w, x, c = operands()

    def program(layers):
        def loss(w, y):
            for _ in range(layers):
                y = band_loop.stretch(mlp, 2.0, w, (y,), (c,), jnp.int32(3))[0]
            return jnp.sum(y ** 2)

        return jax.jit(jax.grad(loss, (0, 1)))

    del CALLS[:]
    program(1)(w, x)
    one = len(CALLS)
    jax.clear_caches()
    del CALLS[:]
    program(2)(w, x)
    assert 0 < len(CALLS) == one, "two layers of one kind: one trace of the stretch"
    program(3)(w, x)
    assert len(CALLS) == one, "a second program finds it traced"


def test_host_count_is_the_devices():
    rng = np.random.default_rng(0)
    for tokens in (0, 1, 7, 8, 9, 31, 48):
        seg = np.zeros((1, T), np.int32)
        seg[0, :tokens] = rng.integers(1, 4, tokens)
        assert band_loop.band_cells_run(seg) == int(band_loop.live_bands(jnp.asarray(seg))) * BAND
    # rows together, and a row under two bands, run whole
    assert band_loop.band_cells_run(np.ones((2, T), np.int32)) == 2 * T
    assert band_loop.band_cells_run(np.ones((1, BAND), np.int32)) == BAND
    assert not band_loop.loops(1, BAND) and not band_loop.loops(2, T) and band_loop.loops(1, T)
    assert not band_loop.loops(1, T + 1)


def test_weight_gradients_are_summed_in_float32():
    """bf16 operands: five bands' weight gradients meet in float32 and are
    cast once, so they lose nothing a whole-row product keeps."""
    w, x, c = operands(3)
    to16 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), t)
    w16, x16, c16 = to16(w), to16(x), to16(c)
    n = T // BAND - 1
    total = lambda y: jnp.sum(y[:, :n * BAND].astype(jnp.float32))
    g_loop = jax.grad(lambda w: total(
        band_loop.stretch(mlp, 1.0, w, (x16,), (c16,), jnp.int32(n))[0]))(w16)
    g_f32 = jax.grad(lambda w: total(whole(mlp, 1.0, w, (x,), (c,))[0]))(w)
    g_whole = jax.grad(lambda w: total(whole(mlp, 1.0, w, (x16,), (c16,))[0]))(w16)
    for name in w:
        err = lambda g: float(jnp.max(jnp.abs(g[name].astype(jnp.float32) - g_f32[name])))
        assert g_loop[name].dtype == jnp.bfloat16
        assert err(g_loop) <= 2 * err(g_whole) + 1e-3, name


def running(static, w, xs, side, carry):
    """Token-wise but for what it is handed: a running sum of every cell
    so far, the cell before (a tap), and how many cells came before (an
    integer that rides along and numbers the cells)."""
    CALLS.append(static)
    (x,), (c,) = xs, side
    total, last, seen = carry
    h = jnp.tanh((x * c) @ w["gate"])
    sums = total[:, None] + jnp.cumsum(h, axis=1)
    before = jnp.concatenate([last, h[:, :-1]], axis=1)
    y = x + (static * sums + before * jax.nn.silu(h)) @ w["down"]
    number = seen[:, None] + jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    return (y, number), (sums[:, -1], h[:, -1:], seen + x.shape[1])


def start():
    return (jnp.zeros((1, F)), jnp.zeros((1, 1, F)), jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("tokens", [0, 5, 8, 9, 20, 40, 47, 48], ids=[
    "no_live_band", "one_band_half_full", "one_band", "a_band_and_a_cell",
    "last_band_half_full", "all_but_one_band", "all_but_one_cell", "every_band"])
def test_a_carried_stretch_matches_the_whole_row(tokens):
    """`carried`: band after band, each handed what the one before it left,
    against the same function over the live cells at once from the same
    start: values, the integer result, and every gradient (weights and x,
    through the carry from the last live band back to the first)."""
    w, x, c = operands()
    w = {n: w[n] for n in ("gate", "down")}
    seg = (jnp.arange(T) < tokens).astype(jnp.int32)[None]
    n_live = band_loop.live_bands(seg)
    cells = int(n_live) * BAND
    cot = jax.random.normal(jax.random.PRNGKey(11), (1, T, D))

    def loss(run):
        def f(w, x):
            y, number = run(w, x)
            return jnp.sum(y[:, :cells] * cot[:, :cells]), (y, number)
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))

    looped = loss(lambda w, x: band_loop.carried(running, 0.5, w, (x,), (c,), start(), n_live))
    # the whole row: the live cells as one band of their own
    plain = loss(lambda w, x: tuple(jnp.pad(a, ((0, 0), (0, T - cells)) + ((0, 0),) * (a.ndim - 2))
                                    for a in running(0.5, w, (x[:, :cells],), (c[:, :cells],),
                                                     start())[0]))
    (l1, (y1, n1)), g1 = looped(w, x)
    if not cells:
        assert not np.asarray(y1).any() and not float(l1)
        assert not any(np.asarray(g).any() for g in jax.tree_util.tree_leaves(g1))
        return
    (l0, (y0, n0)), g0 = plain(w, x)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(n1, n0)
    assert not np.asarray(y1[:, cells:]).any(), "a dead band's results are zeros"
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert not np.asarray(g1[1][:, cells:]).any(), "nothing flows into a dead band"


def test_a_carried_stretch_is_traced_once_for_two_counts_and_a_second_layer():
    """The trip count is a value of the run and the jit is the module's:
    two counts, two layers of one kind and a second program run the
    function's Python once each way (forward rule, backward rule)."""
    w, x, c = operands()
    w = {n: w[n] for n in ("gate", "down")}

    def program(layers):
        def loss(w, y, n):
            for _ in range(layers):
                y = band_loop.carried(running, 2.0, w, (y,), (c,), start(), n)[0]
            return jnp.sum(y ** 2)

        return jax.jit(jax.grad(loss, (0, 1)))

    del CALLS[:]
    one = program(1)
    one(w, x, jnp.int32(2))
    traced = len(CALLS)
    one(w, x, jnp.int32(5))
    program(2)(w, x, jnp.int32(3))
    assert 0 < traced == len(CALLS)


def test_a_carried_stretch_sums_weight_gradients_in_float32():
    w, x, c = operands(3)
    w = {n: w[n] for n in ("gate", "down")}
    to16 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), t)
    carry16 = lambda: to16(start()[:2]) + start()[2:]
    n = T // BAND - 1
    total = lambda y: jnp.sum(y[:, :n * BAND].astype(jnp.float32))
    g_loop = jax.grad(lambda w: total(band_loop.carried(
        running, 1.0, w, (to16(x),), (to16(c),), carry16(), jnp.int32(n))[0]))(to16(w))
    g_f32 = jax.grad(lambda w: total(running(1.0, w, (x,), (c,), start())[0][0]))(w)
    g_whole = jax.grad(lambda w: total(
        running(1.0, w, (to16(x),), (to16(c),), carry16())[0][0]))(to16(w))
    for name in w:
        err = lambda g: float(jnp.max(jnp.abs(g[name].astype(jnp.float32) - g_f32[name])))
        assert g_loop[name].dtype == jnp.bfloat16
        assert err(g_loop) <= 2 * err(g_whole) + 2e-2 * float(jnp.abs(g_f32[name]).max()), name
