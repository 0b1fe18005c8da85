"""A stack whose window layers and full layers each turn q and k by a
rotary table of their own (`config.RotarySet`: plain, or YaRN's with its
attention factor on `cos` and `sin`) over softmax-routed experts, and the
`mellum` family: the program against the plain reference
`benchmark/reference/mellum.py` on the CPU, float32, seeded random
weights, toy widths (hidden 64, 4 / 2 heads of 16, four layers `S S S F`,
a window of 8, YaRN x16 over an original context of 64 so that a head's
eight frequencies hold a kept, two blended and five divided ones, 16
routed experts top-4 with 4 held)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models import transformer
from areal_tpu.models.config import LayerKind, RotarySet, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from areal_tpu.ops.rotary import (
    apply_rotary, rotary_cos_sin, rotary_inv_freq, yarn_attention_factor)
from benchmark.reference import mellum as ref

from tests.model.test_hybrid_stack import _ppo_loss
from tests.model.test_kda_stack import _program_logprobs
from tests.model.test_layer_kinds import _assert_trees_close, _packed, small_bands

S, F = "sliding_attention", "full_attention"
FACTOR = 1.2772588722239782
YARN = dict(rope_type="yarn", rope_theta=10000, factor=16, original_max_position_embeddings=64,
            beta_fast=32, beta_slow=1, attention_factor=FACTOR)
HF = dict(
    model_type="mellum", hidden_size=64, intermediate_size=96, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=64,
    max_position_embeddings=512, rms_norm_eps=1e-6, hidden_act="silu", attention_bias=False,
    layer_types=[S, S, S, F], mlp_layer_types=["sparse"] * 4, sliding_window=8,
    rope_parameters={F: YARN, S: dict(rope_type="default", rope_theta=10000)},
    num_experts=4, num_experts_routed=16, experts_held_first=4, num_experts_per_tok=4,
    moe_intermediate_size=16, norm_topk_prob=True, tie_word_embeddings=False,
    use_sliding_window=True, max_window_layers=0,
)
LONG = dict(rows=((80, 40),), row_len=128)  # longer than the window and the original context
CONTROLS = dict(
    plain_on_full=dict(tables={F: S}), yarn_on_window=dict(tables={S: F}),
    no_factor=dict(attention_factor=False), half_window=dict(window=4),
    no_window=dict(window=None), window_off_by_one=dict(window=9),
    no_qk_norm=dict(qk_norm=False), top2=dict(top_k=2), no_renorm=dict(renorm=False))


def _cfg(hf=HF, **over):
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    return dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32", **over)


def _params(cfg, seed=0):
    """The seeded draw, its norms moved off their start."""
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))

    def one(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name:
            return a * (1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(len(name)), a.shape))
        return a

    return jax.tree_util.tree_map_with_path(one, params)


def _reference_logprobs(params, hf, seqs, control=None):
    out = []
    for _, _, t in seqs:
        n = -(-len(t) // ref.ROWS) * ref.ROWS
        ids = jnp.asarray(np.concatenate([t, np.zeros(n - len(t), np.int64)]), jnp.int32)
        out.append(ref._forward(params, ids, ref._small(hf), control)[: len(t) - 1])
    return out


@pytest.mark.parametrize("remat", ["full", "none"])
def test_the_stack_matches_the_reference_through_a_ppo_step(remat, monkeypatch):
    """`S S S F` in one scan, one traced body: logprobs, the PPO loss and
    every parameter's gradient, over a sequence of 80 tokens (ten windows,
    more than the original context of 64) packed beside one of 40."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)  # several tiles an expert at toy size
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == ["attention+moe"] * 4
    assert [(k.window, k.table) for k in cfg.kinds()] == [(8, S)] * 3 + [(None, F)]
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [(("attention+moe",), 4)]
    params = _params(cfg)
    assert set(params) == {"embedding", "layers", "final_norm", "head"}
    assert set(params["layers"]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    ids, seg, pos, seqs = _packed(**LONG)
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat=remat)
    want = _reference_logprobs(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _ppo_loss(_program_logprobs(p, cfg, ids, seg, pos, seqs, remat=remat))
    plain = lambda p: _ppo_loss(_reference_logprobs(p, HF, seqs))
    (l_prog, g_prog), (l_ref, g_ref) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (prog, plain))
    np.testing.assert_allclose(float(l_prog), float(l_ref), atol=2e-5)
    _assert_trees_close(g_prog, g_ref, rtol=1e-4)


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_every_control_of_the_tolerance_moves_the_reference(control):
    """What `scripts/tolerance_controls_mellum.py` changes in the reference
    shows in its logprobs at toy size too: no control is a no-op of the
    reference's code. The two swaps of tables are the program's too: a
    stack whose layers name the other set reads what the control reads."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(**LONG)
    want = _reference_logprobs(params, HF, seqs)
    got = _reference_logprobs(params, HF, seqs, CONTROLS[control])
    moved = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    assert moved > 1e-3, moved
    tables = CONTROLS[control].get("tables")
    if tables:
        swapped = dataclasses.replace(cfg, layer_kinds=tuple(
            dataclasses.replace(k, rotary_set=tables.get(k.rotary_set, k.rotary_set))
            for k in cfg.kinds()))
        for g, w in zip(_program_logprobs(params, swapped, ids, seg, pos, seqs), got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_a_packed_row_is_each_of_its_sequences_alone_through_the_stack():
    """Logprobs and the gradient of their sum: three sequences in one row
    against each in a row of its own (positions restart at a sequence's
    start under both tables, and no window reaches over one)."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((70, 30, 20),), row_len=128)
    packed = lambda p: _program_logprobs(p, cfg, ids, seg, pos, seqs)

    def alone(p):
        out = []
        for _, _, t in seqs:
            one = jnp.asarray(t[None], jnp.int32)
            out += _program_logprobs(p, cfg, one, jnp.ones_like(one),
                                     jnp.arange(len(t))[None], [(0, 0, t)])
        return out

    both = lambda fn: jax.jit(
        lambda p: (fn(p), jax.grad(lambda p: sum(x.sum() for x in fn(p)))(p)))
    (lp_packed, g_packed), (lp_alone, g_alone) = both(packed)(params), both(alone)(params)
    for g, w in zip(lp_packed, lp_alone):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    _assert_trees_close(g_packed, g_alone, rtol=1e-4)


@pytest.mark.parametrize("types", [[S, S, S, F], [F, S, F, S]], ids=["SSSF", "FSFS"])
def test_the_band_loop_rotates_by_each_layers_own_table(types, monkeypatch):
    """A half-empty row walks its live bands (`ops/band_loop.stretch`): the
    first stretch of the one scanned body turns q and k band by band by
    the table the layer's variant index picks, and logprobs and gradients
    are the whole row's and the reference's. `F S F S` is a scan over a
    unit of two bodies of one variant each: no index, the table is static."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, layer_types=types)
    cfg = _cfg(hf)
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((70,),), row_len=192)
    assert looping_layers(cfg, 1, 192) == 0  # under two bands of 1,024
    whole = lambda p: sum(x.sum() for x in _program_logprobs(
        p, cfg, ids, seg, pos, seqs, remat="full", bands=True))
    want, g_want = jax.jit(jax.value_and_grad(whole))(params)
    ran = small_bands(monkeypatch)
    assert looping_layers(cfg, 1, 192) == 4
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert ran.count("_before_mixer") >= 1 and ran.count("_after_mixer") >= 1
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)
    for g, w in zip(_program_logprobs(params, cfg, ids, seg, pos, seqs, bands=True),
                    _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test at the cell's own counts: the held-experts results of
    all 4 shares of 16 experts of 64 under top-8 add up to what the
    reference gives for the whole layer, and every pair is one share's."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_experts=64, num_experts_per_tok=8)
    del hf["num_experts_routed"], hf["experts_held_first"]
    cfg = _cfg(hf)
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 64))
    mats = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), []
        for share in range(4):
            held = (16 * share, 16)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 16] if k in mats else v) for k, v in mlp.items()}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + [float(aux["pairs_held"])]
            part = ref.expert_layer(h, mp, dict(
                hf, num_experts=16, num_experts_routed=64, experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert sum(pairs) == h.shape[0] * 8 and min(pairs) > 0


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs guide is not installed here")
    return next(r for r in map(json.loads, open(catalog))
                if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def test_the_family_takes_the_catalog_rows_config_as_it_is():
    hf = _catalog_row()["config"]
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    kinds = cfg.kinds()
    assert len(kinds) == 28 and [k.window for k in kinds] == [1024, 1024, 1024, None] * 7
    assert [k.rotary_set for k in kinds] == hf["layer_types"]
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (
        2304, 32, 4, 128, 98304)
    assert cfg.qk_norm and not cfg.attn_bias and not cfg.tied_embeddings and cfg.mtp is None
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.expert_intermediate_dim,
            cfg.moe.score_func, cfg.moe.route_norm, cfg.moe.n_shared_experts,
            cfg.moe.experts_held) == (64, 8, 896, "softmax", True, 0, None)
    assert cfg.rotary_sets == {
        S: RotarySet(base=500000.0),
        F: RotarySet(base=500000.0, scaling=16.0, scaling_type="yarn", scaling_params=dict(
            original_max_position_embeddings=8192, beta_fast=32, beta_slow=1),
            attention_factor=FACTOR)}
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [(("attention+moe",), 28)]


def test_mellum_config_and_checkpoint_layout_round_trip():
    fam = get_family("mellum")
    cfg = _cfg()
    assert cfg.moe.experts_held == (4, 4) and cfg.moe.num_experts == 16
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    # through the kwargs the launcher hands on (benchmark/model.transformer_config_kwargs)
    assert TransformerConfig(**dataclasses.asdict(cfg)).rotary_sets == cfg.rotary_sets
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    lp = jax.tree_util.tree_map(lambda a: a[3], params["layers"])
    at = "model.layers.3.self_attn"
    assert sd[f"{at}.q_proj.weight"].shape == (64, 64) and sd[f"{at}.k_proj.weight"].shape == (32, 64)
    np.testing.assert_array_equal(sd[f"{at}.q_proj.weight"], lp["attn"]["wq"].T)
    np.testing.assert_array_equal(sd[f"{at}.q_norm.weight"], lp["attn"]["q_norm"])
    np.testing.assert_array_equal(sd[f"{at}.k_norm.weight"], lp["attn"]["k_norm"])
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in sd  # held: 4..7
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    np.testing.assert_array_equal(sd["model.layers.3.mlp.experts.7.down_proj.weight"],
                                  lp["mlp"]["w_down"][3].T)
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 64)
    for name in ("0.input_layernorm.weight", "2.post_attention_layernorm.weight",
                 "3.self_attn.o_proj.weight", "0.self_attn.v_proj.weight"):
        assert f"model.layers.{name}" in sd
    assert "lm_head.weight" in sd and "model.norm.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_the_yarn_table_is_the_closed_form_at_the_published_numbers():
    """`low` 18 and `high` 35 of a head of 128 at theta 5e5 over an original
    context of 8,192: frequencies 0-18 kept, 35-63 divided by 16, a linear
    ramp between; the reference's own table agrees; the attention factor
    absent is `0.1 ln 16 + 1`, which is the published one."""
    p = dict(original_max_position_embeddings=8192, beta_fast=32, beta_slow=1)
    turns = lambda b: 128 * math.log(8192 / (b * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (18, 35)
    plain = rotary_inv_freq(128, 5e5).astype(np.float64)
    np.testing.assert_allclose(plain, 5e5 ** (-np.arange(64) / 64.0), rtol=1e-6)
    got = rotary_inv_freq(128, 5e5, 16.0, "yarn", p).astype(np.float64)
    ratio = got / plain
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)
    ramp = (np.arange(19, 35) - 18) / 17.0
    np.testing.assert_allclose(ratio[19:35], 1 - ramp + ramp / 16, rtol=1e-6)
    assert np.all(np.diff(ratio[18:36]) < 0)
    inv, amp = ref.rope_table(128, dict(p, rope_type="yarn", rope_theta=500000, factor=16))
    np.testing.assert_allclose(inv, got, rtol=1e-6)
    assert amp == yarn_attention_factor(16.0, p) == 0.1 * math.log(16) + 1 == FACTOR
    assert yarn_attention_factor(16.0, dict(attention_factor=1.5)) == 1.5
    assert yarn_attention_factor(16.0, dict(mscale=1.0, mscale_all_dim=1.0)) == 1.0
    # without `truncate` the ramp's ends are no whole dimensions
    loose = rotary_inv_freq(128, 5e5, 16.0, "yarn", dict(p, truncate=False)) / plain
    assert loose[18] == 1.0 and loose[35] == pytest.approx(1 / 16)
    assert loose[19] > ratio[19] and loose[34] < ratio[34]  # a ramp from 18.08 to 34.99
    # the toy head's: a kept, two blended, five divided
    toy = rotary_inv_freq(16, 1e4, 16.0, "yarn", dict(p, original_max_position_embeddings=64))
    np.testing.assert_allclose(toy / rotary_inv_freq(16, 1e4),
                               [1, 0.6875, 0.375] + [0.0625] * 5, rtol=1e-6)


def test_the_factor_on_the_tables_is_its_square_on_the_softmax_scale():
    """HF's way (cos and sin times the attention factor, so q and k each
    carry it) against the factor's square on that variant's softmax scale
    over tables of unit amplitude (`_attention_kernel(softmax_scale=)`):
    the same attention."""
    cfg = _cfg()
    ids, seg, pos, _ = _packed(**LONG)
    rs = cfg.rotary_sets[F]
    inv = jnp.asarray(rotary_inv_freq(16, rs.base, rs.scaling, rs.scaling_type,
                                      rs.scaling_params))
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 128, n, 16))
               for i, n in enumerate((4, 2, 2)))
    attend = lambda q, k, scale: transformer._attention_kernel(
        q, k, v, seg, pos, "reference", cfg, None, None, scale)
    turn = lambda x, table: apply_rotary(x, *table)
    on_tables = rotary_cos_sin(pos, inv, rs.attention_factor)
    unit = rotary_cos_sin(pos, inv)
    np.testing.assert_allclose(np.asarray(on_tables[0]), FACTOR * np.asarray(unit[0]), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        a = attend(turn(q, on_tables), turn(k, on_tables), None)
        b = attend(turn(q, unit), turn(k, unit), FACTOR ** 2 * 16 ** -0.5)
        c = attend(turn(q, unit), turn(k, unit), None)
    real = np.asarray(seg)[0] > 0
    np.testing.assert_allclose(np.asarray(a)[0, real], np.asarray(b)[0, real], atol=1e-5)
    assert float(jnp.abs(a - c)[0, real].max()) > 1e-2


def test_one_set_traces_no_selection_and_two_sets_one():
    """A scan whose layers all name one set, or a stack with the one table
    of old, has no dynamic slice of a table in its program; `S S S F`
    takes its table by the variant index once a layer."""
    ids, seg, pos, _ = _packed(**LONG)

    def jaxpr(cfg):
        params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(lambda p: forward(
            p, cfg, ids, seg, pos, attn_impl="reference"))(params))

    picks = lambda text: text.count("dynamic_slice")
    two = jaxpr(_cfg())
    one = jaxpr(_cfg(dict(HF, layer_types=[S] * 4)))
    old = jaxpr(_cfg(dict(HF, layer_types=[S] * 4), rotary_sets=None, layer_kinds=tuple(
        LayerKind(mlp="moe", window=8) for _ in range(4))))
    assert picks(two) > picks(one) == picks(old)
    assert " cond[" in two and " cond[" not in one  # the switch of masks


@pytest.mark.parametrize("over,match", [
    (dict(mlp_layer_types=["sparse", "dense", "sparse", "sparse"]), "mlp_layer_types"),
    (dict(rope_parameters={S: HF["rope_parameters"][S]}), "rope_parameters"),
    (dict(rope_parameters={**HF["rope_parameters"], F: dict(YARN, rope_type="llama3")}),
     "rope_type 'llama3'"),
    (dict(partial_rotary_factor=0.5), "partial_rotary_factor"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
], ids=["dense_layer", "missing_set", "rope_type", "partial_rotation", "unnormalised_gates"])
def test_what_the_family_cannot_run_is_refused_by_name(over, match):
    with pytest.raises(NotImplementedError, match=match):
        _cfg(dict(HF, **over))


def test_what_a_stack_of_rotary_sets_cannot_run_is_refused_by_mechanism():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    for where in ("prefill", "paged_decode_step", "ServingEngine"):
        with pytest.raises(NotImplementedError, match=(
                r"a rotary table a kind of layer.*2 rotary sets "
                r"\['full_attention', 'sliding_attention'\]")):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError, match=(
                r"a scaled table's attention factor on a plain head.*"
                r"\['full_attention'\].*1\.2772588722239782")):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError, match="a cache manager with a kind per layer"):
            cfg.require_plain_stack(where)
    # one set, every layer the same: the factor alone, and no word of a kind per layer
    same = _cfg(dict(HF, layer_types=[F] * 4))
    with pytest.raises(NotImplementedError) as e:
        same.require_plain_stack("prefill")
    assert "attention factor on a plain head" in str(e.value)
    assert "rotary table a kind" not in str(e.value) and "cache manager" not in str(e.value)
    # a set's name that is none of the stack's; sets beside what has tables of its own
    with pytest.raises(ValueError, match="rotary sets"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(rotary_set="a"),))
    with pytest.raises(ValueError, match="rotary sets"):
        TransformerConfig(n_layers=2, rotary_sets={"a": RotarySet()},
                          layer_kinds=(LayerKind(rotary_set="a"), LayerKind()))
    with pytest.raises(ValueError, match="that no layer names"):
        TransformerConfig(rotary_sets={"a": RotarySet()})
    with pytest.raises(NotImplementedError, match="rotary sets beside"):
        TransformerConfig(n_layers=1, rotary_sets={"a": RotarySet()}, indexer=dict(),
                          layer_kinds=(LayerKind(rotary_set="a"),))
    with pytest.raises(NotImplementedError, match="a plain head's table"):
        LayerKind(rotary_set="a", latent=True)
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mixer="ssm", rotary_set="a")
