"""A stack of gated short-convolution layers beside GQA attention with a q/k
norm, a dense MLP in the leading layer and sigmoid-routed experts chosen on
score + bias in the rest, a share of them held (the `lfm2_moe` family): the
program against the plain reference `benchmark/reference/lfm2_moe.py` on the
CPU, float32, seeded random weights, toy widths (hidden 64, four heads of 16
over two, experts of 16, five layers `conv | attention conv conv conv`),
through the forward pass, a PPO step's loss and gradients, the band loop,
the shares of an expert layer and the family's mapping."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import ConvConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from benchmark.reference import lfm2_moe as ref

from tests.model.test_hybrid_stack import _ppo_loss
from tests.model.test_kda_stack import _no_bias_grad, _program_logprobs
from tests.model.test_layer_kinds import _assert_trees_close, _packed, small_bands

C, F = "conv", "full_attention"
HF = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=96, moe_intermediate_size=16,
    num_hidden_layers=5, layer_types=[C, F, C, C, C], num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=64, max_position_embeddings=512,
    norm_eps=1e-5, rope_theta=1000000.0, conv_L_cache=3, conv_bias=False,
    num_experts=4, num_experts_routed=8, experts_held_first=0, num_experts_per_tok=4,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1.0,
    tie_word_embeddings=True,
)
KINDS = "dense.conv.k3,moe.full.rope,moe.conv.k3 x3"
CONTROLS = ("taps_reversed", "no_B", "no_C", "conv_silu", "no_conv", "select_no_bias", "top_2",
            "no_renorm", "no_qk_norm", "no_rope", "norm_eps_1e-20")


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


@pytest.mark.parametrize("remat", ["full", "none", "mlp"])
def test_the_stack_matches_the_reference_through_a_ppo_step(remat, monkeypatch):
    """`conv+dense | attention+moe | conv+moe x 3`, the last three one scan:
    logprobs, the PPO loss and every parameter's gradient, two sequences
    packed in a row."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == ["conv+dense", "attention+moe"] + ["conv+moe"] * 3
    assert [(s.unit, s.repeats) for s in cfg.segments()] == [
        (("conv+dense",), 1), (("attention+moe",), 1), (("conv+moe",), 3)]
    assert cfg.conv == ConvConfig(kernel=3, bias=False) and cfg.tied_embeddings and cfg.qk_norm
    assert cfg.moe.route_norm_eps == 1e-6 and cfg.moe.experts_held == (0, 4)
    params = _params(cfg)
    assert set(params["stacks"]) == {"conv+dense", "attention+moe", "conv+moe"} and "head" not in params
    conv = params["stacks"]["conv+moe"]["conv"]
    assert {k: v.shape for k, v in conv.items()} == {
        "in_proj": (3, 64, 192), "conv_w": (3, 3, 64), "out_proj": (3, 64, 64)}
    mlp = params["stacks"]["conv+moe"]["mlp"]
    assert mlp["router"].shape == (3, 64, 8) and mlp["w_gate"].shape == (3, 4, 64, 16)
    ids, seg, pos, seqs = _packed(rows=((80, 40),), row_len=128)
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat=remat)
    want = _reference_logprobs(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _ppo_loss(_program_logprobs(p, cfg, ids, seg, pos, seqs, remat=remat))
    plain = lambda p: _ppo_loss(_reference_logprobs(p, HF, seqs))
    (l_prog, g_prog), (l_ref, g_ref) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (prog, plain))
    np.testing.assert_allclose(float(l_prog), float(l_ref), atol=2e-5)
    _no_bias_grad(g_prog, g_ref)
    _assert_trees_close(g_prog, g_ref, rtol=1e-4)


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_of_the_tolerance_moves_the_reference(control):
    """What `scripts/tolerance_controls_lfm2.py` changes in the reference
    shows in its logprobs at toy size too: no control is a no-op of the
    reference's code (the router's constant but faintly: 1e-6 beside a sum
    of four scores)."""
    params = _params(_cfg())
    _, _, _, seqs = _packed(rows=((80,),), row_len=128)
    want = _reference_logprobs(params, HF, seqs)
    got = _reference_logprobs(params, HF, seqs, control)
    moved = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    assert moved > (1e-7 if control == "norm_eps_1e-20" else 1e-3), moved


def test_the_references_convolution_stops_at_a_sequences_start():
    """The control no chip check can run (one sequence a call of the
    reference): with `starts` the reference's mixer over two sequences in one
    array is each alone, and without it the second sequence's first two
    positions read the first's last: the program's packed row sides with the
    first."""
    cfg = _cfg()
    cp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["stacks"]["conv+dense"]["conv"])
    u = jax.random.normal(jax.random.PRNGKey(1), (20, 64))
    starts = jnp.zeros((20,), bool).at[0].set(True).at[12].set(True)
    each = jnp.concatenate([ref.conv_mixer(u[:12], cp), ref.conv_mixer(u[12:], cp)])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(ref.conv_mixer(u, cp, starts=starts)),
                                   np.asarray(each), atol=1e-5)
        through = np.asarray(ref.conv_mixer(u, cp))
    assert np.abs(through[12:14] - np.asarray(each)[12:14]).max() > 1e-2
    np.testing.assert_allclose(through[14:], np.asarray(each)[14:], atol=1e-5)


def test_a_packed_row_is_each_of_its_sequences_alone_through_the_stack(monkeypatch):
    """Logprobs and the gradient of their sum: two sequences in one row
    against each in a row of its own (the convolutions start afresh at a
    sequence's start, attention sees no other sequence, every token is
    routed on its own)."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((70, 40),), row_len=128)
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


@pytest.mark.parametrize("lens,row_len", [((40, 30), 192), ((100, 92), 192), ((17,), 96)],
                         ids=["half_empty", "full", "one_band_of_six"])
def test_the_looping_form_is_the_whole_row_and_the_reference(lens, row_len, monkeypatch):
    """One row alone: the dense convolution layer runs as one carried loop
    over the row's live bands (`_conv_layer`: the taps' last two gated inputs
    handed on; sequences start inside bands and on their second cells), the
    attention layer as its two stretches, the convolution layers over experts
    keep the whole row (`_kind_loops`); logprobs and gradients are the whole
    row's and the reference's. And with every convolution layer steered
    through the loop (the form the probe measured and the rule left out)."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=(lens,), row_len=row_len)
    assert looping_layers(cfg, 1, row_len) == 0  # under two bands of 1,024
    whole = lambda p: sum(x.sum() for x in _program_logprobs(
        p, cfg, ids, seg, pos, seqs, remat="full", bands=True))
    want, g_want = jax.jit(jax.value_and_grad(whole))(params)
    ran = small_bands(monkeypatch)
    assert looping_layers(cfg, 1, row_len) == 2
    assert looping_layers(cfg, 1, row_len, mixer="conv") == 1
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert ran.count("_conv_layer") >= 1 and ran.count("_before_mixer") >= 1
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)
    from areal_tpu.models import transformer as tf

    loops = tf._kind_loops
    monkeypatch.setattr(tf, "_kind_loops", lambda c, k: k.mixer == "conv" or loops(c, k))
    jax.clear_caches()
    del ran[:]
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert looping_layers(cfg, 1, row_len) == 5 and ran.count("_conv_layer") >= 2
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)
    for g, w in zip(_program_logprobs(params, cfg, ids, seg, pos, seqs, bands=True),
                    _reference_logprobs(params, HF, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_the_two_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held experts' results of the two shares of 4
    experts of one expert layer add up to what the uncut reference gives for
    all 8 (no shared expert: nothing both chips compute alike enters the sum;
    the router, its bias and the renormalisation are over all 8 on both)."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    whole_hf = dict(HF, num_experts=8, experts_held_first=0)
    whole = _params(_cfg(whole_hf))
    mlp = jax.tree_util.tree_map(lambda a: a[1], whole["stacks"]["conv+moe"]["mlp"])
    assert np.asarray(mlp["expert_bias"]).any()
    f = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(f[0], mlp, whole_hf)
        total, pairs = 0.0, 0.0
        for first in (0, 4):
            cfg = _cfg(dict(HF, experts_held_first=first))
            assert cfg.moe.experts_held == (first, 4) and cfg.moe.num_experts == 8
            share = {k: v[first:first + 4] if k in ("w_gate", "w_up", "w_down") else v
                     for k, v in mlp.items()}
            y, aux = moe_lib.moe_mlp(f, share, cfg, jnp.float32)
            one = ref.expert_layer(f[0], share, dict(HF, experts_held_first=first))
            np.testing.assert_allclose(np.asarray(y[0]), np.asarray(one), atol=1e-5)
            total, pairs = total + y[0], pairs + float(aux["pairs_held"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert pairs == 96 * 4  # every pair is held by one share


def test_the_seeded_bias_is_one_draw_for_both_halves_and_moves_the_choice():
    """`expert_bias` of this stack is seeded, not zeros, the second half of
    the experts the first half's draw; selection on score + bias differs
    from selection on the score, and the gates are the bare scores over
    their sum + 1e-6."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    for parts in ("attention+moe", "conv+moe"):
        bias = np.asarray(params["stacks"][parts]["mlp"]["expert_bias"])
        assert bias.shape[-1] == 8 and np.abs(bias).min() > 0
        np.testing.assert_array_equal(bias[:, :4], bias[:, 4:])
        assert 0.01 < bias.std() < 0.15
    mlp = jax.tree_util.tree_map(lambda a: a[0], params["stacks"]["conv+moe"]["mlp"])
    f = jax.random.normal(jax.random.PRNGKey(5), (256, 64))
    _, scores, top_p, top_e = moe_lib._router(f, mlp["router"], cfg.moe, mlp["expert_bias"])
    _, _, _, plain_e = moe_lib._router(f, mlp["router"], cfg.moe, None)
    differ = (np.sort(np.asarray(top_e), -1) != np.sort(np.asarray(plain_e), -1)).any(-1)
    assert 0.05 < differ.mean() < 0.95
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(top_e), -1)
    np.testing.assert_allclose(np.asarray(top_p), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    gates = ref.router_gates(f, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), mlp), HF)
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(gates, top_e, -1)), np.asarray(top_p), atol=1e-6)
    # and every other stack's bias starts at zeros, as it did
    other = TransformerConfig(n_layers=1, moe=dict(num_experts=4, router_bias=True,
                                                   score_func="sigmoid"))
    assert not np.asarray(init_params(other, jax.random.PRNGKey(0))["layers"]["mlp"][
        "expert_bias"]).any()


def test_the_programs_own_parameter_count_is_the_configurations():
    """893.7 M parameters at the published widths, by the program's own
    shapes: the configuration file's arithmetic."""
    with open("benchmark/configs/lfm2-8b-a1b-d5-e16.json") as f:
        hf = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 893_696_256
    by = {parts: count(stack) // jax.tree_util.tree_leaves(stack)[0].shape[0]
          for parts, stack in shapes["stacks"].items()}  # a layer of each kind
    assert by == {"conv+dense": 60_827_648, "attention+moe": 186_716_320, "conv+moe": 193_013_792}
    assert cfg.moe.n_held == 16 and cfg.moe.num_experts == 32 and cfg.moe.top_k == 4
    assert cfg.moe.expert_intermediate_dim == 1792 == 14 * 128
    assert kinds_label(cfg) == "dense.conv.k3,moe.full.rope,moe.conv.k3 x3"


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs guide is not installed here")
    return next(r for r in map(json.loads, open(catalog)) if r["name"] == "LFM2-8B-A1B")


def test_the_family_takes_the_catalog_rows_config_as_it_is():
    hf = _catalog_row()["config"]
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    kinds = cfg.kinds()
    assert len(kinds) == 24 and sum(k.mixer == "conv" for k in kinds) == 18
    assert [i for i, k in enumerate(kinds) if k.mixer == "attention"] == [2, 6, 10, 14, 18, 21]
    assert [k.mlp for k in kinds] == ["dense"] * 2 + ["moe"] * 22
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.intermediate_dim,
            cfg.vocab_size, cfg.max_position_embeddings) == (2048, 32, 8, 64, 7168, 65536, 128000)
    assert cfg.qk_norm and cfg.qk_norm_over == "head" and cfg.norm_eps == 1e-5
    assert cfg.rotary_base == 1e6 and cfg.tied_embeddings and not cfg.attn_bias
    assert cfg.conv == ConvConfig(kernel=3, bias=False)
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.expert_intermediate_dim, moe.score_func,
            moe.route_norm, moe.route_norm_eps, moe.router_bias, moe.routed_scaling_factor,
            moe.n_shared_experts, moe.experts_held, moe.dispatch) == (
        32, 4, 1792, "sigmoid", True, 1e-6, True, 1.0, 0, None, "dropless")
    # the benchmark's file is that row but for what `reduced` lists
    with open("benchmark/configs/lfm2-8b-a1b-d5-e16.json") as f:
        ours = json.load(f)
    changed = {k for k in hf if ours.get(k) != hf[k]}
    assert changed == set(ours["benchmark"]["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"}
    assert ours["layer_types"] == hf["layer_types"][1:6]


def test_the_24b_siblings_rope_group_is_read_too():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs guide is not installed here")
    hf = next(r for r in map(json.loads, open(catalog)) if r["name"] == "LFM2-24B-A2B")["config"]
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    assert cfg.rotary_base == 1e6 and cfg.n_layers == 40 and cfg.moe.num_experts == 64
    assert sum(k.mixer == "conv" for k in cfg.kinds()) == 30


def test_lfm2_config_and_checkpoint_layout_round_trip():
    fam = get_family("lfm2_moe")
    cfg = _cfg()
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    assert TransformerConfig(**dataclasses.asdict(cfg)).conv == cfg.conv  # the launcher's kwargs
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    conv = jax.tree_util.tree_map(lambda a: a[1], params["stacks"]["conv+moe"])  # layer 3
    at = "model.layers.3.conv"
    assert sd[f"{at}.in_proj.weight"].shape == (192, 64)
    np.testing.assert_array_equal(sd[f"{at}.out_proj.weight"], conv["conv"]["out_proj"].T)
    assert sd[f"{at}.conv.weight"].shape == (64, 1, 3)
    np.testing.assert_array_equal(sd[f"{at}.conv.weight"][:, 0, :], conv["conv"]["conv_w"].T)
    ff = "model.layers.3.feed_forward"
    assert sd[f"{ff}.gate.weight"].shape == (8, 64) and sd[f"{ff}.expert_bias"].shape == (8,)
    np.testing.assert_array_equal(sd[f"{ff}.experts.2.w2.weight"], conv["mlp"]["w_down"][2].T)
    assert f"{ff}.experts.4.w1.weight" not in sd  # experts 4-7 are another chip's
    attn = jax.tree_util.tree_map(lambda a: a[0], params["stacks"]["attention+moe"])
    np.testing.assert_array_equal(sd["model.layers.1.self_attn.out_proj.weight"],
                                  attn["attn"]["wo"].T)
    assert sd["model.layers.1.self_attn.q_layernorm.weight"].shape == (16,)
    assert sd["model.layers.0.feed_forward.w1.weight"].shape == (96, 64)
    for name in ("0.operator_norm.weight", "4.ffn_norm.weight", "0.conv.in_proj.weight"):
        assert f"model.layers.{name}" in sd
    assert "model.embedding_norm.weight" in sd and "lm_head.weight" not in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("over,match,error", [
    (dict(layer_types=[C, F, "sliding_attention", C, C]), "layer_types", ValueError),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn", "factor": 8.0},
          rope_theta=None), "plain table", NotImplementedError),
    (dict(use_expert_bias=False), "use_expert_bias", NotImplementedError),
    (dict(norm_topk_prob=False), "norm_topk_prob", NotImplementedError),
    (dict(num_dense_layers=6), "num_dense_layers", ValueError),
], ids=["layer_type", "scaled_table", "no_bias", "no_renorm", "dense_layers"])
def test_what_the_family_cannot_run_is_refused_by_name(over, match, error):
    with pytest.raises(error, match=match):
        _cfg({k: v for k, v in dict(HF, **over).items() if v is not None})


def test_what_the_cache_paths_and_the_config_cannot_run_is_refused_by_mechanism():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    for where in ("prefill", "paged_decode_step", "ServingEngine"):
        with pytest.raises(NotImplementedError, match=(
                r"a convolution's reach back beside the KV pages.*the last 2 gated inputs "
                r"B \* x of 64 values each and no KV page")):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError, match="sigmoid router"):
            cfg.require_plain_stack(where)
    with pytest.raises(ValueError, match="needs TransformerConfig.conv"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(mixer="conv"),))
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mixer="conv", window=8)
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        TransformerConfig(n_layers=1, conv=ConvConfig(), hyper=dict(n=2),
                          layer_kinds=(LayerKind(mixer="conv"),))
