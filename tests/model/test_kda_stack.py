"""A stack of delta-rule mixers and latent attention without a q rank or a
position encoding (models/config.py KDAConfig, LayerKind "kda", MLAConfig
q_rank None), and the `kimi_linear` family: the program against the plain
reference `benchmark/reference/kimi_linear.py` on the CPU, float32, seeded
random weights, toy widths (hidden 64, 2 KDA heads of 16, four layers
`K K M K`, one dense and three expert)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models import transformer
from areal_tpu.models.config import KDAConfig, LayerKind, MLAConfig, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from benchmark.reference import kimi_linear as ref

from tests.model.test_hybrid_stack import _ppo_loss
from tests.model.test_layer_kinds import _assert_trees_close, _packed, small_bands

HF = dict(
    model_type="kimi_linear", hidden_size=64, intermediate_size=96, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, vocab_size=64,
    model_max_length=512, rms_norm_eps=1e-5, hidden_act="silu",
    kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, mla_use_nope=True, rope_theta=10000, rope_scaling=None,
    linear_attn_config=dict(kda_layers=[1, 2, 4], full_attn_layers=[3], num_heads=2,
                            head_dim=16, short_conv_kernel_size=4),
    first_k_dense_replace=1, moe_layer_freq=1,
    num_experts=4, num_experts_routed=16, experts_held_first=4, num_experts_per_token=4,
    moe_intermediate_size=16, num_shared_experts=1, moe_router_activation_func="sigmoid",
    moe_renormalize=True, routed_scaling_factor=2.446, num_expert_group=1, topk_group=1,
    use_grouped_topk=True, num_nextn_predict_layers=0, tie_word_embeddings=False,
)


def _cfg(hf=HF, **over):
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    return dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32", **over)


def _params(cfg, seed=0, bias_scale=0.1):
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    for parts, stack in params.get("stacks", {}).items():
        if parts.endswith("+moe") and bias_scale:  # a selection bias that is not zero
            stack["mlp"]["expert_bias"] = bias_scale * jax.random.normal(
                jax.random.PRNGKey(seed + 1), stack["mlp"]["expert_bias"].shape)
    return params


def _program_logprobs(params, cfg, ids, seg, pos, seqs, **kw):
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, ids, seg, pos, attn_impl="reference", **kw)
    lp = jax.nn.log_softmax(logits, -1)
    return [jnp.take_along_axis(lp[r, o:o + len(t) - 1], jnp.asarray(t[1:, None]), -1)[:, 0]
            for r, o, t in seqs]


def _reference_logprobs(params, hf, seqs, control=None):
    out = []
    for _, _, t in seqs:
        n = -(-len(t) // ref.ROWS) * ref.ROWS
        ids = jnp.asarray(np.concatenate([t, np.zeros(n - len(t), np.int64)]), jnp.int32)
        out.append(ref._forward(params, ids, ref._small(hf), control)[: len(t) - 1])
    return out


def _no_bias_grad(g_prog, g_ref):
    """`expert_bias` is a buffer: the program sends it no gradient."""
    for parts, stack in g_prog["stacks"].items():
        if parts.endswith("+moe"):
            assert not np.asarray(stack["mlp"]["expert_bias"]).any()
            g_ref["stacks"][parts]["mlp"]["expert_bias"] = stack["mlp"]["expert_bias"]
    return g_ref


@pytest.mark.parametrize("remat", ["none", "full"])
def test_the_stack_matches_the_reference_through_a_ppo_step(remat, monkeypatch):
    """`K K M K`, a dense layer and three expert layers, three parameter
    stacks: logprobs, the PPO loss and every parameter's gradient; the
    delta rule in chunks against the reference's token by token, latent
    attention with one q projection and no rotary against its masked
    softmax."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)  # several tiles an expert at toy size
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == [
        "kda+dense", "kda+moe", "latentattention+moe", "kda+moe"]
    params = _params(cfg)
    assert {k: jax.tree_util.tree_leaves(v)[0].shape[0]
            for k, v in params["stacks"].items()} == {
        "kda+dense": 1, "kda+moe": 2, "latentattention+moe": 1}
    assert set(params["stacks"]["latentattention+moe"]["attn"]) == {
        "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo"}
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat=remat)
    want = _reference_logprobs(params, HF, seqs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    prog = lambda p: _ppo_loss(_program_logprobs(p, cfg, ids, seg, pos, seqs, remat=remat))
    plain = lambda p: _ppo_loss(_reference_logprobs(p, HF, seqs))
    (l_prog, g_prog), (l_ref, g_ref) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (prog, plain))
    np.testing.assert_allclose(float(l_prog), float(l_ref), atol=2e-5)
    _assert_trees_close(g_prog, _no_bias_grad(g_prog, g_ref), rtol=1e-4)


@pytest.mark.parametrize("control", [
    "beta_one", "scalar_decay", "no_correction", "no_conv", "no_gate", "no_l2", "rotary"])
def test_every_control_of_the_tolerance_moves_the_reference(control):
    """What `scripts/tolerance_controls_kda.py` leaves out or changes in
    the reference shows in its logprobs at toy size too: no control is a
    no-op of the reference's code."""
    cfg = _cfg()
    params = _params(cfg)
    _, _, _, seqs = _packed()
    want = _reference_logprobs(params, HF, seqs[:2])
    got = _reference_logprobs(params, HF, seqs[:2], control)
    moved = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    # (keys that are not unit make `I - b k k^T` expand: the state overflows)
    assert not moved <= 1e-3, moved


def test_a_scan_over_repeated_delta_rule_layers_is_the_layers_one_by_one():
    """Eight layers `K K M K K K M K` after no dense layer: `segments_of`
    finds a scan of two units of four; its logprobs are the reference's."""
    hf = dict(HF, num_hidden_layers=8, first_k_dense_replace=0,
              linear_attn_config=dict(HF["linear_attn_config"], kda_layers=[1, 2, 4, 5, 6, 8],
                                      full_attn_layers=[3, 7]))
    cfg = _cfg(hf)
    assert [(len(s.unit), s.repeats) for s in cfg.segments()] == [(4, 2)]
    params = _params(cfg)
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs, remat="full")
    for g, w in zip(got, _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5)


def test_a_delta_rule_layer_walks_its_live_bands(monkeypatch):
    """A delta-rule mixer beside an MLP takes the band loop
    (`transformer._kind_loops`): a half-empty row runs both stretches of
    every layer through `ops/band_loop.stretch` (the projections before
    the rule; the head norm, gate, output projection and the MLP's
    token-wise part after it), and its logprobs and gradients are the
    whole row's."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((37,),), row_len=96)
    assert looping_layers(cfg, 1, 96) == 0  # under two bands of 1,024
    whole = lambda p: sum(x.sum() for x in _program_logprobs(
        p, cfg, ids, seg, pos, seqs, remat="full", bands=True))
    want, g_want = jax.jit(jax.value_and_grad(whole))(params)
    ran = small_bands(monkeypatch)
    assert all(transformer._kind_loops(cfg, k) for k in cfg.kinds())
    assert looping_layers(cfg, 1, 96) == 4
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert ran.count("_before_mixer") >= 4 and ran.count("_after_mixer") >= 4
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)


def test_latent_attention_without_a_q_rank_or_rotary_is_the_references():
    """One latent layer alone: `q = h W_q` in one product, the 8 rope
    columns left as they are, against the reference's masked softmax; and
    with the rope part turned it differs (the kind's `rotary` is read)."""
    hf = dict(HF, num_hidden_layers=1, first_k_dense_replace=1,
              linear_attn_config=dict(HF["linear_attn_config"], kda_layers=[],
                                      full_attn_layers=[1]))
    cfg = _cfg(hf)
    assert cfg.kinds() == (LayerKind(mlp="dense", latent=True, rotary=False),)
    assert cfg.mla.q_rank is None and cfg.head_dim == 16
    params = _params(cfg)
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs)
    for g, w in zip(got, _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    turned = dataclasses.replace(cfg, layer_kinds=(LayerKind(mlp="dense", latent=True),))
    other = _program_logprobs(params, turned, ids, seg, pos, seqs)
    assert float(jnp.abs(other[0] - got[0]).max()) > 1e-3


def test_a_packed_row_is_each_of_its_sequences_alone_through_the_stack():
    """Logprobs and the gradient of their sum: three sequences in one row
    against each in a row of its own (what holds the resets of state and
    convolution through the family's stack)."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((20, 30, 10),), row_len=64)
    packed = lambda p: _program_logprobs(p, cfg, ids, seg, pos, seqs)

    def alone(p):
        out = []
        for _, _, t in seqs:
            one = jnp.asarray(t[None], jnp.int32)
            out += _program_logprobs(p, cfg, one, jnp.ones_like(one),
                                     jnp.arange(len(t))[None], [(0, 0, t)])
        return out

    # one program each way (op by op the four forwards and two backwards
    # are a thousand small compiles)
    both = lambda fn: jax.jit(
        lambda p: (fn(p), jax.grad(lambda p: sum(x.sum() for x in fn(p)))(p)))
    (lp_packed, g_packed), (lp_alone, g_alone) = both(packed)(params), both(alone)(params)
    for g, w in zip(lp_packed, lp_alone):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    _assert_trees_close(g_packed, g_alone, rtol=1e-4)


def test_the_thirty_two_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 32 shares of 8
    experts of 256, the shared expert counted once, add up to what the
    reference gives for the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_experts=256, num_experts_per_token=8)
    del hf["num_experts_routed"], hf["experts_held_first"]
    cfg = _cfg(hf)
    stack = _params(cfg)["stacks"]["kda+moe"]["mlp"]
    mlp = jax.tree_util.tree_map(lambda a: a[0], stack)
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 64))
    mats = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(32):
            held = (8 * share, 8)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 8] if k in mats else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            if share in (0, 17):
                part = ref.expert_layer(h, mp, dict(
                    hf, num_experts=8, num_experts_routed=256, experts_held_first=held[0]))
                np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h.shape[0] * cfg.moe.top_k  # every pair is held by one share


def test_kimi_linear_config_and_names_round_trip():
    fam = get_family("kimi_linear")
    cfg = _cfg()
    assert cfg.kda == KDAConfig(n_heads=2, head_dim=16, conv_kernel=4, gate_rank=16,
                                chunk_size=64)
    assert cfg.mla == MLAConfig(q_rank=None, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8)
    assert cfg.moe.experts_held == (4, 4) and cfg.moe.num_experts == 16
    assert cfg.moe.score_func == "sigmoid" and cfg.moe.routed_scaling_factor == 2.446
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    at = "model.layers.1.self_attn"
    assert sd[f"{at}.q_conv1d.weight"].shape == (32, 1, 4)
    assert sd[f"{at}.A_log"].shape == (1, 1, 2, 1) and sd[f"{at}.dt_bias"].shape == (32,)
    assert sd[f"{at}.f_a_proj.weight"].shape == (16, 64)
    assert sd[f"{at}.f_b_proj.weight"].shape == (32, 16)
    assert sd[f"{at}.b_proj.weight"].shape == (2, 64)
    assert sd[f"{at}.o_norm.weight"].shape == (16,)
    assert sd["model.layers.2.self_attn.q_proj.weight"].shape == (4 * 16, 64)
    assert sd["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"].shape == (16 + 8, 64)
    assert "model.layers.2.self_attn.q_a_proj.weight" not in sd
    assert "model.layers.1.block_sparse_moe.experts.4.w1.weight" in sd  # held: 4..7
    assert "model.layers.1.block_sparse_moe.experts.0.w1.weight" not in sd
    assert sd["model.layers.1.block_sparse_moe.gate.weight"].shape == (16, 64)
    for name in ("0.mlp.gate_proj.weight", "0.self_attn.g_b_proj.weight",
                 "3.block_sparse_moe.gate.e_score_correction_bias",
                 "3.block_sparse_moe.shared_experts.down_proj.weight",
                 "2.self_attn.kv_a_layernorm.weight", "2.post_attention_layernorm.weight"):
        assert f"model.layers.{name}" in sd
    assert "model.norm.weight" in sd and "lm_head.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("over,error,match", [
    (dict(num_expert_group=2), NotImplementedError, "group-limited"),
    (dict(topk_group=4), NotImplementedError, "group-limited"),
    (dict(num_nextn_predict_layers=1), NotImplementedError, "prediction module"),
    (dict(rope_scaling=dict(type="yarn", factor=4.0)), NotImplementedError, "rope_scaling"),
    (dict(mla_use_nope=False), NotImplementedError, "mla_use_nope"),
    (dict(q_lora_rank=24), NotImplementedError, "q_lora_rank"),
    (dict(moe_router_activation_func="softmax"), NotImplementedError, "sigmoid"),
    (dict(linear_attn_config=dict(HF["linear_attn_config"], full_attn_layers=[])),
     ValueError, "each of the layers"),
    (dict(linear_attn_config=dict(HF["linear_attn_config"], kda_layers=[1, 2, 3, 4])),
     ValueError, "each of the layers"),
], ids=["expert_group", "topk_group", "mtp", "rope_scaling", "rotary_latent", "q_rank",
        "softmax_router", "a_layer_unnamed", "a_layer_twice"])
def test_what_the_family_cannot_run_is_refused_by_name(over, error, match):
    with pytest.raises(error, match=match):
        _cfg(dict(HF, **over))


def test_what_a_delta_rule_stack_cannot_run_is_refused_by_mechanism():
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh

    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    mesh = make_mesh(MeshSpec(seq=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="delta-rule layer on a mesh that splits"):
        forward(params, cfg, ids, seg, pos, attn_impl="ring", mesh=mesh)
    for where in ("prefill", "paged_decode_step", "ServingEngine"):
        with pytest.raises(NotImplementedError,
                           match=r"delta-rule state beside the KV pages.*\[2, 16, 16\]"):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError, match="a latent cache"):
            cfg.require_plain_stack(where)
    with pytest.raises(ValueError, match="needs TransformerConfig.kda"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(mixer="kda"),))
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mixer="kda", rotary=False)
    with pytest.raises(NotImplementedError, match="no window"):
        LayerKind(latent=True, rotary=False, window=8)
    with pytest.raises(ValueError, match="multiple of 16"):
        KDAConfig(chunk_size=24)


def test_a_mesh_of_two_runs_the_delta_rule_in_its_plain_form():
    """On a mesh of several devices the walk over chunks is the plain scan
    (a kernel is opaque to the partitioner); the new leaves shard by the
    rules that are there (the three projections column-parallel, `wo`
    row-parallel, the rest replicated), and an fsdp mesh of 2 gives the
    single device's logits."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh
    from areal_tpu.parallel.sharding import fitted_param_spec, shard_params
    from jax.sharding import PartitionSpec as P

    # (dense layers: a share of the experts does not run across chips)
    cfg = _cfg(dict(HF, num_hidden_layers=2, first_k_dense_replace=2,
                    linear_attn_config=dict(HF["linear_attn_config"], kda_layers=[1],
                                            full_attn_layers=[2])))
    params = _params(cfg)
    mesh = make_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    sizes = dict(mesh.shape)
    kp = params["stacks"]["kda+dense"]["kda"]
    spec = lambda name: fitted_param_spec(f"stacks/kda+dense/kda/{name}", kp[name].shape, sizes)
    assert spec("wq") == spec("wk") == spec("wv") == P(None, "fsdp", "tensor")
    assert spec("wo") == P(None, "tensor", "fsdp")
    for name in ("conv_q", "w_fa", "w_fb", "w_b", "w_gb", "A_log", "dt_bias", "o_norm"):
        assert all(e is None for e in spec(name)), name
    ids, seg, pos, _ = _packed()
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, ids, seg, pos, attn_impl="reference")
        got = forward(shard_params(params, mesh), cfg, ids, seg, pos,
                      attn_impl="reference", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
