"""A stack of Gated DeltaNet mixers (the delta rule with one decay a head,
key heads under value heads, a full-rank silu gate) and gated attention
with a partial rotation over softmax-routed experts with a gated shared
expert, and the `qwen3_next` family: the program against the plain
reference `benchmark/reference/qwen3_next.py` on the CPU, float32, seeded
random weights, toy widths (hidden 64, 2 key heads under 4 value heads of
16, attention 4 / 2 heads of 32 with 8 columns turned, four layers `L L L
F`, 16 routed experts top-4 with 4 held)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models import transformer
from areal_tpu.models.config import KDAConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from benchmark.reference import qwen3_next as ref

from tests.model.test_hybrid_stack import _ppo_loss
from tests.model.test_kda_stack import _program_logprobs
from tests.model.test_layer_kinds import _assert_trees_close, _packed, small_bands

HF = dict(
    model_type="qwen3_next", hidden_size=64, intermediate_size=96, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=64,
    max_position_embeddings=512, rms_norm_eps=1e-6, hidden_act="silu",
    full_attention_interval=4, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    rope_theta=10000000.0, rope_scaling=None, partial_rotary_factor=0.25,
    decoder_sparse_step=1, mlp_only_layers=[], num_experts=4, num_experts_routed=16,
    experts_held_first=4, num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, norm_topk_prob=True, use_sliding_window=False,
    tie_word_embeddings=False,
)
CONTROLS = ("beta_one", "no_decay", "no_correction", "no_conv", "no_z", "z_sigmoid", "no_l2",
            "pair_mod", "rotary_whole", "no_rotary", "no_attn_gate", "w_for_1pw",
            "no_shared_gate", "top8", "decay_bf16")


def _cfg(hf=HF, **over):
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    return dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32", **over)


def _params(cfg, seed=0):
    """The seeded draw, its norms moved off 1 (a trained `1 + w`)."""
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


@pytest.mark.parametrize("remat", ["full"])  # the cell's; `none` is the packed-row test's
def test_the_stack_matches_the_reference_through_a_ppo_step(remat, monkeypatch):
    """`L L L F`, every layer an expert layer, two parameter stacks:
    logprobs, the PPO loss and every parameter's gradient; the delta rule
    in chunks against the reference's token by token with q and k repeated,
    gated attention with a quarter of each head turned against its masked
    softmax, the gated shared expert and the held experts' share."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)  # several tiles an expert at toy size
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == ["kda+moe"] * 3 + ["attention+moe"]
    assert cfg.rotary_dim == 8 and cfg.attn_gate and cfg.qk_norm
    params = _params(cfg)
    assert {k: jax.tree_util.tree_leaves(v)[0].shape[0]
            for k, v in params["stacks"].items()} == {"kda+moe": 3, "attention+moe": 1}
    assert set(params["stacks"]["attention+moe"]["attn"]) == {
        "wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm"}
    assert set(params["stacks"]["kda+moe"]["mlp"]["shared"]) == {
        "w_gate", "w_up", "w_down", "w_s"}
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
    _assert_trees_close(g_prog, g_ref, rtol=1e-4)


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_of_the_tolerance_moves_the_reference(control):
    """What `scripts/tolerance_controls_gdn.py` leaves out or changes in
    the reference shows in its logprobs at toy size too: no control is a
    no-op of the reference's code."""
    cfg = _cfg()
    params = _params(cfg)
    _, _, _, seqs = _packed()
    want = _reference_logprobs(params, HF, seqs[:2])
    got = _reference_logprobs(params, HF, seqs[:2], control)
    moved = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    # (keys that are not unit make `I - b k k^T` expand: the state may overflow)
    assert not moved <= (1e-5 if control == "decay_bf16" else 1e-3), moved


def test_a_gated_deltanet_layer_walks_its_live_bands(monkeypatch):
    """Both kinds take the band loop (`transformer._kind_loops`), as the
    `kimi_linear` family's do: a half-empty row runs both stretches of
    every layer through `ops/band_loop.stretch`, and its logprobs and
    gradients are the whole row's."""
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
    assert looping_layers(cfg, 1, 96, sharded=True) == 0
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert ran.count("_before_mixer") >= 2 and ran.count("_after_mixer") >= 2
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)


def test_a_partial_rotation_and_the_gates_are_the_references():
    """Gated attention layers alone (`full_attention_interval` 1): q and k
    under their norms, the first 8 of a head's 32 columns turned, the
    sigmoid gate, against the reference; turning the whole head, or
    nothing, differs, and so does a stack without the shared expert's
    gate."""
    hf = dict(HF, num_hidden_layers=2, full_attention_interval=1)
    cfg = _cfg(hf)
    assert cfg.kinds() == (LayerKind(mlp="moe"),) * 2 and cfg.rotary_dim == 8
    params = _params(cfg)
    ids, seg, pos, seqs = _packed()
    got = _program_logprobs(params, cfg, ids, seg, pos, seqs)
    for g, w in zip(got, _reference_logprobs(params, hf, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    whole = _program_logprobs(params, dataclasses.replace(cfg, rotary_fraction=1.0),
                              ids, seg, pos, seqs)
    for g, w in zip(whole, _reference_logprobs(params, hf, seqs, "rotary_whole")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    assert float(jnp.abs(whole[0] - got[0]).max()) > 1e-3
    ungated = jax.tree_util.tree_map(lambda a: a, params)
    del ungated["layers"]["mlp"]["shared"]["w_s"]
    other = _program_logprobs(ungated, cfg, ids, seg, pos, seqs)
    for g, w in zip(other, _reference_logprobs(params, hf, seqs, "no_shared_gate")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    assert float(jnp.abs(other[0] - got[0]).max()) > 1e-3


@pytest.mark.parametrize("fraction", [0.3, 1.5, 0.0])
def test_a_rotation_of_no_even_number_of_columns_is_refused(fraction):
    with pytest.raises(ValueError, match="rotary_fraction"):
        TransformerConfig(head_dim=32, rotary_fraction=fraction)


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

    both = lambda fn: jax.jit(
        lambda p: (fn(p), jax.grad(lambda p: sum(x.sum() for x in fn(p)))(p)))
    (lp_packed, g_packed), (lp_alone, g_alone) = both(packed)(params), both(alone)(params)
    for g, w in zip(lp_packed, lp_alone):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    _assert_trees_close(g_packed, g_alone, rtol=1e-4)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 16 shares of 32
    experts of 512 under top-10, the gated shared expert counted once, add
    up to what the reference gives for the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_experts=512, num_experts_per_tok=10)
    del hf["num_experts_routed"], hf["experts_held_first"]
    cfg = _cfg(hf)
    stack = _params(cfg)["stacks"]["kda+moe"]["mlp"]
    mlp = jax.tree_util.tree_map(lambda a: a[0], stack)
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 64))
    mats = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(16):
            held = (32 * share, 32)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 32] if k in mats else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            if share in (0, 9):
                part = ref.expert_layer(h, dict(mlp, **mp), dict(
                    hf, num_experts=32, num_experts_routed=512, experts_held_first=held[0]))
                if share:  # the reference's share counts the shared expert every time
                    part = part - ref.shared_expert(h, mlp["shared"])
                np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h.shape[0] * cfg.moe.top_k  # every pair is held by one share


def test_qwen3_next_config_and_checkpoint_layout_round_trip():
    fam = get_family("qwen3_next")
    cfg = _cfg()
    assert cfg.kda == KDAConfig(n_heads=4, n_key_heads=2, head_dim=16, conv_kernel=4,
                                gate_rank=None, chunk_size=64, decay="head",
                                decay_input="column", gate_act="silu")
    assert cfg.moe.experts_held == (4, 4) and cfg.moe.num_experts == 16
    assert cfg.moe.score_func == "softmax" and cfg.moe.shared_gate and cfg.moe.top_k == 4
    assert cfg.moe.shared_intermediate_dim == 16 and cfg.rotary_fraction == 0.25
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    at = "model.layers.1.linear_attn"
    assert sd[f"{at}.in_proj_qkvz.weight"].shape == (2 * (16 + 16 + 32 + 32), 64)
    assert sd[f"{at}.in_proj_ba.weight"].shape == (2 * 4, 64)
    assert sd[f"{at}.conv1d.weight"].shape == (32 + 32 + 64, 1, 4)
    assert sd[f"{at}.A_log"].shape == sd[f"{at}.dt_bias"].shape == (4,)
    assert sd[f"{at}.norm.weight"].shape == (16,)
    # interleaved a key head: key head 1's q rows stand after key head 0's z
    kp = jax.tree_util.tree_map(lambda a: a[1], params["stacks"]["kda+moe"]["kda"])
    qkvz = sd[f"{at}.in_proj_qkvz.weight"].reshape(2, 96, 64)
    np.testing.assert_array_equal(qkvz[1, :16], kp["wq"].T[16:32])
    np.testing.assert_array_equal(qkvz[1, 16:32], kp["wk"].T[16:32])
    np.testing.assert_array_equal(qkvz[1, 32:64], kp["wv"].T[32:64])  # value heads 2, 3
    np.testing.assert_array_equal(qkvz[0, 64:96], kp["w_g"].T[:32])
    ba = sd[f"{at}.in_proj_ba.weight"].reshape(2, 4, 64)
    np.testing.assert_array_equal(ba[1, :2], kp["w_b"].T[2:4])
    np.testing.assert_array_equal(ba[1, 2:], kp["w_a"].T[2:4])
    # q with its gate a head; the family's norms as w, the DeltaNet's own as it is
    ap = jax.tree_util.tree_map(lambda a: a[0], params["stacks"]["attention+moe"]["attn"])
    qg = sd["model.layers.3.self_attn.q_proj.weight"].reshape(4, 64, 64)
    np.testing.assert_array_equal(qg[2, :32], ap["wq"].T[64:96])
    np.testing.assert_array_equal(qg[2, 32:], ap["wg"].T[64:96])
    np.testing.assert_allclose(sd["model.layers.3.self_attn.q_norm.weight"] + 1, ap["q_norm"],
                               rtol=1e-6)
    np.testing.assert_allclose(sd["model.norm.weight"] + 1, params["final_norm"]["weight"],
                               rtol=1e-6)
    np.testing.assert_array_equal(sd[f"{at}.norm.weight"], kp["o_norm"])
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in sd  # held: 4..7
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 64)
    assert sd["model.layers.1.mlp.shared_expert_gate.weight"].shape == (1, 64)
    for name in ("0.mlp.shared_expert.down_proj.weight", "3.self_attn.k_norm.weight",
                 "3.self_attn.o_proj.weight", "2.post_attention_layernorm.weight"):
        assert f"model.layers.{name}" in sd
    assert "lm_head.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("over,match", [
    (dict(mlp_only_layers=[1]), "mlp_only_layers"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(rope_scaling=dict(type="yarn", factor=4.0)), "rope_scaling"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(linear_value_head_dim=32), "linear_key_head_dim"),
    (dict(linear_num_key_heads=3), "do not divide"),
    (dict(num_nextn_predict_layers=1), "prediction module"),
    (dict(mtp_num_hidden_layers=1), "prediction module"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
], ids=["dense_layers", "sparse_step", "rope_scaling", "window", "head_sizes", "key_heads",
        "mtp", "mtp_layers", "unnormalised_gates"])
def test_what_the_family_cannot_run_is_refused_by_name(over, match):
    with pytest.raises(NotImplementedError, match=match):
        _cfg(dict(HF, **over))


def test_what_a_gated_deltanet_stack_cannot_run_is_refused_by_mechanism():
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
                           match=r"delta-rule state beside the KV pages.*\[4, 16, 16\]"):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError,
                           match=r"a rotation of part of a head \(rotary_fraction 0.25\)"):
            cfg.require_plain_stack(where)
    with pytest.raises(NotImplementedError, match="partial rotation beside latent"):
        TransformerConfig(head_dim=32, rotary_fraction=0.25, indexer=dict())


def test_a_mesh_of_two_runs_the_rule_in_its_plain_form():
    """A tensor mesh of 2 gives the single device's logits: the plain form
    of the scalar rule partitions as the channel form's does. (An fsdp
    mesh that divides the experts takes `moe._moe_mlp_ep`, which refuses a
    shared expert, as for every family that has one.)"""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.parallel.mesh import make_mesh
    from areal_tpu.parallel.sharding import shard_params

    hf = dict(HF, num_hidden_layers=2, full_attention_interval=2, num_experts=16)
    del hf["num_experts_routed"], hf["experts_held_first"]  # a share does not run across chips
    cfg = _cfg(hf)
    params = _params(cfg)
    with pytest.raises(NotImplementedError, match="no shared expert"):
        forward(params, cfg, *_packed()[:3], attn_impl="reference",
                mesh=make_mesh(MeshSpec(fsdp=2), jax.devices()[:2]))
    mesh = make_mesh(MeshSpec(tensor=2), jax.devices()[:2])
    ids, seg, pos, _ = _packed()
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, ids, seg, pos, attn_impl="reference")
        got = forward(shard_params(params, mesh), cfg, ids, seg, pos,
                      attn_impl="reference", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
