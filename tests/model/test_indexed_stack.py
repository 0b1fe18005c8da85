"""Keye-VL-2.0's language model at toy widths (`model_type` KeyeVL2): GQA
attention over the keys a learned indexer chooses, a query at a time,
over softmax-routed experts holding a share. The program against the
plain reference (`benchmark/reference/keye_vl2.py`): logprobs, the choice
itself, the KL, a packed row against its sequences alone; the shares of
the expert layer; the family's round trip; what other paths refuse (the
kernels and the threshold: `test_index_kernels.py`). Float32 on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import IndexerConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params
from areal_tpu.ops.loss import fused_next_token_logprobs
from benchmark.reference import keye_vl2 as ref

TOPK = 12
SA = dict(indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1,
          topk=TOPK, q_chunk_size=512, kv_chunk_size=512)
HF = dict(model_type="KeyeVL2", num_hidden_layers=2, hidden_size=32,
          num_attention_heads=4, num_key_value_heads=2, head_dim=8,
          intermediate_size=48, vocab_size=64, moe_intermediate_size=16,
          num_experts=4, num_local_experts=4, num_experts_routed=16,
          experts_held_first=4, num_experts_per_tok=4, norm_topk_prob=True,
          decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
          rope_theta=1e7, rope_scaling={"mrope_section": [1, 1, 2], "rope_type": "default",
                                        "type": "default"},
          sa_config=SA, attention_bias=False, tie_word_embeddings=False,
          sliding_window=None, use_sliding_window=False, max_position_embeddings=512)


def _cfg(hf=HF, **over):
    hf = dict(hf, **over)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    return cfg


def _params(cfg, seed=0):
    """Seeded weights with the norms moved off their initial values, so
    that each matters."""
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return treedef.unflatten([a + 0.1 * jax.random.normal(k, a.shape) if a.ndim <= 2
                              else a for a, k in zip(leaves, keys)])


def _row(lens, T, seed=1, vocab=64):
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


@pytest.fixture(autouse=True)
def _small_tiles(monkeypatch):
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)


# ---------------------------------------------------------------------------
# Against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("remat", ["none", "full"])
def test_logprobs_are_the_plain_references(remat):
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([200], 256)  # 188 of its queries choose 12 keys
    got = _logprobs(params, cfg, ids, seg, pos, remat=remat)
    want = ref.next_token_logprobs(params, HF, np.asarray(ids[0, :200]), pad_to=256)
    np.testing.assert_allclose(got[:199], want, atol=5e-5)
    # and the controls move them: a choice that is not the indexer's is seen
    for control in (dict(mode="all"), dict(mode="last"), dict(index_rotary=False)):
        other = ref.next_token_logprobs(params, HF, np.asarray(ids[0, :200]), pad_to=256,
                                        **control)
        assert np.abs(other - want).max() > 1e-3, control


@pytest.mark.parametrize("remat", ["none", "full", "mlp"])
def test_a_half_empty_row_of_the_indexed_stack_walks_its_live_bands(remat, monkeypatch):
    """One row alone, 100 tokens in 256 cells at bands of 16: both layers
    (one scan, one traced body of two stretches) run their projections
    (the indexer's among them), their router and their output over seven
    bands of sixteen; the logprobs and the KL are the plain reference's,
    and every gradient is the whole row's."""
    from areal_tpu.models.transformer import looping_layers
    from tests.model.test_layer_kinds import small_bands

    ran = small_bands(monkeypatch)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([100], 256)
    got = _logprobs(params, cfg, ids, seg, pos, remat=remat, bands=True)
    assert looping_layers(cfg, 1, 256) == 2 and ran == ["_before_mixer", "_after_mixer"]
    want = ref.next_token_logprobs(params, HF, np.asarray(ids[0, :100]), pad_to=256)
    np.testing.assert_allclose(got[:99], want, atol=5e-5)
    _, sums = forward(params, cfg, ids, seg, pos, output="hidden", return_aux=True,
                      index_loss=True, remat=remat, bands=True)
    np.testing.assert_allclose(float(sums["index_kl"]), ref.indexer_kl(
        params, HF, np.asarray(ids[0, :100]), pad_to=256).sum(), rtol=2e-5)

    def loss(p, rows):  # rows together keep the whole row
        tile = lambda a: jnp.tile(a, (rows, 1))
        hidden, sums = forward(p, cfg, tile(ids), tile(seg), tile(pos), output="hidden",
                               return_aux=True, index_loss=True, remat=remat, bands=True)
        lp = fused_next_token_logprobs(hidden, p["head"]["weight"], tile(ids), tile(seg))
        return (lp.sum() + sums["index_kl"]) / rows

    g_loop, g_whole = jax.grad(loss)(params, 1), jax.grad(loss)(params, 2)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_loop),
                            jax.tree_util.tree_leaves(g_whole)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=2e-4 * scale + 1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_choice_is_the_references_in_float32():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([200], 256)
    _, (choice, tau) = forward(params, cfg, ids, seg, pos, output="hidden",
                               index_choice=True)
    got = np.asarray(choice)[:, 0, :200, :200]
    want = ref.indexer_choice(params, HF, np.asarray(ids[0, :200]), pad_to=256)
    assert got.shape == want.shape == (2, 200, 200)
    np.testing.assert_array_equal(got, want)
    # ties at the threshold (the relu's exact zeros, at two heads) are all kept
    floor = np.minimum(np.arange(200) + 1, TOPK)[None].repeat(2, 0)
    assert (want.sum(axis=-1) >= floor).all() and (want.sum(axis=-1)[:, :TOPK] == floor[:, :TOPK]).all()
    assert np.isneginf(np.asarray(tau)[:, 0, :TOPK - 1]).all()
    assert np.isfinite(np.asarray(tau)[:, 0, TOPK - 1:200]).all()


def test_the_kl_is_the_references():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([200], 256)
    _, sums = forward(params, cfg, ids, seg, pos, output="hidden", return_aux=True,
                      index_loss=True)
    want = ref.indexer_kl(params, HF, np.asarray(ids[0, :200]), pad_to=256)
    np.testing.assert_allclose(float(sums["index_kl"]), want.sum(), rtol=2e-5)
    assert float(sums["index_cells"]) == 2 * 200 * 201 / 2
    assert float(sums["index_chosen"]) >= 2 * np.minimum(np.arange(200) + 1, TOPK).sum()
    _, off = forward(params, cfg, ids, seg, pos, output="hidden", return_aux=True)
    assert float(off["index_kl"]) == 0.0 and float(off["index_chosen"]) > 0


LENS, ROW = [21, 1, 29, 3], 64


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_a_packed_row_is_each_of_its_sequences_alone(what):
    """No key of another sequence is scored, chosen or counted, a sequence
    shorter than topk is dense, and the padding adds nothing: to 2e-5,
    logprobs, the KL and the gradients of both."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row(LENS, ROW)
    offs = np.concatenate([[0], np.cumsum(LENS)])

    def run(p, *row, **kw):
        hidden, sums = forward(p, cfg, *row, output="hidden", return_aux=True,
                               index_loss=True, **kw)
        return (fused_next_token_logprobs(hidden, p["head"]["weight"], row[0], row[1])[0],
                sums)

    def packed(p):
        return run(p, ids, seg, pos, remat="full")

    def alone(p):
        out, kl, chosen = [], 0.0, 0.0
        for j, l in enumerate(LENS):
            o = offs[j]
            lp, sums = run(p, ids[:, o:o + l], jnp.ones((1, l), jnp.int32), pos[:, o:o + l])
            out.append(lp[: l - 1])
            kl, chosen = kl + sums["index_kl"], chosen + sums["index_chosen"]
        return jnp.concatenate(out), dict(index_kl=kl, index_chosen=chosen)

    scored = np.concatenate([np.arange(offs[j], offs[j] + l - 1) for j, l in enumerate(LENS)])
    if what == "values":
        lp, sums = packed(params)
        want, want_sums = alone(params)
        np.testing.assert_allclose(lp[scored], want, atol=2e-5)
        assert not np.delete(np.asarray(lp), scored).any()
        np.testing.assert_allclose(sums["index_kl"], want_sums["index_kl"], rtol=2e-5)
        assert float(sums["index_chosen"]) == float(want_sums["index_chosen"]) >= 2 * sum(
            np.minimum(np.arange(l) + 1, TOPK).sum() for l in LENS)
        assert float(sums["index_cells"]) == 2 * sum(l * (l + 1) // 2 for l in LENS)
        return
    w = jax.random.normal(jax.random.PRNGKey(5), (ROW,))

    def total(fn):
        def f(p):
            lp, sums = fn(p)
            if fn is packed:
                lp = lp[scored]
            return (lp * w[scored]).sum() + sums["index_kl"]
        return f

    g_packed, g_alone = jax.grad(total(packed))(params), jax.grad(total(alone))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_packed), jax.tree_util.tree_leaves(g_alone)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))
    moved = g_packed["layers"]["attn"]["indexer"]
    assert all(float(jnp.abs(a).max()) > 0 for a in jax.tree_util.tree_leaves(moved))


# ---------------------------------------------------------------------------
# The expert layer's shares
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test under the softmax router: the held-experts results
    of all 8 shares of 16 experts of 128 add up to what the reference
    gives for the whole layer."""
    hf = dict(HF, num_experts=128, num_local_experts=128, num_experts_routed=128,
              experts_held_first=0, num_experts_per_tok=8)
    cfg = _cfg(hf)
    assert cfg.moe.experts_held is None and cfg.moe.score_func == "softmax"
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, 3)["layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 32))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(8):
            held = (16 * share, 16)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 16] if k in ("w_gate", "w_up", "w_down") else v)
                  for k, v in mlp.items()}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            part = ref.expert_layer(h, mp, dict(hf, num_experts=16,
                                                experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h.shape[0] * 8  # every pair is held by one share


# ---------------------------------------------------------------------------
# The family
# ---------------------------------------------------------------------------


def test_config_from_hf_reads_the_published_keys():
    import json

    with open("benchmark/configs/keye-vl-2.0-d6-e16.json") as f:
        hf = json.load(f)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    assert (cfg.n_layers, cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (
        6, 2048, 32, 4, 128)
    assert cfg.qk_norm and not cfg.attn_bias and not cfg.tied_embeddings
    assert cfg.rotary_base == 1e7 and cfg.vocab_size == 18992
    assert cfg.indexer == IndexerConfig(n_heads=16, head_dim=64, top_k=2048, loss_weight=1.0)
    assert cfg.indexer.scale == 64 ** -0.5 * 16 ** -0.5
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.experts_held, moe.score_func) == (
        128, 8, (0, 16), "softmax")
    assert moe.expert_intermediate_dim == 768 and not moe.n_shared_experts
    assert all(k == LayerKind(mlp="moe", indexed=True) for k in cfg.kinds())
    assert {k.parts for k in cfg.kinds()} == {"indexedattention+moe"}
    assert cfg.stack_paths() == {"indexedattention+moe": (("layers",), tuple(range(6)))}
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))))
    assert 655e6 < n < 663e6  # 659 M: 9.2 GB at 14 bytes a parameter


@pytest.mark.parametrize("bad,match", [
    (dict(decoder_sparse_step=2), "an expert layer in every layer"),
    (dict(mlp_only_layers=[0]), "an expert layer in every layer"),
    (dict(sliding_window=128, use_sliding_window=True), "full causal attention"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "default rotary"),
    (dict(sa_config=None), "sa_config"),
    (dict(sa_config=dict(SA, indexer_num_kv_heads=2)), "one key head"),
    (dict(norm_topk_prob=False), "renormalises"),
])
def test_config_from_hf_refuses_what_the_program_does_not_run(bad, match):
    with pytest.raises(NotImplementedError, match=match):
        _cfg(**bad)


def test_hf_round_trip_on_a_toy_checkpoint(tmp_path):
    from areal_tpu.models.hf import load_hf_model, save_hf_model

    cfg = _cfg()
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = get_family("KeyeVL2").params_to_hf(params, cfg)
    at = "model.layers.1.self_attn"
    assert sd[f"{at}.q_proj.weight"].shape == (32, 32)
    assert sd[f"{at}.k_proj.weight"].shape == (16, 32)
    assert sd[f"{at}.q_norm.weight"].shape == (8,)
    assert sd[f"{at}.indexer.wq.weight"].shape == (16, 32)
    assert sd[f"{at}.indexer.wk.weight"].shape == (8, 32)
    assert sd[f"{at}.indexer.weights_proj.weight"].shape == (2, 32)
    assert sd[f"{at}.indexer.k_norm.weight"].shape == sd[f"{at}.indexer.k_norm.bias"].shape == (8,)
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 32)
    assert "model.layers.1.mlp.experts.4.up_proj.weight" in sd  # the first held
    assert "model.layers.1.mlp.experts.3.up_proj.weight" not in sd
    save_hf_model(str(tmp_path), cfg, params, "KeyeVL2")
    cfg2, back = load_hf_model(str(tmp_path))
    assert cfg2.kinds() == cfg.kinds() and cfg2.indexer == cfg.indexer
    assert cfg2.moe == cfg.moe and cfg2.qk_norm
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_seeded_attention_is_peaked_where_an_indexer_chooses():
    """The seeded draw's first departure (`_INDEXED_Q_GAIN`): the q norm
    of an indexed layer starts at 3, every other norm at 1."""
    params = init_params(_cfg(), jax.random.PRNGKey(0))
    at = params["layers"]["attn"]
    assert float(at["q_norm"].min()) == float(at["q_norm"].max()) == 3.0
    assert float(at["k_norm"].min()) == float(at["indexer"]["ik_norm"]["weight"].max()) == 1.0
    plain = init_params(dataclasses.replace(_cfg(), indexer=None), jax.random.PRNGKey(0))
    assert float(plain["layers"]["attn"]["q_norm"].max()) == 1.0
    assert "indexer" not in plain["layers"]["attn"]


def test_the_seeded_embedding_is_a_tokens_own_under_an_indexer():
    """The seeded draw's second departure (`_INDEXED_EMBED_SCALE`): the
    embedding of a stack with indexed layers is the plain stack's draw at
    a scale of 2, key for key, and nothing else moves but the q norm."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plain = init_params(dataclasses.replace(cfg, indexer=None), jax.random.PRNGKey(0))
    emb = params["embedding"]["weight"]
    assert 1.8 < float(jnp.std(emb)) < 2.2
    np.testing.assert_allclose(emb, 100.0 * plain["embedding"]["weight"], rtol=1e-5)
    np.testing.assert_array_equal(params["head"]["weight"], plain["head"]["weight"])
    for name in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(params["layers"]["mlp"][name],
                                      plain["layers"]["mlp"][name])


# ---------------------------------------------------------------------------
# What other paths lack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["prefill", "decode_step", "paged_decode_step",
                                   "ServingEngine"])
def test_the_cache_paths_name_what_they_lack(where):
    cfg = _cfg()
    for what in ("the indexer: a cache of indexer keys",
                 "the sigmoid router, shared expert and held-experts share"):
        with pytest.raises(NotImplementedError, match=what):
            cfg.require_plain_stack(where)
    with pytest.raises(NotImplementedError) as e:
        cfg.require_plain_stack(where)
    assert "a kind per layer" not in str(e.value)


def test_what_the_stack_cannot_run_is_refused_by_mechanism():
    from areal_tpu.models import transformer as tf
    from areal_tpu.models.generation import prefill

    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([20], 32)
    with pytest.raises(NotImplementedError, match="no indexer keys beside k and v"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    with pytest.raises(NotImplementedError, match="a cache of indexer keys"):
        prefill(params, cfg, ids, seg, pos)
    with pytest.raises(NotImplementedError, match="no window"):
        LayerKind(indexed=True, window=8)
    with pytest.raises(ValueError, match="describe an attention mixer"):
        LayerKind(mixer="ssm", indexed=True)
    with pytest.raises(ValueError, match="needs TransformerConfig.indexer"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(indexed=True),))
    # the context-parallel paths refuse the indexer by name
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    q, k, v, _, _ = tf._attn_in(jnp.zeros((1, 32, 32)), lp, cfg, jnp.float32)
    cos = sin = jnp.zeros((1, 32, 4))
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="with an indexer"):
            tf._attn_core(q, k, v, cfg, cos, sin, seg, pos, impl, None, ((None, True),), None,
                          False, tf._Index(cos, sin, False, None), None)
