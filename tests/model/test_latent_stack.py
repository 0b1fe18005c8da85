"""A stack of DeepSeek-V3's shape (`model_type` joyai_llm_flash at toy
widths): latent attention over packed rows (q and k of nope + rope against
v of another size, one rope key a token for every head, rotary over the
rope part alone), a leading dense layer and sigmoid-routed expert layers
holding a share, and a multi-token-prediction module after the stack. The
program against the plain reference (`benchmark/reference/joyai_llm_flash.py`),
a packed row against its sequences alone, the shares of the expert layer,
the family's round trip, and what the cache paths lack. Float32 on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moe_lib
from areal_tpu.models.config import LayerKind, MLAConfig, MTPConfig, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models import transformer as tf
from areal_tpu.models.transformer import forward, init_params
from areal_tpu.ops.loss import fused_next_token_logprobs, head_cells_run, two_on
from areal_tpu.ops.rotary import rotary_cos_sin, rotary_inv_freq
from benchmark.reference import joyai_llm_flash as ref

HF = dict(model_type="joyai_llm_flash", num_hidden_layers=3, hidden_size=32,
          num_attention_heads=4, num_key_value_heads=4, head_dim=4,
          q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
          qk_head_dim=12, v_head_dim=16, intermediate_size=48, vocab_size=64,
          moe_intermediate_size=16, n_routed_experts=4, num_experts_routed=16,
          experts_held_first=4, num_experts_per_tok=4, n_shared_experts=1,
          first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
          norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
          topk_method="noaux_tc", num_nextn_predict_layers=1, rms_norm_eps=1e-6,
          rope_theta=32e6, rope_interleave=True, rope_scaling=None,
          tie_word_embeddings=False, max_position_embeddings=512)
# latent attention alone: dense layers only, no module
DENSE = dict(HF, num_hidden_layers=2, first_k_dense_replace=2, num_nextn_predict_layers=0)


def _cfg(hf=HF, **over):
    hf = dict(hf, **over)
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    return cfg


def _params(cfg, seed=0):
    """Seeded weights with the norms and the selection bias moved off
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
    """([T] next-token logprobs, [T] the module's of the token two on; 0
    where a position has no such target in its own sequence)."""
    mtp = cfg.mtp is not None
    out = forward(params, cfg, ids, seg, pos, output="hidden", mtp=mtp, **kw)
    hidden, x_mtp = out if mtp else (out, None)
    head = params["head"]["weight"]
    lp = fused_next_token_logprobs(hidden, head, ids, seg)[0]
    if not mtp:
        return lp, None
    return lp, fused_next_token_logprobs(x_mtp, head, ids, seg, shift=2)[0]


def _flat(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# The program against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("remat", ["full", "none"])
@pytest.mark.parametrize("hf", [HF, DENSE], ids=["whole", "latent_alone"])
def test_logprobs_are_the_plain_references(hf, remat):
    cfg = _cfg(hf)
    assert [s.repeats for s in cfg.segments()] == ([1, 2] if hf is HF else [2])
    params = _params(cfg)
    n = 40
    ids, seg, pos = _row([n], 48)
    lp, lp2 = _logprobs(params, cfg, ids, seg, pos, remat=remat)
    want = ref.next_token_logprobs(params, hf, np.asarray(ids[0, :n]), pad_to=256)
    np.testing.assert_allclose(lp[: n - 1], want, atol=3e-5)
    assert float(jnp.abs(lp[n - 1:]).max()) == 0
    if hf is HF:
        want2 = ref.mtp_logprobs(params, hf, np.asarray(ids[0, :n]), pad_to=256)
        np.testing.assert_allclose(lp2[: n - 2], want2, atol=3e-5)
        assert float(jnp.abs(lp2[n - 2:]).max()) == 0


@pytest.mark.parametrize("hf", [HF, DENSE], ids=["whole", "latent_alone"])
def test_gradients_are_the_plain_references(hf):
    """Of the sum of the logprobs, and with the module of its own too:
    every leaf is reached, the module's and the stack's. `forward` hands
    the module the stack's output and the embedding table as constants."""
    _assert_gradients_are_the_references(hf, "full")


@pytest.mark.parametrize("remat", ["none", "full", "mlp"])
def test_a_half_empty_row_of_the_latent_stack_walks_its_live_bands(remat, monkeypatch):
    """One row alone, 40 tokens in 256 cells at bands of 16: the dense
    layer, the two scanned expert layers (one traced body) and the
    module's block, which runs once after the stack, run their two
    stretches each over three bands of sixteen; logprobs, the module's,
    and every gradient are the plain reference's."""
    from areal_tpu.models.transformer import looping_layers

    from tests.model.test_layer_kinds import small_bands

    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    ran = small_bands(monkeypatch)
    cfg = _cfg(HF)
    params = _params(cfg)
    n = 40
    ids, seg, pos = _row([n], 256)
    lp, lp2 = _logprobs(params, cfg, ids, seg, pos, remat=remat, bands=True)
    assert looping_layers(cfg, 1, 256) == 3 and looping_layers(cfg, 1, 256, mtp=True) == 4
    assert ran == ["_before_mixer", "_after_mixer"] * 3
    np.testing.assert_allclose(lp[: n - 1], ref.next_token_logprobs(
        params, HF, np.asarray(ids[0, :n]), pad_to=256), atol=3e-5)
    np.testing.assert_allclose(lp2[: n - 2], ref.mtp_logprobs(
        params, HF, np.asarray(ids[0, :n]), pad_to=256), atol=3e-5)
    _assert_gradients_are_the_references(HF, remat, bands=True)


def _assert_gradients_are_the_references(hf, remat, **kw):
    cfg = _cfg(hf)
    params = _params(cfg)
    n, T = 40, 256
    ids, seg, pos = _row([n], T)
    small = {k: hf[k] for k in ref._KEYS}

    def want_fn(p):
        still = jax.lax.stop_gradient
        with jax.default_matmul_precision("highest"):
            h, head = ref._stack(p, ids[0], small), p["head"]["weight"]
            total = ref._head_logprobs(h, head, jnp.roll(ids[0], -1))[: n - 1].sum()
            if hf is HF:
                frozen = dict(p, embedding=still(p["embedding"]))
                total += ref._mtp(frozen, ids[0], still(h), head, small)[: n - 2].sum()
        return total

    def got_fn(p):
        lp, lp2 = _logprobs(p, cfg, ids, seg, pos, remat=remat, **kw)
        return lp.sum() + (lp2.sum() if hf is HF else 0.0)

    want, got = _flat(jax.grad(want_fn)(params)), _flat(jax.grad(got_fn)(params))
    assert want.keys() == got.keys()
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        if "expert_bias" in name:  # chosen on it, under stop_gradient
            assert scale == 0 and float(jnp.abs(got[name]).max()) == 0
            continue
        assert scale > 0, name  # every leaf is reached
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale + 1e-6,
                                   err_msg=name)


def test_the_latent_block_is_the_references_materialised_form():
    """One layer's attention alone: the two norms inside the projections,
    rotary over the rope part's 4 of 12 dimensions in pairs (2i, 2i + 1),
    the one rope key under every head, scores over sqrt(12), v of 16."""
    cfg = _cfg()
    at = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["lead_layers"]["attn"])
    T = 256
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, 32))
    pos = jnp.arange(T)[None]
    cos, sin = rotary_cos_sin(pos, jnp.asarray(rotary_inv_freq(4, cfg.rotary_base)))
    assert cfg.rotary_dim == 4 and cos.shape == (1, T, 2)
    with jax.default_matmul_precision("highest"):
        q, k, v = tf._latent_in(h, at, cfg, cos, sin, jnp.float32)
        got = tf._latent_out(tf._latent_core(
            q, k, v, cfg, jnp.ones((1, T), jnp.int32), pos, "reference", None), at, jnp.float32)
        want = ref.latent_attention(h[0], at, HF)
    assert k.shape == (1, T, 4, 12) and v.shape == (1, T, 4, 16)
    # every head's k ends in the same rope key
    assert float(jnp.abs(k[..., 8:] - k[:, :, :1, 8:]).max()) == 0
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# ---------------------------------------------------------------------------
# Packed rows
# ---------------------------------------------------------------------------


LENS, ROW = [21, 1, 29, 2, 17, 3], 80


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_a_packed_row_is_each_of_its_sequences_alone(what, monkeypatch):
    """The shared rope key, the rotary part and the module's two-token
    shift stay inside a sequence, and the padding adds nothing: to 2e-5,
    values and gradients. A sequence of one token scores nothing, one of
    two has no target two on."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row(LENS, ROW)
    offs = np.concatenate([[0], np.cumsum(LENS)])

    def packed(p):
        return _logprobs(p, cfg, ids, seg, pos, remat="full")

    def alone(p):
        out, out2 = [], []
        for j, l in enumerate(LENS):
            o = offs[j]
            one = (ids[:, o:o + l], jnp.ones((1, l), jnp.int32), pos[:, o:o + l])
            lp, lp2 = _logprobs(p, cfg, *one)
            out.append(lp[: l - 1])
            out2.append(lp2[: max(l - 2, 0)])
        return jnp.concatenate(out), jnp.concatenate(out2)

    at = lambda back: np.concatenate(
        [np.arange(offs[j], offs[j] + max(l - back, 0)) for j, l in enumerate(LENS)])
    scored, scored2 = at(1), at(2)
    if what == "values":
        lp, lp2 = packed(params)
        want, want2 = alone(params)
        np.testing.assert_allclose(lp[scored], want, atol=2e-5)
        np.testing.assert_allclose(lp2[scored2], want2, atol=2e-5)
        rest = lambda a, idx: np.delete(np.asarray(a), idx)
        assert not rest(lp, scored).any() and not rest(lp2, scored2).any()
        return
    w = jax.random.normal(jax.random.PRNGKey(5), (ROW,))

    def total(fn):
        def f(p):
            lp, lp2 = fn(p)
            if fn is packed:
                lp, lp2 = lp[scored], lp2[scored2]
            return (lp * w[scored]).sum() + (lp2 * w[scored2]).sum()
        return f

    g_packed, g_alone = jax.grad(total(packed))(params), jax.grad(total(alone))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_packed), jax.tree_util.tree_leaves(g_alone)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))


def test_what_the_padding_holds_reaches_nothing():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row([30], 48)
    other = ids.at[0, 30:].set(7)
    for a, b in zip(_logprobs(params, cfg, ids, seg, pos),
                    _logprobs(params, cfg, other, seg, pos)):
        np.testing.assert_array_equal(a[:29], b[:29])


def test_the_host_counts_the_modules_targets_by_the_devices_rule():
    """`two_on` of the loss's scored positions, the shift of two inside a
    sequence, and the chunks the head then runs: the host's count
    (`head_cells_run`) against the positions the device's head fills."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos = _row(LENS, ROW)
    prompt = np.zeros((1, ROW), np.int32)
    o = 0
    for l, pl in zip(LENS, [8, 1, 10, 1, 16, 1]):
        prompt[0, o:o + pl] = 1
        o += l
    from areal_tpu.ops.loss import response_scoring_mask

    scored = response_scoring_mask(np.asarray(seg), prompt)
    keep = two_on(scored)
    # per sequence: positions prompt_len - 2 .. len - 3
    want = sum(max(l - 2 - max(pl - 2, 0), 0) for l, pl in zip(LENS, [8, 1, 10, 1, 16, 1]))
    n_read, n_cells = head_cells_run(np.asarray(seg), keep, 64, shift=2)
    assert n_read == want == 13 + 0 + 19 + 0 + 1 + 1
    assert n_cells == ROW  # one chunk of 80 holds them
    _, x_mtp = forward(params, cfg, ids, seg, pos, output="hidden", mtp=True)
    lp, hit = fused_next_token_logprobs(
        x_mtp, params["head"]["weight"], ids, seg, scored=jnp.asarray(keep), shift=2, top=True)
    assert int((lp != 0).sum()) == want
    assert set(np.unique(hit)) <= {0.0, 1.0} and not hit[lp == 0].any()
    # the label is the argmax exactly where its logprob is the largest
    logits = x_mtp[0] @ params["head"]["weight"]
    label = np.roll(np.asarray(ids[0]), -2)
    np.testing.assert_array_equal(
        np.asarray(hit[0]) > 0, (np.asarray(logits.argmax(-1)) == label) & np.asarray(lp[0] != 0))


# ---------------------------------------------------------------------------
# The expert layer's shares
# ---------------------------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The share test: the held-experts results of all 16 shares of 16
    experts of 256, the shared expert counted once, add up to what the
    reference gives for the whole layer."""
    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    hf = dict(HF, num_hidden_layers=2, n_routed_experts=256, num_experts_routed=256,
              experts_held_first=0, num_experts_per_tok=8, num_nextn_predict_layers=0)
    cfg = _cfg(hf)
    assert cfg.moe.experts_held is None and cfg.moe.num_experts == 256
    mlp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg, 3)["layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (96, 32))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, mlp, hf)
        total, pairs = jnp.zeros_like(h), 0.0
        for share in range(16):
            held = (16 * share, 16)
            c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mp = {k: (v[held[0]: held[0] + 16] if k in ("w_gate", "w_up", "w_down") else v)
                  for k, v in mlp.items() if k != "shared" or share == 0}
            y, aux = moe_lib.moe_mlp(h, mp, c, jnp.float32)
            total, pairs = total + y, pairs + float(aux["pairs_held"])
            part = ref.expert_layer(h, mp, dict(hf, n_routed_experts=16,
                                                experts_held_first=held[0]))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5)
    assert pairs == h.shape[0] * 8  # every pair is held by one share


# ---------------------------------------------------------------------------
# The family
# ---------------------------------------------------------------------------


def test_config_from_hf_reads_the_published_keys():
    import json

    with open("benchmark/configs/joyai-llm-flash-d6-e16.json") as f:
        hf = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = family_from_hf_config(hf).config_from_hf(hf)
    assert cfg.mla == MLAConfig(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.n_q_heads, cfg.n_kv_heads) == (192, 64, 32, 32)
    assert cfg.rotary_interleaved and cfg.rotary_base == 32e6 and cfg.rotary_scaling is None
    assert cfg.mtp == MTPConfig(n_modules=1, loss_weight=0.1)
    moe = cfg.moe
    assert (moe.num_experts, moe.experts_held, moe.top_k) == (256, (0, 16), 8)
    assert (moe.score_func, moe.route_norm, moe.routed_scaling_factor) == ("sigmoid", True, 2.5)
    assert moe.router_bias and moe.n_shared_experts == 1 and moe.aux_loss_coef == 0
    kinds = cfg.kinds()
    assert kinds[0] == LayerKind(mlp="dense", latent=True)
    assert set(kinds[1:]) == {LayerKind(mlp="moe", latent=True)} and len(kinds) == 6
    assert cfg.stack_paths() == {
        "latentattention+moe": (("layers",), (1, 2, 3, 4, 5)),
        "latentattention+dense": (("lead_layers",), (0,))}
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 787.5  # the configuration file's count
    back = get_family("joyai_llm_flash").config_to_hf(cfg)
    assert {k: back[k] for k in hf if k in back} == {k: hf[k] for k in hf if k in back}
    assert not set(hf) - set(back) - {"ep_size"}


@pytest.mark.parametrize("bad,err,match", [
    (dict(n_group=2), NotImplementedError, "group-limited"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), NotImplementedError, "rope_scaling"),
    (dict(q_lora_rank=None), NotImplementedError, "q_lora_rank"),
    (dict(moe_layer_freq=2), NotImplementedError, "moe_layer_freq"),
    (dict(num_key_value_heads=2), ValueError, "k and v a head"),
    (dict(num_nextn_predict_layers=2), NotImplementedError, "one prediction module"),
])
def test_config_from_hf_refuses_what_the_program_does_not_run(bad, err, match):
    with pytest.raises(err, match=match):
        _cfg(**bad)


def test_hf_round_trip_on_a_toy_checkpoint(tmp_path):
    from areal_tpu.models.hf import load_hf_model, save_hf_model

    cfg = _cfg()
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = get_family("joyai_llm_flash").params_to_hf(params, cfg)
    at = "model.layers.1.self_attn"
    assert sd[f"{at}.q_a_proj.weight"].shape == (24, 32)
    assert sd[f"{at}.q_a_layernorm.weight"].shape == (24,)
    assert sd[f"{at}.q_b_proj.weight"].shape == (4 * 12, 24)
    assert sd[f"{at}.kv_a_proj_with_mqa.weight"].shape == (16 + 4, 32)
    assert sd[f"{at}.kv_b_proj.weight"].shape == (4 * (8 + 16), 16)
    assert sd[f"{at}.o_proj.weight"].shape == (32, 4 * 16)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (48, 32)  # the dense layer
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 32)
    assert sd["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (16,)
    assert "model.layers.1.mlp.experts.4.up_proj.weight" in sd  # the first held
    assert "model.layers.1.mlp.experts.3.up_proj.weight" not in sd
    # the module is layer `num_hidden_layers`
    for name, shape in (("enorm", (32,)), ("hnorm", (32,)), ("eh_proj", (32, 64)),
                        ("shared_head.norm", (32,)), ("shared_head.head", (64, 32)),
                        ("embed_tokens", (64, 32)), ("self_attn.kv_b_proj", (96, 16)),
                        ("mlp.shared_experts.down_proj", (32, 16))):
        assert sd[f"model.layers.3.{name}.weight"].shape == shape, name
    save_hf_model(str(tmp_path), cfg, params, "joyai_llm_flash")
    cfg2, back = load_hf_model(str(tmp_path))
    assert cfg2.kinds() == cfg.kinds() and cfg2.mla == cfg.mla and cfg2.mtp == cfg.mtp
    assert cfg2.moe == cfg.moe
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_seeded_latent_attention_is_peaked_and_its_norms_do_work():
    """What `init_params` draws for a latent layer (the configuration
    file's `assumed`): scores with a standard deviation near 3, latents
    well off unit size before their norms."""
    cfg = _cfg(hidden_size=256, q_lora_rank=192, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, qk_head_dim=48, head_dim=16)
    at = jax.tree_util.tree_map(
        lambda a: a[0], init_params(cfg, jax.random.PRNGKey(0))["lead_layers"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    rms = lambda a: float(jnp.sqrt(jnp.mean(a * a)))
    assert 0.2 < rms(h @ at["wq_a"]) < 0.3 and 0.2 < rms((h @ at["wkv_a"])[:, :64]) < 0.3
    assert 0.9 < rms((h @ at["wkv_a"])[:, 64:]) < 1.1  # the rope key has no norm
    q = ref._rms(h @ at["wq_a"], at["q_a_norm"], 1e-6) @ at["wq_b"]
    assert 2.7 < rms(q) < 3.3


# ---------------------------------------------------------------------------
# What cannot run it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["prefill", "decode_step", "paged_decode_step",
                                   "ServingEngine"])
def test_the_cache_paths_name_what_they_lack(where):
    cfg = _cfg()
    for what in (r"a latent cache: latent attention keeps, a token, one row of "
                 r"kv_rank \+ rope_dim = 20 values",
                 "the multi-token-prediction module: the cache paths have no pages",
                 "the sigmoid router, shared expert and held-experts share"):
        with pytest.raises(NotImplementedError, match=what):
            cfg.require_plain_stack(where)
    with pytest.raises(NotImplementedError) as e:
        _cfg(DENSE).require_plain_stack(where)
    assert "a latent cache" in str(e.value) and "prediction module" not in str(e.value)
    assert "a kind per layer" not in str(e.value)


def test_what_the_stack_cannot_run_is_refused_by_mechanism():
    from areal_tpu.engine.serving import ServingEngine
    from areal_tpu.models.generation import prefill

    cfg = _cfg(DENSE)
    params = _params(cfg)
    ids, seg, pos = _row([20], 32)
    with pytest.raises(NotImplementedError, match="no latent row"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        prefill(params, cfg, ids, seg, pos)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        ServingEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    with pytest.raises(ValueError, match="mtp=True needs cfg.mtp"):
        forward(params, cfg, ids, seg, pos, mtp=True)
    with pytest.raises(NotImplementedError, match="no window"):
        LayerKind(latent=True, window=8)
    with pytest.raises(ValueError, match="needs TransformerConfig.mla"):
        TransformerConfig(n_layers=1, layer_kinds=(LayerKind(latent=True),))
    with pytest.raises(ValueError, match="head_dim is a head's q and k"):
        TransformerConfig(n_layers=1, mla=MLAConfig(), head_dim=8, n_kv_heads=4)
    with pytest.raises(ValueError, match="one more transformer block"):
        TransformerConfig(n_layers=1, mtp=MTPConfig(), is_critic=True)
