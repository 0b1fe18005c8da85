"""A stack of Gated DeltaNet layers with a state of K x V a head and a doubled
beta beside plain attention without rotary, every layer under a dense MLP
behind output norms (the `olmo_hybrid` family): the program against the
plain reference `benchmark/reference/olmo_hybrid.py` on the CPU, float32,
seeded random weights, toy widths (hidden 64, two delta-rule heads of 16 x
32, four attention heads of 16, four layers `L L L F`), through the forward
pass, a PPO step's loss and gradients, `JaxTrainEngine` and the PPO
interface."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine.train_counts import kinds_label
from areal_tpu.models.config import KDAConfig, LayerKind, TransformerConfig
from areal_tpu.models.hf import family_from_hf_config, get_family
from areal_tpu.models.transformer import forward, init_params, looping_layers
from areal_tpu.ops.loss import response_positions
from benchmark.reference import olmo_hybrid as ref

from tests.engine.test_latent_engine import n_response, ppo_like_batch, response_loss
from tests.model.test_hybrid_stack import _ppo_loss
from tests.model.test_hyper_stack import _flat
from tests.model.test_kda_stack import _program_logprobs
from tests.model.test_layer_kinds import _assert_trees_close, _packed, small_bands

L, F = "linear_attention", "full_attention"
HF = dict(
    model_type="olmo_hybrid", hidden_size=64, intermediate_size=96, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, vocab_size=64, max_position_embeddings=512,
    rms_norm_eps=1e-6, hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    layer_types=[L, L, L, F], linear_num_key_heads=2, linear_num_value_heads=2,
    linear_key_head_dim=16, linear_value_head_dim=32, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
)
KINDS = "dense.kda.head.k2.16x32.b2.c64 x3,dense.full.nope"
CONTROLS = ("beta_sigmoid", "no_decay", "no_correction", "no_k_scale", "no_conv", "z_sigmoid",
            "norm_in", "no_out_norms", "qk_head_norm", "rotary", "v_halves")


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
def test_the_stack_matches_the_reference_through_a_ppo_step(remat):
    """`L L L F`, a scan of three delta-rule layers and the attention layer
    alone: logprobs, the PPO loss and every parameter's gradient, two
    sequences packed in a row of two chunks of 64."""
    cfg = _cfg()
    assert [k.parts for k in cfg.kinds()] == ["kda+dense"] * 3 + ["attention+dense"]
    assert not cfg.pre_norms and cfg.post_norms and cfg.qk_norm_over == "width"
    params = _params(cfg)
    assert set(params["stacks"]) == {"kda+dense", "attention+dense"}
    for stack in params["stacks"].values():  # output norms only
        assert {n for n in stack if n.startswith("ln")} == {"ln1_post", "ln2_post"}
    at = params["stacks"]["attention+dense"]["attn"]
    assert set(at) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert at["q_norm"].shape == at["k_norm"].shape == (1, 64)  # over the width, not a head
    kd = params["stacks"]["kda+dense"]["kda"]
    assert kd["wq"].shape == (3, 64, 32) and kd["wv"].shape == kd["w_g"].shape == (3, 64, 64)
    assert kd["o_norm"].shape == (3, 32) and kd["wo"].shape == (3, 64, 64)
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
    _assert_trees_close(g_prog, g_ref, rtol=1e-4)


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_of_the_tolerance_moves_the_reference(control):
    """What `scripts/tolerance_controls_olmo_hybrid.py` changes in the
    reference shows in its logprobs at toy size too: no control is a no-op of
    the reference's code."""
    hf = dict(HF, num_hidden_layers=2, layer_types=[L, F])  # one layer of each kind: a shorter build
    params = _params(_cfg(hf))
    _, _, _, seqs = _packed(rows=((80,),), row_len=128)
    want = _reference_logprobs(params, hf, seqs)
    got = _reference_logprobs(params, hf, seqs, control)
    assert max(float(jnp.abs(g - w).max()) for g, w in zip(got, want)) > 1e-3


def test_a_packed_row_is_each_of_its_sequences_alone_through_the_stack():
    """Logprobs and the gradient of their sum: two sequences in one row (the
    second starts inside a chunk) against each in a row of its own (state and
    convolution start afresh at a sequence's start, and attention sees no
    other sequence)."""
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


def test_a_half_empty_row_walks_its_live_bands_and_matches_the_reference(monkeypatch):
    """One row alone, 70 tokens in 192 cells: every layer's two stretches
    (no norm on the way in, the output norms and the width-wide norm of q and
    k inside them) run over the row's live bands, and logprobs and gradients
    are the whole row's and the reference's."""
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((70,),), row_len=192)
    assert looping_layers(cfg, 1, 192) == 0  # under two bands of 1,024
    whole = lambda p: sum(x.sum() for x in _program_logprobs(
        p, cfg, ids, seg, pos, seqs, remat="full", bands=True))
    want, g_want = jax.jit(jax.value_and_grad(whole))(params)
    ran = small_bands(monkeypatch)
    assert looping_layers(cfg, 1, 192) == 4
    got, g_got = jax.jit(jax.value_and_grad(whole))(params)
    assert ran.count("_before_mixer") >= 2 and ran.count("_after_mixer") >= 2
    np.testing.assert_allclose(float(got), float(want), atol=2e-4)
    _assert_trees_close(g_got, g_want, rtol=2e-4)
    for g, w in zip(_program_logprobs(params, cfg, ids, seg, pos, seqs, bands=True),
                    _reference_logprobs(params, HF, seqs)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_the_sliced_vocabulary_is_the_uncut_references_logits_over_the_slice():
    """The first eighth of the rows of embedding and of head: the program's
    logits over the slice are the uncut reference's logits at those columns
    (token ids drawn from the slice), so softmax and loss over the slice are
    one vocabulary-parallel chip's."""
    whole_hf = dict(HF, vocab_size=512)
    whole = _params(_cfg(whole_hf))
    cut = dict(whole, embedding={"weight": whole["embedding"]["weight"][:64]},
               head={"weight": whole["head"]["weight"][:, :64]})
    cfg = _cfg()
    ids, seg, pos, seqs = _packed(rows=((80, 40),), row_len=128)  # ids under 64
    with jax.default_matmul_precision("highest"):
        got = forward(cut, cfg, ids, seg, pos, attn_impl="reference")
    for r, o, t in seqs:
        n = -(-len(t) // ref.ROWS) * ref.ROWS
        want = ref.logits(whole, whole_hf, np.concatenate([t, np.zeros(n - len(t), np.int64)]))
        np.testing.assert_allclose(np.asarray(got[r, o:o + len(t)]),
                                   np.asarray(want[: len(t), :64]), atol=5e-5)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs guide is not installed here")
    return next(r for r in map(json.loads, open(catalog)) if r["name"] == "Olmo-Hybrid-7B")


def test_the_family_takes_the_catalog_rows_config_as_it_is():
    hf = _catalog_row()["config"]
    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    kinds = cfg.kinds()
    assert len(kinds) == 32 and [k.mixer for k in kinds] == ["kda", "kda", "kda", "attention"] * 8
    assert all(k.mlp == "dense" for k in kinds)
    assert not any(k.rotary for k in kinds if k.mixer == "attention")  # rope_theta null
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.intermediate_dim,
            cfg.vocab_size, cfg.max_position_embeddings) == (3840, 30, 30, 128, 11008, 100352,
                                                             65536)
    assert cfg.qk_norm and cfg.qk_norm_over == "width" and not cfg.pre_norms and cfg.post_norms
    assert not cfg.attn_bias and not cfg.tied_embeddings and cfg.moe is None
    assert cfg.kda == KDAConfig(
        n_heads=30, n_key_heads=30, head_dim=96, value_head_dim=192, neg_eigval=True,
        conv_kernel=4, gate_rank=None, chunk_size=64, decay="head", decay_input="column",
        gate_act="silu")
    assert (cfg.kda.d_key, cfg.kda.d_inner) == (2880, 5760)
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [
        (("kda+dense", "kda+dense", "kda+dense", "attention+dense"), 8)]
    assert kinds_label(cfg) == (
        "dense.kda.head.k30.96x192.b2.c64 x3,dense.full.nope," * 8).rstrip(",")


def test_olmo_hybrid_config_and_checkpoint_layout_round_trip():
    fam = get_family("olmo_hybrid")
    cfg = _cfg()
    back = fam.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    again = dataclasses.replace(fam.config_from_hf(back), param_dtype="float32",
                                compute_dtype="float32")
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    assert TransformerConfig(**dataclasses.asdict(cfg)).kda == cfg.kda  # the launcher's kwargs
    params = jax.tree_util.tree_map(np.asarray, _params(cfg))
    sd = fam.params_to_hf(params, cfg)
    lin = jax.tree_util.tree_map(lambda a: a[1], params["stacks"]["kda+dense"])
    at = "model.layers.1.linear_attn"
    assert sd[f"{at}.q_proj.weight"].shape == (32, 64) and sd[f"{at}.v_proj.weight"].shape == (64, 64)
    np.testing.assert_array_equal(sd[f"{at}.g_proj.weight"], lin["kda"]["w_g"].T)
    assert sd[f"{at}.v_conv1d.weight"].shape == (64, 1, 4)
    np.testing.assert_array_equal(sd[f"{at}.k_conv1d.weight"][:, 0, :], lin["kda"]["conv_k"].T)
    assert sd[f"{at}.o_norm.weight"].shape == (32,) and sd[f"{at}.A_log"].shape == (2,)
    full = jax.tree_util.tree_map(lambda a: a[0], params["stacks"]["attention+dense"])
    assert sd["model.layers.3.self_attn.q_norm.weight"].shape == (64,)
    np.testing.assert_array_equal(sd["model.layers.3.self_attn.o_proj.weight"],
                                  full["attn"]["wo"].T)
    for name in ("0.post_attention_layernorm.weight", "3.post_feedforward_layernorm.weight",
                 "2.mlp.down_proj.weight"):
        assert f"model.layers.{name}" in sd
    assert not any("input_layernorm" in k for k in sd)  # no norm on the way in
    assert "lm_head.weight" in sd and "model.norm.weight" in sd
    back = fam.params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("over,match", [
    (dict(attention_bias=True), "attention_bias"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(rope_parameters={"rope_theta": 5e5, "rope_type": "yarn", "factor": 8.0}),
     "rope_parameters"),
    (dict(layer_types=[L, L, "sliding_attention", F]), "layer_types"),
    (dict(linear_num_key_heads=3, linear_num_value_heads=4), "do not divide"),
], ids=["bias", "window", "scaled_table", "layer_type", "key_heads"])
def test_what_the_family_cannot_run_is_refused_by_name(over, match):
    with pytest.raises(NotImplementedError, match=match):
        _cfg(dict(HF, **over))


def test_a_theta_that_is_a_number_turns_the_attention_layers():
    hf = dict(HF, rope_parameters={"rope_theta": 10000.0})
    cfg = _cfg(hf)
    assert [k.rotary for k in cfg.kinds() if k.mixer == "attention"] == [True]
    params = _params(cfg)
    ids, seg, pos, seqs = _packed(rows=((80, 40),), row_len=128)
    for g, w in zip(_program_logprobs(params, cfg, ids, seg, pos, seqs),
                    _reference_logprobs(params, HF, seqs, "rotary")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_what_the_cache_paths_and_the_config_cannot_run_is_refused_by_mechanism():
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed()
    with pytest.raises(NotImplementedError, match="return_kv"):
        forward(params, cfg, ids, seg, pos, return_kv=True)
    for where in ("prefill", "paged_decode_step", "ServingEngine"):
        with pytest.raises(NotImplementedError, match=(
                r"a delta-rule state beside the KV pages.*its state \[2, 16, 32\]")):
            cfg.require_plain_stack(where)
        with pytest.raises(NotImplementedError, match="a block with output norms only"):
            cfg.require_plain_stack(where)
    with pytest.raises(NotImplementedError, match="no norm at all"):
        TransformerConfig(pre_norms=False)
    with pytest.raises(ValueError, match="qk_norm_over"):
        TransformerConfig(qk_norm=True, qk_norm_over="rows")
    with pytest.raises(NotImplementedError, match="normed over their whole width"):
        TransformerConfig(n_layers=2, qk_norm=True, qk_norm_over="width", layer_kinds=(
            LayerKind(keeps=True), LayerKind(reads=0)))
    with pytest.raises(NotImplementedError, match="values wider than keys"):
        KDAConfig(n_heads=2, head_dim=16, value_head_dim=32)


# --- through the trainer engine -------------------------------------------------------


@pytest.fixture
def _tracing_off(monkeypatch):
    monkeypatch.setenv("AREAL_RL_TRACE", "0")
    monkeypatch.delenv("AREAL_RL_TRACE_DIR", raising=False)
    tracing.reconfigure()
    yield
    tracing.reconfigure()


def engine(depth=2, row_len_multiple=32):
    cfg = _cfg()
    eng = JaxTrainEngine(
        cfg, _params(cfg, seed=2),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10, row_len_multiple=row_len_multiple, prefetch_depth=depth,
        attn_impl="reference", hf_family="olmo_hybrid")
    return cfg, eng


def test_the_engines_logprobs_are_the_plain_references(_tracing_off):
    cfg, eng = engine()
    rng = np.random.default_rng(3)
    lens = [40, 23, 31]
    ids = rng.integers(0, 64, sum(lens)).astype(np.int32)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))], seqlens=lens,
        data={"packed_input_ids": ids})
    got = np.asarray(eng.forward(sample, MicroBatchSpec()).data["logprobs"], np.float32)
    o = 0
    for l in lens:
        want = ref.next_token_logprobs(eng.params, HF, ids[o:o + l], pad_to=256)
        np.testing.assert_allclose(got[o:o + l - 1], want, atol=5e-5)
        o += l


@pytest.mark.parametrize("depth", [0, 2], ids=["fused", "overlapped"])
def test_a_train_step_moves_both_mixers_and_counts_what_the_rule_ran(depth, _tracing_off):
    cfg, eng = engine(depth)
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    lens = [30, 1, 44, 2, 25, 3, 38, 17, 51]
    prompts = [10, 1, 20, 1, 24, 1, 5, 8, 30]
    batch = ppo_like_batch(lens, prompts)
    tracing.start()
    try:
        stats = dict(eng.train_batch(batch, MicroBatchSpec(n_mbs=3), response_loss,
                                     n_response, loss_name="t", scored_fn=response_positions))
    finally:
        got = tracing.stop()
    after = jax.tree_util.tree_map(np.asarray, eng.params)
    moved = _flat(jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), after, before))
    assert all(v > 0 for v in moved.values()), moved
    assert sum("'kda'" in k for k in moved) == 13  # as the other head form's mixer
    assert sum("'attn'" in k for k in moved) == 6  # wq, wk, wv, wo, q_norm, k_norm
    assert sum("_post" in k for k in moved) == 4 and not any("'ln1'" in k for k in moved)
    assert np.isfinite(stats["t/loss"]) and stats["t/update_norm"] > 0
    c = got["counters"]
    assert c["train.kda_cells"] == 3 * c["train.cells"] // 96 * 128 > 0
    assert c["train.kda_chunks"] * 64 == c["train.kda_cells"]
    assert c["train.kda_fwd_kernel_cells"] == c["train.kda_bwd_kernel_cells"] == 0  # the CPU
    assert c["train.kda_taps_cells"] == 3 * c["train.cells"]
    assert c["train.kda_taps_kernel_cells"] == 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"]
    assert c["train.kda_resets"] == 3 * len(lens)
    assert c["train.attn_cells"] == c["train.cells"]  # the one attention layer's alone
    assert "train.moe_pairs" not in c or c["train.moe_pairs"] == 0
    dispatch = [s["attrs"] for s in got["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(d["kinds"] == KINDS for d in dispatch)


def test_the_family_runs_through_the_ppo_interface(_tracing_off):
    from areal_tpu.api.config import ModelName
    from areal_tpu.api.model_api import Model
    from areal_tpu.interfaces.ppo import PPOActorInterface

    cfg, eng = engine(2)
    lens, prompts = [30, 44, 25, 38], [10, 20, 24, 5]
    total = sum(lens)
    batch = ppo_like_batch(lens, prompts)
    rng = np.random.default_rng(0)
    mask = np.concatenate([np.r_[np.zeros(p - 1), np.ones(l - p), 0.0]
                           for l, p in zip(lens, prompts)]).astype(np.float32)
    lp = np.asarray(eng.forward(batch, MicroBatchSpec()).data["logprobs"], np.float32)
    lp = np.r_[lp, 0.0][:total] if len(lp) < total else lp
    batch.update_(SequenceSample.from_default(
        ids=batch.ids, seqlens=lens,
        data={"packed_logprobs": (lp * mask).astype(np.float32),
              "ref_logprobs": (lp * mask).astype(np.float32),
              "rewards": rng.normal(size=len(lens)).astype(np.float32),
              "seq_no_eos_mask": np.zeros(len(lens), np.float32)}))
    before = jax.tree_util.tree_map(np.asarray, eng.params)
    stats = PPOActorInterface(n_minibatches=1).train_step(
        Model(name=ModelName("actor"), module=eng, tokenizer=None), batch, MicroBatchSpec())
    assert stats["ppo_actor/n_tokens"] == total
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                                   eng.params, before)
    assert moved["stacks"]["kda+dense"]["kda"]["A_log"] > 0
    assert moved["stacks"]["attention+dense"]["attn"]["q_norm"] > 0
    assert moved["stacks"]["kda+dense"]["ln2_post"]["weight"] > 0
