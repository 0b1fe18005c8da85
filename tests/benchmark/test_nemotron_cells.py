"""The cell PR 32 adds (`nemotron3n-d9e8-train-ppo-long`), its
configuration, traffic, operation count and metrics, read from their
files. CPU only."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_hybrid, manifest, traffic
from benchmark.readers import flops_rate_hybrid, program_counter_ratio
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
CELL, CONFIG, TRAFFIC = ("nemotron3n-d9e8-train-ppo-long", "nemotron-3-nano-d9-e8",
                         "ppo-packed-long-2b")
NEW_METRICS = ["train_ssm_live_chunks_pct", "train_mfu_hybrid_pct"]
REDUCED = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384}

# The language model's settings as the catalog beside the model-configs
# guide read them from nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's config.json.
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2, head_dim=128,
    hidden_size=2688,
    hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    intermediate_size=1856, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_routed_experts=128, n_shared_experts=1, norm_eps=1e-05, norm_topk_prob=True,
    num_attention_heads=32, num_experts_per_tok=6, num_hidden_layers=52,
    num_key_value_heads=2, num_logits_to_keep=1, partial_rotary_factor=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, rope_theta=10000,
    routed_scaling_factor=2.5, sliding_window=None, ssm_state_size=128,
    tie_word_embeddings=False, time_step_floor=0.0001, time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072,
)


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _pool_lengths(name=TRAFFIC):
    pool = traffic.ppo_batch_lengths(traffic.effective(_load("traffic", name), False))
    return [[s["prompt_len"] + s["resp_len"] for s in b] for b in pool]


def test_config_keeps_every_published_key_but_the_four_reduced():
    cfg, entry = _load("configs", CONFIG), next(
        c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["benchmark"]["reduced"]) == sorted(REDUCED)
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    # beside the published keys: this repository's two, and the block
    assert set(cfg) - set(PUBLISHED) == {"num_experts_routed", "experts_held_first", "benchmark"}
    # the depth cut takes the first letters of the published pattern
    assert PUBLISHED["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"])
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == [4, 4, 1]
    # the router keeps its published width, top-k and scale
    assert (cfg["num_experts_routed"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"]) == (128, 6, 2.5)
    b = cfg["benchmark"]
    assert b["published"]["n_routed_experts"] == 128 and b["published"]["vocab_size"] == 131072
    assert b["held_here"] == dict(REDUCED, num_experts_routed=128, experts_held_first=0)
    assert "16 chips" in b["deployment"] and "nothing stands in" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "nemotron_h" and b["dtype"] == "bfloat16"
    # no width among the keys reduced; the toy widths only under rehearsal_overrides
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank"))
                and k != "vocab_size"]
    # the floors of a model_config cut: every kind of layer and four of
    # each that repeats, at least 8 experts, at least an eighth of the vocabulary
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_config_goes_through_the_family_as_the_share_it_states():
    import jax

    from areal_tpu.models.hf import family_from_hf_config
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    # the catalog's config, unchanged, is the whole model
    whole = family_from_hf_config(PUBLISHED).config_from_hf(dict(PUBLISHED))
    assert [sum(k.parts == p for k in whole.kinds()) for p in ("ssm", "moe", "attention")] == [
        23, 23, 6]
    assert whole.moe.experts_held is None and whole.moe.num_experts == 128
    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert "".join({"ssm": "M", "moe": "E", "attention": "*"}[k.parts]
                   for k in cfg.kinds()) == "MEMEM*EME"
    assert all(not k.rotary for k in cfg.kinds() if k.mixer == "attention")
    moe, ssm = cfg.moe, cfg.ssm
    assert (moe.num_experts, moe.top_k, moe.experts_held, moe.score_func) == (
        128, 6, (0, 8), "sigmoid")
    assert (moe.expert_intermediate_dim, moe.shared_intermediate_dim,
            moe.routed_scaling_factor, moe.aux_loss_coef) == (1856, 3712, 2.5, 0.0)
    assert (ssm.n_heads, ssm.head_dim, ssm.n_groups, ssm.state_dim, ssm.conv_kernel,
            ssm.chunk_size) == (64, 64, 8, 128, 4, 128)
    assert (ssm.d_inner, ssm.conv_dim, ssm.in_proj_dim) == (4096, 6144, 10304)
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (2688, 32, 2, 128)
    assert (cfg.mlp_type, cfg.activation, cfg.norm_eps) == ("plain", "relu2", 1e-5)
    # the program's own parameter count: 667.0 M
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 667.0) < 0.1
    assert {k: round(count(v) / jax.tree_util.tree_leaves(v)[0].shape[0] / 1e6, 2)
            for k, v in shapes["stacks"].items()} == {
        "ssm": 38.74, "moe": 100.13, "attention": 23.40}
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert toy.moe.experts_held == (0, 4) and toy.moe.num_experts == 16 and toy.ssm.chunk_size == 16


def test_the_traffic_is_the_long_pools_first_two_batches():
    new, old = _load("traffic", TRAFFIC), _load("traffic", "ppo-packed-long")
    assert {k for k in set(new) | set(old) if new.get(k) != old.get(k)} == {
        "pool_batches", "why", "pool_notes"}
    assert (new["pool_batches"], old["pool_batches"]) == (2, 4)
    lens, long = _pool_lengths(), _pool_lengths("ppo-packed-long")
    assert lens == long[:2]
    assert [sum(b) for b in lens] == [68569, 69408] and sum(map(len, lens)) == 24
    assert min(map(min, lens)) == 1055 and max(map(max, lens)) == 14920
    # the two pools are told apart by their squared lengths a token
    # (`flops_rate_moe.window_pool_lengths`)
    per_token = lambda p: sum(l * l for b in p for l in b) / sum(map(sum, p))
    assert abs(per_token(lens) / per_token(long) - 1) > 1e-3


def test_the_cell_runs_the_engine_block_of_the_accepted_long_cell():
    cell, trinity = _load("cells", CELL), _load("cells", "trinity-d5e16-train-ppo-long")
    for key in ("engine", "optimizer", "rehearsal", "chips"):
        assert cell[key] == trinity[key], key
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    entry = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")} == entry
    assert len(cell["why"]) <= 200 and "16x" in cell["why"] and "1/16" in cell["why"]
    tol = cell["logprob_tolerance"]
    assert set(tol) == {"max", "mean"} and 0 < tol["mean"] < tol["max"]
    assert len(cell["logprob_tolerance_notes"]) > 400 and len(cell["engine_notes"]) > 200


def test_every_micro_batch_is_one_row_of_16384():
    """The forward over a batch and every minibatch's micro-batches pack
    to the one shape (1, 16384): a run compiles one forward program and
    two forward-backward ones (first, next), and a pass is 16 rows."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.models.packing import pack_sequences

    cell = manifest.load_cell(CELL)
    p = traffic.effective(cell["traffic_file"], False)
    budget = MicroBatchSpec(max_tokens_per_mb=p["ppo"]["max_tokens_per_mb"])

    def shape(mb):
        lens = [l for sl in mb.seqlens["packed_input_ids"] for l in sl]
        b = pack_sequences([np.zeros(l, np.int32) for l in lens],
                           row_len_multiple=cell["engine"]["row_len_multiple"],
                           max_row_len=cell["engine"]["max_row_len"])
        return b.n_rows, b.row_len

    shapes, kinds = set(), []
    for lens in _pool_lengths():
        batch = SequenceSample.from_default(
            ids=[str(i) for i in range(len(lens))], seqlens=lens,
            data=dict(packed_input_ids=np.zeros(sum(lens), np.int32)), metadata={})
        shapes |= {shape(mb) for mb in batch.split(budget)[0]}  # engine.forward
        for mini in batch.split(MicroBatchSpec(n_mbs=p["ppo"]["n_minibatches"]))[0]:
            mbs = mini.split(budget)[0]
            shapes |= {shape(mb) for mb in mbs}
            kinds.append(len(mbs))
    assert shapes == {(1, 16384)}
    assert kinds == [2] * 8  # 16 rows a pass; no third forward-backward program


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_metrics_are_listed_only_where_their_files_are_read(metric):
    m = next(e for e in MAN["per_layer"] if e["name"] == metric)
    d = _load("layer_metrics", metric)
    assert {k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == {
        k: v for k, v in m.items() if k != "workloads"}
    matched = [c for c in CELLS if any(fnmatch.fnmatchcase(c, g) for g in d["cells"])]
    assert m["workloads"] == matched == [CELL]
    assert m["moves"] == "train_tokens_per_s" and m["unit"] == "%" and m["better"] == "higher"
    # they close the list; what the benchmark had comes before them
    assert [e["name"] for e in MAN["per_layer"]][-2:] == NEW_METRICS


def test_the_cell_is_listed_wherever_a_train_metric_is_read_but_the_dense_mfu():
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        globs = _load("layer_metrics", name)["cells"]
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in globs)
        assert (CELL in listed[name]) == (read_here and name != "train_mfu_pct"), name
        if CELL in listed[name]:
            assert listed[name][-1] == CELL  # appended, nothing moved
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert e2e["train_tokens_per_s"]["workloads"][-1] == CELL
    assert (MAN["configs"][-1]["name"], MAN["workloads"][-1]["name"]) == (CONFIG, CELL)
    # the expert-share and attention-skip metrics of `trinity-*` do not reach it
    for name in ("train_moe_rows_ratio_pct", "train_moe_held_pairs_pct",
                 "train_attn_active_cells_pct", "train_mfu_moe_pct"):
        assert CELL not in listed[name]


def test_flops_count_the_stack_by_part():
    """At a hand-counted size: d 2688, 4 M + 4 E + 1 *."""
    hf = manifest.hf_config(_load("configs", CONFIG), False)
    m = flops_hybrid.matmul_params(hf)
    d = 2688
    assert m["ssm_proj"] == 4 * (d * (4096 + 6144 + 64) + 4096 * d)
    # a token: causal within its chunk of 128 (64.5 cells on average), C B^T
    # over 8 groups' states of 128 and the sum over 64 heads of 64; between
    # chunks the state built and read, 64 x 64 x 128 each
    assert m["ssm_scan"] == 4 * (64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128)
    assert m["attn_proj"] == d * (4096 + 256 + 256) + 4096 * d and m["attn_layers"] == 1
    assert m["router"] == 4 * d * 128 and m["shared"] == 4 * 2 * d * 3712
    assert m["pair"] == 2 * d * 1856 and m["head"] == d * 16384 and m["dense_mlp"] == 0
    lens = [6144, 512]
    tokens = sum(lens)
    f = flops_hybrid.train_flops(hf, lens, pairs_held=4 * 6 * tokens * 8 / 128)
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    assert f["attention"] == 12 * 4096 * sum(l * (l + 1) / 2 for l in lens)
    assert f["ssm_proj"] == 6 * m["ssm_proj"] * tokens
    assert f["experts"] == 6 * m["pair"] * 4 * 6 * tokens / 16
    # a `-` layer counts its two matrices
    dense = flops_hybrid.matmul_params(dict(hf, hybrid_override_pattern="M-*E"))
    assert dense["dense_mlp"] == 2 * d * 1856 and dense["ssm_proj"] == m["ssm_proj"] / 4
    # about 2.0 GFLOP a token on the cell's traffic, nearly half of it the
    # state-space layers' projections, the scan's products a fiftieth
    lens = [l for b in _pool_lengths() for l in b]
    f = flops_hybrid.train_flops(hf, lens, pairs_held=4 * 6 * sum(lens) / 16)
    assert 1.9e9 < f["total"] / sum(lens) < 2.2e9
    assert 0.40 < f["ssm_proj"] / f["total"] < 0.50 and f["ssm_scan"] / f["total"] < 0.03


def test_the_readers_read_the_programs_counters_or_nothing():
    hf = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    tokens, sq = float(sum(lens)), float(sum(l * l for l in lens))
    pairs = 4 * 6 * tokens / 16
    # a window of four passes over the pool, as the runner's evidence says it
    ev = dict(work=dict(tokens=4 * tokens, elapsed_s=48.0, sum_len_sq=4 * sq),
              peaks={"bf16_flops_per_s": 197e12}, chips=1, hf_config=hf,
              program={"counters": {"train.tokens": tokens, "train.moe_pairs_held": pairs}})
    got = flops_rate_hybrid.read(ev)
    want = 100 * 4 * flops_hybrid.train_flops(hf, lens, pairs)["total"] / 48.0 / 197e12
    assert got == pytest.approx(want) and 0 < got < 100
    # a window over a pool no traffic file makes: nothing, not another pool's number
    assert flops_rate_hybrid.read(dict(ev, work=dict(ev["work"], sum_len_sq=3 * sq))) is None
    # a program without the counters (the parent's), another family's
    # configuration, no window, no peak: nothing, and no error
    assert flops_rate_hybrid.read(dict(ev, program={"counters": {"train.tokens": 5}})) is None
    assert flops_rate_hybrid.read(dict(ev, program={"counters": {}})) is None
    assert flops_rate_hybrid.read(dict(ev, program=None)) is None
    assert flops_rate_hybrid.read(dict(ev, hf_config={"num_hidden_layers": 2})) is None
    assert flops_rate_hybrid.read(dict(ev, work=None)) is None
    assert flops_rate_hybrid.read(dict(ev, peaks=None)) is None
    d = _load("layer_metrics", "train_ssm_live_chunks_pct")
    assert d["reader"] == "program_counter_ratio" and d["args"]["num"] == "train.ssm_chunks_live"
    assert program_counter_ratio.read({"program": {"counters": {}}}, **d["args"]) is None
    both = {"program": {"counters": {"train.ssm_chunks_live": 3.0, "train.ssm_chunks": 4.0}}}
    assert program_counter_ratio.read(both, **d["args"]) == 75.0


def test_the_live_chunks_of_the_pool_are_counted_by_the_devices_rule():
    """About 53 % of the scan's chunks hold a token at one row of 16,384
    a micro-batch: what a later PR may skip."""
    from areal_tpu.models.packing import pack_sequences
    from areal_tpu.ops.ssm import chunk_counts

    lens = _pool_lengths()[0]
    rows = pack_sequences([np.zeros(l, np.int32) for l in lens[:2]],
                          row_len_multiple=16384, max_row_len=None)
    seg = rows.segment_ids
    assert seg.shape == (1, 16384)
    chunks, live, mixed, resets = chunk_counts(seg, 128)
    assert chunks == 128 and resets == 2
    assert live == -(-sum(lens[:2]) // 128) and mixed == (lens[0] % 128 != 0)


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    assert {"setup_s", "train_tokens_per_s", "train_ssm_live_chunks_pct",
            "train_pack_density_pct", "train_attn_row_ratio_pct",
            "train_head_cells_pct"} <= set(line["would_report"])
    assert not {"train_moe_held_pairs_pct", "train_attn_active_cells_pct"} & set(
        line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    assert max(s["positions"] for s in ref["samples"]) > 16  # several toy chunks
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert c["train.ssm_chunks"] * 16 == 4 * c["train.cells"]  # 4 layers, chunks of 16
    assert 0 < c["train.ssm_chunks_mixed"] <= c["train.ssm_chunks_live"] < c["train.ssm_chunks"]
    assert c["train.ssm_resets"] > 0
    assert c["train.moe_pairs"] == 4 * 4 * c["train.tokens"]  # k x tokens x expert layers
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["window"] is None for s in dispatch)
    assert dispatch[0]["attrs"]["kinds"] == "ssm,moe,ssm,moe,ssm,attn.full.nope,moe,ssm,moe"
