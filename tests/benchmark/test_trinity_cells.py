"""What PR 28 added to the benchmark: the configuration `trinity-mini-d5-e16`
against the catalog's published keys, its two traffic mixes and cells,
`flops_moe.py` and its reader, the new layer metrics, and the CPU
walk-through of both new cells."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_moe, manifest, traffic
from benchmark.readers import flops_rate_moe, program_counter_ratio
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
TRINITY, SHORT = "trinity-d5e16-train-ppo-long", "q15d12-train-short"
NEW_METRICS = ["train_moe_rows_ratio_pct", "train_moe_held_pairs_pct",
               "train_attn_active_cells_pct", "train_mfu_moe_pct"]
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
           "vocab_size": 25024}

# The language model's settings as the catalog beside the model-configs
# guide read them from arcee-ai/Trinity-Mini's config.json.
PUBLISHED = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act="silu", hidden_size=2048,
    intermediate_size=6144, load_balance_coeff=0.001, max_position_embeddings=131072,
    model_type="afmoe", moe_intermediate_size=1024, mup_enabled=True, n_group=1,
    num_attention_heads=32, num_dense_layers=2, num_expert_groups=1, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=32, num_key_value_heads=4,
    num_limited_groups=1, num_shared_experts=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, route_norm=True, route_scale=2.826, score_func="sigmoid",
    sliding_window=2048, tie_word_embeddings=False, topk_group=1, use_grouped_mm=True,
    vocab_size=200192,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 8,
)


def config_file():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "trinity-mini-d5-e16.json")) as f:
        return json.load(f)


def test_config_keeps_every_published_key_but_the_four_reduced():
    cfg, entry = config_file(), next(
        c for c in MAN["configs"] if c["name"] == "trinity-mini-d5-e16")
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["benchmark"]["reduced"]) == sorted(REDUCED)
    differs = {k for k in PUBLISHED if k != "layer_types" and PUBLISHED[k] != cfg.get(k, "absent")}
    assert differs == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    # the depth cut takes the first entries of the published pattern
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:5]
    # the router keeps its published width, top-k and scale
    assert (cfg["num_experts_routed"], cfg["num_experts_per_tok"], cfg["route_scale"]) == (
        128, 8, 2.826)
    b = cfg["benchmark"]
    assert b["published"]["num_experts"] == 128 and b["published"]["vocab_size"] == 200192
    assert b["held_here"]["num_experts"] == 16 and "8 chips" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "afmoe" and b["dtype"] == "bfloat16"
    # the floors of a model_config cut: a whole period and four expert
    # layers, at least 8 experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    assert "full_attention" in cfg["layer_types"][cfg["num_dense_layers"]:]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_config_goes_through_the_family_as_the_share_it_states():
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(config_file(), False), "bfloat16")
    assert [(k.mlp, k.window, k.rotary) for k in cfg.kinds()] == [
        ("dense", 2048, True), ("moe", 2048, True), ("moe", 2048, True),
        ("moe", None, False), ("moe", 2048, True)]
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.experts_held, moe.score_func) == (
        128, 8, (0, 16), "sigmoid")
    assert moe.aux_loss_coef == 0.0 and moe.n_shared_experts == 1
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert cfg.attn_gate and cfg.post_norms and cfg.qk_norm and not cfg.tied_embeddings
    toy = model.transformer_config(manifest.hf_config(config_file(), True), "float32")
    assert toy.moe.experts_held == (0, 4) and toy.moe.num_experts == 16


@pytest.mark.parametrize("name,base,differs", [
    ("ppo-packed-short", "ppo-packed",
     {"why", "lengths_seed", "response_len_lognormal", "response_len_clip", "pool_notes"}),
    ("ppo-packed-long", "ppo-packed",
     {"why", "lengths_seed", "tokens_per_step", "group_size", "prompt_len_uniform",
      "response_len_lognormal", "response_len_clip", "ppo", "check", "pool_notes"}),
])
def test_new_traffic_is_the_old_file_but_for_what_it_names(name, base, differs):
    load = lambda n: json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", f"{n}.json")))
    new, old = load(name), load(base)
    assert {k for k in set(new) | set(old) if new.get(k) != old.get(k)} == differs
    seeds = [load(n)["lengths_seed"] for n in manifest.list_names("traffic")]
    assert len(seeds) == len(set(seeds))


def test_long_traffic_is_sequences_two_to_eight_windows_long():
    p = traffic.effective(json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "ppo-packed-long.json"))), False)
    assert (p["tokens_per_step"], p["group_size"], p["pool_batches"]) == (65536, 8, 4)
    assert p["ppo"] == {"n_minibatches": 4, "max_tokens_per_mb": 16384}
    assert p["check"] == {"sequences": 3, "max_positions": 6144}
    pool = traffic.ppo_batch_lengths(p)
    lens = np.array([s["prompt_len"] + s["resp_len"] for b in pool for s in b])
    assert lens.max() <= 16384 and 4500 <= lens.mean() <= 6000
    in_long = lens[lens >= 2 * 2048].sum() / lens.sum()
    assert in_long > 0.7  # most tokens sit in sequences of two windows or more
    short = traffic.ppo_batch_lengths(traffic.effective(json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "ppo-packed-short.json"))), False))
    lens = np.array([s["prompt_len"] + s["resp_len"] for b in short for s in b])
    assert 192 <= lens.min() and lens.max() <= 1536


def test_new_cells_run_the_launchers_settings_as_the_accepted_cell_does():
    """But for the one setting the trinity cell departs in, and says so:
    rows as long as the traffic's micro-batch budget, so that every
    micro-batch is one shape (at 128 the pool is 46 shapes and a run's
    set-up 19-21 minutes, which the benchmark's check cannot take)."""
    base = manifest.load_cell("q15d12-train-ppo")
    for name in (TRINITY, SHORT):
        cell = manifest.load_cell(name)
        differs = {k for k in base["engine"] if cell["engine"][k] != base["engine"][k]}
        assert differs == ({"row_len_multiple"} if name == TRINITY else set())
        assert set(cell["engine"]) == set(base["engine"]) and cell["optimizer"] == base["optimizer"]
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
    long = manifest.load_cell(TRINITY)
    budget = long["traffic_file"]["ppo"]["max_tokens_per_mb"]
    assert long["engine"]["row_len_multiple"] == budget and "departure" in long["engine_notes"]
    short = manifest.load_cell(SHORT)
    assert {k for k in base if k not in ("config_file", "traffic_file")
            and base[k] != short[k]} == {"name", "traffic", "why"}
    tol = manifest.load_cell(TRINITY)["logprob_tolerance"]
    assert 0 < tol["mean"] < 0.03 and 1.0 < tol["max"] <= 2.0


def test_every_micro_batch_of_the_long_pool_is_one_row_of_the_budget():
    """What the cell's `row_len_multiple` buys: the forward over a batch
    and every minibatch's micro-batches pack to the one shape (1, 16384),
    whatever the sequences in them, so a run compiles one forward program
    and two forward-backward ones (first, next) instead of one a
    micro-batch."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.models.packing import pack_sequences

    cell = manifest.load_cell(TRINITY)
    p = traffic.effective(cell["traffic_file"], False)
    budget = MicroBatchSpec(max_tokens_per_mb=p["ppo"]["max_tokens_per_mb"])

    def shape(mb):
        lens = [l for sl in mb.seqlens["packed_input_ids"] for l in sl]
        b = pack_sequences([np.zeros(l, np.int32) for l in lens],
                           row_len_multiple=cell["engine"]["row_len_multiple"],
                           max_row_len=cell["engine"]["max_row_len"])
        return b.n_rows, b.row_len

    shapes, kinds = set(), []
    for seqs in traffic.ppo_batch_lengths(p):
        lens = [s["prompt_len"] + s["resp_len"] for s in seqs]
        batch = SequenceSample.from_default(
            ids=[str(i) for i in range(len(lens))], seqlens=lens,
            data=dict(packed_input_ids=np.zeros(sum(lens), np.int32)), metadata={})
        shapes |= {shape(mb) for mb in batch.split(budget)[0]}  # engine.forward
        for mini in batch.split(MicroBatchSpec(n_mbs=p["ppo"]["n_minibatches"]))[0]:
            mbs = mini.split(budget)[0]
            shapes |= {shape(mb) for mb in mbs}
            kinds.append(len(mbs))
    assert shapes == {(1, 16384)}
    # and every minibatch is two micro-batches (`lengths_seed`'s notes):
    # no third forward-backward program for a minibatch of one
    assert kinds == [2] * 16


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_a_metric_is_listed_only_where_its_file_is_read(metric):
    """For a benchmark of any number of cells: the entry is its file's,
    and every cell it lists is one the file's globs match (the harness
    runs a metric's reader by those globs), in the manifest's order."""
    m = next(e for e in MAN["per_layer"] if e["name"] == metric)
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{metric}.json")) as f:
        d = json.load(f)
    assert {k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == {
        k: v for k, v in m.items() if k != "workloads"}
    matched = [c for c in CELLS if any(fnmatch.fnmatchcase(c, g) for g in d["cells"])]
    assert m["workloads"] and set(m["workloads"]) <= set(matched)
    assert m["workloads"] == sorted(m["workloads"], key=CELLS.index)


def test_the_dense_mfu_is_not_listed_for_the_expert_model():
    """`benchmark/flops.py` counts a dense block under a full causal mask:
    the harness still prints `train_mfu_pct` in every `*-train-*` cell,
    the manifest does not list it where that arithmetic is wrong
    (`train_mfu_moe_pct` is listed there instead)."""
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    moe_cells = [w["name"] for w in MAN["workloads"]
                 if "num_experts" in manifest.load_cell(w["name"])["config_file"]]
    assert moe_cells == [TRINITY]
    for cell in moe_cells:
        assert cell not in listed["train_mfu_pct"] and cell in listed["train_mfu_moe_pct"]


def test_flops_count_the_share_by_part():
    hf = manifest.hf_config(config_file(), False)
    m = flops_moe.matmul_params(hf)
    d = 2048
    assert m["attn_proj"] == 5 * (d * (4096 + 512 + 512 + 4096) + 4096 * d)
    assert m["dense_mlp"] == 3 * d * 6144 and m["shared"] == 4 * 3 * d * 1024
    assert m["router"] == 4 * d * 128 and m["head"] == d * 25024
    assert m["pair"] == 3 * d * 1024
    # attention: causal up to the window, then `window` keys a query
    assert flops_moe.attention_cells(100) == 5050
    assert flops_moe.attention_cells(100, 2048) == 5050
    assert flops_moe.attention_cells(4096, 2048) == 2048 * 2049 / 2 + 2048 * 2048
    lens = [6144, 512]
    f = flops_moe.train_flops(hf, lens, pairs_held=4 * sum(lens))
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    cells = 4 * sum(flops_moe.attention_cells(l, 2048) for l in lens) + sum(
        flops_moe.attention_cells(l) for l in lens)
    assert f["attention"] == 12 * 4096 * cells
    assert f["experts"] == 6 * m["pair"] * 4 * sum(lens)
    # under even routing a token's held pairs cost what its shared expert does
    assert f["experts"] == pytest.approx(f["shared"])
    # about 2.2 GFLOP a token on the cell's traffic, a quarter of it attention
    p = traffic.effective(json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "ppo-packed-long.json"))), False)
    lens = [s["prompt_len"] + s["resp_len"] for b in traffic.ppo_batch_lengths(p) for s in b]
    f = flops_moe.train_flops(hf, lens, pairs_held=4 * sum(lens))
    assert 2.0e9 < f["total"] / sum(lens) < 2.4e9
    assert 0.18 < f["attention"] / f["total"] < 0.30


def test_mfu_reader_reads_the_programs_counter_or_nothing():
    hf = manifest.hf_config(config_file(), False)
    p = traffic.effective(json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "ppo-packed-long.json"))), False)
    lens = [s["prompt_len"] + s["resp_len"] for b in traffic.ppo_batch_lengths(p) for s in b]
    tokens, sq = float(sum(lens)), float(sum(l * l for l in lens))
    # a window of six passes over the pool, as the runner's evidence says it
    ev = dict(work=dict(tokens=6 * tokens, elapsed_s=45.0, sum_len_sq=6 * sq),
              peaks={"bf16_flops_per_s": 197e12}, chips=1, hf_config=hf,
              program={"counters": {"train.tokens": tokens,
                                    "train.moe_pairs_held": 4 * tokens}})
    assert flops_rate_moe.window_pool_lengths(ev["work"]) == lens
    got = flops_rate_moe.read(ev)
    want = 100 * 6 * flops_moe.train_flops(hf, lens, 4 * tokens)["total"] / 45.0 / 197e12
    assert got == pytest.approx(want) and 0 < got < 100
    # a window over a pool no traffic file makes: nothing, not another pool's number
    assert flops_rate_moe.read(dict(ev, work=dict(ev["work"], sum_len_sq=5 * sq))) is None
    # a program without the counter (the parent's), no window, no peak: nothing
    assert flops_rate_moe.read(dict(ev, program={"counters": {"train.tokens": 5}})) is None
    assert flops_rate_moe.read(dict(ev, program=None)) is None
    assert flops_rate_moe.read(dict(ev, work=None)) is None
    assert flops_rate_moe.read(dict(ev, peaks=None)) is None
    for name in NEW_METRICS[:3]:
        d = json.load(open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{name}.json")))
        assert program_counter_ratio.read({"program": {"counters": {}}}, **d["args"]) is None
        both = {"program": {"counters": {d["args"]["num"]: 3.0, d["args"]["den"]: 4.0}}}
        assert program_counter_ratio.read(both, **d["args"]) == 75.0


def test_trinity_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(TRINITY, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    assert {"setup_s", "train_tokens_per_s", "train_moe_rows_ratio_pct",
            "train_moe_held_pairs_pct", "train_attn_active_cells_pct",
            "train_pack_density_pct", "train_attn_row_ratio_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and afmoe's plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    assert max(s["positions"] for s in ref["samples"]) > 16  # past the toy window
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert c["train.moe_pairs"] == 4 * 4 * c["train.tokens"]  # k x tokens x expert layers
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.moe_rows"] >= c["train.moe_pairs_held"]
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0  # einsum on the CPU
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["window"] == 16 for s in dispatch)
    assert dispatch[0]["attrs"]["kinds"] == (
        "dense.w16.rope,moe.w16.rope x2,moe.full.nope,moe.w16.rope")


def test_short_cell_rehearsal_prints_the_contract_line(tmp_path):
    r = rehearse(SHORT, tmp_path, 0)
    line = last_line(r)
    check_contract_line(line)
    assert "cell q15d12-train-short: config qwen2.5-1.5b-d12, traffic ppo-packed-short" in r.stdout
    assert {"setup_s", "train_tokens_per_s"} <= set(line["would_report"])
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
