"""The cell PR 42 adds (`keye-d6e16-train-ppo-long`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np

from benchmark import flops_dsa, manifest, traffic
from benchmark.readers import flops_rate_dsa, program_counter_ratio, trace_op_roofline_dsa
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = ("keye-d6e16-train-ppo-long", "keye-vl-2.0-d6-e16",
                         "ppo-packed-long-2b")
REDUCED = {"num_hidden_layers": 6, "num_experts": 16, "num_local_experts": 16,
           "vocab_size": 18992}
OURS = {"num_experts_routed": 128, "experts_held_first": 0, "indexer_loss_weight": 1.0}
ROOFLINES = ("train_index_select_roofline_pct", "train_index_kl_fwd_roofline_pct",
             "train_index_kl_bwd_roofline_pct")
NEW_METRICS = ("train_mfu_dsa_pct", "train_index_selected_pct") + ROOFLINES

# The language model's settings as the catalog beside the model-configs
# guide read them from Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json.
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128, hidden_act="silu",
    hidden_size=2048, intermediate_size=6144, max_position_embeddings=262144,
    max_window_layers=48, mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
    norm_topk_prob=True, num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=4, num_local_experts=128, rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
               "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936)


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _entry(section, name):
    return next(e for e in MAN[section] if e["name"] == name)


def _pool_lengths():
    pool = traffic.ppo_batch_lengths(traffic.effective(_load("traffic", TRAFFIC), False))
    return [[s["prompt_len"] + s["resp_len"] for s in b] for b in pool]


def test_config_keeps_every_published_key_but_the_reduced():
    cfg, entry = _load("configs", CONFIG), _entry("configs", CONFIG)
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    # num_local_experts goes with num_experts: one line of the file's `reduced` says both
    assert sorted(cfg["benchmark"]["reduced"]) == sorted(set(REDUCED) - {"num_local_experts"})
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["sa_config"] == PUBLISHED["sa_config"]  # the nested group whole
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert b["held_here"] == {**REDUCED, **{k: OURS[k] for k in ("num_experts_routed",
                                                                 "experts_held_first")}}
    assert "one of 8 chips" in b["deployment"] and "eight times their share" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "keye_vl2" and b["dtype"] == "bfloat16"
    assert any("stop_gradient" in a and "two disjoint sets" in a for a in b["assumed"])
    assert any("Seeded weights" in a and "q norm" in a for a in b["assumed"])
    assert any("Text only" in a and "mrope" in a for a in b["assumed"])
    assert any("Ties at the threshold" in a for a in b["assumed"])
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] >= 4
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "head_dim", "sa_config"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["indexedattention+moe"] * 6
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (
        2048, 32, 4, 128, 18992)
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k, cfg.moe.score_func) == (
        128, (0, 16), 8, "softmax")
    ix = cfg.indexer
    assert (ix.n_heads, ix.head_dim, ix.top_k, ix.loss_weight) == (16, 64, 2048, 1.0)
    # the program's own parameter count: the issue's 659 M, 9.2 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 659.3) < 0.2 and abs(count(shapes) * 14 / 1e9 - 9.23) < 0.01
    layer = shapes["layers"]
    assert round(count(layer["attn"]) / 6e6, 2) == 21.14  # attention 18.87 + indexer 2.26
    assert round(count(layer["attn"]["indexer"]) / 6e6, 2) == 2.26
    assert round(count(layer["mlp"]) / 6e6, 2) == 75.76  # 16 experts + the router
    assert [seg.repeats for seg in cfg.segments()] == [6]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.indexer.n_heads, toy.indexer.head_dim, toy.indexer.top_k) == (2, 8, 16)


def test_every_micro_batch_is_one_row_of_16384_and_most_queries_choose():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 16384 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the cells that share the traffic file, and their optimizer
    for other in manifest.list_names("cells"):
        if other != CELL and _load("cells", other)["traffic"] == TRAFFIC:
            assert cell["engine"] == _load("cells", other)["engine"]
    assert cell["optimizer"] == {"lr": 0.0001}
    lens = _pool_lengths()
    assert [sum(b) for b in lens] == [68569, 69408] and sum(map(len, lens)) == 24
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=16384)
    shapes, per_mini = set(), []
    for i, batch_lens in enumerate(lens):
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(batch_lens))], seqlens=batch_lens,
            data={"packed_input_ids": np.zeros(sum(batch_lens), np.int32)})
        shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple)
                   for mb in batch.split(budget)[0]}
        for mini in batch.split(MicroBatchSpec(n_mbs=4))[0]:
            mbs = mini.split(budget)[0]
            per_mini.append(len(mbs))
            shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in mbs}
    assert shapes == {(1, 16384)}
    assert per_mini == [2] * 8  # 16 rows a pass
    # the issue's count of what the indexer does here, from the lengths alone
    flat = [l for b in lens for l in b]
    tokens = sum(flat)
    assert round(100.0 * sum(l for l in flat if l > 2048) / tokens, 1) == 95.5
    assert round(100.0 * sum(max(l - 2048, 0) for l in flat) / tokens) == 66
    scored, kept = flops_dsa.pool_cells(flat, 2048)
    assert round(scored / 1e6) == 558 and round(kept / 1e6) == 233
    assert 41 < 100.0 * kept / scored < 43


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in _load("layer_metrics", name)["cells"])
        # a dense block's arithmetic; a roofline whose kernel the traced
        # pass's ten heaviest ops do not hold is unlisted on purpose
        unlisted = name == "train_mfu_pct" or (name in ROOFLINES and name not in listed)
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in NEW_METRICS:
        f = _load("layer_metrics", name)
        assert f["cells"] == ["keye-*"] and f["moves"] == "train_tokens_per_s" and f["unit"] == "%"
        if name not in listed:
            assert name in ROOFLINES
            continue
        m = _entry("per_layer", name)
        assert listed[name] == [CELL]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert {"train_mfu_dsa_pct", "train_index_selected_pct"} <= set(listed)
    assert _load("layer_metrics", "train_index_selected_pct")["reader"] == "program_counter_ratio"
    for name in ROOFLINES:
        f = _load("layer_metrics", name)
        assert f["reader"] == "trace_op_roofline_dsa" and f["source"] == "device_trace"
        assert callable(getattr(flops_dsa, f["args"]["work"]))
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


HF_TOY = dict(model_type="KeyeVL2", num_hidden_layers=3, hidden_size=8, num_attention_heads=4,
              num_key_value_heads=2, head_dim=3, moe_intermediate_size=5, num_experts=2,
              num_experts_routed=6, vocab_size=10,
              sa_config=dict(indexer_num_heads=2, indexer_head_dim=5, topk=2))


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    s = flops_dsa.sizes(HF_TOY)
    assert s["attn_proj_token"] == 8 * 12 * 2 + 8 * 6 * 2 == 288
    assert s["index_proj_token"] == 8 * (10 + 5 + 2) == 136
    assert (s["index_cell"], s["attn_cell"], s["router_token"], s["pair"], s["head"]) == (
        10, 12, 48, 120, 80)
    # sequences of 3 and 1 at topk 2: 6 + 1 cells scored a layer, 1 + 2 + 2 + 1 kept
    assert flops_dsa.pool_cells([3, 1], 2) == (7.0, 6.0)
    scored, chosen = 3 * 7.0, 3 * 6.0
    out = flops_dsa.train_flops(HF_TOY, 4, scored, chosen, pairs_held=5, head_cells=4)
    assert out["attn_proj"] == 6.0 * 288 * 3 * 4
    assert out["index_proj"] == 4.0 * 136 * 3 * 4  # forward + the weights' gradient
    assert out["index_scores"] == 2.0 * 10 * (scored + 2 * chosen)
    assert out["attention"] == 6.0 * 2 * 12 * chosen
    assert out["index_kl"] == 2.0 * 12 * chosen
    assert (out["router"], out["experts"], out["head"]) == (
        6.0 * 48 * 3 * 4, 6.0 * 120 * 5, 6.0 * 80 * 4)
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    off = flops_dsa.train_flops(dict(HF_TOY, indexer_loss_weight=0.0), 4, scored, chosen, 5, 4)
    assert off["index_kl"] == 0 and off["index_proj"] == 2.0 * 136 * 3 * 4
    assert off["index_scores"] == 2.0 * 10 * scored
    # the kernels' work
    sel = flops_dsa.index_select_work(HF_TOY, 4, scored, chosen, calls=2)
    assert sel["flops"] == 2 * 2.0 * 10 * scored
    assert sel["bytes"] == 2 * (3 * 4 * (2 * 15 + 4 * 2 + 12) + scored)
    fwd = flops_dsa.index_kl_fwd_work(HF_TOY, 4, scored, chosen)
    assert fwd["flops"] == 2.0 * 22 * chosen
    bwd = flops_dsa.index_kl_bwd_work(HF_TOY, 4, scored, chosen)
    assert bwd["flops"] == fwd["flops"] + 2.0 * 2 * 10 * chosen and bwd["bytes"] > fwd["bytes"]
    # the cell's own: multiply-adds a cell, the issue's 1,024 against 8,192 over chosen cells
    big = flops_dsa.sizes(manifest.hf_config(_load("configs", CONFIG), False))
    assert big["index_cell"] == 1024 and 2 * big["attn_cell"] == 8192
    assert round(big["attn_proj_token"] / 1e6, 2) == 18.87
    assert round(big["index_proj_token"] / 1e6, 2) == 2.26


def _evidence():
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    scored, kept = flops_dsa.pool_cells(lens, 2048)
    work = dict(tokens=2.0 * n, sum_len_sq=2.0 * sum(l * l for l in lens), elapsed_s=30.0)
    counters = {"train.tokens": n, "train.cells": 262144, "train.moe_pairs_held": 3.1 * n,
                "train.head_cells": 196608, "train.index_cells": 6 * scored,
                "train.index_selected": 6 * kept, "train.index_queries_choosing": 6 * 91000}
    ops = [["fusion", 5.0], ["index_kl_bwd", 0.9], ["index_select", 0.8], ["index_kl_fwd", 0.4]]
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=ops)), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    want = 100.0 * flops_dsa.train_flops(
        cfg, 2 * n, 2 * c["train.index_cells"], 2 * c["train.index_selected"],
        2 * 3.1 * n, 2 * 196608)["total"] / 30.0 / 197e12
    assert abs(flops_rate_dsa.read(ev) - want) < 1e-9 and 5 < want < 60
    args = _load("layer_metrics", "train_index_selected_pct")["args"]
    assert 41 < program_counter_ratio.read(ev, **args) < 43
    for name, seconds in zip(ROOFLINES, (0.8, 0.4, 0.9)):
        args = _load("layer_metrics", name)["args"]
        got = trace_op_roofline_dsa.read(ev, **args)
        need = getattr(flops_dsa, args["work"])(
            cfg, n, c["train.index_cells"], c["train.index_selected"], args["calls"])
        least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
        assert abs(got - 100.0 * least / seconds) < 1e-9 and 0 < got < 100, name
        # not among the ten heaviest: nothing, not the share of half the time
        assert trace_op_roofline_dsa.read(dict(ev, trace=dict(device_ops=[["fusion", 5.0]])),
                                          **args) is None
    # "index_kl_fwd" does not read the backward kernel's seconds
    only_bwd = dict(ev, trace=dict(device_ops=[["index_kl_bwd", 0.9]]))
    assert trace_op_roofline_dsa.read(
        only_bwd, **_load("layer_metrics", "train_index_kl_fwd_roofline_pct")["args"]) is None
    # nothing to read: another family, no counters (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if not k.startswith("train.index_")}
    args = _load("layer_metrics", ROOFLINES[0])["args"]
    for reader, a in ((flops_rate_dsa, {}), (trace_op_roofline_dsa, args)):
        assert reader.read(dict(ev, hf_config={"model_type": "qwen2"}), **a) is None
        assert reader.read(dict(ev, program=dict(counters=less)), **a) is None
        assert reader.read(dict(ev, program=None), **a) is None
        assert reader.read(dict(ev, peaks=None), **a) is None
    assert flops_rate_dsa.read(dict(ev, work=None)) is None
    assert trace_op_roofline_dsa.read(dict(ev, trace=None), **args) is None


def test_the_hosts_three_counters_are_a_brute_force_count():
    from areal_tpu.ops.indexer import index_counts

    lens, top_k, t = [5, 1, 9, 3], 4, 24
    seg, pos, o = np.zeros((1, t), np.int32), np.zeros((1, t), np.int32), 0
    for j, l in enumerate(lens):
        seg[0, o:o + l], pos[0, o:o + l] = j + 1, np.arange(l)
        o += l
    pos[0, o:] = np.arange(t - o)  # the padding's places count for nothing
    cells = sum(1 for l in lens for q in range(l) for k in range(q + 1))
    kept = sum(min(q + 1, top_k) for l in lens for q in range(l))
    choosing = sum(q + 1 > top_k for l in lens for q in range(l))
    assert tuple(int(c) for c in index_counts(pos, seg, top_k)) == (cells, kept, choosing)
    assert (cells, kept) == tuple(int(x) for x in flops_dsa.pool_cells(lens, top_k))


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_index_selected_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # six indexed layers, topk 16 at toy size: some queries choose, most cells are kept
    assert 0 < c["train.index_queries_choosing"] < 6 * c["train.tokens"]
    assert 0 < c["train.index_selected"] < c["train.index_cells"]
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 6
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["kinds"] == "moe.indexed.full.rope x6" for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
