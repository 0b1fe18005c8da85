"""The cell PR 54 adds (`qwen3next-d4e32-train-ppo-long`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_gdn, manifest, traffic
from benchmark.flops_moe import attention_cells
from benchmark.readers import flops_rate_gdn, trace_op_roofline_gdn
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "qwen3next-d4e32-train-ppo-long", "qwen3-next-d4-e32", "ppo-packed-long-2b"
SIBLING = "kimilinear-d5e8-train-ppo-long"
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
OURS = {"num_experts_routed": 512, "experts_held_first": 0}
ROOFLINES = ("train_gdn_fwd_roofline_pct", "train_gdn_bwd_roofline_pct")
NEW = ("train_mfu_gdn_pct", "train_gdn_live_chunks_pct", "train_gdn_tile_rows_ratio_pct") + ROOFLINES

# The settings as the catalog beside the model-configs guide read them from
# Qwen/Qwen3-Next-80B-A3B-Instruct's config.json.
PUBLISHED = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=256, hidden_act="silu",
    hidden_size=2048, intermediate_size=5120, linear_conv_kernel_dim=4, linear_key_head_dim=128,
    linear_num_key_heads=16, linear_num_value_heads=32, linear_value_head_dim=128,
    max_position_embeddings=262144, mlp_only_layers=[], model_type="qwen3_next",
    moe_intermediate_size=512, norm_topk_prob=True, num_attention_heads=16, num_experts=512,
    num_experts_per_tok=10, num_hidden_layers=48, num_key_value_heads=2,
    partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None, rope_theta=10000000,
    shared_expert_intermediate_size=512, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936)


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _entry(section, name):
    return next(e for e in MAN[section] if e["name"] == name)


def _pool_lengths():
    pool = traffic.ppo_batch_lengths(traffic.effective(_load("traffic", TRAFFIC), False))
    return [[s["prompt_len"] + s["resp_len"] for s in b] for b in pool]


def _hf():
    return manifest.hf_config(_load("configs", CONFIG), False)


def test_config_keeps_every_published_key_but_the_reduced():
    cfg, entry = _load("configs", CONFIG), _entry("configs", CONFIG)
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog's own row, where the guide is installed
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert b["held_here"] == {**REDUCED, **OURS}
    assert "one of 16 chips" in b["deployment"] and "16 times their share" in b["deployment"]
    assert "nothing stands in for it" in b["deployment"]
    assert len(b["assumed"]) >= 10 and b["reference"] == "qwen3_next" and b["dtype"] == "bfloat16"
    for said in ("laid out a key head", "no bias", "1e-6", "released code's order",
                 "forgets within two tokens", "scaling by w", "1 + w", "first 64",
                 "No prediction module", "5120 is used by no layer", "Seeded weights",
                 "from memory", "repository's keys"):
        assert any(said in a for a in b["assumed"]), said
    assert "625.7 M" in b["reduced"]["num_hidden_layers"]
    assert "8.76 GB" in b["reduced"]["num_hidden_layers"]
    assert "14.4 GB" in b["reduced"]["num_experts"] and "5.9 GB" in b["reduced"]["num_experts"]
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4  # one whole period
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "linear_num_key_heads", "head_dim"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import KDAConfig
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(_hf(), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["kda+moe"] * 3 + ["attention+moe"]
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (
        2048, 16, 2, 256, 18992)
    assert (cfg.rotary_fraction, cfg.rotary_dim, cfg.rotary_base) == (0.25, 64, 1e7)
    assert cfg.qk_norm and cfg.attn_gate and cfg.norm_eps == 1e-6 and not cfg.tied_embeddings
    assert cfg.kda == KDAConfig(n_heads=32, n_key_heads=16, head_dim=128, conv_kernel=4,
                                gate_rank=None, chunk_size=64, decay="head",
                                decay_input="column", gate_act="silu")
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k, cfg.moe.score_func,
            cfg.moe.route_norm, cfg.moe.n_shared_experts, cfg.moe.shared_intermediate_dim,
            cfg.moe.shared_gate, cfg.moe.router_bias, cfg.moe.routed_scaling_factor) == (
        512, (0, 32), 10, "softmax", True, 1, 512, True, False, 1.0)
    assert cfg.mtp is None and cfg.hyper is None and cfg.mla is None
    # the program's own parameter count: the issue's 625.7 M, 8.76 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 625.7) < 0.2 and abs(count(shapes) * 14 / 1e9 - 8.76) < 0.01
    stacks = shapes["stacks"]
    assert round(count(stacks["kda+moe"]["kda"]) / 3e6, 2) == 33.72
    assert round(count(stacks["attention+moe"]["attn"]) / 1e6, 2) == 27.26
    assert round(count(stacks["attention+moe"]["mlp"]) / 1e6, 2) == 104.86
    assert round(count(stacks["kda+moe"]) / 3e6, 2) == 138.58
    assert round(count(stacks["attention+moe"]) / 1e6, 2) == 132.13
    assert round((count(shapes["embedding"]) + count(shapes["head"])) / 1e6, 2) == 77.79
    # the decay a head: no leaf of the rule holds it a channel
    assert stacks["kda+moe"]["kda"]["dt_bias"].shape == (3, 32)
    assert stacks["kda+moe"]["kda"]["w_a"].shape == (3, 2048, 32)
    assert stacks["kda+moe"]["kda"]["wq"].shape == (3, 2048, 16 * 128)
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [
        (("kda+moe",), 3), (("attention+moe",), 1)]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.kda.n_heads, toy.kda.key_heads, toy.kda.head_dim,
            toy.moe.experts_held, toy.rotary_dim) == (64, 4, 2, 16, (0, 4), 8)


def test_every_micro_batch_is_one_row_of_16384_and_every_layer_loops():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack
    from areal_tpu.models.transformer import looping_layers
    from benchmark import model

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 16384 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the kimi-linear cell, unchanged, and its optimizer
    sibling = _load("cells", SIBLING)
    assert sibling["traffic"] == TRAFFIC and cell["engine"] == sibling["engine"]
    assert cell["rehearsal"] == sibling["rehearsal"] and cell["optimizer"] == sibling["optimizer"]
    assert cell["optimizer"] == {"lr": 0.0001} and cell["engine"]["remat"] == "full"
    lens = _pool_lengths()
    assert sum(map(sum, lens)) == 137977 and sum(map(len, lens)) == 24
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=16384)
    shapes = set()
    for i, batch_lens in enumerate(lens):
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(batch_lens))], seqlens=batch_lens,
            data={"packed_input_ids": np.zeros(sum(batch_lens), np.int32)})
        shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in batch.split(budget)[0]}
        for mini in batch.split(MicroBatchSpec(n_mbs=4))[0]:
            shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple)
                       for mb in mini.split(budget)[0]}
    assert shapes == {(1, 16384)}
    assert looping_layers(model.transformer_config(_hf(), "bfloat16"), 1, 16384) == 4


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert len(_entry("configs", CONFIG)["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in _load("layer_metrics", name)["cells"])
        # a dense block's arithmetic; a roofline share the traced run cannot read
        unlisted = name == "train_mfu_pct" or (name in ROOFLINES and name not in listed)
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in NEW:
        f = _load("layer_metrics", name)
        assert f["cells"] == ["qwen3next-*"] and f["moves"] == "train_tokens_per_s"
        assert f["unit"] == "%"
        if name in listed:
            m = _entry("per_layer", name)
            assert listed[name] == [CELL]
            assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
                k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert {"train_mfu_gdn_pct", "train_gdn_live_chunks_pct",
            "train_gdn_tile_rows_ratio_pct"} <= set(listed)
    assert _load("layer_metrics", "train_mfu_gdn_pct")["reader"] == "flops_rate_gdn"
    live = _load("layer_metrics", "train_gdn_live_chunks_pct")
    assert live["reader"] == "program_counter_ratio" and live["args"] == {
        "num": "train.kda_chunks_live", "den": "train.kda_chunks", "scale": 100.0}
    tiles = _load("layer_metrics", "train_gdn_tile_rows_ratio_pct")
    assert tiles["better"] == "lower" and tiles["args"] == {
        "num": "train.moe_rows", "den": "train.moe_pairs_held", "scale": 100.0}
    fwd, bwd = (_load("layer_metrics", n) for n in ROOFLINES)
    assert fwd["args"] == {"needs": ["kda_fwd_rule"], "calls": 2}  # that kernel alone, full remat
    assert bwd["args"] == {"needs": ["kda_bwd"], "calls": 1, "backward": True}
    for f in (fwd, bwd):
        assert f["reader"] == "trace_op_roofline_gdn" and f["source"] == "device_trace"
        assert f["layer"] == "kernels, training"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


HF_TOY = dict(model_type="qwen3_next", num_hidden_layers=4, full_attention_interval=4,
              hidden_size=8, num_attention_heads=2, num_key_value_heads=1, head_dim=6,
              linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=3,
              linear_value_head_dim=3, moe_intermediate_size=5,
              shared_expert_intermediate_size=7, num_experts=2, num_experts_routed=6,
              vocab_size=10)


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    assert flops_gdn.layer_counts(HF_TOY) == (3, 1)
    assert flops_gdn.layer_counts(dict(HF_TOY, num_hidden_layers=9)) == (7, 2)
    m = flops_gdn.matmul_params(HF_TOY)
    gdn = 8 * (2 * 2 * 3 + 2 * 4 * 3) + 8 * 2 * 4 + 4 * 3 * 8
    attn = 8 * 2 * 2 * 6 + 2 * 8 * 1 * 6 + 2 * 6 * 8
    assert (m["gdn_proj"], m["gdn_rule"], m["attn_proj"]) == (3 * gdn, 3 * 4 * 9 * 4, attn)
    assert m["attn_dim"] == 2 * 2 * 6
    assert (m["shared"], m["router"], m["head"], m["pair"]) == (
        4 * (3 * 8 * 7 + 8), 4 * 8 * 6, 80, 120)
    out = flops_gdn.train_flops(HF_TOY, [3, 1], pairs_held=5, head_cells=4)
    for part in ("gdn_proj", "gdn_rule", "attn_proj", "router", "shared"):
        assert out[part] == 6.0 * m[part] * 4, part
    assert out["attention"] == 6.0 * m["attn_dim"] * (attention_cells(3) + attention_cells(1))
    assert out["experts"] == 6.0 * 120 * 5 and out["head"] == 6.0 * 80 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the rule's own work a position: 4 K V multiply-adds a value head; q and k once
    # a key head, v and o a value head at two bytes, g and beta at four a value head
    fwd = flops_gdn.gdn_work(HF_TOY, cells=10, calls=2)
    assert fwd["flops"] == 2 * 10 * 2.0 * 4 * 9 * 4
    assert fwd["bytes"] == 2 * 10 * ((2 * 2 * 3 + 2 * 4 * 3) * 2 + 2 * 4 * 4.0)
    bwd = flops_gdn.gdn_work(HF_TOY, cells=10, backward=True)
    assert bwd["flops"] == fwd["flops"] and bwd["bytes"] == fwd["bytes"]  # twice one call's
    # the cell's own: the issue's parts, a token
    big = flops_gdn.matmul_params(_hf())
    assert round(big["gdn_proj"] / 3e6, 2) == 33.69 and big["gdn_rule"] == 3 * 4 * 128 * 128 * 32
    assert round(big["attn_proj"] / 1e6, 2) == 27.26
    assert round((big["shared"] + big["router"]) / 4e6, 2) == 4.2
    assert round(big["head"] / 1e6, 1) == 38.9 and round(big["pair"] / 1e6, 3) == 3.146
    # the rule is bound by its bytes (30 ns a position against 21), and reads less than the channel form's
    # (a decay a head, q and k a key head): 0.56 of its bytes a position
    work = flops_gdn.gdn_work(_hf(), cells=1)
    assert work["bytes"] / 819e9 > 1.3 * work["flops"] / 197e12
    assert work["bytes"] == (2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4.0


def _evidence():
    cfg = _hf()
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=3.0 * n, sum_len_sq=3.0 * sum(l * l for l in lens), elapsed_s=40.0)
    counters = {"train.tokens": n, "train.cells": 16 * 16384, "train.moe_pairs_held": 2.5 * n,
                "train.moe_rows": 4.0 * n, "train.head_cells": 150000,
                "train.kda_cells": 3 * 160000, "train.kda_chunks": 3 * 2500,
                "train.kda_chunks_live": 3 * 2200}
    ops = [["fusion", 5.0], ["kda_fwd_rule", 0.3], ["kda_fwd_states", 0.5],
           ["convolution", 0.8], ["kda_bwd_states", 0.4]]
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=ops)), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    want = 100.0 * 3 * flops_gdn.train_flops(cfg, lens, 2.5 * n, 150000)["total"] / 40.0 / 197e12
    assert abs(flops_rate_gdn.read(ev) - want) < 1e-9 and 5 < want < 60
    for name, seconds in zip(ROOFLINES, (0.3, 0.4)):  # `kda_fwd_states` is the backward loop's
        args = _load("layer_metrics", name)["args"]
        got = trace_op_roofline_gdn.read(ev, **args)
        need = flops_gdn.gdn_work(cfg, c["train.kda_cells"], args["calls"],
                                  args.get("backward", False))
        assert abs(got - 100.0 * need["bytes"] / 819e9 / seconds) < 1e-9 and 0 < got < 100, name
        # not among the ten heaviest: nothing, not the share of half the time
        assert trace_op_roofline_gdn.read(dict(ev, trace=dict(device_ops=[["fusion", 5.0]])),
                                          **args) is None
    ratio = manifest.load_reader("program_counter_ratio")
    assert ratio.read(ev, **_load("layer_metrics", "train_gdn_tile_rows_ratio_pct")["args"]) == 160.0
    assert round(ratio.read(ev, **_load("layer_metrics", "train_gdn_live_chunks_pct")["args"]),
                 6) == 88.0
    # nothing to read: another family (the other rule's among them), no counters
    # (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if k != "train.kda_cells"}
    args = _load("layer_metrics", ROOFLINES[0])["args"]
    kimi = manifest.hf_config(_load("configs", "kimi-linear-d5-e8"), False)
    for reader, a in ((flops_rate_gdn, {}), (trace_op_roofline_gdn, args)):
        assert reader.read(dict(ev, hf_config={"model_type": "qwen2"}), **a) is None
        assert reader.read(dict(ev, hf_config=kimi), **a) is None
        assert reader.read(dict(ev, program=dict(counters=less)), **a) is None
        assert reader.read(dict(ev, program=None), **a) is None
        assert reader.read(dict(ev, peaks=None), **a) is None
    assert flops_rate_gdn.read(dict(ev, work=None)) is None
    assert trace_op_roofline_gdn.read(dict(ev, trace=None), **args) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_read_in_this_cell_alone(name):
    cells = [c for c in manifest.list_names("cells")
             if any(m["name"] == name for m in manifest.layer_metrics_for(c))]
    assert cells == [CELL]


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct", "train_gdn_live_chunks_pct",
            "train_gdn_tile_rows_ratio_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # three delta-rule layers; a toy row of 192 cells is three chunks of 64, one group
    assert c["train.kda_cells"] == 3 * c["train.cells"] == 64 * c["train.kda_chunks"] > 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"] and c["train.kda_resets"] > 0
    assert c["train.attn_cells"] == c["train.cells"]  # the one attention layer's
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 4
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == "moe.kda.head.k2.c64 x3,moe.full.rope" for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
