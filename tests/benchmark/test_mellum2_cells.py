"""The cell PR 56 adds (`mellum2-d4e16-train-ppo-long`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_mellum, manifest, traffic
from benchmark.flops_moe import attention_cells
from benchmark.readers import flops_rate_mellum
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "mellum2-d4e16-train-ppo-long", "mellum2-d4-e16", "ppo-packed-long-2b"
SIBLING = "qwen3next-d4e32-train-ppo-long"
S, F = "sliding_attention", "full_attention"
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}
CUT_WITH_THE_DEPTH = {"layer_types": [S, S, S, F], "mlp_layer_types": ["sparse"] * 4}
OURS = {"num_experts_routed": 64, "experts_held_first": 0}
NEW = ("train_mfu_mellum_pct", "train_mellum_window_cells_pct", "train_mellum_held_pairs_pct",
       "train_mellum_tile_rows_ratio_pct")
SOURCE = "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json"

# The settings as the catalog beside the model-configs guide read them from
# JetBrains/Mellum2-12B-A2.5B-Instruct's config.json.
PUBLISHED = dict(
    attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=2304,
    intermediate_size=7168, layer_types=[S, S, S, F] * 7, mlp_layer_types=["sparse"] * 28,
    max_position_embeddings=131072, max_window_layers=0, model_type="mellum",
    moe_intermediate_size=896, norm_topk_prob=True, num_attention_heads=32, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=28, num_key_value_heads=4, rms_norm_eps=1e-06,
    rope_parameters={
        F: dict(rope_type="yarn", rope_theta=500000, factor=16,
                original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
                attention_factor=1.2772588722239782),
        S: dict(rope_type="default", rope_theta=500000)},
    sliding_window=1024, tie_word_embeddings=False, vocab_size=98304, use_sliding_window=True)


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
    assert entry["source"] == cfg["benchmark"]["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    # the two lists of a kind a layer are cut with the depth, to their first period
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == (
        set(REDUCED) | set(CUT_WITH_THE_DEPTH))
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    for k, v in CUT_WITH_THE_DEPTH.items():
        assert cfg[k] == v == PUBLISHED[k][:4]
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog's own row, where the guide is installed
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]
        # every number of the row's config under the same key, but the reduced
        assert {k for k, v in row["config"].items() if isinstance(v, (int, float))
                and not isinstance(v, bool) and cfg[k] != v} == set(REDUCED)
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert b["held_here"] == {**REDUCED, **OURS}
    assert "one of 4 chips" in b["deployment"] and "four times their share" in b["deployment"]
    assert "nothing stands in for it" in b["deployment"]
    assert len(b["assumed"]) >= 9 and b["reference"] == "mellum" and b["dtype"] == "bfloat16"
    for said in ("RMSNorm over the 128", "softmax over all 64", "1,023 positions before",
                 "max_window_layers (0) and use_sliding_window (true) are not consulted",
                 "truncate true", "= 18", "= 35", "1.6314", "No prediction module",
                 "7168 is used by no layer", "Seeded weights", "from memory",
                 "repository's keys"):
        assert any(said in a for a in b["assumed"]), said
    assert "595,154,176" in b["reduced"]["num_hidden_layers"]
    assert "8.33 GB" in b["reduced"]["num_hidden_layers"]
    assert "15.1 GB" in b["reduced"]["num_hidden_layers"]
    assert "15.5 GB" in b["reduced"]["num_experts"] and "13.9 GB" in b["reduced"]["num_experts"]
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"] and cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] == 4  # one whole period: three window layers, one full
    over = b["rehearsal_overrides"]
    assert set(over) >= {"hidden_size", "head_dim", "sliding_window", "rope_parameters"}
    assert over["rope_parameters"][F]["original_max_position_embeddings"] == 64


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import RotarySet
    from areal_tpu.models.transformer import init_params
    from areal_tpu.ops.rotary import rotary_inv_freq
    from benchmark import model

    cfg = model.transformer_config(_hf(), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["attention+moe"] * 4
    assert [(k.window, k.rotary_set) for k in cfg.kinds()] == [(1024, S)] * 3 + [(None, F)]
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (
        2304, 32, 4, 128, 24576)
    assert cfg.qk_norm and not cfg.attn_gate and cfg.norm_eps == 1e-6 and not cfg.tied_embeddings
    assert cfg.rotary_sets[S] == RotarySet(base=500000.0)
    assert cfg.rotary_sets[F].attention_factor == 1.2772588722239782
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k, cfg.moe.score_func,
            cfg.moe.route_norm, cfg.moe.n_shared_experts, cfg.moe.expert_intermediate_dim,
            cfg.moe.router_bias, cfg.moe.routed_scaling_factor) == (
        64, (0, 16), 8, "softmax", True, 0, 896, False, 1.0)
    assert cfg.mtp is None and cfg.hyper is None and cfg.mla is None and cfg.indexer is None
    # the program's own parameter count: the issue's, to the digit; 8.33 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 595_154_176 and round(count(shapes) * 14 / 1e9, 2) == 8.33
    layers = shapes["layers"]
    assert count(layers["attn"]) == 4 * (21_233_664 + 256)
    assert count(layers["mlp"]) == 4 * (99_090_432 + 147_456)
    assert count(layers) == 4 * 120_476_416
    assert count(shapes["embedding"]) + count(shapes["head"]) == 113_246_208
    assert layers["mlp"]["w_gate"].shape == (4, 16, 2304, 896)
    assert layers["mlp"]["router"].shape == (4, 2304, 64)
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [(("attention+moe",), 4)]
    full = cfg.rotary_sets[F]
    ratio = rotary_inv_freq(128, full.base, full.scaling, full.scaling_type,
                            full.scaling_params) / rotary_inv_freq(128, 5e5)
    assert np.all(ratio[:19] == 1.0) and np.allclose(ratio[35:], 1 / 16)
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.head_dim, toy.moe.experts_held, toy.kinds()[0].window) == (
        32, 16, (0, 4), 16)
    t = toy.rotary_sets[F]
    ramp = rotary_inv_freq(16, t.base, t.scaling, t.scaling_type, t.scaling_params) / (
        rotary_inv_freq(16, t.base))
    assert ramp[0] == 1.0 and 1 / 16 < ramp[2] < ramp[1] < 1.0 and np.allclose(ramp[3:], 1 / 16)


def test_every_micro_batch_is_one_row_of_16384_and_every_layer_loops():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack
    from areal_tpu.models.transformer import looping_layers
    from benchmark import model

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 16384 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the qwen3-next cell, unchanged, and its optimizer
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
    # why this mix: at 16k a window of 1,024 and a full layer stand far apart
    flat = [l for b in lens for l in b]
    window = sum(attention_cells(l, 1024) for l in flat) / sum(flat)
    full = sum(attention_cells(l) for l in flat) / sum(flat)
    assert round(window) == 933 and round(full) == 4042
    assert round(100 * sum(l for l in flat if l > 2048) / sum(flat), 1) == 95.5


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert len(_entry("configs", CONFIG)["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in _load("layer_metrics", name)["cells"])
        unlisted = name == "train_mfu_pct"  # a dense block's arithmetic
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in NEW:
        f, m = _load("layer_metrics", name), _entry("per_layer", name)
        assert f["cells"] == ["mellum2-*"] and f["moves"] == "train_tokens_per_s"
        assert f["unit"] == "%" and listed[name] == [CELL]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    mfu = _load("layer_metrics", "train_mfu_mellum_pct")
    assert (mfu["reader"], mfu["source"], mfu["layer"], mfu["better"]) == (
        "flops_rate_mellum", "host_clock", "trainer engine", "higher")
    ratios = {"train_mellum_window_cells_pct": ("train.attn_window_cells",
                                                "train.attn_active_cells"),
              "train_mellum_held_pairs_pct": ("train.moe_pairs_held", "train.moe_pairs"),
              "train_mellum_tile_rows_ratio_pct": ("train.moe_rows", "train.moe_pairs_held")}
    for name, (num, den) in ratios.items():
        f = _load("layer_metrics", name)
        assert f["reader"] == "program_counter_ratio" and f["source"] == "program_counter"
        assert f["args"] == {"num": num, "den": den, "scale": 100.0}
        assert f["layer"] == "kernels, training"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


HF_TOY = dict(model_type="mellum", num_hidden_layers=4, layer_types=[S, F, S, S],
              sliding_window=2, hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
              head_dim=6, moe_intermediate_size=5, num_experts=2, num_experts_routed=6,
              vocab_size=10)


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    assert flops_mellum.layer_counts(HF_TOY) == (3, 1)
    m = flops_mellum.matmul_params(HF_TOY)
    attn = 8 * (2 + 2 * 1) * 6 + 2 * 6 * 8
    assert (m["attn_proj"], m["attn_dim"], m["router"], m["head"], m["pair"]) == (
        4 * attn, 2 * 2 * 6, 4 * 8 * 6, 80, 120)
    out = flops_mellum.train_flops(HF_TOY, [3, 1], pairs_held=5, head_cells=4)
    assert out["attn_proj"] == 6.0 * 4 * attn * 4 and out["router"] == 6.0 * 4 * 48 * 4
    # a window of 2 over 3 tokens: 1 + 2 + 2 cells; a full layer 1 + 2 + 3
    assert attention_cells(3, 2) == 5 and attention_cells(3) == 6
    assert out["attention_window"] == 6.0 * 24 * 3 * (5 + 1)
    assert out["attention_full"] == 6.0 * 24 * 1 * (6 + 1)
    assert out["experts"] == 6.0 * 120 * 5 and out["head"] == 6.0 * 80 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the cell's own: the issue's parts a token, forward MFLOP
    hf = _hf()
    big = flops_mellum.matmul_params(hf)
    assert big["attn_proj"] == 4 * 21_233_664 and big["pair"] == 3 * 2304 * 896
    assert big["router"] == 4 * 147_456 and big["head"] == 2304 * 24576
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    per_token = {k: v / n / 3e6 for k, v in flops_mellum.train_flops(
        hf, lens, pairs_held=2 * 4 * n, head_cells=0.82 * n).items()}
    assert [round(per_token[k]) for k in ("attn_proj", "attention_window", "attention_full",
                                          "experts", "head")] == [170, 46, 66, 99, 93]
    attention = per_token["attention_window"] + per_token["attention_full"]
    assert (per_token["attn_proj"] + attention) / per_token["total"] > 0.5  # over half


def _evidence():
    cfg = _hf()
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=3.0 * n, sum_len_sq=3.0 * sum(l * l for l in lens), elapsed_s=15.0)
    counters = {"train.tokens": n, "train.cells": 16 * 16384, "train.moe_pairs": 32 * n,
                "train.moe_pairs_held": 8.0 * n, "train.moe_rows": 9.0 * n,
                "train.head_cells": 150000, "train.attn_active_cells": 400e6,
                "train.attn_window_cells": 250e6, "train.attn_full_cells": 150e6}
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=[["fusion", 5.0]])), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    want = 100.0 * 3 * flops_mellum.train_flops(cfg, lens, 8.0 * n, 150000)["total"] / 15.0 / 197e12
    assert abs(flops_rate_mellum.read(ev) - want) < 1e-9 and 5 < want < 60
    ratio = manifest.load_reader("program_counter_ratio")
    read = lambda name: ratio.read(ev, **_load("layer_metrics", name)["args"])
    assert read("train_mellum_window_cells_pct") == 62.5
    assert read("train_mellum_held_pairs_pct") == 25.0
    assert read("train_mellum_tile_rows_ratio_pct") == 112.5
    # nothing to read: another family, no counters (a program without the split, as
    # this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if k != "train.attn_window_cells"}
    keye = manifest.hf_config(_load("configs", "keye-vl-2.0-d6-e16"), False)
    assert flops_rate_mellum.read(dict(ev, hf_config={"model_type": "qwen2"})) is None
    assert flops_rate_mellum.read(dict(ev, hf_config=keye)) is None
    assert flops_rate_mellum.read(dict(ev, program=dict(counters=less))) is None
    assert flops_rate_mellum.read(dict(ev, program=None)) is None
    assert flops_rate_mellum.read(dict(ev, peaks=None)) is None
    assert flops_rate_mellum.read(dict(ev, work=None)) is None
    assert ratio.read(dict(ev, program=dict(counters=less)),
                      **_load("layer_metrics", "train_mellum_window_cells_pct")["args"]) is None


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
    # the share of the chip's peak needs a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct", "train_mellum_window_cells_pct",
            "train_mellum_held_pairs_pct",
            "train_mellum_tile_rows_ratio_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree, over sequences
    # longer than the toy window (16) and the toy original context (64)
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    assert max(s["positions"] for s in ref["samples"]) > 64
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert c["train.attn_cells"] == c["train.cells"]  # the cells a layer runs its rows at
    assert c["train.attn_window_cells"] + c["train.attn_full_cells"] == c["train.attn_active_cells"]
    assert 0 < c["train.attn_window_cells"] and 0 < c["train.attn_full_cells"]
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 4
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == f"moe.w16.rope[{S}] x3,moe.full.rope[{F}]" and
        s["attrs"]["window"] == 16 for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
