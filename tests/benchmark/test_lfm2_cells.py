"""The cell PR 63 adds (`lfm2-d5e16-train-ppo-8k`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import dataclasses
import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_lfm2, manifest, traffic
from benchmark.flops_moe import attention_cells
from benchmark.readers import flops_rate_lfm2
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "lfm2-d5e16-train-ppo-8k", "lfm2-8b-a1b-d5-e16", "ppo-packed-8k"
SIBLING = "olmohybrid-d4-train-ppo-8k"
C, F = "conv", "full_attention"
REDUCED = {"num_hidden_layers": 5, "layer_types": [C, F, C, C, C], "num_dense_layers": 1,
           "num_experts": 16, "vocab_size": 32768}
OURS = {"num_experts_routed": 32, "experts_held_first": 0}
COUNTS = ("train_lfm2_held_pairs_pct", "train_lfm2_tile_rows_ratio_pct",
          "train_lfm2_conv_live_cells_pct")
NEW = ("train_mfu_lfm2_pct",) + COUNTS

# The settings as the catalog beside the model-configs guide read them from
# LiquidAI/LFM2-8B-A1B's config.json (a copy: the row's `config`).
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=7168,
    layer_types=[C, C, F, C, C, C, F, C, C, C, F, C, C, C, F, C, C, C, F, C, C, F, C, C],
    max_position_embeddings=128000, model_type="lfm2_moe", moe_intermediate_size=1792,
    norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
    num_experts=32, num_experts_per_tok=4, num_hidden_layers=24, num_key_value_heads=8,
    rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)


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
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert set(cfg) - set(PUBLISHED) == {"benchmark"} | set(OURS)
    assert {k: cfg[k] for k in OURS} == OURS
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog's own row, where the guide is installed
        row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]
    b = cfg["benchmark"]
    assert b["published"]["num_hidden_layers"] == 24 and b["published"]["num_experts"] == 32
    assert b["published"]["vocab_size"] == 65536 and b["published"]["num_dense_layers"] == 2
    assert b["held_here"] == dict(REDUCED, **OURS)
    assert b["deployment"].startswith("one of 2 chips that share each layer")
    assert "nothing standing in for it" in b["deployment"]
    assert len(b["assumed"]) >= 9 and b["reference"] == "lfm2_moe" and b["dtype"] == "bfloat16"
    for said in ("tie_word_embeddings", "[B | C | x]", "no activation", "q_layernorm",
                 "half-split", "1e-6", "BUFFER_LEAVES", "same 16 draws", "embedding_norm",
                 "from memory", "Seeded weights", "num_experts_routed"):
        assert any(said in a for a in b["assumed"]), said
    said = b["reduced"]["num_hidden_layers"]
    for number in ("893,696,256", "12.51 GB", "60,827,648", "186,716,320", "193,013,792",
                   "67,108,864", "23.3 GB", "8.34 B"):
        assert number in said, number
    # no width and no head count among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank", "_heads"))
                and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["num_experts"] >= 8
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6]  # a dense layer, a whole period
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "moe_intermediate_size",
                                             "num_experts", "num_experts_routed"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import ConvConfig
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(_hf(), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["conv+dense", "attention+moe"] + ["conv+moe"] * 3
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.intermediate_dim,
            cfg.vocab_size) == (2048, 32, 8, 64, 7168, 32768)
    assert cfg.qk_norm and cfg.norm_eps == 1e-5 and cfg.rotary_base == 1e6 and cfg.tied_embeddings
    assert cfg.conv == ConvConfig(kernel=3, bias=False)
    moe = cfg.moe
    assert (moe.num_experts, moe.experts_held, moe.top_k, moe.expert_intermediate_dim) == (
        32, (0, 16), 4, 1792)
    assert (moe.score_func, moe.router_bias, moe.route_norm, moe.route_norm_eps,
            moe.routed_scaling_factor, moe.n_shared_experts) == ("sigmoid", True, True, 1e-6, 1.0, 0)
    # the program's own parameter count: the issue's 893.7 M, 12.51 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 893_696_256 and abs(count(shapes) * 14 / 1e9 - 12.51) < 0.01
    stacks = shapes["stacks"]
    assert count(stacks["conv+dense"]["conv"]) == count(stacks["conv+moe"]["conv"]) // 3 == 16_783_360
    assert count(stacks["attention+moe"]["attn"]) == 10_485_888
    assert count(stacks["conv+dense"]["mlp"]) == 44_040_192
    assert count(stacks["attention+moe"]["mlp"]) == 176_226_336
    assert count(shapes["embedding"]) == 67_108_864 and "head" not in shapes
    assert stacks["conv+moe"]["conv"]["in_proj"].shape == (3, 2048, 6144)
    assert stacks["conv+moe"]["mlp"]["w_gate"].shape == (3, 16, 2048, 1792)
    assert stacks["conv+moe"]["mlp"]["router"].shape == (3, 2048, 32)
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [
        (("conv+dense",), 1), (("attention+moe",), 1), (("conv+moe",), 3)]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.moe.num_experts, toy.moe.experts_held, toy.head_dim) == (
        32, 8, (0, 4), 8)


def test_every_micro_batch_is_one_row_of_8192_and_every_layer_loops():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack
    from areal_tpu.models.transformer import looping_layers
    from benchmark import model

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 8192 and t["ppo"]["n_minibatches"] == 4
    assert (t["tokens_per_step"], t["group_size"], t["pool_batches"], t["lengths_seed"]) == (
        65536, 8, 2, 3401)
    # the engine block of the olmo-hybrid cell, unchanged, and its optimizer
    sibling = _load("cells", SIBLING)
    assert sibling["traffic"] == TRAFFIC and cell["engine"] == sibling["engine"]
    assert cell["rehearsal"] == sibling["rehearsal"] and cell["optimizer"] == sibling["optimizer"]
    assert cell["optimizer"] == {"lr": 0.0001} and cell["engine"]["remat"] == "full"
    assert cell["engine"]["mesh"] is None and cell["engine"]["prefetch_depth"] == 2
    lens = _pool_lengths()
    assert sum(map(sum, lens)) == 136541
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=8192)
    shapes = set()
    for i, batch_lens in enumerate(lens):
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(batch_lens))], seqlens=batch_lens,
            data={"packed_input_ids": np.zeros(sum(batch_lens), np.int32)})
        shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in batch.split(budget)[0]}
        for mini in batch.split(MicroBatchSpec(n_mbs=4))[0]:
            shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple)
                       for mb in mini.split(budget)[0]}
    assert shapes == {(1, 8192)}
    cfg = model.transformer_config(_hf(), "bfloat16")
    assert looping_layers(cfg, 1, 8192) == 2 and looping_layers(cfg, 1, 8192, mixer="conv") == 1


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    for said in ("conv", "8,192", "16 of 32", "2x share", "host"):
        assert said in cell["why"], said
    assert len(_entry("configs", CONFIG)["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        f = _load("layer_metrics", name)
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in f["cells"])
        unlisted = name == "train_mfu_pct"  # a dense GQA block's arithmetic
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in NEW:
        f, m = _load("layer_metrics", name), _entry("per_layer", name)
        assert f["cells"] == ["lfm2-*"] and listed[name] == [CELL]
        assert f["moves"] == "train_tokens_per_s" and f["unit"] == "%"
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    mfu = _load("layer_metrics", "train_mfu_lfm2_pct")
    assert (mfu["reader"], mfu["source"], mfu["layer"]) == (
        "flops_rate_lfm2", "host_clock", "trainer engine")
    want = {"train_lfm2_held_pairs_pct": ("train.moe_pairs_held", "train.moe_pairs", "lower"),
            "train_lfm2_tile_rows_ratio_pct": ("train.moe_rows", "train.moe_pairs_held", "lower"),
            "train_lfm2_conv_live_cells_pct": ("train.conv_live_cells", "train.conv_cells",
                                               "higher")}
    for name, (num, den, better) in want.items():
        f = _load("layer_metrics", name)
        assert f["reader"] == "program_counter_ratio" and f["source"] == "program_counter"
        assert f["args"] == {"num": num, "den": den, "scale": 100.0} and f["better"] == better
        assert f["layer"] == "kernels, training"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"]
    for said in ("float8", "taps", "chiprun_out/lfm2_controls63.jsonl"):
        assert said in cell["logprob_tolerance_notes"], said


HF_TOY = dict(model_type="lfm2_moe", num_hidden_layers=5, layer_types=[C, F, C, C, C],
              num_dense_layers=1, hidden_size=8, intermediate_size=5, moe_intermediate_size=3,
              num_attention_heads=4, num_key_value_heads=2, num_experts=2,
              num_experts_routed=6, num_experts_per_tok=2, vocab_size=10)


def test_flops_count_the_held_share_by_part_at_a_hand_counted_size():
    assert flops_lfm2.layer_counts(HF_TOY) == (4, 1, 1, 4)
    assert flops_lfm2.layer_counts(dict(HF_TOY, num_hidden_layers=2)) == (1, 1, 1, 1)
    m = flops_lfm2.matmul_params(HF_TOY)
    assert m["conv_proj"] == 4 * (8 * 24 + 8 * 8)
    assert m["attn_proj"] == 8 * (4 + 2 * 2) * 2 + 4 * 2 * 8 and m["attn_dim"] == 4 * 2 * 2
    assert (m["dense_mlp"], m["router"], m["head"], m["pair"]) == (3 * 8 * 5, 4 * 8 * 6, 80, 72)
    out = flops_lfm2.train_flops(HF_TOY, [3, 1], pairs_held=7, head_cells=4)
    for part in ("conv_proj", "attn_proj", "dense_mlp", "router"):
        assert out[part] == 6.0 * m[part] * 4, part
    assert out["attention"] == 6.0 * m["attn_dim"] * (attention_cells(3) + attention_cells(1))
    assert out["experts"] == 6.0 * 72 * 7 and out["head"] == 6.0 * 80 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # nothing of the absent chip's: no pair held, no expert work; the router whole
    none = flops_lfm2.train_flops(HF_TOY, [3, 1], pairs_held=0, head_cells=4)
    assert none["experts"] == 0 and none["router"] == out["router"]
    # the cell's own: the issue's parts, a token
    big = flops_lfm2.matmul_params(_hf())
    assert big["conv_proj"] == 4 * (2048 * 6144 + 2048 * 2048)
    assert big["attn_proj"] == 2048 * 3072 + 2048 * 2048 and big["attn_dim"] == 32 * 2 * 64
    assert big["dense_mlp"] == 3 * 2048 * 7168 and big["router"] == 4 * 2048 * 32
    assert big["head"] == 2048 * 32768 and big["pair"] == 3 * 2048 * 1792
    # the gated convolution alone: 4 values a channel a cell forward, 7 backward
    work = flops_lfm2.conv_work(2048, 3, 8192)
    assert work["fwd_bytes"] == 4 * 8192 * 2048 * 2 and work["bwd_bytes"] == 7 * 8192 * 2048 * 2
    assert work["fwd_flops"] == 8 * 8192 * 2048 and work["bwd_flops"] == 3 * work["fwd_flops"]
    assert work["fwd_bytes"] / 819e9 > 10 * work["fwd_flops"] / 197e12  # bound by its bytes


def _evidence():
    cfg = _hf()
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=3.0 * n, sum_len_sq=3.0 * sum(l * l for l in lens), elapsed_s=14.0)
    counters = {"train.tokens": n, "train.cells": 24 * 8192, "train.head_cells": 140000,
                "train.moe_pairs": 16 * n, "train.moe_pairs_held": 8 * n,
                "train.moe_rows": 9.6 * n, "train.conv_cells": 4 * 160000,
                "train.conv_live_cells": 4 * n}
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=[["fusion", 5.0]])), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    want = 100.0 * 3 * flops_lfm2.train_flops(cfg, lens, 8 * n, 140000)["total"] / (
        14.0 * 197e12)
    assert abs(flops_rate_lfm2.read(ev) - want) < 1e-9 and 5 < want < 70
    ratio = manifest.load_reader("program_counter_ratio")
    read = lambda name, e=ev: ratio.read(e, **_load("layer_metrics", name)["args"])
    assert read(COUNTS[0]) == 50.0 and round(read(COUNTS[1]), 6) == 120.0
    assert round(read(COUNTS[2]), 4) == round(100.0 * n / 160000, 4)
    # nothing to read: another family, no counters (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if not k.startswith("train.conv")}
    other = manifest.hf_config(_load("configs", "mellum2-d4-e16"), False)
    for e in (dict(ev, hf_config={"model_type": "qwen2"}), dict(ev, hf_config=other),
              dict(ev, program=dict(counters=less)), dict(ev, program=None),
              dict(ev, peaks=None), dict(ev, work=None)):
        assert flops_rate_lfm2.read(e) is None
    assert read(COUNTS[2], dict(ev, program=dict(counters=less))) is None
    assert read(COUNTS[0], dict(ev, program=None)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_read_in_this_cell_alone(name):
    cells = [c for c in manifest.list_names("cells")
             if any(m["name"] == name for m in manifest.layer_metrics_for(c))]
    assert cells == [CELL]


def test_the_host_counts_the_cells_the_devices_loops_run(monkeypatch):
    """`train.conv_cells`, `train.conv_live_cells` and `train.band_cells`
    (`engine/train_counts.py`) against what the device ran, counted where it
    runs: a callback in the mixer, a call a band. One row of 128 cells with 40
    tokens at bands of 16: the dense convolution layer runs three bands, the
    three over experts the whole row; rows together, and a packer that fills
    every band, run every cell."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.train_counts import TrainCounts
    from areal_tpu.models import moe as moe_lib
    from areal_tpu.models.transformer import forward
    from areal_tpu.ops import ssm as ssm_lib
    from tests.model.test_layer_kinds import _packed, small_bands
    from tests.model.test_lfm2_stack import _cfg, _params

    monkeypatch.setattr(moe_lib, "_HELD_ROW_TILE", 8)
    small_bands(monkeypatch)
    cfg = _cfg()
    params = _params(cfg)
    ids, seg, pos, _ = _packed(rows=[[24, 16]], row_len=128)
    ran, mixer = [], ssm_lib.gated_conv_mixer

    def counted(carry, u, *a):
        jax.debug.callback(lambda: ran.append(u.shape[1]))
        return mixer(carry, u, *a)

    monkeypatch.setattr(ssm_lib, "gated_conv_mixer", counted)
    forward(params, cfg, ids, seg, pos, attn_impl="reference", bands=True).block_until_ready()
    jax.effects_barrier()
    # the dense layer's three live bands, then the scan's one trace of a whole row
    assert ran[:3] == [16] * 3 and set(ran[3:]) == {128} and len(ran) in (4, 6)

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    counts = TrainCounts(cfg, mesh, "reference", 128, 1, False, cfg.n_moe_layers)
    said = lambda c, s: c.of({"segment_ids": np.asarray(s)}, 40)[0]
    c = said(counts, seg)
    assert c["train.conv_cells"] == 48 + 3 * 128 and c["train.conv_live_cells"] == 4 * 40
    assert c["train.band_cells"] == (2 * 48 + 3 * 128) // 5  # the attention layer's too
    assert c["train.moe_pairs"] == 4 * 40 * 4
    together = said(counts, np.concatenate([seg, seg]))
    assert together["train.conv_cells"] == 4 * 256 and together["train.conv_live_cells"] == 4 * 80
    filled = dataclasses.replace(counts, row_len_multiple=16)
    assert said(filled, seg)["train.conv_cells"] == 4 * 128
    # a stack without such a mixer says nothing of it
    from areal_tpu.models.config import TransformerConfig

    plain = TrainCounts(TransformerConfig(), mesh, "reference", 128, 1, False, 0)
    assert not [k for k in said(plain, seg) if "conv" in k]


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the share of the chip's peak needs a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct"} | set(COUNTS) <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # four convolution layers; a toy row of 192 cells is under two bands: the whole row
    assert c["train.conv_cells"] == 4 * c["train.cells"] > 0
    assert c["train.conv_live_cells"] == 4 * c["train.tokens"]
    assert c["train.moe_pairs"] == 4 * 4 * c["train.tokens"]
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.moe_rows"] >= c["train.moe_pairs_held"]
    assert c["train.attn_cells"] == c["train.cells"]  # the one attention layer's
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == "dense.conv.k3,moe.full.rope,moe.conv.k3 x3" for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
