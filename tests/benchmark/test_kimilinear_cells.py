"""The cell PR 50 adds (`kimilinear-d5e8-train-ppo-long`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np

from benchmark import flops_kda, manifest, traffic
from benchmark.flops_moe import attention_cells
from benchmark.readers import flops_rate_kda, trace_op_roofline_kda
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "kimilinear-d5e8-train-ppo-long", "kimi-linear-d5-e8", "ppo-packed-long-2b"
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480,
           "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                                  "num_heads": 32, "head_dim": 128,
                                  "short_conv_kernel_size": 4}}
OURS = {"num_experts_routed": 256, "experts_held_first": 0}
ROOFLINES = ("train_kda_fwd_roofline_pct", "train_kda_bwd_roofline_pct")
LISTED = ("train_mfu_kda_pct", "train_kda_live_chunks_pct")

# The settings as the catalog beside the model-configs guide read them
# from moonshotai/Kimi-Linear-48B-A3B-Instruct's config.json.
PUBLISHED = dict(
    first_k_dense_replace=1, head_dim=72, hidden_act="silu", hidden_size=2304,
    intermediate_size=9216, kv_lora_rank=512,
    linear_attn_config={"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                                       21, 22, 23, 25, 26],
                        "num_heads": 32, "short_conv_kernel_size": 4},
    mla_use_nope=True, model_max_length=1048576, model_type="kimi_linear",
    moe_intermediate_size=1024, moe_layer_freq=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_attention_heads=32, num_expert_group=1,
    num_experts=256, num_experts_per_token=8, num_hidden_layers=27, num_key_value_heads=32,
    num_nextn_predict_layers=0, num_shared_experts=1, q_lora_rank=None, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    routed_scaling_factor=2.446, tie_word_embeddings=False, topk_group=1,
    use_grouped_topk=True, v_head_dim=128, vocab_size=163840)


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
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    # of the nested group only the two lists are cut, and they are the published
    # lists as far as the depth goes
    lin, pub = cfg["linear_attn_config"], PUBLISHED["linear_attn_config"]
    assert {k for k in pub if pub[k] != lin[k]} == {"kda_layers", "full_attn_layers"}
    for name in ("kda_layers", "full_attn_layers"):
        assert lin[name] == [i for i in pub[name] if i <= cfg["num_hidden_layers"]]
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    b = cfg["benchmark"]
    assert b["published"]["num_hidden_layers"] == 27 and b["published"]["num_experts"] == 256
    assert b["published"]["linear_attn_config"]["kda_layers"] == pub["kda_layers"]
    assert b["held_here"] == {
        **{k: v for k, v in REDUCED.items() if k != "linear_attn_config"}, **OURS,
        "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]}}
    assert "one of 32 chips" in b["deployment"] and "32 times their share" in b["deployment"]
    assert "nothing stands in for it" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "kimi_linear" and b["dtype"] == "bfloat16"
    for said in ("rank of the two low-rank products", "A_log is one value a head",
                 "carry no bias", "1e-6", "sigmoid of the gate", "unrotated", "Seeded weights",
                 "from memory", "repository's keys"):
        assert any(said in a for a in b["assumed"]), said
    assert "602.4 M" in b["reduced"]["num_hidden_layers"]
    assert "11.60 GB" in b["reduced"]["num_experts"]
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts_per_token"] == 8 and cfg["routed_scaling_factor"] == 2.446
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "linear_attn_config", "kv_lora_rank"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import KDAConfig, MLAConfig
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == [
        "kda+dense", "kda+moe", "kda+moe", "latentattention+moe", "kda+moe"]
    assert not any(k.rotary for k in cfg.kinds() if k.latent)
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.head_dim, cfg.vocab_size, cfg.intermediate_dim) == (
        2304, 32, 192, 20480, 9216)
    assert cfg.kda == KDAConfig(n_heads=32, head_dim=128, conv_kernel=4, gate_rank=128,
                                chunk_size=64)
    assert cfg.mla == MLAConfig(q_rank=None, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128)
    assert cfg.mla.softmax_scale is None  # the head size's own 192^-0.5
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k, cfg.moe.score_func,
            cfg.moe.routed_scaling_factor, cfg.moe.n_shared_experts) == (
        256, (0, 8), 8, "sigmoid", 2.446, 1)
    assert cfg.mtp is None and cfg.hyper is None
    # the program's own parameter count: the issue's 602.4 M, 8.43 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 602.4) < 0.2 and abs(count(shapes) * 14 / 1e9 - 8.43) < 0.01
    stacks = shapes["stacks"]
    assert round(count(stacks["kda+dense"]["kda"]) / 1e6, 2) == 39.51
    assert round(count(stacks["latentattention+moe"]["attn"]) / 1e6, 2) == 29.11
    assert round(count(stacks["kda+dense"]) / 1e6, 2) == 103.22
    assert round(count(stacks["kda+moe"]) / 3e6, 2) == 103.81
    assert round(count(stacks["latentattention+moe"]) / 1e6, 2) == 93.41
    assert round((count(shapes["embedding"]) + count(shapes["head"])) / 1e6, 2) == 94.37
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [
        (("kda+dense",), 1), (("kda+moe",), 2), (("latentattention+moe",), 1), (("kda+moe",), 1)]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.kda.n_heads, toy.kda.head_dim, toy.moe.experts_held) == (
        64, 2, 16, (0, 4))


def test_every_micro_batch_is_one_row_of_16384_and_every_layer_loops():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack
    from areal_tpu.models.transformer import looping_layers
    from benchmark import model

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 16384 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the cells that share the traffic file, and their optimizer
    others = [o for o in manifest.list_names("cells")
              if o != CELL and _load("cells", o)["traffic"] == TRAFFIC]
    assert "nemotron3n-d9e8-train-ppo-long" in others
    for other in others:
        assert cell["engine"] == _load("cells", other)["engine"]
        assert cell["rehearsal"] == _load("cells", other)["rehearsal"]
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
    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert looping_layers(cfg, 1, 16384) == 5


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in _load("layer_metrics", name)["cells"])
        unlisted = name == "train_mfu_pct"  # a dense block's arithmetic
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in LISTED:
        f, m = _load("layer_metrics", name), _entry("per_layer", name)
        assert f["cells"] == ["kimilinear-*"] and listed[name] == [CELL]
        assert f["moves"] == "train_tokens_per_s" and f["unit"] == "%"
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert _load("layer_metrics", "train_mfu_kda_pct")["reader"] == "flops_rate_kda"
    live = _load("layer_metrics", "train_kda_live_chunks_pct")
    assert live["reader"] == "program_counter_ratio" and live["args"] == {
        "num": "train.kda_chunks_live", "den": "train.kda_chunks", "scale": 100.0}
    for name, needs in zip(ROOFLINES, ("kda_fwd", "kda_bwd")):
        # the kernels of those names are the walk over chunks alone: their
        # seconds leave out `intra`, so the shares are read in no cell and
        # listed nowhere until the whole rule runs under ops of those names
        f = _load("layer_metrics", name)
        assert f["cells"] == [] and name not in listed
        assert f["reader"] == "trace_op_roofline_kda" and f["source"] == "device_trace"
        assert f["layer"] == "kernels, training" and f["args"]["needs"] == [needs]
    assert _load("layer_metrics", ROOFLINES[0])["args"]["calls"] == 2  # full remat
    assert _load("layer_metrics", ROOFLINES[1])["args"]["backward"] is True
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


HF_TOY = dict(model_type="kimi_linear", num_hidden_layers=4, first_k_dense_replace=1,
              hidden_size=8, num_attention_heads=2, qk_nope_head_dim=3, qk_rope_head_dim=2,
              v_head_dim=4, kv_lora_rank=6, intermediate_size=7, moe_intermediate_size=5,
              num_experts=2, num_experts_routed=6, num_shared_experts=1, vocab_size=10,
              linear_attn_config=dict(kda_layers=[1, 2, 4], full_attn_layers=[3], num_heads=2,
                                      head_dim=3, short_conv_kernel_size=4))


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    m = flops_kda.matmul_params(HF_TOY)
    kda = 3 * 8 * 6 + 2 * (8 * 3 + 3 * 6) + 8 * 2 + 6 * 8
    attn = 8 * 2 * 5 + 8 * (6 + 2) + 6 * 2 * (3 + 4) + 2 * 4 * 8
    assert (m["kda_proj"], m["kda_rule"], m["attn_proj"]) == (3 * kda, 3 * 4 * 9 * 2, attn)
    assert m["attn_dim"] == 2 * (3 + 2 + 4) and m["dense_mlp"] == 3 * 8 * 7
    assert (m["shared"], m["router"], m["head"], m["pair"]) == (3 * 3 * 8 * 5, 3 * 8 * 6, 80, 120)
    out = flops_kda.train_flops(HF_TOY, [3, 1], pairs_held=5, head_cells=4)
    for part in ("kda_proj", "kda_rule", "attn_proj", "dense_mlp", "router", "shared"):
        assert out[part] == 6.0 * m[part] * 4, part
    assert out["attention"] == 6.0 * m["attn_dim"] * (attention_cells(3) + attention_cells(1))
    assert out["experts"] == 6.0 * 120 * 5 and out["head"] == 6.0 * 80 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the rule's own work a position: 4 K V multiply-adds a head; q, k, v and o at
    # two bytes, the decay's product at four, beta
    fwd = flops_kda.kda_work(HF_TOY, cells=10, calls=2)
    assert fwd["flops"] == 2 * 10 * 2.0 * 4 * 9 * 2
    assert fwd["bytes"] == 2 * 10 * 2 * (4 * 3 * 2 + 3 * 4.0 + 4.0)
    bwd = flops_kda.kda_work(HF_TOY, cells=10, backward=True)
    assert bwd["flops"] == fwd["flops"] and bwd["bytes"] == fwd["bytes"]  # twice one call's
    # the cell's own: the issue's parts, a token
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    big = flops_kda.matmul_params(cfg)
    assert round(big["kda_proj"] / 4e6, 2) == 39.46 and big["kda_rule"] == 4 * 4 * 128 * 128 * 32
    assert round(big["attn_proj"] / 1e6, 2) == 29.11 and round(big["dense_mlp"] / 1e6, 1) == 63.7
    assert round((big["shared"] + big["router"]) / 4e6, 2) == 7.67
    # the rule is bound by its bytes: 1.9 ns a position a head against 0.7
    work = flops_kda.kda_work(cfg, cells=1)
    assert work["bytes"] / 819e9 > 2.5 * work["flops"] / 197e12


def _evidence():
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=3.0 * n, sum_len_sq=3.0 * sum(l * l for l in lens), elapsed_s=40.0)
    counters = {"train.tokens": n, "train.cells": 16 * 16384, "train.moe_pairs_held": 1.0 * n,
                "train.head_cells": 150000, "train.kda_cells": 4 * 160000,
                "train.kda_chunks": 4 * 2500, "train.kda_chunks_live": 4 * 2200}
    ops = [["fusion", 5.0], ["kda_fwd_states", 0.5], ["convolution", 0.8],
           ["kda_bwd_states", 0.4]]
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=ops)), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    want = 100.0 * 3 * flops_kda.train_flops(cfg, lens, 1.0 * n, 150000)["total"] / 40.0 / 197e12
    assert abs(flops_rate_kda.read(ev) - want) < 1e-9 and 5 < want < 60
    for name, seconds in zip(ROOFLINES, (0.5, 0.4)):
        args = _load("layer_metrics", name)["args"]
        got = trace_op_roofline_kda.read(ev, **args)
        need = flops_kda.kda_work(cfg, c["train.kda_cells"], args["calls"],
                                  args.get("backward", False))
        assert abs(got - 100.0 * need["bytes"] / 819e9 / seconds) < 1e-9 and 0 < got < 100, name
        # not among the ten heaviest: nothing, not the share of half the time
        assert trace_op_roofline_kda.read(dict(ev, trace=dict(device_ops=[["fusion", 5.0]])),
                                          **args) is None
    # nothing to read: another family, no counters (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if k != "train.kda_cells"}
    args = _load("layer_metrics", ROOFLINES[0])["args"]
    for reader, a in ((flops_rate_kda, {}), (trace_op_roofline_kda, args)):
        assert reader.read(dict(ev, hf_config={"model_type": "qwen2"}), **a) is None
        assert reader.read(dict(ev, program=dict(counters=less)), **a) is None
        assert reader.read(dict(ev, program=None), **a) is None
        assert reader.read(dict(ev, peaks=None), **a) is None
    assert flops_rate_kda.read(dict(ev, work=None)) is None
    assert trace_op_roofline_kda.read(dict(ev, trace=None), **args) is None


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct", "train_kda_live_chunks_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # four delta-rule layers; a toy row of 192 cells is three chunks of 64, one group
    assert c["train.kda_cells"] == 4 * c["train.cells"] == 64 * c["train.kda_chunks"] > 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"] and c["train.kda_resets"] > 0
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 4
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == "dense.kda.c64,moe.kda.c64 x2,moe.latent.full.nope,moe.kda.c64"
        for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
