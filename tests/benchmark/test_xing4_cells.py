"""The cell PR 47 adds (`xing4-d5e8-train-ppo-8k`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np

from benchmark import flops_mhc, flops_mla, manifest, traffic
from benchmark.readers import flops_rate_mhc, trace_op_roofline_mhc
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "xing4-d5e8-train-ppo-8k", "xing4.0-d5-e8", "ppo-packed-8k"
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 8,
           "vocab_size": 16384, "num_nextn_predict_layers": 0}
OURS = {"num_experts_routed": 64, "experts_held_first": 0}
ROOFLINES = ("train_mhc_mix_roofline_pct", "train_mhc_coef_grad_roofline_pct")
NEW_METRICS = ("train_mfu_mhc_pct",) + ROOFLINES

# The settings as the catalog beside the model-configs guide read them
# from XingChen-AGI/Xing4.0-29B-A4B's config.json.
PUBLISHED = dict(
    attention_bias=False, ep_size=1, first_k_dense_replace=2, hidden_act="silu",
    hidden_size=3584, intermediate_size=9216, kv_lora_rank=512,
    max_position_embeddings=262144, model_type="xing4_0", moe_intermediate_size=1024,
    moe_layer_freq=1, n_group=1, n_routed_experts=64, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=32, num_experts_per_tok=4, num_hidden_layers=40,
    num_key_value_heads=32, num_nextn_predict_layers=1, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-06, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, q_lora_rank=768,
    qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"},
    routed_scaling_factor=2, scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1,
    topk_method="noaux_tc", v_head_dim=128, vocab_size=131072)


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
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["rope_scaling"] == PUBLISHED["rope_scaling"]  # the nested group whole
    assert {k: cfg[k] for k in cfg if k.startswith(("hc_", "mhc_"))} == {
        k: PUBLISHED[k] for k in PUBLISHED if k.startswith(("hc_", "mhc_"))}
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert b["held_here"] == {**REDUCED, **OURS}
    assert "one of 8 chips" in b["deployment"] and "eight times their share" in b["deployment"]
    assert "nothing stands in for it" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "xing4_0" and b["dtype"] == "bfloat16"
    for said in ("no weight of its own", "hc_eps stands in Sinkhorn's denominators",
                 "n copies of the embedding", "Seeded weights", "2.0047", "repository's own"):
        assert any(said in a for a in b["assumed"]), said
    assert "modeling file" in b["reduced"]["num_nextn_predict_layers"]
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["n_routed_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts_per_tok"] == 4 and cfg["routed_scaling_factor"] == 2
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "q_lora_rank", "qk_rope_head_dim"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import HyperConnConfig
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["latentattention+dense"] + [
        "latentattention+moe"] * 4 and cfg.hyper.n == 4
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.head_dim, cfg.vocab_size, cfg.intermediate_dim) == (
        3584, 32, 192, 16384, 9216)
    assert (cfg.mla.q_rank, cfg.mla.kv_rank, cfg.mla.nope_dim, cfg.mla.rope_dim, cfg.mla.v_dim) == (
        768, 512, 128, 64, 128)
    assert abs(cfg.mla.softmax_scale - 192 ** -0.5 * 2.0047397) < 1e-7
    assert (cfg.rotary_scaling_type, cfg.rotary_scaling, cfg.rotary_base) == ("yarn", 64.0, 10000.0)
    assert cfg.hyper == HyperConnConfig(n=4, sinkhorn_iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k, cfg.moe.score_func,
            cfg.moe.routed_scaling_factor, cfg.moe.n_shared_experts) == (
        64, (0, 8), 4, "sigmoid", 2.0, 1)
    assert cfg.mtp is None
    # the program's own parameter count: the issue's 759.3 M, 10.63 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 759.3) < 0.2 and abs(count(shapes) * 14 / 1e9 - 10.63) < 0.01
    layer = shapes["layers"]
    assert round(count(layer["attn"]) / 4e6, 2) == 28.41
    assert count(layer["hc1"]) // 4 == count(layer["hc2"]) // 4 == 4 * 3584 * 24 + 24 + 3
    assert round(count(layer) / 4e6, 2) == 128.43 and round(count(shapes["lead_layers"]) / 1e6, 2) == 128.20
    assert round((count(shapes["embedding"]) + count(shapes["head"])) / 1e6, 2) == 117.44
    assert [seg.repeats for seg in cfg.segments()] == [1, 4]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.hyper.n, toy.moe.experts_held) == (32, 4, (0, 4))


def test_every_micro_batch_is_one_row_of_8192_and_the_scanned_layers_loop():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack
    from areal_tpu.models.transformer import looping_layers
    from benchmark import model

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 8192 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the cells that share the traffic file, and their optimizer
    others = [o for o in manifest.list_names("cells")
              if o != CELL and _load("cells", o)["traffic"] == TRAFFIC]
    assert others
    for other in others:
        assert cell["engine"] == _load("cells", other)["engine"]
        assert cell["rehearsal"] == _load("cells", other)["rehearsal"]
    assert cell["optimizer"] == {"lr": 0.0001} and cell["engine"]["remat"] == "full"
    lens = _pool_lengths()
    assert sum(map(sum, lens)) == 136541 and sum(map(len, lens)) == 41
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=8192)
    shapes, n_fb, n_fwd = set(), 0, 0
    for i, batch_lens in enumerate(lens):
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(batch_lens))], seqlens=batch_lens,
            data={"packed_input_ids": np.zeros(sum(batch_lens), np.int32)})
        fwd = batch.split(budget)[0]
        n_fwd += len(fwd)
        shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in fwd}
        for mini in batch.split(MicroBatchSpec(n_mbs=4))[0]:
            mbs = mini.split(budget)[0]
            n_fb += len(mbs)
            shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in mbs}
    assert shapes == {(1, 8192)} and (n_fb, n_fwd) == (24, 18)
    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert looping_layers(cfg, 1, 8192) == 4  # the dense layer, outside a scan, keeps the row


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
    for name in NEW_METRICS:
        f = _load("layer_metrics", name)
        assert f["moves"] == "train_tokens_per_s" and f["unit"] == "%"
        if name in ROOFLINES:
            # bytes in and out of the calls are not bytes through HBM (a band's
            # operands live in VMEM): the shares are read in no cell and listed nowhere
            assert f["cells"] == [] and name not in listed
            continue
        assert f["cells"] == ["xing4-*"]
        m = _entry("per_layer", name)
        assert listed[name] == [CELL]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert "train_mfu_mhc_pct" in listed
    assert _load("layer_metrics", "train_mfu_mhc_pct")["reader"] == "flops_rate_mhc"
    for name in ROOFLINES:
        f = _load("layer_metrics", name)
        assert f["reader"] == "trace_op_roofline_mhc" and f["source"] == "device_trace"
        assert f["layer"] == "kernels, training" and callable(getattr(flops_mhc, f["args"]["work"]))
        assert f["args"]["needs"] == [name[len("train_"):-len("_roofline_pct")]]
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


HF_TOY = dict(model_type="xing4_0", num_hidden_layers=3, first_k_dense_replace=1, hidden_size=8,
              num_attention_heads=2, qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=4,
              q_lora_rank=5, kv_lora_rank=6, intermediate_size=7, moe_intermediate_size=5,
              n_routed_experts=2, num_experts_routed=6, n_shared_experts=1, vocab_size=10,
              hc_mult=2)


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    s = flops_mhc.sizes(HF_TOY)
    assert (s["n"], s["coefs"], s["sublayers"]) == (2, 8, 6)
    assert s["proj_token"] == 2 * 8 * 8 and s["mix_token"] == 8 * 8
    out = flops_mhc.train_flops(HF_TOY, [3, 1], pairs_held=5, head_cells=4)
    base = flops_mla.train_flops(HF_TOY, [3, 1], 5, 4)
    assert base["mtp"] == 0 and "mtp" not in out
    for part in ("attn_proj", "attention", "dense_mlp", "router", "shared", "experts", "head"):
        assert out[part] == base[part] > 0, part
    attn = 8 * 5 + 5 * 2 * 5 + 8 * (6 + 2) + 6 * 2 * (3 + 4) + 2 * 4 * 8
    assert out["attn_proj"] == 6.0 * 3 * attn * 4
    assert out["mhc_proj"] == 6.0 * 128 * 6 * 4 and out["mhc_mix"] == 6.0 * 64 * 6 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the kernels' work a cell of a sublayer: stream-rows of `hidden` in and out
    # ten sublayer cells are five cells of a layer: six reads (3 rows each at n = 2)
    # and five writes (5 rows), the MLP's write's second forward left out
    whole = {"train.mhc_cells": 10}
    mix = flops_mhc.mhc_mix_work(HF_TOY, whole)
    assert mix["bytes"] == 5 * ((6 * 3 + 5 * 5) * 8 * 2 + (6 * 2 + 5 * 6) * 4)
    assert mix["flops"] == 5 * 2.0 * (6 * 2 + 5 * 6) * 8 and mix["again"] == 0
    # four of the ten inside a layer that walks bands: two cells of a layer make
    # both reads and the mixer's write a third time, a term of its own
    looped = flops_mhc.mhc_mix_work(HF_TOY, {"train.mhc_cells": 10, "train.mhc_loop_cells": 4})
    assert looped["again"] == 2 * ((2 * 3 + 5) * 8 * 2 + (2 * 2 + 6) * 4)
    assert looped["bytes"] == mix["bytes"] + looped["again"]
    assert looped["flops"] == mix["flops"] + 2 * 2.0 * (2 * 2 + 6) * 8
    grad = flops_mhc.mhc_coef_grad_work(HF_TOY, whole)
    assert grad["bytes"] == 10 * ((3 + 5) * 8 * 2 + (2 + 6) * 4) and grad["flops"] == 10 * 2.0 * 64
    # the cell's own: the issue's 0.344 M weights a sublayer, 24 multiply-adds a
    # feature, 2.7 MB and 1 MB a cell a step over ten sublayers
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    big = flops_mhc.sizes(cfg)
    assert (big["proj_token"], big["mix_token"], big["sublayers"]) == (344064, 24 * 3584, 10)
    ten = {"train.mhc_cells": 10}
    assert round(flops_mhc.mhc_mix_work(cfg, ten)["bytes"] / 1e6, 2) == 2.69
    assert round(flops_mhc.mhc_mix_work(cfg, dict(ten, **{"train.mhc_loop_cells": 10}))[
        "bytes"] / 1e6, 2) == 3.37  # a band's forward a third time: 94 stream-rows for 75
    assert round(flops_mhc.mhc_coef_grad_work(cfg, ten)["bytes"] / 1e6, 2) == 1.00
    # both stream kernels are bound by their bytes
    for work in (flops_mhc.mhc_mix_work(cfg, ten), flops_mhc.mhc_coef_grad_work(cfg, ten)):
        assert work["bytes"] / 819e9 > 50 * work["flops"] / 197e12
    # the cell's traced pass by its counters (my chip run, PR 47): 425 GB required,
    # 81 GB more in the bands' third forward
    pass_ = flops_mhc.mhc_mix_work(cfg, {"train.mhc_cells": 1581056,
                                         "train.mhc_loop_cells": 8 * 148480})
    assert round((pass_["bytes"] - pass_["again"]) / 1e9) == 425 and round(pass_["again"] / 1e9) == 81


def _evidence():
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=4.0 * n, sum_len_sq=4.0 * sum(l * l for l in lens), elapsed_s=40.0)
    counters = {"train.tokens": n, "train.cells": 196608, "train.moe_pairs_held": 2.0 * n,
                "train.head_cells": 150000, "train.mhc_cells": 10 * 160000,
                "train.mhc_loop_cells": 8 * 150000}
    ops = [["fusion", 5.0], ["mhc_mix", 0.9], ["convolution", 0.8], ["mhc_coef_grad", 0.3]]
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=ops)), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    want = 100.0 * 4 * flops_mhc.train_flops(cfg, lens, 2.0 * n, 150000)["total"] / 40.0 / 197e12
    assert abs(flops_rate_mhc.read(ev) - want) < 1e-9 and 5 < want < 60
    for name, seconds in zip(ROOFLINES, (0.9, 0.3)):
        args = _load("layer_metrics", name)["args"]
        got = trace_op_roofline_mhc.read(ev, **args)
        need = getattr(flops_mhc, args["work"])(cfg, c)
        least = max(need["bytes"] / 819e9, need["flops"] / 197e12)
        assert abs(got - 100.0 * least / seconds) < 1e-9 and 0 < got < 100, name
        # the bands' third forward is in the mix's count and not in the contraction's
        fewer = trace_op_roofline_mhc.read(dict(ev, program=dict(counters=dict(
            c, **{"train.mhc_loop_cells": 0}))), **args)
        assert (fewer < got) == (name == ROOFLINES[0]) and (fewer == got) == (name != ROOFLINES[0])
        # not among the ten heaviest: nothing, not the share of half the time
        assert trace_op_roofline_mhc.read(dict(ev, trace=dict(device_ops=[["fusion", 5.0]])),
                                          **args) is None
    # nothing to read: one stream, no counters (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if k != "train.mhc_cells"}
    args = _load("layer_metrics", ROOFLINES[0])["args"]
    for reader, a in ((flops_rate_mhc, {}), (trace_op_roofline_mhc, args)):
        assert reader.read(dict(ev, hf_config={"model_type": "qwen2"}), **a) is None
        assert reader.read(dict(ev, hf_config=dict(cfg, hc_mult=1)), **a) is None
        assert reader.read(dict(ev, program=dict(counters=less)), **a) is None
        assert reader.read(dict(ev, program=None), **a) is None
        assert reader.read(dict(ev, peaks=None), **a) is None
    assert flops_rate_mhc.read(dict(ev, work=None)) is None
    assert trace_op_roofline_mhc.read(dict(ev, trace=None), **args) is None


def test_an_ops_calls_are_read_from_a_traces_own_instructions():
    """`scripts/trace_op_events.py`: a device event's name is the HLO
    instruction, so the trace says which operands of a call the compiled
    program keeps in VMEM (`S(1)`): bytes in and out, and the part in HBM."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_op_events", os.path.join(os.path.dirname(manifest.BENCH_DIR), "scripts",
                                        "trace_op_events.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    hbm = ("%mhc_mix.3 = bf16[1024,3584]{1,0:T(8,128)(2,1)} custom-call(f32[1024,4]{1,0:T(8,128)} "
           "%a, bf16[1024,14336]{1,0:T(8,128)(2,1)} %x), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={f32[1024,4]{1,0}, bf16[1024,14336]{1,0}}")
    vmem = ("%mhc_mix.7 = bf16[1024,3584]{1,0:T(8,128)(2,1)S(1)} custom-call(f32[1024,4]{1,0:"
            "T(8,128)S(1)} %a, bf16[1024,14336]{1,0:T(8,128)(2,1)} %x)")
    assert mod.type_bytes(hbm) == (36716544, 36716544)
    assert mod.type_bytes(vmem) == (36716544, 1024 * 14336 * 2)
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        [hbm, 0.0, 50e3, ""], [hbm, 60e3, 70e3, ""], [vmem, 200e3, 20e3, ""],
        ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 300e3, 5e3, ""]]}]}]}
    rows = mod.op_rows(trace, ["mhc_mix"])
    assert [(r["instr"], r["calls"]) for r in rows] == [("mhc_mix.3", 2), ("mhc_mix.7", 1)]
    assert rows[0]["median_us"] == 60.0 and abs(rows[0]["gb_per_s"] - 36716544 / 60e-6 / 1e9) < 1e-6
    assert rows[1]["hbm_bytes"] == 1024 * 14336 * 2 and rows[1]["gb_per_s"] > 1500 > rows[1][
        "hbm_gb_per_s"]


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # five layers of two sublayers; toy rows are under two bands and run whole
    assert c["train.mhc_cells"] == 10 * c["train.cells"] > 0 == c["train.mhc_loop_cells"]
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 4
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == "hc4.dense.latent.full.rope,hc4.moe.latent.full.rope x4"
        for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
    stats = [l for l in r.stdout.splitlines() if "mhc_res_err" in l]
    assert not stats or "nan" not in stats[0]
