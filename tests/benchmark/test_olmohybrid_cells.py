"""The cell PR 60 adds (`olmohybrid-d4-train-ppo-8k`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_olmo_hybrid, manifest, traffic
from benchmark.flops_moe import attention_cells
from benchmark.readers import (
    flops_rate_olmoh, program_counter_sum_ratio, trace_op_roofline_olmoh)
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = "olmohybrid-d4-train-ppo-8k", "olmo-hybrid-d4", "ppo-packed-8k"
SIBLING = "phi4flash-d8-train-ppo-8k"
L, F = "linear_attention", "full_attention"
REDUCED = {"num_hidden_layers": 4, "layer_types": [L, L, L, F], "vocab_size": 12544}
ROOFLINES = ("train_olmoh_fwd_roofline_pct", "train_olmoh_bwd_roofline_pct")
COUNTS = ("train_olmoh_live_chunks_pct", "train_olmoh_rule_kernel_cells_pct",
          "train_olmoh_taps_kernel_cells_pct")
NEW = ("train_mfu_olmoh_pct",) + COUNTS + ROOFLINES

# The settings as the catalog beside the model-configs guide read them from
# allenai/Olmo-Hybrid-7B's config.json (a copy: the row's `config`).
PUBLISHED = dict(
    model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840, intermediate_size=11008,
    num_hidden_layers=32, num_attention_heads=30, num_key_value_heads=30, hidden_act="silu",
    max_position_embeddings=65536, attention_bias=False, rms_norm_eps=1e-06,
    tie_word_embeddings=False, layer_types=[L, L, L, F] * 8, linear_num_key_heads=30,
    linear_num_value_heads=30, linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None})


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
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(cfg["benchmark"]["reduced"])
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert set(cfg) - set(PUBLISHED) == {"benchmark"}  # no key of this repository's own
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog's own row, where the guide is installed
        row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]
    b = cfg["benchmark"]
    assert b["published"]["num_hidden_layers"] == 32 and b["published"]["vocab_size"] == 100352
    assert b["held_here"] == REDUCED
    assert "one of 64 chips" in b["deployment"] and "nothing stands in for it" in b["deployment"]
    assert "host's share" in b["deployment"]
    assert len(b["assumed"]) >= 9 and b["reference"] == "olmo_hybrid" and b["dtype"] == "bfloat16"
    for said in ("output norms only", "whole projected width", "No rotary", "4 taps", "1e-6",
                 "2 sigmoid", "released Gated DeltaNet code's order", "[96, 192]",
                 "forgets within two tokens", "Seeded weights", "chunks of 64",
                 "doubling blocks", "from memory", "sizes nothing"):
        assert any(said in a for a in b["assumed"]), said
    said = b["reduced"]["num_hidden_layers"]
    assert "928.9 M" in said and "928,862,196" in said and "13.00 GB" in said
    assert "88.75 M" in said and "58.99 M" in said and "126.81 M" in said and "24.7 GB" in said
    assert "14.4 GB" in b["reduced"]["vocab_size"]
    # no width and no head count among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank", "_heads"))
                and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:4]  # one whole period
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "linear_key_head_dim",
                                             "linear_value_head_dim"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.config import KDAConfig
    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(_hf(), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["kda+dense"] * 3 + ["attention+dense"]
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.intermediate_dim,
            cfg.vocab_size) == (3840, 30, 30, 128, 11008, 12544)
    assert cfg.qk_norm and cfg.qk_norm_over == "width" and not cfg.pre_norms and cfg.post_norms
    assert not any(k.rotary for k in cfg.kinds() if k.mixer == "attention")
    assert cfg.norm_eps == 1e-6 and not cfg.tied_embeddings and cfg.moe is None
    assert cfg.kda == KDAConfig(n_heads=30, n_key_heads=30, head_dim=96, value_head_dim=192,
                                neg_eigval=True, conv_kernel=4, gate_rank=None, chunk_size=64,
                                decay="head", decay_input="column", gate_act="silu")
    # the program's own parameter count: the issue's 928.9 M, 13.0 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 928_862_196 and abs(count(shapes) * 14 / 1e9 - 13.0) < 0.01
    stacks = shapes["stacks"]
    assert round(count(stacks["kda+dense"]["kda"]) / 3e6, 2) == 88.75
    assert round(count(stacks["attention+dense"]["attn"]) / 1e6, 2) == 58.99
    assert round(count(stacks["attention+dense"]["mlp"]) / 1e6, 2) == 126.81
    assert round(count(stacks["kda+dense"]) / 3e6, 2) == 215.57
    assert round(count(stacks["attention+dense"]) / 1e6, 2) == 185.81
    assert round((count(shapes["embedding"]) + count(shapes["head"])) / 1e6, 2) == 96.34
    kd = stacks["kda+dense"]["kda"]
    assert kd["wq"].shape == kd["wk"].shape == (3, 3840, 2880)
    assert kd["wv"].shape == kd["w_g"].shape == (3, 3840, 5760) and kd["wo"].shape == (3, 5760, 3840)
    assert kd["o_norm"].shape == (3, 192) and kd["dt_bias"].shape == (3, 30)
    assert stacks["attention+dense"]["attn"]["q_norm"].shape == (1, 3840)
    assert {n for n in stacks["kda+dense"] if n.startswith("ln")} == {"ln1_post", "ln2_post"}
    assert [(seg.unit, seg.repeats) for seg in cfg.segments()] == [
        (("kda+dense",), 3), (("attention+dense",), 1)]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.hidden_dim, toy.kda.n_heads, toy.kda.head_dim, toy.kda.value_dim) == (64, 2, 16, 32)


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
    # the engine block of the phi4flash cell, unchanged, and its optimizer
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
    assert looping_layers(model.transformer_config(_hf(), "bfloat16"), 1, 8192) == 4


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert "96 x 192" in cell["why"] and "host" in cell["why"] and "8,192" in cell["why"]
    assert len(_entry("configs", CONFIG)["why"]) <= 200
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        f = _load("layer_metrics", name)
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in f["cells"])
        # a dense GQA block's arithmetic; a roofline share the traced run cannot read
        unlisted = name == "train_mfu_pct" or (name in ROOFLINES and name not in listed)
        if name in ROOFLINES and not read_here:  # kept with `"cells": []`: file, reader, tests
            assert f["cells"] == [] and name not in listed
            continue
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    for name in NEW:
        f = _load("layer_metrics", name)
        assert f["cells"] in (["olmohybrid-*"], []) and f["moves"] == "train_tokens_per_s"
        assert f["unit"] == "%" and f["better"] == "higher"
        if name in listed:
            m = _entry("per_layer", name)
            assert listed[name] == [CELL] and f["cells"] == ["olmohybrid-*"]
            assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
                k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert {"train_mfu_olmoh_pct"} | set(COUNTS) <= set(listed)
    assert _load("layer_metrics", "train_mfu_olmoh_pct")["reader"] == "flops_rate_olmoh"
    live = _load("layer_metrics", "train_olmoh_live_chunks_pct")
    assert live["reader"] == "program_counter_ratio" and live["args"] == {
        "num": "train.kda_chunks_live", "den": "train.kda_chunks", "scale": 100.0}
    rule = _load("layer_metrics", "train_olmoh_rule_kernel_cells_pct")
    assert rule["reader"] == "program_counter_sum_ratio" and rule["args"] == {
        "nums": ["train.kda_fwd_kernel_cells", "train.kda_bwd_kernel_cells"],
        "den": "train.kda_cells", "share": 2.0, "scale": 100.0}
    taps = _load("layer_metrics", "train_olmoh_taps_kernel_cells_pct")
    assert taps["args"] == {"num": "train.kda_taps_kernel_cells", "den": "train.kda_taps_cells",
                            "scale": 100.0}
    fwd, bwd = (_load("layer_metrics", n) for n in ROOFLINES)
    assert fwd["args"] == {"needs": ["kda_fwd_rule"], "calls": 2}  # that kernel alone, full remat
    assert bwd["args"] == {"needs": ["kda_bwd_rule"], "calls": 1, "backward": True}
    for f in (fwd, bwd):
        assert f["reader"] == "trace_op_roofline_olmoh" and f["source"] == "device_trace"
        assert f["layer"] == "kernels, training"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"]
    for said in ("float8", "beta", "chiprun_out/olmoh_controls60.jsonl"):
        assert said in cell["logprob_tolerance_notes"], said


HF_TOY = dict(model_type="olmo_hybrid", num_hidden_layers=4, layer_types=[L, L, L, F],
              hidden_size=8, intermediate_size=5, num_attention_heads=2, num_key_value_heads=2,
              linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=3,
              linear_value_head_dim=6, linear_conv_kernel_dim=4, vocab_size=10)


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    assert flops_olmo_hybrid.layer_counts(HF_TOY) == (3, 1)
    assert flops_olmo_hybrid.layer_counts(dict(HF_TOY, num_hidden_layers=2)) == (2, 0)
    assert flops_olmo_hybrid.head_dim(HF_TOY) == 4 and flops_olmo_hybrid.head_dim(
        dict(HF_TOY, head_dim=6)) == 6
    m = flops_olmo_hybrid.matmul_params(HF_TOY)
    gdn = 8 * (2 * 2 * 3 + 2 * 2 * 6) + 8 * 2 * 2 + 2 * 6 * 8  # q, k; v, gate; a, b; out
    attn = 8 * (2 + 2 * 2) * 4 + 2 * 4 * 8
    assert (m["gdn_proj"], m["gdn_rule"], m["attn_proj"]) == (3 * gdn, 3 * 4 * 3 * 6 * 2, attn)
    assert (m["attn_dim"], m["mlp"], m["head"]) == (2 * 2 * 4, 4 * 3 * 8 * 5, 80)
    out = flops_olmo_hybrid.train_flops(HF_TOY, [3, 1], head_cells=4)
    for part in ("gdn_proj", "gdn_rule", "attn_proj", "mlp"):
        assert out[part] == 6.0 * m[part] * 4, part
    assert out["attention"] == 6.0 * m["attn_dim"] * (attention_cells(3) + attention_cells(1))
    assert out["head"] == 6.0 * 80 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the rule's own work a position: 4 K V multiply-adds a head at the rectangle's
    # own K and V; q and k as they cross HBM (a toy key of 3 is not widened), v and
    # o at two bytes, the decay's input at two and beta at four a head
    fwd = flops_olmo_hybrid.rule_work(HF_TOY, cells=10, calls=2)
    assert fwd["flops"] == 2 * 10 * 2.0 * 4 * 3 * 6 * 2
    assert fwd["bytes"] == 2 * 10 * ((2 * 2 * 3 + 2 * 2 * 6 + 2) * 2 + 2 * 4.0)
    bwd = flops_olmo_hybrid.rule_work(HF_TOY, cells=10, backward=True)
    assert bwd["flops"] == fwd["flops"] and bwd["bytes"] == fwd["bytes"]  # twice one call's
    taps = flops_olmo_hybrid.taps_work(HF_TOY, cells=10)
    assert taps["flops"] == 10 * 2.0 * (2 * 2 * 3 + 2 * 6) * 4
    assert taps["bytes"] == 10 * 2 * (2 * 2 * 3 + 2 * 6) * 2.0
    assert flops_olmo_hybrid.taps_work(HF_TOY, 10, backward=True)["bytes"] == 1.5 * taps["bytes"]
    # the cell's own: the issue's parts, a token
    big = flops_olmo_hybrid.matmul_params(_hf())
    assert round(big["gdn_proj"] / 3e6, 2) == 88.7 and big["gdn_rule"] == 3 * 4 * 96 * 192 * 30
    assert round(big["attn_proj"] / 1e6, 2) == 58.98 and round(big["mlp"] / 4e6, 2) == 126.81
    assert round(big["head"] / 1e6, 2) == 48.17 and big["attn_dim"] == 30 * 2 * 128
    # keys of 96 cross HBM at 128 lanes, and the zeros are counted: a third more of
    # q's and k's bytes, a tenth of the rule's; the rule is bound by its bytes
    assert flops_olmo_hybrid.key_lanes(96) == 128 and flops_olmo_hybrid.key_lanes(128) == 128
    assert flops_olmo_hybrid.key_lanes(16) == 16 and flops_olmo_hybrid.key_lanes(64) == 64
    work = flops_olmo_hybrid.rule_work(_hf(), cells=1)
    assert work["bytes"] == (2 * 30 * 128 + 2 * 30 * 192 + 30) * 2 + 30 * 4.0
    unwidened = (2 * 30 * 96 + 2 * 30 * 192 + 30) * 2 + 30 * 4.0
    assert 1.10 < work["bytes"] / unwidened < 1.12
    assert work["bytes"] / 819e9 > 1.5 * work["flops"] / 197e12
    from areal_tpu.ops import kda  # the restated rule is the program's

    assert all(flops_olmo_hybrid.key_lanes(k) == kda.key_lanes(k) for k in range(1, 600))


def _evidence():
    cfg = _hf()
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=3.0 * n, sum_len_sq=3.0 * sum(l * l for l in lens), elapsed_s=30.0)
    counters = {"train.tokens": n, "train.cells": 20 * 8192, "train.head_cells": 90000,
                "train.kda_cells": 3 * 150000, "train.kda_chunks": 3 * 2400,
                "train.kda_chunks_live": 3 * 2160, "train.kda_fwd_kernel_cells": 3 * 150000,
                "train.kda_bwd_kernel_cells": 3 * 150000, "train.kda_taps_cells": 3 * 163840,
                "train.kda_taps_kernel_cells": 3 * 163840}
    ops = [["fusion", 5.0], ["kda_fwd_rule", 0.3], ["convolution", 0.8], ["kda_bwd_rule", 0.4],
           ["kda_taps_fwd", 0.05]]
    return dict(work=work, hf_config=cfg, chips=1, program=dict(counters=counters),
                peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
                trace=dict(device_ops=ops)), counters, n


def test_the_readers_read_the_runs_evidence_or_nothing():
    ev, c, n = _evidence()
    cfg = ev["hf_config"]
    lens = [l for b in _pool_lengths() for l in b]
    scale = n / c["train.tokens"]
    want = 100.0 * 3 * flops_olmo_hybrid.train_flops(cfg, lens, 90000 * scale)["total"] / (
        30.0 * 197e12)
    assert abs(flops_rate_olmoh.read(ev) - want) < 1e-9 and 5 < want < 70
    for name, seconds in zip(ROOFLINES, (0.3, 0.4)):
        args = _load("layer_metrics", name)["args"]
        got = trace_op_roofline_olmoh.read(ev, **args)
        need = flops_olmo_hybrid.rule_work(cfg, c["train.kda_cells"], args["calls"],
                                           args.get("backward", False))
        assert abs(got - 100.0 * need["bytes"] / 819e9 / seconds) < 1e-9 and 0 < got < 100, name
        # not among the ten heaviest: nothing, not the share of half the time
        assert trace_op_roofline_olmoh.read(dict(ev, trace=dict(device_ops=[["fusion", 5.0]])),
                                            **args) is None
    taps = trace_op_roofline_olmoh.read(ev, needs=["kda_taps_fwd"], work="taps", calls=2,
                                        cells="train.kda_taps_kernel_cells")
    need = flops_olmo_hybrid.taps_work(cfg, c["train.kda_taps_kernel_cells"], 2)
    assert abs(taps - 100.0 * need["bytes"] / 819e9 / 0.05) < 1e-9
    ratio = manifest.load_reader("program_counter_ratio")
    assert round(ratio.read(ev, **_load("layer_metrics", COUNTS[0])["args"]), 6) == 90.0
    assert ratio.read(ev, **_load("layer_metrics", COUNTS[2])["args"]) == 100.0
    rule = _load("layer_metrics", COUNTS[1])["args"]
    assert program_counter_sum_ratio.read(ev, **rule) == 100.0
    plain = dict(c, **{"train.kda_fwd_kernel_cells": 0, "train.kda_bwd_kernel_cells": 0})
    assert program_counter_sum_ratio.read(dict(ev, program=dict(counters=plain)), **rule) == 0.0
    half = dict(c, **{"train.kda_bwd_kernel_cells": 0})
    assert program_counter_sum_ratio.read(dict(ev, program=dict(counters=half)), **rule) == 50.0
    # nothing to read: another family (the other head form's among them), no
    # counters (this PR's parent), no window, no peak
    less = {k: v for k, v in c.items() if k != "train.kda_cells"}
    args = _load("layer_metrics", ROOFLINES[0])["args"]
    other = manifest.hf_config(_load("configs", "qwen3-next-d4-e32"), False)
    for reader, a in ((flops_rate_olmoh, {}), (trace_op_roofline_olmoh, args)):
        assert reader.read(dict(ev, hf_config={"model_type": "qwen2"}), **a) is None
        assert reader.read(dict(ev, hf_config=other), **a) is None
        assert reader.read(dict(ev, program=dict(counters=less)), **a) is None
        assert reader.read(dict(ev, program=None), **a) is None
        assert reader.read(dict(ev, peaks=None), **a) is None
    assert program_counter_sum_ratio.read(dict(ev, program=dict(counters=less)), **rule) is None
    assert program_counter_sum_ratio.read(dict(ev, program=None), **rule) is None
    assert flops_rate_olmoh.read(dict(ev, work=None)) is None
    assert trace_op_roofline_olmoh.read(dict(ev, trace=None), **args) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_read_in_this_cell_alone(name):
    cells = [c for c in manifest.list_names("cells")
             if any(m["name"] == name for m in manifest.layer_metrics_for(c))]
    assert cells == ([CELL] if _load("layer_metrics", name)["cells"] else [])


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_band_cells_pct"} | set(COUNTS) <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    # three delta-rule layers; a toy row of 192 cells is three chunks of 64, one group
    assert c["train.kda_cells"] == 3 * c["train.cells"] == 64 * c["train.kda_chunks"] > 0
    assert 0 < c["train.kda_chunks_live"] <= c["train.kda_chunks"] and c["train.kda_resets"] > 0
    assert c["train.kda_fwd_kernel_cells"] == c["train.kda_taps_kernel_cells"] == 0  # the CPU
    assert c["train.attn_cells"] == c["train.cells"]  # the one attention layer's
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(
        s["attrs"]["kinds"] == "dense.kda.head.k2.16x32.b2.c64 x3,dense.full.nope"
        for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
