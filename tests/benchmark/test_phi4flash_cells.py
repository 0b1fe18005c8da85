"""The cell PR 34 adds (`phi4flash-d8-train-ppo-8k`), its configuration,
traffic, operation count and metrics, read from their files. CPU only.
Nothing here says where an entry stands in a list, nor names the cells
that are: a cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np
import pytest

from benchmark import flops_sambay, manifest, traffic
from benchmark.readers import flops_rate_sambay, trace_op_roofline
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = ("phi4flash-d8-train-ppo-8k", "phi-4-mini-flash-d8", "ppo-packed-8k")
REDUCED = {"num_hidden_layers": 8, "vocab_size": 25008}

# The language model's settings as the catalog beside the model-configs
# guide read them from microsoft/Phi-4-mini-flash-reasoning's config.json.
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560, intermediate_size=10240,
    layer_norm_eps=1e-05, max_position_embeddings=262144, mb_per_layer=2,
    model_type="phi4flash", num_attention_heads=40, num_hidden_layers=32,
    num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, vocab_size=200064)


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _entry(section, name):
    return next(e for e in MAN[section] if e["name"] == name)


def _pool_lengths():
    pool = traffic.ppo_batch_lengths(traffic.effective(_load("traffic", TRAFFIC), False))
    return [[s["prompt_len"] + s["resp_len"] for s in b] for b in pool]


def test_config_keeps_every_published_key_but_the_two_reduced():
    cfg, entry = _load("configs", CONFIG), _entry("configs", CONFIG)
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["benchmark"]["reduced"]) == sorted(REDUCED)
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert set(cfg) - set(PUBLISHED) == {"benchmark"}  # nothing beside the published keys
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED} and b["held_here"] == REDUCED
    assert "44 %" in b["deployment"] and "3 : 2 : 1 : 1 : 1" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "phi4flash" and b["dtype"] == "bfloat16"
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["num_hidden_layers"] % 4 == 0
    # what the catalog is silent on is assumed, not set: the family's defaults run
    assert not [k for k in cfg if k.startswith(("mamba_", "scan_"))]
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "mamba_dt_rank", "scan_chunk_size"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == [
        "ssm+dense", "diffattention+dense", "ssm+dense", "diffattention+dense",
        "ssm+dense^", "diffattention+dense^", "gmu+dense", "xdiffattention+dense"]
    assert [k.window for k in cfg.kinds()] == [None, 512, None, 512, None, None, None, None]
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_dim, cfg.vocab_size) == (2560, 40, 20, 64, 10240, 25008)
    s = cfg.ssm
    assert (s.form, s.channels, s.dt_rank, s.state_dim, s.conv_kernel, s.chunk_size) == (
        "mamba1", 5120, 160, 16, 4, 128)
    # the program's own parameter count: the issue's 915.3 M, 12.81 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 915.3) < 0.1 and abs(count(shapes) * 14 / 1e9 - 12.81) < 0.01
    assert {k: round(count(v) / jax.tree_util.tree_leaves(v)[0].shape[0] / 1e6, 2)
            for k, v in shapes["stacks"].items()} == {
        "ssm+dense": 119.90, "ssm+dense^": 119.90, "diffattention+dense": 98.32,
        "diffattention+dense^": 98.32, "gmu+dense": 104.87, "xdiffattention+dense": 91.77}
    assert [seg.repeats for seg in cfg.segments()] == [1] * 8  # layer by layer: memory
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.ssm.channels, toy.ssm.dt_rank, toy.ssm.chunk_size, toy.head_dim) == (64, 2, 16, 8)


def test_the_traffic_is_an_8k_budget_with_a_seed_of_its_own():
    t = _load("traffic", TRAFFIC)
    assert (t["kind"], t["runner"], t["pool_batches"], t["tokens_per_step"], t["group_size"]) == (
        "ppo_batches", "train", 2, 65536, 8)
    assert t["prompt_len_uniform"] == [256, 1024] and t["response_len_clip"] == [64, 7168]
    assert t["response_len_lognormal"] == {"median": 2048, "sigma": 0.8}
    assert t["ppo"] == {"n_minibatches": 4, "max_tokens_per_mb": 8192}
    assert t["check"] == {"sequences": 3, "max_positions": 6144}
    others = [_load("traffic", n)["lengths_seed"] for n in manifest.list_names("traffic")
              if n != TRAFFIC]
    assert t["lengths_seed"] not in others
    lens = _pool_lengths()
    assert [sum(b) for b in lens] == [70504, 66037] and sum(map(len, lens)) == 41
    assert max(map(max, lens)) == 8174 <= 8192
    assert "70,504 + 66,037 = 136,541" in t["pool_notes"]


def test_every_micro_batch_is_one_row_of_8192_and_a_minibatch_three():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 8192
    assert {k: v for k, v in cell["engine"].items() if k != "row_len_multiple"} == dict(
        remat="full", max_row_len=None, prefetch_depth=2, stats_fetch_interval=1,
        attn_impl="auto", mesh=None, total_train_steps=1000)  # the launcher's defaults
    budget = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=8192)
    shapes, per_mini, prep = set(), [], set()
    for i, lens in enumerate(_pool_lengths()):
        batch = SequenceSample.from_default(
            ids=[f"{i}/{j}" for j in range(len(lens))], seqlens=lens,
            data={"packed_input_ids": np.zeros(sum(lens), np.int32)})
        prep.add(datapack.ladder_shape(lens, multiple))
        shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple)
                   for mb in batch.split(budget)[0]}
        for mini in batch.split(MicroBatchSpec(n_mbs=4))[0]:
            mbs = mini.split(budget)[0]
            per_mini.append(len(mbs))
            shapes |= {datapack.ladder_shape(mb.seqlens_of(), multiple) for mb in mbs}
    assert shapes == {(1, 8192)}
    assert per_mini == [3] * 8  # never one: two forward-backward programs, no third
    assert prep == {(1, 73728)}  # both batches' whole-batch prep rows share one rung


def test_the_cell_and_its_metrics_are_listed_where_their_files_are_read():
    cell, entry = _load("cells", CELL), _entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert all(w["chips"] == 1 for w in MAN["workloads"])
    assert CELL in _entry("end_to_end", "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    for name in manifest.list_names("layer_metrics"):
        read_here = any(fnmatch.fnmatchcase(CELL, g) for g in _load("layer_metrics", name)["cells"])
        unlisted = name in ("train_mfu_pct", "train_sscan_roofline_pct")
        assert (CELL in listed.get(name, [])) == (read_here and not unlisted), name
    assert listed["train_mfu_sambay_pct"] == [CELL]
    m = _entry("per_layer", "train_mfu_sambay_pct")
    f = _load("layer_metrics", "train_mfu_sambay_pct")
    assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert f["cells"] == ["phi4flash-*"] and m["moves"] == "train_tokens_per_s"
    # the roofline share waits for a loader that keeps every op (PERF.md 7 (c))
    assert "train_sscan_roofline_pct" not in listed
    assert _load("layer_metrics", "train_sscan_roofline_pct")["unit"] == "%"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] < 1.0  # no router: far under the expert cells' 2.0


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    assert flops_sambay.layer_letters(8) == "MSMSMFGX"
    assert [flops_sambay.layer_letters(32).count(c) for c in "MSFGX"] == [9, 8, 1, 7, 7]
    hf = dict(model_type="phi4flash", num_hidden_layers=8, hidden_size=8,
              num_attention_heads=4, num_key_value_heads=2, intermediate_size=12,
              vocab_size=10, sliding_window=2, mamba_dt_rank=1, mamba_d_state=3)
    # d_in 16, N 3, rank 1, head size 2, q 8, kv 4
    m = flops_sambay.matmul_params(hf)
    assert m["ssm_proj"] == 3 * (8 * 32 + 16 * 7 + 1 * 16 + 16 * 8)
    assert m["ssm_scan"] == 3 * 3 * 16 * 3
    assert m["gmu"] == 2 * 8 * 16
    assert m["attn_proj"] == 3 * (8 * 16 + 8 * 8) + 2 * 8 * 8
    assert (m["mlp"], m["head"]) == (8 * 3 * 8 * 12, 80)
    out = flops_sambay.train_flops(hf, [3, 1])
    tokens = 4
    # cells: window 2 in two layers: 3 -> 1 + 2 + 2 = 5, 1 -> 1; full in two: 6, 1
    cells = 2 * (5 + 1) + 2 * (6 + 1)
    assert out["attention"] == 18.0 * 8 * cells
    for part in ("ssm_proj", "ssm_scan", "gmu", "attn_proj", "mlp", "head"):
        assert out[part] == 6.0 * m[part] * tokens
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    # the cell's own: 5.1 GFLOP a token in matrix products, the scan's 1.5 M
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    big = flops_sambay.train_flops(cfg, [1])
    assert abs((big["total"] - big["attention"]) / 1e9 - 5.494) < 0.01
    assert big["ssm_scan"] == 6.0 * 3 * 3 * 5120 * 16
    # the scan's bytes: 8 d_in + 6 N values a position a layer, at two bytes
    assert flops_sambay.sscan_bytes(cfg, 1000) == 3 * (8 * 5120 + 6 * 16) * 2 * 1000


def test_the_readers_read_the_runs_evidence_or_nothing():
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    work = dict(tokens=2.0 * sum(lens), sum_len_sq=2.0 * sum(l * l for l in lens), elapsed_s=25.0)
    ev = dict(work=work, hf_config=cfg, peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
              chips=1)
    want = 100.0 * 2 * flops_sambay.train_flops(cfg, lens)["total"] / 25.0 / 197e12
    assert abs(flops_rate_sambay.read(ev) - want) < 1e-9 and 10 < want < 60
    assert flops_rate_sambay.read(dict(ev, hf_config=dict(cfg, model_type="qwen2"))) is None
    assert flops_rate_sambay.read(dict(ev, work=None)) is None
    assert flops_rate_sambay.read(dict(ev, peaks=None)) is None
    # the roofline share: a made-up list of the ten heaviest ops
    args = _load("layer_metrics", "train_sscan_roofline_pct")["args"]
    ops = [["fusion", 5.0], ["sscan_bwd", 0.8], ["sscan_fwd", 0.4], ["splash_mqa_fwd", 0.3]]
    ev = dict(ev, trace=dict(device_ops=ops), program=dict(counters={"train.tokens": 136541}))
    got = trace_op_roofline.read(ev, **args)
    assert abs(got - 100.0 * flops_sambay.sscan_bytes(cfg, 136541) / 819e9 / 1.2) < 1e-9
    assert 0 < got < 100
    # one of the two kernels outside the ten: nothing, not a share of half the time
    assert trace_op_roofline.read(dict(ev, trace=dict(device_ops=ops[:2])), **args) is None
    assert trace_op_roofline.read(dict(ev, trace=dict(device_ops=ops[:1])), **args) is None
    assert trace_op_roofline.read(dict(ev, trace=None), **args) is None
    assert trace_op_roofline.read(dict(ev, program=dict(counters={})), **args) is None
    assert trace_op_roofline.read(dict(ev, hf_config=dict(cfg, model_type="qwen2")), **args) is None


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct",
            "train_attn_row_ratio_pct", "train_head_cells_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    assert max(s["positions"] for s in ref["samples"]) > 16  # several toy blocks of time
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert c["train.sscan_cells"] == c["train.ssm_chunks"] * 16 == 3 * c["train.cells"]
    assert 0 < c["train.ssm_chunks_mixed"] <= c["train.ssm_chunks_live"] <= c["train.ssm_chunks"]
    assert c["train.ssm_resets"] > 0 and "train.moe_pairs" not in c
    assert c["train.attn_active_cells"] == c["train.attn_causal_cells"] > 0
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["window"] == 16 for s in dispatch)
    assert dispatch[0]["attrs"]["kinds"].endswith(
        "ssm+dense^,dense.diff.full.nope^,gmu+dense<4,dense.diff.full.nope<5")
