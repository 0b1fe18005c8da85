"""The cell PR 40 adds (`joyai-d6e16-train-ppo-long`), its configuration,
operation count and metrics, read from their files. CPU only. Nothing here
says where an entry stands in a list, nor names the cells that are: a
cell appended after this one breaks none of it."""

import fnmatch
import json
import os

import numpy as np

from benchmark import flops_mla, manifest, traffic
from benchmark.readers import flops_rate_mla, program_counter_ratio
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
CELL, CONFIG, TRAFFIC = ("joyai-d6e16-train-ppo-long", "joyai-llm-flash-d6-e16",
                         "ppo-packed-long-2b")
REDUCED = {"num_hidden_layers": 6, "n_routed_experts": 16, "vocab_size": 16160}
OURS = {"num_experts_routed": 256, "experts_held_first": 0, "mtp_loss_weight": 0.1}
NEW_METRICS = ("train_mfu_mla_pct", "train_mtp_head_cells_pct")

# The language model's settings as the catalog beside the model-configs
# guide read them from jdopensource/JoyAI-LLM-Flash's config.json.
PUBLISHED = dict(
    attention_bias=False, ep_size=1, first_k_dense_replace=1, head_dim=64, hidden_act="silu",
    hidden_size=2048, intermediate_size=7168, kv_lora_rank=512, max_position_embeddings=131072,
    model_type="joyai_llm_flash", moe_intermediate_size=768, moe_layer_freq=1, n_group=1,
    n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True, num_attention_heads=32,
    num_experts_per_tok=8, num_hidden_layers=40, num_key_value_heads=32,
    num_nextn_predict_layers=1, q_lora_rank=1536, qk_head_dim=192, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_interleave=True, rope_scaling=None,
    rope_theta=32000000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc", v_head_dim=128,
    vocab_size=129280)


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _entry(section, name):
    return next(e for e in MAN[section] if e["name"] == name)


def _pool_lengths():
    pool = traffic.ppo_batch_lengths(traffic.effective(_load("traffic", TRAFFIC), False))
    return [[s["prompt_len"] + s["resp_len"] for s in b] for b in pool]


def test_config_keeps_every_published_key_but_the_three_reduced():
    cfg, entry = _load("configs", CONFIG), _entry("configs", CONFIG)
    assert entry["source"] == cfg["benchmark"]["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["benchmark"]["reduced"]) == sorted(REDUCED)
    assert {k for k in PUBLISHED if PUBLISHED[k] != cfg.get(k, "absent")} == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert {k: cfg[k] for k in set(cfg) - set(PUBLISHED) - {"benchmark"}} == OURS
    b = cfg["benchmark"]
    assert b["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert b["held_here"] == {**REDUCED, **{k: OURS[k] for k in ("num_experts_routed",
                                                                 "experts_held_first")}}
    assert "one of 16 chips" in b["deployment"] and "sixteen times their share" in b["deployment"]
    assert len(b["assumed"]) >= 8 and b["reference"] == "joyai_llm_flash"
    assert b["dtype"] == "bfloat16"
    assert any("stop_gradient" in a and "not the pretraining objective" in a for a in b["assumed"])
    assert any("Seeded weights" in a and "W_qb" in a for a in b["assumed"])
    # no width among the keys reduced; the floors of a model_config cut
    assert not [k for k in REDUCED if k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and cfg["n_routed_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert set(b["rehearsal_overrides"]) >= {"hidden_size", "q_lora_rank", "kv_lora_rank",
                                             "qk_rope_head_dim", "v_head_dim"}


def test_config_goes_through_the_family_at_the_published_widths():
    import jax

    from areal_tpu.models.transformer import init_params
    from benchmark import model

    cfg = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), False), "bfloat16")
    assert [k.parts for k in cfg.kinds()] == ["latentattention+dense"] + ["latentattention+moe"] * 5
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim,
            cfg.intermediate_dim, cfg.vocab_size) == (2048, 32, 32, 192, 64, 7168, 16160)
    assert (cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.top_k) == (256, (0, 16), 8)
    assert cfg.mtp.loss_weight == 0.1
    # the program's own parameter count: the issue's 787.5 M, 11.0 GB at 14 B
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert abs(count(shapes) / 1e6 - 787.5) < 0.1 and abs(count(shapes) * 14 / 1e9 - 11.03) < 0.01
    assert round(count(shapes["lead_layers"]) / 1e6, 2) == 70.39
    assert round(count(shapes["layers"]) / 5e6, 2) == 107.09
    assert round(count(shapes["mtp"]) / 1e6, 2) == 115.49
    assert round(count(shapes["layers"]["attn"]) / 5e6, 2) == 26.35
    assert [seg.repeats for seg in cfg.segments()] == [1, 5]
    toy = model.transformer_config(manifest.hf_config(_load("configs", CONFIG), True), "float32")
    assert (toy.mla.q_rank, toy.mla.kv_rank, toy.head_dim, toy.rotary_dim) == (24, 16, 16, 8)


def test_every_micro_batch_is_one_row_of_16384_and_a_minibatch_two():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base import datapack

    cell, t = _load("cells", CELL), _load("traffic", TRAFFIC)
    multiple = cell["engine"]["row_len_multiple"]
    assert multiple == t["ppo"]["max_tokens_per_mb"] == 16384 and t["ppo"]["n_minibatches"] == 4
    # the engine block of the cell that shares the traffic file, and its optimizer
    other = next(n for n in manifest.list_names("cells")
                 if n != CELL and _load("cells", n)["traffic"] == TRAFFIC)
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
        m, f = _entry("per_layer", name), _load("layer_metrics", name)
        assert listed[name] == [CELL] and f["cells"] == ["joyai-*"]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert m["moves"] == "train_tokens_per_s" and m["unit"] == "%"
    assert _load("layer_metrics", "train_mtp_head_cells_pct")["reader"] == "program_counter_ratio"
    tol = cell["logprob_tolerance"]
    assert 0 < tol["mean"] < tol["max"] and "float8" in cell["logprob_tolerance_notes"]


def test_flops_count_the_stack_by_part_at_a_hand_counted_size():
    hf = dict(model_type="joyai_llm_flash", num_hidden_layers=3, hidden_size=8,
              num_attention_heads=2, qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=3,
              q_lora_rank=4, kv_lora_rank=3, intermediate_size=12, moe_intermediate_size=5,
              n_routed_experts=2, num_experts_routed=6, n_shared_experts=1,
              first_k_dense_replace=1, vocab_size=10, num_nextn_predict_layers=1)
    m = flops_mla.matmul_params(hf)
    attn = 8 * 4 + 4 * 2 * 4 + 8 * 5 + 3 * 2 * 5 + 2 * 3 * 8
    assert m["layer_attn"] == attn == 182 and m["attn_proj"] == 3 * attn
    assert (m["dense_mlp"], m["shared"], m["router"]) == (3 * 8 * 12, 2 * 3 * 8 * 5, 2 * 8 * 6)
    assert (m["head"], m["pair"], m["attn_dim"]) == (80, 3 * 8 * 5, 2 * (2 + 2 + 3))
    out = flops_mla.train_flops(hf, [3, 1], pairs_held=5, head_cells=4, mtp_head_cells=2)
    tokens, cells = 4, 6 + 1  # causal within each sequence, one layer
    for part in ("attn_proj", "dense_mlp", "shared", "router"):
        assert out[part] == 6.0 * m[part] * tokens
    assert out["attention"] == 6.0 * 14 * 3 * cells
    assert out["experts"] == 6.0 * 120 * 5
    assert out["mtp"] == 6.0 * ((2 * 64 + attn + 48 + 120) * tokens + 14 * cells)
    assert out["head"] == 80 * (6.0 * 4 + 4.0 * 2)
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    assert flops_mla.train_flops(dict(hf, num_nextn_predict_layers=0), [3, 1], 5, 4)["mtp"] == 0
    # the cell's own: matrix parameters a token
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    big = flops_mla.matmul_params(cfg)
    assert round(big["attn_proj"] / 1e6, 1) == 158.1 and round(big["layer_attn"] / 1e6, 2) == 26.35
    assert big["attn_dim"] == 32 * 320
    one = flops_mla.train_flops(cfg, [1], pairs_held=6 * 0.5, head_cells=1, mtp_head_cells=1)
    stack = sum(one[p] for p in ("attn_proj", "dense_mlp", "shared", "router", "experts")) / 6e6
    assert 235 < stack < 245  # the issue's 240 M a token with half a pair a layer held


def test_the_readers_read_the_runs_evidence_or_nothing():
    cfg = manifest.hf_config(_load("configs", CONFIG), False)
    lens = [l for b in _pool_lengths() for l in b]
    n = float(sum(lens))
    work = dict(tokens=2.0 * n, sum_len_sq=2.0 * sum(l * l for l in lens), elapsed_s=36.0)
    counters = {"train.tokens": n, "train.cells": 262144, "train.moe_pairs_held": 3.1 * n,
                "train.head_cells": 196608, "train.mtp_head_cells": 180224}
    ev = dict(work=work, hf_config=cfg, peaks=dict(bf16_flops_per_s=197e12), chips=1,
              program=dict(counters=counters))
    want = 100.0 * 2 * flops_mla.train_flops(cfg, lens, 3.1 * n, 196608, 180224)["total"] \
        / 36.0 / 197e12
    assert abs(flops_rate_mla.read(ev) - want) < 1e-9 and 5 < want < 60
    # a program without the module's counter: its head ran no cell
    less = {k: v for k, v in counters.items() if k != "train.mtp_head_cells"}
    assert 0 < flops_rate_mla.read(dict(ev, program=dict(counters=less))) < want
    # nothing to read: another family, no counters (this PR's parent), no window, no peak
    assert flops_rate_mla.read(dict(ev, hf_config={"model_type": "qwen2"})) is None
    assert flops_rate_mla.read(dict(ev, program=dict(counters={}))) is None
    assert flops_rate_mla.read(dict(ev, program=None)) is None
    assert flops_rate_mla.read(dict(ev, work=None)) is None
    assert flops_rate_mla.read(dict(ev, peaks=None)) is None
    args = _load("layer_metrics", "train_mtp_head_cells_pct")["args"]
    assert program_counter_ratio.read(ev, **args) == 100.0 * 180224 / 262144
    assert program_counter_ratio.read(dict(ev, program=dict(counters=less)), **args) is None


def test_the_cell_rehearsal_walks_the_whole_path(tmp_path):
    r = rehearse(CELL, tmp_path, 2)
    line = last_line(r)
    check_contract_line(line)
    assert line["counts"]["steps"] >= 2 and line["counts"]["compiles_in_window"] == 0
    # the shares of the chip's peak need a chip's peaks; the counters' ratios do not
    assert {"setup_s", "train_tokens_per_s", "train_pack_density_pct", "train_head_cells_pct",
            "train_mtp_head_cells_pct"} <= set(line["would_report"])
    # float32 at toy widths: the engine and the plain reference agree
    ref = json.loads(next(l for l in r.stdout.splitlines() if "reference check: " in l)
                     .split("reference check: ", 1)[1])
    assert ref["ok"] and len(ref["samples"]) == 3 and ref["worst"] < 1e-3
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert 0 < c["train.mtp_targets"] <= c["train.scored_cells"]
    assert 0 < c["train.mtp_head_cells"] <= c["train.head_cells"] <= c["train.cells"]
    # six expert layers with the module's, top-4 at toy size
    assert c["train.moe_pairs"] == 4 * c["train.tokens"] * 6
    assert 0 < c["train.moe_pairs_held"] < c["train.moe_pairs"]
    assert c["train.attn_active_cells"] > 0
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["kinds"] ==
                            "dense.latent.full.rope,moe.latent.full.rope x5+mtp" for s in dispatch)
    steps = [json.loads(l) for l in open(tmp_path / "out" / "steps.jsonl")]
    assert all(s["ok"] for s in steps)
