"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and layer metric loads and cross-references, and the manifest
keeps to the contract's shape (names, units, limits)."""

import fnmatch
import json
import os

import pytest

from benchmark import manifest

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
CONFIGS = [c["name"] for c in MAN["configs"]]
E2E = {m["name"]: m for m in MAN["end_to_end"]}
LAYER = {m["name"]: m for m in MAN["per_layer"]}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state_size", "proj",
               "head_dim", "expansion", "experts_per_tok")


# config.json as published, by source: the shaping keys are the literal
# chip_smoke.py proved on the chip (PR 21).
PUBLISHED = {
    "https://huggingface.co/Qwen/Qwen2.5-1.5B-Instruct/blob/main/config.json": dict(
        model_type="qwen2", hidden_size=1536, intermediate_size=8960,
        num_hidden_layers=28, num_attention_heads=12, num_key_value_heads=2,
        vocab_size=151936, max_position_embeddings=32768, hidden_act="silu",
        rms_norm_eps=1e-6, rope_theta=1000000.0, tie_word_embeddings=True,
        torch_dtype="bfloat16"),
}


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_manifest_has_exactly_the_contract_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer", "trace_in_run"}
    assert MAN["trace_in_run"] is True  # the harness takes --trace 2
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert MAN["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(MAN["workloads"]) <= 24 and 1 <= len(MAN["configs"]) <= 24
    assert 1 <= len(MAN["end_to_end"]) <= 16 and 1 <= len(MAN["per_layer"]) <= 128


def test_names_are_unique():
    for group in (CELLS, CONFIGS, list(E2E) + list(LAYER)):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_matches_its_entry_and_resolves(cell):
    entry = next(w for w in MAN["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert manifest.NAME_RE.match(cell) and manifest.NAME_RE.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    c = manifest.load_cell(cell)
    assert (c["config"], c["traffic"], c["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert entry["config"] in CONFIGS
    runner = c["traffic_file"]["runner"]
    assert os.path.isfile(os.path.join(manifest.BENCH_DIR, "runners", f"{runner}.py"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH_DIR, "reference",
        f"{c['config_file']['benchmark']['reference']}.py"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    e2e = [n for n, m in E2E.items() if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in cells_of(m) for m in LAYER.values())


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_holds_the_published_widths(config):
    entry = next(c for c in MAN["configs"] if c["name"] == config)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{config}.json"
    assert entry["source"].startswith("https://huggingface.co/Qwen/")
    assert any(w["config"] == config for w in MAN["workloads"])
    for key in entry["reduced"]:
        assert manifest.NAME_RE.match(key)
        assert not any(w in key for w in WIDTH_WORDS) and not key.endswith(("_dim", "_rank"))
    with open(os.path.join(manifest.REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert sorted(cfg["benchmark"]["reduced"]) == sorted(entry["reduced"])
    assert cfg["benchmark"]["source"] == entry["source"]
    # Against the published file of its source, only `reduced` differs.
    ref = PUBLISHED[entry["source"]]
    assert {k for k in ref if ref[k] != cfg.get(k)} == set(entry["reduced"])
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128


@pytest.mark.parametrize("metric", list(E2E))
def test_end_to_end_metric_entry(metric):
    m = E2E[metric]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert manifest.NAME_RE.match(metric) and manifest.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(cells_of(m)) <= set(CELLS)


@pytest.mark.parametrize("metric", list(LAYER))
def test_layer_metric_entry_matches_its_file_and_reader(metric):
    m = LAYER[metric]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert manifest.NAME_RE.match(metric) and manifest.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{metric}.json")) as f:
        d = json.load(f)
    for k in ("name", "unit", "better", "source", "layer", "moves"):
        assert d[k] == m[k], k
    assert sorted(cells_of(m)) == sorted(
        c for c in CELLS if any(fnmatch.fnmatchcase(c, g) for g in d["cells"]))
    assert hasattr(manifest.load_reader(d["reader"]), "read")
    # The metric it should move is reported in every cell where this one is.
    moved = E2E[m["moves"]]
    assert set(cells_of(m)) <= set(cells_of(moved))


def test_every_file_under_the_paths_is_named_from_allowed_characters():
    import re

    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MAN["paths"]:
        for root, dirs, files in os.walk(os.path.join(manifest.REPO, path)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".jax_cache", "out")]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(root, f), manifest.REPO)), f


def test_peaks_table_names_its_source_and_the_v5e():
    peaks = manifest.load_peaks()
    assert "Google Cloud documentation" in peaks["source"]
    v5e = manifest.device_peaks("TPU v5 lite", peaks)
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        manifest.device_peaks("a chip nobody listed", peaks)


LAUNCHER_KEYS = ["attn_impl", "remat", "row_len_multiple", "max_row_len",
                 "prefetch_depth", "stats_fetch_interval"]


@pytest.mark.parametrize("key", LAUNCHER_KEYS)
def test_train_cell_runs_the_launchers_engine_defaults(key):
    """The cell stands for what deployments run: every engine setting is
    the default a launcher hands to JaxTrainBackend (a bool remat means
    "full")."""
    import dataclasses

    from areal_tpu.engine.factories import JaxTrainBackend

    default = {f.name: f.default for f in dataclasses.fields(JaxTrainBackend)}[key]
    if key == "remat":
        default = {True: "full", False: "none"}[default]
    assert manifest.load_cell("q15d12-train-ppo")["engine"][key] == default
