"""The metric PR 62 adds (`train_attn_in_place_pct`): its file and entry,
its reader over a run's evidence, and the host's counter against what
the device traces at each listed cell's widths. CPU only; nothing here
says where an entry stands in a list."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.readers import program_counter_ratio
from tests.benchmark.test_run_rehearsal import check_contract_line, last_line, rehearse

MAN = manifest.load_manifest()
METRIC = "train_attn_in_place_pct"
# cell -> (what the metric reads there, the cell's one micro-batch shape)
CELLS = {
    "trinity-d5e16-train-ppo-long": (0.0, (1, 16384)),  # heads of 128: the control
    "joyai-d6e16-train-ppo-long": (100.0, (1, 16384)),
    "xing4-d5e8-train-ppo-8k": (100.0, (1, 8192)),
    "kimilinear-d5e8-train-ppo-long": (100.0, (1, 16384)),
}


def _load(kind, name):
    with open(os.path.join(manifest.BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def _counts(cell, rehearsal=False):
    """The cell's `TrainCounts` at the widths it runs (or rehearses) at,
    where splash is what runs: the chip's choice."""
    from areal_tpu.engine.train_counts import TrainCounts
    from benchmark.model import transformer_config

    hf = manifest.hf_config(_load("configs", _load("cells", cell)["config"]), rehearsal)
    cfg = transformer_config(hf, "bfloat16")
    return TrainCounts(cfg=cfg, mesh=types.SimpleNamespace(size=1), attn_impl="splash",
                       row_len_multiple=128, n_row_multiple=1, mtp=cfg.mtp is not None,
                       n_moe_layers=0)


def test_the_metric_is_listed_for_the_cells_its_file_reads_it_in():
    entry = next(m for m in MAN["per_layer"] if m["name"] == METRIC)
    f = _load("layer_metrics", METRIC)
    assert entry["workloads"] == f["cells"] == list(CELLS)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        k: f[k] for k in ("unit", "better", "source", "layer", "moves")} == dict(
            unit="%", better="higher", source="program_counter", layer="kernels, training",
            moves="train_tokens_per_s")
    assert f["reader"] == "program_counter_ratio"
    assert f["args"] == dict(num="train.attn_cells_in_place", den="train.attn_cells", scale=100.0)
    e2e = next(m for m in MAN["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert set(CELLS) <= set(e2e["workloads"])
    assert [m["name"] for m in manifest.layer_metrics_for("q15d12-train-ppo")].count(METRIC) == 0


def test_the_reader_reads_the_counters_or_nothing():
    args = _load("layer_metrics", METRIC)["args"]
    counters = {"train.attn_cells": 262144, "train.attn_cells_in_place": 262144}
    ev = dict(program=dict(counters=counters))
    assert program_counter_ratio.read(ev, **args) == 100.0
    assert program_counter_ratio.read(
        dict(program=dict(counters=dict(counters, **{"train.attn_cells_in_place": 0}))),
        **args) == 0.0
    # the parent's program has no such counter: nothing, and the line leaves the metric out
    assert program_counter_ratio.read(
        dict(program=dict(counters={"train.attn_cells": 262144})), **args) is None
    assert program_counter_ratio.read(dict(program=None), **args) is None
    assert manifest.read_layer_metrics(
        "joyai-d6e16-train-ppo-long", dict(program=dict(counters=counters)))[METRIC] == {
            "value": 100.0, "unit": "%"}


@pytest.mark.parametrize("cell", CELLS)
def test_the_hosts_count_is_the_devices_rule_at_the_cells_widths(cell):
    """`train.attn_cells_in_place` at the cell's shape and widths against
    what the attention call traces there: sequence-minor operands of the
    pair kernels, `[heads, hd, T]`, or none; and at its rehearsal's toy
    rows (under 2,048 cells: the static kernels) none, whatever the head."""
    from areal_tpu.ops.attention import splash_packed_attention

    want, (rows, t) = CELLS[cell]
    counts = _counts(cell)
    cfg = counts.cfg
    seg = np.zeros((1, rows, t), np.int32)
    seg[..., : t // 2] = 1
    said, attrs = counts._attention(seg)
    assert said["train.attn_cells"] == rows * t
    assert 100.0 * said["train.attn_cells_in_place"] / said["train.attn_cells"] == want
    assert attrs["in_place"] == (len(counts.qk_dims) if want else 0) > (0 if want else -1)

    hd = set(counts.qk_dims)
    assert hd == ({192} if want else {128})
    (hd,), h = hd, cfg.n_q_heads
    qk = jax.ShapeDtypeStruct((rows, t, h, hd), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((rows, t, h, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((rows, t), jnp.int32)
    text = str(jax.make_jaxpr(lambda q, k, v, s: splash_packed_attention(
        q, k, v, s, s, interpret=True))(qk, qk, v, ids))
    assert "splash_pairs_fwd" in text
    assert (f"bf16[{h},{hd},{t}]" in text) == bool(want)

    toy = _counts(cell, rehearsal=True)
    short = np.ones((2, 1, 256), np.int32)
    assert toy._attention(short)[0]["train.attn_cells_in_place"] == 0
    # and where the einsum reference runs (this sandbox's CPU) nothing reads in place
    toy.attn_impl = counts.attn_impl = "reference"
    assert counts._attention(seg)[0]["train.attn_cells_in_place"] == 0


def test_a_latent_cells_rehearsal_carries_the_counter_to_the_line(tmp_path):
    """End to end on the CPU: the einsum reference runs here, so no kernel
    reads an operand in place; the counter and the span's attribute are
    there all the same, for the metric to read 0 from (100 on the chip)."""
    line = last_line(rehearse("xing4-d5e8-train-ppo-8k", tmp_path, 2))
    check_contract_line(line)
    assert METRIC in line["would_report"]
    prog = json.load(open(tmp_path / "out" / "program.json"))
    c = prog["counters"]
    assert c["train.attn_cells_in_place"] == 0 < c["train.attn_cells"]
    dispatch = [s for s in prog["spans"] if s["name"] == "train.dispatch"]
    assert dispatch and all(s["attrs"]["in_place"] == 0 for s in dispatch)
