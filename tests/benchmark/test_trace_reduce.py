"""The reduction from a trace to busy time, idle time, category shares
and labelled idle gaps, on a hand-made trace with known answers and on a
small slice recorded on the chip (tests/benchmark/data/)."""

import json
import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    dev = [["%while.1 = (s32[]) while((s32[]) %t), body=%b", 1000.0, 8000.0, ""],
           ["%convolution_add_fusion.2 = bf16[8,8]{1,0} fusion(bf16[8] %copy.1), kind=kOutput", 1000.0, 3000.0, ""],
           ["%splash_mqa_fwd.3 = bf16[8] custom-call(bf16[8] %bitcast.1)", 5000.0, 2000.0, ""],
           ["%copy.4 = bf16[8]{0} copy(bf16[8]{0} %p)", 20000.0, 1000.0, ""],
           ["%all-reduce.5 = f32[8] all-reduce(f32[8] %x)", 60000.0, 4000.0, ""]]
    host = [["bench/trace_window", 0.0, 100000.0, ""],
            ["bench/train_step", 500.0, 50000.0, ""],
            ["bench/train_batch", 9200.0, 30000.0, ""],
            ["PjitFunction(step)", 9500.0, 9000.0, ""],
            ["np.asarray(jax.Array)", 25000.0, 30000.0, ""]]
    for e in dev + host:  # tens of microseconds, so that gaps get a label each
        e[1], e[2] = e[1] * 10, e[2] * 10
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": dev},
                                            {"name": "Steps", "events": [["0", 0.0, 900000.0, ""]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def test_op_label_and_category_come_from_the_instruction_not_its_operands():
    name = ("%splash_mqa_dkv_segmented.11 = (f32[3,2,512,128]{3,2,1,0:T(8,128)}, "
            "bf16[3]{0:T(8,128)(2,1)S(1)}) custom-call(s8[1] %copy-done.78, bf16[3] %bitcast.4)")
    assert tr.op_label(name) == ("splash_mqa_dkv_segmented.11", "custom-call")
    assert tr.categorize(name) == "attention"
    conv = "%convolution_bitcast_fusion.3 = bf16[16,16]{1,0} fusion(bf16[1] %copy.1), kind=kOutput"
    assert tr.categorize(conv) == "gemm"
    assert tr.base_name(tr.op_label(conv)[0]) == "convolution_bitcast_fusion"
    loop = "%fusion.12 = f32[8]{0} fusion(f32[8] %copy-done.1, f32[8] %bitcast.2), kind=kLoop"
    assert tr.categorize(loop) == "fusion"
    assert tr.categorize("%copy-start.1 = (s32[5]) copy-start(s32[5] %p)") == "memory"
    assert tr.categorize("paged_attention") == "attention"  # a bare name, off the TPU


def test_self_time_does_not_count_a_while_body_twice():
    st = dict((n.split(" = ")[0], s) for n, s, _ in tr.self_times(
        [tuple(e) for e in hand_made()["planes"][0]["lines"][0]["events"]]))
    assert st["%while.1"] == 30000.0 and st["%convolution_add_fusion.2"] == 30000.0
    assert st["%splash_mqa_fwd.3"] == 20000.0


def test_hand_made_trace_gives_the_known_numbers():
    r = tr.reduce_trace(hand_made())
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx((8000 + 1000 + 4000) * 1e-8)
    share = r["category_share"]
    assert share["gemm"] == pytest.approx(3 / 13) and share["attention"] == pytest.approx(2 / 13)
    assert share["fusion"] == pytest.approx(3 / 13)  # the while's own 30 us
    assert share["memory"] == pytest.approx(1 / 13) and share["collective"] == pytest.approx(4 / 13)
    assert sum(share.values()) == pytest.approx(1.0)
    assert [n for n, _ in r["device_ops"]][0] == "all-reduce"
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(1000e-6 - r["busy_s"])
    # 90-200 us: inside train_step > train_batch, the host was in PjitFunction(step)
    assert gaps["train_batch|PjitFunction(step)"] == pytest.approx(110e-6)
    assert gaps["train_step|np.asarray(jax.Array)"] == pytest.approx(390e-6)
    assert gaps["-|-"] == pytest.approx(360e-6)


def test_no_device_plane_means_nothing_to_read():
    t = hand_made()
    t["planes"] = t["planes"][1:]
    assert tr.reduce_trace(t) is None


def test_recorded_slice_agrees_with_a_brute_force_count():
    with open(os.path.join(DATA, "v5e_decode_slice.json")) as f:
        trace = json.load(f)
    r = tr.reduce_trace(trace)
    ops = next(l for l in trace["planes"][0]["lines"] if l["name"] == "XLA Ops")["events"]
    win = trace["planes"][1]["lines"][0]["events"][0]
    w0, w1 = int(win[1]), int(win[1] + win[2])
    grid = np.zeros(w1 - w0, bool)  # one cell a nanosecond
    for _, s, d, _ in ops:
        grid[int(s) - w0: int(s + d) - w0] = True
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-9, rel=1e-4)
    assert 0.93 < r["busy_s"] / r["window_s"] < 0.96
    # the copies of the KV pool take most of this millisecond; no matmul is named
    assert r["category_share"]["memory"] > 0.6
    assert sum(r["category_share"].values()) == pytest.approx(1.0)
    assert abs(sum(v for _, v in r["idle_gaps"]) - (r["window_s"] - r["busy_s"])) < 1e-9
    labels = dict(r["idle_gaps"])
    assert labels["wait_reply|PjitFunction(paged_decode_block)"] == pytest.approx(20e-6)
    assert "-|np.asarray(jax.Array)" in labels
    top = [n for n, _ in r["device_ops"]]
    assert "copy_bitcast_fusion" in top and "fusion" in top
    # the hand-added parent keeps only its own two nanoseconds
    st = {n.split(" = ")[0]: v for n, v, _ in tr.self_times([tuple(e) for e in ops])}
    assert st["%while.3"] == pytest.approx(6.0)  # its margins and the gaps between its children
