"""chip_smoke.py is the standing proof that the system starts on the
chip. Off the chip it must refuse to call itself a pass; its rehearsal
argument walks the same legs at toy size on the CPU and says that it
proves nothing."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(*args, timeout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_no_argument_no_cpu_path():
    """With jax held to the CPU there is no accelerator: non-zero exit
    and no result line, only the reason on stderr."""
    r = _run(timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr and "platform=cpu" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """A copy with nothing else of the repo beside it cannot run."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_rehearsal_serve_leg_reports_cpu_and_never_ok():
    """The serve leg at toy size: a GenerationServer started through
    worker_main, requests on both sides of a bucket and a chunk, a
    sampled group, a resubmitted qid, and the reference-forward check."""
    r = _run("--rehearse-on-cpu", "--legs", "serve", timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "proves nothing about the chip" in r.stdout
    assert "platform=cpu" in r.stdout
    assert "--- leg serve: ok" in r.stdout
    last = _last_json(r.stdout)
    assert last["ok"] is False and last["rehearsal_legs_ok"] is True
    assert last["device"]["platform"] == "cpu"


@pytest.mark.slow
def test_rehearsal_all_legs():
    """Every leg, including the async loop on a decoupled allocation
    (two servers + an fsdp-2 trainer over four virtual devices): servers
    cut over to a published weight version and later samples carry it."""
    r = _run("--rehearse-on-cpu", timeout=900)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    for leg in ("kernels", "serve", "train", "async"):
        assert f"--- leg {leg}: ok" in r.stdout
    summary = [l for l in r.stdout.splitlines() if l.startswith("summary: ")][-1]
    assert json.loads(summary[len("summary: "):])["claim"] is None
    assert _last_json(r.stdout)["ok"] is False


def test_model_is_the_published_qwen25_1p5b_and_depth_is_derived():
    """No width is cut: the config goes through the repo's own qwen2
    family; only the one-chip trainer's depth is cut, by a byte model."""
    sys.path.insert(0, REPO)
    import chip_smoke

    cfg = chip_smoke.model_config(chip_smoke.QWEN25_1P5B_HF, "bfloat16")
    assert (cfg["hidden_dim"], cfg["n_q_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], cfg["intermediate_dim"]) == (1536, 12, 2, 128, 8960)
    assert cfg["vocab_size"] == 151936 and cfg["n_layers"] == 28
    assert cfg["attn_bias"] and cfg["tied_embeddings"]
    assert cfg["param_dtype"] == cfg["compute_dtype"] == "bfloat16"
    # 12 bytes a parameter in 10 GB: 233M embedding + 12 x 46.8M layers.
    assert chip_smoke.train_depth(cfg) == 12
    # A model that fits whole keeps its depth.
    toy = chip_smoke.model_config(chip_smoke.TOY_HF, "float32")
    assert chip_smoke.train_depth(toy) == toy["n_layers"]


def test_judge_ran_flags_a_hidden_device_and_shared_chips():
    """The checks behind a leg's verdict, on canned `areal-ran` lines."""
    sys.path.insert(0, REPO)
    import chip_smoke

    def line(**f):
        return "ts ran INFO: areal-ran " + json.dumps(f)

    def dev(worker, chips, pid):
        return line(kind="devices", worker=worker, pid=pid, platform="tpu",
                    device_kind="TPU v5 lite", count=len(chips.split(",")),
                    ids=[0], coords=[[0, 0, 0]], visible_chips=chips,
                    native_host_ops=True)

    def use(worker, peaks):
        return line(kind="usage", worker=worker, peak_hbm_bytes=peaks, compile_s=1.0)

    ctx = dict(platform="tpu", rehearsal=False)
    good = "\n".join([
        dev("generation_server/0", "0", 1), use("generation_server/0", [7e9]),
        dev("model_worker/0", "2,3", 2), use("model_worker/0", [1e10, 1e10]),
        line(kind="attn_impl", requested="auto", ran="splash", why="w", t=128),
        line(kind="paged_decode_impl", requested="auto", ran="kernel", why="w"),
    ])
    summary, problems = chip_smoke.judge_ran(
        good, ctx, "splash", "kernel", ["model_worker", "generation_server"])
    assert problems == []
    assert summary["owned_chips"] == {"generation_server/0": [0],
                                      "model_worker/0": [2, 3]}
    bad = "\n".join([
        dev("generation_server/0", "0", 1), use("generation_server/0", [7e9]),
        dev("model_worker/0", "0,1", 2), use("model_worker/0", [1e10]),  # 1 of 2
        line(kind="attn_impl", requested="auto", ran="reference", why="w", t=64),
    ])
    _, problems = chip_smoke.judge_ran(bad, ctx, "splash", None, ["model_worker"])
    text = "\n".join(problems)
    assert "chip 0 owned by both" in text
    assert "peak HBM" in text
    assert "attention auto resolved to ['reference']" in text
