"""`scripts/stall_hunt.py`: its report over a run's files (no run here)."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _script():
    spec = importlib.util.spec_from_file_location(
        "stall_hunt", os.path.join(ROOT, "scripts", "stall_hunt.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_long_step_is_laid_to_the_pause_both_clocks_saw(tmp_path):
    # Three steps a batch; step 2 waits 3 s longer for its stats, and both
    # clocks stood still inside it; a pause between two runs is no step's.
    steps, spans, t = [], [], 100.0
    for i in range(6):
        wait = 4.0 if i == 2 else 1.0
        steps.append(dict(step=i, batch=i % 2, start=t, end=t + wait + 0.1))
        spans.append(dict(name="train.fetch_stats", start_ns=int((t + 0.05) * 1e9),
                          end_ns=int((t + 0.05 + wait) * 1e9)))
        spans.append(dict(name="jit.trace", start_ns=int(t * 1e9), end_ns=int(t * 1e9)))
        t += wait + 0.1
    (tmp_path / "rl_trace").mkdir()
    with open(tmp_path / "rl_trace" / "w.jsonl", "w") as f:
        f.write(json.dumps(dict(kind="header")) + "\n")
        f.writelines(json.dumps(s) + "\n" for s in spans)
    with open(tmp_path / "steps.jsonl", "w") as f:
        f.writelines(json.dumps(s) + "\n" for s in steps)
    json.dump([[102.5, 3.0]], open(tmp_path / "thread.json", "w"))
    with open(tmp_path / "pauses.jsonl", "w") as f:
        f.write(json.dumps([50.0, 5.0]) + "\n" + json.dumps([102.6, 2.9]) + "\n")

    *long, window = _script().report(str(tmp_path), str(tmp_path / "steps.jsonl"))
    assert [l["step"] for l in long] == [2]
    assert abs(long[0]["over_median"] - 3.0) < 1e-6
    got, usual = long[0]["spans"]["train.fetch_stats"]
    assert abs(got - 4.0) < 1e-6 and abs(usual - 1.0) < 1e-6
    assert len(long[0]["thread"]) == 1 and len(long[0]["machine"]) == 1
    assert window["steps"] == 6 and len(window["machine_pauses_in_window"]) == 1
