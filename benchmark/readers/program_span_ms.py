"""Host-clock time of spans the program records itself
(`areal_tpu.base.tracing`), per step: the mean over the traced steps
(traces that hold a span named `root`) of the summed duration of the
spans named in `spans`, in milliseconds. Evidence: `program`, what
`tracing.stop()` returned (`{"spans": [{"name", "trace", "start_ns",
"end_ns", ...}], ...}`). None when the program recorded no step (a
program without the control, or a run that traced nothing)."""


def read(evidence, spans, root="ppo.train_step"):
    by_trace = {}
    for s in (evidence.get("program") or {}).get("spans") or []:
        by_trace.setdefault(s["trace"], []).append(s)
    steps = [sp for sp in by_trace.values() if any(s["name"] == root for s in sp)]
    if not steps:
        return None
    wanted = set(spans)
    per_step = [sum(s["end_ns"] - s["start_ns"] for s in sp if s["name"] in wanted)
                for sp in steps]
    return sum(per_step) / len(per_step) / 1e6
