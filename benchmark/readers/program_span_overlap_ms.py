"""Host-clock time of one kind of span the program records itself
(`areal_tpu.base.tracing`), split by what else the same thread was inside
meanwhile. Per traced step (a span named `root`, as `program_span_ms`
finds its steps): the spans named `within` on the root's thread, clipped
to the root; of them the whole (no `spans`), the part that spans named in
`spans` on that thread cover, or with `rest` the part none of them covers.
The mean a step, in milliseconds. A `within` span lies in the trace of the
step that ended it and may have begun in the step before: each step takes
the part inside its own root, under the leaves that step ran. Evidence:
`program`, what `tracing.stop()` returned. None when the program recorded
no step or no `within` span (a program without the span, or a run that
traced nothing)."""


def _covered_ns(intervals, lo, hi):
    """Length of the union of `intervals` inside [lo, hi]."""
    total, reach = 0, lo
    for start, end in sorted(intervals):
        start, end = max(start, reach), min(end, hi)
        if end > start:
            total += end - start
            reach = end
    return total


def read(evidence, within, spans=(), rest=False, root="ppo.train_step"):
    recorded = (evidence.get("program") or {}).get("spans") or []
    roots = [s for s in recorded if s["name"] == root]
    inside = [s for s in recorded if s["name"] == within]
    if not roots or not inside:
        return None
    listed = set(spans)
    cover = {}
    for s in recorded:
        if s["name"] in listed:
            cover.setdefault(s.get("tid"), []).append((s["start_ns"], s["end_ns"]))
    total = 0
    for r in roots:
        for w in inside:
            lo, hi = max(w["start_ns"], r["start_ns"]), min(w["end_ns"], r["end_ns"])
            if w.get("tid") != r.get("tid") or hi <= lo:
                continue
            under = _covered_ns(cover.get(w.get("tid"), ()), lo, hi)
            total += (hi - lo) - under if rest else (under if listed else hi - lo)
    return total / len(roots) / 1e6
