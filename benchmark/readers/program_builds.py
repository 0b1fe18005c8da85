"""What the program built during set-up, from its own build records
(`areal_tpu.base.tracing.builds`, returned by `tracing.stop()` under
`builds`: one record a stage jax ran for a program, `phase` one of
`trace`, `lower`, `compile`, `cache_load`, `start_ns` / `end_ns` on
`time.monotonic_ns`, `cache_hit` true, false or null).

Set-up ends where the first `train_step` span of the run's own spans
(`evidence["spans"]`, `time.monotonic()`: the same clock) starts; a
record counts if it ended before that. `what`:

- `seconds`: the records' summed duration, over `phases`;
- `programs`: how many records there are, over `phases`;
- `cache_hit_pct`: 100 x hits / (hits + misses) of the persistent
  cache, over records whose `cache_hit` is not null.

None when the program returned no `builds` (a program without the
listener), when no step ran, or for a share of nothing.
"""


def read(evidence, what, phases=("trace", "lower", "compile", "cache_load")):
    builds = (evidence.get("program") or {}).get("builds")
    steps = [s["start"] for s in evidence.get("spans") or []
             if s["name"] == "train_step"]
    if builds is None or not steps:
        return None
    window_ns = min(steps) * 1e9
    setup = [b for b in builds if b["end_ns"] <= window_ns and b["phase"] in phases]
    if what == "seconds":
        return sum(b["end_ns"] - b["start_ns"] for b in setup) / 1e9
    if what == "programs":
        return len(setup)
    if what == "cache_hit_pct":
        answers = [b["cache_hit"] for b in setup if b["cache_hit"] is not None]
        return 100.0 * sum(answers) / len(answers) if answers else None
    raise ValueError(f"program_builds cannot read {what!r}")
