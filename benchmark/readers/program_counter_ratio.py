"""One of the program's own counters over another (`tracing.count`,
returned by `tracing.stop()` under `counters`), times `scale`: useful
work over attempted work, counted where the work happens."""


def read(evidence, num, den, scale=100.0):
    c = (evidence.get("program") or {}).get("counters") or {}
    if not c.get(den) or num not in c:
        return None
    return scale * c[num] / c[den]
