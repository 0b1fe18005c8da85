"""The longest whole span of one kind in the window, host clock."""


def read(evidence, span, scale=1.0):
    d = [s["end"] - s["start"] for s in evidence.get("spans") or []
         if s["name"] == span]
    return scale * max(d) if d else None
