"""Host-clock time of one kind of benchmark-side span, per occurrence:
mean over the window's spans of (the span - the spans named in
`minus` that lie inside it), times `scale`."""


def read(evidence, span, minus=(), scale=1.0):
    spans = evidence.get("spans") or []
    outer = [s for s in spans if s["name"] == span]
    if not outer:
        return None
    total = 0.0
    for o in outer:
        t = o["end"] - o["start"]
        for s in spans:
            if s["name"] in minus and s["start"] >= o["start"] and s["end"] <= o["end"]:
                t -= s["end"] - s["start"]
        total += t
    return scale * total / len(outer)
