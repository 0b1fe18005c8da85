"""Share of device op time (self time, so nothing counts twice) that the
trace reduction classes under one category, in percent."""


def read(evidence, category):
    t = evidence.get("trace")
    if not t:
        return None
    return 100.0 * t["category_share"].get(category, 0.0)
