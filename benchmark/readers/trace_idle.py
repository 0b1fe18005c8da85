"""Device idle share of the traced window, in percent: 1 - union of the
device op intervals / window, averaged over the chips used."""


def read(evidence):
    t = evidence.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
