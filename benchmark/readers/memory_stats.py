"""A key of the fullest chip's `memory_stats()` after the window."""


def read(evidence, key="peak_bytes_in_use", scale=1e-9):
    m = evidence.get("memory")
    if not m or key not in m:
        return None
    return m[key] * scale
