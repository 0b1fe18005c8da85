"""The sum of some of the program's own counters over `share` times
another (`tracing.count`, returned by `tracing.stop()` under `counters`),
times `scale`: `program_counter_ratio` where the useful work is counted under
several names (the delta rule's forward and backward kernels' cells over
twice the rule's). None where the denominator or any summand is missing (a
program without them, as this PR's parent)."""


def read(evidence, nums, den, share=1.0, scale=100.0):
    c = (evidence.get("program") or {}).get("counters") or {}
    if not c.get(den) or any(n not in c for n in nums):
        return None
    return scale * sum(c[n] for n in nums) / (share * c[den])
