"""TPU op library. Env-tunable knobs are snapshotted per engine
construction via snapshot_env_tuning()."""


def snapshot_env_tuning():
    """Validate + pin every AREAL_* op-tuning env var (the CE chunk
    size) in one place. Engines call this once at construction: a
    mid-run retrace then reuses the pinned settings instead of
    re-reading a possibly-mutated environment, and malformed values fail
    at init instead of inside a jit trace."""
    from areal_tpu.ops import loss

    return {"ce_chunk": loss.snapshot_ce_chunk()}
