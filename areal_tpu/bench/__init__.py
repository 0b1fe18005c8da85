"""Benchmark banking.

One wedged XLA compile (or one PJRT crash) must not lose a whole run,
so the bench is decomposed into independently-banked *phases*:

- ``phases``    phase registry: priority, estimated compile/measure
                cost, minimal viable steady-state window
- ``runner``    one phase per subprocess with a hard deadline; a wedged
                compile kills one phase, not the run; compile (warm the
                persistent XLA cache) and measure are separate passes
- ``devices``   the one plain probe child a jax-free parent asks for
                the platform
- ``bank``      atomic per-phase JSON records (tmp+rename) carrying an
                attestation block (device/topology/versions/git sha and
                ``driver_verified``) so on-chip and CPU-proxy evidence
                can never be conflated
- ``report``    assembles a ``BENCH_rNN``-style report from the bank,
                folding in proxy evidence (pack density, prefetch
                overlap, multichip dryrun) explicitly labeled as
                non-driver-verified

``bench.py`` at the repo root is a thin CLI over this package.

No eager submodule imports here: the runner child executes as
``python -m areal_tpu.bench.runner`` and must not find itself already
half-imported by its own package init.
"""
