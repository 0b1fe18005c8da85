"""One plain probe child: what `jax.devices()` says, asked from a process
that must not touch jax itself.

A chip belongs to one process, so a parent that starts jax workers
(`bench.py`, `serving_http_phase`, `chip_smoke.py`) learns the platform
from a throwaway child that is gone before the workers start. A device
that is not there is a failure, not something to wait for.
"""

from __future__ import annotations

import json
import subprocess
import sys
from typing import Dict

_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))"
)


def probe_devices(timeout_s: float = 120.0) -> Dict:
    """{"platform", "kind", "count"} as jax reports them in a child of
    this process's environment. Raises RuntimeError (with the child's
    output) if no backend comes up within `timeout_s`."""
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"device probe exceeded {timeout_s:.0f}s") from e
    if out.returncode != 0:
        raise RuntimeError(
            f"device probe failed (rc={out.returncode}): "
            f"{(out.stderr or out.stdout)[-1500:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])
