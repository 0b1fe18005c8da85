"""The configuration as the program takes it, and the comparison with the
plain reference that decides `correct`."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, List

import numpy as np


def transformer_config(hf: Dict[str, Any], dtype: str):
    """TransformerConfig through the repo's own HF family (the route
    `chip_smoke.model_config` proved on the chip)."""
    from areal_tpu.models.hf import family_from_hf_config

    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    cfg.param_dtype = cfg.compute_dtype = dtype
    return cfg


def transformer_config_kwargs(hf: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    return dataclasses.asdict(transformer_config(hf, dtype))


def compare_with_reference(params, hf: Dict[str, Any], reference: str,
                           samples: List[Dict[str, Any]], tol: Dict[str, float],
                           pad_to: int) -> Dict[str, Any]:
    """Each sample: name, token_ids, first (index into the next-token
    logprob array where `got` starts), got (the system's logprobs).
    `tol` holds two limits on the absolute difference against the
    reference: `max` for any one position (a wrong mask, segment or
    position moves a logprob by about 1) and `mean` for a sequence (a
    loss of precision moves every position a little)."""
    ref = importlib.import_module(f"benchmark.reference.{reference}")
    rows, worst, worst_mean = [], 0.0, 0.0
    for s in samples:
        want = ref.next_token_logprobs(params, hf, s["token_ids"], pad_to=pad_to)
        got = np.asarray(s["got"], np.float32)
        want = want[s["first"]: s["first"] + len(got)]
        diff = np.abs(want - got)
        err, mean = (float(diff.max()), float(diff.mean())) if len(got) else (
            float("inf"), float("inf"))
        worst, worst_mean = max(worst, err), max(worst_mean, mean)
        rows.append(dict(name=s["name"], positions=len(s["token_ids"]),
                         compared=len(got), max_abs_logprob_err=err,
                         mean_abs_logprob_err=mean))
    ok = bool(samples) and worst <= tol["max"] and worst_mean <= tol["mean"]
    return dict(ok=ok, tol=tol, worst=worst, worst_mean=worst_mean, samples=rows)
