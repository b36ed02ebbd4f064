"""How `correct` is decided: the cycles that the timed path returned for a
seeded sample of its workloads, against the plain reference's cycles for
the same workloads (`bench/reference.py`, at the configuration's stated
precision: ``matmul_operands`` in its sizes file).

Numbers compared, each against its own limit from
``bench/limits/<cell>.json``:

- ``max_gap``: the largest |program - reference| / reference over the
  sampled workloads;
- ``pack_gap``: |sum program - sum reference| / sum reference over them;
- ``failed``: workloads or jobs that failed, were refused or never came.

A number passes when it is at most its limit.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np

from bench.traffic import SAMPLE, rng_for


def sample(n: int, k: int, seed: int, must: Sequence[int] = ()) -> list:
    """`k` of indices 0..n-1 drawn from the seed, always with `must`."""
    rng = rng_for(seed, SAMPLE)
    rest = [i for i in rng.permutation(n).tolist() if i not in set(must)]
    return sorted(list(must) + rest[: max(0, k - len(must))])


def gaps(program: Sequence[float], reference: Sequence[float]) -> Dict[str, float]:
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    if p.shape != r.shape or not p.size:
        raise ValueError(f"cannot compare {p.shape} with {r.shape}")
    if not np.isfinite(p).all():
        return {"max_gap": math.inf, "pack_gap": math.inf}
    return {
        "max_gap": float(np.max(np.abs(p - r) / r)),
        "pack_gap": float(abs(p.sum() - r.sum()) / r.sum()),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, dict]):
    """(correct, {name: {"value", "limit"}}) — every limit must hold."""
    out, ok = {}, True
    for name, spec in limits.items():
        value = numbers.get(name, math.inf)
        limit = float(spec["limit"])
        out[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, out
