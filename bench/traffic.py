"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with ``--seed``, into the
slices and arrivals a run sends.

Every seed gets the same work in another order: the same number of slices
per call from each benchmark, the same set of job sizes and the same set of
inter-arrival gaps, permuted by the seed. What the seed changes is which
slice of the pool each request carries and the order of sizes and gaps.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import List, NamedTuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.exists():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    if mix.get("mode") not in ("sweep", "serve"):
        raise ValueError(f"traffic {name}: mode must be 'sweep' or 'serve'")
    return mix


# independent random streams of one seed
CALLS, SCHEDULE, WARM_BUCKETS, WARM, SAMPLE = 1, 2, 3, 4, 5


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator per (seed, stream): any integer seed, however large."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


class Slice(NamedTuple):
    bench: int  # index into the pool
    lo: int  # first instruction
    n: int  # instructions
    lanes: int  # sub-traces it is split into


class Job(NamedTuple):
    due_s: float  # seconds after the window opens
    slice: Slice


def _spread(rng, n_bench: int, count: int) -> np.ndarray:
    """`count` benchmark indices, each benchmark as often as the others
    (to within one), in a seeded order."""
    return rng.permutation(np.arange(count) % n_bench)


def sweep_call(mix: dict, subtrace: int, pool_len: int, n_bench: int,
               seed: int, call: int, stream: int = CALLS) -> List[Slice]:
    """The slices of one closed-loop call: `slices_per_call` slices of
    `slice_instructions`, each split into sub-traces of `subtrace`."""
    n = int(mix["slice_instructions"])
    if n % subtrace:
        raise ValueError(f"slice of {n} is not a whole number of {subtrace}-sub-traces")
    rng = rng_for(seed, stream, call)
    count = int(mix["slices_per_call"])
    benches = _spread(rng, n_bench, count)
    los = rng.integers(0, pool_len - n + 1, size=count)
    return [Slice(int(b), int(lo), n, n // subtrace) for b, lo in zip(benches, los)]


def serve_schedule(mix: dict, subtrace: int, pool_len: int, n_bench: int,
                   seed: int, seconds: float) -> List[Job]:
    """An open-loop schedule of round(rate x seconds) jobs over the window.

    Gaps are the quantiles of an exponential at the mix's rate (a Poisson
    process's gaps), scaled so that they add up to the window, then
    permuted; job sizes are the quantiles of a log-uniform lane count
    between `lanes_min` and `lanes_max`, permuted on their own."""
    rate = float(mix["rate_jobs_per_s"])
    n = max(1, round(rate * seconds))
    rng = rng_for(seed, SCHEDULE)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    due = np.cumsum(rng.permutation(gaps))
    lo2, hi2 = math.log2(mix["lanes_min"]), math.log2(mix["lanes_max"])
    lanes = rng.permutation(np.rint(2.0 ** (lo2 + q * (hi2 - lo2))).astype(int))
    benches = _spread(rng, n_bench, n)
    jobs = []
    for t, ln, b in zip(due, lanes, benches):
        size = int(ln) * subtrace
        lo = int(rng.integers(0, pool_len - size + 1))
        jobs.append(Job(float(t), Slice(int(b), lo, size, int(ln))))
    return jobs


def warm_jobs(mix: dict, subtrace: int, pool_len: int, n_bench: int,
              seed: int, lanes_total: int) -> List[Slice]:
    """Jobs of the mix's largest size whose lanes add up to just past
    `lanes_total` / 2, so that one batch of them lands in the
    `lanes_total` lane bucket (a warm-up of that bucket's program)."""
    per = int(mix["lanes_max"])
    want = lanes_total // 2 + 1
    out, rng = [], rng_for(seed, WARM_BUCKETS, lanes_total)
    while want > 0:
        ln = max(int(mix["lanes_min"]), min(per, want))
        size = ln * subtrace
        b = int(rng.integers(0, n_bench))
        out.append(Slice(b, int(rng.integers(0, pool_len - size + 1)), size, ln))
        want -= ln
    return out
