"""The DES trace pool every cell draws its slices from.

The reference DES (`repro.des`, JAX-free) runs each evaluation benchmark
once, at a fixed length, in parallel worker processes. The pool does not
depend on the seed: `--seed` only chooses which slices of it a run sends.
It is written once per checkout under ``artifacts/bench_pool/`` and loaded
by every later run; its content hash is printed so two checkouts can be
seen to hold the same inputs.

Importing this module imports neither JAX nor the program's simulator, so
a worker process never touches the chip.
"""
from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing
import os
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

POOL_DIR = Path(__file__).resolve().parents[1] / "artifacts" / "bench_pool"


def _des_one(args):
    """Worker: one benchmark through the reference DES (numpy only)."""
    name, n_instructions = args
    from repro.des.o3 import O3Config, O3Simulator
    from repro.des.workloads import get_benchmark

    trace = O3Simulator(O3Config()).run(get_benchmark(name, n_instructions))
    return {f.name: getattr(trace, f.name) for f in dataclasses.fields(trace)}


def _fields_hash(traces: Sequence[dict]) -> str:
    h = hashlib.sha256()
    for t in traces:
        h.update(str(t["name"]).encode())
        for k in sorted(t):
            if k != "name":
                a = np.ascontiguousarray(t[k])
                h.update(f"{k}:{a.dtype.str}:{a.shape}".encode())
                h.update(a.tobytes())
    return h.hexdigest()


def build_pool(benchmarks: Sequence[str], n_instructions: int,
               workers: int) -> List[dict]:
    ctx = multiprocessing.get_context("spawn")
    jobs = [(name, n_instructions) for name in benchmarks]
    with ctx.Pool(max(1, min(workers, len(jobs)))) as pool:
        return pool.map(_des_one, jobs)


def load_pool(benchmarks: Sequence[str], n_instructions: int,
              workers: int = 0, log=print):
    """The pool as a list of trace-field dicts, built on first use.

    Returns (traces, sha256 of their content)."""
    key = hashlib.sha256(
        f"{','.join(benchmarks)}:{n_instructions}".encode()
    ).hexdigest()[:16]
    path = POOL_DIR / f"pool_{key}.npz"
    if path.exists():
        with np.load(path, allow_pickle=False) as z:
            traces = []
            for i, name in enumerate(benchmarks):
                t = {"name": name}
                for k in z.files:
                    pre, _, field = k.partition("/")
                    if pre == str(i):
                        t[field] = z[k]
                traces.append(t)
    else:
        n_workers = workers or min(len(benchmarks), os.cpu_count() or 1)
        log(f"pool: running the DES over {len(benchmarks)} benchmarks x "
            f"{n_instructions} instructions in {n_workers} processes")
        traces = build_pool(benchmarks, n_instructions, n_workers)
        POOL_DIR.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.stem + f".tmp{os.getpid()}.npz")
        arrays: Dict[str, np.ndarray] = {}
        for i, t in enumerate(traces):
            for k, v in t.items():
                if k != "name":
                    arrays[f"{i}/{k}"] = np.asarray(v)
        np.savez(tmp, **arrays)
        os.replace(tmp, path)
        for t, name in zip(traces, benchmarks):
            t["name"] = name
    return traces, _fields_hash(traces)
