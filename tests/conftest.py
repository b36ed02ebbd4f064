"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on the real single
CPU device; only launch/dryrun.py forces 512 virtual devices."""
import os

# JAX's persistent compile cache stays off in the tests and in every process
# they start: `repro` entry points (cli.main) turn it on for real runs.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

from repro.des.o3 import O3Config, O3Simulator  # noqa: E402
from repro.des.workloads import get_benchmark  # noqa: E402


def synth_fields(T, seed, store_share=0.3, labels=None):
    """Random integer trace fields (`features.FIELDS`) of T instructions:
    ``store_share`` of them stores, pc and data addresses drawn from 50
    values so that dependency keys repeat. ``labels(rng, T)`` gives the
    (T, 3) DES latencies (default: fetch 0-3, exec 1-11, store 1-5)."""
    from repro.des.isa import N_OP_CLASSES, Op

    rng = np.random.default_rng(seed)
    op = rng.integers(0, N_OP_CLASSES, T)
    op = np.where(op == Op.STORE, Op.INT_ALU, op)
    op = np.where(rng.random(T) < store_share, Op.STORE, op)
    lab = labels(rng, T) if labels else np.stack([
        rng.integers(0, 4, T),
        rng.integers(1, 12, T),
        rng.integers(1, 6, T),
    ], axis=1)
    return {
        "op": op.astype(np.int8),
        "src": rng.integers(-1, 128, (T, 8)).astype(np.int16),
        "dst": rng.integers(-1, 128, (T, 6)).astype(np.int16),
        "mispred": rng.random(T) < 0.1,
        "fetch_level": rng.integers(0, 4, T).astype(np.int8),
        "fetch_tw": rng.integers(0, 3, (T, 3)).astype(np.int8),
        "fetch_wb": rng.integers(0, 2, (T, 2)).astype(np.int8),
        "data_level": rng.integers(0, 4, T).astype(np.int8),
        "data_tw": rng.integers(0, 3, (T, 3)).astype(np.int8),
        "data_wb": rng.integers(0, 2, (T, 3)).astype(np.int8),
        "pc": rng.integers(0, 50, T),
        "addr": rng.integers(0, 50, T),
        "fetch_lat": lab[:, 0], "exec_lat": lab[:, 1], "store_lat": lab[:, 2],
    }


def synth_arrays(T, seed):
    """A tiny synthetic trace-arrays dict (teacher-forced label replay) —
    the serving tests' fast-tier workload; the machinery under test is
    identical for predictor models. The host expansion of random integer
    fields: a trace_arrays dict the service can invert."""
    from repro.core import features as F

    return F.expand(synth_fields(T, seed))


@pytest.fixture(scope="session")
def small_o3():
    return O3Config()


@pytest.fixture(scope="session")
def small_trace(small_o3):
    """A 6k-instruction mixed trace through the DES (session-cached)."""
    sim = O3Simulator(small_o3)
    return sim.run(get_benchmark("mlb_mixed", 6000))


@pytest.fixture(scope="session")
def loop_trace(small_o3):
    sim = O3Simulator(small_o3)
    return sim.run(get_benchmark("sim_loop", 4000))
