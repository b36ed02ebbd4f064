"""TX6, the zoo's attention predictor, against the benchmark's plain
reference (``bench/configs/tx6.py``), on seeded weights from its own
`init`, at a small size on the CPU: the forward pass, the FLOP count, and
the cycles of a whole `SimNet.simulate_many` pack.
"""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, reference
from bench.manifest import load_module
from bench.traffic import Slice
from repro.core.api import SimNet
from repro.core.predictor import PredictorConfig, apply_raw, inference_mflops
from repro.core.simulator import SimConfig
from repro.des.o3 import O3Config, O3Simulator
from repro.des.trace import Trace
from repro.des.workloads import get_benchmark

CONFIG = Path(__file__).resolve().parents[1] / "bench" / "configs" / "tx6.json"
SIZES = json.loads(CONFIG.read_text())
# steps per lane of the control test (the cell runs 256): enough for the
# control to show, few enough for seconds on the CPU
STEPS = 64
SMALL = dict(SIZES["predictor"], tx_dim=16, tx_heads=4, tx_layers=2, ctx_len=8)


@pytest.fixture(scope="module")
def tx6():
    return load_module(CONFIG.with_suffix(".py"))


def _pcfg(p):
    return PredictorConfig(**dict(p, channels=tuple(p["channels"])))


def test_forward_matches_apply_raw(tx6):
    params = tx6.init(jax.random.PRNGKey(3), SMALL)
    x = jax.random.uniform(jax.random.PRNGKey(4), (16, tx6.seq_padded(SMALL), 50))
    with jax.default_matmul_precision("highest"):
        want = apply_raw(params, x, _pcfg(SMALL))
    got = tx6.forward(params, x, reference.dot_f32, SMALL)
    # both sides in float32 with exact products; only the order of the
    # reductions (softmax, RMS, mean, matmul sums) may differ: a few ulp of
    # outputs of magnitude ~1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_flops_match_the_program_count(tx6):
    p = SIZES["predictor"]
    # the program's count is in MFLOP (divided by 1e6): equal to rounding
    want = 2e6 * inference_mflops(_pcfg(p))
    assert tx6.flops_per_instruction(p) == pytest.approx(want, rel=1e-12)
    assert tx6.attention_flops_per_instruction(p) == 6 * 2 * 2 * 65 * 65 * 64
    assert tx6.attention_bytes_per_instruction(p) == 399_360


def test_simulate_many_matches_the_reference(tx6):
    sim = dict(SIZES["sim"], ctx_len=SMALL["ctx_len"])
    params = tx6.init(jax.random.PRNGKey(11), SMALL)
    des = O3Simulator(O3Config())
    pool = []
    for name in ("sim_loop", "sim_chase_small", "mlb_stream"):
        t = des.run(get_benchmark(name, 1024))
        pool.append({k: getattr(t, k) for k in Trace.__dataclass_fields__})
    slices = [Slice(0, 0, 512, 4), Slice(1, 256, 512, 4), Slice(2, 128, 768, 6),
              Slice(0, 512, 256, 2)]
    sn = SimNet(params=params, pcfg=_pcfg(SMALL), sim_cfg=SimConfig(**sim), chunk=32)
    got = sn.simulate_many(
        [Trace(**{k: (v[s.lo:s.lo + s.n] if k != "name" else v) for k, v in pool[s.bench].items()})
         for s in slices], n_lanes=[s.lanes for s in slices])
    simulate = reference.make_simulate(
        lambda prm, x, d: tx6.forward(prm, x, d, SMALL), params, sim,
        tx6.seq_padded(SMALL), reference.dot_f32)
    want = reference.workload_cycles(simulate, pool, slices, 16)
    gaps = check.gaps([w.total_cycles for w in got.workloads], want)
    # the c3.sweep cell's limits: float32 on both sides, so a predicted
    # latency can differ only where a few-ulp difference in the head's
    # outputs crosses a class or a rounding boundary
    assert gaps["max_gap"] <= 1e-5, gaps
    assert gaps["pack_gap"] <= 1e-6, gaps


@pytest.fixture(scope="module")
def des_pool():
    des = O3Simulator(O3Config())
    return [{k: getattr(t, k) for k in Trace.__dataclass_fields__}
            for t in (des.run(get_benchmark(n, 2048))
                      for n in ("sim_loop", "sim_chase_small", "mlb_stream", "sim_branchy_hard"))]


@pytest.mark.parametrize("seed", [2147483105, 2147483201, 2**33 + 5])
def test_float8_control_fails_the_cell_limits(tx6, des_pool, seed):
    """The control that sets `tx6.sweep`'s limits (the reference with float8
    operands in the program's place) must move the cycles past them at the
    cell's widths, on the weights the harness makes from each seed. With
    small random head biases in place of `init`'s centring, the control
    reads exactly 0 on the last of these seeds: a centring gone stale (say,
    after the feature layout changes) fails here."""
    p = SIZES["predictor"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)
    params = tx6.init(key, p)
    slices = [Slice(b, 0, 8 * STEPS, 8) for b in range(len(des_pool))]

    def cycles(dot):
        simulate = reference.make_simulate(lambda prm, x, d: tx6.forward(prm, x, d, p), params,
                                           SIZES["sim"], tx6.seq_padded(p), dot)
        return reference.workload_cycles(simulate, des_pool, slices, 32)

    stated = SIZES["matmul_operands"]
    gaps = check.gaps(cycles(reference.DOTS[reference.BELOW[stated]]),
                      cycles(reference.DOTS[stated]))
    limits = json.loads((CONFIG.parents[1] / "limits" / "tx6.sweep.json").read_text())
    correct, _ = check.verdict(dict(gaps, failed=0.0), limits["limits"])
    assert not correct, gaps
