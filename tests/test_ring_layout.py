"""Ring-vs-roll step-layout exactness guards (the tentpole invariant).

The ring layout replaces the roll layout's O(L·Q·F) shift-push with an
O(1)-slot write + head cursor; these tests pin the contract that bought
that optimization: per-lane and per-workload totals are BIT-IDENTICAL
between the layouts — teacher-forced and predicted — across ragged packs,
heterogeneous retire widths / lane-ctx capacities, overflow, bf16 state,
and the chunked/bucketed engine path.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import features as F
from repro.core.predictor import PredictorConfig, init_predictor, make_predict_fn
from repro.core.simulator import (
    SimConfig,
    simulate_many,
    simulate_trace,
)
from repro.des.o3 import O3Config, O3Simulator
from repro.des.workloads import get_benchmark

STYLES = ["mlb_stream", "mlb_compute", "sim_loop", "mlb_branchy"]
SIZES = [3000, 2500, 2000, 3500]  # ragged on purpose


@pytest.fixture(scope="module")
def traces():
    sim = O3Simulator(O3Config())
    return [sim.run(get_benchmark(n, s)) for n, s in zip(STYLES, SIZES)]


@pytest.fixture(scope="module")
def arrs(traces):
    return [F.trace_arrays(t) for t in traces]


@pytest.fixture(scope="module")
def fields(traces):
    return [F.trace_fields(t) for t in traces]


def _both(cfg_kw):
    return (SimConfig(layout="roll", **cfg_kw), SimConfig(layout="ring", **cfg_kw))


def assert_states_identical(roll_res, ring_res, keys=("lane_cycles",)):
    for k in keys:
        a, b = np.asarray(roll_res[k]), np.asarray(ring_res[k])
        np.testing.assert_array_equal(a, b, err_msg=k)


def test_teacher_forced_bit_identical(arrs):
    """Per-lane totals AND overflow equal exactly across ctx/lane grids."""
    for ctx in (8, 64):
        for lanes in (1, 4):
            roll_cfg, ring_cfg = _both(dict(ctx_len=ctx))
            roll = simulate_trace(arrs[0], None, roll_cfg, lanes)
            ring = simulate_trace(arrs[0], None, ring_cfg, lanes)
            assert_states_identical(roll, ring)
            assert int(roll["overflow"]) == int(ring["overflow"])


def test_packed_heterogeneous_bit_identical(fields):
    """Ragged pack × heterogeneous per-lane retire_width / lane_ctx: the
    ring scan replays every per-workload SimConfig exactly."""

    def cfgs(layout):
        return [
            SimConfig(ctx_len=16, retire_width=2, layout=layout),
            SimConfig(ctx_len=32, retire_width=8, layout=layout),
            SimConfig(ctx_len=8, retire_width=4, layout=layout),
            SimConfig(ctx_len=32, retire_width=1, layout=layout),
        ]

    lanes = [4, 2, 8, 4]
    roll = simulate_many(fields, None, cfgs("roll"), n_lanes=lanes)
    ring = simulate_many(fields, None, cfgs("ring"), n_lanes=lanes)
    assert_states_identical(
        roll, ring, keys=("lane_cycles", "workload_cycles", "workload_overflow")
    )


def test_overflow_bit_identical_under_pressure(arrs):
    """A saturating lane-ctx (tiny capacity, deep queues) must drop the
    same entries in both layouts."""
    roll_cfg, ring_cfg = _both(dict(ctx_len=4))
    roll = simulate_trace(arrs[1], None, roll_cfg, 2)
    ring = simulate_trace(arrs[1], None, ring_cfg, 2)
    assert_states_identical(roll, ring)
    assert int(roll["overflow"]) == int(ring["overflow"]) > 0


def test_predicted_bit_identical(arrs):
    """Predictor-driven simulation: identical model inputs → identical
    latency predictions → identical totals, bit for bit."""
    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    predict = make_predict_fn(params, pcfg)
    roll_cfg, ring_cfg = _both(dict(ctx_len=16))
    roll = simulate_trace(arrs[0], predict, roll_cfg, 4)
    ring = simulate_trace(arrs[0], predict, ring_cfg, 4)
    assert_states_identical(roll, ring)


def test_bf16_state_bit_identical_and_tolerant(arrs):
    """The advertised bf16 state: ring == roll stays bit-identical (same
    rounded values both sides), and bf16 CPI lands near the f32 CPI
    (only the context FEATURES round — cycle counters stay f32)."""
    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    predict = make_predict_fn(params, pcfg)
    roll_cfg, ring_cfg = _both(dict(ctx_len=16, state_dtype="bfloat16"))
    roll = simulate_trace(arrs[0], predict, roll_cfg, 4)
    ring = simulate_trace(arrs[0], predict, ring_cfg, 4)
    assert_states_identical(roll, ring)

    f32 = simulate_trace(
        arrs[0], predict, SimConfig(ctx_len=16, layout="ring"), 4
    )
    bf16_total = float(np.asarray(ring["total_cycles"]))
    f32_total = float(np.asarray(f32["total_cycles"]))
    assert bf16_total == pytest.approx(f32_total, rel=0.05)


def test_bf16_state_teacher_forced_exact(arrs):
    """Teacher forcing never reads the (bf16) feature planes, so bf16
    state totals must equal f32 totals EXACTLY — in both layouts."""
    for layout in ("roll", "ring"):
        f32 = simulate_trace(
            arrs[2], None, SimConfig(ctx_len=32, layout=layout), 2
        )
        bf16 = simulate_trace(
            arrs[2], None,
            SimConfig(ctx_len=32, layout=layout, state_dtype="bfloat16"), 2,
        )
        assert_states_identical(f32, bf16)


def test_engine_path_bit_identical(fields):
    """Chunked/donated/lane-bucketed engine: ring == roll per workload."""
    from repro.serving.compile_cache import CompileCache
    from repro.serving.simnet_engine import SimNetEngine

    sub = fields[:2]

    def run(layout):
        eng = SimNetEngine(
            sim_cfg=SimConfig(ctx_len=16, layout=layout), cache=CompileCache()
        )
        return eng.simulate_many(sub, n_lanes=[3, 5], chunk=256)

    roll, ring = run("roll"), run("ring")
    np.testing.assert_array_equal(roll["workload_cycles"], ring["workload_cycles"])
    np.testing.assert_array_equal(roll["workload_overflow"], ring["workload_overflow"])


def test_bf16_state_fused_kernel_falls_back(fields):
    """use_kernel + ring + bf16 state must match the UNFUSED bf16 engine
    exactly: the fused kernel assembles in f32 and would skip the bf16
    rounding of the dynamic features, so the engine gates it off."""
    from repro.serving.compile_cache import CompileCache
    from repro.serving.simnet_engine import SimNetEngine

    pcfg = PredictorConfig(kind="c3", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    scfg = SimConfig(ctx_len=16, layout="ring", state_dtype="bfloat16")

    def run(use_kernel):
        eng = SimNetEngine(
            params, pcfg, scfg, use_kernel=use_kernel, cache=CompileCache()
        )
        return eng.simulate_many(fields[:1], n_lanes=4, chunk=256)

    np.testing.assert_array_equal(
        run(False)["workload_cycles"], run(True)["workload_cycles"]
    )


def test_serve_rejects_layout_mismatch(arrs):
    """SimServe admission: a job whose SimConfig layout differs from the
    resident engine's must be refused at submit with a layout-specific
    error (the layout is baked into the resident executable)."""
    from repro.serving.service import SimServe

    serve = SimServe()
    engine_cfg = SimConfig(layout="ring")
    mid = serve.register("tf-ring", sim_cfg=engine_cfg)
    with pytest.raises(ValueError, match="layout"):
        serve.submit(
            arrs[0], mid, n_lanes=2,
            sim_cfg=dataclasses.replace(engine_cfg, layout="roll"),
        )
    # same layout still admits fine
    h = serve.submit(arrs[0], mid, n_lanes=2, sim_cfg=engine_cfg)
    assert h.result().total_cycles > 0


def test_cli_simulate_ring_smoke(capsys, tmp_path):
    """`python -m repro simulate --layout ring` runs end to end and its
    teacher-forced totals equal the roll layout's."""
    from repro.cli import main

    totals = {}
    for layout in ("ring", "roll"):
        assert main([
            "simulate", "--layout", layout, "--bench", "sim_loop",
            "-n", "2000", "--lanes", "2", "--cache-dir", str(tmp_path),
        ]) == 0
        out = json.loads(capsys.readouterr().out)
        totals[layout] = out["result"]["workloads"][0]["total_cycles"]
    assert totals["ring"] == totals["roll"]
