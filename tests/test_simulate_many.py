"""Batched multi-workload engine: packing round-trip, ragged masking,
per-workload overflow accounting, heterogeneous SimConfigs, engine parity."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import api, features as F
from repro.core.simulator import (
    SimConfig,
    pack_workloads,
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


def test_packed_matches_separate_exact(arrs):
    """Round-trip: pack → simulate → per-workload totals bit-identical to
    N separate simulate_trace calls (teacher forcing)."""
    cfg = SimConfig(ctx_len=32)
    lanes = [4, 2, 8, 4]
    many = simulate_many(arrs, None, cfg, n_lanes=lanes)
    for i, (a, ln) in enumerate(zip(arrs, lanes)):
        ref = simulate_trace(a, None, cfg, ln)
        assert float(many["workload_cycles"][i]) == float(ref["total_cycles"])
        assert int(many["n_instructions"][i]) == int(ref["n_instructions"])
        assert int(many["workload_overflow"][i]) == int(ref["overflow"])


def test_ragged_lengths_masked(arrs):
    """Lanes from shorter workloads freeze once their sub-trace ends; the
    packed time axis is max(per-lane length) rounded up to pad_to."""
    packed = pack_workloads(arrs, n_lanes=4, cfg=SimConfig(ctx_len=16), pad_to=256)
    per = [a["feat"].shape[0] // 4 for a in arrs]
    assert packed.n_steps == ((max(per) + 255) // 256) * 256
    active = packed.xs["active"]
    lo = 0
    for w, p in enumerate(per):
        assert active[:p, lo : lo + 4].all()
        assert not active[p:, lo : lo + 4].any()
        assert int(packed.n_instructions[w]) == p * 4
        lo += 4
    # padded rows are zero-filled
    assert packed.xs["labels"][max(per):].sum() == 0.0


def test_heterogeneous_configs_exact(arrs):
    """Workloads × SimConfigs: per-lane retire width and context capacity
    replay each job's own config exactly inside the shared scan."""
    cfgs = [
        SimConfig(ctx_len=16, retire_width=2),
        SimConfig(ctx_len=32, retire_width=8),
        SimConfig(ctx_len=8, retire_width=4),
        SimConfig(ctx_len=32, retire_width=1),
    ]
    lanes = [4, 2, 8, 4]
    many = simulate_many(arrs, None, cfgs, n_lanes=lanes)
    for i, (a, c, ln) in enumerate(zip(arrs, cfgs, lanes)):
        ref = simulate_trace(a, None, c, ln)
        assert float(many["workload_cycles"][i]) == float(ref["total_cycles"])
        assert int(many["workload_overflow"][i]) == int(ref["overflow"])


def test_rejects_mismatched_shared_config_fields(arrs):
    """Only ctx_len/retire_width are replayed per lane; packing configs that
    differ elsewhere (e.g. max_latency) must fail loudly, not silently
    clip with the wrong bound."""
    with pytest.raises(ValueError, match="other SimConfig fields"):
        pack_workloads(arrs[:2], 2, cfg=[SimConfig(max_latency=50.0), SimConfig()])


def test_overflow_accounted_per_workload():
    """Overflow stays attributed to the workload whose lanes dropped
    entries — a saturating workload must not leak into a well-behaved one."""
    T = 64

    def synth(exec_lat):
        return {
            "feat": np.zeros((T, F.STATIC_END), np.float32),
            "addr": np.zeros((T, F.N_ADDR_KEYS), np.int32),
            "is_store": np.zeros(T, bool),
            "labels": np.stack(
                [np.zeros(T), np.full(T, exec_lat), np.zeros(T)], axis=1
            ).astype(np.float32),
        }

    cfg = SimConfig(ctx_len=4)
    # workload 0: fetch 0 + huge exec → everything stays in flight → overflow
    # workload 1: exec 1 with fetch 0... also saturates, so give it fetch 1
    busy = synth(1e4)
    calm = synth(1.0)
    calm["labels"][:, 0] = 1.0
    many = simulate_many([busy, calm], None, cfg, n_lanes=2)
    ref_busy = simulate_trace(busy, None, cfg, 2)
    ref_calm = simulate_trace(calm, None, cfg, 2)
    assert int(many["workload_overflow"][0]) == int(ref_busy["overflow"]) > 0
    assert int(many["workload_overflow"][1]) == int(ref_calm["overflow"]) == 0
    assert float(many["workload_cycles"][1]) == float(ref_calm["total_cycles"])


def test_api_simulate_many_teacher_forced(traces):
    """Public API, teacher-forced, one lane per workload: per-workload
    totals equal the traces' own Eq. 1 golden cycle counts exactly."""
    res = api.SimNet().simulate_many(traces, n_lanes=1)
    assert res.n_workloads == len(traces)
    for tr, w in zip(traces, res):
        assert w.name == tr.name
        assert w.total_cycles == tr.total_cycles
        assert w.cpi_error == 0.0
    assert res.total_cycles == sum(t.total_cycles for t in traces)


@pytest.mark.slow
def test_api_simulate_many_predictor_mode(traces):
    """Predictor-driven packed run agrees with per-workload simulate."""
    from repro.core.predictor import PredictorConfig, init_predictor
    import jax

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    sn = api.SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(ctx_len=16))
    sub = traces[:2]
    many = sn.simulate_many(sub, n_lanes=2)
    for tr, w in zip(sub, many):
        ref = sn.simulate(tr, n_lanes=2)[0]
        assert w.total_cycles == pytest.approx(ref.total_cycles, rel=1e-5)


@pytest.mark.slow
def test_packed_beats_sequential_wall_clock(traces):
    """The batched engine's reason to exist: simulating W workloads as one
    packed scan is faster end-to-end than W sequential compile+dispatch
    cycles. The sequential side gets a fresh COLD cache per call — the
    pre-SimServe behaviour this is the baseline for (one jit wrapper per
    session, exact-length chunks that never matched); a shared cache would
    let it free-ride on the very executable reuse this PR added."""
    from repro.core.predictor import PredictorConfig, init_predictor
    from repro.serving.compile_cache import CompileCache
    import jax, time

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    scfg = SimConfig(ctx_len=16)

    def fresh(cache):
        return api.SimNet(params=params, pcfg=pcfg, sim_cfg=scfg, cache=cache)

    t0 = time.time()
    seq = [fresh(CompileCache()).simulate(tr, n_lanes=4, timeit=True) for tr in traces]
    # simulate(timeit=True) runs each compiled pass twice (warmup + timed);
    # subtract the re-runs so both sides are compile + one execution
    seq_wall = (time.time() - t0) - sum(r.seconds for r in seq)
    many = fresh(CompileCache()).simulate_many(traces, n_lanes=4)
    assert many.first_call_seconds < seq_wall / 1.3, (
        f"packed {many.first_call_seconds:.2f}s vs sequential {seq_wall:.2f}s"
    )


@pytest.mark.slow
def test_engine_simulate_many_matches_core(traces):
    """Chunked streaming engine (donated state buffers) returns the same
    per-workload totals as the one-shot packed scan."""
    from repro.core.predictor import PredictorConfig, init_predictor, make_predict_fn
    from repro.serving.simnet_engine import SimNetEngine
    import jax

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    arrs2 = [F.trace_arrays(t) for t in traces[:2]]
    engine = SimNetEngine(params, pcfg, SimConfig(ctx_len=16))
    res_e = engine.simulate_many(arrs2, n_lanes=4, chunk=128)
    predict = make_predict_fn(params, pcfg)
    res_c = simulate_many(arrs2, predict, SimConfig(ctx_len=16), n_lanes=4)
    np.testing.assert_allclose(
        res_e["workload_cycles"], np.asarray(res_c["workload_cycles"]), rtol=1e-6
    )
    assert res_e["n_workloads"] == 2
    assert res_e["total_instructions"] == int(np.sum(res_c["n_instructions"]))


_SHARDED_SCRIPT = """
import json
from repro.core.api import SimNet
from repro.des.o3 import O3Config, O3Simulator
from repro.des.workloads import get_benchmark
from repro.launch.mesh import make_host_mesh
trs = [O3Simulator(O3Config()).run(get_benchmark(n, 3000))
       for n in ("mlb_stream", "sim_loop")]
mesh = make_host_mesh()
many = SimNet(mesh=mesh).simulate_many(trs, n_lanes=1)
one = SimNet().simulate_many(trs, n_lanes=1)
print(json.dumps({"devices": int(mesh.devices.size),
                  "des": [t.total_cycles for t in trs],
                  "sharded": [w.total_cycles for w in many.workloads],
                  "single": [w.total_cycles for w in one.workloads]}))
"""


def test_lane_sharded_session_matches_des_and_one_device():
    """SimNet over a 4-device lane mesh (virtual CPU devices, set before
    JAX starts, so in a subprocess): teacher-forced totals equal the DES
    and the one-device run. Two one-lane workloads also check that a pack
    narrower than the mesh is padded to one lane per device."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p))
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["sharded"] == out["des"] == out["single"]
