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


@pytest.fixture(scope="module")
def fields(traces):
    return [F.trace_fields(t) for t in traces]


def test_packed_matches_separate_exact(arrs, fields):
    """Round-trip: pack → simulate → per-workload totals bit-identical to
    N separate simulate_trace calls (teacher forcing)."""
    cfg = SimConfig(ctx_len=32)
    lanes = [4, 2, 8, 4]
    many = simulate_many(fields, None, cfg, n_lanes=lanes)
    for i, (a, ln) in enumerate(zip(arrs, lanes)):
        ref = simulate_trace(a, None, cfg, ln)
        assert float(many["workload_cycles"][i]) == float(ref["total_cycles"])
        assert int(many["n_instructions"][i]) == int(ref["n_instructions"])
        assert int(many["workload_overflow"][i]) == int(ref["overflow"])


def _lane_rows(a):
    """A pack key, (n_chunks, L, chunk, ...), as each lane's whole time
    axis: (L, n_chunks * chunk, ...)."""
    return np.concatenate(list(a), axis=1)


def _expanded_lane_rows(packed):
    """The pack's fields expanded on the host, each lane's whole time axis:
    {feat, addr, is_store, labels, active}: (L, n_chunks * chunk, ...)."""
    rows = {k: _lane_rows(v) for k, v in packed.xs.items()}
    return {**F.expand(rows), "active": rows["active"]}


def _old_time_major_pack(arrs, lanes, cfgs, pad_to, total_lanes):
    """The time-major (T, L, ...) pack as it was built before the lane-major
    layout: zeros, each job's lanes written as one column block, dead lanes
    appended."""
    per = [a["feat"].shape[0] // ln for a, ln in zip(arrs, lanes)]
    T = -(-max(per) // pad_to) * pad_to
    L = sum(lanes)
    xs = {
        "feat": np.zeros((T, total_lanes, F.STATIC_END), np.float32),
        "addr": np.zeros((T, total_lanes, F.N_ADDR_KEYS), np.int32),
        "is_store": np.zeros((T, total_lanes), bool),
        "labels": np.zeros((T, total_lanes, 3), np.float32),
        "active": np.zeros((T, total_lanes), bool),
    }
    lane = {"workload_id": np.zeros(total_lanes, np.int32),
            "retire_width": np.ones(total_lanes, np.int32),
            "lane_ctx": np.full(total_lanes, max(c.ctx_len for c in cfgs), np.int32),
            "lane_steps": np.zeros(total_lanes, np.int64)}
    lo = 0
    for w, (a, ln, c, p) in enumerate(zip(arrs, lanes, cfgs, per)):
        for k in ("feat", "addr", "is_store", "labels"):
            v = np.asarray(a[k])[: p * ln]
            xs[k][:p, lo:lo + ln] = np.swapaxes(v.reshape(ln, p, *v.shape[1:]), 0, 1)
        xs["active"][:p, lo:lo + ln] = True
        for k, v in (("workload_id", w), ("retire_width", c.retire_width),
                     ("lane_ctx", c.ctx_len), ("lane_steps", p)):
            lane[k][lo:lo + ln] = v
        lo += ln
    assert lo == L
    return xs, lane


@pytest.mark.parametrize("chunk,total_lanes", [(256, None), (256, 32), (100, 19), (None, None)])
def test_lane_major_pack_matches_time_major(arrs, fields, chunk, total_lanes):
    """Ragged jobs, mixed SimConfigs and 18 live lanes (not a power of two):
    the lane-major pack of integer fields, expanded and swapped back to
    time-major, equals the old time-major pack of features element for
    element, dead lanes included."""
    lanes = [4, 2, 8, 4]
    cfgs = [SimConfig(ctx_len=16, retire_width=2), SimConfig(ctx_len=32),
            SimConfig(ctx_len=8, retire_width=4), SimConfig(ctx_len=32, retire_width=1)]
    packed = pack_workloads(fields, lanes, cfgs, chunk=chunk, total_lanes=total_lanes)
    per = [a["feat"].shape[0] // ln for a, ln in zip(arrs, lanes)]
    L = total_lanes or sum(lanes)
    want, lane = _old_time_major_pack(arrs, lanes, cfgs, chunk or max(per), L)
    assert packed.n_lanes == L
    for k, got in packed.xs.items():
        assert got.shape[:3] == (packed.n_chunks, L, packed.chunk)
    expanded = _expanded_lane_rows(packed)
    for k, v in want.items():
        time_major = np.swapaxes(expanded[k], 0, 1)
        assert time_major.dtype == v.dtype
        np.testing.assert_array_equal(time_major, v, err_msg=k)
    for k, v in lane.items():
        np.testing.assert_array_equal(getattr(packed, k), v, err_msg=k)


def test_ragged_lengths_masked(fields):
    """Lanes from shorter workloads freeze once their sub-trace ends; the
    packed time axis is max(per-lane length) rounded up to the chunk."""
    packed = pack_workloads(fields, n_lanes=4, cfg=SimConfig(ctx_len=16), chunk=256)
    per = [F.n_instructions(f) // 4 for f in fields]
    assert packed.n_steps == ((max(per) + 255) // 256) * 256
    assert packed.chunk == 256
    expanded = _expanded_lane_rows(packed)
    active = expanded["active"]  # (L, T)
    labels = expanded["labels"]  # (L, T, 3)
    assert not expanded["feat"][~active].any()
    lo = 0
    for w, p in enumerate(per):
        assert active[lo : lo + 4, :p].all()
        assert not active[lo : lo + 4, p:].any()
        assert int(packed.n_instructions[w]) == p * 4
        # each lane's steps past its own sub-trace are zero-filled
        assert labels[lo : lo + 4, p:].sum() == 0.0
        lo += 4
    # padded rows are zero-filled
    assert labels[:, max(per):].sum() == 0.0


def test_engine_reused_pack_buffer_matches_fresh_engine(fields):
    """One engine packs a large ragged batch, then a smaller full one, then
    a third shape, into the host buffer it keeps: every call's totals are
    bit-identical to a fresh engine's, so no stale row, dead lane or
    aliased host memory of an earlier call leaks in; a repeated shape
    reuses the buffer."""
    import jax

    from repro.core.predictor import PredictorConfig, init_predictor
    from repro.serving.compile_cache import CompileCache
    from repro.serving.simnet_engine import SimNetEngine

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    cache = CompileCache()

    def engine():
        return SimNetEngine(params, pcfg, SimConfig(ctx_len=16), cache=cache)

    packs = [
        (fields, [4, 2, 8, 4], 256),  # ragged, 18 lanes in a 32-lane bucket
        ([{k: v[:1024] for k, v in f.items()} for f in fields[:2]],
         [4, 4], 128),  # two full chunks, 8 lanes, no dead lane
        (fields[1:3], [2, 1], 512),  # a third shape: 3 lanes in a bucket of 4
    ]
    eng = engine()
    for trs, lanes, chunk in packs:
        got = eng.simulate_many(trs, n_lanes=lanes, chunk=chunk)
        want = engine().simulate_many(trs, n_lanes=lanes, chunk=chunk)
        np.testing.assert_array_equal(got["workload_cycles"], want["workload_cycles"])
        np.testing.assert_array_equal(got["workload_overflow"], want["workload_overflow"])
    assert got["pack_buffer"]["allocations"] == 0  # the third fits the first's buffer
    again = eng.simulate_many(*packs[2][:1], n_lanes=packs[2][1], chunk=packs[2][2])
    assert again["pack_buffer"]["reuses"] == 1
    assert again["pack_buffer"]["allocations"] == 0
    np.testing.assert_array_equal(again["workload_cycles"], got["workload_cycles"])
    assert eng.pack_buffer_counters == {
        "reuses": 3, "allocations": 1, "bytes": again["pack_buffer"]["bytes"]}
    assert again["pack_buffer"]["bytes"] > 0


def test_heterogeneous_configs_exact(arrs, fields):
    """Workloads × SimConfigs: per-lane retire width and context capacity
    replay each job's own config exactly inside the shared scan."""
    cfgs = [
        SimConfig(ctx_len=16, retire_width=2),
        SimConfig(ctx_len=32, retire_width=8),
        SimConfig(ctx_len=8, retire_width=4),
        SimConfig(ctx_len=32, retire_width=1),
    ]
    lanes = [4, 2, 8, 4]
    many = simulate_many(fields, None, cfgs, n_lanes=lanes)
    for i, (a, c, ln) in enumerate(zip(arrs, cfgs, lanes)):
        ref = simulate_trace(a, None, c, ln)
        assert float(many["workload_cycles"][i]) == float(ref["total_cycles"])
        assert int(many["workload_overflow"][i]) == int(ref["overflow"])


def test_rejects_mismatched_shared_config_fields(fields):
    """Only ctx_len/retire_width are replayed per lane; packing configs that
    differ elsewhere (e.g. max_latency) must fail loudly, not silently
    clip with the wrong bound."""
    with pytest.raises(ValueError, match="other SimConfig fields"):
        pack_workloads(fields[:2], 2, cfg=[SimConfig(max_latency=50.0), SimConfig()])


def test_overflow_accounted_per_workload():
    """Overflow stays attributed to the workload whose lanes dropped
    entries — a saturating workload must not leak into a well-behaved one."""
    T = 64

    def synth(fetch_lat, exec_lat):
        fields = {f.name: np.zeros(f.shape(T), f.dtype) for f in F.FIELDS}
        fields["fetch_lat"][:] = fetch_lat
        fields["exec_lat"][:] = exec_lat
        return fields

    cfg = SimConfig(ctx_len=4)
    # workload 0: fetch 0 + huge exec → everything stays in flight → overflow
    # workload 1: exec 1 with fetch 0... also saturates, so give it fetch 1
    busy = synth(0.0, 1e4)
    calm = synth(1.0, 1.0)
    many = simulate_many([busy, calm], None, cfg, n_lanes=2)
    ref_busy = simulate_trace(F.expand(busy), None, cfg, 2)
    ref_calm = simulate_trace(F.expand(calm), None, cfg, 2)
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


def test_warm_call_compiles_nothing_and_repeats_totals(traces):
    """The steady state: a second simulate_many of the same shapes on one
    session reuses the resident executable (no cache miss), gives
    bit-identical per-workload totals, and its stream-and-wait time is
    inside its own first-call time."""
    from repro.core.predictor import PredictorConfig, init_predictor
    from repro.serving.compile_cache import CompileCache
    import jax

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    sn = api.SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(ctx_len=16),
                    cache=CompileCache())
    sub = traces[:2]
    cold = sn.simulate_many(sub, n_lanes=4)
    warm = sn.simulate_many(sub, n_lanes=4)
    assert cold.cache["misses"] >= 1
    assert warm.cache["misses"] == 0 and warm.cache["hits"] >= 1
    assert [w.total_cycles for w in warm] == [w.total_cycles for w in cold]
    assert [w.overflow for w in warm] == [w.overflow for w in cold]
    assert 0 < warm.seconds <= warm.first_call_seconds


@pytest.mark.slow
def test_engine_simulate_many_matches_core(traces):
    """Chunked streaming engine (donated state buffers) returns the same
    per-workload totals as the one-shot packed scan."""
    from repro.core.predictor import PredictorConfig, init_predictor, make_predict_fn
    from repro.serving.simnet_engine import SimNetEngine
    import jax

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    fields2 = [F.trace_fields(t) for t in traces[:2]]
    engine = SimNetEngine(params, pcfg, SimConfig(ctx_len=16))
    res_e = engine.simulate_many(fields2, n_lanes=4, chunk=128)
    predict = make_predict_fn(params, pcfg)
    res_c = simulate_many(fields2, predict, SimConfig(ctx_len=16), n_lanes=4)
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


def test_engine_pack_buffer_shared_by_threads(fields):
    """Threads calling one engine's simulate_many at once share its host
    pack buffer: each call still returns what it returns alone."""
    import sys
    import threading

    from repro.serving.compile_cache import CompileCache
    from repro.serving.simnet_engine import SimNetEngine

    eng = SimNetEngine(None, None, SimConfig(ctx_len=16), cache=CompileCache())
    packs = [(fields, [4, 2, 8, 4]), (fields[::-1], [2, 8, 4, 4]), (fields[1:3], [2, 1])]
    want = [eng.simulate_many(a, n_lanes=ln, chunk=256)["workload_cycles"] for a, ln in packs]
    got, errors = {}, []

    def worker(i):
        try:
            a, ln = packs[i % len(packs)]
            for r in range(2):
                got[i, r] = eng.simulate_many(a, n_lanes=ln, chunk=256)["workload_cycles"]
        except Exception as e:  # reported below, with the thread's index
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(got) == 2 * len(threads)
    for (i, _), cycles in got.items():
        np.testing.assert_array_equal(cycles, want[i % len(packs)])
