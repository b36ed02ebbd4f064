"""The engine's input form: each instruction's integer trace fields, packed
lane-major and expanded to the static feature block on the device, in
`chunk_time_major` (scope ``layout``).

- The device expansion of a pack equals the time-major pack of the host's
  `trace_arrays`, bit for bit, for every DES workload generator (training,
  evaluation and co-run mixes) and for a trace that holds every field's
  edge values; the host's expansion equals the feature expressions the
  schema was first written with.
- `simulate_many` totals for c3 and tx6 predictors equal the values the
  feature-shipping pack gave, exactly.
- Submit refuses fields the pack cannot hold; a trace_arrays dict is
  inverted into the same fields (in process and over HTTP), and one whose
  rows are not the image of integer fields is refused.
- The pack holds 50 bytes per lane-step (`pack_buffer.bytes`).
"""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import features as F
from repro.core.api import SimNet
from repro.core.predictor import PredictorConfig, init_predictor
from repro.core.simulator import SimConfig, chunk_time_major, pack_workloads
from repro.des import workloads as W
from repro.des.isa import MAX_DST, MAX_SRC, N_OP_CLASSES, N_REGS, Op
from repro.des.multicore import run_corun
from repro.des.o3 import O3Config, O3Simulator
from repro.des.trace import Trace
from repro.serving.compile_cache import CompileCache
from repro.serving.service import SimServe

LANES, CHUNK, BUCKET = (3, 2, 4), 64, 16  # ragged jobs, a tail, dead lanes


def _features_as_first_written(tr):
    """The static block, keys, store flag and labels exactly as the schema
    computed them on the host before the fields were packed."""
    T = tr.n
    f = np.zeros((T, F.STATIC_END), np.float32)
    f[np.arange(T), tr.op.astype(np.int64)] = 1.0
    f[:, 13:13 + MAX_SRC] = (tr.src.astype(np.float32) + 1.0) / N_REGS
    f[:, 21:21 + MAX_DST] = (tr.dst.astype(np.float32) + 1.0) / N_REGS
    f[:, 27] = tr.mispred.astype(np.float32)
    f[:, 28] = tr.fetch_level.astype(np.float32) / 3.0
    f[:, 29:32] = tr.fetch_tw.astype(np.float32) / 2.0
    f[:, 32:34] = tr.fetch_wb.astype(np.float32)
    f[:, 34] = tr.data_level.astype(np.float32) / 3.0
    f[:, 35:38] = tr.data_tw.astype(np.float32) / 2.0
    f[:, 38:41] = tr.data_wb.astype(np.float32)
    a = np.zeros((T, F.N_ADDR_KEYS), np.int64)
    a[:, 0] = tr.pc
    a[:, 1] = tr.pc // 64
    has = tr.addr != 0
    a[:, 2] = np.where(has, tr.addr, 0)
    a[:, 3] = np.where(has, tr.addr // 64, 0)
    a[:, 4] = np.where(has, tr.addr // 4096, 0)
    return dict(feat=f, addr=a.astype(np.int32), is_store=tr.op == int(Op.STORE),
                labels=np.stack([tr.fetch_lat, tr.exec_lat, tr.store_lat],
                                axis=1).astype(np.float32))


def _edge_trace():
    """Every int8 value of every history field, register indices -1, 127
    and -128, every op class, pc 0 and 2**31 - 1, data address 0,
    2**31 - 1 and negatives, in the dtypes the DES writes."""
    T = 256 * 3
    rng = np.random.default_rng(5)
    every = np.tile(np.arange(-128, 128), 3).astype(np.int8)
    regs = np.array([-1, 127, -128, 0, 5], np.int16)

    def hist(k):
        return np.stack([np.roll(every, 17 * i) for i in range(k)], axis=1)

    pc = rng.integers(0, 2**31, T)
    pc[:4] = [0, 2**31 - 1, 64, 4095]
    addr = rng.integers(-2**31, 2**31, T)
    addr[:5] = [0, 2**31 - 1, -1, -2**31, 4096]
    addr[rng.random(T) < 0.3] = 0
    return Trace(
        name="edges",
        pc=pc, op=np.arange(T).astype(np.int8) % N_OP_CLASSES,
        src=regs[rng.integers(0, len(regs), (T, MAX_SRC))],
        dst=regs[rng.integers(0, len(regs), (T, MAX_DST))],
        addr=addr, mispred=rng.random(T) < 0.5,
        fetch_level=every, fetch_tw=hist(3), fetch_wb=hist(2),
        data_level=np.roll(every, 91), data_tw=hist(3)[::-1].copy(), data_wb=hist(3)[:, ::-1].copy(),
        fetch_lat=rng.integers(0, 40, T), exec_lat=rng.integers(1, 40, T),
        store_lat=rng.integers(0, 40, T),
    )


def _des(names, T):
    sim = O3Simulator(O3Config())
    return [sim.run(W.get_benchmark(n, T)) for n in names]


def _workloads(case):
    """Three traces of one generator family, one case each."""
    kind, name = case
    if kind == "bench":
        tr = _des([name], 3 * 256)[0]
        return [tr.slice(0, 256), tr.slice(256, 512), tr.slice(512, 704)]
    if kind == "mix":
        traces, _ = run_corun(W.get_mix(name, 200, n_cores=3))
        return [t.slice(0, min(t.n, 300)) for t in traces]
    tr = _edge_trace()
    return [tr.slice(0, 256), tr.slice(256, 512), tr.slice(512, 768)]


CASES = ([("bench", n) for n in sorted(W.ALL_BENCHMARKS)]
         + [("mix", n) for n in W.MULTICORE_MIXES] + [("edges", "edges")])


def _old_pack(arrs_list, lanes, chunk, total_lanes):
    """The time-major pack of host features, (n_chunks, chunk, L, ...) per
    key: each job's lanes one block, padding zero, dead lanes appended."""
    per = [a["feat"].shape[0] // ln for a, ln in zip(arrs_list, lanes)]
    steps = -(-max(per) // chunk) * chunk
    out = {k: np.zeros((steps, total_lanes) + v.shape[1:], v.dtype)
           for k, v in arrs_list[0].items()}
    out["active"] = np.zeros((steps, total_lanes), bool)
    lo = 0
    for a, ln, p in zip(arrs_list, lanes, per):
        for k, v in a.items():
            out[k][:p, lo:lo + ln] = np.swapaxes(v[:p * ln].reshape(ln, p, *v.shape[1:]), 0, 1)
        out["active"][:p, lo:lo + ln] = True
        lo += ln
    return {k: v.reshape((-1, chunk) + v.shape[1:]) for k, v in out.items()}


@pytest.fixture(scope="module")
def expand_on_device():
    return jax.jit(chunk_time_major)


@pytest.mark.parametrize("case", CASES, ids=[n for _, n in CASES])
def test_device_expansion_matches_host_features(case, expand_on_device):
    traces = _workloads(case)
    arrs = [F.trace_arrays(t) for t in traces]
    for t, a in zip(traces, arrs):
        first = _features_as_first_written(t)
        for k, v in first.items():
            assert a[k].dtype == v.dtype and a[k].shape == v.shape, k
            np.testing.assert_array_equal(a[k].view(np.uint8), v.view(np.uint8), err_msg=k)
    packed = pack_workloads([F.trace_fields(t) for t in traces], list(LANES),
                            chunk=CHUNK, total_lanes=BUCKET)
    want = _old_pack(arrs, LANES, CHUNK, BUCKET)
    assert packed.n_chunks == len(want["feat"]) >= 2
    for c in range(packed.n_chunks):
        got = expand_on_device({k: v[c] for k, v in packed.xs.items()})
        assert set(got) == set(want)
        for k, v in want.items():
            g = np.asarray(got[k])
            assert g.dtype == v.dtype and g.shape == v[c].shape, k
            np.testing.assert_array_equal(g.view(np.uint8), v[c].view(np.uint8),
                                          err_msg=f"{k}, chunk {c}")


def test_level_table_holds_every_int8_value():
    """A level's table entry is x / 3 for every int8 x, on the host and
    through the device's select tree."""
    x = np.arange(-128, 128).astype(np.int8)
    want = x.astype(np.float32) / 3.0
    host = F.Table(F.LEVEL_TABLE)(x, np)
    device = jax.jit(lambda v: F.Table(F.LEVEL_TABLE)(v, jax.numpy))(x)
    np.testing.assert_array_equal(host.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(device).view(np.uint32), want.view(np.uint32))


# ------------------------------------------- totals against the old pack

TOTAL_TRACES = (("sim_loop", 600), ("mlb_stream", 500), ("sim_chase_small", 700))


@pytest.fixture(scope="module")
def total_traces():
    sim = O3Simulator(O3Config())
    return [sim.run(W.get_benchmark(n, T)) for n, T in TOTAL_TRACES]


def test_c3_totals_equal_the_feature_pack(total_traces):
    """A seeded c3 over ragged jobs, two chunks and dead lanes: the totals
    the pack of features gave, exactly (CPU, float32)."""
    pcfg = PredictorConfig(kind="c3", ctx_len=8, channels=(16, 16, 16), hidden=32)
    params = init_predictor(jax.random.PRNGKey(7), pcfg)[0]
    sn = SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(ctx_len=8), chunk=64,
                cache=CompileCache())
    res = sn.simulate_many(total_traces, n_lanes=[3, 2, 4])
    assert [w.total_cycles for w in res.workloads] == [1589.0, 1009.0, 1572.0]


def test_tx6_totals_equal_the_feature_pack(total_traces):
    """tx6 at two layers of width 16, on the benchmark configuration's own
    `init`: the totals the pack of features gave, exactly."""
    from bench.manifest import load_module

    path = Path(__file__).resolve().parents[1] / "bench" / "configs" / "tx6.json"
    sizes = json.loads(path.read_text())
    small = dict(sizes["predictor"], tx_dim=16, tx_heads=4, tx_layers=2, ctx_len=8)
    params = load_module(path.with_suffix(".py")).init(jax.random.PRNGKey(11), small)
    pcfg = PredictorConfig(**dict(small, channels=tuple(small["channels"])))
    sn = SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(**dict(sizes["sim"], ctx_len=8)),
                chunk=64, cache=CompileCache())
    res = sn.simulate_many(total_traces, n_lanes=[3, 2, 4])
    assert [w.total_cycles for w in res.workloads] == [1262.0, 1358.0, 2263.0]


# ------------------------------------------------------------- submit

def _serve():
    serve = SimServe(cache=CompileCache())
    serve.register("tf", sim_cfg=SimConfig(ctx_len=8))
    return serve


@pytest.fixture(scope="module")
def des_trace():
    return _des(["mlb_mixed"], 512)[0]


@pytest.mark.parametrize("field,value", [
    ("op", 13), ("op", -1), ("src", 128), ("dst", -129), ("fetch_level", 200),
    ("data_tw", -300), ("pc", 2**31), ("pc", -2**31 - 1), ("addr", 2**31),
])
def test_submit_refuses_fields_the_pack_cannot_hold(des_trace, field, value):
    kw = {f.name: getattr(des_trace, f.name) for f in F.FIELDS}
    bad = np.array(kw[field], dtype=np.int64)
    bad.flat[7] = value
    kw[field] = bad
    serve = _serve()
    with pytest.raises(ValueError, match=field):
        serve.submit(Trace(name="bad", **kw), "tf", n_lanes=2)
    assert serve.stats()["jobs_submitted"] == 0


def _not_images(arrs):
    """trace_arrays dicts that no integer fields expand to."""
    def edited(key, fn):
        a = {k: np.array(v) for k, v in arrs.items()}
        fn(a[key])
        return a

    def two_ops(f):
        f[3, :N_OP_CLASSES] = 0
        f[3, 1] = f[3, 4] = 1

    def off_grid_register(f):
        f[5, 14] += 1e-3

    def flip_store(s):
        s[9] = not s[9]

    def bad_line_key(a):
        a[2, F.ADDR_ILINE] += 1

    def nan_level(f):
        f[0, 28] = np.nan

    return {"random": {**arrs, "feat": np.random.default_rng(0).random(
                arrs["feat"].shape).astype(np.float32)},
            "two_ops": edited("feat", two_ops),
            "off_grid_register": edited("feat", off_grid_register),
            "store_flag": edited("is_store", flip_store),
            "line_key": edited("addr", bad_line_key),
            "nan": edited("feat", nan_level)}


def test_arrays_dict_inverts_to_the_trace_fields(des_trace):
    arrs = F.trace_arrays(des_trace)
    fields = F.trace_fields(arrs)
    for f in F.FIELDS:
        np.testing.assert_array_equal(fields[f.name], getattr(des_trace, f.name), err_msg=f.name)
    serve = _serve()
    h_trace = serve.submit(des_trace, "tf", n_lanes=2)
    h_dict = serve.submit(arrs, "tf", n_lanes=2)
    serve.drain()
    assert h_dict.result().total_cycles == h_trace.result().total_cycles
    for name, bad in _not_images(arrs).items():
        with pytest.raises(ValueError, match="trace arrays"):
            serve.submit(bad, "tf", n_lanes=2)
    assert serve.stats()["jobs_submitted"] == 2


def test_http_arrays_match_the_trace_and_non_images_are_bad_trace(des_trace):
    from repro.serving.http import SimServeHTTP, http_request, wait_job

    arrs = F.trace_arrays(des_trace)
    serve = SimServe(cache=CompileCache(), max_wait_ms=5.0)
    serve.register("tf", sim_cfg=SimConfig(ctx_len=8))
    front = SimServeHTTP(serve)
    front.start()
    try:
        ref = serve.submit(des_trace, "tf", n_lanes=2).result(timeout=120)
        st, body = http_request(f"{front.url}/v1/jobs", "POST", {
            "trace": {k: np.asarray(v).tolist() for k, v in arrs.items()},
            "model": "tf", "lanes": 2})
        assert st == 202, body
        done = wait_job(front.url, body["job_id"], timeout=120)
        assert done["status"] == "done"
        assert done["result"]["total_cycles"] == ref.total_cycles
        for name, bad in _not_images(arrs).items():
            if name == "nan":  # JSON holds no NaN
                continue
            st, body = http_request(f"{front.url}/v1/jobs", "POST", {
                "trace": {k: np.asarray(v).tolist() for k, v in bad.items()},
                "model": "tf", "lanes": 2})
            assert st == 400 and body["error"]["type"] == "bad_trace", (name, body)
    finally:
        front.stop(stop_service=True)


# --------------------------------------------------------------- the pack

def test_pack_holds_fifty_bytes_per_lane_step(des_trace):
    """29 int8 fields, pc and address int32, three float32 labels and the
    active flag: 50 bytes per lane-step, against 198 for the float32
    feature block, int32 keys, store flag, labels and active it replaced."""
    from repro.serving.simnet_engine import SimNetEngine

    assert sum(max(f.width, 1) * np.dtype(f.dtype).itemsize for f in F.FIELDS) + 1 == 50
    eng = SimNetEngine(None, None, SimConfig(ctx_len=8), cache=CompileCache())
    res = eng.simulate_many([F.trace_fields(des_trace)] * 3, n_lanes=[4, 2, 2], chunk=32)
    lane_steps = res["n_lanes"] * res["n_steps"]
    assert (res["n_lanes"], res["n_steps"]) == (8, 256)
    assert res["pack_buffer"]["bytes"] == 50 * lane_steps
