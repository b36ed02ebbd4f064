"""The benchmark's own arithmetic and plumbing, on the CPU at tiny sizes.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

No number here is a device measurement: the harness refuses to run on a
CPU, and these tests drive it only with its look for a chip replaced.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, counts, manifest, reference, stats, tracing, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


# ------------------------------------------------------------- arithmetic

def test_flops_match_the_program_count():
    from repro.core.predictor import PredictorConfig, inference_mflops

    c3 = manifest.load_module(ROOT / "bench/configs/c3.py")
    rb7 = manifest.load_module(ROOT / "bench/configs/rb7.py")
    c3_sizes = json.loads((ROOT / "bench/configs/c3.json").read_text())["predictor"]
    rb7_sizes = json.loads((ROOT / "bench/configs/rb7.json").read_text())["predictor"]
    assert c3.flops_per_instruction(c3_sizes) == 2 * 1_123_584
    assert c3.flops_per_instruction(c3_sizes) == 2e6 * inference_mflops(PredictorConfig())
    want = 2e6 * inference_mflops(PredictorConfig(kind="rb7", channels=(128,)))
    assert rb7.flops_per_instruction(rb7_sizes) == pytest.approx(want, rel=1e-12)


def test_step_bytes_add_up():
    c3 = manifest.load_module(ROOT / "bench/configs/c3.py")
    sizes = json.loads((ROOT / "bench/configs/c3.json").read_text())["predictor"]
    got = counts.step_counts(c3, sizes, 64, 4096)
    w = counts.weight_bytes(c3, sizes)
    assert counts.INPUT_BYTES_PER_LANE == 198
    ring = 4096 * 64 * (2 * 7 + 8) + 4096 * (41 * 4 + 5 * 4 + 8)
    assert got["bytes"] == 4096 * 198 + ring + w
    least, bound = counts.least_step_seconds(got, {"bf16_flops_per_s": 197e12,
                                                   "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and least == pytest.approx(4096 * 2 * 1_123_584 / 197e12)


def test_busy_union_and_idle_share():
    ev = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 50)]
    assert tracing.union(ev) == [(0, 20), (30, 40)]
    assert tracing.covered(ev) == 30
    assert tracing.clip(ev, 8, 32) == [(8, 10), (8, 20), (30, 32)]
    assert tracing.covered(tracing.clip(ev, 8, 32)) == 14
    assert tracing.gaps(tracing.union(ev), 0, 60) == [(20, 30), (40, 60)]
    assert tracing.idle_share(30, 60) == 0.5
    with pytest.raises(ValueError):
        tracing.idle_share(1, 0)


def test_idle_gaps_are_named_by_host_spans():
    t = {"lo": 0, "hi": 100, "devices": [{"busy": [(0, 10), (40, 50)], "busy_ns": 20}],
         "host": [("bench.submit", 12, 38), ("bench.generator_sleep", 55, 100)],
         "ops": {"fusion": 15, "convolution": 5}, "modules": {}, "window_ns": 100}
    assert tracing.idle_gaps(t) == [["bench.generator_sleep", 50e-9],
                                    ["bench.submit", 30e-9]]
    assert tracing.top_ops(t) == [["fusion", 15e-9], ["convolution", 5e-9]]


def test_percentile_counts_misses_above_every_job():
    lat = [float(i) for i in range(1, 101)]
    assert stats.percentile(lat, 50) == 50.0
    assert stats.percentile(lat, 95) == 95.0
    missed = lat[:94] + [math.inf] * 6
    assert stats.percentile(missed, 95) == math.inf
    assert stats.percentile(missed, 50) == 50.0
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def test_schedule_and_slices_repeat_per_seed():
    mix = traffic.load_mix("serve")
    a = traffic.serve_schedule(mix, 2048, 131072, 9, 2**33 + 5, 10.0)
    b = traffic.serve_schedule(mix, 2048, 131072, 9, 2**33 + 5, 10.0)
    c = traffic.serve_schedule(mix, 2048, 131072, 9, 6, 10.0)
    assert a == b and a != c
    # every seed sends the same sizes and gaps, in another order
    assert sorted(j.slice.lanes for j in a) == sorted(j.slice.lanes for j in c)
    assert len(a) == round(mix["rate_jobs_per_s"] * 10.0)
    assert a[-1].due_s == pytest.approx(10.0)
    assert all(2 <= j.slice.lanes <= 32 and j.slice.n == 2048 * j.slice.lanes for j in a)
    sw = traffic.load_mix("sweep")
    s1 = traffic.sweep_call(sw, 2048, 131072, 9, 123, 4)
    assert s1 == traffic.sweep_call(sw, 2048, 131072, 9, 123, 4)
    assert s1 != traffic.sweep_call(sw, 2048, 131072, 9, 123, 5)
    assert len(s1) == 512 and {s.lanes for s in s1} == {8}
    per_bench = np.bincount([s.bench for s in s1], minlength=9)
    assert per_bench.max() - per_bench.min() <= 1
    assert all(0 <= s.lo and s.lo + s.n <= 131072 for s in s1)


def test_check_compares_every_number_with_its_limit():
    g = check.gaps([100.0, 210.0], [100.0, 200.0])
    assert g == {"max_gap": 0.05, "pack_gap": pytest.approx(10 / 300)}
    ok, out = check.verdict({**g, "failed": 0.0},
                            {"max_gap": {"limit": 0.05}, "failed": {"limit": 0}})
    assert ok and out["max_gap"] == {"value": 0.05, "limit": 0.05}
    ok, _ = check.verdict({**g, "failed": 1.0}, {"failed": {"limit": 0}})
    assert not ok
    assert check.gaps([float("nan")], [1.0])["max_gap"] == math.inf
    s = check.sample(50, 10, 99, must=[42])
    assert 42 in s and len(s) == 10 and s == check.sample(50, 10, 99, must=[42])


# ----------------------------------------------------- files found by name

def test_every_cell_resolves_by_name():
    for w in SPEC["workloads"]:
        cell = manifest.resolve(w["name"])
        assert cell.chips == w["chips"] and cell.mix["mode"] in ("sweep", "serve")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(manifest.reader(m["name"]).read)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        assert set(cell.limits["limits"]) >= {"max_gap", "pack_gap", "failed"}


def test_a_new_cell_and_metric_are_taken_up_from_new_files(tmp_path, monkeypatch):
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "c3.slow", "config": "c3", "traffic": "slow",
                              "chips": 1, "why": "a new mix"})
    spec["per_layer"].append({"name": "probe.new", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "engine",
                              "moves": "sim_instr_per_s", "workloads": ["c3.slow"]})
    spec["end_to_end"][0]["workloads"].append("c3.slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for d in ("traffic", "limits", "metrics"):
        (tmp_path / d).mkdir()
    mix = dict(traffic.load_mix("sweep"), slices_per_call=64)
    (tmp_path / "traffic" / "slow.json").write_text(json.dumps(mix))
    shutil.copy(ROOT / "bench/limits/c3.sweep.json", tmp_path / "limits" / "c3.slow.json")
    (tmp_path / "metrics" / "probe.new.py").write_text("def read(r):\n    return 1.5\n")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path / "traffic")
    monkeypatch.setattr(manifest, "LIMITS_DIR", tmp_path / "limits")
    monkeypatch.setattr(manifest, "METRICS_DIR", tmp_path / "metrics")
    cell = manifest.resolve("c3.slow", tmp_path / "BENCHMARK.json")
    assert cell.mix["slices_per_call"] == 64
    assert [m["name"] for m in cell.per_layer] == ["probe.new"]
    assert manifest.reader("probe.new").read(None) == 1.5


# -------------------------------------------------------------- no chip

def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _run(["--workload", "c3.sweep", "--seed", "1", "--seconds", "1"], ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(["--workload", "c3.sweep", "--seed", "1", "--seconds", "1"], tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()


# ---------------------------------------------------- reference vs program

def _pool_fields(n=4096, names=("sim_loop", "sim_chase_small")):
    from bench.pool import _des_one

    return [dict(_des_one((name, n)), name=name) for name in names]


def test_reference_features_match_the_program():
    from repro.core import features as F
    from repro.des.trace import Trace

    t = _pool_fields(2048)[1]
    want = F.trace_arrays(Trace(**t))
    f, k, st = reference.features(t, 0, 2048)
    assert np.array_equal(f, want["feat"]) and np.array_equal(k, want["addr"])
    assert np.array_equal(st, want["is_store"])


@pytest.mark.parametrize("name", ["c3", "rb7"])
def test_reference_forward_matches_the_program(name):
    import jax

    from repro.core.predictor import PredictorConfig, apply_raw

    mod = manifest.load_module(ROOT / f"bench/configs/{name}.py")
    p = json.loads((ROOT / f"bench/configs/{name}.json").read_text())["predictor"]
    params = mod.init(jax.random.PRNGKey(3), p)
    x = jax.random.uniform(jax.random.PRNGKey(4), (8, p["ctx_len"] + 1, 50))
    pcfg = PredictorConfig(**dict(p, channels=tuple(p["channels"])))
    with jax.default_matmul_precision("highest"):
        want = apply_raw(params, x, pcfg)
    xp = jax.numpy.pad(x, ((0, 0), (0, mod.seq_padded(p) - x.shape[1]), (0, 0)))
    got = mod.forward(params, xp, reference.dot_f32, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ------------------------------------------------ a whole run, chip check off

@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    """A tiny c3 cell of each mode, with the harness's look for a chip
    replaced: everything else of a run is the real path."""
    import jax

    from bench import pool, run

    for d in ("configs", "traffic", "limits", "pool", "cache"):
        (tmp_path / d).mkdir()
    sizes = json.loads((ROOT / "bench/configs/c3.json").read_text())
    sizes["predictor"].update(ctx_len=8, channels=[8, 8, 8], hidden=16)
    sizes["sim"]["ctx_len"] = 8
    sizes["subtrace_instructions"] = 64
    sizes["matmul_operands"] = "float32"  # the CPU's default precision
    (tmp_path / "configs/c3.json").write_text(json.dumps(sizes))
    shutil.copy(ROOT / "bench/configs/c3.py", tmp_path / "configs/c3.py")
    pool_spec = {"benchmarks": ["sim_loop", "sim_chase_small"], "instructions": 4096}
    mixes = {
        "sweep": {"mode": "sweep", "pool": pool_spec, "slices_per_call": 8,
                  "slice_instructions": 256, "chunk": 32},
        "serve": {"mode": "serve", "pool": pool_spec, "rate_jobs_per_s": 20.0,
                  "lanes_min": 2, "lanes_max": 4,
                  "service": {"chunk": 32, "max_batch_lanes": 64, "max_wait_ms": 5.0}},
    }
    for name, mix in mixes.items():
        (tmp_path / f"traffic/{name}.json").write_text(json.dumps(mix))
        (tmp_path / f"limits/tiny.{name}.json").write_text(json.dumps(
            {"sample_lanes": 16, "limits": {"max_gap": {"limit": 1e-3},
                                            "pack_gap": {"limit": 1e-3},
                                            "failed": {"limit": 0}}}))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"] = [dict(spec["configs"][0], file=str(tmp_path / "configs/c3.json"))]
    spec["workloads"] = [
        {"name": f"tiny.{m}", "config": "c3", "traffic": m, "chips": 1, "why": "test"}
        for m in mixes]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.sweep"]
    # the serve mix's metrics, as a serve cell would list them
    spec["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny.serve"]}
        for n in ("job_p95_ms", "job_p50_ms")]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock", "layer": "l",
         "moves": "job_p95_ms", "workloads": ["tiny.serve"]}
        for n, u in (("device.idle_share.serve", "%"), ("engine.prep_ms.serve", "ms"),
                     ("service.lane_occupancy.serve", "%"),
                     ("session.submit_ms.serve", "ms"))]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(manifest, "MANIFEST", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(manifest, "LIMITS_DIR", tmp_path / "limits")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path / "traffic")
    monkeypatch.setattr(pool, "POOL_DIR", tmp_path / "pool")
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(run, "find_chips", lambda n: {"platform": "cpu", "kind": "cpu",
                                                      "count": n})
    from repro.serving.compile_cache import global_cache

    global_cache().clear()
    yield run
    global_cache().clear()
    jax.config.update("jax_compilation_cache_dir", None)


def _result(capsys):
    cap = capsys.readouterr()
    if os.environ.get("BENCH_TEST_SHOW"):
        with capsys.disabled():
            print(cap.err[-3000:], cap.out[-3000:])
    return json.loads(cap.out.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["sweep", "serve"])
def test_tiny_run_is_correct(tiny, capsys, mode):
    assert tiny.main(["--workload", f"tiny.{mode}", "--seed", str(2**33 + 1),
                      "--seconds", "1.5"]) == 0
    res = _result(capsys)
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {"setup_s", "sim_instr_per_s"} if mode == "sweep" else {
        "setup_s", "job_p95_ms", "job_p50_ms"}
    assert set(res["metrics"]) == want
    assert res["device"]["platform"] == "cpu"


def test_tiny_traced_run_reports_no_cpu_device_metric(tiny, capsys):
    # the CPU backend's trace has no TPU plane: the harness refuses to
    # read a device metric from it instead of reporting a CPU number
    with pytest.raises(ValueError, match="no TPU device plane"):
        tiny.main(["--workload", "tiny.sweep", "--seed", "3", "--seconds", "1",
                   "--trace", "1"])


def test_control_fails_where_the_program_passes(tiny, capsys):
    """The control (the reference in the program's place, its matrix
    products in float8) reads far above the program on the same sample."""
    from bench import calibrate

    assert calibrate.main(["--workload", "tiny.sweep", "--seeds", "5", "6"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    rows, summary = lines[:-1], lines[-1]
    assert [r["seed"] for r in rows] == [5, 6]
    assert all(r["compared"] > 0 and r["failed"] == 0 for r in rows)
    limits = json.loads((manifest.LIMITS_DIR / "tiny.sweep.json").read_text())["limits"]
    assert summary["program.max_gap"]["max"] <= limits["max_gap"]["limit"]
    assert summary["control.max_gap"]["min"] > limits["max_gap"]["limit"]
    assert all(r["program_correct"] and not r["control_correct"] for r in rows)
    assert summary["program_correct"] and not summary["control_correct_on_any_seed"]


def test_knee_reports_each_rate(tiny, capsys):
    from bench import knee

    assert knee.main(["--workload", "tiny.serve", "--rates", "10", "30",
                      "--seconds", "1"]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert [r["rate"] for r in rows] == [10.0, 30.0]
    assert [r["jobs"] for r in rows] == [10, 30] and all(r["failed"] == 0 for r in rows)


def _fault_state_unchanged(mp):
    from repro.core import simulator

    mp.setattr(simulator, "sim_step", lambda state, *a, **k: state)


def _fault_half_batch(mp):
    """Totals from every other lane, doubled: the mean over half the batch."""
    from repro.serving import simnet_engine

    real = simnet_engine.workload_totals

    def half(state, packed):
        keep = (np.arange(packed.n_lanes) % 2 == 0).astype(np.float32)
        lane, cycles, over = real(state._replace(
            cur_tick=state.cur_tick * keep * 2,
            valid=state.valid & (keep[:, None] > 0)), packed)
        return lane, cycles, over

    mp.setattr(simnet_engine, "workload_totals", half)


def _fault_no_exchange(mp):
    """Per-workload sums from the first quarter of the lanes only, as if
    each of four chips kept its own lanes' cycles."""
    from repro.serving import simnet_engine

    real = simnet_engine.workload_totals

    def local(state, packed):
        keep = np.arange(packed.n_lanes) < packed.n_lanes // 4
        return real(state._replace(cur_tick=state.cur_tick * keep,
                                   valid=state.valid & keep[:, None]), packed)

    mp.setattr(simnet_engine, "workload_totals", local)


def _fault_answer_altered(mp):
    """One more cycle on every predicted fetch latency, where it is made."""
    from repro.serving import simnet_engine

    real = simnet_engine.decode_latency
    mp.setattr(simnet_engine, "decode_latency",
               lambda raw, cfg: real(raw, cfg).at[:, 0].add(1.0))


FAULTS = {"state_unchanged": _fault_state_unchanged, "half_batch": _fault_half_batch,
          "no_exchange": _fault_no_exchange, "answer_altered": _fault_answer_altered}


@pytest.mark.parametrize("mode,fault", [
    ("sweep", "state_unchanged"), ("sweep", "half_batch"), ("sweep", "no_exchange"),
    ("sweep", "answer_altered"), ("serve", "state_unchanged"), ("serve", "answer_altered")])
def test_a_broken_timed_path_is_not_correct(tiny, capsys, monkeypatch, mode, fault):
    FAULTS[fault](monkeypatch)
    assert tiny.main(["--workload", f"tiny.{mode}", "--seed", "17", "--seconds", "1"]) == 0
    res = _result(capsys)
    assert res["correct"] is False, res["check"]
