"""SimServe service layer: compile cache, lane bucketing, continuous
batching, service-vs-session bit-identity, `repro serve` batch mode.

The two contract guards for the SimServe redesign:
  * jobs submitted through the service produce cycles identical to the
    direct `SimNet.simulate_many` path (same pack, same executables);
  * a zoo sweep (≥3 models × ≥3 workloads) compiles each distinct
    (kind, lane bucket, chunk) executable exactly once — hits ≥ misses.
"""
import json

import pytest

from conftest import synth_arrays
from repro.core import features as F
from repro.core.api import SimNet
from repro.core.simulator import SimConfig, simulate_many as core_simulate_many
from repro.des.o3 import O3Config, O3Simulator
from repro.des.workloads import get_benchmark
from repro.serving.compile_cache import (
    CompileCache,
    chunk_bucket,
    global_cache,
    lane_bucket,
)
from repro.serving.service import SimServe

STYLES = ["mlb_stream", "sim_loop", "mlb_branchy"]
SIZES = [3000, 2000, 2600]  # ragged on purpose


@pytest.fixture(scope="module")
def traces():
    sim = O3Simulator(O3Config())
    return [sim.run(get_benchmark(n, s)) for n, s in zip(STYLES, SIZES)]


@pytest.fixture(scope="module")
def arrs(traces):
    return [F.trace_arrays(t) for t in traces]


# ------------------------------------------------------------- bucket maths

def test_lane_bucket_powers_of_two():
    assert [lane_bucket(n) for n in (1, 2, 3, 5, 8, 9, 64)] == [1, 2, 4, 8, 8, 16, 64]
    with pytest.raises(ValueError):
        lane_bucket(0)


def test_chunk_bucket_rounds_and_caps():
    assert chunk_bucket(650, 1024) == 1024
    assert chunk_bucket(500, 1024) == 512
    assert chunk_bucket(5000, 1024) == 1024  # capped: stream in 1024-chunks
    assert chunk_bucket(1, 1024) == 1


def test_compile_cache_counts_hits_and_misses():
    cache = CompileCache()
    calls = []
    key_a = ("a",)  # the cache is shape-agnostic about its keys

    def build():
        calls.append(1)
        return lambda: "exe"

    assert cache.get(key_a, build) is cache.get(key_a, build)
    assert len(calls) == 1
    st = cache.stats()
    assert (st["hits"], st["misses"], st["n_executables"]) == (1, 1, 1)
    cache.clear()
    assert cache.stats()["n_executables"] == 0


def test_persistent_cache_honours_env_dir(tmp_path, monkeypatch):
    import jax

    from repro.serving.compile_cache import enable_persistent_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_persistent_cache() == str(tmp_path / "jc")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "jc")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_persistent_cache_default_is_fixed_in_the_checkout(tmp_path, monkeypatch):
    """Unset, the cache sits at <checkout>/.jax_cache whatever the cwd or
    the process: another process in another directory finds the same one."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    import jax

    from repro.serving.compile_cache import enable_persistent_cache

    checkout = Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    was = jax.config.jax_compilation_cache_dir
    try:
        here = enable_persistent_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert here == str(checkout / ".jax_cache")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(checkout / "src")
    other = subprocess.run(
        [sys.executable, "-c", "from repro.serving.compile_cache import "
         "enable_persistent_cache as e; print(e())"],
        cwd=tmp_path.parent, env=env, capture_output=True, text=True, timeout=120,
    )
    assert other.returncode == 0, other.stderr[-2000:]
    assert other.stdout.strip().splitlines()[-1] == here


# --------------------------------------------------- service vs session

def test_service_matches_session_bit_identical(traces):
    """Jobs submitted through SimServe produce cycles identical to the
    direct SimNet.simulate_many pack of the same workloads."""
    cfg = SimConfig(ctx_len=32)
    sn = SimNet(sim_cfg=cfg)
    ref = sn.simulate_many(traces, n_lanes=[4, 2, 8])

    serve = SimServe()
    serve.register("tf32", sim_cfg=cfg)
    handles = [
        serve.submit(tr, "tf32", n_lanes=ln)
        for tr, ln in zip(traces, [4, 2, 8])
    ]
    serve.drain()
    assert all(h.done() for h in handles)
    for h, w_ref in zip(handles, ref):
        w = h.result()
        assert w.total_cycles == w_ref.total_cycles
        assert w.overflow == w_ref.overflow
        assert w.n_instructions == w_ref.n_instructions
    st = serve.stats()
    assert st["batches"] == 1  # one shared lane batch for all three requests
    assert st["jobs_completed"] == 3


def test_result_drains_lazily(traces):
    serve = SimServe()
    h = serve.submit(traces[0], n_lanes=2, sim_cfg=SimConfig(ctx_len=16))
    assert not h.done() and serve.pending == 1
    w = h.result()  # implicit drain
    assert h.done() and serve.pending == 0
    assert w.total_cycles > 0


def test_pack_buffer_reused_batch_after_batch(traces):
    """The engine packs every batch into the host buffer it keeps: the first
    batch allocates it, a batch of the same shape reuses it, and the
    counts show on each BatchReport and in stats()."""
    serve = SimServe(cache=CompileCache())
    serve.register("tf16", sim_cfg=SimConfig(ctx_len=16))
    reports = []
    for _ in range(2):
        for tr in traces:
            serve.submit(tr, "tf16", n_lanes=2)
        reports += serve.drain()
    assert [r.pack_buffer["allocations"] for r in reports] == [1, 0]
    assert [r.pack_buffer["reuses"] for r in reports] == [0, 1]
    held = reports[0].pack_buffer["bytes"]
    assert held > 0 and reports[1].pack_buffer["bytes"] == held
    assert serve.stats()["pack_buffer"] == {"reuses": 1, "allocations": 1, "bytes": held}


def test_incompatible_sim_cfg_rejected_at_submit(traces):
    """SimConfig fields the pack cannot replay per lane (max_latency here)
    are baked into the resident executable — a mismatching job must fail
    loudly at submit, never silently simulate with the engine's values."""
    serve = SimServe()
    serve.register("tf", sim_cfg=SimConfig(ctx_len=16))
    serve.submit(traces[0], "tf", n_lanes=1, sim_cfg=SimConfig(ctx_len=16))
    with pytest.raises(ValueError, match="only ctx_len/retire_width"):
        serve.submit(traces[1], "tf", n_lanes=1,
                     sim_cfg=SimConfig(ctx_len=16, max_latency=50.0))
    # differing per-lane fields remain batchable
    serve.submit(traces[1], "tf", n_lanes=1,
                 sim_cfg=SimConfig(ctx_len=8, retire_width=2))
    reports = serve.drain()
    assert len(reports) == 1 and reports[0].n_jobs == 2


def test_oversized_job_gets_own_batch_never_wedges(traces):
    """A single job wider than max_batch_lanes still runs (own batch)
    instead of deadlocking the queue."""
    serve = SimServe(max_batch_lanes=4)
    h_big = serve.submit(traces[0], n_lanes=6, sim_cfg=SimConfig(ctx_len=16))
    h_small = serve.submit(traces[1], n_lanes=2, sim_cfg=SimConfig(ctx_len=16))
    reports = serve.drain()
    assert [r.n_jobs for r in reports] == [1, 1]
    assert h_big.result().total_cycles > 0
    assert h_small.result().total_cycles > 0
    assert serve.pending == 0


def test_unknown_model_rejected(traces):
    serve = SimServe()
    with pytest.raises(KeyError, match="no resident model"):
        serve.submit(traces[0], "nope")


def test_invalid_lane_count_rejected_at_submit(traces):
    """A job that cannot fill its lanes is refused at submit — at drain it
    would detonate the shared batch and poison valid batchmates."""
    serve = SimServe()
    with pytest.raises(ValueError, match="n_lanes=9999 invalid"):
        serve.submit(traces[0], n_lanes=9999)
    with pytest.raises(ValueError, match="n_lanes=0 invalid"):
        serve.submit(traces[0], n_lanes=0)
    assert serve.pending == 0


def test_ctx_len_wider_than_engine_rejected_at_submit(traces):
    """The predictor input width is fixed at registration; a wider job ctx
    must be refused at submit, not detonate (and drop batchmates) at drain."""
    serve = SimServe()
    serve.register("tf16", sim_cfg=SimConfig(ctx_len=16))
    with pytest.raises(ValueError, match="exceeds resident model"):
        serve.submit(traces[0], "tf16", sim_cfg=SimConfig(ctx_len=32))


def test_failed_batch_pins_error_on_jobs(traces, monkeypatch):
    """If a batch dies mid-run its jobs must not vanish silently:
    result() re-raises the batch failure instead of returning None."""
    serve = SimServe()
    h = serve.submit(traces[0], n_lanes=2, sim_cfg=SimConfig(ctx_len=16))
    monkeypatch.setattr(
        serve.registry.get(h.model_id), "simulate_many",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("device lost")),
    )
    with pytest.raises(RuntimeError, match="device lost"):
        serve.drain()
    with pytest.raises(RuntimeError, match="failed in its batch"):
        h.result()


def test_cancel_withdraws_pending_job(traces):
    serve = SimServe()
    h = serve.submit(traces[0], n_lanes=2, sim_cfg=SimConfig(ctx_len=16))
    assert serve.cancel(h) and serve.pending == 0
    assert not serve.cancel(h)  # already gone
    assert serve.drain() == []
    with pytest.raises(RuntimeError, match="was cancelled"):
        h.result()  # never silently None


def test_session_failed_submit_leaves_no_orphans(traces):
    """A per-workload validation failure mid-submit must unwind the jobs
    already queued — the next simulate call's batch must not inherit them."""
    sn = SimNet(sim_cfg=SimConfig(ctx_len=16))
    with pytest.raises(ValueError, match="n_lanes=9999 invalid"):
        sn.simulate_many(traces, n_lanes=[2, 9999, 2])
    assert sn.service.pending == 0
    res = sn.simulate(traces[1], n_lanes=2)  # clean follow-up call
    assert len(res) == 1 and res[0].name == traces[1].name


def test_session_rejects_mismatched_sequence_lengths(traces):
    """A short per-workload n_lanes/sim_cfgs list must raise, not silently
    drop the unmatched workloads."""
    sn = SimNet(sim_cfg=SimConfig(ctx_len=16))
    with pytest.raises(ValueError, match="n_lanes has 2 entries"):
        sn.simulate_many(traces, n_lanes=[2, 2])
    with pytest.raises(ValueError, match="sim_cfgs has 1 entries"):
        sn.simulate_many(traces, n_lanes=1, sim_cfgs=[SimConfig(ctx_len=16)])


# ------------------------------------------------------- the zoo acceptance

def test_zoo_sweep_compiles_each_executable_once(traces):
    """≥3 models × ≥3 workloads through one SimServe: every model of the
    same (kind, bucket, chunk) shape reuses ONE compiled executable
    (hits ≥ misses), and per-workload cycles are bit-identical to the
    direct SimNet.simulate_many path for each model."""
    import jax
    from repro.core.predictor import PredictorConfig, init_predictor

    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    zoo = {
        f"m{i}": init_predictor(jax.random.PRNGKey(i), pcfg)[0]
        for i in range(3)
    }
    cache = CompileCache()  # private: exact hit/miss accounting
    serve = SimServe(cache=cache, chunk=512)
    for mid, params in zoo.items():
        serve.register(mid, params=params, pcfg=pcfg,
                       sim_cfg=SimConfig(ctx_len=16))
    handles = {
        (mid, tr.name): serve.submit(tr, mid, n_lanes=2)
        for mid in zoo for tr in traces
    }
    serve.drain()

    st = serve.stats()
    assert st["batches"] == 3  # one shared batch per resident model
    # all three batches have the same (kind, lane bucket, chunk) → exactly
    # one compile, reused by the other two models
    assert st["cache"]["misses"] == 1
    assert st["cache"]["hits"] >= st["cache"]["misses"]
    assert st["cache"]["n_executables"] == 1

    # bit-identity against the direct session path, per model
    for mid, params in zoo.items():
        sn = SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(ctx_len=16),
                    cache=cache, chunk=512)
        ref = sn.simulate_many(traces, n_lanes=2)
        for tr, w_ref in zip(traces, ref):
            assert handles[(mid, tr.name)].result().total_cycles == w_ref.total_cycles
    # the session runs hit the same resident executable: still no recompiles
    assert cache.stats()["misses"] == 1


# ------------------------------------------------- bucketing exactness

def test_dead_lane_masking_exact_vs_unbucketed():
    """5 live lanes bucket to 8; the three dead lanes must contribute
    exactly nothing (bit-identical totals vs the unbucketed core scan)."""
    jobs = [synth_arrays(96, 0), synth_arrays(80, 1)]
    lanes = [3, 2]
    cfg = SimConfig(ctx_len=8)
    ref = core_simulate_many([F.trace_fields(j) for j in jobs], None, cfg, n_lanes=lanes)
    res = SimNet(sim_cfg=cfg).simulate_many(jobs, n_lanes=lanes)
    for i, w in enumerate(res):
        assert w.total_cycles == float(ref["workload_cycles"][i])
        assert w.overflow == int(ref["workload_overflow"][i])


# (the randomized version of this invariant — arbitrary job mixes through
# the service vs the unbucketed core scan — is the hypothesis property
# test in tests/test_property.py::test_service_bucketing_never_changes_totals)


# ------------------------------------------------------------- CLI smoke

def test_cli_serve_smoke(tmp_path, capsys):
    """`python -m repro serve` batch mode (the CI fast-tier smoke): tiny
    teacher-forced job file → per-job JSON results + service stats."""
    from repro.cli import main

    spec = {
        "jobs": [
            {"id": "a", "bench": "sim_loop", "n": 2000, "lanes": 1},
            {"id": "b", "bench": "mlb_stream", "n": 2000, "lanes": 2},
        ]
    }
    jobs = tmp_path / "jobs.json"
    jobs.write_text(json.dumps(spec))
    rc = main(["serve", "--jobs", str(jobs), "--cache-dir", str(tmp_path / "tr")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert [j["id"] for j in out["jobs"]] == ["a", "b"]
    # teacher-forced at 1 lane reproduces the DES total exactly
    assert out["jobs"][0]["result"]["cpi_error"] == 0.0
    assert out["stats"]["jobs_completed"] == 2
    assert out["stats"]["models_resident"] == ["teacher-forced"]
    assert {"hits", "misses", "compile_seconds"} <= set(out["stats"]["cache"])
    assert len(out["batches"]) >= 1
