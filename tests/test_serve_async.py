"""Async SimServe: the background drain loop, admission control, per-model
fairness, and regression tests for the concurrency bugs that blocked them.

The acceptance guard is the threaded stress test: ≥4 client threads
submitting against ≥2 resident models while the background loop drains,
with per-workload totals bit-identical to a sequential one-batch-per-job
baseline, jobs_per_batch > 1, and zero lost or duplicated jobs.

Workloads here are tiny synthetic trace_arrays dicts (teacher-forced label
replay) so the whole file stays in the fast tier — the concurrency
machinery under test is identical for predictor models.
"""
import threading
import time

import pytest

from conftest import synth_arrays
from repro.core.session import SimNet
from repro.core.simulator import SimConfig
from repro.serving.compile_cache import CompileCache
from repro.serving.registry import TEACHER_FORCED, ModelRegistry
from repro.serving.service import QueueFull, SimServe

CFG = SimConfig(ctx_len=8)


TRACES = {f"w{i}": synth_arrays(64 + 16 * i, i) for i in range(4)}
MODELS = ("alpha", "beta")  # ≥2 resident models (label-replay engines)


def _make_serve(**kw):
    serve = SimServe(**kw)
    for mid in MODELS:
        serve.register(mid, sim_cfg=CFG)
    return serve


# ------------------------------------------------ the acceptance stress test

def test_threaded_clients_match_sequential_baseline():
    """4 client threads × 2 resident models through the background loop:
    totals bit-identical to one-batch-per-job sequential dispatch, batches
    actually shared (jobs_per_batch > 1), no job lost or duplicated."""
    jobs = [(mid, name) for mid in MODELS for name in TRACES]  # 8 distinct
    n_clients = 4

    # baseline: one batch per job, fully sequential
    seq = _make_serve(cache=CompileCache())
    baseline = {}
    for mid, name in jobs:
        h = seq.submit(TRACES[name], mid, n_lanes=2)
        seq.drain()
        baseline[(mid, name)] = (h.result().total_cycles, h.result().overflow)
    assert seq.stats()["jobs_per_batch"] == 1.0

    serve = _make_serve(cache=CompileCache(), max_wait_ms=30.0)
    results = {}
    errors = []
    gate = threading.Barrier(n_clients)

    def client(c):
        try:
            gate.wait(timeout=10)
            # every client submits the full grid — same workload from
            # different clients must pack, not collide
            handles = [
                (mid, name, serve.submit(TRACES[name], mid, n_lanes=2))
                for mid, name in jobs
            ]
            for mid, name, h in handles:
                w = h.result(timeout=120)
                results[(c, mid, name)] = (w.total_cycles, w.overflow)
        except Exception as e:  # pragma: no cover - failure readout
            errors.append(e)

    with serve:
        threads = [threading.Thread(target=client, args=(c,)) for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
    assert not errors
    assert len(results) == n_clients * len(jobs)  # nothing lost
    for (c, mid, name), got in results.items():
        assert got == baseline[(mid, name)], (c, mid, name)

    st = serve.stats()
    assert st["jobs_submitted"] == st["jobs_completed"] == n_clients * len(jobs)
    assert st["jobs_pending"] == 0
    assert st["loop_errors"] == 0
    # batches were genuinely shared across clients
    assert st["jobs_per_batch"] > 1
    # ...and no job ran twice: every dispatched job id is unique
    dispatched = [jid for b in serve.batches for jid in b.job_ids]
    assert len(dispatched) == len(set(dispatched)) == st["jobs_completed"]


# ------------------------------------------------------- lifecycle + results

def test_background_loop_completes_without_client_drain():
    with _make_serve(max_wait_ms=1.0) as serve:
        assert serve.running
        h = serve.submit(TRACES["w0"], "alpha", n_lanes=2)
        w = h.result(timeout=60)  # blocks on the job event, never drains
        assert w.total_cycles > 0
    assert not serve.running
    assert serve.stats()["running"] is False


def test_start_stop_idempotent_and_stop_drains_stragglers():
    serve = _make_serve(max_wait_ms=0.0)
    assert serve.start() is serve.start()  # idempotent
    serve.stop()
    serve.stop()  # no-op on a stopped service
    # jobs accepted before stop() are not abandoned: stop drains inline
    h = serve.submit(TRACES["w1"], "beta", n_lanes=2)
    serve.stop()
    assert h.done() and h.result().total_cycles > 0


def test_result_timeout_raises_instead_of_draining():
    serve = _make_serve()  # not started: nothing will run the queue
    h = serve.submit(TRACES["w0"], "alpha", n_lanes=2)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="did not complete"):
        h.result(timeout=0.05)
    assert time.monotonic() - t0 < 5
    assert serve.pending == 1  # the timed-out wait ran nothing
    assert h.result().total_cycles > 0  # sync fallback still drains


def test_wait_reports_completion():
    serve = _make_serve()
    h = serve.submit(TRACES["w2"], "alpha", n_lanes=2)
    assert h.wait(timeout=0.01) is False
    serve.drain()
    assert h.wait(timeout=0.01) is True and h.done()


# ------------------------------------------------------- admission control

def test_queue_full_backpressure():
    serve = _make_serve(max_queue_depth=2)
    serve.submit(TRACES["w0"], "alpha", n_lanes=2)
    serve.submit(TRACES["w1"], "alpha", n_lanes=2)
    with pytest.raises(QueueFull, match="max_queue_depth=2"):
        serve.submit(TRACES["w2"], "alpha", n_lanes=2)
    st = serve.stats()
    assert st["jobs_rejected"] == 1 and st["jobs_pending"] == 2  # nothing enqueued
    serve.drain()
    h = serve.submit(TRACES["w2"], "alpha", n_lanes=2)  # admitted again
    serve.drain()
    assert h.result().total_cycles > 0


# ------------------------------------------------------ per-model fairness

def test_round_robin_across_models_prevents_starvation():
    """With model alpha's backlog needing 3 batches, beta's single job —
    submitted LAST — rides the second dispatch, not the fourth."""
    serve = _make_serve(max_batch_lanes=4)
    for _ in range(6):
        serve.submit(TRACES["w0"], "alpha", n_lanes=2)  # 3 batches of 2 jobs
    serve.submit(TRACES["w1"], "beta", n_lanes=2)
    reports = serve.drain()
    assert [r.model_id for r in reports] == ["alpha", "beta", "alpha", "alpha"]
    assert serve.pending == 0


# ------------------------------------------------- satellite bug regressions

def test_result_on_failed_job_never_runs_unrelated_jobs(monkeypatch):
    """An already-failed job must re-raise its recorded batch error without
    draining: before the fix, result() saw done()==False and ran OTHER
    clients' queued jobs on this thread as a side effect."""
    serve = _make_serve()
    h_bad = serve.submit(TRACES["w0"], "alpha", n_lanes=2)
    engine = serve.registry.get("alpha")
    real = engine.simulate_many
    monkeypatch.setattr(
        engine, "simulate_many",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("device lost")),
    )
    with pytest.raises(RuntimeError, match="device lost"):
        serve.drain()
    monkeypatch.setattr(engine, "simulate_many", real)  # device "recovers"
    h_other = serve.submit(TRACES["w1"], "alpha", n_lanes=2)  # unrelated client
    with pytest.raises(RuntimeError, match="failed in its batch"):
        h_bad.result()
    assert not h_other.done() and serve.pending == 1  # result() ran nothing
    serve.drain()
    assert h_other.result().total_cycles > 0


def test_ensure_teacher_forced_race_registers_once(monkeypatch):
    """Two concurrent submit(trace) calls (model_id=None) must resolve to
    ONE teacher-forced resident. The engine build is slowed so the old
    check-then-act window reliably raced ('already registered')."""
    import repro.serving.registry as reg

    real_engine = reg.SimNetEngine

    def slow_engine(*a, **k):
        time.sleep(0.05)  # widen the check→add window
        return real_engine(*a, **k)

    monkeypatch.setattr(reg, "SimNetEngine", slow_engine)
    registry = ModelRegistry()
    gate = threading.Barrier(2)
    errors = []

    def ensure():
        try:
            gate.wait(timeout=10)
            registry.ensure_teacher_forced(CFG)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=ensure) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert len(registry) == 1 and TEACHER_FORCED in registry


def test_concurrent_default_job_names_are_unique():
    """Default names derive from the lock-minted job id — the old fallback
    read the submitted-jobs counter outside the lock and minted colliding
    names under concurrent submits."""
    serve = _make_serve()
    handles = []
    hlock = threading.Lock()
    gate = threading.Barrier(8)

    def client():
        gate.wait(timeout=10)
        hs = [serve.submit(TRACES["w0"], "alpha", n_lanes=2) for _ in range(10)]
        with hlock:
            handles.extend(hs)

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    names = [h._job.name for h in handles]
    assert len(names) == 80
    assert len(set(names)) == 80  # no collisions
    assert all(n == f"job{h.job_id}" for n, h in zip(names, handles))


def test_cancel_pending_yes_inflight_no():
    """cancel() withdraws a queued job but cannot recall an in-flight one:
    once a batch took the job, cancel returns False and the result stands."""
    serve = _make_serve()
    h_pending = serve.submit(TRACES["w0"], "alpha", n_lanes=2)
    assert serve.cancel(h_pending) is True
    with pytest.raises(RuntimeError, match="was cancelled"):
        h_pending.result()

    h_run = serve.submit(TRACES["w1"], "alpha", n_lanes=2)
    took = threading.Event()
    real_take = serve._take_batch

    def spying_take():
        out = real_take()
        took.set()
        return out

    serve._take_batch = spying_take
    cancel_result = {}

    def cancel_late():
        took.wait(timeout=30)  # the batch holds the job now
        cancel_result["inflight"] = serve.cancel(h_run)

    t = threading.Thread(target=cancel_late)
    t.start()
    serve.drain()
    t.join(timeout=30)
    assert cancel_result["inflight"] is False
    assert h_run.result().total_cycles > 0  # completed despite the cancel


# ---------------------------------------------- compile-cache concurrency

def test_compile_cache_failed_build_not_counted_not_poisoned():
    cache = CompileCache()
    key = ("k",)

    def bad():
        raise RuntimeError("lowering exploded")

    with pytest.raises(RuntimeError, match="lowering exploded"):
        cache.get(key, bad)
    st = cache.stats()
    assert (st["hits"], st["misses"], st["n_executables"]) == (0, 0, 0)
    assert st["compile_seconds"] == 0.0
    # the key is not wedged: the next get retries and succeeds
    assert cache.get(key, lambda: "exe") == "exe"
    assert cache.stats()["misses"] == 1


def test_compile_cache_same_key_compiles_once_across_threads():
    cache = CompileCache()
    builds = []
    gate = threading.Barrier(4)
    results = []

    def build():
        builds.append(1)
        time.sleep(0.05)  # long enough that all waiters queue behind it
        return "exe"

    def worker():
        gate.wait(timeout=10)
        results.append(cache.get(("k",), build))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(builds) == 1  # one compile, three future-waiters
    assert results == ["exe"] * 4
    st = cache.stats()
    assert (st["hits"], st["misses"]) == (3, 1)


def test_compile_cache_different_keys_compile_in_parallel():
    """Two distinct keys must not serialize behind one global lock: with
    each build sleeping 0.3s, parallel compiles finish in well under the
    0.6s a serialized cache needs (sleep releases the GIL, so the only
    way to exceed the bound is lock contention)."""
    cache = CompileCache()
    gate = threading.Barrier(2)

    def worker(key):
        gate.wait(timeout=10)
        cache.get((key,), lambda: time.sleep(0.3) or key)

    threads = [threading.Thread(target=worker, args=(k,)) for k in ("a", "b")]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    elapsed = time.monotonic() - t0
    assert elapsed < 0.5, f"builds serialized: {elapsed:.2f}s"
    assert cache.stats()["misses"] == 2


def test_compile_cache_clear_mid_build_stays_cleared():
    """A build racing clear() must not repopulate the wiped cache or bump
    its reset counters — its waiters still receive the executable."""
    cache = CompileCache()
    in_build = threading.Event()
    release = threading.Event()
    got = []

    def slow_build():
        in_build.set()
        release.wait(timeout=30)
        return "exe"

    t = threading.Thread(target=lambda: got.append(cache.get(("k",), slow_build)))
    t.start()
    assert in_build.wait(timeout=10)
    cache.clear()  # wipes while the build is in flight
    release.set()
    t.join(timeout=30)
    assert got == ["exe"]  # the caller still got its executable...
    st = cache.stats()
    assert (st["misses"], st["n_executables"]) == (0, 0)  # ...the cache stayed cleared
    assert st["compile_seconds"] == 0.0


def test_compile_cache_hit_lookup_not_blocked_by_other_keys_compile():
    cache = CompileCache()
    cache.get(("hot",), lambda: "hot-exe")
    in_build = threading.Event()
    release = threading.Event()

    def slow_build():
        in_build.set()
        release.wait(timeout=30)
        return "cold-exe"

    t = threading.Thread(target=lambda: cache.get(("cold",), slow_build))
    t.start()
    assert in_build.wait(timeout=10)
    t0 = time.monotonic()
    assert cache.get(("hot",), lambda: "never") == "hot-exe"  # mid-compile hit
    assert time.monotonic() - t0 < 1.0
    release.set()
    t.join(timeout=30)
    assert cache.stats()["n_executables"] == 2


# ------------------------------------------------- session background mode

def test_session_background_matches_sync_session():
    ref = SimNet(sim_cfg=CFG).simulate_many(list(TRACES.values()), n_lanes=2)
    with SimNet(sim_cfg=CFG, background=True) as sn:
        assert sn.service.running
        res = sn.simulate_many(list(TRACES.values()), n_lanes=2)
    assert not sn.service.running  # close() stopped the private loop
    for w, w_ref in zip(res, ref):
        assert w.total_cycles == w_ref.total_cycles
        assert w.overflow == w_ref.overflow


# ------------------------------------------------------------- CLI smoke

def test_cli_serve_async_smoke(tmp_path, capsys):
    """`python -m repro serve --async` (the CI fast-tier smoke): background
    drain loop + admission flags produce the same per-job JSON shape."""
    import json

    from repro.cli import main

    spec = {
        "jobs": [
            {"id": "a", "bench": "sim_loop", "n": 2000, "lanes": 1},
            {"id": "b", "bench": "mlb_stream", "n": 2000, "lanes": 2},
        ]
    }
    jobs = tmp_path / "jobs.json"
    jobs.write_text(json.dumps(spec))
    rc = main([
        "serve", "--jobs", str(jobs), "--cache-dir", str(tmp_path / "tr"),
        "--async", "--max-queue-depth", "64", "--max-wait-ms", "5",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "async"
    assert [j["id"] for j in out["jobs"]] == ["a", "b"]
    assert out["jobs"][0]["result"]["cpi_error"] == 0.0
    assert out["stats"]["jobs_completed"] == 2
    assert out["stats"]["jobs_rejected"] == 0
    assert out["stats"]["running"] is False  # stopped before emit
    assert out["stats"]["max_queue_depth"] == 64


def test_cli_serve_sync_queue_depth_backpressure(tmp_path, capsys):
    """A job file deeper than --max-queue-depth must apply backpressure
    (drain-and-retry), not crash the CLI with an uncaught QueueFull."""
    import json

    from repro.cli import main

    spec = {"jobs": [
        {"id": f"j{i}", "bench": "sim_loop", "n": 2000, "lanes": 1}
        for i in range(3)
    ]}
    jobs = tmp_path / "jobs.json"
    jobs.write_text(json.dumps(spec))
    rc = main([
        "serve", "--jobs", str(jobs), "--cache-dir", str(tmp_path / "tr"),
        "--max-queue-depth", "1",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert [j["id"] for j in out["jobs"]] == ["j0", "j1", "j2"]
    assert out["stats"]["jobs_completed"] == 3
    assert out["stats"]["jobs_rejected"] >= 2  # backpressure fired and recovered


# ---------------------------------------------------------- stats atomicity

def test_stats_counter_snapshot_is_atomic():
    """Failing-before regression: stats() used to read each counter
    without the queue lock, so a dispatch mid-update could be observed
    halfway through (jobs_completed already bumped, batches not yet) and
    the jobs_per_batch readout went momentarily wrong. The counter block
    now copies under _qlock: a reader landing mid-update blocks until the
    writer finishes instead of returning the torn state."""
    serve = _make_serve(cache=CompileCache())
    for name in ("w0", "w1"):
        serve.submit(TRACES[name], "alpha", n_lanes=2)
    serve.drain()  # one 2-job batch: jobs_completed=2, batches=1

    done = threading.Event()
    snap = {}

    def read():
        snap["stats"] = serve.stats()
        done.set()

    # freeze a dispatch mid-counter-update: lock held, jobs_completed
    # bumped, the batch counter not yet
    with serve._qlock:
        serve._jobs_completed += 3
        t = threading.Thread(target=read)
        t.start()
        assert not done.wait(0.3)  # pre-fix, stats() returned the tear here
        serve._jobs_completed -= 3  # the writer completes consistently
    t.join(10)
    assert done.is_set()
    s = snap["stats"]
    assert s["jobs_completed"] == 2 and s["batches"] == 1
    assert s["jobs_per_batch"] * s["batches"] == s["jobs_completed"]
