"""Fleet process management: replica subprocesses, the CLI entry, and
the subprocess-level failure drill.

`ReplicaProcess` lifecycle and failure cleanup are covered with cheap
fake commands (``cmd=`` override — no JAX import); the real-subprocess
paths (`repro fleet` CLI smoke, kill → resubmit → readmit) spawn actual
``python -m repro serve --http 0`` replicas. Replica startup is ~1 s in
this container, so the 2-replica CLI smoke stays in the fast tier; the
full failure drill is marked slow.
"""
import json
import os
import sys
import threading
import time

import pytest

from repro.serving import fleet as fleet_mod
from repro.serving.fleet import Fleet, ReplicaProcess, ReplicaSpawnError, _repro_env


def _wait_until(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


# ------------------------------------------------------- ReplicaProcess

def test_replica_command_composition(tmp_path):
    r = ReplicaProcess("r3", models={"b": "art/b", "a": "art/a"},
                       max_queue_depth=7, max_wait_ms=2.5, chunk=512,
                       cache_dir=str(tmp_path / "tr"),
                       log_dir=str(tmp_path))
    cmd = r.command()
    assert cmd[:5] == [sys.executable, "-u", "-m", "repro", "serve"]
    assert cmd[cmd.index("--http") + 1] == "0"
    assert cmd[cmd.index("--max-queue-depth") + 1] == "7"
    # models registered in sorted order; trace cache gets a per-replica subdir
    mi = cmd.index("--model")
    assert cmd[mi + 1] == "a=art/a" and cmd[mi + 3] == "b=art/b"
    assert cmd[cmd.index("--cache-dir") + 1].endswith(os.path.join("tr", "r3"))


def test_replica_spawn_failure_is_reaped_with_stderr_tail(tmp_path):
    """A replica that exits before announcing its port raises a
    ReplicaSpawnError carrying the exit code and its stderr tail, and the
    process is reaped (no zombie)."""
    r = ReplicaProcess(
        "bad", log_dir=str(tmp_path),
        cmd=[sys.executable, "-c",
             "import sys; print('boom', file=sys.stderr); sys.exit(3)"],
    )
    r.spawn()
    with pytest.raises(ReplicaSpawnError) as exc:
        r.wait_listening(timeout_s=30)
    assert "rc=3" in str(exc.value)
    assert "boom" in str(exc.value)
    assert not r.alive


def test_replica_never_announcing_times_out_and_is_killed(tmp_path):
    """A replica that hangs without printing the listening line is torn
    down at the timeout — the fleet never leaks a silent subprocess."""
    r = ReplicaProcess(
        "mute", log_dir=str(tmp_path),
        cmd=[sys.executable, "-c", "import time; time.sleep(600)"],
    )
    r.spawn()
    pid = r.pid
    with pytest.raises(ReplicaSpawnError, match="did not announce"):
        r.wait_listening(timeout_s=1.0)
    assert not r.alive
    with pytest.raises(OSError):  # reaped: the pid is gone
        os.kill(pid, 0)


def test_replica_ignores_stdout_noise_before_listening(tmp_path):
    """Banner noise on stdout (jax warnings etc.) must not confuse the
    port hand-shake; only the listening JSON line counts."""
    script = (
        "import json, sys\n"
        "print('some banner noise')\n"
        "print('{not json either')\n"
        "print(json.dumps({'event': 'listening', 'port': 45678}))\n"
        "import time; time.sleep(600)\n"
    )
    r = ReplicaProcess("noisy", log_dir=str(tmp_path),
                       cmd=[sys.executable, "-u", "-c", script])
    r.spawn()
    try:
        assert r.wait_listening(timeout_s=30) == 45678
        assert r.port == 45678
        assert r.url.endswith(":45678")
    finally:
        r.stop()
    assert not r.alive


def test_fleet_constructor_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        Fleet(0)
    with pytest.raises(ValueError, match="models_per_replica has 1"):
        Fleet(2, models_per_replica=[{"a": "x"}])


def test_fleet_spawn_failure_tears_everything_down(tmp_path, monkeypatch):
    """One replica failing to start stops every already-spawned sibling
    (no orphan subprocesses) and re-raises."""
    fleet = Fleet(2, startup_timeout_s=2.0)
    fleet.replicas[0]._cmd_override = [
        sys.executable, "-u", "-c",
        "import json, time; print(json.dumps({'event': 'listening', "
        "'port': 1})); time.sleep(600)",
    ]
    fleet.replicas[1]._cmd_override = [sys.executable, "-c",
                                       "import sys; sys.exit(9)"]
    with pytest.raises(ReplicaSpawnError):
        fleet.start()
    assert fleet.router is None
    assert all(not r.alive for r in fleet.replicas)


def test_repro_env_prepends_src():
    """The child env must resolve `-m repro` to THIS checkout."""
    import repro

    env = _repro_env()
    first = env["PYTHONPATH"].split(os.pathsep)[0]
    assert os.path.isdir(os.path.join(first, "repro"))
    assert first in {os.path.dirname(os.path.abspath(p))
                     for p in repro.__path__}


# ------------------------------------------------------- one chip each

def test_fleet_pins_one_tpu_chip_per_replica(tmp_path, monkeypatch):
    """On a TPU host each replica child gets exactly its own chip:
    a one-chip slice, its chip index, and a slice-builder port of its own."""
    monkeypatch.setattr(fleet_mod, "tpu_chip_count", lambda: 4)
    fleet = Fleet(2)
    assert [r.chip for r in fleet.replicas] == [0, 1]
    envs = [r.env() for r in fleet.replicas]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["PYTHONPATH"] == _repro_env()["PYTHONPATH"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 2
    # the spawned child really runs with its chip's settings
    r = fleet.replicas[1]
    r._cmd_override = [sys.executable, "-c",
                       "import os, sys; sys.exit(0 if os.environ"
                       "['TPU_VISIBLE_CHIPS'] == '1' else 7)"]
    r.spawn()
    try:
        assert r._proc.wait(timeout=60) == 0
    finally:
        r.stop()


def test_fleet_without_tpu_chips_pins_nothing(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert fleet_mod.tpu_chip_count() == 0
    fleet = Fleet(3)
    assert [r.chip for r in fleet.replicas] == [None] * 3
    assert fleet.replicas[0].env() == _repro_env()


def test_fleet_refuses_more_replicas_than_chips(tmp_path, monkeypatch, capsys):
    from repro.cli import main

    monkeypatch.setattr(fleet_mod, "tpu_chip_count", lambda: 1)
    with pytest.raises(ValueError, match="2 replicas need 2 TPU chips"):
        Fleet(2)
    jobs = tmp_path / "jobs.json"
    jobs.write_text(json.dumps({"jobs": [{"id": "a", "bench": "sim_loop"}]}))
    assert main(["fleet", "--replicas", "2", "--jobs", str(jobs)]) == 2
    assert "this host has 1" in capsys.readouterr().err


def test_fleet_refuses_to_start_from_a_process_holding_the_tpu(monkeypatch):
    monkeypatch.setattr(fleet_mod, "tpu_chip_count", lambda: 2)
    monkeypatch.setattr(fleet_mod, "holds_tpu", lambda: True)
    fleet = Fleet(2)
    with pytest.raises(RuntimeError, match="holds the TPU"):
        fleet.start()
    assert all(r.pid is None for r in fleet.replicas)  # nothing was spawned
    assert fleet.router is None


# ------------------------------------------------------------- CLI smoke

def test_cli_fleet_quick_smoke(tmp_path, capsys):
    """`python -m repro fleet --replicas 2 --quick` (the CI fast-tier
    smoke): real replica subprocesses, real router, job results and
    fleet-wide stats on stdout."""
    from repro.cli import main

    spec = {
        "jobs": [
            {"id": "a", "bench": "sim_loop", "n": 2000, "lanes": 1},
            {"id": "b", "bench": "mlb_stream", "n": 2000, "lanes": 2,
             "priority": 2},
            {"id": "c", "bench": "sim_loop", "n": 2000, "lanes": 2},
        ]
    }
    jobs = tmp_path / "jobs.json"
    jobs.write_text(json.dumps(spec))
    rc = main([
        "fleet", "--replicas", "2", "--jobs", str(jobs), "--quick",
        "--cache-dir", str(tmp_path / "tr"), "--max-wait-ms", "5",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "fleet" and out["replicas"] == 2
    assert out["port"] > 0
    assert out["healthz"]["ok"] is True
    assert out["healthz"]["healthy_replicas"] == 2
    assert [j["id"] for j in out["jobs"]] == ["a", "b", "c"]
    assert all(j["status"] == "done" for j in out["jobs"])
    assert all(j["replica"] in ("r0", "r1") for j in out["jobs"])
    assert out["stats"]["router"]["jobs_routed"] == 3
    assert out["stats"]["fleet"]["jobs_completed"] == 3
    assert out["stats"]["telemetry"]["service_ms"]["count"] == 3


# ----------------------------------------------------- the failure drill

@pytest.mark.slow
def test_fleet_kill_restart_drill_subprocesses():
    """The subprocess edition of the acceptance drill: SIGKILL a replica
    holding an accepted job mid-stream — the job is resubmitted to the
    survivor and completes; restarting the replica on its original port
    gets it readmitted — all asserted via the router's /v1/stats."""
    from repro.serving.http import http_request
    from repro.serving.router import route_jobs

    # a long batch window parks accepted jobs as pending — the window for
    # the kill; the survivor pays the same window once, nothing more
    with Fleet(2, max_wait_ms=3000.0, poll_interval_s=0.05,
               probe_initial_s=0.05, probe_cap_s=0.5) as fleet:
        payloads = [{"id": "drill", "bench": "sim_loop", "n": 2000,
                     "lanes": 1, "replica": "r0"}]
        out = {}

        def run():
            out["entries"] = route_jobs(fleet.url, payloads, timeout=180)

        t = threading.Thread(target=run)
        t.start()
        _wait_until(
            lambda: fleet.router.stats(refresh=False)["router"]["jobs_routed"] >= 1,
            msg="job accepted on r0",
        )
        fleet.kill_replica(0)
        t.join(timeout=180)
        assert not t.is_alive()
        (e,) = out["entries"]
        assert e["status"] == "done", e
        assert e["replica"] == "r1" and e["resubmits"] >= 1

        stats = fleet.stats()
        assert stats["router"]["ejections"] >= 1
        assert stats["router"]["healthy_replicas"] == 1

        fleet.restart_replica(0)
        _wait_until(
            lambda: fleet.router.stats(refresh=False)["router"]["readmissions"] >= 1,
            timeout=60,
            msg="r0 readmitted",
        )
        st, body = http_request(f"{fleet.url}/v1/healthz")
        assert st == 200 and body["healthy_replicas"] == 2


# ------------------------------------------- graceful stop vs forced kill

def test_stop_grace_sigterm_then_sigkill(tmp_path):
    """A replica that ignores SIGTERM is SIGKILLed after ``stop_grace_s``;
    one that honours it exits inside the grace without force."""
    defiant = (
        "import json, signal, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "print(json.dumps({'event': 'listening', 'port': 1}), flush=True)\n"
        "time.sleep(600)\n"
    )
    r = ReplicaProcess("defiant", log_dir=str(tmp_path), stop_grace_s=0.5,
                       cmd=[sys.executable, "-u", "-c", defiant])
    r.spawn()
    r.wait_listening(timeout_s=30)  # SIG_IGN is installed before this line
    t0 = time.monotonic()
    r.stop()
    assert not r.alive
    assert time.monotonic() - t0 >= 0.5  # the grace was actually granted

    polite = "import time\ntime.sleep(600)\n"  # default SIGTERM kills it
    r2 = ReplicaProcess("polite", log_dir=str(tmp_path), stop_grace_s=30.0,
                        cmd=[sys.executable, "-u", "-c", polite])
    r2.spawn()
    _wait_until(lambda: r2.alive, msg="polite child up")
    t0 = time.monotonic()
    r2.stop()
    assert not r2.alive
    assert time.monotonic() - t0 < 10.0  # graceful exit, not the full grace


def test_replica_command_carries_chaos_flags(tmp_path):
    r = ReplicaProcess("rc", batch_timeout_s=7.5,
                       faults_spec="seed=3;compile=fail_once:1",
                       log_dir=str(tmp_path))
    cmd = r.command()
    assert cmd[cmd.index("--batch-timeout-s") + 1] == "7.5"
    assert cmd[cmd.index("--faults") + 1] == "seed=3;compile=fail_once:1"
    # disabled watchdog / no plan: the flags stay off the command line
    r2 = ReplicaProcess("rc2", log_dir=str(tmp_path))
    assert "--batch-timeout-s" not in r2.command()
    assert "--faults" not in r2.command()


# ------------------------------------------------------- fleet supervisor

def test_supervisor_restarts_chaos_killed_replica():
    """The supervisor's own chaos site kills a replica (deterministic,
    seeded), then detects the corpse and restarts it under the budget —
    counters visible through the router's aggregated /v1/stats."""
    from repro.serving import faults
    from repro.serving.faults import FaultPlan, FaultSpec

    faults.install(FaultPlan(1, {"replica.crash": FaultSpec(fail_once=1)}))
    try:
        with Fleet(1, supervise=True, restart_budget=2,
                   supervise_interval_s=0.05,
                   restart_backoff_initial_s=0.05,
                   restart_backoff_cap_s=0.2,
                   probe_initial_s=0.05, probe_cap_s=0.5) as fleet:
            _wait_until(
                lambda: fleet.supervisor_stats()["restarts_total"] >= 1,
                timeout=120, msg="supervised restart",
            )
            faults.clear()  # one kill was the drill; stop rolling the dice
            _wait_until(lambda: fleet.replicas[0].alive, timeout=60,
                        msg="replica back up")
            sup = fleet.stats()["supervisor"]
            assert sup["enabled"] is True
            assert sup["chaos_kills"] == 1
            assert sup["restarts"]["r0"] >= 1
            assert sup["restart_failures"] == 0
            _wait_until(
                lambda: fleet.router.stats(refresh=False)["router"]["readmissions"] >= 1,
                timeout=60, msg="restarted replica readmitted",
            )
    finally:
        faults.clear()


def test_supervisor_off_by_default_dead_stays_dead():
    with Fleet(1, probe_initial_s=0.05, probe_cap_s=0.5) as fleet:
        assert fleet.supervise is False
        assert fleet.stats()["supervisor"]["enabled"] is False
        fleet.kill_replica(0)
        time.sleep(1.0)  # a supervisor tick would have fired many times over
        assert not fleet.replicas[0].alive
        assert fleet.supervisor_stats()["restarts_total"] == 0
