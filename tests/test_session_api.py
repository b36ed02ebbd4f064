"""SimNet session API: service routing must reproduce the core scan
exactly, typed results must serialize, the legacy shims must stay gone.

The bit-identity tests are the regression guard for the api_redesign(s):
`SimNet.simulate*` routes exclusively through the SimServe → SimNetEngine
pack path (lane-bucketed resident executables), and its totals must equal
the one-shot core scan's.
"""
import json

import numpy as np
import pytest

from repro.core import api, features as F
from repro.core.api import SimNet
from repro.core.results import SimResult, SweepResult, WorkloadResult
from repro.core.simulator import SimConfig, simulate_many as core_simulate_many
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


def test_session_totals_bit_identical_to_core(traces, fields):
    """Teacher-forced pack through the session (engine path) vs the
    one-shot core scan: totals must be bit-identical, not just close."""
    cfg = SimConfig(ctx_len=32)
    lanes = [4, 2, 8, 4]
    sn = SimNet(sim_cfg=cfg)
    res = sn.simulate_many(traces, n_lanes=lanes)
    ref = core_simulate_many(fields, None, cfg, n_lanes=lanes)
    for i, w in enumerate(res):
        assert w.total_cycles == float(ref["workload_cycles"][i])
        assert w.n_instructions == int(ref["n_instructions"][i])
        assert w.overflow == int(ref["workload_overflow"][i])
    assert res.total_cycles == float(ref["total_cycles"])


def test_session_heterogeneous_cfgs_bit_identical(traces, fields):
    """Per-workload SimConfigs through the session replay each job's own
    config exactly inside the shared engine scan."""
    cfgs = [
        SimConfig(ctx_len=16, retire_width=2),
        SimConfig(ctx_len=32, retire_width=8),
        SimConfig(ctx_len=8, retire_width=4),
        SimConfig(ctx_len=32, retire_width=1),
    ]
    sn = SimNet(sim_cfg=SimConfig(ctx_len=32))
    res = sn.simulate_many(traces, n_lanes=4, sim_cfgs=cfgs)
    ref = core_simulate_many(fields, None, cfgs, n_lanes=4)
    for i, w in enumerate(res):
        assert w.total_cycles == float(ref["workload_cycles"][i])
        assert w.overflow == int(ref["workload_overflow"][i])


def test_teacher_forced_golden_cycles(traces):
    """One lane per workload teacher-forced: per-workload totals equal the
    traces' own Eq. 1 golden cycle counts exactly (the invariant the
    removed legacy shims used to guard)."""
    res = SimNet().simulate_many(traces, n_lanes=1)
    for tr, w in zip(traces, res):
        assert w.total_cycles == tr.total_cycles
        assert w.cpi_error == 0.0
    assert res.total_cycles == sum(t.total_cycles for t in traces)


def test_deprecated_shims_are_gone():
    """The one-release deprecation window for the loose functions is over
    (ROADMAP open item): the session/service methods are the only surface."""
    for name in ("simulate", "simulate_many", "train_predictor"):
        assert not hasattr(api, name), f"api.{name} should have been removed"


def test_simnet_import_loads_no_lm_module():
    """The SimNet path stands apart from the language-model stack:
    importing the session API in a fresh interpreter loads none of the
    LM layers, models, configs or launchers."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH")) if p)}
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys, repro.core.api; "
         "print(json.dumps(sorted(m for m in sys.modules if m.startswith('repro.'))))"],
        env=env, capture_output=True, text=True, check=True,
    )
    loaded = json.loads(out.stdout.strip().splitlines()[-1])
    assert "repro.core.api" in loaded
    lm = {f"repro.nn.{m}" for m in ("attention", "layers", "moe", "rope", "ssm",
                                   "transformer")}
    lm |= {f"repro.launch.{m}" for m in ("train", "specs", "dryrun")}
    bad = [m for m in loaded
           if m in lm or m.split(".")[:2] in (["repro", "models"], ["repro", "configs"])]
    assert not bad, f"importing repro.core.api loaded LM modules: {bad}"


def test_results_are_frozen_and_json_ready(traces):
    sn = SimNet()
    res = sn.simulate_many(traces[:2], n_lanes=2)
    assert isinstance(res, SimResult) and isinstance(res[0], WorkloadResult)
    with pytest.raises(Exception):
        res.workloads[0].cpi = 0.0  # frozen dataclass
    payload = json.loads(json.dumps(res.to_dict()))
    assert payload["n_workloads"] == 2
    assert res.workload(traces[0].name).name == traces[0].name
    with pytest.raises(KeyError):
        res.workload("no_such_workload")


def test_sweep_one_pack_and_relative(traces):
    """A sweep rides one packed call; relative() reads per-benchmark
    speedups vs the baseline point from both SimNet and DES sides."""
    sn = SimNet()
    tr = traces[2]
    swept = sn.sweep([("base", tr), ("alt", tr)], n_lanes=2)
    assert isinstance(swept, SweepResult)
    assert swept.points == ("base", "alt")
    rel = swept.relative()
    cell = rel["alt"][tr.name]
    # same trace at both points → speedup exactly 1 on both sides
    assert cell["simnet"] == 1.0 and cell["des"] == 1.0
    json.dumps(swept.to_dict())


def test_sweep_with_sim_cfg_axis(traces):
    """(label, trace, SimConfig) jobs sweep processor configs without
    retraining; each point matches a standalone run of that config."""
    from repro.core.simulator import simulate_trace

    tr = traces[2]
    a = F.trace_arrays(tr)
    cfg_small = SimConfig(ctx_len=8, retire_width=2)
    cfg_big = SimConfig(ctx_len=32, retire_width=8)
    sn = SimNet(sim_cfg=SimConfig(ctx_len=32))
    swept = sn.sweep(
        [("narrow", tr, cfg_small), ("wide", tr, cfg_big)], n_lanes=4
    )
    for label, cfg in [("narrow", cfg_small), ("wide", cfg_big)]:
        ref = simulate_trace(a, None, cfg, 4)
        assert swept.point(label)[0].total_cycles == float(ref["total_cycles"])


def test_cli_sweep_smoke(capsys):
    """`python -m repro sweep --quick` (the CI dry-run): teacher-forced
    replay through the full CLI → session → engine → results stack."""
    from repro.cli import main

    rc = main(["sweep", "--quick", "--bench", "sim_loop", "-n", "2000",
               "--lanes", "2", "--points", "262144", "1048576"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "teacher-forced"
    workloads = out["sweep"]["result"]["workloads"]
    assert len(workloads) == 2
    assert all(w["cpi_error"] is not None for w in workloads)


def test_cli_trace_smoke(tmp_path, capsys):
    from repro.cli import main

    rc = main(["trace", "--bench", "sim_loop", "-n", "2000",
               "--cache-dir", str(tmp_path)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["traces"][0]["n_instructions"] == 2000
    assert list(tmp_path.glob("*.npz"))  # cached for the next command
