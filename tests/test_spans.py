"""Host spans, per-batch phase counters and the chunk program's named
scopes.

A batch's host phases are `telemetry.span`s: each is a `TraceAnnotation`
on the profiler's timeline and adds its seconds into the batch's
`BatchReport`. The named scopes (`layout`, `assembly`, `trunk`, `head`,
`retire`, and tx6's `attention` inside `trunk`) are HLO metadata only: the compiled chunk program must be the same
instructions with or without them.
"""
import contextlib
import re
from pathlib import Path

import jax
import pytest

from repro.core.api import SimNet
from repro.core.predictor import PredictorConfig, init_predictor
from repro.core.simulator import SimConfig
from repro.des.o3 import O3Config, O3Simulator
from repro.des.workloads import get_benchmark
from repro.serving.compile_cache import CompileCache
from repro.serving.simnet_engine import SimNetEngine
from repro.serving.telemetry import span

CTX = 8
PHASES = ("simnet.pack", "simnet.executable", "simnet.stage", "simnet.device_wait",
          "simnet.results")


def _pcfg(kind):
    if kind == "tx6":
        return PredictorConfig(kind=kind, ctx_len=CTX, hidden=16, tx_dim=16, tx_layers=2)
    return PredictorConfig(kind=kind, ctx_len=CTX, hidden=16,
                           channels=(8, 8, 8) if kind == "c3" else (8,))


def _session(kind):
    if kind == "tf":
        return SimNet(sim_cfg=SimConfig(ctx_len=CTX), chunk=64)
    pcfg = _pcfg(kind)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    return SimNet(params=params, pcfg=pcfg, sim_cfg=SimConfig(ctx_len=CTX), chunk=64,
                  cache=CompileCache())


@pytest.fixture(scope="module")
def traces():
    sim = O3Simulator(O3Config())
    return [sim.run(get_benchmark(n, s)) for n, s in (("sim_loop", 600), ("mlb_stream", 400))]


def _host_spans(trace_dir):
    """(name, start, end) of every ``simnet.*`` span in the recorded trace."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    return sorted(((e.name, int(e.start_ns), int(e.end_ns))
                   for p in pd.planes if p.name.startswith("/host:")
                   for ln in p.lines for e in ln.events if e.name.startswith("simnet.")),
                  key=lambda ev: ev[1])


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("kind", ["tf", "c3"])
def test_spans_nest_in_order_on_the_profiler_timeline(kind, traces, tmp_path):
    sn = _session(kind)
    sn.simulate_many(traces, n_lanes=2)  # compile outside the recorded window
    with jax.profiler.trace(str(tmp_path)):
        sn.simulate_many(traces, n_lanes=2)
    spans = _host_spans(tmp_path)
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev[0], []).append(ev)
    submits, featurizes = by_name["simnet.submit"], by_name["simnet.featurize"]
    assert len(submits) == len(featurizes) == len(traces)
    assert all(_within(f, s) for f, s in zip(featurizes, submits))
    (batch,) = by_name["simnet.batch"]
    assert submits[-1][2] <= batch[1]
    phases = [ev for ev in spans if ev[0] in PHASES]
    assert all(_within(ev, batch) for ev in phases)
    names = [ev[0] for ev in phases]
    # one stage (the lane configs, the state and the chunk enqueues),
    # results twice (the engine's copies and guard, then the job results)
    assert names == ["simnet.pack", "simnet.executable", "simnet.stage",
                     "simnet.device_wait", "simnet.results", "simnet.results"]


@pytest.mark.parametrize("kind", ["tf", "c3", "tx6"])
def test_batch_report_phase_counters(kind, traces):
    sn = _session(kind)
    sn.simulate_many(traces, n_lanes=2)
    sn.simulate_many(traces, n_lanes=2)
    b = sn.service.batches[-1]
    counters = ("featurize_seconds", "pack_seconds", "stage_seconds",
                "device_wait_seconds", "results_seconds")
    assert all(getattr(b, c) > 0 for c in counters), b
    # the engine's phases all fall inside the call it times
    assert b.pack_seconds + b.stage_seconds + b.device_wait_seconds <= b.first_call_seconds
    d = b.to_dict()
    assert set(counters) <= set(d) and all(isinstance(d[c], float) for c in counters)


def test_arrays_skip_featurize(traces):
    """trace_arrays dicts are no longer widened at submit, but they are
    inverted into integer fields there: that is timed by the same span
    and counter as a Trace's checks."""
    from repro.core import features as F

    sn = _session("tf")
    sn.simulate_many([F.trace_arrays(t) for t in traces], n_lanes=2)
    assert sn.service.batches[-1].featurize_seconds > 0.0


def test_span_adds_into_its_counter_even_when_the_body_raises():
    into = {}
    with span("simnet.test", into, "k", job_id=1) as ann:
        ann.set_metadata(extra=2)
    with pytest.raises(RuntimeError):
        with span("simnet.test", into, "k"):
            raise RuntimeError("boom")
    assert into["k"] > 0
    with span("simnet.test"):  # no counter: nothing to add into
        pass


def _strip_metadata(hlo_text):
    """The compiled module without per-op metadata and the stack-frame
    tables that follow it (source lines and scope names only)."""
    return re.sub(r",? metadata=\{[^}]*\}", "", hlo_text.split("\nFileNames")[0])


def _compiled_run_chunk(kind):
    if kind == "tf":
        eng = SimNetEngine(None, None, SimConfig(ctx_len=CTX), cache=CompileCache())
    else:
        pcfg = _pcfg(kind)
        params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
        eng = SimNetEngine(params, pcfg, SimConfig(ctx_len=CTX), cache=CompileCache())
    return eng.lower(64, 16).compile().as_text()


SCOPES = {"layout", "assembly", "trunk", "head", "retire", "attention"}


@pytest.mark.parametrize("kind,scopes", [("c3", SCOPES - {"attention"}),
                                         ("rb7", SCOPES - {"attention"}),
                                         ("tx6", SCOPES),
                                         ("tf", {"layout", "retire"})])
def test_named_scopes_leave_run_chunk_the_same(kind, scopes, monkeypatch):
    scoped = _compiled_run_chunk(kind)
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    assert {part for n in names for part in n.split("/")} & SCOPES == scopes
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = _compiled_run_chunk(kind)
    assert "/retire/" not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)
