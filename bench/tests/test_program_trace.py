"""The readers of the program's own spans, counters and named scopes, on
small traces: one recorded on the CPU (host spans only: the CPU backend
has no TPU plane) and one written by hand in the profiler's own format
with a TPU plane, whose numbers are worked out below.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

No number here is a device measurement.
"""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import manifest, program_trace, report, tracing  # noqa: E402

US = 1000  # ns
CELL = "c3.sweep"
CHUNK = 4


def _reader(name):
    return manifest.reader(name).read


# ------------------------------------------------ a trace written by hand

def _events(rows, metadata_ids):
    out = []
    for name, start, end, stats in rows:
        st = "".join(f" stats {{ metadata_id: {k} {v} }}" for k, v in stats)
        out.append(f"events {{ metadata_id: {metadata_ids[name]} offset_ps: {start * 1000} "
                   f"duration_ps: {(end - start) * 1000}{st} }}")
    return " ".join(out)


def _plane(pid, name, lines, event_meta, stat_meta, display=None):
    ids = {n: i + 1 for i, n in enumerate(event_meta)}
    body = [f'id: {pid} name: "{name}"']
    for lid, (lname, rows) in enumerate(lines.items(), 1):
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    f"{_events(rows, ids)} }}")
    for n, stats in event_meta.items():
        st = "".join(f" stats {{ metadata_id: {k} {v} }}" for k, v in stats)
        shown = f' display_name: "{display[n]}"' if display and n in display else ""
        body.append(f'event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} name: "{n}"'
                    f"{shown}{st} }} }}")
    for k, n in stat_meta.items():
        body.append(f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}')
    return "planes { " + " ".join(body) + " }"


TF_OP, JOB_ID, RETIRE_PATH = 1, 3, 4
STATS = {TF_OP: "tf_op", JOB_ID: "job_id",
         RETIRE_PATH: "jit(run_chunk)/while/body/closed_call/retire/add:"}
SCOPE = "jit(run_chunk)/while/body/closed_call/"
# a TPU op event is named by its whole instruction text
OPS = {n: f"%{n} = f32[8] {n.split('.')[0]}()" for n in (
    "while.1", "fusion.1", "convolution.2", "fusion.3", "fusion.4", "copy-done.5", "fusion.9")}


def _chunk_ops(base):
    """One `run_chunk` execution of 20 us at ``base``: the scan's `while`
    holds assembly 2, trunk 8, head 1, retire 3 and a copy of 1 us that
    carries only the loop's op_name; outside the predictor: 2 + 3 + 1 =
    6 us."""
    b = base
    return [(OPS["while.1"], b, b + 20 * US, []),
            (OPS["fusion.1"], b, b + 2 * US, []),
            (OPS["convolution.2"], b + 2 * US, b + 10 * US, []),
            (OPS["fusion.3"], b + 10 * US, b + 11 * US, []),
            (OPS["fusion.4"], b + 11 * US, b + 14 * US, []),
            (OPS["copy-done.5"], b + 14 * US, b + 15 * US, [])]


def _device_plane(scoped=True):
    ops = _chunk_ops(10 * US) + _chunk_ops(50 * US) + [(OPS["fusion.9"], 80 * US, 82 * US, [])]
    tf_op = {"while.1": "jit(run_chunk)/while:",
             "fusion.1": f"{SCOPE}assembly/concatenate:",
             "convolution.2": f"{SCOPE}trunk/conv_general_dilated:",
             "fusion.3": f"{SCOPE}head:",  # an op at the scope itself
             "copy-done.5": "jit(run_chunk)/while:",
             "fusion.9": "jit(totals)/add:"}
    meta = {"jit_run_chunk(7)": [], "jit_totals(8)": []}
    for short, text in OPS.items():
        if short == "fusion.4":  # the value by reference to a stat metadata
            meta[text] = [(TF_OP, f"ref_value: {RETIRE_PATH}")] if scoped else []
        else:
            meta[text] = [(TF_OP, f'str_value: "{tf_op[short]}"')] if scoped else []
    modules = [("jit_run_chunk(7)", 10 * US, 30 * US, []),
               ("jit_run_chunk(7)", 50 * US, 70 * US, []),
               ("jit_totals(8)", 80 * US, 82 * US, [])]
    return _plane(1, "/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}, meta, STATS,
                  display={text: short for short, text in OPS.items()})


LEAVES = [("simnet.featurize", 0, 5), ("simnet.pack", 5, 8), ("simnet.executable", 8, 9),
          ("simnet.stage", 9, 12), ("simnet.device_wait", 12, 30), ("simnet.results", 30, 33),
          ("simnet.featurize", 35, 40), ("simnet.pack", 40, 45), ("simnet.stage", 45, 50),
          ("simnet.device_wait", 50, 70), ("simnet.results", 70, 75)]
PARENTS = [("simnet.submit", 0, 6), ("simnet.batch", 5, 34), ("simnet.batch", 40, 80)]


def _host_plane(spans=True):
    rows = [("bench.window", 0, 100 * US, []), ("bench.simulate_many", 0, 80 * US, [])]
    if spans:
        rows += [(n, s * US, e * US, [(JOB_ID, "int64_value: 3")] if n == "simnet.submit"
                  else []) for n, s, e in LEAVES + PARENTS]
    meta = {n: [] for n, *_ in rows}
    return _plane(2, "/host:CPU", {"python": rows}, meta, STATS)


def _write(dir_: Path, *planes) -> Path:
    from jax.profiler import ProfileData

    dir_.mkdir(parents=True, exist_ok=True)
    path = dir_ / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
    return path


def _reading(path, batches):
    from jax.profiler import ProfileData

    window = types.SimpleNamespace(batches=batches, t_open=0.0, t_close=1e-4,
                                   instructions=0)
    return report.Reading(cell=types.SimpleNamespace(name=CELL), window=window, spans={},
                          trace=tracing.read(ProfileData.from_file(str(path)), 1),
                          peak={}, chips=1)


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    from bench import run

    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    return tmp_path


def _batch(**kw):
    return types.SimpleNamespace(chunk=CHUNK, n_lanes=8, **kw)


def test_the_hand_written_trace_reduces_to_its_spans_and_scoped_leaves(trace_root):
    path = _write(trace_root / CELL, _host_plane(), _device_plane())
    t = program_trace.for_cell(CELL)
    assert program_trace.for_cell(CELL) is t  # parsed once per file
    assert sorted((n, s // US, e // US) for n, s, e, _ in t["host"]) == sorted(LEAVES + PARENTS)
    (ops,) = t["chunk_ops"]
    assert len(ops) == 10  # two executions' five leaves; not the `while`, not fusion.9
    assert [program_trace.is_model_op(scope) for _, _, scope in ops[:5]] == [
        False, True, True, False, False]
    assert [program_trace.is_scoped(scope) for _, _, scope in ops[:5]] == [
        True, True, True, True, False]
    scopes = program_trace.op_scopes(path)["/device:TPU:0"]
    assert scopes["fusion.4"] == scopes[OPS["fusion.4"]] == STATS[RETIRE_PATH]


def test_state_time_per_step_leaves_out_the_predictor(trace_root):
    path = _write(trace_root / CELL, _host_plane(), _device_plane())
    r = _reading(path, [_batch(), _batch()])
    # (2 + 3 + 1) us in each of 2 executions of CHUNK steps
    got = _reader("run_chunk.state_us_per_step.sweep")(r)
    assert got == pytest.approx(2 * 6 / (2 * CHUNK))


def test_unattributed_idle_counts_only_leaf_spans(trace_root):
    path = _write(trace_root / CELL, _host_plane(), _device_plane())
    r = _reading(path, [_batch()])
    # device 0 busy 10-30, 50-70, 80-82 us; leaf spans 0-33 and 35-75:
    # 33-35 and 75-80 and 82-100 are explained by neither (`simnet.batch`
    # to 80 is a parent and explains nothing)
    got = _reader("device.idle_unattributed.sweep")(r)
    assert got == pytest.approx(100.0 * (2 + 5 + 18) / 100)


def test_a_program_without_spans_or_scopes_reads_nothing(trace_root):
    path = _write(trace_root / CELL, _host_plane(spans=False), _device_plane(scoped=False))
    r = _reading(path, [types.SimpleNamespace(chunk=CHUNK, n_lanes=8)])
    for name in ("run_chunk.state_us_per_step.sweep", "device.idle_unattributed.sweep",
                 "session.featurize_ms.sweep", "engine.pack_ms.sweep", "engine.stage_ms.sweep",
                 "engine.device_wait_ms.sweep", "service.results_ms.sweep"):
        assert _reader(name)(r) is None, name


@pytest.mark.parametrize("name,field", [
    ("session.featurize_ms.sweep", "featurize_seconds"), ("engine.pack_ms.sweep", "pack_seconds"),
    ("engine.stage_ms.sweep", "stage_seconds"),
    ("engine.device_wait_ms.sweep", "device_wait_seconds"),
    ("service.results_ms.sweep", "results_seconds")])
def test_phase_counters_are_means_over_the_batches_in_ms(name, field):
    r = types.SimpleNamespace(window=types.SimpleNamespace(
        batches=[_batch(**{field: 0.25}), _batch(**{field: 0.5})]))
    assert _reader(name)(r) == pytest.approx(375.0)
    assert _reader(name)(types.SimpleNamespace(window=types.SimpleNamespace(batches=[]))) is None


def test_the_leaves_of_nested_ops():
    ev = [(0, 100, "while"), (0, 10, "a"), (10, 30, "fusion"), (12, 20, "inner"),
          (30, 30, "empty"), (40, 120, "overlaps"), (130, 140, "after")]
    assert [x[2] for x in program_trace.leaves(ev)] == ["a", "inner", "empty", "overlaps",
                                                       "after"]
    assert [x[2] for x in program_trace.inside(ev, [(0, 100)])] == [
        "while", "a", "fusion", "inner", "empty"]


def test_a_cpu_recorded_trace_holds_the_programs_host_spans(tmp_path):
    """The real spans, recorded on the CPU around a tiny teacher-forced
    `simulate_many`: every leaf span is found, on the trace's clock."""
    import jax

    from repro.core.api import SimNet
    from repro.core.simulator import SimConfig
    from repro.des.o3 import O3Config, O3Simulator
    from repro.des.workloads import get_benchmark

    traces = [O3Simulator(O3Config()).run(get_benchmark("sim_loop", 300))]
    sn = SimNet(sim_cfg=SimConfig(ctx_len=8), chunk=32)
    sn.simulate_many(traces, n_lanes=2)
    with jax.profiler.trace(str(tmp_path)):
        sn.simulate_many(traces, n_lanes=2)
    t = program_trace.read(tmp_path)
    names = {n for n, *_ in t["host"]}
    assert set(program_trace.LEAF_SPANS) | {"simnet.submit", "simnet.batch"} == names
    assert t["chunk_ops"] == []  # no TPU plane on the CPU
    assert all(e >= s > 0 for _, s, e, _ in t["host"])
