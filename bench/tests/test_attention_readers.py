"""The readers of tx6's `attention` scope, on a trace written by hand in
the profiler's own format (`test_program_trace`'s writer), whose numbers
are worked out below.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

No number here is a device measurement.
"""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import manifest, report, tracing

tpt = manifest.load_module(Path(__file__).resolve().parent / "test_program_trace.py")
US = tpt.US
CELL = "tx6.sweep"
CHUNK = 4
SCOPE = "jit(run_chunk)/while/body/closed_call/trunk/"
OPS = {n: f"%{n} = f32[8] {n.split('.')[0]}()" for n in (
    "while.1", "fusion.1", "fusion.2", "fusion.3", "fusion.4")}


def _device_plane(scoped=True):
    """Two `run_chunk` executions of 20 us; in each, attention ops of 3 us
    and 2 us (one fused with an op of another scope) beside 6 us of other
    trunk ops: 5 us of attention an execution."""
    tf_op = {"while.1": "jit(run_chunk)/while:",
             "fusion.1": f"{SCOPE}attention/dot_general:",
             "fusion.2": f"{SCOPE}dot_general:",
             "fusion.3": f"{SCOPE}attention/div:;{SCOPE}attention/exp:",
             "fusion.4": f"{SCOPE}add:;{SCOPE}attention/reduce_sum:"}
    ops = []
    for b in (10 * US, 50 * US):
        ops += [(OPS["while.1"], b, b + 20 * US, []),
                (OPS["fusion.1"], b, b + 3 * US, []),
                (OPS["fusion.2"], b + 3 * US, b + 9 * US, []),
                (OPS["fusion.3"], b + 9 * US, b + 10 * US, []),
                (OPS["fusion.4"], b + 10 * US, b + 11 * US, [])]
    meta = {"jit_run_chunk(7)": []}
    for short, text in OPS.items():
        meta[text] = [(tpt.TF_OP, f'str_value: "{tf_op[short]}"')] if scoped else []
    modules = [("jit_run_chunk(7)", 10 * US, 30 * US, []),
               ("jit_run_chunk(7)", 50 * US, 70 * US, [])]
    return tpt._plane(1, "/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}, meta,
                      tpt.STATS, display={text: short for short, text in OPS.items()})


def _reading(path, model, lanes=4096):
    from jax.profiler import ProfileData

    batch = types.SimpleNamespace(chunk=CHUNK, n_lanes=lanes)
    window = types.SimpleNamespace(batches=[batch, batch], t_open=0.0, t_close=1e-4,
                                   instructions=0)
    cell = types.SimpleNamespace(name=CELL, model=model, sizes={"predictor": {}})
    return report.Reading(cell=cell, window=window, spans={},
                          trace=tracing.read(ProfileData.from_file(str(path)), 1),
                          peak={"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 1e12}, chips=1)


MODEL = types.SimpleNamespace(attention_flops_per_instruction=lambda p: 1e6,
                              attention_bytes_per_instruction=lambda p: 2.5e3)


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    from bench import run

    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    return tmp_path


def test_attention_time_and_roofline(trace_root):
    path = tpt._write(trace_root / CELL, tpt._host_plane(), _device_plane())
    r = _reading(path, MODEL)
    us = manifest.reader("run_chunk.attention_us_per_step.sweep").read(r)
    assert us == pytest.approx(2 * 5 / (2 * CHUNK))
    # least per step: 4,096 x 2.5 kB / 1e12 B/s = 10.24 us (memory) against
    # 4,096 x 1 MFLOP / 2e14 = 20.48 us (compute): compute-bound
    share = manifest.reader("run_chunk.attention_roofline.sweep").read(r)
    assert share == pytest.approx(100.0 * 20.48 / us)


def test_a_program_without_the_scope_reads_nothing(trace_root):
    path = tpt._write(trace_root / CELL, tpt._host_plane(), _device_plane(scoped=False))
    r = _reading(path, MODEL)
    for name in ("run_chunk.attention_us_per_step.sweep", "run_chunk.attention_roofline.sweep"):
        assert manifest.reader(name).read(r) is None, name


def test_a_model_without_attention_counts_reads_no_roofline(trace_root):
    path = tpt._write(trace_root / CELL, tpt._host_plane(), _device_plane())
    assert manifest.reader("run_chunk.attention_roofline.sweep").read(
        _reading(path, types.SimpleNamespace())) is None
