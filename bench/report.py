"""From a window to the metrics of the result line.

End-to-end metrics are taken by the harness on the host clock. Per-layer
metrics are each read by a file of their own, ``bench/metrics/<name>.py``,
whose ``read(r)`` gets a `Reading` and returns a number, or None where it
finds nothing to read (the metric is then left out of the line).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

from bench import counts, manifest, stats, tracing

PEAKS = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())


def peak_of(device_kind: str) -> dict:
    try:
        return PEAKS["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json") from None


def _value(metric: dict, value: float) -> dict:
    return {"value": float(value), "unit": metric["unit"]}


def end_to_end(run, window, setup_s: float) -> dict:
    elapsed = window.t_close - window.t_open
    values = {
        "setup_s": setup_s,
        "sim_instr_per_s": window.instructions / elapsed if elapsed > 0 else None,
    }
    if window.latencies_ms:
        values["job_p95_ms"] = stats.percentile(window.latencies_ms, 95)
        values["job_p50_ms"] = stats.percentile(window.latencies_ms, 50)
    out = {}
    for m in run.cell.end_to_end:
        v = values.get(m["name"])
        if v is None or v != v or v == float("inf"):
            raise RuntimeError(f"{m['name']}: no finite value ({v!r})")
        out[m["name"]] = _value(m, v)
    return out


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may read."""

    cell: object  # manifest.Cell
    window: object  # drivers.Window
    spans: dict  # harness span name -> [seconds, ...]
    trace: Optional[dict]  # tracing.read(...) of the window
    peak: dict  # peaks.json entry of the device
    chips: int

    @property
    def window_s(self) -> float:
        return self.window.t_close - self.window.t_open

    def flops_per_instruction(self) -> float:
        return self.cell.model.flops_per_instruction(self.cell.sizes["predictor"])

    def step_counts(self, lanes_per_device: int) -> dict:
        return counts.step_counts(self.cell.model, self.cell.sizes["predictor"],
                                  int(self.cell.sizes["sim"]["ctx_len"]), lanes_per_device)


def per_layer(run, window, spans, trace_dir) -> tuple:
    pd = tracing.load(trace_dir)
    t = tracing.read(pd, run.cell.chips)
    r = Reading(cell=run.cell, window=window, spans=spans.seconds, trace=t,
                peak=peak_of(run.device["kind"]), chips=run.cell.chips)
    out = {}
    for m in run.cell.per_layer:
        v = manifest.reader(m["name"]).read(r)
        if v is not None:
            out[m["name"]] = _value(m, v)
    extra = {
        "device": {"busy_s": tracing.busy_seconds(t), "window_s": t["window_ns"] / 1e9},
        "breakdown": {"device_ops": tracing.top_ops(t), "idle_gaps": tracing.idle_gaps(t)},
    }
    return out, extra
