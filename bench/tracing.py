"""The reduction from a profiler trace to numbers.

A traced run records the window with `jax.profiler`; this module reads the
``.xplane.pb`` it writes with `jax.profiler.ProfileData`:

- device planes (``/device:TPU:<n>``): the "XLA Ops" line holds every
  operation the device ran, the "XLA Modules" line every program
  execution, by the program's name (the chunk program is ``run_chunk``);
  where a trace holds no op line, busy time comes from the executions;
- host planes: the harness's own spans (``bench.<name>``, written with
  `TraceAnnotation`), on the same clock, so that an idle gap on the device
  can be put down to what the host was doing in it.

Busy time is the union of the operation intervals on a device, clipped to
the window (the ``bench.window`` span); the idle share is 1 - busy / window.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"

Interval = Tuple[int, int]  # [start, end) in ns


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) between disjoint sorted busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_share(busy_ns: int, window_ns: int) -> float:
    if window_ns <= 0:
        raise ValueError("empty window")
    return 1.0 - busy_ns / window_ns


def load(trace_dir: Path):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def op_name(event_name: str) -> str:
    """An HLO op event is named by its whole instruction text; keep the
    instruction's own name (``%fusion.12 = f32[...] fusion(...)`` ->
    ``%fusion.12``)."""
    return event_name.split(" = ", 1)[0]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def read(pd, n_devices: int) -> dict:
    """Everything the per-layer readers and the breakdown take from a
    trace: per-device busy time, program executions and operations, and the
    host spans, all inside the window."""
    host: List[Tuple[str, int, int]] = []
    devices: Dict[str, dict] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [ev for ev in _events(ln) if ev[0].startswith("bench.")]
    win = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = win[0][1], win[0][2]
    names = sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1]))[:n_devices]
    if not names:
        raise ValueError("the trace holds no TPU device plane")
    per_device, modules, ops = [], {}, {}
    for name in names:
        lines = devices[name]
        # a trace of program executions only (no op line) still gives busy
        # time: every op runs inside its program's execution
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = union(clip([(s, e) for _, s, e in op_events], lo, hi))
        per_device.append({"busy_ns": covered(busy), "busy": busy})
        for n, s, e in lines.get(MODULES_LINE) or []:
            if e > lo and s < hi:
                m = modules.setdefault(n, [0, 0])
                m[0] += min(e, hi) - max(s, lo)
                m[1] += 1
        for n, s, e in op_events:
            if e > lo and s < hi:
                n = op_name(n)
                ops[n] = ops.get(n, 0) + min(e, hi) - max(s, lo)
    return {
        "window_ns": hi - lo, "lo": lo, "hi": hi, "devices": per_device,
        "modules": modules, "ops": ops,
        "host": [ev for ev in host if ev[0] != WINDOW_SPAN],
    }


def busy_seconds(t: dict) -> float:
    """Busy seconds averaged over the devices used."""
    return sum(d["busy_ns"] for d in t["devices"]) / len(t["devices"]) / 1e9


def module_time(t: dict, needle: str) -> Optional[Tuple[float, int]]:
    """(device seconds per device, executions per device) of the programs
    whose name holds `needle`, or None where none ran."""
    hits = [(v[0], v[1]) for k, v in t["modules"].items() if needle in k]
    if not hits:
        return None
    n_dev = len(t["devices"])
    return sum(h[0] for h in hits) / n_dev / 1e9, sum(h[1] for h in hits) // n_dev


def top_ops(t: dict, k: int = 10) -> List[list]:
    """The device ops that took most time (a loop's op holds its body's
    ops, so the scan's `while` heads the list)."""
    n_dev = len(t["devices"])
    ranked = sorted(t["ops"].items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n_dev / 1e9] for name, ns in ranked]


def idle_gaps(t: dict, k: int = 10) -> List[list]:
    """The longest idle gaps of device 0 in the window, each named by the
    harness span that covers most of it ("no host span" where none does)."""
    d0 = t["devices"][0]["busy"]
    ranked = sorted(gaps(d0, t["lo"], t["hi"]), key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in ranked:
        best, cover = "no host span", 0
        for name, hs, he in t["host"]:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) / 1e9])
    return out
