"""What the program's own instrumentation left in a traced run.

The program times each host phase of a batch with a `simnet.*` span
(`repro.serving.telemetry.span`) and adds its seconds into the batch's
`BatchReport`; its chunk program carries named scopes (``assembly``,
``trunk``, ``head``, ``retire``) in the HLO metadata of its ops. This
module reads both:

- `batch_mean_ms`: the mean over the window's batches of one phase
  counter of the `BatchReport`s, in ms;
- `read`: from the run's ``.xplane.pb`` (parsed once per file, however
  many readers ask), the host ``simnet.*`` spans on the trace's clock and,
  per device, the ops that ran inside ``run_chunk`` executions, each with
  its named-scope path.

A program without the counters or spans (an older one) gives None and
empty lists, and its readers then report nothing.
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import tracing

SPAN_PREFIX = "simnet."
# the spans of the host phases; `simnet.submit` and `simnet.batch` hold them
LEAF_SPANS = ("simnet.featurize", "simnet.pack", "simnet.executable", "simnet.stage",
              "simnet.device_wait", "simnet.results")
CHUNK_PROGRAM = "run_chunk"
MODEL_SCOPES = ("trunk", "head")
SCOPES = ("assembly", "trunk", "head", "retire")

_parsed: Dict[str, dict] = {}


def batch_mean_ms(batches: Sequence, field: str) -> Optional[float]:
    """Mean of ``field`` (seconds) over the batches, in ms; None where
    there are none or a batch lacks the counter."""
    values = [getattr(b, field, None) for b in batches]
    if not values or any(v is None for v in values):
        return None
    return 1e3 * sum(values) / len(values)


def for_cell(cell_name: str) -> dict:
    """`read` of the cell's traced run (the harness's ``TRACE_DIR``)."""
    from bench import run

    return read(Path(run.TRACE_DIR) / cell_name)


def read(directory: Path) -> dict:
    """The program's spans and chunk ops in the newest trace under
    ``directory``, parsed once per file."""
    files = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    key = str(files[-1].resolve())
    if key not in _parsed:
        from jax.profiler import ProfileData

        _parsed[key] = reduce(ProfileData.from_file(key), op_scopes(key))
    return _parsed[key]


# ---------------------------------------------------------------- scopes
#
# On the TPU the op events of a device plane carry no stats of their own
# that name a scope: each op's HLO op_name sits, as the ``tf_op`` stat
# ("<op_name>:<op_type>"), on the metadata record its events share.
# `ProfileData` gives an event its own stats only, so the records are read
# from the file's protobuf wire format:
#   XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map<int64,
#   XEventMetadata>), .stat_metadata 5 (map<int64, XStatMetadata>);
#   XEventMetadata.name 2, .display_name 4, .stats 5; XStat.metadata_id 1,
#   .str_value 5, .ref_value 7 (a stat metadata whose name is the value);
#   XStatMetadata.name 2.

SCOPE_STAT = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of a message in ``buf[lo:hi]``: an int for a
    varint or fixed field, a (start, end) span for a length-delimited one."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield tag >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(path: Path) -> Dict[str, Dict[str, str]]:
    """Per TPU device plane, the scope path of each op metadata record that
    has one, keyed by the record's name and display name."""
    buf = Path(path).read_bytes()
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, records, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf in (4, 5):
                for mf, m in _fields(buf, *v):
                    if mf != 2:
                        continue
                    if pf == 4:
                        records.append(m)
                    else:
                        d = dict(_fields(buf, *m))
                        stat_names[d.get(1, 0)] = _text(buf, d[2]) if 2 in d else ""
        if not name.startswith("/device:TPU:"):
            continue
        scopes = out.setdefault(name, {})
        for rec in records:
            names, scope = [], None
            for rf, v in _fields(buf, *rec):
                if rf in (2, 4):
                    names.append(_text(buf, v))
                elif rf == 5:
                    d = dict(_fields(buf, *v))
                    if stat_names.get(d.get(1)) == SCOPE_STAT:
                        scope = _text(buf, d[5]) if 5 in d else stat_names.get(d.get(7))
            if scope:
                scopes.update((n, scope) for n in names if n)
    return out


def leaves(events: Sequence[tuple]) -> List[tuple]:
    """The events (start, end, ...) whose interval holds no other event (a
    loop's op holds its body's ops, a fusion may hold its parts)."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    inner = [False] * len(ordered)
    stack: List[int] = []
    for i, (s, e, *_) in enumerate(ordered):
        while stack and ordered[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ordered[stack[-1]][1]:
            inner[stack[-1]] = True
        stack.append(i)
    return [ev for ev, has_inner in zip(ordered, inner) if not has_inner]


def inside(events: Sequence[tuple], spans: Sequence[Tuple[int, int]]) -> List[tuple]:
    """The events (start, end, ...) that lie within one of the disjoint
    ``spans``."""
    spans = sorted(spans)
    out, j = [], 0
    for ev in sorted(events, key=lambda e: e[0]):
        while j < len(spans) and spans[j][1] <= ev[0]:
            j += 1
        if j < len(spans) and spans[j][0] <= ev[0] and ev[1] <= spans[j][1]:
            out.append(ev)
    return out


def reduce(pd, scopes: Optional[Dict[str, Dict[str, str]]] = None) -> dict:
    """``host``: (name, start, end, thread) of every ``simnet.*`` span;
    ``chunk_ops``: per TPU device, in plane order, (start, end, scope) of
    every leaf op that ran inside a ``run_chunk`` execution, its scope
    looked up by event name in ``scopes`` (`op_scopes`)."""
    scopes = scopes or {}
    host, chunk_ops = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.end_ns), f"{plane.name}/{line.name}")
                         for e in line.events if e.name.startswith(SPAN_PREFIX)]
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if tracing.OPS_LINE not in lines or tracing.MODULES_LINE not in lines:
                continue
            runs = [(int(e.start_ns), int(e.end_ns))
                    for e in lines[tracing.MODULES_LINE].events if CHUNK_PROGRAM in e.name]
            ops = [(int(e.start_ns), int(e.end_ns), e.name)
                   for e in lines[tracing.OPS_LINE].events]
            known = scopes.get(plane.name, {})
            chunk_ops[plane.name] = [(s, e, known.get(name))
                                     for s, e, name in leaves(inside(ops, runs))]
    names = sorted(chunk_ops, key=lambda n: int(n.rsplit(":", 1)[1]))
    return {"host": host, "chunk_ops": [chunk_ops[n] for n in names]}


@functools.lru_cache(maxsize=None)
def _parts(scope: Optional[str]) -> frozenset:
    """The path components of a scope, without the ``:<op_type>`` suffix
    (a fused op may join several paths with ``;``)."""
    return frozenset(part for path in (scope or "").split(";")
                     for part in path.rsplit(":", 1)[0].split("/"))


def is_scoped(scope: Optional[str]) -> bool:
    """An op under one of the chunk program's named scopes."""
    return not _parts(scope).isdisjoint(SCOPES)


def is_model_op(scope: Optional[str]) -> bool:
    """An op of the predictor: one whose scope path passes through the
    ``trunk`` or ``head`` scope."""
    return not _parts(scope).isdisjoint(MODEL_SCOPES)
