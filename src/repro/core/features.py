"""SimNet feature schema — the paper's Table 1, concretely laid out.

Every instruction is a 50-float row:

  [0:13)   operation features (one-hot op class; branch/barrier bits)
  [13:21)  8 source register indices, scaled to [0,1]
  [21:27)  6 destination register indices, scaled
  [27]     branch misprediction flag            ┐
  [28]     fetch access level (/3)              │
  [29:32)  fetch table-walk levels (/2)         │ history context
  [32:34)  fetch-caused writebacks              │ (14 features, from the
  [34]     data access level (/3)               │ lightweight history
  [35:38)  data table-walk levels (/2)          │ simulation)
  [38:41)  data-caused writebacks               ┘
  [41]     residence latency (× LAT_SCALE)      ┐ dynamic — assembled by
  [42]     execution latency (× LAT_SCALE)      │ the simulator from the
  [43]     store latency (× LAT_SCALE)          │ queues at each step
  [44:49)  memory dependency flags vs current   │
  [49]     valid (1 = real context entry)       ┘

The static block [0:41) is fixed per instruction: a function of its integer
trace fields (`FIELDS`), defined once by `STATIC_RULES` and computed by
`expand`. The host expands a trace into `trace_arrays` (dataset builder,
one-shot `simulate_trace`, the wire form); the engine packs the integer
fields themselves and the chunk program expands them on the device
(`core.simulator.chunk_time_major`). Both run the same rules on the same
float32 tables, so they agree bit for bit. The dynamic block [41:50) is
written by the simulator/dataset builder. The to-be-predicted instruction
uses the same row with zeros in the dynamic block (the paper pads 47 → 50
the same way).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping

import numpy as np

from repro.des.isa import MAX_DST, MAX_SRC, N_OP_CLASSES, N_REGS, Op

N_FEATURES = 50
STATIC_END = 41
IDX_RESID = 41
IDX_EXEC = 42
IDX_STORE = 43
IDX_DEP = 44  # 5 flags: same pc / same iline / same data addr / line / page
IDX_VALID = 49
LAT_SCALE = 1.0 / 64.0

# address-key columns for dependency-flag comparison
ADDR_PC = 0
ADDR_ILINE = 1
ADDR_DATA = 2
ADDR_DLINE = 3
ADDR_DPAGE = 4
N_ADDR_KEYS = 5

LINE_BYTES = 64
PAGE_BYTES = 4096
LINE_SHIFT = 6  # keys are shifts of the int32 addresses: floor division
PAGE_SHIFT = 12
assert 1 << LINE_SHIFT == LINE_BYTES and 1 << PAGE_SHIFT == PAGE_BYTES


# ------------------------------------------------------------------ fields

@dataclasses.dataclass(frozen=True)
class Field:
    """One trace field as the engine packs it: ``width`` columns per
    instruction (0: one value), stored as ``dtype``. ``pad`` fills the
    steps and lanes a pack pads with, and expands to all-zero columns."""

    name: str
    width: int
    dtype: Any
    pad: int = 0

    def shape(self, n: int) -> tuple:
        return (n,) if self.width == 0 else (n, self.width)


FIELDS = (
    Field("op", 0, np.int8, pad=-1),  # op class, [0, 13)
    Field("src", MAX_SRC, np.int8, pad=-1),  # register index, -1 = unused
    Field("dst", MAX_DST, np.int8, pad=-1),
    Field("mispred", 0, np.int8),
    Field("fetch_level", 0, np.int8),
    Field("fetch_tw", 3, np.int8),
    Field("fetch_wb", 2, np.int8),
    Field("data_level", 0, np.int8),
    Field("data_tw", 3, np.int8),
    Field("data_wb", 3, np.int8),
    Field("pc", 0, np.int32),
    Field("addr", 0, np.int32),  # data address, 0 = none
    Field("fetch_lat", 0, np.float32),  # labels: the DES latencies
    Field("exec_lat", 0, np.float32),
    Field("store_lat", 0, np.float32),
)
FIELD = {f.name: f for f in FIELDS}
LABELS = ("fetch_lat", "exec_lat", "store_lat")


# ------------------------------------------------------- static-block rules

# Each rule maps a field, its columns last (a one-value field given one
# column), to its float32 columns of the static block.

@dataclasses.dataclass(frozen=True)
class OneHot:
    """1.0 in column x of n, zeros elsewhere (none for x outside [0, n))."""

    n: int

    def width(self, field: Field) -> int:
        return self.n

    def __call__(self, x, xp):
        return (x == xp.arange(self.n, dtype=x.dtype)).astype(np.float32)

    def invert(self, v):
        return v.argmax(axis=-1)[..., None]


@dataclasses.dataclass(frozen=True)
class Scale:
    """(x + offset) * scale. The scale is a power of two, so the result is
    the one float32 rounding of the integer sum: exact, no division."""

    offset: float
    scale: float

    def __post_init__(self):
        assert math.frexp(self.scale)[0] == 0.5, "scale must be a power of two"

    def width(self, field: Field) -> int:
        return max(field.width, 1)

    def __call__(self, x, xp):
        v = x.astype(np.float32)
        if self.offset:
            v = v + np.float32(self.offset)
        return v * np.float32(self.scale) if self.scale != 1 else v

    def invert(self, v):
        return np.rint(v / self.scale - self.offset)


@dataclasses.dataclass(frozen=True, eq=False)
class Table:
    """values[x] for an int8 field: one float32 entry for every value the
    dtype holds, indexed by the value's two's-complement byte."""

    values: np.ndarray  # (256,) float32

    def width(self, field: Field) -> int:
        return max(field.width, 1)

    def __call__(self, x, xp):
        if xp is np:
            return self.values[x.astype(np.uint8)]
        # the device: a binary tree of selects on the byte's bits. A select
        # moves a table value without rounding it; XLA's TPU cost model puts
        # a gather of single values from a table far above the tree.
        u = x.astype(np.int32) & 0xFF
        vals = list(self.values)
        for b in range(8):
            bit = ((u >> b) & 1) == 1
            vals = [xp.where(bit, hi, lo) for lo, hi in zip(vals[0::2], vals[1::2])]
        return vals[0]

    def invert(self, v):
        order = np.argsort(self.values, kind="stable")
        at = np.searchsorted(self.values[order], v).clip(0, len(order) - 1)
        return order[at].astype(np.uint8).view(np.int8)


_BYTES = np.arange(256, dtype=np.uint8).view(np.int8)  # every int8, byte order
LEVEL_TABLE = _BYTES.astype(np.float32) / 3.0  # access levels: x / 3
LEVELS = Table(LEVEL_TABLE)

# the static block [0:41), in column order: (field, rule)
STATIC_RULES = (
    ("op", OneHot(N_OP_CLASSES)),
    ("src", Scale(1.0, 1.0 / N_REGS)),
    ("dst", Scale(1.0, 1.0 / N_REGS)),
    ("mispred", Scale(0.0, 1.0)),
    ("fetch_level", LEVELS),
    ("fetch_tw", Scale(0.0, 0.5)),
    ("fetch_wb", Scale(0.0, 1.0)),
    ("data_level", LEVELS),
    ("data_tw", Scale(0.0, 0.5)),
    ("data_wb", Scale(0.0, 1.0)),
)
assert sum(r.width(FIELD[k]) for k, r in STATIC_RULES) == STATIC_END


# address keys: (pc or data address) >> shift, in ADDR_* order. Zero means
# "no address": dependency flags require both sides nonzero.
KEY_SHIFTS = (0, LINE_SHIFT, 0, LINE_SHIFT, PAGE_SHIFT)


def expand(fields: Mapping[str, Any], xp=np) -> Dict[str, Any]:
    """Integer fields (any leading shape, each field's columns last) to
    what the simulator consumes: feat (..., 41) float32, addr (..., 5)
    int32, is_store (...) bool, labels (..., 3) float32. ``xp`` is numpy
    on the host or `jax.numpy` on the device: the same rules either way.
    On time-major fields XLA writes each rule's columns, and the keys'
    one select and shift, straight into the layout the scan reads."""
    groups, lookups = [], {}
    for name, rule in STATIC_RULES:
        x = fields[name] if FIELD[name].width else fields[name][..., None]
        if isinstance(rule, Table):  # looked up below
            lookups.setdefault(rule, []).append((len(groups), x))
            groups.append(None)
        else:
            groups.append(rule(x, xp))
    # the fields of one table share one lookup (one select tree to compile)
    for rule, at in lookups.items():
        looked = rule(xp.concatenate([x for _, x in at], axis=-1), xp)
        for i, (g, _) in enumerate(at):
            groups[g] = looked[..., i:i + 1]
    pc = fields["pc"].astype(np.int32)[..., None]
    ad = fields["addr"].astype(np.int32)[..., None]
    key = xp.arange(N_ADDR_KEYS)
    return dict(
        feat=xp.concatenate(groups, axis=-1),
        addr=xp.where(key < ADDR_DATA, pc, ad) >> xp.asarray(KEY_SHIFTS, np.int32),
        is_store=fields["op"] == int(Op.STORE),
        labels=xp.stack([fields[k].astype(np.float32) for k in LABELS], axis=-1),
    )


# ------------------------------------------------------ the engine's input

def n_instructions(fields: Mapping[str, Any]) -> int:
    return int(np.shape(fields["op"])[0])


# per field: its columns' shape and, for an integer field, the values it
# may hold to be packed exactly
_CHECKS = tuple(
    (f.name, f.shape(1)[1:], np.dtype(f.dtype),
     None if f.dtype == np.float32 else (0, N_OP_CLASSES - 1) if f.name == "op"
     else (int(np.iinfo(f.dtype).min), int(np.iinfo(f.dtype).max)))
    for f in FIELDS)


@functools.lru_cache(maxsize=64)
def _value_checks(dtypes: tuple) -> tuple:
    """For fields of these dtypes (in `FIELDS` order): refuse a label that is
    not numeric or another field that is not integer, and return (name,
    lo, hi, view) for each field whose values must be checked: the op
    class, and every field whose dtype is wider than its packed one. With
    lo 0, ``view`` is the unsigned dtype of the field's width: one max
    over it finds a negative value too."""
    out = []
    for (name, _, packed, bounds), dt in zip(_CHECKS, dtypes):
        if bounds is None:  # a label
            if dt.kind not in "biuf":
                raise ValueError(f"trace field {name!r} is not numeric ({dt})")
        elif dt.kind not in "biu":
            raise ValueError(f"trace field {name!r} is not integer ({dt})")
        elif name == "op" or not np.can_cast(dt, packed):
            view = np.dtype(f"u{dt.itemsize}") if bounds[0] == 0 else None
            out.append((name,) + bounds + (view,))
    return tuple(out)


def check_fields(fields: Dict[str, np.ndarray]) -> None:
    """Refuse fields the pack cannot hold exactly: a shape other than the
    field's, an op class outside [0, 13), or an integer that does not fit
    its packed dtype (register indices int8, pc and address int32). Runs
    once per submitted job, so it does little besides the value checks."""
    op = fields["op"]
    if op.ndim != 1:
        raise ValueError(f"trace field 'op' has shape {op.shape}, want (T,)")
    T = op.shape[0]
    for name, tail, _, _ in _CHECKS:
        if fields[name].shape != (T,) + tail:
            raise ValueError(f"trace field {name!r} has shape {fields[name].shape}, "
                             f"want {(T,) + tail}")
    checks = _value_checks(tuple(fields[c[0]].dtype for c in _CHECKS))
    for name, lo, hi, view in checks if T else ():
        a = fields[name]
        if (a.view(view).max() > hi if view is not None
                else a.min() < lo or a.max() > hi):
            raise ValueError(f"trace field {name!r} holds values outside "
                             f"[{lo}, {hi}]: [{a.min()}, {a.max()}]")


def trace_fields(trace) -> Dict[str, np.ndarray]:
    """The engine's input form of a workload: its integer trace fields and
    labels, checked by `check_fields`. ``trace`` is a `des.trace.Trace`, a
    dict of those fields, or a `trace_arrays` dict (inverted exactly by
    `fields_from_arrays`). A Trace's own arrays are returned, not copied:
    the pack casts while it copies them."""
    if isinstance(trace, Mapping):
        if "feat" in trace:
            return fields_from_arrays(trace)
        get = trace.__getitem__
    else:
        get = trace.__getattribute__
    try:
        fields = {c[0]: np.asarray(get(c[0])) for c in _CHECKS}
    except (KeyError, AttributeError) as e:
        raise ValueError(f"trace lacks field {e}") from None
    check_fields(fields)
    return fields


def fields_from_arrays(arrs: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """Invert a `trace_arrays` dict into the fields it was expanded from,
    and check the inversion by expanding again: a dict whose rows are not
    the image of integer fields, bit for bit, raises ValueError."""
    try:
        feat = np.asarray(arrs["feat"], np.float32)
        keys = np.asarray(arrs["addr"])
        is_store = np.asarray(arrs["is_store"])
        labels = np.asarray(arrs["labels"], np.float32)
    except KeyError as e:
        raise ValueError(f"trace arrays lack {e}") from None
    T = feat.shape[0] if feat.ndim == 2 else -1
    if (feat.shape != (T, STATIC_END) or keys.shape != (T, N_ADDR_KEYS)
            or is_store.shape != (T,) or labels.shape != (T, 3)):
        raise ValueError(
            f"trace arrays must be feat (T, {STATIC_END}), addr (T, {N_ADDR_KEYS}), "
            f"is_store (T,), labels (T, 3); got feat {feat.shape}, addr "
            f"{keys.shape}, is_store {is_store.shape}, labels {labels.shape}")
    if not np.isfinite(feat).all() or keys.dtype.kind not in "biu":
        raise ValueError("trace arrays: feat must be finite and addr integer")
    fields, c = {}, 0
    for name, rule in STATIC_RULES:
        f = FIELD[name]
        w = rule.width(f)
        info = np.iinfo(f.dtype)
        x = np.clip(rule.invert(feat[:, c:c + w]), info.min, info.max).astype(f.dtype)
        fields[name] = x if f.width else x[:, 0]
        c += w
    fields["pc"] = keys[:, ADDR_PC].clip(-2**31, 2**31 - 1).astype(np.int32)
    fields["addr"] = keys[:, ADDR_DATA].clip(-2**31, 2**31 - 1).astype(np.int32)
    for i, k in enumerate(LABELS):
        fields[k] = labels[:, i]
    again = expand(fields)
    if not (np.array_equal(again["feat"].view(np.uint32), feat.view(np.uint32))
            and np.array_equal(again["addr"], keys)
            and np.array_equal(again["is_store"], is_store.astype(bool))):
        raise ValueError(
            "trace arrays are not the expansion of integer trace fields "
            "(feat / addr / is_store rows outside the schema of core.features)")
    return fields


# ------------------------------------------------------------ host helpers

def trace_arrays(trace) -> Dict[str, np.ndarray]:
    """Everything the JAX simulator consumes, expanded on the host:
    feat (T, 41) f32, addr (T, 5) i32, is_store (T,) bool, labels (T, 3)
    f32."""
    return expand(trace_fields(trace))


def static_features(trace) -> np.ndarray:
    """(T, 41) float32 static+history feature block."""
    return trace_arrays(trace)["feat"]
