"""The plain reference of SimNet's simulation, independent of the program.

It follows the paper (SimNet, arXiv:2105.05821, sections 2-3) and this
repository's feature schema (Table 1): each instruction's static features
and address keys are computed from the DES trace, each sub-trace runs on
its own lane from an empty in-flight buffer, and one step per instruction
assembles the predictor's input from the buffer (current instruction first,
then the context newest first), predicts fetch / execution / store
latencies with the hybrid head, advances the clock by the fetch latency,
retires in order (the processor queue up to retire width x fetch cycles,
retired stores through the memory-write queue) and pushes the instruction.
A workload's cycles are the sum over its lanes of clock + drain.

It imports nothing of the program. The buffer is kept in the plain
shift-push order (slot 0 = newest), the predictor is the configuration's
own `forward` from ``bench/configs/<name>.py``, and every matrix product
goes through a `dot` of the precision that the configuration states for
its products' operands (`DOTS`): weights and activations stay float32,
each product rounds both operands to that type and accumulates in float32
exactly. The control is the same reference one step lower (`BELOW`).
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

N_OPS = 13
N_REGS = 128
STORE_OP = 7
STATIC = 41
N_KEYS = 5
LINE, PAGE = 64, 4096
LAT_SCALE = 1.0 / 64.0
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------- features

def features(t: Dict[str, np.ndarray], lo: int, hi: int):
    """Static features (T, 41) f32, address keys (T, 5) i32 and the store
    flag (T,) of instructions [lo, hi) of one trace's fields."""
    op = t["op"][lo:hi].astype(np.int64)
    T = op.shape[0]
    f = np.zeros((T, STATIC), np.float32)
    f[np.arange(T), op] = 1.0
    f[:, 13:21] = (t["src"][lo:hi].astype(np.float32) + np.float32(1)) / np.float32(N_REGS)
    f[:, 21:27] = (t["dst"][lo:hi].astype(np.float32) + np.float32(1)) / np.float32(N_REGS)
    f[:, 27] = t["mispred"][lo:hi]
    f[:, 28] = t["fetch_level"][lo:hi].astype(np.float32) / np.float32(3)
    f[:, 29:32] = t["fetch_tw"][lo:hi].astype(np.float32) / np.float32(2)
    f[:, 32:34] = t["fetch_wb"][lo:hi]
    f[:, 34] = t["data_level"][lo:hi].astype(np.float32) / np.float32(3)
    f[:, 35:38] = t["data_tw"][lo:hi].astype(np.float32) / np.float32(2)
    f[:, 38:41] = t["data_wb"][lo:hi]
    pc = t["pc"][lo:hi].astype(np.int64)
    ad = t["addr"][lo:hi].astype(np.int64)
    keys = np.stack([pc, pc // LINE, ad, ad // LINE, ad // PAGE], axis=1)
    keys[ad == 0, 2:] = 0
    if keys.max() >= 2**31:
        raise ValueError("address keys exceed int32")
    return f, keys.astype(np.int32), op == STORE_OP


def lanes_of(pool: Sequence[dict], slices) -> Dict[str, np.ndarray]:
    """Time-major inputs (T, L, ...) of every sub-trace of `slices`
    (bench, lo, n, lanes), lane-contiguous per slice in the given order.
    Every sub-trace of every slice must have the same length."""
    feats, keys, stores = [], [], []
    steps = {s.n // s.lanes for s in slices}
    if len(steps) != 1:
        raise ValueError(f"sub-traces of unequal length: {sorted(steps)}")
    (T,) = steps
    for s in slices:
        f, k, st = features(pool[s.bench], s.lo, s.lo + s.n)
        feats.append(f.reshape(s.lanes, T, STATIC))
        keys.append(k.reshape(s.lanes, T, N_KEYS))
        stores.append(st.reshape(s.lanes, T))
    return {
        "feat": np.ascontiguousarray(np.concatenate(feats).swapaxes(0, 1)),
        "addr": np.ascontiguousarray(np.concatenate(keys).swapaxes(0, 1)),
        "is_store": np.ascontiguousarray(np.concatenate(stores).swapaxes(0, 1)),
    }


# -------------------------------------------------------------- precision

def _exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def dot_f32(a, b):
    return _exact(a, b)


def dot_bf16(a, b):
    """bfloat16 operands, float32 products and sums: what JAX's default
    precision runs on the TPU for float32 operands."""
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return _exact(r(a), r(b))


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def dot_fp8(a, b):
    """float8 e4m3 operands under a per-tensor scale, float32 sums."""
    return _exact(_fp8(a), _fp8(b))


DOTS = {"float32": dot_f32, "bfloat16": dot_bf16, "float8_e4m3": dot_fp8}
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3"}


# ------------------------------------------------------------------ decode

def decode(raw, n_classes: int):
    """Hybrid head: per latency type, the argmax class, or for the last
    (overflow) class the regression output (x 64 cycles), at least 9."""
    r = raw.reshape(raw.shape[0], 3, n_classes + 1)
    logits, reg = r[..., :n_classes], r[..., n_classes]
    reg = jnp.maximum(reg, 0.0) * 64.0
    cls = jnp.argmax(logits, axis=-1)
    over = cls == n_classes - 1
    return jnp.where(over, jnp.maximum(reg, float(n_classes - 1)), cls.astype(jnp.float32))


# -------------------------------------------------------------------- scan

def _older_count(x):
    """Per slot: how many entries strictly older (higher slots) are set."""
    xi = x.astype(jnp.int32)
    return jnp.cumsum(xi[:, ::-1], axis=1)[:, ::-1] - xi


def _older_any(x):
    return _older_count(x) > 0


def make_simulate(forward: Callable, params, sim: dict, seq_padded: int,
                  dot: Callable):
    """A jitted fn(inputs (T, L, ...)) -> per-lane cycles (L,) f32."""
    Q = int(sim["ctx_len"])
    width = float(sim["retire_width"])
    max_lat = float(sim["max_latency"])
    n_classes = int(sim["n_classes"])

    def step(st, x):
        feat, keys, is_store = x["feat"], x["addr"], x["is_store"]
        L = feat.shape[0]
        valid_f = st["valid"].astype(jnp.float32)
        dep = (st["addr"] == keys[:, None, :]) & (keys[:, None, :] != 0)
        ctx = jnp.concatenate([
            st["feat"],
            (st["resid"] * LAT_SCALE)[..., None],
            (st["exec"] * LAT_SCALE)[..., None],
            (st["store"] * LAT_SCALE)[..., None],
            dep.astype(jnp.float32),
            valid_f[..., None],
        ], axis=-1) * valid_f[..., None]
        cur = jnp.concatenate(
            [feat, jnp.zeros((L, 8), jnp.float32), jnp.ones((L, 1), jnp.float32)], axis=-1)
        inp = jnp.concatenate([cur[:, None], ctx], axis=1)
        inp = jnp.pad(inp, ((0, 0), (0, seq_padded - inp.shape[1]), (0, 0)))
        lat = decode(forward(params, inp, dot), n_classes)
        fetch = jnp.clip(jnp.round(lat[:, 0]), 0.0, max_lat)
        ex = jnp.clip(jnp.round(lat[:, 1]), 1.0, max_lat)
        sto = jnp.where(is_store, jnp.clip(jnp.round(lat[:, 2]), 1.0, max_lat), 0.0)

        tick = st["tick"] + fetch
        resid = st["resid"] + jnp.where(st["valid"], fetch[:, None], 0.0)
        valid, in_mw = st["valid"], st["in_mw"]
        # processor queue: oldest first, stop at the first entry not done,
        # at most retire width x max(fetch, 1) entries
        budget = (width * jnp.maximum(fetch, 1.0)).astype(jnp.int32)
        proc = valid & ~in_mw
        ready = proc & (resid >= st["exec"])
        ok = ready & ~_older_any(proc & ~ready)
        retire = ok & (_older_count(ok) < budget[:, None])
        to_mw = retire & st["is_store"]
        in_mw = in_mw | to_mw
        valid = valid & ~(retire & ~to_mw)
        # memory-write queue: oldest first, stop at the first not written
        mw = valid & in_mw
        ready_m = mw & (resid >= st["store"])
        valid = valid & ~(ready_m & ~_older_any(mw & ~ready_m))
        in_mw = in_mw & valid

        def push(buf, new):
            return jnp.concatenate([new[:, None].astype(buf.dtype), buf[:, :-1]], axis=1)

        return {
            "feat": push(st["feat"], feat), "addr": push(st["addr"], keys),
            "resid": push(resid, jnp.zeros_like(fetch)),
            "exec": push(st["exec"], ex), "store": push(st["store"], sto),
            "valid": push(valid, jnp.ones_like(is_store)),
            "in_mw": push(in_mw, jnp.zeros_like(is_store)),
            "is_store": push(st["is_store"], is_store), "tick": tick,
        }, None

    @jax.jit
    def simulate(xs):
        L = xs["feat"].shape[1]
        st = {
            "feat": jnp.zeros((L, Q, STATIC), jnp.float32),
            "addr": jnp.zeros((L, Q, N_KEYS), jnp.int32),
            "resid": jnp.zeros((L, Q), jnp.float32),
            "exec": jnp.zeros((L, Q), jnp.float32),
            "store": jnp.zeros((L, Q), jnp.float32),
            "valid": jnp.zeros((L, Q), bool),
            "in_mw": jnp.zeros((L, Q), bool),
            "is_store": jnp.zeros((L, Q), bool),
            "tick": jnp.zeros((L,), jnp.float32),
        }
        st, _ = jax.lax.scan(step, st, xs)
        need = jnp.where(st["valid"], jnp.maximum(st["exec"], st["store"]) - st["resid"], 0.0)
        return st["tick"] + jnp.max(jnp.maximum(need, 0.0), axis=1)

    return simulate


def workload_cycles(simulate, pool, slices, block_lanes: int) -> np.ndarray:
    """Cycles per slice (f64), the lanes run in blocks of whole slices of
    at most `block_lanes` lanes, each block padded with idle lanes to
    `block_lanes` so that one compiled program serves every block."""
    out, blk = [], []

    def flush():
        xs = lanes_of(pool, blk)
        pad = block_lanes - xs["feat"].shape[1]
        xs = {k: np.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)) for k, v in xs.items()}
        lane = np.asarray(simulate({k: jnp.asarray(v) for k, v in xs.items()}), np.float64)
        i = 0
        for s in blk:
            out.append(lane[i:i + s.lanes].sum())
            i += s.lanes
        blk.clear()

    for s in slices:
        if blk and sum(b.lanes for b in blk) + s.lanes > block_lanes:
            flush()
        blk.append(s)
    if blk:
        flush()
    return np.asarray(out)
