"""Instruction-centric SimNet simulator in JAX (paper §3).

State per lane: an in-flight buffer that plays both paper queues — entries
carry an ``in_mw`` flag that flips when a retired store moves to the
memory-write queue. One `lax.scan` step = one instruction: assemble model
input from the buffer, predict (or teacher-force) the three latencies,
advance the clock, retire in order, push.

Step layouts (``SimConfig.layout``): the buffer state was the simulator's
dominant HBM roofline term, so TWO physical layouts implement the same
logical recency-ordered queue:

  "ring" (default) — slots form a ring buffer with a global ``head``
    write cursor. A push is ONE `dynamic_update_slice` per plane; recency
    order is recovered by index arithmetic (`recency_view` = flip +
    cyclic roll) instead of physically moving every plane. Per-step queue
    traffic for the wide feat/addr planes drops from O(L·Q·F) writes to
    an O(L·F) slot write (the latency planes are still read in full by
    retirement, and the small (L, Q) bookkeeping planes still update in
    place).
  "roll" — the original shift-push layout (slot 0 = physically newest;
    every plane moves one slot per step). Kept as the exactness
    reference: the ring step reproduces `_retire`'s recency-ordered
    retirement decisions in physical order via head-anchored cyclic
    prefix-sums (`older_count` in `_sim_step_ring`) — exact integer/
    boolean math, so per-lane totals are bit-identical between the
    layouts, teacher-forced and predicted (guarded by
    tests/test_ring_layout.py and a hypothesis property test).

Lanes are the paper's sub-traces: `vmap` over lanes batches the predictor
inference exactly like the paper's GPU batching; under `pjit` the lane axis
shards over ("pod","data") with zero steady-state communication.

Multi-workload packing (one level up from the paper): lanes from *many*
workloads × SimConfigs share one scan. Each lane carries a workload id, a
per-lane retire width / context capacity (so heterogeneous SimConfigs pack
together), and a per-step validity mask for ragged trace lengths — a lane
whose sub-trace has ended freezes in place, so packed per-lane results are
bit-identical to running each workload alone. Per-workload totals come out
of one `segment_sum` over the lane axis.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as F


@dataclasses.dataclass(frozen=True)
class SimConfig:
    ctx_len: int = 64  # in-flight buffer capacity = max context instructions
    retire_width: int = 8
    n_classes: int = 10  # hybrid head classes per latency type
    max_latency: float = 100000.0
    state_dtype: str = "float32"  # "bfloat16" halves the queue-state HBM
    # traffic that the ring layout has not already eliminated; cycle
    # counters stay f32 so totals are exact (see tests/test_ring_layout).
    layout: str = "ring"  # "ring" = O(1)-push slot writes + head cursor;
    # "roll" = shift-push every plane (the original exactness reference).
    # Totals are bit-identical between the two (the ring step reproduces
    # the roll retirement decisions with exact integer math — see the
    # module docstring); layout is part of the compiled executable's
    # cache identity because SimConfig rides in serving.ExecutableKey.

    def __post_init__(self):
        if self.layout not in ("ring", "roll"):
            raise ValueError(f"layout must be 'ring' or 'roll', got {self.layout!r}")


class SimState(NamedTuple):
    feat: jax.Array  # (L, Q, 41) static blocks of in-flight instrs
    addr: jax.Array  # (L, Q, 5) int32 comparison keys
    resid: jax.Array  # (L, Q) f32 cycles since entry
    exec_lat: jax.Array  # (L, Q) f32 predicted execution latency
    store_lat: jax.Array  # (L, Q) f32 predicted store latency
    valid: jax.Array  # (L, Q) bool
    in_mw: jax.Array  # (L, Q) bool — retired store awaiting memory write
    is_store_q: jax.Array  # (L, Q) bool — store marker of in-flight entries.
    # Duplicates feat[:, :, 7] (the Op.STORE one-hot) so retirement never
    # READS the wide feat plane: in the ring layout a read of a plane that
    # is then slice-written in place can force XLA into a defensive full
    # copy, which would hand back the whole O(L·Q·F) traffic the layout
    # exists to remove.
    cur_tick: jax.Array  # (L,) f32
    overflow: jax.Array  # (L,) i32 force-dropped entries (diagnostic)
    head: jax.Array  # () i32 ring write cursor (stays 0 in roll layout).
    # GLOBAL, not per-lane: every step advances it whether or not a lane is
    # active. A frozen (inactive) lane's plane values never change, and
    # nothing that survives the freeze — drain, totals, overflow — depends
    # on recency order, so reinterpreting a frozen buffer under a moved
    # head is harmless. This assumes inactivity is terminal (pack_workloads
    # masks only ragged TAILS); a lane that went active again would need
    # the per-lane-head variant. Being a scalar keeps the push a single
    # `dynamic_update_slice` (no scatter) and replicates with zero
    # communication under the mesh.


def init_state(n_lanes: int, cfg: SimConfig) -> SimState:
    L, Q = n_lanes, cfg.ctx_len
    sd = jnp.dtype(cfg.state_dtype)
    return SimState(
        feat=jnp.zeros((L, Q, F.STATIC_END), sd),
        addr=jnp.zeros((L, Q, F.N_ADDR_KEYS), jnp.int32),
        resid=jnp.zeros((L, Q), jnp.float32),  # cycle counters stay exact
        exec_lat=jnp.zeros((L, Q), jnp.float32),
        store_lat=jnp.zeros((L, Q), jnp.float32),
        valid=jnp.zeros((L, Q), bool),
        in_mw=jnp.zeros((L, Q), bool),
        is_store_q=jnp.zeros((L, Q), bool),
        cur_tick=jnp.zeros((L,), jnp.float32),
        overflow=jnp.zeros((L,), jnp.int32),
        head=jnp.zeros((), jnp.int32),
    )


def recency_view(state: SimState) -> SimState:
    """Ring-layout state reordered so index 0 = newest (the roll layout's
    physical invariant): recency r lives at slot (head - 1 - r) mod Q,
    which is a flip followed by a cyclic roll — two slices, no gather.
    Values are moved, never recomputed, so anything derived from the view
    is bit-identical to the roll path."""

    def rec(a):
        return jnp.flip(jnp.roll(a, -state.head, axis=1), axis=1)

    return state._replace(
        feat=rec(state.feat), addr=rec(state.addr), resid=rec(state.resid),
        exec_lat=rec(state.exec_lat), store_lat=rec(state.store_lat),
        valid=rec(state.valid), in_mw=rec(state.in_mw),
        is_store_q=rec(state.is_store_q),
    )


def model_input(state: SimState, cur_feat, cur_addr, cfg: SimConfig):
    """Layout-aware input assembly: recency-order the ring state first."""
    if cfg.layout == "ring":
        state = recency_view(state)
    return build_model_input(state, cur_feat, cur_addr)


def build_model_input(state: SimState, cur_feat, cur_addr):
    """Assemble (L, 1+Q, 50): current instruction + context, recency order
    (the state must already be recency-ordered — i.e. roll layout, or a
    ring state through `recency_view`)."""
    L, Q, _ = state.feat.shape
    sd = state.feat.dtype
    dep = jnp.logical_and(
        state.addr == cur_addr[:, None, :], cur_addr[:, None, :] != 0
    )  # (L, Q, 5)
    valid_f = state.valid.astype(sd)
    ctx = jnp.concatenate(
        [
            state.feat,
            (state.resid * F.LAT_SCALE)[..., None].astype(sd),
            (state.exec_lat * F.LAT_SCALE)[..., None].astype(sd),
            (state.store_lat * F.LAT_SCALE)[..., None].astype(sd),
            dep.astype(sd),
            valid_f[..., None],
        ],
        axis=-1,
    )  # (L, Q, 50)
    ctx = ctx * valid_f[..., None]  # zero out padding rows entirely
    cur = jnp.concatenate(
        [
            cur_feat.astype(sd),
            jnp.zeros((L, 3 + 5), sd),
            jnp.ones((L, 1), sd),
        ],
        axis=-1,
    )  # (L, 50)
    return jnp.concatenate([cur[:, None, :], ctx], axis=1)  # (L, 1+Q, 50)


def _suffix_any(x):
    """suffix_any[q] = any(x[q+1:]) along the last axis."""
    rev_cs = jnp.cumsum(x[..., ::-1].astype(jnp.int32), axis=-1)[..., ::-1]
    after = rev_cs - x.astype(jnp.int32)
    return after > 0


def _suffix_count(x):
    """suffix_count[q] = sum(x[q+1:])."""
    rev_cs = jnp.cumsum(x[..., ::-1].astype(jnp.int32), axis=-1)[..., ::-1]
    return rev_cs - x.astype(jnp.int32)


def _lane_where(active, new, old):
    """Per-lane select: keep `old` where the lane is inactive this step."""
    a = active.reshape(active.shape + (1,) * (new.ndim - 1))
    return jnp.where(a, new, old)


def _clip_lats(cur, lats, cfg: SimConfig):
    """Round/clip the three predicted latencies (shared by both layouts)."""
    fetch, exec_lat, store_lat = lats[:, 0], lats[:, 1], lats[:, 2]
    fetch = jnp.clip(jnp.round(fetch), 0, cfg.max_latency)
    exec_lat = jnp.clip(jnp.round(exec_lat), 1, cfg.max_latency)
    store_lat = jnp.where(
        cur["is_store"], jnp.clip(jnp.round(store_lat), 1, cfg.max_latency), 0.0
    )
    return fetch, exec_lat, store_lat


def _retire(valid, in_mw, resid, exec_lat, store_lat, is_store, fetch, cfg,
            retire_width):
    """Both paper queues' retirement over RECENCY-ordered (L, Q) planes
    (index 0 = newest) — the roll layout's in-place path. The ring layout
    reproduces exactly these decisions in physical order via cyclic
    prefix-sums (see `_sim_step_ring.older_count`): integer/boolean math
    only, so the two layouts stay bit-identical."""
    # --- processor-queue retirement: in-order, bandwidth-limited ---
    rw = jnp.asarray(cfg.retire_width, jnp.float32) if retire_width is None else retire_width.astype(jnp.float32)
    budget = (rw * jnp.maximum(fetch, 1.0)).astype(jnp.int32)  # (L,)
    proc = valid & ~in_mw
    ready_p = proc & (resid >= exec_lat)
    blocked = proc & ~ready_p
    eligible = ready_p & ~_suffix_any(blocked)
    retire_p = eligible & (_suffix_count(eligible) < budget[:, None])
    # retired stores move to the memory-write queue; others leave
    to_mw = retire_p & is_store
    in_mw = in_mw | to_mw
    valid = valid & ~(retire_p & ~to_mw)

    # --- memory-write queue retirement: in-order, unlimited ---
    mw = valid & in_mw
    ready_m = mw & (resid >= store_lat)
    blocked_m = mw & ~ready_m
    retire_m = ready_m & ~_suffix_any(blocked_m)
    valid = valid & ~retire_m
    in_mw = in_mw & valid
    return valid, in_mw


def sim_step(
    state: SimState,
    cur,
    lats,
    cfg: SimConfig,
    *,
    active: Optional[jax.Array] = None,
    retire_width: Optional[jax.Array] = None,
    lane_ctx: Optional[jax.Array] = None,
) -> SimState:
    """Advance one instruction. cur: dict(feat (L,41), addr (L,5),
    is_store (L,)); lats: (L, 3) predicted/true (fetch, exec, store).

    Optional per-lane controls (packed multi-workload mode):
      active (L,) bool — lanes with False keep their state unchanged (ragged
        trace lengths: a finished lane freezes, its drain stays exact).
      retire_width (L,) i32 — per-lane processor retire bandwidth, overriding
        the scalar ``cfg.retire_width`` (heterogeneous SimConfigs in one pack).
      lane_ctx (L,) i32 — per-lane in-flight capacity ≤ cfg.ctx_len; entries
        pushed past it are force-dropped and counted in ``overflow`` exactly
        as a standalone run with that smaller ctx_len would.
    """
    if cfg.layout == "ring":
        return _sim_step_ring(
            state, cur, lats, cfg,
            active=active, retire_width=retire_width, lane_ctx=lane_ctx,
        )
    fetch, exec_lat, store_lat = _clip_lats(cur, lats, cfg)

    # clock + residence advance
    cur_tick = state.cur_tick + fetch
    resid = state.resid + jnp.where(state.valid, fetch[:, None], 0.0)

    # roll layout: slot index IS recency order, retire in place
    valid, in_mw = _retire(
        state.valid, state.in_mw, resid, state.exec_lat, state.store_lat,
        state.is_store_q, fetch, cfg, retire_width,
    )

    # --- push current instruction at slot 0 (roll the buffer) ---
    Q = state.valid.shape[1]
    if lane_ctx is None:
        overflow = state.overflow + valid[:, -1].astype(jnp.int32)
    else:
        # entry at the lane's own capacity boundary is force-dropped on push
        idx = jnp.clip(lane_ctx - 1, 0, Q - 1)
        at_cap = jnp.take_along_axis(valid, idx[:, None], axis=1)[:, 0]
        overflow = state.overflow + at_cap.astype(jnp.int32)

    def push(buf, new):
        return jnp.concatenate([new[:, None].astype(buf.dtype), buf[:, :-1]], axis=1)

    valid_new = push(valid, jnp.ones_like(fetch, dtype=bool))
    in_mw_new = push(in_mw, jnp.zeros_like(fetch, dtype=bool))
    if lane_ctx is not None:
        keep = jnp.arange(Q)[None, :] < lane_ctx[:, None]
        valid_new = valid_new & keep
        in_mw_new = in_mw_new & keep

    new_state = SimState(
        feat=push(state.feat, cur["feat"]),
        addr=push(state.addr, cur["addr"]),
        resid=push(resid, jnp.zeros_like(fetch)),
        exec_lat=push(state.exec_lat, exec_lat),
        store_lat=push(state.store_lat, store_lat),
        valid=valid_new,
        in_mw=in_mw_new,
        is_store_q=push(state.is_store_q, cur["is_store"]),
        cur_tick=cur_tick,
        overflow=overflow,
        head=state.head,
    )
    if active is None:
        return new_state
    # head is a global scalar (last field) — lane-select every other plane
    merged = [_lane_where(active, n, o)
              for n, o in zip(new_state[:-1], state[:-1])]
    return SimState(*merged, state.head)


def _sim_step_ring(
    state: SimState,
    cur,
    lats,
    cfg: SimConfig,
    *,
    active: Optional[jax.Array] = None,
    retire_width: Optional[jax.Array] = None,
    lane_ctx: Optional[jax.Array] = None,
) -> SimState:
    """Ring-layout step: identical semantics to the roll step, but the push
    is ONE `dynamic_update_slice` at the global ``head`` cursor instead of
    shifting every plane, and retirement runs directly in PHYSICAL order:
    "how many set entries are strictly older (in recency) than slot p" is
    a cyclic prefix-sum anchored at the head cursor, so the roll layout's
    reversed cumsums (`_suffix_any`/`_suffix_count` over recency order)
    are reproduced with exact integer arithmetic and zero permutation
    traffic. The heavy (L, Q, F) feat/addr planes and the latency planes
    are only ever written at the pushed slot."""
    L, Q = state.valid.shape
    fetch, exec_lat, store_lat = _clip_lats(cur, lats, cfg)

    # clock + residence advance (physical order: elementwise, no reorder)
    cur_tick = state.cur_tick + fetch
    resid = state.resid + jnp.where(state.valid, fetch[:, None], 0.0)

    head = state.head  # () i32 — global write cursor (= step count mod Q)
    slot = jnp.arange(Q, dtype=head.dtype)[None, :]

    def older_count(x):
        """Per slot: how many set entries of ``x`` are OLDER in recency.
        Physical cyclic order runs oldest→newest from the head cursor, so
        the count is the cyclic-range sum over [head, p) — exact int32,
        bit-for-bit the roll layout's `_suffix_count` over recency order."""
        xi = x.astype(jnp.int32)
        cs = jnp.cumsum(xi, axis=-1)
        excl = cs - xi  # exclusive prefix sum in physical order
        total = cs[:, -1:]
        at_head = jax.lax.dynamic_slice_in_dim(excl, head, 1, axis=1)  # (L, 1)
        return jnp.where(slot >= head, excl - at_head, total - at_head + excl)

    # --- processor-queue retirement: in-order, bandwidth-limited ---
    rw = jnp.asarray(cfg.retire_width, jnp.float32) if retire_width is None else retire_width.astype(jnp.float32)
    budget = (rw * jnp.maximum(fetch, 1.0)).astype(jnp.int32)  # (L,)
    proc = state.valid & ~state.in_mw
    ready_p = proc & (resid >= state.exec_lat)
    blocked = proc & ~ready_p
    eligible = ready_p & (older_count(blocked) == 0)
    retire_p = eligible & (older_count(eligible) < budget[:, None])
    # retired stores move to the memory-write queue; others leave
    to_mw = retire_p & state.is_store_q
    in_mw_p = state.in_mw | to_mw
    valid_p = state.valid & ~(retire_p & ~to_mw)

    # --- memory-write queue retirement: in-order, unlimited ---
    mw = valid_p & in_mw_p
    ready_m = mw & (resid >= state.store_lat)
    blocked_m = mw & ~ready_m
    retire_m = ready_m & (older_count(blocked_m) == 0)
    valid_p = valid_p & ~retire_m
    in_mw_p = in_mw_p & valid_p

    # push accounting (recency index r lives at slot (head - 1 - r) mod Q)
    if lane_ctx is None:
        # the oldest entry sits AT the head slot, about to be overwritten
        at_cap = jax.lax.dynamic_slice_in_dim(valid_p, head, 1, axis=1)[:, 0]
    else:
        cap_slot = (head - lane_ctx.astype(head.dtype)) % Q  # (L,)
        at_cap = jnp.take_along_axis(valid_p, cap_slot[:, None], axis=1)[:, 0]
        # entries whose post-push recency would reach the lane's capacity
        # are force-dropped now (the new entry itself is always kept)
        age = (head - 1 - slot) % Q  # (1, Q) — lane-independent
        keep = age < (lane_ctx[:, None] - 1)
        valid_p = valid_p & keep
        in_mw_p = in_mw_p & keep
    overflow = state.overflow + at_cap.astype(jnp.int32)

    # freeze inactive lanes on the planes that were rewritten above; the
    # wide planes below are only touched at the push slot, where the write
    # itself is made conditional — no full-plane select needed for them
    if active is not None:
        resid = _lane_where(active, resid, state.resid)
        valid_p = _lane_where(active, valid_p, state.valid)
        in_mw_p = _lane_where(active, in_mw_p, state.in_mw)
        cur_tick = jnp.where(active, cur_tick, state.cur_tick)
        overflow = jnp.where(active, overflow, state.overflow)

    # --- O(1) push: one head-slot slice write per plane ---
    def put(buf, new):
        """Write the (L, 1, ...) head slot; inactive lanes keep theirs."""
        new = new[:, None].astype(buf.dtype)
        if active is not None:
            old = jax.lax.dynamic_slice_in_dim(buf, head, 1, axis=1)
            sel = active.reshape((L, 1) + (1,) * (new.ndim - 2))
            new = jnp.where(sel, new, old)
        return jax.lax.dynamic_update_slice_in_dim(buf, new, head, axis=1)

    return SimState(
        feat=put(state.feat, cur["feat"]),
        addr=put(state.addr, cur["addr"]),
        resid=put(resid, jnp.zeros_like(fetch)),
        exec_lat=put(state.exec_lat, exec_lat),
        store_lat=put(state.store_lat, store_lat),
        valid=put(valid_p, jnp.ones((L,), bool)),
        in_mw=put(in_mw_p, jnp.zeros((L,), bool)),
        is_store_q=put(state.is_store_q, cur["is_store"]),
        cur_tick=cur_tick,
        overflow=overflow,
        # the cursor is global: it advances past frozen lanes too (their
        # plane values are frozen; nothing after a freeze reads recency)
        head=(head + 1) % Q,
    )


def drain_cycles(state: SimState) -> jax.Array:
    """Δ of Eq. 1: cycles until the last in-flight instruction exits."""
    need = jnp.maximum(state.exec_lat, state.store_lat) - state.resid
    need = jnp.where(state.valid, need, 0.0)
    return jnp.max(jnp.maximum(need, 0.0), axis=-1)


def make_sim_scan(
    predict_fn: Optional[Callable],
    cfg: SimConfig,
    *,
    retire_width: Optional[jax.Array] = None,
    lane_ctx: Optional[jax.Array] = None,
    emit_outputs: bool = True,
    predict_state_fn: Optional[Callable] = None,
):
    """Returns scan_fn(state, trace_chunk) -> (state, per-step outputs).

    trace_chunk: dict of (T, L, ...) arrays (feat, addr, is_store, labels),
    plus an optional per-step "active" (T, L) bool lane mask (packed mode).
    predict_fn: (L, 1+Q, 50) -> (L, 3) latencies. None = teacher forcing
    (dataset-builder mode: emits the assembled model inputs instead).
    predict_state_fn: (state, cur_feat, cur_addr) -> (L, 3) latencies —
    the fused-kernel entry: input assembly happens INSIDE the predictor
    (ring layout + `kernels.ops.fused_step`), so the (L, 1+Q, 50) tensor
    never materializes in HBM. Overrides predict_fn when given.
    retire_width / lane_ctx: per-lane SimConfig overrides (see sim_step).
    emit_outputs=False scans with empty per-step outputs — the packed
    multi-workload path uses this so memory stays O(state), not O(T).
    """

    # Named scopes (HLO metadata only, the same instructions): `assembly`,
    # `retire` here and `trunk`, `head` in the predictor, so a profile of
    # the device splits each step's time between them.
    # repro-lint: scan-reachable — runs under lax.scan inside jit
    def step(state, xs):
        cur = {"feat": xs["feat"], "addr": xs["addr"], "is_store": xs["is_store"]}
        if predict_state_fn is not None:
            # the fused kernel assembles its input inside the trunk
            with jax.named_scope("trunk"):
                lats = predict_state_fn(state, cur["feat"], cur["addr"])
            out = {"lats": lats} if emit_outputs else {}
        elif predict_fn is None:
            lats = xs["labels"]
            with jax.named_scope("assembly"):
                out = ({"x": model_input(state, cur["feat"], cur["addr"], cfg)}
                       if emit_outputs else {})
        else:
            with jax.named_scope("assembly"):
                x = model_input(state, cur["feat"], cur["addr"], cfg)
            lats = predict_fn(x)  # sim_step zeroes store latency for non-stores
            out = {"lats": lats} if emit_outputs else {}
        with jax.named_scope("retire"):
            new_state = sim_step(
                state, cur, lats, cfg,
                active=xs.get("active"), retire_width=retire_width, lane_ctx=lane_ctx,
            )
        return new_state, out

    return step


def simulate_trace(trace_arrays: dict, predict_fn, cfg: SimConfig, n_lanes: int):
    """Parallel simulation (paper §3.3): partition into equal sub-traces
    (lanes), simulate independently, total = Σ per-lane (ΣF + Δ).

    trace_arrays: dict of (T, ...) numpy arrays. Returns dict of results.
    """
    T = trace_arrays["feat"].shape[0]
    per = T // n_lanes
    T_used = per * n_lanes

    def lanes_first(a):
        return np.swapaxes(a[:T_used].reshape(n_lanes, per, *a.shape[1:]), 0, 1)

    xs = {k: jnp.asarray(lanes_first(v)) for k, v in trace_arrays.items()}
    state = init_state(n_lanes, cfg)
    step = make_sim_scan(predict_fn, cfg)
    state, outs = jax.lax.scan(step, state, xs)
    total = state.cur_tick + drain_cycles(state)
    return {
        "lane_cycles": total,
        "total_cycles": jnp.sum(total),
        "overflow": jnp.sum(state.overflow),
        "outs": outs,
        "n_instructions": T_used,
    }


# ---------------------------------------------------------------------------
# packed multi-workload simulation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedWorkloads:
    """Lanes from many (workload, SimConfig) jobs packed on one lane axis.

    ``xs`` is numpy, lane-major per chunk: one array per integer trace
    field of `features.FIELDS` (op, register indices and history fields
    int8, pc and address int32, the three labels float32), each
    (n_chunks, L, chunk) or (n_chunks, L, chunk, columns), and active
    (n_chunks, L, chunk) bool, so chunk c of a lane is one contiguous
    block and ``xs[k][c]`` one contiguous put. `chunk_time_major` expands
    a chunk into the time-major (chunk, L, ...) feat/addr/is_store/labels
    the scan steps over. Steps past a lane's own sub-trace length hold each
    field's pad value, which expands to zeros, and are inactive
    (ragged-length masking).
    """

    xs: dict
    workload_id: np.ndarray  # (L,) i32 — lane → job index
    retire_width: np.ndarray  # (L,) i32 per-lane retire bandwidth
    lane_ctx: np.ndarray  # (L,) i32 per-lane in-flight capacity
    lane_steps: np.ndarray  # (L,) i64 real (unpadded) steps per lane
    n_instructions: np.ndarray  # (W,) i64 packed instructions per job
    cfg: SimConfig  # unified config (ctx_len = max over jobs)
    uniform: bool  # True when every job shares retire_width/ctx_len

    @property
    def n_lanes(self) -> int:
        return int(self.workload_id.shape[0])

    @property
    def n_workloads(self) -> int:
        return int(self.n_instructions.shape[0])

    @property
    def n_chunks(self) -> int:
        return int(self.xs["active"].shape[0])

    @property
    def chunk(self) -> int:
        return int(self.xs["active"].shape[2])

    @property
    def n_steps(self) -> int:
        return self.n_chunks * self.chunk


class PackBuffer:
    """Host memory a pack is written into: one flat array per key, grown to
    the largest pack seen and viewed at each pack's shape (a prefix of the
    flat array). A caller that packs again and again keeps one, so the
    pages are faulted in once rather than on every pack; it must not pack
    into it again while a put from the previous pack may still read it.
    ``reuses`` / ``allocations`` count the packs that fitted / grew it."""

    def __init__(self):
        self._flat: Dict[str, np.ndarray] = {}
        self.reuses = 0
        self.allocations = 0

    def counters(self) -> Dict[str, int]:
        return {"reuses": self.reuses, "allocations": self.allocations,
                "bytes": sum(a.nbytes for a in self._flat.values())}

    def views(self, shapes: Dict[str, tuple]) -> Dict[str, np.ndarray]:
        """{key: (shape, dtype)} -> {key: array of that shape}, contents
        left as they were: the caller writes every element."""
        grew = False
        out = {}
        for k, (shape, dtype) in shapes.items():
            n = math.prod(shape)
            flat = self._flat.get(k)
            if flat is None or flat.size < n:
                flat = self._flat[k] = np.empty(n, dtype)
                grew = True
            out[k] = flat[:n].reshape(shape)
        if grew:
            self.allocations += 1
        else:
            self.reuses += 1
        return out


def lane_counts(n_workloads: int, n_lanes: Union[int, Sequence[int]]) -> list:
    """Lanes per job: one count for every job, or one count each."""
    lanes = [n_lanes] * n_workloads if isinstance(n_lanes, int) else list(n_lanes)
    if len(lanes) != n_workloads:
        raise ValueError(f"n_lanes has {len(lanes)} entries for {n_workloads} workloads")
    return lanes


def pack_workloads(
    fields_list: Sequence[dict],
    n_lanes: Union[int, Sequence[int]] = 8,
    cfg: Union[SimConfig, Sequence[SimConfig], None] = None,
    chunk: Optional[int] = None,
    total_lanes: Optional[int] = None,
    buffer: Optional[PackBuffer] = None,
) -> PackedWorkloads:
    """Pack W workloads (each a `features.trace_fields` dict) into one lane
    batch, casting each field to its packed dtype as it is copied.

    n_lanes / cfg may be per-workload sequences; the packed scan runs with
    ctx_len = max over jobs, and per-lane retire_width / lane_ctx replay
    each job's own SimConfig exactly. Job w's lane l is rows
    [l*p, (l+1)*p) of its own fields, copied chunk by chunk as contiguous
    blocks. ``chunk`` cuts the time axis (None: one chunk as long as the
    longest lane); the last chunk is padded with inactive steps.
    ``total_lanes`` pads the lane axis with dead lanes (executable
    bucketing): inactive at every step, they freeze in their all-zero
    initial state (cur_tick 0, no in-flight entries, drain 0, overflow 0)
    and add exactly nothing to any workload's segment_sum. The pack is
    written into ``buffer`` (a fresh one by default), every element of
    it, so nothing of an earlier pack in a reused buffer survives.
    """
    W = len(fields_list)
    if W == 0:
        raise ValueError("pack_workloads needs at least one workload")
    lanes = lane_counts(W, n_lanes)
    if cfg is None:
        cfgs = [SimConfig()] * W
    elif isinstance(cfg, SimConfig):
        cfgs = [cfg] * W
    else:
        cfgs = list(cfg)
    if len(cfgs) != W:
        raise ValueError(f"cfg has {len(cfgs)} entries for {W} workloads")
    # ctx_len and retire_width are replayed per lane; every other SimConfig
    # field is shared scan state and must agree or exactness would silently
    # break (e.g. a per-job max_latency would clip with the wrong bound)
    base = cfgs[0]
    for c in cfgs[1:]:
        if dataclasses.replace(c, ctx_len=base.ctx_len, retire_width=base.retire_width) != base:
            raise ValueError(
                "pack_workloads replays only ctx_len/retire_width per workload; "
                f"other SimConfig fields must match across jobs ({c} vs {base})"
            )

    per = []
    for fields, ln in zip(fields_list, lanes):
        T = F.n_instructions(fields)
        if T < ln:
            raise ValueError(f"workload of {T} instructions cannot fill {ln} lanes")
        per.append(T // ln)
    chunk = chunk or max(per)
    n_chunks = -(-max(per) // chunk)
    n_live = sum(lanes)
    L = n_live if total_lanes is None else total_lanes
    if L < n_live:
        raise ValueError(f"cannot pack {n_live} lanes into {L}")
    Q = max(c.ctx_len for c in cfgs)
    ucfg = dataclasses.replace(cfgs[0], ctx_len=Q)

    lead = (n_chunks, L, chunk)
    xs = (buffer or PackBuffer()).views({
        **{f.name: (lead + f.shape(1)[1:], f.dtype) for f in F.FIELDS},
        "active": (lead, bool),
    })

    def pad(*at):
        for f in F.FIELDS:
            xs[f.name][at] = f.pad
        xs["active"][at] = False

    # dead lanes: id 0 is safe, their totals are exactly zero
    workload_id = np.zeros(L, np.int32)
    retire_width = np.ones(L, np.int32)
    lane_ctx = np.full(L, Q, np.int32)
    lane_steps = np.zeros(L, np.int64)
    n_instructions = np.zeros(W, np.int64)

    lo = 0
    for w, (fields, ln, c, p) in enumerate(zip(fields_list, lanes, cfgs, per)):
        hi = lo + ln
        used = p * ln
        # each lane's rows of each field: (ln, p, ...) views, no copy; a job
        # is many small copies, so the loop keeps per-copy work low
        rows = [(xs[k], a[:used].reshape((ln, p) + a.shape[1:]))
                for k, a in ((f.name, fields[f.name]) for f in F.FIELDS)]
        for ci in range(n_chunks):
            t0 = ci * chunk
            n = min(chunk, max(p - t0, 0))  # the lanes' live steps in it
            for dst, v in rows:
                dst[ci, lo:hi, :n] = v if n == p else v[:, t0 : t0 + n]
            xs["active"][ci, lo:hi, :n] = True
            if n < chunk:  # the ragged tail
                pad(ci, slice(lo, hi), slice(n, None))
        workload_id[lo:hi] = w
        retire_width[lo:hi] = c.retire_width
        lane_ctx[lo:hi] = c.ctx_len
        lane_steps[lo:hi] = p
        n_instructions[w] = used
        lo = hi
    pad(slice(None), slice(lo, None))

    uniform = all(
        c.retire_width == cfgs[0].retire_width and c.ctx_len == Q for c in cfgs
    )
    return PackedWorkloads(
        xs=xs, workload_id=workload_id, retire_width=retire_width,
        lane_ctx=lane_ctx, lane_steps=lane_steps,
        n_instructions=n_instructions, cfg=ucfg, uniform=uniform,
    )


def chunk_time_major(xs: dict) -> dict:
    """One chunk of a pack, (L, chunk, ...) per field, expanded by
    `features.expand` into the time-major (chunk, L, ...) feat, addr,
    is_store, labels and active that `lax.scan` steps over. Each lane is
    widened on its own: under a lane mesh each device widens and swaps its
    own lane slice, with no communication."""
    with jax.named_scope("layout"):
        t = {k: jnp.swapaxes(v, 0, 1) for k, v in xs.items()}
        return {**F.expand(t, jnp), "active": t["active"]}


def max_packed_steps(
    fields_list: Sequence[dict], n_lanes: Union[int, Sequence[int]]
) -> int:
    """Longest per-lane sub-trace over a prospective pack (= the packed time
    axis before chunk rounding). The session uses this to shrink the
    streaming chunk for small packs so padding stays negligible."""
    lanes = lane_counts(len(fields_list), n_lanes)
    return max(F.n_instructions(f) // ln for f, ln in zip(fields_list, lanes))


def workload_totals(state: SimState, packed: PackedWorkloads):
    """Per-workload (cycles, overflow) via segment_sum over the lane axis."""
    lane_total = state.cur_tick + drain_cycles(state)
    wid = jnp.asarray(packed.workload_id)
    W = packed.n_workloads
    cycles = jax.ops.segment_sum(lane_total, wid, num_segments=W)
    overflow = jax.ops.segment_sum(state.overflow, wid, num_segments=W)
    return lane_total, cycles, overflow


def simulate_many(
    fields_list: Sequence[dict],
    predict_fn: Optional[Callable],
    cfg: Union[SimConfig, Sequence[SimConfig], None] = None,
    n_lanes: Union[int, Sequence[int]] = 8,
) -> dict:
    """Batched multi-workload simulation: one scan over all packed lanes.

    ``fields_list`` holds `features.trace_fields` dicts. Teacher-forced
    (predict_fn=None) per-workload totals are bit-identical to W separate
    `simulate_trace` calls on their `features.trace_arrays`, each with the
    job's own SimConfig.
    """
    packed = pack_workloads(fields_list, n_lanes, cfg)
    rw = None if packed.uniform else jnp.asarray(packed.retire_width)
    lc = None if packed.uniform else jnp.asarray(packed.lane_ctx)
    step = make_sim_scan(
        predict_fn, packed.cfg, retire_width=rw, lane_ctx=lc, emit_outputs=False
    )
    xs = chunk_time_major({k: jnp.asarray(v[0]) for k, v in packed.xs.items()})
    state = init_state(packed.n_lanes, packed.cfg)
    state, _ = jax.lax.scan(step, state, xs)
    lane_total, cycles, overflow = workload_totals(state, packed)
    return {
        "lane_cycles": lane_total,
        "workload_cycles": cycles,
        "workload_overflow": overflow,
        "total_cycles": jnp.sum(cycles),
        "n_instructions": packed.n_instructions,
        "workload_id": packed.workload_id,
        "n_lanes": packed.n_lanes,
        "n_steps": packed.n_steps,
    }
