"""Distributed SimNet parallel-simulation engine (paper §3.3, TPU-native).

Lanes (= the paper's sub-traces) are a batch axis sharded over the mesh's
data axes; the predictor weights are replicated (tiny). The whole
simulation — context management, inference, clock — is ONE jitted scan, so
multi-device scaling has the paper's "no inter-device communication"
property: the only collective is the final lane-cycle reduction.

The lane axis is multi-workload: ``simulate_many`` packs lanes from many
workloads × SimConfigs into one sharded scan (per-lane workload ids,
validity masks for ragged trace lengths, per-lane retire width / context
capacity) and streams arbitrarily long traces through chunked jitted calls
with donated state buffers.

Since the SimServe redesign the chunk program is **resident**: executables
come from the process-wide `serving.compile_cache` keyed by architecture
(never weights — params are a call argument), and lane counts round up to
power-of-two buckets with dead lanes masked. Each call packs, puts the
lane configs and the state, then puts and enqueues one chunk at a time
(device memory stays O(chunk)) and waits once for the totals. Two engines
around two models of the same kind share one compiled program; a fresh
engine on a warm cache pays zero compiles.

``input_specs()`` / ``lower()`` make the engine dry-runnable on the
production mesh alongside the LM pool (simnet-c3 / simnet-rb7 arch cells).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import features as F
from repro.core.predictor import (
    PredictorConfig,
    apply_raw,
    decode_latency,
    make_fused_predict_fn,
)
from repro.core.simulator import (
    PackBuffer,
    SimConfig,
    SimState,
    chunk_time_major,
    init_state,
    lane_counts,
    make_sim_scan,
    pack_workloads,
    workload_totals,
)
from repro.serving import faults
from repro.serving.compile_cache import (
    CompileCache,
    ExecutableKey,
    global_cache,
    lane_bucket,
    mesh_fingerprint,
)
from repro.serving.telemetry import span


class NumericError(RuntimeError):
    """Predictor outputs produced non-finite cycle totals (NaN/Inf).

    Raised by the numeric guard in ``simulate_many`` so a poisoned batch
    fails loudly instead of silently corrupting CPI totals downstream."""

    def __init__(self, bad_workloads, cycles):
        self.bad_workloads = [int(i) for i in bad_workloads]
        super().__init__(
            f"non-finite cycle totals for workload(s) {self.bad_workloads}: "
            f"{[float(cycles[i]) for i in self.bad_workloads]}"
        )


def _lane_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def lane_sharding(mesh):
    ax = _lane_axes(mesh)
    return NamedSharding(mesh, P(ax if len(ax) > 1 else ax[0]))


def state_shardings(mesh):
    lanes = lane_sharding(mesh)
    # every plane is lane-sharded except the scalar ring cursor, which is
    # replicated (each device advances it identically — no communication)
    return SimState(**{
        f: NamedSharding(mesh, P()) if f == "head" else lanes
        for f in SimState._fields
    })


def chunk_specs(n_lanes: int, chunk: int):
    """ShapeDtypeStructs for one chunk of packed trace input: each integer
    trace field and the active mask, lane-major as the pack lays them out
    (`run_chunk` expands them and swaps to time-major)."""
    lead = (n_lanes, chunk)
    return {
        **{f.name: jax.ShapeDtypeStruct(lead + f.shape(1)[1:], f.dtype)
           for f in F.FIELDS},
        "active": jax.ShapeDtypeStruct(lead, jnp.bool_),
    }


def lane_param_specs(n_lanes: int):
    """ShapeDtypeStructs for the per-lane SimConfig arrays."""
    return (
        jax.ShapeDtypeStruct((n_lanes,), jnp.int32),  # retire_width
        jax.ShapeDtypeStruct((n_lanes,), jnp.int32),  # lane_ctx
    )


def chunk_shardings(mesh):
    """A chunk's lane axis (axis 0) over the mesh: each device's slice is
    one contiguous block of the host's pack."""
    s = lane_sharding(mesh)
    return {k: s for k in chunk_specs(1, 1)}


class SimNetEngine:
    def __init__(self, params=None, pcfg: Optional[PredictorConfig] = None,
                 sim_cfg: Optional[SimConfig] = None, mesh=None,
                 use_kernel: bool = False, cache: Optional[CompileCache] = None):
        """params=None runs teacher-forced: the scan replays the packed DES
        labels through the identical chunked/donated/sharded path (exactness
        harness + label-replay dry-runs without a predictor).

        ``cache`` overrides the process-wide compile cache (cold-cache
        benchmarks / isolation in tests)."""
        if params is not None and pcfg is None:
            raise ValueError("pcfg is required when params are given")
        self.pcfg = pcfg
        self.sim_cfg = sim_cfg or (
            SimConfig(ctx_len=pcfg.ctx_len) if pcfg is not None else SimConfig()
        )
        self.mesh = mesh
        self.use_kernel = use_kernel
        self.cache = cache if cache is not None else global_cache()
        self.params = params
        self._params_staged = params is None  # nothing to stage teacher-forced
        # host memory every pack is written into, kept from call to call so
        # its pages are faulted in once; the lock keeps a second caller from
        # rewriting it while this call's asynchronous puts may still read it
        self._pack_lock = threading.Lock()
        self._pack_buffer = PackBuffer()  # guarded-by: _pack_lock
        # its reuse/allocation counts and bytes, replaced whole after each
        # pack so that readers need no lock
        self.pack_buffer_counters = self._pack_buffer.counters()

        # repro-lint: scan-reachable — the jitted per-chunk body
        def run_chunk(p, state: SimState, xs, retire_width, lane_ctx):
            predict = predict_state = None
            if self.pcfg is not None:
                if (use_kernel and self.sim_cfg.layout == "ring"
                        and self.pcfg.kind == "c3"
                        and self.sim_cfg.state_dtype == "float32"):
                    # fused sim-step: assembly + conv trunk in one Pallas
                    # kernel off the ring buffer; the model input never
                    # materializes in HBM. f32 state only: the kernel
                    # assembles in f32, while the unfused path rounds the
                    # dynamic features through the state dtype — a bf16
                    # state would diverge from use_kernel=False, so it
                    # falls back to the unfused kernel path below.
                    predict_state = make_fused_predict_fn(p, self.pcfg)
                else:
                    def predict(x):
                        raw = apply_raw(p, x, self.pcfg, use_kernel=self.use_kernel)
                        return decode_latency(raw, self.pcfg)
            step = make_sim_scan(
                predict, self.sim_cfg,
                retire_width=retire_width, lane_ctx=lane_ctx, emit_outputs=False,
                predict_state_fn=predict_state,
            )
            state, _ = jax.lax.scan(step, state, chunk_time_major(xs))
            return state

        if mesh is not None:
            st_sh = state_shardings(mesh)
            xs_sh = chunk_shardings(mesh)
            lane_sh = lane_sharding(mesh)
            p_sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), self.params
            )
            self._run_chunk = jax.jit(
                run_chunk,
                in_shardings=(p_sh, st_sh, xs_sh, lane_sh, lane_sh),
                out_shardings=st_sh,
                donate_argnums=(1,),
            )
        else:
            self._run_chunk = jax.jit(run_chunk, donate_argnums=(1,))

    # -- resident executables ------------------------------------------

    def executable_key(self, n_lanes: int, chunk: int) -> ExecutableKey:
        """Cache identity of the chunk program at a (bucketed) shape.
        Weights are absent on purpose: same-architecture models share."""
        return ExecutableKey(
            predictor=self.pcfg,
            sim_cfg=self.sim_cfg,
            n_lanes=n_lanes,
            chunk=chunk,
            mesh=mesh_fingerprint(self.mesh),
            use_kernel=self.use_kernel,
        )

    def _param_specs(self):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params
        )

    def _stage_params(self):
        """Put the weights on device once (replicated over the mesh when
        present) — lazily, so dry-run lowering stays allocation-free."""
        if not self._params_staged:
            if self.mesh is not None:
                self.params = jax.device_put(
                    self.params, jax.tree_util.tree_map(
                        lambda _: NamedSharding(self.mesh, P()), self.params
                    ),
                )
            else:
                self.params = jax.device_put(self.params)
            self._params_staged = True

    def lower(self, n_lanes: int, chunk: int):
        """Dry-run lowering against ShapeDtypeStructs (no allocation)."""
        state = jax.eval_shape(lambda: init_state(n_lanes, self.sim_cfg))
        rw, lc = lane_param_specs(n_lanes)
        ctx = self.mesh if self.mesh is not None else _nullcontext()
        with ctx:
            return self._run_chunk.lower(
                self._param_specs(), state, chunk_specs(n_lanes, chunk), rw, lc
            )

    def executable(self, n_lanes: int, chunk: int):
        """The AOT-compiled chunk program from the shared cache (compiled
        exactly once per ExecutableKey process-wide)."""
        return self.cache.get(
            self.executable_key(n_lanes, chunk),
            lambda: self.lower(n_lanes, chunk).compile(),
        )

    # -- packed multi-workload path ------------------------------------

    def simulate_many(
        self,
        fields_list: Sequence[Dict[str, np.ndarray]],
        n_lanes: Union[int, Sequence[int]] = 8,
        chunk: int = 1024,
        cfgs: Union[SimConfig, Sequence[SimConfig], None] = None,
    ) -> dict:
        """Simulate many workloads (each a `features.trace_fields` dict) in
        one packed lane batch, streaming the time axis through chunked calls
        to the cache-resident executable with donated state buffers.

        The lane axis is bucketed to the next power of two (dead lanes are
        fully masked and contribute nothing), so nearby lane counts reuse
        one executable. ``seconds`` times the stream and the wait;
        ``first_call_seconds`` also holds the pack and the compile (or
        cache hit).

        The host phases come back as ``pack_seconds``, ``stage_seconds``
        (host-to-device puts and chunk enqueues), ``device_wait_seconds``
        and ``results_seconds``, each also a ``simnet.*`` span on the
        profiler's timeline; ``pack_buffer`` holds the host pack buffer's
        reuses and allocations in this call and the bytes it holds."""
        with self._pack_lock:
            return self._simulate_many(fields_list, n_lanes, chunk, cfgs)

    def _simulate_many(self, fields_list, n_lanes, chunk, cfgs) -> dict:
        t_start = time.perf_counter()
        cache_before = self.cache.counters()
        # host seconds of each phase, one span each on the profiler's timeline
        phases = dict.fromkeys(
            ("pack_seconds", "stage_seconds", "device_wait_seconds", "results_seconds"), 0.0
        )
        with span("simnet.pack", phases, "pack_seconds"):
            n_live = sum(lane_counts(len(fields_list), n_lanes))
            n_bucket = lane_bucket(n_live)
            if self.mesh is not None:  # every device holds an equal lane slice
                per = int(np.prod([self.mesh.shape[a] for a in _lane_axes(self.mesh)]))
                n_bucket = -(-n_bucket // per) * per
            buffer_before = self._pack_buffer.counters()
            packed = pack_workloads(
                fields_list, n_lanes, cfgs if cfgs is not None else self.sim_cfg,
                chunk=chunk, total_lanes=n_bucket, buffer=self._pack_buffer,
            )
            self.pack_buffer_counters = self._pack_buffer.counters()
            if packed.cfg.ctx_len > self.sim_cfg.ctx_len:
                raise ValueError(
                    f"packed ctx_len {packed.cfg.ctx_len} exceeds engine ctx_len "
                    f"{self.sim_cfg.ctx_len} (the predictor input width is fixed)"
                )
        with span("simnet.executable"):
            self._stage_params()
            exe = self.executable(packed.n_lanes, chunk)

        mesh = self.mesh
        xs_sh = chunk_shardings(mesh) if mesh is not None else {}
        lane_sh = lane_sharding(mesh) if mesh is not None else None

        def put(x, sh):
            return jnp.asarray(x) if sh is None else jax.device_put(x, sh)

        # the puts and the chunk enqueues: the host returns before the
        # device has run them
        with span("simnet.stage", phases, "stage_seconds"):
            rw = put(np.asarray(packed.retire_width), lane_sh)
            lc = put(np.asarray(packed.lane_ctx), lane_sh)
            t0 = time.perf_counter()  # `seconds` starts at the state's put
            state = init_state(packed.n_lanes, self.sim_cfg)
            if mesh is not None:
                state = jax.device_put(state, state_shardings(mesh))
            # one chunk on the device at a time: device memory stays O(chunk)
            for c in range(packed.n_chunks):
                xs = {k: put(v[c], xs_sh.get(k)) for k, v in packed.xs.items()}
                state = exe(self.params, state, xs, rw, lc)
        with span("simnet.device_wait", phases, "device_wait_seconds"):
            lane_total, cycles, overflow = workload_totals(state, packed)
            jax.block_until_ready(cycles)
        t_end = time.perf_counter()
        with span("simnet.results", phases, "results_seconds"):
            cycles = np.asarray(cycles, np.float64)
            # Numeric guard: a NaN/Inf anywhere in the predictor's latency
            # stream propagates into these per-workload sums — catch it here,
            # at the batch boundary, before it can poison aggregated CPI.
            # (The chaos "batch.numeric" corrupt trigger poisons the totals
            # directly, flushing this exact path.)
            cycles = faults.fire("batch.numeric", payload=cycles)
            finite = np.isfinite(cycles)
            if not finite.all():
                raise NumericError(np.flatnonzero(~finite), cycles)
            overflow = np.asarray(overflow)
        n_instr = packed.n_instructions
        total_instr = int(n_instr.sum())
        dt = t_end - t0
        return {
            "workload_cycles": cycles,
            "workload_cpi": cycles / np.maximum(n_instr, 1),
            "workload_overflow": overflow,
            "n_instructions": n_instr,
            "total_cycles": float(cycles.sum()),
            "total_instructions": total_instr,
            "n_lanes": packed.n_lanes,
            "n_live_lanes": n_live,
            "n_steps": packed.n_steps,  # padded scan length actually run
            "n_workloads": packed.n_workloads,
            "throughput_ips": total_instr / dt,
            "seconds": dt,
            "first_call_seconds": t_end - t_start,  # pack + compile/cache hit + run
            "cache": self.cache.delta_since(cache_before),
            "pack_buffer": {
                "reuses": self.pack_buffer_counters["reuses"] - buffer_before["reuses"],
                "allocations": (self.pack_buffer_counters["allocations"]
                                - buffer_before["allocations"]),
                "bytes": self.pack_buffer_counters["bytes"],
            },
            **phases,
        }


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
