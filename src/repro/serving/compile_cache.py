"""Process-wide cache of compiled SimNet chunk executables.

The paper's throughput story is amortization: ONE compiled predictor
executable serves massive lane batches (§3.3). Before this cache, every
`SimNetEngine` held its own `jax.jit` wrapper with the params *closed
over* — so every model in a zoo sweep recompiled an identical program,
and two requests with slightly different lane counts could never share.

Two mechanisms fix that:

1. **Params are an argument, not a closure.** Executables are keyed by
   `ExecutableKey` — (PredictorConfig, SimConfig, lane bucket, chunk,
   mesh, kernel flag) — never by the weights. Every model of the same
   kind/ctx reuses one executable; teacher-forced runs key on
   ``predictor=None``. The step layout (``SimConfig.layout``: ring vs
   roll) rides in the SimConfig, so the two layouts' compiled programs
   never collide in the cache.
2. **Bucketing.** Lane counts round up to power-of-two buckets (dead
   lanes ride along fully masked via the ``active`` input, so totals are
   bit-identical — see `pack_workloads`' ``total_lanes``), and the streaming chunk
   rounds to a power of two capped at the configured maximum. A
   heterogeneous request mix therefore lands on a handful of executable
   shapes instead of one per (model × lane count × trace length).

Entries are AOT-compiled (`jit → lower → compile`) at miss time, so
``stats()`` reports true compile seconds separated from run time:
hits / misses / compile_seconds / per-key breakdown.

Compiles run OUTSIDE the global lock, coordinated by per-key in-flight
futures: two batches needing *different* shapes compile in parallel (and
hit-path lookups for resident keys never block behind a multi-second AOT
compile), while two needing the *same* shape still compile exactly once —
the second caller waits on the first's future. A ``builder()`` that
raises is never counted as a compile and never poisons the key: its
waiters see the error, and the next ``get`` retries the build.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core.predictor import PredictorConfig
from repro.core.simulator import SimConfig

# <checkout>/.jax_cache, from this file's place in <checkout>/src/repro/serving/
DEFAULT_PERSISTENT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent (on-disk) compilation cache for this
    process and return its directory, so a cold process reuses what an
    earlier one compiled. ``JAX_COMPILATION_CACHE_DIR`` wins when set;
    otherwise the cache lives at a fixed path in the checkout — fixed
    because the directory is part of what a later process must find.

    Entry points call this before their first compile; it is never run
    at import, so library users and the tests stay cache-free."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_PERSISTENT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def lane_bucket(n_lanes: int) -> int:
    """Round a lane count up to the next power of two (min 1)."""
    if n_lanes < 1:
        raise ValueError(f"need at least one lane, got {n_lanes}")
    return 1 << (n_lanes - 1).bit_length()


def chunk_bucket(n_steps: int, max_chunk: int) -> int:
    """Streaming chunk for a pack of ``n_steps``: the next power of two,
    capped at ``max_chunk``. Short packs pay a little padding (inactive
    masked steps) in exchange for executable reuse across trace lengths."""
    if n_steps < 1 or max_chunk < 1:
        raise ValueError(f"need positive steps/chunk, got {n_steps}/{max_chunk}")
    return min(1 << (n_steps - 1).bit_length(), max_chunk)


def mesh_fingerprint(mesh) -> Optional[Tuple]:
    """Hashable identity of a mesh (axis names × shape × device ids)."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


@dataclasses.dataclass(frozen=True)
class ExecutableKey:
    """Everything a chunk executable's compiled program depends on.

    Weights are deliberately absent: params are a runtime argument, so any
    model with the same architecture hits the same entry. ``predictor`` is
    None for teacher-forced replay.
    """

    predictor: Optional[PredictorConfig]
    sim_cfg: SimConfig
    n_lanes: int  # bucketed lane count
    chunk: int  # bucketed streaming chunk
    mesh: Optional[Tuple] = None  # mesh_fingerprint(...)
    use_kernel: bool = False

    def describe(self) -> str:
        kind = self.predictor.kind if self.predictor is not None else "teacher-forced"
        return (f"{kind}/ctx{self.sim_cfg.ctx_len}/{self.sim_cfg.layout}"
                f"/L{self.n_lanes}/T{self.chunk}")


class CompileCache:
    """Thread-safe map ExecutableKey → compiled chunk executable.

    ``get(key, builder)`` returns the cached executable or invokes
    ``builder()`` (which must return a ready-to-call compiled function),
    timing it as compile cost. One instance (`global_cache()`) is shared
    process-wide; tests and benchmarks may construct private ones to
    measure cold-cache behaviour.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[ExecutableKey, Callable] = {}  # guarded-by: _lock
        self._inflight: Dict[ExecutableKey, concurrent.futures.Future] = {}  # guarded-by: _lock
        self._generation = 0  # guarded-by: _lock — bumped by clear(); stale builds don't land
        self._hits = 0  # guarded-by: _lock
        self._misses = 0  # guarded-by: _lock
        self._compile_seconds = 0.0  # guarded-by: _lock
        self._per_key: Dict[ExecutableKey, Dict[str, Any]] = {}  # guarded-by: _lock

    def get(self, key: ExecutableKey, builder: Callable[[], Callable]) -> Callable:
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._hits += 1
                self._per_key[key]["hits"] += 1
                return exe
            fut = self._inflight.get(key)
            owner = fut is None
            if owner:
                # we build; concurrent same-key callers wait on the future
                # (one compile per key) while other keys — and hit-path
                # lookups — proceed: the lock is never held across a build
                fut = concurrent.futures.Future()
                self._inflight[key] = fut
                gen = self._generation
        if not owner:
            exe = fut.result()  # the owner's compile is our reuse
            with self._lock:
                self._hits += 1
                if key in self._per_key:
                    self._per_key[key]["hits"] += 1
            return exe
        t0 = time.time()
        try:
            # Chaos seam: the "compile" fault site meters real build
            # attempts only (hits and future-waiters above never arrive
            # here), so an injected failure exercises exactly the
            # failed-build path: waiters see it, the key stays clean, and
            # the next get() retries.
            from repro.serving import faults

            faults.fire("compile")
            exe = builder()
        except BaseException as e:
            # a failed build must not count as a compile or wedge the key:
            # waiters see the error, the next get() retries the build
            with self._lock:
                self._inflight.pop(key, None)
            fut.set_exception(e)
            raise
        dt = time.time() - t0
        with self._lock:
            if self._generation == gen:
                self._entries[key] = exe
                self._misses += 1
                self._compile_seconds += dt
                self._per_key[key] = {"hits": 0, "compile_seconds": dt}
            # else: clear() ran mid-build — hand the executable to our
            # waiters but keep it (and its counters) out of the wiped cache
            self._inflight.pop(key, None)
        fut.set_result(exe)
        return exe

    def clear(self) -> None:
        with self._lock:
            self._generation += 1  # builds in flight must not repopulate us
            self._entries.clear()
            self._per_key.clear()
            self._hits = self._misses = 0
            self._compile_seconds = 0.0

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "hits": self._hits,
                "misses": self._misses,
                "n_executables": len(self._entries),
                "compile_seconds": self._compile_seconds,
                "executables": {
                    getattr(k, "describe", lambda k=k: repr(k))(): dict(v)
                    for k, v in self._per_key.items()
                },
            }

    def counters(self) -> Dict[str, float]:
        """Lightweight hits/misses/compile-seconds snapshot (no per-key
        breakdown — cheap enough to take around every dispatch)."""
        with self._lock:
            return {
                "hits": self._hits,
                "misses": self._misses,
                "compile_seconds": self._compile_seconds,
            }

    def delta_since(self, before: Dict[str, Any]) -> Dict[str, Any]:
        """Hits/misses/compile-seconds accumulated since a counters()/stats()
        snapshot."""
        now = self.counters()
        return {k: now[k] - before[k] for k in now}


_GLOBAL_CACHE = CompileCache()


def global_cache() -> CompileCache:
    """The process-wide executable cache every engine uses by default."""
    return _GLOBAL_CACHE
