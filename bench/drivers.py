"""The two ways a mix drives the system under test, through the entry points
users call: closed-loop calls of `SimNet.simulate_many` (mode "sweep") and
open-loop jobs submitted to a running `SimServe` (mode "serve").

Each driver warms up every shape its window will use, then runs the window
and returns what it saw: the work completed, the host time, the program's
own batch reports and counters, and what each sampled workload returned.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import traffic


@dataclasses.dataclass
class Window:
    t_open: float  # perf_counter when the window opened
    t_close: float  # perf_counter when the last work of the window returned
    instructions: int  # instructions of every workload completed in it
    attempted: int  # workloads (sweep) or jobs (serve) due in it
    failed: int
    slices: List[traffic.Slice]  # every workload due, in order
    cycles: List[float]  # what the program returned for each (nan: none)
    batches: list  # the program's BatchReports of the window's dispatches
    latencies_ms: List[float] = dataclasses.field(default_factory=list)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    host: Dict[str, List[float]] = dataclasses.field(default_factory=dict)


class Spans:
    """Host spans around the harness's calls into the program: durations
    kept per name, and in a traced run a `TraceAnnotation` on the
    profiler's own timeline, so idle gaps on the device can be put down to
    what the host was doing."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.seconds: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


def make_trace(pool, s: traffic.Slice):
    from repro.des.trace import Trace

    t = pool[s.bench]
    return Trace(name=f"{t['name']}[{s.lo}:{s.lo + s.n}]",
                 **{k: v[s.lo:s.lo + s.n] for k, v in t.items() if k != "name"})


def _new_batches(service, before: int) -> list:
    n = service.stats()["batches"] - before
    return list(service.batches[-n:]) if n > 0 else []


# ------------------------------------------------------------------ sweep

class Sweep:
    """Closed loop, one caller: call after call of `simulate_many`, each a
    fresh seeded draw of the mix's slices, until the window closes."""

    def __init__(self, run, session):
        self.run, self.sn = run, session
        self.sub = int(run.cell.sizes["subtrace_instructions"])
        self.pool_len = len(run.pool[0]["pc"])

    def _call(self, seed_stream: int, k: int, spans: Spans):
        mix, pool = self.run.cell.mix, self.run.pool
        slices = traffic.sweep_call(mix, self.sub, self.pool_len, len(pool),
                                    self.run.seed, k, stream=seed_stream)
        traces = [make_trace(pool, s) for s in slices]
        with spans("simulate_many"):
            res = self.sn.simulate_many(traces, n_lanes=[s.lanes for s in slices])
        return slices, res

    def warm_up(self):
        self._call(traffic.WARM, 0, Spans(False))

    def window(self, seconds: float, spans: Spans) -> Window:
        svc = self.sn.service
        b0 = svc.stats()["batches"]
        slices, cycles, instr = [], [], 0
        t_open = time.perf_counter()
        k = 0
        while True:
            s, res = self._call(traffic.CALLS, k, spans)
            t = time.perf_counter()
            slices += s
            cycles += [w.total_cycles for w in res.workloads]
            instr += res.total_instructions
            k += 1
            if t - t_open >= seconds:
                break
        return Window(t_open=t_open, t_close=t, instructions=instr,
                      attempted=len(slices), failed=0, slices=slices,
                      cycles=cycles, batches=_new_batches(svc, b0))


# ------------------------------------------------------------------ serve

class Serve:
    """Open loop: jobs submitted to a running `SimServe` at the mix's
    fixed rate, whether or not earlier ones have finished. A job's latency
    runs from the time it was due to the time its result was seen on the
    client; one that fails or never comes counts as missing."""

    POLL_S = 0.002
    GRACE_S = 60.0  # how long after the window a due job is waited for

    def __init__(self, run, service, model_id: str):
        self.run, self.svc, self.model = run, service, model_id
        self.sub = int(run.cell.sizes["subtrace_instructions"])
        self.pool_len = len(run.pool[0]["pc"])
        self.chunk = int(run.cell.mix["service"]["chunk"])

    def _submit(self, s: traffic.Slice):
        return self.svc.submit(make_trace(self.run.pool, s), self.model,
                               n_lanes=s.lanes, chunk=self.chunk)

    def warm_up(self):
        """One batch in each lane bucket the window can reach, from the
        smallest job's bucket up to the service's `max_batch_lanes`,
        drained on this thread before the service's loop starts."""
        mix, pool = self.run.cell.mix, self.run.pool
        lanes = 1 << (int(mix["lanes_min"]) - 1).bit_length()
        while lanes <= int(mix["service"]["max_batch_lanes"]):
            for s in traffic.warm_jobs(mix, self.sub, self.pool_len, len(pool),
                                       self.run.seed, lanes):
                self._submit(s)
            self.svc.drain()
            lanes *= 2

    def window(self, seconds: float, spans: Spans) -> Window:
        mix, pool = self.run.cell.mix, self.run.pool
        jobs = traffic.serve_schedule(mix, self.sub, self.pool_len, len(pool),
                                      self.run.seed, seconds)
        st0 = self.svc.stats()
        handles: List[Optional[object]] = [None] * len(jobs)
        done_at = [np.nan] * len(jobs)
        late = []
        submitted = threading.Event()
        lock = threading.Lock()

        def collect():
            pending = []
            nxt = 0
            while True:
                with lock:
                    while nxt < len(jobs) and handles[nxt] is not None:
                        pending.append(nxt)
                        nxt += 1
                if not pending:
                    if submitted.is_set() and nxt >= len(jobs):
                        return
                    time.sleep(self.POLL_S)
                    continue
                handles[pending[0]].wait(self.POLL_S)
                now = time.perf_counter()
                still = []
                for i in pending:
                    if handles[i].done():
                        done_at[i] = now
                    else:
                        still.append(i)
                pending = still
                if now > deadline:
                    return

        deadline = float("inf")
        collector = threading.Thread(target=collect, name="bench-collect", daemon=True)
        self.svc.start()
        t_open = time.perf_counter()
        collector.start()
        for i, job in enumerate(jobs):
            due = t_open + job.due_s
            wait = due - time.perf_counter()
            if wait > 0:
                with spans("generator_sleep"):
                    time.sleep(wait)
            late.append(time.perf_counter() - due)
            try:
                with spans("submit"):
                    h = self._submit(job.slice)
            except Exception as e:  # refused at admission: a missed job
                print(f"serve: job {i} refused: {e!r}", flush=True)
                h = _Refused()
            with lock:
                handles[i] = h
        deadline = t_open + seconds + self.GRACE_S
        submitted.set()
        with spans("wait_results"):
            collector.join(max(0.0, deadline - time.perf_counter()) + 1.0)
        t_close = max([t for t in done_at if t == t] or [time.perf_counter()])
        cycles, lat, instr, failed = [], [], 0, 0
        for job, h, t in zip(jobs, handles, done_at):
            try:
                if t != t:
                    raise TimeoutError("never came")
                cycles.append(h.result(timeout=0).total_cycles)
                lat.append((t - (t_open + job.due_s)) * 1e3)
                instr += job.slice.n
            except Exception:  # failed, refused or never came: a miss
                cycles.append(np.nan)
                lat.append(float("inf"))
                failed += 1
        self.svc.stop(drain=False)
        st1 = self.svc.stats()
        return Window(
            t_open=t_open, t_close=t_close, instructions=instr,
            attempted=len(jobs), failed=failed, slices=[j.slice for j in jobs],
            cycles=cycles, batches=_new_batches(self.svc, st0["batches"]),
            latencies_ms=lat,
            counters={k: st1[k] - st0[k] for k in
                      ("lanes_live", "lanes_dispatched", "dead_lane_steps", "batches",
                       "jobs_completed", "loop_errors")},
            host={"late_s": late},
        )


class _Refused:
    """Stands in for the handle of a job the service refused."""

    def done(self):
        return True

    def wait(self, timeout=None):
        return True

    def result(self, timeout=None):
        raise RuntimeError("refused at submit")
