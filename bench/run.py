#!/usr/bin/env python3
"""The chip benchmark of SimNet: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload c3.sweep --seed 7 --seconds 20 --trace 0

The cell names a configuration, a traffic mix and the chips it needs, each
resolved by name (see `bench/manifest.py`). A run:

1. finds the chips (a TPU with as many devices as the cell asks for, or it
   exits non-zero with no result: it never falls back to the CPU);
2. sets up: the DES trace pool (built on a checkout's first run), the
   predictor's weights made on the device from the seed, the program's
   session or service, and a warm-up of every shape the window uses, with
   JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. measures for ``--seconds`` through the entry points users call; under
   ``--trace 1`` the profiler records a window of at most
   ``TRACED_WINDOW_S`` (its per-layer metrics are shares and means, and a
   longer trace would not be written and read within a run's time limit);
4. reads the device's peak memory, frees the program's state and compares a
   seeded sample of what the window returned with the plain reference
   (`bench/check.py`), printing each number beside its limit;
5. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
   per-layer metrics), ``device`` and, last, ``check``.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "artifacts" / "bench_trace"
TRACED_WINDOW_S = 20.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        btime = next(int(line.split()[1]) for line in Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return T_IMPORT


def find_chips(n: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        log(f"needs a TPU, but JAX found {len(devs)} {d.platform} device(s)")
        raise SystemExit(3)
    if len(devs) < n:
        log(f"the cell needs {n} chips, JAX found {len(devs)}")
        raise SystemExit(3)
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


class CompileCounter:
    """Executables built (compiled or loaded from the persistent cache)
    and persistent-cache loads, counted from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.builds = self.loads = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.builds += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.builds, self.loads


@dataclasses.dataclass
class Run:
    cell: object
    seed: int
    seconds: float
    traced: bool
    pool: list
    device: dict


def program_configs(cell):
    from repro.core.predictor import PredictorConfig
    from repro.core.simulator import SimConfig

    p = dict(cell.sizes["predictor"])
    p["channels"] = tuple(p["channels"])
    return PredictorConfig(**p), SimConfig(**cell.sizes["sim"])


def make_weights(cell, seed: int):
    """The predictor's weights on the device, from the seed, in one
    jitted call, in the dtype they are served in (float32)."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)
    params = jax.jit(lambda k: cell.model.init(k, cell.sizes["predictor"]))(key)
    jax.block_until_ready(params)
    return params


def build_system(run: Run, params):
    """The system under test, through the entry points users call."""
    from bench import drivers

    pcfg, scfg = program_configs(run.cell)
    mix = run.cell.mix
    if mix["mode"] == "sweep":
        from repro.core.api import SimNet

        mesh = None
        if run.cell.chips > 1:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh()
        sn = SimNet(params=params, pcfg=pcfg, sim_cfg=scfg, mesh=mesh,
                    chunk=int(mix["chunk"]), use_kernel=bool(run.cell.sizes["use_kernel"]))
        return drivers.Sweep(run, sn), sn.close
    from repro.core.api import SimServe

    svc = SimServe(use_kernel=bool(run.cell.sizes["use_kernel"]), **mix["service"])
    svc.register("model", params=params, pcfg=pcfg, sim_cfg=scfg)
    return drivers.Serve(run, svc, "model"), lambda: svc.stop(drain=False)


def peak_memory(n: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def sample_indices(run: Run, window) -> list:
    """A seeded sample of the window's completed workloads, the longest
    among them, as many as fill the cell's `sample_lanes`."""
    from bench import check

    block = int(run.cell.limits["sample_lanes"])
    n = len(window.slices)
    longest = max(range(n), key=lambda i: window.slices[i].lanes)
    idx, lanes = [], 0
    for i in check.sample(n, n, run.seed, must=[longest]):
        if window.cycles[i] != window.cycles[i]:
            continue  # a miss: counted under `failed`, nothing to compare
        if lanes + window.slices[i].lanes <= block:
            idx.append(i)
            lanes += window.slices[i].lanes
    return idx


def reference_cycles(run: Run, slices, dot):
    """The plain reference's cycles of `slices`, on the first chip, with
    weights made anew from the seed (nothing the program made is used)."""
    import jax

    from bench import reference

    cell = run.cell
    params = make_weights(cell, run.seed)
    p = cell.sizes["predictor"]
    simulate = reference.make_simulate(
        lambda prm, x, d: cell.model.forward(prm, x, d, p), params,
        cell.sizes["sim"], cell.model.seq_padded(p), dot)
    with jax.default_device(jax.devices()[0]):
        return reference.workload_cycles(simulate, run.pool, slices,
                                         int(cell.limits["sample_lanes"]))


def reference_check(run: Run, window) -> tuple:
    """Compare a seeded sample of the window's workloads with the plain
    reference at the configuration's stated precision: (numbers, workloads
    compared)."""
    from bench import check, reference

    idx = sample_indices(run, window)
    if not idx:
        return {"max_gap": math.inf, "pack_gap": math.inf}, 0
    dot = reference.DOTS[run.cell.sizes["matmul_operands"]]
    ref = reference_cycles(run, [window.slices[i] for i in idx], dot)
    return check.gaps([window.cycles[i] for i in idx], ref), len(idx)


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import manifest

    cell = manifest.resolve(args.workload)
    device = find_chips(cell.chips)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = CompileCounter()
    seconds = min(args.seconds, TRACED_WINDOW_S) if args.trace else args.seconds
    log(f"{cell.name}: {device['count']} x {device['kind']}; seed {args.seed}; "
        f"{seconds:g} s window; trace {args.trace}")

    from bench import drivers, pool as pool_mod

    pool_spec = cell.mix["pool"]
    pool, sha = pool_mod.load_pool(pool_spec["benchmarks"], int(pool_spec["instructions"]), log=log)
    log(f"pool sha256 {sha} ({len(pool)} traces x {len(pool[0]['pc'])} instructions)")

    run = Run(cell=cell, seed=args.seed, seconds=seconds, traced=bool(args.trace),
              pool=pool, device=device)
    params = make_weights(cell, args.seed)
    system, close = build_system(run, params)
    system.warm_up()
    builds0, loads0 = counter.snapshot()
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f} s; {builds0} executables built ({loads0} from the "
        "persistent cache)")

    # from here on, any executable built (compiled or loaded from the
    # persistent cache) is logged by name: the window should build none
    jax.config.update("jax_log_compiles", True)
    spans = drivers.Spans(run.traced)
    trace_dir = None
    if run.traced:
        trace_dir = TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        # device ops and the host's TraceMe spans; no Python tracer, which
        # would record every Python call of the host path and slow it
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with spans("window"):
            window = system.window(seconds, spans)
    finally:
        if run.traced:
            t0 = time.time()
            jax.profiler.stop_trace()
            log(f"profiler stopped in {time.time() - t0:.1f} s")
    jax.config.update("jax_log_compiles", False)
    builds1, loads1 = counter.snapshot()
    log(f"window {window.t_close - window.t_open:.3f} s: {window.instructions} "
        f"instructions, {window.attempted} attempted, {window.failed} failed; "
        f"compiles in the window: {builds1 - builds0 - (loads1 - loads0)} "
        f"(+{loads1 - loads0} persistent-cache loads)")
    shapes = sorted({(b.n_lanes, b.n_jobs) for b in window.batches})
    log(f"{len(window.batches)} batches; (lanes, jobs) shapes: {shapes}")
    if window.host.get("late_s"):
        late = sorted(window.host["late_s"])
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} jobs")
    device["memory_peak_bytes"] = peak_memory(cell.chips)

    from bench import report

    if run.traced:
        t0 = time.time()
        metrics, extra = report.per_layer(run, window, spans, trace_dir)
        log(f"trace read in {time.time() - t0:.1f} s")
        device.update(extra.pop("device"))
    else:
        metrics, extra = report.end_to_end(run, window, setup_s), {}

    close()
    del system, close, params
    gc.collect()
    from bench import check

    numbers, n_compared = reference_check(run, window)
    numbers["failed"] = float(window.failed)
    correct, compared = check.verdict(numbers, cell.limits["limits"])
    log(f"compared {n_compared} workloads with the reference")
    for name, v in compared.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    line = {"correct": bool(correct), "attempted": window.attempted,
            "failed": window.failed, "metrics": metrics, "device": device}
    line.update(extra)
    line["check"] = {k: {"value": v["value"] if math.isfinite(v["value"]) else None,
                         "limit": v["limit"]} for k, v in compared.items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
