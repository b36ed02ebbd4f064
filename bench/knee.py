#!/usr/bin/env python3
"""Find the highest rate a serve cell's service sustains (the knee); the
benchmark's own runs never run this. A serve cell then offers load at a
fixed rate written into its mix (``rate_jobs_per_s``), at about 4/5 of the
knee.

    python3 bench/knee.py --workload c3.serve --rates 20 40 80 --seconds 20

For each rate, in one process: the cell's open-loop window at that rate,
then its p50 / p95 and whether the backlog grew: the median latency of the
last quarter of jobs over that of the first quarter (about 1 when the
service keeps up; it climbs with the queue when it does not).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from bench import drivers, manifest, pool as pool_mod, stats

    cell = manifest.resolve(args.workload)
    if cell.mix["mode"] != "serve":
        raise SystemExit(f"{cell.name} is not a serve cell")
    device = bench_run.find_chips(cell.chips)
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = cell.mix["pool"]
    pool, _ = pool_mod.load_pool(spec["benchmarks"], int(spec["instructions"]),
                                 log=bench_run.log)
    params = bench_run.make_weights(cell, args.seed)
    counter = bench_run.CompileCounter()
    for i, rate in enumerate(args.rates):
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_jobs_per_s=rate))
        run = bench_run.Run(cell=c, seed=args.seed, seconds=args.seconds, traced=False,
                            pool=pool, device=device)
        system, close = bench_run.build_system(run, params)
        if i == 0:
            system.warm_up()
        before = counter.snapshot()
        w = system.window(args.seconds, drivers.Spans(False))
        after = counter.snapshot()
        close()
        lat = w.latencies_ms
        q = max(1, len(lat) // 4)
        row = {
            "rate": rate, "jobs": w.attempted, "failed": w.failed,
            "p50_ms": stats.percentile(lat, 50), "p95_ms": stats.percentile(lat, 95),
            "backlog_growth": statistics.median(lat[-q:]) / statistics.median(lat[:q]),
            "instr_per_s": w.instructions / (w.t_close - w.t_open),
            "lane_occupancy": w.counters["lanes_live"] / max(1, w.counters["lanes_dispatched"]),
            "batches": w.counters["batches"],
            "late_ms_max": 1e3 * max(w.host["late_s"]),
            "builds_in_window": after[0] - before[0],
            "batch_shapes": sorted({(b.n_lanes, b.n_jobs) for b in w.batches}),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
