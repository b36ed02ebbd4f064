#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``); the
benchmark's own runs never run this.

    python3 bench/calibrate.py --workload c3.sweep --seeds 1 2 3 --seconds 0

For each seed, in one process: the timed path at the cell's own size and
load (a sweep cell's window of ``--seconds 0`` is exactly one call; a
serve cell needs a few seconds), the same seeded sample as a run compares,
then the plain reference at the configuration's stated precision and the
control: the reference put in the program's place one precision lower
(`reference.BELOW`). Prints, per seed, the program's numbers and the
control's (and, as context, the stated reference against float32
products), each side's verdict at the cell's limits (`check.verdict`, as
a run decides `correct`), and at the end the largest and smallest of
each, as JSON lines.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    from bench import check, drivers, manifest, pool as pool_mod, reference

    cell = manifest.resolve(args.workload)
    device = bench_run.find_chips(cell.chips)
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = cell.mix["pool"]
    pool, sha = pool_mod.load_pool(spec["benchmarks"], int(spec["instructions"]),
                                   log=bench_run.log)
    rows = []
    for seed in args.seeds:
        t0 = time.time()
        run = bench_run.Run(cell=cell, seed=seed, seconds=args.seconds, traced=False,
                            pool=pool, device=device)
        system, close = bench_run.build_system(run, bench_run.make_weights(cell, seed))
        system.warm_up()
        window = system.window(args.seconds, drivers.Spans(False))
        close()
        del system, close
        idx = bench_run.sample_indices(run, window)
        slices = [window.slices[i] for i in idx]
        stated = cell.sizes["matmul_operands"]
        ref = bench_run.reference_cycles(run, slices, reference.DOTS[stated])
        ctl = bench_run.reference_cycles(run, slices,
                                         reference.DOTS[reference.BELOW[stated]])
        f32 = bench_run.reference_cycles(run, slices, reference.dot_f32)
        row = {"seed": seed, "compared": len(idx), "failed": window.failed,
               "program": check.gaps([window.cycles[i] for i in idx], ref),
               "control": check.gaps(ctl, ref), "stated_vs_f32": check.gaps(ref, f32),
               "seconds": time.time() - t0}
        # each side through the verdict a run gives, at the cell's limits
        for side, failed in (("program", window.failed), ("control", 0)):
            row[f"{side}_correct"] = check.verdict(
                dict(row[side], failed=float(failed)), cell.limits["limits"])[0]
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": cell.name, "device": device, "pool_sha256": sha,
               "program_correct": all(r["program_correct"] for r in rows),
               "control_correct_on_any_seed": any(r["control_correct"] for r in rows)}
    for side in ("program", "control"):
        for k in ("max_gap", "pack_gap"):
            vals = [r[side][k] for r in rows]
            summary[f"{side}.{k}"] = {"min": min(vals), "max": max(vals)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
