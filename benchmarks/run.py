"""Benchmark harness: one section per paper table/figure, reading the
artifacts produced by benchmarks/pipeline.py and the dry-run sweep.
(The pipeline trains each model once into a PredictorArtifact directory
under artifacts/simnet/models/ and evaluates through the SimNet session
API — `python -m repro simulate --artifact artifacts/simnet/models/c3_hybrid`
reuses the same predictors interactively.)

  PYTHONPATH=src python -m benchmarks.run            # print all tables
  PYTHONPATH=src python -m benchmarks.run --csv      # plus name,us_per_call,derived CSV

Sections:
  table4     ML model zoo: prediction error / simulation error / MFlops
  fig5_6     per-benchmark CPIs + phase-level accuracy
  fig7       parallel-simulation error vs sub-trace size
  contention multicore co-run traces: solo vs contention-augmented training
  chaos      seeded fault injection, integrity guards, self-healing
  table5     design-space relative accuracy (branch predictors, L2 size)
  a64fx      second processor configuration (paper §4.1)
  roofline   dry-run roofline summary and the compiled HLO's collective
             count (full tables: python -m benchmarks.roofline)

Speed is measured on the chip by `bench/run.py`; its records are
PERF.md and PERF_LEDGER.jsonl.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

ART = Path("artifacts/simnet")
CSV_ROWS = []


def _load(name):
    p = ART / name
    if not p.exists():
        return None
    return json.loads(p.read_text())


def _sec(title):
    print("\n" + "=" * 72)
    print(title)
    print("=" * 72)


def table4():
    data = _load("table4.json")
    _sec("Table 4 — ML model accuracy & computation intensity")
    if data is None:
        print("(artifacts missing — run `python -m benchmarks.pipeline`)")
        return
    f = lambda x: f"{100*x:6.1f}%" if x is not None else "     —"
    print(f"{'model':16s} {'MFlops':>8s} {'fetch':>7s} {'exec':>7s} {'store':>7s} {'train avg':>9s} {'sim avg':>8s} {'all avg':>8s}")
    for mid, row in data.items():
        pe = row["pred_errors"] or {"fetch": None, "execution": None, "store": None}
        print(
            f"{mid:16s} {row['mflops']:8.2f} {f(pe['fetch'])} {f(pe['execution'])} "
            f"{f(pe['store'])}  {f(row.get('train_avg'))}  {f(row.get('sim_avg'))} {f(row.get('all_avg'))}"
        )
        CSV_ROWS.append((f"table4/{mid}", row["mflops"], row.get("all_avg")))


def fig5_6():
    data = _load("fig56_cpi.json")
    _sec("Figures 5 & 6 — per-benchmark CPI and phase-level accuracy")
    if data is None:
        print("(artifacts missing)")
        return
    print(f"{'benchmark':22s} {'DES CPI':>8s} {'C3 CPI':>8s} {'C3 err':>7s} {'RB7 CPI':>8s} {'RB7 err':>8s}")
    for bench, models in sorted(data["benchmarks"].items()):
        c3 = models.get("c3_hybrid", {})
        rb7 = models.get("rb7_hybrid", {})
        print(
            f"{bench:22s} {c3.get('des_cpi', 0):8.3f} {c3.get('cpi', 0):8.3f} "
            f"{100*c3.get('err', 0):6.1f}% {rb7.get('cpi', 0):8.3f} {100*rb7.get('err', 0):7.1f}%"
        )
    for mid, curves in data["phase_curves"].items():
        sim = np.asarray(curves["simnet"])
        des = np.asarray(curves["des"])
        n = min(len(sim), len(des))
        corr = float(np.corrcoef(sim[:n], des[:n])[0, 1])
        print(f"phase-curve corr({mid} vs DES) over {n} windows: {corr:.3f}")
        CSV_ROWS.append((f"fig6/phase_corr_{mid}", 0.0, corr))


def fig7():
    data = _load("fig7_subtrace.json")
    _sec("Figure 7 — parallel simulation error vs sub-trace size")
    if data is None:
        print("(artifacts missing)")
        return
    for p in data["points"]:
        print(f"  lanes {p['lanes']:4d} (sub-trace {p['subtrace_len']:7d} instrs): CPI error {100*p['cpi_error']:6.2f}%")
        CSV_ROWS.append((f"fig7/lanes{p['lanes']}", 0.0, p["cpi_error"]))


def _loadd(name):
    p = Path("artifacts/dryrun") / name
    return json.loads(p.read_text()) if p.exists() else None


def contention():
    data = _load("contention_chaos.json")
    _sec("Contention — multicore DES co-run traces: solo vs augmented training")
    ct = (data or {}).get("contention")
    if ct is None:
        print("(artifacts missing — run `python -m benchmarks.pipeline`)")
        return
    rep = ct["report_stream_chase"]
    print(f"  mixes: {', '.join(ct['mixes'])} "
          f"(train seed {ct['train_seed']}, held-out eval seed {ct['eval_seed']})")
    print(f"  DES mix_stream_chase ({rep['n_cores']} cores, shared L2, "
          f"bus {rep['mc']['bus_cycles_per_fill']} cyc/fill, "
          f"{rep['mc']['mshrs']} MSHRs):")
    for i, core in enumerate(rep["cores"]):
        print(f"    core {i} ({core['name']}): solo CPI "
              f"{core['solo_cpi']:.3f} -> co-run {core['corun_cpi']:.3f} "
              f"({core['slowdown']:.2f}x), shared-L2 hit rate "
              f"{core['l2_hit_rate_corun']:.3f} (solo {core['l2_hit_rate_solo']:.3f})")
        CSV_ROWS.append((f"contention/slowdown_{core['name']}", 0.0,
                         core["slowdown"]))
    print(f"  bus occupancy {rep['bus']['occupancy']:.3f}, "
          f"queue {rep['bus']['queue_cycles']} cyc, "
          f"MSHR wait {rep['bus']['mshr_wait_cycles']} cyc")
    print("  CPI error on held-out co-run traces (one simulate_many pack):")
    for mid, row in ct["models"].items():
        print(f"    {mid:16s} avg {100*row['avg_err']:6.2f}%  "
              f"(worst {100*max(row['per_trace'].values()):6.2f}%)")
        CSV_ROWS.append((f"contention/{mid}_avg_err", 0.0, row["avg_err"]))
    pk = ct["pack"]
    print(f"  heterogeneous pack: {pk['n_workloads']} co-run workloads, "
          f"lanes {pk['n_lanes']}, retire widths {pk['retire_widths']} "
          f"in ONE simulate_many — totals "
          f"{'bit-identical' if pk['totals_match'] else 'MISMATCH'} "
          f"vs per-trace simulation")
    CSV_ROWS.append(("contention/pack_totals_match", 0.0,
                     float(pk["totals_match"])))


def chaos():
    data = _load("contention_chaos.json")
    _sec("Chaos — seeded fault injection, integrity guards, self-healing")
    ch = (data or {}).get("chaos")
    if ch is None:
        print("(artifacts missing — run `python -m benchmarks.pipeline` "
              "or `repro chaos --quick` directly)")
        return
    for lane in ("single", "fleet"):
        d = ch.get(lane)
        if not d:
            continue
        failed = sorted(k for k, v in d["checks"].items() if not v)
        print(f"  {lane}: {'OK' if d['ok'] else 'FAILED ' + str(failed)} — "
              f"{d['n_jobs']} jobs, {d['resubmits']} resubmits, "
              f"{d['wall_seconds']:.1f}s (seed {d['seed']})")
        CSV_ROWS.append((f"chaos/{lane}_ok", 0.0, float(d["ok"])))
        CSV_ROWS.append((f"chaos/{lane}_resubmits", 0.0,
                         float(d["resubmits"])))
    fl = ch.get("fleet")
    if fl:
        sup = fl.get("supervisor", {})
        print(f"  fleet supervisor: {sup.get('chaos_kills', 0)} injected "
              f"crash(es), {sup.get('restarts_total', 0)} supervised "
              f"restart(s), {fl['router'].get('readmissions', 0)} "
              f"readmission(s); healthz {fl['healthz'].get('status')}")
        CSV_ROWS.append(("chaos/fleet_restarts", 0.0,
                         float(sup.get("restarts_total", 0))))


def table5():
    data = _load("table5_usecases.json")
    _sec("Table 5 / §5 — design-space exploration relative accuracy")
    if data is None:
        print("(artifacts missing)")
        return
    bp = data["branch_predictor"]
    base = "bimodal"
    print("branch predictors (speedup vs bimodal baseline):")
    for alt in [k for k in bp if k != base]:
        des_sp, sim_sp, errs = [], [], []
        for bench in bp[base]["des"]:
            d = bp[base]["des"][bench] / bp[alt]["des"][bench]
            s = bp[base]["simnet"][bench] / bp[alt]["simnet"][bench]
            des_sp.append(d)
            sim_sp.append(s)
            errs.append(s / d - 1.0)
        print(f"  {alt:8s}: DES {100*(np.mean(des_sp)-1):+6.2f}%  SimNet {100*(np.mean(sim_sp)-1):+6.2f}%  "
              f"relative error range [{100*min(errs):+.2f}%, {100*max(errs):+.2f}%]")
        CSV_ROWS.append((f"table5/bpred_{alt}", 0.0, float(np.mean(errs))))
    l2 = data["l2_size"]
    sizes = sorted(l2, key=int)
    base_sz = sizes[0]
    print("L2 size scaling (speedup vs smallest):")
    for sz in sizes[1:]:
        des_sp, sim_sp, errs = [], [], []
        for bench in l2[base_sz]["des"]:
            d = l2[base_sz]["des"][bench] / l2[sz]["des"][bench]
            s = l2[base_sz]["simnet"][bench] / l2[sz]["simnet"][bench]
            des_sp.append(d)
            sim_sp.append(s)
            errs.append(abs(s / d - 1.0))
        print(f"  {int(sz)//1024:5d}kB: DES {100*(np.mean(des_sp)-1):+6.2f}%  SimNet {100*(np.mean(sim_sp)-1):+6.2f}%  "
              f"avg |rel err| {100*np.mean(errs):.2f}%")
        CSV_ROWS.append((f"table5/l2_{sz}", 0.0, float(np.mean(errs))))


def a64fx():
    data = _load("a64fx.json")
    _sec("§4.1 — second processor configuration (A64FX-like)")
    if data is None:
        print("(artifacts missing)")
        return
    print(f"  prediction errors: {data['pred_errors']}")
    for k, v in data["sim_errors"].items():
        print(f"  {k:20s} CPI error {100*v:6.2f}%")
    print(f"  average: {100*data['sim_avg']:.2f}%")
    CSV_ROWS.append(("a64fx/sim_avg", 0.0, data["sim_avg"]))


def roofline_summary():
    _sec("Roofline (dry-run) — summary; full tables: python -m benchmarks.roofline")
    try:
        from benchmarks.roofline import summary

        print(summary("pod"))
    except Exception as e:
        print(f"(unavailable: {e})")
    sim_mp = _loadd("simnet-c3__simulate_64k__multipod.json")
    if sim_mp:
        print(f"  dry-run simnet-c3 on 512 devices: {sim_mp['collectives']['total_count']:.0f} "
              "collective ops in the compiled HLO (paper §3.3: no inter-device communication)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", action="store_true")
    args = ap.parse_args()
    table4()
    fig5_6()
    fig7()
    contention()
    chaos()
    table5()
    a64fx()
    roofline_summary()
    if args.csv:
        print("\nname,us_per_call,derived")
        for name, us, derived in CSV_ROWS:
            print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
