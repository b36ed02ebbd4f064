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
  fig8_9_10  simulation throughput, device scaling + training amortization
  throughput batched multi-workload engine: packed vs sequential instr/s
  contention multicore co-run traces: solo vs contention-augmented training
  table5     design-space relative accuracy (branch predictors, L2 size)
  a64fx      second processor configuration (paper §4.1)
  roofline   dry-run roofline summary (full tables: python -m benchmarks.roofline)
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


def fig8_9_10():
    data = _load("fig89_throughput.json")
    _sec("Figures 8–10 — simulation throughput & scaling")
    if data is None:
        print("(artifacts missing)")
        return
    print(f"  reference DES: {data['des_ips']:.0f} instr/s ({data['hardware']})")
    for p in data["points"]:
        speedup = p["ips"] / data["des_ips"]
        print(f"  SimNet lanes {p['lanes']:4d}: {p['ips']:9.0f} instr/s  ({speedup:5.1f}x DES)")
        CSV_ROWS.append((f"fig8/lanes{p['lanes']}", 1e6 / p["ips"], speedup))
    sim_mp = _loadd("simnet-c3__simulate_64k__multipod.json")
    if sim_mp:
        print(f"  dry-run simnet-c3 on 512 devices: {sim_mp['collectives']['total_count']:.0f} "
              "collective ops in the compiled HLO (paper §3.3: no inter-device communication)")


def throughput():
    data = _load("packed_throughput.json")
    _sec("Batched multi-workload engine — packed vs sequential throughput")
    if data is None or "packed" not in data:
        print("(artifacts missing — run `python -m benchmarks.pipeline`)")
        return
    seq, packed = data["sequential"], data["packed"]
    print(f"  workloads: {data['n_workloads']} × {data['lanes_per_workload']} lanes each")
    print(f"  sequential (one jitted call per workload): {seq['ips']:12.0f} instr/s "
          f"({seq['n_instructions']} instrs, {seq['wall_seconds']:.2f}s wall: W compiles + W runs)")
    print(f"  packed     (all workloads in one scan):    {packed['ips']:12.0f} instr/s "
          f"({packed['n_instructions']} instrs, {packed['wall_seconds']:.2f}s wall: 1 compile + 1 run)")
    print(f"  whole-sweep wall-clock speedup: {data['speedup_wall']:.2f}x "
          f"(steady-state, compiled vs compiled: {data['speedup_steady']:.2f}x)")
    CSV_ROWS.append(("throughput/sequential", 1e6 / seq["ips"], None))
    CSV_ROWS.append(("throughput/packed", 1e6 / packed["ips"], data["speedup_wall"]))
    for side in ("sequential", "packed"):
        c = data[side].get("cache")
        if c:
            print(f"  {side} compile cache: {c['misses']} compiles "
                  f"({c['compile_seconds']:.2f}s), {c['hits']} hits")
            CSV_ROWS.append((f"throughput/{side}_compile_s", 0.0, c["compile_seconds"]))
    zoo = data.get("serve_zoo")
    if zoo:
        c = zoo["cache"]
        print(f"  SimServe zoo sweep: {zoo['n_jobs']} jobs over "
              f"{len(zoo['models'])} resident models × {zoo['n_workloads']} workloads "
              f"in {zoo['wall_seconds']:.1f}s ({zoo['batches']} shared batches)")
        print(f"    compile cache: {c['misses']} misses / {c['hits']} hits, "
              f"{c['compile_seconds']:.2f}s total compile "
              f"(executable reuse — wave 2 pays zero compiles)")
        for i, wave in enumerate(zoo.get("waves", [])):
            fc = wave["per_model_first_call_seconds"]
            rng = (f", per-model first_call {min(fc.values()):.2f}–"
                   f"{max(fc.values()):.2f}s" if fc else " (no resident models)")
            print(f"    wave {i}: {wave['wall_seconds']:6.2f}s wall{rng}")
        CSV_ROWS.append(("serve_zoo/cache_hits", 0.0, c["hits"]))
        CSV_ROWS.append(("serve_zoo/cache_misses", 0.0, c["misses"]))
        CSV_ROWS.append(("serve_zoo/compile_seconds", 0.0, c["compile_seconds"]))
    sa = data.get("serve_async")
    if sa:
        seq_s, asy = sa["sequential"], sa["async"]
        print(f"  SimServe async drain loop: {sa['n_jobs']} jobs from "
              f"{sa['n_clients']} client threads over {len(sa['models'])} models")
        print(f"    sequential one-batch-per-job: {seq_s['batches']} batches "
              f"in {seq_s['wall_seconds']:.1f}s")
        print(f"    background loop:              {asy['batches']} batches "
              f"({asy['jobs_per_batch']:.1f} jobs/batch) in "
              f"{asy['wall_seconds']:.1f}s — totals "
              f"{'bit-identical' if sa['totals_match'] else 'MISMATCH'}")
        CSV_ROWS.append(("serve_async/seq_wall_s", 0.0, seq_s["wall_seconds"]))
        CSV_ROWS.append(("serve_async/async_wall_s", 0.0, asy["wall_seconds"]))
        CSV_ROWS.append(("serve_async/jobs_per_batch", 0.0, asy["jobs_per_batch"]))
        CSV_ROWS.append(("serve_async/totals_match", 0.0, float(sa["totals_match"])))
    sh = data.get("serve_http")
    if sh:
        print(f"  SimServe over HTTP: {sh['n_jobs']} jobs from "
              f"{sh['n_clients']} wire clients over {len(sh['models'])} models")
        print(f"    {sh['batches']} batches ({sh['jobs_per_batch']:.1f} "
              f"jobs/batch) in {sh['wall_seconds']:.1f}s — p99 service "
              f"{sh['service_ms_p99']:.0f} ms, p99 queue wait "
              f"{sh['queue_wait_ms_p99']:.0f} ms, totals "
              f"{'bit-identical' if sh['totals_match'] else 'MISMATCH'}")
        CSV_ROWS.append(("serve_http/wall_s", 0.0, sh["wall_seconds"]))
        CSV_ROWS.append(("serve_http/jobs_per_batch", 0.0, sh["jobs_per_batch"]))
        CSV_ROWS.append(("serve_http/service_ms_p99", 0.0, sh["service_ms_p99"]))
        CSV_ROWS.append(("serve_http/totals_match", 0.0, float(sh["totals_match"])))
    sf = data.get("serve_fleet")
    if sf:
        print(f"  SimServe fleet: {sf['n_jobs']} jobs through the router "
              f"over replica subprocesses ({len(sf['models'])} models)")
        for lane in ("replicas_1", "replicas_2"):
            r = sf.get(lane)
            if not r:
                continue
            print(f"    {lane:14s} {r['wall_seconds']:6.1f}s wall "
                  f"(startup + cold per-replica compiles), "
                  f"{r['jobs_per_batch']:.1f} jobs/batch, totals "
                  f"{'bit-identical' if r['totals_match'] else 'MISMATCH'}")
            CSV_ROWS.append((f"serve_fleet/{lane}_wall_s", 0.0,
                             r["wall_seconds"]))
            CSV_ROWS.append((f"serve_fleet/{lane}_totals_match", 0.0,
                             float(r["totals_match"])))
        fo = sf.get("failover")
        if fo:
            print(f"    failover drill: {fo['completed']}/{sf['n_jobs']} done "
                  f"after killing a replica mid-run — {fo['resubmits']} "
                  f"resubmit(s), {fo['ejections']} ejection(s), totals "
                  f"{'bit-identical' if fo['totals_match'] else 'MISMATCH'}")
            CSV_ROWS.append(("serve_fleet/failover_completed", 0.0,
                             fo["completed"]))
            CSV_ROWS.append(("serve_fleet/failover_totals_match", 0.0,
                             float(fo["totals_match"])))
    lay = data.get("step_layout")
    if lay:
        print(f"  step layouts (ring vs roll state traffic, ctx_len "
              f"{lay['ctx_len']}, {lay['n_workloads']}×{lay['lanes_per_workload']} lanes):")
        for mode in ("teacher_forced", "predictor_c3"):
            for row in lay.get(mode, []):
                tag = f"{mode}/{row['layout']}-{row['state_dtype']}"
                print(f"    {tag:34s} {row['ips']:10.0f} instr/s "
                      f"({row['seconds']:6.2f}s steady, "
                      f"{row['speedup_vs_roll']:.2f}x roll)")
                CSV_ROWS.append((f"step_layout/{tag}", 1e6 / row["ips"],
                                 row["speedup_vs_roll"]))
        tm = lay.get("traffic_model")
        if tm:
            print(f"    roofline traffic model: roll {tm['roll_bytes_per_step']/1e6:.2f} "
                  f"MB/step vs ring {tm['ring_bytes_per_step']/1e6:.2f} MB/step "
                  f"→ {tm['ratio']:.1f}x less queue-state HBM traffic")


def contention():
    data = _load("packed_throughput.json")
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
    data = _load("packed_throughput.json")
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", action="store_true")
    args = ap.parse_args()
    table4()
    fig5_6()
    fig7()
    fig8_9_10()
    throughput()
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
