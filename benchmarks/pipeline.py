"""End-to-end experiment pipeline producing the artifacts every benchmark
table reads (resumable: steps skip if their artifact exists).

  python -m benchmarks.pipeline           # full run (background-friendly)
  python -m benchmarks.pipeline --quick   # tiny settings (CI smoke)

Built on the session API: every model is trained once (`SimNet.train`),
saved as a `PredictorArtifact` directory under models/, and every
evaluation reloads the artifact and routes through the engine pack path
(`SimNet.simulate_many` / `SimNet.sweep`) — the same flow as
`python -m repro train/simulate/sweep`.

Artifacts (artifacts/simnet/):
  models/<kind>/           PredictorArtifact dirs (params + configs + metadata)
  table4.json              model zoo: prediction err, sim err, MFlops (Table 4)
  fig56_cpi.json           per-benchmark CPIs + phase curves (Figs. 5, 6)
  fig7_subtrace.json       parallel-lane error vs sub-trace size (Fig. 7)
  contention_chaos.json    multicore contention section (solo-trained vs
                           contention-augmented on held-out co-run traces) +
                           seeded chaos drill section
  table5_usecases.json     design-space relative accuracy (Table 5 / §5)
  a64fx.json               second-processor-config accuracy (§4.1)
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.checkpoint import PredictorArtifact
from repro.core import api
from repro.core.api import SimNet
from repro.core.predictor import PredictorConfig, inference_mflops
from repro.core.simulator import SimConfig
from repro.des.o3 import A64FX_CONFIG, O3Config, O3Simulator
from repro.des.workloads import ML_BENCHMARKS, SIM_BENCHMARKS, get_benchmark

ART = Path("artifacts/simnet")
TRACE_DIR = "artifacts/traces"

ZOO = [
    # kind, output, epochs (sized for the 1-core CPU container; the paper
    # trains 200 epochs on a DGX — accuracy here is a lower bound)
    ("fc2", "hybrid", 8),
    ("fc3", "hybrid", 8),
    ("c1", "hybrid", 8),
    ("c3", "reg", 8),
    ("c3", "hybrid", 14),
    ("rb7", "hybrid", 2),
    ("lstm2", "hybrid", 2),
    ("tx6", "hybrid", 1),
    ("ithemal_lstm2", "hybrid", 2),
]

SLOW_KINDS = {"lstm2", "tx6"}  # sequence models: evaluate on a subset


def model_id(kind, output):
    return f"{kind}_{output}"


def _save_json(name, obj):
    ART.mkdir(parents=True, exist_ok=True)
    (ART / name).write_text(json.dumps(obj, indent=2, default=float))
    print(f"[pipeline] wrote {ART/name}", flush=True)


def _exists(name):
    return (ART / name).exists()


def get_traces(quick):
    n_ml = 12000 if quick else 100000
    n_sim = 6000 if quick else 30000
    ml = api.generate_traces(sorted(ML_BENCHMARKS), n_ml, cache_dir=TRACE_DIR)
    sim = api.generate_traces(sorted(SIM_BENCHMARKS), n_sim, cache_dir=TRACE_DIR)
    # "training benchmarks under simulation settings": fresh segment lengths
    ml_eval = api.generate_traces(sorted(ML_BENCHMARKS), n_sim, cache_dir=TRACE_DIR)
    return ml, ml_eval, sim


def train_zoo(data, quick, skip_missing=False):
    """Train every zoo model once and save it as a PredictorArtifact dir;
    later steps reload the artifacts (train-once / simulate-everywhere)."""
    (ART / "models").mkdir(parents=True, exist_ok=True)
    for kind, output, epochs in ZOO:
        mid = model_id(kind, output)
        path = ART / "models" / mid
        if PredictorArtifact.exists(path):
            continue
        if skip_missing:
            continue
        pcfg = PredictorConfig(kind=kind, ctx_len=64, output=output)
        if kind == "ithemal_lstm2":
            from repro.core.dataset import ithemal_samples

            # fixed-window inputs (no context management) — paper's baseline
            Xs, Ys = [], []
            for tr in data["ml_traces"][:2]:
                X, Y = ithemal_samples(tr.slice(0, len(tr.pc) // 2), window=64)
                Xs.append(X)
                Ys.append(Y)
            X, Y = np.concatenate(Xs), np.concatenate(Ys)
            n_val = max(len(X) // 20, 1)
            dset = {
                "train_x": X[: -2 * n_val], "train_y": Y[: -2 * n_val],
                "val_x": X[-2 * n_val : -n_val], "val_y": Y[-2 * n_val : -n_val],
                "test_x": X[-n_val:], "test_y": Y[-n_val:],
            }
        else:
            dset = data["dataset"]
        ep = max(1, epochs // 4) if quick else epochs
        sn = SimNet.train(dset, pcfg, SimConfig(ctx_len=64),
                          epochs=ep, batch_size=1024, log_every=1)
        sn.save(path)
        tr_res = sn.train_result
        print(f"[pipeline] trained {mid} in {tr_res.seconds:.0f}s: "
              f"{tr_res.pred_errors}", flush=True)


def load_session(mid) -> SimNet:
    """Reload a zoo model's artifact as a simulation session."""
    return SimNet.from_artifact(ART / "models" / mid)


def step_table4(data, quick):
    if _exists("table4.json"):
        return
    out = {}
    eval_traces = data["ml_eval"] + data["sim_traces"]
    names_ml = [t.name for t in data["ml_eval"]]
    for kind, output, _ in ZOO:
        mid = model_id(kind, output)
        try:
            sn = load_session(mid)
        except (FileNotFoundError, ValueError):
            print(f"[pipeline] table4: {mid} not trained yet — skipped", flush=True)
            continue
        train_meta = sn.artifact.metadata.get("train", {})
        row = {
            "mflops": inference_mflops(sn.pcfg),
            "pred_errors": train_meta.get("pred_errors"),
            "train_seconds": train_meta.get("seconds"),
            "sim_errors": {},
        }
        if kind == "ithemal_lstm2":
            # window inputs aren't produced by the queue simulator; evaluate
            # prediction error only (sim comparison in DESIGN.md §1 terms)
            out[mid] = row
            continue
        traces_for_model = eval_traces[:4] if kind in SLOW_KINDS else eval_traces
        # one packed call per model instead of len(traces) sequential ones
        res = sn.simulate_many(traces_for_model, n_lanes=8)
        row["sim_errors"] = {w.name: float(w.cpi_error) for w in res}
        errs = row["sim_errors"]
        ml_errs = [v for k, v in errs.items() if any(k.startswith(n.split("[")[0]) for n in names_ml)]
        sim_errs = [v for k, v in errs.items() if k.startswith("sim_")]
        row["train_avg"] = float(np.mean(ml_errs)) if ml_errs else None
        row["sim_avg"] = float(np.mean(sim_errs)) if sim_errs else None
        row["all_avg"] = float(np.mean(list(errs.values())))
        out[mid] = row
        print(f"[pipeline] table4 {mid}: all_avg={row['all_avg']:.3f}", flush=True)
    _save_json("table4.json", out)


def step_fig56(data, quick):
    if _exists("fig56_cpi.json"):
        return
    out = {"benchmarks": {}, "phase_curves": {}}
    eval_traces = data["ml_eval"] + data["sim_traces"]
    for mid in ["c3_hybrid", "rb7_hybrid"]:
        sn = load_session(mid)
        # all evaluation benchmarks packed into ONE scan (batched engine)
        many = sn.simulate_many(eval_traces, n_lanes=8)
        for w in many:
            out["benchmarks"].setdefault(w.name, {})[mid] = {
                "cpi": w.cpi, "des_cpi": w.des_cpi, "err": w.cpi_error,
            }
        # phase curves on the phased benchmark
        tr = [t for t in data["sim_traces"] if "phased" in t.name][0]
        sim_cpi, des_cpi = api.phase_cpis(tr, sn.params, sn.pcfg,
                                          n_lanes=4, window=1000)
        out["phase_curves"][mid] = {"simnet": sim_cpi.tolist(), "des": des_cpi.tolist()}
    _save_json("fig56_cpi.json", out)


def step_fig7(data, quick):
    if _exists("fig7_subtrace.json"):
        return
    sn = load_session("c3_hybrid")
    tr = data["ml_eval"][0]
    lanes_sweep = [1, 2, 4, 8, 16, 32] if not quick else [1, 4, 16]
    out = {"trace": tr.name, "n_instructions": int(tr.n), "points": []}
    # pack the sweep, but group lane counts with similar per-lane lengths:
    # the packed time axis is max(T//lanes) over the group, so letting the
    # 1-lane job share a scan with the 32-lane job would run 32 mostly-
    # inactive lanes for T steps (≈10x wasted inference)
    groups, cur = [], []
    for lanes in lanes_sweep:
        if cur and (tr.n // cur[0]) > 2 * (tr.n // lanes):
            groups.append(cur)
            cur = []
        cur.append(lanes)
    groups.append(cur)
    for g in groups:
        many = sn.simulate_many([tr] * len(g), n_lanes=g)
        for lanes, w in zip(g, many):
            out["points"].append({
                "lanes": lanes, "subtrace_len": int(tr.n // lanes),
                "cpi_error": w.cpi_error,
            })
            print(f"[pipeline] fig7 lanes={lanes}: err={w.cpi_error:.4f}", flush=True)
    _save_json("fig7_subtrace.json", out)


def step_table5(data, quick):
    if _exists("table5_usecases.json"):
        return
    sn = load_session("c3_hybrid")
    n = 6000 if quick else 20000
    bench_names = ["mlb_branchy", "sim_branchy_hard", "sim_loop", "sim_chase_small"]
    out = {"branch_predictor": {}, "l2_size": {}}

    # --- branch predictor study: baseline bimodal vs bimode vs tage ---
    # the whole study is ONE SimNet.sweep call: every (design point ×
    # benchmark) cell packs into one engine dispatch
    jobs = []
    for bp in ["bimodal", "bimode", "tage"]:
        sim = O3Simulator(O3Config(bpred=bp))
        for name in bench_names:
            jobs.append((bp, sim.run(get_benchmark(name, n))))
    swept = sn.sweep(jobs, n_lanes=8)
    for bp in swept.points:
        out["branch_predictor"][bp] = {
            "des": {w.name: w.des_cycles for w in swept.point(bp)},
            "simnet": {w.name: w.total_cycles for w in swept.point(bp)},
        }
        print(f"[pipeline] table5 bpred={bp} done", flush=True)

    # --- L2 size exploration ---
    # needs a workload whose working set straddles the swept sizes AND
    # enough accesses to build reuse: sim_chase_mid cycles 2MB (256KB
    # thrashes, 1MB partially holds it, 4MB fits), sim_chase (16MB)
    # covers the thrash-everything regime. sim_chase_small (256KB) fit in
    # the smallest L2, so every size produced identical DES cycles.
    n_l2 = 30000 if quick else 150000
    l2_names = ["sim_chase_mid", "sim_chase"]
    jobs = []
    for l2 in [256 * 1024, 1024 * 1024, 4 * 1024 * 1024]:
        sim = O3Simulator(O3Config(caches=dict(l2_size=l2)))
        for name in l2_names:
            jobs.append((str(l2), sim.run(get_benchmark(name, n_l2))))
    swept = sn.sweep(jobs, n_lanes=8)
    for l2 in swept.points:
        out["l2_size"][l2] = {
            "des": {w.name: w.des_cycles for w in swept.point(l2)},
            "simnet": {w.name: w.total_cycles for w in swept.point(l2)},
        }
        print(f"[pipeline] table5 l2={l2} done", flush=True)
    _save_json("table5_usecases.json", out)


def step_contention(data, quick):
    """Shared-resource contention (multicore DES): does SimNet track co-run
    latencies? Trains nothing new for the solo baseline — the zoo's c3_hybrid
    (solo traces only) is evaluated on held-out co-run traces, against a
    contention-augmented twin trained on solo + co-run traces. Also packs
    every co-run trace (mixed lengths, mixed retire widths, mixed lane
    counts) through ONE teacher-forced `simulate_many` and checks totals are
    bit-identical to per-trace simulation (heterogeneous-lane correctness).
    Merges a `contention` section into contention_chaos.json."""
    from repro.des.multicore import contention_report
    from repro.des.workloads import MULTICORE_MIXES, get_mix

    path = ART / "contention_chaos.json"
    prior = json.loads(path.read_text()) if path.exists() else {}
    if "contention" in prior:
        return
    n_tr = 4000 if quick else 20000   # base instr/core (mix multipliers apply)
    n_ev = 3000 if quick else 12000
    mixes = list(MULTICORE_MIXES)
    corun_train, corun_eval = [], []
    for m in mixes:  # seed-disjoint: seed 0 trains, seed 7 is held out
        corun_train += api.generate_corun_traces(m, n_tr, seed=0, cache_dir=TRACE_DIR)
        corun_eval += api.generate_corun_traces(m, n_ev, seed=7, cache_dir=TRACE_DIR)
    print(f"[pipeline] contention: {len(corun_train)} co-run train traces, "
          f"{len(corun_eval)} held-out", flush=True)

    scfg = SimConfig(ctx_len=64)
    pcfg = PredictorConfig(kind="c3", ctx_len=64, output="hybrid")

    def trained(path, traces, epochs):
        if PredictorArtifact.exists(path):
            return SimNet.from_artifact(path)
        dset = api.build_training_data(traces, scfg, n_lanes=8)
        sn = SimNet.train(dset, pcfg, scfg, epochs=epochs, batch_size=1024)
        sn.save(path)
        return sn

    solo_path = ART / "models" / "c3_hybrid"  # zoo artifact (solo-only data)
    ep = 3 if quick else 14
    sn_solo = (SimNet.from_artifact(solo_path) if PredictorArtifact.exists(solo_path)
               else trained(ART / "models" / "c3_hybrid_solo",
                            data["ml_traces"], ep))
    sn_ct = trained(ART / "models" / "c3_hybrid_ct",
                    list(data["ml_traces"]) + corun_train, ep)

    def evaluate(sn):
        res = sn.simulate_many(corun_eval, n_lanes=4)
        per = {t.name: float(w.cpi_error) for t, w in zip(corun_eval, res)}
        return {"per_trace": per, "avg_err": float(np.mean(list(per.values())))}

    models = {"c3_solo": evaluate(sn_solo), "c3_contention": evaluate(sn_ct)}
    if not quick:  # sequence model pair on the cheapest mix only (slow)
        tx_eval = corun_eval[:2]  # mix_chase_sym pair (mixes are sorted)
        tx_pcfg = PredictorConfig(kind="tx6", ctx_len=64, output="hybrid")

        def tx_trained(path, traces):
            if PredictorArtifact.exists(path):
                return SimNet.from_artifact(path)
            dset = api.build_training_data(traces, scfg, n_lanes=8)
            sn = SimNet.train(dset, tx_pcfg, scfg, epochs=1, batch_size=1024)
            sn.save(path)
            return sn

        tx_solo_path = ART / "models" / "tx6_hybrid"
        sn_tx = (SimNet.from_artifact(tx_solo_path)
                 if PredictorArtifact.exists(tx_solo_path)
                 else tx_trained(ART / "models" / "tx6_hybrid_solo",
                                 data["ml_traces"]))
        sn_tx_ct = tx_trained(ART / "models" / "tx6_hybrid_ct",
                              list(data["ml_traces"]) + corun_train)
        for name, sn in (("tx6_solo", sn_tx), ("tx6_contention", sn_tx_ct)):
            res = sn.simulate_many(tx_eval, n_lanes=4)
            per = {t.name: float(w.cpi_error) for t, w in zip(tx_eval, res)}
            models[name] = {"per_trace": per,
                            "avg_err": float(np.mean(list(per.values())))}

    # heterogeneous-lane pack: every co-run trace, mixed lanes AND retire
    # widths, one teacher-forced simulate_many vs per-trace references
    lanes = [2 + (i % 3) for i in range(len(corun_eval))]
    widths = [(8, 4, 2)[i % 3] for i in range(len(corun_eval))]
    cfgs = [SimConfig(ctx_len=64, retire_width=w) for w in widths]
    packed = SimNet().simulate_many(corun_eval, n_lanes=lanes, sim_cfgs=cfgs)
    refs = [SimNet(sim_cfg=c).simulate(t, n_lanes=l)
            for t, l, c in zip(corun_eval, lanes, cfgs)]
    totals_match = all(int(w.total_cycles) == int(r.total_cycles)
                       for w, r in zip(packed, refs))

    # one mix's solo-vs-co-run DES story rides along for the table
    _, report = contention_report(get_mix("mix_stream_chase", n_ev, seed=7),
                                  mix="mix_stream_chase")
    prior["contention"] = {
        "mixes": mixes,
        "n_base_train": n_tr, "n_base_eval": n_ev,
        "train_seed": 0, "eval_seed": 7,
        "models": models,
        "pack": {"n_workloads": len(corun_eval), "n_lanes": lanes,
                 "retire_widths": widths, "totals_match": totals_match},
        "report_stream_chase": report.to_dict(),
    }
    print(f"[pipeline] contention: c3 solo {models['c3_solo']['avg_err']:.4f} "
          f"-> augmented {models['c3_contention']['avg_err']:.4f}, "
          f"pack totals_match={totals_match}", flush=True)
    _save_json("contention_chaos.json", prior)


def step_chaos(quick):
    """Seeded chaos drill over the serving stack (PR 9): deterministic
    faults at all five injection sites — corrupt artifact bytes, failed
    compile, hung batch vs the watchdog, transport drops, a replica crash
    — with the integrity guards and the fleet supervisor healing around
    them. The drill's own invariants (survivors bit-identical to a
    fault-free baseline, zero jobs lost, crashed replica restarted and
    readmitted, corrupt model breaker-isolated) ride in the ``checks``
    maps. Merges a `chaos` section into contention_chaos.json."""
    from repro.serving.chaos import run_chaos_fleet, run_chaos_single

    path = ART / "contention_chaos.json"
    prior = json.loads(path.read_text()) if path.exists() else {}
    if "chaos" in prior:
        return
    single = run_chaos_single(seed=7, quick=quick,
                              batch_timeout_s=10.0 if quick else 20.0)
    fleet = run_chaos_fleet(seed=7, quick=quick, n_replicas=2,
                            batch_timeout_s=20.0 if quick else 30.0)
    prior["chaos"] = {"seed": 7, "single": single, "fleet": fleet,
                      "ok": single["ok"] and fleet["ok"]}
    print(f"[pipeline] chaos: single ok={single['ok']} "
          f"({single['wall_seconds']:.1f}s), fleet ok={fleet['ok']} "
          f"({fleet['wall_seconds']:.1f}s, "
          f"{fleet['supervisor'].get('restarts_total', 0)} supervised "
          f"restart(s), {fleet['resubmits']} resubmits)", flush=True)
    _save_json("contention_chaos.json", prior)


def step_a64fx(quick):
    """Second processor configuration (§4.1): train on A64FX-labelled
    traces, save the artifact, evaluate held-out benchmarks in ONE pack."""
    if _exists("a64fx.json"):
        return
    n_ml = 8000 if quick else 60000
    n_ev = 4000 if quick else 20000
    ml = api.generate_traces(sorted(ML_BENCHMARKS), n_ml, o3=A64FX_CONFIG, cache_dir=TRACE_DIR)
    scfg = SimConfig(ctx_len=64)
    pcfg = PredictorConfig(kind="c3", ctx_len=64)
    art_path = ART / "models" / "a64fx_c3"
    if PredictorArtifact.exists(art_path):
        sn = SimNet.from_artifact(art_path)
    else:
        data = api.build_training_data(ml, scfg)
        sn = SimNet.train(data, pcfg, scfg,
                          epochs=2 if quick else 10, batch_size=1024)
        sn.save(art_path)
    eval_names = ["sim_loop", "sim_branchy_easy", "sim_stream2", "sim_compute2"]
    eval_traces = api.generate_traces(eval_names, n_ev, o3=A64FX_CONFIG, cache_dir=TRACE_DIR)
    # held-out evaluation rides one simulate_many pack, not per-trace calls
    res = sn.simulate_many(eval_traces, n_lanes=8)
    out = {
        "pred_errors": sn.artifact.metadata.get("train", {}).get("pred_errors"),
        "sim_errors": {name: float(w.cpi_error)
                       for name, w in zip(eval_names, res)},
    }
    out["sim_avg"] = float(np.mean(list(out["sim_errors"].values())))
    _save_json("a64fx.json", out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--steps", default="all")
    ap.add_argument("--eval-only", action="store_true",
                    help="skip training missing models; run table steps with what exists")
    args = ap.parse_args()
    t0 = time.time()
    ml, ml_eval, sim = get_traces(args.quick)
    data = {"ml_traces": ml, "ml_eval": ml_eval, "sim_traces": sim}
    print(f"[pipeline] traces ready {time.time()-t0:.0f}s", flush=True)
    dset_path = ART / "dataset.npz"
    if dset_path.exists():
        z = np.load(dset_path)
        data["dataset"] = {k: z[k] for k in z.files}
    else:
        data["dataset"] = api.build_training_data(ml, SimConfig(ctx_len=64), n_lanes=8)
        ART.mkdir(parents=True, exist_ok=True)
        np.savez(dset_path, **data["dataset"])
    print(f"[pipeline] dataset {data['dataset']['train_x'].shape} {time.time()-t0:.0f}s", flush=True)
    train_zoo(data, args.quick, skip_missing=args.eval_only)
    steps = args.steps.split(",") if args.steps != "all" else [
        "table4", "fig56", "fig7", "contention", "chaos", "table5", "a64fx"]
    if "table4" in steps:
        step_table4(data, args.quick)
    if "fig56" in steps:
        step_fig56(data, args.quick)
    if "fig7" in steps:
        step_fig7(data, args.quick)
    if "contention" in steps:
        step_contention(data, args.quick)
    if "chaos" in steps:
        step_chaos(args.quick)
    if "table5" in steps:
        step_table5(data, args.quick)
    if "a64fx" in steps:
        step_a64fx(args.quick)
    print(f"[pipeline] DONE in {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
