"""END-TO-END DRIVER (the paper is a simulation/serving paper): serve a
stream of simulation requests through the distributed SimNet engine.

Pipeline: synthetic program → lightweight history-context simulation (fast
path — no DES pipeline model!) → massively-parallel ML simulation via the
SimNet session (engine pack path) → accuracy + throughput vs the DES.

  PYTHONPATH=src python examples/simulate_workload.py [--lanes 32] [--n 60000]
"""
import argparse
import time
from pathlib import Path

from repro.checkpoint import PredictorArtifact
from repro.core import api
from repro.core.api import SimNet
from repro.core.predictor import PredictorConfig
from repro.core.simulator import SimConfig
from repro.des.history import trace_with_history
from repro.des.o3 import O3Config, O3Simulator
from repro.des.workloads import get_benchmark

ARTIFACT = Path("artifacts/simnet/models/c3_hybrid")
FALLBACK = Path("artifacts/models/quick_c3")


def get_session() -> SimNet:
    """Reuse the pipeline's trained artifact if present, else train a quick
    one and save it so the next run reloads instead of retraining."""
    for path in (ARTIFACT, FALLBACK):
        if PredictorArtifact.exists(path):
            return SimNet.from_artifact(path)
    print("(no pretrained artifact found — training a quick one)")
    traces = api.generate_traces(["mlb_mixed", "mlb_stream"], 20000,
                                 cache_dir="artifacts/traces")
    sn = SimNet.train(traces, PredictorConfig(kind="c3", ctx_len=64),
                      SimConfig(ctx_len=64), epochs=6, batch_size=512)
    sn.save(FALLBACK)
    return sn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="sim_phased")
    ap.add_argument("--n", type=int, default=60000)
    ap.add_argument("--lanes", type=int, default=32)
    args = ap.parse_args()

    sn = get_session()
    prog = get_benchmark(args.bench, args.n)

    print("== history-context simulation (fast path, no pipeline model) ==")
    t0 = time.time()
    trace = trace_with_history(prog)  # caches/TLB/branch predictor only
    t_hist = time.time() - t0
    print(f"  {args.n} instructions in {t_hist:.1f}s ({args.n/t_hist:.0f} IPS)")

    print(f"== parallel ML simulation: {args.lanes} lanes ==")
    res = sn.simulate(trace, n_lanes=args.lanes, chunk=512)
    w = res[0]
    print(f"  SimNet: {w.total_cycles:.0f} cycles, CPI {w.cpi:.3f}, "
          f"{res.throughput_ips:.0f} instr/s")

    print("== reference DES comparison ==")
    t0 = time.time()
    ref = O3Simulator(O3Config()).run(prog)
    t_des = time.time() - t0
    err = abs(w.cpi - ref.cpi) / ref.cpi
    print(f"  DES: {ref.total_cycles} cycles, CPI {ref.cpi:.3f}, "
          f"{args.n/t_des:.0f} instr/s")
    print(f"  CPI error {100*err:.2f}%  |  SimNet speedup over DES "
          f"{(res.throughput_ips*t_des/args.n):.1f}x on 1 CPU core "
          f"(TPU roofline bound: see benchmarks.roofline simnet-c3 cells)")


if __name__ == "__main__":
    main()
