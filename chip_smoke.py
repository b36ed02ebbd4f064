#!/usr/bin/env python3
"""Chip smoke: SimNet's simulate/serve path, end to end, on a TPU.

A smoke, not a benchmark: it proves that the main path compiles, runs and
gives right answers on the chip at the full width of the zoo's c3 hybrid
predictor. The timings it prints are set-up context, not measurements.

    python chip_smoke.py            # one chip, phases 1-7
    python chip_smoke.py --chips 4  # the lane-sharded packs over 4 chips,
                                    # against the same packs on one chip

Phases on one chip:
  1. device check: a TPU or a non-zero exit (no CPU fallback)
  2. traces: the reference DES over the evaluation benchmarks, in process,
     sliced into 1024 workloads of 2048 instructions
  3. teacher-forced pack: 1024 lanes (one per workload), two donated
     1024-step chunks per lane; every total equals its DES cycles exactly
  4. predicted pack: random c3 weights from --seed; default matmul
     precision against "highest", within CPI_TOL / PACK_TOL
  5. fused-kernel pack (use_kernel=True): a native Mosaic kernel in the
     chunk program, totals within the same tolerance of phase 4, and both
     Pallas kernels against their jnp oracle on one ring state
  6. served jobs: SimServe's background loop, teacher-forced and c3 jobs
     over the same traces, bit-identical to phases 3-4
  7. training: a few Adam steps of `train_loop`, every loss finite

The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Any failed phase raises, so the exit code is non-zero and no such line is
printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SLICE = 2048  # instructions per workload: two 1024-step chunks on its lane
CHUNK = 1024
N_WORKLOADS = 1024  # one lane each: a 1024-lane pack
# Predicted totals under two matmul precisions (and the fused kernel, whose
# Mosaic matmuls round differently from XLA's) may differ: random-weight
# hybrid heads flip a class wherever bf16 rounding (2^-9 relative) moves a
# near-tied argmax. A CPU rehearsal that rounds the trunk to bf16 moved
# per-workload CPI by at most 1.3% (median 0.17%) and the pack by 0.16%;
# these bounds leave room for the head's matmuls, which the TPU rounds too.
CPI_TOL = 0.05  # per workload, |ΔCPI| / CPI
PACK_TOL = 0.01  # whole pack, |Δcycles| / cycles
F32_EXACT = 1 << 24  # f32 lane clocks and totals are exact integers below this


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name):
    log(f"{name} ...")
    t0 = time.perf_counter()
    yield
    log(f"{name} passed ({time.perf_counter() - t0:.2f} s incl. compiles)")


def device_check(n_chips):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {len(devs)} "
              f"{d.platform} device(s) ({d.device_kind})", file=sys.stderr)
        sys.exit(1)
    if len(devs) < n_chips:
        print(f"chip_smoke: --chips {n_chips} but JAX found {len(devs)} "
              f"{d.platform} device(s)", file=sys.stderr)
        sys.exit(1)
    log(f"{len(devs)} x {d.device_kind} ({d.platform}); using {n_chips}")
    return {"platform": d.platform, "kind": d.device_kind, "count": n_chips}


def count_persistent_cache():
    """Hits / writes / lookups of JAX's on-disk compile cache."""
    from jax import monitoring

    counts = {"hits": 0, "writes": 0, "lookups": 0}
    names = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
    }

    def on_event(event, **_):
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(on_event)
    return counts


def make_workloads():
    """The evaluation benchmarks through the reference DES, in process and
    uncached, sliced into N_WORKLOADS workloads of SLICE instructions."""
    from repro.core.api import generate_traces
    from repro.des.workloads import SIM_BENCHMARKS

    names = sorted(SIM_BENCHMARKS)
    per = -(-N_WORKLOADS // len(names))
    traces = generate_traces(names, per * SLICE, cache_dir=None)
    slices = [t.slice(i * SLICE, (i + 1) * SLICE)
              for t in traces for i in range(per)][:N_WORKLOADS]
    worst = max(t.total_cycles for t in slices)
    check(worst < F32_EXACT,
          f"a workload runs {worst} DES cycles, past f32's exact range")
    log(f"{len(traces)} benchmarks, {sum(t.n for t in traces)} DES "
        f"instructions -> {len(slices)} workloads of {SLICE}; "
        f"max workload {worst} cycles (< 2^24)")
    return slices


def report(label, sn, res):
    b = sn.service.batches[-1]
    log(f"{label}: {b.n_live_lanes} live / {b.n_lanes} lanes, chunk "
        f"{b.chunk}, first call {res.first_call_seconds:.2f} s, in-memory "
        f"compile cache {res.cache}")
    return b


def cpi_gap(res, ref):
    cpi = np.array([w.cpi for w in res.workloads])
    ref_cpi = np.array([w.cpi for w in ref.workloads])
    per = np.abs(cpi - ref_cpi) / ref_cpi
    pack = abs(res.total_cycles - ref.total_cycles) / ref.total_cycles
    return per, pack


def check_gap(label, res, ref):
    per, pack = cpi_gap(res, ref)
    by_bench = {}
    for w, g in zip(ref.workloads, per):
        by_bench.setdefault(w.name.split("[")[0], []).append(g)
    for name, gs in sorted(by_bench.items()):
        log(f"  {label} {name}: per-workload CPI gap max {max(gs):.4%} "
            f"mean {np.mean(gs):.4%}")
    log(f"{label}: per-workload CPI gap max {per.max():.4%} median "
        f"{np.median(per):.4%}, {int((per > 0).sum())}/{len(per)} differ; "
        f"pack cycles gap {pack:.4%}")
    check(per.max() <= CPI_TOL, f"{label}: CPI gap {per.max():.4%} > {CPI_TOL:.0%}")
    check(pack <= PACK_TOL, f"{label}: pack gap {pack:.4%} > {PACK_TOL:.0%}")


def teacher_forced(slices, mesh=None):
    from repro.core.api import SimNet

    sn = SimNet(chunk=CHUNK, mesh=mesh)
    res = sn.simulate_many(slices, n_lanes=1)
    b = report("teacher-forced", sn, res)
    check(b.n_live_lanes >= 1024, f"only {b.n_live_lanes} lanes packed")
    check(b.chunk == CHUNK and SLICE // b.chunk >= 2,
          f"chunk {b.chunk}: a lane must run two or more chunks")
    bad = [w.name for w in res.workloads if w.total_cycles != w.des_cycles]
    check(not bad, f"{len(bad)} workloads differ from the DES, e.g. {bad[:3]}")
    return sn, res


def c3_model(seed):
    import jax

    from repro.core.predictor import PredictorConfig, init_predictor

    pcfg = PredictorConfig()
    params, _ = init_predictor(jax.random.PRNGKey(seed), pcfg)
    return params, pcfg


def predicted(slices, params, pcfg, mesh=None):
    from repro.core.api import SimNet

    sn = SimNet(params=params, pcfg=pcfg, chunk=CHUNK, mesh=mesh)
    res = sn.simulate_many(slices, n_lanes=1)
    report("c3 predicted", sn, res)
    return sn, res


def precision_gap(slices, params, pcfg, res):
    import jax

    from repro.core.api import SimNet
    from repro.serving.compile_cache import CompileCache

    # a private compile cache: its key does not carry the matmul precision
    with jax.default_matmul_precision("highest"):
        sn = SimNet(params=params, pcfg=pcfg, chunk=CHUNK, cache=CompileCache())
        hi = sn.simulate_many(slices, n_lanes=1)
    report("c3 predicted, highest precision", sn, hi)
    check_gap("default vs highest", res, hi)


def populated_ring_state(slices, pcfg, n_lanes=256, steps=100):
    """A ring state mid-trace (head cursor away from 0) plus the next
    instruction, from a teacher-forced scan over the first workloads."""
    import jax
    import jax.numpy as jnp

    from repro.core import features as F
    from repro.core.simulator import (
        SimConfig, chunk_time_major, init_state, make_sim_scan, pack_workloads,
    )

    cfg = SimConfig(ctx_len=pcfg.ctx_len)
    packed = pack_workloads([F.trace_fields(t) for t in slices[:n_lanes]], 1, cfg)
    lanes = {k: v[0] for k, v in packed.xs.items()}  # the one chunk, (L, T, ...)
    xs = {k: jnp.asarray(v[:, :steps]) for k, v in lanes.items()}
    step = make_sim_scan(None, cfg, emit_outputs=False)
    state, _ = jax.jit(lambda s, x: jax.lax.scan(step, s, chunk_time_major(x)))(
        init_state(n_lanes, cfg), xs)
    nxt = F.expand({k: v[:, steps] for k, v in lanes.items()})
    return cfg, state, jnp.asarray(nxt["feat"]), jnp.asarray(nxt["addr"])


def kernels_vs_oracle(slices, params, pcfg):
    """Both Pallas kernels against the jnp oracle on one mid-trace state."""
    import jax
    import jax.numpy as jnp

    from repro.core.predictor import _pad_seq
    from repro.core.simulator import model_input
    from repro.kernels import ops, ref

    cfg, state, cur_feat, cur_addr = populated_ring_state(slices, pcfg)
    check(int(state.head) != 0, "the ring cursor must sit away from slot 0")
    conv = [params[f"conv{i}"] for i in range(3)]
    x = _pad_seq(model_input(state, cur_feat, cur_addr, cfg), pcfg)
    with jax.default_matmul_precision("highest"):
        want = ref.cnn_trunk_ref([(c["w"], c["b"]) for c in conv], x)
    got = {
        "fused_step": ops.fused_step(conv, state, cur_feat, cur_addr,
                                     seq_padded=pcfg.seq_padded),
        "cnn_trunk": ops.cnn_trunk(conv, x),
    }
    scale = float(jnp.max(jnp.abs(want)))
    for name, out in got.items():
        err = float(jnp.max(jnp.abs(out - want))) / scale
        log(f"{name} vs jnp oracle: max |error| {err:.2e} of max |activation| "
            f"{scale:.3g}")
        check(err < 1e-2, f"{name} disagrees with its oracle ({err:.2e})")


def kernel_pack(slices, params, pcfg, res_ref):
    from repro.core.api import SimNet

    sn = SimNet(params=params, pcfg=pcfg, chunk=CHUNK, use_kernel=True)
    res = sn.simulate_many(slices, n_lanes=1)
    b = report("c3 fused kernel", sn, res)
    hlo = sn.engine.executable(b.n_lanes, b.chunk).as_text()
    check("tpu_custom_call" in hlo, "the fused chunk program holds no Mosaic kernel")
    log("fused chunk program holds a tpu_custom_call (native Mosaic kernel)")
    check_gap("fused kernel vs jnp", res, res_ref)
    kernels_vs_oracle(slices, params, pcfg)


def served(slices, engine, tf_res, c3_res):
    from repro.core import features as F
    from repro.core.api import SimServe

    arrs = [F.trace_arrays(t) for t in slices]
    # a batch window long enough that each model's jobs share one batch
    with SimServe(chunk=CHUNK, max_wait_ms=5000.0) as serve:
        serve.register_engine("c3", engine)
        handles = [(m, serve.submit(a, m, n_lanes=1, chunk=CHUNK))
                   for m in (None, "c3") for a in arrs]
        results = [(m, h.result(timeout=900)) for m, h in handles]
        st = serve.stats()
    log(f"served {len(results)} jobs in {st['batches']} batches, "
        f"jobs_per_batch {st['jobs_per_batch']}, loop_errors {st['loop_errors']}")
    check(st["loop_errors"] == 0, f"loop_errors {st['loop_errors']}")
    check(st["jobs_per_batch"] > 1, f"jobs_per_batch {st['jobs_per_batch']}")
    want = [w.total_cycles for w in tf_res.workloads] + [w.total_cycles for w in c3_res.workloads]
    got = [r.total_cycles for _, r in results]
    bad = sum(g != w for g, w in zip(got, want))
    check(bad == 0, f"{bad} served totals differ from the in-process runs")


def training(slices, pcfg, seed):
    from repro.core.dataset import build_dataset
    from repro.core.session import train_loop
    from repro.core.simulator import SimConfig

    data = build_dataset(slices[:8], SimConfig(ctx_len=pcfg.ctx_len))
    _, hist = train_loop(data, pcfg, epochs=2, batch_size=512, seed=seed)
    n_steps = 2 * (len(data["train_x"]) // 512)
    losses = hist["train_loss"] + hist["val_loss"]
    log(f"{n_steps} Adam steps on {len(data['train_x'])} samples: "
        f"train {hist['train_loss']}, val {hist['val_loss']}")
    check(n_steps > 0 and np.isfinite(losses).all(), f"losses {losses}")


def lane_state_shards(sn, slices, mesh):
    """One chunk through the sharded engine's own executable: every lane
    plane of the returned state must be split over all mesh devices."""
    import jax

    from repro.core import features as F
    from repro.core.simulator import init_state, pack_workloads
    from repro.serving.simnet_engine import chunk_shardings, lane_sharding, state_shardings

    eng = sn.engine
    packed = pack_workloads([F.trace_fields(t) for t in slices], 1, eng.sim_cfg, chunk=CHUNK)
    L = packed.n_lanes
    xs_sh, lane_sh = chunk_shardings(mesh), lane_sharding(mesh)
    state = jax.device_put(init_state(L, eng.sim_cfg), state_shardings(mesh))
    xs = {k: jax.device_put(v[0], xs_sh[k]) for k, v in packed.xs.items()}
    out = eng.executable(L, CHUNK)(
        eng.params, state, xs,
        jax.device_put(packed.retire_width, lane_sh),
        jax.device_put(packed.lane_ctx, lane_sh),
    )
    n = mesh.devices.size
    for name, arr in out._asdict().items():
        if name == "head":  # the replicated scalar ring cursor
            continue
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        check(len(devices) == n and all(s.data.shape[0] == L // n for s in shards),
              f"state plane {name}: shards {[(s.device, s.data.shape) for s in shards]}")
    log(f"every lane plane of the state sits on {n} devices, {L // n} lanes each")


def one_chip(args, slices):
    with phase("phase 3: teacher-forced pack"):
        tf_sn, tf_res = teacher_forced(slices)
    with phase("phase 4: predicted pack"):
        params, pcfg = c3_model(args.seed)
        c3_sn, c3_res = predicted(slices, params, pcfg)
        precision_gap(slices, params, pcfg, c3_res)
    with phase("phase 5: fused-kernel pack"):
        kernel_pack(slices, params, pcfg, c3_res)
    with phase("phase 6: served jobs"):
        served(slices, c3_sn.engine, tf_res, c3_res)
    with phase("phase 7: training"):
        training(slices, pcfg, args.seed)


def four_chips(args, slices):
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    check(mesh.devices.size == args.chips, f"mesh {mesh.devices.shape}")
    params, pcfg = c3_model(args.seed)
    with phase("teacher-forced pack, one chip vs sharded"):
        _, one = teacher_forced(slices)
        sn, many = teacher_forced(slices, mesh=mesh)
        check([w.total_cycles for w in many.workloads]
              == [w.total_cycles for w in one.workloads],
              "sharded teacher-forced totals differ from one chip")
        lane_state_shards(sn, slices, mesh)
    with phase("predicted pack, one chip vs sharded"):
        _, one = predicted(slices, params, pcfg)
        sn, many = predicted(slices, params, pcfg, mesh=mesh)
        diff = sum(a.total_cycles != b.total_cycles
                   for a, b in zip(many.workloads, one.workloads))
        check(diff == 0, f"{diff} sharded predicted totals differ from one chip")
        lane_state_shards(sn, slices, mesh)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded packs over four chips")
    ap.add_argument("--seed", type=int, default=0, help="predictor weight seed")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    with phase("phase 1: device check"):
        device = device_check(args.chips)
    import jax

    from repro.serving.compile_cache import enable_persistent_cache

    log(f"persistent compile cache: {enable_persistent_cache()}")
    cache_counts = count_persistent_cache()
    with phase("phase 2: traces"):
        slices = make_workloads()
    if args.chips == 1:
        one_chip(args, slices)
    else:
        four_chips(args, slices)

    stats = jax.devices()[0].memory_stats() or {}
    log(f"persistent compile cache {cache_counts}; device 0 peak memory "
        f"{stats.get('peak_bytes_in_use', 'not reported')} B; "
        f"{time.perf_counter() - t0:.1f} s in all")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
