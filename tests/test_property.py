"""Property-based tests (hypothesis) for the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from conftest import synth_fields  # noqa: E402
from repro.core import features as F
from repro.core.simulator import (
    SimConfig,
    _suffix_any,
    _suffix_count,
    drain_cycles,
    init_state,
    sim_step,
    simulate_many,
    simulate_trace,
)
from repro.des.cache import Cache
from repro.runtime import hlo as hlo_lib
from repro.training.optimizer import AdamConfig, adam_init, adam_update


# ---------------------------------------------------------------- suffix ops
@given(st.lists(st.booleans(), min_size=1, max_size=32))
@settings(max_examples=50, deadline=None)
def test_suffix_ops_match_numpy(bits):
    x = jnp.asarray([bits])
    a = np.asarray(_suffix_any(x))[0]
    c = np.asarray(_suffix_count(x))[0]
    ref_a = [any(bits[i + 1 :]) for i in range(len(bits))]
    ref_c = [sum(bits[i + 1 :]) for i in range(len(bits))]
    np.testing.assert_array_equal(a, ref_a)
    np.testing.assert_array_equal(c, ref_c)


# ------------------------------------------------------------ clock invariant
@given(
    st.lists(
        st.tuples(
            st.integers(0, 20),  # fetch
            st.integers(1, 60),  # exec
            st.integers(0, 80),  # store (0 → not a store)
        ),
        min_size=1,
        max_size=24,
    )
)
@settings(max_examples=30, deadline=None)
def test_curtick_equals_sum_of_fetch(lat_list):
    """Paper Eq. 1: curTick after N steps == Σ fetch latencies, always."""
    cfg = SimConfig(ctx_len=8)
    state = init_state(1, cfg)
    feat = jnp.zeros((1, F.STATIC_END))
    addr = jnp.zeros((1, F.N_ADDR_KEYS), jnp.int32)
    total_f = 0
    for f, e, s in lat_list:
        is_store = s > 0
        fr = np.zeros((1, F.STATIC_END), np.float32)
        if is_store:
            fr[0, 7] = 1.0
        cur = {"feat": jnp.asarray(fr), "addr": addr, "is_store": jnp.asarray([is_store])}
        state = sim_step(state, cur, jnp.asarray([[float(f), float(e), float(s)]]), cfg)
        total_f += f
    assert float(state.cur_tick[0]) == float(total_f)
    assert float(drain_cycles(state)[0]) >= 0.0


# ------------------------------------------------------- in-order retirement
@given(st.lists(st.integers(1, 50), min_size=3, max_size=12), st.integers(1, 10))
@settings(max_examples=30, deadline=None)
def test_retirement_never_reorders(execs, advance):
    """After any clock advance, the set of remaining processor-queue entries
    is a prefix-closed suffix in age order (no younger survives... precisely:
    if an older proc entry is still present, every younger one is too —
    wait, in-order retirement means: present entries form a contiguous
    *youngest* block: any entry older than a present entry must be absent
    only if it retired earlier, which in-order forbids. So: present(proc)
    must be a contiguous suffix ending at the oldest unready entry."""
    cfg = SimConfig(ctx_len=16, retire_width=100)
    state = init_state(1, cfg)
    addr = jnp.zeros((1, F.N_ADDR_KEYS), jnp.int32)
    feat = jnp.zeros((1, F.STATIC_END))
    for e in execs:
        cur = {"feat": feat, "addr": addr, "is_store": jnp.asarray([False])}
        state = sim_step(state, cur, jnp.asarray([[0.0, float(e), 0.0]]), cfg)
    cur = {"feat": feat, "addr": addr, "is_store": jnp.asarray([False])}
    state = sim_step(state, cur, jnp.asarray([[float(advance), 1.0, 0.0]]), cfg)
    valid = np.asarray(state.valid[0])
    # slots: 0 newest ... Q-1 oldest. In-order ⇒ valid proc entries are a
    # contiguous block starting at slot 0 side... i.e. once we see an
    # invalid slot scanning from newest to oldest *after the first valid*,
    # no valid may follow (retirement consumes strictly from the old end).
    seen_invalid_after_valid = False
    ok = True
    started = False
    for q in range(len(valid)):  # newest → oldest
        if valid[q]:
            if seen_invalid_after_valid:
                ok = False
            started = True
        elif started:
            seen_invalid_after_valid = True
    assert ok


# ------------------------------------------------------- multi-workload pack
def _synthetic_arrs(T, seed):
    return F.expand(synth_fields(
        T, seed, store_share=0.2, labels=lambda rng, n: rng.integers(0, 30, (n, 3))))


def _check_packed_matches_separate(jobs):
    """jobs: list of (T, lanes, seed). Teacher-forced packed totals must be
    bit-identical to separate per-workload runs, for ANY job mix."""
    cfg = SimConfig(ctx_len=8)
    arrs = [_synthetic_arrs(T, seed) for T, _, seed in jobs]
    lanes = [ln for _, ln, _ in jobs]
    many = simulate_many([F.trace_fields(a) for a in arrs], None, cfg, n_lanes=lanes)
    for i, (a, ln) in enumerate(zip(arrs, lanes)):
        ref = simulate_trace(a, None, cfg, ln)
        assert float(many["workload_cycles"][i]) == float(ref["total_cycles"])
        assert int(many["workload_overflow"][i]) == int(ref["overflow"])


@given(
    st.lists(
        st.tuples(
            st.integers(8, 40),  # T instructions
            st.integers(1, 4),  # lanes
            st.integers(0, 100),  # workload seed
        ),
        min_size=1,
        max_size=4,
    )
)
@settings(max_examples=8, deadline=None)
def test_packed_workloads_match_separate_runs(jobs):
    _check_packed_matches_separate(jobs)


@given(
    st.lists(
        st.tuples(
            st.integers(8, 48),  # T instructions (ragged across jobs)
            st.integers(1, 4),  # lanes
            st.integers(0, 100),  # workload seed
            st.sampled_from([4, 8, 16]),  # per-job ctx_len (→ lane_ctx)
            st.integers(1, 4),  # per-job retire_width
        ),
        min_size=1,
        max_size=4,
    )
)
@settings(max_examples=10, deadline=None)
def test_ring_layout_bit_identical_to_roll(jobs):
    """Tentpole invariant: the ring step layout's packed per-lane and
    per-workload totals are BIT-IDENTICAL to the roll layout's for random
    traces, ragged lengths, and heterogeneous retire_width / lane_ctx."""
    arrs = [_synthetic_arrs(T, seed) for T, _, seed, _, _ in jobs]
    lanes = [min(ln, T) for T, ln, _, _, _ in jobs]

    def run(layout):
        cfgs = [
            SimConfig(ctx_len=ctx, retire_width=rw, layout=layout)
            for _, _, _, ctx, rw in jobs
        ]
        return simulate_many([F.trace_fields(a) for a in arrs], None, cfgs, n_lanes=lanes)

    roll, ring = run("roll"), run("ring")
    for k in ("lane_cycles", "workload_cycles", "workload_overflow"):
        np.testing.assert_array_equal(
            np.asarray(roll[k]), np.asarray(ring[k]), err_msg=k
        )


@given(
    st.lists(
        st.tuples(
            st.integers(8, 64),  # T instructions
            st.integers(1, 5),  # lanes (buckets to 1/2/4/8 with dead lanes)
            st.integers(0, 100),  # workload seed
            st.sampled_from([4, 8]),  # per-job ctx_len
            st.integers(1, 4),  # per-job retire_width
        ),
        min_size=1,
        max_size=3,
    )
)
@settings(max_examples=10, deadline=None)
def test_service_bucketing_never_changes_totals(jobs):
    """SimServe invariant: lane-count bucketing + dead-lane masking never
    changes any workload's totals, for random job mixes with heterogeneous
    per-job SimConfigs (teacher-forced; service path vs unbucketed core)."""
    from repro.core.api import SimServe

    arrs = [_synthetic_arrs(T, seed) for T, _, seed, _, _ in jobs]
    lanes = [min(ln, T) for T, ln, _, _, _ in jobs]  # a lane needs ≥1 instr
    cfgs = [SimConfig(ctx_len=ctx, retire_width=rw) for _, _, _, ctx, rw in jobs]
    ref = simulate_many([F.trace_fields(a) for a in arrs], None, cfgs, n_lanes=lanes)
    serve = SimServe()
    serve.register("tf", sim_cfg=SimConfig(ctx_len=8))
    handles = [
        serve.submit(a, "tf", n_lanes=ln, sim_cfg=c)
        for a, ln, c in zip(arrs, lanes, cfgs)
    ]
    serve.drain()
    for i, h in enumerate(handles):
        w = h.result()
        assert w.total_cycles == float(ref["workload_cycles"][i])
        assert w.overflow == int(ref["workload_overflow"][i])


# ----------------------------------------------------------------- cache LRU
@given(st.lists(st.integers(0, 31), min_size=1, max_size=200))
@settings(max_examples=30, deadline=None)
def test_cache_matches_reference_lru(addrs):
    """The Cache must agree with a literal LRU-list reference model."""
    c = Cache(4 * 64, 4, 64)  # 1 set, 4 ways
    ref = []  # list of line ids, most-recent last
    for a in addrs:
        line = a  # 1 set → line id == tag
        hit, _ = c.access(a * 64)
        ref_hit = line in ref
        assert hit == ref_hit, (a, ref)
        if ref_hit:
            ref.remove(line)
        elif len(ref) == 4:
            ref.pop(0)
        ref.append(line)


# ----------------------------------------------------------------- optimizer
@given(st.floats(0.5, 5.0), st.integers(1, 3))
@settings(max_examples=10, deadline=None)
def test_adam_descends_quadratic(scale, seed):
    params = {"w": jnp.asarray(np.random.default_rng(seed).normal(0, scale, 4), jnp.float32)}
    opt = adam_init(params)
    cfg = AdamConfig(lr=0.1, clip_norm=0.0)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, opt, _ = adam_update(g, opt, params, cfg)
    assert float(loss(params)) < l0 * 0.2


# ------------------------------------------------------------------ HLO shapes
@given(st.sampled_from(["bf16", "f32", "s8"]), st.lists(st.integers(1, 64), min_size=0, max_size=3))
@settings(max_examples=30, deadline=None)
def test_hlo_shape_bytes(dtype, dims):
    text = f"{dtype}[{','.join(map(str, dims))}]"
    n = int(np.prod(dims)) if dims else 1
    per = {"bf16": 2, "f32": 4, "s8": 1}[dtype]
    assert hlo_lib._shape_bytes_all(text) == n * per
