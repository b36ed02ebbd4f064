"""Compile the SimNet hot path for a described TPU v5e chip (no chip needed).

Interpret mode and the CPU backend accept kernels the TPU compiler refuses
(a sublane-into-lane reshape, an in-kernel dynamic slice, a reverse), so
these tests lower and compile, for one chip of a `v5e:2x2` topology, the
two Pallas kernels at the c3 widths (1024 lanes, lane tile 64, Q=64,
channels 64/128/128) and the plain and fused c3 chunk programs at 1024
lanes x chunk 1024, each fed the pack's lane-major chunk, and the plain tx6
chunk program at its benchmark cell's 4,096 lanes x chunk 256, whose
fusions must keep tx6's attention apart from other scopes' work. Nothing
runs. The topology is described inside a fixture, never at import: only
one process at a time may load libtpu.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

LANES, CHUNK, TILE = 1024, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def c3():
    from repro.core.predictor import PredictorConfig

    return PredictorConfig()  # c3 hybrid, ctx 64, channels 64/128/128


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _conv_weights(spec, c3):
    chans = [64] + list(c3.channels)  # first layer channel-padded 50 -> 64
    return [(spec((2 * chans[i], chans[i + 1])), spec((chans[i + 1],)))
            for i in range(3)]


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_cnn_trunk_compiles_for_v5e(one_chip, c3):
    from repro.kernels.cnn_trunk import cnn_trunk_pallas

    spec = _spec(one_chip)
    hlo = _compiled_text(
        lambda x, w: cnn_trunk_pallas(x, w, lane_tile=TILE, interpret=False),
        spec((LANES, c3.seq_padded, 64)), _conv_weights(spec, c3),
    )
    assert "tpu_custom_call" in hlo


def test_fused_step_compiles_for_v5e(one_chip, c3):
    from repro.core import features as F
    from repro.kernels.fused_step import fused_step_pallas

    spec = _spec(one_chip)
    L, Q = LANES, c3.ctx_len
    planes = [spec((L, Q, F.STATIC_END)), spec((L, Q, F.N_ADDR_KEYS), jnp.int32)]
    planes += [spec((L, Q)) for _ in range(4)]  # resid/exec/store/valid
    head = spec((1,), jnp.int32)
    cur = [spec((L, F.STATIC_END)), spec((L, F.N_ADDR_KEYS), jnp.int32)]
    hlo = _compiled_text(
        lambda planes, head, cur, w: fused_step_pallas(
            *planes, head, *cur, w, seq_padded=c3.seq_padded,
            lane_tile=TILE, interpret=False,
        ),
        planes, head, cur, _conv_weights(spec, c3),
    )
    assert "tpu_custom_call" in hlo


def _compiled_chunk_program(one_chip, pcfg, use_kernel, lanes=LANES, chunk=CHUNK):
    from repro.core.predictor import init_predictor
    from repro.core.simulator import init_state
    from repro.serving.simnet_engine import SimNetEngine, chunk_specs, lane_param_specs

    params = jax.eval_shape(lambda: init_predictor(jax.random.PRNGKey(0), pcfg)[0])
    eng = SimNetEngine(params, pcfg, use_kernel=use_kernel)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    xs = chunk_specs(lanes, chunk)
    # lane-major integer trace fields, as packed
    assert all(v.shape[:2] == (lanes, chunk) for v in xs.values())
    args = (
        params,
        jax.eval_shape(lambda: init_state(lanes, eng.sim_cfg)),
        xs,
        *lane_param_specs(lanes),
    )
    return eng._run_chunk.lower(*on_chip(args)).compile()


def test_c3_chunk_program_compiles_for_v5e(one_chip, c3):
    compiled = _compiled_chunk_program(one_chip, c3, use_kernel=False)
    assert compiled.memory_analysis() is not None


def test_fused_c3_chunk_program_compiles_for_v5e(one_chip, c3, monkeypatch):
    from repro.kernels import ops

    # the kernels pick interpret mode from the default backend, the CPU here
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled = _compiled_chunk_program(one_chip, c3, use_kernel=True)
    assert compiled.memory_analysis() is not None
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def tx6_program(one_chip):
    from repro.core.predictor import PredictorConfig

    # the tx6.sweep cell's batch: the zoo's widths (d 64, 4 heads, 6 layers),
    # 4,096 lanes x chunk 256
    tx6 = PredictorConfig(kind="tx6")
    return _compiled_chunk_program(one_chip, tx6, use_kernel=False, lanes=4096, chunk=256)


def test_tx6_chunk_program_compiles_for_v5e(tx6_program):
    # the compiler refuses a program that does not fit the chip's memory
    assert tx6_program.memory_analysis() is not None


# what an instruction of another scope may be inside a fusion that the
# trace puts down to tx6's `attention` scope: no arithmetic, no data moved
NO_WORK = {"parameter", "constant", "bitcast", "broadcast", "fusion"}


def _hlo_computations(text):
    """{computation name: [(opcode, op_name, called computations), ...]}
    of a compiled module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        ins = re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = .*?\s([a-z][\w\-]*)\(", line)
        if cur is not None and ins:
            name = re.search(r'op_name="([^"]*)"', line)
            cur.append((ins.group(1), name.group(1) if name else "",
                        re.findall(r"calls=%([\w.\-]+)", line)))
    return comps


def test_tx6_attention_fusions_hold_only_attention_work(tx6_program):
    """The device trace names a fusion by its root's op_name, so the
    attention readers count a fusion rooted in the `attention` scope whole
    and no other. On the chip's compiled program that is exact: every
    instruction of another scope inside such a fusion moves no data (the
    q / k / v slices of the projection's output are bitcasts), and no
    fusion rooted elsewhere holds an attention instruction."""
    comps = _hlo_computations(tx6_program.as_text())
    fused = {c for body in comps.values() for op, _, called in body if op == "fusion"
             for c in called}

    def inner(c):
        for op, name, called in comps.get(c, []):
            yield op, name
            for cc in called:
                yield from inner(cc)

    rooted = 0
    for c in set(comps) - fused:
        for op, name, called in comps[c]:
            if op != "fusion":
                continue
            parts = [(o, "/attention/" in n) for o, n in inner(called[0])]
            if "/attention/" in name:
                rooted += 1
                assert all(o in NO_WORK for o, att in parts if not att), (name, parts)
            else:
                assert not any(att for _, att in parts), name
    assert rooted >= 6 * 3  # QK^T, the softmax's sums and PV in each of the 6 layers
