"""Compile the SimNet hot path for a described TPU v5e chip (no chip needed).

Interpret mode and the CPU backend accept kernels the TPU compiler refuses
(a sublane-into-lane reshape, an in-kernel dynamic slice, a reverse), so
these tests lower and compile, for one chip of a `v5e:2x2` topology, the
two Pallas kernels at the c3 widths (1024 lanes, lane tile 64, Q=64,
channels 64/128/128) and the plain and fused c3 chunk programs at 1024
lanes x chunk 1024, each fed the pack's lane-major chunk. Nothing runs. The topology is described inside a fixture, never at
import: only one process at a time may load libtpu.
"""
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


def _compiled_chunk_program(one_chip, c3, use_kernel):
    from repro.core.predictor import init_predictor
    from repro.core.simulator import init_state
    from repro.serving.simnet_engine import SimNetEngine, chunk_specs, lane_param_specs

    params = jax.eval_shape(lambda: init_predictor(jax.random.PRNGKey(0), c3)[0])
    eng = SimNetEngine(params, c3, use_kernel=use_kernel)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    xs = chunk_specs(LANES, CHUNK)
    assert xs["feat"].shape[:2] == (LANES, CHUNK)  # lane-major, as packed
    args = (
        params,
        jax.eval_shape(lambda: init_state(LANES, eng.sim_cfg)),
        xs,
        *lane_param_specs(LANES),
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
