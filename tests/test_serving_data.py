"""Serving engine, SimNet engine, data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced_config
from repro.core import features as F
from repro.core.predictor import PredictorConfig, init_predictor, make_predict_fn
from repro.core.simulator import SimConfig, simulate_trace
from repro.data.pipeline import SyntheticCorpus, TokenLoader
from repro.models.registry import build_model
from repro.serving.engine import DecodeEngine, lm_decoder
from repro.serving.simnet_engine import SimNetEngine


@pytest.mark.slow
def test_decode_engine_greedy(small_trace):
    cfg = get_reduced_config("tinyllama-1.1b")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = DecodeEngine(lm_decoder(model), params, donate=False)
    state = model.init_decode_state(2, 32)
    toks, state, tps = engine.generate(state, jnp.asarray([1, 2], jnp.int32), 8)
    assert toks.shape == (8, 2)
    assert int(state["pos"]) == 8
    assert tps > 0


def test_simnet_engine_matches_direct_scan(small_trace):
    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    scfg = SimConfig(ctx_len=16)
    arrs = F.trace_arrays(small_trace)
    engine = SimNetEngine(params, pcfg, scfg)
    res_e = engine.simulate_many([F.trace_fields(small_trace)], n_lanes=4, chunk=256)
    predict = make_predict_fn(params, pcfg)
    res_d = simulate_trace(arrs, predict, scfg, n_lanes=4)
    # chunked-scan engine must agree with the single-scan reference wherever
    # both consumed the same number of instructions
    if int(res_e["n_instructions"][0]) == int(res_d["n_instructions"]):
        assert float(res_e["workload_cycles"][0]) == pytest.approx(
            float(res_d["total_cycles"]), rel=1e-6)
    else:
        assert float(res_e["workload_cpi"][0]) == pytest.approx(
            float(res_d["total_cycles"]) / int(res_d["n_instructions"]), rel=0.1
        )


def test_simnet_engine_lowers():
    pcfg = PredictorConfig(kind="c1", ctx_len=16)
    params, _ = init_predictor(jax.random.PRNGKey(0), pcfg)
    engine = SimNetEngine(params, pcfg, SimConfig(ctx_len=16))
    lowered = engine.lower(n_lanes=8, chunk=16)
    assert lowered.compile() is not None


class TestData:
    def test_loader_shapes_and_masks(self):
        loader = TokenLoader(vocab=100, batch_size=4, seq_len=32)
        b = next(loader)
        assert b["tokens"].shape == (4, 32)
        assert b["tokens"].max() < 100
        assert b["loss_mask"].shape == (4, 32)
        loader.close()

    def test_host_sharding_disjoint(self):
        l0 = TokenLoader(vocab=100, batch_size=4, seq_len=16, host_id=0, n_hosts=2, seed=3)
        l1 = TokenLoader(vocab=100, batch_size=4, seq_len=16, host_id=1, n_hosts=2, seed=3)
        b0, b1 = next(l0), next(l1)
        assert b0["tokens"].shape == (2, 16)
        assert not np.array_equal(b0["tokens"], b1["tokens"])
        l0.close()
        l1.close()

    def test_corpus_has_learnable_structure(self):
        c = SyntheticCorpus(vocab=1000, seed=0)
        toks = c.tokens(20000, stream_seed=1)
        # phrase reuse ⇒ repeated 4-grams far above random chance
        grams = {}
        for i in range(len(toks) - 4):
            g = tuple(toks[i : i + 4])
            grams[g] = grams.get(g, 0) + 1
        assert max(grams.values()) > 3
