"""PredictorArtifact: save → load round-trip must be exact.

The deployment contract (train-once / simulate-everywhere) only holds if a
reloaded artifact is indistinguishable from the in-process predictor:
params bit-identical, configs equal, simulation results equal.
"""
import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, PredictorArtifact
from repro.core.predictor import PredictorConfig, init_predictor
from repro.core.session import SimNet
from repro.core.simulator import SimConfig


@pytest.fixture(scope="module")
def pcfg():
    return PredictorConfig(kind="c1", ctx_len=16, channels=(16, 16, 16), hidden=32)


@pytest.fixture(scope="module")
def params(pcfg):
    p, _ = init_predictor(jax.random.PRNGKey(7), pcfg)
    return p


def test_roundtrip_bit_identical(tmp_path, params, pcfg):
    scfg = SimConfig(ctx_len=16, retire_width=4)
    art = PredictorArtifact(params, pcfg, scfg, metadata={"note": "rt"})
    art.save(tmp_path / "a")
    back = PredictorArtifact.load(tmp_path / "a")
    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(back.params))
    assert len(flat_a) == len(flat_b)
    for path, leaf in flat_a:
        a, b = np.asarray(leaf), np.asarray(flat_b[path])
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes(), f"params differ at {path}"
    assert back.pcfg == pcfg  # tuple fields (channels) must survive json
    assert isinstance(back.pcfg.channels, tuple)
    assert back.sim_cfg == scfg
    assert back.metadata == {"note": "rt"}


def test_simulate_from_loaded_matches_fresh(tmp_path, params, pcfg, loop_trace):
    """A session built from the loaded artifact reproduces the fresh
    session's totals exactly (acceptance criterion for cross-process
    reproduction — here the 'other process' is a reload)."""
    fresh = SimNet(params=params, pcfg=pcfg)
    fresh.save(tmp_path / "m")
    loaded = SimNet.from_artifact(tmp_path / "m")
    a = fresh.simulate(loop_trace, n_lanes=4)
    b = loaded.simulate(loop_trace, n_lanes=4)
    assert a[0].total_cycles == b[0].total_cycles
    assert a[0].cpi == b[0].cpi
    assert a[0].overflow == b[0].overflow


def test_save_via_session_carries_training_metadata(tmp_path, params, pcfg):
    sn = SimNet(params=params, pcfg=pcfg)
    sn.save(tmp_path / "m", metadata={"run": "unit"})
    art = PredictorArtifact.load(tmp_path / "m")
    assert art.metadata["run"] == "unit"


def test_overwrite_keeps_single_artifact(tmp_path, params, pcfg):
    """Saving twice into one directory keeps exactly one live artifact
    (keep-1 checkpoint semantics — no stale step dirs pile up)."""
    art = PredictorArtifact(params, pcfg, SimConfig(ctx_len=16))
    art.save(tmp_path / "a")
    art.save(tmp_path / "a")
    assert CheckpointManager(tmp_path / "a").all_steps() == [0]
    assert PredictorArtifact.exists(tmp_path / "a")


def test_reload_preserves_metadata(tmp_path, params, pcfg):
    """from_artifact → save must carry the saved provenance forward, not
    strip it (table4 reads pred_errors/train metadata from reloaded
    artifacts)."""
    sn = SimNet(params=params, pcfg=pcfg)
    sn.save(tmp_path / "m", metadata={"train": {"pred_errors": {"fetch": 0.1}}})
    loaded = SimNet.from_artifact(tmp_path / "m")
    assert loaded.artifact.metadata["train"]["pred_errors"] == {"fetch": 0.1}
    loaded.save(tmp_path / "m2")
    again = PredictorArtifact.load(tmp_path / "m2")
    assert again.metadata["train"]["pred_errors"] == {"fetch": 0.1}


def test_exists_and_load_are_pure_reads(tmp_path):
    """Probing or loading a missing path must not create directories."""
    missing = tmp_path / "nope" / "deep"
    assert not PredictorArtifact.exists(missing)
    with pytest.raises(FileNotFoundError):
        PredictorArtifact.load(missing)
    assert not missing.exists() and not (tmp_path / "nope").exists()


def test_exists_rejects_non_artifacts(tmp_path):
    assert not PredictorArtifact.exists(tmp_path / "missing")
    # a plain checkpoint directory is not a predictor artifact
    CheckpointManager(tmp_path / "ckpt").save(3, {"x": np.zeros(2)})
    assert not PredictorArtifact.exists(tmp_path / "ckpt")
    with pytest.raises(ValueError, match="not a simnet-predictor"):
        PredictorArtifact.load(tmp_path / "ckpt")


def test_load_rejects_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        PredictorArtifact.load(tmp_path / "nope")
