"""Checkpoint/resume + fault-injection tests for coordinate descent
(SURVEY.md §5: the reference has no mid-training checkpointing; the TPU
build adds orbax-style state saves every k coordinate updates and a
fault-injection test that kills and resumes mid-descent)."""

import numpy as np
import pytest

from photon_ml_tpu.algorithm import CoordinateDescent
from photon_ml_tpu.evaluation import build_evaluator
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.checkpoint import (
    CheckpointState,
    all_checkpoint_steps,
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
)

from tests.test_coordinate_descent import build_coordinates, make_glmix_data


def _final_coefs(result):
    fe = result.model.get_model("fixed")
    return np.asarray(fe.glm.coefficients.means)


def test_checkpoint_save_load_roundtrip(tmp_path):
    state = CheckpointState(
        step=3, models={"a": np.arange(4.0)},
        objective_history=[3.0, 2.0, 1.0], validation_history=[{"AUC": 0.7}],
        best_metric=0.7, best_models=None, timings={"a": 1.5})
    save_checkpoint(tmp_path, state)
    loaded = load_checkpoint(latest_checkpoint(tmp_path))
    assert loaded.step == 3
    np.testing.assert_array_equal(loaded.models["a"], np.arange(4.0))
    assert loaded.objective_history == [3.0, 2.0, 1.0]
    assert loaded.best_metric == 0.7


def test_checkpoint_retention_and_atomicity(tmp_path):
    for step in (1, 2, 3, 4):
        save_checkpoint(tmp_path, CheckpointState(
            step=step, models={}, objective_history=[],
            validation_history=[], best_metric=None, best_models=None,
            timings={}), keep=2)
    assert sorted(all_checkpoint_steps(tmp_path)) == [3, 4]
    assert not list(tmp_path.glob("*.tmp"))
    # A stray truncated tmp file never shadows a real checkpoint.
    (tmp_path / "ckpt-00000009.tmp").write_bytes(b"garbage")
    assert latest_checkpoint(tmp_path).name == "ckpt-00000004.pkl"


@pytest.mark.slow
def test_resume_matches_uninterrupted_run(rng, tmp_path):
    """Kill after a mid-descent checkpoint; the resumed run must reproduce
    the uninterrupted run (fold_in per-step keys make this exact)."""
    data, *_ = make_glmix_data(rng)

    # Uninterrupted reference run (no checkpointing).
    cd_ref = CoordinateDescent(build_coordinates(data),
                               TaskType.LOGISTIC_REGRESSION)
    ref = cd_ref.run(num_iterations=3, seed=11)

    # Fault-injected run: crash during iteration 2 (step 4 of 6). The hot
    # loop runs through the jitted block, here over spans of one
    # coordinate, so the fault is injected at the dispatch layer (the jit
    # cache means a fault inside pure_update would only fire while
    # tracing).
    coords = build_coordinates(data)
    cd_crash = CoordinateDescent(coords, TaskType.LOGISTIC_REGRESSION)
    original_update = cd_crash._fused_block_fn(1, 1, 2)  # perUser alone
    calls = {"n": 0}

    def failing_update(*args):
        calls["n"] += 1
        if calls["n"] == 2:  # second perUser update = step 4
            raise RuntimeError("injected fault")
        return original_update(*args)

    cd_crash._block_fns[1, 1, 2] = failing_update
    with pytest.raises(RuntimeError, match="injected fault"):
        cd_crash.run(num_iterations=3, seed=11, checkpoint_dir=tmp_path)
    # Steps 1..3 completed and were checkpointed before the crash.
    assert max(all_checkpoint_steps(tmp_path)) == 3

    # Fresh process-equivalent: new coordinates, resume from disk.
    cd_resume = CoordinateDescent(build_coordinates(data),
                                  TaskType.LOGISTIC_REGRESSION)
    resumed = cd_resume.run(num_iterations=3, seed=11,
                            checkpoint_dir=tmp_path)

    np.testing.assert_allclose(_final_coefs(resumed), _final_coefs(ref),
                               rtol=1e-6)
    assert len(resumed.objective_history) == len(ref.objective_history)
    np.testing.assert_allclose(resumed.objective_history,
                               ref.objective_history, rtol=1e-5)
    # Trackers are checkpointed too: pre-crash updates are not lost.
    assert len(resumed.trackers["fixed"]) == len(ref.trackers["fixed"])
    assert len(resumed.trackers["perUser"]) == len(ref.trackers["perUser"])


@pytest.mark.parametrize("case", [
    "saves between iteration boundaries", "the default interval",
    "a resume from inside an iteration"])
def test_spans_of_one_coordinate_repeat_the_whole_blocks(rng, tmp_path, case):
    """Saves that fall inside an iteration, and a resume that lands there,
    run the block over spans of one coordinate: no other program, each
    traced once, and the history of the uninterrupted run entry for entry,
    in step order, with one tracker entry an update."""
    data, *_ = make_glmix_data(rng, n=200)

    def descent():
        return CoordinateDescent(build_coordinates(data),
                                 TaskType.LOGISTIC_REGRESSION)

    cd_ref = descent()
    ref = cd_ref.run(num_iterations=3, seed=7)
    assert cd_ref.tracing_guard.counts() == {"block:3": 1}

    cd = descent()
    if case == "a resume from inside an iteration":
        # Steps 1..4 with one save, at step 3: the first coordinate of
        # the second iteration. Resumed with saves at iteration
        # boundaries: one span finishes that iteration, a whole block
        # runs the third.
        descent().run(num_iterations=2, seed=7, checkpoint_dir=tmp_path,
                      checkpoint_interval=3)
        assert all_checkpoint_steps(tmp_path) == [3]
        res = cd.run(num_iterations=3, seed=7, checkpoint_dir=tmp_path,
                     checkpoint_interval=2)
        programs = {"block:1:1-2", "block:1"}
        saved = [3, 4, 6]
    else:
        interval = 3 if "between" in case else 1
        res = cd.run(num_iterations=3, seed=7, checkpoint_dir=tmp_path,
                     checkpoint_interval=interval)
        programs = {"block:1:0-1", "block:1:1-2"}
        saved = [s for s in range(1, 7) if s % interval == 0]
    assert cd.tracing_guard.counts() == dict.fromkeys(programs, 1)
    # the newest two saves are kept
    assert sorted(all_checkpoint_steps(tmp_path)) == saved[-2:]

    assert len(res.objective_history) == len(ref.objective_history) == 6
    np.testing.assert_allclose(res.objective_history, ref.objective_history,
                               rtol=1e-5)
    np.testing.assert_allclose(_final_coefs(res), _final_coefs(ref),
                               rtol=1e-6)
    for name in ("fixed", "perUser"):
        assert len(res.trackers[name]) == len(ref.trackers[name]) == 3
    assert set(res.timings) == {"fixed", "perUser"}


def test_resume_rejects_mismatched_configuration(rng, tmp_path):
    data, *_ = make_glmix_data(rng, n=200)
    cd = CoordinateDescent(build_coordinates(data),
                           TaskType.LOGISTIC_REGRESSION)
    cd.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path)
    cd2 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    with pytest.raises(ValueError, match="different configuration"):
        cd2.run(num_iterations=1, seed=2, checkpoint_dir=tmp_path)
    with pytest.raises(ValueError, match="checkpoint_interval"):
        cd2.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
                checkpoint_interval=0)


def test_resume_survives_benign_tag_reordering(rng, tmp_path):
    """Checkpoint identity is a canonical hash: a mapping tag with a
    different insertion order is the SAME configuration and must resume;
    a changed updating sequence is a DIFFERENT one and must hard-error."""
    data, *_ = make_glmix_data(rng, n=200)
    tag = {"fixed": "10,1e-4,1.0,LBFGS,L2", "perUser": "5,1e-4,1.0,LBFGS,L2"}
    cd = CoordinateDescent(build_coordinates(data),
                           TaskType.LOGISTIC_REGRESSION)
    first = cd.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
                   checkpoint_tag=tag)

    reordered = dict(reversed(list(tag.items())))
    assert list(reordered) != list(tag)  # genuinely different insertion order
    cd2 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    second = cd2.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
                     checkpoint_tag=reordered)  # must NOT raise
    np.testing.assert_allclose(_final_coefs(second), _final_coefs(first),
                               rtol=1e-7)

    # Changed updating sequence (list order is semantic) still rejects.
    coords = build_coordinates(data)
    swapped = {k: coords[k] for k in reversed(list(coords))}
    cd3 = CoordinateDescent(swapped, TaskType.LOGISTIC_REGRESSION)
    with pytest.raises(ValueError, match="different configuration"):
        cd3.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
                checkpoint_tag=tag)

    # A semantically different tag value rejects too.
    changed = dict(tag, fixed="99,1e-4,1.0,TRON,L2")
    cd4 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    with pytest.raises(ValueError, match="different configuration"):
        cd4.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
                checkpoint_tag=changed)


def test_config_fingerprint_canonicalization():
    from photon_ml_tpu.utils.checkpoint import config_fingerprint

    a = {"x": 1, "y": {"b": 2, "a": 3}, "seq": ["f", "r"]}
    b = {"y": {"a": 3, "b": 2}, "seq": ["f", "r"], "x": 1}
    assert config_fingerprint(a) == config_fingerprint(b)
    # List order is semantic.
    c = dict(a, seq=["r", "f"])
    assert config_fingerprint(c) != config_fingerprint(a)


def test_legacy_string_tag_still_resumes(rng, tmp_path):
    """Checkpoints written when tags were flattened 'k=v;...' strings must
    resume under the equivalent mapping tag (and vice versa)."""
    from photon_ml_tpu.utils.checkpoint import meta_fingerprints

    tag_map = {"fixed": "10,1e-4,1.0,LBFGS,L2", "perUser": "5,..."}
    legacy = ";".join(f"{k}={v}" for k, v in sorted(tag_map.items()))
    old_meta = {"seed": 1, "coordinates": ["fixed", "perUser"],
                "taskType": "LOGISTIC_REGRESSION", "tag": legacy}
    new_meta = dict(old_meta, tag=tag_map)
    assert meta_fingerprints(old_meta) & meta_fingerprints(new_meta)

    # End-to-end: save under the legacy string, resume under the mapping.
    data, *_ = make_glmix_data(rng, n=200)
    cd = CoordinateDescent(build_coordinates(data),
                           TaskType.LOGISTIC_REGRESSION)
    cd.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
           checkpoint_tag=legacy)
    cd2 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    cd2.run(num_iterations=1, seed=1, checkpoint_dir=tmp_path,
            checkpoint_tag=tag_map)  # must NOT raise


@pytest.mark.slow
def test_resume_preserves_best_model_and_validation(rng, tmp_path):
    data, *_ = make_glmix_data(rng, n=300)
    vdata, *_ = make_glmix_data(rng, n=120)
    ev = [build_evaluator("AUC")]

    cd1 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION,
                            validation_data=vdata,
                            validation_evaluators=ev)
    cd1.run(num_iterations=1, seed=5, checkpoint_dir=tmp_path)

    # Continue to 2 iterations in a "new process".
    cd2 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION,
                            validation_data=vdata,
                            validation_evaluators=ev)
    res = cd2.run(num_iterations=2, seed=5, checkpoint_dir=tmp_path)
    assert len(res.validation_history) == 2
    assert res.best_model is not None and res.best_metric is not None
    # Resumed run skipped iteration 1's updates: only iteration 2 re-ran.
    assert len(res.objective_history) == 4  # history restored + appended


def test_completed_run_resume_is_noop(rng, tmp_path):
    data, *_ = make_glmix_data(rng, n=200)
    cd1 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    first = cd1.run(num_iterations=2, seed=3, checkpoint_dir=tmp_path)
    cd2 = CoordinateDescent(build_coordinates(data),
                            TaskType.LOGISTIC_REGRESSION)
    second = cd2.run(num_iterations=2, seed=3, checkpoint_dir=tmp_path)
    np.testing.assert_allclose(_final_coefs(second), _final_coefs(first),
                               rtol=1e-7)
    assert second.objective_history == first.objective_history


def test_estimator_checkpoint_plumbing(rng, tmp_path):
    from photon_ml_tpu.estimators.game_estimator import (
        FixedEffectSpec,
        GameEstimator,
    )
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
    )

    data, *_ = make_glmix_data(rng, n=200)
    spec = FixedEffectSpec(
        name="fixed", feature_shard_id="global",
        configs=[GLMOptimizationConfiguration(
            max_iterations=20, regularization_weight=1.0)])
    est = GameEstimator(task_type=TaskType.LOGISTIC_REGRESSION,
                        coordinate_specs=[spec], num_iterations=2)
    est.fit(data, checkpoint_dir=tmp_path)
    assert all_checkpoint_steps(tmp_path / "combo-0")
