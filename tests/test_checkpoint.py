"""Checkpoint: atomic roundtrip, latest-step discovery, async, reshard."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step, load_checkpoint,
                              save_checkpoint)
from repro.distributed.sharding import make_mesh


def _tree():
    return {"params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                       "b": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 7, _tree())
    assert latest_step(d) == 7
    out = load_checkpoint(d, 7, jax.eval_shape(_tree))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(_tree()["params"]["w"]))
    assert out["params"]["b"].dtype == jnp.bfloat16
    assert int(out["step"]) == 7


def test_latest_step_and_gc(tmp_path):
    d = str(tmp_path)
    assert latest_step(d) is None
    for s in (1, 5, 3):
        save_checkpoint(d, s, _tree())
    assert latest_step(d) == 5


def test_atomicity_no_partial_dirs(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    # simulate a crashed save: stale tmp dir must be ignored and removed
    os.makedirs(os.path.join(d, "step_9.tmp-deadbeef"))
    assert latest_step(d) == 1
    save_checkpoint(d, 2, _tree())
    assert not any(".tmp-" in p for p in os.listdir(d))


def test_missing_leaf_raises(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, {"a": jnp.zeros(3)})
    with pytest.raises(KeyError):
        load_checkpoint(d, 1, jax.eval_shape(lambda: {"b": jnp.zeros(3)}))


def test_shape_mismatch_raises(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        load_checkpoint(d, 1, jax.eval_shape(lambda: {"a": jnp.zeros(4)}))


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    ck = AsyncCheckpointer(d, keep=2)
    for s in range(1, 5):
        ck.save(s, _tree())
    ck.close()
    assert latest_step(d) == 4
    steps = sorted(int(p.split("_")[1]) for p in os.listdir(d))
    assert len(steps) <= 2          # gc keeps the last 2


def test_elastic_reshard_load(tmp_path):
    """Checkpoint written under one sharding loads under another (here:
    single-device target with explicit sharding objects)."""
    d = str(tmp_path)
    mesh = make_mesh((1,), ("data",))
    sharding = jax.sharding.NamedSharding(mesh,
                                          jax.sharding.PartitionSpec("data"))
    tree = {"w": jax.device_put(jnp.arange(8, dtype=jnp.float32), sharding)}
    save_checkpoint(d, 3, tree)
    target = {"w": jax.ShapeDtypeStruct((8,), jnp.float32,
                                        sharding=sharding)}
    out = load_checkpoint(d, 3, target)
    assert out["w"].sharding == sharding
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8))


def test_train_resume_equivalence(tmp_path):
    """Stopping and resuming from a checkpoint reproduces the un-interrupted
    run exactly (deterministic step-indexed data + saved state)."""
    from repro.launch import steps as steps_lib
    from repro.data.synthetic import TokenStream
    from repro.models.config import ArchConfig
    from repro.optim import make_optimizer
    from repro.optim.schedules import ScheduleConfig, make_schedule

    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32")
    opt = make_optimizer("adamw")
    sched = make_schedule(ScheduleConfig(kind="constant", lr=1e-3))
    step_fn = jax.jit(steps_lib.make_train_step(cfg, opt, sched))
    stream = TokenStream(vocab=64, seq_len=16, global_batch=2)

    state = steps_lib.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    # run 4 steps straight
    s_straight = state
    for t in range(4):
        s_straight, _ = step_fn(s_straight, stream.batch_at(jnp.int32(t)))

    # run 2 steps, checkpoint, "crash", restore, run 2 more
    s = state
    for t in range(2):
        s, _ = step_fn(s, stream.batch_at(jnp.int32(t)))
    save_checkpoint(str(tmp_path), 2, s)
    restored = load_checkpoint(str(tmp_path), 2, jax.eval_shape(lambda: s))
    for t in range(2, 4):
        restored, _ = step_fn(restored, stream.batch_at(jnp.int32(t)))

    for a, b in zip(jax.tree.leaves(s_straight.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


# --------------------------------------------- KPCA / Nyström state trees ---
def test_nystrom_state_roundtrip(tmp_path):
    """NystromState (nested KPCAState + Knm + grow-rows Xrows) survives the
    npz store bit-exactly, both row regimes."""
    from repro.core import kernels_fn as kf, nystrom

    rng = np.random.default_rng(4)
    X = rng.normal(size=(20, 3))
    spec = kf.KernelSpec(name="rbf", sigma=4.0)
    for grow in (False, True):
        if grow:
            state = nystrom.init_nystrom(None, jnp.asarray(X[:4]),
                                         capacity=8, spec=spec,
                                         dtype=jnp.float64, grow_rows=True)
            state = nystrom.observe_rows(state, jnp.asarray(X[4:]), spec)
            state = nystrom.add_landmark(state, None, jnp.asarray(X[5]),
                                         spec)
        else:
            state = nystrom.init_nystrom(jnp.asarray(X), jnp.asarray(X[:4]),
                                         capacity=8, spec=spec,
                                         dtype=jnp.float64)
            state = nystrom.add_landmark(state, jnp.asarray(X),
                                         jnp.asarray(X[5]), spec)
        d = str(tmp_path / f"grow_{grow}")
        save_checkpoint(d, 1, state)
        out = load_checkpoint(d, 1, jax.eval_shape(lambda: state))
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(
            np.asarray(nystrom.reconstruct_tilde(out)),
            np.asarray(nystrom.reconstruct_tilde(state)), atol=0)


def test_windowed_kpca_midwindow_resume_equivalence(tmp_path):
    """Save a SLIDING-WINDOW stream mid-window (evictions already past),
    restore into a fresh process-alike stream, continue: the result must
    equal the uninterrupted windowed run exactly.  This is what the FIFO
    ring being IN the state (window.WindowState.ages/clock) buys — the
    eviction order is checkpoint state, not host bookkeeping."""
    from repro.core import inkpca, kernels_fn as kf

    rng = np.random.default_rng(21)
    X = rng.normal(size=(30, 4))
    spec = kf.KernelSpec(name="rbf", sigma=5.0)

    def make_stream():
        return inkpca.KPCAStream(jnp.asarray(X[:4]), 16, spec,
                                 adjusted=True, dtype=jnp.float64,
                                 dispatch="bucketed", min_bucket=8,
                                 window=8)

    straight = make_stream()
    for i in range(4, 30):
        straight.update(jnp.asarray(X[i]))

    part = make_stream()
    for i in range(4, 18):                      # window full, 6 evictions
        part.update(jnp.asarray(X[i]))
    save_checkpoint(str(tmp_path), 18, part.state)

    resumed = make_stream()                     # "crash": fresh stream
    resumed.state = load_checkpoint(str(tmp_path), 18,
                                    jax.eval_shape(lambda: part.state))
    assert int(resumed.state.clock) == 18
    for i in range(18, 30):
        resumed.update(jnp.asarray(X[i]))

    for a, b in zip(jax.tree.leaves(straight.state),
                    jax.tree.leaves(resumed.state)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-12)


def test_replaced_landmark_nystrom_resume_equivalence(tmp_path):
    """Save a NystromState right after a replace_landmark, restore,
    continue the lifecycle (observe + add + replace): equals the
    uninterrupted run bit-for-bit at save and to rounding afterwards."""
    from repro.core import engine as eng, kernels_fn as kf, nystrom

    rng = np.random.default_rng(33)
    X = rng.normal(size=(26, 3))
    spec = kf.KernelSpec(name="rbf", sigma=4.0)
    engine = eng.Engine(spec, eng.UpdatePlan(dispatch="bucketed",
                                             min_bucket=8), adjusted=False)

    def grow():
        st = nystrom.init_nystrom(None, jnp.asarray(X[:4]), capacity=16,
                                  spec=spec, dtype=jnp.float64,
                                  grow_rows=True)
        st = nystrom.observe_rows(st, jnp.asarray(X[4:20]), spec)
        for i in range(4, 10):
            st = engine.add_landmark(st, None, jnp.asarray(X[i]))
        return engine.replace_landmark(st, None, 2, jnp.asarray(X[15]))

    def continue_lifecycle(st):
        st = nystrom.observe_rows(st, jnp.asarray(X[20:]), spec)
        st = engine.add_landmark(st, None, jnp.asarray(X[21]))
        return engine.replace_landmark(st, None, 0, jnp.asarray(X[22]))

    state = grow()
    save_checkpoint(str(tmp_path), 1, state)
    restored = load_checkpoint(str(tmp_path), 1,
                               jax.eval_shape(lambda: state))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    straight = continue_lifecycle(state)
    resumed = continue_lifecycle(restored)
    np.testing.assert_allclose(
        np.asarray(nystrom.reconstruct_tilde(resumed)),
        np.asarray(nystrom.reconstruct_tilde(straight)), atol=0)


def test_bucketed_kpca_midstream_resume_equivalence(tmp_path):
    """Save a bucketed stream mid-bucket (m inside M_b), restore, continue:
    the result must match the uninterrupted bucketed run exactly, bucket
    crossings included."""
    from repro.core import inkpca, kernels_fn as kf

    rng = np.random.default_rng(9)
    X = rng.normal(size=(26, 4))
    spec = kf.KernelSpec(name="rbf", sigma=5.0)

    def make_stream():
        return inkpca.KPCAStream(jnp.asarray(X[:4]), 32, spec,
                                 adjusted=True, dtype=jnp.float64,
                                 dispatch="bucketed", min_bucket=8)

    straight = make_stream()
    straight.update_block(jnp.asarray(X[4:]))

    part = make_stream()
    part.update_block(jnp.asarray(X[4:14]))     # m=14, inside bucket 16
    save_checkpoint(str(tmp_path), 14, part.state)

    resumed = make_stream()                     # "crash": fresh process
    resumed.state = load_checkpoint(str(tmp_path), 14,
                                    jax.eval_shape(lambda: part.state))
    assert int(resumed.state.m) == 14
    resumed.update_block(jnp.asarray(X[14:]))   # crosses bucket 16 -> 32

    assert int(resumed.state.m) == int(straight.state.m) == 26
    for a, b in zip(jax.tree.leaves(straight.state),
                    jax.tree.leaves(resumed.state)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-12)


def test_windowed_kpca_midblock_resume_equivalence(tmp_path):
    """Save a windowed stream BETWEEN window_block calls (steady state,
    scanned evict+ingest already past), restore, continue with more
    blocks: equals the uninterrupted blocked run — the scanned path
    keeps the arrival ring checkpoint-portable exactly like the
    per-point path (ISSUE satellite)."""
    from repro.core import inkpca, kernels_fn as kf

    rng = np.random.default_rng(27)
    X = rng.normal(size=(36, 4))
    spec = kf.KernelSpec(name="rbf", sigma=5.0)

    def make_stream():
        return inkpca.KPCAStream(jnp.asarray(X[:4]), 16, spec,
                                 adjusted=True, dtype=jnp.float64,
                                 dispatch="bucketed", min_bucket=8,
                                 window=8)

    straight = make_stream()
    straight.update_block(jnp.asarray(X[4:20]))     # growth + steady scan
    straight.update_block(jnp.asarray(X[20:36]))

    part = make_stream()
    part.update_block(jnp.asarray(X[4:20]))
    save_checkpoint(str(tmp_path), 20, part.state)

    resumed = make_stream()                          # "crash": fresh stream
    resumed.state = load_checkpoint(str(tmp_path), 20,
                                    jax.eval_shape(lambda: part.state))
    assert int(resumed.state.clock) == 20
    resumed.update_block(jnp.asarray(X[20:36]))

    for a, b in zip(jax.tree.leaves(straight.state),
                    jax.tree.leaves(resumed.state)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-12)
