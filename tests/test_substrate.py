"""Substrate tests: checkpointing (atomic/elastic), fault-tolerant loop,
straggler policy, data pipeline."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ck
from repro.data import PrefetchLoader, synthetic_tabular
from repro.runtime import ResilientLoop, StragglerError, StragglerPolicy


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16) * 3,
                  "d": jnp.asarray(7, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    t = _tree()
    ck.save(d, 5, t)
    assert ck.latest_step(d) == 5
    out = ck.restore(d, 5, jax.eval_shape(lambda: t))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), t, out)


def test_checkpoint_gc_and_latest(tmp_path):
    d = str(tmp_path / "ck")
    for s in [1, 2, 3, 4, 5]:
        ck.save(d, s, _tree())
    assert ck.latest_step(d) == 5
    kept = sorted(os.listdir(d))
    assert len(kept) == 3          # gc keeps 3


def test_checkpoint_async(tmp_path):
    d = str(tmp_path / "ck")
    fut = ck.save_async(d, 9, _tree())
    fut.result(timeout=30)
    assert ck.latest_step(d) == 9


def test_resilient_loop_recovers(tmp_path):
    d = str(tmp_path / "ck")
    calls = {"n": 0, "fail_at": 7}
    saved = {}

    def step_fn(state, batch):
        calls["n"] += 1
        if calls["n"] == calls["fail_at"]:
            raise RuntimeError("simulated device failure")
        return state + 1

    def save_fn(step, state):
        saved["last"] = (step, state)
        ck.save(d, step, {"s": jnp.asarray(state)})

    def restore_fn():
        last = ck.latest_step(d)
        if last is None:
            return 0, 0
        return last, int(np.asarray(
            ck.restore(d, last, {"s": jax.ShapeDtypeStruct((), jnp.int32)})["s"]))

    loop = ResilientLoop(step_fn, save_fn, restore_fn, lambda s: None,
                         save_every=2, backoff=0.01)
    step, state = loop.run(0, 0, 10)
    assert step == 10 and loop.failures == 1
    assert state == 10               # replayed steps after restore


def test_straggler_policy_trips():
    p = StragglerPolicy(factor=2.0, tolerance=3)
    for _ in range(20):
        p.observe(1.0)
    with pytest.raises(StragglerError):
        for _ in range(5):
            p.observe(10.0)


def test_data_determinism_and_prefetch():
    def source(step):
        rng = np.random.default_rng((3, step))
        return {"x": rng.integers(0, 100, (2, 8), dtype=np.int32)}
    np.testing.assert_array_equal(source(5)["x"], source(5)["x"])
    loader = PrefetchLoader(source, depth=2)
    for step in range(3):      # batches arrive in step order
        np.testing.assert_array_equal(loader(step)["x"], source(step)["x"])
    loader.stop()


def test_synthetic_tabular_shapes():
    X, y = synthetic_tabular(100, 7, task="multi", n_classes=4, sparsity=0.5)
    assert X.shape == (100, 7) and set(np.unique(y)) <= {0, 1, 2, 3}
    assert (X == 0).mean() > 0.3
