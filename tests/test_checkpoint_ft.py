"""Checkpoint roundtrip, elastic resharding, fault-tolerant restart."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import save, restore, latest_step, CheckpointManager
from repro.distributed.fault_tolerance import (FTConfig, SimulatedFailure,
                                               run_training)
from repro.optim import adamw


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.bfloat16),
                       "c": [jnp.zeros((2, 2)), jnp.full((1,), 7.0)]}}


def test_save_restore_roundtrip():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        save(d, 3, t)
        assert latest_step(d) == 3
        out = restore(d, 3, jax.eval_shape(lambda: t))
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
            assert a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))


def test_async_save_and_retention():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, every=1, keep=2)
        for s in range(1, 6):
            mgr.maybe_save(s, t)
        mgr.wait()
        steps = sorted(int(x.split("_")[1]) for x in os.listdir(d)
                       if x.startswith("step_"))
        assert steps == [4, 5]


def test_restore_mismatched_shape_raises():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        save(d, 0, t)
        bad = dict(t)
        bad["a"] = jnp.zeros((4, 4))
        try:
            restore(d, 0, jax.eval_shape(lambda: bad))
            assert False, "should raise"
        except ValueError:
            pass


def test_ft_restart_resumes_and_converges():
    params = {"w": jnp.full((4,), 5.0)}
    opt = adamw.AdamWConfig(lr=0.2, warmup_steps=0, total_steps=60,
                            weight_decay=0.0)
    state = adamw.init_state(params)

    @jax.jit
    def step_fn(state, batch):
        loss, g = jax.value_and_grad(
            lambda p: jnp.sum((p["w"] - batch["t"]) ** 2))(state.params)
        return adamw.apply_update(opt, state, g), {"loss": loss}

    def batches():
        while True:
            yield {"t": jnp.zeros((4,))}

    fails = {9, 23}

    def injector(step):
        if step in fails:
            fails.discard(step)
            raise SimulatedFailure()

    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d, every=5, keep=3)
        state, report = run_training(step_fn, state, batches(), ckpt, 40,
                                     FTConfig(ckpt_every=5),
                                     fail_injector=injector)
    assert report["restarts"] == 2
    assert int(state.step) == 40
    assert abs(float(state.params["w"][0])) < 1.0  # converged toward 0


def test_elastic_restore_across_meshes():
    """Checkpoint written from one sharding restores onto another mesh size.

    (Single real device here: shardings on 1-device meshes with different
    axis splits exercise the device_put resharding path.)"""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    t = {"w": jnp.arange(16, dtype=jnp.float32)}
    m1 = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with tempfile.TemporaryDirectory() as d:
        save(d, 0, jax.device_put(t, NamedSharding(m1, P())))
        sh = {"w": NamedSharding(m1, P("data"))}
        out = restore(d, 0, jax.eval_shape(lambda: t), shardings=sh)
        np.testing.assert_allclose(np.asarray(out["w"]), np.arange(16))
        assert out["w"].sharding.spec == P("data")
