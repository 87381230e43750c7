"""Distributed-runtime checks that need multiple (host) devices.

Executed in a subprocess by tests/test_distributed.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single-device view.  Usage: python host_mesh_checks.py <check>
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.checkpoint.checkpointer import Checkpointer  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.data.pipeline import RunaheadLoader, synthetic_batch  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.steps import (abstract_state, build_train_step,  # noqa
                                init_train_state, make_optimizer)
from repro.models import api  # noqa: E402
from repro.models.types import ShapeConfig  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.optim.compression import ErrorFeedback  # noqa: E402
from repro.runtime.elastic import reshard_state  # noqa: E402
from repro.runtime.fault_tolerance import (SimulatedFailure,  # noqa: E402
                                           StragglerWatchdog, TrainDriver)
from repro.sharding.rules import MeshRules  # noqa: E402

SHAPE = ShapeConfig("tiny_train", "train", seq_len=64, global_batch=8)
ARCH = "qwen2-1.5b"


def tiny_setup(mesh=None, arch=ARCH):
    cfg = registry.smoke(arch)
    mesh = mesh or make_host_mesh(2, 4)
    rules = MeshRules(mesh, sequence_parallel=False)
    built = build_train_step(cfg, SHAPE, rules)
    opt = make_optimizer(cfg)
    params = api.init_params(jax.random.key(0), cfg)
    state = adamw.init_state(params, opt)
    state = jax.device_put(state, rules.named(rules.state_specs(state)))
    batch_fn = lambda step: synthetic_batch(cfg, SHAPE, seed=7, step=step)
    return cfg, mesh, rules, built, state, batch_fn


def check_sharded_train_step_matches_single_device():
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    batch = batch_fn(0)
    new_state, metrics = built.fn(state, batch)
    dist_loss = float(metrics["loss"])
    # single-device reference
    params = api.init_params(jax.random.key(0), cfg)
    ref_loss = float(api.train_loss(params, jax.tree.map(jnp.asarray, batch), cfg))
    assert abs(dist_loss - ref_loss) / max(abs(ref_loss), 1e-6) < 5e-3, \
        (dist_loss, ref_loss)
    print("OK sharded==single", dist_loss, ref_loss)


def check_sharded_init_matches_device_put():
    """init_train_state creates the state already sharded; it must equal
    the single-device init placed with device_put, bit for bit and spec for
    spec."""
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    sharded = init_train_state(cfg, rules, jax.random.key(0))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(sharded)):
        assert a.sharding.spec == b.sharding.spec, (a.sharding, b.sharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK sharded init == device_put init")


def check_checkpoint_roundtrip():
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        state, _ = built.fn(state, batch_fn(0))
        ck.save(1, state, blocking=True)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            state)
        restored = ck.restore(1, abstract)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK checkpoint roundtrip")


def check_crash_resume_bitwise():
    with tempfile.TemporaryDirectory() as d:
        cfg, mesh, rules, built, state0, batch_fn = tiny_setup()
        ck = Checkpointer(d)
        driver = TrainDriver(built.fn, batch_fn, ck, checkpoint_every=3)
        # uninterrupted run
        ref_state, ref_hist = driver.run(state0, 8)
        # crashed run from a fresh copy of the same init
        _, _, _, _, state1, _ = tiny_setup(mesh)
        try:
            driver.run(state1, 8, fail_at=5)
            raise AssertionError("failure not raised")
        except SimulatedFailure:
            pass
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), ref_state)
        resumed_state, hist2 = driver.resume(abstract, 8)
        np.testing.assert_allclose(
            float(ref_hist[-1]["loss"]), float(hist2[-1]["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(ref_state),
                        jax.tree.leaves(resumed_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK crash->resume bitwise")


def check_elastic_reshard():
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    state, m1 = built.fn(state, batch_fn(0))
    loss_a = float(m1["loss"])
    # new mesh shape (as after losing/gaining hosts)
    mesh2 = make_host_mesh(4, 2)
    rules2 = MeshRules(mesh2, sequence_parallel=False)
    state2 = reshard_state(jax.tree.map(np.asarray, state), rules2)
    built2 = build_train_step(cfg, SHAPE, rules2)
    _, m2 = built2.fn(state2, batch_fn(1))
    assert np.isfinite(float(m2["loss"]))
    print("OK elastic reshard", loss_a, float(m2["loss"]))


def check_reshard_roundtrip():
    """Mesh A -> mesh B -> mesh A must be a bitwise no-op: resharding only
    moves bytes between devices, it never touches values, so an elastic
    downsize followed by a recovery to the original topology restores the
    exact state."""
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    state, _ = built.fn(state, batch_fn(0))
    rules2 = MeshRules(make_host_mesh(4, 2), sequence_parallel=False)
    state_b = reshard_state(state, rules2)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state_a2 = reshard_state(state_b, rules)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state_a2)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the round-tripped state lands back on the original shardings
    for orig, rt in zip(jax.tree.leaves(state), jax.tree.leaves(state_a2)):
        assert orig.sharding.spec == rt.sharding.spec, (orig.sharding,
                                                        rt.sharding)
    print("OK reshard roundtrip")


def check_grad_compression_convergence():
    cfg, mesh, rules, built, state, batch_fn = tiny_setup()
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=0,
                            moment_dtype=cfg.adam_dtype)
    ef = ErrorFeedback()
    params = api.init_params(jax.random.key(1), cfg)
    state = adamw.init_state(params, opt)
    residual = ef.init(params)
    losses = []
    batch = jax.tree.map(jnp.asarray, batch_fn(0))

    @jax.jit
    def step(state, residual):
        loss, grads = jax.value_and_grad(
            lambda p: api.train_loss(p, batch, cfg))(state["params"])
        deq, residual = ef.compress(grads, residual)
        state = adamw.apply_updates(state, deq, cfg=opt)
        return state, residual, loss

    for _ in range(12):
        state, residual, loss = step(state, residual)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses
    print("OK compression converges", losses[0], "->", losses[-1])


def check_straggler_watchdog():
    flagged = []
    wd = StragglerWatchdog(min_samples=4,
                           on_straggler=lambda s, t, m: flagged.append(s))
    for i in range(10):
        wd.record(i, 0.1)
    assert not flagged
    assert wd.record(10, 1.0)
    assert flagged == [10]
    print("OK watchdog")


def check_runahead_loader():
    import time
    seen = []
    def batch_fn(step):
        seen.append(step)
        return {"step": step}
    loader = RunaheadLoader(batch_fn, depth=3)
    b = loader.get(0)
    assert b["step"] == 0
    deadline = time.time() + 5            # async window: wait for prefetches
    while time.time() < deadline and len(set(seen)) < 4:
        time.sleep(0.01)
    assert set(seen) >= {0, 1, 2, 3}, sorted(set(seen))
    assert loader.get(1)["step"] == 1
    loader.close()
    print("OK runahead loader")


CHECKS = {name[len("check_"):]: fn
          for name, fn in list(globals().items())
          if name.startswith("check_")}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
