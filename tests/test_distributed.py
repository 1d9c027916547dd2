"""Distributed machinery: elastic rescaling (in a multi-device subprocess),
DRP shrink, vmap-clustering correctness, trainer+compression interplay."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DeviceExecutorPool, DRPConfig, Engine, FalkonConfig,
                        FalkonService, RealClock, SimClock)
from repro.core.engine import FalkonProvider
from repro.distributed.elastic import ElasticPolicy


def test_elastic_policy_decisions():
    p = ElasticPolicy(min_dp=1, max_dp=16)
    assert p.decide(4, backlog=10.0, step_time=1.0) == 8     # grow
    assert p.decide(4, backlog=0.1, step_time=1.0) == 2      # shrink
    assert p.decide(4, backlog=1.0, step_time=1.0) == 4      # hold
    assert p.decide(16, backlog=100.0, step_time=1.0) == 16  # capped


def test_elastic_reshard_subprocess():
    """Reshard a param tree from a 2-wide to a 4-wide DP mesh (8 fake
    devices) and verify values survive."""
    code = """
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.elastic import make_mesh_for_dp, reshard_tree
from repro.models.params import ParamDesc
descs = {"w": ParamDesc((8, 16), ("batch", None))}
tree = {"w": jnp.arange(128, dtype=jnp.float32).reshape(8, 16)}
m2 = make_mesh_for_dp(2)
t2 = reshard_tree(tree, descs, m2)
m4 = make_mesh_for_dp(4)
t4 = reshard_tree(t2, descs, m4)
np.testing.assert_array_equal(np.asarray(t4["w"]), np.asarray(tree["w"]))
print("OK", t4["w"].sharding)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, cwd="/root/repo")
    assert "OK" in out.stdout, out.stderr[-2000:]


def test_drp_shrinks_idle_executors():
    clock = SimClock()
    svc = FalkonService(clock, FalkonConfig(drp=DRPConfig(
        max_executors=8, alloc_latency=0.0, idle_timeout=10.0,
        min_executors=1)))
    eng = Engine(clock)
    eng.add_site("f", FalkonProvider(svc), capacity=8)
    outs = [eng.submit(f"t{i}", None, duration=1.0) for i in range(16)]
    eng.run()
    assert all(o.resolved for o in outs)
    n_busy_peak = len(svc.executors)
    assert n_busy_peak >= 2
    # after a long idle gap, a single late task's completion triggers the
    # idle-timeout de-registration sweep (paper: idle auto-deregistration)
    late = []
    clock.schedule(100.0, lambda: late.append(
        eng.submit("late", None, duration=1.0)))
    eng.run()
    assert late and late[0].resolved
    assert len(svc.executors) < n_busy_peak  # idles de-registered


def device_engine(executors=64, max_bundle=64):
    """Engine -> Falkon -> `DeviceExecutorPool`, provisioned so that one
    scheduler pump dispatches every ready task."""
    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=max_bundle)
    svc = FalkonService(clock, FalkonConfig(drp=DRPConfig(
        min_executors=executors, max_executors=executors,
        alloc_latency=0.0, alloc_chunk=executors)), pool=pool)
    svc.provision(executors)
    eng = Engine(clock)
    eng.add_site("d", FalkonProvider(svc), capacity=executors)
    return eng, svc, pool


def test_vmap_clustering_results_match_per_task():
    eng_c, svc, pool = device_engine()

    def f(x, w):
        return jnp.tanh(x @ w).sum()

    xs = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (16, 8, 8)))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    outs = [eng_c.submit(f"t{i}", f, [xs[i], w], vmap_key="k")
            for i in range(16)]
    eng_c.run()
    svc.shutdown()
    got = np.array([float(o.get()) for o in outs])
    exp = np.array([float(f(jnp.asarray(xs[i]), w)) for i in range(16)])
    np.testing.assert_allclose(got, exp, rtol=1e-5)
    assert pool.bundles_run == 1 and pool.fused_tasks == 16  # fused


def test_vmap_clustering_mixed_signatures_separate_bundles():
    eng, svc, pool = device_engine()

    def f(x):
        return x * 2

    a = [eng.submit(f"a{i}", f, [jnp.ones((4,))], vmap_key="a")
         for i in range(4)]
    b = [eng.submit(f"b{i}", f, [jnp.ones((8,))], vmap_key="b")
         for i in range(4)]
    eng.run()
    svc.shutdown()
    assert all(o.resolved for o in a + b)
    assert pool.bundles_run == 2  # one bundle per signature


def test_grad_compression_in_training_loop():
    """Simulated cross-pod sync: train with error-feedback int8-compressed
    gradients and verify the loss still decreases on a quadratic."""
    from repro.optim import adamw, compression
    hp = adamw.Hyper(lr=0.05, warmup=0, weight_decay=0.0, clip=1e9,
                     total_steps=300, min_lr_frac=1.0)
    params = {"w": jnp.array([4.0, -2.0, 7.0])}
    opt = adamw.init(params)
    target = jnp.array([1.0, 2.0, 3.0])
    residual = compression.init_residual(params)
    for step in range(300):
        grads = {"w": params["w"] - target}
        _, residual, grads = compression.compress_with_feedback(
            grads, residual, scheme="int8")
        params, opt = adamw.update(grads, opt, params, jnp.asarray(step), hp)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)
