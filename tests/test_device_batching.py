"""Device-batched execution (DESIGN.md §11): the DeviceExecutorPool seam,
vmap-signature bundling, HLO-priced scheduling, and their composition with
staging, streaming, DRP, and the duration-aware balancer.

Covers the acceptance surface of the device-batching PR:
  * bundles fuse into one vmapped call with per-task results identical to
    per-task execution (and measured stats attributed per task);
  * signature keying is structural (shapes/dtypes) and GC-safe (stable
    callable keys, not raw ids);
  * non-batchable tasks, fault-check failures, real staging, streaming
    `foreach(window=)`, and DRP autoscaling all compose unchanged;
  * `DurationPredictor` prices tasks without device work, caches by
    signature, and drives identical scheduling decisions in simulated and
    real runs of the same program.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DRPConfig, DataLayer, DeviceExecutorPool,
                        Engine, FalkonConfig, FalkonProvider, FalkonService,
                        RealClock, SharedStore, SimClock, Tracer, Workflow)
from repro.core.clustering import VmapClusteringProvider, vmap_signature
from repro.core.sites import LoadBalancer, Site
from repro.core.task import FnKeyRegistry, stable_fn_key
from repro.launch.hlo_cost import DeviceModel, DurationPredictor

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def device_stack(clock, executors=16, max_bundle=16, data_layer=None,
                 alloc_latency=0.0, predictor=None):
    """Engine + Falkon service backed by a DeviceExecutorPool, with DRP
    pre-sized so one scheduler pump dispatches the whole ready set (the
    bundling-friendly configuration the benchmark uses)."""
    pool = DeviceExecutorPool(clock, max_bundle=max_bundle)
    cfg = FalkonConfig(drp=DRPConfig(
        min_executors=executors, max_executors=executors,
        alloc_latency=alloc_latency, alloc_chunk=executors))
    svc = FalkonService(clock, cfg, data_layer=data_layer, pool=pool)
    svc.provision(executors)
    eng = Engine(clock, duration_predictor=predictor)
    eng.add_site("dev", FalkonProvider(svc), capacity=executors)
    return eng, svc, pool


def body(x, w):
    return jnp.sum(jnp.tanh(x @ w), axis=-1) + x


# ---------------------------------------------------------------------------
# fusion correctness
# ---------------------------------------------------------------------------


def test_bundle_results_match_per_task_reference():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=32, max_bundle=32)
    w = np.asarray(np.random.default_rng(0).normal(size=(8, 8)), np.float32)
    xs = np.asarray(np.random.default_rng(1).normal(size=(24, 8)), np.float32)
    futs = [eng.submit(f"t{i}", body, [xs[i], w], vmap_key="b")
            for i in range(24)]
    eng.run()
    svc.shutdown()
    for i, f in enumerate(futs):
        np.testing.assert_allclose(np.asarray(f.get()),
                                   np.asarray(body(jnp.asarray(xs[i]),
                                                   jnp.asarray(w))),
                                   # the vmapped dot sums in another order
                                   rtol=1e-5, atol=1e-6)
    # actually fused: far fewer device calls than tasks, and every task
    # went through the vmapped path
    assert pool.tasks_run == 24
    assert pool.fused_tasks == 24
    assert pool.fallbacks == 0
    assert pool.bundles_run == 1
    # exact counters: one bundle of all 24, its measured dispatcher time,
    # no staging (no data layer)
    m = svc.metrics()["pool"]      # surfaced by the service on this path
    assert m["bundle_mean"] == 24.0
    assert m["device_s"] == pool.device_s > 0.0
    assert m["io_s"] == pool.io_s == 0.0
    assert m["fused_tasks"] == 24


def test_mixed_signatures_form_separate_bundles():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)

    def f(x):
        return x * 2.0

    # same vmap_key, different shapes: the structural signature must split
    # them into two bundles instead of failing the stack at execution time
    a = [eng.submit(f"a{i}", f, [np.ones((4,), np.float32)], vmap_key="k")
         for i in range(4)]
    b = [eng.submit(f"b{i}", f, [np.ones((8,), np.float32)], vmap_key="k")
         for i in range(4)]
    eng.run()
    svc.shutdown()
    assert all(np.asarray(x.get()).shape == (4,) for x in a)
    assert all(np.asarray(x.get()).shape == (8,) for x in b)
    assert pool.bundles_run == 2
    assert pool.fused_tasks == 8


def test_non_batchable_tasks_run_as_measured_singletons():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=4)
    # no vmap_key: ordinary python body through the same pool
    futs = [eng.submit(f"p{i}", lambda i=i: i * 10) for i in range(3)]
    eng.run()
    svc.shutdown()
    assert [f.get() for f in futs] == [0, 10, 20]
    assert pool.tasks_run == 3
    assert pool.fused_tasks == 0
    # each ran alone, measured: three bundles of one
    assert pool.bundles_run == 3
    assert pool.metrics()["bundle_mean"] == 1.0
    assert pool.device_s > 0.0


def branchy(x):
    # Python control flow on a value: runs per task, refuses jit(vmap)
    return x * 2.0 if float(x.sum()) > 0 else -x


def test_failed_fused_call_degrades_per_task_and_is_counted():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=8, max_bundle=8)
    xs = [np.full((4,), i - 3.5, np.float32) for i in range(8)]
    futs = [eng.submit(f"t{i}", branchy, [xs[i]], vmap_key="b")
            for i in range(8)]
    eng.run()
    svc.shutdown()
    for x, f in zip(xs, futs):
        np.testing.assert_array_equal(np.asarray(f.get()),
                                      np.asarray(branchy(jnp.asarray(x))))
    # every task still completed, but the degrade is counted, not hidden
    assert pool.fused_tasks == 0
    assert pool.fallbacks == pool.bundles_run >= 1
    assert "Tracer" in pool.last_fallback
    assert svc.metrics()["pool"]["fallbacks"] == pool.fallbacks


def test_vmap_provider_counts_failed_fused_calls():
    eng = Engine(SimClock())
    prov = VmapClusteringProvider(eng.clock, max_bundle=64)
    eng.add_site("d", prov, capacity=8)
    futs = [eng.submit(f"t{i}", branchy, [np.full((4,), i - 1.5, np.float32)],
                       vmap_key="b") for i in range(4)]
    eng.run()
    assert all(f.resolved for f in futs)
    assert prov.fused_tasks == 0
    assert prov.metrics()["fallbacks"] == prov.bundles_executed == 1


def test_fault_check_fails_one_task_not_the_bundle():
    from repro.core import FaultInjector, RetryPolicy, TaskFailure
    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=16)
    cfg = FalkonConfig(drp=DRPConfig(min_executors=8, max_executors=8,
                                     alloc_latency=0.0, alloc_chunk=8))
    svc = FalkonService(clock, cfg, pool=pool)
    svc.provision(8)
    inj = FaultInjector().fail_first_n("t2", 1)
    eng = Engine(clock, fault_injector=inj,
                 retry_policy=RetryPolicy(max_retries=0))
    eng.add_site("dev", FalkonProvider(svc), capacity=8)
    futs = [eng.submit(f"t{i}", body,
                       [np.ones((4,), np.float32) * i,
                        np.ones((4, 4), np.float32)], vmap_key="b")
            for i in range(6)]
    eng.run()
    svc.shutdown()
    for i, f in enumerate(futs):
        if i == 2:
            with pytest.raises(TaskFailure):
                f.get()
        else:
            assert f.resolved
    # the failing task was excluded from the batch, the rest still fused
    assert pool.fused_tasks == 5


def test_max_bundle_caps_fuse_width():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=32, max_bundle=4)
    # each fused bundle emits one `bundle_fused` event valued at its size
    pool.tracer = Tracer()
    futs = [eng.submit(f"t{i}", body,
                       [np.ones((4,), np.float32),
                        np.ones((4, 4), np.float32)], vmap_key="b")
            for i in range(12)]
    eng.run()
    svc.shutdown()
    assert all(f.resolved for f in futs)
    assert max(v for _t, v in pool.tracer.events["bundle_fused"]) <= 4
    assert pool.bundles_run >= 3


# ---------------------------------------------------------------------------
# output placement: fused edges stay on the device (DESIGN.md §11)
# ---------------------------------------------------------------------------


def step(x):
    return jnp.tanh(x * 1.5) + 0.25


def np_step(x):
    # an unbatched (numpy) body: it reads host or device values alike
    return np.tanh(np.asarray(x) * 1.5) + 0.25


def assert_placement_counted(pool):
    m = pool.metrics()
    assert m["outputs_on_device"] + m["outputs_to_host"] == m["fused_tasks"]


def test_fused_chain_keeps_edges_on_device_and_answers_on_host():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    xs = [np.full((4, 8), 0.1 * i - 0.3, np.float32) for i in range(6)]
    s1 = [eng.submit(f"a{i}", step, [x], vmap_key="a")
          for i, x in enumerate(xs)]
    s2 = [eng.submit(f"b{i}", step, [f], vmap_key="b")
          for i, f in enumerate(s1)]
    s3 = [eng.submit(f"c{i}", step, [f], vmap_key="c")
          for i, f in enumerate(s2)]
    eng.run()
    svc.shutdown()
    # the two edges between fused stages are device arrays, the finals
    # (read by no task) come back to the host
    assert all(isinstance(f.get(), jax.Array) for f in s1 + s2)
    assert all(type(f.get()) is np.ndarray for f in s3)
    for x, f in zip(xs, s3):
        want = step(step(step(jnp.asarray(x))))
        np.testing.assert_allclose(f.get(), np.asarray(want), rtol=1e-6)
    assert pool.bundles_run == 3 and pool.fused_tasks == 18
    assert pool.outputs_on_device == 12 and pool.outputs_to_host == 6
    assert_placement_counted(pool)


@pytest.mark.parametrize("why", ["unbatched_reader", "durable", "journaled"])
def test_fused_outputs_go_to_host_unless_every_reader_is_fused(tmp_path,
                                                                 why):
    from repro.core.jobstore import JobStore
    from repro.core.restart_log import RestartLog

    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    store = None
    if why == "durable":
        eng.restart_log = RestartLog(str(tmp_path / "restart.log"))
    elif why == "journaled":
        store = JobStore(str(tmp_path / "jobs.db"))
        eng.journal = store.journal(default_wf="placement")
    xs = [np.full((4, 8), 0.2 * i, np.float32) for i in range(4)]
    firsts = [eng.submit(f"a{i}", step, [x], vmap_key="a",
                         durable=why == "durable")
              for i, x in enumerate(xs)]
    if why == "unbatched_reader":
        # one unbatched reader is enough to send the value to the host
        eng.submit("join", lambda *v: sum(np_step(a) for a in v), firsts)
    finals = [eng.submit(f"b{i}", step, [f], vmap_key="b")
              for i, f in enumerate(firsts)]
    eng.run()
    svc.shutdown()
    if store is not None:
        store.close()
    assert all(type(f.get()) is np.ndarray for f in firsts + finals)
    for x, f in zip(xs, finals):
        np.testing.assert_allclose(
            f.get(), np.asarray(step(step(jnp.asarray(x)))), rtol=1e-6)
    assert pool.fused_tasks == 8 and pool.outputs_on_device == 0
    assert_placement_counted(pool)


@pytest.mark.parametrize("late", ["fused", "unbatched"])
def test_consumer_registered_after_the_ship_gets_the_right_value(late):
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    xs = [np.full((4, 8), 0.3 * i - 0.5, np.float32) for i in range(4)]
    firsts = [eng.submit(f"a{i}", step, [x], vmap_key="a")
              for i, x in enumerate(xs)]
    # only the first output has a (fused) reader when its bundle ships
    early = eng.submit("early", step, [firsts[0]], vmap_key="b")
    eng.run()
    # readers that register after the ship: they get the values as placed
    if late == "fused":
        later = [eng.submit(f"b{i}", step, [f], vmap_key="b")
                 for i, f in enumerate(firsts)]
    else:
        later = [eng.submit(f"n{i}", np_step, [f])
                 for i, f in enumerate(firsts)]
    eng.run()
    svc.shutdown()
    assert isinstance(firsts[0].get(), jax.Array)
    assert all(type(f.get()) is np.ndarray for f in firsts[1:])
    for x, f in zip(xs, later):
        np.testing.assert_allclose(
            np.asarray(f.get()), np.asarray(step(step(jnp.asarray(x)))),
            rtol=1e-6)
    np.testing.assert_allclose(np.asarray(early.get()),
                               np.asarray(later[0].get()), rtol=1e-6)
    assert pool.outputs_on_device == 1
    assert_placement_counted(pool)


# ---------------------------------------------------------------------------
# composition: staging, streaming, DRP
# ---------------------------------------------------------------------------


def test_real_staging_composes_with_bundling():
    clock = RealClock()
    store = SharedStore()
    payloads = {f"in{i}": np.full((16,), float(i), np.float32)
                for i in range(8)}
    objs = {name: store.put(name, arr.tobytes())
            for name, arr in payloads.items()}
    dl = DataLayer(store, cache_capacity=1e6)
    eng, svc, pool = device_stack(clock, executors=8, max_bundle=8,
                                  data_layer=dl)
    futs = [eng.submit(f"t{i}", body,
                       [payloads[f"in{i}"], np.eye(16, dtype=np.float32)],
                       vmap_key="b", inputs=(objs[f"in{i}"],))
            for i in range(8)]
    eng.run()
    svc.shutdown()
    assert all(f.resolved for f in futs)
    # staging ran through the pool's measured io path, for every task
    assert pool.tasks_run == 8
    assert pool.io_s > 0.0
    assert svc.metrics()["pool"]["io_s"] == pool.io_s
    assert pool.fused_tasks > 0


def test_foreach_window_streams_through_device_pool():
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=8, max_bundle=8)
    wf = Workflow("stream", eng)
    step = wf.atomic(lambda x: jnp.sum(x * 2.0), name="step", vmap_key="s")
    total = wf.foreach((np.full((4,), i, np.float32) for i in range(40)),
                       step, window=16,
                       reduce=lambda acc, v: acc + float(v), init=0.0)
    eng.run()
    svc.shutdown()
    assert total.get() == pytest.approx(sum(8.0 * i for i in range(40)))
    assert pool.tasks_run == 40
    assert pool.fused_tasks > 0


def test_drp_autoscaling_composes_with_device_pool():
    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=8)
    # start from zero executors with a real (small) allocation latency:
    # the pool is fixed-size (autoscale False), so DRP only grows the
    # logical executor set and never resizes the pool
    cfg = FalkonConfig(drp=DRPConfig(max_executors=8, alloc_latency=0.01,
                                     alloc_chunk=4))
    svc = FalkonService(clock, cfg, pool=pool)
    eng = Engine(clock)
    eng.add_site("dev", FalkonProvider(svc), capacity=8)
    futs = [eng.submit(f"t{i}", body,
                       [np.ones((4,), np.float32),
                        np.ones((4, 4), np.float32)], vmap_key="b")
            for i in range(16)]
    eng.run()
    assert pool.size() == 1          # dispatcher count untouched by DRP
    svc.shutdown()
    assert all(f.resolved for f in futs)
    assert len(svc.executors) > 0
    assert pool.tasks_run == 16


# ---------------------------------------------------------------------------
# prediction: pricing compute before running it
# ---------------------------------------------------------------------------


def test_predictor_fills_task_duration_via_engine():
    pred = DurationPredictor(device=DeviceModel(launch_overhead=0.25))
    eng = Engine(SimClock(), duration_predictor=pred)
    eng.local_site(concurrency=1)
    futs = [eng.submit(f"t{i}", body,
                       [np.ones((8,), np.float32),
                        np.ones((8, 8), np.float32)])
            for i in range(4)]
    eng.run()
    assert all(f.resolved for f in futs)
    # one host compile for the shared signature, then cache hits; the
    # predicted duration is the simulated service time, so four serial
    # tasks advance the sim clock by at least 4x the launch floor
    assert pred.compiles == 1
    assert pred.hits == 3
    assert eng.clock.now() >= 4 * 0.25


def test_predictor_caches_unpredictable_bodies_as_none():
    pred = DurationPredictor()

    def untraceable(xs):
        return sorted(xs)

    assert pred.predict_duration(untraceable, [[3, 1, 2]]) is None
    assert pred.predict_duration(untraceable, [[3, 1, 2]]) is None
    assert pred.compiles == 1        # the failure was cached, not retried
    assert pred.hits == 1


def test_duration_aware_balancer_prices_outstanding_work():
    s1 = Site("a", provider=None, capacity=4)
    s2 = Site("b", provider=None, capacity=4)
    lb = LoadBalancer([s1, s2])
    # duration-blind: equal weights tie toward the first-registered site
    assert lb.pick(None, now=0.0) is s1
    s1.outstanding_work = 10.0
    assert lb.pick(None, now=0.0) is s1   # still blind to predicted work
    lb.duration_aware = True
    assert lb.pick(None, now=0.0) is s2   # queued seconds now priced


def test_sim_and_real_scheduling_decisions_match():
    """The same MolDyn-shaped submit sequence, priced by the same
    predictor, must split across sites identically in a simulated run and
    a real device-pool run — predicted durations, not measured ones,
    drive placement."""
    shapes = [16, 16, 32, 16, 32, 32, 16, 32, 16, 16, 32, 16]

    def run_one(real):
        clock = RealClock() if real else SimClock()
        pred = DurationPredictor()
        eng = Engine(clock, duration_predictor=pred)
        eng.balancer.duration_aware = True
        sites = []
        for name, cap in (("anl_tg", 4), ("uc_tp", 2)):
            if real:
                pool = DeviceExecutorPool(clock, max_bundle=8)
                cfg = FalkonConfig(drp=DRPConfig(
                    min_executors=cap, max_executors=cap,
                    alloc_latency=0.0, alloc_chunk=cap))
                svc = FalkonService(clock, cfg, pool=pool)
                svc.provision(cap)
                sites.append((eng.add_site(name, FalkonProvider(svc),
                                           capacity=cap), svc))
            else:
                prov = VmapClusteringProvider(clock, max_bundle=8)
                sites.append((eng.add_site(name, prov, capacity=cap), None))
        futs = [eng.submit(f"m{i}", body,
                           [np.ones((d,), np.float32),
                            np.ones((d, d), np.float32)], vmap_key="md")
                for i, d in enumerate(shapes)]
        # literal args place synchronously at submit: the split is decided
        # here, before any execution, by predicted durations alone
        split = tuple(s.stats.submitted for s, _ in sites)
        eng.run()
        for _, svc in sites:
            if svc is not None:
                svc.shutdown()
        assert all(f.resolved for f in futs)
        return split

    assert run_one(real=False) == run_one(real=True)


# ---------------------------------------------------------------------------
# GC-safe callable identity
# ---------------------------------------------------------------------------


def test_fn_key_registry_stable_and_gc_safe():
    reg = FnKeyRegistry()

    def f(x):
        return x

    def g(x):
        return x + 1

    kf, kg = reg.key(f), reg.key(g)
    assert kf != kg
    assert reg.key(f) == kf          # stable across calls
    n = len(reg)
    del g
    gc.collect()
    assert len(reg) == n - 1         # dead entry reaped, no id pinning

    # a NEW callable must never inherit a dead callable's key, even if the
    # allocator reuses its id (the bug raw id(fn) keying had)
    seen = {kf}
    for _ in range(50):
        def h(x):
            return x * 3
        k = reg.key(h)
        assert k not in seen
        seen.add(k)
        del h
        gc.collect()


def test_vmap_signature_distinguishes_shapes_and_callables():
    def f(x):
        return x

    a4 = np.ones((4,), np.float32)
    a8 = np.ones((8,), np.float32)
    assert vmap_signature(f, [a4]) == vmap_signature(f, [a4])
    assert vmap_signature(f, [a4]) != vmap_signature(f, [a8])
    assert vmap_signature(f, [a4]) != vmap_signature(lambda x: x, [a4])
    assert stable_fn_key(f) == stable_fn_key(f)


def test_vmap_provider_singleton_fallback_reports_measured_stats():
    eng = Engine(SimClock())
    prov = VmapClusteringProvider(eng.clock, max_bundle=64)
    eng.add_site("d", prov, capacity=8)
    out = eng.submit("solo", body, [np.ones((4,), np.float32),
                                    np.ones((4, 4), np.float32)],
                     vmap_key="s")
    eng.run()
    assert out.resolved
    # a singleton bundle still lands in the throughput stats instead of
    # vanishing (same shape as the real pools' metrics)
    assert prov.run_stat.count == 1
    assert prov.metrics()["bundles"] == 1
    assert prov.fused_tasks == 0
