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
                        LocalProvider, RealClock, SharedStore, SimClock,
                        Tracer, Workflow)
from repro.core.clustering import vmap_signature
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


def test_pool_counts_failed_fused_calls():
    eng, svc, pool = device_stack(RealClock(), executors=8, max_bundle=64)
    futs = [eng.submit(f"t{i}", branchy, [np.full((4,), i - 1.5, np.float32)],
                       vmap_key="b") for i in range(4)]
    eng.run()
    svc.shutdown()
    assert all(f.resolved for f in futs)
    m = pool.metrics()
    assert m["fused_tasks"] == 0
    assert m["fallbacks"] == m["bundles_run"] == 1


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
                sites.append((eng.add_site(name, LocalProvider(clock, cap),
                                           capacity=cap), None))
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


def test_pool_singleton_fallback_reports_measured_stats():
    eng, svc, pool = device_stack(RealClock(), executors=8, max_bundle=64)
    out = eng.submit("solo", body, [np.ones((4,), np.float32),
                                    np.ones((4, 4), np.float32)],
                     vmap_key="s")
    eng.run()
    svc.shutdown()
    assert out.resolved
    # a singleton bundle still lands in the measured stats instead of
    # vanishing: one bundle of one task, run unfused, its time counted
    m = svc.metrics()["pool"]
    assert m["tasks_run"] == m["bundles_run"] == 1
    assert m["fused_tasks"] == 0
    assert m["device_s"] > 0.0


# ---------------------------------------------------------------------------
# output stacks: one output per bundle, passed through whole (DESIGN.md §11)
# ---------------------------------------------------------------------------


def flip_y(v):
    return jnp.transpose(v, (1, 0, 2))


def flip_x(v):
    return jnp.flip(v, axis=0)


def fit(ref, v):
    rc = ref - jnp.mean(ref)
    gain = jnp.sum(rc * (v - jnp.mean(v))) / jnp.sum(rc * rc)
    return jnp.stack([gain, jnp.mean(v) - gain * jnp.mean(ref)])


def apply_fit(v, p):
    return p[0] * v + p[1]


def eager_volume(vol, ref):
    x = flip_x(flip_y(jnp.asarray(vol)))
    return np.asarray(apply_fit(x, fit(jnp.asarray(ref), x)))


@pytest.mark.parametrize("built", ["before_the_run", "in_a_clock_step"])
def test_fmri_shaped_chain_passes_every_device_edge_through(built):
    """480 volumes through reorient_y -> reorient_x -> alignlinear (a
    shared reference per run of 120) -> reslice, as the fMRI campaign
    runs them: every device edge takes its producer's stack whole.
    `in_a_clock_step`: the study's graph is built inside one clock step
    of a running system, as a campaign's next study is; a bundle that
    fills as it is built ships when the step returns, once its last
    task's reader is known, so that output stays on the device too."""
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=1024, max_bundle=256)
    wf = Workflow("fmri", eng)
    ry, rx, al, rs = (wf.atomic(f, vmap_key=f.__name__)
                      for f in (flip_y, flip_x, fit, apply_fit))
    rng = np.random.default_rng(0)
    vols = rng.normal(size=(480, 4, 6, 2)).astype(np.float32)
    refs = list(rng.normal(size=(4, 6, 4, 2)).astype(np.float32))

    def study():
        outs = []
        for i, vol in enumerate(vols):
            x = rx(ry(vol))
            outs.append(rs(x, al(refs[i // 120], x)))
        return outs

    base = {}
    if built == "in_a_clock_step":
        study()                       # the system runs a study first
        eng.run()
        base = pool.metrics()
        box = []
        clock.schedule(0.0, lambda: box.append(study()))
        eng.run()
        outs = box[0]
    else:
        outs = study()
        eng.run()
    svc.shutdown()
    for i, (vol, f) in enumerate(zip(vols, outs)):
        got = f.get()
        assert type(got) is np.ndarray
        # the vmapped reductions sum in another order than the eager ones
        np.testing.assert_allclose(got, eager_volume(vol, refs[i // 120]),
                                   rtol=1e-5, atol=1e-6)
    m = {k: v - base.get(k, 0) for k, v in pool.metrics().items()
         if isinstance(v, int)}
    stage_bundles = m["bundles_run"] // 4
    assert m["bundles_run"] == 4 * stage_bundles and m["fallbacks"] == 0
    # reorient_x, alignlinear and reslice (twice) read device edges
    assert m["args_passed_through"] == 4 * stage_bundles
    assert m["args_restacked"] == 0 and m["stacks_cut"] == 0
    assert m["outputs_on_device"] == 3 * 480 and m["outputs_to_host"] == 480


def prep(x, w):
    return jnp.tanh(x @ w)


def charmm(x, p, w):
    return jnp.tanh((x * p) @ w)


def join(*runs):
    return jnp.mean(jnp.stack(runs), axis=0)


def test_fan_out_and_fan_in_cut_each_source_stack_once():
    """MolDyn's shape: a prep chain per molecule, a fan-out of runs that
    each read their molecule's prep output, a join of a molecule's runs,
    a post stage.  Readers that are not a stack's rows in order cut it
    once; the chains still pass their stacks through."""
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=64, max_bundle=64)
    wf = Workflow("moldyn", eng)
    p1, p2 = (wf.atomic(prep, name=f"prep{i}", vmap_key=f"prep{i}")
              for i in (1, 2))
    run = wf.atomic(charmm, vmap_key="charmm")
    jn = wf.atomic(join, vmap_key="join")
    post = wf.atomic(prep, name="post", vmap_key="post")
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(4, 3, 8)).astype(np.float32)
    ps = rng.normal(size=(6, 8)).astype(np.float32)
    w = (rng.normal(size=(8, 8)) / 3).astype(np.float32)
    outs = []
    for x in xs:
        f = p2(p1(x, w), w)
        outs.append(post(jn(*[run(f, p, w) for p in ps]), w))
    eng.run()
    svc.shutdown()
    for x, f in zip(xs, outs):
        f2 = prep(prep(jnp.asarray(x), w), w)
        want = prep(join(*[charmm(f2, p, w) for p in ps]), w)
        np.testing.assert_allclose(f.get(), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    m = pool.metrics()
    assert m["bundles_run"] == 5 and m["fallbacks"] == 0
    # prep1 -> prep2 and join -> post pass through; the runs read prep2's
    # rows 6 times each, the join each run stack's rows out of order
    assert m["args_passed_through"] == 2
    assert m["stacks_cut"] == 2
    assert m["args_restacked"] == 1 + 6


def double_jnp(x):
    return 2.0 * x


@pytest.mark.parametrize("reader", ["singleton", "late", "get"])
def test_a_row_read_outside_a_fused_bundle_is_a_plain_array(reader):
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    wf = Workflow("rows", eng)
    seen = []

    def look(x):
        seen.append(x)
        return 2.0 * np.asarray(x)

    a = wf.atomic(step, vmap_key="a")
    b = wf.atomic(double_jnp, vmap_key="b")
    xs = [np.full((4, 8), 0.2 * i - 0.3, np.float32) for i in range(4)]
    firsts = [a(x) for x in xs]
    for f in firsts[1:]:
        b(f)                       # so every first output stays on device
    if reader == "singleton":
        # one fused task alone: its bundle runs the body eagerly
        wf.atomic(lambda x: look(x) + 0.0, vmap_key="solo")(firsts[0])
    else:
        b(firsts[0])
    eng.run()
    if reader == "late":
        wf.atomic(look)(firsts[0])
        eng.run()
    svc.shutdown()
    want = np.asarray(step(jnp.asarray(xs[0])))
    got = firsts[0].get()
    assert isinstance(got, jax.Array)
    np.testing.assert_array_equal(np.asarray(got), want)
    if reader != "get":
        assert len(seen) == 1 and isinstance(seen[0], jax.Array)
        np.testing.assert_array_equal(np.asarray(seen[0]), want)
    assert pool.outputs_on_device == 4 and pool.fallbacks == 0


@pytest.mark.parametrize("mixed", [False, True])
def test_host_bound_answers_come_from_one_fetch_of_the_stack(monkeypatch,
                                                              mixed):
    """A bundle whose outputs all go to the host fetches its stack once;
    one that keeps some on the device fetches the host rows alone, each
    an array of its own."""
    from repro.core import clustering

    fetched = []
    real = clustering.jax.device_get

    def device_get(x):
        fetched.append(x)
        return real(x)

    monkeypatch.setattr(clustering.jax, "device_get", device_get)
    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    xs = [np.full((4, 8), 0.1 * i, np.float32) for i in range(12)]
    firsts = [eng.submit(f"a{i}", step, [x], vmap_key="a")
              for i, x in enumerate(xs)]
    finals = [eng.submit(f"b{i}", step, [f], vmap_key="b")
              for i, f in enumerate(firsts)]
    thirds = [eng.submit(f"c{i}", step, [f], vmap_key="c")
              for i, f in enumerate(finals[:4] if mixed else [])]
    eng.run()
    svc.shutdown()
    if mixed:
        # b keeps 4 rows on the device and fetches its 8 others alone;
        # c fetches its whole (4, 4, 8) stack
        assert len(fetched) == 2 and fetched[1].shape == (4, 4, 8)
        assert [r.shape for r in fetched[0]] == [(4, 8)] * 8
        host = [f.get() for f in finals[4:]]
        # none is a view of a fetched stack
        assert not any(isinstance(r.base, np.ndarray) and r.base.ndim == 3
                       for r in host)
    else:
        # one fetch, of the whole (12, 4, 8) output stack of the last stage
        assert len(fetched) == 1 and fetched[0].shape == (12, 4, 8)
    n = len(thirds)
    for x, f in zip(xs[n:], finals[n:]):
        assert type(f.get()) is np.ndarray
        np.testing.assert_allclose(
            f.get(), np.asarray(step(step(jnp.asarray(x)))), rtol=1e-6)
    for x, f in zip(xs, thirds):
        np.testing.assert_allclose(
            f.get(), np.asarray(step(step(step(jnp.asarray(x))))),
            rtol=1e-6)
    assert pool.metrics()["args_passed_through"] == 1


def test_declared_bundles_keep_a_padded_output_per_task():
    from repro.core import Padded, Uneven
    from repro.core.clustering import Row

    def grow(shape, ext, img):
        return img * 2.0

    clock = RealClock()
    eng, svc, pool = device_stack(clock, executors=16, max_bundle=16)
    wf = Workflow("declared", eng)
    decl = Uneven(args=(0,), extent=0, padded_out=True)
    g1 = wf.atomic(grow, vmap_key="g1", uneven=decl)
    g2 = wf.atomic(grow, vmap_key="g2", uneven=decl)
    imgs = [np.ones((5 + i, 7), np.float32) for i in range(4)]
    mids = [g1(img) for img in imgs]
    finals = [g2(m) for m in mids]
    eng.run()
    svc.shutdown()
    for img, m, f in zip(imgs, mids, finals):
        held = m.get_padded()
        assert isinstance(held, Padded) and not isinstance(held.data, Row)
        assert held.extent == img.shape
        np.testing.assert_array_equal(np.asarray(f.get()), 4.0 * img)
    m = pool.metrics()
    assert m["args_passed_through"] == 0 and m["stacks_cut"] == 0


def test_pool_buckets_declared_tasks_by_their_held_extents():
    """The pool keys a declared task's held `Padded` arguments by their
    buckets: two stages whose padded outputs land in two buckets run four
    bundles, none of them degraded."""
    from repro.core import Uneven

    def grow(shape, ext, img):
        return img * 2.0

    eng, svc, pool = device_stack(RealClock(), executors=64, max_bundle=64)
    wf = Workflow("declared", eng)
    decl = Uneven(args=(0,), extent=0, padded_out=True)
    g1 = wf.atomic(grow, vmap_key="g1", uneven=decl)
    g2 = wf.atomic(grow, vmap_key="g2", uneven=decl)
    imgs = [np.full(s, 0.5, np.float32)
            for s in ((5, 7), (6, 9), (20, 300), (21, 290))]
    finals = [g2(g1(img)) for img in imgs]
    eng.run()
    svc.shutdown()
    for img, f in zip(imgs, finals):
        np.testing.assert_array_equal(np.asarray(f.get()), 4.0 * img)
    m = pool.metrics()
    assert m["fallbacks"] == 0 and m["bundles_run"] == 4
    assert m["fused_tasks"] == 8


def test_concurrent_readers_cut_a_stack_once():
    import sys
    import threading

    from repro.core.clustering import Row, Stack

    data = jnp.arange(64 * 5, dtype=jnp.float32).reshape(64, 5)
    stack = Stack(data, 64)
    rows = [Row(stack, j) for j in range(64)]
    cuts, got = [], {}
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def read(j0):
            cuts.append(stack.cut())
            for j in range(j0, 64, 8):
                got[j] = np.asarray(rows[j].unwrap())

        threads = [threading.Thread(target=read, args=(j,))
                   for j in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert cuts.count(True) == 1 and stack.data is None
    for j in range(64):
        np.testing.assert_array_equal(got[j], np.asarray(data[j]))
