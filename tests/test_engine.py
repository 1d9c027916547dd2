"""Unit tests for the Karajan engine, Falkon service, sites, and faults."""
import pytest

from repro.core import (BatchSchedulerProvider, ClusteringProvider, DRPConfig,
                        Engine, FalkonConfig, FalkonProvider, FalkonService,
                        LocalProvider, SimClock, Workflow)
from repro.core.faults import FaultInjector, RetryPolicy, TaskFailure
from repro.core.futures import DataFuture, resolved, when_all


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------

def test_future_single_assignment():
    f = DataFuture("x")
    f.set(1)
    assert f.get() == 1
    with pytest.raises(Exception):
        f.set(2)


def test_when_all_fires_once():
    fs = [DataFuture() for _ in range(3)]
    hits = []
    when_all(fs, lambda: hits.append(1))
    for f in fs:
        f.set(0)
    assert hits == [1]


def test_future_callbacks_after_resolution():
    f = resolved(42)
    got = []
    f.on_done(lambda ff: got.append(ff.get()))
    assert got == [42]


# ---------------------------------------------------------------------------
# dispatch / dependencies
# ---------------------------------------------------------------------------

def test_dataflow_ordering():
    clock = SimClock()
    eng = Engine(clock)
    eng.local_site(concurrency=2)
    order = []
    a = eng.submit("a", lambda: order.append("a") or 1)
    b = eng.submit("b", lambda x: order.append("b") or x + 1, [a])
    c = eng.submit("c", lambda x: order.append("c") or x + 1, [b])
    eng.run()
    assert order == ["a", "b", "c"]
    assert c.get() == 3


def test_implicit_parallelism():
    """Independent tasks overlap in (virtual) time."""
    clock = SimClock()
    eng = Engine(clock)
    eng.add_site("s", LocalProvider(clock, concurrency=8), capacity=8)
    outs = [eng.submit(f"t{i}", None, duration=10.0) for i in range(8)]
    eng.run()
    assert clock.now() == pytest.approx(10.0)
    assert all(o.resolved for o in outs)


# ---------------------------------------------------------------------------
# retries / fault handling
# ---------------------------------------------------------------------------

def test_transient_retry_succeeds():
    clock = SimClock()
    inj = FaultInjector().fail_first_n("flaky", 2)
    eng = Engine(clock, retry_policy=RetryPolicy(max_retries=3),
                 fault_injector=inj)
    eng.local_site(concurrency=1)
    out = eng.submit("flaky", lambda: "ok")
    eng.run()
    assert out.get() == "ok"
    assert eng.vdc.summary()["failed"] == 2  # two retried attempts recorded


def test_retry_exhaustion_fails_future():
    clock = SimClock()
    inj = FaultInjector().fail_first_n("doomed", 10)
    eng = Engine(clock, retry_policy=RetryPolicy(max_retries=2),
                 fault_injector=inj)
    eng.local_site(concurrency=1)
    out = eng.submit("doomed", lambda: "ok")
    eng.run()
    assert out.failed
    assert eng.tasks_failed == 1


def test_upstream_failure_propagates():
    clock = SimClock()
    eng = Engine(clock, retry_policy=RetryPolicy(max_retries=0))
    eng.local_site()

    def boom():
        raise TaskFailure("boom")

    a = eng.submit("a", boom)
    b = eng.submit("b", lambda x: x, [a])
    eng.run()
    assert a.failed and b.failed


def test_site_rescheduling_on_site_fault():
    """Site-kind failures move the task to a different site (§3.12)."""
    clock = SimClock()
    eng = Engine(clock, retry_policy=RetryPolicy(max_retries=3))
    ran_on = []

    class RecordingProvider(LocalProvider):
        def __init__(self, clock, name):
            super().__init__(clock, concurrency=4)
            self.site_name = name

        def submit(self, task, when_done):
            ran_on.append(self.site_name)
            if self.site_name == "bad":
                when_done(False, None, TaskFailure("stale NFS", kind="site"))
                return
            super().submit(task, when_done)

    bad = eng.add_site("bad", RecordingProvider(clock, "bad"), capacity=4)
    bad.score = 10.0  # make it the first choice
    eng.add_site("good", RecordingProvider(clock, "good"), capacity=4)
    out = eng.submit("t", lambda: "done")
    eng.run()
    assert out.get() == "done"
    assert "bad" in ran_on and "good" in ran_on


def test_falkon_host_suspension():
    """Repeated failures on one executor suspend that host."""
    clock = SimClock()
    svc = FalkonService(clock, FalkonConfig(
        drp=DRPConfig(max_executors=2, alloc_latency=0.0),
        host_fail_threshold=2, host_suspend_time=1000.0))
    svc.provision(2)
    inj = FaultInjector().fail_host("falkon-host0", 2)
    eng = Engine(clock, retry_policy=RetryPolicy(max_retries=4),
                 fault_injector=inj)
    eng.add_site("f", FalkonProvider(svc), capacity=2)
    outs = [eng.submit(f"t{i}", None, duration=1.0) for i in range(6)]
    eng.run()
    assert all(o.resolved for o in outs)
    host0 = svc.executors[0]
    assert host0.suspended_until > 0  # was suspended at some point


# ---------------------------------------------------------------------------
# falkon DRP + metrics
# ---------------------------------------------------------------------------

def test_drp_grows_pool_on_queue_pressure():
    clock = SimClock()
    svc = FalkonService(clock, FalkonConfig(
        drp=DRPConfig(max_executors=16, alloc_latency=10.0, alloc_chunk=4)),
        trace=True)
    eng = Engine(clock)
    eng.add_site("f", FalkonProvider(svc), capacity=16)
    outs = [eng.submit(f"t{i}", None, duration=5.0) for i in range(32)]
    eng.run()
    assert all(o.resolved for o in outs)
    assert len(svc.alloc_log) >= 2  # grew incrementally
    # bounded allocation summary matches the full trace
    assert svc.alloc_stat.count == len(svc.alloc_log)
    assert svc.alloc_stat.total == sum(n for _, n in svc.alloc_log)
    assert svc.utilization()["dispatched"] == 32


def test_clustering_amortizes_overhead():
    """Bundled submission beats per-task submission on a slow scheduler."""

    def run(cluster):
        clock = SimClock()
        eng = Engine(clock)
        # admit_window=0: exact per-job admission — the 2.0x ratio below is
        # calibrated to the exact model with zero slack, so wave-quantized
        # admission lateness (default sched_latency/8) would skew it
        inner = BatchSchedulerProvider(clock, nodes=4, submit_rate=1.0,
                                       sched_latency=10.0, admit_window=0.0)
        prov = ClusteringProvider(clock, inner, window=0.5, bundle_size=8) \
            if cluster else inner
        eng.add_site("s", prov, capacity=4)
        outs = [eng.submit(f"t{i}", None, duration=1.0) for i in range(32)]
        eng.run()
        assert all(o.resolved for o in outs)
        return clock.now()

    t_clustered = run(True)
    t_plain = run(False)
    assert t_plain / t_clustered >= 2.0  # paper: 2-4x improvement


def test_load_balancing_proportional_to_speed():
    """Fig 11: the faster site completes more jobs."""
    clock = SimClock()
    eng = Engine(clock)

    class TimedProvider(LocalProvider):
        def __init__(self, clock, factor):
            super().__init__(clock, concurrency=8)
            self.factor = factor

        def submit(self, task, when_done):
            task.duration = task.duration * self.factor
            super().submit(task, when_done)

    fast = eng.add_site("fast", TimedProvider(clock, 0.5), capacity=8)
    slow = eng.add_site("slow", TimedProvider(clock, 1.0), capacity=8)
    wf = Workflow("lb", eng)
    p = wf.sim_proc("job", duration=4.0)
    out = wf.foreach(list(range(480)), p)
    wf.run()
    assert out.resolved
    assert fast.stats.completed + slow.stats.completed == 480
    assert fast.stats.completed > slow.stats.completed


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

def test_provenance_records_invocations():
    clock = SimClock()
    eng = Engine(clock)
    eng.local_site(concurrency=2)
    a = eng.submit("stage_a", lambda: 1)
    b = eng.submit("stage_b", lambda x: x + 1, [a])
    eng.run()
    s = eng.vdc.summary()
    assert s["invocations"] == 2 and s["ok"] == 2
    recs = eng.vdc.by_task("stage_b")
    assert len(recs) == 1
    assert recs[0].end_time >= recs[0].start_time >= 0


# ---------------------------------------------------------------------------
# lifecycle hooks: each is its owner's public method, called once a task
# ---------------------------------------------------------------------------

def test_engine_calls_tracer_and_health_hooks_once_per_task():
    from collections import Counter

    from repro.core import HealthMonitor, Tracer

    calls = Counter()

    class CountingTracer(Tracer):
        def task_created(self, task, now, shard=None):
            calls["task_created"] += 1
            return super().task_created(task, now, shard)

        def task_ready(self, task, now):
            calls["task_ready"] += 1
            super().task_ready(task, now)

        def task_done(self, task, now, status="ok"):
            calls[f"task_done.{status}"] += 1
            return super().task_done(task, now, status)

    class CountingMonitor(HealthMonitor):
        def task_dispatched(self, task, now):
            calls["task_dispatched"] += 1
            super().task_dispatched(task, now)

        def task_finished(self, task, site, ok, now):
            calls[f"task_finished.{ok}"] += 1
            super().task_finished(task, site, ok, now)

    clock = SimClock()
    tracer = CountingTracer()
    eng = Engine(clock, tracer=tracer)
    CountingMonitor(clock).watch(eng)
    eng.local_site(concurrency=4)
    a = eng.submit("a", None, duration=1.0)
    b = eng.submit("b", None, [a], duration=2.0)
    c = eng.submit("c", None, [a, b], duration=3.0)
    eng.run()
    assert c.resolved
    assert calls == {"task_created": 3, "task_ready": 3, "task_done.ok": 3,
                     "task_dispatched": 3, "task_finished.True": 3}
    # the critical path runs a -> b -> c, ready to done: 1 + 2 + 3 s
    assert tracer.critical_path_s == pytest.approx(6.0)
    assert c.path == pytest.approx(6.0)
    assert [sp.ready for sp in tracer.spans] == [0.0, 1.0, 3.0]


class _RecordingJournal:
    """The journal calls the engine makes with no store behind them."""

    full = False

    def __init__(self):
        self.failed = []

    def unique_key(self, key):
        return key

    def task_done(self, key, value):
        pass

    def task_failed(self, key, reason):
        self.failed.append((key, reason))


@pytest.mark.parametrize("site", ["upstream_traced", "upstream_untraced",
                                  "no_site", "retries_spent"])
def test_every_terminal_failure_takes_one_path(site):
    from repro.core import Tracer

    class Recording(Engine):
        def _fail(self, task, err, reason):
            failed.append(task)
            super()._fail(task, err, reason)

    failed = []
    clock = SimClock()
    tracer = None if site == "upstream_untraced" else Tracer()
    inj = FaultInjector()
    eng = Recording(clock, tracer=tracer, fault_injector=inj,
                    retry_policy=RetryPolicy(
                        max_retries=1 if site == "retries_spent" else 0,
                        backoff=0.0))
    journal = eng.journal = _RecordingJournal()
    if site != "no_site":
        eng.local_site(concurrency=2)
    if site == "retries_spent":
        inj.fail_first_n("t", 2)
        out = eng.submit("t", None, [1], duration=1.0)
        reasons = ["injected"]
    elif site == "no_site":
        out = eng.submit("t", None, [1], duration=1.0)
        reasons = ["no site"]
    else:
        inj.fail_first_n("up", 1)
        up = eng.submit("up", None, [1], duration=1.0)
        out = eng.submit("t", None, [up], duration=1.0)
        reasons = ["injected", "upstream failure"]
    eng.run()
    assert out.failed
    with pytest.raises(TaskFailure):
        out.get()
    assert failed[-1].output is out
    assert len(failed) == len(reasons) == eng.tasks_failed
    for t, (key, reason), want in zip(failed, journal.failed, reasons):
        assert key == t.key and want in reason
        assert t.args == () and t.fault_check is None
        assert t.output.failed
    if tracer is not None:
        assert tracer.tasks_failed == len(reasons)
        assert tracer.tasks_retried == (1 if site == "retries_spent" else 0)
