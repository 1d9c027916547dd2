"""Device-batched executor pool: fuse tiny tasks into vmapped dispatches.

The paper's task clustering (§3.13) amortizes per-job batch-scheduler
overhead; on an accelerator the analogous cost is per-task dispatch + kernel
launch.  `DeviceExecutorPool` closes that loop behind the same
``submit(task, done, stage=None)`` seam as the real pools
(`repro.core.realpool`, DESIGN.md §10/§11): ready tasks sharing a
`vmap_signature` accumulate into bundles, and each bundle executes as ONE
jitted+vmapped device call — one launch for K tiny tasks — on a dedicated
dispatcher thread.  Per-task completions fan back through
`Clock.post_release`, each with its share of the bundle's measured time,
and the pool keeps exact counters (tasks, bundles, dispatcher seconds).

Wiring is identical to the other pools::

    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=256)
    svc = FalkonService(clock, cfg, pool=pool)
    eng = Engine(clock)
    eng.add_site("dev", FalkonProvider(svc), capacity=256)
    ... submit tasks with vmap_key=... ...
    eng.run(); svc.shutdown()

Batching contract: a task is *batchable* when it carries both a callable
and a ``vmap_key`` (the opt-in by which the user asserts the body is a
pure, vmappable JAX function).  Everything else
runs as a measured singleton on the dispatcher thread, so mixed workloads
need no special casing.  Composition with the rest of the stack is
unchanged: DRP provisioning still sizes the *logical* executor set (the
pool is fixed — one device does not grow threads), the data layer's
`stage` closures run on the dispatcher thread inside the measured staging
time, and `foreach(window=)` / federation operate above the pool seam.

Threading contract (DESIGN.md §10): `submit` and `flush` run on the clock
thread, and place each shipped task's output there; dispatcher threads
touch only the bundle queue, the vmapped-jit cache, and `post`/`release`;
completions and all counters run back on the clock thread.  The
dispatcher reads the clock once per bundle, when it takes the bundle off
the queue, and hands that time back in the posted completion
(`Span.dequeued`): it never touches a span itself.

Layer spans on the profiler's clock (`observability.span`): clock thread
``pool.submit`` (each `submit` and `flush`) and ``pool.complete`` (one per
bundle, around the per-task completions it fans out); dispatcher thread
``pool.bundle`` around one bundle, with `execute_bundle`'s ``bundle.*``
phases inside it.
"""
from __future__ import annotations

import queue
import threading
from functools import partial
from time import perf_counter
from typing import Any, Callable, Optional

from repro.core.clustering import (Programs, bundle_key, execute_bundle,
                                   place_outputs)
from repro.core.observability import span
from repro.core.realpool import _require_threadsafe_clock
from repro.core.simclock import Clock

_STOP = object()


class DeviceExecutorPool:
    """Real pool whose dispatch loop fuses same-signature tasks into one
    vmapped device call (DESIGN.md §11).

    Knobs: `max_bundle` caps the fuse width (a full bucket flushes
    immediately); `linger` is the bundling window in clock seconds —
    with the default ``0.0`` a flush is scheduled behind the current
    event cascade, so every task dispatched in one scheduler pump (up to
    the site's throttle) lands in the same bundle without adding latency;
    `dispatchers` is the number of device-feeding threads (one per device
    stream; the default 1 matches a single accelerator's serial launch
    queue).

    Measured, not priced: `done(ok, value, err, io_s, run_s)` receives the
    staging seconds observed for that task and its share of the bundle's
    measured execution time (`bundle_s / K`).  `device_s` accumulates the
    dispatcher thread's host wall seconds inside `execute_bundle` —
    stacking, the copies to and from the device, the call (with any
    compile) and `device_get` — *not* device time (a profiler trace
    gives that); `io_s` the staging seconds of every task.  `fallbacks`
    counts bundles whose fused call failed and ran per task instead, and
    `last_fallback` keeps the newest such error's repr: on the device path
    a compile refusal or an out-of-memory shows up there, not as silent
    slowness.

    Placement (DESIGN.md §11): as a bucket ships, each task's output is
    placed (`place_outputs`) — kept on the device when every task that
    reads it is batchable, fetched to the host otherwise — so a dataflow
    edge between two fused stages never passes through host memory.
    `outputs_on_device` and `outputs_to_host` count the fused outputs of
    each kind (together `fused_tasks`).  A bundle's outputs kept on the
    device are rows of one stack, which a next bundle reading exactly its
    rows in order takes whole; `metrics()` counts `args_passed_through`,
    `args_restacked` and `stacks_cut` (`clustering.Programs`).
    """

    autoscale = False

    def __init__(self, clock: Clock, max_bundle: int = 256,
                 linger: float = 0.0, dispatchers: int = 1,
                 name: str = "device", tracer=None):
        if max_bundle < 1:
            raise ValueError("max_bundle must be >= 1")
        _require_threadsafe_clock(clock, name)
        self.clock = clock
        self.name = name
        # observability (DESIGN.md §12): each fused bundle emits one
        # `bundle_fused` event (value = tasks fused); clock thread only
        self.tracer = tracer
        self.max_bundle = max_bundle
        self.linger = linger
        self._pending: dict[Any, list] = {}
        self._full: list = []             # buckets sealed full, to ship
        self._flush_scheduled = False
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._vmapped_cache = Programs()
        self._shutdown = False
        self._threads = [
            threading.Thread(target=self._loop, daemon=True,
                             name=f"{name}-dispatch{i}")
            for i in range(max(1, dispatchers))
        ]
        for t in self._threads:
            t.start()
        # counters/summaries — mutated on the clock thread only
        self.tasks_run = 0
        self.bundles_run = 0
        self.fused_tasks = 0
        self.outputs_on_device = 0    # fused outputs kept as device arrays
        self.outputs_to_host = 0      # fused outputs fetched to the host
        # tasks of procedures that declare uneven extents (DESIGN.md §16)
        # and their true / padded element counts, dummy rows included
        self.padded_tasks = 0
        self.true_elements = 0
        self.padded_elements = 0.0
        self.fallbacks = 0
        self.last_fallback: Optional[str] = None
        self.device_s = 0.0   # dispatcher host s inside execute_bundle
        self.io_s = 0.0       # staging s, every task

    def size(self) -> int:
        return len(self._threads)

    def resize(self, n: int) -> None:
        """Fixed-size by design: DRP allocations size the *logical*
        executor set, not device streams (`autoscale` is False, so the
        service never calls this on the real path)."""

    # -- the seam (clock thread) ----------------------------------------
    def submit(self, task, done: Callable,
               stage: Optional[Callable[[], None]] = None) -> None:
        """Hand one task to the dispatcher.  Batchable tasks (callable +
        `vmap_key`) accumulate per `vmap_signature` until `max_bundle` or
        the `linger` flush; others ship immediately as singletons.
        `done(ok, value, err, io_s, run_s)` is called back on the clock
        thread, once per task."""
        with span("pool.submit"):
            if self._shutdown:
                raise RuntimeError(f"pool {self.name!r} is shut down")
            self.clock.hold()
            if task.vmap_key is None or task.fn is None:
                self._q.put([(task, done, stage)])
                return
            key = bundle_key(task)
            bucket = self._pending.get(key)
            if bucket is None:
                self._pending[key] = bucket = []
            bucket.append((task, done, stage))
            if len(bucket) >= self.max_bundle:
                del self._pending[key]
                if task.output.consumers_fused is None:
                    # no reader yet: a graph built in one clock step
                    # submits a task before its readers, so ship once the
                    # step returns, when `place_outputs` can know them
                    self._full.append(bucket)
                    if len(self._full) == 1:
                        self.clock.schedule(0.0, self._ship_full)
                else:
                    self._ship(bucket)
            elif not self._flush_scheduled:
                self._flush_scheduled = True
                self.clock.schedule(self.linger, self.flush)

    def flush(self) -> None:
        """Ship every pending bucket to the dispatcher (clock thread)."""
        with span("pool.submit"):
            self._flush_scheduled = False
            self._ship_full()
            if not self._pending:
                return
            pending, self._pending = self._pending, {}
            for bundle in pending.values():
                self._ship(bundle)

    def _ship_full(self) -> None:
        """Ship the buckets sealed full (clock thread)."""
        full, self._full = self._full, []
        for bundle in full:
            self._ship(bundle)

    def _ship(self, bundle: list) -> None:
        """Queue a bucket for the dispatcher, with each task's output
        placement decided now, on the clock thread (`place_outputs`)."""
        place_outputs(t for t, _done, _stage in bundle)
        self._q.put(bundle)

    def shutdown(self, wait: bool = True) -> None:
        """Stop the dispatcher threads (after their queued bundles) and
        join them.  Call after `run()` returns; queued work has completed."""
        if self._shutdown:
            return
        self._shutdown = True
        for _ in self._threads:
            self._q.put(_STOP)
        if wait:
            for t in self._threads:
                t.join()
        self._threads.clear()

    # -- dispatcher side -------------------------------------------------
    def _loop(self) -> None:
        while True:
            bundle = self._q.get()
            if bundle is _STOP:
                return
            self._run_bundle(bundle, self.clock.now())

    def _run_bundle(self, bundle: list, dequeued: float) -> None:
        with span("pool.bundle"):
            # real staging copies first, per task, inside the measured io
            # time (exactly where the simulated path adds priced staging
            # I/O); a staging failure fails that task and drops it from
            # the batch
            io_ss = [0.0] * len(bundle)
            staged_err: dict[int, BaseException] = {}
            for i, (_task, _done, stage) in enumerate(bundle):
                if stage is None:
                    continue
                t0 = perf_counter()
                try:
                    stage()
                except BaseException as err:  # noqa: BLE001 — staging
                    staged_err[i] = err
                io_ss[i] = perf_counter() - t0
            live = [i for i in range(len(bundle)) if i not in staged_err]
            tasks = [bundle[i][0] for i in live]
            if tasks:
                results, exec_s, n_fused, fallback = execute_bundle(
                    tasks[0].fn, tasks, self._vmapped_cache)
            else:
                results, exec_s, n_fused, fallback = [], 0.0, 0, None
            # measured bundle time attributed evenly across the tasks that
            # actually executed; staged-failure tasks carry only their io
            out: list = [None] * len(bundle)
            run_ss = [0.0] * len(bundle)
            per_task = exec_s / max(1, len(live))
            for i, err in staged_err.items():
                out[i] = (False, None, err)
            for i, res in zip(live, results):
                out[i] = res
                run_ss[i] = per_task
            # one posted completion per bundle; the post lands before any
            # hold token is returned, so the loop can never observe
            # "no holds, no events" mid-handoff
            self.clock.post(partial(self._complete_bundle, bundle, out,
                                    io_ss, run_ss, exec_s, n_fused,
                                    fallback, dequeued))
            for _ in bundle:
                self.clock.release()

    # -- back on the clock thread ----------------------------------------
    def _complete_bundle(self, bundle, out, io_ss, run_ss, exec_s,
                         n_fused, fallback, dequeued) -> None:
        with span("pool.complete"):
            now = self.clock.now()
            self.bundles_run += 1
            self.device_s += exec_s
            self.fused_tasks += n_fused
            if n_fused:
                # a fused task's result is ok; a failed one never ran fused
                on = sum(1 for (task, _d, _s), res in zip(bundle, out)
                         if res[0] and task.on_device)
                self.outputs_on_device += on
                self.outputs_to_host += n_fused - on
            if fallback is not None:
                self.fallbacks += 1
                self.last_fallback = repr(fallback)
            if self.tracer is not None and n_fused:
                self.tracer.event("bundle_fused", now, n_fused)
            tr = self.tracer
            self.io_s += sum(io_ss)
            padded = n_fused and bundle[0][0].uneven is not None
            for (task, done, _stage), (ok, v, err), io_s, run_s in zip(
                    bundle, out, io_ss, run_ss):
                self.tasks_run += 1
                if padded and ok:
                    true, pad = task.elements
                    self.padded_tasks += 1
                    self.true_elements += true
                    self.padded_elements += pad
                if tr is not None:
                    sp = getattr(task, "span", None)
                    if sp is not None:
                        sp.dequeued = dequeued
                        if padded and ok:
                            sp.true_elements, sp.padded_elements = \
                                task.elements
                    if not ok:
                        # worker-level failure signal (DESIGN.md §13),
                        # same kind the thread/process pools emit
                        tr.event("worker_error", now)
                done(ok, v, err, io_s, run_s)

    def metrics(self) -> dict:
        """Exact counters — safe at any task count.  `device_s` is the
        dispatcher's host seconds inside `execute_bundle`, not device
        time; `io_s` the staging seconds of every task;
        `outputs_on_device` + `outputs_to_host` = `fused_tasks`."""
        return {
            "dispatchers": self.size(),
            "tasks_run": self.tasks_run,
            "bundles_run": self.bundles_run,
            "bundle_mean": (self.tasks_run / self.bundles_run
                            if self.bundles_run else 0.0),
            "fused_tasks": self.fused_tasks,
            "outputs_on_device": self.outputs_on_device,
            "outputs_to_host": self.outputs_to_host,
            "args_passed_through": self._vmapped_cache.args_passed_through,
            "args_restacked": self._vmapped_cache.args_restacked,
            "stacks_cut": self._vmapped_cache.stacks_cut,
            "padded_tasks": self.padded_tasks,
            "true_elements": self.true_elements,
            "padded_elements": self.padded_elements,
            "fallbacks": self.fallbacks,
            "last_fallback": self.last_fallback,
            "device_s": self.device_s,
            "io_s": self.io_s,
        }
