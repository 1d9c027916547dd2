"""Device-native task clustering: vmap-bundling of small JAX tasks.

The paper's clustering (§3.13) amortizes batch-scheduler submission overhead
by bundling small jobs.  On an accelerator the analogous per-task cost is
*dispatch + kernel launch* of many small jitted computations; the adaptation
fuses ready tasks that share a callable and argument shapes into ONE batched
device call via `jax.vmap` — one launch, one dispatch, full-width compute.

Two consumers share the bundle-execution core in this module
(`execute_bundle` + `vmap_signature`):

  * `VmapClusteringProvider` — a provider for simulated/engine-driven runs:
    bundles form on the clock thread and execute inline (works under
    `SimClock`).
  * `DeviceExecutorPool` (`repro.core.devicepool`, DESIGN.md §11) — the
    real pool behind `FalkonService(pool=...)`: bundles execute on a
    dispatcher thread and measured completions re-enter through
    `Clock.post_release`.

Signature identity is GC-safe: callables are keyed through
`repro.core.task.stable_fn_key`, never raw ``id(fn)`` — a collected
callable's address can be reused by a new function, and an id-keyed bundle
or jit cache would then silently fuse (or run) the wrong callable.

benchmarks/vmap_clustering.py and benchmarks/device_batching.py measure the
amortization exactly like the paper's Fig 6 measures PBS-overhead
amortization.
"""
from __future__ import annotations

from time import perf_counter
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import StreamStat
from repro.core.observability import span
from repro.core.providers import Provider
from repro.core.simclock import Clock
from repro.core.task import Task, arg_signature, execute_task, stable_fn_key


def vmap_signature(fn: Callable, args: list) -> tuple:
    """Tasks sharing this signature can be fused into one vmapped call.

    The callable component is a `stable_fn_key` serial (GC-safe), not
    ``id(fn)``; the argument component is the structural
    `arg_signature` (shapes + dtypes), so tasks with the same callable
    but unstackable argument shapes land in different bundles instead of
    failing the stack at execution time."""
    return (stable_fn_key(fn), arg_signature(args))


def resolve_args(task) -> list:
    """Argument values of a dispatched task (futures are resolved)."""
    return [a.get() if hasattr(a, "get") and hasattr(a, "on_done") else a
            for a in task.args]


def place_outputs(tasks) -> None:
    """Decide where each task's fused output goes, as its bundle ships
    (clock thread): it stays on the device (``task.on_device``) when
    every task that reads it is batchable, as the engine's dependency scan
    has recorded so far in ``output.consumers_fused``.  Answers no task
    reads, outputs read by an unbatched body and journaled or durable
    values go to the host.  A consumer that registers after the ship gets
    a host value, which stacks as any host input does (DESIGN.md §11)."""
    for t in tasks:
        t.on_device = t.output.consumers_fused is True


def _fused(fn: Callable, in_axes: tuple):
    """``jit(vmap(fn))`` that returns one output pytree per task: the
    unstack runs inside the one program, so a bundle size compiles one
    program and per-task device values cost no index operation after it.
    The program keeps the body's name (``jit_<body>``)."""
    batched = jax.vmap(fn, in_axes=in_axes)

    def split(*args):
        k = jax.tree_util.tree_leaves(
            [a for a, ax in zip(args, in_axes) if ax == 0])[0].shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(batched(*args))
        return [treedef.unflatten([jax.lax.index_in_dim(x, i, keepdims=False)
                                   for x in leaves]) for i in range(k)]

    split.__name__ = getattr(batched, "__name__", "fused")
    return jax.jit(split)


def execute_bundle(fn: Callable, tasks: list, vmapped_cache: dict):
    """Run same-signature tasks as one jitted+vmapped device call.

    Returns ``(results, exec_s, n_fused, fallback)``: `results` is a
    per-task list of ``(ok, value, error)`` aligned with `tasks`, `exec_s`
    the measured host wall seconds of the execution on the calling
    thread (stacking, the copies to and from the device, the fused call
    with any compile, and `device_get`; or the sum of per-task executions
    on the fallback path) — not device time — `n_fused` how
    many tasks went through the batched call (0 when it was not used), and
    `fallback` the exception that made a bundle of two or more tasks
    degrade to per-task execution (None when it did not).  Fault checks
    run per task first — a failing check fails only that task and
    excludes it from the batch.  An error in the fused path (unstackable
    args, non-vmappable body, a compile refusal, device OOM) degrades to
    per-task `execute_task`, never to a lost completion; callers count
    the degrade (`fallbacks` in their metrics) so it is never silent.

    Placement (DESIGN.md §11): a fused task whose ``on_device`` is set
    (`place_outputs`) gets its output as a device `jax.Array`; the others
    get host values from one `device_get` over their outputs.  A
    one-task bundle runs its body eagerly and returns what the body
    returns.

    `vmapped_cache` maps ``(stable_fn_key, in_axes)`` to the compiled
    program (`_fused`), so steady-state bundles pay zero retrace.

    Each phase is a span on the profiler's clock (`observability.span`):
    ``bundle.stack`` (resolving and stacking the arguments; one jitted
    stack per argument of device inputs), ``bundle.h2d`` (the copy of a
    host stack to the device), ``bundle.call`` (the fused call, with a
    compile on a cache miss), ``bundle.wait`` (until its output is
    ready), ``bundle.d2h`` (`device_get` of the host-bound outputs, when
    there are any), ``bundle.split`` (handing each task its output; the
    device split itself runs inside the fused call), ``bundle.single``
    (a one-task bundle's eager body) and ``bundle.fallback`` (the
    per-task re-run after a fused-path error).
    """
    n = len(tasks)
    results: list = [None] * n
    live: list[int] = []
    for i, t in enumerate(tasks):
        chk = getattr(t, "fault_check", None)
        if chk is not None:
            try:
                chk(t)
            except BaseException as err:  # noqa: BLE001 — per-task failure
                results[i] = (False, None, err)
                continue
        live.append(i)
    if not live:
        return results, 0.0, 0, None
    if len(live) == 1:
        i = live[0]
        t0 = perf_counter()
        with span("bundle.single"):
            results[i] = _execute_unchecked(tasks[i])
        return results, perf_counter() - t0, 0, None
    t0 = perf_counter()
    try:
        with span("bundle.stack"):
            arg_lists = [resolve_args(tasks[i]) for i in live]
            n_args = len(arg_lists[0])
            # args identical across the bundle broadcast (in_axes=None)
            # instead of being stacked — no 256x weight copies
            shared = [all(al[i] is arg_lists[0][i] for al in arg_lists)
                      for i in range(n_args)]
            in_axes = tuple(None if s else 0 for s in shared)
            stacked = [arg_lists[0][i] if shared[i]
                       else _stack([al[i] for al in arg_lists])
                       for i in range(n_args)]
        vkey = (stable_fn_key(fn), in_axes)
        vfn = vmapped_cache.get(vkey)
        if vfn is None:
            vfn = vmapped_cache[vkey] = _fused(fn, in_axes)
        with span("bundle.call"):
            outs = vfn(*stacked)
        with span("bundle.wait"):
            jax.block_until_ready(outs)
        host = [j for j, i in enumerate(live) if not tasks[i].on_device]
        if host:
            with span("bundle.d2h"):
                fetched = jax.device_get([outs[j] for j in host])
            for j, v in zip(host, fetched):
                outs[j] = v
        with span("bundle.split"):
            for i, r in zip(live, outs):
                results[i] = (True, r, None)
        return results, perf_counter() - t0, len(live), None
    except Exception as err:  # noqa: BLE001 — degrade, counted by callers
        t0 = perf_counter()
        with span("bundle.fallback"):
            for i in live:
                results[i] = _execute_unchecked(tasks[i])
        return results, perf_counter() - t0, 0, err


@jax.jit
def _stack_on_device(*items):
    """Device-resident items of one bundle argument stacked in one program
    per (K, shape, dtype), not K eager ``expand_dims`` and a
    ``concatenate``."""
    return jnp.stack(items)


def _stack(items: list):
    """One bundle argument stacked along a new leading axis: host arrays
    with `np.stack` and one copy to the device (``bundle.h2d``), anything
    else (device arrays, or a mix) with one jitted device stack."""
    if all(isinstance(a, np.ndarray) for a in items):
        host = np.stack(items)
        with span("bundle.h2d"):
            return jnp.asarray(host)
    return _stack_on_device(*items)


def _execute_unchecked(task):
    """`execute_task` minus the fault check (already run by the bundle)."""
    fn = getattr(task, "fn", None)
    if fn is None:
        return True, getattr(task, "sim_value", None), None
    try:
        return True, fn(*resolve_args(task)), None
    except BaseException as err:  # noqa: BLE001 — engine handles retries
        return False, None, err


class VmapClusteringProvider(Provider):
    """Bundle ready tasks with identical (callable, shapes) signatures into a
    single vmapped execution.  Runs singletons and non-batchable tasks
    per task; a bundle whose fused call fails also runs per task, and is
    counted in `fallbacks`.

    Bundles key on the task's user `vmap_key` *and* the structural
    `vmap_signature` — the signature already embeds the callable's stable
    identity, so there is exactly one level of keying.  Measured execution
    seconds are recorded per task into bounded `StreamStat`s (`io_stat`,
    `run_stat`) with the same meaning as the real pools' metrics
    (DESIGN.md §10), so singleton fallbacks show up in throughput metrics
    instead of vanishing.
    """

    name = "vmap-cluster"

    def __init__(self, clock: Clock, window: float = 0.0,
                 max_bundle: int = 1024):
        self.clock = clock
        self.window = window
        self.max_bundle = max_bundle
        self._pending: dict[Any, list] = {}
        self._flush_scheduled = False
        self.bundles_executed = 0
        self.tasks_executed = 0
        self.fused_tasks = 0
        self.fallbacks = 0                 # bundles degraded to per-task
        self._vmapped_cache: dict = {}
        # measured execution seconds per task, same shape as the pool
        # metrics (io is zero here: no staging path on this provider)
        self.io_stat = StreamStat(cap=256)
        self.run_stat = StreamStat(cap=256)

    def submit(self, task: Task, when_done: Callable) -> None:
        if task.vmap_key is None or task.fn is None:
            t0 = perf_counter()
            ok, v, e = execute_task(task)
            self._observe(perf_counter() - t0)
            self.tasks_executed += 1
            when_done(ok, v, e)
            return
        key = (task.vmap_key, vmap_signature(task.fn, resolve_args(task)))
        bucket = self._pending.get(key)
        if bucket is None:
            self._pending[key] = bucket = []
        bucket.append((task, when_done))
        if len(bucket) >= self.max_bundle:
            self._flush_key(key)
        elif not self._flush_scheduled:
            self._flush_scheduled = True
            self.clock.schedule(self.window, self.flush)

    def _observe(self, run_s: float, io_s: float = 0.0) -> None:
        now = self.clock.now()
        self.io_stat.observe(now, io_s)
        self.run_stat.observe(now, run_s)

    def flush(self):
        self._flush_scheduled = False
        for key in list(self._pending):
            self._flush_key(key)

    def _flush_key(self, key):
        bundle = self._pending.pop(key, [])
        if not bundle:
            return
        self.bundles_executed += 1
        self.tasks_executed += len(bundle)
        tasks = [t for t, _ in bundle]
        place_outputs(tasks)
        results, exec_s, n_fused, fallback = execute_bundle(
            tasks[0].fn, tasks, self._vmapped_cache)
        self.fused_tasks += n_fused
        self.fallbacks += fallback is not None
        per_task = exec_s / max(1, len(bundle))
        for (t, cb), (ok, v, e) in zip(bundle, results):
            self._observe(per_task)
            cb(ok, v, e)

    def metrics(self) -> dict:
        """Bounded snapshot — safe at any task count."""
        return {
            "tasks": self.tasks_executed,
            "bundles": self.bundles_executed,
            "fused_tasks": self.fused_tasks,
            "fallbacks": self.fallbacks,
            "io_s": self.io_stat.summary(),
            "run_s": self.run_stat.summary(),
        }
