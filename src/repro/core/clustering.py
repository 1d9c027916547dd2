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

from functools import partial
from math import prod
from time import perf_counter
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.futures import DECLARED, Padded
from repro.core.metrics import StreamStat
from repro.core.observability import span
from repro.core.providers import Provider
from repro.core.simclock import Clock
from repro.core.task import (Task, Uneven, arg_signature, execute_task,
                             extent_of, stable_fn_key)

# the TPU's (8, 128) tile: the first rung of the shape ladder for the
# second-to-last and last dimension of an uneven value (DESIGN.md §16)
TILE = (8, 128)
# what a floating-point value holds past its true extent: padding added as
# it stacks at its bucket, and whatever a body wrote past its own extent in
# a padded output.  NaN, so that a body that reads past its extents instead
# of masking by them poisons its answer (integer values pad with 0)
PAD_FILL = float("nan")


def rung(n: int, tile: int) -> int:
    """The shape ladder: `n` rounded up to whole tiles, then to the next
    count of tiles with at most 3 significant bits (1, 2, 3, 4, 5, 6, 7,
    8, 10, 12, 14, 16, 20, ...), so that a rung is at most 5/4 of the
    tiles it holds."""
    u = max(1, -(-int(n) // tile))
    e = max(0, u.bit_length() - 3)
    return (-(-u >> e) << e) * tile


def bucket(extent) -> tuple:
    """Shape bucket of the trailing dimensions `extent` of an uneven
    value: the last on the ladder of 128, the one before it of 8, any
    others of 1."""
    tiles = (1,) * max(0, len(extent) - 2) + TILE[-len(extent):]
    return tuple(rung(n, t) for n, t in zip(extent, tiles))


def width(n: int) -> int:
    """The K ladder: a bundle of `n` tasks of a declared procedure runs at
    the next power of 4 (1, 4, 16, 64, 256), the rest masked dummy rows."""
    k = 1
    while k < n:
        k *= 4
    return k


def uneven_signature(fn: Callable, decl: Uneven, args: list) -> tuple:
    """Bundle key of a task whose procedure declares uneven extents: the
    `vmap_signature` with each uneven argument's trailing dimensions and
    the own extent rounded up to their shape bucket, so tasks whose
    extents share a bucket fuse into one program."""
    sig = list(arg_signature(
        [a.data if isinstance(a, Padded) else a for a in args]))
    nd = decl.ndim
    for p in decl.args:
        shape, dtype = sig[p]
        sig[p] = (shape[:len(shape) - nd] + bucket(shape[len(shape) - nd:]),
                  dtype)
    own = decl.own_extent(args)
    return (stable_fn_key(fn), tuple(sig),
            None if own is None else bucket(own))


def vmap_signature(fn: Callable, args: list) -> tuple:
    """Tasks sharing this signature can be fused into one vmapped call.

    The callable component is a `stable_fn_key` serial (GC-safe), not
    ``id(fn)``; the argument component is the structural
    `arg_signature` (shapes + dtypes), so tasks with the same callable
    but unstackable argument shapes land in different bundles instead of
    failing the stack at execution time."""
    return (stable_fn_key(fn), arg_signature(args))


def resolve_args(task, padded: bool = False) -> list:
    """Argument values of a dispatched task (futures are resolved; a
    `Padded` value comes cropped, or with `padded` as it is held, for a
    task that declares uneven extents, DESIGN.md §16)."""
    if padded:
        return [a.get_padded() if hasattr(a, "get_padded") else a
                for a in task.args]
    return [a.get() if hasattr(a, "get") and hasattr(a, "on_done") else a
            for a in task.args]


def place_outputs(tasks) -> None:
    """Decide where each task's fused output goes, as its bundle ships
    (clock thread): it stays on the device (``task.on_device``) when
    every task that reads it is batchable, as the engine's dependency scan
    has recorded so far in ``output.consumers_fused``.  Answers no task
    reads, outputs read by an unbatched body and journaled or durable
    values go to the host.  A padded output (of a procedure that declares
    uneven extents, `Uneven.padded_out`) stays on the device only when
    every reader declares them too (`DECLARED`); otherwise it goes to the
    host, cropped (DESIGN.md §16).  A consumer that registers after the
    ship gets a host value, which stacks as any host input does
    (DESIGN.md §11), or, of a value kept padded, the value cropped
    (`DataFuture.get`)."""
    for t in tasks:
        c = t.output.consumers_fused
        t.on_device = c is DECLARED or (c is True and (
            t.uneven is None or not t.uneven.padded_out))


def _fused(fn: Callable, in_axes: tuple, name: str | None = None):
    """``jit(vmap(fn))`` that returns one output pytree per task: the
    unstack runs inside the one program, so a bundle size compiles one
    program and per-task device values cost no index operation after it.
    The program keeps the body's name (``jit_<body>``, or `name`)."""
    batched = jax.vmap(fn, in_axes=in_axes)

    def split(*args):
        k = jax.tree_util.tree_leaves(
            [a for a, ax in zip(args, in_axes) if ax == 0])[0].shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(batched(*args))
        return [treedef.unflatten([jax.lax.index_in_dim(x, i, keepdims=False)
                                   for x in leaves]) for i in range(k)]

    split.__name__ = name or getattr(batched, "__name__", "fused")
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

    Tasks of a procedure that declares uneven extents (``task.uneven``,
    DESIGN.md §16) share a shape bucket (`uneven_signature`) and take the
    same path with four steps more: uneven arguments are padded to the
    bucket as they stack (`_stack_padded`: `np.pad` for host values,
    inside the device stack for device values); the bundle widens to its
    rung of the K ladder (`width`) by repeating the first task, whose
    extra rows are dropped; the true extents go to the body ahead of its
    arguments as one traced int32 array (`Uneven.extents`); and a padded
    output is `Padded` where it stays on the device, cropped to its
    extent where it goes to the host.  Each such task's ``elements`` gets
    its true and padded element counts.  A one-task bundle of theirs
    runs fused too, at its bucket.

    `vmapped_cache` maps ``(stable_fn_key, in_axes)`` (declared: and the
    bucket) to the compiled program (`_fused`), so steady-state bundles
    pay zero retrace.

    Each phase is a span on the profiler's clock (`observability.span`):
    ``bundle.stack`` (resolving and stacking the arguments; one jitted
    stack per argument of device inputs), ``bundle.pad`` (an uneven
    argument's padded stack, inside ``bundle.stack``), ``bundle.h2d`` (the
    copy of a host stack to the device), ``bundle.call`` (the fused call,
    with a compile on a cache miss), ``bundle.wait`` (until its output is
    ready), ``bundle.d2h`` (`device_get` of the host-bound outputs, when
    there are any), ``bundle.crop`` (cutting them to their extents),
    ``bundle.split`` (handing each task its output; the device split
    itself runs inside the fused call), ``bundle.single`` (a one-task
    bundle's eager body) and ``bundle.fallback`` (the per-task re-run
    after a fused-path error).
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
    decl = tasks[live[0]].uneven
    if len(live) == 1 and decl is None:
        i = live[0]
        t0 = perf_counter()
        with span("bundle.single"):
            results[i] = _execute_unchecked(tasks[i])
        return results, perf_counter() - t0, 0, None
    t0 = perf_counter()
    try:
        with span("bundle.stack"):
            arg_lists = [resolve_args(tasks[i], decl is not None)
                         for i in live]
            m, n_args = len(live), len(arg_lists[0])
            # declared: the bundle runs at its rung of the K ladder, and
            # an uneven argument always stacks in a bundle of two or
            # more, so that whether tasks happen to share one never makes
            # a program
            k, uneven = (m, ()) if decl is None else (width(m), decl.args)
            # args identical across the bundle broadcast (in_axes=None)
            # instead of being stacked — no 256x weight copies
            shared = [m == 1 or (i not in uneven and all(
                al[i] is arg_lists[0][i] for al in arg_lists))
                for i in range(n_args)]
            in_axes = tuple(None if s else 0 for s in shared)
            stacked = [_stack_arg([al[i] for al in arg_lists], shared[i],
                                  k, decl if i in uneven else None)
                       for i in range(n_args)]
            if decl is not None:
                own = [decl.own_extent(al) for al in arg_lists]
                shape = None if own[0] is None else bucket(own[0])
                ext = np.stack([decl.extents(al) for al in arg_lists])
                stacked.insert(0, jnp.asarray(np.concatenate(
                    [ext, np.repeat(ext[:1], k - m, axis=0)])))
                in_axes = (0,) + in_axes
        vkey = (stable_fn_key(fn), in_axes) if decl is None else \
            (stable_fn_key(fn), in_axes, shape)
        vfn = vmapped_cache.get(vkey)
        if vfn is None:
            vfn = vmapped_cache[vkey] = (
                _fused(fn, in_axes) if decl is None else
                _fused(_declared_body(fn, decl, shape), in_axes,
                       getattr(fn, "__name__", None)))
        with span("bundle.call"):
            outs = vfn(*stacked)[:m]
        with span("bundle.wait"):
            jax.block_until_ready(outs)
        host = [j for j, i in enumerate(live) if not tasks[i].on_device]
        if host:
            with span("bundle.d2h"):
                fetched = jax.device_get([outs[j] for j in host])
            if decl is not None and decl.padded_out:
                with span("bundle.crop"):
                    fetched = [np.ascontiguousarray(Padded(v, own[j]).crop())
                               for j, v in zip(host, fetched)]
            for j, v in zip(host, fetched):
                outs[j] = v
        with span("bundle.split"):
            if decl is not None:
                _hand_out_declared(decl, [tasks[i] for i in live], outs,
                                   arg_lists, own, k)
            for i, r in zip(live, outs):
                results[i] = (True, r, None)
        return results, perf_counter() - t0, len(live), None
    except Exception as err:  # noqa: BLE001 — degrade, counted by callers
        t0 = perf_counter()
        with span("bundle.fallback"):
            for i in live:
                results[i] = _execute_unchecked(tasks[i])
        return results, perf_counter() - t0, 0, err


def _stack_arg(items: list, shared: bool, k: int, decl: Uneven | None):
    """One bundle argument: the value itself where the bundle shares it,
    else stacked along a new leading axis widened to `k` rows by
    repeating the first.  With `decl` it is an uneven argument, padded to
    its bucket as it stacks (``bundle.pad``)."""
    if decl is None:
        return items[0] if shared else _stack(items + items[:1] * (
            k - len(items)))
    with span("bundle.pad"):
        if shared:
            return _pad(items[0], decl.ndim)
        return _stack_padded(items + items[:1] * (k - len(items)),
                             decl.ndim)


def _declared_body(fn: Callable, decl: Uneven, shape) -> Callable:
    """A declared procedure's body at the bucket `shape`, taking the
    extents first: ``body(ext, *args)``; with padded outputs, each output
    element past the task's own extent is set to the fill inside the
    same program (`_filled_past`)."""
    if decl.padded_out:
        return _filled_past(fn, shape, decl.ndim)
    return partial(fn, shape)


def _hand_out_declared(decl: Uneven, tasks: list, outs: list,
                       arg_lists: list, own: list, k: int) -> None:
    """Wrap each padded output kept on the device as `Padded` with its
    extent, and give each task its ``(true, padded)`` element counts
    (own extent, else its uneven arguments), the bundle's dummy rows
    shared out over its tasks."""
    sizes = _elements(decl, arg_lists, own)
    dummy = (k - len(tasks)) * sizes[0][1] / len(tasks)
    for j, t in enumerate(tasks):
        if decl.padded_out and t.on_device:
            outs[j] = Padded(outs[j], own[j])
        t.elements = (sizes[j][0], sizes[j][1] + dummy)


def _filled_past(fn: Callable, shape: tuple, ndim: int) -> Callable:
    """The body of a procedure with padded outputs at the bucket `shape`,
    each output element past the task's own extent (``ext[-1]``) set to
    the fill (`PAD_FILL`, 0 for integers) inside the same program, so
    that what a reader finds there never depends on the body."""

    def body(ext, *args):
        def fill(x):
            inside = True
            for d in range(ndim):
                axis = x.ndim - ndim + d
                inside = inside & (jax.lax.broadcasted_iota(
                    jnp.int32, x.shape, axis) < ext[-1, d])
            return jnp.where(inside, x, jnp.asarray(_fill(x.dtype), x.dtype))

        return jax.tree.map(fill, fn(shape, ext, *args))

    return body


def _elements(decl: Uneven, arg_lists: list, own: list) -> list:
    """``(true, padded)`` element counts of each task: of its own extent
    where it has one, else summed over its uneven arguments."""
    out = []
    for al, e in zip(arg_lists, own):
        exts = [e] if e is not None else [
            extent_of(al[p], decl.ndim) for p in decl.args]
        out.append((sum(prod(x) for x in exts),
                    sum(prod(bucket(x)) for x in exts)))
    return out


def _bucket_shape(value, ndim: int) -> tuple:
    """Full shape of an uneven value at its bucket."""
    data = value.data if isinstance(value, Padded) else value
    return (tuple(data.shape[:data.ndim - ndim])
            + bucket(extent_of(value, ndim)))


def _pad(value, ndim: int):
    """One uneven argument at its bucket, unstacked (a one-task bundle):
    a `Padded` value's data as it is, a host array padded with `np.pad`,
    a device array padded on the device."""
    if isinstance(value, Padded):
        return value.data
    shape = _bucket_shape(value, ndim)
    fill = _fill(value.dtype)
    if isinstance(value, np.ndarray):
        return np.pad(value, [(0, s - n) for s, n in zip(shape, value.shape)],
                      constant_values=fill)
    return _pad_on_device(value, shape=shape, fill=fill)


def _stack_padded(items: list, ndim: int):
    """One uneven bundle argument stacked at its shape bucket: host arrays
    padded with `np.pad` and copied up once, device values (`Padded`, or
    arrays at their true extent) padded inside the device stack."""
    datas = [a.data if isinstance(a, Padded) else a for a in items]
    shape = _bucket_shape(items[0], ndim)
    if all(isinstance(a, np.ndarray) for a in datas):
        fill = _fill(datas[0].dtype)
        host = np.stack([np.pad(a, [(0, s - n) for s, n in
                                    zip(shape, a.shape)],
                                constant_values=fill) for a in datas])
        with span("bundle.h2d"):
            return jnp.asarray(host)
    return _stack_on_device(*datas, shape=shape,
                            fill=_fill(datas[0].dtype))


def _fill(dtype):
    return PAD_FILL if jnp.issubdtype(dtype, jnp.floating) else 0


@partial(jax.jit, static_argnames=("shape", "fill"))
def _pad_on_device(a, shape, fill):
    return jnp.pad(a, [(0, s - n) for s, n in zip(shape, a.shape)],
                   constant_values=fill)


@partial(jax.jit, static_argnames=("shape", "fill"))
def _stack_on_device(*items, shape=None, fill=0.0):
    """Device-resident items of one bundle argument stacked in one program
    per (K, shape, dtype), not K eager ``expand_dims`` and a
    ``concatenate``.  With `shape`, each item is first padded with `fill`
    up to it (a no-op for items already at the bucket)."""
    if shape is not None:
        items = [jnp.pad(a, [(0, s - n) for s, n in zip(shape, a.shape)],
                         constant_values=fill) for a in items]
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
        decl = getattr(task, "uneven", None)
        if decl is not None:
            return True, decl.call_exact(fn, resolve_args(task)), None
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
