"""Task records, dataflow keying, and task-body execution.

Lowest layer of the scheduler stack (see DESIGN.md §1): a task is a
lightweight record — no OS thread, Karajan-style — carrying its callable,
argument futures, output future, and retry/provenance bookkeeping.  Both the
engine and every provider operate on these records; execution of the body
(`execute_task`) and simulated-duration lookup (`sim_duration`) live here so
providers and the Falkon service share one implementation.
"""
from __future__ import annotations

import hashlib
import itertools
import weakref
from typing import Optional

import numpy as np

from repro.core.futures import DataFuture, Padded

_task_ids = itertools.count()


class FnKeyRegistry:
    """Stable, GC-safe identity keys for callables.

    ``id(fn)`` is only unique while `fn` is alive: once collected, a new
    callable can land at the same address, so any cache keyed on raw ids
    (vmap bundles, compiled-function caches, prediction caches) can
    silently serve results for the *wrong* callable.  This registry hands
    out monotonically increasing serials and invalidates an id's entry the
    moment its callable dies (weakref finalizer), so a reused address gets
    a fresh serial.  Callables that cannot be weak-referenced (builtins,
    some C extensions) are pinned with a strong reference instead — their
    id can then never be reused while the registry lives.

    Single-threaded by contract: call only from the clock thread (the
    same contract every scheduler object follows, DESIGN.md §10).
    """

    __slots__ = ("_serial", "_by_id")

    def __init__(self):
        self._serial = itertools.count()
        self._by_id: dict = {}     # id(fn) -> (serial, weakref-or-strong-ref)

    def __len__(self) -> int:
        return len(self._by_id)

    def key(self, fn) -> int:
        i = id(fn)
        ent = self._by_id.get(i)
        if ent is not None:
            serial, ref = ent
            target = ref() if isinstance(ref, weakref.ref) else ref
            if target is fn:
                return serial
        serial = next(self._serial)
        try:
            ref = weakref.ref(fn, self._make_reaper(i))
        except TypeError:
            ref = fn                       # un-weakrefable: pin it
        self._by_id[i] = (serial, ref)
        return serial

    def _make_reaper(self, i: int):
        by_id = self._by_id

        def reap(dead_ref):
            # only drop the entry if it still belongs to the dead callable
            # — the id may already have been reused and re-registered
            ent = by_id.get(i)
            if ent is not None and ent[1] is dead_ref:
                del by_id[i]

        return reap


_fn_keys = FnKeyRegistry()


def stable_fn_key(fn) -> int:
    """Process-wide stable identity key for a callable (see
    `FnKeyRegistry`).  Unlike ``id(fn)``, the key is never reused for a
    different callable, so it is safe in long-lived signature caches."""
    return _fn_keys.key(fn)


def arg_signature(args) -> tuple:
    """Structural signature of a call's argument values: per-argument
    ``(shape, dtype-or-type-name)``.  Array-likes (numpy/JAX, anything
    with `.shape`) contribute shape + dtype; scalars and other literals
    contribute their type name.  Two calls with equal signatures can be
    stacked along a new leading axis and executed as one vmapped call."""
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(a, "dtype", ""))))
        else:
            sig.append(((), type(a).__name__))
    return tuple(sig)


def extent_of(value, ndim: int) -> tuple:
    """True extent of the trailing `ndim` dimensions of an array value: a
    `Padded` value's `extent`, else the array's own shape."""
    if isinstance(value, Padded):
        return value.extent
    shape = value.shape
    return tuple(int(n) for n in shape[len(shape) - ndim:])


class Uneven:
    """Declares that a procedure's array values have uneven extents
    (DESIGN.md §16), so that the device pool fuses its tasks by shape
    bucket instead of by exact shape.

    * `args`: positions of the arguments whose trailing `ndim` dimensions
      vary from task to task;
    * `extent`: the task's own extent — the true size of the trailing
      `ndim` dimensions of what the body computes over — as a function of
      the task's argument values, or the position of an uneven argument
      whose extent it shares, or None when the task has none of its own;
    * `padded_out`: the body's output has the own extent in its trailing
      dimensions, so a fused output is padded and carries its extent
      (`Padded`) or is cropped to it.

    The body is called as ``fn(shape, ext, *args)``: `shape` is the
    static shape it computes at (the bucket of the own extent, or the
    true extent itself when a task runs alone outside a bundle; None when
    there is no own extent), `ext` an int32 array of shape
    ``(len(args) + 1, ndim)``, the true extents of the uneven arguments
    in `args` order and then the own extent (zeros when there is none).
    Uneven arguments arrive padded past their extent with NaN (0 for
    integers; `repro.core.clustering.PAD_FILL`): the body must mask
    everything it reads by `ext`, or its answer is NaN.  Whatever it
    writes past the own extent of a padded output is replaced by the
    same fill.

    Example::

        def scale(shape, ext, img, k):
            h, w = ext[-1, 0], ext[-1, 1]
            rows = jnp.arange(shape[0])[:, None] < h
            cols = jnp.arange(shape[1])[None, :] < w
            return jnp.where(rows & cols, img * k, 0)

        proc = wf.atomic(scale, vmap_key="scale",
                         uneven=Uneven(args=(0,), extent=0,
                                       padded_out=True))
    """

    __slots__ = ("args", "extent", "ndim", "padded_out")

    def __init__(self, args=(), extent=None, ndim: int = 2,
                 padded_out: bool = False):
        self.args = tuple(args)
        self.extent = extent
        self.ndim = ndim
        self.padded_out = padded_out

    def own_extent(self, args) -> tuple | None:
        """The task's own true extent, from its argument values."""
        e = self.extent
        if e is None:
            return None
        if isinstance(e, int):
            return extent_of(args[e], self.ndim)
        return tuple(int(n) for n in e(*args))

    def extents(self, args):
        """The body's `ext` argument for one task (a numpy int32 array):
        true extents of the uneven arguments, then the own extent."""
        own = self.own_extent(args)
        rows = [extent_of(args[p], self.ndim) for p in self.args]
        rows.append(own if own is not None else (0,) * self.ndim)
        return np.asarray(rows, np.int32)

    def call_exact(self, fn, args):
        """Run the body for one task at true extents: no padding, the
        static shape is the own extent itself (resolved futures come
        cropped, `DataFuture.get`)."""
        return fn(self.own_extent(args), self.extents(args), *args)


class Task:
    """One schedulable unit: a lightweight record, not an OS thread.

    Carries the callable (`fn`, None for pure-simulation tasks), argument
    futures (`args`), the output `DataFuture`, declared file `inputs` for
    the data layer, and retry/provenance bookkeeping.  Engines create these
    via `Engine.submit`; providers and the Falkon service consume them.

    Example (normally done for you by `Engine.submit`)::

        t = Task("double", lambda x: 2 * x, [21], DataFuture(),
                 duration=None, app=None, retries=0, durable=False, key="")
        ok, value, err = execute_task(t)      # -> (True, 42, None)
    """

    # "span"/"path0" are observability fields (DESIGN.md §12), deliberately
    # NOT initialized in __init__: the tracing-off hot path never touches
    # them, and the engine assigns both at submit/ready time when a tracer
    # is attached ("path0" encodes parent critical path minus ready time).
    __slots__ = ("id", "name", "key", "fn", "args", "output", "duration",
                 "sim_value", "app", "attempt", "retries_left", "site",
                 "host", "created_time", "submit_time", "start_time",
                 "durable", "fault_check", "_falkon_done", "vmap_key",
                 "site_failures", "inputs", "span", "path0", "on_device",
                 "uneven", "elements")

    def __init__(self, name: str, fn, args, output: DataFuture,
                 duration: float | None, app: str | None,
                 retries: int, durable: bool, key: str,
                 inputs: tuple = ()):
        self.id = next(_task_ids)
        self.name = name
        self.key = key
        self.fn = fn
        self.args = args
        self.output = output
        self.duration = duration
        self.sim_value = None
        self.app = app
        self.attempt = 0
        self.retries_left = retries
        self.site = None
        self.host = ""
        self.created_time = 0.0
        self.submit_time = 0.0
        self.start_time = 0.0
        self.durable = durable
        self.fault_check = None
        self.vmap_key = None
        # where a fused output goes (DESIGN.md §11): a bundling provider
        # sets it on the clock thread as the bundle ships
        # (`clustering.place_outputs`); True keeps it on the device
        self.on_device = False
        # a procedure's declaration of uneven extents (`Uneven`, DESIGN.md
        # §16), and what its fused run padded: ``(true, padded)`` element
        # counts, written by the dispatcher with the bundle's results
        self.uneven = None
        self.elements = None
        # declared file inputs (DataObject tuple) — the data layer's
        # cache-aware dispatch keys on these; empty for compute-only tasks
        self.inputs = inputs
        # lazily allocated on first failure: a dict per task is measurable
        # overhead at 10^6 tasks and almost all tasks never fail
        self.site_failures: Optional[dict] = None


def task_key(name: str, args: list) -> str:
    """Dataflow-stable key for restart-log lookups (paper §3.12).

    Derived from the task name and the *identity* of its inputs (future
    names, array fingerprints, literal reprs) — not from graph position — so
    a modified-and-restarted program still resolves unchanged flows.
    """
    parts = [name]
    for a in args:
        if isinstance(a, DataFuture):
            parts.append(f"f:{a.name or a.id}")
        elif hasattr(a, "shape") and hasattr(a, "dtype"):
            # arrays: cheap structural fingerprint (repr would format the
            # whole buffer)
            parts.append(f"arr:{a.shape}:{a.dtype}:{id(a)}")
        else:
            parts.append(repr(a))
    return name + "#" + hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]


def sim_duration(task) -> float:
    d = getattr(task, "duration", None)
    return float(d) if d else 0.0


def execute_task(task):
    """Run a task body, returning (ok, value, error).

    Pure-simulation tasks (no callable, no fault check) take the early path:
    they dominate the paper-figure benchmarks and must cost O(ns), not a
    try/except plus an argument scan.
    """
    chk = getattr(task, "fault_check", None)
    fn = getattr(task, "fn", None)
    if chk is None and fn is None:
        return True, getattr(task, "sim_value", None), None
    if chk is not None:
        try:
            chk(task)
        except BaseException as err:  # noqa: BLE001
            return False, None, err
    if fn is None:
        return True, getattr(task, "sim_value", None), None
    try:
        args = [a.get() if hasattr(a, "get") and hasattr(a, "on_done") else a
                for a in task.args]
        decl = getattr(task, "uneven", None)
        if decl is not None:
            return True, decl.call_exact(fn, args), None
        return True, fn(*args), None
    except BaseException as err:  # noqa: BLE001 - engine handles retries
        return False, None, err
