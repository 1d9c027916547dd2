"""Device-native task clustering: vmap-bundling of small JAX tasks.

The paper's clustering (§3.13) amortizes batch-scheduler submission overhead
by bundling small jobs.  On an accelerator the analogous per-task cost is
*dispatch + kernel launch* of many small jitted computations; the adaptation
fuses ready tasks that share a callable and argument shapes into ONE batched
device call via `jax.vmap` — one launch, one dispatch, full-width compute.

The consumer is `DeviceExecutorPool` (`repro.core.devicepool`,
DESIGN.md §11), the pool behind `FalkonService(pool=...)`: bundles form on
the clock thread (`bundle_key`, `place_outputs`), execute on a dispatcher
thread (`execute_bundle`), and measured completions re-enter through
`Clock.post_release`.

Signature identity is GC-safe: callables are keyed through
`repro.core.task.stable_fn_key`, never raw ``id(fn)`` — a collected
callable's address can be reused by a new function, and an id-keyed bundle
or jit cache would then silently fuse (or run) the wrong callable.

benchmarks/device_batching.py measures the amortization exactly like the
paper's Fig 6 measures PBS-overhead amortization.
"""
from __future__ import annotations

import threading
from functools import partial
from math import prod
from time import perf_counter
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.futures import DECLARED, DataFuture, Held, Padded
from repro.core.observability import span
from repro.core.task import Uneven, arg_signature, extent_of, stable_fn_key

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


def resolve_args(task, fused: bool = False) -> list:
    """Argument values of a dispatched task: futures resolved with `get()`
    (a `Held` value unwrapped).  With `fused`, as the task's fused bundle
    reads them: a held value the bundle can take is kept as it is — a
    `Padded` value for a task that declares uneven extents (DESIGN.md
    §16), a `Row` for one that does not (DESIGN.md §11)."""
    if fused:
        keep = Row if task.uneven is None else Padded
        return [_as_read(a.get_held(), keep) if isinstance(a, DataFuture)
                else a for a in task.args]
    return [a.get() if hasattr(a, "get") and hasattr(a, "on_done") else a
            for a in task.args]


def _as_read(value, keep: type):
    return (value.unwrap() if isinstance(value, Held)
            and type(value) is not keep else value)


def bundle_key(task) -> tuple:
    """The key of the bundle a batchable task joins: its `vmap_key` and
    the signature of its arguments as its bundle reads them
    (`vmap_signature`, or `uneven_signature` for a task that declares
    uneven extents, whose held `Padded` arguments bucket by extent)."""
    args, decl = resolve_args(task, True), task.uneven
    return (task.vmap_key,
            vmap_signature(task.fn, args) if decl is None else
            uneven_signature(task.fn, decl, args))


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


class Programs(dict):
    """The compiled programs of one bundle executor, keyed as
    `execute_bundle` says, and its counts of how fused bundles took their
    arguments: `args_passed_through` (an earlier bundle's whole output
    stack handed on as it is), `args_restacked` (an argument stacked again
    on the device from device values) and `stacks_cut` (output stacks cut
    into rows for readers that could not take them whole).  Dispatcher
    threads add to the counts (`count`); any thread may read them."""

    def __init__(self):
        super().__init__()
        self.args_passed_through = 0
        self.args_restacked = 0
        self.stacks_cut = 0
        self._lock = threading.Lock()

    def count(self, tally: dict) -> None:
        """Add one bundle's counts, ``{name: n}``."""
        with self._lock:
            for name, n in tally.items():
                setattr(self, name, getattr(self, name) + n)


@jax.jit
def _cut_rows(data):
    """Every row of a stacked pytree, as one value each: one program per
    (K, leaf shapes, dtypes)."""
    leaves, treedef = jax.tree_util.tree_flatten(data)
    return [treedef.unflatten([jax.lax.index_in_dim(x, i, keepdims=False)
                               for x in leaves])
            for i in range(leaves[0].shape[0])]


class Stack:
    """A fused bundle's output held whole on the device (DESIGN.md §11):
    `data` is the pytree its program returned, with the bundle's K tasks
    along the leading axis of every leaf; each task's output is a `Row`
    of it.  A next bundle whose tasks read exactly its K rows in order
    takes `data` whole as an argument.  Any other reader cuts it (`cut`),
    once, into `rows`, one value a task, and `data` is dropped so the
    device does not hold it twice.  `size` is K."""

    __slots__ = ("data", "size", "rows", "_lock")

    def __init__(self, data, size: int):
        self.data = data
        self.size = size
        self.rows = None
        self._lock = threading.Lock()

    def cut(self) -> bool:
        """Cut `data` into `rows` unless that is done; True when this call
        did it.  Safe from any thread."""
        with self._lock:
            if self.rows is not None:
                return False
            self.rows = _cut_rows(self.data)
            self.data = None
            return True


class Row(Held):
    """Row `index` of a `Stack`: a fused task's output kept on the device
    (DESIGN.md §11).  It answers `shape` and `dtype` as the row would
    (for an array output), so bundle keys are those of the row.  A fused
    reader that declares nothing receives it as it is (`resolve_args`);
    every other reader gets `unwrap()`, the row cut from its stack.

    Example::

        r = Row(Stack(jnp.zeros((4, 3)), 4), 1)
        r.shape                        # -> (3,)
        r.unwrap()                     # -> a (3,) array, the stack cut
    """

    __slots__ = ("stack", "index")

    def __init__(self, stack: Stack, index: int):
        self.stack = stack
        self.index = index

    def unwrap(self):
        """The row as a value of its own (the whole stack is cut once,
        `Stack.cut`)."""
        rows = self.stack.rows
        if rows is None:
            self.stack.cut()
            rows = self.stack.rows
        return rows[self.index]

    def _leaf(self):
        # `cut` sets `rows` before it drops `data`: read in the other order
        data = self.stack.data
        if data is not None:
            return data, True
        return self.stack.rows[self.index], False

    @property
    def shape(self) -> tuple:
        v, stacked = self._leaf()
        if not hasattr(v, "shape"):
            raise AttributeError("shape")   # a pytree output
        return tuple(v.shape[1:]) if stacked else tuple(v.shape)

    @property
    def dtype(self):
        v, _ = self._leaf()
        if not hasattr(v, "dtype"):
            raise AttributeError("dtype")
        return v.dtype

    def __array__(self, dtype=None, copy=None):
        v = np.asarray(self.unwrap())
        return v if dtype is None else v.astype(dtype)

    def __repr__(self):
        return f"<Row {self.index} of a stack>"


def _fused(fn: Callable, in_axes: tuple, name: str | None = None):
    """``jit(vmap(fn))`` for a procedure that declares uneven extents,
    returning one output pytree per task: the unstack runs inside the one
    program, so each task's `Padded` output is a device value of its own
    (DESIGN.md §16).  The program keeps the body's name (``jit_<body>``,
    or `name`)."""
    batched = jax.vmap(fn, in_axes=in_axes)

    def split(*args):
        k = jax.tree_util.tree_leaves(
            [a for a, ax in zip(args, in_axes) if ax == 0])[0].shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(batched(*args))
        return [treedef.unflatten([jax.lax.index_in_dim(x, i, keepdims=False)
                                   for x in leaves]) for i in range(k)]

    split.__name__ = name or getattr(batched, "__name__", "fused")
    return jax.jit(split)


def execute_bundle(fn: Callable, tasks: list, vmapped_cache: Programs):
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

    Placement (DESIGN.md §11): the program of a procedure that declares
    nothing returns its output as one stack, the K tasks along the
    leading axis (`jit(vmap(body))`, no split).  A task whose ``on_device``
    is set (`place_outputs`) gets a `Row` of it: the stack and its row
    index.  The others come from one `device_get`: NumPy rows of the
    whole stack where every output of the bundle goes to the host, else
    their rows alone.  A one-task bundle runs its body eagerly and
    returns what the body returns.

    Arguments (DESIGN.md §11): an argument the whole bundle shares is
    broadcast (``in_axes=None``).  An argument whose K items are rows
    0..K-1 of one stack of exactly K rows, in order, is that stack, passed
    through whole (`args_passed_through`).  Any other argument stacks from
    its items: a `Row` among them first cuts its whole stack, once, into
    one value a row (``bundle.cut``, `stacks_cut`), and device items stack
    in one program per (K, shape, dtype) (`args_restacked`).

    Tasks of a procedure that declares uneven extents (``task.uneven``,
    DESIGN.md §16) share a shape bucket (`uneven_signature`) and keep an
    output per task, so that each can be `Padded` with its own extent,
    with four steps more: uneven arguments are padded to the bucket as
    they stack (`_stack_padded`: `np.pad` for host values, inside the
    device stack for device values); the bundle widens to its rung of the
    K ladder (`width`) by repeating the first task, whose extra rows are
    dropped; the true extents go to the body ahead of its arguments as one
    traced int32 array (`Uneven.extents`); and a padded output is `Padded`
    where it stays on the device, cropped to its extent where it goes to
    the host.  They never pass a stack through (their readers come in
    any order, and a cut program per bucket would compile outside the
    warmed set).  Each such task's ``elements`` gets its true and padded
    element counts.  A one-task bundle of theirs runs fused too, at its
    bucket.

    `vmapped_cache` maps ``(stable_fn_key, in_axes)`` (declared: and the
    bucket) to the compiled program, so steady-state bundles pay zero
    retrace, and keeps the argument counts above.

    Each phase is a span on the profiler's clock (`observability.span`):
    ``bundle.stack`` (resolving and stacking the arguments; one jitted
    stack per argument of device inputs), ``bundle.cut`` (a stack cut
    into rows, inside ``bundle.stack``), ``bundle.pad`` (an uneven
    argument's padded stack, inside ``bundle.stack``), ``bundle.h2d`` (the
    copy of a host stack to the device), ``bundle.call`` (the fused call,
    with a compile on a cache miss), ``bundle.wait`` (until its output is
    ready), ``bundle.d2h`` (`device_get` of the host-bound outputs, when
    there are any), ``bundle.crop`` (cutting them to their extents),
    ``bundle.split`` (handing each task its output: a `Row`, a host row,
    or a declared task's own output), ``bundle.single`` (a one-task
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
    tally = {"args_passed_through": 0, "args_restacked": 0, "stacks_cut": 0}
    try:
        with span("bundle.stack"):
            arg_lists = [resolve_args(tasks[i], True) for i in live]
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
                                  k, decl if i in uneven else None, tally)
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
                jax.jit(jax.vmap(fn, in_axes=in_axes)) if decl is None else
                _fused(_declared_body(fn, decl, shape), in_axes,
                       getattr(fn, "__name__", None)))
        with span("bundle.call"):
            out = vfn(*stacked)
        with span("bundle.wait"):
            jax.block_until_ready(out)
        if decl is None:
            outs = _hand_out_stack(out, [tasks[i] for i in live])
        else:
            outs = _hand_out_declared(decl, [tasks[i] for i in live],
                                      out[:m], arg_lists, own, k)
        for i, r in zip(live, outs):
            results[i] = (True, r, None)
        vmapped_cache.count(tally)
        return results, perf_counter() - t0, len(live), None
    except Exception as err:  # noqa: BLE001 — degrade, counted by callers
        vmapped_cache.count(tally)
        t0 = perf_counter()
        with span("bundle.fallback"):
            for i in live:
                results[i] = _execute_unchecked(tasks[i])
        return results, perf_counter() - t0, 0, err


def _stack_arg(items: list, shared: bool, k: int, decl: Uneven | None,
               tally: dict):
    """One bundle argument: the value itself where the bundle shares it;
    the stack that `items` are the rows of, in order, where there is one
    (`_whole_stack`); else stacked along a new leading axis widened to `k`
    rows by repeating the first.  With `decl` it is an uneven argument,
    padded to its bucket as it stacks (``bundle.pad``)."""
    if decl is None:
        if shared:
            return _plain(items[0], tally)
        whole = _whole_stack(items)
        if whole is not None:
            tally["args_passed_through"] += 1
            return whole
        items = [_plain(a, tally) for a in items]
        if not all(isinstance(a, np.ndarray) for a in items):
            tally["args_restacked"] += 1
        return _stack(items + items[:1] * (k - len(items)))
    with span("bundle.pad"):
        if shared:
            return _pad(items[0], decl.ndim)
        return _stack_padded(items + items[:1] * (k - len(items)),
                             decl.ndim)


def _whole_stack(items: list):
    """The data of the one uncut `Stack` whose rows `items` are, all of
    them and in order, else None."""
    first = items[0]
    if type(first) is not Row:
        return None
    stack = first.stack
    data = stack.data
    if data is None or stack.size != len(items):
        return None
    for j, a in enumerate(items):
        if type(a) is not Row or a.stack is not stack or a.index != j:
            return None
    return data


def _plain(value, tally: dict):
    """A held argument value as a value of its own: a `Row` cut from its
    stack, the whole stack at once and once only (``bundle.cut``)."""
    if type(value) is not Row:
        return value
    if value.stack.rows is None:
        with span("bundle.cut"):
            tally["stacks_cut"] += value.stack.cut()
    return value.unwrap()


def _hand_out_stack(out, tasks: list) -> list:
    """Each task's output from its bundle's output stack `out`: a `Row`
    where it stays on the device; host-bound ones from one `device_get`
    (``bundle.d2h``) of the whole stack where every output goes to the
    host, else of their rows alone."""
    host = [j for j, t in enumerate(tasks) if not t.on_device]
    whole = len(host) == len(tasks)
    fetched = {}
    if host:
        with span("bundle.d2h"):
            if whole:
                out = jax.device_get(out)
            else:
                fetched = dict(zip(host, jax.device_get(
                    [_row(out, j) for j in host])))
    with span("bundle.split"):
        if whole:
            return [_row(out, j) for j in host]
        held = Stack(out, len(tasks))
        return [Row(held, j) if t.on_device else fetched[j]
                for j, t in enumerate(tasks)]


def _row(tree, j: int):
    return jax.tree_util.tree_map(lambda x: x[j], tree)


def _declared_body(fn: Callable, decl: Uneven, shape) -> Callable:
    """A declared procedure's body at the bucket `shape`, taking the
    extents first: ``body(ext, *args)``; with padded outputs, each output
    element past the task's own extent is set to the fill inside the
    same program (`_filled_past`)."""
    if decl.padded_out:
        return _filled_past(fn, shape, decl.ndim)
    return partial(fn, shape)


def _hand_out_declared(decl: Uneven, tasks: list, outs: list,
                       arg_lists: list, own: list, k: int) -> list:
    """Each declared task's own output: host-bound ones fetched in one
    `device_get` (``bundle.d2h``) and cut to their extents
    (``bundle.crop``), padded ones kept on the device wrapped as `Padded`
    with their extent; and each task's ``(true, padded)`` element counts
    (own extent, else its uneven arguments), the bundle's dummy rows
    shared out over its tasks."""
    outs = list(outs)
    host = [j for j, t in enumerate(tasks) if not t.on_device]
    if host:
        with span("bundle.d2h"):
            fetched = jax.device_get([outs[j] for j in host])
        if decl.padded_out:
            with span("bundle.crop"):
                fetched = [np.ascontiguousarray(Padded(v, own[j]).crop())
                           for j, v in zip(host, fetched)]
        for j, v in zip(host, fetched):
            outs[j] = v
    with span("bundle.split"):
        sizes = _elements(decl, arg_lists, own)
        dummy = (k - len(tasks)) * sizes[0][1] / len(tasks)
        for j, t in enumerate(tasks):
            if decl.padded_out and t.on_device:
                outs[j] = Padded(outs[j], own[j])
            t.elements = (sizes[j][0], sizes[j][1] + dummy)
    return outs


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
