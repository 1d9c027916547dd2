"""Uneven extents fused in padded, masked shape buckets (DESIGN.md §16).

A procedure that declares its uneven extents (`Uneven`) fuses by shape
bucket instead of by exact shape: arguments are padded to the bucket as
they stack, the bundle widens to its rung of the K ladder with dummy rows,
the body gets the true extents as a traced argument and masks by them, and
a padded output stays on the device with its extent (`Padded`) for
declared readers or is cropped to it on the host.  Checked here against
per-task execution at true extents, with padding filled with NaN, against
a body that ignores its extents, and for the bound on programs compiled.
Procedures that declare nothing bundle as before.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DRPConfig, DeviceExecutorPool, Engine, FalkonConfig,
                        FalkonProvider, FalkonService, Padded, RealClock,
                        Tracer, Uneven, Workflow)
from repro.core import clustering
from repro.core.clustering import (bucket, rung, uneven_signature,
                                   vmap_signature, width)
from repro.core.futures import DECLARED
from repro.core.task import Task, execute_task


def stack(executors=64, max_bundle=64):
    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=max_bundle)
    cfg = FalkonConfig(drp=DRPConfig(
        min_executors=executors, max_executors=executors,
        alloc_latency=0.0, alloc_chunk=executors))
    svc = FalkonService(clock, cfg, pool=pool)
    svc.provision(executors)
    eng = Engine(clock)
    eng.add_site("dev", FalkonProvider(svc), capacity=executors)
    return Workflow("uneven", eng), eng, svc, pool


def _mask(shape, e):
    r = jnp.arange(shape[0])[:, None]
    c = jnp.arange(shape[1])[None, :]
    return (r < e[0]) & (c < e[1])


def centre(shape, ext, img, k):
    """An image less its mean, times `k`: the mean over the true extent
    only, so padding read as data would move every pixel."""
    m = _mask(shape, ext[-1])
    mean = jnp.sum(jnp.where(m, img, 0.0)) / (ext[-1, 0] * ext[-1, 1])
    return jnp.where(m, (img - mean) * k, 0.0)


def centre_unmasked(shape, ext, img, k):
    """`centre` that ignores its extents (a planted fault)."""
    return (img - jnp.mean(img)) * k


def window_stats(shape, ext, img, off):
    """Mean and sum of squares of ``img[oy:oy+h, ox:ox+w]`` (own extent
    ``h, w``), cut at the static bucket `shape` and masked.  The cut may
    reach past the padded image: its start is clamped as `dynamic_slice`
    does and rolled back into place."""
    sy = jnp.minimum(off[0], img.shape[0] - shape[0])
    sx = jnp.minimum(off[1], img.shape[1] - shape[1])
    win = jax.lax.dynamic_slice(img, (sy, sx), shape)
    win = jnp.roll(jnp.roll(win, sy - off[0], axis=0), sx - off[1], axis=1)
    m = _mask(shape, ext[-1])
    x = jnp.where(m, win, 0.0)
    n = ext[-1, 0] * ext[-1, 1]
    return jnp.stack([jnp.sum(x) / n, jnp.sum(x * x)])


CENTRE = Uneven(args=(0,), extent=0, padded_out=True)
STATS = Uneven(args=(0,), extent=lambda img, off: off[2:4])


def images(n, seed=0, lo=9, hi=70):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        h, w = rng.integers(lo, hi, 2)
        out.append(rng.normal(3.0, 1.0, (h, w)).astype(np.float32))
    return out


def exact(fn, decl, args):
    return np.asarray(decl.call_exact(fn, list(args)))


# ---------------------------------------------------------------------------
# the ladders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile", [8, 128])
def test_shape_ladder_rounds_to_tiles_then_at_most_a_quarter_up(tile):
    rungs = sorted({rung(n, tile) for n in range(1, 40 * tile)})
    assert rungs[:8] == [k * tile for k in range(1, 9)]
    for n in range(1, 40 * tile):
        r = rung(n, tile)
        assert r >= n and r % tile == 0 and rung(r, tile) == r
        assert r <= 1.25 * (-(-n // tile) * tile)
    assert bucket((1030, 540)) == (1280, 640)
    assert bucket((2, 1030, 540)) == (2, 1280, 640)
    assert bucket((2200,)) == (2560,)


def test_width_ladder_is_powers_of_four():
    assert [width(n) for n in (1, 2, 4, 5, 16, 17, 64, 65, 256)] == \
        [1, 4, 4, 16, 16, 64, 64, 256, 256]


# ---------------------------------------------------------------------------
# fused against per-task at true extents
# ---------------------------------------------------------------------------

def test_padded_fusion_equals_per_task_execution_at_true_extents():
    wf, eng, svc, pool = stack()
    cen = wf.atomic(centre, vmap_key="centre", uneven=CENTRE)
    stats = wf.atomic(window_stats, vmap_key="stats", uneven=STATS)
    imgs = images(16, lo=9, hi=40)
    rng = np.random.default_rng(1)
    offs, cens, outs = [], [], []
    for img in imgs:
        h, w = img.shape
        wh, ww = rng.integers(1, h + 1), rng.integers(1, w + 1)
        off = np.array([rng.integers(0, h - wh + 1),
                        rng.integers(0, w - ww + 1), wh, ww], np.int32)
        offs.append(off)
        c = cen(img, np.float32(2.0))
        cens.append(c)
        outs.append(stats(c, off))
    eng.run()
    svc.shutdown()
    for img, off, c, o in zip(imgs, offs, cens, outs):
        want_c = exact(centre, CENTRE, (img, np.float32(2.0)))
        # read by a declared task: kept on the device, padded; `get()`
        # hands out the value cropped to its extent
        got_c = c.get_padded()
        assert isinstance(got_c, Padded) and got_c.extent == img.shape
        np.testing.assert_allclose(np.asarray(c.get()), want_c,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o.get()),
                                   exact(window_stats, STATS, (want_c, off)),
                                   rtol=1e-4, atol=1e-4)
    m = pool.metrics()
    assert m["fallbacks"] == 0
    # 16 extents in far fewer bundles than tasks
    assert m["bundles_run"] < 16
    assert m["padded_tasks"] == 32
    assert 0 < m["true_elements"] < m["padded_elements"]


def test_execute_task_runs_a_declared_body_at_true_extents():
    img = images(1, seed=3)[0]
    t = Task("centre", centre, [img, np.float32(1.5)], None, None, None, 0,
             False, "k")
    t.uneven = CENTRE
    ok, v, err = execute_task(t)
    assert ok, err
    assert v.shape == img.shape
    np.testing.assert_allclose(np.asarray(v), (img - img.mean()) * 1.5,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# padding never reaches an answer; a body has to mask
# ---------------------------------------------------------------------------

def run_chain(body, imgs):
    wf, eng, svc, pool = stack()
    cen = wf.atomic(body, vmap_key="centre", uneven=CENTRE)
    stats = wf.atomic(window_stats, vmap_key="stats", uneven=STATS)
    offs = [np.array([0, 0, img.shape[0], img.shape[1]], np.int32)
            for img in imgs]
    cens = [cen(img, np.float32(1.0)) for img in imgs]
    futs = [stats(c, off) for c, off in zip(cens, offs)]
    eng.run()
    svc.shutdown()
    pool.centred = [c.get_padded() for c in cens]
    return [np.asarray(f.get()) for f in futs], offs, pool


def test_padding_filled_with_nan_never_reaches_an_answer():
    assert np.isnan(clustering.PAD_FILL)
    imgs = images(10, seed=5, hi=40)
    got, offs, pool = run_chain(centre, imgs)
    # the centred images stayed on the device, padded, between the stages,
    # and their padding holds the fill
    assert pool.outputs_on_device >= 10
    for img, p in zip(imgs, pool.centred):
        data = np.asarray(p.data)
        assert isinstance(p, Padded) and data.shape == bucket(img.shape)
        inside = np.zeros(data.shape, bool)
        inside[:img.shape[0], :img.shape[1]] = True
        assert np.all(np.isfinite(data[inside]))
        assert np.all(np.isnan(data[~inside]))
    for img, off, g in zip(imgs, offs, got):
        assert np.all(np.isfinite(g))
        want = exact(window_stats, STATS,
                     (exact(centre, CENTRE, (img, np.float32(1.0))), off))
        np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fill", [float("nan"), 0.0])
def test_a_body_that_ignores_its_extents_fails_the_comparison(monkeypatch,
                                                              fill):
    # the fill makes it loud (NaN); zero padding moves the mean as well
    monkeypatch.setattr(clustering, "PAD_FILL", fill)
    imgs = images(10, seed=6, hi=40)
    got, offs, _pool = run_chain(centre_unmasked, imgs)
    gaps = [np.max(np.abs(g - exact(
        window_stats, STATS, (exact(centre, CENTRE, (img, np.float32(1.0))),
                              off))))
        for img, off, g in zip(imgs, offs, got)]
    assert not all(g <= 1e-2 for g in gaps)


# ---------------------------------------------------------------------------
# placement of padded outputs
# ---------------------------------------------------------------------------

def plain_sum(x):
    return jnp.sum(x)


def test_a_padded_output_stays_only_for_declared_readers():
    wf, eng, svc, pool = stack()
    cen = wf.atomic(centre, vmap_key="centre", uneven=CENTRE)
    stats = wf.atomic(window_stats, vmap_key="stats", uneven=STATS)
    plain = wf.atomic(plain_sum, vmap_key="plain")
    imgs = images(8, seed=7)
    declared = [cen(img, np.float32(1.0)) for img in imgs[:4]]
    for c, img in zip(declared, imgs[:4]):
        stats(c, np.array([0, 0, 4, 4], np.int32))
    mixed = [cen(img, np.float32(1.0)) for img in imgs[4:]]
    sums = [plain(c) for c in mixed]
    assert all(c.consumers_fused is DECLARED for c in declared)
    assert all(c.consumers_fused is True for c in mixed)
    eng.run()
    svc.shutdown()
    for c, img in zip(declared, imgs[:4]):
        v = c.get_padded()
        assert isinstance(v, Padded) and v.extent == img.shape
        assert v.data.shape == bucket(img.shape)
        assert c.get().shape == img.shape
    for c, s, img in zip(mixed, sums, imgs[4:]):
        v = c.get()
        assert isinstance(v, np.ndarray) and v.shape == img.shape
        np.testing.assert_allclose(float(s.get()), float(v.sum()),
                                   rtol=1e-4, atol=1e-3)


def unbatched_double(x):
    return 2.0 * np.asarray(x)


@pytest.mark.parametrize("reader", ["fused", "unbatched", "then"])
def test_a_late_undeclared_reader_of_a_padded_value_gets_it_cropped(reader):
    # the outputs ship while every reader so far declares uneven extents,
    # so they stay on the device padded; a reader that registers later
    # and declares nothing must still get the value at its true extent
    wf, eng, svc, pool = stack()
    cen = wf.atomic(centre, vmap_key="centre", uneven=CENTRE)
    stats = wf.atomic(window_stats, vmap_key="stats", uneven=STATS)
    imgs = images(6, seed=10)
    outs = [cen(img, np.float32(1.0)) for img in imgs]
    for c in outs:
        stats(c, np.array([0, 0, 4, 4], np.int32))
    eng.run()
    assert all(isinstance(c.get_padded(), Padded) for c in outs)
    if reader == "fused":
        late = [wf.atomic(double, vmap_key="double")(c) for c in outs]
    elif reader == "unbatched":
        late = [wf.atomic(unbatched_double)(c) for c in outs]
    else:
        late = [wf.then(c, unbatched_double) for c in outs]
    eng.run()
    svc.shutdown()
    for img, f in zip(imgs, late):
        v = np.asarray(f.get())
        assert v.shape == img.shape and np.all(np.isfinite(v))
        np.testing.assert_allclose(
            v, 2.0 * exact(centre, CENTRE, (img, np.float32(1.0))),
            rtol=1e-5, atol=1e-5)
    assert pool.fallbacks == 0


def test_spans_carry_true_and_padded_elements():
    wf, eng, svc, pool = stack()
    tr = Tracer(sample_every=1)
    eng.tracer = svc.tracer = pool.tracer = tr
    cen = wf.atomic(centre, vmap_key="centre", uneven=CENTRE)
    imgs = images(5, seed=8)
    for img in imgs:
        cen(img, np.float32(1.0))
    eng.run()
    svc.shutdown()
    spans = [sp for sp in tr.spans if sp.padded_elements > 0]
    assert len(spans) == 5
    assert sum(sp.true_elements for sp in spans) == \
        sum(img.size for img in imgs) == pool.true_elements
    assert sum(sp.padded_elements for sp in spans) == \
        pytest.approx(pool.padded_elements)
    # buckets, and the dummy rows of the K ladder on top
    assert pool.padded_elements >= sum(int(np.prod(bucket(img.shape)))
                                       for img in imgs)
    assert {"true_elements", "padded_elements"} <= set(spans[0].to_dict())


# ---------------------------------------------------------------------------
# undeclared procedures are untouched; programs are bounded
# ---------------------------------------------------------------------------

def double(x):
    return 2.0 * x


def test_an_undeclared_procedure_keys_and_compiles_as_before():
    wf, eng, svc, pool = stack()
    dbl = wf.atomic(double, vmap_key="double")
    xs = [np.full((3, 5), float(i), np.float32) for i in range(6)]
    futs = [dbl(x) for x in xs]
    keys = []
    real = pool._ship

    def ship(bundle):
        t = bundle[0][0]
        keys.append(t.uneven)
        return real(bundle)

    pool._ship = ship
    eng.run()
    svc.shutdown()
    assert keys and all(k is None for k in keys)
    # the bundle key is the exact-shape signature, the program cache key
    # (callable, in_axes) with no bucket, the program named after the body
    x = xs[0]
    assert vmap_signature(double, [x]) == \
        (clustering.stable_fn_key(double), (((3, 5), "float32"),))
    assert list(pool._vmapped_cache) == \
        [(clustering.stable_fn_key(double), (0,))]
    prog = next(iter(pool._vmapped_cache.values()))
    assert "jit_double" in prog.lower(np.stack(xs)).as_text()
    for f, x in zip(futs, xs):
        v = f.get()
        assert not isinstance(v, Padded)
        np.testing.assert_array_equal(np.asarray(v), 2.0 * x)
    assert pool.padded_tasks == 0


def test_uneven_signature_buckets_extents_and_undeclared_stays_exact():
    a = np.zeros((30, 100), np.float32)
    b = np.zeros((31, 120), np.float32)
    k = np.float32(1.0)
    assert uneven_signature(centre, CENTRE, [a, k]) == \
        uneven_signature(centre, CENTRE, [b, k])
    assert vmap_signature(centre, [a, k]) != vmap_signature(centre, [b, k])
    c = np.zeros((60, 100), np.float32)
    assert uneven_signature(centre, CENTRE, [a, k]) != \
        uneven_signature(centre, CENTRE, [c, k])


def test_many_extents_compile_at_most_buckets_times_widths_programs():
    wf, eng, svc, pool = stack()
    cen = wf.atomic(centre, vmap_key="centre", uneven=CENTRE)
    rng = np.random.default_rng(9)
    seen = set()
    # bursts of every size from 1 to 30, each a new mix of 9..40 x 9..40
    for size in range(1, 31):
        for _ in range(size):
            h, w = rng.integers(9, 41, 2)
            seen.add(bucket((h, w)))
            cen(np.ones((h, w), np.float32), np.float32(1.0))
        eng.run()
    svc.shutdown()
    widths = {width(n) for n in range(1, 31)}
    programs = sum(p._cache_size() for p in pool._vmapped_cache.values())
    assert programs <= len(seen) * len(widths)
    assert pool.fallbacks == 0
