"""The Montage M16 3x3 deg mosaic (arXiv:0808.3548 Fig 14) as device task
bodies with uneven extents.

One unit is one whole mosaic of a field of its own (unit i, field i; its
layout and its sky drawn from the seed and i, its raw images rendered from
that sky through each image's position and rotation): 440 mProjectPP ->
mOverlaps -> one mDiffFit per overlap (the fan-out is made at runtime from
mOverlaps' table) -> mBgModel -> 440 mBackground -> mAdd -> mShrink, about
3,100 tasks.  Every image-sized value has a shape of its own: a projected
image is its footprint's bounding box, an overlap difference the overlap's
rectangle.  The procedures declare this (`repro.core.Uneven`), so the
device pool fuses them by shape bucket, pads as it stacks, and passes the
true extents to the bodies, which mask by them (DESIGN.md §16).  Sizes,
the layout and what is assumed are in ``montage-m16.json``.

The bodies compute in float32 with elementwise arithmetic, slices,
segment sums and reductions (no matmul, so no matmul precision setting is
involved).  The plain reference recomputes a sample of each window's
mosaics (`checked_units`) in float64 NumPy at true extents, with no
padding, no bundling and no JAX, over host threads; with ``control=True``
the inputs and each stage's output are rounded to bfloat16, the precision
below the configuration's float32.
"""
from __future__ import annotations

import os
import re
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import DataFuture, Uneven, when_all
from repro.core.clustering import bucket, rung

F32 = jnp.float32
SCALE = 1000.0     # plane coordinates: mosaic px / 1000 from the centre
DAMPING = 0.5      # mBgModel's Jacobi damping
WIDTHS = (1, 4, 16, 64)   # the K ladder up to max_bundle 64
ROW = 10           # an overlap row: a, b, ya, xa, yb, xb, h, w, gy, gx
GROUP = 64         # mAdd stacks the corrected images this many at a time
# mAdd divides by at least this much area (in pixels): inside the
# coverage, where one image alone gives an area of 1, the area-weighted
# mean; over the last pixel of coverage the mosaic fades to 0 with the
# area, instead of jumping from a full value to a blank between an area
# of 1e-9 and of 0 (which float32 and float64 resampling place on
# different pixels) or dividing by an area that float32 holds to ~1e-4
AREA_FLOOR = 1.0


# ---------------------------------------------------------------------------
# device bodies: ``fn(shape, ext, *args)`` (repro.core.Uneven)
# ---------------------------------------------------------------------------

def _grid(shape):
    return (jnp.arange(shape[0], dtype=jnp.int32)[:, None],
            jnp.arange(shape[1], dtype=jnp.int32)[None, :])


def _inside(shape, e):
    r, c = _grid(shape)
    return (r < e[0]) & (c < e[1])


def _cover(i, f, n):
    """The bilinear weight that falls inside ``[0, n)`` from the
    neighbours ``i`` and ``i + 1`` at fraction `f`."""
    return ((1 - f) * ((i >= 0) & (i < n))
            + f * ((i + 1 >= 0) & (i + 1 < n)))


@lru_cache(maxsize=None)
def _project_body(max_deg: float):
    """mProjectPP for rotations up to `max_deg` degrees either way (one
    callable per bound, so its identity is stable)."""
    smax = np.sin(np.radians(max_deg))

    def mProjectPP(shape, ext, raw, geom, box):
        """Bilinear resampling of a raw image into its bounding box in the
        mosaic frame, and its area (the same interpolation of an all-ones
        image), zero outside the image.  `geom` = [cos, sin, a_y, a_x]:
        the rotation and the image centre in box coordinates; `box` =
        [h, w].  One output row at a time: its source pixels lie in a
        band of `S` rows, which one `dynamic_slice` cuts from the image
        framed in zeros; within the band each output pixel picks its two
        rows and its two columns by comparison (no element gather).  A
        pixel of the box whose source lies outside the band, as a
        rotation past `max_deg` would make it, comes out NaN.  Past the
        box the device pool fills the output (DESIGN.md §16)."""
        H, W = raw.shape
        hb, wb = shape
        S = int(smax * (wb - 1)) + 3     # rows one output row reaches
        top = int(np.ceil(smax * wb)) + 5
        left = int(np.ceil(smax * hb)) + 5
        p = jnp.pad(raw, ((top, top + S), (left, left + max(0, wb + 2 - W))))
        c = jnp.arange(wb, dtype=jnp.int32)
        dx = c.astype(F32) - geom[3]
        dy = jnp.arange(hb, dtype=F32)[:, None] - geom[2]
        # the source coordinates of every output pixel, made before the
        # row loop so that each is one stored value: recomputed where it is
        # used (XLA fuses each use on its own, each with its own rounding),
        # a coordinate a hair below an integer could give its floor from one
        # rounding and its fraction from another, a whole pixel off
        qys = geom[0] * dy + geom[1] * dx + (H - 1) / 2
        qxs = geom[0] * dx - geom[1] * dy + (W - 1) / 2
        band = jnp.arange(S, dtype=jnp.int32)[:, None]
        in_box = c < ext[-1, 1]

        def row(r, out):
            qy = lax.dynamic_index_in_dim(qys, r, keepdims=False)
            qx = lax.dynamic_index_in_dim(qxs, r, keepdims=False)
            y0, x0 = jnp.floor(qy), jnp.floor(qx)
            fy, fx = qy - y0, qx - x0
            y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
            yb = jnp.min(y0)
            xo = x0 - c                  # takes two values along a row
            xb = jnp.min(xo)
            sy, sx = yb + top, xb + left
            slab = lax.dynamic_slice(p, (sy, sx), (S, wb + 2))
            first = xo == xb
            lo = jnp.where(first, slab[:, :wb], slab[:, 1:wb + 1])
            hi = jnp.where(first, slab[:, 1:wb + 1], slab[:, 2:])
            h = (1 - fx) * lo + fx * hi
            j = y0 - yb
            img = jnp.sum(jnp.where(band == j, (1 - fy) * h,
                                    jnp.where(band == j + 1, fy * h, 0.0)),
                          axis=0)
            ok = ((jnp.max(j) + 1 < S) & (jnp.max(xo) - xb <= 1)
                  & (sy >= 0) & (sy + S <= p.shape[0])
                  & (sx >= 0) & (sx + wb + 2 <= p.shape[1]))
            img = jnp.where((r < ext[-1, 0]) & in_box & ~ok, jnp.nan, img)
            area = _cover(y0, fy, H) * _cover(x0, fx, W)
            return lax.dynamic_update_slice(
                out, jnp.stack([img, area])[:, None], (0, r, 0))

        return lax.fori_loop(0, hb, row, jnp.zeros((2, hb, wb), F32))

    return mProjectPP


def _window(p, y, x, shape):
    """``p[:, y:y + h, x:x + w]`` at the static `shape`, where the slice
    may reach past the array: the start is clamped as `dynamic_slice`
    does and the rows rolled back into place (only rows within the
    overlap's extent are read)."""
    hp, wp = p.shape[-2:]
    sy = jnp.minimum(y, hp - shape[0])
    sx = jnp.minimum(x, wp - shape[1])
    win = lax.dynamic_slice(p, (0, sy, sx), (p.shape[0],) + tuple(shape))
    return jnp.roll(jnp.roll(win, sy - y, axis=1), sx - x, axis=2)


def mDiffFit(shape, ext, pa, pb, row):
    """The difference of two projected images over their overlap
    rectangle, fitted by a plane weighted by the product of their areas:
    ``[p0, p1, p2, weight]`` with the difference ~ p0 + p1 u + p2 v in
    mosaic coordinates (u along columns, v along rows)."""
    wa = _window(pa, row[2], row[3], shape)
    wb = _window(pb, row[4], row[5], shape)
    m = _inside(shape, ext[-1])
    wgt = jnp.where(m, wa[1] * wb[1], 0.0)
    d = jnp.where(m, wa[0] - wb[0], 0.0)
    r, c = _grid(shape)
    u = (row[9] + c).astype(F32) / SCALE
    v = (row[8] + r).astype(F32) / SCALE
    tot = jnp.sum(wgt)
    ub = jnp.sum(wgt * u) / tot
    vb = jnp.sum(wgt * v) / tot
    db = jnp.sum(wgt * d) / tot
    # about the weighted centroid, the difference too: the sums that set
    # the slopes then cancel far less in float32
    du, dv, dd = u - ub, v - vb, d - db
    suu, svv = jnp.sum(wgt * du * du), jnp.sum(wgt * dv * dv)
    suv = jnp.sum(wgt * du * dv)
    sdu, sdv = jnp.sum(wgt * dd * du), jnp.sum(wgt * dd * dv)
    det = suu * svv - suv * suv
    p1 = (sdu * svv - sdv * suv) / det
    p2 = (sdv * suu - sdu * suv) / det
    return jnp.stack([db - p1 * ub - p2 * vb, p1, p2, tot])


def mBgModel(shape, ext, fits, table, images, sweeps):
    """Plane corrections of every image (``[n_images, 3]``) by damped
    Jacobi relaxation against the fits: each image's correction moves
    halfway to the weighted mean of (fit + the other image's correction)
    over its overlaps.  `fits` is ``[4, n]``, `table` ``[ROW, n]``, both
    padded past the true n (``ext[0]``)."""
    n_img = images.shape[0]
    valid = jnp.arange(fits.shape[-1]) < ext[0, 0]
    w = jnp.where(valid, fits[3], 0.0)[:, None]
    f = jnp.where(valid[None], fits[:3], 0.0).T
    a, b = table[0], table[1]
    den = (jax.ops.segment_sum(w, a, n_img)
           + jax.ops.segment_sum(w, b, n_img))
    has = den > 0
    den = jnp.where(has, den, 1.0)

    def sweep(_, corr):
        num = (jax.ops.segment_sum(w * (f + corr[b]), a, n_img)
               + jax.ops.segment_sum(w * (corr[a] - f), b, n_img))
        return corr + DAMPING * (jnp.where(has, num / den, corr) - corr)

    return lax.fori_loop(0, sweeps[0], sweep, jnp.zeros((n_img, 3), F32))


def mBackground(shape, ext, p, corr, info):
    """A projected image less its plane correction; `info` = [image,
    origin row, origin column] (origin from the mosaic centre).  Nothing
    is read across pixels, so past the extent the output is the input's
    fill, and the device pool fills it again."""
    r, c = _grid(shape)
    k = corr[info[0]]
    plane = (k[0] + k[1] * (info[2] + c).astype(F32) / SCALE
             + k[2] * (info[1] + r).astype(F32) / SCALE)
    return jnp.stack([p[0] - plane, p[1]])


@lru_cache(maxsize=None)
def _add_body(mosaic: int):
    """mAdd for a mosaic of `mosaic` px (one callable per size, so its
    identity is stable)."""

    def mAdd(shape, ext, place, *tiles):
        """The area-weighted mean of the corrected images over the mosaic
        (over at least `AREA_FLOOR` of area): each image's weighted pixels
        and area added into one canvas at its box origin (`place`), taking
        the images 64 at a time from one stack (a loop over all of them at
        once would stack every image in one more copy)."""
        hb = max(t.shape[1] for t in tiles)
        wb = max(t.shape[2] for t in tiles)
        r, c = _grid((hb, wb))
        canvas = jnp.zeros((2, mosaic + 2 * hb, mosaic + 2 * wb), F32)
        for g in range(0, len(tiles), GROUP):
            stack = jnp.stack([jnp.pad(t, ((0, 0), (0, hb - t.shape[1]),
                                           (0, wb - t.shape[2])))
                               for t in tiles[g:g + GROUP]])

            def add(t, canvas, stack=stack, g=g):
                j = g + t
                m = (r < ext[j, 0]) & (c < ext[j, 1])
                tile = stack[t]
                val = jnp.stack([jnp.where(m, tile[0] * tile[1], 0.0),
                                 jnp.where(m, tile[1], 0.0)])
                at = (0, place[j, 0] + hb, place[j, 1] + wb)
                return lax.dynamic_update_slice(
                    canvas, lax.dynamic_slice(canvas, at, (2, hb, wb)) + val,
                    at)

            canvas = lax.fori_loop(0, stack.shape[0], add, canvas)
        num = canvas[0, hb:hb + mosaic, wb:wb + mosaic]
        den = canvas[1, hb:hb + mosaic, wb:wb + mosaic]
        return num / jnp.maximum(den, AREA_FLOOR)

    return mAdd


@lru_cache(maxsize=None)
def _shrink_body(factor: int):
    def mShrink(mosaic):
        """Means of `factor` x `factor` blocks."""
        n = mosaic.shape[0] // factor
        return mosaic.reshape(n, factor, n, factor).mean(axis=(1, 3))

    return mShrink


# ---------------------------------------------------------------------------
# host side: footprints and mOverlaps
# ---------------------------------------------------------------------------

def geometry(spec: dict, seed: int, field: int) -> dict:
    """The layout of `field`: per image its centre, rotation, bounding
    box (origin and extent, from the corners of the interpolation's
    support) and the half-sizes of the box inscribed in its footprint.
    Drawn from the seed and the field, again from the same stream until
    mOverlaps finds a count of overlaps within ``overlaps`` (the
    published ~2,200 +- 5%)."""
    rng = np.random.default_rng([seed % 2**63, (field + 2**40) % 2**63])
    lo, hi = spec["overlaps"]
    count = make_overlaps(spec["min_overlap_px2"], spec["mosaic_px"])
    for _ in range(100):
        g = _layout(spec, rng)
        if lo <= len(count(footprints(g))) <= hi:
            return g
    raise ValueError(f"no layout of field {field} gives {lo}-{hi} overlaps")


def _layout(spec: dict, rng) -> dict:
    s_n, t_n = spec["scans"], spec["per_scan"]
    n = s_n * t_n
    H, W = spec["image_shape"]
    size = spec["mosaic_px"]
    sx, sy = spec["scan_stride_px"], spec["tile_stride_px"]
    js, ji = spec["scan_jitter_px"], spec["tile_jitter_px"]
    s = np.repeat(np.arange(s_n), t_n)
    t = np.tile(np.arange(t_n), s_n)
    phase = rng.uniform(0, sy, s_n)
    shift = rng.uniform(-js, js, s_n)
    hmax = (H + 1) + (W + 1) * np.sin(np.radians(spec["rotation_deg"][1]))
    cx = (size - (s_n - 1) * sx) / 2 + s * sx + shift[s] \
        + rng.uniform(-ji, ji, n)
    cy = (size - t_n * sy - hmax) / 2 + hmax / 2 + phase[s] + t * sy \
        + rng.uniform(-ji, ji, n)
    ang = np.radians(rng.uniform(*spec["rotation_deg"], n)) \
        * rng.choice([-1.0, 1.0], n)
    cos, sin = np.cos(ang), np.sin(ang)
    icy, icx = (H - 1) / 2, (W - 1) / 2
    ys, xs = [], []
    for qy in (-1, H):
        for qx in (-1, W):
            ys.append(cy + cos * (qy - icy) - sin * (qx - icx))
            xs.append(cx + sin * (qy - icy) + cos * (qx - icx))
    ys, xs = np.array(ys), np.array(xs)
    oy = np.floor(ys.min(0)).astype(np.int64)
    ox = np.floor(xs.min(0)).astype(np.int64)
    h = np.floor(ys.max(0)).astype(np.int64) - oy + 1
    w = np.floor(xs.max(0)).astype(np.int64) - ox + 1
    if oy.min() < 0 or ox.min() < 0 or (oy + h).max() > size \
            or (ox + w).max() > size:
        raise ValueError("the layout does not fit the mosaic")
    a, b = (H + 1) / 2, (W + 1) / 2
    return {"cy": cy, "cx": cx, "cos": cos, "sin": sin, "oy": oy,
            "ox": ox, "h": h, "w": w,
            "iy": a * cos - b * np.abs(sin), "ix": b * cos - a * np.abs(sin)}


def footprints(g: dict) -> np.ndarray:
    """The header data mOverlaps reads: ``[n, 8]`` float64 (centre,
    inscribed half-sizes, box origin, box extent)."""
    return np.stack([g["cy"], g["cx"], g["iy"], g["ix"], g["oy"], g["ox"],
                     g["h"], g["w"]], axis=1)


def make_overlaps(min_area: float, size: int):
    """The mOverlaps body for a mosaic of `size` px."""

    def mOverlaps(fp):
        """The overlap table ``[n, ROW]`` int32 from the footprints: each
        pair whose inscribed boxes share `min_area` px^2, with the
        intersection of their bounding boxes in each one's box
        coordinates and in mosaic coordinates from the centre."""
        cy, cx, iy, ix, oy, ox, h, w = (fp[:, k] for k in range(8))
        ih = (np.minimum(cy[:, None] + iy[:, None], cy + iy)
              - np.maximum(cy[:, None] - iy[:, None], cy - iy))
        iw = (np.minimum(cx[:, None] + ix[:, None], cx + ix)
              - np.maximum(cx[:, None] - ix[:, None], cx - ix))
        keep = np.triu((np.clip(ih, 0, None) * np.clip(iw, 0, None))
                       >= min_area, 1)
        a, b = np.nonzero(keep)
        y0 = np.maximum(oy[a], oy[b])
        x0 = np.maximum(ox[a], ox[b])
        y1 = np.minimum(oy[a] + h[a], oy[b] + h[b])
        x1 = np.minimum(ox[a] + w[a], ox[b] + w[b])
        return np.stack([a, b, y0 - oy[a], x0 - ox[a], y0 - oy[b],
                         x0 - ox[b], y1 - y0, x1 - x0, y0 - size // 2,
                         x0 - size // 2], axis=1).astype(np.int32)

    return mOverlaps


def members_of(g: dict, size: int, regions: int) -> np.ndarray:
    """``[regions^2, 1 + n]``: per sub-region the count of images whose
    boxes meet it, then their indices."""
    rs = size // regions
    n = len(g["oy"])
    out = np.zeros((regions * regions, 1 + n), np.int32)
    for ky in range(regions):
        for kx in range(regions):
            hit = np.nonzero((g["oy"] < (ky + 1) * rs)
                             & (g["oy"] + g["h"] > ky * rs)
                             & (g["ox"] < (kx + 1) * rs)
                             & (g["ox"] + g["w"] > kx * rs))[0]
            out[ky * regions + kx, 0] = len(hit)
            out[ky * regions + kx, 1:1 + len(hit)] = hit
    return out


# ---------------------------------------------------------------------------
# data, units and warm-up
# ---------------------------------------------------------------------------

# the sky of a field, in mosaic pixels: a level, plane waves, point
# sources of a Gaussian PSF; each raw image adds noise and a plane
# background of its own (montage-m16.json, "assumed": "pixels")
SKY_LEVEL = 100.0
WAVES = 4
WAVE_AMPLITUDE = (5.0, 20.0)
WAVELENGTH_PX = (400.0, 4000.0)
STARS_PER_PX2 = 8e-5
STAR_PEAK = (50.0, 5000.0)
PSF_SIGMA_PX = 1.1
NOISE = 2.0
NOISE_FRAMES = 16
PLANE_SD = (10.0, 5.0, 5.0)
RENDER_THREADS = 4


def make_data(spec: dict, seed: int) -> dict:
    """What every field shares, made on the host from `seed`: frames of
    Gaussian pixel noise.  A field's raw images are rendered from its own
    sky when its mosaic is submitted (`raw_image`), into a set of buffers
    that ``raw_sets`` holds between mosaics."""
    rng = np.random.default_rng(seed)
    H, W = spec["image_shape"]
    return {"seed": seed,
            "noise": [rng.normal(0, NOISE, (H, W)).astype(np.float32)
                      for _ in range(NOISE_FRAMES)],
            "raw_sets": []}


def sky(spec: dict, seed: int, field: int) -> dict:
    """The sky of `field`, drawn from the seed and the field: plane waves
    (amplitude, wave vector, phase) and point sources (mosaic position,
    peak) over the mosaic, and each image's plane background."""
    rng = np.random.default_rng([seed % 2**63, (field + 2**40) % 2**63,
                                 1])
    size, n = spec["mosaic_px"], spec["images"]
    turn = rng.uniform(0, 2 * np.pi, WAVES)
    k = 2 * np.pi / rng.uniform(*WAVELENGTH_PX, WAVES)
    stars = rng.poisson(STARS_PER_PX2 * size * size)
    return {"amp": rng.uniform(*WAVE_AMPLITUDE, WAVES),
            "ky": k * np.sin(turn), "kx": k * np.cos(turn),
            "phase": rng.uniform(0, 2 * np.pi, WAVES),
            "star_y": rng.uniform(0, size, stars),
            "star_x": rng.uniform(0, size, stars),
            "star_peak": np.exp(rng.uniform(*np.log(STAR_PEAK), stars)),
            "plane": rng.normal(0, PLANE_SD, (n, 3))}


def raw_image(spec: dict, data: dict, g: dict, sk: dict, field: int,
              k: int, out: np.ndarray | None = None) -> np.ndarray:
    """Raw image `k` of a field: its sky seen through the image's own
    position and rotation (so that overlapping images agree), its plane
    background and a noise frame, float32 ``[H, W]`` (written into `out`
    where given).  The level, the waves and the plane are one low-rank
    product (a wave is separable in any rotated frame); the point sources
    are 9 x 9 stamps."""
    H, W = spec["image_shape"]
    cos, sin, cy, cx = g["cos"][k], g["sin"][k], g["cy"][k], g["cx"][k]
    u = np.arange(H) - (H - 1) / 2
    v = np.arange(W) - (W - 1) / 2
    # mosaic position of raw pixel (u, v): (cy + cos u - sin v,
    # cx + sin u + cos v); a wave's phase there is a u + b v + c
    a = sk["ky"] * cos + sk["kx"] * sin
    b = sk["kx"] * cos - sk["ky"] * sin
    c = sk["ky"] * cy + sk["kx"] * cx + sk["phase"]
    pl = sk["plane"][k]
    left = np.concatenate([
        sk["amp"] * np.cos(np.outer(u, a) + c),
        -sk["amp"] * np.sin(np.outer(u, a) + c),
        np.ones((H, 1)), (np.arange(H)[:, None] - H / 2) / H], axis=1)
    right = np.concatenate([
        np.cos(np.outer(b, v)), np.sin(np.outer(b, v)),
        (SKY_LEVEL + pl[0] + pl[1] * (np.arange(W) - W / 2) / W)[None],
        np.full((1, W), pl[2])], axis=0)
    img = np.matmul(left.astype(np.float32), right.astype(np.float32),
                    out=out)
    np.add(img, data["noise"][(k + 7 * field) % len(data["noise"])],
           out=img)
    # the point sources within reach of the image, in its own pixels
    dy, dx = sk["star_y"] - cy, sk["star_x"] - cx
    si = cos * dy + sin * dx + (H - 1) / 2
    sj = cos * dx - sin * dy + (W - 1) / 2
    near = (si > -5) & (si < H + 4) & (sj > -5) & (sj < W + 4)
    si, sj, peak = si[near], sj[near], sk["star_peak"][near]
    off = np.arange(-4, 5)
    rows = np.floor(si).astype(np.int64)[:, None] + off
    cols = np.floor(sj).astype(np.int64)[:, None] + off
    gy = np.exp(-((rows - si[:, None]) ** 2) / (2 * PSF_SIGMA_PX ** 2))
    gx = np.exp(-((cols - sj[:, None]) ** 2) / (2 * PSF_SIGMA_PX ** 2))
    val = peak[:, None, None] * gy[:, :, None] * gx[:, None, :]
    r = np.broadcast_to(rows[:, :, None], val.shape)
    q = np.broadcast_to(cols[:, None, :], val.shape)
    inside = (r >= 0) & (r < H) & (q >= 0) & (q < W)
    np.add.at(img, (r[inside], q[inside]), val[inside].astype(np.float32))
    return img


def _mosaics(unit: dict) -> int:
    if set(unit) != {"mosaics"} or int(unit["mosaics"]) != 1:
        raise ValueError(f"a montage unit takes {{'mosaics': 1}}, "
                         f"not {unit!r}")
    return 1


def _procs(wf, spec: dict) -> dict:
    """The procedures, each declaring its uneven extents."""
    n = spec["images"]
    return {
        "mProjectPP": wf.atomic(
            _project_body(spec["rotation_deg"][1]), name="mProjectPP",
            vmap_key="mProjectPP",
            uneven=Uneven(extent=lambda raw, geom, box: box,
                          padded_out=True)),
        "mOverlaps": wf.atomic(
            make_overlaps(spec["min_overlap_px2"], spec["mosaic_px"]),
            name="mOverlaps"),
        "mDiffFit": wf.atomic(
            mDiffFit, name="mDiffFit", vmap_key="mDiffFit",
            uneven=Uneven(args=(0, 1), extent=lambda a, b, row: row[6:8])),
        "mBgModel": wf.atomic(
            mBgModel, name="mBgModel", vmap_key="mBgModel",
            uneven=Uneven(args=(0, 1), ndim=1)),
        "mBackground": wf.atomic(
            mBackground, name="mBackground", vmap_key="mBackground",
            uneven=Uneven(args=(0,), extent=0, padded_out=True)),
        "mAdd": wf.atomic(
            _add_body(spec["mosaic_px"]), name="mAdd", vmap_key="mAdd",
            uneven=Uneven(args=tuple(range(1, 1 + n)))),
        "mShrink": wf.atomic(_shrink_body(spec["shrink"]), name="mShrink",
                             vmap_key="mShrink"),
    }


def _proj_args(g: dict, k: int):
    """mProjectPP's arguments besides the raw image: geometry and box."""
    geom = np.array([g["cos"][k], g["sin"][k], g["cy"][k] - g["oy"][k],
                     g["cx"][k] - g["ox"][k]], np.float32)
    return geom, np.array([g["h"][k], g["w"][k]], np.int32)


def _info(spec: dict, g: dict, k: int) -> np.ndarray:
    half = spec["mosaic_px"] // 2
    return np.array([k, g["oy"][k] - half, g["ox"][k] - half], np.int32)


def submit(wf, spec: dict, data: dict, unit: dict, index: int,
           tenant: int = 0):
    """Submit mosaic `index`, of the field of the same number: its raw
    images, rendered from the field's sky, and its whole workflow, with
    the mDiffFit fan-out made when mOverlaps' table resolves.  Returns
    the futures of the shrunk mosaic and of mBgModel's corrections, keyed
    ``(index, "mosaic")`` and ``(index, "bgmodel")``."""
    _mosaics(unit)
    g = geometry(spec, data["seed"], index)
    sk = sky(spec, data["seed"], index)
    n = spec["images"]
    p = _procs(wf, spec)
    # the raw images enter once every task of the mosaic is submitted, so
    # that mBackground reads each projection when its bundle ships and the
    # projections are placed on the device (DESIGN.md §11)
    start = DataFuture()
    proj = []
    # rendering stands in for reading the images from the archive; the
    # work is in NumPy calls that release the interpreter lock.  The
    # images go into a set of buffers of an earlier mosaic where one is
    # free (0.92 GB of fresh pages a mosaic would each be touched first
    # here, on the clock thread), and their set is free again once every
    # image is projected: nothing else reads a raw image
    free = data["raw_sets"]
    H, W = spec["image_shape"]
    bufs = free.pop() if free else [np.empty((H, W), np.float32)
                                    for _ in range(n)]
    with ThreadPoolExecutor(RENDER_THREADS) as pool:
        raws = list(pool.map(
            lambda k: raw_image(spec, data, g, sk, index, k, bufs[k]),
            range(n)))
    for k, raw in enumerate(raws):
        proj.append(p["mProjectPP"](
            wf.then(start, lambda _s, raw=raw: raw), *_proj_args(g, k)))
    # as in Montage, mOverlaps reads the table of the projected images
    # (mImgtbl), so it runs once every image is projected; it reads their
    # footprints only, and its table alone makes the mDiffFit fan-out,
    # all of it ready at once
    projected = DataFuture()

    def all_projected():
        free.append(bufs)
        projected.set(None)

    when_all(proj, all_projected)
    table = p["mOverlaps"](wf.then(projected, lambda _p: footprints(g)))
    fits = wf.foreach(table, lambda row: p["mDiffFit"](
        proj[row[0]], proj[row[1]], row))
    bg = p["mBgModel"](wf.then(fits, lambda fs: np.stack(fs, axis=1)),
                       wf.then(table, lambda t: np.ascontiguousarray(t.T)),
                       np.arange(n, dtype=np.int32),
                       np.array([spec["bg_sweeps"]], np.int32))
    back = [p["mBackground"](proj[k], bg, _info(spec, g, k))
            for k in range(n)]
    place = np.stack([g["oy"], g["ox"]], axis=1).astype(np.int32)
    mosaic = p["mAdd"](place, *back)
    start.set(None)
    return ([p["mShrink"](mosaic), bg],
            [(index, "mosaic"), (index, "bgmodel")])


def unit_keys(spec: dict, unit: dict, index: int, tenant: int = 0) -> list:
    """The answer keys of unit `index`, as `submit` gives them."""
    _mosaics(unit)
    return [(index, "mosaic"), (index, "bgmodel")]


def _ranges(spec: dict) -> dict:
    """Bounds of every extent the layout can give: the bounding boxes,
    and per class of overlap the rectangle's rows and columns (2 px of
    margin each way)."""
    H, W = spec["image_shape"]
    lo, hi = np.radians(spec["rotation_deg"])
    h0 = int((H + 1) * np.cos(lo) + (W + 1) * np.sin(lo)) - 2
    h1 = int((H + 1) * np.cos(hi) + (W + 1) * np.sin(hi)) + 3
    w0 = int((W + 1) * np.cos(lo) + (H + 1) * np.sin(lo)) - 2
    w1 = int((W + 1) * np.cos(hi) + (H + 1) * np.sin(hi)) + 3
    sx, sy = spec["scan_stride_px"], spec["tile_stride_px"]
    js, ji = spec["scan_jitter_px"], spec["tile_jitter_px"]
    gap = sx - 2 * js - 2 * ji                 # adjacent scans, at least
    if 2 * gap <= w1 or 3 * (sy - 2 * ji) <= h1:
        raise ValueError("the layout lets far images overlap")
    ix = (W + 1) / 2 * np.cos(lo) - (H + 1) / 2 * np.sin(lo)
    thin_h = max(1, int(spec["min_overlap_px2"] / max(1.0, 2 * ix - gap)))
    classes = [((thin_h - 2, h1 + 2), (1, w1 - gap + 3))]
    for d in (1, 2):                           # same scan, d images apart
        classes.append(((h0 - d * sy - 2 * ji - 3, h1 - d * sy + 2 * ji + 3),
                        (w0 - 2 * ji - 3, w1 + 3)))
    return {"box": ((h0, h1), (w0, w1)), "overlaps": classes}


def _rungs(lo: int, hi: int, tile: int) -> list:
    return sorted({rung(n, tile) for n in range(max(1, lo), hi + 1)})


def buckets(spec: dict) -> dict:
    """Every shape bucket each declared procedure can meet: of the boxes
    (mProjectPP, mBackground) and of the overlap rectangles (mDiffFit)."""
    rg = _ranges(spec)
    (h0, h1), (w0, w1) = rg["box"]
    box = [(r, c) for r in _rungs(h0, h1, 8) for c in _rungs(w0, w1, 128)]
    diff = set()
    for (ra, rb), (ca, cb) in rg["overlaps"]:
        if rb < 1 or cb < 1:
            continue
        diff |= {(r, c) for r in _rungs(ra, rb, 8)
                 for c in _rungs(ca, cb, 128)}
    return {"box": box, "diff": sorted(diff)}


def warm_up(ctx, params: dict) -> None:
    """Drive the traffic's warm-up through the timed path, so that the
    window finds its programs compiled.  ``buckets``: every (shape
    bucket, K) program of every declared procedure — for each bucket of
    `buckets` and each width of the K ladder, that many tasks at once;
    mBgModel at each rung of the overlap counts a layout may give.
    ``units``: that many whole mosaics, one after another (mOverlaps,
    mAdd, mShrink and the stacks)."""
    spec, wf, clock = ctx.spec, ctx.wf, ctx.clock
    if params.get("buckets"):
        p = _procs(wf, spec)
        g = geometry(spec, ctx.data["seed"], -2_000_000)
        sk = sky(spec, ctx.data["seed"], -2_000_000)
        raws = [raw_image(spec, ctx.data, g, sk, -2_000_000, j)
                for j in range(min(max(WIDTHS), spec["images"]))]
        corr = jax.device_put(np.zeros((spec["images"], 3), np.float32))
        half = spec["mosaic_px"] // 2
        bk = buckets(spec)
        n = spec["images"]
        tiles = []
        for r, c in bk["box"]:
            box = _inside_rung(r, c, spec)
            for k in WIDTHS:
                # distinct arguments per task, as in a mosaic (a shared
                # one would broadcast and make another program), and the
                # readers submitted before the projections start
                gate = DataFuture()
                tiles = []
                for j in range(k):
                    raw = raws[j % len(raws)]
                    tiles.append(p["mProjectPP"](
                        wf.then(gate, lambda _t, raw=raw: raw),
                        _proj_args(g, j % n)[0], np.array(box, np.int32)))
                for j, f in enumerate(tiles):
                    p["mBackground"](f, corr, _info(spec, g, j % n))
                gate.set(None)
                clock.run()
        ha, wa = tiles[0].get_padded().extent
        for r, c in bk["diff"]:
            h, w = min(r, ha), min(c, wa)
            if bucket((h, w)) != (r, c):
                continue
            for k in WIDTHS:
                for j in range(k):
                    row = np.array([0, 1, 0, 0, 0, 0, h, w, -half, -half],
                                   np.int32)
                    p["mDiffFit"](tiles[j], tiles[(j + 1) % len(tiles)], row)
                clock.run()
        lo, hi = spec["overlaps"]
        for count in sorted({rung(m, 128) for m in range(lo, hi + 1)}):
            table = np.zeros((ROW, count), np.int32)
            table[1] = 1
            p["mBgModel"](np.ones((4, count), np.float32), table,
                          np.arange(n, dtype=np.int32),
                          np.array([spec["bg_sweeps"]], np.int32))
            clock.run()
    for i in range(params.get("units", 0)):
        ctx.submit(-1_000_000 + i)
        clock.run()


def _inside_rung(r: int, c: int, spec: dict) -> tuple:
    """The largest extent within the layout's box bounds in bucket
    ``(r, c)``."""
    (_h0, h1), (_w0, w1) = _ranges(spec)["box"]
    return (max(n for n in range(1, h1 + 1) if rung(n, 8) == r),
            max(n for n in range(1, w1 + 1) if rung(n, 128) == c))


# ---------------------------------------------------------------------------
# the plain reference: float64 NumPy at true extents
# ---------------------------------------------------------------------------

def _round(a, control: bool):
    if not control:
        return np.asarray(a, np.float64)
    return np.asarray(a).astype(jnp.bfloat16).astype(np.float64)


def _ref_project(raw, geom, box):
    """Bilinear resampling with zero outside the image, read from the
    image framed by one row and column of zeros, and the interpolation's
    coverage of the image (its area)."""
    H, W = raw.shape
    h, w = int(box[0]), int(box[1])
    r = np.arange(h, dtype=np.float64)[:, None]
    c = np.arange(w, dtype=np.float64)[None, :]
    cos, sin, ay, ax = (float(v) for v in geom)
    qy = cos * (r - ay) + sin * (c - ax) + (H - 1) / 2
    qx = cos * (c - ax) - sin * (r - ay) + (W - 1) / 2
    y0, x0 = np.floor(qy), np.floor(qx)
    fy, fx = qy - y0, qx - x0
    y0, x0 = y0.astype(np.int64), x0.astype(np.int64)
    p = np.pad(raw, 1).ravel()
    row0 = np.clip(y0 + 1, 0, H + 1) * (W + 2)
    row1 = np.clip(y0 + 2, 0, H + 1) * (W + 2)
    col0 = np.clip(x0 + 1, 0, W + 1)
    col1 = np.clip(x0 + 2, 0, W + 1)
    img = ((1 - fy) * ((1 - fx) * p[row0 + col0] + fx * p[row0 + col1])
           + fy * ((1 - fx) * p[row1 + col0] + fx * p[row1 + col1]))

    def cover(i, f, n):
        return ((1 - f) * ((i >= 0) & (i < n))
                + f * ((i + 1 >= 0) & (i + 1 < n)))

    return img, cover(y0, fy, H) * cover(x0, fx, W)


def _ref_overlaps(fp, min_area, size):
    rows = []
    n = len(fp)
    for i in range(n):
        cy, cx, iy, ix, oy, ox, h, w = fp[i]
        for j in range(i + 1, n):
            cy2, cx2, iy2, ix2, oy2, ox2, h2, w2 = fp[j]
            dh = min(cy + iy, cy2 + iy2) - max(cy - iy, cy2 - iy2)
            dw = min(cx + ix, cx2 + ix2) - max(cx - ix, cx2 - ix2)
            if max(dh, 0) * max(dw, 0) < min_area:
                continue
            y0, x0 = max(oy, oy2), max(ox, ox2)
            rows.append((i, j, y0 - oy, x0 - ox, y0 - oy2, x0 - ox2,
                         min(oy + h, oy2 + h2) - y0,
                         min(ox + w, ox2 + w2) - x0,
                         y0 - size // 2, x0 - size // 2))
    return np.array(rows, np.int64).reshape(-1, ROW)


def _ref_fit(pa, pb, row):
    a, b, ya, xa, yb, xb, h, w, gy, gx = (int(v) for v in row)
    ia, aa = pa[0][ya:ya + h, xa:xa + w], pa[1][ya:ya + h, xa:xa + w]
    ib, ab = pb[0][yb:yb + h, xb:xb + w], pb[1][yb:yb + h, xb:xb + w]
    wgt = aa * ab
    d = ia - ib
    u = (gx + np.arange(w))[None, :] / SCALE
    v = (gy + np.arange(h))[:, None] / SCALE
    tot = wgt.sum()
    ub, vb = (wgt * u).sum() / tot, (wgt * v).sum() / tot
    du, dv = u - ub, v - vb
    suu, svv = (wgt * du * du).sum(), (wgt * dv * dv).sum()
    suv = (wgt * du * dv).sum()
    sdu, sdv = (wgt * d * du).sum(), (wgt * d * dv).sum()
    det = suu * svv - suv * suv
    p1 = (sdu * svv - sdv * suv) / det
    p2 = (sdv * suu - sdu * suv) / det
    return np.array([(wgt * d).sum() / tot - p1 * ub - p2 * vb, p1, p2,
                     tot])


def _ref_bgmodel(fits, table, n_img, sweeps):
    a, b = table[:, 0], table[:, 1]
    w, f = fits[:, 3], fits[:, :3]
    den = (np.bincount(a, w, n_img) + np.bincount(b, w, n_img))
    corr = np.zeros((n_img, 3))
    for _ in range(sweeps):
        num = np.stack([np.bincount(a, w * (f[:, q] + corr[b, q]), n_img)
                        + np.bincount(b, w * (corr[a, q] - f[:, q]), n_img)
                        for q in range(3)], axis=1)
        tgt = np.where(den[:, None] > 0,
                       num / np.where(den > 0, den, 1.0)[:, None], corr)
        corr = corr + DAMPING * (tgt - corr)
    return corr


def _ref_mosaic(spec, data, field, control, pool):
    """The shrunk mosaic and the corrections of `field`."""
    rnd = (lambda a: _round(a, control))
    g = geometry(spec, data["seed"], field)
    sk = sky(spec, data["seed"], field)
    n = spec["images"]
    size, R, f = spec["mosaic_px"], spec["regions"], spec["shrink"]
    half = size // 2

    def project(k):
        raw = raw_image(spec, data, g, sk, field, k)
        img, area = _ref_project(rnd(raw), *_proj_args(g, k))
        return rnd(img), rnd(area)

    proj = list(pool.map(project, range(n)))
    table = _ref_overlaps(footprints(g), spec["min_overlap_px2"], size)
    fits = np.array(list(pool.map(
        lambda row: rnd(_ref_fit(proj[row[0]], proj[row[1]], row)),
        table))).reshape(-1, 4)
    corr = rnd(_ref_bgmodel(fits, table, n, spec["bg_sweeps"]))

    def background(k):
        img, area = proj[k]
        h, w = img.shape
        u = (g["ox"][k] - half + np.arange(w))[None, :] / SCALE
        v = (g["oy"][k] - half + np.arange(h))[:, None] / SCALE
        plane = corr[k, 0] + corr[k, 1] * u + corr[k, 2] * v
        return rnd(img - plane), area

    back = list(pool.map(background, range(n)))
    members = members_of(g, size, R)
    rs = size // R

    def region(k):
        ky, kx = divmod(k, R)
        num = np.zeros((rs, rs))
        den = np.zeros((rs, rs))
        for j in members[k, 1:1 + members[k, 0]]:
            img, area = back[j]
            y, x = g["oy"][j] - ky * rs, g["ox"][j] - kx * rs
            y0, x0 = max(y, 0), max(x, 0)
            y1 = min(y + img.shape[0], rs)
            x1 = min(x + img.shape[1], rs)
            sl = (slice(y0 - y, y1 - y), slice(x0 - x, x1 - x))
            num[y0:y1, x0:x1] += img[sl] * area[sl]
            den[y0:y1, x0:x1] += area[sl]
        mean = rnd(num / np.maximum(den, AREA_FLOOR))
        return mean.reshape(rs // f, f, rs // f, f).mean(axis=(1, 3))

    parts = list(pool.map(region, range(R * R)))
    shrunk = np.block([[parts[ky * R + kx] for kx in range(R)]
                       for ky in range(R)])
    return rnd(shrunk), corr


class Unchecked:
    """What `reference` gives for an answer outside its sample: the shape
    the answer must have.  `gap` then checks only that the answer has
    that shape and holds finite values."""

    def __init__(self, shape: tuple):
        self.shape = shape


def checked_units(spec: dict, units) -> list:
    """The units whose answers `reference` recomputes: ``check_units`` of
    them, evenly spread over `units` in order, the first and the last
    among them (all, where there are no more)."""
    units = sorted(units)
    m = spec["check_units"]
    if len(units) <= m:
        return units
    return [units[round(i * (len(units) - 1) / (m - 1))] for i in range(m)]


def reference(spec: dict, data: dict, keys: list, control: bool = False
              ) -> dict:
    """Answers `keys` (``(unit, "mosaic" | "bgmodel")``): for the units
    `checked_units` picks, each unit's mosaic (its own field) recomputed
    in float64 NumPy at true extents, with the work of each stage spread
    over host threads (no JAX, no padding, no bundling); for the others,
    `Unchecked` with the answer's shape."""
    done = {}
    with ThreadPoolExecutor(max(1, os.cpu_count() or 1)) as pool:
        for u in checked_units(spec, {k[0] for k in keys}):
            done[u] = _ref_mosaic(spec, data, u, control, pool)
    side = spec["mosaic_px"] // spec["shrink"]
    shapes = {"mosaic": (side, side), "bgmodel": (spec["images"], 3)}
    return {k: done[k[0]][0 if k[1] == "mosaic" else 1] if k[0] in done
            else Unchecked(shapes[k[1]]) for k in keys}


def gap(got, want) -> float:
    """Widest absolute gap between an answer and the reference's, as a
    share of the reference's largest magnitude; infinite for an answer of
    another shape or with a value that is not finite (padding read as
    data: DESIGN.md §16).  Against `Unchecked`, 0 for an answer of its
    shape with finite values (or `Unchecked` itself, the control's),
    else infinite."""
    if isinstance(got, Unchecked):
        return 0.0
    got = np.asarray(got, np.float64)
    if isinstance(want, Unchecked):
        ok = got.shape == want.shape and np.all(np.isfinite(got))
        return 0.0 if ok else float("inf")
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# FLOPs and bytes of the fused stage programs (for stage_roofline)
# ---------------------------------------------------------------------------

_SHAPE = re.compile(r"f32\[(\d+),2,(\d+),(\d+)\]")
_WINDOW = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def stage_cost(spec: dict, module: str, op_names: list):
    """``(flops, bytes)`` that one execution of a fused stage program
    needs, at the padded shapes it computes, or None for any other
    program.  Tiles are held at the layout's largest box bucket
    (`buckets`); K is read from the op shapes (the stacked ``[K, 2, h,
    w]`` tiles at that bucket; for mDiffFit also the ``[K, h, w]``
    overlap windows inside it).  mAdd runs once a mosaic over every
    tile.  FLOPs count the arithmetic per padded element; bytes are what
    the program must move: the inputs it reads once and the outputs it
    writes (mAdd: each tile, and its window of the canvas read and
    written once, then the mosaic)."""
    body = module[4:] if module.startswith("jit_") else None
    if body not in ("mProjectPP", "mDiffFit", "mBackground", "mAdd"):
        return None
    hb, wb = max(buckets(spec)["box"])
    H, W = spec["image_shape"]
    tile = 2 * hb * wb * 4
    if body == "mAdd":
        n, size = spec["images"], spec["mosaic_px"]
        return 6.0 * n * 2 * hb * wb, float(3 * n * tile + size * size * 4)
    ks = [int(m[0]) for n in op_names for m in _SHAPE.findall(n)
          if (int(m[1]), int(m[2])) == (hb, wb)]
    if not ks:
        return None
    k = max(ks)
    if body == "mProjectPP":
        return 40.0 * k * hb * wb, float(k * (H * W * 4 + tile))
    if body == "mBackground":
        return 8.0 * k * hb * wb, float(k * 2 * tile)
    wins = [tuple(int(v) for v in m) for n in op_names
            for m in _WINDOW.findall(n)]
    wins = [w for w in wins if w[0] == k and w[1] <= hb and w[2] <= wb
            and (w[1], w[2]) != (hb, wb)]
    if not wins:
        return None
    _k, h, w = max(wins, key=lambda t: t[1] * t[2])
    return 30.0 * k * h * w, float(k * 2 * 2 * h * w * 4)
