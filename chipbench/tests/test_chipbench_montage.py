"""The Montage cell at a tiny size on the CPU (16 images of 32 x 64 in a
192 x 192 mosaic; a stand-in TPU device), through the harness's own run
and check: Workflow -> Engine -> Falkon -> DeviceExecutorPool against the
plain float64 NumPy reference.  Then the check is shown to fail for the
bfloat16 control, for padding read as data (a body that ignores its
extents) and not to be moved by what padding holds (NaN).  The layout and
the shape buckets the warm-up meets are checked at the published size,
from the footprints alone; so is the row-wise resampling, against the
plain bilinear reference.  Overlapping raw images are checked to see one
sky, and the check to recompute its sample of units and screen the
rest."""
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402
from repro.core import Tracer, clustering  # noqa: E402
from repro.core.clustering import bucket  # noqa: E402

CELL = "montage-m16.campaign"
TINY = dict(images=16, scans=4, per_scan=4, image_shape=[64, 32],
            mosaic_px=192, regions=3, shrink=4, scan_stride_px=29,
            tile_stride_px=30, scan_jitter_px=1, tile_jitter_px=1,
            min_overlap_px2=64, overlaps=[30, 60], max_bundle=16,
            bg_sweeps=20)
SECONDS = 1.0


class FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1 << 20}


def tiny_cell(buckets=False):
    cell = harness.Cell(harness.load_bench(), CELL)
    cell.spec.update(TINY)
    cell.traffic["warm_up"] = {"units": 1, "buckets": buckets}
    return cell


def run(cell, trace=False, seed=2**31 + 11, control=False):
    return harness.run_cell(cell, seed, SECONDS, trace, time.perf_counter(),
                            FakeTpu(), 1, control=control)


def test_tiny_montage_matches_its_reference_and_meets_every_program():
    res = run(tiny_cell(buckets=True))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["gap"]["value"] <= 1e-4
    assert set(res["metrics"]) == {"tasks_per_s", "setup_s"}


def test_traced_montage_reports_its_per_layer_metrics():
    res = run(tiny_cell(), trace=True)
    assert res["correct"] is True, res["checks"]
    # no device plane on the CPU: the roofline reads nothing
    assert set(res["metrics"]) == {
        "pad_waste_share.montage", "compiles_in_window.montage",
        "device_idle_share.montage", "hbm_peak_bytes.montage",
        "bundle_tasks_mean.montage"}
    assert 0.0 < res["metrics"]["pad_waste_share.montage"]["value"] < 100.0
    assert res["metrics"]["bundle_tasks_mean.montage"]["value"] > 1.0


def test_the_fan_out_is_made_from_the_overlap_table():
    cell = tiny_cell()
    cfg, spec = cell.config, cell.spec
    data = cfg.make_data(spec, 7)
    system = harness.System(spec)
    tracer = Tracer(sample_every=1)
    system.attach_tracer(tracer)
    futures, keys = cfg.submit(system.wf, spec, data, {"mosaics": 1}, 3)
    system.clock.run()
    system.shutdown()
    names = [sp.name for sp in tracer.spans]
    n = spec["images"]
    table = cfg.make_overlaps(spec["min_overlap_px2"], spec["mosaic_px"])(
        cfg.footprints(cfg.geometry(spec, 7, 3)))
    assert len(table) > n
    assert names.count("mDiffFit") == len(table)
    assert names.count("mProjectPP") == names.count("mBackground") == n
    for one in ("mOverlaps", "mBgModel", "mAdd", "mShrink"):
        assert names.count(one) == 1
    assert keys == [(3, "mosaic"), (3, "bgmodel")]
    want = cfg.reference(spec, data, keys)
    for f, k in zip(futures, keys):
        assert cfg.gap(f.get(), want[k]) <= 1e-4


def test_the_bfloat16_control_fails_the_limit_the_program_meets():
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        res = run(tiny_cell(), seed=seed, control=True)
        assert res["correct"] is False, res["checks"]
        gap = res["checks"]["gap"]
        assert gap["value"] > 3 * gap["limit"]
        assert res["checks"]["missing_answers"]["value"] == 0


@pytest.mark.parametrize("fill", [float("nan"), -1e30])
def test_padding_filled_with_nan_never_reaches_an_answer(monkeypatch, fill):
    monkeypatch.setattr(clustering, "PAD_FILL", fill)
    res = run(tiny_cell())
    assert res["correct"] is True, res["checks"]


def test_a_diff_fit_that_ignores_its_extents_is_not_correct(monkeypatch):
    cell = tiny_cell()
    cfg = cell.config
    real = cfg.mDiffFit

    def unmasked(shape, ext, pa, pb, row):
        # the whole bucket-sized window, as if it were all overlap
        full = np.broadcast_to(np.asarray(shape, np.int32), ext[-1].shape)
        return real(shape, ext.at[-1].set(full), pa, pb, row)

    monkeypatch.setattr(cfg, "mDiffFit", unmasked)
    res = run(cell)
    assert res["correct"] is False, res["checks"]


def test_the_published_layout_gives_its_overlaps_and_buckets():
    cell = harness.Cell(harness.load_bench(), CELL)
    cfg, spec = cell.config, cell.spec
    ov = cfg.make_overlaps(spec["min_overlap_px2"], spec["mosaic_px"])
    known = set(cfg.buckets(spec)["diff"])
    counts = []
    for field in range(40):
        g = cfg.geometry(spec, 2**31 + 5, field)
        assert g["h"].min() >= 1024 and g["w"].max() <= 640
        table = ov(cfg.footprints(g))
        counts.append(len(table))
        assert {bucket((int(h), int(w))) for h, w in table[:, 6:8]} <= known
        if field == 0:
            # the reference's own pairwise loop gives the same table
            np.testing.assert_array_equal(
                cfg._ref_overlaps(cfg.footprints(g), spec["min_overlap_px2"],
                                  spec["mosaic_px"]), table)
    # every field within 2,200 +- 5%
    assert 2090 <= min(counts) and max(counts) <= 2310
    assert cfg.buckets(spec)["box"] == [(1024, 640), (1280, 640)]


def test_stage_cost_reads_k_and_the_buckets_from_the_op_shapes():
    cell = harness.Cell(harness.load_bench(), CELL)
    cfg, spec = cell.config, cell.spec
    ops = ["%fusion.1 = f32[64,2,1280,640]{3,2,1,0:T(8,128)} fusion("
           "f32[64,1024,512]{2,1,0:T(8,128)} %raw.1)"]
    flops, nbytes = cfg.stage_cost(spec, "jit_mProjectPP", ops)
    assert nbytes == 64 * (1024 * 512 * 4 + 2 * 1280 * 640 * 4)
    assert flops == 40.0 * 64 * 1280 * 640
    diff = ["%gather.2 = f32[16,2,1280,640]{3,2,1,0} parameter(1)",
            "%fusion.7 = f32[16,96,128]{2,1,0} fusion(f32[16,2,96,128] %x)"]
    flops, nbytes = cfg.stage_cost(spec, "jit_mDiffFit", diff)
    assert nbytes == 16 * 2 * 2 * 96 * 128 * 4
    # mAdd's canvas is not a tile: its cost comes from the spec alone
    canvas = ["%fusion.3 = f32[1,2,13360,12080]{3,2,1,0} fusion("
              "f32[64,2,1280,640]{3,2,1,0} %stack)"]
    tile = 2 * 1280 * 640 * 4
    assert cfg.stage_cost(spec, "jit_mAdd", canvas) == (
        6.0 * 440 * 2 * 1280 * 640, float(3 * 440 * tile + 10800 ** 2 * 4))
    assert cfg.stage_cost(spec, "jit_mBgModel", ops) is None
    assert cfg.stage_cost(spec, "jit_charmm", ops) is None


@pytest.mark.parametrize("kind", ["mosaic", "bgmodel"])
def test_gap_is_relative_to_the_reference_magnitude(kind):
    cell = harness.Cell(harness.load_bench(), CELL)
    want = np.full((4, 3), 10.0)
    got = want.copy()
    got[1, 2] += 0.01
    assert cell.config.gap(got, want) == pytest.approx(1e-3)
    assert cell.config.gap(got[:2], want) == float("inf")


def test_overlapping_images_see_one_sky():
    # without noise and backgrounds, two overlapping raw images projected
    # into the mosaic frame agree (up to the interpolation of the point
    # sources), so the fits see the plane backgrounds alone
    cell = tiny_cell()
    cfg, spec = cell.config, cell.spec
    data = cfg.make_data(spec, 2**31 + 21)
    data["noise"] = [np.zeros_like(f) for f in data["noise"]]
    g = cfg.geometry(spec, data["seed"], 4)
    sk = cfg.sky(spec, data["seed"], 4)
    sk["plane"][:] = 0.0
    table = cfg.make_overlaps(spec["min_overlap_px2"], spec["mosaic_px"])(
        cfg.footprints(g))
    for a, b, ya, xa, yb, xb, h, w, _gy, _gx in table[:8]:
        pa = cfg._ref_project(cfg.raw_image(spec, data, g, sk, 4, a),
                              *cfg._proj_args(g, a))
        pb = cfg._ref_project(cfg.raw_image(spec, data, g, sk, 4, b),
                              *cfg._proj_args(g, b))
        full = (pa[1][ya:ya + h, xa:xa + w] > 0.999) & \
            (pb[1][yb:yb + h, xb:xb + w] > 0.999)
        d = pa[0][ya:ya + h, xa:xa + w] - pb[0][yb:yb + h, xb:xb + w]
        assert full.sum() > 20
        assert np.median(np.abs(d[full])) < 0.05


@pytest.mark.parametrize("shape,max_deg", [((72, 128), 2.0),
                                            ((1280, 640), 2.0)])
def test_row_wise_projection_equals_plain_bilinear_resampling(shape,
                                                               max_deg):
    import jax.numpy as jnp

    cell = harness.Cell(harness.load_bench(), CELL)
    cfg = cell.config
    rng = np.random.default_rng(3)
    H, W = (64, 32) if shape[0] < 1000 else (1024, 512)
    raw = rng.normal(100.0, 20.0, (H, W)).astype(np.float32)
    body = cfg._project_body(max_deg)
    for deg in (-max_deg, -0.2, 0.7, max_deg):
        th = np.radians(deg)
        cos, sin = np.cos(th), np.sin(th)
        # the bounding box of the support's corners, as `geometry` has it
        cy, cx = 0.0, 0.0
        ys = [cos * (qy - (H - 1) / 2) - sin * (qx - (W - 1) / 2)
              for qy in (-1, H) for qx in (-1, W)]
        xs = [sin * (qy - (H - 1) / 2) + cos * (qx - (W - 1) / 2)
              for qy in (-1, H) for qx in (-1, W)]
        oy, ox = np.floor(min(ys)), np.floor(min(xs))
        box = (int(np.floor(max(ys)) - oy + 1),
               int(np.floor(max(xs)) - ox + 1))
        geom = np.array([cos, sin, cy - oy, cx - ox], np.float32)
        assert box[0] <= shape[0] and box[1] <= shape[1]
        ext = jnp.asarray([box], jnp.int32)
        got = np.asarray(body(shape, ext, raw, geom, np.array(box)))
        img, area = cfg._ref_project(raw.astype(np.float64), geom, box)
        h, w = box
        assert np.all(np.isfinite(got[:, :h, :w]))
        # float32 coordinates near 1,000 px hold ~1e-4 px: a pixel's
        # value to ~1e-2 here, where a wrong neighbour would miss by ~20
        np.testing.assert_allclose(got[0, :h, :w], img, atol=5e-2)
        np.testing.assert_allclose(got[1, :h, :w], area, atol=5e-4)
    # a rotation past the bound leaves the band: loud, not wrong
    th = np.radians(3 * max_deg)
    geom = np.array([np.cos(th), np.sin(th), shape[0] / 2, shape[1] / 2],
                    np.float32)
    out = np.asarray(body(shape, jnp.asarray([shape], jnp.int32), raw, geom,
                          np.array(shape)))
    assert np.isnan(out[0]).any()


def test_the_check_recomputes_a_sample_and_screens_the_rest():
    cell = tiny_cell()
    cfg, spec = cell.config, cell.spec
    assert cfg.checked_units(spec, [4, 0, 2]) == [0, 2, 4]
    assert cfg.checked_units(spec, range(10)) == [0, 4, 9]
    data = cfg.make_data(spec, 2**31 + 9)
    keys = [(u, kind) for u in range(5) for kind in ("mosaic", "bgmodel")]
    want = cfg.reference(spec, data, keys)
    side = spec["mosaic_px"] // spec["shrink"]
    for (u, kind), v in want.items():
        shape = (side, side) if kind == "mosaic" else (spec["images"], 3)
        if u in (0, 2, 4):
            assert np.shape(v) == shape
            assert cfg.gap(v, v) == 0.0
        else:
            assert isinstance(v, cfg.Unchecked) and v.shape == shape
            assert cfg.gap(np.ones(shape), v) == 0.0
            assert cfg.gap(np.full(shape, np.nan), v) == float("inf")
            assert cfg.gap(np.ones((2, 2)), v) == float("inf")
            # the control's own answer for a unit outside the sample
            assert cfg.gap(v, v) == 0.0


def test_successive_mosaics_reuse_raw_buffers_and_stay_correct():
    cell = tiny_cell()
    cfg, spec = cell.config, cell.spec
    spec["check_units"] = 3
    data = cfg.make_data(spec, 2**31 + 33)
    system = harness.System(spec)
    answers, sets = {}, []
    for index in range(3):
        futures, keys = cfg.submit(system.wf, spec, data, {"mosaics": 1},
                                   index)
        system.clock.run()
        sets.append(list(data["raw_sets"]))
        answers.update({k: f.get() for f, k in zip(futures, keys)})
    system.shutdown()
    # one set of buffers, free after each mosaic and taken by the next
    assert all(len(s) == 1 for s in sets)
    assert sets[0][0] is sets[1][0] is sets[2][0]
    want = cfg.reference(spec, data, sorted(answers))
    assert not any(isinstance(v, cfg.Unchecked) for v in want.values())
    for k, v in answers.items():
        assert cfg.gap(v, want[k]) <= 1e-5, k
