"""bench/trace.py: busy time is the union of device intervals, kernel time
is summed by name, and idle gaps are named by the host span open then."""
import glob
import os

import pytest

from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_names():
    # chip 0: two overlapping ops and one apart; window 0..100 ns
    dev = {"/device:TPU:0": [("_gemm_kernel", 10, 30), ("fusion.1", 20, 40),
                             ("_gemm_kernel", 60, 70)]}
    spans = [("bench.window", 0, 100), ("bench.step", 5, 45),
             ("bench.submit", 45, 58)]
    red = TR.reduce_events(dev, spans, (0, 100))
    assert red["busy_s"] == pytest.approx(40e-9)      # 10..40 and 60..70
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["ops"]["_gemm_kernel"] == pytest.approx(30e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(20e-9)
    # gaps: 70..100 (30), 40..60 (20), 0..10 (10), innermost open span
    assert [g[0] for g in red["gaps"]] == ["bench.window", "bench.submit",
                                           "bench.step"]
    assert [g[1] for g in red["gaps"]] == pytest.approx([30e-9, 20e-9,
                                                         10e-9])
    assert TR.kernel_seconds(red["ops"], ("_gemm",)) == pytest.approx(30e-9)
    assert TR.kernel_seconds(red["ops"], ("_grouped",)) is None


def test_parent_ops_are_left_out_of_op_time():
    dev = {"/device:TPU:0": [("while.1", 0, 100), ("a.1", 10, 20),
                             ("b.2", 30, 90)]}
    red = TR.reduce_events(dev, [], (0, 100))
    assert set(red["ops"]) == {"a.1", "b.2"}
    assert red["busy_s"] == pytest.approx(100e-9)
    assert TR.op_name("%copy.3 = s16[2]{0} copy(x)") == "copy.3"


def test_busy_averages_over_chips_and_clips_to_window():
    dev = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 40, 200)]}
    red = TR.reduce_events(dev, [], (0, 100))
    assert red["busy_s"] == pytest.approx((50e-9 + 60e-9) / 2)
    assert red["ops"]["a"] == pytest.approx(110e-9)
    assert red["chips"] == 2


def test_breakdown_is_at_most_ten():
    dev = {"/device:TPU:0": [(f"op{i}", 10 * i, 10 * i + 5)
                             for i in range(30)]}
    bd = TR.breakdown(TR.reduce_events(dev, [], (0, 300)))
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A short trace recorded on one TPU v5e: a few decode steps of the
    posit engine.  Its device line is read, busy time is the union of the
    op intervals (checked here against a plain merge written in this
    test), and the Pallas kernels are there by name."""
    dev, spans = TR.read_events(RECORDED[0])
    assert list(dev) == ["/device:TPU:0"]
    evs = sorted((s, e) for _, s, e in dev["/device:TPU:0"])
    busy, cur_s, cur_e = 0, None, None
    for s, e in evs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    red = TR.reduce_events(dev, spans, (evs[0][0], max(e for _, e in evs)))
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    # leaf ops tile the busy time but for the scan's own bookkeeping
    assert 0.95 * red["busy_s"] <= sum(red["ops"].values()) <= \
        red["busy_s"] + 1e-9
    names = set(red["ops"])
    for k in ("posit_gemm.", "paged_flash_decode."):
        assert any(n.startswith(k) for n in names), sorted(names)[:40]
    # the layer scan's `while` holds the others and is left out of ops
    assert not any(n.startswith("while") for n in names)
    assert any(n.startswith("bench.") for n, _, _ in spans)
