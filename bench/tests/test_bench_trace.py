"""The trace reduction gives known numbers on hand-made planes and on a
small trace recorded on a TPU v5 lite (``data/small_fig1_single``)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace_reduce as tr  # noqa: E402

RECORDED = ROOT / "bench" / "tests" / "data" / "small_fig1_single.xplane.pb"
MS = 1_000_000


def _planes():
    host = ("/host:CPU", [("python", [
        ("window", 0, 100 * MS), ("job", 0, 100 * MS),
        ("inputs", 0, 30 * MS), ("summaries", 70 * MS, 30 * MS),
        ("window", 200 * MS, 50 * MS), ("job", 200 * MS, 50 * MS),
        ("inputs", 200 * MS, 10 * MS), ("summaries", 240 * MS, 10 * MS)])])
    dev0 = ("/device:TPU:0", [
        ("XLA Modules", [("jit_sweep", 0, 250 * MS)]),
        ("XLA Ops", [("%fusion.1 = f32[8] fusion(%p)", 10 * MS, 40 * MS),
                     ("%while.7 = (s32[]) while(%t)", 12 * MS, 30 * MS),
                     ("%while.8 = (s32[]) while(%u)", 20 * MS, 30 * MS),
                     ("fusion.2", 30 * MS, 30 * MS),      # overlaps .1
                     ("all-reduce.3", 60 * MS, 5 * MS),
                     ("fusion.1", 210 * MS, 20 * MS)])])
    dev1 = ("/device:TPU:1", [
        ("XLA Ops", [("fusion.1", 0, 100 * MS),
                     ("all-reduce.3", 200 * MS, 10 * MS)])])
    return [host, dev0, dev1]


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_reduce_hand_made_planes():
    red = tr.reduce_planes(_planes(), 2)
    assert red["window_s"] == pytest.approx(0.25)
    # chip 0: [10, 65) and [210, 230) ms; chip 1: [0, 100) and [200, 210)
    assert red["busy_per_device"] == pytest.approx([0.075, 0.11])
    assert red["busy_s"] == pytest.approx(0.0925)
    # chip 0's loops: [12, 42) and the nested [20, 50) count once
    assert red["loop_per_device"] == pytest.approx([0.038, 0.0])
    assert red["collective_per_device"] == pytest.approx([0.005, 0.01])
    assert red["jobs"] == 2
    assert sorted(red["spans"]["inputs"]) == pytest.approx([0.01, 0.03])
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(0.02)
    assert ops["fusion.1"] == pytest.approx((0.02 + 0.1) / 2)
    gaps = red["breakdown"]["idle_gaps"]
    # chip 0's idle gaps, longest first, labelled by the host span
    assert [g[0] for g in gaps] == ["between jobs", "summaries", "inputs"]
    assert [g[1] for g in gaps] == pytest.approx([0.145, 0.02, 0.01])
    assert sum(g[1] for g in gaps) == pytest.approx(0.25 - 0.075)


def test_one_chip_of_a_cell_is_read():
    red = tr.reduce_planes(_planes(), 1)
    assert red["devices"] == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(0.075)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes([("/host:CPU", [("python", [])])], 1)


def test_recorded_trace():
    """Two 200 us jobs of fig1_single: 6.0 ms of device work in a 70.2 ms
    window, most of the idle time in the input build."""
    red = tr.reduce_file(str(RECORDED), 1)
    assert red["devices"] == ["/device:TPU:0"]
    assert red["jobs"] == 2
    assert red["window_s"] == pytest.approx(0.070214992)
    assert red["busy_s"] == pytest.approx(0.005988699)
    assert red["collective_per_device"] == [0.0]
    # the loop is all but the whole of the device time
    assert red["loop_per_device"] == pytest.approx([0.005962205])
    assert len(red["spans"]["inputs"]) == 2
    assert red["breakdown"]["device_ops"][0][0] == "%while.94"
    assert red["breakdown"]["idle_gaps"][0][0] == "inputs"


def test_device_us_per_step_reads_the_loop_only():
    """Loop time of the busiest chip per job over the loop steps per job:
    the trace's other device operations do not count."""
    import importlib.util
    path = ROOT / "bench" / "metrics" / "device_us_per_step.py"
    spec = importlib.util.spec_from_file_location("dups", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    red = tr.reduce_planes(_planes(), 2)
    # one traced job per window span, each a call of 128-event chunks
    # whose longest lane needs two chunks: 256 steps a job
    ctx = {"trace": red, "lane_events": [[(128, [200, 50])]] * 2}
    assert mod.read(ctx) == pytest.approx(0.038 / 2 / 256 * 1e6)
    red["loop_per_device"] = [0.0, 0.0]
    assert mod.read(ctx) is None
