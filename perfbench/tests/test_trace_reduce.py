"""The trace reduction: busy union, kernel time by name, module runs,
idle gaps, and a roofline share from ``peaks.json``.

A hand-built trace with known answers, and (where it is committed) a
small trace recorded on the chip."""

import collections
import glob
import os

import pytest

from perfbench import readers, roofline, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

Ev = collections.namedtuple("Ev", "name start_ns end_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines stats")
Profile = collections.namedtuple("Profile", "planes")


def ev(name, s, e):
    return Ev(name, s, e, e - s)


def fake_profile():
    ops = [ev("segment_aggregate", 100, 400), ev("fusion.1", 350, 450),
           ev("segment_aggregate.2", 600, 900), ev("scalegate_merge", 950,
                                                   960),
           ev("segment_aggregate_x", 970, 980)]
    mods = [ev("jit__persistent_fn(12)", 90, 910),
            ev("jit_push(3)", 940, 990)]
    host = [ev("bench.sink_fetch", 455, 590),
            ev("whole-run host span", 0, 2_000_000_000)]
    return Profile([
        Plane("Task Environment", [], [("profile_start_time", 0),
                                       ("profile_stop_time", 1000)]),
        Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                Line("XLA Modules", mods)], []),
        Plane("/host:CPU", [Line("python", host)], []),
    ])


def test_busy_is_the_union_of_op_intervals():
    tr = trace_reduce.reduce(fake_profile())
    # [100, 450] + [600, 900] + [950, 960] + [970, 980]
    assert tr.devices[0].busy_ns == 350 + 300 + 10 + 10
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.busy_s == pytest.approx(670e-9)


def test_kernel_time_by_name_and_inside_module_runs():
    dev = trace_reduce.reduce(fake_profile()).devices[0]
    assert dev.op_ns("segment_aggregate") == (600, 2)
    runs = dev.module_runs(r"jit__persistent_fn")
    assert [n for _, _, n in runs] == ["jit__persistent_fn(12)"]
    assert dev.op_ns("scalegate_merge", inside=runs) == (0, 0)
    assert dev.op_ns("segment_aggregate", inside=runs) == (600, 2)


def test_idle_gaps_are_labelled_by_the_host():
    tr = trace_reduce.reduce(fake_profile())
    gaps = tr.idle_gaps(2)
    assert gaps[0] == ["bench.sink_fetch", pytest.approx(150e-9)]
    assert len(gaps) == 2
    top = dict(tr.top_ops(10))
    assert top["segment_aggregate"] == pytest.approx(300e-9)


def test_top_ops_rank_by_self_time():
    ops = [("while.1", 0, 100), ("body_a", 10, 40), ("body_b", 50, 60),
           ("inner", 12, 20), ("after", 100, 130)]
    got = dict(trace_reduce.self_ns([(s, e, n) for n, s, e in ops]))
    assert got == {"while.1": 100 - 30 - 10, "body_a": 30 - 8,
                   "body_b": 10, "inner": 8, "after": 30}


def test_roofline_share_from_the_peaks_table():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")
    ctx = {"trace": trace_reduce.reduce(fake_profile()), "peaks": pk,
           "run": {"step_module": r"jit__persistent_fn",
                   "ticks_per_run": 2, "shards": 1}}
    share = readers.kernel_roofline(ctx, "segment_aggregate",
                                    lambda run: 100.0, per_block=True)
    # 2 ticks x 100 bytes at 819 GB/s over 600 ns
    assert share == pytest.approx(100 * (200 / 819e9) / 600e-9)
    assert readers.tick_device_ms(ctx) == pytest.approx(820 / 1e6 / 2)


def test_segment_aggregate_bytes_count_hits_and_cells_only():
    # one hit per cell: 8 bytes of key and slot, 4 of value, and the cell's
    # accumulator read and written once (2 x 4)
    assert roofline.segment_aggregate_bytes(10, 10) == 10 * (8 + 4 + 8)
    assert roofline.scalegate_merge_bytes(3) == 3 * 20


CHIP_TRACES = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", CHIP_TRACES,
                         ids=[os.path.basename(p) for p in CHIP_TRACES])
def test_trace_recorded_on_the_chip(path):
    tr = trace_reduce.reduce(trace_reduce.load(path))
    assert tr.devices and 0 < tr.busy_s <= tr.window_s
    ns, calls = tr.devices[min(tr.devices)].op_ns("segment_aggregate")
    assert calls > 0 and ns > 0
    assert tr.top_ops(1)[0][0].startswith("segment_aggregate")
    ctx = {"trace": tr, "peaks": roofline.peaks("TPU v5 lite"),
           "run": {"step_module": r"jit__persistent_fn",
                   "ticks_per_run": 8, "shards": 1}}
    hits = 8192 * 6 * 2                       # a tick's hits, about
    share = readers.kernel_roofline(
        ctx, "segment_aggregate",
        lambda run: roofline.segment_aggregate_bytes(hits, hits), True)
    assert 0 < share < 100
    assert readers.tick_device_ms(ctx) > 0
