"""Trace reduction, operation and byte counts, and the peaks table."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import costs  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness import View, breakdown, load_module, reader_path  # noqa: E402

RECORDED = os.path.join(HERE, "data", "probe_trace.json")


def recorded():
    with open(RECORDED) as f:
        data = json.load(f)
    devices = {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
               for p, lines in data["devices"].items()}
    return tr.Trace(devices, [tuple(s) for s in data["spans"]])


def synthetic():
    # one chip; a loop op spanning two inner ops, then a gap, then a
    # kernel; the host spans cover the first gap with "bench.fetch"
    ops = [("%while.1", 100.0, 50.0), ("%fusion.2", 100.0, 20.0),
           ("%fusion.3", 130.0, 20.0), ("%diffuse_evaporate.4", 200.0, 30.0),
           ("%diffuse_evaporate.4", 240.0, 10.0)]
    mods = [("jit_ants_evaluation", 100.0, 50.0),
            ("jit_ants_evaluation", 200.0, 50.0)]
    spans = [("bench.window", 90.0, 170.0), ("bench.fetch", 150.0, 50.0)]
    return tr.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, spans)


def test_busy_and_idle_share_on_a_synthetic_trace():
    t = synthetic()
    ops = t.devices["/device:TPU:0"]["ops"]
    lo, hi = t.span("bench.window")
    assert (lo, hi) == (90.0, 260.0)
    assert tr.intervals(ops) == [(100.0, 150.0), (200.0, 230.0),
                                 (240.0, 250.0)]
    assert tr.busy_ns(ops, lo, hi) == 90.0
    gaps = tr.idle_gaps(ops, lo, hi)
    assert gaps == [(90.0, 100.0), (150.0, 200.0), (230.0, 240.0),
                    (250.0, 260.0)]
    assert sum(b - a for a, b in gaps) == (hi - lo) - 90.0
    view = View(t, 1, {}, "TPU v5 lite")
    assert view.busy_s() == pytest.approx(90e-9)
    idle = load_module(reader_path("device_idle.init"),
                       "m").read(view)
    assert idle == pytest.approx(100 * 80 / 170)


def test_gaps_are_attributed_to_the_innermost_benchmark_span():
    t = synthetic()
    lo, hi = t.span("bench.window")
    gaps = tr.idle_gaps(t.devices["/device:TPU:0"]["ops"], lo, hi)
    got = tr.attribute(gaps, t.spans)
    assert got == {"bench.window": 30.0, "bench.fetch": 50.0}
    assert tr.attribute([(0.0, 5.0)], t.spans) == {"no benchmark span": 5.0}


def test_kernel_time_by_name_and_loop_ops_left_out_of_totals():
    t = synthetic()
    ops = t.devices["/device:TPU:0"]["ops"]
    assert tr.by_name(ops, "%diffuse_evaporate") == (40.0, 2)
    totals = tr.op_totals(ops, skip=("%while",))
    assert "%while.1" not in totals
    assert sum(totals.values()) == 80.0
    view = View(t, 1, {"ants_module": "jit_ants_evaluation",
                       "lanes_per_program": 4, "ticks": 5,
                       "kernel": "%diffuse_evaporate",
                       "kernel_bytes_per_call": 8190}, "TPU v5 lite")
    read = {n: load_module(reader_path(n), "m").read(view)
            for n in ("lane_tick_ns.init", "diffusion_roofline.init",
                      "chunk_gap_ms.init")}
    assert read["lane_tick_ns.init"] == pytest.approx(100.0 / (2 * 4 * 5))
    # 2 calls x 8190 bytes at 819 GB/s take 20 ns; the kernel took 40 ns
    assert read["diffusion_roofline.init"] == pytest.approx(50.0)
    assert read["chunk_gap_ms.init"] == pytest.approx(50.0 / 1e6)


def test_recorded_chip_trace_reduces_to_its_known_numbers():
    t = recorded()
    dev = t.devices["/device:TPU:0"]
    assert tr.by_name(dev["ops"], "%diffuse_evaporate") == (1782110.0, 100)
    assert tr.by_name(dev["ops"], "%dominance_pass") == (22647.0, 1)
    assert [m[0] for m in dev["modules"]] == ["jit_tapped", "jit_tapped",
                                              "jit_merge"]
    lo, hi = t.span("bench.window")
    busy = tr.busy_ns(dev["ops"], lo, hi)
    gaps = tr.idle_gaps(dev["ops"], lo, hi)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(hi - lo)
    # the loop ops span each run's 50 ticks: the chip is busy for nearly
    # all of the two runs, which is most of the window
    runs = sum(m[2] for m in dev["modules"][:2])
    assert 0.98 * runs <= busy <= hi - lo
    view = View(t, 1, {}, "TPU v5 lite")
    b = breakdown(view)
    assert "%diffuse_evaporate.13" in [n for n, _ in b["device_ops"]]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        (hi - lo - busy) / 1e9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_tick_bytes_and_operations_at_the_papers_size():
    model = {"world_size": 72, "population": 125}
    # chemical field read and written once in f32: 2 x 72 x 72 x 4;
    # positions (2 x int32) and carrying flags (1 byte) read and written:
    # 2 x 125 x 9; the food under each ant read: 125 x 4
    assert costs.tick_bytes(model) == 41472 + 2250 + 500 == 44222
    assert costs.tick_flops(model) == 12 * 5184 + 24 * 125
    # at 30 us per lane-tick the tick reads 0.18% of a v5e: bytes bound
    share = costs.roofline_share(costs.tick_flops(model),
                                 costs.tick_bytes(model), 30e-6,
                                 "TPU v5 lite")
    assert share == pytest.approx(100 * 44222 / 819e9 / 30e-6)


def test_diffusion_bytes_count_each_lanes_field_once_each_way():
    # 320 lanes of a 72 x 72 float32 field, read and written, and two rates
    assert costs.diffusion_bytes({"world_size": 72}, 320) == \
        320 * (2 * 72 * 72 * 4 + 8)


def test_a_device_kind_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")
