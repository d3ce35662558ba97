"""The trace reduction and every per-layer metric reader, on fixed
inputs: a synthetic trace with known busy time, idle gaps and kernel
time, and hand-made observations."""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import cost, run, trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def planes(device_events, host_events, device_line="XLA Ops"):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": device_line, "events": device_events},
            {"name": "XLA Modules", "events": [("jit_step", 0, 100 * MS)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events}]},
        {"name": "/host:metadata", "lines": []},
    ]


WINDOW = [("window", 10 * MS, 100 * MS)]  # [10, 110) ms


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    dev = [("bitunpack", 0, 20 * MS),           # clipped to [10, 20)
           ("fusion.1", 15 * MS, 10 * MS),      # overlaps: [15, 25)
           ("bitunpack.3", 50 * MS, 5 * MS),    # [50, 55)
           ("copy", 105 * MS, 20 * MS)]         # clipped to [105, 110)
    red = tr.reduce(planes(dev, WINDOW))
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.015 + 0.005 + 0.005)
    assert red["devices"] == 1
    secs, n = tr.op_seconds(red, "bitunpack")
    assert (secs, n) == (pytest.approx(0.015), 2)
    assert tr.op_seconds(red, "fusion") == (pytest.approx(0.010), 1)
    assert tr.op_seconds(red, "fus") == (0.0, 0)


def test_idle_gaps_are_labelled_by_the_span_open_at_their_midpoint():
    dev = [("op", 20 * MS, 10 * MS), ("op", 60 * MS, 10 * MS),
           ("op", 85 * MS, 5 * MS)]
    host = WINDOW + [("scan.plan", 10 * MS, 8 * MS),      # gap [10,20)
                     ("scan.execute", 30 * MS, 28 * MS),  # gap [30,60)
                     ("osd.serve", 70 * MS, 10 * MS)]     # gap [70,85)
    red = tr.reduce(planes(dev, host))                    # gap [90,110)
    gaps = dict(red["top_gaps"])
    assert gaps["scan.plan"] == pytest.approx(0.010)
    assert gaps["scan.execute"] == pytest.approx(0.030)
    assert gaps["osd.serve"] == pytest.approx(0.015)
    assert gaps[tr.NO_SPAN] == pytest.approx(0.020)
    assert red["spans"]["scan.plan"] == [pytest.approx(0.008)]
    # a span no one named beforehand is kept by its name
    assert red["spans"]["osd.serve"] == [pytest.approx(0.010)]


def test_the_innermost_span_on_a_python_line_labels_a_gap():
    ps = planes([("op", 10 * MS, 10 * MS)], WINDOW + [
        ("scan.execute", 20 * MS, 80 * MS),
        ("$store.py:10 exec_concat", 20 * MS, 70 * MS),
        ("osd.serve", 30 * MS, 40 * MS),        # nested: innermost
        ("digest", 40 * MS, 10 * MS)])          # deeper still
    ps[1]["lines"].append({"name": "tf_runtime", "events": [
        ("Runtime::Poll", 44 * MS, 60 * MS)]})  # no Python on this line
    red = tr.reduce(ps)
    # gaps [20, 110): midpoint 65 ms lies in osd.serve, not in digest
    # (closed) and not in the runtime's later-starting poll
    assert dict(red["top_gaps"]) == {"osd.serve": pytest.approx(0.090)}
    # the runtime's span is still kept for a reader; Python calls are not
    assert red["spans"]["Runtime::Poll"] == [pytest.approx(0.060)]
    assert not any(k.startswith("$") for k in red["spans"])


def test_innermost_sweeps_points_in_order():
    spans = [("a", 0, 100), ("b", 10, 20), ("c", 15, 18), ("d", 50, 60)]
    assert tr.innermost(spans, [5, 12, 16, 19, 30, 55, 100, 120]) == [
        "a", "b", "c", "b", "a", "d", tr.NO_SPAN, tr.NO_SPAN]
    assert tr.innermost([], [1.0]) == [tr.NO_SPAN]


def test_ops_come_from_every_line_when_there_is_no_ops_line():
    red = tr.reduce(planes([("k", 10 * MS, 50 * MS)], WINDOW,
                           device_line="Ops"))
    assert red["busy_s"] == pytest.approx(0.090)  # module line: [10, 100)


def test_a_cpu_trace_has_no_device_and_no_busy_time():
    red = tr.reduce([{"name": "/host:CPU", "lines": [
        {"name": "python", "events": WINDOW}]}])
    assert red["devices"] == 0 and red["busy_s"] == 0.0
    with pytest.raises(ValueError):
        tr.reduce([{"name": "/host:CPU", "lines": []}])


def test_device_planes_and_op_names():
    assert tr.is_device_plane("/device:TPU:0")
    assert tr.is_device_plane("/device:TPU:3")
    for name in ("/device:CPU:0", "/device:CUSTOM:Megascale Trace",
                 "/host:CPU", "#Chip0 Host Interface"):
        assert not tr.is_device_plane(name)
    assert tr.op_name("%bitunpack.1 = s32[480,128]{1,0} custom-call("
                      "s32[480,48]{1,0} %copy.2)") == "bitunpack.1"
    assert tr.op_name("%while.246 = (s32[], bf16[4])") == "while.246"
    assert tr.op_name("scan.plan") == "scan.plan"


def test_a_runtime_plane_beside_the_chip_is_not_a_device():
    ps = planes([("op", 10 * MS, 50 * MS)], WINDOW)
    ps.append({"name": "/device:CUSTOM:Megascale Trace", "lines": []})
    red = tr.reduce(ps)
    assert red["devices"] == 1 and red["busy_s"] == pytest.approx(0.050)


def test_union_gaps_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert tr.clip([(0, 5), (8, 20)], 2, 10) == [(2, 5), (8, 10)]


def _reader(name: str):
    path = run.reader_file(name, ROOT)
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def obs(**kw):
    base = {"trace": {"window_s": 10.0, "busy_s": 0.5, "devices": 1,
                      "ops": {"bitunpack": {"seconds": 0.01, "count": 100}},
                      "spans": {"scan.plan": [0.001, 0.003, 0.002],
                                "loader.next": [0.0002, 0.0001, 0.0004]}},
            "counters": {"requests": 4, "result_rows": 1000,
                         "client_rx_bytes": 150_000, "decode_calls": 3200,
                         "bitunpack_bytes": 819e9 * 0.004, "tokens": 65536,
                         "flops_per_token": 3e9},
            "window_s": 2.0, "peaks": PEAKS}
    base.update(kw)
    return base


def test_metric_readers_on_fixed_inputs():
    o = obs()
    assert _reader("plan_ms")(o) == pytest.approx(2.0)
    assert _reader("input_wait_ms")(o) == pytest.approx(0.2)
    assert _reader("rx_bytes_per_row")(o) == pytest.approx(150.0)
    assert _reader("decode_calls_per_scan")(o) == pytest.approx(800.0)
    assert _reader("bitunpack_roofline")(o) == pytest.approx(40.0)
    for cell in ("scan", "range", "train"):
        assert _reader(f"device_idle.{cell}")(o) == pytest.approx(95.0)
    assert _reader("train_mfu")(o) == pytest.approx(
        100 * 3e9 * 65536 / 2.0 / 197e12)


def test_metric_readers_return_nothing_when_nothing_is_there():
    empty = obs(trace={"window_s": 10.0, "busy_s": 0.0, "devices": 0,
                       "ops": {}, "spans": {}},
                counters={"requests": 0, "result_rows": 0,
                          "bitunpack_bytes": math.nan})
    for name in ("plan_ms", "input_wait_ms", "rx_bytes_per_row",
                 "decode_calls_per_scan", "bitunpack_roofline",
                 "device_idle.scan", "train_mfu"):
        assert _reader(name)(empty) is None, name
    # a count of zero is a reading; a roofline with no kernel is not
    zero = obs(counters={"requests": 5, "decode_calls": 0,
                         "bitunpack_bytes": 0.0})
    assert _reader("decode_calls_per_scan")(zero) == 0.0
    assert _reader("bitunpack_roofline")(zero) is None


def test_bitunpack_launch_bytes_follow_the_adapter_padding():
    # 60,416 values: 1,888 groups, 472 rows -> two blocks of 240
    assert cost.bitunpack_launch_rows(60_416) == 480
    assert cost.bitunpack_launch_rows(100) == 1
    assert cost.bitunpack_launch_rows(32 * 4 * 256) == 256
    assert cost.bitunpack_bytes(60_416, 12) == 480 * (4 * 12 * 4 + 512)


def test_bitunpack_launch_rows_match_the_kernel_adapter():
    from repro.kernels.bitunpack import pad_to_grid
    for n in (1, 31, 4096, 60_416, 19_031, 1 << 20):
        rows = -(-(-(-n // 32)) // 4)
        assert cost.bitunpack_launch_rows(n) == pad_to_grid(rows)[1]


def test_train_flops_per_token_of_the_yi_cell():
    cfg = json.loads((ROOT / "bench/configs/yi9b_2l_train.json").read_text())
    f = cost.lm_train_flops_per_token(cfg["model"], 4096)
    matmul = 2 * (4096 * 40 * 128 + 4096 * 4096 + 3 * 4096 * 11008) \
        + 4096 * 64000
    assert f == 6 * matmul + 12 * 2 * 4096 * 4096
    assert f == pytest.approx(4.05e9, rel=0.01)
