"""The reduction from trace rows to busy seconds and idle gaps: a
hand-worked case, and a slice recorded on the chip (data/trace_rows.json)
against a brute-force timeline."""

import json
import os

import pytest
import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
SPAN = "bench_request"


def test_hand_worked_case():
    rows = [
        [HOST, "python3", "bench_request:a", 1000.0, 9000.0],     # 1000-10000
        [HOST, "python3", "bench_request:b", 12000.0, 8000.0],    # 12000-20000
        [HOST, "srv", "np.asarray(jax.Array)", 6000.0, 3000.0],   # 6000-9000
        [DEV, "XLA Ops", "%f.1 = f32[8]{0} fusion(x)", 0.0, 2000.0],      # clipped to 1000-2000
        [DEV, "XLA Ops", "%f.2 = f32[8]{0} fusion(y)", 1500.0, 1500.0],   # overlaps: 1500-3000
        [DEV, "XLA Ops", "%f.1 = f32[8]{0} fusion(x)", 13000.0, 1000.0],  # 13000-14000
        [DEV, "XLA Ops", "%f.3 = f32[8]{0} copy(z)", 25000.0, 1000.0],    # outside the window
        [DEV, "XLA Modules", "jit_kernel(1)", 900.0, 2200.0],
        [DEV, "Async XLA Ops", "%copy-start", 0.0, 20000.0],              # not an operation line
    ]
    red = tr.reduce_events(rows, SPAN)
    assert red["window_s"] == pytest.approx(19000e-9)
    # union: 1000-3000 and 13000-14000
    assert red["busy_s"] == pytest.approx(3000e-9)
    assert red["requests"] == 2 and red["devices"] == 1
    # gaps: 3000-13000 (middle 8000: request a, inside np.asarray) and
    # 14000-20000 (middle 17000: request b, no host span)
    assert red["idle_gaps"] == 2
    assert red["longest_gap_s"] == pytest.approx(10000e-9)
    assert dict(map(tuple, red["breakdown"]["idle_gaps"])) == pytest.approx({
        "a|np.asarray(jax.Array)": 10000e-9, "b|host_without_span": 6000e-9})
    ops = dict(map(tuple, red["breakdown"]["device_ops"]))
    assert ops == pytest.approx({"%f.1 = fusion(x)": 2000e-9,
                                 "%f.2 = fusion(y)": 1500e-9})
    assert red["modules"] == [["jit_kernel(1)", pytest.approx(2100e-9)]]


def test_nothing_to_read_gives_nothing():
    spans = [[HOST, "python3", "bench_request:a", 0.0, 10.0]]
    ops = [[DEV, "XLA Ops", "%f = f32[] fusion()", 0.0, 5.0]]
    assert tr.reduce_events(spans, SPAN) is None   # no device operation
    assert tr.reduce_events(ops, SPAN) is None     # no request span
    assert tr.reduce_events(spans + ops, SPAN)["busy_s"] == pytest.approx(5e-9)


def test_recorded_slice_against_a_timeline():
    path = os.path.join(os.path.dirname(__file__), "data", "trace_rows.json")
    with open(path) as f:
        rows = json.load(f)["rows"]
    red = tr.reduce_events(rows, SPAN)
    spans = [r for r in rows if r[2].startswith(SPAN)]
    assert red["requests"] == len(spans) == 10
    w0 = min(r[3] for r in spans)
    w1 = max(r[3] + r[4] for r in spans)
    # brute force: mark every 100 ns tick of the window an operation covers
    tick = 100.0
    n = int((w1 - w0) / tick) + 1
    busy = bytearray(n)
    for r in rows:
        if r[0] == DEV and r[1] == "XLA Ops":
            a = max(0, int((r[3] - w0) / tick))
            b = min(n, int((r[3] + r[4] - w0) / tick) + 1)
            if b > a:
                busy[a:b] = b"\x01" * (b - a)
    assert red["busy_s"] == pytest.approx(sum(busy) * tick * 1e-9, rel=0.02)
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    # as read on the chip for these ten requests (PR 26)
    assert red["busy_s"] == pytest.approx(0.041935, rel=1e-3)
    assert red["window_s"] == pytest.approx(0.149719, rel=1e-3)
    idle = sum(v for _k, v in red["breakdown"]["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
