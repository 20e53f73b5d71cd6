"""The trace reduction on a small synthetic event list (no chip)."""
import pytest

import names
import tracesum

KERNEL = "%_fused_join_hits_pallas.1 = (s8[2,128,32]) custom-call(%a, %b)"
DECOY = "%get-tuple-element.5 = s8[2,128,32] get-tuple-element(" \
    "%_fused_join_hits_pallas.1)"

HOST = ("/host:CPU", [("main", [
    ("bench.window", 100.0, 1000.0),
    ("bench.join", 100.0, 500.0),
    ("PjitFunction(step)", 600.0, 500.0),
])])
DEVICE = ("/device:TPU:0", [
    ("XLA Ops", [
        (KERNEL, 200.0, 100.0),
        ("fusion.1", 250.0, 150.0),       # overlaps the kernel
        ("scatter.2", 700.0, 100.0),
        (DECOY, 790.0, 10.0),
        ("fusion.1", 2000.0, 50.0),       # after the window
    ]),
    ("XLA Modules", [
        ("jit__emit_from_hits(7)", 650.0, 200.0),
        ("jit_self_join(3)", 50.0, 150.0),   # starts before the window
    ]),
])


def test_union_and_gaps():
    ivs = [(0, 10), (5, 15), (20, 30)]
    assert tracesum.union_length(ivs) == 25
    assert tracesum.gaps(ivs, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert tracesum.gaps([], 0, 10) == [(0, 10)]


def test_busy_and_idle_share():
    s = tracesum.summarize([HOST, DEVICE])
    assert s.window_s == pytest.approx(1000e-9)
    # union of [200, 400) and [700, 800) inside [100, 1100)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.device_events == (4, 5)


def test_attribution_by_name():
    s = tracesum.summarize([HOST, DEVICE])
    assert s.seconds_prefix(s.ops, names.KERNEL_OP_PREFIX) == \
        pytest.approx(100e-9)              # the decoy names it as operand
    assert s.ops["fusion.1"][1] == 1          # the late event is outside
    assert s.seconds(s.modules, *names.EMIT_MODULES) == \
        pytest.approx(200e-9)
    # clipped to the window: [100, 200) of the module that began at 50
    assert s.seconds(s.modules, "self_join") == pytest.approx(100e-9)
    assert s.top(s.ops, 1) == [["fusion.1", pytest.approx(150e-9)]]
    # gaps [100,200) and [400,700) fall in bench.join, [800,1100) in the
    # innermost host event there
    assert s.idle_by_host == {
        "bench.join": pytest.approx(400e-9),
        "PjitFunction(step)": pytest.approx(300e-9)}
    assert s.spans["bench.join"] == [pytest.approx(500e-9)]


def test_no_window_span_is_an_error():
    host = ("/host:CPU", [("main", [("bench.join", 0.0, 10.0)])])
    with pytest.raises(ValueError, match="bench.window"):
        tracesum.summarize([host, DEVICE])


def test_no_device_reads_zero_busy():
    s = tracesum.summarize([HOST])
    assert s.n_devices == 0 and s.busy_s == 0.0
