"""The reduction from a profiler trace to metrics, checked on a small trace
recorded on a TPU v5e (three steps of a 65,536-row sparse cell, the
``cs_adam_tiled`` kernel on its path) and on hand-made intervals."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


def test_union_and_gaps_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.union([(0, 10)], window=(2, 4)) == [(2, 4)]
    r = trace.Reduced(
        devices={"/device:TPU:0": [trace.Op("a", 10, 20),
                                   trace.Op("b", 15, 30),
                                   trace.Op("c", 50, 60)]},
        host=[("bench.data", 0, 12), ("bench.step", 30, 45)],
        window=(0, 100))
    assert r.busy_ns("/device:TPU:0") == 30
    assert r.gaps("/device:TPU:0") == [(0, 10), (30, 50), (60, 100)]
    assert r.label((0, 10)) == "bench.data"
    assert r.label((30, 50)) == "bench.step"
    assert r.label((60, 100)).startswith("host")
    b = r.breakdown()
    assert b["device_ops"][0] == ["b", 15e-9]
    assert b["idle_gaps"][0][1] == pytest.approx(40e-9)


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_file(str(DATA), n_devices=1)


def test_recorded_trace_has_device_ops_and_bench_spans(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    ops = recorded.devices["/device:TPU:0"]
    assert len(ops) > 10
    names = {h[0] for h in recorded.host}
    assert {"bench.data", "bench.step"} <= names


def test_recorded_busy_within_window(recorded):
    assert 0 < recorded.busy_s <= recorded.window_s
    # the union never exceeds the sum of the op durations
    ops = recorded.devices["/device:TPU:0"]
    total = sum(o.end - o.start for o in ops) / 1e9
    assert recorded.busy_s <= total + 1e-12


def test_op_names_and_opcodes():
    text = ("%psum.46 = f32[3,333440,128]{2,1,0:T(8,128)} all-reduce("
            "f32[3,333440,128]{2,1,0:T(8,128)} %fusion.3), channel_id=1")
    assert trace.op_name(text) == "psum.46"
    assert trace.op_code(text) == "all-reduce"
    t2 = ("%while.1 = (s32[]{:T(128)}, f32[3]{0:T(128)}) while((s32[]"
          "{:T(128)}, f32[3]{0:T(128)}) %tuple.26), condition=%c")
    assert trace.op_code(t2) == "while"
    assert trace.op_code("%copy-start = (s32[1,65536]{1,0:T(1,128)S(1)}, "
                         "u32[]{:S(2)}) copy-start(s32[1,65536]") == "copy-start"


def test_recorded_opcodes(recorded):
    codes = {o.opcode for o in recorded.devices["/device:TPU:0"]}
    assert "custom-call" in codes and "fusion" in codes


def test_recorded_kernel_time(recorded):
    k = recorded.kernel_ns("cs_adam_tiled")
    assert 0 < k < recorded.busy_s * 1e9


def test_recorded_breakdown_shape(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    for label, s in b["idle_gaps"]:
        assert s > 0 and (label.startswith("bench.")
                          or label.startswith("host"))
