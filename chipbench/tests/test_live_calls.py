"""``live_calls.cs_adam_tiled``: the kernel's calls per step and device,
on hand-made traces and on the v5e trace of ``Trainer.fit`` kept in
``data/`` (three steps of a 1,024-id batch: one call a step)."""
import pathlib
import types

import pytest

from chipbench import trace
from chipbench.bench import Benchmark
from chipbench.tests import fixtures

TRAIN = pathlib.Path(__file__).resolve().parent / "data" / \
    "trace_train.xplane.pb"


@pytest.fixture(scope="module")
def reader():
    return Benchmark(fixtures.REPO).metric_reader(
        {"name": "live_calls.cs_adam_tiled"})


def _ctx(calls, devices, steps):
    """A loop of ``calls`` kernel calls inside a ``while`` per step, with
    a fusion beside it, on each of ``devices``."""
    ops, t = [], 0
    for _ in range(steps):
        ops.append(trace.Op("while.1", t, t + 10 * calls + 10, "while"))
        ops += [trace.Op("cs_adam_tiled.4", t + 10 * i, t + 10 * i + 9,
                         "custom-call") for i in range(calls)]
        ops.append(trace.Op("fusion.8", t + 10 * calls + 10,
                            t + 10 * calls + 20, "fusion"))
        t += 10 * calls + 30
    red = trace.Reduced(devices={f"/device:TPU:{d}": list(ops)
                                 for d in range(devices)},
                        host=[], window=(0, t))
    return types.SimpleNamespace(trace=red, steps=steps)


@pytest.mark.parametrize("calls,devices,steps",
                         [(10, 1, 3), (82, 1, 2), (4, 2, 5), (1, 1, 1)])
def test_counts_calls_per_step_and_device(reader, calls, devices, steps):
    assert reader.read(_ctx(calls, devices, steps)) == calls


def test_no_kernel_reads_none(reader):
    assert reader.read(_ctx(0, 1, 3)) is None
    ctx = _ctx(4, 1, 2)
    ctx.steps = 0
    assert reader.read(ctx) is None


def test_train_trace_reads_one_call_a_step(reader):
    red = trace.reduce_file(str(TRAIN), n_devices=1)
    ctx = types.SimpleNamespace(trace=red, steps=3)
    assert reader.read(ctx) == 1.0
