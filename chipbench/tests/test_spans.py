"""The program's spans in a trace (``spans.py``) and the readers of the
metrics built on them: on hand-made intervals, on the trace PR 13 recorded
(no ``train.*`` spans, no ``obs.apply`` scope: what a parent program
gives) and on a trace of ``Trainer.fit`` with its spans (three steps of a
65,536-row, 1,024-id sparse cell on a TPU v5e, with the scope of every op
its module's metadata gave, in ``trace_train.scopes.json``)."""
import json
import pathlib
import shutil
import types

import pytest

from chipbench import spans, trace
from chipbench.bench import Benchmark
from chipbench.tests import fixtures

DATA = pathlib.Path(__file__).resolve().parent / "data"
OLD = DATA / "trace_small.xplane.pb"
NEW = DATA / "trace_train.xplane.pb"
NEW_SCOPES = DATA / "trace_train.scopes.json"
READERS = ("host_ms.sparse", "dedup_ms.sparse", "apply_ms.sparse")
DEV = "/device:TPU:0"


def _reduced(ops, host=(), window=(0, 1000)):
    return trace.Reduced(devices={DEV: [trace.Op(*o) for o in ops]},
                         host=list(host), window=window)


def test_exclusive_time_counts_a_while_around_a_kernel_once():
    ops = [trace.Op("while.1", 0, 100), trace.Op("kernel.4", 10, 90),
           trace.Op("fusion.2", 100, 120), trace.Op("kernel.4", 20, 20)]
    ex = {(o.name, o.start): ns for o, ns in spans.exclusive_ns(ops)}
    assert ex == {("while.1", 0): 20, ("kernel.4", 10): 80,
                  ("fusion.2", 100): 20, ("kernel.4", 20): 0}
    # the same interval twice: the first is the parent, the second counted
    same = [trace.Op("while.1", 5, 50), trace.Op("kernel.4", 5, 50)]
    assert sorted(ns for _, ns in spans.exclusive_ns(same)) == [0, 45]


def test_scope_ns_sums_to_busy():
    r = _reduced([("while.1", 0, 100), ("kernel.4", 10, 90),
                  ("fusion.2", 100, 120), ("fusion.8", 200, 260),
                  ("copy.3", 300, 310)])
    scopes = {"while.1": "obs.kernel", "kernel.4": "obs.kernel",
              "fusion.2": "obs.dedup", "fusion.8": "unscoped"}
    got = {s: spans.scope_ns(r, s, scopes)
           for s in ("obs.kernel", "obs.dedup", "unscoped")}
    # copy.3 is not in the map: it counts as unscoped
    assert got == {"obs.kernel": 100, "obs.dedup": 20, "unscoped": 70}
    assert sum(got.values()) == r.busy_ns(DEV)


def _spans(program, host=(), modules=()):
    r = _reduced([("fusion", 100, 200), ("fusion", 400, 500)], host=host,
                 window=(100, 500))
    return spans.Spans(reduced=r, program=list(program),
                       modules={DEV: list(modules)}, hlo={})


def test_label_prefers_a_train_span():
    s = _spans(program=[("train.record", 200, 260),
                        ("train.dispatch", 260, 400)],
               host=[("bench.step", 250, 400)])
    # bench.step covers more of the gap; the program's phase still wins
    assert s.label((200, 400)) == "train.dispatch"
    bare = _spans(program=[], host=[("bench.step", 250, 400)])
    assert bare.label((200, 400)) == "bench.step"
    assert bare.label((600, 700)).startswith("host")
    assert s.idle_gaps() == [["train.dispatch", 200e-9]]


def test_host_device_corrects_the_device_clock():
    program = [("train.dispatch", 50, 60), ("train.wait", 60, 230),
               ("train.record", 230, 240), ("train.dispatch", 300, 330),
               ("train.wait", 330, 510)]
    mods = [("7", 100, 200), ("7", 400, 500)]
    s = _spans(program=program, modules=mods)
    # a module starts after its dispatch began, ends before its wait ended
    assert s.clock_offset() == (-10, 50) and s.offset == 20
    assert s.host_device() == [(30, 50), (80, 30)]
    assert s.host_ns() == 10 + 10 + 30
    summ = s.summary(steps=2)
    assert summ["clock_offset_us"] == {"lo": -0.01, "hi": 0.05,
                                       "used": 0.02}
    assert summ["launch_us"]["max"] == pytest.approx(0.08)
    assert summ["wake_us"]["min"] == pytest.approx(0.03)
    # the runtime's enqueue and sync-flag read bound it more tightly
    s.runtime = [("DoEnqueueProgram", 90, 95), ("ReadSyncFlag", 150, 205),
                 ("DoEnqueueProgram", 380, 385), ("ReadSyncFlag", 480, 505)]
    assert s.clock_offset() == (-5, 10)
    # a step without its module gives no pairing, and no offset
    s2 = _spans(program=program, modules=mods[:1])
    assert s2.clock_offset() is None and s2.host_device() == []
    assert s2.offset == 0.0


def _ctx(out_dir, monkeypatch, path, name="fixture-emb.cs_adam", steps=3):
    """A reader's context for a recorded trace put where ``run.py`` keeps
    the trace of a run of cell ``name``."""
    from chipbench import run
    monkeypatch.setattr(run, "OUT_DIR", out_dir)
    dest = out_dir / "trace" / name / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(path, dest / "host.xplane.pb")
    red = trace.reduce_dir(str(out_dir / "trace" / name), 1)
    return types.SimpleNamespace(
        trace=red, steps=steps,
        cell=types.SimpleNamespace(name=name, chips=1))


def _read(ctx):
    bench = Benchmark(fixtures.REPO)
    return {m: bench.metric_reader({"name": m}).read(ctx) for m in READERS}


def test_old_trace_reads_as_a_parent_program(tmp_path, monkeypatch):
    s = spans.load(str(OLD), 1)
    assert s.missing_ops() == []
    assert set(s.scopes.values()) >= {"obs.dedup", "obs.kernel"}
    assert "obs.apply" not in s.scopes.values()
    assert s.program == [] and s.host_device() == []
    got = _read(_ctx(tmp_path, monkeypatch, OLD))
    assert got["host_ms.sparse"] is None
    assert got["apply_ms.sparse"] is None
    assert got["dedup_ms.sparse"] > 0


def test_reader_ignores_a_trace_of_another_window(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, OLD)
    ctx.trace = _reduced([("fusion", 0, 10)])
    assert set(_read(ctx).values()) == {None}
    ctx.cell.name = "no-such-cell"
    assert set(_read(ctx).values()) == {None}


def test_program_without_scope_map_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "program_scope_map", lambda: None)
    spans._load_cached.cache_clear()
    got = _read(_ctx(tmp_path, monkeypatch, OLD))
    spans._load_cached.cache_clear()
    assert got["dedup_ms.sparse"] is None and got["apply_ms.sparse"] is None


@pytest.mark.parametrize("path", [OLD, NEW], ids=["old", "train"])
def test_device_by_scope_sums_to_busy(path):
    s = spans.load(str(path), 1)
    by = s.device_by_scope()
    assert sum(by.values()) == pytest.approx(s.reduced.busy_s * 1e9,
                                             rel=1e-9)
    assert by["obs.kernel"] > 0 and by["obs.dedup"] > 0


@pytest.fixture(scope="module")
def train():
    return spans.load(str(NEW), 1)


def test_train_trace_every_op_has_its_recorded_scope(train):
    with open(NEW_SCOPES) as f:
        recorded = json.load(f)
    ops = {o.name for o in train.reduced.devices[DEV]}
    assert train.missing_ops() == []
    assert {k: train.scopes[k] for k in ops} == recorded
    assert {"obs.dedup", "obs.kernel", "obs.apply"} <= set(recorded.values())


def test_train_trace_readers_return_values(train, tmp_path, monkeypatch):
    got = _read(_ctx(tmp_path, monkeypatch, NEW))
    assert all(v is not None and v > 0 for v in got.values()), got


def test_train_trace_has_the_loop_phases(train):
    names = [n for n, _, _ in train.program]
    assert {"train.data", "train.feed", "train.dispatch", "train.wait",
            "train.record", "train.checkpoint"} <= set(names)
    assert names.count("train.dispatch") == 3


def test_train_trace_gaps_are_labeled_by_phase(train):
    gaps = train.idle_gaps()
    assert gaps and all(label.startswith("train.") for label, _ in gaps)
    # the harness's label keeps naming the benchmark's own spans
    assert not train.reduced.label(train.reduced.gaps(DEV)[0]) \
        .startswith("train.")


def test_train_trace_wake_after_module_end(train):
    # the device's clock runs 1.39-1.86 ms behind the host's here: read
    # as they stand, the modules would start before their dispatch
    lo, hi = train.clock_offset()
    assert -2.0e6 < lo < hi < -1.0e6
    hd = train.host_device()
    assert len(hd) == 3
    assert all(launch > 0 and wake >= 0 for launch, wake in hd)
