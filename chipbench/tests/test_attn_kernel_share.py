"""``attn_kernel_share.lm``: the splash kernel's share of the device time
in ``obs.attn``, on hand-made traces: the kernel path, the scan (the
parent program: 0), and programs without the scope (nothing to read)."""
import types

import pytest

from chipbench import spans, trace
from chipbench.bench import Benchmark
from chipbench.tests import fixtures


@pytest.fixture(scope="module")
def reader():
    return Benchmark(fixtures.REPO).metric_reader(
        {"name": "attn_kernel_share.lm"})


def _ctx(monkeypatch, ops, scopes, steps=2, devices=1):
    """``ops`` ((name, start, end) in ns) on each of ``devices``, with the
    scope map ``scopes``."""
    red = trace.Reduced(
        devices={f"/device:TPU:{d}": [trace.Op(*o) for o in ops]
                 for d in range(devices)},
        host=[], window=(0, max(e for _, _, e in ops)))
    s = spans.Spans(reduced=red, program=[], modules={}, hlo={})
    s.__dict__["scopes"] = scopes
    monkeypatch.setattr(spans, "of", lambda ctx: s)
    return types.SimpleNamespace(trace=red, steps=steps)


# one step of a layer: the projections (fusion.1), the kernel's forward,
# dkv and dq calls, a transpose (copy.5), and ops of other scopes
KERNEL_STEP = [("fusion.1", 0, 100), ("splash_mha_fwd_residuals.2", 100, 400),
               ("splash_mha_dkv_no_residuals.3", 400, 900),
               ("splash_mha_dq_no_residuals.4", 900, 1200),
               ("copy.5", 1200, 1300), ("gmm.6", 1300, 2000),
               ("fusion.7", 2000, 2100)]
KERNEL_SCOPES = {"fusion.1": "obs.attn", "splash_mha_fwd_residuals.2":
                 "obs.attn", "splash_mha_dkv_no_residuals.3": "obs.attn",
                 "splash_mha_dq_no_residuals.4": "obs.attn",
                 "copy.5": "obs.attn", "gmm.6": "obs.experts"}


@pytest.mark.parametrize("devices", [1, 2])
def test_kernel_share_of_the_attention_scope(monkeypatch, reader, devices):
    ctx = _ctx(monkeypatch, KERNEL_STEP, KERNEL_SCOPES, devices=devices)
    # 1,100 ns of kernel calls in 1,300 ns of obs.attn
    assert reader.read(ctx) == pytest.approx(100.0 * 1100 / 1300)


def test_the_scan_reads_zero(monkeypatch, reader):
    """A ``while`` around the scan's fusions: obs.attn ran, no kernel."""
    ops = [("fusion.1", 0, 100), ("while.2", 100, 900),
           ("fusion.3", 150, 500), ("fusion.4", 500, 850),
           ("gmm.5", 900, 1000)]
    scopes = {"fusion.1": "obs.attn", "while.2": "obs.attn",
              "fusion.3": "obs.attn", "fusion.4": "obs.attn",
              "gmm.5": "obs.experts"}
    assert reader.read(_ctx(monkeypatch, ops, scopes)) == 0.0


def test_kernel_outside_the_scope_is_not_counted(monkeypatch, reader):
    scopes = dict(KERNEL_SCOPES, **{"splash_mha_dq_no_residuals.4":
                                    "obs.grad"})
    assert reader.read(_ctx(monkeypatch, KERNEL_STEP, scopes)) == \
        pytest.approx(100.0 * 800 / 1000)


def test_without_the_scope_reads_none(monkeypatch, reader):
    assert reader.read(_ctx(monkeypatch, KERNEL_STEP, None)) is None
    other = {k: "obs.grad" for k in KERNEL_SCOPES}
    assert reader.read(_ctx(monkeypatch, KERNEL_STEP, other)) is None
    ctx = _ctx(monkeypatch, KERNEL_STEP, KERNEL_SCOPES, steps=0)
    assert reader.read(ctx) is None
