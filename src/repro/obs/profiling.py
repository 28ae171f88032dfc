"""Phase-level profiling: named spans, trace dumps, latency histograms.

Two complementary span mechanisms (DESIGN.md §15):

  * ``scope(name)`` — ``jax.named_scope`` for code INSIDE a jit trace
    (dedup / kernel / clean / collective).  Free at runtime; the names
    survive into HLO and show up in ``--profile-dir`` traces.
  * ``PhaseTimer.phase(name)`` — host-side spans around the training
    loop's phases (data / step / checkpoint).  Each span enters a
    ``jax.profiler.TraceAnnotation`` (so it lines up with device traces)
    AND accumulates wall time, drained into ``phase`` metrics records.

Span naming convention: dotted ``obs.<phase>`` names — ``obs.dedup``,
``obs.kernel``, ``obs.clean``, ``obs.collective`` inside the step;
``data`` / ``step`` / ``checkpoint`` at the loop level.

``LatencyTracker`` is the p50/p99 machinery behind serve-side adapt
latency and trainer steps/s histograms: a bounded ring buffer of
durations summarized into the schema's histogram shape
(``metrics.HISTOGRAM_FIELDS``).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

import numpy as np


def scope(name: str):
    """Named scope for traced (in-jit) code — ``jax.named_scope``."""
    import jax
    return jax.named_scope(name)


class PhaseTimer:
    """Host-side named phase spans with wall-time accumulation.

        timer = PhaseTimer()
        with timer.phase("data"):
            batch = stream.batch(i)
        ...
        record = timer.drain()   # {"data": {count, total_ms, mean_ms}, ...}
    """

    def __init__(self):
        self._total_s: Dict[str, float] = {}
        self._count: Dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self._total_s[name] = self._total_s.get(name, 0.0) + dt
                self._count[name] = self._count.get(name, 0) + 1

    def drain(self) -> Dict[str, Dict[str, float]]:
        """Per-phase timing since the last drain; resets the counters."""
        out = {}
        for name, total in self._total_s.items():
            n = self._count[name]
            out[name] = {"count": n,
                         "total_ms": round(total * 1e3, 4),
                         "mean_ms": round(total * 1e3 / max(n, 1), 4)}
        self._total_s.clear()
        self._count.clear()
        return out


class LatencyTracker:
    """Bounded reservoir of durations → p50/p90/p99 histogram summaries.

    ``record`` takes seconds; ``summary`` emits the schema's histogram
    shape (milliseconds).  The buffer keeps the most recent ``capacity``
    samples — serving runs care about the current latency regime, not the
    warmup tail."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._buf = np.zeros((self.capacity,), np.float64)
        self._n = 0          # total recorded (monotonic)

    def record(self, seconds: float) -> None:
        self._buf[self._n % self.capacity] = float(seconds)
        self._n += 1

    @property
    def count(self) -> int:
        return self._n

    def _window(self) -> np.ndarray:
        return self._buf[: min(self._n, self.capacity)]

    def summary(self) -> Dict[str, float]:
        """Histogram summary over the retained window (ms)."""
        w = self._window()
        if w.size == 0:
            return {"count": 0, "mean_ms": 0.0, "p50_ms": 0.0, "p90_ms": 0.0,
                    "p99_ms": 0.0, "max_ms": 0.0}
        ms = w * 1e3
        return {
            "count": int(self._n),
            "mean_ms": round(float(ms.mean()), 4),
            "p50_ms": round(float(np.percentile(ms, 50)), 4),
            "p90_ms": round(float(np.percentile(ms, 90)), 4),
            "p99_ms": round(float(np.percentile(ms, 99)), 4),
            "max_ms": round(float(ms.max()), 4),
        }

    def per_second(self) -> float:
        """Mean throughput implied by the retained window (events/s)."""
        w = self._window()
        tot = float(w.sum())
        return w.size / tot if tot > 0 else 0.0


@contextlib.contextmanager
def maybe_trace(profile_dir: Optional[str]) -> Iterator[None]:
    """``jax.profiler`` trace dump scoped over a block — a no-op when
    ``profile_dir`` is falsy.  The dump contains both the device timeline
    and every ``TraceAnnotation``/``named_scope`` span above."""
    if not profile_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(str(profile_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
