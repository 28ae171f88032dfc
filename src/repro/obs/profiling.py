"""Phase-level profiling: named spans, trace dumps, latency histograms.

Two span mechanisms (DESIGN.md §15), both readable from a chip trace:

  * ``scope(name)`` — ``jax.named_scope`` for code INSIDE a jit trace
    (``obs.dedup`` / ``obs.kernel`` / ``obs.apply`` / ``obs.clean`` /
    ``obs.collective``).  Free at runtime.  A TPU trace's op events carry
    only the HLO instruction's text, so a scope reaches the trace through
    the compiled module's metadata: ``scope_map`` maps each instruction
    of ``compiled.as_text()`` to its innermost ``obs.*`` scope.
  * ``span(name, timer)`` — a host-side span around one phase of the
    training loop (``train.data`` / ``train.feed`` / ``train.clean`` /
    ``train.dispatch`` / ``train.wait`` / ``train.record`` /
    ``train.checkpoint``).  It enters a ``jax.profiler.TraceAnnotation``
    (the trace's clock) AND adds its wall time to a ``PhaseTimer``,
    drained into ``phase`` metrics records.

``CompileCounter`` counts the programs the backend compiles (or loads
from the persistent cache) while it is open: a compile inside a timed
window is a stall of its own.

``LatencyTracker`` is the p50/p99 machinery behind serve-side adapt
latency and trainer steps/s histograms: a bounded ring buffer of
durations summarized into the schema's histogram shape
(``metrics.HISTOGRAM_FIELDS``).
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterator, Optional

import numpy as np

UNSCOPED = "unscoped"
# ``jax._src.dispatch.BACKEND_COMPILE_EVENT``: wraps every backend compile,
# a persistent-cache hit included
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def scope(name: str):
    """Named scope for traced (in-jit) code — ``jax.named_scope``."""
    import jax
    return jax.named_scope(name)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction: scope}`` over an HLO module's text (the optimized
    module of ``compiled.as_text()``, whose instruction names are those the
    trace's op events carry): each instruction's innermost ``obs.*``
    component of its ``metadata={op_name=...}`` path, or ``UNSCOPED``.
    An instruction's text runs up to the next instruction: a Pallas
    kernel's frontend attributes can hold line breaks, which put its
    metadata on a later line."""
    out = {}

    def add(name, text):
        op = _OP_NAME.search(text)
        path = op.group(1).split("/") if op else ()
        out[name] = next((c for c in reversed(path) if c.startswith("obs.")),
                         UNSCOPED)

    name, text = None, []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            text.append(line)
            continue
        if name is not None:
            add(name, "\n".join(text))
        name, text = m.group(1), [line]
    if name is not None:
        add(name, "\n".join(text))
    return out


class PhaseTimer:
    """Wall time of host-side phases, per phase name.

        timer = PhaseTimer()
        with span("train.data", timer):
            batch = stream.batch(i)
        ...
        record = timer.drain()   # {"train.data": {count, total_ms, ...}}
    """

    def __init__(self):
        self._total_s: Dict[str, float] = {}
        self._max_s: Dict[str, float] = {}
        self._count: Dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        self._total_s[name] = self._total_s.get(name, 0.0) + seconds
        self._max_s[name] = max(self._max_s.get(name, 0.0), seconds)
        self._count[name] = self._count.get(name, 0) + 1

    def drain(self) -> Dict[str, Dict[str, float]]:
        """Per-phase timing since the last drain; resets the counters."""
        out = {}
        for name, total in self._total_s.items():
            n = self._count[name]
            out[name] = {"count": n,
                         "total_ms": round(total * 1e3, 4),
                         "mean_ms": round(total * 1e3 / max(n, 1), 4),
                         "max_ms": round(self._max_s[name] * 1e3, 4)}
        self._total_s.clear()
        self._max_s.clear()
        self._count.clear()
        return out


@contextlib.contextmanager
def span(name: str, timer: PhaseTimer) -> Iterator[None]:
    """A host span: a ``TraceAnnotation`` named ``name`` whose wall time
    ``timer`` also records."""
    import jax
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        timer.add(name, time.perf_counter() - t0)


class CompileCounter:
    """Backend compiles (persistent-cache loads included) while open.

        with CompileCounter() as compiles:
            trainer.fit(state)
        compiles.count, compiles.seconds
    """

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event: str, duration_secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration_secs

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


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
