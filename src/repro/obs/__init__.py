"""Observability: sketch-health telemetry, probes, and phase profiling.

The paper's "negligible accuracy loss" claim rests on count-sketch
estimation error staying small under the run's actual traffic; this
package is the runtime instrumentation that *measures* it instead of
assuming it (DESIGN.md §15):

  * ``metrics``   — schema-versioned JSONL emitter (step-keyed records,
    on-device aggregation, host fetch only at ``log_every`` boundaries);
  * ``probes``    — shadow ground-truth probes (exact dense moments for K
    sampled hot/cold rows vs sketch ``read()`` estimates), per-store
    health stats via ``AuxStore.stats``, planner predicted-vs-measured
    collision error, and the ``RunObserver`` the Trainer drives;
  * ``profiling`` — ``train.*`` host spans (``TraceAnnotation`` plus a
    ``PhaseTimer``), the ``obs.*`` scope of each compiled instruction,
    a compile counter, ``--profile-dir`` trace dumps, and p50/p99
    latency histograms;
  * ``report``    — ``python -m repro.obs.report``: render a run's JSONL
    into a health summary with re-planning warnings.
"""
from repro.obs.metrics import (MetricsWriter, SCHEMA_VERSION, StepAccumulator,
                               validate_file, validate_record)
from repro.obs.probes import (RunObserver, TableMonitor, TableProbe,
                              predicted_table_errors, rows_ema_update)
from repro.obs.profiling import (CompileCounter, LatencyTracker, PhaseTimer,
                                 maybe_trace, scope, scope_map, span)

__all__ = [
    "MetricsWriter", "SCHEMA_VERSION", "StepAccumulator", "validate_file",
    "validate_record", "RunObserver", "TableMonitor", "TableProbe",
    "predicted_table_errors", "rows_ema_update", "CompileCounter",
    "LatencyTracker", "PhaseTimer", "maybe_trace", "scope", "scope_map",
    "span",
]
