"""Benchmark aggregator — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full pass
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-speed pass
    PYTHONPATH=src python -m benchmarks.run --only small_lm,roofline

Results: experiments/bench/<name>.json + a printed summary.
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

BENCHES = [
    ("power_law", "Fig 1-2: power-law + drifting identities"),
    ("approx_error", "Fig 4: l2 error CS vs rank-1"),
    ("small_lm", "Tab 3/4/7: perplexity per optimizer"),
    ("cleaning", "Fig 5: CMS cleaning ablation"),
    ("memory_time", "Tab 5/6: aux bytes + step time"),
    ("extreme", "Tab 8: MACH extreme classification"),
    ("extreme_scale", "Tab 8 at scale: batch sweep to the memory wall"),
    ("ablations", "(ours) compression sweep / strict semantics / fold"),
    ("fused_store", "(ours) fused vs composed update_read steps/sec"),
    ("obs_overhead", "(ours) telemetry on/off steps/s A-B"),
    ("serving", "(ours) SLO traffic replay: dense vs count-min adaptation"),
    ("roofline", "(ours) dry-run roofline tables"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    failures = 0
    for name, desc in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"\n=== {name}: {desc} ===", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            summary = mod.run(quick=args.quick)
            print(f"[{name}] {summary}")
            print(f"[{name}] done in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"[{name}] FAILED:\n{traceback.format_exc()[-2000:]}",
                  flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
