"""Readings that the correctness limits of a cell are set from.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds ...] [--fault-seeds ...]

In one process (set-up is long): for each of ``--seeds`` the program's
first three steps against the reference (the lower readings); for each of
``--control-seeds`` the reference computed in the cell's control
precision against the reference (the control's readings); for each of
``--fault-seeds`` each of the cell's faults planted in the timed path.
Writes one JSON line per reading to standard output and all of them to
``.chipbench/calibrate-<cell>.json`` in the checkout.  The benchmark's own
runs never run this."""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import compare, generate, harness, run
    from chipbench.bench import Benchmark
    run.setup_jax()
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    run.require_tpu(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    runner, reference = bench.runner(cell), bench.reference(cell)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    out = []

    def emit(rec):
        out.append(rec)
        print(json.dumps(rec), flush=True)

    def program(seed, fault=None):
        t0 = time.perf_counter()
        pool = generate.batches(runner.n_ids(cell), cell.traffic, seed)
        sess = runner.build(cell, seed, pool, fault=fault)
        state, prog = harness.first_three(sess, pool)
        sess.free(state)
        ref = reference.numbers(cell, seed, pool[:3])
        emit({"kind": fault or "program", "seed": seed,
              "gaps": compare.gaps(prog, ref), "prog": prog, "ref": ref,
              "seconds": time.perf_counter() - t0})

    for seed in seeds(args.seeds):
        program(seed)
    for seed in seeds(args.control_seeds):
        t0 = time.perf_counter()
        pool = generate.batches(runner.n_ids(cell), cell.traffic, seed)
        ref = reference.numbers(cell, seed, pool[:3])
        ctl = reference.numbers(cell, seed, pool[:3],
                                control=cell.spec["control"])
        emit({"kind": "control", "seed": seed,
              "gaps": compare.gaps(ctl, ref), "prog": ctl, "ref": ref,
              "seconds": time.perf_counter() - t0})
    for seed in seeds(args.fault_seeds):
        for fault in cell.spec.get("faults", ()):
            if fault != "state_unchanged":
                program(seed, fault)
    dest = ROOT / ".chipbench"
    dest.mkdir(exist_ok=True)
    with open(dest / f"calibrate-{args.workload}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
