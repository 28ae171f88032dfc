"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine holding the chips the cell asks
for.  The last line on standard output is the result object; the numbers
the correctness check compared, each beside its limit, are the last lines
on standard error.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell needs, or when the checkout lacks the
program under test."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".chipbench"


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_tpu(chips: int):
    """The devices the cell needs, or ``NoChip``: never the CPU."""
    import jax
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"JAX finds no TPU: {e}") from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX finds "
                     f"{len(devs)}")
    return devs


def setup_jax():
    """The compile cache at a fixed path inside the checkout, for every
    program the run compiles (the program's own cache helper takes it)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def format_checks(checks) -> list:
    return [f"check {k}: {v['value']:.6g} (limit {v['limit']:.6g})"
            for k, v in checks.items()]


def main(argv=None) -> int:
    args = parse(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.bench import Benchmark
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("the checkout holds no program under test (src/repro)",
              file=sys.stderr)
        return 2
    setup_jax()
    try:
        require_tpu(cell.chips)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from chipbench.harness import run_cell
    trace_dir = None
    if args.trace:
        trace_dir = OUT_DIR / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    try:
        line = run_cell(bench, cell, args.seed, args.seconds,
                        bool(args.trace), T_START,
                        trace_dir=str(trace_dir) if trace_dir else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    sys.stdout.flush()
    for text in format_checks(line["checks"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
