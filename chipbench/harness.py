"""One run of one cell: set-up, the first three steps checked, the measured
window through the program's own training loop, the reference.

Order within a run (the numbers it prints come from these phases):

1. set-up — the runner builds the step exactly as the launcher does, with
   weights drawn from ``--seed`` on the device and the traffic pool drawn
   on the host; then the first three steps run through ``Trainer.fit``
   (the window's own call and feed) and their readings are kept; a few
   more steps size the window.  ``setup_s`` ends here.
2. the window — ``Trainer.fit`` over a whole number of steps filling
   ``--seconds``, under the profiler with ``--trace 1``.
3. ``memory_peak_bytes`` is read, the program's state freed, and the
   plain reference replays the first three steps; ``compare`` decides
   ``correct``."""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
import time
from typing import Callable, Dict, List, Optional

from chipbench import compare, generate
from chipbench.bench import Benchmark, Cell

WARM_STEPS = 2            # timed steps after the checked three
TRACE_SECONDS = 3.0       # at most this much of the window is traced
GIB = 1 << 30


class PoolData:
    """The trainer's data object: ``batch(step)`` serves the pool drawn in
    set-up, cycled, inside a ``bench.data`` host span."""

    def __init__(self, pool: List[Dict]):
        self.pool = pool

    def batch(self, step: int):
        import jax
        with jax.profiler.TraceAnnotation("bench.data"):
            return self.pool[step % len(self.pool)]


def traced_step(step_fn: Callable) -> Callable:
    """``step_fn`` inside a ``bench.step`` host span (its dispatch)."""
    import jax

    def step(params, opt_state, batch):
        with jax.profiler.TraceAnnotation("bench.step"):
            return step_fn(params, opt_state, batch)

    return step


def device_info(n: int) -> dict:
    import jax
    devs = jax.devices()[:n]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(n: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def host_usage() -> Dict[str, float]:
    """This process's CPU seconds (all threads): with the window's step
    times they tell a step stalled while the process did not run from a
    host that ran the same work slower."""
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": ru.ru_utime + ru.ru_stime}


def step_times(hist, elapsed: float) -> Dict[str, float]:
    """The window's step times (``Trainer.fit``'s ``time_s``: the step's
    call until its loss is ready) and their share of the window."""
    t = sorted(h["time_s"] for h in hist)
    return {"step_s_min": t[0], "step_s_median": statistics.median(t),
            "step_s_p99": t[min(len(t) - 1, int(0.99 * len(t)))],
            "step_s_max": t[-1], "in_step_share": sum(t) / elapsed}


def first_three(sess, pool):
    """Drive the session's trainer through steps 1–3 (the window's own call
    and feed) and keep the readings the reference is compared with: the
    first gradient from the state after step 1, each step's loss, the
    parameters' change after step 3."""
    trainer, state = sess.trainer, sess.state
    trainer.data = PoolData(pool)
    trainer.step_fn = traced_step(trainer.step_fn)
    trainer.tcfg.total_steps = 1
    state = trainer.fit(state)
    prog = {"grad": sess.grad_norms(state)}
    trainer.tcfg.total_steps = 3
    state = trainer.fit(state)
    prog["change"] = sess.change_norms(state)
    prog["loss"] = [h["loss"] for h in trainer.history[:3]]
    return state, prog


def run_cell(bench: Benchmark, cell: Cell, seed: int, seconds: float,
             trace: bool, t_start: float,
             trace_dir: Optional[str] = None,
             fault: Optional[str] = None) -> dict:
    """Run ``cell`` once and return its result line (``checks``, the
    numbers compared beside their limits, last).  ``fault`` plants one of
    the runner's faults in the timed path (tests and ``calibrate.py``)."""
    import jax
    runner = bench.runner(cell)
    reference = bench.reference(cell)
    phases = {"start": time.perf_counter() - t_start}
    pool = generate.batches(runner.n_ids(cell), cell.traffic, seed)
    phases["traffic"] = time.perf_counter() - t_start
    sess = runner.build(cell, seed, pool, fault=fault)
    phases["build"] = time.perf_counter() - t_start
    # the peak so far at the end of each phase: what set-up alone reaches,
    # against the run's peak read after the window
    mem = {"build": memory_peak(cell.chips)}
    trainer = sess.trainer
    state, prog = first_three(sess, pool)
    phases["checked_steps"] = time.perf_counter() - t_start
    mem["checked_steps"] = memory_peak(cell.chips)

    trainer.tcfg.total_steps = 3 + WARM_STEPS
    state = trainer.fit(state)
    step_s = statistics.median(h["time_s"] for h in trainer.history[3:])
    n_steps = max(1, int(round(seconds / step_s)))
    if trace:
        n_steps = min(n_steps, max(2, int(math.ceil(TRACE_SECONDS / step_s))))
    setup_s = time.perf_counter() - t_start

    first = state.step
    trainer.tcfg.total_steps = first + n_steps
    if trace:
        jax.profiler.start_trace(trace_dir)
    host0 = host_usage()
    t0 = time.perf_counter()
    state = trainer.fit(state)
    jax.block_until_ready((state.params, state.opt_state))
    elapsed = time.perf_counter() - t0
    host = {k: v - host0[k] for k, v in host_usage().items()}
    if trace:
        jax.profiler.stop_trace()
    peak = mem["window"] = memory_peak(cell.chips)
    window_hist = trainer.history[-n_steps:]
    sess.free(state)
    del state

    ref = reference.numbers(cell, seed, pool[:3])
    gaps = compare.gaps(prog, ref)
    limits = cell.spec["limits"]
    checks = {k: {"value": gaps[k], "limit": limits[k]} for k in limits}
    correct = compare.judge(gaps, limits)
    failed = sum(1 for h in window_hist
                 if not math.isfinite(h["loss"]))

    device = device_info(cell.chips)
    device["memory_peak_bytes"] = peak
    line = {"correct": bool(correct and failed == 0),
            "attempted": n_steps, "failed": failed}
    if trace:
        from chipbench import trace as tr
        red = tr.reduce_dir(trace_dir, cell.chips)
        steps_work = [runner.step_work(cell, b) for b in
                      (pool[(first + i) % len(pool)] for i in range(n_steps))]
        ctx = MetricContext(trace=red, steps=n_steps, cell=cell,
                            cost=sess.cost(steps_work),
                            peaks=load_peaks(bench, device["kind"]))
        metrics = {}
        for m in cell.per_layer:
            v = bench.metric_reader(m).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = red.breakdown()
    else:
        rate = n_steps * sess.work_per_step / elapsed
        values = {sess.rate_metric: rate,
                  "peak_hbm_gib": peak / GIB,
                  "setup_s": setup_s}
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
        line["device"] = device
    line["setup_phases_s"] = phases
    line["peak_bytes_by_phase"] = mem
    line["window_host"] = dict(host, **step_times(window_hist, elapsed))
    line["compile_s"] = sess.compile_s
    line["backends"] = [list(b) for b in sess.backends]
    line["checks"] = checks
    return line


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader gets: the reduced trace of the traced
    window, the number of steps in it, the cell, the work the steps needed
    (``cost/``), and the chip's peaks."""
    trace: object
    steps: int
    cell: Cell
    cost: dict
    peaks: dict


def load_peaks(bench: Benchmark, kind: str) -> dict:
    with open(bench.bench_dir / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"chipbench/peaks.json")
    return table["devices"][kind]
