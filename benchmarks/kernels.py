"""Sketch-kernel micro-benchmarks: µs/call of the jnp reference path on
CPU (what actually executes here) + the analytical bytes-moved model for
the Pallas TPU kernels (what executes on the target).

The fused-Adam traffic model is the DESIGN.md §3 argument in numbers:
    unfused  = 4 sketch traversals / moment  (query, update ×2 reads+write)
    fused    = 1 HBM round trip per depth row

Backend axis (DESIGN.md §10): ``--backend <name|all>`` times the
sparse-rows CS-Adam step through each registered kernel backend
(ref | stream | tiled | interpret) on a duplicate-heavy id batch, so the
stream-vs-tiled crossover is *measured*, not asserted.  'stream' and
'tiled' compile for a TPU only; off-TPU ``all`` times 'interpret' (the
tiled kernel under the interpreter) instead — its absolute numbers are
Python-interpreter timings, only the grid-step counts (k for stream,
k/TILE for tiled) transfer to hardware; the traffic model supplies the
projected ratio.

    PYTHONPATH=src python benchmarks/kernels.py                 # ref only
    PYTHONPATH=src python benchmarks/kernels.py --backend all
    PYTHONPATH=src python benchmarks/kernels.py --backend tiled  # TPU
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import save_result
except ModuleNotFoundError:  # invoked as `python benchmarks/kernels.py`
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import save_result
from repro import kernels as K
from repro.core import sketch as cs
from repro.core.hashing import HashFamily
from repro.kernels import ops, ref
from repro.kernels.cs_adam_tiled import DEFAULT_TILE


def _time(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def traffic_model(depth, width, dim, k, dtype_bytes=4):
    """Bytes through HBM per op on TPU (whole rows, VMEM-tiled)."""
    row = dim * dtype_bytes
    return {
        "query": depth * k * row,                   # read k rows per depth
        "update": 2 * depth * k * row,              # RMW per depth row
        "adam_unfused": (3 + 3 + 2) * depth * k * row * 2,  # m & v, 3-pass
        "adam_fused": 2 * 2 * depth * k * row,      # one RMW per sketch
    }


def _adam_backend_rows(backends: List[str], *, depth=3, width=256, dim=128,
                       k=64, dup_frac=0.5, iters=3):
    """Time the sparse-rows CS-Adam step per backend on one batch shape.

    ``dup_frac`` of the ids are duplicates (drawn from a small pool) —
    the regime the dedup pre-pass targets.
    """
    n = 4096
    spec_m = cs.for_param((n, dim), compression=4.0, depth=depth,
                          signed=True, seed=1, width_multiple=16)
    spec_v = cs.for_param((n, dim), compression=4.0, depth=depth,
                          signed=False, seed=2, width_multiple=16)
    M, V = cs.init(spec_m), cs.init(spec_v)
    rng = np.random.RandomState(0)
    n_dup = int(k * dup_frac)
    ids = np.concatenate([rng.randint(0, n, k - n_dup),
                          rng.randint(0, 8, n_dup)])  # hot duplicate pool
    ids = jnp.asarray(rng.permutation(ids), jnp.int32)
    g = jnp.asarray(rng.randn(k, dim), jnp.float32)
    step = jnp.asarray(1, jnp.int32)

    rows = []
    for name in backends:
        fn = jax.jit(lambda M, V, ids, g, step, _b=name: K.adam_rows(
            spec_m, spec_v, M, V, ids, g, step, lr=1e-3, backend=_b))
        us = _time(fn, M, V, ids, g, step, iters=iters)
        # items processed per sequential step: per-item for ref/stream,
        # per-tile for the tiled kernels, the whole batch at once for xla
        grid_steps = {"ref": k, "stream": k,
                      "xla": 1}.get(name, -(-k // DEFAULT_TILE))
        rows.append({"backend": name, "k": k, "dim": dim, "depth": depth,
                     "dup_frac": dup_frac, "us_per_step_cpu": round(us, 1),
                     "grid_steps": grid_steps})
        print(f"  adam[{name:9s}] k={k:4d} dup={dup_frac:.1f} "
              f"{us:10.1f} µs/step  (grid steps: {grid_steps})")
    return rows


def run(quick: bool = False, backend: Optional[str] = None):
    shapes = [(3, 1024, 256, 128), (3, 4096, 512, 1024)]
    if quick:
        shapes = shapes[:1]
    results = []
    for depth, width, dim, k in shapes:
        spec = cs.SketchSpec(depth=depth, width=width, dim=dim, seed=0)
        S = cs.init(spec)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 10 * width,
                                                           size=k), jnp.int32)
        delta = jax.random.normal(jax.random.PRNGKey(0), (k, dim))

        q = jax.jit(lambda S, i: ops.sketch_query(spec, S, i))
        u = jax.jit(lambda S, i, d: ops.sketch_update(spec, S, i, d))
        tm = traffic_model(depth, width, dim, k)
        results.append({
            "shape": {"depth": depth, "width": width, "dim": dim, "k": k},
            "query_us_cpu": _time(q, S, ids),
            "update_us_cpu": _time(u, S, ids, delta),
            "traffic_bytes": tm,
            "fused_traffic_saving":
                round(tm["adam_unfused"] / tm["adam_fused"], 2),
        })

    # ---- backend axis ------------------------------------------------------
    if backend is None:
        names = ["ref"]               # default: the fast-on-CPU oracle only
    elif backend == "all":
        # 'stream' and 'tiled' compile for a TPU only; elsewhere the
        # kernel body runs by name under the interpreter ('interpret')
        names = [n for n in K.backends()
                 if jax.default_backend() == "tpu"
                 or n not in ("stream", "tiled")]
    else:
        names = [K.resolve_backend(backend)]
    # interpret-mode Pallas on CPU is slow — shrink the batch there
    pallas_names = {"stream", "tiled", "interpret"}
    small = jax.default_backend() != "tpu" and bool(pallas_names & set(names))
    adam_rows = _adam_backend_rows(
        names, k=16 if small else 64, dim=128, iters=1 if small else 10)

    save_result("kernels", {"rows": results, "adam_backends": adam_rows})
    return ([{**r["shape"], "query_us": round(r["query_us_cpu"], 1),
              "fused_saving": r["fused_traffic_saving"]} for r in results]
            + [{k_: r[k_] for k_ in ("backend", "us_per_step_cpu",
                                     "grid_steps")} for r in adam_rows])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--backend", default=None,
                    help="kernel backend to time (ref|xla|stream|tiled|"
                         "interpret|all); default ref")
    args = ap.parse_args()
    print(run(quick=args.quick, backend=args.backend))
