import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

"""Model-parallel sketches: train a table whose TOTAL sketch bytes exceed
the per-device aux budget (DESIGN.md §17).

The acceptance demo for sketch sharding, end to end on a forced 8-device
host platform:

  1. **budget failure** — planning the table UNSHARDED under the
     per-device budget raises ``InfeasibleBudgetError`` (the cheapest
     CS-MV sketch pair already overflows one device);
  2. **sharded plan** — the same budget with ``shards=8`` plans: each
     device holds one width slab, so the per-device bytes fit while the
     TOTAL sketch bytes exceed the budget (the state could not live on
     any single device);
  3. **training** — the planned store tree trains the sparse-embedding
     regression for a few dozen steps on the 8-way 'model' mesh
     (``make_sparse_embedding_step(sketch_shards=8)``), loss decreasing,
     and the per-shard occupancy gauges come back balanced.

    PYTHONPATH=src python benchmarks/sharded_sketch.py
    PYTHONPATH=src python benchmarks/sharded_sketch.py --quick

Results land in experiments/bench/sharded_sketch.json; the table in
EXPERIMENTS.md §ShardedSketch is generated from them.  The routing-
traffic counterpart rows live in benchmarks/traffic.py.
"""
import argparse

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import save_result
except ImportError:     # run as `python benchmarks/sharded_sketch.py`
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import save_result

from repro.distributed import sharding as shd
from repro.plan import allocator
from repro.plan.cli import plan_for_tables
from repro.train.steps import make_sparse_embedding_step, \
    sparse_embedding_stores

N_DEV = 8
PATH = "tok_embed/table"


def run(n_rows: int, dim: int, batch: int, steps: int, budget: int,
        shards: int, layout: str, lr: float, alpha: float,
        seed: int = 0) -> dict:
    shapes = {PATH: (n_rows, dim)}
    ps = {PATH: jax.ShapeDtypeStruct((n_rows, dim), jnp.float32)}
    floor_1 = allocator.min_budget_bytes(ps)
    floor_n = allocator.min_budget_bytes(ps, shards=shards)

    # 1. unsharded: the budget failure the per-device budget forces
    try:
        plan_for_tables(shapes, budget, optimizer="cs_adam")
        unsharded = {"planned": True}      # would invalidate the demo
    except allocator.InfeasibleBudgetError as e:
        unsharded = {"planned": False, "error": type(e).__name__,
                     "message": str(e)}
    print(f"[sharded_sketch] unsharded floor {floor_1:,} B vs budget "
          f"{budget:,} B -> "
          + ("PLANNED (demo void!)" if unsharded["planned"]
             else unsharded["error"]), flush=True)

    # 2. sharded: same budget, per-device accounting
    plan = plan_for_tables(shapes, budget, optimizer="cs_adam",
                           shards=shards, shard_layout=layout)
    leaf = plan.leaf(PATH)
    total = plan.predicted_aux_bytes
    per_dev = plan.predicted_aux_bytes_per_device
    print(f"[sharded_sketch] shards={shards}({layout}) width={leaf.width} "
          f"per-device {per_dev:,} B <= {budget:,} B < total {total:,} B",
          flush=True)

    # 3. train the sparse-embedding regression on the 8-way 'model' mesh
    tree = plan.store_tree()
    mesh = jax.make_mesh((shards,), ("model",), axis_types=(AxisType.Auto,))
    init_fn, step_fn, opt = make_sparse_embedding_step(
        n_rows, dim, lr=lr, stores=tree, path=PATH, mesh=mesh,
        sketch_shards=shards, shard_layout=layout)
    scale = 1.0 / np.sqrt(dim)
    table = init_fn(jax.random.PRNGKey(seed))
    target = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (n_rows, dim), jnp.float32) * scale
    state = opt.init()
    step_c = jax.jit(step_fn)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        ids = jnp.asarray((rng.zipf(alpha, size=batch) - 1) % n_rows,
                          jnp.int32)
        rows = table[ids] - target[ids]        # d/dtable ½‖table−target‖²
        losses.append(float(jnp.mean(jnp.square(rows))))
        table, state = step_c(table, state, ids, rows)
    m_st, v_st = sparse_embedding_stores(
        n_rows, dim, stores=tree, path=PATH, sketch_shards=shards,
        shard_layout=layout)
    v_stats = {k: float(v) for k, v in v_st.stats(state["v"]).items()}
    print(f"[sharded_sketch] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({steps} steps)  shard occ "
          f"{v_stats.get('shard_occ_min', 0.0):.3f} .. "
          f"{v_stats.get('shard_occ_max', 0.0):.3f}", flush=True)

    return {
        "devices": N_DEV, "rows": n_rows, "dim": dim, "batch": batch,
        "steps": steps, "alpha": alpha, "lr": lr,
        "budget_bytes": budget,
        "unsharded_floor_bytes": floor_1,
        "sharded_floor_bytes_per_device": floor_n,
        "unsharded": unsharded,
        "sharded_plan": {
            "shards": shards, "layout": layout, "width": leaf.width,
            "total_bytes": total, "per_device_bytes": per_dev,
            "exceeds_single_device_budget": total > budget,
        },
        "train": {
            "first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses[:: max(1, len(losses) // 50)],
            "v_stats": v_stats,
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8_192)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--budget", type=int, default=256 * 2**10,
                    help="per-DEVICE aux budget in bytes; keep it below "
                         "the unsharded CS-MV floor (2×3×256×dim×4 B) so "
                         "the unsharded plan fails")
    ap.add_argument("--shards", type=int, default=N_DEV)
    ap.add_argument("--layout", default="width", choices=("width", "hash"))
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--alpha", type=float, default=1.3)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        args.rows, args.batch, args.steps = 20_000, 2_048, 20
    payload = run(args.rows, args.dim, args.batch, args.steps, args.budget,
                  args.shards, args.layout, args.lr, args.alpha)
    path = save_result("sharded_sketch", payload)
    print(f"[sharded_sketch] wrote {path}")
    ok = (not payload["unsharded"]["planned"]
          and payload["sharded_plan"]["exceeds_single_device_budget"]
          and payload["sharded_plan"]["per_device_bytes"] <= args.budget
          and payload["train"]["final_loss"] < payload["train"]["first_loss"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
