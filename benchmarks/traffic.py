import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

"""Dense vs sketched all-reduce traffic for the DP sparse-embedding step.

Mirrors the paper's systems claim at CPU scale (DESIGN.md §13): for a
data-parallel (ids, rows) embedding gradient, all-reducing the
(depth, width, dim) count sketches moves a fraction of the bytes of
all-gathering the (k, d) rows.  Both paths are COMPILED against an
8-device forced host platform and the collective bytes are read from the
optimized post-SPMD HLO (launch/analysis.parse_collectives) — measured,
not just predicted; the prediction (`sketched_reduce.traffic_ratio`, the
bytes-based accounting) is recorded alongside for regression.

Each record also carries the MODEL-PARALLEL sketch rows (DESIGN.md §17):

  * ``routing_bytes`` — the shard-axis routing psum, measured from a
    shard-ONLY compile (state sharded 8-way over 'model', no dp axis):
    the routing psum is then the step's only collective, so the HLO
    collective bytes ARE the routing traffic.  ``routing_predicted`` is
    ``sketched_reduce.routing_bytes`` over the four query groups the
    step routes (g, v, g², m).
  * ``dp_sharded_bytes`` — the composed dp×shard step (2×4 mesh): the
    PR 4 gradient-sketch psum now moves width SLABS, so its payload is
    1/shards of the 1D dp sketched payload, plus the routing psum.

    PYTHONPATH=src python benchmarks/traffic.py            # full sweep
    PYTHONPATH=src python benchmarks/traffic.py --quick

Results land in experiments/bench/traffic.json; the table in
EXPERIMENTS.md §Traffic is generated from them.
"""
import argparse

import jax
from jax.sharding import AxisType
import jax.numpy as jnp

try:
    from benchmarks.common import save_result
except ImportError:     # run as `python benchmarks/traffic.py`
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import save_result

from repro.core import optimizers as O
from repro.core.optimizers import SketchHParams
from repro.distributed import sharding as shd
from repro.distributed import sketched_reduce as sr
from repro.launch import analysis
from repro.train.steps import make_sparse_embedding_step

N_DEV = 8
SHARDS = 4          # shard count for the composed dp(2) × shard(4) mesh


def _collective_bytes(fn, args) -> dict:
    """Compile ``fn(*args)`` and read per-kind collective bytes from the
    optimized HLO."""
    compiled = jax.jit(fn).lower(*args).compile()
    cols = analysis.parse_collectives(compiled.as_text(), N_DEV)
    return {k: v["bytes"] for k, v in cols.items() if v["count"]}


def dense_dp_step(mesh, n_rows, dim, hp):
    """The baseline DP path: all-gather the (k, d) gradient rows + ids,
    run the single-device sparse CS-Adam update on the concatenated
    batch.  Same optimizer, dense collective."""
    opt = O.sparse_rows_adam(1e-2, shape=(n_rows, dim), hparams=hp)

    def inner(table, state, ids, rows):
        gids = jax.lax.all_gather(ids, "data").reshape(-1)
        grows = jax.lax.all_gather(rows, "data").reshape(-1, dim)
        updates, state = opt.update({"ids": gids, "rows": grows}, state)
        return O.apply_sparse_updates(table, updates), state

    return shd.dp_sparse_wrap(inner, mesh=mesh), opt


def run(n_rows: int, dim: int, batch: int, compressions) -> dict:
    mesh = jax.make_mesh((N_DEV,), ("data",), axis_types=(AxisType.Auto,))
    rows_arr = jnp.zeros((batch, dim), jnp.float32)
    ids_arr = jnp.zeros((batch,), jnp.int32)
    table = jnp.zeros((n_rows, dim), jnp.float32)

    records = []
    for compression in compressions:
        hp = SketchHParams(compression=compression)
        # sketched path
        _, dp_step, dp_opt = make_sparse_embedding_step(
            n_rows, dim, lr=1e-2, hparams=hp, dp_axis="data", mesh=mesh)
        sk_cols = _collective_bytes(
            dp_step, (table, dp_opt.init(), ids_arr, rows_arr))
        # dense path (same optimizer semantics, rows over the wire)
        dn_step, dn_opt = dense_dp_step(mesh, n_rows, dim, hp)
        dn_cols = _collective_bytes(
            dn_step, (table, dn_opt.init(), ids_arr, rows_arr))

        # sharded-sketch routing row (DESIGN.md §17): shard-only mesh —
        # no dp axis, so the shard-axis routing psum is the step's ONLY
        # collective and the measured HLO bytes are pure routing traffic
        mesh_sh = jax.make_mesh((N_DEV,), ("model",),
                                axis_types=(AxisType.Auto,))
        _, sh_step, sh_opt = make_sparse_embedding_step(
            n_rows, dim, lr=1e-2, hparams=hp, mesh=mesh_sh,
            sketch_shards=N_DEV)
        rt_cols = _collective_bytes(
            sh_step, (table, sh_opt.init(), ids_arr, rows_arr))
        # composed dp × shard: the PR 4 psum payload shrinks to slabs
        mesh_2d = jax.make_mesh((N_DEV // SHARDS, SHARDS), ("data", "model"),
                                axis_types=(AxisType.Auto,) * 2)
        _, ds_step, ds_opt = make_sparse_embedding_step(
            n_rows, dim, lr=1e-2, hparams=hp, dp_axis="data", mesh=mesh_2d,
            sketch_shards=SHARDS)
        ds_cols = _collective_bytes(
            ds_step, (table, ds_opt.init(), ids_arr, rows_arr))

        sk_bytes = sum(sk_cols.values())
        dn_bytes = sum(dn_cols.values())
        rt_bytes = sum(rt_cols.values())
        ds_bytes = sum(ds_cols.values())
        spec_m = hp.spec("sparse_embedding", (n_rows, dim), signed=True)
        spec_v = hp.spec("sparse_embedding", (n_rows, dim), signed=False)
        predicted = sr.traffic_ratio(spec_m, batch,
                                     extra_specs=(spec_v,))
        # the sharded step routes four (depth, k, dim) query groups per
        # step: ghat, v_old, g²hat, m_old (sketched_reduce.sharded_adam_rows)
        rt_pred = sr.routing_bytes(batch, spec_m, spec_v, spec_v, spec_m)
        rec = {
            "compression": compression,
            "rows": n_rows, "dim": dim, "batch": batch,
            "dense_bytes": dn_bytes, "dense_collectives": dn_cols,
            "sketched_bytes": sk_bytes, "sketched_collectives": sk_cols,
            "measured_ratio": dn_bytes / sk_bytes if sk_bytes else None,
            "predicted_ratio": predicted,
            "sketch_shards": N_DEV,
            "routing_bytes": rt_bytes, "routing_collectives": rt_cols,
            "routing_predicted": rt_pred,
            "dp_sharded_shards": SHARDS,
            "dp_sharded_bytes": ds_bytes, "dp_sharded_collectives": ds_cols,
        }
        records.append(rec)
        print(f"compression={compression:6.1f}x  dense={dn_bytes:>12,} B  "
              f"sketched={sk_bytes:>12,} B  "
              f"measured {rec['measured_ratio']:.1f}x  "
              f"predicted {predicted:.1f}x", flush=True)
        print(f"{'':>18s}  routing(x{N_DEV})={rt_bytes:>10,} B "
              f"(pred {rt_pred:,})  dp×shard(2x{SHARDS})={ds_bytes:>12,} B",
              flush=True)
    return {"devices": N_DEV, "records": records}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=100_000,
                    help="global touched rows per step (default k == n: "
                         "the full-softmax regime the paper compresses)")
    ap.add_argument("--compressions", default="5,10,20,40,100",
                    help="paper compressions: 5x (LM1B aux memory) up to "
                         "100x (49.5M-class Amazon)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        args.rows = args.batch = 16_384
        args.compressions = "10,40"
    comps = [float(c) for c in args.compressions.split(",")]
    payload = run(args.rows, args.dim, args.batch, comps)
    path = save_result("traffic", payload)
    print(f"[traffic] wrote {path}")
    # with both moment sketches riding the collective the byte ratio is
    # ~compression/2: the 5x gate is met from compression ≳ 10 up
    best = max(r["measured_ratio"] for r in payload["records"])
    print(f"[traffic] best measured reduction: {best:.1f}x")
    return 0 if best >= 5.0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
