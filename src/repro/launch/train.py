"""Training driver: config → mesh → jit'd step → fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b \
        --reduced --steps 200 --optimizer cs_adam --ckpt-dir /tmp/run1

    # one chip's share of Moonlight-16B-A3B over 8-way expert parallelism
    python -m repro.launch.train --arch moonlight-16b-a3b-ep8 \
        --batch 2 --seq 8192 --steps 20

On a real pod this binary runs per host (jax.distributed.initialize is
called when JAX_COORDINATOR is set); here it exercises the same code
path on one CPU device.  ``--reduced`` swaps in the smoke-size config.
Recovery: on restart the trainer restores the latest atomic checkpoint
and the deterministic zipf stream replays the remaining steps
bit-identically (tests/test_substrate.py::TestTrainer).

Distributed data parallelism (DESIGN.md §13):

  * ``--dp`` runs the step as an explicit ``shard_map`` over a 'data'
    axis spanning every local device (manual collectives instead of
    GSPMD), with the derived param/opt-state/batch shardings threaded
    through ``jax.jit`` and checkpoint restore;
  * ``--workload sparse_embedding`` trains a standalone (rows, dim)
    embedding table in the paper's (ids, grad-rows) regime — under
    ``--dp`` the gradient collective moves (depth, width, dim) COUNT
    SKETCHES instead of the (k, d) rows, and the sketch state itself is
    stored width-sharded over 'data' (``sharding.opt_specs_for_state``).

Every training workload compiles its step once before the loop and
prints the compile time, the kernel backend each sketched table resolved
to, and XLA's memory analysis.  ``run(argv)`` returns that with the loss
history as a ``RunReport``; ``main(argv)`` returns its exit code — 0 only
when the loss is finite and fell.
"""
import argparse
import dataclasses
import os
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import configs
from repro.checkpoint import store
from repro.data import ZipfLM, ZipfLMConfig
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.obs import (MetricsWriter, PhaseTimer, RunObserver, maybe_trace)
from repro.train.steps import (make_sparse_embedding_step, make_train_step,
                               sparse_embedding_stores)
from repro.train.trainer import Trainer, TrainerConfig, TrainState


@dataclasses.dataclass
class RunReport:
    """What one launcher run did: its exit code, the per-step records of
    its (last) training loop, and the compiled step's report."""
    rc: int
    history: List[dict] = dataclasses.field(default_factory=list)
    compile_s: Optional[float] = None
    # (kind, op, backend, sketch shapes) resolved while the step traced
    backends: Tuple[tuple, ...] = ()
    memory: Any = None            # compiled.memory_analysis()


def loss_fell(hist, w: int) -> bool:
    """Finite losses whose last ``w``-step mean is below the first's."""
    losses = np.array([h["loss"] for h in hist], np.float64)
    if losses.size == 0 or not np.isfinite(losses).all():
        return False
    return losses[-w:].mean() < losses[:w].mean()


def compile_step(jit_step, *args):
    """Lower and compile ``jit_step`` for ``args`` (placed as the step
    expects them) before the loop.  Prints the compile time, the backend
    each sketched table resolved to, and the memory analysis; returns
    the executable and a ``RunReport`` with those fields filled in."""
    from repro.kernels import registry
    t0 = time.perf_counter()
    with registry.recording() as rec:
        compiled = jit_step.lower(*args).compile()
    dt = time.perf_counter() - t0
    backends = tuple(sorted(set(rec)))
    mem = compiled.memory_analysis()
    print(f"[compile] step compiled in {dt:.2f} s", flush=True)
    for kind, op, name, specs in backends:
        print(f"[compile] {kind}/{op} -> {name} ({', '.join(specs)})",
              flush=True)
    if mem is not None:
        print(f"[compile] memory: arguments "
              f"{mem.argument_size_in_bytes:,} B, outputs "
              f"{mem.output_size_in_bytes:,} B, aliased "
              f"{mem.alias_size_in_bytes:,} B, temporaries "
              f"{mem.temp_size_in_bytes:,} B", flush=True)
    return compiled, RunReport(rc=1, compile_s=dt, backends=backends,
                               memory=mem)


def jit_train_step(ts, mesh, batch_tpl, step_fn=None):
    """``jax.jit`` of a ``TrainStep``'s step (or of ``step_fn``, a wrapper
    of it taking batches shaped as ``batch_tpl``) with the derived in/out
    shardings and the params and optimizer state donated.  Returns it
    with the (params, optimizer state) shardings."""
    pshard, oshard, bshard, mshard = ts.shardings(mesh, batch_tpl)
    return jax.jit(step_fn or ts.step_fn,
                   in_shardings=(pshard, oshard, bshard),
                   out_shardings=(pshard, oshard, mshard),
                   donate_argnums=(0, 1)), (pshard, oshard)


def make_observer(args, run_meta, monitors=(), subdir: str = ""):
    """A ``RunObserver`` over ``--metrics-dir`` (None when the flag is
    off — every call site treats the whole obs layer as optional)."""
    if not args.metrics_dir:
        return None
    out = os.path.join(args.metrics_dir, subdir) if subdir \
        else args.metrics_dir
    writer = MetricsWriter(out, run_meta=run_meta)
    return RunObserver(writer, monitors=monitors, log_every=args.log_every,
                       phase_timer=PhaseTimer())


def run_sparse_embedding(args, mesh) -> int:
    """The (ids, grad-rows) workload: pull a zipf-touched embedding table
    toward a fixed target table (∇ = table[ids] − target[ids] on touched
    rows — a convergent quadratic), through the DP sparse step when
    ``--dp``.  Store state (m/v sketches, optional residual) is sharded
    per ``opt_specs_for_state`` at the jit boundary.  With
    ``--sketch-shards N`` the sketches become first-class sharded objects
    (DESIGN.md §17): width slabs live on the mesh's 'model' axis and the
    step routes deduped ids to the owning shard."""
    import jax.numpy as jnp
    from repro.core.optimizers import SketchHParams

    n_rows, dim = args.sparse_rows, args.sparse_dim
    shards, layout = args.sketch_shards, args.shard_layout
    hp = SketchHParams(compression=args.sparse_compression,
                       backend=args.store_backend or None,
                       dtype=args.sketch_cell_dtype)
    # count-min cleaning (paper §4): sync gates the decay inside the
    # compiled step; async moves it to the trainer's 'clean' phase
    # (bit-identical schedule — DESIGN.md §18)
    cleaning = cleaner = None
    if args.cleaning_every > 0:
        from repro.core.cleaning import AsyncCleaner, CleaningSchedule
        cleaning = CleaningSchedule(alpha=args.cleaning_alpha,
                                    every=args.cleaning_every,
                                    mode=args.cleaning_mode)
        if cleaning.mode == "async":
            cleaner = AsyncCleaner(cleaning)
    dp_axis = "data" if args.dp else None
    init_fn, step_fn, opt = make_sparse_embedding_step(
        n_rows, dim, lr=args.lr, hparams=hp, dp_axis=dp_axis, mesh=mesh,
        error_feedback=args.error_feedback, cleaning=cleaning,
        sketch_shards=shards, shard_layout=layout)

    # the executable vocabulary of this run's sketch state — recorded in
    # every checkpoint manifest so restore can verify the shard layout
    # and the cell dtype (and elastic restore gets the exact fold
    # predicate)
    from repro.core.stores import StoreTree
    m_st, v_st = sparse_embedding_stores(n_rows, dim, hparams=hp,
                                         cleaning=cleaning,
                                         sketch_shards=shards,
                                         shard_layout=layout)
    run_tree = StoreTree(rules=(("sparse_embedding", m_st, v_st),))

    if args.ckpt_dir and store.latest_step(args.ckpt_dir) is not None:
        saved = store.read_manifest(args.ckpt_dir).get("extra", {})
        rec = (StoreTree.from_json(saved["store_tree"])
               if saved.get("store_tree") is not None else None)
        rec_v = rec.rules[0][2] if rec is not None and rec.rules else None
        rec_shards = getattr(rec_v, "shards", 1)
        rec_layout = getattr(rec_v, "shard_layout", "width")
        rec_dtype = (rec_v.cell_dtype_name if rec_v is not None
                     and hasattr(rec_v, "cell_dtype_name") else "float32")
        if rec_dtype != args.sketch_cell_dtype:
            raise ValueError(
                f"{args.ckpt_dir} holds sketch state with {rec_dtype!r} "
                f"cells; restoring it under --sketch-cell-dtype "
                f"{args.sketch_cell_dtype} would silently reinterpret "
                f"quantized state — resume with --sketch-cell-dtype "
                f"{rec_dtype}, or start a fresh --ckpt-dir")
        if rec_layout != layout:
            raise ValueError(
                f"{args.ckpt_dir} holds sketch state in the "
                f"{rec_layout!r} shard layout; restoring it under "
                f"--shard-layout {layout} would read buckets hashed by a "
                f"different family — resume with the recorded layout")
        if layout == "hash" and rec_shards != shards:
            raise ValueError(
                f"{args.ckpt_dir} holds hash-layout sketch state built "
                f"for {rec_shards} shards; the two-level owner hash bakes "
                f"the shard count into every bucket, so restoring onto "
                f"{shards} shards would scramble the state — keep "
                f"--sketch-shards {rec_shards}, or use the width layout "
                f"(placement-only; elastic across shard counts)")
        if rec_shards != shards:
            print(f"[train] width-layout sketch state re-placed: "
                  f"{rec_shards} -> {shards} shards (state bytes "
                  f"identical; slabs re-routed at restore)", flush=True)

    data_cfg = ZipfLMConfig(
        vocab_size=n_rows, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, n_hosts=jax.process_count(),
        host_id=jax.process_index())
    data = ZipfLM(data_cfg)

    # observability (DESIGN.md §15): the monitor reads the SAME codec
    # pair the optimizer binds; the shadow probe rides inside opt_state
    # under "probe" (a non-moment tag — opt_specs_for_state replicates
    # it, while m/v keep the width-over-'data' sketch layout).
    probe = None
    monitors = []
    if args.metrics_dir:
        from repro.obs import TableMonitor, TableProbe, predicted_table_errors
        m_store, v_store = m_st, v_st
        if args.probe_rows > 0:
            probe = TableProbe.for_table("sparse_embedding", n_rows,
                                         k=args.probe_rows)
        monitors = [TableMonitor(
            path="sparse_embedding", m_store=m_store, v_store=v_store,
            probe=probe, cleaner=cleaner,
            predicted=predicted_table_errors(m_store, v_store, n_rows,
                                             alpha=data_cfg.alpha))]
    observer = make_observer(args, {
        "workload": "sparse_embedding", "rows": n_rows, "dim": dim,
        "compression": args.sparse_compression, "steps": args.steps,
        "batch": args.batch, "dp": bool(args.dp),
        "sketch_cell_dtype": args.sketch_cell_dtype,
        "probe_rows": args.probe_rows}, monitors)

    with shd.active_mesh(mesh):
        table = init_fn(jax.random.PRNGKey(args.seed))
        opt_state = opt.init()
        if probe is not None:
            opt_state = dict(opt_state, probe=probe.init(dim))
        target = init_fn(jax.random.PRNGKey(args.seed + 1))

        # shardings: table replicated; sketch state width-over-'data'
        # (replicated sketches) or slabbed over 'model' (--sketch-shards)
        from jax.sharding import NamedSharding, PartitionSpec as P
        table_spec = NamedSharding(mesh, P())
        opt_shape = jax.eval_shape(lambda: opt_state)
        if shards > 1:
            opt_spec = shd.named(mesh, shd.sketch_state_specs(opt_shape))
        else:
            opt_spec = shd.named(mesh, shd.opt_specs_for_state(
                opt_shape, table, mesh))
        bspec = shd.named(mesh, {
            "tokens": shd.batch_spec(mesh, (args.batch, args.seq)),
            "labels": shd.batch_spec(mesh, (args.batch, args.seq))})
        mspec = NamedSharding(mesh, P())

        # the target is an argument, not a closed-over constant: baked
        # into the program, a 512 MiB table slows the compile and bloats
        # every executable (and its compile-cache entry) by its size
        def train_step(table, opt_state, batch, target):
            ids = batch["tokens"].reshape(-1).astype(jnp.int32)
            rows = table[ids] - target[ids]
            loss = jnp.mean(jnp.square(rows))
            inner = {k: v for k, v in opt_state.items() if k != "probe"}
            table, inner = step_fn(table, inner, ids, rows)
            if probe is not None:
                # shadow update sees the same GLOBAL (ids, rows) batch
                # the kernels consume (jit level — outside the shard_map)
                inner = dict(inner,
                             probe=probe.update(opt_state["probe"],
                                                ids, rows))
            gn = jnp.sqrt(jnp.sum(jnp.square(rows)))
            return table, inner, {"loss": loss, "grad_norm": gn}

        jit_step = jax.jit(train_step,
                           in_shardings=(table_spec, opt_spec, bspec,
                                         table_spec),
                           out_shardings=(table_spec, opt_spec, mspec),
                           donate_argnums=(0, 1))
        tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every,
                             log_every=args.log_every)
        state = TrainState(step=0, params=table, opt_state=opt_state)
        trainer = Trainer(None, data, tcfg, observer=observer,
                          store_tree=run_tree, cleaner=cleaner)
        state = trainer.restore_or_init(
            state, shardings=({"params": table_spec, "opt_state": opt_spec}
                              if shards > 1 else None))
        state = dataclasses.replace(
            state, params=jax.device_put(state.params, table_spec),
            opt_state=jax.device_put(state.opt_state, opt_spec))
        target = jax.device_put(target, table_spec)
        compiled, report = compile_step(
            jit_step, state.params, state.opt_state,
            jax.tree_util.tree_map(np.asarray, data.batch(state.step)),
            target)
        trainer.step_fn = lambda table, opt_state, batch: compiled(
            table, opt_state, batch, target)
        with maybe_trace(args.profile_dir):
            state = trainer.fit(state)

    hist = trainer.history
    # history covers only the steps run in THIS process; a resumed run
    # may hold fewer than 10 records, so clamp to disjoint half-windows
    # (overlapping windows compare a window against itself and can
    # never satisfy last < first).
    w = min(10, max(1, len(hist) // 2))
    first = np.mean([h["loss"] for h in hist[:w]])
    last = np.mean([h["loss"] for h in hist[-w:]])
    print(f"[train] workload=sparse_embedding rows={n_rows} dim={dim} "
          f"dp={bool(args.dp)} shards={shards}({layout}) "
          f"feedback={bool(args.error_feedback)} "
          f"steps={state.step} loss {first:.4f} -> {last:.4f}")
    return dataclasses.replace(report, rc=0 if loss_fell(hist, w) else 1,
                               history=hist)


def run_serve_replay(args, mesh) -> int:
    """The online-adaptation serving workload (DESIGN.md §16): replay a
    fixed-seed zipf traffic trace through the full serving subsystem —
    bounded admission, size-or-deadline batching with cross-request
    dedup, double-buffered (table, sketch) state — and emit a
    schema-valid ``serve`` record.  ``--optimizer dense_adam`` runs the
    dense-baseline arm; anything else runs the count-min arm sized by
    ``--sparse-compression`` (backend via ``--store-backend``)."""
    del mesh  # single-host workload; the server owns its own device state
    from repro.core.optimizers import SketchHParams
    from repro.serve import (AdaptServer, ServerConfig, TraceConfig,
                             make_dense_adapt_step, make_online_adapt_step,
                             make_trace, replay, trace_stats)

    n_rows, dim = args.sparse_rows, args.sparse_dim
    tcfg = TraceConfig(n_requests=args.serve_requests, n_rows=n_rows,
                       dim=dim, ids_per_request=args.serve_ids_per_request,
                       offered_load=args.offered_load, seed=args.seed)
    trace = make_trace(tcfg)

    arm = "dense" if args.optimizer == "dense_adam" else "countmin"
    if arm == "dense":
        init_fn, adapt_fn = make_dense_adapt_step(n_rows, dim, lr=args.lr)
    else:
        init_fn, adapt_fn = make_online_adapt_step(
            n_rows, dim, lr=args.lr,
            hparams=SketchHParams(compression=args.sparse_compression),
            store_backend=args.store_backend or None)

    table = jax.random.normal(jax.random.PRNGKey(args.seed),
                              (n_rows, dim)) * 0.1
    server = AdaptServer(table, init_fn(), adapt_fn, ServerConfig(
        batch_ids=args.serve_batch_ids,
        max_delay_s=args.serve_deadline_ms / 1e3,
        queue_cap=args.queue_cap, slo_p99_ms=args.serve_slo_ms))
    replay(server, trace)

    rec = server.metrics_record(offered_load=args.offered_load)
    if args.metrics_dir:
        with MetricsWriter(args.metrics_dir, run_meta={
                "workload": "serve-replay", "arm": arm, "rows": n_rows,
                "dim": dim, "compression": args.sparse_compression,
                "requests": args.serve_requests,
                "offered_load": args.offered_load}) as w:
            w.write("serve", **rec, **{f"trace_{k}": v
                                       for k, v in trace_stats(trace).items()})
    h = rec["adapt_ms"]
    print(f"[serve] arm={arm} rows={n_rows} dim={dim} "
          f"load={args.offered_load:.0f}/s requests={server.n_submitted} "
          f"batches={server.n_batches} shed={server.shed_rate:.3f} "
          f"adapt p50 {h['p50_ms']:.2f} ms p99 {h['p99_ms']:.2f} ms "
          f"adapts/s {rec['reads_per_s']:.1f}")
    return RunReport(rc=0 if server.n_done > 0 else 1)


class _MetaStream:
    """Host-side MACH mapping for one replica: the extreme stream's
    true-label ids → this replica's meta-class ids (``cmap``), applied to
    labels AND sampled-softmax negatives before the batch reaches jit."""

    def __init__(self, stream, cmap):
        self.stream = stream
        self.cmap = cmap

    def batch(self, step):
        b = self.stream.batch(step)
        return {"features": b["features"],
                "labels": self.cmap[b["labels"]].astype(np.int32),
                "negatives": self.cmap[b["negatives"]].astype(np.int32)}


def run_extreme(args, mesh) -> int:
    """The MACH + sampled-softmax workload (paper §7.3 at table scale):
    ``--replicas`` independent meta-classifiers over an ``--meta-rows``
    output table, gradients as (ids, rows) through the dedup pre-pass,
    sketch sizing solved by the planner from ``--aux-budget`` and the DP
    sparse step moving (depth, width, dim) sketches under ``--dp``."""
    from repro.core.optimizers import SketchHParams
    from repro.data import ExtremeStream
    from repro.train.extreme import (MachConfig, make_extreme_step,
                                     plan_extreme)

    cfg = MachConfig(n_classes=args.classes, n_meta=args.meta_rows,
                     n_features=args.features, dim=args.extreme_dim,
                     n_replicas=args.replicas, nnz=args.nnz,
                     n_negatives=args.negatives, seed=args.seed)
    plan = None
    if args.aux_budget:
        plan = plan_extreme(cfg, args.aux_budget, optimizer=args.optimizer,
                            backend=args.store_backend or None,
                            sketch_dtype=args.sketch_cell_dtype)
        print(plan.table(), flush=True)
    hp = SketchHParams(compression=args.sparse_compression,
                       backend=args.store_backend or None,
                       dtype=args.sketch_cell_dtype)
    dp_axis = "data" if args.dp else None
    init_fn, step_fn, opts = make_extreme_step(
        cfg, optimizer=args.optimizer, lr=args.lr, hparams=hp, plan=plan,
        backend=args.store_backend or None, dp_axis=dp_axis, mesh=mesh,
        error_feedback=args.error_feedback)

    def replica_monitors():
        """Per-table health monitors over the step's own bound stores —
        store stats + planner predicted error (``LeafPlan.predicted_error``
        when a plan solved the sizing, the raw error model otherwise).
        No shadow probe here: the extreme step owns its gradients inside
        jit; measured error telemetry lives on the sparse_embedding
        workload, which exposes (ids, rows) at the jit level."""
        if not args.metrics_dir:
            return []
        from repro.obs import TableMonitor, predicted_table_errors
        from repro.train.steps import sparse_embedding_stores as _stores
        mons = []
        for path, shape in cfg.table_shapes().items():
            if args.optimizer == "dense_adam":
                continue                  # dense baseline: nothing sketched
            m_store, v_store = _stores(
                shape[0], shape[1], hparams=hp,
                track_first_moment=(args.optimizer == "cs_adam"),
                path=path, stores=plan.store_tree() if plan else None)
            if plan is not None and plan.leaf(path) is not None:
                pred = {"v_pred_error": float(plan.leaf(path).predicted_error)}
            else:
                pred = predicted_table_errors(m_store, v_store, shape[0],
                                              alpha=cfg.alpha)
            mons.append(TableMonitor(
                path=path, m_store=m_store, v_store=v_store, predicted=pred,
                getter=lambda s, p=path: s[p]))
        return mons

    cmaps = cfg.class_maps()
    finals = []
    report = None
    with shd.active_mesh(mesh):
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        for r in range(cfg.n_replicas):
            data = _MetaStream(ExtremeStream(cfg.data_config(args.batch)),
                               cmaps[r])
            params = init_fn(jax.random.PRNGKey(args.seed + r))
            opt_state = {p: o.init() for p, o in opts.items()}
            ckpt = (os.path.join(args.ckpt_dir, f"replica{r}")
                    if args.ckpt_dir else None)
            tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=ckpt,
                                 ckpt_every=args.ckpt_every,
                                 log_every=args.log_every)
            observer = make_observer(args, {
                "workload": "extreme", "replica": r,
                "classes": cfg.n_classes, "meta_rows": cfg.n_meta,
                "optimizer": args.optimizer, "batch": args.batch,
                "dp": bool(args.dp)}, replica_monitors(),
                subdir=f"replica{r}")
            trainer = Trainer(None, data, tcfg, plan=plan,
                              observer=observer)
            state = trainer.restore_or_init(
                TrainState(step=0, params=params, opt_state=opt_state))
            if report is None:
                # one executable serves every replica (same shapes)
                compiled, report = compile_step(
                    jit_step, state.params, state.opt_state,
                    jax.tree_util.tree_map(np.asarray,
                                           data.batch(state.step)))
            trainer.step_fn = compiled
            with maybe_trace(args.profile_dir if r == 0 else None):
                state = trainer.fit(state)
            hist = trainer.history
            # disjoint head/tail windows even on short smoke runs
            w = max(1, min(10, len(hist) // 3))
            first = np.mean([h["loss"] for h in hist[:w]])
            last = np.mean([h["loss"] for h in hist[-w:]])
            finals.append((loss_fell(hist, w), first, last))
            print(f"[train] workload=extreme replica={r} "
                  f"steps={state.step} loss {first:.4f} -> {last:.4f}",
                  flush=True)
    print(f"[train] workload=extreme classes={cfg.n_classes:,} "
          f"meta_rows={cfg.n_meta:,} replicas={cfg.n_replicas} "
          f"optimizer={args.optimizer} dp={bool(args.dp)} "
          f"batch={args.batch} per-replica losses "
          f"{[round(float(l), 4) for _, _, l in finals]}")
    return dataclasses.replace(
        report, rc=0 if all(ok for ok, _, _ in finals) else 1,
        history=hist)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Command-line entry point: the exit code of ``run(argv)``."""
    return run(argv).rc


def run(argv: Optional[Sequence[str]] = None) -> RunReport:
    """Parse ``argv`` (default: ``sys.argv[1:]``), run the workload, and
    report what ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="cs_adam")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dp", action="store_true",
                    help="explicit shard_map data parallelism over a "
                         "'data' axis spanning every local device")
    ap.add_argument("--workload", default="lm",
                    choices=["lm", "sparse_embedding", "extreme",
                             "serve-replay"],
                    help="lm: full model train step; sparse_embedding: "
                         "the (ids, grad-rows) table regime (sketched "
                         "all-reduce under --dp); extreme: MACH + sampled "
                         "softmax over a --meta-rows output table "
                         "(paper §7.3 — the big-batch regime); "
                         "serve-replay: replay a zipf traffic trace through "
                         "the online-adaptation server (DESIGN.md §16)")
    ap.add_argument("--sparse-rows", type=int, default=65536)
    ap.add_argument("--sparse-dim", type=int, default=64)
    ap.add_argument("--sparse-compression", type=float, default=5.0)
    ap.add_argument("--sketch-cell-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="cell storage dtype of every sketch tensor "
                         "(DESIGN.md §18): bfloat16 halves sketch bytes, "
                         "int8 quarters them (per-block f32 scales ride "
                         "along); all low-precision writes go through "
                         "per-step stochastic rounding.  Recorded in the "
                         "checkpoint manifest; restore refuses a silent "
                         "dtype change")
    ap.add_argument("--cleaning-every", type=int, default=0,
                    help="sparse_embedding: decay the count-min sketch "
                         "every N steps (paper §4 cleaning); 0 = off")
    ap.add_argument("--cleaning-alpha", type=float, default=0.2,
                    help="cleaning decay factor (paper §4)")
    ap.add_argument("--cleaning-mode", default="sync",
                    choices=("sync", "async"),
                    help="sync: the decay runs inside the compiled step "
                         "(lax.cond at the boundary); async: an "
                         "AsyncCleaner dispatches it BETWEEN steps — "
                         "bit-identical numerics, cost off the step "
                         "phase's critical section (DESIGN.md §18)")
    ap.add_argument("--sketch-shards", type=int, default=1,
                    help="sparse_embedding: shard each (depth, width, dim) "
                         "sketch into this many width slabs over the "
                         "mesh's 'model' axis (DESIGN.md §17); composes "
                         "with --dp on a 2D (data × model) mesh.  The "
                         "step is bit-identical to the unsharded run "
                         "under dyadic betas")
    ap.add_argument("--shard-layout", default="width",
                    choices=("width", "hash"),
                    help="width: contiguous width slabs, placement-only "
                         "(elastic across shard counts); hash: two-level "
                         "owner hash keeps every id's depth rows on ONE "
                         "shard (one-shard routing per id, but the shard "
                         "count is baked into the state)")
    ap.add_argument("--serve-requests", type=int, default=256,
                    help="serve-replay: trace length (fixed --seed zipf)")
    ap.add_argument("--serve-ids-per-request", type=int, default=8)
    ap.add_argument("--serve-batch-ids", type=int, default=64,
                    help="serve-replay: id capacity of a coalesced batch")
    ap.add_argument("--serve-deadline-ms", type=float, default=2.0,
                    help="serve-replay: max time the batcher holds its "
                         "oldest request before dispatching a partial batch")
    ap.add_argument("--offered-load", type=float, default=500.0,
                    help="serve-replay: trace arrival rate, requests/s")
    ap.add_argument("--queue-cap", type=int, default=32,
                    help="serve-replay: admission-queue bound; arrivals "
                         "past it are shed, not delayed")
    ap.add_argument("--serve-slo-ms", type=float, default=250.0,
                    help="serve-replay: adapt-latency p99 SLO stamped into "
                         "the emitted serve record (obs.report warns on "
                         "violation)")
    ap.add_argument("--classes", type=int, default=1_000_000,
                    help="extreme: true-label space (MACH hashes it down "
                         "to --meta-rows per replica)")
    ap.add_argument("--meta-rows", type=int, default=131_072,
                    help="extreme: rows of each replica's meta output "
                         "table — the table the optimizer state covers")
    ap.add_argument("--replicas", type=int, default=2,
                    help="extreme: MACH meta-classifier count R")
    ap.add_argument("--features", type=int, default=65_536,
                    help="extreme: sparse feature vocabulary")
    ap.add_argument("--extreme-dim", type=int, default=64,
                    help="extreme: embedding width of both tables")
    ap.add_argument("--nnz", type=int, default=16,
                    help="extreme: active features per example")
    ap.add_argument("--negatives", type=int, default=1024,
                    help="extreme: shared sampled-softmax negatives")
    ap.add_argument("--error-feedback", action="store_true",
                    help="accumulate the 2nd-moment cross-replica term "
                         "in a residual sketch (MicroAdam-style)")
    ap.add_argument("--aux-budget", default="",
                    help="optimizer aux-memory budget: bytes | '8.6GB' | "
                         "'0.85x' of dense | 'floor' | 'config'; the solved "
                         "plan replaces the regex sketch policy and is "
                         "recorded in every checkpoint manifest")
    ap.add_argument("--metrics-dir", default="",
                    help="emit schema-versioned JSONL sketch-health "
                         "telemetry (repro.obs) into this directory: "
                         "step/table/phase records every --log-every "
                         "steps; render with `python -m repro.obs.report`")
    ap.add_argument("--probe-rows", type=int, default=0,
                    help="sparse_embedding: shadow-probe K rows (half hot, "
                         "half cold) with exact dense moments and report "
                         "the measured sketch estimation error against "
                         "the planner's prediction (needs --metrics-dir)")
    ap.add_argument("--profile-dir", default="",
                    help="dump a jax.profiler trace of the run (device "
                         "timeline + the obs.* phase annotations)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="steps between metric windows / telemetry "
                         "fetches (the only host-sync cadence obs adds)")
    ap.add_argument("--store-backend", default="",
                    help="kernel backend for the sketch hot paths: the "
                         "fused dense-path update_read AND the sparse-rows "
                         "step ('ref' | 'xla' | 'tiled' | 'interpret' | "
                         "'auto'; DESIGN.md §14).  Empty = composed "
                         "fallback on the dense path.  An execution knob "
                         "only — overrides whatever backend a recorded "
                         "plan/manifest carries without touching state "
                         "layout, so restores stay valid")
    args = ap.parse_args(argv)
    if args.probe_rows and not args.metrics_dir:
        ap.error("--probe-rows needs --metrics-dir (probe errors are "
                 "emitted as 'table' metrics records)")

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()
    enable_compile_cache()

    if args.sketch_cell_dtype == "int8" and (args.dp
                                             or args.sketch_shards > 1):
        ap.error("--sketch-cell-dtype int8 does not compose with --dp or "
                 "--sketch-shards: the per-(depth, block) absmax scales "
                 "need a whole-sketch view the sharded/collective paths "
                 "don't have (DESIGN.md §18) — use bfloat16 there")

    if args.sketch_shards > 1:
        if args.workload != "sparse_embedding":
            ap.error("--sketch-shards applies to the sparse_embedding "
                     "workload only (the sharded sparse-rows step, "
                     "DESIGN.md §17)")
        if jax.device_count() % args.sketch_shards != 0:
            raise ValueError(
                f"--sketch-shards {args.sketch_shards} needs the device "
                f"count ({jax.device_count()}) divisible by it — each "
                f"shard owns one (depth, local_width, dim) slab")
        dp_size = (jax.device_count() // args.sketch_shards
                   if args.dp else 1)
        mesh = make_host_mesh(data=dp_size, model=args.sketch_shards)
        if args.dp and args.batch % dp_size != 0:
            raise ValueError(
                f"--dp needs the global batch ({args.batch}) divisible by "
                f"the data-axis size ({dp_size})")
    else:
        mesh = (make_host_mesh(data=jax.device_count()) if args.dp
                else make_host_mesh())
        if args.dp and args.batch % jax.device_count() != 0:
            raise ValueError(
                f"--dp needs the global batch ({args.batch}) divisible by "
                f"the device count ({jax.device_count()})")

    if args.workload == "serve-replay":
        # serve-time default is the paper's Theorem 5.1 RMSProp variant
        if args.optimizer == ap.get_default("optimizer"):
            args.optimizer = "cs_rmsprop"
        return run_serve_replay(args, mesh)
    if args.workload == "sparse_embedding":
        return run_sparse_embedding(args, mesh)
    if args.workload == "extreme":
        # the extreme optimizer default is the paper's Theorem 5.1 choice,
        # not the LM default — only override when the user didn't pick one
        if args.optimizer == ap.get_default("optimizer"):
            args.optimizer = "cs_rmsprop"
        return run_extreme(args, mesh)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ckpt_plan = None
    if args.ckpt_dir and store.latest_step(args.ckpt_dir) is not None:
        saved = store.read_manifest(args.ckpt_dir).get("extra", {})
        if saved.get("plan") is not None:
            from repro.plan import Plan
            ckpt_plan = Plan.from_json(saved["plan"])
            if saved.get("store_tree") is not None:
                # The manifest's executable vocabulary: the StoreTree the
                # sketch state was actually written under.  It must agree
                # with the plan it rode in with (guards manifest skew).
                from repro.core.stores import StoreTree
                recorded = StoreTree.from_json(saved["store_tree"])
                if recorded != ckpt_plan.store_tree():
                    raise ValueError(
                        f"{args.ckpt_dir}'s manifest is inconsistent: its "
                        f"serialized StoreTree does not match the plan it "
                        f"was recorded with — refusing to restore sketch "
                        f"state under ambiguous specs")
    plan = None
    if args.aux_budget:
        from repro.plan import plan_for_config
        plan = plan_for_config(cfg, args.aux_budget,
                               optimizer=args.optimizer,
                               sketch_dtype=args.sketch_cell_dtype)
        if (ckpt_plan is None
                and args.ckpt_dir
                and store.latest_step(args.ckpt_dir) is not None):
            raise ValueError(
                f"{args.ckpt_dir} holds a checkpoint written WITHOUT a "
                f"memory plan (regex-policy state); restoring it under "
                f"--aux-budget {args.aux_budget} would load mismatched "
                f"optimizer state — resume without the flag, or start a "
                f"fresh --ckpt-dir")
        if ckpt_plan is not None and \
                plan.with_backend(None) != ckpt_plan.with_backend(None):
            # The checkpointed sketch arrays were written under the
            # recorded plan's (width, seed) specs; querying them through
            # a differently-solved plan would misread state silently.
            # (The kernel backend is normalized out: it is an execution
            # knob, not state layout — DESIGN.md §14.)
            raise ValueError(
                f"--aux-budget {args.aux_budget} solves a plan that "
                f"differs from the one recorded in {args.ckpt_dir}'s "
                f"manifest ({ckpt_plan.budget_bytes:,} B budget) — resume "
                f"without --aux-budget to reuse the recorded plan, or "
                f"point --ckpt-dir at a fresh run")
        if ckpt_plan is not None and plan.backend is None:
            # keep the recorded execution backend when re-solving the
            # same budget (resuming WITH the flag must not silently
            # drop fused execution the run was launched with)
            plan = plan.with_backend(ckpt_plan.backend)
        print(plan.table(), flush=True)
    elif ckpt_plan is not None:
        # Resuming a planned run without --aux-budget: the optimizer MUST
        # be rebuilt from the manifest's plan, or the restored sketch
        # state would be queried with mismatched (width, seed) specs.
        plan = ckpt_plan
        print("[plan] recovered from checkpoint manifest "
              f"({plan.budget_bytes:,} B budget)", flush=True)
    if args.store_backend and plan is not None:
        # applied AFTER the consistency checks: same state layout, only
        # the fused-vs-composed execution of update_read changes
        plan = plan.with_backend(args.store_backend)
        print(f"[plan] store backend -> {args.store_backend}", flush=True)
    elif plan is not None and plan.backend == "tiled" \
            and jax.default_backend() != "tpu":
        # a recorded 'tiled' backend is a TPU execution knob: the kernel
        # compiles for a TPU only (state layout is backend-independent)
        raise ValueError(
            f"{args.ckpt_dir}'s plan records store backend 'tiled', which "
            f"compiles for a TPU only; this host is "
            f"{jax.default_backend()} — resume with --store-backend auto "
            f"(or xla)")
    # a sparse-embedding LM (``cfg.sparse_embedding``, e.g. the
    # moonlight-16b-a3b-ep8 share) takes no global-norm clip: its
    # embedding gradient leaves the tree as rows
    ts = make_train_step(cfg, optimizer=args.optimizer, lr=args.lr,
                         plan=plan, dp_axis="data" if args.dp else None,
                         kernel_backend=args.store_backend or None,
                         grad_clip=None if cfg.sparse_embedding else 1.0)

    with shd.active_mesh(mesh):
        import jax.numpy as jnp
        params = ts.init_fn(jax.random.PRNGKey(args.seed))
        opt_state = ts.optimizer.init(params)

        data = ZipfLM(ZipfLMConfig(
            vocab_size=cfg.vocab, seq_len=args.seq,
            global_batch=args.batch, seed=args.seed,
            n_hosts=jax.process_count(), host_id=jax.process_index()))

        # derive the full in/out shardings (params per the rule table,
        # optimizer state ZeRO-1 / sketch layout, batch over 'data') and
        # thread them through jit AND checkpoint restore — the same trees
        # launch/dryrun.py lowers against.
        sample = data.batch(0)
        batch_tpl = {k: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                             jnp.asarray(v).dtype)
                     for k, v in sample.items()}
        if cfg.family == "encdec":
            batch_tpl["frames"] = jax.ShapeDtypeStruct(
                (args.batch, cfg.enc_seq, cfg.d_model), cfg.dtype)
        if cfg.family == "vlm":
            batch_tpl["patches"] = jax.ShapeDtypeStruct(
                (args.batch, cfg.n_patches, cfg.d_model), cfg.dtype)
        step_fn, (pshard, oshard) = jit_train_step(ts, mesh, batch_tpl)
        tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every,
                             log_every=args.log_every)

        def wrapped_batch(batch):
            if cfg.family == "encdec":
                batch = dict(batch, frames=jax.numpy.zeros(
                    (args.batch, cfg.enc_seq, cfg.d_model), cfg.dtype))
            if cfg.family == "vlm":
                batch = dict(batch, patches=jax.numpy.zeros(
                    (args.batch, cfg.n_patches, cfg.d_model), cfg.dtype))
            return batch

        def wrapped_step(params, opt_state, batch):
            return step_fn(params, opt_state, wrapped_batch(batch))

        observer = make_observer(args, {
            "workload": "lm", "arch": cfg.name, "optimizer": args.optimizer,
            "steps": args.steps, "batch": args.batch, "dp": bool(args.dp),
            "aux_budget": args.aux_budget or None})
        trainer = Trainer(wrapped_step, data, tcfg, plan=plan,
                          observer=observer)
        state = trainer.restore_or_init(
            TrainState(step=0, params=params, opt_state=opt_state),
            shardings={"params": pshard, "opt_state": oshard})
        state = dataclasses.replace(
            state, params=jax.device_put(state.params, pshard),
            opt_state=jax.device_put(state.opt_state, oshard))
        step_fn, report = compile_step(
            step_fn, state.params, state.opt_state,
            wrapped_batch(jax.tree_util.tree_map(np.asarray,
                                                 data.batch(state.step))))
        with maybe_trace(args.profile_dir):
            state = trainer.fit(state)

    hist = trainer.history
    # disjoint head/tail windows on short runs too
    w = min(10, max(1, len(hist) // 2))
    first = np.mean([h["loss"] for h in hist[:w]])
    last = np.mean([h["loss"] for h in hist[-w:]])
    print(f"[train] arch={cfg.name} optimizer={args.optimizer} "
          f"dp={bool(args.dp)} steps={state.step} "
          f"loss {first:.3f} -> {last:.3f}")
    return dataclasses.replace(report, rc=0 if loss_fell(hist, w) else 1,
                               history=hist)


if __name__ == "__main__":
    raise SystemExit(main())
